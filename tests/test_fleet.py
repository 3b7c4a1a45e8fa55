"""Fleet serving plane (ray_lightning_tpu/serve/fleet/): paged-KV
prefix reuse, router policy, fleet-wide quotas, failover, and
signal-driven autoscaling.

Three tiers:

- host-only: PagePool/PrefixIndex/FleetConfig units and the paged
  Scheduler driven against fabricated fleet results (no jax work);
- engine-level: prefix reuse through the REAL copy/suffix programs with
  the token-parity-vs-cold-prefill bar (reused pages asserted > 0);
- router-level: a FleetServer over in-process fake replicas (real
  Scheduler + real routing/failover/autoscale machinery, fabricated
  step results) — deterministic and fast — plus the real-fleet e2e on
  the local backend (marked slow).
"""

import threading
import time

import numpy as np
import pytest

from ray_lightning_tpu.serve.fleet.config import FleetConfig
from ray_lightning_tpu.serve.fleet.pages import (
    PageConfig,
    PagedKV,
    PagePool,
    PrefixIndex,
)
from ray_lightning_tpu.serve.fleet.router import (
    FleetReplicaLost,
    FleetServer,
    pick_replica,
)
from ray_lightning_tpu.serve.scheduler import Scheduler


def test_pick_replica_least_loaded_sticky_slack():
    rows = [{"rid": 0, "active": 2, "queued": 1, "slots": 4},
            {"rid": 1, "active": 1, "queued": 0, "slots": 4},
            {"rid": 2, "active": 1, "queued": 1, "slots": 4}]
    assert pick_replica(rows) == 1                       # least loaded
    assert pick_replica(rows, sticky_rid=2) == 2         # within slack
    assert pick_replica(rows, sticky_rid=0,
                        sticky_slack=0) == 1             # past slack
    assert pick_replica([]) is None

def test_pick_replica_pool_routing():
    rows = [{"rid": 0, "active": 2, "queued": 0, "slots": 4,
             "role": "prefill"},
            {"rid": 1, "active": 0, "queued": 0, "slots": 4,
             "role": "decode"},
            {"rid": 2, "active": 1, "queued": 0, "slots": 4,
             "role": "prefill"}]
    assert pick_replica(rows, pool="prefill") == 2   # least loaded in pool
    assert pick_replica(rows, pool="decode") == 1
    # a pool that emptied (shrink/failover) degrades to pooled routing
    assert pick_replica([rows[0], rows[2]], pool="decode") == 2
    # bare rows carry no role: the filter matches nothing, falls back
    bare = [{"rid": 5, "active": 0, "queued": 0, "slots": 2}]
    assert pick_replica(bare, pool="prefill") == 5


def test_fleet_roles_config_validation_and_env_roundtrip(monkeypatch):
    cfg = FleetConfig(roles=("prefill", "decode"), kvship_codec="int8")
    for k, v in cfg.worker_env().items():
        monkeypatch.setenv(k, v)
    assert FleetConfig.resolve(None) == cfg
    assert [cfg.role_for(i) for i in range(4)] == \
        ["prefill", "decode", "prefill", "decode"]
    assert FleetConfig().role_for(3) == "pooled"     # no roles: pooled
    with pytest.raises(ValueError, match="role"):
        FleetConfig(roles=("prefill", "verify"))
    with pytest.raises(ValueError, match="kvship_codec"):
        FleetConfig(kvship_codec="zstd")


PAGED = PageConfig(enabled=True, page_size=8)


# -- pages: pool + index ---------------------------------------------------

def test_page_pool_accounting():
    pool = PagePool(slots=4, max_seq_len=32, page_size=8)
    assert pool.total_pages == 16
    pool.note_written(0, 1)
    pool.note_written(0, 17)                 # 3 pages, high-water
    assert pool.held(0) == 3 and pool.free == 13
    assert pool.shrink_to(0, 16) == 1        # donor keeps 2 prefix pages
    pool.check()
    assert pool.release(0) == 2 and pool.free == 16
    pool.check()
    with pytest.raises(ValueError):
        PagePool(slots=2, max_seq_len=8, page_size=16)


def test_prefix_index_longest_match_and_verification():
    idx = PrefixIndex(page_size=4)
    tokens = np.arange(50, 68, dtype=np.int32)      # 18 tokens
    assert idx.register(1, tokens, limit=31) == 16  # 4 whole pages
    # longest page-aligned match wins; exact tokens verified
    probe = np.concatenate([tokens[:12], [1, 2, 3, 4]])
    assert idx.lookup(probe) == (1, 12)
    assert idx.lookup(tokens[:3]) is None           # under a page
    diverged = tokens.copy()
    diverged[0] = 9
    assert idx.lookup(diverged) is None
    idx.drop(1)
    assert idx.lookup(tokens) is None


def test_paged_kv_retention_and_lru_eviction():
    kv = PagedKV(PageConfig(enabled=True, page_size=4), slots=2,
                 max_seq_len=16)
    a = np.arange(1, 9)
    kv.on_admit(0, a, computed=len(a))
    assert kv.retain(0) is True and kv.donor_count == 1
    b = np.arange(21, 29)
    kv.on_admit(1, b, computed=len(b))
    assert kv.retain(1) is True and kv.donor_count == 2
    # a lookup refreshes donor 0's LRU stamp, so 1 is evicted first
    assert kv.match(np.concatenate([a, [3, 3]])) == (0, 8)
    assert kv.evict_lru_donor() == 1
    kv.pool.check()
    assert kv.match(np.concatenate([b, [3]])) is None


def test_fleet_and_page_config_env_roundtrip(monkeypatch):
    cfg = FleetConfig(min_replicas=2, max_replicas=4,
                      grow_queue_depth=1.5, grow_ttft_p99_ms=100.0,
                      cooldown_s=3.0, tick_interval_s=0.2)
    for k, v in cfg.worker_env().items():
        monkeypatch.setenv(k, v)
    assert FleetConfig.resolve(None) == cfg
    pc = PageConfig(enabled=True, page_size=64)
    for k, v in pc.worker_env().items():
        monkeypatch.setenv(k, v)
    assert PageConfig.resolve(None) == pc
    monkeypatch.delenv("RLT_SERVE_PAGED")
    monkeypatch.delenv("RLT_SERVE_PAGE_SIZE")
    assert PageConfig.resolve(None) == PageConfig(enabled=False)
    # sugar forms
    assert PageConfig.resolve(True).enabled
    assert PageConfig.resolve(32).page_size == 32
    assert not PageConfig.resolve(False).enabled


# -- paged scheduler against a fabricated fleet ----------------------------

def _fake_step(sched):
    plan = sched.plan()
    if plan is None:
        return None
    result = {"prefill": {p["slot"]: 7 for p in plan["prefills"]},
              "decode": {}}
    if plan["decode"] is not None:
        result["decode"] = {s: 9 for s in plan["decode"]["slots"]}
    sched.apply(plan, result)
    return plan


def test_paged_scheduler_emits_reuse_and_retains_donors():
    sched = Scheduler(buckets=(16, 32), slots=2, max_seq_len=32,
                      max_prefills_per_step=1,
                      default_max_new_tokens=2, paged=PAGED)
    shared = np.arange(1, 17)                  # 2 whole pages
    r1 = sched.submit(np.concatenate([shared, [40]]))
    plans = [p for p in iter(lambda: _fake_step(sched), None)]
    assert r1.done() and all("reuse" not in p
                             for plan in plans
                             for p in plan["prefills"])
    assert sched.pages.donor_count == 1        # retained after finish
    # a later request with the same system prompt reuses the donor
    r2 = sched.submit(np.concatenate([shared, [50, 51]]))
    plan = sched.plan()
    entry = plan["prefills"][0]
    assert entry["reuse"]["matched"] == 16
    st = sched.pages.stats()
    assert st["prefill_tokens_requested"] > st["prefill_tokens_computed"]
    assert st["prefix_reuse_ratio"] > 0
    # idle-slot dummy decode writes aim at the LAST row under paging
    result = {"prefill": {entry["slot"]: 7}, "decode": {}}
    sched.apply(plan, result)
    plan2 = sched.plan()
    assert plan2["decode"] is not None
    dummies = [s for s in range(2) if s not in plan2["decode"]["slots"]]
    for s in dummies:
        assert plan2["decode"]["positions"][s] == 31
    sched.apply(plan2, {"prefill": {},
                        "decode": {s: 9 for s
                                   in plan2["decode"]["slots"]}})
    while not sched.idle():
        _fake_step(sched)
    assert r2.done()
    sched.pages.pool.check()


def test_paged_scheduler_evicts_donors_under_slot_pressure():
    sched = Scheduler(buckets=(16,), slots=2, max_seq_len=32,
                      max_prefills_per_step=2,
                      default_max_new_tokens=2, paged=PAGED)
    for i in range(2):
        sched.submit(np.arange(1, 10) + 20 * i)
    while not sched.idle():
        _fake_step(sched)
    assert sched.pages.donor_count == 2        # both slots retained
    assert sched.allocator.free_count == 0
    # new admissions must evict donors for slots — and succeed
    r = sched.submit(np.arange(100, 110))
    while not sched.idle():
        _fake_step(sched)
    assert r.done()
    sched.pages.pool.check()


def test_withdraw_queued_leaves_active_untouched():
    sched = Scheduler(buckets=(8,), slots=1, max_seq_len=16,
                      default_max_new_tokens=4)
    active = sched.submit([1, 2, 3])
    queued = [sched.submit([4, 5]) for _ in range(3)]
    _fake_step(sched)                          # admit the first
    out = sched.withdraw_queued()
    assert [r.id for r in out] == [r.id for r in queued]
    assert all(r.state == "withdrawn" and not r.done() for r in out)
    assert sched.queued_count == 0 and sched.active_count == 1
    while not sched.idle():
        _fake_step(sched)
    assert active.done()


# -- fake replicas: the router harness -------------------------------------

class _FakeServer:
    """Server-surface double: the REAL Scheduler under the router, with
    fabricated step results instead of an engine.  ``auto=False`` gives
    the test manual control over admission timing (failover tests need
    requests pinned in the queued-but-unprefilled state)."""

    def __init__(self, slots=2, step_delay=0.0, auto=True, paged=None):
        self.scheduler = Scheduler(buckets=(32,), slots=slots,
                                   max_seq_len=64,
                                   max_prefills_per_step=slots,
                                   default_max_new_tokens=3,
                                   paged=paged)
        self.max_batch_slots = slots
        self.step_delay = step_delay
        self.auto = auto
        self._error = None
        self.failure_report = None
        self.started = False
        self.shut_down = False
        self._stop = threading.Event()
        self._thread = None

    def start(self):
        self.started = True
        if self.auto:
            self._thread = threading.Thread(target=self._pump,
                                            daemon=True)
            self._thread.start()
        return self

    def _pump(self):
        while not self._stop.is_set():
            if self._error is not None:
                return
            if self.step() is None:
                time.sleep(0.002)
            elif self.step_delay:
                time.sleep(self.step_delay)

    def step(self):
        plan = self.scheduler.plan()
        if plan is None:
            return None
        result = {"prefill": {p["slot"]: 7 for p in plan["prefills"]},
                  "decode": {}}
        if plan["decode"] is not None:
            result["decode"] = {s: 9 for s
                                in plan["decode"]["slots"]}
        self.scheduler.apply(plan, result)
        return plan

    def submit(self, prompt, tenant="default", max_new_tokens=None):
        if self._error is not None:
            raise RuntimeError("replica failed") from self._error
        return self.scheduler.submit(prompt, tenant=tenant,
                                     max_new_tokens=max_new_tokens)

    # -- KV-ship surface (federation pulls need both ends) -------------

    def can_ship_kv(self):
        return self.started and self.scheduler.pages is not None

    def can_adopt_kv(self):
        sched = self.scheduler
        if sched.pages is None:
            return False
        with sched._lock:
            return (sched.allocator.free_count > 0
                    or sched.pages.donor_count > 0)

    def export_kv(self, prompt_tokens, req_id=None):
        """Server.export_kv double: same match+pin-under-lock donor
        lookup, fabricated non-zero rows (arange, never zeros — an fp8
        quantize of all-zeros would divide by a zero scale)."""
        sched = self.scheduler
        if sched.pages is None or not self.started:
            return None
        if req_id is not None:
            boxed = sched.pop_kv_export(int(req_id))
            if boxed is not None:
                return boxed
        prompt_tokens = np.asarray(prompt_tokens,
                                   dtype=np.int32).reshape(-1)
        with sched._lock:
            hit = sched.pages.match(prompt_tokens)
            if hit is None:
                return None
            _, matched = hit
        rows = (np.arange(2 * int(matched) * 4, dtype=np.float32)
                .reshape(2, int(matched), 4) + 1.0)
        return rows, rows.copy(), int(matched)

    def import_kv(self, prompt_tokens, k_rows, v_rows):
        prompt_tokens = np.asarray(prompt_tokens,
                                   dtype=np.int32).reshape(-1)
        slot = self.scheduler.adopt_imported(prompt_tokens)
        if slot is None:
            return False
        self.scheduler.adopt_commit(slot, prompt_tokens)
        return True

    def die(self, error):
        """Simulate a mid-serve fleet failure: the pump's failure path
        (flight dumps + fail_all)."""
        self._error = error
        self.failure_report = {
            "cause": repr(error),
            "flight_paths": {0: "/tmp/flight_0.json"}}
        self.scheduler.fail_all(error)

    def goodput(self):
        """Synthetic finalized serve partition (telemetry/goodput.py)
        so router-level tests exercise fleet goodput aggregation —
        including the retired-replica fold — without an engine."""
        from ray_lightning_tpu.telemetry.goodput import GoodputLedger
        led = GoodputLedger("serve")
        led.note_step(1.0, k=4)
        led.add("prefill", 0.25)
        return led.finalize(2.0)

    def drain(self, timeout=None):
        deadline = time.monotonic() + (timeout or 10)
        while not self.scheduler.idle():
            if time.monotonic() > deadline:
                raise TimeoutError
            time.sleep(0.002)

    def shutdown(self, graceful=True):
        self._stop.set()
        if self._thread is not None:
            self._thread.join(5)
        self.shut_down = True


def _mk_fleet(n=2, factory=None, paged=False, autoscale=False,
              fleet=None, **kw):
    return FleetServer(
        object(), replicas=n, autoscale=autoscale, fleet=fleet,
        paged=paged, telemetry=False,
        replica_factory=factory or (lambda rid: _FakeServer()), **kw)


def _wait(predicate, timeout=10.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while not predicate():
        assert time.monotonic() < deadline, f"timed out on: {msg}"
        time.sleep(0.005)


def test_router_routes_and_completes_mixed_load():
    fleet = _mk_fleet(2).start()
    try:
        reqs = [fleet.submit(np.arange(1, 6), tenant=t)
                for t in ("a", "b", "a", "b", "a", "b", "c", "c")]
        outs = [r.result(timeout=10) for r in reqs]
        assert all(len(o) == 3 for o in outs)
        assert fleet.completed == 8 and fleet.failed == 0
        # stickiness recorded per tenant, and every tenant has a home
        with fleet._lock:
            assert set(fleet._sticky) == {"a", "b", "c"}
        # both replicas exist and served without failovers
        assert not fleet.failovers
        sig = fleet.signals()
        assert sig["replicas"] == 2 and sig["queued"] == 0
    finally:
        fleet.shutdown()


def test_router_fleet_wide_quota_holds_under_load():
    """A quota-1 tenant never holds more than one in-flight slot ACROSS
    replicas, while an unquoted tenant proceeds unimpeded (no
    head-of-line blocking)."""
    fleet = _mk_fleet(
        2, factory=lambda rid: _FakeServer(step_delay=0.01),
        tenant_quotas={"greedy": 1}).start()
    try:
        reqs = [fleet.submit(np.arange(1, 4), tenant="greedy")
                for _ in range(5)]
        quiet = [fleet.submit(np.arange(1, 4), tenant="quiet")
                 for _ in range(3)]
        peak = 0
        deadline = time.monotonic() + 10
        while not all(r.done() for r in reqs + quiet):
            assert time.monotonic() < deadline
            with fleet._lock:
                greedy_inflight = sum(
                    1 for fr in fleet._inflight.values()
                    if fr.tenant == "greedy")
            peak = max(peak, greedy_inflight)
            assert greedy_inflight <= 1, "fleet-wide quota violated"
            time.sleep(0.001)
        assert peak == 1            # the quota actually bound
        assert fleet.completed == 8
    finally:
        fleet.shutdown()


def test_router_failover_requeues_queued_fails_inflight():
    """The dying replica's queued-but-unprefilled requests complete on
    the survivor; its admitted in-flight request fails with the cause
    and the flight-recorder links."""
    servers = {}

    def factory(rid):
        servers[rid] = _FakeServer(slots=1, auto=False)
        return servers[rid]

    fleet = _mk_fleet(2, factory=factory,
                      fleet={"sticky_slack": 5}).start()
    try:
        # pin every dispatch onto replica 0 via stickiness (both idle,
        # rid 0 wins first; a wide sticky_slack keeps the tenant there
        # even as its queue grows)
        reqs = [fleet.submit(np.arange(1, 5), tenant="t")
                for _ in range(3)]
        _wait(lambda: all(r.inner is not None for r in reqs),
              msg="dispatch")
        assert {r.replica for r in reqs} == {0}
        servers[0].step()           # admit exactly one (slots=1)
        admitted = [r for r in reqs if r.inner.t_admit is not None]
        queued = [r for r in reqs if r.inner.t_admit is None]
        assert len(admitted) == 1 and len(queued) == 2
        servers[0].die(RuntimeError("chaos: replica 0 lost"))
        # router: requeue the queued two onto replica 1, fail the
        # admitted one with the flight-linked error
        _wait(lambda: admitted[0].done(), msg="in-flight failed")
        with pytest.raises(FleetReplicaLost, match="flight"):
            admitted[0].result(1)
        assert admitted[0].error.flight_paths == {
            0: "/tmp/flight_0.json"}
        _wait(lambda: all(r.replica == 1 for r in queued),
              msg="requeue to survivor")
        while not all(r.done() for r in queued):
            servers[1].step()
            time.sleep(0.002)
        assert all(len(r.result(1)) == 3 for r in queued)
        assert fleet.failovers and fleet.failovers[0]["requeued"] == 2 \
            and fleet.failovers[0]["failed"] == 1
        assert fleet.failovers[0]["flight_paths"]
        # replacement grow back toward min_replicas
        _wait(lambda: len([r for r in fleet._replicas.values()
                           if r.state == "serving"]) >= 2,
              msg="failover replacement")
    finally:
        fleet.shutdown(graceful=False)


def test_autoscaler_grow_and_shrink_through_router():
    """Queue pressure grows the fleet 1→2; the idle tail shrinks it
    back; no request is lost and the drained replica's requests
    complete elsewhere."""
    fleet = _mk_fleet(
        1, factory=lambda rid: _FakeServer(slots=1, step_delay=0.02),
        autoscale=True,
        fleet={"min_replicas": 1, "max_replicas": 2,
               "grow_queue_depth": 1.0, "patience_ticks": 1,
               "cooldown_s": 0.05, "tick_interval_s": 0.02}).start()
    try:
        reqs = [fleet.submit(np.arange(1, 6)) for _ in range(10)]
        _wait(lambda: fleet.autoscaler.stats()["grows"] >= 1,
              msg="grow event")
        outs = [r.result(timeout=20) for r in reqs]
        assert all(len(o) == 3 for o in outs)
        _wait(lambda: fleet.autoscaler.stats()["shrinks"] >= 1,
              timeout=20, msg="shrink event")
        _wait(lambda: len(fleet._replicas) == 1, timeout=20,
              msg="replica reaped")
        st = fleet.autoscaler.stats()
        assert st["events"][0]["action"] == "grow"
        assert st["events"][0]["seconds"] is not None
        assert fleet.failed == 0 and fleet.completed == 10
        # late requests still served after the shrink
        assert len(fleet.generate(np.arange(1, 4), timeout=10)) == 3

        # fleet goodput (telemetry/goodput.py): the reaped replica's
        # finalized doc is preserved next to the survivor's live peek,
        # and the autoscaler's actuation seconds extend the wall as
        # their own bucket — the identity holds on the aggregate by
        # construction
        from ray_lightning_tpu.telemetry.goodput import check_identity
        gp = fleet.goodput_stats()
        assert gp["kind"] == "serve" and gp["ranks"] >= 2
        assert check_identity(gp), gp
        assert gp["buckets"]["decode"] == pytest.approx(1.0 * gp["ranks"])
        # actuation seconds land in their own bucket (fake replicas
        # actuate in sub-ms, so the rounded event sum may be 0.0 —
        # equality, not >0, is the contract here)
        actuation = sum(e["seconds"] or 0.0
                        for e in fleet.autoscaler.stats()["events"])
        assert gp["buckets"]["autoscale"] == pytest.approx(
            actuation, abs=1e-6)
        assert fleet.status()["fleet"]["goodput"]["ranks"] == gp["ranks"]
    finally:
        fleet.shutdown()


def test_fleet_drain_rejects_new_and_settles():
    fleet = _mk_fleet(1).start()
    try:
        reqs = [fleet.submit(np.arange(1, 4)) for _ in range(4)]
        fleet.drain(timeout=10)
        assert all(r.done() for r in reqs)
        with pytest.raises(RuntimeError, match="draining"):
            fleet.submit([1, 2])
    finally:
        fleet.shutdown()


# -- prefix federation: the fleet-wide directory + pull-driven kvship ------


def test_prefix_directory_lifecycle_and_liveness():
    """register → lookup → invalidate round-trip, exclusion, ttl
    expiry under an injected clock, and the size bound (re-registration
    replaces — the directory can never outgrow retained pages)."""
    from ray_lightning_tpu.serve.fleet.federation import PrefixDirectory

    clock = [0.0]
    d = PrefixDirectory(page_size=8, ttl_s=5.0, clock=lambda: clock[0])
    base = np.arange(1, 25, dtype=np.int32)
    assert d.register(0, 2, base[:17]) == 16       # whole pages only
    assert d.register(1, 0, base) == 24
    assert d.lookup(base) == (1, 0, 24)            # longest wins
    assert d.lookup(base, exclude_rid=1) == (0, 2, 16)
    assert d.lookup(np.arange(100, 107)) is None   # sub-page: miss
    # re-registration REPLACES the donor's entry
    d.register(1, 0, base[:8])
    assert d.entries() == 2 and d.pages() == 2 + 1
    assert d.lookup(base) == (0, 2, 16)
    d.invalidate(0, 2)
    assert d.lookup(base) == (1, 0, 8)
    d.invalidate_replica(1)
    assert d.lookup(base) is None and d.entries() == 0
    # liveness: a wedged replica's advertisement ages out
    d.register(3, 1, base[:8])
    clock[0] = 4.0
    assert d.lookup(base) == (3, 1, 8)
    clock[0] = 6.0
    assert d.lookup(base) is None
    assert d.entries() == 0, "expired entry not pruned"
    assert d.stats()["invalidations"] == 2


def test_pick_replica_prefix_affinity_within_slack():
    rows = [{"rid": 0, "active": 2, "queued": 0, "slots": 4},
            {"rid": 1, "active": 0, "queued": 2, "slots": 4},
            {"rid": 2, "active": 0, "queued": 0, "slots": 4}]
    # the replica measured to hold the prefix wins inside the slack,
    # over least-loaded AND over stickiness; longest prefix wins ties
    assert pick_replica(rows, sticky_slack=2, affinity={1: 16}) == 1
    assert pick_replica(rows, sticky_rid=2, sticky_slack=2,
                        affinity={1: 16}) == 1
    assert pick_replica(rows, sticky_slack=2,
                        affinity={1: 8, 2: 16}) == 2
    # past the slack the pages get FETCHED instead of routed-to
    assert pick_replica(rows, sticky_slack=1, affinity={0: 16}) == 2
    assert pick_replica(rows, sticky_slack=0, affinity={1: 16}) == 2


def _mk_fed_fleet(fleet_extra=None, **fake_kw):
    """Two fake paged replicas under a federation-enabled router with
    manual stepping (auto=False): tests control exactly when each
    replica admits and completes."""
    servers = {}

    def factory(rid):
        servers[rid] = _FakeServer(slots=2, auto=False, paged=PAGED,
                                   **fake_kw)
        return servers[rid]

    cfg = {"sticky_slack": 0, "prefix_fed": True}
    cfg.update(fleet_extra or {})
    fleet = _mk_fleet(2, factory=factory, paged=PAGED, fleet=cfg)
    return fleet, servers


def _run_to_done(server, fr, timeout=10.0):
    """Step one fake replica until the fleet request completes (the
    router's poll loop finishes it off-thread)."""
    deadline = time.monotonic() + timeout
    while not fr.done():
        assert time.monotonic() < deadline, "request never completed"
        server.step()
        time.sleep(0.005)


def _seed_donor(fleet, servers, prompt, tenant="alice"):
    """Complete one request on replica 0 so its pages retain as a
    donor and advertise to the fleet directory."""
    r = fleet.submit(prompt, tenant=tenant)
    _wait(lambda: servers[0].scheduler.queued_count
          + servers[0].scheduler.active_count > 0,
          msg="seed request admitted on replica 0")
    _run_to_done(servers[0], r)
    _wait(lambda: fleet.directory.entries() >= 1,
          msg="donor advertised to the directory")
    return r


def test_router_federated_fetch_installs_remote_prefix():
    """The tentpole path end-to-end at the router tier: a prefix
    prefilled on replica 0 is PULLED by replica 1 over the kvship
    plane on a directory hit — the admission computes only the suffix
    (federated_tokens_reused), the wire bytes land in the federation
    counters, and the fetch seconds land in the kv_fed goodput
    bucket, distinct from prefill."""
    fleet, servers = _mk_fed_fleet()
    fleet.start()
    try:
        shared = np.arange(1, 17)               # 2 whole pages
        _seed_donor(fleet, servers, shared)
        # occupy replica 0 so slack-0 routing sends the next request
        # to replica 1 (which holds nothing)
        filler = fleet.submit(np.arange(40, 52), tenant="carol")
        _wait(lambda: servers[0].scheduler.queued_count > 0,
              msg="filler queued on replica 0")
        servers[0].step()                        # admit, don't finish
        target = fleet.submit(np.concatenate([shared, [99]]),
                              tenant="bob")
        _wait(lambda: servers[1].scheduler.queued_count
              + servers[1].scheduler.active_count > 0,
              msg="target submitted on replica 1 after the fetch")
        _run_to_done(servers[1], target)
        assert list(target.result(0)) == [7, 9, 9]
        fed = fleet.federation
        assert fed["hits"] == 1 and fed["fetches"] == 1 \
            and fed["ships"] == 1, fed
        assert fed["bytes_wire"] > 0 \
            and fed["bytes_raw"] > fed["bytes_wire"], fed
        st1 = servers[1].scheduler.pages.stats()
        assert st1["remote_imports"] == 1, st1
        # prompt is 17 tokens, 16 arrived over the wire: only the
        # suffix token was computed locally
        assert st1["federated_tokens_reused"] == 16, st1
        pages = fleet.pages_stats()
        assert pages["federated_tokens_reused"] == 16 \
            and pages["federated_reuse_ratio"] > 0, pages
        doc = fleet.status()["fleet"]
        assert doc["federation"]["compression_ratio"] > 1, doc
        assert doc["federation"]["directory"]["entries"] >= 1
        gp = fleet.goodput_stats()
        assert gp["buckets"].get("kv_fed", 0) > 0, \
            "federated wire seconds must land in their own bucket"
        _run_to_done(servers[0], filler)
    finally:
        fleet.shutdown(graceful=False)


def test_router_federated_fetch_stale_donor_heals_and_prefills():
    """The lookup→fetch race (satellite 2): the donor evicts between
    the directory hit and the export — the fetch comes back empty,
    the stale entry is healed, and the request falls over to a LOCAL
    prefill with exact tokens (counted, never wedged)."""
    fleet, servers = _mk_fed_fleet()
    fleet.start()
    try:
        shared = np.arange(1, 17)
        _seed_donor(fleet, servers, shared)
        # evict the donor BEHIND the directory's back (hooks bypassed)
        # so the directory entry goes stale exactly like a donor dying
        # between lookup and fetch
        pages = servers[0].scheduler.pages
        with servers[0].scheduler._lock:
            slot = next(iter(pages._donors))
            pages._donors.pop(slot)
            pages.index.drop(slot)
        assert fleet.directory.entries() == 1    # stale on purpose
        filler = fleet.submit(np.arange(40, 52), tenant="carol")
        _wait(lambda: servers[0].scheduler.queued_count > 0,
              msg="filler queued")
        servers[0].step()
        target = fleet.submit(np.concatenate([shared, [99]]),
                              tenant="bob")
        _wait(lambda: servers[1].scheduler.queued_count
              + servers[1].scheduler.active_count > 0,
              msg="target fell over to local prefill on replica 1")
        _run_to_done(servers[1], target)
        assert list(target.result(0)) == [7, 9, 9]   # token-exact
        fed = fleet.federation
        assert fed["fetches"] == 1 and fed["ships"] == 0 \
            and fed["skipped"] >= 1, fed
        # the stale advertisement was healed by the failed fetch:
        # replica 0 no longer claims the prefix (entries() may be >0
        # again — the target's own completion re-advertises on r1)
        assert fleet.directory.stats()["invalidations"] == 1
        assert 0 not in fleet.directory.affinity(shared), \
            "stale entry must be healed by the failed fetch"
        st1 = servers[1].scheduler.pages.stats()
        assert st1["remote_imports"] == 0 \
            and st1["federated_tokens_reused"] == 0, st1
        _run_to_done(servers[0], filler)
    finally:
        fleet.shutdown(graceful=False)


def test_router_federated_fetch_chaos_peerdrop_failover(monkeypatch):
    """Chaos leg over the existing RLT_FAULT peerdrop machinery: a
    dropped federated pull exhausts its bounded retries
    (RLT_PEER_RETRIES), fails over to local prefill token-exactly,
    and does NOT invalidate the directory (the donor is alive — only
    the wire lost)."""
    monkeypatch.setenv("RLT_FAULT", "peerdrop:rank=0,step=1,count=1")
    monkeypatch.setenv("RLT_PEER_RETRIES", "2")
    monkeypatch.setenv("RLT_PEER_BACKOFF_S", "0.01")
    monkeypatch.setenv("RLT_KVSHIP_TIMEOUT_S", "0.05")
    fleet, servers = _mk_fed_fleet()
    assert fleet._kvship_drop == 1, \
        "RLT_FAULT peerdrop must arm the router's kvship chaos"
    fleet.start()
    try:
        shared = np.arange(1, 17)
        _seed_donor(fleet, servers, shared)
        filler = fleet.submit(np.arange(40, 52), tenant="carol")
        _wait(lambda: servers[0].scheduler.queued_count > 0,
              msg="filler queued")
        servers[0].step()
        target = fleet.submit(np.concatenate([shared, [99]]),
                              tenant="bob")
        _wait(lambda: servers[1].scheduler.queued_count
              + servers[1].scheduler.active_count > 0,
              msg="target fell over after the chaos drop")
        _run_to_done(servers[1], target)
        assert list(target.result(0)) == [7, 9, 9]
        fed = fleet.federation
        assert fed["retries"] == 2 and fed["failovers"] == 1 \
            and fed["ships"] == 0, fed
        # the donor is alive — only the wire lost: its advertisement
        # must survive for the next fetch
        assert fleet.directory.stats()["invalidations"] == 0
        assert fleet.directory.affinity(shared).get(0) == 16, \
            "a wire timeout must NOT invalidate a live donor"
        _run_to_done(servers[0], filler)
    finally:
        fleet.shutdown(graceful=False)


# -- engine tier: prefix reuse through the real copy/suffix programs -------

TINY = None


def _tiny():
    global TINY
    if TINY is None:
        from ray_lightning_tpu.models.gpt import GPTConfig
        TINY = GPTConfig(vocab_size=128, block_size=32, n_layer=2,
                         n_head=2, n_embd=32, remat=False)
    return TINY


@pytest.fixture(scope="module")
def paged_engine():
    from ray_lightning_tpu.models.gpt import GPTLightningModule
    from ray_lightning_tpu.parallel.strategy import DataParallelStrategy
    from ray_lightning_tpu.serve.engine import ServeEngine
    module = GPTLightningModule(_tiny())
    return ServeEngine(module, DataParallelStrategy(), buckets=(16, 32),
                       slots=4, max_seq_len=32, seed=0,
                       paged=PAGED).setup()


def _assert_greedy_parity(eng, prompt, got, atol=2e-2):
    """tests/test_serve.py's teacher-forced parity bar: every generated
    token is the whole-sequence reference argmax, or within the bf16
    near-tie tolerance of it — corrupted K/V fails hard."""
    import jax
    model = eng.module.configure_decode_model()
    params = jax.device_get(eng.params)
    seq = [int(t) for t in np.asarray(prompt)]
    for i, tok in enumerate(got):
        logits = np.asarray(model.apply(
            {"params": params}, np.asarray([seq], np.int32), True))[0, -1]
        best = int(np.argmax(logits))
        assert tok == best or logits[tok] >= logits[best] - atol, \
            (i, seq, tok, best, float(logits[tok]), float(logits[best]))
        seq.append(int(tok))


@pytest.mark.slow
def test_prefix_reuse_token_parity_vs_cold_prefill(paged_engine):
    """The acceptance bar for the paged path: requests admitted through
    a prefix-cache hit (page copy + suffix-only compute) generate
    token-for-token what the cold whole-sequence reference generates,
    and reused pages are asserted > 0 — while concurrent decodes,
    donor retention, and idle-slot dummy writes all churn the cache."""
    from ray_lightning_tpu.serve.worker import ServeWorker
    eng = paged_engine
    sched = Scheduler(buckets=(16, 32), slots=4, max_seq_len=32,
                      max_prefills_per_step=1,
                      default_max_new_tokens=5, paged=PAGED)
    worker = ServeWorker()
    worker._engine = eng
    worker._rank = 0
    shared = np.arange(1, 17)            # 2-page shared system prompt
    prompts = [np.concatenate([shared, np.array([30 + i, 40 + i])])
               for i in range(5)]
    prompts.append(np.arange(100, 107))  # cold-path control
    reqs = [sched.submit(p, tenant=("alice", "bob")[i % 2])
            for i, p in enumerate(prompts)]
    reused = 0
    for _ in range(300):
        plan = sched.plan()
        if plan is None:
            if sched.idle():
                break
            continue
        reused += sum(1 for p in plan["prefills"] if "reuse" in p)
        sched.apply(plan, worker.serve_step(plan))
    assert all(r.done() for r in reqs)
    assert reused >= 3, "prefix cache never hit"
    st = sched.pages.stats()
    assert st["reused_prefills"] == reused
    assert st["prefill_tokens_computed"] \
        < st["prefill_tokens_requested"]
    assert st["prefix_reuse_ratio"] > 0.3, st
    sched.pages.pool.check()
    for r in reqs:
        _assert_greedy_parity(eng, r.tokens, r.result(1).tolist())
    # the paged programs traced once each; serving never re-traced
    warm = eng.trace_counts_at_warmup
    assert eng.trace_counts == warm \
        and warm.get("kv_copy") == 1 and warm.get("suffix") == 1


@pytest.mark.slow
def test_retained_donor_survives_dummy_write_traffic(paged_engine):
    """Cross-wave reuse: a donor retained after its request finished
    keeps donating CORRECT pages even after many decode steps of
    idle-slot dummy writes (aimed at the never-registered last row)."""
    from ray_lightning_tpu.serve.worker import ServeWorker
    eng = paged_engine
    sched = Scheduler(buckets=(32,), slots=4, max_seq_len=32,
                      max_prefills_per_step=1,
                      default_max_new_tokens=4, paged=PAGED)
    worker = ServeWorker()
    worker._engine = eng
    worker._rank = 0
    shared = np.arange(3, 19)

    def drive():
        for _ in range(300):
            plan = sched.plan()
            if plan is None:
                if sched.idle():
                    return
                continue
            sched.apply(plan, worker.serve_step(plan))

    r1 = sched.submit(np.concatenate([shared, [77]]))
    drive()
    assert sched.pages.donor_count == 1
    # a full wave of unrelated traffic (dummy writes every decode step)
    other = [sched.submit(np.arange(50, 60) + i) for i in range(3)]
    drive()
    hits0 = sched.pages.stats()["prefix_hits"]
    r2 = sched.submit(np.concatenate([shared, [88, 89]]))
    drive()
    assert sched.pages.stats()["prefix_hits"] > hits0, \
        "retained donor was not reused"
    for r in [r1, *other, r2]:
        _assert_greedy_parity(eng, r.tokens, r.result(1).tolist())


# -- real fleet e2e on the local backend -----------------------------------

def _real_server_kwargs(tmp_path):
    return dict(num_workers=1, platform="cpu", buckets=(16, 32),
                max_batch_slots=4, max_new_tokens=6,
                compile_cache=str(tmp_path / "compile_cache"),
                telemetry=False)


@pytest.mark.slow
def test_fleet_e2e_autoscale_grow_shrink_local_backend(tmp_path, seed):
    """The real thing on the builtin local backend: a FleetServer of
    real Servers (subprocess worker actors) grows 1→2 under a burst,
    serves every request greedy-parity-correct through paged prefix
    reuse, shrinks back to 1 on the idle tail — the drained replica's
    requests complete elsewhere — and loses nothing."""
    from ray_lightning_tpu.models.gpt import GPTLightningModule
    from ray_lightning_tpu.parallel.strategy import DataParallelStrategy
    from ray_lightning_tpu.serve.engine import ServeEngine
    from ray_lightning_tpu.serve.fleet import FleetServer

    module = GPTLightningModule(_tiny())
    fleet = FleetServer(
        module, replicas=1,
        fleet={"min_replicas": 1, "max_replicas": 2,
               "grow_queue_depth": 1.0, "patience_ticks": 1,
               "cooldown_s": 0.2, "tick_interval_s": 0.05},
        paged={"page_size": 8},
        default_root_dir=str(tmp_path / "fleet"),
        **_real_server_kwargs(tmp_path)).start()
    try:
        shared = np.arange(1, 17)
        reqs = [fleet.submit(
            np.concatenate([shared, [20 + i]]),
            tenant=("alice", "bob")[i % 2]) for i in range(12)]
        outs = [r.result(timeout=180) for r in reqs]
        assert all(len(o) == 6 for o in outs)
        # the burst grew the fleet; the idle tail shrinks it
        _wait(lambda: fleet.autoscaler.stats()["grows"] >= 1,
              timeout=120, msg="grow event")
        _wait(lambda: fleet.autoscaler.stats()["shrinks"] >= 1,
              timeout=120, msg="shrink event")
        _wait(lambda: len(fleet._replicas) == 1, timeout=60,
              msg="drained replica reaped")
        st = fleet.autoscaler.stats()
        assert all(e["seconds"] is not None for e in st["events"])
        assert fleet.failed == 0 and not fleet.failovers
        # requests routed across the scale events still parity-check
        pages = fleet.pages_stats()
        assert pages["prefix_reuse_ratio"] > 0, pages
        # a late request lands on the survivor
        late = fleet.generate(np.concatenate([shared, [99]]),
                              tenant="alice", timeout=120)
        assert len(late) == 6
        status = fleet.status()["fleet"]
        assert status["completed"] == 13 and status["failed"] == 0
    finally:
        fleet.shutdown()
    # greedy parity vs the cold whole-sequence reference (the fixture
    # engine shares the fleet's params: same config/seed/strategy)
    eng = ServeEngine(module, DataParallelStrategy(), buckets=(16, 32),
                      slots=4, max_seq_len=32, seed=0).setup()
    for r, out in zip(reqs, outs):
        _assert_greedy_parity(eng, r.prompt, out.tolist())


@pytest.mark.slow
def test_disagg_roles_ship_resume_parity_and_chaos_failover(
        tmp_path, seed, monkeypatch):
    """Disaggregated decode e2e on the local backend: a 1-prefill +
    1-decode fleet serves every request with tokens IDENTICAL to a
    pooled fleet's (ship -> resume parity; raw ships fp32 and is
    bit-exact, fp8 rides the wire >= 3x smaller under the same bar),
    sub-page prompts stay pooled, and a chaos-dropped ship exhausts
    its bounded retries (RLT_PEER_RETRIES) then fails over PER-REQUEST
    to a local prefill — same tokens, counted failover."""
    from ray_lightning_tpu.models.gpt import GPTLightningModule
    from ray_lightning_tpu.serve.fleet import FleetServer

    monkeypatch.setenv("RLT_PEER_RETRIES", "2")
    monkeypatch.setenv("RLT_PEER_BACKOFF_S", "0.01")
    monkeypatch.setenv("RLT_KVSHIP_TIMEOUT_S", "0.05")
    module = GPTLightningModule(_tiny())
    kw = _real_server_kwargs(tmp_path)
    shared = np.arange(1, 17)                  # 2 whole pages
    prompts = [np.concatenate([shared, [20 + i]]) for i in range(3)]
    prompts.append(np.arange(1, 7))            # sub-page: stays pooled

    def serve(tag, fleet_cfg):
        fleet = FleetServer(
            module, replicas=2, autoscale=False, fleet=fleet_cfg,
            paged={"page_size": 8},
            default_root_dir=str(tmp_path / tag), **kw).start()
        outs, kv = [], None
        try:
            # sequential: each ship sees its own fresh donor pages
            outs = [fleet.generate(p, timeout=180).tolist()
                    for p in prompts]
            if fleet_cfg:
                fleet.arm_kvship_drop(1)
                outs.append(fleet.generate(prompts[0],
                                           timeout=180).tolist())
            kv = fleet.status()["fleet"].get("kvship")
        finally:
            fleet.shutdown()
        return outs, kv

    want, kv = serve("pooled", None)
    assert kv is None                  # pooled fleets carry no kvship
    for codec in ("raw", "fp8"):
        outs, kv = serve(codec, {"roles": ("prefill", "decode"),
                                 "kvship_codec": codec})
        # clean legs: exact ship->resume token parity vs pooled
        assert outs[:len(prompts)] == want, codec
        # chaos leg replays prompt 0: identical tokens via failover
        assert outs[-1] == want[0], codec
        assert kv["ships"] == 3 and kv["failovers"] == 1, kv
        assert kv["retries"] == 2, kv      # bounded: RLT_PEER_RETRIES
        if codec == "fp8":
            assert kv["compression_ratio"] >= 3.0, kv
        else:
            assert kv["compression_ratio"] == 1.0, kv


@pytest.mark.slow
def test_serve_pump_flight_dump_on_worker_death(tmp_path, seed):
    """Satellite: a replica classified dead MID-SERVE dumps
    flight_<rank>.json with the serve cause, and the server's
    failure_report links the paths (the router's failover report
    surface)."""
    import os

    from ray_lightning_tpu.models.gpt import GPTLightningModule
    from ray_lightning_tpu.serve import Server

    kwargs = _real_server_kwargs(tmp_path)
    kwargs["telemetry"] = {"metrics": False, "heartbeat_interval": 0.2}
    server = Server(
        GPTLightningModule(_tiny()),
        default_root_dir=str(tmp_path / "serve"), **kwargs).start()
    try:
        # kill the worker process out from under the pump, then submit:
        # the next serve_step dispatch dies mid-serve — the death-
        # classification path, deterministically
        server._workers[0].kill()
        req = server.submit(np.arange(1, 12))
        with pytest.raises(BaseException):
            req.result(timeout=120)
        report = server.failure_report
        assert report is not None and "cause" in report
        assert report["flight_paths"], report
        for rank, path in report["flight_paths"].items():
            assert os.path.exists(path), path
            import json
            doc = json.load(open(path))
            assert doc["cause"].startswith("serve fleet failure"), \
                doc["cause"]
        assert "failure" in server.stats()
    finally:
        server.shutdown(graceful=False)
