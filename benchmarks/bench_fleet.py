"""Fleet-plane benchmark: traffic-record-and-replay against 1 vs N
serve replicas — the "heavy traffic" leg made measurable.

The harness RECORDS a request trace (a multi-tenant session: arrival
offsets, tenant, prompt tokens with a shared system prompt inside each
tenant group, per-request token budget) to a JSON file, then REPLAYS it
at 1x/2x/4x time compression:

- **1x / 2x, 1 vs 2 replicas** — a plain :class:`Server` (the
  single-fleet reference; also the greedy-parity oracle) vs a
  :class:`FleetServer` with 2 replicas and paged-KV prefix reuse.  The
  acceptance bar: 2 replicas sustain strictly higher tokens/s than 1
  at the 2x multiplier.
- **4x, autoscaling 1→3 replicas** — the burst drives queue depth past
  the grow threshold (at least one grow event), and the idle tail
  after the burst drives occupancy to zero (at least one shrink, the
  drained replica's requests completing elsewhere).
- **4x, disaggregated 1 prefill + 1 decode vs 2 pooled** — role-split
  replicas (``FleetConfig(roles=...)``) with codec-compressed KV-page
  shipping (fp8 wire leg + raw fp32 control).  The acceptance bars:
  disaggregated TTFT p99 strictly below the 2-pooled-replica baseline
  at 4x, KV pages genuinely shipped on both codec legs, and fp8 wire
  bytes >= 3x under raw.
- **prefix reuse** — each tenant group shares a system prompt, so the
  fleet's ``prefill tokens computed vs requested`` ratio must come out
  nonzero.
- **2x, prefix federation A/B** — its own 8-group trace, 2 replicas
  with stickiness defeated (scrambled per-request tenants), the fleet
  prefix directory off vs on, plus a 1-replica locality control.  The
  acceptance bars: fed-on reuse ratio recovers at least the
  single-replica control and beats fed-off outright, KV pages
  genuinely federate (directory hits → wire ships → federated tokens
  reused), TTFT p50 holds, and every leg is greedy-parity-exact
  against its own reference replay.  A fourth leg runs the
  disaggregated pair with federation on: decode-pool donors serve
  fetch-backs so prefill-pool evictions do not force re-prefills.
- **parity** — every routed request's tokens are compared with the
  single-``Server`` reference; bf16 near-tie flips fall back to the
  teacher-forced tolerance bar (tests/test_serve.py's 2e-2).

Emits ONE ``fleet`` JSON line with tokens/s + TTFT p50/p99 per
multiplier, the replicas A/B, autoscale events (with actuation
seconds), the prefix-reuse ratio and the parity verdict.  Wired into
``bench.py`` as the ``RLT_FLEET_AB=1`` leg and into the perf ledger
(``bench.py --compare``) through the ``fleet.tokens_per_sec`` /
``fleet.ttft_p99_ms`` bands.

    python -m benchmarks.bench_fleet [--requests N] [--trace PATH]
"""

from __future__ import annotations

import argparse
import json
import os
import tempfile
import time

import numpy as np

from ray_lightning_tpu.ops.flash_decode import resolve_decode_impl

#: serving geometry for the CPU-proxy run (tiny GPT, block 32)
BUCKETS = (16, 32)
SLOTS = 4
PAGE_SIZE = 8
MAX_NEW = 14
#: absolute TTFT-p50 slack for the federation A/B gate: median drift
#: under this is scheduler noise on the CPU proxy, not a signal
MIN_TTFT_FLOOR_MS = 5.0


def record_trace(path: str, requests: int = 64, seed: int = 0,
                 duration_s: float = 0.8,
                 groups: "int | None" = None) -> list:
    """Record a multi-tenant request trace to ``path``.

    Three tenant groups; the tenants inside a group share a 2-page
    system prompt (the prefix-reuse mix), each request appending its
    own suffix.  Arrival offsets spread over ``duration_s`` with a
    front-loaded burst so compressed replays genuinely queue.

    ``groups=N`` records the federation-A/B shape instead: N shared-
    prompt groups with FEW requests each, arriving group-staggered
    over ``duration_s`` — a group's first request completes (and its
    pages become a retained, advertised donor) while load from the
    other groups keeps both replicas busy, so the group's later
    requests land on a replica that does NOT hold the prefix and the
    only alternatives are a federated pull or a duplicate prefill.
    """
    rng = np.random.default_rng(seed)
    if groups is not None:
        group_map = {
            f"g{i}": np.asarray(rng.integers(1, 100, size=2 * PAGE_SIZE))
            for i in range(int(groups))}
        tenants = list(group_map)
        trace = []
        for i in range(requests):
            # round-robin over the groups: consecutive same-group
            # arrivals are ``groups`` slots apart, so a group's donor
            # is retained before its next request, while the OTHER
            # groups' decode tails keep every replica busy enough
            # that affinity routing can't always land on the donor
            tenant = tenants[i % len(tenants)]
            shared = group_map[tenant]
            suffix = rng.integers(1, 100, size=int(rng.integers(3, 9)))
            trace.append({
                "at": round(i * duration_s / requests
                            + float(rng.uniform(0, 0.5))
                            * duration_s / requests, 4),
                "tenant": tenant,
                "prompt": [int(t) for t in
                           np.concatenate([shared, suffix])],
                "max_new": int(MAX_NEW),
            })
        trace.sort(key=lambda r: r["at"])
        with open(path, "w") as f:
            json.dump({"version": 1, "requests": trace}, f)
        return trace
    groups_map = {
        "alice": np.asarray(rng.integers(1, 100, size=2 * PAGE_SIZE)),
        "bob": np.asarray(rng.integers(1, 100, size=2 * PAGE_SIZE)),
        "carol": None,    # no shared prompt: the cold-path control
    }
    groups = groups_map
    tenants = list(groups)
    trace = []
    for i in range(requests):
        tenant = tenants[i % len(tenants)]
        shared = groups[tenant]
        suffix = rng.integers(1, 100, size=int(rng.integers(3, 9)))
        if shared is None:
            # cold tenant: no shared prefix (nothing for the prefix
            # cache), but still a page-sized prompt — every request
            # owns >= 1 whole page, so the cold path rides every
            # serving mode including disaggregation (sub-page prompts
            # are covered by tests/test_fleet.py)
            prompt = np.concatenate(
                [rng.integers(1, 100, size=PAGE_SIZE), suffix])
        else:
            prompt = np.concatenate([shared, suffix])
        trace.append({
            # front-loaded: 70% of arrivals in the first half
            "at": round(float(rng.beta(1.2, 2.0)) * duration_s, 4),
            "tenant": tenant,
            "prompt": [int(t) for t in prompt],
            "max_new": int(MAX_NEW),
        })
    trace.sort(key=lambda r: r["at"])
    with open(path, "w") as f:
        json.dump({"version": 1, "requests": trace}, f)
    return trace


def load_trace(path: str) -> list:
    with open(path) as f:
        return json.load(f)["requests"]


def replay(endpoint, trace: list, multiplier: float,
           timeout: float = 600.0, scramble: bool = False) -> dict:
    """Replay the trace at ``multiplier``x time compression against any
    ``submit``-surface endpoint (Server or FleetServer); returns the
    measured leg.  ``scramble`` suffixes every tenant with its request
    index, defeating tenant stickiness entirely — the worst case for
    per-replica prefix locality and the federation A/B's substrate
    (tokens are tenant-independent, so parity is unaffected)."""
    t0 = time.monotonic()
    handles = []
    for i, rec in enumerate(trace):
        due = t0 + rec["at"] / multiplier
        delay = due - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        tenant = f"{rec['tenant']}~{i}" if scramble else rec["tenant"]
        handles.append(endpoint.submit(
            np.asarray(rec["prompt"], np.int32), tenant=tenant,
            max_new_tokens=rec["max_new"]))
    outs = [h.result(timeout=timeout) for h in handles]
    wall = time.monotonic() - t0
    ttfts = np.asarray([h.ttft_s for h in handles
                        if h.ttft_s is not None]) * 1e3
    tokens = int(sum(len(o) for o in outs))
    return {
        "tokens_per_sec": round(tokens / wall, 2),
        "total_tokens": tokens,
        "wall_s": round(wall, 3),
        "requests": len(handles),
        "ttft_p50_ms": round(float(np.percentile(ttfts, 50)), 2)
        if len(ttfts) else None,
        "ttft_p99_ms": round(float(np.percentile(ttfts, 99)), 2)
        if len(ttfts) else None,
        "outputs": [o.tolist() for o in outs],
    }


def check_parity(module, engine_params_ref, trace: list, legs: dict
                 ) -> dict:
    """Every routed request greedy-parity-equal to the single-Server
    reference: exact token match, with the teacher-forced 2e-2
    tolerance bar (tests/test_serve.py) deciding bf16 near-tie flips."""
    model = module.configure_decode_model()
    params = engine_params_ref
    ref_outputs = legs["reference"]["outputs"]
    checked = flipped = bad = 0
    for leg_name, leg in legs.items():
        if leg_name == "reference":
            continue
        for rec, got, want in zip(trace, leg["outputs"], ref_outputs):
            checked += 1
            if got == want:
                continue
            flipped += 1
            seq = [int(t) for t in rec["prompt"]]
            for tok in got:
                logits = np.asarray(model.apply(
                    {"params": params},
                    np.asarray([seq], np.int32), True))[0, -1]
                best = int(np.argmax(logits))
                if tok != best and logits[tok] < logits[best] - 2e-2:
                    bad += 1
                    break
                seq.append(int(tok))
    return {"checked": checked, "exact": checked - flipped,
            "tolerance_flips": flipped - bad, "mismatched": bad,
            "ok": bad == 0}


def run_fleet_ab(metric: str, requests: int = 64,
                 trace_path: "str | None" = None) -> "list[dict]":
    """The RLT_FLEET_AB=1 bench leg; returns the emitted records."""
    from ray_lightning_tpu.models.gpt import GPTConfig, GPTLightningModule
    from ray_lightning_tpu.serve import Server
    from ray_lightning_tpu.serve.fleet import FleetServer

    cfg = GPTConfig(vocab_size=128, block_size=32, n_layer=2, n_head=2,
                    n_embd=32, remat=False)
    num_workers = int(os.environ.get("RLT_FLEET_WORKERS", "1"))
    platform = os.environ.get("RLT_FLEET_PLATFORM", "cpu")
    root = os.environ.get("RLT_FLEET_DIR") or tempfile.mkdtemp(
        prefix="rlt_bench_fleet_")

    if trace_path and os.path.exists(trace_path):
        trace = load_trace(trace_path)
    else:
        trace_path = trace_path or os.path.join(root, "trace.json")
        trace = record_trace(trace_path, requests=requests)
    # the federation A/B's own trace (record_trace ``groups=``): 8
    # small groups round-robin over a spread window — a group's donor
    # is retained before its next request arrives, while the other
    # groups' decode tails keep the donor replica busy enough that
    # affinity routing regularly loses and the pages must be PULLED
    fed_trace = record_trace(os.path.join(root, "fed_trace.json"),
                             requests=48, seed=3, duration_s=1.0,
                             groups=8)

    server_kw = dict(
        num_workers=num_workers, platform=platform, buckets=BUCKETS,
        max_batch_slots=SLOTS, max_new_tokens=MAX_NEW,
        telemetry=False)

    legs: dict = {}
    # -- single Server: the reference fleet AND the parity oracle ------
    module = GPTLightningModule(cfg)
    server = Server(module, default_root_dir=os.path.join(root, "ref"),
                    paged=False, **server_kw).start()
    legs_fed: dict = {}
    try:
        legs["reference"] = replay(server, trace, 1.0)
        legs["single_2x"] = replay(server, trace, 2.0)
        # the federation trace's parity oracle rides the same Server
        legs_fed["reference"] = replay(server, fed_trace, 2.0)
    finally:
        server.shutdown()

    # -- 2 fixed replicas, paged prefix reuse --------------------------
    fleet2 = FleetServer(
        GPTLightningModule(cfg), replicas=2, autoscale=False,
        paged={"page_size": PAGE_SIZE},
        default_root_dir=os.path.join(root, "fleet2"),
        **server_kw).start()
    try:
        legs["fleet2_1x"] = replay(fleet2, trace, 1.0)
        legs["fleet2_2x"] = replay(fleet2, trace, 2.0)
        # the 4x burst is the disaggregation baseline: same 2 replicas,
        # both pooled, slots held hostage by 14-token decode tails
        legs["pooled2_4x"] = replay(fleet2, trace, 4.0)
        fleet2_pages = fleet2.pages_stats()
        fleet2_status = fleet2.status()["fleet"]
    finally:
        fleet2.shutdown()

    # -- prefix federation A/B: 2 replicas, NO stickiness, fed off/on --
    # Scrambled per-request tenants mean nothing keeps a group's
    # requests on the replica that already holds their prefix — the
    # worst case for per-replica reuse.  fed_off pays one group-prompt
    # prefill PER REPLICA; fed_on pulls the pages over the kvship
    # plane and prefills once per FLEET.  Replica goodput ledgers are
    # armed on these legs so prefill-seconds-saved is MEASURED wall,
    # not an estimate; the 1-replica leg is the sticky upper bound
    # (perfect locality) the federated ratio is held against.
    fed_kw = {**server_kw,
              "telemetry": {"enabled": True, "metrics": False,
                            "incident": False}}
    # the kvship codec's jnp kernels compile per rows-shape on first
    # use.  That cache is process-global XLA state, not fleet state —
    # the fed fleets stay COLD (donor/directory state is the A/B) but
    # a timed fetch must not pay a one-time compile the disagg legs
    # amortize in their warm replay
    from ray_lightning_tpu.comm.quant import (dequantize_blob,
                                              quantize_blob)
    for pages in (1, 2, 3, 4):
        rows = np.zeros((cfg.n_layer, pages * PAGE_SIZE, cfg.n_embd),
                        np.float32)
        payload, scale = quantize_blob(rows, "fp8")
        dequantize_blob(np.asarray(payload),
                        None if scale is None else np.asarray(scale),
                        "fp8", rows.shape)
    single1 = FleetServer(
        GPTLightningModule(cfg), replicas=1, autoscale=False,
        paged={"page_size": PAGE_SIZE},
        default_root_dir=os.path.join(root, "single1"),
        **fed_kw).start()
    try:
        legs_fed["single1_2x"] = replay(single1, fed_trace, 2.0,
                                        scramble=True)
        single1_pages = single1.pages_stats()
    finally:
        single1.shutdown()
    fed_status, fed_pages, fed_gp = {}, {}, {}
    for fed_on in (False, True):
        tag = "fed_on" if fed_on else "fed_off"
        f = FleetServer(
            GPTLightningModule(cfg), replicas=2, autoscale=False,
            fleet={"sticky_slack": 0, "prefix_fed": fed_on},
            paged={"page_size": PAGE_SIZE},
            default_root_dir=os.path.join(root, tag),
            **fed_kw).start()
        try:
            legs_fed[f"{tag}_2x"] = replay(f, fed_trace, 2.0,
                                           scramble=True)
            fed_status[tag] = f.status()["fleet"]
            fed_pages[tag] = f.pages_stats()
            fed_gp[tag] = f.goodput_stats() or {"buckets": {}}
        finally:
            f.shutdown()

    # -- disaggregated: 1 prefill + 1 decode replica, KV pages ship ----
    # over the peer channel.  The prefill replica's slots free after
    # ONE token (no decode tail), so burst admissions stop queueing
    # behind held slots — the TTFT-p99 win the 4x comparison pins.
    # fp8 is the compressed wire leg; raw (fp32) is the A/B control.
    disagg_status = {}
    for codec in ("fp8", "raw"):
        dis = FleetServer(
            GPTLightningModule(cfg), replicas=2, autoscale=False,
            fleet={"roles": ("prefill", "decode"),
                   "kvship_codec": codec},
            paged={"page_size": PAGE_SIZE},
            default_root_dir=os.path.join(root, f"disagg_{codec}"),
            **server_kw).start()
        try:
            # warm pass (discarded): the pooled2 baseline replays 1x
            # and 2x before ITS timed 4x leg, so its programs, donors
            # and pools are hot — the A/B is only fair if the disagg
            # fleet starts its timed leg equally warm
            replay(dis, trace, 1.0)
            legs[f"disagg_{codec}_4x"] = replay(dis, trace, 4.0)
            disagg_status[codec] = dis.status()["fleet"]
        finally:
            dis.shutdown()

    # -- disaggregated + federation: decode donors feed the prefill ----
    # pool.  A prefill replica whose donor evicted under burst churn
    # would re-prefill a prefix the decode replica ALREADY adopted
    # (the shipped pages retained there); with the directory on, the
    # prefill pool fetches those pages back over the same wire instead
    # of paying the prefill twice.
    disfed = FleetServer(
        GPTLightningModule(cfg), replicas=2, autoscale=False,
        fleet={"roles": ("prefill", "decode"), "prefix_fed": True},
        paged={"page_size": PAGE_SIZE},
        default_root_dir=os.path.join(root, "disagg_fed"),
        **server_kw).start()
    try:
        replay(disfed, trace, 1.0)     # warm, like the other disagg legs
        legs["disagg_fed_4x"] = replay(disfed, trace, 4.0)
        disfed_status = disfed.status()["fleet"]
        disfed_pages = disfed.pages_stats()
    finally:
        disfed.shutdown()

    # -- autoscaling fleet under the 4x burst --------------------------
    auto = FleetServer(
        GPTLightningModule(cfg), replicas=1,
        fleet={"min_replicas": 1, "max_replicas": 3,
               "grow_queue_depth": 2.0, "patience_ticks": 2,
               "cooldown_s": 1.0, "tick_interval_s": 0.1,
               "shrink_occupancy": 0.25},
        paged={"page_size": PAGE_SIZE},
        default_root_dir=os.path.join(root, "auto"),
        **server_kw).start()
    try:
        legs["auto_4x"] = replay(auto, trace, 4.0)
        # idle tail: empty queue + zero occupancy drives the shrink
        deadline = time.monotonic() + 90
        while time.monotonic() < deadline:
            st = auto.autoscaler.stats()
            if st["shrinks"] >= 1 and not st["actuating"]:
                break
            time.sleep(0.2)
        autoscale = auto.autoscaler.stats()
        auto_status = auto.status()["fleet"]
        auto_pages = auto.pages_stats()
    finally:
        auto.shutdown()

    # -- parity: every routed request vs the single-Server reference ---
    import jax
    eng = None
    try:
        from ray_lightning_tpu.parallel.strategy import (
            DataParallelStrategy)
        from ray_lightning_tpu.serve.engine import ServeEngine
        eng = ServeEngine(module, DataParallelStrategy(),
                          buckets=BUCKETS, slots=SLOTS,
                          max_seq_len=cfg.block_size, seed=0).setup()
        ref_params = jax.device_get(eng.params)
    finally:
        del eng
    parity = check_parity(module, ref_params, trace, legs)
    # the federation legs replay their own trace — same oracle, its
    # own reference outputs (scrambled tenants don't touch tokens)
    parity_fed = check_parity(module, ref_params, fed_trace, legs_fed)

    headline = legs["fleet2_2x"]
    fleet_doc = {
        "trace": {"path": trace_path, "requests": len(trace),
                  "tenants": len({r['tenant'] for r in trace})},
        "workers_per_replica": num_workers,
        "platform": platform,
        "slots": SLOTS,
        "page_size": PAGE_SIZE,
        # the REQUESTED decode impl (env or "auto"); what an engine
        # lowered is in its own stats()["decode_kernel"]
        "decode_impl": resolve_decode_impl(None),
        "tokens_per_sec": headline["tokens_per_sec"],
        "ttft_p99_ms": headline["ttft_p99_ms"],
        "multipliers": {
            "1x": {"single": _slim(legs["reference"]),
                   "fleet2": _slim(legs["fleet2_1x"])},
            "2x": {"single": _slim(legs["single_2x"]),
                   "fleet2": _slim(legs["fleet2_2x"])},
            "4x": {"autoscale": _slim(legs["auto_4x"]),
                   "pooled2": _slim(legs["pooled2_4x"]),
                   "disagg": _slim(legs["disagg_fp8_4x"]),
                   "disagg_raw": _slim(legs["disagg_raw_4x"]),
                   "disagg_fed": _slim(legs["disagg_fed_4x"])},
        },
        "disagg": {
            "roles": ["prefill", "decode"],
            "ttft_p99_ms": legs["disagg_fp8_4x"]["ttft_p99_ms"],
            "pooled2_ttft_p99_ms": legs["pooled2_4x"]["ttft_p99_ms"],
            "kvship": {c: disagg_status[c]["kvship"]
                       for c in disagg_status},
            # fp8's own raw-baseline ratio (bytes_raw is the fp32 size
            # of the same shipped rows — the raw control leg's wire)
            "fp8_compression_ratio":
                disagg_status["fp8"]["kvship"]["compression_ratio"],
        },
        "federation": {
            "trace": {"requests": len(fed_trace), "groups": 8},
            # the locality control: ONE replica sees every request, so
            # its donors get perfect routing — but also only one
            # replica's worth of slots to retain them in.  The fed-on
            # fleet must recover at least this reuse ratio with ZERO
            # tenant locality across twice the slots.
            "single_sticky_reuse_ratio":
                single1_pages["prefix_reuse_ratio"],
            "single1": _slim(legs_fed["single1_2x"]),
            "fed_off": {
                **_slim(legs_fed["fed_off_2x"]),
                "prefix_reuse_ratio":
                    fed_pages["fed_off"]["prefix_reuse_ratio"],
                "prefill_s":
                    round(fed_gp["fed_off"]["buckets"].get(
                        "prefill", 0.0), 3),
            },
            "fed_on": {
                **_slim(legs_fed["fed_on_2x"]),
                "prefix_reuse_ratio":
                    fed_pages["fed_on"]["prefix_reuse_ratio"],
                "federated_reuse_ratio":
                    fed_pages["fed_on"].get("federated_reuse_ratio"),
                "federated_tokens_reused":
                    fed_pages["fed_on"].get("federated_tokens_reused"),
                "prefill_s":
                    round(fed_gp["fed_on"]["buckets"].get(
                        "prefill", 0.0), 3),
                "kv_fed_s":
                    round(fed_gp["fed_on"]["buckets"].get(
                        "kv_fed", 0.0), 3),
                "counters": fed_status["fed_on"]["federation"],
            },
            # MEASURED prefill wall delta (replica goodput ledgers),
            # not an estimate from token counts.  Reported, not
            # asserted: on the CPU proxy a 16-token prefill costs
            # single milliseconds, so the delta is noise-band — the
            # reuse-ratio recovery above is the contract
            "prefill_seconds_saved": round(
                fed_gp["fed_off"]["buckets"].get("prefill", 0.0)
                - fed_gp["fed_on"]["buckets"].get("prefill", 0.0), 3),
        },
        "disagg_fed": {
            "ttft_p99_ms": legs["disagg_fed_4x"]["ttft_p99_ms"],
            "federation": disfed_status.get("federation"),
            "federated_tokens_reused":
                disfed_pages.get("federated_tokens_reused"),
            "kvship_ships": disfed_status["kvship"]["ships"],
        },
        "autoscale": {
            "events": autoscale["events"],
            "grows": autoscale["grows"],
            "shrinks": autoscale["shrinks"],
        },
        "prefix_reuse": fleet2_pages,
        "prefix_reuse_auto": auto_pages,
        # fraction of requested prefill tokens satisfied by pages
        # PULLED from another replica (the fed_on leg) — the ledger's
        # fleet.federated_reuse_ratio band
        "federated_reuse_ratio":
            fed_pages["fed_on"].get("federated_reuse_ratio", 0.0),
        "failovers": (fleet2_status["failovers"]
                      + auto_status["failovers"]),
        "requests_lost": fleet2_status["failed"] + auto_status["failed"],
        "parity": parity,
        "parity_federation": parity_fed,
    }
    record = {"metric": metric, "value": headline["tokens_per_sec"],
              "unit": "tokens/s", "fleet": fleet_doc}
    print(json.dumps(record), flush=True)

    # the acceptance bars, enforced where the bench runs
    assert legs["fleet2_2x"]["tokens_per_sec"] \
        > legs["single_2x"]["tokens_per_sec"], (
        "2 replicas did not beat 1 at the 2x replay",
        legs["fleet2_2x"]["tokens_per_sec"],
        legs["single_2x"]["tokens_per_sec"])
    assert autoscale["grows"] >= 1, autoscale
    assert autoscale["shrinks"] >= 1, autoscale
    assert fleet_doc["prefix_reuse"]["prefix_reuse_ratio"] > 0, \
        fleet_doc["prefix_reuse"]
    assert fleet_doc["requests_lost"] == 0, fleet_doc["failovers"]
    assert parity["ok"], parity
    # disaggregation bars: prefill/decode split beats 2 pooled replicas
    # on 4x-burst TTFT p99; KV pages genuinely shipped; fp8 rides the
    # wire at >= 3x under the raw (fp32) control leg
    dis = fleet_doc["disagg"]
    assert dis["ttft_p99_ms"] < dis["pooled2_ttft_p99_ms"], dis
    for codec, kv in dis["kvship"].items():
        assert kv["ships"] > 0, (codec, kv)
    assert dis["fp8_compression_ratio"] >= 3.0, dis
    assert all(st["failed"] == 0 for st in disagg_status.values()), \
        disagg_status
    # federation bars: pages genuinely federate (directory hits turn
    # into wire ships that save real prefill tokens); the fed-on reuse
    # ratio beats fed-off outright AND recovers the single-replica
    # sticky control (small slack: capacity-gated fetches may skip
    # under burst).  TTFT: the MEDIAN must hold — fetches ride the
    # tail by construction on this proxy, where a 2-page wire pull
    # (two worker RPCs against a busy donor) costs more wall than the
    # 16-token prefill it replaces; the tail win needs prefix lengths
    # that only exist off the CPU proxy, so p99 is reported, not gated
    fed = fleet_doc["federation"]
    assert fed["fed_on"]["counters"]["fetches"] > 0, fed
    assert fed["fed_on"]["counters"]["ships"] > 0, fed
    assert fed["fed_on"]["federated_tokens_reused"] > 0, fed
    assert fed["fed_on"]["federated_reuse_ratio"] > 0, fed
    assert fed["fed_on"]["prefix_reuse_ratio"] \
        > fed["fed_off"]["prefix_reuse_ratio"], fed
    assert fed["fed_on"]["prefix_reuse_ratio"] \
        >= fed["single_sticky_reuse_ratio"] - 0.05, fed
    assert fed["fed_on"]["ttft_p50_ms"] \
        <= 2.0 * fed["fed_off"]["ttft_p50_ms"] + MIN_TTFT_FLOOR_MS, fed
    assert fed_status["fed_on"]["failed"] == 0, fed_status["fed_on"]
    assert parity_fed["ok"], parity_fed
    # disaggregated + federation: decode-held prefixes come back over
    # the wire instead of being re-prefilled, and nothing is lost
    disf = fleet_doc["disagg_fed"]
    assert disf["kvship_ships"] > 0, disf
    assert disfed_status["failed"] == 0, disfed_status
    return [record]


def _slim(leg: dict) -> dict:
    return {k: v for k, v in leg.items() if k != "outputs"}


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--requests", type=int, default=64)
    parser.add_argument("--trace", default=None,
                        help="replay this recorded trace JSON instead "
                        "of recording a fresh one")
    args = parser.parse_args()
    run_fleet_ab("fleet_serve", requests=args.requests,
                 trace_path=args.trace)


if __name__ == "__main__":
    main()
