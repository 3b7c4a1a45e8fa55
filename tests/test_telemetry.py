"""Run telemetry: span/counter recording, driver-side aggregation,
heartbeat watchdog, and Perfetto trace export (telemetry/).

The e2e case mirrors the subsystem's reason to exist (SURVEY.md §5: the
reference observes nothing but an epoch timer, and only on rank 0): a
2-worker local-backend fit must land step/compile/collective spans from
BOTH ranks on one driver timeline.
"""

import json
import logging
import os
import time

import pytest

from ray_lightning_tpu import Trainer, telemetry
from ray_lightning_tpu.models import BoringModel
from ray_lightning_tpu.telemetry import tracing
from ray_lightning_tpu.telemetry.aggregator import (
    TelemetryAggregator,
    WorkerHeartbeatTimeout,
)
from ray_lightning_tpu.telemetry.flight import FlightRecorder
from ray_lightning_tpu.telemetry.heartbeat import make_heartbeat

from tests.utils import cpu_plugin


@pytest.fixture(autouse=True)
def _clean_telemetry():
    """Recorder and active aggregator are process/thread-ambient; never
    leak them across tests."""
    yield
    telemetry.disable()
    telemetry.disable_anatomy()
    telemetry.disable_metrics()
    telemetry.set_active(None)


# -- span/counter API ----------------------------------------------------

def test_span_nesting_depth_and_rank():
    telemetry.enable(rank=3, sink=None, flush_every=None)
    with telemetry.span("outer"):
        with telemetry.span("inner", step=7):
            pass
    recs = telemetry.drain()
    by_name = {r["name"]: r for r in recs}
    assert set(by_name) == {"outer", "inner"}
    assert by_name["inner"]["depth"] == 1
    assert by_name["outer"]["depth"] == 0
    assert by_name["inner"]["attrs"] == {"step": 7}
    assert all(r["rank"] == 3 for r in recs)
    assert all(r["dur"] >= 0 for r in recs)
    # inner is fully contained in outer on the timeline
    assert by_name["inner"]["ts"] >= by_name["outer"]["ts"]


def test_disabled_mode_is_noop_singleton():
    assert not telemetry.enabled()
    # identity: no allocation per call when disabled
    assert telemetry.span("a") is telemetry.span("b")
    telemetry.counter("x", 1.0)      # must not raise
    assert telemetry.drain() == []
    # overhead: purely a bound sanity check (generous: ~20µs/span)
    t0 = time.monotonic()
    for _ in range(10_000):
        with telemetry.span("step"):
            pass
    assert time.monotonic() - t0 < 0.2


# -- a span is a span: ids, parents, steps, threads ---------------------------

def test_span_ids_parents_and_inherited_step():
    telemetry.enable(rank=0, sink=None, flush_every=None)
    with telemetry.span("serve_step", step=41):
        with telemetry.span("decode", slots=3):
            with telemetry.span("fetch"):
                pass
        with telemetry.span("prefill", step=7):   # its own wins
            pass
    with telemetry.span("alone"):
        pass
    by = {r["name"]: r for r in telemetry.drain()}
    ids = [r["id"] for r in by.values()]
    assert len(set(ids)) == 5 and all(isinstance(i, int) for i in ids)
    assert by["serve_step"]["parent"] is None
    assert by["decode"]["parent"] == by["serve_step"]["id"]
    assert by["fetch"]["parent"] == by["decode"]["id"]
    assert by["prefill"]["parent"] == by["serve_step"]["id"]
    assert by["alone"]["parent"] is None and "attrs" not in by["alone"]
    # a child belongs to its parent's step unless it names its own
    assert by["decode"]["attrs"] == {"slots": 3, "step": 41}
    assert by["fetch"]["attrs"] == {"step": 41}
    assert by["prefill"]["attrs"] == {"step": 7}
    assert [by[n]["depth"] for n in ("serve_step", "decode", "fetch")] \
        == [0, 1, 2]


def test_span_stacks_are_per_thread():
    """A background thread's spans (the AOT compile thread) are roots of
    their own and leave the loop's nesting alone."""
    import threading
    telemetry.enable(rank=0, sink=None, flush_every=None)
    inside, release = threading.Event(), threading.Event()

    def other():
        with telemetry.span("aot", program="p"):
            inside.set()
            release.wait(10)

    with telemetry.span("loop", step=1):
        t = threading.Thread(target=other)
        t.start()
        assert inside.wait(10)
        with telemetry.span("child"):
            pass
        release.set()
        t.join(10)
    by = {r["name"]: r for r in telemetry.drain()}
    assert by["aot"]["parent"] is None and by["aot"]["depth"] == 0
    assert by["aot"]["attrs"] == {"program": "p"}      # no foreign step
    assert by["child"]["parent"] == by["loop"]["id"]
    assert by["child"]["depth"] == 1


def test_recorder_off_span_site_allocates_no_record():
    from ray_lightning_tpu.telemetry import spans
    assert not telemetry.enabled() and spans._windows == ()
    site = telemetry.span("step", step=3, traces={0: "abc"})
    with site:
        with telemetry.span("inner"):
            pass
    # no recorder, no keep window, no profiler session: the no-op
    # singleton, and nothing anywhere afterwards
    assert site is telemetry.span("other")
    assert telemetry.drain() == []
    assert spans.kept("step") == []


def test_keep_window_records_without_a_recorder_and_adopt_renumbers():
    from ray_lightning_tpu.telemetry import spans
    assert not telemetry.enabled()
    with spans.keep("server_start") as kept:
        with telemetry.span("server_start"):
            with telemetry.span("worker_setup") as waiting:
                # what a worker returned with setup_serve's result: ids
                # of another process, which may collide with ours
                theirs = [
                    {"t": "span", "name": "weights", "ts": 1.0, "dur": 2.0,
                     "rank": 0, "depth": 1, "id": 2, "parent": 1},
                    {"t": "span", "name": "setup_serve", "ts": 0.5,
                     "dur": 3.0, "rank": 0, "depth": 0, "id": 1,
                     "parent": None}]
                spans.adopt(kept, theirs, parent=waiting.id, rank=5)
    with telemetry.span("after"):     # window closed: not kept
        pass
    assert spans._windows == ()
    got = {r["name"]: r for r in spans.kept("server_start")}
    assert list(kept) == spans.kept("server_start")
    assert set(got) == {"server_start", "worker_setup", "weights",
                        "setup_serve"}
    assert got["worker_setup"]["parent"] == got["server_start"]["id"]
    assert got["setup_serve"]["parent"] == got["worker_setup"]["id"]
    assert got["weights"]["parent"] == got["setup_serve"]["id"]
    assert len({r["id"] for r in got.values()}) == 4
    assert got["weights"]["rank"] == 5 and got["weights"]["ts"] == 1.0
    assert theirs[0]["id"] == 2      # the caller's records are not touched
    # the next window of the name replaces this one: nothing grows
    with spans.keep("server_start"):
        pass
    assert spans.kept("server_start") == [] and len(kept) == 4


def test_keep_windows_nest_and_can_be_held_to_their_own_thread():
    """The pump's window keeps the pump thread's spans only; a set-up
    window keeps the AOT thread's beside the main thread's."""
    import threading

    from ray_lightning_tpu.telemetry import spans

    def other():
        with telemetry.span("aot", thread="aot"):
            pass

    with spans.keep("fit_setup") as whole:
        with spans.keep("pump", own_thread=True) as mine:
            with telemetry.span("pump.wait", step=1):
                t = threading.Thread(target=other)
                t.start()
                t.join(10)
        with telemetry.span("later"):
            pass
    assert [r["name"] for r in mine] == ["pump.wait"]
    assert [r["name"] for r in whole] == ["aot", "pump.wait", "later"]


def _host_plane(trace_dir):
    """``{name: [(start_s, dur_s, stats)]}`` of the ``rlt/`` annotations
    in the newest trace under ``trace_dir``."""
    import glob

    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    found = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("rlt/"):
                        found.setdefault(e.name, []).append(
                            (e.start_ns * 1e-9, e.duration_ns * 1e-9,
                             dict(e.stats)))
    return found


def test_profiler_session_holds_annotations_and_the_clock_anchor(tmp_path):
    """With a profiler session open every span site is an ``rlt/``
    annotation in the trace's host plane, attrs as stats, recorder on or
    off; ``rlt/clock`` maps the trace's clock to the recorder's."""
    import jax

    from ray_lightning_tpu.telemetry import spans
    telemetry.enable(rank=0, sink=None, flush_every=None)
    jax.profiler.start_trace(str(tmp_path))
    try:
        spans.clock_anchor()
        with telemetry.span("serve_step", step=12, traces={0: "x"}):
            time.sleep(0.01)
        telemetry.disable()
        with telemetry.span("unrecorded", bucket=512):
            time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    (record,) = [r for r in telemetry.drain() or []] or [None]
    host = _host_plane(str(tmp_path))
    assert set(host) == {"rlt/clock", "rlt/serve_step", "rlt/unrecorded"}
    (start, dur, stats), = host["rlt/serve_step"]
    assert stats == {"step": 12}              # scalars only: no slot map
    assert host["rlt/unrecorded"][0][2] == {"bucket": 512}
    a_start, _, a_stats = max(host["rlt/clock"],
                              key=lambda a: a[0])   # the last one made
    assert abs(a_stats["wall_ns"] * 1e-9 - time.time()) < 60
    offset = a_stats["wall_ns"] * 1e-9 - a_start
    assert dur >= 0.009
    if record is not None:      # the recorder's view of the same span
        assert record["name"] == "serve_step"
        assert abs((start + offset) - record["ts"]) < 1e-3


def test_recorded_span_lines_up_with_its_annotation_through_the_anchor(
        tmp_path):
    import jax

    from ray_lightning_tpu.telemetry import spans
    telemetry.enable(rank=0, sink=None, flush_every=None)
    jax.profiler.start_trace(str(tmp_path))
    try:
        spans.clock_anchor()
        for i in range(3):
            with telemetry.span("step", step=i):
                time.sleep(0.003)
    finally:
        jax.profiler.stop_trace()
    records = [r for r in telemetry.drain() if r["name"] == "step"]
    host = _host_plane(str(tmp_path))
    a_start, _, a_stats = max(host["rlt/clock"],
                              key=lambda a: a[0])   # the last one made
    offset = a_stats["wall_ns"] * 1e-9 - a_start
    assert len(records) == len(host["rlt/step"]) == 3
    for rec, (start, _dur, stats) in zip(records, sorted(host["rlt/step"])):
        assert stats == {"step": rec["attrs"]["step"]}
        assert abs((start + offset) - rec["ts"]) < 1e-3


# -- scopes and names -----------------------------------------------------------

@pytest.mark.parametrize("op_name,want", [
    ("jit(step_fn)/transpose(jvp(GPT))/h3/attn/qkv/dot_general", "attn"),
    ("jit(serve_decode)/GPT.decode/h0/attn/kv_cache/scatter", "kv_cache"),
    ("jit(step_fn)/jvp(lm_head)/dot_general", "lm_head"),
    ("jit(step_fn)/optimizer/mul", "optimizer"),
    ("jit(step_fn)/jvp(GPT)/h0/ln/ln1/reduce_sum", "ln"),
    ("jit(serve_decode)/lm_head/wte/dot_general", "lm_head"),
    ("jit(step_fn)/convert_element_type", None),
    ("state.params['h0']['ln1']['bias']", None),
])
def test_scope_of_takes_the_innermost_listed_name(op_name, want):
    from ray_lightning_tpu.telemetry import scopes
    assert scopes.scope_of(op_name) == want


def test_a_compiler_made_mover_is_listed_under_what_it_moves_and_marked():
    """The decode program's per-layer relayout copies of the cache have
    no path of their own (read on the v5e, PR 24): the table names the
    scope of what they move, marked, so that no reader takes it for the
    program's own placement."""
    from ray_lightning_tpu.telemetry import scopes
    text = """HloModule jit_serve_decode, is_scheduled=true
ENTRY %main (k: bf16[2]) -> bf16[2] {
  %k = bf16[2]{0} parameter(0)
  %fusion.2032 = (bf16[2]{0}, bf16[2]{0}) fusion(bf16[2]{0} %k), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(serve_decode)/GPT.decode/kv_cache/slice"}
  %get-tuple-element.267 = bf16[2]{0} get-tuple-element((bf16[2]{0}, bf16[2]{0}) %fusion.2032), index=0
  %copy.777 = bf16[2]{0:T(8,128)(2,1)} copy(bf16[2]{0:T(8,128)(2,1)} %get-tuple-element.267)
  %copy.2 = bf16[2]{0} copy(%copy.777)
  %copy.1 = bf16[2]{0} copy(bf16[2]{0} %k)
  %add.5 = bf16[2]{0} add(%copy.2, %copy.1)
  ROOT %flash_decode.3 = bf16[2]{0} custom-call(bf16[2]{0} %copy.777), custom_call_target="tpu_custom_call", metadata={op_name="jit(serve_decode)/GPT.decode/h0/attn/flash_decode/pallas_call"}
}
"""
    module, table = scopes.table_from_text(text)
    assert module == "jit_serve_decode"
    assert table == {
        "k": None, "fusion.2032": "kv_cache",
        "get-tuple-element.267": "kv_cache*", "copy.777": "kv_cache*",
        "copy.2": "kv_cache*",
        "copy.1": None,          # moves a parameter: nothing to take
        "add.5": None,           # computes: no path, no scope
        "flash_decode.3": "attn"}


def test_scope_tables_are_read_from_the_live_executables_when_asked(
        tmp_path):
    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.telemetry import scopes

    def step_fn(x, w):
        with jax.named_scope("mlp"):
            h = jnp.tanh(x @ w)
        with jax.named_scope("loss"):
            return jnp.sum(h * h)

    jitted = jax.jit(step_fn)
    text = jitted.lower(jnp.ones((8, 8)), jnp.ones((8, 8))).compile() \
        .as_text()
    module, table = scopes.table_from_text(text)
    assert module == "jit_step_fn"
    assert {"mlp", "loss"} <= set(table.values())
    assert None in table.values()       # parameters: no path, still listed
    # nothing was registered anywhere: the program that ran is live, and
    # its text is read when a window asks
    jitted(jnp.ones((8, 8)), jnp.ones((8, 8)))
    assert scopes.tables()["jit_step_fn"] == table
    path = scopes.write_tables(str(tmp_path))
    with open(path) as f:
        doc = json.load(f)
    assert doc["scopes"] == list(scopes.SCOPES)
    assert doc["programs"]["jit_step_fn"] == table
    # the same name compiled for another shape: operations both place
    # alike stay, one they place differently is left out
    jitted(jnp.ones((4, 8)), jnp.ones((8, 8)))
    both = scopes.tables()["jit_step_fn"]
    assert {"mlp", "loss"} <= set(both.values())
    assert all(table[op] == scope for op, scope in both.items()
               if op in table)


def _run_once(name: str, n: int = 8):
    """Jit and run a small scoped function named ``name``: ``(the jitted
    function, its program, its table)``."""
    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.telemetry import scopes

    def fn(x, w):
        with jax.named_scope("mlp"):
            h = jnp.tanh(x @ w)
        with jax.named_scope("loss"):
            return jnp.sum(h * h)

    fn.__name__ = fn.__qualname__ = name
    jitted = jax.jit(fn)
    jitted(jnp.ones((n, 8)), jnp.ones((8, 8))).block_until_ready()
    program = "jit_" + name
    return jitted, program, scopes._live(scopes.SCOPES)[program]


def test_remembered_scope_tables_outlive_their_executable():
    import gc

    from ray_lightning_tpu.telemetry import scopes
    jitted, program, table = _run_once("outlives_fn")
    assert {"mlp", "loss"} <= set(table.values())
    scopes.remember()
    del jitted
    gc.collect()
    # the executable went with its jitted function ...
    assert program not in scopes._live(scopes.SCOPES)
    # ... and a reader that asks now still finds what ran, under both
    # lists of names
    assert scopes.tables()[program] == table
    assert program in scopes.tables(scopes.FINE_SCOPES)
    assert set(scopes.tables(scopes.FINE_SCOPES)[program].values()) == {None}


def test_a_live_program_wins_whole_over_a_remembered_one():
    from ray_lightning_tpu.telemetry import scopes
    jitted, program, table = _run_once("live_wins_fn")
    # what was remembered under this name is another tree's program:
    # nothing of it may show beside the live one's operations
    scopes._remembered.setdefault(tuple(scopes.SCOPES), {})[program] = {
        "fusion.stale": "attn", **dict.fromkeys(table, "embed")}
    assert scopes.tables()[program] == table
    # and a program that is NOT live is served from the store untouched
    scopes._remembered[tuple(scopes.SCOPES)]["jit_only_remembered"] = {
        "fusion.1": "mlp"}
    assert scopes.tables()["jit_only_remembered"] == {"fusion.1": "mlp"}
    del scopes._remembered[tuple(scopes.SCOPES)]["jit_only_remembered"]
    del jitted


def test_the_remembered_store_does_not_grow_over_fifty_rounds():
    """A program's remembered table is replaced, never added to: fifty
    programs of one name (fifty fits' ``jit_step_fn``), each with
    operations of its own shape, leave one table of the newest."""
    import gc

    from ray_lightning_tpu.telemetry import scopes
    sizes, programs = [], []
    for i in range(50):
        jitted, program, table = _run_once("round_fn", n=8 + 8 * (i % 5))
        scopes.remember()
        kept = scopes._remembered[tuple(scopes.SCOPES)]
        assert kept[program] == table          # the newest, whole
        sizes.append(len(kept[program]))
        programs.append(len(kept))
        del jitted
        gc.collect()
    assert set(scopes._remembered) == {tuple(scopes.SCOPES),
                                       tuple(scopes.FINE_SCOPES)}
    # as many programs kept at the end as after the first round, and the
    # table no longer than one program's
    assert programs[-1] == programs[0]
    assert max(sizes) <= 2 * min(sizes)


def test_session_seen_is_set_inside_a_profiler_session_and_cleared_by_asking(
        tmp_path):
    import jax

    from ray_lightning_tpu.telemetry import spans
    spans.session_seen()
    with telemetry.span("outside"):
        pass
    assert spans.session_seen() is False
    jax.profiler.start_trace(str(tmp_path))
    try:
        with telemetry.span("inside", step=1):
            pass
    finally:
        jax.profiler.stop_trace()
    assert spans.session_seen() is True
    assert spans.session_seen() is False       # asking cleared it


def test_trainer_remembers_its_programs_only_after_a_profiler_session(
        tmp_path, monkeypatch):
    """A fit that a profiler session lay over keeps its programs' tables
    at the end of the stage (one reading under each list of names); the
    next fit, with no session, parses nothing."""
    import jax

    from ray_lightning_tpu.core.callbacks import Callback
    from ray_lightning_tpu.telemetry import scopes, spans

    class Session(Callback):
        needs_batch = False

        def on_train_batch_end(self, trainer, module, metrics, batch, idx):
            if trainer.global_step == 2:
                jax.profiler.start_trace(str(tmp_path / "trace"))
            elif trainer.global_step == 4:
                jax.profiler.stop_trace()

    readings = []
    live = scopes._live
    monkeypatch.setattr(scopes, "_live",
                        lambda names: readings.append(names) or live(names))
    scopes._remembered.clear()
    spans.session_seen()

    def fit(callbacks):
        Trainer(max_epochs=1, limit_train_batches=6, callbacks=callbacks,
                enable_checkpointing=False, num_sanity_val_steps=0,
                limit_val_batches=0, telemetry=False,
                default_root_dir=str(tmp_path / "run")).fit(BoringModel())

    fit([Session()])
    assert readings == [scopes.SCOPES, scopes.FINE_SCOPES]
    assert "jit_step_fn" in scopes._remembered[tuple(scopes.SCOPES)]
    assert spans.session_seen() is False       # the trainer asked
    fit([])
    assert readings == [scopes.SCOPES, scopes.FINE_SCOPES]
    import gc
    gc.collect()        # the two fits' programs go with their trainers


def test_gpt_train_step_operations_fall_under_the_fixed_scopes():
    """Every instruction of the tiny GPT train step that carries a path
    at all carries a listed scope: models/gpt.py, ops/ and core/steps.py
    enter them, and a new unscoped region shows here before a chip run."""
    import re

    import jax

    from ray_lightning_tpu.core.steps import build_init_fn, build_train_step
    from ray_lightning_tpu.models.gpt import GPTLightningModule
    from ray_lightning_tpu.telemetry import scopes
    module = GPTLightningModule("tiny", batch_size=2)
    module.setup_model()
    tx = module.configure_optimizers()
    batch = next(iter(module.train_dataloader()))
    state = jax.eval_shape(build_init_fn(module, tx),
                           jax.random.PRNGKey(0), batch)
    text = jax.jit(build_train_step(module, tx)).lower(
        state, batch).as_text(debug_info=True)
    paths = set(re.findall(r'loc\("(jit\(step_fn\)/[^"]+)"', text))
    assert len(paths) > 50
    unscoped = sorted(p for p in paths if scopes.scope_of(p) is None)
    # what is left is the rng bookkeeping and the step counter
    assert all(re.search(r"step_fn\)/(jit\(_?\w+\)/)*[\w\[\]]+$", p)
               or "threefry" in p or "random" in p for p in unscoped), \
        unscoped
    assert {scopes.scope_of(p) for p in paths} >= {
        "embed", "attn", "mlp", "ln", "lm_head", "loss", "optimizer"}


def test_serve_programs_carry_their_names():
    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.serve.engine import ServeEngine
    eng = ServeEngine(None, None, (16, 32), 4, 64)
    for name in ("decode", "prefill_16", "kv_init"):
        fn = eng._counted(name, lambda x: x + 1)
        text = jax.jit(fn).lower(jnp.ones(())).as_text()
        assert f"module @jit_serve_{name} " in text, name
    assert eng.trace_counts == {"decode": 1, "prefill_16": 1, "kv_init": 1}


@pytest.mark.parametrize("which,names", [
    ("fwd", {"flash_fwd"}),
    ("bwd", {"flash_fwd", "flash_bwd_fused", "flash_bwd_dkv",
             "flash_bwd_dq"}),
    ("decode", {"flash_decode"}),
])
def test_kernels_carry_their_names_in_a_program_lowered_for_the_chip(
        which, names):
    import re

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"cannot describe a v5e topology: {e}")
    sh = SingleDeviceSharding(topo.devices[0])

    def sds(*shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sh)

    if which == "decode":
        from ray_lightning_tpu.ops.flash_decode import flash_decode_attention
        low = jax.jit(lambda q, k, v, pos: flash_decode_attention(
            q, k, v, pos, layer=0, interpret=False)).lower(
            sds(8, 1, 4, 64), sds(1, 8, 256, 4 * 64),
            sds(1, 8, 256, 4 * 64), sds(8, dt=jnp.int32))
    else:
        from ray_lightning_tpu.ops.flash_attention import flash_attention

        def fwd(q, k, v):
            return flash_attention(q, k, v, causal=True, interpret=False)

        def bwd(q, k, v):
            return jax.grad(lambda *a: fwd(*a).astype(jnp.float32).sum(),
                            argnums=(0, 1, 2))(q, k, v)

        x = sds(2, 256, 4, 64)
        low = jax.jit(fwd if which == "fwd" else bwd).lower(x, x, x)
    found = set(re.findall(r'kernel_name = "([^"]+)"', low.as_text()))
    assert found and found <= names, found
    assert "flash_fwd" in found or which == "decode"



def test_counter_and_last_span():
    telemetry.enable(rank=0, sink=None, flush_every=None)
    assert telemetry.last_span() is None
    with telemetry.span("compile"):
        assert telemetry.last_span() == "compile"
        telemetry.counter("hbm_mb", 12.5)
    recs = telemetry.drain()
    counters = [r for r in recs if r["t"] == "counter"]
    (c,) = counters
    assert c["name"] == "hbm_mb" and c["value"] == 12.5


def test_sink_batching_and_flush():
    batches = []
    telemetry.enable(rank=1, sink=batches.append, flush_every=2)
    with telemetry.span("a"):
        pass
    assert batches == []           # below the batch threshold
    with telemetry.span("b"):
        pass
    assert len(batches) == 1 and len(batches[0]) == 2
    with telemetry.span("c"):
        pass
    telemetry.flush()
    assert len(batches) == 2 and batches[1][0]["name"] == "c"


def test_ring_buffer_drops_oldest_never_grows():
    telemetry.enable(rank=0, sink=None, capacity=3, flush_every=None)
    for i in range(10):
        telemetry.counter("c", i)
    assert telemetry.dropped() == 7
    recs = telemetry.drain()
    assert [r["value"] for r in recs] == [7.0, 8.0, 9.0]


def test_failing_sink_never_raises_into_training():
    def bad_sink(batch):
        raise RuntimeError("sink down")

    telemetry.enable(rank=0, sink=bad_sink, flush_every=1)
    with telemetry.span("step"):   # must not raise
        pass
    telemetry.flush()


# -- aggregator ----------------------------------------------------------

def _span_rec(rank, name, ts, dur, **attrs):
    r = {"t": "span", "name": name, "ts": ts, "dur": dur, "rank": rank,
         "depth": 0}
    if attrs:
        r["attrs"] = attrs
    return r


def test_aggregator_merges_ranks_and_exports(tmp_path):
    agg = TelemetryAggregator(str(tmp_path / "telemetry"))
    # rank 1 is a 2x straggler
    for i in range(10):
        agg.maybe_ingest(telemetry.spans_item(
            0, [_span_rec(0, "step", 100.0 + i, 0.010)]))
        agg.maybe_ingest(telemetry.spans_item(
            1, [_span_rec(1, "step", 100.0 + i, 0.020)]))
    agg.ingest_records(0, [_span_rec(0, "compile", 99.0, 1.0)])
    stats = agg.step_stats()
    assert stats["per_rank"]["0"]["steps"] == 10
    assert stats["per_rank"]["1"]["mean_ms"] == pytest.approx(20.0)
    assert stats["straggler_skew"] == pytest.approx(2.0)

    paths = agg.export()
    with open(paths["trace"]) as f:
        trace = json.load(f)
    events = trace["traceEvents"]
    span_events = [e for e in events if e.get("ph") == "X"]
    assert {e["pid"] for e in span_events} == {0, 1}
    assert {"step", "compile"} <= {e["name"] for e in span_events}
    with open(paths["jsonl"]) as f:
        lines = [json.loads(line) for line in f]
    assert lines[-1]["t"] == "summary"
    assert lines[-1]["step_stats"]["straggler_skew"] == pytest.approx(2.0)
    assert {r.get("rank") for r in lines[:-1]} == {0, 1}


def test_aggregator_normalizes_chunked_steps(tmp_path):
    agg = TelemetryAggregator(str(tmp_path))
    # one span covering k=4 steps in 40ms -> 10ms/step
    agg.ingest_records(0, [_span_rec(0, "step", 10.0, 0.040, k=4)])
    assert agg.step_stats()["per_rank"]["0"]["mean_ms"] == \
        pytest.approx(10.0)


def test_non_telemetry_items_pass_through(tmp_path):
    agg = TelemetryAggregator(str(tmp_path))
    assert not agg.maybe_ingest({"some": "dict"})
    assert not agg.maybe_ingest((0, lambda: None))
    assert not agg.maybe_ingest("string")


def test_watchdog_names_silent_rank(tmp_path, caplog):
    clock = [0.0]
    agg = TelemetryAggregator(str(tmp_path), heartbeat_timeout=5.0,
                              clock=lambda: clock[0])
    agg.maybe_ingest(make_heartbeat(0))
    beat1 = make_heartbeat(1)
    beat1["pid"] = beat1["pid"] + 1   # distinct worker process
    beat1["last_span"] = "step"
    agg.maybe_ingest(beat1)
    clock[0] = 3.0
    agg.maybe_ingest(make_heartbeat(0))   # rank 0 keeps beating
    clock[0] = 7.0
    agg.maybe_ingest(make_heartbeat(0))
    with caplog.at_level(logging.WARNING,
                         logger="ray_lightning_tpu.telemetry.aggregator"):
        agg.watchdog_check()
    msgs = [r.message for r in caplog.records]
    assert any("rank 1" in m and "last span 'step'" in m for m in msgs)
    assert not any("rank 0:" in m for m in msgs)
    # warned once, not per poll iteration
    caplog.clear()
    with caplog.at_level(logging.WARNING,
                         logger="ray_lightning_tpu.telemetry.aggregator"):
        agg.watchdog_check()
    assert not caplog.records


def test_watchdog_hard_timeout_raises(tmp_path):
    clock = [0.0]
    agg = TelemetryAggregator(str(tmp_path), heartbeat_timeout=1.0,
                              hard_timeout=5.0, clock=lambda: clock[0])
    agg.maybe_ingest(make_heartbeat(2))
    clock[0] = 6.0
    with pytest.raises(WorkerHeartbeatTimeout, match="rank 2"):
        agg.watchdog_check()


# -- per-request tracing (telemetry/tracing.py) --------------------------

def test_trace_id_round_trip_driver_worker_aggregator(tmp_path):
    """THE trace-propagation round-trip: a driver-side request span and
    worker-side spans carrying the same trace id (the single ``trace``
    attr and the decode's ``traces`` fan-out map) reassemble into ONE
    time-ordered tree in the aggregator — exactly the id flow of a
    serve request (driver plan broadcast -> worker span batch -> queue
    -> aggregator)."""
    agg = TelemetryAggregator(str(tmp_path))
    telemetry.set_active(agg)
    # worker-side recorder whose sink delivers like the queue channel
    telemetry.enable(
        rank=0,
        sink=lambda recs: agg.maybe_ingest(telemetry.spans_item(0, recs)),
        flush_every=1)
    tid = tracing.mint_trace_id()
    sibling = tracing.mint_trace_id()
    t0 = time.time()
    # driver: queue-wait phase (thread-ambient active aggregator)
    tracing.record_request_span("queue_wait", t0 - 0.3, t0 - 0.1,
                                trace=tid, tenant="alice", req=0)
    # worker: per-bucket prefill + one shared decode over two requests
    with telemetry.span("prefill", trace=tid, bucket=16, slot=2):
        pass
    with telemetry.span("decode", traces={2: tid, 3: sibling}, slots=2):
        pass
    # driver: completion summary span carrying the attribution
    tracing.record_request_span("request", t0 - 0.3, t0 + 0.2,
                                trace=tid, tenant="alice", status="ok",
                                tokens=8, queue_s=0.2, ttft_s=0.25,
                                tpot_s=0.03)
    trees = agg.request_trees()
    assert set(trees) == {tid, sibling}
    names = [r["name"] for r in trees[tid]]
    assert names[0] in ("queue_wait", "request")     # same start ts
    assert set(names) == {"queue_wait", "request", "prefill", "decode"}
    # one tree spans BOTH sides of the queue channel
    assert {r["rank"] for r in trees[tid]} == {-1, 0}
    # the shared decode span fans out to the sibling's tree too
    assert [r["name"] for r in trees[sibling]] == ["decode"]
    # and the per-tenant breakdown attributes the phases
    bd = agg.tenant_breakdown()["alice"]
    assert bd["requests"] == 1 and bd["tokens"] == 8
    assert bd["queue_wait_p50_ms"] == pytest.approx(200.0, abs=1.0)
    assert bd["ttft_p50_ms"] == pytest.approx(250.0, abs=1.0)
    assert bd["decode_p50_ms"] == pytest.approx(250.0, abs=1.0)
    assert bd["prefill_p50_ms"] is not None
    # the exported summary carries the trace-plane section
    paths = agg.export()
    assert paths["summary"]["requests"]["traced"] == 2
    assert "alice" in paths["summary"]["requests"]["tenants"]


def test_tenant_breakdown_counts_failed_requests(tmp_path):
    agg = TelemetryAggregator(str(tmp_path))
    t0 = time.time()
    agg.ingest_records(-1, [
        tracing.span_record("request", t0, t0 + 1.0, trace="aaaa",
                            tenant="bob", status="ok", tokens=4,
                            ttft_s=0.5, queue_s=0.1),
        tracing.span_record("request", t0, t0 + 2.0, trace="bbbb",
                            tenant="bob", status="failed", tokens=0,
                            ttft_s=2.0, queue_s=2.0)])
    bd = agg.tenant_breakdown()["bob"]
    assert bd["requests"] == 2 and bd["failed"] == 1
    # failed requests participate in the percentiles (optimism fix)
    assert bd["ttft_p99_ms"] == pytest.approx(2000.0, abs=1.0)


# -- crash flight recorder (telemetry/flight.py) -------------------------

def test_flight_recorder_bounded_and_dumps(tmp_path):
    fr = FlightRecorder(str(tmp_path), span_capacity=8, beat_capacity=3)
    for i in range(100):
        fr.note_records(2, [{"t": "span", "name": f"step{i}",
                             "ts": float(i), "dur": 0.01, "rank": 2}])
        fr.note_heartbeat({"rank": 2, "pid": 1, "wall": float(i),
                           "last_span": f"step{i}", "dropped": 0})
    # bounded-size invariant: rings never exceed capacity
    assert len(fr._records[2]) == 8 and len(fr._beats[2]) == 3
    path = fr.dump(2, "unit-test cause")
    assert path == str(tmp_path / "flight_2.json")
    doc = json.load(open(path))
    assert doc["rank"] == 2 and doc["cause"] == "unit-test cause"
    assert doc["last_span"] == "step99"      # newest records survive
    assert len(doc["spans"]) == 8
    assert doc["heartbeats"][-1]["last_span"] == "step99"
    assert fr.dumped[2] == path


def test_aggregator_mirrors_into_flight_and_watchdog_dumps(tmp_path):
    """A wedge verdict dumps the rank's black box: the watchdog's first
    warning for a silent rank writes flight_<rank>.json with its last
    spans and heartbeat trail."""
    clock = [0.0]
    agg = TelemetryAggregator(str(tmp_path), heartbeat_timeout=5.0,
                              clock=lambda: clock[0])
    agg.ingest_records(1, [{"t": "span", "name": "step", "ts": 100.0,
                            "dur": 0.02, "rank": 1, "depth": 0}])
    beat = make_heartbeat(1)
    agg.maybe_ingest(beat)
    clock[0] = 10.0
    agg.watchdog_check()
    path = tmp_path / "flight_1.json"
    assert path.exists()
    doc = json.load(open(path))
    assert doc["rank"] == 1
    assert "wedge" in doc["cause"]
    assert doc["last_span"] == "step"
    assert doc["heartbeats"], "heartbeat trail missing from black box"


# -- on-demand profiling (POST /debug/profile) ---------------------------

def test_debug_profile_endpoint_and_status(tmp_path):
    """POST /debug/profile arms a window on the controller; /status
    links its state and, with the serve pump's hooks driven, the
    resulting dir."""
    import urllib.request
    from ray_lightning_tpu.telemetry import exporter as _exporter
    from ray_lightning_tpu.telemetry.tracing import ServeProfileController

    agg = TelemetryAggregator(str(tmp_path))
    ctl = ServeProfileController(str(tmp_path))
    server = _exporter.MetricsHTTPServer(agg, port=0,
                                         profile_controller=ctl).start()
    try:
        req = urllib.request.Request(
            server.url + "/debug/profile?steps=2", method="POST")
        with urllib.request.urlopen(req, timeout=5) as r:
            resp = json.loads(r.read())
        assert resp["accepted"] and resp["steps"] == 2
        # a second POST while armed is rejected with 409
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(
                urllib.request.Request(
                    server.url + "/debug/profile?steps=1",
                    method="POST"), timeout=5)
        assert exc.value.code == 409
        # drive the pump hooks: claim the window, count its steps
        pending = ctl.take_pending()
        assert pending["id"] == resp["id"]
        ctl.note_step()
        ctl.note_step()
        with urllib.request.urlopen(server.url + "/status",
                                    timeout=5) as r:
            status = json.loads(r.read())
        assert status["profile"]["state"] == "done"
        assert status["profile"]["last_dir"] == resp["dir"]
    finally:
        server.stop()


def test_debug_profile_without_controller_is_501(tmp_path):
    import urllib.request
    from ray_lightning_tpu.telemetry import exporter as _exporter
    agg = TelemetryAggregator(str(tmp_path))
    server = _exporter.MetricsHTTPServer(agg, port=0).start()
    try:
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(
                urllib.request.Request(
                    server.url + "/debug/profile?steps=1",
                    method="POST"), timeout=5)
        assert exc.value.code == 501
    finally:
        server.stop()


def test_fit_profile_control_file_round_trip(tmp_path, monkeypatch):
    """The fit path's arm: FileProfileController writes the control
    file, the loop-engine poller (profile_tick) picks it up from the
    env, captures a real jax.profiler window, and drops the rank done
    marker /status reports."""
    control = str(tmp_path / "profile" / "control.json")
    ctl = tracing.FileProfileController(control)
    assert ctl.status() == {"state": "idle"}
    resp = ctl.request(1)
    assert resp["accepted"] and os.path.exists(control)
    monkeypatch.setenv(tracing.PROFILE_CONTROL_ENV, control)
    monkeypatch.setenv("RLT_PROCESS_ID", "0")
    tracing.reset_profile_tick()
    try:
        tracing.profile_tick()       # polls the file, starts the trace
        tracing.profile_tick()       # counts the step, stops + marks
        status = ctl.status()
        assert status["state"] == "done", status
        assert status["ranks_done"] == ["rank0"]
        trace_dir = os.path.join(resp["dir"], "rank0")
        found = [os.path.join(dp, f) for dp, _, fs in os.walk(trace_dir)
                 for f in fs]
        assert found, "profiler window wrote no trace files"
    finally:
        tracing.reset_profile_tick()


# -- anatomy plane (telemetry/anatomy.py) --------------------------------

def test_status_names_a_capture_it_cannot_parse(tmp_path):
    """A window's capture that cannot be parsed used to be a missing
    ``anatomy`` field on ``/status``; now the reason is there."""
    from ray_lightning_tpu.telemetry.anatomy import (
        profile_dir_anatomy, write_synthetic_trace)
    ctl = tracing.ServeProfileController(str(tmp_path))
    ctl.last_dir = str(tmp_path / "profile" / "w1")
    os.makedirs(os.path.join(ctl.last_dir, "rank0"))     # empty capture
    status = ctl.status()
    assert "anatomy" not in status
    assert "rank0" in status["anatomy_error"] or "trace" in \
        status["anatomy_error"].lower()
    with pytest.raises(Exception):
        profile_dir_anatomy(ctl.last_dir)
    assert profile_dir_anatomy(str(tmp_path / "nowhere")) is None
    # a capture that parses is linked as before
    ctl.last_dir = str(tmp_path / "profile" / "w2")
    write_synthetic_trace(
        os.path.join(ctl.last_dir, "rank0"),
        ops=[{"name": "fusion.1", "ts": 0.0, "dur": 900.0}],
        modules=[{"name": "jit_step_fn", "ts": 0.0, "dur": 1000.0}])
    assert "0" in ctl.status()["anatomy"]


def test_anatomy_parses_real_capture(tmp_path, monkeypatch):
    """A REAL profiler capture (via the fit control-file machinery, the
    same path POST /debug/profile arms) parses into a StepAnatomy whose
    parts are nonnegative and sum to <= the step wall, and the
    controller's status links the parsed anatomy next to last_dir."""
    import jax
    import jax.numpy as jnp
    from ray_lightning_tpu.telemetry import anatomy

    control = str(tmp_path / "profile" / "control.json")
    ctl = tracing.FileProfileController(control)
    resp = ctl.request(3)
    monkeypatch.setenv(tracing.PROFILE_CONTROL_ENV, control)
    monkeypatch.setenv("RLT_PROCESS_ID", "0")
    tracing.reset_profile_tick()
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    try:
        tracing.profile_tick()       # polls the file, starts the trace
        for _ in range(3):           # real device work INSIDE the window
            f(x).block_until_ready()
            tracing.profile_tick()
    finally:
        tracing.reset_profile_tick()
    status = ctl.status()
    assert status["state"] == "done", status

    a = anatomy.parse_trace_anatomy(os.path.join(resp["dir"], "rank0"))
    assert a.steps >= 1 and a.devices >= 1
    assert a.compute_s >= 0 and a.collective_s >= 0
    assert a.exposed_s >= 0 and a.host_s >= 0
    # the decomposition identity: parts sum to the step wall (tiny
    # epsilon: the compact dict rounds to nanoseconds)
    assert a.compute_s + a.exposed_s + a.host_s <= a.wall_s + 1e-8
    assert a.compute_s > 0, "no compute measured from a real capture"
    # the controller's status links the parsed anatomy per rank
    assert "anatomy" in status, status
    assert status["anatomy"]["0"]["compute_s"] > 0
    # Pallas decode-kernel events land in compute, not comm: the
    # category table files them under pallas/custom and the collective
    # classifier (the one the anatomy parser consults) rejects them.
    from ray_lightning_tpu.comm import audit
    assert anatomy.bucket_of(
        "flash_decode_kernel.12") == "pallas/custom"
    assert anatomy.bucket_of(
        "flash_decode_paged_kernel") == "pallas/custom"
    assert audit.collective_kind("flash_decode_kernel.12") is None
    assert audit.collective_kind(
        "custom-call.flash_decode_paged_kernel") is None
    assert audit.collective_kind("all-gather.7") == "all-gather"


def test_anatomy_golden_overlap_math(tmp_path):
    """The golden synthetic fixture pins the exposed-comm interval
    math: fully-overlapped -> ~0 exposed, serialized -> exposed ≈
    collective; partial overlap measures exactly the uncovered part."""
    from ray_lightning_tpu.telemetry import anatomy

    serial = tmp_path / "serial"
    anatomy.write_synthetic_trace(str(serial), ops=[
        {"name": "fusion.1", "ts": 0, "dur": 10_000},
        {"name": "all-reduce.1", "ts": 10_000, "dur": 4_000},
    ], modules=[{"name": "jit_step", "ts": 0, "dur": 14_000}])
    a = anatomy.parse_trace_anatomy(str(serial), steps=1, ici_size=1,
                                    multi_process=False)
    assert a.exposed_s == pytest.approx(0.004)
    assert a.collective_s == pytest.approx(0.004)
    assert a.collective_by_op == {"all-reduce": pytest.approx(0.004)}
    assert a.collective_by_link == {"ici": pytest.approx(0.004)}
    assert a.wall_s == pytest.approx(
        a.compute_s + a.exposed_s + a.host_s)

    overlapped = tmp_path / "overlapped"
    anatomy.write_synthetic_trace(str(overlapped), ops=[
        {"name": "fusion.1", "ts": 0, "dur": 10_000},
        {"name": "all-reduce.1", "ts": 2_000, "dur": 4_000},
    ])
    a = anatomy.parse_trace_anatomy(str(overlapped), steps=1, ici_size=1,
                                    multi_process=True)
    assert a.exposed_s == 0.0
    assert a.collective_s == pytest.approx(0.004)
    # group-less collective on a multi-process mesh charges DCN
    assert a.collective_by_link == {"dcn": pytest.approx(0.004)}

    partial = tmp_path / "partial"
    anatomy.write_synthetic_trace(str(partial), ops=[
        {"name": "fusion.1", "ts": 0, "dur": 10_000},
        {"name": "all-reduce.1", "ts": 8_000, "dur": 4_000},
    ])
    a = anatomy.parse_trace_anatomy(str(partial), steps=1, ici_size=1,
                                    multi_process=False)
    assert a.exposed_s == pytest.approx(0.002)   # [10ms, 12ms) uncovered


def test_anatomy_replica_groups_decide_link(tmp_path):
    """A collective event whose args carry the lowered HLO's
    replica_groups is classified by comm/audit.py's crosses_dcn, not
    the topology fallback: groups inside one 2-rank host block -> ici
    even on a multi-process mesh."""
    from ray_lightning_tpu.telemetry import anatomy

    d = tmp_path / "groups"
    anatomy.write_synthetic_trace(str(d), ops=[
        {"name": "fusion.1", "ts": 0, "dur": 5_000},
        {"name": "all-reduce.2", "ts": 5_000, "dur": 1_000,
         "args": {"long_name": "all-reduce(f32[8]), "
                               "replica_groups={{0,1},{2,3}}"}},
        {"name": "all-reduce.3", "ts": 6_000, "dur": 2_000,
         "args": {"long_name": "all-reduce(f32[8]), "
                               "replica_groups={{0,2},{1,3}}"}},
    ])
    a = anatomy.parse_trace_anatomy(str(d), steps=1, ici_size=2,
                                    multi_process=True)
    assert a.collective_by_link["ici"] == pytest.approx(0.001)
    assert a.collective_by_link["dcn"] == pytest.approx(0.002)


def test_anatomy_ingest_status_flight_and_export(tmp_path):
    """Anatomy wire items land on the aggregator: /status gains the
    per-rank section with straggler skew, the export summary carries
    it, and a flight dump names where the rank's device time went."""
    from ray_lightning_tpu.telemetry import anatomy
    from ray_lightning_tpu.telemetry import exporter as _exporter

    agg = TelemetryAggregator(str(tmp_path))
    a0 = {"steps": 2, "devices": 1, "wall_s": 0.010, "compute_s": 0.006,
          "collective_s": 0.004, "exposed_s": 0.003, "host_s": 0.001,
          "collective_by_op": {"all-reduce": 0.004},
          "collective_by_link": {"dcn": 0.004},
          "bubble_fraction": 0.1, "modules": {}, "source": "cpu-host"}
    a1 = dict(a0, wall_s=0.020)      # rank 1 is a 2x straggler
    assert agg.maybe_ingest(anatomy.anatomy_item(0, a0))
    assert agg.maybe_ingest(anatomy.anatomy_item(1, a1))
    stats = agg.anatomy_stats()
    assert set(stats["per_rank"]) == {"0", "1"}
    assert stats["windows"] == 2
    assert stats["straggler_skew"] == pytest.approx(2.0)
    doc = _exporter.render_status(agg)
    assert doc["anatomy"]["per_rank"]["1"]["wall_s"] == 0.020
    paths = agg.export()
    assert paths["summary"]["anatomy"]["straggler_skew"] == \
        pytest.approx(2.0)
    dump = agg.flight.dump(1, "unit-test cause")
    assert json.load(open(dump))["anatomy"]["wall_s"] == 0.020


def test_anatomy_controller_cadence_and_gauges(tmp_path):
    """The auto-capture controller: every_n dispatches arm a window
    through the WorkerProfiler machinery, the rank parses its OWN
    capture, ships only the compact dict, and publishes the
    rlt_anatomy_* gauges + the measured exposed-comm source label."""
    import jax
    import jax.numpy as jnp
    from ray_lightning_tpu.telemetry import anatomy
    from ray_lightning_tpu.telemetry import metrics as _metrics

    reg = _metrics.enable_metrics(rank=0, sink=None, pump=False)
    shipped = []
    ctl = telemetry.enable_anatomy(rank=0, every_n=2, window=2,
                                   sink=shipped.append)
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((32, 32))
    f(x).block_until_ready()
    for _ in range(6):               # ticks 2..4 arm + close one window
        telemetry.anatomy_tick()
        f(x).block_until_ready()
    assert ctl.windows >= 1, "no anatomy window completed"
    assert shipped, "anatomy dict was not shipped"
    item = shipped[0]
    assert item["kind"] == "anatomy" and item["rank"] == 0
    a = item["anatomy"]
    assert a["compute_s"] > 0
    assert a["compute_s"] + a["exposed_s"] + a["host_s"] \
        <= a["wall_s"] + 1e-8
    # teardown abandons the in-flight second window and removes its
    # capture dir — only compact dicts ever leave the rank
    inflight = ctl._dir
    telemetry.disable_anatomy()
    assert ctl._dir is None
    assert inflight is None or not os.path.isdir(inflight)
    assert reg.gauge("rlt_anatomy_compute_seconds").value() == \
        pytest.approx(a["compute_s"])
    assert reg.counter("rlt_anatomy_windows_total").value() >= 1
    # measured exposed feeds the comm gauge under the anatomy source
    assert reg.gauge("rlt_comm_exposed_seconds").value(
        source="anatomy") == pytest.approx(a["exposed_s"])


def test_anatomy_config_env_roundtrip(monkeypatch):
    from ray_lightning_tpu.telemetry import TelemetryConfig, anatomy

    for var in (anatomy.ANATOMY_ENV, anatomy.ANATOMY_EVERY_ENV,
                anatomy.ANATOMY_STEPS_ENV):
        monkeypatch.delenv(var, raising=False)
    assert TelemetryConfig().resolved_anatomy()[0] is None
    assert TelemetryConfig().worker_env() == {}
    cfg = TelemetryConfig(anatomy_every_n_steps=10, anatomy_steps=3)
    assert cfg.resolved_anatomy() == (10, 3)
    env = cfg.worker_env()
    assert env == {anatomy.ANATOMY_EVERY_ENV: "10",
                   anatomy.ANATOMY_STEPS_ENV: "3"}
    # a worker's default config resolves the same cadence from the env
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    assert TelemetryConfig().resolved_anatomy() == (10, 3)
    monkeypatch.delenv(anatomy.ANATOMY_EVERY_ENV)
    monkeypatch.delenv(anatomy.ANATOMY_STEPS_ENV)
    monkeypatch.setenv(anatomy.ANATOMY_ENV, "1")
    assert TelemetryConfig().resolved_anatomy() == \
        (anatomy.DEFAULT_EVERY_N, anatomy.DEFAULT_WINDOW)


def test_local_fit_with_anatomy_armed(tmp_path, seed):
    """An in-process fit with the cadence armed lands a measured
    per-rank anatomy in the exported summary."""
    trainer = Trainer(max_epochs=1, limit_train_batches=8,
                      limit_val_batches=0, num_sanity_val_steps=0,
                      enable_checkpointing=False, seed=0,
                      log_every_n_steps=1, default_root_dir=str(tmp_path),
                      telemetry={"anatomy_every_n_steps": 2,
                                 "anatomy_steps": 2})
    trainer.fit(BoringModel())
    summary = trainer._telemetry_paths["summary"]
    assert "anatomy" in summary, "no anatomy in export summary"
    a = summary["anatomy"]["per_rank"]["0"]
    assert a["compute_s"] >= 0 and a["exposed_s"] >= 0
    assert a["compute_s"] + a["exposed_s"] + a["host_s"] \
        <= a["wall_s"] + 1e-8
    # controller torn down with the rest of telemetry
    assert telemetry.get_anatomy_controller() is None


# -- spans where the work happens, telemetry off ---------------------------

SETUP_SPANS_FIT = {"setup_model", "loaders", "mesh", "compile", "init",
                   "first_step"}
SETUP_SPANS_SERVE = {"spawn", "rendezvous", "ship", "worker_setup",
                     "setup_serve", "imports", "devices", "weights",
                     "build", "warmup", "warm", "kv_init"}


def _tree(records, root_name):
    """``(root, {name: [records]})`` of the newest root's descendants."""
    root = [r for r in records if r["name"] == root_name][-1]
    kids = {}
    for r in records:
        kids.setdefault(r.get("parent"), []).append(r)
    under, todo = {}, [root]
    while todo:
        for c in kids.get(todo.pop()["id"], []):
            under.setdefault(c["name"], []).append(c)
            todo.append(c)
    return root, under


def test_fit_leaves_every_setup_span_under_one_root(tmp_path, seed):
    from ray_lightning_tpu.telemetry import spans
    trainer = Trainer(max_epochs=1, limit_train_batches=3,
                      limit_val_batches=0, num_sanity_val_steps=0,
                      enable_checkpointing=False, seed=0,
                      default_root_dir=str(tmp_path), telemetry=False)
    trainer.fit(BoringModel())
    assert trainer._telemetry_paths is None and not telemetry.enabled()
    records = spans.kept("fit_setup")
    assert spans._windows == ()
    assert [r["name"] for r in records].count("fit_setup") == 1
    root, under = _tree(records, "fit_setup")
    assert root["parent"] is None
    assert SETUP_SPANS_FIT <= set(under), SETUP_SPANS_FIT - set(under)
    for name in SETUP_SPANS_FIT:
        assert under[name][0]["parent"] == root["id"], name
    # the first step's dispatch and the wait for its result are inside
    first = under["first_step"][0]
    assert {r["parent"] for r in under["step"]} == {first["id"]}
    assert under["step"][0]["attrs"] == {"step": 0}
    assert under["device_wait"][0]["parent"] == first["id"]
    # the window closed with the first step: the loop's later steps are
    # not kept (3 batches ran, one step span is here)
    assert len(under["step"]) == 1
    end = root["ts"] + root["dur"]
    assert all(r["ts"] >= root["ts"] - 1e-6
               and r["ts"] + r["dur"] <= end + 1e-6
               for rs in under.values() for r in rs)


@pytest.fixture(scope="module")
def tiny_server(tmp_path_factory):
    """One tiny CPU server with ``telemetry=False``, driven through a
    handful of requests with an on-demand profile window on its third
    plan: what the serve-side tests below read."""
    import numpy as np

    from ray_lightning_tpu.models.gpt import GPTLightningModule
    from ray_lightning_tpu.serve import Server
    from ray_lightning_tpu.telemetry import spans
    tmp = tmp_path_factory.mktemp("tiny_server")
    server = Server(GPTLightningModule("tiny"), checkpoint=None,
                    platform="cpu", buckets=(16, 32), max_batch_slots=4,
                    max_new_tokens=8, telemetry=False,
                    # the AOT thread runs with the compile cache only
                    compile_cache=str(tmp / "compile_cache"),
                    default_root_dir=str(tmp / "root")).start()
    try:
        sched, window = server.scheduler, {"plans": 0}
        plain_plan = sched.plan

        def plan():
            p = plain_plan()
            if p is not None:
                window["plans"] += 1
                if window["plans"] == 3:
                    p["profile"] = {"id": "t", "steps": 4,
                                    "dir": str(tmp / "prof")}
                    window["first_step"] = server.scheduler.pump.steps
            return p

        sched.plan = plan
        reqs = [server.submit(np.arange(1, 9 + i, dtype=np.int32),
                              max_new_tokens=8) for i in range(6)]
        for r in reqs:
            r.result(timeout=120)
        time.sleep(1.0)       # idle iterations are pump time too
        stats = server.stats()
    finally:
        server.shutdown()
    return {"stats": stats,
            "records": spans.kept("server_start") + spans.kept("pump"),
            "prof": str(tmp / "prof"), "first_step": window["first_step"],
            "after": server.scheduler.stats()}


def test_pump_phases_add_up_to_the_pumps_wall_time(tiny_server):
    pump = tiny_server["stats"]["scheduler"]["pump"]
    phases = sum(pump[k + "_s"] for k in
                 ("loop", "plan", "call", "wait", "apply", "idle"))
    assert pump["steps"] >= 10 and pump["idle_s"] > 0.5
    assert phases == pytest.approx(pump["wall_s"], rel=0.05)
    # the worker's own seconds are inside call + wait: the rest is RPC
    assert 0 < pump["worker_s"] < pump["call_s"] + pump["wait_s"]
    # stopped with the pump: the clock does not run on after shutdown
    done = tiny_server["after"]["pump"]
    assert done["wall_s"] == pytest.approx(
        sum(done[k + "_s"] for k in
            ("loop", "plan", "call", "wait", "apply", "idle")), rel=0.05)


def test_server_start_leaves_every_setup_span_under_one_root(tiny_server):
    records = tiny_server["records"]
    assert [r["name"] for r in records].count("server_start") == 1
    root, under = _tree(records, "server_start")
    assert root["parent"] is None
    assert SETUP_SPANS_SERVE <= set(under), SETUP_SPANS_SERVE - set(under)
    for name in ("spawn", "rendezvous", "ship", "worker_setup"):
        assert under[name][0]["parent"] == root["id"], name
    # the worker's own spans came back with setup_serve's result and
    # hang under the driver's wait for it, renumbered
    worker_root = under["setup_serve"][0]
    assert worker_root["parent"] == under["worker_setup"][0]["id"]
    for name in ("imports", "devices", "weights", "build", "warmup",
                 "kv_init"):
        assert all(r["parent"] == worker_root["id"]
                   for r in under[name]), name
    warmed = {r["attrs"]["program"] for r in under["warm"]}
    assert warmed == {"kv_init", "prefill_16", "prefill_32", "decode"}
    assert all(r["parent"] == under["warmup"][0]["id"]
               for r in under["warm"])
    assert len({r["id"] for r in records}) == len(records)
    # one host, one wall clock: the worker's spans lie inside the wait
    wait = under["worker_setup"][0]
    assert wait["ts"] - 0.05 <= worker_root["ts"] and \
        worker_root["ts"] + worker_root["dur"] <= \
        wait["ts"] + wait["dur"] + 0.05


def test_profile_window_keeps_the_pumps_spans_with_the_workers_steps(
        tiny_server):
    """The pump is not in the captured process: its spans of the
    window's steps are kept in its own, and the step number on the plan
    ties them to the worker's ``rlt/serve_step`` annotations."""
    first = tiny_server["first_step"]
    window = list(range(first, first + 4))
    pump = [r for r in tiny_server["records"]
            if r["name"].startswith("pump.")]
    by_name = {}
    for r in pump:
        by_name.setdefault(r["name"], []).append(r["attrs"]["step"])
    for name in ("pump.call", "pump.wait", "pump.apply"):
        assert sorted(by_name[name]) == window, name
    # the window opens on the plan that carries it: that step's root and
    # plan span were already behind it
    assert sorted(by_name["pump.step"]) == window[1:]
    assert sorted(by_name["pump.plan"]) == window[1:]
    roots = {r["attrs"]["step"]: r["id"] for r in pump
             if r["name"] == "pump.step"}
    assert all(r["parent"] == roots[r["attrs"]["step"]] for r in pump
               if r["name"] != "pump.step"
               and r["attrs"]["step"] in roots)
    host = _host_plane(tiny_server["prof"])
    steps = sorted(stats["step"] for _, _, stats in host["rlt/serve_step"])
    assert steps == window
    assert {"rlt/clock", "rlt/decode", "rlt/prefill", "rlt/dispatch",
            "rlt/fetch"} <= set(host)
    # anchor applied, the worker's step lies between the moment the pump
    # sent the call and the moment its wait returned (on a loaded box the
    # worker may start before the pump thread gets as far as the wait)
    a_start, _, a_stats = max(host["rlt/clock"],
                              key=lambda a: a[0])   # the last one made
    offset = a_stats["wall_ns"] * 1e-9 - a_start
    calls, waits = ({r["attrs"]["step"]: r for r in pump
                     if r["name"] == name}
                    for name in ("pump.call", "pump.wait"))
    for start, dur, stats in host["rlt/serve_step"]:
        c, w = calls[stats["step"]], waits[stats["step"]]
        assert c["ts"] - 1e-3 <= start + offset
        assert start + offset + dur <= w["ts"] + w["dur"] + 1e-3


def test_profile_window_writes_the_scope_table_beside_the_trace(
        tiny_server):
    path = os.path.join(tiny_server["prof"], "rank0", "op_scopes.json")
    with open(path) as f:
        doc = json.load(f)
    programs = doc["programs"]
    assert {"jit_serve_decode", "jit_serve_prefill_16",
            "jit_serve_prefill_32"} <= set(programs)
    assert {"attn", "mlp", "ln", "embed", "lm_head", "kv_cache",
            "sample"} <= set(programs["jit_serve_decode"].values())
    assert set(programs["jit_serve_decode"].values()) - {None} \
        <= set(doc["scopes"])



# -- trainer integration -------------------------------------------------

def test_local_fit_exports_trace(tmp_path, seed):
    trainer = Trainer(max_epochs=1, limit_train_batches=4,
                      limit_val_batches=2, num_sanity_val_steps=0,
                      enable_checkpointing=True, seed=0,
                      log_every_n_steps=1, default_root_dir=str(tmp_path),
                      telemetry=True)
    trainer.fit(BoringModel())
    paths = trainer._telemetry_paths
    assert paths is not None
    with open(paths["trace"]) as f:
        trace = json.load(f)
    names = {e["name"] for e in trace["traceEvents"]
             if e.get("ph") == "X"}
    assert {"step", "compile", "init", "data_wait", "eval",
            "checkpoint"} <= names
    assert paths["summary"]["step_stats"]["per_rank"]["0"]["steps"] == 4
    # recorder must be torn down after the run
    assert not telemetry.enabled()
    assert telemetry.get_active() is None


def test_telemetry_disabled_records_nothing(tmp_path, seed):
    trainer = Trainer(max_epochs=1, limit_train_batches=2,
                      limit_val_batches=0, num_sanity_val_steps=0,
                      enable_checkpointing=False, seed=0,
                      default_root_dir=str(tmp_path))
    trainer.fit(BoringModel())
    assert trainer._telemetry_paths is None
    assert not os.path.exists(os.path.join(str(tmp_path), "telemetry"))


def test_config_resolution():
    from ray_lightning_tpu.telemetry import TelemetryConfig
    assert not TelemetryConfig.resolve(None).enabled
    assert TelemetryConfig.resolve(True).enabled
    cfg = TelemetryConfig.resolve({"heartbeat_timeout": 7.5})
    assert cfg.enabled and cfg.heartbeat_timeout == 7.5
    assert TelemetryConfig.resolve(cfg) is cfg
    with pytest.raises(TypeError):
        TelemetryConfig.resolve(3)
    assert cfg.resolve_dir("/root/x") == "/root/x/telemetry"


def test_per_trial_dir_resolution(tmp_path):
    """Inside a builtin tune trial, telemetry lands in the trial's own
    logdir (tune/runner.py Trial.telemetry_dir contract)."""
    from ray_lightning_tpu.telemetry import TelemetryConfig
    from ray_lightning_tpu.tune.runner import Trial
    from ray_lightning_tpu.tune.session import TrialSession, set_session
    trial = Trial("trial_00000", {}, str(tmp_path / "trial_00000"))
    set_session(TrialSession(trial, lambda *a: None))
    try:
        cfg = TelemetryConfig.resolve(True)
        assert cfg.resolve_dir("/elsewhere") == trial.telemetry_dir
    finally:
        set_session(None)


# -- end-to-end over the cluster backend --------------------------------

@pytest.mark.slow
def test_e2e_two_workers_spans_from_both_ranks(tmp_path, seed):
    """2-worker local-backend fit: the driver aggregator must see
    step/compile/collective spans from BOTH ranks and export a
    Perfetto-loadable trace.json."""
    trainer = Trainer(max_epochs=1, limit_train_batches=4,
                      limit_val_batches=0, num_sanity_val_steps=0,
                      enable_checkpointing=False, seed=0,
                      log_every_n_steps=1, plugins=[cpu_plugin(2)],
                      default_root_dir=str(tmp_path),
                      telemetry={"heartbeat_interval": 0.5,
                                 "anatomy_every_n_steps": 2,
                                 "anatomy_steps": 2})
    trainer.fit(BoringModel())

    # anatomy acceptance: with the cadence armed, BOTH ranks parsed a
    # real capture locally and the driver's summary carries per-rank
    # measured step anatomy (the same dict /status serves live)
    anatomy = trainer._telemetry_paths["summary"].get("anatomy")
    assert anatomy and set(anatomy["per_rank"]) == {"0", "1"}, anatomy
    for rank, a in anatomy["per_rank"].items():
        assert a["compute_s"] >= 0 and a["exposed_s"] >= 0
        assert a["compute_s"] + a["exposed_s"] + a["host_s"] \
            <= a["wall_s"] + 1e-8, (rank, a)
        # the 2-process data axis all-reduce is measured and, being
        # group-less across hosts, charged to the DCN link
        assert "all-reduce" in a["collective_by_op"], (rank, a)
        assert a["collective_by_link"].get("dcn", 0) > 0, (rank, a)

    paths = trainer._telemetry_paths
    assert paths is not None
    with open(paths["trace"]) as f:
        trace = json.load(f)          # valid JSON by construction
    span_events = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
    by_rank = {}
    for e in span_events:
        by_rank.setdefault(e["pid"], set()).add(e["name"])
    assert set(by_rank) == {0, 1}
    for rank, names in by_rank.items():
        assert {"step", "compile", "collective"} <= names, \
            f"rank {rank} missing spans: {names}"

    with open(paths["jsonl"]) as f:
        lines = [json.loads(line) for line in f]
    summary = lines[-1]
    assert summary["t"] == "summary"
    per_rank = summary["step_stats"]["per_rank"]
    assert set(per_rank) == {"0", "1"}
    assert per_rank["0"]["steps"] == 4 and per_rank["1"]["steps"] == 4
    # both workers heartbeat over the queue channel
    hb = trainer.plugin._telemetry_agg.heartbeats()
    assert {v["beat"]["rank"] for v in hb.values()} == {0, 1}
