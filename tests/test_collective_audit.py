"""Compiled-collective audit: the sharding claims that matter on a pod,
asserted over the ACTUAL lowered programs on the 8-virtual-device mesh
(VERDICT r3 next #3).

Round 3 asserted these in docstrings; this file asserts them against
``jit(...).lower(...).compile()`` — op kinds, element types, and
per-device argument bytes — so a strategy that silently degrades to the
wrong collective, loses its sharding, or widens a buffer to fp32 fails
CI instead of shipping a pod-scale regression no single-chip bench can
see.

Audited facts (current XLA CPU lowering; shapes/bytes are
backend-independent sharding truth, op *formation* can vary by backend
pass pipeline — reduce-scatter creation is such a pass, which is why
the ZeRO-1 assertion accepts all-reduce + dynamic-slice as the summed
grads' spelling):

- DDP: grads cross-replica summed (all-reduce), params NEVER gathered
  (they are replicated), full-size optimizer buffers.
- ZeRO-1: optimizer buffers 1/N per device, each rank slices its grad
  shard, updated params re-assembled by all-gather.
- FSDP: params also 1/N; all-gathers at use sites (strictly more than
  ZeRO-1's single post-update gather).
- Gradient collectives ride at f32 — the partitioner resolves partial
  sums at the f32-accumulating grad dots, before the bf16 cotangent
  cast (a bf16 all-reduce here would be a silent numerics change; a
  f64 one a silent widening — both fail this audit).

Reference anchor: SURVEY.md §2.2 FairScale row (reduce-scatter /
all-gather is the stated parity mechanism, ray_ddp_sharded.py:17-34).
"""

from __future__ import annotations

import re

import jax
import numpy as np
import pytest

from ray_lightning_tpu.comm import CommPolicy
from ray_lightning_tpu.comm.audit import (collective_defs,
                                          collective_wire_bytes)
from ray_lightning_tpu.core.steps import build_init_fn, build_train_step
from ray_lightning_tpu.models.gpt import GPTLightningModule
from ray_lightning_tpu.parallel.strategy import resolve_strategy

BATCH = 16


def _compiled(strategy, comm_policy=None, module=None, **module_kw):
    """Compile the real train step under ``strategy`` (optionally with
    an active comm policy, replicating the trainer's wiring: resolved
    GradSync, wrapped tx, residual shardings fixup)."""
    strat = resolve_strategy(strategy) if isinstance(strategy, str) \
        else strategy
    if module is None:
        module = GPTLightningModule("tiny", dataset_size=4 * BATCH,
                                    batch_size=BATCH, **module_kw)
    module.setup_model()
    tx = module.configure_optimizers()
    mesh = strat.build_mesh(batch_hint=BATCH)
    comm = strat.grad_transform(mesh, comm_policy) \
        if comm_policy is not None else None
    if comm is not None:
        tx = comm.wrap_tx(tx)
    batch = jax.tree_util.tree_map(
        np.asarray, next(iter(module.train_dataloader())))
    abstract = jax.eval_shape(build_init_fn(module, tx),
                              jax.random.PRNGKey(0), batch)
    shardings = strat.state_shardings(mesh, abstract)
    if comm is not None:
        shardings = shardings.replace(
            opt_state=comm.fix_opt_shardings(shardings.opt_state,
                                             abstract.opt_state))
    jitted = jax.jit(build_train_step(module, tx, grad_sync=comm),
                     donate_argnums=0,
                     in_shardings=(shardings,
                                   strat.batch_shardings(mesh, batch)),
                     out_shardings=(shardings, None))
    return mesh, jitted.lower(abstract, batch).compile()


@pytest.fixture(scope="module")
def programs():
    """One compile per strategy, shared by every assertion below."""
    out = {}
    for name in ("ddp", "zero1", "fsdp"):
        mesh, comp = _compiled(name)
        assert dict(mesh.shape)["data"] == 8, "audit needs the full mesh"
        out[name] = {
            "text": comp.as_text(),
            "args": comp.memory_analysis().argument_size_in_bytes,
        }
    return out


def _count(text: str, op: str) -> int:
    """Occurrences of collective-op DEFINITIONS (async start variants
    count once; `-done` and get-tuple-element references do not)."""
    return len(re.findall(rf"= \(?[a-z0-9]+\[[^)]*?\]\S* {op}(?:-start)?\(",
                          text))


def _def_dtypes(text: str, op: str) -> set:
    """Element types produced by ``op`` definitions (tuple or scalar)."""
    out = set()
    for m in re.finditer(rf"= (\(?)([a-z0-9]+)\[[^)]*?\]\S* {op}", text):
        if m.group(1):   # tuple type: collect every element type inside
            span = text[m.start():text.index(")", m.start())]
            out.update(re.findall(r"([a-z0-9]+)\[", span))
        else:
            out.add(m.group(2))
    return out


def test_ddp_allreduces_grads_and_never_gathers_params(programs):
    t = programs["ddp"]["text"]
    assert _count(t, "all-reduce") > 0, "DDP lost its gradient psum"
    assert _count(t, "all-gather") == 0, (
        "DDP program gathers something — params/opt must be replicated")
    assert _count(t, "reduce-scatter") == 0


def test_zero1_shards_update_and_gathers_params(programs):
    t = programs["zero1"]["text"]
    # summed grads: either a literal reduce-scatter or the partitioner's
    # all-reduce + per-rank dynamic-slice spelling
    rs = _count(t, "reduce-scatter")
    assert rs > 0 or (_count(t, "all-reduce") > 0
                      and t.count("dynamic-slice") > 0), (
        "ZeRO-1 lost the sharded-update pattern entirely")
    assert _count(t, "all-gather") > 0, (
        "ZeRO-1 must re-assemble updated params with an all-gather")


def test_fsdp_gathers_params_at_use_sites(programs):
    ag_fsdp = _count(programs["fsdp"]["text"], "all-gather")
    ag_zero1 = _count(programs["zero1"]["text"], "all-gather")
    assert ag_fsdp > ag_zero1 > 0, (
        f"FSDP should gather params at use sites (fwd+bwd): "
        f"{ag_fsdp} vs zero1's {ag_zero1}")


def test_grad_allreduce_rides_f32(programs):
    """The cross-replica grad sum must stay f32: bf16 would silently
    change numerics (summing rounded partials), f64 silently widen the
    dominant collective (module docstring, ops/optim.py)."""
    for name in ("ddp", "zero1", "fsdp"):
        types = _def_dtypes(programs[name]["text"], "all-reduce")
        assert types and types <= {"f32"}, (
            f"{name}: gradient all-reduce element types {types} != f32")


def test_per_device_state_bytes_order(programs):
    """The memory story IS the point of the sharded strategies: per
    device, fsdp (params+opt sharded) < zero1 (opt sharded) < ddp
    (everything replicated).  A lost sharding annotation collapses one
    of these gaps."""
    ddp = programs["ddp"]["args"]
    zero1 = programs["zero1"]["args"]
    fsdp = programs["fsdp"]["args"]
    assert fsdp < zero1 < ddp, (ddp, zero1, fsdp)
    # opt state (f32 master + bf16 mu + f32 nu ≈ 5 bytes/param) dwarfs
    # bf16 params; sharding it 8-way should reclaim well over half
    assert zero1 < 0.45 * ddp, (zero1, ddp)
    # fsdp shards the bf16 params too
    assert fsdp < 0.75 * zero1, (fsdp, zero1)


def test_tensor_parallel_psums_forward(programs):
    """Megatron-style tensor parallelism: row-parallel matmuls produce
    partial activations that MUST be psum'd in the forward pass — a
    tensor-sharded program with no all-reduce is silently computing
    garbage.  Params shard on the tensor axis, so per-device state
    bytes drop vs DDP."""
    from ray_lightning_tpu.models.gpt import gpt_partition_rules
    from ray_lightning_tpu.parallel.strategy import SpmdStrategy

    strat = SpmdStrategy(rules=gpt_partition_rules(),
                         axis_names=("data", "tensor"),
                         axis_sizes={"tensor": 2})
    mesh, comp = _compiled(strat)
    assert dict(mesh.shape) == {"data": 4, "tensor": 2}
    assert _count(comp.as_text(), "all-reduce") > 0
    assert comp.memory_analysis().argument_size_in_bytes \
        < 0.8 * programs["ddp"]["args"]


# ---------------------------------------------------------------------------
# compressed collectives (comm/): dtype + wire-byte audit
# ---------------------------------------------------------------------------

INT8_POLICY = CommPolicy(compress="int8", axes=("data",))


@pytest.fixture(scope="module")
def compressed(programs):
    """The int8-compressed ddp/zero1 programs (one compile each)."""
    out = {}
    for name in ("ddp", "zero1"):
        _mesh, comp = _compiled(name, comm_policy=INT8_POLICY)
        out[name] = {"text": comp.as_text()}
    return out


def _wire(text):
    return collective_wire_bytes(text, axis_size=8)


def test_compressed_ddp_reduction_bytes(programs, compressed):
    """With comm=int8 on the data axis, the DDP grad reduction rides
    s8 all-to-all + all-gather and the program's total collective wire
    bytes drop >= 3.5x vs the fp32 all-reduce (the acceptance bar; the
    residue above 4x is the fp32 per-block scales)."""
    fp = _wire(programs["ddp"]["text"])
    q = _wire(compressed["ddp"]["text"])
    assert ("all-to-all", "s8") in q and ("all-gather", "s8") in q, q
    # the fp32 gradient all-reduce is gone (only epsilon-sized scalar
    # psums remain: loss/logged means)
    assert q.get(("all-reduce", "f32"), 0) < 1024
    ratio = sum(fp.values()) / sum(q.values())
    assert ratio >= 3.5, (ratio, fp, q)


def test_compressed_zero1_grad_phase_bytes(programs, compressed):
    """ZeRO-1's grad reduce-scatter (+ its all-gather leg) carries >=
    3.5x fewer bytes compressed.  The updated-param all-gather is
    unchanged between legs (param_gather="none"), so subtracting the
    fp32 leg's f32 all-gather isolates the grad phases."""
    fp = _wire(programs["zero1"]["text"])
    q = _wire(compressed["zero1"]["text"])
    assert ("all-to-all", "s8") in q and ("all-gather", "s8") in q, q
    param_gather_f32 = fp.get(("all-gather", "f32"), 0) \
        + fp.get(("all-gather", "bf16"), 0)
    grad_fp = fp[("all-reduce", "f32")]
    grad_q = sum(q.values()) - param_gather_f32 \
        - q.get(("all-reduce", "f32"), 0)
    assert grad_fp / grad_q >= 3.5, (grad_fp, grad_q, fp, q)


HIER_POLICY = CommPolicy(compress="int8", axes=("data",), hierarchy=4)


@pytest.fixture(scope="module")
def hierarchical(programs):
    """The two-level (ici4 x dcn2) int8 ddp/zero1 programs."""
    out = {}
    for name in ("ddp", "zero1"):
        _mesh, comp = _compiled(name, comm_policy=HIER_POLICY)
        out[name] = {"text": comp.as_text()}
    return out


@pytest.mark.parametrize("name", ["ddp", "zero1"])
def test_hierarchical_dcn_bytes_vs_flat_int8(compressed, hierarchical,
                                             name):
    """THE tentpole pin: on a 2-level (ici4 x dcn2) split of the 8-way
    mesh, the hierarchical program's DCN-crossing compressed payload is
    >= 2x below the flat-int8 path's (the flat collectives span all 8
    ranks, so every compressed byte crosses hosts; the hierarchical
    level-2 phases move a 1/ici shard).  Audited over the lowered HLO's
    replica groups — a lost ``axis_index_groups`` (everything suddenly
    full-span) fails here, not on a pod."""
    from ray_lightning_tpu.comm.audit import wire_bytes_by_link

    qdt = ("s8", "u8")
    flat = wire_bytes_by_link(compressed[name]["text"], ici_size=4,
                              axis_size=8, dtypes=qdt)
    hier = wire_bytes_by_link(hierarchical[name]["text"], ici_size=4,
                              axis_size=8, dtypes=qdt)
    assert flat["dcn"] > 0 and hier["dcn"] > 0, (flat, hier)
    assert flat["ici"] == 0, flat    # flat program: all spans cross
    assert 2 * hier["dcn"] <= flat["dcn"], (hier, flat)


def test_hierarchical_ici_phases_stay_fp32(hierarchical):
    """The EQuARX trade in the lowered program: the hierarchical ddp
    step moves fp32 INSIDE the ICI groups (levels 1/3 — the fast link
    carries full precision) while the compressed dtype appears only on
    host-crossing groups."""
    from ray_lightning_tpu.comm.audit import wire_bytes_by_link

    t = hierarchical["ddp"]["text"]
    f32 = wire_bytes_by_link(t, ici_size=4, axis_size=8, dtypes=("f32",),
                             ops=("all-to-all", "all-gather"))
    assert f32["ici"] > 0, f32
    q = wire_bytes_by_link(t, ici_size=4, axis_size=8, dtypes=("s8", "u8"))
    assert q["ici"] == 0, q          # codec never rides the fast tier


def test_fp8_program_rides_one_byte_wire():
    """The fp8 codec's collectives must move a 1-byte element type (the
    u8 bitcast) — an f16-widened wire (what a raw f8 collective lowers
    to on CPU) would silently double the DCN bytes."""
    _mesh, comp = _compiled(
        "ddp", comm_policy=CommPolicy(compress="fp8", axes=("data",)))
    wire = collective_wire_bytes(comp.as_text(), axis_size=8)
    assert ("all-to-all", "u8") in wire and ("all-gather", "u8") in wire, \
        wire
    assert not any(dt == "f16" for _op, dt in wire), wire


def test_int4_program_halves_the_payload():
    """int4's packed wire: the all-to-all payload is half the element
    count, so total compressed bytes land >= 1.6x under the int8 leg's
    (scales are the fixed overhead)."""
    _mesh, comp8 = _compiled("ddp", comm_policy=INT8_POLICY)
    _mesh, comp4 = _compiled(
        "ddp", comm_policy=CommPolicy(compress="int4", axes=("data",)))
    qdt = ("s8", "u8")
    b8 = sum(b for (op, dt), b in
             collective_wire_bytes(comp8.as_text(), axis_size=8).items()
             if dt in qdt)
    b4 = sum(b for (op, dt), b in
             collective_wire_bytes(comp4.as_text(), axis_size=8).items()
             if dt in qdt)
    assert b4 * 1.6 <= b8, (b4, b8)


def _without_locations(text: str) -> str:
    """HLO text less where in the SOURCE each operation was traced: the
    four tables under the header (file names, function names, locations,
    stack frames) and each operation's index into them.  The frames are
    the Python stack at trace time, pytest's own among them: a fixture
    and a test body trace the same program under different stacks.
    Operation names, scopes, shapes and layouts stay."""
    text = re.sub(r"^(?:FileNames|FunctionNames|FileLocations|StackFrames)"
                  r"\n(?:\d+ .*\n)*\n", "", text, flags=re.M)
    return re.sub(r" ?stack_frame_id=\d+", "", text)


def test_comm_policy_off_is_bit_identical(programs):
    """The resolved-but-off policy (compress="none") routes through the
    comm-aware wiring and must produce the IDENTICAL program — every
    operation, name, shape and layout; default behavior is today's
    build."""
    _mesh, comp = _compiled("ddp", comm_policy=CommPolicy())
    assert _without_locations(comp.as_text()) \
        == _without_locations(programs["ddp"]["text"])


def test_zero1_param_gather_compresses():
    """param_gather="int8" re-routes the updated-param all-gather
    through the quantize→replicate sandwich: the s8 all-gather appears
    and the full-precision param-sized gather disappears (boring model:
    one [32, 2] dense layer, cheap compile)."""
    from ray_lightning_tpu.models import BoringModel

    def boring():
        return BoringModel(batch_size=BATCH)

    _m, comp_fp = _compiled("zero1", module=boring())
    _m, comp_q = _compiled(
        "zero1", module=boring(),
        comm_policy=CommPolicy(compress="int8", axes=("data",),
                               param_gather="int8"))
    fp = _wire(comp_fp.as_text())
    q = _wire(comp_q.as_text())
    assert all(dt != "s8" for _op, dt in fp), fp
    assert ("all-gather", "s8") in q
    # full-precision gather traffic is reduced to scale-sized f32 rows —
    # strictly smaller than the s8 payload it describes
    assert q.get(("all-gather", "f32"), 0) < q[("all-gather", "s8")]


# ---------------------------------------------------------------------------
# ring attention + pipeline (VERDICT #5): the other compiled collectives
# ---------------------------------------------------------------------------


def test_ring_attention_collective_permute_bytes():
    """Ring attention rotates K/V with collective-permute — per hop one
    LOCAL block of O(T/N · D) bytes, never an all-gather of the full
    sequence — and its traced byte note matches the schedule model
    (ring-1 rotations x global K+V)."""
    from ray_lightning_tpu.parallel.mesh import build_device_mesh
    from ray_lightning_tpu.parallel.ring import ring_attention
    from ray_lightning_tpu.telemetry.metrics import (disable_metrics,
                                                     enable_metrics)

    mesh = build_device_mesh(("data", "sequence"),
                             {"data": 1, "sequence": 8})
    ring = 8
    b, t, h, d = 2, 64, 2, 8
    aval = jax.ShapeDtypeStruct((b, t, h, d), np.float32)
    reg = enable_metrics(rank=0, sink=None, pump=False)
    try:
        comp = jax.jit(
            lambda q, k, v: ring_attention(q, k, v, mesh=mesh)).lower(
                aval, aval, aval).compile()
        traced = reg.traced_bytes.get("ring")
    finally:
        disable_metrics()
    text = comp.as_text()
    hop_bytes = b * (t // ring) * h * d * 4      # one f32 K or V block
    cps = [x for x in collective_defs(text)
           if x[0] == "collective-permute"]
    assert len(cps) == 2 * (ring - 1), len(cps)  # K and V per rotation
    assert all(nbytes == hop_bytes for _op, _dt, nbytes in cps), cps
    assert _count(text, "all-gather") == 0, (
        "ring must rotate blocks, not gather the sequence")
    # schedule model: (ring-1) rotations move the global K+V once each
    kv_bytes = 2 * (b * t * h * d * 4)
    assert traced == (ring - 1) * kv_bytes


def test_pipeline_collective_permute_matches_microbatch_schedule():
    """The pipeline's cross-stage transfer is one collective-permute of
    exactly one microbatch activation block (B_local/M rows), and its
    traced byte note matches the GPipe schedule: S stages x (M+S-1)
    time steps x (x_bytes/M) per hop + the final psum broadcast."""
    import jax.numpy as jnp

    from ray_lightning_tpu.parallel.mesh import build_device_mesh
    from ray_lightning_tpu.parallel.pipeline import pipeline_forward
    from ray_lightning_tpu.telemetry.metrics import (disable_metrics,
                                                     enable_metrics)

    mesh = build_device_mesh(("data", "stage"), {"data": 2, "stage": 4})
    S, M, L, F, B = 4, 2, 4, 8, 16

    def stage_fn(p, h):
        return jnp.tanh(h @ p)

    reg = enable_metrics(rank=0, sink=None, pump=False)
    try:
        comp = jax.jit(
            lambda params, x: pipeline_forward(
                stage_fn, params, x, n_microbatches=M, mesh=mesh)).lower(
            jax.ShapeDtypeStruct((L, F, F), np.float32),
            jax.ShapeDtypeStruct((B, F), np.float32)).compile()
        traced = reg.traced_bytes.get("pipeline")
    finally:
        disable_metrics()
    text = comp.as_text()
    mb_bytes = (B // 2 // M) * F * 4     # per-data-shard microbatch, f32
    cps = [x for x in collective_defs(text)
           if x[0] == "collective-permute"]
    assert cps, "pipeline lost its cross-stage ppermute"
    assert all(nbytes == mb_bytes for _op, _dt, nbytes in cps), cps
    # the last stage's outputs broadcast with a psum (not a ppermute
    # chain); its payload is the stacked microbatch outputs
    assert _count(text, "all-reduce") > 0
    x_bytes = B * F * 4
    assert traced == S * (M + S - 1) * x_bytes // M + x_bytes
