"""The comparison that decides ``correct``: what the timed path produced
against the configuration's plain reference, each number beside a limit
of its own (``chipbench/limits/<workload>.json``, set from readings that
PERF.md records: above the largest that sound runs gave, below the
smallest that the lower-precision control gave).

Served model: for every request the window finished, the reference runs
once over prompt + served tokens, and the numbers are the widest and the
mean gap by which a served (greedy) token's reference logit lies below
the reference's best at that position.  Exactly the same tokens give 0;
a near-tie flipped by bf16 rounding gives a small gap; a wrong cache row,
mask, position or weight gives a gap of the order of the logits' spread.

Training: the reference follows the timed object's first three steps
with a plain float32 AdamW.  Compared: each step's loss; per parameter
leaf, the norm of the first gradient as the optimizer got it (read back
from its first moment after one step) and the norm of the master
parameters' change after three steps.  A leaf's gap is the difference of
the two norms over the reference's norm of that leaf or of the median
leaf, whichever is larger.

The control (``precision="fp8"``, the step below the configuration's
bfloat16) puts the reference in the program's place.  ``run.py`` never
runs it; ``chipbench/tests`` and ``python -m chipbench.control`` do.
"""

from __future__ import annotations

import importlib.util
import json
import os
import statistics

import numpy as np


def load_reference(config_doc: dict, root: str):
    path = os.path.join(root, config_doc["reference"])
    spec = importlib.util.spec_from_file_location(
        "chipbench_reference_" + config_doc["name"].replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_limits(root: str, workload: str) -> dict:
    with open(os.path.join(root, "chipbench", "limits",
                           workload + ".json")) as f:
        return {k: v for k, v in json.load(f).items()
                if not k.startswith("_")}


def verdict(numbers: dict, limits: dict) -> tuple[bool, list[dict]]:
    """Every number named in the limits must be present and within its
    limit; each comparison is returned for printing."""
    rows = []
    for name, limit in sorted(limits.items()):
        value = numbers.get(name)
        ok = value is not None and np.isfinite(value) and value <= limit
        rows.append({"number": name, "value": value, "limit": limit,
                     "ok": bool(ok)})
    return all(r["ok"] for r in rows) and bool(rows), rows


# -- served model ----------------------------------------------------------

ROWS_PER_BLOCK = 8


def served_positions(ref, weights, model: dict, finished: list,
                     also: tuple = (), *, context: int,
                     rows_per_block: int = ROWS_PER_BLOCK) -> dict:
    """``finished`` is [(prompt ids, served ids)]: every request the
    window finished.  The reference runs once over each prompt with its
    served tokens, ``rows_per_block`` requests of ``context`` positions
    (the family's, ``adapter.context``) to a call, and is handed
    ``weights`` as the family's ``make_weights`` returned them.  It yields
    one entry per served token: ``gap``, how far the served token's reference
    logit lies below the reference's best there, and ``request``, whose
    token it was.  For every precision in ``also`` (the control's, by
    hand) the same mathematics runs again on lower-precision operands
    over the same rows, and ``gap_<precision>`` is the gap of the token
    IT puts first."""
    import jax
    import jax.numpy as jnp

    T = int(context)

    @jax.jit
    def block(w, tokens, targets):
        logits = ref.forward(w, tokens, model)
        best = jnp.max(logits, axis=-1)
        out = [best - jnp.take_along_axis(
            logits, targets[..., None], axis=-1)[..., 0]]
        for precision in also:
            first = jnp.argmax(ref.forward(w, tokens, model, precision),
                               axis=-1)
            out.append(best - jnp.take_along_axis(
                logits, first[..., None], axis=-1)[..., 0])
        return out

    names = ["gap"] + ["gap_" + p for p in also]
    cols = {k: [] for k in names + ["request"]}
    for at in range(0, len(finished), rows_per_block):
        rows = finished[at:at + rows_per_block]
        tokens = np.zeros((rows_per_block, T), np.int32)
        targets = np.zeros((rows_per_block, T), np.int32)
        mask = np.zeros((rows_per_block, T), bool)
        for r, (prompt, served) in enumerate(rows):
            P, A = len(prompt), len(served)
            tokens[r, :P] = prompt
            tokens[r, P:P + A - 1] = served[:-1]
            targets[r, P - 1:P + A - 1] = served
            mask[r, P - 1:P + A - 1] = True
        got = block(weights, tokens, targets)
        for k, a in zip(names, got):
            cols[k].append(np.asarray(a)[mask])
        cols["request"].append(at + np.nonzero(mask)[0])
    return {k: (np.concatenate(v) if v else np.zeros(0))
            for k, v in cols.items()}


def served_numbers(positions: dict) -> dict:
    """The numbers compared, from ``served_positions``.  A served greedy
    token differs from the float32 reference's best only where a rounding
    flips a near-tie: rare events (the bf16 program: 7-45 of the ~7700
    tokens a window serves), so every token of the window is read.  The
    mean gap is what the lower precision moves most (the control reads
    15x the sound runs' largest); the widest gap is what one wrong token
    in one slot moves.  The share of tokens off the best is printed, not
    judged: the control reads only twice the sound runs' largest."""
    n = int(positions["gap"].size)
    if not n:
        return {}
    out = {"tokens_compared": n}
    for key, gap in positions.items():
        if not key.startswith("gap"):
            continue
        tag = key[4:] + "_" if key != "gap" else ""
        out[tag + "logit_gap"] = float(gap.max())
        out[tag + "mean_logit_gap"] = float(gap.mean())
        out[tag + "not_best_share"] = float((gap > 0).mean())
    return out


# -- training ----------------------------------------------------------------

def leaf_norms(tree: dict, axes) -> dict:
    """L2 norms of a reference-layout dict's tensors (arrays, still on
    the device), each over the axes that the family's ``axes(name,
    array)`` names (``adapter.leaf_norm_axes``): what is left is one norm
    per leaf of the program's tree."""
    import jax.numpy as jnp
    return {k: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)),
                                axis=axes(k, a)))
            for k, a in tree.items()}


def train_reference(ref, weights: dict, model: dict, job: dict, rows,
                    precision: str = "float32", place=None, *,
                    axes) -> dict:
    """Three plain AdamW steps from ``weights`` on the job's first three
    batches.  Returns the losses, the first gradient's leaf norms and
    the leaf norms of the parameters' change (reference layout, over
    ``axes``: ``leaf_norms``).

    ``place`` (a multi-chip cell's) constrains every parameter-shaped
    tree to the sharding the weights came in, so that the float32
    parameters, gradients and both moments of a model that no single chip
    holds are spread over the cell's chips; the mathematics is the same."""
    import jax
    import jax.numpy as jnp

    place = place or (lambda tree: tree)

    opt = job["optimizer"]
    lr, wd = float(opt["lr"]), float(opt["weight_decay"])
    b1, b2, eps = float(opt["b1"]), float(opt["b2"]), float(opt["eps"])
    warmup = int(opt["warmup_steps"])
    B, block = int(job["global_batch"]), int(job["reference_rows_per_block"])
    x, y = rows

    @jax.jit
    def grad(w, xb, yb):
        loss, g = jax.value_and_grad(
            lambda w: ref.loss(w, xb, yb, model, precision))(w)
        return loss, place(g)

    @jax.jit
    def accumulate(total, part):
        return place(jax.tree_util.tree_map(jnp.add, total, part))

    @jax.jit
    def adamw(w, g, m, v, t):
        step_lr = lr * jnp.minimum(t / warmup, 1.0)
        m = jax.tree_util.tree_map(lambda a, b: b1 * a + (1 - b1) * b, m, g)
        v = jax.tree_util.tree_map(
            lambda a, b: b2 * a + (1 - b2) * b * b, v, g)
        c1, c2 = 1 - b1 ** (t + 1), 1 - b2 ** (t + 1)
        w = jax.tree_util.tree_map(
            lambda p, a, b: p - step_lr * (
                (a / c1) / (jnp.sqrt(b / c2) + eps) + wd * p), w, m, v)
        return place(w), place(m), place(v)

    w0 = weights
    w = w0
    m = jax.tree_util.tree_map(jnp.zeros_like, w)
    v = jax.tree_util.tree_map(jnp.zeros_like, w)
    losses, first_grad = [], None
    for t in range(3):
        total, loss_sum = None, 0.0
        for r in range(t * B, (t + 1) * B, block):
            loss, g = grad(w, x[r:r + block], y[r:r + block])
            loss_sum += float(loss) * block / B
            total = g if total is None else accumulate(total, g)
        g = jax.tree_util.tree_map(lambda a: a * (block / B), total)
        if t == 0:
            first_grad = leaf_norms(g, axes)
        losses.append(loss_sum)
        w, m, v = adamw(w, g, m, v, jnp.float32(t))
    change = leaf_norms(jax.tree_util.tree_map(jnp.subtract, w, w0), axes)
    return {"losses": losses, "grad_norms": first_grad,
            "change_norms": change}


def worst_leaf_gap(program: dict, reference: dict) -> float:
    """Both are {leaf: norm}.  The largest | program - reference | over
    max(reference, median reference)."""
    floor = statistics.median(float(v) for v in reference.values())
    worst = 0.0
    for name, r in reference.items():
        r = float(r)
        gap = abs(float(program[name]) - r) / max(r, floor)
        worst = max(worst, gap)
    return worst


def train_numbers(program: dict, reference: dict) -> dict:
    """``program`` and ``reference``: {"losses": [3], "grad_norms":
    {leaf: norm}, "change_norms": {leaf: norm}} with the same leaves."""
    return {
        "loss_gap": max(abs(p - r) / abs(r) for p, r in
                        zip(program["losses"], reference["losses"])),
        "grad_norm_gap": worst_leaf_gap(program["grad_norms"],
                                        reference["grad_norms"]),
        "change_norm_gap": worst_leaf_gap(program["change_norms"],
                                          reference["change_norms"]),
    }
