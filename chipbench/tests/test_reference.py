"""Each configuration's plain reference against the program's model at a
tiny size on the CPU, and the control that the comparison has to fail."""

import dataclasses
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import check
from chipbench.adapters import gpt2
from chipbench.adapters.gpt2 import BenchModule, to_program_tree
from chipbench.module import init_key, leaves

from conftest import ROOT, TINY

CONFIGS = ["gpt2-small", "gpt2-large"]
MODEL = {**TINY, "layer_norm_epsilon": 1e-6}


def _reference(name):
    with open(os.path.join(ROOT, "chipbench", "configs", name + ".json")) as f:
        doc = json.load(f)
    return check.load_reference(doc, ROOT), doc


def _weights(seed):
    return gpt2.make_weights(MODEL, init_key("serve", seed))


def _tokens(shape, seed=0):
    return np.random.default_rng(seed).integers(
        0, MODEL["vocab_size"], shape).astype(np.int32)


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_agrees_with_the_programs_model_in_float32(name):
    from ray_lightning_tpu.models.gpt import GPT
    ref, _ = _reference(name)
    module = BenchModule(MODEL, 5)
    params = module.init_params(init_key("serve", 5), None)["params"]
    tokens = _tokens((2, 48))
    want = ref.forward(_weights(5), tokens, MODEL)
    cfg = dataclasses.replace(module.config, dtype=jnp.float32, remat=False)
    got = GPT(cfg).apply({"params": params}, tokens)
    # float32 on both sides, summation order aside
    assert float(jnp.max(jnp.abs(want - got))) < 1e-5
    assert float(jnp.std(want)) > 0.05


@pytest.mark.parametrize("name", CONFIGS)
def test_configuration_file_names_its_reference_and_its_cuts(name):
    ref, doc = _reference(name)
    assert ref.PRECISIONS == ("float32", "bfloat16", "fp8")
    assert sorted(doc["reduced"]) == sorted(doc["reduced_why"])
    for key, published in doc["published"].items():
        assert doc["model"][key] != published and key in doc["reduced"]
    shapes = gpt2.shapes(doc["model"])
    assert shapes["qkv_w"] == (doc["model"]["n_layer"],
                               doc["model"]["n_embd"],
                               3 * doc["model"]["n_embd"])


def test_weights_follow_the_seed_and_take_the_drivers_large_seeds():
    a, b, c = _weights(2 ** 31 + 11), _weights(2 ** 31 + 11), _weights(11)
    assert any((a[k] != gpt2.make_weights(
        MODEL, init_key("train", 2 ** 31 + 11))[k]).any() for k in a)
    assert all((a[k] == b[k]).all() for k in a)
    assert any((a[k] != c[k]).any() for k in a)
    tree = leaves(to_program_tree(a))
    assert tree["h1/attn/qkv/kernel"].shape == (64, 192)
    assert len(tree) == 4 + 2 + 12 * MODEL["n_layer"] - 2


@pytest.mark.parametrize("name", CONFIGS)
def test_served_control_fp8_fails_where_bfloat16_passes(name):
    """The served comparison, at every position of the same sequences:
    the token that a bfloat16 computation puts first lies close under the
    reference's best; the fp8 control's lies at least three times further
    below, in the widest and in the mean gap, and limits between the two
    pass one and fail the other."""
    ref, _ = _reference(name)
    w = _weights(3)
    rows = _tokens((8, 64), seed=4)
    samples = [(r[:1], list(r[1:])) for r in rows]
    got = check.served_numbers(check.served_positions(
        ref, w, MODEL, samples, also=("bfloat16", "fp8"),
        context=gpt2.context(MODEL)))
    assert got["tokens_compared"] == 8 * 63
    sound = {k: got["bfloat16_" + k] for k in ("logit_gap", "mean_logit_gap")}
    control = {k: got["fp8_" + k] for k in ("logit_gap", "mean_logit_gap")}
    assert all(control[k] > 3 * sound[k] and control[k] > 0 for k in sound)
    limits = {k: (3 * sound[k] + control[k]) / 4 for k in sound}
    assert check.verdict(sound, limits)[0]
    bad, compared = check.verdict(control, limits)
    assert not bad and not any(row["ok"] for row in compared)
    assert not check.verdict({}, limits)[0]      # no number is no pass


def test_training_control_fp8_fails_where_bfloat16_passes():
    ref, _ = _reference("gpt2-small")
    job = {"global_batch": 4, "reference_rows_per_block": 2,
           "optimizer": {"lr": 3e-4, "weight_decay": 0.01,
                         "warmup_steps": 10, "b1": 0.9, "b2": 0.95,
                         "eps": 1e-8}}
    w = _weights(7)
    x = _tokens((12, 64), seed=1)
    y = _tokens((12, 64), seed=2)

    def numbers(precision):
        got = check.train_reference(ref, w, MODEL, job, (x, y), precision,
                                    axes=gpt2.leaf_norm_axes)
        return {"losses": got["losses"],
                "grad_norms": {k: float(v) for k, v in leaves(
                    to_program_tree(got["grad_norms"])).items()},
                "change_norms": {k: float(v) for k, v in leaves(
                    to_program_tree(got["change_norms"])).items()}}

    exact = numbers("float32")
    sound = check.train_numbers(numbers("bfloat16"), exact)
    control = check.train_numbers(numbers("fp8"), exact)
    assert control["grad_norm_gap"] > 3 * sound["grad_norm_gap"]
    # a step that returns its state unchanged moves no parameter
    frozen = dict(exact, change_norms={k: 0.0 for k in exact["change_norms"]})
    assert check.train_numbers(frozen, exact)["change_norm_gap"] == 1.0
    # a part of the batch left out shows in the loss
    assert check.train_numbers(exact, exact) == {
        "loss_gap": 0.0, "grad_norm_gap": 0.0, "change_norm_gap": 0.0}
