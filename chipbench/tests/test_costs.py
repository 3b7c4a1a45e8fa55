"""The GPT-2 adapter's FLOP and byte functions against hand arithmetic; the
table of peaks."""

import json
import os

import pytest

from chipbench.adapters import gpt2

from conftest import ROOT


def _model(name):
    with open(os.path.join(ROOT, "chipbench", "configs", name + ".json")) as f:
        return json.load(f)["model"]


@pytest.mark.parametrize("name,params,mflop", [
    # 12 L d^2 + V d, then 6 N + 6 L T d
    ("gpt2-small", 12 * 12 * 768 ** 2 + 50304 * 768, 798.03),
    ("gpt2-large", 12 * 36 * 1280 ** 2 + 50304 * 1280, 4916.18),
])
def test_train_flops_per_token(name, params, mflop):
    m = _model(name)
    assert gpt2.matmul_params(m) == params
    assert gpt2.train_flops_per_token(m, 1024) / 1e6 == pytest.approx(
        mflop, abs=0.01)


@pytest.mark.parametrize("name,weights_gb,kv_row", [
    ("gpt2-small", 0.249, 2 * 2 * 12 * 768),
    ("gpt2-large", 1.548, 2 * 2 * 36 * 1280),
])
def test_decode_bytes(name, weights_gb, kv_row):
    m = _model(name)
    assert gpt2.weight_bytes(m) / 1e9 == pytest.approx(
        weights_gb, abs=0.001)
    assert gpt2.kv_bytes_per_token(m) == kv_row
    assert gpt2.decode_step_bytes(m, 1000) == \
        gpt2.weight_bytes(m) + 1000 * kv_row


def test_a_slot_of_gpt2_large_holds_189_megabytes_of_cache():
    m = _model("gpt2-large")
    assert gpt2.kv_bytes_per_token(m) * 1024 / 1e6 == \
        pytest.approx(188.7, abs=0.1)


def test_peaks_are_keyed_by_device_kind_with_their_source():
    with open(os.path.join(ROOT, "chipbench", "peaks.json")) as f:
        peaks = json.load(f)
    assert "TPU v5e" in peaks["_source"]
    assert peaks["TPU v5 lite"] == {"tflops_bf16": 197.0, "hbm_gbps": 819.0,
                                    "hbm_gb": 16.0, "ici_gbps": 200.0}
