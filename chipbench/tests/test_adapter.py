"""The harness takes a model of another family with new files only.

A second family lives under ``family2/`` beside this file: an adapter
whose ``model`` spells its sizes otherwise and whose weights are laid out
otherwise, a plain reference of its own, a configuration file.  A
temporary root gets a copy of ``BENCHMARK.json`` with that configuration
and two cells appended, as a later PR would append them; ``run_cell``
rehearses both cells from there on the CPU; no file that existed is
written.  And nothing of the harness outside the GPT-2 family's own files
knows GPT-2."""

import hashlib
import io
import json
import os
import re

import pytest

from chipbench import run

from conftest import ROOT

F2 = "chipbench/tests/family2/"
TRAIN_LIMITS = {"loss_gap": 1e-3, "grad_norm_gap": 0.05,
                "change_norm_gap": 0.5}
SERVE_LIMITS = {"logit_gap": 0.05}
CELLS = {
    "f2-train": {
        "entry": {"config": "tiny-f2", "traffic": "lm-fixed1024", "chips": 1,
                  "why": "rehearsal: Trainer.fit of the second family"},
        "like": "gpt2s-train-1chip", "limits": TRAIN_LIMITS,
        "traffic": {"global_batch": 4, "steps_per_epoch": 64,
                    "token_ids_below": 500}},
    "f2-serve": {
        "entry": {"config": "tiny-f2", "traffic": "doc-saturated", "chips": 1,
                  "why": "rehearsal: Server of the second family"},
        "like": "gpt2l-serve-doc", "limits": SERVE_LIMITS,
        "traffic": {"slots": 4, "ramp_s": 1.5, "token_ids_below": 500,
                    "buckets": [16, 32, 64], "reference_rows_per_block": 4,
                    "server": {"max_new_tokens": 16},
                    "prompt": {"median": 20, "min": 8, "max": 40},
                    "answer": {"min": 4, "max": 12}}},
}


def _existing_files() -> dict:
    """Every file the benchmark has (caches of the interpreter aside), by
    content: what a PR that adds a family may not touch."""
    paths = [os.path.join(ROOT, "BENCHMARK.json")]
    for at, dirs, files in os.walk(os.path.join(ROOT, "chipbench")):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        paths += [os.path.join(at, name) for name in files]
    found = {}
    for path in paths:
        with open(path, "rb") as f:
            found[path] = hashlib.sha256(f.read()).hexdigest()
    return found


@pytest.fixture
def other_root(tmp_path):
    """A root whose ``BENCHMARK.json`` lists the second family's
    configuration and cells beside everything the tree's lists; the
    files under ``chipbench/`` are the tree's own, new ones included."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tiny-f2", "source": "none", "file": F2 + "tiny-f2.json",
        "reduced": [], "why": "a second family, of new files only"})
    for name, cell in CELLS.items():
        bench["workloads"].append({"name": name, **cell["entry"]})
        for metric in bench["end_to_end"] + bench["per_layer"]:
            if cell["like"] in metric.get("workloads", []):
                metric["workloads"].append(name)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    os.symlink(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench")
    return str(tmp_path)


def _rehearse(root, workload, trace=False):
    cell = CELLS[workload]
    out = io.StringIO()
    got = run.run_cell(workload, 2 ** 31 + 29, 2.0, trace, root=root, out=out,
                       rehearsal={"platform": "cpu", "chips": 1,
                                  "traffic": cell["traffic"],
                                  "limits": cell["limits"]})
    return got, [json.loads(x) for x in out.getvalue().splitlines()]


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_a_second_family_runs_with_no_edit_of_a_file_that_existed(
        other_root, workload):
    before = _existing_files()
    got, lines = _rehearse(other_root, workload)
    line = got["line"]
    assert line["correct"] is True and line["rehearsal"]
    assert line["attempted"] > 0 and line["failed"] == 0
    with open(os.path.join(other_root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert set(line["metrics"]) == {
        m["name"] for m in bench["end_to_end"]
        if workload in m.get("workloads", [workload])}
    compared = {row["number"]: row for row in lines[1]["compared"]}
    assert set(compared) == set(CELLS[workload]["limits"])
    assert all(row["ok"] for row in compared.values())
    ctx = got["result"]["ctx"]
    assert ctx["adapter"].__name__ == "chipbench.tests.family2.adapter"
    assert "n_embd" not in ctx["model"] and ctx["model"]["hidden_size"] == 64
    assert _existing_files() == before


def test_the_second_familys_served_token_altered_comes_out_not_correct(
        other_root, monkeypatch):
    from ray_lightning_tpu.serve.scheduler import Scheduler
    apply = Scheduler.apply

    def broken(self, plan, result):
        result["decode"] = {s: (int(t) + 1) % 512
                            for s, t in result["decode"].items()}
        apply(self, plan, result)

    monkeypatch.setattr(Scheduler, "apply", broken)
    got, lines = _rehearse(other_root, "f2-serve")
    assert got["line"]["correct"] is False
    assert lines[1]["compared"][0]["value"] > SERVE_LIMITS["logit_gap"]


def test_the_cost_readers_ask_the_cells_own_family():
    """``train_mfu_pct`` and ``tput_decode_roofline`` divide by what the
    cell's adapter computes from ITS model's keys."""
    from chipbench.tests.family2 import adapter
    with open(os.path.join(ROOT, F2 + "tiny-f2.json")) as f:
        model = json.load(f)["model"]
    peaks = {"tflops_bf16": 197.0, "hbm_gbps": 819.0}
    ctx = {"adapter": adapter, "model": model, "peaks": peaks, "chips": 1,
           "window": {"tokens_per_s": 1e6, "seq_len": 64,
                      "live_tokens_mean": 100.0},
           "trace": {"ms_by_kind": {"decode": 0.01}}}
    # 6 x (12 x 2 x 64^2 + 512 x 64) + 6 x 2 x 64 x 64 FLOPs a token
    assert run.read_layer_metric(ROOT, "train_mfu_pct", ctx) == \
        pytest.approx(100 * 1e6 * 835584 / 197e12)
    # bf16 weights 2 x (2 x (12 x 64^2 + 13 x 64) + 512 x 64 + 64 x 64
    # + 128) + 100 live rows of 2 x 2 x 2 x 64 bytes
    assert run.read_layer_metric(ROOT, "tput_decode_roofline", ctx) == \
        pytest.approx(100 * (273920 + 51200) / 819e9 / 1e-5)


def test_a_configuration_without_an_adapter_is_an_error_that_says_so():
    with pytest.raises(SystemExit, match="names no model family"):
        run.load_adapter({"name": "x", "reference": "y.py"}, ROOT)
    with pytest.raises(SystemExit, match="no adapter"):
        run.load_adapter({"name": "x", "adapter": "chipbench/adapters/"
                          "nothing_here.py"}, ROOT)


def test_only_the_gpt2_familys_own_files_know_gpt2():
    """Outside ``adapters/gpt2.py``, ``gpt2_reference.py`` (``flops.py``
    re-exports the former for a test of the program's) and the tests, no
    code under ``chipbench/`` imports the program's GPT or reads one of
    GPT-2's configuration keys."""
    own = {os.path.join("adapters", "gpt2.py"), "gpt2_reference.py",
           "flops.py"}      # the adapter's counts under their old address
    knows = re.compile(r"\bn_(embd|head|layer|positions)\b"
                       r"|\bmodels\.gpt\b|\bmodels import gpt\b"
                       r"|\bimport GPT|adapters\.gpt2\b|adapters import gpt2")
    hits = []
    top = os.path.join(ROOT, "chipbench")
    for at, dirs, files in os.walk(top):
        dirs[:] = [d for d in dirs if d not in ("__pycache__", "tests")]
        for name in files:
            path = os.path.relpath(os.path.join(at, name), top)
            if not name.endswith(".py") or path in own:
                continue
            with open(os.path.join(at, name)) as f:
                hits += [f"{path}:{n}: {text.strip()}"
                         for n, text in enumerate(f, 1) if knows.search(text)]
    # each configuration's reference may name the family's reference
    hits = [h for h in hits if not re.match(
        r"configs/gpt2-\w+_reference\.py", h)]
    assert not hits, "\n".join(hits)
