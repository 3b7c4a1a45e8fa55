"""One run of one cell: ``python3 -m chipbench.run --workload <name>
--seed <n> --seconds <s> --trace <0|1>``.

Loads the cell named in ``BENCHMARK.json`` (its configuration file, its
traffic file), sets the system up, warms the cell's own shapes, measures
for ``--seconds``, checks what the timed path produced against the plain
reference, and prints the contract's one JSON object as the last line of
standard output.  Earlier lines are JSON too: the set-up's phases and
each number compared beside its limit.

There is no CPU mode and no smaller size on the command line.  Without
the chips the cell asks for the run exits non-zero and prints no result.
(``chipbench/tests`` rehearse the control flow on the CPU at a tiny size
by calling ``run_cell`` with overrides that no argument reaches.)
"""

from __future__ import annotations

import argparse
import importlib
import importlib.util
import json
import os
import shutil
import sys
import time

T_PROCESS = time.monotonic()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXIT_NO_ACCELERATOR = 2


def _read(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_adapter(config_doc: dict, root: str):
    """The configuration's model family: the module its file names under
    ``"adapter"`` (``chipbench/README.md`` has the contract).  Imported by
    name, as the serve worker will import it to unpickle the module it is
    sent.  There is no default family."""
    path = config_doc.get("adapter")
    if not path:
        raise SystemExit(
            f"configuration {config_doc.get('name')!r} names no model "
            f"family: its file needs \"adapter\": \"chipbench/adapters/"
            f"<family>.py\" beside \"reference\" (there is no default)")
    if not os.path.isfile(os.path.join(root, path)):
        raise SystemExit(f"no adapter {path!r} under {root}")
    return importlib.import_module(os.path.splitext(path)[0].replace("/", "."))


def load_cell(root: str, workload: str, rehearsal: "dict | None") -> dict:
    bench = _read(os.path.join(root, "BENCHMARK.json"))
    entry = (rehearsal or {}).get("entry") or next(
        (w for w in bench["workloads"] if w["name"] == workload), None)
    if entry is None:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    config_entry = next(c for c in bench["configs"]
                        if c["name"] == entry["config"])
    config = _read(os.path.join(root, config_entry["file"]))
    traffic = _read(os.path.join(root, "chipbench", "traffic",
                                 entry["traffic"] + ".json"))
    chips = int(entry["chips"])
    if rehearsal:
        config = {**config, "model": {**config["model"],
                                      **rehearsal.get("model", {})}}
        traffic = _merged(traffic, rehearsal.get("traffic", {}))
        chips = int(rehearsal.get("chips", chips))
    work = os.path.join(root, ".chipbench_work", workload)
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    return {
        "name": workload, "root": root, "work": work, "chips": chips,
        "config": config, "traffic": traffic, "bench": bench,
        "adapter": load_adapter(config, root),
        "peaks": {k: v for k, v in _read(os.path.join(
            root, "chipbench", "peaks.json")).items()
            if not k.startswith("_")},
    }


def _merged(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = _merged(base[k], v) if isinstance(v, dict) \
            and isinstance(base.get(k), dict) else v
    return out


def read_layer_metric(root: str, name: str, ctx: dict):
    """``chipbench/layer_metrics/<name>.py``'s ``read(ctx)``: a number, or
    None when this run holds nothing for it to read."""
    path = os.path.join(root, "chipbench", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_layer_metric", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def require_peak(peaks: dict, device_kind: str) -> dict:
    """A device that is not in the table is an error, never a default."""
    if device_kind not in peaks:
        raise SystemExit(
            f"no published peak on record for device kind {device_kind!r} "
            f"(known: {sorted(peaks)}): add it to chipbench/peaks.json with "
            f"its source")
    return peaks[device_kind]


def wanted(metric: dict, workload: str) -> bool:
    return workload in metric.get("workloads", [workload])


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             root: str = ROOT, rehearsal: "dict | None" = None,
             control: "str | None" = None, out=sys.stdout) -> dict:
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(root, ".jax_cache"))
    from chipbench import check
    cell = load_cell(root, workload, rehearsal)
    platform = (rehearsal or {}).get("platform", "tpu")
    kind = cell["traffic"]["kind"]
    if kind == "train":
        from chipbench import train_cell
        res = train_cell.run(cell, seed, seconds, trace, T_PROCESS, platform)
    elif kind in ("serve-open", "serve-closed"):
        from chipbench import serve_cell
        res = serve_cell.run(cell, seed, seconds, trace, T_PROCESS, platform,
                             control)
    else:
        raise SystemExit(f"unknown traffic kind {kind!r}")
    device = res["device"]
    if not rehearsal:
        require_peak(cell["peaks"], device["kind"])

    print(json.dumps({"phases": res["phases"]}), file=out)
    limits = (rehearsal or {}).get("limits") \
        or check.load_limits(root, workload)
    correct, rows = check.verdict(res["numbers"], limits)
    print(json.dumps({"compared": rows, "numbers": res["numbers"]}),
          file=out)

    bench = cell["bench"]
    metrics = {}
    if trace:
        ctx = res["ctx"]
        for m in bench["per_layer"]:
            if not wanted(m, workload):
                continue
            value = read_layer_metric(root, m["name"], ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = {**res["end_to_end"], "setup_s": res["setup_s"]}
        for m in bench["end_to_end"]:
            if wanted(m, workload):
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    line = {"correct": bool(correct), "attempted": res["attempted"],
            "failed": res["failed"], "metrics": metrics,
            "device": {**device,
                       "memory_peak_bytes": res["memory_peak_bytes"]}}
    red = res["ctx"].get("trace")
    if trace and red and not rehearsal:
        line["device"]["busy_s"] = red["busy_s"]
        line["device"]["window_s"] = red["window_s"]
        line["breakdown"] = {"device_ops": red["device_ops"],
                             "idle_gaps": red["idle_gaps"]}
    if rehearsal:
        # a CPU rehearsal proves control flow; it names no device metric
        line["metrics"] = {k: {"value": None, "unit": v["unit"]}
                           for k, v in metrics.items()}
        line["rehearsal"] = True
    print(json.dumps(line), file=out, flush=True)
    return {"line": line, "result": res}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from chipbench.train_cell import NoAccelerator
    try:
        run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    except NoAccelerator as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return EXIT_NO_ACCELERATOR
    return 0


if __name__ == "__main__":
    sys.exit(main())
