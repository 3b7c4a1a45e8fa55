"""Readings for a cell's limits, made on the chip by hand (PERF.md records
them); ``run.py`` never runs this.

    python3 -m chipbench.control --workload <name> --seeds 1,2,3 --seconds 12

For each seed it runs the cell with a short window and prints the
numbers compared for the program (the sound reading) and for the control:
the plain reference put in the program's place, computed in fp8, the
precision below the bfloat16 that the configurations state.

- a serving cell (one seed per call): an ordinary run of the cell, and
  after its window the control is read at the same prompts and served
  tokens, in the same process; ``--dump <file>.npz`` keeps the gap at
  every served token (``check.served_positions``'s columns), so that a
  number can be chosen from the readings and not before them;
- a training cell: the control needs no window.  The reference's three
  steps are computed in fp8 and compared with its own float32 steps; with
  ``--seconds 0`` only that is done (no fit, any number of seeds in one
  process).
"""

from __future__ import annotations

import argparse
import json
import sys

from chipbench import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--precision", default="fp8")
    ap.add_argument("--dump")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    cell = run.load_cell(run.ROOT, args.workload, None)
    if cell["traffic"]["kind"] != "train":
        if len(seeds) != 1:
            # after the window this process holds the chip for the
            # reference, so it cannot start a second server
            raise SystemExit("a serving cell takes one seed per call")
        got = run.run_cell(args.workload, seeds[0], args.seconds, False,
                           control=args.precision)
        if args.dump:
            import numpy as np
            np.savez_compressed(args.dump, **got["result"]["positions"])
        print(json.dumps({"seed": seeds[0], "workload": args.workload,
                          **got["result"]["numbers"]}), flush=True)
        return 0

    from chipbench import check, train_cell
    train_cell.claim_devices(cell["chips"], "tpu")
    for seed in seeds:
        rows = cell["adapter"].module(cell["config"]["model"], seed,
                                      cell["traffic"]).train_rows()
        exact = train_cell.reference_numbers(cell, seed, rows, None)
        low = train_cell.reference_numbers(cell, seed, rows, None,
                                           args.precision)
        print(json.dumps({"seed": seed, "workload": args.workload,
                          "control": args.precision,
                          **check.train_numbers(low, exact)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
