"""The rate sweep behind an open-loop cell's ``rate_rps``, made once, by
hand, on the chip (PERF.md keeps its table); ``run.py`` never runs this.

    python3 -m chipbench.sweep --workload gpt2l-serve-chat --seed 7 \\
        --rates 2,3,4,5,6 --seconds 51

One server, one set-up; each rate gets the mix's ramp and a window, then
the queue drains before the next.  A rate is sustained when the backlog
does not grow over the window: the queue at its close is no deeper than
a few requests and the time to first token has not run away.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

from chipbench import run, serve_cell


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    args = ap.parse_args(argv)
    cell = run.load_cell(run.ROOT, args.workload, None)
    session = serve_cell.ServeSession(cell, args.seed, "tpu", run.T_PROCESS)
    try:
        print(json.dumps({"phases": session.phases}), flush=True)
        for rate in (float(r) for r in args.rates.split(",")):
            mix = {**cell["traffic"], "rate_rps": rate}
            drove = session.drive(mix, args.seconds, False)
            counted = session.counter.between(drove["t0"], drove["t1"])
            sent = drove["sent"]
            row = {"rate_rps": rate, "sent": len(sent),
                   "queued_at_close": drove["queued_at_close"],
                   "late_ms_max": 1e3 * max(s.sent - s.due for s in sent),
                   "steps": counted["steps"],
                   "step_ms": 1e3 * args.seconds / max(1, counted["steps"]),
                   **serve_cell.latencies(sent, drove["t0"], drove["t1"])}
            print(json.dumps(row), flush=True)
            deadline = time.monotonic() + 120
            while not session.server.scheduler.idle() \
                    and time.monotonic() < deadline:
                time.sleep(0.2)
    finally:
        session.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
