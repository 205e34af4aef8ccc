#!/usr/bin/env python3
"""Find an open-loop cell's knee: one set-up, then a window at each rate.

    python3 perfbench/sweep.py --workload <cell> --seed <n> --seconds <s> --rates r1,r2,..

Prints one JSON line per rate: the offered and completed decisions per
second, p50 and p95 latency, the mean batch, and the backlog (requests due
in the window whose call had not started when it ended). The knee is the
highest rate whose completed rate keeps up with the offered one and whose
backlog stays near empty. Needs a TPU.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

_ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(_ROOT), str(_ROOT / "src")]

import numpy as np  # noqa: E402

from perfbench import harness, tracing  # noqa: E402


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--rates", required=True)
    args = p.parse_args()
    cell = harness.load_cell(args.workload)
    harness.enable_cache()
    harness.require_tpu(cell.chips)
    spans = tracing.Spans()
    dep = harness.deployment_class(cell.config)(cell.config, cell.mix,
                                                args.seed, spans)
    dep.setup()
    print(json.dumps({"setup_s": time.perf_counter() - T_START}), flush=True)
    for rate in (float(r) for r in args.rates.split(",")):
        mix = dict(cell.mix, rate=rate)
        win = harness.run_window(dep, mix, args.seed, args.seconds, spans)
        dep.outputs = []
        e2e = harness.end_to_end(win, 0.0)
        end = win.t0 + win.seconds
        print(json.dumps({
            "rate": rate, "completed_per_s": e2e["decisions_per_s"],
            "p50_ms": e2e["decision_p50_ms"], "p95_ms": e2e["decision_p95_ms"],
            "mean_batch": float(np.mean(win.batches)),
            "max_batch": max(win.batches),
            "calls_over_8": int(np.sum(np.asarray(win.batches) > 8)),
            "calls": len(win.batches),
            "backlog_at_end": int(np.sum((win.due <= end) &
                                         ~(win.start <= end))),
            "drain_s": win.close - end}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
