#!/usr/bin/env python3
"""Readings for the limits of ``correct``: per seed, the numbers of the
program's own run and of the control (the plain reference one precision
lower, put in the program's place) on the same requests.

    python3 perfbench/control.py --workload <cell> --seconds <s> --seeds a,b,c

Each seed is set up and served for one short window at the cell's own
load, as a benchmark run is; the control then replaces the program for the
requests that window served. One JSON line per seed. Needs a TPU.
"""

import argparse
import json
import sys
from pathlib import Path

_ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(_ROOT), str(_ROOT / "src")]

from perfbench import harness, tracing  # noqa: E402


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seeds", required=True)
    args = p.parse_args()
    cell = harness.load_cell(args.workload)
    harness.enable_cache()
    harness.require_tpu(cell.chips)
    for seed in (int(s) for s in args.seeds.split(",")):
        spans = tracing.Spans()
        dep = harness.deployment_class(cell.config)(cell.config, cell.mix,
                                                    seed, spans)
        dep.setup()
        win = harness.run_window(dep, cell.mix, seed, args.seconds, spans)
        dep.release()
        print(json.dumps({"seed": seed, "requests": win.n_due,
                          "program": dep.check(win),
                          "control": dep.control(win)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
