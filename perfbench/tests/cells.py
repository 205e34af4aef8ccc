"""Cells cut to a size the CPU tests can run, built from the benchmark's
files: every traffic mix of every configuration, whether or not
`BENCHMARK.json` lists it yet."""

import json
from pathlib import Path

from perfbench import harness

BENCH_DIR = Path(__file__).resolve().parents[1]
#: (config, traffic) of every file in perfbench/mixes/
MIXES = sorted(tuple(p.name[:-len(".json")].split(".", 1))
               for p in (BENCH_DIR / "mixes").glob("*.json"))


def small_cell(config: str, traffic: str) -> harness.Cell:
    """The cell with its data and traffic cut down and its widths kept
    small, so that its whole run fits in a few CPU seconds."""
    bench = json.loads((BENCH_DIR.parent / "BENCHMARK.json").read_text())
    cfg = json.loads((BENCH_DIR / "configs" / f"{config}.json").read_text())
    mix = json.loads((BENCH_DIR / "mixes" / f"{config}.{traffic}.json")
                     .read_text())
    if cfg["path"] == "retrieve":
        cfg.update(d_emb=64, d_hidden=64, n_entities=2000,
                   calibration_questions=8, check_requests=12)
        mix["pool"] = 32
        if mix["loop"] == "open":
            mix["rate"] = 10
    else:
        mix["pool"] = 2048
        if mix["loop"] == "open":
            mix["rate"] = 1000
    return harness.Cell(f"{config}.{traffic}", 1, cfg, mix,
                        bench["end_to_end"], [])
