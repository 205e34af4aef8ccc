"""Cells cut to a size the CPU tests can run, built from the benchmark's
files: every traffic mix of every configuration, whether or not
`BENCHMARK.json` lists it yet. Each kind of deployment cuts its own cells
(``small`` of its module under ``perfbench/paths/``) and declares what the
planted faults need: no cut and no fault is chosen by a kind's or a
cell's name."""

import importlib
import json
import sys
import types
from pathlib import Path

from perfbench import harness
from perfbench.paths import prescored

BENCH_DIR = Path(__file__).resolve().parents[1]
#: (config, traffic) of every file in perfbench/mixes/
MIXES = sorted(tuple(p.name[:-len(".json")].split(".", 1))
               for p in (BENCH_DIR / "mixes").glob("*.json"))


def load(config: str, traffic: str) -> tuple[dict, dict]:
    """A configuration's file and one of its mixes, as run on the chip."""
    cfg = json.loads((BENCH_DIR / "configs" / f"{config}.json").read_text())
    mix = json.loads((BENCH_DIR / "mixes" / f"{config}.{traffic}.json")
                     .read_text())
    return cfg, mix


def cut(cfg: dict, mix: dict) -> tuple[dict, dict]:
    """Copies of ``cfg`` and ``mix`` cut by the ``small`` of the module that
    builds their kind of deployment."""
    module = importlib.import_module(f"perfbench.paths.{cfg['path']}")
    if not callable(getattr(module, "small", None)):
        raise NotImplementedError(
            f"{module.__name__} defines no small(config, mix): its cells "
            "would run in the CPU tests at their full size")
    return module.small(cfg, mix)


def small_cell(config: str, traffic: str) -> harness.Cell:
    """The cell with its data and traffic cut down and its widths kept
    small, so that its whole run fits in a few CPU seconds."""
    bench = json.loads((BENCH_DIR.parent / "BENCHMARK.json").read_text())
    cfg, mix = cut(*load(config, traffic))
    return harness.Cell(f"{config}.{traffic}", 1, cfg, mix,
                        bench["end_to_end"], [])


def hands_off(cfg: dict) -> bool:
    """Whether the configuration's kind declares that it hands requests to
    tier runners through the program's pipeline."""
    return bool(harness.deployment_class(cfg).hands_off)


def register_kind(monkeypatch, name: str, small=None,
                  **declared) -> tuple[dict, dict]:
    """A kind of deployment that no file holds: a module
    ``perfbench.paths.<name>`` whose ``Deployment`` subclasses the
    pre-scored one with ``declared`` as class attributes, and with
    ``small`` where one is given; ``monkeypatch`` takes it away again.
    Returns the pre-scored online cell's files, as that kind's."""
    module = types.ModuleType(f"perfbench.paths.{name}")
    module.Deployment = type("Deployment", (prescored.Deployment,), declared)
    if small is not None:
        module.small = small
    monkeypatch.setitem(sys.modules, module.__name__, module)
    cfg, mix = load("prescored-k100", "online")
    cfg["path"] = name
    return cfg, mix
