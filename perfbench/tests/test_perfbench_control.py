"""The comparison that decides ``correct`` fails where it should: the
control (the reference one precision lower in the program's place) breaks
a limit, and so does a run whose timed path is broken underneath."""

import time

import numpy as np
import pytest

from perfbench import compare, harness, tracing
from perfbench.tests.cells import MIXES, small_cell

#: one mix of each configuration: the control replaces the program
CONTROL_MIXES = sorted({config: (config, traffic)
                        for config, traffic in MIXES}.values())
SEED = 2 ** 35 + 11


@pytest.mark.parametrize("config,traffic", CONTROL_MIXES)
def test_control_is_not_correct(config, traffic):
    cell = small_cell(config, traffic)
    dep = harness.deployment_class(cell.config)(cell.config, cell.mix, SEED,
                                                tracing.Spans())
    dep.setup()
    win = harness.run_window(dep, cell.mix, SEED, 1.0, tracing.Spans())
    dep.release()
    program_ok, program = compare.verdict(dep.check(win),
                                          cell.config["limits"])
    control_ok, control = compare.verdict(dep.control(win),
                                          cell.config["limits"])
    assert program_ok, program
    assert not control_ok, control


def _tier_column(dep) -> int:
    return 1 if "pool_nv" in vars(dep) else 5


def alter_one_answer(dep, serve, i, j):
    """Each call's first decision is flipped to the other tier where it
    is produced."""
    serve(i, j)
    out = list(dep.outputs[-1])
    col = _tier_column(dep)
    tiers = np.array(out[col])
    tiers[0] = 1 - tiers[0]
    out[col] = tiers
    dep.outputs[-1] = tuple(out)


def leave_out_half(dep, serve, i, j):
    """Each call serves only the first half of its batch."""
    if (j - i) // 2:
        serve(i, i + (j - i) // 2)


@pytest.mark.parametrize("fault", [alter_one_answer, leave_out_half])
@pytest.mark.parametrize("config,traffic", MIXES)
def test_broken_timed_path_is_not_correct(config, traffic, fault,
                                          monkeypatch):
    cell = small_cell(config, traffic)
    sound = harness.deployment_class(cell.config)

    class Broken(sound):
        def serve(self, i, j):
            fault(self, super().serve, i, j)

    monkeypatch.setattr(harness, "deployment_class", lambda config: Broken)
    res = harness.run(cell.name, SEED, 1.0, False, time.perf_counter(),
                      need_tpu=False, cell=cell)
    assert not res["correct"], res["checks"]


class SwappedRunners:
    """Each tier's runner receives the other tier's micro-batches."""

    def _handoff(self, tier):
        return super()._handoff(1 - tier)


class DroppedTail:
    """The partial micro-batch each call leaves in a tier's queue is
    dropped instead of handed to the runner."""

    def serve(self, i, j):
        super().serve(i, j)
        for q in self.session.pipeline.queues.values():
            q.flush()


PRESCORED_MIXES = [m for m in MIXES if m[0].startswith("prescored")]


@pytest.mark.parametrize("fault", [SwappedRunners, DroppedTail])
@pytest.mark.parametrize("config,traffic", PRESCORED_MIXES)
def test_broken_handoff_is_not_correct(config, traffic, fault, monkeypatch):
    cell = small_cell(config, traffic)
    broken = type("Broken", (fault, harness.deployment_class(cell.config)),
                  {})
    monkeypatch.setattr(harness, "deployment_class", lambda config: broken)
    res = harness.run(cell.name, SEED, 1.0, False, time.perf_counter(),
                      need_tpu=False, cell=cell)
    assert not res["correct"], res["checks"]
