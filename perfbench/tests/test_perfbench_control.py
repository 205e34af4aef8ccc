"""The comparison that decides ``correct`` fails where it should: the
control (the reference one precision lower in the program's place) breaks
a limit, and so does a run whose timed path is broken underneath."""

import time

import numpy as np
import pytest

from perfbench import compare, harness, tracing
from perfbench.tests.cells import (MIXES, cut, hands_off, load,
                                   register_kind, small_cell)

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


def alter_one_answer(dep, serve, i, j):
    """Each call's first decision is flipped to the other tier where it
    is produced."""
    serve(i, j)
    out = list(dep.outputs[-1])
    col = dep.tiers_column
    tiers = np.array(out[col])
    tiers[0] = 1 - tiers[0]
    out[col] = tiers
    dep.outputs[-1] = tuple(out)


def leave_out_half(dep, serve, i, j):
    """Each call serves only the first half of its batch."""
    if (j - i) // 2:
        serve(i, i + (j - i) // 2)


def _run_broken(cell, fault) -> dict:
    """A CPU run of ``cell`` with ``fault`` planted under its timed path:
    a class that overrides the deployment's methods, or a function that
    wraps each call's ``serve``."""
    sound = harness.deployment_class(cell.config)
    if isinstance(fault, type):
        broken = type("Broken", (fault, sound), {})
    else:
        class broken(sound):
            def serve(self, i, j):
                fault(self, super().serve, i, j)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(harness, "deployment_class", lambda config: broken)
        return harness.run(cell.name, SEED, 1.0, False, time.perf_counter(),
                           need_tpu=False, cell=cell)


@pytest.mark.parametrize("fault", [alter_one_answer, leave_out_half])
@pytest.mark.parametrize("config,traffic", MIXES)
def test_broken_timed_path_is_not_correct(config, traffic, fault):
    res = _run_broken(small_cell(config, traffic), fault)
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


#: the mixes of every kind that declares a hand-off to tier runners
HANDOFF_MIXES = [m for m in MIXES if hands_off(load(*m)[0])]


@pytest.mark.parametrize("fault", [SwappedRunners, DroppedTail])
@pytest.mark.parametrize("config,traffic", HANDOFF_MIXES)
def test_broken_handoff_is_not_correct(config, traffic, fault):
    res = _run_broken(small_cell(config, traffic), fault)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("fault", [SwappedRunners, DroppedTail,
                                   alter_one_answer])
def test_unseen_kind_gets_the_faults_it_declares(fault, monkeypatch):
    """A kind of deployment that no file holds, which hands requests to
    tier runners as the pre-scored kind does, is picked for the hand-off
    faults by its declaration, and the tier fault finds its tiers where it
    declares them."""
    def small(config, mix):
        return dict(config), dict(mix, pool=1024, rate=400)

    cfg, mix = register_kind(monkeypatch, "_unseen_handoff", small=small,
                             hands_off=True, tiers_column=1)
    assert hands_off(cfg)
    cfg, mix = cut(cfg, mix)
    res = _run_broken(harness.Cell("unseen.online", 1, cfg, mix, [], []),
                      fault)
    assert not res["correct"], res["checks"]
