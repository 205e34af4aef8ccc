"""Each kind of deployment cuts its own cells for the CPU tests and declares
what the planted faults need; the tests learn nothing from a kind's name."""

import copy
import functools
import importlib
import time

import pytest

from perfbench import harness
from perfbench.paths import prescored
from perfbench.tests import cells
from perfbench.tests import test_perfbench_control as control_tests
from perfbench.tests import test_perfbench_harness as harness_tests
from perfbench.tests.cells import BENCH_DIR, MIXES

SEED = 2 ** 34 + 5
KINDS = sorted(p.stem for p in (BENCH_DIR / "paths").glob("*.py")
               if p.stem != "__init__")


#: the cut of each cell whose files exist today, as the keys it changes
#: over the files; a mix that a later kind adds is held to its own ``small``
TODAY = {
    ("prescored-k100", "offline"): ({}, {"pool": 2048}),
    ("prescored-k100", "online"): ({}, {"pool": 2048, "rate": 1000}),
    ("subgraphrag-gte1024", "single"): (
        {"d_emb": 64, "d_hidden": 64, "n_entities": 2000,
         "calibration_questions": 8, "check_requests": 12},
        {"pool": 32}),
}


@pytest.mark.parametrize("config,traffic", sorted(TODAY))
def test_existing_cells_keep_their_cut(config, traffic):
    cfg, mix = cells.load(config, traffic)
    cfg_cut, mix_cut = TODAY[config, traffic]
    want = ({**cfg, **cfg_cut}, {**mix, **mix_cut})
    assert cells.cut(cfg, mix) == want
    cell = cells.small_cell(config, traffic)
    assert (cell.config, cell.mix) == want


@pytest.mark.parametrize("config,traffic", MIXES)
def test_small_leaves_its_arguments_unchanged(config, traffic):
    cfg, mix = cells.load(config, traffic)
    as_loaded = copy.deepcopy((cfg, mix))
    cells.cut(cfg, mix)
    assert (cfg, mix) == as_loaded


@pytest.mark.parametrize("kind", KINDS)
def test_every_kind_declares_its_cut_and_handoff(kind):
    module = importlib.import_module(f"perfbench.paths.{kind}")
    assert callable(module.small)
    assert isinstance(module.Deployment.tiers_column, int)
    assert isinstance(module.Deployment.hands_off, bool)


def _unseen(monkeypatch, name, **kind) -> tuple[dict, dict]:
    """A kind that no file holds, whose cell `cells.small_cell` loads."""
    cfg, mix = cells.register_kind(monkeypatch, name, **kind)
    monkeypatch.setattr(cells, "load", lambda config, traffic: (cfg, mix))
    return cfg, mix


def test_unseen_kind_gets_the_cut_it_declares(monkeypatch):
    def small(config, mix):
        return dict(config, calibration_rows=512), dict(mix, pool=1024,
                                                        rate=400)

    _unseen(monkeypatch, "_unseen_cut", small=small, hands_off=True,
            tiers_column=1)
    cell = cells.small_cell("unseen", "online")
    assert cell.config["calibration_rows"] == 512
    assert (cell.mix["pool"], cell.mix["rate"]) == (1024, 400)
    res = harness.run(cell.name, SEED, 1.0, False, time.perf_counter(),
                      need_tpu=False, cell=cell)
    assert res["correct"], res["checks"]
    assert res["attempted"] == 400


def test_unseen_kind_without_handoff_is_not_picked(monkeypatch):
    cfg, _ = _unseen(monkeypatch, "_unseen_no_handoff",
                     small=prescored.small, hands_off=False, tiers_column=1)
    assert not cells.hands_off(cfg)


def test_kind_without_small_makes_small_cell_raise(monkeypatch):
    _unseen(monkeypatch, "_unseen_no_small", hands_off=True, tiers_column=1)
    with pytest.raises(NotImplementedError,
                       match=r"perfbench\.paths\._unseen_no_small"):
        cells.small_cell("unseen", "online")


def _own_cut(config, mix):
    """A cut that no kind of today makes."""
    config, mix = prescored.small(config, mix)
    return dict(config, calibration_rows=512), dict(mix, pool=1024, rate=400)


#: every test that runs each file of perfbench/mixes/
MIX_TESTS = {
    "small_leaves_its_arguments_unchanged":
        test_small_leaves_its_arguments_unchanged,
    "cell_runs_and_is_correct_on_cpu":
        harness_tests.test_cell_runs_and_is_correct_on_cpu,
    "control_is_not_correct": control_tests.test_control_is_not_correct,
    **{f"broken_timed_path_is_not_correct-{f.__name__}": functools.partial(
        control_tests.test_broken_timed_path_is_not_correct, fault=f)
       for f in (control_tests.alter_one_answer, control_tests.leave_out_half)},
    **{f"broken_handoff_is_not_correct-{f.__name__}": functools.partial(
        control_tests.test_broken_handoff_is_not_correct, fault=f)
       for f in (control_tests.SwappedRunners, control_tests.DroppedTail)},
}


@pytest.mark.parametrize("name", MIX_TESTS)
def test_unseen_kind_with_its_own_mix_passes_every_mix_test(name,
                                                           monkeypatch):
    """A kind that no file holds, with a mix of its own that its own
    ``small`` cuts unlike any kind of today, passes each test that a mix
    file of it would be run through, the hand-off faults among them."""
    cfg, mix = _unseen(monkeypatch, "_unseen_mix", small=_own_cut,
                       hands_off=True, tiers_column=1)
    mix.update(rate=9000, max_batch=32)
    assert cells.hands_off(cfg)
    MIX_TESTS[name]("unseen", "online")
