"""The work counts against hand counts at small shapes."""

import pytest

from perfbench import work


def test_scorer_flops_hand_count():
    # 3 candidates, Dt=5, Dq=2, H=4: first layer 3*5*4 MACs on the triple
    # half, 2*4 on the query half, second layer 3*4 MACs; 2 FLOPs a MAC
    assert work.scorer_flops(3, 5, 2, 4) == 2 * (60 + 8 + 12)
    # two questions sum
    assert work.scorer_flops([3, 1], 5, 2, 4) == 2 * (60 + 8 + 12) + 2 * (20 + 8 + 4)


def test_scorer_kernel_work_hand_count():
    flops, nbytes = work.scorer_kernel_work([3, 2], d_triple=5, d_hidden=4)
    # both layers on 5 candidates: 5 * (5*4 + 4) MACs
    assert flops == 2 * 5 * (20 + 4)
    # features 5*5 floats, 5 scores, 2 query biases of 4, W1_t 5*4, w2 4, b2 1
    assert nbytes == 4 * (25 + 5 + 8 + 20 + 4 + 1)


def test_decision_work_hand_count():
    flops, nbytes = work.decision_work([100, 10])
    assert flops == work.DECISION_FLOPS_PER_SCORE * 110
    # scores, one count each, tier + difficulty + 4 metrics each
    assert nbytes == 4 * 110 + 4 * 2 + 24 * 2


def test_roofline_takes_the_larger_bound():
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.roofline_seconds(1000.0, 50.0, peaks) == 10.0
    assert work.roofline_seconds(100.0, 50.0, peaks) == 5.0


def test_peaks_keyed_by_device_kind():
    v5e = work.load_peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in v5e["source"]
    with pytest.raises(KeyError):
        work.load_peaks("cpu")
