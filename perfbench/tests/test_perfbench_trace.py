"""The trace reduction on a hand-made trace and on one recorded on a
TPU v5e (`data/small.xplane.pb`: a few calls of both deployments)."""

from pathlib import Path

import pytest

from perfbench import tracing

DATA = Path(__file__).parent / "data"


def _trace():
    # window 0..100 ns; two chips; chip 0 busy 10-30 and 25-40 (union
    # 10-40) and 90-110 (clipped to 90-100); chip 1 busy 0-10
    return tracing.DeviceTrace(
        window=(0.0, 100.0),
        ops=[[("triple_score_batched", 10.0, 30.0), ("fusion", 25.0, 40.0),
              ("skew_metrics", 90.0, 110.0)],
             [("copy", 0.0, 10.0)]],
        modules=[[("jit__retrieved_program", 10.0, 40.0),
                  ("jit__decision_program", 90.0, 110.0)],
                 [("jit_copy", 0.0, 10.0)]],
        spans={"session_call": [(5.0, 45.0), (85.0, 100.0)],
               "feature_build": [(45.0, 85.0)]})


def test_busy_union_and_idle_share():
    tr = _trace()
    assert tracing.busy(tr)[0] == [(10.0, 40.0), (90.0, 100.0)]
    # chip 0: 40 ns, chip 1: 10 ns -> mean 25 ns over a 100 ns window
    assert tracing.busy_seconds(tr) == pytest.approx(25e-9)
    assert tracing.idle_share(tr) == pytest.approx(0.75)


def test_kernel_and_module_time_clip_to_window():
    tr = _trace()
    assert tracing.kernel_seconds(tr, "triple_score_batched") == pytest.approx(20e-9)
    assert tracing.kernel_seconds(tr, "skew_metrics") == pytest.approx(10e-9)
    assert tracing.module_seconds(tr, "jit__decision_program") == pytest.approx(10e-9)


def test_device_time_inside_spans():
    # chip 0: 30 ns inside 5-45 and 10 inside 85-100; chip 1: 5 ns (5-10)
    assert tracing.device_seconds_in(_trace(), "session_call") == \
        pytest.approx(45e-9)
    assert tracing.device_seconds_in(_trace(), "feature_build") == 0.0


def test_host_self_time_and_idle_gaps_by_span():
    tr = _trace()
    # session call 5-45 holds 30 ns of device time, 85-100 holds 10
    assert list(tracing.host_self_seconds(tr, "session_call")) == \
        pytest.approx([10e-9, 5e-9])
    gaps = tracing.idle_gaps(tr)
    assert gaps[0] == ("feature_build", pytest.approx(50e-9))
    assert gaps[1] == ("session_call", pytest.approx(10e-9))
    assert len(gaps) == 2


def test_top_ops_named_by_program():
    top = dict(tracing.top_ops(_trace()))
    assert top["jit__retrieved_program/triple_score_batched"] == pytest.approx(20e-9)
    assert top["jit__decision_program/skew_metrics"] == pytest.approx(10e-9)


def test_op_names():
    assert tracing.op_name("%triple_score_batched.1 = f32[64,1,512]{2,1,0} "
                           "custom-call(f32[64,512,3086] %a)") == \
        "triple_score_batched"
    assert tracing.op_name("%copy-start.3 = (s32[8]) copy-start(s32[8] %n)") \
        == "copy-start"
    assert tracing.module_name("jit__decision_program(5435511654415)") == \
        "jit__decision_program"


def test_recorded_chip_trace():
    """`data/probe.xplane.pb`: a trace recorded on one TPU v5e around four
    session calls (pre-scored B=64 and B=1024, retrieve B=2 with its
    compilation, retrieve B=64) and one feature build, trimmed to the
    device plane and the benchmark's spans. The expected values are the
    durations of its single events, read off the trace by hand."""
    tr = tracing.load(str(DATA / "probe.xplane.pb"))
    assert {k: len(v) for k, v in tr.spans.items()} == {"session_call": 4,
                                                       "feature_build": 1}
    # one triple_score_batched event of 6,738,936 ns
    assert tracing.kernel_seconds(tr, "triple_score_batched") == \
        pytest.approx(6.738936e-3)
    # the two decision programs: 6,485 ns + 68,488 ns
    assert tracing.module_seconds(tr, "jit__decision_program") == \
        pytest.approx(74.973e-6)
    # the two retrieve programs: 884,665 ns + 9,297,677 ns
    assert tracing.module_seconds(tr, "jit__retrieved_program") == \
        pytest.approx(10.182342e-3)
    assert 10.18e-3 < tracing.busy_seconds(tr) < 10.4e-3
    assert 0.99 < tracing.idle_share(tr) < 1.0
    in_calls = tracing.device_seconds_in(tr, "session_call")
    assert 84.973e-6 + 10.182342e-3 - 10e-6 <= in_calls <= \
        tracing.busy_seconds(tr)
    self_s = tracing.host_self_seconds(tr, "session_call")
    assert all(0 < s <= e for s, e in zip(
        self_s, [(b - a) * 1e-9 for a, b in sorted(tr.spans["session_call"])]))
    assert {name for name, _ in tracing.idle_gaps(tr)} <= {
        "session_call", "feature_build", "wait"}
    top = tracing.top_ops(tr, 3)
    assert top[0] == ("jit__retrieved_program/triple_score_batched",
                      pytest.approx(6.738936e-3))
