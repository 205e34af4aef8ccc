"""The benchmark's trace reduction beside the program's own spans.

The program annotates its stages as ``repro.<name>`` (they nest inside the
benchmark's ``bench.session_call`` and each other); the reduction reads
``bench.*`` spans alone, so its readers and breakdown must read the same
with or without them. ``data/probe.xplane.pb`` was recorded on a TPU v5e
before the program had spans, ``data/spans.xplane.pb`` after.
"""

import types
from pathlib import Path

import numpy as np
import pytest

from perfbench import harness, tracing, work

DATA = Path(__file__).parent / "data"


def test_existing_readers_and_breakdown_hold_on_the_recorded_trace():
    """The four readers and the breakdown read the values they read before
    the program's spans existed."""
    tr = tracing.load(str(DATA / "probe.xplane.pb"))

    class Dep:
        def served_ids(self):
            return np.arange(64)

        def n_valid_of(self, ids):
            return np.full(len(ids), 100)

    win = harness.Window(t0=0.0, seconds=1.0, due=np.arange(10) * 0.1,
                         start=np.arange(10) * 0.102,
                         done=np.arange(10) * 0.1 + 0.05, batches=[1] * 10)
    ctx = types.SimpleNamespace(trace=tr, dep=Dep(), window=win,
                                mix={"loop": "open"},
                                peaks=work.load_peaks("TPU v5 lite"))
    values = {m: harness.find_reader(m)(ctx) for m in (
        "queue_wait_p95_ms", "dispatch_host_ms", "device_idle_share",
        "decision_roofline")}
    assert values == pytest.approx({
        "queue_wait_p95_ms": 17.100000000000016,
        "dispatch_host_ms": 1558.9347572500003,
        "device_idle_share": 99.83661800199232,
        "decision_roofline": 0.00032605077210606516}, rel=1e-12)
    assert tracing.top_ops(tr) == pytest.approx([
        ("jit__retrieved_program/triple_score_batched", 0.006738936),
        ("jit__retrieved_program/copy", 0.0012715870000000002),
        ("jit__retrieved_program/pad", 0.001256349),
        ("jit__retrieved_program/fusion", 0.0008486790000000001),
        ("jit__decision_program/skew_metrics", 6.756200000000001e-05),
        ("jit__retrieved_program/copy-done", 2.3511e-05),
        ("jit__retrieved_program/sort", 1.7291e-05),
        ("jit__retrieved_program/convolution_add_fusion", 1.6246e-05),
        ("jit_atleast_2d/copy", 4.3990000000000006e-06),
        ("jit__retrieved_program/skew_metrics", 3.973e-06)], rel=1e-12)
    assert tracing.idle_gaps(tr)[:10] == pytest.approx([
        ("session_call", 6.158681849000001), ("session_call", 0.094701371),
        ("session_call", 0.006212349000000001),
        ("session_call", 0.005427384), ("session_call", 0.001102038),
        ("session_call", 0.000823667), ("session_call", 0.0006724420000000001),
        ("session_call", 0.000539262), ("session_call", 5.7e-07),
        ("session_call", 3.5200000000000003e-07)], rel=1e-12)


def _host_events(xplane: Path, prefix: str) -> dict[str, list]:
    import jax
    out: dict[str, list] = {}
    for plane in jax.profiler.ProfileData.from_file(str(xplane)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(prefix):
                        out.setdefault(e.name[len(prefix):], []).append(
                            (e.start_ns, e.start_ns + e.duration_ns))
    return out


def test_program_spans_stay_out_of_the_benchmark_spans():
    """`data/spans.xplane.pb`: 24 session calls of the pre-scored
    deployment on one TPU v5e (batches of 5, 60, 64, 33, 8 and 1 rows, four
    times: both sides of auto), with the program's spans. Each call holds
    one ``repro.submit`` and the stages nest inside it; the reduction keeps
    the calls alone, so its gaps are named by them."""
    xplane = DATA / "spans.xplane.pb"
    program = _host_events(xplane, "repro.")
    # 684 rows fill 85 micro-batches of 8
    assert {k: len(v) for k, v in program.items()} == {
        "submit": 24, "dispatch": 24, "launch": 24, "pull": 24,
        "decide": 24, "handoff": 24, "execute": 85}
    tr = tracing.load(str(xplane))
    assert {k: len(v) for k, v in tr.spans.items()} == {"session_call": 24}
    calls = sorted(tr.spans["session_call"])

    def inside(inner, outer):
        return [sum(o0 <= i0 and i1 <= o1 for o0, o1 in outer)
                for i0, i1 in inner]

    assert inside(sorted(program["submit"]), calls) == [1] * 24
    for child, parent in (("dispatch", "submit"), ("handoff", "submit"),
                          ("launch", "dispatch"), ("pull", "dispatch"),
                          ("decide", "dispatch"), ("execute", "handoff")):
        assert inside(program[child], program[parent]) == (
            [1] * len(program[child])), child
    assert {name for name, _ in tracing.idle_gaps(tr)} <= {"session_call",
                                                           "wait"}
    self_s = tracing.host_self_seconds(tr, "session_call")
    assert len(self_s) == 24 and (self_s > 0).all()
