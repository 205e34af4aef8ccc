"""Spans of the benchmark's own calls, and the reduction of a profiler
trace to device time.

The benchmark wraps its window and each call into the program in a span.
With tracing on, a span is also a ``jax.profiler.TraceAnnotation``
(``bench.<name>``), so it lands on the profiler's clock beside the device
operations. A trace reduces to:

* busy time: the union of the device operation intervals (the ``XLA Ops``
  line of every ``/device:TPU:<n>`` plane) inside the window, averaged
  over the chips;
* the device time of a kernel (operations whose HLO name is the kernel's
  name), of a jitted program (``XLA Modules`` events of that name), or of
  everything that ran inside a benchmark span;
* idle gaps: the stretches of the window with no device operation, each
  named by the benchmark span it falls in (``wait`` where none).
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
import time

import numpy as np

PREFIX = "bench."
WINDOW = "window"
_OP_NAME = re.compile(r"^%?([^\s=]+?)(?:\.\d+)?\s*=")


class Spans:
    """Host-clock spans by name: ``[start, end, requests]`` rows."""

    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.rows: dict[str, list] = {}
        if annotate:
            import jax
            self._annotation = jax.profiler.TraceAnnotation

    def span(self, name: str, n: int = 0) -> "_Span":
        return _Span(self, name, n)

    def array(self, name: str) -> np.ndarray:
        return np.asarray(self.rows.get(name, []), np.float64).reshape(-1, 3)


class _Span:
    __slots__ = ("spans", "name", "n", "t0", "ann")

    def __init__(self, spans: Spans, name: str, n: int):
        self.spans, self.name, self.n = spans, name, n

    def __enter__(self):
        if self.spans.annotate:
            self.ann = self.spans._annotation(PREFIX + self.name)
            self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        if self.spans.annotate:
            self.ann.__exit__(*exc)
        self.spans.rows.setdefault(self.name, []).append((self.t0, t1, self.n))
        return False


def op_name(hlo_text: str) -> str:
    """``%triple_score_batched.1 = f32[..] custom-call(..)`` ->
    ``triple_score_batched``."""
    m = _OP_NAME.match(hlo_text)
    return m.group(1) if m else hlo_text.split(" ")[0].lstrip("%")


def module_name(event_name: str) -> str:
    """``jit__decision_program(5435511)`` -> ``jit__decision_program``."""
    return event_name.split("(")[0]


@dataclasses.dataclass
class DeviceTrace:
    """A trace reduced to intervals in nanoseconds on the profiler's clock.

    ``ops`` and ``modules``: one list per chip of ``(name, start, end)``;
    ``spans``: the benchmark's spans by short name, ``(start, end)``.
    """

    window: tuple[float, float]
    ops: list[list[tuple[str, float, float]]]
    modules: list[list[tuple[str, float, float]]]
    spans: dict[str, list[tuple[float, float]]]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def find_xplane(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {found}")
    return found[0]


def load(xplane_path: str) -> DeviceTrace:
    """Read a profiler trace into a :class:`DeviceTrace`."""
    import jax
    data = jax.profiler.ProfileData.from_file(xplane_path)
    ops, modules = [], []
    spans: dict[str, list] = {}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            chip_ops, chip_modules = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    chip_ops = [(op_name(e.name), e.start_ns,
                                 e.start_ns + e.duration_ns)
                                for e in line.events]
                elif line.name == "XLA Modules":
                    chip_modules = [(module_name(e.name), e.start_ns,
                                     e.start_ns + e.duration_ns)
                                    for e in line.events]
            ops.append(chip_ops)
            modules.append(chip_modules)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        spans.setdefault(e.name[len(PREFIX):], []).append(
                            (e.start_ns, e.start_ns + e.duration_ns))
    if not ops:
        raise RuntimeError("the trace holds no TPU device plane")
    windows = spans.pop(WINDOW, [])
    if len(windows) != 1:
        raise RuntimeError(f"expected one {PREFIX}{WINDOW} span, "
                           f"found {len(windows)}")
    return DeviceTrace(window=windows[0], ops=ops, modules=modules,
                       spans=spans)


# -- reduction --------------------------------------------------------------


def merge(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Union of ``(start, end)`` intervals clipped to [lo, hi], sorted."""
    out: list[list[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy(tr: DeviceTrace) -> list[list[tuple[float, float]]]:
    """Per chip, the merged intervals in which a device operation ran."""
    lo, hi = tr.window
    return [merge([(s, e) for _, s, e in chip], lo, hi) for chip in tr.ops]


def busy_seconds(tr: DeviceTrace) -> float:
    """Device-busy seconds in the window, averaged over the chips."""
    per_chip = [sum(e - s for s, e in chip) for chip in busy(tr)]
    return float(np.mean(per_chip)) * 1e-9


def idle_share(tr: DeviceTrace) -> float:
    """1 - busy / window."""
    return 1.0 - busy_seconds(tr) / tr.window_s


def overlap(merged: list[tuple[float, float]], starts: list[float],
            s: float, e: float) -> float:
    """Length of [s, e] covered by sorted disjoint ``merged`` intervals
    (``starts``: their start points)."""
    i = max(bisect.bisect_right(starts, s) - 1, 0)
    total = 0.0
    while i < len(merged) and merged[i][0] < e:
        a, b = merged[i]
        total += max(0.0, min(b, e) - max(a, s))
        i += 1
    return total


def kernel_seconds(tr: DeviceTrace, kernel: str) -> float:
    """Device seconds of operations named ``kernel``, inside the window,
    summed over the chips."""
    lo, hi = tr.window
    return sum(max(0.0, min(e, hi) - max(s, lo))
               for chip in tr.ops for name, s, e in chip
               if name == kernel) * 1e-9


def module_seconds(tr: DeviceTrace, module: str) -> float:
    """Device seconds of the jitted program ``module`` inside the window,
    summed over the chips."""
    lo, hi = tr.window
    return sum(max(0.0, min(e, hi) - max(s, lo))
               for chip in tr.modules for name, s, e in chip
               if name == module) * 1e-9


def device_seconds_in(tr: DeviceTrace, span: str) -> float:
    """Device-busy seconds inside the ``span`` spans, summed over the
    chips: every program the calls launched, whatever its name."""
    total = 0.0
    for merged in busy(tr):
        starts = [a for a, _ in merged]
        total += sum(overlap(merged, starts, s, e)
                     for s, e in tr.spans.get(span, []))
    return total * 1e-9


def host_self_seconds(tr: DeviceTrace, span: str) -> np.ndarray:
    """Per ``span``, its length less the device-busy time inside it
    (chip 0's clock)."""
    merged = busy(tr)[0]
    starts = [a for a, _ in merged]
    return np.asarray([(e - s - overlap(merged, starts, s, e)) * 1e-9
                       for s, e in tr.spans.get(span, [])])


def idle_gaps(tr: DeviceTrace) -> list[tuple[str, float]]:
    """Chip 0's idle stretches of the window, longest first, each named by
    the benchmark span that holds its midpoint (``wait`` where none)."""
    lo, hi = tr.window
    merged = busy(tr)[0]
    edges = [lo] + [x for iv in merged for x in iv] + [hi]
    spans = sorted((s, e, name) for name, ivs in tr.spans.items()
                   for s, e in ivs)
    starts = [s for s, _, _ in spans]
    gaps = []
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        mid = 0.5 * (a + b)
        i = bisect.bisect_right(starts, mid) - 1
        name = "wait"
        # spans do not nest, so the last one starting before mid is the
        # only one that can hold it
        if i >= 0 and spans[i][1] >= mid:
            name = spans[i][2]
        gaps.append((name, (b - a) * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    return gaps


def top_ops(tr: DeviceTrace, n: int = 10) -> list[tuple[str, float]]:
    """The ``n`` device operations that took most time in the window,
    named ``<program>/<operation>``, with their seconds summed over the
    chips."""
    lo, hi = tr.window
    totals: dict[str, float] = {}
    for chip_ops, chip_modules in zip(tr.ops, tr.modules):
        mods = sorted(chip_modules, key=lambda m: m[1])
        starts = [m[1] for m in mods]
        for name, s, e in chip_ops:
            d = min(e, hi) - max(s, lo)
            if d <= 0:
                continue
            i = bisect.bisect_right(starts, s) - 1
            prog = mods[i][0] if i >= 0 and mods[i][2] >= s else "?"
            key = f"{prog}/{name}"
            totals[key] = totals.get(key, 0.0) + d * 1e-9
    return sorted(totals.items(), key=lambda kv: -kv[1])[:n]
