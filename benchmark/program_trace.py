"""The program's own spans in a traced run.

Every `timed_span` of the program (`kubernetes_tpu/utils/trace.py`) opens
a profiler annotation of the span's name, so a traced run's `.xplane.pb`
carries the spans on its host plane, on the same clock as the device ops
that `trace_reduce.DeviceTrace` reads. This module keeps the spans of
the scheduling loop's thread (the line that carries the benchmark's
`stream.step` annotations, drivers.py) and sets them against the
device's idle time:

    idle_unspanned = device-idle time inside the record's intervals
                     during which that thread had no program span open,
                     as a share of all device-idle time there.

It is the idle the program's spans cannot account for. A program that
opens no span on that thread (one older than these spans) reads None.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

from stats import share_pct

Interval = Tuple[float, float]

PROGRAM_PREFIXES = ("sched.", "pipeline.", "bind.", "extender.", "stream.")
LOOP_MARK = "stream.step"
# the benchmark's own annotations share the `stream.` prefix; they are
# not the program's
BENCH_NAMES = ("stream.step", "stream.idle_wait")


def loop_spans(path: str) -> List[Interval]:
    """The program's spans on the loop thread of the trace at `path` (an
    `.xplane.pb`, or a directory holding one), as (start, end) trace ns;
    empty when no line carries both the loop mark and a program span."""
    import os

    from jax.profiler import ProfileData

    import trace_reduce
    if os.path.isdir(path):
        path = trace_reduce.find_xplane(path)
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            spans: List[Interval] = []
            marked = False
            for e in ln.events:
                name = e.name
                if name == LOOP_MARK:
                    marked = True
                elif name.startswith(PROGRAM_PREFIXES) \
                        and name not in BENCH_NAMES:
                    spans.append((e.start_ns, e.start_ns + e.duration_ns))
            if marked and spans:
                return spans
    return []


def _merge(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(iv for iv in intervals if iv[1] > iv[0]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _overlap(xs: List[Interval], ys: List[Interval]) -> float:
    """Length of the intersection of two sorted, disjoint interval lists."""
    i = j = 0
    total = 0.0
    while i < len(xs) and j < len(ys):
        lo, hi = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if hi > lo:
            total += hi - lo
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return total


def unspanned_idle_pct(busy: Sequence[Interval], spans: Sequence[Interval],
                       windows: Sequence[Interval]) -> Optional[float]:
    """100 x (idle - idle within a span) / idle, where idle is the windows
    less the busy intervals. All intervals share one clock; busy and spans
    may overlap among themselves. None when the windows hold no idle."""
    busy_m = _merge(busy)
    idle: List[Interval] = []
    for a, b in _merge(windows):
        prev = a
        for s, e in busy_m:
            if e <= a or s >= b:
                continue
            if s > prev:
                idle.append((prev, s))
            prev = max(prev, e)
        if b > prev:
            idle.append((prev, b))
    total = sum(e - s for s, e in idle)
    return share_pct(total - _overlap(idle, _merge(spans)), total)


def idle_unspanned(trace, path: str,
                   intervals_mono: Sequence[Interval]) -> Optional[float]:
    """`unspanned_idle_pct` of a run: `trace` is its DeviceTrace, `path`
    its trace, `intervals_mono` the record's intervals on time.monotonic."""
    if not trace.ops or not intervals_mono:
        return None
    spans = loop_spans(path)
    if not spans:
        return None
    windows = trace.window_ns(intervals_mono)
    busy = [iv for a, b in windows for iv in trace._busy_union(a, b)]
    return unspanned_idle_pct(busy, spans, windows)


def read_idle_unspanned(ctx) -> Optional[float]:
    """The per-layer reader's body: the run's trace directory is
    `drivers.TRACE_DIR`, still on disk while the readers run."""
    import drivers
    import trace_reduce
    if ctx.trace is None or drivers.TRACE_DIR is None:
        return None
    try:
        path = trace_reduce.find_xplane(drivers.TRACE_DIR)
    except FileNotFoundError:
        return None
    return idle_unspanned(ctx.trace, path,
                          [(a, b) for _n, a, b, _p in ctx.rec.intervals])
