"""The cyclic collector's pauses inside the bursts, per 1,000 burst pods:
the `gc.pause` annotations of the traced run's profile (the stream loop's
collector hook, `engine/streaming.py`), on any host thread, clipped to
each burst's interval (its due time to its last bind). Pauses between the
bursts cost this cell nothing and are left out. None when the trace holds
no `gc.pause` at all (a program without the hook)."""

from __future__ import annotations

from stats import per_kpod_ms, union_length

PAUSE = "gc.pause"


def pauses_ns(path):
    """Every `gc.pause` on the host planes of the trace at `path` (an
    `.xplane.pb`), as (start, end) trace ns."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    return [(e.start_ns, e.start_ns + e.duration_ns)
            for plane in pd.planes if plane.name.startswith("/host:")
            for ln in plane.lines for e in ln.events if e.name == PAUSE]


def read(ctx):
    import drivers
    import trace_reduce
    if ctx.trace is None or drivers.TRACE_DIR is None \
            or not ctx.rec.intervals:
        return None
    try:
        path = trace_reduce.find_xplane(drivers.TRACE_DIR)
    except FileNotFoundError:
        return None
    pauses = pauses_ns(path)
    if not pauses:
        return None
    windows = ctx.trace.window_ns([(a, b) for _n, a, b, _p
                                   in ctx.rec.intervals])
    inside = union_length([(max(s, a), min(e, b)) for a, b in windows
                           for s, e in pauses if e > a and s < b])
    return per_kpod_ms(inside / 1e9,
                       sum(p for _n, _a, _b, p in ctx.rec.intervals))
