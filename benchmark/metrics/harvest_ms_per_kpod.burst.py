"""The engine's `pipeline.harvest` span total in the window (device
wait, tail, fence and assume of each wave), per 1,000 pods bound in it."""

from metrics._common import span_ms_per_kpod


def read(ctx):
    return span_ms_per_kpod(ctx, ("pipeline.harvest",))
