"""The engine's `pipeline.encode` span total in the window (each wave's
class encoding, inside `pipeline.dispatch`), per 1,000 pods bound in it."""

from metrics._common import span_ms_per_kpod


def read(ctx):
    return span_ms_per_kpod(ctx, ("pipeline.encode",))
