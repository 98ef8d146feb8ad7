"""The scheduler's `bind.flush` span total in the window (the bulk bind
write and its bookkeeping), per 1,000 pods bound in it."""

from metrics._common import span_ms_per_kpod


def read(ctx):
    return span_ms_per_kpod(ctx, ("bind.flush",))
