"""The scheduler's `sched.sync` span total in the window (draining the
watch events into cache and queue, the watch wait left out), per 1,000
pods bound in it."""

from metrics._common import span_ms_per_kpod


def read(ctx):
    return span_ms_per_kpod(ctx, ("sched.sync",))
