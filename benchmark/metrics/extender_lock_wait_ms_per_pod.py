"""The backend's `extender.lock_wait` span total in the window (each
verb's wait for `TPUExtenderBackend._lock`), per scheduleOne."""

from metrics._common import span_ms_per_pod


def read(ctx):
    return span_ms_per_pod(ctx, ("extender.lock_wait",))
