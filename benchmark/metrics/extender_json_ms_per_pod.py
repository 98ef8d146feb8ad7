"""The extender's `extender.http.decode` and `extender.http.encode` span
totals in the window (JSON and argument decode of each request, response
serialisation and write), per scheduleOne."""

from metrics._common import span_ms_per_pod


def read(ctx):
    return span_ms_per_pod(ctx, ("extender.http.decode",
                                 "extender.http.encode"))
