"""The share of the device's idle time over the bursts during which the
scheduling loop's thread had no program span open, from the profiler
trace (program_trace.py)."""

from program_trace import read_idle_unspanned


def read(ctx):
    return read_idle_unspanned(ctx)
