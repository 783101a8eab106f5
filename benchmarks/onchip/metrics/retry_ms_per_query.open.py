"""Execution: host milliseconds per answered query spent in executor
attempts that overflowed and were run again at twice the capacity, device
time included, over the measured phase of an open-loop cell (ServeStats
retry_ms delta, the sum of DistMetrics.retry_ms).  Nothing is read from a
program that does not count it."""


def read(window):
    if window.loop != "open" or "retry_ms" not in window.serve:
        return None
    return window.per_query("retry_ms")
