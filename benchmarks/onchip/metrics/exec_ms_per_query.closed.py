"""Execution: host milliseconds in the executor per answered query, device
waits included, over the measured phase of a closed-loop cell (ServeStats
exec_ms delta)."""


def read(window):
    return window.per_query("exec_ms") if window.loop == "closed" else None
