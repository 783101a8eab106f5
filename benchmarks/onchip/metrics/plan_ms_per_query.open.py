"""Planning: host milliseconds in optimize_batch per answered query, over
the measured phase of an open-loop cell (ServeStats plan_ms delta)."""


def read(window):
    return window.per_query("plan_ms") if window.loop == "open" else None
