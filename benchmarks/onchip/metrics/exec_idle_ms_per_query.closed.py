"""Execution: milliseconds of the traced window in which the chip was idle
while the innermost span open was the executor's (``repro.engine.*``:
readback, collect, assemble), per query answered in the window (answers
before the close, plus the done share of the one in service at the close),
in a closed-loop cell: host work in the executor that holds the chip back."""


def read(window):
    if window.trace is None or window.loop != "closed" or not window.completed_in_window:
        return None
    idle = getattr(window.trace, "idle_s_in_engine", None)
    return None if idle is None else 1e3 * idle / window.completed_in_window
