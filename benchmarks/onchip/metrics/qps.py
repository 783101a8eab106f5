"""Queries answered per second over the window: every answer that came
before the close, plus the share of the one being executed at the close
that was done by then, over the length of the window."""


def read(window):
    return window.completed_in_window / window.seconds
