"""Median latency of every request in the window, in ms: from its due
time in an open loop, from its submission in a closed loop."""


def read(window):
    return window.percentile_ms(50)
