"""Compile: in-process compile requests during the measured phase, whether
XLA compiled the program or loaded it from the persistent cache.  A
warmed-up window has none."""


def read(window):
    return window.compile_requests
