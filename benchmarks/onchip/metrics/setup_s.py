"""Seconds from process start to the start of the window: data generation,
statistics, loading the tables onto the device, and the warm-up that runs
every template once (compiling, or loading from the persistent cache, every
program the window uses)."""


def read(window):
    return window.setup_s
