"""Admission: queries per executed batch over the measured phase
(ServeStats n_served / n_steps deltas)."""


def read(window):
    steps = window.serve["n_steps"]
    return window.serve["n_served"] / steps if steps else None
