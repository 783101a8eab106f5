"""Device: share of the traced window, in %, in which no operation ran on
the chip (1 - union of the device operations' intervals / window), in a
closed-loop cell."""


def read(window):
    if window.trace is None or window.loop != "closed":
        return None
    return 100.0 * window.trace.idle_share
