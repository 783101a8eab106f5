"""Device: milliseconds the star programs (``jit_fed_star<n>``, from the
trace's module line) ran on the chip in the traced window, per query
answered in it (answers before the close, plus the done share of the one in
service at the close), in an open-loop cell.  Nothing is read where no star
program ran."""


def read(window):
    if window.trace is None or window.loop != "open" or not window.completed_in_window:
        return None
    star = [s for p, s in getattr(window.trace, "device_s_by_program", {}).items()
            if p.startswith("jit_fed_star")]
    return 1e3 * sum(star) / window.completed_in_window if star else None
