"""Admission: milliseconds per answered query from submission until its
batch left admission (QueryRequest t_flushed - t_submit), over the measured
phase of a closed-loop cell (ServeStats admission_wait_ms delta).  Nothing
is read from a program that does not stamp it."""


def read(window):
    if window.loop != "closed" or "admission_wait_ms" not in window.serve:
        return None
    return window.per_query("admission_wait_ms")
