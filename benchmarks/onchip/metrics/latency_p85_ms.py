"""85th-percentile latency of every request due in the window, in ms, from
its due time; a failed request counts as infinitely late.  Nothing is read
where fewer than ten requests lie beyond it."""


def read(window):
    return window.percentile_ms(85)
