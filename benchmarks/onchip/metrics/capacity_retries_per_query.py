"""Execution: capacity doublings the executor paid per answered query
(DistMetrics.capacity_retries), each a whole re-run of the plan."""


def read(window):
    n = window.serve["n_served"]
    return window.capacity_retries / n if n else None
