"""The comparison that decides ``correct``.

Three layers are held to what the configuration guarantees:

- admission: every request due in the window is answered exactly once
  (``lost``: never answered, a minute past the close at the latest;
  ``repeated``: reported twice);
- planning: every plan reaches the device executor (``off_device``: an
  answer from a fallback, or without the device executor's metrics);
- execution: every answer equals the plain reference's as a multiset of
  projected rows, DISTINCT respected (``wrong``); a request that raised
  (such as ``CapacityExceededError``) or fell back has no answer to compare
  and is counted ``failed``.

Each is an exact count with the limit 0.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from obench.reference import Reference, canonical

LIMITS = {"wrong": 0, "failed": 0, "lost": 0, "repeated": 0, "off_device": 0}


@dataclass
class Checks:
    wrong: int
    failed: int
    lost: int
    repeated: int
    off_device: int
    compared: int

    @property
    def correct(self) -> bool:
        return self.compared > 0 and all(getattr(self, k) <= v for k, v in LIMITS.items())

    def lines(self) -> list[str]:
        return [f"check {k} {getattr(self, k)} limit {v}" for k, v in LIMITS.items()] \
            + [f"check compared {self.compared} (answers held to the reference)"]

    def as_json(self) -> dict:
        return {k: {"value": getattr(self, k), "limit": v} for k, v in LIMITS.items()}


def answer_rows(rows: dict, projection) -> np.ndarray:
    """A program answer's projected rows in the reference's canonical form."""
    cols = [np.asarray(rows[v]) for v in projection]
    n = len(cols[0]) if cols else 0
    return canonical(np.stack(cols, axis=1) if cols else np.zeros((n, 0)))


def compare(ref: Reference, answered: list, failed: int, lost: int, repeated: int,
            off_device: int) -> Checks:
    """``answered``: (plain query, program rows) of every answer to hold to
    the reference; each distinct query is evaluated once."""
    want: dict = {}
    wrong = 0
    for q, rows in answered:
        if q not in want:
            want[q] = ref.evaluate(q)
        try:
            got = answer_rows(rows, q.projection)
        except (KeyError, ValueError):
            wrong += 1
            continue
        wrong += not np.array_equal(got, want[q])
    return Checks(wrong=wrong, failed=failed, lost=lost, repeated=repeated,
                  off_device=off_device, compared=len(answered))
