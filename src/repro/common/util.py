from __future__ import annotations

import numpy as np


def stable_unique(values: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """np.unique returning (unique_sorted, inverse) with int32 inverse."""
    uniq, inv = np.unique(values, return_inverse=True)
    return uniq, inv.astype(np.int32)


def ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def next_pow2(x: int) -> int:
    return 1 if x <= 1 else 1 << (x - 1).bit_length()
