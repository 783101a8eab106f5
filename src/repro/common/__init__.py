from repro.common.hashing import fnv1a64, splitmix64
from repro.common.util import stable_unique

__all__ = ["fnv1a64", "splitmix64", "stable_unique"]
