"""Time the ways the executor's merge join and exchange can find match
ranges and ranks, each alone on one device, at the on-chip cells' shapes.

    python3 benchmarks/join_search_bench.py [--small] [--reps N]

Three searches are timed, each by every candidate formulation, and the
candidates' results are checked equal to the binary search they replace:

* ``ranges``: for each left key, how many right keys equal it and where
  they are (``searchsorted`` left and right on the sorted right keys);
* ``owner``: the left row that owns output slot ``t`` of the join, from the
  row's cumulative match counts;
* ``rank``: each row's rank within its run of a sorted bucket array (the
  exchange's position in the destination bucket);

and the whole bounded join, built from each pair of ``ranges`` and
``owner`` candidates.  Shapes: a star program joins ``slots`` sources of
``cap`` rows each (vmapped), the exchange probes ``cap`` rows against the
``slots * cap`` build rows.  ``--small`` runs tiny shapes (a CPU rehearsal:
its times say nothing about the chip).  One JSON line per case and
candidate: the median milliseconds of ``--reps`` timed calls after a
first call, the first call's seconds (it compiles), and the device.  On one
TPU v5e the full run takes over 20 minutes, most of it compiling.
"""
import argparse
import json
import os
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "src"))

from repro.engine import operators as ops  # noqa: E402

BIG = 2**31 - 1


# -- ranges ----------------------------------------------------------------

def ranges_scan(lk, rk):
    """Sorted right keys, then two binary searches (``method="scan"``)."""
    order = jnp.argsort(rk, stable=True)
    rk_s = rk[order]
    start = jnp.searchsorted(rk_s, lk, side="left")
    end = jnp.searchsorted(rk_s, lk, side="right")
    return end - start, start, order


def ranges_sort_method(lk, rk):
    """As ``ranges_scan`` with ``searchsorted(method="sort")``."""
    order = jnp.argsort(rk, stable=True)
    rk_s = rk[order]
    start = jnp.searchsorted(rk_s, lk, side="left", method="sort")
    end = jnp.searchsorted(rk_s, lk, side="right", method="sort")
    return end - start, start, order


def _merged(lk, rk):
    """One stable sort of right keys then left keys: at an equal key the
    right rows come first.  Per merged position: the original index, the
    count of equal right keys before it, and the run's first position."""
    R, n = rk.shape[0], rk.shape[0] + lk.shape[0]
    pos = jnp.arange(n, dtype=jnp.int32)
    key, idx = lax.sort((jnp.concatenate([rk, lk]), pos), num_keys=1, is_stable=True)
    is_right = (idx < R).astype(jnp.int32)
    n_right = jnp.cumsum(is_right) - is_right
    return idx, n_right - ops.run_start(key, n_right), ops.run_start(key, pos)


def ranges_sort_back(lk, rk):
    """The merged sort, then a second sort by original index back to the
    left's order (``ops.match_runs``, the one the executor runs).
    ``first`` is a merged position, ``idx`` maps it to the right row."""
    return ops.match_runs(lk, rk)


def ranges_scatter_back(lk, rk):
    """The merged sort, then a scatter (unique indices) back to the left's
    order."""
    R, L = rk.shape[0], lk.shape[0]
    idx, count, first = _merged(lk, rk)
    dst = jnp.where(idx >= R, idx - R, L + idx)       # right rows: out of bounds
    back = lambda v: jnp.zeros(L, v.dtype).at[dst].set(v, mode="drop",
                                                       unique_indices=True)
    return back(count), back(first), idx


RANGES = {"scan": ranges_scan, "sort_method": ranges_sort_method,
          "sort_back": ranges_sort_back, "scatter_back": ranges_scatter_back}


def right_rows(kind, first, rank, idx, R):
    """Right row of match ``rank`` of a left row whose ranges are
    ``first``: a position in the sorted right keys (``scan``,
    ``sort_method``) or in the merged sort (the others)."""
    if kind in ("scan", "sort_method"):
        return idx[jnp.clip(first + rank, 0, R - 1)]
    return jnp.clip(idx[jnp.clip(first + rank, 0, idx.shape[0] - 1)], 0, R - 1)


# -- owner -----------------------------------------------------------------

def owner_scan(counts, offsets, cap):
    return jnp.searchsorted(offsets, jnp.arange(cap), side="right")


def owner_marks(counts, offsets, cap):
    """Each row with a match marks its first slot (unique), then a running
    maximum (``ops.slot_owner``, the one the executor runs)."""
    return ops.slot_owner(counts, offsets, cap)


def owner_histogram(counts, offsets, cap):
    """Rows counted by their end slot, then a running sum."""
    h = jnp.zeros(cap, jnp.int32).at[jnp.minimum(offsets, cap)].add(1, mode="drop")
    return jnp.cumsum(h)


def owner_sort(counts, offsets, cap):
    """A merge by sort of the end slots and the output slots, then a second
    sort back to slot order."""
    L = offsets.shape[0]
    n = L + cap
    pos = jnp.arange(n, dtype=jnp.int32)
    keys = jnp.concatenate([offsets.astype(jnp.int32), jnp.arange(cap, dtype=jnp.int32)])
    _, idx = lax.sort((keys, pos), num_keys=1, is_stable=True)
    ends = jnp.cumsum((idx < L).astype(jnp.int32))
    _, ends = lax.sort((idx, ends), num_keys=1)
    return ends[L:]


OWNER = {"scan": owner_scan, "marks": owner_marks, "histogram": owner_histogram,
         "sort": owner_sort}


# -- rank in bucket ----------------------------------------------------------

def rank_scan(sorted_dest):
    n = sorted_dest.shape[0]
    return jnp.arange(n) - jnp.searchsorted(sorted_dest, sorted_dest, side="left")


def rank_cummax(sorted_dest):
    """A running maximum of the runs' first positions (``ops.run_start``,
    the one the exchange runs)."""
    pos = jnp.arange(sorted_dest.shape[0], dtype=jnp.int32)
    return pos - ops.run_start(sorted_dest, pos)


RANK = {"scan": rank_scan, "cummax": rank_cummax}


# -- the whole join ----------------------------------------------------------

def join(ranges, owner, left, lvalid, right, rvalid, cap):
    """``ops.merge_join`` on column 0 of both sides, with the given
    searches."""
    L, R = left.shape[0], right.shape[0]
    rk = jnp.where(rvalid, right[:, 0], BIG)
    lk = jnp.where(lvalid, left[:, 0], BIG - 1)
    count, first, idx = RANGES[ranges](lk, rk)
    counts = jnp.where(lvalid, count, 0)
    offsets = jnp.cumsum(counts)
    total = offsets[-1]
    t = jnp.arange(cap)
    li = jnp.clip(OWNER[owner](counts, offsets, cap), 0, L - 1)
    rank = t - (offsets - counts)[li]
    ri = right_rows(ranges, first[li], rank, idx, R)
    valid = (t < total) & lvalid[li] & rvalid[ri]
    data = jnp.concatenate([left[li], right[ri]], axis=1)
    return jnp.where(valid[:, None], data, 0), valid, total > cap


# -- cases -------------------------------------------------------------------

def relation(rng, n, keys, valid_share=0.8):
    data = rng.integers(0, keys, (n, 2)).astype(np.int32)
    valid = rng.random(n) < valid_share
    return data, valid


def cases(small: bool):
    """(name, slots, cap, star): a star joins each of ``slots`` sources at
    ``cap`` rows; the exchange probes ``cap`` rows against ``slots * cap``."""
    if small:
        return [("star9.cap64", 9, 64, True), ("exchange9.cap64", 9, 64, False)]
    out = []
    for slots, cap in ((13, 131072), (9, 2048), (9, 65536)):
        out.append((f"star{slots}.cap{cap}", slots, cap, True))
        out.append((f"exchange{slots}.cap{cap}", slots, cap, False))
    return out


def timed(fn, args, reps):
    """Median and all of ``reps`` timed calls (ms), and the first call's
    seconds, which compile."""
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    first_s = time.perf_counter() - t0
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(times), times, first_s


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--small", action="store_true")
    ap.add_argument("--reps", type=int, default=7)
    args = ap.parse_args()
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind}
    rng = np.random.default_rng(14)

    def emit(case, search, cand, timing, same):
        ms, times, first_s = timing
        print(json.dumps({"case": case, "search": search, "candidate": cand,
                          "median_ms": ms, "ms": times, "first_call_s": first_s,
                          "equal": same, "device": device}), flush=True)

    for name, slots, cap, star in cases(args.small):
        keys = cap // 2
        if star:
            shape_l = shape_r = (slots, cap)
        else:
            shape_l, shape_r = (cap,), (slots * cap,)
        left, lvalid = relation(rng, int(np.prod(shape_l)), keys)
        right, rvalid = relation(rng, int(np.prod(shape_r)), keys)
        left = jnp.asarray(left.reshape(*shape_l, 2))
        right = jnp.asarray(right.reshape(*shape_r, 2))
        lvalid = jnp.asarray(lvalid.reshape(shape_l))
        rvalid = jnp.asarray(rvalid.reshape(shape_r))
        lift = jax.vmap if star else (lambda f: f)
        lk = jnp.where(lvalid, left[..., 0], BIG - 1)
        rk = jnp.where(rvalid, right[..., 0], BIG)

        # ranges: equal when every left row sees the same right rows
        def matches(kind):
            def f(lk, rk):
                count, first, idx = RANGES[kind](lk, rk)
                r = jnp.arange(4)[None, :]
                rows = right_rows(kind, first[:, None], r, idx, rk.shape[0])
                return count, jnp.where(r < count[:, None], rows, -1)
            return jax.jit(lift(f))
        want = None
        for kind in RANGES:
            fn = jax.jit(lift(RANGES[kind]))
            timing = timed(fn, (lk, rk), args.reps)
            got = jax.device_get(matches(kind)(lk, rk))
            same = True if want is None else all(np.array_equal(a, b) for a, b in zip(got, want))
            want = got if want is None else want
            emit(name, "ranges", kind, timing, same)

        # owner: counts of a join whose total fills about the capacity
        L = shape_l[-1]
        counts = rng.integers(0, 3, shape_l).astype(np.int32)
        counts = jnp.asarray(counts)
        offsets = jnp.cumsum(counts, axis=-1)
        ref = None
        for kind in OWNER:
            fn = jax.jit(lift(lambda c, o, k=kind: OWNER[k](c, o, cap)))
            timing = timed(fn, (counts, offsets), args.reps)
            got = np.asarray(fn(counts, offsets))
            total = np.asarray(offsets)[..., -1:]
            live = np.arange(cap) < total
            got = np.where(live, np.clip(got, 0, L - 1), -1)
            same = True if ref is None else bool(np.array_equal(got, ref))
            ref = got if ref is None else ref
            emit(name, "owner", kind, timing, same)

        if not star:
            dest = jnp.sort(jnp.asarray(rng.integers(0, 2, slots * cap).astype(np.int32)))
            ref = None
            for kind in RANK:
                fn = jax.jit(RANK[kind])
                timing = timed(fn, (dest,), args.reps)
                got = np.asarray(fn(dest))
                same = True if ref is None else bool(np.array_equal(got, ref))
                ref = got if ref is None else ref
                emit(name, "rank", kind, timing, same)

        ref = None
        for r_kind, o_kind in (("scan", "scan"), ("sort_back", "marks"),
                               ("scatter_back", "marks"), ("sort_back", "histogram"),
                               ("scatter_back", "histogram"), ("sort_back", "sort")):
            fn = jax.jit(lift(lambda l, lv, r, rv, a=r_kind, b=o_kind:
                              join(a, b, l, lv, r, rv, cap)))
            timing = timed(fn, (left, lvalid, right, rvalid), args.reps)
            got = jax.device_get(fn(left, lvalid, right, rvalid))
            same = True if ref is None else all(np.array_equal(a, b) for a, b in zip(got, ref))
            ref = got if ref is None else ref
            emit(name, "join", f"{r_kind}+{o_kind}", timing, same)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
