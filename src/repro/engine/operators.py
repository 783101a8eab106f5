"""Bounded-buffer relational operators in pure jnp (DESIGN.md D1).

Accelerators need static shapes, so every operator takes/returns fixed-
capacity relations:

    rel = (data: (CAP, NCOLS) int32, valid: (CAP,) bool, overflow: bool[])

Rows beyond the live count are zeroed and invalid. Overflow flags propagate to
the host, which runs the plan again with a doubled capacity
(``DistributedEngine.execute``) and never keeps a truncated result.
Compaction uses stable sorts instead of gathers-with-dynamic-shapes; joins use
the counts/offsets construction that ``kernels/join_count`` accelerates, and
find each left row's matches and each output row's left row with sorts,
running sums and running maxima (``match_runs``, ``slot_owner``), never with
a sequential binary search: on the TPU every step of one is a dependent
gather over all rows.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax


def compact(mask: jax.Array, cap: int) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Indices of the first ``cap`` True rows (stable), their validity, and an
    overflow flag."""
    n = mask.shape[0]
    order = jnp.argsort(~mask, stable=True)
    if n >= cap:
        idx = order[:cap]
    else:
        idx = jnp.concatenate([order, jnp.zeros(cap - n, order.dtype)])
    total = jnp.sum(mask)
    valid = jnp.arange(cap) < jnp.minimum(total, n)
    return idx, valid, total > cap


@partial(jax.jit, static_argnames=("cap", "out_cols"))
def scan_pattern(table: jax.Array, trow: jax.Array, pattern: jax.Array,
                 cap: int, out_cols: tuple[int, ...]) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Match (s, p, o) with -1 wildcards against table (N, 3) rows (invalid
    rows marked by ``trow`` False). Returns bounded relation over the columns
    in ``out_cols`` (subset of (0, 1, 2))."""
    s, p, o = pattern[0], pattern[1], pattern[2]
    m = trow
    m &= (s < 0) | (table[:, 0] == s)
    m &= (p < 0) | (table[:, 1] == p)
    m &= (o < 0) | (table[:, 2] == o)
    idx, valid, ovf = compact(m, cap)
    data = table[idx][:, list(out_cols)]
    data = jnp.where(valid[:, None], data, 0)
    return data, valid, ovf


@partial(jax.jit, static_argnames=("cap",))
def semi_bind(rel: jax.Array, valid: jax.Array, keys: jax.Array, kvalid: jax.Array,
              key_col: int, cap: int):
    """Bind-join filter: keep rel rows whose ``key_col`` appears in ``keys``
    (the shipped bindings). Mirrors dispatching a subquery with VALUES."""
    eq = (rel[:, key_col][:, None] == keys[None, :]) & kvalid[None, :]
    m = valid & eq.any(axis=1)
    idx, v, ovf = compact(m, cap)
    return jnp.where(v[:, None], rel[idx], 0), v, ovf


def run_start(keys: jax.Array, values: jax.Array) -> jax.Array:
    """``values`` at the first position of each element's run of equal
    ``keys``, for sorted ``keys`` and non-negative, non-decreasing
    ``values``: a running maximum over the runs' first positions."""
    begins = jnp.concatenate([jnp.ones(1, bool), keys[1:] != keys[:-1]])
    return lax.cummax(jnp.where(begins, values, 0))


def match_runs(lkey: jax.Array, rkey: jax.Array):
    """Where each left key's equal right keys are, by sorting both sides'
    keys together.

    One stable sort of the right keys followed by the left keys puts, at an
    equal key, every right row before every left row, each side in its own
    order; a left row matches the right rows from its key's first position
    up to itself.  A second sort, by original position, brings the counts
    back to the left's order.  Returns ``(count, first, rows)``: left row
    ``i`` matches ``count[i]`` right rows, and the ``r``-th of them in the
    right's order is right row ``rows[first[i] + r]``."""
    R = rkey.shape[0]
    pos = jnp.arange(R + lkey.shape[0], dtype=jnp.int32)
    key, rows = lax.sort((jnp.concatenate([rkey, lkey]), pos), num_keys=1,
                         is_stable=True)
    is_right = (rows < R).astype(jnp.int32)
    n_right = jnp.cumsum(is_right) - is_right        # right rows before each
    first = run_start(key, pos)
    count = n_right - run_start(key, n_right)
    _, count, first = lax.sort((rows, count, first), num_keys=1)
    return count[R:], first[R:], rows


def slot_owner(counts: jax.Array, offsets: jax.Array, cap: int) -> jax.Array:
    """The row that owns each of ``cap`` output slots when row ``i`` fills
    the ``counts[i]`` slots that end at ``offsets = cumsum(counts)``; right
    for every slot below ``offsets[-1]``.  Each row that fills a slot marks
    its first one (no two rows mark the same slot), and a running maximum
    carries the mark over the row's other slots."""
    row = jnp.arange(counts.shape[0], dtype=jnp.int32)
    begin = offsets - counts
    slot = jnp.where((counts > 0) & (begin < cap), begin, cap + row)
    marks = jnp.zeros(cap, jnp.int32).at[slot].set(row, mode="drop",
                                                  unique_indices=True)
    return lax.cummax(marks)


def _expand(left, right, first, rows, counts, offsets, cap):
    """Output slot ``t``'s left row and the right row of its match (by its
    rank among its left row's slots), for every ``t`` below
    ``offsets[-1]``; rows past it are clipped into range."""
    li = slot_owner(counts, offsets, cap)
    at = (first - (offsets - counts))[li] + jnp.arange(cap)
    ri = rows[jnp.clip(at, 0, rows.shape[0] - 1)]
    return li, jnp.clip(ri, 0, right.shape[0] - 1)


@partial(jax.jit, static_argnames=("cap",))
def merge_join(left: jax.Array, lvalid: jax.Array, lkey: int,
               right: jax.Array, rvalid: jax.Array, rkey: int,
               cap: int):
    """Inner join on one key column with bounded output.

    Finds each left row's run of equal right keys (``match_runs``), gives
    left row ``i`` as many output rows as it has matches, in the left's
    order (offsets by a running sum), and materializes output row ``t`` from
    its left row (``slot_owner``) and its rank among that row's matches, in
    the right's order — no dynamic shapes, no binary search.
    Output columns: left cols ++ right cols (join key duplicated).
    """
    # invalid rows take the largest keys, right ones above left ones, so an
    # invalid left row matches no right row
    BIG = jnp.int32(2**31 - 1)
    rk = jnp.where(rvalid, right[:, rkey], BIG)
    lk = jnp.where(lvalid, left[:, lkey], BIG - 1)
    count, first, rows = match_runs(lk, rk)
    counts = jnp.where(lvalid, count, 0)
    offsets = jnp.cumsum(counts)
    total = offsets[-1]
    li, ri = _expand(left, right, first, rows, counts, offsets, cap)
    # a slot below the total belongs to a valid left row
    valid = (jnp.arange(cap) < total) & rvalid[ri]
    data = jnp.concatenate([left[li], right[ri]], axis=1)
    data = jnp.where(valid[:, None], data, 0)
    return data, valid, total > cap


@partial(jax.jit, static_argnames=("cap",))
def distinct(rel: jax.Array, valid: jax.Array, cap: int):
    """Sort rows lexicographically and keep first occurrences."""
    keys = [rel[:, c] for c in range(rel.shape[1] - 1, -1, -1)]
    keys.append(~valid)  # invalid rows last  (most significant)
    order = jnp.lexsort(keys[::-1])
    r = rel[order]
    v = valid[order]
    first = jnp.ones(rel.shape[0], bool)
    same = jnp.all(r[1:] == r[:-1], axis=1) & v[1:] & v[:-1]
    first = first.at[1:].set(~same)
    m = v & first
    idx, vv, ovf = compact(m, cap)
    return jnp.where(vv[:, None], r[idx], 0), vv, ovf


# --------------------------------------------------------------------------
# Group-algebra operators (OPTIONAL / UNION / FILTER, docs/algebra.md)
# --------------------------------------------------------------------------

# unbound marker inside int32 columns (mirrors repro.engine.local.UNDEF)
UNDEF = -1

OP_CODES = {"=": 0, "!=": 1, "<": 2, "<=": 3, ">": 4, ">=": 5}


@partial(jax.jit, static_argnames=("cap",))
def left_merge_join(left: jax.Array, lvalid: jax.Array, lkey: int,
                    right: jax.Array, rvalid: jax.Array, rkey: int,
                    cap: int):
    """OPTIONAL on one key column with bounded output: ``merge_join`` plus
    one pad row per unmatched valid left row, right columns set to UNDEF.
    Output columns: left cols ++ right cols, like ``merge_join``."""
    BIG = jnp.int32(2**31 - 1)
    rk = jnp.where(rvalid, right[:, rkey], BIG)
    lk = jnp.where(lvalid, left[:, lkey], BIG - 1)
    count, first, rows = match_runs(lk, rk)
    counts = jnp.where(lvalid, count, 0)
    # every valid left row emits max(matches, 1) rows
    outcnt = jnp.where(lvalid, jnp.maximum(counts, 1), 0)
    offsets = jnp.cumsum(outcnt)
    total = offsets[-1]
    li, ri = _expand(left, right, first, rows, outcnt, offsets, cap)
    matched = counts[li] > 0
    valid = jnp.arange(cap) < total
    rdata = jnp.where(matched[:, None], right[ri], jnp.int32(UNDEF))
    data = jnp.concatenate([left[li], rdata], axis=1)
    data = jnp.where(valid[:, None], data, 0)
    return data, valid, total > cap


@partial(jax.jit, static_argnames=("col_map",))
def align_columns(rel: jax.Array, valid: jax.Array, col_map: tuple[int, ...]):
    """Schema alignment before ``union_rels``: output column j is input
    column ``col_map[j]``, or UNDEF where ``col_map[j] < 0`` (the variable is
    absent from this branch)."""
    cols = [rel[:, c] if c >= 0
            else jnp.full(rel.shape[0], jnp.int32(UNDEF))
            for c in col_map]
    data = jnp.stack(cols, axis=1)
    return jnp.where(valid[:, None], data, 0), valid


@partial(jax.jit, static_argnames=("cap",))
def union_rels(a: jax.Array, avalid: jax.Array, b: jax.Array, bvalid: jax.Array,
               cap: int):
    """Union of two schema-aligned bounded relations (align branches with
    ``align_columns`` first), a-rows before b-rows, stable."""
    data = jnp.concatenate([a, b], axis=0)
    valid = jnp.concatenate([avalid, bvalid])
    idx, v, ovf = compact(valid, cap)
    return jnp.where(v[:, None], data[idx], 0), v, ovf


@partial(jax.jit, static_argnames=("op", "lhs_col", "rhs_col"))
def compare_mask(rel: jax.Array, valid: jax.Array, op: int,
                 lhs_col: int, rhs_col: int,
                 lhs_const: jax.Array, rhs_const: jax.Array) -> jax.Array:
    """Row mask of one FILTER comparison (``OP_CODES``); a side is a column
    when its ``*_col >= 0``, else the ``*_const`` scalar.  Two-valued: rows
    with an UNDEF side are false.  Combine masks with jnp logical ops for
    &&/||/! and compact with ``filter_rows``."""
    n = rel.shape[0]
    lv = rel[:, lhs_col] if lhs_col >= 0 else jnp.full(n, lhs_const, jnp.int32)
    rv = rel[:, rhs_col] if rhs_col >= 0 else jnp.full(n, rhs_const, jnp.int32)
    bound = (lv != UNDEF) & (rv != UNDEF)
    # op is static, so only the requested comparison is traced
    res = [lambda: lv == rv, lambda: lv != rv, lambda: lv < rv,
           lambda: lv <= rv, lambda: lv > rv, lambda: lv >= rv][op]()
    return valid & bound & res


@partial(jax.jit, static_argnames=("cap",))
def filter_rows(rel: jax.Array, valid: jax.Array, mask: jax.Array, cap: int):
    """Compact the rows where ``mask`` holds (FILTER application)."""
    idx, v, ovf = compact(valid & mask, cap)
    return jnp.where(v[:, None], rel[idx], 0), v, ovf
