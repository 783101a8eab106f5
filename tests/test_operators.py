"""Bounded-buffer jnp operators vs numpy semantics."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.engine import operators as ops


def test_scan_pattern_wildcards():
    rng = np.random.default_rng(0)
    table = rng.integers(0, 20, (64, 3)).astype(np.int32)
    trow = np.ones(64, bool)
    trow[50:] = False
    for pattern in ([5, -1, -1], [-1, 3, -1], [-1, 3, 7], [2, 1, -1]):
        data, valid, ovf = ops.scan_pattern(jnp.asarray(table), jnp.asarray(trow),
                                            jnp.asarray(pattern, jnp.int32), 32, (0, 2))
        s, p, o = pattern
        m = trow.copy()
        if s >= 0:
            m &= table[:, 0] == s
        if p >= 0:
            m &= table[:, 1] == p
        if o >= 0:
            m &= table[:, 2] == o
        want = table[m][:, [0, 2]]
        got = np.asarray(data)[np.asarray(valid)]
        assert not bool(ovf)
        np.testing.assert_array_equal(np.sort(got, axis=0), np.sort(want[:32], axis=0))


def test_scan_pattern_overflow_flag():
    table = np.zeros((64, 3), np.int32)
    trow = np.ones(64, bool)
    _, valid, ovf = ops.scan_pattern(jnp.asarray(table), jnp.asarray(trow),
                                     jnp.asarray([-1, -1, -1], jnp.int32), 16, (0, 1))
    assert bool(ovf) and int(np.asarray(valid).sum()) == 16


@pytest.mark.parametrize("cap", [64, 256])
def test_merge_join_matches_numpy(cap):
    rng = np.random.default_rng(cap)
    L, R = 48, 56
    left = rng.integers(0, 12, (64, 2)).astype(np.int32)
    right = rng.integers(0, 12, (64, 2)).astype(np.int32)
    lvalid = np.arange(64) < L
    rvalid = np.arange(64) < R
    data, valid, ovf = ops.merge_join(jnp.asarray(left), jnp.asarray(lvalid), 0,
                                      jnp.asarray(right), jnp.asarray(rvalid), 1, cap)
    got = {tuple(r) for r in np.asarray(data)[np.asarray(valid)].tolist()}
    want = set()
    for i in range(L):
        for j in range(R):
            if left[i, 0] == right[j, 1]:
                want.add(tuple(left[i].tolist() + right[j].tolist()))
    if not bool(ovf):
        assert got == want
    else:
        assert got <= want


def test_distinct():
    rng = np.random.default_rng(5)
    rel = rng.integers(0, 4, (32, 2)).astype(np.int32)
    valid = np.arange(32) < 30
    data, v, ovf = ops.distinct(jnp.asarray(rel), jnp.asarray(valid), 32)
    got = [tuple(r) for r in np.asarray(data)[np.asarray(v)].tolist()]
    want = {tuple(r) for r in rel[:30].tolist()}
    assert len(got) == len(set(got)) == len(want)
    assert set(got) == want


def test_semi_bind():
    rel = np.array([[1, 10], [2, 20], [3, 30], [4, 40]], np.int32)
    valid = np.array([True, True, True, False])
    keys = np.array([2, 4, 9], np.int32)
    kvalid = np.array([True, True, False])
    data, v, ovf = ops.semi_bind(jnp.asarray(rel), jnp.asarray(valid),
                                 jnp.asarray(keys), jnp.asarray(kvalid), 0, 4)
    got = np.asarray(data)[np.asarray(v)]
    np.testing.assert_array_equal(got, [[2, 20]])


# --------------------------------------------------------------------------
# Group-algebra operators (OPTIONAL / UNION / FILTER twins)
# --------------------------------------------------------------------------

def test_left_merge_join_matches_numpy():
    rng = np.random.default_rng(9)
    left = rng.integers(0, 16, (32, 2)).astype(np.int32)   # keys 8..15 miss
    right = rng.integers(0, 8, (32, 2)).astype(np.int32)
    lvalid = np.arange(32) < 20
    rvalid = np.arange(32) < 24
    data, valid, ovf = ops.left_merge_join(
        jnp.asarray(left), jnp.asarray(lvalid), 0,
        jnp.asarray(right), jnp.asarray(rvalid), 1, 256)
    got = sorted(tuple(r) for r in np.asarray(data)[np.asarray(valid)].tolist())
    want = []
    for i in range(20):
        matches = [j for j in range(24) if right[j, 1] == left[i, 0]]
        if matches:
            for j in matches:
                want.append(tuple(left[i].tolist() + right[j].tolist()))
        else:                                   # unmatched: UNDEF-padded row
            want.append(tuple(left[i].tolist() + [ops.UNDEF, ops.UNDEF]))
    assert not bool(ovf)
    assert got == sorted(want)
    assert any(ops.UNDEF in r for r in got)     # the pad path is exercised


def test_left_merge_join_overflow_flag():
    left = np.zeros((16, 1), np.int32)
    right = np.zeros((16, 1), np.int32)
    valid = np.ones(16, bool)
    _, v, ovf = ops.left_merge_join(jnp.asarray(left), jnp.asarray(valid), 0,
                                    jnp.asarray(right), jnp.asarray(valid), 0, 64)
    assert bool(ovf) and int(np.asarray(v).sum()) == 64  # 256 rows, cap 64


def test_align_columns_and_union_rels():
    a = np.array([[1, 2], [3, 4], [0, 0]], np.int32)
    av = np.array([True, True, False])
    b = np.array([[5], [6], [7]], np.int32)
    bv = np.array([True, False, True])
    # shared schema (x, y, z): a has (x, y), b has (y,) only
    aa, av2 = ops.align_columns(jnp.asarray(a), jnp.asarray(av), (0, 1, -1))
    bb, bv2 = ops.align_columns(jnp.asarray(b), jnp.asarray(bv), (-1, 0, -1))
    data, v, ovf = ops.union_rels(aa, av2, bb, bv2, 8)
    got = {tuple(r) for r in np.asarray(data)[np.asarray(v)].tolist()}
    U = ops.UNDEF
    assert not bool(ovf)
    assert got == {(1, 2, U), (3, 4, U), (U, 5, U), (U, 7, U)}


def test_compare_mask_two_valued_and_filter_rows():
    U = ops.UNDEF
    rel = np.array([[3, 3], [3, 5], [5, 3], [U, 3], [3, U]], np.int32)
    valid = np.ones(5, bool)
    zero = jnp.int32(0)
    jrel, jv = jnp.asarray(rel), jnp.asarray(valid)
    for op_s, fn in [("=", np.equal), ("!=", np.not_equal), ("<", np.less),
                     ("<=", np.less_equal), (">", np.greater),
                     (">=", np.greater_equal)]:
        m = ops.compare_mask(jrel, jv, ops.OP_CODES[op_s], 0, 1, zero, zero)
        want = fn(rel[:, 0], rel[:, 1]) & (rel[:, 0] != U) & (rel[:, 1] != U)
        np.testing.assert_array_equal(np.asarray(m), want)
    # UNDEF rows are false even for != (two-valued semantics)
    m = ops.compare_mask(jrel, jv, ops.OP_CODES["!="], 0, 1, zero, zero)
    assert not bool(np.asarray(m)[3]) and not bool(np.asarray(m)[4])
    # constant side + compaction
    m = ops.compare_mask(jrel, jv, ops.OP_CODES[">="], 0, -1, zero, jnp.int32(4))
    data, v, ovf = ops.filter_rows(jrel, jv, m, 5)
    got = np.asarray(data)[np.asarray(v)]
    np.testing.assert_array_equal(got, [[5, 3]])
    assert not bool(ovf)


# --------------------------------------------------------------------------
# Search-free joins == the binary-search formulation they replaced
# --------------------------------------------------------------------------

_BIG = 2**31 - 1


def _searchsorted_join(left, lvalid, lkey, right, rvalid, rkey, cap, pad):
    """The bounded joins as they were written with ``jnp.searchsorted``
    (inner join, or OPTIONAL when ``pad``): the oracle of row order, row
    validity and overflow."""
    L = left.shape[0]
    rk = jnp.where(rvalid, right[:, rkey], _BIG)
    order = jnp.argsort(rk, stable=True)
    right_s, rvalid_s, rk_s = right[order], rvalid[order], rk[order]
    lk = jnp.where(lvalid, left[:, lkey], _BIG - 1)
    start = jnp.searchsorted(rk_s, lk, side="left")
    end = jnp.searchsorted(rk_s, lk, side="right")
    counts = jnp.where(lvalid, end - start, 0)
    outcnt = jnp.where(lvalid, jnp.maximum(counts, 1), 0) if pad else counts
    offsets = jnp.cumsum(outcnt)
    total = offsets[-1]
    t = jnp.arange(cap)
    li = jnp.clip(jnp.searchsorted(offsets, t, side="right"), 0, L - 1)
    rank = t - jnp.where(li > 0, offsets[li - 1], 0)
    ri = jnp.clip(start[li] + rank, 0, right.shape[0] - 1)
    if pad:
        valid = (t < total) & lvalid[li]
        rdata = jnp.where((counts[li] > 0)[:, None], right_s[ri], jnp.int32(ops.UNDEF))
    else:
        valid = (t < total) & lvalid[li] & rvalid_s[ri]
        rdata = right_s[ri]
    data = jnp.concatenate([left[li], rdata], axis=1)
    return jnp.where(valid[:, None], data, 0), valid, total > cap


# (L, R, keys, left valid share, right valid share, cap): keys are drawn
# from range(keys), or from the three largest int32 values when keys < 0
_JOIN_CASES = {
    "duplicates": (64, 64, 8, 0.8, 0.8, 1024),
    "duplicates_overflow": (64, 64, 4, 0.8, 0.8, 128),
    "right_13x_left": (32, 13 * 32, 16, 0.7, 0.9, 2048),
    "right_13x_left_overflow": (32, 13 * 32, 4, 0.9, 0.9, 64),
    "left_longer": (200, 24, 10, 0.9, 0.6, 512),
    "left_all_invalid": (48, 48, 6, 0.0, 0.9, 256),
    "right_all_invalid": (48, 48, 6, 0.9, 0.0, 256),
    "both_all_invalid": (16, 16, 4, 0.0, 0.0, 32),
    "one_key_fills_cap": (16, 8, 1, 1.0, 1.0, 128),
    "one_left_row": (1, 40, 3, 1.0, 0.8, 64),
    "one_right_row": (40, 1, 3, 0.8, 1.0, 64),
    "largest_keys": (32, 32, -1, 0.8, 0.8, 512),
}


def _join_inputs(case, seed):
    L, R, keys, lshare, rshare, cap = _JOIN_CASES[case]
    rng = np.random.default_rng(seed)
    if keys < 0:
        draw = lambda n: rng.choice(np.array([_BIG - 2, _BIG - 1, _BIG], np.int32), (n, 2))
    else:
        draw = lambda n: rng.integers(0, keys, (n, 2)).astype(np.int32)
    left, right = draw(L), draw(R)
    lvalid, rvalid = rng.random(L) < lshare, rng.random(R) < rshare
    return (jnp.asarray(left), jnp.asarray(lvalid), 1, jnp.asarray(right),
            jnp.asarray(rvalid), 0, cap)


def _assert_same(got, want):
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("pad", [False, True], ids=["merge_join", "left_merge_join"])
@pytest.mark.parametrize("case", sorted(_JOIN_CASES))
def test_join_equals_searchsorted_formulation(case, pad):
    """Bit for bit the rows, their order, their validity and the overflow
    flag of the binary-search formulation, on three seeds."""
    join = ops.left_merge_join if pad else ops.merge_join
    for seed in range(3):
        args = _join_inputs(case, seed)
        _assert_same(join(*args), _searchsorted_join(*args, pad=pad))


def test_join_cases_reach_overflow_and_the_exact_fill():
    """The cases above hold overflowing joins and one whose matches fill
    the capacity exactly, where ``total > cap`` is false."""
    flags = {c: bool(ops.merge_join(*_join_inputs(c, 0))[2]) for c in _JOIN_CASES}
    assert flags["duplicates_overflow"] and flags["right_13x_left_overflow"]
    assert not flags["duplicates"] and not flags["one_key_fills_cap"]
    _, valid, _ = ops.merge_join(*_join_inputs("one_key_fills_cap", 0))
    assert int(np.asarray(valid).sum()) == 128


@pytest.mark.parametrize("n, runs", [(1, 1), (12, 1), (12, 12), (50, 7)])
def test_run_start_marks_each_runs_first_position(n, runs):
    rng = np.random.default_rng(n + runs)
    keys = np.sort(rng.integers(0, runs, n)).astype(np.int32)
    pos = np.arange(n)
    got = np.asarray(ops.run_start(jnp.asarray(keys), jnp.asarray(pos)))
    np.testing.assert_array_equal(got, np.searchsorted(keys, keys, side="left"))
