"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref.py oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.join_count import join_count
from repro.kernels.seg_bitmap import NBUCKETS, seg_bitmap
from repro.kernels.sorted_intersect import sorted_intersect_weighted
from repro.kernels.summary_probe import summary_probe


@pytest.mark.parametrize("na,nb", [(256, 256), (512, 256), (256, 768), (1024, 1024)])
@pytest.mark.parametrize("overlap", [0.0, 0.3, 1.0])
def test_sorted_intersect_sweep(na, nb, overlap):
    rng = np.random.default_rng(na + nb + int(overlap * 10))
    pool = rng.choice(50_000, size=na + nb, replace=False)
    a = np.sort(pool[:na]).astype(np.int32)
    b = np.sort(rng.permutation(np.concatenate([
        rng.choice(a, size=int(overlap * min(na, nb)), replace=False) if overlap else np.empty(0, np.int32),
        pool[na: na + nb - int(overlap * min(na, nb))],
    ]))[:nb]).astype(np.int32)
    b = np.sort(np.unique(b))[:nb]
    b = np.pad(b, (0, nb - len(b)), constant_values=-2).astype(np.int32)
    aw = rng.integers(1, 5, na).astype(np.int32)
    bw = rng.integers(1, 5, nb).astype(np.int32)
    bw[b == -2] = 0
    got = sorted_intersect_weighted(jnp.asarray(a), jnp.asarray(aw), jnp.asarray(b), jnp.asarray(bw))
    want = ref.sorted_intersect_weighted_ref(jnp.asarray(a), jnp.asarray(aw), jnp.asarray(b), jnp.asarray(bw))
    assert int(got) == int(want)


def test_intersect_count_wrapper_vs_numpy():
    rng = np.random.default_rng(0)
    for trial in range(10):
        na, nb = rng.integers(1, 700, 2)
        a = np.unique(rng.choice(10_000, size=na)).astype(np.int32)
        b = np.unique(rng.choice(10_000, size=nb)).astype(np.int32)
        aw = rng.integers(1, 6, len(a)).astype(np.int32)
        bw = rng.integers(1, 6, len(b)).astype(np.int32)
        got = ops.intersect_count(a, aw, b, bw)
        common, ia, ib = np.intersect1d(a, b, assume_unique=True, return_indices=True)
        want = int((aw[ia] * bw[ib]).sum())
        assert got == want


@pytest.mark.parametrize("n,n_seg", [(256, 128), (512, 256), (1024, 128)])
def test_seg_bitmap_sweep(n, n_seg):
    rng = np.random.default_rng(n + n_seg)
    seg = np.sort(rng.integers(0, n_seg, n)).astype(np.int32)
    bucket = rng.integers(0, NBUCKETS, n).astype(np.int32)
    # pad rows with -1 segments
    pad = (-n) % 256
    seg_p = np.concatenate([seg, np.full(pad, -1, np.int32)])
    bkt_p = np.concatenate([bucket, np.zeros(pad, np.int32)])
    got = seg_bitmap(jnp.asarray(seg_p), jnp.asarray(bkt_p), n_seg)
    want = ref.seg_bitmap_ref(jnp.asarray(seg_p), jnp.asarray(bkt_p), n_seg, NBUCKETS)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0, atol=0)


def test_predicate_bitmaps_wrapper():
    rng = np.random.default_rng(7)
    n, n_seg = 700, 37
    seg = np.sort(rng.integers(0, n_seg, n)).astype(np.int32)
    bucket = rng.integers(0, NBUCKETS, n).astype(np.int32)
    got = ops.predicate_bitmaps(seg, bucket, n_seg)
    want = np.zeros((n_seg, NBUCKETS), bool)
    for s, b in zip(seg, bucket):
        want[s, b] = True
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("np_,nb", [(256, 256), (512, 512), (768, 256)])
def test_join_count_sweep(np_, nb):
    rng = np.random.default_rng(np_ + nb)
    build = np.sort(rng.choice(5000, size=nb, replace=False)).astype(np.int32)
    bw = rng.integers(0, 4, nb).astype(np.int32)
    probe = rng.choice(6000, size=np_).astype(np.int32)
    got = join_count(jnp.asarray(probe), jnp.asarray(build), jnp.asarray(bw))
    want = ref.join_count_ref(jnp.asarray(probe), jnp.asarray(build), jnp.asarray(bw))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_match_counts_wrapper():
    rng = np.random.default_rng(3)
    build = np.unique(rng.choice(1000, 300)).astype(np.int32)
    bw = rng.integers(1, 5, len(build)).astype(np.int32)
    probe = rng.choice(1200, 450).astype(np.int32)
    got = ops.match_counts(probe, build, bw)
    lut = dict(zip(build.tolist(), bw.tolist()))
    want = np.array([lut.get(int(p), 0) for p in probe], np.int32)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("na,nb,w", [(128, 128, 8), (256, 128, 16), (128, 256, 8)])
def test_summary_probe_sweep(na, nb, w):
    rng = np.random.default_rng(na + nb + w)
    a = rng.integers(-(2**31), 2**31 - 1, (na, w), dtype=np.int64).astype(np.int32)
    b = rng.integers(-(2**31), 2**31 - 1, (nb, w), dtype=np.int64).astype(np.int32)
    got = summary_probe(jnp.asarray(a), jnp.asarray(b))
    want = ref.summary_probe_ref(jnp.asarray(a), jnp.asarray(b))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_signature_overlap_matches_summaries(small_fed):
    """Kernel path must agree with the numpy candidate generation on real
    summary signatures (uint64 host layout)."""
    fed, _ = small_fed
    from repro.core.characteristic_sets import compute_characteristic_sets
    from repro.core.summaries import build_summary

    kinds = np.asarray(fed.dictionary.kinds, np.int8)
    auth = fed.dictionary.authority_array()
    cs_a = compute_characteristic_sets(fed.sources[7].table)
    cs_b = compute_characteristic_sets(fed.sources[3].table)
    sa = build_summary(fed.sources[7].table, cs_a, auth, src=7, entity_mask=kinds == 0)
    sb = build_summary(fed.sources[3].table, cs_b, auth, src=3, entity_mask=kinds == 0)
    if len(sa.obj_sig) == 0 or len(sb.subj_sig) == 0:
        pytest.skip("no signatures")
    pop = ops.signature_overlap(sa.obj_sig, sb.subj_sig)
    want = (sa.obj_sig[:, None, :] & sb.subj_sig[None, :, :]).any(-1)
    np.testing.assert_array_equal(pop > 0, want)


@pytest.mark.parametrize("B,R,C", [(1, 2, 3), (4, 130, 7), (8, 260, 140)])
def test_dp_layer_sweep(B, R, C):
    """dp_layer (interpret mode) vs the jnp oracle: dense candidate pricing
    plus the per-column first-strict-minimum — exact equality, including on
    injected cost ties (the DP's tie-breaking contract) and all-invalid
    columns."""
    from repro.kernels.dp_layer import dp_layer

    rng = np.random.default_rng(B * 1000 + R + C)
    cost_a = rng.uniform(1, 100, (B, R, C))
    cost_b = rng.uniform(1, 100, (B, R, C))
    card_a = rng.uniform(0, 50, (B, R, C))
    n_src_b = rng.integers(1, 4, (B, R, C)).astype(np.float64)
    src_w_b = rng.uniform(0.5, 2, (B, R, C))
    bindable = rng.random((B, R, C)) < 0.5
    valid = rng.random((R, C)) < 0.6
    if C > 1:
        valid[:, -1] = False                    # an all-invalid column
    card_s = rng.uniform(0, 80, (B, C))
    cost_a[:, ::3, :] = 5.0                     # exact ties across rows
    cost_b[:, ::3, :] = 5.0
    params = (1.0, 1.0, 5.0, 20)
    got = dp_layer(cost_a, cost_b, card_a, n_src_b, src_w_b, bindable, valid,
                   card_s, params)
    with jax.enable_x64(True):
        want = ref.dp_layer_ref(
            jnp.asarray(cost_a), jnp.asarray(cost_b), jnp.asarray(card_a),
            jnp.asarray(n_src_b), jnp.asarray(src_w_b), jnp.asarray(bindable),
            jnp.asarray(valid), jnp.asarray(card_s), params)
    np.testing.assert_array_equal(got[0], np.asarray(want[0]))
    np.testing.assert_array_equal(got[1], np.asarray(want[1]))
    np.testing.assert_array_equal(got[2], np.asarray(want[2]))
    if C > 1:                                   # no valid pair -> inf / BIG
        assert np.isinf(got[0][:, -1]).all()


def test_cost_jnp_twins_bitwise_equal_numpy_forms():
    """Every ``CostModel.*_jnp`` twin must reproduce its ``*_v`` numpy form
    bit for bit under x64 — the contract the on-device sweep's bit-identical
    plans rest on (``hash_join_cost_jnp`` runs inside the kernel; the others
    are pinned here so they cannot silently drift)."""
    from repro.core.cost import CostModel

    rng = np.random.default_rng(23)
    cm = CostModel(intermediate_weight=1.25, transfer_weight=0.75,
                   request_cost=5.0, bind_batch=20)
    card = rng.uniform(0, 1e4, 257)
    card_l = rng.uniform(0, 1e3, 257)
    n_src = rng.integers(1, 6, 257).astype(np.float64)
    src_w = rng.uniform(0.25, 4.0, 257)
    bindable = rng.random(257) < 0.5
    with jax.enable_x64(True):
        pairs = [
            (cm.leaf_cost_v(card, n_src, src_w),
             cm.leaf_cost_jnp(jnp.asarray(card), jnp.asarray(n_src),
                              jnp.asarray(src_w))),
            (cm.hash_join_cost_v(card),
             cm.hash_join_cost_jnp(jnp.asarray(card))),
            (cm.bind_join_cost_v(card_l, card, n_src, src_w),
             cm.bind_join_cost_jnp(jnp.asarray(card_l), jnp.asarray(card),
                                   jnp.asarray(n_src), jnp.asarray(src_w))),
        ]
        for want, got in pairs:
            assert np.asarray(got).dtype == np.float64
            np.testing.assert_array_equal(np.asarray(got), want)
        hj = cm.hash_join_cost_v(card)
        want_c, want_b = cm.join_candidates_v(card_l, card_l[::-1], card, hj,
                                              card_l, n_src, src_w, bindable)
        got_c, got_b = cm.join_candidates_jnp(
            jnp.asarray(card_l), jnp.asarray(card_l[::-1]), jnp.asarray(card),
            jnp.asarray(hj), jnp.asarray(card_l), jnp.asarray(n_src),
            jnp.asarray(src_w), jnp.asarray(bindable))
        np.testing.assert_array_equal(np.asarray(got_c), want_c)
        np.testing.assert_array_equal(np.asarray(got_b), want_b)


def test_popcount_identity():
    rng = np.random.default_rng(11)
    v = jnp.asarray(rng.integers(-(2**31), 2**31 - 1, 4096, dtype=np.int64).astype(np.int32))
    got = np.asarray(ref.popcount32_ref(v))
    want = np.array([bin(int(np.uint32(x))).count("1") for x in np.asarray(v)])
    np.testing.assert_array_equal(got, want)


def test_cost_params_form_bitwise_equal_numpy():
    """The traced-params fused form (``join_candidates_params_jnp``) must
    reproduce ``join_candidates_v`` bit for bit under x64, including the
    in-place ``iw * card_out`` hash term — the resident sweep's plans rest
    on it."""
    from repro.core.cost import CostModel

    rng = np.random.default_rng(31)
    cm = CostModel(intermediate_weight=1.25, transfer_weight=0.75,
                   request_cost=5.0, bind_batch=20)
    card_out = rng.uniform(0, 1e4, 257)
    cost_a = rng.uniform(0, 1e3, 257)
    cost_b = rng.uniform(0, 1e3, 257)
    card_a = rng.uniform(0, 1e3, 257)
    n_src = rng.integers(0, 6, 257).astype(np.float64)
    src_w = rng.uniform(0.25, 4.0, 257)
    bindable = n_src > 0
    hj = cm.hash_join_cost_v(card_out)
    want_c, want_b = cm.join_candidates_v(cost_a, cost_b, card_out, hj,
                                          card_a, n_src, src_w, bindable)
    with jax.enable_x64(True):
        params = jnp.asarray([cm.intermediate_weight, cm.transfer_weight,
                              cm.request_cost, cm.bind_batch], jnp.float64)
        got_c, got_b = CostModel.join_candidates_params_jnp(
            params, jnp.asarray(cost_a), jnp.asarray(cost_b),
            jnp.asarray(card_out), jnp.asarray(card_a), jnp.asarray(n_src),
            jnp.asarray(src_w), jnp.asarray(bindable))
    assert np.asarray(got_c).dtype == np.float64
    np.testing.assert_array_equal(np.asarray(got_c), want_c)
    np.testing.assert_array_equal(np.asarray(got_b), want_b)


def test_program_cache_one_compile_across_shapes_and_params():
    """Regression for the old ``lru_cache`` keyed on ``(params, interpret)``:
    two bucketed tile shapes under two different cost-model parameter sets
    must share ONE cached program entry (params are traced, shapes are
    specialized inside jax's own jit cache), and a parameter change must not
    add a jit specialization."""
    from repro.kernels.dp_layer import PROGRAM_CACHE, dp_layer

    PROGRAM_CACHE.clear()
    rng = np.random.default_rng(5)

    def tile(B, R, C):
        return (rng.uniform(1, 9, (B, R, C)), rng.uniform(1, 9, (B, R, C)),
                rng.uniform(0, 5, (B, R, C)),
                rng.integers(1, 3, (B, R, C)).astype(np.float64),
                rng.uniform(0.5, 2, (B, R, C)),
                rng.random((B, R, C)) < 0.5, rng.random((R, C)) < 0.7,
                rng.uniform(0, 9, (B, C)))

    p1, p2 = (1.0, 1.0, 5.0, 20), (2.0, 0.5, 7.0, 10)
    shapes = [(2, 5, 3), (2, 13, 9)]        # distinct bucketed extents
    for B, R, C in shapes:
        args = tile(B, R, C)
        for params in (p1, p2):
            dp_layer(*args, params)
    assert len(PROGRAM_CACHE) == 1          # one program entry, ever
    assert PROGRAM_CACHE.misses == 1
    assert PROGRAM_CACHE.hits == 2 * len(shapes) - 1
    assert PROGRAM_CACHE.evictions == 0
    fn = PROGRAM_CACHE._entries[("layer", True)]
    if hasattr(fn, "_cache_size"):
        # one jit specialization per bucketed shape — none per param set
        assert fn._cache_size() == len(shapes)


def test_program_cache_eviction_counter():
    from repro.kernels.dp_layer import _ProgramCache

    c = _ProgramCache(max_entries=2)
    for k in ("a", "b", "c"):
        c.get((k,), lambda: k)
    assert len(c) == 2
    assert c.evictions == 1
    assert c.misses == 3
    c.get(("c",), lambda: "c")
    assert c.hits == 1


def test_dp_sweep_resident_matches_scalar_ref():
    """The whole resident fused program (compiled XLA, one ``lax.scan``)
    vs the independent scalar oracle, on a real topology schedule with
    injected cost ties, exclusive-group seeds and source-less singletons."""
    from repro.core import join_order as jo
    from repro.kernels.dp_layer import dp_sweep_resident
    from repro.rdf.shapes import shaped_planning_inputs

    g, _, _, _ = shaped_planning_inputs("tree", 8, seed=3)
    B, n = 4, 8
    size = 1 << n
    sched = jo._dp_schedule(g, jo.DP_BLOCK_BYTES, B)
    assert sched is not None
    rng = np.random.default_rng(17)
    # small-integer stats force exact cost ties; the program must break
    # them like the scalar first-strict-minimum
    card = rng.integers(1, 5, (B, size)).astype(np.float64)
    cost0 = np.full((B, size), np.inf)
    n_src0 = np.zeros((B, size))
    src_w0 = np.ones((B, size))
    for i in range(n):
        m = 1 << i
        cost0[:, m] = rng.integers(1, 6, B)
        n_src0[:, m] = rng.integers(0, 3, B)      # some source-less leaves
        src_w0[:, m] = rng.choice([1.0, 1.5], B)
    excl_cost = np.full((B, size), np.inf)
    excl_w = np.ones((B, size))
    conn_masks = sched.layer_cols[sched.layer_cols < size]
    pick = rng.choice(conn_masks, 12, replace=False)
    excl_cost[:, pick] = rng.integers(1, 8, (B, len(pick)))
    excl_w[:, pick] = rng.choice([1.0, 2.0], (B, len(pick)))
    params = (1.0, 1.0, 5.0, 20)
    got = dp_sweep_resident(params, sched.pair_a, sched.pair_b,
                            sched.pair_seg, sched.layer_cols, card,
                            excl_cost, excl_w, cost0, n_src0, src_w0)
    want = ref.dp_sweep_ref(params, sched.pair_a, sched.pair_b,
                            sched.pair_seg, sched.layer_cols, card,
                            excl_cost, excl_w, cost0, n_src0, src_w0)
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])
    np.testing.assert_array_equal(got[2], want[2])
