"""Pure-jnp oracles for every kernel (the correctness contract)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def sorted_intersect_weighted_ref(a, aw, b, bw) -> jax.Array:
    eq = a[:, None] == b[None, :]
    return jnp.sum(jnp.where(eq, aw[:, None] * bw[None, :], 0), dtype=jnp.int32)


def seg_bitmap_ref(seg, bucket, n_seg, n_buckets=128) -> jax.Array:
    """(n_seg, n_buckets) float32 counts of (segment, bucket) pairs."""
    valid = seg >= 0
    seg_oh = (seg[:, None] == jnp.arange(n_seg)[None, :]) & valid[:, None]
    bkt_oh = bucket[:, None] == jnp.arange(n_buckets)[None, :]
    return (seg_oh.astype(jnp.float32).T @ bkt_oh.astype(jnp.float32))


def join_count_ref(probe, build, build_w) -> jax.Array:
    eq = probe[:, None] == build[None, :]
    return jnp.sum(jnp.where(eq, build_w[None, :], 0), axis=1).astype(jnp.int32)


def popcount32_ref(v) -> jax.Array:
    s = jax.lax.shift_right_logical
    v = v - (s(v, 1) & 0x55555555)
    v = (v & 0x33333333) + (s(v, 2) & 0x33333333)
    v = (v + s(v, 4)) & 0x0F0F0F0F
    return s(v * 0x01010101, 24)


def summary_probe_ref(a_sig, b_sig) -> jax.Array:
    return popcount32_ref(a_sig[:, None, :] & b_sig[None, :, :]).sum(-1).astype(jnp.int32)


def dp_layer_ref(cost_a, cost_b, card_a, n_src_b, src_w_b, bindable, valid,
                 card_s, params) -> "tuple[jax.Array, jax.Array, jax.Array]":
    """Oracle for ``kernels/dp_layer.py``: dense candidate pricing + the
    per-column first-strict-minimum reduction of the join-order DP's layer
    sweep.

    ``cost_a``/``cost_b``/``card_a``/``n_src_b``/``src_w_b`` are ``(B, R, C)``
    float64 per-pair gathers (member, relative submask row, connected-subset
    column), ``bindable`` is ``(B, R, C)`` bool, ``valid`` is the
    member-independent ``(R, C)`` connectivity mask, ``card_s`` is the
    ``(B, C)`` per-subset cardinality (the hash-join cost is derived from it
    in place, as the kernel does), and ``params = (intermediate_weight,
    transfer_weight, request_cost, bind_batch)``.  Returns per
    ``(member, column)``: the minimum candidate cost (``inf`` when no pair
    is valid), the first row attaining it (rows ascend in the reference
    enumeration order, so first == the numpy DP's first-strict-minimum
    tie-breaking) and whether that candidate is a bind join.  Runs in
    float64 — call under ``jax.enable_x64(True)``."""
    iw, tw, rc, bb = params
    hash_s = iw * card_s
    hc = (cost_a + cost_b) + hash_s[:, None, :]
    n_req = jnp.maximum(1.0, card_a / bb) * n_src_b
    bc = cost_a + ((rc * n_req + tw * card_s[:, None, :] * src_w_b)
                   + iw * card_s[:, None, :])
    is_bind = bindable & (bc < hc)
    pair = jnp.where(valid[None, :, :], jnp.where(is_bind, bc, hc), jnp.inf)
    best = jnp.min(pair, axis=1)
    rows = jnp.arange(pair.shape[1], dtype=jnp.int32)[None, :, None]
    is_min = valid[None, :, :] & (pair == best[:, None, :])
    first = jnp.min(jnp.where(is_min, rows, jnp.int32(2**31 - 1)), axis=1)
    bind_at = jnp.any(is_min & (rows == first[:, None, :]) & is_bind, axis=1)
    return best, first, bind_at


def dp_sweep_ref(params, pair_a, pair_b, pair_seg, layer_cols, card,
                 excl_cost, excl_w, cost0, n_src0, src_w0):
    """Oracle for ``kernels/dp_layer.dp_sweep_resident``: the whole scanned
    sweep re-evaluated candidate by candidate in scalar form (python loops
    over layers, columns and flat pairs — deliberately nothing shared with
    the scatter/gather program it checks).  Inputs are the program's exactly:
    the ``(L, P)``/``(L, C)`` sentinel-padded schedule, the ``(B, size)``
    cardinality plane, the exclusive-leaf seeds and the singleton seeds.
    Returns ``(cost, strat, split)`` with the program's strategy codes
    (0 never-written, 2 exclusive leaf, 3 hash, 4 bind).  Float64 numpy
    throughout, with the scalar operation order of ``CostModel`` —
    candidates priced in flat-position order, first strict minimum wins,
    the exclusive leaf is candidate 0."""
    import numpy as np

    iw, tw, rc, bb = [float(v) for v in params]
    cost = np.array(cost0, dtype=np.float64)
    n_src = np.array(n_src0, dtype=np.float64)
    src_w = np.array(src_w0, dtype=np.float64)
    B, size = cost.shape
    strat = np.zeros((B, size), np.int32)
    split = np.zeros((B, size), np.int32)
    C = layer_cols.shape[1]
    for li in range(pair_a.shape[0]):
        for ci in range(C):
            S = int(layer_cols[li, ci])
            if S >= size:                      # padded column
                continue
            flat = np.nonzero(pair_seg[li] == ci)[0]
            for b in range(B):
                best, b_split, b_bind = np.inf, 0, False
                for p in flat:
                    am, bm = int(pair_a[li, p]), int(pair_b[li, p])
                    hc = (cost[b, am] + cost[b, bm]) + iw * card[b, S]
                    n_req = max(1.0, card[b, am] / bb) * n_src[b, bm]
                    bc = cost[b, am] + ((rc * n_req
                                         + tw * card[b, S] * src_w[b, bm])
                                        + iw * card[b, S])
                    is_bind = bool(n_src[b, bm] > 0) and bc < hc
                    c = bc if is_bind else hc
                    if c < best:
                        best, b_split, b_bind = c, am, is_bind
                ec = excl_cost[b, S]
                if best < ec:
                    cost[b, S] = best
                    strat[b, S] = 4 if b_bind else 3
                    split[b, S] = b_split
                    n_src[b, S] = 0.0
                    src_w[b, S] = 1.0
                elif np.isfinite(ec):
                    cost[b, S] = ec
                    strat[b, S] = 2
                    n_src[b, S] = 1.0
                    src_w[b, S] = excl_w[b, S]
    return cost, strat, split


def ssm_scan_ref(dt, bt, ct, x, a) -> jax.Array:
    """Selective-scan oracle via associative scan (models/mamba.py math)."""
    dA = jnp.exp(dt[..., None] * a)                          # (B,S,D,N)
    dBx = (dt * x)[..., None] * bt[:, :, None, :]

    def combine(l, r):
        (a1, b1), (a2, b2) = l, r
        return a1 * a2, b1 * a2 + b2

    _, h = jax.lax.associative_scan(combine, (dA, dBx), axis=1)
    return jnp.einsum("bsdn,bsn->bsd", h, ct)
