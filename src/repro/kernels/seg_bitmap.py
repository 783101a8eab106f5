"""Per-entity predicate bitmaps via one-hot MXU matmul.

CS computation needs, per subject segment, the OR of its predicates' bucket
bits. A CUDA port would use atomics or a segmented scan; the TPU-native
formulation is a *blocked matmul*: with S segment one-hots (BN × BS) and
predicate-bucket one-hots (BN × NBUCKETS),

    bitmap[BS, NBUCKETS] += seg_onehotᵀ @ bucket_onehot

runs on the MXU (128-aligned on both output dims) and the >0 threshold
recovers the OR. Row padding uses segment id -1, which one-hot-encodes to
zero rows.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode

BLOCK_N = 256       # rows (s,p) per step
BLOCK_S = 128       # segments per output tile
NBUCKETS = 128      # predicate hash buckets (one MXU lane tile)


def _kernel(seg_ref, bkt_ref, out_ref):
    n = pl.program_id(1)

    @pl.when(n == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    seg = seg_ref[...]                                   # (BLOCK_N, 1) int32
    bkt = bkt_ref[...]                                   # (BLOCK_N, 1) int32
    s0 = pl.program_id(0) * BLOCK_S
    seg_iota = jax.lax.broadcasted_iota(jnp.int32, (BLOCK_N, BLOCK_S), 1) + s0
    seg_oh = (seg == seg_iota).astype(jnp.float32)       # (BLOCK_N, BLOCK_S)
    bkt_iota = jax.lax.broadcasted_iota(jnp.int32, (BLOCK_N, NBUCKETS), 1)
    bkt_oh = (bkt == bkt_iota).astype(jnp.float32)       # (BLOCK_N, NBUCKETS)
    out_ref[...] += jax.lax.dot_general(
        seg_oh, bkt_oh, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def seg_bitmap(seg: jax.Array, bucket: jax.Array, n_seg: int) -> jax.Array:
    """seg: (N,) sorted int32 segment ids (pad -1); bucket: (N,) int32 in
    [0, NBUCKETS). Returns (n_seg, NBUCKETS) float32 *counts* per (segment,
    bucket); callers binarize for the OR semantics."""
    n = seg.shape[0]
    assert n % BLOCK_N == 0 and n_seg % BLOCK_S == 0
    grid = (n_seg // BLOCK_S, n // BLOCK_N)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((BLOCK_N, 1), lambda s, n: (n, 0)),
            pl.BlockSpec((BLOCK_N, 1), lambda s, n: (n, 0)),
        ],
        out_specs=pl.BlockSpec((BLOCK_S, NBUCKETS), lambda s, n: (s, 0)),
        out_shape=jax.ShapeDtypeStruct((n_seg, NBUCKETS), jnp.float32),
        interpret=interpret_mode(),
    )(seg.reshape(-1, 1), bucket.reshape(-1, 1))
