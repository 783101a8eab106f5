"""Weighted sorted-set intersection count (Algorithm 1 inner loop).

GPU/CPU implementations of set intersection are branchy merge loops; on TPU we
reformulate as tiled all-pairs equality over VMEM blocks: each grid step loads
an (BA, 1) tile of A and a (1, BB) tile of B, compares on the VPU, and
accumulates ``Σ eq(a, b) · w_a · w_b`` into a scalar accumulator. Padding uses
weight 0, so no sentinel tests are needed in the hot loop.

A and B are sorted; a production grid could skip disjoint tile pairs via a
host-computed tile map — kept dense here because Algorithm 1's inputs are
per-(CS, pred) lists, which are short and numerous (the batching matters more
than asymptotics).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode

BLOCK_A = 256
BLOCK_B = 256


def _kernel(a_ref, aw_ref, b_ref, bw_ref, out_ref):
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when((i == 0) & (j == 0))
    def _init():
        out_ref[0, 0] = jnp.int32(0)

    a = a_ref[...]            # (BLOCK_A, 1) int32
    aw = aw_ref[...]          # (BLOCK_A, 1) int32
    b = b_ref[...]            # (1, BLOCK_B) int32
    bw = bw_ref[...]          # (1, BLOCK_B) int32
    eq = a == b               # (BLOCK_A, BLOCK_B)
    w = aw * bw
    out_ref[0, 0] += jnp.sum(jnp.where(eq, w, 0), dtype=jnp.int32)


def sorted_intersect_weighted(a: jax.Array, aw: jax.Array, b: jax.Array,
                              bw: jax.Array) -> jax.Array:
    """a, b: sorted int32 ids, padded to multiples of the block sizes with
    weight-0 entries. Returns scalar int32 Σ_{a_i == b_j} aw_i · bw_j."""
    na, nb = a.shape[0], b.shape[0]
    assert na % BLOCK_A == 0 and nb % BLOCK_B == 0
    grid = (na // BLOCK_A, nb // BLOCK_B)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((BLOCK_A, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((BLOCK_A, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, BLOCK_B), lambda i, j: (0, j)),
            pl.BlockSpec((1, BLOCK_B), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.int32),
        interpret=interpret_mode(),
    )(a.reshape(-1, 1), aw.reshape(-1, 1), b.reshape(1, -1), bw.reshape(1, -1))
    return out[0, 0]
