"""Batched bitset AND + popcount between entity-summary signatures.

Candidate federated-CP generation intersects every object-signature row of
one source with every subject-signature row of another (per authority). The
kernel computes the full (nA, nB) popcount matrix tile-by-tile; bit counting
uses the SWAR popcount on int32 words (logical shifts via lax) — pure VPU
work, W-axis innermost so each tile reuses its signature block from VMEM.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode

BLOCK_A = 128
BLOCK_B = 128
BLOCK_W = 8        # signature words per step


def _popcount32(v: jax.Array) -> jax.Array:
    s = jax.lax.shift_right_logical
    v = v - (s(v, 1) & 0x55555555)
    v = (v & 0x33333333) + (s(v, 2) & 0x33333333)
    v = (v + s(v, 4)) & 0x0F0F0F0F
    return s(v * 0x01010101, 24)


def _kernel(a_ref, b_ref, out_ref):
    w = pl.program_id(2)

    @pl.when(w == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    a = a_ref[...]                       # (BLOCK_A, BLOCK_W) int32 words
    b = b_ref[...]                       # (BLOCK_B, BLOCK_W)
    acc = jnp.zeros((BLOCK_A, BLOCK_B), jnp.int32)
    for k in range(BLOCK_W):             # unrolled: VREG-resident columns
        acc += _popcount32(a[:, k:k + 1] & b[:, k:k + 1].T)
    out_ref[...] += acc


def summary_probe(a_sig: jax.Array, b_sig: jax.Array) -> jax.Array:
    """a_sig: (nA, W) int32 words; b_sig: (nB, W). Returns (nA, nB) int32
    popcount of the pairwise AND (0 ⇔ definitely-no-overlap)."""
    na, w = a_sig.shape
    nb, w2 = b_sig.shape
    assert w == w2 and na % BLOCK_A == 0 and nb % BLOCK_B == 0 and w % BLOCK_W == 0
    grid = (na // BLOCK_A, nb // BLOCK_B, w // BLOCK_W)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((BLOCK_A, BLOCK_W), lambda i, j, w: (i, w)),
            pl.BlockSpec((BLOCK_B, BLOCK_W), lambda i, j, w: (j, w)),
        ],
        out_specs=pl.BlockSpec((BLOCK_A, BLOCK_B), lambda i, j, w: (i, j)),
        out_shape=jax.ShapeDtypeStruct((na, nb), jnp.int32),
        interpret=interpret_mode(),
    )(a_sig, b_sig)
