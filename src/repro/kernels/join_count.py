"""Per-probe-row match counts against a sorted build side.

The distributed engine's bounded-buffer joins need, for every probe key, the
number of matching build rows (to size output offsets before materializing).
Same tiled all-pairs-equality pattern as ``sorted_intersect`` but reducing
over the build axis only, producing an (N_probe,) count vector.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode

BLOCK_P = 256
BLOCK_B = 256


def _kernel(p_ref, b_ref, bw_ref, out_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    p = p_ref[...]              # (BLOCK_P, 1)
    b = b_ref[...]              # (1, BLOCK_B)
    bw = bw_ref[...]            # (1, BLOCK_B)
    eq = p == b                 # (BLOCK_P, BLOCK_B)
    out_ref[...] += jnp.sum(jnp.where(eq, bw, 0), axis=1, keepdims=True).astype(jnp.int32)


def join_count(probe: jax.Array, build: jax.Array, build_w: jax.Array) -> jax.Array:
    """probe: (NP,) int32; build: (NB,) sorted int32 (pad with weight 0).
    Returns (NP,) int32 match multiplicities."""
    np_, nb = probe.shape[0], build.shape[0]
    assert np_ % BLOCK_P == 0 and nb % BLOCK_B == 0
    grid = (np_ // BLOCK_P, nb // BLOCK_B)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((BLOCK_P, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, BLOCK_B), lambda i, j: (0, j)),
            pl.BlockSpec((1, BLOCK_B), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((BLOCK_P, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((np_, 1), jnp.int32),
        interpret=interpret_mode(),
    )(probe.reshape(-1, 1), build.reshape(1, -1), build_w.reshape(1, -1))
    return out[:, 0]
