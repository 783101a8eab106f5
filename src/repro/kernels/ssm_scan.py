"""Chunked selective-scan (Mamba-1) Pallas TPU kernel.

The jnp associative scan materializes (B, S, d_inner, N) state through HBM —
the §Perf falcon-mamba diagnosis. The TPU-native structure mirrors the
chunked jnp path (`models/mamba.py`) but keeps the chunk state in VMEM:

  grid = (B, d_inner/BD, S/CHUNK) with the sequence axis innermost; the
  carry state h (BD, N) lives in VMEM scratch across sequence steps; within
  a chunk the recurrence runs as an unrolled first-order scan over CHUNK
  steps on the VPU (d_inner is the vectorized lane axis, N unrolled).

Inputs are the per-timestep scan parameters (already activated):
  dt (B, S, D), Bt (B, S, N), Ct (B, S, N), x (B, S, D), A (D, N)
Output: y (B, S, D) with y_t = C_t · h_t, h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

CHUNK = 64
BLOCK_D = 256


def _kernel(dt_ref, bt_ref, ct_ref, x_ref, a_ref, y_ref, h_ref, *,
            n_state: int, chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    dt = dt_ref[0]            # (chunk, BD)
    x = x_ref[0]              # (chunk, BD)
    a = a_ref[...]            # (BD, n_state)
    bt = bt_ref[0]            # (chunk, n_state)
    ct = ct_ref[0]            # (chunk, n_state)

    dtx = dt * x              # (chunk, BD)
    y = jnp.zeros((chunk, dt.shape[1]), jnp.float32)
    h = h_ref[...]            # (BD, n_state) carry
    for t in range(chunk):    # first-order recurrence, VPU-vectorized over BD
        dA = jnp.exp(dt[t][:, None] * a)                 # (BD, N)
        h = h * dA + dtx[t][:, None] * bt[t][None, :]    # (BD, N)
        y = y.at[t].set(jnp.sum(h * ct[t][None, :], axis=1))
    h_ref[...] = h
    y_ref[0] = y.astype(y_ref.dtype)


def ssm_scan(dt: jax.Array, bt: jax.Array, ct: jax.Array, x: jax.Array,
             a: jax.Array, *, chunk: int = CHUNK, block_d: int = BLOCK_D) -> jax.Array:
    """dt, x: (B, S, D) f32; bt, ct: (B, S, N) f32; a: (D, N) f32 (negative).
    Returns y: (B, S, D) f32. S % chunk == 0, D % block_d == 0."""
    B, S, D = x.shape
    N = bt.shape[-1]
    assert S % chunk == 0 and D % block_d == 0
    grid = (B, D // block_d, S // chunk)
    kern = functools.partial(_kernel, n_state=N, chunk=chunk)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, block_d), lambda b, d, c: (b, c, d)),
            pl.BlockSpec((1, chunk, N), lambda b, d, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, d, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, block_d), lambda b, d, c: (b, c, d)),
            pl.BlockSpec((block_d, N), lambda b, d, c: (d, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, block_d), lambda b, d, c: (b, c, d)),
        out_shape=jax.ShapeDtypeStruct((B, S, D), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_d, N), jnp.float32)],
        interpret=interpret_mode(),
    )(dt, bt, ct, x, a)
