"""Flash attention (online softmax) Pallas TPU kernel.

The TPU-native tiling: grid = (batch·kv_heads·q_groups, S_q/BQ, S_k/BK) with
the KV axis innermost so the (BQ, BK) score tile lives entirely in VMEM and
the running (max, denom, output) state is carried in VMEM scratch across KV
steps. Q/K tiles are MXU-aligned (BQ, BK multiples of 128; head_dim padded to
128 by the wrapper). Causal/local masking happens on the fly from program
ids — no (S, S) mask tensor exists anywhere.

``repro.models.attention_chunked`` is the identical math as a jnp double scan
(used by the 512-device dry-run); this kernel is what a real TPU deployment
runs per shard after the GSPMD partitioner has split heads/batch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            causal: bool, window: int, block_q: int, block_k: int, n_k: int):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                       # (BQ, hd)
    k = k_ref[0]                       # (BK, hd)
    v = v_ref[0]                       # (BK, hd)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    kpos = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = jnp.zeros((block_q, block_k), jnp.float32)
    if causal:
        mask = jnp.where(kpos > qpos, NEG_INF, mask)
    if window:
        mask = jnp.where(qpos - kpos >= window, NEG_INF, mask)
    s = s + mask

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(kj == n_k - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-20)).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128) -> jax.Array:
    """q: (BH, S, hd) with per-q-head k/v already broadcast: k, v: (BH, S, hd).
    Scaling (hd^-0.5) is the caller's job. Returns (BH, S, hd)."""
    BH, S, hd = q.shape
    assert S % block_q == 0 and S % block_k == 0
    n_q = S // block_q
    n_k = S // block_k
    grid = (BH, n_q, n_k)
    kern = functools.partial(_kernel, causal=causal, window=window,
                             block_q=block_q, block_k=block_k, n_k=n_k)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),    # running max
            pltpu.VMEM((block_q, 1), jnp.float32),    # running denom
            pltpu.VMEM((block_q, hd), jnp.float32),   # output accumulator
        ],
        interpret=interpret_mode(),
    )(q, k, v)
