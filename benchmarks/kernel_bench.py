"""Kernel microbenchmarks: Pallas (interpret mode on CPU — structural check,
TPU is the target) vs the pure-jnp reference, per shape.

Both sides are timed through ``jax.jit`` uniformly — timing the Pallas side
through a bare lambda would charge it Python dispatch/trace overhead on
every call that the jitted reference never pays, skewing the comparison.
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.kernels.join_count import join_count
from repro.kernels.seg_bitmap import NBUCKETS, seg_bitmap
from repro.kernels.sorted_intersect import sorted_intersect_weighted
from repro.kernels.summary_probe import summary_probe


def _time(fn, *args, n=5) -> float:
    fn(*args)  # warmup/compile
    t0 = time.perf_counter()
    for _ in range(n):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / n * 1e6  # us


def _naive_attention(q, k, v):
    S = q.shape[1]
    s = jnp.einsum("bqd,bkd->bqk", q, k)
    m = jnp.where(jnp.arange(S)[None, :] > jnp.arange(S)[:, None], -1e30, 0.0)
    return jax.nn.softmax(s + m, -1) @ v


def run():
    rng = np.random.default_rng(0)
    rows = []
    # Jitted wrappers are bound once, outside the shape loops: a fresh
    # `jax.jit(lambda ...)` per iteration would defeat jax's identity-keyed
    # jit cache and retrace every pass (RPR004).  Static extents (seg_bitmap's
    # n_seg) ride through `static_argnums` so shape sweeps reuse one wrapper.
    # sorted_intersect
    jit_si_ref = jax.jit(ref.sorted_intersect_weighted_ref)
    jit_si = jax.jit(sorted_intersect_weighted)
    for n in (1024, 4096):
        a = jnp.asarray(np.sort(rng.choice(10 * n, n, replace=False)).astype(np.int32))
        b = jnp.asarray(np.sort(rng.choice(10 * n, n, replace=False)).astype(np.int32))
        w = jnp.ones(n, jnp.int32)
        t_ref = _time(jit_si_ref, a, w, b, w)
        t_pal = _time(jit_si, a, w, b, w)
        rows.append((f"kernel/sorted_intersect/{n}", t_pal, t_ref))
    # seg_bitmap
    jit_sb_ref = jax.jit(ref.seg_bitmap_ref, static_argnums=(2, 3))
    jit_sb = jax.jit(seg_bitmap, static_argnums=2)
    for n, s in ((1024, 128), (4096, 256)):
        seg = jnp.asarray(np.sort(rng.integers(0, s, n)).astype(np.int32))
        bkt = jnp.asarray(rng.integers(0, NBUCKETS, n).astype(np.int32))
        t_ref = _time(jit_sb_ref, seg, bkt, s, NBUCKETS)
        t_pal = _time(jit_sb, seg, bkt, s)
        rows.append((f"kernel/seg_bitmap/{n}x{s}", t_pal, t_ref))
    # join_count
    jit_jc_ref = jax.jit(ref.join_count_ref)
    jit_jc = jax.jit(join_count)
    for n in (1024, 4096):
        probe = jnp.asarray(rng.integers(0, 5000, n).astype(np.int32))
        build = jnp.asarray(np.sort(rng.choice(8000, n, replace=False)).astype(np.int32))
        bw = jnp.ones(n, jnp.int32)
        t_ref = _time(jit_jc_ref, probe, build, bw)
        t_pal = _time(jit_jc, probe, build, bw)
        rows.append((f"kernel/join_count/{n}", t_pal, t_ref))
    # summary_probe
    jit_sp_ref = jax.jit(ref.summary_probe_ref)
    jit_sp = jax.jit(summary_probe)
    for na, w in ((128, 8), (256, 32)):
        a = jnp.asarray(rng.integers(-2**31, 2**31 - 1, (na, w), dtype=np.int64).astype(np.int32))
        b = jnp.asarray(rng.integers(-2**31, 2**31 - 1, (na, w), dtype=np.int64).astype(np.int32))
        t_ref = _time(jit_sp_ref, a, b)
        t_pal = _time(jit_sp, a, b)
        rows.append((f"kernel/summary_probe/{na}x{w}", t_pal, t_ref))
    # flash attention
    from repro.kernels.flash_attention import flash_attention

    jit_fa_ref = jax.jit(_naive_attention)
    jit_fa = jax.jit(functools.partial(flash_attention, causal=True))
    for S in (256, 512):
        q = jnp.asarray(rng.normal(size=(2, S, 128)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(2, S, 128)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(2, S, 128)), jnp.float32)
        t_ref = _time(jit_fa_ref, q, k, v)
        t_pal = _time(jit_fa, q, k, v)
        rows.append((f"kernel/flash_attention/{S}", t_pal, t_ref))
    # selective scan
    from repro.kernels.ssm_scan import ssm_scan

    jit_ss_ref = jax.jit(ref.ssm_scan_ref)
    jit_ss = jax.jit(functools.partial(ssm_scan, chunk=32))
    for S, D in ((64, 256),):
        dt = jnp.asarray(np.abs(rng.normal(0.1, 0.05, (1, S, D))), jnp.float32)
        bt = jnp.asarray(rng.normal(size=(1, S, 8)), jnp.float32)
        ct = jnp.asarray(rng.normal(size=(1, S, 8)), jnp.float32)
        x = jnp.asarray(rng.normal(size=(1, S, D)), jnp.float32)
        a = -jnp.asarray(np.abs(rng.normal(1.0, 0.3, (D, 8))), jnp.float32)
        t_ref = _time(jit_ss_ref, dt, bt, ct, x, a, n=2)
        t_pal = _time(jit_ss, dt, bt, ct, x, a, n=2)
        rows.append((f"kernel/ssm_scan/{S}x{D}", t_pal, t_ref))
    # dp_layer (join-order DP layer sweep: dense candidate pricing + per-
    # column first-strict-min).  Both sides are jitted calls on device
    # arrays (dp_layer_program is the device-level entry the host wrapper
    # uses after padding); float64, so the section runs under enable_x64.
    # Shapes are block multiples and stay modest, and the section's x64 jit
    # caches are dropped afterwards — they are one-shot here, and the whole
    # quick suite runs under a guarded peak-RSS ceiling (benchmarks.compare)
    from repro.kernels.dp_layer import dp_layer_program

    params = (1.0, 1.0, 5.0, 20)
    with jax.enable_x64(True):
        for B, R, C in ((8, 256, 128), (8, 384, 128)):
            cost_a = rng.uniform(1, 100, (B, R, C))
            cost_b = rng.uniform(1, 100, (B, R, C))
            card_a = rng.uniform(0, 50, (B, R, C))
            n_src_b = rng.integers(1, 4, (B, R, C)).astype(np.float64)
            src_w_b = np.ones((B, R, C))
            bindable = rng.random((B, R, C)) < 0.5
            valid = rng.random((R, C)) < 0.6
            card_s = rng.uniform(0, 80, (B, C))
            jargs = [jnp.asarray(x) for x in
                     (cost_a, cost_b, card_a, n_src_b, src_w_b, bindable,
                      valid, card_s)]
            t_ref = _time(jax.jit(functools.partial(ref.dp_layer_ref,
                                                    params=params)), *jargs, n=3)
            pal_args = [jnp.asarray(x) for x in
                        (cost_a, cost_b, card_a, n_src_b, src_w_b,
                         bindable.astype(np.int8), valid.astype(np.int8),
                         card_s)]
            t_pal = _time(dp_layer_program(params), *pal_args, n=3)
            rows.append((f"kernel/dp_layer/{B}x{R}x{C}", t_pal, t_ref))
    jax.clear_caches()
    lines = ["== Kernel microbench (us/call; Pallas interpret vs jnp ref) =="]
    for name, t_pal, t_ref in rows:
        lines.append(f"{name:40} pallas={t_pal:10.1f}  ref={t_ref:10.1f}")
    return [(n, p, r) for n, p, r in rows], "\n".join(lines)
