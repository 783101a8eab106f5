"""Public jit'd wrappers: padding, dtype handling, and host-friendly entry
points for the Pallas kernels. Each kernel runs compiled on an accelerator and in
interpret mode on the CPU backend (``repro.kernels.interpret_mode``).

Host-coercion audit (``repro.analysis`` RPR001): every ``int(...)`` /
``np.asarray(...)`` in this module sits in an *untraced* host entry point —
the jit boundary is the kernel call each wrapper makes, so the coercions
here are the single intended device->host sync per call, not a hidden sync
inside a traced body.  Keep it that way: anything new that runs *under*
``jax.jit``/``pallas_call`` must not coerce traced values (the analyzer's
jit-reachability inference will flag it).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.kernels.join_count import BLOCK_B as JC_BB, BLOCK_P as JC_BP, join_count
from repro.kernels.seg_bitmap import BLOCK_N as SB_BN, BLOCK_S as SB_BS, NBUCKETS, seg_bitmap
from repro.kernels.sorted_intersect import BLOCK_A as SI_BA, BLOCK_B as SI_BB, sorted_intersect_weighted
from repro.kernels.summary_probe import BLOCK_A as SP_BA, BLOCK_B as SP_BB, BLOCK_W as SP_BW, summary_probe


def _pad_to(x: np.ndarray | jax.Array, mult: int, fill) -> jax.Array:
    n = x.shape[0]
    m = (-n) % mult
    if m == 0:
        return jnp.asarray(x)
    return jnp.concatenate([jnp.asarray(x), jnp.full((m,) + x.shape[1:], fill, x.dtype)])


def intersect_count(a, aw, b, bw) -> int:
    """Weighted intersection count of sorted unique id lists."""
    a = _pad_to(np.asarray(a, np.int32), SI_BA, -1)
    aw = _pad_to(np.asarray(aw, np.int32), SI_BA, 0)
    b = _pad_to(np.asarray(b, np.int32), SI_BB, -2)
    bw = _pad_to(np.asarray(bw, np.int32), SI_BB, 0)
    return int(sorted_intersect_weighted(a, aw, b, bw))


def predicate_bitmaps(seg, bucket, n_seg) -> np.ndarray:
    """(n_seg, NBUCKETS) bool predicate-presence bitmaps."""
    seg = _pad_to(np.asarray(seg, np.int32), SB_BN, -1)
    bucket = _pad_to(np.asarray(bucket, np.int32), SB_BN, 0)
    n_seg_p = n_seg + ((-n_seg) % SB_BS)
    counts = seg_bitmap(seg, bucket, n_seg_p)
    return np.asarray(counts[:n_seg] > 0)


def match_counts(probe, build, build_w) -> np.ndarray:
    """(len(probe),) int32 match multiplicities against the sorted build."""
    n = len(probe)
    p = _pad_to(np.asarray(probe, np.int32), JC_BP, -1)
    b = _pad_to(np.asarray(build, np.int32), JC_BB, -2)
    w = _pad_to(np.asarray(build_w, np.int32), JC_BB, 0)
    return np.asarray(join_count(p, b, w))[:n]


def signature_overlap(a_sig, b_sig) -> np.ndarray:
    """(nA, nB) int32 popcounts of pairwise signature ANDs.

    Accepts uint64-word signatures (host layout) and converts to int32 words.
    """
    a32 = _u64_to_i32(np.asarray(a_sig))
    b32 = _u64_to_i32(np.asarray(b_sig))
    na, nb = a32.shape[0], b32.shape[0]
    a32 = _pad2(a32, SP_BA, SP_BW)
    b32 = _pad2(b32, SP_BB, SP_BW)
    out = summary_probe(jnp.asarray(a32), jnp.asarray(b32))
    return np.asarray(out)[:na, :nb]


def flash_attention_gqa(q, k, v, *, causal=True, window=0):
    """(B, S, H, hd) GQA wrapper over the flash kernel: broadcasts KV heads,
    flattens (B, H) into the kernel's grid axis. Scaling included."""
    from repro.kernels.flash_attention import flash_attention

    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    kb = jnp.repeat(k, G, axis=2).transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    vb = jnp.repeat(v, G, axis=2).transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    qb = (q * hd ** -0.5).transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    out = flash_attention(qb, kb, vb, causal=causal, window=window)
    return out.reshape(B, H, S, hd).transpose(0, 2, 1, 3)


def selective_scan(dt, bt, ct, x, a, chunk: int = 64):
    """Chunked Mamba selective scan (see kernels/ssm_scan.py)."""
    from repro.kernels.ssm_scan import ssm_scan

    return ssm_scan(dt, bt, ct, x, a, chunk=chunk)


def _u64_to_i32(x: np.ndarray) -> np.ndarray:
    if x.dtype == np.uint64:
        return x.view(np.uint32).astype(np.int32).reshape(x.shape[0], -1)
    return x.astype(np.int32)


def _pad2(x: np.ndarray, row_mult: int, col_mult: int) -> np.ndarray:
    r = (-x.shape[0]) % row_mult
    c = (-x.shape[1]) % col_mult
    return np.pad(x, ((0, r), (0, c)))
