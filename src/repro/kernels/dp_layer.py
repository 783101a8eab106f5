"""On-device join-order DP: the resident fused sweep + the per-tile kernel.

``repro.core.join_order._dp_sweep`` prices, per popcount layer, every
(connected subset, connected partition) candidate pair and keeps the first
strict minimum per subset.  Two device entry points live here:

``dp_sweep_resident``
    The whole sweep as **one compiled device program**: the host enumerates
    the layer schedule once per graph topology (connected subsets, flat
    candidate-pair index tiles — see ``join_order._dp_schedule``) and ships
    only those int32 index tiles plus the seed state; a ``lax.scan`` over
    the layers then fuses candidate pricing (``CostModel.
    join_candidates_params_jnp``), the segmented first-strict-minimum
    reduction and the best-plan state scatter into one XLA program, with the
    full DP state (cost / cardinality / source counts / weights / bindable
    flags / winner strategy + split) resident on device for the whole
    sweep.  The member axis is batched straight through every gather and
    scatter.  Nothing crosses host<->device between layers — the old
    per-layer ``_pad3``/``_pad2`` round-trips were exactly the inversion
    that made ``dp_backend='jax'`` lose to numpy.  On CPU this is the
    *compiled (non-interpret)* jax path: XLA:CPU compiles the scan program
    (compiled Pallas is TPU/GPU-only), and it beats the numpy sweep at
    n >= 12 / B >= 8.

``dp_layer``
    The original per-tile Pallas kernel (grid over ``(member, column tile,
    row tile)``), kept for the tiled path on the CPU backend — layer tiles
    too large for a resident schedule under the memory budget.  It prices
    in float64, which a TPU ``pallas_call`` cannot compile, so the planner
    refuses that path on an accelerator
    (``join_order.TiledSweepUnsupportedError``) instead of running it
    anywhere else.

Both entries price in float64 (callers run under
``jax.enable_x64(True)``) and reproduce the numpy sweep's
enumeration order and first-strict-minimum tie-breaking bit for bit; the
cost-model parameters are **traced** ``(4,)`` inputs, so one compiled
program serves every ``CostModel`` — a parameter sweep (``kernel_bench``,
a user tuning weights) never retraces or thrashes the program cache.
"""
from __future__ import annotations

import functools
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode

BLOCK_R = 128
BLOCK_C = 128
_BIG_ROW = np.int32(2**31 - 1)     # "no valid pair in this column"

_STRAT_EXCL, _STRAT_HASH, _STRAT_BIND = 2, 3, 4   # mirror join_order's codes


def _kernel(params_ref, cost_a_ref, cost_b_ref, card_a_ref, n_src_b_ref,
            src_w_b_ref, bind_ref, valid_ref, card_s_ref,
            best_c_ref, best_r_ref, best_b_ref, *, block_r):
    from repro.core.cost import CostModel

    r = pl.program_id(2)

    @pl.when(r == 0)
    def _init():
        best_c_ref[...] = jnp.full(best_c_ref.shape, jnp.inf, best_c_ref.dtype)
        best_r_ref[...] = jnp.full(best_r_ref.shape, _BIG_ROW, jnp.int32)
        best_b_ref[...] = jnp.zeros(best_b_ref.shape, jnp.int32)

    valid = valid_ref[...] != 0                       # (block_r, bc)
    bindable = bind_ref[0] != 0
    card_s = card_s_ref[...]                          # (1, bc) per-subset
    pair_c, is_bind = CostModel.join_candidates_params_jnp(
        params_ref[...], cost_a_ref[0], cost_b_ref[0], card_s,
        card_a_ref[0], n_src_b_ref[0], src_w_b_ref[0], bindable)
    pair_c = jnp.where(valid, pair_c, jnp.inf)

    tile_min = jnp.min(pair_c, axis=0, keepdims=True)           # (1, bc)
    rows = (jax.lax.broadcasted_iota(jnp.int32, pair_c.shape, 0)
            + r * block_r)
    is_min = valid & (pair_c == tile_min)
    first = jnp.min(jnp.where(is_min, rows, _BIG_ROW), axis=0,
                    keepdims=True)
    bind_at = jnp.max(jnp.where(is_min & (rows == first),
                                is_bind.astype(jnp.int32), 0),
                      axis=0, keepdims=True)

    # strictly-less running update: an equal minimum in a later row tile
    # never displaces the earlier (lower-row) one
    upd = tile_min < best_c_ref[...]
    best_c_ref[...] = jnp.where(upd, tile_min, best_c_ref[...])
    best_r_ref[...] = jnp.where(upd, first, best_r_ref[...])
    best_b_ref[...] = jnp.where(upd, bind_at, best_b_ref[...])


def _bucket(n: int, block: int) -> int:
    """Padded extent for ``n``: the next power of two (>= 8) below ``block``,
    a multiple of ``block`` above it.  Buckets the kernel's trace shapes so
    layers/queries of nearby sizes share one compiled program instead of
    retracing per exact tile shape (padding is inert: ``valid`` is 0 there)."""
    if n >= block:
        return n + (-n) % block
    p = 8
    while p < n:
        p *= 2
    return p


def _pad3(x, rp, cp, dtype):
    if x.shape[1] == rp and x.shape[2] == cp:
        # extents already match the bucketed trace shape: no alloc+copy, just
        # a dtype view (astype(copy=False) is free when the dtype matches)
        return np.asarray(x).astype(dtype, copy=False)
    out = np.zeros((x.shape[0], rp, cp), dtype)
    out[:, :x.shape[1], :x.shape[2]] = x
    return out


def _pad2(x, cp, dtype):
    if x.shape[1] == cp:
        return np.asarray(x).astype(dtype, copy=False)
    out = np.zeros((x.shape[0], cp), dtype)
    out[:, :x.shape[1]] = x
    return out


class _ProgramCache:
    """Tiny LRU of compiled device programs with observable counters.

    The old ``lru_cache(maxsize=64)`` keyed the per-tile program on
    ``(params, interpret)`` — but the *trace* does not depend on the
    cost-model values at all once they are passed as a traced ``(4,)``
    array, so a cost-model parameter sweep was silently compiling (and at
    >64 sets, evicting) one program per parameter tuple.  Programs are now
    keyed on what the trace actually depends on (the kernel variant +
    ``interpret`` — jax's own jit cache handles shape specialization under
    each entry), and ``evictions`` / ``hits`` / ``misses`` make any future
    keying regression observable."""

    def __init__(self, max_entries: int = 16):
        self.max_entries = max_entries
        self._entries: OrderedDict[tuple, object] = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def __len__(self) -> int:
        return len(self._entries)

    def get(self, key: tuple, build):
        fn = self._entries.get(key)
        if fn is not None:
            self.hits += 1
            self._entries.move_to_end(key)
            return fn
        self.misses += 1
        fn = build()
        self._entries[key] = fn
        while len(self._entries) > self.max_entries:
            self._entries.popitem(last=False)
            self.evictions += 1
        return fn

    def clear(self) -> None:
        self._entries.clear()
        self.hits = 0
        self.misses = 0
        self.evictions = 0


PROGRAM_CACHE = _ProgramCache()


def _build_layer_program(interpret: bool):
    @jax.jit
    def call(params, cost_a, cost_b, card_a, n_src_b, src_w_b, bindable,
             valid, card_s):
        B, R_p, C_p = cost_a.shape          # pre-padded to bucketed extents
        br, bc = min(BLOCK_R, R_p), min(BLOCK_C, C_p)
        grid = (B, C_p // bc, R_p // br)
        pair_spec = pl.BlockSpec((1, br, bc), lambda b, c, r: (b, r, c))
        col_spec = pl.BlockSpec((1, bc), lambda b, c, r: (b, c))
        return pl.pallas_call(
            functools.partial(_kernel, block_r=br),
            grid=grid,
            in_specs=[pl.BlockSpec((4,), lambda b, c, r: (0,))]
            + [pair_spec] * 6
            + [pl.BlockSpec((br, bc), lambda b, c, r: (r, c)), col_spec],
            out_specs=[col_spec, col_spec, col_spec],
            # repro: ignore[RPR005] -- trace-time dtype only: this jitted body
            # executes under the enable_x64 context its callers (dp_layer /
            # kernel_bench) hold by documented contract
            out_shape=[jax.ShapeDtypeStruct((B, C_p), jnp.float64),
                       jax.ShapeDtypeStruct((B, C_p), jnp.int32),
                       jax.ShapeDtypeStruct((B, C_p), jnp.int32)],
            interpret=interpret,
        )(params, cost_a, cost_b, card_a, n_src_b, src_w_b, bindable, valid,
          card_s)

    return call


def dp_layer_program(params: tuple):
    """The jitted device-level entry: expects pre-padded arrays whose row /
    column extents are block multiples (see ``_bucket``), ``float64`` pair
    state and ``int8`` masks, and returns the raw padded outputs.  ``params``
    is passed on every call as a traced ``(4,)`` array, so the returned
    program is shared across cost models.  Run it under
    ``jax.enable_x64(True)``; benchmarks time this directly so the
    Pallas side is a jitted call on device arrays exactly like the jitted
    oracle — not the host wrapper with its padding logic."""
    interpret = interpret_mode()
    fn = PROGRAM_CACHE.get(("layer", interpret),
                           lambda: _build_layer_program(interpret))
    # repro: ignore[RPR005] -- the docstring contract requires callers to run
    # the returned program under enable_x64; building the params array f64
    # here would silently truncate to f32 only if that contract is broken
    p = jnp.asarray([float(v) for v in params], jnp.float64)
    return functools.partial(fn, p)


def dp_layer(cost_a, cost_b, card_a, n_src_b, src_w_b, bindable, valid,
             card_s, params: tuple):
    """Price one dense layer tile and reduce it per column.

    Inputs are the per-pair gathers described in ``ref.dp_layer_ref`` (same
    shapes, same semantics); ``params`` is the cost model's
    ``(intermediate_weight, transfer_weight, request_cost, bind_batch)``.
    Returns numpy ``(best_cost (B, C) float64, first_row (B, C) int32,
    is_bind (B, C) bool)`` with the numpy sweep's exact tie-breaking.

    Row/column extents are padded host-side to bucketed trace shapes
    (powers of two below a block, block multiples above) so nearby tile
    sizes share one compiled program; when the extents already match their
    buckets the inputs are passed through without the padding alloc+copy.
    The ``enable_x64`` context is only entered when x64 is not already on —
    hot loops (the tiled sweep fallback) enable it once around the whole
    sweep instead of paying the context switch per layer tile."""
    B, R, C = np.shape(cost_a)
    R_p, C_p = _bucket(R, BLOCK_R), _bucket(C, BLOCK_C)
    f64 = np.float64

    def run():
        call = dp_layer_program(params)
        if valid.shape == (R_p, C_p):
            valid_p = np.asarray(valid, np.int8)
        else:
            valid_p = np.zeros((R_p, C_p), np.int8)
            valid_p[:R, :C] = valid
        best, row, bind = call(
            _pad3(cost_a, R_p, C_p, f64), _pad3(cost_b, R_p, C_p, f64),
            _pad3(card_a, R_p, C_p, f64), _pad3(n_src_b, R_p, C_p, f64),
            _pad3(src_w_b, R_p, C_p, f64), _pad3(bindable, R_p, C_p, np.int8),
            valid_p, _pad2(card_s, C_p, f64))
        return (np.asarray(best)[:, :C], np.asarray(row)[:, :C],
                np.asarray(bind)[:, :C].astype(bool))

    if jax.config.jax_enable_x64:
        return run()
    with jax.enable_x64(True):
        return run()


# --------------------------------------------------------------------------
# Resident fused sweep: the whole DP as one scanned device program
# --------------------------------------------------------------------------

def _build_sweep_program():
    @jax.jit
    def dp_sweep(params, pair_a, pair_b, pair_seg, layer_cols,
                 card, excl_cost, excl_w, cost0, n_src0, src_w0):
        """One ``lax.scan`` over the padded layer schedule.

        ``pair_a``/``pair_b``/``pair_seg`` are ``(L, P)`` int32: per layer,
        the flat candidate pairs in the reference order (column-major over
        the layer's connected subsets, relative submasks ascending within a
        column); ``pair_seg`` is the pair's column position within the layer
        (sentinel ``C`` marks padding).  ``layer_cols`` is ``(L, C)`` int32:
        the layer's connected-subset masks (sentinel ``size`` marks
        padding).  ``card``/``excl_cost``/``excl_w`` are the host-
        precomputed per-(member, mask) subset cardinalities and exclusive-
        group leaf seeds (``excl_cost = inf`` where no exclusive leaf
        exists); ``cost0``/``n_src0``/``src_w0`` seed the singleton leaves
        (a mask is bind-join-able exactly when its source count is > 0, so
        there is no separate bindable plane).  Everything stays on device
        for the whole scan; the return is the final ``(cost, strat,
        split)`` state."""
        B = cost0.shape[0]
        size = cost0.shape[1]
        C = layer_cols.shape[1]
        P = pair_a.shape[1]
        INF = jnp.inf
        BIG = jnp.int32(2**31 - 1)
        pos = jnp.arange(P, dtype=jnp.int32)

        from repro.core.cost import CostModel

        def step(carry, layer):
            cost, n_src, src_w, strat, split = carry
            a, b, seg, cols = layer
            pad_pair = seg >= C                     # (P,)
            pad_col = cols >= size                  # (C,)
            a_g = jnp.where(pad_pair, 0, a)
            b_g = jnp.where(pad_pair, 0, b)
            cols_g = jnp.where(pad_col, 0, cols)

            # fused candidate pricing over the flat pair tile (member axis
            # batched straight through the gathers)
            ca = jnp.take(cost, a_g, axis=1)
            cb = jnp.take(cost, b_g, axis=1)
            card_a = jnp.take(card, a_g, axis=1)
            ns_b = jnp.take(n_src, b_g, axis=1)
            sw_b = jnp.take(src_w, b_g, axis=1)
            card_out = jnp.take(card, jnp.where(pad_pair, 0, a ^ b), axis=1)
            pair_c, is_bind = CostModel.join_candidates_params_jnp(
                params, ca, cb, card_out, card_a, ns_b, sw_b, ns_b > 0)
            pair_c = jnp.where(pad_pair[None, :], INF, pair_c)

            # segmented first-strict-minimum per column: scatter-min the
            # costs, then scatter-min the flat positions attaining them
            # (positions ascend in the reference enumeration order, so the
            # winner is the numpy sweep's first strict minimum)
            seg_min = jnp.full((B, C), INF).at[:, seg].min(
                pair_c, mode="drop")
            min_of_pair = jnp.take(seg_min, jnp.minimum(seg, C - 1), axis=1)
            elig = (pair_c == min_of_pair) & jnp.isfinite(pair_c)
            first = jnp.full((B, C), BIG).at[:, seg].min(
                jnp.where(elig, pos[None, :], BIG), mode="drop")
            fp = jnp.minimum(first, P - 1)
            split_a = jnp.take(a, fp)                       # (B, C)
            bind_at = jnp.take_along_axis(is_bind, fp, axis=1)

            # exclusive-group leaf seed: candidate index 0 in the reference
            # order — pair candidates must beat it strictly
            ec = jnp.where(pad_col[None, :], INF,
                           jnp.take(excl_cost, cols_g, axis=1))
            ew = jnp.take(excl_w, cols_g, axis=1)
            pair_win = seg_min < ec
            has_excl = jnp.isfinite(ec)
            is_excl = has_excl & ~pair_win

            # unconditional state scatter: each subset lives in exactly one
            # layer, so the current value at any scattered column is still
            # its seed — and where *no* candidate won (``pair_win`` and
            # ``has_excl`` both false) every "new" value below reproduces
            # that seed exactly (cost inf, counts 0, weight 1, strat 0).
            # Skipping the read-modify-write keeps the step at one scatter
            # per plane; padded columns (sentinel ``size``) drop out.
            cost = cost.at[:, cols].set(
                jnp.where(pair_win, seg_min, ec), mode="drop")
            n_src = n_src.at[:, cols].set(
                jnp.where(is_excl, 1.0, 0.0), mode="drop")
            src_w = src_w.at[:, cols].set(
                jnp.where(is_excl, ew, 1.0), mode="drop")
            strat = strat.at[:, cols].set(
                jnp.where(pair_win,
                          jnp.where(bind_at, _STRAT_BIND, _STRAT_HASH),
                          jnp.where(has_excl, _STRAT_EXCL, 0)
                          ).astype(jnp.int32), mode="drop")
            split = split.at[:, cols].set(
                jnp.where(pair_win, split_a, 0).astype(jnp.int32),
                mode="drop")
            return (cost, n_src, src_w, strat, split), None

        strat0 = jnp.zeros((B, size), jnp.int32)
        split0 = jnp.zeros((B, size), jnp.int32)
        (cost, _, _, strat, split), _ = jax.lax.scan(
            step, (cost0, n_src0, src_w0, strat0, split0),
            (pair_a, pair_b, pair_seg, layer_cols))
        return cost, strat, split

    return dp_sweep


def dp_sweep_resident(params: tuple, pair_a, pair_b, pair_seg, layer_cols,
                      card, excl_cost, excl_w, cost0, n_src0, src_w0):
    """Run the whole member-batched DP sweep as one compiled device program.

    Host-side contract: the schedule arrays are int32 with the sentinels
    described in the program docstring (pad ``P``/``C`` extents to shared
    buckets so nearby topologies reuse one compile — jax's jit cache keys
    on shapes under the single ``PROGRAM_CACHE`` entry); the numeric seeds
    are float64 and ``n_src0`` doubles as the bindable plane (> 0).
    Returns numpy ``(cost (B, size) float64, strat (B, size) int32, split
    (B, size) int32)`` — strategy codes match ``join_order``'s
    ``_STRAT_*`` constants, ``split`` is the winning submask A, strat 0
    means the device never wrote the mask.  This is the single
    host<->device round trip of the sweep: index tiles + seeds up, the
    final plan state down."""
    fn = PROGRAM_CACHE.get(("sweep",), _build_sweep_program)

    def run():
        # param array built under x64 so the traced values stay float64
        p = jnp.asarray([float(v) for v in params], jnp.float64)
        cost, strat, split = fn(p, pair_a, pair_b, pair_seg, layer_cols,
                                card, excl_cost, excl_w, cost0, n_src0,
                                src_w0)
        return (np.asarray(cost), np.asarray(strat), np.asarray(split))

    if jax.config.jax_enable_x64:
        return run()
    with jax.enable_x64(True):
        return run()
