"""Pallas TPU kernels for the statistics/engine hot spots.

Each kernel has a pure-jnp oracle in ``ref.py`` and a jit'd public wrapper in
``ops.py``. Kernels are written with MXU/VPU-aligned block shapes for TPU as
the target; they run compiled on an accelerator and in Pallas interpret mode
on the CPU backend, where the tests validate them (``interpret_mode``):

  * ``sorted_intersect`` -- multiplicity-weighted intersection count of sorted
    id lists (Algorithm 1's inner loop);
  * ``seg_bitmap``      -- per-entity predicate bitmaps as a one-hot MXU
    matmul (CS computation's segmented OR, re-thought for the MXU);
  * ``join_count``      -- per-probe-row match counts against a sorted build
    side (bounded-buffer join sizing in the distributed engine);
  * ``summary_probe``   -- batched bitset AND + popcount between entity
    summaries (candidate federated-CP pruning);
  * ``dp_layer``        -- the join-order DP's per-layer candidate pricing +
    first-strict-minimum reduction, gridded over (member, column tile, row
    tile); float64, bit-identical to the numpy sweep (``dp_backend='jax'``).
"""


def interpret_mode() -> bool:
    """Whether Pallas kernels run in interpret mode: exactly when the default
    backend is the CPU, which has no compiled Pallas.  The single place this
    is decided — an accelerator always gets the compiled kernel, never a
    silent interpreter."""
    import jax

    return jax.default_backend() == "cpu"
