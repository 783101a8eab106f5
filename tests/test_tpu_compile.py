"""Compile the device programs of the served path for a TPU v5e that is
described, not attached: the resident join-order DP sweep and the SPMD
executor's star, exchange and collect programs in the multi-source layout.
What the chip's compiler refuses fails here, at no chip time.  Nothing runs,
so nothing here says anything about results or times.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

# the smoke's one-chip federation: fedbench_like_spec(scale=10) has nine
# sources, the largest (DBpedia-shaped) holds 341k triples -> table_cap 2^19
N_SOURCES = 9
TABLE_CAP = 1 << 19
CAP = 8192


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- any failure to describe skips
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _mesh(topo, shape):
    n = shape[0] * shape[1]
    return Mesh(np.array(topo.devices[:n]).reshape(shape), ("data", "model"))


@pytest.mark.parametrize("shape", [(1, 1), (2, 2)])
def test_executor_fits_its_capacity_bound(topo, shape):
    """At the capacity ``capacity_bound`` allows under a byte limit, the
    compiled programs' arguments, outputs and temporaries fit that limit:
    the engine's memory model is not a guess.  The limit is a cut of the
    chip's 16 GiB so that the compile stays short (at 16 GiB the bound is
    2^23 rows on one chip, too large a compile for a test).  The compiled
    memory does not grow linearly with the capacity: the compiler picks its
    sort by size."""
    from repro.engine.distributed import (capacity_bound, fed_dryrun_lower,
                                          source_slots)

    limit = 1 << 30
    mesh = _mesh(topo, shape)
    table_cap = TABLE_CAP // shape[1]
    n_pat1, n_pat2 = 3, 2
    cap = capacity_bound(limit, mesh, source_slots(N_SOURCES, mesh), table_cap,
                         2 * (n_pat1 + n_pat2))
    assert cap >= 1 << 16
    mem = fed_dryrun_lower(mesh, cap=cap, table_cap=table_cap, n_pat1=n_pat1,
                           n_pat2=n_pat2, n_sources=N_SOURCES).compile() \
        .memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used <= limit, (cap, used)


def test_resident_dp_sweep_compiles_x64(one_chip):
    """The resident ``lax.scan`` sweep at n=12 stars, B=8 members, in
    (emulated) float64 — the program ``dp_backend='jax'`` runs per shape."""
    from repro.core import join_order as jo
    from repro.kernels.dp_layer import _build_sweep_program
    from repro.rdf.shapes import shaped_planning_inputs

    g, _, _, _ = shaped_planning_inputs("tree", 12, seed=3)
    B, size = 8, 1 << 12
    sched = jo._dp_schedule(g, jo.DP_BLOCK_BYTES, B)
    assert sched is not None and jo._resident_fits(sched, B, jo.DP_BLOCK_BYTES)
    sds = jax.ShapeDtypeStruct
    with jax.enable_x64(True):
        idx = [sds(a.shape, jnp.int32, sharding=one_chip)
               for a in (sched.pair_a, sched.pair_b, sched.pair_seg,
                         sched.layer_cols)]
        plane = sds((B, size), jnp.float64, sharding=one_chip)
        params = sds((4,), jnp.float64, sharding=one_chip)
        compiled = _build_sweep_program().lower(
            params, *idx, plane, plane, plane, plane, plane, plane).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 0
    assert mem.temp_size_in_bytes < 16 * 2**30


@pytest.mark.parametrize("shape", [(1, 1), (2, 2)])
def test_executor_programs_compile(topo, shape):
    """Two star scans, the exchange join and the collect in the
    ``DistributedEngine`` layout: all nine sources on one chip, or blocks of
    them on a 2x2 mesh with collectives over both axes."""
    from repro.engine.distributed import fed_dryrun_lower

    mesh = _mesh(topo, shape)
    table_cap = TABLE_CAP // shape[1]
    compiled = fed_dryrun_lower(mesh, cap=CAP, table_cap=table_cap,
                                n_sources=N_SOURCES).compile()
    mem = compiled.memory_analysis()
    # the tables are the largest argument: every source slot of the block
    local = -(-N_SOURCES // shape[0])
    assert mem.argument_size_in_bytes >= local * table_cap * 3 * 4
    hlo = compiled.as_text()
    if shape != (1, 1):
        assert "all-to-all" in hlo and "all-gather" in hlo
