"""Serve federated SPARQL queries end to end on one accelerator chip.

Drives the system's main path once, through the entry points a user calls:
a FedBench-shaped nine-source federation (``fedbench_like_spec``, scale 10:
about 1.07M triples) is loaded onto the device by ``DistributedEngine``, and
the eighteen conjunctive star, hybrid and path queries of
``generate_workload`` are served by ``QueryServeEngine`` with the join-order
DP on the device (``dp_backend="jax"``).  Every answer must equal
``naive_evaluate``'s as a multiset of projected rows (SPARQL's bag
semantics: row counts matter where a query has no DISTINCT); the resident DP
sweep must run and the tiled one must not; no result may come from a
fallback.  The last line of standard output
is one JSON object naming the device.

    python chip_smoke.py                  # one TPU chip
    python chip_smoke.py --chips 4        # the executor alone on a 2x2 mesh
    python chip_smoke.py --cpu-rehearsal  # tiny scale on the CPU backend

Without an accelerator (and without ``--cpu-rehearsal``) it exits non-zero
and prints no result.  Run it as the only process that uses the chip.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from collections import Counter

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

SEED = 7                          # fedbench_like_spec's data
PER_FAMILY = 6
FAMILIES = ("ST", "HY", "PA")     # star, hybrid (two linked stars), path


def fail(msg: str) -> "SystemExit":
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr)
    return SystemExit(1)


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the served path on one chip; 4: the SPMD "
                         "executor alone on a 2x2 mesh, against the oracle")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="run on the CPU backend at a tiny scale (virtual "
                         "devices for --chips 4); the last line names the CPU")
    ap.add_argument("--scale", type=float, default=None,
                    help="fedbench_like_spec scale (default 10, rehearsal 0.3)")
    return ap.parse_args(argv)


def selective_first(q, union):
    """``q`` with its patterns in the order the oracle evaluates fastest:
    the most selective pattern first, then each time the most selective one
    that shares a variable with those before it.  A BGP's answer does not
    depend on the order of its patterns, but ``naive_evaluate`` binds each
    pattern once per binding of the ones before it, and a query that opens
    on a predicate as broad as rdf:type would keep it busy for minutes."""
    todo, order, bound = list(q.patterns), [], set()
    while todo:
        linked = [tp for tp in todo if tp.variables() & bound] or todo
        tp = min(linked, key=lambda tp: union.count(*tp.constants()))
        todo.remove(tp)
        order.append(tp)
        bound |= tp.variables()
    return dataclasses.replace(q, patterns=order)


def answer_bag(rows, q) -> Counter:
    """The projected rows of a result, as a multiset."""
    proj = q.effective_projection()
    return Counter(zip(*[rows[v].tolist() for v in proj]))


class CompileCounter:
    """Backend compiles and persistent-cache hits, from jax.monitoring."""

    def __init__(self):
        import jax.monitoring as mon

        self.requests = 0
        self.cache_hits = 0

        def on_duration(event, duration, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.requests += 1

        def on_event(event, **kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        mon.register_event_duration_secs_listener(on_duration)
        mon.register_event_listener(on_event)

    @property
    def compiles(self) -> int:
        return self.requests - self.cache_hits


def main(argv=None) -> int:
    args = parse_args(argv)
    if args.cpu_rehearsal:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.chips} "
            + os.environ.get("XLA_FLAGS", ""))
    import jax
    import numpy as np

    if args.cpu_rehearsal:
        jax.config.update("jax_platforms", "cpu")
    from repro.common.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    counter = CompileCounter()

    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    want = "cpu" if args.cpu_rehearsal else "tpu"
    if platform != want:
        raise fail(f"needs a {want.upper()} backend, found {platform!r} "
                   "(--cpu-rehearsal runs on the CPU at a tiny scale)")
    if len(devices) < args.chips:
        raise fail(f"needs {args.chips} devices, found {len(devices)}")
    scale = args.scale if args.scale is not None else (0.3 if args.cpu_rehearsal else 10)
    print(f"device: {platform} {kind} x{args.chips}; compile cache {cache_dir}")

    from repro.core.decomposition import decompose
    from repro.core.federation import build_federated_stats
    from repro.engine.distributed import DistMetrics, DistributedEngine
    from repro.engine.local import naive_evaluate
    from repro.rdf.dataset import TripleTable
    from repro.rdf.generator import (fedbench_like_spec, generate_federation,
                                     generate_workload)

    # -- set-up: federation, statistics, mesh, device-resident tables -------
    t0 = time.perf_counter()
    fed, gt = generate_federation(fedbench_like_spec(scale=scale, seed=SEED))
    stats = build_federated_stats(fed)
    shape = (2, 2) if args.chips == 4 else (1, 1)
    mesh = jax.sharding.Mesh(np.array(devices[:args.chips]).reshape(shape),
                             ("data", "model"))
    engine = DistributedEngine(fed, mesh)
    jax.block_until_ready((engine.tables, engine.trow))
    resident = sum(s.data.nbytes for a in (engine.tables, engine.trow)
                   for s in a.addressable_shards)
    print(f"federation: scale {scale}, {len(fed.sources)} sources, "
          f"{engine.n_triples} triples resident on {args.chips} device(s), "
          f"{resident} bytes (table_cap {engine.table_cap}, mesh {shape}); "
          f"set-up {time.perf_counter() - t0:.3f}s")
    if not args.cpu_rehearsal and engine.n_triples < 1_000_000:
        raise fail(f"only {engine.n_triples} triples resident, expected >= 1M")

    union = TripleTable.from_triples(
        *(np.concatenate([getattr(s.table, c) for s in fed.sources]) for c in "spo"))
    queries = generate_workload(fed, gt, n_star=PER_FAMILY, n_hybrid=PER_FAMILY,
                                n_path=PER_FAMILY, seed=13)
    n_multi = sum(len(decompose(q).stars) >= 2 for q in queries)
    if len(queries) < 6 or n_multi < 2 or {q.name[:2] for q in queries} != set(FAMILIES) \
            or not all(q.is_conjunctive() for q in queries):
        raise fail(f"workload too thin: {[q.name for q in queries]}")
    widest = max(len(q) for q in queries)
    print(f"memory: device bytes_limit {engine.bytes_limit()}; capacity bound "
          f"{engine.max_cap(2 * widest)} rows for the widest query "
          f"({widest} patterns)")

    t0 = time.perf_counter()
    gold = [naive_evaluate(fed, selective_first(q, union), bag=True)
            for q in queries]
    print(f"oracle: naive_evaluate over {len(queries)} queries "
          f"({', '.join(q.name for q in queries)}) in "
          f"{time.perf_counter() - t0:.3f}s")

    if args.chips == 4:
        passes = run_executor_only(engine, stats, queries)
    else:
        passes = run_served(fed, stats, engine, queries)

    # -- checks: every answer of both passes equals the oracle's ------------
    n_equal = 0
    for results in passes:
        for q, want_rows, (rows, metrics, fallback) in zip(queries, gold, results):
            if fallback is not None or not isinstance(metrics, DistMetrics):
                raise fail(f"{q.name} was answered by a fallback ({fallback})")
            got = answer_bag(rows, q)
            if got != want_rows:
                raise fail(f"{q.name}: {got.total()} rows, oracle has "
                           f"{want_rows.total()} (missing "
                           f"{sorted((want_rows - got).items())[:3]}, extra "
                           f"{sorted((got - want_rows).items())[:3]})")
            n_equal += 1
    cold = [m for _, m, _ in passes[0]]
    print(f"answers: {len(queries)} queries answered twice, {n_equal} of "
          f"{2 * len(queries)} answers equal to the oracle as multisets "
          f"({sum(g.total() for g in gold)} rows, {sum(not q.distinct for q in queries)} "
          "queries without DISTINCT); "
          f"{sum(m.capacity_retries for m in cold)} capacity retries (final "
          f"capacities {[m.capacity for m in cold]}); shipped tuples "
          f"{sum(m.transferred_tuples for m in cold)}, collective bytes "
          f"{sum(m.collective_bytes for m in cold)}")
    print(f"compiles: {counter.compiles} backend compiles, "
          f"{counter.cache_hits} persistent-cache hits")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": args.chips}}))
    return 0


def run_served(fed, stats, engine, queries):
    """One chip: the full served path, cold then warm (plan-cache hits)."""
    from repro.core import join_order
    from repro.core.planner import OdysseyOptimizer, plans_equal
    from repro.serve.query import QueryServeEngine

    for k in join_order.DP_SWEEP_COUNTERS:
        join_order.DP_SWEEP_COUNTERS[k] = 0
    server = QueryServeEngine(fed, stats, dp_backend="jax", engine=engine)
    passes = []
    for label in ("cold", "warm"):
        t0 = time.perf_counter()
        reqs = [server.submit(q) for q in queries]
        server.drain()
        wall = time.perf_counter() - t0
        done = [r.t_done - reqs[0].t_submit for r in reqs]
        print(f"{label}: {len(reqs)} queries in {wall:.6f}s wall "
              f"({wall / len(reqs):.6f}s per query); all submitted at once, "
              "each done after "
              + " ".join(f"{q.name}={x:.6f}s" for q, x in zip(queries, done)))
        passes.append(reqs)
    counters = dict(join_order.DP_SWEEP_COUNTERS)
    print(f"dp sweeps: {counters}")
    if counters["resident"] < 1 or counters["tiled"] != 0:
        raise fail(f"device DP sweep not used as required: {counters}")
    if any(r.plan.fallback for reqs in passes for r in reqs):
        raise fail("a query was planned with the variable-predicate fallback")
    host = OdysseyOptimizer(stats, dp_backend="numpy").optimize_batch(queries)
    same = sum(plans_equal(r.plan, p) for r, p in zip(passes[0], host))
    print(f"plans: {same}/{len(queries)} device-DP plans equal the "
          "dp_backend='numpy' plan")
    return [[(r.rows, r.metrics, r.fallback) for r in reqs] for reqs in passes]


def run_executor_only(engine, stats, queries):
    """Four chips: only the sharded executor, on host-planned queries."""
    from repro.core.planner import OdysseyOptimizer

    plans = OdysseyOptimizer(stats).optimize_batch(queries)
    passes = []
    for label in ("cold", "warm"):
        t0 = time.perf_counter()
        out = [engine.execute(p) for p in plans]
        wall = time.perf_counter() - t0
        print(f"{label}: {len(plans)} plans executed in {wall:.6f}s wall "
              f"({wall / len(plans):.6f}s per query)")
        passes.append([(r.rows, r.metrics, r.fallback) for r in out])
    return passes


if __name__ == "__main__":
    sys.exit(main())
