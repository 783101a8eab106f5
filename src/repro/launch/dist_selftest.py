"""Distributed-engine self-test: run the federated workload through the
shard_map executor on a small fake-device CPU mesh and compare against the
exact oracle, row counts included.  Run it as its own process: the fake-device XLA flag is set
before jax is imported, so it never leaks into a parent (smoke tests must
see 1 device).

Usage: python -m repro.launch.dist_selftest [n_dev_data] [n_dev_model]
"""
import os
import sys
from collections import Counter

_d = int(sys.argv[1]) if len(sys.argv) > 1 else 4
_m = int(sys.argv[2]) if len(sys.argv) > 2 else 2
os.environ["XLA_FLAGS"] = (
    f"--xla_force_host_platform_device_count={_d * _m} "
    + os.environ.get("XLA_FLAGS", "")
)

import jax  # noqa: E402


def main() -> int:
    from repro.core.federation import build_federated_stats
    from repro.core.planner import OdysseyOptimizer
    from repro.engine.distributed import DistributedEngine
    from repro.engine.local import LocalEngine, naive_evaluate
    from repro.rdf.generator import (
        FederationSpec,
        LinkSpec,
        SourceSpec,
        generate_federation,
        generate_workload,
    )

    # four sources on every mesh: a (2, m) mesh holds two per data shard
    spec = FederationSpec(sources=[
        SourceSpec("A", n_entities=160, n_templates=6, n_local_preds=10),
        SourceSpec("B", n_entities=120, n_templates=5, n_local_preds=8,
                   links=[LinkSpec("owl:sameAs", "A", 0.5)]),
        SourceSpec("C", n_entities=100, n_templates=4, n_local_preds=8,
                   links=[LinkSpec("c:ref", "B", 0.4), LinkSpec("c:self", "C", 0.3)]),
        SourceSpec("D", n_entities=80, n_templates=4, n_local_preds=8,
                   links=[LinkSpec("owl:sameAs", "A", 0.4)]),
    ], seed=21)
    fed, gt = generate_federation(spec)
    stats = build_federated_stats(fed)
    queries = generate_workload(fed, gt, n_star=6, n_hybrid=4, n_path=2, seed=9)
    mesh = jax.make_mesh((_d, _m), ("data", "model"))
    opt = OdysseyOptimizer(stats)
    local = LocalEngine(fed)
    aware = os.environ.get("REPRO_PARTITION_AWARE", "1") == "1"
    # a small first capacity, so the larger answers take the overflow retry
    dist = DistributedEngine(fed, mesh, cap=256, partition_aware=aware)

    n_ok = n_run = retries = 0
    for q in queries:
        plan = opt.optimize(q)
        if plan.fallback:
            continue  # variable predicates: the SPMD engine refuses them
        proj = q.effective_projection()
        rel_l = local.execute(plan).rows
        want = Counter(zip(*[rel_l[v].tolist() for v in proj]))
        gold = naive_evaluate(fed, q, bag=True)
        res_d = dist.execute(plan)
        rel_d = res_d.rows
        retries += res_d.metrics.capacity_retries
        got = Counter(zip(*[rel_d[v].tolist() for v in proj]))
        n_run += 1
        if got == gold == want:
            n_ok += 1
        else:
            print(f"FAIL {q.name}: dist={got.total()} local={want.total()} "
                  f"gold={gold.total()} rows")
            a = sorted((gold - got).items())[:3]
            b = sorted((got - gold).items())[:3]
            print("  missing:", a, " extra:", b)
    print(f"dist_selftest: {n_ok}/{n_run} queries OK on mesh ({_d},{_m}), "
          f"{retries} capacity retries")
    return 0 if (n_run > 0 and n_ok == n_run) else 1


if __name__ == "__main__":
    sys.exit(main())
