"""Benchmark harness: one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--scale S] [--quick]

Prints ``name,us_per_call,derived`` CSV rows (stdout) and human-readable
tables (stderr + results/benchmarks.txt).

``--quick`` is the CI smoke: besides the hard in-bench assertions (batched
planning >= 3x the sequential loop, incremental statistics lifecycle >= 3x
the rebuild) it writes the guarded metrics — geomean planner speedups, batch
planning throughput, statistics-lifecycle speedups, peak RSS — to
``results/bench_quick.json`` for ``benchmarks.compare`` to diff against the
committed ``benchmarks/baseline_quick.json`` (the CI benchmark-regression
gate).
"""
from __future__ import annotations

import argparse
import json
import os
import resource
import sys


def _peak_rss_mb() -> float:
    ru = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    return ru / (2**20 if sys.platform == "darwin" else 1024)


# guarded metrics: name -> direction (True == higher is better)
HIGHER_IS_BETTER = {
    "planner_geomean_speedup_x": True,
    "planner_cache_hit_x": True,
    "batch_throughput_x": True,
    "stats_remove_speedup_x": True,
    "stats_refresh_speedup_x": True,
    "dp_sweep_jax_vs_numpy_x": True,
    "extended_completeness": True,
    "serve_throughput_x": True,
    "failover_salvage_x": True,
    "peak_rss_mb": False,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=None)
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    scale = args.scale if args.scale is not None else (0.25 if args.quick else 1.0)

    from repro.common.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import fedbench_figs as F
    from benchmarks import (
        adaptive_bench,
        kernel_bench,
        planner_bench,
        roofline_bench,
        serve_bench,
        stats_refresh_bench,
    )
    from benchmarks.common import run_all

    csv_rows: list[tuple] = []
    tables: list[str] = []
    metrics: dict[str, float] = {}

    def add(result):
        csv, text = result[0], result[1]
        csv_rows.extend(csv)
        tables.append(text)
        if len(result) > 2 and result[2]:
            metrics.update(result[2])

    add(F.table2_statistics(scale))
    add(F.cardinality_accuracy(scale))
    # group-algebra workload: every OPTIONAL/UNION/FILTER query's plan must
    # execute bit-identical to the oracle (guarded, hard floor 1.0)
    add(F.extended_workload(scale))
    runs = run_all(scale)
    incomplete = [r for r in runs if not r.complete]
    tables.append(f"result completeness: {len(runs) - len(incomplete)}/{len(runs)} "
                  f"runs complete" + (f" INCOMPLETE: {[(r.engine, r.query) for r in incomplete]}"
                                      if incomplete else ""))
    add(F.fig4_optimization_time(runs))
    add(F.fig5_selected_sources(runs))
    add(F.fig6_subqueries(runs))
    add(F.fig7_execution_time(runs))
    add(F.fig8_transferred_tuples(runs))
    add(F.fig9_hybrids(runs))
    add(planner_bench.run(scale))
    # --quick (the CI smoke) asserts batched planning >= 3x the loop
    add(planner_bench.run_batch(scale, assert_speedup=args.quick))
    add(planner_bench.run_large(quick=args.quick))
    # informational until the next baseline refresh: the on-device (Pallas)
    # DP layer sweep vs the numpy sweep, bit-identical plans asserted
    add(planner_bench.run_dp_backends())
    # serving loop: open-loop arrivals, affinity+pipeline vs arrival-order
    # drain — guarded sustained-throughput multiple (hard floor 1.0: the
    # scheduler must beat the legacy drain loop) + per-request answer parity
    add(serve_bench.run(scale, quick=args.quick))
    # --quick also asserts incremental failover >= 3x full rebuild
    add(stats_refresh_bench.run(scale, assert_speedup=args.quick))
    # mid-query endpoint death: pipeline salvage vs exclude-and-replan —
    # guarded recovery-cost multiple (hard floor 1.0: keeping the shipped
    # operator state must never cost more than re-executing from scratch)
    add(adaptive_bench.run(scale, quick=args.quick))
    add(kernel_bench.run())
    add(roofline_bench.run())
    metrics["peak_rss_mb"] = _peak_rss_mb()

    text = "\n\n".join(tables)
    os.makedirs("results", exist_ok=True)
    with open("results/benchmarks.txt", "w") as f:
        f.write(text)
    print(text, file=sys.stderr)

    if args.quick:
        payload = {
            "schema": 1,
            "scale": scale,
            "metrics": {
                name: {"value": float(value),
                       "higher_is_better": HIGHER_IS_BETTER.get(name, True)}
                for name, value in sorted(metrics.items())
            },
        }
        with open("results/bench_quick.json", "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"wrote results/bench_quick.json ({len(metrics)} guarded metrics)",
              file=sys.stderr)

    print("name,us_per_call,derived")
    for name, us, derived in csv_rows:
        print(f"{name},{us:.3f},{derived}")


if __name__ == "__main__":
    main()
