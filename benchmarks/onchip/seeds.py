"""Many seeds of one cell in one process: the readings the limits of
``correct`` are set from, and the control that has to fail them.

    python3 benchmarks/onchip/seeds.py --workload <cell> --seeds 1,2,3 \\
        --seconds <s> [--control]

Sets the cell up once (``run.py``'s set-up), then measures one window per
seed and prints one JSON line for each: the seed, ``correct``, every number
compared with its limit, and the end-to-end metrics.  Later windows find the
earlier ones' plans and capacities cached, so their timings are not the
benchmark's; only the comparison is read from them.  ``--control`` runs the
program with the executor's capacity retries switched off
(``obench/control.py``).  Not part of a benchmark run.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(HERE, "..", "..", "src")]

from obench import harness  # noqa: E402
from obench.control import control_engine_class  # noqa: E402


def by_template(answered) -> dict:
    """Per template: answers, mean latency (s), mean service (s), largest
    capacity, mean retries."""
    out: dict = {}
    for name, lat, service, cap, retries in answered:
        n, s, v, c, r = out.get(name, (0, 0.0, 0.0, 0, 0))
        out[name] = (n + 1, s + lat, v + service, max(c, cap), r + retries)
    return {k: [n, s / n, v / n, c, r / n] for k, (n, s, v, c, r) in sorted(out.items())}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args()
    seeds = [int(s) for s in args.seeds.split(",")]
    root = Path(HERE).resolve().parents[1]
    try:
        cell = harness.load_cell(root, args.workload, trace=False)
        harness.enable_compile_cache(root)
        bench = harness.Bench(root, cell, T_START, seeds[0], args.seconds,
                              engine_class=control_engine_class() if args.control else None)
    except harness.Refusal as e:
        print(f"seeds: refused: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"workload": cell.name, "control": args.control,
                      "setup_s": bench.setup_s}), flush=True)
    for seed in seeds:
        window, checks, device, attempted, failed = bench.measure(seed, args.seconds, False)
        line = harness.result_line(cell, window, checks, device, attempted, failed)
        lat = sorted(window.latencies_s)
        print(json.dumps({"seed": seed, "correct": line["correct"],
                          "compared": checks.compared, "attempted": attempted,
                          "failed": failed, "checks": line["checks"],
                          "metrics": line["metrics"], "answered_in_window":
                          window.completed_in_window, "latency_s_quartiles":
                          [lat[len(lat) // 4], lat[len(lat) // 2], lat[3 * len(lat) // 4],
                           lat[-1]] if lat else [], "serve": window.serve,
                          "capacity_retries": window.capacity_retries,
                          "compile_requests": window.compile_requests,
                          "compiled": window.compiled,
                          "memory_peak_bytes": device["memory_peak_bytes"],
                          "templates": by_template(window.answered)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
