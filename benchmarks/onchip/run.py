"""On-chip benchmark of federated SPARQL serving.

    python3 benchmarks/onchip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Run from the root of a checkout, as the only process that uses the chip.
Set-up time counts from here.  Without a TPU, or with fewer chips than the
cell asks for, it exits non-zero and prints no result.  The last line of
standard output is the run's result as one JSON object; the last lines of
standard error are the numbers compared for ``correct``, each beside its
limit.  See ``obench/harness.py``.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(HERE, "..", "..", "src")]

from obench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
