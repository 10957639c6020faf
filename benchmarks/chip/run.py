#!/usr/bin/env python3
"""The chip benchmark: run one cell of ``BENCHMARK.json`` once.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine whose TPUs JAX finds.  The run
sets up, measures for ``--seconds`` and prints, as the last line of standard
output, one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``
(end-to-end ones with ``--trace 0``, per-layer ones with ``--trace 1``),
``device``, with ``--trace 1`` a ``breakdown``, and last ``checks``: every
number ``correct`` compared, beside its limit.  Facts about the run (memory,
filesystem, bytes written, compiles) come on the line before it.  Without a
TPU, or with fewer chips than the cell asks for, it exits non-zero and prints
no result.
"""
import time

T0 = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

from chiplib.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], T0, ROOT, BENCH))
