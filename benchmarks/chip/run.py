#!/usr/bin/env python3
"""Chip benchmark of the bittide scenario engines: one run of one cell.

    python3 benchmarks/chip/run.py --workload torus22.free --seed 1 \
        --seconds 10 --trace 0

The cells, their metrics and bounds are in ``BENCHMARK.json`` at the root
of the checkout; ``chipbench/harness.py`` says what a run does.
"""
import time

T0 = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

from chipbench.host import fix_allocator  # noqa: E402

fix_allocator()   # before anything large is allocated

from chipbench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t0=T0))
