#!/usr/bin/env python3
"""Run one benchmark cell once on the chip; see ``bench/harness.py``.

    python3 bench/run.py --workload w1_compim.frames --seed 7 --seconds 10 --trace 0
"""

import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

if __name__ == "__main__":
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    # the compile cache lives in the checkout, at a fixed path
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    from bench import harness

    sys.exit(harness.main(sys.argv[1:], t_start=T_START))
