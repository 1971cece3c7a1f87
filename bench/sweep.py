"""Find a live cell's knee: run it at several session counts in one
process and print, for each, the decision tail and whether the open loop's
queue grew over the window.

    python3 bench/sweep.py --workload w1_compim.frames --seconds 10 \\
        --sessions 6144,7168,8192,9216,10240

The knee is the highest session count whose queue wait does not grow
(the last third of the window's ticks wait less than a tenth of a tick
longer than the first third) and whose ``decision_p95_ms`` stays within
one frame period (500 ms).  A cell runs at 0.8 x the knee, rounded down to
a multiple of 1,024; that count goes into ``bench/cells/<cell>.json``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

if __name__ == "__main__":
    _ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(_ROOT, ".jax_cache")

from bench import harness  # noqa: E402

FRAME_MS = 500.0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--sessions", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    tick_ms = 1e3 * harness.load_cell(harness.ROOT, args.workload)[
        "mix"]["tick_s"]
    knee = None
    for n in (int(s) for s in args.sessions.split(",")):
        run_args = harness.parse([
            "--workload", args.workload, "--seed", str(args.seed),
            "--seconds", str(args.seconds), "--trace", "0",
            "--sessions", str(n)])
        line, notes = harness.run(run_args, t_start=time.perf_counter())
        growth = (notes["generator_lag_ms_last_third"]
                  - notes["generator_lag_ms_first_third"])
        p95 = line["metrics"]["decision_p95_ms"]["value"]
        ok = line["correct"] and growth < 0.1 * tick_ms and p95 <= FRAME_MS
        if ok:
            knee = n
        print(json.dumps({
            "sessions": n, "p50_ms": line["metrics"]["decision_p50_ms"][
                "value"], "p95_ms": p95,
            "wait_first_third_ms": notes["generator_lag_ms_first_third"],
            "wait_last_third_ms": notes["generator_lag_ms_last_third"],
            "pushes": notes["pushes"], "tiles": notes["tiles"],
            "correct": line["correct"], "sustained": ok}), flush=True)
    print(json.dumps({"workload": args.workload, "knee": knee,
                      "cell_sessions": None if knee is None else
                      int(0.8 * knee) // 1024 * 1024}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
