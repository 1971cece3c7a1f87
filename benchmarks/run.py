"""Benchmark harness: one module per paper table/figure + framework perf.

Prints ``name,us_per_call,derived`` CSV rows to stdout and writes a
machine-readable ``BENCH_<module>.json`` per module to ``--out-dir`` (CI
uploads these as artifacts, so the perf trajectory accumulates).

  fig1c       naive-sparse energy/area breakdown       (paper Fig. 1c)
  fig4        delay/accuracy vs max HV density          (paper Fig. 4)
  fig5        4-variant energy/area + headline ratios   (paper Fig. 5)
  table1      SotA comparison                           (paper Table I)
  throughput  HDC pipeline throughput + traffic model   (TPU-side perf)
  fleet       StreamingFleet vs looped-session serving  (framework)
  online      one-shot vs iterative/online retraining   (framework)
  reliability BER degradation curves + AM ECC tradeoff  (framework)
  channelfault electrode faults: quarantine vs unmasked  (framework)
  coldstart   fresh-JIT vs warm-cache vs serialized AOT (framework)
  churn       elastic fleet under Poisson session churn (framework)
  roofline    aggregated dry-run roofline terms          (framework)

A module that raises still prints a ``<mod>.ERROR`` CSV row (so partial runs
stay greppable) but the error is ALSO recorded in the module's JSON and the
process exits nonzero — crashes do not masquerade as results.
"""

from __future__ import annotations

import argparse
import os
import sys
import traceback

from benchmarks.common import emit, write_bench_json

DEFAULT_MODULES = ["fig1c", "fig4", "fig5", "table1", "throughput", "fleet",
                   "online", "reliability", "channelfault", "coldstart",
                   "churn", "roofline"]


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("modules", nargs="*", default=None,
                    help=f"benchmark modules to run (default: {' '.join(DEFAULT_MODULES)})")
    ap.add_argument("--out-dir", default=".",
                    help="directory for the BENCH_<module>.json artifacts")
    ap.add_argument("--tiny", action="store_true",
                    help="CI-smoke mode: modules shrink to tiny configs (sets BENCH_TINY=1)")
    args = ap.parse_args(argv)
    if args.tiny:
        os.environ["BENCH_TINY"] = "1"
    from repro.runtime.aot import setup_compilation_cache

    setup_compilation_cache()
    mods = args.modules or DEFAULT_MODULES
    os.makedirs(args.out_dir, exist_ok=True)

    failed: list[str] = []
    print("name,us_per_call,derived")
    for mod in mods:
        name = "benchmarks.roofline" if mod == "roofline" else f"benchmarks.bench_{mod}"
        try:
            module = __import__(name, fromlist=["run"])
            rows = module.run()
            emit(rows)
            write_bench_json(args.out_dir, mod, rows)
        except Exception as e:  # noqa: BLE001 - recorded, then exit nonzero
            print(f"{mod}.ERROR,,{type(e).__name__}: {e}")
            write_bench_json(args.out_dir, mod, [],
                             error=f"{type(e).__name__}: {e}\n{traceback.format_exc()}")
            failed.append(mod)
    if failed:
        print(f"benchmark modules failed: {', '.join(failed)}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
