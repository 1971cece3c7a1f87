"""Where a push's time goes, read from the program's own spans: one traced
run of a cell, by the harness, and what its trace holds of the program.

    python3 bench/decompose.py --workload w1_compim.frames --seed 7 \\
        --seconds 30 [--untraced] [--keep-trace w1.xplane.pb]

The run is ``harness.run`` with ``--trace 1``, so its result line (the
cell's per-layer metrics and breakdown) is computed as the benchmark
computes it.  Besides, this reads the ``fleet.*`` spans of the same trace
(``bench/fleet_spans.py``) and prints, as one JSON line:

- ``line``: the harness's result line;
- ``program_metrics``: the metrics read from the program's spans, each by
  its reader ``bench/metrics/<name>.py`` (``PROGRAM_METRICS``);
- ``clock``: the clock check (smallest slacks, any offset applied).  On a
  violation beyond 1 ms (kept as ``clock_unshifted``: the offending push
  and its size) the device planes are shifted by the offset that the
  host's launch events give (``fleet_spans.offset``) and checked again.
  Where no offset is found, or the shifted check fails too, the run stops
  there: ``clock_error`` says why, and the command exits 1 after printing
  what it has;
- ``idle_gaps_program``: the longest device idle gaps, each named by the
  innermost ``bench.*`` or ``fleet.*`` span covering most of it;
- ``decomposition``: each push's owed interval as a chain of named parts;
- ``traced_critical_ms``: the traced run's mean due -> collected time;
- ``counters``: the window's deltas of ``StreamingFleet.counters``;
- with ``--untraced``, ``untraced``: the result line of a ``--trace 0`` run
  at the same seed made first in the same process, to set the cost of
  tracing against.

``--keep-trace`` copies the raw ``.xplane.pb`` before the harness deletes
it.  The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time
from unittest import mock

if __name__ == "__main__":
    _ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(_ROOT, ".jax_cache")

from bench import fleet_spans, harness, trace  # noqa: E402

PROGRAM_METRICS = ("stage_ms.live", "h2d_ms.live", "dispatch_ms.live",
                   "launch_wait_ms.live", "kernel_device_ms.live",
                   "d2h_ms.live", "decode_ms.live")


def traced(args, *, root: str = harness.ROOT, require_chip: bool = True,
           keep_trace: str | None = None) -> dict:
    """One traced run of ``args.workload`` and what its trace holds of the
    program (the JSON of this module's doc)."""
    taps: dict = {}

    def patch(fleet):           # the harness's hook, after the warm-up
        taps["fleet"] = fleet
        taps["counters"] = fleet.counters

    reduce_bench = trace.reduce

    def reduce(data):           # the same trace, read for the program too
        taps["program"] = fleet_spans.reduce(data)
        if keep_trace:
            shutil.copy(harness._xplane(os.path.join(root, harness.TRACE_DIR)),
                        keep_trace)
        return reduce_bench(data)

    read_metric = harness.read_metric

    def read(root_, metric, run):   # the harness's Run, as its readers see it
        taps["run"] = run
        return read_metric(root_, metric, run)

    traced_args = argparse.Namespace(**{**vars(args), "trace": 1})
    with mock.patch.object(trace, "reduce", reduce), \
            mock.patch.object(harness, "read_metric", read):
        line, notes = harness.run(traced_args, root=root,
                                  t_start=time.perf_counter(),
                                  require_chip=require_chip, patch=patch)
    run, prog = taps["run"], taps["program"]
    run.program = prog
    after = taps.pop("fleet").counters
    out = {"line": line, "notes": notes,
           "counters": {k: after[k] - v for k, v in taps["counters"].items()},
           "traced_critical_ms": sum(p.collected - p.due for p in run.pushes)
           / len(run.pushes) * 1e3}
    if any(run.trace.devices[d].modules for d in run.devices):
        try:
            _device_part(run, prog, out)
        except fleet_spans.ClockError as e:
            out["clock_error"] = str(e)
            return out
    out["program_metrics"] = {}
    for name in PROGRAM_METRICS:
        v = read_metric(root, {"name": name}, run)
        if v is None and require_chip:
            raise RuntimeError(f"{name}: the trace holds nothing to read")
        out["program_metrics"][name] = v
    return out


def _device_part(run, prog: fleet_spans.Program, out: dict) -> None:
    try:
        out["clock"] = fleet_spans.clock(run)
    except fleet_spans.ClockError as e:
        # the device planes are off the host's clock: shift them by the
        # offset the launch events give, and check again
        found = fleet_spans.offset(prog)
        if found is None:
            raise
        out["clock_unshifted"] = str(e)
        prog.offset_ns = found
        out["clock"] = fleet_spans.clock(run)
    out["idle_gaps_program"] = fleet_spans.idle_gaps(run)
    out["decomposition"] = fleet_spans.decompose(run)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--untraced", action="store_true",
                    help="first a --trace 0 run at the same seed")
    ap.add_argument("--keep-trace", default=None,
                    help="copy the raw .xplane.pb here")
    args = ap.parse_args(argv)
    run_args = harness.parse([
        "--workload", args.workload, "--seed", str(args.seed),
        "--seconds", str(args.seconds), "--trace", "0"])
    untraced = None
    if args.untraced:
        untraced, _ = harness.run(run_args, t_start=time.perf_counter())
    out = traced(run_args, keep_trace=args.keep_trace)
    if untraced is not None:
        out["untraced"] = untraced
    print(json.dumps(out), flush=True)
    if "clock_error" in out:
        print(f"bench: clock check: {out['clock_error']}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        sys.exit(2)
