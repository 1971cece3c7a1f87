"""Readings that set the limits of ``correct``: the program's and the
control's, on several seeds, at a cell's own size, in one process.

    python3 bench/control.py --workload w1_compim.frames --seconds 5 \\
        --seeds 101,102,103

For every seed it makes one short run of the cell (``harness.run``: the
same bank, traffic, warm-up, window and check as a benchmark run) and
prints the program's ``mismatch`` and ``missing``.  Then it puts the
control in the program's place for the same checked sessions and the same
pushes and compares it the same way.  The control is the reference with
one guarantee of the configuration broken: every session is decided with
patient 0's item memory, threshold and class HVs instead of its own
patient's (the owner routing dropped).  The benchmark's own runs never run
the control.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from dataclasses import dataclass

import numpy as np

if __name__ == "__main__":
    _ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(_ROOT, ".jax_cache")

from bench import harness, reference  # noqa: E402
from bench import traffic as traffic_mod  # noqa: E402


@dataclass
class Decision:
    frame_index: int
    prediction: int
    scores: np.ndarray
    frame_hv: np.ndarray


def control_decisions(traffic, pushes: int, bank, thresholds, class_hvs,
                      geo: reference.Geometry) -> dict:
    """The control's decisions of the checked sessions over ``pushes``
    pushes: the reference with every session routed to patient 0."""
    owed = traffic.sent(pushes) // geo.window
    got = {}
    for i in traffic.checked:
        n = int(owed[i])
        frames = traffic.stream(i, pushes)[:n * geo.window].reshape(
            n, geo.window, geo.channels)
        hvs, scores, preds = reference.decisions(
            bank, thresholds, class_hvs, np.zeros((n,), np.int32), frames,
            geo)
        got[i] = [Decision(f, int(preds[f]), scores[f], hvs[f])
                  for f in range(n)]
    return got


def control_reading(args, seed: int, pushes: int, root: str) -> dict:
    """Mismatches of the control against the reference, for the traffic
    and bank a run of ``args.workload`` with ``seed`` makes."""
    loaded = harness.load_cell(root, args.workload)
    cfg, mix = loaded["config"], loaded["mix"]
    sessions = int(args.sessions or loaded["sizes"]["sessions"])
    geo = reference.Geometry.from_config(cfg)
    rng = np.random.default_rng(seed)
    bank, thresholds, class_hvs = harness.make_bank(cfg, geo, rng)
    traffic = traffic_mod.Traffic(
        mix, sessions=sessions, patients=cfg["patients"],
        channels=geo.channels, lbp_bits=geo.lbp_bits, rng=rng)
    got = control_decisions(traffic, pushes, bank, thresholds, class_hvs,
                            geo)
    return harness.check(traffic, got, pushes, bank, thresholds, class_hvs,
                         geo)


def main(argv=None, *, root: str = harness.ROOT, require_chip: bool = True
         ) -> list[dict]:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--sessions", type=int, default=0)
    args = ap.parse_args(argv)
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        row = {"workload": args.workload, "seed": seed}
        run_args = harness.parse([
            "--workload", args.workload, "--seed", str(seed),
            "--seconds", str(args.seconds), "--trace", "0",
            "--sessions", str(args.sessions)])
        line, notes = harness.run(run_args, root=root,
                                  t_start=time.perf_counter(),
                                  require_chip=require_chip)
        row["program"] = {k: c["value"] for k, c in line["checks"].items()}
        row["program_correct"] = line["correct"]
        row["program_checked"] = notes["checked_decisions"]
        pushes = notes["pushes_total"]
        row["pushes"] = pushes
        row["control"] = control_reading(args, seed, pushes, root)
        print(json.dumps(row), flush=True)
        rows.append(row)
    return rows


if __name__ == "__main__":
    try:
        main()
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        sys.exit(2)
