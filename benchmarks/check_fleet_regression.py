"""CI perf-regression gate for the fleet and cold-start benchmarks.

Compares the ``fleet.*.speedup`` rows of a freshly produced BENCH_fleet.json
against a committed reference and fails (exit 1) when any matching row's
fleet-vs-baseline speedup regressed by more than ``--tolerance`` (default
25%).  Speedups are RATIOS of two timings from the same process on the same
machine, so they transfer across runner hardware far better than absolute
times; the committed CI reference (benchmarks/BENCH_fleet_tiny.json) uses
the BENCH_TINY geometry so the gate stays stable on small shared runners.

Row families the REFERENCE does not know (new benchmarks land ahead of
their reference refresh) are reported as warnings and skipped — the gate
fails only on KNOWN rows that regressed, went missing, or stopped parsing.
The committed reference itself is held to strict parsing: it is a curated
artifact, and a malformed row there is a repo bug, not a perf signal.

With ``--coldstart-fresh``/``--coldstart-reference`` the same known-row
speedup machinery additionally gates BENCH_coldstart.json's
``coldstart.*.speedup`` ratio rows (warm-cache / serialized-executable vs
process-fresh trace+compile, see bench_coldstart.py), and the run's
``coldstart.bitexact`` and ``coldstart.fallback`` status rows must start
with ``ok`` — a fast cold start that changed decisions, or a stale
artifact that did not fall back to JIT, is a correctness bug, not a perf
win.

``--churn-fresh``/``--churn-reference`` do the same for the elastic-fleet
churn benchmark (bench_churn.py): the ``churn.*.speedup`` ratio rows
(fleet vs looped baseline under an identical Poisson churn trace, plus
the churn-vs-steady-state throughput retention) gate like any other
known-row family, and the ``churn.norecompile`` / ``churn.recovery``
status rows must start with ``ok`` — an admission path that recompiles,
or a restore+replay that changes decisions, defeats the elasticity
subsystem's whole contract.

``--channelfault-fresh``/``--channelfault-reference`` gate the electrode
fault benchmark (bench_channelfault.py): the ``channelfault.*.speedup``
rows are accuracy RETENTION ratios (quarantined fleet / clean fleet) —
another same-process ratio, so the committed tiny reference holds the
graceful-degradation floor — and the ``channelfault.maskparity`` /
``channelfault.gracefuldeg`` status rows must start with ``ok``: an
all-live mask that changes decisions (broken program identity), a masked
encode diverging from the reduced-channel oracle, or a sparse-variant
accuracy cliff at 1-2 failed channels all fail CI.

Usage::

    python -m benchmarks.check_fleet_regression FRESH.json REFERENCE.json \
        [--tolerance 0.25] \
        [--coldstart-fresh BENCH_coldstart.json \
         --coldstart-reference benchmarks/BENCH_coldstart_tiny.json] \
        [--churn-fresh BENCH_churn.json \
         --churn-reference benchmarks/BENCH_churn_tiny.json] \
        [--channelfault-fresh BENCH_channelfault.json \
         --channelfault-reference benchmarks/BENCH_channelfault_tiny.json]
"""

from __future__ import annotations

import argparse
import json
import re
import sys

_SPEEDUP = re.compile(r"^([0-9.]+)x ")

# rows whose derived string must start with "ok" for the gate to pass
COLDSTART_STATUS_ROWS = ("coldstart.bitexact", "coldstart.fallback")
CHURN_STATUS_ROWS = ("churn.norecompile", "churn.recovery")
CHANNELFAULT_STATUS_ROWS = ("channelfault.maskparity",
                            "channelfault.gracefuldeg")


def _load(path: str) -> dict:
    with open(path) as f:
        payload = json.load(f)
    if payload.get("status") != "ok":
        raise SystemExit(f"{path}: benchmark status is not ok: "
                         f"{payload.get('error')}")
    return payload


def speedups(path: str, *, prefix: str = "fleet.", strict: bool = True
             ) -> tuple[dict[str, float], dict[str, dict]]:
    """``<prefix>*.speedup`` rows -> ``({name: speedup}, {name: bad_row})``.

    ``strict`` (the committed reference) raises on an unparseable row;
    the fresh run parses leniently and returns bad rows separately —
    whether one fails the gate depends on whether the reference knows it.
    """
    payload = _load(path)
    out: dict[str, float] = {}
    bad: dict[str, dict] = {}
    for row in payload.get("rows", []):
        name = row.get("name", "")
        if not (name.startswith(prefix) and name.endswith(".speedup")):
            continue
        m = _SPEEDUP.match(row.get("derived", ""))
        if not m:
            if strict:
                raise SystemExit(f"{path}: unparseable speedup row {row!r}")
            bad[name] = row
            continue
        out[name] = float(m.group(1))
    return out, bad


def status_rows(path: str, names: tuple[str, ...]) -> dict[str, str]:
    """The derived strings of the named status rows (missing rows absent)."""
    payload = _load(path)
    want = set(names)
    return {row["name"]: row.get("derived", "")
            for row in payload.get("rows", []) if row.get("name") in want}


def gate_speedups(fresh_path: str, ref_path: str, *, prefix: str,
                  tolerance: float) -> list[str]:
    """Known-row speedup comparison; returns the failed row names."""
    fresh, fresh_bad = speedups(fresh_path, prefix=prefix, strict=False)
    ref, _ = speedups(ref_path, prefix=prefix)
    if not ref:
        print(f"{ref_path}: no {prefix}*.speedup rows — the committed "
              "reference is empty, the gate would pass vacuously",
              file=sys.stderr)
        return [f"{prefix}<empty reference>"]
    for name in sorted((set(fresh) | set(fresh_bad)) - set(ref)):
        print(f"warning: {name}: not in reference {ref_path}; "
              "skipping (refresh the committed reference to gate it)",
              file=sys.stderr)

    failed = []
    for name in sorted(ref):
        if name in fresh_bad:
            print(f"{name}: unparseable fresh row "
                  f"{fresh_bad[name]!r} -> FAILED")
            failed.append(name)
            continue
        if name not in fresh:
            print(f"{name}: in reference but missing from fresh run "
                  "-> FAILED")
            failed.append(name)
            continue
        floor = ref[name] * (1.0 - tolerance)
        status = "OK" if fresh[name] >= floor else "REGRESSED"
        print(f"{name}: fresh {fresh[name]:.2f}x vs reference "
              f"{ref[name]:.2f}x (floor {floor:.2f}x) -> {status}")
        if fresh[name] < floor:
            failed.append(name)
    return failed


def gate_status_rows(fresh_path: str,
                     names: tuple[str, ...]) -> list[str]:
    """The named status rows must exist and start with "ok"."""
    failed = []
    rows = status_rows(fresh_path, names)
    for name in names:
        derived = rows.get(name)
        if derived is None:
            print(f"{name}: missing from {fresh_path} -> FAILED")
            failed.append(name)
            continue
        ok = derived.startswith("ok")
        print(f"{name}: {derived} -> {'OK' if ok else 'FAILED'}")
        if not ok:
            failed.append(name)
    return failed


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("fresh", help="BENCH_fleet.json from this run")
    ap.add_argument("reference", help="committed reference BENCH_fleet.json")
    ap.add_argument("--tolerance", type=float, default=0.25,
                    help="allowed fractional regression (default 0.25)")
    ap.add_argument("--coldstart-fresh", default=None,
                    help="BENCH_coldstart.json from this run (enables the "
                         "cold-start ratio + correctness gate)")
    ap.add_argument("--coldstart-reference", default=None,
                    help="committed cold-start reference "
                         "(benchmarks/BENCH_coldstart_tiny.json)")
    ap.add_argument("--churn-fresh", default=None,
                    help="BENCH_churn.json from this run (enables the "
                         "elastic-fleet churn ratio + lifecycle gate)")
    ap.add_argument("--churn-reference", default=None,
                    help="committed churn reference "
                         "(benchmarks/BENCH_churn_tiny.json)")
    ap.add_argument("--channelfault-fresh", default=None,
                    help="BENCH_channelfault.json from this run (enables "
                         "the electrode-fault retention + parity gate)")
    ap.add_argument("--channelfault-reference", default=None,
                    help="committed channel-fault reference "
                         "(benchmarks/BENCH_channelfault_tiny.json)")
    args = ap.parse_args(argv)
    if (args.coldstart_fresh is None) != (args.coldstart_reference is None):
        ap.error("--coldstart-fresh and --coldstart-reference go together")
    if (args.churn_fresh is None) != (args.churn_reference is None):
        ap.error("--churn-fresh and --churn-reference go together")
    if (args.channelfault_fresh is None) != \
            (args.channelfault_reference is None):
        ap.error("--channelfault-fresh and --channelfault-reference "
                 "go together")

    failed = gate_speedups(args.fresh, args.reference,
                           prefix="fleet.", tolerance=args.tolerance)

    if args.coldstart_fresh:
        failed += gate_speedups(args.coldstart_fresh,
                                args.coldstart_reference,
                                prefix="coldstart.",
                                tolerance=args.tolerance)
        failed += gate_status_rows(args.coldstart_fresh,
                                   COLDSTART_STATUS_ROWS)

    if args.churn_fresh:
        failed += gate_speedups(args.churn_fresh, args.churn_reference,
                                prefix="churn.", tolerance=args.tolerance)
        failed += gate_status_rows(args.churn_fresh, CHURN_STATUS_ROWS)

    if args.channelfault_fresh:
        failed += gate_speedups(args.channelfault_fresh,
                                args.channelfault_reference,
                                prefix="channelfault.",
                                tolerance=args.tolerance)
        failed += gate_status_rows(args.channelfault_fresh,
                                   CHANNELFAULT_STATUS_ROWS)

    if failed:
        print(f"fleet perf gate failed: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
