"""One run of one benchmark cell on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The run finds everything by name: the cell in ``BENCHMARK.json``, its
configuration file, ``bench/traffic/<mix>.json``, ``bench/cells/<cell>.json``
(sessions) and one reader per metric, ``bench/metrics/<metric>.py``.  It
refuses to run without as many TPU chips as the cell asks for, keeps JAX's
compile cache in ``<checkout>/.jax_cache``, makes the bank and the traffic
from ``--seed``, warms the cell's one chunk bucket and pushes the warm-up
traffic (all of that is set-up), then drives ``push_codes_raw`` +
``collect_decisions`` for ``--seconds`` on the mix's schedule.  After the
window it reads peak device memory, frees the fleet and compares every
decision of the checked sessions with the plain reference (``reference``).

Standard error ends with the compared numbers and their limits; the last
line of standard output is the result: ``correct``, ``attempted``,
``failed``, ``metrics`` (end-to-end with ``--trace 0``, per-layer with
``--trace 1``), ``device``, with ``--trace 1`` a ``breakdown``, and last
``checks``.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import importlib.util
import json
import os
import shutil
import sys
import time
from dataclasses import dataclass

import numpy as np

from bench import reference, trace
from bench import traffic as traffic_mod
from bench import workbytes

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE_DIR = os.path.join("bench", ".trace")

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class NoChip(Exception):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling, and the number of
    XLA compiles, from its own monitoring events (a hit in the persistent
    cache is no compile)."""

    def __init__(self):
        import jax

        self.secs = 0.0
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_):
        if event in _COMPILE_EVENTS:
            self.secs += secs
            self.compiles += event == _COMPILE_EVENTS[-1]


@dataclass
class Push:
    """One timed push, in ``time.perf_counter`` seconds."""
    due: float          # when it was due
    start: float        # push_codes_raw called
    pushed: float       # push_codes_raw returned
    ready: float | None  # rounds ready on the device (traced run only)
    collected: float    # collect_decisions returned
    decisions: int      # decisions this push delivered
    cycles: int         # cycles each session sent


@dataclass
class Run:
    """What the metric readers read."""
    cell: dict
    config: dict
    mix: dict
    sessions: int
    setup_s: float
    pushes: list
    devices: list            # trace plane names of the devices used
    peaks: dict
    trace: trace.Reduced | None = None
    window_ns: tuple | None = None
    step_modules: tuple = ()
    ns_offset: int = 0

    def to_ns(self, t: float) -> int:
        """A ``perf_counter`` time on the trace's clock."""
        return int(t * 1e9) + self.ns_offset


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_cell(root: str, name: str) -> dict:
    """The cell ``name`` with everything it needs, found by name."""
    spec = load_json(os.path.join(root, "BENCHMARK.json"))
    cell = next((w for w in spec["workloads"] if w["name"] == name), None)
    if cell is None:
        raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json")
    conf = next(c for c in spec["configs"] if c["name"] == cell["config"])
    return {
        "spec": spec, "cell": cell,
        "config": load_json(os.path.join(root, conf["file"])),
        "mix": load_json(os.path.join(root, "bench", "traffic",
                                      cell["traffic"] + ".json")),
        "sizes": load_json(os.path.join(root, "bench", "cells",
                                        name + ".json")),
    }


def metrics_for(spec: dict, cell: str, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics this cell reports.  A
    per-layer metric without ``workloads`` is reported by every cell that
    reports the end-to-end metric it moves."""
    if kind == "per_layer":
        e2e = {m["name"] for m in metrics_for(spec, cell, "end_to_end")}
        return [m for m in spec[kind]
                if (cell in m["workloads"] if "workloads" in m
                    else m["moves"] in e2e)]
    return [m for m in spec[kind] if cell in m.get("workloads", [cell])]


def read_metric(root: str, metric: dict, run: Run):
    path = os.path.join(root, "bench", "metrics", metric["name"] + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric["name"].replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


def chips(n: int, require_chip: bool) -> list:
    import jax

    devs = jax.devices()
    if require_chip and devs[0].platform != "tpu":
        raise NoChip(f"JAX finds no TPU (devices: {devs})")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips, JAX finds {len(devs)}")
    return devs[:n]


def make_bank(cfg: dict, geo: reference.Geometry, rng: np.random.Generator):
    """Item memory, thresholds and class HVs of every patient, from the
    seed (``reference``)."""
    import jax

    patients = cfg["patients"]
    bank = reference.make_bank(
        jax.random.PRNGKey(int(rng.integers(0, 2**31))), geo, patients)
    codes, labels = traffic_mod.training_frames(
        rng, patients=patients, channels=geo.channels,
        lbp_bits=geo.lbp_bits, window=geo.window, record=cfg["train"])
    targets = [cfg["density_targets"][p % len(cfg["density_targets"])]
               for p in range(patients)] if geo.sparse else []
    thresholds, class_hvs = reference.train(bank, codes, labels, targets, geo)
    return bank, thresholds, class_hvs


def program_bank(cfg: dict, bank: dict, thresholds, class_hvs) -> dict:
    """The program's patient -> pipeline bank, built from the seed's arrays
    (nothing is trained by the program)."""
    import jax.numpy as jnp

    from repro.core.im import DenseIMParams, IMParams
    from repro.core.pipeline import HDCConfig, HDCPipeline

    out = {}
    for p in range(cfg["patients"]):
        hcfg = HDCConfig(
            dim=cfg["dim"], segments=cfg["segments"],
            channels=cfg["channels"], lbp_bits=cfg["lbp_bits"],
            window=cfg["window"], variant=cfg["variant"],
            backend=cfg["backend"], spatial_thinning=cfg["spatial_thinning"],
            temporal_threshold=int(thresholds[p]),
            n_classes=cfg["n_classes"], class_density=cfg["class_density"])
        if cfg["variant"] == "dense":
            params = DenseIMParams(item_packed=bank["item"][p],
                                   elec_packed=bank["elec"][p],
                                   dim=cfg["dim"])
        else:
            params = IMParams(item_pos=bank["item"][p],
                              elec_pos=bank["elec"][p], dim=cfg["dim"],
                              segments=cfg["segments"])
        out[p] = HDCPipeline(params=params, cfg=hcfg,
                             class_hvs=jnp.asarray(class_hvs[p]))
    return out


def step_module_names(fleet, bucket: int) -> tuple:
    """The XLA module names of the fleet's step programs at ``bucket``."""
    names = set()
    for e in fleet.aot_entries(buckets=[bucket]):
        if ".step." in e.name:
            text = e.fn.lower(*e.args).as_text()
            names.add(text.split("module @", 1)[1].split(" ", 1)[0])
    return tuple(sorted(names))


def check(traffic, got: dict, pushes: int, bank, thresholds, class_hvs,
          geo: reference.Geometry) -> dict:
    """Every decision of the checked sessions against the reference.

    ``got[i]`` is session i's FrameDecision list over all pushes.  Returns
    the checked count, decisions that differ from the reference (or that
    the reference does not owe), and decisions owed that never came.
    """
    owed = traffic.sent(pushes) // geo.window
    frames, owner = [], []
    for i in traffic.checked:
        n = int(owed[i])
        frames.append(traffic.stream(i, pushes)[:n * geo.window]
                      .reshape(n, geo.window, geo.channels))
        owner.append(np.full((n,), traffic.owner[i], np.int32))
    hvs, scores, preds = reference.decisions(
        bank, thresholds, class_hvs, np.concatenate(owner),
        np.concatenate(frames), geo)
    mismatch = missing = checked = 0
    k = 0
    for i in traffic.checked:
        n = int(owed[i])
        dec = got[i]
        for f in range(n):
            if f >= len(dec):
                missing += 1
                continue
            d = dec[f]
            checked += 1
            mismatch += not (d.frame_index == f
                             and d.prediction == preds[k + f]
                             and np.array_equal(d.scores, scores[k + f])
                             and np.array_equal(d.frame_hv, hvs[k + f]))
        mismatch += max(len(dec) - n, 0)
        k += n
    return {"checked": checked, "mismatch": mismatch, "missing": missing}


def span(name: str, on: bool):
    import jax

    return jax.profiler.TraceAnnotation(name) if on else contextlib.nullcontext()


def window(fleet, traffic, mix: dict, seconds: float, tracing: bool,
           got: dict) -> list[Push]:
    """Drive the fleet for ``seconds`` on the mix's open-loop schedule: push
    j is due at ``j * tick_s`` from the window's start, however late the
    fleet runs."""
    import jax

    tick = float(mix["tick_s"])
    pushes: list[Push] = []
    j = traffic.warm_pushes
    t0 = time.perf_counter()
    t_end = t0 + seconds
    with span("bench.window", tracing):
        while True:
            now = time.perf_counter()
            due = t0 + len(pushes) * tick
            if due >= t_end:
                break
            if now < due:
                with span("bench.wait_tick", tracing):
                    time.sleep(due - now)
            start = time.perf_counter()
            with span("bench.ingest", tracing):
                rounds = fleet.push_codes_raw(traffic.batch(j),
                                              traffic.lengths(j))
            pushed = time.perf_counter()
            ready = None
            if tracing:
                with span("bench.device_wait", tracing):
                    jax.block_until_ready([r.tiles for r in rounds])
                ready = time.perf_counter()
            with span("bench.collect", tracing):
                out = fleet.collect_decisions(rounds)
            collected = time.perf_counter()
            pushes.append(Push(due, start, pushed,
                               ready, collected, sum(map(len, out)),
                               traffic.cycles))
            for i in traffic.checked:
                got[i].extend(out[i])
            j += 1
    return pushes


def run(args, *, root: str = ROOT, t_start: float, require_chip: bool = True,
        patch=None) -> tuple[dict, dict]:
    """One run; returns the result line and notes on how it went.
    ``patch(fleet)``, for tests, may break the fleet's timed path before
    the window."""
    loaded = load_cell(root, args.workload)
    spec, cell, cfg, mix = (loaded[k] for k in ("spec", "cell", "config",
                                                "mix"))
    sessions = int(args.sessions or loaded["sizes"]["sessions"])
    devs = chips(int(cell["chips"]), require_chip)

    import jax

    from repro.runtime.aot import setup_compilation_cache
    from repro.serve.fleet import StreamingFleet

    setup_compilation_cache()
    clock = CompileClock()
    peaks = (workbytes.peaks(devs[0].device_kind) if require_chip else
             {"hbm_bytes_per_s": 1.0})
    geo = reference.Geometry.from_config(cfg)
    rng = np.random.default_rng(args.seed)
    bank, thresholds, class_hvs = make_bank(cfg, geo, rng)
    traffic = traffic_mod.Traffic(
        mix, sessions=sessions, patients=cfg["patients"],
        channels=geo.channels, lbp_bits=geo.lbp_bits, rng=rng)
    owners = traffic.owner.tolist()
    fleet = StreamingFleet(program_bank(cfg, bank, thresholds, class_hvs),
                           owners, backend=cfg["backend"])
    bucket = int(mix["bucket"])
    fleet.warmup(buckets=[bucket])
    got = {i: [] for i in traffic.checked}
    for j in range(traffic.warm_pushes):
        out = fleet.collect_decisions(
            fleet.push_codes_raw(traffic.batch(j), traffic.lengths(j)))
        for i in traffic.checked:
            got[i].extend(out[i])
    compile_s, compiles, n_exec = clock.secs, clock.compiles, \
        fleet.compile_count
    if patch is not None:
        patch(fleet)
    tracing = bool(args.trace)
    trace_dir = os.path.join(root, TRACE_DIR)
    if tracing:
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # spans only: no per-call tracing
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    setup_s = time.perf_counter() - t_start
    pushes = window(fleet, traffic, mix, args.seconds, tracing, got)
    if tracing:
        jax.profiler.stop_trace()
    window_compiles = clock.compiles - compiles
    window_execs = fleet.compile_count - n_exec
    n_tiles = fleet.n_tiles
    local = jax.local_devices()  # the fleet puts tile k on local[k % n]
    used = local[:min(n_tiles, len(local))]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in used)
    step_modules = step_module_names(fleet, bucket) if tracing else ()
    n_pushes = traffic.warm_pushes + len(pushes)
    del fleet
    gc.collect()

    owed = traffic.sent(n_pushes) // geo.window \
        - traffic.sent(traffic.warm_pushes) // geo.window
    attempted = int(owed.sum())
    delivered = sum(p.decisions for p in pushes)
    result = check(traffic, got, n_pushes, bank, thresholds, class_hvs, geo)
    checks = {"mismatch": {"value": result["mismatch"], "limit": 0},
              "missing": {"value": result["missing"], "limit": 0}}
    correct = result["checked"] > 0 and all(
        c["value"] <= c["limit"] for c in checks.values())

    r = Run(cell=cell, config=cfg, mix=mix, sessions=sessions,
            setup_s=setup_s, pushes=pushes,
            devices=[f"{trace.DEVICE_PREFIX}{d.id}" for d in used],
            peaks=peaks)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(used), "memory_peak_bytes": int(peak)}
    breakdown = None
    notes_trace = {}
    if tracing:
        r.trace = trace.reduce(jax.profiler.ProfileData.from_file(
            _xplane(trace_dir)))
        if require_chip:
            trace.require(r.trace, r.devices, step_modules)
        for name in r.devices:  # a CPU rehearsal has no device plane
            r.trace.devices.setdefault(name, trace.Device())
        notes_trace = trace.summary(r.trace, r.devices)
        r.window_ns = r.trace.span(trace.WINDOW)
        r.ns_offset = r.window_ns[0] - int(pushes[0].due * 1e9) if pushes \
            else 0
        r.step_modules = step_modules
        lo, hi = r.window_ns
        device["busy_s"] = sum(
            trace.covered(trace.busy(r.trace.devices[d]), lo, hi)
            for d in r.devices) / len(r.devices) / 1e9
        device["window_s"] = (hi - lo) / 1e9
        breakdown = trace.breakdown(
            trace.Reduced({d: r.trace.devices[d] for d in r.devices},
                          r.trace.spans), lo, hi)
        shutil.rmtree(trace_dir, ignore_errors=True)
    kind = "per_layer" if tracing else "end_to_end"
    metrics = {}
    for m in metrics_for(spec, cell["name"], kind):
        v = read_metric(root, m, r)
        if v is None and not require_chip:
            continue  # a CPU rehearsal has no device numbers
        if v is None:
            raise RuntimeError(f"metric {m['name']} is declared for "
                               f"{cell['name']} but found nothing to read")
        metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    lat = [p.start - p.due for p in pushes]
    notes = {
        "sessions": sessions, "tiles": n_tiles,
        "pushes": len(pushes), "pushes_total": n_pushes,
        "delivered": delivered,
        "checked_sessions": len(traffic.checked),
        "checked_decisions": result["checked"],
        "setup_compile_s": compile_s, "setup_xla_compiles": compiles,
        "window_xla_compiles": window_compiles,
        "window_new_executables": window_execs,
        "generator_lag_ms_mean": float(np.mean(lat) * 1e3) if lat else 0.0,
        "generator_lag_ms_max": float(np.max(lat) * 1e3) if lat else 0.0,
        "generator_lag_ms_first_third": _mean_ms(lat[:len(lat) // 3]),
        "generator_lag_ms_last_third": _mean_ms(lat[-(len(lat) // 3):]),
        "memory_peak_bytes": int(peak),
        "step_modules": list(step_modules),
        **notes_trace,
    }
    for k, v in notes.items():
        print(f"bench: {k} = {v}", file=sys.stderr)
    for k, c in checks.items():
        print(f"bench: check {k} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    line = {"correct": bool(correct), "attempted": attempted,
            "failed": max(attempted - delivered, 0), "metrics": metrics,
            "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = checks
    return line, notes


def _mean_ms(xs) -> float:
    return float(np.mean(xs) * 1e3) if len(xs) else 0.0


def _xplane(trace_dir: str) -> str:
    for dirpath, _, files in os.walk(trace_dir):
        for f in files:
            if f.endswith(".xplane.pb"):
                return os.path.join(dirpath, f)
    raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")


def parse(argv) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sessions", type=int, default=0,
                    help="override the cell's session count (knee sweeps)")
    return ap.parse_args(argv)


def main(argv, *, t_start: float) -> int:
    args = parse(argv)
    try:
        line, _ = run(args, t_start=t_start)
    except NoChip as e:
        print(f"bench: {e}; this benchmark runs on the chip only",
              file=sys.stderr)
        return 2
    print(json.dumps(line), flush=True)
    return 0
