#!/usr/bin/env python3
"""Smoke run of the streaming seizure-detection fleet on a TPU.

    python chip_smoke.py [--seed N]            # one chip: every phase below
    python chip_smoke.py --four-chips [--seed N]   # four chips: sharded paths

Drives the served path through the library's own entry points at the
paper's full geometry — 64 electrodes, 6-bit LBP codes, D=1024, 256-cycle
frames, each patient's temporal threshold calibrated — with 1,024 sessions
over a bank of 16 patients trained from ``--seed`` (``launch.serve.
train_bank``, as ``repro.launch.serve --hdc-fleet`` builds it):

  fleet     per variant x backend: 8 window-length rounds, then 2 ragged
            rounds of lengths in [0, 300] (several chunk buckets run);
            pallas decisions bit-exact with jnp for every session, both
            bit-exact with SeizureSession over the jnp pipeline for 8
            sampled sessions, and the pallas step holds a Mosaic kernel
  mask      sparse_compim with channel masking, 2 channels quarantined in
            a quarter of the sessions: pallas bit-exact with jnp
  pipeline  per variant, HDCPipeline(backend="pallas").infer bit-exact
            with backend="jnp"
  aot       save_aot into artifacts/, warm a fresh fleet from it: every
            step executable loaded, none compiled, decisions bit-exact

``--four-chips`` runs only the multi-chip path and what it is compared
with: the same 1,024 streams (sparse_compim, jnp) through a one-device
fleet, a fleet sharded over a 4-device data mesh, and a fleet of four
256-session tiles placed round-robin on the four chips; all three must
agree bit-exactly.

Each phase prints one JSON line: device, derived tile, compile seconds
(trace + lower + XLA compile, from JAX's monitoring events) and executable
count, decisions emitted, verdicts, peak device memory, and the wall time
of a steady round — a smoke timing, not a benchmark.  Any mismatch or
error exits non-zero, and so does a host where JAX finds no TPU.  On
success the last line is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time
import warnings

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
SESSIONS = 1024
PATIENTS = 16
STEADY_ROUNDS = 8
RAGGED_ROUNDS = 2
RAGGED_MAX = 300
SAMPLED = 8
QUARANTINED = 2
VARIANTS = ("sparse_compim", "dense", "sparse_naive")
GEOMETRY: dict = {}  # HDCConfig defaults: the paper's geometry

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class SmokeFailure(Exception):
    """A phase produced a wrong or missing result."""


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling, from its own
    monitoring events (a persistent-cache hit skips the compile)."""

    def __init__(self):
        import jax

        self.secs = 0.0
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_):
        if event in _COMPILE_EVENTS:
            self.secs += secs
            self.compiles += event == _COMPILE_EVENTS[-1]

    def mark(self) -> tuple[float, int]:
        return self.secs, self.compiles

    def since(self, mark: tuple[float, int]) -> tuple[float, int]:
        return self.secs - mark[0], self.compiles - mark[1]


def require_tpu(count: int):
    """The device list, or exit: this script never runs on a CPU."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (devices: {devs}); "
              "this script runs on the chip only", file=sys.stderr)
        raise SystemExit(2)
    if len(devs) < count:
        print(f"chip_smoke: needs {count} TPU devices, JAX sees {len(devs)}",
              file=sys.stderr)
        raise SystemExit(2)
    return devs


def check(ok: bool, what: str) -> bool:
    if not ok:
        raise SmokeFailure(what)
    return True


def same_decisions(a, b) -> bool:
    """Two FrameDecision lists agree bit-exactly, as tests/test_fleet.py
    compares them: frame index, prediction, scores and frame HV."""
    return len(a) == len(b) and all(
        x.frame_index == y.frame_index and x.prediction == y.prediction
        and np.array_equal(x.scores, y.scores)
        and np.array_equal(x.frame_hv, y.frame_hv)
        for x, y in zip(a, b))


def all_same(a, b) -> bool:
    return len(a) == len(b) and all(same_decisions(x, y) for x, y in zip(a, b))


def make_traffic(cfg, rng) -> list:
    """8 window-length rounds as pre-stacked (S, window, C) batches, then 2
    ragged rounds as per-session chunk lists of lengths in [0, 300]."""
    rounds = [("steady", rng.integers(0, cfg.codes,
                                      (SESSIONS, cfg.window, cfg.channels),
                                      np.uint8))
              for _ in range(STEADY_ROUNDS)]
    for _ in range(RAGGED_ROUNDS):
        lens = rng.integers(0, RAGGED_MAX + 1, SESSIONS)
        rounds.append(("ragged", [
            rng.integers(0, cfg.codes, (int(t), cfg.channels), np.uint8)
            for t in lens]))
    return rounds


def stream(fleet, traffic) -> dict:
    """Push every round through ``fleet``; per-session decisions, the
    devices that held each tile's outputs, and steady-round wall times."""
    decisions = [[] for _ in range(fleet.n_sessions)]
    tile_devices = None
    steady_s = []
    for i, (kind, data) in enumerate(traffic):
        t0 = time.perf_counter()
        rounds = (fleet.push_codes_raw(data) if kind == "steady"
                  else fleet.push_raw(data))
        out = fleet.collect_decisions(rounds)
        dt = time.perf_counter() - t0
        if kind == "steady" and i > 0:  # round 0 compiles
            steady_s.append(dt)
        if tile_devices is None and rounds:
            tile_devices = [sorted(d.id for d in fo.frames.devices())
                            for fo in rounds[0].tiles]
        for d, o in zip(decisions, out):
            d.extend(o)
    return {"decisions": decisions, "tile_devices": tile_devices,
            "steady_s": steady_s}


def reference(bank, owners, traffic, sampled) -> dict:
    """SeizureSession over the jnp pipeline for the sampled sessions."""
    from repro.serve.engine import SeizureSession

    out = {}
    for i in sampled:
        sess = SeizureSession(bank[owners[i]])
        out[i] = [d for _, data in traffic for d in sess.push(data[i])]
    return out


def peak_bytes(dev) -> int | None:
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def report(phase: str, devs, **fields) -> None:
    import jax

    line = {"phase": phase, "jax": jax.__version__,
            "device_kind": devs[0].device_kind, "device_count": len(devs)}
    line.update(fields)
    print(json.dumps(line), flush=True)


def timing(steady_s: list) -> dict:
    return {"smoke_steady_round_s": {
        "note": "smoke timing, not a benchmark",
        "median": float(np.median(steady_s)) if steady_s else None,
        "min": float(np.min(steady_s)) if steady_s else None}}


def run_fleet(bank, owners, traffic, clock, mask=None, **kw) -> tuple:
    """Build a fleet (with ``mask`` = (rows, sessions) quarantined through
    channel masking), stream ``traffic`` through it, count compiles."""
    from repro.serve.fleet import StreamingFleet

    mark = clock.mark()
    fleet = StreamingFleet(bank, owners, channel_masking=mask is not None,
                           **kw)
    if mask is not None:
        fleet.set_channel_mask(mask[0], sessions=mask[1])
    res = stream(fleet, traffic)
    secs, n = clock.since(mark)
    return fleet, res, {"n_tiles": fleet.n_tiles, "compile_s": secs,
                        "xla_compiles": n,
                        "compile_count": fleet.compile_count,
                        "decisions": sum(map(len, res["decisions"]))}


def one_chip(seed: int, devs: list) -> None:
    import jax

    from repro.core.pipeline import HDCConfig
    from repro.kernels.common import use_interpret
    from repro.launch.serve import train_bank
    from repro.runtime import aot as aot_mod
    from repro.serve.fleet import StreamingFleet, derive_tile

    check(not use_interpret(), "Pallas kernels would run in interpret mode")
    clock = CompileClock()
    rng = np.random.default_rng(seed)
    owners = [f"patient{i % PATIENTS}" for i in range(SESSIONS)]
    sampled = sorted(rng.choice(SESSIONS, SAMPLED, replace=False).tolist())
    banks, kept = {}, {}
    for variant in VARIANTS:
        cfg = HDCConfig(variant=variant, **GEOMETRY)
        banks[variant] = bank = train_bank(cfg, PATIENTS, rng)
        traffic = make_traffic(cfg, rng)
        ref = reference(bank, owners, traffic, sampled)
        results = {}
        for backend in ("jnp", "pallas"):
            fleet, res, stats = run_fleet(bank, owners, traffic, clock,
                                          backend=backend)
            results[backend] = res
            fields = {
                "variant": variant, "backend": backend,
                "tile": derive_tile(cfg), "sessions": SESSIONS,
                "patients": PATIENTS, **stats,
                "bitexact_vs_session": check(all(
                    same_decisions(res["decisions"][i], ref[i])
                    for i in sampled), f"{variant}/{backend} != session"),
            }
            if backend == "pallas":
                fields["bitexact_vs_jnp"] = check(
                    all_same(res["decisions"], results["jnp"]["decisions"]),
                    f"{variant}: pallas != jnp")
                step = next(e for e in fleet.aot_entries()
                            if ".step." in e.name)
                fields["tpu_custom_call"] = check(
                    "tpu_custom_call" in step.fn.lower(*step.args)
                    .compile().as_text(),
                    f"{variant}: pallas step holds no Mosaic kernel")
            check(stats["decisions"] > 0, f"{variant}/{backend}: no decisions")
            report("fleet", devs, **fields, **timing(res["steady_s"]),
                   peak_bytes_in_use=peak_bytes(devs[0]))
            del fleet
        kept[variant] = (traffic, results["pallas"]["decisions"])

    # -- mask: quarantine 2 channels in a quarter of the sessions ----------
    cfg = HDCConfig(variant="sparse_compim", **GEOMETRY)
    traffic = kept["sparse_compim"][0]
    quarter = rng.choice(SESSIONS, SESSIONS // 4, replace=False)
    mask = np.ones((quarter.size, cfg.channels), np.uint8)
    for row in mask:
        row[rng.choice(cfg.channels, QUARANTINED, replace=False)] = 0
    masked = {}
    for backend in ("jnp", "pallas"):
        fleet, res, stats = run_fleet(banks["sparse_compim"], owners, traffic,
                                      clock, mask=(mask, quarter.tolist()),
                                      backend=backend)
        masked[backend] = res["decisions"]
        fields = {"variant": "sparse_compim", "backend": backend,
                  "tile": derive_tile(cfg), "quarantined_sessions":
                  int(quarter.size), "channels_quarantined": QUARANTINED,
                  **stats}
        if backend == "pallas":
            fields["bitexact_vs_jnp"] = check(
                all_same(masked["pallas"], masked["jnp"]),
                "masked: pallas != jnp")
        report("mask", devs, **fields, **timing(res["steady_s"]),
               peak_bytes_in_use=peak_bytes(devs[0]))
        del fleet

    # -- pipeline: HDCPipeline pallas vs jnp at paper geometry -------------
    for variant in VARIANTS:
        pipe = banks[variant]["patient0"]
        cfg = pipe.cfg
        codes = rng.integers(0, cfg.codes, (2, 8 * cfg.window, cfg.channels),
                             np.uint8)
        mark = clock.mark()
        s_j, p_j = pipe.infer(codes)
        t0 = time.perf_counter()
        s_p, p_p = pipe.with_backend("pallas").infer(codes)
        jax.block_until_ready((s_p, p_p))
        secs, n = clock.since(mark)
        report("pipeline", devs, variant=variant, tile=derive_tile(cfg),
               compile_s=secs, xla_compiles=n,
               frames=int(np.asarray(p_p).size),
               bitexact_vs_jnp=check(
                   np.array_equal(np.asarray(s_j), np.asarray(s_p))
                   and np.array_equal(np.asarray(p_j), np.asarray(p_p)),
                   f"pipeline {variant}: pallas != jnp"),
               smoke_pallas_infer_s={
                   "note": "smoke timing (includes compile), not a benchmark",
                   "value": time.perf_counter() - t0},
               peak_bytes_in_use=peak_bytes(devs[0]))

    # -- aot: save the pallas fleet's executables, warm a fresh fleet ------
    cfg = HDCConfig(variant="sparse_compim", **GEOMETRY)
    traffic, want = kept["sparse_compim"]
    art_dir = os.path.join(ROOT, "artifacts", "chip_smoke_aot")
    shutil.rmtree(art_dir, ignore_errors=True)
    bank = banks["sparse_compim"]
    mark = clock.mark()
    manifest = StreamingFleet(bank, owners, backend="pallas").save_aot(art_dir)
    save_s, _ = clock.since(mark)
    n_steps = sum(".step." in e["name"] for e in manifest["entries"])
    fleet = StreamingFleet(bank, owners, backend="pallas")
    mark = clock.mark()
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # a failed load is an error here
        art = aot_mod.load_artifact(art_dir)
        check(art is not None, "AOT artifact did not load")
        stats = fleet.warmup(aot=art)
    warm_s, warm_n = clock.since(mark)
    check(stats["loaded"] == n_steps and stats["compiled"] == 0,
          f"AOT warmup {stats}, expected {n_steps} loaded, 0 compiled")
    mark = clock.mark()
    res = stream(fleet, traffic)
    _, stream_n = clock.since(mark)
    report("aot", devs, variant="sparse_compim", backend="pallas",
           tile=derive_tile(cfg), artifact=os.path.relpath(art_dir, ROOT),
           entries=len(manifest["entries"]), step_entries=n_steps,
           save_compile_s=save_s, loaded=stats["loaded"],
           compiled=stats["compiled"], warmup_s=warm_s,
           warmup_xla_compiles=warm_n, stream_xla_compiles=stream_n,
           compile_count=fleet.compile_count,
           decisions=sum(map(len, res["decisions"])),
           bitexact_vs_jit=check(all_same(res["decisions"], want),
                                 "AOT-warmed fleet != JIT fleet"),
           **timing(res["steady_s"]), peak_bytes_in_use=peak_bytes(devs[0]))
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))


def four_chips(seed: int, devs: list) -> None:
    from repro.core.pipeline import HDCConfig
    from repro.launch.mesh import make_mesh
    from repro.launch.serve import train_bank

    clock = CompileClock()
    rng = np.random.default_rng(seed)
    cfg = HDCConfig(variant="sparse_compim", **GEOMETRY)
    bank = train_bank(cfg, PATIENTS, rng)
    owners = [f"patient{i % PATIENTS}" for i in range(SESSIONS)]
    traffic = make_traffic(cfg, rng)
    tile = SESSIONS // len(devs)
    runs = {}
    for name, kw in (("one_device", {"tile": SESSIONS}),
                     ("mesh", {"mesh": make_mesh((len(devs),), ("data",),
                                                 devices=devs)}),
                     ("tiles", {"tile": tile})):
        fleet, res, stats = run_fleet(bank, owners, traffic, clock,
                                      backend="jnp", **kw)
        runs[name] = res["decisions"]
        fields = {"fleet": name, "variant": "sparse_compim", "backend": "jnp",
                  "sessions": SESSIONS, "tile": kw.get("tile"), **stats,
                  "tile_devices": res["tile_devices"]}
        if name == "mesh":
            fields["counts_shard_devices"] = sorted(
                s.device.id for s in fleet.state.counts.addressable_shards)
            check(len(fields["counts_shard_devices"]) == len(devs),
                  "mesh fleet state is not spread over every device")
        if name == "tiles":
            check(len({d for t in res["tile_devices"] for d in t})
                  == len(devs), "tiles are not spread over every device")
        if name != "one_device":
            fields["bitexact_vs_one_device"] = check(
                all_same(runs[name], runs["one_device"]),
                f"{name} fleet != one-device fleet")
        report("four_chips", devs, **fields, **timing(res["steady_s"]),
               peak_bytes_in_use=[peak_bytes(d) for d in devs])
        del fleet
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the patient bank and the traffic")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip paths (mesh-sharded and "
                         "round-robin tiles) against a one-device fleet")
    args = ap.parse_args(argv)
    n = 4 if args.four_chips else 1
    devs = require_tpu(n)[:n]
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.runtime.aot import setup_compilation_cache

    setup_compilation_cache()
    try:
        (four_chips if args.four_chips else one_chip)(args.seed, devs)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
