"""Cold start to first decision: fresh JIT vs warm cache vs serialized AOT.

A restarted or autoscaled serving worker pays trace+compile for every
(bucket, tile) executable before its first decision — the tax
runtime/aot.py exists to kill.  This module measures that tax three ways on
ONE fleet geometry, all in the SAME process (the ±30% container-noise rule:
only same-process ratio rows are meaningful, absolute times are not):

  coldstart.S*.jit        fresh fleet, persistent compilation cache DISABLED
                          -> first push is a full trace + XLA compile
  coldstart.S*.warmcache  fresh fleet, persistent cache ON after the deploy
                          artifact was built into it -> first push traces
                          but the XLA compile is a disk hit
  coldstart.S*.serialized fresh fleet warmed from the artifact's serialized
                          executables (``warmup(aot=...)`` timed INCLUSIVE)
                          -> no tracing, no XLA compile, no cache needed

plus the ``*.speedup`` ratio rows CI gates (check_fleet_regression.py
--coldstart), an ``artifact_compile`` row recording what ``save_aot`` cost,
and two correctness rows the gate requires to start with "ok":

  coldstart.bitexact      all three paths produced identical decisions
  coldstart.fallback      a key-tampered (stale) artifact loads as None and
                          the fleet falls back to JIT with identical
                          decisions

The persistent cache lives wherever the entry point placed it
(``aot.setup_compilation_cache``: ``JAX_COMPILATION_CACHE_DIR`` or the
checkout's ``.jax_cache``); this module only turns it off
(``aot.persistent_cache_off``) for the fresh-JIT and serialized scenarios,
so nothing they compile can be served from it.  Each
scenario starts from a freshly constructed fleet and ``jax.clear_caches()``,
so in-process tracing caches cannot leak between them either.

BENCH_TINY=1 (CI smoke) shrinks to a small geometry; the committed
BENCH_coldstart.json is a full-geometry run.
"""

from __future__ import annotations

import json
import os
import shutil
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import tiny
from repro.core.pipeline import HDCConfig, HDCPipeline
from repro.runtime import aot as aot_mod
from repro.serve.fleet import StreamingFleet


def _config() -> tuple[HDCConfig, int]:
    if tiny():
        return HDCConfig(dim=256, segments=8, channels=16, window=64,
                         temporal_threshold=8), 8
    return HDCConfig(), 64


def _trained(cfg: HDCConfig) -> HDCPipeline:
    rng = np.random.default_rng(0)
    codes = jnp.asarray(
        rng.integers(0, cfg.codes, (1, 4 * cfg.window, cfg.channels), np.uint8))
    labels = np.asarray(rng.integers(0, 2, (1, 4), np.int32))
    labels[0, :2] = (0, 1)  # every class needs >= 1 example
    return HDCPipeline.init(jax.random.PRNGKey(42), cfg).train_one_shot(
        codes, jnp.asarray(labels))


def _decisions(out) -> list[tuple]:
    return [(d.frame_index, d.prediction, tuple(np.asarray(d.scores)))
            for per_session in out for d in per_session]


def run() -> list[dict]:
    cfg, s = _config()
    pipe = _trained(cfg)
    owners = ["p"] * s
    buckets = (cfg.window,)  # one executable: apples-to-apples across paths
    rng = np.random.default_rng(7)
    chunks = [rng.integers(0, cfg.codes, (cfg.window, cfg.channels), np.uint8)
              for _ in range(s)]

    def fresh_fleet() -> StreamingFleet:
        jax.clear_caches()
        return StreamingFleet({"p": pipe}, owners, buckets=buckets)

    rows: list[dict] = []
    tmp = tempfile.mkdtemp(prefix="bench_coldstart_")
    try:
        art_dir = os.path.join(tmp, "aot")

        # -- A: fresh JIT, persistent cache off ---------------------------
        with aot_mod.persistent_cache_off():
            fleet = fresh_fleet()
            t0 = time.perf_counter()
            out_jit = fleet.push(chunks)
            t_jit = time.perf_counter() - t0
        rows.append({
            "name": f"coldstart.S{s}.jit",
            "us_per_call": round(t_jit * 1e6, 1),
            "derived": "first push = trace + XLA compile + run "
                       "(persistent cache disabled)",
        })

        # -- build the deploy artifact (after A, so A saw cold everything);
        # its plain-jit compiles land in the persistent cache
        builder = fresh_fleet()
        t0 = time.perf_counter()
        builder.save_aot(art_dir)
        t_build = time.perf_counter() - t0
        rows.append({
            "name": f"coldstart.S{s}.artifact_compile",
            "us_per_call": round(t_build * 1e6, 1),
            "derived": "one-time `serve compile`: export + compile the "
                       "executable set into the artifact",
        })

        # -- B: warm persistent cache, plain JIT --------------------------
        fleet = fresh_fleet()
        t0 = time.perf_counter()
        out_cache = fleet.push(chunks)
        t_cache = time.perf_counter() - t0
        rows.append({
            "name": f"coldstart.S{s}.warmcache",
            "us_per_call": round(t_cache * 1e6, 1),
            "derived": "first push traces, XLA compile served from the "
                       "persistent cache the artifact build filled",
        })

        # -- C: serialized executables (warmup timed inclusive) -----------
        with aot_mod.persistent_cache_off():
            fleet = fresh_fleet()
            t0 = time.perf_counter()
            art = aot_mod.load_artifact(art_dir)
            stats = fleet.warmup(aot=art)
            out_aot = fleet.push(chunks)
            t_aot = time.perf_counter() - t0
        rows.append({
            "name": f"coldstart.S{s}.serialized",
            "us_per_call": round(t_aot * 1e6, 1),
            "derived": f"load artifact + warmup({stats['loaded']} loaded) + "
                       "first push: no tracing, no XLA compile",
        })

        for label, t in (("warmcache", t_cache), ("serialized", t_aot)):
            rows.append({
                "name": f"coldstart.S{s}.{label}.speedup",
                "us_per_call": "",
                "derived": f"{t_jit / t:.2f}x faster to first decision than "
                           "process-fresh trace+compile (same process)",
            })

        # -- correctness rows the CI gate requires ------------------------
        ok = _decisions(out_jit) == _decisions(out_cache) == _decisions(out_aot)
        rows.append({
            "name": "coldstart.bitexact",
            "us_per_call": "",
            "derived": ("ok all three cold-start paths produced identical "
                        "decisions" if ok else
                        "MISMATCH between cold-start paths"),
        })

        # tamper the artifact key -> load must refuse, fleet must fall back
        stale_dir = os.path.join(tmp, "aot_stale")
        shutil.copytree(art_dir, stale_dir)
        mpath = os.path.join(stale_dir, aot_mod.MANIFEST)
        with open(mpath) as f:
            manifest = json.load(f)
        manifest["key"]["jax"] = "0.0.0-stale"
        with open(mpath, "w") as f:
            json.dump(manifest, f)
        import warnings as _warnings

        with _warnings.catch_warnings():
            _warnings.simplefilter("ignore")
            stale = aot_mod.load_artifact(stale_dir)
        fleet = fresh_fleet()
        fleet.warmup(aot=stale)  # stale is None: pre-compiles via JIT
        out_stale = fleet.push(chunks)
        fb_ok = stale is None and _decisions(out_stale) == _decisions(out_jit)
        rows.append({
            "name": "coldstart.fallback",
            "us_per_call": "",
            "derived": ("ok stale artifact refused (load_artifact -> None), "
                        "JIT fallback decisions identical" if fb_ok else
                        "STALE-ARTIFACT FALLBACK BROKEN"),
        })
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return rows


if __name__ == "__main__":
    from benchmarks.common import emit

    aot_mod.setup_compilation_cache()
    emit(run())
