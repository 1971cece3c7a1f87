"""Fleet serving throughput: StreamingFleet vs looped SeizureSessions.

The looped baseline is the pre-fleet serving shape — one Python object and
one jit dispatch per stream per service interval.  The fleet advances ALL
streams in cache-tiled jitted steps (code/packed/bit-plane domain, see
serve/fleet.py).  For S in {1, 64, 1024} (window-length chunks, one decision
per stream per push) we report sessions-per-second, decisions per second and
per-decision latency, plus the fleet/baseline speedup row the CI
perf-regression gate reads from BENCH_fleet.json, and a ``fleet_codes`` row
for the zero-scatter pre-stacked ``push_codes`` ingest path.

Methodology: both sides run the SAME repeat count and statistic (min over
iters — on this shared container scheduler bursts inflate single samples
3-10x and noise only ever adds, so the minimum estimates the true cost;
medians flaked the CI gate) and block on device results explicitly
(``jax.block_until_ready`` on the fleet's raw rounds; the baseline's
decisions are host arrays already) — no reliance on implicit syncs — and
each fleet's cold first push (jit trace + compile) is reported as its own
``*_compile`` row, never mixed into the steady-state timing.

BENCH_TINY=1 (CI smoke) shrinks to S in {1, 8} on a small geometry.
"""

from __future__ import annotations

import os
import time

# multiple CPU "devices" let the fleet round-robin its session tiles over
# all cores.  Only effective when this module is the first jax-backend user
# in the process — run ``-m benchmarks.run fleet`` (or list fleet first,
# like CI's bench-smoke does) for multi-device numbers; the ``devices`` row
# records what the run actually got.  Deliberately NOT set in run.py: the
# other modules' committed baselines were measured without forced host
# devices, and their environment should stay as-measured.
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={os.cpu_count() or 1}"
    ).strip()

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import tiny
from repro.core.pipeline import HDCConfig, HDCPipeline
from repro.serve.engine import SeizureSession
from repro.serve.fleet import StreamingFleet


def _config() -> tuple[HDCConfig, tuple[int, ...], int]:
    if tiny():
        cfg = HDCConfig(dim=256, segments=8, channels=16, window=64,
                        temporal_threshold=8)
        return cfg, (1, 8), 3
    return HDCConfig(), (1, 64, 1024), 7


def _trained(cfg: HDCConfig) -> HDCPipeline:
    rng = np.random.default_rng(0)
    codes = jnp.asarray(
        rng.integers(0, cfg.codes, (1, 4 * cfg.window, cfg.channels), np.uint8))
    labels = np.asarray(rng.integers(0, 2, (1, 4), np.int32))
    labels[0, :2] = (0, 1)  # every class needs >= 1 example
    return HDCPipeline.init(jax.random.PRNGKey(42), cfg).train_one_shot(
        codes, jnp.asarray(labels))


def _time(fn, iters: int) -> float:
    """Min wall-time (s) over iters calls (fn must block on its results).

    Min, not median: this container is a shared 2-vCPU box whose scheduler
    bursts inflate individual samples 3-10x, and noise only ever ADDS time
    — the minimum is the standard robust estimator of the true cost, and
    every row (baseline, fleet, stages) uses the same statistic, so the
    ratio rows the CI gate reads stay comparable.
    """
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return min(times)


def run() -> list[dict]:
    cfg, s_list, iters = _config()
    pipe = _trained(cfg)
    rng = np.random.default_rng(1)
    chunk = rng.integers(0, cfg.codes, (cfg.window, cfg.channels), np.uint8)
    rows = [{
        "name": "fleet.devices",
        "us_per_call": "",
        "derived": (f"n={len(jax.devices())} (session tiles round-robin "
                    "across local devices)"),
    }]
    for s in s_list:
        sessions = [SeizureSession(pipe) for _ in range(s)]
        chunks = [chunk] * s

        def run_baseline():
            for sess, c in zip(sessions, chunks):
                assert len(sess.push(c)) == 1  # decisions are host arrays

        run_baseline()  # warmup (jit compiles shared across sessions)
        t_base = _time(run_baseline, iters)

        fleet = StreamingFleet({"p": pipe}, ["p"] * s, buckets=(cfg.window,))
        batch = np.broadcast_to(chunk, (s, *chunk.shape))

        def run_fleet():
            rounds = fleet.push_raw(chunks)
            jax.block_until_ready([r.tiles for r in rounds])
            assert rounds[0].n_emit[0] == 1

        def run_fleet_codes():
            rounds = fleet.push_codes_raw(batch)
            jax.block_until_ready([r.tiles for r in rounds])
            assert rounds[0].n_emit[0] == 1

        t_compile = _time(run_fleet, 1)  # cold: jit trace + compile + run
        run_fleet()  # one warm push so the timed calls are pure steady state
        t_fleet = _time(run_fleet, iters)
        run_fleet_codes()
        t_codes = _time(run_fleet_codes, iters)

        for name, t in (("baseline_loop", t_base), ("fleet", t_fleet),
                        ("fleet_codes", t_codes)):
            rows.append({
                "name": f"fleet.S{s}.{name}",
                "us_per_call": f"{t * 1e6:.0f}",
                "derived": (f"sessions/s={s / t:.1f}"
                            f";decisions/s={s / t:.1f}"
                            f";us/decision={t * 1e6 / s:.1f}"),
            })
        rows.append({
            "name": f"fleet.S{s}.fleet_compile",
            "us_per_call": f"{t_compile * 1e6:.0f}",
            "derived": (f"cold first push (trace+compile+run); steady-state "
                        f"push={t_fleet * 1e6:.0f}us"),
        })
        rows.append({
            "name": f"fleet.S{s}.speedup",
            "us_per_call": "",
            "derived": (f"{t_base / t_fleet:.2f}x sessions/s vs looped "
                        f"SeizureSession baseline"),
        })
        rows.append({
            # ".speedup" suffix so the CI regression gate ratio-checks the
            # push_codes ingest fast path too
            "name": f"fleet.S{s}.codes.speedup",
            "us_per_call": "",
            "derived": (f"{t_base / t_codes:.2f}x sessions/s vs looped "
                        f"baseline (pre-stacked push_codes ingest)"),
        })
    return rows


if __name__ == "__main__":
    from benchmarks.common import emit
    emit(run())
