"""A tiny copy of the benchmark for CPU tests: the repo's own files, with
one small configuration, mix and cell added by name."""

from __future__ import annotations

import contextlib
import json
import os
import shutil

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

TINY_GEOMETRY = {"channels": 8, "dim": 256, "segments": 8, "window": 64,
                 "patients": 3,
                 "train": {"pre_s": 0.5, "ictal_s": 0.5, "post_s": 0.0}}

# A packets mix, made from ``frames``: 32-cycle packets every 62.5 ms, frame
# ends staggered over 8 phases, so pushes end off frame boundaries and the
# temporal counters are carried across them.
PACKETS = {"tick_s": 0.0625, "cycles": 32, "phases": 8, "bucket": 32}


def load_mix(name: str) -> dict:
    """The mix ``name``: a file of ``bench/traffic/``, or ``packets``."""
    base = "frames" if name == "packets" else name
    with open(os.path.join(BENCH, "traffic", base + ".json")) as f:
        m = json.load(f)
    if name == "packets":
        m.update(PACKETS)
    return m


def make_root(dst: str, *, variant: str = "sparse_compim",
              backend: str = "pallas", mix: str = "frames",
              sessions: int = 12) -> str:
    """Write a benchmark tree under ``dst`` holding a copy of ``bench/`` and
    a BENCHMARK.json whose one cell, ``tiny.<mix>``, runs a small
    ``variant`` configuration on a shortened copy of ``mix``.  Returns the
    cell's name."""
    shutil.copytree(BENCH, os.path.join(dst, "bench"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__",
                                                  ".trace"))
    with open(os.path.join(BENCH, "configs", "w1_compim.json")) as f:
        cfg = json.load(f)
    cfg.update(TINY_GEOMETRY, name="tiny", variant=variant, backend=backend)
    _dump(dst, "bench/configs/tiny.json", cfg)
    m = load_mix(mix)
    m.update(cycles=m["cycles"] // 4, bucket=max(32, m["bucket"] // 4),
             pool_cycles=256, check_sessions=5,
             record={"pre_s": 0.5, "ictal_s": 0.5, "post_s": 0.5})
    if "tick_s" in m:
        m["tick_s"] = 0.1
    _dump(dst, f"bench/traffic/tiny_{mix}.json", m)
    cell = f"tiny.{mix}"
    _dump(dst, f"bench/cells/{cell}.json", {"sessions": sessions})
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "tiny", "source": "test",
                            "file": "bench/configs/tiny.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": cell, "config": "tiny",
                              "traffic": f"tiny_{mix}", "chips": 1,
                              "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "w1_compim.frames" in m.get("workloads", []):
            m["workloads"].append(cell)   # report what the twin reports
    _dump(dst, "BENCHMARK.json", spec)
    return cell


def _dump(root: str, rel: str, obj) -> None:
    with open(os.path.join(root, rel), "w") as f:
        json.dump(obj, f)


@contextlib.contextmanager
def no_persistent_cache():
    """Keep the tests' compiles out of the checkout's compile cache."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()
