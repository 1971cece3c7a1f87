#!/usr/bin/env python3
"""Check and time the fleet kernel per chunk bucket on one TPU.

    PYTHONPATH=src python3 chip_kernel_bench.py [--out FILE]

At the paper's geometry (64 electrodes, 6-bit LBP codes, D=1024, 256-cycle
frames):

  exact  on 512 sessions (the jnp path's gathers outgrow the chip's memory
         at a whole tile), every bundle mode (sparse_compim ``or``, sparse_naive ``thin``,
         dense ``majority``), unmasked and with a tenth of the channels
         quarantined, at the shortest and the longest default bucket, over
         random table bits (as a faulted bank has): the fused kernel
         (``ops.fleet_counts_fused``) equals the jnp code-domain path
         (``dispatch.owner_spatial_codes`` + ``ops.fleet_counts``) on every
         count
  time   on one 4,096-session tile, for each default bucket,
         ``fleet_counts_fused`` jitted (the kernel and the ops around it),
         16 patients with sessions assigned
         round-robin as the benchmark's traffic does, and at the shortest
         and longest bucket one patient per session: the median of 10
         calls after two warm-up calls, each ended by ``block_until_ready``

Each result is one JSON line naming the device; ``--out`` writes them to a
file too.  Any mismatch exits non-zero, and so does a host without a TPU.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

SESSIONS = 4096
EXACT_SESSIONS = 512
PATIENTS = 16
BUCKETS = (32, 64, 128, 256)
MODES = (("or", "sparse_compim"), ("thin", "sparse_naive"),
         ("majority", "dense"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from repro.core.pipeline import HDCConfig
    from repro.kernels.hdc_fleet import ops as fleet_ops
    from repro.serve import dispatch

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU: JAX found {dev.platform}", file=sys.stderr)
        return 2
    out = open(args.out, "w") if args.out else None

    def emit(**kw):
        kw["device"] = dev.device_kind
        line = json.dumps(kw)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    base = HDCConfig()
    c, k, w, window = base.channels, base.codes, base.words, base.window

    def inputs(t, sessions, patients, seed, masked=False):
        rng = np.random.default_rng(seed)
        tables = jnp.asarray(rng.integers(0, 2**32, (patients, c, k, w),
                                          dtype=np.uint32))
        owner = jnp.asarray(np.arange(sessions) % patients, jnp.int32)
        codes = jnp.asarray(rng.integers(0, k, (sessions, t, c), np.uint8))
        filled = jnp.asarray(rng.integers(0, window, sessions), jnp.int32)
        lengths = jnp.asarray(rng.integers(0, t + 1, sessions), jnp.int32)
        mask = (jnp.asarray(rng.random((sessions, c)) > 0.1, jnp.uint8)
                if masked else None)
        return tables, owner, codes, filled, lengths, mask

    def fused(cfg):
        def f(tables, owner, codes, filled, lengths, mask=None):
            return fleet_ops.fleet_counts_fused(tables, owner, codes, filled,
                                                lengths, cfg, chan_mask=mask)
        return jax.jit(f)

    def plain(cfg):
        def f(tables, owner, codes, filled, lengths, mask=None):
            words = dispatch.owner_spatial_codes(tables, owner, codes, cfg,
                                                 mask)
            return fleet_ops.fleet_counts(words, filled, lengths, cfg)
        return jax.jit(f)

    ok = True
    for t in (BUCKETS[0], BUCKETS[-1]):
        for mode, variant in MODES:
            cfg = HDCConfig(variant=variant, backend="pallas")
            for masked in (False, True):
                a = inputs(t, EXACT_SESSIONS, PATIENTS, seed=t,
                           masked=masked)
                got = np.asarray(fused(cfg)(*a))
                want = np.asarray(plain(cfg)(*a))
                equal = bool((got == want).all())
                ok &= equal
                emit(kind="exact", bucket=t, mode=mode, masked=masked,
                     equal=equal, counted=int(want.sum()))

    cfg = HDCConfig(backend="pallas")
    cases = [(t, PATIENTS) for t in BUCKETS]
    cases += [(BUCKETS[0], SESSIONS), (BUCKETS[-1], SESSIONS)]
    for t, patients in cases:
        a = inputs(t, SESSIONS, patients, seed=1)[:5]
        f = fused(cfg)
        for _ in range(2):
            f(*a).block_until_ready()
        ms = []
        for _ in range(10):
            t0 = time.perf_counter()
            f(*a).block_until_ready()
            ms.append((time.perf_counter() - t0) * 1e3)
        emit(kind="time", bucket=t, sessions=SESSIONS, patients=patients,
             ms_median=float(np.median(ms)), ms_min=float(np.min(ms)))
        del a
    emit(kind="done", ok=ok)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
