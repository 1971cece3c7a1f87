"""Public entry points for the fleet's packed-domain temporal bundling.

Two paths, both bit-exact with the per-session reference datapaths:

* ``fleet_counts`` — pure-jnp bit-plane path (ref.py): takes the per-cycle
  packed spatial HVs and needs NO masks (slot membership is contiguous, so
  counts are prefix-count differences at slot boundaries).
* ``fleet_counts_fused`` — the Pallas kernel (kernel.py): takes RAW uint8
  codes plus the stacked pre-bound codebook bank and runs the table gather
  (bind) and spatial bundling as one-hot codes x the patient's unpacked
  bit table on the MXU, then the temporal accumulation as a second
  product against the device-computed emission masks
  (ref.emission_masks).  Nothing per-cycle wider than the codes
  themselves ever crosses HBM.

``spatial_mode`` maps an HDCConfig onto the kernel's spatial-bundle variant
exactly as serve/dispatch.owner_spatial_codes routes it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.classifier import HDCConfig
from repro.kernels.common import use_interpret
from repro.kernels.hdc_fleet.kernel import fleet_counts_pallas
from repro.kernels.hdc_fleet.ref import emission_masks, fleet_counts_ref


def spatial_mode(cfg: HDCConfig) -> tuple[str, int]:
    """(mode, threshold) for the fused kernel's spatial bundling stage."""
    if cfg.variant == "dense":
        return "majority", 0
    if cfg.variant == "sparse_naive" or cfg.spatial_thinning:
        return "thin", cfg.spatial_threshold
    return "or", 0


def fleet_counts(words: jax.Array, filled: jax.Array, lengths: jax.Array,
                 cfg: HDCConfig) -> jax.Array:
    """(S, T, W) spatial HVs -> (S, K+1, D) int32 frame-slot counts."""
    return fleet_counts_ref(words, filled, lengths, window=cfg.window,
                            dim=cfg.dim)


def fleet_counts_fused(tables: jax.Array, owner: jax.Array,
                       codes: jax.Array, filled: jax.Array,
                       lengths: jax.Array, cfg: HDCConfig,
                       tables_xor: jax.Array | None = None,
                       chan_mask: jax.Array | None = None) -> jax.Array:
    """(S, T, C) raw uint8 codes -> (S, K+1, D) counts, one fused pass.

    ``tables`` is the stacked (P, C, K, W) pre-bound codebook bank and
    ``owner`` each session's row into it (scalar-prefetched by the kernel's
    table BlockSpec).  Pads the cycle axis to a 32 multiple (padded cycles
    are masked off by the emission schedule) and runs the fused kernel;
    interpret mode off-TPU.

    ``tables_xor`` (same shape as ``tables``) is the reliability
    subsystem's fault-injection hook (repro.reliability.faults): an
    effective bit-flip mask XORed into the codebook bank HERE, adjacent to
    the kernel launch, so the table BlockSpec fetches (and the kernel
    unpacks) the FAULTED bank — the corruption rides the same operand path
    as the clean bank and the kernel body is untouched.  ``None`` (the
    default) skips the XOR entirely.

    ``chan_mask`` (S, C) uint8/uint32, the channel-fault tolerance hook
    (repro.reliability.channels): quarantined channels drop out of the
    in-kernel spatial bundle with renormalized count denominators, exactly
    like dispatch.owner_spatial_codes' masked path.  ``None`` (the
    default) leaves the mask operand out of the kernel.
    """
    s, t, c = codes.shape
    if tables_xor is not None:
        tables = tables ^ tables_xor
    t32 = -(-t // 32) * 32
    if t32 != t:
        codes = jnp.pad(codes, ((0, 0), (0, t32 - t), (0, 0)))
    tm = emission_masks(filled, lengths, t_pad=t, window=cfg.window)
    mode, threshold = spatial_mode(cfg)
    return fleet_counts_pallas(tables, owner, codes, tm, mode=mode,
                               dim=cfg.dim, threshold=threshold,
                               chan_mask=chan_mask,
                               interpret=use_interpret())
