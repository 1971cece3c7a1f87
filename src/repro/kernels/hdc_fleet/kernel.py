"""Fused code-domain fleet-step kernel: gather + bind + bundle + counters.

One grid cell is (session, 32-cycle time group).  The kernel consumes RAW
LBP codes — the only per-cycle input that ever crosses HBM — and keeps the
session's pre-bound CompIM table bank (binding folded into the table build,
serve/dispatch.py) resident in VMEM, selected per session by a
scalar-prefetched owner index (the table BlockSpec's index map reads
``owner[i]``, so patients sharing a codebook share one VMEM block and no
per-session table copy is ever materialized):

    codes (32, C) in SMEM (four 8-bit codes per int32 word)
        --VMEM row loads-->     per cycle, C bound rows (1, W)
           (row c = table[c, codes[j, c]], a dynamic sublane slice of the
           resident bank; the CompIM insight one stage further: binding IS
           the lookup)
        --spatial bundle-->     per cycle, (32, W) kept bits (bit b of word w)
           (OR of the rows / per-bit channel count + thinning / majority)
        --slot counters-->      (K+1, 32, W) int32 counter bank
           accumulated across time groups, like hdc_encoder's counter bank

The codes, the emission schedule and the channel mask are read as scalars
from SMEM; the table bank and the counters live in VMEM.  HBM traffic per
group is 32*C bytes of codes in and (on the last group) one (K+1, D) count
bank out — the bound rows, the per-cycle HVs and the temporal counters never
leave the core, and no float math exists anywhere (the TPU analogue of the
paper's binary-domain argument; see README.md "Kernel & datapath design").

Memory per grid step (defaults window=256, C=64, K=64 codes, D=1024, K+1=2):
  table bank    64*64*32*4 B = 512 KiB (VMEM, lane-padded to 2 MiB;
                                        re-fetched only when the session's
                                        owner row changes)
  codes block   32*16*4 B    =   2 KiB (SMEM)
  counter bank  2*32*32*4 B  =   8 KiB (VMEM)

The emission schedule arrives as time-packed per-slot cycle masks
(ref.emission_masks) computed on device from (filled, lengths): bit j of
mask word g selects cycle 32 g + j into a slot, so adding a cycle's kept
bits to every slot whose mask bit is set IS the temporal bundling.
Bit-exact with the pure-jnp code-domain path (dispatch.owner_spatial_codes
+ ref.fleet_counts_ref); validated in interpret mode (tests/test_kernels.py)
and compiled by Mosaic for a TPU v5e at paper geometry in every bundle mode,
masked and unmasked (tests/test_tpu_compile.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _fleet_kernel(owner_ref, tab_ref, codes_ref, tm_ref, *refs,
                  mode: str, channels: int, n_codes: int, dim: int,
                  threshold: int, masked: bool):
    """Spatial-bundle modes mirror dispatch.owner_spatial_codes: ``or`` =
    OR of the bound rows (optimized sparse), ``thin`` = per-bit channel
    count + threshold (naive sparse), ``majority`` = per-bit count +
    majority (dense).

    With ``masked`` a quarantined channel's row contributes nothing (OR
    identity / zero addend) and the count-variant denominators renormalize
    to the live channel count — thinning threshold via the ceil rule of
    dispatch.effective_spatial_threshold, majority over the live count."""
    del owner_ref  # consumed by the BlockSpec index maps (scalar prefetch)
    cm_ref, out_ref = (refs if masked else (None,) + refs)
    g = pl.program_id(1)
    kp1 = out_ref.shape[1]
    w = dim // 32

    @pl.when(g == 0)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    live = channels
    if masked:
        live = cm_ref[0, 0, 0]
        for c in range(1, channels):
            live = live + cm_ref[0, 0, c]
    if mode == "thin" and masked:
        threshold = jnp.maximum(1, (threshold * live + channels - 1)
                                // channels)
    bit = jax.lax.broadcasted_iota(jnp.uint32, (32, w), 0)

    def bound_row(j, c):
        word = codes_ref[0, j, c // 4]
        # out-of-alphabet codes clamp within their channel's rows, like the
        # jnp path (dispatch.owner_spatial_codes) and the reference indexing
        code = jnp.minimum((word >> (8 * (c % 4))) & 0xFF, n_codes - 1)
        row = tab_ref[0, c, pl.ds(code, 1), :]              # (1, W)
        if masked:
            row = jnp.where(cm_ref[0, 0, c] != 0, row, jnp.uint32(0))
        return row

    def cycle(j, acc):
        if mode == "or":
            word = bound_row(j, 0)
            for c in range(1, channels):
                word = word | bound_row(j, c)
            keep = (word >> bit) & jnp.uint32(1)            # (32, W)
        else:
            cnt = jnp.zeros((32, w), jnp.uint32)
            for c in range(channels):
                cnt = cnt + ((bound_row(j, c) >> bit) & jnp.uint32(1))
            cnt = cnt.astype(jnp.int32)
            if mode == "thin":
                keep = cnt >= threshold
            else:  # majority (ties broken low, matches hv.majority_pack)
                keep = cnt * 2 > live
        keep = keep.astype(jnp.int32)
        # cycle j joins every slot whose time-packed mask has bit j set
        return tuple(a + keep * ((tm_ref[0, k, g] >> j) & 1)
                     for k, a in enumerate(acc))

    zero = jnp.zeros((32, w), jnp.int32)
    acc = jax.lax.fori_loop(0, 32, cycle, (zero,) * kp1)
    for k in range(kp1):
        out_ref[0, k] += acc[k]


def fleet_counts_pallas(tables: jax.Array, owner: jax.Array,
                        codes: jax.Array, tm: jax.Array, *, mode: str,
                        dim: int, threshold: int = 1,
                        chan_mask: jax.Array | None = None,
                        interpret: bool = True) -> jax.Array:
    """tables: (P, C, K, W) uint32 stacked pre-bound codebook bank;
    owner: (S,) int32 each session's table row (scalar-prefetched so the
    BlockSpec can gather the right bank into VMEM);
    codes: (S, T32, C) uint8 raw LBP codes (T32 a multiple of 32; padded
    cycles are masked off by ``tm``);
    tm: (S, K+1, T32 // 32) uint32 time-packed slot masks
    (ref.emission_masks);
    chan_mask: optional (S, C) uint32 per-session channel mask (1 = live)
    — a fourth operand, one SMEM row per session: quarantined channels
    drop out of the spatial bundle and the count-variant denominators
    renormalize to the live count (see _fleet_kernel).
    Returns (S, K+1, D) int32 slot counts."""
    p, c, k, w = tables.shape
    s, t32, c2 = codes.shape
    assert c2 == c and t32 % 32 == 0 and w * 32 == dim
    groups = t32 // 32
    kp1 = tm.shape[1]
    masked = chan_mask is not None
    # four codes per int32 word, little end first: SMEM holds 32-bit scalars
    cw = -(-c // 4)
    packed = jnp.pad(codes, ((0, 0), (0, 0), (0, cw * 4 - c))).astype(
        jnp.int32).reshape(s, t32, cw, 4)
    packed = (packed[..., 0] | (packed[..., 1] << 8) | (packed[..., 2] << 16)
              | (packed[..., 3] << 24))
    kernel = functools.partial(_fleet_kernel, mode=mode, channels=c,
                               n_codes=k, dim=dim, threshold=threshold,
                               masked=masked)
    smem = pltpu.SMEM
    in_specs = [
        pl.BlockSpec((1, c, k, w), lambda i, g, owner_ref: (owner_ref[i], 0, 0, 0)),
        pl.BlockSpec((1, 32, cw), lambda i, g, owner_ref: (i, g, 0),
                     memory_space=smem),
        pl.BlockSpec((1, kp1, groups), lambda i, g, owner_ref: (i, 0, 0),
                     memory_space=smem),
    ]
    inputs = [owner.astype(jnp.int32), tables, packed,
              jax.lax.bitcast_convert_type(tm, jnp.int32)]
    if masked:
        # (S, 1, C): a block's last two dims must span the array's
        in_specs.append(pl.BlockSpec((1, 1, c),
                                     lambda i, g, owner_ref: (i, 0, 0),
                                     memory_space=smem))
        inputs.append(chan_mask.astype(jnp.int32).reshape(s, 1, c))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(s, groups),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, kp1, 32, w),
                               lambda i, g, owner_ref: (i, 0, 0, 0)),
    )
    counts = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, kp1, 32, w), jnp.int32),
        interpret=interpret,
    )(*inputs)
    # (bit, word) layout -> standard d = word * 32 + bit order
    return counts.transpose(0, 1, 3, 2).reshape(s, kp1, dim)
