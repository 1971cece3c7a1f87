"""Fused code-domain fleet-step kernel: the table gather and spatial bundle
as one matrix product on the MXU, then the temporal slot counters.

One grid step is one session and its whole chunk.  The kernel consumes RAW
LBP codes — the only per-cycle input that ever crosses HBM — and the
session's pre-bound CompIM table bank (binding folded into the table build,
serve/dispatch.py), selected by a scalar-prefetched index map.  The grid
walks the sessions in owner order (a stable argsort of ``owner``,
scalar-prefetched next to each step's table row), so sessions of one
patient run back to back and no data moves:

    table (C, K, W) uint32, the patient's packed bound rows
        --unpack, once per owner run-->   bank (D, C·K) int8 0/1 in VMEM
           bank[d, c·K + k] = bit d of table[c, k]
    codes (C, t) uint8, clamped, masked channels set to K (matches nothing)
        --one-hot-->                      onehot (C·K, t) int8
           onehot[c·K + k, j] = [code[j, c] == k]
    counts (D, t) int32 = bank @ onehot   (MXU; the per-bit channel counts)
    keep = counts > 0 (``or``) | counts >= threshold (``thin``)
           | 2·counts > live (``majority``, ties broken low)
    slots (K+1, D) int32 = masks (K+1, t) · keepᵀ   (MXU; temporal bundle)

Every product is exact: operands are 0/1 and the int32 sums are at most C
(spatial) and t (temporal).  One algorithm serves every bundle mode, faulted
tables (arbitrary bits) and masked channels.  The emission schedule arrives
as time-packed per-slot cycle masks (ref.emission_masks) read from SMEM:
bit j of mask word g selects cycle 32 g + j into a slot.

The MXU path runs every bucket: on one TPU v5e at paper geometry (S=4,096
sessions, 16 patients, C=64, K=64, D=1024) it takes, per push, 13.5 /
13.6 / 13.4 / 24.8 ms at t = 32 / 64 / 128 / 256 cycles, against 21.9 /
43.0 / 85.2 / 169.7 ms for the per-row gather kernel it replaced
(chip_kernel_bench.py; PERF.md section 5).  Below t = 128 the MXU pads
the chunk to 128 columns, so the short buckets cost what t = 128 costs.

VMEM per grid step at paper geometry: the bank 4 MiB (scratch), the
double-buffered table block 2 x 2 MiB (lane-padded), the one-hot and its
int32 compare 5 MiB, the int32 counts 1 MiB.  Chunks longer than 256 cycles
run in 256-cycle pieces, so the working set does not grow with the bucket.
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

# cycles per MXU pass: longer chunks loop over pieces of this many cycles
_PIECE = 256
# the bank, the table blocks and a piece's temporaries with room to spare;
# a v5e core has 128 MiB of VMEM
_VMEM_LIMIT = 64 * 1024 * 1024


def _fleet_kernel(perm_ref, rows_ref, tab_ref, codes_ref, tm_ref, *refs,
                  mode: str, threshold: int, masked: bool):
    """With ``masked`` a quarantined channel's codes arrive as K (no
    one-hot row) and the count-variant denominators renormalize to the live
    channel count — thinning threshold via the ceil rule of
    dispatch.effective_spatial_threshold, majority over the live count."""
    del perm_ref  # consumed by the BlockSpec index maps (scalar prefetch)
    live_ref, out_ref, bank_ref = refs if masked else (None,) + refs
    _, c, k, w = tab_ref.shape
    dim, ck = bank_ref.shape
    t = codes_ref.shape[2]
    kp1 = out_ref.shape[1]
    i = pl.program_id(0)

    @pl.when((i == 0) | (rows_ref[i] != rows_ref[jnp.maximum(i - 1, 0)]))
    def _unpack():
        xt = tab_ref[0].reshape(ck, w).T                    # (W, C·K)
        bit = jax.lax.broadcasted_iota(jnp.uint32, (32, ck), 0)
        for j in range(w):  # d = 32 j + b: bank rows 32 j .. 32 j + 31
            bits = (xt[j:j + 1] >> bit) & jnp.uint32(1)
            bank_ref[32 * j:32 * (j + 1), :] = bits.astype(
                jnp.int32).astype(jnp.int8)

    live = channels = c
    if masked:
        live = live_ref[0, 0, 0]
    if mode == "thin" and masked:
        threshold = jnp.maximum(1, (threshold * live + channels - 1)
                                // channels)
    piece = _PIECE if t % _PIECE == 0 else t
    groups = piece // 32
    code_k = jax.lax.broadcasted_iota(jnp.int32, (c, k, piece), 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, piece), 1)

    slots = jnp.zeros((kp1, dim), jnp.int32)
    for p in range(t // piece):
        codes = codes_ref[0, :, p * piece:(p + 1) * piece].astype(jnp.int32)
        onehot = (codes[:, None, :] == code_k).astype(jnp.int32).astype(
            jnp.int8).reshape(ck, piece)
        counts = jnp.dot(bank_ref[...], onehot,
                         preferred_element_type=jnp.int32)  # (D, piece)
        if mode == "or":
            keep = counts > 0
        elif mode == "thin":
            keep = counts >= threshold
        else:
            keep = counts * 2 > live
        keep = keep.astype(jnp.int32).astype(jnp.int8)
        # slot s's cycle mask over this piece, one lane per cycle
        masks = []
        for s in range(kp1):
            word = jnp.zeros((1, piece), jnp.int32)
            for g in range(groups):
                word = jnp.where(lane // 32 == g,
                                 tm_ref[0, s, p * groups + g], word)
            masks.append((word >> (lane % 32)) & 1)
        masks = jnp.concatenate(masks, axis=0).astype(jnp.int8)
        slots = slots + jax.lax.dot_general(
            masks, keep, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32)               # (K+1, D)
    out_ref[0] = slots


def fleet_counts_pallas(tables: jax.Array, owner: jax.Array,
                        codes: jax.Array, tm: jax.Array, *, mode: str,
                        dim: int, threshold: int = 1,
                        chan_mask: jax.Array | None = None,
                        interpret: bool = True) -> jax.Array:
    """tables: (P, C, K, W) uint32 stacked pre-bound codebook bank;
    owner: (S,) int32 each session's table row;
    codes: (S, T32, C) uint8 raw LBP codes (T32 a multiple of 32; padded
    cycles are masked off by ``tm``; out-of-alphabet codes clamp within
    their channel's rows, like the jnp path and the reference indexing);
    tm: (S, K+1, T32 // 32) uint32 time-packed slot masks
    (ref.emission_masks);
    chan_mask: optional (S, C) per-session channel mask (1 = live):
    quarantined channels drop out of the spatial bundle and the
    count-variant denominators renormalize to the live count (see
    _fleet_kernel).
    Returns (S, K+1, D) int32 slot counts."""
    _, c, k, w = tables.shape
    s, t32, c2 = codes.shape
    assert c2 == c and t32 % 32 == 0 and w * 32 == dim
    kp1 = tm.shape[1]
    masked = chan_mask is not None
    owner = owner.astype(jnp.int32)
    perm = jnp.argsort(owner, stable=True).astype(jnp.int32)
    with jax.named_scope("transpose_codes"):
        codes = jnp.minimum(codes, jnp.asarray(k - 1, codes.dtype))
        if masked:  # code K has no one-hot row: the channel adds nothing
            codes = jnp.where(chan_mask[:, None, :] != 0, codes,
                              jnp.asarray(k, codes.dtype))
        codes = jnp.swapaxes(codes, 1, 2)                   # (S, C, T32)
    kernel = functools.partial(_fleet_kernel, mode=mode, threshold=threshold,
                               masked=masked)
    smem = pltpu.SMEM
    in_specs = [
        pl.BlockSpec((1, c, k, w), lambda i, perm, rows: (rows[i], 0, 0, 0)),
        pl.BlockSpec((1, c, t32), lambda i, perm, rows: (perm[i], 0, 0)),
        pl.BlockSpec((1, kp1, t32 // 32), lambda i, perm, rows: (perm[i], 0, 0),
                     memory_space=smem),
    ]
    # the table map reads one prefetched row per step, not owner[perm[i]]:
    # a nested SMEM lookup in an index map halted the chip at P >= 1,024
    inputs = [perm, owner[perm], tables, codes,
              jax.lax.bitcast_convert_type(tm, jnp.int32)]
    if masked:
        # (S, 1, 1): a block's last two dims must span the array's
        in_specs.append(pl.BlockSpec((1, 1, 1),
                                     lambda i, perm, rows: (perm[i], 0, 0),
                                     memory_space=smem))
        inputs.append(chan_mask.astype(jnp.int32).sum(
            axis=1, dtype=jnp.int32).reshape(s, 1, 1))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, kp1, dim),
                               lambda i, perm, rows: (perm[i], 0, 0)),
        scratch_shapes=[pltpu.VMEM((dim, c * k), jnp.int8)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, kp1, dim), jnp.int32),
        # sequential: the unpacked bank carries from one step to the next
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        # the custom call's HLO name, and so the kernel's name in profiles
        name="hdc_fleet_counts",
    )(*inputs)
