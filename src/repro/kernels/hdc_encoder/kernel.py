"""Fused sparse-HDC encoder kernel (the paper's main datapath, CompIM domain).

One grid step produces ONE time-frame HV for one (batch, frame) cell:

    bound positions (window, S, C)  --bind-->  (pos + elec) mod L
        --demux-->  per-cycle (S, L) one-hot per channel  --OR/thin-->
        (S, L) spatial indicator  --temporal accumulate-->  (S, L) int32
        --threshold-->  (S, L) frame bits (packed to D // 32 words outside)

Fusing the whole encoder keeps the per-cycle 1024-bit spatial HVs and the
8-bit temporal counters in VMEM: HBM traffic is the cycle's positions in and
one frame's bits out (the TPU analogue of the CompIM energy win; see
README.md "Kernel & datapath design").  The (S, L) planes are one (8, 128)
vreg at the paper geometry: segments on sublanes, positions on lanes, so
each channel's demux is a lane broadcast and one compare.

VMEM per grid step (defaults window=256, C=64, S=8, L=128):
  positions block  256*8*64*4 B = 512 KiB (int32)
  counters / bits  8*128*4 B    =   4 KiB
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import hv


def _encoder_kernel(pos_ref, elec_ref, out_ref, *, window: int, segments: int,
                    seg_len: int, temporal_threshold: int,
                    spatial_thinning: bool, spatial_threshold: int):
    c = elec_ref.shape[1]
    elec = elec_ref[...]                                          # (S, C)
    lane = jax.lax.broadcasted_iota(jnp.int32, (segments, seg_len), 1)

    def cycle(j, counts):
        bound = pos_ref[0, 0, j] + elec                           # (S, C)
        bound = jnp.where(bound >= seg_len, bound - seg_len, bound)
        spat = jnp.zeros((segments, seg_len), jnp.int32)
        for ch in range(c):
            hit = (bound[:, ch:ch + 1] == lane).astype(jnp.int32)  # (S, L)
            spat = spat + hit if spatial_thinning else spat | hit
        if spatial_thinning:
            spat = (spat >= spatial_threshold).astype(jnp.int32)
        return counts + spat

    counts = jax.lax.fori_loop(
        0, window, cycle, jnp.zeros((segments, seg_len), jnp.int32))
    out_ref[0, 0] = (counts >= temporal_threshold).astype(jnp.int32)


def encoder_pallas(positions: jax.Array, elec: jax.Array, *, window: int,
                   segments: int, seg_len: int, temporal_threshold: int,
                   spatial_thinning: bool = False, spatial_threshold: int = 1,
                   interpret: bool = True) -> jax.Array:
    """positions: (B, F, window, C, S) uint8 bound-input item positions
    elec: (C, S) uint8 electrode positions
    returns: (B, F, D // 32) uint32 packed frame HVs."""
    b, f, w, c, s = positions.shape
    assert w == window and s == segments
    dim = segments * seg_len
    kernel = functools.partial(
        _encoder_kernel, window=window, segments=segments, seg_len=seg_len,
        temporal_threshold=temporal_threshold,
        spatial_thinning=spatial_thinning, spatial_threshold=spatial_threshold)
    # segments on sublanes, channels on lanes; positions widened to int32
    pos = positions.astype(jnp.int32).swapaxes(-1, -2)          # (B,F,w,S,C)
    bits = pl.pallas_call(
        kernel,
        grid=(b, f),
        in_specs=[
            pl.BlockSpec((1, 1, window, s, c), lambda i, j: (i, j, 0, 0, 0)),
            pl.BlockSpec((s, c), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, s, seg_len), lambda i, j: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, f, s, seg_len), jnp.int32),
        interpret=interpret,
    )(pos, elec.astype(jnp.int32).T)
    return hv.pack_bits(bits.reshape(b, f, dim))
