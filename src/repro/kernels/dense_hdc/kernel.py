"""Fused dense-HDC encoder kernel (the paper's comparison baseline [1]).

XOR binding + spatial majority (over channels) + temporal majority (over the
window), all in VMEM; one grid step emits one packed time-frame HV.  This is
the bit-packed TPU analogue of the dense accelerator whose switching energy
the paper beats by 7.5x — and our §Perf baseline for the sparse/dense
byte-traffic comparison.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _dense_kernel(item_ref, elec_ref, out_ref, *, window: int, channels: int,
                  dim: int):
    elec = elec_ref[...]                                         # (C, W)
    w = dim // 32
    bit = jax.lax.broadcasted_iota(jnp.uint32, (32, w), 0)

    def cycle(j, tcounts):
        bound = jnp.bitwise_xor(item_ref[0, 0, j], elec)         # (C, W)
        # per-bit channel counts, (bit, word) layout: row c of the bound
        # HVs broadcast over 32 sublanes, one bit position each
        scounts = jnp.zeros((32, w), jnp.int32)
        for c in range(channels):
            scounts = scounts + ((bound[c:c + 1] >> bit)
                                 & jnp.uint32(1)).astype(jnp.int32)
        return tcounts + (scounts * 2 > channels).astype(jnp.int32)  # majority

    tcounts = jax.lax.fori_loop(
        0, window, cycle, jnp.zeros((32, w), jnp.int32))
    keep = (tcounts * 2 > window).astype(jnp.uint32)
    # disjoint bits: the int32 sum is the OR (Mosaic reduces no unsigned)
    words = jnp.sum((keep << bit).astype(jnp.int32), axis=0, keepdims=True,
                    dtype=jnp.int32)
    out_ref[0, 0] = words.astype(jnp.uint32)


def dense_encoder_pallas(item_hvs: jax.Array, elec: jax.Array, *, window: int,
                         dim: int, interpret: bool = True) -> jax.Array:
    """item_hvs: (B, F, window, C, W) uint32 looked-up item HVs
    elec: (C, W) uint32 -> (B, F, W) uint32 packed frame HVs."""
    b, f, w, c, words = item_hvs.shape
    kernel = functools.partial(_dense_kernel, window=window, channels=c, dim=dim)
    return pl.pallas_call(
        kernel,
        grid=(b, f),
        in_specs=[
            pl.BlockSpec((1, 1, window, c, words), lambda i, j: (i, j, 0, 0, 0)),
            pl.BlockSpec((c, words), lambda i, j: (0, 0)),
        ],
        # (B, F, 1, W): a block's last two dims must span the array's
        out_specs=pl.BlockSpec((1, 1, 1, words), lambda i, j: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, f, 1, words), jnp.uint32),
        interpret=interpret,
    )(item_hvs, elec)[:, :, 0]
