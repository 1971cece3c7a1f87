"""Jit'd public entry point for LBP preprocessing with time chunking."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.common import use_interpret
from repro.kernels.lbp.kernel import lbp_pallas
from repro.kernels.lbp.ref import lbp_ref

# one (chunk+bits, C) f32 tile, lane-padded to 128 and double-buffered,
# stays well inside the 16 MiB scoped VMEM of a v5e (2 MiB at C <= 128)
MAX_CHUNK_T = 4096


@functools.partial(jax.jit, static_argnames=("bits", "use_kernel"))
def lbp_codes(x: jax.Array, *, bits: int = 6, use_kernel: bool = True) -> jax.Array:
    """x: (B, T, C) raw signal -> (B, T - bits, C) uint8 LBP codes.

    Long time axes are processed in overlapping chunks (halo = `bits`
    samples) outside the kernel, so each pallas_call sees a bounded tile."""
    if not use_kernel:
        return lbp_ref(x, bits=bits)
    b, t, c = x.shape
    t_out = t - bits
    if t_out <= MAX_CHUNK_T:
        return lbp_pallas(x, bits=bits, interpret=use_interpret())
    chunks = []
    for start in range(0, t_out, MAX_CHUNK_T):
        size = min(MAX_CHUNK_T, t_out - start)
        xin = jax.lax.dynamic_slice_in_dim(x, start, size + bits, axis=1)
        chunks.append(lbp_pallas(xin, bits=bits, interpret=use_interpret()))
    return jnp.concatenate(chunks, axis=1)
