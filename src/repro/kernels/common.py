"""Shared helpers for the Pallas TPU kernels.

All kernels target TPU (pl.pallas_call + explicit BlockSpec VMEM tiling) and
are validated on CPU with ``interpret=True`` (the kernel body executes in
Python with identical semantics).  ``use_interpret()`` selects the mode from
the local backend so the same ``ops.py`` entry points work everywhere.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def use_interpret() -> bool:
    """True when the default backend is not a TPU — the CPU test path
    (``JAX_PLATFORMS=cpu``): run kernels in Pallas interpret mode.  On a TPU
    this returns False and the Mosaic-compiled kernel runs; ``chip_smoke.py``
    checks that it did (``tpu_custom_call`` in the compiled step)."""
    return jax.default_backend() != "tpu"


def pack_words_in_kernel(bits: jax.Array) -> jax.Array:
    """(D,) {0,1} -> (D//32,) uint32 inside a kernel body (iota + shift, no
    gather/scatter so it vectorizes on the VPU)."""
    d = bits.shape[-1]
    w = d // 32
    b = bits.reshape(w, 32).astype(jnp.uint32)
    shifts = jax.lax.broadcasted_iota(jnp.uint32, (w, 32), 1)
    return jnp.sum(b << shifts, axis=1, dtype=jnp.uint32)


def unpack_words_in_kernel(words: jax.Array, dim: int) -> jax.Array:
    """(..., W) uint32 -> (..., W*32) {0,1} uint8 inside a kernel body."""
    w = words.shape[-1]
    shifts = jax.lax.broadcasted_iota(
        jnp.uint32, (*words.shape, 32), words.ndim)
    bits = (words[..., None] >> shifts) & jnp.uint32(1)
    return bits.reshape(*words.shape[:-1], w * 32)[..., :dim].astype(jnp.uint8)
