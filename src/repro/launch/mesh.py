"""Production mesh construction.

Defined as FUNCTIONS (not module-level constants) so importing this module
never touches jax device state — required because the dry-run must set
XLA_FLAGS before any jax initialization.

Production topology (TPU v5e pods):
  single-pod  (data=16, model=16)            = 256 chips
  multi-pod   (pod=2, data=16, model=16)     = 512 chips
The `pod` axis is the slow (DCI) axis: only data-parallel gradient
reduction crosses it (optionally int8-compressed, optim/compress.py).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...], *,
              devices=None):
    """The one mesh constructor: every axis ``Auto``, so sharding follows
    the placed operands and ``with_sharding_constraint`` (runtime/sharding.py)
    instead of JAX's default ``Explicit`` axes, under which the fleet's
    owner gathers and the constraints are type errors."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(shape),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def parse_mesh(s: str | None):
    """``--mesh`` flag value (``4``, ``2x2``, ``2x16x16``; ``none``) -> mesh."""
    if not s or s == "none":
        return None
    dims = tuple(int(x) for x in s.split("x"))
    axes = {1: ("data",), 2: ("data", "model"), 3: ("pod", "data", "model")}[len(dims)]
    return make_mesh(dims, axes)


def host_device_count_or_die(n: int):
    have = len(jax.devices())
    if have < n:
        raise RuntimeError(
            f"mesh needs {n} devices but jax sees {have}; the dry-run must "
            f"set XLA_FLAGS=--xla_force_host_platform_device_count={n} "
            f"BEFORE importing jax (see launch/dryrun.py)")
