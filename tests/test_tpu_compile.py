"""Compiles for a TPU v5e that is described, not attached (no chip needed).

The Pallas kernels and the fleet step at the paper's geometry (64
electrodes, 64 LBP codes, D=1024, 256-cycle frames, 1,024 sessions) go
through Mosaic and XLA:TPU here exactly as they would on the chip, so a
block shape, a lowering or a VMEM budget the chip's compiler refuses fails
in CI instead of on the chip.  Interpret mode (tests/test_kernels.py)
checks what the kernels compute; this file checks that they compile.

The topology is described inside a module fixture, never at import: only
one process at a time may hold the TPU compiler library, and every test
worker imports this file.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core.pipeline import HDCConfig
from repro.kernels.dense_hdc.kernel import dense_encoder_pallas
from repro.kernels.hdc_am.kernel import am_search_pallas
from repro.kernels.hdc_encoder.kernel import encoder_pallas
from repro.kernels.hdc_fleet import ops as fleet_ops
from repro.kernels.hdc_fleet.kernel import fleet_counts_pallas
from repro.kernels.lbp.kernel import lbp_pallas
from repro.launch.mesh import make_mesh
from repro.runtime import sharding as shd
from repro.runtime.aot import persistent_cache_off
from repro.serve.fleet import FleetState, _fleet_step

CFG = HDCConfig()                      # the paper's geometry
S, PATIENTS, T = 1024, 16, CFG.window  # sessions, table rows, chunk cycles
C, K, D, W = CFG.channels, CFG.codes, CFG.dim, CFG.words


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        mp.undo()
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without one: keep the cache out of these compiles
    with persistent_cache_off():
        yield topo
    mp.undo()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    """Compile for the described chip; return the optimized HLO text."""
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("masked", [False, True])
@pytest.mark.parametrize("mode", ["or", "thin", "majority"])
def test_fleet_kernel_compiles(one_chip, mode, masked):
    args = [_sds((PATIENTS, C, K, W), jnp.uint32, one_chip),
            _sds((S,), jnp.int32, one_chip),
            _sds((S, T, C), jnp.uint8, one_chip),
            _sds((S, 2, T // 32), jnp.uint32, one_chip)]
    if masked:
        args.append(_sds((S, C), jnp.uint32, one_chip))

    def f(tables, owner, codes, tm, chan_mask=None):
        return fleet_counts_pallas(tables, owner, codes, tm, mode=mode, dim=D,
                                   threshold=2, chan_mask=chan_mask,
                                   interpret=False)

    assert "tpu_custom_call" in _compile(f, *args)


def _step_args(sharding, *, masked: bool, batch=None):
    """Abstract operands of one 1,024-session fleet step; ``batch`` is the
    sharding of the session-axis leaves (default: ``sharding``)."""
    b = batch or (lambda nd: sharding)
    n = CFG.n_classes
    state = FleetState(
        counts=_sds((S, D), jnp.int32, b(2)),
        filled=_sds((S,), jnp.int32, b(1)),
        frame_index=_sds((S,), jnp.int32, b(1)),
        class_rows=_sds((S, n, W), jnp.uint32, b(3)),
        am_counts=_sds((S, n, D), jnp.int32, b(3)),
        am_n=_sds((S, n), jnp.int32, b(2)),
        last_frame=_sds((S, W), jnp.uint32, b(2)),
        last_scores=_sds((S, n), jnp.int32, b(2)),
        has_frame=_sds((S,), jnp.int32, b(1)))
    args = [state, _sds((PATIENTS, C, K, W), jnp.uint32, sharding),
            _sds((S,), jnp.int32, b(1)), _sds((S,), jnp.int32, b(1)),
            _sds((S, T, C), jnp.uint8, b(3)), _sds((S,), jnp.int32, b(1))]
    if masked:
        args += [None, None, _sds((S, C), jnp.uint8, b(2))]
    return args


@pytest.mark.parametrize("backend,masked", [("jnp", False),
                                            ("pallas", False),
                                            ("pallas", True)])
def test_fleet_step_compiles(one_chip, monkeypatch, backend, masked):
    """The whole jitted step as StreamingFleet builds it; off the chip the
    ops wrapper would pick interpret mode, so the test asks for Mosaic."""
    monkeypatch.setattr(fleet_ops, "use_interpret", lambda: False)
    step = functools.partial(_fleet_step, cfg=CFG, ctx=shd.make_ctx(None),
                             use_kernel=backend == "pallas", masked=masked)
    text = jax.jit(step, donate_argnums=(0,)).lower(
        *_step_args(one_chip, masked=masked)).compile().as_text()
    assert ("tpu_custom_call" in text) == (backend == "pallas")


def test_sharded_fleet_step_compiles(topo):
    """The mesh-placed step (StreamingFleet(mesh=...)) over four chips:
    session-axis state splits on the data axis, the table bank
    replicates."""
    mesh = make_mesh((4,), ("data",), devices=topo.devices)
    ctx = shd.make_ctx(mesh)
    repl = NamedSharding(mesh, P())

    def batch(ndim):
        return NamedSharding(mesh, P("data", *([None] * (ndim - 1))))

    step = functools.partial(_fleet_step, cfg=CFG, ctx=ctx, use_kernel=False)
    compiled = jax.jit(step, donate_argnums=(0,)).lower(
        *_step_args(repl, masked=False, batch=batch)).compile()
    counts = compiled.output_shardings[0].counts
    assert counts.spec[0] == "data" and len(counts.device_set) == 4


@pytest.mark.parametrize("thinning", [False, True])
def test_encoder_kernel_compiles(one_chip, thinning):
    f = functools.partial(
        encoder_pallas, window=CFG.window, segments=CFG.segments,
        seg_len=CFG.seg_len, temporal_threshold=CFG.temporal_threshold,
        spatial_thinning=thinning, spatial_threshold=CFG.spatial_threshold,
        interpret=False)
    assert "tpu_custom_call" in _compile(
        f, _sds((2, 4, CFG.window, C, CFG.segments), jnp.uint8, one_chip),
        _sds((C, CFG.segments), jnp.uint8, one_chip))


def test_dense_kernel_compiles(one_chip):
    f = functools.partial(dense_encoder_pallas, window=CFG.window, dim=D,
                          interpret=False)
    assert "tpu_custom_call" in _compile(
        f, _sds((2, 4, CFG.window, C, W), jnp.uint32, one_chip),
        _sds((C, W), jnp.uint32, one_chip))


@pytest.mark.parametrize("mode", ["overlap", "hamming"])
def test_am_kernel_compiles(one_chip, mode):
    f = functools.partial(am_search_pallas, mode=mode, dim=D, interpret=False)
    assert "tpu_custom_call" in _compile(
        f, _sds((S, W), jnp.uint32, one_chip),
        _sds((CFG.n_classes, W), jnp.uint32, one_chip))


def test_lbp_kernel_compiles(one_chip):
    from repro.kernels.lbp.ops import MAX_CHUNK_T

    f = functools.partial(lbp_pallas, bits=CFG.lbp_bits, interpret=False)
    assert "tpu_custom_call" in _compile(
        f, _sds((2, MAX_CHUNK_T + CFG.lbp_bits, C), jnp.float32, one_chip))
