"""Tests for the HLO-graph cost analyzer (runtime/hlo_cost.py)."""

import jax
import jax.numpy as jnp
import pytest

from repro.runtime.hlo_cost import analyze_hlo, _shape_numel_bytes

jax.config.update("jax_platform_name", "cpu")


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def test_shape_parse():
    assert _shape_numel_bytes("f32[4,8]{1,0}") == (32.0, 128.0)
    assert _shape_numel_bytes("bf16[10]") == (10.0, 20.0)
    n, b = _shape_numel_bytes("(s32[], f32[2,2]{1,0})")
    assert n == 5.0 and b == 20.0


def test_dot_flops_exact():
    a = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    b = jax.ShapeDtypeStruct((256, 64), jnp.float32)
    txt = _compiled_text(lambda x, y: x @ y, a, b)
    r = analyze_hlo(txt)
    assert r["flops"] == pytest.approx(2 * 128 * 256 * 64, rel=0.01)


def test_scan_trip_count_multiplied():
    """The whole point: a matmul inside a 10-step scan must count 10x."""
    a = jax.ShapeDtypeStruct((64, 64), jnp.float32)

    def ten_matmuls(x):
        def step(c, _):
            return c @ c * 0.5, None
        out, _ = jax.lax.scan(step, x, None, length=10)
        return out

    r1 = analyze_hlo(_compiled_text(ten_matmuls, a))
    flops_one = 2 * 64 * 64 * 64
    assert r1["flops"] >= 9 * flops_one, r1["flops"]
    assert r1["flops"] <= 12 * flops_one, r1["flops"]


def test_nested_scan_trip_counts_compose():
    a = jax.ShapeDtypeStruct((32, 32), jnp.float32)

    def nested(x):
        def inner(c, _):
            return c @ c * 0.1, None

        def outer(c, _):
            c, _ = jax.lax.scan(inner, c, None, length=4)
            return c, None

        out, _ = jax.lax.scan(outer, x, None, length=3)
        return out

    r = analyze_hlo(_compiled_text(nested, a))
    flops_one = 2 * 32 * 32 * 32
    assert r["flops"] >= 11 * flops_one   # 3*4 = 12 matmuls (tolerance 1)
    assert r["flops"] <= 14 * flops_one


def test_bytes_positive_and_scale():
    a = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    r_small = analyze_hlo(_compiled_text(lambda x: x @ x, a))
    b = jax.ShapeDtypeStruct((512, 512), jnp.float32)
    r_big = analyze_hlo(_compiled_text(lambda x: x @ x, b))
    assert r_big["bytes"] > 3 * r_small["bytes"]


def test_collectives_counted_in_sharded_module(tmp_path):
    """Collectives inside a scan body count trip-count times (subprocess
    with 4 fake devices so the main process keeps 1)."""
    import os
    import subprocess
    import sys
    import textwrap
    code = textwrap.dedent("""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.runtime.hlo_cost import analyze_hlo
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ("d",))
        sh = NamedSharding(mesh, P(None, "d"))

        def f(w, x):
            def step(c, _):
                y = jnp.einsum("ij,kj->ik", c, w)   # contract sharded dim
                return jax.lax.with_sharding_constraint(y, sh), None
            out, _ = jax.lax.scan(step, x, None, length=6)
            return out

        wa = jax.ShapeDtypeStruct((64, 64), jnp.float32)
        xa = jax.ShapeDtypeStruct((64, 64), jnp.float32)
        c = jax.jit(f, in_shardings=(sh, sh), out_shardings=sh).lower(wa, xa).compile()
        r = analyze_hlo(c.as_text())
        total = sum(v for k, v in r["collectives"].items() if k != "n_ops")
        assert total > 0, r
        print("COLL_OK", total)
    """)
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(os.path.dirname(
                   os.path.abspath(__file__))), "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "COLL_OK" in r.stdout
