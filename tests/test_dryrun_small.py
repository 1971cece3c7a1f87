"""Small-mesh dry-run smoke tests (subprocess: needs >1 fake device, while
the main test process must stay at 1 device).

These prove the sharding specs lower+compile on a mesh for one cell per step
kind; the full 512-device production sweep runs via launch/dryrun.py and is
recorded in EXPERIMENTS.md.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_py(code: str, devices: int = 8, timeout: int = 900):
    env = dict(os.environ,
               PYTHONPATH=os.path.join(REPO, "src"),
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    return subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, env=env, cwd=REPO,
                          timeout=timeout)


@pytest.mark.slow
@pytest.mark.parametrize("kind", ["train", "prefill", "decode"])
def test_small_mesh_lower_compile(kind):
    code = f"""
    import jax
    import jax.numpy as jnp
    from repro.configs.registry import get_config
    from repro.data import lm as lmdata
    from repro.models import params as pmod
    from repro.optim import adamw
    from repro.runtime import steps as steps_mod

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 4), ("data", "model"))
    cfg = get_config("qwen3-0.6b").reduced(d_model=256, n_heads=4,
                                           n_kv_heads=2, head_dim=64,
                                           vocab=1024, d_ff=512)
    kind = "{kind}"
    if kind == "train":
        shape = lmdata.ShapeSpec("t", 64, 4, "train")
        specs = lmdata.input_specs(cfg, shape)
        jitted, ctx, spec = steps_mod.jit_train_step(
            cfg, adamw.OptConfig(), mesh, specs)
        pa = pmod.abstract(spec, jnp.float32)
        mv = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32),
                          spec, is_leaf=lambda s: isinstance(s, pmod.ParamSpec))
        opt = dict(m=mv, v=mv, step=jax.ShapeDtypeStruct((), jnp.int32))
        lowered = jitted.lower(pa, opt, specs)
    elif kind == "prefill":
        shape = lmdata.ShapeSpec("p", 64, 4, "prefill")
        specs = lmdata.input_specs(cfg, shape)
        jitted, ctx, spec = steps_mod.jit_prefill(cfg, mesh, specs, 64)
        pa = pmod.abstract(spec, jnp.float32)
        lowered = jitted.lower(pa, specs)
    else:
        shape = lmdata.ShapeSpec("d", 64, 4, "decode")
        specs = lmdata.input_specs(cfg, shape)
        jitted, ctx, spec = steps_mod.jit_decode_step(cfg, mesh, specs)
        pa = pmod.abstract(spec, jnp.float32)
        lowered = jitted.lower(pa, specs["tokens"], specs["caches"], specs["pos"])
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    assert mem is not None
    print("OK", kind, int(mem.temp_size_in_bytes))
    """
    r = _run_py(code)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK " + kind in r.stdout


@pytest.mark.slow
def test_multipod_axis_shards():
    """The 3-axis (pod, data, model) mesh lowers with the pod axis active."""
    code = """
    import jax
    import jax.numpy as jnp
    from repro.configs.registry import get_config
    from repro.data import lm as lmdata
    from repro.models import params as pmod
    from repro.optim import adamw
    from repro.runtime import steps as steps_mod

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    cfg = get_config("qwen3-0.6b").reduced(d_model=256, n_heads=4,
                                           n_kv_heads=2, head_dim=64,
                                           vocab=1024, d_ff=512)
    shape = lmdata.ShapeSpec("t", 64, 4, "train")
    specs = lmdata.input_specs(cfg, shape)
    jitted, ctx, spec = steps_mod.jit_train_step(cfg, adamw.OptConfig(), mesh, specs)
    pa = pmod.abstract(spec, jnp.float32)
    mv = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32),
                      spec, is_leaf=lambda s: isinstance(s, pmod.ParamSpec))
    opt = dict(m=mv, v=mv, step=jax.ShapeDtypeStruct((), jnp.int32))
    compiled = jitted.lower(pa, opt, specs).compile()
    txt = compiled.as_text()
    assert ("all-reduce" in txt) or ("all-gather" in txt), "no cross-device collectives?"
    print("OK multipod")
    """
    r = _run_py(code)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK multipod" in r.stdout


def test_sweep_artifacts_when_present():
    """If the full 512-device sweep has produced artifacts, every non-skipped
    cell must be status=ok (this validates the committed sweep results)."""
    art = os.path.join(REPO, "artifacts", "dryrun")
    files = [f for f in os.listdir(art)] if os.path.isdir(art) else []
    if len(files) < 10:
        pytest.skip("full sweep not run in this environment")
    bad = []
    for f in files:
        with open(os.path.join(art, f)) as fh:
            rec = json.load(fh)
        if rec.get("status") not in ("ok", "skipped"):
            bad.append((f, rec.get("error", "")[:100]))
    assert not bad, bad
