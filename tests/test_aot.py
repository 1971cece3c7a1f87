"""AOT deploy artifacts (runtime/aot.py): fingerprint/key invalidation,
artifact roundtrips, warmed-fleet and prewarm-engine bit-exactness with the
JIT path, checkpoint-recorded artifacts, and stale-artifact JIT fallback."""

import gc
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.pipeline import HDCConfig, HDCPipeline, VARIANTS
from repro.reliability.faults import FaultConfig
from repro.runtime import aot as aot_mod
from repro.serve.engine import ServingEngine
from repro.serve.fleet import StreamingFleet

jax.config.update("jax_platform_name", "cpu")

# tiny geometry keeps every compile in milliseconds (same as test_fleet)
DIM, SEGMENTS, CHANNELS, WINDOW = 256, 8, 8, 32


def _cfg(variant: str, **overrides) -> HDCConfig:
    base = dict(dim=DIM, segments=SEGMENTS, channels=CHANNELS, window=WINDOW,
                variant=variant, spatial_threshold=1, temporal_threshold=4)
    base.update(overrides)
    return HDCConfig(**base)


def _trained(variant: str, seed: int, **overrides) -> HDCPipeline:
    rng = np.random.default_rng(seed)
    cfg = _cfg(variant, **overrides)
    codes = jnp.asarray(rng.integers(0, 64, (2, 4 * WINDOW, CHANNELS), np.uint8))
    frames = codes.shape[1] // cfg.window
    labels = np.asarray(rng.integers(0, 2, (2, frames), np.int32))
    labels[0, :2] = (0, 1)  # every class needs >= 1 example
    pipe = HDCPipeline.init(jax.random.PRNGKey(seed), cfg)
    return pipe.train_one_shot(codes, jnp.asarray(labels))


def _chunks(seed: int, n: int, t: int = WINDOW) -> list[np.ndarray]:
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 64, (t, CHANNELS), np.uint8) for _ in range(n)]


def _decisions(out) -> list[tuple]:
    return [(d.frame_index, d.prediction, tuple(np.asarray(d.scores)))
            for per_session in out for d in per_session]


# ---------------------------------------------------------------------------
# validity key: kernel fingerprint + artifact key + staleness
# ---------------------------------------------------------------------------

def test_kernel_fingerprint_stable_and_source_sensitive(tmp_path):
    root = tmp_path / "src"
    (root / "kernels").mkdir(parents=True)
    (root / "kernels" / "k.py").write_text("def f(): return 1\n")
    fp1 = aot_mod.kernel_fingerprint(root=str(root))
    assert fp1 == aot_mod.kernel_fingerprint(root=str(root))  # deterministic
    # non-.py files do not participate
    (root / "kernels" / "notes.md").write_text("irrelevant")
    assert aot_mod.kernel_fingerprint(root=str(root)) == fp1
    # kernel source changes MUST change the fingerprint
    (root / "kernels" / "k.py").write_text("def f(): return 2\n")
    assert aot_mod.kernel_fingerprint(root=str(root)) != fp1


def test_artifact_key_and_stale_fields():
    key = aot_mod.artifact_key()
    assert set(key) == {"jax", "device", "kernels"}
    assert aot_mod.stale_fields(key, dict(key)) == {}
    tampered = dict(key, jax="0.0.0-stale")
    bad = aot_mod.stale_fields(tampered, key)
    assert list(bad) == ["jax"]
    assert bad["jax"] == ("0.0.0-stale", key["jax"])


# ---------------------------------------------------------------------------
# fleet warmup + artifact roundtrip: bit-exact, compile_count honest
# ---------------------------------------------------------------------------

def test_warmup_precompiles_and_matches_jit(no_recompiles):
    pipe = _trained("sparse_compim", seed=0)
    jit_fleet = StreamingFleet({"p": pipe}, ["p"] * 4, buckets=(WINDOW,))
    warm = StreamingFleet({"p": pipe}, ["p"] * 4, buckets=(WINDOW,))
    stats = warm.warmup()  # no artifact: pre-lower + compile
    assert stats["compiled"] > 0 and stats["loaded"] == 0
    assert warm.aot_count == stats["compiled"]
    chunks = _chunks(7, 4)
    want = _decisions(jit_fleet.push(chunks))
    # pushes run through the installed executables: zero compiles on top
    # (a shape miss would fall back to jit and trip the sanitizer)
    with no_recompiles():
        got = warm.push(chunks)
    assert _decisions(got) == want


def test_warmup_freezes_the_heap_it_built():
    """Warm-up moves what it built (the executables among it) out of the
    cyclic collector's view, so a full collection while serving walks only
    what serving made; objects made afterwards stay collectable."""
    pipe = _trained("sparse_compim", seed=0)
    fleet = StreamingFleet({"p": pipe}, ["p"] * 4, buckets=(WINDOW,))
    gc.unfreeze()
    try:
        fleet.warmup()
        assert gc.get_freeze_count() > 0
        assert id(fleet._exec) not in {id(o) for o in gc.get_objects()}
        later = {"made": ["after warm-up"]}
        assert id(later) in {id(o) for o in gc.get_objects()}
    finally:
        gc.unfreeze()


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("variant", VARIANTS)
def test_artifact_roundtrip_bitexact(tmp_path, variant, backend,
                                     no_recompiles):
    """save_aot -> load_artifact -> warmup(aot=...) must load (not compile)
    every executable and reproduce the JIT fleet bit-exactly, for every
    datapath variant on both backends."""
    pipes = {"a": _trained(variant, seed=0),
             "b": _trained(variant, seed=1, temporal_threshold=6)}
    owners = ["a", "b", "a"]
    kw = dict(buckets=(WINDOW,), backend=backend)
    StreamingFleet(pipes, owners, **kw).save_aot(str(tmp_path / "aot"))

    art = aot_mod.load_artifact(str(tmp_path / "aot"))
    assert art is not None and art.names
    warm = StreamingFleet(pipes, owners, **kw)
    stats = warm.warmup(aot=art)
    assert stats["loaded"] > 0 and stats["compiled"] == 0
    # the AOT executables ARE the compile count: jit cache stays cold but
    # the bucketed-compile guard must not pass vacuously at 0
    assert warm.aot_count == stats["loaded"]

    jit_fleet = StreamingFleet(pipes, owners, **kw)
    chunks = _chunks(11, len(owners))
    want = _decisions(jit_fleet.push(chunks))
    # the loaded executables serve every push: zero XLA compiles
    with no_recompiles():
        got = warm.push(chunks)
    assert _decisions(got) == want


class _Failing:
    """A warmed executable whose call fails at run time."""

    def __init__(self, compiled):
        self._compiled = compiled

    def __getattr__(self, name):
        return getattr(self._compiled, name)

    def __call__(self, *args):
        raise RuntimeError("executable failed")


def test_warmed_executable_checked_before_call():
    """A warmed step executable that does not accept the operands (here:
    the other bucket's) is dropped before the call and the jitted step
    serves the push bit-exactly; one that accepts them but fails at run time
    raises — its donated state is gone, so nothing re-runs it under JIT."""
    pipe = _trained("sparse_compim", seed=2)
    kw = dict(buckets=(WINDOW, 2 * WINDOW))
    chunks = _chunks(3, 2)
    want = _decisions(StreamingFleet({"p": pipe}, ["p"] * 2, **kw)
                      .push(chunks))

    warm = StreamingFleet({"p": pipe}, ["p"] * 2, **kw)
    warm.warmup()
    small, big = sorted(warm._exec, key=lambda key: key[2])
    warm._exec[small] = warm._exec[big]
    assert _decisions(warm.push(chunks)) == want
    assert small not in warm._exec and big in warm._exec

    failing = StreamingFleet({"p": pipe}, ["p"] * 2, **kw)
    failing.warmup()
    failing._exec[small] = _Failing(failing._exec[small])
    with pytest.raises(RuntimeError, match="executable failed"):
        failing.push(chunks)


def test_entries_ship_xla_executables(tmp_path):
    """Every exported entry also carries a serialized PjRt executable, and
    the load path hands it back without an XLA recompile; a signature
    mismatch falls through to None (callers then take the StableHLO tier)."""
    pipe = _trained("sparse_compim", seed=4)
    StreamingFleet({"p": pipe}, ["p"] * 2,
                   buckets=(WINDOW,)).save_aot(str(tmp_path / "aot"))
    art = aot_mod.load_artifact(str(tmp_path / "aot"))
    recs = art.manifest["entries"]
    assert recs and all(r.get("executable") for r in recs if r["exported"])
    name = recs[0]["name"]
    loaded = art.load_executable(name)
    assert loaded is not None
    good = tuple(jax.tree_util.tree_leaves(loaded.args_info))
    bad = tuple(jax.ShapeDtypeStruct((s.shape[0] + 1,) + tuple(s.shape[1:]),
                                     s.dtype) for s in good)
    assert art.load_executable(name, good) is not None
    assert art.load_executable(name, bad) is None


def test_artifact_rebuilt_under_warm_persistent_cache(tmp_path):
    """A second ``save_aot`` whose compiles the persistent compilation
    cache could serve still ships executables that load and run bit-exactly
    (a cache-served executable does not re-serialize into a runnable
    one)."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    pipe = _trained("sparse_compim", seed=5)
    kw = dict(buckets=(WINDOW,))
    chunks = _chunks(13, 2)
    prev = (jax.config.jax_compilation_cache_dir,
            jax.config.jax_persistent_cache_min_compile_time_secs)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cc.reset_cache()
    try:
        for name in ("aot1", "aot2"):
            jax.clear_caches()
            StreamingFleet({"p": pipe}, ["p"] * 2, **kw).save_aot(
                str(tmp_path / name))
        warm = StreamingFleet({"p": pipe}, ["p"] * 2, **kw)
        stats = warm.warmup(aot=aot_mod.load_artifact(str(tmp_path / "aot2")))
        got = _decisions(warm.push(chunks))
    finally:
        jax.config.update("jax_compilation_cache_dir", prev[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          prev[1])
        cc.reset_cache()
    assert stats["loaded"] > 0 and stats["compiled"] == 0
    want = StreamingFleet({"p": pipe}, ["p"] * 2, **kw).push(chunks)
    assert got == _decisions(want)


def test_faulted_fleet_artifact_roundtrip(tmp_path):
    """The faulted step (fault plan + SECDED ECC) exports and reloads too,
    with identical decisions AND identical ECC telemetry."""
    pipe = _trained("sparse_compim", seed=2)
    faults = FaultConfig(am=1e-2, seed=9, ecc="secded")
    kw = dict(buckets=(WINDOW,), faults=faults)
    StreamingFleet({"p": pipe}, ["p"] * 3, **kw).save_aot(str(tmp_path / "aot"))

    art = aot_mod.load_artifact(str(tmp_path / "aot"))
    warm = StreamingFleet({"p": pipe}, ["p"] * 3, **kw)
    assert warm.warmup(aot=art)["compiled"] == 0
    jit_fleet = StreamingFleet({"p": pipe}, ["p"] * 3, **kw)
    chunks = _chunks(13, 3)
    assert _decisions(warm.push(chunks)) == _decisions(jit_fleet.push(chunks))
    np.testing.assert_array_equal(warm.ecc_stats, jit_fleet.ecc_stats)


def test_stale_artifact_refuses_to_load(tmp_path):
    pipe = _trained("sparse_compim", seed=0)
    StreamingFleet({"p": pipe}, ["p"], buckets=(WINDOW,)).save_aot(
        str(tmp_path / "aot"))
    mpath = tmp_path / "aot" / aot_mod.MANIFEST
    manifest = json.loads(mpath.read_text())
    manifest["key"]["kernels"] = "deadbeefdeadbeef"
    mpath.write_text(json.dumps(manifest))
    with pytest.warns(UserWarning, match="kernels"):
        assert aot_mod.load_artifact(str(tmp_path / "aot")) is None


# ---------------------------------------------------------------------------
# checkpoint-recorded artifacts: from_artifact restore + stale JIT fallback
# ---------------------------------------------------------------------------

def _ckpt_manifest_path(root) -> str:
    steps = sorted(os.listdir(root))
    return os.path.join(root, steps[-1], "manifest.json")


def test_checkpoint_records_aot_entry_and_from_artifact_restores(tmp_path):
    pipes = {"p": _trained("sparse_compim", seed=4)}
    fleet = StreamingFleet(pipes, ["p"] * 3, buckets=(WINDOW,))
    chunks = _chunks(17, 3)
    fleet.push(chunks)  # advance state so restore is non-trivial
    root, aot_dir = str(tmp_path / "ckpt"), str(tmp_path / "aot")
    fleet.save(root, aot_dir=aot_dir)

    manifest = json.loads(open(_ckpt_manifest_path(root)).read())
    assert manifest["aot"]["path"] == aot_dir
    assert manifest["aot"]["key"] == aot_mod.artifact_key()

    restored = StreamingFleet.from_artifact(pipes, ["p"] * 3, root,
                                            buckets=(WINDOW,))
    assert restored.aot_count > 0  # warmed from the recorded artifact
    more = _chunks(19, 3)
    assert _decisions(restored.push(more)) == _decisions(fleet.push(more))


def test_stale_ckpt_aot_entry_falls_back_to_jit(tmp_path):
    """A checkpoint whose recorded AOT key no longer matches (here: written
    by another jax version) must warn, skip the artifact, and restore via
    plain JIT — with identical decisions."""
    pipes = {"p": _trained("sparse_compim", seed=4)}
    fleet = StreamingFleet(pipes, ["p"] * 2, buckets=(WINDOW,))
    chunks = _chunks(23, 2)
    fleet.push(chunks)
    root = str(tmp_path / "ckpt")
    fleet.save(root, aot_dir=str(tmp_path / "aot"))

    mpath = _ckpt_manifest_path(root)
    manifest = json.loads(open(mpath).read())
    manifest["aot"]["key"]["jax"] = "0.0.0-stale"
    with open(mpath, "w") as f:
        json.dump(manifest, f)

    with pytest.warns(UserWarning, match="stale"):
        restored = StreamingFleet.from_artifact(pipes, ["p"] * 2, root,
                                                buckets=(WINDOW,))
    more = _chunks(29, 2)
    assert _decisions(restored.push(more)) == _decisions(fleet.push(more))


# ---------------------------------------------------------------------------
# engine prewarm
# ---------------------------------------------------------------------------

def test_engine_prewarm_artifact_bitexact(tmp_path):
    pipes = {"a": _trained("sparse_compim", seed=0),
             "b": _trained("sparse_compim", seed=1)}
    t = 2 * WINDOW
    builder = ServingEngine(pipes)
    aot_mod.save_artifact(str(tmp_path / "aot"),
                          builder.aot_entries([1, 2, 4], t))

    art = aot_mod.load_artifact(str(tmp_path / "aot"))
    warm = ServingEngine(pipes)
    stats = warm.prewarm(4, t, aot=art)
    assert stats["loaded"] > 0 and stats["compiled"] == 0
    assert warm.aot_count == stats["loaded"]

    cold = ServingEngine(pipes)
    rng = np.random.default_rng(31)
    reqs = [(pid, jnp.asarray(rng.integers(0, 64, (t, CHANNELS), np.uint8)))
            for pid in ("a", "b", "a")]
    got = warm.serve(reqs)
    want = cold.serve(reqs)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g.predictions, w.predictions)
        np.testing.assert_array_equal(g.scores, w.scores)
