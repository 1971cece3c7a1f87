"""Online continual learning: fit_iterative one-shot equivalence + backend
parity, SeizureSession.adapt gating, fleet-vs-session adapt bit-exactness,
and mid-stream checkpoint save/restore."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import online
from repro.core.pipeline import BACKENDS, HDCConfig, HDCPipeline, VARIANTS
from repro.launch.mesh import make_mesh
from repro.serve.engine import SeizureSession
from repro.serve.fleet import StreamingFleet

jax.config.update("jax_platform_name", "cpu")

# tiny geometry keeps every jit compile in milliseconds
DIM, SEGMENTS, CHANNELS, WINDOW = 256, 8, 8, 32


def _cfg(variant: str, **overrides) -> HDCConfig:
    base = dict(dim=DIM, segments=SEGMENTS, channels=CHANNELS, window=WINDOW,
                variant=variant, spatial_threshold=1, temporal_threshold=4)
    base.update(overrides)
    return HDCConfig(**base)


def _train_data(seed: int, frames: int = 8):
    rng = np.random.default_rng(seed)
    codes = jnp.asarray(
        rng.integers(0, 64, (1, frames * WINDOW, CHANNELS), np.uint8))
    labels = np.asarray(rng.integers(0, 2, (1, frames), np.int32))
    labels[0, :2] = (0, 1)  # every class needs >= 1 example
    return codes, jnp.asarray(labels)


def _trained(variant: str, seed: int = 0, **overrides) -> HDCPipeline:
    codes, labels = _train_data(seed)
    pipe = HDCPipeline.init(jax.random.PRNGKey(seed), _cfg(variant, **overrides))
    return pipe.train_one_shot(codes, labels)


def _chunk(rng, t):
    return rng.integers(0, 64, (t, CHANNELS), np.uint8)


# ---------------------------------------------------------------------------
# fit_iterative
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("variant", VARIANTS)
def test_fit_iterative_zero_epochs_is_one_shot(variant):
    """The counter-file state seeds from the one-shot accumulation, so zero
    retraining epochs must reproduce train_one_shot bit-exactly."""
    codes, labels = _train_data(1)
    pipe = HDCPipeline.init(jax.random.PRNGKey(1), _cfg(variant))
    one = pipe.train_one_shot(codes, labels)
    it0 = pipe.fit_iterative(codes, labels, epochs=0)
    np.testing.assert_array_equal(np.asarray(one.class_hvs),
                                  np.asarray(it0.class_hvs))
    np.testing.assert_array_equal(np.asarray(one.am_state.counts),
                                  np.asarray(it0.am_state.counts))
    np.testing.assert_array_equal(np.asarray(one.am_state.n),
                                  np.asarray(it0.am_state.n))


@pytest.mark.parametrize("variant", VARIANTS)
def test_fit_iterative_backends_bit_exact(variant):
    codes, labels = _train_data(2)
    pipe = HDCPipeline.init(jax.random.PRNGKey(2), _cfg(variant))
    trained = {b: pipe.with_backend(b).fit_iterative(codes, labels, epochs=3,
                                                     margin=1.0)
               for b in BACKENDS}
    np.testing.assert_array_equal(np.asarray(trained["jnp"].class_hvs),
                                  np.asarray(trained["pallas"].class_hvs))
    np.testing.assert_array_equal(np.asarray(trained["jnp"].am_state.counts),
                                  np.asarray(trained["pallas"].am_state.counts))


def test_fit_iterative_reduces_training_errors():
    """On a noisy-but-learnable stream, retraining epochs must cut the number
    of misclassified training frames (the classic iterative-HD claim)."""
    rng = np.random.default_rng(3)
    frames = 24
    # class-conditional code statistics with heavy overlap: class 1 draws
    # from a narrow sub-alphabet of class 0's, so one-shot prototypes confuse
    stream = rng.integers(0, 64, (1, frames * WINDOW, CHANNELS))
    labels = np.asarray(rng.integers(0, 2, (1, frames), np.int32))
    labels[0, :2] = (0, 1)
    for f in np.nonzero(labels[0])[0]:
        seg = slice(f * WINDOW, (f + 1) * WINDOW)
        narrow = rng.integers(0, 12, (WINDOW, CHANNELS))
        keep = rng.random((WINDOW, CHANNELS)) < 0.9  # 10% signal dilution
        stream[0, seg] = np.where(keep, stream[0, seg], narrow)
    codes, labels = jnp.asarray(stream.astype(np.uint8)), jnp.asarray(labels)
    pipe = HDCPipeline.init(jax.random.PRNGKey(3), _cfg("sparse_compim"))
    pipe = pipe.calibrate_density(codes, target=0.25)
    one = pipe.train_one_shot(codes, labels)
    it = pipe.fit_iterative(codes, labels, epochs=10)
    _, preds_one = one.infer(codes)
    _, preds_it = it.infer(codes)
    err_one = int((np.asarray(preds_one) != np.asarray(labels)).sum())
    err_it = int((np.asarray(preds_it) != np.asarray(labels)).sum())
    assert err_one > 0, "stream unexpectedly separable; pick another seed"
    assert err_it < err_one


def test_fit_iterative_validation():
    codes, labels = _train_data(4)
    pipe = HDCPipeline.init(jax.random.PRNGKey(4), _cfg("sparse_compim"))
    with pytest.raises(ValueError, match="epochs"):
        pipe.fit_iterative(codes, labels, epochs=-1)
    with pytest.raises(ValueError, match="no examples"):
        pipe.fit_iterative(codes, jnp.zeros_like(labels), epochs=1)


def test_with_cfg_drops_am_state_with_class_hvs():
    pipe = _trained("sparse_compim", seed=5)
    assert pipe.am_state is not None
    recal = pipe.with_cfg(temporal_threshold=pipe.cfg.temporal_threshold + 1)
    assert recal.class_hvs is None and recal.am_state is None
    kept = pipe.with_backend("pallas")
    assert kept.class_hvs is not None and kept.am_state is not None


# ---------------------------------------------------------------------------
# core update rule
# ---------------------------------------------------------------------------

def test_update_gates_and_clamps():
    state = online.OnlineAMState(
        counts=jnp.asarray([[2, 0, 1], [0, 3, 0]], jnp.int32),
        n=jnp.asarray([1, 1], jnp.int32))
    bits = jnp.asarray([1, 1, 0], jnp.int32)
    # correct, confident -> no update
    st, applied = online.update(state, bits, jnp.asarray(0),
                                jnp.asarray([5, 1], jnp.int32))
    assert not bool(applied)
    np.testing.assert_array_equal(np.asarray(st.counts),
                                  np.asarray(state.counts))
    # wrong -> add to true (0), subtract from rival (1), clamp at zero
    st, applied = online.update(state, bits, jnp.asarray(0),
                                jnp.asarray([1, 5], jnp.int32))
    assert bool(applied)
    np.testing.assert_array_equal(np.asarray(st.counts),
                                  [[3, 1, 1], [0, 2, 0]])
    np.testing.assert_array_equal(np.asarray(st.n), [2, 0])
    # correct but low margin -> confidence gate fires
    _, applied = online.update(state, bits, jnp.asarray(0),
                               jnp.asarray([5, 4], jnp.int32), margin=2.0)
    assert bool(applied)
    # label -1 masks the update
    _, applied = online.update(state, bits, jnp.asarray(-1),
                               jnp.asarray([1, 5], jnp.int32))
    assert not bool(applied)


# ---------------------------------------------------------------------------
# SeizureSession.adapt
# ---------------------------------------------------------------------------

def test_session_adapt_semantics():
    pipe = _trained("sparse_compim", seed=6)
    sess = SeizureSession(pipe)
    with pytest.raises(ValueError, match="no frame emitted"):
        sess.adapt(1)
    rng = np.random.default_rng(0)
    [dec] = sess.push(_chunk(rng, WINDOW))
    with pytest.raises(ValueError, match="not in"):
        sess.adapt(7)
    before = np.asarray(sess.class_hvs)
    # feeding back the predicted label with no margin: gate must not fire
    assert sess.adapt(dec.prediction) is False
    np.testing.assert_array_equal(np.asarray(sess.class_hvs), before)
    # feeding back the other label: gate fires and the AM personalizes
    assert sess.adapt(1 - dec.prediction) is True
    assert not np.array_equal(np.asarray(sess.class_hvs), before)
    # the pipeline object itself stays immutable
    np.testing.assert_array_equal(np.asarray(pipe.class_hvs), before)


def test_session_adapt_requires_am_state():
    pipe = _trained("sparse_compim", seed=6)
    bare = dataclasses.replace(pipe, am_state=None)
    sess = SeizureSession(bare)
    rng = np.random.default_rng(0)
    sess.push(_chunk(rng, WINDOW))
    with pytest.raises(ValueError, match="am_state"):
        sess.adapt(1)


def test_session_adapt_changes_decisions():
    """Persistent wrong-label feedback must eventually flip the session's
    prediction for a repeated frame (the AM really moves)."""
    codes, labels = _train_data(7)
    pipe = HDCPipeline.init(jax.random.PRNGKey(7), _cfg("sparse_compim"))
    pipe = pipe.calibrate_density(codes, 0.25).train_one_shot(codes, labels)
    sess = SeizureSession(pipe)
    rng = np.random.default_rng(1)
    chunk = _chunk(rng, WINDOW)
    [dec] = sess.push(chunk)
    target = 1 - dec.prediction
    for _ in range(8):
        sess.adapt(target)
        [dec] = sess.push(chunk)
        if dec.prediction == target:
            break
    assert dec.prediction == target


# ---------------------------------------------------------------------------
# fleet adapt: bit-exact with per-session loops
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("variant", ["sparse_compim", "sparse_naive", "dense"])
def test_fleet_adapt_matches_session_loop(variant):
    """Random chunk schedules + random masked feedback: the fleet's single
    jitted adapt step must reproduce per-session SeizureSession.adapt calls
    bit-exactly — applied gates, counter files, class rows, and every
    subsequent decision."""
    pipes = {"a": _trained(variant, seed=0, temporal_threshold=4),
             "b": _trained(variant, seed=1, temporal_threshold=6)}
    owners = ["a", "b", "a", "b", "a"]
    fleet = StreamingFleet(pipes, owners, buckets=(8, 16, 64))
    sessions = [SeizureSession(pipes[o]) for o in owners]
    rng = np.random.default_rng(7)
    adapts = 0
    for _ in range(8):
        lens = rng.integers(0, 90, len(owners))
        chunks = [_chunk(rng, int(t)) for t in lens]
        fleet_out = fleet.push(chunks)
        emitted = []
        for i, sess in enumerate(sessions):
            sess_out = sess.push(chunks[i])
            assert len(fleet_out[i]) == len(sess_out)
            for f, s in zip(fleet_out[i], sess_out):
                np.testing.assert_array_equal(f.scores, s.scores)
                np.testing.assert_array_equal(f.frame_hv, s.frame_hv)
            emitted.append(len(sess_out) > 0)
        labels = rng.integers(0, 2, len(owners))
        feedback = rng.random(len(owners)) < 0.7  # some sessions stay silent
        masked = np.where(np.logical_and(emitted, feedback), labels, -1)
        applied = fleet.adapt(masked)
        for i, sess in enumerate(sessions):
            if masked[i] >= 0:
                assert sess.adapt(int(labels[i])) == bool(applied[i])
                adapts += bool(applied[i])
            else:
                assert not applied[i]
            np.testing.assert_array_equal(np.asarray(sess.class_hvs),
                                          fleet.class_rows[i])
    assert adapts > 0  # the schedule really exercised gated updates


def test_fleet_adapt_validation():
    pipe = _trained("sparse_compim", seed=3)
    fleet = StreamingFleet({"p": pipe}, ["p", "p"])
    with pytest.raises(ValueError, match="one label per session"):
        fleet.adapt([1])
    with pytest.raises(ValueError, match="n_classes"):
        fleet.adapt([2, 0])
    # adapt before any frame: silently skipped for every session
    assert not fleet.adapt([1, 1]).any()
    bare = dataclasses.replace(pipe, am_state=None)
    no_state = StreamingFleet({"p": bare}, ["p"])
    with pytest.raises(ValueError, match="am_state"):
        no_state.adapt([1])


def test_fleet_adapt_per_patient_class_density():
    """Patients may configure different class_density targets; the fleet's
    re-threshold must honor each session's own value (bit-exact with the
    per-session loop, which reads it from the pipeline cfg)."""
    pipes = {"a": _trained("sparse_compim", seed=0, class_density=0.3),
             "b": _trained("sparse_compim", seed=1, class_density=0.6)}
    owners = ["a", "b"]
    fleet = StreamingFleet(pipes, owners, buckets=(WINDOW,))
    sessions = [SeizureSession(pipes[o]) for o in owners]
    rng = np.random.default_rng(5)
    chunk = _chunk(rng, WINDOW)
    fleet_out = fleet.push([chunk, chunk])
    for i, sess in enumerate(sessions):
        sess.push(chunk)
    labels = [1 - fleet_out[i][0].prediction for i in range(2)]  # force gates
    applied = fleet.adapt(labels)
    assert applied.all()
    for i, sess in enumerate(sessions):
        assert sess.adapt(labels[i]) is True
        np.testing.assert_array_equal(np.asarray(sess.class_hvs),
                                      fleet.class_rows[i])


# ---------------------------------------------------------------------------
# durable fleets: checkpoint save/restore
# ---------------------------------------------------------------------------

def _assert_same_decisions(a, b):
    for da, db in zip(a, b):
        assert len(da) == len(db)
        for x, y in zip(da, db):
            assert x.frame_index == y.frame_index
            assert x.prediction == y.prediction
            np.testing.assert_array_equal(x.scores, y.scores)
            np.testing.assert_array_equal(x.frame_hv, y.frame_hv)


@pytest.mark.parametrize("variant", ["sparse_compim", "dense"])
def test_fleet_checkpoint_resumes_mid_stream(tmp_path, variant):
    """save -> restore into a FRESH fleet mid-stream (partial windows,
    adapted AMs) must continue bit-exactly with the uninterrupted fleet."""
    pipes = {"a": _trained(variant, seed=0, temporal_threshold=4),
             "b": _trained(variant, seed=1, temporal_threshold=6)}
    owners = ["a", "b", "a"]
    rng = np.random.default_rng(11)
    fleet = StreamingFleet(pipes, owners, buckets=(8, 32))
    # advance mid-stream: odd lengths leave partial accumulator fills
    sched1 = [[_chunk(rng, int(t)) for t in rng.integers(0, 50, 3)]
              for _ in range(3)]
    sched2 = [[_chunk(rng, int(t)) for t in rng.integers(0, 50, 3)]
              for _ in range(3)]
    for chunks in sched1:
        out = fleet.push(chunks)
        labels = np.where([len(o) > 0 for o in out],
                          rng.integers(0, 2, 3), -1)
        fleet.adapt(labels)
    step = fleet.save(str(tmp_path))
    assert step.endswith("step_00000000")
    saved_fill = fleet.fill_levels.copy()
    ref = [fleet.push(chunks) for chunks in sched2]

    fresh = StreamingFleet(pipes, owners, buckets=(8, 32))
    assert fresh.restore(str(tmp_path)) == 0
    np.testing.assert_array_equal(fresh.fill_levels, saved_fill)
    got = [fresh.push(chunks) for chunks in sched2]
    for r, g in zip(ref, got):
        _assert_same_decisions(r, g)


def test_fleet_checkpoint_validates_geometry(tmp_path):
    fleet = StreamingFleet({"p": _trained("sparse_compim", seed=0)}, ["p"])
    fleet.save(str(tmp_path))
    other = StreamingFleet({"p": _trained("sparse_compim", seed=0)},
                           ["p", "p"])
    with pytest.raises(ValueError, match="does not match"):
        other.restore(str(tmp_path))
    # same geometry/session count but a DIFFERENT patient bank: the state
    # would silently score foreign frames against the restored class rows
    foreign = StreamingFleet({"p": _trained("sparse_compim", seed=1)}, ["p"])
    with pytest.raises(ValueError, match="does not match"):
        foreign.restore(str(tmp_path))
    with pytest.raises(FileNotFoundError):
        fleet.restore(str(tmp_path / "empty"))


def test_fleet_checkpoint_elastic_onto_mesh(tmp_path):
    """A fleet saved unsharded restores onto a mesh (and keeps deciding
    identically) — the elastic-restore contract."""
    pipes = {"a": _trained("sparse_compim", seed=0)}
    owners = ["a", "a"]
    rng = np.random.default_rng(2)
    plain = StreamingFleet(pipes, owners, buckets=(16, 32))
    plain.push([_chunk(rng, 20), _chunk(rng, 45)])
    plain.save(str(tmp_path))
    mesh = make_mesh((1,), ("data",))
    sharded = StreamingFleet(pipes, owners, buckets=(16, 32), mesh=mesh)
    sharded.restore(str(tmp_path))
    chunks = [_chunk(rng, 40), _chunk(rng, 40)]
    _assert_same_decisions(plain.push(chunks), sharded.push(chunks))


def test_fleet_reset_restores_trained_am(tmp_path):
    pipe = _trained("sparse_compim", seed=9)
    fleet = StreamingFleet({"p": pipe}, ["p"])
    rng = np.random.default_rng(3)
    [out] = fleet.push([_chunk(rng, WINDOW)])
    assert fleet.adapt([1 - out[0].prediction]).all()
    assert not np.array_equal(fleet.class_rows[0], np.asarray(pipe.class_hvs))
    fleet.reset()
    np.testing.assert_array_equal(fleet.class_rows[0],
                                  np.asarray(pipe.class_hvs))
    np.testing.assert_array_equal(fleet.fill_levels, [0])
