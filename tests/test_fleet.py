"""StreamingFleet: fleet-vs-loop bit-exactness (random chunk schedules,
sparse + dense variants), masked emission at window boundaries, bucketed
compile-count guard, sharded placement, and the engine's padded dispatch."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.pipeline import HDCConfig, HDCPipeline, VARIANTS
from repro.launch.mesh import make_mesh
from repro.serve.dispatch import datapath_key
from repro.serve.engine import SeizureSession, ServingEngine
from repro.serve.fleet import StreamingFleet

jax.config.update("jax_platform_name", "cpu")

# tiny geometry keeps every jit compile in milliseconds
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


def _chunk(rng, t):
    return rng.integers(0, 64, (t, CHANNELS), np.uint8)


def _assert_decisions_equal(fleet_dec, session_dec):
    assert len(fleet_dec) == len(session_dec)
    for f, s in zip(fleet_dec, session_dec):
        assert f.frame_index == s.frame_index
        assert f.prediction == s.prediction
        np.testing.assert_array_equal(f.scores, s.scores)
        np.testing.assert_array_equal(f.frame_hv, s.frame_hv)


# ---------------------------------------------------------------------------
# fleet vs per-session loops: bit-exactness
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("variant", VARIANTS)
def test_fleet_matches_sessions_random_schedule(variant):
    """Random per-session chunk lengths (0, sub-window, window-crossing,
    beyond-max-bucket) must reproduce per-patient SeizureSession loops
    bit-exactly: frame indices, HVs, scores and predictions."""
    # two patients: different codebooks AND different calibrated thresholds
    pipes = {"a": _trained(variant, seed=0, temporal_threshold=4),
             "b": _trained(variant, seed=1, temporal_threshold=6)}
    owners = ["a", "b", "a", "b", "a"]
    fleet = StreamingFleet(pipes, owners, buckets=(8, 16, 64))
    sessions = [SeizureSession(pipes[o]) for o in owners]

    rng = np.random.default_rng(7)
    total = 0
    for _ in range(10):
        lens = rng.integers(0, 90, len(owners))  # 90 > max bucket: splits too
        chunks = [_chunk(rng, int(t)) for t in lens]
        fleet_out = fleet.push(chunks)
        for i, sess in enumerate(sessions):
            _assert_decisions_equal(fleet_out[i], sess.push(chunks[i]))
            total += len(fleet_out[i])
    assert total > 0  # schedule produced real decisions
    np.testing.assert_array_equal(
        fleet.fill_levels, [s.cycles_buffered for s in sessions])


def test_fleet_many_sessions_one_push(no_recompiles):
    """A wide fleet (S >> patients) advances in one step call per bucket."""
    pipe = _trained("sparse_compim", seed=3)
    s = 64
    fleet = StreamingFleet({"p": pipe}, ["p"] * s, buckets=(WINDOW,))
    rng = np.random.default_rng(0)
    chunk = _chunk(rng, WINDOW)
    out = fleet.push([chunk] * s)
    ref = SeizureSession(pipe).push(chunk)
    assert len(ref) == 1
    for dec_list in out:
        _assert_decisions_equal(dec_list, ref)
    # steady state: the single bucketed program is compiled; further pushes
    # must not trigger any XLA compile (shared analysis/guards sanitizer)
    with no_recompiles():
        fleet.push([chunk] * s)


# ---------------------------------------------------------------------------
# masked emission at window boundaries
# ---------------------------------------------------------------------------

def test_masked_emission_at_window_boundaries():
    pipe = _trained("sparse_compim", seed=5)
    fleet = StreamingFleet({"p": pipe}, ["p"] * 3, buckets=(8, 32))
    rng = np.random.default_rng(1)
    # session 0: exactly one window; session 1: one cycle short; session 2: idle
    out = fleet.push([_chunk(rng, WINDOW), _chunk(rng, WINDOW - 1), _chunk(rng, 0)])
    assert [len(o) for o in out] == [1, 0, 0]
    assert out[0][0].frame_index == 0
    np.testing.assert_array_equal(fleet.fill_levels, [0, WINDOW - 1, 0])
    np.testing.assert_array_equal(fleet.frame_indices, [1, 0, 0])
    # one more cycle completes session 1's frame at the boundary; session 0
    # starts its next frame; session 2 stays idle
    out = fleet.push([_chunk(rng, 3), _chunk(rng, 1), _chunk(rng, 0)])
    assert [len(o) for o in out] == [0, 1, 0]
    assert out[1][0].frame_index == 0
    np.testing.assert_array_equal(fleet.fill_levels, [3, 0, 0])
    # a multi-window chunk emits two frames with consecutive indices
    out = fleet.push([_chunk(rng, 2 * WINDOW - 3), _chunk(rng, 0), _chunk(rng, 0)])
    assert [d.frame_index for d in out[0]] == [1, 2]
    np.testing.assert_array_equal(fleet.fill_levels, [0, 0, 0])


def test_fleet_reset_and_validation():
    pipe = _trained("sparse_compim", seed=5)
    fleet = StreamingFleet({"p": pipe}, ["p", "p"])
    rng = np.random.default_rng(2)
    fleet.push([_chunk(rng, WINDOW), _chunk(rng, 5)])
    fleet.reset()
    np.testing.assert_array_equal(fleet.fill_levels, [0, 0])
    np.testing.assert_array_equal(fleet.frame_indices, [0, 0])
    with pytest.raises(ValueError, match="one chunk per session"):
        fleet.push([_chunk(rng, 5)])
    with pytest.raises(ValueError, match="chunk must be"):
        fleet.push([_chunk(rng, 5), _chunk(rng, 5)[:, :3]])
    with pytest.raises(KeyError, match="owners"):
        StreamingFleet({"p": pipe}, ["p", "nobody"])
    untrained = HDCPipeline.init(jax.random.PRNGKey(0), _cfg("sparse_compim"))
    with pytest.raises(ValueError, match="untrained"):
        StreamingFleet({"p": untrained}, ["p"])
    mixed = {"p": pipe, "q": _trained("sparse_compim", seed=6, window=2 * WINDOW)}
    with pytest.raises(ValueError, match="mismatch"):
        StreamingFleet(mixed, ["p", "q"])


# ---------------------------------------------------------------------------
# compile-count guard: bucketed chunk lengths must not fan out recompiles
# ---------------------------------------------------------------------------

def test_bucketed_lengths_bound_compiles(no_recompiles):
    pipe = _trained("sparse_compim", seed=9)
    buckets = (8, 32)
    fleet = StreamingFleet({"p": pipe}, ["p"] * 2, buckets=buckets)
    rng = np.random.default_rng(3)
    lengths = (1, 3, 8, 5, 20, 32, 17, 40, 2, 31, 9, 64)
    # every chunk length (incl. > max bucket, split over rounds) maps onto
    # the fixed bucket set: at most one XLA compile per bucket...
    with no_recompiles(allow=len(buckets)):
        for t in lengths:
            fleet.push([_chunk(rng, t), _chunk(rng, max(0, t - 1))])
    # ...and replaying every length is pure steady state: zero compiles
    with no_recompiles():
        for t in lengths:
            fleet.push([_chunk(rng, t), _chunk(rng, max(0, t - 1))])


class _NoCacheSize:
    """Wraps the jitted step but hides the private ``_cache_size`` API."""

    def __init__(self, fn):
        self._fn = fn

    def __call__(self, *args, **kwargs):
        return self._fn(*args, **kwargs)


def test_compile_count_bucket_fallback(monkeypatch):
    """If jax's private ``_cache_size`` disappears, ``compile_count`` falls
    back to counting distinct bucket shapes — and must still count
    multi-bucket pushes correctly (one entry per bucket, not per push)."""
    pipe = _trained("sparse_compim", seed=9)
    fleet = StreamingFleet({"p": pipe}, ["p"] * 2, buckets=(8, 32))
    monkeypatch.setattr(fleet, "_step", _NoCacheSize(fleet._step))
    assert not hasattr(fleet._step, "_cache_size")
    assert fleet.compile_count == 0
    rng = np.random.default_rng(4)
    fleet.push([_chunk(rng, 5), _chunk(rng, 3)])     # bucket 8
    assert fleet.compile_count == 1
    fleet.push([_chunk(rng, 7), _chunk(rng, 0)])     # bucket 8 again
    assert fleet.compile_count == 1
    # 40 > max bucket: splits into a 32-round AND an 8-round in ONE push
    fleet.push([_chunk(rng, 40), _chunk(rng, 12)])
    assert fleet.compile_count == 2
    # decisions through the wrapped step still work
    out = fleet.push([_chunk(rng, WINDOW), _chunk(rng, 0)])
    assert len(out[0]) >= 1


def test_push_codes_matches_push():
    """The zero-scatter stacked-ingest path (per-tile staging rings, one
    device put per tile per round) must be bit-exact with the ragged-list
    path — including reused staging buffers across pushes with shrinking
    lengths (stale ring bytes must never leak into decisions)."""
    pipes = {"a": _trained("sparse_compim", seed=0, temporal_threshold=4),
             "b": _trained("sparse_compim", seed=1, temporal_threshold=6)}
    owners = ["a", "b", "a"]
    fleet_list = StreamingFleet(pipes, owners, buckets=(8, 32))
    fleet_codes = StreamingFleet(pipes, owners, buckets=(8, 32))
    rng = np.random.default_rng(21)
    # equal lengths first (fills the staging rings), then shorter and
    # ragged-length pushes that leave stale bytes behind
    for t, ragged in ((40, False), (32, False), (5, False), (17, True),
                      (3, True), (0, False), (9, False)):
        if ragged:
            lens = rng.integers(0, t + 1, len(owners))
        else:
            lens = np.full(len(owners), t)
        chunks = [_chunk(rng, int(L)) for L in lens]
        via_list = fleet_list.push(chunks)
        batch = np.zeros((len(owners), t, CHANNELS), np.uint8)
        for i, c in enumerate(chunks):
            batch[i, :len(c)] = c
        via_codes = fleet_codes.push_codes(batch, lengths=lens)
        for da, db in zip(via_list, via_codes):
            _assert_decisions_equal(da, db)
    np.testing.assert_array_equal(fleet_list.fill_levels,
                                  fleet_codes.fill_levels)


def test_staging_ring_double_buffer_discipline():
    """The staging rings are zero-copy-aliased by device_put on CPU, so a
    slot may be rewritten only after the round that read it completed:
    consecutive rounds must alternate slots and record a completion marker
    per slot, and results must stay bit-exact across slot reuse."""
    pipe = _trained("sparse_compim", seed=3)
    fleet = StreamingFleet({"p": pipe}, ["p"] * 2, buckets=(WINDOW,))
    sessions = [SeizureSession(pipe) for _ in range(2)]
    rng = np.random.default_rng(9)
    # 4 full-bucket rounds -> each slot reused twice
    for i in range(4):
        chunks = [_chunk(rng, WINDOW), _chunk(rng, WINDOW)]
        out = fleet.push(chunks)
        for j, s in enumerate(sessions):
            _assert_decisions_equal(out[j], s.push(chunks[j]))
    assert fleet._stage_phase == 4
    for per_tile in fleet._stage_busy:
        # both (slot, bucket) buffers carry a completion marker
        assert {(0, WINDOW), (1, WINDOW)} <= set(per_tile)


def _fleet_spans(tmp_path, fn) -> list[tuple]:
    """Run ``fn`` under the profiler; the ``fleet.*`` spans it recorded as
    (name, start_ns, end_ns, args), outer spans before inner ones."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    data = jax.profiler.ProfileData.from_file(
        str(next(tmp_path.rglob("*.xplane.pb"))))
    spans = [(e.name, int(e.start_ns), int(e.end_ns), dict(e.stats))
             for p in data.planes for line in p.lines for e in line.events
             if e.name.startswith("fleet.")]
    return sorted(spans, key=lambda s: (s[1], -s[2]))


@pytest.mark.parametrize("entry", ["push_raw", "push_codes_raw"])
def test_push_and_collect_spans_nest_in_order(tmp_path, entry):
    """Two tiles, two rounds: the push span holds stage, h2d and dispatch
    per tile per round, in that order; the collection holds d2h and decode
    per tile per round.  The bytes args are the arrays' nbytes and the
    counters their sums."""
    pipe = _trained("sparse_compim", seed=3)
    fleet = StreamingFleet({"p": pipe}, ["p"] * 7, buckets=(WINDOW,),
                           tile=4)
    rng = np.random.default_rng(4)
    batch = np.stack([_chunk(rng, 2 * WINDOW) for _ in range(7)])
    got = {}

    def push_and_collect():
        rounds = (fleet.push_raw(list(batch)) if entry == "push_raw"
                  else fleet.push_codes_raw(batch))
        got["rounds"] = rounds
        got["decisions"] = fleet.collect_decisions(rounds)

    spans = _fleet_spans(tmp_path, push_and_collect)
    per_tile = ["fleet.stage", "fleet.h2d", "fleet.dispatch"]
    assert [n for n, *_ in spans] == (
        ["fleet.push"] + per_tile * 4
        + ["fleet.collect"] + ["fleet.d2h", "fleet.decode"] * 4)
    push, collect = spans[0], spans[13]
    assert push[3] == {"rounds": 2}
    for name, s, e, args in spans[1:13]:
        assert push[1] <= s <= e <= push[2]
    for name, s, e, args in spans[14:]:
        assert collect[1] <= s <= e <= collect[2]
    assert [a["tile"] for n, _, _, a in spans if n == "fleet.dispatch"] \
        == [0, 1, 0, 1]
    assert {(a["bucket"], a["path"]) for n, _, _, a in spans
            if n == "fleet.dispatch"} == {(WINDOW, "jit")}
    h2d = [a["bytes"] for n, _, _, a in spans if n == "fleet.h2d"]
    assert h2d == [4 * WINDOW * CHANNELS + 4 * 4] * 4  # codes + lengths
    d2h = [a["bytes"] for n, _, _, a in spans if n == "fleet.d2h"]
    assert d2h == [fo.frames.nbytes + fo.scores.nbytes
                   for r in got["rounds"] for fo in r.tiles]
    decode = [a["decisions"] for n, _, _, a in spans if n == "fleet.decode"]
    assert sum(decode) == sum(map(len, got["decisions"])) == 14
    assert fleet.counters == {"h2d_bytes": sum(h2d), "d2h_bytes": sum(d2h),
                              "stage_waits": 0, "jit_steps": 4}


def test_counters_are_a_snapshot_and_count_without_a_profiler():
    """``counters`` hands out a copy; pushes without a profiler still count
    what they move."""
    pipe = _trained("sparse_compim", seed=3)
    fleet = StreamingFleet({"p": pipe}, ["p"] * 2, buckets=(WINDOW,))
    snap = fleet.counters
    snap["h2d_bytes"] = 99
    assert fleet.counters["h2d_bytes"] == 0
    rng = np.random.default_rng(1)
    fleet.push([_chunk(rng, WINDOW), _chunk(rng, WINDOW)])
    c = fleet.counters
    assert c["h2d_bytes"] == 2 * WINDOW * CHANNELS + 2 * 4
    assert c["d2h_bytes"] > 0 and c["jit_steps"] == 1


def test_stage_waits_counts_a_slot_whose_reader_is_in_flight():
    """A staging slot rewritten while the step that read it still runs
    blocks the push on that step, and counts; a finished reader does not.
    Decisions stay bit-exact either way."""
    pipe = _trained("sparse_compim", seed=3)
    fleet = StreamingFleet({"p": pipe}, ["p"] * 2, buckets=(WINDOW,))
    session = SeizureSession(pipe)
    rng = np.random.default_rng(6)
    for _ in range(3):  # both slots reused, every reader long finished
        chunk = _chunk(rng, WINDOW)
        _assert_decisions_equal(fleet.push([chunk, chunk])[0],
                                session.push(chunk))
    assert fleet.counters["stage_waits"] == 0
    slow = jax.jit(lambda x: jax.lax.fori_loop(
        0, 200, lambda i, a: jnp.tanh(a @ a), x))
    x = jnp.full((512, 512), 1e-3, jnp.float32)
    slow(x).block_until_ready()  # compiled; the next call runs async
    reader = slow(x)
    assert not reader.is_ready()
    fleet._stage_busy[0][(fleet._stage_phase & 1, WINDOW)] = reader
    chunk = _chunk(rng, WINDOW)
    _assert_decisions_equal(fleet.push([chunk, chunk])[0],
                            session.push(chunk))
    assert reader.is_ready()
    assert fleet.counters["stage_waits"] == 1


def test_jit_steps_counts_steps_a_warmed_executable_refused():
    """Warmed executables serve steps without the jit; one that refuses its
    operands (here: the other bucket's) sends the step, and every later one
    of that bucket, through the jitted callable."""
    pipe = _trained("sparse_compim", seed=2)
    fleet = StreamingFleet({"p": pipe}, ["p"] * 2,
                           buckets=(WINDOW, 2 * WINDOW))
    fleet.warmup()
    rng = np.random.default_rng(3)
    fleet.push([_chunk(rng, 2 * WINDOW)] * 2)
    assert fleet.counters["jit_steps"] == 0
    small, big = sorted(fleet._exec, key=lambda key: key[2])
    fleet._exec[small] = fleet._exec[big]
    fleet.push([_chunk(rng, WINDOW)] * 2)
    fleet.push([_chunk(rng, WINDOW)] * 2)
    fleet.push([_chunk(rng, 2 * WINDOW)] * 2)
    assert fleet.counters["jit_steps"] == 2


def test_step_and_adapt_modules_are_named():
    """The step's and adapt's XLA modules carry their names, so a profile
    shows ``jit_fleet_step`` and ``jit_fleet_adapt``."""
    pipe = _trained("sparse_compim", seed=3)
    fleet = StreamingFleet({"p": pipe}, ["p"] * 2, buckets=(WINDOW,))
    names = {e.fn.lower(*e.args).as_text().split("module @", 1)[1]
             .split(" ", 1)[0] for e in fleet.aot_entries()}
    assert names == {"jit_fleet_step", "jit_fleet_adapt"}


def test_push_codes_validation():
    pipe = _trained("sparse_compim", seed=3)
    fleet = StreamingFleet({"p": pipe}, ["p"] * 2, buckets=(8,))
    with pytest.raises(ValueError, match="push_codes needs"):
        fleet.push_codes(np.zeros((3, 8, CHANNELS), np.uint8))
    with pytest.raises(ValueError, match="lengths must be"):
        fleet.push_codes(np.zeros((2, 8, CHANNELS), np.uint8),
                         lengths=[9, 0])
    assert fleet.push_codes(np.zeros((2, 0, CHANNELS), np.uint8)) == [[], []]


@pytest.mark.parametrize("variant", VARIANTS)
def test_fleet_pallas_backend_matches_jnp(variant):
    """backend="pallas" (fused code-domain VMEM kernel, interpret mode on
    CPU) must reproduce the jnp bit-plane path decision-for-decision."""
    pipes = {"a": _trained(variant, seed=0, temporal_threshold=4),
             "b": _trained(variant, seed=1, temporal_threshold=6)}
    owners = ["a", "b", "b"]
    fj = StreamingFleet(pipes, owners, buckets=(8, 32), backend="jnp")
    fp = StreamingFleet(pipes, owners, buckets=(8, 32), backend="pallas")
    rng = np.random.default_rng(5)
    for _ in range(3):
        chunks = [_chunk(rng, int(t))
                  for t in rng.integers(0, 40, len(owners))]
        for a, b in zip(fj.push(chunks), fp.push(chunks)):
            _assert_decisions_equal(a, b)


def test_push_raw_matches_push():
    """push_raw + collect_decisions is push; raw rounds expose the schedule
    (n_emit / frame_base) and per-tile device outputs without syncing."""
    pipes = {"a": _trained("sparse_compim", seed=0, temporal_threshold=4),
             "b": _trained("sparse_compim", seed=1, temporal_threshold=6)}
    owners = ["a", "b", "a"]
    fleet_a = StreamingFleet(pipes, owners, buckets=(8, 32))
    fleet_b = StreamingFleet(pipes, owners, buckets=(8, 32))
    rng = np.random.default_rng(12)
    for _ in range(5):
        lens = rng.integers(0, 70, len(owners))
        chunks = [_chunk(rng, int(t)) for t in lens]
        via_push = fleet_a.push(chunks)
        rounds = fleet_b.push_raw(chunks)
        assert all(isinstance(r.tiles, tuple) for r in rounds)
        via_raw = fleet_b.collect_decisions(rounds)
        for da, db in zip(via_push, via_raw):
            _assert_decisions_equal(da, db)
        # schedule consistency: emitted counts sum to collected decisions
        total = sum(int(r.n_emit.sum()) for r in rounds)
        assert total == sum(len(d) for d in via_raw)


# ---------------------------------------------------------------------------
# sharded placement
# ---------------------------------------------------------------------------

def test_fleet_on_mesh_matches_unsharded():
    """A 1-device data mesh must not change any decision (SPMD placement is
    a deployment knob, not a modeling knob)."""
    pipes = {"a": _trained("sparse_compim", seed=0, temporal_threshold=4),
             "b": _trained("sparse_compim", seed=1, temporal_threshold=6)}
    owners = ["a", "b", "a", "b"]
    mesh = make_mesh((1,), ("data",))
    plain = StreamingFleet(pipes, owners, buckets=(16, 32))
    sharded = StreamingFleet(pipes, owners, buckets=(16, 32), mesh=mesh)
    rng = np.random.default_rng(11)
    for _ in range(4):
        chunks = [_chunk(rng, int(t))
                  for t in rng.integers(0, 40, len(owners))]
        for a, b in zip(sharded.push(chunks), plain.push(chunks)):
            _assert_decisions_equal(a, b)


# ---------------------------------------------------------------------------
# engine: single padded dispatch on the same machinery
# ---------------------------------------------------------------------------

def test_engine_mixed_codebooks_matches_direct_infer():
    """Patients with DIFFERENT design-time codebooks (distinct init keys) in
    one bank: the single owner-gathered dispatch must match each pipeline's
    own infer bit-exactly, including padded batch sizes."""
    bank = {"a": _trained("sparse_compim", seed=0, temporal_threshold=4),
            "b": _trained("sparse_compim", seed=1, temporal_threshold=6),
            "c": _trained("sparse_compim", seed=2, temporal_threshold=5)}
    engine = ServingEngine(bank)
    rng = np.random.default_rng(4)
    for pids in (["a"], ["b", "a", "c"], ["c", "c", "a", "b", "a"]):
        reqs = [(pid, _chunk(rng, 2 * WINDOW)) for pid in pids]
        decisions = engine.serve(reqs)
        for (pid, codes), dec in zip(reqs, decisions):
            s, p = bank[pid].infer(jnp.asarray(codes[None]))
            np.testing.assert_array_equal(dec.scores, np.asarray(s)[0])
            np.testing.assert_array_equal(dec.predictions, np.asarray(p)[0])
            frames = bank[pid].encode_frames(jnp.asarray(codes[None]))
            np.testing.assert_array_equal(dec.frames, np.asarray(frames)[0])


def test_engine_batch_sizes_bucketed():
    from repro.serve import engine as engine_mod
    if not hasattr(engine_mod._serve_dispatch, "_cache_size"):
        pytest.skip("jax private _cache_size API unavailable")
    bank = {"a": _trained("sparse_compim", seed=0)}
    engine = ServingEngine(bank)
    rng = np.random.default_rng(4)
    before = engine_mod._serve_dispatch._cache_size()
    for b in (1, 2, 3, 4, 3, 2, 4):
        engine.serve([("a", _chunk(rng, WINDOW)) for _ in range(b)])
    # batch sizes 1..4 pad onto power-of-two buckets {1, 2, 4}
    assert engine_mod._serve_dispatch._cache_size() - before <= 3


def test_datapath_key_normalizes_only_per_patient_fields():
    import dataclasses

    cfg = _cfg("sparse_compim")
    same = dataclasses.replace(cfg, temporal_threshold=99, backend="pallas")
    assert datapath_key(cfg) == datapath_key(same)
    other = dataclasses.replace(cfg, window=2 * WINDOW)
    assert datapath_key(cfg) != datapath_key(other)


# ---------------------------------------------------------------------------
# benchmark harness: errors must propagate (no silent CSV-only failures)
# ---------------------------------------------------------------------------

def test_bench_run_propagates_errors(tmp_path, capsys):
    bench_run = pytest.importorskip("benchmarks.run")
    rc = bench_run.main(["no_such_bench", "--out-dir", str(tmp_path)])
    assert rc == 1
    out = capsys.readouterr().out
    assert "no_such_bench.ERROR" in out
    payload = json.loads((tmp_path / "BENCH_no_such_bench.json").read_text())
    assert payload["status"] == "error"
    assert "ModuleNotFoundError" in payload["error"]


def test_bench_json_written_for_ok_module(tmp_path):
    from benchmarks.common import write_bench_json
    rows = [{"name": "x", "us_per_call": "1", "derived": "ok"}]
    path = write_bench_json(str(tmp_path), "demo", rows)
    payload = json.loads(open(path).read())
    assert payload == {"module": "demo", "status": "ok", "rows": rows,
                       "error": None}
