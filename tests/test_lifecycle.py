"""Elastic fleet lifecycle (serve/lifecycle.py): admission/eviction slot
invariants, reconnect-with-state bit-exactness, spill/compaction,
overload shedding, recompile-free guarantees, incremental checkpoints,
and crash recovery via restore+replay — including a real SIGTERM kill of
``launch/serve.py``."""

import json
import os
import signal
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.pipeline import HDCConfig, HDCPipeline
from repro.serve.engine import SeizureSession, SessionSnapshot
from repro.serve.lifecycle import CapacityError, ElasticFleet

jax.config.update("jax_platform_name", "cpu")

DIM, SEGMENTS, CHANNELS, WINDOW = 256, 8, 8, 32
BUCKETS = (32, 64)


def _trained(seed: int) -> HDCPipeline:
    rng = np.random.default_rng(seed)
    cfg = HDCConfig(dim=DIM, segments=SEGMENTS, channels=CHANNELS,
                    window=WINDOW, variant="sparse_compim",
                    spatial_threshold=1, temporal_threshold=4)
    codes = jnp.asarray(rng.integers(0, 64, (2, 4 * WINDOW, CHANNELS),
                                     np.uint8))
    labels = np.asarray(rng.integers(0, 2, (2, 4), np.int32))
    labels[0, :2] = (0, 1)
    pipe = HDCPipeline.init(jax.random.PRNGKey(seed), cfg)
    return pipe.train_one_shot(codes, jnp.asarray(labels))


@pytest.fixture(scope="module")
def bank():
    return {f"p{i}": _trained(i) for i in range(2)}


def _fleet(bank, **kw):
    kw.setdefault("tile", 4)
    kw.setdefault("max_tiles", 2)
    kw.setdefault("queue_limit", 2)
    kw.setdefault("buckets", BUCKETS)
    return ElasticFleet(bank, **kw)


def _chunk(rng, t):
    return rng.integers(0, 64, (t, CHANNELS), np.uint8)


def _assert_same_decisions(a, b):
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert x.frame_index == y.frame_index
        assert x.prediction == y.prediction
        np.testing.assert_array_equal(x.scores, y.scores)


def _slot_invariants(fleet):
    """The free-slot-map safety properties every op must preserve."""
    occupied = set(fleet._slot_sid)
    free = set().union(*fleet._free) if fleet._free else set()
    # bijection: no two live sessions alias one slot, maps agree
    assert len(fleet._sid_slot) == len(set(fleet._sid_slot.values()))
    assert {s: k for k, s in fleet._sid_slot.items()} == fleet._slot_sid
    # partition: every slot is exactly one of free/occupied
    assert free.isdisjoint(occupied)
    assert free | occupied == set(range(fleet.capacity))
    # the fleet-wide emission invariant dead slots rely on
    assert (fleet._filled_h < WINDOW).all()


# ---------------------------------------------------------------------------
# admission / eviction / reconnect
# ---------------------------------------------------------------------------

def test_admit_push_evict_matches_sessions(bank):
    rng = np.random.default_rng(0)
    fleet = _fleet(bank)
    s0, s1 = fleet.admit("p0"), fleet.admit("p1")
    ref0, ref1 = SeizureSession(bank["p0"]), SeizureSession(bank["p1"])
    for t in (WINDOW + 7, 2 * WINDOW, 5, 0, WINDOW - 5):
        c0, c1 = _chunk(rng, t), _chunk(rng, max(t - 3, 0))
        decs = fleet.push_sessions({s0: c0, s1: c1})
        _assert_same_decisions(decs[s0], ref0.push(c0))
        _assert_same_decisions(decs[s1], ref1.push(c1))
        _slot_invariants(fleet)
    snaps = fleet.evict([s0, s1])
    assert snaps[s0].patient_id == "p0"
    assert fleet.sessions == {} and fleet.free_slots == fleet.capacity
    _slot_invariants(fleet)


def test_evict_readmit_bit_exact_with_uninterrupted(bank):
    """Reconnect-with-state: evict mid-window, round-trip the snapshot
    through its wire encoding, readmit, and stay bit-exact with a session
    that never dropped."""
    rng = np.random.default_rng(1)
    fleet = _fleet(bank)
    sid = fleet.admit("p0")
    ref = SeizureSession(bank["p0"])
    c1 = _chunk(rng, WINDOW + 11)  # ends mid-window: filled = 11
    _assert_same_decisions(fleet.push_sessions({sid: c1})[sid], ref.push(c1))

    snap = fleet.evict([sid])[sid]
    assert snap.filled == 11 and snap.frame_index == 1
    snap = SessionSnapshot.from_bytes(snap.to_bytes())  # wire round-trip

    sid2 = fleet.admit("p0", snapshot=snap)
    c2 = _chunk(rng, 2 * WINDOW)
    _assert_same_decisions(fleet.push_sessions({sid2: c2})[sid2],
                           ref.push(c2))
    # adaptation state survived the drop too
    assert fleet.adapt({sid2: 1}) == {sid2: True}
    assert ref.adapt(1)
    c3 = _chunk(rng, WINDOW)
    _assert_same_decisions(fleet.push_sessions({sid2: c3})[sid2],
                           ref.push(c3))


def test_snapshot_interops_with_engine_session(bank):
    """A fleet eviction resumes in a plain SeizureSession and vice versa."""
    rng = np.random.default_rng(2)
    fleet = _fleet(bank)
    sid = fleet.admit("p1")
    ref = SeizureSession(bank["p1"])
    c1 = _chunk(rng, WINDOW + 3)
    fleet.push_sessions({sid: c1})
    ref.push(c1)

    # fleet -> engine
    resumed = SeizureSession.from_snapshot(bank["p1"],
                                           fleet.evict([sid])[sid])
    c2 = _chunk(rng, WINDOW)
    _assert_same_decisions(resumed.push(c2), ref.push(c2))

    # engine -> fleet
    sid2 = fleet.admit("p1", snapshot=resumed.snapshot())
    c3 = _chunk(rng, WINDOW - 3)
    _assert_same_decisions(fleet.push_sessions({sid2: c3})[sid2],
                           ref.push(c3))


def test_admission_validation(bank):
    fleet = _fleet(bank)
    with pytest.raises(KeyError):
        fleet.admit("nobody")
    sid = fleet.admit("p0")
    snap = fleet.evict([sid])[sid]
    with pytest.raises(ValueError, match="belongs to patient"):
        fleet.admit("p1", snapshot=snap)
    with pytest.raises(KeyError):
        fleet.evict([99])
    with pytest.raises(KeyError):
        fleet.push_sessions({99: np.zeros((4, CHANNELS), np.uint8)})
    with pytest.raises(KeyError):
        fleet.adapt({99: 1})


# ---------------------------------------------------------------------------
# spill / compaction / backpressure
# ---------------------------------------------------------------------------

def test_spill_compact_and_capacity_error(bank):
    rng = np.random.default_rng(3)
    fleet = _fleet(bank)
    sids = [fleet.admit("p0") for _ in range(4)]
    assert fleet.n_tiles == 1 and fleet.free_slots == 0
    spilled = fleet.admit("p1")  # 5th session: spill
    assert fleet.n_tiles == 2 and fleet.capacity == 8
    assert fleet.stats["spills"] == 1
    _slot_invariants(fleet)

    ref = SeizureSession(bank["p1"])
    c = _chunk(rng, WINDOW)
    _assert_same_decisions(fleet.push_sessions({spilled: c})[spilled],
                           ref.push(c))

    for _ in range(3):
        fleet.admit("p0")
    with pytest.raises(CapacityError):
        fleet.admit("p0")

    # drain tile 0, then compact: the spilled tile's survivors migrate
    # into earlier free slots and the trailing tile is dropped
    fleet.evict(sids, with_state=False)
    extras = [s for s in fleet.sessions if s not in (spilled,)]
    fleet.evict(extras, with_state=False)
    assert fleet.compact() == 1
    assert fleet.n_tiles == 1 and fleet.capacity == 4
    assert fleet.slot_of(spilled) < 4
    _slot_invariants(fleet)
    c2 = _chunk(rng, WINDOW)
    _assert_same_decisions(fleet.push_sessions({spilled: c2})[spilled],
                           ref.push(c2))


def test_offer_queue_shed_drain_and_degraded_adapt(bank):
    rng = np.random.default_rng(4)
    fleet = _fleet(bank, max_tiles=1, queue_limit=2)
    keep = fleet.admit("p0")
    fleet.push_sessions({keep: _chunk(rng, WINDOW)})
    others = [fleet.admit("p0") for _ in range(3)]
    assert fleet.free_slots == 0

    assert fleet.offer("p1")[0] == "queued"
    assert fleet.offer("p1")[0] == "queued"
    assert fleet.offer("p1")[0] == "shed"
    assert fleet.queue_depth == 2 and fleet.stats["shed"] == 1
    assert fleet.overloaded

    # degraded decision-only mode: adapt sheds, decisions keep flowing
    assert fleet.adapt({keep: 1}) == {keep: False}
    assert fleet.stats["adapt_shed"] == 1
    decs = fleet.push_sessions({keep: _chunk(rng, WINDOW)})
    assert len(decs[keep]) == 1

    # evictions drain the queue oldest-first
    fleet.evict(others[:2], with_state=False)
    assert fleet.queue_depth == 0 and not fleet.overloaded
    assert sorted(fleet.sessions.values()).count("p1") == 2
    _slot_invariants(fleet)
    # adaptation works again once the pressure clears
    assert fleet.adapt({keep: 1}) == {keep: True}


# ---------------------------------------------------------------------------
# recompile-free lifecycle (ISSUE acceptance criterion)
# ---------------------------------------------------------------------------

def test_lifecycle_recompile_free_after_warmup(bank, no_recompiles):
    rng = np.random.default_rng(5)
    fleet = _fleet(bank, max_tiles=2)
    fleet.warmup()
    with no_recompiles():
        sids = [fleet.admit("p0"), fleet.admit("p1")]
        fleet.push_sessions({sids[0]: _chunk(rng, WINDOW + 5),
                             sids[1]: _chunk(rng, 2 * WINDOW)})
        snap = fleet.evict([sids[0]])[sids[0]]
        s2 = fleet.admit("p0", snapshot=snap)
        for _ in range(3):
            fleet.admit("p1")
        assert fleet.n_tiles == 2          # spilled, still recompile-free
        fleet.push_sessions({s2: _chunk(rng, WINDOW)})
        fleet.adapt({s2: 1})
        doomed = [s for s in fleet.sessions if fleet.slot_of(s) >= 4]
        fleet.evict(doomed, with_state=False)
        fleet.compact()                    # migration + tile drop
        assert fleet.n_tiles == 1


# ---------------------------------------------------------------------------
# property tests: free-slot-map invariants under arbitrary op sequences
# ---------------------------------------------------------------------------

@settings(max_examples=12, deadline=None)
@given(st.lists(st.tuples(st.sampled_from(["admit", "evict", "compact",
                                           "offer"]),
                          st.integers(0, 7)),
                min_size=1, max_size=14))
def test_slot_map_invariants_hold_under_churn(ops):
    bank = {"p0": _trained(0)}
    fleet = ElasticFleet(bank, tile=2, max_tiles=2, queue_limit=1,
                         buckets=BUCKETS)
    for op, arg in ops:
        if op == "admit":
            try:
                fleet.admit("p0")
            except CapacityError:
                pass
        elif op == "offer":
            fleet.offer("p0")
        elif op == "evict":
            live = sorted(fleet.sessions)
            if live:
                fleet.evict([live[arg % len(live)]],
                            with_state=bool(arg % 2))
        elif op == "compact":
            fleet.compact()
        _slot_invariants(fleet)


@settings(max_examples=6, deadline=None)
@given(st.integers(0, 2 ** 31 - 1), st.integers(1, 3 * WINDOW))
def test_eviction_readmission_bit_exact_property(seed, t1):
    """Any split point (mid-window or not): drop + resume == never dropped."""
    bank = {"p0": _trained(0)}
    rng = np.random.default_rng(seed)
    fleet = ElasticFleet(bank, tile=2, max_tiles=1, buckets=BUCKETS)
    ref = SeizureSession(bank["p0"])
    sid = fleet.admit("p0")
    c1, c2 = _chunk(rng, t1), _chunk(rng, WINDOW + 1)
    _assert_same_decisions(fleet.push_sessions({sid: c1})[sid], ref.push(c1))
    snap = fleet.evict([sid])[sid]
    sid2 = fleet.admit("p0", snapshot=snap)
    _assert_same_decisions(fleet.push_sessions({sid2: c2})[sid2],
                           ref.push(c2))


# ---------------------------------------------------------------------------
# durability: incremental checkpoints, restore, replay
# ---------------------------------------------------------------------------

def test_incremental_checkpoint_hard_links_clean_tiles(bank, tmp_path):
    rng = np.random.default_rng(6)
    root = str(tmp_path / "ckpt")
    fleet = _fleet(bank, max_tiles=2)
    a = fleet.admit("p0")
    for _ in range(4):
        fleet.admit("p0")               # spill to 2 tiles
    spilled = [s for s in fleet.sessions if fleet.slot_of(s) >= 4][0]
    fleet.push_sessions({a: _chunk(rng, WINDOW),
                         spilled: _chunk(rng, WINDOW)})
    p0 = fleet.save(root)
    fleet.push_sessions({spilled: _chunk(rng, 8)})  # only tile 1 advances
    p1 = fleet.save(root)

    def files(p):
        with open(os.path.join(p, "manifest.json")) as f:
            return {leaf["key"]: os.path.join(p, leaf["file"])
                    for leaf in json.load(f)["leaves"]}
    f0, f1 = files(p0), files(p1)
    for key in f0:
        same = os.stat(f0[key]).st_ino == os.stat(f1[key]).st_ino
        if key.startswith("tile_00/"):
            assert same, f"clean tile leaf {key} was re-serialized"
    assert any(os.stat(f0[k]).st_ino != os.stat(f1[k]).st_ino
               for k in f0 if k.startswith("tile_01/")), \
        "dirty tile must be rewritten"


def test_restore_replay_matches_uninterrupted_run(bank, tmp_path):
    """The crash-recovery contract: checkpoint, keep serving, crash,
    restore + replay the post-checkpoint events in a NEW fleet — its
    decisions (replayed and future) are bit-exact with the fleet that
    never died."""
    rng = np.random.default_rng(7)
    root = str(tmp_path / "ckpt")
    fleet = _fleet(bank, max_tiles=2, log_rounds=64)
    a, b = fleet.admit("p0"), fleet.admit("p1")
    fleet.push_sessions({a: _chunk(rng, 2 * WINDOW + 5),
                         b: _chunk(rng, WINDOW)})
    fleet.save(root)
    ckpt_op = fleet.op_id

    # post-checkpoint traffic the crash will wipe: churn + decisions
    live_results = []
    c1, c2 = _chunk(rng, WINDOW + 2), _chunk(rng, WINDOW)
    live_results.append(fleet.push_sessions({a: c1, b: c1}))
    snap = fleet.evict([b])[b]
    b2 = fleet.admit("p1", snapshot=snap)
    live_results.append(fleet.push_sessions({a: c2, b2: c2}))
    events = fleet.events_since(ckpt_op)
    post = _chunk(rng, 2 * WINDOW)
    live_final = fleet.push_sessions({a: post, b2: post})

    restored = _fleet(bank, max_tiles=2, log_rounds=64)
    step = restored.restore(root)
    assert step == 0 and restored.sessions == {a: "p0", b: "p1"}
    replayed = restored.replay(events)
    replay_pushes = [v for v in replayed.values() if isinstance(v, dict)
                     and all(isinstance(k, int) for k in v)]
    pushes = [r for r in replay_pushes if any(
        isinstance(d, list) for d in r.values())]
    assert len(pushes) == len(live_results)
    for live, redo in zip(live_results, pushes):
        assert live.keys() == redo.keys()
        for sid in live:
            _assert_same_decisions(live[sid], redo[sid])
    re_final = restored.push_sessions({a: post, b2: post})
    for sid in live_final:
        _assert_same_decisions(live_final[sid], re_final[sid])
    assert restored.sessions == fleet.sessions


def test_restore_rejects_mismatched_bank(bank, tmp_path):
    root = str(tmp_path / "ckpt")
    fleet = _fleet(bank)
    fleet.admit("p0")
    fleet.save(root)
    other = ElasticFleet({"p0": _trained(7), "p1": _trained(8)},
                         tile=4, max_tiles=2, buckets=BUCKETS)
    with pytest.raises(ValueError, match="does not match"):
        other.restore(root)


def test_replay_gap_detection(bank):
    fleet = _fleet(bank)
    fleet.admit("p0")
    with pytest.raises(ValueError, match="gap"):
        fleet.replay([(fleet.op_id + 3, "compact", ())])


def test_events_since_reports_ring_overflow(bank):
    fleet = _fleet(bank, log_rounds=2)
    sid = fleet.admit("p0")
    for _ in range(4):
        fleet.evict([sid], with_state=False)
        sid = fleet.admit("p0")
    with pytest.raises(ValueError, match="dropped"):
        fleet.events_since(0)


def test_checkpoint_resume_under_churn_property(bank, tmp_path):
    """Randomized churn + checkpoint at an arbitrary point: restore+replay
    reconverges to the live fleet's exact session table and decisions."""
    rng = np.random.default_rng(11)
    root = str(tmp_path / "ckpt")
    fleet = _fleet(bank, max_tiles=2, log_rounds=256)
    for _ in range(3):
        fleet.admit("p0")
    fleet.save(root)
    ckpt_op = fleet.op_id
    for i in range(12):
        live = sorted(fleet.sessions)
        r = rng.integers(0, 4)
        if r == 0 and live:
            fleet.evict([live[int(rng.integers(len(live)))]],
                        with_state=False)
        elif r == 1:
            fleet.offer("p1")
        elif r == 2:
            fleet.compact()
        elif live:
            fleet.push_sessions({live[0]: _chunk(rng, int(
                rng.integers(1, WINDOW + 1)))})
    events = fleet.events_since(ckpt_op)
    restored = _fleet(bank, max_tiles=2, log_rounds=256)
    restored.restore(root)
    restored.replay(events)
    assert restored.sessions == fleet.sessions
    assert restored.op_id == fleet.op_id
    np.testing.assert_array_equal(restored._filled_h, fleet._filled_h)
    np.testing.assert_array_equal(restored._fidx_h, fleet._fidx_h)
    live = sorted(fleet.sessions)
    if live:
        c = _chunk(rng, 2 * WINDOW)
        d_live = fleet.push_sessions({live[0]: c})
        d_redo = restored.push_sessions({live[0]: c})
        _assert_same_decisions(d_live[live[0]], d_redo[live[0]])


# ---------------------------------------------------------------------------
# SIGTERM: a real kill of launch/serve.py leaves a resumable checkpoint
# ---------------------------------------------------------------------------

def test_sigterm_writes_final_checkpoint_and_exits_clean(tmp_path):
    ckpt_dir = str(tmp_path / "ckpt")
    env = dict(os.environ, PYTHONPATH="src", REPRO_FLEET_TILE="64",
               JAX_PLATFORMS="cpu")
    # output goes to a file: an undrained pipe would block a chatty server
    # (e.g. compile-cache log lines) before it ever reaches a checkpoint
    log_path = tmp_path / "serve.log"
    with open(log_path, "w") as log:
        proc = subprocess.Popen(
            [sys.executable, "-m", "repro.launch.serve", "--hdc-fleet",
             "--sessions", "4", "--patients", "1", "--rounds", "100000",
             "--chunk", "64", "--ckpt-dir", ckpt_dir, "--ckpt-every", "2"],
            env=env, cwd=os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))),
            stdout=log, stderr=subprocess.STDOUT, text=True)
    try:
        deadline = time.time() + 180
        while time.time() < deadline:
            if proc.poll() is not None:
                pytest.fail("serve exited early:\n" + log_path.read_text())
            if os.path.isdir(ckpt_dir) and any(
                    d.startswith("step_") and not d.endswith(".tmp")
                    for d in os.listdir(ckpt_dir)):
                break
            time.sleep(0.25)
        else:
            pytest.skip("serve did not reach first checkpoint in time")
        proc.send_signal(signal.SIGTERM)
        proc.wait(timeout=120)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    out = log_path.read_text()
    assert proc.returncode == 0, out
    assert "caught SIGTERM" in out
    steps = [d for d in os.listdir(ckpt_dir)
             if d.startswith("step_") and not d.endswith(".tmp")]
    assert steps, "final checkpoint missing after SIGTERM"
