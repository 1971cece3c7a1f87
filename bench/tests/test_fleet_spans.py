"""The program's spans: the readers of ``bench/fleet_spans.py`` on a
hand-made trace, the clock check, and a trace recorded on the CPU.

The recorded trace is a traced run of the tiny jnp cell (``tiny.make_root``
with ``backend="jnp"``, seed 5, 0.25 s: three pushes), kept with
``decompose.traced(..., keep_trace=...)`` and gzipped."""

import gzip
import os

import jax
import pytest

from bench import fleet_spans, harness, trace

RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "cpu_frames_program.xplane.pb.gz")
DEV = "/device:TPU:0"
KERNEL = fleet_spans.KERNEL
TICK = 100.0   # ms between pushes


def ms(x: float) -> int:
    return round(x * 1e6)


def push_spans(i: int):
    """Push i of the hand-made trace, in ms from its due time: an ingest
    holding 2 ms of staging, 5 of h2d and 1 of dispatch; the device's first
    operation 0.6 ms after the dispatch returned and the kernel running 25
    ms of the 29 ms execution; a collection holding 3 ms of d2h and 4 of
    decode."""
    t = i * TICK
    bench = [("bench.ingest", 1, 11), ("bench.device_wait", 11, 40),
             ("bench.collect", 41, 49), ("bench.wait_tick", 50, 100)]
    program = [("fleet.push", 1.1, 10.9, {"rounds": 1}),
               ("fleet.stage", 1.2, 3.2, {"tile": 0}),
               ("fleet.h2d", 3.3, 8.3, {"bytes": 64}),
               ("fleet.dispatch", 8.4, 9.4,
                {"tile": 0, "bucket": 256, "path": "aot"}),
               ("fleet.collect", 41.1, 48.9, {}),
               ("fleet.d2h", 41.2, 44.2, {"bytes": 32}),
               ("fleet.decode", 44.3, 48.3, {"decisions": 4})]
    ops = [("%fusion.3 = s32[4,2]{1,0} fusion(%a)", 10.0, 12.0),
           ("%hdc_fleet_counts.1 = s32[4,2,32,32]{3,2,1,0} custom-call(%b)",
            12.0, 37.0),
           ("%copy.2 = s32[4,2]{1,0} copy(%c)", 37.0, 38.0)]
    return ([(n, ms(t + s), ms(t + e)) for n, s, e in bench],
            [(n, ms(t + s), ms(t + e), a) for n, s, e, a in program],
            [(n, ms(t + s), ms(t + e)) for n, s, e in ops],
            [("jit_fleet_step(12)", ms(t + 9.0), ms(t + 38.0))])


def hand_made(pushes: int = 2, shift_ms: float = 0.0) -> harness.Run:
    """A traced run of ``pushes`` pushes on one device whose planes are
    ``shift_ms`` off the host's clock."""
    spans, program, dev = [("bench.window", 0, ms(pushes * TICK))], [], \
        trace.Device()
    for i in range(pushes):
        b, p, o, m = push_spans(i)
        spans += b
        program += p
        dev.ops += [(n, s + ms(shift_ms), e + ms(shift_ms)) for n, s, e in o]
        dev.modules += [(n, s + ms(shift_ms), e + ms(shift_ms))
                        for n, s, e in m]
    run = harness.Run(
        cell={}, config={}, mix={}, sessions=4, setup_s=0.0,
        pushes=[harness.Push(due=i * TICK / 1e3, start=(i * TICK + 1) / 1e3,
                             pushed=(i * TICK + 11) / 1e3,
                             ready=(i * TICK + 40) / 1e3,
                             collected=(i * TICK + 49.5) / 1e3, decisions=4,
                             cycles=256) for i in range(pushes)],
        devices=[DEV], peaks={},
        trace=trace.Reduced({DEV: dev}, sorted(spans, key=lambda s: s[1])),
        window_ns=(0, ms(pushes * TICK)), step_modules=("jit_fleet_step",))
    run.program = fleet_spans.Program(sorted(program, key=lambda s: s[1]))
    return run


@pytest.mark.parametrize("metric,want", [
    ("stage_ms.live", 2.0), ("h2d_ms.live", 5.0), ("dispatch_ms.live", 1.0),
    ("launch_wait_ms.live", 0.6), ("kernel_device_ms.live", 25.0),
    ("d2h_ms.live", 3.0), ("decode_ms.live", 4.0)])
def test_reader_on_a_hand_made_trace(metric, want):
    v = harness.read_metric(harness.ROOT, {"name": metric}, hand_made())
    assert v == pytest.approx(want)


@pytest.mark.parametrize("metric", [
    "stage_ms.live", "h2d_ms.live", "dispatch_ms.live", "launch_wait_ms.live",
    "kernel_device_ms.live", "d2h_ms.live", "decode_ms.live"])
def test_reader_finds_nothing_without_the_programs_names(metric):
    """A program that writes no spans and leaves its kernel unnamed (as one
    without this instrumentation) gives nothing to read: None, no raise."""
    run = hand_made()
    run.program = fleet_spans.Program([])
    run.trace.devices[DEV].ops = [(n.replace("hdc_fleet_counts", "_unknown_"),
                                   s, e) for n, s, e in
                                  run.trace.devices[DEV].ops]
    assert harness.read_metric(harness.ROOT, {"name": metric}, run) is None


def test_clock_check_passes_on_one_clock():
    notes = fleet_spans.clock(hand_made())
    assert notes["clock_pairs"] == 2
    assert notes["clock_start_slack_ms"] == pytest.approx(0.6)
    assert notes["clock_end_slack_ms"] == pytest.approx(2.0)
    assert notes["clock_offset_ms"] == 0


@pytest.mark.parametrize("shift", [5.0, -5.0])
def test_clock_check_fails_on_a_shifted_device_plane(shift):
    """Device planes 5 ms late end executions after the host's wait
    returned; 5 ms early start them before their dispatch.  Shifted back
    by the offset, the check passes."""
    run = hand_made(shift_ms=shift)
    with pytest.raises(fleet_spans.ClockError, match="push 0") as e:
        fleet_spans.clock(run)
    late = 5.0 - 2.0 if shift > 0 else 5.0 - 0.6
    assert f"by {late:.3f} ms" in str(e.value)
    run.program.offset_ns = -ms(shift)
    assert fleet_spans.clock(run)["clock_end_slack_ms"] == pytest.approx(2.0)


def test_pairs_need_one_execution_per_dispatch():
    run = hand_made()
    run.trace.devices[DEV].modules.pop()
    with pytest.raises(fleet_spans.ClockError, match="2 fleet.dispatch"):
        fleet_spans.pairs(run)


def test_offset_joins_launches_to_executions_on_run_id():
    prog = fleet_spans.Program(
        [], device_runs={DEV: [(7, ms(109)), (8, ms(209))]},
        host_runs=[("launch", 7, ms(9)), ("launch", 7, ms(10)),
                   ("launch", 8, ms(109)), ("other", 9, ms(5))])
    assert fleet_spans.offset(prog) == ms(-100)
    assert fleet_spans.offset(fleet_spans.Program([])) is None


def test_idle_gaps_named_by_the_innermost_span_covering_most():
    run = hand_made()
    gaps = fleet_spans.idle_gaps(run)
    # between pushes: 38 -> 110 ms, most of it waiting for the tick
    assert gaps[0] == ["bench.wait_tick", pytest.approx(0.072)]
    # before the first execution: 0 -> 10 ms; fleet.push covers more than
    # half of it, and no span inside fleet.push does
    assert ["fleet.push", pytest.approx(0.010)] in gaps
    run.program.spans = []
    assert ["bench.ingest", pytest.approx(0.010)] in \
        fleet_spans.idle_gaps(run)


def test_decomposition_sums_to_the_owed_interval():
    parts = fleet_spans.decompose(hand_made())
    owed = parts.pop("owed_ms")
    assert owed == pytest.approx(49.5)
    assert sum(parts.values()) == pytest.approx(owed)
    assert parts == pytest.approx({
        "queue_wait_ms": 1.0, "stage_ms": 2.0, "h2d_ms": 5.0,
        "dispatch_ms": 1.0, "ingest_other_ms": 0.4, "launch_wait_ms": 0.6,
        "step_device_ms": 28.0, "device_wait_rest_ms": 2.0,
        "handover_ms": 1.0, "d2h_ms": 3.0, "decode_ms": 4.0,
        "collect_other_ms": 1.0, "after_collect_ms": 0.5})


def test_op_name():
    assert fleet_spans.op_name(
        "%hdc_fleet_counts.1 = s32[4]{0} custom-call(%a)") == KERNEL
    assert fleet_spans.op_name("hdc_fleet_counts.12") == KERNEL
    assert fleet_spans.op_name("hdc_fleet_counts") == KERNEL
    assert fleet_spans.op_name("fusion.3") == "fusion"


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(RECORDED) as f:
        data = jax.profiler.ProfileData.from_serialized_xspace(f.read())
    return trace.reduce(data), fleet_spans.reduce(data)


def test_recorded_program_spans_nest_in_the_harness_spans(recorded):
    """Each push of the recorded run: its ingest holds one fleet.push with
    stage, h2d and dispatch in that order, its collection one fleet.collect
    with d2h and decode; the harness's reduction keeps its own spans
    only."""
    red, prog = recorded
    assert {n for n, _, _ in red.spans} <= {
        "bench.window", "bench.wait_tick", "bench.ingest",
        "bench.device_wait", "bench.collect"}
    for parent, names in (("bench.ingest", ["fleet.push", "fleet.stage",
                                            "fleet.h2d", "fleet.dispatch"]),
                          ("bench.collect", ["fleet.collect", "fleet.d2h",
                                             "fleet.decode"])):
        outer = [(s, e) for n, s, e in red.spans if n == parent]
        assert len(outer) == 3
        for lo, hi in outer:
            inner = [(n, a) for n, s, e, a in prog.spans
                     if lo <= s and e <= hi]
            assert [n for n, _ in inner] == names
            for n, a in inner:
                if n in ("fleet.h2d", "fleet.d2h"):
                    assert a["bytes"] > 0
    dispatch = [a for n, _, _, a in prog.spans if n == "fleet.dispatch"]
    assert all(a["path"] == "aot" and a["tile"] == 0 for a in dispatch)


def test_recorded_spans_read_per_push(recorded):
    red, prog = recorded
    run = harness.Run(cell={}, config={}, mix={}, sessions=12, setup_s=0.0,
                      pushes=[], devices=[], peaks={}, trace=red,
                      window_ns=red.span(trace.WINDOW))
    run.program = prog
    for name, parent in (("fleet.stage", "bench.ingest"),
                         ("fleet.h2d", "bench.ingest"),
                         ("fleet.dispatch", "bench.ingest"),
                         ("fleet.d2h", "bench.collect"),
                         ("fleet.decode", "bench.collect")):
        ns = fleet_spans.per_push(run, name, parent)
        assert len(ns) == 3 and all(v > 0 for v in ns)
    ingest = [e - s for n, s, e in red.spans if n == "bench.ingest"]
    host = [sum(v) for v in zip(*(fleet_spans.per_push(run, n, "bench.ingest")
                                  for n in ("fleet.stage", "fleet.h2d",
                                            "fleet.dispatch")))]
    assert all(h < i for h, i in zip(host, ingest))
