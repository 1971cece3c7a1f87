"""The reduction from trace to intervals, on hand-made intervals and on a
small trace recorded from a traced run of a tiny cell on the CPU."""

import gzip
import os

import jax
import pytest

from bench import trace

RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "cpu_frames.xplane.pb.gz")


def test_merge_covered_gaps():
    m = trace.merge([(5, 9), (0, 2), (1, 3), (9, 10)])
    assert m == [(0, 3), (5, 10)]
    assert trace.covered(m, 2, 6) == 2
    assert trace.gaps(m, 0, 12) == [(3, 5), (10, 12)]
    assert trace.gaps(m, 6, 8) == []


def test_activity_names_the_host_span_inside_the_window():
    spans = [("bench.window", 0, 100), ("bench.ingest", 10, 20),
             ("bench.collect", 20, 60)]
    assert trace.activity(spans, 15, 50) == "bench.collect"
    assert trace.activity(spans, 70, 90) == "bench.window"


def test_breakdown_sums_ops_and_names_gaps():
    dev = trace.Device(ops=[("fusion.1", 10, 30), ("fusion.2", 30, 35),
                            ("fusion.1", 70, 80)])
    red = trace.Reduced({"/device:TPU:0": dev},
                        [("bench.window", 0, 100), ("bench.collect", 35, 70)])
    b = trace.breakdown(red, 0, 100)
    assert b["device_ops"] == [["fusion.1", 30e-9], ["fusion.2", 5e-9]]
    assert b["idle_gaps"][0] == ["bench.collect", 35e-9]
    assert len(b["idle_gaps"]) == 3


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(RECORDED) as f:
        return trace.reduce(jax.profiler.ProfileData.from_serialized_xspace(
            f.read()))


def test_recorded_trace_spans(recorded):
    """A traced run of a tiny cell on the CPU: three pushes, each an
    ingest, a device wait and a collect inside the window, with the wait
    for the next tick between them; the CPU has no TPU plane."""
    assert recorded.devices == {}
    lo, hi = recorded.span(trace.WINDOW)
    names = [n for n, _, _ in recorded.spans if n != trace.WINDOW]
    assert names.count("bench.ingest") == 3
    assert names.count("bench.collect") == 3
    assert all(lo <= s <= e <= hi for _, s, e in recorded.spans)
    ingest = [(s, e) for n, s, e in recorded.spans if n == "bench.ingest"]
    assert trace.activity(recorded.spans, *ingest[1]) == "bench.ingest"


def test_recorded_spans_name_the_idle_gaps(recorded):
    """A device that ran only during the first ingest: its idle gaps are
    named by what the host was doing in them."""
    ingest = next((s, e) for n, s, e in recorded.spans
                  if n == "bench.ingest")
    red = trace.Reduced({"/device:TPU:0": trace.Device(ops=[
        ("step", *ingest)])}, recorded.spans)
    lo, hi = recorded.span(trace.WINDOW)
    gaps = trace.breakdown(red, lo, hi)["idle_gaps"]
    assert gaps[0][0] == "bench.wait_tick"
    assert sum(g for _, g in gaps) == pytest.approx(
        (hi - lo - (ingest[1] - ingest[0])) / 1e9)
