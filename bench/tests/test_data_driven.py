"""A later change adds a configuration, a mix, a cell and metrics by adding
files and BENCHMARK.json entries alone: a throwaway set of them in a
temporary tree runs end to end with no edit to any existing file.  The
added mix sends uploads of stored recordings, two frames a push, on a
tick of its own."""

import json
import os
import time

from bench import harness
from bench.tests import tiny

RATE = '''"""Decisions per second over the window."""


def read(run):
    n = sum(p.decisions for p in run.pushes)
    return n / (run.pushes[-1].collected - run.pushes[0].start)
'''

PUSHES = '''"""Pushes in the window (count)."""


def read(run):
    return len(run.pushes)
'''


def test_new_files_alone_make_a_new_cell_and_metrics(tmp_path):
    tiny.make_root(str(tmp_path), variant="dense", backend="jnp")
    bench = tmp_path / "bench"
    (bench / "traffic" / "tiny_upload.json").write_text(json.dumps({
        "tick_s": 0.05, "cycles": 128, "phases": 1, "bucket": 64,
        "pool_cycles": 256, "check_sessions": 5, "about": "test uploads",
        "record": {"pre_s": 0.5, "ictal_s": 0.5, "post_s": 0.5}}))
    (bench / "cells" / "tiny.upload.json").write_text('{"sessions": 40}')
    (bench / "metrics" / "upload_frames_per_s.py").write_text(RATE)
    (bench / "metrics" / "pushes_n.upload.py").write_text(PUSHES)
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "tiny.upload", "config": "tiny",
                              "traffic": "tiny_upload", "chips": 1,
                              "why": "test"})
    spec["end_to_end"].append({
        "name": "upload_frames_per_s", "unit": "frames/s",
        "better": "higher", "bound": 0.05, "source": "host_clock",
        "workloads": ["tiny.upload"]})
    spec["per_layer"].append({
        "name": "pushes_n.upload", "unit": "pushes", "better": "higher",
        "source": "program_counter", "layer": "test",
        "moves": "upload_frames_per_s", "workloads": ["tiny.upload"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    for name in ("harness.py", "reference.py", "traffic.py", "trace.py"):
        with open(os.path.join(tiny.BENCH, name)) as a, \
                open(bench / name) as b:
            assert a.read() == b.read()
    lines = {}
    with tiny.no_persistent_cache():
        for tr in ("0", "1"):
            args = harness.parse(["--workload", "tiny.upload", "--seed", "9",
                                  "--seconds", "0.5", "--trace", tr])
            lines[tr], _ = harness.run(args, root=str(tmp_path),
                                       t_start=time.perf_counter(),
                                       require_chip=False)
    assert lines["0"]["correct"] and lines["1"]["correct"]
    assert set(lines["0"]["metrics"]) == {"upload_frames_per_s", "setup_s"}
    assert lines["0"]["metrics"]["upload_frames_per_s"]["value"] > 0
    assert set(lines["1"]["metrics"]) == {"pushes_n.upload"}
    assert lines["1"]["metrics"]["pushes_n.upload"]["value"] >= 1
