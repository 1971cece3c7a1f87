"""The least bytes of a push, from shapes, and the table of peaks."""

import json
import os

import pytest

from bench import harness, workbytes


def config(name):
    with open(os.path.join(harness.ROOT, "bench", "configs",
                           name + ".json")) as f:
        return json.load(f)


def test_step_min_bytes_counts_codes_tables_counts_and_outputs():
    cfg = config("w1_compim")
    got = workbytes.step_min_bytes(cfg, sessions=7168, cycles=256,
                                   frames_out=7168, patients=16)
    codes = 7168 * 256 * 64
    tables = 16 * 64 * 64 * 8          # one position byte per segment
    counts = 2 * 7168 * 1024 * 4
    out = 7168 * (32 + 2) * 4
    assert got == codes + tables + counts + out


def test_dense_tables_are_packed_bits_on_every_device():
    cfg = dict(config("w1_compim"), variant="dense")
    one = workbytes.step_min_bytes(cfg, sessions=4, cycles=32, frames_out=0,
                                   patients=16)
    four = workbytes.step_min_bytes(cfg, sessions=4, cycles=32,
                                    frames_out=0, patients=16, devices=4)
    assert four - one == 3 * 16 * 64 * 64 * 128


def test_peaks_known_and_unknown_device_kinds():
    v5e = workbytes.peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["source"]
    with pytest.raises(KeyError, match="no peaks"):
        workbytes.peaks("TPU v9 imaginary")
    with open(workbytes.PEAKS) as f:
        assert all("source" in v for v in json.load(f).values())
