"""The dense-HDC twin of the implant fleet, ``w1_dense``: its configuration
is ``w1_compim``'s but for the datapath and what describes it, and
``correct`` sees a broken timed path on the dense datapath as it does on
the sparse one (CPU, tiny size, the harness's look for a chip skipped)."""

import pytest

from bench.tests.test_correct import (altered_answer, frozen_state,
                                      half_batch, run_tiny)
from bench.tests.test_workbytes import config

# what may differ between the twins: the datapath and the words about it
DESCRIBES_THE_DATAPATH = {"name", "source", "variant", "deployment",
                          "geometry_sources", "assumed", "guarantees"}


def test_dense_config_is_the_compim_twin():
    dense, compim = config("w1_dense"), config("w1_compim")
    assert dense["variant"] == "dense" and dense["name"] == "w1_dense"
    assert set(dense) == set(compim)
    differ = {k for k in dense if dense[k] != compim[k]}
    assert differ <= DESCRIBES_THE_DATAPATH
    assert set(dense["assumed"]) >= set(compim["assumed"]) | {
        "segments", "density_targets", "spatial_thinning"}
    assert len(dense["guarantees"]) == len(compim["guarantees"])


@pytest.mark.parametrize("fault,mix,want", [
    (None, "frames", True),
    (None, "packets", True),
    (frozen_state, "packets", False),
    (half_batch, "frames", False),
    (altered_answer, "frames", False),
], ids=["sound-frames", "sound-packets", "frozen-state", "half-batch",
        "altered-answer"])
def test_dense_correct_sees_faults(tmp_path, fault, mix, want):
    line, notes = run_tiny(tmp_path, mix, fault, variant="dense")
    assert line["correct"] is want
    assert notes["checked_decisions"] > 0
    if want:
        assert line["failed"] == 0 and line["attempted"] > 0
        assert notes["window_xla_compiles"] == 0
