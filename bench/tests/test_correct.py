"""``correct`` on the CPU at a tiny size: a sound run passes; the timed
path broken underneath fails; the control fails; the first run's set-up
names no chip.  The harness's look for a chip is skipped."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import control, harness
from bench.tests import tiny


def frozen_state(fleet):
    """The step returns its state unchanged."""
    call = fleet._call_step

    def step(t_pad, sl, dev, args):
        kept = jax.tree.map(jnp.copy, args[0])
        res = call(t_pad, sl, dev, args)
        return (kept, *res[1:])

    fleet._call_step = step


def half_batch(fleet):
    """Half of the sessions' codes are left out of every push."""
    push = fleet.push_codes_raw

    def half(batch, lengths=None):
        n = batch.shape[0]
        ll = (np.full(n, batch.shape[1]) if lengths is None
              else np.array(lengths))
        ll[n // 2:] = 0
        return push(batch, ll)

    fleet.push_codes_raw = half


def altered_answer(fleet):
    """One bit of one frame HV flipped where the step produces it."""
    call = fleet._call_step

    def step(t_pad, sl, dev, args):
        state, out = call(t_pad, sl, dev, args)
        frames = out.frames.at[0, 0, 0].set(out.frames[0, 0, 0] ^ 1)
        return state, type(out)(frames=frames, scores=out.scores)

    fleet._call_step = step


def run_tiny(tmp_path, mix, patch=None, variant="sparse_compim"):
    cell = tiny.make_root(str(tmp_path), mix=mix, variant=variant)
    args = harness.parse(["--workload", cell, "--seed", str(2**31 + 77),
                          "--seconds", "0.5", "--trace", "0"])
    with tiny.no_persistent_cache():
        return harness.run(args, root=str(tmp_path),
                           t_start=time.perf_counter(), require_chip=False,
                           patch=patch)


@pytest.mark.parametrize("fault,mix,want", [
    (None, "frames", True),
    (None, "packets", True),
    (frozen_state, "packets", False),
    (half_batch, "frames", False),
    (altered_answer, "frames", False),
], ids=["sound-frames", "sound-packets", "frozen-state", "half-batch",
        "altered-answer"])
def test_correct_sees_faults(tmp_path, fault, mix, want):
    line, notes = run_tiny(tmp_path, mix, fault)
    assert line["correct"] is want
    assert notes["checked_decisions"] > 0
    assert list(line)[-1] == "checks"
    if want:
        assert line["failed"] == 0 and line["attempted"] > 0
        assert notes["window_xla_compiles"] == 0


@pytest.mark.parametrize("variant", ["sparse_compim", "dense"])
def test_control_fails_where_program_passes(tmp_path, variant):
    cell = tiny.make_root(str(tmp_path), variant=variant)
    with tiny.no_persistent_cache():
        rows = control.main(["--workload", cell, "--seeds", "3,4",
                             "--seconds", "0.5"], root=str(tmp_path),
                            require_chip=False)
    for row in rows:
        assert row["program_correct"] and row["program"]["mismatch"] == 0
        assert row["control"]["mismatch"] > 0
