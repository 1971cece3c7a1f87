"""The one general traffic generator: a mix file's parameters -> pushes.

A mix (``bench/traffic/<name>.json``) says how sessions send their codes:

``tick_s``       seconds between due times: the loop is open, so pushes
                 are due on a fixed tick that never waits for the fleet.
``cycles``       cycles each session sends per push.
``bucket``       the fleet's chunk bucket those pushes run in, the one
                 bucket the run warms.
``phases``       frame boundaries are staggered over this many pushes:
                 during warm-up session i starts at push i mod phases.
``pool_cycles``  distinct cycles each session holds; pushes cycle through
                 them, so nothing is generated inside the window.
``record``       the per-patient recording the pool is cut from
                 (``pre_s``, ``ictal_s``, ``post_s`` seconds).
``check_sessions``  sessions whose every decision is compared with the
                 reference (drawn from the seed; the first and last
                 session always among them).
``about``        one line on who sends such traffic.

Every seed gets the same sizes and the same schedule; the seed draws only
the signal and where in its patient's record each session starts.
"""

from __future__ import annotations

import numpy as np

from bench import ieeg


class Traffic:
    """The pre-built pushes of one run.

    ``owner[i]`` is session i's patient (round-robin), ``start[i]`` the push
    at which it starts sending, and push ``j`` carries cycles
    ``[j * cycles, (j + 1) * cycles)`` modulo ``pool_cycles`` of every
    session's pool row.  Pushes before ``warm_pushes`` are warm-up.
    """

    def __init__(self, mix: dict, *, sessions: int, patients: int,
                 channels: int, lbp_bits: int, rng: np.random.Generator):
        self.cycles = int(mix["cycles"])
        self.phases = int(mix["phases"])
        self.pool_cycles = int(mix["pool_cycles"])
        if self.pool_cycles % self.cycles:
            raise ValueError("pool_cycles must be a multiple of cycles")
        self.owner = np.arange(sessions, dtype=np.int32) % patients
        self.start = np.arange(sessions) % self.phases
        self.warm_pushes = self.phases
        rec, _ = ieeg.records(rng, patients=patients, channels=channels,
                              bits=lbp_bits, **mix["record"])
        if rec.shape[1] < self.pool_cycles:
            raise ValueError("the record is shorter than pool_cycles")
        off = rng.integers(0, rec.shape[1], sessions)
        idx = (off[:, None] + np.arange(self.pool_cycles)) % rec.shape[1]
        self.pool = rec[self.owner[:, None], idx]     # (S, pool_cycles, C)
        n_check = min(int(mix["check_sessions"]), sessions)
        rest = rng.choice(np.arange(1, sessions - 1), max(n_check - 2, 0),
                          replace=False) if sessions > 2 else []
        self.checked = sorted({0, sessions - 1, *map(int, rest)})

    def batch(self, j: int) -> np.ndarray:
        """Push j's (S, cycles, C) codes: a view of the pool, no copy."""
        lo = (j * self.cycles) % self.pool_cycles
        return self.pool[:, lo:lo + self.cycles]

    def lengths(self, j: int) -> np.ndarray | None:
        """Push j's per-session cycle counts; None when all send."""
        if j >= self.phases - 1:
            return None
        return np.where(self.start <= j, self.cycles, 0)

    def sent(self, pushes: int) -> np.ndarray:
        """(S,) cycles each session has sent after ``pushes`` pushes."""
        return self.cycles * np.maximum(pushes - self.start, 0)

    def stream(self, i: int, pushes: int) -> np.ndarray:
        """Session i's codes over the first ``pushes`` pushes, in order."""
        return np.concatenate(
            [self.batch(j)[i] for j in range(int(self.start[i]), pushes)])


def training_frames(rng: np.random.Generator, *, patients: int,
                    channels: int, lbp_bits: int, window: int,
                    record: dict) -> tuple[np.ndarray, np.ndarray]:
    """Labelled frames to calibrate and train each patient on.

    Returns (codes (P, F, window, C), labels (P, F)): a frame is ictal (1)
    when most of its cycles are.
    """
    rec, ictal = ieeg.records(rng, patients=patients, channels=channels,
                              bits=lbp_bits, **record)
    f = rec.shape[1] // window
    codes = rec[:, :f * window].reshape(patients, f, window, channels)
    labels = (ictal[:f * window].reshape(f, window).mean(axis=1) >= 0.5)
    return codes, np.tile(labels.astype(np.int32), (patients, 1))
