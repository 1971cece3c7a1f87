"""Bytes the fleet step must move, from shapes alone, and the peak table.

The step is bound by bytes: it does integer and bit work on the vector
units, for which no peak is published, and no matrix work.  So its share of
the chip is the least time the algorithm's bytes need at peak HBM bandwidth
over the measured step time.  The least bytes of one push do not depend on
how the step is written:

* codes in: 1 B per (cycle, channel) of every real session;
* the pre-bound table bank, read once per push on each device that steps
  sessions: sparse CompIM keeps one position byte per segment, dense HVs
  are packed bits;
* the (S, D) int32 temporal counts, read and written once;
* the frames and scores out, for the frames the push completed.
"""

from __future__ import annotations

import json
import os

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown kind is an error."""
    with open(PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add them "
                       f"to {os.path.basename(PEAKS)} with their source")
    return table[device_kind]


def table_bytes(cfg: dict, patients: int) -> int:
    """The pre-bound table bank of ``patients`` patients, in bytes."""
    codes = 1 << cfg["lbp_bits"]
    if cfg["variant"] == "dense":
        per_row = cfg["dim"] // 8
    else:
        per_row = cfg["segments"]
    return patients * cfg["channels"] * codes * per_row


def step_min_bytes(cfg: dict, *, sessions: int, cycles: int,
                   frames_out: int, patients: int, devices: int = 1) -> int:
    """Least bytes one push moves through HBM: ``sessions`` real sessions
    each sending ``cycles`` cycles, ``frames_out`` frames completed, the
    sessions spread over ``devices`` devices."""
    words = cfg["dim"] // 32
    return (sessions * cycles * cfg["channels"]
            + devices * table_bytes(cfg, patients)
            + 2 * sessions * cfg["dim"] * 4
            + frames_out * (words + cfg["n_classes"]) * 4)
