"""Synthetic iEEG and its LBP codes: the benchmark's own signal generator.

The SWEC-ETHZ recordings cannot be shipped, so every patient's signal is
generated from the run's seed.  Interictal background is a broadband AR(2)
process; an ictal discharge adds a rhythmic 18-40 Hz wave on a recruited
subset of channels, ramped in over 2 s.  The 6-bit local binary pattern
(LBP) code at cycle t holds the signs of the six first differences ending
at t.  The model follows the program's ``data/ieeg.py`` and is kept here
so that the traffic cannot move with the program; it generates all
patients in one pass (the AR recursion runs over every patient's channels
at once).
"""

from __future__ import annotations

import numpy as np

FS = 512  # Hz, the SWEC-ETHZ short-term recordings' rate


def lbp_codes(x: np.ndarray, bits: int = 6) -> np.ndarray:
    """(..., T) signal -> (..., T - bits) uint8 codes:
    code[t] = sum_i 2**i * [x[t - i] > x[t - i - 1]], i = 0..bits-1."""
    d = (np.diff(x, axis=-1) > 0).astype(np.uint8)
    t_out = d.shape[-1] - bits + 1
    code = np.zeros((*d.shape[:-1], t_out), np.uint8)
    for i in range(bits):
        code |= d[..., bits - 1 - i: bits - 1 - i + t_out] << i
    return code


def _ar2(rng: np.random.Generator, rows: int, t: int) -> np.ndarray:
    a1, a2 = 0.9, -0.25
    e = rng.standard_normal((t + 64, rows)).astype(np.float32)
    x = np.zeros_like(e)
    for i in range(2, t + 64):
        x[i] = a1 * x[i - 1] + a2 * x[i - 2] + e[i]
    return x[64:].T                                   # (rows, t)


def records(rng: np.random.Generator, *, patients: int, channels: int,
            pre_s: float, ictal_s: float, post_s: float, bits: int = 6,
            fs: int = FS) -> tuple[np.ndarray, np.ndarray]:
    """One record per patient: background, then a seizure, then background.

    Returns ``(codes, ictal)``: (patients, T, channels) uint8 LBP codes and
    the (T,) bool mask of ictal cycles (the same onset for every patient).
    """
    t_pre, t_ict, t_post = int(pre_s * fs), int(ictal_s * fs), int(post_s * fs)
    t = t_pre + t_ict + t_post
    x = _ar2(rng, patients * channels, t).reshape(patients, channels, t)
    tt = np.arange(t_ict) / fs
    ramp = np.clip(np.arange(t_ict) / (2.0 * fs), 0.0, 1.0)
    for p in range(patients):
        freq = float(rng.uniform(18.0, 40.0)) * (
            1.0 + 0.15 * np.sin(2 * np.pi * 0.05 * tt))
        phase = 2 * np.pi * np.cumsum(freq) / fs
        wave = np.sin(phase) * (1.0 + 0.3 * np.sin(2 * np.pi * 2.7 * tt))
        part = rng.random(channels) < float(rng.uniform(0.4, 0.8))
        if not part.any():
            part[rng.integers(channels)] = True
        gains = part[:, None] * rng.uniform(6.0, 12.0, (channels, 1))
        jitter = 0.2 * rng.standard_normal((channels, t_ict))
        x[p, :, t_pre:t_pre + t_ict] += (
            gains * (wave[None] + jitter) * ramp).astype(np.float32)
    codes = lbp_codes(x, bits)                        # (P, C, T - bits)
    ictal = np.zeros(codes.shape[-1], bool)
    ictal[t_pre:t_pre + t_ict] = True
    return np.ascontiguousarray(codes.transpose(0, 2, 1)), ictal
