"""The plain reference of the seizure-detection decision path, and the bank.

Everything here is written from the published description of the three
stages and imports nothing of the program under test:

* spatial encode: each channel's LBP code selects an item HV, which is bound
  to the channel's electrode HV and bundled over the channels.  Sparse
  CompIM HVs hold one 1-bit per segment, kept as positions; binding adds the
  positions modulo the segment length and bundling ORs the channels.  Dense
  HVs are 50% random; binding is XOR and bundling the per-bit majority
  (strictly more than half the channels).
* temporal encode: the spatial HVs of one frame's cycles are counted per
  bit; sparse frames keep the bits whose count reaches the patient's
  temporal threshold, dense frames those set in more than half the cycles.
* AM search: sparse score = |frame AND class|, dense score = D - |frame XOR
  class|; the prediction is the first class of highest score.

The bank (the "weights") is made here from the run's seed: item and
electrode HVs on the device in one jitted call, then each patient's
temporal threshold calibrated and its class HVs trained one-shot on
labelled frames of that patient's generated record, both with the
reference encoder.  The program is handed these arrays; the reference
uses them as they are.

HVs are packed LSB-first into uint32 words: bit d is bit d % 32 of word
d // 32, and a sparse position p in segment s is bit s * seg_len + p.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 16  # frames per reference call: one compiled shape, bounded memory


@dataclass(frozen=True)
class Geometry:
    variant: str        # "sparse_compim" or "dense"
    channels: int
    lbp_bits: int
    dim: int
    segments: int
    window: int
    n_classes: int
    class_density: float

    @classmethod
    def from_config(cls, cfg: dict) -> "Geometry":
        if cfg["variant"] not in ("sparse_compim", "dense"):
            raise ValueError(f"variant {cfg['variant']!r} has no reference")
        if cfg.get("spatial_thinning", False):
            raise ValueError("the reference bundles sparse channels by OR")
        return cls(cfg["variant"], cfg["channels"], cfg["lbp_bits"],
                   cfg["dim"], cfg["segments"], cfg["window"],
                   cfg["n_classes"], cfg["class_density"])

    @property
    def codes(self) -> int:
        return 1 << self.lbp_bits

    @property
    def seg_len(self) -> int:
        return self.dim // self.segments

    @property
    def words(self) -> int:
        return self.dim // 32

    @property
    def sparse(self) -> bool:
        return self.variant != "dense"


def pack(bits) -> jax.Array:
    """(..., D) {0,1} -> (..., D//32) uint32, LSB-first."""
    b = jnp.asarray(bits).astype(jnp.uint32)
    b = b.reshape(*b.shape[:-1], b.shape[-1] // 32, 32)
    return jnp.sum(b << jnp.arange(32, dtype=jnp.uint32), axis=-1,
                   dtype=jnp.uint32)


def unpack(words) -> jax.Array:
    """(..., W) uint32 -> (..., W*32) int32 {0,1}, LSB-first."""
    w = jnp.asarray(words, jnp.uint32)
    bits = (w[..., None] >> jnp.arange(32, dtype=jnp.uint32)) & 1
    return bits.reshape(*w.shape[:-1], w.shape[-1] * 32).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("geo", "patients"))
def make_bank(key: jax.Array, geo: Geometry, patients: int) -> dict:
    """Item and electrode HVs of every patient, from one key.

    Sparse: ``item`` (P, C, codes, S) and ``elec`` (P, C, S) uint8 positions
    in [0, seg_len).  Dense: packed (P, C, codes, W) and (P, C, W) uint32.
    """
    k_item, k_elec = jax.random.split(key)
    if geo.sparse:
        item = jax.random.randint(
            k_item, (patients, geo.channels, geo.codes, geo.segments), 0,
            geo.seg_len, jnp.int32).astype(jnp.uint8)
        elec = jax.random.randint(
            k_elec, (patients, geo.channels, geo.segments), 0, geo.seg_len,
            jnp.int32).astype(jnp.uint8)
    else:
        item = pack(jax.random.bernoulli(
            k_item, 0.5, (patients, geo.channels, geo.codes, geo.dim)))
        elec = pack(jax.random.bernoulli(
            k_elec, 0.5, (patients, geo.channels, geo.dim)))
    return {"item": item, "elec": elec}


@functools.partial(jax.jit, static_argnames=("geo",))
def frame_counts(bank: dict, owner: jax.Array, codes: jax.Array,
                 geo: Geometry) -> jax.Array:
    """(B, window, C) codes of B whole frames, owned by patients ``owner``
    (B,) -> (B, D) int32 temporal counts."""
    codes = codes.astype(jnp.int32)
    ch = jnp.arange(geo.channels)
    o = owner[:, None, None]
    if geo.sparse:
        pos = (bank["item"][o, ch, codes].astype(jnp.int32)
               + bank["elec"][owner][:, None].astype(jnp.int32)
               ) % geo.seg_len                        # (B, T, C, S)
        hit = pos[..., None] == jnp.arange(geo.seg_len)   # (B, T, C, S, L)
        spatial = jnp.any(hit, axis=2).reshape(*codes.shape[:2], geo.dim)
    else:
        bound = bank["item"][o, ch, codes] ^ bank["elec"][owner][:, None]
        per_ch = unpack(bound)                        # (B, T, C, D)
        spatial = 2 * jnp.sum(per_ch, axis=2) > geo.channels
    return jnp.sum(spatial.astype(jnp.int32), axis=1)


@functools.partial(jax.jit, static_argnames=("geo",))
def decide(counts: jax.Array, threshold: jax.Array, class_hvs: jax.Array,
           geo: Geometry) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Counts (B, D), per-frame threshold (B,) and class HVs (B, K, W) ->
    (packed frame HVs (B, W), scores (B, K) int32, predictions (B,))."""
    if geo.sparse:
        bits = (counts >= threshold[:, None]).astype(jnp.int32)
    else:
        bits = (2 * counts > geo.window).astype(jnp.int32)
    cls = unpack(class_hvs)                           # (B, K, D)
    if geo.sparse:
        scores = jnp.sum(bits[:, None] & cls, axis=-1)
    else:
        scores = geo.dim - jnp.sum(bits[:, None] ^ cls, axis=-1)
    scores = scores.astype(jnp.int32)
    return pack(bits), scores, jnp.argmax(scores, axis=-1).astype(jnp.int32)


def counts_of_frames(bank: dict, owner: np.ndarray, frames: np.ndarray,
                     geo: Geometry) -> np.ndarray:
    """Temporal counts of (N, window, C) frames in blocks of ``BLOCK``."""
    n = len(frames)
    out = np.zeros((n, geo.dim), np.int32)
    for lo in range(0, n, BLOCK):
        hi = min(lo + BLOCK, n)
        f = np.zeros((BLOCK, geo.window, geo.channels), np.uint8)
        o = np.zeros((BLOCK,), np.int32)
        f[:hi - lo], o[:hi - lo] = frames[lo:hi], owner[lo:hi]
        out[lo:hi] = np.asarray(frame_counts(bank, jnp.asarray(o),
                                             jnp.asarray(f), geo))[:hi - lo]
    return out


def thin_threshold(counts: np.ndarray, density: float) -> int:
    """The smallest threshold >= 1 that keeps at most ``density`` of the
    counts' entries."""
    flat = np.asarray(counts).ravel()
    t = 1
    while np.count_nonzero(flat >= t) > density * flat.size:
        t += 1  # ends by max + 1 at the latest, which keeps nothing
    return t


def train(bank: dict, codes: np.ndarray, labels: np.ndarray,
          targets: list[float], geo: Geometry
          ) -> tuple[np.ndarray, np.ndarray]:
    """Calibrate each patient's temporal threshold, then train its class HVs
    one-shot.

    codes: (P, F, window, C) labelled training frames; labels: (P, F) class
    ids, every class present for every patient; targets: each patient's
    frame density target (sparse only).  Returns (thresholds (P,) int32,
    class HVs (P, n_classes, W) uint32).
    """
    p_n, f_n = labels.shape
    owner = np.repeat(np.arange(p_n, dtype=np.int32), f_n)
    counts = counts_of_frames(bank, owner, codes.reshape(
        p_n * f_n, geo.window, geo.channels), geo).reshape(p_n, f_n, geo.dim)
    thresholds = np.zeros((p_n,), np.int32)
    class_hvs = np.zeros((p_n, geo.n_classes, geo.words), np.uint32)
    for p in range(p_n):
        if geo.sparse:
            thresholds[p] = thin_threshold(counts[p], targets[p])
            bits = (counts[p] >= thresholds[p]).astype(np.int32)
        else:
            bits = (2 * counts[p] > geo.window).astype(np.int32)
        for k in range(geo.n_classes):
            mine = bits[labels[p] == k]
            if not len(mine):
                raise ValueError(f"patient {p}: class {k} has no frames")
            acc = mine.sum(axis=0)
            if geo.sparse:
                row = acc >= thin_threshold(acc, geo.class_density)
            else:
                row = 2 * acc > len(mine)
            class_hvs[p, k] = np.asarray(pack(row))
    return thresholds, class_hvs


def decisions(bank: dict, thresholds: np.ndarray, class_hvs: np.ndarray,
              owner: np.ndarray, frames: np.ndarray, geo: Geometry
              ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Reference decisions of (N, window, C) frames owned by ``owner`` (N,):
    (frame HVs (N, W) uint32, scores (N, K) int32, predictions (N,))."""
    counts = counts_of_frames(bank, owner, frames, geo)
    n = len(frames)
    hvs = np.zeros((n, geo.words), np.uint32)
    scores = np.zeros((n, geo.n_classes), np.int32)
    preds = np.zeros((n,), np.int32)
    for lo in range(0, n, BLOCK):
        hi = min(lo + BLOCK, n)
        c = np.zeros((BLOCK, geo.dim), np.int32)
        o = np.zeros((BLOCK,), np.int32)
        c[:hi - lo], o[:hi - lo] = counts[lo:hi], owner[lo:hi]
        h, s, p = decide(jnp.asarray(c), jnp.asarray(thresholds[o]),
                         jnp.asarray(class_hvs[o]), geo)
        hvs[lo:hi] = np.asarray(h)[:hi - lo]
        scores[lo:hi] = np.asarray(s)[:hi - lo]
        preds[lo:hi] = np.asarray(p)[:hi - lo]
    return hvs, scores, preds
