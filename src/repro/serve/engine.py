"""Batched multi-patient serving engine for the unified HDC pipeline.

Serves a fleet of implant streams against one accelerator:

* ``ServingEngine`` — request batching across patients.  Requests are
  ``(patient_id, codes)``; the engine stacks every patient's design-time
  codebooks and class HVs into device-resident banks at construction, then
  serves each batch with ONE padded jitted dispatch (serve/dispatch.py): the
  per-request params/class rows are gathered from the banks INSIDE the
  computation, so a batch mixing any number of distinct patient datapaths
  costs one compile + one device call — the old per-datapath-group Python
  loop is gone.  Batch sizes are padded to power-of-two buckets so request
  traffic does not fan out recompiles.
* ``SeizureSession`` — streaming stateful per-patient API.  ``push(codes)``
  accepts arbitrary-length sub-window chunks and carries the temporal-bundling
  accumulator (the hardware's D x 8-bit counter file) across calls, emitting
  one decision per completed window; chunked pushes are bit-exact with the
  one-shot encoder.  For thousands of concurrent streams use
  ``serve.fleet.StreamingFleet`` — one jitted step for the whole fleet.

The batched encode path is code-domain end to end: the spatial stage is the
fused gather+bind+bundle over the pre-bound codebook bank
(``dispatch.owner_spatial_codes`` — the request's uint8 codes are the only
per-cycle operand, and the (B, F, win, C, W) bound expansion is never
materialized), and temporal bundling runs on the bit-plane popcount adder
(``hv.unpacked_counts`` routes window-length reductions through
``hv.bitplane_counts``), so no unpacked (..., window, D) expansion is
materialized either.

All per-patient configs in a bank must share one datapath
(``dispatch.datapath_key``): per-patient calibrated ``temporal_threshold``
(and training-only / deployment-only fields) may differ, anything that
changes the encoder datapath may not.
"""

from __future__ import annotations

import functools
import hashlib
import io
import json
from dataclasses import dataclass
from typing import Hashable, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import am, hv, online
from repro.core.pipeline import HDCConfig, HDCPipeline, _scores, spatial_encode
from repro.runtime import aot as aot_mod
from repro.serve import dispatch


@functools.partial(jax.jit, static_argnames=("cfg",))
def _serve_dispatch(tables, class_bank, param_owner, owner, thresholds,
                    codes, cfg: HDCConfig):
    """One padded batch: encode + gathered AM search + argmax, all jitted.

    codes: (B_pad, T, channels); owner: (B_pad,) patient rows into the class
    bank; param_owner: (B_pad,) rows into the stacked pre-bound codebook
    bank; thresholds: (B_pad,) per-request temporal-threshold registers."""
    frames = dispatch.owner_encode_frames(tables, param_owner, thresholds,
                                          codes, cfg)             # (B, F, W)
    cls = class_bank[owner]                                       # (B, C, W)
    scores = dispatch.owner_am_scores(frames, cls[:, None], cfg)  # (B, F, C)
    return frames, scores, am.am_predict(scores)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _session_adapt(state, class_hvs, frame_hv, scores, label, margin,
                   cfg: HDCConfig):
    """One gated online update for one session (core.online): feed the true
    label of the last emitted frame, refresh the class HVs from the counter
    file when the gate fires.  Returns (state, class_hvs, applied)."""
    bits = hv.unpack_bits(frame_hv, cfg.dim)
    new_state, applied = online.update(state, bits, label, scores,
                                       margin=margin)
    chvs = online.class_hvs_from_state(
        new_state, cfg, density=jnp.float32(cfg.class_density))
    return new_state, jnp.where(applied, chvs, class_hvs), applied


@functools.partial(jax.jit, static_argnames=("cfg",))
def _chunk_spatial_bits(params, chunk: jax.Array, cfg: HDCConfig) -> jax.Array:
    """(t, channels) codes -> (t, D) uint8 per-cycle spatial bits.

    Jitted per caller chunk length (ONE compile for a steady stream);
    window-boundary splitting happens on the concrete result array, so odd
    chunk/window ratios do not fan out into per-residue recompiles."""
    spat = spatial_encode(params, chunk, cfg)
    return hv.unpack_bits(spat, cfg.dim)


@dataclass(frozen=True)
class Decision:
    """Result for one request: per-frame scores/predictions (+ the frame HVs,
    exposed for regression testing and downstream post-processing)."""
    request_id: int
    patient_id: Hashable
    scores: np.ndarray       # (F, n_classes)
    predictions: np.ndarray  # (F,) int32; 1 = ictal for the 2-class system
    frames: np.ndarray       # (F, W) packed frame HVs


class ServingEngine:
    """Batched serving over a bank of trained per-patient pipelines.

    All pipelines must be trained (``class_hvs`` set) and share one datapath
    (``dispatch.datapath_key``); each patient keeps its own calibrated
    ``temporal_threshold`` and its own codebooks.  The dispatch runs the
    vectorized pure-XLA datapath, which is bit-exact with both pipeline
    backends.
    """

    def __init__(self, pipelines: Mapping[Hashable, HDCPipeline]):
        if not pipelines:
            raise ValueError("ServingEngine needs at least one pipeline")
        self._pipelines = dict(pipelines)
        self._cfg = dispatch.validate_bank(self._pipelines)
        self._pids = list(self._pipelines)
        self._pid_index = {pid: i for i, pid in enumerate(self._pids)}
        pipes = [self._pipelines[pid] for pid in self._pids]
        # stacked pre-bound codebook bank + per-patient row indices
        self._tables, self._param_rows = dispatch.stack_bound_tables(pipes)
        # stacked per-patient AM bank; the dispatch gathers rows per request
        self._bank = jnp.stack([p.class_hvs for p in pipes])      # (P, C, W)
        self._thresholds = np.asarray(
            [p.cfg.temporal_threshold for p in pipes], np.int32)
        # AOT executables (runtime/aot.py): ``prewarm`` fills these with
        # pre-compiled dispatches keyed by (padded batch, T); ``serve``
        # prefers them and falls back to the jitted dispatch
        self._exec: dict[tuple[int, int], jax.stages.Compiled] = {}

    @property
    def patient_ids(self) -> list:
        return list(self._pids)

    @property
    def aot_count(self) -> int:
        """Dispatch executables installed by ``prewarm`` (the jit cache
        stays cold when these serve)."""
        return len(self._exec)

    # -- ahead-of-time compilation (runtime/aot.py) ---------------------------

    def _aot_sig(self) -> str:
        h = hashlib.sha256()
        h.update(repr(self._cfg).encode())
        h.update(str(tuple(jnp.shape(self._tables))).encode())
        h.update(str(tuple(jnp.shape(self._bank))).encode())
        h.update(str(bool(jax.config.jax_enable_x64)).encode())
        return h.hexdigest()[:10]

    def _aot_name(self, b_pad: int, t: int) -> str:
        return f"engine.{self._cfg.variant}.b{b_pad}.t{t}.{self._aot_sig()}"

    def _dispatch_avals(self, b_pad: int, t: int) -> tuple:
        def sds(x):
            return jax.ShapeDtypeStruct(jnp.shape(x), jnp.asarray(x).dtype)

        return (
            sds(self._tables),
            sds(self._bank),
            jax.ShapeDtypeStruct((b_pad,), self._param_rows.dtype),
            jax.ShapeDtypeStruct((b_pad,), jnp.int32),
            jax.ShapeDtypeStruct((b_pad,), self._thresholds.dtype),
            jax.ShapeDtypeStruct((b_pad, t, self._cfg.channels), jnp.uint8),
        )

    @staticmethod
    def _pow2_buckets(max_batch: int) -> list[int]:
        top = 1 << (max(1, int(max_batch)) - 1).bit_length()
        return [1 << i for i in range(top.bit_length())]

    def aot_entries(self, batch_sizes: Sequence[int], t: int
                    ) -> list[aot_mod.AOTEntry]:
        """AOT entries for this engine's dispatch at the power-of-two batch
        buckets covering ``batch_sizes`` and request length ``t`` (cfg rides
        along as the jit's static argument)."""
        buckets = sorted({1 << (max(1, int(b)) - 1).bit_length()
                          for b in batch_sizes})
        return [aot_mod.AOTEntry(
                    name=self._aot_name(b, t),
                    fn=_serve_dispatch,
                    args=self._dispatch_avals(b, t),
                    static=(self._cfg,))
                for b in buckets]

    def prewarm(self, max_batch: int, t: int,
                *, aot: aot_mod.AOTArtifact | None = None) -> dict[str, int]:
        """Build the dispatch executable for every power-of-two batch bucket
        up to ``max_batch`` (request length ``t``) before traffic arrives —
        loaded from a deploy artifact when one is given, pre-lowered and
        compiled otherwise.  Returns {"loaded", "compiled", "skipped"}."""
        stats = {"loaded": 0, "compiled": 0, "skipped": 0}
        for b_pad in self._pow2_buckets(max_batch):
            key = (b_pad, t)
            if key in self._exec:
                stats["skipped"] += 1
                continue
            compiled = None
            if aot is not None:
                compiled = aot.compile(self._aot_name(b_pad, t),
                                       *self._dispatch_avals(b_pad, t))
                if compiled is not None:
                    stats["loaded"] += 1
            if compiled is None:
                compiled = _serve_dispatch.lower(
                    *self._dispatch_avals(b_pad, t), self._cfg).compile()
                stats["compiled"] += 1
            self._exec[key] = compiled
        return stats

    def serve(self, requests: Sequence[tuple[Hashable, jax.Array]]) -> list[Decision]:
        """Serve one batch of ``(patient_id, codes)`` requests.

        ``codes``: (T, channels) uint8 LBP codes, same T across the batch,
        T >= window (sub-window chunks belong to ``SeizureSession``); cycles
        past the last full window are truncated, like ``encode_frames``.
        Returns one Decision per request, in request order.
        """
        if not requests:
            return []
        pids, codes = zip(*requests)
        for pid in pids:
            if pid not in self._pid_index:
                raise KeyError(f"unknown patient id {pid!r}")
        shapes = {tuple(jnp.shape(c)) for c in codes}
        if len(shapes) > 1:
            # a shorter request's frames would silently broadcast into the
            # (B, F, W) buffer below — reject loudly instead
            raise ValueError(f"all requests in a batch must share one codes "
                             f"shape; got {sorted(shapes)}")
        t = next(iter(shapes))[0]
        if t < self._cfg.window:
            raise ValueError(
                f"request codes span {t} cycles < one {self._cfg.window}-cycle "
                "window, which would yield zero frames; use SeizureSession "
                "for sub-window streaming chunks")

        # pad the batch to a power-of-two bucket (padded rows replay patient
        # row 0 on zero codes) so batch-size traffic compiles once per bucket
        b = len(requests)
        b_pad = 1 << (b - 1).bit_length()
        owner = np.zeros(b_pad, np.int32)
        owner[:b] = [self._pid_index[pid] for pid in pids]
        first = np.asarray(codes[0])
        batch = np.zeros((b_pad, *first.shape), first.dtype)
        for i, c in enumerate(codes):
            batch[i] = np.asarray(c)

        args = (self._tables, self._bank,
                jnp.asarray(self._param_rows[owner]), jnp.asarray(owner),
                jnp.asarray(self._thresholds[owner]), jnp.asarray(batch))
        fn = self._exec.get((b_pad, t))
        if fn is not None and not aot_mod.accepts(fn, args):
            self._exec.pop((b_pad, t))
            fn = None
        out = fn(*args) if fn is not None else _serve_dispatch(*args,
                                                               self._cfg)
        frames, scores, preds = out

        frames_np, scores_np, preds_np = (np.asarray(x) for x in
                                          (frames, scores, preds))
        return [Decision(request_id=i, patient_id=pid, scores=scores_np[i],
                         predictions=preds_np[i], frames=frames_np[i])
                for i, pid in enumerate(pids)]


# ---------------------------------------------------------------------------
# streaming sessions
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class FrameDecision:
    frame_index: int
    scores: np.ndarray        # (n_classes,)
    prediction: int           # argmax class id
    frame_hv: np.ndarray      # (W,) packed


@dataclass(frozen=True)
class SessionSnapshot:
    """Compact host-side capture of ONE streaming session's full state.

    This is the unit of reconnect-with-state: ``SeizureSession.snapshot()``
    and ``ElasticFleet.evict(..., with_state=True)`` both produce one, and
    either consumer (``SeizureSession.from_snapshot`` or
    ``ElasticFleet.admit(pid, snapshot=...)``) resumes the stream
    bit-exactly where it left off — mid-window accumulator, adapted AM
    counter files, and the last emitted frame (so ``adapt`` feedback
    survives the reconnect) all round-trip.  The nine array/scalar fields
    mirror one row of ``serve.fleet.FleetState``; ``channel_mask``
    additionally carries the session's electrode quarantine (a
    ``channel_masking`` fleet's ``set_channel_mask`` row — electrode
    health must survive a reconnect) and stays None for sessions without
    one, keeping old blobs loadable.

    ``to_bytes``/``from_bytes`` serialize through one compressed ``.npz``
    blob (a few KB at paper geometry) for transport or queueing; the
    patient id must be JSON-representable to cross that boundary.
    """

    patient_id: Hashable
    counts: np.ndarray             # (D,) int32 temporal accumulator
    filled: int                    # cycles toward the next frame (< window)
    frame_index: int               # frames emitted so far
    class_rows: np.ndarray         # (C, W) uint32 (possibly adapted) AM
    am_counts: np.ndarray | None   # (C, D) int32 online counter file
    am_n: np.ndarray | None        # (C,) int32 frames bundled per class
    last_frame: np.ndarray         # (W,) uint32 last emitted frame HV
    last_scores: np.ndarray        # (C,) int32 its AM scores
    has_frame: int                 # 1 once a frame has been emitted
    channel_mask: np.ndarray | None = None  # (channels,) uint8 live mask

    def to_bytes(self) -> bytes:
        arrays = {
            "counts": np.asarray(self.counts, np.int32),
            "class_rows": np.asarray(self.class_rows, np.uint32),
            "last_frame": np.asarray(self.last_frame, np.uint32),
            "last_scores": np.asarray(self.last_scores, np.int32),
            "scalars": np.asarray(
                [self.filled, self.frame_index, self.has_frame,
                 int(self.am_counts is not None)], np.int64),
            "pid": np.frombuffer(
                json.dumps(self.patient_id).encode(), np.uint8),
        }
        if self.am_counts is not None:
            arrays["am_counts"] = np.asarray(self.am_counts, np.int32)
            arrays["am_n"] = np.asarray(self.am_n, np.int32)
        if self.channel_mask is not None:
            arrays["channel_mask"] = np.asarray(self.channel_mask, np.uint8)
        buf = io.BytesIO()
        np.savez_compressed(buf, **arrays)
        return buf.getvalue()

    @classmethod
    def from_bytes(cls, blob: bytes) -> "SessionSnapshot":
        with np.load(io.BytesIO(blob)) as d:
            filled, fidx, has_frame, has_am = (int(x) for x in d["scalars"])
            return cls(
                patient_id=json.loads(bytes(d["pid"]).decode()),
                counts=d["counts"], filled=filled, frame_index=fidx,
                class_rows=d["class_rows"],
                am_counts=d["am_counts"] if has_am else None,
                am_n=d["am_n"] if has_am else None,
                last_frame=d["last_frame"], last_scores=d["last_scores"],
                has_frame=has_frame,
                # key-presence guard: blobs from before channel masking
                # (or from unmasked sessions) simply lack the array
                channel_mask=(d["channel_mask"]
                              if "channel_mask" in d.files else None))


class SeizureSession:
    """Stateful streaming detector for one patient.

    Mirrors the hardware's always-on operation: LBP codes arrive a few cycles
    at a time, the temporal accumulator integrates them, and every ``window``
    cycles a frame HV is thresholded out and scored.  ``push`` accepts chunks
    of ANY length (sub-window, window-crossing, multi-window) and returns the
    decisions completed by that chunk; accumulator state carries over, so
    chunked pushes are bit-exact with a one-shot ``encode_frames`` of the
    concatenated stream.

    ``adapt(label)`` feeds back the true label of the LAST emitted frame:
    a confidence-gated online update (core.online) adds the frame's bits to
    the true class's counter file, subtracts them from the rival's, and
    re-thresholds this session's class HVs in place — the pipeline object
    itself stays immutable.  Requires a pipeline trained via
    ``train_one_shot`` / ``fit_iterative`` (they carry the ``am_state``
    counter file the update continues from).

    One Python object + one jit dispatch per stream per push: for
    population-scale concurrency use ``serve.fleet.StreamingFleet``, which is
    bit-exact with this class (including ``adapt``) and advances every
    stream in one jitted step.
    """

    def __init__(self, pipeline: HDCPipeline):
        if pipeline.class_hvs is None:
            raise ValueError("SeizureSession needs a trained pipeline")
        self._pipe = pipeline
        cfg = pipeline.cfg
        self._counts = np.zeros((cfg.dim,), np.int32)
        self._filled = 0
        self._frame_index = 0
        # per-session adaptive AM: seeded from the pipeline, updated by adapt
        self._class_hvs = pipeline.class_hvs
        self._online = pipeline.am_state
        self._last: FrameDecision | None = None

    @property
    def cycles_buffered(self) -> int:
        """Cycles accumulated toward the next (incomplete) frame."""
        return self._filled

    @property
    def class_hvs(self) -> jax.Array:
        """This session's (possibly adapted) class HVs."""
        return self._class_hvs

    @property
    def am_state(self) -> online.OnlineAMState | None:
        """This session's (possibly adapted) AM counter-file state."""
        return self._online

    def _emit_frame(self) -> FrameDecision:
        cfg = self._pipe.cfg
        counts = jnp.asarray(self._counts[None])
        if cfg.variant == "dense":
            frame = hv.majority_pack(counts, cfg.window, cfg.dim)[0]
        else:
            frame = hv.threshold_pack(counts, cfg.temporal_threshold)[0]
        scores = np.asarray(_scores(frame[None], self._class_hvs, cfg))[0]
        dec = FrameDecision(frame_index=self._frame_index, scores=scores,
                            prediction=int(np.argmax(scores)),
                            frame_hv=np.asarray(frame))
        self._counts = np.zeros_like(self._counts)
        self._filled = 0
        self._frame_index += 1
        self._last = dec
        return dec

    def adapt(self, label: int, *, margin: float = 0.0) -> bool:
        """Online update from the true label of the last emitted frame.

        Returns True when the gated update fired (prediction wrong, or its
        score lead over the rival class below ``margin``); the session's
        class HVs are refreshed from the updated counter file.  Bit-exact
        with ``StreamingFleet.adapt`` on the same stream."""
        if self._last is None:
            raise ValueError("no frame emitted yet; adapt() labels the most "
                             "recent decision")
        if self._online is None:
            raise ValueError(
                "pipeline carries no am_state counter file; train it with "
                "train_one_shot or fit_iterative before adapting")
        cfg = self._pipe.cfg
        if not 0 <= label < cfg.n_classes:
            raise ValueError(f"label {label} not in [0, {cfg.n_classes})")
        self._online, self._class_hvs, applied = _session_adapt(
            self._online, self._class_hvs,
            jnp.asarray(self._last.frame_hv), jnp.asarray(self._last.scores),
            jnp.asarray(label, jnp.int32), jnp.asarray(margin, jnp.float32),
            cfg)
        return bool(applied)

    def snapshot(self, patient_id: Hashable = None) -> SessionSnapshot:
        """Capture this session's full streaming state as a
        ``SessionSnapshot`` (the session itself is untouched).  A session
        rebuilt from it — here or admitted into an ``ElasticFleet`` slot —
        continues the stream bit-exactly, including mid-window accumulator
        fill and adapted AM state."""
        cfg = self._pipe.cfg
        c = cfg.n_classes
        last = self._last
        return SessionSnapshot(
            patient_id=patient_id,
            counts=self._counts.astype(np.int32, copy=True),
            filled=int(self._filled),
            frame_index=int(self._frame_index),
            class_rows=np.asarray(self._class_hvs, np.uint32),
            am_counts=(np.asarray(self._online.counts, np.int32)
                       if self._online is not None else None),
            am_n=(np.asarray(self._online.n, np.int32)
                  if self._online is not None else None),
            last_frame=(np.asarray(last.frame_hv, np.uint32)
                        if last is not None
                        else np.zeros((cfg.words,), np.uint32)),
            last_scores=(np.asarray(last.scores, np.int32)
                         if last is not None
                         else np.zeros((c,), np.int32)),
            has_frame=int(last is not None))

    @classmethod
    def from_snapshot(cls, pipeline: HDCPipeline,
                      snap: SessionSnapshot) -> "SeizureSession":
        """Rebuild a session from a ``snapshot()`` against the SAME trained
        pipeline; the reconnect counterpart of ``snapshot``."""
        sess = cls(pipeline)
        sess._counts = np.asarray(snap.counts, np.int32).copy()
        sess._filled = int(snap.filled)
        sess._frame_index = int(snap.frame_index)
        sess._class_hvs = jnp.asarray(np.asarray(snap.class_rows, np.uint32))
        if snap.am_counts is not None:
            sess._online = online.OnlineAMState(
                counts=jnp.asarray(np.asarray(snap.am_counts, np.int32)),
                n=jnp.asarray(np.asarray(snap.am_n, np.int32)))
        if snap.has_frame:
            scores = np.asarray(snap.last_scores, np.int32)
            sess._last = FrameDecision(
                frame_index=int(snap.frame_index) - 1,
                scores=scores, prediction=int(np.argmax(scores)),
                frame_hv=np.asarray(snap.last_frame, np.uint32))
        return sess

    def push(self, codes: jax.Array) -> list[FrameDecision]:
        """Feed (t, channels) uint8 codes; returns decisions for every frame
        completed by this chunk (possibly empty).

        Codes are validated at the ingest boundary: a NaN-corrupted or
        mis-wired preprocessor that ships codes outside the item-memory
        alphabet fails HERE with a clear error instead of silently
        clamping into the wrong codebook rows."""
        cfg = self._pipe.cfg
        host = np.asarray(codes)
        if host.ndim != 2 or host.shape[1] != cfg.channels:
            raise ValueError(
                f"push needs a (t, {cfg.channels}) code chunk, got "
                f"{host.shape}")
        if not np.issubdtype(host.dtype, np.integer):
            raise ValueError(
                f"push needs integer LBP codes, got dtype {host.dtype} "
                "(run raw signal through data.ieeg.lbp_codes_np first; "
                "it rejects NaN/Inf and clamps ADC rails)")
        if host.size and (host.min() < 0 or host.max() >= cfg.codes):
            bad = host[(host < 0) | (host >= cfg.codes)][0]
            raise ValueError(
                f"code {int(bad)} outside the item-memory alphabet "
                f"[0, {cfg.codes}); corrupt ingest would silently clamp "
                "into the wrong codebook rows")
        codes = jnp.asarray(host.astype(np.uint8, copy=False))
        t = codes.shape[0]
        out: list[FrameDecision] = []
        if t == 0:
            return out
        bits = np.asarray(_chunk_spatial_bits(self._pipe.params, codes, cfg))
        pos = 0
        while pos < t:
            take = min(cfg.window - self._filled, t - pos)
            self._counts += bits[pos:pos + take].sum(axis=0, dtype=np.int32)
            self._filled += take
            pos += take
            if self._filled == cfg.window:
                out.append(self._emit_frame())
        return out
