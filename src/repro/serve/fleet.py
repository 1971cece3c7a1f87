"""Sharded streaming fleet: thousands of concurrent sessions, one jitted step.

``SeizureSession`` (serve/engine.py) is the single-patient streaming API: a
host-side Python object per stream, one jit dispatch + numpy accumulator
update per push.  That shape cannot serve a population — S streams cost S
Python loops per service interval.  ``StreamingFleet`` vectorizes S concurrent
sessions into ONE device-resident pytree:

* ``counts``       (S, D) int32 — the stacked temporal-accumulator register
                   files (the hardware's D x 8-bit counter bank, one per
                   implant),
* ``filled``       (S,)   int32 — cycles accumulated toward each next frame,
* ``frame_index``  (S,)   int32 — frames emitted so far per stream,

plus per-stream operands gathered once at construction: each session's class
HVs from the stacked (P, C, W) AM bank, its calibrated temporal threshold,
and its row into the stacked unique-params codebook bank.

One jitted ``step(state, chunk, lengths)`` advances ALL sessions, and the
whole step consumes RAW uint8 LBP codes end to end (the CODE domain —
1 byte per (cycle, channel) of host->device traffic): the spatial stage is
a fused gather+bind+bundle out of the pre-bound per-(channel, code)
codebook bank (``dispatch.owner_spatial_codes`` — binding folded into the
table build, the reduction fused into the gather consumer, the
(S, T, C, W) bound expansion never materialized), ``hv.time_pack`` flips
the per-cycle packed HVs into bit planes (one uint32 = 32 cycles of one
bit position), and per-frame-slot temporal counts fall out of popcount
prefix sums — no unpacked (S, block, D) float tensor, no f32 GEMM, no
per-cycle branching.  WHEN each session's window boundaries fall is a pure
function of ``(filled, lengths)``, so the emission schedule is computed
INSIDE the jitted step (at most K = ceil(t_pad / window) completed slots
plus a leftover tail per step); the host ships only the codes and the (S,)
chunk lengths and keeps O(S) mirrors for collection.  ONE
threshold/majority-pack + AM search scores all K frame slots of all
sessions together.  ``lengths`` masks the padding — sessions push chunks
of ANY length, including 0 — and chunk lengths are bucketed/padded to a
fixed set so steady streams compile once per bucket.  With
``backend="pallas"`` the table gather + spatial bundle + bit transpose +
masked-popcount accumulate run as ONE fused VMEM kernel with the CompIM
table bank resident in VMEM (codes in, per-slot counts out).

The step is memory-bound, so the fleet partitions sessions into TILES
(``derive_tile``: sized from the device's reported memory geometry, the
``REPRO_FLEET_TILE`` env var, or the cache-tuned ``DEFAULT_TILE=256`` CPU
fallback) that keep each step's gather/bit-plane temporaries
cache-resident — throughput now grows with S instead of plateauing — and
round-robins tiles over the local devices: per-tile steps dispatch
asynchronously, so multi-device hosts advance tiles concurrently with no
SPMD machinery.  All tiles share one jitted executable per chunk bucket.
Ingest is staged through per-tile pinned uint8 code rings: one vectorized
slice write + one device put per tile per round (``push_codes`` skips even
the ragged-list packing for pre-stacked steady streams).

Online adaptation (core.online): the fleet carries a stacked (S, C, D)
counter-file bank — each session's private, adaptable view of its patient's
AM — plus per-session class-HV rows refreshed from it.  ``adapt(labels)``
applies ONE jitted confidence-gated update across all S sessions (labels
``-1`` mask out sessions with no feedback), bit-exact with a per-session
``SeizureSession.adapt`` loop; the step itself tracks each session's last
emitted frame/scores so the adapt operands never round-trip the host.

Durability: ``save``/``restore`` round-trip the full ``FleetState``
(streaming accumulators + online AM banks) through ``ckpt.checkpoint`` —
atomic-rename directories, elastic re-placement under the current mesh — so
an interrupted fleet resumes mid-stream bit-exactly
(``launch/serve.py --hdc-fleet --ckpt-dir ... --resume``).

Sharding: pass ``mesh=`` to place the fleet on a device mesh — session-axis
state and operands shard along the ``batch`` logical axis (-> ``data`` mesh
axis per runtime/sharding.py), the codebook/AM banks replicate, and the step
stays a single SPMD program.

Decisions are bit-exact with per-patient ``SeizureSession`` loops for all
variants (tested in tests/test_fleet.py); benchmarks/bench_fleet.py measures
the sessions-per-second win over the looped baseline.
"""

from __future__ import annotations

import functools
import hashlib
import json
import os
import warnings
from dataclasses import dataclass, replace
from typing import Hashable, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.ckpt import checkpoint as ckpt
from repro.core import hv, online
from repro.core.pipeline import HDCConfig, HDCPipeline
from repro.kernels.hdc_fleet import ops as fleet_ops
from repro.reliability import ecc as rel_ecc
from repro.reliability import faults as rel_faults
from repro.reliability.faults import FaultConfig, FaultPlan
from repro.runtime import aot as aot_mod
from repro.runtime import sharding as shd
from repro.serve import dispatch
from repro.serve.engine import FrameDecision

DEFAULT_BUCKETS = (32, 64, 128, 256)
# sessions per device step: the step is memory-bound, and tiles this size
# keep its gather/bit-plane temporaries cache-resident (one 1024-session
# step measures ~1.7x slower than four 256-session steps on CPU).  Session
# capacity is provisioned in WHOLE tiles: a fleet pads up to a multiple of
# ``tile``, so every step runs the ONE tile-shaped executable per chunk
# bucket, a fleet grows within its provisioned capacity without
# recompiling, and step latency is predictable.  Fleets smaller than a
# quarter tile compile exact shapes instead (tile-padding down there
# would dominate their cost, and latency-sensitive few-stream users are
# better served by exact shapes or by SeizureSession directly).
# DEFAULT_TILE is the CPU-cache-tuned fallback; ``derive_tile`` sizes the
# tile from the device's memory geometry when it exposes one.
DEFAULT_TILE = 256


def derive_tile(cfg: HDCConfig, *, max_bucket: int = DEFAULT_BUCKETS[-1],
                device=None) -> int:
    """Sessions-per-tile default for this device and config geometry.

    Resolution order:

    1. ``REPRO_FLEET_TILE`` env var (explicit operator override);
    2. devices that report a memory size (``device.memory_stats()``:
       TPU/GPU ``bytes_limit``): the largest power-of-two tile whose
       per-session working set — streaming state, online AM bank, staged
       chunk codes and the step's bit-plane temporaries — fills at most
       ~1/16 of device memory, clamped to [64, 4096] (the banks, the other
       round-robin tiles and the executables share the rest);
    3. otherwise (CPU hosts expose no memory stats): ``DEFAULT_TILE``, the
       L2/L3-cache-tuned measurement from this repo's benchmark container.

    The ``StreamingFleet(tile=...)`` constructor argument bypasses all of
    this.
    """
    env = os.environ.get("REPRO_FLEET_TILE", "")
    if env:
        try:
            tile = int(env)
        except ValueError:
            raise ValueError(
                f"REPRO_FLEET_TILE={env!r} is not an integer; expected a "
                "power of two in [64, 4096]") from None
        if not (64 <= tile <= 4096 and tile & (tile - 1) == 0):
            raise ValueError(
                f"REPRO_FLEET_TILE={env!r} must be a power of two in "
                "[64, 4096] (the range derive_tile itself produces); use "
                "StreamingFleet(tile=...) for out-of-range experiments")
        return tile
    if device is None:
        device = jax.local_devices()[0]
    try:
        stats = device.memory_stats() or {}
    except Exception:  # backends without memory introspection
        stats = {}
    limit = stats.get("bytes_limit") or stats.get("bytes_reservable_limit")
    if not limit:
        return DEFAULT_TILE
    per_session = (
        cfg.dim * 4 * (1 + cfg.n_classes)          # counts + online AM bank
        + cfg.n_classes * cfg.words * 4            # class-HV rows
        + max_bucket * cfg.channels                # staged uint8 codes
        + 8 * max_bucket * cfg.words               # bit-plane temporaries
    )
    budget = int(limit) // 16
    tile = max(64, min(4096, budget // max(per_session, 1)))
    return 1 << (tile.bit_length() - 1)            # floor to a power of two


@dataclass(frozen=True)
class FleetState:
    """Device-resident state of all S sessions (a pytree of stacked leaves).

    The first block is the streaming state; the second is the online
    continual-learning state — per-session counter-file AM banks, the class
    rows re-thresholded from them, and the last emitted frame's operands
    (what ``adapt`` consumes).  Checkpointing the whole dataclass captures a
    fleet mid-stream."""

    counts: jax.Array  # (S, D) int32 temporal accumulators
    filled: jax.Array  # (S,) int32 cycles toward each next frame
    frame_index: jax.Array  # (S,) int32 frames emitted so far
    class_rows: jax.Array  # (S, C, W) uint32 per-session (adaptive) AM rows
    am_counts: jax.Array  # (S, C, D) int32 online counter-file bank
    am_n: jax.Array  # (S, C) int32 frames bundled per class
    last_frame: jax.Array  # (S, W) uint32 last emitted frame HV
    last_scores: jax.Array  # (S, C) int32 last emitted frame's AM scores
    has_frame: jax.Array  # (S,) int32 1 once a session has emitted


@dataclass(frozen=True)
class FleetOut:
    """Raw step outputs: one row per potential frame slot (K per step); the
    host-side schedule knows which (session, slot) pairs really emitted."""

    frames: jax.Array  # (S, K, W) uint32 packed frame HVs
    scores: jax.Array  # (S, K, C) int32 AM scores


@dataclass(frozen=True)
class FleetRound:
    """One step's raw results plus the host-side schedule needed to read
    them: ``tiles`` holds each session tile's ``FleetOut`` as DEVICE arrays
    (no forced sync), and ``(session, slot)`` pairs with ``slot <
    n_emit[session]`` are real emissions with frame index
    ``frame_base[session] + slot``."""

    tiles: tuple[FleetOut, ...]  # per-tile (tile_s, K, ...) device outputs
    n_emit: np.ndarray      # (S,) frames emitted this round
    frame_base: np.ndarray  # (S,) frame index of each session's slot 0


for _cls, _fields in (
    (FleetState, ["counts", "filled", "frame_index", "class_rows",
                  "am_counts", "am_n", "last_frame", "last_scores",
                  "has_frame"]),
    (FleetOut, ["frames", "scores"]),
):
    jax.tree_util.register_dataclass(_cls, data_fields=_fields, meta_fields=[])
    # the same pytrees cross the jax.export boundary in the AOT deploy
    # artifacts (runtime/aot.py); no-op when export serialization is absent
    aot_mod.register_pytree_serialization(
        _cls, f"repro.serve.fleet.{_cls.__name__}")

# logical sharding axes per FleetState leaf: session state splits along the
# batch axis, everything trailing replicates (used by the step's constraints
# and by the elastic checkpoint restore)
_STATE_AXES = {
    "counts": ("batch", None),
    "filled": ("batch",),
    "frame_index": ("batch",),
    "class_rows": ("batch", None, None),
    "am_counts": ("batch", None, None),
    "am_n": ("batch", None),
    "last_frame": ("batch", None),
    "last_scores": ("batch", None),
    "has_frame": ("batch",),
}


def _fleet_step(
    state: FleetState,
    tables: jax.Array,
    owner: jax.Array,
    thresholds: jax.Array,
    chunk: jax.Array,
    lengths: jax.Array,
    fault_ber: jax.Array | None = None,
    fault_seed: jax.Array | None = None,
    chan_mask: jax.Array | None = None,
    *,
    cfg: HDCConfig,
    ctx: shd.ShardCtx,
    use_kernel: bool,
    faults: FaultPlan | None = None,
    masked: bool = False,
) -> tuple:
    """Advance all S sessions by one padded chunk batch.

    chunk: (S, t_pad, channels) uint8 RAW LBP codes — the only per-cycle
    payload the host ever ships; lengths: (S,) int32 valid cycles per
    session.  The emission schedule is computed HERE from
    ``(state.filled, lengths)`` — the host ships no masks — and the whole
    datapath stays in the code/packed/bit-plane domain (kernels/hdc_fleet):
    the spatial stage is a fused gather+bind+bundle out of the pre-bound
    codebook bank (``dispatch.owner_spatial_codes``, never materializing
    the (S, T, C, W) bound expansion), temporal counts are popcount prefix
    sums at frame-slot boundaries, or ONE fused VMEM kernel does all of it
    when ``use_kernel``.  Frames score against ``state.class_rows``
    (refreshed by ``adapt``), and the step records each emitting session's
    last frame HV + scores — the operands a later ``adapt`` call consumes,
    captured inside the same jitted program.

    Fault injection (repro.reliability): with a static ``faults`` plan the
    step additionally takes the traced ``fault_ber`` (3,) BER vector and
    scalar ``fault_seed``, derives per-component PRNG keys INSIDE the jit,
    and corrupts the memory READS of the enabled targets — the codebook
    bank (before the gather / via the fused kernel's ``tables_xor`` hook),
    the AM class rows (optionally through the ECC word codec, whose
    corrected rows then score), and the carried temporal accumulators (low
    counter bits only).  Storage is never mutated.  The step then returns
    a third output: the (S, 3) [corrected, detected, uncorrectable] ECC
    word counts of this read (zeros when no ECC scheme is configured).
    With ``faults=None`` (the default) none of this is traced and the step
    is the unmodified two-output program; with faults enabled but BER 0
    every mask is all-zero and the outputs are bit-exact with it.

    Channel masking (repro.reliability.channels): with the static
    ``masked`` flag the step additionally takes the traced ``chan_mask``
    (S, channels) uint8 operand — 1 = live, 0 = quarantined electrode —
    and the spatial stage drops masked channels from the bundle with
    renormalized count denominators (dispatch.owner_spatial_codes /
    the fused kernel's mask operand).  The mask is DATA: walking masks
    never recompiles, and an all-live mask is bit-exact with the
    unmasked step.  ``masked=False`` (the default) keeps the jaxpr
    byte-identical to the mask-free program.
    """
    s, t_pad, _ = chunk.shape
    counts_in = state.counts
    tables_xor = None
    if not masked:
        chan_mask = None
    if faults is not None:
        k_tab, k_am, k_cnt = rel_faults.component_keys(fault_seed)
        if faults.tables:
            tables_xor = rel_faults.xor_mask(tables, k_tab, fault_ber[0],
                                             mode=faults.mode)
        if faults.counts:
            counts_in = rel_faults.flip_counts(
                counts_in, k_cnt, fault_ber[2],
                bits=rel_faults.counter_bits(faults, cfg.window),
                mode=faults.mode)
    if use_kernel:
        # fused kernel: codes in, slot counts out — the table gather,
        # spatial bundle, bit transpose and masked popcount stay in VMEM
        seg = fleet_ops.fleet_counts_fused(tables, owner, chunk,
                                           state.filled, lengths, cfg,
                                           tables_xor=tables_xor,
                                           chan_mask=chan_mask)
    else:
        if tables_xor is not None:
            tables = tables ^ tables_xor
        words = dispatch.owner_spatial_codes(tables, owner, chunk, cfg,
                                             chan_mask)
        seg = fleet_ops.fleet_counts(words, state.filled, lengths, cfg)
    seg = shd.constrain(seg, ("batch", None, None), ctx)  # (S, K+1, D) int32

    n_emit = (state.filled + lengths) // cfg.window  # (S,)
    # the carried accumulator belongs to the FIRST completed frame when the
    # session emits, and to the tail otherwise
    emits = n_emit > 0
    frame_counts = seg[:, :-1].at[:, 0].add(
        jnp.where(emits[:, None], counts_in, 0)
    )
    if cfg.variant == "dense":
        frames = hv.majority_pack(frame_counts, cfg.window, cfg.dim)
    else:
        frames = hv.threshold_pack(frame_counts, thresholds[:, None, None])
    ecc_counts = None
    if faults is None:
        scores = dispatch.owner_am_scores(frames, state.class_rows[:, None],
                                          cfg)
    else:
        rows = state.class_rows
        check = (rel_ecc.encode(rows, faults.ecc)
                 if faults.ecc != "none" else None)
        if faults.am:
            k_am_d, k_am_c = jax.random.split(k_am)
            rows = rel_faults.flip_words(rows, k_am_d, fault_ber[1],
                                         mode=faults.mode)
            if check is not None:
                check = rel_faults.flip_words(
                    check, k_am_c, fault_ber[1],
                    bits=rel_ecc.n_check_bits(faults.ecc), mode=faults.mode)
        if check is not None:
            scores, ecc_counts = dispatch.owner_am_scores_protected(
                frames, rows, check, cfg, faults.ecc)
        else:
            scores = dispatch.owner_am_scores(frames, rows[:, None], cfg)
        if ecc_counts is None:
            ecc_counts = jnp.zeros((s, 3), jnp.int32)
        ecc_counts = shd.constrain(ecc_counts, ("batch", None), ctx)
    new_counts = seg[:, -1] + jnp.where(emits[:, None], 0, counts_in)
    # capture each emitting session's LAST completed frame for adapt
    sidx = jnp.arange(s, dtype=jnp.int32)
    last_slot = jnp.maximum(n_emit - 1, 0)
    new_state = replace(
        state,
        counts=shd.constrain(new_counts, _STATE_AXES["counts"], ctx),
        filled=shd.constrain(
            state.filled + lengths - n_emit * cfg.window,
            _STATE_AXES["filled"], ctx,
        ),
        frame_index=shd.constrain(
            state.frame_index + n_emit, _STATE_AXES["frame_index"], ctx
        ),
        last_frame=shd.constrain(
            jnp.where(emits[:, None], frames[sidx, last_slot],
                      state.last_frame),
            _STATE_AXES["last_frame"], ctx,
        ),
        last_scores=shd.constrain(
            # int32 pinned: the popcount scores promote to int64 under
            # JAX_ENABLE_X64, which would drift the carried state dtype
            # (and the jit cache key) after the first step
            jnp.where(emits[:, None], scores[sidx, last_slot],
                      state.last_scores).astype(jnp.int32),
            _STATE_AXES["last_scores"], ctx,
        ),
        has_frame=shd.constrain(
            state.has_frame | emits.astype(jnp.int32),
            _STATE_AXES["has_frame"], ctx,
        ),
    )
    out = FleetOut(frames=frames, scores=scores)
    if faults is None:
        return new_state, out
    return new_state, out, ecc_counts


def _fleet_adapt(
    state: FleetState,
    labels: jax.Array,
    margin: jax.Array,
    density: jax.Array,
    *,
    cfg: HDCConfig,
    ctx: shd.ShardCtx,
) -> tuple[FleetState, jax.Array]:
    """One gated online update for ALL S sessions (core.online).

    labels: (S,) int32 true class of each session's last emitted frame
    (-1 = no feedback); density: (S,) f32 per-patient ``class_density``.
    Sessions whose gate fires get their counter-file rows updated and their
    class rows re-thresholded; everyone else's state passes through
    bit-identically.  Returns (state, applied (S,) bool)."""
    bits = hv.unpack_bits(state.last_frame, cfg.dim)            # (S, D)
    am_state = online.OnlineAMState(counts=state.am_counts, n=state.am_n)
    new_am, applied = online.update(
        am_state, bits, labels, state.last_scores,
        margin=margin, valid=state.has_frame > 0)
    chvs = online.class_hvs_from_state(new_am, cfg, density=density[:, None])
    class_rows = jnp.where(applied[:, None, None], chvs, state.class_rows)
    new_state = replace(
        state,
        am_counts=shd.constrain(new_am.counts, _STATE_AXES["am_counts"], ctx),
        am_n=shd.constrain(new_am.n, _STATE_AXES["am_n"], ctx),
        class_rows=shd.constrain(class_rows, _STATE_AXES["class_rows"], ctx),
    )
    return new_state, applied


class StreamingFleet:
    """S concurrent streaming seizure sessions advanced by one jitted step.

    ``pipelines`` is the patient -> trained-pipeline bank (one shared
    datapath; per-patient calibrated thresholds and codebooks welcome, see
    ``dispatch.datapath_key``).  ``owners[i]`` names the patient session ``i``
    belongs to — any number of sessions per patient.

    ``push(chunks)`` feeds one (t_i, channels) chunk per session (lengths may
    differ; 0 is fine) and returns the completed ``FrameDecision`` lists,
    bit-exact with per-session ``SeizureSession`` loops.  Chunks are padded to
    the smallest configured bucket (longer chunks are split over multiple
    steps), so a steady stream compiles once per bucket — see
    ``compile_count``.  Steady-state serving should prefer ``push_raw``: it
    returns the device-resident ``FleetRound`` results WITHOUT materializing
    per-frame Python objects or forcing a device sync (``push`` is
    ``collect_decisions(push_raw(...))``).  Equal-length pre-stacked streams
    should use ``push_codes`` / ``push_codes_raw`` — the (S, t, channels)
    batch goes straight into the per-tile staging rings with no ragged-list
    packing at all.

    ``backend`` selects the device datapath ("jnp" = pure XLA code-domain
    gather + bit-plane path, "pallas" = fused VMEM kernel with the CompIM
    table bank resident on chip; both bit-exact); defaults to the bank's
    pipeline backend.

    ``adapt(labels)`` personalizes AMs in place: one jitted gated update for
    the whole fleet against each session's last emitted frame (labels of -1
    mask out sessions without feedback), bit-exact with per-session
    ``SeizureSession.adapt`` calls.  ``save``/``restore`` checkpoint the
    full fleet state (streaming + online AM banks) for mid-stream resume.

    ``faults`` (repro.reliability.faults.FaultConfig) turns the fleet into
    a degradation testbench: the jitted step corrupts the configured
    memory reads (codebook bank / AM rows / temporal counters) at the
    configured bit-error rates, optionally decoding AM reads through an
    ECC word codec (``ecc_stats`` accumulates per-session corrected /
    detected / uncorrectable counts).  BER values are traced operands —
    ``set_ber`` sweeps a grid with no recompiles — and ``faults=None``
    (the default) compiles the exact fault-free step, zero overhead.

    ``channel_masking=True`` threads a per-session (S, channels) electrode
    mask through the step as a TRACED operand: ``set_channel_mask``
    quarantines failing channels (the spatial bundle drops them with
    renormalized denominators — see serve/dispatch.py) and walks mask
    grids with zero recompiles; an all-live mask (the initial state) is
    bit-exact with an unmasked fleet.  ``channel_masking=False`` (the
    default) compiles the exact mask-free step, zero overhead.
    """

    def __init__(
        self,
        pipelines: Mapping[Hashable, HDCPipeline],
        owners: Sequence[Hashable],
        *,
        buckets: Sequence[int] = DEFAULT_BUCKETS,
        mesh=None,
        backend: str | None = None,
        tile: int | None = None,
        faults: FaultConfig | None = None,
        channel_masking: bool = False,
    ):
        self._cfg = dispatch.validate_bank(pipelines)
        self._faults = faults
        self._plan = None if faults is None else faults.plan()
        self._masked = bool(channel_masking)
        if backend is None:
            backend = next(iter(pipelines.values())).cfg.backend
        if backend not in ("jnp", "pallas"):
            raise ValueError(f"unknown backend {backend!r}")
        self._backend = backend
        if not owners:
            raise ValueError("StreamingFleet needs at least one session")
        if not buckets or any(b <= 0 for b in buckets):
            raise ValueError(f"buckets must be positive, got {buckets!r}")
        self._buckets = tuple(sorted(set(int(b) for b in buckets)))
        pids = list(pipelines)
        pid_index = {pid: i for i, pid in enumerate(pids)}
        for pid in owners:
            if pid not in pid_index:
                raise KeyError(f"unknown patient id {pid!r} in owners")
        pipes = [pipelines[pid] for pid in pids]
        tables, param_rows = dispatch.stack_bound_tables(pipes)
        bank = jnp.stack([p.class_hvs for p in pipes])  # (P, C, W)
        thresholds = np.asarray(
            [p.cfg.temporal_threshold for p in pipes], np.int32
        )
        owner_idx = np.asarray([pid_index[pid] for pid in owners], np.int32)

        self._ctx = shd.make_ctx(mesh)
        self._n = len(owner_idx)
        self._owners = list(owners)
        # session tiles: bound each device step's working set so the
        # memory-bound step stays cache-resident, and round-robin tiles over
        # the local devices (independent async dispatches, so multi-device
        # hosts advance tiles concurrently).  Capacity pads to whole tiles
        # (see DEFAULT_TILE); padded phantom sessions always push
        # zero-length chunks and never emit or adapt.  A mesh replaces
        # tiling with SPMD sharding: one (padded) tile spanning the mesh.
        if tile is None:
            tile = derive_tile(self._cfg, max_bucket=self._buckets[-1])
            if not os.environ.get("REPRO_FLEET_TILE", ""):
                # phantom-capacity guard: capacity pads to WHOLE tiles, so
                # a memory-derived tile (up to 4096 on big accelerators) is
                # also capped at the fleet's own size rounded up to a power
                # of two — provisioning headroom stays < n instead of up to
                # 4095 phantom rows stepped on every push.  Explicit
                # tile=/env overrides are the operator's choice, uncapped.
                tile = min(tile,
                           max(64, 1 << (max(self._n - 1, 1).bit_length())))
        if tile <= 0:
            raise ValueError(f"tile={tile} must be positive")
        if self._n < tile // 4:
            # tile-padding a tiny fleet would dominate its cost: compile an
            # exact shape instead
            self._np = self._n
        else:
            self._np = -(-self._n // tile) * tile
        if self._np > self._n:
            owner_idx = np.concatenate(
                [owner_idx, np.zeros(self._np - self._n, np.int32)])
        if self._ctx.mesh is not None:
            tile = self._np
        self._tile_slices = [slice(i, min(i + tile, self._np))
                             for i in range(0, self._np, tile)]
        if self._ctx.mesh is not None:
            devs: list = [None]
        else:
            devs = jax.local_devices()
        self._tile_devs = [devs[k % len(devs)]
                           for k in range(len(self._tile_slices))]
        # per-tile pinned uint8 code staging rings: each round writes one
        # vectorized slice per tile then ships it with ONE device put — no
        # per-push allocation and no np scatter on the steady path.  Stale
        # bytes past a session's round length are never re-zeroed: the step
        # masks dead cycles via ``lengths`` and the table gather clips.
        # One CONTIGUOUS buffer per (slot, bucket) — allocated lazily on a
        # bucket's first use, so every round's put is the zero-copy aliasing
        # case, never a strided-view copy — and DOUBLE-buffered: a slot is
        # rewritten only after the round that consumed it completed
        # (``_stage_busy``).  On the CPU backend ``jax.device_put`` of a
        # contiguous aligned numpy array is ZERO-COPY — the jitted step
        # reads the ring itself — so an unsynchronized rewrite would race
        # an in-flight async step.
        self._stage_t: list[dict] = [{} for _ in self._tile_slices]
        # per tile: {(slot, bucket): output of the last round that read it}
        self._stage_busy: list[dict] = [{} for _ in self._tile_slices]
        self._stage_phase = 0
        self._ragged_buf: np.ndarray | None = None
        # pre-bound codebook bank (P_unique, C, codes, W): replicated across
        # the mesh, or one copy per device used by the tiles
        if self._ctx.mesh is not None:
            shared = self._put(tables, (None,) * 4)
            self._tables_t = [shared]
        else:
            per_dev = {d: jax.device_put(tables, d) for d in set(devs)}
            self._tables_t = [per_dev[d] for d in self._tile_devs]
        # per-session operand registers, sliced per tile
        thr_all = thresholds[owner_idx]
        prow_all = np.asarray(param_rows)[owner_idx]
        dens_all = np.asarray(
            [p.cfg.class_density for p in pipes], np.float32)[owner_idx]
        self._thresholds_t = self._put_tiles(thr_all, ("batch",))
        self._param_owner_t = self._put_tiles(prow_all, ("batch",))
        self._density_t = self._put_tiles(dens_all, ("batch",))
        # online-adaptation operands: each session starts from its patient's
        # class rows + counter-file am_state (host copies: the jitted step
        # donates its state, so reset() must rebuild fresh device arrays)
        self._class_rows0 = np.asarray(bank)[owner_idx]  # (S, C, W)
        if all(p.am_state is not None for p in pipes):
            self._am_counts0 = np.stack(
                [np.asarray(pipes[i].am_state.counts) for i in owner_idx])
            self._am_n0 = np.stack(
                [np.asarray(pipes[i].am_state.n) for i in owner_idx])
        else:  # bank mixes in externally built pipelines: adapt unavailable
            self._am_counts0 = self._am_n0 = None
        self._state_t = self._zero_states()
        # fault-injection operands: the (3,) BER vector rides as a TRACED
        # per-tile operand (set_ber moves along a BER grid with no
        # recompile) and the per-tile (tile_s, 3) ECC word counters
        # accumulate device-side, OUTSIDE FleetState (checkpoints stay
        # compatible with fault-free fleets)
        if self._plan is not None:
            self._ber_t = [self._put_tile(faults.ber_vector(), (None,), d)
                           for d in self._tile_devs]
            self._ecc_t = self._zero_ecc()
        # channel-fault quarantine operand: a host-mirrored (S_prov, C)
        # uint8 mask (1 = live) with per-tile device copies, rides the step
        # as a TRACED operand like the BER vector (set_channel_mask walks
        # masks with no recompile).  Phantom capacity rows stay all-live.
        if self._masked:
            self._cmask_h = np.ones((self._np, self._cfg.channels), np.uint8)
            self._cmask_t = self._put_tiles(self._cmask_h, ("batch", None))
        # host mirrors of filled/frame_index: the emission schedule runs on
        # device, but the host needs O(S) mirrors to route raw results
        # (which (session, slot) pairs really emitted) without a round-trip
        self._filled_h = np.zeros((self._np,), np.int64)
        self._fidx_h = np.zeros((self._np,), np.int64)
        # per-tile "state changed since last checkpoint" flags: steps and
        # adapts set them, ckpt writers clear them — the incremental
        # checkpoint path (ckpt.save link_from=...) hard-links untouched
        # tiles from the previous step instead of re-serializing them
        self._dirty_t = [True] * len(self._tile_slices)
        self._shapes_seen: set[int] = set()  # buckets JIT-dispatched so far
        # AOT executables (runtime/aot.py): ``warmup`` fills these with
        # pre-compiled step/adapt executables — loaded from a serialized
        # deploy artifact or lowered+compiled here ahead of traffic — keyed
        # by (device, tile sessions, bucket); the hot loops prefer them and
        # take the jitted callables when one does not accept its operands
        self._exec: dict[tuple, jax.stages.Compiled] = {}
        self._adapt_exec: dict[tuple, jax.stages.Compiled] = {}
        # faults=None keeps the partial's jaxpr IDENTICAL to the fault-free
        # step — the fault path costs nothing unless a plan is configured
        # (and masked=False likewise keeps the mask-free jaxpr byte-exact)
        self._step = jax.jit(
            functools.partial(_fleet_step, cfg=self._cfg, ctx=self._ctx,
                              use_kernel=self._backend == "pallas",
                              faults=self._plan, masked=self._masked),
            donate_argnums=(0,),
        )
        # NOT donated: several state leaves pass through adapt untouched and
        # XLA cannot alias every same-shaped pair, which trips the
        # donation warning; adapt is rare relative to push, so the one
        # transient copy is the cheaper trade
        self._adapt_step = jax.jit(
            functools.partial(_fleet_adapt, cfg=self._cfg, ctx=self._ctx),
        )

    # -- state management ---------------------------------------------------

    def _put(self, x: jax.Array, axes: tuple) -> jax.Array:
        s = shd.sharding_for(axes, self._ctx, jnp.shape(x))
        return jax.device_put(x, s) if s is not None else jnp.asarray(x)

    def _put_tile(self, x, axes: tuple, dev) -> jax.Array:
        """Place one tile's operand: sharded under a mesh, pinned to the
        tile's device otherwise."""
        if self._ctx.mesh is not None:
            return self._put(jnp.asarray(x), axes)
        return jax.device_put(x, dev)

    def _put_tiles(self, x: np.ndarray, axes: tuple) -> list[jax.Array]:
        return [self._put_tile(x[sl], axes, d)
                for sl, d in zip(self._tile_slices, self._tile_devs)]

    def _zero_state(self, sl: slice, d) -> FleetState:
        """Fresh device state for ONE capacity tile (every session reset to
        its patient's trained bank) — also the template the elastic fleet
        uses to provision a spilled tile."""
        cfg = self._cfg
        c = self._class_rows0.shape[1]
        axes = _STATE_AXES
        s = sl.stop - sl.start
        if self._am_counts0 is not None:
            am_counts, am_n = self._am_counts0[sl], self._am_n0[sl]
        else:
            am_counts = np.zeros((s, c, cfg.dim), np.int32)
            am_n = np.zeros((s, c), np.int32)
        put = self._put_tile
        return FleetState(
            counts=put(np.zeros((s, cfg.dim), np.int32),
                       axes["counts"], d),
            filled=put(np.zeros((s,), np.int32), axes["filled"], d),
            frame_index=put(np.zeros((s,), np.int32),
                            axes["frame_index"], d),
            class_rows=put(self._class_rows0[sl], axes["class_rows"], d),
            am_counts=put(am_counts, axes["am_counts"], d),
            am_n=put(am_n, axes["am_n"], d),
            last_frame=put(np.zeros((s, cfg.words), np.uint32),
                           axes["last_frame"], d),
            last_scores=put(np.zeros((s, c), np.int32),
                            axes["last_scores"], d),
            has_frame=put(np.zeros((s,), np.int32), axes["has_frame"], d),
        )

    def _zero_states(self) -> list[FleetState]:
        return [self._zero_state(sl, d)
                for sl, d in zip(self._tile_slices, self._tile_devs)]

    def _split_state(self, full: FleetState) -> list[FleetState]:
        """Scatter a whole-fleet state (e.g. a restored checkpoint) back
        onto the session tiles and their devices."""
        if self._ctx.mesh is not None:
            return [full]
        return [
            jax.tree.map(lambda x, sl=sl, d=d: jax.device_put(x[sl], d), full)
            for sl, d in zip(self._tile_slices, self._tile_devs)
        ]

    def _zero_ecc(self) -> list[jax.Array]:
        return [self._put_tile(np.zeros((sl.stop - sl.start, 3), np.int32),
                               ("batch", None), d)
                for sl, d in zip(self._tile_slices, self._tile_devs)]

    def reset(self) -> None:
        """Zero all accumulators, fill levels, frame indices and ECC
        counters, and restore every session's AM to its patient's trained
        (pre-adaptation) state."""
        self._state_t = self._zero_states()
        self._filled_h[:] = 0
        self._fidx_h[:] = 0
        self._dirty_t = [True] * len(self._tile_slices)
        if self._plan is not None:
            self._ecc_t = self._zero_ecc()

    @property
    def n_sessions(self) -> int:
        return self._n

    @property
    def n_tiles(self) -> int:
        return len(self._tile_slices)

    @property
    def state(self) -> FleetState:
        """Whole-fleet state view (tiles concatenated; one gather when the
        fleet spans several tiles — cheap relative to how rarely callers
        need it: checkpointing and tests).  Leading dim is the PROVISIONED
        capacity (sessions padded to whole capacity tiles); rows past
        ``n_sessions`` are phantom slots that never emit or adapt."""
        if len(self._state_t) == 1:
            return self._state_t[0]
        return jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0),
                            *self._state_t)

    @property
    def fill_levels(self) -> np.ndarray:
        """(S,) cycles accumulated toward each next (incomplete) frame."""
        return self._filled_h[:self._n].copy()

    @property
    def frame_indices(self) -> np.ndarray:
        """(S,) frames emitted so far per session."""
        return self._fidx_h[:self._n].copy()

    @property
    def fault_config(self) -> FaultConfig | None:
        """The active fault campaign (None = fault-free fleet)."""
        return self._faults

    def set_ber(self, ber: float) -> None:
        """Move every ENABLED fault target to one bit-error rate.

        BER rides as a traced operand of the jitted step, so sweeping a BER
        grid through one fleet never recompiles; which targets / mode / ECC
        scheme are enabled is static (build a new fleet to change those).
        """
        if self._faults is None:
            raise ValueError(
                "fleet was built without faults; pass "
                "StreamingFleet(..., faults=FaultConfig(...)) to enable "
                "fault injection")
        self._faults = self._faults.with_ber(ber)
        vec = self._faults.ber_vector()
        self._ber_t = [self._put_tile(vec, (None,), d)
                       for d in self._tile_devs]

    @property
    def channel_masking(self) -> bool:
        """True when the step carries the channel-mask operand."""
        return self._masked

    @property
    def channel_masks(self) -> np.ndarray:
        """(S, channels) uint8 live-channel masks (1 = live).  All ones —
        including for fleets built without ``channel_masking`` — until
        ``set_channel_mask`` quarantines something."""
        if not self._masked:
            return np.ones((self._n, self._cfg.channels), np.uint8)
        return self._cmask_h[:self._n].copy()

    def set_channel_mask(self, mask, sessions: Sequence[int] | None = None
                         ) -> None:
        """Quarantine / reinstate electrodes: install per-session live-
        channel masks (1 = live, 0 = masked out of the spatial bundle).

        ``mask`` is (S, channels) — or (channels,), broadcast to every
        session — of 0/1 values; ``sessions`` optionally restricts the
        update to those session indices (then ``mask`` is (len(sessions),
        channels) or (channels,)).  The mask rides the jitted step as a
        TRACED operand, so walking a mask grid (the channel-health
        monitor's quarantine/reinstate churn, the degradation benchmark's
        sweep) never recompiles.  Masks persist across ``reset`` — they
        describe electrode health, not stream state — and are carried by
        ``save``/``restore`` checkpoints.
        """
        if not self._masked:
            raise ValueError(
                "fleet was built without channel_masking; pass "
                "StreamingFleet(..., channel_masking=True) to enable "
                "electrode quarantine")
        c = self._cfg.channels
        m = np.asarray(mask)
        idx = (np.arange(self._n) if sessions is None
               else np.asarray(list(sessions), np.int64))
        if sessions is not None and (idx.size == 0 or idx.min() < 0
                                     or idx.max() >= self._n):
            raise ValueError(
                f"sessions must be indices in [0, {self._n})")
        if m.ndim == 1:
            m = np.broadcast_to(m, (idx.size, c))
        if m.shape != (idx.size, c):
            raise ValueError(
                f"mask must be ({idx.size}, {c}) or ({c},), got {m.shape}")
        if not np.isin(m, (0, 1)).all():
            raise ValueError("mask entries must be 0 or 1")
        self._cmask_h[idx] = m.astype(np.uint8)
        self._cmask_t = self._put_tiles(self._cmask_h, ("batch", None))

    @property
    def ecc_stats(self) -> np.ndarray:
        """(S, 3) cumulative per-session ECC word counts since the last
        ``reset``: [corrected, detected, uncorrectable] — ``detected``
        counts every faulty word observed (= corrected + uncorrectable for
        SECDED; parity only detects).  All zeros when no ECC scheme is
        configured (or no faults landed)."""
        if self._plan is None:
            return np.zeros((self._n, 3), np.int64)
        return np.concatenate(
            [np.asarray(x) for x in self._ecc_t]).astype(np.int64)[:self._n]

    @property
    def compile_count(self) -> int:
        """Step executables built or loaded so far (<= buckets x tiles).

        Counts BOTH the jit cache (preferring jit's real cache size, which
        catches accidental recompiles; falling back to the count of distinct
        JIT-dispatched bucket shapes if the private jax API ever disappears)
        AND the AOT executables installed by ``warmup`` — a warmed fleet
        whose pushes never touch the jit cache still reports its real
        executable count, so bucketed compile-count guards hold on the AOT
        path instead of passing vacuously at 0."""
        cache_size = getattr(self._step, "_cache_size", None)
        jit_n = (cache_size() if cache_size is not None
                 else len(self._shapes_seen))
        return jit_n + len(self._exec)

    @property
    def aot_count(self) -> int:
        """Step executables that came from ``warmup`` (artifact-loaded or
        pre-compiled) rather than first-push JIT."""
        return len(self._exec)

    # -- ahead-of-time compilation (runtime/aot.py) ---------------------------

    def _aot_sig(self) -> str:
        """Digest of everything that selects this fleet's step program
        beyond the argument shapes: datapath config, fault plan, channel
        masking, backend, the stacked table-bank geometry and the x64
        regime.  Rides in the artifact entry names so a lookup can never
        hand back an executable compiled for a different program."""
        h = hashlib.sha256()
        h.update(repr(self._cfg).encode())
        h.update(repr(self._plan).encode())
        h.update(str(self._masked).encode())
        h.update(self._backend.encode())
        h.update(str(tuple(jnp.shape(self._tables_t[0]))).encode())
        h.update(str(bool(jax.config.jax_enable_x64)).encode())
        return h.hexdigest()[:10]

    def _aot_name(self, kind: str, tile_s: int, t_pad: int | None = None) -> str:
        base = (f"fleet.{self._cfg.variant}.{self._backend}"
                f"{'.faulted' if self._plan is not None else ''}"
                f"{'.masked' if self._masked else ''}.s{tile_s}")
        mid = f".t{t_pad}" if kind == "step" else ""
        return f"{base}{mid}.{kind}.{self._aot_sig()}"

    def _sds(self, x, dev) -> jax.ShapeDtypeStruct:
        sharding = (None if dev is None
                    else jax.sharding.SingleDeviceSharding(dev))
        return jax.ShapeDtypeStruct(jnp.shape(x), jnp.asarray(x).dtype,
                                    sharding=sharding)

    def _step_avals(self, k: int, t_pad: int, dev) -> tuple:
        """Abstract args of tile ``k``'s step at bucket ``t_pad`` (pinned to
        ``dev``; dev=None = portable, for export blobs)."""
        sl = self._tile_slices[k]
        tile_s = sl.stop - sl.start
        avals = (
            jax.tree.map(lambda x: self._sds(x, dev), self._state_t[k]),
            self._sds(self._tables_t[k], dev),
            self._sds(self._param_owner_t[k], dev),
            self._sds(self._thresholds_t[k], dev),
            jax.ShapeDtypeStruct((tile_s, t_pad, self._cfg.channels),
                                 jnp.uint8,
                                 sharding=None if dev is None else
                                 jax.sharding.SingleDeviceSharding(dev)),
            self._sds(np.zeros((tile_s,), np.int32), dev),
        )
        if self._plan is not None:
            avals += (self._sds(np.zeros((3,), np.float32), dev),
                      self._sds(np.int32(0), dev))
        if self._masked:
            avals += (self._sds(
                np.ones((tile_s, self._cfg.channels), np.uint8), dev),)
        return avals

    def _adapt_avals(self, k: int, dev) -> tuple:
        sl = self._tile_slices[k]
        tile_s = sl.stop - sl.start
        return (
            jax.tree.map(lambda x: self._sds(x, dev), self._state_t[k]),
            self._sds(np.zeros((tile_s,), np.int32), dev),
            self._sds(np.float32(0), dev),
            self._sds(np.zeros((tile_s,), np.float32), dev),
        )

    def aot_entries(self, buckets: Sequence[int] | None = None
                    ) -> list[aot_mod.AOTEntry]:
        """The executable set of this fleet, as portable AOT entries: one
        step per (distinct tile shape) x (chunk bucket) — the faulted step
        when a fault plan is configured — plus the adapt step per tile
        shape.  ``aot_mod.save_artifact`` turns these into a serialized
        deploy artifact; ``warmup(aot=...)`` loads them back."""
        out: list[aot_mod.AOTEntry] = []
        seen: set[tuple] = set()
        # the pinned (cache_args) form is what a plain-JIT restart actually
        # compiles — its operands are committed to their tile device, which
        # hashes to a different persistent-cache key than the portable form
        dev = None if self._ctx.mesh is not None else jax.local_devices()[0]
        for k, sl in enumerate(self._tile_slices):
            tile_s = sl.stop - sl.start
            for b in buckets or self._buckets:
                if ("step", tile_s, b) in seen:
                    continue
                seen.add(("step", tile_s, b))
                out.append(aot_mod.AOTEntry(
                    name=self._aot_name("step", tile_s, b),
                    fn=self._step,
                    args=self._step_avals(k, b, dev=None),
                    cache_args=(self._step_avals(k, b, dev=dev)
                                if dev is not None else None)))
            if self._am_counts0 is not None and ("adapt", tile_s) not in seen:
                seen.add(("adapt", tile_s))
                out.append(aot_mod.AOTEntry(
                    name=self._aot_name("adapt", tile_s),
                    fn=self._adapt_step,
                    args=self._adapt_avals(k, dev=None),
                    cache_args=(self._adapt_avals(k, dev=dev)
                                if dev is not None else None)))
        return out

    def save_aot(self, path: str) -> dict:
        """Serialize + pre-compile this fleet's whole executable set into a
        versioned deploy artifact at ``path`` (see runtime/aot.py); returns
        the artifact manifest.  Run at deploy time — e.g. the
        ``launch/serve.py compile`` subcommand — so restarted workers load
        executables instead of compiling them."""
        return aot_mod.save_artifact(path, self.aot_entries())

    def warmup(self, *, aot: aot_mod.AOTArtifact | None = None,
               buckets: Sequence[int] | None = None) -> dict[str, int]:
        """Build every step (and adapt) executable BEFORE traffic arrives.

        With ``aot`` (a loaded deploy artifact), executables deserialize
        from it — no tracing, and no XLA compile when the entry ships its
        PjRt executable; entries the artifact lacks (or whose load fails)
        are pre-lowered and compiled here, which still beats paying the
        compile under the first push.  Installed executables serve the hot loops
        directly (the jit cache stays cold — ``compile_count`` counts them,
        see above).  Returns ``{"loaded", "compiled", "skipped"}`` counts.
        Under a mesh the step is a sharded SPMD program the artifact format
        does not carry; warmup is a no-op there (plain JIT, one warning).
        """
        stats = {"loaded": 0, "compiled": 0, "skipped": 0}
        if self._ctx.mesh is not None:
            warnings.warn("StreamingFleet.warmup: mesh-sharded fleets "
                          "fall back to JIT (no AOT path)", stacklevel=2)
            return stats
        default_dev = jax.local_devices()[0]
        for k, (sl, dev) in enumerate(zip(self._tile_slices,
                                          self._tile_devs)):
            tile_s = sl.stop - sl.start
            for b in buckets or self._buckets:
                key = (dev, tile_s, b)
                if key in self._exec:
                    stats["skipped"] += 1
                    continue
                compiled = None
                if aot is not None and dev == default_dev:
                    compiled = aot.compile(
                        self._aot_name("step", tile_s, b),
                        *self._step_avals(k, b, dev=None))
                    if compiled is not None:
                        stats["loaded"] += 1
                if compiled is None:
                    compiled = self._step.lower(
                        *self._step_avals(k, b, dev=dev)).compile()
                    stats["compiled"] += 1
                self._exec[key] = compiled
            akey = (dev, tile_s)
            if self._am_counts0 is not None and akey not in self._adapt_exec:
                compiled = None
                if aot is not None and dev == default_dev:
                    compiled = aot.compile(self._aot_name("adapt", tile_s),
                                           *self._adapt_avals(k, dev=None))
                if compiled is None:
                    compiled = self._adapt_step.lower(
                        *self._adapt_avals(k, dev=dev)).compile()
                self._adapt_exec[akey] = compiled
        return stats

    @classmethod
    def from_artifact(
        cls,
        pipelines: Mapping[Hashable, HDCPipeline],
        owners: Sequence[Hashable],
        root: str,
        *,
        step: int | None = None,
        aot_dir: str | None = None,
        warm: bool = True,
        **fleet_kwargs,
    ) -> "StreamingFleet":
        """Deploy-restore: build a fleet, warm its executables from the
        checkpoint's recorded AOT artifact, and restore the checkpointed
        state — the worker-restart path, first decision without a compile.

        The checkpoint manifest's ``aot`` entry (written by
        ``save(..., aot_dir=...)``) names the artifact directory and its
        validity key; ``aot_dir`` overrides the recorded path.  A stale or
        missing artifact (different jax version / device kind / kernel
        sources) degrades to plain-JIT warmup with a warning — decisions
        are identical either way, only the cold-start latency differs.
        """
        fleet = cls(pipelines, owners, **fleet_kwargs)
        if step is None:
            step = ckpt.latest_step(root)
            if step is None:
                raise FileNotFoundError(f"no fleet checkpoint under {root!r}")
        with open(os.path.join(root, f"step_{step:08d}",
                               "manifest.json")) as f:
            manifest = json.load(f)
        art = None
        path = aot_dir
        if path is None:
            entry = manifest.get("aot")
            if entry is not None:
                saved_key = entry.get("key")
                bad = (aot_mod.stale_fields(saved_key, aot_mod.artifact_key())
                       if saved_key is not None else {})
                if bad:
                    warnings.warn(
                        "checkpoint AOT entry is stale ("
                        + ", ".join(f"{k}: saved {s!r} != current {c!r}"
                                    for k, (s, c) in sorted(bad.items()))
                        + "); warming up via JIT", stacklevel=2)
                else:
                    path = entry.get("path")
                    if path is not None and not os.path.isabs(path):
                        path = os.path.join(root, path)
        if path is not None:
            art = aot_mod.load_artifact(path)  # None (+warning) when stale
        if warm:
            fleet.warmup(aot=art)
        fleet.restore(root, step)
        return fleet

    def _call_step(self, t_pad: int, sl: slice, dev, args: tuple):
        """One tile step through the warmed executable when it accepts
        these operands (signature and placement, checked before the call),
        else the jitted callable.  An error raised by the executable
        itself propagates: its donated state is already consumed."""
        key = (dev, sl.stop - sl.start, t_pad)
        fn = self._exec.get(key)
        if fn is not None:
            if aot_mod.accepts(fn, args):
                return fn(*args)
            self._exec.pop(key)
        self._shapes_seen.add(t_pad)
        return self._step(*args)

    # -- streaming ----------------------------------------------------------

    def _bucket_for(self, n: int) -> int:
        for b in self._buckets:
            if n <= b:
                return b
        raise AssertionError("length exceeds max bucket")  # pragma: no cover

    def _stage_buf(self, k: int, slot: int, t_pad: int) -> np.ndarray:
        """Tile ``k``'s contiguous staging buffer for (slot, bucket), safe
        to rewrite: waits for the previous round that read this buffer (the
        CPU backend's device_put aliases it zero-copy) before returning."""
        key = (slot, t_pad)
        busy = self._stage_busy[k].pop(key, None)
        if busy is not None:
            jax.block_until_ready(busy)
        if key not in self._stage_t[k]:
            sl = self._tile_slices[k]
            self._stage_t[k][key] = np.zeros(
                (sl.stop - sl.start, t_pad, self._cfg.channels), np.uint8)
        return self._stage_t[k][key]

    def _validate(self, chunks: Sequence) -> tuple[list[np.ndarray], np.ndarray]:
        """Per-chunk dtype/shape validation; returns (arrays, lengths)."""
        ch = self._cfg.channels
        arrs = []
        for i, c in enumerate(chunks):
            a = np.asarray(c, dtype=np.uint8)
            if a.size == 0:
                a = a.reshape(0, ch)
            if a.ndim != 2 or a.shape[1] != ch:
                raise ValueError(
                    f"session {i}: chunk must be (t, {ch}), got {a.shape}"
                )
            arrs.append(a)
        return arrs, np.asarray([a.shape[0] for a in arrs], np.int64)

    def _pack(self, arrs: list[np.ndarray], lengths: np.ndarray) -> np.ndarray:
        """Ragged chunk list -> one (S, T_max, ch) code batch.

        Steady streams (all lengths equal — the service-interval shape) are
        one concatenate + reshape VIEW, no scatter.  Ragged pushes scatter
        once into a REUSED staging buffer (grown geometrically, never
        re-zeroed: rows past a session's length are dead cycles — the
        device step masks them via ``lengths`` and the code-domain gather
        clips, so stale bytes are harmless).
        """
        ch = self._cfg.channels
        total = int(lengths.max(initial=0))
        flat = np.concatenate(arrs, axis=0)                # (sum(t_i), ch)
        if (lengths == total).all():                       # steady streams
            return flat.reshape(self._n, total, ch)
        if (self._ragged_buf is None
                or self._ragged_buf.shape[1] < total):
            cap = max(total, 2 * (0 if self._ragged_buf is None
                                  else self._ragged_buf.shape[1]))
            self._ragged_buf = np.empty((self._n, cap, ch), np.uint8)
        big = self._ragged_buf
        rows = np.repeat(np.arange(self._n), lengths)
        starts = np.cumsum(lengths) - lengths
        cols = np.arange(int(lengths.sum())) - np.repeat(starts, lengths)
        big[rows, cols] = flat
        return big

    def _rounds(self, big: np.ndarray, lengths: np.ndarray) -> list[FleetRound]:
        """Advance the fleet over one packed (S, T, ch) code batch.

        The per-round device payload is staged through PER-TILE pinned uint8
        code buffers (one contiguous buffer per (slot, bucket), allocated on
        first use, reused round-robin): one vectorized slice write + ONE
        device put per tile per round, nothing else — codes are 1 byte per
        (cycle, channel), 128x less than the packed bound rows the spatial
        stage used to expand on device.  The CPU backend's ``device_put``
        zero-copy-aliases the staging buffer, so each buffer is rewritten
        only AFTER the round that read it finished (``_stage_buf``; double
        buffering keeps a pipeline depth of two before that wait can
        stall).  ``lengths`` must already be padded to provisioned capacity
        (phantom rows 0).
        """
        rounds: list[FleetRound] = []
        max_bucket = self._buckets[-1]
        pos = 0
        total = int(lengths.max(initial=0))
        while pos < total:
            round_len = np.clip(lengths - pos, 0, max_bucket)
            t_pad = self._bucket_for(int(round_len.max()))
            width = min(t_pad, total - pos)
            round_len32 = round_len.astype(np.int32)
            n_emit = (self._filled_h + round_len) // self._cfg.window
            phase = self._stage_phase
            slot = phase & 1
            self._stage_phase += 1
            fos = []
            # per-tile steps dispatch asynchronously: tiles on different
            # devices overlap, and nothing here waits on the results
            # (except a slot whose previous reader is still in flight)
            for k, (sl, d) in enumerate(
                    zip(self._tile_slices, self._tile_devs)):
                stage = self._stage_buf(k, slot, t_pad)
                hi = min(sl.stop, self._n)   # phantom rows: stale == masked
                if hi > sl.start:
                    stage[:hi - sl.start, :width] = big[sl.start:hi,
                                                        pos:pos + width]
                args = (
                    self._state_t[k],
                    self._tables_t[k],
                    self._param_owner_t[k],
                    self._thresholds_t[k],
                    self._put_tile(stage, ("batch", None, None), d),
                    self._put_tile(round_len32[sl], ("batch",), d),
                )
                if self._plan is not None:
                    seed = rel_faults.step_seed(
                        self._plan, tile=k, n_tiles=len(self._tile_slices),
                        phase=phase)
                    args += (self._ber_t[k],
                             self._put_tile(np.int32(seed), (), d))
                if self._masked:
                    args += (self._cmask_t[k],)
                res = self._call_step(t_pad, sl, d, args)
                if self._plan is None:
                    self._state_t[k], fo = res
                else:
                    self._state_t[k], fo, ecc_c = res
                    self._ecc_t[k] = self._ecc_t[k] + ecc_c
                if round_len[sl].any():  # all-masked rounds leave the tile
                    self._dirty_t[k] = True  # VALUE-identical (clean)
                # fo depends on the staged codes: once it is ready the
                # step has consumed the slot and it is safe to rewrite
                self._stage_busy[k][(slot, t_pad)] = fo
                fos.append(fo)
            # rounds expose REAL sessions only ((S,) arrays); phantom
            # capacity-padding rows never emit, so dropping them is lossless
            rounds.append(FleetRound(tiles=tuple(fos),
                                     n_emit=n_emit[:self._n],
                                     frame_base=self._fidx_h[:self._n].copy()))
            self._filled_h += round_len - n_emit * self._cfg.window
            self._fidx_h += n_emit
            pos += max_bucket
        return rounds

    def push_raw(self, chunks: Sequence) -> list[FleetRound]:
        """Feed one (t_i, channels) uint8 chunk per session; zero host-side
        schedule work beyond O(S) per round.

        Returns one ``FleetRound`` per bucketed device step (chunks longer
        than the largest bucket split over several).  ``frames``/``scores``
        stay on device — nothing here blocks on the step's results, so
        steady-state serving can overlap pushes with downstream reads; use
        ``collect_decisions`` (or ``push``) to materialize FrameDecisions.
        For pre-stacked equal-length streams prefer ``push_codes`` (skips
        the ragged-list handling entirely).
        """
        if len(chunks) != self._n:
            raise ValueError(
                f"push needs one chunk per session ({self._n}), got {len(chunks)}"
            )
        arrs, real_lengths = self._validate(chunks)
        lengths = np.zeros((self._np,), np.int64)  # phantom rows stay empty
        lengths[:self._n] = real_lengths
        if int(real_lengths.max(initial=0)) == 0:
            return []
        return self._rounds(self._pack(arrs, real_lengths), lengths)

    def push_codes_raw(self, batch, lengths: Sequence[int] | None = None
                       ) -> list[FleetRound]:
        """Zero-scatter ingest fast path: feed one pre-stacked (S, t, ch)
        uint8 code batch for the whole fleet.

        The batch goes straight to the per-tile staging buffers — no
        per-session list handling, no concatenate, no scatter; the host
        work per round is one vectorized tile-slice write and one device
        put per tile.  ``lengths`` optionally gives per-session valid
        cycles (default: all ``t``); bit-exact with ``push_raw`` on the
        equivalent chunk list.
        """
        batch = np.asarray(batch, np.uint8)
        ch = self._cfg.channels
        if batch.ndim != 3 or batch.shape[0] != self._n or batch.shape[2] != ch:
            raise ValueError(
                f"push_codes needs a ({self._n}, t, {ch}) batch, got "
                f"{batch.shape}")
        t = batch.shape[1]
        lens = np.zeros((self._np,), np.int64)
        if lengths is None:
            lens[:self._n] = t
        else:
            ll = np.asarray(lengths, np.int64)
            if ll.shape != (self._n,) or ll.min(initial=0) < 0 or \
                    ll.max(initial=0) > t:
                raise ValueError(
                    f"lengths must be ({self._n},) ints in [0, {t}]")
            lens[:self._n] = ll
        if t == 0 or int(lens.max(initial=0)) == 0:
            return []
        return self._rounds(batch, lens)

    def push_codes(self, batch, lengths: Sequence[int] | None = None
                   ) -> list[list[FrameDecision]]:
        """``push`` for a pre-stacked (S, t, ch) uint8 code batch: the
        zero-scatter steady-stream ingest path.  Bit-exact with
        ``push(list(batch))``."""
        return self.collect_decisions(self.push_codes_raw(batch, lengths))

    def collect_decisions(
        self, rounds: Sequence[FleetRound]
    ) -> list[list[FrameDecision]]:
        """Materialize per-session FrameDecision lists from raw rounds.

        This is the ONLY place the raw path syncs with the device; the
        argmax runs vectorized over all (session, slot) pairs and the Python
        loop touches only sessions that actually emitted."""
        out: list[list[FrameDecision]] = [[] for _ in range(self._n)]
        for r in rounds:
            if not r.n_emit.any():
                continue
            for sl, fo in zip(self._tile_slices, r.tiles):
                ne = r.n_emit[sl]
                if not ne.any():
                    continue
                frames = np.asarray(fo.frames)
                scores = np.asarray(fo.scores)
                preds = np.argmax(scores, axis=-1)         # (tile_s, K)
                for i in np.nonzero(ne)[0]:
                    g = sl.start + int(i)
                    base = int(r.frame_base[g])
                    out[g].extend(
                        FrameDecision(frame_index=base + k,
                                      scores=scores[i, k],
                                      prediction=int(preds[i, k]),
                                      frame_hv=frames[i, k])
                        for k in range(int(ne[i]))
                    )
        return out

    def push(self, chunks: Sequence) -> list[list[FrameDecision]]:
        """Feed one (t_i, channels) uint8 chunk per session.

        Chunk lengths may differ per session (0 included).  Returns, per
        session, the decisions for every frame completed by this push.
        """
        return self.collect_decisions(self.push_raw(chunks))

    # -- instrumentation ------------------------------------------------------

    def stage_probes(self, batch) -> dict[str, tuple]:
        """Per-stage sub-benchmarks of one steady push round, for the fleet
        benchmark's breakdown rows (bench_fleet.py) — the stages live HERE so
        the probe tracks the step implementation instead of reaching into
        fleet internals from the benchmark.

        ``batch`` is one (S, t, channels) uint8 code round (t <= max
        bucket).  Returns ``{stage: (fn, scale)}``: ``fn()`` runs that stage
        once on ONE session tile and blocks on the result; ``scale`` (the
        tile count, 1 for the host-side ``ingest``) multiplies the time to
        cover the whole fleet.  Each fn is pre-run once, so jit compilation
        never pollutes the first timed call.  Stages overlap/fuse inside
        the real jitted step, so their times need not sum to a push.
        """
        cfg = self._cfg
        if self._backend != "jnp":
            # the probes time the jnp reference stages; the pallas backend
            # fuses gather+bundle+transpose+counters into one kernel, so
            # per-stage shares measured here would describe a datapath the
            # fleet never runs
            raise ValueError(
                "stage_probes breaks the step into the jnp reference "
                f"stages; this fleet runs backend={self._backend!r} — "
                "benchmark a backend='jnp' fleet")
        batch = np.asarray(batch, np.uint8)
        t = batch.shape[1]
        if not 0 < t <= self._buckets[-1]:
            raise ValueError(
                f"stage_probes needs one round, 0 < t <= {self._buckets[-1]}")
        sl, dev = self._tile_slices[0], self._tile_devs[0]
        tile_s = sl.stop - sl.start
        tables, owner = self._tables_t[0], self._param_owner_t[0]
        thresholds = self._thresholds_t[0]
        # SNAPSHOT the class rows: the live state leaf is donated by the
        # next real push, which would delete the buffer under the probe
        # (callers interleave probe timings with reference pushes)
        class_rows = jnp.array(self._state_t[0].class_rows)
        tile_batch = np.zeros((tile_s, t, cfg.channels), np.uint8)
        tile_batch[:min(tile_s, self._n)] = batch[sl.start:
                                                  min(sl.stop, self._n)]
        chunk_d = self._put_tile(tile_batch, ("batch", None, None), dev)
        filled = self._put_tile(np.zeros(tile_s, np.int32), ("batch",), dev)
        lengths = self._put_tile(np.full(tile_s, t, np.int32),
                                 ("batch",), dev)

        # cfg rides in the closure (a static, like the step's partial) —
        # operands stay explicit jit arguments so nothing constant-folds
        if self._masked:
            f_spatial = jax.jit(
                lambda t_, o, c, m: dispatch.owner_spatial_codes(
                    t_, o, c, cfg, m))
            spatial_args = (tables, owner, chunk_d, self._cmask_t[0])
        else:
            f_spatial = jax.jit(
                lambda t_, o, c: dispatch.owner_spatial_codes(t_, o, c, cfg))
            spatial_args = (tables, owner, chunk_d)
        words = jax.block_until_ready(f_spatial(*spatial_args))
        f_temporal = jax.jit(
            lambda w, f, l: fleet_ops.fleet_counts(w, f, l, cfg))
        seg = jax.block_until_ready(f_temporal(words, filled, lengths))

        def _am(seg, thr, cls):
            if cfg.variant == "dense":
                frames = hv.majority_pack(seg[:, :-1], cfg.window, cfg.dim)
            else:
                frames = hv.threshold_pack(seg[:, :-1], thr[:, None, None])
            return dispatch.owner_am_scores(frames, cls[:, None], cfg)
        f_am = jax.jit(_am)
        jax.block_until_ready(f_am(seg, thresholds, class_rows))

        t_bucket = self._bucket_for(t)

        def run_ingest():  # host side of one round: ring writes + puts
            for k, (tsl, d) in enumerate(zip(self._tile_slices,
                                             self._tile_devs)):
                stage = self._stage_buf(k, 0, t_bucket)
                hi = min(tsl.stop, self._n)
                if hi > tsl.start:
                    stage[:hi - tsl.start, :t] = batch[tsl.start:hi]
                jax.block_until_ready(self._put_tile(
                    stage, ("batch", None, None), d))
        run_ingest()

        n_tiles = self.n_tiles
        return {
            "ingest": (run_ingest, 1),
            "spatial": (lambda: jax.block_until_ready(
                f_spatial(*spatial_args)), n_tiles),
            "temporal": (lambda: jax.block_until_ready(
                f_temporal(words, filled, lengths)), n_tiles),
            "am": (lambda: jax.block_until_ready(
                f_am(seg, thresholds, class_rows)), n_tiles),
        }

    # -- online adaptation ----------------------------------------------------

    @property
    def class_rows(self) -> np.ndarray:
        """(S, C, W) per-session (possibly adapted) class HV rows."""
        return np.asarray(self.state.class_rows)[:self._n]

    def adapt(self, labels: Sequence[int], *,
              margin: float = 0.0) -> np.ndarray:
        """Personalize all S sessions' AMs from one feedback label each.

        ``labels[i]`` is the true class of session ``i``'s LAST emitted
        frame; ``-1`` means no feedback (skip).  Sessions that have not
        emitted a frame yet are skipped too.  One jitted gated update
        (core.online) for the whole fleet: misclassified / low-margin
        sessions add the frame's bits to the true class's counters, subtract
        from the rival's, and get their class rows re-thresholded.
        Bit-exact with calling ``SeizureSession.adapt`` per stream.  Returns
        the (S,) bool mask of sessions whose update fired."""
        if self._am_counts0 is None:
            raise ValueError(
                "fleet bank has pipelines without am_state counter files; "
                "train them with train_one_shot/fit_iterative to enable "
                "adapt()")
        lab = np.asarray(labels, np.int64)
        if lab.shape != (self._n,):
            raise ValueError(
                f"adapt needs one label per session ({self._n}), got shape "
                f"{lab.shape}")
        if lab.max(initial=-1) >= self._cfg.n_classes:
            raise ValueError(
                f"labels must be < n_classes={self._cfg.n_classes} "
                "(-1 = no feedback)")
        lab32 = np.full((self._np,), -1, np.int32)  # phantoms: no feedback
        lab32[:self._n] = lab
        applied = []
        for k, (sl, d) in enumerate(zip(self._tile_slices, self._tile_devs)):
            args = (
                self._state_t[k],
                self._put_tile(lab32[sl], ("batch",), d),
                # committed per tile so the warmed (device-pinned) adapt
                # executables accept it directly
                self._put_tile(np.float32(margin), (), d),
                self._density_t[k],
            )
            akey = (d, sl.stop - sl.start)
            fn = self._adapt_exec.get(akey)
            if fn is not None and not aot_mod.accepts(fn, args):
                self._adapt_exec.pop(akey)
                fn = None
            self._state_t[k], app = (fn or self._adapt_step)(*args)
            self._dirty_t[k] = True
            applied.append(app)
        return np.concatenate([np.asarray(a) for a in applied])[:self._n]

    # -- durability -----------------------------------------------------------

    def _meta(self) -> dict:
        return {
            "kind": "hdc_fleet",
            "n_sessions": self._n,
            "dim": self._cfg.dim,
            "window": self._cfg.window,
            "n_classes": self._cfg.n_classes,
            "variant": self._cfg.variant,
            "bank": self._bank_fingerprint(),
        }

    def _bank_fingerprint(self) -> str:
        """Digest of everything a checkpointed state is only valid against:
        the per-session codebook tables, initial class rows / AM banks and
        the per-session operand registers.  A fleet built from DIFFERENT
        patient pipelines shares none of these, and restoring state across
        banks would silently score one bank's frames against another's class
        HVs."""
        h = hashlib.sha256()
        operands = [self._tables_t[0],
                    np.concatenate([np.asarray(x)
                                    for x in self._param_owner_t]),
                    np.concatenate([np.asarray(x)
                                    for x in self._thresholds_t]),
                    np.concatenate([np.asarray(x) for x in self._density_t]),
                    self._class_rows0]
        if self._am_counts0 is not None:
            operands += [self._am_counts0, self._am_n0]
        for a in operands:
            arr = np.ascontiguousarray(np.asarray(a))
            h.update(str((arr.dtype.str, arr.shape)).encode())
            h.update(arr.tobytes())
        return h.hexdigest()[:16]

    def _state_shardings(self) -> FleetState | None:
        if self._ctx.mesh is None:
            return None
        full = self.state
        return FleetState(**{
            f: shd.sharding_for(axes, self._ctx,
                                jnp.shape(getattr(full, f)))
            for f, axes in _STATE_AXES.items()
        })

    def save(self, root: str, step: int | None = None,
             aot_dir: str | None = None) -> str:
        """Checkpoint the full fleet state (streaming accumulators + online
        AM banks) under ``root`` via ckpt.checkpoint's atomic-rename
        contract; ``step`` defaults to one past the latest.  Returns the
        checkpoint directory.

        ``aot_dir`` additionally serializes this fleet's executable set
        there (``save_aot``) and records the artifact path + validity key in
        the checkpoint manifest, which is what lets ``from_artifact``
        restore a worker without recompiling.  Relative paths are resolved
        against ``root`` at restore time."""
        if step is None:
            latest = ckpt.latest_step(root)
            step = 0 if latest is None else latest + 1
        aot_entry = None
        if aot_dir is not None:
            self.save_aot(aot_dir)
            aot_entry = {"path": aot_dir, "key": aot_mod.artifact_key()}
        meta = self._meta()
        if self._masked:
            # electrode-health carriage: the quarantine masks ride the
            # manifest meta OUTSIDE the _meta() comparison dict, so
            # checkpoints stay loadable by mask-free fleets (extra keys
            # are ignored at restore)
            meta["channel_mask"] = {
                "shape": [self._n, self._cfg.channels],
                "hex": self._cmask_h[:self._n].tobytes().hex(),
            }
        return ckpt.save(root, step, self.state, meta=meta,
                         aot=aot_entry)

    def restore(self, root: str, step: int | None = None) -> int:
        """Restore a ``save``d fleet state into THIS fleet (same bank
        geometry and session count), elastic under the current mesh: leaves
        re-shard onto however many devices the restored fleet runs on.  The
        host-side emission schedule resumes from the restored fill levels,
        so pushes continue mid-stream bit-exactly.  Returns the step."""
        if step is None:
            step = ckpt.latest_step(root)
            if step is None:
                raise FileNotFoundError(f"no fleet checkpoint under {root!r}")
        with open(os.path.join(root, f"step_{step:08d}",
                               "manifest.json")) as f:
            meta = json.load(f).get("meta", {})
        want = self._meta()
        bad = {k: (meta.get(k), v) for k, v in want.items()
               if meta.get(k) != v}
        if bad:
            raise ValueError(
                f"checkpoint does not match this fleet: {bad} "
                "(saved, expected)")
        full = ckpt.restore(root, step, like=self.state,
                            shardings=self._state_shardings())
        self._state_t = self._split_state(full)
        self._filled_h = np.asarray(full.filled).astype(np.int64)
        self._fidx_h = np.asarray(full.frame_index).astype(np.int64)
        self._dirty_t = [True] * len(self._tile_slices)
        if self._masked:
            # re-establish the checkpoint's electrode quarantine (all-live
            # when the checkpoint came from a fleet without masking)
            cm = meta.get("channel_mask")
            self._cmask_h[:] = 1
            if cm is not None:
                n, c = cm["shape"]
                if (n, c) != (self._n, self._cfg.channels):
                    raise ValueError(
                        f"checkpoint channel_mask is ({n}, {c}); this "
                        f"fleet is ({self._n}, {self._cfg.channels})")
                self._cmask_h[:self._n] = np.frombuffer(
                    bytes.fromhex(cm["hex"]), np.uint8).reshape(n, c)
            self._cmask_t = self._put_tiles(self._cmask_h, ("batch", None))
        return step
