"""Elastic session lifecycle on the fleet's capacity tiles.

``StreamingFleet`` models production as a FIXED set of S sessions.  Real
implant telemetry is churn: streams connect, drop mid-window, reconnect
with their accumulated state, and sometimes arrive faster than the fleet
can grow.  ``ElasticFleet`` makes that lifecycle a first-class,
failure-tolerant subsystem on top of the existing tile machinery — without
giving up the property that makes the fleet fast: after ``warmup()``,
NOTHING on the admit/evict/push path compiles.

Free-slot maps over capacity tiles
    Provisioned capacity stays padded to whole tiles, so the tile-shaped
    step executables never change.  ``admit`` claims the lowest free slot
    and re-initializes it IN PLACE with one jitted ``_slot_write`` whose
    slot index is a TRACED operand (one executable serves every slot);
    ``evict`` just returns the slot to the free map — a dead slot always
    pushes a zero-length chunk, and since ``filled < window`` is a fleet
    invariant, ``n_emit = (filled + 0) // window = 0``: stale device state
    in a free slot is masked cycles, exactly the stale-staging-ring trick
    the ingest path already relies on.

Spill and compaction
    When every slot is taken the fleet SPILLS: it appends one more
    capacity tile (round-robined onto the local devices like the
    originals) up to ``max_tiles``.  ``warmup`` pre-compiles the step for
    every local device at the tile shape, so a spilled tile lands on warm
    executables — growth without recompiles.  ``compact()`` migrates the
    trailing tile's survivors into earlier free slots (snapshot out,
    slot-write in) and drops empty trailing tiles, shrinking the per-push
    working set after a churn wave recedes.

Reconnect-with-state
    ``evict(..., with_state=True)`` reads the slot's nine state rows into
    a compact host-side ``SessionSnapshot`` (serve/engine.py) — temporal
    accumulator, mid-window fill, adapted AM counter file, last emitted
    frame.  Re-admitting that snapshot (here, or into a plain
    ``SeizureSession.from_snapshot``) resumes the stream bit-exactly,
    including the next ``adapt`` against the pre-drop frame.

Overload backpressure
    ``offer`` is the admission front door: a full fleet that cannot spill
    QUEUES the arrival (bounded by ``queue_limit``) and beyond that SHEDS
    it explicitly — an "admitted" / "queued" / "shed" verdict instead of
    unbounded latency.  While arrivals are queued the fleet is overloaded
    and drops into a degraded decision-only mode: ``adapt`` becomes a
    no-op (counted in ``stats["adapt_shed"]``) so feedback processing
    never competes with decision latency under pressure.  Evictions drain
    the queue oldest-first.

Crash recovery
    ``save`` writes per-tile incremental checkpoints: tiles whose state
    did not change since the last checkpoint (``_dirty_t``, maintained by
    the step/adapt/slot-write paths) are HARD-LINKED from the previous
    step's files (``ckpt.save(..., link_from=...)``) instead of
    re-serialized, and the session table / queue / replay cursor ride in
    the manifest meta.  Every mutating call is also appended to a bounded
    in-memory replay ring; after a crash, ``restore`` + ``replay`` of the
    post-checkpoint events reproduces the uninterrupted fleet's decisions
    bit-exactly (tests/test_lifecycle.py and benchmarks/bench_churn.py
    both verify this end to end).

``benchmarks/bench_churn.py`` drives all of it at fleet scale with
Poisson arrivals/departures and reports p50/p99 decision latency and
sessions/s under churn; ``check_fleet_regression.py`` gates the ratios.
"""

from __future__ import annotations

import base64
import collections
import hashlib
import json
import os
import warnings
from typing import Hashable, Mapping, Sequence

import jax
import numpy as np

from repro.ckpt import checkpoint as ckpt
from repro.core.pipeline import HDCPipeline
from repro.runtime import aot as aot_mod
from repro.serve.engine import FrameDecision, SessionSnapshot
from repro.serve.fleet import (DEFAULT_BUCKETS, FleetRound, FleetState,
                               StreamingFleet, derive_tile)


class CapacityError(RuntimeError):
    """The fleet is full and cannot spill another tile (``max_tiles``)."""


def _slot_write(state: FleetState, slot, counts, filled, frame_index,
                class_rows, am_counts, am_n, last_frame, last_scores,
                has_frame) -> FleetState:
    """Overwrite ONE session slot's row in every state leaf.

    ``slot`` is a TRACED int32 scalar, so a single compiled program serves
    every slot of a tile, and the state is DONATED: re-initializing a slot
    rewrites the live tile buffers in place — no copy of the other
    ``tile_s - 1`` sessions, no recompile per slot."""
    return FleetState(
        counts=state.counts.at[slot].set(counts),
        filled=state.filled.at[slot].set(filled),
        frame_index=state.frame_index.at[slot].set(frame_index),
        class_rows=state.class_rows.at[slot].set(class_rows),
        am_counts=state.am_counts.at[slot].set(am_counts),
        am_n=state.am_n.at[slot].set(am_n),
        last_frame=state.last_frame.at[slot].set(last_frame),
        last_scores=state.last_scores.at[slot].set(last_scores),
        has_frame=state.has_frame.at[slot].set(has_frame),
    )


def _slot_read(state: FleetState, slot) -> tuple:
    """Gather ONE slot's row from every state leaf (the device half of an
    eviction snapshot); ``slot`` is traced like in ``_slot_write``."""
    return (state.counts[slot], state.filled[slot], state.frame_index[slot],
            state.class_rows[slot], state.am_counts[slot], state.am_n[slot],
            state.last_frame[slot], state.last_scores[slot],
            state.has_frame[slot])


class ElasticFleet(StreamingFleet):
    """A ``StreamingFleet`` whose sessions come and go at runtime.

    ``pipelines`` is the patient -> trained-pipeline bank (the set of
    per-patient configs sessions may connect with); capacity starts at ONE
    tile of ``tile`` slots and spills up to ``max_tiles`` tiles.  Sessions
    are addressed by the integer session id ``admit``/``offer`` return;
    ``push_sessions({sid: codes})`` advances whoever has traffic this
    round and returns ``{sid: [FrameDecision]}``.

    See the module docstring for the lifecycle semantics (free-slot maps,
    spill/compaction, reconnect snapshots, backpressure, replay recovery).
    Mesh sharding and fault campaigns stay on ``StreamingFleet`` — an
    elastic fleet is a per-device-tile construction.
    """

    def __init__(
        self,
        pipelines: Mapping[Hashable, HDCPipeline],
        *,
        tile: int | None = None,
        max_tiles: int = 4,
        queue_limit: int = 32,
        log_rounds: int = 64,
        buckets: Sequence[int] = DEFAULT_BUCKETS,
        backend: str | None = None,
        channel_masking: bool = False,
    ):
        if not pipelines:
            raise ValueError("ElasticFleet needs at least one pipeline")
        pids = list(pipelines)
        if tile is None:
            cfg = next(iter(pipelines.values())).cfg
            tile = derive_tile(cfg, max_bucket=max(buckets))
        if tile < len(pids):
            raise ValueError(
                f"tile={tile} < {len(pids)} patients: every patient needs "
                "at least one addressable slot in the owner cycle")
        if max_tiles < 1:
            raise ValueError(f"max_tiles={max_tiles} must be >= 1")
        # owners cycle the patient list so slot i < P starts as patient i:
        # the first P rows of the parent's per-owner arrays double as the
        # per-PATIENT init registers admissions are written from
        owners = [pids[i % len(pids)] for i in range(tile)]
        super().__init__(pipelines, owners, buckets=buckets,
                         backend=backend, tile=tile,
                         channel_masking=channel_masking)
        assert self._np == tile and len(self._tile_slices) == 1
        self._tile = int(tile)
        self._max_tiles = int(max_tiles)
        self._pid_of = {pid: i for i, pid in enumerate(pids)}
        p = len(pids)
        # host mirrors of the per-slot operand registers (device copies are
        # re-put per touched tile on admit/evict moves)
        self._thr_h = np.concatenate(
            [np.asarray(x) for x in self._thresholds_t])
        self._prow_h = np.concatenate(
            [np.asarray(x) for x in self._param_owner_t])
        self._dens_h = np.concatenate(
            [np.asarray(x) for x in self._density_t])
        # per-patient init registers (rows :P are patients in pid order)
        self._pat_thr = self._thr_h[:p].copy()
        self._pat_prow = self._prow_h[:p].copy()
        self._pat_dens = self._dens_h[:p].copy()
        self._pat_rows = self._class_rows0[:p].copy()
        if self._am_counts0 is not None:
            self._pat_am_counts = self._am_counts0[:p].copy()
            self._pat_am_n = self._am_n0[:p].copy()
        else:
            self._pat_am_counts = self._pat_am_n = None
        # lifecycle bookkeeping
        self._free: list[set[int]] = [set(range(tile))]
        self._sid_slot: dict[int, int] = {}
        self._slot_sid: dict[int, int] = {}
        self._sid_pid: dict[int, Hashable] = {}
        self._next_sid = 0
        self._queue: collections.deque = collections.deque()
        self._queue_limit = int(queue_limit)
        self._log: collections.deque = collections.deque(
            maxlen=int(log_rounds))
        self._op_id = 0
        self._stats = {"admitted": 0, "evicted": 0, "queued": 0, "shed": 0,
                       "adapt_shed": 0, "spills": 0, "compactions": 0}
        self._push_buf: np.ndarray | None = None
        # slot-surgery executables: jit fallbacks + per-(device, tile_s)
        # warmed executables, mirroring the step's _exec discipline
        self._slot_write_jit = jax.jit(_slot_write, donate_argnums=(0,))
        self._slot_read_jit = jax.jit(_slot_read)
        self._slot_exec: dict[tuple, jax.stages.Compiled] = {}
        self._read_exec: dict[tuple, jax.stages.Compiled] = {}

    # -- introspection ------------------------------------------------------

    @property
    def capacity(self) -> int:
        """Provisioned slots (tiles x tile size); grows on spill, shrinks
        on compaction."""
        return self._np

    @property
    def n_tiles(self) -> int:
        return len(self._tile_slices)

    @property
    def sessions(self) -> dict[int, Hashable]:
        """``{session id: patient id}`` of every live session."""
        return dict(sorted(self._sid_pid.items()))

    @property
    def free_slots(self) -> int:
        return sum(len(f) for f in self._free)

    @property
    def queue_depth(self) -> int:
        return len(self._queue)

    @property
    def overloaded(self) -> bool:
        """True while admissions are queued — the fleet sheds adapt work
        (decision-only degraded mode) until the queue drains."""
        return bool(self._queue)

    @property
    def stats(self) -> dict[str, int]:
        return dict(self._stats)

    @property
    def op_id(self) -> int:
        """Monotonic cursor of mutating operations; checkpoints record it
        and ``events_since``/``replay`` are keyed by it."""
        return self._op_id

    def slot_of(self, sid: int) -> int:
        return self._sid_slot[sid]

    # -- slot surgery (device side) -----------------------------------------

    def _slot_avals(self, dev) -> tuple:
        cfg = self._cfg
        c = cfg.n_classes
        sds = self._sds
        return (
            jax.tree.map(lambda x: sds(x, dev), self._state_t[0]),
            sds(np.int32(0), dev),
            sds(np.zeros((cfg.dim,), np.int32), dev),
            sds(np.int32(0), dev),
            sds(np.int32(0), dev),
            sds(np.zeros((c, cfg.words), np.uint32), dev),
            sds(np.zeros((c, cfg.dim), np.int32), dev),
            sds(np.zeros((c,), np.int32), dev),
            sds(np.zeros((cfg.words,), np.uint32), dev),
            sds(np.zeros((c,), np.int32), dev),
            sds(np.int32(0), dev),
        )

    def _fresh_rows(self, p: int) -> tuple:
        """A patient's pristine state row (fresh connection)."""
        cfg = self._cfg
        c = cfg.n_classes
        if self._pat_am_counts is not None:
            am_c, am_n = self._pat_am_counts[p], self._pat_am_n[p]
        else:
            am_c = np.zeros((c, cfg.dim), np.int32)
            am_n = np.zeros((c,), np.int32)
        return (np.zeros((cfg.dim,), np.int32), np.int32(0), np.int32(0),
                self._pat_rows[p], am_c, am_n,
                np.zeros((cfg.words,), np.uint32),
                np.zeros((c,), np.int32), np.int32(0))

    def _snap_rows(self, snap: SessionSnapshot) -> tuple:
        """A reconnecting session's state row, validated against this
        fleet's geometry."""
        cfg = self._cfg
        c = cfg.n_classes
        counts = np.asarray(snap.counts, np.int32)
        rows = np.asarray(snap.class_rows, np.uint32)
        lastf = np.asarray(snap.last_frame, np.uint32)
        lasts = np.asarray(snap.last_scores, np.int32)
        if (counts.shape != (cfg.dim,) or rows.shape != (c, cfg.words)
                or lastf.shape != (cfg.words,) or lasts.shape != (c,)):
            raise ValueError(
                f"snapshot geometry {counts.shape}/{rows.shape} does not "
                f"match this fleet (dim={cfg.dim}, classes={c}, "
                f"words={cfg.words})")
        if not 0 <= int(snap.filled) < cfg.window:
            raise ValueError(
                f"snapshot filled={snap.filled} outside [0, {cfg.window})")
        if snap.am_counts is not None:
            am_c = np.asarray(snap.am_counts, np.int32)
            am_n = np.asarray(snap.am_n, np.int32)
            if am_c.shape != (c, cfg.dim) or am_n.shape != (c,):
                raise ValueError(
                    f"snapshot AM geometry {am_c.shape} does not match "
                    f"this fleet ({c}, {cfg.dim})")
        else:
            am_c = np.zeros((c, cfg.dim), np.int32)
            am_n = np.zeros((c,), np.int32)
        return (counts, np.int32(snap.filled), np.int32(snap.frame_index),
                rows, am_c, am_n, lastf, lasts, np.int32(snap.has_frame))

    def _reput_registers(self, k: int) -> None:
        sl, d = self._tile_slices[k], self._tile_devs[k]
        self._thresholds_t[k] = self._put_tile(self._thr_h[sl],
                                               ("batch",), d)
        self._param_owner_t[k] = self._put_tile(self._prow_h[sl],
                                                ("batch",), d)
        self._density_t[k] = self._put_tile(self._dens_h[sl], ("batch",), d)
        if self._masked:
            self._cmask_t[k] = self._put_tile(self._cmask_h[sl],
                                              ("batch", None), d)

    def _write_slot(self, slot: int, pid: Hashable,
                    snapshot: SessionSnapshot | None) -> None:
        """Re-initialize one slot's device row (fresh or from a snapshot)
        and its host mirrors/operand registers.  Recompile-free after
        ``warmup``: the slot index is a traced operand."""
        k = slot // self._tile
        sl, d = self._tile_slices[k], self._tile_devs[k]
        p = self._pid_of[pid]
        rows = (self._fresh_rows(p) if snapshot is None
                else self._snap_rows(snapshot))
        args = (self._state_t[k], jax.device_put(np.int32(slot - sl.start), d)
                ) + tuple(jax.device_put(r, d) for r in rows)
        akey = (d, sl.stop - sl.start)
        fn = self._slot_exec.get(akey)
        if fn is not None and not aot_mod.accepts(fn, args):
            self._slot_exec.pop(akey)
            fn = None
        self._state_t[k] = (fn or self._slot_write_jit)(*args)
        self._dirty_t[k] = True
        self._filled_h[slot] = int(rows[1])
        self._fidx_h[slot] = int(rows[2])
        self._thr_h[slot] = self._pat_thr[p]
        self._prow_h[slot] = self._pat_prow[p]
        self._dens_h[slot] = self._pat_dens[p]
        if self._masked:
            # electrode quarantine follows the SESSION: a reconnecting
            # snapshot re-installs its mask, a fresh admit (or a snapshot
            # from an unmasked source) starts all-live
            ch = self._cfg.channels
            if snapshot is not None and snapshot.channel_mask is not None:
                cm = np.asarray(snapshot.channel_mask, np.uint8)
                if cm.shape != (ch,):
                    raise ValueError(
                        f"snapshot channel_mask must be ({ch},), got "
                        f"{cm.shape}")
                self._cmask_h[slot] = cm
            else:
                self._cmask_h[slot] = 1
        self._reput_registers(k)

    def _snapshot_slot(self, slot: int) -> SessionSnapshot:
        """Read one slot's state row into a host-side SessionSnapshot.
        This is control-plane code: the ``np.asarray`` syncs are explicit
        and intentional (an eviction must land its state on the host)."""
        k = slot // self._tile
        sl, d = self._tile_slices[k], self._tile_devs[k]
        args = (self._state_t[k],
                jax.device_put(np.int32(slot - sl.start), d))
        akey = (d, sl.stop - sl.start)
        fn = self._read_exec.get(akey)
        if fn is not None and not aot_mod.accepts(fn, args):
            self._read_exec.pop(akey)
            fn = None
        rows = (fn or self._slot_read_jit)(*args)
        counts, _, _, rows9, am_c, am_n, lastf, lasts, hasf = (
            np.asarray(r) for r in rows)
        has_am = self._am_counts0 is not None
        return SessionSnapshot(
            patient_id=self._sid_pid[self._slot_sid[slot]],
            counts=counts,
            filled=int(self._filled_h[slot]),
            frame_index=int(self._fidx_h[slot]),
            class_rows=rows9,
            am_counts=am_c if has_am else None,
            am_n=am_n if has_am else None,
            last_frame=lastf, last_scores=lasts, has_frame=int(hasf),
            channel_mask=(self._cmask_h[slot].copy()
                          if self._masked else None))

    # -- tile growth / shrink -----------------------------------------------

    def _spill_tile(self) -> int:
        """Append one more capacity tile (round-robined onto the local
        devices).  Recompile-free when ``warmup`` ran: every local device
        already holds the tile-shaped executables."""
        if len(self._tile_slices) >= self._max_tiles:
            raise CapacityError(
                f"fleet at max_tiles={self._max_tiles} "
                f"({self.capacity} slots)")
        k = len(self._tile_slices)
        t = self._tile
        start = self._np
        sl = slice(start, start + t)
        devs = jax.local_devices()
        d = devs[k % len(devs)]
        self._tile_slices.append(sl)
        self._tile_devs.append(d)
        # reuse an existing per-device table-bank copy when one lives on
        # this device already; first landing on a new device pays one put
        for i, dd in enumerate(self._tile_devs[:-1]):
            if dd == d:
                self._tables_t.append(self._tables_t[i])
                break
        else:
            self._tables_t.append(jax.device_put(self._tables_t[0], d))
        # grow the host-side per-slot arrays by one tile of placeholder
        # rows (first tile's pattern; admissions overwrite per slot)
        self._class_rows0 = np.concatenate(
            [self._class_rows0, self._class_rows0[:t]])
        if self._am_counts0 is not None:
            self._am_counts0 = np.concatenate(
                [self._am_counts0, self._am_counts0[:t]])
            self._am_n0 = np.concatenate([self._am_n0, self._am_n0[:t]])
        for name in ("_thr_h", "_prow_h", "_dens_h"):
            arr = getattr(self, name)
            setattr(self, name, np.concatenate([arr, arr[:t]]))
        self._filled_h = np.concatenate(
            [self._filled_h, np.zeros((t,), np.int64)])
        self._fidx_h = np.concatenate(
            [self._fidx_h, np.zeros((t,), np.int64)])
        if self._masked:
            self._cmask_h = np.concatenate(
                [self._cmask_h,
                 np.ones((t, self._cfg.channels), np.uint8)])
        self._np += t
        self._n = self._np
        for lst in (self._thresholds_t, self._param_owner_t,
                    self._density_t):
            lst.append(None)  # filled by _reput_registers just below
        if self._masked:
            self._cmask_t.append(None)  # likewise
        self._reput_registers(k)
        self._state_t.append(self._zero_state(sl, d))
        self._stage_t.append({})
        self._stage_busy.append({})
        self._dirty_t.append(True)
        self._free.append(set(range(start, start + t)))
        self._ragged_buf = None  # parent scatter buffer is capacity-shaped
        self._push_buf = None
        self._stats["spills"] += 1
        if self._exec:
            self._warm_tile(k)
        return k

    def _warm_tile(self, k: int) -> None:
        """Ensure tile ``k``'s device holds the step/adapt/slot
        executables; compiles only what ``warmup`` did not already cover
        (nothing, when warmup ran — spill stays recompile-free)."""
        d = self._tile_devs[k]
        t = self._tile
        for b in self._buckets:
            if (d, t, b) not in self._exec:
                self._exec[(d, t, b)] = self._step.lower(
                    *self._step_avals(k, b, dev=d)).compile()
        if self._am_counts0 is not None and (d, t) not in self._adapt_exec:
            self._adapt_exec[(d, t)] = self._adapt_step.lower(
                *self._adapt_avals(k, dev=d)).compile()
        if (d, t) not in self._slot_exec:
            avals = self._slot_avals(d)
            self._slot_exec[(d, t)] = self._slot_write_jit.lower(
                *avals).compile()
            self._read_exec[(d, t)] = self._slot_read_jit.lower(
                *avals[:2]).compile()

    def _drop_last_tile(self) -> None:
        k = len(self._tile_slices) - 1
        sl = self._tile_slices[k]
        if any(slot in self._slot_sid for slot in range(sl.start, sl.stop)):
            raise RuntimeError("dropping a tile with live sessions")
        for lst in (self._tile_slices, self._tile_devs, self._state_t,
                    self._tables_t, self._thresholds_t, self._param_owner_t,
                    self._density_t, self._stage_t, self._stage_busy,
                    self._dirty_t, self._free):
            lst.pop()
        if self._masked:
            self._cmask_t.pop()
        t = self._tile
        self._np -= t
        self._n = self._np
        for name in ("_filled_h", "_fidx_h", "_thr_h", "_prow_h",
                     "_dens_h"):
            setattr(self, name, getattr(self, name)[:self._np].copy())
        if self._masked:
            self._cmask_h = self._cmask_h[:self._np].copy()
        self._class_rows0 = self._class_rows0[:self._np].copy()
        if self._am_counts0 is not None:
            self._am_counts0 = self._am_counts0[:self._np].copy()
            self._am_n0 = self._am_n0[:self._np].copy()
        self._ragged_buf = None
        self._push_buf = None

    # -- admission / eviction -----------------------------------------------

    def _logged(self, kind: str, payload) -> None:
        self._log.append((self._op_id, kind, payload))
        self._op_id += 1

    def _take_slot(self) -> int:
        """Claim the lowest free slot, spilling a tile when none is free;
        raises CapacityError at max_tiles."""
        for free in self._free:
            if free:
                slot = min(free)
                free.discard(slot)
                return slot
        k = self._spill_tile()
        slot = min(self._free[k])
        self._free[k].discard(slot)
        return slot

    def _place(self, pid: Hashable,
               snapshot: SessionSnapshot | None) -> int:
        slot = self._take_slot()
        sid = self._next_sid
        self._next_sid += 1
        self._write_slot(slot, pid, snapshot)
        self._sid_slot[sid] = slot
        self._slot_sid[slot] = sid
        self._sid_pid[sid] = pid
        self._stats["admitted"] += 1
        return sid

    def _check_admission(self, pid: Hashable,
                         snapshot: SessionSnapshot | None) -> None:
        if pid not in self._pid_of:
            raise KeyError(f"unknown patient id {pid!r}")
        if snapshot is not None and snapshot.patient_id is not None \
                and snapshot.patient_id != pid:
            raise ValueError(
                f"snapshot belongs to patient {snapshot.patient_id!r}, "
                f"admission names {pid!r}")

    def admit(self, patient_id: Hashable, *,
              snapshot: SessionSnapshot | None = None) -> int:
        """Admit one session (fresh, or resuming from an eviction
        ``SessionSnapshot``) into the lowest free slot; returns its session
        id.  Spills a new tile when full; raises :class:`CapacityError` at
        ``max_tiles`` — use :meth:`offer` for queue/shed semantics."""
        self._check_admission(patient_id, snapshot)
        self._logged("admit", (patient_id, snapshot))
        return self._place(patient_id, snapshot)

    def offer(self, patient_id: Hashable, *,
              snapshot: SessionSnapshot | None = None
              ) -> tuple[str, int | None]:
        """Backpressured admission: ``("admitted", sid)`` when a slot (or a
        spill) is available, ``("queued", None)`` when full but the bounded
        queue has room (drained oldest-first by evictions), and
        ``("shed", None)`` beyond that — the explicit overload decision."""
        self._check_admission(patient_id, snapshot)
        if snapshot is not None and snapshot.patient_id is None:
            # queued snapshots must carry their patient for ckpt round-trips
            snapshot = SessionSnapshot(**{
                **snapshot.__dict__, "patient_id": patient_id})
        self._logged("offer", (patient_id, snapshot))
        if self._queue or self.free_slots == 0 and \
                len(self._tile_slices) >= self._max_tiles:
            if len(self._queue) >= self._queue_limit:
                self._stats["shed"] += 1
                return ("shed", None)
            self._queue.append((patient_id, snapshot))
            self._stats["queued"] += 1
            return ("queued", None)
        return ("admitted", self._place(patient_id, snapshot))

    def evict(self, session_ids: Sequence[int], *,
              with_state: bool = True
              ) -> dict[int, SessionSnapshot | None]:
        """Evict sessions, returning ``{sid: SessionSnapshot}`` (``None``
        values under ``with_state=False`` — a drop with no reconnect
        intent).  Slots return to the free map without touching device
        state (free slots are masked cycles) and queued admissions drain
        into them oldest-first."""
        sids = [int(s) for s in session_ids]
        for sid in sids:
            if sid not in self._sid_slot:
                raise KeyError(f"unknown session id {sid}")
        self._logged("evict", (tuple(sids), with_state))
        out: dict[int, SessionSnapshot | None] = {}
        for sid in sids:
            slot = self._sid_slot[sid]
            out[sid] = self._snapshot_slot(slot) if with_state else None
            self._free[slot // self._tile].add(slot)
            del self._sid_slot[sid]
            del self._slot_sid[slot]
            del self._sid_pid[sid]
            self._stats["evicted"] += 1
        self._drain_queue()
        return out

    def _drain_queue(self) -> None:
        while self._queue:
            try:
                slot = self._take_slot()
            except CapacityError:
                return
            pid, snap = self._queue.popleft()
            sid = self._next_sid
            self._next_sid += 1
            self._write_slot(slot, pid, snap)
            self._sid_slot[sid] = slot
            self._slot_sid[slot] = sid
            self._sid_pid[sid] = pid
            self._stats["admitted"] += 1

    def compact(self) -> int:
        """Defragment: migrate the trailing tile's sessions into earlier
        free slots (snapshot out, slot-write in) and drop trailing tiles
        that empty out, shrinking provisioned capacity.  Returns the
        number of tiles dropped.  A tile is only drained when the earlier
        tiles can absorb ALL its sessions."""
        self._logged("compact", ())
        dropped = 0
        while len(self._tile_slices) > 1:
            k = len(self._tile_slices) - 1
            sl = self._tile_slices[k]
            live = sorted(s for s in range(sl.start, sl.stop)
                          if s in self._slot_sid)
            if len(live) > sum(len(self._free[j]) for j in range(k)):
                break
            for slot in live:
                sid = self._slot_sid[slot]
                snap = self._snapshot_slot(slot)
                del self._slot_sid[slot]
                new_slot = self._take_slot()  # earlier tiles have room
                self._write_slot(new_slot, self._sid_pid[sid], snap)
                self._sid_slot[sid] = new_slot
                self._slot_sid[new_slot] = sid
            self._drop_last_tile()
            dropped += 1
            self._stats["compactions"] += 1
        return dropped

    # -- traffic ------------------------------------------------------------

    def push_sessions_raw(self, chunks: Mapping[int, np.ndarray]
                          ) -> tuple[list[FleetRound], dict[int, int]]:
        """Advance the sessions named in ``chunks`` (``{sid: (t, channels)
        uint8 codes}``, lengths may differ; everyone else idles this
        round).  Returns the raw device rounds plus the ``{sid: slot}``
        routing captured at push time; ``push_sessions`` is the
        materializing wrapper."""
        ch = self._cfg.channels
        lengths = np.zeros((self._np,), np.int64)
        arrs: dict[int, np.ndarray] = {}
        t_max = 0
        for sid, codes in chunks.items():
            slot = self._sid_slot.get(int(sid))
            if slot is None:
                raise KeyError(f"unknown session id {sid}")
            a = np.asarray(codes, np.uint8)
            if a.size == 0:
                a = a.reshape(0, ch)
            if a.ndim != 2 or a.shape[1] != ch:
                raise ValueError(
                    f"session {sid}: chunk must be (t, {ch}), "
                    f"got {a.shape}")
            arrs[slot] = a
            lengths[slot] = a.shape[0]
            t_max = max(t_max, a.shape[0])
        self._logged("push", {int(s): arrs[self._sid_slot[int(s)]].copy()
                              for s in chunks})
        mapping = {int(sid): self._sid_slot[int(sid)] for sid in chunks}
        if t_max == 0:
            return [], mapping
        if self._push_buf is None or self._push_buf.shape[0] < self._np \
                or self._push_buf.shape[1] < t_max:
            cap = max(t_max, self._buckets[-1],
                      0 if self._push_buf is None
                      else 2 * self._push_buf.shape[1])
            self._push_buf = np.zeros((self._np, cap, ch), np.uint8)
        big = self._push_buf
        for slot, a in arrs.items():
            big[slot, :a.shape[0]] = a  # stale bytes past t are masked
        return self._rounds(big, lengths), mapping

    def push_sessions(self, chunks: Mapping[int, np.ndarray]
                      ) -> dict[int, list[FrameDecision]]:
        """``push_sessions_raw`` + decision materialization: returns
        ``{sid: [FrameDecision]}`` for every pushed session (empty list
        when its chunk completed no frame)."""
        rounds, mapping = self.push_sessions_raw(chunks)
        decs = self.collect_decisions(rounds)
        return {sid: decs[slot] for sid, slot in mapping.items()}

    def adapt(self, labels: Mapping[int, int], *,  # type: ignore[override]
              margin: float = 0.0) -> dict[int, bool]:
        """Feedback for live sessions: ``{sid: true label of its last
        emitted frame}``.  Under overload (queued admissions) the fleet is
        in decision-only degraded mode and the whole call is SHED — every
        verdict False, counted in ``stats["adapt_shed"]`` — so adaptation
        never competes with decision latency while the queue drains."""
        labels = {int(s): int(v) for s, v in labels.items()}
        for sid in labels:
            if sid not in self._sid_slot:
                raise KeyError(f"unknown session id {sid}")
        self._logged("adapt", (dict(labels), float(margin)))
        if self._queue:
            self._stats["adapt_shed"] += 1
            return {sid: False for sid in labels}
        full = np.full((self._n,), -1, np.int64)
        for sid, lab in labels.items():
            full[self._sid_slot[sid]] = lab
        applied = super().adapt(full, margin=margin)
        return {sid: bool(applied[self._sid_slot[sid]]) for sid in labels}

    # -- warmup / AOT -------------------------------------------------------

    def warmup(self, *, aot: aot_mod.AOTArtifact | None = None,
               buckets: Sequence[int] | None = None) -> dict[str, int]:
        """Parent warmup plus the elastic extras: the step/adapt
        executables for EVERY local device at the tile shape (a spilled
        tile round-robins onto any of them and must land warm) and the
        slot-write/slot-read surgery programs.  After this, admit / evict
        / spill / compact / push are all recompile-free."""
        stats = super().warmup(aot=aot, buckets=buckets)
        t = self._tile
        for d in jax.local_devices():
            for b in buckets or self._buckets:
                if (d, t, b) in self._exec:
                    continue
                compiled = None
                if aot is not None and d == jax.local_devices()[0]:
                    compiled = aot.compile(self._aot_name("step", t, b),
                                           *self._step_avals(0, b, dev=None))
                if compiled is None:
                    compiled = self._step.lower(
                        *self._step_avals(0, b, dev=d)).compile()
                    stats["compiled"] += 1
                else:
                    stats["loaded"] += 1
                self._exec[(d, t, b)] = compiled
            if self._am_counts0 is not None and (d, t) not in \
                    self._adapt_exec:
                self._adapt_exec[(d, t)] = self._adapt_step.lower(
                    *self._adapt_avals(0, dev=d)).compile()
                stats["compiled"] += 1
            if (d, t) not in self._slot_exec:
                avals = self._slot_avals(d)
                self._slot_exec[(d, t)] = self._slot_write_jit.lower(
                    *avals).compile()
                self._read_exec[(d, t)] = self._slot_read_jit.lower(
                    *avals[:2]).compile()
                stats["compiled"] += 2
        return stats

    # -- replay recovery ----------------------------------------------------

    def events_since(self, op_id: int) -> list[tuple]:
        """The replay-ring suffix at or after ``op_id`` (a checkpoint's
        recorded cursor).  Raises when the bounded ring has already
        dropped events from that window — checkpoint more often or raise
        ``log_rounds``."""
        events = [e for e in self._log if e[0] >= op_id]
        if events and events[0][0] != op_id and \
                (not self._log or self._log[0][0] > op_id):
            raise ValueError(
                f"replay ring starts at op {self._log[0][0]}, checkpoint "
                f"cursor is {op_id}: events were dropped (log_rounds="
                f"{self._log.maxlen})")
        return events

    def replay(self, events: Sequence[tuple]) -> dict[int, object]:
        """Re-apply a contiguous event suffix (``events_since`` of the
        surviving fleet, or a mirrored ring) onto a just-restored fleet.
        Every mutating op re-executes through the public API — and
        re-logs, so the restored fleet's ring keeps covering future
        crashes.  Returns ``{op_id: result}`` (push decisions, admit sids,
        offer verdicts, adapt verdict maps); a restarted worker's push
        results are bit-exact with the uninterrupted run's."""
        results: dict[int, object] = {}
        for op, kind, payload in events:
            if op != self._op_id:
                raise ValueError(
                    f"replay gap: event {op} arrived while the fleet "
                    f"expects {self._op_id} (non-contiguous suffix)")
            if kind == "push":
                results[op] = self.push_sessions(payload)
            elif kind == "admit":
                pid, snap = payload
                results[op] = self.admit(pid, snapshot=snap)
            elif kind == "offer":
                pid, snap = payload
                results[op] = self.offer(pid, snapshot=snap)
            elif kind == "evict":
                sids, with_state = payload
                results[op] = self.evict(sids, with_state=with_state)
            elif kind == "adapt":
                labels, margin = payload
                results[op] = self.adapt(labels, margin=margin)
            elif kind == "compact":
                results[op] = self.compact()
            else:  # pragma: no cover - ring holds only the kinds above
                raise ValueError(f"unknown replay event kind {kind!r}")
        return results

    # -- durability ---------------------------------------------------------

    @staticmethod
    def _tile_key(k: int) -> str:
        return f"tile_{k:02d}"

    def _meta(self) -> dict:
        return {
            "kind": "elastic_fleet",
            "tile": self._tile,
            "dim": self._cfg.dim,
            "window": self._cfg.window,
            "n_classes": self._cfg.n_classes,
            "variant": self._cfg.variant,
            "bank": self._bank_fingerprint(),
        }

    def _bank_fingerprint(self) -> str:
        """PATIENT-level bank digest: unlike the parent's per-slot version
        this is invariant to which sessions currently occupy which slots,
        so checkpoints stay valid across admissions/evictions/spills as
        long as the trained per-patient bank is the same."""
        h = hashlib.sha256()
        operands = [self._tables_t[0], self._pat_prow, self._pat_thr,
                    self._pat_dens, self._pat_rows]
        if self._pat_am_counts is not None:
            operands += [self._pat_am_counts, self._pat_am_n]
        for a in operands:
            arr = np.ascontiguousarray(np.asarray(a))
            h.update(str((arr.dtype.str, arr.shape)).encode())
            h.update(arr.tobytes())
        return h.hexdigest()[:16]

    def _lifecycle_meta(self) -> dict:
        out = {
            "n_tiles": len(self._tile_slices),
            "sessions": [[sid, slot, json.dumps(self._sid_pid[sid])]
                         for sid, slot in sorted(self._sid_slot.items())],
            "next_sid": self._next_sid,
            "op_id": self._op_id,
            "queue": [[json.dumps(pid),
                       None if snap is None
                       else base64.b64encode(snap.to_bytes()).decode()]
                      for pid, snap in self._queue],
            "stats": dict(self._stats),
        }
        if self._masked:
            out["channel_mask"] = {
                "shape": [self._np, self._cfg.channels],
                "hex": self._cmask_h[:self._np].tobytes().hex(),
            }
        return out

    def save(self, root: str, step: int | None = None,
             aot_dir: str | None = None) -> str:
        """Incremental per-tile checkpoint: tiles untouched since the last
        ``save`` are hard-linked from the previous step's files instead of
        re-serialized (``ckpt.save(..., link_from=...)``); the session
        table, admission queue (snapshots and all) and the replay cursor
        ride in the manifest meta.  ``restore`` + ``replay`` of the
        post-cursor events is the crash-recovery contract."""
        if step is None:
            latest = ckpt.latest_step(root)
            step = 0 if latest is None else latest + 1
        aot_entry = None
        if aot_dir is not None:
            self.save_aot(aot_dir)
            aot_entry = {"path": aot_dir, "key": aot_mod.artifact_key()}
        tree = {self._tile_key(k): st
                for k, st in enumerate(self._state_t)}
        link_from: dict[str, str] = {}
        prev = ckpt.latest_step(root)
        if prev is not None and prev < step:
            try:
                prev_files = ckpt.leaf_files(root, prev)
            except (OSError, json.JSONDecodeError):
                prev_files = {}
            for k in range(len(self._state_t)):
                if self._dirty_t[k]:
                    continue
                prefix = self._tile_key(k) + "/"
                link_from.update({key: path
                                  for key, path in prev_files.items()
                                  if key.startswith(prefix)})
        meta = dict(self._meta())
        meta["lifecycle"] = self._lifecycle_meta()
        path = ckpt.save(root, step, tree, meta=meta, aot=aot_entry,
                         link_from=link_from)
        self._dirty_t = [False] * len(self._state_t)
        return path

    def restore(self, root: str, step: int | None = None) -> int:
        """Restore a ``save``d elastic fleet into THIS fleet (same patient
        bank and tile size; the tile COUNT adapts — the restoring fleet
        spills or drops tiles to match the checkpoint).  Live sessions,
        the queue and the replay cursor come back exactly; follow with
        ``replay(events)`` to reproduce post-checkpoint traffic."""
        if step is None:
            step = ckpt.latest_step(root)
            if step is None:
                raise FileNotFoundError(
                    f"no fleet checkpoint under {root!r}")
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
        life = meta.get("lifecycle")
        if life is None:
            raise ValueError(
                "checkpoint lacks lifecycle meta (saved by a non-elastic "
                "fleet?)")  # unreachable after the kind check, belt+braces
        # adapt provisioned capacity to the checkpoint's tile count
        self._sid_slot.clear()
        self._slot_sid.clear()
        self._sid_pid.clear()
        self._queue.clear()
        self._log.clear()
        n_tiles = int(life["n_tiles"])
        while len(self._tile_slices) < n_tiles:
            self._spill_tile()
        while len(self._tile_slices) > n_tiles:
            self._drop_last_tile()
        like = {self._tile_key(k): self._state_t[k]
                for k in range(n_tiles)}
        shardings = {
            self._tile_key(k): jax.tree.map(
                lambda _, d=self._tile_devs[k]:
                    jax.sharding.SingleDeviceSharding(d),
                self._state_t[k])
            for k in range(n_tiles)}
        restored = ckpt.restore(root, step, like=like, shardings=shardings)
        for k in range(n_tiles):
            self._state_t[k] = restored[self._tile_key(k)]
        filled = np.concatenate(
            [np.asarray(restored[self._tile_key(k)].filled)
             for k in range(n_tiles)])
        fidx = np.concatenate(
            [np.asarray(restored[self._tile_key(k)].frame_index)
             for k in range(n_tiles)])
        self._filled_h = filled.astype(np.int64)
        self._fidx_h = fidx.astype(np.int64)
        # session table + per-slot operand registers
        self._free = [set(range(sl.start, sl.stop))
                      for sl in self._tile_slices]
        self._thr_h[:] = self._pat_thr[0]
        self._prow_h[:] = self._pat_prow[0]
        self._dens_h[:] = self._pat_dens[0]
        for sid, slot, pid_json in life["sessions"]:
            pid = json.loads(pid_json)
            if pid not in self._pid_of:
                raise ValueError(
                    f"checkpointed session {sid} belongs to unknown "
                    f"patient {pid!r}")
            sid, slot = int(sid), int(slot)
            self._free[slot // self._tile].discard(slot)
            self._sid_slot[sid] = slot
            self._slot_sid[slot] = sid
            self._sid_pid[sid] = pid
            p = self._pid_of[pid]
            self._thr_h[slot] = self._pat_thr[p]
            self._prow_h[slot] = self._pat_prow[p]
            self._dens_h[slot] = self._pat_dens[p]
        if self._masked:
            self._cmask_h[:] = 1
            cm = life.get("channel_mask")
            if cm is not None:
                n, c = (int(v) for v in cm["shape"])
                if (n, c) != (self._np, self._cfg.channels):
                    raise ValueError(
                        f"checkpoint channel_mask is ({n}, {c}); this "
                        f"fleet provisions ({self._np}, "
                        f"{self._cfg.channels})")
                self._cmask_h[:] = np.frombuffer(
                    bytes.fromhex(cm["hex"]), np.uint8).reshape(n, c)
        for k in range(n_tiles):
            self._reput_registers(k)
        for pid_json, b64snap in life["queue"]:
            snap = (None if b64snap is None
                    else SessionSnapshot.from_bytes(
                        base64.b64decode(b64snap)))
            self._queue.append((json.loads(pid_json), snap))
        self._next_sid = int(life["next_sid"])
        self._op_id = int(life["op_id"])
        self._stats.update({k: int(v)
                            for k, v in life.get("stats", {}).items()})
        self._dirty_t = [True] * n_tiles
        return step

    @classmethod
    def from_checkpoint(
        cls,
        pipelines: Mapping[Hashable, HDCPipeline],
        root: str,
        *,
        step: int | None = None,
        aot_dir: str | None = None,
        warm: bool = True,
        **fleet_kwargs,
    ) -> "ElasticFleet":
        """Worker-restart path: build an elastic fleet, warm it (from the
        checkpoint's recorded AOT artifact when valid), and restore the
        checkpointed lifecycle state.  The caller then ``replay``s the
        surviving event suffix to catch up to the crash point."""
        fleet = cls(pipelines, **fleet_kwargs)
        if step is None:
            step = ckpt.latest_step(root)
            if step is None:
                raise FileNotFoundError(
                    f"no fleet checkpoint under {root!r}")
        with open(os.path.join(root, f"step_{step:08d}",
                               "manifest.json")) as f:
            manifest = json.load(f)
        art = None
        path = aot_dir
        if path is None:
            entry = manifest.get("aot")
            if entry is not None:
                saved_key = entry.get("key")
                bad = aot_mod.stale_fields(saved_key or {},
                                           aot_mod.artifact_key())
                if bad:
                    warnings.warn(
                        f"checkpoint AOT artifact is stale ({bad}); "
                        "warming via JIT", stacklevel=2)
                else:
                    path = entry.get("path")
                    if path is not None and not os.path.isabs(path):
                        path = os.path.join(root, path)
        if path is not None:
            art = aot_mod.load_artifact(path)
        if warm:
            fleet.warmup(aot=art)
        fleet.restore(root, step)
        return fleet

    @classmethod
    def from_artifact(cls, *args, **kwargs):  # pragma: no cover - guard
        raise NotImplementedError(
            "ElasticFleet restores via from_checkpoint(pipelines, root) — "
            "its session set lives in the checkpoint, not a constructor "
            "owners list")
