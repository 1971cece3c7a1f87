"""The program's own spans on the trace's clock, and what they measure.

``StreamingFleet`` (``src/repro/serve/fleet.py``) writes ``fleet.*`` host
spans with ``jax.profiler.TraceAnnotation``: ``fleet.push`` around a push,
with ``fleet.stage`` (holding ``fleet.stage_wait`` when the staging slot's
previous step was still running), ``fleet.h2d`` and ``fleet.dispatch`` per
tile and round inside it, and ``fleet.collect`` around a collection, with
``fleet.d2h`` and ``fleet.decode`` per tile.  They land in the same trace as
the harness's ``bench.*`` spans and the devices' events (``bench/trace.py``),
so all of them are read on the trace's own clock, in nanoseconds.  A push's
program spans are those inside its ``bench.ingest`` / ``bench.collect``
span.

Each ``fleet.dispatch`` issues one execution of the step on its tile's
device.  Paired in order per device, they give the launch wait (dispatch
returned -> the execution's first operation: the device idle with work
queued) and the clock check: no execution may start before its dispatch
began, nor end after the host's wait for it (``bench.device_wait``)
returned.  Where a trace breaks that, ``offset`` finds the shift of the
device planes that the host's launch events give, joined to the device's
executions on their ``run_id``; the readers of this module apply it.
"""

from __future__ import annotations

import bisect
from dataclasses import dataclass, field

from bench import trace

PREFIX = "fleet."
KERNEL = "hdc_fleet_counts"   # the fleet kernel's name (its pallas_call)
SLACK_NS = 1_000_000          # a clock violation beyond this stops the run

Span = tuple[str, int, int, dict]   # name, start, end, args


class ClockError(RuntimeError):
    """The host spans and the device events are not on one clock."""


@dataclass
class Program:
    """What a trace holds of the program: its ``fleet.*`` host spans with
    their args, the device executions' ``run_id`` stats and the host events
    that carry one (to join them), and the shift put on device times."""
    spans: list[Span]
    device_runs: dict[str, list[tuple[int, int]]] = field(
        default_factory=dict)   # device plane -> (run_id, start) of modules
    host_runs: list[tuple[str, int, int]] = field(
        default_factory=list)   # (event name, run_id, start) on host planes
    offset_ns: int = 0          # added to device times by these readers


def reduce(data) -> Program:
    """``jax.profiler.ProfileData`` -> the program's spans and run ids."""
    spans, host_runs, device_runs = [], [], {}
    for plane in data.planes:
        if plane.name.startswith(trace.DEVICE_PREFIX):
            runs = device_runs.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == trace.MODULES_LINE:
                    for ev in line.events:
                        rid = dict(ev.stats).get("run_id")
                        if rid is not None:
                            runs.append((int(rid), int(ev.start_ns)))
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    spans.append((ev.name, int(ev.start_ns), int(ev.end_ns),
                                  dict(ev.stats)))
                    continue
                rid = dict(ev.stats).get("run_id")
                if rid is not None:
                    host_runs.append((ev.name, int(rid), int(ev.start_ns)))
    spans.sort(key=lambda s: s[1])
    return Program(spans, device_runs, host_runs)


def bench_spans(run, name: str) -> list[trace.Interval]:
    """The window's harness spans called ``name``, in order: one per push
    for ``bench.ingest``, ``bench.device_wait`` and ``bench.collect``."""
    lo, hi = run.window_ns
    return [(s, e) for n, s, e in run.trace.spans
            if n == name and lo <= s and e <= hi]


def per_push(run, name: str, parent: str) -> list[int] | None:
    """Nanoseconds of the program's ``name`` spans inside each of the
    window's ``parent`` spans; None where the trace holds none of them."""
    prog = getattr(run, "program", None)
    if prog is None or run.trace is None or run.window_ns is None:
        return None
    outer = bench_spans(run, parent)
    inner = [(s, e) for n, s, e, _ in prog.spans if n == name]
    if not outer or not inner:
        return None
    return [sum(e - s for s, e in inner if lo <= s and e <= hi)
            for lo, hi in outer]


def mean_ms(ns: list[int] | None) -> float | None:
    return None if not ns else sum(ns) / len(ns) / 1e6


@dataclass
class Pair:
    """One ``fleet.dispatch`` and the step execution it issued, with the
    device times shifted onto the host spans' clock."""
    push: int                 # the window's push whose ingest holds it
    device: str
    dispatch: trace.Interval
    module: trace.Interval
    first_op: int             # start of the execution's first operation


def pairs(run, offset_ns: int | None = None) -> list[Pair]:
    """Every dispatch of the window paired, in order on its tile's device
    (tile k runs on the k % n-th device used), with that device's step
    executions.  Raises ``ClockError`` when their numbers differ."""
    prog = run.program
    off = prog.offset_ns if offset_ns is None else offset_ns
    ingest = bench_spans(run, "bench.ingest")
    starts = [s for s, _ in ingest]
    n_dev = len(run.devices)
    out = []
    for j, d in enumerate(run.devices):
        disp = [(s, e) for n, s, e, a in prog.spans
                if n == "fleet.dispatch" and int(a.get("tile", 0)) % n_dev == j
                and ingest and ingest[0][0] <= s <= ingest[-1][1]]
        dev = run.trace.devices[d]
        mods = sorted((s, e) for n, s, e in dev.modules
                      if n.split("(")[0] in run.step_modules)
        if len(disp) != len(mods):
            raise ClockError(
                f"{d}: {len(disp)} fleet.dispatch spans in the window but "
                f"{len(mods)} executions of {list(run.step_modules)}")
        op_starts = sorted(s for _, s, _ in dev.ops)
        for (ds, de), (ms, me) in zip(disp, mods):
            i = bisect.bisect_left(op_starts, ms)
            first = op_starts[i] if i < len(op_starts) else ms
            out.append(Pair(push=bisect.bisect_right(starts, ds) - 1,
                            device=d, dispatch=(ds, de),
                            module=(ms + off, me + off),
                            first_op=min(first, me) + off))
    return out


def launch_wait_ns(run) -> list[int] | None:
    """Per push, the device's wait from each dispatch's end to its
    execution's first operation (0 where it started sooner), summed over
    the push's tiles and rounds."""
    prog = getattr(run, "program", None)
    if prog is None or run.window_ns is None or not any(
            n == "fleet.dispatch" for n, *_ in prog.spans) or not any(
            run.trace.devices[d].modules for d in run.devices):
        return None
    ps = pairs(run)
    out = [0] * len(bench_spans(run, "bench.ingest"))
    for p in ps:
        out[p.push] += max(0, p.first_op - p.dispatch[1])
    return out


def kernel_ns(run) -> int:
    """Device nanoseconds of the fleet kernel's operations inside the
    window, summed over the devices used."""
    if run.trace is None or run.window_ns is None:
        return 0
    lo, hi = run.window_ns
    off = run.program.offset_ns if getattr(run, "program", None) else 0
    return sum(max(0, min(e + off, hi) - max(s + off, lo))
               for d in run.devices
               for name, s, e in run.trace.devices[d].ops
               if op_name(name) == KERNEL)


def op_name(event: str) -> str:
    """The HLO instruction an ``XLA Ops`` event names, without its ``.n``
    suffix: ``%hdc_fleet_counts.1 = s32[...] custom-call(...)`` and
    ``hdc_fleet_counts.1`` both give ``hdc_fleet_counts``."""
    name = event.lstrip("%").split(" ", 1)[0].split("=", 1)[0]
    base, _, tail = name.rpartition(".")
    return base if base and tail.isdigit() else name


def clock(run, offset_ns: int | None = None) -> dict:
    """The clock check over every dispatch/execution pair of the window:
    the smallest slack of (execution start - dispatch start) and of (end of
    the push's ``bench.device_wait`` - execution end), in ms, each with its
    push.  Raises ``ClockError`` on a violation beyond ``SLACK_NS``."""
    waits = bench_spans(run, "bench.device_wait")
    ps = pairs(run, offset_ns)
    if not ps:
        raise ClockError("no fleet.dispatch span in the traced window")
    start = min((p.module[0] - p.dispatch[0], p.push) for p in ps)
    end = min((waits[p.push][1] - p.module[1], p.push) for p in ps)
    off = run.program.offset_ns if offset_ns is None else offset_ns
    notes = {"clock_pairs": len(ps),
             "clock_start_slack_ms": start[0] / 1e6,
             "clock_start_slack_push": start[1],
             "clock_end_slack_ms": end[0] / 1e6,
             "clock_end_slack_push": end[1],
             "clock_offset_ms": off / 1e6}
    for what, (slack, push) in (("starts before its dispatch", start),
                                ("ends after the host's wait", end)):
        if slack < -SLACK_NS:
            raise ClockError(f"push {push}: a step execution {what} by "
                             f"{-slack / 1e6:.3f} ms (offset {off / 1e6} "
                             f"ms); clock notes {notes}")
    return notes


def offset(prog: Program) -> int | None:
    """The shift that puts the device planes on the host's clock, from the
    host launch events and the device executions that share a ``run_id``:
    the median of (launch start - execution start), so that each execution
    starts where the host launched it at the earliest.  None where the
    trace carries no shared run id."""
    host = {}
    for _, rid, s in prog.host_runs:
        host[rid] = min(s, host.get(rid, s))
    deltas = sorted(host[rid] - s for runs in prog.device_runs.values()
                    for rid, s in runs if rid in host)
    return deltas[len(deltas) // 2] if deltas else None


def idle_gaps(run, top: int = 10) -> list[list]:
    """The longest idle gaps of the devices used in the window, device
    times shifted by the program's offset, each named by the innermost
    ``bench.*`` or ``fleet.*`` span that covers most of it (``_cover``):
    ``[name, seconds]``."""
    lo, hi = run.window_ns
    named = [(n, s, e) for n, s, e in run.trace.spans if n != trace.WINDOW]
    named += [(n, s, e) for n, s, e, _ in run.program.spans]
    off = run.program.offset_ns
    out = []
    for d in run.devices:
        busy = [(s + off, e + off)
                for s, e in trace.busy(run.trace.devices[d])]
        for s, e in trace.gaps(busy, lo, hi):
            out.append((e - s, _cover(named, s, e)))
    out.sort(key=lambda g: -g[0])
    return [[n, ns / 1e9] for ns, n in out[:top]]


def _cover(named, lo: int, hi: int) -> str:
    """The innermost (shortest) span covering more than half of ``[lo,
    hi)``; else the one covering most of it; else ``bench.window``."""
    over = [(max(0, min(e, hi) - max(s, lo)), e - s, n) for n, s, e in named]
    most = [(length, n) for ov, length, n in over if 2 * ov > hi - lo]
    if most:
        return min(most)[1]
    ov, _, n = max(over, default=(0, 0, trace.WINDOW))
    return n if ov else trace.WINDOW


def decompose(run) -> dict:
    """Each push's owed interval, due -> decisions on the host, as a chain
    of named parts (mean ms per push) that sums to it: the queue wait (host
    clock), then on the trace's clock the ingest up to the last dispatch's
    end (``fleet.stage``, ``fleet.h2d``, ``fleet.dispatch`` and the rest),
    the launch wait, the step on the device, the rest of the host's wait
    after the last execution ended, the hand-over to the collection, the
    collection (``fleet.d2h``, ``fleet.decode`` and the rest) and what
    follows it."""
    ingest = bench_spans(run, "bench.ingest")
    waits = bench_spans(run, "bench.device_wait")
    collect = bench_spans(run, "bench.collect")
    n = len(ingest)
    if not (n == len(waits) == len(collect) == len(run.pushes)) or not n:
        raise ClockError(f"{n} ingest, {len(waits)} wait and {len(collect)} "
                         f"collect spans for {len(run.pushes)} pushes")
    by_push: dict[int, list[Pair]] = {}
    for p in pairs(run):
        by_push.setdefault(p.push, []).append(p)
    parts = {k: 0 for k in ("queue_wait", "stage", "h2d", "dispatch",
                            "ingest_other", "launch_wait", "step_device",
                            "device_wait_rest", "handover", "d2h", "decode",
                            "collect_other", "after_collect")}
    spans = {k: per_push(run, f"fleet.{k}", parent) or [0] * n
             for k, parent in (("stage", "bench.ingest"),
                               ("h2d", "bench.ingest"),
                               ("dispatch", "bench.ingest"),
                               ("d2h", "bench.collect"),
                               ("decode", "bench.collect"))}
    owed = 0.0
    for i, push in enumerate(run.pushes):
        ps = by_push.get(i)
        if not ps:
            raise ClockError(f"push {i} dispatched no step in the trace")
        de = max(p.dispatch[1] for p in ps)
        fo = max(de, min(p.first_op for p in ps))
        me = max(fo, max(p.module[1] for p in ps))
        we = max(me, waits[i][1])
        for k, ns in spans.items():
            parts[k] += ns[i]
        parts["queue_wait"] += (push.start - push.due) * 1e9
        parts["ingest_other"] += de - ingest[i][0] - sum(
            spans[k][i] for k in ("stage", "h2d", "dispatch"))
        parts["launch_wait"] += fo - de
        parts["step_device"] += me - fo
        parts["device_wait_rest"] += we - me
        parts["handover"] += collect[i][0] - we
        parts["collect_other"] += collect[i][1] - collect[i][0] - sum(
            spans[k][i] for k in ("d2h", "decode"))
        parts["after_collect"] += (push.collected - push.start) * 1e9 - (
            collect[i][1] - ingest[i][0])
        owed += (push.collected - push.due) * 1e9
    out = {f"{k}_ms": v / n / 1e6 for k, v in parts.items()}
    out["owed_ms"] = owed / n / 1e6
    return out
