"""Reduction of a profiler trace to the intervals the metric readers use.

A trace (an ``.xplane.pb`` read with ``jax.profiler.ProfileData``) holds one
plane per device (``/device:TPU:<n>``) and host planes with a line per
thread.  On a device plane the ``XLA Ops`` line has one event per operation
that ran and the ``XLA Modules`` line one per program execution.  The
harness writes its own host spans (``bench.*``) with
``jax.profiler.TraceAnnotation``, so they sit on the same clock as the
device events.  All times here are nanoseconds of that clock.
"""

from __future__ import annotations

from dataclasses import dataclass, field

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW = "bench.window"

Interval = tuple[int, int]


@dataclass
class Device:
    ops: list[tuple[str, int, int]] = field(default_factory=list)
    modules: list[tuple[str, int, int]] = field(default_factory=list)


@dataclass
class Reduced:
    devices: dict[str, Device]             # plane name -> its events
    spans: list[tuple[str, int, int]]      # the harness's host spans
    planes: dict[str, list[str]] = field(default_factory=dict)
    # every plane's name -> the names of its lines, for error messages

    def span(self, name: str) -> tuple[int, int] | None:
        """The first host span called ``name``."""
        return next(((s, e) for n, s, e in self.spans if n == name), None)


def reduce(data) -> Reduced:
    """``jax.profiler.ProfileData`` -> device op / module events and the
    harness's host spans, each as (name, start_ns, end_ns)."""
    devices: dict[str, Device] = {}
    spans = []
    planes = {}
    for plane in data.planes:
        planes[plane.name] = sorted({line.name for line in plane.lines})
        if plane.name.startswith(DEVICE_PREFIX):
            dev = devices.setdefault(plane.name, Device())
            for line in plane.lines:
                dest = {OPS_LINE: dev.ops, MODULES_LINE: dev.modules}.get(
                    line.name)
                if dest is not None:
                    dest.extend((ev.name, int(ev.start_ns), int(ev.end_ns))
                                for ev in line.events)
        else:
            for line in plane.lines:
                spans.extend((ev.name, int(ev.start_ns), int(ev.end_ns))
                             for ev in line.events
                             if ev.name.startswith(SPAN_PREFIX))
    spans.sort(key=lambda s: s[1])
    return Reduced(devices, spans, planes)


def merge(intervals) -> list[Interval]:
    """Union of (start, end) intervals, sorted and disjoint."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(merged: list[Interval], lo: int, hi: int) -> int:
    """Nanoseconds of ``[lo, hi)`` that the merged intervals cover."""
    return sum(max(0, min(e, hi) - max(s, lo)) for s, e in merged)


def busy(dev: Device) -> list[Interval]:
    """The intervals in which an operation ran on the device."""
    return merge((s, e) for _, s, e in dev.ops)


def gaps(merged: list[Interval], lo: int, hi: int) -> list[Interval]:
    """The idle intervals of ``[lo, hi)`` between the merged ones."""
    out, t = [], lo
    for s, e in merged:
        if e <= lo or s >= hi:
            continue
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def activity(spans: list[tuple[str, int, int]], lo: int, hi: int) -> str:
    """The name of the harness span inside the window that covers most of
    ``[lo, hi)``; ``bench.window`` when none does."""
    best, best_ns = WINDOW, 0
    for name, s, e in spans:
        ov = max(0, min(e, hi) - max(s, lo))
        if name != WINDOW and ov > best_ns:
            best, best_ns = name, ov
    return best


def breakdown(red: Reduced, lo: int, hi: int, top: int = 10) -> dict:
    """The device operations that took most time in ``[lo, hi)`` (summed
    over devices) and the longest idle gaps, each named by what the host
    was doing then.  Seconds, unrounded."""
    per_op: dict[str, int] = {}
    idle = []
    for dev in red.devices.values():
        for name, s, e in dev.ops:
            ov = max(0, min(e, hi) - max(s, lo))
            if ov:
                per_op[name] = per_op.get(name, 0) + ov
        for s, e in gaps(busy(dev), lo, hi):
            idle.append((e - s, activity(red.spans, s, e)))
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    idle.sort(key=lambda g: -g[0])
    return {"device_ops": [[n, ns / 1e9] for n, ns in ops],
            "idle_gaps": [[n, ns / 1e9] for ns, n in idle[:top]]}


def step_ns(run) -> int:
    """Device nanoseconds, inside the traced window and summed over the
    devices used, of executions of the fleet step's programs (modules whose
    name, before any ``(id)`` suffix, is one of ``run.step_modules``)."""
    if run.trace is None:
        return 0
    lo, hi = run.window_ns
    return sum(max(0, min(e, hi) - max(s, lo))
               for d in run.devices
               for name, s, e in run.trace.devices[d].modules
               if name.split("(")[0] in run.step_modules)


def require(red: Reduced, devices: list[str], step_modules) -> None:
    """Raise unless every device used has a plane with operations on it and
    the step's programs appear among its module events: a declared device
    metric must never go missing in silence."""
    for d in devices:
        dev = red.devices.get(d)
        if dev is None:
            raise RuntimeError(f"the trace has no plane {d!r}; its planes "
                               f"and lines: {red.planes}")
        if not dev.ops:
            raise RuntimeError(f"no {OPS_LINE!r} events on {d!r}; its "
                               f"lines: {red.planes.get(d)}")
        names = {n.split("(")[0] for n, _, _ in dev.modules}
        if step_modules and not names & set(step_modules):
            raise RuntimeError(f"no {MODULES_LINE!r} event on {d!r} matches "
                               f"the step's modules {list(step_modules)}; "
                               f"modules seen: {sorted(names)[:20]}")


def summary(red: Reduced, devices: list[str]) -> dict:
    """What the trace held, for the run's notes: each used device's lines
    and its most frequent module names."""
    out = {}
    for d in devices:
        counts: dict[str, int] = {}
        for n, _, _ in red.devices[d].modules:
            counts[n] = counts.get(n, 0) + 1
        out[f"trace_lines {d}"] = red.planes.get(d, [])
        out[f"trace_modules {d}"] = sorted(counts.items(),
                                           key=lambda kv: -kv[1])[:8]
    return out
