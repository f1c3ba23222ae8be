"""From a profiler trace to device busy time, op time and idle gaps.

``load`` reads the ``.xplane.pb`` file that ``jax.profiler`` writes, with
nothing but JAX.  What it keeps:

  * per device plane (``/device:TPU:<n>``), the events of its ``XLA Ops``
    line — one per operation the device ran — and of its ``XLA Modules``
    line — one per program run;
  * the benchmark's own host spans: ``jax.profiler.TraceAnnotation`` events
    whose names start with ``cb:``, among them ``cb:window`` around the
    measured window.

Everything after that is arithmetic on ``(start_ns, end_ns)`` intervals
on the trace's one clock, kept here so that every PR reduces a trace the
same way.
"""

from __future__ import annotations

import dataclasses
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "cb:"
WINDOW_SPAN = "cb:window"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str   # on a TPU's XLA Ops line, the op's HLO text
    start: int  # ns on the trace clock
    end: int

    @property
    def dur(self) -> int:
        return self.end - self.start


@dataclasses.dataclass
class Trace:
    ops: dict[str, list[Event]]       # device plane -> op events
    modules: dict[str, list[Event]]   # device plane -> program events
    spans: list[Event]                # the benchmark's host spans

    @property
    def window(self) -> tuple[int, int]:
        w = [s for s in self.spans if s.name == WINDOW_SPAN]
        if len(w) != 1:
            raise ValueError(f"expected one {WINDOW_SPAN} span, found {len(w)}")
        return w[0].start, w[0].end

    @property
    def devices(self) -> list[str]:
        """Device planes on which some operation ran."""
        return sorted(p for p, evs in self.ops.items() if evs)


def _event(ev) -> Event:
    start = int(ev.start_ns)
    return Event(ev.name, start, start + int(ev.duration_ns))


def load(path) -> Trace:
    """Read one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    ops, modules, spans = {}, {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            ops[plane.name], modules[plane.name] = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[plane.name] += [_event(e) for e in line.events]
                elif line.name == MODULES_LINE:
                    modules[plane.name] += [_event(e) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [_event(e) for e in line.events if e.name.startswith(SPAN_PREFIX)]
    return Trace(ops=ops, modules=modules, spans=spans)


def union(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    """The union of ``(start, end)`` intervals clipped to ``[lo, hi)``,
    as disjoint ascending intervals."""
    out: list[list[int]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(events, lo: int, hi: int) -> int:
    """Nanoseconds of ``[lo, hi)`` in which at least one event runs."""
    return sum(e - s for s, e in union(((x.start, x.end) for x in events), lo, hi))


def gaps(events, lo: int, hi: int) -> list[tuple[int, int]]:
    """The idle intervals of ``[lo, hi)`` between the events."""
    out, t = [], lo
    for s, e in union(((x.start, x.end) for x in events), lo, hi):
        if s > t:
            out.append((t, s))
        t = e
    if hi > t:
        out.append((t, hi))
    return out


def matching_ns(events, pattern: str, lo: int, hi: int) -> int:
    """Summed durations of the events that start in ``[lo, hi)`` and whose
    name matches ``pattern`` (a regular expression)."""
    rx = re.compile(pattern)
    return sum(x.dur for x in events if lo <= x.start < hi and rx.search(x.name))


def busy_share(trace: Trace) -> tuple[float, float]:
    """``(busy_s, window_s)``: the busy time averaged over the devices that
    ran anything, and the window's length."""
    lo, hi = trace.window
    devs = trace.devices
    if not devs:
        return 0.0, (hi - lo) / 1e9
    busy = sum(busy_ns(trace.ops[d], lo, hi) for d in devs) / len(devs)
    return busy / 1e9, (hi - lo) / 1e9


def idle_pct(trace: Trace) -> float | None:
    """Share of the window in which no operation ran on the device, in %:
    ``100 * (1 - busy / window)``; ``None`` with no device in the trace."""
    if not trace.devices:
        return None
    busy, window = busy_share(trace)
    return 100.0 * (1.0 - busy / window) if window > 0 else None


def outermost(events) -> list[Event]:
    """The events that no other event of the list encloses (a ``while`` op
    encloses the ops of its body on the same line)."""
    out: list[Event] = []
    for x in sorted(events, key=lambda x: (x.start, -x.end)):
        if out and x.end <= out[-1].end:
            continue
        out.append(x)
    return out


def short_name(name: str, width: int = 96) -> str:
    """An op's HLO text cut to its name and the start of its shape."""
    return name if len(name) <= width else name[: width - 3] + "..."


def breakdown(trace: Trace, top: int = 10) -> dict:
    """The outermost device operations that took most time in the window,
    summed by name, and the longest idle gaps of the first device, each
    named by the benchmark span that overlaps it most."""
    lo, hi = trace.window
    devs = trace.devices
    if not devs:
        return {"device_ops": [], "idle_gaps": []}
    by_name: dict[str, int] = {}
    for x in outermost(trace.ops[devs[0]]):
        if lo <= x.start < hi:
            name = short_name(x.name)
            by_name[name] = by_name.get(name, 0) + x.dur
    ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    inner = [s for s in trace.spans if s.name != WINDOW_SPAN]
    named = []
    for s, e in gaps(trace.ops[devs[0]], lo, hi):
        best, cover = "host:outside-spans", 0
        for sp in inner:
            ov = min(e, sp.end) - max(s, sp.start)
            if ov > cover:
                best, cover = sp.name[len(SPAN_PREFIX):], ov
        named.append((best, (e - s) / 1e9))
    named.sort(key=lambda kv: -kv[1])
    return {
        "device_ops": [[n, ns / 1e9] for n, ns in ops],
        "idle_gaps": [[n, s] for n, s in named[:top]],
    }
