"""Reduction of a profiler trace to the benchmark's device numbers.

``load`` reads the ``.xplane.pb`` that ``jax.profiler.trace`` wrote into a
`Trace`: per chip, the device operations (the ``XLA Ops`` line of each TPU
plane) as (name, start, end, detail) in nanoseconds, and the host spans the
benchmark annotated (``window``, ``round``, ``fetch``, ``block``). Host and
device events share the profiler's clock.

The rest are pure functions on those lists, tested on synthesized traces:
the union of busy intervals inside the window, the idle share, the summed
time of the events a name matches, and the idle gaps labelled by the
innermost host span that covers each gap's midpoint.
"""

from __future__ import annotations

import bisect
import glob
import os
from typing import Dict, Iterable, List, NamedTuple, Sequence, Tuple

HOST_SPANS = ("window", "round", "fetch", "block")
OPS_LINE = "XLA Ops"

Interval = Tuple[float, float]


class Event(NamedTuple):
    name: str         # the HLO instruction's name, e.g. ``fusion.3``
    start: float      # ns
    end: float        # ns
    detail: str = ""  # the rest of the event's name and its string stats


class Trace(NamedTuple):
    devices: Dict[str, List[Event]]   # plane name -> its op events
    host: List[Event]                 # the benchmark's annotated spans

    def window(self) -> Interval:
        spans = [e for e in self.host if e.name == "window"]
        if len(spans) != 1:
            raise ValueError(f"expected one 'window' span, found {len(spans)}")
        return spans[0].start, spans[0].end


def _device_event(ev) -> Event:
    """A TPU op event, named by its HLO instruction: the trace names it by
    the instruction's text (``%fusion.3 = f32[...] fusion(...)``), of which
    the rest and the string stats, each cut to 200 characters, go to
    ``detail``."""
    name, _, rest = ev.name.partition(" = ")
    stats = " ".join(f"{k}={v[:200]}" for k, v in ev.stats
                     if isinstance(v, str))
    return Event(name.lstrip("%"), ev.start_ns, ev.end_ns,
                 f"{rest[:200]} {stats}")


def load(trace_dir: str, chips: int) -> Trace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    devices: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and \
                plane.name[len("/device:TPU:"):].isdigit():
            if int(plane.name[len("/device:TPU:"):]) >= chips:
                continue
            evs = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                evs.extend(_device_event(e) for e in line.events)
            devices[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(Event(e.name, e.start_ns, e.end_ns)
                            for e in line.events if e.name in HOST_SPANS)
    return Trace(devices, host)


# ---------------------------------------------------------------------------
# pure reductions
# ---------------------------------------------------------------------------

def clip(events: Iterable[Event], lo: float, hi: float) -> List[Interval]:
    out = []
    for e in events:
        s, t = max(e.start, lo), min(e.end, hi)
        if t > s:
            out.append((s, t))
    return out


def union(intervals: Iterable[Interval]) -> List[Interval]:
    merged: List[List[float]] = []
    for s, t in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], t)
        else:
            merged.append([s, t])
    return [(s, t) for s, t in merged]


def busy_ns(events: Sequence[Event], window: Interval) -> float:
    return sum(t - s for s, t in union(clip(events, *window)))


def idle_share(events: Sequence[Event], window: Interval) -> float:
    w = window[1] - window[0]
    return 1.0 - busy_ns(events, window) / w


def matching(events: Iterable[Event], needles: Sequence[str]) -> List[Event]:
    """The events whose name starts with one of ``needles``."""
    return [e for e in events if e.name.startswith(tuple(needles))]


def summed_ns(events: Iterable[Event], window: Interval) -> float:
    """Summed device time of ``events`` inside the window (not a union:
    each event's own duration, clipped to the window)."""
    return sum(t - s for s, t in clip(events, *window))


def gaps(events: Sequence[Event], window: Interval) -> List[Interval]:
    busy = union(clip(events, *window))
    out, cur = [], window[0]
    for s, t in busy:
        if s > cur:
            out.append((cur, s))
        cur = max(cur, t)
    if window[1] > cur:
        out.append((cur, window[1]))
    return out


def label_gaps(gap_list: Sequence[Interval], host: Sequence[Event]
               ) -> Dict[str, float]:
    """Seconds of idle device time per host span: each gap goes to the
    innermost (shortest) benchmark span, other than ``window``, that covers
    its midpoint, else to ``none``. Spans of one name never overlap (one
    host thread), so each name is searched by bisection."""
    by_name: Dict[str, List[Event]] = {}
    for e in host:
        if e.name != "window":
            by_name.setdefault(e.name, []).append(e)
    starts = {}
    for name, evs in by_name.items():
        evs.sort(key=lambda e: e.start)
        starts[name] = [e.start for e in evs]
    out: Dict[str, float] = {}
    for s, t in gap_list:
        mid = 0.5 * (s + t)
        best = None
        for name, evs in by_name.items():
            i = bisect.bisect_right(starts[name], mid) - 1
            if i >= 0 and evs[i].end >= mid and \
                    (best is None or evs[i].end - evs[i].start
                     < best.end - best.start):
                best = evs[i]
        label = "none" if best is None else best.name
        out[label] = out.get(label, 0.0) + (t - s) * 1e-9
    return out


def self_times(events: Sequence[Event], window: Interval
               ) -> Dict[str, float]:
    """Seconds per op name inside the window, each event counted without
    the events nested in it (a ``while`` op holds its loop body's ops)."""
    evs = sorted(((max(e.start, window[0]), min(e.end, window[1]), e.name)
                  for e in events), key=lambda x: (x[0], -x[1]))
    tot: Dict[str, float] = {}
    stack: List[list] = []   # [end, name, nested time, start] per open event

    def close(top):
        s_end, name, child, start = top
        tot[name] = tot.get(name, 0.0) + (s_end - start - child) * 1e-9

    for s, t, name in evs:
        if t <= s:
            continue
        while stack and stack[-1][0] <= s:
            close(stack.pop())
        if stack:
            stack[-1][2] += t - s
        stack.append([t, name, 0.0, s])
    while stack:
        close(stack.pop())
    return tot


def top_ops(events: Sequence[Event], window: Interval, n: int = 10
            ) -> List[List]:
    """The ``n`` device operations (by name) with most self time."""
    tot = self_times(events, window)
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]
