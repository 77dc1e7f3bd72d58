"""The program's own instrumentation in a profiler trace.

The program emits two kinds of marks that ``tracing.load`` leaves out:

  * host spans (``repro.obs.span``), which enter a profiler annotation of
    the span's bare name while a profiler session collects. The readers use
    ``PROGRAM_SPANS``: ``trainer.round`` around one ``FederatedTrainer.round``
    call and ``executor.dispatch`` around the call of the jitted step inside
    it; the rest of ``trainer.round`` is round assembly;
  * ``jax.named_scope``s in the step, one per layer of a server update
    (``SCOPES``), which XLA keeps in each instruction's ``op_name``: its name
    stack. An op belongs to the innermost ``fl_`` scope of its stack: a
    backward-pass op of the downlink codec, run inside the uplink codec's
    custom VJP, reads ``.../transpose(jvp(fl_uplink_codec))/
    vmap(fl_downlink_codec)/...`` and counts as downlink. An op whose own
    stack is empty (XLA leaves some fusions without one) takes the scope of
    the first instruction with one in the computations it calls. An op
    under no scope is left unscoped.

A TPU trace's op events carry no name stack: only the instruction's name,
inside an ``XLA Modules`` event that names the compiled module. The
profiler files each module's optimized HLO (an ``HloProto``) on the
``/host:metadata`` plane under that name, and ``load`` reads the scopes
from there, keyed by module and instruction. ``ProfileData`` does not give
those payloads, so they are read from the protobuf wire format with the
field numbers of tsl's ``xplane.proto`` and XLA's ``hlo.proto``. ``of``
finds the traced run's file and keeps what it read on the readers'
context, so the readers of one run share one read. A trace of a program
without these marks yields no spans and unscoped ops: its readers then
return None.

The rest are pure functions on those lists, tested on synthesized traces.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from pathlib import Path
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

from bench import tracing
from bench.tracing import Event, Interval

PROGRAM_SPANS = ("trainer.round", "executor.dispatch")
SCOPES = ("fl_client", "fl_uplink_codec", "fl_downlink_codec", "fl_server",
          "fl_optimizer")
MODULES_LINE = "XLA Modules"
METADATA_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"
_SCOPE_RE = re.compile(r"\b(" + "|".join(SCOPES) + r")\b")


class Op(NamedTuple):
    name: str         # the HLO instruction's name, e.g. ``fusion.3``
    start: float      # ns
    end: float        # ns
    scope: str = ""   # the innermost ``fl_`` scope of its name stack


class ProgramTrace(NamedTuple):
    devices: Dict[str, List[Op]]      # plane name -> its op events
    program: List[Event]              # the program's spans (PROGRAM_SPANS)
    windows: List[Interval]           # the benchmark's ``window`` spans


def innermost_scope(name_stack: str) -> str:
    """The last (innermost) of ``SCOPES`` in a name stack, else ``""``.
    Where XLA merged the stacks of several ops (joined by ``;``), the last
    scope found counts."""
    found = _SCOPE_RE.findall(name_stack)
    return found[-1] if found else ""


def _tpu_index(plane_name: str) -> Optional[int]:
    prefix = "/device:TPU:"
    if plane_name.startswith(prefix) and plane_name[len(prefix):].isdigit():
        return int(plane_name[len(prefix):])
    return None


def load(path: str, chips: int) -> ProgramTrace:
    """Read one ``.xplane.pb``: the ops of the first ``chips`` TPU planes
    with their scopes, the program's spans and the benchmark's windows."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    scopes = module_scopes(path)
    devices: Dict[str, List[Op]] = {}
    program: List[Event] = []
    windows: List[Interval] = []
    for plane in pd.planes:
        idx = _tpu_index(plane.name)
        if idx is not None:
            if idx >= chips:
                continue
            lines = {line.name: [Event(e.name, e.start_ns, e.end_ns)
                                 for e in line.events]
                     for line in plane.lines
                     if line.name in (tracing.OPS_LINE, MODULES_LINE)}
            devices[plane.name] = attribute(
                lines.get(tracing.OPS_LINE, []),
                lines.get(MODULES_LINE, []), scopes)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in PROGRAM_SPANS:
                        program.append(Event(ev.name, ev.start_ns,
                                             ev.end_ns))
                    elif ev.name == "window":
                        windows.append((ev.start_ns, ev.end_ns))
    return ProgramTrace(devices, program, windows)


def attribute(ops: Sequence[Event], modules: Sequence[Event],
              scopes: Dict[str, Dict[str, str]]) -> List[Op]:
    """The op events as `Op`s: each named by its instruction (the event
    holds the instruction's text, ``%fusion.3 = ...``), with the scope that
    the HLO of the module it ran in, the ``XLA Modules`` event around its
    start, gives that instruction."""
    mods = sorted((m.start, m.end, m.name) for m in modules)
    starts = [m[0] for m in mods]
    out = []
    for ev in ops:
        name = ev.name.partition(" = ")[0].lstrip("%")
        i = bisect.bisect_right(starts, ev.start) - 1
        module = mods[i][2] if i >= 0 and ev.start < mods[i][1] else ""
        out.append(Op(name, ev.start, ev.end,
                      scopes.get(module, {}).get(name, "")))
    return out


# ---------------------------------------------------------------------------
# the modules' HLO on the metadata plane, read from the protobuf wire format
# ---------------------------------------------------------------------------

def _varint(buf, i: int) -> Tuple[int, int]:
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if b < 0x80:
            return x, i
        shift += 7


def _fields(buf, span: Interval):
    """(field number, value) of the message serialized in ``buf[span]``:
    an int for a varint, a (start, end) span for a length-delimited field,
    None for a fixed-width one."""
    i, end = span
    while i < end:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif kind in (1, 5):
            value, i = None, i + (8 if kind == 1 else 4)
        else:
            raise ValueError(f"wire type {kind} at byte {i}")
        yield key >> 3, value


def _text(buf, span: Interval) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _ints(buf, value) -> List[int]:
    """A repeated integer field's entry: one varint, or a packed run."""
    if isinstance(value, int):
        return [value]
    out, i = [], value[0]
    while i < value[1]:
        x, i = _varint(buf, i)
        out.append(x)
    return out


def _hlo_scopes(buf, span: Interval) -> Dict[str, str]:
    """{instruction name: scope} of one ``HloProto``: its module (field 1)
    holds computations (3: name 1, instructions 2, id 5); an instruction
    has a name (1), metadata (7, whose ``op_name`` is field 2) and the ids
    of the computations it calls (38)."""
    comps: Dict[int, List[Tuple[str, str, List[int]]]] = {}
    for n, module in _fields(buf, span):
        if n != 1:
            continue
        for m, comp in _fields(buf, module):
            if m != 3:
                continue
            cid, instrs = 0, []   # an id left out is 0
            for c, v in _fields(buf, comp):
                if c == 5:
                    cid = v
                elif c == 2:
                    name, stack, calls = "", "", []
                    for f, w in _fields(buf, v):
                        if f == 1:
                            name = _text(buf, w)
                        elif f == 7:
                            stack = next((_text(buf, x) for g, x in
                                          _fields(buf, w) if g == 2), "")
                        elif f == 38:
                            calls += _ints(buf, w)
                    instrs.append((name, innermost_scope(stack), calls))
            comps[cid] = instrs

    def called(ids, seen) -> str:
        for cid in ids:
            if cid in seen:
                continue
            seen.add(cid)
            for _, scope, calls in comps.get(cid, ()):
                scope = scope or called(calls, seen)
                if scope:
                    return scope
        return ""

    return {name: scope or called(calls, set())
            for instrs in comps.values() for name, scope, calls in instrs}


def module_scopes(path: str) -> Dict[str, Dict[str, str]]:
    """{module name, as its ``XLA Modules`` events give it: {instruction
    name: scope}} from the HLO the trace holds on its metadata plane.

    An XSpace holds planes (field 1); a plane has a name (2), event
    metadata (4) and stat metadata (5), both maps whose entries hold the
    value in field 2. An event metadata has a name (2) and stats (5); a
    stat metadata an id (1) and a name (2). A module's HLO is the bytes (6)
    of its stat whose metadata id (1) names ``Hlo Proto``."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    for n, plane in _fields(buf, (0, len(buf))):
        if n != 1:
            continue
        name = next((_text(buf, v) for k, v in _fields(buf, plane)
                     if k == 2), "")
        if name != METADATA_PLANE:
            continue
        stat_names, modules = {}, []
        for k, v in _fields(buf, plane):
            if k not in (4, 5):
                continue
            entry = dict(_fields(buf, v)).get(2)
            if entry is None:
                continue
            fields = list(_fields(buf, entry))
            if k == 5:
                d = dict(fields)
                stat_names[d.get(1, 0)] = _text(buf, d[2]) if 2 in d else ""
            else:
                label = next((_text(buf, x) for g, x in fields if g == 2),
                             "")
                stats = [dict(_fields(buf, x)) for g, x in fields if g == 5]
                modules.append((label, stats))
        out = {}
        for label, stats in modules:
            for st in stats:
                if stat_names.get(st.get(1, 0)) == HLO_STAT and 6 in st:
                    out[label] = _hlo_scopes(buf, st[6])
        return out
    return {}


def of(ctx, reader_file: str) -> Optional[ProgramTrace]:
    """The traced run's `ProgramTrace`, read once per run and kept on the
    readers' context ``ctx``. The harness writes the trace under
    ``<bench>/out/trace/`` beside the readers' ``<bench>/metrics/``; the
    file is the one whose ``window`` span is the run's window. None where
    no such file is found."""
    if not hasattr(ctx, "program_trace"):
        ctx.program_trace = None
        root = Path(reader_file).resolve().parents[1] / "out" / "trace"
        paths = sorted(glob.glob(str(root / "**" / "*.xplane.pb"),
                                 recursive=True),
                       key=os.path.getmtime, reverse=True)
        for path in paths:
            pt = load(path, ctx.chips)
            if tuple(ctx.window) in pt.windows:
                ctx.program_trace = pt
                break
    return ctx.program_trace


# ---------------------------------------------------------------------------
# pure reductions
# ---------------------------------------------------------------------------

def scope_self_ns(ops: Sequence[Op], window: Interval) -> Dict[str, float]:
    """Nanoseconds of self time per scope inside the window (``""`` for the
    unscoped remainder): each op counted without the ops nested in it, so a
    ``while`` and its body are not counted twice, and the scopes with the
    remainder add up to the busy time."""
    secs = tracing.self_times([Event(o.scope, o.start, o.end) for o in ops],
                              window)
    return {k: v * 1e9 for k, v in secs.items()}


def intersect(a: Sequence[Interval], b: Sequence[Interval]
              ) -> List[Interval]:
    """The intersection of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, t = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if t > s:
            out.append((s, t))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def length(intervals: Sequence[Interval]) -> float:
    return sum(t - s for s, t in intervals)


def spans(program: Sequence[Event], name: str, window: Interval
          ) -> List[Interval]:
    """The union of the spans named ``name``, clipped to the window."""
    return tracing.union(tracing.clip(
        (e for e in program if e.name == name), *window))


def assembly_dispatch_ns(program: Sequence[Event], window: Interval
                         ) -> Tuple[float, float]:
    """Host time in the window inside ``trainer.round`` but outside
    ``executor.dispatch`` (round assembly), and inside ``executor.dispatch``
    (the step's dispatch)."""
    rounds = spans(program, "trainer.round", window)
    dispatch = spans(program, "executor.dispatch", window)
    return (length(rounds) - length(intersect(rounds, dispatch)),
            length(dispatch))


def idle_split_ns(events: Sequence, program: Sequence[Event],
                  window: Interval) -> Tuple[float, float]:
    """Idle device time (gaps between the device's ops) in the window that
    falls inside ``trainer.round`` but outside ``executor.dispatch``, and
    inside ``executor.dispatch``. The two are disjoint parts of the idle
    time, so together they never exceed it."""
    idle = tracing.gaps(events, window)
    dispatch = spans(program, "executor.dispatch", window)
    in_round = intersect(idle, spans(program, "trainer.round", window))
    return (length(in_round) - length(intersect(in_round, dispatch)),
            length(intersect(idle, dispatch)))


# ---------------------------------------------------------------------------
# what the metric readers share
# ---------------------------------------------------------------------------

def scope_ms(ctx, reader_file: str, scope: str) -> Optional[float]:
    """Device self time of ``scope``'s ops per update, in ms, summed over
    the chips; None where the trace holds none."""
    pt = of(ctx, reader_file)
    if pt is None or ctx.updates <= 0:
        return None
    total = sum(scope_self_ns(ops, ctx.window).get(scope, 0.0)
                for ops in pt.devices.values())
    return 1e-6 * total / ctx.updates if total > 0 else None


def host_ms(ctx, reader_file: str, part: int) -> Optional[float]:
    """Round assembly (``part`` 0) or the step's dispatch (1) per update,
    in ms of host time; None where the trace holds no such span."""
    pt = of(ctx, reader_file)
    if pt is None or ctx.updates <= 0:
        return None
    ns = assembly_dispatch_ns(pt.program, ctx.window)[part]
    return 1e-6 * ns / ctx.updates if ns > 0 else None


def idle_share(ctx, reader_file: str, part: int) -> Optional[float]:
    """The share of the window, in %, in which the device is idle inside
    round assembly (``part`` 0) or inside the step's dispatch (1), averaged
    over the chips; None where the trace holds no ``PROGRAM_SPANS``."""
    pt = of(ctx, reader_file)
    if pt is None or not ctx.trace.devices or \
            {e.name for e in pt.program} != set(PROGRAM_SPANS):
        return None
    w = ctx.window[1] - ctx.window[0]
    shares = [idle_split_ns(evs, pt.program, ctx.window)[part] / w
              for evs in ctx.trace.devices.values()]
    return 100.0 * sum(shares) / len(shares)
