"""What the program itself says in a ``jax.profiler`` capture: the scope of
every device op and the program's own host spans, for the per-layer
metrics that read them (``layer_metrics/train/forward_share.py`` and its
eleven neighbours).

``trace_reduce.py`` reads a capture through ``jax.profiler.ProfileData``,
which shows an op event's own stats only (its offset and duration). The
*metadata* of the same event, in the same file, holds what the compiler
knew of the HLO instruction: ``tf_op`` (the JAX name stack, so every
``jax.named_scope`` and every ``pallas_call(name=...)`` round it),
``hlo_category``, ``program_id``. ``ProfileData`` does not expose it, so
:func:`read_xplane` reads the protobuf wire format directly, with the
standard library alone. Field numbers (``tsl/profiler/protobuf/
xplane.proto``; the ones that worked on a v5e capture of jax 0.9.0):

    XSpace          planes 1
    XPlane          name 2, lines 3, event_metadata 4, stat_metadata 5
                    (maps: key 1, value 2)
    XLine           id 1, name 2, timestamp_ns 3, events 4
    XEvent          metadata_id 1, offset_ps 2, duration_ps 3, stats 4
    XEventMetadata  id 1, name 2, stats 5
    XStat           metadata_id 1, double 2, uint64 3, int64 4, str 5,
                    ref 7 (a ref points into stat_metadata)
    XStatMetadata   id 1, name 2

What is read:

- per ``/device:TPU:<n>`` plane, the events of the line ``XLA Ops`` with
  start, end and their metadata's scope, category, program and kernel
  name; containers (``while``, ``conditional``, ``call``) are dropped as
  ``trace_reduce`` drops them. A scope reads
  ``jit(train_step)/ds.optimizer/mul`` outside differentiation and
  ``jvp(ds.kv_gather)`` or ``transpose(jvp(ds.kv_gather))`` inside it:
  :func:`in_scope` matches the name within the wrappers. A Pallas
  kernel's name is the path component before ``pallas_call``;
- the ``ds.*`` events of the ``/host:`` planes (the program's spans,
  ``deepspeed_tpu/telemetry/tracer.py``) with their stats and, by nesting
  on one line, their parent;
- self time (a span's duration less what its children cover) and the idle
  seconds of the window by the innermost ``ds.*`` span open at the time,
  with ``trace_reduce``'s interval helpers and its ``bench.window``.

Times are seconds on the clock ``trace_reduce.load_xplane`` uses (a line's
``timestamp_ns`` plus the event's offset), so the two can be mixed: every
share here is taken of the busy time ``trace_reduce.reduce`` computed.
The parse is memoised per path: twelve readers, one parse.

By hand, what the program says in a capture, or a cut of it as a fixture
(``from ms`` counts from the start of ``bench.window``; the cut becomes the
fixture's window):

    python3 benchmarks/program_trace.py <profile dir or .xplane.pb[.gz]>
    python3 benchmarks/program_trace.py <...> <from ms> <length ms> <out.json.gz> [note]
"""

import functools
import glob
import gzip
import json
import os
import re
import struct
import sys
from dataclasses import dataclass, field
from typing import Dict, Iterable, Iterator, List, Optional, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import trace_reduce as tr  # noqa: E402

SPAN_PREFIX = "ds."
# the spans round a decode dispatch, whichever program serves the step
DECODE_SPANS = ("decode_step", "mixed_step", "spec_step")
KERNEL_RE = re.compile(r"(?:^|/)([^/]+)/pallas_call\b")
LAYER_RE = re.compile(r"^h_\d+$")
MODULE_RE = re.compile(r"^(?:c_attn|c_proj|c_fc|mlp_proj|moe|ln_\w+|wte|wpe"
                       r"|lm_head|mlm_\w+|pooler|nsp_head)$")
WRAPPER_RE = re.compile(r"^[A-Za-z_]+\((.*)\)$")


# ---------------------------------------------------------------------------
# The protobuf wire format, as far as an xplane needs it
# ---------------------------------------------------------------------------

def _varint(buf, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, int, object]]:
    """``(field number, wire type, value)`` of every field of a message:
    an int for a varint, a ``memoryview`` for the rest."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}: not an xplane")
        yield number, wire, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _stat(buf, stat_names: Dict[int, str]) -> Tuple[str, object]:
    name, value = "", None
    for number, _, v in _fields(buf):
        if number == 1:
            name = stat_names.get(v, str(v))
        elif number == 2:
            value = struct.unpack("<d", v)[0]
        elif number == 3:
            value = v
        elif number == 4:
            value = v - (1 << 64) if v >> 63 else v
        elif number == 5:
            value = _text(v)
        elif number == 7:
            value = stat_names.get(v, "")
    return name, value


def _map_entry(buf) -> Tuple[int, object]:
    key, value = 0, b""
    for number, _, v in _fields(buf):
        if number == 1:
            key = v
        elif number == 2:
            value = v
    return key, value


@dataclass
class RawEvent:
    name: str                   # the event metadata's name
    start: float                # seconds
    end: float
    stats: Dict[str, object]    # the event's own stats
    meta: Dict[str, object]     # its metadata's stats


@dataclass
class RawPlane:
    """``lines`` holds ``(line name, line id, events)``: two threads of a
    host may bear one name."""
    name: str
    lines: List[Tuple[str, int, List[RawEvent]]] = field(
        default_factory=list)


def _plane(buf, wanted_line) -> RawPlane:
    name, lines, event_meta, stat_meta = "", [], [], []
    for number, _, v in _fields(buf):
        if number == 2:
            name = _text(v)
        elif number == 3:
            lines.append(v)
        elif number == 4:
            event_meta.append(v)
        elif number == 5:
            stat_meta.append(v)
    plane = RawPlane(name)
    stat_names: Dict[int, str] = {}
    for entry in stat_meta:
        key, value = _map_entry(entry)
        for number, _, v in _fields(value):
            if number == 2:
                stat_names[key] = _text(v)
    metadata: Dict[int, Tuple[str, Dict[str, object]]] = {}
    for entry in event_meta:
        key, value = _map_entry(entry)
        ev_name, stats = "", {}
        for number, _, v in _fields(value):
            if number == 2:
                ev_name = _text(v)
            elif number == 5:
                stat, val = _stat(v, stat_names)
                stats[stat] = val
        metadata[key] = (ev_name, stats)
    for line in lines:
        line_name, line_id, t0_ns, events = "", 0, 0, []
        for number, _, v in _fields(line):
            if number == 1:
                line_id = v
            elif number == 2:
                line_name = _text(v)
            elif number == 3:
                t0_ns = v
            elif number == 4:
                events.append(v)
        if not wanted_line(name, line_name):
            continue
        out: List[RawEvent] = []
        plane.lines.append((line_name, line_id, out))
        for ev in events:
            meta_id = offset_ps = duration_ps = 0
            stats = {}
            for number, _, v in _fields(ev):
                if number == 1:
                    meta_id = v
                elif number == 2:
                    offset_ps = v
                elif number == 3:
                    duration_ps = v
                elif number == 4:
                    stat, val = _stat(v, stat_names)
                    stats[stat] = val
            ev_name, meta = metadata.get(meta_id, ("", {}))
            start = (t0_ns * 1000 + offset_ps) / 1e12
            out.append(RawEvent(ev_name, start, start + duration_ps / 1e12,
                                stats, meta))
    return plane


def read_xplane(path: str, wanted_line=lambda plane, line: True
                ) -> List[RawPlane]:
    """Every plane of an ``.xplane.pb`` (or ``.xplane.pb.gz``) with the
    events of the lines ``wanted_line(plane name, line name)`` admits."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        data = memoryview(f.read())
    return [_plane(v, wanted_line) for number, _, v in _fields(data)
            if number == 1]


# ---------------------------------------------------------------------------
# The program's part of a capture
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class DeviceOp:
    """One operation of a device's ``XLA Ops`` line. ``scope`` is the
    compiler's ``tf_op``: the JAX name stack down to the primitive."""
    name: str
    start: float
    end: float
    scope: str = ""
    category: str = ""
    program_id: int = 0
    kernel: str = ""            # a Pallas kernel's name, else ""


@dataclass
class Span:
    """One ``ds.*`` host span. ``parent`` and ``children`` index
    ``ProgramTrace.spans``."""
    name: str                   # without the ``ds.`` prefix
    start: float
    end: float
    stats: Dict[str, object]
    line: Tuple[str, str]
    parent: Optional[int] = None
    children: List[int] = field(default_factory=list)

    @property
    def duration(self) -> float:
        return self.end - self.start


@dataclass
class ProgramTrace:
    devices: Dict[int, List[DeviceOp]] = field(default_factory=dict)
    spans: List[Span] = field(default_factory=list)
    window: Optional[tr.Interval] = None        # ``bench.window``, if any


def kernel_of(scope: str) -> str:
    m = KERNEL_RE.search(scope)
    return m.group(1) if m else ""


@functools.lru_cache(maxsize=None)
def scope_parts(scope: str) -> Tuple[str, ...]:
    """The path components of a ``tf_op`` with the transformation wrappers
    taken off: ``jit(f)/transpose(jvp(ds.kv_gather))/mul`` gives ``f``,
    ``ds.kv_gather``, ``mul``. (Cached: a capture of 200,000 ops holds a
    few hundred distinct scopes.)"""
    parts = []
    for part in scope.split("/"):
        while True:
            m = WRAPPER_RE.match(part)
            if not m:
                break
            part = m.group(1)
        parts.append(part)
    return tuple(parts)


def in_scope(op: DeviceOp, *names: str) -> bool:
    """Whether the op lies under one of the named scopes (``ds.optimizer``),
    inside or outside differentiation."""
    return any(p in names for p in scope_parts(op.scope))


def programs_under(trace: "ProgramTrace", *names: str) -> Dict[int, str]:
    """The programs (``program_id``) that hold an op under one of the named
    scopes, each with the first such scope found. A whole jitted body under
    ``ds.decode`` makes the executable the decode program, and the ops the
    compiler added to it without a scope (layout copies of its parameters
    and results, hoisted converts) are then that program's too."""
    out: Dict[int, str] = {}
    for ops in trace.devices.values():
        for op in ops:
            if op.program_id and op.program_id not in out:
                found = [p for p in scope_parts(op.scope) if p in names]
                if found:
                    out[op.program_id] = found[0]
    return out


def is_backward(op: DeviceOp) -> bool:
    return "transpose(" in op.scope


def is_forward(op: DeviceOp) -> bool:
    return "jvp(" in op.scope and not is_backward(op)


def link_spans(spans: List[Span]) -> None:
    """Parent and children of every span: the parent is the innermost
    span that encloses it on the same line (a thread)."""
    by_line: Dict[Tuple[str, str], List[int]] = {}
    for i, s in enumerate(spans):
        s.parent, s.children = None, []
        by_line.setdefault(s.line, []).append(i)
    for idx in by_line.values():
        idx.sort(key=lambda i: (spans[i].start, -spans[i].end))
        stack: List[int] = []
        for i in idx:
            while stack and spans[stack[-1]].end <= spans[i].start:
                stack.pop()
            if stack:
                spans[i].parent = stack[-1]
                spans[stack[-1]].children.append(i)
            stack.append(i)


def self_seconds(trace: "ProgramTrace", span: Span,
                 less: Optional[Iterable[str]] = None) -> float:
    """A span's duration less what its children cover; with ``less``,
    only the children of those names are taken off."""
    cover = [(trace.spans[c].start, min(trace.spans[c].end, span.end))
             for c in span.children
             if less is None or trace.spans[c].name in less]
    return span.duration - tr.total(tr.merge(cover))


def _wanted(plane: str, line: str) -> bool:
    return (line == tr.OP_LINE if tr.DEVICE_PLANE_RE.match(plane)
            else plane.startswith("/host:"))


@functools.lru_cache(maxsize=4)
def load(path: str) -> ProgramTrace:
    """The program's part of the capture at ``path``, parsed once."""
    trace = ProgramTrace()
    windows = []
    for plane in read_xplane(path, _wanted):
        m = tr.DEVICE_PLANE_RE.match(plane.name)
        if m:
            ops = trace.devices.setdefault(int(m.group(1)), [])
            for ev in (e for _, _, events in plane.lines for e in events):
                name, opcode, _, _ = tr.parse_hlo(ev.name)
                if ev.end <= ev.start or opcode in tr.CONTAINERS:
                    continue
                scope = str(ev.meta.get("tf_op", ""))
                ops.append(DeviceOp(
                    name, ev.start, ev.end, scope,
                    str(ev.meta.get("hlo_category", "")),
                    int(ev.meta.get("program_id", 0) or 0),
                    kernel_of(scope)))
            continue
        for line_name, line_id, events in plane.lines:
            for ev in events:
                if ev.name.startswith(SPAN_PREFIX):
                    trace.spans.append(Span(
                        ev.name[len(SPAN_PREFIX):], ev.start, ev.end,
                        ev.stats, (plane.name, f"{line_name}#{line_id}")))
                elif ev.name == tr.WINDOW:
                    windows.append((ev.start, ev.end))
    if windows:
        trace.window = (min(s for s, _ in windows),
                        max(e for _, e in windows))
    link_spans(trace.spans)
    return trace


def of_run(run) -> Optional[ProgramTrace]:
    """The program's part of the run's capture, or ``None`` if the run
    left none."""
    path = run.xplane()
    return load(path) if path else None


# ---------------------------------------------------------------------------
# Reductions the readers share
# ---------------------------------------------------------------------------

def inside(ops: Iterable[DeviceOp], window: tr.Interval
           ) -> Iterator[Tuple[DeviceOp, float]]:
    """Every op that touches the window, with its seconds inside it."""
    for op in ops:
        for s, e in tr.clip([(op.start, op.end)], window):
            yield op, e - s


def share_of_busy(trace: Optional[ProgramTrace], reduced, pred
                  ) -> Optional[float]:
    """Device seconds of the ops ``pred`` admits, inside the reduced
    window, over the busy time ``trace_reduce.reduce`` computed, in
    percent. ``None`` without a device plane or without any such op: a
    program that does not name the scope (the parent of the PR that added
    it) reports nothing."""
    if trace is None or reduced is None or not sum(reduced.busy.values()):
        return None
    seconds = sum(sec for dev in reduced.busy
                  for op, sec in inside(trace.devices.get(dev, []),
                                        reduced.window) if pred(op))
    if not seconds:
        return None
    return 100.0 * seconds / sum(reduced.busy.values())


def spans_in_window(trace: Optional[ProgramTrace], name: str) -> List[Span]:
    """The spans of that name that lie wholly inside ``bench.window``."""
    if trace is None or trace.window is None:
        return []
    w0, w1 = trace.window
    return [s for s in trace.spans
            if s.name == name and s.start >= w0 and s.end <= w1]


def decode_spans(trace: Optional[ProgramTrace]) -> List[Span]:
    return [s for name in DECODE_SPANS for s in spans_in_window(trace, name)]


def idle_by_span(trace: ProgramTrace, window: tr.Interval
                 ) -> Dict[str, float]:
    """The seconds of the window in which no op ran on the device (mean
    over the devices that ran any), by the innermost ``ds.*`` span open at
    the time, as ``trace_reduce`` files them under its ``bench.*`` spans;
    what no span covers is ``unattributed``."""
    out: Dict[str, float] = {}
    labels = sorted((tr.Op(tr.HOST_PREFIX + s.name, s.start, s.end)
                     for s in trace.spans), key=lambda o: o.start)
    devices = 0
    for ops in trace.devices.values():
        union = tr.merge(tr.clip([(o.start, o.end) for o in ops], window))
        if not union:
            continue
        devices += 1
        for gap in tr.uncovered(window, union):
            for name, sec in tr._host_labels(gap, labels).items():
                out[name] = out.get(name, 0.0) + sec
    return {k: v / max(devices, 1) for k, v in out.items()}


def module_of(scope: str) -> str:
    """The flax module an op belongs to, as far as its path says
    (``h_3/c_attn/dot_general`` gives ``c_attn``, ``ln_f/...`` gives
    ``ln_f``); ``block`` for a layer's own arithmetic (the attention over
    the keys, the residual adds), ``other`` where the path names none."""
    parts = scope_parts(scope)
    for part in parts:
        if MODULE_RE.match(part):
            return part
    return "block" if any(LAYER_RE.match(p) for p in parts) else "other"


def stem(name: str) -> str:
    """An instruction's name without its per-instance number."""
    return re.sub(r"[._\d]+$", "", name)


def ds_scope_of(scope: str) -> str:
    """The innermost ``ds.*`` scope of a path, or ``-``."""
    named = [p for p in scope_parts(scope) if p.startswith(SPAN_PREFIX)]
    return named[-1] if named else "-"


def seconds_by(trace: ProgramTrace, reduced, key) -> Dict[str, float]:
    """Device seconds inside the reduced window by ``key(op)``, mean over
    the devices that ran anything."""
    out: Dict[str, float] = {}
    for dev in reduced.busy:
        for op, sec in inside(trace.devices.get(dev, []), reduced.window):
            k = key(op)
            out[k] = out.get(k, 0.0) + sec
    n = max(len(reduced.busy), 1)
    return {k: v / n for k, v in out.items()}


# ---------------------------------------------------------------------------
# Fixtures: a cut of a capture as plain JSON
# ---------------------------------------------------------------------------

def to_json(trace: ProgramTrace, window: tr.Interval) -> dict:
    """The part of the program's trace that touches ``window``, times in
    integer nanoseconds from its start (``cut_program_fixture.py``)."""
    t0 = window[0]
    ns = lambda t: round((t - t0) * 1e9)
    keep = lambda o: o.end > window[0] and o.start < window[1]
    scopes: Dict[str, int] = {}
    index = lambda s: scopes.setdefault(s, len(scopes))
    devices = {str(d): [[o.name, ns(o.start), ns(o.end), index(o.scope),
                         o.category, o.program_id]
                        for o in ops if keep(o)]
               for d, ops in trace.devices.items()}
    return {"scopes": list(scopes), "devices": devices,
            "spans": [[s.name, ns(s.start), ns(s.end), s.stats,
                       list(s.line)] for s in trace.spans if keep(s)],
            "window": [0, ns(window[1])]}


def from_json(doc: dict) -> ProgramTrace:
    scopes = doc["scopes"]
    trace = ProgramTrace(window=(doc["window"][0] / 1e9,
                                 doc["window"][1] / 1e9))
    for d, rows in doc["devices"].items():
        trace.devices[int(d)] = [
            DeviceOp(r[0], r[1] / 1e9, r[2] / 1e9, scopes[r[3]], r[4], r[5],
                     kernel_of(scopes[r[3]])) for r in rows]
    trace.spans = [Span(r[0], r[1] / 1e9, r[2] / 1e9, r[3], tuple(r[4]))
                   for r in doc["spans"]]
    link_spans(trace.spans)
    return trace


def load_fixture(path: str) -> ProgramTrace:
    with gzip.open(path, "rt") as f:
        return from_json(json.load(f))


def main(argv) -> int:
    path = argv[1]
    if os.path.isdir(path):
        path = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb*"),
                                recursive=True))[-1]
    trace = load(path)
    if len(argv) > 4:
        w0 = trace.window[0] + float(argv[2]) / 1e3
        doc = to_json(trace, (w0, w0 + float(argv[3]) / 1e3))
        doc["recorded"] = argv[5] if len(argv) > 5 else ""
        with gzip.GzipFile(argv[4], "wb", mtime=0) as f:
            f.write(json.dumps(doc, separators=(",", ":")).encode())
        print(f"{argv[4]}: {os.path.getsize(argv[4])} bytes, "
              f"{sum(len(v) for v in doc['devices'].values())} device rows, "
              f"{len(doc['spans'])} spans, {len(doc['scopes'])} scopes")
        return 0
    print(f"{path}: {os.path.getsize(path)} bytes; window {trace.window}")
    for dev, ops in sorted(trace.devices.items()):
        table: Dict[Tuple[str, str], float] = {}
        for op in ops:
            way = ("backward" if is_backward(op) else
                   "forward" if is_forward(op) else "-")
            key = (ds_scope_of(op.scope) + (f" [{op.kernel}]" if op.kernel
                                            else ""), way)
            table[key] = table.get(key, 0.0) + op.end - op.start
        print(f"device {dev}: {len(ops)} ops, "
              f"{sum(table.values()):.4f}s of op time")
        for (scope, way), sec in sorted(table.items(), key=lambda kv: -kv[1]):
            print(f"  {sec:10.4f}s  {scope:<32} {way}")
    names: Dict[str, List[Span]] = {}
    for s in trace.spans:
        names.setdefault(s.name, []).append(s)
    for name, spans in sorted(names.items()):
        parents = sorted({trace.spans[s.parent].name if s.parent is not None
                          else "-" for s in spans})
        print(f"span ds.{name}: {len(spans)}, "
              f"{sum(s.duration for s in spans):.4f}s, under {parents}, "
              f"stats of the first {spans[0].stats}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
