"""From a ``jax.profiler`` trace to numbers: the one reduction every PR's
per-layer metrics go through.

A trace is reduced in two steps. :func:`load_xplane` turns the profiler's
``.xplane.pb`` into a :class:`Trace`: per device its operations, plus the
benchmark's own host annotations (``bench.*``), all in seconds on the
trace's clock. Everything after that is interval arithmetic on plain
tuples, which the tests check against a small trace recorded on the chip
(``fixtures/``) and against hand-made cases.

What is read was decided by looking at a real v5e trace by hand
(jax 0.9.0, libtpu 0.0.34; ``dump_xplane.py``; ``PERF.md``, Findings,
PR 22):

- a device is a plane named ``/device:TPU:<n>``;
- its operations are the events of the line ``XLA Ops``. The lines ``XLA
  Modules`` and ``Steps`` hold whole programs and would read as "always
  busy"; so would the ``while`` (``conditional``, ``call``) events on the
  op line itself, which span everything their bodies run: containers are
  dropped and only the ops inside them count;
- an event's name is the full text of its HLO instruction and the trace
  carries no category for it, so opcode, fusion kind and custom-call
  target are parsed from that text (:func:`parse_hlo`);
- the line ``Async XLA Ops`` holds the start-to-done span of every
  asynchronous operation. On the op line a ``...-start`` lasts
  nanoseconds and the ``...-done`` lasts as long as the core waits. Only
  collectives' spans are taken from it (their time in flight); the
  asynchronous copies and slices the compiler schedules round the matmuls
  are left out. On a four-chip host the profiler fills this line for the
  first chip only, so how much communication is already hidden can be
  read there and nowhere else, while the exposed part (the core sits in
  a collective op) reads alike on every chip;
- a collective is an op whose opcode or name holds one of the collective
  names, or ``async-collective`` (XLA's custom fusions that start and
  finish a decomposed all-gather or reduce-scatter);
- host annotations are the events named ``bench.*`` on any line of a
  ``/host:`` plane. The profiler puts both on one clock: the first device
  op of the window starts a few milliseconds after ``bench.window`` does.

The interval helpers follow ``deepspeed_tpu/telemetry/traceparse.py``
(merge, uncovered segments), copied so that a PR which changes the program
cannot change the yardstick.
"""

import gzip
import json
import re
from dataclasses import dataclass, field
from typing import Callable, Dict, Iterable, List, Optional, Tuple

Interval = Tuple[float, float]

DEVICE_PLANE_RE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
HOST_PREFIX = "bench."
WINDOW = "bench.window"
CONTAINERS = frozenset({"while", "conditional", "call"})

COLLECTIVE_RE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
    r"|async-collective", re.IGNORECASE)
HLO_RE = re.compile(
    r"^%(?P<name>\S+) = (?P<result>.*?) (?P<opcode>[a-z][a-z\-]*)\(")
KIND_RE = re.compile(r"\bkind=(\w+)")
TARGET_RE = re.compile(r'custom_call_target="([^"]+)"')
LAYOUT_RE = re.compile(r"\{[^{}]*\}")


@dataclass(frozen=True)
class Op:
    """One device operation (or host annotation). ``detail`` is a fusion's
    kind or a custom call's target; ``result`` a custom call's result
    shape, which tells one unnamed kernel from another; ``in_flight``
    marks a start-to-done span of the async line."""
    name: str
    start: float
    end: float
    opcode: str = ""
    detail: str = ""
    result: str = ""
    in_flight: bool = False


@dataclass
class Trace:
    devices: Dict[int, List[Op]] = field(default_factory=dict)
    host: List[Op] = field(default_factory=list)


def parse_hlo(text: str):
    """``(name, opcode, detail, result)`` of an HLO instruction's text as
    the trace prints it: ``%fusion.7 = bf16[8,128]{1,0} fusion(...),
    kind=kOutput, calls=...``."""
    m = HLO_RE.match(text)
    if not m:
        return text, "", "", ""
    opcode = m.group("opcode")
    detail = result = ""
    if opcode == "fusion":
        kind = KIND_RE.search(text)
        detail = kind.group(1) if kind else ""
    elif opcode == "custom-call":
        target = TARGET_RE.search(text)
        detail = target.group(1) if target else ""
        result = LAYOUT_RE.sub("", m.group("result"))
    return m.group("name"), opcode, detail, result


# ---------------------------------------------------------------------------
# Loading
# ---------------------------------------------------------------------------

def load_xplane(path: str) -> Trace:
    """Read a profiler capture with nothing but JAX."""
    from jax.profiler import ProfileData

    trace = Trace()
    for plane in ProfileData.from_file(path).planes:
        m = DEVICE_PLANE_RE.match(plane.name)
        if m:
            ops = trace.devices.setdefault(int(m.group(1)), [])
            for line in plane.lines:
                if line.name not in (OP_LINE, ASYNC_LINE):
                    continue
                in_flight = line.name == ASYNC_LINE
                for ev in line.events:
                    if ev.duration_ns <= 0:
                        continue
                    name, opcode, detail, result = parse_hlo(ev.name)
                    op = Op(name, ev.start_ns / 1e9,
                            (ev.start_ns + ev.duration_ns) / 1e9,
                            opcode, detail, result, in_flight)
                    if opcode in CONTAINERS or (
                            in_flight and not is_collective(op)):
                        continue
                    ops.append(op)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        trace.host.append(
                            Op(ev.name, ev.start_ns / 1e9,
                               (ev.start_ns + ev.duration_ns) / 1e9))
    return trace


def to_json(trace: Trace, window: Interval) -> dict:
    """The part of a trace that touches ``window`` as plain JSON, times in
    integer nanoseconds from the window's start: how a fixture is
    recorded."""
    t0 = window[0]
    keep = lambda o: o.end > window[0] and o.start < window[1]
    row = lambda o: [o.name, round((o.start - t0) * 1e9),
                     round((o.end - t0) * 1e9), o.opcode, o.detail,
                     o.result, int(o.in_flight)]
    return {"devices": {str(d): [row(o) for o in ops if keep(o)]
                        for d, ops in trace.devices.items()},
            "host": [row(o) for o in trace.host if keep(o)]}


def from_json(doc: dict) -> Trace:
    op = lambda r: Op(r[0], r[1] / 1e9, r[2] / 1e9, r[3], r[4], r[5],
                      bool(r[6]))
    return Trace({int(d): [op(r) for r in rows]
                  for d, rows in doc["devices"].items()},
                 [op(r) for r in doc["host"]])


def load_fixture(path: str) -> Trace:
    with gzip.open(path, "rt") as f:
        return from_json(json.load(f))


# ---------------------------------------------------------------------------
# Interval arithmetic
# ---------------------------------------------------------------------------

def merge(ivs: Iterable[Interval]) -> List[Interval]:
    """Sorted union of (start, end) intervals."""
    out: List[Interval] = []
    for s, e in sorted(ivs):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(merged: Iterable[Interval]) -> float:
    return sum(e - s for s, e in merged)


def clip(ivs: Iterable[Interval], window: Interval) -> List[Interval]:
    w0, w1 = window
    return [(max(s, w0), min(e, w1)) for s, e in ivs if e > w0 and s < w1]


def uncovered(iv: Interval, merged: List[Interval]) -> List[Interval]:
    """The pieces of ``iv`` that the merged union does not cover."""
    s, e = iv
    out: List[Interval] = []
    cur = s
    for ms, me in merged:
        if me <= cur:
            continue
        if ms >= e:
            break
        if ms > cur:
            out.append((cur, min(ms, e)))
        cur = max(cur, me)
        if cur >= e:
            break
    if cur < e:
        out.append((cur, e))
    return out


# ---------------------------------------------------------------------------
# Classification
# ---------------------------------------------------------------------------

def is_collective(op: Op) -> bool:
    """By opcode (``all-gather-start``) or, for one wrapped in a generic
    ``async-start``, by the name XLA gives it (``all-gather-start.5``)."""
    return bool(COLLECTIVE_RE.search(op.opcode)
                or COLLECTIVE_RE.search(op.name))


def is_mosaic(op: Op) -> bool:
    """A Pallas (Mosaic) kernel."""
    return op.opcode == "custom-call" and op.detail == "tpu_custom_call"


def is_matmul(op: Op) -> bool:
    """Work for the MXU: a convolution (XLA's name for every dot on a
    TPU), alone or as the root of an output fusion. XLA gives
    ``kind=kOutput`` to the fusions it builds round a convolution; in the
    recorded trace their time is 1.10x what the step's matmul FLOPs take
    at the chip's peak, and no other class of op is within reach of
    that."""
    return (op.opcode in ("convolution", "dot")
            or (op.opcode == "fusion" and op.detail == "kOutput"))


def table_key(op: Op) -> str:
    """The name an op is filed under in the ``breakdown``: the opcode and
    what tells its kind, without the per-instance number, so that the
    same op of every layer and step adds up."""
    stem = re.sub(r"[._\d]+$", "", op.name)
    if op.opcode == "fusion":
        return f"fusion {op.detail} {stem}"
    if op.opcode == "custom-call":
        return f"custom-call {op.detail} -> {op.result}"
    return f"{op.opcode} {stem}" if op.opcode else op.name


# ---------------------------------------------------------------------------
# Reductions
# ---------------------------------------------------------------------------

def window_of(trace: Trace) -> Interval:
    """The traced window: the benchmark's ``bench.window`` annotation."""
    spans = [(o.start, o.end) for o in trace.host if o.name == WINDOW]
    if not spans:
        raise ValueError(f"the trace holds no {WINDOW!r} annotation")
    return min(s for s, _ in spans), max(e for _, e in spans)


@dataclass
class Reduced:
    """Per-device seconds inside the window; the metrics average them
    over the devices that ran anything."""
    window: Interval
    busy: Dict[int, float]
    by_class: Dict[str, Dict[int, float]]       # matmul / mosaic / collective
    exposed_collective: Dict[int, float]
    spans_in_flight: List[int]                  # devices whose trace has them
    ops: Dict[str, float]                       # name -> seconds, all devices
    gaps: Dict[str, float]                      # host label -> idle seconds

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def mean(self, per_device: Dict[int, float]) -> float:
        return (sum(per_device.values()) / len(per_device)
                if per_device else 0.0)

    @property
    def busy_s(self) -> float:
        return self.mean(self.busy)

    def share_of_busy(self, cls: str) -> float:
        busy = sum(self.busy.values())
        return sum(self.by_class[cls].values()) / busy if busy else 0.0


CLASSES: Dict[str, Callable[[Op], bool]] = {
    "matmul": is_matmul, "mosaic": is_mosaic, "collective": is_collective}


def reduce(trace: Trace, window: Optional[Interval] = None) -> Reduced:
    """Busy is the union of the op line's operations (a core that waits in
    a collective's ``-done`` is busy: the wait is the collective's, and
    counted as exposed). A collective's time is the union of its ops and
    of its spans in flight; its exposed part is what no other op of that
    device covers."""
    window = window or window_of(trace)
    busy, exposed = {}, {}
    by_class = {c: {} for c in CLASSES}
    ops: Dict[str, float] = {}
    gaps: Dict[str, float] = {}
    labels = sorted((o for o in trace.host if o.name != WINDOW),
                    key=lambda o: o.start)
    for dev, dev_ops in trace.devices.items():
        inside = [(o, c) for o in dev_ops
                  for c in clip([(o.start, o.end)], window)]
        on_core = [(o, c) for o, c in inside if not o.in_flight]
        if not on_core:
            continue
        union = merge(c for _, c in on_core)
        busy[dev] = total(union)
        for cls, pred in CLASSES.items():
            by_class[cls][dev] = total(merge(c for o, c in inside if pred(o)))
        compute = merge(c for o, c in on_core if not is_collective(o))
        exposed[dev] = sum(
            total(uncovered(iv, compute))
            for iv in merge(c for o, c in inside if is_collective(o)))
        for o, (s, e) in on_core:
            key = table_key(o)
            ops[key] = ops.get(key, 0.0) + (e - s)
        for gap in uncovered(window, union):
            for label, sec in _host_labels(gap, labels).items():
                gaps[label] = gaps.get(label, 0.0) + sec
    n = max(len(busy), 1)
    in_flight = [d for d in busy
                 if any(o.in_flight for o in trace.devices[d])]
    return Reduced(window, busy, by_class, exposed, in_flight,
                   ops, {k: v / n for k, v in gaps.items()})


def _host_labels(gap: Interval, labels: List[Op]) -> Dict[str, float]:
    """What the host was doing in ``gap``: its seconds split over the
    ``bench.*`` annotations that overlap it (where two nest, the inner one
    takes its part), the rest ``unattributed``."""
    out: Dict[str, float] = {}
    left = [gap]
    for o in reversed(labels):              # later start first: innermost
        if o.end <= gap[0] or o.start >= gap[1]:
            continue
        rest: List[Interval] = []
        for piece in left:
            free = uncovered(piece, [(o.start, o.end)])
            took = (piece[1] - piece[0]) - total(free)
            if took > 0:
                name = o.name[len(HOST_PREFIX):]
                out[name] = out.get(name, 0.0) + took
            rest += free
        left = rest
        if not left:
            break
    if total(left) > 0:
        out["unattributed"] = total(left)
    return out


def top(table: Dict[str, float], k: int = 10) -> List[list]:
    rows = sorted(table.items(), key=lambda kv: kv[1], reverse=True)
    return [[name, sec] for name, sec in rows[:k]]


def breakdown(red: Reduced) -> dict:
    """The ``breakdown`` of a traced run's result line: device seconds by
    operation (mean over devices) and idle seconds by host activity."""
    n = max(len(red.busy), 1)
    return {"device_ops": top({k: v / n for k, v in red.ops.items()}),
            "idle_gaps": top(red.gaps)}
