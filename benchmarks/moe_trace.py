"""What the six readers of the ``glm4_moe_lite`` cell share: which device
ops are the expert layer's, the counters the program hands to the trace
(``ds.step_counters`` spans, ``TPUEngine._trace_step_counters``), and
which stretch of the device's time belongs to which optimizer step.

One thing the scopes cannot say. ``jax.lax.ragged_dot`` reaches the chip
as XLA's own grouped-matmul kernel, and the compiler names that
instruction ``ragged-dot-*`` and drops the JAX name stack (its ``tf_op``
reads ``ragged-dot-none``, whatever scope the call was traced under). The
program has no other ragged dot, so the readers file every such op under
``ds.moe_experts``; which layer it belongs to (the MTP block's or
another's) cannot be told.

The six readers wait for their ``BENCHMARK.json`` entries (PERF.md,
section 7). Until then, by hand, on the capture a ``--trace 1`` run of
the cell left under ``.bench_out/``:

    python3 benchmarks/moe_trace.py glm47flash-train-s4096 [--rehearsal]
"""

import functools
import os
import sys
from typing import Dict, List, Optional, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import program_trace as pt  # noqa: E402
from benchmarks import trace_reduce as tr  # noqa: E402

ROUTE, DISPATCH, EXPERTS, SHARED, COMBINE = (
    "ds.moe_route", "ds.moe_dispatch", "ds.moe_experts", "ds.moe_shared",
    "ds.moe_combine")
COUNTERS_SPAN = "step_counters"
DISPATCH_SPAN = "train_step"            # one per optimizer step, ``step``
MODULE_LINE = "XLA Modules"             # one event per executable launch
READERS = ("moe.device_share", "moe.dispatch_share", "moe.experts_roofline",
           "moe.held_load_max_over_mean", "attn.mla_share",
           "train.mtp_share")


def is_grouped_matmul(op: pt.DeviceOp) -> bool:
    """XLA's grouped-matmul kernel and the small program that lays out its
    tiles (``ragged-dot-none.7``, ``ragged-dot-metadata.1``)."""
    return op.name.startswith("ragged-dot")


def is_experts(op: pt.DeviceOp) -> bool:
    return is_grouped_matmul(op) or pt.in_scope(op, EXPERTS)


def is_moe(op: pt.DeviceOp) -> bool:
    return is_grouped_matmul(op) or pt.in_scope(
        op, ROUTE, DISPATCH, EXPERTS, SHARED, COMBINE)


def is_dispatch(op: pt.DeviceOp) -> bool:
    """Router, sort and gather, and the way back: no expert's matmul."""
    return (not is_grouped_matmul(op)
            and pt.in_scope(op, ROUTE, DISPATCH, COMBINE))


def step_counters(trace: Optional[pt.ProgramTrace]) -> List[Dict]:
    """The stats of the window's ``ds.step_counters`` spans, one per
    optimizer step they speak of. The program hands over only what the
    device has finished, so they run two steps behind the dispatch."""
    by_step = {s.stats.get("of_step"): s.stats
               for s in pt.spans_in_window(trace, COUNTERS_SPAN)}
    return [stats for step, stats in sorted(
        by_step.items(), key=lambda kv: str(kv[0]))]


def mean_counter(trace: Optional[pt.ProgramTrace], name: str
                 ) -> Optional[float]:
    values = [float(c[name]) for c in step_counters(trace) if name in c]
    return sum(values) / len(values) if values else None


@functools.lru_cache(maxsize=4)
def launches(path: str) -> Dict[int, List[tr.Interval]]:
    """Per device, the stretch of every executable launch in the capture
    (the events of the line ``XLA Modules``), in time order."""
    out: Dict[int, List[tr.Interval]] = {}
    wanted = lambda plane, line: bool(
        tr.DEVICE_PLANE_RE.match(plane)) and line == MODULE_LINE
    for plane in pt.read_xplane(path, wanted):
        m = tr.DEVICE_PLANE_RE.match(plane.name)
        if m:
            out[int(m.group(1))] = sorted(
                (e.start, e.end) for _, _, events in plane.lines
                for e in events if e.end > e.start)
    return out


def counted_steps(run, trace: Optional[pt.ProgramTrace], reduced
                  ) -> List[Tuple[Dict, Dict[int, tr.Interval]]]:
    """``(counters, {device: stretch})`` of every optimizer step the
    capture holds BOTH ways: it ran inside the window, and its counters
    reached the window as a ``ds.step_counters`` span (two steps later,
    so a window of ``n`` steps gives ``n - 2``). On each device the
    launches that hold an expert op are the optimizer steps, in the
    order of the window's ``ds.train_step`` spans, whose ``step`` names
    them; where the two counts differ nothing is matched."""
    if trace is None or reduced is None:
        return []
    counters = {c.get("of_step"): c for c in step_counters(trace)}
    steps = [s.stats.get("step") for s in sorted(
        pt.spans_in_window(trace, DISPATCH_SPAN), key=lambda s: s.start)]
    w0, w1 = reduced.window
    stretches: Dict[object, Dict[int, tr.Interval]] = {}
    for dev, intervals in launches(run.xplane()).items():
        experts = [(op.start, op.end) for op in trace.devices.get(dev, [])
                   if is_experts(op)]
        mine = [(s, e) for s, e in intervals if s >= w0 and e <= w1
                and any(s <= a and b <= e for a, b in experts)]
        if len(mine) != len(steps):
            continue
        for step, stretch in zip(steps, mine):
            stretches.setdefault(step, {})[dev] = stretch
    return [(counters[step], stretches[step]) for step in steps
            if step in counters and step in stretches]


def seconds_in(trace: pt.ProgramTrace, stretches: Dict[int, tr.Interval],
               pred) -> float:
    """Device seconds of the ops ``pred`` admits inside each device's
    stretch, mean over those devices."""
    total = sum(sec for dev, stretch in stretches.items()
                for op, sec in pt.inside(trace.devices.get(dev, []), stretch)
                if pred(op))
    return total / max(len(stretches), 1)


def main(argv) -> int:
    """The six readers on the capture the cell's last traced run left."""
    from benchmarks.harness import (Run, capture_dir, load_module,
                                    open_cell, read_layer_metrics,
                                    start_device)
    rehearsal = "--rehearsal" in argv[2:]
    _, cell, config, traffic = open_cell(argv[1], rehearsal)
    _, peaks, _ = start_device(cell, rehearsal)
    run = Run(cell=cell, config=config, traffic=traffic,
              family=load_module("families", config["family"]), seed=0,
              seconds=0.0, trace=True, rehearsal=rehearsal, peaks=peaks,
              compiles=None,
              xplane_dir=capture_dir(cell["name"], rehearsal))
    values, _ = read_layer_metrics([{"name": n} for n in READERS], run, {})
    for name in READERS:
        print(f"{name} {values.get(name)}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
