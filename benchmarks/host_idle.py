"""The device's idle seconds of a traced stretch put down to the serving
engine's host path, a decode step at a time: the arithmetic of the
``serve.idle_*`` readers (``layer_metrics/serve/``).

``program_trace.idle_by_span`` gives each idle gap of the stretch
(``reduced.window``) to the innermost ``ds.*`` span open at the time. The
engine's spans round each part of a step's host path
(``serving/engine.py``): ``admit`` and ``batch_inputs`` (the scheduler and
the host arrays), ``put`` (transfers, the step's key), ``dispatch`` (a
jitted call), ``fetch`` (the wait for what the host needs next) and
``post_step`` (the bookkeeping after it). Each part's idle seconds over the
decode spans of the stretch, in ms, is a metric; what none of the parts
takes (a container's own time, ``submit``, no span at all) is the unnamed
share, of all the stretch's idle seconds. The parts and the unnamed part
add up to the stretch's idle seconds over its decode steps.

A program without the host path's spans (before they came) reads nothing:
``None``, as ``program_trace.share_of_busy`` does for a scope a program does
not name; a program with them and no idle under a part reads 0.0.
"""

from typing import Dict, Optional

from benchmarks import program_trace as pt

PARTS = {"prep": ("admit", "batch_inputs"), "put": ("put",),
         "dispatch": ("dispatch",), "fetch": ("fetch",),
         "post": ("post_step",)}
HOST_PATH = ("batch_inputs", "put", "dispatch", "fetch", "post_step")

_last = (None, None, None)          # (trace, window, split): six readers


def split(run, reduced) -> Optional[Dict[str, float]]:
    """Idle seconds of the stretch by part, with ``unnamed``, ``idle`` (all
    of them) and ``decode_steps``; ``None`` without a device trace, a
    decode step or the host path's spans."""
    global _last
    trace = pt.of_run(run)
    if trace is None or reduced is None:
        return None
    if _last[0] is trace and _last[1] == reduced.window:
        return _last[2]
    steps = len(pt.decode_spans(trace))
    out = None
    if steps and any(s.name in HOST_PATH for s in trace.spans):
        idle = pt.idle_by_span(trace, reduced.window)
        out = {part: sum(idle.get(name, 0.0) for name in names)
               for part, names in PARTS.items()}
        out["idle"] = sum(idle.values())
        out["unnamed"] = out["idle"] - sum(out[p] for p in PARTS)
        out["decode_steps"] = steps
    _last = (trace, reduced.window, out)
    return out


def ms_per_decode_step(run, reduced, part: str) -> Optional[float]:
    """Idle ms under ``part`` (a key of ``PARTS``, or ``unnamed``) a
    decode step of the stretch."""
    got = split(run, reduced)
    return None if got is None else 1e3 * got[part] / got["decode_steps"]


def unnamed_share(run, reduced) -> Optional[float]:
    """Percent of the stretch's idle seconds that no part takes."""
    got = split(run, reduced)
    if got is None or not got["idle"]:
        return None
    return 100.0 * got["unnamed"] / got["idle"]
