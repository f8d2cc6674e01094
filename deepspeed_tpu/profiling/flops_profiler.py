"""FLOPs profiler — compiler-derived, not monkey-patched.

The reference's ``FlopsProfiler`` (``deepspeed/profiling/flops_profiler/
profiler.py:11``) wraps every ``torch.nn.functional`` op to count MACs as
they execute. On TPU the compiled program already knows its own cost: XLA's
``cost_analysis`` reports exact post-fusion FLOPs and bytes for the whole
step, and the jaxpr gives the pre-fusion per-primitive breakdown. This is
both cheaper (no per-op Python hooks in the hot path) and more truthful
(it counts what actually runs after fusion/remat).

``profile_callable`` profiles any jittable ``fn(*args)``; the engine's
``_maybe_profile`` hook calls it (measure=False) at
``flops_profiler.profile_step`` when the config block enables it
(reference engine hook parity). CAUTION: with measure=True a donating fn
consumes its args — the first (cold) call's timing is reported and the
inputs are gone afterwards.
"""

import sys
import time
from collections import defaultdict
from typing import Any, Dict, Optional

import jax
import numpy as np

# Peak matmul throughput per chip kind and dtype (TFLOP/s), keyed by
# ``jax.devices()[0].device_kind``. bf16 numbers are the published MXU
# peaks (Google Cloud TPU documentation; v5e: 197 TFLOP/s bf16, 819 GB/s
# HBM); fp32 runs the MXU in multi-pass mode at half rate. fp16 inputs go
# through the same bf16 MXU path on TPU. The table is the ONE source
# every MFU in the tree divides by — bench.py, engine/mfu
# (telemetry/goodput.py) and tools/goodput_report all route through
# :func:`mfu` below. A device kind that is not in the table has NO peak:
# the lookups return ``None`` and every figure divided by a peak is then
# absent rather than computed against somebody else's chip.
TPU_PEAK_TFLOPS = {
    "TPU v4": {"bfloat16": 275.0, "float32": 137.5},
    "TPU v5 lite": {"bfloat16": 197.0, "float32": 98.5},
    "TPU v5p": {"bfloat16": 459.0, "float32": 229.5},
    "TPU v6 lite": {"bfloat16": 918.0, "float32": 459.0},
    "TPU v6e": {"bfloat16": 918.0, "float32": 459.0},
}

# Peak HBM bandwidth per chip kind (GB/s) — the denominator of the
# roofline ridge point (telemetry/devicetime.py): ridge [flop/byte] =
# peak_flops / peak_bytes_per_sec. Published chip numbers.
TPU_PEAK_HBM_GBPS = {
    "TPU v4": 1228.0,
    "TPU v5 lite": 819.0,
    "TPU v5p": 2765.0,
    "TPU v6 lite": 1638.0,
    "TPU v6e": 1638.0,
}


def peak_hbm_gbps(device_kind: Optional[str] = None) -> Optional[float]:
    """Per-chip peak HBM bandwidth (GB/s); ``None`` for a device kind
    that is not in the table (CPU test meshes, chips nobody entered)."""
    return TPU_PEAK_HBM_GBPS.get(device_kind or "")

_DTYPE_ALIASES = {
    "bf16": "bfloat16", "bfloat16": "bfloat16",
    "fp32": "float32", "float32": "float32",
    # fp16 inputs ride the bf16 MXU path on TPU
    "fp16": "bfloat16", "float16": "bfloat16",
}


def peak_tflops(device_kind: Optional[str] = None,
                dtype: str = "bfloat16") -> Optional[float]:
    """Per-chip peak TFLOP/s for a device kind + compute dtype; ``None``
    for a device kind that is not in the table (CPU test meshes, chips
    nobody entered) — an unknown device gets no peak, not v5e's."""
    dtype = _DTYPE_ALIASES.get(str(dtype).lower(), "bfloat16")
    kinds = TPU_PEAK_TFLOPS.get(device_kind or "")
    if kinds is None:
        return None
    return kinds.get(dtype, kinds["bfloat16"])


def mfu(flops_per_step: Optional[float], step_time_s: float,
        n_chips: int = 1, peak_tflops_per_chip: Optional[float] = None,
        device_kind: Optional[str] = None,
        dtype: str = "bfloat16") -> Optional[float]:
    """Model FLOPs utilisation: ``flops_per_step`` (the WHOLE global
    step's FLOPs, across all chips) / (step time × chips × per-chip
    peak). Pass ``peak_tflops_per_chip`` explicitly or let the
    device-kind/dtype table supply it. ``None`` when there is no peak to
    divide by (device kind not in the table); 0.0 for degenerate inputs
    (no FLOPs, non-positive time) rather than raising — MFU is a report
    field, not a control signal."""
    if peak_tflops_per_chip is None:
        peak_tflops_per_chip = peak_tflops(device_kind, dtype)
    if peak_tflops_per_chip is None:
        return None
    if not flops_per_step or flops_per_step <= 0 or step_time_s <= 0:
        return 0.0
    denom = step_time_s * max(int(n_chips), 1) * peak_tflops_per_chip * 1e12
    return float(flops_per_step) / denom


def _count_params(tree: Any) -> int:
    return sum(int(np.prod(x.shape))
               for x in jax.tree_util.tree_leaves(tree)
               if hasattr(x, "shape"))


def _jaxpr_breakdown(closed_jaxpr) -> Dict[str, float]:
    """Pre-fusion FLOPs per primitive family from the jaxpr (the analogue of
    the reference's per-module table at module_depth granularity)."""
    flops = defaultdict(float)

    def visit(jaxpr):
        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name
            if prim == "dot_general":
                dims = eqn.params["dimension_numbers"]
                lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
                (lc, rc), (lb, rb) = dims
                m = np.prod([d for i, d in enumerate(lhs.shape)
                             if i not in set(lc) | set(lb)], dtype=float)
                n = np.prod([d for i, d in enumerate(rhs.shape)
                             if i not in set(rc) | set(rb)], dtype=float)
                k = np.prod([lhs.shape[i] for i in lc], dtype=float)
                b = np.prod([lhs.shape[i] for i in lb], dtype=float)
                flops["matmul"] += 2.0 * b * m * n * k
            elif prim in ("conv_general_dilated",):
                flops["conv"] += 0.0  # counted by XLA total; rare in-tree
            elif prim in ("exp", "log", "tanh", "logistic", "erf", "rsqrt",
                          "sqrt", "sin", "cos", "pow"):
                flops["transcendental"] += float(
                    np.prod(eqn.outvars[0].aval.shape, dtype=float))
            elif prim in ("add", "mul", "sub", "div", "max", "min",
                          "integer_pow"):
                flops["elementwise"] += float(
                    np.prod(eqn.outvars[0].aval.shape, dtype=float))
            elif prim in ("reduce_sum", "reduce_max", "reduce_min",
                          "argmax", "argmin"):
                flops["reduction"] += float(
                    np.prod(eqn.invars[0].aval.shape, dtype=float))
            # recurse into sub-jaxprs (scan/cond/while/pjit/remat bodies)
            for v in eqn.params.values():
                if hasattr(v, "jaxpr"):          # ClosedJaxpr
                    visit(v.jaxpr)
                elif hasattr(v, "eqns"):         # raw Jaxpr
                    visit(v)
                elif isinstance(v, (list, tuple)):
                    for u in v:
                        if hasattr(u, "jaxpr"):
                            visit(u.jaxpr)
                        elif hasattr(u, "eqns"):
                            visit(u)

    visit(closed_jaxpr.jaxpr)
    return dict(flops)


class FlopsProfiler:
    """Profile a jitted callable: compiled-cost totals + jaxpr breakdown +
    measured wall clock → achieved FLOP/s.

    Reference surface: ``get_model_profile``/``print_model_profile``
    (profiler.py:735,602).
    """

    def __init__(self, config=None):
        self.config = config
        self.last: Optional[Dict[str, Any]] = None

    def profile_callable(self, fn, *args, params: Any = None,
                         detailed: bool = True,
                         measure: bool = True) -> Dict[str, Any]:
        jfn = fn if isinstance(fn, jax.stages.Wrapped) else jax.jit(fn)
        lowered = jfn.lower(*args)
        compiled = lowered.compile()
        cost = compiled.cost_analysis() or {}
        result: Dict[str, Any] = {
            "flops": float(cost.get("flops", 0.0)),
            "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
            "params": _count_params(params) if params is not None else None,
        }
        if detailed:
            try:
                result["breakdown"] = _jaxpr_breakdown(
                    jax.make_jaxpr(fn)(*args))
            except Exception:  # jaxpr walking is best-effort diagnostics
                result["breakdown"] = {}
        if measure:
            # Warm-up, then a timed call — but a donating fn deletes its
            # inputs on the first call, so fall back to timing that first
            # (cold) call rather than crashing or re-running on corpses.
            t0 = time.perf_counter()
            out = compiled(*args)
            jax.block_until_ready(out)
            cold = time.perf_counter() - t0
            deleted = any(isinstance(a, jax.Array) and a.is_deleted()
                          for a in jax.tree_util.tree_leaves(args))
            if deleted:
                dt = cold
            else:
                t0 = time.perf_counter()
                out = compiled(*args)
                jax.block_until_ready(out)
                dt = time.perf_counter() - t0
            result["latency_s"] = dt
            result["achieved_tflops"] = result["flops"] / dt / 1e12
        self.last = result
        return result

    # ------------------------------------------------------------------
    def mfu(self, step_time_s: float,
            peak_tflops_per_chip: Optional[float] = None,
            n_chips: int = 1, flops: Optional[float] = None,
            device_kind: Optional[str] = None,
            dtype: str = "bfloat16") -> Optional[float]:
        """MFU of the last profiled callable (or explicit ``flops``) at a
        measured step time — delegates to the module-level :func:`mfu`,
        the single MFU formula in the tree."""
        if flops is None:
            flops = (self.last or {}).get("flops")
        return mfu(flops, step_time_s, n_chips=n_chips,
                   peak_tflops_per_chip=peak_tflops_per_chip,
                   device_kind=device_kind, dtype=dtype)

    # ------------------------------------------------------------------
    def print_profile(self, result: Optional[Dict[str, Any]] = None,
                      file=None) -> str:
        r = result or self.last
        if r is None:
            return ""
        lines = ["-" * 60, "DeepSpeed-TPU Flops Profiler (XLA cost analysis)"]
        if r.get("params") is not None:
            lines.append(f"params:               {r['params'] / 1e6:.2f} M")
        lines.append(f"fwd+bwd flops/step:   {r['flops'] / 1e9:.2f} G")
        lines.append(f"HBM bytes/step:       {r['bytes_accessed'] / 1e9:.3f} GB")
        if r["flops"] and r["bytes_accessed"]:
            lines.append(f"arithmetic intensity: "
                         f"{r['flops'] / max(r['bytes_accessed'], 1):.1f} flop/B")
        if "latency_s" in r:
            lines.append(f"step latency:         {r['latency_s'] * 1e3:.2f} ms")
            lines.append(f"achieved:             {r['achieved_tflops']:.2f} TFLOP/s")
        for k, v in sorted((r.get("breakdown") or {}).items(),
                           key=lambda kv: -kv[1]):
            lines.append(f"  {k:<18} {v / 1e9:10.2f} GFLOP (pre-fusion)")
        lines.append("-" * 60)
        text = "\n".join(lines)
        out = file if file is not None else sys.stderr
        print(text, file=out, flush=True)
        return text
