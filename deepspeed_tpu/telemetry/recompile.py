"""Silent-recompilation detector.

An XLA recompilation is the single most expensive silent failure mode on
TPU: a jitted step that retraces because one input's shape / dtype /
sharding changed costs seconds to minutes of compile time *per occurrence*
and produces no error — the run just mysteriously crawls. The classic
triggers: a ragged final batch, a dataloader that pads to the longest
sequence in the batch, a host scalar passed as a python int (every new
value is a new constant → new program).

The detector fingerprints the *call signature XLA's jit cache keys on* —
every leaf's (path, shape, dtype, sharding) — per named step function:

- the FIRST fingerprint for a function is the expected one-time compile;
- a REPEATED fingerprint is a cache hit (silent, free);
- a NEW fingerprint after the first is a **retrace**: a loud warning names
  the function and the exact leaves that changed, the
  ``telemetry/recompiles`` counter increments, and the tracer gets an
  instant event so the retrace shows up in the Perfetto timeline at the
  step where it happened.

Fingerprinting is host-side tuple hashing over aval metadata — no device
work, no sync — so the per-step cost is linear in batch-tree leaf count
and safe to leave on.
"""

import threading
from typing import Any, Dict, List, Optional, Tuple

from deepspeed_tpu.utils.logging import logger

RECOMPILE_COUNTER = "telemetry/recompiles"


def _leaf_sig(path, leaf) -> Tuple[str, str, str, str]:
    """(path, shape, dtype, sharding) — the aval metadata jit keys on."""
    name = "/".join(str(getattr(k, "key", getattr(k, "name",
                                getattr(k, "idx", k)))) for k in path)
    shape = getattr(leaf, "shape", None)
    if shape is None:
        if isinstance(leaf, str):
            # Strings are how callers declare STATIC jit inputs (closure /
            # static_argnums values): the VALUE keys the cache.
            return (name, "static", leaf, "-")
        # Python number scalars: jit traces them weakly-typed; the TYPE is
        # the stable part of the signature (a new float value does not
        # retrace, a float-where-int-was does).
        return (name, "scalar", type(leaf).__name__, "-")
    dtype = str(getattr(leaf, "dtype", "-"))
    sharding = getattr(leaf, "sharding", None)
    spec = str(getattr(sharding, "spec", "-")) if sharding is not None \
        else "host"
    return (name, str(tuple(shape)), dtype, spec)


def tree_signature(*trees) -> Tuple[Tuple[str, str, str, str], ...]:
    import jax

    sig: List[Tuple[str, str, str, str]] = []
    for i, tree in enumerate(trees):
        flat, _ = jax.tree_util.tree_flatten_with_path(tree)
        for path, leaf in flat:
            name, shape, dtype, spec = _leaf_sig(path, leaf)
            sig.append((f"arg{i}.{name}", shape, dtype, spec))
    return tuple(sig)


class RecompileDetector:
    """Per-function fingerprint cache + retrace accounting."""

    def __init__(self, registry=None, tracer=None, enabled: bool = True,
                 warn: bool = True):
        self.enabled = bool(enabled)
        self.warn = bool(warn)
        self.registry = registry
        self.tracer = tracer
        self._lock = threading.Lock()
        # fn -> {fingerprint-hash: signature-tuple}
        self._seen: Dict[str, Dict[int, Tuple]] = {}
        self.stats: Dict[str, Dict[str, int]] = {}

    def check(self, fn_name: str, *trees, step: Optional[int] = None) -> str:
        """Returns ``"compile"`` (expected first trace), ``"hit"`` (cached)
        or ``"retrace"`` (cache miss after the first — warned loudly)."""
        if not self.enabled:
            return "hit"
        sig = tree_signature(*trees)
        key = hash(sig)
        with self._lock:
            seen = self._seen.setdefault(fn_name, {})
            st = self.stats.setdefault(fn_name,
                                       {"compiles": 0, "retraces": 0})
            if key in seen:
                return "hit"
            first = not seen
            prev = next(reversed(seen.values())) if seen else None
            seen[key] = sig
            st["compiles"] += 1
            if first:
                return "compile"
            st["retraces"] += 1
        self._report(fn_name, prev, sig, step)
        return "retrace"

    def forget(self, fn_name: str) -> None:
        """Drop every fingerprint for ``fn_name`` so its next trace counts
        as the expected one-time compile, not a retrace. For EXPECTED
        recompilations only — today that is the in-process elastic world
        change (resilience/elastic.py), whose rebuilt step functions MUST
        recompile, and the serving degradation ladder's swap of the kernel
        decode for the default one; warning about them would train
        operators to ignore the detector."""
        with self._lock:
            self._seen.pop(fn_name, None)

    # ------------------------------------------------------------------
    def _report(self, fn_name: str, prev: Optional[Tuple], sig: Tuple,
                step: Optional[int]) -> None:
        changed = self._diff(prev, sig)
        if self.registry is not None:
            self.registry.counter(RECOMPILE_COUNTER).inc(step=step,
                                                         fn=fn_name)
        if self.tracer is not None:
            self.tracer.instant("recompile", fn=fn_name,
                                changed=changed[:8])
        if self.warn:
            logger.warning(
                "RECOMPILATION DETECTED: jitted step %r retraced%s — XLA is "
                "recompiling this function (seconds-to-minutes of silent "
                "stall per occurrence). Changed inputs: %s. Stabilize input "
                "shapes/dtypes/shardings (pad ragged batches, drop the "
                "short final batch, pass host scalars as jnp arrays).",
                fn_name,
                f" at step {step}" if step is not None else "",
                "; ".join(changed[:8]) if changed else "<signature length>")

    @staticmethod
    def _diff(prev: Optional[Tuple], sig: Tuple) -> List[str]:
        if prev is None:
            return []
        prev_map = {e[0]: e for e in prev}
        out = []
        for entry in sig:
            old = prev_map.get(entry[0])
            if old is None:
                out.append(f"{entry[0]}: new leaf "
                           f"{entry[1]}/{entry[2]}/{entry[3]}")
            elif old != entry:
                out.append(
                    f"{entry[0]}: {old[1]}/{old[2]}/{old[3]} -> "
                    f"{entry[1]}/{entry[2]}/{entry[3]}")
        new_names = {e[0] for e in sig}
        out.extend(f"{e[0]}: leaf removed" for e in prev
                   if e[0] not in new_names)
        return out

    # ------------------------------------------------------------------
    def compiles(self, fn_name: str) -> int:
        return self.stats.get(fn_name, {}).get("compiles", 0)

    def retraces(self, fn_name: Optional[str] = None) -> int:
        if fn_name is not None:
            return self.stats.get(fn_name, {}).get("retraces", 0)
        return sum(s["retraces"] for s in self.stats.values())
