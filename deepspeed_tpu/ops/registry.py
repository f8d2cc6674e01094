"""Op registry — discoverable, named op implementations.

Reference: ``op_builder/`` + ``deepspeed/ops/__init__.py``: every CUDA
extension registers a builder that reports availability/compatibility and
is listed by ``ds_report``. On TPU there is nothing to compile at install
time, but the same discoverability contract matters: which attention/
optimizer/quantizer implementations exist, which are Pallas-accelerated,
and whether the current backend can run them.
"""

from dataclasses import dataclass
from typing import Callable, Dict, Optional

from deepspeed_tpu.utils.platform import on_tpu


@dataclass(frozen=True)
class OpSpec:
    name: str
    kind: str                      # attention | optimizer | quantizer | ...
    loader: Callable               # () -> the op callable/class
    pallas: bool = False           # uses a hand-written Pallas kernel
    requires_tpu: bool = False
    available_fn: Optional[Callable] = None   # env-dependent availability

    def available(self) -> bool:
        if self.available_fn is not None:
            return bool(self.available_fn())
        if self.requires_tpu:
            return on_tpu()
        return True

    def load(self):
        return self.loader()


_REGISTRY: Dict[str, OpSpec] = {}


def register_op(name: str, kind: str, loader: Callable, *,
                pallas: bool = False, requires_tpu: bool = False,
                available_fn: Optional[Callable] = None) -> None:
    if name in _REGISTRY:
        raise ValueError(f"op '{name}' already registered")
    _REGISTRY[name] = OpSpec(name, kind, loader, pallas, requires_tpu,
                             available_fn)


def get_op(name: str):
    if name not in _REGISTRY:
        raise KeyError(f"unknown op '{name}'; known: {sorted(_REGISTRY)}")
    return _REGISTRY[name].load()


def list_ops(kind: Optional[str] = None) -> Dict[str, OpSpec]:
    return {n: s for n, s in _REGISTRY.items()
            if kind is None or s.kind == kind}


def _builtin(name, kind, path, attr, **kw):
    def loader():
        import importlib
        return getattr(importlib.import_module(path), attr)

    register_op(name, kind, loader, **kw)


# ---------------------------------------------------------------------------
# Built-in ops (the in-tree analogue of op_builder's ALL_OPS table)
# ---------------------------------------------------------------------------
_builtin("xla_attention", "attention",
         "deepspeed_tpu.ops.transformer.attention", "xla_attention")
_builtin("flash_attention", "attention",
         "deepspeed_tpu.ops.transformer.flash_attention", "flash_attention",
         pallas=True, requires_tpu=True)
_builtin("sparse_attention", "attention",
         "deepspeed_tpu.ops.sparse_attention", "sparse_attention")
_builtin("fused_adam", "optimizer",
         "deepspeed_tpu.ops.adam.fused_adam", "FusedAdam")
_builtin("fused_adamw", "optimizer",
         "deepspeed_tpu.ops.adam.fused_adam", "FusedAdamW")
_builtin("cpu_adam", "optimizer",
         "deepspeed_tpu.ops.adam.fused_adam", "HostOffloadAdam")
_builtin("fused_lamb", "optimizer",
         "deepspeed_tpu.ops.lamb.fused_lamb", "FusedLamb")
_builtin("onebit_adam", "optimizer",
         "deepspeed_tpu.ops.onebit.adam", "OneBitAdam")
_builtin("onebit_lamb", "optimizer",
         "deepspeed_tpu.ops.onebit.lamb", "OneBitLamb")
_builtin("transformer_layer", "transformer",
         "deepspeed_tpu.ops.transformer", "DeepSpeedTransformerLayer")
_builtin("moq_quantizer", "quantizer",
         "deepspeed_tpu.ops.quantizer", "MoQQuantizer")
_builtin("weight_quantizer", "quantizer",
         "deepspeed_tpu.inference.quantization", "quantize_params")


def _aio_loader():
    from deepspeed_tpu.ops.aio_native import load_aio
    mod = load_aio()
    if mod is None:
        raise RuntimeError("native aio unavailable (no C++ toolchain); the "
                           "swap tier uses the numpy fallback")
    return mod


def _aio_available():
    from deepspeed_tpu.ops.aio_native import load_aio
    return load_aio() is not None


register_op("async_io", "io", _aio_loader, available_fn=_aio_available)
