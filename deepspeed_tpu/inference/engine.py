"""Inference engine — TP-sharded forward + KV-cache generation.

TPU-native re-design of the reference's ``InferenceEngine``
(``deepspeed/inference/engine.py:19``) and ``module_inject`` TP slicing
(``module_inject/replace_module.py:89``, ``replace_policy.py``):

- **TP injection → partition rules.** The reference walks the module tree and
  splits qkv/mlp weights onto ranks with ``ReplaceWithTensorSlicing``. Here
  the same Megatron-style split is declarative: the model family's
  ``(regex → PartitionSpec)`` rules (``models/partition.py``) are applied to
  the param tree and GSPMD inserts the all-reduces — no module surgery.
- **Kernel injection → attention dispatch.** ``replace_with_kernel_inject``
  selects the fused CUDA op in the reference; here the models already route
  through ``ops/transformer/attention`` whose ``auto`` mode picks the Pallas
  flash kernel when profitable.
- **KV cache** (reference ``csrc/transformer/inference`` attention cache):
  static-shape per-layer (k, v) arrays updated via ``dynamic_update_slice``;
  the whole prefill + N-token decode runs as ONE jitted program (prefill +
  ``lax.scan``) — one dispatch per generate call, not per token.
- **Int8 weight quantization** (reference ``runtime/weight_quantizer.py:5``):
  weights live in HBM as int8 + scales; dequant is fused into each consumer
  matmul inside the jitted step. See ``inference/quantization.py``.
"""

import functools
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from deepspeed_tpu.inference.quantization import (dequantize_params,
                                                  quantize_params,
                                                  quantized_nbytes)
from deepspeed_tpu.models.partition import build_specs
from deepspeed_tpu.telemetry.tracer import device_scope
from deepspeed_tpu.utils.logging import log_dist

# Smallest prompt bucket: prompts shorter than this share one compiled
# prefill (the compile-cache floor — a 1-token and a 7-token prompt are
# not worth distinct programs).
MIN_PROMPT_BUCKET = 8


def bucket_length(t: int, floor: int = MIN_PROMPT_BUCKET,
                  cap: Optional[int] = None) -> int:
    """Round ``t`` up to the bucket the jitted prefill compiles for: the
    next power of two, at least ``floor``, clamped to ``cap`` (the usable
    context minus the decode budget) but never below ``t`` itself."""
    b = max(floor, 1 << max(0, (t - 1).bit_length()))
    if cap is not None:
        b = min(b, cap)
    return max(b, t)


@device_scope("sample")
def sample_logits(logits, rng, temperature: float, top_k: int):
    """Greedy (``temperature == 0``) or temperature/top-k sampling over
    ``[B, V]`` fp32 logits — shared by ``generate()`` and the serving
    engine's decode program (one sampling implementation in the tree)."""
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / temperature
    if top_k > 0:
        kth = jnp.sort(logits, axis=-1)[:, -top_k][:, None]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    return jax.random.categorical(rng, logits, axis=-1).astype(jnp.int32)


class InferenceConfig:
    """Normalized ``init_inference`` kwargs (reference
    ``deepspeed/__init__.py:227`` signature)."""

    def __init__(self, mp_size: int = 1, dtype: Any = None,
                 quantize: bool = False, quantize_groups: int = 1,
                 replace_with_kernel_inject: bool = True,
                 max_tokens: Optional[int] = None,
                 recompile_detection: bool = True,
                 bucket_prompts: bool = True, **extra):
        self.mp_size = int(mp_size)
        self.dtype = dtype if dtype is not None else jnp.bfloat16
        self.quantize = bool(quantize)
        self.quantize_groups = int(quantize_groups)
        self.replace_with_kernel_inject = bool(replace_with_kernel_inject)
        self.max_tokens = max_tokens
        self.recompile_detection = bool(recompile_detection)
        # Pad prompts (left, masked) to power-of-two buckets so varying
        # prompt lengths hit a bounded set of compiled prefill programs
        # instead of retracing per length.
        self.bucket_prompts = bool(bucket_prompts)
        self.extra = extra


class InferenceEngine:
    """Sharded, jitted inference over a flax module.

    ``model``: a flax module whose ``apply({'params': p}, batch,
    deterministic=True)`` returns a dict with "logits" (the in-tree GPT/BERT
    families). Generation additionally needs the module to accept
    ``cache=``/``pos=`` (GPT) — see ``models/gpt.py``.
    """

    def __init__(self, model, params: Any = None,
                 config: Optional[InferenceConfig] = None,
                 mp_size: int = 1, dtype: Any = None,
                 quantize: bool = False, quantize_groups: int = 1,
                 partition_rules=None, injection_policy=None,
                 mesh: Optional[Mesh] = None,
                 checkpoint: Optional[str] = None,
                 example_batch: Any = None, tracer: Any = None, **kwargs):
        self.module = model
        cfg = config or InferenceConfig(
            mp_size=mp_size, dtype=dtype, quantize=quantize,
            quantize_groups=quantize_groups, **kwargs)
        self.config = cfg
        self.model_cfg = getattr(model, "cfg", None)

        # --- external-model injection (module_inject/replace_policy.py):
        # a recognized HF-Flax model is converted onto the in-tree family
        # so it serves through the TPU kernels + TP rules — the
        # reference's replace_with_kernel_inject for other people's
        # models (replace_module.py:11). ``injection_policy`` may name a
        # policy class explicitly; (regex, dims) partition-rule tuples
        # keep their existing meaning below.
        inject_pol = None
        if (isinstance(injection_policy, type)
                and hasattr(injection_policy, "convert")):
            inject_pol = injection_policy
            injection_policy = None
        if cfg.replace_with_kernel_inject or inject_pol is not None:
            from deepspeed_tpu.module_inject import convert_external_model
            if inject_pol is not None or (hasattr(model, "config")
                                          and self.model_cfg is None):
                conv = convert_external_model(model, params=params,
                                              injection_policy=inject_pol,
                                              dtype=cfg.dtype)
                if conv is not None:
                    src_name = type(model).__name__
                    model, params = conv
                    self.module = model
                    self.model_cfg = model.cfg
                    log_dist(
                        f"kernel injection: converted {src_name} weights "
                        f"onto the in-tree {type(model).__name__} family",
                        ranks=[0])

        if checkpoint is not None and params is None:
            from deepspeed_tpu.runtime.checkpointing import load_module_params
            params = load_module_params(checkpoint)
        if params is None:
            if example_batch is None:
                raise ValueError("init_inference needs params, checkpoint, "
                                 "or example_batch to initialise the module")
            params = model.init({"params": jax.random.PRNGKey(0),
                                 "dropout": jax.random.PRNGKey(1)},
                                example_batch)["params"]

        # --- tensor-parallel mesh + param sharding -----------------------
        self.mesh = mesh
        if self.mesh is None and cfg.mp_size > 1:
            from deepspeed_tpu.parallel.mesh import build_mesh
            self.mesh = build_mesh(model=cfg.mp_size)
        self.mp_size = cfg.mp_size

        rules = partition_rules if partition_rules is not None else \
            injection_policy
        if rules is None:
            rules = self._default_rules()
        if rules is None and cfg.mp_size > 1:
            raise ValueError(
                f"mp_size={cfg.mp_size} requested but "
                f"{type(model).__name__} has no built-in partition rules — "
                f"pass partition_rules=/injection_policy= ((regex, dims) "
                f"pairs, see models/partition.py) or mp_size=1")
        self._param_specs = None
        cast = lambda p: (p.astype(cfg.dtype)
                          if jnp.issubdtype(p.dtype, jnp.floating) else p)
        params = jax.tree_util.tree_map(cast, params)
        if cfg.quantize:
            params = quantize_params(params, groups=cfg.quantize_groups)
            log_dist(f"int8 weight quantization: model weights now "
                     f"{quantized_nbytes(params) / 1e6:.1f} MB", ranks=[0])
        if self.mesh is not None and rules is not None:
            # Specs only need paths + ranks: use shape structs for quantized
            # leaves, never materializing a dense dequantized copy.
            from deepspeed_tpu.inference.quantization import QuantizedWeight
            base = jax.tree_util.tree_map(
                lambda x: (jax.ShapeDtypeStruct(x.shape, jnp.bfloat16)
                           if isinstance(x, QuantizedWeight) else x),
                params, is_leaf=lambda x: isinstance(x, QuantizedWeight))
            self._param_specs = build_specs(base, rules,
                                            mesh_axes=dict(self.mesh.shape))
            params = self._shard_params(params)
        self.params = params

        self._forward_jit = None
        self._generate_jit: Dict = {}
        self._generate_calls = 0
        # Serving-side retrace alarm (telemetry/recompile.py): a ragged
        # prompt length or dtype drift recompiles prefill+decode per
        # request — seconds of silent tail latency the detector names.
        from deepspeed_tpu.telemetry import RecompileDetector, StepTracer
        self.recompile_detector = RecompileDetector(
            enabled=cfg.recompile_detection)
        # Inference spans land in the same Perfetto timeline as training:
        # pass the run's StepTracer (telemetry.tracer) and every
        # forward/generate dispatch is bracketed; without one the span is
        # the reusable zero-cost no-op.
        self.tracer = tracer if tracer is not None else \
            StepTracer(enabled=False)

    # ------------------------------------------------------------------
    def _default_rules(self):
        from deepspeed_tpu.models import (BertModel, GPT,
                                          bert_partition_rules,
                                          gpt_partition_rules)
        if isinstance(self.module, GPT):
            return gpt_partition_rules()
        if isinstance(self.module, BertModel):
            return bert_partition_rules()
        return None

    def _shard_params(self, params):
        """Place each leaf with its TP NamedSharding (QuantizedWeight leaves:
        shard the int8 payload with the same spec, replicate the scales)."""
        from deepspeed_tpu.inference.quantization import QuantizedWeight

        def place(leaf, spec):
            if isinstance(leaf, QuantizedWeight):
                qdims = (None,) + tuple(spec) + (None,) * max(
                    0, leaf.q.ndim - 1 - len(tuple(spec)))
                qspec = PartitionSpec(*qdims[:leaf.q.ndim])
                return QuantizedWeight(
                    jax.device_put(leaf.q,
                                   NamedSharding(self.mesh, qspec)),
                    jax.device_put(leaf.scale,
                                   NamedSharding(self.mesh, PartitionSpec())),
                    leaf.shape)
            return jax.device_put(leaf, NamedSharding(self.mesh, spec))

        return jax.tree_util.tree_map(
            place, params, self._param_specs,
            is_leaf=lambda x: isinstance(x, QuantizedWeight))

    def _materialized(self, params):
        return (dequantize_params(params, self.config.dtype)
                if self.config.quantize else params)

    # ------------------------------------------------------------------
    def forward(self, batch, **kwargs):
        """Jitted deterministic forward; returns the module's output dict."""
        self.recompile_detector.check("inference.forward", batch)
        if self._forward_jit is None:
            def fwd(params, batch):
                p = self._materialized(params)
                return self.module.apply({"params": p}, batch,
                                         deterministic=True)
            self._forward_jit = jax.jit(fwd)
        with self.tracer.span("inference_forward"):
            return self._forward_jit(self.params, batch)

    __call__ = forward

    # ------------------------------------------------------------------
    def generate(self, input_ids, max_new_tokens: int = 32,
                 temperature: float = 0.0, top_k: int = 0,
                 seed: Optional[int] = None,
                 attention_mask=None):
        """Autoregressive generation with a KV cache.

        ``input_ids``: [B, T0] int32 prompts. Ragged prompts must be
        **left-padded** to a uniform T0 and accompanied by
        ``attention_mask`` ([B, T0], 1 = real token, 0 = pad, pads leading):
        pad slots are masked out of every attention step (prefill and the
        whole decode) and learned positions are re-based per row so each
        row's content starts at position 0. Without a mask, prompts are
        taken as unpadded.

        Greedy when ``temperature == 0``, else temperature sampling with
        optional top-k. Sampling uses ``seed`` when given (byte-identical
        outputs for the same seed); when ``seed`` is None an engine-held
        call counter is mixed in so repeated calls draw fresh samples.
        The whole prefill + ``max_new_tokens``-step decode is one jitted
        program. Returns [B, T0 + max_new_tokens].
        """
        import inspect
        sig = inspect.signature(type(self.module).__call__)
        if self.model_cfg is None or "cache" not in sig.parameters:
            raise ValueError(
                f"generate() needs a cache-capable causal LM whose __call__ "
                f"takes cache=/pos= (the in-tree GPT family); "
                f"{type(self.module).__name__} does not")
        ids = jnp.asarray(input_ids, jnp.int32)
        b, t0 = ids.shape
        total = t0 + int(max_new_tokens)
        limit = getattr(self.model_cfg, "max_seq_len", None)
        if self.config.max_tokens is not None:
            limit = (min(limit, self.config.max_tokens) if limit is not None
                     else self.config.max_tokens)
        if limit is not None and total > limit:
            raise ValueError(
                f"prompt ({t0}) + max_new_tokens ({max_new_tokens}) = "
                f"{total} exceeds the usable context of {limit} "
                f"(model max_seq_len / init_inference max_tokens) — "
                f"positions past it would silently clamp")
        if attention_mask is not None:
            mask = np.asarray(attention_mask)
            if mask.shape != (b, t0):
                raise ValueError(f"attention_mask shape {mask.shape} != "
                                 f"{(b, t0)}")
            if not (np.diff(mask.astype(np.int8), axis=1) >= 0).all():
                raise ValueError("attention_mask must be left-padded "
                                 "(0s before 1s in every row)")
            if (mask.sum(axis=1) == 0).any():
                raise ValueError("attention_mask has a fully-padded row — "
                                 "every prompt needs at least one real "
                                 "token (all-masked softmax is NaN)")
            mask = jnp.asarray(mask, jnp.int32)
        else:
            mask = None
        # --- prompt-length bucketing -----------------------------------
        # A ragged prompt length retraces the whole prefill+decode program
        # (seconds of silent stall per NEW length). Left-pad to the next
        # power-of-two bucket instead: ≤ log2(context) compiled programs
        # ever, and the existing left-pad masking/position-rebase makes
        # the padded call token-identical to the unpadded one. The pad
        # columns are stripped from the returned ids.
        t_pad = 0
        if self.config.bucket_prompts:
            cap = limit - int(max_new_tokens) if limit is not None else None
            bucket = bucket_length(t0, cap=cap)
            t_pad = bucket - t0
            if mask is None:
                # Always run the masked path when bucketing: a mask that
                # appears only for non-power-of-two lengths would split
                # each bucket into two jit signatures.
                mask = jnp.ones((b, t0), jnp.int32)
            if t_pad:
                ids = jnp.pad(ids, ((0, 0), (t_pad, 0)))
                mask = jnp.pad(mask, ((0, 0), (t_pad, 0)))
        if seed is None:
            # Unseeded sampled calls draw fresh samples each time (counter-
            # mixed); greedy decoding ignores the PRNG so the counter only
            # advances for sampled calls. seed=N reproduces the N-th
            # unseeded sampled call byte-for-byte.
            seed = self._generate_calls
            if temperature > 0.0:
                self._generate_calls += 1
        self.recompile_detector.check(
            "inference.generate", ids, mask,
            {"static": f"max_new_tokens={int(max_new_tokens)},"
                       f"temperature={float(temperature)},"
                       f"top_k={int(top_k)}"})
        key = (b, int(ids.shape[1]), int(max_new_tokens),
               float(temperature), int(top_k), mask is not None)
        if key not in self._generate_jit:
            self._generate_jit[key] = jax.jit(functools.partial(
                self._generate_impl, max_new_tokens=int(max_new_tokens),
                temperature=float(temperature), top_k=int(top_k)))
        with self.tracer.span("generate", prompt_len=t0,
                              bucket=int(ids.shape[1]),
                              new_tokens=int(max_new_tokens)):
            out = self._generate_jit[key](self.params, ids, mask,
                                          jax.random.PRNGKey(seed))
        # Strip the bucket's left-pad columns: callers see [B, T0 + new].
        return out[:, t_pad:] if t_pad else out

    def _sample(self, logits, rng, temperature, top_k):
        return sample_logits(logits, rng, temperature, top_k)

    def _generate_impl(self, params, ids, mask, rng, *, max_new_tokens,
                       temperature, top_k):
        from deepspeed_tpu.models.gpt import init_kv_cache

        cfg = self.model_cfg
        b, t0 = ids.shape
        max_len = t0 + max_new_tokens
        cache = init_kv_cache(cfg, b, max_len, dtype=self.config.dtype)

        # Left-padded prompts: one fixed [B, max_len] key-validity mask
        # (pad slots never visible, generated slots always are) and per-row
        # re-based position ids.
        if mask is not None:
            n_pads = (t0 - jnp.sum(mask, axis=1)).astype(jnp.int32)  # [B]
            km = jnp.concatenate(
                [mask, jnp.ones((b, max_new_tokens), jnp.int32)], axis=1)
            prefill = {"input_ids": ids, "attention_mask": km,
                       "position_ids": jnp.clip(
                           jnp.arange(t0)[None] - n_pads[:, None], 0)}
        else:
            n_pads = None
            km = None
            prefill = {"input_ids": ids}

        # Dequant happens inside each traced body (not hoisted out of the
        # scan) so XLA fuses it into the consumer matmuls and no dense copy
        # of the whole quantized model stays live across the decode loop.
        out = self.module.apply({"params": self._materialized(params)},
                                prefill, deterministic=True, cache=cache,
                                pos=0)
        rng, sub = jax.random.split(rng)
        nxt = self._sample(out["logits"][:, -1].astype(jnp.float32), sub,
                           temperature, top_k)

        def step(carry, _):
            tok, cache, pos, rng = carry
            batch = {"input_ids": tok[:, None]}
            if km is not None:
                batch["attention_mask"] = km
                batch["position_ids"] = jnp.clip(
                    pos - n_pads, 0)[:, None]
            out = self.module.apply({"params": self._materialized(params)},
                                    batch, deterministic=True, cache=cache,
                                    pos=pos)
            rng, sub = jax.random.split(rng)
            nxt = self._sample(out["logits"][:, -1].astype(jnp.float32), sub,
                               temperature, top_k)
            return (nxt, out["cache"], pos + 1, rng), nxt

        if max_new_tokens > 1:
            (_, _, _, _), toks = jax.lax.scan(
                step, (nxt, out["cache"], t0, rng), None,
                length=max_new_tokens - 1)
            gen = jnp.concatenate([nxt[:, None], toks.T], axis=1)
        else:
            gen = nxt[:, None]
        return jnp.concatenate([ids, gen], axis=1)
