"""Config keys and defaults.

JSON key names deliberately match the reference (``deepspeed/runtime/constants.py``)
so that existing DeepSpeed config files parse unchanged; defaults are TPU-first
(bf16 preferred over fp16, no loss scaling needed for bf16).
"""

#############################################
# Batch size triple (reference constants.py)
#############################################
TRAIN_BATCH_SIZE = "train_batch_size"
TRAIN_MICRO_BATCH_SIZE_PER_GPU = "train_micro_batch_size_per_gpu"
# TPU-native alias accepted everywhere the reference key is.
TRAIN_MICRO_BATCH_SIZE_PER_CHIP = "train_micro_batch_size_per_chip"
GRADIENT_ACCUMULATION_STEPS = "gradient_accumulation_steps"

#############################################
# Optimizer / scheduler blocks
#############################################
OPTIMIZER = "optimizer"
OPTIMIZER_TYPE = "type"
OPTIMIZER_PARAMS = "params"
OPTIMIZER_TYPE_DEFAULT = None
# Fused blockwise Adam(W) update (ops/adam/fused_update.py): one Pallas
# pass over master + grad + moments per flat block instead of XLA's
# elementwise chain. Opt-in; requires a device-resident FusedAdam(W).
OPTIMIZER_FUSED_UPDATE = "fused_update"
OPTIMIZER_FUSED_UPDATE_DEFAULT = False
MAX_GRAD_NORM = "max_grad_norm"

SCHEDULER = "scheduler"
SCHEDULER_TYPE = "type"
SCHEDULER_PARAMS = "params"

# Optimizer names understood by the engine (reference engine.py:746-835).
ADAM_OPTIMIZER = "adam"
ADAMW_OPTIMIZER = "adamw"
LAMB_OPTIMIZER = "lamb"
ONEBIT_ADAM_OPTIMIZER = "onebitadam"
ONEBIT_LAMB_OPTIMIZER = "onebitlamb"
CPU_ADAM_OPTIMIZER = "cpuadam"  # host-offloaded update path
SGD_OPTIMIZER = "sgd"
DEEPSPEED_OPTIMIZERS = [
    ADAM_OPTIMIZER, ADAMW_OPTIMIZER, LAMB_OPTIMIZER, ONEBIT_ADAM_OPTIMIZER,
    ONEBIT_LAMB_OPTIMIZER, CPU_ADAM_OPTIMIZER, SGD_OPTIMIZER,
]

#############################################
# Precision (fp16 block kept for config parity; bf16 is TPU-native default)
#############################################
FP16 = "fp16"
FP16_ENABLED = "enabled"
FP16_LOSS_SCALE = "loss_scale"
FP16_INITIAL_SCALE_POWER = "initial_scale_power"
FP16_INITIAL_SCALE_POWER_DEFAULT = 32
FP16_LOSS_SCALE_WINDOW = "loss_scale_window"
FP16_LOSS_SCALE_WINDOW_DEFAULT = 1000
FP16_HYSTERESIS = "hysteresis"
FP16_HYSTERESIS_DEFAULT = 2
FP16_MIN_LOSS_SCALE = "min_loss_scale"
FP16_MIN_LOSS_SCALE_DEFAULT = 1.0

BF16 = "bf16"  # TPU-native block: {"enabled": true}
BFLOAT16 = "bfloat16"  # accepted alias
BF16_ENABLED = "enabled"

AMP = "amp"
AMP_ENABLED = "enabled"

GRADIENT_CLIPPING = "gradient_clipping"
GRADIENT_CLIPPING_DEFAULT = 0.0

PRESCALE_GRADIENTS = "prescale_gradients"
PRESCALE_GRADIENTS_DEFAULT = False
GRADIENT_PREDIVIDE_FACTOR = "gradient_predivide_factor"
GRADIENT_PREDIVIDE_FACTOR_DEFAULT = 1.0

#############################################
# Sparse gradients (embedding grads as COO/CSR — reference csr_tensor.py)
#############################################
SPARSE_GRADIENTS = "sparse_gradients"
SPARSE_GRADIENTS_DEFAULT = False

#############################################
# Resilience (TPU-native block, no reference analogue: preemption-aware
# async checkpointing + fault injection + auto-resume, resilience/)
#############################################
RESILIENCE = "resilience"
RESILIENCE_ENABLED = "enabled"
RESILIENCE_CHECKPOINT = "checkpoint"
RESILIENCE_CKPT_DIR = "dir"
RESILIENCE_CKPT_INTERVAL = "interval"
RESILIENCE_CKPT_INTERVAL_DEFAULT = 100
RESILIENCE_CKPT_KEEP_LAST = "keep_last"
RESILIENCE_CKPT_KEEP_LAST_DEFAULT = 3
RESILIENCE_CKPT_MAX_RETRIES = "max_retries"
RESILIENCE_CKPT_MAX_RETRIES_DEFAULT = 3
RESILIENCE_CKPT_BACKOFF = "backoff_seconds"
RESILIENCE_CKPT_BACKOFF_DEFAULT = 0.5
RESILIENCE_CKPT_ASYNC = "async"
RESILIENCE_CKPT_ASYNC_DEFAULT = True
RESILIENCE_AUTO_RESUME = "auto_resume"
RESILIENCE_AUTO_RESUME_DEFAULT = True
RESILIENCE_FAULT_INJECTION = "fault_injection"

#############################################
# Guardrails (TPU-native block, no reference analogue beyond the fp16
# CheckOverflow path: anomaly detection + in-memory rollback + step
# watchdog, guardrails/; docs/RESILIENCE.md "Guardrails")
#############################################
GUARDRAILS = "guardrails"
GUARDRAILS_ENABLED = "enabled"
GUARDRAILS_DETECTOR = "detector"
GUARDRAILS_DET_ZSCORE = "zscore_threshold"
GUARDRAILS_DET_ZSCORE_DEFAULT = 6.0
GUARDRAILS_DET_WARMUP = "warmup_steps"
GUARDRAILS_DET_WARMUP_DEFAULT = 20
GUARDRAILS_DET_EWMA_ALPHA = "ewma_alpha"
GUARDRAILS_DET_EWMA_ALPHA_DEFAULT = 0.02
GUARDRAILS_DET_TRACK_GRAD_NORM = "track_grad_norm"
GUARDRAILS_DET_TRACK_GRAD_NORM_DEFAULT = True
GUARDRAILS_DET_NONFINITE_GRADS = "check_nonfinite_grads"
GUARDRAILS_DET_NONFINITE_GRADS_DEFAULT = False
GUARDRAILS_ROLLBACK = "rollback"
GUARDRAILS_RB_ENABLED = "enabled"
GUARDRAILS_RB_ENABLED_DEFAULT = True
GUARDRAILS_RB_SNAPSHOT_INTERVAL = "snapshot_interval"
GUARDRAILS_RB_SNAPSHOT_INTERVAL_DEFAULT = 10
GUARDRAILS_RB_RING_SIZE = "ring_size"
GUARDRAILS_RB_RING_SIZE_DEFAULT = 2
GUARDRAILS_RB_CONSECUTIVE_SPIKES = "consecutive_spikes"
GUARDRAILS_RB_CONSECUTIVE_SPIKES_DEFAULT = 2
GUARDRAILS_RB_SKIP_BATCHES = "skip_batches"
GUARDRAILS_RB_SKIP_BATCHES_DEFAULT = 2
GUARDRAILS_RB_LR_DECAY = "lr_decay"
GUARDRAILS_RB_LR_DECAY_DEFAULT = 1.0
GUARDRAILS_RB_MAX_ROLLBACKS = "max_rollbacks"
GUARDRAILS_RB_MAX_ROLLBACKS_DEFAULT = 3
GUARDRAILS_RB_ESCALATE = "escalate_to_disk"
GUARDRAILS_RB_ESCALATE_DEFAULT = True
GUARDRAILS_WATCHDOG = "watchdog"
GUARDRAILS_WD_ENABLED = "enabled"
GUARDRAILS_WD_ENABLED_DEFAULT = False
GUARDRAILS_WD_TIMEOUT = "step_timeout_seconds"
GUARDRAILS_WD_TIMEOUT_DEFAULT = 1800.0
GUARDRAILS_WD_POLL = "poll_interval_seconds"
GUARDRAILS_WD_CRASHDUMP_DIR = "crashdump_dir"
GUARDRAILS_WD_CRASHDUMP_DIR_DEFAULT = "crashdumps"
GUARDRAILS_WD_EXIT_CODE = "exit_code"
# Distinct from everything the runtime otherwise produces (1 generic, 2
# pytest/usage, 137/139/143 signal deaths): the supervisor maps THIS rc to
# an immediate no-backoff restart — a hang already burned its budget.
GUARDRAILS_WATCHDOG_EXIT_CODE_DEFAULT = 113

#############################################
# Telemetry (TPU-native block, no reference analogue: unified metrics
# registry + step tracer + recompilation detector, telemetry/)
#############################################
TELEMETRY = "telemetry"
TELEMETRY_ENABLED = "enabled"
TELEMETRY_DIR = "dir"
TELEMETRY_DIR_DEFAULT = "telemetry"
TELEMETRY_TRACE = "trace"
TELEMETRY_TRACE_ENABLED = "enabled"
TELEMETRY_TRACE_ENABLED_DEFAULT = True
TELEMETRY_TRACE_FILE = "file"
TELEMETRY_TRACE_FILE_DEFAULT = "trace.json"
TELEMETRY_TRACE_SYNC_SPANS = "sync_spans"
TELEMETRY_TRACE_SYNC_SPANS_DEFAULT = True
TELEMETRY_TRACE_JAX_PROFILER_DIR = "jax_profiler_dir"
TELEMETRY_METRICS = "metrics"
TELEMETRY_METRICS_SINKS = "sinks"
TELEMETRY_METRICS_SINKS_DEFAULT = ("jsonl",)
TELEMETRY_METRICS_VALID_SINKS = ("jsonl", "tensorboard", "memory")
TELEMETRY_METRICS_FILE = "file"
TELEMETRY_METRICS_FILE_DEFAULT = "metrics.jsonl"
TELEMETRY_RECOMPILE = "recompile_detection"
TELEMETRY_RECOMPILE_DEFAULT = True
# Goodput accounting (telemetry/goodput.py): run-level wall-clock
# attribution + MFU + per-attempt run manifests. Rides the telemetry
# block; default ON when telemetry is enabled (it adds zero device syncs
# — pure host clock reads).
TELEMETRY_GOODPUT = "goodput"
TELEMETRY_GOODPUT_DEFAULT = True
# Fleet observability (telemetry/fleet.py): cross-host metric aggregation
# at flush boundaries (a tiny jitted all-gather OFF the step path) +
# rolling-window straggler detection. Default OFF: enabled it adds one
# collective + one host fetch per flush, which the zero-overhead contract
# reserves for explicit opt-in.
TELEMETRY_FLEET = "fleet"
TELEMETRY_FLEET_ENABLED = "enabled"
TELEMETRY_FLEET_ENABLED_DEFAULT = False
TELEMETRY_FLEET_WINDOW = "window"
TELEMETRY_FLEET_WINDOW_DEFAULT = 8            # flushes in the z-score window
TELEMETRY_FLEET_MIN_WINDOW = "min_window"
TELEMETRY_FLEET_MIN_WINDOW_DEFAULT = 3        # flushes before verdicts fire
TELEMETRY_FLEET_ZSCORE = "zscore"
TELEMETRY_FLEET_ZSCORE_DEFAULT = 3.0
TELEMETRY_FLEET_PERSIST = "persist"
TELEMETRY_FLEET_PERSIST_DEFAULT = 3           # verdicts until "persistent"
TELEMETRY_FLEET_BREAKDOWN_FILE = "breakdown_file"
TELEMETRY_FLEET_BREAKDOWN_FILE_DEFAULT = "fleet_breakdown.json"
# Memory observatory (telemetry/memory.py): XLA memory attribution +
# model-state ledger + capacity planner + OOM forensics. Default OFF:
# enabled it adds one AOT lower+compile per step function (attribution)
# and per-step headroom gauges — reserved for explicit opt-in like fleet.
TELEMETRY_MEMORY = "memory"
TELEMETRY_MEMORY_ENABLED = "enabled"
TELEMETRY_MEMORY_ENABLED_DEFAULT = False
TELEMETRY_MEMORY_HEADROOM_WARN_FRAC = "headroom_warn_frac"
TELEMETRY_MEMORY_HEADROOM_WARN_FRAC_DEFAULT = 0.1   # warn below 10% of HBM
TELEMETRY_MEMORY_CRASHDUMP_DIR = "crashdump_dir"
TELEMETRY_MEMORY_CRASHDUMP_DIR_DEFAULT = "crashdumps"
TELEMETRY_MEMORY_OOM_EXIT_CODE = "oom_exit_code"
TELEMETRY_MEMORY_PLAN_AT_INIT = "plan_at_init"
TELEMETRY_MEMORY_PLAN_AT_INIT_DEFAULT = True
TELEMETRY_MEMORY_PLAN_FILE = "plan_file"
TELEMETRY_MEMORY_PLAN_FILE_DEFAULT = "memory_plan.json"
TELEMETRY_MEMORY_ACT_BYTES = "activation_bytes_per_sample"
TELEMETRY_MEMORY_ACT_BYTES_DEFAULT = 0.0
TELEMETRY_MEMORY_HBM_LIMIT_GB = "hbm_limit_gb"
# Distinct from rc 113 (watchdog: immediate restart) by design: the
# supervisor maps THIS rc to cause=oom and does NOT restart at all — a
# deterministic OOM is a config bug, and a hot restart loop would just
# re-OOM until the budget is gone.
MEMORY_OOM_EXIT_CODE_DEFAULT = 114
# Device-time observatory (telemetry/devicetime.py): scheduled
# jax.profiler captures parsed into measured op-level attribution,
# roofline classification and measured exposed-comm. Default OFF:
# enabled it adds profiler start/stop + one device drain + a parse at
# capture boundaries (never on the in-between step path) — explicit
# opt-in like fleet/memory.
TELEMETRY_DEVICETIME = "devicetime"
TELEMETRY_DEVICETIME_ENABLED = "enabled"
TELEMETRY_DEVICETIME_ENABLED_DEFAULT = False
TELEMETRY_DEVICETIME_CAPTURE_STEPS = "capture_steps"
TELEMETRY_DEVICETIME_CAPTURE_STEPS_DEFAULT = 3    # steps per capture
TELEMETRY_DEVICETIME_EVERY_STEPS = "every_steps"
TELEMETRY_DEVICETIME_EVERY_STEPS_DEFAULT = 200    # capture cadence
TELEMETRY_DEVICETIME_KEEP_LAST = "keep_last"
TELEMETRY_DEVICETIME_KEEP_LAST_DEFAULT = 2        # capture-dir GC
TELEMETRY_DEVICETIME_DIR = "dir"
TELEMETRY_DEVICETIME_DIR_DEFAULT = "devicetime"   # under telemetry.dir
TELEMETRY_DEVICETIME_TOP_K = "top_k"
TELEMETRY_DEVICETIME_TOP_K_DEFAULT = 10           # hottest-op table rows
TELEMETRY_DEVICETIME_DIVERGENCE_WARN = "divergence_warn"
TELEMETRY_DEVICETIME_DIVERGENCE_WARN_DEFAULT = 0.25  # |measured-modeled|
TELEMETRY_DEVICETIME_HBM_GBPS = "hbm_gbps"        # None -> per-kind table
# Numerics observatory (telemetry/numerics.py): per-layer-group
# gradient/update statistics + dtype-saturation counters computed INSIDE
# the jitted step (one small stacked aux array, fetched once per flush),
# and quantization-error attribution for the int8 wire paths. Default
# OFF: enabled it adds the in-program stat reductions to the step
# program (the lowered step changes — explicit opt-in, unlike the
# jaxpr-neutral memory observatory) and one host transfer per flush.
TELEMETRY_NUMERICS = "numerics"
TELEMETRY_NUMERICS_ENABLED = "enabled"
TELEMETRY_NUMERICS_ENABLED_DEFAULT = False
TELEMETRY_NUMERICS_MAX_GROUPS = "max_groups"
TELEMETRY_NUMERICS_MAX_GROUPS_DEFAULT = 16        # top-level key cap
TELEMETRY_NUMERICS_MAX_SPIKE_DUMPS = "max_spike_dumps"
TELEMETRY_NUMERICS_MAX_SPIKE_DUMPS_DEFAULT = 8    # per-run dump budget
# Request observatory (telemetry/requests.py): per-request SLO
# accounting for the serve engine — exact lifetime partition, TPOT/e2e
# histograms, host-scoped requests.<host>.jsonl records, an engine-side
# serving-time partition, and the rolling decode-throughput window.
# Default OFF: enabled it adds host float arithmetic per step (no device
# syncs) plus one JSONL append per finished request — explicit opt-in
# like fleet/memory, and the off state keeps the engine's emitted tag
# set byte-identical.
TELEMETRY_REQUESTS = "requests"
TELEMETRY_REQUESTS_ENABLED = "enabled"
TELEMETRY_REQUESTS_ENABLED_DEFAULT = False
TELEMETRY_REQUESTS_FILE = "file"
TELEMETRY_REQUESTS_FILE_DEFAULT = "requests.jsonl"
TELEMETRY_REQUESTS_WINDOW_SEC = "window_sec"
TELEMETRY_REQUESTS_WINDOW_SEC_DEFAULT = 10.0  # rolling-throughput window

#############################################
# Serving (TPU-native block, no reference analogue: continuous-batching
# serving engine over the inference stack — serving/; docs/SERVING.md)
#############################################
SERVING = "serving"
SERVING_MAX_BATCH_SIZE = "max_batch_size"
SERVING_MAX_BATCH_SIZE_DEFAULT = 8            # decode slots
SERVING_KV_BLOCK_SIZE = "kv_block_size"
SERVING_KV_BLOCK_SIZE_DEFAULT = 16            # cache positions per block
SERVING_KV_NUM_BLOCKS = "kv_num_blocks"
SERVING_KV_NUM_BLOCKS_DEFAULT = 256           # pool size (block 0 = scratch)
SERVING_INT8_KV_CACHE = "int8_kv_cache"
SERVING_INT8_KV_CACHE_DEFAULT = False         # blockwise-int8 KV pools
SERVING_MAX_MODEL_LEN = "max_model_len"       # None -> model max_seq_len
SERVING_MAX_PREFILLS_PER_STEP = "max_prefills_per_step"
SERVING_MAX_PREFILLS_PER_STEP_DEFAULT = 1     # prefill/decode interleave cap
SERVING_EOS_TOKEN_ID = "eos_token_id"         # None -> length-only stopping
SERVING_TEMPERATURE = "temperature"
SERVING_TEMPERATURE_DEFAULT = 0.0             # greedy
SERVING_TOP_K = "top_k"
SERVING_TOP_K_DEFAULT = 0
SERVING_SEED = "seed"
SERVING_SEED_DEFAULT = 0
# decode fast path (docs/SERVING.md "Decode fast path"): "gather" is
# ONE decode program over the flat list of live blocks; "auto" runs the
# Pallas paged decode-attention kernel on a TPU where the geometry tiles
# and that same "gather" program elsewhere; "kernel" forces the kernel
# (Pallas interpreter off-TPU — the parity/bench path).
SERVING_DECODE_ATTENTION = "decode_attention"
SERVING_DECODE_ATTENTION_DEFAULT = "gather"
SERVING_DECODE_ATTENTION_CHOICES = ("gather", "auto", "kernel")
# prefix-cache reuse: ref-counted prompt-head trie over KV blocks —
# warm heads skip the shared portion of prefill (COW adoption).
SERVING_PREFIX_CACHE = "prefix_cache"
SERVING_PREFIX_CACHE_DEFAULT = False
# speculative decoding sub-block
SERVING_SPECULATIVE = "speculative"
SERVING_SPEC_ENABLED = "enabled"
SERVING_SPEC_ENABLED_DEFAULT = False
SERVING_SPEC_K = "k"                      # draft tokens proposed per round
SERVING_SPEC_K_DEFAULT = 4
SERVING_SPEC_DRAFT_LAYERS = "draft_layers"  # None -> num_layers // 2
# serving resilience sub-block (serving/resilience.py; docs/SERVING.md
# "Serving under failure"): deadlines + cancellation, SLO-aware load
# shedding, in-flight recovery + degradation ladder — off by default
# under the established zero-overhead contract.
SERVING_RESILIENCE = "resilience"
SERVING_RESIL_ENABLED = "enabled"
SERVING_RESIL_ENABLED_DEFAULT = False
SERVING_RESIL_MAX_QUEUE_DEPTH = "max_queue_depth"      # None -> unbounded
SERVING_RESIL_MAX_QUEUE_WAIT_MS = "max_queue_wait_ms"  # None -> no wait gate
SERVING_RESIL_DEFAULT_DEADLINE_MS = "default_deadline_ms"  # None -> none
SERVING_RESIL_MAX_RETRIES = "max_retries"  # decode-dispatch retries
SERVING_RESIL_MAX_RETRIES_DEFAULT = 2
SERVING_RESIL_RETRY_BASE_SEC = "retry_base_sec"
SERVING_RESIL_RETRY_BASE_SEC_DEFAULT = 0.05
SERVING_RESIL_DEGRADE_AFTER = "degrade_after"  # anomalies per ladder rung
SERVING_RESIL_DEGRADE_AFTER_DEFAULT = 2
SERVING_RESIL_SLOW_STEP_MS = "slow_step_ms"  # None -> no slow-step anomaly
# chunked-prefill sub-block (ops/transformer/chunked_prefill.py;
# docs/SERVING.md "Chunked prefill admission"): Sarathi-style mixed
# decode + prefill-chunk steps through ONE ragged program — off by
# default under the established zero-overhead contract.
SERVING_CHUNKED_PREFILL = "chunked_prefill"
SERVING_CHUNKED_ENABLED = "enabled"
SERVING_CHUNKED_ENABLED_DEFAULT = False
SERVING_CHUNKED_TOKEN_BUDGET = "token_budget"  # tokens per mixed step
SERVING_CHUNKED_TOKEN_BUDGET_DEFAULT = 64

#############################################
# Logging / misc
#############################################
STEPS_PER_PRINT = "steps_per_print"
STEPS_PER_PRINT_DEFAULT = 10
WALL_CLOCK_BREAKDOWN = "wall_clock_breakdown"
WALL_CLOCK_BREAKDOWN_DEFAULT = False
DUMP_STATE = "dump_state"
DUMP_STATE_DEFAULT = False
CHECK_NUMERICS = "check_numerics"
CHECK_NUMERICS_DEFAULT = False
MEMORY_BREAKDOWN = "memory_breakdown"
MEMORY_BREAKDOWN_DEFAULT = False

TENSORBOARD = "tensorboard"
TENSORBOARD_ENABLED = "enabled"
TENSORBOARD_OUTPUT_PATH = "output_path"
TENSORBOARD_JOB_NAME = "job_name"

#############################################
# ZeRO (full key set in runtime/zero/config.py)
#############################################
ZERO_OPTIMIZATION = "zero_optimization"

#############################################
# Activation checkpointing
#############################################
ACTIVATION_CHECKPOINTING = "activation_checkpointing"
ACT_CHKPT_PARTITION_ACTIVATIONS = "partition_activations"
ACT_CHKPT_NUMBER_CHECKPOINTS = "number_checkpoints"
ACT_CHKPT_CONTIGUOUS_MEMORY_OPTIMIZATION = "contiguous_memory_optimization"
ACT_CHKPT_SYNCHRONIZE_CHECKPOINT_BOUNDARY = "synchronize_checkpoint_boundary"
ACT_CHKPT_PROFILE = "profile"
ACT_CHKPT_CPU_CHECKPOINTING = "cpu_checkpointing"

#############################################
# Pipeline block (reference config.py:409)
#############################################
PIPELINE = "pipeline"
PIPELINE_STAGES = "stages"
PIPELINE_PARTITION = "partition"
PIPELINE_SEED_LAYERS = "seed_layers"
PIPELINE_ACTIVATION_CHECKPOINT_INTERVAL = "activation_checkpoint_interval"

#############################################
# Sparse attention presets (reference config.py:261-407)
#############################################
SPARSE_ATTENTION = "sparse_attention"
SPARSE_MODE = "mode"
SPARSE_DENSE_MODE = "dense"
SPARSE_FIXED_MODE = "fixed"
SPARSE_VARIABLE_MODE = "variable"
SPARSE_BIGBIRD_MODE = "bigbird"
SPARSE_BSLONGFORMER_MODE = "bslongformer"

#############################################
# Flops profiler
#############################################
FLOPS_PROFILER = "flops_profiler"
FLOPS_PROFILER_ENABLED = "enabled"
FLOPS_PROFILER_PROFILE_STEP = "profile_step"
FLOPS_PROFILER_MODULE_DEPTH = "module_depth"
FLOPS_PROFILER_TOP_MODULES = "top_modules"
FLOPS_PROFILER_DETAILED = "detailed"
FLOPS_PROFILER_OUTPUT_FILE = "output_file"

#############################################
# Progressive layer drop / eigenvalue / MoQ
#############################################
PROGRESSIVE_LAYER_DROP = "progressive_layer_drop"
PLD_ENABLED = "enabled"
PLD_THETA = "theta"
PLD_GAMMA = "gamma"

EIGENVALUE = "eigenvalue"
QUANTIZE_TRAINING = "quantize_training"

#############################################
# Elasticity
#############################################
ELASTICITY = "elasticity"
# Live elasticity (resilience/elastic.py; docs/RESILIENCE.md "Live
# elasticity"): in-process shrink on a preemption advance warning,
# step-boundary rejoin, and goodput-driven straggler eviction. Rides the
# elasticity block (`elasticity.live`); default OFF — disabled means no
# signal handlers, zero extra syncs, bit-identical lowered step.
ELASTICITY_LIVE = "live"
ELASTICITY_LIVE_ENABLED = "enabled"
ELASTICITY_LIVE_ENABLED_DEFAULT = False
# Preemption advance-warning grace window: the platform sends SIGTERM
# this many seconds before pulling the slice; the coordinator must drain
# + reshard inside it (GCE preemptible TPUs give 30s; tests use less).
ELASTICITY_LIVE_GRACE = "grace_seconds"
ELASTICITY_LIVE_GRACE_DEFAULT = 30.0
# Step cadence at which the coordinator polls the rejoin rendezvous file
# (one os.path check per poll — rejoin admission happens at the next
# snapshot boundary, not mid-step).
ELASTICITY_LIVE_CHECK_INTERVAL = "check_interval_steps"
ELASTICITY_LIVE_CHECK_INTERVAL_DEFAULT = 10
# Straggler eviction (the PR-6 Supervisor.straggler_hosts loop closed):
# a persistent straggler is evicted only when the goodput cost model says
# projected_gain = straggler_sec_rate x horizon_steps exceeds
# min_gain_factor x measured reshard cost.
ELASTICITY_LIVE_EVICTION = "eviction"
ELASTICITY_LIVE_EVICTION_ENABLED = "enabled"
ELASTICITY_LIVE_EVICTION_ENABLED_DEFAULT = False
ELASTICITY_LIVE_EVICTION_HORIZON = "horizon_steps"
ELASTICITY_LIVE_EVICTION_HORIZON_DEFAULT = 1000
ELASTICITY_LIVE_EVICTION_MIN_GAIN = "min_gain_factor"
ELASTICITY_LIVE_EVICTION_MIN_GAIN_DEFAULT = 2.0
# Reshard cost assumed before the first measured in-process reshard
# (afterwards the measured elastic/reshard_sec wins).
ELASTICITY_LIVE_EVICTION_ASSUMED_RESHARD = "assumed_reshard_sec"
ELASTICITY_LIVE_EVICTION_ASSUMED_RESHARD_DEFAULT = 60.0
# Exit code when the coordinator received the advance warning but could
# not stay up (no surviving capacity / no valid elastic world): the
# supervisor classifies it `preemption_warned` — distinct from rc -15
# (plain preemption: the process died without handling the warning).
# Distinct from 113 (watchdog) and 114 (oom) by design.
ELASTICITY_LIVE_EXIT_CODE = "exit_code"
ELASTIC_PREEMPT_EXIT_CODE_DEFAULT = 115

#############################################
# Offload / async IO
#############################################
AIO = "aio"
AIO_BLOCK_SIZE = "block_size"
AIO_BLOCK_SIZE_DEFAULT = 1048576
AIO_QUEUE_DEPTH = "queue_depth"
AIO_QUEUE_DEPTH_DEFAULT = 8
AIO_THREAD_COUNT = "thread_count"
AIO_THREAD_COUNT_DEFAULT = 1
AIO_SINGLE_SUBMIT = "single_submit"
AIO_SINGLE_SUBMIT_DEFAULT = False
AIO_OVERLAP_EVENTS = "overlap_events"
AIO_OVERLAP_EVENTS_DEFAULT = True

#############################################
# Mesh / parallelism (TPU-native block, no reference analogue:
# the reference takes TP degree from the external mpu object)
#############################################
MESH = "mesh"
MESH_DATA = "data"
MESH_MODEL = "model"
MESH_PIPE = "pipe"
MESH_SEQUENCE = "sequence"
MESH_EXPERT = "expert"
MESH_SLICES = "slices"

#############################################
# Communication / compression
#############################################
COMMUNICATION_DATA_TYPE = "communication_data_type"
COMPRESSED_ALLREDUCE = "compressed_allreduce"

# comm block — hierarchical quantized gradient sync (comm/grad_sync.py):
# bucketed ICI reduce-scatter + blockwise-quantized DCN all-reduce.
COMM = "comm"
COMM_HIERARCHICAL = "hierarchical"
# Default OFF: the implicit pjit path stays bit-identical unless the user
# opts in ("auto" engages on multi-slice meshes, "on" forces).
COMM_HIERARCHICAL_DEFAULT = "off"             # auto | on | off
COMM_DCN_QUANT_BITS = "dcn_quant_bits"
COMM_DCN_QUANT_BITS_DEFAULT = 8               # 8=int8, 16=bf16, 32=fp32
COMM_QUANT_BLOCK_SIZE = "quant_block_size"
COMM_QUANT_BLOCK_SIZE_DEFAULT = 1024
COMM_BUCKET_MB = "bucket_mb"
COMM_BUCKET_MB_DEFAULT = 16.0
# Overlapped gradient sync (docs/PERFORMANCE.md "Overlapped gradient
# sync"): readiness-ordered per-bucket ICI reduce-scatter during
# backward + double-buffered per-microstep DCN all-reduce. "auto"
# (default) engages whenever the hierarchical sync does; "off" keeps
# the PR-4 GAS-boundary schedule.
COMM_OVERLAP_GRAD_SYNC = "overlap_grad_sync"
COMM_OVERLAP_GRAD_SYNC_DEFAULT = "auto"       # auto | on | off
# Nominal per-device link bandwidths behind the modeled device-time
# attribution (comm/exposed_frac): exposed-collective seconds =
# bytes_dcn / dcn + bytes_ici / ici. Defaults approximate a v4-class
# slice (ICI ~90 GB/s per chip) and a 100 Gbit/s DCN NIC per host;
# override per deployment for honest fractions.
COMM_ICI_GBPS = "ici_gbps"
COMM_ICI_GBPS_DEFAULT = 90.0
COMM_DCN_GBPS = "dcn_gbps"
COMM_DCN_GBPS_DEFAULT = 12.5

# ZeRO++ weight path: zero_optimization.zeropp — runtime/zero/config.py
# ZeroPPConfig owns the keys/defaults (they live beside the other
# zero_optimization key constants); the param-hop comm gauge names are
# declared in comm/grad_sync.py COMM_PARAM_METRIC_TAGS, doc-lint-pinned.

#############################################
# Autotuning (autotuning/; docs/PERFORMANCE.md "Autotuning"): startup
# config search — enumerate the knob space, prune against the ConfigError
# walls and the capacity projection, rank survivors with the modeled cost
# model, measure the top-K with short in-process trials, adopt the
# fastest. Default OFF: no autotuning import at engine init, zero extra
# syncs, bit-identical lowered step (tests/test_autotuning.py pins it).
#############################################
AUTOTUNING = "autotuning"
AUTOTUNING_ENABLED = "enabled"
AUTOTUNING_ENABLED_DEFAULT = False
# Launcher handshake: `dstpu --autotune ...` exports this env for every
# child so unmodified training scripts pick the search up through their
# config parse (the script still owes the tuner a batch source — see
# deepspeed_tpu.autotune / initialize(autotune_batches=...)).
AUTOTUNING_ENV = "DSTPU_AUTOTUNE"
# Knob-space overrides. Empty tuples mean "derive from the runtime
# shape": stages 0-3, the elastic ladder's (micro, gas) splits (divisor
# re-splits of the configured product when elasticity is off), and the
# comm/zeropp axes only where the mesh gives them meaning (dcn > 1).
AUTOTUNING_ZERO_STAGES = "zero_stages"
AUTOTUNING_MICRO_GAS = "micro_gas"               # [[micro, gas], ...]
AUTOTUNING_BUCKET_MBS = "bucket_mbs"
AUTOTUNING_DCN_QUANT_BITS = "dcn_quant_bits"
AUTOTUNING_OVERLAP = "overlap"                   # overlap_grad_sync values
AUTOTUNING_ZEROPP = "zeropp"                     # "off" | "bf16" | "int8"
# Measured-trial knobs: top_k survivors get compile + trial_steps timed
# steps; successive halving drops candidates slower than
# halving_factor x the round's best before the confirmation round.
AUTOTUNING_TOP_K = "top_k"
AUTOTUNING_TOP_K_DEFAULT = 3
AUTOTUNING_TRIAL_STEPS = "trial_steps"
AUTOTUNING_TRIAL_STEPS_DEFAULT = 3
AUTOTUNING_TRIAL_WARMUP = "trial_warmup"
AUTOTUNING_TRIAL_WARMUP_DEFAULT = 1
AUTOTUNING_HALVING_FACTOR = "halving_factor"
AUTOTUNING_HALVING_FACTOR_DEFAULT = 1.5
# Capacity wall: a candidate whose projected device bytes exceed
# headroom_frac x the HBM limit is pruned before any trial (the
# projection is telemetry/memory.py plan_capacity, engine-free).
AUTOTUNING_HEADROOM_FRAC = "headroom_frac"
AUTOTUNING_HEADROOM_FRAC_DEFAULT = 0.9
AUTOTUNING_ACT_BYTES = "activation_bytes_per_sample"
AUTOTUNING_ACT_BYTES_DEFAULT = 0.0
AUTOTUNING_HBM_LIMIT_GB = "hbm_limit_gb"         # None -> device limit
AUTOTUNING_MAX_CANDIDATES = "max_candidates"
AUTOTUNING_MAX_CANDIDATES_DEFAULT = 64
AUTOTUNING_RESULT_FILE = "result_file"
AUTOTUNING_RESULT_FILE_DEFAULT = "autotune_result.json"
# MoE axes (active only when the moe block is enabled; collapsed with a
# note otherwise). capacity_factor and dispatch are trial-safe —
# lowering-only changes. num_experts re-shapes the expert params, so
# candidates that change it are enumerated (the config-parse walls prune
# invalid counts for free) but never measured in-process: the trial
# rebuild reinstalls the pre-search parameter snapshot, which an
# expert-count change cannot fit.
AUTOTUNING_MOE_EXPERTS = "moe_experts"
AUTOTUNING_MOE_CAPACITY_FACTORS = "moe_capacity_factors"
AUTOTUNING_MOE_DISPATCH = "moe_dispatch"

#############################################
# MoE / expert parallelism (moe/; docs/MOE.md): the GShard-style MoE FFN
# swap for the in-tree GPT family plus the explicit all-to-all dispatch
# path. Default ABSENT: no moe block => initialize() performs no model
# surgery and the lowered train step is bit-identical (tests/test_moe.py
# pins it). The moe/* gauge names are declared in telemetry/moe.py
# MOE_METRIC_TAGS, doc-lint-pinned like numerics/goodput.
#############################################
MOE = "moe"
MOE_ENABLED = "enabled"
MOE_ENABLED_DEFAULT = False
MOE_NUM_EXPERTS = "num_experts"
MOE_NUM_EXPERTS_DEFAULT = 8
MOE_TOP_K = "k"                                # top-k routing (1 or 2)
MOE_TOP_K_DEFAULT = 1
MOE_LAYER_FREQ = "layer_freq"                  # every Nth block is MoE
MOE_LAYER_FREQ_DEFAULT = 2
MOE_CAPACITY_FACTOR = "capacity_factor"
MOE_CAPACITY_FACTOR_DEFAULT = 1.25
MOE_EVAL_CAPACITY_FACTOR = "eval_capacity_factor"
MOE_EVAL_CAPACITY_FACTOR_DEFAULT = 2.0
MOE_MIN_CAPACITY = "min_capacity"
MOE_MIN_CAPACITY_DEFAULT = 4
MOE_AUX_ALPHA = "aux_alpha"                    # load-balance loss scale
MOE_AUX_ALPHA_DEFAULT = 0.01
MOE_ROUTER_JITTER = "router_jitter"            # train-only input jitter
MOE_ROUTER_JITTER_DEFAULT = 0.0
MOE_DISPATCH = "dispatch"
MOE_DISPATCH_DEFAULT = "scatter"
MOE_DISPATCH_CHOICES = ("einsum", "scatter", "alltoall")
