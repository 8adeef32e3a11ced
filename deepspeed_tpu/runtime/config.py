"""JSON config system.

TPU-native analogue of the reference's ``runtime/config.py``
(``DeepSpeedConfig``, reference runtime/config.py:686) and per-feature config
models (e.g. ``runtime/zero/config.py:81``). The JSON surface keeps the
reference's key names (train_batch_size / zero_optimization / fp16 / bf16 /
optimizer / scheduler / pipeline / ...) so configs are drop-in recognizable,
while the semantics target a JAX device mesh: the data-parallel degree is
``total_devices // (tp * pp * sp)`` rather than a torch.distributed world size.
"""

import json
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Union

from .config_utils import AUTO, ConfigError, as_dict, hydrate, subconfig

TRAIN_BATCH_SIZE = "train_batch_size"
TRAIN_MICRO_BATCH_SIZE_PER_GPU = "train_micro_batch_size_per_gpu"
GRADIENT_ACCUMULATION_STEPS = "gradient_accumulation_steps"


@dataclass
class FP16Config:
    """Reference: runtime/fp16 loss-scaling config block."""

    enabled: bool = False
    auto_cast: bool = False
    loss_scale: float = 0.0  # 0 => dynamic
    initial_scale_power: int = 16
    loss_scale_window: int = 1000
    hysteresis: int = 2
    consecutive_hysteresis: bool = False
    min_loss_scale: float = 1.0


@dataclass
class BF16Config:
    enabled: bool = False


@dataclass
class OffloadConfig:
    """Reference: runtime/zero/offload_config.py (device: cpu|nvme).

    ``pin_memory`` on ``offload_optimizer`` with ``device: cpu`` selects
    the TIERED offload path (runtime/offload.py): optimizer state in
    host memory (``pinned_host`` where the runtime supports it), update
    streamed bucket-by-bucket at ``stage3_prefetch_bucket_size``
    granularity with ``buffer_count`` fetches in flight. Without it,
    ``device: cpu`` keeps the legacy host C++ optimizer
    (runtime/zero/offload.py)."""

    device: str = "none"
    nvme_path: Optional[str] = None
    pin_memory: bool = False
    buffer_count: int = 4
    buffer_size: int = 100_000_000
    pipeline_read: bool = False
    pipeline_write: bool = False
    fast_init: bool = False
    ratio: float = 1.0

    def __post_init__(self):
        if self.device not in ("none", "cpu", "nvme"):
            # the engine used to reject unknown devices only at init —
            # a config load is the cheapest place to fail
            raise ConfigError(
                f"offload device must be 'cpu' or 'nvme' (or 'none'), "
                f"got {self.device!r}")
        if self.device == "nvme" and not self.nvme_path:
            raise ConfigError(
                "offload device 'nvme' requires nvme_path")
        # buffer-count style knobs are CONSUMED (tiered prefetch depth,
        # AIO buffer sizing) — nonsense must fail at load, like the
        # bucket-size checks below (a buffer_count of 0 would silently
        # serialize every fetch; a negative size would wrap a malloc)
        if self.buffer_count < 1:
            raise ConfigError(
                f"offload buffer_count must be >= 1, got "
                f"{self.buffer_count}")
        if self.buffer_size <= 0:
            raise ConfigError(
                f"offload buffer_size must be > 0, got "
                f"{self.buffer_size}")
        if not 0.0 < self.ratio <= 1.0:
            raise ConfigError(
                f"offload ratio must be in (0, 1], got {self.ratio}")


@dataclass
class ZeroConfig:
    """Reference: runtime/zero/config.py:81 DeepSpeedZeroConfig."""

    stage: int = 0
    contiguous_gradients: bool = True
    reduce_scatter: bool = True
    # bucket caps are ELEMENT counts (reference zero/config.py semantics),
    # consumed by runtime/grad_overlap.py: reduce_bucket_size caps
    # reduce-scatter buckets; min(reduce_bucket_size, allgather_bucket_size)
    # caps all-reduce buckets (reduce + implicit allgather of the result)
    reduce_bucket_size: int = 500_000_000
    allgather_partitions: bool = True
    allgather_bucket_size: int = 500_000_000
    overlap_comm: bool = True
    # bucketed grad-reduction program (runtime/grad_overlap.py):
    #   "auto"     engage on pure data-parallel meshes with dp > 1
    #   "bucketed" force it (unsupported compositions raise)
    #   "off"      legacy GSPMD-inserted monolithic reduction
    overlap_grad_reduce: str = "auto"
    offload_optimizer: OffloadConfig = subconfig(OffloadConfig)
    offload_param: OffloadConfig = subconfig(OffloadConfig)
    sub_group_size: int = 1_000_000_000
    stage3_max_live_parameters: int = 1_000_000_000
    stage3_max_reuse_distance: int = 1_000_000_000
    stage3_prefetch_bucket_size: int = 50_000_000
    stage3_param_persistence_threshold: int = 100_000
    stage3_gather_16bit_weights_on_model_save: bool = False
    ignore_unused_parameters: bool = True
    round_robin_gradients: bool = False
    # ZeRO++ knobs (reference zero/config.py:256-272)
    zero_hpz_partition_size: int = 1
    zero_quantized_weights: bool = False
    zero_quantized_gradients: bool = False
    # block-quantized ring gradient reduction (EQuARX, arXiv:2506.17615;
    # runtime/grad_overlap.py): every hop of the bucketed ppermute-ring
    # reduce ships int8/fp8 + per-block fp32 scales instead of fp32
    # (~4x fewer collective bytes), with per-bucket ERROR FEEDBACK
    # residuals carried across steps so transport error does not bias
    # convergence. Stages 0-2 (stage-3 grads reduce inside the gather
    # VJP); forces the bucketed overlap program; mutually exclusive with
    # zero_quantized_gradients (qgZ already quantizes those buckets).
    quantized_reduce: str = "off"   # off | int8 | fp8
    quant_block: int = 2048         # elements per wire-quantization block
    # two-level (EQuARX multi-pod) shape for quantized_reduce: the
    # number of HOSTS the dp ring spans — intra-host legs stay fp32,
    # only inter-host legs ride the quantized wire
    # (comm/quantized.ring_*_hier). 0/1 = flat single-level ring; must
    # divide the dp world (validated where the mesh is known).
    quantized_reduce_hierarchy: int = 0
    # MiCS-style shard group (reference runtime/zero/mics.py)
    mics_shard_size: int = -1
    mics_hierarchical_params_gather: bool = False

    def __post_init__(self):
        if self.stage not in (0, 1, 2, 3):
            raise ConfigError(f"zero_optimization.stage must be 0-3, got {self.stage}")
        # bucket knobs are CONSUMED (grad_overlap.py / stage-3 plan) and
        # REGISTERED tunables (runtime/tunables.py): a nonsensical value
        # fails at config load naming the registry entry and its
        # documented range, and the effective value lands in /statusz
        # with its provenance
        from . import tunables
        for key in ("reduce_bucket_size", "allgather_bucket_size",
                    "stage3_prefetch_bucket_size"):
            tunables.check(f"zero_optimization.{key}",
                           getattr(self, key), exc=ConfigError)
            tunables.observe(f"zero_optimization.{key}",
                             getattr(self, key), "config")
        if self.overlap_grad_reduce not in ("auto", "bucketed", "off"):
            raise ConfigError(
                "zero_optimization.overlap_grad_reduce must be one of "
                f"'auto'|'bucketed'|'off', got {self.overlap_grad_reduce!r}")
        if self.quantized_reduce not in ("off", "int8", "fp8"):
            raise ConfigError(
                "zero_optimization.quantized_reduce must be one of "
                f"'off'|'int8'|'fp8', got {self.quantized_reduce!r}")
        tunables.check("zero_optimization.quant_block", self.quant_block,
                       exc=ConfigError)
        tunables.observe("zero_optimization.quant_block",
                         self.quant_block, "config")
        if self.quantized_reduce_hierarchy < 0:
            raise ConfigError(
                "zero_optimization.quantized_reduce_hierarchy must be "
                f">= 0 (a host count, 0/1 = flat), got "
                f"{self.quantized_reduce_hierarchy}")
        if (self.quantized_reduce_hierarchy > 1
                and self.quantized_reduce == "off"):
            raise ConfigError(
                "zero_optimization.quantized_reduce_hierarchy shapes "
                "the quantized ring — set quantized_reduce to "
                "'int8'|'fp8' (or drop the hierarchy knob)")
        if self.quantized_reduce != "off":
            if self.stage == 3:
                raise ConfigError(
                    "zero_optimization.quantized_reduce targets stages 0-2 "
                    "(stage-3 gradients reduce inside the parameter "
                    "gather's VJP; use zero_quantized_gradients for the "
                    "qgZ int8 all-to-all there)")
            if self.zero_quantized_gradients:
                raise ConfigError(
                    "quantized_reduce and zero_quantized_gradients both "
                    "quantize the gradient exchange — pick one transport")
        offloaded = (self.offload_optimizer.device != "none"
                     or self.offload_param.device != "none")
        if self.quantized_reduce != "off" and offloaded:
            # the offload paths (host C++ optimizer, tiered stream,
            # Infinity per-layer executor) build their own gradient
            # programs that never consult the knob — running fp32 wire
            # while the config claims int8 would be a silent no-op
            # (previously rejected at engine init, after the expensive
            # state build)
            raise ConfigError(
                "zero_optimization.quantized_reduce requires the "
                "standard jitted step: ZeRO-Offload / ZeRO-Infinity "
                "keep their own gradient transports")
        if self.offload_optimizer.pin_memory:
            # pin_memory selects the TIERED path (runtime/offload.py)
            if self.offload_optimizer.device == "nvme":
                raise ConfigError(
                    "offload_optimizer.pin_memory selects the tiered "
                    "HOST-RAM tier and composes with device 'cpu' only; "
                    "'nvme' runs the AIO-swapped host optimizer "
                    "(drop pin_memory or set device: cpu)")
            if (self.offload_optimizer.device == "cpu"
                    and self.stage not in (1, 2)):
                raise ConfigError(
                    "tiered optimizer offload (offload_optimizer "
                    "{device: cpu, pin_memory: true}) targets ZeRO "
                    f"stages 1/2 (got stage {self.stage}); stage-3 "
                    "state already shards via the parameter plan, "
                    "stage 0 has no sharded optimizer tier")
            if (self.offload_optimizer.device == "cpu"
                    and (self.zero_quantized_gradients
                         or self.zero_quantized_weights)):
                raise ConfigError(
                    "tiered optimizer offload does not compose with "
                    "ZeRO++ quantized gradients/weights (the streamed "
                    "update rides the plain bucketed grad program)")
        if self.zero_hpz_partition_size > 1 and self.stage != 3:
            # hpZ is a stage-3 feature (secondary partition of the COMPUTE
            # params; reference zero/config.py:256-272) — rejecting loudly
            # beats silently no-op'ing the key
            raise ConfigError(
                f"zero_hpz_partition_size={self.zero_hpz_partition_size} "
                f"requires zero stage 3 (got stage {self.stage})")
        if self.zero_hpz_partition_size > 1 and self.mics_shard_size > 1:
            raise ConfigError(
                "zero_hpz_partition_size and mics_shard_size cannot be "
                "combined: both partition over the shard sub-axis with "
                "opposite replication semantics")


@dataclass
class OptimizerConfig:
    type: str = "adamw"
    params: Dict[str, Any] = field(default_factory=dict)


@dataclass
class SchedulerConfig:
    type: Optional[str] = None
    params: Dict[str, Any] = field(default_factory=dict)


@dataclass
class PipelineConfig:
    """Pipeline-parallel block (reference: PipelineModule kwargs, pipe/module.py:86)."""

    stages: int = 1
    partition_method: str = "parameters"
    seed_layers: bool = False
    activation_checkpoint_interval: int = 0
    pipe_partitioned: bool = True
    grad_partitioned: bool = True
    num_microbatches: Optional[int] = None  # defaults to gradient_accumulation_steps


@dataclass
class ActivationCheckpointingConfig:
    """Reference: runtime/activation_checkpointing/checkpointing.py:1057 configure()."""

    partition_activations: bool = False
    cpu_checkpointing: bool = False
    contiguous_memory_optimization: bool = False
    number_checkpoints: Optional[int] = None
    synchronize_checkpoint_boundary: bool = False
    profile: bool = False
    # TPU-native: remat policy name passed to jax.checkpoint. "auto": the
    # engine keeps what the chip's free memory allows (checkpointing.py
    # choose_policy); any other name is obeyed as written
    policy: str = "auto"


@dataclass
class CommsLoggerConfig:
    enabled: bool = False
    verbose: bool = False
    prof_all: bool = True
    debug: bool = False
    prof_ops: List[str] = field(default_factory=list)


@dataclass
class FlopsProfilerConfig:
    enabled: bool = False
    profile_step: int = 1
    module_depth: int = -1
    top_modules: int = 1
    detailed: bool = True
    output_file: Optional[str] = None


@dataclass
class TensorboardConfig:
    enabled: bool = False
    output_path: str = ""
    job_name: str = "DeepSpeedTpuJobName"


@dataclass
class WandbConfig:
    enabled: bool = False
    group: Optional[str] = None
    team: Optional[str] = None
    project: str = "deepspeed_tpu"


@dataclass
class CSVConfig:
    enabled: bool = False
    output_path: str = ""
    job_name: str = "DeepSpeedTpuJobName"


# the diagnostics block (flight recorder, anomaly detectors, post-mortem
# bundles) is shared with the serving runtime's ServingConfig — one
# schema for both stacks (telemetry/anomaly.py)
from ..telemetry.anomaly import DiagnosticsConfig  # noqa: E402


@dataclass
class TelemetryConfig:
    """Unified telemetry layer (telemetry/registry.py + bridge.py).
    ``enabled`` gates the TRAINING engine's registry series, its samples
    of the calling thread and the judgement of a batch's spans
    (telemetry/collector.HostThread), the bridge that flushes registry
    scalars into the monitor backends, and the span->XLA-annotation
    mirroring; inference/serving instrumentation
    records unconditionally (allocation-free hot path)."""

    enabled: bool = True
    flush_interval: int = 10        # flush registry scalars every N steps
    xla_annotations: bool = False   # mirror spans into jax.profiler


@dataclass
class DataTypesConfig:
    grad_accum_dtype: Optional[str] = None


@dataclass
class CheckpointConfig:
    tag_validation: str = "Warn"  # Ignore | Warn | Fail
    load_universal: bool = False
    use_node_local_storage: bool = False
    parallel_write: Dict[str, Any] = field(default_factory=dict)
    async_save: bool = False


@dataclass
class AioConfig:
    block_size: int = 1_048_576
    queue_depth: int = 8
    thread_count: int = 1
    single_submit: bool = False
    overlap_events: bool = True


@dataclass
class MoEConfig:
    """Expert-parallel block. Reference keeps this on the MoE layer args; we also
    accept it in config for engine-level group setup (reference moe/layer.py:16)."""

    enabled: bool = False
    num_experts: int = 1
    expert_parallel_size: int = 1
    capacity_factor: float = 1.0
    eval_capacity_factor: float = 1.0
    min_capacity: int = 4
    top_k: int = 1
    noisy_gate_policy: Optional[str] = None
    drop_tokens: bool = True
    use_residual: bool = False


@dataclass
class EigenvalueConfig:
    enabled: bool = False
    verbose: bool = False
    max_iter: int = 100
    tol: float = 1e-2
    stability: float = 1e-6
    gas_boundary_resolution: int = 1
    layer_name: str = "bert.encoder.layer"
    layer_num: int = 0


@dataclass
class PLDConfig:
    enabled: bool = False
    theta: float = 1.0
    gamma: float = 0.001


@dataclass
class ElasticityConfig:
    enabled: bool = False
    max_train_batch_size: int = 2000
    micro_batch_sizes: List[int] = field(default_factory=lambda: [2, 4, 6])
    min_gpus: int = 1
    max_gpus: int = 10000
    min_time: int = 0
    prefer_larger_batch: bool = True
    ignore_non_elastic_batch_info: bool = False
    version: float = 0.1


@dataclass
class HybridEngineConfig:
    """Reference: deepspeed/inference/config.py HybridEngineConfig (consumed
    by runtime/hybrid_engine.py)."""

    enabled: bool = False
    max_out_tokens: int = 512
    inference_tp_size: int = 1
    release_inference_cache: bool = False
    pin_parameters: bool = True
    tp_gather_partition_size: int = 8
    # train->serve seam (docs/TRAINING.md § Hybrid engine): publication
    # bucket size (host bytes gathered per payload chunk — the ZeRO
    # gather granularity and the remote push's per-frame wire unit)
    publish_bucket_bytes: int = 16 << 20
    # bounded rollout->training queue (oldest rollouts drop when full,
    # counted — an RLHF actor loop must never grow host memory
    # unboundedly behind a slow learner)
    rollout_queue_size: int = 64
    # quantized weight-DELTA publication (serve/weights.py § delta
    # payloads; docs/SERVING.md § Delta weight push): publish-every-N
    # RLHF cadence ships current-base block-quantized int8 + fp32
    # block scales (~4x fewer push bytes) with publisher-side error
    # feedback across pushes. delta_publish=False disables base
    # tracking (and its fp32 host copy of the model); delta_quant is
    # "int8" or "off" (changed leaves at full fp32 — bitwise-exact
    # reconstruction)
    delta_publish: bool = True
    delta_quant: str = "int8"
    delta_block: int = 2048
    # overrides for the colocated serving engine the hybrid engine
    # builds (keys: "state_manager", "engine", "serving" — the worker
    # --spec layout); empty = geometry derived from the model config
    serving: Dict[str, Any] = field(default_factory=dict)


@dataclass
class DeepSpeedTpuConfig:
    """Top-level typed view of the JSON config.

    Field names match the reference JSON schema (runtime/config.py:686).
    """

    train_batch_size: Optional[Union[int, str]] = None
    train_micro_batch_size_per_gpu: Optional[Union[int, str]] = None
    gradient_accumulation_steps: Optional[Union[int, str]] = None
    steps_per_print: int = 10
    wall_clock_breakdown: bool = False
    dump_state: bool = False
    prescale_gradients: bool = False
    gradient_predivide_factor: float = 1.0
    gradient_clipping: float = 0.0
    sparse_gradients: bool = False
    memory_breakdown: bool = False
    disable_allgather: bool = False

    optimizer: Optional[OptimizerConfig] = None
    scheduler: Optional[SchedulerConfig] = None
    fp16: FP16Config = subconfig(FP16Config)
    bf16: BF16Config = subconfig(BF16Config)
    zero_optimization: ZeroConfig = subconfig(ZeroConfig)
    pipeline: PipelineConfig = subconfig(PipelineConfig)
    activation_checkpointing: ActivationCheckpointingConfig = subconfig(ActivationCheckpointingConfig)
    comms_logger: CommsLoggerConfig = subconfig(CommsLoggerConfig)
    flops_profiler: FlopsProfilerConfig = subconfig(FlopsProfilerConfig)
    tensorboard: TensorboardConfig = subconfig(TensorboardConfig)
    wandb: WandbConfig = subconfig(WandbConfig)
    csv_monitor: CSVConfig = subconfig(CSVConfig)
    telemetry: TelemetryConfig = subconfig(TelemetryConfig)
    diagnostics: DiagnosticsConfig = subconfig(DiagnosticsConfig)
    data_types: DataTypesConfig = subconfig(DataTypesConfig)
    checkpoint: CheckpointConfig = subconfig(CheckpointConfig)
    aio: AioConfig = subconfig(AioConfig)
    moe: MoEConfig = subconfig(MoEConfig)
    eigenvalue: EigenvalueConfig = subconfig(EigenvalueConfig)
    progressive_layer_drop: PLDConfig = subconfig(PLDConfig)
    elasticity: ElasticityConfig = subconfig(ElasticityConfig)
    hybrid_engine: HybridEngineConfig = subconfig(HybridEngineConfig)

    # Parallel topology (TPU mesh axes; tp/sp are first-class here rather than
    # via an external mpu object as in the reference engine.py:94)
    tensor_parallel_size: int = 1
    sequence_parallel_size: int = 1

    # Misc reference keys accepted for compatibility
    zero_allow_untested_optimizer: bool = True
    zero_force_ds_cpu_optimizer: bool = False
    communication_data_type: Optional[str] = None
    seq_parallel_communication_data_type: str = "fp32"
    curriculum_learning: Dict[str, Any] = field(default_factory=dict)
    data_efficiency: Dict[str, Any] = field(default_factory=dict)
    compression_training: Dict[str, Any] = field(default_factory=dict)
    autotuning: Dict[str, Any] = field(default_factory=dict)
    train_steps: Optional[int] = None


def _contains_auto(node) -> bool:
    if isinstance(node, str):
        return node == AUTO
    if isinstance(node, (list, tuple)):
        return any(_contains_auto(v) for v in node)
    return False


def _scrub_auto(node):
    """Drop every ``"auto"`` value recursively: HF-style configs ship
    ``"auto"`` for fields the integration layer would fill (reference
    __init__.py add_config_arguments / HF Trainer contract); here a
    dropped key falls back to the field's default, which is the same
    resolution standalone DeepSpeed applies. A list-valued field with an
    ``"auto"`` element (e.g. ``betas: ["auto", "auto"]``) is auto as a
    whole: the key is dropped."""
    if isinstance(node, dict):
        return {k: _scrub_auto(v) for k, v in node.items()
                if not (isinstance(v, str) and v == AUTO)
                and not (isinstance(v, (list, tuple)) and _contains_auto(v))}
    if isinstance(node, (list, tuple)):
        return type(node)(_scrub_auto(v) for v in node)
    return node


def _coerce_optional_blocks(raw: Dict[str, Any]) -> Dict[str, Any]:
    raw = _scrub_auto(raw)
    for key, cls in (("optimizer", OptimizerConfig), ("scheduler", SchedulerConfig)):
        if isinstance(raw.get(key), dict):
            raw[key] = hydrate(cls, raw[key], path=f"{key}.")
    return raw


class DeepSpeedConfig:
    """Parse + validate a config (path or dict) and resolve batch-size math.

    Reference: runtime/config.py:686 DeepSpeedConfig; the batch triple
    resolution (train_batch = micro * gas * dp_world) mirrors
    runtime/config.py's _configure_train_batch_size.
    """

    def __init__(self, config: Union[str, Dict[str, Any]], world_size: Optional[int] = None):
        if isinstance(config, str):
            with open(config, "r") as fh:
                raw: Dict[str, Any] = json.load(fh)
        elif isinstance(config, dict):
            raw = config
        else:
            raise ConfigError(f"config must be a path or dict, got {type(config)}")
        self.raw = raw
        self.cfg = hydrate(DeepSpeedTpuConfig, _coerce_optional_blocks(raw))
        # tuned-config provenance: scripts/autotune.py stamps the knobs
        # it moved under autotuning.tuned; /statusz then reports them
        # as provenance "tuned" rather than "config"
        from . import tunables
        tuned = (self.cfg.autotuning or {}).get("tuned", {})
        if isinstance(tuned, dict):
            for name, value in tuned.items():
                if name in tunables.REGISTRY:
                    tunables.observe(name, value, "tuned")
        if world_size is None:
            import jax

            world_size = jax.device_count()
        self.world_size = world_size
        mp = self.cfg.tensor_parallel_size * self.cfg.pipeline.stages * self.cfg.sequence_parallel_size
        if world_size % mp != 0:
            raise ConfigError(
                f"device count {world_size} not divisible by tp*pp*sp={mp}")
        self.dp_world_size = world_size // mp
        self._resolve_batch_sizes()
        # cross-block reject (optimizer type x zero offload): 1-bit
        # optimizers own their communication AND their own state layout —
        # neither host-offload backend can stream it. Fails at load
        # instead of deep inside the engine's state init.
        if self.cfg.zero_optimization.offload_optimizer.device != "none" \
                and self.cfg.optimizer is not None:
            from .fp16.onebit import is_onebit_optimizer
            if is_onebit_optimizer(self.cfg.optimizer.type):
                raise ConfigError(
                    "offload_optimizer does not compose with 1-bit "
                    "optimizers (they own their error-feedback state "
                    "and communication); use the standard optimizer "
                    "registry or drop the offload block")

    def _resolve_batch_sizes(self):
        c = self.cfg
        # "auto" was scrubbed to the field default (None) at ingestion
        tb = None if c.train_batch_size is None else int(c.train_batch_size)
        mb = (None if c.train_micro_batch_size_per_gpu is None
              else int(c.train_micro_batch_size_per_gpu))
        gas = (None if c.gradient_accumulation_steps is None
               else int(c.gradient_accumulation_steps))
        dp = self.dp_world_size
        if tb is not None and mb is not None and gas is None:
            gas, rem = divmod(tb, mb * dp)
            if rem:
                raise ConfigError(
                    f"train_batch_size {tb} not divisible by micro_batch*dp = {mb}*{dp}")
        elif tb is not None and gas is not None and mb is None:
            mb, rem = divmod(tb, gas * dp)
            if rem:
                raise ConfigError(
                    f"train_batch_size {tb} not divisible by gas*dp = {gas}*{dp}")
        elif mb is not None and tb is None:
            gas = gas or 1
            tb = mb * gas * dp
        elif tb is not None and mb is None and gas is None:
            gas = 1
            mb, rem = divmod(tb, dp)
            if rem:
                raise ConfigError(f"train_batch_size {tb} not divisible by dp {dp}")
        elif tb is None and mb is None:
            raise ConfigError(
                "must provide train_batch_size or train_micro_batch_size_per_gpu")
        if tb != mb * gas * dp:
            raise ConfigError(
                f"inconsistent batch config: train_batch_size {tb} != "
                f"micro {mb} * gas {gas} * dp {dp}")
        self.train_batch_size = tb
        self.train_micro_batch_size_per_gpu = mb
        self.gradient_accumulation_steps = gas

    # -- convenience accessors -------------------------------------------------
    @property
    def zero_enabled(self) -> bool:
        return self.cfg.zero_optimization.stage > 0

    @property
    def zero_stage(self) -> int:
        return self.cfg.zero_optimization.stage

    @property
    def precision_dtype(self) -> str:
        if self.cfg.fp16.enabled and self.cfg.bf16.enabled:
            raise ConfigError("fp16 and bf16 cannot both be enabled")
        if self.cfg.fp16.enabled:
            return "float16"
        if self.cfg.bf16.enabled:
            return "bfloat16"
        return "float32"

    def to_dict(self) -> Dict[str, Any]:
        return as_dict(self.cfg)

    def print_config(self):
        from ..utils.logging import logger

        logger.info(json.dumps(self.to_dict(), indent=2, default=str))
