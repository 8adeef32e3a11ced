"""deepspeed_tpu — a TPU-native distributed training & inference framework.

Provides the capability surface of the reference DeepSpeed
(see /root/repo/SURVEY.md) re-designed for JAX/XLA/Pallas: ZeRO sharding as
partition specs, compiled 1F1B pipelines over sub-meshes, expert/sequence
parallelism via mesh-axis collectives, Pallas kernels for the hot ops, and a
mesh-aware comm layer in place of NCCL.

Public API mirrors ``deepspeed/__init__.py:21-45``:
  initialize, init_distributed, init_inference, DeepSpeedConfig,
  comm, zero, moe, pipe, sequence, ops, monitor, checkpoint.
"""

__version__ = "0.1.0"
__git_branch__ = "main"

from typing import Any, Optional, Tuple

from . import comm  # noqa: F401
from .runtime.config import DeepSpeedConfig  # noqa: F401
from .runtime.engine import DeepSpeedTpuEngine  # noqa: F401
from .runtime.lr_schedules import LRScheduler  # noqa: F401
from .runtime.dataloader import DeepSpeedDataLoader, RepeatingLoader  # noqa: F401
from .parallel.topology import MeshTopology, TopologyConfig, build_topology  # noqa: F401
from .runtime.pipe import LayerSpec, PipelineModule, TiedLayerSpec  # noqa: F401
from .sequence.layer import DistributedAttention  # noqa: F401 (reference deepspeed/__init__.py:38)
from .pipeline import ServePipeline, pipeline  # noqa: F401 (MII-style front end)
from .utils.logging import log_dist, logger  # noqa: F401


def initialize(args=None,
               model=None,
               optimizer=None,
               model_parameters=None,
               training_data=None,
               lr_scheduler=None,
               distributed_port: int = 29500,
               mpu=None,
               dist_init_required: Optional[bool] = None,
               collate_fn=None,
               config=None,
               config_params=None,
               seed: int = 0,
               topology: Optional[MeshTopology] = None,
               ) -> Tuple[DeepSpeedTpuEngine, Any, Any, Any]:
    """Initialize the engine (reference deepspeed/__init__.py:64).

    Returns ``(engine, optimizer, training_dataloader, lr_scheduler)`` to match
    the reference tuple. ``model`` must expose ``init_params(rng)`` and
    ``apply(params, batch, train=..., rng=...)`` (see runtime/engine.py).
    """
    config = config if config is not None else config_params
    if config is None and args is not None and hasattr(args, "deepspeed_config"):
        config = args.deepspeed_config
    if config is None:
        raise ValueError("a config (dict or json path) is required")

    comm.init_distributed(distributed_port=distributed_port)
    # an explicit topology (e.g. one chip of a four-chip host) sets the
    # world the batch triple is checked against, not jax.device_count()
    ds_config = DeepSpeedConfig(
        config, world_size=(topology.mesh.size if topology is not None
                            else None))

    dataloader = None
    if training_data is not None:
        dataloader = DeepSpeedDataLoader(
            training_data,
            micro_batch_size=ds_config.train_micro_batch_size_per_gpu,
            dp_world_size=ds_config.dp_world_size,
            collate_fn=collate_fn)

    engine_cls = DeepSpeedTpuEngine
    if ds_config.cfg.hybrid_engine.enabled:
        from .runtime.hybrid_engine import DeepSpeedHybridEngine
        engine_cls = DeepSpeedHybridEngine
    engine = engine_cls(model=model, config=ds_config,
                        topology=topology, seed=seed,
                        dataloader=RepeatingLoader(dataloader) if dataloader else None,
                        lr_scheduler=lr_scheduler)
    return engine, engine.optimizer, dataloader, engine.lr_scheduler


def init_distributed(dist_backend: str = "xla", **kwargs):
    """Reference deepspeed/__init__.py init_distributed passthrough."""
    return comm.init_distributed(dist_backend=dist_backend, **kwargs)


def add_config_arguments(parser):
    """Reference deepspeed/__init__.py:246 — argparse flags."""
    group = parser.add_argument_group("DeepSpeed-TPU",
                                      "DeepSpeed-TPU configurations")
    group.add_argument("--deepspeed", default=False, action="store_true",
                       help="Enable DeepSpeed-TPU")
    group.add_argument("--deepspeed_config", default=None, type=str,
                       help="DeepSpeed-TPU json configuration file")
    group.add_argument("--deepscale", default=False, action="store_true",
                       help=argparse_suppress())
    group.add_argument("--local_rank", type=int, default=-1)
    return parser


def argparse_suppress():
    import argparse

    return argparse.SUPPRESS


def init_inference(model=None, config=None, params=None, **kwargs):
    """Reference deepspeed/__init__.py:269 — inference engine entry.

    Accepts either a native functional model (init_params/apply protocol)
    or an HF torch module (GPT-2/OPT/Llama/Mistral/Mixtral/BERT families),
    which is converted in place of the reference's kernel injection
    (module_inject/replace_module.py). ``use_ragged=True`` routes to the
    FastGen-class v2 paged engine (reference inference/v2/engine_v2.py:89
    build_hf_engine) instead of the v1 KV-cache engine. ``params``
    supplies trained weights for a native model (HF modules carry their
    own state_dict).
    """
    from .inference.engine import InferenceEngine
    from .inference.config import DeepSpeedInferenceConfig

    cfg = DeepSpeedInferenceConfig.from_dict_or_kwargs(config, kwargs)
    if (model is not None and hasattr(model, "state_dict")
            and not hasattr(model, "init_params")):
        # torch nn.Module (HF transformer): convert weights + architecture
        from .module_inject import load_hf_model
        model, params = load_hf_model(model)
    if cfg.use_ragged:
        if cfg.checkpoint:
            # silently serving random weights would be worse than refusing
            raise NotImplementedError(
                "use_ragged=True does not take 'checkpoint' yet; pass an "
                "HF model or explicit params (v1 path supports the key)")
        from .inference.v2 import (InferenceEngineV2,
                                   RaggedInferenceEngineConfig)
        rdict = dict(cfg.ragged or {})
        rdict.setdefault("dtype", cfg.dtype)
        rdict.setdefault("tensor_parallel_size", cfg.tensor_parallel.tp_size)
        if cfg.quant_bits:
            rdict.setdefault("quant_bits", cfg.quant_bits)
        return InferenceEngineV2(model,
                                 RaggedInferenceEngineConfig.from_dict(rdict),
                                 params=params)
    return InferenceEngine(model, cfg, params=params)
