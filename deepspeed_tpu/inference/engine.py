"""Inference engine (v1): TP-sharded KV-cache generation.

Reference parity: ``InferenceEngine`` (inference/engine.py:39) — TP group
creation (:247), kernel injection (:401), forward (:577), and HF-style
``generate``. TPU-native design:

* tensor parallelism is a "model" mesh axis with the same column/row-parallel
  layout AutoTP derives by name-parsing (module_inject/auto_tp.py:259) —
  declared as PartitionSpecs, XLA inserts the per-layer allreduce;
* the CUDA-graph capture/replay path (engine.py:517) is unnecessary: both the
  prefill and the decode step are jitted once and cached;
* generation runs the decode loop as a ``lax.scan`` over steps with a
  dense KV cache (the ragged/paged engine lives in inference/v2).
"""

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..parallel.topology import build_topology
from ..utils.logging import log_dist
from .config import DeepSpeedInferenceConfig

DTYPES = {"float32": jnp.float32, "float16": jnp.float16,
          "bfloat16": jnp.bfloat16}


class InferenceEngine:
    """Wraps a model family instance for TP-sharded generation.

    ``model`` follows the same protocol as training (init_params /
    param_partition_specs) plus ``init_kv_cache`` / ``forward_cached``.
    Pass ``params`` to reuse trained weights; otherwise they are initialized
    (and optionally loaded from ``config.checkpoint``).
    """

    def __init__(self, model, config: DeepSpeedInferenceConfig, params=None):
        self.module = self.model = model
        if hasattr(getattr(model, "cfg", None), "refuse_served_only"):
            model.cfg.refuse_served_only("the v1 inference engine")
        self.config = config
        self.dtype = DTYPES[config.dtype]
        tp = config.tensor_parallel.tp_size
        # TP group of exactly tp devices (reference
        # _create_model_parallel_group, inference/engine.py:247); batch is
        # replicated, activations/weights shard over "model".
        self.topology = build_topology(model=tp, devices=jax.devices()[:tp])
        self.mesh = self.topology.mesh
        if hasattr(model, "set_topology"):
            model.set_topology(self.topology)
        self._checkpoint_loaded = False

        specs = (model.param_partition_specs(self.topology)
                 if hasattr(model, "param_partition_specs") else None)
        from jax.sharding import NamedSharding, PartitionSpec as P

        if specs is not None:
            self.param_sharding = jax.tree.map(
                lambda s: NamedSharding(self.mesh, s), specs,
                is_leaf=lambda x: isinstance(x, P))
        else:
            self.param_sharding = None

        if params is not None:
            self.params = self._shard(self._cast(params))
        elif config.checkpoint:
            self.params = self._load_checkpoint(config.checkpoint)
        else:
            init = jax.jit(
                lambda r: jax.tree.map(lambda x: x.astype(self.dtype),
                                       model.init_params(r)),
                out_shardings=self.param_sharding)
            self.params = init(jax.random.PRNGKey(config.seed))

        if config.quant_bits:
            # quantize_params validates bits in {4, 8} — an invalid value
            # must raise, not silently serve unquantized weights
            from .quantization import dequantize_params, quantize_params

            self.params, self._qmeta = quantize_params(
                self.params, bits=config.quant_bits)

            def _deq_nonlayer(p):
                # layer leaves stay quantized into the forward: the model
                # scan dequantizes ONE layer per step inside its body
                # (transformer.py scan_fn), keeping peak HBM at the
                # quantized footprint; only embed/lm_head dequant here
                return {k: (v if k == "layers" else dequantize_params(v))
                        for k, v in p.items()}

            self._deq = _deq_nonlayer
        else:
            self._deq = lambda p: p
        self._gen_jit = None
        log_dist(f"inference engine ready: tp={tp} dtype={config.dtype}",
                 ranks=[0])

    # ------------------------------------------------------------------
    def _cast(self, params):
        return jax.tree.map(lambda x: jnp.asarray(x, self.dtype), params)

    def _shard(self, params):
        if self.param_sharding is None:
            return params
        return jax.device_put(params, self.param_sharding)

    def _load_checkpoint(self, path):
        from ..checkpoint.state_checkpoint import load_params_for_inference

        return load_params_for_inference(path, self.model, self.dtype,
                                         self.param_sharding)

    # ------------------------------------------------------------------
    def forward(self, input_ids, **_kw):
        """Plain logits forward (reference engine.forward :577)."""
        ids = jnp.asarray(np.asarray(input_ids))
        if not hasattr(self, "_fwd_jit"):
            self._fwd_jit = jax.jit(
                lambda p, x: self.model.forward_logits(self._deq(p), x))
        return self._fwd_jit(self.params, ids)

    __call__ = forward

    # ------------------------------------------------------------------
    def generate(self, input_ids, max_new_tokens: int = 32,
                 temperature: float = 0.0, top_k: int = 0,
                 top_p: float = 0.0, eos_token_id: Optional[int] = None,
                 seed: int = 0, **_kw):
        """Autoregressive generation. input_ids: [B, S_prompt] (numpy/jax).
        Returns [B, S_prompt + max_new_tokens] token ids (post-EOS positions
        hold EOS). The full prefill+decode loop is ONE jitted program, cached
        per (shape, sampling-config) — the XLA analogue of the reference's
        CUDA-graph replay (inference/engine.py:517)."""
        ids = np.asarray(input_ids)
        if ids.ndim == 1:
            ids = ids[None]
        # enforce the engine limits the reference enforces (max_out_tokens /
        # max_batch_size in the reference config gate its workspace alloc)
        if ids.shape[0] > self.config.max_batch_size:
            raise ValueError(
                f"batch size {ids.shape[0]} exceeds config.max_batch_size="
                f"{self.config.max_batch_size}")
        total = ids.shape[1] + int(max_new_tokens)
        if total > self.config.max_out_tokens:
            raise ValueError(
                f"prompt + max_new_tokens = {total} exceeds "
                f"config.max_out_tokens={self.config.max_out_tokens}")
        if int(max_new_tokens) < self.config.min_out_tokens:
            raise ValueError(
                f"max_new_tokens={max_new_tokens} below "
                f"config.min_out_tokens={self.config.min_out_tokens}")
        eos = -1 if eos_token_id is None else int(eos_token_id)
        if self._gen_jit is None:
            self._gen_jit = jax.jit(
                self._generate_impl,
                static_argnames=("max_new_tokens", "temperature", "top_k",
                                 "top_p", "eos"))
        toks = self._gen_jit(self.params, jnp.asarray(ids),
                             jax.random.PRNGKey(seed),
                             max_new_tokens=int(max_new_tokens),
                             temperature=float(temperature), top_k=int(top_k),
                             top_p=float(top_p), eos=eos)
        return np.asarray(jnp.concatenate([jnp.asarray(ids), toks], axis=1))

    def _generate_impl(self, params, ids, rng, *, max_new_tokens, temperature,
                       top_k, top_p, eos):
        params = self._deq(params)   # fused into first use; int8 at rest
        return generate_tokens(self.model, params, ids, rng, self.dtype,
                               max_new_tokens=max_new_tokens,
                               temperature=temperature, top_k=top_k,
                               top_p=top_p, eos=eos)


def generate_tokens(model, params, ids, rng, dtype, *, max_new_tokens,
                    temperature, top_k, top_p, eos):
    """Prefill + scan decode loop shared by the v1 inference engine and the
    hybrid (RLHF) engine. Jittable; returns [B, max_new_tokens] tokens."""
    B, S = ids.shape
    cache = model.init_kv_cache(B, S + max_new_tokens, dtype)
    logits, cache = model.forward_cached(params, ids, cache, 0)
    last = logits[:, -1]

    def step(carry, i):
        cache, last, rng, done = carry
        rng, sub = jax.random.split(rng)
        tok = _sample(last, sub, temperature, top_k, top_p)  # [B]
        tok = jnp.where(done, eos if eos >= 0 else 0, tok)
        done = done | (tok == eos)

        def fwd(cache):
            logits, cache = model.forward_cached(
                params, tok[:, None], cache, S + i)
            return cache, logits[:, 0]

        # the final iteration's logits are never sampled: skip that
        # forward entirely (runtime cond, not compile-time)
        cache, nxt = jax.lax.cond(i < max_new_tokens - 1, fwd,
                                  lambda c: (c, last), cache)
        return (cache, nxt, rng, done), tok

    done0 = jnp.zeros((B,), bool)
    _, toks = jax.lax.scan(
        step, (cache, last, rng, done0), jnp.arange(max_new_tokens))
    return toks.T


def _sample(logits, rng, temperature, top_k, top_p):
    """Greedy / temperature / top-k / nucleus sampling over [B, V] logits."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1)
    logits = logits / temperature
    if top_k and top_k > 0:
        kth = jnp.sort(logits, axis=-1)[:, -top_k][:, None]
        logits = jnp.where(logits < kth, -1e30, logits)
    if top_p and 0.0 < top_p < 1.0:
        sorted_logits = jnp.sort(logits, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        cutoff_idx = jnp.sum(cum < top_p, axis=-1)         # [B]
        cutoff = jnp.take_along_axis(sorted_logits, cutoff_idx[:, None],
                                     axis=-1)
        logits = jnp.where(logits < cutoff, -1e30, logits)
    return jax.random.categorical(rng, logits, axis=-1)
