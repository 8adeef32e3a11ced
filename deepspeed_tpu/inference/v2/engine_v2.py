"""Ragged/continuous-batching inference engine (FastGen-style).

Reference: inference/v2/engine_v2.py:26 (InferenceEngineV2): the serving
loop calls ``put(batch_uids, batch_tokens)`` each step with a mix of new
prompts and one next-token per running sequence; the engine returns the
next-token logits for every entry. KV lives in a blocked (paged) pool
managed by DSStateManager; sequences are freed with ``flush``.

TPU-native scheduling: every put() — mixed prompts, continuations and
decode rows — packs into ONE RaggedBatch and runs as a single unified
compiled program per (token bucket, row bucket, table-width bucket)
(``paged_ragged_step`` + ``kernels/ragged_attention.py``), the Ragged
Paged Attention design (PAPERS.md arXiv:2604.15464); a prompt set over
the step's budget goes in as several such steps. The compiled-program
cache plays the role the reference's CUDA graphs + atom builder play.

The decode hot loop itself is fused on device (``decode_window`` > 1):
``paged_decode_window`` runs up to K decode steps per dispatch — cache
write, paged attention, argmax/per-row-keyed sampling, EOS + budget
masking, arithmetic block-table advancement over pre-allocated blocks —
with one [N, K] int32 transfer per window instead of a Python round-trip
per token (docs/SERVING.md, "Fused multi-token decode").
"""

import contextlib
import dataclasses
import time
from typing import Dict, Iterable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ...models.transformer import PAGED_KINDS, TransformerConfig
from ...telemetry import memory as ds_memory
from ...telemetry import recorder as flight
from ...telemetry import trace, watchdog
from ...utils.bucketing import ceil_bucket, pow2_bucket
from ...utils.logging import log_dist
from .config_v2 import RaggedInferenceEngineConfig
from .kernels import power_retention, state_space
from .kernels.linear_attention import (chunk_kernel_serves,
                                       conv_kernel_serves)
from .kernels.ragged_attention import (LATENT, decode_positions,
                                       decode_walks, kernel_variant,
                                       launch_copies, one_token_tile_serves,
                                       prompt_chunks, prompt_walks,
                                       token_tile, token_tile_serves)
from .paged_model import (STATE_LEAVES, index_positions_swept,
                          index_prompt_form, init_lora_bank,
                          init_paged_kv_cache,
                          moe_rows_form, moe_share_runs, paged_continue,
                          paged_decode, paged_decode_window,
                          paged_ragged_step, paged_spec_decode_window)
from .ragged import batch as ragged_batch
from .ragged.blocked_allocator import NULL_BLOCK
from .ragged.ragged_manager import DSStateManager
from .sampling import fold_in_rows, greedy_tokens, sample_tokens_rowwise

DTYPES = {"float32": jnp.float32, "float16": jnp.float16,
          "bfloat16": jnp.bfloat16}


class DraftModelMismatchError(ValueError):
    """A draft model cannot verify-share with the serving target:
    greedy verification compares raw token ids, so the vocabularies
    must be the SAME id space, and the draft writes its KV through the
    target's block tables, so it must cover the same sequence range."""


class SpecChooser:
    """Routes each speculative request between the two draft sources —
    the host n-gram index (``"ngram"``, prompt-lookup) and the in-window
    draft model (``"draft"``) — by observed accept rate.

    Hysteresis-armed like the online autotuner (autotuning/online.py's
    armed/hold cycle): a switch commits only after the OTHER source's
    accept-rate EMA beats the current one by ``margin`` for ``hold``
    consecutive observations, so one noisy window never flips the
    route. Cold start (no accept history for either source) routes by a
    repetitiveness prior: histories whose trailing n-gram already
    recurs draft well from their own text; everything else goes to the
    draft model."""

    def __init__(self, mode: str = "auto", alpha: float = 0.3,
                 margin: float = 0.05, hold: int = 3):
        self.mode = mode
        self.alpha = float(alpha)
        self.margin = float(margin)
        self.hold = int(hold)
        self.rate: Dict[str, Optional[float]] = {"ngram": None,
                                                 "draft": None}
        self.current = "draft" if mode == "draft" else "ngram"
        self.switches = 0
        self._armed: Optional[str] = None
        self._streak = 0

    def observe(self, mode: str, drafted: int, accepted: int) -> None:
        """Fold one round's (drafted, accepted) counts into ``mode``'s
        accept-rate EMA; may arm or commit a route switch."""
        if drafted <= 0:
            return
        r = min(max(accepted / drafted, 0.0), 1.0)
        prev = self.rate.get(mode)
        self.rate[mode] = (r if prev is None
                           else (1 - self.alpha) * prev + self.alpha * r)
        self._maybe_switch()

    def _maybe_switch(self) -> None:
        if self.mode != "auto":
            return
        other = "draft" if self.current == "ngram" else "ngram"
        ro, rc = self.rate[other], self.rate[self.current]
        if ro is None or rc is None or ro <= rc + self.margin:
            self._armed, self._streak = None, 0
            return
        if self._armed != other:
            self._armed, self._streak = other, 1
        else:
            self._streak += 1
        if self._streak >= self.hold:
            self.current = other
            self.switches += 1
            self._armed, self._streak = None, 0

    def choose(self, has_draft_model: bool, ngram_hit: bool) -> str:
        """Route one incoming request. Pinned modes and a missing draft
        model short-circuit; "auto" returns the hysteresis-settled
        current source once any accept history exists."""
        if self.mode == "ngram" or not has_draft_model:
            return "ngram"
        if self.mode == "draft":
            return "draft"
        if self.rate["ngram"] is None and self.rate["draft"] is None:
            return "ngram" if ngram_hit else "draft"
        return self.current


@dataclasses.dataclass
class _Window:
    """A fused decode window that was launched: what collecting it
    needs, and the rows' state it hands to a window queued behind it."""
    uids: List[int]
    steps_left: List[int]
    # the fed tokens: the host's list, or the window this one was queued
    # behind (whose collect notes every row's last emit in ``last``)
    fed: object
    t0: float               # perf_counter at the start of the launch
    out: object             # [N, K] tokens, still on the device
    moe: list
    state: tuple            # (token, position, alive), on the device
    last: Optional[Dict[int, int]] = None


@dataclasses.dataclass
class _RaggedStep:
    """A ragged step that was launched: its outputs, still on the device,
    and what noting them takes once they are fetched."""
    rows: int               # the entries it ran (the logits' valid rows)
    valid: int              # the tokens they fed
    tokens: int             # its token bucket: what _note_moe reads a form by
    t0: float               # perf_counter at the start of its pack
    logits: object          # [row bucket, vocab] float32, on the device
    moe: list
    # a chunked put(): (row of the step, row of the call) of the rows
    # whose LAST token went in here, the only rows whose logits are read
    ended: tuple = ()


class InferenceEngineV2:
    def __init__(self, model, config: Optional[RaggedInferenceEngineConfig]
                 = None, params=None):
        if isinstance(config, dict) or config is None:
            config = RaggedInferenceEngineConfig.from_dict(config or {})
        self.config = config
        self.model = model
        cfg: TransformerConfig = model.cfg
        if cfg.moe_num_experts > 0 and config.expert_parallel_size > 1:
            # ep>1 serving routes through the worst-case-capacity einsum
            # dispatch (moe_layer_dropless_ep -> moe_layer), whose gating
            # implements the training top-1/top-2 conventions only. ep=1
            # serving uses the k-generic sorted-token grouped GEMM
            # (dropless_topk_dispatch) with renormalized top-k weights —
            # the Mixtral/Qwen-MoE/DBRX convention — so any k serves.
            assert cfg.moe_top_k <= 2 and cfg.served_only is None, \
                f"expert-parallel serving is top-1/top-2 only and " \
                f"routes as training does (softmax scores, no selection " \
                f"bias, scale or shared expert; got moe_top_k=" \
                f"{cfg.moe_top_k}, {cfg.served_only}); serve top-k>2 " \
                f"and the deployed expert layer at ep=1"
        if cfg.walks_runs:
            self._refuse_for_latent(config, cfg)
        if not cfg.has_state and config.state_dtype != "float32":
            raise ValueError(
                "state_dtype is for a model that keeps recurrent state "
                "(linear-attention, state-space, power-retention or "
                "short-convolution layers); this one keeps none")
        sm = config.state_manager
        if sm.max_seq_len > cfg.max_seq_len:
            sm.max_seq_len = cfg.max_seq_len
        self.dtype = DTYPES[config.dtype]
        self.block_size = sm.block_size

        from ...parallel.topology import build_topology
        tp = config.tensor_parallel_size
        ep = config.expert_parallel_size
        if ep > 1:
            assert cfg.moe_num_experts > 0, \
                "expert_parallel_size > 1 requires an MoE model"
            assert cfg.moe_num_experts % ep == 0, \
                f"num experts {cfg.moe_num_experts} not divisible by " \
                f"expert_parallel_size {ep}"
        self.topology = build_topology(model=tp, expert=ep,
                                       devices=jax.devices()[:tp * ep])
        self.mesh = self.topology.mesh
        if hasattr(model, "set_topology"):
            model.set_topology(self.topology)
        from jax.sharding import NamedSharding, PartitionSpec as P
        specs = (model.param_partition_specs(self.topology)
                 if hasattr(model, "param_partition_specs") else None)
        self.param_sharding = (jax.tree.map(
            lambda s: NamedSharding(self.mesh, s), specs,
            is_leaf=lambda x: isinstance(x, P)) if specs is not None else None)

        if params is not None and tp * ep == 1 and all(
                isinstance(x, jax.Array) and x.dtype == self.dtype
                for x in jax.tree.leaves(params)):
            # already what the cast below would give: taken as they are
            # (an equivalent sharding on the one device moves no bytes).
            # A copy of an 11 GB tree does not fit beside it on a chip
            self.params = (params if self.param_sharding is None else
                           jax.device_put(params, self.param_sharding))
        elif params is not None:
            cast = jax.jit(lambda p: jax.tree.map(
                lambda x: jnp.asarray(x, self.dtype), p),
                out_shardings=self.param_sharding)
            self.params = cast(params)
        else:
            init = jax.jit(
                lambda r: jax.tree.map(lambda x: x.astype(self.dtype),
                                       model.init_params(r)),
                out_shardings=self.param_sharding)
            self.params = init(jax.random.PRNGKey(config.seed))

        if config.quant_bits:
            # WOQ at rest (v1 machinery, inference/quantization.py):
            # int8/packed-int4 + per-block scales in HBM. paged_model
            # dequantizes non-layer leaves at entry and each scanned
            # layer INSIDE the scan body (per-layer stacked quant), so
            # peak HBM really is the quantized footprint — see
            # QuantizedTensor.stacked. tp/ep shardings are declared
            # against the dense leaf structure: single-device only
            assert tp == 1 and ep == 1, \
                "quant_bits requires tensor_parallel_size == " \
                "expert_parallel_size == 1 (shardings are declared " \
                "against dense leaves)"
            from ..quantization import quantize_params
            self.params, self._qmeta = quantize_params(
                self.params, bits=config.quant_bits)

        # a model with linear-attention layers keeps recurrent state a
        # sequence: a slot a tracked sequence, beside its blocks
        self._has_state = cfg.has_state
        self._conv_layers = cfg.leaf_places("conv")
        # a model with window-attention layers keeps their keys and
        # values in a second pool, a RING a sequence: the window, the
        # most tokens a sequence feeds in one step (its share of a full
        # step, in whole blocks) and one block, so that a step's writes
        # never land on a position its earliest token still sees
        bs = sm.block_size
        self.max_row_chunk = None
        ring = 0
        if {"window", "mla_window"} & set(cfg.layer_kinds):
            self.max_row_chunk = max(
                sm.max_ragged_batch_size // sm.max_tracked_sequences
                // bs, 1) * bs
            ring = min(-(-cfg.attn_window // bs) * bs
                       + self.max_row_chunk + bs,
                       -(-sm.max_seq_len // bs) * bs)
        # a model whose layers ALL keep a state caches no position: its
        # sequences own a slot and no block, and the cache is the state
        # leaves alone (``DSStateManager``'s ``paged``)
        self.state_manager = DSStateManager(
            sm, state_slots=sm.max_tracked_sequences
            if self._has_state else 0, window_ring=ring,
            paged=cfg.caches_positions)
        self._has_ring = bool(ring)
        # note: the fresh pool carries no sharding, while every program
        # returns the donated cache with an explicit NamedSharding — so
        # a bucket's FIRST call compiles against a different executable
        # signature than its steady repeats (one respecialization per
        # bucket). Warmup should replay the bucket set twice before
        # watchdog.mark_steady(); committing
        # the pool sharded at init was tried and destabilizes unrelated
        # XLA-CPU executables later in the process (see PR 7 notes)
        self.kv_cache = init_paged_kv_cache(
            cfg, sm.num_blocks, sm.block_size, self.dtype,
            kv_quant=config.kv_quant,
            state_slots=self.state_manager.state_slots,
            state_dtype=DTYPES[config.state_dtype],
            window_blocks=sm.max_tracked_sequences
            * self.state_manager.ring_blocks + 1)
        # cold-block KV spill tier (ragged/spill.py): installed on the
        # state manager so prefix eviction demotes content to host RAM
        # (+ optional disk) and match_prefix restores it between steps
        self.spill = None
        if sm.enable_kv_spill:
            from .ragged.spill import KVSpillTier
            self.spill = KVSpillTier(self, sm)
            self.state_manager.spill = self.spill
        # per-uid consecutive failed-verify counter for speculative
        # decoding; entries are cleared on flush() and at generate() entry
        # so a cold streak never bans a uid across independent calls
        self._spec_miss_streak: Dict[int, int] = {}
        # per-uid incremental n-gram index (ngram_index.py): keeps draft
        # lookup O(ngram) per round instead of re-scanning the history
        # window; same lifecycle as the miss streaks
        self._draft_index: Dict[int, object] = {}
        # per-uid distributed-trace ids (telemetry/context.py): the
        # scheduler binds them at submit/resume so batch-level spans
        # (decode_step/decode_window/ragged_step) carry the trace ids of
        # every request they served; cleared on flush()
        self._uid_traces: Dict[int, str] = {}
        # live-weight version (serve/weights.py hot-swap): 0 = the boot
        # checkpoint; bumped by swap_engine_params. Advertised through
        # /healthz so the router's blue/green rollout can converge a
        # fleet onto one version
        self.weight_version = 0
        # multi-tenant batched LoRA (config_v2.max_lora_adapters): the
        # stacked adapter bank lives on device next to the params; slot
        # 0 holds the all-zero base delta, so rows without an adapter
        # ride the same gathered program bit-exactly (+0.0). The bank is
        # a jit ARGUMENT, not a closure constant, so loading an adapter
        # is a same-shape slot update — no recompile.
        self.lora_bank = None
        self._adapter_slots: Dict[str, int] = {}
        self._uid_adapter: Dict[int, str] = {}
        if config.max_lora_adapters > 0:
            self.lora_bank = init_lora_bank(
                cfg, config.max_lora_adapters + 1, config.lora_rank,
                self.dtype)
        # draft-model speculation (load_draft_model): the draft shares
        # the target's block tables against its OWN paged KV pool, so
        # propose->verify->accept runs entirely inside one jitted window
        # (paged_spec_decode_window); jits cached per (window, spec_k)
        self.draft_model = None
        self.draft_params = None
        self.draft_cache = None
        self._draft_cfg = None
        self._draft_seen: Dict[int, int] = {}
        self._spec_window_jits: Dict[tuple, object] = {}
        self.spec_chooser = SpecChooser(config.spec_mode)
        self._spec_mode_of: Dict[int, str] = {}
        self._spec_switches_seen = 0
        self._init_telemetry()
        # Pallas kernels only at tp=1: a bare pallas_call is not
        # GSPMD-partitionable, so sharded-param (tp>1) serving keeps the
        # jnp paths, which the partitioner splits over the head axis (same
        # gate as the v1 decode kernel, models/transformer.py). The
        # kernels carry no alibi bias; the jnp paths add the
        # softmax-invariant row. int8 kv_quant pools ride the same kernels
        # (scales dequantize in VMEM). WHICH kernel variant serves the
        # pool is a static function of its geometry
        # (kernels/ragged_attention.kernel_variant, pinned against the
        # Mosaic compiler by tests/unit/ops/test_kernels_lower_tpu.py) —
        # resolved here once, readable as ``attention_impl`` and in
        # /statusz (health.attention_impl), never by trying a compile
        use_kernel = (config.use_paged_kernel and tp == 1 and ep == 1
                      and cfg.positional != "alibi")
        # a latent pool (attention='mla') has one kernel, whatever its
        # widths: kernels/ragged_attention.latent_attention
        self.attention_impl = (
            "none:no-layer-caches-positions" if not cfg.caches_positions
            else "pallas:" + (LATENT if cfg.attention == "mla" else
                              kernel_variant(cfg.head_dim, cfg.kv_heads,
                                             bool(config.kv_quant)))
            + "+window" * self._has_ring + "+indexed" * bool(cfg.index_topk)
            if use_kernel else "jnp:gather")
        topo = self.topology if ep > 1 else None
        # load_draft_model builds jits after __init__; it reuses the
        # same kernel gate and topology the serving programs resolved
        self._use_kernel = use_kernel
        self._topo = topo
        # every compile point below is watchdog-wrapped: the power-of-two
        # bucketing is SUPPOSED to make steady-state serving compile-free,
        # and the watchdog is what proves it (telemetry/watchdog.py)
        # every decode-family jit takes trailing (lb, aid): the LoRA
        # bank and per-row adapter slots. Both are None when the bank is
        # disabled (an empty pytree — same compiled programs as before),
        # and they TRAIL the existing argument lists so every
        # donate_argnums index stays put. The programs that run a model
        # with recurrent state (decode, the fused window, the ragged
        # step) take one more behind them, ``ss``: each row's state
        # slot, None for every other model
        def _decode_tok(p, t, pos, bt, c, a, lb, aid, ss, wt=None):
            # greedy variant for the generate() hot loop: argmax on device
            # so the per-token host transfer is [N] int32, not [N, vocab]
            # (the reference's sampler also runs device-side)
            logits, *moe, c = paged_decode(
                cfg, p, t, pos, bt, c, a, sm.block_size,
                use_kernel=use_kernel, topo=topo, lora=lb, adapter_ids=aid,
                state_slots=ss, window_tables=wt)
            return (greedy_tokens(logits), *moe, c)

        self._decode_tok_jit = watchdog.watch_jit(
            "decode_greedy", _decode_tok, donate_argnums=(4,))

        def _decode_sample(p, t, pos, bt, c, a, rng, seeds, gidx, temp,
                           topp, topk, lb, aid, ss, wt=None):
            # sampling variant (FastGen temperature/top-p/top-k): the
            # sampler runs device-side too, still an [N] int32 transfer.
            # Per-ROW keys (stable row seed + generated-token index) so
            # the stream matches the fused window path bit-for-bit
            from .sampling import fold_in_rows, sample_tokens_rowwise
            logits, *moe, c = paged_decode(
                cfg, p, t, pos, bt, c, a, sm.block_size,
                use_kernel=use_kernel, topo=topo, lora=lb, adapter_ids=aid,
                state_slots=ss, window_tables=wt)
            keys = fold_in_rows(rng, seeds, gidx)
            return (sample_tokens_rowwise(logits, keys, temp, topp, topk),
                    *moe, c)

        self._decode_sample_jit = watchdog.watch_jit(
            "decode_sample", _decode_sample, donate_argnums=(4,))
        # fused multi-token decode window (the generate()/scheduler hot
        # path when decode_window > 1): K decode steps per dispatch, one
        # [N, K] int32 transfer per window. K is baked into the compiled
        # program; batch rows pad to the same power-of-two buckets as the
        # per-token path, so the compile cache stays one program per
        # (batch bucket, table-width bucket). The rows' state (t, pos,
        # alive) comes from the host or from the window before
        # (_launch_window): uploaded under the sharding the program
        # returns it with, both are one signature
        self.decode_window = max(int(config.decode_window), 1)
        self._m_window_size.set(self.decode_window)

        # K is baked into each compiled window program, so runtime
        # adaptation (autotuning/online.py set_decode_window) swaps
        # whole jit OBJECTS from this per-K cache — reusing one jit
        # across K values would silently serve the old-K program (the
        # closure int is not part of jax's cache key). All K values
        # share the watchdog program names, so compile accounting stays
        # one row per path regardless of the ladder.
        self._fused_jit_cache: Dict[int, tuple] = {}

        def _build_fused_pair(K: int):
            greedy = watchdog.watch_jit(
                "decode_window_greedy",
                lambda p, t, pos, bt, c, sl, eos, alive, lb, aid, ss,
                wt=None, _K=K: paged_decode_window(
                    cfg, p, t, pos, bt, c, sl, eos, sm.block_size,
                    _K, use_kernel=use_kernel,
                    topo=topo, lora=lb, adapter_ids=aid, alive=alive,
                    state_slots=ss, window_tables=wt),
                donate_argnums=(4,))
            sample = watchdog.watch_jit(
                "decode_window_sample",
                lambda p, t, pos, bt, c, sl, eos, alive, rng, seeds, g0, \
                temp, topp, topk, lb, aid, ss, wt=None, _K=K:
                paged_decode_window(
                    cfg, p, t, pos, bt, c, sl, eos, sm.block_size,
                    _K, rng=rng, row_seeds=seeds, gen_idx0=g0,
                    temp=temp, topp=topp, topk=topk,
                    use_kernel=use_kernel, topo=topo, lora=lb,
                    adapter_ids=aid, alive=alive, state_slots=ss,
                    window_tables=wt),
                donate_argnums=(4,))
            return greedy, sample

        self._build_fused_pair = _build_fused_pair
        # windows whose programs have actually run (and therefore
        # compiled for the current buckets): the online adapter's
        # steady-state move set
        self._warmed_windows: set = set()
        self._fused_greedy_jit, self._fused_sample_jit = \
            self._fused_pair(self.decode_window)
        self._row_state_sharding = NamedSharding(self.mesh, P())
        # ragged unified step (kernels/ragged_attention.py +
        # ragged/batch.py): every mixed prefill+decode composition runs
        # as ONE program keyed by (token bucket, row bucket, table-width
        # bucket); put() and the SplitFuse scheduler have no other way
        # in. The ragged kernel shares the decode kernel's gates (no
        # alibi, tp=ep=1; int8 kv_quant pools ride the same kernels);
        # gated-off configs serve through the jnp ragged fallback inside
        # the same unified program.
        # read by benchmark/runners/generate.py's log line and by
        # nothing else: it goes when that read does (ROADMAP D11)
        self.ragged_enabled = True
        self._ragged_jit = watchdog.watch_jit(
            "ragged_step",
            lambda p, ids, rows, pos, ln, wb, wo, bt, li, c, lb, aid, ss,
            wt=None: paged_ragged_step(
                cfg, p, ids, rows, pos, ln, wb, wo, bt, li, c,
                sm.block_size, use_kernel=use_kernel, topo=topo,
                lora=lb, adapter_ids=aid, state_slots=ss,
                window_tables=wt),
            donate_argnums=(9,))
        # generate()'s pick of the first token over the prompt's logits
        # where they are, [row bucket, vocab] on the device: programs of
        # their own, so that the ragged step's stays what put() runs
        self._first_greedy_jit = watchdog.watch_jit(
            "first_token_greedy", lambda logits: greedy_tokens(logits))
        self._first_sample_jit = watchdog.watch_jit(
            "first_token_sample",
            lambda logits, rng, seeds, g0, temp, topp, topk:
            sample_tokens_rowwise(logits, fold_in_rows(rng, seeds, g0),
                                  temp, topp, topk))
        # speculative verification: greedy ids for a static window of
        # fed positions from one fused continuation pass (prompt-lookup
        # decoding); one compiled program per window size
        self._continue_spec_jits: Dict[int, object] = {}

        def _spec_jit(window: int):
            if window not in self._continue_spec_jits:
                self._continue_spec_jits[window] = watchdog.watch_jit(
                    f"spec_verify_w{window}",
                    lambda p, ids, s, n, c, b, o, t, lb, aid:
                    paged_continue(
                        cfg, p, ids, s, n, c, b, o, t, sm.block_size,
                        topo=topo, greedy_window=window, lora=lb,
                        adapter_ids=aid),
                    donate_argnums=(4,))
            return self._continue_spec_jits[window]

        self._spec_jit = _spec_jit
        if config.kv_quant:
            # the capacity win, as a live gauge: pool bytes the int8
            # layout frees vs the same (num_blocks x block_size) pool at
            # the serving dtype
            pool = jax.eval_shape(lambda: init_paged_kv_cache(
                cfg, sm.num_blocks, sm.block_size, self.dtype))
            unquant, quant = (
                sum(int(np.prod(v.shape)) * v.dtype.itemsize
                    for v in c.values())
                for c in (pool, self.kv_cache))
            self._m_kv_quant_saved.set(max(unquant - quant, 0))
        try:  # HBM accounting (telemetry/memory.py): the two big
            # long-lived buffers every decode program references
            ds_memory.record_buffer(
                "latent_pool" if cfg.attention == "mla" else "kv_pool",
                ds_memory.tree_bytes(self.kv_cache))
            ds_memory.record_buffer("params",
                                    ds_memory.tree_bytes(self.params))
            self._m_state_bytes.set(ds_memory.tree_bytes(
                {k: v for k, v in self.kv_cache.items()
                 if k in STATE_LEAVES}))
            self._m_ssm_groups.set(
                cfg.mamba_n_groups if cfg.leaf_places("ssm") else 0)
            # (the indexer's keys lie on the full tables: "full")
            for kind in ("full", "window"):
                self._m_pool_bytes.labels(kind=kind).set(
                    ds_memory.tree_bytes({
                        k: v for k, v in self.kv_cache.items()
                        if k not in STATE_LEAVES
                        and k.endswith("_window") == (kind == "window")}))
        except Exception:  # accounting must never block serving
            pass
        log_dist(
            f"ragged inference engine: blocks={sm.num_blocks}x"
            f"{sm.block_size} max_seqs={sm.max_tracked_sequences} tp={tp}"
            f" ep={ep} attention={self.attention_impl}",
            ranks=[0])

    @staticmethod
    def _refuse_for_latent(config, cfg):
        """What this engine does not do for a model served by the walk
        of runs: an attention='mla' model, one whose layer pattern
        keeps recurrent state a sequence (``cfg.has_state``), and a
        pattern over per-head attention (``cfg.layer_types``), whose
        window layers keep a ring in a pool of their own; said at
        construction rather than run wrong."""
        who, refused = (InferenceEngineV2._pattern_refusals(config, cfg)
                        if cfg.layer_types is not None
                        and cfg.attention != "mla"
                        else InferenceEngineV2._latent_refusals(config, cfg))
        bad = [what for what, on in refused.items() if on]
        if bad:
            raise NotImplementedError(
                who + " is served without: " + "; ".join(bad))

    @staticmethod
    def _pattern_refusals(config, cfg):
        sm = config.state_manager
        state = cfg.has_state
        return ("a layer_types pattern (" + (
            "power-retention layers with a power-kernel state a key/value "
            "head and sequence" + ("" if cfg.caches_positions else
                                   ", no position cached at all")
            if "retention" in cfg.layer_kinds else
            "short-convolution layers with a row's last inputs a "
            "sequence beside per-head attention"
            if "conv" in cfg.layer_kinds else
            "state-space layers with a recurrent state a sequence beside "
            "per-head attention" if state else
            "window and full per-head layers, a cache of two geometries")
                + ")", {
            "tensor_parallel_size > 1 (the pattern's kernels and "
            "stacks are written for one device)":
                config.tensor_parallel_size > 1,
            "expert_parallel_size > 1 (the expert layer as deployed "
            "is served at ep = 1)": config.expert_parallel_size > 1,
            "quant_bits (the quantiser does not know a stack a layer "
            "kind, and the expert stack is read whole)":
                bool(config.quant_bits),
            "max_lora_adapters (the bank is one stack of every "
            "layer's wq / wv; a pattern keeps a stack a kind)":
                config.max_lora_adapters > 0,
            "enable_prefix_caching (a shared block holds the full "
            "layers' keys and values only: a row that skipped a "
            "prefix would " + (
                "start from the wrong recurrent state" if state
                else "find its ring empty") + ")":
                sm.enable_prefix_caching,
            "enable_kv_spill (the spill tier moves the blocks of one "
            "geometry and no " + ("state slot" if state else "ring") + ")":
                sm.enable_kv_spill,
            "kv_quant (an int8 pool has not been served beside state "
            "leaves)": state and config.kv_quant})

    @staticmethod
    def _latent_refusals(config, cfg):
        sm = config.state_manager
        state = cfg.has_state
        if cfg.layer_types is not None:
            return InferenceEngineV2._latent_pattern_refusals(config, cfg)
        return "attention='mla'" + (
            " with linear-attention layers" if state else ""), {
            "tensor_parallel_size > 1 (the latent projections and the "
            "kernel are written for one device)":
                config.tensor_parallel_size > 1,
            "expert_parallel_size > 1 (the expert layer as deployed is "
            "served at ep = 1)": config.expert_parallel_size > 1,
            "quant_bits (the quantiser does not know the two stacks, and "
            "the expert stack is read whole)": bool(config.quant_bits),
            "max_lora_adapters (LoRA targets wq / wv, which the latent "
            "projections replace)": config.max_lora_adapters > 0,
            "enable_prefix_caching (the prefix index has not been shown "
            "to share latent blocks" + (
                ", and a shared block carries no recurrent state: a row "
                "that skipped a prefix would start from the wrong state"
                if state else "") + ")": sm.enable_prefix_caching,
            "enable_kv_spill (the spill tier moves k / v leaves" + (
                " and no state slot" if state else "") + ")":
                sm.enable_kv_spill,
            "kv_quant (the int8 latent pool has not been served beside "
            "state leaves)": state and config.kv_quant}

    @staticmethod
    def _latent_pattern_refusals(config, cfg):
        """A ``layer_types`` pattern over latent attention: two latent
        kinds, the second's rows a ring, and (``cfg.index_topk``) the
        indexer's keys beside the first's."""
        sm = config.state_manager
        picks = bool(cfg.index_topk)
        return ("a layer_types pattern over latent attention (full "
                "latent layers" + (" that read the positions an indexer "
                                   "picks" if picks else "")
                + " beside latent layers over a ring)"), {
            "tensor_parallel_size > 1 (the two kinds' projections, the "
            "indexer and the selected read are written for one device; "
            "the index keys and the ring are not sharded)":
                config.tensor_parallel_size > 1,
            "expert_parallel_size > 1 (the expert layer as deployed is "
            "served at ep = 1)": config.expert_parallel_size > 1,
            "quant_bits (the quantiser does not know a stack a latent "
            "kind, and the expert stack is read whole)":
                bool(config.quant_bits),
            "max_lora_adapters (LoRA targets wq / wv, which the latent "
            "projections replace)": config.max_lora_adapters > 0,
            "enable_prefix_caching (a shared block holds the full "
            "layers' rows" + (" and index keys" if picks else "")
            + " only: a row that skipped a prefix would find its ring "
            "empty)": sm.enable_prefix_caching,
            "enable_kv_spill (the spill tier moves the blocks of one "
            "geometry: no ring" + (" and no index key" if picks else "")
            + ")": sm.enable_kv_spill,
            "kv_quant (an int8 latent pool has no form beside a ring"
            + (" or under a selection: a selected row is gathered as it "
               "is stored" if picks else "") + ")": config.kv_quant}

    # ------------------------------------------------------------------
    # Telemetry (unified registry, telemetry/registry.py)
    # ------------------------------------------------------------------
    def _note_moe(self, program: str, tokens: int, stats=None) -> None:
        """What the launch's expert layers routed (the latent programs'
        middle output, ``paged_model._pattern_step``, fetched with the
        launch's own result) into the registry's counters. ``tokens``:
        the flat tokens an expert layer of the program takes, which say
        how its routed rows came back from expert order
        (``paged_model.moe_rows_form``)."""
        if stats is None:
            return
        launches, rows, touched, share = (float(v) for v in stats)
        self._m_moe_combined.labels(
            program=program, form=moe_rows_form(
                self.model.cfg, tokens, self.dtype)).inc(rows)
        self._m_moe_launches.labels(program=program).inc(launches)
        self._m_moe_share_runs.labels(program=program).inc(
            launches * moe_share_runs(self.model.cfg, tokens,
                                      self.dtype)[0])
        self._m_moe_form_launches.labels(
            program=program, form=self.model.cfg.moe_expert_form).inc(
            launches)
        self._m_moe_rows.labels(program=program).inc(rows)
        self._m_moe_touched.labels(program=program).inc(touched)
        self._m_moe_share.labels(program=program).set(share)

    def _init_telemetry(self):
        from ...telemetry import collector, get_registry
        collector.install_gc_hook()
        # the calling thread's CPU, run-queue wait, switches and faults
        # over every launch span and generate() call, and the judgement
        # of a closed call's leaves (a ``host_stall``), a kind of leaf
        # being its name under these attrs of its own and its launch's
        self._host = collector.HostThread(
            "generate", kind_attrs=("program", "ahead", "chunk"))
        reg = get_registry()
        self._m_moe_launches = reg.counter(
            "moe_launches_total",
            "expert-layer passes run by the latent block's programs (a "
            "ragged step: one an expert layer; a decode window: one an "
            "expert layer and step)", labelnames=("program",))
        self._m_moe_form_launches = reg.counter(
            "moe_form_launches_total",
            "moe_launches_total by the form of expert the pass ran: "
            "swiglu (three matrices) or relu2 (two, a squared ReLU)",
            labelnames=("program", "form"))
        self._m_moe_rows = reg.counter(
            "moe_routed_rows_total",
            "rows routed to experts (valid tokens x top-k), summed over "
            "expert layers and launches", labelnames=("program",))
        self._m_moe_combined = reg.counter(
            "moe_rows_combined_total",
            "moe_routed_rows_total by the form that brought the rows back "
            "from expert order, chosen from the launch's shape: kernel "
            "(moe_rows_whole + moe_rows_combine: a share's prompt launch "
            "on a TPU) or gather (XLA's: every other launch)",
            labelnames=("program", "form"))
        self._m_moe_share_runs = reg.counter(
            "moe_share_runs_total",
            "runs of tokens a share's launches went through the experts "
            "in (an expert-layer pass over a run: paged_model."
            "moe_share_runs), from the launch's shape; over "
            "moe_launches_total the runs a pass: 0 where a pass is one "
            "dispatch (every expert held, a decode step)",
            labelnames=("program",))
        self._m_moe_touched = reg.counter(
            "moe_experts_touched_total",
            "distinct experts with at least one row, summed over expert "
            "layers and launches", labelnames=("program",))
        self._m_moe_share = reg.gauge(
            "moe_fullest_expert_share",
            "the fullest expert's share of an expert layer's rows, the "
            "largest of the last launch", labelnames=("program",))
        self._m_prefill_tokens = reg.counter(
            "inference_prefill_tokens_total",
            "prompt tokens run through prefill/continuation passes")
        self._m_decode_tokens = reg.counter(
            "inference_decode_tokens_total",
            "tokens produced by batched decode steps")
        self._m_decode_steps = reg.counter(
            "inference_decode_steps_total", "batched decode passes")
        self._m_decode_time = reg.histogram(
            "inference_decode_step_seconds",
            "batched decode pass wall time (a per-token step, or a fused "
            "window from the start of its launch to its tokens on the "
            "host)", unit="s")
        self._m_decode_tput = reg.gauge(
            "inference_decode_tokens_per_s",
            "last decode pass throughput (batch tokens / wall time)")
        self._m_ttft = reg.histogram(
            "inference_ttft_seconds",
            "generate(): time to the first token batch", unit="s")
        self._m_kv_util = reg.gauge(
            "inference_kv_pool_utilization",
            "fraction of usable KV blocks currently allocated")
        self._m_kv_util_peak = reg.gauge(
            "inference_kv_pool_utilization_peak",
            "high-water mark of inference_kv_pool_utilization")
        self._m_tracked = reg.gauge(
            "inference_tracked_sequences", "sequences with live KV state")
        self._m_state_bytes = reg.gauge(
            "inference_state_bytes",
            "bytes of the recurrent-state leaves (linear-attention, "
            "state-space, power-retention or short-convolution layers: "
            "every slot of every such layer, the null slot included); 0 "
            "for a model that keeps none", unit="bytes")
        self._m_state_slots = reg.gauge(
            "inference_state_slots_in_use",
            "recurrent-state slots owned by tracked sequences")
        self._m_state_rows = reg.counter(
            "inference_state_rows_total",
            "rows whose recurrent state a launch read and wrote, by "
            "program (a fused window counts a row once a step it may "
            "take)", labelnames=("program",))
        self._m_conv_tokens = reg.counter(
            "inference_conv_state_tokens_total",
            "tokens that passed through a short-convolution layer's "
            "state, by program: launches x conv layers x tokens, from "
            "the host's own shapes (0 for a model without such layers)",
            labelnames=("program",))
        self._m_chunk_kernel_steps = reg.counter(
            "inference_linear_chunk_kernel_steps_total",
            "ragged steps launched whose linear-attention layers ran "
            "their chunked form as the kernel kda_chunk_fwd (0 for a "
            "model without such layers, and where the backend or the "
            "widths leave it to the XLA form)")
        self._m_conv_kernel_steps = reg.counter(
            "inference_linear_conv_kernel_steps_total",
            "decode steps launched whose linear-attention layers ran "
            "their short convolution as the kernel kda_conv_update (a "
            "fused window counts its steps; 0 for a model without such "
            "layers, and where the backend or the widths leave it to "
            "the XLA form)")
        self._m_ssm_state_kernel_steps = reg.counter(
            "inference_ssm_state_kernel_steps_total",
            "decode steps launched whose state-space layers ran their "
            "one-token update as the kernel ssm_state_update (a fused "
            "window counts its steps; 0 for a model without such layers, "
            "and where the backend or the widths leave it to the XLA form)")
        self._m_retention_state_kernel_steps = reg.counter(
            "inference_retention_state_kernel_steps_total",
            "decode steps whose power-retention layers ran their "
            "one-token update as the kernel retention_state_update (a "
            "fused window counts its steps; 0 for a model without such "
            "layers and where the XLA twin runs: off a TPU, heads not 128 "
            "wide)")
        self._m_retention_chunk_kernel_launches = reg.counter(
            "inference_retention_chunk_kernel_launches_total",
            "ragged steps whose power-retention layers ran their chunked "
            "form as the kernel retention_chunk_fwd (0 for a model "
            "without such layers and where the XLA twin runs)")
        self._m_ssm_groups = reg.gauge(
            "inference_ssm_groups",
            "groups of B and C a token the state-space layers' programs "
            "were built for (mamba_n_groups; 0 for a model without such "
            "layers)")
        self._m_ssm_scan_kernel_steps = reg.counter(
            "inference_ssm_scan_kernel_steps_total",
            "ragged steps launched whose state-space layers ran their "
            "chunked form as the kernel ssm_chunk_fwd (0 for a model "
            "without such layers, and where the backend or the widths "
            "leave it to the XLA form)")
        self._m_one_token_steps = reg.counter(
            "inference_attention_one_token_steps_total",
            "decode steps launched whose attention kernels took the "
            "one-token form: a row's pages against that row's own query "
            "rows (a fused window counts its steps; 0 off the TPU and "
            "where the decode programs run no tiled or latent kernel)")
        self._m_decode_positions = reg.counter(
            "inference_attention_decode_positions_total",
            "cached positions under the one-token form's launches, by "
            "kind: \"held\" (what the rows' pages hold, to whole pages; a "
            "window layer's from its window's first page) and \"chunked\" "
            "(what the walk's chunks hold whole); held / chunked is the "
            "share of a chunk that is there. A position a layer, "
            "row and step, from the contexts the manager holds at the "
            "launch; 0 wherever inference_attention_one_token_steps_total "
            "is", labelnames=("kind",))
        self._m_index_queries = reg.counter(
            "inference_index_queries_total",
            "query tokens of the full latent layers of a model whose "
            "indexer picks what they read (index_topk): one a token and "
            "full layer, by program", labelnames=("program",))
        self._m_index_attended = reg.counter(
            "inference_index_positions_attended_total",
            "cached positions those queries ATTENDED: min(the token's "
            "bound, index_topk) a query, whatever read them",
            labelnames=("program",))
        self._m_index_read = reg.counter(
            "inference_index_positions_read_total",
            "cached positions whose rows those queries' attention READ: "
            "what it attended in a decode step (a token gathers the rows "
            "it picked), the token's whole bound in a prompt's launch "
            "(the picks a mask over the row's pages read once, or over "
            "the per-head keys and values made of them: "
            "inference_index_prompt_launches_total says which) and under "
            "tables of no more than index_topk positions (the dense "
            "launch); "
            "over inference_index_queries_total the mean positions read "
            "a query", labelnames=("program",))
        self._m_index_prompt_launches = reg.counter(
            "inference_index_prompt_launches_total",
            "ragged steps' launches of a full latent layer that SELECT "
            "(tables wider than index_topk), a launch a full layer, by "
            "the form the launch's static shapes gave it "
            "(paged_model.index_prompt_form): \"expanded\" (a row's "
            "cached latent rows made per-head keys and values once, a "
            "per-head kernel under the picks' mask) or \"absorbed\" "
            "(the masked latent kernel: a launch of few tokens a row)",
            labelnames=("form",))
        self._m_index_scored = reg.counter(
            "inference_index_positions_scored_total",
            "cached positions the model's equations have the indexer "
            "score for those queries: a token's bound, under the launches "
            "that select (0 under the dense ones). The LEAST a program "
            "can score, not what it did: "
            "inference_index_positions_swept_total", labelnames=("program",))
        self._m_index_swept = reg.counter(
            "inference_index_positions_swept_total",
            "cached positions the indexer's products COVERED for those "
            "queries: a token's tile scores whole chunks of 4,096 "
            "positions as far as the tile's largest bound reaches "
            "(paged_model.index_positions_swept: from the rows' tokens "
            "and contexts at the launch, no device read); over "
            "inference_index_positions_scored_total the over-scoring, 1 "
            "at the least", labelnames=("program",))
        self._m_prompt_chunks = reg.counter(
            "inference_attention_prompt_chunks_total",
            "chunk visits of the token tile's launches (a ragged step's "
            "attention where the tiled kernel serves), by kind: \"whole\" "
            "(a tile of one row's tokens that all see every position of "
            "the chunk) and \"masked\" (an edge of a bound or a window, a "
            "tile of several rows or of a row's tail). A visit a layer, "
            "tile, row and chunk of 512 positions: what a launch's time "
            "goes by, and what a larger tile halves; from the rows' "
            "tokens and contexts at the launch, no device read; 0 off "
            "the TPU and for a latent pool", labelnames=("kind",))
        self._m_copy_pages = reg.counter(
            "inference_attention_copy_pages_total",
            "pages a leaf's copies brought under the tiled attention "
            "kernel's launches (the one-token form's and the token "
            "tile's): a page a layer, walk and place, by the host's own "
            "block tables at the launch; 0 off the TPU and for a latent "
            "pool")
        self._m_copy_descriptors = reg.counter(
            "inference_attention_copy_descriptors_total",
            "copies a leaf's pages were started as under the same "
            "launches, by the cut the kernel makes of the table "
            "(kernels/ragged_attention.copy_counts): a run of pages on "
            "consecutive blocks a few descriptors, a page that lies alone "
            "one. pages / descriptors is 1.0 over a pool with no two "
            "neighbouring places on neighbouring blocks (and wherever "
            "the launches are handed no runs: a pool whose page is 32 KB "
            "a leaf and more) and a chunk's 32 where every chunk lies "
            "together")
        self._m_prefill_chunks = reg.counter(
            "inference_prefill_chunks_total",
            "ragged steps put() ran for a prompt set it fed in chunks (a "
            "call that fits one step counts none)")
        self._m_pool_bytes = reg.gauge(
            "inference_kv_pool_bytes",
            "bytes of the cache's key / value (or latent) leaves, scales "
            "included, by geometry: \"full\" (a sequence holds every "
            "position) and \"window\" (a ring a sequence: the "
            "window-attention layers of a layer_types pattern)",
            unit="bytes", labelnames=("kind",))
        self._m_blocks_in_use = reg.gauge(
            "inference_kv_blocks_in_use",
            "blocks owned by tracked sequences or the prefix index, by "
            "geometry", labelnames=("kind",))
        self._m_spec_drafted = reg.counter(
            "inference_spec_drafted_tokens_total",
            "speculative tokens drafted for verification")
        self._m_spec_accepted = reg.counter(
            "inference_spec_accepted_tokens_total",
            "speculative tokens accepted by greedy verification")
        self._m_spec_miss_rounds = reg.counter(
            "inference_spec_miss_rounds_total",
            "speculative rounds whose whole draft was rejected")
        self._m_spec_window_rounds = reg.counter(
            "inference_spec_window_rounds_total",
            "draft-model propose->verify->accept rounds run inside "
            "fused speculative decode windows (per-row, summed on "
            "device)")
        self._m_spec_mode_requests = reg.counter(
            "inference_spec_mode_requests_total",
            "speculative requests routed per speculation source",
            labelnames=("mode",))
        self._m_spec_switches = reg.counter(
            "inference_spec_chooser_switches_total",
            "speculation-source switches committed by the hysteresis "
            "chooser")
        self._m_spec_rate = reg.gauge(
            "inference_spec_accept_rate",
            "EMA accept rate (accepted/drafted) per speculation source",
            labelnames=("mode",))
        self._m_adapter_loads = reg.counter(
            "inference_lora_adapter_loads_total",
            "LoRA adapters (re)loaded into device bank slots")
        self._m_adapters_live = reg.gauge(
            "inference_lora_adapters_live",
            "adapter names currently resident in the device bank")
        self._m_window_size = reg.gauge(
            "inference_decode_window_size",
            "configured fused decode window K (1 = per-token decode)")
        self._m_host_syncs = reg.counter(
            "inference_decode_host_syncs_total",
            "device->host transfers made by the decode loop (one per "
            "per-token step, one per fused multi-step window)")
        self._m_windows_ahead = reg.counter(
            "inference_decode_windows_ahead_total",
            "fused decode windows launched while the window before them "
            "was still in flight (generate() launches ahead; a window "
            "launched after its predecessor's tokens were fetched does "
            "not count)")
        self._m_fused_time = reg.histogram(
            "inference_fused_window_seconds",
            "fused multi-step decode window wall time, from the start of "
            "its launch to its tokens on the host (under launch-ahead "
            "that spans the fetch of the window before it)", unit="s")
        self._m_ragged_steps = reg.counter(
            "inference_ragged_steps_total",
            "unified ragged steps run (mixed prefill+decode, one "
            "compiled program per step)")
        self._m_ragged_tokens = reg.counter(
            "inference_ragged_tokens_total",
            "valid tokens run through unified ragged steps")
        self._m_ragged_prefill_rows = reg.counter(
            "inference_ragged_prefill_rows_total",
            "ragged rows carrying prompt/continuation chunks")
        self._m_ragged_decode_rows = reg.counter(
            "inference_ragged_decode_rows_total",
            "ragged rows carrying a single decode token")
        self._m_ragged_time = reg.histogram(
            "inference_ragged_step_seconds",
            "unified ragged step wall time, from the start of its pack "
            "to the host's seeing it end (under launch-ahead that spans "
            "the wait for the step before it)", unit="s")
        self._m_ragged_pad = reg.gauge(
            "inference_ragged_pad_fraction",
            "padding waste of the last ragged step's token bucket")
        self._m_ragged_host_syncs = reg.counter(
            "inference_ragged_host_syncs_total",
            "unified ragged steps the host waited for with nothing "
            "queued behind them (one a put(), a step_ragged() and a "
            "generate() call: the chunk steps before the last are "
            "launched ahead)")
        self._m_logits_fetched = reg.counter(
            "inference_ragged_logits_fetched_bytes_total",
            "bytes of [row bucket, vocab] logits brought to the host by "
            "put() and step_ragged() (generate() picks its first token "
            "on the device and fetches none)", unit="bytes")
        self._m_kv_quant_saved = reg.gauge(
            "inference_kv_pool_quant_bytes_saved",
            "HBM the int8 KV pool frees vs the same pool at the serving "
            "dtype (0 when kv_quant is off) — the capacity headroom that "
            "admits ~2x concurrent sequences", unit="bytes")
        self._m_weight_swaps = reg.counter(
            "inference_weight_swaps_total",
            "live param hot-swaps applied to this engine (donated "
            "buffer replacement; zero recompiles by construction)")
        self._m_weight_swap_time = reg.histogram(
            "inference_weight_swap_seconds",
            "param hot-swap apply time (device_put of every leaf onto "
            "its existing sharding)", unit="s",
            buckets=(1e-4, 1e-3, 1e-2, 0.1, 1.0, 10.0))
        self._m_weight_version = reg.gauge(
            "serving_weight_version",
            "live weight version this engine serves (0 = the boot "
            "checkpoint; bumped by each hot-swap)")

    def _update_pool_telemetry(self):
        sm = self.state_manager
        # block 0 is the null; a model that caches no position has no
        # other (``DSStateManager``'s ``paged``), and reads 0 throughout
        usable = max(sm.allocator.num_blocks - 1, 1)
        util = (sm.allocator.num_blocks - 1 - sm.free_blocks()) / usable
        self._m_kv_util.set(util)
        # the live gauge reads 0 between requests (flush returns blocks),
        # so pool-pressure tuning needs the high-water mark too
        if util > self._m_kv_util_peak.value:
            self._m_kv_util_peak.set(util)
        self._m_tracked.set(sm.tracked_sequences())
        self._m_state_slots.set(sm.state_slots_in_use())
        self._m_blocks_in_use.labels(kind="full").set(
            sm.allocator.num_blocks - 1 - sm.free_blocks())
        self._m_blocks_in_use.labels(kind="window").set(
            sm.window_blocks_in_use())

    # ------------------------------------------------------------------
    # Fused decode window K: per-K jit cache + live adaptation
    # ------------------------------------------------------------------
    def _fused_pair(self, window: int):
        if window not in self._fused_jit_cache:
            self._fused_jit_cache[window] = self._build_fused_pair(window)
        return self._fused_jit_cache[window]

    def warmed_decode_windows(self):
        """Window sizes whose decode program has dispatched at least
        once (so its compiled program is cached for the buckets traffic
        actually uses) — the only K values the online adapter may move
        to at steady state."""
        return sorted(self._warmed_windows)

    def set_decode_window(self, window: int, *,
                          source: str = "online") -> int:
        """Switch the fused decode window K at runtime
        (autotuning/online.py actuates here; must be called from the
        thread that owns the engine). Swaps the per-K jit pair, so an
        already-warmed K never recompiles; a brand-new K compiles on
        its next dispatch like any cold program."""
        from ...runtime import tunables
        window = tunables.check("serving.decode_window", window,
                                label="decode_window")
        if window == self.decode_window:
            return window
        self._fused_greedy_jit, self._fused_sample_jit = \
            self._fused_pair(window)
        self.decode_window = window
        self.config.decode_window = window
        self._m_window_size.set(window)
        tunables.observe("serving.decode_window", window, source)
        flight.record("tunable_set", name="serving.decode_window",
                      value=window, source=source)
        return window

    # ------------------------------------------------------------------
    # Multi-tenant batched LoRA (config_v2.max_lora_adapters)
    # ------------------------------------------------------------------
    def load_adapter(self, name: str, adapters: Dict[str, tuple],
                     scale: float = 1.0) -> int:
        """Install a LoRA adapter into a device bank slot (hot-deploy:
        a same-shape ``.at[:, slot].set`` — no recompile, serving
        continues through the same programs).

        ``adapters`` is the hybrid engine's external-adapter payload
        convention (``runtime/hybrid_engine.py fuse_flat_leaves``):
        ``{"layers/wq": (a, b), "layers/wv": (a, b)}`` with a [L, h, r]
        and b [L, r, out]. ``scale`` folds into b at load time so the
        gathered per-row delta matches the fused-weight definition
        ``_fused_w``: w + scale * (a @ b). Ranks below the bank rank
        zero-pad (extra rank contributes exactly 0); larger ranks are a
        typed error. Re-loading a known name updates its slot in place
        (hot redeploy of a freshly trained adapter). Returns the slot."""
        if self.lora_bank is None:
            raise ValueError(
                "adapter bank disabled: set max_lora_adapters > 0 in "
                "RaggedInferenceEngineConfig")
        from ...models.transformer import lora_target_leaves
        cfg = self.model.cfg
        targets = lora_target_leaves(cfg)
        if set(adapters) != set(targets):
            raise ValueError(
                f"adapter {name!r} leaves {sorted(adapters)} != serving "
                f"targets {sorted(targets)} (q/v projections only)")
        R = self.config.lora_rank
        L = cfg.num_layers
        staged = {}
        for leaf, keys in (("layers/wq", ("qa", "qb")),
                           ("layers/wv", ("va", "vb"))):
            a, b = adapters[leaf]
            a = np.asarray(a, np.float32)
            b = np.asarray(b, np.float32)
            in_dim, out_dim = targets[leaf]
            if (a.ndim != 3 or b.ndim != 3 or a.shape[0] != L
                    or b.shape[0] != L or a.shape[1] != in_dim
                    or b.shape[2] != out_dim or a.shape[2] != b.shape[1]):
                raise ValueError(
                    f"adapter {name!r} leaf {leaf}: got a{a.shape} "
                    f"b{b.shape}, want a[{L},{in_dim},r] "
                    f"b[{L},r,{out_dim}]")
            r = a.shape[2]
            if r > R:
                raise ValueError(
                    f"adapter {name!r} rank {r} exceeds bank rank {R} "
                    f"(config_v2.lora_rank)")
            if r < R:   # zero-pad: the extra rank contributes exactly 0
                a = np.concatenate(
                    [a, np.zeros((L, in_dim, R - r), a.dtype)], axis=2)
                b = np.concatenate(
                    [b, np.zeros((L, R - r, out_dim), b.dtype)], axis=1)
            staged[keys] = (a, b * float(scale))
        slot = self._adapter_slots.get(name)
        if slot is None:
            used = set(self._adapter_slots.values())
            slot = next(
                (s for s in range(1, self.config.max_lora_adapters + 1)
                 if s not in used), None)
            if slot is None:
                raise RuntimeError(
                    f"adapter bank full "
                    f"({self.config.max_lora_adapters} slots); "
                    f"unload_adapter() one or raise max_lora_adapters")
        bank = self.lora_bank
        for (ka, kb), (a, b) in staged.items():
            bank[ka] = bank[ka].at[:, slot].set(jnp.asarray(a, self.dtype))
            bank[kb] = bank[kb].at[:, slot].set(jnp.asarray(b, self.dtype))
        self.lora_bank = bank
        self._adapter_slots[name] = slot
        self._m_adapter_loads.inc()
        self._m_adapters_live.set(len(self._adapter_slots))
        flight.record("adapter_load", name=str(name), slot=int(slot))
        return slot

    def unload_adapter(self, name: str) -> None:
        """Zero the adapter's slot (back to the base no-op delta) and
        free it for reuse; uids still routed to the name fall back to
        the base model."""
        slot = self._adapter_slots.pop(name, None)
        if slot is None:
            return
        bank = self.lora_bank
        for k in bank:
            bank[k] = bank[k].at[:, slot].set(
                jnp.zeros(bank[k].shape[2:], bank[k].dtype))
        self.lora_bank = bank
        self._uid_adapter = {u: n for u, n in self._uid_adapter.items()
                             if n != name}
        self._m_adapters_live.set(len(self._adapter_slots))

    def assign_adapter(self, uid: int, name: Optional[str]) -> int:
        """Route ``uid``'s tokens through a loaded adapter's bank slot
        (None/"" clears to the base slot 0). Typed failure at SUBMIT
        time when the adapter is unknown — not mid-batch on device."""
        uid = int(uid)
        if not name:
            self._uid_adapter.pop(uid, None)
            return 0
        if self.lora_bank is None:
            raise ValueError(
                f"adapter {name!r} requested but the bank is disabled "
                f"(max_lora_adapters=0)")
        slot = self._adapter_slots.get(name)
        if slot is None:
            raise KeyError(
                f"unknown adapter {name!r}: load_adapter() it first "
                f"(loaded: {sorted(self._adapter_slots)})")
        self._uid_adapter[uid] = str(name)
        seq = self.state_manager.seqs.get(uid)
        if seq is not None:
            seq.adapter = str(name)
            seq.adapter_slot = int(slot)
        return slot

    def adapter_of(self, uid: int) -> Optional[str]:
        """The adapter NAME serving ``uid`` (None = base). Names — not
        engine-local slot ints — are the identity prefix digests and
        router affinity key on, so they agree across replicas."""
        return self._uid_adapter.get(int(uid))

    def _adapter_slot_of(self, uid: int) -> int:
        name = self._uid_adapter.get(int(uid))
        if name is None:
            return 0
        return self._adapter_slots.get(name, 0)

    # ------------------------------------------------------------------
    # Schedulability (reference engine_v2.py:135 query / :161 can_schedule)
    # ------------------------------------------------------------------
    def query(self, uid: int) -> Dict[str, int]:
        seq = self.state_manager.seqs.get(uid)
        return {
            "seen_tokens": seq.seen_tokens if seq else 0,
            "free_blocks": self.state_manager.free_blocks(),
            **({"free_window_blocks":
                self.state_manager.window_allocator.free_blocks}
               if self._has_ring else {}),
            "tracked_sequences": self.state_manager.tracked_sequences(),
            "max_seq_len": self.state_manager.config.max_seq_len,
        }

    def can_schedule(self, uids: Sequence[int],
                     lengths: Sequence[int]) -> bool:
        """Whether ONE step can take ``lengths[i]`` more tokens of each
        ``uids[i]``: both pools hold them (:meth:`_can_hold`), the step
        its token budget, and a row of a model whose window layers keep
        a ring no more than ``max_row_chunk``."""
        return self._can_hold(uids, lengths) and self._fits_a_step(lengths)

    def _fits_a_step(self, lengths: Sequence[int]) -> bool:
        cap = self.max_row_chunk
        return sum(lengths) \
            <= self.state_manager.config.max_ragged_batch_size \
            and (cap is None or max(lengths, default=0) <= cap)

    def _can_hold(self, uids: Sequence[int],
                  lengths: Sequence[int]) -> bool:
        sm = self.state_manager
        total_new = ring_new = 0
        # retained prefix blocks are evictable on demand (ensure_blocks
        # evicts LRU) — counting only free blocks would spuriously
        # reject requests once the index occupies the pool
        free = self.state_manager.reclaimable_blocks()
        for uid, n in zip(uids, lengths):
            if not self.state_manager.can_schedule(uid, n):
                return False
            seq = sm.descriptor(uid)
            total_new += seq.blocks_needed(n, self.block_size)
            ring_new += seq.window_blocks_needed(n, self.block_size,
                                                 sm.ring_blocks)
        return total_new <= free and (
            not ring_new or ring_new <= sm.window_allocator.free_blocks)

    # ------------------------------------------------------------------
    # Bucketing (shared rules: utils/bucketing.py — the same helpers key
    # the RaggedBatch packer, so every layer buckets identically)
    # ------------------------------------------------------------------
    def _bucket(self, n: int) -> int:
        """Chunk-length bucket of the one-sequence passes, the n-gram
        verify and the draft model's catch-up (multiple of
        prefill_bucket, capped at the max_seq_len bucket)."""
        return ceil_bucket(n, self.config.prefill_bucket,
                           cap=self.state_manager.config.max_seq_len)

    def _chunk_inputs(self, uid: int, start: int, tokens: np.ndarray):
        """``tokens`` of ONE tracked sequence from position ``start``, as
        ``paged_continue`` takes them (the sequence's blocks hold them
        already): ids [1, C] padded to the chunk bucket, ``start``, the
        count, each chunk position's (block, slot) with padding on the
        null block, and the sequence's full table."""
        n = len(tokens)
        C = self._bucket(n)
        ids = np.zeros((1, C), np.int32)
        ids[0, :n] = tokens
        positions = start + np.arange(C)
        table = np.full(C, NULL_BLOCK, np.int32)
        blocks = np.asarray(self.state_manager.seqs[uid].blocks, np.int32)
        table[:n] = blocks[positions[:n] // self.block_size]
        return (jnp.asarray(ids), jnp.asarray(start), jnp.asarray(n),
                jnp.asarray(table), jnp.asarray(positions % self.block_size),
                jnp.asarray(self.state_manager.block_table_for(uid)))

    def _spec_verify(self, uid: int, tokens: np.ndarray) -> np.ndarray:
        """Feed ``tokens`` to ONE tracked sequence in one compiled pass
        (``paged_continue``) and return the greedy id after each fed
        position: device-side argmax, [len(tokens)] int32 to the host.
        The n-gram speculation's verify pass; one program a window
        size (``spec_verify_w*``)."""
        sm = self.state_manager
        n = len(tokens)
        seq = sm.ensure_blocks(uid, n)
        start = seq.seen_tokens
        lb = self.lora_bank
        aid = (jnp.asarray(self._adapter_slot_of(uid), jnp.int32)
               if lb is not None else None)
        with trace.span("spec_verify", uid=int(uid), tokens=int(n),
                        **self._trace_attr(uid)):
            ids, s, fed, table, offs, full_table = \
                self._chunk_inputs(uid, start, tokens)
            greedy, self.kv_cache = self._spec_jit(n)(
                self.params, ids, s, fed, self.kv_cache, table, offs,
                full_table, lb, aid)
        seq.seen_tokens = start + n
        if sm.config.enable_prefix_caching:
            seq.token_log.extend(map(int, tokens))
        self._update_pool_telemetry()
        return np.asarray(greedy)

    # -- speculative decoding (prompt-lookup) ---------------------------
    _SPEC_SCAN_WINDOW = 512   # bound the per-round host scan (the scan
    # is O(window); an unbounded history would make draft lookup
    # quadratic over a long generation)

    @staticmethod
    def _lookup_draft(history: List[int], k: int, ngram: int) -> List[int]:
        """Draft the k tokens that followed the most recent earlier
        occurrence of the history's trailing n-gram (prompt-lookup
        decoding: the sequence's own text is the draft model). Scans at
        most the last _SPEC_SCAN_WINDOW tokens.

        This right-to-left scan is the REFERENCE implementation (O(window
        * ngram) per round); the hot path uses the incremental
        NGramIndex (ngram_index.py, parity-tested against this)."""
        W = InferenceEngineV2._SPEC_SCAN_WINDOW
        base = max(0, len(history) - W)
        win = history[base:]
        for n in range(ngram, 1, -1):
            if len(win) <= n:
                continue
            tail = win[-n:]
            # scan right-to-left for the most recent earlier match
            for i in range(len(win) - n - 1, -1, -1):
                if win[i:i + n] == tail:
                    start = base + i + n
                    draft = history[start:start + k]
                    if draft:
                        return list(draft)
        return []

    def _speculative_step(self, uid: int, cur: int,
                          draft: List[int]) -> List[int]:
        """Feed [cur] + draft through one fused continuation, accept the
        longest greedily-verified draft prefix, roll the cache position
        back over rejected tokens, and return the emitted tokens
        (1 + accepted; the last emitted token is NOT yet in the cache —
        same invariant as the normal decode loop).

        Rollback is a host-side counter reset: attention masks by
        position (ctx_pos <= pos), so the rejected tokens' stale KV
        slots are never attended and the next write overwrites them."""
        sm = self.state_manager
        seq = sm.seqs[uid]
        fed = [int(cur)] + list(map(int, draft))
        start = seq.seen_tokens
        greedy = self._spec_verify(uid, np.asarray(fed, np.int64))
        emitted = [int(greedy[0])]
        accepted = 0
        for j, d in enumerate(draft):
            if int(d) != emitted[-1]:
                break
            accepted += 1
            emitted.append(int(greedy[j + 1]))
        # rewind over the rejected fed tokens (cur + accepted stay)
        seq.seen_tokens = start + 1 + accepted
        if sm.config.enable_prefix_caching:
            rejected = len(fed) - 1 - accepted
            if rejected:
                del seq.token_log[-rejected:]
        return emitted

    def _speculative_round(self, step_uids, outs, row_of, prompt_lens,
                           live, max_new_tokens, eos_token_id,
                           spec_k, spec_ngram) -> Dict[int, int]:
        """One greedy round with prompt-lookup speculation: per uid,
        draft from its own history and verify in one fused pass. The
        accepted extras append to ``outs`` here (with per-token
        eos/budget checks); the final emitted token becomes the round's
        ``cur`` — the last-token-never-fed invariant the plain loop
        keeps. Sequences without a usable draft fall back to the normal
        batched greedy decode."""
        cur: Dict[int, int] = {}
        plain_uids: List[int] = []
        sm = self.state_manager
        for uid in step_uids:
            row = outs[row_of[uid]]
            remaining = max_new_tokens - (len(row) - prompt_lens[uid])
            # draft length budget: the generation budget, the sequence
            # length limit (1+k fed tokens must fit max_seq_len — the
            # loop's guard only covered 1), and a cold-streak cutoff
            # (natural text with recurring n-grams but divergent
            # continuations would otherwise pay a rejected verify pass
            # every round, slower than plain batched greedy)
            seq_room = sm.config.max_seq_len - sm.seqs[uid].seen_tokens - 1
            k = min(spec_k, remaining - 1, seq_room)
            if k > 0 and self._spec_miss_streak.get(uid, 0) < 3:
                idx = self._draft_index.get(uid)
                if idx is None:
                    from .ngram_index import NGramIndex
                    idx = self._draft_index[uid] = NGramIndex(
                        spec_ngram, self._SPEC_SCAN_WINDOW)
                idx.sync(row)
                draft = idx.draft(k, spec_ngram)
            else:
                draft = []
            if draft and not self.can_schedule([uid], [1 + len(draft)]):
                draft = []
            if not draft:
                plain_uids.append(uid)
                continue
            emitted = self._speculative_step(uid, row[-1], draft)
            self._m_spec_drafted.inc(len(draft))
            self._m_spec_accepted.inc(len(emitted) - 1)
            self.spec_chooser.observe("ngram", len(draft),
                                      len(emitted) - 1)
            if len(emitted) == 1:
                self._m_spec_miss_rounds.inc()
                self._spec_miss_streak[uid] = \
                    self._spec_miss_streak.get(uid, 0) + 1
            else:
                self._spec_miss_streak[uid] = 0
            finished = False
            for tok in emitted[:-1]:
                row.append(tok)
                if ((eos_token_id is not None and tok == eos_token_id)
                        or len(row) - prompt_lens[uid] >= max_new_tokens):
                    finished = True
                    break
            if finished:
                live.discard(uid)
            else:
                cur[uid] = emitted[-1]
        if plain_uids:
            cur.update(self._decode_batch_greedy(
                plain_uids, [outs[row_of[u]][-1] for u in plain_uids]))
        self._observe_spec_rates()
        return cur

    # -- draft-model speculation (in-window propose->verify->accept) ----
    def load_draft_model(self, model, params=None) -> None:
        """Attach a small draft model for in-window speculative
        decoding. The draft shares the TARGET's block tables against its
        own paged KV pool (same num_blocks x block_size geometry), so
        the fused spec window (``paged_spec_decode_window``) needs no
        extra table plumbing and rollback stays free. Raises the typed
        :class:`DraftModelMismatchError` when the draft cannot
        verify-share with the target. ``params`` defaults to a fresh
        init (tests); production passes the trained draft weights."""
        dcfg = model.cfg
        cfg = self.model.cfg
        if cfg.walks_runs or dcfg.walks_runs:
            raise NotImplementedError(
                "draft-model speculation with an attention='mla' or "
                "layer_types target or draft: the verify pass has no form "
                "for the walk of runs (and none over a ring)")
        if dcfg.vocab_size != cfg.vocab_size:
            raise DraftModelMismatchError(
                f"draft vocab_size {dcfg.vocab_size} != target "
                f"{cfg.vocab_size}: greedy verification compares raw "
                f"token ids, so the vocabularies must be the same id "
                f"space")
        sm = self.state_manager
        if dcfg.max_seq_len < sm.config.max_seq_len:
            raise DraftModelMismatchError(
                f"draft max_seq_len {dcfg.max_seq_len} < serving "
                f"max_seq_len {sm.config.max_seq_len}: the draft must "
                f"decode at every position the target serves")
        self.draft_model = model
        self._draft_cfg = dcfg
        if params is not None:
            self.draft_params = jax.jit(lambda p: jax.tree.map(
                lambda x: jnp.asarray(x, self.dtype), p))(params)
        else:
            self.draft_params = jax.jit(lambda p: jax.tree.map(
                lambda x: x.astype(self.dtype), model.init_params(p)))(
                jax.random.PRNGKey(self.config.seed + 1))
        self.draft_cache = init_paged_kv_cache(
            dcfg, sm.config.num_blocks, sm.block_size, self.dtype)
        self._draft_seen.clear()
        self._spec_window_jits.clear()
        # draft catch-up: one fused continuation over the DRAFT pool,
        # replaying history the target built through non-draft paths
        # (prefill, plain decode, n-gram rounds) before a uid's first
        # spec window
        bs = self.block_size
        self._draft_catchup_jit = watchdog.watch_jit(
            "draft_catchup",
            lambda p, ids, s, n, c, b, o, t: paged_continue(
                    dcfg, p, ids, s, n, c, b, o, t, bs, topo=None),
                donate_argnums=(4,))
        try:
            ds_memory.record_buffer(
                "draft_params", ds_memory.tree_bytes(self.draft_params))
            ds_memory.record_buffer(
                "draft_kv_pool", ds_memory.tree_bytes(self.draft_cache))
        except Exception:   # accounting must never block serving
            pass
        log_dist(
            f"draft model attached: layers={dcfg.num_layers} "
            f"hidden={dcfg.hidden_size} (target hidden="
            f"{cfg.hidden_size})", ranks=[0])

    def _spec_window_jit(self, window: int, spec_k: int):
        """Per-(window, spec_k) fused speculative window program — like
        the per-K plain-window cache, both constants are baked into the
        compiled loop, so per-request draft lengths ride a bounded jit
        cache instead of growing it. One watchdog name for all sizes."""
        key = (int(window), int(spec_k))
        if key not in self._spec_window_jits:
            cfg = self.model.cfg
            dcfg = self._draft_cfg
            bs = self.block_size
            uk, topo = self._use_kernel, self._topo
            self._spec_window_jits[key] = watchdog.watch_jit(
                "spec_decode_window",
                lambda p, dp, t, pos, bt, c, dc, sl, eos, lb, aid,
                    _K=window, _k=spec_k: paged_spec_decode_window(
                        cfg, dcfg, p, dp, t, pos, bt, c, dc, sl, eos,
                        bs, _K, _k, use_kernel=uk, topo=topo,
                        lora=lb, adapter_ids=aid),
                    donate_argnums=(5, 6))
        return self._spec_window_jits[key]

    def _draft_catchup(self, uid: int, row: List[int]) -> None:
        """Bring the draft KV pool level with the target's cache for
        ``uid``: feed the fed-token suffix the draft has not seen
        (``row[:seen_tokens]`` is exactly the fed history — the last
        emitted token is never fed, the loop invariant). No-op when the
        draft is already level (consecutive spec windows)."""
        sm = self.state_manager
        seq = sm.seqs[uid]
        seen = seq.seen_tokens
        d0 = self._draft_seen.get(uid, 0)
        if d0 >= seen:
            return
        toks = np.asarray(row[d0:seen], np.int64)
        with trace.span("draft_catchup", uid=int(uid), tokens=len(toks),
                        **self._trace_attr(uid)):
            ids, s, n, table, offs, full_table = \
                self._chunk_inputs(uid, d0, toks)
            _, self.draft_cache = self._draft_catchup_jit(
                self.draft_params, ids, s, n, self.draft_cache, table,
                offs, full_table)
        self._draft_seen[uid] = seen

    def _observe_spec_rates(self) -> None:
        """Publish the chooser's per-source accept-rate EMAs and any
        newly committed route switches."""
        for mode in ("ngram", "draft"):
            r = self.spec_chooser.rate.get(mode)
            if r is not None:
                self._m_spec_rate.labels(mode=mode).set(r)
        d = self.spec_chooser.switches - self._spec_switches_seen
        if d > 0:
            self._m_spec_switches.inc(d)
            self._spec_switches_seen = self.spec_chooser.switches

    def _spec_window_round(self, step_uids, outs, row_of, prompt_lens,
                           live, max_new_tokens, eos_token_id,
                           spec_k) -> Dict[int, int]:
        """One fused draft-model speculative window per batch:
        propose(k) -> target-verify -> accept-prefix loops ON DEVICE
        (``paged_spec_decode_window``) — speculation adds zero host
        round-trips on top of the window's single [N, K] transfer.
        Rows without the sequence room / KV blocks the widened
        pre-allocation contract needs (``steps_left + spec_k`` writes)
        fall back to the plain batched greedy step."""
        sm = self.state_manager
        K = max(self.decode_window, spec_k + 1)
        spec_uids: List[int] = []
        plain_uids: List[int] = []
        sl: List[int] = []
        for uid in step_uids:
            row = outs[row_of[uid]]
            remaining = max_new_tokens - (len(row) - prompt_lens[uid])
            room = (sm.config.max_seq_len - sm.seqs[uid].seen_tokens
                    - spec_k)
            s = min(K, remaining, room)
            if s < 1 or not self.can_schedule([uid], [s + spec_k]):
                plain_uids.append(uid)
                continue
            spec_uids.append(uid)
            sl.append(s)
        cur: Dict[int, int] = {}
        if plain_uids:
            cur.update(self._decode_batch_greedy(
                plain_uids, [outs[row_of[u]][-1] for u in plain_uids]))
        if not spec_uids:
            return cur
        for uid in spec_uids:
            self._draft_catchup(uid, outs[row_of[uid]])
        tokens = [outs[row_of[u]][-1] for u in spec_uids]
        t0 = time.perf_counter()
        with trace.span("spec_decode_window", batch=len(spec_uids),
                        window=K, spec_k=spec_k,
                        uids=[int(u) for u in spec_uids],
                        **self._trace_attrs(spec_uids)):
            # widened pre-allocation contract: row i may write KV at
            # positions pos..pos+sl[i]+spec_k-1 (the final round's
            # unaccepted tail), so those blocks exist BEFORE dispatch
            N, toks, pos, tables = self._assemble_decode_rows(
                spec_uids, tokens, [s + spec_k for s in sl])
            eos = np.full(N, -1, np.int32)
            eos[:len(spec_uids)] = (
                -1 if eos_token_id is None else int(eos_token_id))
            lb = self.lora_bank
            aid = (self._pad_i32(N, [self._adapter_slot_of(u)
                                     for u in spec_uids])
                   if lb is not None else None)
            out, stats, self.kv_cache, self.draft_cache = \
                self._spec_window_jit(K, spec_k)(
                    self.params, self.draft_params, jnp.asarray(toks),
                    jnp.asarray(pos), jnp.asarray(tables),
                    self.kv_cache, self.draft_cache,
                    self._pad_i32(N, sl), jnp.asarray(eos), lb, aid)
            out = np.asarray(out)   # one transfer for the whole window
            stats = np.asarray(stats)
        self._m_host_syncs.inc()
        dt = time.perf_counter() - t0
        drafted, accepted, miss, rounds = (int(x) for x in stats)
        self._m_spec_drafted.inc(drafted)
        self._m_spec_accepted.inc(accepted)
        self._m_spec_miss_rounds.inc(miss)
        self._m_spec_window_rounds.inc(rounds)
        self.spec_chooser.observe("draft", drafted, accepted)
        self._observe_spec_rates()
        log_tokens = sm.config.enable_prefix_caching
        total = 0
        for i, uid in enumerate(spec_uids):
            out_row = out[i]
            e = int((out_row >= 0).sum())   # emissions are a prefix
            toks_out = [int(t) for t in out_row[:e]]
            seq = sm.seqs[uid]
            seq.seen_tokens += e
            # accepted draft tokens ARE the canonical stream, so the
            # draft cache is level with the target after the window
            self._draft_seen[uid] = seq.seen_tokens
            if log_tokens:
                seq.token_log.extend([int(tokens[i])] + toks_out[:-1])
            total += e
            row = outs[row_of[uid]]
            finished = False
            # all but the last emit are fed/cached already; the host
            # re-applies the eos/budget cuts (defensively — the device
            # enforced them too), same fold-back as the plain window
            for tok in toks_out[:-1]:
                row.append(tok)
                if ((eos_token_id is not None and tok == eos_token_id)
                        or len(row) - prompt_lens[uid] >= max_new_tokens):
                    finished = True
                    break
            if finished or not toks_out:
                live.discard(uid)
            else:
                cur[uid] = toks_out[-1]
        self._m_decode_steps.inc()
        self._m_decode_tokens.inc(total)
        self._m_decode_time.observe(dt)
        self._m_fused_time.observe(dt)
        if dt > 0:
            self._m_decode_tput.set(total / dt)
        flight.record("spec_decode_window", batch=len(spec_uids),
                      tokens=total, window=K, spec_k=spec_k,
                      drafted=drafted, accepted=accepted,
                      dur_s=round(dt, 5))
        self._update_pool_telemetry()
        return cur

    # next power-of-two >= count, capped (one compiled program per
    # bucket keeps the jit-cache size logarithmic in the range); the
    # shared utils/bucketing rule, kept as a static method for the
    # existing call sites
    _pow2_bucket = staticmethod(pow2_bucket)

    def _decode_bucket(self, count: int) -> int:
        """Pad the decode batch to the next power-of-two bucket instead of
        always the tracked-sequence cap (one compiled program per bucket);
        fixes the fixed-cap padding waste (round-2 Weak #6)."""
        return pow2_bucket(
            count, self.state_manager.config.max_tracked_sequences)

    @staticmethod
    def _pad_i32(N: int, vals) -> jnp.ndarray:
        """[N] int32 with ``vals`` in the leading rows, zeros as padding."""
        out = np.zeros(N, np.int32)
        out[:len(vals)] = vals
        return jnp.asarray(out)

    def _assemble_decode_rows(self, uids: List[int], tokens: List[int],
                              new_tokens: List[int]):
        """Shared decode-batch assembly (per-token step AND fused
        window): pad rows to the power-of-two batch bucket, allocate
        each row's blocks for the ``new_tokens[i]`` KV writes it will
        make, and slice tables to the used-page bucket. The decode
        program's cost scales with table width (the BlockSpec-pipelined
        kernel streams EVERY table slot, and the gather fallback
        materializes [N, MB*bs, ...]), so a 128-token sequence in a
        2048-token-wide table would pay 16x the bandwidth."""
        sm = self.state_manager
        N = self._decode_bucket(len(uids))
        MB = sm.max_blocks_per_seq
        toks = np.zeros(N, np.int32)
        pos = np.zeros(N, np.int32)
        tables = np.full((N, MB), NULL_BLOCK, np.int32)
        used_pages = 1
        for i, (uid, tok, k) in enumerate(zip(uids, tokens, new_tokens)):
            seq = sm.ensure_blocks(uid, int(k))
            toks[i] = tok
            pos[i] = seq.seen_tokens
            tables[i] = sm.block_table_for(uid)
            used_pages = max(used_pages, len(seq.blocks))
        tables = tables[:, :self._pow2_bucket(used_pages, MB)]
        return N, toks, pos, tables

    def _window_tables(self, uids: List[int], N: int) -> tuple:
        """What a decode program takes behind its state slots: ``([N,
        ring blocks] int32,)``, each row's ring in the window layers'
        pool (the null block for padding rows), or ``()`` for a model
        without one, whose programs are then called as they always
        were."""
        if not self._has_ring:
            return ()
        sm = self.state_manager
        tables = np.full((N, sm.ring_blocks), NULL_BLOCK, np.int32)
        for i, uid in enumerate(uids):
            tables[i] = sm.window_table_for(uid)
        return (jnp.asarray(tables),)

    def _state_slots(self, uids: List[int], N: int):
        """[N] int32: each row's slot of recurrent state, the null slot
        for padding rows; None for a model that keeps none."""
        if not self._has_state:
            return None
        seqs = self.state_manager.seqs
        return self._pad_i32(N, [seqs[u].state_slot for u in uids])

    def _build_decode_inputs(self, uids: List[int], tokens: List[int]):
        N, toks, pos, tables = self._assemble_decode_rows(
            uids, tokens, [1] * len(uids))
        active = np.zeros(N, bool)
        active[:len(uids)] = True
        # (the tables twice: the device's, and the host's own for what
        # is counted behind the launch)
        return (jnp.asarray(toks), jnp.asarray(pos), jnp.asarray(tables),
                jnp.asarray(active), tables)

    def _decode_common(self, uids: List[int], tokens: List[int], jit_fn,
                       extract) -> Dict[int, object]:
        sm = self.state_manager
        with trace.span("decode_step", batch=len(uids),
                        uids=[int(u) for u in uids],
                        **self._trace_attrs(uids)) as step:
            with trace.span("step_assemble"):
                toks, pos, tables, active, host_tables = \
                    self._build_decode_inputs(uids, tokens)
                lb = self.lora_bank
                aid = (self._pad_i32(active.shape[0],
                                     [self._adapter_slot_of(u)
                                      for u in uids])
                       if lb is not None else None)
            with trace.span("step_dispatch"):
                vals, *moe, self.kv_cache = jit_fn(
                    self.params, toks, pos, tables, self.kv_cache, active,
                    lb, aid, self._state_slots(uids, active.shape[0]),
                    *self._window_tables(uids, active.shape[0]))
            with trace.span("step_fetch"):
                # blocks: the pass completes here
                vals, moe = jax.device_get((vals, moe))
        with trace.span("step_bookkeeping"):
            dt = step["duration_s"]
            self._m_host_syncs.inc()
            self._note_moe("decode_step", active.shape[0], *moe)
            self._note_state_rows("decode_step", len(uids), len(uids))
            self._note_kernel_steps(1, uids, [1] * len(uids),
                                    host_tables)
            self._m_decode_steps.inc()
            self._m_decode_tokens.inc(len(uids))
            self._m_decode_time.observe(dt)
            if dt > 0:
                self._m_decode_tput.set(len(uids) / dt)
            flight.record("decode_step", batch=len(uids),
                          dur_s=round(dt, 5))
            self._warmed_windows.add(1)   # per-token path == window 1
            log_tokens = sm.config.enable_prefix_caching
            out = {}
            for i, uid in enumerate(uids):
                seq = sm.seqs[uid]
                seq.seen_tokens += 1
                if log_tokens:
                    seq.token_log.append(int(tokens[i]))
                out[uid] = extract(vals, i)
            self._update_pool_telemetry()
        return out

    def _decode_batch_greedy(self, uids: List[int],
                             tokens: List[int]) -> Dict[int, int]:
        """Greedy decode step returning next TOKENS (device argmax): the
        generate() hot loop's [N] int transfer instead of [N, vocab]."""
        return self._decode_common(uids, tokens, self._decode_tok_jit,
                                   lambda v, i: int(v[i]))

    def _sampling_arrays(self, N: int, row_seeds: List[int],
                         gen_idx: List[int], temperature: float,
                         top_p: float, top_k: int):
        """Padded per-row sampling inputs shared by the per-token and
        fused-window sampled paths (keeping them one definition is part
        of the bit-identical-streams guarantee)."""
        return (self._pad_i32(N, row_seeds), self._pad_i32(N, gen_idx),
                jnp.full((N,), temperature, jnp.float32),
                jnp.full((N,), top_p, jnp.float32),
                jnp.full((N,), top_k, jnp.int32))

    def _decode_batch_sample(self, uids: List[int], tokens: List[int],
                             rng, row_seeds: List[int],
                             gen_idx: List[int], temperature: float,
                             top_p: float,
                             top_k: int = 0) -> Dict[int, int]:
        """Sampled decode step (device-side temperature/top-p/top-k with
        per-row keys — see sampling.fold_in_rows)."""
        seeds, g0, temp, topp, topk = self._sampling_arrays(
            self._decode_bucket(len(uids)), row_seeds, gen_idx,
            temperature, top_p, top_k)
        return self._decode_common(
            uids, tokens,
            lambda p, t, pos, bt, c, a, lb, aid, ss, *wt:
            self._decode_sample_jit(
                p, t, pos, bt, c, a, rng, seeds, g0, temp, topp, topk,
                lb, aid, ss, *wt),
            lambda v, i: int(v[i]))

    def _note_state_rows(self, program: str, rows: int, tokens: int):
        """A launch of ``program`` read and wrote the recurrent state of
        ``rows`` rows (a window: a row once a step it may take) and fed
        ``tokens`` tokens, each of which passed through every
        short-convolution layer's state."""
        if not self._has_state:
            return
        self._m_state_rows.labels(program=program).inc(rows)
        if self._conv_layers:
            self._m_conv_tokens.labels(program=program).inc(
                self._conv_layers * tokens)

    def _note_kernel_steps(self, steps: int, uids: List[int],
                           steps_left: List[int], tables: np.ndarray,
                           in_flight: Optional[List[int]] = None):
        """``steps`` decode steps went to the device: a model with
        linear layers ran their convolution as the kernel, and the
        attention kernels took their one-token form, where the decode
        programs' own tests say so. Row i of ``uids`` takes
        ``steps_left[i]`` of them from the position the manager holds
        for it plus its ``in_flight`` writes (a window launched and not
        collected), over ``tables`` (the launch's, row i's at i): what
        the positions and the copies under the attention launches are
        counted from."""
        if self.model.cfg.index_topk:
            bounds, taken = self._decode_bounds(uids, steps_left, in_flight)
            rows, step = np.nonzero(taken)
            self._note_index_reads(
                "decode", [(rows[step == s], bounds[step == s])
                           for s in range(taken.shape[1])], tables.shape,
                tables.shape[0])
        if not self._use_kernel:
            return
        cache = self.kv_cache
        if "kda_conv" in cache and conv_kernel_serves(cache["kda_conv"]):
            self._m_conv_kernel_steps.inc(steps)
        cfg = self.model.cfg
        if "ssm_state" in cache and state_space.state_kernel_serves(
                cache["ssm_state"], cfg.mamba_n_groups):
            self._m_ssm_state_kernel_steps.inc(steps)
        if "retention_state" in cache and power_retention.\
                state_kernel_serves(cache["retention_state"]):
            self._m_retention_state_kernel_steps.inc(steps)
        if cfg.caches_positions and one_token_tile_serves(
                cfg.attention == "mla", cfg.head_dim, cfg.kv_heads):
            self._m_one_token_steps.inc(steps)
            self._note_decode_positions(uids, steps_left, tables, in_flight)

    def _decode_bounds(self, uids, steps_left, in_flight):
        """``(bounds, taken)`` of the decode steps rows ``uids`` take:
        a row's bound at a step (the token it feeds, itself included)
        for every (row, step) it takes, ``taken`` [rows, steps] which
        those are; from the position the manager holds for the row plus
        its ``in_flight`` writes."""
        sm = self.state_manager
        start = np.asarray([sm.seqs[u].seen_tokens for u in uids], np.int64)
        if in_flight is not None:
            start = start + np.asarray(in_flight, np.int64)
        step = np.arange(max(steps_left, default=0))[None, :]
        taken = step < np.asarray(steps_left)[:, None]
        return (start[:, None] + 1 + step)[taken], taken

    def _note_index_reads(self, program: str, launches, tables,
                          tokens: int):
        """What the full latent layers of a model with an indexer did
        under ``launches`` of ``program`` (a ragged step; each step of a
        decode window), each ``(row_ids, bounds)`` of its query tokens
        in pack order: the table row a token belongs to and its causal
        bound (a whole number: its own position included), over tables
        of shape ``tables`` and a token bucket of ``tokens`` (a decode
        step: a token a table row). A query a token and full layer; the
        positions it ATTENDED, ``min(bound, index_topk)``; the positions
        whose rows its attention READ: as many in a decode step (its
        tokens gather what they picked), the whole bound in a prompt's
        launch (the row's pages once, the picks a mask) and under tables
        of no more than ``index_topk`` positions (the dense launch);
        and, where the launch selected at all, the positions the
        equations have the indexer score for it (its bound), the
        positions the program's products covered
        (``paged_model.index_positions_swept``) and the form a prompt's
        launch took. Host arithmetic on what the manager holds."""
        cfg = self.model.cfg
        table_rows, table_pages = tables
        ctx = table_pages * self.block_size
        form = index_prompt_form(tokens, table_rows, ctx, cfg.index_topk)
        if form and program == "decode":
            form = "decode"
        bounds = np.concatenate([b for _, b in launches]).astype(np.int64)
        layers = cfg.layer_kinds.count("mla")
        attended = int(np.minimum(bounds, cfg.index_topk).sum())
        self._m_index_queries.labels(program=program).inc(
            layers * bounds.size)
        self._m_index_attended.labels(program=program).inc(
            layers * attended)
        self._m_index_read.labels(program=program).inc(layers * (
            attended if form == "decode" else int(bounds.sum())))
        if not form:
            return
        self._m_index_scored.labels(program=program).inc(
            layers * int(bounds.sum()))
        self._m_index_swept.labels(program=program).inc(layers * sum(
            index_positions_swept(form, rows, b, tokens, table_rows, ctx)
            for rows, b in launches if len(b)))
        if program != "decode":
            self._m_index_prompt_launches.labels(form=form).inc(
                layers * len(launches))

    def _note_decode_positions(self, uids, steps_left, tables, in_flight):
        """The positions under the one-token form's launches of these
        rows and steps, layer kind by layer kind
        (``kernels/ragged_attention.decode_positions``), and the copies
        that bring them (:meth:`_note_copies`)."""
        contexts, taken = self._decode_bounds(uids, steps_left, in_flight)
        held, chunked = self._over_attention_layers(
            decode_positions, tables.shape[1], contexts)
        self._m_decode_positions.labels(kind="held").inc(held)
        self._m_decode_positions.labels(kind="chunked").inc(chunked)
        if self.model.cfg.attention != "mla":
            rows = np.nonzero(taken)[0]
            self._note_copies(
                uids, tables,
                lambda bs, window: (rows, *decode_walks(contexts, bs,
                                                        window)))

    def _note_copies(self, uids, tables, walks):
        """The pages a leaf's copies brought under the tiled kernel's
        launches of these walks and the descriptors they were started
        as, layer kind by layer kind
        (``kernels/ragged_attention.launch_copies``): ``walks(block
        size, window) -> (rows, first page, pages)``, a walk of row
        ``rows[i]`` of ``tables`` (row j is ``uids[j]``'s; a window
        layer's walks go over the rows' rings). numpy on what the host
        holds, behind a launch that is already queued."""
        sm = self.state_manager
        rings = np.stack([sm.window_table_for(u) for u in uids]) \
            if self._has_ring else None

        def count(bs, table_pages, pool_blocks, window=0):
            cache = self.kv_cache
            leaf = cache["k_window"] if window \
                else cache.get("k_full", cache.get("k"))
            return launch_copies(
                rings if window else tables, *walks(bs, window), bs,
                pool_blocks, bs * leaf.shape[-1] * leaf.dtype.itemsize,
                table_pages if window else 0)
        pages, descriptors = self._over_attention_layers(
            count, tables.shape[1])
        self._m_copy_pages.inc(pages)
        self._m_copy_descriptors.inc(descriptors)

    def _over_attention_layers(self, count, table_pages, *rows, **kw):
        """``count(*rows, block size, a table's pages, its pool's
        blocks, window=, **kw)`` -> a pair, summed over the layers that
        cache positions: the full ones over a table of ``table_pages``
        places of the pool, the window ones over their rings."""
        sm, cfg = self.state_manager, self.model.cfg
        kinds = cfg.layer_kinds
        ringed = ("window", "mla_window")
        rings = sum(k in ringed for k in kinds)
        # (a full latent layer whose indexer picks what it reads walks
        # no chunk of a table that holds more than it picks: its reads
        # are counted by ``_note_index_reads``)
        picked = cfg.index_topk and \
            table_pages * sm.block_size > cfg.index_topk
        a, b = np.asarray(count(
            *rows, sm.block_size, table_pages, sm.config.num_blocks, **kw)) \
            * sum(k in PAGED_KINDS and k not in ringed
                  and not (picked and k == "mla") for k in kinds)
        if rings:
            ring = count(
                *rows, sm.block_size, sm.ring_blocks,
                sm.config.max_tracked_sequences * sm.ring_blocks + 1,
                window=cfg.attn_window, **kw)
            a, b = a + rings * ring[0], b + rings * ring[1]
        return int(a), int(b)

    def _note_prompt_chunks(self, entries, rb):
        """The chunk visits of a ragged step's attention launches, from
        the tokens its rows feed and the contexts they end at
        (``kernels/ragged_attention.prompt_chunks``), and the copies
        that bring their pages (:meth:`_note_copies`), where the token
        tile serves them."""
        cfg = self.model.cfg
        if not (self._use_kernel and cfg.caches_positions
                and cfg.attention != "mla"
                and token_tile_serves(cfg.head_dim, cfg.kv_heads)):
            return
        seqs = self.state_manager.seqs
        new = [len(toks) for _, toks in entries]
        contexts = [seqs[uid].seen_tokens + n
                    for (uid, _), n in zip(entries, new)]
        tq = token_tile(rb.token_bucket, cfg.num_heads, cfg.head_dim,
                        cfg.kv_heads)
        whole, masked = self._over_attention_layers(
            prompt_chunks, rb.block_tables.shape[1], new, contexts, tq=tq)
        self._m_prompt_chunks.labels(kind="whole").inc(whole)
        self._m_prompt_chunks.labels(kind="masked").inc(masked)
        self._note_copies(
            [uid for uid, _ in entries], rb.block_tables,
            lambda bs, window: prompt_walks(new, contexts, bs, window, tq))

    # -- fused multi-token decode window --------------------------------
    def _launch_window(self, uids: List[int], tokens: Optional[List[int]],
                       steps_left: List[int], eos_ids: List[int],
                       sampling=None,
                       behind: Optional["_Window"] = None):
        """Launch one fused window and return ``(window, span)`` without
        waiting for it: the handle :meth:`_collect_window` takes, and
        the ``decode_window`` span, still open (the caller's collect
        closes it). ``sampling`` is None for the greedy program, else
        ``(rng, row_seeds, gen_idx0, temperature, top_p, top_k)``.

        ``behind`` is a window of the SAME rows in the same order that
        is still in flight: this one takes the rows' state from it on
        the device (``tokens`` is not read) and is queued behind it, so
        the device goes from one to the next without the host. A row
        the host knows dead has ``steps_left`` 0 there; one that dies
        inside ``behind`` is masked by the state it hands on."""
        sm = self.state_manager
        with contextlib.ExitStack() as stack:
            stack.enter_context(self._host.launch(
                "decode_window", batch=len(uids), window=self.decode_window,
                ahead=int(behind is not None), uids=[int(u) for u in uids],
                **self._trace_attrs(uids)))
            t0 = time.perf_counter()
            with trace.span("window_assemble"):
                # block pre-allocation contract: every block row i can
                # write during its steps_left[i] steps is allocated HERE,
                # so the device loop never needs the host mid-window
                # (block-table advancement is position arithmetic over a
                # complete table). Behind a window in flight the host's
                # position is that window's start: its writes count too
                writes = steps_left if behind is None else [
                    b + s if s else 0
                    for b, s in zip(behind.steps_left, steps_left)]
                N, toks, pos, tables = self._assemble_decode_rows(
                    uids, tokens or [0] * len(uids), writes)
                eos = np.full(N, -1, np.int32)
                eos[:len(uids)] = eos_ids
                lb = self.lora_bank
                aid = (self._pad_i32(N, [self._adapter_slot_of(u)
                                         for u in uids])
                       if lb is not None else None)
                extra = ()
                if sampling is not None:
                    rng, row_seeds, gen_idx0, *knobs = sampling
                    extra = (rng, *self._sampling_arrays(
                        N, row_seeds, gen_idx0, *knobs))
                jit_fn = (self._fused_greedy_jit if sampling is None
                          else self._fused_sample_jit)
            with trace.span("window_dispatch"):
                # a launch's two parts as leaves: the host arrays handed
                # to the device, then the jit call alone
                with trace.span("window_upload"):
                    state = behind.state if behind is not None else \
                        jax.device_put((toks, pos, np.ones(N, bool)),
                                       self._row_state_sharding)
                    tables_in, left_in, eos_in = (
                        jnp.asarray(tables), self._pad_i32(N, steps_left),
                        jnp.asarray(eos))
                    slots_in = (self._state_slots(uids, N),
                                *self._window_tables(uids, N))
                with trace.span("window_call", program=jit_fn.program):
                    out, state, *moe, self.kv_cache = jit_fn(
                        self.params, state[0], state[1], tables_in,
                        self.kv_cache, left_in, eos_in, state[2], *extra,
                        lb, aid, *slots_in)
                if behind is not None:
                    self._m_windows_ahead.inc()
                self._note_kernel_steps(
                    self.decode_window, uids, steps_left, tables,
                    behind.steps_left if behind is not None else None)
                win = _Window(
                    uids=list(uids), steps_left=list(steps_left),
                    fed=behind if behind is not None else tokens,
                    t0=t0, out=out, moe=moe, state=state)
            return win, stack.pop_all()

    def _collect_window(self, win: "_Window",
                        span=None) -> Dict[int, List[int]]:
        """Wait for a launched window's tokens and fold the [N, K]
        result back into host state. ``span`` is the open
        ``decode_window`` span the wait belongs to (the window's own, or
        under launch-ahead the next one's; None: the wait stands alone).
        Returns {uid: emitted tokens}: 1..steps_left[i] each for a row
        that ran (the row's last emitted token is never fed/cached — the
        same invariant as the per-token loop), none for a masked row."""
        sm = self.state_manager
        with span if span is not None else contextlib.nullcontext():
            with trace.span("window_fetch"):
                # ONE transfer for the whole window: the device wait
                out, moe = jax.device_get((win.out, win.moe))
                dt = time.perf_counter() - win.t0
        with trace.span("window_bookkeeping"):
            win.out = win.moe = win.state = None
            self._m_host_syncs.inc()
            self._note_moe("decode_window", out.shape[0], *moe)
            self._note_state_rows("decode_window", sum(win.steps_left),
                                  sum(win.steps_left))
            log_tokens = sm.config.enable_prefix_caching
            emitted: Dict[int, List[int]] = {}
            win.last = {}
            total = 0
            for i, uid in enumerate(win.uids):
                row = out[i]
                e = int((row >= 0).sum())   # active steps are a prefix
                toks_out = [int(t) for t in row[:e]]
                emitted[uid] = toks_out
                if not e:
                    continue
                seq = sm.seqs[uid]
                seq.seen_tokens += e        # e tokens were fed and cached
                if log_tokens:
                    win.last[uid] = toks_out[-1]
                    # fed tokens: the input token (the host's, or the
                    # last emit of the window this one was queued
                    # behind) plus all but the last emit
                    fed = (win.fed.last[uid] if isinstance(win.fed, _Window)
                           else int(win.fed[i]))
                    seq.token_log.extend([fed] + toks_out[:-1])
                total += e
            win.fed = None
            self._m_decode_steps.inc()
            self._m_decode_tokens.inc(total)
            self._m_decode_time.observe(dt)
            self._m_fused_time.observe(dt)
            if dt > 0:
                self._m_decode_tput.set(total / dt)
            flight.record("decode_window", batch=len(win.uids),
                          tokens=total, window=self.decode_window,
                          dur_s=round(dt, 5))
            self._warmed_windows.add(self.decode_window)
            self._update_pool_telemetry()
        return emitted

    def _decode_window_greedy(self, uids: List[int], tokens: List[int],
                              steps_left: List[int],
                              eos_ids: List[int]) -> Dict[int, List[int]]:
        """One synchronous fused window: launched, then collected."""
        return self._collect_window(*self._launch_window(
            uids, tokens, steps_left, eos_ids))

    def _window_steps_left(self, step_uids: List[int],
                           remaining: List[int],
                           in_flight: Optional[List[int]] = None
                           ) -> Optional[List[int]]:
        """Per-row step budgets for one window: the generation budget,
        the sequence-length room, and — when the KV pool is too tight for
        the full window everywhere — a halving cap so the window shrinks
        instead of failing (cap 1 is always schedulable: the caller
        already ran the per-token can_schedule guard).

        The halving checks ONLY the KV block pool. can_schedule's other
        term — sum(lengths) <= max_ragged_batch_size — is the put()
        prefill cap (one pass over that many tokens); a window is K
        sequential steps of at most N tokens each, so a large decode
        batch times K must not shrink the window against it.

        ``in_flight[i]`` is the writes row i may still make in a window
        launched and not collected: the budgets are then those of the
        window AFTER it, from the host-known position plus those writes
        (an upper bound: a row that stops early wrote less). There the
        answer is None wherever the synchronous schedule would have had
        to raise or to halve, and the caller collects first."""
        sm = self.state_manager
        K = self.decode_window
        ahead = in_flight is not None
        queued = in_flight if ahead else [0] * len(step_uids)
        room = [sm.config.max_seq_len - sm.seqs[u].seen_tokens - q
                for u, q in zip(step_uids, queued)]
        if ahead and min(room) < 1:
            return None
        sl = [max(1, min(K, r, m)) for r, m in zip(remaining, room)]

        def blocks_ok(lengths):
            need = sum(sm.seqs[u].blocks_needed(q + n, self.block_size)
                       for u, q, n in zip(step_uids, queued, lengths))
            return need <= sm.reclaimable_blocks()

        cap = K
        while cap > 1 and not blocks_ok([min(cap, s) for s in sl]):
            if ahead:
                return None
            cap //= 2
        return [min(cap, s) for s in sl]

    def _window_steps_ahead(self, flying: "_Window", live,
                            remaining: List[int]) -> Optional[List[int]]:
        """``steps_left`` of the window to queue behind ``flying`` before
        its tokens are fetched, or None where the host has to see them
        first. ``live`` is the rows the host has not seen stop;
        ``remaining[i]`` is row i's budget once ``flying`` has emitted
        all its ``steps_left[i]``, which it does unless the row dies, and
        a dead row is masked on the device. So the next window is known
        wherever it has the same rows: none of them out of budget, not
        so many seen dead that a smaller batch bucket would hold the
        rest (the synchronous schedule recomposes), and both windows'
        blocks in the pool (:meth:`_window_steps_left`). A row seen dead
        stays in its place with no step."""
        going = [i for i, u in enumerate(flying.uids) if u in live]
        if not going or min(remaining[i] for i in going) < 1 \
                or self._decode_bucket(len(going)) \
                < self._decode_bucket(len(flying.uids)):
            return None
        sl = self._window_steps_left(
            [flying.uids[i] for i in going], [remaining[i] for i in going],
            in_flight=[flying.steps_left[i] for i in going])
        if sl is None:
            return None
        out = [0] * len(flying.uids)
        for i, s in zip(going, sl):
            out[i] = s
        return out

    # -- ragged unified step --------------------------------------------
    def step_ragged(self, batch_uids: Sequence[int],
                    batch_tokens: Sequence[Iterable[int]],
                    chunk: Optional[tuple] = None) -> np.ndarray:
        """One compiled launch for a MIXED batch: prompt chunks,
        continuations and decode rows pack into a single
        :class:`~.ragged.batch.RaggedBatch` and run through the unified
        ragged program (paged_model.paged_ragged_step). Same contract
        as put(): returns
        [len(batch_uids), vocab] last-token logits per entry. ``chunk``
        = (i, n): this is step i of the n that put() runs for a prompt
        set fed in chunks (the span's ``chunk`` / ``chunks`` attrs; the
        tables then keep their full width, one program for all n)."""
        step = self._launch_ragged(batch_uids, batch_tokens, chunk)
        return self._logits_to_host([step], step.logits, step.rows)

    def _launch_ragged(self, batch_uids, batch_tokens,
                       chunk: Optional[tuple] = None,
                       behind: Optional[_RaggedStep] = None) -> _RaggedStep:
        """:meth:`step_ragged` less its fetch: the step packed, launched
        and booked on the host, its logits and expert counters left on
        the device (:meth:`_collect_ragged` fetches what a caller reads).
        ``behind`` is the step launched before this one and not waited
        for yet: the host waits for it once THIS one is queued (launch,
        THEN wait, one step deep: the device goes from one to the next
        on its own, and no more than two steps' buffers are asked for at
        a time). Nothing the next pack reads comes from the device:
        positions, blocks, slots and rings are the manager's."""
        sm = self.state_manager
        t0 = time.perf_counter()
        with trace.span("ragged_pack"):
            entries = [(int(uid),
                        np.atleast_1d(np.asarray(toks, np.int64)))
                       for uid, toks in zip(batch_uids, batch_tokens)]
            if not self.can_schedule([u for u, _ in entries],
                                     [len(t) for _, t in entries]):
                raise RuntimeError(
                    "batch not schedulable (KV blocks / sequence budget); "
                    "check can_schedule()/query() before put()")
            for i, (uid, toks) in enumerate(entries):
                if not sm.known_seq(uid) and len(toks) > 1:
                    # prefix caching: shared full blocks shorten the row
                    # to its unseen suffix. Adapter-keyed: a LoRA row's
                    # v-projection KV differs from the base model's, so
                    # prefixes only share
                    # within one adapter identity (the NAME — stable
                    # across replicas, unlike engine-local slot ints)
                    _, n_reused = sm.match_prefix(
                        uid, toks, adapter=self._uid_adapter.get(int(uid)))
                    if n_reused:
                        entries[i] = (uid, toks[n_reused:])
            # classify rows BEFORE packing mutates allocation state: a
            # decode row is one token for a sequence with cached history
            decode_rows = sum(
                1 for uid, toks in entries
                if len(toks) == 1 and sm.known_seq(uid)
                and sm.seqs[uid].seen_tokens > 0)
            if self.lora_bank is not None:
                # stamp each row's adapter identity into its descriptor
                # so the packer carries the per-row bank slots in the
                # ragged layout (and flush-time prefix registration keys
                # on it)
                for uid, _ in entries:
                    seq = sm.get_or_create_sequence(uid)
                    seq.adapter = self._uid_adapter.get(int(uid))
                    seq.adapter_slot = self._adapter_slot_of(uid)
            rb = ragged_batch.pack(entries, sm, full_width=chunk is not None)
        with self._host.launch(
                "ragged_step", rows=len(entries), tokens=rb.total_tokens,
                uids=[u for u, _ in entries],
                **(dict(chunk=chunk[0], chunks=chunk[1])
                   if chunk is not None else {}),
                **self._trace_attrs(u for u, _ in entries)):
            with trace.span("ragged_dispatch"):
                # a launch's two parts as leaves: the host arrays handed
                # to the device, then the jit call alone
                with trace.span("ragged_upload"):
                    packed_in = [jnp.asarray(a) for a in (
                        rb.ids, rb.row_ids, rb.positions, rb.lengths,
                        rb.write_blocks, rb.write_offsets, rb.block_tables,
                        rb.last_index)]
                    slots_in = (
                        (jnp.asarray(rb.adapter_slots)
                         if self.lora_bank is not None else None),
                        (jnp.asarray(rb.state_slots)
                         if self._has_state else None),
                        *((jnp.asarray(rb.window_tables),)
                          if self._has_ring else ()))
                with trace.span("ragged_call",
                                program=self._ragged_jit.program):
                    logits, *moe, self.kv_cache = self._ragged_jit(
                        self.params, *packed_in, self.kv_cache,
                        self.lora_bank, *slots_in)
            with trace.span("ragged_fetch"):
                # the wait for the step BEFORE this one, which ends
                # while this one is queued behind it (no transfer: what
                # a caller reads of a step, _collect_ragged fetches)
                if behind is not None:
                    jax.block_until_ready(behind.logits)
        with trace.span("ragged_bookkeeping"):
            if behind is not None:
                self._ragged_ended(behind)
            self._note_prompt_chunks(entries, rb)
            if self.model.cfg.index_topk:
                seqs = self.state_manager.seqs
                self._note_index_reads("ragged_step", [(
                    np.repeat(np.arange(len(entries)),
                              [len(toks) for _, toks in entries]),
                    np.concatenate([
                        seqs[uid].seen_tokens + 1 + np.arange(len(toks))
                        for uid, toks in entries]))],
                    rb.block_tables.shape, rb.token_bucket)
            self._note_state_rows("ragged_step", len(entries),
                                  rb.total_tokens)
            if self._has_state:
                cache = self.kv_cache
                if self._use_kernel and "kda_state" in cache \
                        and chunk_kernel_serves(cache["kda_state"]):
                    self._m_chunk_kernel_steps.inc()
                if self._use_kernel and "ssm_state" in cache \
                        and state_space.chunk_kernel_serves(
                            cache["ssm_state"], self.model.cfg.mamba_d_head,
                            self.model.cfg.mamba_n_groups):
                    self._m_ssm_scan_kernel_steps.inc()
                if self._use_kernel and "retention_state" in cache \
                        and power_retention.chunk_kernel_serves(
                            cache["retention_state"]):
                    self._m_retention_chunk_kernel_launches.inc()
            log_tokens = sm.config.enable_prefix_caching
            for uid, toks in entries:
                seq = sm.seqs[uid]
                seq.seen_tokens += len(toks)
                if log_tokens:
                    seq.token_log.extend(map(int, toks))
            chunk_tokens = rb.total_tokens - decode_rows
            self._m_ragged_steps.inc()
            self._m_ragged_tokens.inc(rb.total_tokens)
            self._m_ragged_prefill_rows.inc(len(entries) - decode_rows)
            self._m_ragged_decode_rows.inc(decode_rows)
            self._m_ragged_pad.set(rb.pad_fraction)
            # chunk tokens are prefill work: the family counter the
            # dashboards read
            if chunk_tokens:
                self._m_prefill_tokens.inc(chunk_tokens)
            self._update_pool_telemetry()
        return _RaggedStep(rows=len(entries), valid=rb.total_tokens,
                           tokens=rb.token_bucket, t0=t0, logits=logits,
                           moe=moe)

    def _ragged_ended(self, step: _RaggedStep) -> None:
        """The host has seen ``step`` end (its wait returned).
        inference_ragged_step_seconds: from the start of its pack to
        here, as a window's is from its launch to its tokens (under
        launch-ahead that spans the wait for the step before it)."""
        dt = time.perf_counter() - step.t0
        self._m_ragged_time.observe(dt)
        flight.record("ragged_step", rows=step.rows, tokens=step.valid,
                      bucket=step.tokens, dur_s=round(dt, 5))

    def _collect_ragged(self, steps: List[_RaggedStep], result):
        """Wait for launched ``steps`` (the last is the only one not
        waited for yet): ONE transfer of ``result``, a device array that
        depends on the last step (a put()'s logits, generate()'s first
        tokens), and of every step's expert counters, which are noted a
        step at a time as a step that fetched its own would have.
        Returns ``result`` on the host. The caller's span is the leaf."""
        result, moes = jax.device_get((result, [s.moe for s in steps]))
        # the one ragged step of these that the host waited for with
        # nothing queued behind it
        self._m_ragged_host_syncs.inc()
        self._ragged_ended(steps[-1])
        for step, moe in zip(steps, moes):
            self._note_moe("ragged_step", step.tokens, *moe)
            step.logits = step.moe = None
        return result

    def _logits_to_host(self, steps: List[_RaggedStep], logits,
                        rows: int) -> np.ndarray:
        """put()'s and step_ragged()'s fetch: the ``[row bucket, vocab]``
        device ``logits`` behind ``steps`` on the host, the first
        ``rows`` of them."""
        with trace.span("ragged_fetch"):
            # blocks: the pass completes here
            logits = self._collect_ragged(steps, logits)
            self._m_logits_fetched.inc(logits.nbytes)
        return logits[:rows]

    def put(self, batch_uids: Sequence[int],
            batch_tokens: Sequence[Iterable[int]]) -> np.ndarray:
        """Reference engine_v2.put: returns [len(batch_uids), vocab] logits
        for the last token of each entry. The whole batch (prompts,
        continuations, one-token decode rows) runs as ONE unified ragged
        launch (:meth:`step_ragged`).

        A prompt set of more tokens than ``max_ragged_batch_size`` (or,
        for a model whose window layers keep a ring, a row of more than
        ``max_row_chunk``) goes in as SEVERAL ragged steps, each row's
        tokens in consecutive chunks, the rows in lock step (a step's
        budget shared evenly among the rows that have tokens left): one
        program signature for all of them, and the logits returned are
        each row's from the step its last token went in."""
        steps, logits = self._put(batch_uids, batch_tokens)
        return self._logits_to_host(steps, logits, len(batch_uids))

    def _put(self, batch_uids, batch_tokens):
        """:meth:`put` less its fetch, which is all generate() runs of
        it: ``(steps, logits)``, the launched steps (the last not waited
        for) and the rows' logits ``[row bucket, vocab]`` on the device,
        row i the i-th entry's."""
        plan = self._chunk_plan([len(np.atleast_1d(t))
                                 for t in batch_tokens])
        if len(plan) == 1:
            step = self._launch_ragged(batch_uids, batch_tokens)
            return [step], step.logits
        return self._put_chunks(batch_uids, batch_tokens, plan)

    def _chunk_plan(self, lengths: Sequence[int]) -> List[List[int]]:
        """How put() feeds rows of ``lengths`` tokens: a list of steps,
        each the tokens every row feeds in it. One step where the whole
        set fits ``max_ragged_batch_size`` and every row its
        ``max_row_chunk``; else the rows with tokens left share each
        step's budget evenly, in whole blocks."""
        if self._fits_a_step(lengths):
            return [list(lengths)]
        budget = self.state_manager.config.max_ragged_batch_size
        cap, bs, left, plan = self.max_row_chunk, self.block_size, \
            list(lengths), []
        while any(left):
            share = budget // sum(1 for n in left if n)
            share = max(share // bs * bs, 1)
            take = [min(n, share, cap or share) for n in left]
            plan.append(take)
            left = [n - t for n, t in zip(left, take)]
        return plan

    def _put_chunks(self, batch_uids, batch_tokens, plan):
        """:meth:`_put` for a prompt set fed in ``plan``'s steps, each
        launched without waiting for the one before. Asks first
        that BOTH pools hold every row's whole prompt (a step's own
        ``can_schedule`` sees only that step), so that a call that
        cannot finish raises before it has fed a token."""
        rows = [np.atleast_1d(np.asarray(t, np.int64)) for t in batch_tokens]
        uids = [int(u) for u in batch_uids]
        if not self._can_hold(uids, [len(t) for t in rows]):
            raise RuntimeError(
                "batch not schedulable (KV blocks / sequence budget); "
                "check can_schedule()/query() before put()")
        steps: List[_RaggedStep] = []
        fed = [0] * len(rows)
        for i, take in enumerate(plan):
            live = [r for r, n in enumerate(take) if n]
            step = self._launch_ragged(
                [uids[r] for r in live],
                [rows[r][fed[r]:fed[r] + take[r]] for r in live],
                chunk=(i, len(plan)), behind=steps[-1] if steps else None)
            # put()'s own work behind a chunk step, a leaf like the
            # step's: the rows' counts and which of them ended here, and
            # behind the last step the ended rows' logits brought
            # together, on the device
            with trace.span("put_chunk"):
                self._m_prefill_chunks.inc()
                if steps and not steps[-1].ended:
                    # the step before was waited for inside this one's
                    # launch, and nobody reads its logits
                    steps[-1].logits = None
                for r in live:
                    fed[r] += take[r]
                step.ended = tuple((at, r) for at, r in enumerate(live)
                                   if fed[r] == len(rows[r]))
                steps.append(step)
                if i == len(plan) - 1:
                    logits = self._ended_rows(steps, len(rows))
        return steps, logits

    def _ended_rows(self, steps: List[_RaggedStep], rows: int):
        """The logits of a chunked put()'s ``rows`` rows, each from the
        step its last token went in, ``[row bucket, vocab]`` on the
        device. Rows of one length end together in the last step, whose
        logits are theirs as they stand; rows that end in different
        steps are gathered there, on the device."""
        kept = [s for s in steps if s.ended]
        if len(kept) == 1 and kept[0].ended == tuple(
                (r, r) for r in range(rows)):
            return kept[0].logits
        place = np.zeros(self._decode_bucket(rows), np.int32)
        base = 0
        for step in kept:
            for at, r in step.ended:
                place[r] = base + at
            base += step.logits.shape[0]
        return jnp.concatenate([s.logits for s in kept])[jnp.asarray(place)]

    # -- weight hot-swap (serve/weights.py) -----------------------------
    def note_weight_swap(self, seconds: float) -> None:
        """Book-keeping after ``swap_engine_params`` replaced
        ``self.params``: telemetry, flight event, and the params-buffer
        HBM accounting (the swapped tree may differ in dtype bytes only
        if the publisher changed — record the live truth)."""
        self._m_weight_swaps.inc()
        self._m_weight_swap_time.observe(seconds)
        self._m_weight_version.set(self.weight_version)
        flight.record("weight_swap", version=int(self.weight_version),
                      dur_s=round(float(seconds), 5))
        try:
            ds_memory.record_buffer("params",
                                    ds_memory.tree_bytes(self.params))
        except Exception:   # accounting must never block serving
            pass

    def swap_params(self, flat_leaves, version: int) -> None:
        """Install published weight leaves (``{path: fp32 ndarray}``) by
        donated buffer replacement — see serve/weights.py
        ``swap_engine_params`` (this is the method form the serving
        runtime and the hybrid engine call)."""
        from .serve import weights as serve_weights
        serve_weights.swap_engine_params(self, flat_leaves, version)

    # -- distributed tracing (telemetry/context.py) ---------------------
    def bind_trace(self, uid: int, trace_id: str) -> None:
        """Correlate ``uid``'s engine spans with a distributed trace:
        until flush(uid), every span that serves the uid carries the
        trace id (single-request spans as ``trace_id``, batch spans as
        a ``trace_ids`` list) — the stitched fleet timeline selects on
        it (timeline.trace_spans)."""
        self._uid_traces[int(uid)] = str(trace_id)

    def _trace_attr(self, uid: int) -> Dict[str, str]:
        tid = self._uid_traces.get(int(uid))
        return {"trace_id": tid} if tid is not None else {}

    def _trace_attrs(self, uids) -> Dict[str, List[str]]:
        seen: List[str] = []
        for u in uids:
            tid = self._uid_traces.get(int(u))
            if tid is not None and tid not in seen:
                seen.append(tid)
        return {"trace_ids": seen} if seen else {}

    def sequence_state(self, uid: int) -> Dict[str, np.ndarray]:
        """The recurrent state a tracked sequence holds in its slot, on
        the host, after every token fed so far: of linear-attention
        layers ``kda_state`` ``[linear layers, heads, d_k, d_v]`` and
        ``kda_conv`` ``[linear layers, taps - 1, 3 x heads x d_k]``; of
        state-space layers ``ssm_state`` ``[state-space layers, heads,
        d_head, d_state]`` and ``ssm_conv`` ``[state-space layers, taps
        - 1, x | B | C channels]`` (an input's channels in one row and a
        head's state by its own axes, however the leaves fold them); of
        power-retention layers ``retention_state`` ``[retention layers,
        kv_heads, head_dim (head_dim + 1) / 2, head_dim]`` and
        ``retention_norm`` ``[retention layers, kv_heads, head_dim
        (head_dim + 1) / 2]``: a key/value head's state and normaliser
        against phi in the order a <= b (the mechanism's 8,256 rows at
        head_dim 128, whatever the leaf keeps twice); of
        short-convolution layers ``conv_state`` ``[conv layers, taps -
        1, hidden]``: the row's last gated inputs, oldest first.
        The read half of a snapshot (what preemption and handoff of such
        a model would carry: ROADMAP M5)."""
        if not self._has_state:
            raise ValueError("sequence_state: this model keeps no "
                             "recurrent state (no linear-attention, "
                             "state-space, power-retention or "
                             "short-convolution layer)")
        sm = self.state_manager
        if not sm.known_seq(uid):
            raise KeyError(f"sequence_state: uid {uid} is not tracked")
        slot = sm.seqs[uid].state_slot
        state = {name: np.asarray(leaf[:, slot])
                 for name, leaf in self.kv_cache.items()
                 if name in STATE_LEAVES}
        for name in ("kda_conv", "ssm_conv", "conv_state"):
            if name in state:
                conv = state[name]
                state[name] = conv.reshape(*conv.shape[:2], -1)
        if "ssm_state" in state:
            state["ssm_state"] = np.asarray(state_space.heads_of(
                state["ssm_state"], self.model.cfg.mamba_n_heads))
        for name, canonical in (
                ("retention_state", power_retention.canonical_state),
                ("retention_norm", power_retention.canonical_norm)):
            if name in state:
                state[name] = canonical(state[name])
        return state

    def sequence_kv(self, uid: int, kind: str = "window"
                    ) -> Dict[str, np.ndarray]:
        """The keys and values a tracked sequence holds in the ``kind``
        leaves ("window": its ring; "full": its blocks) of a
        ``layer_types`` pattern, on the host, by POSITION: ``{"k", "v":
        [layers of the kind, positions held, kv_heads * head_dim],
        "positions": [positions held]}``, the positions ascending: every
        one fed so far of a full layer, of a window layer the last
        ``ring - block_size`` at most (the ring's places put back in
        order; the oldest block is the one the next write lands on). An
        int8 pool comes back dequantised. The read half of a snapshot,
        beside ``sequence_state``."""
        latent = self.model.cfg.attention == "mla"
        # a pattern over LATENT attention: a full layer's rows and, with
        # an indexer, its index keys ({"latent", "index_k"}); the window
        # layers' ring of rows ({"latent_window"}), by their leaves' names
        leaves = [n for n in ({"full": ("latent", "index_k"),
                               "window": ("latent_window",)}[kind]
                              if latent else ("k_" + kind, "v_" + kind))
                  if n in self.kv_cache]
        if self.model.cfg.layer_types is None or not leaves:
            raise ValueError(f"sequence_kv: this model keeps no {kind!r} "
                             f"leaves (no layer_types pattern with such "
                             f"layers)")
        sm, bs = self.state_manager, self.block_size
        if not sm.known_seq(uid):
            raise KeyError(f"sequence_kv: uid {uid} is not tracked")
        seq = sm.seqs[uid]
        n = seq.seen_tokens
        if kind == "window":
            ring = sm.ring_blocks * bs
            first = max(0, -(-n // bs) * bs - (ring - bs))
            blocks = np.asarray(seq.window_blocks, np.int32)
        else:
            ring, first = sm.max_blocks_per_seq * bs, 0
            blocks = np.asarray(seq.blocks, np.int32)
        pos = np.arange(first, n)
        place = pos % ring
        out = {"positions": pos}
        for leaf_name in leaves:
            name = leaf_name if latent else leaf_name[0]
            leaf = np.asarray(
                self.kv_cache[leaf_name][:, blocks]
            ).astype(np.float32)              # [L, blocks, bs, F]
            scales = self.kv_cache.get(f"{name}s_{kind}")
            if scales is not None:
                hd = self.model.cfg.head_dim
                leaf = leaf * np.repeat(np.asarray(scales[:, blocks]), hd,
                                        axis=-1)[:, :, None, :]
            out[name] = leaf.reshape(leaf.shape[0], -1, leaf.shape[-1])[
                :, place]
        return out

    def flush(self, uid: int) -> None:
        """Release a finished sequence's KV blocks (reference flush).
        Also forgets the uid's speculative cold-streak state: uids are
        caller-assigned and commonly reused, and a streak carried across
        independent requests would permanently ban drafting for them."""
        self._spec_miss_streak.pop(uid, None)
        self._draft_index.pop(uid, None)
        self._uid_traces.pop(int(uid), None)
        self._uid_adapter.pop(int(uid), None)
        self._spec_mode_of.pop(int(uid), None)
        self._draft_seen.pop(int(uid), None)
        self.state_manager.flush_sequence(uid)
        self._update_pool_telemetry()

    # ------------------------------------------------------------------
    # Device-memory accounting (telemetry/memory.py; chip-free)
    # ------------------------------------------------------------------
    def memory_report(self, batch: int = 1) -> Dict[str, object]:
        """AOT compile-and-analyze the serving hot-path programs —
        per-token decode, the fused window (when ``decode_window`` > 1)
        and one mixed ragged step — at the bucket shapes a ``batch``-row
        step uses, with the FULL block-table width (the worst-case
        program a long sequence pays). Publishes peak/argument/temp
        bytes per program and returns ``{"programs", "buffers", "flops"
        per program}``. Runs chip-free: the compiler is a host library,
        so OOM forensics and the tests never need a TPU.

        Analysis compiles are NOT watchdog events — they never run on
        the serving path."""
        sm = self.state_manager
        N = self._decode_bucket(max(int(batch), 1))
        MB = sm.max_blocks_per_seq

        def sds(x):
            return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                        sharding=getattr(x, "sharding",
                                                         None))

        def i32(*shape):
            return jax.ShapeDtypeStruct(shape, jnp.int32)

        params = jax.tree.map(sds, self.params)
        cache = jax.tree.map(sds, self.kv_cache)
        toks, pos, tables = i32(N), i32(N), i32(N, MB)
        # the LoRA bank rides every hot-path program as trailing (bank,
        # adapter-ids) args; None keeps the pre-bank program signatures
        lb = (jax.tree.map(sds, self.lora_bank)
              if self.lora_bank is not None else None)
        aidN = i32(N) if self.lora_bank is not None else None
        ssN = i32(N) if self._has_state else None
        wtN = (i32(N, sm.ring_blocks),) if self._has_ring else ()
        programs: Dict[str, dict] = {}
        compiled = self._decode_tok_jit.lower(
            params, toks, pos, tables, cache,
            jax.ShapeDtypeStruct((N,), jnp.bool_), lb, aidN, ssN,
            *wtN).compile()
        programs["decode_greedy"] = ds_memory.record_memory_analysis(
            "decode_greedy", compiled)
        if self.decode_window > 1:
            compiled = self._fused_greedy_jit.lower(
                params, toks, pos, tables, cache, i32(N), i32(N),
                jax.ShapeDtypeStruct((N,), jnp.bool_), lb, aidN,
                ssN, *wtN).compile()
            programs["decode_window_greedy"] = \
                ds_memory.record_memory_analysis("decode_window_greedy",
                                                 compiled)
        # a representative mixed bucket: one prefill chunk plus a decode
        # row per batch slot, full table width (the worst-case ragged
        # program a long sequence pays). The analyzed bucket geometry
        # rides along in the record so consumers (a per-token
        # normalization) read the bucket this analysis actually compiled
        TB = pow2_bucket(self.config.prefill_bucket + N,
                         sm.config.max_ragged_batch_size)
        compiled = self._ragged_jit.lower(
            params, i32(TB), i32(TB), i32(TB), i32(TB), i32(TB),
            i32(TB), i32(N, MB), i32(N), cache, lb, aidN,
            ssN, *wtN).compile()
        programs["ragged_step"] = dict(
            ds_memory.record_memory_analysis("ragged_step", compiled),
            token_bucket=TB, row_bucket=N)
        return {"programs": programs, "buffers": ds_memory.buffers()}

    # convenience: serve-style generation over the ragged engine
    def generate(self, prompts: Sequence[Sequence[int]], max_new_tokens: int,
                 uids: Optional[Sequence[int]] = None,
                 eos_token_id: Optional[int] = None,
                 temperature: float = 0.0, top_p: float = 1.0,
                 top_k: int = 0, seed: int = 0, speculative: bool = False,
                 spec_k: int = 4, spec_ngram: int = 3,
                 spec_mode: Optional[str] = None,
                 adapter=None,
                 keep_sequences: bool = False) -> List[np.ndarray]:
        """Greedy by default; temperature > 0 samples with nucleus top_p
        (FastGen's sampling surface), deterministic for a given seed.
        ``speculative`` turns on speculative decoding (greedy only):
        per request the chooser routes between prompt-lookup drafting
        (spec_ngram-gram history match + one fused verify pass) and the
        draft MODEL in-window path (propose->verify->accept inside one
        jitted program) when one is loaded — output is IDENTICAL to
        plain greedy either way. ``spec_mode`` overrides the configured
        chooser mode for this call ("auto"/"ngram"/"draft"). ``adapter``
        routes rows through a loaded LoRA adapter: a str applies to all
        rows, a sequence gives one name (or None) per row.

        A call is one ``generate`` span and no part of it runs outside a
        leaf span under it (``train_batch``'s rule; docs/TELEMETRY.md,
        "Span tracing"): ``gen_admit`` to the prompt's first launch,
        ``put()``'s own leaves less its fetch, ``gen_first_token`` (the
        pick launched over the logits where they are, and its ``[N]``
        tokens' arrival: the wait for the prompt's last step), then a
        decode window at a time ``gen_schedule`` (from
        the last window's return to the next one's call) and the
        window's own leaves, and ``gen_flush``. The leaves carry no
        attrs: what a call was is on the root and on ``ragged_step`` /
        ``decode_window``."""
        with trace.span("generate", rows=len(prompts),
                        max_new_tokens=int(max_new_tokens)) as root, \
                self._host.call(root):
            with trace.span("gen_admit"):
                uids = (list(uids) if uids is not None
                        else list(range(len(prompts))))
                outs: List[List[int]] = [list(map(int, p)) for p in prompts]
                row_of = {uid: i for i, uid in enumerate(uids)}
                sampling = temperature > 0.0
                assert not (speculative and sampling), \
                    "speculative decoding is greedy-only (draft " \
                    "verification compares argmax)"
                if speculative and self.model.cfg.walks_runs:
                    raise NotImplementedError(
                        "speculative decoding of an attention='mla' or "
                        "layer_types model: the verify pass has no form "
                        "for the walk of runs (and none over a ring)")
                # each generate() call is an independent request batch: spec
                # cold-streaks (and draft indexes) from earlier calls must not
                # leak into this one
                self._spec_miss_streak.clear()
                self._draft_index.clear()
                if adapter is not None:
                    names = ([adapter] * len(uids) if isinstance(adapter, str)
                             else list(adapter))
                    if len(names) != len(uids):
                        raise ValueError(
                            f"adapter list length {len(names)} != batch size "
                            f"{len(uids)}")
                    for uid, name in zip(uids, names):
                        self.assign_adapter(uid, name)
                if speculative:
                    if spec_mode not in (None, "auto", "ngram", "draft"):
                        raise ValueError("spec_mode must be auto|ngram|"
                                         f"draft, got {spec_mode!r}")
                    if spec_mode == "draft" and self.draft_model is None:
                        raise ValueError(
                            "spec_mode='draft' requires a draft model: call "
                            "load_draft_model() first")
                    from .ngram_index import NGramIndex
                    for uid in uids:
                        # the request's routing decision is made ONCE, up
                        # front: the n-gram index over the prompt is the
                        # chooser's cheap repetitiveness prior, the per-mode
                        # accept-rate EMAs its learned history
                        idx = self._draft_index[uid] = NGramIndex(
                            spec_ngram, self._SPEC_SCAN_WINDOW)
                        idx.sync(outs[row_of[uid]])
                        if spec_mode in ("ngram", "draft"):
                            mode = spec_mode
                        else:
                            mode = self.spec_chooser.choose(
                                self.draft_model is not None,
                                idx.has_candidate(spec_ngram))
                        self._spec_mode_of[int(uid)] = mode
                        self._m_spec_mode_requests.labels(mode=mode).inc()
                base_rng = jax.random.PRNGKey(seed) if sampling else None
            t_start = time.perf_counter()
            served = False
            # prompts go through put()'s launches (prefill) and every
            # pick runs on the device: the call stays in token space,
            # only [N] int32s cross to the host a step (put()'s [N,
            # vocab] logits are the API for external schedulers, not the
            # hot loop)
            try:
                steps, logits = self._put(uids, prompts)
                with trace.span("gen_first_token"):
                    # queued behind the last chunk step; its tokens'
                    # arrival is the wait for that step
                    if sampling:
                        # per-row keys (stable row seed + generated-token
                        # index): a row's stream depends only on its own
                        # draw history, so the per-token and fused-window
                        # paths sample the exact same tokens for a given
                        # seed
                        first = self._first_sample_jit(
                            logits, base_rng, *self._sampling_arrays(
                                logits.shape[0], list(range(len(uids))),
                                [0] * len(uids), temperature, top_p, top_k))
                    else:
                        first = self._first_greedy_jit(logits)
                    del logits      # the steps' own handle goes below
                    first = self._collect_ragged(steps, first)
                    self._m_ttft.observe(time.perf_counter() - t_start)
                    # what came back and is not in ``outs`` yet: the
                    # prefill's pick here, then a window's tokens or a
                    # step's one (the last of a row is the next one fed)
                    em = {uid: [int(t)] for uid, t in zip(uids, first)}
                    live = set(uids)
                    prompt_lens = {uid: len(prompts[row_of[uid]])
                                   for uid in uids}
                    row_seed = {uid: i for i, uid in enumerate(uids)}
                    window = 1 if speculative else self.decode_window
                    eos = -1 if eos_token_id is None else int(eos_token_id)
                    # the window launched and not collected yet (fused
                    # windows only; speculative rounds need the host's
                    # accept counts, a per-token step has no row state
                    # to hand on)
                    flying = None

                while max_new_tokens > 0:   # 0 -> prompt-only rows (no emit)
                    with trace.span("gen_schedule"):
                        # all but a row's last emit are fed/cached
                        # already; the host only re-applies the eos/budget
                        # cuts (defensively — the device enforced them
                        # too). Per-uid budget (not a step counter):
                        # speculative rounds and fused windows emit
                        # several tokens, so sequences finish at
                        # different steps
                        for uid, toks_out in em.items():
                            row = outs[row_of[uid]]
                            full = prompt_lens[uid] + max_new_tokens
                            for tok in toks_out:
                                row.append(tok)
                                if tok == eos_token_id or len(row) >= full:
                                    live.discard(uid)
                                    break
                        em = {}
                        sl = None
                        if flying is not None:
                            # a row in flight emits its steps_left unless
                            # it dies, so the window after is known
                            # before this one's tokens are
                            gen_count = [g + s for g, s in
                                         zip(gen_count, flying.steps_left)]
                            sl = self._window_steps_ahead(
                                flying, live,
                                [max_new_tokens - g for g in gen_count])
                        else:
                            step_uids = [u for u in uids if u in live]
                            if not step_uids:
                                break
                            # same guard put() applies: generating past
                            # max_seq_len (or a drained block pool) must
                            # raise, not silently overrun or crash inside
                            # table assembly
                            if not self.can_schedule(step_uids,
                                                     [1] * len(step_uids)):
                                raise RuntimeError(
                                    "generation not schedulable: prompt + "
                                    "generated tokens exceed max_seq_len "
                                    "or the free KV block pool; lower "
                                    "max_new_tokens or raise the limits")
                            # every step_uid is already tracked, so the
                            # batch can never exceed
                            # max_tracked_sequences — one call suffices
                            feed = [outs[row_of[u]][-1] for u in step_uids]
                            gen_count = [len(outs[row_of[u]])
                                         - prompt_lens[u] for u in step_uids]
                            if window > 1:
                                sl = self._window_steps_left(
                                    step_uids,
                                    [max_new_tokens - g for g in gen_count])
                    if flying is not None and sl is None:
                        # the host has to see the window in flight first:
                        # the schedule above starts over from its tokens
                        em, flying = self._collect_window(flying), None
                    elif window > 1:
                        # launch, THEN wait for the window before: the
                        # device goes from one to the next on its own
                        nxt, span = self._launch_window(
                            step_uids, feed, sl, [eos] * len(step_uids),
                            (base_rng, [row_seed[u] for u in step_uids],
                             gen_count, temperature, top_p, top_k)
                            if sampling else None, behind=flying)
                        if flying is not None:
                            em = self._collect_window(flying, span)
                        else:
                            # nothing to wait for yet; the launch took
                            # blocks, which the pool's gauges show now
                            span.close()
                            with trace.span("window_bookkeeping"):
                                self._update_pool_telemetry()
                        flying = nxt
                    elif speculative:
                        # per-request routing: draft-model rows take the
                        # fused in-window path, the rest keep prompt-lookup
                        draft_set = {u for u in step_uids
                                     if self._spec_mode_of.get(int(u))
                                     == "draft"}
                        cur = {}
                        if draft_set:
                            cur.update(self._spec_window_round(
                                [u for u in step_uids if u in draft_set],
                                outs, row_of, prompt_lens, live,
                                max_new_tokens, eos_token_id, spec_k))
                        ngram_uids = [u for u in step_uids
                                      if u not in draft_set]
                        if ngram_uids:
                            cur.update(self._speculative_round(
                                ngram_uids, outs, row_of, prompt_lens, live,
                                max_new_tokens, eos_token_id, spec_k,
                                spec_ngram))
                        em = {u: [t] for u, t in cur.items()}
                    elif sampling:
                        em = {u: [t] for u, t in self._decode_batch_sample(
                            step_uids, feed, base_rng,
                            [row_seed[u] for u in step_uids], gen_count,
                            temperature, top_p, top_k).items()}
                    else:
                        em = {u: [t] for u, t in self._decode_batch_greedy(
                            step_uids, feed).items()}
                served = True
            finally:
                # flush even on the schedulability raise: a long-lived
                # engine must not leak this call's KV blocks / sequence
                # slots. A window still in flight (an exception between
                # its launch and its collect) is dropped: the pool it
                # returns is already the engine's, and what is launched
                # next runs after it
                with trace.span("gen_flush"):
                    if not (keep_sequences and served):
                        for uid in uids:
                            self.flush(uid)
                    rows = [np.asarray(o) for o in outs]
        # behind the root: the call's leaves against their medians
        self._host.judge()
        return rows
