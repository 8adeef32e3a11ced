"""Transformer language-model family (functional, mesh-aware).

This is the model zoo backbone: one configurable transformer that
instantiates the Llama/Mistral family (RMSNorm + rotary + SwiGLU + GQA),
the GPT-2/OPT family (LayerNorm + learned positions + GELU) and the
BERT/RoBERTa MLM encoder family (post-LN, bidirectional attention, MLM
prediction head), replacing the reference's per-architecture
implementations (inference/v2/model_implementations/{llama_v2,mistral,opt}/
and the HF-injection containers in module_inject/containers/*).

TPU-first design:
  * layers are stacked and executed with lax.scan (one compiled layer body,
    O(1) compile time in depth; the idiomatic XLA equivalent of the
    reference's per-layer module lists),
  * attention runs the Pallas flash kernel (ops/flash_attention.py),
  * tensor parallelism is declared as PartitionSpecs over the "model" mesh
    axis (column-parallel qkv/up, row-parallel out/down — the same sharding
    AutoTP derives by parsing module names, module_inject/auto_tp.py:259),
  * sequence parallelism (Ulysses) wraps attention via the "seq" axis,
  * activation checkpointing = jax.checkpoint around the scanned layer body
    (reference runtime/activation_checkpointing/checkpointing.py:477).
"""

import functools
import math
from dataclasses import dataclass
from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from ..ops.norms import layer_norm, rms_norm
from ..telemetry import registry as _registry


# a source's word for a layer -> the kind it is served as: per-head
# attention over a window or over every position ("attention": the
# word of a source whose other layers are no attention at all), a
# Mamba-2 state-space mixer, a power-retention mixer (a fixed-size
# power-kernel state a key/value head in place of cached positions), or
# "moe": an expert layer that is a layer of its own. A pattern that
# names "moe" is one whose every layer is ONE sub-layer behind ONE norm
# (``TransformerConfig.one_sublayer``). "mamba_attention": a layer whose
# mixer is TWO mixers on ONE norm, a Mamba-2 mixer beside full per-head
# attention, their outputs summed (kind "hybrid"). "conv": a layer whose
# WHOLE mixer is a doubly gated short convolution (kind "conv"): no
# matrix state and no positions, a row's memory is its last taps - 1
# gated inputs
LAYER_TYPE_KINDS = {"sliding_attention": "window", "full_attention": "full",
                    "attention": "full", "mamba": "ssm", "moe": "moe",
                    "power_retention": "retention",
                    "mamba_attention": "hybrid", "conv": "conv"}
# what ``layer_types`` names under attention="mla" (the dots3_note
# block): a full LATENT layer, which reads the positions its indexer
# picks, and a second latent kind with sizes of its own over a window
LATENT_TYPE_KINDS = {"full_attention": "mla",
                     "sliding_attention": "mla_window"}
# the kinds that cache positions in blocks; the others keep a state a
# sequence, or nothing ("hybrid" does both)
PAGED_KINDS = ("mha", "mla", "mla_window", "window", "full", "hybrid")
# the kinds that own a place in a family of cache leaves: a "hybrid"
# layer has one in the full pool AND one in the state-space leaves
LEAF_OWNERS = {"full": ("full", "hybrid"), "ssm": ("ssm", "hybrid"),
               "conv": ("conv",)}
# the kinds whose layers keep a state a sequence, in a slot
STATE_KINDS = ("kda", "ssm", "retention", "hybrid", "conv")


class LatentKind(NamedTuple):
    """A latent mixer kind's sizes (``TransformerConfig.latent_kind``)."""
    heads: int
    q_rank: int
    kv_rank: int
    nope: int
    rope: int
    v: int
    theta: float
    window: int
    topk: int

    @property
    def row(self) -> int:
        """What one cached position holds: latent and rotated key part"""
        return self.kv_rank + self.rope


@dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: Optional[int] = None     # GQA; None => MHA
    max_seq_len: int = 4096
    norm: str = "rmsnorm"                  # rmsnorm | layernorm
    norm_eps: float = 1e-5
    activation: str = "swiglu"     # swiglu | geglu | geglu_exact | gelu | relu
    # rope | learned | alibi | none ("none", served only, under
    # ``layer_types``: no position signal on any layer)
    positional: str = "rope"
    attn_bias: bool = False                # q/k/v/o projection biases (GPT-2/OPT)
    # Gemma-family knobs: q/o project to num_heads*head_dim != hidden
    # (Gemma-7B: 16x256 vs H=3072); embeddings scale by sqrt(H) at lookup
    # while the tied logits head uses the raw table
    head_dim_override: Optional[int] = None
    embed_scale: float = 1.0
    # Falcon-family: one shared input norm feeds BOTH sublayers and the
    # residual adds once (x + attn(ln x) + mlp(ln x)); MLP without biases.
    # parallel_norms (NeoX/Pythia): the parallel MLP reads its OWN norm
    # of x (x + attn(ln1 x) + mlp(ln2 x)) instead of sharing ln1
    parallel_residual: bool = False
    parallel_norms: bool = False
    mlp_bias: bool = True
    # fraction of head_dim that rotates (GPT-NeoX/Phi-class partial
    # rotary); the remaining dims pass through untouched
    rotary_pct: float = 1.0
    # Phi-class causal lm_head carries a logit bias
    lm_head_bias: bool = False
    # v1 decode: Pallas dense-cache attention kernel (ops/decode_attention)
    # instead of the repeat+einsum path; interpret-mode off-TPU
    decode_kernel: bool = True
    # layer-scan unroll factor. A lax.scan iteration is a scheduling
    # barrier: with ZeRO-3 the per-layer param all-gather cannot overlap
    # the PREVIOUS layer's compute across it. Unrolling by 2 puts
    # gather(l+1) and compute(l) in one block where XLA's latency-hiding
    # scheduler can interleave them — the compiled-program equivalent of
    # the reference's two-stream prefetch (stage3.py:1151). The engine
    # raises this via scan_unroll_hint when zero_optimization.overlap_comm
    # is on (runtime/engine.py).
    scan_unroll: int = 1
    rope_theta: float = 10000.0
    tie_embeddings: bool = False
    remat: bool = True                     # activation checkpointing per layer
    use_flash: bool = True
    # minimum sequence length for the Pallas flash kernel; below it XLA's
    # fused attention is used. Round-1 measured flash at 11.1% vs XLA 16.2%
    # MFU (S=2048, v5e) — but that kernel ran f32 matmuls; with bf16 MXU
    # dots + group-accumulated dkv + auto blocks the crossover moves down.
    flash_min_seq: int = 2048
    attn_block_q: int = 0                  # 0 = auto (ops/flash_attention)
    attn_block_kv: int = 0
    seq_parallel: bool = False             # sequence parallelism over "seq" axis
    seq_parallel_impl: str = "ulysses"     # ulysses (all-to-all) | ring (blockwise)
    loss_chunk: int = 512                  # chunked cross-entropy (0 = whole seq)
    # MoE (expert parallelism; reference deepspeed/moe/layer.py:16). When
    # moe_num_experts > 0 every layer's MLP becomes a top-k routed MoE.
    moe_num_experts: int = 0
    moe_top_k: int = 1
    moe_capacity_factor: float = 1.0
    moe_min_capacity: int = 4
    moe_aux_loss_coef: float = 0.01
    # Residual MoE (PR-MoE building block, reference moe/layer.py:29
    # use_residual): dense MLP + coefficient-weighted routed experts
    moe_use_residual: bool = False
    # drop_tokens=False equivalent: ragged_dot grouped GEMM, ep=1 only
    moe_dropless: bool = False
    # router noise policy (reference moe/layer.py noisy_gate_policy).
    # Currently every non-None value is rejected in __post_init__ (the
    # scanned layer body threads no per-layer rng yet); the field exists —
    # and is forwarded identically to BOTH the GSPMD and manual-pipeline
    # MoE branches — so that when rng support lands, the two routing paths
    # cannot silently diverge. Use deepspeed_tpu.moe.layer.MoE for noisy
    # gating today.
    moe_noisy_gate_policy: Optional[str] = None
    # the expert layer as DeepSeek-V3-class checkpoints deploy it (served
    # only, inference/v2/paged_model.py): an expert's width apart from
    # the dense MLP's (None: intermediate_size); how many LEADING layers
    # keep a dense MLP (they form the ``lead_layers`` stack of the
    # parameter tree, the expert layers ``layers``); always-on shared
    # experts beside the routed ones; how a router logit becomes a score
    # ("softmax" | "sigmoid"); a per-expert bias added to the scores for
    # SELECTION only (``moe_gate_bias``); whether the chosen k > 1
    # weights are normalised over the chosen set; and what they are then
    # scaled by
    moe_intermediate_size: Optional[int] = None
    moe_first_dense_layers: int = 0
    moe_shared_experts: int = 0
    moe_scoring: str = "softmax"
    moe_selection_bias: bool = False
    moe_norm_topk: bool = True
    moe_routed_scale: float = 1.0
    # what guards the sum of the chosen SIGMOID scores where they are
    # normalised (a softmax's chosen never sum to 0): DeepSeek-V3's
    # 1e-20, the lfm2_moe block's 1e-6
    moe_norm_topk_eps: float = 1e-20
    # attention kind: "mha" (per-head keys and values; GQA/MQA by
    # num_kv_heads) or "mla" (multi-head latent attention, served only:
    # queries through a q_lora_rank bottleneck, keys and values expanded
    # from ONE kv_lora_rank-wide normed latent a token, plus one
    # qk_rope_head_dim-wide rotated key part shared by all heads; a head
    # is qk_nope_head_dim + qk_rope_head_dim wide for scores and
    # v_head_dim for values). rope_interleave: the rotation pairs lanes
    # (2i, 2i+1), the published DeepSeek layout, and not (i, i + half)
    attention: str = "mha"
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    rope_interleave: bool = False
    # a per-layer PATTERN of mixers (served only): with
    # ``linear_attn_period`` p > 0 layer i keeps ``attention`` (which has
    # to be "mla") where (i + 1) % p == 0 and is a LINEAR-attention layer
    # otherwise (Kimi Delta Attention, arXiv:2510.26692: ``num_heads``
    # heads of ``linear_head_dim`` keys and values, a causal depthwise
    # convolution of ``linear_conv_size`` taps on q, k and v, a decay a
    # head and key channel of ``linear_decay_floor`` * sigmoid(.), and a
    # recurrent float32 state [linear_head_dim, linear_head_dim] a head
    # in place of cached positions). ``attn_gate`` "head" multiplies each
    # head's output by sigmoid of one projection of the layer's input
    # before ``wo``, on both mixers. ``q_lora_rank`` 0 under "mla": the
    # query is one projection, without the bottleneck and its norm
    linear_attn_period: int = 0
    linear_head_dim: int = 0
    linear_conv_size: int = 4
    linear_decay_floor: float = -5.0
    attn_gate: str = "none"
    # a per-layer pattern over LATENT attention (served only; the
    # dots3_note block): ``layer_types`` under ``attention="mla"`` names
    # "full_attention" (the latent attention above) and
    # "sliding_attention", a SECOND latent kind whose head count, ranks,
    # head widths and theta are its own (``swa_*``; its gate one scalar
    # a head of ITS heads) and which sees its last ``attn_window``
    # positions, kept as a ring of latent rows. ``index_*``: a full
    # layer reads only the ``index_topk`` positions its indexer picks a
    # query token (DeepSeek-V3.2's lightning indexer: ``index_n_heads``
    # heads of ``index_head_dim`` from the query's latent against one
    # LayerNormed key a position, ReLU, a learned weight a head; every
    # position while the context is no longer than ``index_topk``).
    # ``mla_lora_rescale``: each normed latent times sqrt(hidden_size /
    # its rank)
    swa_num_heads: int = 0
    swa_q_lora_rank: int = 0
    swa_kv_lora_rank: int = 0
    swa_qk_nope_head_dim: int = 0
    swa_qk_rope_head_dim: int = 0
    swa_v_head_dim: int = 0
    swa_rope_theta: float = 0.0
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    mla_lora_rescale: bool = False
    # a per-layer pattern over PER-HEAD attention (served only; the
    # afmoe block of Trinity-Mini): ``layer_types`` lists every layer's
    # mixer as the source publishes it, "sliding_attention" (a token sees
    # the last ``attn_window`` positions, itself included) or
    # "full_attention" (all of them), under ``attention="mha"``; the
    # cache then keeps a leaf a kind, the sliding layers' a ring
    # (``paged_model.init_paged_kv_cache``). ``qk_norm``: q and k are
    # RMS-normed a head over ``head_dim`` with a learned weight, before
    # the rotation. ``attn_gate`` "elementwise": the heads' output times
    # sigmoid of one ``num_heads * head_dim``-wide projection of the
    # layer's input, before ``wo``. ``rope_sliding_only``: only the
    # sliding layers rotate q and k, a full layer has no position
    # signal. ``norm_scheme`` "sandwich" (below): a norm before each
    # sub-layer and one more on what it adds to the stream
    layer_types: Optional[tuple] = None
    attn_window: int = 0
    qk_norm: bool = False
    rope_sliding_only: bool = False
    # a pattern of STATE-SPACE layers beside per-head attention (served
    # only; the granitemoehybrid block): ``layer_types`` "mamba" is a
    # Mamba-2 mixer (SSD, arXiv:2405.21060) and "attention" a full
    # per-head layer. The mixer: one ``in_proj`` to [z | x B C | dt]
    # (``mamba_n_heads * mamba_d_head`` | that + 2 ``mamba_n_groups``
    # ``mamba_d_state`` | ``mamba_n_heads``), a causal depthwise convolution of
    # ``mamba_d_conv`` taps (with a bias where ``mamba_conv_bias``) and
    # SiLU over x, B and C, a float32 state [mamba_d_head,
    # mamba_d_state] a head with a SCALAR decay a head and token, B and
    # C [``mamba_n_groups``, mamba_d_state] a token, a group of
    # consecutive heads reading one of them (heads a whole multiple of
    # groups), a gated RMS norm over each group's channels, and
    # ``out_proj``. The inner width is heads x head width whatever
    # ``mamba_expand`` says (read and unused). ``mamba_chunk_size`` is
    # the source's tile of the chunked form: results do not depend on it
    # but for rounding, and the programs tile by their own
    # (kernels/state_space.py).
    # The muP multipliers of the same block: ``attn_scale`` replaces
    # head_dim^-1/2 on the scores (0: that default), ``residual_scale``
    # multiplies what every sub-layer adds to the stream,
    # ``logit_scale`` DIVIDES the logits (``embed_scale`` is above)
    mamba_n_heads: int = 0
    mamba_d_head: int = 0
    mamba_d_state: int = 0
    mamba_d_conv: int = 4
    mamba_n_groups: int = 1
    mamba_expand: int = 2
    mamba_conv_bias: bool = True
    mamba_chunk_size: int = 256
    attn_scale: float = 0.0
    residual_scale: float = 1.0
    logit_scale: float = 1.0
    # a layer whose mixer is TWO mixers on one norm (served only;
    # ``layer_types`` "mamba_attention", the falcon_h1 block): the
    # Mamba-2 mixer above and full per-head attention both read ONE
    # ``attn_norm`` of the stream and their outputs are summed into it,
    # ahead of the layer's MLP. The muP multipliers of that block, each a
    # plain scalar where it stands in the source (1: none), read by every
    # per-head, state-space and dense-MLP sub-layer of a pattern:
    # ``attn_in_scale`` on the attention's normed input, ``key_scale``
    # on its keys ahead of the rotation, ``attn_out_scale`` on what it
    # adds; ``ssm_in_scale`` on the state-space mixer's normed input,
    # ``ssm_z_scale`` / ``ssm_x_scale`` / ``ssm_b_scale`` /
    # ``ssm_c_scale`` / ``ssm_dt_scale`` on the five segments of its
    # projection's output, ``ssm_out_scale`` on what it adds;
    # ``mlp_gate_scale`` on the dense MLP's gate ahead of its
    # activation, ``mlp_down_scale`` on what the MLP adds
    attn_in_scale: float = 1.0
    key_scale: float = 1.0
    attn_out_scale: float = 1.0
    ssm_in_scale: float = 1.0
    ssm_z_scale: float = 1.0
    ssm_x_scale: float = 1.0
    ssm_b_scale: float = 1.0
    ssm_c_scale: float = 1.0
    ssm_dt_scale: float = 1.0
    ssm_out_scale: float = 1.0
    mlp_gate_scale: float = 1.0
    mlp_down_scale: float = 1.0
    # POWER RETENTION layers (served only; ``layer_types``
    # "power_retention"; arXiv:2507.04239, degree 2): the per-head
    # block's projections, ``qk_norm`` and rotation as they are, with
    # the softmax's exp(q.k) replaced by (q.k / sqrt(head_dim))^2 times
    # a cumulative decay whose log is ``log sigmoid(w_decay x +
    # b_decay)``, one scalar a key/value head and token, and the
    # weights' sum (+ ``retention_eps``) as normaliser. A key/value head
    # keeps a float32 state over the symmetric second power of its key
    # (head_dim (head_dim + 1) / 2 products x head_dim) in place of
    # cached positions, which its whole GROUP of query heads reads
    # (kernels/power_retention.py); a model of such layers alone caches
    # no position at all
    retention_eps: float = 1e-6
    # a layer whose WHOLE mixer is a doubly gated SHORT CONVOLUTION
    # (served only; ``layer_types`` "conv", the lfm2 block): one
    # projection of the normed input to [B | C | u], each ``hidden_size``
    # wide; ``g = B * u``; a causal depthwise convolution of
    # ``conv_taps`` taps a channel over a row's own ``g`` (zeros before
    # its start), NO activation; ``C *`` the result; one projection
    # back. A row's whole state is its last ``conv_taps - 1`` gated
    # inputs ``g``. ``conv_bias``: the source's switch for a bias on the
    # two projections and the taps; read, and refused where true
    conv_taps: int = 0
    conv_bias: bool = False
    # the group limit of the deployed router (DeepSeek-V3 noaux_tc): the
    # experts form ``moe_n_group`` groups, a group scores the sum of its
    # best two, and the top k are chosen inside the best
    # ``moe_topk_group`` groups (1 / 1: no limit)
    moe_n_group: int = 1
    moe_topk_group: int = 1
    # the chip's share of an expert layer (expert parallelism's cut, on
    # one chip): the router scores all ``moe_num_experts``, this tree
    # HOLDS ``moe_experts_held`` of them from index ``moe_experts_first``
    # (0: all), and a pick that is held elsewhere adds nothing here
    moe_experts_held: int = 0
    moe_experts_first: int = 0
    # an expert's form (served only, the walk of runs): "swiglu", three
    # matrices, ``down(silu(gate x) * (up x))``; "relu2", two and a
    # squared ReLU, ``down(relu(up x)^2)`` (the nemotron_h block; no
    # ``e_gate`` / ``shared_gate`` leaf; ``e_up`` [.., F, H], out x in,
    # the model's width last as ``e_down``'s), routed and shared alike;
    # "reglu", SwiGLU's three matrices and leaves with a ReLU gate,
    # ``down(relu(gate x) * (up x))`` (the SmallThinker block)
    moe_expert_form: str = "swiglu"
    # where the router reads (served only, a ``layer_types`` pattern of
    # window and full per-head layers): False, the experts' own normed
    # input, behind the mixer; True, the MIXER's normed input
    # (``attn_norm``), ahead of attention, so that a layer's experts are
    # known before its attention runs (the SmallThinker block); the
    # experts still run on the stream behind the mixer, under
    # ``mlp_norm``
    moe_router_ahead: bool = False

    # training objective: "causal_lm" (next-token, causal attention) or
    # "mlm" (BERT-family masked-LM: bidirectional attention, loss at the
    # positions marked by batch["loss_mask"] against batch["labels"]).
    # The reference's BERT-era training kernel (csrc/transformer/
    # ds_transformer_cuda.cpp) and its test models (tests/unit/modeling.py)
    # are this family.
    objective: str = "causal_lm"
    # residual/norm ordering: "pre" (norm before the sublayer, the modern
    # default and what every causal preset uses) or "post" (norm AFTER the
    # residual add — original BERT; the reference kernel's
    # pre_layer_norm=False mode, ds_transformer_cuda.cpp). Post-LN has no
    # final norm: the last layer's output LayerNorm plays that role.
    # "sandwich" (served only, with ``layer_types``): pre-norm, and a
    # second norm on each sub-layer's output before it joins the stream
    norm_scheme: str = "pre"
    # BERT-family extras: LayerNorm over the summed embeddings
    # (bert.embeddings.LayerNorm) and the MLM prediction head transform
    # (cls.predictions: dense+gelu+LN+decoder bias)
    embed_ln: bool = False
    mlm_head: bool = False

    def __post_init__(self):
        if self.num_kv_heads and self.num_heads % self.num_kv_heads:
            # fail at CONFIG time with the fix in the message: the r05
            # chip window lost its second bench scale point to this
            # pairing asserting deep inside flash_attention mid-capture
            divisors = [d for d in range(1, self.num_heads + 1)
                        if self.num_heads % d == 0]
            raise ValueError(
                f"GQA requires num_heads % num_kv_heads == 0, got "
                f"num_heads={self.num_heads}, "
                f"num_kv_heads={self.num_kv_heads}; pick num_kv_heads "
                f"from {divisors}")
        if self.objective not in ("causal_lm", "mlm"):
            # a typo here would silently pair bidirectional attention with
            # the shifted next-token loss — label leakage, loss collapse
            raise ValueError(
                f"objective must be 'causal_lm' or 'mlm', got "
                f"{self.objective!r}")
        if self.norm_scheme not in ("pre", "post", "sandwich"):
            raise ValueError(
                f"norm_scheme must be 'pre', 'post' or 'sandwich', got "
                f"{self.norm_scheme!r}")
        if self.norm_scheme == "post" and self.moe_num_experts > 0:
            raise NotImplementedError("post-LN + MoE is not supported")
        if self.attention not in ("mha", "mla"):
            raise ValueError(
                f"attention must be 'mha' or 'mla', got {self.attention!r}")
        if self.attention == "mla":
            sizes = (self.kv_lora_rank, self.qk_nope_head_dim,
                     self.qk_rope_head_dim, self.v_head_dim)
            if min(sizes) <= 0 or self.qk_rope_head_dim % 2 \
                    or self.q_lora_rank < 0:
                raise ValueError(
                    f"attention='mla' needs kv_lora_rank, "
                    f"qk_nope_head_dim, an even qk_rope_head_dim and "
                    f"v_head_dim (and q_lora_rank, or 0 for a query "
                    f"without the bottleneck), got "
                    f"{(self.q_lora_rank, *sizes)}")
            if (self.positional != "rope" or self.norm != "rmsnorm"
                    or self.attn_bias or not self.is_causal
                    or self.norm_scheme != "pre" or self.parallel_residual
                    or not self.is_gated_mlp):
                raise NotImplementedError(
                    "attention='mla' is the DeepSeek-V3 block: rope, "
                    "RMSNorm, pre-norm, causal, a gated MLP, no biases")
        if self.moe_scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"moe_scoring must be 'softmax' or 'sigmoid', "
                             f"got {self.moe_scoring!r}")
        if not 0 <= self.moe_first_dense_layers < max(self.num_layers, 1):
            raise ValueError(
                f"moe_first_dense_layers={self.moe_first_dense_layers} "
                f"leaves no expert layer of {self.num_layers}")
        if self.attn_gate not in ("none", "head", "elementwise"):
            raise ValueError(f"attn_gate must be 'none', 'head' or "
                             f"'elementwise', got {self.attn_gate!r}")
        if self.layer_types is not None:
            # a list from a JSON file; the dataclass is frozen
            object.__setattr__(self, "layer_types",
                               tuple(self.layer_types))
            names = self._type_kinds
            if (len(self.layer_types) != self.num_layers
                    or set(self.layer_types) - set(names)):
                raise ValueError(
                    f"layer_types names a mixer a layer ({self.num_layers}"
                    f" of {sorted(names)}), got "
                    f"{self.layer_types!r}")
            if self.attention == "mla":
                self._check_latent_pattern()
            elif (self.attention != "mha" or self.linear_attn_period
                    or self.positional not in ("rope", "none")
                    or self.norm != "rmsnorm"
                    or self.attn_bias or not self.is_causal
                    or self.parallel_residual or not self.is_gated_mlp
                    or self.rotary_pct != 1.0):
                raise NotImplementedError(
                    "a layer_types pattern is served as attention='mha', "
                    "rope over the whole head or no position signal "
                    "(positional='none'), RMSNorm, causal, a gated MLP, no "
                    "biases")
            if "sliding_attention" in self.layer_types \
                    and self.attn_window < 1:
                raise ValueError("a sliding_attention layer needs "
                                 "attn_window > 0")
            if "power_retention" in self.layer_types and (
                    self.head_dim % 2 or self.positional != "rope"
                    or self.retention_eps <= 0 or self.attn_scale
                    or self.attn_gate != "none"):
                raise ValueError(
                    "a power_retention layer needs an even head_dim "
                    "(phi is laid out by circular distance), "
                    "positional='rope', retention_eps > 0, and neither "
                    "attn_scale nor attn_gate")
            if "power_retention" in self.layer_types and (
                    self.attn_in_scale != 1.0 or self.key_scale != 1.0
                    or self.attn_out_scale != 1.0):
                raise NotImplementedError(
                    "attn_in_scale, key_scale and attn_out_scale are read "
                    "by the per-head attention layers, not by "
                    "power_retention ones")
            if {"mamba", "mamba_attention"} & set(self.layer_types) and (
                    min(self.mamba_n_heads, self.mamba_d_head,
                        self.mamba_d_state, self.mamba_n_groups) < 1
                    or self.mamba_d_conv < 2
                    or self.mamba_n_heads % self.mamba_n_groups):
                raise ValueError(
                    f"a mamba layer needs mamba_n_heads, mamba_d_head and "
                    f"mamba_d_state > 0, mamba_d_conv >= 2 and "
                    f"mamba_n_heads a whole multiple of mamba_n_groups "
                    f"(a group of heads reads one B and C), got heads "
                    f"{(self.mamba_n_heads, self.mamba_d_head)}, state "
                    f"{self.mamba_d_state}, taps {self.mamba_d_conv}, "
                    f"groups {self.mamba_n_groups}")
            if "conv" in self.layer_types and (
                    self.conv_taps < 2 or self.conv_bias
                    or self.one_sublayer):
                raise ValueError(
                    f"a conv layer (a doubly gated short convolution as "
                    f"the whole mixer) needs conv_taps >= 2, no bias "
                    f"(conv_bias=False) and an MLP behind it (no 'moe' "
                    f"layers); got taps {self.conv_taps}, conv_bias "
                    f"{self.conv_bias}")
            if "mamba_attention" in self.layer_types and (
                    min(self.num_heads, self.kv_heads, self.head_dim) < 1
                    or self.norm_scheme != "pre" or self.one_sublayer):
                raise ValueError(
                    f"a mamba_attention layer (two mixers on one norm) "
                    f"needs the mamba_* sizes and per-head attention "
                    f"sizes (num_heads, num_kv_heads, head_dim > 0), "
                    f"pre-norm, and an MLP behind it (no 'moe' layers); "
                    f"got heads {(self.num_heads, self.kv_heads)} of "
                    f"{self.head_dim}, norm_scheme {self.norm_scheme!r}")
            if (self.mlp_gate_scale != 1.0 or self.mlp_down_scale != 1.0) \
                    and self.moe_num_experts:
                raise NotImplementedError(
                    "mlp_gate_scale and mlp_down_scale are read by a "
                    "pattern's dense MLP, not by an expert layer")
            if self.one_sublayer and (
                    self.moe_num_experts < 1 or self.moe_first_dense_layers
                    or self.norm_scheme != "pre"):
                raise NotImplementedError(
                    "a layer_types pattern that names 'moe' layers (every "
                    "layer ONE sub-layer behind one norm) is served "
                    "pre-norm, with routed experts (moe_num_experts > 0) "
                    "and no leading dense stack")
            if self.attention == "mla" and (
                    self.qk_norm or self.rope_sliding_only
                    or self.norm_scheme != "pre" or self.one_sublayer
                    or self.attn_gate == "elementwise"
                    or self.positional != "rope"):
                raise NotImplementedError(
                    "qk_norm, rope_sliding_only, norm_scheme, 'moe' "
                    "layers, positional='none' and attn_gate="
                    "'elementwise' describe a pattern over PER-HEAD "
                    "attention, not one over latent attention")
        elif self.attn_window or self.qk_norm or self.rope_sliding_only \
                or self.norm_scheme == "sandwich" \
                or self.attn_gate == "elementwise":
            raise NotImplementedError(
                "attn_window, qk_norm, rope_sliding_only, norm_scheme="
                "'sandwich' and attn_gate='elementwise' describe the "
                "per-head block of a layer pattern: give layer_types")
        if (self.layer_types is None or self.attention != "mla") and (
                self.swa_num_heads or self.swa_kv_lora_rank
                or self.index_topk or self.index_n_heads
                or self.mla_lora_rescale):
            raise NotImplementedError(
                "the swa_* sizes, the index_* sizes and mla_lora_rescale "
                "describe a layer_types pattern over latent attention: "
                "give attention='mla' and layer_types")
        if self.conv_taps and "conv" not in (self.layer_types or ()):
            raise NotImplementedError(
                "conv_taps describes the conv layers of a layer pattern: "
                "give layer_types that names one")
        if self.moe_norm_topk_eps <= 0:
            raise ValueError(f"moe_norm_topk_eps must be > 0, got "
                             f"{self.moe_norm_topk_eps}")
        if self.layer_types is None and (
                self.positional == "none" or self.mamba_n_heads
                or self.attn_scale or self.residual_scale != 1.0
                or self.logit_scale != 1.0 or self.sublayer_scales):
            raise NotImplementedError(
                "positional='none', the mamba_* sizes, attn_scale, "
                "residual_scale, logit_scale and the sub-layers' "
                "multipliers (attn_in_scale ... mlp_down_scale) describe "
                "the blocks of a layer pattern: give layer_types")
        if self.linear_attn_period < 0 or (
                self.linear_attn_period
                and (self.attention != "mla" or self.linear_head_dim <= 0
                     or self.linear_conv_size < 2
                     or self.linear_decay_floor >= 0)):
            raise ValueError(
                f"a layer pattern (linear_attn_period="
                f"{self.linear_attn_period}) needs attention='mla' for "
                f"the layers it leaves, linear_head_dim > 0, "
                f"linear_conv_size >= 2 and linear_decay_floor < 0")
        if self.attn_gate == "head" and self.attention != "mla":
            raise NotImplementedError(
                "attn_gate 'head' is served with attention='mla' (and "
                "the linear layers of its pattern) only")
        E, G = self.moe_num_experts, self.moe_n_group
        if G < 1 or not 1 <= self.moe_topk_group <= G or (
                G > 1 and (E % G or E // G < 2
                           or self.moe_topk_group * (E // G)
                           < self.moe_top_k)):
            raise ValueError(
                f"moe_n_group={G} / moe_topk_group={self.moe_topk_group} "
                f"do not divide {E} experts into groups of two or more "
                f"that hold the top {self.moe_top_k}")
        if self.moe_experts_held < 0 or self.moe_experts_first < 0 \
                or self.moe_experts_first + self.moe_experts_held > E:
            raise ValueError(
                f"moe_experts_held={self.moe_experts_held} from "
                f"{self.moe_experts_first} lie outside {E} experts")
        if (self.moe_experts_held or self.moe_experts_first) and (
                not self.walks_runs
                or not 0 < self.moe_experts_held):
            raise NotImplementedError(
                "a share of the experts (moe_experts_held > 0 from "
                "moe_experts_first) is served by the walk of runs' expert "
                "layer only (attention='mla' or layer_types)")
        if self.moe_first_dense_layers and (
                self.moe_num_experts == 0 or not self.walks_runs):
            # the runs' scans live in paged_model._pattern_step
            raise NotImplementedError(
                "leading dense layers (moe_first_dense_layers) are served "
                "for an MoE model with attention='mla' or a layer_types "
                "pattern only")
        if self.moe_expert_form not in ("swiglu", "relu2", "reglu") or (
                self.moe_expert_form != "swiglu"
                and (self.layer_types is None or self.moe_num_experts < 1
                     or self.moe_use_residual)):
            raise NotImplementedError(
                f"moe_expert_form is 'swiglu' or, for the routed and "
                f"shared experts of a layer_types pattern, 'relu2' or "
                f"'reglu'; got {self.moe_expert_form!r}")
        if self.moe_router_ahead and (
                self.layer_types is None or self.moe_num_experts < 1
                or set(self.layer_kinds) - {"window", "full"}):
            raise NotImplementedError(
                "moe_router_ahead (the router reads the mixer's normed "
                "input, ahead of attention) is served for the routed "
                "experts of a layer_types pattern whose every mixer is "
                "per-head attention (sliding_attention / full_attention)")
        if self.moe_noisy_gate_policy is not None:
            # RSample needs an rng threaded through the scanned layer body,
            # which neither the GSPMD nor the manual-pipeline MoE branch
            # has; accepting it silently would make routing diverge between
            # the two branches the moment one gained rng support.
            raise NotImplementedError(
                "moe_noisy_gate_policy is not wired into the in-tree "
                "transformer (use deepspeed_tpu.moe.layer.MoE, which "
                f"supports it); got {self.moe_noisy_gate_policy!r}")

    @property
    def _type_kinds(self) -> dict:
        """What a word of ``layer_types`` names: a latent kind under
        attention='mla', a kind of ``LAYER_TYPE_KINDS`` otherwise."""
        return LATENT_TYPE_KINDS if self.attention == "mla" \
            else LAYER_TYPE_KINDS

    def _check_latent_pattern(self):
        """``layer_types`` under attention='mla': what the two latent
        kinds need said."""
        if self.linear_attn_period:
            raise NotImplementedError(
                "a layer_types pattern over latent attention and "
                "linear_attn_period are two patterns: give one")
        if "sliding_attention" in self.layer_types:
            swa = (self.swa_num_heads, self.swa_kv_lora_rank,
                   self.swa_qk_nope_head_dim, self.swa_qk_rope_head_dim,
                   self.swa_v_head_dim)
            if min(swa) <= 0 or self.swa_qk_rope_head_dim % 2 \
                    or self.swa_q_lora_rank < 0 or self.swa_rope_theta <= 0 \
                    or self.attn_window < 1:
                raise ValueError(
                    f"a sliding_attention layer over latent attention "
                    f"needs attn_window > 0, swa_rope_theta > 0 and its "
                    f"own swa_num_heads, swa_kv_lora_rank, "
                    f"swa_qk_nope_head_dim, an even swa_qk_rope_head_dim "
                    f"and swa_v_head_dim (swa_q_lora_rank, or 0), got "
                    f"window {self.attn_window}, theta "
                    f"{self.swa_rope_theta}, sizes "
                    f"{(self.swa_q_lora_rank, *swa)}")
        index = (self.index_n_heads, self.index_head_dim, self.index_topk)
        if any(index) and (
                min(index) <= 0 or not self.q_lora_rank
                or self.index_head_dim < self.qk_rope_head_dim
                or "full_attention" not in self.layer_types):
            raise ValueError(
                f"an indexer needs index_n_heads, index_head_dim (no "
                f"less than qk_rope_head_dim, whose lanes rotate) and "
                f"index_topk > 0, a full_attention layer to select for "
                f"and q_lora_rank > 0 (its queries are projections of "
                f"the query's latent), got {index}")

    @property
    def is_causal(self) -> bool:
        return self.objective == "causal_lm"

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    @property
    def head_dim(self) -> int:
        return self.head_dim_override or self.hidden_size // self.num_heads

    @property
    def served_only(self) -> Optional[str]:
        """What of this configuration only ``InferenceEngineV2``'s paged
        programs implement (None: nothing); the trainer, the v1 engine
        and this module's own forwards refuse it by this line."""
        what = [name for name, on in (
            ("attention='mla'", self.attention == "mla"),
            ("linear_attn_period (linear-attention layers and their "
             "recurrent state)", self.linear_attn_period > 0),
            ("attn_gate", self.attn_gate != "none"),
            ("layer_types (window and full per-head layers, a cache leaf "
             "a kind, the leading dense stack)",
             self.layer_types is not None),
            ("mamba layers (a Mamba-2 state-space mixer and its "
             "recurrent state)", "ssm" in self.layer_kinds),
            ("mamba_attention layers (two mixers on one norm: a Mamba-2 "
             "mixer beside per-head attention, summed)",
             "hybrid" in self.layer_kinds),
            ("the sub-layers' multipliers ("
             + ", ".join(self.sublayer_scales) + ")",
             bool(self.sublayer_scales)),
            ("power_retention layers (a power-kernel state a key/value "
             "head)", "retention" in self.layer_kinds),
            ("conv layers (a doubly gated short convolution as the whole "
             "mixer, a row's last inputs its state)",
             "conv" in self.layer_kinds),
            ("'moe' layers (a layer is one sub-layer behind one norm)",
             self.one_sublayer),
            ("mamba_n_groups (B and C a group of heads)",
             self.leaf_places("ssm") > 0 and self.mamba_n_groups > 1),
            ("moe_expert_form='relu2' (two-matrix experts)",
             self.moe_expert_form == "relu2"),
            ("moe_expert_form='reglu' (a ReLU gate on the three-matrix "
             "experts)", self.moe_expert_form == "reglu"),
            ("moe_router_ahead (the router reads the mixer's normed "
             "input)", self.moe_router_ahead),
            ("a second latent kind over a window (swa_*) and an indexer "
             "that picks what a full latent layer reads (index_*)",
             self.attention == "mla" and self.layer_types is not None),
            ("positional='none'", self.positional == "none"),
            ("attn_scale", self.attn_scale != 0.0),
            ("residual_scale", self.residual_scale != 1.0),
            ("logit_scale", self.logit_scale != 1.0),
            ("qk_norm", self.qk_norm),
            ("rope_sliding_only", self.rope_sliding_only),
            ("norm_scheme='sandwich'", self.norm_scheme == "sandwich"),
            ("moe_n_group", self.moe_n_group > 1),
            ("moe_experts_held (a share of the experts)",
             self.experts_held < self.moe_num_experts),
            ("moe_scoring='sigmoid'", self.moe_scoring != "softmax"),
            ("moe_selection_bias", self.moe_selection_bias),
            ("moe_shared_experts", self.moe_shared_experts > 0),
            ("moe_routed_scale", self.moe_routed_scale != 1.0),
            ("moe_norm_topk=False", not self.moe_norm_topk),
            ("moe_norm_topk_eps", self.moe_norm_topk_eps != 1e-20))
            if on]
        return ", ".join(what) or None

    def refuse_served_only(self, who: str):
        if self.served_only:
            raise NotImplementedError(
                f"{who} does not implement {self.served_only}: this "
                f"block is served by InferenceEngineV2 only")

    @property
    def expert_size(self) -> int:
        """Width of one routed (or shared) expert."""
        return self.moe_intermediate_size or self.intermediate_size

    @property
    def experts_held(self) -> int:
        """Routed experts whose weights this tree holds."""
        return self.moe_experts_held or self.moe_num_experts

    @property
    def one_sublayer(self) -> bool:
        """Whether every layer is ONE sub-layer behind ONE norm, ``h = h
        + f_i(norm_i(h))``: a mixer with no MLP behind it, or an expert
        layer (``layer_types`` "moe") with no mixer ahead of it. A
        pattern says so by naming "moe" layers."""
        return self.layer_types is not None and "moe" in self.layer_types

    @property
    def expert_keys(self) -> tuple:
        """The leaves of a routed expert, in the order the grouped
        matmuls take them."""
        return ("e_up", "e_down") if self.moe_expert_form == "relu2" \
            else ("e_gate", "e_up", "e_down")

    @property
    def layer_kinds(self) -> tuple:
        """The mixer of every layer, in order: "kda" (linear attention)
        or ``attention`` under ``linear_attn_period``; "window" or "full"
        (per-head attention), "ssm" (a Mamba-2 state-space mixer),
        "retention" (a power-retention mixer), "hybrid" (a Mamba-2
        mixer AND full per-head attention on one norm, summed) or "moe"
        (an expert layer that is a layer of its own: ``one_sublayer``)
        from an explicit ``layer_types``."""
        if self.layer_types is not None:
            return tuple(self._type_kinds[t] for t in self.layer_types)
        p = self.linear_attn_period
        return tuple("kda" if p and (i + 1) % p else self.attention
                     for i in range(self.num_layers))

    @property
    def walks_runs(self) -> bool:
        """Whether the model is served by the walk of runs
        (``paged_model._pattern_step``: a stack a mixer kind, the
        leading dense stack, the deployed expert layer) and not by the
        homogeneous per-head scan."""
        return self.attention == "mla" or self.layer_types is not None

    @property
    def pattern(self) -> bool:
        """Whether the layers' mixers differ by layer: a parameter stack
        and a cache leaf a kind."""
        return self.linear_attn_period > 0 or self.layer_types is not None

    @property
    def has_state(self) -> bool:
        """Whether a sequence owns recurrent state beside its blocks."""
        return bool(set(STATE_KINDS) & set(self.layer_kinds))

    def leaf_places(self, family: str, upto: Optional[int] = None) -> int:
        """How many of the first ``upto`` layers (None: all) own a place
        in the cache leaves of ``family``: "full" (``k_full`` ...: the
        full per-head layers and the two-mixer layers) or "ssm"
        (``ssm_state`` / ``ssm_conv``: the state-space layers and the
        two-mixer layers) or "conv" (``conv_state``: the short-convolution
        layers); any other kind's leaves are its own. With
        ``upto`` a layer's index this is the layer's place along its
        leaves' leading axis."""
        owners = LEAF_OWNERS.get(family, (family,))
        return sum(k in owners for k in self.layer_kinds[:upto])

    @property
    def sublayer_scales(self) -> tuple:
        """The sub-layers' multipliers that are not 1, by name."""
        return tuple(n for n in (
            "attn_in_scale", "key_scale", "attn_out_scale", "ssm_in_scale",
            "ssm_z_scale", "ssm_x_scale", "ssm_b_scale", "ssm_c_scale",
            "ssm_dt_scale", "ssm_out_scale", "mlp_gate_scale",
            "mlp_down_scale") if getattr(self, n) != 1.0)

    @property
    def ssm_proj_scales(self) -> Optional[tuple]:
        """What a state-space mixer's projection [z | x | B | C | dt]
        is multiplied by, a (width, scalar) a segment with the input's
        multiplier folded in (the projection is linear); None where
        every one is 1."""
        n = self.mamba_n_groups * self.mamba_d_state
        segs = tuple((w, s * self.ssm_in_scale) for w, s in (
            (self.mamba_d_inner, self.ssm_z_scale),
            (self.mamba_d_inner, self.ssm_x_scale), (n, self.ssm_b_scale),
            (n, self.ssm_c_scale), (self.mamba_n_heads, self.ssm_dt_scale)))
        return segs if any(s != 1.0 for _, s in segs) else None

    @property
    def caches_positions(self) -> bool:
        """Whether any layer caches positions in blocks. A model of
        state-keeping layers alone has no pool: a sequence owns a state
        slot and no block."""
        return bool(set(PAGED_KINDS) & set(self.layer_kinds))

    @property
    def mamba_d_inner(self) -> int:
        """Channels of a Mamba-2 mixer's x, z and output."""
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def mamba_conv_dim(self) -> int:
        """Channels the mixer's convolution runs over: x, B and C."""
        return self.mamba_d_inner + 2 * self.mamba_n_groups \
            * self.mamba_d_state

    @property
    def latent_row(self) -> int:
        """What one cached position holds a layer under attention='mla':
        the normed latent and the rotated shared key part."""
        return self.latent_kind().row

    def latent_kind(self, kind: str = "mla") -> "LatentKind":
        """The sizes of a latent mixer kind: "mla" (the model's own
        ``num_heads``, ranks and head widths; ``topk`` where an indexer
        picks what it reads) or "mla_window" (the ``swa_*`` sizes over
        the last ``attn_window`` positions)."""
        if kind == "mla_window":
            return LatentKind(
                self.swa_num_heads, self.swa_q_lora_rank,
                self.swa_kv_lora_rank, self.swa_qk_nope_head_dim,
                self.swa_qk_rope_head_dim, self.swa_v_head_dim,
                self.swa_rope_theta, self.attn_window, 0)
        return LatentKind(
            self.num_heads, self.q_lora_rank, self.kv_lora_rank,
            self.qk_nope_head_dim, self.qk_rope_head_dim, self.v_head_dim,
            self.rope_theta, 0, self.index_topk)

    @property
    def is_gated_mlp(self) -> bool:
        return self.activation in ("swiglu", "geglu", "geglu_exact")


# ---------------------------------------------------------------------------


def alibi_slopes(nh: int) -> jnp.ndarray:
    """Standard ALiBi head slopes (press et al.; HF build_alibi_tensor):
    geometric sequence 2^(-8/nh) for power-of-two head counts, with the
    interleaved extension otherwise."""
    def pow2(n):
        start = 2.0 ** (-(2.0 ** -(math.log2(n) - 3)))
        return [start * (start ** i) for i in range(n)]

    if math.log2(nh).is_integer():
        return jnp.asarray(pow2(nh), jnp.float32)
    closest = 2 ** math.floor(math.log2(nh))
    extra = pow2(2 * closest)[0::2][:nh - closest]
    return jnp.asarray(pow2(closest) + extra, jnp.float32)


def rotary_dims(cfg: TransformerConfig) -> int:
    """How many leading head dims rotate (rotary_pct < 1: NeoX/Phi;
    attention='mla': all of the query's and the shared key's rope part).
    Always even."""
    if cfg.attention == "mla":
        return cfg.qk_rope_head_dim
    rot = int(cfg.head_dim * cfg.rotary_pct)
    return rot - (rot % 2)


def _rope_tables(cfg: TransformerConfig, seq_len: int, offset=0):
    """offset may be a traced scalar (decode position under jit)."""
    half = rotary_dims(cfg) // 2
    freqs = 1.0 / (cfg.rope_theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    t = offset + jnp.arange(seq_len, dtype=jnp.float32)
    angles = jnp.outer(t, freqs)                      # (S, half)
    return jnp.cos(angles), jnp.sin(angles)


def dense_mlp(cfg: TransformerConfig, lp, x):
    """Non-gated dense MLP with optional biases — ONE definition shared
    by training, v1 cached decode, and v2 paged serving (cfg.mlp_bias is
    Falcon's bias-free variant)."""
    u = x @ lp["w_up"]
    if cfg.mlp_bias:
        u = u + lp["b_up"]
    out = ffn_act(cfg)(u) @ lp["w_down"]
    if cfg.mlp_bias:
        out = out + lp["b_down"]
    return out


def gate_act(cfg: TransformerConfig):
    """Gated-MLP gate nonlinearity: silu for swiglu (llama family), tanh
    gelu for geglu (Gemma's gelu_pytorch_tanh), erf gelu for geglu_exact
    (HF hidden_activation="gelu") — the two gelus differ by ~1e-3 and
    conversions must pick the right one."""
    if cfg.activation == "swiglu":
        return jax.nn.silu
    if cfg.activation == "geglu_exact":
        return lambda x: jax.nn.gelu(x, approximate=False)
    return jax.nn.gelu


def ffn_act(cfg: TransformerConfig):
    """Non-gated FFN activation for the gelu/relu model families (one
    definition shared by training, cached decode, and paged inference).
    "gelu" is the tanh approximation (HF gelu_new, GPT-2); "gelu_exact" is
    the erf form (HF "gelu", BERT) — they differ by ~1e-3 and conversions
    must pick the right one."""
    if cfg.activation == "relu":
        return jax.nn.relu
    if cfg.activation == "gelu":
        return jax.nn.gelu
    if cfg.activation == "gelu_exact":
        return functools.partial(jax.nn.gelu, approximate=False)
    raise ValueError(f"unknown FFN activation {cfg.activation!r}")


def apply_rotary(x, cos, sin):
    """x: [B, H, S, D]; rotate-half convention (reference
    csrc/transformer/inference/csrc/apply_rotary_pos_emb.cu). When the
    tables cover fewer than D dims (partial rotary, rotary_pct < 1) the
    trailing dims pass through untouched."""
    rot = 2 * cos.shape[-1]
    tail = x[..., rot:]
    xr = x[..., :rot]
    half = rot // 2
    x1, x2 = xr[..., :half], xr[..., half:]
    c = cos[None, None, :, :]
    s = sin[None, None, :, :]
    out = jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)
    if tail.shape[-1]:
        out = jnp.concatenate([out, tail], axis=-1)
    return out.astype(x.dtype)


def qkv_proj(lp, hn):
    """q/k/v projections with optional biases (attn_bias families: GPT-2/OPT).
    hn: [..., H]; returns flat [..., nh*hd] / [..., nkv*hd] projections."""
    q = hn @ lp["wq"]
    k = hn @ lp["wk"]
    v = hn @ lp["wv"]
    if "b_q" in lp:
        q = q + lp["b_q"]
        k = k + lp["b_k"]
        v = v + lp["b_v"]
    return q, k, v


def out_proj(lp, o):
    """Attention output projection with optional bias."""
    x = o @ lp["wo"]
    if "b_o" in lp:
        x = x + lp["b_o"]
    return x


def lora_target_leaves(cfg: TransformerConfig):
    """Flat leaf paths multi-tenant serving LoRA may target (classic
    LoRA: the q and v projections) mapped to their layer-stacked
    (fan_in, fan_out) dims — the one validation surface shared by
    ``InferenceEngineV2.load_adapter`` and the adapter publication
    path, and the same flat-leaf key space the hybrid engine's external
    adapters fuse into (``runtime/hybrid_engine.fuse_flat_leaves``)."""
    h, hd = cfg.hidden_size, cfg.head_dim
    return {"layers/wq": (h, cfg.num_heads * hd),
            "layers/wv": (h, cfg.kv_heads * hd)}


def _sequence_chunks(chunk: int, *arrays):
    """``[B, S, ...]`` arrays as ``[n_chunks, B, chunk, ...]`` scan inputs,
    zero-padded to a whole number of chunks; ``chunk`` 0 is one chunk."""
    S = arrays[0].shape[1]
    chunk = min(chunk, S) if chunk and chunk > 0 else S
    pad = (-S) % chunk

    def split(a):
        if pad:
            a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        return a.reshape(a.shape[0], -1, chunk, *a.shape[2:]).swapaxes(0, 1)

    return tuple(split(a) for a in arrays)


def _ce_walk(x, head, bias, targets, mask, chunk: int, with_grads: bool):
    """The scan behind :func:`_chunked_ce_loss`: the masked nll sum and,
    ``with_grads``, its gradients for a unit cotangent ``(dx, dhead,
    dbias)`` in float32, formed from each chunk's logits in the iteration
    that made them."""
    _registry.get_registry().gauge(
        "loss_head_logit_matmuls", "vocabulary-wide matmuls a chunk of the "
        "loss head runs, set while it is traced", labelnames=("mode",)
    ).labels(mode="grad" if with_grads else "eval").set(
        3 if with_grads else 1)
    B, S, H = x.shape
    f32 = jnp.float32
    head_c = head.astype(x.dtype)
    bias32 = None if bias is None else bias.astype(f32)

    def body(carry, inputs):
        total, unit = carry
        x_c, t_c, m_c = inputs
        logits = (x_c @ head_c).astype(f32)
        if bias is not None:
            logits = logits + bias32
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        # the target's logit from its own column of the head, accumulated
        # in float32 as the matmul accumulates it: a gather from the logits
        # wants them written out in float32 first, and the compute-dtype
        # copy that the other passes read has been rounded
        tgt = jnp.einsum("bch,hbc->bc", x_c,
                         jnp.take(head_c, t_c, axis=1, mode="clip"),
                         preferred_element_type=f32)
        if bias is not None:
            tgt = tgt + bias32[t_c]
        total = total + jnp.sum((lse - tgt) * m_c)
        if not with_grads:
            return (total, unit), None
        dhead, dbias = unit
        hit = jax.lax.broadcasted_iota(
            t_c.dtype, logits.shape, logits.ndim - 1) == t_c[..., None]
        dlogits = (jnp.exp(logits - lse[..., None]) - hit) * m_c[..., None]
        if bias is not None:
            dbias = dbias + jnp.sum(dlogits, axis=(0, 1))
        # cast where autodiff casts the logits' cotangent: at the matmul
        dlogits = dlogits.astype(x.dtype)
        dx_c = jnp.einsum("bcv,hv->bch", dlogits, head_c,
                          preferred_element_type=f32)
        dhead = dhead + jnp.einsum("bch,bcv->hv", x_c, dlogits,
                                   preferred_element_type=f32)
        return (total, (dhead, dbias)), dx_c

    unit = (jnp.zeros(head.shape, f32),
            None if bias is None else jnp.zeros(bias.shape, f32)) \
        if with_grads else None
    (total, unit), dx = jax.lax.scan(
        body, (jnp.zeros((), f32), unit),
        _sequence_chunks(chunk, x, targets, mask))
    if not with_grads:
        return total, None
    return total, (dx.swapaxes(0, 1).reshape(B, -1, H)[:, :S], *unit)


def _chunked_ce_loss(x, targets, mask, head, chunk: int, bias=None):
    """Cross-entropy without materializing [B, S, V] logits: a scan over
    sequence chunks of ``chunk`` positions, so peak memory is O(chunk*V)
    and not O(S*V), which is what lets large micro-batches fit on one
    chip — the role the reference's fused CUDA softmax-xent kernels play.

    The loss is a scalar, so a chunk's logits' gradient is known the
    moment its softmax is, up to the scalar that arrives later. Under
    differentiation the forward walk therefore makes each chunk's logits
    once and forms ``(softmax - onehot) * mask`` and from it ``dx`` and
    ``dhead`` (``dbias``) on the spot; those three are all that is kept
    for the backward, in float32 and for a unit cotangent, and the
    backward only scales them by the cotangent of the total (``1/count``
    times any fp16 loss scale) before casting to the primals' dtypes.
    Nothing is recomputed: three vocabulary-wide matmuls a chunk. Not
    differentiated (``eval``), the walk makes the loss alone: one matmul,
    nothing kept. ``targets`` and ``mask`` get no gradient.
    Returns (sum of masked nll, sum of mask)."""
    dtypes = jax.tree.map(lambda p: p.dtype, (x, head, bias))

    @jax.custom_vjp
    def total_nll(x, head, bias, targets, mask):
        return _ce_walk(x, head, bias, targets, mask, chunk, False)[0]

    def fwd(x, head, bias, targets, mask):
        return _ce_walk(x, head, bias, targets, mask, chunk, True)

    def bwd(unit_grads, g):
        scaled = jax.tree.map(
            lambda d, dtype: (d * g.astype(jnp.float32)).astype(dtype),
            unit_grads, dtypes)
        return (*scaled, None, None)

    total_nll.defvjp(fwd, bwd)
    return total_nll(x, head, bias, targets, mask), jnp.sum(mask)


def _chunked_token_logprobs(x, targets, head, chunk: int):
    """Per-token ``log softmax(x @ head)[target]`` [B, S] without
    materializing [B, S, V] logits, over the same sequence chunks as
    :func:`_chunked_ce_loss` (the PPO ratio and KL terms need each
    token's logprob, not an aggregate). Its cotangent is a value per
    token and not one scalar, so a chunk's gradient is not known on the
    forward walk: the logits are made again in the backward
    (``jax.checkpoint``) rather than kept."""
    B, S, _ = x.shape

    @jax.checkpoint
    def chunk_lp(x_c, t_c):
        logits = (x_c @ head.astype(x_c.dtype)).astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, t_c[..., None], axis=-1)[..., 0]
        return tgt - lse

    def body(carry, inputs):
        return carry, chunk_lp(*inputs)

    _, lps = jax.lax.scan(body, None, _sequence_chunks(chunk, x, targets))
    return lps.swapaxes(0, 1).reshape(B, -1)[:, :S]


class TransformerLM:
    """Functional decoder-only LM implementing the engine model protocol."""

    # pp x ep composes: _layer dispatches experts with the explicit
    # static-capacity all-to-all (moe_layer_manual) inside the manual
    # pipeline program
    supports_pp_ep = True
    # offload_param streams this subtree from pinned_host per scan
    # iteration (forward_hidden); everything else (embed/head/norm) stays
    # in HBM — it is touched outside the layer loop
    param_offload_keys = ("layers",)
    # ZeRO-3 on the GSPMD path: the engine's gather-on-use for one layer's
    # slice of params["layers"] (zero/partition.scanned_gather_on_use), or
    # None. forward_hidden applies it inside the checkpointed scan body,
    # the one place that does; a model that declares the attribute is one
    # the engine may hand the function to
    layer_param_gather = None

    @property
    def supports_param_offload(self) -> bool:
        # without remat the scan saves every streamed layer as a device
        # residual for backward, silently voiding the memory bound the
        # offload exists for — refuse so the engine rejects loudly
        return bool(self.cfg.remat)

    def __init__(self, cfg: TransformerConfig):
        self.cfg = cfg
        self.topology = None  # set by the engine (set_topology) for shard_map

    def set_topology(self, topo):
        self.topology = topo

    # -- parameters --------------------------------------------------------
    def init_params(self, rng) -> Dict[str, Any]:
        cfg = self.cfg
        h, ffn, v = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
        hd, nh, nkv = cfg.head_dim, cfg.num_heads, cfg.kv_heads
        L = cfg.num_layers
        dt = jnp.float32
        k = jax.random.split(rng, 18)
        std = 0.02
        out_std = std / math.sqrt(2 * L)

        def init(key, shape, scale=std):
            return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dt)

        if cfg.walks_runs:
            return self._init_latent_params(rng)
        layer = {
            "attn_norm": jnp.ones((L, h), dt),
            "wq": init(k[0], (L, h, nh * hd)),
            "wk": init(k[1], (L, h, nkv * hd)),
            "wv": init(k[2], (L, h, nkv * hd)),
            "wo": init(k[3], (L, nh * hd, h), out_std),
            "mlp_norm": jnp.ones((L, h), dt),
        }
        if cfg.moe_num_experts > 0:
            E = cfg.moe_num_experts
            ffn = cfg.expert_size
            layer["moe_gate_w"] = init(k[4], (L, h, E))
            layer["e_gate"] = init(k[8], (L, E, h, ffn))
            layer["e_up"] = init(k[10], (L, E, h, ffn))
            layer["e_down"] = init(k[11], (L, E, ffn, h), out_std)
            layer.update(self._init_deployed_router(k[12], L, init, out_std))
            ffn = cfg.intermediate_size
            if cfg.moe_use_residual:
                layer["res_gate"] = init(k[12], (L, h, ffn))
                layer["res_up"] = init(k[13], (L, h, ffn))
                layer["res_down"] = init(k[14], (L, ffn, h), out_std)
                layer["res_coef_w"] = init(k[15], (L, h, 2))
                layer["res_coef_b"] = jnp.zeros((L, 2), dt)
        elif cfg.is_gated_mlp:
            layer["w_gate"] = init(k[4], (L, h, ffn))
            layer["w_up"] = init(k[5], (L, h, ffn))
            layer["w_down"] = init(k[6], (L, ffn, h), out_std)
        else:
            layer["w_up"] = init(k[5], (L, h, ffn))
            layer["w_down"] = init(k[6], (L, ffn, h), out_std)
            if cfg.mlp_bias:
                layer["b_up"] = jnp.zeros((L, ffn), dt)
                layer["b_down"] = jnp.zeros((L, h), dt)
        if cfg.norm == "layernorm":
            layer["attn_norm_b"] = jnp.zeros((L, h), dt)
            if not cfg.parallel_residual or cfg.parallel_norms:
                layer["mlp_norm_b"] = jnp.zeros((L, h), dt)
        if cfg.parallel_residual and not cfg.parallel_norms:
            # one shared norm: the mlp_norm slot does not exist
            del layer["mlp_norm"]
        if cfg.attn_bias:
            layer["b_q"] = jnp.zeros((L, nh * hd), dt)
            layer["b_k"] = jnp.zeros((L, nkv * hd), dt)
            layer["b_v"] = jnp.zeros((L, nkv * hd), dt)
            layer["b_o"] = jnp.zeros((L, h), dt)

        params = {
            "embed": init(k[7], (v, h)),
            "layers": layer,
        }
        if cfg.norm_scheme == "pre":
            # post-LN has no final norm (the last layer's output LN is it)
            params["final_norm"] = jnp.ones((h,), dt)
            if cfg.norm == "layernorm":
                params["final_norm_b"] = jnp.zeros((h,), dt)
        if cfg.positional == "learned":
            params["pos_embed"] = init(k[16], (cfg.max_seq_len, h))
        if cfg.embed_ln:
            params["embed_ln_w"] = jnp.ones((h,), dt)
            params["embed_ln_b"] = jnp.zeros((h,), dt)
        if cfg.mlm_head:
            params["mlm_transform_w"] = init(k[17], (h, h))
            params["mlm_transform_b"] = jnp.zeros((h,), dt)
            params["mlm_ln_w"] = jnp.ones((h,), dt)
            params["mlm_ln_b"] = jnp.zeros((h,), dt)
            params["mlm_bias"] = jnp.zeros((v,), dt)
        if not cfg.tie_embeddings:
            params["lm_head"] = init(k[9], (h, v))
        if cfg.lm_head_bias:
            params["lm_head_b"] = jnp.zeros((v,), dt)
        return params

    def _init_deployed_router(self, key, L, init, out_std):
        """The leaves the deployed expert layer adds to ``L`` expert
        layers: the selection bias and the shared experts (one SwiGLU
        ``moe_shared_experts`` experts wide)."""
        cfg, dt = self.cfg, jnp.float32
        h, fs = cfg.hidden_size, cfg.moe_shared_experts * cfg.expert_size
        out = {}
        if cfg.moe_selection_bias:
            out["moe_gate_bias"] = jnp.zeros((L, cfg.moe_num_experts), dt)
        if fs:
            ks = jax.random.split(key, 3)
            if cfg.moe_expert_form != "relu2":
                out["shared_gate"] = init(ks[0], (L, h, fs))
            out["shared_up"] = init(ks[1], (L, h, fs))
            out["shared_down"] = init(ks[2], (L, fs, h), out_std)
        return out

    def _init_latent_params(self, rng):
        """The tree of an attention='mla' model: every layer the latent
        attention's leaves; ``lead_layers`` [moe_first_dense_layers, ...]
        with a dense gated MLP apart from ``layers`` [the rest, ...],
        the scanned expert stack (every layer, where the model has no
        experts or no leading dense layer). Under a layer pattern
        (``linear_attn_period``) the mixers leave those two stacks for
        one of their own a kind, ``kda_layers`` and ``mla_layers``, each
        in layer order; an expert stack holds ``cfg.experts_held``
        experts under a router of ``moe_num_experts``. A pattern over
        per-head attention (``layer_types``) has the same four stacks
        with ``window_layers`` / ``full_layers`` as its mixers'
        (``per_head``), and under the sandwich scheme a post-norm a
        sub-layer (``attn_post_norm`` with the mixer, ``mlp_post_norm``
        with the MLP). A two-mixer layer's stack (``hybrid_layers``)
        holds both halves' leaves and one ``attn_norm``; a
        short-convolution layer's (``conv_layers``) its two projections
        and taps."""
        cfg, dt = self.cfg, jnp.float32
        h, v, nh = cfg.hidden_size, cfg.vocab_size, cfg.num_heads
        L, std = cfg.num_layers, 0.02
        out_std = std / math.sqrt(2 * L)

        def init(key, shape, scale=std):
            return (jax.random.normal(key, shape, jnp.float32)
                    * scale).astype(dt)

        def attention(key, n, mlp_norm=True, kind="mla"):
            # five keys as before the gate came: the leaves that were
            # there are seeded as they were; the gate folds one in, the
            # indexer three more. ``kind``: whose sizes
            # (``cfg.latent_kind``)
            ks = jax.random.split(key, 5)
            lk = cfg.latent_kind(kind)
            nh, qk = lk.heads, lk.nope + lk.rope
            query = {"wq_a": init(ks[0], (n, h, lk.q_rank)),
                     "q_norm": jnp.ones((n, lk.q_rank), dt),
                     "wq_b": init(ks[1], (n, lk.q_rank, nh * qk))} \
                if lk.q_rank else {"wq": init(ks[0], (n, h, nh * qk))}
            out = {
                "attn_norm": jnp.ones((n, h), dt), **query,
                "wkv_a": init(ks[2], (n, h, lk.row)),
                "kv_norm": jnp.ones((n, lk.kv_rank), dt),
                "wkv_b": init(ks[3], (n, lk.kv_rank, nh * (
                    lk.nope + lk.v))),
                "wo": init(ks[4], (n, nh * lk.v, h), out_std)}
            if cfg.attn_gate == "head":
                out["wg"] = init(jax.random.fold_in(key, 5), (n, h, nh))
            if lk.topk:
                # the indexer: its heads' queries from the query's
                # latent, ONE key a position behind a LayerNorm (weight
                # and bias), a weight a head from the layer's input
                ik = jax.random.split(jax.random.fold_in(key, 6), 3)
                ih, idim = cfg.index_n_heads, cfg.index_head_dim
                out.update(
                    index_wq=init(ik[0], (n, lk.q_rank, ih * idim)),
                    index_wk=init(ik[1], (n, h, idim)),
                    index_k_norm=jnp.ones((n, idim), dt),
                    index_k_bias=jnp.zeros((n, idim), dt),
                    index_ww=init(ik[2], (n, h, ih)))
            if mlp_norm:
                out["mlp_norm"] = jnp.ones((n, h), dt)
            return out

        def linear(key, n):
            """A linear-attention (KDA) mixer's leaves: q, k, v and the
            decay's projection ``wf`` to ``nh * linear_head_dim``, the
            update strength ``wb`` and the output gate ``wg`` one a
            head, the depthwise taps ``conv`` [taps, q | k | v], the
            decay's ``a_log`` a head and ``dt_bias`` a channel (float32
            in the checkpoint), the heads' output norm ``o_norm``."""
            ks = jax.random.split(key, 9)
            d = nh * cfg.linear_head_dim
            out = {"attn_norm": jnp.ones((n, h), dt),
                   "wq": init(ks[0], (n, h, d)),
                   "wk": init(ks[1], (n, h, d)),
                   "wv": init(ks[2], (n, h, d)),
                   "wf": init(ks[3], (n, h, d)),
                   "wb": init(ks[4], (n, h, nh)),
                   "conv": init(ks[5], (n, cfg.linear_conv_size, 3 * d),
                                cfg.linear_conv_size ** -0.5),
                   "a_log": jnp.log(jax.random.uniform(
                       ks[6], (n, nh), dt, 1.0, 16.0)),
                   "dt_bias": jnp.zeros((n, d), dt),
                   "o_norm": jnp.ones((n, cfg.linear_head_dim), dt),
                   "wo": init(ks[7], (n, d, h), out_std)}
            if cfg.attn_gate == "head":
                out["wg"] = init(ks[8], (n, h, nh))
            return out

        def per_head(key, n):
            """A per-head (GQA) mixer's leaves: ``wq`` / ``wk`` / ``wv``
            / ``wo``, the learned weights of the q and k norms a head
            lane, the element-wise output gate's projection ``wg``."""
            ks = jax.random.split(key, 5)
            hd, nkv = cfg.head_dim, cfg.kv_heads
            out = {"attn_norm": jnp.ones((n, h), dt),
                   "wq": init(ks[0], (n, h, nh * hd)),
                   "wk": init(ks[1], (n, h, nkv * hd)),
                   "wv": init(ks[2], (n, h, nkv * hd)),
                   "wo": init(ks[3], (n, nh * hd, h), out_std)}
            if cfg.qk_norm:
                out["q_norm"] = jnp.ones((n, hd), dt)
                out["k_norm"] = jnp.ones((n, hd), dt)
            if cfg.attn_gate == "elementwise":
                out["wg"] = init(ks[4], (n, h, nh * hd))
            if cfg.norm_scheme == "sandwich":
                out["attn_post_norm"] = jnp.ones((n, h), dt)
            return out

        def retention(key, n):
            """A power-retention mixer's leaves: the per-head mixer's,
            and the decay gate's projection ``w_decay`` to one scalar a
            key/value head with its bias ``b_decay`` (float32 in the
            checkpoint), drawn so that ``log sigmoid`` spans soft and
            hard decays."""
            out = per_head(key, n)
            ks = jax.random.split(jax.random.fold_in(key, 5), 2)
            out["w_decay"] = init(ks[0], (n, h, cfg.kv_heads))
            out["b_decay"] = jax.random.uniform(
                ks[1], (n, cfg.kv_heads), dt, -3.0, 5.0)
            return out

        def state_space(key, n):
            """A Mamba-2 mixer's leaves: ``w_in`` to [z | x B C | dt],
            the depthwise taps ``conv`` [taps, x | B | C] and their bias
            ``conv_b``, ``dt_bias`` / ``a_log`` / ``d_skip`` a head
            (float32 in the checkpoint), the gated norm's weight over
            the heads' whole output, ``w_out``."""
            ks = jax.random.split(key, 5)
            di, dc, mh = cfg.mamba_d_inner, cfg.mamba_conv_dim, \
                cfg.mamba_n_heads
            step = jnp.exp(jax.random.uniform(
                ks[3], (n, mh), dt, math.log(0.001), math.log(0.1)))
            out = {"attn_norm": jnp.ones((n, h), dt),
                   "w_in": init(ks[0], (n, h, di + dc + mh)),
                   "conv": init(ks[1], (n, cfg.mamba_d_conv, dc),
                                cfg.mamba_d_conv ** -0.5),
                   "dt_bias": step + jnp.log(-jnp.expm1(-step)),
                   "a_log": jnp.log(jax.random.uniform(
                       ks[2], (n, mh), dt, 1.0, 16.0)),
                   "d_skip": jnp.ones((n, mh), dt),
                   "gate_norm": jnp.ones((n, di), dt),
                   "w_out": init(ks[4], (n, di, h), out_std)}
            if cfg.mamba_conv_bias:
                out["conv_b"] = jnp.zeros((n, dc), dt)
            return out

        def hybrid(key, n):
            """A two-mixer layer's leaves: both halves' under the names
            the halves read, and ONE ``attn_norm`` for both."""
            return {**state_space(jax.random.fold_in(key, 1), n),
                    **per_head(key, n)}

        def short_conv(key, n):
            """A short-convolution mixer's leaves: ``w_in`` to [B | C |
            u], the depthwise taps ``conv`` [taps, H] (tap taps - 1
            meets the token itself), ``w_out``."""
            ks = jax.random.split(key, 3)
            return {"attn_norm": jnp.ones((n, h), dt),
                    "w_in": init(ks[0], (n, h, 3 * h)),
                    "conv": init(ks[1], (n, cfg.conv_taps, h),
                                 cfg.conv_taps ** -0.5),
                    "w_out": init(ks[2], (n, h, h), out_std)}

        def mlp_norms(n):
            return {"mlp_norm": jnp.ones((n, h), dt),
                    **({"mlp_post_norm": jnp.ones((n, h), dt)}
                       if cfg.norm_scheme == "sandwich" else {})}

        def dense(key, n):
            ks, ffn = jax.random.split(key, 3), cfg.intermediate_size
            return {"w_gate": init(ks[0], (n, h, ffn)),
                    "w_up": init(ks[1], (n, h, ffn)),
                    "w_down": init(ks[2], (n, ffn, h), out_std)}

        def experts(key, n):
            ks, E, f = jax.random.split(key, 5), cfg.moe_num_experts, \
                cfg.expert_size
            held = cfg.experts_held
            return {"moe_gate_w": init(ks[0], (n, h, E)),
                    **({"e_gate": init(ks[1], (n, held, h, f))}
                       if cfg.moe_expert_form != "relu2" else {}),
                    # a relu2 expert's ``up`` keeps the model's width last,
                    # out x in (``sharded_moe.ragged_relu2_experts``)
                    "e_up": init(ks[2], (n, held, h, f)
                                 if cfg.moe_expert_form != "relu2"
                                 else (n, held, f, h)),
                    "e_down": init(ks[3], (n, held, f, h), out_std),
                    **self._init_deployed_router(ks[4], n, init, out_std)}

        k = jax.random.split(rng, 7)
        lead = cfg.moe_first_dense_layers
        mlp = experts if cfg.moe_num_experts else dense
        params = {"embed": init(k[0], (v, h)),
                  "final_norm": jnp.ones((h,), dt)}
        if not cfg.tie_embeddings:
            params["lm_head"] = init(k[5], (h, v))
        if cfg.pattern:
            # a stack a mixer kind (``cfg.layer_kinds``), and the MLPs
            # apart: ``lead_layers`` / ``layers`` hold the norm and the
            # MLP of the leading dense and of the other layers
            kinds = cfg.layer_kinds
            mixers = {"kda": (linear, 7), "window": (per_head, 9),
                      "full": (per_head, 10), "ssm": (state_space, 11),
                      "retention": (retention, 12), "hybrid": (hybrid, 13),
                      "conv": (short_conv, 14),
                      "mla": (functools.partial(attention, mlp_norm=False),
                              8),
                      "mla_window": (functools.partial(
                          attention, mlp_norm=False, kind="mla_window"), 15)}
            for kind in dict.fromkeys(kinds):
                if kind == "moe":
                    continue
                make, fold = mixers[kind]
                params[kind + "_layers"] = make(
                    jax.random.fold_in(rng, fold), kinds.count(kind))
            if cfg.one_sublayer:
                # a layer is ONE sub-layer: ``layers`` holds the expert
                # layers alone (their norm, router and experts), in
                # layer order, and no mixer has an MLP behind it
                n = kinds.count("moe")
                params["layers"] = {**mlp_norms(n), **experts(k[2], n)}
                return params
            params["layers"] = {**mlp_norms(L - lead), **mlp(k[2], L - lead)}
            if lead:
                params["lead_layers"] = {**mlp_norms(lead),
                                         **dense(k[4], lead)}
            return params
        params["layers"] = {**attention(k[1], L - lead),
                            **mlp(k[2], L - lead)}
        if lead:
            params["lead_layers"] = {**attention(k[3], lead),
                                     **dense(k[4], lead)}
        return params

    # -- sharding (TP over "model", PP over "pipe"; ZeRO composes on top) --
    def param_partition_specs(self, topo) -> Dict[str, Any]:
        cfg = self.cfg
        if cfg.walks_runs:
            # served at tp = ep = 1 only (engine_v2 refuses the rest):
            # every leaf whole on its device
            shapes = jax.eval_shape(self.init_params, jax.random.PRNGKey(0))
            return jax.tree.map(lambda x: P(*([None] * x.ndim)), shapes)
        tp = topo.axis_size("model") if "model" in topo.sizes else 1
        pp = topo.axis_size("pipe") if "pipe" in topo.sizes else 1
        pipe = "pipe" if pp > 1 else None
        col = P(pipe, None, "model") if tp > 1 else P(pipe, None, None)
        row = P(pipe, "model", None) if tp > 1 else P(pipe, None, None)
        vec = P(pipe, None)
        layer = {
            "attn_norm": vec, "mlp_norm": vec,
            "wq": col, "wk": col, "wv": col, "wo": row,
            "w_up": col, "w_down": row,
        }
        if cfg.moe_num_experts > 0:
            ep = "expert" if topo.axis_size("expert") > 1 else None
            layer.pop("w_up"); layer.pop("w_down")
            layer["moe_gate_w"] = P(pipe, None, None)
            layer["e_gate"] = P(pipe, ep, None, "model" if tp > 1 else None)
            layer["e_up"] = P(pipe, ep, None, "model" if tp > 1 else None)
            layer["e_down"] = P(pipe, ep, "model" if tp > 1 else None, None)
            if cfg.moe_selection_bias:
                layer["moe_gate_bias"] = P(pipe, None)
            if cfg.moe_shared_experts:
                layer["shared_gate"] = layer["shared_up"] = col
                layer["shared_down"] = row
            if cfg.moe_use_residual:
                layer["res_gate"] = col
                layer["res_up"] = col
                layer["res_down"] = row
                layer["res_coef_w"] = P(pipe, None, None)
                layer["res_coef_b"] = P(pipe, None)
        elif cfg.is_gated_mlp:
            layer["w_gate"] = col
        else:
            if cfg.mlp_bias:
                layer["b_up"] = (P(pipe, "model") if tp > 1
                                 else P(pipe, None))
                layer["b_down"] = vec
        if cfg.norm == "layernorm":
            layer["attn_norm_b"] = vec
            if not cfg.parallel_residual or cfg.parallel_norms:
                layer["mlp_norm_b"] = vec
        if cfg.parallel_residual and not cfg.parallel_norms:
            layer.pop("mlp_norm")
        if cfg.attn_bias:
            col_b = P(pipe, "model") if tp > 1 else P(pipe, None)
            layer["b_q"] = col_b
            layer["b_k"] = col_b
            layer["b_v"] = col_b
            layer["b_o"] = vec
        specs = {
            "embed": P("model", None) if tp > 1 else P(None, None),
            "layers": layer,
        }
        if cfg.norm_scheme == "pre":
            specs["final_norm"] = P(None)
            if cfg.norm == "layernorm":
                specs["final_norm_b"] = P(None)
        if cfg.positional == "learned":
            specs["pos_embed"] = P(None, None)
        if cfg.embed_ln:
            specs["embed_ln_w"] = P(None)
            specs["embed_ln_b"] = P(None)
        if cfg.lm_head_bias:
            specs["lm_head_b"] = P("model") if tp > 1 else P(None)
        if cfg.mlm_head:
            specs["mlm_transform_w"] = P(None, None)
            specs["mlm_transform_b"] = P(None)
            specs["mlm_ln_w"] = P(None)
            specs["mlm_ln_b"] = P(None)
            specs["mlm_bias"] = P(None)
        if not cfg.tie_embeddings:
            specs["lm_head"] = P(None, "model") if tp > 1 else P(None, None)
        return specs

    # -- forward -----------------------------------------------------------
    def _norm(self, x, w, b=None):
        if self.cfg.norm == "rmsnorm":
            return rms_norm(x, w, self.cfg.norm_eps)
        return layer_norm(x, w, b, self.cfg.norm_eps)

    def _flash_at(self, seq: int) -> bool:
        """XLA fused attention for short sequences, Pallas flash once the
        S^2 score tensor dominates (see flash_min_seq rationale); the ALiBi
        branch is always XLA's."""
        cfg = self.cfg
        return (cfg.use_flash and cfg.positional != "alibi"
                and seq >= cfg.flash_min_seq)

    def _attention(self, q, k, v):
        cfg = self.cfg
        from ..sequence.layer import sharded_attention

        if cfg.positional == "alibi":
            # ALiBi bias is softmax-invariant in the query position, so
            # it reduces to slope_h * key_pos — one [H, 1, S] row added
            # pre-softmax. Plain einsum path (GSPMD partitions dp/tp);
            # flash/sequence-parallel do not carry the bias.
            if (self.topology is not None
                    and self.topology.axis_size("seq") > 1):
                raise NotImplementedError(
                    "alibi attention does not compose with sequence "
                    "parallelism")
            B, H, S, D = q.shape
            if k.shape[1] != H:
                k = jnp.repeat(k, H // k.shape[1], axis=1)
                v = jnp.repeat(v, H // v.shape[1], axis=1)
            scores = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(
                jnp.float32) / math.sqrt(D)
            bias = alibi_slopes(cfg.num_heads)[:, None, None] \
                * jnp.arange(S, dtype=jnp.float32)[None, None, :]
            scores = scores + bias[None]
            if cfg.is_causal:
                causal = jnp.tril(jnp.ones((S, S), bool))
                scores = jnp.where(causal[None, None], scores, -1e30)
            probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
            o = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
            return checkpoint_name(o, "attn_out")

        use_flash = self._flash_at(q.shape[2])
        # the output is named "attn_out" for selective remat where it is
        # made (sequence/layer._inner_attention for the XLA and ring paths;
        # the flash kernel names o and its row statistics itself, as
        # "attn_out" and "attn_lse"): a policy that saves those names
        # (save_attn, the engine's default when memory allows) leaves no
        # attention forward to re-run in the backward
        return sharded_attention(q, k, v, self.topology,
                                 causal=cfg.is_causal, use_flash=use_flash,
                                 block_q=cfg.attn_block_q,
                                 block_kv=cfg.attn_block_kv,
                                 impl=cfg.seq_parallel_impl)

    def _layer(self, x, lp, cos, sin):
        """One block, in two named scopes (``attention``, then ``mlp`` or
        ``moe``): what the step's phase metrics and XProf group by."""
        cfg = self.cfg
        if cfg.parallel_residual:
            with jax.named_scope("attention"):
                hn, attn_out = self._attention_sublayer(x, lp, cos, sin)
            with jax.named_scope("mlp"):
                # Falcon block: both sublayers read the normed input and
                # the residual adds once; NeoX (parallel_norms) norms
                # separately
                hn2 = (self._norm(x, lp["mlp_norm"], lp.get("mlp_norm_b"))
                       if cfg.parallel_norms else hn)
                return (x + attn_out + dense_mlp(cfg, lp, hn2),
                        jnp.zeros((), jnp.float32))
        with jax.named_scope("attention"):
            _, attn_out = self._attention_sublayer(x, lp, cos, sin)
            x = x + attn_out
            if cfg.norm_scheme == "post":
                x = self._norm(x, lp["attn_norm"], lp.get("attn_norm_b"))
        with jax.named_scope("moe" if cfg.moe_num_experts > 0 else "mlp"):
            return self._mlp_sublayer(x, lp)

    def _attention_sublayer(self, x, lp, cos, sin):
        """(normed input, attention output before the residual add)."""
        cfg = self.cfg
        B, S, H = x.shape
        nh, nkv, hd = cfg.num_heads, cfg.kv_heads, cfg.head_dim
        # post-LN (original BERT; reference kernel pre_layer_norm=False):
        # the sublayer reads the raw residual stream and the norm lands
        # AFTER the residual add
        hn = x if cfg.norm_scheme == "post" else self._norm(
            x, lp["attn_norm"], lp.get("attn_norm_b"))
        q, k, v = qkv_proj(lp, hn)
        q = q.reshape(B, S, nh, hd).transpose(0, 2, 1, 3)
        k = k.reshape(B, S, nkv, hd).transpose(0, 2, 1, 3)
        v = v.reshape(B, S, nkv, hd).transpose(0, 2, 1, 3)
        if cfg.positional == "rope":
            q = apply_rotary(q, cos, sin)
            k = apply_rotary(k, cos, sin)
        o = self._attention(q, k, v)
        o = o.transpose(0, 2, 1, 3).reshape(B, S, nh * hd)
        return hn, out_proj(lp, o)

    def _mlp_sublayer(self, x, lp):
        cfg = self.cfg
        post = cfg.norm_scheme == "post"
        hn = x if post else self._norm(x, lp["mlp_norm"],
                                       lp.get("mlp_norm_b"))
        aux = jnp.zeros((), jnp.float32)
        if cfg.moe_num_experts > 0:
            from ..moe.sharded_moe import (moe_layer, moe_layer_dropless,
                                           moe_layer_manual,
                                           residual_moe_combine)

            def expert_fn(p, xe):
                wg, wu, wd = p
                return (jax.nn.silu(xe @ wg) * (xe @ wu)) @ wd

            experts = (lp["e_gate"], lp["e_up"], lp["e_down"])
            if cfg.moe_dropless:
                if cfg.moe_top_k != 1:
                    raise NotImplementedError(
                        "moe_dropless supports top-1 routing only "
                        f"(got moe_top_k={cfg.moe_top_k})")
                if getattr(self, "_inside_manual_pipe", False) and \
                        self.topology.axis_size("expert") > 1:
                    raise NotImplementedError(
                        "dropless MoE is not supported inside the manual "
                        "pipeline program with ep>1 (use capacity routing "
                        "for pp x ep)")
                if (self.topology is not None
                        and self.topology.axis_size("expert") > 1):
                    from ..moe.sharded_moe import moe_layer_dropless_ep
                    # ep>1: worst-case static capacity (C=T) dispatch —
                    # see moe_layer_dropless_ep for the memory trade
                    moe_out, aux = moe_layer_dropless_ep(
                        hn, lp["moe_gate_w"], experts, expert_fn,
                        self.topology)
                else:
                    moe_out, aux = moe_layer_dropless(
                        hn, lp["moe_gate_w"], experts, topo=self.topology)
            elif (getattr(self, "_inside_manual_pipe", False)
                  and self.topology.axis_size("expert") > 1):
                # pp x ep: inside the manual 1F1B shard_map GSPMD cannot
                # insert the expert collective — dispatch with the
                # explicit static-capacity all-to-all; expert params are
                # already the local [E/ep, ...] slice
                moe_out, aux = moe_layer_manual(
                    hn, lp["moe_gate_w"], experts, expert_fn,
                    ep_axis="expert", top_k=cfg.moe_top_k,
                    capacity_factor=cfg.moe_capacity_factor,
                    min_capacity=cfg.moe_min_capacity,
                    noisy_gate_policy=cfg.moe_noisy_gate_policy)
            else:
                moe_out, aux = moe_layer(
                    hn, lp["moe_gate_w"], experts,
                    expert_fn, self.topology, top_k=cfg.moe_top_k,
                    capacity_factor=cfg.moe_capacity_factor,
                    min_capacity=cfg.moe_min_capacity,
                    noisy_gate_policy=cfg.moe_noisy_gate_policy)
            if cfg.moe_use_residual:
                dense = (jax.nn.silu(hn @ lp["res_gate"])
                         * (hn @ lp["res_up"])) @ lp["res_down"]
                moe_out = residual_moe_combine(hn, moe_out, dense,
                                               lp["res_coef_w"],
                                               lp["res_coef_b"])
            x = x + moe_out
        elif cfg.is_gated_mlp:
            g = gate_act(cfg)(hn @ lp["w_gate"])
            u = hn @ lp["w_up"]
            x = x + (g * u) @ lp["w_down"]
        else:
            x = x + dense_mlp(cfg, lp, hn)
        if post:
            x = self._norm(x, lp["mlp_norm"], lp.get("mlp_norm_b"))
        return x, aux

    def forward_hidden(self, params, input_ids):
        cfg = self.cfg
        cfg.refuse_served_only("TransformerLM's training forward")
        with jax.named_scope("embed"):
            x = params["embed"][input_ids]                # [B, S, H] gather
            if cfg.embed_scale != 1.0:
                x = x * jnp.asarray(cfg.embed_scale, x.dtype)
            if cfg.positional == "learned":
                x = x + params["pos_embed"][: input_ids.shape[1]][None]
            if "embed_ln_w" in params:
                # BERT-family embedding LayerNorm (applied to the summed
                # word+position embeddings; HF bert.embeddings.LayerNorm)
                x = layer_norm(x, params["embed_ln_w"],
                               params.get("embed_ln_b"), cfg.norm_eps)
            S = input_ids.shape[1]
            if cfg.positional == "rope":
                cos, sin = _rope_tables(cfg, S)
                cos = cos.astype(x.dtype)
                sin = sin.astype(x.dtype)
            else:
                cos = sin = jnp.zeros((S, 1), x.dtype)

        body = self._layer
        if getattr(self, "stream_params_from_host", False):
            # ZeRO-Infinity param offload (engine.param_offload): the layer
            # stack is STORED in pinned_host; pull only this iteration's
            # slice into HBM. Placed INSIDE the remat boundary so the saved
            # residuals are the host slices, not device copies — backward
            # re-fetches each layer exactly like the reference's param
            # swapper (swap_tensor/partitioned_param_swapper.py:36).
            inner = body

            def body(h, lp, cos, sin, _inner=inner):
                lp = jax.tree.map(
                    lambda a: jax.device_put(a, jax.memory.Space.Device), lp)
                return _inner(h, lp, cos, sin)

        gather = self.layer_param_gather
        if gather is not None:
            # ZeRO-3 allgather-on-use, and its transpose the reduction of
            # the layer's weight gradients into their shards. INSIDE the
            # remat boundary for the same reason as the wrap above: what
            # is saved for the backward is the shard, and the backward
            # gathers again
            inner = body

            def body(h, lp, cos, sin, _inner=inner):
                return _inner(h, gather(lp), cos, sin)

        if cfg.remat:
            from ..runtime.activation_checkpointing import checkpointing as ds_ckpt
            body = ds_ckpt.checkpoint_wrapper(body)

        def scan_fn(h, lp):
            # WOQ leaves dequantize per layer INSIDE the scan body (fused
            # into the consuming matmuls); identity on dense params. An
            # upfront whole-tree dequant materializes every layer as scan
            # inputs (r05 AOT serving fit: ~23 GiB on a 7B).
            from ..inference.quantization import dequantize_params
            h, aux = body(h, dequantize_params(lp), cos, sin)
            return h, aux

        unroll = max(self.cfg.scan_unroll,
                     getattr(self, "scan_unroll_hint", 1))
        with jax.named_scope("layers"):
            x, aux = jax.lax.scan(scan_fn, x, params["layers"],
                                  unroll=unroll)
        if cfg.norm_scheme == "pre":
            # post-LN has no final norm: the last layer's output LN is it
            with jax.named_scope("loss_head"):
                x = self._norm(x, params["final_norm"],
                               params.get("final_norm_b"))
        return x, jnp.mean(aux)

    def _head_inputs(self, params, x):
        """(transformed hidden, head matrix, logit bias): the MLM prediction
        head (HF cls.predictions: dense+gelu+LN+decoder bias) applies when
        its params are present; otherwise the plain (tied) LM head."""
        bias = None
        if "mlm_transform_w" in params:
            x = ffn_act(self.cfg)(
                x @ params["mlm_transform_w"].astype(x.dtype)
                + params["mlm_transform_b"].astype(x.dtype))
            x = layer_norm(x, params["mlm_ln_w"], params.get("mlm_ln_b"),
                           self.cfg.norm_eps)
            bias = params.get("mlm_bias")
        else:
            # Phi-class causal heads carry a logit bias
            bias = params.get("lm_head_b")
        head = (params["embed"].T if self.cfg.tie_embeddings
                else params["lm_head"])
        return x, head, bias

    def forward_logits(self, params, input_ids):
        x, _ = self.forward_hidden(params, input_ids)
        x, head, bias = self._head_inputs(params, x)
        logits = x @ head.astype(x.dtype)
        if bias is not None:
            logits = logits + bias.astype(logits.dtype)
        return logits

    # -- pipeline-parallel forward (compiled 1F1B-style, runtime/pipe) ------
    def _apply_pipelined(self, params, batch, train: bool = True, rng=None):
        """Pipelined loss over the "pipe" axis. batch: {input_ids [M, B, S]}
        where M = num microbatches (= gradient_accumulation_steps)."""
        from ..runtime.pipe.pipeline import (broadcast_from_last,
                                             pipeline_scan)
        from ..parallel.topology import PIPE_AXIS

        topo = self.topology
        cfg = self.cfg
        pp = topo.axis_size(PIPE_AXIS)
        ids = batch["input_ids"]
        M, B, S = ids.shape
        cos, sin = _rope_tables(cfg, S)
        dp_axes = topo.batch_axes
        batch_spec = dp_axes if len(dp_axes) > 1 else dp_axes[0]

        param_specs = self.param_partition_specs(topo)
        ids_spec = P(None, batch_spec, None)
        mask = batch.get("loss_mask")
        mask_specs = (ids_spec,) if mask is not None else ()

        def body(params, ids_local, *mask_local):
            x = params["embed"][ids_local]               # [M, b, S, H] (all stages)
            if cfg.embed_scale != 1.0:
                x = x * jnp.asarray(cfg.embed_scale, x.dtype)
            if cfg.positional == "learned":
                x = x + params["pos_embed"][None, None, :x.shape[2]].astype(
                    x.dtype)
            cos_c = cos.astype(x.dtype)
            sin_c = sin.astype(x.dtype)
            layers_local = params["layers"]              # [L/pp, ...]

            layer_body = self._layer
            if cfg.remat:
                from ..runtime.activation_checkpointing import (
                    checkpointing as ds_ckpt)
                layer_body = ds_ckpt.checkpoint_wrapper(self._layer)

            moe = cfg.moe_num_experts > 0

            def stage_fn(h):
                def scan_fn(carry, lp):
                    out, aux = layer_body(carry, lp, cos_c, sin_c)
                    return out, aux
                out, auxs = jax.lax.scan(scan_fn, h, layers_local)
                if moe:
                    # stage-local share of the layer-mean aux loss
                    return out, (cfg.moe_aux_loss_coef * jnp.sum(auxs)
                                 / cfg.num_layers)
                return out

            if moe:
                ys, aux_sum = pipeline_scan(stage_fn, x, pp, remat=False,
                                            stage_aux=True)
            else:
                ys = pipeline_scan(stage_fn, x, pp, remat=False)  # [M,b,S,H]
            ys = self._norm(ys, params["final_norm"],
                            params.get("final_norm_b"))
            head = (params["embed"].T if cfg.tie_embeddings
                    else params["lm_head"])
            logits = (ys @ head.astype(ys.dtype)).astype(jnp.float32)
            if "lm_head_b" in params:
                logits = logits + params["lm_head_b"].astype(jnp.float32)
            logits = logits[:, :, :-1]
            targets = ids_local[:, :, 1:]
            logp = jax.nn.log_softmax(logits, axis=-1)
            nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
            if mask_local:
                m = mask_local[0][:, :, 1:].astype(jnp.float32)
                loss_local = jnp.sum(nll * m) / jnp.maximum(jnp.sum(m), 1.0)
            else:
                loss_local = jnp.mean(nll)
            # only the last stage's loss is real; make it replicated everywhere
            loss = broadcast_from_last(loss_local, pp)
            if moe:
                # every stage contributed aux for its own layers
                loss = loss + jax.lax.psum(aux_sum, "pipe") / M
            return jax.lax.pmean(loss, dp_axes)

        args = (params, ids) + ((mask,) if mask is not None else ())
        self._inside_manual_pipe = True
        try:
            from ..comm.quantized import shard_map_unchecked
            return shard_map_unchecked(
                body, mesh=topo.mesh,
                in_specs=(param_specs, ids_spec) + mask_specs,
                out_specs=P())(*args)
        finally:
            self._inside_manual_pipe = False

    def loss_and_grads(self, params, batch, rng=None):
        """(loss, grads) through the bounded-memory 1F1B pipeline
        (runtime/pipe/pipeline.py pipeline_1f1b) — the training path under
        pp>1; replaces autodiff over the GPipe-shaped forward scan whose
        tick stack grew with the microbatch count. batch: {input_ids
        [M, B, S], optional loss_mask}."""
        from ..runtime.pipe.pipeline import pipeline_1f1b, stage_index
        from ..parallel.topology import PIPE_AXIS

        topo = self.topology
        cfg = self.cfg
        pp = topo.axis_size(PIPE_AXIS)
        ids = batch["input_ids"]
        M, B, S = ids.shape
        cos, sin = _rope_tables(cfg, S)
        dp_axes = topo.dp_axes
        bt = topo.batch_axes
        param_specs = self.param_partition_specs(topo)
        ids_spec = P(None, bt, None)
        mask = batch.get("loss_mask")
        mask_specs = (ids_spec,) if mask is not None else ()
        # stacked layer weights are pipe-SHARDED (each stage owns its
        # slice); everything else is replicated over pipe
        reduce_mask = {k: jax.tree.map(lambda _: k != "layers", v)
                       for k, v in params.items()}

        def body(p, ids_l, *mask_l):
            cos_c = cos.astype(p["embed"].dtype)
            sin_c = sin.astype(p["embed"].dtype)
            layer_body = self._layer
            if cfg.remat:
                from ..runtime.activation_checkpointing import (
                    checkpointing as ds_ckpt)
                layer_body = ds_ckpt.checkpoint_wrapper(self._layer)

            moe = cfg.moe_num_experts > 0

            def stage_fn(pp_, ids_mb, h):
                x0 = pp_["embed"][ids_mb]
                if cfg.positional == "learned":
                    x0 = x0 + pp_["pos_embed"][None, :x0.shape[1]].astype(
                        x0.dtype)
                x = jnp.where(stage_index() == 0, x0, h)

                def scan_fn(carry, lp):
                    out, aux = layer_body(carry, lp, cos_c, sin_c)
                    return out, aux

                out, auxs = jax.lax.scan(scan_fn, x, pp_["layers"])
                if moe:
                    # stage-local, pre-scaled share of the layer-mean aux
                    # loss; pipeline_1f1b differentiates it in this stage's
                    # backward slot (cotangent 1.0)
                    return out, (cfg.moe_aux_loss_coef * jnp.sum(auxs)
                                 / cfg.num_layers).astype(jnp.float32)
                return out

            def loss_fn(p_, ys, ids_mb, *m_mb):
                # per-microbatch masked mean, averaged over microbatches by
                # the pipeline — the same mean-of-means the engine's gas
                # scan computes on the non-pipeline path
                ys = self._norm(ys, p_["final_norm"], p_.get("final_norm_b"))
                head = (p_["embed"].T if cfg.tie_embeddings
                        else p_["lm_head"])
                m = (m_mb[0][:, 1:].astype(jnp.float32) if m_mb
                     else jnp.ones(ids_mb[:, 1:].shape, jnp.float32))
                total, count = _chunked_ce_loss(ys[:, :-1], ids_mb[:, 1:],
                                                m, head, cfg.loss_chunk)
                return total / jnp.maximum(count, 1.0)

            b_local = ids_l.shape[1]
            h_spec = jax.ShapeDtypeStruct((b_local, S, cfg.hidden_size),
                                          p["embed"].dtype)
            loss, grads = pipeline_1f1b(
                stage_fn, loss_fn, p, ids_l, pp, h_spec=h_spec,
                loss_args=(ids_l,) + tuple(mask_l), dp_axes=(),
                pipe_reduce_mask=reduce_mask, stage_aux=moe)
            # data-parallel reduction, per leaf: skip any axis the leaf is
            # SHARDED on (under pp x ep the expert-sharded weights hold
            # different experts across the expert axis — a pmean over it
            # would average distinct experts into garbage). A leaf sharded
            # on a dp axis accumulated a SUM over that axis's group (the
            # a2a routed every group member's tokens through it), so the
            # mean still owes a 1/size division for those axes.
            loss = jax.lax.pmean(loss, dp_axes)

            def dp_reduce(g, spec):
                used = {a for e in spec
                        for a in (e if isinstance(e, tuple) else (e,))
                        if a is not None}
                axes_r = tuple(a for a in dp_axes if a not in used)
                if axes_r:
                    g = jax.lax.pmean(g, axes_r)
                denom = 1
                for a in dp_axes:
                    if a in used:
                        denom *= topo.axis_size(a)
                return g / denom if denom > 1 else g

            grads = jax.tree.map(dp_reduce, grads, param_specs)
            return loss, grads

        args = (params, ids) + ((mask,) if mask is not None else ())
        grad_specs = param_specs
        # _layer switches MoE to the explicit-all-to-all dispatch while the
        # fully-manual pipeline program traces (pp x ep)
        self._inside_manual_pipe = True
        try:
            from ..comm.quantized import shard_map_unchecked
            return shard_map_unchecked(
                body, mesh=topo.mesh,
                in_specs=(param_specs, ids_spec) + mask_specs,
                out_specs=(P(), grad_specs))(*args)
        finally:
            self._inside_manual_pipe = False

    def apply(self, params, batch, train: bool = True, rng=None):
        """Loss for one batch. objective="causal_lm": next-token loss on
        {input_ids [B,S], optional loss_mask}; objective="mlm" (BERT
        family): masked-LM loss on {input_ids, labels, loss_mask} with
        bidirectional attention, no shift. Under pipeline parallelism
        input_ids is [M, B, S].

        A batch carrying ``ppo_old_logprobs`` routes to the clipped-PPO
        objective (:meth:`_apply_ppo`) — the RLHF learner's loss. The
        batch-dict STRUCTURE is part of the jit trace, so PPO batches
        compile their own program per shape bucket and coexist with LM
        batches in one engine without respecialization."""
        if "ppo_old_logprobs" in batch:
            return self._apply_ppo(params, batch)
        if self.topology is not None and self.topology.axis_size("pipe") > 1:
            assert self.cfg.is_causal, \
                "pipeline parallelism supports objective='causal_lm' only"
            assert self.cfg.norm_scheme == "pre", \
                "pipeline parallelism supports norm_scheme='pre' only"
            return self._apply_pipelined(params, batch, train=train, rng=rng)
        ids = batch["input_ids"]
        # shift AFTER the forward so the model sees the full (sp-divisible)
        # sequence length under sequence parallelism
        x, aux = self.forward_hidden(params, ids)
        mask = batch.get("loss_mask")
        if self.cfg.objective == "mlm":
            # loss at the masked positions against the original tokens. A
            # missing loss_mask is always a caller error for MLM: defaulting
            # to all-ones would make ~85% of the loss a trivial copy task
            assert mask is not None, \
                "objective='mlm' requires batch['loss_mask'] (1 at masked " \
                "positions)"
        with jax.named_scope("loss_head"):
            if self.cfg.objective == "mlm":
                x, head, bias = self._head_inputs(params, x)
                total, count = _chunked_ce_loss(
                    x, batch["labels"], mask.astype(jnp.float32), head,
                    self.cfg.loss_chunk, bias=bias)
            else:
                head = (params["embed"].T if self.cfg.tie_embeddings
                        else params["lm_head"])
                mask = (mask[:, 1:].astype(jnp.float32) if mask is not None
                        else jnp.ones(ids[:, 1:].shape, jnp.float32))
                total, count = _chunked_ce_loss(x[:, :-1], ids[:, 1:], mask,
                                                head, self.cfg.loss_chunk)
            loss = total / jnp.maximum(count, 1.0)
            if self.cfg.moe_num_experts > 0:
                loss = loss + self.cfg.moe_aux_loss_coef * aux
        return loss

    def _apply_ppo(self, params, batch):
        """Clipped-PPO loss with a reference-policy KL term (the RLHF
        learner objective; rl/learner.py packs the batch).

        Batch (all [B, S] aligned with ``input_ids``, plus
        ``ppo_hparams`` [B, 2]):
          * ``loss_mask`` — 1 at GENERATED token positions (the
            rollout's sampled tokens; prompt + pad are 0),
          * ``ppo_old_logprobs`` — the behavior policy's per-token
            logprobs recorded AT ROLLOUT TIME (serving as both the
            importance-ratio denominator and the reference policy of
            the KL term — no second reference forward),
          * ``ppo_advantages`` — host-computed GAE advantages
            (rl/advantage.py),
          * ``ppo_hparams`` — every row ``[clip_eps, kl_coef]``:
            traced values, so tuning them never recompiles.

        Per masked token t (predicted at position t-1 — the causal
        shift):  ratio = exp(new_lp - old_lp),
        pg = -min(ratio*adv, clip(ratio, 1±eps)*adv), and the k3 KL
        estimator kl = exp(old-new) - 1 - (old-new) (unbiased,
        non-negative). Loss is the masked mean of pg + kl_coef*kl —
        same masked-mean discipline as the LM objective, so the
        engine's fp16 loss scaling and gradient plumbing apply
        verbatim."""
        assert self.cfg.is_causal, \
            "PPO batches require objective='causal_lm' (the rollout " \
            "policy is a decoder)"
        assert (self.topology is None
                or self.topology.axis_size("pipe") == 1), \
            "PPO learner batches are not supported under pipeline " \
            "parallelism yet (the shifted per-token logprob gather " \
            "needs the last stage's full sequence)"
        ids = batch["input_ids"]
        x, aux = self.forward_hidden(params, ids)
        head = (params["embed"].T if self.cfg.tie_embeddings
                else params["lm_head"])
        new_lp = _chunked_token_logprobs(x[:, :-1], ids[:, 1:], head,
                                         self.cfg.loss_chunk)
        mask = batch["loss_mask"][:, 1:].astype(jnp.float32)
        old_lp = batch["ppo_old_logprobs"][:, 1:].astype(jnp.float32)
        adv = batch["ppo_advantages"][:, 1:].astype(jnp.float32)
        hp = batch["ppo_hparams"].astype(jnp.float32)
        # every row carries the same (clip_eps, kl_coef); the mean is a
        # plain reduction (no single-row gather across the dp shards)
        clip_eps = jnp.mean(hp[:, 0])
        kl_coef = jnp.mean(hp[:, 1])
        ratio = jnp.exp(new_lp - old_lp)
        surrogate = jnp.minimum(
            ratio * adv,
            jnp.clip(ratio, 1.0 - clip_eps, 1.0 + clip_eps) * adv)
        log_ref_over_new = old_lp - new_lp
        kl = jnp.exp(log_ref_over_new) - 1.0 - log_ref_over_new
        per_token = -surrogate + kl_coef * kl
        loss = (jnp.sum(per_token * mask)
                / jnp.maximum(jnp.sum(mask), 1.0))
        if self.cfg.moe_num_experts > 0:
            loss = loss + self.cfg.moe_aux_loss_coef * aux
        return loss

    # -- KV-cache inference (prefill + decode) ------------------------------
    # TPU-native replacement for the reference's inference kernel path
    # (csrc/transformer/inference KV transforms; inference/v2 blocked KV):
    # dense per-layer cache updated with dynamic_update_slice under jit.
    def init_kv_cache(self, batch_size: int, max_len: int,
                      dtype=jnp.bfloat16) -> Dict[str, jnp.ndarray]:
        cfg = self.cfg
        assert cfg.is_causal, \
            "KV-cache generation requires objective='causal_lm' (the MLM " \
            "encoder family attends bidirectionally and does not decode)"
        assert cfg.norm_scheme == "pre", \
            "KV-cache generation supports norm_scheme='pre' only"
        shape = (cfg.num_layers, batch_size, cfg.kv_heads, max_len, cfg.head_dim)
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}

    def _layer_cached(self, x, lp, ck, cv, cos, sin, start_pos, max_len):
        """One layer step attending over the cache. x: [B, S, H] (S=prefill
        length or 1 for decode); ck/cv: [B, nkv, max_len, hd]; cos/sin:
        position-offset RoPE tables [S, hd//2]. Returns (x, new_ck, new_cv)."""
        cfg = self.cfg
        B, S, H = x.shape
        nh, nkv, hd = cfg.num_heads, cfg.kv_heads, cfg.head_dim

        hn = self._norm(x, lp["attn_norm"], lp.get("attn_norm_b"))
        q, k, v = qkv_proj(lp, hn)
        q = q.reshape(B, S, nh, hd).transpose(0, 2, 1, 3)
        k = k.reshape(B, S, nkv, hd).transpose(0, 2, 1, 3)
        v = v.reshape(B, S, nkv, hd).transpose(0, 2, 1, 3)
        if cfg.positional == "rope":
            q = apply_rotary(q, cos, sin)
            k = apply_rotary(k, cos, sin)

        ck = jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype),
                                          (0, 0, start_pos, 0))
        cv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype),
                                          (0, 0, start_pos, 0))

        topo = self.topology
        tp1 = topo is None or ("model" not in topo.sizes
                               or topo.axis_size("model") <= 1)
        # tp>1 keeps the einsum path: GSPMD can partition it over the head
        # axis, while a bare pallas_call is not partition-safe
        if (cfg.decode_kernel and S == 1 and hd % 8 == 0 and tp1
                and cfg.positional != "alibi"):
            # Pallas dense-cache decode: streams each kv head's cache once
            # (no GQA repeat materialization) and skips blocks past the
            # sequence length — the v1-kernel decode path (reference
            # csrc/transformer/inference attention kernels)
            from ..ops.decode_attention import dense_decode_attention

            lengths = jnp.broadcast_to(start_pos + 1, (B,))
            o = dense_decode_attention(q[:, :, 0].astype(ck.dtype), ck, cv,
                                       lengths)
            o = o[:, :, None].astype(x.dtype)                  # [B,nh,1,hd]
        else:
            # attend over cache[0:max_len] with validity+causal mask. Dots
            # stay in the cache dtype with f32 accumulation (decode is
            # HBM-bound: upcasting the cache to f32 would double the read
            # traffic — the fix the reference makes with its fp16 inference
            # kernels, csrc/transformer/inference)
            rep = nh // nkv
            kk = jnp.repeat(ck, rep, axis=1)                   # [B,nh,M,hd]
            vv = jnp.repeat(cv, rep, axis=1)
            s = jnp.einsum("bhsd,bhmd->bhsm", q.astype(kk.dtype), kk,
                           preferred_element_type=jnp.float32) / math.sqrt(hd)
            q_pos = start_pos + jnp.arange(S)[:, None]         # [S,1]
            k_pos = jnp.arange(max_len)[None, :]               # [1,M]
            if cfg.positional == "alibi":
                s = s + (alibi_slopes(nh)[:, None, None]
                         * k_pos.astype(jnp.float32))[None]
            mask = k_pos <= q_pos                              # causal+valid
            s = jnp.where(mask[None, None], s, -1e30)
            p = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("bhsm,bhmd->bhsd", p.astype(vv.dtype), vv,
                           preferred_element_type=jnp.float32).astype(x.dtype)
        o = o.transpose(0, 2, 1, 3).reshape(B, S, nh * hd)
        if cfg.parallel_residual:
            hn2 = (self._norm(x, lp["mlp_norm"], lp.get("mlp_norm_b"))
                   if cfg.parallel_norms else hn)
            return (x + out_proj(lp, o) + dense_mlp(cfg, lp, hn2),
                    ck, cv)
        x = x + out_proj(lp, o)

        hn = self._norm(x, lp["mlp_norm"], lp.get("mlp_norm_b"))
        if cfg.moe_num_experts > 0:
            # inference MoE: dense top-k gating without capacity dropping
            gate = jax.nn.softmax(
                (hn @ lp["moe_gate_w"]).astype(jnp.float32), axis=-1)
            topv, topi = jax.lax.top_k(gate, cfg.moe_top_k)
            topv = topv / jnp.sum(topv, axis=-1, keepdims=True)
            out = jnp.zeros_like(hn)
            for j in range(cfg.moe_top_k):
                eg = lp["e_gate"][topi[..., j]]
                eu = lp["e_up"][topi[..., j]]
                ed = lp["e_down"][topi[..., j]]
                h = jax.nn.silu(jnp.einsum("bsh,bshf->bsf", hn, eg)) * \
                    jnp.einsum("bsh,bshf->bsf", hn, eu)
                out = out + (topv[..., j:j + 1] * jnp.einsum(
                    "bsf,bsfh->bsh", h, ed)).astype(hn.dtype)
            x = x + out
        elif cfg.is_gated_mlp:
            g = gate_act(cfg)(hn @ lp["w_gate"])
            x = x + (g * (hn @ lp["w_up"])) @ lp["w_down"]
        else:
            x = x + dense_mlp(cfg, lp, hn)
        return x, ck, cv

    def forward_cached(self, params, input_ids, cache, start_pos):
        """Forward over [B, S] tokens attending to + updating the KV cache.
        Returns (logits [B, S, V], new_cache). Used for both prefill
        (start_pos=0, S=prompt) and decode (S=1)."""
        cfg = self.cfg
        cfg.refuse_served_only("TransformerLM.forward_cached")
        max_len = cache["k"].shape[3]
        S = input_ids.shape[1]
        x = params["embed"][input_ids].astype(cache["k"].dtype)
        if cfg.embed_scale != 1.0:
            x = x * jnp.asarray(cfg.embed_scale, x.dtype)
        if "embed_ln_w" in params:   # Bloom/BERT-family embeddings LN
            x = layer_norm(x, params["embed_ln_w"],
                           params.get("embed_ln_b"), cfg.norm_eps)
        if cfg.positional == "learned":
            pos = start_pos + jnp.arange(S)
            x = x + params["pos_embed"][pos][None].astype(x.dtype)
        if cfg.positional == "rope":
            cos, sin = _rope_tables(cfg, S, start_pos)
        else:
            cos = sin = jnp.zeros((S, 1), jnp.float32)

        def scan_fn(h, layer_in):
            lp, ck, cv = layer_in
            from ..inference.quantization import dequantize_params
            h, ck, cv = self._layer_cached(h, dequantize_params(lp), ck,
                                           cv, cos, sin, start_pos,
                                           max_len)
            return h, (ck, cv)

        x, (new_k, new_v) = jax.lax.scan(
            scan_fn, x, (params["layers"], cache["k"], cache["v"]))
        x = self._norm(x, params["final_norm"], params.get("final_norm_b"))
        head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
        logits = (x @ head.astype(x.dtype)).astype(jnp.float32)
        if "lm_head_b" in params:
            logits = logits + params["lm_head_b"].astype(jnp.float32)
        return logits, {"k": new_k, "v": new_v}

    def activation_save_sets(self, batch, micro_batch_size: int,
                             itemsize: int):
        """What ``activation_checkpointing.policy: auto`` may keep of a
        layer for the backward, smallest first: (policy name, bytes a
        device over all layers of one micro-batch). ``batch`` gives the
        sequence length, ``micro_batch_size`` the sequences a data-parallel
        rank holds, ``itemsize`` the activations' bytes an element. Empty
        where there is no layer checkpoint to steer (``remat`` off, or the
        pipeline program, which checkpoints by stage).

        One set: the attention output and, from the flash kernel, float32
        row statistics a head. The MLP's pre-activation was measured as a
        second one and is not offered: 1.7 % on one chip at 90 % of its
        memory, a loss of 2.2 % under ZeRO-3 (PERF.md, PR 27)."""
        cfg, topo = self.cfg, self.topology
        axis = topo.axis_size if topo is not None else (lambda a: 1)
        if not cfg.remat or axis("pipe") > 1 or "input_ids" not in batch:
            return []
        seq = batch["input_ids"].shape[-1]
        # heads are split over "model", the sequence over "seq"
        tokens = micro_batch_size * seq // (axis("model") * axis("seq"))
        return [("save_attn", cfg.num_layers * tokens * cfg.num_heads * (
            cfg.head_dim * itemsize + (4 if self._flash_at(seq) else 0)))]

    def flops_per_token(self, seq_len: Optional[int] = None) -> float:
        """6*N_active + attention flops per token (for MFU accounting)."""
        cfg = self.cfg
        n_params = self.active_params()
        f = 6.0 * n_params
        s = seq_len or cfg.max_seq_len
        f += 12.0 * cfg.num_layers * cfg.hidden_size * s  # attention matmuls
        # lm head
        f += 6.0 * cfg.hidden_size * cfg.vocab_size
        return f

    def num_params(self, include_embed: bool = True) -> int:
        cfg = self.cfg
        h, ffn, v, L = (cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size,
                        cfg.num_layers)
        attn = h * cfg.num_heads * cfg.head_dim + 2 * h * cfg.kv_heads * cfg.head_dim \
            + cfg.num_heads * cfg.head_dim * h
        if cfg.moe_num_experts > 0:
            mlp = cfg.moe_num_experts * 3 * h * ffn + h * cfg.moe_num_experts
        else:
            mlp = (3 if cfg.is_gated_mlp else 2) * h * ffn
        per_layer = attn + mlp + 2 * h
        total = L * per_layer + h
        if include_embed:
            total += v * h * (1 if cfg.tie_embeddings else 2)
        return total

    def active_params(self) -> int:
        """Params touched per token (MoE: only top_k experts are active)."""
        cfg = self.cfg
        h, ffn, L = cfg.hidden_size, cfg.intermediate_size, cfg.num_layers
        attn = h * cfg.num_heads * cfg.head_dim + 2 * h * cfg.kv_heads * cfg.head_dim \
            + cfg.num_heads * cfg.head_dim * h
        if cfg.moe_num_experts > 0:
            mlp = cfg.moe_top_k * 3 * h * ffn + h * cfg.moe_num_experts
        else:
            mlp = (3 if cfg.is_gated_mlp else 2) * h * ffn
        return L * (attn + mlp + 2 * h) + h


# -- canonical configs (model zoo) ------------------------------------------

def llama2_7b() -> TransformerConfig:
    return TransformerConfig(vocab_size=32000, hidden_size=4096,
                             intermediate_size=11008, num_layers=32,
                             num_heads=32, max_seq_len=4096)


def llama2_13b() -> TransformerConfig:
    return TransformerConfig(vocab_size=32000, hidden_size=5120,
                             intermediate_size=13824, num_layers=40,
                             num_heads=40, max_seq_len=4096)


def mistral_7b() -> TransformerConfig:
    return TransformerConfig(vocab_size=32000, hidden_size=4096,
                             intermediate_size=14336, num_layers=32,
                             num_heads=32, num_kv_heads=8, max_seq_len=8192)


def mixtral_8x7b() -> TransformerConfig:
    """Mixtral-8x7B: the Mixtral-class sparse-MoE family the reference's
    v2 engine serves (inference/v2/model_implementations/mixtral/): 8
    experts, top-2 routing, Mistral attention geometry, 32k context with
    rope_theta=1e6 (the values the released weights were trained with)."""
    return TransformerConfig(vocab_size=32000, hidden_size=4096,
                             intermediate_size=14336, num_layers=32,
                             num_heads=32, num_kv_heads=8, max_seq_len=32768,
                             rope_theta=1e6,
                             moe_num_experts=8, moe_top_k=2)


def gpt2_small() -> TransformerConfig:
    return TransformerConfig(vocab_size=50257, hidden_size=768,
                             intermediate_size=3072, num_layers=12,
                             num_heads=12, max_seq_len=1024, norm="layernorm",
                             activation="gelu", positional="learned", attn_bias=True,
                             tie_embeddings=True)


def opt_1_3b() -> TransformerConfig:
    """OPT-1.3B (reference inference/v2/model_implementations/opt/): pre-LN
    decoder with learned positions and ReLU MLP."""
    return TransformerConfig(vocab_size=50272, hidden_size=2048,
                             intermediate_size=8192, num_layers=24,
                             num_heads=32, max_seq_len=2048,
                             norm="layernorm", activation="relu",
                             positional="learned", attn_bias=True, tie_embeddings=True)


def opt_125m() -> TransformerConfig:
    return TransformerConfig(vocab_size=50272, hidden_size=768,
                             intermediate_size=3072, num_layers=12,
                             num_heads=12, max_seq_len=2048,
                             norm="layernorm", activation="relu",
                             positional="learned", attn_bias=True, tie_embeddings=True)


def bert_base() -> TransformerConfig:
    """BERT-base MLM encoder, faithful to the original (the family behind
    the reference's BERT-era training kernel
    csrc/transformer/ds_transformer_cuda.cpp and its tests/unit/modeling.py
    fixture): post-LN residuals, embedding LayerNorm, MLM prediction head,
    bidirectional attention."""
    return TransformerConfig(vocab_size=30522, hidden_size=768,
                             intermediate_size=3072, num_layers=12,
                             num_heads=12, max_seq_len=512,
                             norm="layernorm", norm_eps=1e-12,
                             activation="gelu", positional="learned",
                             attn_bias=True, tie_embeddings=True,
                             objective="mlm", norm_scheme="post",
                             embed_ln=True, mlm_head=True)


def tiny_test(vocab=256, hidden=128, layers=2, heads=4, seq=128) -> TransformerConfig:
    return TransformerConfig(vocab_size=vocab, hidden_size=hidden,
                             intermediate_size=hidden * 4, num_layers=layers,
                             num_heads=heads, max_seq_len=seq)
