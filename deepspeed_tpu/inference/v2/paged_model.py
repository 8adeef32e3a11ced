"""Paged (blocked-KV) transformer forward for the ragged engine.

Device-side core of inference v2. Reference counterparts:
  * blocked flash attention over the paged KV cache
    (inference/v2/kernels/ragged_ops/blocked_flash/)
  * fused rotary + KV-block append
    (ragged_ops/blocked_kv_rotary/)
  * ragged embedding + logits gather (ragged_ops/ragged_embed, logits_gather)

The entry points, all pure and jit-compiled by the engine:
  * ``paged_ragged_step``: a MIXED batch as one flat token buffer —
    prompt chunks, continuations and decode rows; each token's K/V
    scattered into its row's cache blocks, attention over the row's block
    table up to the token's own position, returns each row's last-token
    logits. Everything ``put()`` is given runs here.
  * ``paged_decode``: one token for each of N sequences — K/V appended at
    each sequence's next slot, attention over the sequence's block table,
    returns [N, V] logits; ``paged_decode_window`` runs K such steps in
    one device loop with the pick inside (the ``generate()`` hot loop).
  * ``paged_continue``: several tokens of ONE tracked sequence — the
    n-gram speculation's verify pass and the draft model's catch-up;
    ``paged_spec_decode_window`` is the decode window with the draft
    model's propose -> verify -> accept rounds inside.

The KV pool is ``[L, num_blocks, block_size, kv_heads * head_dim]``: a
cached position is ONE lane-dense row, its kv heads side by side (head h
the lanes ``[h * hd, (h + 1) * hd)``). The two minor axes ``(block_size,
kv_heads * head_dim)`` are whole TPU tiles at every head width (``(kv_heads,
head_dim) = (32, 64)`` as minor axes padded 64 lanes to 128, and every launch
paid a slice, a reshape pass and relayout copies for it), a page is
contiguous, and the attention kernels take the leaves WHOLE with the layer
as a scalar and copy a page from where it lies (kernels/ragged_attention.py).
One stored layout for every geometry and dtype; ``_kv_write`` and
``_kv_read`` are the only code that knows it. Block 0
is the null block (padding writes land there). Static shapes throughout:
a step's tokens, rows and table width each pad to the next power-of-two
bucket (the one-sequence passes to multiples of ``prefill_bucket``) — each
bucket compiles once
(the XLA analogue of the reference's CUDA-graph'd atom sizes).

Design note — why there is no dedicated rotary+KV-append kernel (reference
inference/v2/kernels/ragged_ops/blocked_kv_rotary/): that CUDA kernel exists
because torch eager would otherwise launch separate rotary, transpose and
scatter kernels per layer. Here the rotary and the ``.at[block_ids,
offsets].set`` cache write sit INSIDE the jitted, scanned layer body, so XLA
fuses them into the same program as the qkv projections — the "fusion" the
reference hand-writes is the compiler's default. The Pallas budget goes
where fusion cannot: the attention reads (kernels/ragged_attention.py,
kernels/paged_attention.py).
"""

import functools
import math
from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...models.transformer import TransformerConfig, out_proj, qkv_proj

NEG_INF = -1e30


def init_paged_kv_cache(cfg: TransformerConfig, num_blocks: int,
                        block_size: int, dtype, kv_quant: bool = False,
                        state_slots: int = 0, state_dtype=jnp.float32,
                        window_blocks: int = 0
                        ) -> Dict[str, jnp.ndarray]:
    """``kv_quant`` stores the pool int8 with PER-BLOCK (page x kv-head)
    fp32 scales — ~0.5x the bf16 bytes (scale overhead 4/(bs*hd) per
    element instead of the old per-slot 4/hd), so the same HBM holds
    ~2x the tokens. Writes quantize against a running per-block absmax
    (requantizing the block's earlier content when the scale grows);
    reads dequantize. The per-block granularity is what lets the Pallas
    decode/ragged kernels dequantize IN-KERNEL: one scale per (streamed
    page, kv head), so int8 KV serves through the same one-program
    kernel family as bf16 (kernels/ragged_attention.py). Scales init to
    0 = "nothing written".

    ``k``/``v`` are ``[L, nb, bs, kv_heads * head_dim]`` for every
    geometry and dtype (the module docstring says why); the int8 scales
    ``[L, nb, kv_heads]``. ``state_slots`` / ``state_dtype``: the
    recurrent state of a model with linear-attention layers
    (``_init_latent_cache``).

    A leaf a layer KIND under a pattern over per-head attention
    (``cfg.layer_types``): the full layers' ``k_full`` / ``v_full``
    ``[L_full, nb, bs, kvh * hd]``, every position of a sequence as
    above, and the window layers' ``k_window`` / ``v_window``
    ``[L_window, window_blocks, bs, kvh * hd]``, a pool of its own in
    which a sequence owns a RING: position p lies at place ``p % ring``
    of the row's own table (``ragged/ragged_manager.py``), so a layer
    that sees ``attn_window`` positions holds ``window + largest chunk +
    one block`` and not the context. Each in layer order, each with its
    int8 scales (``ks_full`` ...) under ``kv_quant``."""
    assert cfg.is_causal and cfg.norm_scheme in ("pre", "sandwich"), \
        "paged serving requires a causal pre-LN model (the MLM/post-LN " \
        "encoder family does not decode)"
    if cfg.attention == "mla":
        return _init_latent_cache(cfg, num_blocks, block_size, dtype,
                                  kv_quant, state_slots, state_dtype,
                                  window_blocks)

    def leaves(layers, blocks, tag=""):
        shape = (layers, blocks, block_size, cfg.kv_heads * cfg.head_dim)
        if kv_quant:
            sshape = (layers, blocks, cfg.kv_heads)
            return {"k" + tag: jnp.zeros(shape, jnp.int8),
                    "v" + tag: jnp.zeros(shape, jnp.int8),
                    "ks" + tag: jnp.zeros(sshape, jnp.float32),
                    "vs" + tag: jnp.zeros(sshape, jnp.float32)}
        return {"k" + tag: jnp.zeros(shape, dtype),
                "v" + tag: jnp.zeros(shape, dtype)}

    if cfg.layer_types is not None:
        kinds = cfg.layer_kinds
        # a two-mixer layer owns a place in the full pool AND one in
        # the state-space leaves (``cfg.leaf_places``)
        full = cfg.leaf_places("full")
        return {**(leaves(full, num_blocks, "_full") if full else {}),
                **(leaves(kinds.count("window"), window_blocks, "_window")
                   if "window" in kinds else {}),
                **_init_ssm_state(cfg, state_slots, state_dtype),
                **_init_retention_state(cfg, state_slots, state_dtype),
                **_init_conv_state(cfg, state_slots, state_dtype)}
    return leaves(cfg.num_layers, num_blocks)


# the leaves that hold recurrent state, indexed by a sequence's slot
STATE_LEAVES = ("kda_state", "kda_conv", "ssm_state", "ssm_conv",
                "retention_state", "retention_norm", "conv_state")


def _init_conv_state(cfg, state_slots, state_dtype):
    """The short-convolution layers' whole state: ``conv_state``
    ``[L_conv, slots + 1, taps - 1, hidden / 128, 128]`` (a row's last
    gated inputs ``B * u``, oldest first:
    ``linear_attention.conv_leaf_shape``) in ``state_dtype``, slot 0 the
    null slot. The kind has no other leaf: no matrix state and no
    position."""
    n = cfg.leaf_places("conv")
    if not n:
        return {}
    from .kernels.linear_attention import conv_leaf_shape
    return {"conv_state": jnp.zeros(conv_leaf_shape(
        n, state_slots + 1, cfg.conv_taps, cfg.hidden_size), state_dtype)}


def _init_retention_state(cfg, state_slots, state_dtype):
    """The power-retention layers' recurrent state: ``retention_state``
    ``[L_retention, slots + 1, kv_heads, head_dim / 2 + 1, head_dim,
    head_dim]`` (a key/value head's state against the symmetric second
    power of its key, laid out by circular distance:
    ``kernels/power_retention``; 8,320 x 128 float32 a head at the
    published width, of which 8,256 are the mechanism's and 64 are kept
    twice) and ``retention_norm`` ``[L_retention, slots + 1, kv_heads,
    rows, head_dim]`` (the normaliser's, phi's rows rounded up to whole
    sublane tiles: 72 for 65), both in
    ``state_dtype``, slot 0 the null slot. A model of such layers alone
    has no other leaf: nothing of it is paged."""
    n = cfg.layer_kinds.count("retention")
    if not n:
        return {}
    from .kernels.power_retention import leaf_shapes
    state, norm = leaf_shapes(n, state_slots + 1, cfg.kv_heads, cfg.head_dim)
    return {"retention_state": jnp.zeros(state, state_dtype),
            "retention_norm": jnp.zeros(norm, state_dtype)}


def _init_ssm_state(cfg, state_slots, state_dtype):
    """The state-space (Mamba-2) layers' recurrent state (and the
    two-mixer layers', whose attention half has a place in the full pool
    as well), beside a per-head pool: ``ssm_state`` ``[L_ssm, slots + 1,
    channels / 128, d_state, 128]`` (a channel of a head a lane:
    ``kernels/state_space.state_leaf_shape``) and ``ssm_conv``
    ``[L_ssm, slots + 1, taps - 1, (channels + 2 d_state) / 128, 128]``
    (the convolution's last inputs of x, B and C:
    ``linear_attention.conv_leaf_shape``), both in ``state_dtype``,
    slot 0 the null slot."""
    n = cfg.leaf_places("ssm")
    if not n:
        return {}
    from .kernels.linear_attention import conv_leaf_shape
    from .kernels.state_space import state_leaf_shape
    return {"ssm_state": jnp.zeros(state_leaf_shape(
                n, state_slots + 1, cfg.mamba_d_inner, cfg.mamba_d_state),
                state_dtype),
            "ssm_conv": jnp.zeros(conv_leaf_shape(
                n, state_slots + 1, cfg.mamba_d_conv, cfg.mamba_conv_dim),
                state_dtype)}


def latent_pool_row(cfg, kind: str = "mla") -> int:
    """Lanes of one position of a latent kind's pool: its row
    (``cfg.latent_kind``) rounded up to whole 128-lane blocks, the rest
    zeros."""
    return -(-cfg.latent_kind(kind).row // 128) * 128


# a latent kind's leaf: the full layers' by block table, the window
# layers' a ring in a pool of their own; and the scope round its layers
LATENT_LEAVES = {"mla": "latent", "mla_window": "latent_window"}
LATENT_SCOPES = {"mla": "mla_attention",
                 "mla_window": "mla_window_attention"}


def _init_latent_cache(cfg, num_blocks, block_size, dtype, kv_quant,
                       state_slots=0, state_dtype=jnp.float32,
                       window_blocks=0):
    """The pool of an attention='mla' model: ONE leaf, ``latent``
    ``[L, nb, bs, kv_lora_rank + qk_rope_head_dim]``: a cached position
    holds a layer's normed latent and, behind it, the rotated key part
    all heads share (DeepSeek-V3: 512 + 64 values, 1,152 B in bf16,
    where 32 heads of keys and values would be 8,192). The row is stored
    padded with zeros to whole 128-lane blocks (``latent_pool_row``: 640
    lanes for 576): the TPU's tiles pad it so in HBM whatever the shape
    says, and Mosaic copies no slice of a page that is not whole lane
    blocks (it refused the 576-wide one). The leaf's shape
    is a function of the attention kind alone; the layer leads, so that
    the kernel takes the whole pool and a layer index
    (``kernels/ragged_attention.latent_attention``).

    A leaf a layer KIND: under a layer pattern (``cfg.layer_kinds``) the
    pool has the LATENT layers only, in layer order, and the linear
    layers' recurrent state lies beside it, indexed by a sequence's
    state SLOT and not by block: ``kda_state`` ``[L_linear, slots + 1,
    heads, d_k, d_v]`` and ``kda_conv`` ``[L_linear, slots + 1, taps - 1,
    3 x heads x d_k / 128, 128]`` (the convolution's last inputs, an
    input of q, k and v as rows of 128 lanes, so that a slot is whole
    tiles: ``linear_attention.conv_leaf_shape``), both in
    ``state_dtype`` (float32: a state rounded to bfloat16 at every token
    drifts from the recurrence). Slot 0 is the null slot, as block 0 is
    the null block: padded and masked rows read and write it.

    Under a pattern over latent attention (``cfg.layer_types``) a leaf
    a latent KIND: ``latent`` holds the full layers' rows, every
    position of a sequence by its block table; ``latent_window``
    ``[L_window, window_blocks, bs, its own row]`` the window layers', a
    pool of its own in which a sequence owns a RING (position p at
    place ``p % ring`` of the row's own table, as the per-head window
    leaves: ``init_paged_kv_cache``); and, where an indexer picks what a
    full layer reads, ``index_k`` ``[L_full, nb, bs, index_head_dim]``:
    the indexer's ONE key a position, beside the row it may select and
    on the same tables.

    ``kv_quant``: see below."""
    kinds = cfg.layer_kinds
    shape = (kinds.count("mla"), num_blocks, block_size,
             latent_pool_row(cfg))
    state = {}
    if "mla_window" in kinds:
        state["latent_window"] = jnp.zeros(
            (kinds.count("mla_window"), window_blocks, block_size,
             latent_pool_row(cfg, "mla_window")), dtype)
    if cfg.index_topk:
        state["index_k"] = jnp.zeros(
            (*shape[:3], cfg.index_head_dim), dtype)
    assert not (kv_quant and state), \
        "an int8 latent pool has no form beside a ring or an indexer"
    if "kda" in kinds:
        from .kernels.linear_attention import conv_leaf_shape
        n, d = kinds.count("kda"), cfg.linear_head_dim
        state = {"kda_state": jnp.zeros(
                     (n, state_slots + 1, cfg.num_heads, d, d), state_dtype),
                 "kda_conv": jnp.zeros(conv_leaf_shape(
                     n, state_slots + 1, cfg.linear_conv_size,
                     3 * cfg.num_heads * d), state_dtype)}
    if kv_quant:
        # int8 rows, one float32 scale a cached position (its row's
        # absmax / 127): half the pool's bytes. A launch dequantises the
        # layer it attends into a transient copy (``_latent_rows``)
        return {"latent": jnp.zeros(shape, jnp.int8),
                "latent_scale": jnp.zeros(shape[:3], jnp.float32), **state}
    return {"latent": jnp.zeros(shape, dtype), **state}


def _kv_write(kc, ksc, l, blocks, offs, k):
    """Scatter one write-set ``k`` [C, kvh, hd] into the pool, a token
    one ``kvh * hd`` row. Under kv_quant the pool is
    int8 with per-(block, kv-head) scales: the block scale is a running
    absmax over everything written to the block, so a write whose
    magnitude exceeds the current scale first rescales the block's
    existing int8 content to the grown scale (deterministic
    round-to-nearest requant — grow-only, so earlier tokens only ever
    lose up to half an LSB per growth; a (block, head) ratio spread over
    the head's ``hd`` lanes), then quantizes the new tokens.
    Duplicate block indices in one write-set (a prefill chunk spanning a
    block) scatter identical per-block values, so the duplicate-index
    writes stay deterministic; the final per-slot writes are unique."""
    C, _, hd = k.shape
    if ksc is None:
        return kc.at[l, blocks, offs].set(
            k.astype(kc.dtype).reshape(C, -1)), None
    xf = k.astype(jnp.float32)                          # [C, kvh, hd]
    tok_scale = jnp.max(jnp.abs(xf), axis=-1) / 127.0   # [C, kvh]
    old = ksc[l]                                        # [nb, kvh]
    new = old.at[blocks].max(tok_scale)                 # running absmax

    def _requant(c):
        ratio = jnp.where(new > 0, old / jnp.where(new > 0, new, 1.0), 0.0)
        r_tok = jnp.repeat(ratio[blocks], hd, axis=-1)  # [C, kvh * hd]
        pages = c[l, blocks].astype(jnp.float32)        # [C, bs, kvh * hd]
        pages = jnp.round(pages * r_tok[:, None, :])
        return c.at[l, blocks].set(pages.astype(jnp.int8))

    # steady-state decode almost never grows a block's absmax, so the
    # full-page rescale RMW is condition-gated: a ratio-1 requant is the
    # identity on the (integer-valued) int8 content, and never-written
    # blocks keep scale 0 (dequant reads 0 either way) — skipping is
    # bit-identical, and only the slot write below touches the pool
    kc = jax.lax.cond(jnp.any(tok_scale > old[blocks]), _requant,
                      lambda c: c, kc)
    s_tok = jnp.where(new > 0, new, 1.0)[blocks]        # [C, kvh]
    q = jnp.clip(jnp.round(xf / s_tok[..., None]), -127, 127)
    kc = kc.at[l, blocks, offs].set(q.astype(jnp.int8).reshape(C, -1))
    return kc, ksc.at[l].set(new)


def _cache_dict(kc, vc, ksc, vsc):
    out = {"k": kc, "v": vc}
    if ksc is not None:
        out["ks"], out["vs"] = ksc, vsc
    return out


def _kv_read(kc, ksc, l, table, kvh, dtype):
    """Gather layer ``l``'s pages by ``table`` and cut a stored row into
    its heads: [*table.shape, bs, kvh, hd], dequantizing when scales
    exist (per-block scale row broadcast over the page's slot and
    head-dim axes — the same multiply the kernels run per head slice of
    a page, so kernel and gather dequant agree bit-for-bit at fp32).
    The ``jnp:gather`` path (parity reference, tp > 1, alibi): under
    GSPMD the reshape is where the merged lane axis splits by heads."""
    pages = kc[l][table]
    pages = pages.reshape(pages.shape[:-1] + (kvh, -1))
    if ksc is None:
        return pages
    return (pages.astype(jnp.float32)
            * ksc[l][table][..., None, :, None]).astype(dtype)


def _lora_delta(a, b, hn, aid):
    """Per-row LoRA delta ``(hn @ a[aid]) @ b[aid]`` gathered from a
    stacked adapter bank. ``a`` [S, h, r] and ``b`` [S, r, o] hold one
    layer's A/B factors for every hot slot (the adapter scale is folded
    into ``b`` at load time, so this matches the training-side fused
    semantics ``W + scale * (a @ b)`` bit-for-bit under fp32); ``hn``
    [T, h]; ``aid`` int32 [T] per row, or a scalar for single-sequence
    chunks (``paged_continue``), which skips the gather entirely. Slot 0
    is all-zeros — base-model rows add an exact +0.0."""
    aid = jnp.asarray(aid)
    if aid.ndim == 0:
        t = hn @ a[aid].astype(hn.dtype)
        return t @ b[aid].astype(hn.dtype)
    t = jnp.einsum("ti,tir->tr", hn, a[aid].astype(hn.dtype))
    return jnp.einsum("tr,tro->to", t, b[aid].astype(hn.dtype))


def _lora_qv(ll, hn, aid, q, v):
    """Add one layer's per-row LoRA deltas to the FLAT q/v projections
    (classic LoRA targets the q and v projections); ``ll`` is the scan-
    sliced bank layer {"qa","qb","va","vb"} or None (bank disabled)."""
    if ll is None:
        return q, v
    return (q + _lora_delta(ll["qa"], ll["qb"], hn, aid),
            v + _lora_delta(ll["va"], ll["vb"], hn, aid))


def init_lora_bank(cfg: TransformerConfig, slots: int, rank: int,
                   dtype) -> Dict[str, jnp.ndarray]:
    """All-zero stacked adapter bank: ``slots`` INCLUDES the reserved
    base slot 0. Allocated once at engine init so every jitted program's
    signature is stable from boot — hot-deploying an adapter is a same-
    shape ``.at[:, slot].set`` update, never a recompile."""
    h, r = cfg.hidden_size, int(rank)
    L = cfg.num_layers
    nh, nkv, hd = cfg.num_heads, cfg.kv_heads, cfg.head_dim
    return {"qa": jnp.zeros((L, slots, h, r), dtype),
            "qb": jnp.zeros((L, slots, r, nh * hd), dtype),
            "va": jnp.zeros((L, slots, h, r), dtype),
            "vb": jnp.zeros((L, slots, r, nkv * hd), dtype)}


def _norm(cfg, x, w, b=None):
    from ...ops.norms import layer_norm, rms_norm

    if cfg.norm == "rmsnorm":
        return rms_norm(x, w, cfg.norm_eps)
    return layer_norm(x, w, b, cfg.norm_eps)


def _rope_at(cfg: TransformerConfig, pos: jnp.ndarray, kind=None):
    """cos/sin tables at integer positions `pos` [...]-> [..., half]
    (half = rotating dims / 2; partial rotary leaves the tail alone).
    ``kind``: a latent mixer kind with a theta and a rotating width of
    its own (``cfg.latent_kind``)."""
    from ...models.transformer import rotary_dims
    half = rotary_dims(cfg) // 2
    theta = cfg.rope_theta
    if kind is not None:
        lk = cfg.latent_kind(kind)
        half, theta = lk.rope // 2, lk.theta
    freqs = 1.0 / (theta
                   ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    angles = pos.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(angles), jnp.sin(angles)


def _rotate(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray):
    """x [..., D]; cos/sin broadcastable to [..., rot/2] — when rot < D
    (partial rotary) the trailing dims pass through untouched."""
    rot = 2 * cos.shape[-1]
    tail = x[..., rot:]
    xr = x[..., :rot]
    half = rot // 2
    x1, x2 = xr[..., :half], xr[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                          axis=-1)
    if tail.shape[-1]:
        out = jnp.concatenate([out, tail], axis=-1)
    return out.astype(x.dtype)


def _mlp(cfg, lp, x, topo=None):
    if cfg.moe_num_experts > 0:
        return _moe_mlp(cfg, lp, x, topo)
    if cfg.is_gated_mlp:
        from ...models.transformer import gate_act
        return (gate_act(cfg)(x @ lp["w_gate"])
                * (x @ lp["w_up"])) @ lp["w_down"]
    from ...models.transformer import dense_mlp
    return dense_mlp(cfg, lp, x)


def _moe_mlp(cfg, lp, x, topo=None):
    """Routed-expert MLP for serving (reference v2 serves Mixtral-class
    MoE, inference/v2/model_implementations/): dropless sorted-token
    grouped GEMM — no [T,E,C] capacity tensor, no token drops (dropping
    tokens at inference corrupts outputs), ep=1.

    Routing is ``sharded_moe.topk_routing`` on float32 router logits.
    By default it matches the training graph, so that serving is
    parity-testable against the same weights: softmax scores, top-1 its
    raw gate probability (top1gating g1), top-k >= 2 renormalised over
    the chosen set (top2gating's g1/g2; for k > 2 the Mixtral/Qwen-MoE/
    DBRX convention, serving-only). The configuration may instead ask
    for the layer as DeepSeek-V3-class checkpoints deploy it: sigmoid
    scores (``moe_scoring``), a bias that chooses and does not weigh
    (``moe_selection_bias``), the chosen weights scaled
    (``moe_routed_scale``), and always-on shared experts added beside
    the routed ones (``moe_shared_experts``), any k.
    """
    orig_shape = x.shape
    H = orig_shape[-1]
    xt = x.reshape(-1, H)
    if topo is not None and topo.axis_size("expert") > 1:
        # expert-parallel serving: experts live sharded over the "expert"
        # axis, so the ragged grouped GEMM (device-local experts) cannot
        # run — route through the worst-case-capacity dropless dispatch
        # (serving must never drop a token) and let GSPMD insert the
        # expert all-to-all. Same gating math as training's moe_layer, so
        # ep>1 == ep=1 logits (parity-tested). Quadratic-dispatch regime
        # (long prefill chunks) is rejected loudly by the helper.
        from ...moe.sharded_moe import moe_layer_dropless_ep

        def expert_fn(p, xe):
            g_, u_, d_ = p
            return (jax.nn.silu(xe @ g_) * (xe @ u_)) @ d_

        out3, _aux = moe_layer_dropless_ep(
            xt[None], lp["moe_gate_w"],
            (lp["e_gate"], lp["e_up"], lp["e_down"]),
            expert_fn, topo, top_k=cfg.moe_top_k)
        out = out3[0]
    else:
        out, _ = _moe_routed(cfg, lp, xt)
    if cfg.moe_use_residual:
        from ...moe.sharded_moe import residual_moe_combine
        dense = (jax.nn.silu(xt @ lp["res_gate"])
                 * (xt @ lp["res_up"])) @ lp["res_down"]
        out = residual_moe_combine(xt, out, dense, lp["res_coef_w"],
                                   lp["res_coef_b"])
    return out.reshape(orig_shape)


# A share's prompt launch goes through its experts in RUNS of tokens
# (:func:`_moe_experts`), and a run is sized by what the grouped matmul
# needs. Every run streams every held expert's weights anew, and an
# expert's rows make 2 operations a weight value: in bf16 the rows an
# expert ARE the run's operations a weight byte, and the chip's ridge is
# 197 TFLOP/s over 819 GB/s = 240. The kernel does not hide the stream
# behind the products: on the chip a run-dispatch's three ``gmm``
# launches take 1.07 ms + 0.617 us a token at granite's share
# (``scripts/bench_kernels.py --only share-gmm``, PERF.md section 6,
# PR 64), and 1.07 ms is what 241 rows an expert cost: a run of r ridges
# runs at r / (r + 1) of what rows alone would take (half at the 285
# rows bytes alone gave granite; a row tile of 128 is also visited whole
# for every group it spans, which few rows an expert pay most). So a
# run's expected rows a held expert (tokens x k / the router's experts)
# reach ``_SHARE_ROWS``, about four ridges (80 %), inside
# ``_SHARE_RUN_BYTES`` of sorted rows a buffer and never under
# ``_SHARE_TOKENS``. The bytes are the largest power of two at which
# the three share cells' ragged steps fit their chip with 0.5 GB to
# spare by the compiler's memory analysis (the rows, their products and
# the kernels' relaid copy are live together: granite 15.42 GB of 16 at
# 0.67 GB, 15.94 at twice that).
_SHARE_ROWS = 1024
_SHARE_TOKENS = 4096
_SHARE_RUN_BYTES = 4 * 4096 * 8 * 2560 * 2


def _share_tokens(cfg, dtype):
    """Tokens a share's run takes, for rows of ``cfg.hidden_size``
    values of ``dtype`` and ``cfg.moe_top_k`` picks over the router's
    ``cfg.moe_num_experts``: the power of two that gives a held expert
    ``_SHARE_ROWS`` rows, ``_SHARE_TOKENS`` at least, the largest that
    fits ``_SHARE_RUN_BYTES`` at most (granite's 10 picks of 4,096 over
    72 experts: 8,192 tokens, the bytes to the byte; nemotron's and
    ling's launches of 16,384 whole)."""
    k = cfg.moe_top_k
    fit = _SHARE_RUN_BYTES // (k * cfg.hidden_size
                               * jnp.dtype(dtype).itemsize)
    want = -(-_SHARE_ROWS * cfg.moe_num_experts // k)
    return min(1 << max(fit.bit_length() - 1, 0),
               max(_SHARE_TOKENS, 1 << (want - 1).bit_length()))


def _held_from(cfg):
    """The first expert this tree holds where it holds a share of the
    router's, else None (it holds them all)."""
    return cfg.moe_experts_first \
        if cfg.experts_held < cfg.moe_num_experts else None


def moe_share_runs(cfg, tokens: int, dtype):
    """How an expert layer's launch of ``tokens`` flat tokens goes
    through its experts: (the runs, the tokens of ONE dispatch). A tree
    that holds a share sends a launch over a run
    (:func:`_share_tokens`) through in runs; every other launch is one
    dispatch of its own tokens, (0, ``tokens``). What ``_moe_experts``
    traces and what the engine counts (``moe_share_runs_total``)."""
    run = tokens if _held_from(cfg) is None else _share_tokens(cfg, dtype)
    return (-(-tokens // run), run) if tokens > run else (0, tokens)


def moe_rows_form(cfg, tokens: int, dtype) -> str:
    """How an expert layer's launch of ``tokens`` flat tokens brings its
    routed rows back from expert order: ``"kernel"``
    (``kernels/expert_combine.rows_combine``) or ``"gather"`` (XLA's
    lines in ``dropless_topk_dispatch``), from the shape of ONE dispatch
    (:func:`moe_share_runs`), the experts' type and whether the tree
    holds a share of them. What ``_moe_experts`` traces and what the
    engine counts (``moe_rows_combined_total``)."""
    from .kernels.expert_combine import rows_combine_serves

    _, tokens = moe_share_runs(cfg, tokens, dtype)
    return "kernel" if rows_combine_serves(
        tokens * cfg.moe_top_k, cfg.hidden_size, dtype,
        _held_from(cfg) is not None) else "gather"


def _moe_route(cfg, lp, xt, router_precision=None):
    """The ROUTING half of the ep = 1 expert layer on flat tokens ``xt``
    [T, H], what the router reads: (the chosen experts [T, k], their
    weights [T, k]). ``xt`` is the experts' own normed input, or, under
    ``cfg.moe_router_ahead``, the MIXER's (``_pattern_step`` norms the
    stream once and hands it to the router and the mixer both).
    ``xt`` may be float32 beside bf16 experts: the router reads it as it
    is. ``router_precision`` is the router matmul's: the latent block's
    published router is float32 (``s = sigmoid(x Wr)`` in float32) and
    on a TPU a float32 matmul runs in bf16 passes unless asked
    otherwise, so ``_pattern_step`` asks for ``HIGHEST``; the per-head
    path's softmax router keeps the backend's default (None), which is
    what its programs compiled to before."""
    from ...moe.sharded_moe import topk_routing

    with jax.named_scope("moe_router"):
        gate_w = lp["moe_gate_w"]
        # on a TPU a float32 matmul runs in bf16 passes unless asked
        # otherwise; rounded scores flip which experts are chosen
        logits = jnp.matmul(xt.astype(jnp.float32),
                            gate_w.astype(jnp.float32),
                            precision=router_precision)
        limit = dict(n_group=cfg.moe_n_group,
                     topk_group=cfg.moe_topk_group) \
            if cfg.moe_n_group > 1 else {}
        return topk_routing(
            logits, cfg.moe_top_k, cfg.moe_scoring,
            lp["moe_gate_bias"] if cfg.moe_selection_bias else None,
            cfg.moe_norm_topk, cfg.moe_routed_scale,
            norm_eps=cfg.moe_norm_topk_eps, **limit)


def _moe_experts(cfg, lp, xt, topi, topv, experts=None, stack_layer=None):
    """The EXPERTS half: what the routed and the shared experts add for
    flat tokens ``xt`` [T, H] routed as ``topi`` / ``topv``
    (:func:`_moe_route`, of this tensor or of another), in the experts'
    type (they read ``xt``'s rounding). ``experts`` with
    ``stack_layer``: the scanned stack's expert weights whole and this
    layer's index in it (``sharded_moe.dropless_topk_dispatch``); else
    ``lp``'s own."""
    from ...moe.sharded_moe import (dropless_topk_dispatch, expert_forms,
                                    gmm_serves)
    from .kernels.expert_combine import rows_combine

    xt = xt.astype(lp["moe_gate_w"].dtype)
    with jax.named_scope("moe_experts"):
        if experts is None:
            experts = tuple(lp[k] for k in cfg.expert_keys)
        ragged, gmm, shared_expert = expert_forms(cfg.moe_expert_form)
        # the router scores every expert; this tree may hold a share of
        # them (cfg.moe_experts_held from cfg.moe_experts_first), and a
        # pick that is held elsewhere adds nothing here
        # the rows come back through the kernels where the launch is
        # one they serve (a share's prompt launch), else by XLA's gather
        kernel = moe_rows_form(cfg, xt.shape[0], xt.dtype) == "kernel"

        def dispatch(xt, topi, topv):
            return dropless_topk_dispatch(
                xt, topi, topv, experts, cfg.experts_held,
                gmm if gmm_serves(experts) else ragged,
                stack_layer=stack_layer, held_from=_held_from(cfg),
                rows_combine=rows_combine if kernel else None)

        T = xt.shape[0]
        runs, run = moe_share_runs(cfg, T, xt.dtype)
        if runs:
            # a share's launch sorts and gathers EVERY pick's row and
            # computes the held ones (all of a token's picks may be
            # held, so no smaller buffer is safe): a launch of any
            # length over a run goes through in runs of tokens, so that
            # the sorted rows of one run, not of the launch, are what is
            # live (16,384 tokens x 8 picks x 2560 are 0.67 GB a
            # buffer). The last run is padded with rows of zeros, which
            # add nothing and are cut off
            pad = runs * run - T
            out = jax.lax.map(lambda a: dispatch(*a), tuple(
                jnp.pad(a, ((0, pad), (0, 0))).reshape(
                    -1, run, a.shape[-1])
                for a in (xt, topi, topv))).reshape(T + pad, -1)[:T]
        else:
            out = dispatch(xt, topi, topv)
    if cfg.moe_shared_experts:
        with jax.named_scope("moe_shared_expert"):
            # the shared expert's leaves are the routed ones' by name:
            # e_up -> shared_up
            out = out + shared_expert(xt, *(
                lp[k.replace("e_", "shared_", 1)] for k in cfg.expert_keys))
    return out


def _moe_routed(cfg, lp, xt, experts=None, stack_layer=None,
                router_precision=None):
    """The expert layer whose router reads the experts' own input: the
    two halves on one tensor, (what the experts add, the chosen experts
    [T, k])."""
    topi, topv = _moe_route(cfg, lp, xt, router_precision)
    return _moe_experts(cfg, lp, xt, topi, topv, experts,
                        stack_layer), topi


def _deq_nonlayer(params):
    """Dequantize every WOQ leaf OUTSIDE params["layers"] (embed/lm_head;
    one-shot temps XLA frees after use). Layer leaves stay quantized: the
    lax.scan slices them and the body dequantizes ONE layer at a time —
    dequantizing the stack up front materializes every layer's bf16
    weights as scan inputs (the r05 AOT serving fit measured 13 GiB of
    them on a 7B model, making int8 serving WORSE than bf16 at peak)."""
    from ..quantization import dequantize_params
    return {k: (v if k == "layers" else dequantize_params(v))
            for k, v in params.items()}


def _deq_layer(lp):
    """Dequantize one scan-sliced layer's WOQ leaves (identity on dense
    params); runs inside the scan body where XLA fuses the dequant into
    the consuming matmul."""
    from ..quantization import dequantize_params
    return dequantize_params(lp)


def _embed_ln(cfg, params, x):
    """Bloom/BERT-family embeddings LayerNorm (keyed on param presence)."""
    if "embed_ln_w" in params:
        from ...ops.norms import layer_norm
        return layer_norm(x, params["embed_ln_w"],
                          params.get("embed_ln_b"), cfg.norm_eps)
    return x


def _alibi_row(cfg, positions):
    """[nh, 1, len(positions)] softmax-invariant ALiBi bias row."""
    from ...models.transformer import alibi_slopes
    return (alibi_slopes(cfg.num_heads)[:, None, None]
            * positions.astype(jnp.float32)[None, None, :])


def _logits(cfg, params, x):
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    out = (x @ head.astype(x.dtype)).astype(jnp.float32)
    if "lm_head_b" in params:
        out = out + params["lm_head_b"].astype(jnp.float32)
    if cfg.logit_scale != 1.0:
        out = out / cfg.logit_scale
    return out


# ---------------------------------------------------------------------------
# Scopes: what a device trace can say about a serving program
# ---------------------------------------------------------------------------
# A device trace names an operation by its HLO instruction (``fusion.147``)
# and the compiled program keeps, per instruction, the ``jax.named_scope``
# path it was traced under. The helpers below are the one place the
# serving programs open theirs, in the training model's words where they
# exist (models/transformer.py): ``embed``, ``layers`` > ``attention`` >
# {``qkv_proj``, ``kv_write``, ``attn_kernel``, ``out_proj``}, ``mlp``,
# ``head`` (final norm and logits) and ``pick`` (sampling.py: the argmax
# or the sampler). ``telemetry.memory.scopes(program)`` hands out the map
# and ``utils.xla_profile.serve_phase`` reads a path as a phase. A scope
# is metadata: it adds no instruction and moves no fusion (pinned by
# tests/unit/inference/test_serving_scopes.py).
def _embed(cfg, params, ids, pos):
    """Token embeddings of ``ids`` at cache positions ``pos`` (the
    learned table's rows, clipped: a bucket may round past
    ``max_seq_len``), under scope ``embed``."""
    with jax.named_scope("embed"):
        x = params["embed"][ids]
        if cfg.embed_scale != 1.0:
            x = x * jnp.asarray(cfg.embed_scale, x.dtype)
        x = _embed_ln(cfg, params, x)
        if cfg.positional == "learned":
            x = x + params["pos_embed"][
                jnp.clip(pos, 0, cfg.max_seq_len - 1)]
    return x


def _qkv_heads(cfg, lp, hn, lead, cos, sin, lora_qv=None):
    """q, k, v of the normed input as heads ``[*lead, heads, hd]``,
    rotated where the model rotates, under scope ``qkv_proj``.
    ``lora_qv(q, v) -> (q, v)`` adds a row's adapter to the flat
    projections."""
    nh, nkv, hd = cfg.num_heads, cfg.kv_heads, cfg.head_dim
    with jax.named_scope("qkv_proj"):
        q, k, v = qkv_proj(lp, hn)
        if lora_qv is not None:
            q, v = lora_qv(q, v)
        q = q.reshape(*lead, nh, hd)
        k = k.reshape(*lead, nkv, hd)
        v = v.reshape(*lead, nkv, hd)
        if cfg.positional == "rope":
            q = _rotate(q, cos[..., None, :], sin[..., None, :])
            k = _rotate(k, cos[..., None, :], sin[..., None, :])
    return q, k, v


def _kv_write_pair(kc, vc, ksc, vsc, l, blocks, offs, k, v):
    """The write-set's keys and values into layer ``l`` of the pool,
    under scope ``kv_write``."""
    with jax.named_scope("kv_write"):
        kc, ksc = _kv_write(kc, ksc, l, blocks, offs, k)
        vc, vsc = _kv_write(vc, vsc, l, blocks, offs, v)
    return kc, vc, ksc, vsc


def _scan_layers(cfg, params, x, cache, lora, topo, attend):
    """The per-head block's layer stack, scanned, for every program that
    runs it (continue, decode, the ragged step, the speculative
    verify). What differs between them is ``attend(lp, ll, l, hn, kc,
    vc, ksc, vsc) -> (o, kc, vc, ksc, vsc)``: the program's projections
    by its own shapes (``_qkv_heads``), its write-set
    (``_kv_write_pair``) and its attention over the pool (scope
    ``attn_kernel``: the Pallas call or the gather fallback). The norms,
    the output projection, the residual adds and the MLP are the same
    everywhere, and so are the scopes: ``layers`` > ``attention`` (the
    norm, ``attend``, ``out_proj``, the add) and ``mlp``. Returns (x,
    cache)."""

    def layer_fn(carry, inputs):
        x_in, kc, vc, ksc, vsc = carry
        lp, l = inputs[0], inputs[1]
        ll = inputs[2] if lora is not None else None
        lp = _deq_layer(lp)
        with jax.named_scope("attention"):
            hn = _norm(cfg, x_in, lp["attn_norm"], lp.get("attn_norm_b"))
            o, kc, vc, ksc, vsc = attend(lp, ll, l, hn, kc, vc, ksc, vsc)
            with jax.named_scope("out_proj"):
                a = out_proj(lp, o)
            x = x_in + a
        with jax.named_scope("mlp"):
            if cfg.parallel_residual:
                # Falcon block: attention and MLP both read the normed
                # input of the layer and add to one stream (NeoX
                # parallel_norms norms separately)
                hn = (_norm(cfg, x_in, lp["mlp_norm"],
                            lp.get("mlp_norm_b"))
                      if cfg.parallel_norms else hn)
            else:
                hn = _norm(cfg, x, lp["mlp_norm"], lp.get("mlp_norm_b"))
            x = x + _mlp(cfg, lp, hn, topo)
        return (x, kc, vc, ksc, vsc), None

    with jax.named_scope("layers"):
        (x, kc, vc, ksc, vsc), _ = jax.lax.scan(
            layer_fn, (x, cache["k"], cache["v"],
                       cache.get("ks"), cache.get("vs")),
            (params["layers"], jnp.arange(cfg.num_layers))
            + ((lora,) if lora is not None else ()))
    return x, _cache_dict(kc, vc, ksc, vsc)


def _head(cfg, params, x, rows=None):
    """The final norm over every position, ``rows(x)`` of the result
    where only some feed the head, and their logits, under scope
    ``head``."""
    with jax.named_scope("head"):
        x = _norm(cfg, x, params["final_norm"], params.get("final_norm_b"))
        return _logits(cfg, params, x if rows is None else rows(x))


# ---------------------------------------------------------------------------
# The latent-attention block (attention='mla'): one step for every program
# ---------------------------------------------------------------------------
def _rotate_pairs(x, cos, sin, interleave):
    """Rope on x [..., D] (D even, all of it rotates), cos/sin
    broadcastable to [..., D/2]. ``interleave``: the pairs are lanes
    (2i, 2i+1), the published DeepSeek layout; they are read by a
    strided slice and the result is written half-split, evens' results
    then odds'. Queries and the shared key part go through this one
    function, so a score (a dot product over the pairs) is what the
    pair-wise rotation in place gives; nothing else reads the rotated
    lanes, so the permutation lives here, at trace time, and in no
    weight. Otherwise the pairs are (i, i + D/2): ``_rotate``."""
    if not interleave:
        return _rotate(x, cos, sin)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1).astype(x.dtype)


def _latent_write(pool, l, blocks, offs, row, leaf="latent"):
    """The new tokens' rows [T, row] into layer ``l`` of the pool's
    ``leaf`` (``{"latent"[, "latent_scale"]}``; a pattern's
    ``latent_window`` and ``index_k``), padded with zeros to the leaf's
    lanes; into an int8 pool against each row's own absmax."""
    lat = pool[leaf]
    row = jnp.pad(row, ((0, 0), (0, lat.shape[-1] - row.shape[-1])))
    if "latent_scale" not in pool:
        return {**pool,
                leaf: lat.at[l, blocks, offs].set(row.astype(lat.dtype))}
    rf = row.astype(jnp.float32)
    scale = jnp.max(jnp.abs(rf), axis=-1) / 127.0
    q = jnp.round(rf / jnp.where(scale > 0, scale, 1.0)[:, None])
    return {**pool,
            "latent": lat.at[l, blocks, offs].set(q.astype(jnp.int8)),
            "latent_scale": pool["latent_scale"].at[l, blocks, offs]
            .set(scale)}


def _latent_rows(pool, l, dtype, leaf="latent"):
    """(pool, layer) for the attention of layer ``l``: the pool whole
    and ``l``, or, of an int8 pool, that ONE layer dequantised through
    the serving dtype as a pool of one layer (a transient 1 / L of the
    pool at twice its bytes; an in-kernel dequant would save the pass:
    ROADMAP M3)."""
    if "latent_scale" not in pool:
        return pool[leaf], l
    rows = (pool["latent"][l].astype(jnp.float32)
            * pool["latent_scale"][l][..., None]).astype(dtype)
    return rows[None], jnp.int32(0)


# tokens a tile of the indexed read, and cached positions a chunk of a
# tile's indexer scores. A tile scores the chunks its largest bound
# reaches and no further (a chunk step's table is as wide as a row can
# grow, 33,024 positions, whatever the context). A chunk is ``[tokens,
# heads, chunk]`` products that XLA fuses with ``relu``, the weights and
# the sum over heads: 256 x 64 x 4,096 x 128 x 2 = 1.7e10 operations, 94 us
# on a v5e, 92 % of the matrix unit's peak (PERF.md section 5, PR 71); the
# per-head products never lie in HBM. What a tile keeps is the chunk's
# ``[tokens, chunk]`` float32 (4 MB) laid into its scores ``[tokens,
# positions]`` (34 MB) and its flags, as many bf16; the score matrix of a
# launch is never whole. 4,096: the fusion is at its rate there, and a
# finer chunk would save at most the last chunk's half a tile (a few
# percent of a long row's products) for more trips, gathers and writes
_INDEX_TILE = 256
_INDEX_CHUNK = 4096


def _token_scores(qi, wi, keys, l, row_ids, lengths, block_tables,
                  one_token):
    """``(scores [t, ctx] float32, seen [t, ctx])``: the indexer's score
    of each of a tile's query tokens against the positions its row's
    table holds, ``I[t, s] = sum_j w[t, j] * relu(q[t, j] . k[s])``, and
    which of them lie under the token's causal bound ``lengths``. qi
    ``[t, heads, d]``, wi ``[t, heads]`` float32 (the head's weight with
    both scales in it), keys the ``index_k`` leaf whole, ``l`` the
    layer's place in it. Scored are the chunks of ``_INDEX_CHUNK``
    positions under the tile's REACH (its largest bound, a traced value:
    a loop of as many trips), each chunk's keys gathered through its
    pages of the table; what lies past them is zeros that no token sees.
    A table of no whole number of chunks lays its last chunk against its
    end (positions scored twice read the same). A tile's tokens may
    belong to several rows (a ragged launch): each row's keys are scored
    against the tile, and a token keeps its own row's scores;
    ``one_token``: every token is its own row, scored in one batched
    product a chunk."""
    R, MB = block_tables.shape
    bs = keys.shape[2]
    ctx = MB * bs
    chunk = min(_INDEX_CHUNK, ctx)
    pages = chunk // bs
    seen = jnp.arange(ctx)[None, :] < lengths[:, None]
    live = lengths > 0
    lo = jnp.min(jnp.where(live, row_ids, R))
    hi = jnp.max(jnp.where(live, row_ids, -1))

    def scores(product, k):
        s = jnp.einsum(product, qi, k, preferred_element_type=jnp.float32)
        return jnp.einsum("tjc,tj->tc", jax.nn.relu(s), wi)

    def one(c, acc):
        first = jnp.minimum(c * pages, MB - pages)
        if one_token:
            at = jax.lax.dynamic_slice_in_dim(block_tables[row_ids], first,
                                              pages, axis=1)
            s = scores("tjd,tcd->tjc",
                       keys[l, at].reshape(len(row_ids), chunk, -1))
        else:
            def row(r, s):
                at = jax.lax.dynamic_slice(block_tables, (r, first),
                                           (1, pages))[0]
                return jnp.where(
                    (row_ids == r)[:, None],
                    scores("tjd,cd->tjc", keys[l, at].reshape(chunk, -1)), s)
            s = jax.lax.fori_loop(
                lo, hi + 1, row, jnp.zeros((qi.shape[0], chunk), jnp.float32))
        return jax.lax.dynamic_update_slice_in_dim(acc, s, first * bs, axis=1)

    with jax.named_scope("indexer"):
        return jax.lax.fori_loop(
            0, index_chunks(jnp.max(lengths), ctx), one,
            jnp.zeros((qi.shape[0], ctx), jnp.float32)), seen


def index_chunks(reach, ctx: int):
    """Chunks of the indexer's scores under a tile's ``reach`` (its
    largest bound; a traced or a host's whole number) over tables of
    ``ctx`` positions: whole chunks of ``min(_INDEX_CHUNK, ctx)``."""
    return -(-reach // min(_INDEX_CHUNK, ctx))


def index_positions_swept(form, row_ids, bounds, tokens: int,
                          table_rows: int, ctx: int) -> int:
    """Cached positions the indexer's PRODUCTS cover for the query tokens
    of one launch (a ragged step, or one step of a decode window), a
    full layer: a token's count is the whole chunks under its TILE's
    reach (:func:`_token_scores`), times the rows the tile's tokens
    belong to where a tile is scored against each (the absorbed form).
    Over the bounds' sum (the least the equations ask: a query scores
    what lies under its bound) it is the over-scoring. ``form``:
    :func:`index_prompt_form`'s answer for the launch's static shapes
    (``tokens`` its token bucket, ``table_rows`` and ``ctx`` its tables'
    rows and positions), or "decode"; ``row_ids`` / ``bounds`` ``[n]``
    the launch's tokens in pack order (numpy; a row's tokens together,
    rows ascending). "expanded" packs a row's tokens in tiles of their
    own (:func:`_row_tiles`), "absorbed" tiles the launch as it lies, a
    decode step its table rows. Host arithmetic, no device read: what the
    engine's ``inference_index_positions_swept_total`` is told."""
    from .kernels.ragged_attention import picked_heads_tile
    row_ids, bounds = np.asarray(row_ids), np.asarray(bounds, np.int64)
    chunk = min(_INDEX_CHUNK, ctx)

    place = np.arange(len(bounds))
    if form == "expanded":
        tt = min(_INDEX_TILE, picked_heads_tile(tokens, table_rows))
        tile_of = row_ids * len(place) + (
            place - np.searchsorted(row_ids, row_ids)) // tt
    else:
        tile_of = (row_ids if form == "decode" else place) // min(
            _INDEX_TILE, tokens)
    total = 0
    for t in np.unique(tile_of):
        at = np.flatnonzero(tile_of == t)
        rows = int(np.ptp(row_ids[at])) + 1 if form == "absorbed" else 1
        total += len(at) * rows * chunk * int(
            index_chunks(bounds[at].max(), ctx))
    return total


def index_select(qi, wi, keys, l, row_ids, lengths, block_tables, topk,
                 one_token=False):
    """What the indexer picks for each of a tile's query tokens, as
    positions: ``(idx [t, topk] int32, ok [t, topk] bool)``, the
    ``topk`` positions of the token's row with the largest scores
    (:func:`_token_scores`) among those under its causal bound (ties to
    the lower position, ``jax.lax.top_k``'s order), and which of them
    are positions at all: every one under the bound while the bound is
    no larger than ``topk``, when the rest of ``idx`` is filler. The
    form a decode step takes: its few tokens GATHER what they picked
    (:func:`_selected_latent_attention`)."""
    scores, seen = _token_scores(qi, wi, keys, l, row_ids, lengths,
                                 block_tables, one_token)
    with jax.named_scope("index_select"):
        _, idx = jax.lax.top_k(jnp.where(seen, scores, -jnp.inf), topk)
        return idx, idx < lengths[:, None]


def index_mask(qi, wi, keys, l, row_ids, lengths, block_tables, topk):
    """:func:`index_select` as FLAGS, ``[t, ctx]`` bool: the same set a
    token (the ``topk`` largest scores under its bound, ties to the
    lower position; every position under the bound while it is no
    larger than ``topk``), found without sorting: the ``topk``-th
    largest score a token by 32 counting passes over the scores' bits
    (an order-keeping map of float32 to uint32), then every position
    over it and, of the positions AT it, the first ones in position
    order that fill the set. The form a prompt's launch takes: its many
    tokens read their row's positions once and mask
    (``picked_heads_attention`` over per-head keys and values, or
    ``latent_attention(picked=)`` over the latent rows)."""
    scores, seen = _token_scores(qi, wi, keys, l, row_ids, lengths,
                                 block_tables, False)
    with jax.named_scope("index_select"):
        u = jax.lax.bitcast_convert_type(scores, jnp.uint32)
        u = jnp.where(u >> 31 == 1, ~u, u | jnp.uint32(1 << 31))
        u = jnp.where(seen, u, jnp.uint32(0))   # under every seen score

        def bit(i, kth):
            cand = kth | (jnp.uint32(1 << 31) >> i.astype(jnp.uint32))
            enough = jnp.sum(u >= cand[:, None], axis=-1,
                             dtype=jnp.int32) >= topk
            return jnp.where(enough, cand, kth)
        kth = jax.lax.fori_loop(
            0, 32, bit, jnp.zeros((u.shape[0],), jnp.uint32))[:, None]
        over = u > kth
        at = (u == kth) & seen
        room = topk - jnp.sum(over, axis=-1, dtype=jnp.int32)
        return over | (at & (_running_count(at) <= room[:, None]))


def _running_count(flags):
    """``cumsum(flags, axis=-1)`` of bool ``[t, c]`` as int32, made of
    two products with a triangle of ones (128 positions a block, then
    the blocks): exact (0 / 1 in bf16, sums in float32, under 2 ** 24),
    a few percent of ``jnp.cumsum``'s time on the chip, and traced under
    the caller's scope (``cumsum``'s own lowering drops it, and its time
    fell under no scope of a trace: PERF.md section 6, PR 68)."""
    t, c = flags.shape
    n = -(-c // 128)
    x = jnp.pad(flags, ((0, 0), (0, n * 128 - c))).astype(jnp.bfloat16)
    x = x.reshape(t, n, 128)

    def upto(k):        # [k, k]: 1 where row <= column
        return (jnp.arange(k)[:, None] <= jnp.arange(k)[None, :])

    inside = jnp.einsum("tbk,kj->tbj", x, upto(128).astype(jnp.bfloat16),
                        preferred_element_type=jnp.float32)
    blocks = inside[..., -1]                        # a block's own count
    ahead = jnp.einsum("tb,bj->tj", blocks,
                       (jnp.arange(n)[:, None] < jnp.arange(n)[None, :]
                        ).astype(jnp.float32),
                       precision=jax.lax.Precision.HIGHEST)
    return (inside + ahead[..., None]).astype(jnp.int32).reshape(
        t, n * 128)[:, :c]


def _selected_latent_attention(qx, lat, l, idx, ok, row_ids, block_tables,
                               *, dc, scale):
    """The absorbed latent attention of a tile's tokens over the
    positions each one SELECTED: qx ``[nh, t, W]``, ``idx`` / ``ok``
    ``[t, k]`` (:func:`index_select`), ``lat`` the latent leaf whole. A
    token's ``k`` rows are gathered through its row's block table (a row
    is one gather, whatever page it lies on) and every head attends
    them, their first ``dc`` lanes the values; the softmax in float32
    over the positions that are ``ok``. Returns ``[nh, t, dc]``."""
    bs = lat.shape[2]
    rows = lat[l, block_tables[row_ids[:, None], idx // bs], idx % bs]
    s = jnp.einsum("htw,tcw->htc", qx, rows,
                   preferred_element_type=jnp.float32) * scale
    p = jax.nn.softmax(jnp.where(ok[None], s, NEG_INF), axis=-1)
    p = jnp.where(ok[None, :, :1], p, 0.0)              # padding: zeros
    return jnp.einsum("htc,tcd->htd", p.astype(qx.dtype), rows[..., :dc])


def _indexed_latent_attention(qx, qi, wi, pool, l, row_ids, lengths,
                              block_tables, *, dc, scale, topk, one_token,
                              use_kernel):
    """A full latent layer's read in the ABSORBED form where the tables
    hold more positions than ``topk``, a tile of ``_INDEX_TILE`` tokens
    at a time: the indexer's scores and selection, then the attention
    over what was selected and nothing else. A DECODE step's tokens
    (``one_token``) gather the rows they picked (:func:`index_select`,
    :func:`_selected_latent_attention`): ``topk`` rows a token, whatever
    the context. A PROMPT's launch of few tokens a row (a ragged step's
    tail; :func:`index_prompt_form` says "absorbed") reads its rows'
    pages once through the latent kernel with each token's picks laid
    on the causal mask (:func:`index_mask`,
    ``latent_attention(picked=)``): the same sets and the same sums,
    and cheaper on the chip than gathering wherever it was read (a
    gathered row costs ~15 ns whatever its bytes, 2,048 of them a token
    and layer: PERF.md section 6, PR 68). A launch of many tokens a row
    does not come here (:func:`_expanded_index_attention`). qx ``[nh,
    T, W]`` -> ``[nh, T, dc]``."""
    from .kernels.ragged_attention import (latent_attention,
                                           latent_attention_reference)
    nh, T0, W = qx.shape
    tt = min(_INDEX_TILE, T0)
    T = -(-T0 // tt) * tt
    if T != T0:
        qx = jnp.pad(qx, ((0, 0), (0, T - T0), (0, 0)))
        qi = jnp.pad(qi, ((0, T - T0), (0, 0), (0, 0)))
        wi, row_ids, lengths = (jnp.pad(a, ((0, T - T0),) + ((0, 0),) * (
            a.ndim - 1)) for a in (wi, row_ids, lengths))
    attend = latent_attention if use_kernel else latent_attention_reference

    def tile(args):
        qx, qi, wi, rows, bounds = args
        if one_token:
            idx, ok = index_select(qi, wi, pool["index_k"], l, rows, bounds,
                                   block_tables, topk, one_token)
            with jax.named_scope("attn_kernel"):
                return _selected_latent_attention(
                    qx, pool["latent"], l, idx, ok, rows, block_tables,
                    dc=dc, scale=scale)
        picked = index_mask(qi, wi, pool["index_k"], l, rows, bounds,
                            block_tables, topk)
        with jax.named_scope("attn_kernel"):
            return attend(qx, pool["latent"], l, rows, bounds, block_tables,
                          dc=dc, scale=scale, picked=picked)

    n = T // tt
    if n == 1:          # (a decode step's few tokens: no loop round one)
        return tile((qx, qi, wi, row_ids, lengths))[:, :T0]
    out = jax.lax.map(tile, (
        qx.reshape(nh, n, tt, W).transpose(1, 0, 2, 3),
        qi.reshape(n, tt, *qi.shape[1:]), wi.reshape(n, tt, -1),
        row_ids.reshape(n, tt), lengths.reshape(n, tt)))
    return out.transpose(1, 0, 2, 3).reshape(nh, T, dc)[:, :T0]


# a prompt launch of a full latent layer that selects takes the EXPANDED
# form where the launch has at least this many tokens a table row: making
# a row's keys and values costs rows x positions x rank x heads x (nope +
# v) products, and saves tokens x positions x heads x (the absorbed
# form's products a position less the expanded form's); at dots3's widths
# they meet at ~170 tokens a row (PERF.md section 6, PR 69)
_EXPAND_TOKENS_A_ROW = 256
# bytes of keys and values made at once: a group of heads (a power of
# two, four at least) whose rows x the TABLE's positions x heads x (nope
# + v) stay inside (8 heads at the cell's 4 rows x 34,816: 0.57 GB; 4 at
# 8 rows)
_EXPAND_BYTES = 0.6e9
# positions a piece of a row's keys and values: a piece past what the
# row's tokens reach is not made
_EXPAND_PIECE = 2048


def index_prompt_form(tokens: int, rows: int, positions: int, topk: int):
    """How a PROMPT's launch of a full latent layer with an indexer
    reads, by the launch's static shapes (its token bucket, its tables'
    rows and the positions they hold): None where the tables hold no
    more than ``topk`` positions (nothing selects: the dense launch),
    "expanded" where the launch brings ``_EXPAND_TOKENS_A_ROW`` tokens a
    row or more (:func:`_expanded_index_attention`), else "absorbed"
    (the masked latent kernel). What the engine's counter
    ``inference_index_prompt_launches_total`` is told, too."""
    if not topk or positions <= topk:
        return None
    return "expanded" if tokens >= _EXPAND_TOKENS_A_ROW * rows \
        else "absorbed"


def _row_tiles(row_ids, lengths, R, tq):
    """A launch's tokens packed in tiles of ``tq`` that each hold ONE
    row's tokens (a row's tokens are contiguous in pack order; ``lengths``
    0: no token): ``T / tq + R`` tiles (static: a row's last tile may be
    part full), ``src`` ``[tiles * tq]`` the flat token a packed place
    holds (``T``: none), ``slot`` ``[T]`` a token's packed place (past
    the end: no token) and ``tile_rows`` ``[tiles]`` each tile's row."""
    T = row_ids.shape[0]
    n = -(-T // tq) + R
    live = lengths > 0
    tok = jnp.arange(T, dtype=jnp.int32)
    mine = (row_ids[None, :] == jnp.arange(R)[:, None]) & live[None, :]
    first = jnp.min(jnp.where(mine, tok, T), axis=1)
    tiles = -(-jnp.sum(mine, axis=1, dtype=jnp.int32) // tq)
    base = jnp.cumsum(tiles) - tiles
    j = tok - first[row_ids]
    slot = jnp.where(live, (base[row_ids] + j // tq) * tq + j % tq, n * tq)
    src = jnp.full((n * tq,), T, jnp.int32).at[slot].set(tok, mode="drop")
    tile_rows = jnp.zeros((n,), jnp.int32).at[slot // tq].max(
        row_ids.astype(jnp.int32), mode="drop")
    return src, slot, tile_rows


def _expanded_picked_attention(q, lat, wkv_b, picked_t, tile_rows, bounds,
                               reach, *, dc, dn, scale, tq, use_kernel):
    """Packed tokens (:func:`_row_tiles`) attend their rows' positions
    in the EXPANDED form, a group of heads at a time (``_EXPAND_BYTES``):
    the rows' latent rows ``lat`` ``[R, C, E, W]`` (a row's positions in
    ``C`` pieces of ``E``) become the group's per-head keys (``c^kv
    W^UK`` beside the shared rotated part) and values (``c^kv W^UV``)
    ONCE, a temporary of one group, a piece at a time and only the
    pieces under ``reach`` ``[R]`` (the positions a row's tokens reach:
    a chunk step's table is as wide as a row can grow, and a row that
    has no token in the launch is not expanded at all:
    ``kernels/ragged_attention.expand_latent_rows``); every tile then
    attends its row's (``picked_heads_attention``). q ``[nh, N, dn + dr]``, wkv_b ``[dc, nh,
    dn + dv]``, picked_t ``[N / tt, positions, tt]``, bounds ``[N]``.
    Returns ``[N, nh, dv]``."""
    from .kernels.ragged_attention import (
        expand_latent_rows, expand_latent_rows_reference,
        picked_heads_attention, picked_heads_attention_reference)
    nh, N, dk = q.shape
    dr = dk - dn
    dv = wkv_b.shape[-1] - dn
    R, C, E, W = lat.shape
    a_head = R * C * E * (dn + dv) * lat.dtype.itemsize
    hg = math.gcd(nh, max(4, 1 << max(int(_EXPAND_BYTES // a_head), 1
                                      ).bit_length() - 1))
    k_rope = lat[..., dc:dc + dr]
    expand, attend = (expand_latent_rows, picked_heads_attention) \
        if use_kernel else (expand_latent_rows_reference,
                            picked_heads_attention_reference)

    def group(args):
        q, w = args                         # [hg, N, dk], [dc, hg, dn + dv]
        k, v = expand(lat, w, reach, dc=dc, dn=dn)
        return attend(q, k, k_rope, v, picked_t, tile_rows, bounds,
                      scale=scale, tq=tq)

    if hg == nh:
        return group((q, wkv_b)).reshape(N, nh, dv)
    out = jax.lax.map(group, (
        q.reshape(nh // hg, hg, N, dk),
        wkv_b.reshape(dc, nh // hg, hg, dn + dv).transpose(1, 0, 2, 3)))
    return out.reshape(nh // hg, N, hg, dv).transpose(1, 0, 2, 3).reshape(
        N, nh, dv)


def _expanded_index_attention(q, qi, wi, pool, l, wkv_b, row_ids, lengths,
                              block_tables, *, dc, dn, scale, topk,
                              use_kernel):
    """A PROMPT launch's read of a full latent layer that selects, in
    the EXPANDED form (:func:`index_prompt_form`): the launch's tokens
    packed in tiles of one row each (:func:`_row_tiles`), the same sets
    (:func:`index_mask`, ``_INDEX_TILE`` packed tokens at a time, left
    transposed as the kernel reads them: no pass over the launch's flags
    between the two), then every token attends what it picked head by
    head, ``q_nope |
    q_rope`` against the per-head keys and values its row's cached
    latent rows are turned into once for the launch and layer
    (:func:`_expanded_picked_attention`): a third of the absorbed form's
    products a position, and neither ``q_nope Wk^T`` nor ``o_lat Wv``.
    The pool keeps latent rows: the expansion is a temporary. q ``[T,
    nh, dn + dr]`` (the rotated part rotated) -> ``[T, nh, dv]``."""
    from .kernels.ragged_attention import PICKED_CHUNK, picked_heads_tile
    T, (R, MB) = q.shape[0], block_tables.shape
    bs, W = pool["latent"].shape[2:]
    with jax.named_scope("attn_kernel"):
        tq = picked_heads_tile(T, R)
        src, slot, tile_rows = _row_tiles(row_ids, lengths, R, tq)
        at = jnp.minimum(src, T - 1)
        bounds = jnp.where(src < T, lengths[at], 0).astype(jnp.int32)
    tt = min(_INDEX_TILE, tq)
    n = src.shape[0] // tt

    def tile(args):
        """a tile's flags, a token a lane; a tile that holds no token
        (a row's last tile may be its only one) scores nothing"""
        qi, wi, row, bounds = args
        return jax.lax.cond(
            bounds[0] > 0,
            lambda: index_mask(qi, wi, pool["index_k"], l,
                               jnp.full((tt,), row), bounds, block_tables,
                               topk).T.astype(jnp.int8),
            lambda: jnp.zeros((MB * bs, tt), jnp.int8))

    picked_t = jax.lax.map(tile, (
        qi[at].reshape(n, tt, *qi.shape[1:]), wi[at].reshape(n, tt, -1),
        jnp.repeat(tile_rows, tq // tt), bounds.reshape(n, tt)))
    with jax.named_scope("attn_kernel"):
        # a row's positions in pieces of _EXPAND_PIECE, whole chunks of
        # the kernel's (a table at its full width is no power of two):
        # null pages behind, under no token's bound
        ctx = MB * bs
        E = next((e for e in (_EXPAND_PIECE, PICKED_CHUNK) if ctx > e), ctx)
        tables = jnp.pad(block_tables, ((0, 0), (0, -MB % (E // bs))))
        lat = pool["latent"][l, tables].reshape(R, -1, E, W)
        reach = jnp.zeros((R,), jnp.int32).at[row_ids].max(
            lengths.astype(jnp.int32))
        out = _expanded_picked_attention(
            q[at].transpose(1, 0, 2), lat, wkv_b, picked_t, tile_rows,
            bounds, reach, dc=dc, dn=dn, scale=scale, tq=tq,
            use_kernel=use_kernel)
        return jnp.where((slot < src.shape[0])[:, None, None],
                         out[jnp.minimum(slot, src.shape[0] - 1)], 0)


def _query_latent(cfg, lp, hn, lk):
    """c^q: the query's normed latent, rescaled where the block says"""
    from ...ops.norms import rms_norm
    cq = rms_norm(hn @ lp["wq_a"], lp["q_norm"], cfg.norm_eps)
    if cfg.mla_lora_rescale:
        cq = cq * jnp.asarray((cfg.hidden_size / lk.q_rank) ** 0.5, cq.dtype)
    return cq


def _index_queries(cfg, lp, hn, cq, cos, sin):
    """The indexer's side of a query token: its heads' queries from the
    query's latent, their first lanes rotated with the halves paired,
    ``[T, heads, d]``, and a weight a head from the layer's normed
    input with both scales in it, ``[T, heads]`` float32."""
    ih = cfg.index_n_heads
    qi = _rotate((cq @ lp["index_wq"]).reshape(hn.shape[0], ih, -1),
                 cos[:, None], sin[:, None])
    wi = (hn @ lp["index_ww"]).astype(jnp.float32) * (
        ih ** -0.5 * cfg.index_head_dim ** -0.5)
    return qi, wi


def index_picks(cfg, lp, x, pos, keys, l, block_table, one_token=False):
    """What the indexer of ONE full latent layer picks for query tokens
    at positions ``pos`` [n] of one sequence, given the layer's input
    ``x`` [n, H] there: the program's own query path (norm, the query's
    latent, the indexer's queries and weights, in the weights' type) and
    its own selection against the index keys the sequence CACHED
    (``keys``: the ``index_k`` leaf; ``l`` the layer's place in it;
    ``block_table`` [MB] the sequence's), in the form a prompt's launch
    takes (:func:`index_mask`) or, ``one_token``, a decode step's
    (:func:`index_select`). Returns flags ``[n, MB * block size]`` bool:
    row i is what the token at ``pos[i]`` reads. The read half of a check on the selection (the
    benchmark's ``generate_sparse`` runner, the serving tests); no
    serving program calls it."""
    hn = _norm(cfg, x, lp["attn_norm"]).astype(lp["wq_a"].dtype)
    cos, sin = _rope_at(cfg, pos)
    qi, wi = _index_queries(cfg, lp, hn, _query_latent(
        cfg, lp, hn, cfg.latent_kind()), cos, sin)
    rows, table = jnp.zeros_like(pos), block_table[None]
    if not one_token:
        return index_mask(qi, wi, keys, l, rows, pos + 1, table,
                          cfg.index_topk)
    idx, ok = index_select(qi, wi, keys, l, rows, pos + 1, table,
                           cfg.index_topk)
    flags = jnp.zeros((len(pos), table.shape[1] * keys.shape[2]), bool)
    return flags.at[jnp.arange(len(pos))[:, None], idx].max(ok)


def _latent_attention_sublayer(cfg, lp, x, l, pool, cos, sin, row_ids,
                               lengths, write_blocks, write_offsets,
                               block_tables, use_kernel, one_token=False,
                               kind="mla"):
    """Multi-head latent attention on flat tokens x [T, H]: the new
    tokens' rows (normed latent, rotated shared key part) go to layer
    ``l`` of the pool, and the tokens attend the pool's rows in one of
    two forms of the same mathematics. ABSORBED (every decode step,
    every layer without an indexer, a selecting prompt launch of few
    tokens a row): a head's query is carried into the latent's space by
    its slice of ``wkv_b`` (``q_nope Wk^T``), attends the rows there
    (``kernels/ragged_attention.latent_attention``) and its output
    leaves that space by the value slice (``o_lat Wv``). EXPANDED (a
    selecting prompt launch of ``_EXPAND_TOKENS_A_ROW`` tokens a row or
    more, :func:`index_prompt_form`): every cached position's keys and
    values are made a head, once for the launch, and the query needs
    neither product (:func:`_expanded_index_attention`): a third of the
    absorbed form's products a position. A long prefill of a layer
    WITHOUT an indexer still takes the absorbed form (ROADMAP M3: no
    cell feeds one). ``one_token``: every row has exactly one token (a
    decode batch), which the kernel is told.

    ``kind`` (a pattern over latent attention): whose sizes and whose
    leaf (``cfg.latent_kind``, ``LATENT_LEAVES``). "mla_window" sees
    its last ``attn_window`` positions: ``write_blocks`` and
    ``block_tables`` are then places of the row's ring, and the kernel
    is told the window. A "mla" layer with an INDEXER
    (``cfg.index_topk``) writes the indexer's key of every new token
    beside its row (``index_k``) and, where the tables hold more than
    ``index_topk`` positions, attends only the positions the indexer
    picks a query token (a decode step gathers them, a prompt's launch
    masks the rest: :func:`_indexed_latent_attention` over the latent
    rows or :func:`_expanded_index_attention` over per-head keys and
    values, by the launch's shapes); where they hold no more, every
    position under a token's bound is picked and the launch is the
    dense one. ``cfg.mla_lora_rescale``: each normed
    latent times sqrt(hidden / its rank). Returns (what attention adds
    to x, pool)."""
    from ...ops.norms import layer_norm, rms_norm
    from .kernels.ragged_attention import (latent_attention,
                                           latent_attention_reference)
    T = x.shape[0]
    lk = cfg.latent_kind(kind)
    leaf = LATENT_LEAVES[kind]
    nh, dc = lk.heads, lk.kv_rank
    dn, dr, dv = lk.nope, lk.rope, lk.v
    hn = _norm(cfg, x, lp["attn_norm"]).astype(lp["wkv_a"].dtype)
    if lk.q_rank:
        cq = _query_latent(cfg, lp, hn, lk)
        q = cq @ lp["wq_b"]
    else:
        q = hn @ lp["wq"]
    q = q.reshape(T, nh, dn + dr)
    kv = hn @ lp["wkv_a"]                                   # [T, dc + dr]
    ckv = rms_norm(kv[:, :dc], lp["kv_norm"], cfg.norm_eps)
    if cfg.mla_lora_rescale:
        ckv = ckv * jnp.asarray((cfg.hidden_size / dc) ** 0.5, ckv.dtype)
    k_rope = _rotate_pairs(kv[:, dc:], cos, sin, cfg.rope_interleave)
    q_rope = _rotate_pairs(q[..., dn:], cos[:, None], sin[:, None],
                           cfg.rope_interleave)
    W = pool[leaf].shape[-1]
    with jax.named_scope("kv_write"):
        pool = _latent_write(pool, l, write_blocks, write_offsets,
                             jnp.concatenate([ckv, k_rope], axis=-1), leaf)
        if lk.topk:
            # the indexer's key of each new token: a LayerNorm (weight
            # and bias) of one projection, its first lanes rotated with
            # the halves paired
            ki = layer_norm(hn @ lp["index_wk"], lp["index_k_norm"],
                            lp["index_k_bias"], cfg.norm_eps)
            pool = _latent_write(pool, l, write_blocks, write_offsets,
                                 _rotate(ki, cos, sin), "index_k")
    wkv_b = lp["wkv_b"].reshape(dc, nh, dn + dv)
    scale = 1.0 / float(dn + dr) ** 0.5

    def absorbed_query():
        """a head's query carried into the latent's space, ``[nh, T, W]``"""
        q_lat = jnp.einsum("thd,chd->htc", q[..., :dn], wkv_b[..., :dn])
        qx = jnp.concatenate([q_lat, q_rope.transpose(1, 0, 2)], axis=-1)
        return jnp.pad(qx, ((0, 0), (0, 0), (0, W - dc - dr)))

    positions = block_tables.shape[1] * pool[leaf].shape[2]
    form = index_prompt_form(T, block_tables.shape[0], positions, lk.topk)
    if form:
        with jax.named_scope("indexer"):
            qi, wi = _index_queries(cfg, lp, hn, cq, cos, sin)
    if form == "expanded":      # (never a decode step: one token a row)
        o = _expanded_index_attention(
            jnp.concatenate([q[..., :dn], q_rope], axis=-1), qi, wi, pool,
            l, wkv_b, row_ids, lengths, block_tables, dc=dc, dn=dn,
            scale=scale, topk=lk.topk, use_kernel=use_kernel)
    elif form:
        o_lat = _indexed_latent_attention(
            absorbed_query(), qi, wi, pool, l, row_ids, lengths,
            block_tables, dc=dc, scale=scale, topk=lk.topk,
            one_token=one_token, use_kernel=use_kernel)
        o = jnp.einsum("htc,chd->thd", o_lat, wkv_b[..., dn:])
    else:
        qx = absorbed_query()
        with jax.named_scope("attn_kernel"):
            rows, at = _latent_rows(pool, l, hn.dtype, leaf)
            window = {"window": lk.window} if lk.window else {}
            attend = functools.partial(latent_attention, one_token=one_token,
                                       **window) if use_kernel \
                else functools.partial(latent_attention_reference, **window)
            o_lat = attend(qx, rows, at, row_ids, lengths, block_tables,
                           dc=dc, scale=scale)
        o = jnp.einsum("htc,chd->thd", o_lat, wkv_b[..., dn:])
    if cfg.attn_gate == "head":
        o = o * jax.nn.sigmoid(hn @ lp["wg"])[..., None].astype(o.dtype)
    return o.reshape(T, nh * dv) @ lp["wo"], pool


def _per_head_attention_sublayer(cfg, lp, x, kind, l, pool, cos, sin,
                                 row_ids, lengths, write_blocks,
                                 write_offsets, block_tables, use_kernel,
                                 one_token=False, hn=None, runs=None):
    """A per-head (GQA) mixer of a layer pattern on flat tokens x
    [T, H]: ``kind`` "full" (a token sees every position under its
    bound) or "window" (its last ``cfg.attn_window``), ``l`` the layer's
    index among its kind's (its leaves' leading axis). q, k, v and the
    gate are projections of the normed input; q and k are RMS-normed a
    head (``qk_norm``) and rotated (a full layer not, under
    ``rope_sliding_only``); the new keys and values go to the kind's
    leaves at ``write_blocks`` / ``write_offsets``, which for a window
    layer are places of the row's ring; attention is
    ``kernels/ragged_attention.ragged_attention`` over the kind's
    tables (``window``: the walk starts at the window's first page), or
    its gathering reference; the heads' output times sigmoid of the
    gate, element-wise; ``wo``; the post-norm of the sandwich scheme.
    An int8 pool is dequantised a layer at a time into a transient pool
    of one layer (1 / L of the leaf at twice its bytes), as the latent
    pool's is (``_latent_rows``). ``one_token``: every row has exactly
    one token (a decode batch), which the kernel is told. ``hn``:
    ``norm(x, attn_norm)`` where the caller has made it already (a
    router ahead of the mixer reads it too, and so does the other half
    of a two-mixer layer). The block's multipliers, where it has them:
    ``cfg.attn_in_scale`` on the normed input (taken on each
    projection's float32 sum, which is linear in it) and
    ``cfg.key_scale`` on the keys, ahead of the rotation, so that the
    pool holds the keys the source caches. ``runs``: how the kind's
    tables lie, where the program has made it (``_table_runs``).
    Returns (what attention adds to x, pool)."""
    from ...ops.norms import rms_norm
    from .kernels.ragged_attention import (ragged_attention,
                                           ragged_attention_reference)
    T = x.shape[0]
    nh, nkv, hd = cfg.num_heads, cfg.kv_heads, cfg.head_dim
    dt = lp["wq"].dtype
    if hn is None:
        hn = _norm(cfg, x, lp["attn_norm"])
    hn = hn.astype(dt)

    def proj(w, scale):
        # a multiplier is taken on the matmul's float32 sum before its
        # one rounding
        return hn @ lp[w] if scale == 1.0 else (jnp.dot(
            hn, lp[w], preferred_element_type=jnp.float32) * scale).astype(dt)

    s_in = cfg.attn_in_scale
    with jax.named_scope("qkv_proj"):
        # the kernels score q k^T over sqrt(hd): a model that scales
        # its scores otherwise has the ratio in its queries
        q = proj("wq", s_in * (cfg.attn_scale * hd ** 0.5
                               if cfg.attn_scale else 1.0))
        q = q.reshape(T, nh, hd)
        k = proj("wk", s_in * cfg.key_scale).reshape(T, nkv, hd)
        v = proj("wv", s_in).reshape(T, nkv, hd)
        if cfg.qk_norm:
            q = rms_norm(q, lp["q_norm"], cfg.norm_eps)
            k = rms_norm(k, lp["k_norm"], cfg.norm_eps)
        if cfg.positional == "rope" and (
                kind == "window" or not cfg.rope_sliding_only):
            q = _rotate(q, cos[:, None, :], sin[:, None, :])
            k = _rotate(k, cos[:, None, :], sin[:, None, :])
    tag = "_" + kind
    kc, vc = pool["k" + tag], pool["v" + tag]
    ksc, vsc = pool.get("ks" + tag), pool.get("vs" + tag)
    kc, vc, ksc, vsc = _kv_write_pair(kc, vc, ksc, vsc, l, write_blocks,
                                      write_offsets, k, v)
    pool = {**pool, "k" + tag: kc, "v" + tag: vc}
    if ksc is not None:
        pool.update({"ks" + tag: ksc, "vs" + tag: vsc})
    window = cfg.attn_window if kind == "window" else 0
    with jax.named_scope("attn_kernel"):
        if not use_kernel:
            o = ragged_attention_reference(
                q, kc, vc, l, row_ids, lengths, block_tables,
                None if ksc is None else ksc[l],
                None if vsc is None else vsc[l], window=window)
        else:
            at = l
            if ksc is not None:
                # every page of the layer through ``_kv_read``'s dequant
                kc, vc = (_kv_read(c, sc, l, jnp.arange(c.shape[1]), nkv,
                                   dt).reshape(1, *c.shape[1:])
                          for c, sc in ((kc, ksc), (vc, vsc)))
                at = jnp.int32(0)
            o = ragged_attention(q, kc, vc, at, row_ids, lengths,
                                 block_tables, window=window,
                                 one_token=one_token, runs=runs)
    if cfg.attn_gate == "elementwise":
        with jax.named_scope("attn_gate"):
            o = o * jax.nn.sigmoid(
                proj("wg", s_in).astype(jnp.float32)
            ).reshape(T, nh, hd).astype(o.dtype)
    with jax.named_scope("out_proj"):
        a = o.reshape(T, nh * hd) @ lp["wo"]
        if cfg.norm_scheme == "sandwich":
            a = _norm(cfg, a.astype(jnp.float32), lp["attn_post_norm"])
    return a, pool


def _moe_stats(topi, valid, num_experts, held_from=None):
    """What one expert layer routed in one launch, float32 [4]: 1 (a
    launch of an expert layer), the routed rows (valid tokens x k), the
    distinct experts with at least one row, and the fullest expert's
    share of the rows. ``held_from``: the layer holds ``num_experts`` of
    the router's from that index, and only rows routed to THEM and the
    held experts they touch are counted (what its grouped matmuls
    read and compute)."""
    idx = topi.reshape(-1)
    w = jnp.repeat(valid, topi.shape[-1]).astype(jnp.float32)
    if held_from is not None:
        idx = idx - held_from
        w = w * ((idx >= 0) & (idx < num_experts))
        idx = jnp.clip(idx, 0, num_experts - 1)
    counts = jnp.zeros((num_experts,), jnp.float32).at[idx].add(w)
    rows = jnp.sum(counts)
    return jnp.stack([jnp.float32(1.0), rows, jnp.sum(counts > 0),
                      jnp.max(counts) / jnp.maximum(rows, 1.0)])


def _merge_moe_stats(a, b):
    """Counts add; the fullest share is the larger."""
    return jnp.concatenate([a[:3] + b[:3], jnp.maximum(a[3:], b[3:])])


class _StateRows(NamedTuple):
    """Where the rows of a launch lie, for the layers that keep a state
    a row: token -> row, each row's first flat token and token count
    (rows are packed one after another in row order:
    ``ragged/batch.pack``), its state slot (0, the null slot, for a row
    with no token) and whether its first token here is the sequence's
    first, so that it starts from zeros and not from what its slot
    held. ``one_token``: every row has exactly one (a decode batch)."""
    row_ids: Any
    starts: Any
    counts: Any
    slots: Any
    fresh: Any
    one_token: bool


def _state_rows(row_ids, pos, lengths, slots, rows, one_token):
    valid = lengths > 0
    if one_token:
        counts = valid.astype(jnp.int32)
        starts = jnp.arange(rows, dtype=jnp.int32)
    else:
        counts = jnp.zeros((rows,), jnp.int32).at[row_ids].add(
            valid.astype(jnp.int32))
        starts = jnp.cumsum(counts) - counts
    first = pos[jnp.clip(starts, 0, pos.shape[0] - 1)]
    return _StateRows(row_ids, starts, counts,
                      jnp.where(counts > 0, slots, 0), first == 0,
                      one_token)


def _linear_attention_sublayer(cfg, lp, x, l, cache, rows: _StateRows,
                               use_kernel=True):
    """A linear-attention (KDA) mixer on flat tokens x [T, H]; ``l`` is
    the layer's index among the linear layers (its state leaves'
    leading axis). q, k and v pass the short causal convolution over the
    row's own tokens and SiLU (scope ``kda_conv``: a decode batch through
    the kernel that reads and writes the slot's last inputs where they
    lie, ``kda_conv_update``, where ``use_kernel`` and the widths allow
    (the kernel COLOURS the leaf HBM, ``kernels/slot_leaf``, which is
    what keeps it where it lies between launches: ``pl.ANY`` on its
    operand would not), else gather, ``causal_conv_step`` and scatter;
    every other launch through ``causal_conv_rows``); a head's q and k
    are l2-normalised (q
    times d_k^-1/2); the decay a head and key channel is
    ``linear_decay_floor * sigmoid(exp(a_log) (wf x + dt_bias))`` and
    the update strength ``sigmoid(wb x)``; the heads' outputs are
    RMS-normed, gated a head and projected. The recurrence runs in
    float32 from the row's slot (zeros for a row at its first token)
    and its result goes back to the slot: a decode batch through the
    one-token update (scope ``kda_state``: the kernel that reads the
    slot where it lies, ``kda_state_update``, where ``use_kernel`` and
    the widths allow, else gather, ``kda_step`` and scatter), every
    other launch through the chunked form, rows of any lengths (scope
    ``kda_chunk``: under the same two conditions the kernel
    ``kda_chunk_fwd``, which reads the projections where they lie and
    runs the norms and gates on them in VMEM, else the XLA
    ``kda_chunked``): ``kernels/linear_attention``. Returns (what the
    mixer adds to x, cache)."""
    from ...ops.norms import rms_norm
    from .kernels import linear_attention as la
    T = x.shape[0]
    nh, d = cfg.num_heads, cfg.linear_head_dim
    D, f32 = nh * d, jnp.float32
    dt = lp["wq"].dtype
    hn = _norm(cfg, x, lp["attn_norm"]).astype(dt)
    with jax.named_scope("kda_proj"):
        # a decode row's q, k and v stay float32 from the matmul's sum
        # to the recurrence. XLA's fusions kept them so (excess
        # precision) while the convolution was theirs; a kernel between
        # them takes what the program says, and rounded to ``dt`` before
        # and behind it the state's error against the float32 reference
        # read 6.0e-3 where it had read 4.3e-3 (PERF.md section 6, PR 45)
        wide = f32 if rows.one_token else None
        qkv = [jnp.dot(hn, lp[w], preferred_element_type=wide)
               for w in ("wq", "wk", "wv")]
        f, b = hn @ lp["wf"], hn @ lp["wb"]
    slots = rows.slots
    with jax.named_scope("kda_conv"):
        leaf = cache["kda_conv"]            # [L, slots, K - 1, 3D / w, w]
        if rows.one_token and use_kernel and la.conv_kernel_serves(leaf):
            # the kernel takes a row's projections as rows of 128 lanes.
            # Behind the barrier that is a 2 MB copy of each; without it
            # XLA has the matmuls make that shape, and for them copies
            # every layer's wq, wk and wv transposed, once a launch
            mixed, leaf = la.kda_conv_update(
                leaf, l, slots, rows.fresh,
                *jax.lax.optimization_barrier(qkv), lp["conv"])
        else:
            held = leaf[l, slots].reshape(-1, leaf.shape[2], 3 * D)
            held = jnp.where(rows.fresh[:, None, None], 0, held)
            mixed, kept = [], []
            for i, part in enumerate(qkv):
                taps = lp["conv"][:, i * D:(i + 1) * D]
                past = held[..., i * D:(i + 1) * D]
                y, past = la.causal_conv_step(
                    part, taps, past, jax.nn.silu) \
                    if rows.one_token else la.causal_conv_rows(
                        part, taps, past, rows.row_ids, rows.starts,
                        rows.counts, jax.nn.silu)
                mixed.append(y)
                kept.append(past)
            leaf = leaf.at[l, slots].set(jnp.concatenate(
                kept, axis=-1).reshape(-1, *leaf.shape[2:]))
        cache = {**cache, "kda_conv": leaf}
    rate = jnp.exp(lp["a_log"].astype(f32))[:, None]        # [nh, 1]
    dt_bias = lp["dt_bias"].astype(f32).reshape(nh, d)

    def prepare(q, k, v, f, b):
        """float32 (q, k, v, g, beta) of tokens [..., D], heads split"""
        q, k, v, f = (a.astype(f32).reshape(*a.shape[:-1], nh, d)
                      for a in (q, k, v, f))
        return la.kda_inputs(q, k, v, f, b, rate, dt_bias,
                             cfg.linear_decay_floor)

    # the recurrence with its state's way out of the slot (zeros for a
    # row at its first token) and back, one scope: what a roofline of it
    # has to count
    with jax.named_scope("kda_state" if rows.one_token else "kda_chunk"):
        leaf = cache["kda_state"]
        if rows.one_token and use_kernel and la.state_kernel_serves(leaf):
            o, leaf = la.kda_state_update(leaf, l, slots, rows.fresh,
                                          *prepare(*mixed, f, b))
        elif rows.one_token:
            state = jnp.where(rows.fresh[:, None, None, None], 0.0,
                              leaf[l, slots].astype(f32))   # [N, nh, d, d]
            o, state = la.kda_step(*prepare(*mixed, f, b), state)
            leaf = leaf.at[l, slots].set(state.astype(leaf.dtype))
        elif use_kernel and la.chunk_kernel_serves(leaf):
            o, leaf = la.kda_chunk_fwd(
                (*mixed, f, b), rate, dt_bias, leaf, l, slots, rows.fresh,
                rows.starts, rows.counts, cfg.linear_decay_floor)
        else:
            o, leaf = la.kda_chunked(
                (*mixed, f, b), prepare, leaf, l, slots, rows.fresh,
                rows.starts, rows.counts, cfg.linear_decay_floor)
        cache = {**cache, "kda_state": leaf}
    with jax.named_scope("kda_out"):
        o = rms_norm(o, lp["o_norm"], cfg.norm_eps)         # [T, nh, d]
        if cfg.attn_gate == "head":
            o = o * jax.nn.sigmoid((hn @ lp["wg"]).astype(f32))[..., None]
        return o.astype(dt).reshape(T, D) @ lp["wo"], cache


def _state_space_sublayer(cfg, lp, x, l, cache, rows: _StateRows,
                          use_kernel=True, hn=None):
    """A state-space (Mamba-2) mixer on flat tokens x [T, H]; ``l`` is
    the layer's index among the state-space layers (its state leaves'
    leading axis). One projection of the normed input gives [z | x B C |
    dt] (scope ``ssm_proj``); x, B and C pass the short causal
    convolution over the row's own tokens, its bias and SiLU (scope
    ``ssm_conv``: a decode batch through the convolution's kernel on the
    slot where it lies, ``ssm_conv_update`` in a trace, where
    ``use_kernel`` and the widths allow (the kernel colours the leaf
    HBM, ``kernels/slot_leaf``: until PR 57 the compiler carried its
    59-67 MB into the chip's fast memory and back round every launch of
    a decode step, and neither ``pl.ANY`` nor ``pltpu.HBM`` on the
    kernel's ``BlockSpec`` stops that), else gather,
    ``causal_conv_step`` and scatter; every other launch through
    ``causal_conv_rows``); ``dt = softplus(dt + dt_bias)`` and ``A =
    -exp(a_log)`` a head; the recurrence runs in float32 from the row's
    slot (zeros for a row at its first token) and its result goes back
    to the slot: a decode batch through the one-token update (scope
    ``ssm_state``: the kernel ``ssm_state_update`` or gather,
    ``ssm_step`` and scatter), every other launch through the chunked
    form, rows of any lengths (scope ``ssm_scan``: the kernel
    ``ssm_chunk_fwd`` or the XLA ``ssm_chunked``):
    ``kernels/state_space``; the skip term ``d_skip x``; the heads'
    whole output times SiLU(z), RMS-normed a GROUP of heads
    (``cfg.mamba_n_groups`` of them, each with its own B and C: one
    group, the whole output) under one weight (scope ``ssm_gate_norm``)
    and projected (``ssm_out``). ``hn``: ``norm(x, attn_norm)`` where
    the caller has made it already (the other half of a two-mixer layer
    reads it too). The block's multipliers, where it has them
    (``cfg.ssm_proj_scales``: the input's and the five segments' of the
    projection, one vector on its float32 sum). Returns (what the mixer
    adds to x, cache)."""
    from ...ops.norms import rms_norm
    from .kernels import linear_attention as la
    from .kernels import state_space as ss
    di, groups = cfg.mamba_d_inner, cfg.mamba_n_groups
    n = groups * cfg.mamba_d_state      # B's (and C's) width a token
    dc, f32 = cfg.mamba_conv_dim, jnp.float32
    dt_ = lp["w_in"].dtype
    if hn is None:
        hn = _norm(cfg, x, lp["attn_norm"])
    hn = hn.astype(dt_)
    scales = cfg.ssm_proj_scales
    with jax.named_scope("ssm_proj"):
        # a decode row's projections stay float32 from the matmul's sum
        # to the recurrence, as a linear layer's do (PERF.md section 6,
        # PR 45)
        zxd = jnp.dot(hn, lp["w_in"], preferred_element_type=f32
                      if rows.one_token or scales else None)
        if scales:
            zxd = zxd * jnp.concatenate(
                [jnp.full((w,), s, f32) for w, s in scales])
            if not rows.one_token:
                zxd = zxd.astype(dt_)
        z, xbc = zxd[:, :di], zxd[:, di:di + dc]
        dt = jax.nn.softplus(zxd[:, di + dc:].astype(f32)
                             + lp["dt_bias"].astype(f32))
        a = -jnp.exp(lp["a_log"].astype(f32))
    slots = rows.slots
    with jax.named_scope("ssm_conv"):
        leaf = cache["ssm_conv"]            # [L, slots, K - 1, dc / w, w]
        bias = lp.get("conv_b")
        if rows.one_token and use_kernel \
                and la.conv_kernel_serves(leaf, parts=1):
            (xbc,), leaf = la.conv_update(
                leaf, l, slots, rows.fresh, (xbc,), lp["conv"], bias,
                name="ssm_conv_update")
        else:
            def act(y):
                return jax.nn.silu(y if bias is None
                                   else y + bias.astype(f32))
            held = leaf[l, slots].reshape(-1, leaf.shape[2], dc)
            held = jnp.where(rows.fresh[:, None, None], 0, held)
            xbc, held = la.causal_conv_step(xbc, lp["conv"], held, act) \
                if rows.one_token else la.causal_conv_rows(
                    xbc, lp["conv"], held, rows.row_ids, rows.starts,
                    rows.counts, act)
            leaf = leaf.at[l, slots].set(
                held.reshape(-1, *leaf.shape[2:]))
        cache = {**cache, "ssm_conv": leaf}
    xs, b, c = xbc[:, :di], xbc[:, di:di + n], xbc[:, di + n:]
    # the recurrence with its state's way out of the slot and back, one
    # scope: what a roofline of it has to count
    with jax.named_scope("ssm_state" if rows.one_token else "ssm_scan"):
        leaf = cache["ssm_state"]
        if rows.one_token:
            step = ss.ssm_state_update if use_kernel \
                and ss.state_kernel_serves(leaf, groups) else ss.ssm_step
            y, leaf = step(leaf, l, slots, rows.fresh, xs.astype(f32), dt,
                           a, b.astype(f32), c.astype(f32))
        else:
            scan = ss.ssm_chunk_fwd if use_kernel \
                and ss.chunk_kernel_serves(leaf, cfg.mamba_d_head, groups) \
                else ss.ssm_chunked
            y, leaf = scan(leaf, l, slots, rows.fresh, rows.starts,
                           rows.counts, xbc, dt, a)
        cache = {**cache, "ssm_state": leaf}
        y = y.astype(f32) + jnp.repeat(
            lp["d_skip"].astype(f32), cfg.mamba_d_head) * xs.astype(f32)
    with jax.named_scope("ssm_gate_norm"):
        y, w = y * jax.nn.silu(z.astype(f32)), lp["gate_norm"]
        if groups > 1:
            y = rms_norm(y.reshape(-1, groups, di // groups),
                         w.reshape(groups, -1), cfg.norm_eps).reshape(-1, di)
        else:
            y = rms_norm(y, w, cfg.norm_eps)
    with jax.named_scope("ssm_out"):
        return y.astype(dt_) @ lp["w_out"], cache


def _power_retention_sublayer(cfg, lp, x, l, cache, cos, sin,
                              rows: _StateRows, use_kernel=True):
    """A power-retention mixer on flat tokens x [T, H]; ``l`` is the
    layer's index among the retention layers (its state leaves' leading
    axis). q, k and v are the per-head block's projections of the normed
    input (scope ``qkv_proj``): q and k RMS-normed a head (``qk_norm``)
    and rotated, and beside them the decay's log, ``log sigmoid(w_decay
    x + b_decay)``, one scalar a key/value head. They stay float32 from
    the matmuls' sums to the recurrence: phi squares them, and a
    rounding of q or k is twice that of the pair's weight. The
    recurrence runs in float32 from the row's slot (zeros for a row at
    its first token) and its result goes back to the slot, the state of
    ONE key/value head read by its whole group of query heads: a decode
    batch through the one-token update (scope ``retention_state``: the
    kernel ``retention_state_update`` where ``use_kernel`` and the
    widths allow, both leaves coloured HBM by the kernel
    (``kernels/slot_leaf``: the normaliser's 40 MB were carried into
    fast memory and back round every launch), else gather,
    ``retention_step`` and scatter), every
    other launch through the chunked form, rows of any lengths (scope
    ``retention_chunk``: the kernel ``retention_chunk_fwd`` or the XLA
    ``retention_chunked``): ``kernels/power_retention``. No position is
    cached and no block table read. Returns (what the mixer adds to x,
    cache)."""
    from ...ops.norms import rms_norm
    from .kernels import power_retention as pr
    T = x.shape[0]
    nh, nkv, hd = cfg.num_heads, cfg.kv_heads, cfg.head_dim
    f32, dt = jnp.float32, lp["wq"].dtype
    hn = _norm(cfg, x, lp["attn_norm"]).astype(dt)
    with jax.named_scope("qkv_proj"):
        q, k, v, g = (jnp.dot(hn, lp[w], preferred_element_type=f32)
                      for w in ("wq", "wk", "wv", "w_decay"))
        q, k = q.reshape(T, nh, hd), k.reshape(T, nkv, hd)
        g = jax.nn.log_sigmoid(g + lp["b_decay"].astype(f32))
        if cfg.qk_norm:
            q = rms_norm(q, lp["q_norm"].astype(f32), cfg.norm_eps)
            k = rms_norm(k, lp["k_norm"].astype(f32), cfg.norm_eps)
        q = _rotate(q, cos[:, None, :], sin[:, None, :])
        k = _rotate(k, cos[:, None, :], sin[:, None, :])
    # the recurrence with its state's way out of the slot and back, one
    # scope: what a roofline of it has to count
    with jax.named_scope("retention_state" if rows.one_token
                         else "retention_chunk"):
        state, norm = cache["retention_state"], cache["retention_norm"]
        at = (state, norm, l, rows.slots, rows.fresh)
        tokens = (q, k, v.reshape(T, nkv, hd), g, cfg.retention_eps)
        if rows.one_token:
            step = pr.retention_state_update if use_kernel \
                and pr.state_kernel_serves(state) else pr.retention_step
            o, state, norm = step(*at, *tokens)
        else:
            scan = pr.retention_chunk_fwd if use_kernel \
                and pr.chunk_kernel_serves(state) else pr.retention_chunked
            o, state, norm = scan(*at, rows.starts, rows.counts, *tokens)
        cache = {**cache, "retention_state": state, "retention_norm": norm}
    with jax.named_scope("out_proj"):
        return o.astype(dt).reshape(T, nh * hd) @ lp["wo"], cache


def _short_conv_sublayer(cfg, lp, x, l, cache, rows: _StateRows,
                         use_kernel=True):
    """A doubly gated short convolution, a layer's WHOLE mixer, on flat
    tokens x [T, H]; ``l`` is the layer's index among the conv layers
    (its leaf's leading axis). One projection of the normed input gives
    [B | C | u] (scope ``conv_proj``); ``g = B * u``, the input gate; a
    causal depthwise convolution of ``cfg.conv_taps`` taps over the
    row's own ``g``, zeros before its start, and no activation on it;
    ``C *`` the result, the output gate (scope ``conv_gate`` holds both
    gates, the taps and the state's way out of its slot and back); one
    projection back (scope ``conv_out``). The row's state is its last ``taps - 1`` values of
    ``g``, oldest first, in its slot of ``conv_state``: a decode batch
    through the convolution's kernel on the slot where it lies
    (``short_conv_update`` in a trace: ``linear_attention.conv_update``
    without its activation, the gates in the fusions round it)
    where ``use_kernel`` and the width allow, else gather,
    ``causal_conv_step`` and scatter; every other launch through
    ``causal_conv_rows``, rows of any lengths, a row of fewer tokens
    than taps included. A decode row's projection stays float32 from
    the matmul's sum to the output gate, as every state-keeping
    layer's. Returns (what the mixer adds to x, cache)."""
    from .kernels import linear_attention as la
    H, f32 = cfg.hidden_size, jnp.float32
    dt = lp["w_in"].dtype
    hn = _norm(cfg, x, lp["attn_norm"]).astype(dt)
    with jax.named_scope("conv_proj"):
        bcu = jnp.dot(hn, lp["w_in"], preferred_element_type=f32
                      if rows.one_token else None)
    slots = rows.slots
    with jax.named_scope("conv_gate"):
        b, c, u = bcu[:, :H], bcu[:, H:2 * H], bcu[:, 2 * H:]
        g = b * u
        leaf = cache["conv_state"]          # [L, slots, K - 1, H / w, w]
        if rows.one_token and use_kernel \
                and la.conv_kernel_serves(leaf, parts=1):
            (y,), leaf = la.conv_update(
                leaf, l, slots, rows.fresh, (g,), lp["conv"],
                act="none", name="short_conv_update")
        else:
            held = leaf[l, slots].reshape(-1, leaf.shape[2], H)
            held = jnp.where(rows.fresh[:, None, None], 0, held)
            y, held = la.causal_conv_step(g, lp["conv"], held) \
                if rows.one_token else la.causal_conv_rows(
                    g, lp["conv"], held, rows.row_ids, rows.starts,
                    rows.counts)
            leaf = leaf.at[l, slots].set(
                held.reshape(-1, *leaf.shape[2:]))
        cache = {**cache, "conv_state": leaf}
        y = (c * y).astype(dt)
    with jax.named_scope("conv_out"):
        return y @ lp["w_out"], cache


def _table_runs(cfg, cache, block_tables, window_tables, use_kernel):
    """``(how block_tables lie, how window_tables lie)``
    (``kernels/ragged_attention.table_runs``), each None where the
    attention launches over that table do not read it
    (``launch_runs``: off the TPU, a geometry the pipelined variant
    serves, pages of 32 KB a leaf and more) or the model has no such
    table. A function
    of the tables alone, so a program makes it ONCE, here, under the
    scope ``table_runs`` ahead of every layer, and every attention
    launch takes it beside its table as one more prefetched scalar
    array; a decode window makes it once for all its steps (a table
    does not change on the device)."""
    from .kernels.ragged_attention import launch_runs
    if not (use_kernel and cfg.caches_positions) or cfg.attention == "mla":
        return None, None
    with jax.named_scope("table_runs"):
        full = cache.get("k_full", cache.get("k"))
        # behind a barrier: made whole HERE, where the program starts.
        # Left to the scheduler, the full table's few selects landed
        # between two runs of layers and a 16,384-token step kept one
        # more [tokens, hidden] float32 buffer alive round them (+0.17 GB
        # of temporaries by the compiler's analysis, PR 67)
        runs = (None if full is None else launch_runs(
                    block_tables, full, cfg.head_dim),
                None if window_tables is None else launch_runs(
                    window_tables, cache["k_window"], cfg.head_dim))
        # (a program that is handed none is the text it was)
        return runs if all(r is None for r in runs) \
            else jax.lax.optimization_barrier(runs)


def _layer_runs(cfg):
    """The layers as maximal runs of one (mixer kind, MLP kind):
    [(kind, routed, first layer, layers)], ``kind`` one of
    ``cfg.layer_kinds``' and ``routed`` whether the run's MLP is the
    expert layer. One scan a run; the pattern is static. Where a layer
    is ONE sub-layer (``cfg.one_sublayer``) a run is layers of one kind,
    a mixer's with no MLP and ``routed`` only for the kind "moe", an
    expert layer with no mixer: sixteen alternating layers are sixteen
    runs of one (more runs, and no unit that may lack a half)."""
    lead = cfg.moe_first_dense_layers
    runs = []
    for i, kind in enumerate(cfg.layer_kinds):
        key = (kind, kind == "moe", False) if cfg.one_sublayer else \
            (kind, cfg.moe_num_experts > 0 and i >= lead, i < lead)
        if runs and runs[-1][0] == key:
            runs[-1][2] += 1
        else:
            runs.append([key, i, 1])
    return [(k[0], k[1], first, n) for k, first, n in runs]


def _pattern_step(cfg: TransformerConfig, params, ids, row_ids, pos, lengths,
                  write_blocks, write_offsets, block_tables, cache,
                  use_kernel=True, state_slots=None, one_token=False,
                  window_tables=None, table_runs=(None, None)):
    """The whole block of a model that is served as RUNS of layers
    (``cfg.walks_runs``: attention='mla', or a ``layer_types`` pattern
    over per-head attention) on a flat token
    buffer, the one forward behind ``paged_ragged_step`` and
    ``paged_decode`` (a decode batch is the ragged layout with one token
    a row, ``one_token``): embedding, then the layers as RUNS of one
    mixer kind and one MLP kind (``_layer_runs``), each run one scan,
    then the final norm.

    Without a layer pattern there are two runs, the model's two stacks:
    ``lead_layers`` (latent attention + a dense gated MLP) and
    ``layers`` (latent attention + the expert layer, or a dense MLP
    where the model has no experts), both writing ONE pool
    ``[L, nb, bs, row]`` at their own layer indices. Under a pattern
    (``cfg.linear_attn_period``: linear-attention layers with one latent
    layer a period) the parameter tree keeps a stack a mixer kind
    (``kda_layers``, ``mla_layers``) beside the MLPs' two, and the cache
    a leaf a kind: the latent pool ``[L_latent, nb, bs, row]`` indexed
    by block, and the linear layers' state indexed by the row's SLOT
    (``state_slots`` [rows]; ``_init_latent_cache``,
    ``_linear_attention_sublayer``). A run indexes the stacks it reads
    at its own layers inside the scan's body: a static slice of a stack
    handed to the scan would be a copy of it.

    A pattern over PER-HEAD attention (``cfg.layer_types``) is the same
    walk with the kinds "window" and "full"
    (``_per_head_attention_sublayer``; stacks ``window_layers`` /
    ``full_layers``, leaves ``k_window`` ... / ``k_full`` ...): a full
    layer reads and writes by ``block_tables`` / ``write_blocks`` as
    every model's pool, a window layer by ``window_tables`` [rows, ring
    blocks], the row's ring, in which position p lies at place
    ``p % ring`` (its write-set is that arithmetic on ``pos``). Under
    the sandwich scheme each sub-layer's output passes a second norm
    before it joins the stream. ``table_runs``: how the two kinds'
    tables lie (``_table_runs``, made by the program ahead of this
    walk), handed to each kind's launches.

    A layer whose mixer is TWO mixers (kind "hybrid", ``layer_types``
    "mamba_attention") norms the stream ONCE and hands the result to a
    state-space half, which reads and writes the row's SLOT, and to a
    full per-head half, which reads and writes the row's BLOCKS; what
    they return is scaled and summed into the stream in float32 (scope
    ``hybrid_mixer`` round both, ``hybrid_join`` round the sum). Such a
    layer owns a place in the full pool and in the state-space leaves,
    so a run finds its place a leaf FAMILY (``cfg.leaf_places``), not a
    kind.

    A pattern of layers that are ONE sub-layer each (``cfg.one_sublayer``:
    ``layer_types`` names "moe" layers) walks the same way: a run of
    mixers reads ``<kind>_layers`` and has no MLP behind it, a run of
    expert layers reads ``layers`` (the expert layers alone, in layer
    order: their one norm ``mlp_norm``, router and experts) and has no
    mixer ahead of it.

    The stack's expert weights never ride a scan: a layer sliced out of
    them for the grouped-matmul kernel would be a copy of all its
    experts, so the kernel takes the stack whole and the layer by where
    its groups lie (``dropless_topk_dispatch``).

    Returns (normed hidden states [T, H], what this step's expert layers
    routed (``_moe_stats`` merged over them, valid tokens only, held
    experts only; zeros where the model has no experts), cache)."""
    dtype = params["embed"].dtype
    # the residual stream is float32 whatever the weights' type: every
    # sub-layer reads its norm rounded to ``dtype`` and adds what it
    # makes to the sum unrounded. Ten roundings of the stream itself
    # move a router's scores, and a score that moves past a neighbour's
    # swaps an expert: at published widths on the chip a bf16 stream
    # doubled the logits' typical error against the float32 reference
    # and swapped an expert on 7 seeds of 10 where this swaps on 4
    # (PERF.md section 4)
    with jax.named_scope("embed"):
        x = params["embed"][ids].astype(jnp.float32)
        if cfg.embed_scale != 1.0:
            x = x * jnp.asarray(cfg.embed_scale, x.dtype)
    cos, sin = _rope_at(cfg, pos)                # [T, qk_rope_head_dim / 2]
    valid = lengths > 0
    lead = cfg.moe_first_dense_layers
    kinds = cfg.layer_kinds
    pattern = cfg.pattern
    sandwich = cfg.norm_scheme == "sandwich"

    def joined(x, a, scale=1.0):
        """The stream with what a sub-layer adds to it, times the
        model's multiplier and the sub-layer's own (``scale``) where
        they are not 1."""
        a, scale = a.astype(jnp.float32), scale * cfg.residual_scale
        return x + (a if scale == 1.0 else a * scale)

    expert_keys = cfg.expert_keys
    single = cfg.one_sublayer
    latent_ring = "mla_window" in kinds
    if latent_ring:
        # the second latent kind rotates by a theta of its own
        ring_cos, ring_sin = _rope_at(cfg, pos, "mla_window")
    if window_tables is not None:
        # a window layer's write-set: the ring place of each new position
        bs = cache["latent_window" if latent_ring else "k_window"].shape[2]
        ring_blocks = window_tables.shape[1]
        window_writes = jnp.where(
            valid, window_tables[row_ids, (pos // bs) % ring_blocks], 0)
    rows = _state_rows(row_ids, pos, lengths, state_slots,
                       block_tables.shape[0], one_token) \
        if cfg.has_state else None

    def stack(x, pool, stats, kind, routed, first, n):
        led = first < lead
        # a layer that is one sub-layer has a mixer or an MLP, not both
        has_mixer, has_mlp = kind != "moe", routed or not single
        mlps = params["lead_layers" if led else "layers"] if has_mlp else {}
        # the run's place in mlps
        f0 = kinds[:first].count("moe") if single else \
            first if led else first - lead
        experts = tuple(mlps[k] for k in expert_keys) if routed else None
        scanned = {k: v for k, v in mlps.items()
                   if not (routed and k in expert_keys)}
        # the run's place in its mixer's parameters, and in the cache
        # leaves it owns a place in: a kind's own, but the full pool and
        # the state-space leaves, which a two-mixer layer shares with
        # the full and the state-space layers (``cfg.leaf_places``)
        m0 = kinds[:first].count(kind) if pattern else first
        p0, s0 = (cfg.leaf_places(f, first) for f in ("full", "ssm"))
        mixers = params[kind + "_layers"] if pattern and has_mixer else {}

        def layer_fn(carry, inputs):
            x, pool, stats = carry
            if pattern and kind in LATENT_SCOPES and cfg.layer_types:
                # (a pattern over latent attention reads a layer's
                # weights out of the stacks under the sub-layer's own
                # scope: a decode step's slices are a tenth of its time)
                i = inputs
                with jax.named_scope(LATENT_SCOPES[kind]):
                    lp = jax.tree.map(lambda a: a[m0 + i], mixers)
                with jax.named_scope("mlp"):
                    lp = {**lp,
                          **jax.tree.map(lambda a: a[f0 + i], scanned)}
            elif pattern:
                i = inputs
                lp = {**jax.tree.map(lambda a: a[m0 + i], mixers),
                      **jax.tree.map(lambda a: a[f0 + i], scanned)}
            else:
                lp, i = inputs
            picks = mixer_in = None
            if routed and cfg.moe_router_ahead:
                # the router reads the MIXER's normed input, ahead of
                # it: ONE norm, the mixer's own (scope ``attention``),
                # handed to both
                with jax.named_scope("attention"):
                    mixer_in = _norm(cfg, x, lp["attn_norm"])
                picks = _moe_route(cfg, lp, mixer_in,
                                   jax.lax.Precision.HIGHEST)
            if kind == "kda":
                with jax.named_scope("linear_attention"):
                    a, pool = _linear_attention_sublayer(
                        cfg, lp, x, m0 + i, pool, rows, use_kernel)
                    x = joined(x, a)
            elif kind == "ssm":
                with jax.named_scope("ssm_mixer"):
                    a, pool = _state_space_sublayer(
                        cfg, lp, x, s0 + i, pool, rows, use_kernel)
                    x = joined(x, a, cfg.ssm_out_scale)
            elif kind == "hybrid":
                # TWO mixers on ONE norm, summed: the state-space half
                # by the row's slot, the attention half by its block
                # table. The norm and the join stand under ``attention``
                # as every per-head layer's do, so the table of scopes
                # (``xla_profile``) reads them without a word of its own
                with jax.named_scope("hybrid_mixer"):
                    with jax.named_scope("attention"):
                        hn = _norm(cfg, x, lp["attn_norm"])
                    with jax.named_scope("ssm_mixer"):
                        m, pool = _state_space_sublayer(
                            cfg, lp, x, s0 + i, pool, rows, use_kernel, hn)
                    with jax.named_scope("attention"):
                        a, pool = _per_head_attention_sublayer(
                            cfg, lp, x, "full", p0 + i, pool, cos, sin,
                            row_ids, lengths, write_blocks, write_offsets,
                            block_tables, use_kernel, one_token, hn,
                            table_runs[0])
                        with jax.named_scope("hybrid_join"):
                            x = joined(joined(x, m, cfg.ssm_out_scale), a,
                                       cfg.attn_out_scale)
            elif kind == "conv":
                with jax.named_scope("short_conv"):
                    a, pool = _short_conv_sublayer(
                        cfg, lp, x, m0 + i, pool, rows, use_kernel)
                    x = joined(x, a)
            elif kind == "retention":
                with jax.named_scope("attention"):
                    a, pool = _power_retention_sublayer(
                        cfg, lp, x, m0 + i, pool, cos, sin, rows,
                        use_kernel)
                    x = joined(x, a)
            elif kind == "moe":
                pass                    # no mixer ahead of the experts
            elif kind in ("window", "full"):
                ring = kind == "window"
                with jax.named_scope("attention"):
                    a, pool = _per_head_attention_sublayer(
                        cfg, lp, x, kind, (m0 if ring else p0) + i, pool,
                        cos, sin, row_ids, lengths,
                        window_writes if ring else write_blocks,
                        write_offsets,
                        window_tables if ring else block_tables, use_kernel,
                        one_token, mixer_in, table_runs[ring])
                    x = joined(x, a, cfg.attn_out_scale)
            elif kind == "mla_window":
                # the second latent kind: its own sizes, a window over
                # the row's ring (scope ``mla_window_attention``, so the
                # two kinds' launches are told apart under
                # ``attn_kernel``)
                with jax.named_scope(LATENT_SCOPES[kind]):
                    a, pool = _latent_attention_sublayer(
                        cfg, lp, x, m0 + i, pool, ring_cos, ring_sin,
                        row_ids, lengths, window_writes, write_offsets,
                        window_tables, use_kernel, one_token, kind)
                    x = joined(x, a)
            else:
                with jax.named_scope("mla_attention"):
                    a, pool = _latent_attention_sublayer(
                        cfg, lp, x, m0 + i, pool, cos, sin, row_ids,
                        lengths, write_blocks, write_offsets, block_tables,
                        use_kernel, one_token)
                    x = joined(x, a)
            if not has_mlp:
                return (x, pool, stats), None
            with jax.named_scope("mlp"):
                hn = _norm(cfg, x, lp["mlp_norm"])
                if routed:
                    at = f0 + i         # the layer's place in ``experts``
                    if picks is None:   # the router behind the mixer
                        picks = _moe_route(cfg, lp, hn,
                                           jax.lax.Precision.HIGHEST)
                    topi, topv = picks
                    out = _moe_experts(cfg, lp, hn, topi, topv, experts, at)
                    with jax.named_scope("moe_router"):
                        stats = _merge_moe_stats(stats, _moe_stats(
                            topi, valid, cfg.experts_held,
                            _held_from(cfg)))
                else:
                    from ...models.transformer import gate_act
                    with jax.named_scope("dense_mlp"):
                        hn = hn.astype(dtype)
                        g = hn @ lp["w_gate"]
                        if cfg.mlp_gate_scale != 1.0:
                            g = (g.astype(jnp.float32)
                                 * cfg.mlp_gate_scale).astype(dtype)
                        out = (gate_act(cfg)(g)
                               * (hn @ lp["w_up"])) @ lp["w_down"]
                if sandwich:
                    out = _norm(cfg, out.astype(jnp.float32),
                                lp["mlp_post_norm"])
                # (1 beside an expert layer: the config refuses the rest)
                x = joined(x, out, cfg.mlp_down_scale)
            return (x, pool, stats), None

        with jax.named_scope("layers"):
            (x, pool, stats), _ = jax.lax.scan(
                layer_fn, (x, pool, stats),
                jnp.arange(n) if pattern else (scanned, jnp.arange(n)))
        return x, pool, stats

    pool, stats = cache, jnp.zeros((4,), jnp.float32)
    for kind, routed, first, n in _layer_runs(cfg):
        x, pool, stats = stack(x, pool, stats, kind, routed, first, n)
    with jax.named_scope("head"):
        x = _norm(cfg, x, params["final_norm"]).astype(dtype)
    return x, stats, pool


def _refuse_latent(cfg, program):
    if cfg.walks_runs:
        raise NotImplementedError(
            f"{program} has no form for the walk of runs: an "
            f"attention='mla' model or a layer_types pattern is served "
            f"through the ragged step and the decode programs (no "
            f"speculation)")


# ---------------------------------------------------------------------------
# Chunked continuation
# ---------------------------------------------------------------------------
def paged_continue(cfg: TransformerConfig, params, ids: jnp.ndarray,
                   start_pos: jnp.ndarray, n_new: jnp.ndarray,
                   cache: Dict[str, jnp.ndarray], block_ids: jnp.ndarray,
                   offsets: jnp.ndarray, block_table: jnp.ndarray,
                   block_size: int, topo=None,
                   greedy_window: int = 0,
                   lora=None, adapter_ids=None
                   ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Multi-token continuation of ONE existing sequence in a single pass
    (the reference's chunked prefill over ragged atoms,
    inference/v2/kernels/ragged_ops/atom_builder + blocked_flash): the
    chunk's K/V are scattered into the sequence's cache blocks, then every
    chunk token attends over the sequence's full block table (cached prefix
    + the chunk itself) with causal masking. The engine runs it for the
    n-gram speculation's verify pass (``greedy_window``) and the draft
    model's catch-up; a multi-token put() is a ragged step.

    ids [1, C] (padded chunk); start_pos = tokens already cached; n_new =
    valid tokens in the chunk; block_ids/offsets [C] map chunk position ->
    (cache block, slot), padding -> null block; block_table [MB] is the
    sequence's full table. Returns (last-token logits [V], cache).
    """
    _refuse_latent(cfg, "paged_continue")
    C = ids.shape[1]
    MB = block_table.shape[0]
    ctx = MB * block_size
    nh, nkv, hd = cfg.num_heads, cfg.kv_heads, cfg.head_dim
    params = _deq_nonlayer(params)
    pos = start_pos + jnp.arange(C)                             # [C]
    x = _embed(cfg, params, ids[0], pos)                        # [C, H]
    cos, sin = _rope_at(cfg, pos)
    ctx_pos = jnp.arange(ctx)
    # each chunk token sees cache positions up to and including itself
    mask = ctx_pos[None, :] <= pos[:, None]                     # [C, ctx]

    def attend(lp, ll, l, hn, kc, vc, ksc, vsc):
        q, k, v = _qkv_heads(
            cfg, lp, hn, (C,), cos, sin,
            lambda q, v: _lora_qv(ll, hn, adapter_ids, q, v))
        kc, vc, ksc, vsc = _kv_write_pair(kc, vc, ksc, vsc, l, block_ids,
                                          offsets, k, v)
        with jax.named_scope("attn_kernel"):
            kpages = _kv_read(kc, ksc, l, block_table, nkv,
                              hn.dtype).reshape(ctx, nkv, hd)
            vpages = _kv_read(vc, vsc, l, block_table, nkv,
                              hn.dtype).reshape(ctx, nkv, hd)
            if nkv != nh:
                kpages = jnp.repeat(kpages, nh // nkv, axis=1)
                vpages = jnp.repeat(vpages, nh // nkv, axis=1)
            scores = jnp.einsum("qhd,chd->hqc", q,
                                kpages).astype(jnp.float32)
            scores = scores / jnp.sqrt(jnp.asarray(hd, jnp.float32))
            if cfg.positional == "alibi":
                scores = scores + _alibi_row(cfg, ctx_pos)
            scores = jnp.where(mask[None], scores, NEG_INF)
            probs = jax.nn.softmax(scores, axis=-1).astype(hn.dtype)
            o = jnp.einsum("hqc,chd->qhd", probs,
                           vpages).reshape(C, nh * hd)
        return o, kc, vc, ksc, vsc

    x, cache = _scan_layers(cfg, params, x, cache, lora, topo, attend)
    if greedy_window:
        # speculative verification: greedy token ids for the first
        # ``greedy_window`` fed positions — the projection runs on the
        # sliced window (not the padded bucket) and only [window] int32
        # crosses to host, keeping the decode loop's transfer discipline
        from .sampling import greedy_tokens
        return greedy_tokens(_head(cfg, params, x,
                                   lambda x: x[:greedy_window])), cache
    return _head(cfg, params, x,
                 lambda x: jnp.take(x, n_new - 1, axis=0)), cache


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------
def paged_decode(cfg: TransformerConfig, params, toks: jnp.ndarray,
                 pos: jnp.ndarray, block_tables: jnp.ndarray,
                 cache: Dict[str, jnp.ndarray], active: jnp.ndarray,
                 block_size: int, use_kernel: bool = True, topo=None,
                 lora=None, adapter_ids=None, state_slots=None,
                 window_tables=None, table_runs=None
                 ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """toks/pos/active [N]; block_tables [N, MB]. One token per sequence;
    returns ([N, V] logits, cache). Inactive rows write to the null block
    and produce garbage logits (masked by the caller). ``use_kernel`` runs
    the Pallas paged-attention kernel (kernels/paged_attention.py) instead
    of the materializing gather fallback. An attention='mla' model
    returns (logits, what its expert layers routed, cache): see
    ``_pattern_step``. ``state_slots`` [N]: each row's slot of recurrent
    state, for a model whose layer pattern has linear-attention layers
    (an inactive row reads and writes the null slot). ``window_tables``
    [N, ring blocks]: each row's ring in the window layers' pool, for a
    ``layer_types`` pattern that has such layers. ``table_runs``: how
    the tables lie (``_table_runs``), where a decode window has made it
    for all its steps; made here otherwise."""
    N, MB = block_tables.shape
    if table_runs is None:
        table_runs = _table_runs(cfg, cache, block_tables, window_tables,
                                 use_kernel)
    if cfg.walks_runs:
        # the ragged layout with one token a row (_pattern_step); a
        # model that caches no position writes no block
        blk = jnp.take_along_axis(
            block_tables, (pos // block_size)[:, None], axis=1)[:, 0] \
            if cfg.caches_positions else jnp.zeros_like(pos)
        x, stats, cache = _pattern_step(
            cfg, params, toks, jnp.arange(N, dtype=jnp.int32), pos,
            jnp.where(active, pos + 1, 0), jnp.where(active, blk, 0),
            pos % block_size, block_tables, cache, use_kernel=use_kernel,
            state_slots=state_slots, one_token=True,
            window_tables=window_tables, table_runs=table_runs)
        with jax.named_scope("head"):
            return _logits(cfg, params, x), stats, cache
    nh, nkv, hd = cfg.num_heads, cfg.kv_heads, cfg.head_dim
    ctx = MB * block_size
    params = _deq_nonlayer(params)
    x = _embed(cfg, params, toks, pos)                          # [N, H]
    cos, sin = _rope_at(cfg, pos)                               # [N, half]
    blk = jnp.take_along_axis(block_tables,
                              (pos // block_size)[:, None], axis=1)[:, 0]
    blk = jnp.where(active, blk, 0)
    off = pos % block_size
    ctx_pos = jnp.arange(ctx)
    attn_mask = ctx_pos[None, :] <= pos[:, None]                # [N, ctx]

    def attend(lp, ll, l, hn, kc, vc, ksc, vsc):
        q, k, v = _qkv_heads(
            cfg, lp, hn, (N,), cos, sin,
            lambda q, v: _lora_qv(ll, hn, adapter_ids, q, v))
        kc, vc, ksc, vsc = _kv_write_pair(kc, vc, ksc, vsc, l, blk, off,
                                          k, v)
        with jax.named_scope("attn_kernel"):
            if use_kernel:
                from .kernels.paged_attention import paged_attention
                o = paged_attention(
                    q, kc, vc, l, block_tables, pos + 1,
                    k_scale=None if ksc is None else ksc[l],
                    v_scale=None if vsc is None else vsc[l],
                    runs=table_runs[0]).reshape(N, nh * hd)
            else:
                # gather this sequence's pages:
                # [N, MB, bs, nkv, hd] -> [N, ctx, ..]
                kpages = _kv_read(kc, ksc, l, block_tables, nkv,
                                  hn.dtype).reshape(N, ctx, nkv, hd)
                vpages = _kv_read(vc, vsc, l, block_tables, nkv,
                                  hn.dtype).reshape(N, ctx, nkv, hd)
                if nkv != nh:
                    kpages = jnp.repeat(kpages, nh // nkv, axis=2)
                    vpages = jnp.repeat(vpages, nh // nkv, axis=2)
                scores = jnp.einsum("nhd,nchd->nhc", q,
                                    kpages).astype(jnp.float32)
                scores = scores / jnp.sqrt(jnp.asarray(hd, jnp.float32))
                if cfg.positional == "alibi":
                    scores = scores + _alibi_row(cfg, ctx_pos)[None, :, 0, :]
                scores = jnp.where(attn_mask[:, None, :], scores, NEG_INF)
                probs = jax.nn.softmax(scores, axis=-1).astype(hn.dtype)
                o = jnp.einsum("nhc,nchd->nhd", probs,
                               vpages).reshape(N, nh * hd)
        return o, kc, vc, ksc, vsc

    x, cache = _scan_layers(cfg, params, x, cache, lora, topo, attend)
    return _head(cfg, params, x), cache


# ---------------------------------------------------------------------------
# Ragged unified step (mixed prefill + decode, one launch)
# ---------------------------------------------------------------------------
def paged_ragged_step(cfg: TransformerConfig, params, ids: jnp.ndarray,
                      row_ids: jnp.ndarray, pos: jnp.ndarray,
                      lengths: jnp.ndarray, write_blocks: jnp.ndarray,
                      write_offsets: jnp.ndarray,
                      block_tables: jnp.ndarray, last_index: jnp.ndarray,
                      cache: Dict[str, jnp.ndarray], block_size: int,
                      use_kernel: bool = True, topo=None,
                      lora=None, adapter_ids=None, state_slots=None,
                      window_tables=None
                      ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """One compiled program for a MIXED batch (the Ragged Paged
    Attention layout, kernels/ragged_attention.py): prefill chunks,
    continuations and decode rows arrive as one flat token buffer
    ``ids`` [TB] with per-token descriptors — ``row_ids`` (token ->
    batch row), ``pos`` (absolute cache position), ``lengths`` (causal
    bound = pos+1; 0 for padding) and the KV write-set
    ``write_blocks``/``write_offsets`` — plus per-row ``block_tables``
    [RB, MBw] and ``last_index`` [RB] (flat index of each row's last
    valid token): everything put() is given and everything the
    scheduler composes into a step. Returns ([RB, V] last-token logits
    per row, cache); an attention='mla' model returns (logits, what its expert layers
    routed, cache): see ``_pattern_step``; ``state_slots`` [RB] is each
    row's slot of recurrent state where its layer pattern has
    linear-attention layers (rows packed one after another in row
    order, as ``ragged/batch.pack`` lays them); ``window_tables`` [RB,
    ring blocks] each row's ring in the window layers' pool.

    The new tokens' K/V scatter into the pool inside the scanned layer
    body (padding tokens land in the null block), then every token
    attends over ITS row's block table up to its own causal bound —
    in-chunk causality and cached-prefix attention are the same page
    walk. Padding rows/tokens produce garbage logits the caller
    discards; garbage never reaches live rows because tokens only mix
    through attention, which is row-local by construction."""
    T = ids.shape[0]
    RB, MBw = block_tables.shape
    table_runs = _table_runs(cfg, cache, block_tables, window_tables,
                             use_kernel)
    if cfg.walks_runs:
        x, stats, cache = _pattern_step(
            cfg, params, ids, row_ids, pos, lengths, write_blocks,
            write_offsets, block_tables, cache, use_kernel=use_kernel,
            state_slots=state_slots, window_tables=window_tables,
            table_runs=table_runs)
        with jax.named_scope("head"):
            return _logits(cfg, params, x[last_index]), stats, cache
    ctx = MBw * block_size
    nh, nkv, hd = cfg.num_heads, cfg.kv_heads, cfg.head_dim
    params = _deq_nonlayer(params)
    x = _embed(cfg, params, ids, pos)                            # [T, H]
    cos, sin = _rope_at(cfg, pos)                                # [T, half]
    ctx_pos = jnp.arange(ctx)
    attn_mask = ctx_pos[None, :] < lengths[:, None]              # [T, ctx]
    # multi-tenant LoRA: ``adapter_ids`` arrives PER ROW [RB] (the
    # descriptor layout carries one adapter per sequence); gather it to
    # per token here so the bank lookup inside the scanned layer body is
    # a plain [T] indexed read — padding rows carry slot 0 (base)
    tok_aid = adapter_ids[row_ids] if lora is not None else None

    def attend(lp, ll, l, hn, kc, vc, ksc, vsc):
        q, k, v = _qkv_heads(
            cfg, lp, hn, (T,), cos, sin,
            lambda q, v: _lora_qv(ll, hn, tok_aid, q, v))
        kc, vc, ksc, vsc = _kv_write_pair(kc, vc, ksc, vsc, l,
                                          write_blocks, write_offsets, k, v)
        with jax.named_scope("attn_kernel"):
            if use_kernel:
                from .kernels.ragged_attention import ragged_attention
                o = ragged_attention(
                    q, kc, vc, l, row_ids, lengths, block_tables,
                    k_scale=None if ksc is None else ksc[l],
                    v_scale=None if vsc is None else vsc[l],
                    runs=table_runs[0]).reshape(T, nh * hd)
            else:
                # gather each ROW's pages once, indirect per token: the
                # materializing fallback (parity reference +
                # tp/alibi/quant)
                kpages = _kv_read(kc, ksc, l, block_tables, nkv,
                                  hn.dtype).reshape(RB, ctx, nkv, hd)
                vpages = _kv_read(vc, vsc, l, block_tables, nkv,
                                  hn.dtype).reshape(RB, ctx, nkv, hd)
                ktok = kpages[row_ids]                  # [T, ctx, nkv, hd]
                vtok = vpages[row_ids]
                if nkv != nh:
                    ktok = jnp.repeat(ktok, nh // nkv, axis=2)
                    vtok = jnp.repeat(vtok, nh // nkv, axis=2)
                scores = jnp.einsum("thd,tchd->thc", q,
                                    ktok).astype(jnp.float32)
                scores = scores / jnp.sqrt(jnp.asarray(hd, jnp.float32))
                if cfg.positional == "alibi":
                    scores = scores + _alibi_row(cfg, ctx_pos)[None, :, 0, :]
                scores = jnp.where(attn_mask[:, None, :], scores, NEG_INF)
                probs = jax.nn.softmax(scores, axis=-1).astype(hn.dtype)
                o = jnp.einsum("thc,tchd->thd", probs,
                               vtok).reshape(T, nh * hd)
        return o, kc, vc, ksc, vsc

    x, cache = _scan_layers(cfg, params, x, cache, lora, topo, attend)
    return _head(cfg, params, x, lambda x: x[last_index]), cache  # [RB, V]


# ---------------------------------------------------------------------------
# Fused multi-token decode window
# ---------------------------------------------------------------------------
def paged_decode_window(cfg: TransformerConfig, params, toks: jnp.ndarray,
                        pos: jnp.ndarray, block_tables: jnp.ndarray,
                        cache: Dict[str, jnp.ndarray],
                        steps_left: jnp.ndarray, eos_ids: jnp.ndarray,
                        block_size: int, window: int,
                        rng=None, row_seeds: jnp.ndarray = None,
                        gen_idx0: jnp.ndarray = None,
                        temp: jnp.ndarray = None, topp: jnp.ndarray = None,
                        topk: jnp.ndarray = None,
                        use_kernel: bool = True, topo=None,
                        lora=None, adapter_ids=None, alive=None,
                        state_slots=None, window_tables=None):
    """Up to ``window`` decode steps entirely on device — the answer to
    the dispatch-bound per-token loop (one Python round-trip + [N] int32
    transfer PER TOKEN). One ``lax.while_loop`` runs cache write, paged
    attention, sampling, EOS masking and block-table advancement for K
    steps; the only host traffic per window is the [N, window] int32
    token block (plus the donated cache staying resident).

    Block tables never change on device: block boundaries are arithmetic
    in the token position (``pos // block_size``), so as long as the host
    pre-allocates every block the window can write (``steps_left[i]``
    tokens from ``pos[i]``), advancement is just the existing indexing in
    ``paged_decode``. That pre-allocation is the caller's contract.

    Per-row state: ``toks``/``pos`` [N] are the fed token and its cache
    position; ``steps_left`` [N] caps each row's steps (rows with
    exhausted generation budget or sequence room mask out — K stays a
    compile-time constant across ragged budgets); ``eos_ids`` [N] is the
    per-row stop token (-1 = none). A row that emits its EOS goes
    inactive: the EOS is emitted but never fed back (the same
    last-token-never-fed invariant as the per-token loop), later steps
    write to the null block. The loop exits early when every row is
    inactive.

    Sampling (``rng`` is not None): per-row keys
    ``fold_in(fold_in(rng, row_seeds[i]), gen_idx0[i] + s)`` make each
    row's draw depend only on its own seed and its own generated-token
    index — invariant to batch composition, so fused and per-token
    streams are bit-identical under a fixed seed.

    Returns (tokens [N, window] int32 with -1 in steps a row did not
    take, the rows' state, cache). Emitted tokens form a prefix of each
    row. An attention='mla' model returns (tokens, the rows' state, what
    its expert layers routed over the window's steps, cache), as
    ``paged_decode`` does.

    The rows' state is what the NEXT window of the same rows feeds, so
    that it can be launched before this one's tokens reach the host:
    (token [N], position [N], alive [N] bool). A row's token is the last
    it emitted (not yet fed or cached) and its position the one that
    token takes; ``alive`` is False once the row has emitted its EOS (a
    row that only ran out of ``steps_left`` stays alive). Handed back as
    ``toks`` / ``pos`` / ``alive``, a dead row rides the window as a row
    out of steps does: no write but to the null block, -1 in every step.
    ``gen_idx0`` of that window is the host's arithmetic (a row emits
    ``steps_left`` tokens unless it dies, and a dead row's draw is never
    read). A row's RECURRENT state (``state_slots`` [N]: a model with
    linear-attention layers) needs no place in that tuple: it lives in
    the cache at the row's slot, and the cache is what one window hands
    the next on the device.
    """
    N = toks.shape[0]
    sampled = rng is not None
    if alive is None:
        alive = jnp.ones((N,), bool)
    # how the tables lie: once a window, they do not change inside it
    table_runs = _table_runs(cfg, cache, block_tables, window_tables,
                             use_kernel)

    def body(state):
        s, toks, pos, active, alive, out, moe, cache = state
        logits, *routed, cache = paged_decode(
            cfg, params, toks, pos, block_tables, cache, active, block_size,
            use_kernel=use_kernel, topo=topo, lora=lora,
            adapter_ids=adapter_ids, state_slots=state_slots,
            window_tables=window_tables, table_runs=table_runs)
        moe = [_merge_moe_stats(a, b) for a, b in zip(moe, routed)]
        if sampled:
            from .sampling import fold_in_rows, sample_tokens_rowwise
            keys = fold_in_rows(rng, row_seeds, gen_idx0 + s)
            nxt = sample_tokens_rowwise(logits, keys, temp, topp, topk)
        else:
            from .sampling import greedy_tokens
            nxt = greedy_tokens(logits)
        out = out.at[:, s].set(jnp.where(active, nxt, -1))
        pos = jnp.where(active, pos + 1, pos)
        toks = jnp.where(active, nxt, toks)
        alive = alive & ~(active & (nxt == eos_ids))
        active = active & alive & (s + 1 < steps_left)
        return s + 1, toks, pos, active, alive, out, moe, cache

    def cond(state):
        return (state[0] < window) & jnp.any(state[3])

    # what the window's expert layers routed, merged over its steps: one
    # more output where paged_decode has it (the walk of runs), none else
    moe = [jnp.zeros((4,), jnp.float32)] * cfg.walks_runs
    state = (jnp.asarray(0, jnp.int32), toks, pos,
             (steps_left > 0) & alive, alive,
             jnp.full((N, window), -1, jnp.int32), moe, cache)
    _, toks, pos, _, alive, out, moe, cache = jax.lax.while_loop(
        cond, body, state)
    return (out, (toks, pos, alive), *moe, cache)


# ---------------------------------------------------------------------------
# Speculative decode window (draft-model propose -> target verify, on device)
# ---------------------------------------------------------------------------
def _paged_verify(cfg: TransformerConfig, params, fed: jnp.ndarray,
                  pos0: jnp.ndarray, block_tables: jnp.ndarray,
                  cache: Dict[str, jnp.ndarray], active: jnp.ndarray,
                  block_size: int, use_kernel: bool = True, topo=None,
                  lora=None, adapter_ids=None
                  ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Multi-query target forward for in-window speculation: score the
    ``S = spec_k + 1`` fed tokens of every row in ONE pass. ``fed``
    [N, S] (fed[:, 0] is the row's pending token, fed[:, 1:] the draft's
    proposals); ``pos0`` [N] is fed[:, 0]'s cache position. The fed
    tokens' K/V scatter into each row's blocks at pos0..pos0+S-1
    (inactive rows -> null block), then every fed token attends over its
    row's table up to its own position — the same masked-softmax math as
    :func:`paged_continue`'s verify (pinned bit-identical to the decode
    loop), batched over rows. Returns (greedy ids [N, S] int32, cache):
    ids[:, j] is the target's next token AFTER seeing fed[:, :j+1], which
    is exactly what the plain loop would emit at that step — the accept
    rule compares ids[:, :S-1] against fed[:, 1:]."""
    _refuse_latent(cfg, "the speculative verify pass")
    N, S = fed.shape
    MB = block_tables.shape[1]
    ctx = MB * block_size
    nh, nkv, hd = cfg.num_heads, cfg.kv_heads, cfg.head_dim
    params = _deq_nonlayer(params)
    posm = pos0[:, None] + jnp.arange(S)[None, :]               # [N, S]
    x = _embed(cfg, params, fed, posm)                          # [N, S, H]
    cos, sin = _rope_at(cfg, posm)                              # [N, S, half]
    blkm = jnp.take_along_axis(block_tables, posm // block_size, axis=1)
    blkm = jnp.where(active[:, None], blkm, 0).reshape(N * S)
    offm = (posm % block_size).reshape(N * S)
    ctx_pos = jnp.arange(ctx)
    # each fed token sees cache positions up to and including itself
    mask = ctx_pos[None, None, :] <= posm[:, :, None]           # [N, S, ctx]
    row_ids = jnp.repeat(jnp.arange(N, dtype=jnp.int32), S)     # [N*S]
    lengths = jnp.where(active[:, None], posm + 1, 0).reshape(N * S)

    def attend(lp, ll, l, hn, kc, vc, ksc, vsc):
        def lora_qv(q, v):
            if ll is None:
                return q, v
            # bank gather broadcast over the S fed positions of each row
            flat, aid = hn.reshape(N * S, -1), jnp.repeat(adapter_ids, S)
            return (q + _lora_delta(ll["qa"], ll["qb"], flat,
                                    aid).reshape(q.shape),
                    v + _lora_delta(ll["va"], ll["vb"], flat,
                                    aid).reshape(v.shape))

        q, k, v = _qkv_heads(cfg, lp, hn, (N, S), cos, sin, lora_qv)
        kc, vc, ksc, vsc = _kv_write_pair(
            kc, vc, ksc, vsc, l, blkm, offm,
            k.reshape(N * S, nkv, hd), v.reshape(N * S, nkv, hd))
        with jax.named_scope("attn_kernel"):
            if use_kernel:
                from .kernels.ragged_attention import ragged_attention
                o = ragged_attention(
                    q.reshape(N * S, nh, hd), kc, vc, l, row_ids, lengths,
                    block_tables,
                    k_scale=None if ksc is None else ksc[l],
                    v_scale=None if vsc is None else vsc[l]
                ).reshape(N, S, nh * hd)
            else:
                kpages = _kv_read(kc, ksc, l, block_tables, nkv,
                                  hn.dtype).reshape(N, ctx, nkv, hd)
                vpages = _kv_read(vc, vsc, l, block_tables, nkv,
                                  hn.dtype).reshape(N, ctx, nkv, hd)
                if nkv != nh:
                    kpages = jnp.repeat(kpages, nh // nkv, axis=2)
                    vpages = jnp.repeat(vpages, nh // nkv, axis=2)
                scores = jnp.einsum("nshd,nchd->nhsc", q,
                                    kpages).astype(jnp.float32)
                scores = scores / jnp.sqrt(jnp.asarray(hd, jnp.float32))
                if cfg.positional == "alibi":
                    scores = scores + _alibi_row(cfg, ctx_pos)[None]
                scores = jnp.where(mask[:, None], scores, NEG_INF)
                probs = jax.nn.softmax(scores, axis=-1).astype(hn.dtype)
                o = jnp.einsum("nhsc,nchd->nshd", probs,
                               vpages).reshape(N, S, nh * hd)
        return o, kc, vc, ksc, vsc

    x, cache = _scan_layers(cfg, params, x, cache, lora, topo, attend)
    from .sampling import greedy_tokens
    return greedy_tokens(_head(cfg, params, x)), cache


def paged_spec_decode_window(cfg: TransformerConfig, dcfg: TransformerConfig,
                             params, dparams, toks: jnp.ndarray,
                             pos: jnp.ndarray, block_tables: jnp.ndarray,
                             cache: Dict[str, jnp.ndarray],
                             dcache: Dict[str, jnp.ndarray],
                             steps_left: jnp.ndarray, eos_ids: jnp.ndarray,
                             block_size: int, window: int, spec_k: int,
                             use_kernel: bool = True, topo=None,
                             lora=None, adapter_ids=None):
    """Draft-model speculative decoding fused into the jitted decode
    window: every ``lax.while_loop`` round runs propose(k) -> target-
    verify -> accept-prefix entirely on device, so speculation adds ZERO
    host round-trips on top of the fused window's one [N, window] token
    transfer. Greedy-only (the engine rejects sampling + speculation).

    Per round, for every running row (active and window not yet full):

      1. the DRAFT model proposes ``spec_k`` greedy tokens with
         ``spec_k + 1`` sequential single-token decodes over its OWN KV
         pool sharing the target's block tables (same paged layout, so
         block advancement is the same position arithmetic). The extra
         (k+1)-th feed writes the last proposal's draft K/V so an all-
         accept round leaves no hole in the draft cache; rejected
         positions hold stale K/V that position masking never attends
         and the next round overwrites — rollback is free, exactly like
         the host n-gram path.
      2. the TARGET verifies all ``spec_k + 1`` fed tokens in ONE
         multi-query pass (:func:`_paged_verify`) — K/V written, greedy
         ids returned.
      3. accept the longest matching prefix: ``m = accepted + 1``
         emissions (the +1 is the target's own next token — correction
         on a miss, bonus on an all-accept), truncated by the row's
         remaining window/steps budget and by an emitted EOS.

    ``spec_k`` is a compile-time constant (the draft loop is unrolled),
    bucketed by the engine like the window itself — per-request draft
    lengths ride the steady jit cache instead of growing it.

    The host's pre-allocation contract widens: the window can write up
    to ``steps_left[i] + spec_k`` tokens from ``pos[i]`` (the last
    round's rejected tail), so the caller pre-allocates blocks AND
    leaves ``spec_k`` tokens of sequence room beyond the step budget.

    Returns (tokens [N, window] int32, -1 padded — emissions form a
    prefix of each row; stats [4] int32 = (drafted, accepted,
    miss_rounds, row_rounds); target cache; draft cache).
    """
    N = toks.shape[0]
    S = spec_k + 1
    sidx = jnp.arange(S)
    rows = jnp.arange(N)

    def body(state):
        oi, toks, pos, active, out, cache, dcache, st = state
        run = active & (oi < window)
        # -- 1. draft proposes (unrolled: spec_k is static) -------------
        t, p = toks, pos
        seq = [toks]
        for j in range(S):
            dlogits, dcache = paged_decode(
                dcfg, dparams, t, p, block_tables, dcache, run,
                block_size, use_kernel=use_kernel, topo=topo)
            if j < spec_k:
                from .sampling import greedy_tokens
                t = greedy_tokens(dlogits)
                seq.append(t)
                p = p + 1
        fed = jnp.stack(seq, axis=1)                         # [N, S]
        # -- 2. target verifies every fed token in one pass -------------
        ids_v, cache = _paged_verify(
            cfg, params, fed, pos, block_tables, cache, run, block_size,
            use_kernel=use_kernel, topo=topo, lora=lora,
            adapter_ids=adapter_ids)
        # -- 3. accept the matching prefix + the target's own token -----
        matches = ids_v[:, :spec_k] == fed[:, 1:]            # [N, k]
        acc = jnp.sum(jnp.cumprod(matches.astype(jnp.int32), axis=1),
                      axis=1)                                # [N]
        m = jnp.minimum(acc + 1, jnp.minimum(window - oi, steps_left - oi))
        m = jnp.where(run, jnp.maximum(m, 0), 0)
        # an emitted EOS truncates the acceptance and retires the row
        # (emitted, never fed back — the plain loop's invariant)
        within = sidx[None, :] < m[:, None]
        is_eos = within & (ids_v == eos_ids[:, None])
        any_eos = jnp.any(is_eos, axis=1)
        m = jnp.where(any_eos, jnp.argmax(is_eos, axis=1) + 1, m)
        # -- emit: out[i, oi+j] = ids_v[i, j] for j < m (unrolled; cols
        # past the row's slice land out of bounds and drop) ------------
        for j in range(S):
            col = jnp.where(run & (j < m), oi + j, window)
            out = out.at[rows, col].set(ids_v[:, j], mode="drop")
        # -- advance ----------------------------------------------------
        m_safe = jnp.maximum(m, 1)
        last = jnp.take_along_axis(ids_v, (m_safe - 1)[:, None],
                                   axis=1)[:, 0]
        toks = jnp.where(run, last, toks)
        pos = jnp.where(run, pos + m, pos)
        oi = oi + m
        active = jnp.where(run, (~any_eos) & (oi < steps_left), active)
        drafted, accepted, miss, rounds = st
        st = (drafted + jnp.sum(jnp.where(run, spec_k, 0)),
              accepted + jnp.sum(jnp.maximum(m - 1, 0)),
              miss + jnp.sum((run & (acc == 0)).astype(jnp.int32)),
              rounds + jnp.sum(run.astype(jnp.int32)))
        return oi, toks, pos, active, out, cache, dcache, st

    def cond(state):
        oi, _, _, active, *_ = state
        return jnp.any(active & (oi < window))

    zero = jnp.asarray(0, jnp.int32)
    state = (jnp.zeros(N, jnp.int32), toks, pos, steps_left > 0,
             jnp.full((N, window), -1, jnp.int32), cache, dcache,
             (zero, zero, zero, zero))
    oi, _, _, _, out, cache, dcache, st = jax.lax.while_loop(
        cond, body, state)
    return out, jnp.stack(st), cache, dcache
