"""The table of served blocks, and the helpers their serving tests share.

A row (``Block``) is one configuration of ``benchmark/configs/`` served
at its toy widths on the CPU against the benchmark's plain float32
reference: its toy fields, the ``benchmark.reference_*`` /
``benchmark.weights_*`` modules the configuration names, its seed, its
state manager's sizes, its limits, and what each clause of the contract
(``served_block_contract.py``) is given for it.

How a ``model_config`` PR adds a served block to tier-1:

1. Add a row to ``BLOCKS`` below, read from the configuration's file
   (never edited here): the sizes, the limits with the sentence that
   justifies each, and one entry a clause that applies.
2. A clause that does not apply is left EMPTY in the row (``decode=()``,
   ``kept=None``, ``refusals=None``): the contract makes no case of it.
   The contract never asks a block's name.
3. Start ``test_<block>_serving.py`` with the row's name and the
   contract's clauses (``BLOCK = sb.BLOCKS[...]``,
   ``globals().update(contract.clauses(BLOCK))``): the file then holds
   the block to the reference through engines it builds once
   (``Lender``; ``-k <name>`` runs one block's clauses wherever they
   are), and ``test_served_block_contract.py`` fails for a row that no
   file takes.
4. Below that goes what only the block has (a kernel's forms against its
   scan, a routing identity, its tree and cache leaves, its counters,
   its pattern as the source spells it). Those cases take ``lend`` for
   an engine, the same ones the clauses warmed, and define no engine,
   prompt, parameter or reference helper of their own.
5. A case that must own its engine (a refusal at construction, a
   ``monkeypatch`` under which the program is traced, a mutated tree)
   calls ``sb.engine(row, ...)`` and says why in one line.
"""

import dataclasses
import functools
import gc
import importlib
import itertools
import json
from pathlib import Path
from typing import Callable, Optional

import numpy as np

import jax
import jax.numpy as jnp

from benchmark import run as harness
from deepspeed_tpu.inference.v2 import InferenceEngineV2
from deepspeed_tpu.models import TransformerConfig, TransformerLM

REPO = Path(__file__).resolve().parents[3]

# A float32 engine differs from the reference by the order of its sums
# and by its forms (an absorbed latent, chunks of matmuls in place of
# rank-one updates a token, pages of a ring under an online softmax):
# 2e-5 of the largest logit is five to fifty times what any block reads
# (4e-7 to 3e-6), and every fault below reads over three times it.
F32 = 2e-5
# A bf16 engine rounds every activation to 8 bits: the OPT block's toy
# limit on the served token's gap to the reference's best.
BF16_GAP = 4e-2
# At a hidden width of 64 under ten to twenty sub-layers the bf16 LOGITS
# of the state-keeping blocks read 3e-2 to 4.2e-2 of the largest.
BF16_LOGITS = 1e-1


# what a clause is given
@dataclasses.dataclass(frozen=True)
class Case:
    """One case of a clause: ``spec`` is what ``engine()`` is given
    (``dtype``, ``fields``, ``seed``, ``seqs``, ``budget`` and engine
    options), ``lengths`` the prompts' (the row's where None)."""
    id: str
    spec: dict = dataclasses.field(default_factory=dict)
    limit: float = F32
    lengths: Optional[tuple] = None
    prompt_seed: int = 0
    new: int = 13                   # tokens generated (decode, kept)
    chunks: Optional[int] = None    # prefill chunk steps the put() counts
    impl: Optional[str] = None      # attention_impl, where not the row's
    row_chunk: Optional[int] = None  # a row's share of a step; its ring is
    #                                 the window, that share and one block
    rises: tuple = ()               # counters the case must move
    sampled: bool = False           # generate() samples (a seed of its own)


@dataclasses.dataclass(frozen=True)
class Control:
    """A fault, or a lower precision, that must read over ``over`` times
    the float32 limit on what the sound engine passes. ``patch`` is laid
    on before the engine is built (so the case owns its engine: the
    program is traced under it), ``mutate`` on the built engine."""
    id: str
    spec: dict = dataclasses.field(default_factory=dict)
    over: float = 5.0
    measure: str = "logits"         # or "kept": the state after decoding
    sound: Optional[dict] = None    # the engine that passes; None: not asked
    leaf: Optional[tuple] = None    # (cache leaf, the dtype it then has)
    patch: Optional[Callable] = None
    mutate: Optional[Callable] = None


@dataclasses.dataclass(frozen=True)
class Alone:
    lengths: tuple = (33, 64, 7)
    tol: float = F32
    relative: bool = True


@dataclasses.dataclass(frozen=True)
class Chunked:
    """``parts`` feeds ``lengths`` (the row's where None) in ``chunks``
    steps, ``whole`` in one."""
    lengths: Optional[tuple] = None
    whole: dict = dataclasses.field(default_factory=dict)
    parts: dict = dataclasses.field(default_factory=lambda: {"budget": 32})
    chunks: int = 8


@dataclasses.dataclass(frozen=True)
class Pinned:
    """The programs a greedy and a sampled ``generate()`` of ``lengths``
    on ``lend(**spec)`` launch, by the hash of what each lowers to
    (``lowered``): a PR of the host loop leaves them as they are, one
    that means to change a program pins what it made of it."""
    spec: dict
    lengths: tuple
    programs: dict


@dataclasses.dataclass(frozen=True)
class Kept:
    """What a sequence keeps beside its blocks, against the reference
    after the same tokens: ``shapes`` of ``sequence_state``'s leaves,
    ``held(state)`` and ``wanted(row, tokens)`` a layer first."""
    shapes: dict
    held: Callable
    wanted: Callable


@dataclasses.dataclass(frozen=True)
class Refusals:
    """Engine options refused at construction: the message matches
    ``match`` and then each pair's word."""
    match: str
    pairs: tuple
    errors: tuple = (NotImplementedError,)


@dataclasses.dataclass(frozen=True)
class Refuses:
    """What a built engine and the model's other forwards refuse:
    ``speculation`` and ``draft`` are the words of the two messages
    (None: not asked), ``handoff`` that of ``export_sequence``,
    ``forwards`` the methods of ``TransformerLM`` that must name every
    one of ``words``."""
    speculation: str = "verify pass"
    draft: Optional[str] = None
    handoff: Optional[str] = None
    forwards: tuple = ()
    words: tuple = ()


@dataclasses.dataclass(frozen=True, eq=False)
class Block:
    name: str
    seed: int
    manager: dict                   # the state manager's sizes
    seqs: int = 4
    budget: int = 256
    options: dict = dataclasses.field(
        default_factory=lambda: {"decode_window": 4})
    lengths: tuple = (20, 70, 5)
    impl: str = ""
    leaves: frozenset = frozenset()
    put: tuple = ()
    decode: tuple = ()
    alone: Optional[Alone] = None
    chunked: Optional[Chunked] = None
    handed_on: tuple = ()
    pinned: Optional[Pinned] = None
    kept: Optional[Kept] = None
    controls: tuple = ()
    refusals: Optional[Refusals] = None
    refuses: Refuses = Refuses()

    @functools.cached_property
    def config(self):
        return json.loads(
            (REPO / "benchmark/configs" / f"{self.name}.json").read_text())

    @functools.cached_property
    def toy(self):
        return harness.merge(self.config["fields"],
                             self.config["toy_fields"])

    @functools.cached_property
    def reference(self):
        return importlib.import_module(
            "benchmark." + self.config["reference"])

    @functools.cached_property
    def weights(self):
        return importlib.import_module("benchmark." + self.config["weights"])


# the helpers, once
def _key(value):
    return json.dumps(value, sort_keys=True, default=repr)


@functools.lru_cache(maxsize=None)
def _made(row, fields_key, seed, dtype):
    return row.weights.make(json.loads(fields_key), seed, dtype)


def params(row, fields=None, seed=None, dtype="float32", **_):
    """The benchmark's weights of the row with ``fields`` laid on its
    toy's, made once a (fields, seed, dtype) and never written to."""
    return _made(row, _key({**row.toy, **(fields or {})}),
                 row.seed if seed is None else seed, dtype)


@functools.lru_cache(maxsize=None)
def _logits(row, fields_key, seed, tokens):
    fields = json.loads(fields_key)
    return np.asarray(row.reference.logits(
        params(row, fields, seed), fields, np.frombuffer(tokens, np.int64)))


def reference(row, prompt, fields=None, seed=None, **_):
    """The plain float32 reference's logits at every position of
    ``prompt``, one pass a (fields, seed, prompt). The pass is made on
    the prompt padded with zeros to a multiple of 32 tokens and cut back:
    every reference is causal, a length is a program of the reference's
    too (5 s each to compile), and what the padding moves is the sums'
    order (0 to 4.4e-7 of the largest logit at 70 tokens in 96, brumby's
    normaliser 2.1e-6: a tenth of the float32 limit)."""
    tokens = np.asarray(prompt, np.int64)
    padded = np.zeros(-(-len(tokens) // 32) * 32, np.int64)
    padded[:len(tokens)] = tokens
    return _logits(row, _key({**row.toy, **(fields or {})}),
                   row.seed if seed is None else seed,
                   padded.tobytes())[:len(tokens)]


def prompts(row, lengths=None, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, row.toy["vocab_size"], n)
            for n in (row.lengths if lengths is None else lengths)]


def err(got, want):
    """The largest difference over the largest wanted magnitude."""
    want = np.asarray(want, np.float32)
    return float(np.abs(np.asarray(got, np.float32) - want).max()
                 / np.abs(want).max())


def layer_err(got, want):
    """The worst layer's relative error in the 2-norm."""
    return max(float(np.linalg.norm(g - w) / np.linalg.norm(w))
               for g, w in zip(np.asarray(got), np.asarray(want)))


_UIDS = itertools.count(1000)


def uids(n):
    """``n`` uids no other case has served under."""
    return [next(_UIDS) for _ in range(n)]


def engine(row, dtype="float32", fields=None, program=None, seed=None,
           seqs=None, budget=None, weights=None, **options):
    """A new engine of the row: ``fields`` are laid on the toy's for the
    model and its weights, ``program`` for the program alone (a fault on
    the SAME leaves), ``weights`` replaces the made tree."""
    cfg = TransformerConfig(**{**row.toy, **(fields or {}),
                               **(program or {})})
    return InferenceEngineV2(TransformerLM(cfg), {
        "dtype": dtype, "use_paged_kernel": True, **row.options, **options,
        "state_manager": {
            "max_tracked_sequences": row.seqs if seqs is None else seqs,
            "max_ragged_batch_size": row.budget if budget is None
            else budget, **row.manager}},
        params=params(row, fields, seed, dtype) if weights is None
        else weights)


def _in_use(eng):
    sm = eng.state_manager
    return {"sequences": sm.tracked_sequences(),
            "state slots": sm.state_slots_in_use(),
            "ring blocks": sm.window_blocks_in_use(),
            "free blocks": sm.free_blocks()}


def lowered(eng, call):
    """``{program: [a hash a signature]}`` of the programs ``call()``
    launches on ``eng``: sha256 (16 hex digits) of the text each watched
    jit lowers to (StableHLO, no locations) for the shapes it was
    launched with (the benchmark's recorder, ``runners/generate.py``),
    sorted. What a PR that must not touch a program pins it by; the
    engine's jits are put back."""
    import hashlib
    from benchmark.runners.generate import Programs
    programs = Programs(eng)
    try:
        call()
    finally:
        programs.release()
    out = {}
    for fn, shapes in programs.seen.values():
        out.setdefault(fn.program, []).append(hashlib.sha256(
            fn.lower(*shapes).as_text().encode()).hexdigest()[:16])
    return {program: sorted(hashes) for program, hashes in out.items()}


class Lender:
    """One block's engines: ONE a (dtype, spec), built when first asked
    for, lent to the cases of one module and taken back EMPTY after each
    case (``take_back``); all dropped when the module ends (``drop``),
    before the worker's next block builds its own:
    ``telemetry.memory.offer_executable`` keeps an engine's executables
    alive as long as the engine."""

    def __init__(self, row):
        self.row = row
        self._engines = {}
        self._lent = []

    def lend(self, dtype="float32", **spec):
        key = _key({"dtype": dtype, **spec})
        if key not in self._engines:
            eng = engine(self.row, dtype, **spec)
            self._engines[key] = (eng, _in_use(eng))
        eng, _ = self._engines[key]
        self._lent.append(key)
        return eng

    def take_back(self):
        """Every engine lent since the last call holds no sequence, no
        block and no slot; what a failed case left is flushed first, so
        that it fails alone."""
        left = {}
        for key in self._lent:
            eng, empty = self._engines[key]
            now = _in_use(eng)
            for uid in list(eng.state_manager.seqs):
                eng.flush(uid)
            if now != empty:
                left[key] = now
        self._lent.clear()
        assert not left, f"a borrowed engine came back in use: {left}"

    def drop(self):
        self._engines.clear()
        self._lent.clear()
        _logits.cache_clear()
        _made.cache_clear()
        gc.collect()


# what the state-keeping rows hold, against their references
def _leading(**kw):
    def wanted(row, tokens):
        return row.reference.leading_states(
            params(row), row.toy, np.asarray(tokens), **kw)
    return wanted


def _retention_held(state):
    return np.concatenate([state["retention_state"],
                           state["retention_norm"][..., None]], axis=-1)


def _retention_wanted(row, tokens):
    s, z = row.reference.leading_states(params(row), row.toy,
                                        np.asarray(tokens), layers=2)
    return np.concatenate([np.asarray(s), np.asarray(z)[..., None]], -1)


# faults laid on a program before it is traced
def _bf16_router(monkeypatch):
    """The router's logits rounded to 8 bits."""
    from deepspeed_tpu.moe import sharded_moe
    real = sharded_moe.topk_routing
    monkeypatch.setattr(
        sharded_moe, "topk_routing", lambda logits, *a, **k: real(
            logits.astype(jnp.bfloat16).astype(jnp.float32), *a, **k))


def _bias_in_weights(monkeypatch):
    """The selection bias leaks into the chosen weights."""
    from deepspeed_tpu.moe import sharded_moe

    def routing(logits, k, scoring, bias, normalize, scale, **_):
        topv, topi = jax.lax.top_k(jax.nn.sigmoid(logits) + bias, k)
        return topi, topv / jnp.sum(topv, -1, keepdims=True) * scale
    monkeypatch.setattr(sharded_moe, "topk_routing", routing)


def _no_shared_expert(monkeypatch):
    from deepspeed_tpu.inference.v2 import paged_model
    real = paged_model._moe_experts
    monkeypatch.setattr(
        paged_model, "_moe_experts", lambda cfg, *a, **k: real(
            dataclasses.replace(cfg, moe_shared_experts=0), *a, **k))


def _drop_shared_down(eng):
    """The shared expert's output projection zeroed, in a tree of the
    engine's own (the made one is every other engine's too)."""
    layers = dict(eng.params["layers"])
    layers["shared_down"] = jnp.zeros_like(layers["shared_down"])
    eng.params = {**eng.params, "layers": layers}


def _a_norm_a_half(monkeypatch):
    """The state-space half of a two-mixer layer norms the stream
    itself, under a weight of its own (the shared one times 1.5)."""
    from deepspeed_tpu.inference.v2 import paged_model
    real = paged_model._state_space_sublayer
    monkeypatch.setattr(
        paged_model, "_state_space_sublayer",
        lambda cfg, lp, x, l, cache, rows, use_kernel=True, hn=None: real(
            cfg, {**lp, "attn_norm": lp["attn_norm"] * 1.5}, x, l, cache,
            rows, use_kernel))


def _gate_behind_the_norm(monkeypatch):
    """SiLU(z) multiplies the state-space mixer's output BEHIND its
    grouped norm: inside the sub-layer the SiLU of z (the one of the
    inner width) reads one, and the norm that follows takes the gate on
    its result."""
    from deepspeed_tpu.inference.v2 import paged_model
    from deepspeed_tpu.ops import norms
    real, silu, norm = (paged_model._state_space_sublayer, jax.nn.silu,
                        norms.rms_norm)
    held = {}

    def sublayer(cfg, *a, **k):
        held["width"] = cfg.mamba_d_inner
        try:
            return real(cfg, *a, **k)
        finally:
            held.clear()

    def gate(z):
        if z.ndim != 2 or z.shape[-1] != held.get("width"):
            return silu(z)
        held["gate"] = silu(z)
        return jnp.ones_like(z)

    def normed(x, w, eps):
        gated = held.pop("gate")
        return (norm(x, w, eps).reshape(gated.shape) * gated).reshape(x.shape)
    monkeypatch.setattr(paged_model, "_state_space_sublayer", sublayer)
    monkeypatch.setattr(jax.nn, "silu", gate)
    monkeypatch.setattr(
        norms, "rms_norm", lambda x, w, eps: normed(x, w, eps)
        if "gate" in held else norm(x, w, eps))


def _swap_groups(eng):
    """Head h reads the OTHER group's B and C: the two groups' columns
    of the projection, the taps and their bias change places, in a tree
    of the engine's own."""
    cfg = eng.model.cfg
    di, n = cfg.mamba_d_inner, cfg.mamba_d_state
    assert cfg.mamba_n_groups == 2

    def swapped(leaf, first):
        # [.., B group 0 | B group 1 | C group 0 | C group 1, ..]
        b0, b1, c0, c1 = (leaf[..., first + i * n:first + (i + 1) * n]
                          for i in range(4))
        return jnp.concatenate([leaf[..., :first], b1, b0, c1, c0,
                                leaf[..., first + 4 * n:]], axis=-1)
    stack = dict(eng.params["hybrid_layers"])
    stack["w_in"] = swapped(stack["w_in"], 2 * di)
    stack["conv"] = swapped(stack["conv"], di)
    stack["conv_b"] = swapped(stack["conv_b"], di)
    eng.params = {**eng.params, "hybrid_layers": stack}


def _short_conv_but_for(monkeypatch, *swaps):
    """``_short_conv_sublayer`` as it stands but for ``swaps``, (text,
    what stands there instead), each text once in its source."""
    import inspect
    from deepspeed_tpu.inference.v2 import paged_model
    source = inspect.getsource(paged_model._short_conv_sublayer)
    for text, instead in swaps:
        assert source.count(text) == 1, text
        source = source.replace(text, instead)
    scope = dict(vars(paged_model))
    exec(source, scope)
    monkeypatch.setattr(paged_model, "_short_conv_sublayer",
                        scope["_short_conv_sublayer"])


def _no_output_gate(monkeypatch):
    """A short-convolution mixer's output gate ``C *`` dropped."""
    _short_conv_but_for(monkeypatch, ("y = (c * y).astype(dt)",
                                      "y = y.astype(dt)"))


def _silu_on_the_taps(monkeypatch):
    """SiLU left on the taps, as a short convolution ahead of a bigger
    mixer carries it: in the kernel and in both plain forms."""
    _short_conv_but_for(
        monkeypatch, ('act="none"', 'act="silu"'),
        ('lp["conv"], held)', 'lp["conv"], held, jax.nn.silu)'),
        ("rows.counts)", "rows.counts, jax.nn.silu)"))


def _x_first(eng):
    """A short-convolution mixer's projection read as [x | B | C], the
    state-space mixers' order: the three column blocks of ``w_in``
    rolled by one, in a tree of the engine's own."""
    stack = dict(eng.params["conv_layers"])
    h = eng.model.cfg.hidden_size
    stack["w_in"] = jnp.roll(stack["w_in"], h, axis=-1)
    eng.params = {**eng.params, "conv_layers": stack}


def _head_norms_behind_the_rotation(monkeypatch):
    """q and k are rotated first and RMS-normed a head after: the head
    norms hand their input on as it is and remember their weight, and
    the rotation norms what it has turned."""
    from deepspeed_tpu.inference.v2 import paged_model
    from deepspeed_tpu.ops import norms
    norm, rotate = norms.rms_norm, paged_model._rotate
    held = []

    def head_norm(x, w, eps):
        if x.ndim != 3:
            return norm(x, w, eps)
        held.append((w, eps))
        return x
    monkeypatch.setattr(norms, "rms_norm", head_norm)
    monkeypatch.setattr(
        paged_model, "_rotate",
        lambda x, cos, sin: norm(rotate(x, cos, sin), *held.pop(0)))


def _handed_on(*forms):
    """A greedy and a sampled case of each ``(id, spec, lengths, chunk
    steps)``: what ``generate()`` hands from the prompt's steps to its
    first window, on engines the row's other cases build anyway."""
    return tuple(
        Case(f"{name}-{pick}", spec, lengths=lengths, new=5, chunks=chunks,
             sampled=pick == "sampled")
        for name, spec, lengths, chunks in forms
        for pick in ("greedy", "sampled"))


# two rows of a step of 32 tokens in blocks of 16: 16 each a step, and
# the step to itself once the other row has ended
_IN_STEPS_OF_32 = (("in-chunks", {"budget": 32}, (48, 48), 3),
                   ("rows-end-apart", {"budget": 32}, (40, 20), 3))
# a ring's share of a step is 8 tokens a row
_IN_SHARES_OF_8 = (("one-step", {}, (5, 7, 6), 0),
                   ("in-chunks", {}, (24, 24), 3),
                   ("rows-end-apart", {}, (24, 12), 3))

_STATE_REFUSALS = (
    ({"tensor_parallel_size": 2}, "tensor_parallel_size"),
    ({"max_lora_adapters": 2}, "max_lora_adapters"),
    ({"kv_quant": True}, "kv_quant"),
    ({"quant_bits": 8}, "quant_bits"),
    ({"state_manager": {"enable_prefix_caching": True}},
     "wrong recurrent state"),
    ({"state_manager": {"enable_prefix_caching": True,
                        "enable_kv_spill": True}}, "state slot"))
_BF16_STATE = {"state_dtype": "bfloat16"}
_NO_FLIP = {"dtype": "bfloat16", "fields": {"moe_top_k": 16}}
_SEED_11 = {"dtype": "bfloat16", "seed": 11}
_RING = dict(max_seq_len=160, block_size=8, num_blocks=100)
# decoding 40 tokens behind prompts of 3 to 5 windows wraps a ring twice more
_REUSED = ("inference_window_blocks_reused_total",)
_POOL = dict(max_seq_len=256, block_size=16, num_blocks=60)

# the table
_ROWS = (
    # DeepSeek-V3's block: a latent pool, a leading dense stack, sigmoid
    # scores under a selection-only bias. bf16 on a seed whose routing
    # the rounding does not flip (two experts of eight a token: a flipped
    # choice swaps half the routed output, one seed in twelve flips one).
    # The int8 pool reads far over float32 (the weights carry outlier
    # channels in the latent); a router in bf16 reads 8e-5, a bias of
    # spread 0.02 in weights of 1.25 reads 2.5e-3, a missing shared
    # expert 3e-2 and more.
    Block(
        "joyai-llm-flash", seed=7, budget=64, options={},
        manager=dict(max_seq_len=256, block_size=16, num_blocks=40),
        lengths=(16, 16, 16), impl="pallas:latent",
        leaves=frozenset({"latent"}),
        put=(Case("float32"),
             Case("bfloat16", {"dtype": "bfloat16"}, BF16_GAP)),
        decode=(Case("float32", lengths=(16, 21, 9), prompt_seed=1, new=20),),
        handed_on=_handed_on(("one-step", {}, None, 0)),
        controls=(
            Control("int8-pool", {"kv_quant": True}, 500,
                    leaf=("latent", "int8")),
            Control("bf16-router", over=3, patch=_bf16_router),
            Control("bias-in-weights", over=50, patch=_bias_in_weights),
            Control("no-shared-expert", over=1500,
                    patch=_no_shared_expert)),
        refusals=Refusals("", (
            ({"tensor_parallel_size": 2}, "tensor_parallel_size"),
            ({"quant_bits": 8}, "quant_bits"),
            ({"max_lora_adapters": 2}, "max_lora_adapters"),
            ({"state_manager": {"enable_prefix_caching": True}},
             "enable_prefix_caching"))),
        refuses=Refuses("speculative", forwards=("forward_hidden",),
                        words=("served by",))),
    # granitemoehybrid: Mamba-2 layers with a state a sequence beside one
    # per-head layer a period. float32 reads 1e-6; a state kept in
    # bfloat16 and a router that scores in bfloat16 each read over five
    # times the limit. The toy's hard top-4 of 16 under bf16 swaps an
    # expert on every seed read (0.05 to 0.7), so the bf16 engine is held
    # on a router that cannot flip (top-16 of 16), where seed 5 reads
    # 4.2e-2 (logits) and 7e-3 (the served tokens' gap).
    Block(
        "granite-4.0-h-small", seed=5, manager=_POOL,
        impl="pallas:pipelined",
        leaves=frozenset({"k_full", "v_full", "ssm_state", "ssm_conv"}),
        put=(Case("float32"), Case("bfloat16", _NO_FLIP, BF16_LOGITS)),
        decode=(Case("float32"), Case("bfloat16", _NO_FLIP)),
        alone=Alone(tol=2e-6, relative=False), chunked=Chunked(),
        handed_on=_handed_on(("one-step", {}, None, 0), *_IN_STEPS_OF_32),
        kept=Kept({"ssm_state": (9, 8, 16, 32),
                   "ssm_conv": (9, 3, 8 * 16 + 2 * 32)},
                  lambda state: state["ssm_state"][:3], _leading(layers=3)),
        controls=(Control("bf16-state", {**_BF16_STATE, "budget": 32},
                          sound={}, leaf=("ssm_state", "bfloat16")),
                  Control("bf16-router", patch=_bf16_router)),
        refusals=Refusals("state-space layers", _STATE_REFUSALS),
        refuses=Refuses(handoff="no state slot", forwards=("apply",),
                        words=("mamba layers",))),
    # bailing_hybrid: linear-attention (KDA) layers with a state a
    # sequence beside one latent layer a period. float32 reads 4e-7 to
    # 7e-7; bf16 3e-3 to 1e-2 on a seed whose routing does not flip; a
    # state kept in bfloat16 reads over twenty times the float32 limit
    # after a few dozen tokens.
    Block(
        "ling-3.0-flash", seed=5, manager=_POOL, impl="pallas:latent",
        leaves=frozenset({"latent", "kda_state", "kda_conv"}),
        put=(Case("float32"),
             Case("bfloat16", {"dtype": "bfloat16"}, BF16_GAP)),
        decode=(Case("float32"), Case("bfloat16", {"dtype": "bfloat16"})),
        alone=Alone(), handed_on=_handed_on(("one-step", {}, None, 0)),
        kept=Kept({"kda_state": (7, 4, 16, 16), "kda_conv": (7, 3, 192)},
                  lambda state: state["kda_state"][:3], _leading()),
        controls=(Control("bf16-state", _BF16_STATE, 20, sound={},
                          leaf=("kda_state", "bfloat16")),),
        refusals=Refusals("", (
            ({"tensor_parallel_size": 2}, "tensor_parallel_size"),
            ({"expert_parallel_size": 2}, "expert-parallel"),
            ({"quant_bits": 8}, "quant_bits"),
            ({"max_lora_adapters": 2}, "max_lora_adapters"),
            ({"kv_quant": True}, "kv_quant"),
            ({"state_manager": {"enable_prefix_caching": True}},
             "no recurrent state"),
            ({"state_manager": {"enable_prefix_caching": True,
                                "enable_kv_spill": True}},
             "no state slot")),
            errors=(NotImplementedError, AssertionError)),
        refuses=Refuses("speculative", draft="draft",
                        forwards=("forward_hidden",),
                        words=("linear_attn_period",))),
    # nemotron_h: layers of ONE sub-layer (a Mamba-2 mixer whose B and C
    # are a group's | two-matrix relu^2 experts | per-head attention).
    # float32 reads 3e-6 (the state 6e-7); a state kept in bfloat16 and a
    # dropped shared expert each read over five times the limit. No bf16
    # engine is held: the toy's top-4 of 16 flips (granite's router).
    Block(
        "nemotron-3-nano-30b-a3b", seed=5, manager=_POOL,
        impl="pallas:pipelined",
        leaves=frozenset({"k_full", "v_full", "ssm_state", "ssm_conv"}),
        put=(Case("one-step", chunks=0),
             Case("in-chunks", {"budget": 32}, chunks=8)),
        decode=(Case("float32"),),
        handed_on=_handed_on(("one-step", {}, None, 0), *_IN_STEPS_OF_32),
        kept=Kept({"ssm_state": (7, 8, 16, 32),
                   "ssm_conv": (7, 3, 8 * 16 + 2 * 2 * 32)},
                  lambda state: state["ssm_state"][:3], _leading(layers=3)),
        controls=(Control("bf16-state", {**_BF16_STATE, "budget": 32},
                          sound={}, leaf=("ssm_state", "bfloat16")),
                  Control("no-shared-expert", mutate=_drop_shared_down)),
        refusals=Refusals("state-space layers", _STATE_REFUSALS[:3]
                          + _STATE_REFUSALS[4:5]),
        refuses=Refuses(
            handoff="no state slot",
            forwards=("apply", "forward_hidden", "forward_cached"),
            words=("'moe' layers", "mamba_n_groups",
                   "moe_expert_form='relu2'"))),
    # afmoe: window and full per-head layers in one model, the window
    # layers' keys in a ring, prompts fed in chunks smaller than the
    # window (a row's share of a step of 32 is 8) and larger (128: 32).
    # float32 reads 3e-7 to 5e-7. The int8 control and the ring are the
    # block's own file's.
    Block(
        "trinity-mini", seed=5, budget=32, manager=_RING,
        lengths=(50, 70, 80), impl="pallas:pipelined+window",
        leaves=frozenset({"k_full", "v_full", "k_window", "v_window"}),
        put=(Case("chunks-under-the-window", chunks=10, row_chunk=8),
             Case("chunks-over-the-window", {"budget": 128}, chunks=3,
                  row_chunk=32),
             Case("gather", {"use_paged_kernel": False}, lengths=(50, 33),
                  chunks=7, impl="jnp:gather")),
        decode=(Case("chunks-under-the-window", new=40, row_chunk=8,
                     rises=_REUSED),
                Case("chunks-over-the-window", {"budget": 128}, new=40,
                     row_chunk=32, rises=_REUSED)),
        chunked=Chunked((24, 17), {"budget": 128}, {}, 3),
        handed_on=_handed_on(*_IN_SHARES_OF_8),
        # PR 62's programs, which PR 63 (the prompt's steps launched
        # ahead, the first token picked on the device) left to the hash
        pinned=Pinned({}, (24, 12), {
            "ragged_step": ["116a86210638417b", "690592fec2b62d61"],
            "decode_window_greedy": ["ff68bf23ff9f8730"],
            "decode_window_sample": ["820da7320059d02b"]}),
        refusals=Refusals("layer_types", (
            ({"state_manager": {"enable_prefix_caching": True}},
             "enable_prefix_caching"),
            ({"state_manager": {"enable_prefix_caching": True,
                                "enable_kv_spill": True}},
             "enable_kv_spill"),
            ({"max_lora_adapters": 2}, "max_lora_adapters"),
            ({"quant_bits": 8}, "quant_bits"),
            ({"tensor_parallel_size": 2}, "tensor_parallel_size"))),
        refuses=Refuses(draft="verify pass", forwards=("forward_logits",),
                        words=("served by",))),
    # SmallThinker: a router that reads the mixer's normed input, ReGLU
    # experts, a query group of seven in window and full layers. float32
    # reads 9e-7 to 1.2e-6; a bf16 engine, a router behind the mixer, a
    # SiLU gate, a rotated full layer and an unrotated window layer each
    # read 1e-2 and over on the same weights.
    Block(
        "smallthinker-21ba3b-instruct", seed=5, budget=32, manager=_RING,
        lengths=(50, 70, 80), impl="pallas:pipelined+window",
        leaves=frozenset({"k_full", "v_full", "k_window", "v_window"}),
        put=(Case("float32", chunks=10, row_chunk=8),
             Case("gather", {"use_paged_kernel": False}, lengths=(50, 33),
                  chunks=7, impl="jnp:gather")),
        decode=(Case("float32", new=40, row_chunk=8, rises=_REUSED),),
        handed_on=_handed_on(*_IN_SHARES_OF_8),
        controls=tuple(
            Control(name, spec, 500) for name, spec in (
                ("a-bf16-engine", {"dtype": "bfloat16"}),
                ("the-router-behind-the-mixer",
                 {"program": {"moe_router_ahead": False}}),
                ("a-silu-gate", {"program": {"moe_expert_form": "swiglu"}}),
                ("a-rotated-full-layer",
                 {"program": {"rope_sliding_only": False}}),
                ("an-unrotated-window-layer",
                 {"program": {"positional": "none",
                              "rope_sliding_only": False}})))),
    # falcon_h1: EVERY layer two mixers on one norm (a Mamba-2 mixer with
    # B and C in two groups beside rotated per-head attention, five
    # queries a key/value head), summed under multipliers, ahead of a
    # dense MLP. float32 reads 4e-7 (logits, state, keys); bf16 4e-3 to
    # 6e-3 of the largest logit (no router to flip). The toy keeps what
    # is new: 2 groups, d_state 32 != d_head 16, d_ssm 96 != 2 x 64,
    # every multiplier != 1 and all different. A dropped multiplier, the
    # gate behind the norm, a second norm for one half, a head reading
    # the other group and a state kept in bfloat16 each read over five
    # times the limit.
    Block(
        "falcon-h1-34b-instruct", seed=5, manager=_POOL,
        impl="pallas:pipelined",
        leaves=frozenset({"k_full", "v_full", "ssm_state", "ssm_conv"}),
        put=(Case("one-step", chunks=0),
             Case("two-steps", {"budget": 32}, lengths=(24, 24), chunks=2),
             Case("five-steps", {"budget": 32}, lengths=(100, 24), chunks=5),
             Case("bfloat16", {"dtype": "bfloat16"}, BF16_LOGITS)),
        decode=(Case("float32"), Case("bfloat16", {"dtype": "bfloat16"})),
        alone=Alone(),
        chunked=Chunked((100, 24), {}, {"budget": 32}, 5),
        handed_on=_handed_on(
            ("one-step", {}, None, 0),
            ("in-chunks", {"budget": 32}, (24, 24), 2),
            ("rows-end-apart", {"budget": 32}, (100, 24), 5)),
        # PR 62's programs, left to the hash by PR 63 (trinity's row)
        pinned=Pinned({"budget": 32}, (24, 24), {
            "ragged_step": ["d5312dcbe7fd7d87", "ff0eaf2422fe89c5"],
            "decode_window_greedy": ["1309402e8177d3dc"],
            "decode_window_sample": ["a5856130719c3751"]}),
        kept=Kept({"ssm_state": (4, 6, 16, 32),
                   "ssm_conv": (4, 3, 6 * 16 + 2 * 2 * 32)},
                  lambda state: state["ssm_state"][:3], _leading(layers=3)),
        controls=(
            Control("bf16-state", {**_BF16_STATE, "budget": 32}, sound={},
                    leaf=("ssm_state", "bfloat16")),
            Control("a-dropped-multiplier",
                    {"program": {"ssm_out_scale": 1.0}}),
            Control("the-gate-behind-the-norm", patch=_gate_behind_the_norm),
            Control("a-norm-a-half", patch=_a_norm_a_half),
            Control("the-other-group", mutate=_swap_groups)),
        refusals=Refusals("state-space layers", _STATE_REFUSALS),
        refuses=Refuses(handoff="no state slot", forwards=("apply",),
                        words=("mamba_attention layers",))),
    # brumby: power-retention layers in a model that caches no position.
    # float32 reads 4e-7; a state kept in bfloat16 reads 1e-3 and more of
    # the state after a prompt in three chunk steps and eight one-token
    # updates. bf16 (seed 11) reads to 3e-2 of the largest logit and 1e-2
    # on the served tokens' gap.
    Block(
        "brumby-14b-base", seed=5,
        manager=dict(max_seq_len=256, block_size=16, num_blocks=2),
        impl="none:no-layer-caches-positions",
        leaves=frozenset({"retention_state", "retention_norm"}),
        put=(Case("float32"), Case("bfloat16", _SEED_11, BF16_LOGITS)),
        decode=(Case("float32"), Case("bfloat16", _SEED_11)),
        alone=Alone(), chunked=Chunked(),
        handed_on=_handed_on(("one-step", {}, None, 0), *_IN_STEPS_OF_32),
        kept=Kept({"retention_state": (2, 2, 136, 16),
                   "retention_norm": (2, 2, 136)},
                  _retention_held, _retention_wanted),
        controls=(Control("bf16-state", {**_BF16_STATE, "budget": 32}, 50,
                          measure="kept", sound={"budget": 32},
                          leaf=("retention_state", "bfloat16")),),
        refusals=Refusals("power-retention layers.*no position cached",
                          _STATE_REFUSALS),
        refuses=Refuses(handoff="no state slot", forwards=("apply",),
                        words=("power_retention layers",))),
    # lfm2_moe: a layer whose WHOLE mixer is a doubly gated 3-tap
    # convolution (a row's state: its last two gated inputs) beside one
    # rotated GQA layer with head norms, two leading dense layers whose
    # mixers are conv, then experts under sigmoid scores and a
    # selection-only bias. float32 reads 3e-7 to 7e-7 (logits, state,
    # keys). The toy's hard top-2 of 8 under bf16 swaps an expert
    # (granite's router), so the bf16 engine is held on a router that
    # cannot flip (top-8 of 8). SiLU left on the taps, the output gate
    # dropped, the projection read x first, the bias weighing and the
    # head norms behind the rotation each read over five times the
    # limit. A state kept in bfloat16 is NOT a control here: the state is
    # a copy of two gated inputs, not a sum over tokens, and reads 2e-3
    # of itself whatever the length.
    Block(
        "lfm2-8b-a1b", seed=5, manager=_POOL, impl="pallas:pipelined",
        leaves=frozenset({"k_full", "v_full", "conv_state"}),
        put=(Case("one-step", chunks=0),
             Case("two-steps", {"budget": 32}, lengths=(24, 24), chunks=2),
             Case("five-steps", {"budget": 32}, lengths=(100, 24), chunks=5),
             Case("bfloat16", {"dtype": "bfloat16",
                               "fields": {"moe_top_k": 8}}, BF16_LOGITS)),
        decode=(Case("float32"),),
        alone=Alone(),
        chunked=Chunked((100, 24), {}, {"budget": 32}, 5),
        handed_on=_handed_on(("one-step", {}, None, 0)),
        kept=Kept({"conv_state": (5, 2, 128)},
                  lambda state: state["conv_state"][:2], _leading(layers=2)),
        controls=(
            Control("silu-on-the-taps", patch=_silu_on_the_taps),
            Control("the-output-gate-dropped", patch=_no_output_gate),
            Control("x-first", mutate=_x_first),
            Control("the-bias-weighs", patch=_bias_in_weights),
            Control("the-head-norms-behind-the-rotation",
                    patch=_head_norms_behind_the_rotation)),
        refusals=Refusals("short-convolution layers", _STATE_REFUSALS),
        refuses=Refuses(handoff="no state slot", forwards=("apply",),
                        words=("conv layers",))),
)


BLOCKS = {row.name: row for row in _ROWS}
