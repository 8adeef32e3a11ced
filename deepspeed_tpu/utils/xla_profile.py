"""XLA profiling helpers: traces + collective-overlap analysis.

Two tools for the question the reference answers with its two CUDA streams
(runtime/zero/stage3.py:1151 __allgather_stream / reduce_and_partition
stream): is ZeRO communication overlapped with compute?

1. ``capture_trace(fn, *args, trace_dir=...)``: run fn under
   ``jax.profiler.trace`` — the artifact opens in TensorBoard/XProf and is
   what the NVTX ranges + CommsLogger give on the reference.

2. ``overlap_report(fn, *args)``: static scheduling analysis of the
   OPTIMIZED HLO. XLA's latency-hiding scheduler expresses overlap as async
   collective pairs (``all-gather-start``/``all-gather-done`` etc.) with
   compute scheduled between start and done; a collective whose done
   immediately follows its start is fully EXPOSED (no overlap). The report
   counts async pairs per collective kind and the instruction distance
   between start and done — a device-independent, committable measurement
   of how much latency hiding the compiled program actually has.

3. ``scope_map(compiled)`` / ``scope_phase(op_name)``: which part of the
   training step an instruction of the compiled program belongs to. A
   device trace names an operation by its HLO instruction (``fusion.491``);
   the compiled program's text still carries, per instruction, the
   ``jax.named_scope`` and autodiff path it was traced under
   (``op_name``). The map joins the two, so a trace can be summed by
   phase: forward, recomputed forward, backward, loss head, optimizer,
   gradient reduction, parameter gather. ``serve_phase(op_name)`` is the
   same for a serving program (``telemetry.memory.scopes("ragged_step")``
   and the decode programs'; the scopes of inference/v2/paged_model.py):
   embedding, attention projections, the pool write, the attention
   kernel, MLP, router, experts, head, pick. A phase folds several
   scopes; ``serve_scope(op_name)`` is the innermost scope itself, and
   ``scope_seconds(rows)`` sums a traced call by program and scope.
"""

import re
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import jax

# ---------------------------------------------------------------------------
# scope map: instruction name -> op_name, and op_name -> phase of the step
# ---------------------------------------------------------------------------
_HLO_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$")
_HLO_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_HLO_OP_NAME = re.compile(r'\bop_name="([^"]*)"')
_HLO_REFERENCE = re.compile(r"%([\w.\-]+)")
# a fusion's body and a reducer run as part of the instruction that calls
# them; these opcodes run as no device operation at all
_HLO_CALLS_INNER = re.compile(
    r"\sfusion\(.*\bcalls=%?([\w.\-]+)|\bto_apply=%?([\w.\-]+)")
_HLO_NEVER_RUNS = re.compile(
    r"\s(?:parameter|constant|get-tuple-element|tuple|bitcast)\(")

# the fixed scope names of the step (runtime/engine.py's step builders,
# models/transformer.py, comm/quantized.py, runtime/grad_overlap.py) that
# name a phase by themselves, whatever autodiff wrapped them in
_PHASE_OF_SCOPE = {"loss_head": "loss_head", "optimizer": "optimizer",
                   "grad_clip": "optimizer", "grad_reduce": "grad_reduce",
                   "param_gather": "param_gather"}
_MODEL_SCOPES = ("embed", "layers", "attention", "mlp", "moe")
_SCOPE_WORD = re.compile(
    r"\b(" + "|".join(list(_PHASE_OF_SCOPE) + list(_MODEL_SCOPES)) + r")\b")
PHASES = ("forward", "recompute", "backward", "loss_head", "optimizer",
          "grad_reduce", "param_gather", "other")


def scope_map(compiled) -> Dict[str, str]:
    """``{instruction name: op_name}`` of a jax ``Compiled``, from the
    optimized program's text: the instructions a device trace can show
    as operations of their own. That is every computation but fusion
    bodies and reducers — entry, loop bodies and conditions, branches —
    less the opcodes that only name a value; instruction names are unique
    in a module. A fusion carries its root's ``op_name``. What the
    compiler added itself (an async copy into fast memory, the ``-start``
    half of a collective, a layout conversion) carries none: it is given
    that of the instruction it feeds or, failing that, of the one that
    feeds it. Parses the whole text: call it when a reader asks, not on a
    path that is timed."""
    rows, inner, computation = [], set(), None
    for line in compiled.as_text().splitlines():
        m = _HLO_COMPUTATION.match(line)
        if m:
            computation = m.group(1)
            continue
        for called in _HLO_CALLS_INNER.findall(line):
            inner.update(c for c in called if c)
        m = _HLO_INSTRUCTION.match(line)
        if m and not _HLO_NEVER_RUNS.search(line):
            rows.append((computation, m.group(1), m.group(2)))
    # (a computation is printed before the instruction that calls it)
    named: Dict[str, str] = {}
    unnamed, users = [], {}
    for computation, name, rest in rows:
        if computation in inner:
            continue
        op_name = _HLO_OP_NAME.search(rest)
        operands = _HLO_REFERENCE.findall(rest)
        for operand in operands:
            users.setdefault(operand, []).append(name)
        if op_name:
            named[name] = op_name.group(1)
        else:
            unnamed.append((name, operands))
    for _ in range(3):      # a copy that feeds a copy: chains are short
        for name, operands in unnamed:
            near = [n for n in users.get(name, []) + operands if n in named]
            if name not in named and near:
                named[name] = named[near[0]]
    return named


def scope_phase(op_name: str) -> str:
    """The phase of the training step an ``op_name`` belongs to, one of
    ``PHASES``. A phase scope (``loss_head``, ``optimizer`` with
    ``grad_clip``, ``grad_reduce``, ``param_gather``) wins over the
    autodiff wrapper round it, and the innermost of several wins; the
    rest is told apart by autodiff's own marks: ``rematted_computation``
    is the forward run again under activation checkpointing,
    ``transpose(`` otherwise the backward, ``jvp(`` without it (or a
    model scope with neither, in a forward-only program) the forward."""
    words = _SCOPE_WORD.findall(op_name)
    for word in reversed(words):
        if word in _PHASE_OF_SCOPE:
            return _PHASE_OF_SCOPE[word]
    if "rematted_computation" in op_name:
        return "recompute"
    if "transpose(" in op_name:
        return "backward"
    if "jvp(" in op_name or words:
        return "forward"
    return "other"


# the scope names of a serving program (inference/v2/paged_model.py and
# sampling.py) and the phase each stands for. ``attention`` and
# ``mla_attention`` are the projections, norms and residual add round
# the two scopes nested in them that are phases of their own
_SERVE_PHASE_OF_SCOPE = {
    "embed": "embed", "attention": "attn_proj", "mla_attention": "attn_proj",
    "qkv_proj": "attn_proj", "out_proj": "attn_proj",
    "attn_gate": "attn_proj",
    "kv_write": "kv_write", "attn_kernel": "attn_kernel",
    "mlp": "mlp", "dense_mlp": "mlp", "moe_shared_expert": "mlp",
    "moe_router": "router", "moe_experts": "experts",
    "head": "head", "pick": "pick",
    # a linear-attention layer: its projections, convolution, norm and
    # gate are ``linear``; the recurrence is a phase a form
    "linear_attention": "linear", "kda_proj": "linear",
    "kda_conv": "linear", "kda_out": "linear",
    "kda_chunk": "linear_chunk", "kda_state": "linear_state",
    # a state-space (Mamba-2) layer, likewise: its projections,
    # convolution, gated norm are ``ssm``; the recurrence a phase a form
    "ssm_mixer": "ssm", "ssm_proj": "ssm", "ssm_conv": "ssm",
    "ssm_gate_norm": "ssm", "ssm_out": "ssm",
    "ssm_scan": "ssm_scan", "ssm_state": "ssm_state",
    # a power-retention layer stands under ``attention`` as every
    # per-head mixer (its projections, head norms, rotation and decay
    # gate are ``qkv_proj`` / ``out_proj``: attn_proj); the mixer's core,
    # the state's way out of its slot, the update and its way back, is a
    # phase a form
    "retention_chunk": "retention_chunk",
    "retention_state": "retention_state",
    # a short-convolution layer: its norm and residual add
    # (``short_conv``), its two projections and, between them, both
    # gates, the taps and the state's way out of its slot and back
    "short_conv": "short_conv", "conv_proj": "short_conv",
    "conv_gate": "short_conv", "conv_out": "short_conv",
    # a pattern over latent attention: the second latent kind's
    # projections, norms and residual add stand under a scope of their
    # own (``mla_window_attention``, as ``mla_attention`` the first's),
    # so that the two kinds' ``attn_kernel`` are told apart by their
    # paths; a full layer's indexer (its queries and weights, and its
    # scores against a row's cached keys) and the selection (the top
    # ``index_topk`` of a token's scores) are a phase each
    "mla_window_attention": "attn_proj",
    "indexer": "indexer", "index_select": "index_select"}
_SERVE_SCOPE_WORD = re.compile(
    r"\b(" + "|".join(sorted(_SERVE_PHASE_OF_SCOPE, key=len, reverse=True))
    + r")\b")
SERVE_PHASES = ("embed", "attn_proj", "kv_write", "attn_kernel", "mlp",
                "router", "experts", "head", "pick", "linear",
                "linear_chunk", "linear_state", "ssm", "ssm_scan",
                "ssm_state", "retention_chunk", "retention_state",
                "short_conv", "indexer", "index_select", "other")


def serve_scope(op_name: str) -> str:
    """The innermost scope word of ``_SERVE_PHASE_OF_SCOPE`` an
    ``op_name`` holds (``layers/ssm_mixer/ssm_conv/scatter`` is
    ``ssm_conv``, ``layers/mlp/moe_router/dot_general`` is
    ``moe_router``); ``other`` for a path with none (``layers`` alone:
    the scan's own slicing and counting)."""
    words = _SERVE_SCOPE_WORD.findall(op_name)
    return words[-1] if words else "other"


def serve_phase(op_name: str) -> str:
    """The phase of a serving program an ``op_name`` belongs to, one of
    ``SERVE_PHASES``: that of its innermost scope (:func:`serve_scope`;
    ``layers/attention/kv_write/scatter`` is ``kv_write``); ``other``
    for a path with none."""
    return _SERVE_PHASE_OF_SCOPE.get(serve_scope(op_name), "other")


def scope_seconds(rows) -> Dict[tuple, float]:
    """``{(program family, scope): self seconds}`` of a traced call.
    A family is what a program's launches are summed under: the prompt
    path's one program by its name (``ragged_step``), every decode
    program (``decode_window_greedy``, ``decode_window_sample``,
    ``decode_tok``, ...) as ``decode``, any other by its name, and a
    launch of no named program as ``other``.
    ``rows`` are ``(program, instruction, op_name, seconds)``: the
    program whose launch held the operation (``watch_jit``'s name, None
    outside any), the instruction's name (two programs may both have a
    ``fusion.147``: the rows stay apart by program), the ``op_name``
    THAT program's scope map gives the instruction (None where no map
    knows it, or two signatures' maps disagree: scope ``other``) and the
    operation's self time. Every row lands in exactly one cell, so the
    cells of a family add up to the family's device time."""
    out: Dict[tuple, float] = {}
    for program, _, op_name, seconds in rows:
        family = "other" if not program else \
            "decode" if program.startswith("decode") else program
        key = (family, serve_scope(op_name) if op_name else "other")
        out[key] = out.get(key, 0.0) + seconds
    return out


# async-pair HLO opcodes emitted by the latency-hiding scheduler
_ASYNC_KINDS = ("all-gather", "all-reduce", "reduce-scatter",
                "collective-permute", "all-to-all")

# collective kinds that carry a gradient REDUCTION (the data-parallel
# exchange runtime/grad_overlap.py buckets); all-to-all is included because
# qgZ transports the quantized reduce over it
_REDUCE_KINDS = ("all-reduce", "reduce-scatter", "all-to-all")


def capture_trace(fn: Callable, *args, trace_dir: str, steps: int = 2):
    """Run fn(*args) `steps` times under jax.profiler.trace.

    Telemetry spans (telemetry/trace.py) are mirrored into profiler
    TraceAnnotations for the capture's duration, so ``trace.span(...)``
    regions inside fn line up with device activity in the XProf view."""
    from ..telemetry import trace as ds_trace
    out = None
    prev = ds_trace._xla_annotations
    ds_trace.enable_xla_annotations(True)
    try:
        with jax.profiler.trace(trace_dir):
            for _ in range(steps):
                out = fn(*args)
            jax.block_until_ready(out)
    finally:
        ds_trace.enable_xla_annotations(prev)
    return out


@dataclass
class OverlapReport:
    total_instructions: int = 0
    sync_collectives: Dict[str, int] = field(default_factory=dict)
    async_pairs: Dict[str, int] = field(default_factory=dict)
    # per kind: list of instruction distances between -start and -done
    distances: Dict[str, List[int]] = field(default_factory=dict)

    @property
    def exposed_pairs(self) -> int:
        """Pairs with NOTHING scheduled between start and done."""
        return sum(1 for ds in self.distances.values() for d in ds if d <= 1)

    @property
    def total_pairs(self) -> int:
        return sum(self.async_pairs.values())

    @property
    def exposed_fraction(self) -> float:
        """Fraction of async collectives with zero overlap window. Sync
        (non-async) collectives are fully exposed by construction and are
        counted too."""
        n_sync = sum(self.sync_collectives.values())
        total = self.total_pairs + n_sync
        return (self.exposed_pairs + n_sync) / total if total else 0.0

    def summary(self) -> str:
        lines = [f"HLO instructions: {self.total_instructions}"]
        for kind in sorted(set(self.async_pairs) | set(self.sync_collectives)):
            ds = self.distances.get(kind, [])
            avg = sum(ds) / len(ds) if ds else 0.0
            lines.append(
                f"  {kind:<20} async={self.async_pairs.get(kind, 0):>3} "
                f"sync={self.sync_collectives.get(kind, 0):>3} "
                f"avg start->done distance={avg:.1f} instrs")
        lines.append(f"  exposed fraction: {self.exposed_fraction:.2%} "
                     f"({self.exposed_pairs}/{self.total_pairs} async pairs "
                     f"with empty overlap window)")
        return "\n".join(lines)


def overlap_report_from_compiled(compiled) -> OverlapReport:
    """Analyze an already-compiled executable. Prefers the runtime
    executable's post-scheduling modules (where the latency-hiding
    scheduler's async start/done pairs live) over the pre-scheduling
    as_text()."""
    texts = [m.to_string() for m in compiled.runtime_executable().hlo_modules()] \
        if hasattr(compiled, "runtime_executable") else [compiled.as_text()]
    return analyze_hlo("\n".join(texts))


def overlap_report(fn: Callable, *args, **kwargs) -> OverlapReport:
    """Compile fn(*args) and analyze collective scheduling in the optimized
    HLO (see module docstring)."""
    compiled = jax.jit(fn).lower(*args, **kwargs).compile()
    return overlap_report_from_compiled(compiled)


_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
                "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
                "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8}


@dataclass
class TpuOverlapReport:
    """Overlap report for TPU-backend HLO (AOT-compiled via a topology
    description or on a real chip).

    The TPU backend does not use ``all-gather-start``/``done`` pairs; its
    latency hiding is Async Collective Fusion: each overlapped collective is
    cloned into ``%async_collective_fusion.N`` computations bracketed by
    ``AsyncCollectiveStart``/``AsyncCollectiveDone`` custom-calls, tied
    together by a ``chain_id`` frontend attribute, with compute scheduled
    between the barrier flags. A collective with NO chain runs synchronously
    on the tensorcore — that is the exposed set (the reference exposes the
    same failure as a stall on its __allgather_stream, stage3.py:1151)."""

    # per collective kind: logical (channel-deduped) counts
    async_channels: Dict[str, int] = field(default_factory=dict)
    bare_channels: Dict[str, int] = field(default_factory=dict)
    async_bytes: int = 0
    bare_bytes: int = 0
    chains: int = 0
    # every exposed collective, largest first: {kind, bytes, op} — `op` is
    # the tail of the op_name metadata so the source op is identifiable
    bare_ops: List[Dict[str, Any]] = field(default_factory=list)

    @property
    def total_channels(self) -> int:
        return (sum(self.async_channels.values())
                + sum(self.bare_channels.values()))

    @property
    def exposed_fraction(self) -> float:
        """Fraction of logical collectives NOT covered by an async chain."""
        total = self.total_channels
        return sum(self.bare_channels.values()) / total if total else 0.0

    @property
    def exposed_bytes_fraction(self) -> float:
        total = self.async_bytes + self.bare_bytes
        return self.bare_bytes / total if total else 0.0

    @property
    def param_gather_exposed_fraction(self) -> float:
        """Exposed fraction of the ZeRO-3 hot path specifically: all-gathers
        that feed matmuls (parameter gathers, op_name ``.../dot_general``)
        vs the async chains. The embedding/loss-head collectives — one per
        step, inside the chunked-loss loop where ACF cannot reach — are
        excluded here and reported via bare_ops/exposed_bytes_fraction."""
        bare_param = sum(1 for b in self.bare_ops
                         if b["kind"] == "all-gather"
                         and b["op"].endswith("dot_general"))
        # denominator: all-gather chains only — counting grad reduce
        # chains here would dilute the param-gather verdict
        total = self.async_channels.get("all-gather", 0) + bare_param
        return bare_param / total if total else 0.0

    @property
    def grad_reduce_exposed_fraction(self) -> float:
        """Exposed fraction of the gradient-reduction side specifically:
        reduce-kind collectives (all-reduce / reduce-scatter / all-to-all)
        NOT covered by an async chain. The companion of
        ``param_gather_exposed_fraction`` — together they split the ZeRO
        exchange into its gather and reduce halves."""
        bare = sum(v for k, v in self.bare_channels.items()
                   if k in _REDUCE_KINDS)
        chained = sum(v for k, v in self.async_channels.items()
                      if k in _REDUCE_KINDS)
        total = bare + chained
        return bare / total if total else 0.0

    def to_dict(self) -> Dict[str, Any]:
        return {"async_channels": dict(self.async_channels),
                "bare_channels": dict(self.bare_channels),
                "async_chains": self.chains,
                "async_bytes": self.async_bytes,
                "bare_bytes": self.bare_bytes,
                "exposed_fraction": self.exposed_fraction,
                "exposed_bytes_fraction": self.exposed_bytes_fraction,
                "param_gather_exposed_fraction":
                    self.param_gather_exposed_fraction,
                "grad_reduce_exposed_fraction":
                    self.grad_reduce_exposed_fraction,
                "bare_ops": list(self.bare_ops)}

    def summary(self) -> str:
        lines = []
        for kind in sorted(set(self.async_channels) | set(self.bare_channels)):
            lines.append(
                f"  {kind:<20} async={self.async_channels.get(kind, 0):>3} "
                f"bare={self.bare_channels.get(kind, 0):>3}")
        lines.append(
            f"  exposed: {self.exposed_fraction:.2%} by count, "
            f"{self.exposed_bytes_fraction:.2%} by bytes "
            f"({self.chains} async chains)")
        return "\n".join(lines)


def _shape_bytes(shape_str: str) -> int:
    """Bytes of an HLO result shape. Combined collectives have TUPLE
    shapes (``(f32[4096], f32[8192]) all-reduce(...)``) — sum the
    elements so they don't silently contribute zero."""
    total = 0
    for m in re.finditer(r"(\w+)\[([\d,]*)\]", shape_str):
        if m.group(1) not in _DTYPE_BYTES:
            continue
        n = 1
        for d in m.group(2).split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[m.group(1)]
    return total


def analyze_hlo_tpu(hlo: str) -> TpuOverlapReport:
    """Classify every logical collective in TPU-backend HLO as async
    (ACF-chained) or bare/synchronous.

    Deduplication: ACF clones one collective into the start fusion, the done
    fusion, and fusion clones, all sharing a ``chain_id`` — chained logical
    collectives are therefore counted per distinct chain. Bare collectives
    are deduplicated by (kind, channel_id, shape); XLA may reuse a channel
    across structurally identical ops, so the bare count is a lower bound
    (conservative in the exposed direction only if read per-kind — use the
    byte totals for weighting)."""
    rep = TpuOverlapReport()
    chains: Dict[str, Dict[str, Any]] = {}
    bare: Dict[tuple, int] = {}
    for line in hlo.splitlines():
        m = re.search(
            r"%(all-gather|all-reduce|reduce-scatter|all-to-all|"
            r"collective-permute)\.(\d+) = (\S+)", line)
        if not m:
            continue
        kind, opid, shape = m.group(1), m.group(2), m.group(3)
        ch = re.search(r'chain_id="(\d+)"', line)
        if ch:
            ent = chains.setdefault(ch.group(1), {"kind": kind, "bytes": 0})
            ent["bytes"] = max(ent["bytes"], _shape_bytes(shape))
        else:
            cm = re.search(r"channel_id=(\d+)", line)
            key = (kind, cm.group(1) if cm else f"op{opid}", shape)
            om = re.search(r'op_name="([^"]+)"', line)
            prev = bare.get(key)
            ent = {"bytes": _shape_bytes(shape),
                   "op": om.group(1).split("/")[-1] if om else "?"}
            if prev is None or ent["bytes"] > prev["bytes"]:
                bare[key] = ent
    for ent in chains.values():
        rep.async_channels[ent["kind"]] = \
            rep.async_channels.get(ent["kind"], 0) + 1
        rep.async_bytes += ent["bytes"]
    for (kind, _, _), ent in bare.items():
        rep.bare_channels[kind] = rep.bare_channels.get(kind, 0) + 1
        rep.bare_bytes += ent["bytes"]
        rep.bare_ops.append({"kind": kind, "bytes": ent["bytes"],
                             "op": ent["op"]})
    rep.bare_ops.sort(key=lambda b: -b["bytes"])
    rep.chains = len(chains)
    return rep


def tpu_overlap_report_from_compiled(compiled) -> TpuOverlapReport:
    texts = [m.to_string() for m in compiled.runtime_executable().hlo_modules()] \
        if hasattr(compiled, "runtime_executable") else [compiled.as_text()]
    return analyze_hlo_tpu("\n".join(texts))


@dataclass
class GradExchangeReport:
    """Overlap verdict for the GRADIENT exchange specifically.

    Covers (a) all-reduce / reduce-scatter collectives anywhere in the
    program carrying at least ``_GRAD_MIN_BYTES`` (a monolithic GSPMD
    reduction shows up here; the scalar loss-pmean / grad-norm /
    grads_finite reduces do not) and (b) collective-permute / all-gather /
    all-to-all ops whose metadata source points into the gradient
    machinery (``runtime/grad_overlap.py`` rings, ``comm/quantized.py``
    qgZ transport) — forward-path all-to-alls (Ulysses, MoE dispatch) are
    excluded. A sync op is exposed by definition; an async start/done
    pair is exposed when NOTHING is scheduled inside its window. Works on
    both the TPU backend's scheduled HLO (ppermute start/done pairs) and
    the CPU backend's (sync collectives).
    """

    total: int = 0
    exposed: int = 0
    sync_ops: Dict[str, int] = field(default_factory=dict)
    async_ops: Dict[str, int] = field(default_factory=dict)
    distances: Dict[str, List[int]] = field(default_factory=dict)

    @property
    def exposed_fraction(self) -> float:
        return self.exposed / self.total if self.total else 0.0

    def to_dict(self) -> Dict[str, Any]:
        ds = [d for v in self.distances.values() for d in v]
        return {"total": self.total, "exposed": self.exposed,
                "exposed_collective_fraction": self.exposed_fraction,
                "sync_ops": dict(self.sync_ops),
                "async_ops": dict(self.async_ops),
                "median_overlap_window": (sorted(ds)[len(ds) // 2]
                                          if ds else 0)}


_GRAD_SOURCE_HINTS = ("grad_overlap", "comm/quantized")
# reduce-kind collectives smaller than this carry bookkeeping scalars
# (loss pmean, grads_finite, grad-norm), not gradient bytes
_GRAD_MIN_BYTES = 4096


def analyze_grad_exchange(hlo: str) -> GradExchangeReport:
    """Classify every gradient-exchange collective as exposed/overlapped
    (see GradExchangeReport). Walks the scheduled instruction stream in
    order; the distance between an async start and its done is the
    overlap window the scheduler actually created."""
    rep = GradExchangeReport()
    lines = [l.strip() for l in hlo.splitlines()
             if re.match(r"^\s*(ROOT\s+)?%?[\w.\-]+\s*=", l)]
    starts: Dict[str, tuple] = {}
    reduce_kinds = {"all-reduce", "reduce-scatter"}
    sourced_kinds = {"collective-permute", "all-gather", "all-to-all"}
    for pos, line in enumerate(lines):
        name_m = re.match(r"^(?:ROOT\s+)?%?([\w.\-]+)\s*=", line)
        if not name_m:
            continue
        var = name_m.group(1)
        for kind in _ASYNC_KINDS:
            included = (
                (kind in reduce_kinds
                 and _shape_bytes(line) >= _GRAD_MIN_BYTES)
                or (kind in sourced_kinds
                    and any(h in line for h in _GRAD_SOURCE_HINTS)))
            if re.search(rf"\b{kind}-start\(", line):
                if included:
                    starts[var] = (kind, pos)
                    rep.async_ops[kind] = rep.async_ops.get(kind, 0) + 1
                    rep.total += 1
            elif re.search(rf"\b{kind}-done\(", line):
                for tok in re.findall(r"%([\w.\-]+)", line):
                    if tok in starts:
                        kind0, p0 = starts.pop(tok)
                        d = pos - p0
                        rep.distances.setdefault(kind0, []).append(d)
                        if d <= 1:
                            rep.exposed += 1
                        break
            elif re.search(rf"\b{kind}\(", line):
                if included:
                    rep.sync_ops[kind] = rep.sync_ops.get(kind, 0) + 1
                    rep.total += 1
                    rep.exposed += 1
    # a start whose done we failed to locate gives no overlap evidence:
    # count it exposed (conservative) rather than silently overlapped
    rep.exposed += len(starts)
    return rep


def grad_exchange_report_from_compiled(compiled) -> GradExchangeReport:
    texts = [m.to_string() for m in compiled.runtime_executable().hlo_modules()] \
        if hasattr(compiled, "runtime_executable") else [compiled.as_text()]
    return analyze_grad_exchange("\n".join(texts))


def analyze_hlo(hlo: str) -> OverlapReport:
    rep = OverlapReport()
    # walk the entry computation's instruction stream in order
    lines = [l.strip() for l in hlo.splitlines()
             if re.match(r"^\s*(ROOT\s+)?%?[\w.\-]+\s*=", l)]
    rep.total_instructions = len(lines)
    starts: Dict[str, tuple] = {}   # var name -> (kind, position)
    for pos, line in enumerate(lines):
        name_m = re.match(r"^(?:ROOT\s+)?%?([\w.\-]+)\s*=", line)
        if not name_m:
            continue
        var = name_m.group(1)
        for kind in _ASYNC_KINDS:
            if re.search(rf"\b{kind}-start\(", line):
                starts[var] = (kind, pos)
                rep.async_pairs[kind] = rep.async_pairs.get(kind, 0) + 1
            elif re.search(rf"\b{kind}-done\(", line):
                # operand var name: post-scheduling HLO spells the full
                # tuple SHAPE before the operand (%foo-done((f32[..], ..)
                # %foo-start.3)), so scan every %token for a known start
                for tok in re.findall(r"%([\w.\-]+)", line):
                    if tok in starts:
                        kind0, p0 = starts.pop(tok)
                        rep.distances.setdefault(kind0, []).append(pos - p0)
                        break
            elif re.search(rf"\b{kind}\(", line):
                rep.sync_collectives[kind] = \
                    rep.sync_collectives.get(kind, 0) + 1
    return rep
