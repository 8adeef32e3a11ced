"""The generation step measured from inside, on the CPU toy engines (the
per-head block and the latent one): a ``generate()`` call's leaf spans
tile its root span; every serving jit is named for its program; a
program's scope map is offered at no cost and holds every scope word;
``serve_phase`` reads a path as a phase; and scopes are metadata."""

import contextlib
import json
import re
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import run as harness
from benchmark import weights_joyai
from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                        RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.config_v2 import DSStateManagerConfig
from deepspeed_tpu.models import TransformerConfig, TransformerLM
from deepspeed_tpu.telemetry import memory, trace
from deepspeed_tpu.telemetry.watchdog import WatchedFunction
from deepspeed_tpu.utils import xla_profile
from deepspeed_tpu.utils.xla_profile import (SERVE_PHASES, scope_seconds,
                                             serve_phase, serve_scope)

REPO = Path(__file__).resolve().parents[3]
JOYAI = json.loads(
    (REPO / "benchmark/configs/joyai-llm-flash.json").read_text())
TOY_LATENT = harness.merge(JOYAI["fields"], JOYAI["toy_fields"])
NEW_TOKENS = 20

# the leaves of a generate() call: what docs/TELEMETRY.md lists
# (a dispatch span's leaves are its two parts: the upload and the call)
LEAVES = {"gen_admit", "ragged_pack", "ragged_upload", "ragged_call",
          "ragged_fetch", "ragged_bookkeeping", "gen_first_token",
          "gen_schedule", "window_assemble", "window_upload",
          "window_call", "window_fetch", "window_bookkeeping", "gen_flush"}
# the leaves that say which program they launched
CALLS = {"ragged_call": {"ragged_step"},
         "window_call": {"decode_window_greedy", "decode_window_sample"}}
PER_TOKEN_LEAVES = {"step_assemble", "step_dispatch", "step_fetch",
                    "step_bookkeeping"}
# the scope words a program of each block must hold an instruction of
PER_HEAD_WORDS = {"embed", "layers", "attention", "qkv_proj", "kv_write",
                  "attn_kernel", "out_proj", "mlp", "head"}
LATENT_WORDS = {"embed", "layers", "mla_attention", "kv_write",
                "attn_kernel", "mlp", "moe_router", "moe_experts",
                "moe_shared_expert", "dense_mlp", "head"}


def _per_head(tiny, **kw):
    model, params = tiny
    return InferenceEngineV2(
        model, RaggedInferenceEngineConfig(
            state_manager=DSStateManagerConfig(
                max_tracked_sequences=8, max_seq_len=128, num_blocks=65,
                block_size=16), dtype="float32", **kw), params=params)


def _latent(**kw):
    return InferenceEngineV2(
        TransformerLM(TransformerConfig(**TOY_LATENT)),
        {"dtype": "float32", "use_paged_kernel": True, **kw,
         "state_manager": {"max_tracked_sequences": 4,
                           "max_ragged_batch_size": 64, "max_seq_len": 256,
                           "block_size": 16, "num_blocks": 40}},
        params=weights_joyai.make(TOY_LATENT, 7, "float32"))


def _prompts(vocab, n=3, length=12):
    rng = np.random.default_rng(0)
    return [rng.integers(0, vocab, length) for _ in range(n)]


@pytest.fixture(params=["per_head", "latent"])
def engine(request, tiny_model_128):
    """(engine, prompts, the scope words its programs must hold)."""
    memory.reset()
    if request.param == "latent":
        eng = _latent()
        return eng, _prompts(TOY_LATENT["vocab_size"]), LATENT_WORDS
    eng = _per_head(tiny_model_128)
    return eng, _prompts(eng.model.cfg.vocab_size), PER_HEAD_WORDS


def _call_spans(eng, prompts, **kw):
    """The spans of one warm ``generate()`` call: (root, the rest)."""
    eng.generate(prompts, max_new_tokens=NEW_TOKENS, **kw)     # compiles
    trace.clear()
    eng.generate(prompts, max_new_tokens=NEW_TOKENS, **kw)
    ring = trace.export()
    roots = [s for s in ring if s["name"] == "generate"]
    assert len(roots) == 1
    return roots[0], [s for s in ring if s is not roots[0]]


USAGE = {"cpu_s", "runq_s", "nvcsw", "nivcsw", "majflt"}


def _own(attrs):
    """A launch span's or a root's attrs less the host thread's usage,
    which every one of them carries (telemetry/collector.py)."""
    assert USAGE <= set(attrs), attrs
    return {k: v for k, v in attrs.items() if k not in USAGE}


# ---------------------------------------------------------------------------
# host: no part of generate() runs outside a span
# ---------------------------------------------------------------------------
def test_leaf_spans_tile_the_call_and_reach_its_root(engine):
    eng, prompts, _ = engine
    root, spans = _call_spans(eng, prompts)
    assert root["parent"] is None
    assert _own(root["attrs"]) == {"rows": len(prompts),
                                   "max_new_tokens": NEW_TOKENS}
    by_id = {s["id"]: s for s in spans + [root]}
    for s in spans:
        at = s
        while at["parent"] is not None:
            at = by_id[at["parent"]]
        assert at is root, s
    leaves = [s for s in spans if s["name"] in LEAVES]
    assert {s["name"] for s in leaves} == LEAVES
    for s in leaves:
        if s["name"] in CALLS:
            assert set(s["attrs"]) == {"program"}
            assert s["attrs"]["program"] in CALLS[s["name"]]
        else:
            assert "attrs" not in s
    # a leaf holds no span: what the leaves cover is counted once
    assert not {s["parent"] for s in spans} & {s["id"] for s in leaves}
    covered = sum(s["duration_s"] for s in leaves)
    assert covered >= 0.99 * root["duration_s"], (covered, root)
    assert covered <= root["duration_s"]


def test_the_coarse_spans_keep_name_extent_and_attrs(engine):
    eng, prompts, _ = engine
    # the extent is the children's: uploads and launch, then the wait
    # (for what was launched BEFORE: the tokens of the window before a
    # window, the chunk step before a ragged step; so a call's first
    # has none and its last is fetched under the root). What a
    # span's children leave uncovered is the host between them, which
    # beside five other test workers is now and then a preemption: the
    # best of three calls says whether the extent is the children's
    uncovered = []
    for _ in range(3):
        _, spans = _call_spans(eng, prompts)
        step = [s for s in spans if s["name"] == "ragged_step"]
        windows = [s for s in spans if s["name"] == "decode_window"]
        assert len(step) == 1 and len(windows) == -(-(NEW_TOKENS - 1) // 8)
        assert _own(step[0]["attrs"]) == {"rows": 3, "tokens": 36,
                                          "uids": [0, 1, 2]}
        # a span a window launched; all but a call's first were queued
        # behind the one before (generate() launches ahead)
        for i, w in enumerate(windows):
            assert _own(w["attrs"]) == {
                "batch": 3, "window": 8, "ahead": int(i > 0),
                "uids": [0, 1, 2]}
        worst = 0.0
        for outer, names in (
                (step[0], ("ragged_dispatch", "ragged_fetch")),
                (windows[0], ("window_assemble", "window_dispatch")),
                (windows[1], ("window_assemble", "window_dispatch",
                              "window_fetch"))):
            inner = [s for s in spans if s["parent"] == outer["id"]]
            assert tuple(s["name"] for s in sorted(
                inner, key=lambda s: s["start"])) == names
            # (a span with no wait in it is too short to say much: a
            # call's first window, and its one ragged step, whose wait
            # is the first tokens' arrival under gen_first_token)
            if outer is windows[1]:
                worst = max(worst, 1.0 - sum(
                    s["duration_s"] for s in inner) / outer["duration_s"])
        uncovered.append(worst)
        if worst <= 0.02:
            break
    assert min(uncovered) <= 0.02, uncovered
    fetches = [s for s in spans if s["name"] == "window_fetch"]
    assert len(fetches) == len(windows)
    assert [s["parent"] for s in fetches] \
        == [w["id"] for w in windows[1:]] + [windows[0]["parent"]]
    # one gen_schedule before every window, one that finds no window to
    # queue behind the last, and one that ends the loop
    assert sum(s["name"] == "gen_schedule" for s in spans) \
        == len(windows) + 2


def test_the_per_token_path_has_the_same_leaves(tiny_model_128):
    eng = _per_head(tiny_model_128, decode_window=1)
    root, spans = _call_spans(eng, _prompts(eng.model.cfg.vocab_size))
    steps = [s for s in spans if s["name"] == "decode_step"]
    assert len(steps) == NEW_TOKENS - 1
    inner = [s["name"] for s in sorted(
        (s for s in spans if s["parent"] == steps[0]["id"]),
        key=lambda s: s["start"])]
    assert inner == ["step_assemble", "step_dispatch", "step_fetch"]
    leaves = [s for s in spans
              if s["name"] in LEAVES | PER_TOKEN_LEAVES]
    assert sum(s["duration_s"] for s in leaves) \
        >= 0.99 * root["duration_s"]


@pytest.fixture(scope="module")
def dispatches(tiny_model_128):
    """The spans of one warm greedy call and of one warm sampled call of
    ONE per-head engine (a module's: an engine a test is too many
    resident executables a worker): ``{id: span}``."""
    memory.reset()
    eng = _per_head(tiny_model_128)
    prompts = _prompts(eng.model.cfg.vocab_size)
    spans = _call_spans(eng, prompts)[1] \
        + _call_spans(eng, prompts, temperature=0.8, seed=3)[1]
    return {s["id"]: s for s in spans}


@pytest.mark.parametrize("outer,upload,call", [
    ("ragged_dispatch", "ragged_upload", "ragged_call"),
    ("window_dispatch", "window_upload", "window_call")])
def test_a_dispatch_holds_its_upload_and_its_call(dispatches, outer,
                                                  upload, call):
    """Every dispatch span holds exactly its two parts, in that order,
    inside its own extent: the gap metrics lay idle time over either the
    parent or the children, never both."""
    parents = [s for s in dispatches.values() if s["name"] == outer]
    assert len(parents) >= 2
    for p in parents:
        inner = sorted((s for s in dispatches.values()
                        if s["parent"] == p["id"]),
                       key=lambda s: s["start"])
        assert [s["name"] for s in inner] == [upload, call]
        assert inner[0]["start"] >= p["start"]
        assert inner[0]["start"] + inner[0]["duration_s"] \
            <= inner[1]["start"]
        assert inner[1]["start"] + inner[1]["duration_s"] \
            <= p["start"] + p["duration_s"]
        assert "attrs" not in inner[0]
        assert not [s for s in dispatches.values()
                    if s["parent"] in (inner[0]["id"], inner[1]["id"])]


def test_a_call_span_names_the_program_that_ran(dispatches):
    """``watch_jit``'s name, which is the launch's in a device trace
    (``jit_<program>``): greedy windows then sampled ones, and the one
    ragged step of either call."""
    by_start = sorted(dispatches.values(), key=lambda s: s["start"])
    ragged = [s["attrs"] for s in by_start if s["name"] == "ragged_call"]
    assert ragged == [{"program": "ragged_step"}] * 2
    windows = [s["attrs"]["program"] for s in by_start
               if s["name"] == "window_call"]
    n = -(-(NEW_TOKENS - 1) // 8)
    assert windows == ["decode_window_greedy"] * n \
        + ["decode_window_sample"] * n


def test_a_span_hands_out_its_record_and_marks_a_mirrored_one():
    trace.clear()
    with trace.span("outer") as sp:
        assert sp["duration_s"] is None
    assert sp["duration_s"] >= 0 and "annotated" not in sp
    trace.enable_xla_annotations(True)
    try:
        with trace.span("mirrored") as sp:
            pass
    finally:
        trace.enable_xla_annotations(False)
    assert sp["annotated"] is True
    assert [s["name"] for s in trace.export()] == ["outer", "mirrored"]


# ---------------------------------------------------------------------------
# device: every program a name, every fusion a scope, the map on request
# ---------------------------------------------------------------------------
def test_every_serving_jit_is_named_for_its_program(engine):
    eng, prompts, _ = engine
    watched = {name: fn for name, fn in vars(eng).items()
               if isinstance(fn, WatchedFunction)}
    assert {fn.program for fn in watched.values()} == {
        "ragged_step", "first_token_greedy", "first_token_sample",
        "decode_greedy", "decode_sample",
        "decode_window_greedy", "decode_window_sample"}
    for fn in watched.values():
        assert fn.__wrapped__.__name__ == fn.program, fn
    eng.generate(prompts, max_new_tokens=NEW_TOKENS)
    ran = {"ragged_step", "first_token_greedy", "decode_window_greedy"}
    assert ran <= set(memory._executables)
    for program in ran:
        head = memory._executables[program]().as_text().splitlines()[0]
        assert head.startswith(f"HloModule jit_{program},"), head


def test_the_scope_map_is_offered_free_and_holds_every_word(engine):
    eng, prompts, words = engine
    lowered = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, *_a, **_k: lowered.append(name)
        if name.endswith("jaxpr_to_mlir_module_duration") else None)
    eng.generate(prompts, max_new_tokens=NEW_TOKENS)
    eng.generate(prompts, max_new_tokens=NEW_TOKENS)
    assert not memory._scopes           # nothing parsed, and from here on
    del lowered[:]                      # nothing lowered, before the request
    eng.generate(prompts, max_new_tokens=NEW_TOKENS)
    assert not lowered and not memory._scopes
    for program in ("ragged_step", "decode_window_greedy"):
        assert memory.signatures_offered(program) >= 1
        mapped = memory.scopes(program)
        assert lowered                  # the request lowered it
        assert mapped is memory.scopes(program)      # and keeps the map
        seen = {w for op_name in mapped.values()
                for w in re.findall(r"[a-z_]+", op_name)}
        assert words <= seen, (program, words - seen)
        phases = {serve_phase(v) for v in mapped.values()}
        assert phases <= set(SERVE_PHASES)
        assert {"embed", "attn_proj", "kv_write", "attn_kernel", "mlp",
                "head"} <= phases, phases
    # the map outlives the engine, and holds none of its arrays
    del eng
    assert memory.scopes("ragged_step")


def test_the_newest_signature_answers_and_the_count_says_so():
    memory.reset()
    memory.offer_executable("p", lambda: 1 / 0)
    memory.offer_executable("p", lambda: None)
    assert memory.signatures_offered("p") == 2
    assert memory.signatures_offered("never") == 0
    assert memory.scopes("never") is None
    assert memory.scopes_offered("never") == []


def test_a_program_under_two_signatures_offers_a_map_for_each():
    """What ``serve_scope_time`` reads where one call ran a program
    under two bucket shapes: every signature's own map, oldest first,
    built once each, the last being ``scopes(program)``."""
    from deepspeed_tpu.telemetry.watchdog import watch_jit
    memory.reset()

    def body(x, y):
        with jax.named_scope("mlp"):
            x = x @ y
        if x.shape[0] > 4:              # the wider bucket alone
            with jax.named_scope("head"):
                x = jnp.tanh(x).sum(axis=0, keepdims=True) + x
        return x

    fn = watch_jit("two_buckets", body)
    fn(jnp.ones((4, 8)), jnp.ones((8, 8)))
    fn(jnp.ones((16, 8)), jnp.ones((8, 8)))
    fn(jnp.ones((4, 8)), jnp.ones((8, 8)))          # no compile: no offer
    assert memory.signatures_offered("two_buckets") == 2
    narrow, wide = memory.scopes_offered("two_buckets")
    assert wide is memory.scopes("two_buckets")
    assert memory.scopes_offered("two_buckets")[0] is narrow     # kept
    phases = [{serve_phase(v) for v in m.values()} for m in (narrow, wide)]
    assert "mlp" in phases[0] and "head" not in phases[0]
    assert {"mlp", "head"} <= phases[1]
    # a program recorded the other way has the one map
    compiled = jax.jit(body).lower(jnp.ones((4, 8)),
                                   jnp.ones((8, 8))).compile()
    memory.record_memory_analysis("analysed", compiled)
    assert memory.scopes_offered("analysed") == [memory.scopes("analysed")]


PHASE_TABLE = [
    ("jit(ragged_step)/embed/gather", "embed"),
    ("jit(ragged_step)/layers/while/body/attention/reduce_sum",
     "attn_proj"),
    ("jit(ragged_step)/layers/while/body/attention/qkv_proj/dot_general",
     "attn_proj"),
    ("jit(decode_window_greedy)/while/body/layers/while/body/attention/"
     "out_proj/dot_general", "attn_proj"),
    ("jit(ragged_step)/layers/while/body/attention/kv_write/scatter",
     "kv_write"),
    ("jit(ragged_step)/layers/while/body/attention/attn_kernel/"
     "ragged_attention_tiled/pallas_call", "attn_kernel"),
    ("jit(ragged_step)/layers/while/body/mla_attention/dot_general",
     "attn_proj"),
    ("jit(ragged_step)/layers/while/body/mla_attention/kv_write/scatter",
     "kv_write"),
    ("jit(ragged_step)/layers/while/body/mla_attention/attn_kernel/"
     "ragged_attention_latent/pallas_call", "attn_kernel"),
    ("jit(ragged_step)/layers/while/body/closed_call/mlp/dot_general",
     "mlp"),
    ("jit(ragged_step)/layers/while/body/mlp/dense_mlp/mul", "mlp"),
    ("jit(ragged_step)/layers/while/body/mlp/moe_shared_expert/dot_general",
     "mlp"),
    ("jit(ragged_step)/layers/while/body/mlp/moe_router/top_k", "router"),
    ("jit(ragged_step)/layers/while/body/mlp/moe_experts/gmm/pallas_call",
     "experts"),
    ("jit(decode_window_greedy)/while/body/head/dot_general", "head"),
    ("jit(decode_window_greedy)/while/body/pick/argmax", "pick"),
    ("jit(decode_window_sample)/while/body/pick/jit(_threefry_split)/add",
     "pick"),
    ("jit(decode_window_greedy)/while/body/layers/while/body/dynamic_slice",
     "other"),
    ("jit(decode_window_greedy)/while/body/jit(take_along_axis)/gather",
     "other"),
    # a state-space (Mamba-2) layer: the recurrence a phase a form
    ("jit(ragged_step)/layers/while/body/ssm_mixer/reduce_sum", "ssm"),
    ("jit(ragged_step)/layers/while/body/ssm_mixer/ssm_proj/dot_general",
     "ssm"),
    ("jit(ragged_step)/layers/while/body/ssm_mixer/ssm_conv/scatter", "ssm"),
    ("jit(ragged_step)/layers/while/body/ssm_mixer/ssm_scan/"
     "jit(ssm_chunk_fwd)/pallas_call", "ssm_scan"),
    ("jit(decode_window_greedy)/while/body/layers/while/body/ssm_mixer/"
     "ssm_state/ssm_state_update/pallas_call", "ssm_state"),
    ("jit(decode_window_greedy)/while/body/layers/while/body/ssm_mixer/"
     "ssm_gate_norm/mul", "ssm"),
    ("jit(decode_window_greedy)/while/body/layers/while/body/ssm_mixer/"
     "ssm_out/dot_general", "ssm"),
    # a word inside another name is not the scope
    ("jit(ragged_step)/ragged_attention_tiled/heads/mul", "other"),
]


@pytest.mark.parametrize("op_name,phase", PHASE_TABLE)
def test_serve_phase(op_name, phase):
    assert serve_phase(op_name) == phase


@pytest.mark.parametrize("word", sorted(xla_profile._SERVE_PHASE_OF_SCOPE))
def test_serve_scope_reads_every_word_of_the_table(word):
    """The innermost word is the scope, whatever stands round it, and
    its phase is the table's: one regex, one table."""
    path = f"jit(ragged_step)/layers/while/body/{word}/dot_general"
    assert serve_scope(path) == word
    assert serve_phase(path) == xla_profile._SERVE_PHASE_OF_SCOPE[word]
    assert serve_phase(path) in SERVE_PHASES
    # inside another scope it wins; as part of another name it is none
    assert serve_scope(f"jit(x)/layers/attention/{word}/mul") == word
    assert serve_scope(f"jit(x)/my_{word}_kernel/mul") == "other"


@pytest.mark.parametrize("op_name,scope", [
    ("jit(ragged_step)/layers/while/body/ssm_mixer/reduce_sum",
     "ssm_mixer"),
    ("jit(ragged_step)/layers/while/body/ssm_mixer/ssm_conv/scatter",
     "ssm_conv"),
    ("jit(ragged_step)/layers/while/body/ssm_mixer/ssm_out/dot_general",
     "ssm_out"),
    ("jit(ragged_step)/layers/while/body/mlp/moe_router/top_k",
     "moe_router"),
    ("jit(ragged_step)/layers/while/body/attention/kv_write/scatter",
     "kv_write"),
    ("jit(decode_window_greedy)/while/body/layers/while/body/dynamic_slice",
     "other"),
    ("", "other")])
def test_serve_scope_is_the_innermost_word(op_name, scope):
    assert serve_scope(op_name) == scope


def test_scope_seconds_keeps_two_programs_instructions_apart():
    """``fusion.1`` is the ragged step's MLP matmul AND the decode
    window's query projection: the sum is by the launch's program, the
    decode programs one family, and what no map knows is ``other``."""
    rows = [
        ("ragged_step", "fusion.1",
         "jit(ragged_step)/layers/while/body/mlp/dot_general", 3.0),
        ("ragged_step", "fusion.2", "jit(ragged_step)/head/dot_general",
         4.0),
        ("ragged_step", "fusion.7", None, 0.5),
        ("decode_window_greedy", "fusion.1",
         "jit(decode_window_greedy)/while/body/layers/while/body/"
         "attention/qkv_proj/dot_general", 8.0),
        ("decode_window_sample", "fusion.1",
         "jit(decode_window_sample)/while/body/layers/while/body/"
         "attention/qkv_proj/dot_general", 1.0),
        ("decode_window_greedy", "copy.9", None, 1.0),
        (None, "fusion.1", None, 0.25),
        ("draft_catchup", "fusion.3", "jit(draft_catchup)/embed/gather",
         0.125)]
    got = scope_seconds(rows)
    assert got == {("ragged_step", "mlp"): 3.0,
                   ("ragged_step", "head"): 4.0,
                   ("ragged_step", "other"): 0.5,
                   ("decode", "qkv_proj"): 9.0,
                   ("decode", "other"): 1.0,
                   ("other", "other"): 0.25,
                   ("draft_catchup", "embed"): 0.125}
    assert sum(got.values()) == sum(r[-1] for r in rows)
    assert scope_seconds([]) == {}


def test_the_state_space_scopes_open_in_both_programs():
    """The toy state-space hybrid's programs carry every scope the
    per-layer metrics read: ``ssm_mixer`` > {``ssm_proj``, ``ssm_conv``,
    ``ssm_state`` (the decode window) or ``ssm_scan`` (the ragged step),
    ``ssm_gate_norm``, ``ssm_out``}, beside the attention layer's."""
    import json
    from pathlib import Path
    from benchmark import run as harness
    from deepspeed_tpu.inference.v2.paged_model import (
        init_paged_kv_cache, paged_decode_window, paged_ragged_step)
    from deepspeed_tpu.models import TransformerLM
    file = json.loads((Path(__file__).resolve().parents[3] / "benchmark"
                       / "configs/granite-4.0-h-small.json").read_text())
    cfg = TransformerConfig(**harness.merge(file["fields"],
                                            file["toy_fields"]))
    params = jax.eval_shape(TransformerLM(cfg).init_params,
                            jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: init_paged_kv_cache(
        cfg, 9, 16, jnp.float32, state_slots=2))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    window = jax.jit(
        lambda p, t, pos, bt, c, sl, eos, ss: paged_decode_window(
            cfg, p, t, pos, bt, c, sl, eos, 16, 4, state_slots=ss)).lower(
        params, i32(2), i32(2), i32(2, 4), cache, i32(2), i32(2),
        i32(2)).as_text(debug_info=True)
    step = jax.jit(
        lambda p, ids, rows, pos, ln, wb, wo, bt, li, c, ss:
        paged_ragged_step(cfg, p, ids, rows, pos, ln, wb, wo, bt, li, c, 16,
                          state_slots=ss)).lower(
        params, i32(16), i32(16), i32(16), i32(16), i32(16), i32(16),
        i32(2, 4), i32(2), cache, i32(2)).as_text(debug_info=True)
    shared = ("ssm_mixer/ssm_proj", "ssm_mixer/ssm_conv",
              "ssm_mixer/ssm_gate_norm", "ssm_mixer/ssm_out",
              "attention/attn_kernel", "mlp/moe_experts")
    for text, own, other in ((window, "ssm_mixer/ssm_state", "ssm_scan"),
                             (step, "ssm_mixer/ssm_scan", "ssm_state/")):
        for scope in shared + (own,):
            assert scope in text, scope
        assert other not in text


def _scopes_under(jaxpr, found=None):
    """Every name stack under a jaxpr, sub-jaxprs included."""
    found = set() if found is None else found
    for eqn in jaxpr.eqns:
        found.add(str(eqn.source_info.name_stack))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _scopes_under(sub, found)
    return found


@pytest.mark.parametrize("program", ["ragged_step", "decode_window"])
def test_a_layer_that_is_one_sub_layer_opens_its_scopes_and_no_others(
        program):
    """The toy of the one-sub-layer pattern (mamba | moe | attention):
    its sixteen runs are sixteen scans, and each carries its own kind's
    scopes alone: a mamba layer ``ssm_mixer`` > {``ssm_proj``,
    ``ssm_conv``, ``ssm_scan`` | ``ssm_state``, ``ssm_gate_norm``,
    ``ssm_out``} and no ``mlp``; an expert layer ``mlp`` >
    {``moe_router``, ``moe_experts``, ``moe_shared_expert``} with NO
    mixer scope around it; an attention layer ``attention`` > {...} with
    no ``mlp`` behind it."""
    import json
    from pathlib import Path
    from benchmark import run as harness
    from deepspeed_tpu.inference.v2.paged_model import (
        init_paged_kv_cache, paged_decode_window, paged_ragged_step)
    from deepspeed_tpu.models import TransformerLM
    file = json.loads((Path(__file__).resolve().parents[3] / "benchmark"
                       / "configs/nemotron-3-nano-30b-a3b.json").read_text())
    cfg = TransformerConfig(**harness.merge(file["fields"],
                                            file["toy_fields"]))
    params = jax.eval_shape(TransformerLM(cfg).init_params,
                            jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: init_paged_kv_cache(
        cfg, 9, 16, jnp.float32, state_slots=2))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    if program == "decode_window":
        jaxpr = jax.make_jaxpr(
            lambda p, t, pos, bt, c, sl, eos, ss: paged_decode_window(
                cfg, p, t, pos, bt, c, sl, eos, 16, 4, state_slots=ss))(
            params, i32(2), i32(2), i32(2, 4), cache, i32(2), i32(2), i32(2))
        recurrence, other = "ssm_state", "ssm_scan"
    else:
        jaxpr = jax.make_jaxpr(
            lambda p, ids, rows, pos, ln, wb, wo, bt, li, c, ss:
            paged_ragged_step(cfg, p, ids, rows, pos, ln, wb, wo, bt, li, c,
                              16, state_slots=ss))(
            params, i32(16), i32(16), i32(16), i32(16), i32(16), i32(16),
            i32(2, 4), i32(2), cache, i32(2))
        recurrence, other = "ssm_scan", "ssm_state"

    def scans(jp, found):
        for eqn in jp.eqns:
            if eqn.primitive.name == "scan" and str(
                    eqn.source_info.name_stack).endswith("layers"):
                found.append(_scopes_under(eqn.params["jaxpr"].jaxpr))
            else:
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    scans(sub, found)
        return found

    runs = scans(jaxpr.jaxpr, [])
    assert len(runs) == 16
    wants = {
        "ssm": {"ssm_mixer/ssm_proj", "ssm_mixer/ssm_conv",
                "ssm_mixer/" + recurrence, "ssm_mixer/ssm_gate_norm",
                "ssm_mixer/ssm_out"},
        "moe": {"mlp/moe_router", "mlp/moe_experts",
                "mlp/moe_shared_expert"},
        "full": {"attention/qkv_proj", "attention/kv_write",
                 "attention/attn_kernel", "attention/out_proj"}}
    tops = {"ssm": "ssm_mixer", "moe": "mlp", "full": "attention"}
    for kind, scopes in zip(cfg.layer_kinds, runs):
        text = "\n".join(sorted(scopes))
        for scope in wants[kind]:
            assert any(scope in s for s in scopes), (kind, scope, text)
        for k, top in tops.items():
            if k != kind:
                assert not any(re.search(r"(^|/)%s(/|$)" % top, s)
                               for s in scopes), (kind, top, text)
        assert other + "/" not in text and not text.endswith(other)


def _instructions(text):
    return len(re.findall(r"^\s*(?:ROOT\s+)?%?[\w.\-]+ = ", text, re.M))


@pytest.mark.parametrize("block", ["per_head", "latent"])
def test_scopes_are_metadata(block, tiny_model_128, monkeypatch):
    """The toy decode window compiles to the same number of
    instructions, fusions and loops with the scopes and without."""
    from deepspeed_tpu.inference.v2.paged_model import (
        init_paged_kv_cache, paged_decode_window)
    if block == "latent":
        cfg = TransformerConfig(**TOY_LATENT)
        params = jax.eval_shape(
            lambda: weights_joyai.make(TOY_LATENT, 7, "float32"))
    else:
        cfg = tiny_model_128[0].cfg
        params = jax.eval_shape(lambda: tiny_model_128[1])
    cache = jax.eval_shape(
        lambda: init_paged_kv_cache(cfg, 9, 16, jnp.float32))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731

    def lower_it():
        def window(p, t, pos, bt, c, sl, eos):      # a new trace each time
            return paged_decode_window(cfg, p, t, pos, bt, c, sl, eos,
                                       16, 4)
        return jax.jit(window).lower(params, i32(2), i32(2), i32(2, 4),
                                     cache, i32(2), i32(2))

    scoped = lower_it()
    assert "attn_kernel" in scoped.as_text(debug_info=True)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = lower_it()
    assert "attn_kernel" not in bare.as_text(debug_info=True)
    # what the two lower to differs in locations alone (the persistent
    # compile cache keys them alike, for the same reason) ...
    assert scoped.as_text() == bare.as_text()
    # ... and so does what they compile to
    scoped, bare = scoped.compile().as_text(), bare.compile().as_text()
    assert _instructions(scoped) == _instructions(bare) > 100
    for opcode in (" fusion(", " while(", " custom-call("):
        assert scoped.count(opcode) == bare.count(opcode)
