"""Draft-model speculative decoding fused into the jitted decode window
(inference/v2/paged_model.py paged_spec_decode_window).

Pinned contracts (ISSUE 18 acceptance):
  * PARITY — greedy speculative output is BIT-IDENTICAL to
    non-speculative decode, whatever the draft model proposes (a weak
    or even random draft only costs speed, never tokens), under every
    spec_mode and composed with eos / prefix caching / seq-len clamp.
  * TYPED MISMATCH — a draft whose vocab or sequence coverage cannot
    verify-share with the target raises DraftModelMismatchError at
    load time, never mid-batch on device.
  * CHOOSER — the per-request router between n-gram and draft-model
    speculation is hysteresis-armed (margin + hold, like
    autotuning/online.py): one noisy window never flips the route.
"""

import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                        RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.config_v2 import DSStateManagerConfig
from deepspeed_tpu.inference.v2.engine_v2 import (DraftModelMismatchError,
                                                  SpecChooser)
from deepspeed_tpu.models import TransformerConfig, TransformerLM
from deepspeed_tpu.telemetry import get_registry


@pytest.fixture(scope="module")
def tiny(tiny_model_256):
    return tiny_model_256


def _engine(model, params, **kw):
    return InferenceEngineV2(
        model, RaggedInferenceEngineConfig(
            state_manager=DSStateManagerConfig(
                max_tracked_sequences=8, max_seq_len=256, num_blocks=65,
                block_size=16, **kw),
            dtype="float32", prefill_bucket=16), params=params)


def _prompts(repetitive):
    if repetitive:
        unit = [5, 9, 17, 23]
        return [unit * 6, [3] + unit * 4]
    rng = np.random.default_rng(1)
    return [list(map(int, rng.integers(1, 127, n))) for n in (21, 34)]


# ---------------------------------------------------------------------------
# parity: bit-identical to plain greedy, for every draft quality
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("repetitive", [True, False])
def test_draft_spec_greedy_bit_identical(tiny, repetitive):
    """Self-draft (draft == target weights): near-total acceptance, and
    the output must STILL be byte-for-byte the plain greedy stream."""
    model, params = tiny
    prompts = _prompts(repetitive)
    ref = _engine(model, params).generate(prompts, max_new_tokens=20)
    eng = _engine(model, params)
    eng.load_draft_model(model, params)
    out = eng.generate(prompts, max_new_tokens=20, uids=[5, 6],
                       speculative=True, spec_mode="draft")
    for a, b in zip(out, ref):
        np.testing.assert_array_equal(a, b)


def test_divergent_draft_still_bit_identical(tiny):
    """A draft with FRESH random weights disagrees with the target
    almost everywhere — verification must reject its proposals and the
    stream must stay exactly the plain greedy one (speculation changes
    step count, never tokens)."""
    model, params = tiny
    prompts = _prompts(True) + _prompts(False)
    ref = _engine(model, params).generate(prompts, max_new_tokens=16)
    eng = _engine(model, params)
    eng.load_draft_model(model)          # params=None: fresh init
    out = eng.generate(prompts, max_new_tokens=16, speculative=True,
                       spec_mode="draft")
    for a, b in zip(out, ref):
        np.testing.assert_array_equal(a, b)


def test_draft_spec_eos_and_prefix_caching_compose(tiny):
    model, params = tiny
    prompt = [5, 9, 17, 23] * 5
    ref = _engine(model, params).generate([prompt], max_new_tokens=12)[0]
    eos = int(ref[len(prompt) + 5])
    r2 = _engine(model, params).generate([prompt], max_new_tokens=12,
                                         eos_token_id=eos)[0]
    eng = _engine(model, params, enable_prefix_caching=True)
    eng.load_draft_model(model, params)
    out = eng.generate([prompt], max_new_tokens=12, eos_token_id=eos,
                       speculative=True, spec_mode="draft", uids=[1])[0]
    np.testing.assert_array_equal(out, r2)
    # repeat serve: the spec window's token_log kept the prefix cache
    # consistent, so a fresh uid reuses blocks and stays identical
    out2 = eng.generate([prompt], max_new_tokens=12, eos_token_id=eos,
                        speculative=True, spec_mode="draft", uids=[2])[0]
    np.testing.assert_array_equal(out2, r2)


def test_draft_spec_respects_max_seq_len(tiny):
    """A late window must clamp draft length to the sequence budget —
    greedy-exact right up to the limit."""
    model, params = tiny
    prompt = [5, 9, 17, 23] * 4 + [5]                    # 17 tokens
    sm = dict(max_tracked_sequences=2, max_seq_len=33, num_blocks=9,
              block_size=16)

    def eng():
        return InferenceEngineV2(
            model, RaggedInferenceEngineConfig(
                state_manager=DSStateManagerConfig(**sm),
                dtype="float32", prefill_bucket=16), params=params)

    ref = eng().generate([prompt], max_new_tokens=16)[0]
    e = eng()
    e.load_draft_model(model, params)
    out = e.generate([prompt], max_new_tokens=16, speculative=True,
                     spec_mode="draft")[0]
    np.testing.assert_array_equal(out, ref)
    assert len(out) == 33


def test_auto_mode_mixed_batch_parity(tiny):
    """spec_mode=None (auto): the chooser routes each request
    independently — a repetitive prompt (n-gram prior) and a random one
    (draft prior) share a batch, and both stay greedy-exact."""
    model, params = tiny
    prompts = [_prompts(True)[0], _prompts(False)[0]]
    ref = _engine(model, params).generate(prompts, max_new_tokens=16)
    eng = _engine(model, params)
    eng.load_draft_model(model, params)
    reg = get_registry()
    m = reg.get("inference_spec_mode_requests_total")
    n0 = {md: m.labels(mode=md).value for md in ("ngram", "draft")}
    out = eng.generate(prompts, max_new_tokens=16, speculative=True)
    for a, b in zip(out, ref):
        np.testing.assert_array_equal(a, b)
    routed = {md: m.labels(mode=md).value - n0[md]
              for md in ("ngram", "draft")}
    # cold-start prior: the periodic prompt routes to its own history,
    # the random one to the draft model
    assert routed["ngram"] >= 1 and routed["draft"] >= 1, routed


# ---------------------------------------------------------------------------
# typed rejection + request validation
# ---------------------------------------------------------------------------
def test_draft_vocab_mismatch_typed(tiny):
    model, params = tiny
    eng = _engine(model, params)
    bad = TransformerLM(TransformerConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64,
        num_layers=1, num_heads=2, num_kv_heads=2, max_seq_len=256,
        remat=False, use_flash=False))
    with pytest.raises(DraftModelMismatchError, match="vocab_size"):
        eng.load_draft_model(bad)
    assert eng.draft_model is None


def test_draft_seq_len_mismatch_typed(tiny):
    model, params = tiny
    eng = _engine(model, params)
    short = TransformerLM(TransformerConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_layers=1, num_heads=2, num_kv_heads=2, max_seq_len=64,
        remat=False, use_flash=False))
    with pytest.raises(DraftModelMismatchError, match="max_seq_len"):
        eng.load_draft_model(short)
    assert eng.draft_model is None
    # DraftModelMismatchError is a ValueError: callers with the generic
    # typed-failure handler keep working
    assert issubclass(DraftModelMismatchError, ValueError)


def test_spec_mode_validation(tiny):
    model, params = tiny
    eng = _engine(model, params)
    with pytest.raises(ValueError, match="load_draft_model"):
        eng.generate([[1, 2, 3]], max_new_tokens=4, speculative=True,
                     spec_mode="draft")
    with pytest.raises(ValueError):
        eng.generate([[1, 2, 3]], max_new_tokens=4, speculative=True,
                     spec_mode="bogus")
    # no draft model + auto: everything falls back to n-gram, greedily
    # exact
    ref = _engine(model, params).generate([[5, 9, 17, 23] * 5],
                                          max_new_tokens=8)
    out = eng.generate([[5, 9, 17, 23] * 5], max_new_tokens=8,
                       speculative=True)
    np.testing.assert_array_equal(out[0], ref[0])


# ---------------------------------------------------------------------------
# telemetry
# ---------------------------------------------------------------------------
def test_spec_window_telemetry(tiny):
    model, params = tiny
    eng = _engine(model, params)
    eng.load_draft_model(model, params)      # self-draft: accepts ~all
    reg = get_registry()
    f = reg.family_total
    c0 = {n: f(n) for n in ("inference_spec_drafted_tokens_total",
                            "inference_spec_accepted_tokens_total",
                            "inference_spec_window_rounds_total")}
    eng.generate([[5, 9, 17, 23] * 6], max_new_tokens=12,
                 speculative=True, spec_mode="draft")
    drafted = f("inference_spec_drafted_tokens_total") - \
        c0["inference_spec_drafted_tokens_total"]
    accepted = f("inference_spec_accepted_tokens_total") - \
        c0["inference_spec_accepted_tokens_total"]
    rounds = f("inference_spec_window_rounds_total") - \
        c0["inference_spec_window_rounds_total"]
    assert drafted > 0 and rounds > 0
    # self-draft: the draft IS the target, so every verified token
    # matches — the observed rate is below 1.0 only because the final
    # round's proposals are clamped by the token budget (drafted counts
    # the full k, accepted counts what the budget let through)
    assert accepted / drafted > 0.5, (accepted, drafted)
    rate = reg.get("inference_spec_accept_rate").labels(
        mode="draft").value
    assert rate > 0.5


def test_draft_covers_no_less_of_a_mixed_replay_than_ngram(tiny):
    """Half periodic prompts (where the n-gram index hits), half random
    (where only a draft model proposes anything): of the tokens produced,
    the share that speculation paid for must not be lower with the draft
    model than with n-gram on the same prompts. The n-gram index only
    drafts on a hit, so its rate over DRAFTED tokens is high by
    construction; coverage of the stream is the fair comparison."""
    model, params = tiny
    rng = np.random.default_rng(8)
    unit = [5, 9, 17, 23]
    replay = [unit * 6, list(map(int, rng.integers(1, 127, 24))),
              [3] + unit * 4, list(map(int, rng.integers(1, 127, 17)))]
    f = get_registry().family_total

    def accepted_of_produced(mode):
        eng = _engine(model, params)
        if mode == "draft":
            eng.load_draft_model(model, params)       # self-draft
        d0 = f("inference_spec_drafted_tokens_total")
        a0 = f("inference_spec_accepted_tokens_total")
        outs = eng.generate(replay, max_new_tokens=16, speculative=True,
                            spec_mode=mode)
        accepted = f("inference_spec_accepted_tokens_total") - a0
        drafted = f("inference_spec_drafted_tokens_total") - d0
        produced = sum(len(o) - len(p) for o, p in zip(outs, replay))
        return accepted / produced, accepted / max(drafted, 1)

    draft_cover, draft_rate = accepted_of_produced("draft")
    ngram_cover, _ = accepted_of_produced("ngram")
    assert draft_cover >= ngram_cover, (draft_cover, ngram_cover)
    # budget-clamped: the last round drafts its full k, the budget lets
    # fewer through
    assert draft_rate >= 0.6375, draft_rate      # reads 0.6875


# ---------------------------------------------------------------------------
# chooser hysteresis (armed / hold, like autotuning/online.py)
# ---------------------------------------------------------------------------
def test_chooser_hysteresis_margin_and_hold():
    ch = SpecChooser(mode="auto", alpha=1.0, margin=0.05, hold=3)
    assert ch.current == "ngram"
    # cold start routes by the repetitiveness prior
    assert ch.choose(True, ngram_hit=True) == "ngram"
    assert ch.choose(True, ngram_hit=False) == "draft"
    # pinned / missing-draft short circuits
    assert SpecChooser(mode="draft").choose(True, False) == "draft"
    assert SpecChooser(mode="ngram").choose(True, False) == "ngram"
    assert ch.choose(False, ngram_hit=False) == "ngram"

    # draft beats ngram by more than the margin — but a switch commits
    # only after HOLD consecutive winning observations
    ch.observe("ngram", drafted=10, accepted=3)
    ch.observe("draft", drafted=10, accepted=9)
    assert ch.current == "ngram" and ch.switches == 0     # armed (1)
    ch.observe("draft", drafted=10, accepted=9)
    assert ch.current == "ngram"                          # armed (2)
    ch.observe("draft", drafted=10, accepted=9)
    assert ch.current == "draft" and ch.switches == 1     # committed
    assert ch.choose(True, ngram_hit=True) == "draft"

    # a streak broken mid-hold disarms: no flap
    ch2 = SpecChooser(mode="auto", alpha=1.0, margin=0.05, hold=3)
    ch2.observe("ngram", 10, 3)
    ch2.observe("draft", 10, 9)
    ch2.observe("draft", 10, 9)
    ch2.observe("draft", 10, 2)      # draft EMA collapses below margin
    ch2.observe("draft", 10, 9)      # winning again, but streak restarts
    ch2.observe("draft", 10, 9)
    assert ch2.current == "ngram" and ch2.switches == 0
    ch2.observe("draft", 10, 9)
    assert ch2.current == "draft" and ch2.switches == 1

    # within-margin advantage never arms
    ch3 = SpecChooser(mode="auto", alpha=1.0, margin=0.2, hold=1)
    ch3.observe("ngram", 10, 5)
    for _ in range(5):
        ch3.observe("draft", 10, 6)
    assert ch3.current == "ngram" and ch3.switches == 0

    # zero drafted rounds are ignored (no divide-by-zero, no EMA decay)
    ch3.observe("draft", 0, 0)
    assert ch3.rate["draft"] is not None
