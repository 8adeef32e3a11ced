"""The ``brumby`` block served: POWER-RETENTION layers (a gated, degree-2
power-kernel state a key/value head, read by its whole group of query
heads) in a model that caches no position at all, at toy widths on the
CPU, against the benchmark's plain reference
(``benchmark/reference_brumby.py``: float32, the ATTENTION form, and the
recurrence a token at a time as its second route).

Tolerances. A float32 engine differs from the reference by the order of
its sums and by its forms (phi by circular distance, chunks of matmuls,
the one-token update in place of the attention form): 2e-5 of the
largest logit is the other blocks' float32 limit and fifty times what it
reads (4e-7). A state kept in bfloat16 reads 1e-3 and more of the state
after a prompt and a few decode steps. A bf16 engine rounds every
activation to 8 bits: at a hidden width of 64 that reads to 3e-2 of the
largest logit (limit 1e-1) and the served tokens' gap to 1e-2 (the other
blocks' 4e-2). Two forms of one recurrence, both float32: 2e-5 of the
largest output (they read 1e-6).
"""

import json
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import reference_brumby, weights_brumby
from benchmark import run as harness
from deepspeed_tpu.inference.v2 import InferenceEngineV2, paged_model
from deepspeed_tpu.inference.v2.kernels import power_retention as pr
from deepspeed_tpu.models import TransformerConfig, TransformerLM
from deepspeed_tpu.telemetry import get_registry

REPO = Path(__file__).resolve().parents[3]
CONFIG = json.loads(
    (REPO / "benchmark/configs/brumby-14b-base.json").read_text())
TOY = harness.merge(CONFIG["fields"], CONFIG["toy_fields"])
F32_TIGHT, BF16_LOGITS, BF16_LIMIT = 2e-5, 1e-1, 4e-2
SEED, BF16_SEED = 5, 11
EPS = 1e-6


def _engine(dtype="float32", fields=TOY, seqs=4, budget=256, seed=SEED,
            **engine):
    cfg = TransformerConfig(**fields)
    return InferenceEngineV2(TransformerLM(cfg), {
        "dtype": dtype, "use_paged_kernel": True, "decode_window": 4,
        **engine,
        "state_manager": {"max_tracked_sequences": seqs,
                          "max_ragged_batch_size": budget,
                          "max_seq_len": 256, "block_size": 16,
                          "num_blocks": 2}},
        params=weights_brumby.make(fields, seed, dtype))


def _prompts(lengths=(20, 70, 5), seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, TOY["vocab_size"], n) for n in lengths]


def _params(seed=SEED):
    return weights_brumby.make(TOY, seed, "float32")


def _reference(prompt, seed=SEED):
    return np.asarray(reference_brumby.logits(_params(seed), TOY, prompt))


def _err(got, want):
    return float(np.abs(np.asarray(got, np.float32) - want).max()
                 / np.abs(want).max())


# ---------------------------------------------------------------------------
# (a) the configuration, and the engine against the plain reference
# ---------------------------------------------------------------------------
def test_the_sixth_kind_is_written_down_and_caches_no_position():
    cfg = TransformerConfig(**TOY)
    assert cfg.layer_kinds == ("retention", "retention")
    assert cfg.has_state and cfg.walks_runs and cfg.pattern
    assert not cfg.caches_positions
    assert "power_retention layers" in cfg.served_only
    assert paged_model._layer_runs(cfg) == [("retention", False, 0, 2)]
    # the other kinds do cache positions, with or without a state
    assert TransformerConfig(hidden_size=64, num_heads=4).caches_positions
    granite = json.loads((REPO / "benchmark/configs/"
                          "granite-4.0-h-small.json").read_text())
    assert TransformerConfig(**granite["fields"]).caches_positions
    with pytest.raises(ValueError, match="even head_dim"):
        TransformerConfig(**{**TOY, "head_dim_override": 15})
    with pytest.raises(ValueError, match="positional='rope'"):
        TransformerConfig(**{**TOY, "positional": "none"})
    # the parameter stack of the kind: the per-head mixer's and the gate
    shapes = jax.eval_shape(TransformerLM(cfg).init_params,
                            jax.random.PRNGKey(0))
    assert set(shapes) == {"embed", "final_norm", "lm_head",
                           "retention_layers", "layers"}
    assert set(shapes["retention_layers"]) == {
        "attn_norm", "wq", "wk", "wv", "wo", "q_norm", "k_norm", "w_decay",
        "b_decay"}
    assert shapes["retention_layers"]["w_decay"].shape == (2, 64, 2)
    assert shapes["retention_layers"]["b_decay"].shape == (2, 2)


def test_the_cache_is_the_state_leaves_and_a_sequence_owns_no_block():
    eng = _engine()
    assert eng.attention_impl == "none:no-layer-caches-positions"
    assert set(eng.kv_cache) == {"retention_state", "retention_norm"}
    # [layers, slots + 1, kv heads, hd / 2 + 1, hd, hd], the normaliser's
    # rows rounded up to a tile of 8
    assert eng.kv_cache["retention_state"].shape == (2, 5, 2, 9, 16, 16)
    assert eng.kv_cache["retention_norm"].shape == (2, 5, 2, 16, 16)
    sm = eng.state_manager
    assert not sm.paged and sm.max_blocks_per_seq == 1
    prompts = _prompts()
    assert eng.can_schedule([0, 1, 2], [len(p) for p in prompts])
    eng.put([0, 1, 2], prompts)
    assert all(sm.seqs[u].blocks == [] for u in (0, 1, 2))
    assert sorted(sm.seqs[u].state_slot for u in (0, 1, 2)) == [1, 2, 3]
    assert sm.seqs[1].seen_tokens == 70 and sm.state_slots_in_use() == 3
    # what bounds the batch is the slots, and a sequence's length
    eng.put([3], _prompts((4,)))
    assert not eng.can_schedule([4], [4])
    assert not eng.can_schedule([0], [256])
    reg = get_registry()
    assert reg.get("inference_state_bytes").value == sum(
        v.nbytes for v in eng.kv_cache.values())
    for uid in range(4):
        eng.flush(uid)
    assert sm.state_slots_in_use() == 0 and sm.tracked_sequences() == 0


@pytest.mark.parametrize("dtype,seed,limit", [
    ("float32", SEED, F32_TIGHT), ("bfloat16", BF16_SEED, BF16_LOGITS)])
def test_put_logits_match_the_reference(dtype, seed, limit):
    """Rows of 20, 70 and 5 tokens in one ragged step: a prompt that
    ends mid-chunk, and one under a chunk."""
    eng = _engine(dtype, seed=seed)
    prompts = _prompts()
    got = eng.put([0, 1, 2], prompts)
    for i, p in enumerate(prompts):
        assert _err(got[i], _reference(p, seed)[-1]) <= limit, i


@pytest.mark.parametrize("dtype,seed", [("float32", SEED),
                                        ("bfloat16", BF16_SEED)])
def test_decode_through_the_state_matches_the_reference(dtype, seed):
    """The ragged step leaves each row's state in its slot; decode
    windows of 4 (launched one behind the other: the state rides the
    cache) read and update it. float32: at EVERY generated position the
    engine's token is the reference's best on the same prefix, so a
    state, a slot, a decay or a group read wrong shows. bf16: the served
    token's reference logit lies within the bf16 limit of the best."""
    eng = _engine(dtype, seed=seed)
    prompts = _prompts()
    reg = get_registry()
    before = {n: reg.family_total(n) for n in (
        "inference_retention_state_kernel_steps_total",
        "inference_retention_chunk_kernel_launches_total")}
    outs = eng.generate(prompts, max_new_tokens=13, temperature=0.0,
                        eos_token_id=None)
    assert reg.family_total("inference_decode_windows_ahead_total") > 0
    # the XLA twins ran: off a TPU, heads 16 wide
    assert before == {n: reg.family_total(n) for n in before}
    assert eng.state_manager.state_slots_in_use() == 0
    for prompt, out in zip(prompts, outs):
        out = np.asarray(out)
        assert len(out) == len(prompt) + 13
        ref = _reference(out[:-1], seed)[len(prompt) - 1:]
        if dtype == "float32":
            np.testing.assert_array_equal(out[len(prompt):], ref.argmax(-1))
        else:
            served = ref[np.arange(len(ref)), out[len(prompt):]]
            gap = (ref.max(-1) - served) / np.abs(ref).max(-1)
            assert gap.max() <= BF16_LIMIT


def _held(eng, uid):
    state = eng.sequence_state(uid)
    return np.concatenate([state["retention_state"],
                           state["retention_norm"][..., None]], axis=-1)


def _wanted(tokens, seed=SEED):
    s, z = reference_brumby.leading_states(_params(seed), TOY, tokens,
                                           layers=2)
    return np.concatenate([np.asarray(s), np.asarray(z)[..., None]], -1)


def _state_err(got, want):
    return max(float(np.linalg.norm(g - w) / np.linalg.norm(w))
               for g, w in zip(got, want))


def test_the_state_after_n_tokens_is_the_references():
    """``generate(keep_sequences=True)`` leaves every token but the last
    fed: the slot then holds the reference's state after them in every
    layer, as ``sequence_state`` hands it out: phi in the order a <= b,
    the mechanism's hd (hd + 1) / 2 rows whatever the leaf keeps twice."""
    eng = _engine()
    prompts = _prompts((37, 20))
    outs = eng.generate(prompts, max_new_tokens=9, temperature=0.0,
                        eos_token_id=None, keep_sequences=True)
    assert eng.state_manager.state_slots_in_use() == 2
    for uid, out in enumerate(outs):
        state = eng.sequence_state(uid)
        assert state["retention_state"].shape == (2, 2, 136, 16)
        assert state["retention_norm"].shape == (2, 2, 136)
        err = _state_err(_held(eng, uid), _wanted(np.asarray(out)[:-1]))
        assert err <= F32_TIGHT, (uid, err)
        eng.flush(uid)
    assert eng.state_manager.state_slots_in_use() == 0


def test_a_state_kept_in_bfloat16_fails_the_limit_the_engine_passes():
    """``state_dtype: bfloat16`` (the cell's control) rounds the state
    at every update: after a prompt in three chunk steps and eight
    one-token updates it is off by over fifty times the float32 limit,
    which the engine as it stands passes."""
    prompts = _prompts((70,))
    errs = {}
    for name, engine in (("sound", {}),
                         ("control", {"state_dtype": "bfloat16"})):
        eng = _engine(budget=32, **engine)
        out = eng.generate(prompts, max_new_tokens=9, temperature=0.0,
                           eos_token_id=None, keep_sequences=True)[0]
        assert eng.kv_cache["retention_state"].dtype == (
            jnp.float32 if name == "sound" else jnp.bfloat16)
        errs[name] = _state_err(_held(eng, 0),
                                _wanted(np.asarray(out)[:-1]))
    assert errs["sound"] <= F32_TIGHT < 50 * F32_TIGHT < errs["control"], \
        errs


def test_rows_in_one_step_are_the_rows_served_alone():
    """Rows of unequal lengths packed in one ragged step, then a MIXED
    step (a new prompt beside the first rows' decode tokens), give each
    row what it gets served alone: rows mix nowhere, not in a chunk's
    pairs, not in the slots."""
    prompts = _prompts((33, 64, 7))
    late = _prompts((41,), seed=3)[0]
    nxt = [11, 22, 33]
    eng = _engine()
    first = eng.put([0, 1, 2], prompts)
    mixed = eng.put([0, 1, 2, 3], [[t] for t in nxt] + [late])
    for i, p in enumerate(prompts):
        alone = _engine()
        # a row alone is another bucket of rows: the sums' order
        np.testing.assert_allclose(alone.put([9], [p])[0], first[i], rtol=0,
                                   atol=F32_TIGHT * np.abs(first[i]).max())
        np.testing.assert_allclose(alone.put([9], [[nxt[i]]])[0], mixed[i],
                                   rtol=0,
                                   atol=F32_TIGHT * np.abs(mixed[i]).max())
    assert _err(mixed[3], _reference(late)[-1]) <= F32_TIGHT


def test_a_prompt_in_four_put_chunks_is_the_prompt_in_one():
    """``put()`` feeds a prompt set over its step's budget in chunks, a
    row continuing from its slot: four steps of 16 tokens a row give the
    logits of one step, and the state after them is the one step's."""
    prompts = _prompts((64, 64))
    whole, parts = _engine(), _engine(budget=32)
    reg = get_registry()
    before = reg.family_total("inference_prefill_chunks_total")
    got = parts.put([0, 1], prompts)
    assert reg.family_total("inference_prefill_chunks_total") - before == 4
    want = whole.put([0, 1], prompts)
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=F32_TIGHT * np.abs(want).max())
    for uid in (0, 1):
        a, b = _held(parts, uid), _held(whole, uid)
        np.testing.assert_allclose(a, b, rtol=0,
                                   atol=F32_TIGHT * np.abs(b).max())


def test_the_schedulers_streams_are_generates():
    """The SplitFuse scheduler composes steps of prompt chunks beside
    decode rows from sequences that own no block: scheduling changes
    composition, never results."""
    from deepspeed_tpu.inference.v2.scheduler import DynamicSplitFuseScheduler
    eng = _engine()
    prompts = _prompts((50, 21, 9))
    want = eng.generate(prompts, max_new_tokens=9, temperature=0.0,
                        eos_token_id=None)
    sched = DynamicSplitFuseScheduler(eng, chunk=16)
    for uid, p in enumerate(prompts):
        sched.submit(uid, p, max_new_tokens=9)
    sched.run()
    results = sched.results()
    assert sorted(results) == [0, 1, 2]
    for uid, row in results.items():
        np.testing.assert_array_equal(row, np.asarray(want[uid]))
    assert eng.state_manager.state_slots_in_use() == 0
    assert eng.state_manager.free_blocks() == 1     # the one never handed out


def test_a_slot_reused_by_a_later_sequence_leaks_no_state():
    """A slot is not cleared when it changes hands: the next sequence's
    first token starts from zeros in the program. The second sequence to
    hold slot 1 gets the logits and the state it gets on a new engine."""
    eng = _engine(seqs=1)
    first, second = _prompts((50,))[0], _prompts((23,), seed=4)[0]
    eng.put([0], [first])
    assert eng.state_manager.seqs[0].state_slot == 1
    eng.flush(0)
    got = eng.put([1], [second])
    assert eng.state_manager.seqs[1].state_slot == 1
    fresh = _engine(seqs=1)
    want = fresh.put([1], [second])
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(_held(eng, 1), _held(fresh, 1))
    assert _err(got[0], _reference(second)[-1]) <= F32_TIGHT


# ---------------------------------------------------------------------------
# (b) the forms of the recurrence, and their kernels
# ---------------------------------------------------------------------------
def _case(nh, nkv, hd, lengths, T, seed=0, slots=6, hard=False):
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    counts = jnp.asarray(lengths, jnp.int32)
    # log-decays from soft to hard: -0.01 to -3 a token; ``hard``: -3 on
    # every token, whose exp(-G) alone overflows float32 after 29
    g = -np.exp(rng.uniform(np.log(0.01), np.log(3.0), (T, nkv)))
    if hard:
        g = np.full((T, nkv), -3.0)
    # a state as tokens leave it (three of them a slot): sums of phi(k)
    # v^T, the normaliser of phi(k), so that the two lanes that hold a
    # pair at distance hd / 2 agree, as they do in every reachable state
    state, norm = pr.leaf_shapes(2, slots, nkv, hd)
    pk = pr.phi(f(*state[:3], 3, hd))
    z = jnp.pad(jnp.sum(pk, axis=3), [(0, 0)] * 3 + [
        (0, norm[-2] - pr.phi_rows(hd)), (0, 0)])
    return dict(
        state=jnp.einsum("lsjtda,lsjti->lsjdia", pk, f(*state[:3], 3, hd)),
        norm=z, layer=jnp.int32(1),
        slots=jnp.asarray([i % (slots - 1) + 1 if n else 0
                           for i, n in enumerate(lengths)], jnp.int32),
        fresh=jnp.asarray([i % 2 == 0 for i in range(len(lengths))]),
        starts=jnp.cumsum(counts) - counts, counts=counts,
        q=f(T, nh, hd), k=f(T, nkv, hd), v=f(T, nkv, hd),
        g=jnp.asarray(g, jnp.float32))


def _attention_form(q, k, v, g, s0, z0):
    """The reference's attention form over one row's tokens in float64,
    from the state (s0, z0) in the reference's own order: every earlier
    token folded into a start state is phi(q) S_0 with the decay since."""
    q, k, v, g, s0, z0 = (np.asarray(a, np.float64)
                          for a in (q, k, v, g, s0, z0))
    S, nh, hd = q.shape
    nkv = k.shape[1]
    G = np.cumsum(g, axis=0)
    seen = np.tril(np.ones((S, S)))
    out = np.zeros((S, nh, hd))
    for h in range(nh):
        j = h // (nh // nkv)
        w = (q[:, h] @ k[:, j].T) ** 2 / hd * np.where(
            seen > 0, np.exp(np.where(seen > 0, G[:, None, j]
                                      - G[None, :, j], 0.0)), 0.0)
        pq = np.asarray(reference_brumby.phi(jnp.asarray(q[:, h],
                                                         jnp.float32)),
                        np.float64)
        grown = np.exp(G[:, j])[:, None]
        num = w @ v[:, j] + grown * (pq @ s0[j])
        den = w.sum(1, keepdims=True) + grown * (pq @ z0[j])[:, None]
        out[:, h] = num / (den + EPS)
    return out


def _against_the_attention_form(case, o, state, norm):
    hd = case["q"].shape[-1]
    for r, n in enumerate(np.asarray(case["counts"])):
        if not n:
            continue
        at = slice(int(case["starts"][r]), int(case["starts"][r]) + n)
        slot = case["slots"][r]
        keep = 0.0 if case["fresh"][r] else 1.0
        s0 = np.asarray(pr.canonical_state(case["state"][1, slot])) * keep
        z0 = np.asarray(pr.canonical_norm(case["norm"][1, slot])) * keep
        want = _attention_form(case["q"][at], case["k"][at], case["v"][at],
                               case["g"][at], s0, z0)
        assert _err(o[at], want) <= F32_TIGHT, r
        # the state: the recurrence a token at a time, float64
        s, z = s0.astype(np.float64), z0.astype(np.float64)
        for t in range(at.start, at.stop):
            pk = np.asarray(reference_brumby.phi(case["k"][t]), np.float64)
            decay = np.exp(np.asarray(case["g"][t], np.float64))
            s = decay[:, None, None] * s + pk[:, :, None] \
                * np.asarray(case["v"][t], np.float64)[:, None, :]
            z = decay[:, None] * z + pk
        assert _err(pr.canonical_state(state[1, slot]), s) <= F32_TIGHT, r
        assert _err(pr.canonical_norm(norm[1, slot]), z) <= F32_TIGHT, r
    used = np.zeros(len(o), bool)
    for s_, n in zip(np.asarray(case["starts"]), np.asarray(case["counts"])):
        used[s_:s_ + n] = True
    assert not np.asarray(o)[~used].any()       # tokens of no row: zeros
    np.testing.assert_array_equal(state[0], case["state"][0])
    assert np.isfinite(np.asarray(o)).all()
    assert hd == state.shape[-1]


def _chunked(case, fn=pr.retention_chunked, **kw):
    return fn(case["state"], case["norm"], case["layer"], case["slots"],
              case["fresh"], case["starts"], case["counts"], case["q"],
              case["k"], case["v"], case["g"], EPS, **kw)


def test_phi_is_the_symmetric_second_power():
    """``phi(q) . phi(k) = (q . k)^2 / hd`` in the kernels' layout (by
    circular distance) and in the reference's (a <= b), and
    ``canonical_*`` turns the first into the second: a missing sqrt 2
    off the diagonal, or the pairs at distance hd / 2 counted once,
    would show here."""
    rng = np.random.default_rng(0)
    for hd in (16, 128):
        q, k = (jnp.asarray(rng.normal(size=(3, hd)), jnp.float32)
                for _ in range(2))
        want = np.sum(np.asarray(q) * np.asarray(k), -1) ** 2 / hd
        assert pr.phi(q).shape == (3, hd // 2 + 1, hd)
        for phi in (pr.phi, reference_brumby.phi):
            got = np.sum(np.asarray(phi(q) * phi(k)).reshape(3, -1), -1)
            np.testing.assert_allclose(got, want, rtol=0,
                                       atol=1e-5 * want.max())
        assert reference_brumby.phi(q).shape == (3, hd * (hd + 1) // 2)
        np.testing.assert_allclose(pr.canonical_norm(pr.phi(q)),
                                   reference_brumby.phi(q), rtol=1e-6)


@pytest.mark.parametrize("nh,nkv,hd,chunk,hard", [
    (4, 2, 16, 16, False), (10, 2, 16, 8, False), (2, 1, 32, 16, True)])
def test_chunked_form_is_the_attention_form(nh, nkv, hd, chunk, hard):
    """Rows of 37, 0, 16 and 5 tokens from states of their own, a row
    that ends mid-chunk and one with no token; ``hard``: a gate of -3
    on every token, which a naive ``exp(-G)`` does not survive (32
    tokens: e^96 overflows float32)."""
    case = _case(nh, nkv, hd, (37, 0, 16, 5), 64, hard=hard)
    _against_the_attention_form(case, *_chunked(case, chunk=chunk))


@pytest.mark.parametrize("nh,nkv,hd", [(4, 2, 16), (10, 2, 16)])
def test_chunk_kernel_is_the_attention_form(nh, nkv, hd):
    """The kernel under the interpreter at toy widths (a chunk is a
    head's width): rows that share a window with their neighbours."""
    case = _case(nh, nkv, hd, (37, 0, 16, 5), 64)
    _against_the_attention_form(case, *_chunked(
        case, pr.retention_chunk_fwd, chunk=hd, interpret=True))


@pytest.mark.parametrize("kernel", [False, True])
def test_one_token_forms_are_the_attention_form(kernel):
    """One token a row from a state of its own is a row of one token in
    the chunked form; the kernel under the interpreter, and the XLA
    twin."""
    nh, nkv, hd, N = 10, 2, 16, 3
    case = _case(nh, nkv, hd, (1,) * N, N)
    # a fresh row's one weight, (q . k)^2 / hd, stands alone over itself
    # plus eps: a query near its key keeps it far above eps, where a
    # random pair's cancels to the size of float32's rounding of q . k
    case["q"] = jnp.repeat(case["k"], nh // nkv, axis=1) + 0.3 * case["q"]
    step = pr.retention_state_update if kernel else pr.retention_step
    o, state, norm = step(
        case["state"], case["norm"], case["layer"], case["slots"],
        case["fresh"], case["q"], case["k"], case["v"], case["g"], EPS,
        **({"interpret": True} if kernel else {}))
    _against_the_attention_form(case, o, state, norm)


def test_the_state_is_shared_by_a_group_of_query_heads():
    """Five query heads read what one key/value head wrote: permuting
    the query heads inside a group permutes the outputs and leaves the
    state as it was; a query head moved to ANOTHER group reads another
    state."""
    nh, nkv, hd = 10, 2, 16
    case = _case(nh, nkv, hd, (21,), 32)
    o, state, norm = _chunked(case, chunk=16)
    perm = np.array([3, 0, 4, 1, 2, 9, 7, 8, 5, 6])     # inside groups
    o2, state2, norm2 = _chunked({**case, "q": case["q"][:, perm]},
                                 chunk=16)
    np.testing.assert_allclose(o2, np.asarray(o)[:, perm], rtol=0,
                               atol=2e-6)
    np.testing.assert_array_equal(state2, state)
    np.testing.assert_array_equal(norm2, norm)
    across = np.arange(nh)
    across[[0, 5]] = [5, 0]
    o3, state3, _ = _chunked({**case, "q": case["q"][:, across]}, chunk=16)
    np.testing.assert_array_equal(state3, state)        # keys wrote it
    assert np.abs(np.asarray(o3)[:21, 0] - np.asarray(o)[:21, 5]).max() \
        > 1e-3


def test_the_references_two_routes_agree():
    """The attention form and the recurrence a token at a time, both the
    reference's: the one ``logits`` runs and the one ``leading_states``
    runs are the same mixer."""
    rng = np.random.default_rng(2)
    S, nh, nkv, hd = 40, 4, 2, 16
    q, k, v = (jnp.asarray(rng.normal(size=(S, n, hd)), jnp.float32)
               for n in (nh, nkv, nkv))
    g = jnp.asarray(-np.exp(rng.uniform(np.log(0.01), np.log(3.0),
                                        (S, nkv))), jnp.float32)
    q = jnp.repeat(k, nh // nkv, axis=1) + 0.3 * q  # weights far over eps
    with jax.default_matmul_precision("highest"):
        a = reference_brumby.retention(q, k, v, g, EPS)
        b, s, z = reference_brumby.recurrence(q, k, v, g, EPS)
    assert _err(b, np.asarray(a)) <= F32_TIGHT
    assert s.shape == (nkv, 136, hd) and z.shape == (nkv, 136)


# ---------------------------------------------------------------------------
# (c) what is not served with this state is refused by name
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("engine,word", [
    ({"tensor_parallel_size": 2}, "tensor_parallel_size"),
    ({"max_lora_adapters": 2}, "max_lora_adapters"),
    ({"kv_quant": True}, "kv_quant"),
    ({"quant_bits": 8}, "quant_bits"),
    ({"state_manager": {"enable_prefix_caching": True}},
     "wrong recurrent state"),
    ({"state_manager": {"enable_prefix_caching": True,
                        "enable_kv_spill": True}}, "state slot")])
def test_refusals_at_construction(engine, word):
    cfg = TransformerConfig(**TOY)
    with pytest.raises(NotImplementedError,
                       match="power-retention layers.*no position cached"
                             ".*" + word):
        InferenceEngineV2(TransformerLM(cfg), {"dtype": "float32", **engine})


def test_speculation_handoff_and_the_other_forwards_refuse():
    eng = _engine()
    prompts = _prompts((12,))
    with pytest.raises(NotImplementedError, match="verify pass"):
        eng.generate(prompts, max_new_tokens=2, speculative=True)
    eng.put([7], prompts)
    from deepspeed_tpu.inference.v2.serve import handoff
    with pytest.raises(NotImplementedError, match="no state slot"):
        handoff.export_sequence(eng, 7)
    model = TransformerLM(TransformerConfig(**TOY))
    with pytest.raises(NotImplementedError, match="power_retention layers"):
        model.apply(model.init_params(jax.random.PRNGKey(0)),
                    {"input_ids": jnp.zeros((1, 8), jnp.int32)})
    with pytest.raises(ValueError, match="state_dtype"):
        InferenceEngineV2(TransformerLM(TransformerConfig(
            hidden_size=64, num_heads=4, num_layers=2, vocab_size=128)),
            {"dtype": "float32", "state_dtype": "bfloat16"})
