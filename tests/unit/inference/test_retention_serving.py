"""The ``brumby`` block's own: the sixth layer kind as the source spells
it, a cache of state leaves whose sequences own no block, the scheduler
over such sequences, a slot that changes hands, and the forms of the
power-retention recurrence and their kernels against the reference's
attention form. What every served block is held to (the engine against
the plain reference ``benchmark/reference_brumby.py``, its control, its
refusals) is the contract's (``test_served_block_contract.py``), on this
block's row of ``served_blocks.py``, where the limits are justified.

Two forms of one recurrence, both float32: 2e-5 of the largest output
(they read 1e-6).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2 import paged_model
from deepspeed_tpu.inference.v2.kernels import power_retention as pr
from deepspeed_tpu.models import TransformerConfig, TransformerLM
from deepspeed_tpu.telemetry import get_registry
from tests.unit.inference import served_block_contract as contract
from tests.unit.inference import served_blocks as sb
from tests.unit.inference.served_blocks import F32 as F32_TIGHT, err as _err

BLOCK = sb.BLOCKS["brumby-14b-base"]
globals().update(contract.clauses(BLOCK))     # the contract's cases of this row
TOY, reference_brumby = BLOCK.toy, BLOCK.reference
EPS = 1e-6


# ---------------------------------------------------------------------------
# (a) the configuration, the cache and its manager
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
    granite = sb.BLOCKS["granite-4.0-h-small"].config
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


def test_the_cache_is_the_state_leaves_and_a_sequence_owns_no_block(lend):
    eng = lend()
    assert eng.attention_impl == "none:no-layer-caches-positions"
    assert set(eng.kv_cache) == {"retention_state", "retention_norm"}
    # [layers, slots + 1, kv heads, hd / 2 + 1, hd, hd], the normaliser's
    # rows rounded up to a tile of 8
    assert eng.kv_cache["retention_state"].shape == (2, 5, 2, 9, 16, 16)
    assert eng.kv_cache["retention_norm"].shape == (2, 5, 2, 16, 16)
    sm = eng.state_manager
    assert not sm.paged and sm.max_blocks_per_seq == 1
    prompts = sb.prompts(BLOCK)
    assert eng.can_schedule([0, 1, 2], [len(p) for p in prompts])
    eng.put([0, 1, 2], prompts)
    assert all(sm.seqs[u].blocks == [] for u in (0, 1, 2))
    # a slot each of the four behind the null one, whoever held them before
    slots = {sm.seqs[u].state_slot for u in (0, 1, 2)}
    assert len(slots) == 3 and slots <= {1, 2, 3, 4}
    assert sm.seqs[1].seen_tokens == 70 and sm.state_slots_in_use() == 3
    # what bounds the batch is the slots, and a sequence's length
    eng.put([3], sb.prompts(BLOCK, (4,)))
    assert not eng.can_schedule([4], [4])
    assert not eng.can_schedule([0], [256])
    built = sb.engine(BLOCK)    # its own: the gauge is the last one built's
    assert get_registry().get("inference_state_bytes").value == sum(
        v.nbytes for v in built.kv_cache.values())
    for uid in range(4):
        eng.flush(uid)
    assert sm.state_slots_in_use() == 0 and sm.tracked_sequences() == 0


def test_the_schedulers_streams_are_generates(lend):
    """The SplitFuse scheduler composes steps of prompt chunks beside
    decode rows from sequences that own no block: scheduling changes
    composition, never results; and off a TPU, at heads 16 wide, the XLA
    twins of both kernels serve them."""
    from deepspeed_tpu.inference.v2.scheduler import DynamicSplitFuseScheduler
    eng = lend()
    prompts = sb.prompts(BLOCK, (50, 21, 9))
    reg = get_registry()
    kernels = {n: reg.family_total(n) for n in (
        "inference_retention_state_kernel_steps_total",
        "inference_retention_chunk_kernel_launches_total")}
    want = eng.generate(prompts, max_new_tokens=9, temperature=0.0,
                        eos_token_id=None)
    assert kernels == {n: reg.family_total(n) for n in kernels}
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


def test_a_slot_reused_by_a_later_sequence_leaks_no_state(lend):
    """A slot is not cleared when it changes hands: the next sequence's
    first token starts from zeros in the program. The second sequence to
    hold slot 1 gets the logits and the state it gets on a new engine."""
    eng = lend(seqs=1)
    first = sb.prompts(BLOCK, (50,))[0]
    second = sb.prompts(BLOCK, (23,), seed=4)[0]
    eng.put([0], [first])
    assert eng.state_manager.seqs[0].state_slot == 1
    eng.flush(0)
    got = eng.put([1], [second])
    assert eng.state_manager.seqs[1].state_slot == 1
    fresh = sb.engine(BLOCK, seqs=1)    # its own: a slot nobody has held
    want = fresh.put([1], [second])
    np.testing.assert_array_equal(got, want)
    held = BLOCK.kept.held
    np.testing.assert_array_equal(held(eng.sequence_state(1)),
                                  held(fresh.sequence_state(1)))
    assert _err(got[0], sb.reference(BLOCK, second)[-1]) <= F32_TIGHT
    eng.flush(1)


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
