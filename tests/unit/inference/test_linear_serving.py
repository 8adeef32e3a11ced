"""The ``bailing_hybrid`` block's own: a row that continues from its slot,
``sequence_state`` whatever the leaf's layout, slots freed and reused,
the two forms of the KDA recurrence and the convolution with their
kernels, the router's group limit and the shares of the experts, what
the engine counts, and the trees, leaves and runs of a per-layer
pattern. What every served block is held to (the engine against the
plain reference ``benchmark/reference_ling.py``, its control, its
refusals) is the contract's (``test_served_block_contract.py``), on this
block's row of ``served_blocks.py``, where the limits are justified.

Two forms of one recurrence, both float32: 2e-5 of the largest output
(they read 2e-6).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2 import InferenceEngineV2, paged_model
from deepspeed_tpu.inference.v2.kernels import linear_attention as la
from deepspeed_tpu.inference.v2.paged_model import init_paged_kv_cache
from deepspeed_tpu.inference.v2.ragged.ragged_manager import DSStateManager
from deepspeed_tpu.inference.v2.config_v2 import DSStateManagerConfig
from deepspeed_tpu.models import TransformerConfig, TransformerLM
from deepspeed_tpu.moe.sharded_moe import topk_routing
from deepspeed_tpu.telemetry import get_registry
from tests.unit.inference import served_block_contract as contract
from tests.unit.inference import served_blocks as sb
from tests.unit.inference.served_blocks import F32 as F32_TIGHT, err as _err

BLOCK = sb.BLOCKS["ling-3.0-flash"]
globals().update(contract.clauses(BLOCK))     # the contract's cases of this row
CONFIG, TOY, SEED = BLOCK.config, BLOCK.toy, BLOCK.seed
reference_ling, weights_ling = BLOCK.reference, BLOCK.weights


# ---------------------------------------------------------------------------
# (a) rows, slots and what a sequence keeps
# ---------------------------------------------------------------------------
def test_a_row_continues_from_its_slot(lend):
    """A prompt fed in two put()s of 50 and 37 tokens (the second starts
    mid-chunk from the slot's state and the slot's last three conv
    inputs) is the prompt fed at once."""
    prompt = sb.prompts(BLOCK, (87,))[0]
    want = sb.reference(BLOCK, prompt)[-1]
    eng = lend()
    eng.put([4], [prompt[:50]])
    got = eng.put([4], [prompt[50:]])
    assert _err(got[0], want) <= F32_TIGHT
    eng.flush(4)
    # one token at a time from the third on: the ragged step's rows of one
    eng.put([5], [prompt[:3]])
    eng.put([5], [prompt[3:4]])
    eng.put([5], [prompt[4:6]])
    got = eng.put([5], [prompt[6:]])
    assert _err(got[0], want) <= F32_TIGHT
    eng.flush(5)


@pytest.mark.parametrize("head_dim,leaf", [
    (16, (7, 5, 3, 1, 192)),            # TOY's: 192 channels, one row
    (32, (7, 5, 3, 3, 128))])           # whole lane blocks: rows of 128
def test_sequence_state_reads_the_last_inputs_whatever_the_leafs_layout(
        lend, head_dim, leaf):
    """``sequence_state(uid)["kda_conv"]`` is ``[linear layers, taps - 1,
    3 x heads x d_k]`` whether the leaf keeps an input as one row or as
    rows of 128 lanes, and after a prompt (the ragged step's XLA form)
    and decode steps (the one-token form) layer 0 holds the q, k and v
    projections of the last three tokens fed, oldest first."""
    fields = {"linear_head_dim": head_dim}
    eng = lend(fields=fields)
    assert eng.kv_cache["kda_conv"].shape == leaf
    out, = eng.generate(sb.prompts(BLOCK, (9,)), max_new_tokens=5,
                        temperature=0.0, eos_token_id=None,
                        keep_sequences=True)
    conv = eng.sequence_state(0)["kda_conv"]
    D = TOY["num_heads"] * head_dim
    assert conv.shape == (7, 3, 3 * D)
    assert eng.sequence_state(0)["kda_state"].shape == (
        7, TOY["num_heads"], head_dim, head_dim)
    params = sb.params(BLOCK, fields)
    lp = jax.tree.map(lambda a: a[0], params["kda_layers"])
    cfg = eng.model.cfg
    fed = np.asarray(out[:-1][-3:])
    hn = paged_model._norm(cfg, params["embed"][fed] * cfg.embed_scale,
                           lp["attn_norm"])
    want = np.concatenate([hn @ lp[w] for w in ("wq", "wk", "wv")], axis=-1)
    assert _err(conv[0], want) <= F32_TIGHT
    eng.flush(0)


def test_a_call_that_raises_keeps_nothing(lend):
    eng = lend()
    with pytest.raises(RuntimeError, match="not schedulable"):
        eng.generate(sb.prompts(BLOCK, (250,)), max_new_tokens=20,
                     temperature=0.0, eos_token_id=None, keep_sequences=True)
    assert eng.state_manager.state_slots_in_use() == 0


def test_slots_are_freed_and_reused_without_leaking_state():
    """Two tracked sequences at most: a slot changes hands at flush and
    is NOT cleared; its next owner's first token starts from zeros."""
    # its own: slots nobody has held, and the gauge is the last one built's
    eng = sb.engine(BLOCK, seqs=2)
    sm = eng.state_manager
    a, b, c = sb.prompts(BLOCK, (40, 25, 31), seed=9)
    eng.put([0, 1], [a, b])
    slots = {sm.seqs[u].state_slot for u in (0, 1)}
    assert slots == {1, 2} and sm.state_slots_in_use() == 2
    with pytest.raises(RuntimeError, match="not schedulable"):
        eng.put([2], [c])
    eng.flush(0)
    assert sm.state_slots_in_use() == 1
    dirty = np.asarray(eng.kv_cache["kda_state"])
    assert np.abs(dirty[:, 1:]).max() > 0       # what the old rows left
    got = eng.put([2], [c])
    assert sm.seqs[2].state_slot in slots
    assert _err(got[0], sb.reference(BLOCK, c)[-1]) <= F32_TIGHT
    eng.flush(1), eng.flush(2)
    assert sm.state_slots_in_use() == 0
    reg = get_registry()
    assert reg.get("inference_state_slots_in_use").value == 0
    assert reg.get("inference_state_bytes").value == sum(
        v.size * v.dtype.itemsize for k, v in eng.kv_cache.items()
        if k.startswith("kda_"))


def test_the_generation_loop_reuses_slots_across_calls(lend):
    eng = lend()
    prompts = sb.prompts(BLOCK, (9, 17))
    first = eng.generate(prompts, max_new_tokens=6, temperature=0.0,
                         eos_token_id=None)
    again = eng.generate(prompts, max_new_tokens=6, temperature=0.0,
                         eos_token_id=None)
    for x, y in zip(first, again):
        np.testing.assert_array_equal(x, y)
    assert eng.state_manager.state_slots_in_use() == 0


# ---------------------------------------------------------------------------
# (b) the two forms of the recurrence, and the convolution
# ---------------------------------------------------------------------------
def _kda_rows(lengths, nh=2, d=16, seed=0):
    rng = np.random.default_rng(seed)
    T = int(np.ceil((sum(lengths) + 5) / 64) * 64)

    def rnd(*s):
        return rng.standard_normal(s).astype(np.float32)

    q, k = rnd(T, nh, d), rnd(T, nh, d)
    q /= np.linalg.norm(q, axis=-1, keepdims=True)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    g = -5.0 * np.asarray(jax.nn.sigmoid(3 * rnd(T, nh, d)))
    beta = np.asarray(jax.nn.sigmoid(rnd(T, nh)))
    starts = np.cumsum([0] + list(lengths[:-1])).astype(np.int32)
    return (q, k, rnd(T, nh, d), g, beta), starts, \
        np.asarray(lengths, np.int32)


def _kda_projections(lengths, nh, d, seed=0):
    """A linear layer's flat projections (q, k, v, f [T, nh d], b
    [T, nh]) and its gates' (rate [nh], dt_bias [nh d]): the decay gate
    saturates, so a good share of the tokens sit at the floor."""
    rng = np.random.default_rng(seed)
    T = int(np.ceil((sum(lengths) + 5) / 64) * 64)

    def rnd(*s):
        return rng.standard_normal(s).astype(np.float32)

    tokens = (rnd(T, nh * d), rnd(T, nh * d), rnd(T, nh * d),
              3 * rnd(T, nh * d), rnd(T, nh))
    starts = np.cumsum([0] + list(lengths[:-1])).astype(np.int32)
    return tokens, np.exp(0.3 * rnd(nh)), 0.5 * rnd(nh * d), starts, \
        np.asarray(lengths, np.int32)


def _prepare(nh, d, rate, bias):
    """What the layer's ``prepare`` is to the XLA form: the flat
    projections split by head and through ``kda_inputs``."""
    def prepare(q, k, v, f, b):
        q, k, v, f = (a.reshape(*a.shape[:-1], nh, d) for a in (q, k, v, f))
        return la.kda_inputs(q, k, v, f, b, jnp.asarray(rate)[:, None],
                             jnp.asarray(bias).reshape(nh, d), -5.0)
    return prepare


@pytest.mark.parametrize("form,nh,d", [("xla", 2, 16), ("kernel", 8, 128)])
def test_the_chunked_form_is_the_one_token_form_over_a_row(form, nh, d):
    """Rows of 150 (three chunks, the last partial), 1, 70, 0, 64 and 3
    tokens, one after another in the flat buffer (so no row but the
    first starts on a chunk's boundary, and neighbours share the
    kernel's windows), from states that are not zero, decays down to the
    floor of -5 a token (64 of them would overflow ``exp(-G)``: the
    sub-block factoring is what holds); at scattered slots of layer 1,
    two rows at their first token (their slots' rubbish must not show),
    the row of no token at the null slot. Both forms against
    ``kda_step`` token by token, and every other slot and layer as it
    went in; the kernel (under the TPU interpreter, at the published
    head width) against the XLA form on the same operands too. The
    kernel's sums differ in order only (its solve is exact float32 on
    the VPU inside a sub-block, matmuls across): the same 2e-5 holds
    (it reads 6e-6 on a state, 3e-7 on an output)."""
    tokens, rate, bias, starts, counts = _kda_projections(
        (150, 1, 70, 0, 64, 3), nh, d)
    slots = np.array([3, 1, 7, 0, 5, 8])
    fresh = np.array([0, 1, 0, 0, 0, 1], bool)
    leaf = np.random.default_rng(1).standard_normal(
        (2, 9, nh, d, d)).astype(np.float32)
    rows = (1, jnp.asarray(slots), jnp.asarray(fresh), jnp.asarray(starts),
            jnp.asarray(counts), -5.0)

    prepare = _prepare(nh, d, rate, bias)
    xla = jax.jit(lambda *a: la.kda_chunked(a[:5], prepare, a[5], *rows,
                                            rows_a_step=2))
    kernel = jax.jit(lambda *a: la.kda_chunk_fwd(
        a[:5], jnp.asarray(rate), jnp.asarray(bias), a[5], *rows,
        interpret=True))
    out, new = (kernel if form == "kernel" else xla)(*tokens, leaf)
    out, new = np.asarray(out), np.asarray(new)
    each = [np.asarray(a) for a in prepare(*tokens)]
    want_o = np.zeros_like(out)
    for r, (s0, n) in enumerate(zip(starts, counts)):
        state = jnp.asarray(np.where(fresh[r], 0, leaf[1, slots[r]])[None])
        for t in range(s0, s0 + n):
            o, state = la.kda_step(*(a[t:t + 1] for a in each), state)
            want_o[t] = o[0]
        if n:
            np.testing.assert_allclose(new[1, slots[r]], state[0],
                                       atol=2e-5)
    np.testing.assert_allclose(out, want_o, atol=2e-5)
    others = [i for i in range(9) if i not in slots[counts > 0]]
    np.testing.assert_array_equal(new[1, others], leaf[1, others])
    np.testing.assert_array_equal(new[0], leaf[0])          # its layer
    if form == "kernel":
        xo, xnew = xla(*tokens, leaf)
        np.testing.assert_allclose(out, np.asarray(xo), atol=2e-5)
        np.testing.assert_allclose(new, np.asarray(xnew), atol=2e-5)


def test_the_chunk_kernel_takes_a_buffer_of_any_length():
    """A ragged step's token buffer is a power of two, so it can be
    shorter than the kernel's window of 64: 40 tokens, two rows, the
    second fresh. The kernel pads its operands to whole windows and
    returns the buffer's own 40 rows, the XLA form's."""
    nh, d, T = 8, 128, 40
    tokens, rate, bias, _, _ = _kda_projections((T - 5,), nh, d)
    tokens = tuple(a[:T] for a in tokens)
    leaf = np.random.default_rng(3).standard_normal(
        (1, 3, nh, d, d)).astype(np.float32)
    rows = (0, jnp.asarray([1, 2]), jnp.asarray([False, True]),
            jnp.asarray([0, 25]), jnp.asarray([25, 10]), -5.0)

    prepare = _prepare(nh, d, rate, bias)
    out, new = jax.jit(lambda *a: la.kda_chunk_fwd(
        a[:5], jnp.asarray(rate), jnp.asarray(bias), a[5], *rows,
        interpret=True))(*tokens, leaf)
    xo, xnew = jax.jit(lambda *a: la.kda_chunked(
        a[:5], prepare, a[5], *rows))(*tokens, leaf)
    assert out.shape == (T, nh, d)
    np.testing.assert_allclose(out, np.asarray(xo), atol=2e-5)
    np.testing.assert_allclose(new, np.asarray(xnew), atol=2e-5)
    np.testing.assert_array_equal(np.asarray(out)[35:], 0)   # no row's


@pytest.mark.parametrize("kept", ["float32", "bfloat16"])
def test_the_decode_kernel_is_the_one_token_form_in_place(kept):
    """``kda_state_update`` under the TPU interpreter at the published
    head width: rows at scattered slots of layer 1, two of them at their
    first token (their slots' old content must not show), one at the
    null slot; every other slot and layer comes back as it went in."""
    rng = np.random.default_rng(0)
    N, nh, d, L, S = 6, 16, 128, 2, 9

    def rnd(*s):
        return rng.standard_normal(s).astype(np.float32)

    q, k, v = rnd(N, nh, d), rnd(N, nh, d), rnd(N, nh, d)
    g = -5 * np.asarray(jax.nn.sigmoid(rnd(N, nh, d)))
    beta = np.asarray(jax.nn.sigmoid(rnd(N, nh)))
    leaf = jnp.asarray(rnd(L, S, nh, d, d), kept)
    slots = np.array([3, 1, 7, 0, 5, 8])
    fresh = np.array([0, 1, 0, 0, 0, 1], bool)
    o, new = jax.jit(lambda *a: la.kda_state_update(*a, interpret=True))(
        leaf, jnp.int32(1), jnp.asarray(slots), jnp.asarray(fresh),
        q, k, v, g, beta)
    assert new.dtype == leaf.dtype
    old = np.asarray(leaf, np.float32)
    start = np.where(fresh[:, None, None, None], 0, old[1, slots])
    want_o, want_s = la.kda_step(q, k, v, g, beta, jnp.asarray(start))
    tol = 1e-4 if kept == "float32" else 0.2    # a state rounded to 8 bits
    np.testing.assert_allclose(o, want_o, atol=1e-4)
    new = np.asarray(new, np.float32)
    np.testing.assert_allclose(new[1, slots], want_s, atol=tol)
    others = [i for i in range(S) if i not in slots]
    np.testing.assert_array_equal(new[1, others], old[1, others])
    np.testing.assert_array_equal(new[0], old[0])


# (slots, fresh) of six rows in a leaf of nine slots: the slot order is
# never the row order
CONV_ROWS = {
    # two rows at their first token, one at the null slot
    "kept_and_fresh": ([3, 1, 7, 0, 5, 8], [0, 1, 0, 0, 0, 1]),
    # padded and masked rows share the null slot, whatever they claim
    "null_slot_shared": ([0, 4, 0, 2, 0, 0], [0, 0, 1, 1, 0, 1]),
    "every_row_fresh": ([8, 6, 4, 2, 1, 3], [1, 1, 1, 1, 1, 1]),
}


@pytest.mark.parametrize("proj,kept", [
    ("float32", "float32"), ("bfloat16", "float32"),
    ("bfloat16", "bfloat16")])
@pytest.mark.parametrize("rows", sorted(CONV_ROWS))
def test_the_conv_kernel_is_the_one_token_convolution_in_place(
        rows, proj, kept):
    """``kda_conv_update`` under the interpreter, 16 lane blocks a part,
    on layer 1 of a lane-dense leaf: the convolved q, k and v and the
    slots' shifted inputs are ``causal_conv_step``'s on what the slots
    held (zeros for a fresh row), every other slot and layer comes back
    as it went in. A slot's inputs are copies: equal to the bit. The
    sum of bf16 projections is equal to the bit too once rounded; on
    float32 ones the two programs differ by where the CPU's compiler
    contracts a product and a sum (an ulp). Rows that share the null
    slot see each other's writes there in a kernel that copies the
    rows' slots itself, and not in a gather: of them, only the fresh
    ones' outputs are defined, and the slot's newest input is one row's
    (under the interpreter, where a copy is whole; on a chip the copies
    of several rows are in flight side by side and may interleave on
    the null slot, which nothing reads)."""
    rng = np.random.default_rng(0)
    N, D, K, L, S = 6, 16 * 128, 4, 2, 9
    slots, fresh = (np.asarray(a) for a in CONV_ROWS[rows])
    fresh = fresh.astype(bool)
    shape = la.conv_leaf_shape(L, S, K, 3 * D)
    assert shape == (L, S, K - 1, 48, 128)
    leaf = jnp.asarray(rng.standard_normal(shape), kept)
    q, k, v = (jnp.asarray(rng.standard_normal((N, D)), proj)
               for _ in range(3))
    taps = jnp.asarray(rng.standard_normal((K, 3 * D)), proj)
    mixed, new = jax.jit(lambda *a: la.kda_conv_update(*a, interpret=True))(
        leaf, jnp.int32(1), jnp.asarray(slots), jnp.asarray(fresh),
        q, k, v, taps)
    assert new.dtype == leaf.dtype and new.shape == leaf.shape
    held = jnp.where(fresh[:, None, None], 0,
                     leaf[1, slots].reshape(N, K - 1, 3 * D))
    defined = (slots > 0) | fresh
    got = np.asarray(new[1, slots].reshape(N, K - 1, 3 * D), np.float32)
    for i, (x, y) in enumerate(zip((q, k, v), mixed)):
        part = slice(i * D, (i + 1) * D)
        want_y, want_s = la.causal_conv_step(
            x, taps[:, part], held[..., part], jax.nn.silu)
        assert y.dtype == x.dtype and y.shape == x.shape
        y, want_y = (np.asarray(a, np.float32)[defined]
                     for a in (y, want_y))
        if proj == "bfloat16":
            np.testing.assert_array_equal(y, want_y)
        else:
            np.testing.assert_allclose(y, want_y, rtol=2e-6, atol=2e-6)
        np.testing.assert_array_equal(
            got[..., part][slots > 0],
            np.asarray(want_s, np.float32)[slots > 0])
        if (slots == 0).any():          # its newest input: one row's own
            newest = np.asarray(new[1, 0].reshape(K - 1, 3 * D),
                                np.float32)[-1, part]
            assert any(np.array_equal(newest, np.asarray(x[r], np.float32))
                       for r in np.flatnonzero(slots == 0))
    old = np.asarray(leaf, np.float32)
    new = np.asarray(new, np.float32)
    others = [i for i in range(1, S) if i not in slots]
    np.testing.assert_array_equal(new[1, others], old[1, others])
    np.testing.assert_array_equal(new[0], old[0])


def test_a_fresh_row_starts_from_zeros_whatever_its_slot_holds():
    tokens, starts, counts = _kda_rows((70, 20))
    tokens = tuple(jnp.asarray(a) for a in tokens)
    leaf = np.random.default_rng(2).standard_normal(
        (1, 3, 2, 16, 16)).astype(np.float32)
    run = jax.jit(lambda leaf, fresh: la.kda_chunked(
        tokens, lambda *t: t, leaf, 0, jnp.asarray([1, 2]), fresh,
        jnp.asarray(starts), jnp.asarray(counts), -5.0))
    o_dirty, _ = run(leaf, jnp.asarray([True, False]))
    o_clean, _ = run(leaf.copy() * np.asarray([1, 0, 1])[None, :, None,
                                                        None, None],
                     jnp.asarray([False, False]))
    np.testing.assert_allclose(np.asarray(o_dirty), np.asarray(o_clean),
                               atol=1e-6)


def test_the_convolution_stops_at_a_rows_first_token():
    """The flat form against the one-token form a row, rows of 150, 1,
    2, 0 and 64 tokens, from conv states that are not zero."""
    rng = np.random.default_rng(4)
    lengths = (150, 1, 2, 0, 64)
    T, D, K = 256, 6, 4
    starts = np.cumsum([0] + list(lengths[:-1])).astype(np.int32)
    x = rng.standard_normal((T, D)).astype(np.float32)
    taps = rng.standard_normal((K, D)).astype(np.float32)
    held = rng.standard_normal((len(lengths), K - 1, D)).astype(np.float32)
    row_ids = np.zeros(T, np.int32)
    for r, (s0, n) in enumerate(zip(starts, lengths)):
        row_ids[s0:s0 + n] = r
    y, new = la.causal_conv_rows(
        jnp.asarray(x), jnp.asarray(taps), jnp.asarray(held),
        jnp.asarray(row_ids), jnp.asarray(starts),
        jnp.asarray(lengths, jnp.int32), jax.nn.silu)
    for r, (s0, n) in enumerate(zip(starts, lengths)):
        state = jnp.asarray(held[r:r + 1])
        for t in range(s0, s0 + n):
            yt, state = la.causal_conv_step(x[t:t + 1], taps, state,
                                            jax.nn.silu)
            np.testing.assert_allclose(y[t], yt[0], atol=1e-6)
        np.testing.assert_allclose(new[r], state[0], atol=1e-6)


# ---------------------------------------------------------------------------
# (c) the router's group limit and the share of the experts
# ---------------------------------------------------------------------------
def test_the_group_limit_against_a_plain_loop():
    """16 experts in 4 groups, the best 2 groups kept, top 4: a token at
    a time in plain Python."""
    rng = np.random.default_rng(3)
    logits = rng.normal(size=(40, 16)).astype(np.float32) * 1.5
    bias = (rng.normal(size=(16,)) * 0.3).astype(np.float32)
    chosen, weights = topk_routing(jnp.asarray(logits), 4, "sigmoid",
                                   jnp.asarray(bias), True, 2.5,
                                   n_group=4, topk_group=2)
    scores = 1.0 / (1.0 + np.exp(-logits))
    for t in range(40):
        pick = scores[t] + bias
        group = [np.sort(pick[g * 4:(g + 1) * 4])[-2:].sum()
                 for g in range(4)]
        kept = np.argsort(group)[-2:]
        stands = [e for e in range(16) if e // 4 in kept]
        want = sorted(stands, key=lambda e: -pick[e])[:4]
        assert sorted(np.asarray(chosen)[t]) == sorted(want)
        w = {int(e): float(v) for e, v in zip(np.asarray(chosen)[t],
                                              np.asarray(weights)[t])}
        total = sum(scores[t][e] for e in want)
        for e in want:                          # weights: no bias
            assert w[e] == pytest.approx(scores[t][e] / total * 2.5,
                                         rel=1e-5)
    # the reference's own routing is that loop too
    ref_i, ref_w = reference_ling.route(
        jnp.asarray(scores), jnp.asarray(bias),
        dict(moe_n_group=4, moe_topk_group=2, moe_top_k=4,
             moe_routed_scale=2.5))
    np.testing.assert_array_equal(np.sort(ref_i, -1), np.sort(chosen, -1))
    np.testing.assert_allclose(np.sort(ref_w, -1), np.sort(weights, -1),
                               rtol=1e-6)


def test_the_four_shares_add_up_to_the_uncut_layer():
    """The guide's test of the cut: 16 experts over four chips of 4. Each
    chip's expert layer (the program's, told which experts it holds)
    routes over all 16 and computes its own experts' part and the shared
    expert; the four parts, with the shared expert counted ONCE, are
    what the uncut reference layer gives with all 16."""
    fields = {**TOY, "moe_experts_held": 0, "moe_experts_first": 0}
    whole = weights_ling.make(fields, SEED, "float32")["layers"]
    assert whole["e_gate"].shape[1] == 16
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((37, TOY["hidden_size"])),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        uncut = reference_ling._expert_mlp(x, whole, 1, fields) - x
        small = {k: v[1] for k, v in whole.items()
                 if k not in ("e_gate", "e_up", "e_down")}
        hn = reference_ling._rms_norm(x, small["mlp_norm"],
                                      fields["norm_eps"])
        shared = reference_ling._swiglu(hn, small["shared_gate"],
                                        small["shared_up"],
                                        small["shared_down"])
        parts, touched = [], 0
        for chip in range(4):
            cfg = TransformerConfig(**{**TOY, "moe_experts_held": 4,
                                       "moe_experts_first": 4 * chip})
            held = tuple(whole[k][:, 4 * chip:4 * chip + 4]
                         for k in ("e_gate", "e_up", "e_down"))
            out, topi = paged_model._moe_routed(
                cfg, small, hn, held, 1,
                router_precision=jax.lax.Precision.HIGHEST)
            parts.append(out - shared)
            stats = paged_model._moe_stats(
                topi, jnp.ones((37,), bool), 4, 4 * chip)
            touched += float(stats[1])
            # the reference is given the same share
            share = {**whole, **dict(zip(("e_gate", "e_up", "e_down"),
                                         held))}
            ref = reference_ling._expert_mlp(
                x, share, 1, {**TOY, "moe_experts_held": 4,
                              "moe_experts_first": 4 * chip}) - x
            np.testing.assert_allclose(out, ref, atol=2e-5)
        np.testing.assert_allclose(sum(parts) + shared, uncut, atol=2e-5)
    assert touched == 37 * 4        # every pick is held by exactly one
    assert max(float(jnp.abs(p).max()) for p in parts) > 0.01


def test_a_share_takes_a_launch_of_any_length_in_runs(monkeypatch):
    """A launch longer than a run goes through in runs, the last one
    padded: 21 tokens in runs of 8 are the 21 tokens at once."""
    cfg = TransformerConfig(**TOY)
    lp = jax.tree.map(lambda a: a[1],
                      weights_ling.make(TOY, SEED, "float32")["layers"])
    x = jnp.asarray(np.random.default_rng(1).standard_normal(
        (21, TOY["hidden_size"])), jnp.float32)
    at_once, picks = paged_model._moe_routed(cfg, lp, x)
    monkeypatch.setattr(paged_model, "_SHARE_TOKENS", 8)
    monkeypatch.setattr(paged_model, "_SHARE_ROWS", 1)   # the floor decides
    assert paged_model.moe_share_runs(cfg, 21, jnp.float32) == (3, 8)
    in_runs, picks_runs = paged_model._moe_routed(cfg, lp, x)
    np.testing.assert_array_equal(picks, picks_runs)
    np.testing.assert_allclose(in_runs, at_once, atol=1e-6)
    assert float(jnp.abs(at_once).max()) > 0.01


def test_the_share_is_counted_over_held_experts_only():
    topi = jnp.asarray([[0, 5, 6, 15], [4, 5, 7, 9]])
    valid = jnp.asarray([True, True])
    stats = np.asarray(paged_model._moe_stats(topi, valid, 4, 4))
    assert list(stats[:3]) == [1.0, 5.0, 4.0]   # rows to 4..7; 4 touched
    assert stats[3] == pytest.approx(2 / 5)     # expert 5 has two
    whole = np.asarray(paged_model._moe_stats(topi, valid, 16))
    assert list(whole[:3]) == [1.0, 8.0, 7.0]


def test_the_engine_counts_held_rows(lend):
    reg = get_registry()
    eng = lend()                        # registers the families
    before = reg.get("moe_routed_rows_total").labels(
        program="ragged_step").value
    rows0 = reg.get("inference_state_rows_total").labels(
        program="ragged_step").value
    eng.put([0, 1], sb.prompts(BLOCK, (12, 8)))
    routed = reg.get("moe_routed_rows_total").labels(
        program="ragged_step").value - before
    # 20 tokens x 4 picks x 6 expert layers, of which a share is held
    assert 0 < routed < 20 * 4 * 6
    assert reg.get("inference_state_rows_total").labels(
        program="ragged_step").value - rows0 == 2
    eng.flush(0), eng.flush(1)


def test_the_engine_counts_the_steps_the_chunk_kernel_took(lend,
                                                           monkeypatch):
    """``inference_linear_chunk_kernel_steps_total`` follows
    ``chunk_kernel_serves`` a ragged step: 0 here (the CPU, toy widths:
    the XLA form), one a step where it says yes; and what it asks is a
    TPU, head widths of whole lane blocks and heads in whole steps."""
    from deepspeed_tpu.inference.v2 import engine_v2
    reg = get_registry()
    eng = lend()
    steps = reg.get("inference_linear_chunk_kernel_steps_total")
    before = steps.value
    eng.put([0, 1], sb.prompts(BLOCK, (12, 8)))
    assert steps.value == before
    monkeypatch.setattr(engine_v2, "chunk_kernel_serves", lambda leaf: True)
    eng.put([2], sb.prompts(BLOCK, (9,)))
    assert steps.value == before + 1
    eng.flush(0), eng.flush(1), eng.flush(2)

    def leaf(nh, dk, dv):
        return jax.ShapeDtypeStruct((7, 129, nh, dk, dv), jnp.float32)

    assert not la.chunk_kernel_serves(leaf(32, 128, 128))    # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert la.chunk_kernel_serves(leaf(32, 128, 128))
    assert la.chunk_kernel_serves(leaf(8, 256, 128))
    assert la.chunk_kernel_serves(leaf(24, 128, 128))        # 3 steps
    assert not la.chunk_kernel_serves(leaf(4, 16, 16))       # TOY's
    assert not la.chunk_kernel_serves(leaf(12, 128, 128))    # 1.5 steps
    assert not la.chunk_kernel_serves(leaf(4, 128, 128))     # half a tile


def test_the_engine_counts_the_steps_the_conv_kernel_took(lend, monkeypatch):
    """``inference_linear_conv_kernel_steps_total`` follows
    ``conv_kernel_serves`` a decode step LAUNCHED: 0 here (the CPU, toy
    widths: the XLA form), a window's steps a fused window and one a
    per-token step where it says yes; and what it asks is a TPU and a
    leaf whose channels are whole lane blocks, each of q, k and v whole
    (16, 128) tiles."""
    from deepspeed_tpu.inference.v2 import engine_v2
    reg = get_registry()
    eng = lend()                        # decode_window 4
    steps = reg.get("inference_linear_conv_kernel_steps_total")
    before = steps.value
    prompts = sb.prompts(BLOCK, (12, 8))
    eng.generate(prompts, max_new_tokens=9, temperature=0.0,
                 eos_token_id=None)
    assert steps.value == before
    monkeypatch.setattr(engine_v2, "conv_kernel_serves", lambda leaf: True)
    eng.generate(prompts, max_new_tokens=9, temperature=0.0,
                 eos_token_id=None)     # 8 decode steps: two windows of 4
    assert steps.value == before + 8
    eng.put([0], prompts[:1])
    eng._decode_batch_greedy([0], [1])
    assert steps.value == before + 9
    eng.flush(0)

    def leaf(width, taps=4):
        return jax.ShapeDtypeStruct(
            la.conv_leaf_shape(7, 129, taps, width), jnp.float32)

    assert leaf(3 * 4096).shape == (7, 129, 3, 96, 128)
    assert not la.conv_kernel_serves(leaf(3 * 4096))         # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert la.conv_kernel_serves(leaf(3 * 4096))
    assert la.conv_kernel_serves(leaf(3 * 2048, taps=2))
    assert not la.conv_kernel_serves(leaf(3 * 64))           # TOY's
    assert not la.conv_kernel_serves(leaf(3 * 1024))         # half a tile


def test_the_engine_counts_the_steps_the_attention_kernels_took_one_token(
        lend, monkeypatch):
    """``inference_attention_one_token_steps_total`` follows
    ``one_token_tile_serves`` a decode step LAUNCHED: 0 here (the
    CPU: the latent layer's decode launches are the gathering
    reference), a window's steps a fused window and one a per-token
    step where it says yes; and what it asks is a TPU and a latent pool
    or a pool the tiled variant serves."""
    import importlib
    from deepspeed_tpu.inference.v2 import engine_v2
    ra = importlib.import_module(      # the package exports the function
        "deepspeed_tpu.inference.v2.kernels.ragged_attention")
    reg = get_registry()
    eng = lend()                        # decode_window 4
    steps = reg.get("inference_attention_one_token_steps_total")
    before = steps.value
    prompts = sb.prompts(BLOCK, (12, 8))
    eng.generate(prompts, max_new_tokens=9, temperature=0.0,
                 eos_token_id=None)
    assert steps.value == before
    asked = []
    monkeypatch.setattr(engine_v2, "one_token_tile_serves",
                        lambda *a: asked.append(a) or True)
    eng.generate(prompts, max_new_tokens=9, temperature=0.0,
                 eos_token_id=None)     # 8 decode steps: two windows of 4
    assert steps.value == before + 8
    eng.put([0], prompts[:1])
    eng._decode_batch_greedy([0], [1])
    assert steps.value == before + 9
    eng.flush(0)
    cfg = eng.model.cfg                 # a latent pool
    assert set(asked) == {(True, cfg.head_dim, cfg.kv_heads)}

    assert not ra.one_token_tile_serves(True, 192, 32)       # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ra.one_token_tile_serves(True, 192, 32)
    assert ra.one_token_tile_serves(False, 64, 32)           # OPT's
    assert ra.one_token_tile_serves(False, 128, 4)           # GQA at 128
    assert not ra.one_token_tile_serves(False, 96, 32)       # pipelined


# ---------------------------------------------------------------------------
# (d) trees, leaves, runs
# ---------------------------------------------------------------------------
def test_the_tree_keeps_a_stack_a_layer_kind():
    cfg = TransformerConfig(**TOY)
    assert cfg.layer_kinds == ("kda",) * 5 + ("mla",) + ("kda",) * 2
    assert cfg.has_state and cfg.experts_held == 4
    tree = jax.eval_shape(TransformerLM(cfg).init_params,
                          jax.random.PRNGKey(0))
    assert sorted(tree) == ["embed", "final_norm", "kda_layers", "layers",
                            "lead_layers", "lm_head", "mla_layers"]
    assert tree["kda_layers"]["wq"].shape == (7, 64, 64)
    assert tree["kda_layers"]["conv"].shape == (7, 4, 192)
    assert tree["kda_layers"]["wg"].shape == (7, 64, 4)
    assert tree["mla_layers"]["wq"].shape == (1, 64, 4 * 32)
    assert "wq_a" not in tree["mla_layers"]
    assert tree["layers"]["e_gate"].shape == (6, 4, 64, 32)
    assert tree["layers"]["moe_gate_w"].shape == (6, 64, 16)
    assert sorted(tree["lead_layers"]) == ["mlp_norm", "w_down", "w_gate",
                                           "w_up"]
    made = jax.eval_shape(lambda: weights_ling.make(TOY, 1, "float32"))
    assert jax.tree.map(lambda a: a.shape, made) == jax.tree.map(
        lambda a: a.shape, tree)
    assert paged_model._layer_runs(cfg) == [
        ("kda", False, 0, 2), ("kda", True, 2, 3), ("mla", True, 5, 1),
        ("kda", True, 6, 2)]


def test_the_cache_keeps_a_leaf_a_layer_kind():
    cfg = TransformerConfig(**TOY)
    cache = jax.eval_shape(lambda: init_paged_kv_cache(
        cfg, 9, 16, jnp.bfloat16, state_slots=4))
    assert cache["latent"].shape == (1, 9, 16, 128)      # latent layers
    assert cache["kda_state"].shape == (7, 5, 4, 16, 16)  # by slot
    assert cache["kda_conv"].shape == (7, 5, 3, 1, 192)   # one row: toy
    assert cache["kda_state"].dtype == cache["kda_conv"].dtype \
        == jnp.float32
    assert cache["latent"].dtype == jnp.bfloat16


def test_the_published_pattern_and_sizes():
    """The configuration's fields: 8 layers, the latent one at 5; the
    10.54 GB of the issue from the tree's shapes."""
    cfg = TransformerConfig(**CONFIG["fields"])
    assert cfg.layer_kinds == ("kda",) * 5 + ("mla",) + ("kda",) * 2
    tree = jax.eval_shape(TransformerLM(cfg).init_params,
                          jax.random.PRNGKey(0))
    count = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))
    assert 2 * count == pytest.approx(10.54e9, rel=1e-3)
    cache = jax.eval_shape(lambda: init_paged_kv_cache(
        cfg, 3201, 16, jnp.bfloat16, state_slots=128))
    state = sum(int(np.prod(cache[k].shape)) * 4
                for k in ("kda_state", "kda_conv"))
    assert state / 129 == pytest.approx(15.0e6, rel=0.05)  # 15.0 MB a row


def test_the_old_trees_are_what_they_were():
    cfg = TransformerConfig(**sb.BLOCKS["joyai-llm-flash"].toy)
    assert not cfg.has_state and cfg.layer_kinds == ("mla",) * 3
    assert paged_model._layer_runs(cfg) == [("mla", False, 0, 1),
                                            ("mla", True, 1, 2)]
    tree = jax.eval_shape(TransformerLM(cfg).init_params,
                          jax.random.PRNGKey(0))
    assert sorted(tree) == ["embed", "final_norm", "layers", "lead_layers",
                            "lm_head"]
    assert "wq_a" in tree["layers"] and "wg" not in tree["layers"]
    cache = jax.eval_shape(lambda: init_paged_kv_cache(cfg, 5, 16,
                                                       jnp.bfloat16))
    assert set(cache) == {"latent"} and cache["latent"].shape[0] == 3
    # and seeded as they were: the leaves that existed draw the keys
    # they drew (sums read on the tree of commit 0c7ccb8, PRNGKey(3))
    seeded = TransformerLM(cfg).init_params(jax.random.PRNGKey(3))
    for path, want in ((("embed",), -1.3219291393684216),
                       (("lm_head",), -3.9146002336599395),
                       (("layers", "wq_a"), 0.6759318736699242),
                       (("layers", "wo"), -0.8488620366340172),
                       (("layers", "e_up"), -2.132007654832478),
                       (("lead_layers", "wkv_b"), 1.00273535138831),
                       (("lead_layers", "w_down"), 1.2854191246841415)):
        leaf = seeded
        for key in path:
            leaf = leaf[key]
        assert float(np.asarray(leaf, np.float64).sum()) \
            == pytest.approx(want, abs=1e-6), path


# ---------------------------------------------------------------------------
# what is refused
# ---------------------------------------------------------------------------
def test_what_else_is_refused(lend):
    """(Speculation, a draft model and the other forward: the contract's.)"""
    with pytest.raises(NotImplementedError, match="state slot"):
        lend().state_manager.adopt_sequence(5, 1, 3, [1, 2, 3])
    with pytest.raises(ValueError, match="state_dtype"):
        InferenceEngineV2(TransformerLM(TransformerConfig(
            vocab_size=64, hidden_size=32, intermediate_size=64,
            num_layers=1, num_heads=4, max_seq_len=64)),
            {"dtype": "float32", "state_dtype": "bfloat16"})
    with pytest.raises(ValueError, match="state slots"):
        DSStateManager(DSStateManagerConfig(max_tracked_sequences=8),
                       state_slots=4)
    plain = InferenceEngineV2(TransformerLM(TransformerConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64,
        num_layers=1, num_heads=4, max_seq_len=64)), {"dtype": "float32"})
    with pytest.raises(ValueError, match="keeps no recurrent state"):
        plain.sequence_state(0)


@pytest.mark.parametrize("change,word", [
    ({"attention": "mha", "moe_first_dense_layers": 0,
      "moe_experts_held": 0, "moe_experts_first": 0, "attn_gate": "none"},
     "layer pattern"),
    ({"linear_head_dim": 0}, "layer pattern"),
    ({"linear_decay_floor": 0.5}, "layer pattern"),
    ({"attn_gate": "channel"}, "attn_gate"),
    ({"moe_n_group": 3}, "moe_n_group"),
    ({"moe_topk_group": 5}, "moe_n_group"),
    ({"moe_n_group": 8, "moe_topk_group": 1}, "moe_n_group"),
    ({"moe_experts_first": 14}, "moe_experts_held"),
    ({"q_lora_rank": -1}, "q_lora_rank")])
def test_the_configuration_refuses_what_it_cannot_mean(change, word):
    with pytest.raises((ValueError, NotImplementedError), match=word):
        TransformerConfig(**{**TOY, **change})
