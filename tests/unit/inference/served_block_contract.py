"""What every served block is held to, written once: the clauses a row of
``served_blocks.BLOCKS`` is run through at toy widths on the CPU, through
the ragged engine, against the benchmark's plain float32 reference.

Not collected itself: a block's ``test_<block>_serving.py`` names its row
(``BLOCK``) and lays ``clauses(BLOCK)`` into its own namespace, so the
block's contract cases and its own share one ``Lender`` (``conftest.py``:
``served``, ``lend``), and tier-1 (``--dist loadfile``) spreads the blocks
over its workers. A clause makes a case of each entry the row gives it,
ids ``<block>-<entry>``; a clause the row gives nothing is not laid in;
no clause asks a block's name. Every case serves under uids of its own
and every engine comes back empty.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2 import InferenceEngineV2
from deepspeed_tpu.models import TransformerConfig, TransformerLM
from deepspeed_tpu.telemetry import get_registry
from tests.unit.inference import served_blocks as sb

NEW = 13        # tokens generated where a clause reads what is kept after

CLAUSES = {}    # a clause's argument -> (its test, the entries a row gives)


def clause(name, entries):
    def register(test):
        CLAUSES[name] = (test, entries)
        return test
    return register


def clauses(row):
    """The clauses ``row`` has an entry for, and the hook that makes a
    case of each entry, under the names a block's module takes them by."""
    return {"pytest_generate_tests": pytest_generate_tests,
            **{test.__name__: test for test, entries in CLAUSES.values()
               if entries(row)}}


def pytest_generate_tests(metafunc):
    row = metafunc.module.BLOCK
    for name, (test, entries) in CLAUSES.items():
        if metafunc.function is test:
            metafunc.parametrize(name, list(entries(row)), ids=[
                f"{row.name}-{_label(e)}".rstrip("-") for e in entries(row)])


def _label(entry):
    """An entry's own id, a refusal's word, or nothing."""
    if isinstance(entry, tuple):
        return entry[1].replace(" ", "-")
    return getattr(entry, "id", "")


def _lent(lend, row, case):
    """The engine a put or decode case asks for, and what it must be:
    the kernel that serves it, its cache's leaves, whether a sequence
    keeps state beside its blocks and, where rows take a share of a step
    under a window, that share and the ring it sizes (the window, the
    share and one block: no smaller, and no larger)."""
    eng = lend(**case.spec)
    assert eng.attention_impl == (case.impl or row.impl)
    assert set(eng.kv_cache) == row.leaves
    assert eng._has_state == bool(row.kept)
    if case.row_chunk is not None:
        block = row.manager["block_size"]
        assert eng.max_row_chunk == case.row_chunk
        assert eng.state_manager.ring_blocks * block \
            == row.toy["attn_window"] + case.row_chunk + block
    return eng


def _total(*names):
    return [get_registry().family_total(name) for name in names]


def _chunks():
    return _total("inference_prefill_chunks_total")[0]


def _put_err(eng, row, prompts, spec):
    uids = sb.uids(len(prompts))
    got = eng.put(uids, prompts)
    for uid in uids:
        eng.flush(uid)
    return max(sb.err(got[i], sb.reference(row, p, **spec)[-1])
               for i, p in enumerate(prompts))


@clause("put", lambda row: row.put)
def test_put_logits_match_the_reference(served, lend, put):
    """The row's prompts through ``put()``: in one ragged step where the
    step's budget holds them, in chunk steps (a row continuing from its
    slot, its ring or its blocks) where the entry counts them."""
    row = served.row
    eng = _lent(lend, row, put)
    prompts = sb.prompts(row, put.lengths, put.prompt_seed)
    before = _chunks()
    assert _put_err(eng, row, prompts, put.spec) <= put.limit
    if put.chunks is not None:
        assert _chunks() - before == put.chunks


@clause("decode", lambda row: row.decode)
def test_decode_through_the_cache_matches_the_reference(served, lend,
                                                        decode):
    """The ragged step leaves each row's keys in its blocks (its ring)
    and its state in its slot; decode windows (launched one behind the
    other) read and extend them. float32: at EVERY generated position
    the engine's token is the reference's best on the same prefix, so a
    state, a slot, a tap, a position or a page read wrong shows. bf16:
    the served token's reference logit lies within the bf16 limit of the
    best."""
    row = served.row
    eng = _lent(lend, row, decode)
    prompts = sb.prompts(row, decode.lengths, decode.prompt_seed)
    rises = ("inference_decode_windows_ahead_total",) + decode.rises
    before = _total(*rises)
    outs = eng.generate(prompts, max_new_tokens=decode.new, temperature=0.0,
                        eos_token_id=None, uids=sb.uids(len(prompts)))
    assert all(a > b for a, b in zip(_total(*rises), before)), rises
    for prompt, out in zip(prompts, outs):
        out = np.asarray(out)
        assert len(out) == len(prompt) + decode.new
        ref = sb.reference(row, out[:-1], **decode.spec)[len(prompt) - 1:]
        if decode.spec.get("dtype", "float32") == "float32":
            np.testing.assert_array_equal(out[len(prompt):], ref.argmax(-1))
        else:
            served_logit = ref[np.arange(len(ref)), out[len(prompt):]]
            gap = (ref.max(-1) - served_logit) / np.abs(ref).max(-1)
            assert gap.max() <= sb.BF16_GAP


@clause("alone", lambda row: [row.alone] * bool(row.alone))
def test_rows_in_one_step_are_the_rows_served_alone(served, lend, alone):
    """Rows of unequal lengths packed in one ragged step, then a MIXED
    step (a new prompt beside the first rows' decode tokens), give each
    row what it gets served alone (another bucket of rows, so the sums'
    order; on the same engine afterwards, from whatever its slots and
    pages then hold): rows mix nowhere."""
    row = served.row
    eng = lend()
    prompts = sb.prompts(row, alone.lengths)
    late = sb.prompts(row, (41,), seed=3)[0]
    nxt = [11, 22, 33]
    uids = sb.uids(4)
    first = eng.put(uids[:3], prompts)
    mixed = eng.put(uids, [[t] for t in nxt] + [late])
    for uid in uids:
        eng.flush(uid)

    def close(got, want):
        scale = np.abs(want).max() if alone.relative else 1.0
        np.testing.assert_allclose(got, want, rtol=0,
                                   atol=alone.tol * scale)

    for i, p in enumerate(prompts):
        uid, = sb.uids(1)
        close(eng.put([uid], [p])[0], first[i])
        close(eng.put([uid], [[nxt[i]]])[0], mixed[i])
        eng.flush(uid)
    uid, = sb.uids(1)
    close(eng.put([uid], [late])[0], mixed[3])
    eng.flush(uid)
    assert sb.err(mixed[3], sb.reference(row, late)[-1]) <= sb.F32
    assert sb.err(mixed[1], sb.reference(
        row, np.append(prompts[1], nxt[1]))[-1]) <= sb.F32


@clause("chunked", lambda row: [row.chunked] * bool(row.chunked))
def test_a_prompt_in_put_chunks_is_the_prompt_in_one(served, lend, chunked):
    """``put()`` feeds a prompt set over its step's budget in chunks, a
    row continuing from its slot, its ring and its blocks: the logits of
    one step (which counts no chunk), and the state after them."""
    row = served.row
    whole, parts = lend(**chunked.whole), lend(**chunked.parts)
    prompts = sb.prompts(row, chunked.lengths)
    reg = get_registry()
    a, b = sb.uids(len(prompts)), sb.uids(len(prompts))
    before = _chunks()
    got = parts.put(a, prompts)
    assert _chunks() - before == chunked.chunks
    steps = reg.family_total("inference_ragged_steps_total")
    want = whole.put(b, prompts)
    assert _chunks() - before == chunked.chunks
    assert reg.family_total("inference_ragged_steps_total") - steps == 1
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=sb.F32 * np.abs(want).max())
    if row.kept:
        for ua, ub in zip(a, b):
            for name in row.kept.shapes:
                x, y = (eng.sequence_state(u)[name]
                        for eng, u in ((parts, ua), (whole, ub)))
                np.testing.assert_allclose(x, y, rtol=0,
                                           atol=sb.F32 * np.abs(y).max())
    for eng, us in ((parts, a), (whole, b)):
        for uid in us:
            eng.flush(uid)


_SAMPLED = dict(temperature=0.8, top_p=0.9, top_k=0, seed=5)


def _host_pick(logits, sampled):
    """The first token as the host picked it over ``put()``'s logits
    until generate() stopped fetching them: ``np.argmax``, or the
    sampler's rows under the keys of a row's first draw."""
    if not sampled:
        return np.argmax(logits, axis=-1)
    from deepspeed_tpu.inference.v2.sampling import (fold_in_rows,
                                                     sample_tokens_rowwise)
    n = len(logits)
    keys = fold_in_rows(jax.random.PRNGKey(_SAMPLED["seed"]),
                        jnp.arange(n, dtype=jnp.int32),
                        jnp.zeros(n, jnp.int32))
    return np.asarray(sample_tokens_rowwise(
        jnp.asarray(logits), keys,
        *(jnp.full((n,), _SAMPLED[k], t) for k, t in (
            ("temperature", jnp.float32), ("top_p", jnp.float32),
            ("top_k", jnp.int32)))))


@clause("handed_on", lambda row: row.handed_on)
def test_generate_hands_on_tokens_and_put_fetches_its_logits(
        served, lend, monkeypatch, handed_on):
    """Inside ``generate()`` the prompt's steps hand on device arrays
    and ``[N]`` tokens: nothing ``[rows, vocab]`` wide reaches the host
    (the counter of fetched logits stands, and no array that
    ``jax.device_get`` returns is that large), the host waits for ONE
    ragged step however many chunk steps the prompts take (the others
    are launched ahead), and the first token is the one the host picked
    over ``put()``'s logits, greedy and sampled on one seed, for prompts
    fed in one step, in chunk steps, and in chunk steps that rows of
    unequal length end in apart. ``put()`` and ``step_ragged()`` keep
    their contract: the same logits, fetched once, counted."""
    row = served.row
    eng = lend(**handed_on.spec)
    prompts = sb.prompts(row, handed_on.lengths)
    steps = max(handed_on.chunks, 1)
    vocab = row.toy["vocab_size"]
    pick = _SAMPLED if handed_on.sampled else {"temperature": 0.0}
    names = ("inference_ragged_steps_total",
             "inference_ragged_host_syncs_total",
             "inference_ragged_logits_fetched_bytes_total",
             "inference_prefill_chunks_total")
    fetched = []
    device_get = jax.device_get

    def spy(tree):
        out = device_get(tree)
        fetched.extend(np.size(x) for x in jax.tree.leaves(out))
        return out

    before = _total(*names)
    with monkeypatch.context() as patched:
        patched.setattr(jax, "device_get", spy)
        outs = eng.generate(prompts, max_new_tokens=handed_on.new,
                            eos_token_id=None, uids=sb.uids(len(prompts)),
                            **pick)
    after = _total(*names)
    assert [b - a for a, b in zip(before, after)] \
        == [steps, 1, 0, handed_on.chunks]
    assert fetched and max(fetched) < len(prompts) * vocab, fetched
    uids = sb.uids(len(prompts))
    logits = eng.put(uids, prompts)
    for uid in uids:
        eng.flush(uid)
    assert logits.shape == (len(prompts), vocab)
    assert [b - a for a, b in zip(after, _total(*names))] \
        == [steps, 1, eng._decode_bucket(len(prompts)) * vocab * 4,
            handed_on.chunks]
    np.testing.assert_array_equal(
        [out[len(p)] for out, p in zip(outs, prompts)],
        _host_pick(logits, handed_on.sampled))
    if steps == 1:
        # the one step of a put() is step_ragged(), to the bit
        again = eng.step_ragged(uids, prompts)
        for uid in uids:
            eng.flush(uid)
        np.testing.assert_array_equal(again, logits)


@clause("pinned", lambda row: [row.pinned] * bool(row.pinned))
def test_the_programs_a_call_launches_are_the_pinned_ones(served, lend,
                                                          pinned):
    """The ragged step and the decode windows lower to what the row
    pins, and the first token's pick is a program of its own beside
    them: what the host does between launches changes no launch."""
    row = served.row
    eng = lend(**pinned.spec)
    prompts = sb.prompts(row, pinned.lengths)

    def call():
        for pick in ({"temperature": 0.0}, _SAMPLED):
            eng.generate(prompts, max_new_tokens=5, eos_token_id=None,
                         uids=sb.uids(len(prompts)), **pick)

    got = sb.lowered(eng, call)
    assert {"first_token_greedy", "first_token_sample"} <= set(got)
    assert {name: got.get(name) for name in pinned.programs} \
        == pinned.programs


def _kept_err(eng, row, uid, tokens):
    got = row.kept.held(eng.sequence_state(uid))
    want = row.kept.wanted(row, tokens)
    assert np.shape(got) == np.shape(want)      # the same leading layers
    return sb.layer_err(got, want)


@clause("kept", lambda row: [row.kept] * bool(row.kept))
def test_the_kept_state_after_n_tokens_is_the_references(served, lend,
                                                         kept):
    """``generate(keep_sequences=True)`` leaves its rows tracked, every
    token but the last fed: a row's slot then holds the reference's
    state after them (the leading layers': ahead of every routed expert
    and the next ones), not the state after one token more, and the row
    goes on from there through ``put()``."""
    row = served.row
    built = sb.engine(row)      # its own: the gauge is the last one built's
    assert get_registry().get("inference_state_bytes").value == sum(
        built.kv_cache[name].nbytes for name in kept.shapes)
    eng = lend()
    prompts = sb.prompts(row)
    uids = sb.uids(len(prompts))
    outs = eng.generate(prompts, max_new_tokens=NEW, temperature=0.0,
                        eos_token_id=None, uids=uids, keep_sequences=True)
    assert eng.state_manager.state_slots_in_use() == len(prompts)
    for uid, out in zip(uids, outs):
        out = np.asarray(out)
        assert eng.query(uid)["seen_tokens"] == len(out) - 1
        state = eng.sequence_state(uid)
        assert {k: state[k].shape for k in kept.shapes} == kept.shapes
        err = _kept_err(eng, row, uid, out[:-1])
        assert err <= sb.F32, (uid, err)
        # one token more than was fed: a different state
        assert _kept_err(eng, row, uid, out) > 1e-3
    nxt = eng.put(uids[:1], [outs[0][-1:]])
    assert sb.err(nxt[0], sb.reference(row, outs[0])[-1]) <= sb.F32
    for uid in uids:
        eng.flush(uid)
    assert eng.state_manager.state_slots_in_use() == 0
    with pytest.raises(KeyError, match="not tracked"):
        eng.sequence_state(uids[0])


@clause("control", lambda row: row.controls)
def test_the_control_fails_the_limit_the_engine_passes(served, lend,
                                                       monkeypatch, control):
    """A lower precision where the block keeps something (a state in
    bfloat16, an int8 pool) or a fault in its program reads over the
    entry's multiple of the float32 limit on prompts the engine as it
    stands passes."""
    row = served.row
    prompts = sb.prompts(row)

    def read(eng):
        if control.measure == "logits":
            return _put_err(eng, row, prompts, {})
        uids = sb.uids(len(prompts))
        outs = eng.generate(prompts, max_new_tokens=NEW, temperature=0.0,
                            eos_token_id=None, uids=uids,
                            keep_sequences=True)
        errs = [_kept_err(eng, row, uid, np.asarray(out)[:-1])
                for uid, out in zip(uids, outs)]
        for uid in uids:
            eng.flush(uid)
        return max(errs)

    if control.patch or control.mutate:
        # its own: the program is traced under the patch / the tree is
        # written to
        if control.patch:
            control.patch(monkeypatch)
        eng = sb.engine(row, **control.spec)
        if control.mutate:
            control.mutate(eng)
    else:
        eng = lend(**control.spec)
    if control.leaf:
        name, dtype = control.leaf
        assert eng.kv_cache[name].dtype == jnp.dtype(dtype)
    err = read(eng)
    assert err > control.over * sb.F32, err
    if control.sound is not None:
        sound = lend(**control.sound)
        if control.leaf:
            assert sound.kv_cache[control.leaf[0]].dtype == jnp.float32
        assert read(sound) <= sb.F32


@clause("refusal", lambda row: row.refusals.pairs if row.refusals else ())
def test_refusals_at_construction(served, refusal):
    """What is not served with this block's cache is refused by name,
    before a weight is read."""
    row = served.row
    options, word = refusal
    with pytest.raises(row.refusals.errors,
                       match=row.refusals.match + ".*" + word):
        # its own: it is never built
        InferenceEngineV2(TransformerLM(TransformerConfig(**row.toy)),
                          {"dtype": "float32", **options}, params={})


@clause("refuses", lambda row: [row.refuses])
def test_speculation_handoff_and_the_other_forwards_refuse(served, lend,
                                                           refuses):
    row = served.row
    eng = lend()
    prompts = sb.prompts(row)
    with pytest.raises(NotImplementedError, match=refuses.speculation):
        eng.generate(prompts, max_new_tokens=2, speculative=True,
                     uids=sb.uids(len(prompts)))
    model = TransformerLM(TransformerConfig(**row.toy))
    if refuses.draft:
        with pytest.raises(NotImplementedError, match=refuses.draft):
            eng.load_draft_model(model)
    if refuses.handoff:
        from deepspeed_tpu.inference.v2.serve import handoff
        uids = sb.uids(len(prompts))
        eng.put(uids, prompts)
        with pytest.raises(NotImplementedError, match=refuses.handoff):
            handoff.export_sequence(eng, uids[0])
        for uid in uids:
            eng.flush(uid)
    assert eng.state_manager.tracked_sequences() == 0
    ids = jnp.zeros((1, 8), jnp.int32)
    args = {"apply": ({"input_ids": ids},), "forward_cached": (ids, None, 0)}
    for name in refuses.forwards:
        with pytest.raises(NotImplementedError) as e:
            getattr(model, name)({}, *args.get(name, (ids,)))
        for word in refuses.words:
            assert word in str(e.value), (name, word, str(e.value))
