"""The per-head serving programs against logits stored from the parent
of PR 38, which folded their five scan bodies into one
(``paged_model._scan_layers``; each program keeps only its ``attend``).

``fixtures/paged_program_logits_pr37.json`` was written by running
``run_programs`` below on the tree of PR 37 (commit 7118cbb), on the CPU
in float32. The calls use nothing but the programs' public signatures,
which PR 38 left as they were.

PR 48 took ``paged_prefill`` away with the stitched dispatch it served.
Its stored logits stay, as that path's lasting witness: the case
``prefill`` now feeds the same 11 tokens as ONE RAGGED STEP and holds
that step's last-token logits to what the stitched program wrote (two
programs, so ``PREFILL_TOL`` and not the bit-near 2e-6 of the others,
which all read the cache that step wrote)."""

import json
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2 import paged_model as pm
from deepspeed_tpu.models import TransformerConfig, TransformerLM

STORED = Path(__file__).parent / "fixtures" / "paged_program_logits_pr37.json"
BASE = dict(vocab_size=64, hidden_size=32, intermediate_size=64,
            num_layers=2, num_heads=4, max_seq_len=64)
# the branches the shared helpers take: grouped kv heads with rope and
# rmsnorm; learned positions, biases and tied embeddings; the parallel
# residual
BLOCKS = {
    "llama": dict(BASE, num_kv_heads=2),
    "opt": dict(BASE, norm="layernorm", activation="relu",
                positional="learned", attn_bias=True, tie_embeddings=True),
    "falcon": dict(BASE, parallel_residual=True, norm="layernorm"),
}
PROGRAMS = ["prefill", "continue", "ragged_step", "ragged_step_kernel",
            "decode", "decode_kernel", "verify", "pool"]
BS, NB = 8, 9          # block size; blocks (block 0 is the null block)
PREFILL_TOL = 2e-4     # the ragged step against the stitched prefill


def run_programs(block):
    """``{program: logits as nested lists}``: a prompt of 11 fed into
    blocks 1-2 by a ragged step, continued by 3; a second row of 5 and a decode
    token of the first through the ragged step; both rows decoded; two
    fed tokens a row verified (that program answers in token ids); and
    ``pool``, what all of them left in each layer's keys and values.
    Each program reads the cache the one before it wrote."""
    cfg = TransformerConfig(**BLOCKS[block])
    params = TransformerLM(cfg).init_params(jax.random.PRNGKey(0))
    cache = pm.init_paged_kv_cache(cfg, NB, BS, jnp.float32)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab_size, 64)
    i32 = lambda x: jnp.asarray(x, jnp.int32)      # noqa: E731
    out = {}

    def slots(blocks, start, n, pad_to):
        pos = np.arange(start, start + n)
        b = np.zeros(pad_to, np.int32)
        o = np.zeros(pad_to, np.int32)
        b[:n] = np.asarray(blocks)[pos // BS]
        o[:n] = pos % BS
        return i32(b), i32(o)

    # row A: blocks 1, 2, 3; row B: blocks 4, 5
    table = i32([[1, 2, 3], [4, 5, 0]])
    ids = np.zeros(16, np.int32)
    ids[:11] = toks[:11]
    b, o = slots([1, 2, 3], 0, 11, 16)
    pos = np.arange(16) * (np.arange(16) < 11)
    logits, cache = pm.paged_ragged_step(
        cfg, params, i32(ids), i32(np.zeros(16)), i32(pos),
        i32((pos + 1) * (np.arange(16) < 11)), b, o, table[:1], i32([10]),
        cache, BS, use_kernel=False)
    out["prefill"] = logits[0]
    ids = np.zeros((1, 4), np.int32)
    ids[0, :3] = toks[11:14]
    b, o = slots([1, 2, 3], 11, 3, 4)
    logits, cache = pm.paged_continue(cfg, params, i32(ids), i32(11), i32(3),
                                      cache, b, o, table[0], BS)
    out["continue"] = logits
    # a mixed batch: B's prompt of 5 (positions 0-4) and one token of A
    # (position 14), padded to 8
    for name, kernel in (("ragged_step", False), ("ragged_step_kernel", True)):
        bb, ob = slots([4, 5], 0, 5, 5)
        ba, oa = slots([1, 2, 3], 14, 1, 1)
        pad = np.zeros(2, np.int32)
        logits, stepped = pm.paged_ragged_step(
            cfg, params,
            i32(np.concatenate([toks[20:25], toks[14:15], pad])),
            i32([1, 1, 1, 1, 1, 0, 0, 0]),
            i32([0, 1, 2, 3, 4, 14, 0, 0]), i32([1, 2, 3, 4, 5, 15, 0, 0]),
            jnp.concatenate([bb, ba, i32(pad)]),
            jnp.concatenate([ob, oa, i32(pad)]),
            table, i32([5, 4]), cache, BS, use_kernel=kernel)
        out[name] = logits
    cache = stepped
    for name, kernel in (("decode", False), ("decode_kernel", True)):
        logits, stepped = pm.paged_decode(
            cfg, params, i32([toks[15], toks[25]]), i32([15, 5]), table,
            cache, jnp.asarray([True, True]), BS, use_kernel=kernel)
        out[name] = logits
    out.update(_windows(cfg, params, i32([toks[15], toks[25]]), i32([15, 5]),
                        table, cache))
    cache = stepped
    logits, cache = pm._paged_verify(
        cfg, params, i32([toks[16:18], toks[26:28]]), i32([16, 6]), table,
        cache, jnp.asarray([True, True]), BS, use_kernel=False)
    out["verify"] = logits      # the verify pass answers in token ids
    # what all of them wrote: a layer's pool, keys then values
    out["pool"] = jnp.stack([jnp.abs(cache[kv]).sum(axis=(1, 2, 3))
                             for kv in ("k", "v")])
    return {k: np.asarray(v, np.float32).tolist() for k, v in out.items()}


def _windows(cfg, params, toks, pos, table, cache):
    """Not stored, compared among themselves (PR 39): the fused window
    over the two decode rows from the cache the decode step read (three
    steps for row A, whose EOS is the token it emits second; two for
    row B), and the window after it fed twice: with the rows' state the
    first handed on, on the device, and with what a host would make of
    the first's tokens."""
    def window(t, p, steps, eos, c, alive=None):
        return pm.paged_decode_window(
            cfg, params, t, p, table, c, jnp.asarray(steps, jnp.int32),
            jnp.asarray(eos, jnp.int32), BS, 4, use_kernel=False,
            alive=alive)

    free, _, _ = window(toks, pos, [3, 2], [-1, -1], cache)
    eos = [int(free[0, 1]), -1]
    first, (t, p, alive), after = window(toks, pos, [3, 2], eos, cache)
    device, (_, p2, alive2), _ = window(t, p, [2, 2], eos, after, alive)
    # the host's reading of ``first``: row A is dead and leaves, row B
    # feeds its last token at the position after those it fed
    took = (np.asarray(first) >= 0).sum(axis=1)
    host, _, _ = window(
        jnp.asarray([0, int(first[1, took[1] - 1])], jnp.int32),
        pos + jnp.asarray(took, jnp.int32), [0, 2], eos, after)
    return {"window": first, "window_free": free, "window_pos": p,
            "window_alive": alive, "window_next_device": device,
            "window_next_host": host, "window_next_pos": p2,
            "window_next_alive": alive2}


@pytest.fixture(scope="module", params=sorted(BLOCKS))
def ran(request):
    return request.param, run_programs(request.param)


@pytest.mark.parametrize("program", PROGRAMS)
def test_a_programs_logits_are_the_parents(ran, program):
    block, got = ran
    want = np.asarray(json.loads(STORED.read_text())[block][program],
                      np.float32)
    assert want.size and np.abs(want).max() > 1e-3     # a real reading
    tol = PREFILL_TOL if program == "prefill" else 2e-6
    np.testing.assert_allclose(np.asarray(got[program], np.float32), want,
                               rtol=tol, atol=tol)


def test_the_window_hands_on_its_rows_state(ran):
    """``paged_decode_window`` returns (tokens, (token, position, alive),
    cache). Its first step is the pinned decode step; a row stopped by
    its EOS is dead and one out of steps is alive; fed back on the
    device the state gives the tokens a host-made window gives."""
    block, got = ran
    stored = json.loads(STORED.read_text())[block]
    first = np.asarray(got["window"], np.int64)
    np.testing.assert_array_equal(
        first[:, 0], np.argmax(np.asarray(stored["decode"]), axis=-1))
    free = np.asarray(got["window_free"], np.int64)
    assert (free[0, :3] >= 0).all() and (free[1, :2] >= 0).all()
    assert (free[0, 3:] == -1).all() and (free[1, 2:] == -1).all()
    # row A stops where it first emits its EOS (the token of its second
    # step), with steps left; row B runs out of steps
    fed = list(free[0, :3]).index(free[0, 1]) + 1
    np.testing.assert_array_equal(first[0],
                                  list(free[0, :fed]) + [-1] * (4 - fed))
    np.testing.assert_array_equal(first[1], free[1])
    assert got["window_alive"] == [0.0, 1.0]
    assert got["window_pos"] == [15 + fed, 5 + 2]
    nxt = np.asarray(got["window_next_device"], np.int64)
    np.testing.assert_array_equal(nxt,
                                  np.asarray(got["window_next_host"]))
    assert (nxt[0] == -1).all() and (nxt[1, :2] >= 0).all()
    assert got["window_next_alive"] == [0.0, 1.0]
    assert got["window_next_pos"] == [15 + fed, 5 + 4]


# ---------------------------------------------------------------------------
# PR 58 split the expert layer into a routing half and an experts half
# (``paged_model._moe_route`` / ``_moe_experts``), so that a router can
# read another tensor than the experts run on. Every sparse configuration
# of the benchmark goes through the split, and for the five whose router
# stays BEHIND the mixer it stands where it stood: every ``moe_router``
# equation of their two programs at toy widths (``configs/<name>.json``'s
# ``fields`` under its ``toy_fields``) lies under ``mlp``, one router
# matmul a run of expert layers, none ahead of a mixer. (That the split
# moved nothing at all in them was read once, as equal jaxpr hashes on
# the parent and the change: PERF.md section 6, PR 58.)
# ---------------------------------------------------------------------------
ROUTER_BEHIND = ["joyai-llm-flash", "ling-3.0-flash", "trinity-mini",
                 "granite-4.0-h-small", "nemotron-3-nano-30b-a3b"]
MIXERS = ("attention", "mla_attention", "linear_attention", "ssm_mixer")


def walk_jaxprs(name):
    """(the decode window's, the ragged step's) jaxpr of configuration
    ``name`` at toy widths."""
    from benchmark import run as harness
    file = json.loads((Path(__file__).resolve().parents[3] / "benchmark"
                       / "configs" / f"{name}.json").read_text())
    cfg = TransformerConfig(**harness.merge(file["fields"],
                                            file["toy_fields"]))
    params = jax.eval_shape(TransformerLM(cfg).init_params,
                            jax.random.PRNGKey(0))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    kw, more = {}, {}
    if cfg.has_state:
        kw["state_slots"], more["state_slots"] = 3, i32(2)
    if "window" in cfg.layer_kinds:
        kw["window_blocks"], more["window_tables"] = 9, i32(2, 4)
    cache = jax.eval_shape(lambda: pm.init_paged_kv_cache(
        cfg, 9, 16, jnp.float32, **kw))
    window = jax.make_jaxpr(
        lambda p, t, pos, bt, c, sl, eos, *a: pm.paged_decode_window(
            cfg, p, t, pos, bt, c, sl, eos, 16, 4, **dict(zip(more, a))))(
        params, i32(2), i32(2), i32(2, 4), cache, i32(2), i32(2),
        *more.values())
    step = jax.make_jaxpr(
        lambda p, ids, rows, pos, ln, wb, wo, bt, li, c, *a:
        pm.paged_ragged_step(cfg, p, ids, rows, pos, ln, wb, wo, bt, li, c,
                             16, **dict(zip(more, a))))(
        params, i32(16), i32(16), i32(16), i32(16), i32(16), i32(16),
        i32(2, 4), i32(2), cache, *more.values())
    return window, step


def layer_bodies(jaxpr):
    """[(scope, primitive)] of every ``layers`` scan's body, in order."""
    found = []

    def walk(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name == "scan" and str(
                    eqn.source_info.name_stack).endswith("layers"):
                found.append([
                    (str(e.source_info.name_stack), e.primitive.name)
                    for e in eqn.params["jaxpr"].jaxpr.eqns])
            else:
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub)
    walk(jaxpr.jaxpr)
    return found


@pytest.mark.parametrize("name", ROUTER_BEHIND)
def test_the_sparse_configurations_router_stays_behind_the_mixer(name):
    for program in walk_jaxprs(name):
        routed = 0
        for body in layer_bodies(program):
            router = [(i, s, p) for i, (s, p) in enumerate(body)
                      if "moe_router" in s.split("/")]
            if not router:
                continue            # a run of layers with no expert
            routed += 1
            for _, s, _ in router:
                scopes = s.split("/")
                assert "mlp" in scopes[:scopes.index("moe_router")], s
            assert sum(p == "dot_general" for _, _, p in router) == 1
            mixer = [i for i, (s, _) in enumerate(body)
                     if set(s.split("/")) & set(MIXERS)]
            assert not mixer or mixer[-1] < router[0][0]
        assert routed >= 1
