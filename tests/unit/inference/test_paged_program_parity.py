"""The five per-head serving programs against logits stored from the
parent of PR 38, which folded their five scan bodies into one
(``paged_model._scan_layers``; each program keeps only its ``attend``).

``fixtures/paged_program_logits_pr37.json`` was written by running
``run_programs`` below on the tree of PR 37 (commit 7118cbb), on the CPU
in float32. The calls use nothing but the programs' public signatures,
which PR 38 left as they were."""

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


def run_programs(block):
    """``{program: logits as nested lists}``: a prompt of 11 prefilled
    into blocks 1-2, continued by 3; a second row of 5 and a decode
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
    ids = np.zeros((1, 16), np.int32)
    ids[0, :11] = toks[:11]
    b, o = slots([1, 2, 3], 0, 11, 16)
    logits, cache = pm.paged_prefill(cfg, params, i32(ids), i32(11), cache,
                                     b, o, use_kernel=False)
    out["prefill"] = logits
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
    cache = stepped
    logits, cache = pm._paged_verify(
        cfg, params, i32([toks[16:18], toks[26:28]]), i32([16, 6]), table,
        cache, jnp.asarray([True, True]), BS, use_kernel=False)
    out["verify"] = logits      # the verify pass answers in token ids
    # what all of them wrote: a layer's pool, keys then values
    out["pool"] = jnp.stack([jnp.abs(cache[kv]).sum(axis=(1, 2, 3))
                             for kv in ("k", "v")])
    return {k: np.asarray(v, np.float32).tolist() for k, v in out.items()}


@pytest.fixture(scope="module", params=sorted(BLOCKS))
def ran(request):
    return request.param, run_programs(request.param)


@pytest.mark.parametrize("program", PROGRAMS)
def test_a_programs_logits_are_the_parents(ran, program):
    block, got = ran
    want = np.asarray(json.loads(STORED.read_text())[block][program],
                      np.float32)
    assert want.size and np.abs(want).max() > 1e-3     # a real reading
    np.testing.assert_allclose(np.asarray(got[program], np.float32), want,
                               rtol=2e-6, atol=2e-6)
