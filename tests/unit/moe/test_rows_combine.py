"""The routed rows back from expert order through the kernels
(``inference/v2/kernels/expert_combine.py``): under Pallas's TPU
interpreter against XLA's lines (``gather_rows_combine``) and a float32
reference; the rule that says which launches take them; and that the
callers which hand nothing trace what they traced before.
"""

import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2.kernels import expert_combine as ec
from deepspeed_tpu.moe import sharded_moe


def _dispatch(T, k, H, experts, held, seed=0):
    """A dispatch's way back at random: (ys [k T, H] bf16 in expert order,
    its rows past the last group NaN; inv; the held mask or None; topv;
    the held rows' count). Every token picks k distinct experts of
    ``experts``, of which the first ``held`` are held here."""
    rng = np.random.default_rng(seed)
    idx = np.argsort(rng.random((T, experts)), axis=1)[:, :k].T.reshape(-1)
    here = idx < held
    order = np.argsort(np.where(here, idx, held), kind="stable")
    n = int(here.sum())
    ys = rng.standard_normal((k * T, H)).astype(np.float32)
    ys[n:] = np.nan
    return (jnp.asarray(ys, jnp.bfloat16), jnp.asarray(np.argsort(order)),
            None if held == experts else jnp.asarray(here),
            jnp.asarray(rng.random((T, k)), jnp.float32), n)


def _float32(ys, inv, held, topv):
    T, k = topv.shape
    rows = np.asarray(ys.astype(jnp.float32))[np.asarray(inv)].reshape(
        k, T, -1)
    if held is not None:
        rows = np.where(np.asarray(held).reshape(k, T, 1), rows, 0)
    total = np.zeros(rows.shape[1:], np.float32)
    for pick, w in zip(rows, np.asarray(topv).T):       # in the picks' order
        total += pick * w[:, None]
    return total


# (tokens, picks, width, experts, held here): granite's share at k = 10
# (half the picks elsewhere), every expert held at k = 6 and k = 8, a
# token count that is no whole tile, a width of 21 lane blocks
CASES = [(32, 10, 256, 8 * 2, 8), (32, 6, 256, 8, 8), (16, 8, 128, 16, 16),
         (40, 3, 256, 8, 4), (16, 2, 21 * 128, 4, 4)]


@pytest.mark.parametrize("T,k,H,experts,held", CASES)
def test_the_kernels_are_the_float32_sum_rounded_once(T, k, H, experts,
                                                      held):
    """Finite where the rows past the last group are NaN (a pick held
    elsewhere is copied by no one and adds an exact zero; a NaN row that
    comes along as a held row's neighbour is cut off by the mask), and
    the float32 reference to ONE bfloat16 rounding (half a unit of its
    eight bits, beside what float32 loses adding terms of order one)."""
    ys, inv, here, topv, n = _dispatch(T, k, H, experts, held)
    got = ec.rows_combine(ys, inv, here, topv, jnp.int32(n), interpret=True)
    assert got.shape == (T, H) and got.dtype == jnp.bfloat16
    got = np.asarray(got.astype(jnp.float32))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, _float32(ys, inv, here, topv),
                               rtol=2.0 ** -8, atol=1e-5)


@pytest.mark.parametrize("T,k,H,experts,held", CASES[:3])
def test_the_kernels_against_the_gather(T, k, H, experts, held):
    """XLA's lines make the products in bfloat16 and the kernels in
    float32: one bfloat16 rounding of the result apart, no more."""
    ys, inv, here, topv, n = _dispatch(T, k, H, experts, held, seed=1)
    got = ec.rows_combine(ys, inv, here, topv, jnp.int32(n), interpret=True)
    xla = sharded_moe.gather_rows_combine(ys, inv, here, topv)
    got, xla = (np.asarray(a.astype(jnp.float32)) for a in (got, xla))
    # k products each rounded to 8 bits of mantissa, then the sum's own
    assert np.abs(got - xla).max() <= 2.0 ** -7 * np.abs(xla).max()


def test_both_halves_of_a_pair():
    """A pick's row at an odd and at an even place in expert order: the
    high and the low half of the pair's words."""
    T, H = 16, 128
    ys = jnp.arange(2 * T, dtype=jnp.float32)[:, None] * jnp.ones((1, H))
    ys = ys.astype(jnp.bfloat16)
    inv = jnp.arange(2 * T).reshape(T, 2).T.reshape(-1)   # pick j: row 2t+j
    for j in (0, 1):
        topv = jnp.zeros((T, 2)).at[:, j].set(1.0)
        got = ec.rows_combine(ys, inv, None, topv, interpret=True)
        np.testing.assert_array_equal(
            np.asarray(got[:, 0].astype(jnp.float32)),
            2 * np.arange(T) + j)


def test_the_whole_pairs_stop_at_the_last_held_row():
    """``rows_whole`` writes the blocks that hold a held row and leaves
    the rest as they lie: a block's pair ``[p, b, l]`` is rows ``2 p``
    (low half) and ``2 p + 1`` (high) at column ``128 b + l``."""
    N, H, R = 4 * ec.WHOLE_ROWS, 256, ec.WHOLE_ROWS
    ys = jax.random.normal(jax.random.PRNGKey(0), (N, H), jnp.bfloat16)
    pairs = ec.rows_whole(ys, jnp.int32(R + 1), interpret=True)
    assert pairs.shape == (N // 2, H // 128, 128)
    words = np.asarray(pairs).view(np.uint32).reshape(N // 2, H)
    bits = np.asarray(jax.lax.bitcast_convert_type(ys, jnp.uint16))
    np.testing.assert_array_equal(words[:R] & 0xffff, bits[:2 * R:2])
    np.testing.assert_array_equal(words[:R] >> 16, bits[1:2 * R:2])


def test_which_launches_take_the_kernels():
    """By shape, type and whether the experts are a share, nothing else:
    a share's prompt launch does; a decode step's does not (XLA gathers
    its few hundred rows out of fast memory), nor a launch whose every
    expert is held (every row relaid, a copy started for every pick: the
    gather is faster), a float32 output, a width that is no whole number
    of lane blocks, an odd count of rows."""
    bf16, serves = jnp.bfloat16, ec.shape_serves
    assert serves(2048 * 10, 4096, bf16, True)        # granite's run
    assert serves(4096 * 6, 2688, bf16, True)         # nemotron's run
    assert serves(4096 * 8, 2560, bf16, True)         # ling's run
    assert not serves(16384 * 6, 2560, bf16, False)   # smallthinker's step
    assert not serves(16384 * 8, 2048, bf16, False)   # trinity-mini's step
    assert not serves(64 * 10, 4096, bf16, True)      # granite's decode step
    assert not serves(128 * 6, 2688, bf16, True)      # nemotron's
    assert not serves(128 * 8, 2560, bf16, True)      # ling's
    assert not serves(2048 * 10, 4096, jnp.float32, True)
    assert not serves(2048 * 10, 4096 + 64, bf16, True)
    assert not serves(4097 * 3, 4096, bf16, True)
    # off the TPU every launch keeps the gather
    assert not ec.rows_combine_serves(2048 * 10, 4096, bf16, True)


def test_the_serving_path_hands_the_kernels_by_shape(monkeypatch):
    """``paged_model.moe_rows_form``: the three shares' ragged steps
    (a run of 8,192 tokens, or the step's 16,384 whole) take the kernels
    on a TPU; their
    decode steps, the cells that hold every expert, a float32 engine
    and every launch elsewhere the gather."""
    import json
    import pathlib
    from deepspeed_tpu.inference.v2 import paged_model
    from deepspeed_tpu.models.transformer import TransformerConfig
    configs = pathlib.Path(__file__).parents[3] / "benchmark/configs"

    def cfg(name):
        return TransformerConfig(**json.loads(
            (configs / f"{name}.json").read_text())["fields"])

    form = paged_model.moe_rows_form
    assert form(cfg("granite-4.0-h-small"), 16384, jnp.bfloat16) \
        == "gather"                                             # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for name, decode in (("granite-4.0-h-small", 64),
                         ("nemotron-3-nano-30b-a3b", 128),
                         ("ling-3.0-flash", 128)):
        assert form(cfg(name), 16384, jnp.bfloat16) == "kernel", name
        assert form(cfg(name), decode, jnp.bfloat16) == "gather", name
        assert form(cfg(name), 16384, jnp.float32) == "gather", name
    for name in ("trinity-mini", "joyai-llm-flash",
                 "smallthinker-21ba3b-instruct"):
        assert form(cfg(name), 16384, jnp.bfloat16) == "gather", name


def _digest(jaxpr):
    text = re.sub(r"0x[0-9a-f]+", "0x", str(jaxpr))
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def test_the_training_side_traces_what_it_traced():
    """``moe_layer_dropless`` hands no kernel, and under ``jax.grad`` its
    jaxpr is the text it was on PR 61's parent commit (95181e1; sha256,
    16 hex, read there by this function)."""
    x, gate = jnp.zeros((2, 8, 16)), jnp.zeros((16, 4))
    experts = (jnp.zeros((4, 16, 32)), jnp.zeros((4, 16, 32)),
               jnp.zeros((4, 32, 16)))
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda x, gate, experts: sharded_moe.moe_layer_dropless(
            x, gate, experts)[0].sum(), argnums=(0, 1, 2)))(x, gate, experts)
    assert _digest(jaxpr) == "388121239e5b48cb"
