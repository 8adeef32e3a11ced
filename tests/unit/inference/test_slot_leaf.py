"""A slot leaf that a kernel updates in place is coloured HBM where the
kernel is compiled for a TPU, and ONLY there (``kernels/slot_leaf.py``):
the Pallas interpreter refuses the coloured type
(``ShapedArrayWithMemorySpace``), so under ``interpret=True`` every
one-token kernel takes and returns a plain array, whatever the backend
says, and equals its XLA twin; traced for a TPU a kernel still hands
back a plain-typed array, so that a loop can carry the leaf and a
program can return it. What the colour does to the compiled programs is
``tests/unit/ops/test_kernels_lower_tpu.py``'s.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax._src import core as jax_core

from deepspeed_tpu.inference.v2.kernels import linear_attention as la
from deepspeed_tpu.inference.v2.kernels import power_retention as pr
from deepspeed_tpu.inference.v2.kernels import slot_leaf
from deepspeed_tpu.inference.v2.kernels import state_space as ss

EPS = 1e-6


def _normal(i, *shape):
    return jax.random.normal(jax.random.fold_in(jax.random.PRNGKey(7), i),
                             shape)


def _conv_case(parts, bias, interpret):
    """(kernel, twin, leaf) of ``conv_update`` on three rows' slots of a
    leaf of two layers and five slots, the second row fresh."""
    N, K, D = 3, 4, 256
    width = parts * D
    leaf = _normal(0, *la.conv_leaf_shape(2, 5, K, width))
    xs = tuple(_normal(1 + i, N, D) for i in range(parts))
    taps = _normal(5, K, width)
    b = _normal(6, width) if bias else None
    slots = jnp.asarray([3, 1, 4], jnp.int32)
    fresh = jnp.asarray([False, True, False])

    def kernel(leaf):
        mixed, leaf = la.conv_update(leaf, 1, slots, fresh, xs, taps, b,
                                     name="ssm_conv_update",
                                     interpret=interpret)
        return (*mixed, leaf)

    def twin(leaf):
        held = jnp.where(fresh[:, None, None], 0,
                         leaf[1, slots].reshape(N, K - 1, width))
        y, held = la.causal_conv_step(
            jnp.concatenate(xs, axis=-1), taps, held,
            lambda y: jax.nn.silu(y if b is None else y + b))
        return (*(y[:, i * D:(i + 1) * D] for i in range(parts)),
                leaf.at[1, slots].set(held.reshape(N, *leaf.shape[2:])))
    return kernel, twin, leaf


def _ssm_case(interpret):
    N, nh, p, n = 3, 16, 8, 16
    leaf = _normal(0, *ss.state_leaf_shape(2, 5, nh * p, n))
    x, b, c = _normal(1, N, nh * p), _normal(2, N, n), _normal(3, N, n)
    dt = jax.random.uniform(jax.random.PRNGKey(4), (N, nh), minval=0.01,
                            maxval=1.0)
    a = -jax.random.uniform(jax.random.PRNGKey(5), (nh,), minval=1.0,
                            maxval=4.0)
    at = (1, jnp.asarray([3, 1, 4], jnp.int32),
          jnp.asarray([False, True, False]), x, dt, a, b, c)
    return (lambda leaf: ss.ssm_state_update(leaf, *at, interpret=interpret),
            lambda leaf: ss.ssm_step(leaf, *at), leaf)


def _retention_case(interpret):
    N, nh, nkv, hd = 3, 4, 2, 16
    shapes = pr.leaf_shapes(2, 5, nkv, hd)
    state = _normal(0, *shapes[0])
    norm = jnp.abs(_normal(1, *shapes[1])) + 0.1
    norm = norm.at[..., pr.phi_rows(hd):, :].set(0.0)
    q, k, v = _normal(2, N, nh, hd), _normal(3, N, nkv, hd), \
        _normal(4, N, nkv, hd)
    g = -jax.random.uniform(jax.random.PRNGKey(5), (N, nkv), minval=0.01,
                            maxval=3.0)
    at = (1, jnp.asarray([3, 1, 4], jnp.int32),
          jnp.asarray([False, True, False]), q, k, v, g, EPS)
    return (lambda leaves: pr.retention_state_update(
                *leaves, *at, interpret=interpret),
            lambda leaves: pr.retention_step(*leaves, *at), (state, norm))


def _kda_case(interpret):
    N, nh, d = 3, 8, 128
    leaf = _normal(0, 2, 5, nh, d, d)
    q, k, v = (_normal(1 + i, N, nh, d) for i in range(3))
    g = -jnp.abs(_normal(4, N, nh, d))
    beta = jax.nn.sigmoid(_normal(5, N, nh))
    slots = jnp.asarray([3, 1, 4], jnp.int32)
    fresh = jnp.asarray([False, True, False])

    def twin(leaf):
        state = jnp.where(fresh[:, None, None, None], 0.0, leaf[1, slots])
        o, state = la.kda_step(q, k, v, g, beta, state)
        return o, leaf.at[1, slots].set(state)
    return (lambda leaf: la.kda_state_update(
                leaf, 1, slots, fresh, q, k, v, g, beta,
                interpret=interpret), twin, leaf)


CASES = {"conv_one_part_and_a_bias": lambda i: _conv_case(1, True, i),
         "conv_three_parts": lambda i: _conv_case(3, False, i),
         "ssm_state": _ssm_case, "retention_state": _retention_case,
         "kda_state": _kda_case}


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_an_interpreted_kernel_takes_and_returns_a_plain_leaf(
        monkeypatch, case, backend):
    """Each one-token kernel under the interpreter, on the CPU and where
    ``jax.default_backend()`` says "tpu" (an interpreted run on a TPU
    host): no equation colours the leaf, every output's type is a plain
    ``ShapedArray``, and the outputs equal the XLA twin's (gather, the
    one-token form, scatter). The interpreter refused the coloured type
    in ISSUE 57's probe; this is what keeps that from coming back."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    kernel, twin, leaf = CASES[case](True)
    jaxpr = jax.make_jaxpr(kernel)(leaf)
    assert "with_memory_space_constraint" not in str(jaxpr)
    assert all(type(a) is jax_core.ShapedArray for a in jaxpr.out_avals)
    for got, want in zip(jax.tree.leaves(kernel(leaf)),
                         jax.tree.leaves(twin(leaf))):
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_the_colour_is_put_on_only_for_a_compiled_tpu_kernel(monkeypatch):
    """``in_hbm`` / ``hbm_out``: the leaf as it is and a plain
    ``ShapeDtypeStruct`` on the CPU and under the interpreter; on a TPU,
    compiled, the value's type is ``float32<hbm>`` and the aliased
    output's entry says HBM."""
    from jax.experimental.pallas import tpu as pltpu
    leaf = jnp.zeros((2, 3, 8, 128))

    def traced(f):
        # a function of its own a call: a trace is cached by function,
        # and the answer changes with the backend under it
        return jax.make_jaxpr(lambda x: f(x))(leaf)

    assert not traced(slot_leaf.in_hbm).eqns
    assert isinstance(slot_leaf.hbm_out(leaf), jax.ShapeDtypeStruct)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert not traced(lambda x: slot_leaf.in_hbm(x, True)).eqns
    assert isinstance(slot_leaf.hbm_out(leaf, True), jax.ShapeDtypeStruct)
    (aval,) = traced(slot_leaf.in_hbm).out_avals
    assert getattr(aval, "memory_space", None) == pltpu.HBM, aval
    assert aval.shape == leaf.shape and aval.dtype == leaf.dtype
    out = slot_leaf.hbm_out(leaf)
    assert out.memory_space == pltpu.HBM and out.shape == leaf.shape


@pytest.mark.parametrize("case", sorted(CASES))
def test_a_compiled_kernel_hands_back_a_plain_leaf(monkeypatch, case):
    """Each one-token kernel as it is traced FOR a TPU (traced only:
    nothing here can run it): every leaf is coloured once, where the
    kernel hands it to ``pallas_call``, whose aliased outputs are
    declared ``float32<hbm>``; every value the function RETURNS has a
    plain ``ShapedArray`` for its type, and ``lax.scan`` carries the
    leaves (the function's last outputs) through the kernel with one
    type in and out, as a run of layers does. ISSUE 57's first build
    coloured the cache where a decode program takes it and again what a
    kernel returned: the programs compiled, and the first call failed
    (no result handler knows the coloured type)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    kernel, _, leaf = CASES[case](False)
    n = len(jax.tree.leaves(leaf))
    jaxpr = jax.make_jaxpr(kernel)(leaf)
    assert str(jaxpr).count("= with_memory_space_constraint[") == n
    assert str(jaxpr).count("float32<hbm>[") >= n, "out_avals lost HBM"
    assert all(type(a) is jax_core.ShapedArray for a in jaxpr.out_avals)

    def layer(carry, _):
        out = kernel(carry)[-n:]
        return (out if n > 1 else out[0]), None

    carried = jax.make_jaxpr(
        lambda leaf: jax.lax.scan(layer, leaf, None, length=2)[0])(leaf)
    assert all(type(a) is jax_core.ShapedArray for a in carried.out_avals)
