"""Launches of the Mamba-2 recurrence's forms and kernels, and the token
scan they are held to, for the two blocks that serve it
(``test_ssm_serving.py``: one group of heads; ``test_nemotron_serving.py``:
B and C a GROUP of heads'), and the guide's test of a cut in experts,
which both blocks' expert layers take."""

import numpy as np

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2 import paged_model
from deepspeed_tpu.inference.v2.kernels import state_space as ss
from deepspeed_tpu.models import TransformerConfig
from tests.unit.inference.served_blocks import F32, err


def scan(x, dt, a, b, c, s0, groups=1):
    """The recurrence a token at a time: x [T, nh, p], dt [T, nh], b and
    c [T, groups * n], s0 [nh, p, n]; head h reads group h // (nh /
    groups)."""
    nh = x.shape[1]
    of = jnp.arange(nh) // (nh // groups)

    def token(s, t):
        xt, dtt, bt, ct = t
        bt, ct = (v.reshape(groups, -1)[of] for v in (bt, ct))   # [nh, n]
        s = jnp.exp(dtt * a)[:, None, None] * s \
            + (dtt[:, None] * xt)[:, :, None] * bt[:, None, :]
        return s, jnp.einsum("hpn,hn->hp", s, ct)
    return jax.lax.scan(token, s0, (x, dt, b, c))


def case(nh, p, n, lengths, T, groups=1, seed=0, slots=6):
    rng = np.random.default_rng(seed)
    C = nh * p
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    # steps from slow to one that forgets within a token (dt A = -30)
    dt = jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(2.0),
                                        (T, nh))), jnp.float32)
    counts = jnp.asarray(lengths, jnp.int32)
    return dict(
        leaf=f(*ss.state_leaf_shape(2, slots, C, n)), layer=jnp.int32(1),
        slots=jnp.asarray([i % (slots - 1) + 1 if n_ else 0
                           for i, n_ in enumerate(lengths)], jnp.int32),
        fresh=jnp.asarray([i % 2 == 0 for i in range(len(lengths))]),
        starts=jnp.cumsum(counts) - counts, counts=counts,
        xbc=f(T, C + 2 * groups * n), dt=dt,
        a=-jnp.asarray(rng.uniform(1, 16, (nh,)), jnp.float32))


def against_the_scan(case, y, leaf, nh, groups=1):
    """Each row's outputs and final state are the token scan's from its
    slot's state (zeros where fresh); tokens of no row come back zeros,
    the other layer as it went in."""
    n = case["leaf"].shape[3]
    C = case["xbc"].shape[1] - 2 * groups * n
    x, b, c = (case["xbc"][:, :C], case["xbc"][:, C:C + groups * n],
               case["xbc"][:, C + groups * n:])
    used = np.zeros(len(y), bool)
    with jax.default_matmul_precision("highest"):
        for r, n_ in enumerate(np.asarray(case["counts"])):
            if not n_:
                continue
            at = slice(int(case["starts"][r]), int(case["starts"][r]) + n_)
            used[at] = True
            slot = case["slots"][r]
            s0 = jnp.where(case["fresh"][r], 0.0,
                           ss.heads_of(case["leaf"][1, slot], nh))
            s1, want = scan(x[at].reshape(n_, nh, -1), case["dt"][at],
                            case["a"], b[at], c[at], s0, groups)
            assert err(y[at], np.asarray(want).reshape(n_, -1)) <= F32, r
            assert err(ss.heads_of(leaf[1, slot], nh),
                       np.asarray(s1)) <= F32, r
    assert not np.asarray(y)[~used].any()
    np.testing.assert_array_equal(leaf[0], case["leaf"][0])


def one_token_forms(nh, p, n, groups=1, whole=128):
    """``ssm_step`` and, where the channels are whole multiples of
    ``whole``, the kernel ``ssm_state_update`` (interpreted) on three
    rows' slots, a fresh row between two kept ones: one token of the scan
    with each head reading ITS group's B and C, the other slots and the
    other layer untouched. The kernel's state and ``y`` are
    ``ssm_step``'s too (the same float32 operations on the same values,
    a compiled product and sum rounding once where the eager ones round
    twice), and the leaf goes in aliased to the leaf that comes out."""
    rng = np.random.default_rng(1)
    C, N = nh * p, 3
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    leaf0 = f(*ss.state_leaf_shape(2, 5, C, n))
    x, b, c = f(N, C), f(N, groups * n), f(N, groups * n)
    dt = jnp.asarray(rng.uniform(0.01, 1.0, (N, nh)), jnp.float32)
    a = -jnp.asarray(rng.uniform(1, 16, (nh,)), jnp.float32)
    slots, fresh = jnp.asarray([2, 4, 1]), jnp.asarray([False, True, False])
    args = (leaf0, jnp.int32(1), slots, fresh, x, dt, a, b, c)
    forms = [ss.ssm_step] + [
        lambda *args: ss.ssm_state_update(*args, interpret=True)
    ] * (C % whole == 0)
    out = []
    for form in forms:
        y, leaf = form(*args)
        out.append((y, leaf))
        for r in range(N):
            s0 = jnp.where(fresh[r], 0.0, ss.heads_of(leaf0[1, slots[r]], nh))
            s1, want = scan(x[r:r + 1].reshape(1, nh, p), dt[r:r + 1], a,
                            b[r:r + 1], c[r:r + 1], s0, groups)
            assert err(y[r], np.asarray(want).reshape(C)) <= F32
            assert err(ss.heads_of(leaf[1, slots[r]], nh),
                       np.asarray(s1)) <= F32
        np.testing.assert_array_equal(leaf[0], leaf0[0])
        np.testing.assert_array_equal(leaf[1, 3], leaf0[1, 3])
    if len(out) == 2:
        for step, kernel in zip(*out):
            assert err(kernel, np.asarray(step)) <= F32
        (call,) = [e for e in jax.make_jaxpr(forms[1])(*args).eqns
                   if e.primitive.name == "pallas_call"]
        assert call.params["input_output_aliases"] == ((3, 0),)


def shares_add_up(row, stack, experts):
    """The guide's test of a cut in experts: the routed output of the
    share that holds the first half of the experts plus that of the
    share that holds the second half, the shared expert counted once,
    is the uncut reference's expert layer; and the program's expert
    layer on either share is that share's reference. ``stack``: the
    expert stack's leaves with ALL the experts, ``experts`` the leaves
    that are cut."""
    toy, reference = row.toy, row.reference
    E = toy["moe_num_experts"]
    half = E // 2
    x = jnp.asarray(np.random.default_rng(4).normal(
        size=(24, toy["hidden_size"])), jnp.float32)
    with jax.default_matmul_precision("highest"):
        routed, shared = reference.expert_layer(
            x, stack, 3, {**toy, "moe_experts_held": E})
        parts = []
        for first in (0, half):
            cut = {k: v[:, first:first + half] if k in experts else v
                   for k, v in stack.items()}
            fields = {**toy, "moe_experts_held": half,
                      "moe_experts_first": first}
            r, s = reference.expert_layer(x, cut, 3, fields)
            np.testing.assert_allclose(s, shared, atol=1e-6)
            parts.append(r)
            cfg = TransformerConfig(**fields)
            lp = {k: v[3] for k, v in cut.items()}
            hn = paged_model._norm(cfg, x, lp["mlp_norm"])
            got, _ = paged_model._moe_routed(
                cfg, lp, hn, router_precision=jax.lax.Precision.HIGHEST)
            np.testing.assert_allclose(
                got, r + s, atol=F32 * float(jnp.abs(r + s).max()))
        assert float(jnp.abs(parts[0]).max()) > 0 \
            and float(jnp.abs(parts[1]).max()) > 0
        np.testing.assert_allclose(
            parts[0] + parts[1], routed,
            atol=F32 * float(jnp.abs(routed).max()))
