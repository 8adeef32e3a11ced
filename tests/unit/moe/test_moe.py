"""MoE tests: gating semantics + expert-parallel training (mirrors the
reference's tests/unit/moe coverage)."""

import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.moe.sharded_moe import top1gating, top2gating, moe_layer
from deepspeed_tpu.models import TransformerConfig, TransformerLM


def test_top1_capacity_enforced():
    T, E = 64, 4
    logits = jnp.zeros((T, E)).at[:, 0].set(10.0)  # all tokens want expert 0
    aux, combine, dispatch = top1gating(logits, capacity_factor=1.0,
                                        min_capacity=4)
    C = dispatch.shape[-1]
    assert C == T // E
    # expert 0 can hold only C tokens; the rest are dropped
    assert float(jnp.sum(dispatch[:, 0])) == C
    assert float(jnp.sum(dispatch[:, 1:])) == 0.0


def test_top1_dispatch_positions_unique():
    rng = jax.random.PRNGKey(0)
    logits = jax.random.normal(rng, (128, 8))
    _, _, dispatch = top1gating(logits, capacity_factor=2.0)
    # no (expert, slot) claimed twice
    claims = jnp.sum(dispatch, axis=0)
    assert float(jnp.max(claims)) <= 1.0


def test_top1_aux_loss_balanced_lower():
    E = 4
    balanced = jnp.eye(E).repeat(16, axis=0) * 10            # even routing
    skewed = jnp.zeros((64, E)).at[:, 0].set(10.0)
    aux_b, _, _ = top1gating(balanced, capacity_factor=2.0)
    aux_s, _, _ = top1gating(skewed, capacity_factor=2.0)
    assert float(aux_b) < float(aux_s)


def test_top2_routes_two_experts():
    rng = jax.random.PRNGKey(1)
    logits = jax.random.normal(rng, (64, 4))
    _, combine, dispatch = top2gating(logits, capacity_factor=2.0)
    per_token = jnp.sum(dispatch, axis=(1, 2))
    # nearly all tokens get 2 slots at this capacity
    assert float(jnp.mean(per_token)) > 1.5
    # combine weights per token sum to ~1
    sums = jnp.sum(combine, axis=(1, 2))
    np.testing.assert_allclose(sums[per_token == 2], 1.0, atol=1e-5)


def test_moe_layer_identity_experts():
    """With identity experts and full capacity, output ~ gate-weighted input."""
    B, S, H, E = 2, 8, 16, 4
    x = jax.random.normal(jax.random.PRNGKey(0), (B, S, H))
    gate_w = jax.random.normal(jax.random.PRNGKey(1), (H, E))
    eye = jnp.broadcast_to(jnp.eye(H), (E, H, H))

    out, aux = moe_layer(x, gate_w, eye, lambda p, xe: xe @ p, None,
                         top_k=1, capacity_factor=float(E))
    # top-1 with identity experts: out = gate_prob * x (per token)
    logits = x.reshape(-1, H) @ gate_w
    g = jax.nn.softmax(logits, -1).max(-1).reshape(B, S, 1)
    np.testing.assert_allclose(out, x * g, atol=1e-5, rtol=1e-4)


def moe_model_cfg(E=4):
    return TransformerConfig(vocab_size=128, hidden_size=64,
                             intermediate_size=128, num_layers=2, num_heads=4,
                             max_seq_len=64, use_flash=False,
                             moe_num_experts=E, moe_top_k=1,
                             moe_capacity_factor=2.0)


@pytest.mark.parametrize("ep", [1, 2])
def test_moe_model_trains(ep):
    model = TransformerLM(moe_model_cfg())
    config = {
        "train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 1},
        "moe": {"enabled": True, "num_experts": 4, "expert_parallel_size": ep},
        "steps_per_print": 100,
    }
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=config)
    gm = engine.micro_batch_size * engine.ds_config.dp_world_size
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, 128, (1, gm, 64), dtype=np.int64)}
    losses = [engine.train_batch(batch=batch) for _ in range(4)]
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]
    if ep > 1:
        spec = engine.params["layers"]["e_up"].sharding.spec
        assert "expert" in str(spec)


def test_moe_top2_model_trains():
    cfg = TransformerConfig(vocab_size=128, hidden_size=64,
                            intermediate_size=128, num_layers=2, num_heads=4,
                            max_seq_len=64, use_flash=False,
                            moe_num_experts=4, moe_top_k=2)
    model = TransformerLM(cfg)
    config = {
        "train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "steps_per_print": 100,
    }
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=config)
    gm = engine.micro_batch_size * engine.ds_config.dp_world_size
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, 128, (1, gm, 64), dtype=np.int64)}
    losses = [engine.train_batch(batch=batch) for _ in range(4)]
    assert losses[-1] < losses[0]


def _moe_engine(model_cfg_kwargs, config_extra, steps=5, seed=0):
    cfg = TransformerConfig(vocab_size=128, hidden_size=64,
                            intermediate_size=128, num_layers=2, num_heads=4,
                            max_seq_len=64, use_flash=False,
                            moe_num_experts=4, **model_cfg_kwargs)
    model = TransformerLM(cfg)
    config = {
        "train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "steps_per_print": 100,
    }
    config.update(config_extra)
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=config)
    gm = engine.micro_batch_size * engine.ds_config.dp_world_size
    rng = np.random.default_rng(seed)
    batch = {"input_ids": rng.integers(0, 128, (1, gm, 64), dtype=np.int64)}
    losses = [engine.train_batch(batch=batch) for _ in range(steps)]
    return engine, losses


def test_residual_moe_trains():
    """Residual (PR-MoE building block) layer: dense MLP + coefficient-
    weighted experts (reference moe/layer.py use_residual)."""
    engine, losses = _moe_engine({"moe_use_residual": True},
                                 {"moe": {"enabled": True, "num_experts": 4,
                                          "expert_parallel_size": 2},
                                  "zero_optimization": {"stage": 1}})
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]
    assert "res_coef_w" in engine.params["layers"]
    # coefficient head actually learns (moved from zero init)
    cw = np.asarray(engine.params["layers"]["res_coef_b"])
    assert np.abs(cw).max() > 0


def test_pr_moe_pyramid_layers():
    """PR-MoE proper: residual MoE layers with DIFFERENT expert counts per
    layer (reference tests SimplePRMoEModel, tests/unit/simple_model.py:106)
    built directly on the moe_layer API."""
    from deepspeed_tpu.moe.sharded_moe import moe_layer, residual_moe_combine
    from jax.sharding import PartitionSpec as P

    H = 32

    class PRMoEModel:
        """Two residual-MoE blocks: 2 experts then 4 experts (pyramid)."""

        EXPERTS = (2, 4)

        def init_params(self, rng):
            ks = jax.random.split(rng, 12)
            p = {}
            for i, E in enumerate(self.EXPERTS):
                p[f"blk{i}"] = {
                    "gate_w": jax.random.normal(ks[4 * i], (H, E)) * 0.02,
                    "e_w": jax.random.normal(ks[4 * i + 1], (E, H, H)) * 0.05,
                    "mlp_w": jax.random.normal(ks[4 * i + 2], (H, H)) * 0.05,
                    "coef_w": jax.random.normal(ks[4 * i + 3], (H, 2)) * 0.02,
                }
            p["out_w"] = jax.random.normal(ks[-1], (H, H)) * 0.05
            return p

        def param_partition_specs(self, topo):
            ep = "expert" if topo.axis_size("expert") > 1 else None
            return {
                "blk0": {"gate_w": P(), "e_w": P(ep, None, None),
                         "mlp_w": P(), "coef_w": P()},
                "blk1": {"gate_w": P(), "e_w": P(ep, None, None),
                         "mlp_w": P(), "coef_w": P()},
                "out_w": P(),
            }

        def set_topology(self, topo):
            self.topology = topo

        def apply(self, params, batch, train=True, rng=None):
            x = batch["x"]  # [B, H] -> add a seq dim for moe_layer
            h = x[:, None, :]
            aux_total = 0.0
            for i in range(2):
                blk = params[f"blk{i}"]
                moe_out, aux = moe_layer(
                    h, blk["gate_w"], blk["e_w"],
                    lambda w, xe: jnp.tanh(xe @ w),
                    self.topology, top_k=1, capacity_factor=2.0)
                dense = jnp.tanh(h @ blk["mlp_w"])
                h = h + residual_moe_combine(h, moe_out, dense,
                                             blk["coef_w"])
                aux_total = aux_total + aux
            out = (h[:, 0, :] @ params["out_w"]).astype(jnp.float32)
            loss = jnp.mean((out - batch["y"].astype(jnp.float32)) ** 2)
            return loss + 0.01 * aux_total

    config = {
        "train_micro_batch_size_per_gpu": 2,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
        "zero_optimization": {"stage": 1},
        "moe": {"enabled": True, "num_experts": 4,
                "expert_parallel_size": 2},
        "steps_per_print": 100,
    }
    model = PRMoEModel()
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=config)
    gm = engine.micro_batch_size * engine.ds_config.dp_world_size
    rng = np.random.default_rng(0)
    batch = {"x": rng.standard_normal((1, gm, H)).astype(np.float32),
             "y": rng.standard_normal((1, gm, H)).astype(np.float32)}
    losses = [engine.train_batch(batch=batch) for _ in range(8)]
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]
    # pyramid: per-layer expert tensors keep their own expert count, both
    # sharded over the expert axis
    assert engine.params["blk0"]["e_w"].shape[0] == 2
    assert engine.params["blk1"]["e_w"].shape[0] == 4
    assert "expert" in str(engine.params["blk1"]["e_w"].sharding.spec)


def test_moe_ep_x_zero3():
    """EP x ZeRO-3 composition: expert tensors shard over BOTH the expert
    axis and (on a free dim) the data axes (VERDICT round-2 task 4)."""
    engine, losses = _moe_engine(
        {}, {"moe": {"enabled": True, "num_experts": 4,
                     "expert_parallel_size": 2},
             "zero_optimization": {"stage": 3,
                                   "stage3_param_persistence_threshold": 0}})
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]
    spec = str(engine.params["layers"]["e_up"].sharding.spec)
    assert "expert" in spec and "data" in spec
    # dense (non-expert) params are zero-3 sharded too
    assert not engine.params["layers"]["wq"].sharding.is_fully_replicated


def test_moe_expert_checkpoint_ep_resize(tmp_path):
    """Expert checkpoints are stored once as full per-tensor fragments (no
    per-rank duplication — the dedup the reference does in
    _save_moe_checkpoint, engine.py:3068) and reload under a DIFFERENT
    expert_parallel_size."""
    engine, _ = _moe_engine(
        {}, {"moe": {"enabled": True, "num_experts": 4,
                     "expert_parallel_size": 2},
             "zero_optimization": {"stage": 1}}, steps=3)
    engine.save_checkpoint(str(tmp_path / "ck"))
    # exactly ONE fragment file exists per expert tensor (no rank copies)
    import glob
    frags = glob.glob(str(tmp_path / "ck" / "*" / "params__layers__e_up.npy"))
    assert len(frags) == 1

    engine2, _ = _moe_engine(
        {}, {"moe": {"enabled": True, "num_experts": 4,
                     "expert_parallel_size": 4},
             "zero_optimization": {"stage": 1}}, steps=1, seed=9)
    engine2.load_checkpoint(str(tmp_path / "ck"))
    a = np.asarray(jax.device_get(engine.params["layers"]["e_up"]))
    b = np.asarray(jax.device_get(engine2.params["layers"]["e_up"]))
    np.testing.assert_allclose(b, a, rtol=1e-6)
    gm = engine2.micro_batch_size * engine2.ds_config.dp_world_size
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, 128, (1, gm, 64), dtype=np.int64)}
    assert np.isfinite(engine2.train_batch(batch=batch))


def test_dropless_matches_capacity_mode_when_nothing_drops():
    """moe_layer_dropless == capacity-mode moe_layer with capacity so large
    no token is dropped (the reference's drop_tokens=False semantics)."""
    from deepspeed_tpu.moe.sharded_moe import moe_layer, moe_layer_dropless

    H, E, F = 16, 4, 32
    rng = jax.random.PRNGKey(0)
    ks = jax.random.split(rng, 5)
    x = jax.random.normal(ks[0], (2, 8, H))
    gate_w = jax.random.normal(ks[1], (H, E)) * 0.5
    wg = jax.random.normal(ks[2], (E, H, F)) * 0.1
    wu = jax.random.normal(ks[3], (E, H, F)) * 0.1
    wd = jax.random.normal(ks[4], (E, F, H)) * 0.1

    def expert_fn(p, xe):
        g_, u_, d_ = p
        return (jax.nn.silu(xe @ g_) * (xe @ u_)) @ d_

    out_cap, aux_cap = moe_layer(x, gate_w, (wg, wu, wd), expert_fn,
                                 top_k=1, capacity_factor=float(E))
    out_dl, aux_dl = moe_layer_dropless(x, gate_w, (wg, wu, wd))
    np.testing.assert_allclose(np.asarray(out_dl), np.asarray(out_cap),
                               rtol=2e-5, atol=1e-6)
    np.testing.assert_allclose(float(aux_dl), float(aux_cap), rtol=1e-6)


def test_dropless_model_trains_and_ep_parity():
    """Dropless at ep=1 rides the ragged grouped GEMM; at ep>1 it takes
    the worst-case static-capacity dispatch (moe_layer_dropless_ep, the
    XLA analogue of the reference's dynamic-capacity allreduce,
    sharded_moe.py:214-218). Same data, same losses."""
    engine, losses = _moe_engine({"moe_dropless": True},
                                 {"zero_optimization": {"stage": 1}})
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]
    _, losses_ep = _moe_engine({"moe_dropless": True},
                               {"moe": {"enabled": True, "num_experts": 4,
                                        "expert_parallel_size": 2},
                                "zero_optimization": {"stage": 1}})
    np.testing.assert_allclose(np.asarray(losses_ep, dtype=np.float64),
                               np.asarray(losses, dtype=np.float64),
                               rtol=2e-4, atol=2e-4)


def test_moe_class_facade_matches_functional():
    """deepspeed_tpu.moe.MoE (reference moe/layer.py:16 class surface) wraps
    the functional core exactly."""
    from deepspeed_tpu.moe import MoE, moe_layer

    layer = MoE(hidden_size=16, intermediate_size=32, num_experts=4, k=2,
                capacity_factor=2.0)
    params = layer.init_params(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 16), jnp.float32)
    out, aux = layer(params, x)
    experts = (params["e_gate"], params["e_up"], params["e_down"])
    ref_out, ref_aux = moe_layer(
        x, params["gate_w"], experts, MoE._swiglu_expert, None,
        top_k=2, capacity_factor=2.0, min_capacity=4)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                               rtol=1e-6)
    np.testing.assert_allclose(float(aux), float(ref_aux), rtol=1e-6)
    assert out.shape == x.shape and np.isfinite(np.asarray(out)).all()


def test_moe_class_residual_and_dropless():
    from deepspeed_tpu.moe import MoE

    res = MoE(hidden_size=16, intermediate_size=32, num_experts=2, k=1,
              use_residual=True)
    p = res.init_params(jax.random.PRNGKey(2))
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 8, 16), jnp.float32)
    out, aux = res(p, x)
    assert out.shape == x.shape and np.isfinite(np.asarray(out)).all()

    dl = MoE(hidden_size=16, intermediate_size=32, num_experts=2, k=1,
             drop_tokens=False)
    p2 = dl.init_params(jax.random.PRNGKey(4))
    out2, aux2 = dl(p2, x)
    assert out2.shape == x.shape and np.isfinite(np.asarray(out2)).all()


def test_top_level_reference_exports():
    """Reference deepspeed/__init__.py:21-45 export parity."""
    import deepspeed_tpu as ds

    assert callable(ds.DistributedAttention)
    assert callable(ds.PipelineModule)
    from deepspeed_tpu.moe.layer import MoE
    assert callable(MoE)


def test_moe_class_dropless_guards():
    from deepspeed_tpu.moe import MoE
    import pytest as _pt

    with _pt.raises(NotImplementedError, match="top-1"):
        MoE(hidden_size=16, intermediate_size=32, num_experts=2, k=2,
            drop_tokens=False)
    with _pt.raises(NotImplementedError, match="expert_fn"):
        MoE(hidden_size=16, intermediate_size=32, num_experts=2, k=1,
            drop_tokens=False, expert_fn=lambda p, x: x)


def test_moe_class_top2_noise_guard():
    from deepspeed_tpu.moe import MoE
    import pytest as _pt

    with _pt.raises(NotImplementedError, match="top-1"):
        MoE(hidden_size=16, intermediate_size=32, num_experts=2, k=2,
            noisy_gate_policy="RSample")


def _ppep_cfg(aux_coef):
    return TransformerConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_layers=4, num_heads=4, max_seq_len=32,
        moe_num_experts=4, moe_capacity_factor=4.0, moe_min_capacity=8,
        moe_aux_loss_coef=aux_coef)


def _ppep_run(model_cfg, pp, micro, batch, steps=4):
    config = {"train_micro_batch_size_per_gpu": micro,
              "gradient_accumulation_steps": 4,
              "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
              "zero_optimization": {"stage": 1},
              "moe": {"enabled": True, "num_experts": 4,
                      "expert_parallel_size": 2},
              **({"pipeline": {"stages": pp}} if pp > 1 else {}),
              "steps_per_print": 100}
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=TransformerLM(model_cfg), config=config)
    return engine, [engine.train_batch(batch={"input_ids": batch})
                    for _ in range(steps)]


def test_pp_x_ep_matches_ep_only():
    """pp=2 x ep=2 through the explicit static-capacity all-to-all
    dispatch (moe_layer_manual) must match ep=2-only on the same global
    batch (VERDICT r3 #6 'done' bar). Aux loss off: its statistics are
    per-device (reference computes per-rank too), which differs from the
    GSPMD path's global statistics and would mask real dispatch bugs."""
    rng = np.random.default_rng(0)
    batch = rng.integers(0, 128, (4, 16, 32), dtype=np.int64)
    _, l_ep = _ppep_run(_ppep_cfg(0.0), pp=1, micro=2, batch=batch)
    eng, l_pp = _ppep_run(_ppep_cfg(0.0), pp=2, micro=4, batch=batch)
    assert eng.topology.axis_size("pipe") == 2
    assert eng.topology.axis_size("expert") == 2
    np.testing.assert_allclose(l_pp, l_ep, rtol=1e-5, atol=5e-5)
    # expert weights actually sharded over the expert axis
    eg = eng.params["layers"]["e_gate"]
    assert not eg.sharding.is_fully_replicated


@pytest.mark.slow  # tier-1 sibling: test_pp_x_ep_matches_ep_only (same pp x ep composition, aux off)
def test_pp_x_ep_trains_with_aux_loss():
    """With the load-balancing aux on (per-device statistics), pp x ep
    still tracks the ep-only trajectory and decreases."""
    rng = np.random.default_rng(0)
    batch = rng.integers(0, 128, (4, 16, 32), dtype=np.int64)
    _, l_ep = _ppep_run(_ppep_cfg(0.01), pp=1, micro=2, batch=batch)
    _, l_pp = _ppep_run(_ppep_cfg(0.01), pp=2, micro=4, batch=batch)
    assert np.isfinite(l_pp).all() and l_pp[-1] < l_pp[0]
    np.testing.assert_allclose(l_pp, l_ep, rtol=2e-3, atol=1e-2)


# ---------------------------------------------------------------------------
# the sorted dispatch (dropless_topk_dispatch) against a loop over experts
# ---------------------------------------------------------------------------
def _loop_over_experts(xt, topi, topv, ws, first=0):
    """``for e: mask, expert(x), weight, add`` over the experts ``ws``
    hold, the router's ``first`` ..: the plain form of the dispatch."""
    from deepspeed_tpu.moe.sharded_moe import _swiglu_expert
    out = jnp.zeros_like(xt)
    for e in range(ws[0].shape[0]):
        weight = jnp.sum(jnp.where(topi == first + e, topv, 0), axis=-1)
        out = out + _swiglu_expert(xt, *(w[e] for w in ws)) \
            * weight[:, None]
    return out


def _poisoned(expert_params, xs, group_sizes):
    """The routed experts, with every row past the last group NaN: what
    a grouped matmul may leave where it computed nothing."""
    from deepspeed_tpu.moe.sharded_moe import ragged_swiglu_experts
    ys = ragged_swiglu_experts(expert_params, xs, group_sizes)
    computed = jnp.arange(ys.shape[0]) < jnp.sum(group_sizes)
    return jnp.where(computed[:, None], ys, jnp.nan)


# experts the router scores, of which the tree holds [first, first + held):
# a token's k picks lie among ``picked``
_WHOLE = dict(E=12, first=0, held=12, picked=(0, 12))
DISPATCH_CASES = {
    **{f"k{k}-T{T}": dict(_WHOLE, k=k, T=T)
       for k in (1, 2, 8, 10) for T in (13, 1)},
    "k8-T40": dict(_WHOLE, k=8, T=40),
    "held-all": dict(E=12, first=6, held=6, picked=(6, 12), k=2, T=13),
    "held-none": dict(E=12, first=6, held=6, picked=(0, 6), k=2, T=13),
    "held-mixed": dict(E=12, first=6, held=6, picked=(0, 12), k=10, T=13),
    "held-mixed-first": dict(E=12, first=0, held=4, picked=(0, 12), k=8,
                             T=1),
    "stack-layer": dict(_WHOLE, k=2, T=13, layer=1),
    "stack-layer-held": dict(E=12, first=4, held=4, picked=(0, 12), k=10,
                             T=13, layer=2),
}


@pytest.mark.parametrize("case", sorted(DISPATCH_CASES))
def test_the_dispatch_is_a_loop_over_experts(case):
    """Pick-major keys, the gather back and the fused combine give what
    a loop over the experts gives, and its gradient in the rows, the
    weights of the picks and the experts: at every k the callers have,
    at a T that is no multiple of a tile's rows and at one token; as a
    share (``held_from``) where a token's picks are all held, none, or
    some, with the rows no expert computed poisoned (they must add
    nothing, not NaN x 0); as one layer of a stack (``stack_layer``)."""
    from deepspeed_tpu.moe.sharded_moe import dropless_topk_dispatch
    c = DISPATCH_CASES[case]
    T, k, E, first, held = c["T"], c["k"], c["E"], c["first"], c["held"]
    H, F, L = 16, 24, 3
    rng = np.random.default_rng(sorted(DISPATCH_CASES).index(case))
    f = lambda *s: jnp.asarray(rng.normal(size=s) / s[-2] ** 0.5,
                               jnp.float32)
    xt = f(T, H) * H ** 0.5
    stack = (f(L, held, H, F), f(L, held, H, F), f(L, held, F, H))
    lo, hi = c["picked"]
    topi = jnp.asarray(np.stack([rng.choice(np.arange(lo, hi), k,
                                            replace=False)
                                 for _ in range(T)]), jnp.int32)
    topv = jnp.asarray(rng.uniform(0.1, 1.0, (T, k)), jnp.float32)
    layer = c.get("layer")
    share = None if held == E else first

    def got(xt, topv, stack):
        ws = stack if layer is not None else tuple(w[0] for w in stack)
        return dropless_topk_dispatch(
            xt, topi, topv, ws, held, _poisoned, held_from=share,
            stack_layer=None if layer is None else jnp.int32(layer))

    def want(xt, topv, stack):
        return _loop_over_experts(
            xt, topi, topv, tuple(w[layer or 0] for w in stack), first)

    with jax.default_matmul_precision("highest"):
        out = got(xt, topv, stack)
        assert out.shape == (T, H) and np.isfinite(np.asarray(out)).all()
        np.testing.assert_allclose(out, want(xt, topv, stack), atol=2e-5)
        if case == "held-none":
            assert not np.asarray(out).any()
        probe = f(T, H)
        grads = [jax.grad(lambda *a: jnp.sum(fn(*a) * probe),
                          argnums=(0, 1, 2))(xt, topv, stack)
                 for fn in (got, want)]
    for a, b in zip(*(jax.tree.leaves(g) for g in grads)):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(a, b, atol=5e-5)


def test_the_dispatch_moves_no_rows_by_scatter_or_through_a_padded_axis():
    """The shape of the program, not its speed, at granite's run of
    2,048 tokens x 10 picks x 4,096: the rows come back by ONE gather of
    ``[T * k, H]``; nothing is scattered, neither rows into a ``[T * k,
    H]`` (a TPU scatters rows one after another: 613.7 ms of granite's
    prompt, PR 54's trace) nor ones into the experts' counts
    (``bincount``: 51.6 ms); and no array is ``[T, k, H]`` (the tiled
    layout pads the k = 10 to 16: a relayout copy of 1.6x the bytes,
    219.2 ms)."""
    from deepspeed_tpu.moe.sharded_moe import dropless_topk_dispatch
    T, H, F, E, k = 2048, 4096, 768, 36, 10
    sds = jax.ShapeDtypeStruct
    jaxpr = jax.make_jaxpr(
        lambda xt, topi, topv, *ws: dropless_topk_dispatch(
            xt, topi, topv, ws, E, held_from=0))(
        sds((T, H), jnp.bfloat16), sds((T, k), jnp.int32),
        sds((T, k), jnp.float32), sds((E, H, F), jnp.bfloat16),
        sds((E, H, F), jnp.bfloat16), sds((E, F, H), jnp.bfloat16))

    def eqns(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from eqns(sub)

    seen = list(eqns(jaxpr.jaxpr))
    assert sum(e.primitive.name == "gather"
               and e.outvars[0].aval.shape == (T * k, H)
               and e.invars[0].aval.shape == (T * k, H) for e in seen) == 1
    for e in seen:
        assert not e.primitive.name.startswith("scatter"), e
        assert (T, k, H) not in [v.aval.shape for v in (*e.invars,
                                                        *e.outvars)], e
