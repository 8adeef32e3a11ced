"""Transformer LM through the engine on DP / TP / SP / combined meshes."""

import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.models import TransformerConfig, TransformerLM, tiny_test


def make_batch(b, s, vocab, seed=0):
    rng = np.random.default_rng(seed)
    return {"input_ids": rng.integers(0, vocab, (1, b, s), dtype=np.int64)}


def run_engine(cfg_updates, model_cfg=None, steps=4, micro=None):
    mcfg = model_cfg or tiny_test()
    model = TransformerLM(mcfg)
    config = {
        "train_micro_batch_size_per_gpu": micro or 1,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "steps_per_print": 100,
    }
    config.update(cfg_updates)
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=config)
    gm = engine.micro_batch_size * engine.ds_config.dp_world_size
    batch = make_batch(gm, mcfg.max_seq_len, mcfg.vocab_size)
    losses = [engine.train_batch(batch=batch) for _ in range(steps)]
    return losses, engine


def test_tiny_llama_dp_zero2():
    losses, _ = run_engine({"zero_optimization": {"stage": 2},
                            "bf16": {"enabled": True}})
    assert losses[-1] < losses[0]
    assert np.isfinite(losses).all()


def test_tiny_llama_zero3():
    losses, engine = run_engine({
        "zero_optimization": {"stage": 3, "stage3_param_persistence_threshold": 0}})
    assert losses[-1] < losses[0]
    w = engine.params["layers"]["wq"]
    assert not w.sharding.is_fully_replicated


def test_tiny_llama_tp():
    """2-way tensor parallel x 4-way data parallel."""
    losses, engine = run_engine({"tensor_parallel_size": 2,
                                 "zero_optimization": {"stage": 1}})
    assert losses[-1] < losses[0]
    spec = engine.params["layers"]["wq"].sharding.spec
    assert "model" in str(spec)


def test_tiny_llama_sp():
    """2-way Ulysses sequence parallel."""
    losses, _ = run_engine({"sequence_parallel_size": 2}, steps=3)
    assert losses[-1] < losses[0]


def test_tp_matches_dp():
    """TP=2 must be numerically close to pure DP (same 8-row global batch)."""
    l_dp, _ = run_engine({}, steps=3, micro=1)                      # dp=8
    l_tp, _ = run_engine({"tensor_parallel_size": 2}, steps=3, micro=2)  # dp=4
    np.testing.assert_allclose(l_dp, l_tp, rtol=1e-3)


def test_sp_matches_dp():
    l_dp, _ = run_engine({}, steps=3, micro=1)
    l_sp, _ = run_engine({"sequence_parallel_size": 2}, steps=3, micro=2)
    np.testing.assert_allclose(l_dp, l_sp, rtol=1e-3)


def test_gpt2_family():
    cfg = TransformerConfig(vocab_size=128, hidden_size=64,
                            intermediate_size=256, num_layers=2, num_heads=4,
                            max_seq_len=64, norm="layernorm", activation="gelu",
                            positional="learned", tie_embeddings=True)
    losses, _ = run_engine({}, model_cfg=cfg, steps=4)
    assert losses[-1] < losses[0]


def test_gqa_model():
    cfg = TransformerConfig(vocab_size=128, hidden_size=128,
                            intermediate_size=256, num_layers=2, num_heads=8,
                            num_kv_heads=2, max_seq_len=128)
    losses, _ = run_engine({"bf16": {"enabled": True},
                            "zero_optimization": {"stage": 2}},
                           model_cfg=cfg, steps=4)
    assert losses[-1] < losses[0]


def test_mlm_encoder_attention_is_bidirectional():
    """objective='mlm' attends bidirectionally: a LATER token change must
    move an EARLIER position's hidden state (it cannot under causal)."""
    import dataclasses
    from deepspeed_tpu.models import TransformerConfig, TransformerLM

    cfg = TransformerConfig(vocab_size=64, hidden_size=32,
                            intermediate_size=64, num_layers=2, num_heads=4,
                            max_seq_len=16, use_flash=False,
                            objective="mlm", tie_embeddings=True)
    model = TransformerLM(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    ids_a = jnp.asarray([[1, 2, 3, 4, 5, 6, 7, 8]])
    ids_b = ids_a.at[0, 7].set(9)                  # change only the LAST token
    ha, _ = model.forward_hidden(params, ids_a)
    hb, _ = model.forward_hidden(params, ids_b)
    assert not np.allclose(np.asarray(ha[0, 0]), np.asarray(hb[0, 0]))

    causal = TransformerLM(dataclasses.replace(cfg, objective="causal_lm"))
    ca, _ = causal.forward_hidden(params, ids_a)
    cb, _ = causal.forward_hidden(params, ids_b)
    np.testing.assert_allclose(np.asarray(ca[0, 0]), np.asarray(cb[0, 0]),
                               rtol=1e-6)


def test_mlm_training_decreases_loss():
    """BERT-family MLM end-to-end through the engine: mask 15% of tokens,
    predict the originals; loss decreases."""
    import deepspeed_tpu
    from deepspeed_tpu.models import TransformerConfig, TransformerLM

    cfg = TransformerConfig(vocab_size=64, hidden_size=32,
                            intermediate_size=64, num_layers=2, num_heads=4,
                            max_seq_len=16, use_flash=False,
                            objective="mlm", tie_embeddings=True)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=TransformerLM(cfg),
        config={"train_micro_batch_size_per_gpu": 2,
                "optimizer": {"type": "adamw", "params": {"lr": 3e-3}},
                "steps_per_print": 10 ** 9})
    gm = engine.micro_batch_size * engine.ds_config.dp_world_size
    rng = np.random.default_rng(0)
    labels = rng.integers(0, 64, (1, gm, 16), dtype=np.int64)
    mask = (rng.random((1, gm, 16)) < 0.15).astype(np.int64)
    MASK_TOKEN = 63
    inputs = np.where(mask == 1, MASK_TOKEN, labels)
    batch = {"input_ids": inputs, "labels": labels, "loss_mask": mask}
    losses = [float(engine.train_batch(batch=batch)) for _ in range(8)]
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], losses


def test_mlm_rejects_generation():
    from deepspeed_tpu.models import TransformerConfig, TransformerLM

    cfg = TransformerConfig(vocab_size=64, hidden_size=32,
                            intermediate_size=64, num_layers=2, num_heads=4,
                            max_seq_len=16, objective="mlm",
                            tie_embeddings=True)
    with pytest.raises(AssertionError, match="causal_lm"):
        TransformerLM(cfg).init_kv_cache(1, 16)


def test_indivisible_gqa_pair_fails_at_config_time():
    """An indivisible (num_heads, num_kv_heads) pair must fail when the
    config is BUILT, with the valid choices in the message — not
    mid-capture inside flash_attention on a live chip."""
    import dataclasses

    with pytest.raises(ValueError, match=r"num_kv_heads.*\[1, 2, 3, 4"):
        TransformerConfig(vocab_size=128, hidden_size=768,
                          intermediate_size=1536, num_layers=2,
                          num_heads=12, num_kv_heads=8, max_seq_len=128)
    # dataclasses.replace() re-runs validation: replace() setting
    # num_heads without num_kv_heads raises at once instead of compiling
    # toward an assert (a round-5 chip window was lost to that)
    base = TransformerConfig(vocab_size=128, hidden_size=512,
                             intermediate_size=1024, num_layers=2,
                             num_heads=8, num_kv_heads=8, max_seq_len=128)
    with pytest.raises(ValueError, match="GQA requires"):
        dataclasses.replace(base, hidden_size=768, num_heads=12)


def test_mlm_config_and_batch_guards():
    from deepspeed_tpu.models import TransformerConfig, TransformerLM

    with pytest.raises(ValueError, match="objective"):
        TransformerConfig(objective="masked_lm")
    cfg = TransformerConfig(vocab_size=32, hidden_size=16,
                            intermediate_size=32, num_layers=1, num_heads=2,
                            max_seq_len=8, use_flash=False, objective="mlm",
                            tie_embeddings=True)
    model = TransformerLM(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    ids = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(AssertionError, match="loss_mask"):
        model.apply(params, {"input_ids": ids, "labels": ids})
