"""Session-shared tiny-model fixtures, and the paged pool as stored.

Most inference/serving test modules build the SAME tiny transformer
(vocab 128, hidden 64, 2 layers, 4/2 heads) with a module-scoped
fixture — a dozen redundant ``init_params`` jits per tier-1 run.
These session fixtures build each variant once; module fixtures alias
them (params are never mutated by engines — InferenceEngineV2 casts
into its own buffers — so sharing across modules is safe).
"""

import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.models import TransformerConfig, TransformerLM


def _build_tiny(max_seq_len: int):
    cfg = TransformerConfig(vocab_size=128, hidden_size=64,
                            intermediate_size=128, num_layers=2,
                            num_heads=4, num_kv_heads=2,
                            max_seq_len=max_seq_len, remat=False,
                            use_flash=False)
    model = TransformerLM(cfg)
    params = jax.tree.map(lambda x: x.astype(jnp.float32),
                          model.init_params(jax.random.PRNGKey(0)))
    return model, params


@pytest.fixture(scope="session")
def tiny_model_256():
    """(model, params) for the max_seq_len=256 tiny serving model."""
    return _build_tiny(256)


@pytest.fixture(scope="session")
def tiny_model_128():
    """(model, params) for the max_seq_len=128 tiny serving model."""
    return _build_tiny(128)


@pytest.fixture(scope="session")
def stored_pool():
    """``stored_pool(layer_kv, layers=1, at=0)``: the paged pool as
    ``init_paged_kv_cache`` stores it, ``[L, nb, bs, kvh * hd]``, with
    the ``[nb, bs, kvh, hd]`` array its layer ``at``. Every other layer
    holds what no launch may read and no write ever makes: NaN (an int8
    pool: -128)."""
    def stored(layer_kv, layers=1, at=0):
        nb, bs = layer_kv.shape[:2]
        bad = -128 if layer_kv.dtype == jnp.int8 else jnp.nan
        pool = jnp.full((layers, nb, bs, layer_kv[0, 0].size), bad,
                        layer_kv.dtype)
        return pool.at[at].set(layer_kv.reshape(nb, bs, -1))
    return stored
