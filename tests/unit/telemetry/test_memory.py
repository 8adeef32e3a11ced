"""Device-memory accounting: program memory analysis gauges populate
chip-free via AOT lowering, buffer gauges track the big allocations, and
oom_report names the culprits."""

import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                        RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.config_v2 import DSStateManagerConfig
from deepspeed_tpu.models import TransformerConfig, TransformerLM
from deepspeed_tpu.telemetry import (MetricsRegistry, get_registry,
                                     set_registry)
from deepspeed_tpu.telemetry import memory as ds_memory


@pytest.fixture(autouse=True)
def _fresh():
    prev = set_registry(MetricsRegistry())
    ds_memory.reset()
    yield get_registry()
    ds_memory.reset()
    set_registry(prev)


@pytest.fixture(scope="module")
def tiny_model():
    cfg = TransformerConfig(vocab_size=128, hidden_size=64,
                            intermediate_size=128, num_layers=2,
                            num_heads=4, num_kv_heads=2, max_seq_len=128,
                            remat=False, use_flash=False)
    model = TransformerLM(cfg)
    params = jax.tree.map(lambda x: x.astype(jnp.float32),
                          model.init_params(jax.random.PRNGKey(0)))
    return model, params


def test_record_memory_analysis_plain_program(_fresh):
    compiled = jax.jit(lambda x: x @ x).lower(
        jnp.ones((32, 32), jnp.float32)).compile()
    rec = ds_memory.record_memory_analysis("matmul", compiled)
    assert rec["argument_size_in_bytes"] >= 32 * 32 * 4
    assert rec["peak_bytes"] >= rec["argument_size_in_bytes"]
    assert rec["flops"] > 0
    g = _fresh.get("xla_program_peak_bytes")
    assert g.labels(program="matmul").value == rec["peak_bytes"]
    assert _fresh.get("xla_program_argument_bytes").labels(
        program="matmul").value == rec["argument_size_in_bytes"]


def test_engine_memory_report_chip_free(tiny_model, _fresh):
    """The decode and ragged-step programs' memory gauges populate from AOT
    lowering alone — no generate() call, no device execution of the
    analyzed shapes."""
    model, params = tiny_model
    eng = InferenceEngineV2(
        model, RaggedInferenceEngineConfig(
            state_manager=DSStateManagerConfig(
                max_tracked_sequences=8, max_seq_len=128, num_blocks=33,
                block_size=16),
            dtype="float32", prefill_bucket=16, decode_window=8),
        params=params)
    rep = eng.memory_report(batch=2)
    assert set(rep["programs"]) == {"decode_greedy",
                                    "decode_window_greedy", "ragged_step"}
    for rec in rep["programs"].values():
        assert rec["peak_bytes"] > 0
        # every program references the params and pool
        assert rec["argument_size_in_bytes"] > 0
    # the engine registered its long-lived buffers at construction
    assert rep["buffers"]["kv_pool"] > 0
    assert rep["buffers"]["params"] > 0
    g = _fresh.get("device_buffer_bytes")
    assert g.labels(buffer="kv_pool").value == rep["buffers"]["kv_pool"]
    assert _fresh.get("xla_program_peak_bytes").labels(
        program="decode_window_greedy").value > 0


def test_oom_report_ranks_largest_first(_fresh):
    ds_memory.record_buffer("kv_pool", 1000)
    ds_memory.record_buffer("params", 5000)
    c_small = jax.jit(lambda x: x + 1).lower(jnp.ones(8)).compile()
    c_big = jax.jit(lambda x: x @ x).lower(
        jnp.ones((64, 64), jnp.float32)).compile()
    ds_memory.record_memory_analysis("small", c_small)
    ds_memory.record_memory_analysis("big", c_big)
    rep = ds_memory.oom_report()
    assert rep["largest_buffer"] == "params"
    assert rep["programs"][0]["program"] == "big"
    assert rep["total_buffer_bytes"] == 6000
    text = ds_memory.format_oom_report(rep)
    assert "big" in text and "params" in text


def test_tree_bytes_counts_pytrees():
    tree = {"a": jnp.ones((4, 4), jnp.float32),
            "b": [jnp.ones(10, jnp.int32)]}
    assert ds_memory.tree_bytes(tree) == 4 * 4 * 4 + 10 * 4


def test_offers_keep_a_programs_newest_and_release_the_rest():
    """An offer holds the jitted function, and with it every executable
    it loaded: a program keeps its newest ``_OFFERS_KEPT`` and lets the
    functions of older ones go (a test process builds engines by the
    hundred; the benchmark's reader asks after the engine is deleted,
    for the two signatures a program ran)."""
    import gc
    import weakref

    class Fn:
        pass

    ds_memory.reset()
    try:
        kept = ds_memory._OFFERS_KEPT
        fns = [Fn() for _ in range(kept + 3)]
        refs = [weakref.ref(f) for f in fns]
        for f in fns:
            ds_memory.offer_executable("p", lambda f=f: f)
        del fns, f
        gc.collect()
        assert ds_memory.signatures_offered("p") == kept
        assert [r() is None for r in refs] == [True] * 3 + [False] * kept
    finally:
        ds_memory.reset()
