"""The bench mini-autotune ladder only ever CONSTRUCTS on a real chip;
this pins its shape off-chip so edits can't silently break the autotune,
and pins what the ladder may skip: a rung that does not fit the chip,
nothing else."""

import sys

import pytest


def test_bench_trial_ladder_shape():
    sys.path.insert(0, ".")
    import bench
    from deepspeed_tpu.models import TransformerConfig

    base = TransformerConfig(vocab_size=32000, hidden_size=1024,
                             intermediate_size=2816, num_layers=24,
                             num_heads=8, max_seq_len=2048)
    trials = bench.build_trials(base)
    assert len(trials) == 20
    # most promising first: selective remat + flash + biggest micro batch
    cfg0, micro0, pol0 = trials[0]
    assert (cfg0.use_flash, micro0, pol0) == (True, 16, "save_dots_and_attn")
    # the block-size and unchunked-CE variants sit early in the ladder
    assert any(t[0].attn_block_q == 512 for t in trials[:3])
    assert any(t[0].loss_chunk == 0 for t in trials[:7])
    # round-5 additions: mb=24/32 full-recompute (r05 winner was mb=16
    # nothing_saveable — bigger batches amortize further if they fit)
    assert any(t[1] == 24 for t in trials[:4])
    assert any(t[1] == 32 for t in trials[:4])
    # round-4 additions: long-seq and tall-q flash variants, early
    assert any(t[0].max_seq_len == 4096 for t in trials[:8])
    assert any(t[0].attn_block_q == 1024 for t in trials[:8])
    # every policy gets at least one flash and one xla trial
    for pol in ("save_dots_and_attn", "dots_with_no_batch_dims_saveable",
                "nothing_saveable"):
        mine = [t for t in trials if t[2] == pol]
        assert any(t[0].use_flash for t in mine)
        assert any(not t[0].use_flash for t in mine)
    # ladder entries never mutate the base model geometry (the long-seq
    # variant changes max_seq_len only; MFU normalizes by measured seq)
    assert all(t[0].hidden_size == base.hidden_size and
               t[0].num_layers == base.num_layers for t in trials)


def test_bench_scale_points_construct_off_chip():
    """Every bench scale point must CONSTRUCT off-chip: the r05 chip
    window lost its only >374M MFU datum to the large proxy inheriting
    num_kv_heads=8 against num_heads=12 and asserting mid-capture
    ('GQA requires h(12) % hk(8) == 0'). Config validation now rejects
    the pairing at construction, and this test builds the exact configs
    bench.py / benchmarks/aot_scale.py will run on the next window."""
    sys.path.insert(0, ".")
    import bench
    from __graft_entry__ import _flagship_cfg

    base = _flagship_cfg()
    big = bench.large_proxy_cfg(base)
    assert big.num_heads % big.kv_heads == 0
    assert (big.hidden_size, big.num_heads, big.num_kv_heads) \
        == (1536, 12, 4)
    # the ladder's trial configs are all replace()s of base — each one
    # revalidates through __post_init__ when constructed
    for cfg, _, _ in bench.build_trials(base):
        assert cfg.num_heads % cfg.kv_heads == 0
    # aot_scale's overlap proxy (the other off-chip scale point)
    from deepspeed_tpu.models import TransformerConfig
    aot = TransformerConfig(
        vocab_size=32000, hidden_size=1024, intermediate_size=2816,
        num_layers=24, num_heads=8, num_kv_heads=8, max_seq_len=2048)
    assert aot.num_heads % aot.kv_heads == 0


def test_indivisible_gqa_pair_fails_at_config_time():
    """An indivisible (num_heads, num_kv_heads) pair must fail when the
    config is BUILT, with the valid choices in the message — not
    mid-capture inside flash_attention on a live chip."""
    import dataclasses

    from deepspeed_tpu.models import TransformerConfig

    with pytest.raises(ValueError, match=r"num_kv_heads.*\[1, 2, 3, 4"):
        TransformerConfig(vocab_size=128, hidden_size=768,
                          intermediate_size=1536, num_layers=2,
                          num_heads=12, num_kv_heads=8, max_seq_len=128)
    # dataclasses.replace() re-runs validation: the exact r05 failure
    # shape (replace() setting num_heads without num_kv_heads) now
    # raises immediately instead of compiling toward an assert
    base = TransformerConfig(vocab_size=128, hidden_size=512,
                             intermediate_size=1024, num_layers=2,
                             num_heads=8, num_kv_heads=8, max_seq_len=128)
    with pytest.raises(ValueError, match="GQA requires"):
        dataclasses.replace(base, hidden_size=768, num_heads=12)


def _bench_on_a_fake_chip(monkeypatch, measure):
    """bench.main() up to its first measurement, with the chip check and
    the measurement replaced."""
    sys.path.insert(0, ".")
    import bench
    from deepspeed_tpu.accelerator import tpu_accelerator
    monkeypatch.setenv("LIBTPU_INIT_ARGS", "")     # main() exports flags
    monkeypatch.setattr(tpu_accelerator, "require_tpu", lambda: [object()])
    monkeypatch.setattr(bench, "_measure", measure)
    return bench


def test_ladder_skips_only_out_of_memory(monkeypatch, capsys):
    def oom(*a, **k):
        raise RuntimeError("RESOURCE_EXHAUSTED: Ran out of memory in hbm")

    bench = _bench_on_a_fake_chip(monkeypatch, oom)
    with pytest.raises(RuntimeError, match="no bench config fits"):
        bench.main([])

    def broken(*a, **k):
        raise ValueError("Mosaic failed to compile TPU kernel")

    bench = _bench_on_a_fake_chip(monkeypatch, broken)
    with pytest.raises(ValueError, match="Mosaic failed"):
        bench.main([])
    assert capsys.readouterr().out == ""      # no number either way


def test_bench_carries_no_cpu_route():
    """What ISSUE 22 took out of bench.py stays out: the CPU smoke mode,
    the scraped old captures, the mid-run AOT call that re-pinned the
    platform and wrote into artifacts/, and the exit-0 wrapper."""
    import pathlib
    src = pathlib.Path("bench.py").read_text()
    for gone in ("_ensure_jax_platform", "tpu_unreachable",
                 "latest_chip_capture", "grad_overlap_dp8", "artifacts",
                 "CPU smoke", '"value": 0.0'):
        assert gone not in src, gone
