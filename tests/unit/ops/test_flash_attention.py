"""Kernel-parity tests: Pallas flash attention vs jnp reference (mirrors the
reference's tests/unit/ops numeric-parity strategy)."""

import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from deepspeed_tpu.ops import flash_attention as fa
from deepspeed_tpu.ops.flash_attention import (block_schedule, flash_attention,
                                               mha_reference)
from deepspeed_tpu.ops.norms import rms_norm_pallas, rms_norm_ref


def rand_qkv(b=2, h=4, hk=None, s=256, d=64, dtype=jnp.float32, seed=0):
    hk = hk or h
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(k1, (b, h, s, d), dtype)
    k = jax.random.normal(k2, (b, hk, s, d), dtype)
    v = jax.random.normal(k3, (b, hk, s, d), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
def test_flash_forward_parity(causal):
    q, k, v = rand_qkv()
    out = flash_attention(q, k, v, causal=causal)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_flash_gqa():
    q, k, v = rand_qkv(h=8, hk=2)
    out = flash_attention(q, k, v, causal=True)
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_flash_rectangular_blocks():
    q, k, v = rand_qkv(s=384, d=64)
    out = flash_attention(q, k, v, causal=True, block_q=128, block_kv=128)
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_backward_parity(causal):
    q, k, v = rand_qkv(b=1, h=2, s=256, d=64)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=causal) ** 2)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g1, g2, "qkv"):
        np.testing.assert_allclose(a, b, atol=5e-4, rtol=1e-3,
                                   err_msg=f"d{name} mismatch")


def test_flash_backward_gqa():
    q, k, v = rand_qkv(b=1, h=4, hk=2, s=128, d=64)
    g1 = jax.grad(lambda *a: jnp.sum(flash_attention(*a) ** 2), argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda *a: jnp.sum(mha_reference(*a) ** 2), argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g1, g2, "qkv"):
        np.testing.assert_allclose(a, b, atol=5e-4, rtol=1e-3,
                                   err_msg=f"d{name} mismatch")


def test_rms_norm_parity():
    x = jax.random.normal(jax.random.PRNGKey(0), (512, 256))
    w = jax.random.normal(jax.random.PRNGKey(1), (256,)) + 1.0
    np.testing.assert_allclose(rms_norm_pallas(x, w), rms_norm_ref(x, w),
                               atol=1e-5, rtol=1e-5)


def test_flash_cross_length_fwd_bwd():
    """sq != skv: bottom-right-aligned causal + correct dk/dv shapes."""
    q, _, _ = rand_qkv(b=1, h=2, s=256, d=64)
    _, k, v = rand_qkv(b=1, h=2, s=128, d=64, seed=1)
    out = flash_attention(q, k, v, causal=True)
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
    g1 = jax.grad(lambda *a: jnp.sum(flash_attention(*a) ** 2), argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda *a: jnp.sum(mha_reference(*a) ** 2), argnums=(0, 1, 2))(q, k, v)
    assert g1[1].shape == k.shape and g1[2].shape == v.shape
    for a, b, name in zip(g1, g2, "qkv"):
        np.testing.assert_allclose(a, b, atol=5e-4, rtol=1e-3,
                                   err_msg=f"d{name} mismatch")


def test_attention_autotune_parity_and_crossover():
    """parity_check + measure_crossover run on the test backend (interpret
    mode here; the same entry runs on-chip via ds_tpu_flash_check and is
    recorded in every bench)."""
    from deepspeed_tpu.ops.attention_autotune import (measure_crossover,
                                                      parity_check)

    rep = parity_check(batch=1, heads=2, kv_heads=1, seq=128, head_dim=8,
                       dtype=jnp.float32)
    assert rep["out_rel_err"] < 1e-5
    assert max(rep["dq_rel_err"], rep["dk_rel_err"],
               rep["dv_rel_err"]) < 1e-4

    crossover, timings = measure_crossover(
        batch=1, heads=2, kv_heads=2, head_dim=8, dtype=jnp.float32,
        seqs=(128,), steps=1)
    assert 128 in timings
    assert crossover in (None, 128)


# ---------------------------------------------------------------------------
# the block schedule: which blocks the kernels visit, mask and skip
# ---------------------------------------------------------------------------

def _grads(fn, q, k, v):
    return jax.grad(lambda *a: jnp.sum(fn(*a) ** 2), argnums=(0, 1, 2))(
        q, k, v)


FUSED, PAIR = {"fwd", "bwd"}, {"fwd", "dq", "dkv"}


def _traced(fn):
    """(fn(), snapshot of the gauges its trace set), in a registry of its
    own: the ``kernel`` label says which kernels a call took."""
    from deepspeed_tpu.telemetry import registry
    old = registry.set_registry(registry.MetricsRegistry())
    try:
        return fn(), registry.get_registry().snapshot()["metrics"]
    finally:
        registry.set_registry(old)


def _kernels(metrics):
    return {row["labels"]["kernel"]
            for row in metrics["flash_blocks_live"]["series"]}


def _check_fwd_bwd(q, k, v, causal=True, kernels=FUSED, **blocks):
    """Forward and backward against the reference, through whichever
    backward the shapes take: ``kernels`` is the set they must take."""
    out = flash_attention(q, k, v, causal=causal, **blocks)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
    g1, metrics = _traced(lambda: _grads(
        lambda *a: flash_attention(*a, causal=causal, **blocks), q, k, v))
    assert _kernels(metrics) == kernels
    g2 = _grads(lambda *a: mha_reference(*a, causal=causal), q, k, v)
    for a, b, name in zip(g1, g2, "qkv"):
        np.testing.assert_allclose(a, b, atol=5e-4, rtol=1e-3,
                                   err_msg=f"d{name} mismatch")


def _brute_force_schedule(sq, skv, bq, bk, causal):
    """(live, masked) from the mask itself, block pair by block pair."""
    mask = (np.tril(np.ones((sq, skv), bool), k=skv - sq) if causal
            else np.ones((sq, skv), bool))
    live = masked = 0
    for i in range(sq // bq):
        for j in range(skv // bk):
            tile = mask[i * bq:(i + 1) * bq, j * bk:(j + 1) * bk]
            live += bool(tile.any())
            masked += bool(tile.any() and not tile.all())
    return live, masked


@pytest.mark.parametrize("sq,skv,bq,bk,causal", [
    (2048, 2048, 256, 512, True),      # the cells' geometry, parent's blocks
    (2048, 2048, 256, 256, True),
    (2048, 2048, 512, 128, True),
    (2048, 2048, 128, 1024, True),
    (1024, 2048, 256, 512, True),      # off > 0
    (2048, 1024, 256, 256, True),      # off < 0: fully-masked rows
    (384, 384, 128, 128, True),
    (2048, 2048, 256, 512, False),
])
@pytest.mark.parametrize("kv_major", [False, True])
def test_block_schedule_matches_the_mask(sq, skv, bq, bk, causal, kv_major):
    live, masked = _brute_force_schedule(sq, skv, bq, bk, causal)
    inner, block = (sq, bq) if kv_major else (skv, bk)
    for chunk in (None, inner, 2 * block if inner % (2 * block) == 0
                  else block):
        sched = block_schedule(sq, skv, bq, bk, causal, chunk=chunk,
                               kv_major=kv_major)
        assert (sched.live, sched.masked) == (live, masked), (chunk, sched)


def test_block_schedule_at_the_cells_geometry():
    """What ISSUE 26 read off the parent (one block a grid step, every live
    block masked, every block fetched) against the schedule now: the walk
    inside one grid step a q block, the diagonal's blocks alone masked,
    K/V fetched once a head."""
    one_block_a_step = block_schedule(2048, 2048, 256, 512, True)
    assert one_block_a_step[:2] == (32, 20)
    resident = block_schedule(2048, 2048, 256, 512, True, chunk=2048)
    assert resident == (8, 20, 8, 1)
    # two chunks: a dead step re-names the chunk already resident
    two = block_schedule(2048, 2048, 256, 512, True, chunk=1024)
    assert (two.grid, two.live, two.masked) == (16, 20, 8)
    # q blocks 0-3 stay on chunk 0 (one fetch), 4-7 alternate between two
    assert two.fetched == 1 + 1 + 3 * 2
    assert block_schedule(2048, 2048, 256, 512, False, chunk=1024) == (
        16, 32, 0, 16)


@pytest.mark.parametrize("kernels", [FUSED, PAIR], ids=["fused", "pair"])
def test_schedule_gauges_are_published_per_traced_geometry(monkeypatch,
                                                           kernels):
    """The ``kernel`` label is what says which backward a call took:
    ``bwd`` for the fused kernel, ``dq`` and ``dkv`` for the pair."""
    if kernels == PAIR:
        monkeypatch.setattr(fa, "_FUSED_BWD_BYTES", 0)
    q, k, v = rand_qkv(b=1, h=1, s=256, d=64)
    _, metrics = _traced(lambda: _grads(
        lambda *a: flash_attention(*a, block_q=128, block_kv=64), q, k, v))
    assert _kernels(metrics) == kernels
    want = block_schedule(256, 256, 128, 64, True, chunk=256)
    assert (want.live, want.masked) == (6, 4)
    for kernel in kernels:
        read = {name: row["value"]
                for name in ("flash_blocks_grid", "flash_blocks_live",
                             "flash_blocks_masked")
                for row in metrics[name]["series"]
                if row["labels"] == {"kernel": kernel,
                                     "geometry": "256x256x64"}}
        # the forward masks the diagonal's blocks alone, the backward
        # kernels every block they visit; the kv-major walks (dk/dv, the
        # fused backward) take a grid step a kv block
        assert read == {"flash_blocks_grid": 4 if kernel in ("dkv", "bwd")
                        else 2,
                        "flash_blocks_live": want.live,
                        "flash_blocks_masked": want.masked
                        if kernel == "fwd" else want.live}, (kernel, read)


@pytest.mark.parametrize("blocks", [
    dict(block_q=128, block_kv=128),   # 3x3: dead, diagonal and full blocks
    dict(block_q=128, block_kv=384),   # one kv block: every block masked
    dict(block_q=384, block_kv=128),   # one q block: full, then diagonal
    dict(block_q=128, block_kv=192),   # the diagonal crosses off the corner
])
def test_flash_full_diagonal_and_dead_blocks(blocks):
    _check_fwd_bwd(*rand_qkv(b=1, h=2, s=384, d=64), **blocks)


@pytest.mark.parametrize("resident_bytes", [4 * 2 ** 20, 1])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_resident_and_gridded_paths(monkeypatch, resident_bytes, causal):
    """_RESIDENT_BYTES = 1 leaves one block a chunk: the gridded walk,
    with the index maps clamped to live chunks."""
    monkeypatch.setattr(fa, "_RESIDENT_BYTES", resident_bytes)
    want_chunks = 1 if resident_bytes > 1 else 3
    assert 384 // fa._chunk_rows(384, 128, 64, 4) == want_chunks
    _check_fwd_bwd(*rand_qkv(b=1, h=2, s=384, d=64), causal=causal,
                   block_q=128, block_kv=128)


@pytest.mark.parametrize("resident_bytes", [4 * 2 ** 20, 1])
@pytest.mark.parametrize("sq,skv", [(128, 384), (256, 384), (384, 128)])
def test_flash_cross_length_offsets(monkeypatch, resident_bytes, sq, skv):
    """sq != skv, both signs of the offset; (384, 128) has 256 rows that
    see nothing and must come out as zeros with zero gradients."""
    monkeypatch.setattr(fa, "_RESIDENT_BYTES", resident_bytes)
    q, _, _ = rand_qkv(b=1, h=2, s=sq, d=64)
    _, k, v = rand_qkv(b=1, h=2, s=skv, d=64, seed=1)
    _check_fwd_bwd(q, k, v, block_q=128, block_kv=128)
    if sq > skv:
        out = flash_attention(q, k, v, block_q=128, block_kv=128)
        assert not np.asarray(out[:, :, :sq - skv]).any()


@pytest.mark.parametrize("h,hk,sq,skv,d,causal,resident_bytes", [
    (2, 2, 256, 256, 64, True, 4 * 2 ** 20),
    (2, 2, 256, 256, 64, False, 4 * 2 ** 20),
    (2, 2, 256, 256, 128, True, 4 * 2 ** 20),
    (4, 1, 256, 256, 64, True, 4 * 2 ** 20),     # GQA: four heads' dQ live
    (4, 2, 384, 384, 128, True, 1),              # GQA over gridded q chunks
    (2, 2, 384, 384, 64, True, 1),               # one block a chunk
    (2, 2, 384, 384, 64, False, 1),
    (2, 2, 128, 384, 64, True, 4 * 2 ** 20),     # off > 0
    (2, 2, 384, 128, 64, True, 4 * 2 ** 20),     # 256 rows see no key
    (2, 2, 384, 128, 64, True, 1),
])
def test_fused_backward_matches_the_pair(monkeypatch, h, hk, sq, skv, d,
                                         causal, resident_bytes):
    """One kernel or two, the same operands give the same dq, dk and dv to
    bf16 rounding; the budget ``_FUSED_BWD_BYTES`` is what chooses. Rows that
    see no key (sq > skv) are visited by no walk and come out zero from both."""
    monkeypatch.setattr(fa, "_RESIDENT_BYTES", resident_bytes)
    q, _, _ = rand_qkv(b=1, h=h, s=sq, d=d, dtype=jnp.bfloat16)
    _, k, v = rand_qkv(b=1, h=hk, s=skv, d=d, dtype=jnp.bfloat16, seed=1)

    def grads():
        return _grads(lambda *a: flash_attention(
            *a, causal=causal, block_q=128, block_kv=128).astype(jnp.float32),
            q, k, v)

    fused, metrics = _traced(grads)
    assert _kernels(metrics) == FUSED
    monkeypatch.setattr(fa, "_FUSED_BWD_BYTES", 0)
    pair, metrics = _traced(grads)
    assert _kernels(metrics) == PAIR
    for a, b, name in zip(fused, pair, "qkv"):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_allclose(
            a, b, rtol=2 ** -7, atol=2 ** -8 * np.abs(b).max(),
            err_msg=f"d{name} mismatch")
    if causal and sq > skv:
        assert not np.asarray(fused[0][:, :, :sq - skv], np.float32).any()
        assert np.asarray(fused[0][:, :, sq - skv:], np.float32).any()


@pytest.mark.parametrize("group,sq,d,bq,bk,fused", [
    (1, 2048, 64, 512, 512, True),        # both benchmark cells
    (4, 2048, 128, 512, 512, True),       # GQA 4 at width 128
    (1, 4096, 64, 512, 512, True),
    (2, 8192, 64, 512, 512, False),       # 8 MiB of accumulators
    (8, 32768, 128, 512, 512, False),     # 128 MiB: the whole of VMEM
    (1, 2048, 64, 512, 1024, True),
    (1, 2048, 64, 512, 2048, False),      # 4 MiB a score tile
])
def test_fused_backward_is_taken_where_dq_fits(group, sq, d, bq, bk, fused):
    """Which backward runs is read off the call's shapes: the float32 dQ of
    the q heads under one kv head and what the walk holds beside it, against
    ``_FUSED_BWD_BYTES``; ``test_kernels_lower_tpu`` compiles both sides of
    the line for the v5e."""
    cq = fa._chunk_rows(sq, bq, d, 2)
    assert fa._fused_bwd_fits(group, sq, cq, bq, bk, d, 2) == fused


@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("h,hk", [(2, 2), (4, 1)])
def test_flash_head_dims_and_gqa_backward(d, h, hk):
    _check_fwd_bwd(*rand_qkv(b=1, h=h, hk=hk, s=256, d=d), block_q=128,
                   block_kv=128)


@pytest.mark.parametrize("scale", [0.125, 0.1])
def test_flash_scale_multiplies_the_scores(scale):
    """A caller's scale, a power of two or not, matches the reference."""
    q, k, v = rand_qkv(b=1, h=2, s=256, d=64)
    out = flash_attention(q, k, v, scale=scale, block_q=128, block_kv=128)
    ref = mha_reference(q, k, v, scale=scale)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
    g1 = _grads(lambda *a: flash_attention(*a, scale=scale, block_q=128,
                                           block_kv=128), q, k, v)
    g2 = _grads(lambda *a: mha_reference(*a, scale=scale), q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, atol=5e-4, rtol=1e-3)


@pytest.mark.parametrize("sq,block_q,ok", [
    (256, 64, False),      # neither 128 lanes nor the whole of sq
    (704, None, False),    # 704 = 11 x 64: no divisor is a multiple of 128
    (256, 128, True),
    (192, None, True),     # one block, all of sq
    (2048, None, True),
])
def test_q_block_is_lane_aligned_or_all_of_sq(sq, block_q, ok):
    """lse and delta move as (1, 1, bq) blocks with bq on the lanes: Mosaic
    takes a multiple of 128 or the whole dimension, and ``_plan`` says so
    on every backend rather than leave it to the TPU's compiler."""
    shape = (1, 2, sq, 64)
    if ok:
        _, blocks = fa._plan(shape, shape, True, None, block_q, None)
        assert all(bq % 128 == 0 or bq == sq for bq, _ in blocks)
    else:
        with pytest.raises(ValueError, match="multiple of 128"):
            fa._plan(shape, shape, True, None, block_q, None)


@pytest.mark.parametrize("kernels", [("fwd", "bwd"),
                                     ("fwd", "bwd", "dq", "dkv")],
                         ids=["fused", "pair"])
def test_time_kernels_times_each_kernel_alone(monkeypatch, kernels):
    """``bwd`` is the whole backward; ``dq`` and ``dkv`` are there when
    the pair is what the shapes take."""
    from deepspeed_tpu.ops.attention_autotune import time_kernels
    if "dq" in kernels:
        monkeypatch.setattr(fa, "_FUSED_BWD_BYTES", 0)
    t = time_kernels(1, 2, 1, 128, 8, dtype=jnp.float32, steps=1)
    assert t["backend"] == jax.default_backend()
    assert {k for k, v in t.items() if isinstance(v, float)} == set(kernels)
    assert all(t[k] > 0 for k in kernels)
    assert len(t["blocks"]) == 4
