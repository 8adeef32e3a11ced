"""Chip-free Mosaic lowering: every Pallas kernel on the two main paths,
AOT-compiled for a v5e with the local libtpu as a host compiler.

``jax.experimental.topologies`` describes a ``v5e:2x2`` slice with no
device attached; a jit lowered against one of its devices runs the real
TPU pipeline, Mosaic included (~3 s for the topology, ~1 s per kernel).
What this pins, so that the table in the code and the compiler cannot
drift apart:

* for each (head_dim, kv_heads, KV dtype) row and each paged-attention
  variant, either the kernel compiles or the engine's static gate
  (``kernel_variant``) does not select that variant for that row;
* int8 KV compiles in the variant that serves it;
* flash fwd+bwd compile at the head dims the trainer uses;
* the tiled paged-attention kernel compiles at the launch shapes of the
  benchmark's generation cell, under a name its trace pattern matches;
* the whole serving programs (ragged step, fused decode window with
  greedy and sampled picks, prefill) compile at OPT-1.3B's geometry;
* the generation cell's two programs, at its shapes, take the pool as
  an argument and make no copy of it or of a layer of it;
* the four generation cells' DECODE launches lower in the kernels'
  one-token form under the three names their readers find, and the
  decode windows round them relay no pool.

This is the pre-check that costs no chip time: run it before
``chip_smoke.py``. It says nothing about speed or numerics; phase K of
chip_smoke.py compares the same kernels with their references on the chip.
"""

import dataclasses
import itertools
import re

import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2.kernels.ragged_attention import (
    VARIANTS, kernel_variant, ragged_attention)


HEAD_DIMS = (64, 96, 128, 256)
KV_HEADS = (1, 4, 8, 12, 32)
# the tier-1 dozen: the two geometries chip_smoke.py serves, the published
# shapes finding 1 named (GPT-2/OPT-125M 12x64, Falcon-7B MQA, Phi 96-wide,
# Gemma 256-wide), and the edges of the tiled rule (a page row of 64 or of
# 96-wide heads is left to the pipelined variant)
TIER1_ROWS = (
    (64, 32, False), (64, 32, True), (64, 12, False), (64, 1, False),
    (96, 32, False), (128, 8, False), (128, 8, True), (128, 4, True),
    (128, 12, False), (128, 1, True), (256, 8, False), (256, 32, True),
)
ALL_ROWS = tuple(itertools.product(HEAD_DIMS, KV_HEADS, (False, True)))


@pytest.fixture(scope="module")
def tpu_sharding():
    """Describes the topology (and loads libtpu) only once a test of this
    file runs, never while the file is imported: every xdist worker
    imports every test file, and one process at a time may hold libtpu."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        desc = topologies.get_topology_desc("v5e:2x2", platform="tpu")
    except Exception as e:  # noqa: BLE001 — whatever libtpu raises
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(desc.devices[0])


@pytest.fixture(autouse=True)
def _trace_for_tpu(monkeypatch):
    """Every kernel module asks ``jax.default_backend()`` whether to run
    in interpret mode; the programs here are compiled FOR the TPU from a
    CPU host, so answer for the target."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _compile_error(fn, *args):
    """None if ``fn`` compiles for the arguments' device, else the
    compiler's message."""
    try:
        jax.jit(fn).lower(*args).compile()
    except Exception as e:  # noqa: BLE001 — Mosaic raises several types
        return str(e)
    return None


# what makes a second copy of a value, or carries it somewhere else
_MOVES = ("copy", "copy-start", "copy-done", "slice-start", "slice-done",
          "fusion", "transpose")


def _leaves_stay_in_hbm(text, cache, *names):
    """The compiled program ``text`` keeps the cache leaves ``names``
    where they lie: no value of a leaf's shape is laid in the chip's fast
    memory (``S(1)`` in its layout: at a custom call's operand or result,
    or anywhere else), and no instruction whose result (or, of an
    asynchronous start, whose operand) has the leaf's shape copies,
    slices or rewrites it: PR 57's bug, the compiler carrying a whole
    leaf into fast memory and back round a kernel that moves the rows'
    slots of one layer."""
    for name in names:
        leaf = cache[name]
        shape = "%s[%s]" % ({"float32": "f32", "bfloat16": "bf16"}[
            str(leaf.dtype)], ",".join(map(str, leaf.shape)))
        assert shape in text, f"the program no longer holds {name} {shape}"
        fast = re.findall(re.escape(shape) + r"\{[^}]*S\(1\)", text)
        assert not fast, f"{name} {shape} lies in fast memory {len(fast)} x"
        moved = [m.group(2) for m in re.finditer(
            r"^\s*(?:ROOT )?%[\w.\-]+ = ([^\n]*?) ([\w\-]+)\(", text, re.M)
            if shape in m.group(1) and m.group(2) in _MOVES]
        assert not moved, f"{name} {shape} is moved by {moved}"


def _paged_args(sds, hd, kvh, quant, T=8, R=4, MB=8, nb=64, layers=2):
    """``ragged_attention``'s operands: queries, the two pool leaves as
    stored (``[L, nb, 16, kvh * hd]``), the layer, the descriptors, and
    an int8 pool's two scale rows of the layer."""
    nh = kvh if kvh >= 12 else 32
    pool = sds((layers, nb, 16, kvh * hd),
               jnp.int8 if quant else jnp.bfloat16)
    args = [sds((T, nh, hd), jnp.bfloat16), pool, pool, sds((), jnp.int32),
            sds((T,), jnp.int32), sds((T,), jnp.int32),
            sds((R, MB), jnp.int32)]
    if quant:
        args += [sds((nb, kvh), jnp.float32)] * 2
    return args


def _attend(quant, variant=None):
    return lambda *a: ragged_attention(
        *a[:7], k_scale=a[7] if quant else None,
        v_scale=a[8] if quant else None, variant=variant)


def _check_rows(rows, sharding, variants):
    """For each (row, variant): it compiles, or the gate does not select
    it. A variant the gate does not select is only compiled when the
    caller asks for it (the slow full table does, to show where the gate
    is conservative)."""
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    failures = []
    for hd, kvh, quant in rows:
        chosen = kernel_variant(hd, kvh, quant)
        for variant in variants or (chosen,):
            err = _compile_error(_attend(quant, variant),
                                 *_paged_args(sds, hd, kvh, quant))
            if err is not None and chosen == variant:
                failures.append(
                    f"hd={hd} kvh={kvh} {'int8' if quant else 'bf16'}: "
                    f"the gate selects {variant!r} and Mosaic refuses it: "
                    f"{err[:300]}")
    assert not failures, "\n".join(failures)


def test_gate_matches_compiler_tier1_rows(tpu_sharding):
    _check_rows(TIER1_ROWS, tpu_sharding, variants=None)


def test_the_one_token_form_compiles_wherever_the_gate_is_tiled(
        tpu_sharding):
    """The decode entry (``paged_attention``: every row one token) at
    the tier-1 rows the gate gives the tiled variant, bf16 and int8
    pools: Mosaic takes the one-token form at every one of them (query
    rows a lane block from 1 to 32, padded to sublane tiles; lane blocks
    128 and 256 wide)."""
    from deepspeed_tpu.inference.v2.kernels.paged_attention import \
        paged_attention

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=tpu_sharding)

    failures = []
    for hd, kvh, quant in TIER1_ROWS:
        if kernel_variant(hd, kvh, quant) != "tiled":
            continue
        q, kc, vc, layer, _, lens, bt, *scales = _paged_args(
            sds, hd, kvh, quant, T=4, R=4)
        err = _compile_error(
            lambda q, kc, vc, layer, bt, lens, *sc: paged_attention(
                q, kc, vc, layer, bt, lens, k_scale=sc[0] if sc else None,
                v_scale=sc[1] if sc else None),
            q, kc, vc, layer, bt, lens, *scales)
        if err is not None:
            failures.append(f"hd={hd} kvh={kvh} int8={quant}: {err[:300]}")
    assert not failures, "\n".join(failures)


@pytest.mark.slow   # 80 compiles; the tier-1 dozen above is its sibling
def test_gate_matches_compiler_full_table(tpu_sharding):
    _check_rows(ALL_ROWS, tpu_sharding, variants=VARIANTS)


def test_gate_is_tiled_wherever_a_page_row_is_lane_dense():
    """The gate is a table of the pool's geometry: whole 128-lane blocks
    of whole heads take the tiled walk (traffic scales with context, not
    table width; a page moves its own bytes), the rest stay pipelined."""
    for hd, kvh in ((64, 32), (64, 12), (64, 2), (32, 4), (128, 1),
                    (128, 8), (128, 12), (256, 16)):
        assert kernel_variant(hd, kvh, False) == "tiled"
        assert kernel_variant(hd, kvh, True) == "tiled"
    for hd, kvh in ((64, 1), (64, 3), (80, 32), (96, 32), (16, 2)):
        assert kernel_variant(hd, kvh, False) == "pipelined"
        assert kernel_variant(hd, kvh, True) == "pipelined"


# opt-1.3b.rollout-256's two launches: the 4,096-token prefill of 16 rows
# and a decode step of 16 rows, over its pool of 529 blocks of 16
CELL_LAUNCHES = {"prefill": (4096, 16, 16), "decode": (16, 16, 32)}
# benchmark/layer_metrics' pattern for ragged_share.gen / ragged_roofline.gen
TRACE_PATTERN = re.compile(r"ragged_attention_[a-z]+[_.0-9]*$")


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("launch", sorted(CELL_LAUNCHES))
def test_tiled_kernel_at_the_cells_launch_shapes(tpu_sharding, launch,
                                                 quant):
    """32 heads of 64 at the cell's shapes, the whole pool of 24 layers
    and the layer a scalar: the kernel compiles (an int8 page of 16
    positions is half a (32, 128) tile and still copies), the compiled
    program calls it under a name the benchmark's readers find (a name
    with a second word would read both metrics as null), and nothing in
    it is as large as a layer of the pool."""
    T, R, MB = CELL_LAUNCHES[launch]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=tpu_sharding)

    assert kernel_variant(64, 32, quant) == "tiled"
    compiled = jax.jit(_attend(quant)).lower(*_paged_args(
        sds, 64, 32, quant, T=T, R=R, MB=MB, nb=529, layers=24)).compile()
    kernels = re.findall(r"%([\w.\-]+) = [^\n]*tpu_custom_call",
                         compiled.as_text())
    assert len(kernels) == 1 and TRACE_PATTERN.search(kernels[0]), kernels
    assert kernels[0].startswith("ragged_attention_tiled")
    # a layer of the pool is 17 MB int8 (35 bf16): queries and output of
    # the 4,096-token launch are 2 x 0.5 MB, relaid once each way
    assert compiled.memory_analysis().temp_size_in_bytes < 4e6


@pytest.mark.parametrize("hd,kv_heads,causal,seq,fused", [
    (64, 4, True, 2048, True), (128, 2, True, 2048, True),
    (128, 1, True, 2048, True),     # GQA 4: four heads' dQ in VMEM
    (64, 4, True, 4096, True),
    # non-causal: 512 x 1024 blocks in the fused backward (2 MiB a score
    # tile); with four heads' dQ beside them the pair, at 512 x 2048
    (128, 2, False, 2048, True), (128, 1, False, 4096, False),
    # over 4,096 rows K/V no longer fit one chunk: the gridded walk, whose
    # index maps clamp traced chunk indices, with a GQA group in dk/dv;
    # two heads' dQ is 8 MiB there, so dq and dk/dv run apart
    (64, 2, True, 8192, False), (128, 2, True, 8192, False),
    (64, 4, True, 8192, True),      # one head's dQ over two q chunks
])
def test_flash_fwd_bwd_compile(tpu_sharding, hd, kv_heads, causal, seq, fused):
    """Both backwards at the shapes that take them (``_fused_bwd_fits``):
    the kernel that keeps dQ in VMEM has to fit the v5e's scoped limit
    wherever the rule admits it."""
    from deepspeed_tpu.ops import flash_attention as fa
    from deepspeed_tpu.ops.flash_attention import flash_attention

    assert seq // fa._chunk_rows(seq, 512, hd, 2) == max(1, seq // 4096)

    def sds(shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                    sharding=tpu_sharding)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal)
                       .astype(jnp.float32) ** 2)

    q, kv = sds((1, 4, seq, hd)), sds((1, kv_heads, seq, hd))
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile().as_text()
    kernels = re.findall(
        r"%[\w.\-]*?flash_attention_(\w+?)[_.0-9]* = [^\n]*tpu_custom_call",
        text)
    assert sorted(kernels) == (["bwd", "fwd"] if fused
                               else ["bwd_dkv", "bwd_dq", "fwd"])


_CELL_GEOMETRIES = {                   # [batch a chip, heads, seq, head_dim]
    "opt-125m.train-dense": (32, 12, 2048, 64),
    "opt-1.3b.zero3-dp4": (4, 32, 2048, 64),
}


@pytest.mark.parametrize("cell", sorted(_CELL_GEOMETRIES))
def test_flash_at_the_cells_geometry(tpu_sharding, cell):
    """The benchmark's two cells, forward and backward, as the v5e's
    compiler sees them.

    A device trace shows a Mosaic call under its instruction's name:
    ``pallas_call(name=...)`` reaches it (unnamed, they were
    ``checkpoint.20``, ``closed_call.8``, whatever jaxpr was round them),
    and the benchmark's per-kernel shares find them by it. And the row
    statistics stay lane-dense: a ``f32[bh, sq, 1]`` operand is tiled
    T(8,128), i.e. padded 128x in HBM (402 MB each for lse and delta on
    the dense cell) and moved as 128 KB blocks."""
    from deepspeed_tpu.ops.flash_attention import flash_attention

    def loss(q, k, v):
        with jax.named_scope("attention"):      # as the model calls it
            o = flash_attention(q, k, v, causal=True)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    b, h, s, d = _CELL_GEOMETRIES[cell]
    x = jax.ShapeDtypeStruct((b, h, s, d), jnp.bfloat16,
                             sharding=tpu_sharding)
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        x, x, x).compile().as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    kernels = [re.match(r"\s*(?:ROOT )?%([\w.\-]+) = ", c).group(1)
               for c in calls]
    # one backward kernel: dQ is taken on the dK/dV walk at these shapes
    assert len(kernels) == 2, kernels
    for name in ("flash_attention_fwd", "flash_attention_bwd"):
        assert [k for k in kernels if re.search(
            rf"(?<!sparse_){name}[_.0-9]*$", k)], (name, kernels)
    for call in calls:
        types = call.split("metadata=")[0]     # result and operand types
        assert not re.search(r"f32\[[\d,]*,1\]", types), types
    assert f"f32[{b * h},1,{s}]" in "".join(calls)
    assert not re.search(rf"f32\[{b * h},{s},1\]", text)


# ---------------------------------------------------------------------------
# the train step on a chip its state fills: the program nothing_saveable gives
# ---------------------------------------------------------------------------
V5E_BYTES_LIMIT = 15.75 * 2 ** 30      # what a v5e chip's allocator reports


@pytest.mark.parametrize("micro,want,fwd_calls", [
    # 16 layers at OPT-1.3B's widths: 12.7 GB of state on one chip leave
    # 4.2 GB, a quarter of which may go to saved activations (SAVE_SHARE)
    (16, "nothing_saveable", 2),        # the flash set would be 2.2 GB
    (4, "save_attn", 1),                # 0.55 GB: kept
])
def test_train_step_on_a_full_chip(tpu_sharding, monkeypatch, micro, want,
                                   fwd_calls):
    """``activation_checkpointing.policy: auto`` on a memory-full job
    lowers the very program that ``nothing_saveable`` lowers (what every
    job got before the policy was chosen from memory): two forward kernels
    a layer. With a quarter of the batch the same model keeps the kernel's
    output and row statistics, and the recomputed layer holds no forward."""
    import numpy as np
    from deepspeed_tpu.models import TransformerLM
    from deepspeed_tpu.models.transformer import opt_1_3b
    from deepspeed_tpu.parallel.topology import MeshTopology, TopologyConfig
    from deepspeed_tpu.runtime.activation_checkpointing import checkpointing
    from deepspeed_tpu.runtime.config import DeepSpeedConfig
    from deepspeed_tpu.runtime.engine import DeepSpeedTpuEngine

    monkeypatch.setattr(DeepSpeedTpuEngine, "_device_bytes_limit",
                        lambda self: int(V5E_BYTES_LIMIT))
    cfg = dataclasses.replace(opt_1_3b(), num_layers=16)
    (device,) = tpu_sharding.device_set
    batch = {"input_ids": np.zeros((1, micro, cfg.max_seq_len), np.int64)}

    def lowered(policy):
        ds = {"train_micro_batch_size_per_gpu": micro,
              "optimizer": {"type": "adamw", "params": {"lr": 3e-4}},
              "bf16": {"enabled": True}, "zero_optimization": {"stage": 0},
              "steps_per_print": 10 ** 9}
        if policy:
            ds["activation_checkpointing"] = {"policy": policy}
        engine = DeepSpeedTpuEngine(
            TransformerLM(cfg), DeepSpeedConfig(ds, world_size=1),
            topology=MeshTopology(TopologyConfig(), devices=[device]),
            abstract_init=True)
        text = engine._lower_train_step(batch).as_text()
        return engine, text

    try:
        engine, text = lowered(None)
        assert 12.6e9 < engine._placed_state_bytes() < 12.8e9
        assert engine.remat_policy[0] == want
        assert text.count('kernel_name = "flash_attention_fwd"') == fwd_calls
        if want == "nothing_saveable":
            assert text == lowered("nothing_saveable")[1]
    finally:
        checkpointing.reset()


# ---------------------------------------------------------------------------
# whole serving programs at OPT-1.3B's geometry (depth cut to 2)
# ---------------------------------------------------------------------------
def _serving_case(sharding, kv_quant, layers=2, blocks=129):
    from deepspeed_tpu.inference.v2.paged_model import init_paged_kv_cache
    from deepspeed_tpu.models import TransformerLM
    from deepspeed_tpu.models.transformer import opt_1_3b

    cfg = dataclasses.replace(opt_1_3b(), num_layers=layers)

    def on_tpu(x, dtype=None):
        return jax.ShapeDtypeStruct(x.shape, dtype or x.dtype,
                                    sharding=sharding)

    params = jax.tree.map(
        lambda x: on_tpu(x, jnp.bfloat16),
        jax.eval_shape(TransformerLM(cfg).init_params,
                       jax.random.PRNGKey(0)))
    cache = jax.tree.map(on_tpu, jax.eval_shape(
        lambda: init_paged_kv_cache(cfg, blocks, 16, jnp.bfloat16,
                                    kv_quant=kv_quant)))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)

    return cfg, params, cache, i32


@pytest.mark.parametrize("kv_quant", [False, True])
def test_opt_ragged_step_compiles(tpu_sharding, kv_quant):
    from deepspeed_tpu.inference.v2.paged_model import paged_ragged_step
    cfg, params, cache, i32 = _serving_case(tpu_sharding, kv_quant)
    T, R, MB = 64, 8, 16
    err = _compile_error(
        lambda p, ids, rows, pos, ln, wb, wo, bt, li, c: paged_ragged_step(
            cfg, p, ids, rows, pos, ln, wb, wo, bt, li, c, 16,
            use_kernel=True),
        params, i32(T), i32(T), i32(T), i32(T), i32(T), i32(T), i32(R, MB),
        i32(R), cache)
    assert err is None, err


@pytest.mark.slow   # siblings: the ragged-step rows above, same kernel
@pytest.mark.parametrize("kv_quant,sampled", [(False, False), (True, True)])
def test_opt_decode_window_compiles(tpu_sharding, kv_quant, sampled):
    """The fused K-step window with the greedy pick and with the
    per-row-keyed sampler (sampling.py) inside the device loop."""
    from deepspeed_tpu.inference.v2.paged_model import paged_decode_window
    cfg, params, cache, i32 = _serving_case(tpu_sharding, kv_quant)
    N, MB = 8, 16

    def f32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32,
                                    sharding=tpu_sharding)

    def window(p, t, pos, bt, c, sl, eos, seeds, g0, temp, topp, topk):
        kw = (dict(rng=jax.random.PRNGKey(0), row_seeds=seeds, gen_idx0=g0,
                   temp=temp, topp=topp, topk=topk) if sampled else {})
        return paged_decode_window(cfg, p, t, pos, bt, c, sl, eos, 16, 8,
                                   use_kernel=True, **kw)

    err = _compile_error(window, params, i32(N), i32(N), i32(N, MB), cache,
                         i32(N), i32(N), i32(N), i32(N), f32(N), f32(N),
                         i32(N))
    assert err is None, err


def _cell_program(program, cfg, params, cache, i32):
    """(jitted program, its arguments) of opt-1.3b.rollout-256's two
    launches as the engine builds them, the pool donated."""
    from deepspeed_tpu.inference.v2.paged_model import (paged_decode_window,
                                                        paged_ragged_step)
    T, R, MB = CELL_LAUNCHES[program]
    if program == "prefill":
        return jax.jit(
            lambda p, ids, rows, pos, ln, wb, wo, bt, li, c:
            paged_ragged_step(cfg, p, ids, rows, pos, ln, wb, wo, bt, li, c,
                              16, use_kernel=True), donate_argnums=(9,)), (
            params, i32(T), i32(T), i32(T), i32(T), i32(T), i32(T),
            i32(R, MB), i32(R), cache)
    return jax.jit(
        lambda p, t, pos, bt, c, sl, eos: paged_decode_window(
            cfg, p, t, pos, bt, c, sl, eos, 16, 8, use_kernel=True),
        donate_argnums=(4,)), (
        params, i32(R), i32(R), i32(R, MB), cache, i32(R), i32(R))


@pytest.mark.parametrize("layers", [
    2, pytest.param(24, marks=pytest.mark.slow)])   # 24: the cell's depth
@pytest.mark.parametrize("program", sorted(CELL_LAUNCHES))
def test_the_cells_programs_read_the_pool_where_it_lies(tpu_sharding,
                                                        program, layers):
    """opt-1.3b.rollout-256's ragged step (4,096 tokens) and decode
    window (16 rows, 8 steps) over its pool of 529 blocks of 16,
    compiled under the chip's flags: the arguments are the weights and
    the pool as stored (no padded entry layout), the donated pool is
    aliased to the result, the temporaries are far under a pool, and no
    instruction cuts, reshapes or copies a layer or a pool (PR 34's
    tree did all three, 3.0 s of a 4.8 s call). The sibling of
    test_latent_ragged_step_compiles_with_its_experts_in_place."""
    from deepspeed_tpu.accelerator.tpu_accelerator import \
        COLLECTIVE_OVERLAP_COMPILER_OPTIONS
    cfg, params, cache, i32 = _serving_case(tpu_sharding, False, layers, 529)
    fn, args = _cell_program(program, cfg, params, cache, i32)
    compiled = fn.lower(*args).compile(
        compiler_options=dict(COLLECTIVE_OVERLAP_COMPILER_OPTIONS))

    def nbytes(tree):
        return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))

    pool = nbytes(cache)
    assert pool == 2 * layers * 529 * 16 * 2048 * 2    # 1.66 GB at 24
    mem = compiled.memory_analysis()
    assert abs(mem.argument_size_in_bytes / (nbytes(params) + pool) - 1) \
        < 0.02, mem
    assert mem.alias_size_in_bytes >= pool, mem
    assert mem.temp_size_in_bytes < 0.5e9, mem
    text = compiled.as_text()
    kernels = re.findall(r"%([\w.\-]+) = [^\n]*tpu_custom_call", text)
    assert kernels and all(k.startswith("ragged_attention_tiled")
                           for k in kernels), kernels
    # every instruction whose result has the pool's blocks: [529, 16, ..]
    # is a layer, [L, 529, 16, ..] a pool
    made = re.findall(r"= \w+\[((?:\d+,)?529,16,[\d,]+)\]\S* ([\w\-]+)\(",
                      text)
    assert made, "the pattern no longer finds the pool in the program"
    layer_sized = [m for m in made if m[0].startswith(("529,", "1,529,"))]
    assert not layer_sized, layer_sized
    assert {op for _, op in made} <= {
        "parameter", "get-tuple-element", "bitcast", "scatter", "fusion",
        "while", "tuple"}, sorted(set(made))


# ---------------------------------------------------------------------------
# the latent (MLA) pool: joyai-llm-flash.rollout-64x256's geometry
# ---------------------------------------------------------------------------
# its two launches: the 8,192-token prefill of 64 rows and a decode step
# of 64 rows, over 1,601 blocks of 16 in 5 layers; (tokens, rows, table)
LATENT_LAUNCHES = {"prefill": (8192, 64, 32), "decode": (64, 64, 32)}
# benchmark/layer_metrics' patterns for latent_*.gen and experts_*.gen
LATENT_PATTERN = re.compile(r"ragged_attention_latent[_.0-9]*$")
GMM_PATTERN = re.compile(r"^gmm[_.0-9]*$")


def _latent_args(sharding, T, R, MB, row):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    return (sds((32, T, row), jnp.bfloat16),
            sds((5, 1601, 16, row), jnp.bfloat16), sds((), jnp.int32),
            sds((T,), jnp.int32), sds((T,), jnp.int32),
            sds((R, MB), jnp.int32))


@pytest.mark.parametrize("launch", sorted(LATENT_LAUNCHES))
def test_latent_kernel_at_the_cells_launch_shapes(tpu_sharding, launch):
    """32 heads against one 640-lane row (512 + 64, padded) at the
    cell's shapes: the kernel compiles, under the name the benchmark's
    readers find."""
    from deepspeed_tpu.inference.v2.kernels.ragged_attention import \
        latent_attention
    text = jax.jit(lambda *a: latent_attention(
        *a, dc=512, scale=192 ** -0.5)).lower(*_latent_args(
            tpu_sharding, *LATENT_LAUNCHES[launch], 640)).compile().as_text()
    kernels = re.findall(r"%([\w.\-]+) = [^\n]*tpu_custom_call", text)
    assert len(kernels) == 1 and LATENT_PATTERN.search(kernels[0]), kernels


def test_a_latent_row_that_is_not_whole_lane_blocks_is_refused(tpu_sharding):
    """Why ``paged_model.latent_pool_row`` pads 576 lanes to 640: Mosaic
    copies no page whose row is not whole 128-lane blocks."""
    from deepspeed_tpu.inference.v2.kernels.ragged_attention import \
        latent_attention
    err = _compile_error(
        lambda *a: latent_attention(*a, dc=512, scale=192 ** -0.5),
        *_latent_args(tpu_sharding, *LATENT_LAUNCHES["decode"], 576))
    assert err is not None and "aligned to tiling" in err, err


# dots3-note-prev.rollout-8x32768-256: (heads, lanes, values, window,
# tokens, rows, table places, pool blocks, layers) of its launches. The
# full kind's launches (dense under tables of no more than index_topk
# positions, the picks a mask on the kernel's under wider ones): 128
# heads against a 640-lane row, a chunk step of 8 x 1,024; the window
# kind's: 64 heads against a 1,152-lane row over a ring of 98 places, a
# chunk step and 8 one-token rows
DOTS3_LAUNCHES = {
    "full-chunk": (128, 640, 512, 0, 8192, 8, 128, 16521, 2),
    # a tile of 256 tokens of a chunk step with their picks as flags,
    # under a table of 32,768 positions
    "full-chunk-picked": (128, 640, 512, 0, 256, 8, 2048, 16521, 2),
    "full-decode-under-topk": (128, 640, 512, 0, 8, 8, 128, 16521, 2),
    "ring-chunk": (64, 1152, 1024, 513, 8192, 8, 98, 785, 3),
    "ring-decode": (64, 1152, 1024, 513, 8, 8, 98, 785, 3)}


@pytest.mark.parametrize("launch", sorted(DOTS3_LAUNCHES))
def test_the_two_latent_kinds_at_the_long_context_cells_shapes(
        tpu_sharding, launch):
    """Both latent kinds of ``dots3-note-prev`` at the cell's shapes:
    the kernel compiles, the window kind's under a name of its own (a
    trace tells the two apart, and ``latent_share.gen``'s pattern does
    not take the ring's launches for the dense ones)."""
    from deepspeed_tpu.inference.v2.kernels.ragged_attention import \
        latent_attention
    nh, row, dc, window, T, R, MB, nb, layers = DOTS3_LAUNCHES[launch]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=tpu_sharding)
    args = (sds((nh, T, row), jnp.bfloat16),
            sds((layers, nb, 16, row), jnp.bfloat16), sds((), jnp.int32),
            sds((T,), jnp.int32), sds((T,), jnp.int32),
            sds((R, MB), jnp.int32))
    picks = launch.endswith("picked")
    if picks:
        args += (sds((T, MB * 16), jnp.bool_),)
    text = jax.jit(lambda *a: latent_attention(
        *a[:6], dc=dc, scale=256 ** -0.5, one_token=T == R, window=window,
        picked=a[6] if picks else None)).lower(*args).compile().as_text()
    kernels = re.findall(r"%([\w.\-]+) = [^\n]*tpu_custom_call", text)
    assert len(kernels) == 1, kernels
    if picks:
        assert kernels[0].startswith("ragged_attention_latent_picked")
        assert not LATENT_PATTERN.search(kernels[0])
    elif window:
        assert kernels[0].startswith("ragged_attention_latent_window")
        assert not LATENT_PATTERN.search(kernels[0])
    else:
        assert LATENT_PATTERN.search(kernels[0]), kernels


@pytest.mark.parametrize("tokens,rows,positions,room", [
    (4096, 4, 34816, 1.6e9), (8192, 8, 34816, 2.1e9)])
def test_the_expanded_picked_launch_at_the_long_context_cells_shapes(
        tpu_sharding, tokens, rows, positions, room):
    """A selecting prompt launch of ``dots3-note-prev``'s full layers in
    the EXPANDED form (PR 69) at the cell's chunk step (4 rows x 1,024
    tokens under tables at their full width, 33,024 positions in 17
    pieces of 2,048) and at the largest the engine is sized for: the
    rows' keys and values a group of heads at a time
    (``latent_rows_expand``) and the per-head kernel over them compile
    for the chip, each under its own name, the temporaries inside 1.6 GB
    (2.1 at the largest: four heads at a time there)."""
    from deepspeed_tpu.inference.v2 import paged_model as pm
    from deepspeed_tpu.inference.v2.kernels.ragged_attention import \
        picked_heads_tile
    assert pm.index_prompt_form(tokens, rows, positions, 2048) == "expanded"
    tq = picked_heads_tile(tokens, rows)
    N = tokens + rows * tq

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=tpu_sharding)
    compiled = jax.jit(lambda *a: pm._expanded_picked_attention(
        *a, dc=512, dn=128, scale=192 ** -0.5, tq=tq, use_kernel=True)
    ).lower(sds((128, N, 192)),
            sds((rows, positions // pm._EXPAND_PIECE, pm._EXPAND_PIECE, 640)),
            sds((512, 128, 256)), sds((N // 256, 33024, 256), jnp.int8),
            sds((N // tq,), jnp.int32), sds((N,), jnp.int32),
            sds((rows,), jnp.int32)).compile()
    kernels = re.findall(r"%([\w.\-]+) = [^\n]*tpu_custom_call",
                         compiled.as_text())
    assert sorted(k.split(".")[0] for k in kernels) == [
        "latent_rows_expand", "ragged_attention_latent_picked_heads"], kernels
    assert not any(LATENT_PATTERN.search(k) for k in kernels)
    assert compiled.memory_analysis().temp_size_in_bytes < room


def _dots3_cell(tpu_sharding):
    """The cell's configuration, tree and cache as shapes on the chip."""
    import json
    from pathlib import Path
    from deepspeed_tpu.inference.v2.paged_model import init_paged_kv_cache
    from deepspeed_tpu.models import TransformerLM
    from deepspeed_tpu.models.transformer import TransformerConfig

    root = Path(__file__).resolve().parents[3] / "benchmark"
    cfg = TransformerConfig(**json.loads(
        (root / "configs/dots3-note-prev.json").read_text())["fields"])
    sm = json.loads((root / "workloads/dots3-note-prev.rollout-8x32768-256"
                     ".json").read_text())["engine"]["state_manager"]

    def on_tpu(x, dtype=None):
        return jax.ShapeDtypeStruct(x.shape, dtype or x.dtype,
                                    sharding=tpu_sharding)
    params = jax.tree.map(
        lambda x: on_tpu(x, jnp.bfloat16),
        jax.eval_shape(TransformerLM(cfg).init_params,
                       jax.random.PRNGKey(0)))
    cache = jax.tree.map(on_tpu, jax.eval_shape(
        lambda: init_paged_kv_cache(
            cfg, sm["num_blocks"], 16, jnp.bfloat16,
            window_blocks=8 * 98 + 1)))
    return cfg, params, cache


@pytest.mark.slow
@pytest.mark.parametrize("table", [128, 2048])
def test_the_long_context_cells_chunk_step_fits_the_chip(tpu_sharding,
                                                         table):
    """The whole 8,192-token chunk step of ``dots3-note-prev``'s cell at
    published widths, under a table of 2,048 positions (the dense
    launch) and of 32,768 (the indexer, the selection and the picks laid
    on the mask of the per-head kernel over the rows' expanded keys and
    values, PR 69: 12.79 GB by the compiler): it compiles for the chip
    inside the 15.0 GB of the cell's rule, each under its kernel's own
    name."""
    from deepspeed_tpu.inference.v2.paged_model import paged_ragged_step
    cfg, params, cache = _dots3_cell(tpu_sharding)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=tpu_sharding)
    T, R = 8192, 8
    compiled = jax.jit(
        lambda p, ids, rows, pos, ln, wb, wo, bt, li, c, wt:
        paged_ragged_step(cfg, p, ids, rows, pos, ln, wb, wo, bt, li, c, 16,
                          use_kernel=True, window_tables=wt),
        donate_argnums=(9,)).lower(
        params, i32(T), i32(T), i32(T), i32(T), i32(T), i32(T),
        i32(R, table), i32(R), cache, i32(R, 98)).compile()
    m = compiled.memory_analysis()
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 15.0e9
    kernels = re.findall(r"%([\w.\-]+) = [^\n]*tpu_custom_call",
                         compiled.as_text())
    assert any(k.startswith("ragged_attention_latent_window")
               for k in kernels), kernels
    assert any(LATENT_PATTERN.search(k) for k in kernels) == (table == 128)
    assert any(k.startswith("ragged_attention_latent_picked")
               for k in kernels) == (table == 2048)


def _latent_cut(tpu_sharding, blocks):
    """The latent block at published widths, depth cut to the leading
    dense layer and ONE expert layer of 256 experts: the configuration,
    and its parameters and pool of ``blocks`` as shapes on the chip."""
    import json
    from pathlib import Path
    from deepspeed_tpu.inference.v2.paged_model import init_paged_kv_cache
    from deepspeed_tpu.models import TransformerLM
    from deepspeed_tpu.models.transformer import TransformerConfig

    fields = json.loads((Path(__file__).resolve().parents[3]
                         / "benchmark/configs/joyai-llm-flash.json"
                         ).read_text())["fields"]
    cfg = TransformerConfig(**{**fields, "num_layers": 2})

    def on_tpu(x, dtype=None):
        return jax.ShapeDtypeStruct(x.shape, dtype or x.dtype,
                                    sharding=tpu_sharding)

    params = jax.tree.map(
        lambda x: on_tpu(x, jnp.bfloat16),
        jax.eval_shape(TransformerLM(cfg).init_params,
                       jax.random.PRNGKey(0)))
    cache = jax.tree.map(on_tpu, jax.eval_shape(
        lambda: init_paged_kv_cache(cfg, blocks, 16, jnp.bfloat16)))
    return cfg, params, cache


def test_latent_ragged_step_compiles_with_its_experts_in_place(tpu_sharding):
    """The whole ragged step at the published widths, depth cut to the
    leading dense layer and ONE expert layer of 256 experts: it compiles
    for the chip, the latent kernel runs in both stacks and the grouped
    matmul three times under names a trace finds, and no instruction
    holds a copy of a layer's experts (the kernel reads the stack where
    it lies; a sliced layer would be a 1.2 GB copy a launch)."""
    from deepspeed_tpu.inference.v2.paged_model import paged_ragged_step

    cfg, params, cache = _latent_cut(tpu_sharding, 129)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=tpu_sharding)

    T, R, MB = 512, 8, 16
    compiled = jax.jit(
        lambda p, ids, rows, pos, ln, wb, wo, bt, li, c: paged_ragged_step(
            cfg, p, ids, rows, pos, ln, wb, wo, bt, li, c, 16,
            use_kernel=True), donate_argnums=(9,)).lower(
        params, i32(T), i32(T), i32(T), i32(T), i32(T), i32(T), i32(R, MB),
        i32(R), cache).compile()
    text = compiled.as_text()
    kernels = re.findall(r"%([\w.\-]+) = [^\n]*tpu_custom_call", text)
    assert sum(bool(LATENT_PATTERN.search(k)) for k in kernels) == 2, kernels
    assert sum(bool(GMM_PATTERN.search(k)) for k in kernels) == 3, kernels
    # every expert is held: the rows come back by XLA's gather
    assert not any(k.startswith("moe_rows") for k in kernels), kernels
    made = re.findall(r"%[\w.\-]+ = bf16\[(?:1,)?256,(?:2048,768|768,2048)\]"
                      r"\S* (\w[\w\-]*)\(", text)
    assert set(made) <= {"parameter", "bitcast", "get-tuple-element"}, made
    # the experts are arguments read in place: the program's temporaries
    # are far under one expert matrix stack (0.4 GB)
    assert compiled.memory_analysis().temp_size_in_bytes < 0.2e9


# ---------------------------------------------------------------------------
# linear attention: the decode state kernel and the programs round it
# ---------------------------------------------------------------------------
def _ling_fields():
    import json
    from pathlib import Path
    return json.loads((Path(__file__).resolve().parents[3]
                       / "benchmark/configs/ling-3.0-flash.json"
                       ).read_text())["fields"]


@pytest.mark.parametrize("kept", [jnp.float32, jnp.bfloat16])
def test_the_decode_state_kernel_at_published_widths(tpu_sharding, kept):
    """``kda_state_update`` for 128 rows of 32 heads of [128, 128] in a
    leaf of 7 layers and 129 slots: it compiles for the chip, runs as
    ONE custom call under a name a trace finds, and the leaf is aliased
    (no copy of 1.9 GB: temporaries stay under 0.1 GB)."""
    from deepspeed_tpu.inference.v2.kernels import linear_attention as la

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=tpu_sharding)

    N, nh, d = 128, 32, 128
    leaf = sds((7, 129, nh, d, d), kept)
    assert la.state_kernel_serves(leaf)
    compiled = jax.jit(la.kda_state_update, donate_argnums=(0,)).lower(
        leaf, sds((), jnp.int32), sds((N,), jnp.int32),
        sds((N,), jnp.bool_), sds((N, nh, d)), sds((N, nh, d)),
        sds((N, nh, d)), sds((N, nh, d)), sds((N, nh))).compile()
    kernels = re.findall(r"%([\w.\-]+) = [^\n]*tpu_custom_call",
                         compiled.as_text())
    assert len(kernels) == 1 and kernels[0].startswith("kda_state_update")
    assert compiled.memory_analysis().temp_size_in_bytes < 0.1e9


def test_the_chunk_kernel_at_published_widths(tpu_sharding):
    """``kda_chunk_fwd`` for the cell's ragged launch (128 rows of 128
    tokens, bf16 projections of 32 heads of 128, a leaf of 7 layers and
    129 slots): it compiles for the chip, runs as ONE custom call under
    a name a trace finds, the leaf is aliased and the outputs leave in
    the layout the layer reads (no temporary at all: under 0.1 GB)."""
    from deepspeed_tpu.inference.v2.kernels import linear_attention as la

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=tpu_sharding)

    R, T, nh, d = 128, 128 * 128, 32, 128
    leaf = sds((7, 129, nh, d, d))
    assert la.chunk_kernel_serves(leaf)
    wide = sds((T, nh * d), jnp.bfloat16)
    compiled = jax.jit(
        lambda q, k, v, f, b, rate, bias, leaf, *rows: la.kda_chunk_fwd(
            (q, k, v, f, b), rate, bias, leaf, *rows, -5.0),
        donate_argnums=(7,)).lower(
        wide, wide, wide, wide, sds((T, nh), jnp.bfloat16), sds((nh,)),
        sds((nh * d,)), leaf, sds((), jnp.int32), sds((R,), jnp.int32),
        sds((R,), jnp.bool_), sds((R,), jnp.int32),
        sds((R,), jnp.int32)).compile()
    kernels = re.findall(r"%([\w.\-]+) = [^\n]*tpu_custom_call",
                         compiled.as_text())
    assert len(kernels) == 1 and kernels[0].startswith("kda_chunk_fwd")
    assert compiled.memory_analysis().temp_size_in_bytes < 0.1e9


@pytest.mark.parametrize("proj", [jnp.float32, jnp.bfloat16])
def test_the_conv_kernel_at_published_widths(tpu_sharding, proj):
    """``kda_conv_update`` at the cell's decode shape (128 rows, q, k and
    v of 4,096 channels as the decode programs keep them, float32, and
    in bf16; a float32 leaf of 7 layers and 129 slots stored
    lane-dense): it compiles for the chip, runs as ONE custom call
    under a name a trace finds, and the leaf is aliased (no copy of
    0.13 GB: what the program keeps beside its arguments are the
    projections as rows of lanes, 6 MB in and 6 MB out at most)."""
    from deepspeed_tpu.inference.v2.kernels import linear_attention as la

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=tpu_sharding)

    N, D, K = 128, 32 * 128, 4
    leaf = sds(la.conv_leaf_shape(7, 129, K, 3 * D))
    assert leaf.shape == (7, 129, 3, 96, 128) and la.conv_kernel_serves(leaf)
    x = sds((N, D), proj)
    compiled = jax.jit(la.kda_conv_update, donate_argnums=(0,)).lower(
        leaf, sds((), jnp.int32), sds((N,), jnp.int32),
        sds((N,), jnp.bool_), x, x, x,
        sds((K, 3 * D), jnp.bfloat16)).compile()
    kernels = re.findall(r"%([\w.\-]+) = [^\n]*tpu_custom_call",
                         compiled.as_text())
    assert len(kernels) == 1 and kernels[0].startswith("kda_conv_update")
    assert compiled.memory_analysis().temp_size_in_bytes < 0.02e9


@pytest.mark.parametrize("act,proj", [
    ("none", jnp.float32), ("none", jnp.bfloat16), ("silu", jnp.float32)])
def test_the_short_conv_kernel_at_published_widths(tpu_sharding, act, proj):
    """``conv_update`` as a short-convolution MIXER's core at
    lfm2-8b-a1b.rollout-256x512-512's decode shape: ONE part of 2,048
    channels (no q | k | v to keep apart), 3 taps, 256 rows, no
    activation (and with SiLU, the default the other blocks trace), a
    float32 leaf of 11 layers and 257 slots: it compiles for the chip,
    runs as ONE custom call under the name a trace finds, and the leaf
    is aliased (no copy of its 46 MB)."""
    from deepspeed_tpu.inference.v2.kernels import linear_attention as la

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=tpu_sharding)

    N, D, K = 256, 2048, 3
    leaf = sds(la.conv_leaf_shape(11, 257, K, D))
    assert leaf.shape == (11, 257, 2, 16, 128)
    assert la.conv_kernel_serves(leaf, parts=1)
    compiled = jax.jit(
        lambda leaf, l, slots, fresh, g, taps: la.conv_update(
            leaf, l, slots, fresh, (g,), taps, act=act,
            name="short_conv_update"), donate_argnums=(0,)).lower(
        leaf, sds((), jnp.int32), sds((N,), jnp.int32),
        sds((N,), jnp.bool_), sds((N, D), proj),
        sds((K, D), jnp.bfloat16)).compile()
    kernels = re.findall(r"%([\w.\-]+) = [^\n]*tpu_custom_call",
                         compiled.as_text())
    assert len(kernels) == 1 and kernels[0].startswith("short_conv_update")
    assert compiled.memory_analysis().temp_size_in_bytes < 0.01e9


def test_the_short_conv_decode_window_at_256_rows(tpu_sharding):
    """The decode window of the lfm2_moe block at published widths, cut
    to its two leading dense layers, its first attention layer and one
    conv expert layer (4 layers, every expert held), at the cell's 256
    rows over a table of 64 pages: the convolution's kernel runs in both
    conv runs with no gather or scatter of the slots in XLA beside it,
    the grouped matmul in both expert layers, the one-token attention
    kernel once; the conv leaf stays where it lies."""
    from deepspeed_tpu.inference.v2.paged_model import (init_paged_kv_cache,
                                                        paged_decode_window)
    from deepspeed_tpu.models import TransformerLM
    from deepspeed_tpu.models.transformer import TransformerConfig

    import json
    from pathlib import Path
    fields = json.loads((Path(__file__).resolve().parents[3]
                         / "benchmark/configs/lfm2-8b-a1b.json"
                         ).read_text())["fields"]
    cfg = TransformerConfig(**{**fields, "num_layers": 4, "vocab_size": 4096,
                               "layer_types": fields["layer_types"][:4]})

    def on_tpu(x, dtype=None):
        return jax.ShapeDtypeStruct(x.shape, dtype or x.dtype,
                                    sharding=tpu_sharding)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=tpu_sharding)

    R = 256
    params = jax.tree.map(
        lambda x: on_tpu(x, jnp.bfloat16),
        jax.eval_shape(TransformerLM(cfg).init_params,
                       jax.random.PRNGKey(0)))
    cache = jax.tree.map(on_tpu, jax.eval_shape(
        lambda: init_paged_kv_cache(cfg, 16641, 16, jnp.bfloat16,
                                    state_slots=R)))
    assert cache["conv_state"].shape == (3, 257, 2, 16, 128)
    compiled = jax.jit(
        lambda p, t, pos, bt, c, sl, eos, alive, ss: paged_decode_window(
            cfg, p, t, pos, bt, c, sl, eos, 16, 8, use_kernel=True,
            alive=alive, state_slots=ss), donate_argnums=(4,)).lower(
        params, i32(R), i32(R), i32(R, 64), cache, i32(R), i32(R),
        jax.ShapeDtypeStruct((R,), jnp.bool_, sharding=tpu_sharding),
        i32(R)).compile()
    text = compiled.as_text()
    kernels = re.findall(r"%([\w.\-]+) = [^\n]*tpu_custom_call", text)
    assert sum(k.startswith("short_conv_update") for k in kernels) == 2, \
        kernels
    assert sum(bool(GMM_PATTERN.search(k)) for k in kernels) == 6, kernels
    assert sum(k.startswith("ragged_attention_tiled") for k in kernels) \
        == 1, kernels
    under_gate = re.findall(
        r"= \S+ ([\w\-]+)\([^\n]*op_name=\"[^\"]*/conv_gate/", text)
    assert under_gate and not {"gather", "scatter", "copy-start",
                               "copy-done"} & set(under_gate), under_gate
    _leaves_stay_in_hbm(text, cache, "conv_state")
    assert compiled.memory_analysis().temp_size_in_bytes < 0.15e9


def _hybrid_cut(tpu_sharding):
    """The pattern at published widths, cut to its first linear expert
    layer and the layers before it (3 layers, 8 experts held): the
    configuration, and its parameters and cache (33 state slots) as
    shapes on the chip."""
    from deepspeed_tpu.inference.v2.paged_model import init_paged_kv_cache
    from deepspeed_tpu.models import TransformerLM
    from deepspeed_tpu.models.transformer import TransformerConfig

    cfg = TransformerConfig(**{**_ling_fields(), "num_layers": 3,
                               "moe_experts_held": 8, "vocab_size": 4096})

    def on_tpu(x, dtype=None):
        return jax.ShapeDtypeStruct(x.shape, dtype or x.dtype,
                                    sharding=tpu_sharding)

    params = jax.tree.map(
        lambda x: on_tpu(x, jnp.bfloat16),
        jax.eval_shape(TransformerLM(cfg).init_params,
                       jax.random.PRNGKey(0)))
    cache = jax.tree.map(on_tpu, jax.eval_shape(
        lambda: init_paged_kv_cache(cfg, 129, 16, jnp.bfloat16,
                                    state_slots=32)))
    assert cache["latent"].shape[0] == 0 and cache["kda_state"].shape[:2] \
        == (3, 33)
    return cfg, params, cache


def test_the_hybrid_ragged_step_runs_its_chunks_in_the_kernel(tpu_sharding):
    """The ragged step of the same cut, 32 rows in 2,048 tokens: the
    chunk kernel runs in both linear runs and the grouped matmul in the
    expert layer; nothing under ``kda_chunk`` loops or solves a
    triangle in XLA any more; and the launch's temporaries are no more
    than the parent's, whose loop kept a chunk's gathered rows, pairs'
    operands and solve beside the outputs (it read 504,550,912 B; this
    reads 116,973,568)."""
    from deepspeed_tpu.inference.v2.paged_model import paged_ragged_step

    cfg, params, cache = _hybrid_cut(tpu_sharding)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=tpu_sharding)

    T, R = 2048, 32
    compiled = jax.jit(
        lambda p, ids, rows, pos, ln, wb, wo, bt, li, c, ss:
        paged_ragged_step(cfg, p, ids, rows, pos, ln, wb, wo, bt, li, c, 16,
                          use_kernel=True, state_slots=ss),
        donate_argnums=(9,)).lower(
        params, i32(T), i32(T), i32(T), i32(T), i32(T), i32(T), i32(R, 16),
        i32(R), cache, i32(R)).compile()
    text = compiled.as_text()
    kernels = re.findall(r"%([\w.\-]+) = [^\n]*tpu_custom_call", text)
    assert sum(k.startswith("kda_chunk_fwd") for k in kernels) == 2, kernels
    assert sum(bool(GMM_PATTERN.search(k)) for k in kernels) == 3, kernels
    assert sum(k.startswith("moe_rows_combine") for k in kernels) == 1, \
        kernels
    assert "triangular-solve" not in text and "triangular_solve" not in text
    assert "kda_chunk/while" not in text
    assert compiled.memory_analysis().temp_size_in_bytes <= 504_550_912


def test_the_hybrid_decode_window_compiles_with_its_state_in_place(
        tpu_sharding):
    """The decode window of the pattern at published widths, cut to its
    first linear expert layer and the layers before it (3 layers, 8
    experts held): the state kernel and the convolution's kernel run in
    both linear runs, the grouped matmul in the expert layer; nothing
    under ``kda_conv`` gathers or scatters the slots in XLA any more or
    copies the convolution's leaf, and neither leaf is laid in the
    chip's fast memory (this cut's small convolution leaf, 15 MB, was
    moved there and back round every launch until PR 57 coloured it);
    and the program's temporaries hold no copy of the state leaf (32
    rows x 3 layers: 0.2 GB)."""
    from deepspeed_tpu.inference.v2.paged_model import paged_decode_window

    cfg, params, cache = _hybrid_cut(tpu_sharding)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=tpu_sharding)

    R = 32
    compiled = jax.jit(
        lambda p, t, pos, bt, c, sl, eos, alive, ss: paged_decode_window(
            cfg, p, t, pos, bt, c, sl, eos, 16, 8, use_kernel=True,
            alive=alive, state_slots=ss), donate_argnums=(4,)).lower(
        params, i32(R), i32(R), i32(R, 16), cache, i32(R), i32(R),
        jax.ShapeDtypeStruct((R,), jnp.bool_, sharding=tpu_sharding),
        i32(R)).compile()
    text = compiled.as_text()
    kernels = re.findall(r"%([\w.\-]+) = [^\n]*tpu_custom_call", text)
    assert sum(k.startswith("kda_state_update") for k in kernels) == 2, \
        kernels
    assert sum(k.startswith("kda_conv_update") for k in kernels) == 2, \
        kernels
    assert sum(bool(GMM_PATTERN.search(k)) for k in kernels) == 3, kernels
    under_conv = re.findall(
        r"= \S+ ([\w\-]+)\([^\n]*op_name=\"[^\"]*/kda_conv/", text)
    assert under_conv and not {"gather", "scatter", "copy", "copy-start",
                               "copy-done"} & set(under_conv), under_conv
    _leaves_stay_in_hbm(text, cache, "kda_conv", "kda_state")
    assert compiled.memory_analysis().temp_size_in_bytes < 0.15e9


# ---------------------------------------------------------------------------
# a pattern over per-head attention (window and full layers, a cache of
# two geometries): trinity-mini.rollout-16x8192-512's geometry
# ---------------------------------------------------------------------------
# (tokens, rows, the full table's pages, a ring's pages)
WINDOW_LAUNCHES = {"prefill": (16384, 16, 544, 193),
                   "decode": (16, 16, 544, 193)}
WINDOW_PATTERN = re.compile(r"ragged_attention_(window|tiled)[_.0-9]*$")


@pytest.mark.parametrize("launch", sorted(WINDOW_LAUNCHES))
def test_window_kernel_at_the_cells_launch_shapes(tpu_sharding, launch):
    """The tiled kernel with a window at 4 kv heads x 128 (group 8) over
    a ring of 193 pages: it compiles for a v5e at the cell's chunk step
    and decode step, and its name in a trace tells it from the full
    launches (benchmark/layer_metrics/window_roofline.gen.json)."""
    T, R, _, ring = WINDOW_LAUNCHES[launch]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=tpu_sharding)

    pool = sds((4, R * ring + 1, 16, 4 * 128), jnp.bfloat16)
    args = [sds((T, 32, 128), jnp.bfloat16), pool, pool, sds((), jnp.int32),
            sds((T,), jnp.int32), sds((T,), jnp.int32),
            sds((R, ring), jnp.int32)]
    assert kernel_variant(128, 4, False) == "tiled"
    text = jax.jit(lambda *a: ragged_attention(*a, window=2048)).lower(
        *args).compile().as_text()
    kernels = re.findall(r"%([\w.\-]+) = [^\n]*tpu_custom_call", text)
    assert len(kernels) == 1 and kernels[0].startswith(
        "ragged_attention_window") and WINDOW_PATTERN.search(kernels[0])
    # and without one it is the kernel it was, under the name it had
    text = jax.jit(lambda *a: ragged_attention(*a)).lower(
        *args).compile().as_text()
    kernels = re.findall(r"%([\w.\-]+) = [^\n]*tpu_custom_call", text)
    assert len(kernels) == 1 and kernels[0].startswith(
        "ragged_attention_tiled")


def _window_cut(tpu_sharding):
    """The pattern at published widths, cut to [sliding + dense,
    sliding + experts, full + experts] with 8 experts and 4,096 rows of
    the vocabulary: the configuration, and its parameters and cache
    (16 rows' blocks and rings) as shapes on the chip."""
    from deepspeed_tpu.inference.v2.paged_model import init_paged_kv_cache
    from deepspeed_tpu.models import TransformerLM
    import json
    from pathlib import Path
    from deepspeed_tpu.models.transformer import TransformerConfig

    fields = json.loads((Path(__file__).resolve().parents[3]
                         / "benchmark/configs/trinity-mini.json"
                         ).read_text())["fields"]
    cfg = TransformerConfig(**{
        **fields, "num_layers": 3, "layer_types": fields["layer_types"][:3],
        "moe_num_experts": 8, "vocab_size": 4096})

    def on_tpu(x, dtype=None):
        return jax.ShapeDtypeStruct(x.shape, dtype or x.dtype,
                                    sharding=tpu_sharding)

    params = jax.tree.map(
        lambda x: on_tpu(x, jnp.bfloat16),
        jax.eval_shape(TransformerLM(cfg).init_params,
                       jax.random.PRNGKey(0)))
    cache = jax.tree.map(on_tpu, jax.eval_shape(
        lambda: init_paged_kv_cache(cfg, 16 * 545 + 1, 16, jnp.bfloat16,
                                    window_blocks=16 * 193 + 1)))
    assert cache["k_full"].shape == (1, 8721, 16, 512) \
        and cache["k_window"].shape == (2, 3089, 16, 512)
    return cfg, params, cache


def test_the_patterns_decode_window_compiles_with_both_pools_in_place(
        tpu_sharding):
    """The decode window of the per-head pattern at published widths
    (its first three layers): the window kernel runs in the two sliding
    runs, the full kernel in the full one, the grouped matmul in both
    expert layers, and the program's temporaries hold no copy of either
    pool (0.29 + 0.20 GB here)."""
    from deepspeed_tpu.inference.v2.paged_model import paged_decode_window

    cfg, params, cache = _window_cut(tpu_sharding)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=tpu_sharding)

    R = 16
    compiled = jax.jit(
        lambda p, t, pos, bt, c, sl, eos, alive, wt: paged_decode_window(
            cfg, p, t, pos, bt, c, sl, eos, 16, 8, use_kernel=True,
            alive=alive, window_tables=wt), donate_argnums=(4,)).lower(
        params, i32(R), i32(R), i32(R, 544), cache, i32(R), i32(R),
        jax.ShapeDtypeStruct((R,), jnp.bool_, sharding=tpu_sharding),
        i32(R, 193)).compile()
    kernels = re.findall(r"%([\w.\-]+) = [^\n]*tpu_custom_call",
                         compiled.as_text())
    assert sum(k.startswith("ragged_attention_window") for k in kernels) \
        == 2, kernels
    assert sum(k.startswith("ragged_attention_tiled") for k in kernels) \
        == 1, kernels
    assert sum(bool(GMM_PATTERN.search(k)) for k in kernels) == 6, kernels
    assert compiled.memory_analysis().temp_size_in_bytes < 0.1e9
    # the one-token form lays the queries token-major ([16, 4, 8, 128]);
    # neither pool is relaid for it
    assert compiled.as_text().count("bf16[16,4,8,128]") >= 3
    _no_relayout_of(compiled.as_text(), 8721, 3089)
    # PR 67: how the two tables lie is made ONCE a window, ahead of the
    # loop of steps and of every layer
    assert _table_runs_made(compiled.as_text()) == 2


def _table_runs_made(text):
    """How many tables' runs the compiled program makes
    (``paged_model._table_runs``, scope ``table_runs``): each instruction
    of it stands outside every layer's scope, every ``attention`` /
    ``attn_proj`` word and the decode window's loop, and the count is of
    the selects that close ``ragged_attention.table_runs``, one a
    table."""
    made = re.findall(
        r"^\s*(?:ROOT )?%([\w.\-]+) = ([^\n]*?) ([\w\-]+)\([^\n]*"
        r'op_name="([^"]*table_runs[^"]*)"', text, re.M)
    assert made, "no instruction under the scope table_runs"
    for _, _, _, scope in made:
        assert not re.search(
            r"layers|attention|attn_|qkv_proj|out_proj|while|body|scan",
            scope), scope
    # a table's runs end in ONE select (``where(link, run, -stretch)``),
    # which the compiler leaves a fusion of its own
    return sum(op == "fusion" and scope.endswith("select_n")
               for _, _, op, scope in made)


def test_the_patterns_ragged_step_makes_its_tables_runs_once(tpu_sharding):
    """The ragged step of the per-head pattern at published widths (its
    first three layers, 2,048 tokens of 16 rows): the two tables' runs
    are made once each, under the scope ``table_runs`` and no layer's
    (``prefill_attn_proj_ms.gen`` reads what it read), and a model whose
    pages are 64 KB (OPT-1.3B) makes none: its programs are the
    parent's."""
    from deepspeed_tpu.inference.v2.paged_model import paged_ragged_step

    cfg, params, cache = _window_cut(tpu_sharding)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=tpu_sharding)

    T, R = 2048, 16
    text = jax.jit(
        lambda p, ids, rows, pos, ln, wb, wo, bt, li, c, wt:
        paged_ragged_step(cfg, p, ids, rows, pos, ln, wb, wo, bt, li, c, 16,
                          use_kernel=True, window_tables=wt),
        donate_argnums=(9,)).lower(
        params, i32(T), i32(T), i32(T), i32(T), i32(T), i32(T), i32(R, 544),
        i32(R), cache, i32(R, 193)).compile().as_text()
    assert _table_runs_made(text) == 2
    cfg, params, cache, i32 = _serving_case(tpu_sharding, False)
    text = jax.jit(
        lambda p, ids, rows, pos, ln, wb, wo, bt, li, c: paged_ragged_step(
            cfg, p, ids, rows, pos, ln, wb, wo, bt, li, c, 16,
            use_kernel=True)).lower(
        params, i32(64), i32(64), i32(64), i32(64), i32(64), i32(64),
        i32(8, 16), i32(8), cache).compile().as_text()
    assert "table_runs" not in text


# ---------------------------------------------------------------------------
# the one-token form: the four generation cells' decode launches
# ---------------------------------------------------------------------------
# cell -> (kernel, rows, the pool as stored, the table's pages, window,
# the launch's name, the queries as the kernel takes them: token-major)
ONE_TOKEN_LAUNCHES = {
    "joyai-llm-flash.rollout-64x256": (
        "latent", 64, (5, 1601, 16, 640), 32, 0, "ragged_attention_latent",
        "bf16[64,32,640]"),
    "ling-3.0-flash.rollout-128x256": (
        "latent", 128, (1, 3201, 16, 640), 32, 0, "ragged_attention_latent",
        "bf16[128,32,640]"),
    "trinity-mini.rollout-16x8192-512.full": (
        "tiled", 16, (1, 8721, 16, 512), 544, 0, "ragged_attention_tiled",
        "bf16[16,4,8,128]"),
    "trinity-mini.rollout-16x8192-512.window": (
        "tiled", 16, (4, 3089, 16, 512), 193, 2048,
        "ragged_attention_window", "bf16[16,4,8,128]"),
    "opt-1.3b.rollout-256": (
        "tiled", 16, (24, 529, 16, 2048), 32, 0, "ragged_attention_tiled",
        "bf16[16,16,8,128]"),
    # PR 50: the int8 pool's decode launch (the control of rollout-256: an
    # int8 page's bytes are what its chunk waits for, the scales' copies
    # keep their own waits) and the fifth cell's one attention layer
    "opt-1.3b.rollout-256.int8": (
        "tiled-int8", 16, (24, 529, 16, 2048), 32, 0,
        "ragged_attention_tiled", "bf16[16,16,8,128]"),
    "granite-4.0-h-small.rollout-64x1024-256": (
        "tiled", 64, (1, 5185, 16, 1024), 80, 0, "ragged_attention_tiled",
        "bf16[64,8,8,128]"),
}


@pytest.mark.parametrize("cell", sorted(ONE_TOKEN_LAUNCHES))
def test_the_decode_launches_lower_in_the_one_token_form(tpu_sharding, cell):
    """A decode step of each generation cell at its shapes, told that
    every row has one token: Mosaic takes the one-token form of the
    latent kernel (64 and 128 rows of 32 heads over a 640-lane row) and
    of the tiled kernel (16 rows: GQA group 8 at width 128, full and
    over a ring with a window; two 64-wide heads a lane block, their 2
    query rows padded to one sublane tile), under the name the launch
    had (the roofline and share readers' patterns), with the queries
    token-major and nothing as large as a layer of the pool beside
    it."""
    from deepspeed_tpu.inference.v2.kernels.ragged_attention import \
        latent_attention
    kernel, R, pool, MB, window, name, queries = ONE_TOKEN_LAUNCHES[cell]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=tpu_sharding)

    desc = [sds((), jnp.int32), sds((R,), jnp.int32), sds((R,), jnp.int32),
            sds((R, MB), jnp.int32)]
    if kernel == "latent":
        fn = lambda *a: latent_attention(       # noqa: E731
            *a, dc=512, scale=192 ** -0.5, one_token=True)
        args = [sds((32, R, pool[-1]), jnp.bfloat16),
                sds(pool, jnp.bfloat16)] + desc
    else:
        hd = 64 if pool[-1] == 2048 else 128
        quant = kernel == "tiled-int8"
        fn = lambda *a: ragged_attention(       # noqa: E731
            *a[:7], window=window, one_token=True,
            **(dict(k_scale=a[7], v_scale=a[8]) if quant else {}))
        kept = jnp.int8 if quant else jnp.bfloat16
        args = [sds((R, 32, hd), jnp.bfloat16), sds(pool, kept),
                sds(pool, kept)] + desc
        if quant:
            args += [sds((pool[1], pool[-1] // hd), jnp.float32)] * 2
    compiled = jax.jit(fn).lower(*args).compile()
    calls = [line for line in compiled.as_text().splitlines()
             if "tpu_custom_call" in line]
    assert len(calls) == 1, calls
    assert re.match(r"\s*%" + name + r"[_.0-9]* = ", calls[0]), calls[0][:200]
    assert queries in calls[0], calls[0][:400]
    layer = 2 * pool[1] * pool[2] * pool[3]
    assert compiled.memory_analysis().temp_size_in_bytes < layer / 4


# PR 50: the prompt launches no test above holds (rollout-256's 4,096 tokens,
# joyai's 8,192 and trinity's 16,384 with and without the window are
# CELL_LAUNCHES, LATENT_LAUNCHES and WINDOW_LAUNCHES): (kernel, tokens, rows,
# the pool as stored, the table's pages, query heads, window, the token tile
# by the queries' block). PR 59 grows them to the five per-head cells' ragged
# steps at the tile each now takes: up to 1,024 query rows a lane block
# (``_token_tile``), inside ``_TILED_VMEM_BYTES``
PROMPT_LAUNCHES = {
    "ling-3.0-flash.rollout-128x256": (
        "latent", 16384, 128, (1, 3201, 16, 640), 8, 32, 0, None),
    "granite-4.0-h-small.rollout-64x1024-256": (
        "tiled", 16384, 64, (1, 5185, 16, 1024), 80, 32, 0,
        "bf16[8,4,16384,128]"),
    "opt-1.3b.rollout-256": (
        "tiled", 4096, 16, (24, 529, 16, 2048), 16, 32, 0,
        "bf16[16,2,4096,128]"),
    "opt-1.3b.rollout-256.int8": (
        "tiled-int8", 4096, 16, (24, 529, 16, 2048), 16, 32, 0,
        "bf16[16,2,4096,128]"),
    "nemotron-3-nano-30b-a3b.rollout-128x256-384": (
        "tiled", 16384, 128, (2, 5249, 16, 256), 40, 32, 0,
        "bf16[2,16,16384,128]"),
    "trinity-mini.rollout-16x8192-512.full": (
        "tiled", 16384, 16, (1, 8721, 16, 512), 544, 32, 0,
        "bf16[4,8,16384,128]"),
    "trinity-mini.rollout-16x8192-512.window": (
        "tiled", 16384, 16, (4, 3089, 16, 512), 193, 32, 2048,
        "bf16[4,8,16384,128]"),
    "smallthinker-21ba3b-instruct.rollout-16x8192-512.full": (
        "tiled", 16384, 16, (2, 8721, 16, 512), 544, 28, 0,
        "bf16[4,7,16384,128]"),
    "smallthinker-21ba3b-instruct.rollout-16x8192-512.window": (
        "tiled", 16384, 16, (6, 5137, 16, 512), 321, 28, 4096,
        "bf16[4,7,16384,128]"),
}


@pytest.mark.parametrize("cell", sorted(PROMPT_LAUNCHES))
def test_the_prompt_launches_lower_with_a_wait_a_chunk(tpu_sharding, cell):
    """The token tile takes PR 50's waits (the walk is one): a chunk's
    pages waited for by their bytes on descriptors that are never
    started, at the cells' ragged steps of 16,384 (OPT: 4,096) tokens;
    Mosaic takes them under the launches' names, at the tile PR 59 gives
    each (a lane block's query rows a token 2, 4, 7, 8 and 16; a window
    over a ring; an int8 pool), inside the fast memory the launch is
    granted."""
    from deepspeed_tpu.inference.v2.kernels.ragged_attention import \
        latent_attention
    kernel, T, R, pool, MB, nh, window, queries = PROMPT_LAUNCHES[cell]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=tpu_sharding)

    desc = [sds((), jnp.int32), sds((T,), jnp.int32), sds((T,), jnp.int32),
            sds((R, MB), jnp.int32)]
    if kernel == "latent":
        fn = lambda *a: latent_attention(       # noqa: E731
            *a, dc=512, scale=192 ** -0.5)
        args = [sds((32, T, 640), jnp.bfloat16), sds(pool, jnp.bfloat16)]
        pattern = LATENT_PATTERN
    else:
        hd = 64 if pool[-1] == 2048 else 128
        quant = kernel == "tiled-int8"
        fn = lambda *a: ragged_attention(       # noqa: E731
            *a[:7], window=window,
            **(dict(k_scale=a[7], v_scale=a[8]) if quant else {}))
        kept = jnp.int8 if quant else jnp.bfloat16
        args = [sds((T, nh, hd), jnp.bfloat16), sds(pool, kept),
                sds(pool, kept)]
        if quant:
            desc += [sds((pool[1], pool[-1] // hd), jnp.float32)] * 2
        pattern = TRACE_PATTERN
    text = jax.jit(fn).lower(*args, *desc).compile().as_text()
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    kernels = re.findall(r"%([\w.\-]+) = [^\n]*tpu_custom_call", text)
    assert len(kernels) == 1 and pattern.search(kernels[0]), kernels
    assert bool(window) == kernels[0].startswith("ragged_attention_window")
    if queries:
        # the queries as the token tile takes them: [lane block, query
        # row of the block, token, 128]
        assert queries in calls[0], calls[0][:400]


def _no_relayout_of(text, *pools):
    """No ``copy`` or ``transpose`` in the program makes a value of a
    pool's or a layer's shape (the pool's blocks x 16 positions as the
    leading or second axis): PR 37's bug."""
    for blocks in pools:
        made = re.findall(
            r"= \w+\[((?:\d+,)?%d,16,[\d,]+)\]\S* ([\w\-]+)\(" % blocks,
            text)
        assert made, f"the pattern no longer finds the pool of {blocks}"
        relaid = [m for m in made if m[1] in ("copy", "transpose",
                                              "copy-start", "copy-done")]
        assert not relaid, relaid


def test_the_latent_decode_window_takes_the_one_token_form(tpu_sharding):
    """The decode window of the latent block at published widths (the
    leading dense layer and one expert layer, 64 rows): both stacks'
    launches are the latent kernel under its name with the queries
    token-major (a few hundred KB relaid round it), and no copy or
    transpose of the pool's shape."""
    from deepspeed_tpu.inference.v2.paged_model import paged_decode_window

    cfg, params, cache = _latent_cut(tpu_sharding, 1601)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=tpu_sharding)

    R = 64
    compiled = jax.jit(
        lambda p, t, pos, bt, c, sl, eos, alive: paged_decode_window(
            cfg, p, t, pos, bt, c, sl, eos, 16, 8, use_kernel=True,
            alive=alive), donate_argnums=(4,)).lower(
        params, i32(R), i32(R), i32(R, 32), cache, i32(R), i32(R),
        jax.ShapeDtypeStruct((R,), jnp.bool_, sharding=tpu_sharding)
    ).compile()
    text = compiled.as_text()
    lines = [line for line in text.splitlines()
             if "tpu_custom_call" in line and "ragged_attention" in line]
    assert len(lines) == 2 and all(
        LATENT_PATTERN.search(re.match(r"\s*%([\w.\-]+) =", line).group(1))
        and "bf16[64,32,640]" in line for line in lines), lines
    _no_relayout_of(text, 1601)
    assert compiled.memory_analysis().temp_size_in_bytes < 0.2e9


# ---------------------------------------------------------------------------
# state-space (Mamba-2) layers beside per-head attention:
# granite-4.0-h-small.rollout-64x1024-256's geometry
# ---------------------------------------------------------------------------
def _granite_fields():
    import json
    from pathlib import Path
    return json.loads((Path(__file__).resolve().parents[3] / "benchmark"
                       / "configs/granite-4.0-h-small.json")
                      .read_text())["fields"]


def _custom_calls(compiled):
    return re.findall(r"%([\w.\-]+) = [^\n]*tpu_custom_call",
                      compiled.as_text())


@pytest.mark.parametrize("kept", [jnp.float32, jnp.bfloat16])
def test_the_ssm_state_kernel_at_published_widths(tpu_sharding, kept):
    """``ssm_state_update`` at the cell's decode shape (64 rows, 128
    heads of 64 as 64 lane blocks of channels, a state of 128, a leaf of
    9 layers and 65 slots, float32 and the control's bfloat16): it
    compiles for the chip, runs as ONE custom call under a name a trace
    finds, and the leaf is aliased (no copy of 2.45 GB). B and C go in
    as the token's own 128 values, ``[64, 1, 1, 128]``: nothing of the
    program is one of them spread over the lanes (``[64, 128, 128]``,
    4.2 MB each until PR 53), and what it keeps beside its arguments
    are the rows' decay, ``dt x`` and output, 2 MB each, which the
    compiler holds outside HBM's temporaries (it reads 0 here, as it
    did with the two spread pairs: under 0.01 GB holds both out)."""
    from deepspeed_tpu.inference.v2.kernels import state_space as ss

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=tpu_sharding)

    N, nh, p, n = 64, 128, 64, 128
    leaf = sds(ss.state_leaf_shape(9, 65, nh * p, n), kept)
    assert leaf.shape == (9, 65, 64, 128, 128) \
        and ss.state_kernel_serves(leaf)
    compiled = jax.jit(ss.ssm_state_update, donate_argnums=(0,)).lower(
        leaf, sds((), jnp.int32), sds((N,), jnp.int32), sds((N,), jnp.bool_),
        sds((N, nh * p)), sds((N, nh)), sds((nh,)), sds((N, n)),
        sds((N, n))).compile()
    kernels = _custom_calls(compiled)
    assert len(kernels) == 1 and kernels[0].startswith("ssm_state_update")
    assert f"f32[{N},1,1,{n}]" in compiled.as_text()
    assert f"f32[{N},{n},128]" not in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 0.01e9


def test_the_ssm_chunk_kernel_at_published_widths(tpu_sharding):
    """``ssm_chunk_fwd`` for the cell's ragged launch (64 rows of 256
    tokens, x, B and C the ONE bf16 buffer the convolution leaves, 8,448
    wide): it compiles for the chip, runs as ONE custom call under a
    name a trace finds, the leaf is aliased and no slice of the token
    buffer is made (no temporary at all: under 0.05 GB)."""
    from deepspeed_tpu.inference.v2.kernels import state_space as ss

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=tpu_sharding)

    R, T, nh, p, n = 64, 64 * 256, 128, 64, 128
    leaf = sds(ss.state_leaf_shape(9, 65, nh * p, n))
    assert ss.chunk_kernel_serves(leaf, p)
    assert not ss.chunk_kernel_serves(leaf, 48)
    compiled = jax.jit(ss.ssm_chunk_fwd, donate_argnums=(0,)).lower(
        leaf, sds((), jnp.int32), sds((R,), jnp.int32), sds((R,), jnp.bool_),
        sds((R,), jnp.int32), sds((R,), jnp.int32),
        sds((T, nh * p + 2 * n), jnp.bfloat16), sds((T, nh)),
        sds((nh,))).compile()
    kernels = _custom_calls(compiled)
    assert len(kernels) == 1 and kernels[0].startswith("ssm_chunk_fwd")
    assert compiled.memory_analysis().temp_size_in_bytes < 0.05e9


@pytest.mark.parametrize("proj", [jnp.float32, jnp.bfloat16])
def test_the_conv_kernel_takes_a_state_space_layers_input(tpu_sharding,
                                                          proj):
    """``conv_update`` as the state-space layers call it: ONE input of
    8,448 channels (66 lane blocks: not whole (16, 128) tiles, and no
    part of it is cut), a bias, a leaf of 9 layers and 65 slots: ONE
    custom call named ``ssm_conv_update``, the leaf aliased."""
    from deepspeed_tpu.inference.v2.kernels import linear_attention as la

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=tpu_sharding)

    N, D, K = 64, 8448, 4
    leaf = sds(la.conv_leaf_shape(9, 65, K, D))
    assert leaf.shape == (9, 65, 3, 66, 128)
    assert la.conv_kernel_serves(leaf, parts=1)
    assert not la.conv_kernel_serves(leaf)          # three parts of 22
    compiled = jax.jit(
        lambda leaf, layer, slots, fresh, x, taps, bias: la.conv_update(
            leaf, layer, slots, fresh, (x,), taps, bias,
            name="ssm_conv_update"), donate_argnums=(0,)).lower(
        leaf, sds((), jnp.int32), sds((N,), jnp.int32), sds((N,), jnp.bool_),
        sds((N, D), proj), sds((K, D), jnp.bfloat16),
        sds((D,), jnp.bfloat16)).compile()
    kernels = _custom_calls(compiled)
    assert len(kernels) == 1 and kernels[0].startswith("ssm_conv_update")
    assert compiled.memory_analysis().temp_size_in_bytes < 0.02e9


def test_an_expert_wider_than_a_tile_is_tiled_by_columns():
    """An expert of 4,096 x 768 in bf16 is 6.3 MB, over the 4 MiB a tile
    of the grouped matmul holds: it goes as two tiles of 384 columns
    beside all 4,096 rows (``down``: 768 rows by 2,048 of its 4,096
    columns), and every accepted width keeps its whole expert."""
    from deepspeed_tpu.moe import sharded_moe as moe

    def w(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16)

    assert moe._gmm_columns(w(360, 4096, 768)) == 384
    assert moe._gmm_columns(w(360, 768, 4096)) == 2048
    for K, N in ((2048, 768), (2560, 768), (2048, 1024)):
        assert moe._gmm_columns(w(8, K, N)) == N
        assert moe._gmm_columns(w(8, N, K)) == K
    assert moe.gmm_serves((w(36, 4096, 768), w(36, 4096, 768),
                           w(36, 768, 4096)))
    assert not moe.gmm_serves((w(36, 4096, 100),))


# a prompt's dispatch of the four sparse cells whose widths differ:
# tokens (a share's run, or the whole step), picks, width, a share
DISPATCH_RUNS = {"granite": (2048, 10, 4096, True),
                 "smallthinker": (16384, 6, 2560, False),
                 "trinity-mini": (16384, 8, 2048, False),
                 "nemotron": (4096, 6, 2688, True)}


@pytest.mark.parametrize("cell", sorted(DISPATCH_RUNS))
def test_the_rows_come_back_through_the_kernels_at_the_cells_runs(
        tpu_sharding, cell):
    """``expert_combine.rows_combine`` at a cell's run (granite's 2,048
    tokens x 10 picks x 4,096 with half the picks held elsewhere; a
    width of 20 and of 21 lane blocks; the two cells that hold every
    expert, whose launches the rule leaves to the gather, as the bench
    times them): it compiles for the chip as two
    custom calls under names that are not the grouped matmul's, and what
    the program makes besides its result is the pairs, not a gathered
    ``[k, T, H]`` beside them."""
    from deepspeed_tpu.inference.v2.kernels import expert_combine as ec

    T, k, H, share = DISPATCH_RUNS[cell]
    assert ec.rows_combine_serves(k * T, H, jnp.bfloat16, share) == share

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=tpu_sharding)

    compiled = jax.jit(lambda ys, inv, held, topv, n: ec.rows_combine(
        ys, inv, held if share else None, topv, n if share else None)).lower(
        sds((k * T, H), jnp.bfloat16), sds((k * T,), jnp.int32),
        sds((k * T,), jnp.bool_), sds((T, k), jnp.float32),
        sds((), jnp.int32)).compile()
    kernels = _custom_calls(compiled)
    assert [n.rstrip(".0123456789") for n in kernels] == [
        "moe_rows_whole", "moe_rows_combine"], kernels
    assert not any(GMM_PATTERN.search(n) for n in kernels)
    pairs = k * T // 2 * -(-H // 1024) * 1024 * 4
    assert compiled.memory_analysis().temp_size_in_bytes < pairs + 2 ** 20


def test_granites_run_lowers_with_its_five_kernels(tpu_sharding):
    """One run-dispatch of granite's share at the run PR 64 gives it
    (``paged_model.moe_share_runs``: 8,192 tokens x 10 picks, 1,137 rows
    an expert, where bytes alone gave 2,048): ``dropless_topk_dispatch``
    over the stack's 360 groups compiles for the chip as three ``gmm``
    custom calls (an expert in two column tiles, as it was) and the two
    kernels that bring the rows back, and what it makes besides its
    result stays under the sorted rows, their products and the relaid
    pairs (0.67 GB each at most)."""
    from deepspeed_tpu.inference.v2 import paged_model
    from deepspeed_tpu.inference.v2.kernels import expert_combine as ec
    from deepspeed_tpu.models.transformer import TransformerConfig
    from deepspeed_tpu.moe import sharded_moe as moe

    cfg = TransformerConfig(**_granite_fields())
    runs, T = paged_model.moe_share_runs(cfg, 16384, jnp.bfloat16)
    assert (runs, T) == (2, 8192)
    assert paged_model.moe_rows_form(cfg, 16384, jnp.bfloat16) == "kernel"
    k, H, F, E, L = cfg.moe_top_k, cfg.hidden_size, \
        cfg.moe_intermediate_size, cfg.experts_held, 10

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=tpu_sharding)

    experts = (sds((L, E, H, F)), sds((L, E, H, F)), sds((L, E, F, H)))
    assert moe.gmm_serves(experts)
    compiled = jax.jit(lambda xt, topi, topv, experts, layer:
                       moe.dropless_topk_dispatch(
                           xt, topi, topv, experts, E,
                           moe.gmm_swiglu_experts, stack_layer=layer,
                           held_from=0, rows_combine=ec.rows_combine)).lower(
        sds((T, H)), sds((T, k), jnp.int32), sds((T, k), jnp.float32),
        experts, sds((), jnp.int32)).compile()
    kernels = _custom_calls(compiled)
    assert sum(bool(GMM_PATTERN.search(n)) for n in kernels) == 3, kernels
    assert sorted(n.rstrip(".0123456789") for n in kernels
                  if not GMM_PATTERN.search(n)) == [
        "moe_rows_combine", "moe_rows_whole"], kernels
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 4 * paged_model._SHARE_RUN_BYTES


def _state_space_cut(tpu_sharding):
    """The pattern at published widths, cut to a mamba layer, the
    attention layer and a mamba layer (8 experts held, 4,096 rows of the
    vocabulary): the configuration, and its parameters and cache (33
    state slots, 129 blocks) as shapes on the chip."""
    from deepspeed_tpu.inference.v2.paged_model import init_paged_kv_cache
    from deepspeed_tpu.models import TransformerLM
    from deepspeed_tpu.models.transformer import TransformerConfig

    cfg = TransformerConfig(**{
        **_granite_fields(), "num_layers": 3, "moe_experts_held": 8,
        "layer_types": ["mamba", "attention", "mamba"], "vocab_size": 4096})

    def on_tpu(x, dtype=None):
        return jax.ShapeDtypeStruct(x.shape, dtype or x.dtype,
                                    sharding=tpu_sharding)

    params = jax.tree.map(
        lambda x: on_tpu(x, jnp.bfloat16),
        jax.eval_shape(TransformerLM(cfg).init_params,
                       jax.random.PRNGKey(0)))
    cache = jax.tree.map(on_tpu, jax.eval_shape(
        lambda: init_paged_kv_cache(cfg, 129, 16, jnp.bfloat16,
                                    state_slots=32)))
    assert cache["ssm_state"].shape == (2, 33, 64, 128, 128) \
        and cache["ssm_conv"].shape == (2, 33, 3, 66, 128) \
        and cache["k_full"].shape == (1, 129, 16, 1024)
    return cfg, params, cache


def test_the_state_space_ragged_step_runs_its_scan_in_the_kernel(
        tpu_sharding):
    """The ragged step of the cut, 32 rows in 2,048 tokens: the chunk
    kernel runs in both state-space runs, the tiled attention kernel in
    the attention layer and the grouped matmul (an expert in two column
    tiles) in every expert layer; nothing under ``ssm_scan`` loops in
    XLA."""
    from deepspeed_tpu.inference.v2.paged_model import paged_ragged_step

    cfg, params, cache = _state_space_cut(tpu_sharding)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=tpu_sharding)

    T, R = 2048, 32
    compiled = jax.jit(
        lambda p, ids, rows, pos, ln, wb, wo, bt, li, c, ss:
        paged_ragged_step(cfg, p, ids, rows, pos, ln, wb, wo, bt, li, c, 16,
                          use_kernel=True, state_slots=ss),
        donate_argnums=(9,)).lower(
        params, i32(T), i32(T), i32(T), i32(T), i32(T), i32(T), i32(R, 16),
        i32(R), cache, i32(R)).compile()
    kernels = _custom_calls(compiled)
    assert sum(k.startswith("ssm_chunk_fwd") for k in kernels) == 2, kernels
    assert sum(k.startswith("ragged_attention_tiled")
               for k in kernels) == 1, kernels
    assert sum(bool(GMM_PATTERN.search(k)) for k in kernels) == 9, kernels
    assert "ssm_scan/while" not in compiled.as_text()


def test_the_state_space_decode_window_compiles_with_its_state_in_place(
        tpu_sharding):
    """The decode window of the same cut: the state kernel and the
    convolution's kernel run in both state-space runs, the tiled
    attention kernel (its one-token form) and the grouped matmul beside
    them; nothing under ``ssm_conv`` or ``ssm_state`` gathers or
    scatters the slots in XLA, no kernel takes a leaf from the chip's
    fast memory, and the program's temporaries hold no copy of the
    state leaf (32 rows x 2 layers: 0.27 GB). What the colour does NOT
    rule (PERF.md section 7): in this program of three layers the
    compiler keeps the WINDOW's carry of the small convolution leaf
    (6.7 MB) in fast memory from step to step and moves it out and back
    once a step round the coloured launches; at the configuration's own
    depth it does so at no row count from 4 to 128 (the cells' case
    below holds 64)."""
    from deepspeed_tpu.inference.v2.paged_model import paged_decode_window

    cfg, params, cache = _state_space_cut(tpu_sharding)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=tpu_sharding)

    R = 32
    compiled = jax.jit(
        lambda p, t, pos, bt, c, sl, eos, alive, ss: paged_decode_window(
            cfg, p, t, pos, bt, c, sl, eos, 16, 8, use_kernel=True,
            alive=alive, state_slots=ss), donate_argnums=(4,)).lower(
        params, i32(R), i32(R), i32(R, 16), cache, i32(R), i32(R),
        jax.ShapeDtypeStruct((R,), jnp.bool_, sharding=tpu_sharding),
        i32(R)).compile()
    text = compiled.as_text()
    kernels = _custom_calls(compiled)
    for name, count in (("ssm_state_update", 2), ("ssm_conv_update", 2),
                        ("ragged_attention_tiled", 1)):
        assert sum(k.startswith(name) for k in kernels) == count, kernels
    assert sum(bool(GMM_PATTERN.search(k)) for k in kernels) == 9, kernels
    under = re.findall(
        r"= \S+ ([\w\-]+)\([^\n]*op_name=\"[^\"]*/ssm_(?:conv|state)/", text)
    assert under and not {"gather", "scatter"} & set(under), under
    _leaves_stay_in_hbm(text, cache, "ssm_state")
    conv = "f32[%s]" % ",".join(map(str, cache["ssm_conv"].shape))
    assert not [line for line in text.splitlines()
                if "tpu_custom_call" in line
                and re.search(re.escape(conv) + r"\{[^}]*S\(1\)", line)]
    assert compiled.memory_analysis().temp_size_in_bytes < 0.15e9


# ---------------------------------------------------------------------------
# layers of ONE sub-layer (Mamba-2 with B and C a group of heads | relu^2
# experts | attention 32 / 2 x 128):
# nemotron-3-nano-30b-a3b.rollout-128x256-384's geometry
# ---------------------------------------------------------------------------
def _nemotron_fields():
    import json
    from pathlib import Path
    return json.loads((Path(__file__).resolve().parents[3] / "benchmark"
                       / "configs/nemotron-3-nano-30b-a3b.json")
                      .read_text())["fields"]


@pytest.mark.parametrize("kept", [jnp.float32, jnp.bfloat16])
def test_the_ssm_state_kernel_at_eight_groups(tpu_sharding, kept):
    """``ssm_state_update`` at the cell's decode shape (128 rows, 64
    heads of 64 as 32 lane blocks of channels, B and C [8, 128] a token,
    a leaf of 7 layers and 129 slots, float32 and the control's
    bfloat16): a group is four lane blocks and a grid step of sixteen
    takes four groups' pairs, four rows of 128 values each, ``[128, 2,
    4, 128]``; ONE custom call under the name a trace finds, the leaf
    aliased. Nothing of the program is B or C spread over the lanes
    (``[128, 1024, 128]``: 2 x 67 MB a layer and step until PR 53, of
    which the compiler read 0.0675 GB of temporaries here): what it
    keeps beside its arguments are the rows' decay, ``dt x`` and output
    and B and C at their own size (0.5 MB each), and it reads 0; under
    0.03 GB."""
    from deepspeed_tpu.inference.v2.kernels import state_space as ss

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=tpu_sharding)

    N, nh, p, n, g = 128, 64, 64, 128, 8
    leaf = sds(ss.state_leaf_shape(7, 129, nh * p, n), kept)
    assert leaf.shape == (7, 129, 32, 128, 128) \
        and ss.state_kernel_serves(leaf, g)
    compiled = jax.jit(ss.ssm_state_update, donate_argnums=(0,)).lower(
        leaf, sds((), jnp.int32), sds((N,), jnp.int32), sds((N,), jnp.bool_),
        sds((N, nh * p)), sds((N, nh)), sds((nh,)), sds((N, g * n)),
        sds((N, g * n))).compile()
    kernels = _custom_calls(compiled)
    assert len(kernels) == 1 and kernels[0].startswith("ssm_state_update")
    assert f"f32[{N},2,4,{n}]" in compiled.as_text()
    assert f"f32[{N},{g * n},128]" not in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 0.03e9


def test_the_ssm_chunk_kernel_at_eight_groups(tpu_sharding):
    """``ssm_chunk_fwd`` for the cell's ragged launch (128 rows of 128
    tokens, x and the 8 groups' B and C the ONE bf16 buffer the
    convolution leaves, 6,144 wide): a grid step (four lane blocks) is
    one group and takes its B and C where they lie: ONE custom call, the
    leaf aliased, no slice of the token buffer (no temporary)."""
    from deepspeed_tpu.inference.v2.kernels import state_space as ss

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=tpu_sharding)

    R, T, nh, p, n, g = 128, 128 * 128, 64, 64, 128, 8
    leaf = sds(ss.state_leaf_shape(7, 129, nh * p, n))
    assert ss.chunk_kernel_serves(leaf, p, g)
    assert not ss.chunk_kernel_serves(leaf, p, 16)      # half a grid step
    compiled = jax.jit(ss.ssm_chunk_fwd, donate_argnums=(0,)).lower(
        leaf, sds((), jnp.int32), sds((R,), jnp.int32), sds((R,), jnp.bool_),
        sds((R,), jnp.int32), sds((R,), jnp.int32),
        sds((T, nh * p + 2 * g * n), jnp.bfloat16), sds((T, nh)),
        sds((nh,))).compile()
    kernels = _custom_calls(compiled)
    assert len(kernels) == 1 and kernels[0].startswith("ssm_chunk_fwd")
    assert compiled.memory_analysis().temp_size_in_bytes < 0.05e9


# sha256 (16 hex) of the jaxprs of granite's one-group kernel calls at its
# published shapes, kernel bodies and index maps included, addresses struck
# out, read by the test below: the chunk kernel's on PR 52's parent commit
# (e16ef18), the one-token kernel's on PR 57's tree, which coloured its
# leaf HBM and changed nothing else (0c608753769a2afb before, PR 53's, which
# changed it on purpose: B and C a row a group, spread in VMEM; the two
# jaxprs differ by the ``with_memory_space_constraint`` equation ahead of
# the call and ``float32<hbm>`` in its ``out_avals``, variables' names apart).
# The lowered TEXT carries the kernels' source lines (Mosaic's payload
# embeds them), so it moves with every edit of the file; the jaxpr is what
# is lowered
GRANITE_KERNEL_JAXPRS = {"ssm_state_update": "bec48aa2c196eb88",
                         "ssm_chunk_fwd": "9457ed25af6cccce"}


@pytest.mark.parametrize("kernel", sorted(GRANITE_KERNEL_JAXPRS))
def test_granites_one_group_calls_are_the_kernels_they_were(kernel):
    import hashlib
    from deepspeed_tpu.inference.v2.kernels import state_space as ss

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype)

    N, nh, p, n = 64, 128, 64, 128
    leaf = sds(ss.state_leaf_shape(9, 65, nh * p, n))
    if kernel == "ssm_state_update":
        jaxpr = jax.make_jaxpr(ss.ssm_state_update)(
            leaf, sds((), jnp.int32), sds((N,), jnp.int32),
            sds((N,), jnp.bool_), sds((N, nh * p)), sds((N, nh)), sds((nh,)),
            sds((N, n)), sds((N, n)))
    else:
        R, T = 64, 64 * 256
        jaxpr = jax.make_jaxpr(ss.ssm_chunk_fwd)(
            leaf, sds((), jnp.int32), sds((R,), jnp.int32),
            sds((R,), jnp.bool_), sds((R,), jnp.int32), sds((R,), jnp.int32),
            sds((T, nh * p + 2 * n), jnp.bfloat16), sds((T, nh)),
            sds((nh,)))
    text = re.sub(r"0x[0-9a-f]+", "0x", str(jaxpr))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == GRANITE_KERNEL_JAXPRS[kernel]


@pytest.mark.parametrize("rows", [768, 24576])
def test_the_relu2_grouped_matmul_at_published_widths(tpu_sharding, rows):
    """The two-matrix expert at 64 experts of 2,688 x 1,856 a layer (7
    layers' stack, read in place), a decode step's 768 picks and a run
    of 4,096 prompt tokens' 24,576: an expert is 10 MB, over the 4 MiB
    tile, and goes as three tiles of 896 of its 2,688 columns beside all
    1,856 rows (1,856 is 14.5 lane blocks: it is never the last axis of
    a tile, ``up`` being stored out x in); two ``gmm`` custom calls and
    NO copy of an expert stack (4.5 GB: the first build's [.., 2,688,
    1,856] leaf was laid out with 2,688 last by the compiler and copied
    every launch)."""
    from deepspeed_tpu.moe import sharded_moe as moe

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=tpu_sharding)

    L, E, H, F = 7, 64, 2688, 1856
    wu, wd = sds((L * E, F, H)), sds((L * E, F, H))
    assert moe.gmm_serves((wu, wd))
    assert moe._gmm_columns(wu) == moe._gmm_columns(wd) == 896
    assert not moe.gmm_serves((sds((L * E, H, F)), wd))
    compiled = jax.jit(moe.gmm_relu2_experts).lower(
        (wu, wd), sds((rows, H)), sds((L * E,), jnp.int32)).compile()
    kernels = _custom_calls(compiled)
    assert len(kernels) == 2 and all(GMM_PATTERN.search(k) for k in kernels)
    assert compiled.memory_analysis().temp_size_in_bytes < 0.2e9


@pytest.mark.parametrize("launch,T,one_token,queries", [
    ("prompt", 16384, False, "bf16[2,16,16384,128]"),
    ("decode", 128, True, "bf16[128,2,16,128]")])
def test_the_tiled_kernel_at_group_sixteen(tpu_sharding, launch, T,
                                           one_token, queries):
    """32 query heads on 2 key / value heads of 128 (group 16: the
    widest an accepted cell ran was 8) over a pool row of 256 lanes, 128
    rows of 640 positions, two layers: the cell's ragged step and its
    decode step (the one-token form), each ONE tiled launch under the
    name the share readers find, nothing as large as a layer of the
    pool beside it."""
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=tpu_sharding)

    assert kernel_variant(128, 2, False) == "tiled"
    pool = sds((2, 5249, 16, 256), jnp.bfloat16)
    args = [sds((T, 32, 128), jnp.bfloat16), pool, pool, sds((), jnp.int32),
            sds((T,), jnp.int32), sds((T,), jnp.int32),
            sds((128, 40), jnp.int32)]
    compiled = jax.jit(lambda *a: ragged_attention(
        *a, one_token=one_token)).lower(*args).compile()
    calls = [line for line in compiled.as_text().splitlines()
             if "tpu_custom_call" in line]
    assert len(calls) == 1, calls
    assert re.match(r"\s*%ragged_attention_tiled[_.0-9]* = ", calls[0])
    assert queries in calls[0], calls[0][:400]
    layer = 2 * 5249 * 16 * 256
    assert compiled.memory_analysis().temp_size_in_bytes < max(
        layer / 4, 8 * T * 32 * 128)


def _one_sublayer_cut(tpu_sharding):
    """The pattern at published widths, cut to its first six layers
    (mamba, experts, mamba, experts, mamba, attention; 8 experts held,
    4,096 rows of the vocabulary): the configuration, and its parameters
    and cache (33 state slots, 129 blocks) as shapes on the chip."""
    from deepspeed_tpu.inference.v2.paged_model import init_paged_kv_cache
    from deepspeed_tpu.models import TransformerLM
    from deepspeed_tpu.models.transformer import TransformerConfig

    fields = _nemotron_fields()
    cfg = TransformerConfig(**{
        **fields, "num_layers": 6, "moe_experts_held": 8,
        "layer_types": fields["layer_types"][:6], "vocab_size": 4096})

    def on_tpu(x, dtype=None):
        return jax.ShapeDtypeStruct(x.shape, dtype or x.dtype,
                                    sharding=tpu_sharding)

    params = jax.tree.map(
        lambda x: on_tpu(x, jnp.bfloat16),
        jax.eval_shape(TransformerLM(cfg).init_params,
                       jax.random.PRNGKey(0)))
    cache = jax.tree.map(on_tpu, jax.eval_shape(
        lambda: init_paged_kv_cache(cfg, 129, 16, jnp.bfloat16,
                                    state_slots=32)))
    assert cache["ssm_state"].shape == (3, 33, 32, 128, 128) \
        and cache["ssm_conv"].shape == (3, 33, 3, 48, 128) \
        and cache["k_full"].shape == (1, 129, 16, 256)
    return cfg, params, cache


def test_the_one_sublayer_ragged_step_runs_its_kernels(tpu_sharding):
    """The ragged step of the cut, 32 rows in 2,048 tokens: six runs of
    one layer; the chunk kernel in the three mamba layers, two grouped
    matmuls in each of the two expert layers (none behind a mixer), the
    tiled attention kernel once; nothing under ``ssm_scan`` loops in
    XLA."""
    from deepspeed_tpu.inference.v2.paged_model import paged_ragged_step

    cfg, params, cache = _one_sublayer_cut(tpu_sharding)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=tpu_sharding)

    T, R = 2048, 32
    compiled = jax.jit(
        lambda p, ids, rows, pos, ln, wb, wo, bt, li, c, ss:
        paged_ragged_step(cfg, p, ids, rows, pos, ln, wb, wo, bt, li, c, 16,
                          use_kernel=True, state_slots=ss),
        donate_argnums=(9,)).lower(
        params, i32(T), i32(T), i32(T), i32(T), i32(T), i32(T), i32(R, 16),
        i32(R), cache, i32(R)).compile()
    kernels = _custom_calls(compiled)
    assert sum(k.startswith("ssm_chunk_fwd") for k in kernels) == 3, kernels
    assert sum(k.startswith("ragged_attention_tiled")
               for k in kernels) == 1, kernels
    assert sum(bool(GMM_PATTERN.search(k)) for k in kernels) == 4, kernels
    assert "ssm_scan/while" not in compiled.as_text()


def test_the_one_sublayer_decode_window_compiles_with_its_state_in_place(
        tpu_sharding):
    """The decode window of the same cut: the state kernel and the
    convolution's kernel in the three mamba layers, the tiled attention
    kernel (its one-token form) and four grouped matmuls beside them;
    nothing under ``ssm_conv`` or ``ssm_state`` gathers or scatters the
    slots in XLA or copies a leaf (``copy`` under ``ssm_conv`` was
    allowed until PR 57: the compiler's move of the convolution's leaf
    into fast memory and back), neither leaf is laid in the chip's fast
    memory, and the program's temporaries hold no copy of the state
    leaf (32 rows x 3 layers: 0.2 GB) or of an expert stack."""
    from deepspeed_tpu.inference.v2.paged_model import paged_decode_window

    cfg, params, cache = _one_sublayer_cut(tpu_sharding)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=tpu_sharding)

    R = 32
    compiled = jax.jit(
        lambda p, t, pos, bt, c, sl, eos, alive, ss: paged_decode_window(
            cfg, p, t, pos, bt, c, sl, eos, 16, 8, use_kernel=True,
            alive=alive, state_slots=ss), donate_argnums=(4,)).lower(
        params, i32(R), i32(R), i32(R, 16), cache, i32(R), i32(R),
        jax.ShapeDtypeStruct((R,), jnp.bool_, sharding=tpu_sharding),
        i32(R)).compile()
    text = compiled.as_text()
    kernels = _custom_calls(compiled)
    for name, count in (("ssm_state_update", 3), ("ssm_conv_update", 3),
                        ("ragged_attention_tiled", 1)):
        assert sum(k.startswith(name) for k in kernels) == count, kernels
    assert sum(bool(GMM_PATTERN.search(k)) for k in kernels) == 4, kernels
    under = re.findall(
        r"= \S+ ([\w\-]+)\([^\n]*op_name=\"[^\"]*/ssm_(?:conv|state)/", text)
    assert under and not {"gather", "scatter", "copy", "copy-start",
                          "copy-done"} & set(under), under
    _leaves_stay_in_hbm(text, cache, "ssm_conv", "ssm_state")
    assert compiled.memory_analysis().temp_size_in_bytes < 0.15e9


# ---------------------------------------------------------------------------
# power-retention layers (a state of 65 x 128 x 128 a key/value head, five
# query heads a group, no position cached):
# brumby-14b-base.rollout-16x2048-256's geometry
# ---------------------------------------------------------------------------
def _brumby_fields():
    import json
    from pathlib import Path
    return json.loads((Path(__file__).resolve().parents[3] / "benchmark"
                       / "configs/brumby-14b-base.json").read_text())["fields"]


def _retention_leaves(sds, kept=jnp.float32, layers=8, slots=17):
    from deepspeed_tpu.inference.v2.kernels import power_retention as pr
    state, norm = pr.leaf_shapes(layers, slots, 8, 128)
    assert state == (layers, slots, 8, 65, 128, 128) \
        and norm == (layers, slots, 8, 72, 128)
    return sds(state, kept), sds(norm, kept)


@pytest.mark.parametrize("kept", [jnp.float32, jnp.bfloat16])
def test_the_retention_state_kernel_at_published_widths(tpu_sharding, kept):
    """``retention_state_update`` at the cell's decode shape (16 rows,
    40 query heads on 8 key/value heads of 128, leaves of 8 layers and
    17 slots, float32 and the control's bfloat16): it compiles for the
    chip, runs as ONE custom call under a name a trace finds, and both
    leaves are aliased (no copy of 4.67 GB: no temporary at all)."""
    from deepspeed_tpu.inference.v2.kernels import power_retention as pr

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=tpu_sharding)

    N, nh, nkv, hd = 16, 40, 8, 128
    state, norm = _retention_leaves(sds, kept)
    assert pr.state_kernel_serves(state) and pr.chunk_kernel_serves(state)
    assert not pr.state_kernel_serves(sds((2, 5, 2, 9, 16, 16)))
    compiled = jax.jit(lambda *a: pr.retention_state_update(*a, 1e-6),
                       donate_argnums=(0, 1)).lower(
        state, norm, sds((), jnp.int32), sds((N,), jnp.int32),
        sds((N,), jnp.bool_), sds((N, nh, hd)), sds((N, nkv, hd)),
        sds((N, nkv, hd)), sds((N, nkv))).compile()
    kernels = _custom_calls(compiled)
    assert len(kernels) == 1 \
        and kernels[0].startswith("retention_state_update")
    assert compiled.memory_analysis().temp_size_in_bytes < 0.01e9


@pytest.mark.parametrize("kept", [jnp.float32, jnp.bfloat16])
def test_the_retention_chunk_kernel_at_published_widths(tpu_sharding, kept):
    """``retention_chunk_fwd`` for the cell's ragged launch (16 rows of
    512 tokens, q, k and v float32 as the projections leave them; leaves
    in float32 and in the control's bfloat16, which the first build did
    not lower: a distance's row of a bfloat16 normaliser): it
    compiles for the chip, runs as ONE custom call under a name a trace
    finds, both leaves are aliased, and what it keeps beside its
    arguments is the output and the gate on every lane (0.17 + 0.03
    GB)."""
    from deepspeed_tpu.inference.v2.kernels import power_retention as pr

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=tpu_sharding)

    R, T, nh, nkv, hd = 16, 8192, 40, 8, 128
    state, norm = _retention_leaves(sds, kept)
    compiled = jax.jit(lambda *a: pr.retention_chunk_fwd(*a, 1e-6),
                       donate_argnums=(0, 1)).lower(
        state, norm, sds((), jnp.int32), sds((R,), jnp.int32),
        sds((R,), jnp.bool_), sds((R,), jnp.int32), sds((R,), jnp.int32),
        sds((T, nh, hd)), sds((T, nkv, hd)), sds((T, nkv, hd)),
        sds((T, nkv))).compile()
    kernels = _custom_calls(compiled)
    assert len(kernels) == 1 and kernels[0].startswith("retention_chunk_fwd")
    assert compiled.memory_analysis().temp_size_in_bytes < 0.3e9


def _retention_cut(tpu_sharding):
    """The block at published widths, cut to two layers and 4,096 rows
    of the vocabulary: the configuration, and its parameters and cache
    (16 state slots, NO pool) as shapes on the chip."""
    from deepspeed_tpu.inference.v2.paged_model import init_paged_kv_cache
    from deepspeed_tpu.models import TransformerLM
    from deepspeed_tpu.models.transformer import TransformerConfig

    cfg = TransformerConfig(**{
        **_brumby_fields(), "num_layers": 2, "vocab_size": 4096,
        "layer_types": ["power_retention"] * 2})

    def on_tpu(x, dtype=None):
        return jax.ShapeDtypeStruct(x.shape, dtype or x.dtype,
                                    sharding=tpu_sharding)

    params = jax.tree.map(
        lambda x: on_tpu(x, jnp.bfloat16),
        jax.eval_shape(TransformerLM(cfg).init_params,
                       jax.random.PRNGKey(0)))
    cache = jax.tree.map(on_tpu, jax.eval_shape(
        lambda: init_paged_kv_cache(cfg, 2, 16, jnp.bfloat16,
                                    state_slots=16)))
    assert set(cache) == {"retention_state", "retention_norm"}
    assert cache["retention_state"].shape == (2, 17, 8, 65, 128, 128) \
        and cache["retention_state"].dtype == jnp.float32
    return cfg, params, cache


def test_the_retention_ragged_step_runs_its_chunks_in_the_kernel(
        tpu_sharding):
    """The ragged step of the cut, 16 rows in 2,048 tokens, a block
    table ONE null entry wide: the chunk kernel runs in the one run of
    layers, no attention kernel does, and nothing under
    ``retention_chunk`` loops in XLA."""
    from deepspeed_tpu.inference.v2.paged_model import paged_ragged_step

    cfg, params, cache = _retention_cut(tpu_sharding)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=tpu_sharding)

    T, R = 2048, 16
    compiled = jax.jit(
        lambda p, ids, rows, pos, ln, wb, wo, bt, li, c, ss:
        paged_ragged_step(cfg, p, ids, rows, pos, ln, wb, wo, bt, li, c, 16,
                          use_kernel=True, state_slots=ss),
        donate_argnums=(9,)).lower(
        params, i32(T), i32(T), i32(T), i32(T), i32(T), i32(T), i32(R, 1),
        i32(R), cache, i32(R)).compile()
    kernels = _custom_calls(compiled)
    assert len(kernels) == 1 and kernels[0].startswith(
        "retention_chunk_fwd"), kernels
    assert "retention_chunk/while" not in compiled.as_text()


def test_the_retention_decode_window_compiles_with_its_state_in_place(
        tpu_sharding):
    """The decode window of the same cut: the state kernel runs in the
    one run of layers and nothing else is a kernel; nothing under
    ``retention_state`` gathers or scatters the slots in XLA, neither
    leaf is laid in the chip's fast memory or copied, and the program's
    temporaries hold no copy of a state leaf (16 rows x 2 layers: 1.1
    GB)."""
    from deepspeed_tpu.inference.v2.paged_model import paged_decode_window

    cfg, params, cache = _retention_cut(tpu_sharding)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=tpu_sharding)

    R = 16
    compiled = jax.jit(
        lambda p, t, pos, bt, c, sl, eos, alive, ss: paged_decode_window(
            cfg, p, t, pos, bt, c, sl, eos, 16, 8, use_kernel=True,
            alive=alive, state_slots=ss), donate_argnums=(4,)).lower(
        params, i32(R), i32(R), i32(R, 1), cache, i32(R), i32(R),
        jax.ShapeDtypeStruct((R,), jnp.bool_, sharding=tpu_sharding),
        i32(R)).compile()
    text = compiled.as_text()
    kernels = _custom_calls(compiled)
    assert len(kernels) == 1 and kernels[0].startswith(
        "retention_state_update"), kernels
    under = re.findall(
        r"= \S+ ([\w\-]+)\([^\n]*op_name=\"[^\"]*/retention_state/", text)
    assert under and not {"gather", "scatter"} & set(under), under
    _leaves_stay_in_hbm(text, cache, "retention_state", "retention_norm")
    assert compiled.memory_analysis().temp_size_in_bytes < 0.3e9


# ---------------------------------------------------------------------------
# the recurrent cells' decode windows at their OWN cache shapes: a slot
# leaf that a kernel updates in place stays in HBM (PR 57)
# ---------------------------------------------------------------------------
# cell -> (the leaves its one-token kernels update in place, their custom
# calls a decode step: nemotron's seven mamba layers are seven runs of one,
# granite's nine two scanned runs, ling's seven three, brumby's eight one)
IN_PLACE_CELLS = {
    "nemotron-3-nano-30b-a3b.rollout-128x256-384": (
        ("ssm_conv", "ssm_state"),
        {"ssm_conv_update": 7, "ssm_state_update": 7}),
    "granite-4.0-h-small.rollout-64x1024-256": (
        ("ssm_conv", "ssm_state"),
        {"ssm_conv_update": 2, "ssm_state_update": 2}),
    "brumby-14b-base.rollout-16x2048-256": (
        ("retention_norm", "retention_state"),
        {"retention_state_update": 1}),
    "ling-3.0-flash.rollout-128x256": (
        ("kda_conv", "kda_state"),
        {"kda_conv_update": 3, "kda_state_update": 3}),
}


@pytest.mark.parametrize("cell", sorted(IN_PLACE_CELLS))
def test_a_cells_decode_window_keeps_its_slot_leaves_in_hbm(tpu_sharding,
                                                            cell):
    """The decode window of a cell with recurrent layers, at the
    configuration's ``fields`` and the cell's own rows, state slots,
    blocks and table width (``benchmark/workloads/<cell>.json``; the
    parameters and the cache as shapes): it compiles for the chip, every
    one-token kernel runs under its name, and NO leaf that one of them
    updates in place is laid in fast memory or copied whole. Until PR 57
    the compiler carried nemotron's convolution leaf ``[7, 129, 3, 48,
    128]`` (66.6 MB) into fast memory and back round six of its seven
    launches a step, granite's ``[9, 65, 3, 66, 128]`` (59.3 MB) round
    both of its call sites, and brumby's normaliser ``[8, 17, 8, 72,
    128]`` (40.1 MB) round every launch; ling's ``[7, 129, 3, 96, 128]``
    (133 MB) never fitted, and the case holds that the colour lost
    nothing there."""
    import json
    from pathlib import Path

    from deepspeed_tpu.inference.v2.paged_model import (
        init_paged_kv_cache, paged_decode_window)
    from deepspeed_tpu.models import TransformerLM
    from deepspeed_tpu.models.transformer import TransformerConfig

    bench = Path(__file__).resolve().parents[3] / "benchmark"
    work = json.loads((bench / "workloads" / f"{cell}.json").read_text())
    sm = work["engine"]["state_manager"]
    cfg = TransformerConfig(**json.loads(
        (bench / "configs" / f"{work['config']}.json").read_text())["fields"])
    rows, bs = sm["max_tracked_sequences"], sm["block_size"]
    # a model that caches no position has a table one null entry wide
    pages = -(-sm["max_seq_len"] // bs) if cfg.caches_positions else 1

    def on_tpu(x, dtype=None):
        return jax.ShapeDtypeStruct(x.shape, dtype or x.dtype,
                                    sharding=tpu_sharding)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=tpu_sharding)

    params = jax.tree.map(
        lambda x: on_tpu(x, jnp.bfloat16),
        jax.eval_shape(TransformerLM(cfg).init_params,
                       jax.random.PRNGKey(0)))
    cache = jax.tree.map(on_tpu, jax.eval_shape(
        lambda: init_paged_kv_cache(cfg, sm["num_blocks"], bs, jnp.bfloat16,
                                    state_slots=rows)))
    leaves, launches = IN_PLACE_CELLS[cell]
    assert all(cache[k].shape[1] == rows + 1 for k in leaves)
    compiled = jax.jit(
        lambda p, t, pos, bt, c, sl, eos, alive, ss: paged_decode_window(
            cfg, p, t, pos, bt, c, sl, eos, bs, 8, use_kernel=True,
            alive=alive, state_slots=ss), donate_argnums=(4,)).lower(
        params, i32(rows), i32(rows), i32(rows, pages), cache, i32(rows),
        i32(rows),
        jax.ShapeDtypeStruct((rows,), jnp.bool_, sharding=tpu_sharding),
        i32(rows)).compile()
    kernels = _custom_calls(compiled)
    for name, count in launches.items():
        assert sum(k.startswith(name) for k in kernels) == count, kernels
    _leaves_stay_in_hbm(compiled.as_text(), cache, *leaves)


# ---------------------------------------------------------------------------
# ZeRO-3 at dp 4: what the scanned backward moves between chips
# ---------------------------------------------------------------------------
ZERO3_MICRO, ZERO3_DP, ZERO3_HIDDEN, ZERO3_FFN = 2, 4, 256, 1024
_COLLECTIVE = re.compile(
    r"= (\w+)\[([\d,]*)\]\S* (all-to-all|all-gather)(?:-start)?\("
    r"[^\n]*op_name=\"([^\"]*)\"")


@pytest.fixture(scope="module")
def zero3_backward(tpu_sharding):
    """The benchmark's four-chip cell at toy widths (OPT's norm,
    activation and biases; hidden 256, ffn 1024, 4 layers, micro 2 x
    seq 256), its step compiled for the described ``v5e:2x2`` twice: as
    the engine builds it, and with the gather-on-use withheld from the
    model. Each: the collectives under the layers' backward as (kind,
    dims), and what the engine counted."""
    import json
    from pathlib import Path
    from deepspeed_tpu.accelerator.tpu_accelerator import (
        COLLECTIVE_OVERLAP_COMPILER_OPTIONS)
    from deepspeed_tpu.benchmarks.aot_scale import build_abstract_engine
    from deepspeed_tpu.models.transformer import TransformerConfig
    from deepspeed_tpu.runtime.activation_checkpointing import checkpointing

    bench = Path(__file__).resolve().parents[3] / "benchmark"
    fields = json.loads((bench / "configs/opt-1.3b.json").read_text())["fields"]
    ds = json.loads((bench / "workloads/opt-1.3b.zero3-dp4.json")
                    .read_text())["deepspeed"]
    ds["train_micro_batch_size_per_gpu"] = ZERO3_MICRO
    cfg = TransformerConfig(**{
        **fields, "hidden_size": ZERO3_HIDDEN, "num_heads": 4,
        "intermediate_size": ZERO3_FFN, "num_layers": 4, "max_seq_len": 256,
        "vocab_size": 2048})
    out = {}
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        try:
            for case in ("engaged", "withheld"):
                engine, batch = build_abstract_engine(
                    cfg, ds, topology_name="v5e:2x2")
                counted = (engine.gather_on_use_leaves,
                           engine.gather_on_use_layer_bytes)
                if case == "withheld":
                    engine.model.layer_param_gather = None
                text = engine.lower_train_step(
                    batch,
                    compiler_options=COLLECTIVE_OVERLAP_COMPILER_OPTIONS
                ).as_text()
                moved = [(kind, tuple(int(d) for d in dims.split(",")))
                         for _dt, dims, kind, op in _COLLECTIVE.findall(text)
                         if "transpose(jvp(layers))" in op]
                out[case] = {"moved": moved, "counted": counted}
        finally:
            checkpointing.reset()
    return out


def _leads_with_the_global_batch(moved):
    return [dims for kind, dims in moved if kind == "all-gather"
            and dims[0] == ZERO3_MICRO * ZERO3_DP and len(dims) == 3]


@pytest.mark.parametrize("case", ["engaged", "withheld"])
def test_zero3_backward_gathers_the_weight_not_the_batch(zero3_backward,
                                                         case):
    """With the function, no matmul of the layers' backward sees a
    sharded weight: no ``all-to-all``, no ``all-gather`` of an activation
    over the GLOBAL batch, and ``w_down`` is gathered whole (``[ffn,
    hidden]``). Withheld, the partitioner runs the down projection's
    backward tensor-parallel over the ZeRO shards: the cotangent gathered
    over the global batch and an all-to-all back, which is what makes
    the first case mean something on the compiler that runs it."""
    got = zero3_backward[case]
    moved = got["moved"]
    assert got["counted"] == (16, 2 * (4 * ZERO3_HIDDEN ** 2
                                       + 2 * ZERO3_HIDDEN * ZERO3_FFN
                                       + 9 * ZERO3_HIDDEN + ZERO3_FFN))
    kinds = {kind for kind, _ in moved}
    w_down = ("all-gather", (ZERO3_FFN, ZERO3_HIDDEN))
    if case == "engaged":
        assert "all-to-all" not in kinds, moved
        assert not _leads_with_the_global_batch(moved), moved
        assert w_down in moved, moved
    else:
        assert sum(kind == "all-to-all" for kind, _ in moved) == 2, moved
        assert _leads_with_the_global_batch(moved), moved
        assert w_down not in moved, moved


# ---------------------------------------------------------------------------
# PR 58: a query group of SEVEN (28 heads on 4 of 128), a window of 4,096
# over rings of 321 pages, the router ahead of the mixer:
# smallthinker-21ba3b-instruct.rollout-16x8192-512's geometry
# ---------------------------------------------------------------------------
# launch -> (tokens, the pool as stored, the table's pages, window, one
# token a row, the launch's name)
GROUP7_LAUNCHES = {
    "prefill.full": (16384, (2, 8721, 16, 512), 544, 0, False,
                     "ragged_attention_tiled"),
    "prefill.window": (16384, (6, 5137, 16, 512), 321, 4096, False,
                       "ragged_attention_window"),
    "decode.full": (16, (2, 8721, 16, 512), 544, 0, True,
                    "ragged_attention_tiled"),
    "decode.window": (16, (6, 5137, 16, 512), 321, 4096, True,
                      "ragged_attention_window"),
}


@pytest.mark.parametrize("launch", sorted(GROUP7_LAUNCHES))
def test_the_tiled_kernel_at_group_seven(tpu_sharding, launch):
    """Every per-head geometry served before is a power of two; here a
    kv head's SEVEN query heads. Mosaic takes the token tile (an output
    block ``(tq, nblk, 7, 128)``) and the one-token form (a lane block's
    seven query rows padded to one sublane tile of eight: the queries
    ``[16, 4, 8, 128]``, the output ``[16, 4, 7, 128]``), full and over
    a ring with the window, under the names the roofline's reader finds,
    with nothing as large as a layer of the pool beside the call. The
    kernel needed no change for it."""
    T, pool, MB, window, one_token, name = GROUP7_LAUNCHES[launch]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=tpu_sharding)

    assert kernel_variant(128, 4, False) == "tiled"
    args = [sds((T, 28, 128), jnp.bfloat16), sds(pool, jnp.bfloat16),
            sds(pool, jnp.bfloat16), sds((), jnp.int32),
            sds((T,), jnp.int32), sds((T,), jnp.int32),
            sds((16, MB), jnp.int32)]
    compiled = jax.jit(lambda *a: ragged_attention(
        *a, window=window, one_token=one_token)).lower(*args).compile()
    calls = [line for line in compiled.as_text().splitlines()
             if "tpu_custom_call" in line]
    assert len(calls) == 1, calls
    assert re.match(r"\s*%" + name + r"[_.0-9]* = ", calls[0]), calls[0][:200]
    assert WINDOW_PATTERN.search(
        re.match(r"\s*%([\w.\-]+) = ", calls[0]).group(1))
    if one_token:
        assert "= bf16[16,4,7,128]" in calls[0] \
            and "bf16[16,4,8,128]" in calls[0], calls[0][:600]
    layer = 2 * pool[1] * pool[2] * pool[3]
    assert compiled.memory_analysis().temp_size_in_bytes < layer / 4


@pytest.fixture(scope="module")
def group7_programs(tpu_sharding):
    """The cell's two programs at published widths, cut to two layers
    (published layers 3-4: sliding, FULL) with 8 experts and 4,096 rows
    of the vocabulary, over the cell's pools (16 rows' blocks and rings
    of 321 pages), compiled under the chip's flags."""
    import json
    from pathlib import Path
    from deepspeed_tpu.accelerator.tpu_accelerator import \
        COLLECTIVE_OVERLAP_COMPILER_OPTIONS
    from deepspeed_tpu.inference.v2.paged_model import (
        init_paged_kv_cache, paged_decode_window, paged_ragged_step)
    from deepspeed_tpu.models import TransformerLM
    from deepspeed_tpu.models.transformer import TransformerConfig

    fields = json.loads((
        Path(__file__).resolve().parents[3] / "benchmark/configs"
        / "smallthinker-21ba3b-instruct.json").read_text())["fields"]
    cfg = TransformerConfig(**{
        **fields, "num_layers": 2, "layer_types": fields["layer_types"][2:4],
        "moe_num_experts": 8, "vocab_size": 4096})
    assert cfg.layer_kinds == ("window", "full") and cfg.moe_router_ahead

    def on_tpu(x, dtype=None):
        return jax.ShapeDtypeStruct(x.shape, dtype or x.dtype,
                                    sharding=tpu_sharding)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=tpu_sharding)

    params = jax.tree.map(
        lambda x: on_tpu(x, jnp.bfloat16),
        jax.eval_shape(TransformerLM(cfg).init_params,
                       jax.random.PRNGKey(0)))
    cache = jax.tree.map(on_tpu, jax.eval_shape(
        lambda: init_paged_kv_cache(cfg, 16 * 545 + 1, 16, jnp.bfloat16,
                                    window_blocks=16 * 321 + 1)))
    assert cache["k_full"].shape == (1, 8721, 16, 512) \
        and cache["k_window"].shape == (1, 5137, 16, 512)
    R, T = 16, 16384
    programs = {
        "decode_window": (jax.jit(
            lambda p, t, pos, bt, c, sl, eos, alive, wt: paged_decode_window(
                cfg, p, t, pos, bt, c, sl, eos, 16, 8, use_kernel=True,
                alive=alive, window_tables=wt), donate_argnums=(4,)), (
            params, i32(R), i32(R), i32(R, 544), cache, i32(R), i32(R),
            jax.ShapeDtypeStruct((R,), jnp.bool_, sharding=tpu_sharding),
            i32(R, 321))),
        "ragged_step": (jax.jit(
            lambda p, ids, rows, pos, ln, wb, wo, bt, li, c, wt:
            paged_ragged_step(cfg, p, ids, rows, pos, ln, wb, wo, bt, li, c,
                              16, use_kernel=True, window_tables=wt),
            donate_argnums=(9,)), (
            params, i32(T), i32(T), i32(T), i32(T), i32(T), i32(T),
            i32(R, 544), i32(R), cache, i32(R, 321)))}
    pool = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache))
    # ``_trace_for_tpu`` answers for the target a test at a time; a
    # module's fixture is set up outside it
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "default_backend", lambda: "tpu")
        return pool, {
            name: fn.lower(*args).compile(
                compiler_options=dict(COLLECTIVE_OVERLAP_COMPILER_OPTIONS))
            for name, (fn, args) in programs.items()}


@pytest.mark.parametrize("program", ["decode_window", "ragged_step"])
def test_the_router_ahead_programs_compile_with_both_pools_in_place(
        group7_programs, program):
    """The window kernel runs in the sliding layer, the full kernel in
    the full one, the grouped matmul three times a layer (the ReGLU
    experts are the accepted ``gmm``), the donated pools are aliased to
    the result, no instruction copies or relays a pool or a layer of
    one, and the program's temporaries hold no copy of either (the
    decode window's are under 0.1 GB; the 16,384-token step's 1.36 GB
    here are its rows' float32 stream and six sorted picks a token, what
    the whole cell's are: 1.33 GB by the same analysis at eight layers,
    64 experts and the whole vocabulary, beside 9.52 GB of arguments:
    PERF.md section 4). The router's operations stand under
    ``moe_router`` and under no ``mlp``, where the router behind
    stands."""
    pool, compiled = group7_programs
    compiled = compiled[program]
    text = compiled.as_text()
    kernels = re.findall(r"%([\w.\-]+) = [^\n]*tpu_custom_call", text)
    assert sum(k.startswith("ragged_attention_window") for k in kernels) \
        == 1, kernels
    assert sum(k.startswith("ragged_attention_tiled") for k in kernels) \
        == 1, kernels
    assert sum(bool(GMM_PATTERN.search(k)) for k in kernels) == 6, kernels
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool, mem
    assert mem.temp_size_in_bytes < (0.1e9 if program == "decode_window"
                                     else 1.5e9), mem
    _no_relayout_of(text, 8721, 5137)
    routed = set(re.findall(r'op_name="[^"]*?((?:mlp/)?moe_router/'
                            r'(?:dot_general|top_k))"', text))
    assert routed and not any(r.startswith("mlp/") for r in routed), routed


# ---------------------------------------------------------------------------
# a layer of TWO mixers on one norm (Mamba-2 with a state of 256 in two
# groups and heads of 128, beside GQA 20 / 4 x 128):
# falcon-h1-34b-instruct.rollout-64x1024-512's geometry
# ---------------------------------------------------------------------------
def _falcon_fields():
    import json
    from pathlib import Path
    return json.loads((Path(__file__).resolve().parents[3] / "benchmark"
                       / "configs/falcon-h1-34b-instruct.json")
                      .read_text())["fields"]


@pytest.mark.parametrize("kernel", ["ssm_state_update", "ssm_chunk_fwd",
                                    "ssm_conv_update"])
def test_the_state_space_kernels_at_a_state_of_256_in_two_groups(
        tpu_sharding, kernel):
    """The three kernels at the cell's shapes: a leaf of 4 layers and 65
    slots of 32 lane blocks of a state of 256 (a decode grid step takes
    8 of them, 1 MB, where 16 would be 8 MB double-buffered in and out),
    64 rows a token each and 64 rows of 256 prompt tokens with x, B and
    C one bf16 buffer 5,120 wide (a head IS a lane block: its decay is
    spread over the lanes one axis at a time, which Mosaic asks), and
    the convolution's 40 lane blocks: each compiles for the chip as ONE
    custom call under the name a trace finds, its leaf aliased, with no
    temporary to speak of."""
    from deepspeed_tpu.inference.v2.kernels import linear_attention as la
    from deepspeed_tpu.inference.v2.kernels import state_space as ss

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=tpu_sharding)

    f = _falcon_fields()
    nh, p, n, g = (f[k] for k in ("mamba_n_heads", "mamba_d_head",
                                  "mamba_d_state", "mamba_n_groups"))
    assert (nh, p, n, g) == (32, 128, 256, 2)
    N, D, K = 64, nh * p + 2 * g * n, f["mamba_d_conv"]
    leaf = sds(ss.state_leaf_shape(4, 65, nh * p, n))
    assert leaf.shape == (4, 65, 32, 256, 128)
    assert ss.state_kernel_serves(leaf, g) \
        and ss.chunk_kernel_serves(leaf, p, g)
    rows = (sds((), jnp.int32), sds((N,), jnp.int32), sds((N,), jnp.bool_))
    if kernel == "ssm_state_update":
        compiled = jax.jit(ss.ssm_state_update, donate_argnums=(0,)).lower(
            leaf, *rows, sds((N, nh * p)), sds((N, nh)), sds((nh,)),
            sds((N, g * n)), sds((N, g * n))).compile()
        # B and C go in a group a row, at their own size
        assert f"f32[{N},2,1,{n}]" in compiled.as_text()
    elif kernel == "ssm_chunk_fwd":
        compiled = jax.jit(ss.ssm_chunk_fwd, donate_argnums=(0,)).lower(
            leaf, *rows, sds((N,), jnp.int32), sds((N,), jnp.int32),
            sds((N * 256, D), jnp.bfloat16), sds((N * 256, nh)),
            sds((nh,))).compile()
    else:
        conv = sds(la.conv_leaf_shape(4, 65, K, D))
        assert conv.shape == (4, 65, 3, 40, 128) \
            and la.conv_kernel_serves(conv, parts=1)
        compiled = jax.jit(
            lambda leaf, layer, slots, fresh, x, taps, bias: la.conv_update(
                leaf, layer, slots, fresh, (x,), taps, bias,
                name="ssm_conv_update"), donate_argnums=(0,)).lower(
            conv, *rows, sds((N, D)), sds((K, D), jnp.bfloat16),
            sds((D,), jnp.bfloat16)).compile()
    kernels = _custom_calls(compiled)
    assert len(kernels) == 1 and kernels[0].startswith(kernel), kernels
    assert compiled.memory_analysis().temp_size_in_bytes < 0.05e9


def _two_mixer_cut(tpu_sharding):
    """The block at published widths, cut to two layers and 4,096 rows
    of the vocabulary: the configuration, and its parameters and cache
    (32 state slots, 193 blocks) as shapes on the chip."""
    from deepspeed_tpu.inference.v2.paged_model import init_paged_kv_cache
    from deepspeed_tpu.models import TransformerLM
    from deepspeed_tpu.models.transformer import TransformerConfig

    cfg = TransformerConfig(**{
        **_falcon_fields(), "num_layers": 2, "vocab_size": 4096,
        "layer_types": ["mamba_attention"] * 2})

    def on_tpu(x, dtype=None):
        return jax.ShapeDtypeStruct(x.shape, dtype or x.dtype,
                                    sharding=tpu_sharding)

    params = jax.tree.map(
        lambda x: on_tpu(x, jnp.bfloat16),
        jax.eval_shape(TransformerLM(cfg).init_params,
                       jax.random.PRNGKey(0)))
    cache = jax.tree.map(on_tpu, jax.eval_shape(
        lambda: init_paged_kv_cache(cfg, 193, 16, jnp.bfloat16,
                                    state_slots=32)))
    # every layer has a place in BOTH families of leaves
    assert cache["ssm_state"].shape == (2, 33, 32, 256, 128) \
        and cache["ssm_conv"].shape == (2, 33, 3, 40, 128) \
        and cache["k_full"].shape == cache["v_full"].shape \
        == (2, 193, 16, 512)
    return cfg, params, cache


@pytest.mark.parametrize("program", ["ragged_step", "decode_window"])
def test_the_two_mixer_programs_run_both_halves_in_their_kernels(
        tpu_sharding, program):
    """The ragged step (32 rows in 2,048 tokens) and the decode window
    of the cut: ONE run of two layers, so each kernel stands once in the
    scan's body: the chunk kernel and the tiled attention kernel in the
    prompt's launch, the state kernel, the convolution's and the tiled
    kernel's one-token form in the window; nothing under ``ssm_scan``
    loops in XLA and nothing of the window under ``ssm_conv`` /
    ``ssm_state`` gathers or scatters the slots (the prompt's
    convolution is ``causal_conv_rows``, XLA's, as in every state-space
    block); the state leaf stays where it lies; both
    halves' operations and the join stand under ``hybrid_mixer``."""
    from deepspeed_tpu.inference.v2.paged_model import (paged_decode_window,
                                                        paged_ragged_step)

    cfg, params, cache = _two_mixer_cut(tpu_sharding)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=tpu_sharding)

    T, R = 2048, 32
    if program == "ragged_step":
        compiled = jax.jit(
            lambda p, ids, rows, pos, ln, wb, wo, bt, li, c, ss:
            paged_ragged_step(cfg, p, ids, rows, pos, ln, wb, wo, bt, li, c,
                              16, use_kernel=True, state_slots=ss),
            donate_argnums=(9,)).lower(
            params, i32(T), i32(T), i32(T), i32(T), i32(T), i32(T),
            i32(R, 16), i32(R), cache, i32(R)).compile()
        want = ("ssm_chunk_fwd", "ragged_attention_tiled")
    else:
        compiled = jax.jit(
            lambda p, t, pos, bt, c, sl, eos, alive, ss: paged_decode_window(
                cfg, p, t, pos, bt, c, sl, eos, 16, 8, use_kernel=True,
                alive=alive, state_slots=ss), donate_argnums=(4,)).lower(
            params, i32(R), i32(R), i32(R, 16), cache, i32(R), i32(R),
            jax.ShapeDtypeStruct((R,), jnp.bool_, sharding=tpu_sharding),
            i32(R)).compile()
        want = ("ssm_state_update", "ssm_conv_update",
                "ragged_attention_tiled")
    text = compiled.as_text()
    kernels = _custom_calls(compiled)
    assert sorted(re.sub(r"\.\d+$", "", k) for k in kernels) \
        == sorted(want), kernels
    assert "ssm_scan/while" not in text
    if program == "decode_window":
        under = re.findall(
            r"= \S+ ([\w\-]+)\([^\n]*op_name=\"[^\"]*/ssm_(?:conv|state)/",
            text)
        assert under and not {"gather", "scatter"} & set(under), under
    _leaves_stay_in_hbm(text, cache, "ssm_state")
    paths = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in ("ssm_mixer/ssm_proj", "ssm_mixer/ssm_out",
                  "attention/qkv_proj", "attention/attn_kernel",
                  "attention/out_proj", "attention/hybrid_join"):
        assert any(f"/hybrid_mixer/{scope}/" in p for p in paths), scope
