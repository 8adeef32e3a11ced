"""The configuration ``trinity-mini`` and its cell
``trinity-mini.rollout-16x8192-512``: what ``BENCHMARK.json`` and the
files say of them (entries found BY NAME, never by position: a later PR
appends; "at least these"), the 8.48 GB of the cut reckoned from
``fields``, the window's arithmetic by hand at the published sizes, the
new readers on a hand-made trace and where there is nothing to read, the
reference's logits made a slice at a time, the cell's rehearsal with its
readers, and the number of ``correct`` that reads a ring
(``runners/generate_kv.py``), which the cell's control fails.

Toy readings on the sandbox's CPU (no chip result), float32 engine,
seeds 2**31 + 4300000001..2: the cell as it stands ``logit_err`` 3e-7 to
5e-7, ``token_gap`` 0, ``kv_err`` 2e-7 to 3e-7; the control
(``kv_quant``) ``logit_err`` 3e-2 to 1.4e-1, ``kv_err`` 2e-2 to 4e-2."""

import json
import types
from pathlib import Path

import numpy as np
import pytest

from benchmark import (arith, arith_gen, arith_latent, arith_window,
                       manifest, tracing)
from benchmark.readers import (hbm_roofline, registry_family_sum,
                               window_roofline)
from benchmark.run import merge, reported_by
from benchmark.tracing import Event
from deepspeed_tpu.telemetry import (MetricsRegistry, get_registry,
                                     set_registry)

from test_benchmark_run import assert_rehearsed, run_py

REPO = Path(__file__).resolve().parents[2]
BENCH = REPO / "benchmark"
CONFIG, CELL = "trinity-mini", "trinity-mini.rollout-16x8192-512"
TRAFFIC = "rollout-16x8192-512"
FILE = json.loads((BENCH / "configs" / f"{CONFIG}.json").read_text())
FIELDS = FILE["fields"]
TOY = merge(FIELDS, FILE["toy_fields"])
OPT = json.loads((BENCH / "configs/opt-1.3b.json").read_text())["fields"]
PEAKS = arith.peaks("TPU v5 lite")
NEW = ["window_roofline.gen", "kv_gb.gen"]
LAYER = {"window_roofline.gen": "ragged attention kernel",
         "kv_gb.gen": "memory"}
JOINED = ["compiles.gen", "idle.gen", "peak_hbm.gen", "host_ms.gen",
          "gap_host_ms.gen", "gap_launch_ms.gen", "gap_unattributed.gen",
          "prefill_ms.gen", "decode_ms.gen", "attn_proj_ms.gen",
          "kv_write_ms.gen", "mlp_ms.gen", "head_ms.gen", "router_ms.gen",
          "scope_coverage.gen", "experts_share.gen", "experts_roofline.gen",
          "experts_touched.gen", "ragged_share.gen"]
SPECS = {m: json.loads((BENCH / "layer_metrics" / f"{m}.json").read_text())
         for m in NEW}
DEV = "/device:TPU:0"


def _named(entries, name):
    found = [e for e in entries if e["name"] == name]
    assert len(found) == 1, name
    return found[0]


# ---------------------------------------------------------------------------
# the tree
# ---------------------------------------------------------------------------
def test_the_tree_passes_the_manifest():
    manifest.check(REPO)


def test_the_configuration_and_the_cell_by_name():
    bm = manifest.read(REPO)
    c = _named(bm["configs"], CONFIG)
    assert c["reduced"] == FILE["reduced"] == ["num_hidden_layers",
                                               "num_dense_layers"]
    assert c["source"] == FILE["source"] \
        == "https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/" \
           "config.json"
    w = _named(bm["workloads"], CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (CONFIG, TRAFFIC, 1)
    assert "4x the window" in w["why"]
    assert reported_by(bm, CELL, "end_to_end") == ["setup_s", "gen_tok_s"]
    mine = reported_by(bm, CELL, "per_layer")
    assert sorted(mine) == sorted(JOINED + NEW)
    # ragged_roofline.gen counts every layer whole; the latent, linear
    # and state metrics are other blocks'
    for name in ("ragged_roofline.gen", "latent_roofline.gen",
                 "latent_share.gen", "linear_ms.gen", "state_gb.gen"):
        assert CELL not in _named(bm["per_layer"], name)["workloads"]
    for name in NEW:
        m = _named(bm["per_layer"], name)
        assert m["workloads"] == [CELL] and m["moves"] == "gen_tok_s"
        assert m["layer"] == LAYER[name]
        for key in ("unit", "better", "source", "layer", "moves"):
            assert SPECS[name][key] == m[key]


def test_nothing_that_was_there_lost_a_cell_or_an_entry():
    bm = manifest.read(REPO)
    for name in JOINED:
        cells = _named(bm["per_layer"], name)["workloads"]
        assert cells[-1] == CELL and len(cells) >= 2, name
    assert {"opt-1.3b.rollout-256", "joyai-llm-flash.rollout-64x256",
            "ling-3.0-flash.rollout-128x256", CELL} <= set(
        _named(bm["end_to_end"], "gen_tok_s")["workloads"])
    assert {"opt-1.3b", "opt-125m", "joyai-llm-flash", "ling-3.0-flash",
            CONFIG} <= {c["name"] for c in bm["configs"]}
    # every per-layer metric that moves gen_tok_s lists its cells
    assert all("workloads" in m for m in bm["per_layer"]
               if m["moves"] == "gen_tok_s")
    four = [w["name"] for w in bm["workloads"] if w["chips"] == 4]
    assert four == ["opt-1.3b.zero3-dp4"]


def test_published_widths_and_the_cut():
    pub = FILE["published"]
    for key, want in dict(
            hidden_size=2048, num_heads=32, num_kv_heads=4,
            head_dim_override=128, intermediate_size=6144,
            vocab_size=200192, attn_window=2048, qk_norm=True,
            rope_sliding_only=True, attn_gate="elementwise",
            norm_scheme="sandwich", moe_num_experts=128, moe_top_k=8,
            moe_intermediate_size=1024, moe_shared_experts=1,
            moe_first_dense_layers=1, moe_routed_scale=2.826,
            moe_scoring="sigmoid", rope_theta=1e4, norm_eps=1e-5,
            num_layers=5, tie_embeddings=False).items():
        assert FIELDS[key] == want, key
    assert FIELDS["embed_scale"] == pytest.approx(2048 ** 0.5)
    # published layers 1-5: one whole period behind one dense layer
    assert FIELDS["layer_types"] == pub["layer_types"][1:6] == [
        "sliding_attention", "sliding_attention", "full_attention",
        "sliding_attention", "sliding_attention"]
    assert "moe_experts_held" not in FIELDS       # every expert held
    cuts = FILE["cuts"]
    assert (cuts["num_hidden_layers"]["published"],
            cuts["num_hidden_layers"]["here"],
            cuts["num_hidden_layers"]["period"],
            cuts["num_hidden_layers"]["leading_dense"]) == (32, 5, 4, 1)
    assert (cuts["num_dense_layers"]["published"],
            cuts["num_dense_layers"]["here"]) == (2, 1)
    assert "1-5" in cuts["num_hidden_layers"]["layers_kept"]
    # every number of the source's config stands at the top level too,
    # layer_types whole (manifest.cut takes a reduced key as a count)
    for key, value in pub.items():
        if key not in FILE["reduced"]:
            assert FILE[key] == value, key
    assert FILE["num_hidden_layers"] == 5 and FILE["num_dense_layers"] == 1
    for key in ("source_of_equations", "layer_types", "gate", "qk_norm",
                "rope", "window", "norms", "embedding", "router", "seeded",
                "read_and_unused", "sizes"):
        assert FILE["assumed"][key]
    assert "does not apply" in FILE["share_adds_up"]
    assert FILE["deployment"] and FILE["reference"] == "reference_trinity" \
        and FILE["weights"] == "weights_trinity"


def test_the_bytes_of_the_cut_from_fields():
    """ISSUE 43's arithmetic, 2 B a parameter, from ``fields`` alone."""
    f = FIELDS
    h, nh, nkv, hd = f["hidden_size"], f["num_heads"], f["num_kv_heads"], \
        f["head_dim_override"]
    mixer = 2 * h * nh * hd + 2 * h * nkv * hd + h * nh * hd
    assert mixer == 27_262_976
    dense = 3 * h * f["intermediate_size"]
    assert dense == 37_748_736
    expert = 3 * h * f["moe_intermediate_size"]
    assert expert == 6_291_456
    norms = 4 * h + 2 * hd
    routed = f["moe_num_experts"] * expert + expert \
        + h * f["moe_num_experts"] + f["moe_num_experts"]
    assert 2 * (mixer + routed + norms) == pytest.approx(1.678e9, rel=1e-3)
    assert 2 * (mixer + dense + norms) == pytest.approx(0.130e9, rel=2e-3)
    lead = f["moe_first_dense_layers"]
    total = 2 * f["vocab_size"] * h + h + sum(
        mixer + norms + (dense if i < lead else routed)
        for i in range(f["num_layers"]))
    assert 2 * f["vocab_size"] * h == 819_986_432
    assert total == pytest.approx(4241.5e6, rel=1e-4)
    assert 2 * total == pytest.approx(8.48e9, rel=1e-3)
    # the weights module lays out exactly these
    from benchmark import weights_trinity
    count = sum(int(np.prod(shape)) for leaves in
                weights_trinity.shapes(f).values()
                for shape, _ in leaves.values())
    assert count == total
    # a cached position: 2 x 4 x 128 x 2 B a layer
    assert arith_gen.kv_bytes_per_token(f) == 2048


# ---------------------------------------------------------------------------
# the window's arithmetic, by hand
# ---------------------------------------------------------------------------
def test_window_arithmetic_by_hand():
    assert arith_window.layers_of(FIELDS) == {"window": 4, "full": 1}
    # what a row's tokens read, together
    assert arith_window.row_positions(1, 8700, 2048) == 2048
    assert arith_window.row_positions(1, 8700, 0) == 8700
    assert arith_window.row_positions(1024, 3072, 2048) == 3071
    assert arith_window.row_positions(8192, 8192, 2048) == 8192
    assert arith_window.row_positions(1, 100, 2048) == 100
    # what they attend, summed: against a loop
    for new, ctx, w in ((1, 10, 4), (5, 10, 4), (10, 10, 4), (3, 3, 4),
                        (6, 7, 4), (1024, 3000, 2048), (7, 20, 0),
                        (8192, 8192, 2048)):
        loop = sum(min(ctx - new + i + 1, w) if w else ctx - new + i + 1
                   for i in range(new))
        assert arith_window.visible_positions(new, ctx, w) == loop
    # a decode step of the cell at context 8,448: 16 rows, by bytes
    step = [(1, 8448)] * 16
    assert arith_window.launch_bytes(FIELDS, step, 2048) \
        == 16 * (2048 * 2048 + 2 * 4096 * 2)
    assert arith_window.launch_bytes(FIELDS, step, 0) \
        == 16 * (8448 * 2048 + 2 * 4096 * 2)
    assert arith_window.launch_flops(FIELDS, step, 2048) \
        == 4 * 4096 * 16 * 2048
    # ISSUE 43: the cache a step reads, 16 x (8,448 + 4 x 2,048) x 2,048
    # B = 0.55 GB (1.38 GB if every layer held every position)
    both = arith_window.launch_bytes(FIELDS, step, 0) \
        + 4 * arith_window.launch_bytes(FIELDS, step, 2048)
    assert both == pytest.approx(0.55e9, rel=0.02)
    assert 5 * arith_window.launch_bytes(FIELDS, step, 0) \
        == pytest.approx(1.38e9, rel=0.02)
    # the prompt, as ONE launch: ~24 TFLOP of attention (~44 unwindowed)
    prompt = [(8192, 8192)] * 16
    flops = arith_window.launch_flops(FIELDS, prompt, 0) \
        + 4 * arith_window.launch_flops(FIELDS, prompt, 2048)
    assert flops == pytest.approx(24e12, rel=0.03)
    assert 5 * arith_window.launch_flops(FIELDS, prompt, 0) \
        == pytest.approx(44e12, rel=0.03)


def test_the_least_seconds_of_one_call():
    """Launch by launch and kind by kind the larger floor: the prompt by
    operations (0.12 s), the 511 decode steps by bytes (0.34 s)."""
    rows = arith_gen.generate_call_rows(16, 8192, 512)
    least = arith_window.least_seconds(FIELDS, rows, 16, PEAKS)
    prompt = [(8192, 8192)] * 16
    by_hand = (arith_window.launch_flops(FIELDS, prompt, 0)
               + 4 * arith_window.launch_flops(FIELDS, prompt, 2048)) \
        / 197e12
    for step in range(1, 512):
        ctx = 8192 + step
        by_hand += 16 * ((ctx + 4 * 2048) * 2048 + 5 * 2 * 4096 * 2) / 819e9
    assert least == pytest.approx(by_hand, rel=1e-9)
    assert least == pytest.approx(0.4638, rel=1e-3)
    # every layer counted whole, as ragged_roofline.gen would: more
    whole = arith_gen.ragged_attention_bytes(FIELDS, rows) / 819e9
    assert whole > 0.8 > least


def _evidence(events, fields=FIELDS, rows=2, new_tokens=3, prompt_len=5):
    ctx = types.SimpleNamespace(
        fields=fields, traffic={"rows": rows, "new_tokens": new_tokens,
                                "prompt_len": prompt_len},
        cell={"engine": {}},
        devices=[types.SimpleNamespace(device_kind="TPU v5 lite")])
    return types.SimpleNamespace(events=tracing.Events(events), ctx=ctx,
                                 slice_steps=1)


OPS, MODULES = tracing.OPS_LINE, tracing.MODULES_LINE
LAUNCH = Event(DEV, MODULES, "jit_ragged_step(1)", 0.0, 5e-3)
KERNELS = [Event(DEV, OPS, "ragged_attention_window.13:tpu_custom_call",
                 1e-3, 1e-3),
           Event(DEV, OPS, "ragged_attention_tiled.2:tpu_custom_call",
                 2e-3, 5e-4),
           Event(DEV, OPS, "ragged_attention_latent.4:tpu_custom_call",
                 3e-3, 7e-4),
           Event(DEV, OPS, "gmm.3:tpu_custom_call", 4e-3, 9e-4)]


def test_the_window_roofline_on_a_hand_made_trace():
    """The window launches' and the full launches' time together (1.5
    ms; neither the latent kernel's nor the grouped matmul's), against
    the floor of the rows the harness hands over."""
    params = SPECS["window_roofline.gen"]["params"]
    ev = _evidence([LAUNCH] + KERNELS)
    ev.launch_rows = [(5, 5), (5, 5), (1, 6), (1, 6)]
    least = arith_window.least_seconds(FIELDS, ev.launch_rows, 2, PEAKS)
    launches = arith_latent.launches(ev.launch_rows, 2)
    by_hand = sum(
        n * max(arith_window.launch_bytes(FIELDS, ln, w) / 819e9,
                arith_window.launch_flops(FIELDS, ln, w) / 197e12)
        for n, w in ((4, 2048), (1, 0)) for ln in launches)
    assert least == pytest.approx(by_hand)
    assert window_roofline.read(ev, params) \
        == pytest.approx(100 * least / 1.5e-3)
    # the accepted reader counts all five layers whole over both names
    assert hbm_roofline.read(ev, {
        "pattern": "ragged_attention_[a-z]+[_.0-9]*:tpu_custom_call$"}) \
        is not None


@pytest.fixture
def fresh_registry():
    old = get_registry()
    set_registry(MetricsRegistry())
    yield get_registry()
    set_registry(old)


def test_each_new_reader_reads_nothing_where_there_is_nothing(
        fresh_registry):
    """No slice; no rows; a configuration without a pattern; a trace
    without the kernels (the parent's has neither name's launches of
    this model); a registry without the family (the parent's), or with
    nothing set: None, and no error, so that the line leaves the metric
    out."""
    params = SPECS["window_roofline.gen"]["params"]
    rows = [(5, 5), (5, 5), (1, 6), (1, 6)]
    no_slice = _evidence([])
    no_slice.launch_rows = rows
    assert window_roofline.read(no_slice, params) is None
    assert window_roofline.read(_evidence([LAUNCH] + KERNELS), params) \
        is None                                   # no launch_rows
    plain = _evidence([LAUNCH] + KERNELS, fields=OPT)
    plain.launch_rows = rows
    assert window_roofline.read(plain, params) is None
    others = _evidence([LAUNCH] + KERNELS[2:])
    others.launch_rows = rows
    assert window_roofline.read(others, params) is None
    gauge = SPECS["kv_gb.gen"]["params"]
    assert gauge == {"name": "inference_kv_pool_bytes", "scale": 1e-09}
    assert registry_family_sum.read(None, gauge) is None
    fam = fresh_registry.gauge("inference_kv_pool_bytes",
                               labelnames=("kind",))
    fam.labels(kind="full").set(0)
    assert registry_family_sum.read(None, gauge) is None
    fam.labels(kind="full").set(0.286e9)
    fam.labels(kind="window").set(0.405e9)
    assert registry_family_sum.read(None, gauge) == pytest.approx(0.691)


def test_the_pools_bytes_of_the_cell_by_hand():
    """What ``kv_gb.gen`` reads on the chip, from the cell's file: the
    full layer's 8,721 blocks and the four window layers' 16 rings of
    193 blocks and the null block: 0.69 GB, where leaves that held every
    position would be 1.43."""
    cell = json.loads((BENCH / "workloads" / f"{CELL}.json").read_text())
    sm = cell["engine"]["state_manager"]
    bs, rows = sm["block_size"], sm["max_tracked_sequences"]
    page = bs * 512 * 2 * 2                        # k and v, bf16
    chunk = sm["max_ragged_batch_size"] // rows
    ring = (FIELDS["attn_window"] + chunk + bs) // bs
    assert (chunk, ring) == (1024, 193)
    full = sm["num_blocks"] * page
    window = 4 * (rows * ring + 1) * page
    assert full == pytest.approx(0.2858e9, rel=1e-3)
    assert window == pytest.approx(0.4049e9, rel=1e-3)
    assert full + window == pytest.approx(0.69e9, rel=5e-3)
    assert 5 * full == pytest.approx(1.43e9, rel=5e-3)


# ---------------------------------------------------------------------------
# the reference
# ---------------------------------------------------------------------------
def test_the_references_logits_are_made_where_they_are_sliced():
    """``runners/generate.compare`` takes ``[-1]`` and ``[plen - 1:]``:
    the same numbers as the whole array's rows, and a whole
    ``[8703, 200192]`` array is never made."""
    from benchmark import reference_trinity, weights_trinity
    params = weights_trinity.make(TOY, 3, "float32")
    ids = np.random.default_rng(0).integers(0, TOY["vocab_size"], 45)
    lg = reference_trinity.logits(params, TOY, ids)
    assert lg.shape == (45, TOY["vocab_size"]) and len(lg) == 45
    whole = np.asarray(lg)
    assert whole.shape == lg.shape and whole.dtype == np.float32
    np.testing.assert_array_equal(np.asarray(lg[-1]), whole[-1])
    np.testing.assert_array_equal(np.asarray(lg[40:]), whole[40:])
    np.testing.assert_array_equal(np.asarray(lg[:-1]), whole[:-1])
    loss = reference_trinity.next_token_loss(params, TOY, ids)
    z = whole[:-1] - whole[:-1].max(-1, keepdims=True)
    logp = z - np.log(np.exp(z).sum(-1, keepdims=True))
    assert loss == pytest.approx(
        -logp[np.arange(44), ids[1:]].mean(), rel=1e-5)
    k, v = reference_trinity.leading_kv(params, TOY, ids)
    F = TOY["num_kv_heads"] * TOY["head_dim_override"]
    assert k.shape == v.shape == (2, 45, F)


def test_the_reference_refuses_another_block():
    from benchmark import reference_trinity
    reference_trinity.check_supported(FIELDS)
    reference_trinity.check_supported(TOY)
    for change in ({"qk_norm": False}, {"norm_scheme": "pre"},
                   {"layer_types": None}, {"moe_n_group": 2},
                   {"moe_shared_experts": 0}, {"attention": "mla"}):
        with pytest.raises(ValueError, match="reference_trinity"):
            reference_trinity.check_supported({**FIELDS, **change})
    with pytest.raises(ValueError):
        reference_trinity.check_supported(OPT)


# ---------------------------------------------------------------------------
# the cell's files
# ---------------------------------------------------------------------------
def test_the_cell_and_its_traffic_say_what_the_issue_asked():
    cell = json.loads((BENCH / "workloads" / f"{CELL}.json").read_text())
    sm = cell["engine"]["state_manager"]
    traffic = json.loads((BENCH / "traffic" / f"{TRAFFIC}.json")
                         .read_text())
    rows = traffic["rows"]
    assert rows in (16, 12) and traffic["rule"]["branch"]
    assert (sm["max_tracked_sequences"], sm["max_ragged_batch_size"],
            sm["max_seq_len"], sm["block_size"]) == (16, 16384, 8704, 16)
    assert sm["num_blocks"] == 16 * 8704 // 16 + 16 + 1
    assert (traffic["runner"], traffic["prompt_len"], traffic["new_tokens"],
            traffic["temperature"], traffic["distinct_batches"],
            traffic["check_rows"]) == ("generate_kv", 8192, 512, 0.0, 4, 4)
    assert cell["engine"]["dtype"] == "bfloat16" \
        and cell["engine"]["use_paged_kernel"] is True
    assert set(cell["engine"]) == {"dtype", "use_paged_kernel",
                                   "state_manager"}
    assert cell["control"] == {"engine": {"kv_quant": True}}
    for name in ("logit_err", "token_gap", "kv_err"):
        assert 0 < cell["limits"][name]["limit"] < 1
        assert "control" in cell["limits"][name]["from"]
        assert 0 < cell["rehearse"]["limits"][name]["limit"] < 1
    for key in ("overrides", "who", "sizing"):
        assert cell[key]


def test_the_cell_rehearses_with_its_readers():
    p = run_py(["--workload", CELL, "--rehearse", "--trace", "1",
                "--seed", str(2 ** 31 + 4300000001)])
    assert_rehearsed(p)
    assert "pallas:pipelined+window" in p.stderr
    assert "compared: logit_err" in p.stderr
    assert "compared: token_gap" in p.stderr
    assert "compared: kv_err" in p.stderr
    assert "calls finished 0" not in p.stderr
    assert "'compiles_in_window': 0" in p.stderr
    ran = p.stderr.split("readers ran")[1]
    assert "experts_touched.gen" in ran and "kv_gb.gen" in ran
    assert "ragged_roofline.gen" not in ran


def test_the_toy_control_fails_its_limit_and_the_sound_run_passes():
    """``kv_quant`` on the toy: int8 keys and values in both leaves read
    ``kv_err`` hundreds of times its limit where the cell as it stands
    reads a hundredth of it: the comparison can come out not correct on
    what this cell adds."""
    import jax
    from benchmark import control
    from benchmark import run as harness
    seed = 2 ** 31 + 4300000002
    read = {}
    for on in (False, True):
        result = control.run_once(CELL, seed, 2.0, on, jax.devices(),
                                  harness.CompileClock(), rehearse=True)
        read[on] = result.correct, result.correct_detail["compared"]
    assert read[False][0] is True and read[True][0] is False
    for name in ("logit_err", "token_gap", "kv_err"):
        assert read[False][1][name]["value"] \
            <= read[False][1][name]["limit"]
    sound, control_ = (read[on][1]["kv_err"] for on in (False, True))
    assert sound["value"] <= sound["limit"] / 20
    assert control_["value"] >= 50 * control_["limit"]
    assert read[True][1]["logit_err"]["value"] \
        > read[True][1]["logit_err"]["limit"]


def test_a_reference_without_keys_and_values_is_named():
    from benchmark.runners import generate_kv
    ctx = types.SimpleNamespace(
        reference=types.SimpleNamespace(), cell={"config": "some-config"})
    with pytest.raises(SystemExit, match="some-config's reference to "
                       "offer leading_kv"):
        generate_kv.kv_error(ctx)
    ctx = types.SimpleNamespace(
        reference=types.SimpleNamespace(leading_kv=lambda *a: None),
        cell={"config": "some-config"},
        fields={**FIELDS, "layer_types": ["full_attention"] * 5})
    with pytest.raises(SystemExit, match="RING of the layers"):
        generate_kv.kv_error(ctx)
