"""The configuration ``smallthinker-21ba3b-instruct`` and its cell
``smallthinker-21ba3b-instruct.rollout-16x8192-512``: what
``BENCHMARK.json`` and the files say of them (entries found BY NAME,
never by position: a later PR appends; "at least these"), the 7.93 GB of
the cut reckoned from ``fields``, the window's and the experts'
arithmetic by hand at the published sizes (the accepted ``arith_window``
and ``arith_experts``, from ``fields``: the block adds no kernel), the
pools' bytes, the reference's logits made a slice at a time, the cell's
rehearsal with its readers, and the control, which fails ``kv_err``.

Toy readings on the sandbox's CPU (no chip result), float32 engine,
seeds 2**31 + 5800000001..2: the cell as it stands ``logit_err`` 9e-7 to
1.1e-6, ``token_gap`` 0, ``kv_err`` 2e-7; the control (``kv_quant``)
``logit_err`` 4.5e-1 and ``token_gap`` 4.4e-1 (eight toy layers of int8
pages: the served tokens leave the reference's), ``kv_err`` 2.1e-2 (the
values; the keys 8e-3). The chip's limits and the readings they lie
between: the cell's file and PERF.md section 4."""

import json
from pathlib import Path

import numpy as np
import pytest

from benchmark import (arith, arith_experts, arith_gen, arith_window,
                       manifest)
from benchmark.run import merge, reported_by

from test_benchmark_run import assert_rehearsed, run_py

REPO = Path(__file__).resolve().parents[2]
BENCH = REPO / "benchmark"
CONFIG = "smallthinker-21ba3b-instruct"
TRAFFIC = "rollout-16x8192-512"
CELL = f"{CONFIG}.{TRAFFIC}"
TRINITY = f"trinity-mini.{TRAFFIC}"
FILE = json.loads((BENCH / "configs" / f"{CONFIG}.json").read_text())
FIELDS = FILE["fields"]
TOY = merge(FIELDS, FILE["toy_fields"])
OPT = json.loads((BENCH / "configs/opt-1.3b.json").read_text())["fields"]
PEAKS = arith.peaks("TPU v5 lite")
# the lists the cell joins AT LEAST: every one trinity's cell is in, and
# those of the prompt-side and gap metrics whose reader finds something
TRINITYS = ["compiles.gen", "idle.gen", "peak_hbm.gen", "ragged_share.gen",
            "experts_share.gen", "experts_roofline.gen",
            "experts_touched.gen", "host_ms.gen", "gap_host_ms.gen",
            "gap_launch_ms.gen", "gap_unattributed.gen", "prefill_ms.gen",
            "decode_ms.gen", "attn_proj_ms.gen", "kv_write_ms.gen",
            "mlp_ms.gen", "head_ms.gen", "router_ms.gen",
            "scope_coverage.gen", "window_roofline.gen", "kv_gb.gen"]
PROMPT_SIDE = ["prefill_attn_proj_ms.gen", "prefill_attn_kernel_ms.gen",
               "prefill_experts_ms.gen", "prefill_router_ms.gen",
               "prefill_head_ms.gen", "prefill_other_ms.gen",
               "gap_upload_ms.gen", "gap_call_ms.gen", "gap_fetch_ms.gen",
               "gc_pause_ms.gen"]
OTHERS = ["ragged_roofline.gen", "latent_roofline.gen", "latent_share.gen",
          "linear_ms.gen", "state_gb.gen", "ssm_ms.gen", "retention_ms.gen",
          "experts_relu2_roofline.gen", "prefill_linear_ms.gen",
          "prefill_ssm_scan_ms.gen", "prefill_retention_ms.gen"]


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
    assert c["reduced"] == FILE["reduced"] == ["num_hidden_layers"]
    assert c["source"] == FILE["source"] \
        == "https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct" \
           "/blob/main/config.json"
    assert c["file"] == f"benchmark/configs/{CONFIG}.json"
    w = _named(bm["workloads"], CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (CONFIG, TRAFFIC, 1)
    assert "2x the window" in w["why"] and "7" in w["why"]
    assert reported_by(bm, CELL, "end_to_end") == ["setup_s", "gen_tok_s"]


@pytest.mark.parametrize("name", TRINITYS + PROMPT_SIDE)
def test_the_cell_reports_at_least_this(name):
    bm = manifest.read(REPO)
    m = _named(bm["per_layer"], name)
    assert CELL in m["workloads"] and m["moves"] == "gen_tok_s"
    assert name in reported_by(bm, CELL, "per_layer")
    if name in TRINITYS:      # beside the cell whose window path it shares
        assert TRINITY in m["workloads"]


@pytest.mark.parametrize("name", OTHERS)
def test_another_blocks_metric_is_not_the_cells(name):
    assert CELL not in _named(manifest.read(REPO)["per_layer"],
                              name)["workloads"]


def test_the_pr_adds_no_reader_no_metric_and_no_arithmetic():
    """The block adds no kernel and no scope word: every metric the cell
    reports was there, and its readers and arithmetic modules with it."""
    bm = manifest.read(REPO)
    for name in reported_by(bm, CELL, "per_layer"):
        # another cell stands ahead of this one in every list it joined
        assert _named(bm["per_layer"], name)["workloads"].index(CELL) >= 1, \
            name
    assert not list(BENCH.glob("arith_small*")) \
        and not list((BENCH / "readers").glob("*small*")) \
        and not list((BENCH / "layer_metrics").glob("*small*"))
    four = [w["name"] for w in bm["workloads"] if w["chips"] == 4]
    assert four == ["opt-1.3b.zero3-dp4"]


def test_published_widths_and_the_cut():
    pub = FILE["published"]
    for key, want in dict(
            hidden_size=2560, num_heads=28, num_kv_heads=4,
            head_dim_override=128, intermediate_size=768,
            vocab_size=151936, max_seq_len=16384, attn_window=4096,
            rope_sliding_only=True, rope_theta=1.5e6, norm_eps=1e-6,
            moe_num_experts=64, moe_top_k=6, moe_intermediate_size=768,
            moe_scoring="softmax", moe_norm_topk=True,
            moe_expert_form="reglu", moe_router_ahead=True, num_layers=8,
            tie_embeddings=False, positional="rope").items():
        assert FIELDS[key] == want, key
    for absent in ("qk_norm", "attn_gate", "norm_scheme",
                   "moe_shared_experts", "moe_first_dense_layers",
                   "moe_selection_bias", "moe_experts_held", "embed_scale"):
        assert absent not in FIELDS, absent
    for field, key in FILE["published_as"].items():
        if key not in FILE["reduced"]:
            assert FIELDS[field] == pub[key], field
    assert FILE["published_as"]["intermediate_size"] \
        == FILE["published_as"]["moe_intermediate_size"] \
        == "moe_ffn_hidden_size"
    # the two 52-entry lists agree entry for entry (rope_sliding_only),
    # the full layer FIRST in a period; layers 1-8 are served
    assert pub["rope_layout"] == pub["sliding_window_layout"] \
        == [0, 1, 1, 1] * 13
    assert FIELDS["layer_types"] == [
        "sliding_attention" if s else "full_attention"
        for s in pub["sliding_window_layout"][1:9]] \
        == (["sliding_attention"] * 3 + ["full_attention"]) * 2
    cut = FILE["cuts"]["num_hidden_layers"]
    assert (cut["kind"], cut["published"], cut["here"], cut["period"],
            cut["leading_dense"]) == ("depth", 52, 8, 4, 0)
    assert "1-8" in cut["layers_kept"] and "generate_kv" in cut["layers_kept"]
    assert cut["here"] >= cut["leading_dense"] + max(4, cut["period"])
    assert cut["here"] % cut["period"] == 0           # whole periods
    # every number of the source's config stands at the top level too
    for key, value in pub.items():
        if key not in FILE["reduced"]:
            assert FILE[key] == value, key
    assert FILE["num_hidden_layers"] == 8
    for key in ("source_of_equations", "router_ahead", "router", "gate",
                "secondary_experts", "no_bias_no_head_norm", "rope",
                "window", "layer_types", "widths", "max_seq_len", "seeded",
                "sizes"):
        assert FILE["assumed"][key], key
    assert "does not serve" in FILE["assumed"]["router"]
    assert "does not apply" in FILE["share_adds_up"]
    assert FILE["deployment"] \
        and FILE["reference"] == "reference_smallthinker" \
        and FILE["weights"] == "weights_smallthinker"
    toy_groups = TOY["num_heads"] // TOY["num_kv_heads"]
    assert toy_groups == 7 and TOY["attn_window"] < 96 \
        and len(TOY["layer_types"]) == 8


def test_the_bytes_of_the_cut_from_fields():
    """ISSUE 58's arithmetic, 2 B a parameter, from ``fields`` alone."""
    f = FIELDS
    h, nh, nkv, hd = f["hidden_size"], f["num_heads"], f["num_kv_heads"], \
        f["head_dim_override"]
    mixer = h * (nh * hd + 2 * nkv * hd) + nh * hd * h
    assert mixer == 20_971_520
    router = h * f["moe_num_experts"]
    assert router == 163_840
    expert = 3 * h * f["moe_intermediate_size"]
    assert expert == 5_898_240 \
        and f["moe_num_experts"] * expert == 377_487_360
    layer = mixer + router + f["moe_num_experts"] * expert + 2 * h
    assert layer == 398_627_840
    assert 2 * f["vocab_size"] * h == 777_912_320
    total = 2 * f["vocab_size"] * h + h + f["num_layers"] * layer
    assert total == 3_966_937_600
    assert 2 * total == pytest.approx(7.93e9, rel=1e-3)
    # the twelve layers of the driver's ceiling: 11.1 GB
    assert 2 * (total + 4 * layer) == pytest.approx(11.1e9, rel=5e-3)
    from benchmark import weights_smallthinker
    count = sum(int(np.prod(shape)) for leaves in
                weights_smallthinker.shapes(f).values()
                for shape, _ in leaves.values())
    assert count == total
    # a cached position: 2 x 4 x 128 x 2 B a layer; an expert's bytes and
    # a routed row's operations, as the accepted arithmetic counts them
    assert arith_gen.kv_bytes_per_token(f) == 2048
    assert arith_experts.expert_bytes(f) == 2 * expert == 11_796_480
    assert arith_experts.row_flops(f) == 2 * expert
    assert arith_experts.expert_layers(f) == 8


def test_window_and_expert_arithmetic_by_hand():
    assert arith_window.layers_of(FIELDS) == {"window": 6, "full": 2}
    # a decode step at context 8,448: 16 rows; a window layer reads the
    # last 4,096 positions, a full layer all
    step = [(1, 8448)] * 16
    q_and_o = 2 * 28 * 128 * 2
    assert arith_window.launch_bytes(FIELDS, step, 4096) \
        == 16 * (4096 * 2048 + q_and_o)
    assert arith_window.launch_bytes(FIELDS, step, 0) \
        == 16 * (8448 * 2048 + q_and_o)
    assert arith_window.launch_flops(FIELDS, step, 4096) \
        == 4 * 28 * 128 * 16 * 4096
    # ISSUE 58: the cache a step reads, 16 x (2 x 8,448 + 6 x 4,096) x
    # 2,048 B = 1.36 GB, three fifths of it the rings'
    rings = 6 * arith_window.launch_bytes(FIELDS, step, 4096)
    both = 2 * arith_window.launch_bytes(FIELDS, step, 0) + rings
    assert both == pytest.approx(1.36e9, rel=0.01)
    assert rings / both == pytest.approx(0.6, abs=0.01)
    # the prompt as ONE launch: twice trinity's attention operations
    prompt = [(8192, 8192)] * 16
    flops = 2 * arith_window.launch_flops(FIELDS, prompt, 0) \
        + 6 * arith_window.launch_flops(FIELDS, prompt, 4096)
    trinity = json.loads((BENCH / "configs/trinity-mini.json")
                         .read_text())["fields"]
    theirs = arith_window.launch_flops(trinity, prompt, 0) \
        + 4 * arith_window.launch_flops(trinity, prompt, 2048)
    assert flops / theirs == pytest.approx(2.1, abs=0.15)
    # a decode step's experts: ~50 of 64 touched by 96 picks stream 0.59
    # GB a layer, by bytes and not by operations
    assert arith_experts.pass_least_seconds(FIELDS, 50, 96, PEAKS) \
        == pytest.approx(50 * 11_796_480 / 819e9)
    # one call's attention floor, launch by launch
    rows = arith_gen.generate_call_rows(16, 8192, 512)
    least = arith_window.least_seconds(FIELDS, rows, 16, PEAKS)
    by_hand = flops / 197e12
    for s in range(1, 512):
        ctx = 8192 + s
        by_hand += 16 * ((2 * ctx + 6 * 4096) * 2048 + 8 * q_and_o) / 819e9
    assert least == pytest.approx(by_hand, rel=1e-9)


def test_the_pools_bytes_of_the_cell_by_hand():
    """What ``kv_gb.gen`` reads on the chip, from the cell's file: the
    two full layers' 8,721 blocks and the six window layers' 16 rings of
    321 blocks and the null block: 1.58 GB, where leaves that held every
    position would be 2.29."""
    cell = json.loads((BENCH / "workloads" / f"{CELL}.json").read_text())
    sm = cell["engine"]["state_manager"]
    bs, rows = sm["block_size"], sm["max_tracked_sequences"]
    page = bs * 512 * 2 * 2                        # k and v, bf16
    chunk = sm["max_ragged_batch_size"] // rows
    ring = (FIELDS["attn_window"] + chunk + bs) // bs
    assert (chunk, ring, ring * bs) == (1024, 321, 5136)
    full = 2 * sm["num_blocks"] * page
    window = 6 * (rows * ring + 1) * page
    assert full == pytest.approx(0.57e9, rel=5e-3)
    assert window == pytest.approx(1.01e9, rel=5e-3)
    assert 3 * full == pytest.approx(1.71e9, rel=5e-3)


# ---------------------------------------------------------------------------
# the reference
# ---------------------------------------------------------------------------
def test_the_references_logits_are_made_where_they_are_sliced():
    from benchmark import reference_smallthinker as reference
    from benchmark import weights_smallthinker
    params = weights_smallthinker.make(TOY, 3, "float32")
    ids = np.random.default_rng(0).integers(0, TOY["vocab_size"], 45)
    lg = reference.logits(params, TOY, ids)
    assert lg.shape == (45, TOY["vocab_size"]) and len(lg) == 45
    whole = np.asarray(lg)
    assert whole.shape == lg.shape and whole.dtype == np.float32
    np.testing.assert_array_equal(np.asarray(lg[-1]), whole[-1])
    np.testing.assert_array_equal(np.asarray(lg[40:]), whole[40:])
    loss = reference.next_token_loss(params, TOY, ids)
    z = whole[:-1] - whole[:-1].max(-1, keepdims=True)
    logp = z - np.log(np.exp(z).sum(-1, keepdims=True))
    assert loss == pytest.approx(
        -logp[np.arange(44), ids[1:]].mean(), rel=1e-5)
    k, v = reference.leading_kv(params, TOY, ids)
    F = TOY["num_kv_heads"] * TOY["head_dim_override"]
    assert k.shape == v.shape == (1, 45, F)


def test_the_references_programs_follow_the_length_and_not_the_seed():
    """A run's reference passes stand behind its window and inside the
    driver's time limit for the run, with a compile cache that may be
    empty: no program of the reference may depend on what the weights or
    the ids make the router choose (an expert's count as a static size
    was a program a count, 17-25 s each on the chip), and the cell's two
    lengths share one set."""
    from benchmark import reference_smallthinker as reference
    from benchmark import weights_smallthinker
    jitted = (reference._router_and_attention, reference._experts)
    for fn in jitted:
        fn.clear_cache()
    for seed, length in ((3, 45), (4, 70), (2 ** 31 + 5, 70)):
        params = weights_smallthinker.make(TOY, seed, "float32")
        ids = np.random.default_rng(seed).integers(
            0, TOY["vocab_size"], length)
        assert reference.hidden(params, TOY, ids).shape \
            == (length, TOY["hidden_size"])
    # a layer kind's program, and ONE for every layer's experts
    assert [fn._cache_size() for fn in jitted] == [2, 1]


def test_the_references_blocks_hold_every_pick_once():
    from benchmark import reference_smallthinker as reference
    rng = np.random.default_rng(7)
    S, k, E, R = 301, 4, 16, reference.ROW_BLOCK
    # a skewed choice: expert 0 is a pick of two tokens in three
    chosen = np.stack([1 + rng.permutation(E - 1)[:k] for _ in range(S)])
    chosen[: S * 2 // 3, 0] = 0
    w = rng.random((S, k)).astype(np.float32) + 0.1
    expert, token, weight = reference._groups(chosen, w, E)
    assert expert.shape == (S * k // R + E,) \
        and token.shape == weight.shape == (len(expert), R)
    dense = np.zeros((S, E), np.float32)
    np.add.at(dense, (token, np.broadcast_to(expert[:, None], token.shape)),
              weight)
    want = np.zeros((S, E), np.float32)
    np.add.at(want, (np.arange(S)[:, None], chosen), w)
    np.testing.assert_array_equal(dense, want)
    assert (weight > 0).sum() == S * k and (np.diff(expert[
        :np.flatnonzero(weight.any(1))[-1] + 1]) >= 0).all()


def test_the_reference_refuses_another_block():
    from benchmark import reference_smallthinker as reference
    reference.check_supported(FIELDS)
    reference.check_supported(TOY)
    for change in ({"moe_router_ahead": False},
                   {"moe_expert_form": "swiglu"}, {"moe_scoring": "sigmoid"},
                   {"rope_sliding_only": False}, {"qk_norm": True},
                   {"moe_shared_experts": 1}, {"norm_scheme": "sandwich"},
                   {"layer_types": None}, {"attention": "mla"}):
        with pytest.raises(ValueError, match="reference_smallthinker"):
            reference.check_supported({**FIELDS, **change})
    with pytest.raises(ValueError):
        reference.check_supported(OPT)


# ---------------------------------------------------------------------------
# the cell's files
# ---------------------------------------------------------------------------
def test_the_cell_and_its_traffic_say_what_the_issue_asked():
    cell = json.loads((BENCH / "workloads" / f"{CELL}.json").read_text())
    theirs = json.loads((BENCH / "workloads" / f"{TRINITY}.json")
                        .read_text())
    # the engine as trinity's cell, the traffic the accepted file
    assert cell["engine"] == theirs["engine"] == {
        "dtype": "bfloat16", "use_paged_kernel": True,
        "state_manager": {"max_tracked_sequences": 16,
                          "max_ragged_batch_size": 16384,
                          "max_seq_len": 8704, "block_size": 16,
                          "num_blocks": 8721}}
    traffic = json.loads((BENCH / "traffic" / f"{TRAFFIC}.json")
                         .read_text())
    assert (traffic["runner"], traffic["rows"], traffic["prompt_len"],
            traffic["new_tokens"], traffic["temperature"],
            traffic["distinct_batches"], traffic["check_rows"]) \
        == ("generate_kv", 16, 8192, 512, 0.0, 4, 4)
    assert cell["control"] == {"engine": {"kv_quant": True}}
    # each limit lies between its two chip readings: the sound runs'
    # largest and the control's smallest (PERF.md section 4)
    for name, sound, control in (("logit_err", 1.174e-2, 1.31e-1),
                                 ("token_gap", 1.20e-2, 1.17e-1),
                                 ("kv_err", 4.332e-3, 5.636e-2)):
        assert 2.5 * sound <= cell["limits"][name]["limit"] <= control / 2.5
    for name in ("logit_err", "token_gap", "kv_err"):
        assert 0 < cell["limits"][name]["limit"] < 1
        assert "control" in cell["limits"][name]["from"]
        assert 0 < cell["rehearse"]["limits"][name]["limit"] < 1
    for key in ("overrides", "who", "sizing"):
        assert cell[key]
    # the runner reads a ring ahead of every routed expert: the first
    # layer the stage holds is a window layer
    assert FIELDS["layer_types"][0] == "sliding_attention" \
        and "moe_first_dense_layers" not in FIELDS


def test_the_cell_rehearses_with_its_readers():
    p = run_py(["--workload", CELL, "--rehearse", "--trace", "1",
                "--seed", str(2 ** 31 + 5800000001)])
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
    reads a hundredth of it. The check pass alone (``generate_kv.
    kv_error``: an engine of its own, one call, the ring against the
    reference), behind no timed window: no clock decides the case. The
    sound run's ``logit_err`` and ``token_gap`` are the rehearsal's
    (``correct True`` above)."""
    import time
    import jax
    from benchmark import run as harness
    from benchmark.evidence import Context
    from benchmark.runners import generate_kv
    cell, config, traffic = harness.load_cell(CELL, rehearse=True)
    limit = cell["limits"]["kv_err"]["limit"]
    read = {}
    for on in (False, True):
        ctx = Context(
            cell=merge(cell, cell["control"]) if on else cell, config=config,
            traffic=traffic, seed=2 ** 31 + 5800000002, seconds=0.0,
            trace=False, rehearse=True, devices=jax.devices()[:1],
            clock=harness.CompileClock(),
            t_process_start=time.perf_counter(), log=harness.log,
            scratch=REPO / ".bench_scratch" / CELL)
        read[on], parts = generate_kv.kv_error(ctx)
        # the first layer's ring alone, both leaves, every check row
        assert {(layer, leaf) for _, layer, leaf in parts} \
            == {(0, "k"), (0, "v")} and len(parts) == 2 * 2
    assert read[False] <= limit / 20
    assert read[True] >= 50 * limit
