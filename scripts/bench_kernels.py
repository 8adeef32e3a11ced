"""What a decode launch of the attention walk costs, part by part, on the chip.

    chiprun -- python scripts/bench_kernels.py [--only NAME] [--sweep]
        [--check] [--launches 200] [--pool together|shuffled]
        [--runs gate|always|never]

The decode launches of the generation cells that cache positions
(``ragged_attention_tiled`` / ``_window`` / ``_latent`` in their one-token
form, at each cell's rows,
pool, table and contexts), alone, over a random pool: a ``fori_loop`` of
``--launches`` launches in one jitted program, the
next launch's queries made from the last one's output, timed on the host
clock round ``block_until_ready``; microseconds a launch, the best of three.
Beside each time, the launch's BYTES at the chip's peak (the pages its rows
hold, once a row; the window's where there is one): what a launch cannot go
under.

``--pool`` says how the rows' tables lie (PR 67): ``shuffled`` (the default,
and what every number before PR 67 was read over) hands each place a block of
a permutation, no two neighbours together, the pool of a server long in
service; ``together`` hands a row its places' blocks in ascending order, what
``BlockedAllocator`` gives a ``generate()`` call's rows; ``steps-N`` lays a
row's blocks in runs of N pages with the rows' runs interleaved, what chunk
steps that feed every row N pages each leave behind (``lfm2``'s 4, nemotron's
8, falcon's 16), and ``--run-pages`` reads the least run (``_RUN_PAGES``)
otherwise than the tree does. A tiled launch is
handed the table's runs (``ragged_attention.launch_runs``) as the serving
programs hand them: where ``runs_serve`` says so (``--runs gate``), whatever
the table's width (``always``: how the gate is read) or not at all (``never``:
the launch as it was before PR 67); the row's ``runs`` says which it got, and
``pages_a_descriptor`` what ``copy_counts`` counts under the launch. The
latent kernel is handed none.

``ssm_state_update`` (``kernels/state_space.py``) stands beside them at the
two state-space cells' decode shapes, the launch as ``paged_model`` makes it
(decay, ``dt x`` and the pairs' reshape are XLA's, in the time): the leaf is
carried through the loop, aliased, and the bytes are the rows' states read
once and written once.

The two power-retention kernels (``kernels/power_retention.py``) stand
there too at their cell's shapes: ``retention_state_update`` for a decode
step's 16 rows (a row's 8 key/value heads of 65 x 128 x 128 float32 read once
and written once, five query heads reading each) and ``retention_chunk_fwd``
for a prompt launch's 16 rows of 512 tokens (bytes: the launch's q, k, v, gate
and output and the rows' states once each way; ``copies`` there is the
kernel with its distances' loop taken out: the windows' copies, the decay
and the attention form inside a chunk).

The convolution's one-token kernel (``conv_update`` of
``kernels/linear_attention.py``: ``ssm_conv_update`` with one input and a
bias at nemotron's and granite's decode shapes, ``kda_conv_update`` with q,
k and v at ling's) stands there as well, the leaf carried through the loop,
bytes the rows' slots once each way: the kernel's own time FROM HBM, which
is what a decode program pays since PR 57 (the kernel colours its leaf HBM:
``kernels/slot_leaf.py``; on a tree before it the loop's leaf, where it
fits, lies in the chip's fast memory from launch to launch and the time is
the kernel's from THERE).

THE EXPERTS' DISPATCH (PR 61), ``dispatch-rows``: how the routed rows come
back from expert order (``moe/sharded_moe.dropless_topk_dispatch``'s last
lines), alone, at the sparse cells' launches: granite's, nemotron's and
ling's runs (a share: half to three quarters of the picks held elsewhere,
the rows past the last group NaN), smallthinker's and trinity-mini's chunk
steps, and the three shares' decode steps. ``xla`` is the gather and the
fusion every caller had before PR 61 (``gather_rows_combine``), ``kernel``
``kernels/expert_combine.rows_combine`` (two launches), ``whole`` its first
launch alone (the pairs made one piece each), ``copies`` the two with the
arithmetic taken out, ``no-skip`` with the picks held elsewhere relaid and
copied too (``--sweep``: ``starts-N`` / ``sums-N``, the copies started and
the tokens summed a trip of the kernel's loops). Beside the time: the rows, the picks held here (``copy_starts``)
and the bytes no form can go under (the held rows read once, the result
written once). The experts' output is carried through the loop and a row of
it touched a launch, so that no launch's work is the loop's invariant.
``expert_combine.MIN_ROWS`` is read off this table: the row count from which
``kernel`` is under ``xla``.

A SHARE'S RUN THROUGH THE GROUPED MATMUL (PR 64), ``share-gmm``: one
run-dispatch's ``gmm`` launches alone (``moe/sharded_moe.gmm_swiglu_experts``
/ ``gmm_relu2_experts``, as ``dropless_topk_dispatch`` calls them) at the
three share cells' experts: granite's (72 experts, 36 held, 10 picks of
4,096 x 768), nemotron's (128, 64 held, 6 picks, relu2 of 2,688 x 1,856) and
ling's (512, 128 held, 8 picks of 2,560 x 768), the weights a scanned STACK's
(``layers`` x held groups of which one layer's are non-empty, the layer turning
launch by launch), every token's picks distinct and random, the rows sorted
as the dispatch sorts them. A row of the output a RUN of 2,048, 4,096, 8,192
and 16,384 tokens (``--contexts``); ``tree_run`` is what this tree's
``paged_model._share_tokens`` gives the cell's launch. Beside the time: us a
TOKEN (what a longer run is for), the mean rows a held expert, the row tiles
the kernel visits (one a group a tile it spans) over the tiles the rows fill,
the touched experts' bytes and the operations at the chip's peaks.
``--sweep`` adds tilings the program does not run: ``k-tiles`` (an expert's
weight cut along the CONTRACTED width in place of its columns, so the rows
pass once a matrix), ``rows-256`` (the row tile; 512 is over the
kernel's fast memory) and ``aligned`` (every expert's rows from a whole row
tile on, the groups padded to whole tiles: no tile spans two groups).
``--rehearse`` runs a toy share through ``ragged_*_experts`` on the CPU.

The variants take the walk apart by replacing one function of
``kernels/ragged_attention.py`` in this process (nothing a cell runs is
touched, and no option of the program exists for it):

* ``full``           the launch as the engine runs it
* ``copies``         the same walk with the products taken out
                     (``_tile_update`` / ``_blocks_update`` /
                     ``_latent_update`` do nothing): starts, waits and
                     the loop; of the state update, the grid's copies of
                     the states in and back with the token taken out
* ``page-waits``     a wait a page, as before PR 50           (--sweep)
* ``unroll-N``       ``_START_UNROLL`` = N (1, 4): the start loop's trips
                                                              (--sweep)

THE PROMPT'S LAUNCHES of the two 8k-context cells stand beside the decode
ones (PR 59): ``trinity-prompt-full`` / ``-window`` and ``smallthinker-prompt-
full`` / ``-window``, the token tile's launch of a chunk step: 16 rows x 1,024
new tokens that end at contexts 1,024, 4,096 and 8,192 (``--contexts``; a row
of the output each), over the cell's table (544 pages, or the window's ring).
Beside the bytes stand the launch's OPERATIONS at the matrix unit's peak
(``benchmark/arith_window.py``'s count: what the ledger's ``window_roofline
.gen`` divides by); a tenth of ``--launches`` a program (a launch is 5-20 ms);
``--check`` holds 32 sampled tokens to the gathering reference (every token's
gathered context would be terabytes). Their variants (all but the first
timing only: wrong sums):

* ``copies``         as above (``_tile_update`` does nothing)
* ``no-mask``        no chunk's mask built, nothing selected
* ``no-copies``      no page copied or waited for: the walk's loops and the
                     products over whatever the slots hold     (--sweep)
* ``no-exp`` / ``no-reduce`` / ``no-pv``   the update without its
                     exponentials, without its rows' max and sum, without
                     its second product                        (--sweep)
* ``halves``         the update as two products over 256 positions each
                                                               (--sweep)
* ``tq-N``           the tile N tokens (``_token_tile``), alone and with each
                     of the others                             (--sweep)

A SELECTING PROMPT LAUNCH OF A FULL LATENT LAYER (PR 69), ``dots3-picked``:
1,024 tokens (and, ``dots3-picked-256`` / ``-128``, 256 and 128: either side of
the rule's threshold, at context 8,192) of ONE row of ``dots3-note-prev`` (128 heads, ranks 512 + 64,
``nope`` / ``v`` 128) that end at contexts 4,096, 8,192 and 16,384
(``--contexts``) under a table of 34,816 (the cell's chunk steps ride tables
at their full width), each token's picks ~2,048 of the positions under its
bound, at random. The forms stand side by side as variants of one launch:

* ``absorbed``        the masked latent kernel as the program ran it before
                      PR 69, with the two projections round it (``q_nope
                      Wk^T`` ahead, ``o_lat Wv`` behind)
* ``absorbed-kernel`` ``latent_attention(picked=)`` alone
* ``expanded``        the row's keys and values made a group of heads at a
                      time (``latent_rows_expand``, the pieces under the
                      row's reach alone) and the per-head kernel over them
                      (``paged_model._expanded_picked_attention``): what the
                      program runs now
* ``kernel``          ``picked_heads_attention`` alone, over keys and values
                      made ahead of the loop
* ``no-mask`` / ``no-reduce``   that kernel without its mask (the picks'
                      flags and the bound: neither built nor selected by),
                      without its max and sum (``_tile_update`` replaced as
                      the prompt shapes' variants replace it; timing only:
                      wrong sums): what the softmax costs a score at 320
                      products
* ``tq-N`` / ``hs-N`` / ``chunk-N`` / ``heads-N``   the kernel's tile, its
                      heads a step, its chunk (joined by ``+``: both); the
                      heads expanded at once                   (--sweep)

``flops`` is the EXPANDED form's count with a 192-wide score as the two
128-wide passes the matrix unit makes of it, the row's expansion in it;
``--check`` holds ``expanded`` to ``absorbed`` through the gathering
reference on 32 sampled tokens.

A TILE OF THE INDEXER of such a launch (PR 71), ``dots3-indexer``: 256 tokens
of one row whose largest bound (the tile's REACH) is 4,096, 8,192, 16,384 and
33,024 (``--contexts``) under a table of 33,024 positions, 64 heads of 128:

* ``scores``          ``paged_model._token_scores``: the chunks of 4,096 keys
                      the reach asks for, gathered through the table, the
                      products with ``relu``, the weights and the sum over
                      heads behind them
* ``flags``           ``paged_model.index_mask`` turned as the program's tile
                      leaves it (int8, a token a lane): the scores and the
                      2,048 picks a token found by counting

``flops`` is what the model's equations ask (a token's bound), ``swept_flops``
what the tree's products cover (whole chunks under the reach; the whole table
in a tree from before PR 71) and ``scores_peak_share`` / ``flags_peak_share``
that over the time at the matrix unit's peak: the fusion's rate, read and not
inferred. ``--check`` holds the scores under every bound to one full-width
product.

A tree that lacks a function a variant replaces (an older commit) skips that
variant and says so. ``--check`` holds one launch of each shape to the
gathering reference. Like the other chip scripts it exits non-zero without a
TPU; ``--rehearse`` runs toy sizes under the interpreter on the CPU, which
says the script runs and nothing about time.
"""

import argparse
import contextlib
import functools
import importlib
import json
import os
import sys
import time
import types

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax                                                    # noqa: E402
import jax.numpy as jnp                                       # noqa: E402
import numpy as np                                            # noqa: E402
from jax.experimental.pallas import tpu as pltpu              # noqa: E402

# the package exports a function under the module's name
ra = importlib.import_module(                                 # noqa: E402
    "deepspeed_tpu.inference.v2.kernels.ragged_attention")
from deepspeed_tpu.inference.v2.kernels import state_space as ss  # noqa: E402
try:                            # a tree before PR 61: its shapes skip
    from deepspeed_tpu.inference.v2.kernels import (      # noqa: E402
        expert_combine as ec)
except ImportError:
    ec = None
from deepspeed_tpu.inference.v2.kernels import (          # noqa: E402
    linear_attention as la)
try:                            # a tree before the kind: its shapes skip
    from deepspeed_tpu.inference.v2.kernels import (      # noqa: E402
        power_retention as pr)
except ImportError:
    pr = None

from deepspeed_tpu.moe import sharded_moe as sm               # noqa: E402
from deepspeed_tpu.inference.v2 import paged_model as pm      # noqa: E402

from benchmark import arith_window                            # noqa: E402

PEAK_BYTES_S = 819e9            # TPU v5e (benchmark/peaks.json)
PEAK_FLOPS_S = 197e12

# name -> kernel, rows, layers, kv heads (tiled) , head width / pool row,
# query heads, contexts [lo, hi), window, the ring's pages (0: a table that
# holds every position)
SHAPES = {
    "opt-1.3b": dict(kernel="tiled", rows=16, layers=24, kvh=32, hd=64,
                     nh=32, ctx=(256, 512), window=0, ring=0),
    "joyai-latent": dict(kernel="latent", rows=64, layers=5, W=640, nh=32,
                         ctx=(128, 384)),
    "ling-latent": dict(kernel="latent", rows=128, layers=1, W=640, nh=32,
                        ctx=(128, 384)),
    "trinity-full": dict(kernel="tiled", rows=16, layers=1, kvh=4, hd=128,
                         nh=32, ctx=(8192, 8704), window=0, ring=0),
    "trinity-window": dict(kernel="tiled", rows=16, layers=4, kvh=4, hd=128,
                           nh=32, ctx=(8192, 8704), window=2048, ring=193),
    # a query group of SEVEN (28 on 4), beside trinity's eight: the full
    # layers' launch at the same contexts, the window layers' at 4,096
    # over rings of 321 pages
    "smallthinker-full": dict(kernel="tiled", rows=16, layers=2, kvh=4,
                              hd=128, nh=28, ctx=(8192, 8704), window=0,
                              ring=0),
    "smallthinker-window": dict(kernel="tiled", rows=16, layers=6, kvh=4,
                                hd=128, nh=28, ctx=(8192, 8704), window=4096,
                                ring=321),
    "granite-full": dict(kernel="tiled", rows=64, layers=1, kvh=8, hd=128,
                         nh=32, ctx=(1024, 1280), window=0, ring=0),
    # PR 67: the other cells' decode launches, where the table's runs are
    # weighed against a table's width (``ragged_attention.runs_serve``)
    "falcon-full": dict(kernel="tiled", rows=64, layers=4, kvh=4, hd=128,
                        nh=20, ctx=(1024, 1536), window=0, ring=0),
    "lfm2-full": dict(kernel="tiled", rows=256, layers=3, kvh=8, hd=64,
                      nh=32, ctx=(512, 1024), window=0, ring=0),
    "nemotron-full": dict(kernel="tiled", rows=128, layers=2, kvh=2, hd=128,
                          nh=32, ctx=(256, 640), window=0, ring=0),
    # the token tile's launch of a chunk step of the two 8k cells: ``new``
    # tokens a row that end at each of ``ends`` (a row of the output each),
    # over the cell's table of 544 pages or the window's ring
    "trinity-prompt-full": dict(kernel="prompt", rows=16, new=1024, kvh=4,
                                hd=128, nh=32, ends=(1024, 4096, 8192),
                                window=0, ring=0),
    "trinity-prompt-window": dict(kernel="prompt", rows=16, new=1024, kvh=4,
                                  hd=128, nh=32, ends=(1024, 4096, 8192),
                                  window=2048, ring=193),
    "smallthinker-prompt-full": dict(kernel="prompt", rows=16, new=1024,
                                     kvh=4, hd=128, nh=28,
                                     ends=(1024, 4096, 8192), window=0,
                                     ring=0),
    "smallthinker-prompt-window": dict(kernel="prompt", rows=16, new=1024,
                                       kvh=4, hd=128, nh=28,
                                       ends=(1024, 4096, 8192), window=4096,
                                       ring=321),
    # the one-token state-space update: heads of ``p`` channels, ``groups``
    # pairs of B and C a token, a state of ``n`` a channel
    "nemotron-state": dict(kernel="ssm_state", rows=128, layers=7, nh=64,
                           p=64, n=128, groups=8),
    "granite-state": dict(kernel="ssm_state", rows=64, layers=9, nh=128,
                          p=64, n=128, groups=1),
    # power retention: ``kvh`` states of [hd / 2 + 1, hd, hd] a row, read
    # by ``nh`` query heads; ``tokens`` a row of the prompt launch. Two
    # layers of the cell's eight: a launch is one layer's, and --check
    # holds three copies of the leaf (0.58 GB a layer)
    "brumby-state": dict(kernel="retention_state", rows=16, layers=2,
                         nh=40, kvh=8, hd=128),
    "brumby-chunk": dict(kernel="retention_chunk", rows=16, layers=2,
                         nh=40, kvh=8, hd=128, tokens=512),
    # the convolution's one-token kernel: ``width`` channels in ``parts``
    # arrays side by side, four taps
    "nemotron-conv": dict(kernel="conv", rows=128, layers=7, width=6144,
                          parts=1, bias=True, name="ssm_conv_update"),
    "granite-conv": dict(kernel="conv", rows=64, layers=9, width=8448,
                         parts=1, bias=True, name="ssm_conv_update"),
    "ling-conv": dict(kernel="conv", rows=128, layers=7, width=12288,
                      parts=3, bias=False, name="kda_conv_update"),
    # how the routed rows come back from expert order: ``tokens`` of k
    # picks over ``experts`` of which the first ``held`` are held here
    "granite-dispatch": dict(kernel="dispatch", tokens=2048, k=10, H=4096,
                             experts=72, held=36),
    "smallthinker-dispatch": dict(kernel="dispatch", tokens=16384, k=6,
                                  H=2560, experts=64, held=64),
    "trinity-dispatch": dict(kernel="dispatch", tokens=16384, k=8, H=2048,
                             experts=128, held=128),
    "nemotron-dispatch": dict(kernel="dispatch", tokens=4096, k=6, H=2688,
                              experts=128, held=64),
    "ling-dispatch": dict(kernel="dispatch", tokens=4096, k=8, H=2560,
                          experts=512, held=128),
    "granite-dispatch-decode": dict(kernel="dispatch", tokens=64, k=10,
                                    H=4096, experts=72, held=36),
    "nemotron-dispatch-decode": dict(kernel="dispatch", tokens=128, k=6,
                                     H=2688, experts=128, held=64),
    "ling-dispatch-decode": dict(kernel="dispatch", tokens=128, k=8,
                                 H=2560, experts=512, held=128),
    # a share's run through the grouped matmul: tokens of k picks over
    # ``experts`` of which the first ``held`` are held here, an expert of
    # ``form`` H x F, the stack ``layers`` expert layers deep; ``launch``
    # the cell's ragged step (tokens)
    "granite-share-gmm": dict(kernel="share_gmm", k=10, H=4096, F=768,
                              experts=72, held=36, layers=10, form="swiglu",
                              launch=16384, ends=(2048, 4096, 8192, 16384)),
    "nemotron-share-gmm": dict(kernel="share_gmm", k=6, H=2688, F=1856,
                               experts=128, held=64, layers=7, form="relu2",
                               launch=16384, ends=(2048, 4096, 8192, 16384)),
    "ling-share-gmm": dict(kernel="share_gmm", k=8, H=2560, F=768,
                           experts=512, held=128, layers=6, form="swiglu",
                           launch=16384, ends=(2048, 4096, 8192, 16384)),
    # a selecting prompt launch of a full latent layer (PR 69): ``new``
    # tokens of one row that end at each of ``ends``, ``topk`` picks a token
    # under a table of ``table`` positions (the cell's chunk steps ride
    # tables at their full width, 33,024, to whole pieces of 2,048)
    "dots3-picked": dict(kernel="picked", new=1024, nh=128, dc=512, dr=64,
                         dn=128, dv=128, W=640, topk=2048, table=34816,
                         ends=(4096, 8192, 16384)),
    # the same launch at fewer tokens a row: either side of the rule's
    # threshold (``paged_model._EXPAND_TOKENS_A_ROW``)
    "dots3-picked-256": dict(kernel="picked", new=256, nh=128, dc=512, dr=64,
                             dn=128, dv=128, W=640, topk=2048, table=34816,
                             ends=(8192,)),
    "dots3-picked-128": dict(kernel="picked", new=128, nh=128, dc=512, dr=64,
                             dn=128, dv=128, W=640, topk=2048, table=34816,
                             ends=(8192,)),
    # a tile of the indexer of that launch (PR 71): ``new`` tokens of one
    # row whose largest bound is each of ``ends``
    "dots3-indexer": dict(kernel="indexer", new=256, heads=64, d=128,
                          topk=2048, table=33024,
                          ends=(4096, 8192, 16384, 33024)),
}
# the family's name stands for its shapes under --only
FAMILIES = {"dots3-picked": [n for n, v in SHAPES.items()
                             if v["kernel"] == "picked"],
            "dispatch-rows": [n for n, v in SHAPES.items()
                              if v["kernel"] == "dispatch"],
            "share-gmm": [n for n, v in SHAPES.items()
                          if v["kernel"] == "share_gmm"]}
BS = 16
EPS = 1e-6


def build_dispatch(shape, rng, rehearse):
    """One dispatch's way back: ``(fn(topv, _, ys) -> (out, ys), again,
    topv, (ys,), 1, bytes a launch, reference)``. Every token picks k
    distinct experts at random; ``ys`` is in expert order as the stable
    sort of the pick-major keys leaves it, its rows past the last group
    NaN. ``fn.rows`` / ``fn.held``: the dispatch's rows and those held
    here."""
    from deepspeed_tpu.moe.sharded_moe import gather_rows_combine
    T, k, H, E, here = (shape[n] for n in
                        ("tokens", "k", "H", "experts", "held"))
    if rehearse:
        T, H = 24, 256
    topi = np.argsort(rng.random((T, E)), axis=1)[:, :k]
    idx = topi.T.reshape(-1)
    held = idx < here
    order = np.argsort(np.where(held, idx, here), kind="stable")
    inv = jnp.asarray(np.argsort(order), jnp.int32)
    n_held = int(held.sum())
    held = None if here == E else jnp.asarray(held)
    key = jax.random.PRNGKey(int(rng.integers(1 << 30)))
    ys = jax.random.normal(key, (k * T, H), jnp.bfloat16)
    ys = jnp.where(jnp.arange(k * T)[:, None] < n_held, ys, jnp.nan)
    topv = jax.random.uniform(jax.random.fold_in(key, 1), (T, k))

    def touched(out, ys):
        # the loop carries ``ys`` and a launch rewrites one value of it
        return out, ys.at[0, 0].add((out[0, 0] * 0).astype(ys.dtype))

    def fn(topv, _, ys):
        return touched(ec.rows_combine(
            ys, inv, held, topv, jnp.int32(n_held), interpret=rehearse), ys)

    def ref(topv, _, ys):
        rows = ys.astype(jnp.float32)[inv].reshape(k, T, H)
        if held is not None:
            rows = jnp.where(held.reshape(k, T, 1), rows, 0)
        return touched(jnp.sum(rows * topv.T[..., None], axis=0).astype(
            ys.dtype), ys)

    def again(topv, out):
        return topv + out[:1, :k].astype(jnp.float32) * 0
    fn.rows, fn.held = k * T, n_held
    fn.xla = lambda ys, inv, held, topv, rows_held, interpret=False: \
        gather_rows_combine(ys, inv, held, topv)
    return fn, again, topv, (ys,), 1, 2 * H * (n_held + T), ref


def build_share_gmm(shape, run, rng, rehearse):
    """One run-dispatch's grouped matmuls over a stack's groups: ``(fn(xs,
    layer, *weights) -> ys, again, xs, weights, layers, (bytes, flops) a
    dispatch, reference)``. ``run`` tokens pick k distinct experts each at
    random; ``xs`` holds their rows in expert order, the picks held
    elsewhere behind every group (``dropless_topk_dispatch``'s order).
    ``fn.rows`` / ``fn.held`` / ``fn.an_expert``: the dispatch's rows,
    those held here and their mean a held expert;
    ``fn.visits`` / ``fn.tiles``: the row tiles the kernel visits (one a
    group a tile it spans) and the tiles the held rows fill;
    ``fn.tree_run``: this tree's run at the cell's launch."""
    from deepspeed_tpu.inference.v2 import paged_model
    k, H, F, E, here, L, form = (shape[n] for n in (
        "k", "H", "F", "experts", "held", "layers", "form"))
    launch = shape["launch"]
    if rehearse:
        k, H, F, E, here, L, run, launch = 4, 256, 128, 16, 8, 2, run // 32, 512
    topi = np.argsort(rng.random((run, E)), axis=1)[:, :k]
    idx = topi.T.reshape(-1)
    sizes = np.bincount(idx[idx < here], minlength=here).astype(np.int32)
    n_held = int(sizes.sum())
    ends = np.cumsum(sizes)
    at = sizes > 0
    visits = int(((ends[at] - 1) // sm._GMM_ROWS
                  - (ends[at] - sizes[at]) // sm._GMM_ROWS + 1).sum())
    dtype = jnp.float32 if rehearse else jnp.bfloat16
    key = jax.random.PRNGKey(int(rng.integers(1 << 30)))
    xs = jax.random.normal(key, (k * run, H), dtype)
    # one layer's experts at random, the stack that layer scaled layer by
    # layer: a stack's worth of random bits would be gigabytes beside it
    shapes = ((H, F), (H, F), (F, H)) if form != "relu2" \
        else ((F, H), (F, H))
    scale = 1 + jnp.arange(L, dtype=jnp.float32)[:, None, None, None] / L
    weights = tuple(jax.jit(lambda i, s=s: (
        jax.random.normal(jax.random.fold_in(key, i), (1, here, *s))
        * (0.5 * s[0] ** -0.5) * scale).astype(dtype))(i)
        for i, s in enumerate(shapes, 1))
    sizes = jnp.asarray(sizes)

    def through(form_fn, xs, layer, weights, sizes=sizes):
        """The rows through one layer of the stack, chosen as
        ``dropless_topk_dispatch(stack_layer=)`` chooses it: by where its
        groups lie among layers x held."""
        return form_fn(
            tuple(w.reshape(-1, *w.shape[2:]) for w in weights), xs,
            jax.lax.dynamic_update_slice(
                jnp.zeros((L * here,), jnp.int32), sizes, (layer * here,)))

    def launch_of(sizes):
        def fn(xs, layer, *weights):
            # looked up when traced: a variant replaces the module's
            ragged, gmm, _ = sm.expert_forms(form)
            return through(ragged if rehearse else gmm, xs, layer, weights,
                           sizes)
        return fn

    fn = launch_of(sizes)
    # every expert's rows from a whole row tile on: the groups padded to
    # whole tiles (rows of the buffer that no pick owns; timing only: the
    # rows stay where they lay), no tile spans two; its launch wants
    # ``pad`` rows more
    whole = -(-sizes // sm._GMM_ROWS) * sm._GMM_ROWS
    fn.aligned = launch_of(whole)
    fn.aligned.visits = int(whole.sum()) // sm._GMM_ROWS
    fn.aligned.pad = here * sm._GMM_ROWS

    def ref(xs, layer, *weights):
        return through(sm.expert_forms(form)[0], xs, layer, weights)

    def again(xs, ys):
        # the loop carries the rows and a launch rewrites one of them
        return xs.at[0].add(ys[0] * 0)
    try:
        tree_run = min(launch, paged_model._share_tokens(
            types.SimpleNamespace(hidden_size=H, moe_top_k=k,
                                  moe_num_experts=E), dtype))
    except (TypeError, AttributeError):     # before PR 64: bytes alone
        tree_run = min(launch, paged_model._share_tokens(
            jax.ShapeDtypeStruct((launch, H), dtype), k))
    fn.rows, fn.held, fn.tree_run = k * run, n_held, tree_run
    fn.visits, fn.tiles = visits, round(n_held / sm._GMM_ROWS, 1)
    fn.tokens, fn.an_expert = run, round(n_held / here, 1)
    item = jnp.dtype(dtype).itemsize
    nbytes = item * (int(at.sum()) * len(shapes) * H * F + 2 * n_held * H)
    return (fn, again, xs, weights, L,
            (nbytes, 2 * n_held * H * F * len(shapes)), ref)


def build_retention(shape, rng, rehearse):
    """One launch of a power-retention kernel, as ``build_state`` gives
    it: ``(fn(q, layer, state, norm) -> (o, state, norm), again, q,
    (state, norm), layers, bytes a launch, reference)``."""
    rows, L, nh, kvh, hd = (shape[k] for k in
                            ("rows", "layers", "nh", "kvh", "hd"))
    chunked = shape["kernel"] == "retention_chunk"
    per = shape.get("tokens", 1)
    if rehearse:
        rows, L, nh, kvh, hd, per = 3, 2, 4, 2, 16, 20 if chunked else 1
    T = rows * per
    key = jax.random.PRNGKey(int(rng.integers(1 << 30)))
    f = lambda i, *s: jax.random.normal(jax.random.fold_in(key, i), s)
    shapes = pr.leaf_shapes(L, rows + 1, kvh, hd)
    @jax.jit
    def held():
        """A state as tokens leave it: sums of phi(k) v^T, and a
        normaliser > 0."""
        pk = pr.phi(f(0, L, rows + 1, kvh, hd))
        return (pk[..., None, :] * f(1, L, rows + 1, kvh, 1, hd, 1),
                jnp.pad(jnp.abs(pk) + 0.1, [(0, 0)] * 3 + [
                    (0, shapes[1][-2] - pk.shape[-2]), (0, 0)]))

    state, norm = held()
    assert state.shape == shapes[0] and norm.shape == shapes[1]
    q, k, v = f(2, T, nh, hd), f(3, T, kvh, hd), f(4, T, kvh, hd)
    g = -jax.random.uniform(jax.random.fold_in(key, 5), (T, kvh),
                            minval=0.01, maxval=3.0)
    slots = jnp.asarray(rng.permutation(rows) + 1, jnp.int32)
    fresh = jnp.zeros(rows, bool)
    starts = jnp.arange(rows, dtype=jnp.int32) * per
    counts = jnp.full((rows,), per, jnp.int32)
    if chunked:
        kw = dict(chunk=16) if rehearse else {}

        # the function under its own jit: that jit's cache would hand a
        # variant the trace of the one before it
        chunk_fwd = getattr(pr.retention_chunk_fwd, "__wrapped__",
                            pr.retention_chunk_fwd)

        def fn(q, layer, state, norm):
            return chunk_fwd(
                state, norm, layer, slots, fresh, starts, counts, q, k, v,
                g, EPS, interpret=rehearse, **kw)

        def ref(q, layer, state, norm):
            return pr.retention_chunked(
                state, norm, layer, slots, fresh, starts, counts, q, k, v,
                g, EPS, **kw)
        moved = 4 * T * hd * (2 * nh + 2 * kvh + kvh / hd)
    else:
        def fn(q, layer, state, norm):
            return pr.retention_state_update(
                state, norm, layer, slots, fresh, q, k, v, g, EPS,
                interpret=rehearse)

        def ref(q, layer, state, norm):
            return pr.retention_step(state, norm, layer, slots, fresh, q,
                                     k, v, g, EPS)
        moved = 0

    def again(q, o):
        return q + o * 0
    held = rows * (state[0, 0].nbytes + norm[0, 0].nbytes)
    return fn, again, q, (state, norm), L, int(2 * held + moved), ref


def build_conv(shape, rng, rehearse):
    """One launch of ``conv_update`` as the decode programs make it (the
    projections float32): ``(fn(x, layer, leaf) -> (the first part out,
    leaf), again, x, (leaf,), layers, the slots' bytes a launch,
    reference)``; every row a slot of its own, out of order."""
    rows, L, width, n = (shape[k] for k in
                         ("rows", "layers", "width", "parts"))
    K = 4
    if rehearse:
        rows, L, width = 3, 2, 256 * n
    key = jax.random.PRNGKey(int(rng.integers(1 << 30)))
    f = lambda i, *s: jax.random.normal(jax.random.fold_in(key, i), s)
    leaf = f(0, *la.conv_leaf_shape(L, rows + 1, K, width))
    x, *others = (f(1 + i, rows, width // n) for i in range(n))
    taps = f(5, K, width).astype(jnp.bfloat16)
    bias = f(6, width).astype(jnp.bfloat16) if shape["bias"] else None
    slots = jnp.asarray(rng.permutation(rows) + 1, jnp.int32)
    fresh = jnp.zeros(rows, bool)

    def fn(x, layer, leaf):
        mixed, leaf = la.conv_update(
            leaf, layer, slots, fresh, (x, *others), taps, bias,
            name=shape["name"], interpret=rehearse)
        return mixed[0], leaf

    def ref(x, layer, leaf):
        def act(y):
            return jax.nn.silu(y if bias is None
                               else y + bias.astype(jnp.float32))
        held = leaf[layer, slots].reshape(rows, K - 1, width)
        y, held = la.causal_conv_step(
            jnp.concatenate((x, *others), axis=-1), taps, held, act)
        return y[:, :width // n], leaf.at[layer, slots].set(
            held.reshape(rows, *leaf.shape[2:]))

    def again(x, y):
        return x + y * 0
    return fn, again, x, (leaf,), L, 2 * rows * leaf[0, 0].nbytes, ref


def build_state(shape, rng, rehearse):
    """One launch of ``ssm_state_update``: ``(fn(x, layer, leaf) -> (y,
    leaf), again, x, (leaf,), layers, the states' bytes a launch,
    reference)``; every row a slot of its own, out of order."""
    rows, L, nh = shape["rows"], shape["layers"], shape["nh"]
    p, n, groups = shape["p"], shape["n"], shape["groups"]
    if rehearse:
        rows, L, nh = 3, 2, max(16, 2 * groups)
    key = jax.random.PRNGKey(int(rng.integers(1 << 30)))
    f = lambda i, *s: jax.random.normal(jax.random.fold_in(key, i), s)
    leaf = f(0, *ss.state_leaf_shape(L, rows + 1, nh * p, n))
    x, b, c = f(1, rows, nh * p), f(2, rows, groups * n), f(3, rows,
                                                            groups * n)
    dt = jax.random.uniform(jax.random.fold_in(key, 4), (rows, nh),
                            minval=0.01, maxval=1.0)
    a = -jax.random.uniform(jax.random.fold_in(key, 5), (nh,), minval=1.0,
                            maxval=16.0)
    slots = jnp.asarray(rng.permutation(rows) + 1, jnp.int32)
    fresh = jnp.zeros(rows, bool)

    def fn(x, layer, leaf):
        return ss.ssm_state_update(leaf, layer, slots, fresh, x, dt, a, b,
                                   c, interpret=rehearse)

    def ref(x, layer, leaf):
        return ss.ssm_step(leaf, layer, slots, fresh, x, dt, a, b, c)

    def again(x, y):
        return x + y * 0
    return fn, again, x, (leaf,), L, 2 * rows * leaf[0, 0].nbytes, ref


def build(shape, rng, rehearse, pool="shuffled", runs="gate"):
    """One launch: ``(fn(q, layer, *pools) -> out, again(q, out) -> the
    next launch's q, q, the pools (arguments of the jitted program: a
    closed-over pool would be a constant of gigabytes in it), layers,
    bytes a launch, reference(q, layer, *pools))``. ``pool``: how the
    tables lie (``together``: a row's blocks ascending); ``runs``: whether
    a tiled launch is handed its table's runs (``fn.runs`` says whether it
    was, ``fn.pages_a_descriptor`` what the host's counter reads)."""
    rows, lo, hi = shape["rows"], *shape["ctx"]
    if rehearse:
        rows, lo, hi = 4, max(lo // 16, 20), max(hi // 16, 40)
    window, ring = shape.get("window", 0), shape.get("ring", 0)
    if rehearse and window:
        window, ring = 128, 13
    L = 2 if rehearse else shape["layers"]
    lens = rng.integers(lo, hi, rows)
    held = -(-lens // BS)                                   # pages a row
    MB = ring or int(-(-hi // BS))
    nb = 1 + rows * MB
    blocks = np.arange(1, nb)
    if pool.startswith("steps-"):
        # a row's blocks in runs of N pages, the rows' runs interleaved:
        # what chunk steps that feed every row N pages each lay down
        n = int(pool.split("-")[1])
        host_tables = np.zeros((rows, MB), np.int32)
        for at in range(0, MB, n):
            width = min(n, MB - at)
            host_tables[:, at:at + width] = 1 + at * rows + (
                np.arange(rows)[:, None] * width + np.arange(width))
    else:
        host_tables = (rng.permutation(blocks) if pool == "shuffled"
                       else blocks).reshape(rows, MB).astype(np.int32)
    lengths = jnp.asarray(lens, jnp.int32)
    tables = jnp.asarray(host_tables)
    row_ids = jnp.arange(rows, dtype=jnp.int32)
    key = jax.random.PRNGKey(int(rng.integers(1 << 30)))
    dtype = jnp.float32 if rehearse else jnp.bfloat16
    if shape["kernel"] == "latent":
        W, nh, dc = shape["W"], shape["nh"], 512
        pool = jax.random.normal(key, (L, nb, BS, W), dtype)
        q = jax.random.normal(jax.random.fold_in(key, 1), (nh, rows, W),
                              dtype)
        kw = dict(dc=dc, scale=192 ** -0.5)

        def fn(q, layer, pool):
            return ra.latent_attention(q, pool, layer, row_ids, lengths,
                                       tables, one_token=True,
                                       interpret=True if rehearse else None,
                                       **kw)

        def ref(q, layer, pool):
            return ra.latent_attention_reference(
                q, pool, layer, row_ids, lengths, tables, **kw)

        def again(q, out):
            return q.at[..., :dc].add(out * 0)
        nbytes = int(held.sum()) * BS * W * pool.dtype.itemsize
        pools = (pool,)
    else:
        kvh, hd, nh = shape["kvh"], shape["hd"], shape["nh"]
        k = jax.random.normal(key, (L, nb, BS, kvh * hd), dtype)
        v = jax.random.normal(jax.random.fold_in(key, 1), k.shape, dtype)
        q = jax.random.normal(jax.random.fold_in(key, 2), (rows, nh, hd),
                              dtype)

        # how the tables lie, made once as the serving programs make it
        # (a tree before PR 67 has no such thing, and is handed none)
        lie = None
        if hasattr(ra, "table_runs") and runs != "never":
            lie = ra.launch_runs(tables, k, hd) if runs == "gate" \
                else ra.table_runs(tables, ra._chunk_pages(MB, nb, BS))
        kw = {} if lie is None else dict(runs=lie)

        def fn(q, layer, k, v):
            return ra.ragged_attention(
                q, k, v, layer, row_ids, lengths, tables, window=window,
                one_token=True, variant="tiled", **kw)
        fn.runs = lie is not None
        if hasattr(ra, "copy_counts"):
            first = np.maximum(lens - window, 0) // BS if window \
                else np.zeros_like(held)
            pages, descs = ra.copy_counts(
                host_tables, np.arange(rows), first, held - first,
                ra._chunk_pages(MB, nb, BS), ring, runs=fn.runs)
            fn.pages_a_descriptor = round(pages / max(descs, 1), 2)

        def ref(q, layer, k, v):
            return ra.ragged_attention_reference(
                q, k, v, layer, row_ids, lengths, tables, window=window)

        def again(q, out):
            return q + out * 0
        seen = held if not window \
            else held - np.maximum(lens - window, 0) // BS
        nbytes = 2 * int(seen.sum()) * BS * kvh * hd * k.dtype.itemsize
        pools = (k, v)
    return fn, again, q, pools, L, nbytes, ref


def build_prompt(shape, end, rng, rehearse):
    """One launch of the TOKEN TILE, a chunk step's: every row feeds
    ``new`` tokens that end at context ``end``. As :func:`build` returns,
    with the launch's operations behind its bytes and a reference over
    the sampled tokens ``ref.tokens`` alone."""
    rows, new, kvh, hd, nh = (shape[k] for k in
                              ("rows", "new", "kvh", "hd", "nh"))
    window, ring, table = shape["window"], shape["ring"], 544
    if rehearse:
        rows, new, end, table = 2, 160, 160 + 96 * (end > 1024), 48
        if window:
            window, ring = 128, 19
    MB = ring or table
    nb = 1 + rows * MB
    T = rows * new
    tables = jnp.asarray(rng.permutation(np.arange(1, nb)).reshape(rows, MB),
                         jnp.int32)
    row_ids = jnp.repeat(jnp.arange(rows, dtype=jnp.int32), new)
    lengths = jnp.tile(jnp.arange(end - new + 1, end + 1, dtype=jnp.int32),
                       rows)
    key = jax.random.PRNGKey(int(rng.integers(1 << 30)))
    dtype = jnp.float32 if rehearse else jnp.bfloat16
    k = jax.random.normal(key, (1, nb, BS, kvh * hd), dtype)
    v = jax.random.normal(jax.random.fold_in(key, 1), k.shape, dtype)
    q = jax.random.normal(jax.random.fold_in(key, 2), (T, nh, hd), dtype)

    def fn(q, layer, k, v):
        return ra.ragged_attention(q, k, v, layer, row_ids, lengths, tables,
                                   window=window, variant="tiled")

    sample = jnp.asarray(np.sort(rng.choice(T, 32, replace=False)))

    def ref(q, layer, k, v):
        return jnp.concatenate([ra.ragged_attention_reference(
            q[at], k, v, layer, row_ids[at], lengths[at], tables,
            window=window) for at in sample.reshape(4, 8)])
    ref.tokens = sample

    def again(q, out):
        return q + out * 0
    fields = dict(num_heads=nh, num_kv_heads=kvh, head_dim_override=hd)
    launch = [(new, end)] * rows
    return (fn, again, q, (k, v), 1,
            (arith_window.launch_bytes(fields, launch, window,
                                       k.dtype.itemsize),
             arith_window.launch_flops(fields, launch, window)), ref)


def build_picked(shape, end, rng, rehearse):
    """One selecting prompt launch of a full latent layer: ``new``
    tokens of one row that end at context ``end``, every form of it
    behind ``fn.form`` (the variants set it; a fresh jit a timing). As
    :func:`build_prompt` returns; the pools are the latent pool, the
    up-projection, the flags and, for ``kernel`` and its levers, the
    row's keys and values made ahead."""
    new, nh, dc, dr, dn, dv, W, topk, ctx = (shape[k] for k in (
        "new", "nh", "dc", "dr", "dn", "dv", "W", "topk", "table"))
    if rehearse:
        new, nh, dc, dr, dn, dv, W, topk, ctx, end = \
            128, 4, 32, 16, 16, 16, 128, 64, 512, 256 + 128 * (end > 4096)
    MB, T = ctx // BS, new
    tables = jnp.asarray(rng.permutation(np.arange(1, MB + 1))[None],
                         jnp.int32)
    row_ids = jnp.zeros((T,), jnp.int32)
    lengths = jnp.arange(end - new + 1, end + 1, dtype=jnp.int32)
    key = jax.random.PRNGKey(int(rng.integers(1 << 30)))
    dtype = jnp.float32 if rehearse else jnp.bfloat16
    pool = jnp.pad(jax.random.normal(key, (1, MB + 1, BS, dc + dr), dtype),
                   ((0, 0),) * 3 + ((0, W - dc - dr),))
    wkv_b = (jax.random.normal(jax.random.fold_in(key, 1),
                               (dc, nh, dn + dv), jnp.float32)
             * dc ** -0.5).astype(dtype)
    q = jax.random.normal(jax.random.fold_in(key, 2), (T, nh, dn + dr), dtype)
    seen = jnp.arange(ctx)[None, :] < lengths[:, None]
    picked = seen & (jax.random.uniform(jax.random.fold_in(key, 3), (T, ctx))
                     < topk / lengths[:, None])
    picked = picked.at[:, 0].set(True)
    scale = float(dn + dr) ** -0.5
    tq = ra.picked_heads_tile(T, 1)
    # a row's positions in pieces, as the program lays them
    E = pm._EXPAND_PIECE if ctx > pm._EXPAND_PIECE else ctx
    pieces = (1, ctx // E, E, W)
    lat = pool[0][tables].reshape(pieces)
    k, v = ra.expand_latent_rows_reference(lat, wkv_b, dc=dc, dn=dn)
    tile_rows = jnp.zeros((T // tq,), jnp.int32)
    reach = jnp.full((1,), end, jnp.int32)

    def absorbed(q, layer, pool, wkv_b, picked, attend, at=slice(None)):
        q_lat = jnp.einsum("thd,chd->htc", q[..., :dn], wkv_b[..., :dn])
        qx = jnp.pad(jnp.concatenate(
            [q_lat, q[..., dn:].transpose(1, 0, 2)], -1),
            ((0, 0), (0, 0), (0, W - dc - dr)))
        o_lat = attend(qx, pool, layer, row_ids[at], lengths[at], tables,
                       dc=dc, scale=scale, picked=picked)
        return jnp.einsum("htc,chd->thd", o_lat, wkv_b[..., dn:])

    def fn(q, layer, pool, wkv_b, picked, k, v):
        form = fn.form
        if form == "absorbed":
            return absorbed(q, layer, pool, wkv_b, picked,
                            ra.latent_attention)
        if form == "absorbed-kernel":
            qx = jnp.pad(q.transpose(1, 0, 2), ((0, 0), (0, 0),
                                                (0, W - dn - dr)))
            return ra.latent_attention(
                qx, pool, layer, row_ids, lengths, tables, dc=dc,
                scale=scale, picked=picked)[..., :dv].transpose(1, 0, 2)
        # the flags a tile of tokens, a token a lane, as the program's
        # loop over tiles leaves them
        tt = min(pm._INDEX_TILE, tq)
        picked_t = picked.reshape(T // tt, tt, ctx).transpose(
            0, 2, 1).astype(jnp.int8)
        if form.startswith(("expanded", "heads-")):
            # (heads-N: the bytes that give a group of N heads)
            room = int(form.split("-")[1]) * ctx * (dn + dv) \
                * q.dtype.itemsize if form.startswith("heads-") \
                else pm._EXPAND_BYTES
            with patched(pm, _EXPAND_BYTES=room):
                return pm._expanded_picked_attention(
                    q.transpose(1, 0, 2), pool[layer][tables].reshape(
                        pieces), wkv_b, picked_t, tile_rows, lengths, reach,
                    dc=dc, dn=dn, scale=scale, tq=tq,
                    use_kernel=not rehearse)
        opts, t, update = {}, tq, ra._tile_update
        for part in form.split("+"):        # "hs-8+chunk-1024": both
            name, _, n = part.partition("-")
            if part == "no-mask":           # the token tile's own levers
                update = _update_without(mask=False)
            elif part == "no-reduce":
                update = _update_without(reduce=False)
            elif name == "tq":
                t = int(n)
            elif name in ("hs", "chunk"):
                opts[dict(hs="heads_a_step", chunk="chunk")[name]] = int(n)
        with patched(ra, _tile_update=update):
            return ra.picked_heads_attention(
                q.transpose(1, 0, 2), k, pool[layer][tables].reshape(
                    pieces)[..., dc:dc + dr], v, picked_t,
                jnp.zeros((T // t,), jnp.int32), lengths, scale=scale, tq=t,
                interpret=True if rehearse else None, **opts
            ).reshape(T, nh, dv)
    fn.form = "expanded"

    sample = jnp.asarray(np.sort(rng.choice(T, 32, replace=False)))

    def ref(q, layer, pool, wkv_b, picked, k, v):
        return absorbed(q[sample], layer, pool, wkv_b, picked[sample],
                        ra.latent_attention_reference, sample)
    ref.tokens = sample

    def again(q, out):
        return q + out[..., :1] * 0
    # the expanded form's operations, a 192-wide score as two passes of
    # 128, and the row's expansion
    attended = int(np.asarray(lengths, np.int64).sum())
    lanes = -(-(dn + dr) // 128) * 128 + dv
    flops = 2 * nh * (attended * lanes + end * dc * (dn + dv))
    nbytes = (MB * BS * W + 2 * T * nh * (dn + dr)) * pool.dtype.itemsize
    return (fn, again, q, (pool, wkv_b, picked, k, v), 1, (nbytes, flops),
            ref)


def build_indexer(shape, end, rng, rehearse):
    """One tile of the indexer of a selecting prompt launch: ``new``
    tokens of one row whose bounds end at ``end`` (the tile's reach)
    over the row's index keys, ``fn.form`` "scores" or "flags" (the
    variants set it). As :func:`build_picked` returns; the operations
    are ``(the equations', the tree's products')``."""
    new, heads, d, topk, ctx = (shape[k] for k in (
        "new", "heads", "d", "topk", "table"))
    if rehearse:
        new, heads, d, topk, ctx, end = 16, 2, 8, 8, 512, min(end // 64, 512)
    MB = ctx // BS
    tables = jnp.asarray(rng.permutation(np.arange(1, MB + 1))[None],
                         jnp.int32)
    row_ids = jnp.zeros((new,), jnp.int32)
    lengths = jnp.arange(end - new + 1, end + 1, dtype=jnp.int32)
    key = jax.random.PRNGKey(int(rng.integers(1 << 30)))
    dtype = jnp.float32 if rehearse else jnp.bfloat16
    keys = jax.random.normal(key, (1, MB + 1, BS, d), dtype)
    qi = jax.random.normal(jax.random.fold_in(key, 1), (new, heads, d), dtype)
    wi = jax.random.normal(jax.random.fold_in(key, 2), (new, heads),
                           jnp.float32) * (heads * d) ** -0.5
    seen = jnp.arange(ctx)[None, :] < lengths[:, None]

    def fn(qi, layer, keys, wi):
        if fn.form == "flags":
            return pm.index_mask(qi, wi, keys, layer, row_ids, lengths,
                                 tables, topk).T.astype(jnp.int8)
        scores = pm._token_scores(qi, wi, keys, layer, row_ids, lengths,
                                  tables, False)[0]
        return jnp.where(seen, scores, 0) if fn.form == "check" else scores
    fn.form = "scores"

    def ref(qi, layer, keys, wi):
        s = jnp.einsum("tjd,cd->tjc", qi, keys[layer][tables[0]].reshape(
            ctx, d), preferred_element_type=jnp.float32)
        return jnp.where(seen, jnp.einsum("tjc,tj->tc", jax.nn.relu(s), wi),
                         0)

    def again(qi, out):
        return qi + (out[0, 0].astype(jnp.float32) * 0).astype(qi.dtype)
    chunk = min(pm._INDEX_CHUNK, ctx)
    chunks = -(-end // chunk) if hasattr(pm, "index_chunks") \
        else -(-ctx // chunk)
    a_position = 2 * heads * d
    flops = (int(np.asarray(lengths, np.int64).sum()) * a_position,
             new * chunks * chunk * a_position)
    nbytes = end * d * keys.dtype.itemsize + new * end * 4
    return fn, again, qi, (keys, wi), 1, (nbytes, flops), ref


@contextlib.contextmanager
def patched(module, **attrs):
    """``module``'s attributes replaced for the block; a KeyError names
    the one this tree lacks."""
    missing = [a for a in attrs if not hasattr(module, a)]
    if missing:
        raise KeyError(missing[0])
    old = {a: getattr(module, a) for a in attrs}
    for a, new in attrs.items():
        setattr(module, a, new)
    try:
        yield
    finally:
        for a, was in old.items():
            setattr(module, a, was)


def _nothing(*a, **k):
    return None


def _page_waits(n, cp, wait):
    """:func:`ra._chunk_waits` as the walk waited before PR 50: a wait a
    page."""
    jax.lax.fori_loop(0, n, lambda j, _: (wait(1), 0)[1], 0)


def _update_without(mask=True, exp=True, reduce=True, pv=True, split=1):
    """:func:`ra._tile_update` (the token tile's, transposed) with a part
    taken out (wrong sums: timing only): the ``mask`` never built nor
    selected by, the exponential of the scores (``exp``), the rows' max
    and sum (``reduce``), the second product (``pv``); or whole, as
    ``split`` products over as many parts of the chunk's positions, one
    behind the other."""
    def update(q, k, v, visible, acc_sc, m_sc, l_sc, b, *, scale):
        P = k.shape[0] // split
        for h in range(split):                                # static
            at = slice(h * P, (h + 1) * P)
            s = jax.lax.dot_general(
                k[at], q, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if mask:
                s = jnp.where(visible[at], s, 2 * ra.NEG_INF)
            m_prev = m_sc[b]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True)) \
                if reduce else m_prev
            p = jnp.exp(s - m_new) if exp else s - m_new
            corr = jnp.exp(m_prev - m_new)
            l_sc[b] = l_sc[b] * corr + (jnp.sum(p, axis=0, keepdims=True)
                                        if reduce else p[:1])
            acc_sc[b] = acc_sc[b] * corr + (jax.lax.dot_general(
                v[at], p.astype(v.dtype), (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
                if pv else p[:acc_sc.shape[1]])
            m_sc[b] = m_new
    return update


class _NoCopy:
    """A copy that is never made: started and waited for in no time."""

    def start(self):
        pass

    wait = start


class _NoCopies:
    """``pltpu`` with every ``make_async_copy`` a :class:`_NoCopy`: the
    walk's loops and the products over whatever the slots hold."""

    def __getattr__(self, name):
        return getattr(pltpu, name)

    @staticmethod
    def make_async_copy(*a, **k):
        return _NoCopy()



def _state_copies(layer_ref, slots_ref, fresh_ref, s_ref, decay_ref, dtx_ref,
                  b_ref, c_ref, so_ref, y_ref, *scratch, **static):
    """:func:`ss._state_kernel` with the token taken out: a grid step's
    states copied in and copied back as they came."""
    so_ref[...] = s_ref[...]
    y_ref[...] = dtx_ref[...]


def _retention_copies(layer_ref, slots_ref, fresh_ref, s_ref, z_ref, q_ref,
                      k_ref, v_ref, dec_ref, so_ref, zo_ref, o_ref, *scratch,
                      **static):
    """:func:`pr._state_kernel` with the token taken out."""
    so_ref[...] = s_ref[...]
    zo_ref[...] = z_ref[...]
    o_ref[...] = q_ref[...]


def _no_distances(lo, hi, body, init, **kw):
    """``fori_loop`` that skips the loop over phi's distances (the one
    whose carry is the int 0) and runs every other as it is."""
    if isinstance(init, int) and init == 0 and isinstance(lo, int):
        return init
    return _FORI(lo, hi, body, init, **kw)


def _conv_copies(held, x, w_ref, b_ref):
    """:func:`la._conv_token` with the sum taken out: the kernel's copies
    of the slots and the shift by the token."""
    return x


_FORI = jax.lax.fori_loop


def _whole_alone(ys, inv, held, topv, rows_held=None, interpret=False):
    """:func:`ec.rows_combine`'s first launch alone."""
    return ec.rows_whole(ys, rows_held, interpret)[0].astype(jnp.float32)


def _gmm_k_tiles(expert_params, xs, group_sizes, gate=jax.nn.silu):
    """:func:`sm.gmm_swiglu_experts` with an expert's weight cut along the
    CONTRACTED width where the program cuts its columns (the same bytes a
    tile): the rows pass once a matrix and an expert's tiles turn a row
    tile."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm
    xs, m = sm._whole_row_tiles(xs)

    def mm(x, w):
        K, N = w.shape[1:]
        return gmm(x, w, group_sizes, preferred_element_type=x.dtype,
                   tiling=(sm._GMM_ROWS, K * sm._gmm_columns(w) // N, N))
    wg, wu, wd = expert_params
    return mm(gate(mm(xs, wg)) * mm(xs, wu), wd)[:m]


def variants(kernel, sweep, fn=None):
    if kernel == "share_gmm":
        out = {"full": {}}
        if sweep:
            out["k-tiles"] = dict(gmm_swiglu_experts=_gmm_k_tiles)
            out["aligned"] = {}
            out["rows-256"] = dict(_GMM_ROWS=256)
        return out
    if kernel == "dispatch":
        # the function under its own jit: that jit's cache would hand a
        # variant the trace of the one before it
        full = ec._rows_combine
        out = {"xla": dict(rows_combine=fn.xla),
               "kernel": dict(rows_combine=full),
               "whole": dict(rows_combine=_whole_alone),
               "copies": dict(rows_combine=functools.partial(
                   full, arithmetic=False)),
               "no-skip": dict(rows_combine=functools.partial(
                   full, skip=False))}
        if sweep:
            for n in (8, 16):
                out[f"starts-{n}"] = dict(_STARTS=n, rows_combine=full)
                out[f"starts-{n}+copies"] = dict(
                    _STARTS=n, rows_combine=functools.partial(
                        full, arithmetic=False))
            for n in (2, 4):
                out[f"sums-{n}"] = dict(_SUMS=n, rows_combine=full)
        return out
    if kernel == "ssm_state":
        return {"full": {}, "copies": dict(_state_kernel=_state_copies)}
    if kernel == "conv":
        return {"full": {}, "copies": dict(_conv_token=_conv_copies)}
    if kernel == "retention_state":
        return {"full": {},
                "copies": dict(_state_kernel=_retention_copies)}
    if kernel == "retention_chunk":
        return {"full": {}, "copies": dict(_distances_loop=_no_distances)}
    if kernel == "picked":
        forms = ["absorbed", "absorbed-kernel", "expanded", "kernel",
                 "no-mask", "no-reduce"]
        if sweep:
            forms += ["tq-256", "tq-1024", "hs-2", "hs-8", "chunk-512",
                      "chunk-2048", "hs-8+chunk-512", "tq-1024+hs-8",
                      "heads-4", "heads-16"]
        return {f: dict(form=f) for f in forms}
    if kernel == "indexer":
        return {f: dict(form=f) for f in ("scores", "flags")}
    if kernel == "prompt":
        parts = {"": {}, "copies": dict(_tile_update=_nothing),
                 "no-mask": dict(_tile_update=_update_without(mask=False)),
                 "no-copies": dict(pltpu=_NoCopies()),
                 "no-exp": dict(_tile_update=_update_without(exp=False)),
                 "no-reduce": dict(
                     _tile_update=_update_without(reduce=False)),
                 "no-pv": dict(_tile_update=_update_without(pv=False)),
                 "halves": dict(_tile_update=_update_without(split=2))}
        out = {"full": {}, "copies": parts["copies"],
               "no-mask": parts["no-mask"]}
        if sweep:
            for n in (64, 128, 256):
                for label, attrs in parts.items():
                    out[f"tq-{n}" + f"+{label}" * bool(label)] = dict(
                        attrs, _token_tile=lambda tokens, rpb, n=n: n)
        return out
    # the products of this tree's kernels, whichever it has (the parent
    # of PR 50 ran ``_tile_update`` in both forms of the tiled kernel and
    # kept the latent kernel's products inline)
    products = ("_latent_update",) if kernel == "latent" \
        else ("_tile_update", "_blocks_update")
    out = {"full": {}, "copies": {a: _nothing for a in products
                                  if hasattr(ra, a)} or {products[0]: None}}
    if sweep:
        out["page-waits"] = dict(_chunk_waits=_page_waits)
        for u in (1, 4):
            out[f"unroll-{u}"] = dict(_START_UNROLL=u)
    return out


def time_launches(fn, again, q, pools, L, launches, carried=False,
                  kept=False):
    """us a launch: ``launches`` of them in one program, best of three.
    A fresh ``jit`` a call: its cache does not see the attributes a
    variant replaces. ``carried``: ``fn`` returns its pool beside its
    output (a state leaf, updated in place), and the loop hands it on:
    the program is given a copy of the caller's to consume. ``kept``:
    the pools are gigabytes of weights, which the program reads and does
    not return (a returned pool is a copy of it)."""
    def step(i, qp):
        q, pools = qp
        out = fn(q, i % L, *pools)
        out, pools = (out[0], out[1:]) if carried else (out, pools)
        return again(q, out), pools

    def many(q, *pools):
        if kept:
            return jax.lax.fori_loop(
                0, launches, lambda i, q: step(i, (q, pools))[0], q)
        return jax.lax.fori_loop(0, launches, step, (q, pools))
    run = jax.jit(many, donate_argnums=(1,) if carried else ())
    if kept:
        run = lambda q, *pools, run=run: (run(q, *pools), pools)
    if carried:
        pools = tuple(jnp.copy(p) for p in pools)
    q, pools = run(q, *pools)
    jax.block_until_ready(q)
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        q, pools = run(q, *pools)
        jax.block_until_ready(q)
        best = min(best, time.perf_counter() - t0)
    return best / launches * 1e6


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--only", default="", help="comma-separated shape names")
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--check", action="store_true")
    ap.add_argument("--launches", type=int, default=200)
    ap.add_argument("--variants", default="", help="comma-separated: the "
                    "variants to time (all of a kernel's)")
    ap.add_argument("--contexts", default="", help="comma-separated: the "
                    "prompt shapes' contexts to run (all of a shape's)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--pool", default="shuffled", help="how the decode "
                    "launches' tables lie: shuffled (no two neighbours "
                    "together), together (a row's blocks ascending) or "
                    "steps-N (runs of N pages, the rows' interleaved)")
    ap.add_argument("--run-pages", type=int, default=0, help="read the "
                    "least run as this many pages (ragged_attention."
                    "_RUN_PAGES; 0: the tree's)")
    ap.add_argument("--runs", choices=("gate", "always", "never"),
                    default="gate", help="hand a tiled decode launch its "
                    "table's runs where runs_serve says so, always, never")
    args = ap.parse_args()
    if args.rehearse:
        print("REHEARSAL (cpu): toy sizes under the interpreter, no time "
              "here is a device's")
        args.launches = 2
    elif jax.default_backend() != "tpu":
        sys.exit(f"needs a TPU, found {jax.default_backend()!r} "
                 "(--rehearse runs toy sizes on the CPU)")
    if args.run_pages:
        ra._RUN_PAGES = args.run_pages
    names = [m for n in args.only.split(",") if n
             for m in FAMILIES.get(n, [n])] or list(SHAPES)
    wanted = [int(c) for c in args.contexts.split(",") if c]
    chosen = [v for v in args.variants.split(",") if v]
    # a prompt shape is a row of the output a context
    cases = [(n, end) for n in names
             for end in (SHAPES[n].get("ends") or (None,))
             if end is None or not wanted or end in wanted]
    for name, end in cases:
        rng = np.random.default_rng(args.seed)
        kernel = SHAPES[name]["kernel"]
        if kernel.startswith("retention") and pr is None:
            print(json.dumps({"shape": name, "skipped": "no "
                              "kernels/power_retention.py in this tree"}))
            continue
        if kernel == "dispatch" and ec is None:
            print(json.dumps({"shape": name, "skipped": "no "
                              "kernels/expert_combine.py in this tree"}))
            continue
        # the kernels that update a state leaf in place (their builder,
        # the module a variant patches): the loop carries the leaf
        in_place = {"ssm_state": (build_state, ss), "conv": (build_conv, la),
                    "retention_state": (build_retention, pr),
                    "retention_chunk": (build_retention, pr),
                    "dispatch": (build_dispatch, ec)}
        state = kernel in in_place
        builder, module = in_place.get(
            kernel, (build, sm if kernel == "share_gmm" else ra))
        if kernel in ("prompt", "share_gmm", "picked", "indexer"):
            fn, again, q, pools, L, (nbytes, flops), ref = dict(
                prompt=build_prompt, share_gmm=build_share_gmm,
                picked=build_picked, indexer=build_indexer)[kernel](
                SHAPES[name], end, rng, args.rehearse)
            swept = None
            if kernel == "indexer":
                flops, swept = flops
            if kernel in ("picked", "indexer"):
                module = fn         # a variant is a form of the launch
            row = {"shape": name, "context": end} if kernel != "share_gmm" \
                else {"shape": name, "run": fn.tokens,
                      "tree_run": fn.tree_run, "rows": fn.rows,
                      "held": fn.held, "rows_an_expert": fn.an_expert,
                      "visits": fn.visits, "tiles": fn.tiles}
            row.update(flops=flops,
                       flops_us=round(flops / PEAK_FLOPS_S * 1e6, 2))
            if swept:
                row.update(swept_flops=swept, swept_us=round(
                    swept / PEAK_FLOPS_S * 1e6, 2))
            launches = max(2, args.launches // 10)
        else:
            fn, again, q, pools, L, nbytes, ref = builder(
                SHAPES[name], rng, args.rehearse, **(
                    dict(pool=args.pool, runs=args.runs)
                    if builder is build else {}))
            row, launches = {"shape": name}, args.launches
            if builder is build:
                row.update(pool=args.pool, **{
                    k: getattr(fn, k) for k in ("runs", "pages_a_descriptor")
                    if hasattr(fn, k)})
            if kernel == "dispatch":
                row.update(rows=fn.rows, copy_starts=fn.held)
        row.update({"bytes": nbytes,
                    "bytes_us": round(nbytes / PEAK_BYTES_S * 1e6, 2)})
        if args.check:
            # a leaf updated in place goes in donated, as the engine's
            # cache does: a kernel that colours its leaf HBM cannot take
            # a copy the compiler made (kernels/slot_leaf.py)
            given = tuple(range(2, 2 + len(pools))) if state else ()
            if kernel == "indexer":     # the scores under the bounds
                fn.form = "check"
            got, want = (jax.tree.leaves(jax.jit(f, donate_argnums=given)(
                q, L - 1, *(jnp.copy(p) if state else p for p in pools)))
                for f in (fn, ref))
            if kernel in ("prompt", "picked"):
                got = [got[0][ref.tokens]]
            if kernel == "dispatch":     # the carried ys holds NaN rows
                got, want = got[:1], want[:1]
            if kernel == "share_gmm":    # no group computes the rows behind
                got, want = ([a[0][:fn.held]] for a in (got, want))
            row["max_err"] = max(float(jnp.abs(g - w).max())
                                 for g, w in zip(got, want))
            row["rel_err"] = max(
                float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w))
                for g, w in zip(got, want))
        for label, attrs in variants(kernel, args.sweep, fn).items():
            if chosen and label not in chosen:
                continue
            try:
                # (``aligned`` is another launch over other rows)
                timed, rows = (fn.aligned, jnp.pad(
                    q, ((0, fn.aligned.pad), (0, 0)))) \
                    if label == "aligned" else (fn, q)
                with patched(module, **attrs):
                    row[label] = round(time_launches(
                        timed, again, rows, pools, L, launches,
                        carried=state,
                        kept=kernel in ("share_gmm", "picked", "indexer")),
                        2)
                if swept:
                    row[f"{label}_peak_share"] = round(
                        row["swept_us"] / row[label], 4)
                if label == "aligned":
                    row["aligned_visits"] = timed.visits
                if kernel == "share_gmm":
                    row[f"{label}_us_a_token"] = round(
                        row[label] / fn.tokens, 4)
            except KeyError as missing:
                row[label] = f"no {missing.args[0]} in this tree"
            except Exception as refused:      # the compiler's, at a size
                row[label] = f"{type(refused).__name__}: " \
                    f"{str(refused)[:200]}"
        print(json.dumps(row), flush=True)
        # the next shape's weights want the room these hold
        del fn, again, q, pools, ref
    if args.rehearse:
        print("REHEARSAL (cpu)")


if __name__ == "__main__":
    main()
