"""A chunk of the attention walk is copied a RUN of pages a descriptor
(PR 67): how a table lies (``table_runs``) against a brute-force reading,
a run's cut into descriptors, the host's counter (``copy_counts``) against
the kernel's own cut walked chunk by chunk, and the tiled kernel's two
forms handed the runs against the same launch handed none, bit for bit,
over tables that lie every way. CPU, under the TPU interpreter."""

import functools
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.unit.inference import walk_cases

ra = walk_cases.ra()


# ---------------------------------------------------------------------------
# tables that lie every way
# ---------------------------------------------------------------------------
def _mixed(base, width, cp, rng):
    """One row's table over blocks ``base ..``: a first chunk that lies
    together, lone pages (descending), a run that crosses the second
    chunk's end, runs of two and three amid lone pages, and what is left
    shuffled."""
    row = np.zeros(width, np.int64)
    free = list(range(base + width - 1, base - 1, -1))      # descending

    def take(n):
        out = sorted(free[-n:])
        del free[-n:]
        return out
    at = 0

    def put(blocks):
        nonlocal at
        blocks = blocks[:width - at]
        row[at:at + len(blocks)] = blocks
        at += len(blocks)
    put(take(cp))                                   # a chunk together
    lone = take(max(cp - 5, 0))
    put(lone[::-1])                                 # none together
    put(take(12))                                   # crosses 2 * cp
    while at < width - 8 and len(free) > 8:
        put(take(int(rng.integers(2, 4))))          # a run of 2 or 3
        put(take(1))
        put(take(2)[::-1])                          # two that lie alone
    rest = take(len(free))
    put(list(rng.permutation(rest)))
    return row[:width]


LAYOUTS = ("mixed", "together", "none", "shuffled", "padded", "wraps")


def _tables(layout, rows, width, cp, seed=0, used=None):
    """``[rows, width]`` int32 over blocks ``1 ..``, a row's own blocks
    ``1 + r * width ..``: ``together`` ascending, ``none`` descending (no
    two neighbours linked), ``shuffled`` a permutation, ``mixed``
    (:func:`_mixed`), ``padded`` together with the null block behind the
    ``used`` places, ``wraps`` together but turned by nine places, so that
    the blocks of the LAST place and of place 0 are neighbours (a ring's
    run must not wrap)."""
    rng = np.random.default_rng(seed)
    out = np.zeros((rows, width), np.int64)
    for r in range(rows):
        base = 1 + r * width
        own = np.arange(base, base + width)
        if layout == "together":
            out[r] = own
        elif layout == "none":
            out[r] = own[::-1]
        elif layout == "shuffled":
            out[r] = rng.permutation(own)
        elif layout == "mixed":
            out[r] = _mixed(base, width, cp, rng)
        elif layout == "padded":
            out[r] = own
            out[r, (used if used is not None else width // 2):] = 0
        elif layout == "wraps":
            out[r] = base + (np.arange(width) + 9) % width
        else:
            raise KeyError(layout)
    return out.astype(np.int32)


def _brute_runs(tables, cap):
    """``table_runs`` read place by place: at p, the places from p on
    whose link to the next is p's own, +1 for a run (its last page has
    no link), capped as the doubling caps it."""
    R, W = tables.shape
    out = np.zeros((R, W), np.int64)
    for r in range(R):
        link = [p + 1 < W and tables[r, p + 1] == tables[r, p] + 1
                for p in range(W)]
        # a run of under ``_RUN_PAGES`` pages is no run
        p = 0
        while p < W:
            q = p
            while link[q]:
                q += 1
            if q - p + 1 < ra._RUN_PAGES:
                link[p:q] = [False] * (q - p)
            p = q + 1
        for p in range(W):
            n = 1
            while p + n < W and link[p + n] == link[p] \
                    and link[p + n - 1] == link[p]:
                n += 1
            # the count of equal neighbours is capped at ``cap``
            n = min(n, cap + 1)
            out[r, p] = n + 1 if link[p] else -n
    return out


@pytest.mark.parametrize("traced", [False, True], ids=["numpy", "traced"])
@pytest.mark.parametrize("layout", LAYOUTS)
def test_how_a_table_lies_is_what_a_brute_force_reading_finds(layout,
                                                              traced):
    """``table_runs`` at every place, ``numpy`` and traced alike: a run's
    length where one starts, the lone pages ahead otherwise; all
    together, none together, runs of two and three (under the least
    size: their pages lie alone), a run over a chunk's end (the walk
    clips it, not the table), the table's last place (nothing wraps),
    the null block in padding places."""
    cp, width = 32, 75
    tables = _tables(layout, 3, width, cp, seed=3)
    want = _brute_runs(tables, 32)
    got = jax.jit(lambda t: ra.table_runs(t, cp))(jnp.asarray(tables)) \
        if traced else ra.table_runs(tables, cp)
    assert isinstance(got, np.ndarray) != traced
    np.testing.assert_array_equal(np.asarray(got), want)
    if layout == "together":
        assert got[0, 0] == 34 and got[0, width - 2] == 2 \
            and got[0, width - 1] == -1
    if layout in ("none", "shuffled"):
        assert (np.asarray(got) < 0).all() or layout == "shuffled"
    if layout == "padded":
        # a run ends where the null block begins; zeros link to nothing
        assert got[0, 0] == 34 and got[0, width // 2 - 1] < 0 \
            and (np.asarray(got)[:, width // 2:] < 0).all()
    if layout == "wraps":
        # blocks ``.. base + width - 1`` up to place width - 10, then
        # ``base ..``: the last place links to nothing behind it
        assert got[0, width - 1] == -1 and got[0, width - 9] == 9


@pytest.mark.parametrize("cp", [1, 2, 3, 8, 32, 40])
def test_a_runs_cut_into_descriptors_adds_up_to_its_pages(cp):
    """Every length 0..cp: the static sizes in its binary digits, largest
    first, each at the pages the larger ones leave, none over another;
    one descriptor for a whole chunk of a power of two."""
    sizes = ra._run_sizes(cp)
    assert list(sizes) == sorted(sizes, reverse=True) and sizes[-1] == 1
    for s in range(cp + 1):
        cut = ra._run_cut(s, cp)
        assert sum(size for _, size in cut) == s
        at = 0
        for off, size in cut:
            assert off == at and size in sizes
            at += size
    if cp & (cp - 1) == 0:
        assert ra._run_cut(cp, cp) == [(0, cp)]


def _walked(tables, rows, first, pages, cp, ring):
    """``copy_counts`` by walking every chunk as ``_walk_rows.start``
    does: one lookup a segment, ``min(., the chunk's pages left)``."""
    W = ring or tables.shape[1]
    lies = ra.table_runs(np.ascontiguousarray(tables[:, :W]), cp)
    total = starts = 0
    for r, f, n_pages in zip(rows, first, pages):
        done = 0
        while done < n_pages:
            n = min(cp, n_pages - done)
            at0 = (f + done) % ring if ring else f + done
            j = 0
            while j < n:
                at = at0 + j - (ring if ring and at0 + j >= ring else 0)
                count = min(abs(int(lies[r, at])), n - j)
                starts += len(ra._run_cut(count, cp)) \
                    if lies[r, at] > 0 else count
                j += count
            total, done = total + n, done + n
    return total, starts


@pytest.mark.parametrize("seed", range(6))
def test_the_hosts_counter_makes_the_kernels_cut(seed):
    """``copy_counts`` (prefix sums, no loop over pages) against the
    kernel's cut walked chunk by chunk, over seeded tables with runs laid
    anywhere, rings that wrap, chunks that are no power of two, walks
    that start anywhere; and its two ends: 1.0 over a pool with no two
    neighbours together, a chunk's pages where every chunk lies
    together."""
    rng = np.random.default_rng(seed)
    for _ in range(40):
        cp = int(rng.choice([1, 3, 4, 8, 32]))
        R, W = int(rng.integers(1, 5)), int(rng.integers(max(cp, 2), 90))
        ring = W if rng.random() < 0.5 else 0
        t = rng.permutation(np.arange(1, 1 + R * W)).reshape(R, W)
        for r in range(R):
            for _ in range(int(rng.integers(0, 5))):
                a = int(rng.integers(0, W))
                n = int(rng.integers(1, W - a + 1))
                b0 = int(rng.integers(1, 2000))
                t[r, a:a + n] = np.arange(b0, b0 + n)
            if rng.random() < 0.3:
                t[r, int(rng.integers(0, W)):] = 0
        t = t.astype(np.int32)
        m = int(rng.integers(1, 12))
        rows = rng.integers(0, R, m)
        if ring:
            first, pages = rng.integers(0, 5 * W, m), \
                rng.integers(0, W + 1, m)
        else:
            first = rng.integers(0, W, m)
            pages = np.array([rng.integers(0, W - f + 1) for f in first])
        assert ra.copy_counts(t, rows, first, pages, cp, ring) \
            == _walked(t, rows, first, pages, cp, ring)
    none = _tables("none", 4, 64, 32, seed)
    walks = (np.arange(4), np.zeros(4, int), np.full(4, 64))
    assert ra.copy_counts(none, *walks, 32) == (256, 256)
    assert ra.copy_counts(_tables("together", 4, 64, 32), *walks, 32) \
        == (256, 8)
    # a launch that was handed no runs starts a copy a page, whatever lies
    assert ra.copy_counts(_tables("together", 4, 64, 32), *walks, 32,
                          runs=False) == (256, 256)


def test_a_pool_of_large_pages_is_handed_no_runs():
    """``runs_serve`` is static in a page's bytes a leaf: the benchmark's
    pools of 8 and 16 KB pages (nemotron; the 8k cells, falcon, lfm2)
    are handed their tables' runs, granite's 32 KB and OPT-1.3B's 64 KB
    are the launches they were; and off the TPU ``launch_runs`` hands
    none (the pipelined variant copies a page a grid step)."""
    for lanes, want in ((256, True), (512, True), (1024, False),
                        (2048, False)):
        assert ra.runs_serve(16 * lanes * 2) is want
    pool = jnp.zeros((1, 8721, 16, 512), jnp.bfloat16)
    assert ra.launch_runs(jnp.zeros((16, 544), jnp.int32), pool, 128) is None


# ---------------------------------------------------------------------------
# the kernels handed the runs: the same launch, bit for bit
# ---------------------------------------------------------------------------
def _row_layouts(rows, width, cp, contexts, bs, ring):
    """A launch's tables, row r lying as ``LAYOUTS[r % ...]`` (a row's
    padding behind the pages its context holds; ``wraps`` only over a
    ring, where it is the ring's last place that matters)."""
    kinds = [k for k in LAYOUTS if k != "wraps" or ring]
    out = np.zeros((rows, width), np.int32)
    for r in range(rows):
        used = -(-int(contexts[r]) // bs) if not ring else width
        out[r] = _tables(kinds[r % len(kinds)], rows, width, cp, seed=r,
                         used=max(used, 1))[r]
    return out


def _both(fn, args, tables, kw, cp):
    """The launch handed no runs and handed ``table_runs`` of its tables"""
    args = args[:-1] + (jnp.asarray(tables),)
    run = jax.jit(functools.partial(fn, **kw))
    lies = ra.table_runs(jnp.asarray(tables), cp)
    return np.asarray(run(*args)), np.asarray(
        jax.jit(functools.partial(fn, runs=lies, **kw))(*args))


@pytest.mark.parametrize("case", ["tiled-group8", "tiled-hpb2",
                                  "window-ring", "tiled-int8"])
def test_the_one_token_form_handed_the_runs_is_the_same_launch(case):
    """``walk_cases``' decode launches (a last chunk of 1, 2, 3, cp - 1
    and cp pages, contexts that end on a chunk, a row of no length; a
    window over rings that have wrapped; an int8 pool) over tables whose
    rows lie every way: equal to the launch handed no runs to the last
    bit, and the host's counter says the runs were there to take."""
    args, kw, _ = walk_cases.build(case)
    c = walk_cases.CASES[case]
    contexts = walk_cases.contexts(case)
    R, MB = args[-1].shape
    cp = ra._chunk_pages(MB, args[1].shape[1], c["bs"])
    tables = _row_layouts(R, MB, cp, contexts, c["bs"], c.get("ring", 0))
    none, runs = _both(ra.ragged_attention, args, tables, kw, cp)
    assert np.isfinite(runs).all()
    np.testing.assert_array_equal(runs, none)
    pages, starts = ra.copy_counts(
        tables, np.arange(R), *ra.decode_walks(contexts, c["bs"],
                                               c.get("window", 0)),
        cp, c.get("ring", 0))
    assert starts < pages


@pytest.mark.parametrize("case", ["group8-bf16", "hpb2-int8",
                                  "window-wraps"])
def test_the_token_tile_handed_the_runs_is_the_same_launch(case):
    """The prompt launches of ``walk_cases`` (tiles that see chunks
    whole, by an edge, with another row's tokens; a window whose walk
    wraps its ring inside a tile; an int8 pool), their rows' tables lying
    every way: bit for bit the launch handed no runs."""
    args, kw, _ = walk_cases.build_prompt(case)
    c = walk_cases.PROMPT_CASES[case]
    rows = walk_cases.prompt_rows(case)
    R, MB = args[-1].shape
    cp = ra._chunk_pages(MB, args[1].shape[1], walk_cases.PROMPT_BS)
    tables = _row_layouts(R, MB, cp, [ctx for _, ctx in rows],
                          walk_cases.PROMPT_BS, c.get("ring", 0))
    # the first row lies ``mixed``; give a second launch's worth of it to
    # the others too where the case has rows to spare
    none, runs = _both(ra.ragged_attention, args, tables, kw, cp)
    assert np.isfinite(runs).all()
    np.testing.assert_array_equal(runs, none)


def _prefetched(fn, *args):
    """The scalar arrays the launch's ``pallas_call`` prefetches"""
    def calls(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from calls(sub)
    call, = calls(jax.make_jaxpr(fn)(*args).jaxpr)
    return call.params["grid_mapping"].num_index_operands


def test_the_latent_walk_is_handed_no_runs():
    """The latent kernel's launch is the parent's: it takes no ``runs``
    and prefetches the seven scalar arrays it always did (its jaxpr's
    counts are ``test_ragged_attention.OTHER_LAUNCHES``', held there);
    the tiled launch handed none prefetches seven too, handed the runs
    eight."""
    assert "runs" not in inspect.signature(ra.latent_attention).parameters
    args, kw, _ = walk_cases.build("latent")
    assert _prefetched(functools.partial(ra.latent_attention, **kw),
                       *args) == 7
    args, kw, _ = walk_cases.build("tiled-group8")
    assert _prefetched(functools.partial(ra.ragged_attention, **kw),
                       *args) == 7
    lies = ra.table_runs(args[-1], 32)
    assert _prefetched(functools.partial(ra.ragged_attention, runs=lies,
                                         **kw), *args) == 8
