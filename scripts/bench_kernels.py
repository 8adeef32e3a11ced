"""What a decode launch of the attention walk costs, part by part, on the chip.

    chiprun -- python scripts/bench_kernels.py [--only NAME] [--sweep]
        [--check] [--launches 200]

The decode launches of the five generation cells (``ragged_attention_tiled``
/ ``_window`` / ``_latent`` in their one-token form, at each cell's rows,
pool, table and contexts), alone, over a random pool whose pages are out of
order: a ``fori_loop`` of ``--launches`` launches in one jitted program, the
next launch's queries made from the last one's output, timed on the host
clock round ``block_until_ready``; microseconds a launch, the best of three.
Beside each time, the launch's BYTES at the chip's peak (the pages its rows
hold, once a row; the window's where there is one): what a launch cannot go
under.

The variants take the walk apart by replacing one function of
``kernels/ragged_attention.py`` in this process (nothing a cell runs is
touched, and no option of the program exists for it):

* ``full``           the launch as the engine runs it
* ``copies``         the same walk with the products taken out
                     (``_tile_update`` / ``_blocks_update`` /
                     ``_latent_update`` do nothing): starts, waits and
                     the loop
* ``page-waits``     a wait a page, as before PR 50           (--sweep)
* ``block-by-block`` the tiled kernel's lane blocks updated one behind
                     the other, as before PR 50               (--sweep)
* ``unroll-N``       ``_START_UNROLL`` = N (1, 4): the start loop's trips
                                                              (--sweep)

A tree that lacks a function a variant replaces (an older commit) skips that
variant and says so. ``--check`` holds one launch of each shape to the
gathering reference. Like the other chip scripts it exits non-zero without a
TPU; ``--rehearse`` runs toy sizes under the interpreter on the CPU, which
says the script runs and nothing about time.
"""

import argparse
import contextlib
import importlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax                                                    # noqa: E402
import jax.numpy as jnp                                       # noqa: E402
import numpy as np                                            # noqa: E402

# the package exports a function under the module's name
ra = importlib.import_module(                                 # noqa: E402
    "deepspeed_tpu.inference.v2.kernels.ragged_attention")

PEAK_BYTES_S = 819e9            # TPU v5e (benchmark/peaks.json)

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
    "granite-full": dict(kernel="tiled", rows=64, layers=1, kvh=8, hd=128,
                         nh=32, ctx=(1024, 1280), window=0, ring=0),
}
BS = 16


def build(shape, rng, rehearse):
    """One launch: ``(fn(q, layer, *pools) -> out, again(q, out) -> the
    next launch's q, q, the pools (arguments of the jitted program: a
    closed-over pool would be a constant of gigabytes in it), layers,
    bytes a launch, reference(q, layer, *pools))``."""
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
    tables = np.zeros((rows, MB), np.int32)
    tables[:] = rng.permutation(np.arange(1, nb)).reshape(rows, MB)
    lengths = jnp.asarray(lens, jnp.int32)
    tables = jnp.asarray(tables)
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

        def fn(q, layer, k, v):
            return ra.ragged_attention(
                q, k, v, layer, row_ids, lengths, tables, window=window,
                one_token=True, variant="tiled")

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


@contextlib.contextmanager
def patched(**attrs):
    """``ra``'s attributes replaced for the block; a KeyError names the
    one this tree lacks."""
    missing = [a for a in attrs if not hasattr(ra, a)]
    if missing:
        raise KeyError(missing[0])
    old = {a: getattr(ra, a) for a in attrs}
    for a, new in attrs.items():
        setattr(ra, a, new)
    try:
        yield
    finally:
        for a, was in old.items():
            setattr(ra, a, was)


def _nothing(*a, **k):
    return None


def _page_waits(n, cp, wait):
    """:func:`ra._chunk_waits` as the walk waited before PR 50: a wait a
    page."""
    jax.lax.fori_loop(0, n, lambda j, _: (wait(1), 0)[1], 0)


def _block_by_block(q, k, v, visible, acc_sc, m_sc, l_sc, *, scale):
    """:func:`ra._blocks_update` as the one-token form computed before
    PR 50: ``_tile_update`` a lane block at a time."""
    M = q[0].shape[0]
    for b in range(len(q)):
        ra._tile_update(q[b], k[b], v[b], visible[:M], acc_sc, m_sc, l_sc,
                        b, scale=scale)


def variants(kernel, sweep):
    # the products of this tree's kernels, whichever it has (the parent
    # of PR 50 ran ``_tile_update`` in both forms of the tiled kernel and
    # kept the latent kernel's products inline)
    products = ("_latent_update",) if kernel == "latent" \
        else ("_tile_update", "_blocks_update")
    out = {"full": {}, "copies": {a: _nothing for a in products
                                  if hasattr(ra, a)} or {products[0]: None}}
    if sweep:
        out["page-waits"] = dict(_chunk_waits=_page_waits)
        if kernel != "latent":
            out["block-by-block"] = dict(_blocks_update=_block_by_block)
        for u in (1, 4):
            out[f"unroll-{u}"] = dict(_START_UNROLL=u)
    return out


def time_launches(fn, again, q, pools, L, launches):
    """us a launch: ``launches`` of them in one program, best of three.
    A fresh ``jit`` a call: its cache does not see the attributes a
    variant replaces."""
    def many(q, *pools):
        return jax.lax.fori_loop(
            0, launches, lambda i, q: again(q, fn(q, i % L, *pools)), q)
    run = jax.jit(many)
    run(q, *pools).block_until_ready()
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        run(q, *pools).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best / launches * 1e6


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--only", default="", help="comma-separated shape names")
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--check", action="store_true")
    ap.add_argument("--launches", type=int, default=200)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.rehearse:
        print("REHEARSAL (cpu): toy sizes under the interpreter, no time "
              "here is a device's")
        args.launches = 2
    elif jax.default_backend() != "tpu":
        sys.exit(f"needs a TPU, found {jax.default_backend()!r} "
                 "(--rehearse runs toy sizes on the CPU)")
    names = [n for n in args.only.split(",") if n] or list(SHAPES)
    for name in names:
        rng = np.random.default_rng(args.seed)
        fn, again, q, pools, L, nbytes, ref = build(SHAPES[name], rng,
                                                    args.rehearse)
        row = {"shape": name, "bytes": nbytes,
               "bytes_us": round(nbytes / PEAK_BYTES_S * 1e6, 2)}
        if args.check:
            got = np.asarray(jax.jit(fn)(q, L - 1, *pools), np.float32)
            want = np.asarray(jax.jit(ref)(q, L - 1, *pools), np.float32)
            row["max_err"] = float(np.abs(got - want).max())
        for label, attrs in variants(SHAPES[name]["kernel"],
                                     args.sweep).items():
            try:
                with patched(**attrs):
                    row[label] = round(time_launches(
                        fn, again, q, pools, L, args.launches), 2)
            except KeyError as missing:
                row[label] = f"no {missing.args[0]} in this tree"
        print(json.dumps(row), flush=True)
    if args.rehearse:
        print("REHEARSAL (cpu)")


if __name__ == "__main__":
    main()
