"""Decode launches for the attention walk's tests (PR 50): rows whose LAST
chunk holds 1, 2, 3, ``cp - 1`` and ``cp`` pages, contexts that end exactly
on a chunk, a row of no length, over pages out of order; the tiled kernel
at two geometries and over an int8 pool, the window launch over rings that
have wrapped, the latent kernel. The four test files that hold the kernels
to them share this module, and so does the fixture of what the PARENT of
PR 50 (commit cedb8f7: a wait a page, the products over a whole chunk)
returned for the same inputs:

    cd <a checkout of cedb8f7> && PYTHONPATH=. python \\
        <this repo>/tests/unit/inference/walk_cases.py <out.npz>

wrote ``fixtures/walk_parent_outputs_pr50.npz`` (float32, 220 KB).

PR 59 adds the TOKEN TILE's launches beside them (``PROMPT_CASES``): rows
that feed many tokens, so that a tile of up to 128 of them sees most of a
row's chunks whole (no mask), some by an edge, and some with another row's
tokens beside its own.
"""

import functools
import importlib
import os
import sys

import numpy as np

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures",
                       "walk_parent_outputs_pr50.npz")

# name -> what the case is made of; ``pages``: a chunk's (cp), so that P =
# cp * bs is 512 positions everywhere
CASES = {
    "tiled-hpb2": dict(kernel="tiled", nh=4, kvh=4, hd=64, bs=16, pages=32),
    "tiled-group8": dict(kernel="tiled", nh=16, kvh=2, hd=128, bs=16,
                         pages=32),
    "tiled-int8": dict(kernel="tiled", nh=4, kvh=4, hd=64, bs=16, pages=32,
                       int8=True),
    "window-ring": dict(kernel="tiled", nh=16, kvh=2, hd=128, bs=16,
                        pages=32, window=520, ring=40),
    "latent": dict(kernel="latent", nh=4, dc=32, dr=16, W=128, bs=8,
                   pages=64),
}


# the rows of a prompt launch, ``(new tokens, the context they end at)``:
# 172 tokens behind 1,128 (a tile of 128 whose first two chunks are whole
# and whose third is an edge; at 64 a tile, a chunk that the tile's LAST
# token sees whole and its first does not; a tail of 44 tokens), 100 tokens
# behind 500 that start in the first row's last tile (a tile of two rows,
# and of three: never whole), a prompt of 40
_ROWS = ((172, 1300), (100, 600), (40, 40))
# name -> geometry (a lane block's query rows a token: 2, 4, 7, 8, 16: the
# tile is 128 tokens, and 64 at sixteen), the pool kept and served in
# ``dtype``, the launch's token bucket
PROMPT_CASES = {
    "hpb2-bf16": dict(nh=4, kvh=4, hd=64, dtype="bfloat16"),
    "hpb2-int8": dict(nh=4, kvh=4, hd=64, int8=True),
    "group4-bf16": dict(nh=8, kvh=2, hd=128, dtype="bfloat16"),
    "group7-float32": dict(nh=7, kvh=1, hd=128),
    "group8-bf16": dict(nh=16, kvh=2, hd=128, dtype="bfloat16"),
    "group8-int8": dict(nh=16, kvh=2, hd=128, int8=True),
    "group16-float32": dict(nh=16, kvh=1, hd=128),
    # a window of 1,300 over rings of 50 pages: the first row's walk starts
    # at page 45 of its positions and wraps to place 0 inside its first
    # tile's walk; a tile's second chunk is whole (inside every token's
    # window), its first and last are edges
    "window-wraps": dict(nh=16, kvh=2, hd=128, window=1300, ring=50,
                         rows=((256, 3000), (100, 900)), bucket=384),
    # the first token's window starts ON a page (1,801 - 713 = 34 x 32), so
    # the walk's first chunk is whole for the tile's first token and not
    # for its last: masked
    "window-first-not-last": dict(nh=16, kvh=2, hd=128, window=713, ring=30,
                                  rows=((200, 2000),), bucket=256),
    # a prompt shorter than one tile, alone in its launch
    "short": dict(nh=16, kvh=2, hd=128, rows=((40, 40),), bucket=64),
}


PROMPT_BS = 32            # 16 pages a chunk of 512 positions


def prompt_rows(case):
    return PROMPT_CASES[case].get("rows", _ROWS)


@functools.lru_cache(maxsize=None)
def build_prompt(case):
    """``(args, kw, reference kw)`` of the case's token-tile launch, as
    :func:`build` gives a decode launch's: the rows' tokens packed in
    order and padded to the bucket, pages out of order."""
    import jax.numpy as jnp
    c = PROMPT_CASES[case]
    rng = np.random.default_rng(100 + sorted(PROMPT_CASES).index(case))
    rows, bs = prompt_rows(case), PROMPT_BS
    R = len(rows)
    MB = c.get("ring") or -(-max(ctx for _, ctx in rows) // bs)
    nb = 1 + R * MB
    T = c.get("bucket", 384)
    ids = [r for r, (new, _) in enumerate(rows) for _ in range(new)]
    bounds = [b for new, ctx in rows for b in range(ctx - new + 1, ctx + 1)]
    pad = T - len(ids)
    tables = jnp.asarray(rng.permutation(np.arange(1, nb)).reshape(R, MB),
                         jnp.int32)
    desc = (1, jnp.asarray(ids + [0] * pad, jnp.int32),
            jnp.asarray(bounds + [0] * pad, jnp.int32), tables)
    F = c["kvh"] * c["hd"]
    io = getattr(jnp, c.get("dtype", "float32"))
    q = jnp.asarray(rng.normal(size=(T, c["nh"], c["hd"])), io)
    kw = {}
    if c.get("int8"):
        k, v = (jnp.asarray(rng.integers(-127, 128, (2, nb, bs, F)),
                            jnp.int8) for _ in range(2))
        kw = dict(k_scale=jnp.asarray(
            rng.uniform(0.005, 0.03, (nb, c["kvh"])), jnp.float32),
            v_scale=jnp.asarray(
            rng.uniform(0.005, 0.03, (nb, c["kvh"])), jnp.float32))
    else:
        k, v = (jnp.asarray(rng.normal(size=(2, nb, bs, F)), io)
                for _ in range(2))
    if c.get("window"):
        kw["window"] = c["window"]
    return (q, k, v) + desc, dict(kw, variant="tiled"), kw


def prompt_launch(case):
    """The case through the token tile of the importable tree."""
    import jax
    args, kw, _ = build_prompt(case)
    return np.asarray(jax.jit(functools.partial(
        ra().ragged_attention, **kw))(*args), np.float32)


@functools.lru_cache(maxsize=None)
def prompt_output(case):
    return prompt_launch(case)


def prompt_reference(case):
    """The gathering reference, eight tokens at a time: every token's
    gathered context at once is gigabytes."""
    import jax
    (q, k, v, layer, ids, bounds, tables), _, kw = build_prompt(case)

    def some(at):
        return ra().ragged_attention_reference(
            q[at], k, v, layer, ids[at], bounds[at], tables, **kw)
    out = jax.jit(lambda: jax.lax.map(
        some, np.arange(q.shape[0]).reshape(-1, 8)))()
    return np.asarray(out, np.float32).reshape(q.shape)


def ra():
    """``kernels/ragged_attention`` of whichever tree is importable (the
    package exports a function under the module's name)."""
    return importlib.import_module(
        "deepspeed_tpu.inference.v2.kernels.ragged_attention")


def contexts(case):
    """A row a shape of last chunk: by pages 1, 2, 3, cp - 1, cp in a
    row's ONLY chunk and in its SECOND, a context that ends exactly on
    one chunk and on two, a row of no length; over a ring, contexts
    inside the first lap (the same page counts) and far round it."""
    c = CASES[case]
    bs, cp = c["bs"], c["pages"]
    P = cp * bs
    first = [bs - 3, 2 * bs, 3 * bs - 1, (cp - 1) * bs - 5, P - 7, P]
    if c.get("ring"):
        return first[:-1] + [0, P + 8, 1000, 5000, 7777, 12345,
                             c["ring"] * bs, c["window"], c["window"] + 1]
    return first + [0] + [P + n for n in (
        1, 2 * bs - 1, 3 * bs, (cp - 1) * bs, P - 1, P)]


@functools.lru_cache(maxsize=None)
def build(case):
    """``(args, kw, reference kw)`` of the case's launch: what
    ``ragged_attention`` / ``latent_attention`` take in order, the
    keywords of the one-token launch and of the gathering reference."""
    import jax.numpy as jnp
    c = CASES[case]
    rng = np.random.default_rng(sorted(CASES).index(case))
    lens = contexts(case)
    R, bs = len(lens), c["bs"]
    MB = c.get("ring") or -(-max(lens) // bs)
    nb = 1 + R * MB
    tables = jnp.asarray(rng.permutation(np.arange(1, nb)).reshape(R, MB),
                         jnp.int32)
    desc = (1, jnp.arange(R, dtype=jnp.int32), jnp.asarray(lens, jnp.int32),
            tables)
    if c["kernel"] == "latent":
        nh, dc, dr, W = c["nh"], c["dc"], c["dr"], c["W"]
        pool = np.zeros((2, nb, bs, W), np.float32)
        pool[..., :dc + dr] = rng.normal(size=(2, nb, bs, dc + dr))
        q = np.zeros((nh, R, W), np.float32)
        q[..., :dc + dr] = rng.normal(size=(nh, R, dc + dr))
        kw = dict(dc=dc, scale=0.2)
        return ((jnp.asarray(q), jnp.asarray(pool)) + desc,
                dict(kw, interpret=True, one_token=True), kw)
    F = c["kvh"] * c["hd"]
    q = jnp.asarray(rng.normal(size=(R, c["nh"], c["hd"])), jnp.float32)
    kw = {}
    if c.get("int8"):
        k, v = (jnp.asarray(rng.integers(-127, 128, (2, nb, bs, F)),
                            jnp.int8) for _ in range(2))
        kw = dict(k_scale=jnp.asarray(
            rng.uniform(0.005, 0.03, (nb, c["kvh"])), jnp.float32),
            v_scale=jnp.asarray(
            rng.uniform(0.005, 0.03, (nb, c["kvh"])), jnp.float32))
    else:
        k, v = (jnp.asarray(rng.normal(size=(2, nb, bs, F)), jnp.float32)
                for _ in range(2))
    if c.get("window"):
        kw["window"] = c["window"]
    return ((q, k, v) + desc, dict(kw, variant="tiled", one_token=True), kw)


def launch(case):
    """The case through the kernel of the importable tree, float32."""
    args, kw, _ = build(case)
    fn = ra().latent_attention if CASES[case]["kernel"] == "latent" \
        else ra().ragged_attention
    return np.asarray(fn(*args, **kw), np.float32)


@functools.lru_cache(maxsize=None)
def output(case):
    """:func:`launch`, once a process (the test files share it)."""
    return launch(case)


def reference(case):
    args, _, kw = build(case)
    fn = ra().latent_attention_reference \
        if CASES[case]["kernel"] == "latent" \
        else ra().ragged_attention_reference
    return np.asarray(fn(*args, **kw), np.float32)


def parent_output(case):
    with np.load(FIXTURE) as f:
        return f[case]


def lengths(case):
    """The rows' contexts along the output's row axis (1 for the latent
    kernel's ``[nh, R, dc]``, 0 for the tiled one's ``[R, nh, hd]``)."""
    return np.asarray(contexts(case)), \
        1 if CASES[case]["kernel"] == "latent" else 0


if __name__ == "__main__":
    sys.path.insert(0, os.getcwd())
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    np.savez_compressed(sys.argv[1], **{c: launch(c) for c in CASES})
    print("wrote", sys.argv[1], "from", ra().__file__)
