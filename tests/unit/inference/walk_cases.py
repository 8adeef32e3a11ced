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
