"""Paged (blocked-KV) decode attention.

TPU-native equivalent of the reference's blocked flash attention for ragged
decode (inference/v2/kernels/ragged_ops/blocked_flash/ + the CUDA paged-KV
gather). One query token per sequence attends over its block table,
streaming each KV block from HBM into VMEM exactly once — no
[N, max_ctx, ...] gather is ever materialized (the jnp fallback in
paged_model.py does materialize it, which is why this kernel is the
serving hot path).

Decode is the ragged kernel (``ragged_attention.py``) with one token per
row: the token -> row map is the identity and ``lengths`` is per
sequence. One kernel family, one variant table
(:func:`~.ragged_attention.kernel_variant`), one set of numerics. That
every row has one token is known here when the program is traced, so
the tiled variant is asked for its one-token form (``one_token=True``:
a row's chunks meet that row's own query rows, not a tile of 16
tokens' of which 15 are masked).
"""

from typing import Optional

import jax.numpy as jnp

from .ragged_attention import ragged_attention


def paged_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                    v_cache: jnp.ndarray, layer, block_tables: jnp.ndarray,
                    lengths: jnp.ndarray,
                    k_scale: jnp.ndarray = None,
                    v_scale: jnp.ndarray = None,
                    variant: Optional[str] = None,
                    runs: jnp.ndarray = None) -> jnp.ndarray:
    """q [N, nh, hd]; k/v_cache the pool's leaves whole,
    [L, nb, bs, kvh * hd], and the ``layer`` to attend; block_tables
    [N, MB] int32; lengths [N] (valid tokens incl. the current one);
    ``k_scale``/``v_scale`` [nb, kvh], the layer's, for the int8
    ``kv_quant`` pool; ``runs``: how the tables lie
    (``ragged_attention.table_runs``), where the caller has made it.
    Returns [N, nh, hd]."""
    return ragged_attention(
        q, k_cache, v_cache, layer, jnp.arange(q.shape[0], dtype=jnp.int32),
        lengths, block_tables, k_scale=k_scale, v_scale=v_scale,
        variant=variant, one_token=True, runs=runs)
