"""Token sampling for the v2 serving stack.

Reference parity: FastGen serves temperature / top-p sampling (the MII
layer's SamplingParams over inference/v2 logits). Two implementations of
the same math so both call sites are testable against each other:

* ``sample_tokens_rowwise`` — jitted device-side sampler with a PRNG
  key PER ROW (``fold_in_rows``); what ``InferenceEngineV2.generate``
  and both decode hot loops (per-token and the fused multi-step window)
  use, so a row's sampled stream is independent of batch composition.
  Rows with temperature<=0 take the argmax.
* ``sample_tokens`` — single-key batch variant (all rows drawn from one
  key): kept as the distribution-parity reference the sampling tests
  compare against host_sample; shares the scale/sort/mask/unsort body.
* ``host_sample`` — numpy twin used by the SplitFuse scheduler, where
  every request carries its own (temperature, top_p, seed) and sampling
  happens on the host from put()'s logits.

Top-p (nucleus): sort descending, keep the smallest prefix whose
cumulative probability reaches ``top_p`` (the first token always
survives), renormalize, sample.
"""

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30


def _topp_mask_sorted(sorted_logits, top_p, top_k=None):
    """Mask (to NEG_INF) the tail of descending-sorted logits whose
    cumulative softmax probability lies past top_p, and (when top_k > 0)
    every rank past top_k. top_p/top_k broadcast [N] -> rows; top_p <= 0
    clamps to keep-only-the-top-token (the limit behavior — all-masked
    rows would crash the host twin and sample uniform garbage on
    device)."""
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    # exclusive cumsum: a token is kept while the mass BEFORE it is
    # still below top_p — the first token survives any top_p > 0
    cum_before = jnp.cumsum(probs, axis=-1) - probs
    keep = cum_before < jnp.maximum(top_p, 1e-9)[..., None]
    if top_k is not None:
        rank = jnp.arange(sorted_logits.shape[-1])
        k = jnp.where(top_k > 0, top_k,
                      sorted_logits.shape[-1])[..., None]
        keep = keep & (rank[None, :] < k)
    return jnp.where(keep, sorted_logits, NEG_INF)


def _sorted_support(logits, temperature, top_p, top_k):
    """Shared scale/sort/mask body of both device samplers: returns the
    descending sort ``order`` [N, V] and the NEG_INF-masked sorted
    logits the categorical pick draws from (one definition so a top-p/
    top-k change can never diverge the two)."""
    scaled = logits / jnp.maximum(temperature, 1e-6)[..., None]
    order = jnp.argsort(-scaled, axis=-1)
    sorted_logits = jnp.take_along_axis(scaled, order, axis=-1)
    return order, _topp_mask_sorted(sorted_logits, top_p, top_k)


def _unsort_pick(logits, order, pick, temperature):
    """Map sorted-index picks back to token ids, with temperature<=0
    rows taking the plain argmax."""
    sampled = jnp.take_along_axis(order, pick[..., None], axis=-1)[..., 0]
    return jnp.where(temperature <= 0.0, jnp.argmax(logits, axis=-1),
                     sampled).astype(jnp.int32)


def sample_tokens(logits: jnp.ndarray, rng, temperature: jnp.ndarray,
                  top_p: jnp.ndarray,
                  top_k: jnp.ndarray = None) -> jnp.ndarray:
    """logits [N, V]; temperature/top_p/top_k [N] (0 temperature =
    greedy; top_k 0/None = no rank cutoff). Returns [N] int32 tokens.
    Jit-friendly (no data-dependent shapes). One rng for the batch —
    the distribution-parity reference; the decode hot paths use
    ``sample_tokens_rowwise``."""
    order, masked = _sorted_support(logits, temperature, top_p, top_k)
    pick = jax.random.categorical(rng, masked, axis=-1)      # [N] sorted-idx
    return _unsort_pick(logits, order, pick, temperature)


def greedy_tokens(logits: jnp.ndarray) -> jnp.ndarray:
    """Argmax next-token pick as int32 — the one definition of "greedy"
    shared by the decode hot loops and the in-window speculative verify,
    so the accept rule compares tokens produced by the same reduction
    order (the bit-identical-speculation contract leans on this)."""
    with jax.named_scope("pick"):
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def fold_in_rows(rng, row_seeds: jnp.ndarray,
                 gen_idx: jnp.ndarray) -> jnp.ndarray:
    """[N] per-row PRNG keys: fold the row's stable seed then its
    generated-token index into one base key. Both the per-token and the
    fused-window decode paths derive keys this way, which is what makes
    their sampled streams bit-identical (and invariant to how the batch
    is composed or padded)."""
    with jax.named_scope("pick"):
        return jax.vmap(lambda s, g: jax.random.fold_in(
            jax.random.fold_in(rng, s), g))(row_seeds, gen_idx)


def sample_tokens_rowwise(logits: jnp.ndarray, keys: jnp.ndarray,
                          temperature: jnp.ndarray, top_p: jnp.ndarray,
                          top_k: jnp.ndarray = None) -> jnp.ndarray:
    """Same temperature/top-p/top-k math as ``sample_tokens`` but with a
    PRNG key PER ROW (``keys`` [N, ...] from :func:`fold_in_rows`): row
    r's draw depends only on its own key, never on the batch around it.
    ``sample_tokens`` draws all rows from one key (key + row index), so
    a row's stream changes when the batch re-buckets — rowwise keys are
    what let the fused decode window keep EOS'd rows padded in place
    while matching the per-token path token-for-token."""
    with jax.named_scope("pick"):
        order, masked = _sorted_support(logits, temperature, top_p, top_k)
        pick = jax.vmap(jax.random.categorical)(keys, masked)  # sorted-idx
        return _unsort_pick(logits, order, pick, temperature)


def host_sample(logits: np.ndarray, rng: np.random.Generator,
                temperature: float, top_p: float, top_k: int = 0) -> int:
    """One row, host-side: same temperature/top-p/top-k math as
    sample_tokens (tested equivalent) with a per-request numpy
    Generator."""
    if temperature <= 0.0:
        return int(np.argmax(logits))
    scaled = logits.astype(np.float64) / max(temperature, 1e-6)
    order = np.argsort(-scaled)
    s = scaled[order]
    p = np.exp(s - s.max())
    p /= p.sum()
    cum_before = np.cumsum(p) - p
    keep = cum_before < max(top_p, 1e-9)  # <=0 clamps to top-token-only
    if top_k and top_k > 0:
        keep = keep & (np.arange(len(p)) < top_k)
    p = np.where(keep, p, 0.0)
    p /= p.sum()
    return int(order[rng.choice(len(p), p=p)])
