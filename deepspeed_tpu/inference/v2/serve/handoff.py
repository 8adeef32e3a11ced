"""Paged-KV handoff between engine replicas.

The disaggregated serving path (docs/SERVING.md § Routing tier) runs a
request's prefill on a dedicated prefill replica, then moves the
sequence to a decode replica: the prefill side **exports** the
sequence's KV blocks plus a descriptor, the bytes travel (in-process
today, a wire tomorrow — the payload is a real serialized buffer either
way so the path is honest about its cost), and the decode side
**restores** them into its own pool under freshly allocated block ids.
Because KV content is copied bit-for-bit and the descriptor recreates
the exact scheduler state a colocated request has after its final
prompt chunk, handed-off token streams are bit-identical to colocated
serving — parity-pinned by tests/unit/inference/test_router.py.

Payload layout (``serialize``): one ``.npz`` buffer holding a JSON
descriptor (uid, seen_tokens, block count/size, fed-token log) and one
array per KV-pool leaf — ``[num_layers, n_blocks, ...]``, the
sequence's blocks gathered along the pool's block axis. The int8
``kv_quant`` pool hands off the same way (its per-(block, kv-head)
scale leaves — ``[L, n_blocks, kvh]`` — are just more pool leaves;
restore overwrites the destination blocks' scales, so the int8 content
pairs with its exact scales and the roundtrip is bit-exact — pinned by
tests/unit/inference/test_kv_quant_serving.py).

Gather/scatter shapes are bucketed (pow2 over the block count, padded
with the null block) so repeated handoffs of different-length
sequences reuse compiled programs instead of respecializing per
length; pad rows carry zeros and land in the null block, which no
attention read ever sees (reads are masked by position).

**Chunked streaming protocol** (ISSUE 12): :func:`export_chunks`
splits the same payload into one HEADER chunk (the descriptor plus the
chunk manifest: ranges and per-chunk CRCs) and N per-page-range KV
chunks, each an independent ``.npz`` buffer. The decode side drives a
:class:`ChunkedRestore`: ``begin`` adopts the blocks, ``apply``
scatters ONE range (CRC-checked, idempotent on retransmit — the
resumability unit), ``commit_check`` verifies every range arrived, and
``abort`` frees the partially-filled blocks WITHOUT registering their
content in the prefix index (a partial block must never be reused as a
cached prefix). Because each ``apply`` is one small scatter executed
between the serving loop's scheduler steps, the transfer overlaps the
decode replica's running batch instead of stalling it — the
``handoff_chunk_*`` metrics and tests/unit/inference/test_remote_serving.py
hold that overlap.
"""

import io
import json
import zlib
from functools import partial
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ....utils.bucketing import pow2_bucket
from ..ragged.blocked_allocator import NULL_BLOCK

_DESCRIPTOR_KEY = "__descriptor__"


@jax.jit
def _gather_blocks(leaf, idx):
    return leaf[:, idx]


@partial(jax.jit, donate_argnums=(0,))
def _scatter_blocks(leaf, idx, data):
    # pad rows all target the null block with identical (zero) data, so
    # the duplicate-index scatter stays deterministic
    return leaf.at[:, idx].set(data)


def check_leaf_shapes(kv: Dict, cache: Dict) -> None:
    """Refuse content whose rows are not the pool's: a leaf ``[L, n,
    ...]`` must carry the pool leaf's layers and its ``shape[2:]``. A
    pack written by the five-axis per-head layout ``[L, n, bs, kvh,
    hd]`` holds the same bytes as the stored ``[L, n, bs, kvh * hd]``
    and is still refused, by shape, before anything is scattered."""
    for key, leaf in cache.items():
        got = tuple(np.shape(kv[key]))
        if got[:1] + got[2:] != tuple(leaf.shape[:1] + leaf.shape[2:]):
            raise ValueError(
                f"kv leaf {key!r} of shape {got} does not fit the pool's "
                f"{tuple(leaf.shape)}: layers and everything behind the "
                f"block axis must match (the two replicas must share the "
                f"KV layout)")


def export_sequence(engine, uid: int, trace_ctx=None) -> Dict:
    """Snapshot ``uid``'s KV blocks and descriptor from ``engine`` into
    a host-side pack (plain numpy + ints). The sequence stays live on
    the source engine; callers flush it once the handoff is accepted.

    ``trace_ctx`` (telemetry/context.py) rides the descriptor as a wire
    payload, so the decode side CONTINUES the prefill side's
    distributed trace — the trace id must cross the process boundary
    inside the handoff itself for remote replicas, not alongside it."""
    sm = engine.state_manager
    if engine.model.cfg.has_state:
        raise NotImplementedError(
            "handoff moves a sequence's blocks and no state slot: a model "
            "that keeps recurrent state a sequence (linear-attention or "
            "state-space layers) is served without it")
    seq = sm.seqs.get(uid)
    if seq is None:
        raise ValueError(f"cannot export uid {uid}: unknown sequence")
    blocks = [int(b) for b in seq.blocks]
    nb = len(blocks)
    bucket = pow2_bucket(max(nb, 1), sm.max_blocks_per_seq)
    idx = np.full(bucket, NULL_BLOCK, np.int32)
    idx[:nb] = blocks
    kv = {key: np.asarray(_gather_blocks(leaf, jnp.asarray(idx)))[:, :nb]
          for key, leaf in engine.kv_cache.items()}
    pack = {
        "uid": int(uid),
        "seen_tokens": int(seq.seen_tokens),
        "n_blocks": nb,
        "block_size": int(sm.block_size),
        "token_log": [int(t) for t in seq.token_log],
        "kv": kv,
    }
    if trace_ctx is not None:
        pack["trace"] = trace_ctx.to_wire()
    return pack


def serialize(pack: Dict) -> bytes:
    """Pack -> one self-describing ``.npz`` buffer (the wire format)."""
    descriptor = {k: pack[k] for k in
                  ("uid", "seen_tokens", "n_blocks", "block_size",
                   "token_log", "trace") if k in pack}
    kv_wire = {}
    kv_dtypes = {}
    for key, arr in pack["kv"].items():
        arr = np.ascontiguousarray(arr)
        kv_dtypes[key] = arr.dtype.name
        if arr.dtype.kind == "V":
            # numpy cannot round-trip ml_dtypes leaves (bfloat16, fp8)
            # through .npz — np.load hands back an opaque void dtype —
            # so ship the raw bytes and view them back on the far side
            arr = arr.view(np.uint8)
        kv_wire[f"kv_{key}"] = arr
    descriptor["kv_dtypes"] = kv_dtypes
    bio = io.BytesIO()
    np.savez(bio,
             **{_DESCRIPTOR_KEY: np.frombuffer(
                 json.dumps(descriptor).encode(), np.uint8)},
             **kv_wire)
    return bio.getvalue()


def _wire_dtype(name: str) -> np.dtype:
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes
        return np.dtype(getattr(ml_dtypes, name))


def deserialize(buf: bytes) -> Dict:
    with np.load(io.BytesIO(buf)) as z:
        pack = json.loads(bytes(z[_DESCRIPTOR_KEY]).decode())
        dtypes = pack.pop("kv_dtypes", {})
        kv = {}
        for name in z.files:
            if not name.startswith("kv_"):
                continue
            key, arr = name[3:], z[name]
            want = dtypes.get(key)
            if want and arr.dtype.name != want:
                arr = arr.view(_wire_dtype(want))
            kv[key] = arr
        pack["kv"] = kv
    return pack


# ---------------------------------------------------------------------------
# chunked streaming protocol (module docstring)
# ---------------------------------------------------------------------------
def _leaf_wire_bytes(arr: np.ndarray) -> bytes:
    arr = np.ascontiguousarray(arr)
    return (arr.view(np.uint8) if arr.dtype.kind == "V" else arr) \
        .tobytes()


def _chunk_crc(kv: Dict[str, np.ndarray]) -> int:
    crc = 0
    for key in sorted(kv):
        crc = zlib.crc32(_leaf_wire_bytes(kv[key]), crc)
    return crc


def _npz_chunk(descriptor: Dict, kv: Dict[str, np.ndarray]) -> bytes:
    """One self-describing chunk buffer (same ml_dtypes raw-bytes trick
    as :func:`serialize`)."""
    kv_wire, kv_dtypes = {}, {}
    for key, arr in kv.items():
        arr = np.ascontiguousarray(arr)
        kv_dtypes[key] = arr.dtype.name
        if arr.dtype.kind == "V":
            arr = arr.view(np.uint8)
        kv_wire[f"kv_{key}"] = arr
    descriptor = dict(descriptor, kv_dtypes=kv_dtypes)
    bio = io.BytesIO()
    np.savez(bio,
             **{_DESCRIPTOR_KEY: np.frombuffer(
                 json.dumps(descriptor).encode(), np.uint8)},
             **kv_wire)
    return bio.getvalue()


def parse_chunk(buf: bytes) -> Dict:
    """Chunk buffer -> ``{"descriptor": ..., "kv": {...}}`` with wire
    dtypes restored."""
    with np.load(io.BytesIO(buf)) as z:
        descriptor = json.loads(bytes(z[_DESCRIPTOR_KEY]).decode())
        dtypes = descriptor.pop("kv_dtypes", {})
        kv = {}
        for name in z.files:
            if not name.startswith("kv_"):
                continue
            key, arr = name[3:], z[name]
            want = dtypes.get(key)
            if want and arr.dtype.name != want:
                arr = arr.view(_wire_dtype(want))
            kv[key] = arr
    return {"descriptor": descriptor, "kv": kv}


def chunk_pack(pack: Dict, chunk_blocks: int) -> List[bytes]:
    """Split one exported pack into ``[header, kv-chunk...]`` buffers:
    the header carries the descriptor plus the chunk manifest (ranges +
    CRCs), each KV chunk one ``chunk_blocks``-wide block range."""
    chunk_blocks = max(1, int(chunk_blocks))
    nb = int(pack["n_blocks"])
    ranges = [(i, min(i + chunk_blocks, nb))
              for i in range(0, nb, chunk_blocks)]
    chunks: List[bytes] = []
    crcs: List[int] = []
    for seq, (i, j) in enumerate(ranges):
        kv = {key: np.ascontiguousarray(arr[:, i:j])
              for key, arr in pack["kv"].items()}
        crc = _chunk_crc(kv)
        crcs.append(crc)
        chunks.append(_npz_chunk(
            {"kind": "kv", "uid": int(pack["uid"]), "seq": seq,
             "block_start": i, "block_end": j, "crc32": crc}, kv))
    header = {k: pack[k] for k in
              ("uid", "seen_tokens", "n_blocks", "block_size",
               "token_log", "trace") if k in pack}
    header.update({
        "kind": "header", "chunk_blocks": chunk_blocks,
        "n_chunks": len(ranges),
        "chunk_ranges": [[i, j] for i, j in ranges],
        "chunk_crcs": crcs,
        "leaves": sorted(pack["kv"]),
        "leaf_dtypes": {k: np.ascontiguousarray(v).dtype.name
                        for k, v in pack["kv"].items()},
    })
    return [_npz_chunk(header, {})] + chunks


def export_chunks(engine, uid: int, chunk_blocks: int = 4,
                  trace_ctx=None) -> List[bytes]:
    """Snapshot ``uid``'s KV and serialize it as the chunked wire form
    (``[header, kv-chunk...]``) — the streaming counterpart of
    ``serialize(export_sequence(...))``."""
    return chunk_pack(export_sequence(engine, uid, trace_ctx=trace_ctx),
                      chunk_blocks)


def parse_header(buf: bytes) -> Dict:
    chunk = parse_chunk(buf)
    d = chunk["descriptor"]
    if d.get("kind") != "header":
        raise ValueError(
            f"chunked handoff must start with the header chunk "
            f"(got kind={d.get('kind')!r})")
    return d


class ChunkedRestore:
    """Decode-side state machine for one streaming handoff.

    All methods run on the serving-loop thread (they touch the engine).
    ``apply`` is idempotent per chunk sequence number — a retransmitted
    chunk re-scatters identical content — which is what makes the
    transfer resumable over a flaky wire."""

    def __init__(self, engine, uid: int, header: Dict):
        self.engine = engine
        self.uid = int(uid)
        self.header = header
        self.received: set = set()
        self._begun = False
        self._done = False

    def begin(self) -> None:
        """Validate the layout and adopt the destination blocks."""
        sm = self.engine.state_manager
        h = self.header
        if sm.block_size != h["block_size"]:
            raise ValueError(
                f"handoff block-size mismatch: payload has "
                f"{h['block_size']}, target pool has {sm.block_size} "
                f"(disaggregated replicas must share the KV layout)")
        if set(h["leaves"]) != set(self.engine.kv_cache):
            raise ValueError(
                f"handoff pool-leaf mismatch: payload has "
                f"{sorted(h['leaves'])}, target pool has "
                f"{sorted(self.engine.kv_cache)} (kv_quant must match)")
        self.seq = sm.adopt_sequence(self.uid, int(h["n_blocks"]),
                                     h["seen_tokens"], h["token_log"])
        self._begun = True

    def apply(self, chunk: Dict) -> None:
        """Integrity-check and scatter ONE block-range chunk."""
        d = chunk["descriptor"]
        if d.get("kind") != "kv":
            raise ValueError(f"expected a kv chunk, got "
                             f"{d.get('kind')!r}")
        seq_no = int(d["seq"])
        if not 0 <= seq_no < self.header["n_chunks"]:
            raise ValueError(f"chunk seq {seq_no} outside the header's "
                             f"{self.header['n_chunks']} chunks")
        i, j = int(d["block_start"]), int(d["block_end"])
        if [i, j] != list(self.header["chunk_ranges"][seq_no]):
            raise ValueError(
                f"chunk {seq_no} range [{i},{j}) disagrees with the "
                f"header manifest "
                f"{self.header['chunk_ranges'][seq_no]}")
        crc = _chunk_crc(chunk["kv"])
        if crc != int(d["crc32"]) \
                or crc != int(self.header["chunk_crcs"][seq_no]):
            raise ValueError(
                f"chunk {seq_no} failed its crc32 integrity check "
                f"(corrupted in transfer)")
        if set(chunk["kv"]) != set(self.engine.kv_cache):
            raise ValueError("chunk leaf set disagrees with the pool")
        check_leaf_shapes(chunk["kv"], self.engine.kv_cache)
        blocks = self.seq.blocks[i:j]
        nb = len(blocks)
        bucket = pow2_bucket(max(nb, 1),
                             self.engine.state_manager.max_blocks_per_seq)
        idx = np.full(bucket, NULL_BLOCK, np.int32)
        idx[:nb] = blocks
        for key in list(self.engine.kv_cache):
            leaf = self.engine.kv_cache[key]
            data = np.zeros((leaf.shape[0], bucket) + leaf.shape[2:],
                            np.asarray(chunk["kv"][key]).dtype)
            data[:, :nb] = chunk["kv"][key]
            self.engine.kv_cache[key] = _scatter_blocks(
                leaf, jnp.asarray(idx), jnp.asarray(data, leaf.dtype))
        self.received.add(seq_no)

    def missing(self) -> List[int]:
        return [s for s in range(int(self.header["n_chunks"]))
                if s not in self.received]

    def commit_check(self) -> None:
        gaps = self.missing()
        if gaps:
            raise ValueError(
                f"handoff incomplete: missing chunks {gaps} of "
                f"{self.header['n_chunks']}")
        self._done = True

    def abort(self) -> None:
        """Free the adopted blocks. The token log is cleared FIRST so
        flush cannot register partially-filled blocks in the prefix
        index (a later request must never reuse garbage as a cached
        prefix)."""
        if self._begun and not self._done:
            sm = self.engine.state_manager
            seq = sm.seqs.get(self.uid)
            if seq is not None:
                seq.token_log = []
                sm.flush_sequence(self.uid)
        self._done = True


def restore_sequence(engine, pack: Dict, uid: int) -> None:
    """Install the handed-off sequence into ``engine`` as ``uid``:
    allocate fresh blocks, scatter the KV content into them, and adopt
    a descriptor in exactly the state the decode paths expect."""
    sm = engine.state_manager
    if sm.block_size != pack["block_size"]:
        raise ValueError(
            f"handoff block-size mismatch: payload has "
            f"{pack['block_size']}, target pool has {sm.block_size} "
            f"(disaggregated replicas must share the KV layout)")
    if set(pack["kv"]) != set(engine.kv_cache):
        raise ValueError(
            f"handoff pool-leaf mismatch: payload has "
            f"{sorted(pack['kv'])}, target pool has "
            f"{sorted(engine.kv_cache)} (kv_quant must match)")
    check_leaf_shapes(pack["kv"], engine.kv_cache)
    nb = int(pack["n_blocks"])
    seq = sm.adopt_sequence(uid, nb, pack["seen_tokens"],
                            pack["token_log"])
    try:
        bucket = pow2_bucket(max(nb, 1), sm.max_blocks_per_seq)
        idx = np.full(bucket, NULL_BLOCK, np.int32)
        idx[:nb] = seq.blocks
        for key in list(engine.kv_cache):
            leaf = engine.kv_cache[key]
            data = np.zeros((leaf.shape[0], bucket) + leaf.shape[2:],
                            np.asarray(pack["kv"][key]).dtype)
            data[:, :nb] = pack["kv"][key]
            engine.kv_cache[key] = _scatter_blocks(
                leaf, jnp.asarray(idx), jnp.asarray(data, leaf.dtype))
    except Exception:
        sm.flush_sequence(uid)   # do not leak the adopted blocks
        raise
