"""Versioned weight payloads and zero-recompile param hot-swap.

The train->serve seam of the Hybrid Engine (docs/SERVING.md § Blue/green
weight push; docs/TRAINING.md § Hybrid engine): a training engine
publishes its live params as a **versioned, chunked, CRC-checked**
payload — the same frame discipline as the KV handoff (serve/handoff.py)
— and a serving engine ingests it by **donated buffer replacement**:
every new leaf is ``device_put`` onto the OLD leaf's sharding with the
OLD leaf's dtype, so the swapped tree presents the exact executable
signature (shape x dtype x sharding) every compiled serving program was
keyed on. Steady-state recompiles across a swap are zero *by
construction* — and held by the recompile watchdog in
``test_steady_state_recompiles[hot_swap]`` and the parity tests.

Payload layout (``chunk_weight_leaves``): one HEADER chunk carrying the
version, the leaf manifest (names / shapes / dtypes) and per-chunk
CRC32s, then N leaf-group chunks — leaves are packed into size-capped
buckets (``bucket_bytes``) so the publisher gathers and serializes one
bucket at a time instead of materializing the whole model twice. Each
chunk is an independent ``.npz`` buffer (handoff's ``_npz_chunk``), so
retransmit is idempotent and a corrupt chunk fails TYPED at its CRC
without touching the serving params.

Leaves travel as fp32 numpy (the lossless host form of bf16/fp16 train
params — checkpoint/state_checkpoint's ``_fetch`` convention); the
ingest side casts to the serving dtype with the same ``jnp.asarray``
cast a fresh engine applies at init, which is what makes post-swap
streams bit-identical to a fresh engine built from the published
payload (the hot-swap parity pin).

DELTA payloads (docs/SERVING.md § Delta weight push): at RLHF
publish-every-N cadence push bytes are the scaling limit, so
``chunk_weight_deltas`` ships ``current - base`` block-quantized to
int8 with fp32 per-block scales (the PR 9 quantized-wire helpers,
comm/quantized.py) instead of full fp32 leaves — ~4x fewer bytes.
The header grows ``payload_kind="delta"``, ``base_version`` and a
per-chunk manifest; each chunk carries the concatenated int8 values +
scales for its leaf bucket (EQuARX, arXiv:2506.17615 — the publisher
carries error-feedback residuals across pushes, see
hybrid_engine.WeightPublisher). Ingest (``commit_stager``) rebuilds
``base + dequant(delta)`` HOST-SIDE against the fp32 base retained
from the last applied payload, then runs the same donated-buffer swap
— still zero steady-state recompiles. A stale base, version mismatch
or CRC failure raises typed BEFORE any live param is touched (the
router falls back to a full push). Reconstruction is deterministic
numpy fp32, so every replica following the delta chain holds
bit-identical weights — the publisher's error-feedback reference
tracks them exactly. ``quant="off"`` ships changed leaves at full
fp32 (bitwise-unchanged leaves are skipped), making reconstruction
EXACTLY equal to a full push.
"""

import time
from typing import Dict, Iterable, List, Sequence, Tuple

import numpy as np

from .handoff import _chunk_crc, _npz_chunk, parse_chunk

_HEADER_KIND = "weights_header"
_CHUNK_KIND = "weights"

# default leaf-group bucket: bounds how much of the model the publisher
# holds gathered at once (and the per-frame wire unit of a remote push)
DEFAULT_BUCKET_BYTES = 16 << 20


def _metrics():
    from ....telemetry import get_registry
    reg = get_registry()
    return (
        reg.counter("serving_weight_update_chunks_total",
                    "weight-payload chunks staged by serving runtimes"),
        reg.counter("serving_weight_update_bytes_total",
                    "serialized weight-payload bytes staged",
                    unit="bytes"),
    )


def flatten_params(tree) -> Tuple[List[Tuple[str, object]], object]:
    """Flatten a params pytree to ``([(path, leaf)], treedef)`` with the
    checkpoint layer's stable path naming — the one key space the
    publisher, the payload and every ingesting engine share."""
    from ....checkpoint.state_checkpoint import _leaf_paths
    return _leaf_paths(tree)


def fetch_leaf(leaf) -> np.ndarray:
    """Gather one (possibly sharded) leaf to host fp32 numpy — the
    checkpoint layer's lossless wire form (bf16/fp16 upcast)."""
    from ....checkpoint.state_checkpoint import _fetch
    return _fetch(leaf)


def plan_buckets(items: Sequence[Tuple[str, object]],
                 bucket_bytes: int = DEFAULT_BUCKET_BYTES
                 ) -> List[List[str]]:
    """Group leaf names into size-capped publication buckets (fp32 host
    bytes), preserving tree order — the gather/serialize granularity."""
    bucket_bytes = max(int(bucket_bytes), 1)
    buckets: List[List[str]] = []
    cur: List[str] = []
    cur_bytes = 0
    for name, leaf in items:
        nbytes = int(np.prod(getattr(leaf, "shape", ()) or (1,))) * 4
        if cur and cur_bytes + nbytes > bucket_bytes:
            buckets.append(cur)
            cur, cur_bytes = [], 0
        cur.append(name)
        cur_bytes += nbytes
    if cur:
        buckets.append(cur)
    return buckets


def chunk_weight_leaves(groups: Iterable[Dict[str, np.ndarray]],
                        version: int) -> List[bytes]:
    """Serialize host leaf groups into the wire payload
    ``[header, chunk...]``. ``groups`` yields ``{name: fp32 ndarray}``
    dicts (one per publication bucket)."""
    chunks: List[bytes] = []
    crcs: List[int] = []
    chunk_leaves: List[List[str]] = []
    leaf_meta: Dict[str, dict] = {}
    param_count = 0
    for seq, group in enumerate(groups):
        group = {k: np.ascontiguousarray(np.asarray(v, np.float32))
                 for k, v in group.items()}
        crc = _chunk_crc(group)
        crcs.append(crc)
        chunk_leaves.append(sorted(group))
        for name, arr in group.items():
            leaf_meta[name] = {"shape": list(arr.shape)}
            param_count += int(arr.size)
        chunks.append(_npz_chunk(
            {"kind": _CHUNK_KIND, "seq": seq, "crc32": crc,
             "version": int(version)}, group))
    header = _npz_chunk(
        {"kind": _HEADER_KIND, "version": int(version),
         "n_chunks": len(chunks), "chunk_crcs": crcs,
         "chunk_leaves": chunk_leaves, "leaf_meta": leaf_meta,
         "param_count": param_count}, {})
    return [header] + chunks


# ---------------------------------------------------------------------------
# Delta payloads (quantized weight-delta publication)
# ---------------------------------------------------------------------------
# delta quant modes: "int8" (block-quantized values + fp32 block scales,
# the comm/quantized wire form) or "off" (changed leaves at full fp32 —
# reconstruction is bitwise-exact)
DELTA_QUANT_MODES = ("int8", "off")
DEFAULT_DELTA_BLOCK = 2048


def _delta_keys(seq: int) -> Tuple[str, str]:
    """The two kv entries of one int8 delta chunk: concatenated
    quantized values and concatenated fp32 block scales. Seq-suffixed
    so a stager's flat leaf map never collides across chunks."""
    return f"__dq{seq}__", f"__ds{seq}__"


def _dequant_leaf(q_flat: np.ndarray, s_flat: np.ndarray,
                  numel: int) -> np.ndarray:
    """(int8 [nb*block], f32 [nb]) -> flat f32 [numel]. Plain numpy so
    the publisher's error-feedback reference and every ingesting
    replica reconstruct BIT-IDENTICAL values."""
    nb = int(s_flat.shape[0])
    d = q_flat.reshape(nb, -1).astype(np.float32) * \
        s_flat.reshape(nb, 1).astype(np.float32)
    return d.reshape(-1)[:numel]


def chunk_weight_deltas(flat: Dict[str, np.ndarray],
                        base: Dict[str, np.ndarray], version: int,
                        base_version: int, quant: str = "int8",
                        block: int = DEFAULT_DELTA_BLOCK,
                        bucket_bytes: int = DEFAULT_BUCKET_BYTES
                        ) -> Tuple[List[bytes], Dict[str, np.ndarray]]:
    """Serialize ``flat - base`` into a DELTA payload
    ``[header, chunk...]``.

    ``base`` is the receivers' reconstruction of ``base_version`` (the
    publisher's error-feedback reference — it tracks the fleet exactly,
    so the residual the quantizer introduced at version k is folded
    into the k+1 delta automatically). Returns ``(payloads, recon)``
    where ``recon`` is the bit-exact fleet state after this payload is
    applied — the caller's next error-feedback reference."""
    if quant not in DELTA_QUANT_MODES:
        raise ValueError(
            f"delta quant mode must be one of {DELTA_QUANT_MODES} "
            f"(got {quant!r})")
    if set(flat) != set(base):
        raise ValueError(
            "delta publication leaf set changed vs the base version; "
            "publisher and base must share one model structure")
    import jax.numpy as jnp

    from ....comm.quantized import _quantize_wire
    chunks: List[bytes] = []
    crcs: List[int] = []
    chunk_leaves: List[List[str]] = []
    delta_manifest: List[list] = []
    leaf_meta: Dict[str, dict] = {}
    recon: Dict[str, np.ndarray] = {}
    param_count = 0
    items = list(flat.items())
    for seq, names in enumerate(plan_buckets(items, bucket_bytes)):
        manifest: list = []
        if quant == "off":
            kv: Dict[str, np.ndarray] = {}
            for n in names:
                cur = np.ascontiguousarray(np.asarray(flat[n],
                                                      np.float32))
                leaf_meta[n] = {"shape": list(cur.shape)}
                param_count += int(cur.size)
                ref = np.asarray(base[n], np.float32)
                if cur.shape != ref.shape:
                    raise ValueError(
                        f"delta leaf {n!r} shape {cur.shape} != base "
                        f"shape {ref.shape}")
                if np.array_equal(cur, ref):
                    recon[n] = ref     # unchanged: receiver keeps base
                else:
                    kv[n] = cur
                    # recon must not alias the caller's live array (it
                    # becomes the next error-feedback base)
                    recon[n] = np.array(cur, np.float32)
                    manifest.append(n)
        else:
            qk, sk = _delta_keys(seq)
            qs: List[np.ndarray] = []
            ss: List[np.ndarray] = []
            for n in names:
                cur = np.asarray(flat[n], np.float32)
                ref = np.asarray(base[n], np.float32)
                if cur.shape != ref.shape:
                    raise ValueError(
                        f"delta leaf {n!r} shape {cur.shape} != base "
                        f"shape {ref.shape}")
                leaf_meta[n] = {"shape": list(cur.shape)}
                numel = int(cur.size)
                param_count += numel
                d = np.ascontiguousarray(cur - ref).reshape(-1)
                q, s = _quantize_wire(jnp.asarray(d),
                                      max(1, min(int(block),
                                                 max(numel, 1))),
                                      "int8")
                q = np.asarray(q, np.int8)
                s = np.asarray(s, np.float32)
                manifest.append({"name": n, "numel": numel,
                                 "nb": int(q.shape[0]),
                                 "block": int(q.shape[1])})
                recon[n] = (ref.reshape(-1)
                            + _dequant_leaf(q.reshape(-1),
                                            s.reshape(-1), numel)
                            ).astype(np.float32).reshape(cur.shape)
                qs.append(q.reshape(-1))
                ss.append(s.reshape(-1))
            kv = {qk: (np.concatenate(qs) if qs
                       else np.zeros(0, np.int8)),
                  sk: (np.concatenate(ss) if ss
                       else np.zeros(0, np.float32))}
        crc = _chunk_crc(kv)
        crcs.append(crc)
        chunk_leaves.append(sorted(kv))
        delta_manifest.append(manifest)
        chunks.append(_npz_chunk(
            {"kind": _CHUNK_KIND, "seq": seq, "crc32": crc,
             "version": int(version)}, kv))
    header = _npz_chunk(
        {"kind": _HEADER_KIND, "version": int(version),
         "payload_kind": "delta", "base_version": int(base_version),
         "quant": quant, "n_chunks": len(chunks), "chunk_crcs": crcs,
         "chunk_leaves": chunk_leaves,
         "delta_manifest": delta_manifest, "leaf_meta": leaf_meta,
         "param_count": param_count}, {})
    return [header] + chunks, recon


def is_delta_header(header: Dict) -> bool:
    return header.get("payload_kind") == "delta"


def is_delta_payload(payloads: Sequence[bytes]) -> bool:
    return is_delta_header(parse_weights_header(payloads[0]))


def delta_base_version(payloads: Sequence[bytes]) -> int:
    header = parse_weights_header(payloads[0])
    if not is_delta_header(header):
        raise ValueError("not a delta payload (no base_version)")
    return int(header["base_version"])


def reconstruct_delta(header: Dict, staged: Dict[str, np.ndarray],
                      base: Dict[str, np.ndarray]
                      ) -> Dict[str, np.ndarray]:
    """Rebuild the full ``{name: fp32 ndarray}`` map from a staged
    delta payload and the receiver's retained base (``base_version``'s
    fp32 leaves). Pure host math, deterministic — every replica
    applying this payload over the same base holds identical bits."""
    quant = header.get("quant", "int8")
    out = dict(base)
    missing = [n for n in header["leaf_meta"] if n not in base]
    if missing:
        raise ValueError(
            f"delta payload names {len(missing)} leaves absent from "
            f"the retained base (first: {missing[:3]})")
    for seq, manifest in enumerate(header["delta_manifest"]):
        if quant == "off":
            for n in manifest:
                out[n] = np.asarray(staged[n], np.float32)
            continue
        qk, sk = _delta_keys(seq)
        q_flat = np.asarray(staged[qk])
        s_flat = np.asarray(staged[sk], np.float32)
        q_off = s_off = 0
        for ent in manifest:
            n, numel = ent["name"], int(ent["numel"])
            nb, blk = int(ent["nb"]), int(ent["block"])
            ref = np.asarray(base[n], np.float32)
            if int(ref.size) != numel:
                raise ValueError(
                    f"delta leaf {n!r} numel {numel} != base "
                    f"{int(ref.size)}")
            q_seg = q_flat[q_off:q_off + nb * blk]
            s_seg = s_flat[s_off:s_off + nb]
            if q_seg.size != nb * blk or s_seg.size != nb:
                raise ValueError(
                    f"delta chunk {seq} truncated at leaf {n!r}")
            out[n] = (ref.reshape(-1)
                      + _dequant_leaf(q_seg, s_seg, numel)
                      ).astype(np.float32).reshape(ref.shape)
            q_off += nb * blk
            s_off += nb
    return out


# ---------------------------------------------------------------------------
# Adapter payloads (multi-tenant LoRA hot-deploy)
# ---------------------------------------------------------------------------
# A freshly trained LoRA adapter rides the SAME publish path as full /
# delta weight payloads (router.push_weights -> POST /weights ->
# begin_weight_update), so it inherits the chunk CRCs, the retransmit
# idempotence, the fleet blue/green drain and the fault plane for free.
# The header carries ``payload_kind="adapter"`` + the adapter NAME (the
# cross-replica identity the router and prefix cache key on) and the
# scale; each low-rank pair travels as two leaves keyed ``path + "::a"``
# / ``path + "::b"``. Ingest routes to ``engine.load_adapter`` — a
# same-shape bank slot write, no param swap, no recompile — instead of
# ``swap_engine_params``; ``weight_version`` and the retained delta
# base are untouched (the base model did not change).

_ADAPTER_A = "::a"
_ADAPTER_B = "::b"


def chunk_adapter_payload(name: str, adapters: Dict[str, tuple],
                          version: int,
                          scale: float = 1.0) -> List[bytes]:
    """Serialize one LoRA adapter (``{"layers/wq": (a, b), ...}`` —
    the hybrid-engine external-adapter convention) into the weights
    wire ``[header, chunk]``. Adapters are tiny relative to the model,
    so one chunk always suffices."""
    if not str(name):
        raise ValueError("adapter payload requires a non-empty name")
    flat: Dict[str, np.ndarray] = {}
    for path in sorted(adapters):
        a, b = adapters[path]
        flat[path + _ADAPTER_A] = np.ascontiguousarray(
            np.asarray(a, np.float32))
        flat[path + _ADAPTER_B] = np.ascontiguousarray(
            np.asarray(b, np.float32))
    crc = _chunk_crc(flat)
    leaf_meta = {n: {"shape": list(v.shape)} for n, v in flat.items()}
    chunk = _npz_chunk(
        {"kind": _CHUNK_KIND, "seq": 0, "crc32": crc,
         "version": int(version)}, flat)
    header = _npz_chunk(
        {"kind": _HEADER_KIND, "version": int(version),
         "payload_kind": "adapter", "adapter_name": str(name),
         "adapter_scale": float(scale), "n_chunks": 1,
         "chunk_crcs": [crc], "chunk_leaves": [sorted(flat)],
         "leaf_meta": leaf_meta,
         "param_count": sum(int(v.size) for v in flat.values())}, {})
    return [header, chunk]


def is_adapter_header(header: Dict) -> bool:
    return header.get("payload_kind") == "adapter"


def is_adapter_payload(payloads: Sequence[bytes]) -> bool:
    return is_adapter_header(parse_weights_header(payloads[0]))


def adapters_from_flat(flat: Dict[str, np.ndarray]
                       ) -> Dict[str, tuple]:
    """Regroup staged ``path::a`` / ``path::b`` leaves into the
    ``{path: (a, b)}`` map ``engine.load_adapter`` takes. Typed failure
    on an unpaired or unrecognized leaf."""
    adapters: Dict[str, tuple] = {}
    for n in sorted(flat):
        if n.endswith(_ADAPTER_A):
            path = n[:-len(_ADAPTER_A)]
            bk = path + _ADAPTER_B
            if bk not in flat:
                raise ValueError(
                    f"adapter payload leaf {n!r} has no matching "
                    f"{bk!r} (a/b pairs must travel together)")
            adapters[path] = (flat[n], flat[bk])
        elif not n.endswith(_ADAPTER_B):
            raise ValueError(
                f"adapter payload leaf {n!r} is neither "
                f"'{_ADAPTER_A}' nor '{_ADAPTER_B}' suffixed")
    for n in flat:
        if n.endswith(_ADAPTER_B) \
                and n[:-len(_ADAPTER_B)] not in adapters:
            raise ValueError(
                f"adapter payload leaf {n!r} has no matching "
                f"'{_ADAPTER_A}' half")
    return adapters


def parse_weights_header(buf: bytes) -> Dict:
    d = parse_chunk(buf)["descriptor"]
    if d.get("kind") != _HEADER_KIND:
        raise ValueError(
            f"weight payload must start with the header chunk "
            f"(got kind={d.get('kind')!r})")
    return d


def payload_version(payloads: Sequence[bytes]) -> int:
    return int(parse_weights_header(payloads[0])["version"])


def payload_bytes(payloads: Sequence[bytes]) -> int:
    return sum(len(p) for p in payloads)


class WeightStager:
    """Host-side state machine for one incoming weight payload: feed
    each chunk (CRC-checked, idempotent on retransmit), then
    ``commit_check`` + ``flat()`` hand the complete ``{name: ndarray}``
    map to the swap. Staging never touches the engine — the atomic
    swap is the only loop-thread moment."""

    def __init__(self, header: Dict):
        self.header = header
        self.version = int(header["version"])
        self.leaves: Dict[str, np.ndarray] = {}
        self.received: set = set()
        self._m_chunks, self._m_bytes = _metrics()

    def feed(self, chunk_buf: bytes) -> None:
        try:
            chunk = parse_chunk(chunk_buf)
        except Exception as e:
            # a corrupt buffer can die inside np.load (BadZipFile &c.)
            # before the CRC ever runs — surface it as the same typed
            # integrity failure so ingest verdicts stay uniform
            raise ValueError(
                f"weights chunk failed to parse (corrupted in "
                f"transfer): {type(e).__name__}: {e}") from e
        d = chunk["descriptor"]
        if d.get("kind") != _CHUNK_KIND:
            raise ValueError(
                f"expected a weights chunk, got {d.get('kind')!r}")
        seq = int(d["seq"])
        if not 0 <= seq < int(self.header["n_chunks"]):
            raise ValueError(
                f"weights chunk seq {seq} outside the header's "
                f"{self.header['n_chunks']} chunks")
        crc = _chunk_crc(chunk["kv"])
        if crc != int(d["crc32"]) \
                or crc != int(self.header["chunk_crcs"][seq]):
            raise ValueError(
                f"weights chunk {seq} failed its crc32 integrity check "
                f"(corrupted in transfer)")
        if sorted(chunk["kv"]) != list(self.header["chunk_leaves"][seq]):
            raise ValueError(
                f"weights chunk {seq} leaf set disagrees with the "
                f"header manifest")
        self.leaves.update(chunk["kv"])
        self.received.add(seq)
        self._m_chunks.inc()
        self._m_bytes.inc(len(chunk_buf))

    def missing(self) -> List[int]:
        return [s for s in range(int(self.header["n_chunks"]))
                if s not in self.received]

    def commit_check(self) -> None:
        gaps = self.missing()
        if gaps:
            raise ValueError(
                f"weight payload incomplete: missing chunks {gaps} of "
                f"{self.header['n_chunks']}")


def stage_payload(payloads: Sequence[bytes]) -> WeightStager:
    """Parse + CRC-check a complete payload into a ready stager."""
    stager = WeightStager(parse_weights_header(payloads[0]))
    for chunk in payloads[1:]:
        stager.feed(chunk)
    stager.commit_check()
    return stager


def flat_to_tree(template_tree, flat: Dict[str, np.ndarray]):
    """Rebuild a host params pytree shaped like ``template_tree`` from a
    flat ``{path: ndarray}`` map (fresh-engine construction from a
    published payload — the hot-swap parity reference)."""
    import jax
    items, treedef = flatten_params(template_tree)
    leaves = []
    for name, leaf in items:
        if name not in flat:
            raise ValueError(f"weight payload missing leaf {name!r}")
        leaves.append(np.asarray(flat[name], np.float32))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def swap_engine_params(engine, flat: Dict[str, np.ndarray],
                       version: int) -> None:
    """Replace ``engine.params`` (an :class:`InferenceEngineV2`) with
    the published leaves by donated buffer replacement: each new leaf is
    cast to the OLD leaf's dtype and ``device_put`` onto the OLD leaf's
    sharding, so every compiled program's executable signature is
    unchanged — no retrace, no respecialization. Validation happens
    BEFORE any leaf is replaced: a bad payload leaves the engine
    serving its current version."""
    import jax
    import jax.numpy as jnp

    if getattr(engine, "_qmeta", None) is not None:
        raise NotImplementedError(
            "weight hot-swap over quant_bits (WOQ) params is not "
            "supported: the quantized leaf layout does not match the "
            "published dense tree")
    items, treedef = flatten_params(engine.params)
    names = [name for name, _ in items]
    missing = [n for n in names if n not in flat]
    if missing:
        raise ValueError(
            f"weight payload missing {len(missing)} leaves "
            f"(first: {missing[:3]}); publisher and serving engine "
            f"must share one model structure")
    extra = sorted(set(flat) - set(names))
    if extra:
        raise ValueError(
            f"weight payload has {len(extra)} unknown leaves "
            f"(first: {extra[:3]})")
    for name, old in items:
        if tuple(np.shape(flat[name])) != tuple(old.shape):
            raise ValueError(
                f"weight leaf {name!r} shape "
                f"{tuple(np.shape(flat[name]))} != engine shape "
                f"{tuple(old.shape)}")
    t0 = time.perf_counter()
    new_leaves = []
    for name, old in items:
        arr = jnp.asarray(np.asarray(flat[name]), old.dtype)
        # replicate the OLD leaf's placement exactly: the pjit
        # executable cache keys on committed-ness as well as sharding —
        # committing a leaf the engine held uncommitted (a plain jit
        # output on one device) would silently respecialize every
        # program on its next call
        if getattr(old, "committed", True):
            arr = jax.device_put(arr, old.sharding)
        new_leaves.append(arr)
    engine.params = jax.tree_util.tree_unflatten(treedef, new_leaves)
    engine.weight_version = int(version)
    # retain the fp32 flat leaves as the DELTA BASE for the next push:
    # a delta payload reconstructs against exactly these bits (the
    # receiver-side half of the publisher's error-feedback reference).
    # Host cost: one fp32 copy of the model per serving engine.
    set_delta_base(engine, flat)
    engine.note_weight_swap(time.perf_counter() - t0)


def set_delta_base(engine, flat: Dict[str, np.ndarray]) -> None:
    """Record ``flat`` (fp32 host leaves) as the engine's delta base —
    what ``commit_stager`` reconstructs the next delta payload
    against. Called by every ingest path (swap + fresh build)."""
    engine._weight_flat_base = {
        n: np.asarray(a, np.float32) for n, a in flat.items()}


def delta_base_of(engine):
    """The engine's retained ``{name: fp32 ndarray}`` delta base, or
    None when it never ingested a payload (boot-checkpoint engines
    cannot take deltas — the router falls back to a full push)."""
    return getattr(engine, "_weight_flat_base", None)


def prepare_stager(engine, stager: WeightStager
                   ) -> Dict[str, np.ndarray]:
    """The host-side half of ingest: validate + (for deltas)
    reconstruct the full flat leaf map, touching nothing live. Delta
    payloads validate base version + retained base BEFORE any
    reconstruction — a stale base fails typed with the live params
    untouched. Runs off the serving loop thread (heavy host math);
    the returned map goes to ``swap_engine_params`` between scheduler
    steps."""
    header = stager.header
    if is_adapter_header(header):
        # validate pairing off-loop so a malformed payload fails typed
        # BEFORE the loop-thread install; the regrouped map is rebuilt
        # (cheap — adapters are tiny) by install_stager
        adapters_from_flat(stager.leaves)
        return stager.leaves
    if not is_delta_header(header):
        return stager.leaves
    base_version = int(header["base_version"])
    live = int(getattr(engine, "weight_version", 0) or 0)
    base = delta_base_of(engine)
    if live != base_version:
        raise ValueError(
            f"delta payload base_version={base_version} does not "
            f"match the live weight_version={live}; a full push is "
            f"required")
    if base is None:
        raise ValueError(
            "delta payload cannot apply: this engine retains no delta "
            "base (it never ingested a weight payload); a full push "
            "is required")
    return reconstruct_delta(header, stager.leaves, base)


def install_stager(engine, stager: WeightStager,
                   flat: Dict[str, np.ndarray]) -> int:
    """The loop-thread half of ingest: install the prepared leaves into
    the engine. Full/delta payloads run the donated-buffer param swap;
    ADAPTER payloads route to ``engine.load_adapter`` (a bank-slot
    write — ``weight_version`` and the retained delta base stay put,
    the base model did not change). Both the colocated
    ``commit_stager`` and the serving loop's ``WeightUpdate.commit``
    land here, so every payload kind behaves identically on every
    ingest path."""
    if is_adapter_header(stager.header):
        header = stager.header
        engine.load_adapter(
            str(header["adapter_name"]), adapters_from_flat(flat),
            scale=float(header.get("adapter_scale", 1.0)))
        return int(stager.version)
    swap_engine_params(engine, flat, stager.version)
    return int(stager.version)


def commit_stager(engine, stager: WeightStager) -> int:
    """THE ingest choke point: every path that turns a complete stager
    into live params (colocated ``apply_payload``, the serving loop's
    ``WeightUpdate.commit``, the worker ``/weights`` handler above it)
    lands here, so full, delta and adapter payloads behave identically
    everywhere."""
    flat = prepare_stager(engine, stager)
    return install_stager(engine, stager, flat)


def apply_payload(engine, payloads: Sequence[bytes]) -> int:
    """Stage + swap a complete payload (full or delta) into ``engine``
    synchronously (the colocated hybrid path; serving runtimes go
    through :meth:`~.frontend.ServingEngine.begin_weight_update` so the
    swap lands between scheduler steps). Returns the installed
    version."""
    return commit_stager(engine, stage_payload(payloads))
