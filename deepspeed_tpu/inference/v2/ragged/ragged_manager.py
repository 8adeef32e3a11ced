"""Sequence state manager for the ragged engine.

Reference: inference/v2/ragged/ragged_manager.py:19 (DSStateManager): owns
the block allocator and the per-sequence descriptors, answers schedulability
questions, and materializes the per-step block tables the device program
consumes.

Prefix caching (``enable_prefix_caching``, beyond the reference): KV
depends only on the causal token prefix, so FULL blocks whose token
content matches a previously-served prefix are shared instead of
recomputed. Blocks are registered into a chain-hash index at flush time
(holding their own reference so they survive the sequence), matched on
the next arrival, and evicted LRU when the pool needs space. Only
block-aligned prefixes share, so shared blocks are never written again —
no copy-on-write is ever needed.
"""

import hashlib
from collections import OrderedDict
from typing import Dict, List, Optional, Tuple

import numpy as np

from ....telemetry import recorder as flight
from ..config_v2 import DSStateManagerConfig
from .blocked_allocator import NULL_BLOCK, BlockedAllocator
from .sequence_descriptor import DSSequenceDescriptor

# seed of the chain-hash: every digest chain starts here, so digests are
# a pure function of (token content, block size) — stable across
# processes, engines and replicas
_DIGEST_SEED = b"prefix"


def _chain(digest: bytes, tokens) -> bytes:
    return hashlib.sha1(
        digest + np.asarray(tokens, np.int32).tobytes()).digest()


def _digest_seed(adapter: Optional[str]) -> bytes:
    """Chain seed for a (possibly adapter-scoped) digest walk. A LoRA
    adapter changes the KV a prefix produces (q/v projections differ),
    so the same token prefix under different adapters must NEVER share
    blocks — the adapter NAME is folded into the seed, which scopes the
    whole chain without touching per-block hashing. Base-model chains
    (adapter None/"") keep the bare seed, byte-identical to the
    pre-adapter digests (router affinity keys stay stable)."""
    if not adapter:
        return _DIGEST_SEED
    return hashlib.sha1(
        _DIGEST_SEED + adapter.encode("utf-8")).digest()


def prefix_digest(tokens, block_size: int,
                  adapter: Optional[str] = None) -> List[bytes]:
    """Chain-hash digests of the FULL block-aligned prefixes of
    ``tokens``: digest ``i`` covers ``tokens[:(i + 1) * block_size]``.

    This is the exact chain the prefix-cache index keys on (register at
    flush, match at arrival), exported as the STABLE affinity API for
    the serving router (serve/router.py): the router hashes an incoming
    prompt with the replica's block size and routes to the replica that
    last served the longest matching digest — without ever reaching
    into manager state. Digests depend only on token content, block
    size and the adapter scope (sha1 over int32 bytes), so two
    processes with the same config compute identical lists."""
    toks = np.asarray(tokens, np.int64)
    digest = _digest_seed(adapter)
    out: List[bytes] = []
    for n in range(0, (len(toks) // block_size) * block_size, block_size):
        digest = _chain(digest, toks[n:n + block_size])
        out.append(digest)
    return out


class DSStateManager:
    def __init__(self, config: DSStateManagerConfig, state_slots: int = 0,
                 window_ring: int = 0, paged: bool = True):
        """``paged`` False: the model caches no position at all (every
        layer keeps a state a sequence, ``state_slots`` of them). A
        sequence then owns its slot and NO block: the allocator holds
        the null block and one it never hands out, a block table is one
        null entry wide, ``num_blocks`` and ``block_size`` size nothing,
        and what bounds the batch is ``max_tracked_sequences``, the
        slots; ``max_seq_len`` still bounds a sequence's length (its
        positions feed the rotation).

        ``window_ring`` > 0: the model has window-attention layers,
        whose keys and values live in a pool of their own in which a
        sequence owns a RING of that many positions (whole blocks;
        position p at place ``p % window_ring``) and not its whole
        context: a second geometry a sequence, with its own allocator
        (``max_tracked_sequences`` rings and the null block), its own
        table (``window_table_for``), handed out with the blocks of the
        first and taken back with them, and counted by ``can_schedule``.
        The engine sizes it (``InferenceEngineV2.max_row_chunk``, the
        most tokens a sequence feeds in ONE step): the window, that many
        and one block, so that a step's writes never land on a position
        its earliest token still sees.

        ``state_slots`` > 0: the model keeps recurrent state a
        sequence beside its blocks (linear-attention layers), in that
        many slots of the cache's state leaves, numbered from 1 (slot 0
        is the null slot). A tracked sequence owns one from its creation
        to its flush; a slot is not cleared when it changes hands: a
        sequence's first token starts from zeros in the program
        (``paged_model._linear_attention_sublayer``)."""
        self.config = config
        self.state_slots = int(state_slots)
        if self.state_slots and \
                self.state_slots < config.max_tracked_sequences:
            raise ValueError(
                f"{state_slots} state slots are fewer than the "
                f"{config.max_tracked_sequences} sequences tracked")
        self._free_slots = list(range(self.state_slots, 0, -1))
        self.block_size = config.block_size
        self.paged = bool(paged)
        if not self.paged and not self.state_slots:
            raise ValueError("a model that caches no position keeps its "
                             "sequences in state slots: state_slots > 0")
        self.allocator = BlockedAllocator(
            config.num_blocks if self.paged else 2)
        if window_ring % self.block_size:
            raise ValueError(f"a ring of {window_ring} positions is not "
                             f"whole blocks of {self.block_size}")
        self.ring_blocks = window_ring // self.block_size
        self.window_allocator = BlockedAllocator(
            config.max_tracked_sequences * self.ring_blocks + 1) \
            if self.ring_blocks else None
        self.seqs: Dict[int, DSSequenceDescriptor] = {}
        self.max_blocks_per_seq = -(-config.max_seq_len // self.block_size) \
            if self.paged else 1
        # cold-block spill tier (spill.py KVSpillTier, installed by the
        # engine when enable_kv_spill is on): eviction demotes a retained
        # block's CONTENT to host RAM/disk instead of discarding it, and
        # match_prefix re-materializes spilled digests on the next
        # arrival — a spilled prefix is a HIT, not a miss
        self.spill = None
        # chain-hash digest -> retained block id (insertion-ordered: LRU
        # eviction pops from the front)
        self._prefix: "OrderedDict[bytes, int]" = OrderedDict()
        from ....telemetry import get_registry
        reg = get_registry()
        self._m_lookups = reg.counter(
            "inference_prefix_lookups_total",
            "prefix-cache matches attempted for new sequences")
        self._m_hits = reg.counter(
            "inference_prefix_hits_total",
            "prefix-cache lookups that reused at least one block")
        self._m_reused_tokens = reg.counter(
            "inference_prefix_reused_tokens_total",
            "prompt tokens served from shared KV blocks")
        self._m_evicted = reg.counter(
            "inference_prefix_evicted_blocks_total",
            "retained prefix blocks LRU-evicted under pool pressure")
        # KV-pool flow accounting (the leak detector's reconciliation
        # inputs, and the flight recorder's kv_alloc/kv_free events):
        # allocated counts fresh blocks handed to sequences; freed counts
        # block REFERENCES returned (a prefix-shared block freed by one
        # owner still lives until its last reference drops)
        self._m_alloc = reg.counter(
            "inference_kv_blocks_allocated_total",
            "KV blocks allocated to sequences")
        self._m_freed = reg.counter(
            "inference_kv_blocks_freed_total",
            "KV block references released (sequence flush + prefix "
            "eviction)")
        self._m_ring_reused = reg.counter(
            "inference_window_blocks_reused_total",
            "blocks of a sequence's ring (window-attention layers) "
            "written over by positions a whole ring later: what a pool "
            "that held every position would have had to hand out")

    # -- prefix caching -----------------------------------------------------
    _chain = staticmethod(_chain)

    def match_prefix(self, uid: int, tokens: np.ndarray,
                     adapter: Optional[str] = None
                     ) -> Tuple[List[int], int]:
        """Longest retained block-aligned prefix of ``tokens`` (capped one
        token short so the model still produces last-token logits),
        scoped to ``adapter`` — an adapter-scoped chain can only hit
        blocks registered under the SAME adapter name (base-model
        lookups only hit base blocks). Registers ``uid`` with the
        shared blocks; returns (blocks, n_reused_tokens) — (…, 0) when
        nothing matches."""
        if not self.config.enable_prefix_caching or uid in self.seqs:
            return [], 0
        self._m_lookups.inc()
        bs = self.block_size
        usable = ((len(tokens) - 1) // bs) * bs
        blocks: List[int] = []
        digest = _digest_seed(adapter)
        n = 0
        # incremental chain (same rule as prefix_digest, which callers
        # use for the full list): the lookup stops hashing at the first
        # missing digest — a cold long prompt costs one sha1, not one
        # per block
        while n + bs <= usable:
            digest = _chain(digest, tokens[n:n + bs])
            blk = self._prefix.get(digest)
            if blk is None and self.spill is not None \
                    and self.spill.has(digest):
                # the digest's KV was demoted under pool pressure —
                # re-materialize it between scheduler steps (we are on
                # the serving-loop thread, between program launches,
                # riding the same donated-pool scatter a chunked
                # handoff ingest uses). Blocks matched EARLIER in this
                # walk are not share()d until the walk completes, so
                # they still look evictable — protect them, or the
                # restore's own eviction could free-and-reuse a block
                # already in this chain
                blk = self._restore_spilled(digest, protect=blocks)
            if blk is None:
                break
            blocks.append(blk)
            self._prefix.move_to_end(digest)   # LRU touch
            self.allocator.touch(blk)
            n += bs
        if not n:
            return [], 0
        seq = self.get_or_create_sequence(uid)
        for b in blocks:
            self.allocator.share(b)
        seq.blocks = list(blocks)
        seq.seen_tokens = n
        seq.token_log = list(map(int, tokens[:n]))
        seq.adapter = adapter or None
        self._m_hits.inc()
        self._m_reused_tokens.inc(n)
        return blocks, n

    def _register_prefix(self, seq: DSSequenceDescriptor) -> None:
        """Index the sequence's full blocks at flush so the NEXT arrival
        with the same prefix reuses them (the index holds its own block
        references — retained blocks survive the flush). Registration
        uses the sequence's adapter scope, so adapter-served blocks are
        only ever matched by same-adapter arrivals."""
        bs = self.block_size
        full = min(len(seq.token_log) // bs, len(seq.blocks))
        digests = prefix_digest(seq.token_log[:full * bs], bs,
                                adapter=getattr(seq, "adapter", None))
        for i, digest in enumerate(digests):
            if digest not in self._prefix:
                self._prefix[digest] = int(seq.blocks[i])
                self.allocator.share(seq.blocks[i])

    def _restore_spilled(self, digest: bytes,
                         protect=()) -> Optional[int]:
        """Allocate a fresh block and scatter the spilled digest's
        content into it; the restored block re-enters the hot index
        holding the index's own reference, exactly like a retained
        block. ``protect`` lists block ids the in-progress match walk
        already collected (still refcount-1 until the walk share()s
        them) that eviction must not touch. Returns None when the pool
        cannot yield a block or the entry fails its integrity check
        (the caller then treats the digest as a plain miss)."""
        if self.allocator.free_blocks < 1:
            self._evict_retained(1, protect=protect)
            if self.allocator.free_blocks < 1:
                return None
        blk = int(self.allocator.allocate(1)[0])
        if not self.spill.restore_block(digest, blk):
            self.allocator.free([blk])
            return None
        self._prefix[digest] = blk
        self._m_alloc.inc()
        return blk

    def _evictable(self) -> int:
        """Retained blocks held ONLY by the index (reclaimable now).
        Memoized against the allocator's version stamp: decode steps that
        allocate nothing reuse the cached count (the scan is O(index))."""
        ver = self.allocator.version
        if getattr(self, "_evictable_ver", None) != ver:
            self._evictable_val = sum(
                1 for b in self._prefix.values()
                if self.allocator.refcount(b) == 1)
            self._evictable_ver = ver
        return self._evictable_val

    def _evict_retained(self, need: int, protect=()) -> None:
        """Free LRU index entries whose blocks the index alone holds
        until ``need`` blocks are free. Entries shared with live
        sequences are skipped — popping them reclaims nothing and only
        churns hot prefixes out of the cache. ``protect`` blocks
        (an in-progress match walk's collected chain) are skipped too."""
        protected = set(map(int, protect))
        while self.allocator.free_blocks < need:
            victim = next((d for d, b in self._prefix.items()
                           if self.allocator.refcount(b) == 1
                           and int(b) not in protected), None)
            if victim is None:
                return
            blk = self._prefix.pop(victim)
            if self.spill is not None:
                # demote the content to the cold tier BEFORE the free:
                # the next arrival with this prefix restores instead of
                # recomputing (spill.py)
                self.spill.spill_block(victim, blk)
            self.allocator.free([blk])
            self._m_evicted.inc()
            self._m_freed.inc()

    def reclaimable_blocks(self) -> int:
        """Free blocks plus what eviction could free right now — the
        number schedulability checks should compare against."""
        return self.allocator.free_blocks + self._evictable()

    # -- queries (reference DSStateManager.query / engine can_schedule) ----
    def known_seq(self, uid: int) -> bool:
        return uid in self.seqs

    def get_or_create_sequence(self, uid: int) -> DSSequenceDescriptor:
        if uid not in self.seqs:
            if len(self.seqs) >= self.config.max_tracked_sequences:
                raise RuntimeError(
                    f"tracked-sequence limit "
                    f"{self.config.max_tracked_sequences} reached")
            self.seqs[uid] = DSSequenceDescriptor(
                uid=uid, state_slot=self._free_slots.pop()
                if self.state_slots else 0, paged=self.paged)
        return self.seqs[uid]

    def descriptor(self, uid: int) -> DSSequenceDescriptor:
        """The tracked sequence ``uid``, or what it would start as (for
        a question about a sequence not yet created)."""
        return self.seqs.get(uid) or DSSequenceDescriptor(
            uid=uid, paged=self.paged)

    def state_slots_in_use(self) -> int:
        return self.state_slots - len(self._free_slots)

    def can_schedule(self, uid: int, new_tokens: int) -> bool:
        seq = self.descriptor(uid)
        if seq.seen_tokens + new_tokens > self.config.max_seq_len:
            return False
        if uid not in self.seqs and \
                len(self.seqs) >= self.config.max_tracked_sequences:
            return False
        if self.ring_blocks and seq.window_blocks_needed(
                new_tokens, self.block_size, self.ring_blocks) \
                > self.window_allocator.free_blocks:
            return False
        return seq.blocks_needed(new_tokens, self.block_size) \
            <= self.allocator.free_blocks + self._evictable()

    def window_blocks_in_use(self) -> int:
        """Ring blocks owned by tracked sequences (0 without a ring)."""
        return sum(len(s.window_blocks) for s in self.seqs.values())

    # -- allocation ---------------------------------------------------------
    def ensure_blocks(self, uid: int, new_tokens: int) -> DSSequenceDescriptor:
        seq = self.get_or_create_sequence(uid)
        if self.ring_blocks:
            bs, total = self.block_size, seq.seen_tokens + new_tokens
            ring = seq.window_blocks_needed(new_tokens, bs,
                                            self.ring_blocks)
            if ring:
                seq.window_blocks.extend(
                    int(b) for b in self.window_allocator.allocate(ring))
            # pages past the ring's size land on blocks the row holds
            self._m_ring_reused.inc(
                max(0, -(-total // bs) - self.ring_blocks)
                - max(0, -(-seq.seen_tokens // bs) - self.ring_blocks))
        need = seq.blocks_needed(new_tokens, self.block_size)
        if need:
            if need > self.allocator.free_blocks:
                self._evict_retained(need)
            seq.blocks.extend(int(b) for b in self.allocator.allocate(need))
            self._m_alloc.inc(need)
            flight.record("kv_alloc", uid=int(uid), blocks=int(need),
                          free=self.allocator.free_blocks)
        return seq

    def adopt_sequence(self, uid: int, n_blocks: int, seen_tokens: int,
                       token_log) -> DSSequenceDescriptor:
        """Install a sequence restored from a KV handoff
        (serve/handoff.py): allocate ``n_blocks`` fresh blocks (evicting
        retained prefix blocks under pressure, like ensure_blocks) and
        create the descriptor in exactly the state the decode paths and
        flush-time bookkeeping expect — cache-resident token count plus
        the fed-token log the prefix index registers at flush. The
        caller scatters the handed-off KV content into the returned
        descriptor's blocks."""
        if self.state_slots:
            raise NotImplementedError(
                "a sequence with recurrent state cannot be adopted: a "
                "handoff carries KV blocks and no state slot")
        if self.ring_blocks:
            raise NotImplementedError(
                "a sequence of a model with window-attention layers "
                "cannot be adopted: a handoff carries the blocks of one "
                "geometry and no ring")
        if uid in self.seqs:
            raise ValueError(
                f"cannot adopt uid {uid}: sequence already tracked")
        if seen_tokens > n_blocks * self.block_size:
            raise ValueError(
                f"handoff descriptor inconsistent: {seen_tokens} seen "
                f"tokens do not fit {n_blocks} blocks of "
                f"{self.block_size}")
        if n_blocks > self.allocator.free_blocks:
            self._evict_retained(n_blocks)
        # allocate BEFORE creating the descriptor: an exhausted pool
        # must not leave a blockless tracked sequence behind
        blocks = [int(b) for b in self.allocator.allocate(n_blocks)]
        try:
            seq = self.get_or_create_sequence(uid)
        except Exception:
            self.allocator.free(blocks)
            raise
        seq.blocks = blocks
        seq.seen_tokens = int(seen_tokens)
        if self.config.enable_prefix_caching:
            seq.token_log = list(map(int, token_log))
        self._m_alloc.inc(n_blocks)
        flight.record("kv_alloc", uid=int(uid), blocks=int(n_blocks),
                      free=self.allocator.free_blocks)
        return seq

    def flush_sequence(self, uid: int) -> None:
        """Reference flush: return the sequence's blocks to the pool
        (prefix caching first indexes the full blocks for reuse)."""
        seq = self.seqs.pop(uid, None)
        if seq is not None:
            if seq.state_slot:
                self._free_slots.append(seq.state_slot)
            if self.config.enable_prefix_caching:
                self._register_prefix(seq)
            if seq.window_blocks:
                self.window_allocator.free(seq.window_blocks)
            self.allocator.free(seq.blocks)
            if seq.blocks:
                self._m_freed.inc(len(seq.blocks))
                flight.record("kv_free", uid=int(uid),
                              blocks=len(seq.blocks),
                              free=self.allocator.free_blocks)

    # -- device metadata ----------------------------------------------------
    @staticmethod
    def _table(blocks, width: int) -> np.ndarray:
        table = np.full(width, NULL_BLOCK, np.int32)
        table[:len(blocks)] = blocks
        return table

    def block_table_for(self, uid: int) -> np.ndarray:
        """[max_blocks_per_seq] int32 padded with the null block."""
        return self._table(self.seqs[uid].blocks, self.max_blocks_per_seq)

    def window_table_for(self, uid: int) -> np.ndarray:
        """[ring_blocks] int32: the sequence's ring, place by place,
        padded with the null block where it has not grown yet."""
        return self._table(self.seqs[uid].window_blocks, self.ring_blocks)

    def free_blocks(self) -> int:
        return self.allocator.free_blocks

    def tracked_sequences(self) -> int:
        return len(self.seqs)
