"""Per-sequence bookkeeping.

Reference: inference/v2/ragged/sequence_descriptor.py (DSSequenceDescriptor):
tracks a sequence's uid, how many tokens the KV cache has seen, and which
cache blocks it owns.
"""

from dataclasses import dataclass, field
from typing import List, Optional


@dataclass
class DSSequenceDescriptor:
    uid: int
    seen_tokens: int = 0            # tokens whose KV is in the cache
    blocks: List[int] = field(default_factory=list)
    in_flight_tokens: int = 0       # tokens scheduled in the current batch
    # token content in cache order — what prefix caching indexes at flush
    # (appended by the engine's prefill/continue/decode paths)
    token_log: List[int] = field(default_factory=list)
    # multi-tenant LoRA identity: the adapter NAME keys prefix-cache
    # digests (stable across replicas), the engine-local bank SLOT rides
    # the ragged batch so the kernel gathers the right delta per row.
    # Base-model sequences keep (None, 0) — slot 0 is the zero adapter.
    adapter: Optional[str] = None
    adapter_slot: int = 0
    # the sequence's slot of recurrent state (a model with
    # linear-attention layers: the manager hands one out with the
    # sequence and takes it back at flush, beside its blocks); 0, the
    # null slot, where the model keeps none
    state_slot: int = 0
    # the second geometry (a model with window-attention layers): the
    # blocks of the sequence's RING in the window layers' pool, in place
    # order (position p lies in ``window_blocks[(p // block_size) %
    # ring_blocks]``); they grow with the sequence up to the ring's size
    # and are then written over
    window_blocks: List[int] = field(default_factory=list)
    # False for a model that caches no position (its layers all keep a
    # state a sequence): the sequence then owns its state slot and NO
    # block, however long it grows
    paged: bool = True

    def window_blocks_needed(self, new_tokens: int, block_size: int,
                             ring_blocks: int) -> int:
        """Ring blocks still to hand out before ``new_tokens`` more
        positions are written (0 for a model without a ring)."""
        total = self.seen_tokens + new_tokens
        return max(0, min(-(-total // block_size), ring_blocks)
                   - len(self.window_blocks))

    def blocks_needed(self, new_tokens: int, block_size: int) -> int:
        if not self.paged:
            return 0
        total = self.seen_tokens + new_tokens
        have = len(self.blocks)
        need = -(-total // block_size)  # ceil
        return max(0, need - have)

    @property
    def cur_allocated_tokens(self) -> int:
        return len(self.blocks)
