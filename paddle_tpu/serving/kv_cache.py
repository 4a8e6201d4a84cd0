"""Paged block KV cache: the serving engine's memory plane.

The vLLM-style design adapted to the repo's functional-XLA runtime: the
cache is ONE device array of fixed-size blocks

    pages[n_attn_layers * n_blocks, block_size, row]

(``DecodeModel.pool_shape``), and a request owns an ordered *block
table* — the list of block ids its context occupies, the same ids in
every layer that attends: the ``a``-th of them keeps block ``b`` at
row-block ``a * n_blocks + b`` (a layer of another kind, such as a gated
short convolution, owns no share: what it keeps per request lies in the
state pool, by decode slot and not by block, ``DecodeModel.state_shape``).
A token's row holds, K|V head by K|V head (one a query head, or one a
group of them), that head's K then its V (``kv_heads * 2 * head_dim``
lanes); under latent attention it is ONE row for every head, the normed
K|V latent and the rotated key lanes, padded to whole 128-lane tiles.
Nothing below depends on what a row holds. The
decode program scatters the new token's K/V into the tail slot and reads
a request's K/V through its table (page by page where it lies, in
``ops/pallas/paged_attention``), so the cache never compacts and
requests of wildly different lengths share one allocation. Block 0 (of
every layer) is the reserved **scratch block**: padded table entries and
inactive batch rows direct their (masked, never-read) reads and writes
there, which keeps every gather/scatter in the compiled program
unconditional.

The shape is the device's, not the reader's. The TPU runtime stores an
array in the most compact tiled layout for its shape; for the former
``[n_layer, 2, n_blocks, block_size, n_head, head_dim]`` that put the
block id on the fastest-moving dimension, so every program that indexed
by block id copied the whole pool into a padded row-major layout and
back (twice 25 ms of an 89 ms decode tick of GPT-2 XL on a v5e, PERF.md
PR 25). A row that is a whole number of 128-lane tiles (2 x 64 a head)
over a 16-token block is stored as it is indexed, so the programs take
the pool donated and update it in place; folding the layer (and K|V)
into the index and the row leaves no slice to materialise either.

The host-side :class:`BlockAllocator` is deliberately dumb — a free
list with LIFO reuse (the test observes a freed block coming straight
back) and an explicit utilization view the ledger exports as the
``serve_kv_block_utilization`` gauge. Eviction POLICY lives in the
engine (victim = latest SLO deadline); the allocator only answers
"can I have n blocks" honestly.
"""
from __future__ import annotations

import threading
from typing import Dict, List, Optional

__all__ = ["BlockAllocator", "blocks_for_tokens"]


# tokens a KV-cache block holds where a deployment does not say: requests
# hold ceil(context / block_size) blocks and grow one at a time while decoding
BLOCK_SIZE = 16


def blocks_for_tokens(n_tokens: int, block_size: int) -> int:
    """Blocks a context of n_tokens occupies (ceil division)."""
    if n_tokens <= 0:
        return 0
    return (int(n_tokens) + int(block_size) - 1) // int(block_size)


class BlockAllocator:
    """Free-list allocator over block ids [1, n_blocks): block 0 is the
    scratch block and is never handed out. Thread-safe; alloc is
    all-or-nothing (a request half-granted would deadlock the batch)."""

    def __init__(self, n_blocks: int, block_size: int):
        from ..framework import errors as _errors

        if n_blocks < 2:
            raise _errors.errors.InvalidArgument(
                f"kv cache needs >= 2 blocks (1 scratch + 1 usable), "
                f"got {n_blocks}")
        self.n_blocks = int(n_blocks)
        self.block_size = int(block_size)
        self._lock = threading.Lock()
        # LIFO free list: lowest ids on top so reuse is observable and
        # deterministic in tests
        self._free: List[int] = list(range(self.n_blocks - 1, 0, -1))
        self._owner: Dict[int, str] = {}

    @property
    def capacity(self) -> int:
        """Allocatable blocks (scratch excluded)."""
        return self.n_blocks - 1

    def available(self) -> int:
        with self._lock:
            return len(self._free)

    def used(self) -> int:
        with self._lock:
            return len(self._owner)

    def utilization(self) -> float:
        with self._lock:
            return len(self._owner) / float(self.capacity)

    def can_alloc(self, n: int) -> bool:
        with self._lock:
            return len(self._free) >= int(n)

    def alloc(self, n: int, owner: str = "") -> Optional[List[int]]:
        """Grant n blocks to `owner`, or None when the free list cannot
        cover the whole ask (all-or-nothing)."""
        n = int(n)
        if n <= 0:
            return []
        with self._lock:
            if len(self._free) < n:
                return None
            ids = [self._free.pop() for _ in range(n)]
            for b in ids:
                self._owner[b] = owner
            return ids

    def free(self, ids: List[int]) -> None:
        """Return blocks to the free list (LIFO: the next alloc reuses
        the most recently freed block first). Double-frees and scratch
        frees are programming errors and raise — the WHOLE list is
        validated before any block moves, so a rejected free leaves the
        allocator exactly as it was."""
        from ..framework import errors as _errors

        with self._lock:
            seen = set()
            for b in ids:
                b = int(b)
                if b == 0:
                    raise _errors.errors.InvalidArgument(
                        "block 0 is the reserved scratch block")
                if b not in self._owner or b in seen:
                    raise _errors.errors.InvalidArgument(
                        f"block {b} is not allocated (double free?)")
                seen.add(b)
            for b in ids:
                del self._owner[int(b)]
                self._free.append(int(b))

    def owners(self) -> Dict[int, str]:
        with self._lock:
            return dict(self._owner)

    def blocks_of(self, owner: str) -> List[int]:
        with self._lock:
            return sorted(b for b, o in self._owner.items() if o == owner)
