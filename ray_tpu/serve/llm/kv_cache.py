"""Paged KV cache bookkeeping: fixed-size blocks over a preallocated
device pool, handed out by a free-list allocator and mapped per sequence
by a block table.

The device arrays are a model kind's (``init_paged_pool``), run over by
``ray_tpu.models.paged``'s programs; this module is the host-side half: which
pool block belongs to which sequence. Fixed-size blocks make fragmentation structural
zero — any request for ``n <= num_free`` blocks always succeeds, there is
no external fragmentation to compact and no defrag pause on the decode
path. Block 0 is reserved as the null block (padding target for block
tables and masked writes) and is never allocated.

A model kind whose layers keep a state a sequence (``paged_state_bytes``: a
recurrent layer's state is not rows a position) gets a second thing handed out
here: a **state row**, one a live sequence, out of ``state_rows`` rows
``1..state_rows`` (row 0 is the null row, as block 0 is the null block). A
table takes its row with its first blocks, all or nothing, gives it back with
them, and carries it in the last column of its dense form, so whoever holds a
table holds the row: the paged programs read it there (``paged.Step.state_rows``)
and an inactive slot's all-zero table names the null row. A kind without
state has no rows, and its tables are blocks alone.

Parity: vLLM's ``BlockAllocator``/``BlockTable`` split (block_manager),
reduced to the synchronous single-device case the in-tree engine needs.
"""

from __future__ import annotations

import threading
from typing import List, Optional

__all__ = [
    "KVCacheExhausted",
    "BlockAllocator",
    "BlockTable",
    "NULL_BLOCK",
]

# block 0 of every pool is the write/padding sink; never owned by a sequence
NULL_BLOCK = 0


class KVCacheExhausted(Exception):
    """Typed allocator failure: the pool has fewer free blocks than the
    request needs. The engine's admission control makes this unreachable
    for admitted sequences (capacity is reserved up front); reaching it
    from ``allocate`` means an accounting bug, reaching it from admission
    becomes a ``DeploymentOverloadedError`` shed."""

    def __init__(self, requested: int, free: int, what: str = "block"):
        super().__init__(
            f"KV cache exhausted: requested {requested} {what}(s), "
            f"{free} free"
        )
        self.requested = requested
        self.free = free


class BlockAllocator:
    """LIFO free-list over blocks ``1..num_blocks-1`` (block 0 reserved).

    All-or-nothing: ``allocate(n)`` either returns ``n`` distinct blocks
    or raises ``KVCacheExhausted`` without side effects. LIFO reuse keeps
    recently-freed blocks hot (their pool slots are most likely still in
    cache on the host-staging path).
    """

    def __init__(self, num_blocks: int, block_size: int, state_rows: int = 0):
        if num_blocks < 2:
            raise ValueError("need at least 2 blocks (block 0 is reserved)")
        if block_size < 1:
            raise ValueError("block_size must be >= 1")
        if state_rows < 0:
            raise ValueError("state_rows must be >= 0")
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self.state_rows = int(state_rows)  # rows 1..state_rows; 0: the kind keeps no state
        self._lock = threading.Lock()
        self._free: List[int] = list(range(self.num_blocks - 1, 0, -1))
        self._held: set = set()
        self._free_rows: List[int] = list(range(self.state_rows, 0, -1))
        self._held_rows: set = set()

    @property
    def num_usable(self) -> int:
        """Total allocatable blocks (pool minus the reserved null block)."""
        return self.num_blocks - 1

    @property
    def num_free(self) -> int:
        with self._lock:
            return len(self._free)

    @property
    def state_rows_free(self) -> int:
        with self._lock:
            return len(self._free_rows)

    def blocks_for_tokens(self, n_tokens: int) -> int:
        """Blocks needed to hold ``n_tokens`` cache entries."""
        return -(-max(0, int(n_tokens)) // self.block_size)

    def allocate(self, n: int = 1, state_row: bool = False):
        """``n`` blocks; with ``state_row`` a state row too, ``(blocks, row)``,
        both or neither."""
        if n < 0:
            raise ValueError(f"cannot allocate {n} blocks")
        with self._lock:
            if n > len(self._free):
                raise KVCacheExhausted(n, len(self._free))
            if state_row and not self._free_rows:
                raise KVCacheExhausted(1, 0, "state row")
            out = [self._free.pop() for _ in range(n)]
            self._held.update(out)
            if not state_row:
                return out
            row = self._free_rows.pop()
            self._held_rows.add(row)
            return out, row

    def free(self, blocks: List[int], state_row: int = 0) -> None:
        """Return blocks (and a state row) to the free lists; double-free and
        foreign blocks or rows are accounting bugs and raise rather than
        corrupting the pool."""
        with self._lock:
            for b in blocks:
                if b not in self._held:
                    raise ValueError(
                        f"freeing block {b} that is not allocated "
                        f"(double free or foreign block)"
                    )
                self._held.discard(b)
                self._free.append(b)
            if state_row:
                if state_row not in self._held_rows:
                    raise ValueError(f"freeing state row {state_row} that is not allocated")
                self._held_rows.discard(state_row)
                self._free_rows.append(state_row)


class BlockTable:
    """Per-sequence block list plus token length; grows one block at a
    time as decode crosses block boundaries. Under an allocator with state
    rows it owns one from its first reservation to its release."""

    __slots__ = ("allocator", "blocks", "length", "state_row")

    def __init__(self, allocator: BlockAllocator, n_tokens: int = 0):
        self.allocator = allocator
        self.blocks: List[int] = []
        self.length = 0
        self.state_row = NULL_BLOCK  # 0: none (yet); the null row
        if n_tokens:
            self.reserve(n_tokens)

    def reserve(self, n_tokens: int) -> None:
        """Grow the table to cover ``n_tokens`` total positions; the first
        blocks come with the state row, where the allocator has rows."""
        need = self.allocator.blocks_for_tokens(n_tokens) - len(self.blocks)
        if need <= 0:
            return
        if self.allocator.state_rows and not self.state_row:
            blocks, self.state_row = self.allocator.allocate(need, state_row=True)
        else:
            blocks = self.allocator.allocate(need)
        self.blocks.extend(blocks)

    def append_token(self) -> int:
        """Account one more cache entry, allocating a block on boundary
        crossings; returns the new length."""
        self.reserve(self.length + 1)
        self.length += 1
        return self.length

    def release(self) -> None:
        """Free every owned block and the state row (idempotent)."""
        if self.blocks or self.state_row:
            self.allocator.free(self.blocks, self.state_row)
            self.blocks, self.state_row = [], NULL_BLOCK

    def as_list(self, max_blocks: int) -> List[int]:
        """Dense table padded with the null block to ``max_blocks``; under an
        allocator with state rows the last of the ``max_blocks`` columns is the
        state row and the blocks have one column fewer."""
        tail = [self.state_row] if self.allocator.state_rows else []
        max_blocks -= len(tail)
        if len(self.blocks) > max_blocks:
            raise ValueError(
                f"sequence spans {len(self.blocks)} blocks > "
                f"max_blocks_per_seq {max_blocks}"
            )
        return self.blocks + [NULL_BLOCK] * (max_blocks - len(self.blocks)) + tail
