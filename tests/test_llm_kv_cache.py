"""Paged KV cache host-side bookkeeping: free-list allocator invariants
(reuse-after-free, all-or-nothing, typed exhaustion, zero external
fragmentation by construction) and per-sequence block tables."""

import random

import pytest

from ray_tpu.serve.llm.kv_cache import (
    NULL_BLOCK,
    BlockAllocator,
    BlockTable,
    KVCacheExhausted,
)


def test_allocator_basic_and_null_block_reserved():
    a = BlockAllocator(num_blocks=8, block_size=4)
    assert a.num_usable == 7
    assert a.num_free == 7
    got = a.allocate(7)
    assert len(set(got)) == 7
    assert NULL_BLOCK not in got, "null block must never be handed out"
    assert a.num_free == 0


def test_allocator_reuse_after_free():
    a = BlockAllocator(num_blocks=6, block_size=2)
    first = a.allocate(5)
    a.free(first)
    second = a.allocate(5)
    # same physical blocks cycle back (LIFO free list)
    assert set(second) == set(first)
    assert a.num_free == 0


def test_allocator_exhaustion_is_typed_and_atomic():
    a = BlockAllocator(num_blocks=5, block_size=4)
    a.allocate(2)
    free_before = a.num_free
    with pytest.raises(KVCacheExhausted) as ei:
        a.allocate(3)
    # all-or-nothing: the failed request must not leak partial blocks
    assert a.num_free == free_before
    assert ei.value.requested == 3
    assert ei.value.free == 2


def test_allocator_double_free_rejected():
    a = BlockAllocator(num_blocks=4, block_size=1)
    blocks = a.allocate(2)
    a.free(blocks)
    with pytest.raises(ValueError):
        a.free([blocks[0]])
    with pytest.raises(ValueError):
        a.free([NULL_BLOCK])


def test_allocator_no_external_fragmentation():
    """Fixed-size blocks: after ANY alloc/free history, a request for
    n <= num_free always succeeds — there is no fragmentation to hit."""
    rng = random.Random(7)
    a = BlockAllocator(num_blocks=33, block_size=8)
    held = []
    for _ in range(500):
        if held and rng.random() < 0.5:
            a.free(held.pop(rng.randrange(len(held))))
        else:
            want = rng.randint(1, 4)
            if want <= a.num_free:
                held.append(a.allocate(want))
        # the invariant under test, every step
        n = a.num_free
        if n:
            probe = a.allocate(n)
            assert len(probe) == n
            a.free(probe)
    # full reclamation
    for h in held:
        a.free(h)
    assert a.num_free == a.num_usable


def test_block_table_growth_and_release():
    a = BlockAllocator(num_blocks=16, block_size=4)
    t = BlockTable(a)
    t.reserve(6)  # 6 tokens -> 2 blocks
    t.length = 6
    assert len(t.blocks) == 2
    assert a.num_free == a.num_usable - 2
    # appending within the block: no new allocation until the boundary
    t.append_token()  # 7
    t.append_token()  # 8
    assert len(t.blocks) == 2
    t.append_token()  # 9 crosses into block 3
    assert len(t.blocks) == 3
    padded = t.as_list(5)
    assert padded[:3] == t.blocks and padded[3:] == [NULL_BLOCK, NULL_BLOCK]
    with pytest.raises(ValueError):
        t.as_list(2)
    t.release()
    assert a.num_free == a.num_usable
    t.release()  # idempotent


def test_blocks_for_tokens_math():
    a = BlockAllocator(num_blocks=4, block_size=8)
    assert a.blocks_for_tokens(0) == 0
    assert a.blocks_for_tokens(1) == 1
    assert a.blocks_for_tokens(8) == 1
    assert a.blocks_for_tokens(9) == 2
    assert a.blocks_for_tokens(17) == 3


# -- state rows: the second thing handed out, for a kind whose layers keep a state a sequence --


def test_a_kind_without_state_has_no_rows_and_its_tables_are_blocks_alone():
    a = BlockAllocator(num_blocks=8, block_size=4)
    assert a.state_rows == 0 and a.state_rows_free == 0
    t = BlockTable(a, n_tokens=5)
    assert t.state_row == NULL_BLOCK and t.as_list(4) == t.blocks + [NULL_BLOCK] * 2
    with pytest.raises(KVCacheExhausted, match="state row"):
        a.allocate(1, state_row=True)
    assert a.num_free == a.num_usable - 2  # and the refusal took no block


def test_state_rows_come_with_the_first_blocks_and_go_back_with_them_once():
    a = BlockAllocator(num_blocks=16, block_size=4, state_rows=3)
    assert a.state_rows_free == 3
    t = BlockTable(a)
    assert t.state_row == NULL_BLOCK and a.state_rows_free == 3  # an empty table holds nothing
    t.reserve(6)
    row = t.state_row
    assert 1 <= row <= 3 and a.state_rows_free == 2
    t.length = 6
    for _ in range(7):  # growing takes blocks, never a second row
        t.append_token()
    assert t.state_row == row and a.state_rows_free == 2 and len(t.blocks) == 4
    # the dense form: one column fewer of blocks, the row in the last
    assert t.as_list(6) == t.blocks + [NULL_BLOCK, row]
    with pytest.raises(ValueError, match="max_blocks_per_seq 3"):
        t.as_list(4)
    t.release()
    assert (a.state_rows_free, a.num_free, t.state_row) == (3, a.num_usable, NULL_BLOCK)
    t.release()  # idempotent: the row is not given back twice
    assert a.state_rows_free == 3
    with pytest.raises(ValueError, match="state row"):
        a.free([], state_row=row)  # a row that is not held: refused like a foreign block
    assert BlockTable(a, n_tokens=1).state_row == row  # LIFO: a newcomer gets the row just freed


def test_rows_and_blocks_are_reserved_all_or_nothing_and_the_null_row_is_never_handed_out():
    a = BlockAllocator(num_blocks=9, block_size=4, state_rows=2)
    first, second = BlockTable(a, n_tokens=4), BlockTable(a, n_tokens=4)
    assert {first.state_row, second.state_row} == {1, 2} and a.state_rows_free == 0
    third = BlockTable(a)
    with pytest.raises(KVCacheExhausted) as ei:  # blocks are there, no row is: neither is taken
        third.reserve(4)
    assert (ei.value.requested, ei.value.free) == (1, 0) and "state row" in str(ei.value)
    assert a.num_free == a.num_usable - 2 and third.blocks == [] and third.state_row == NULL_BLOCK
    second.release()
    with pytest.raises(KVCacheExhausted):  # a row is there, the blocks are not: neither is taken
        third.reserve(4 * 8)
    assert a.state_rows_free == 1 and a.num_free == a.num_usable - 1 and third.state_row == NULL_BLOCK
    third.reserve(4)
    assert third.state_row == second.state_row or third.state_row in (1, 2)
    # an inactive slot's table is all zeros: the null block in every column and the null row in the last
    assert [NULL_BLOCK] * 5 == BlockTable(a).as_list(5)
