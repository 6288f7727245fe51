"""Prefix-cache allocator semantics (llm/kv_cache.py PrefixPool):
chunk-hash chain matching, refcounts, LRU parking/eviction, and
copy-on-write splits that never corrupt the shared parent block."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.llm.kv_cache import PagedKVCache, PrefixPool  # noqa: E402
from ray_tpu.models.gpt import GPTConfig  # noqa: E402

CFG = GPTConfig(vocab_size=64, max_seq=64, d_model=32, n_layer=2,
                n_head=4, dtype=jnp.float32)


def _pool(num_blocks=8, block_size=4):
    return PrefixPool(CFG, num_blocks=num_blocks, block_size=block_size)


def test_cold_admit_then_rerelease_makes_chain_matchable():
    p = _pool()
    seq = list(range(10))                      # 2 full chunks + tail 2
    table, cached = p.admit(seq, len(seq) + 1)
    assert cached == 0 and len(table) == 3
    assert all(p._ref[b] == 1 for b in table)
    p.release(table, seq=seq)
    # Registered blocks PARK (matchable, evictable) instead of freeing:
    # num_free counts them as allocatable, utilization reads 0.
    assert p.num_free == p.capacity
    assert p.utilization() == 0.0
    t2, c2 = p.admit(seq, len(seq) + 1)
    assert c2 == len(seq)                      # full hit incl exact tail
    assert t2[:3] == table                     # the SAME blocks come back
    assert p.hit_rate() == pytest.approx(10 / 20)


def test_partial_tail_only_matches_exact_remainder():
    p = _pool(num_blocks=16)
    seq = list(range(10))
    t1, _ = p.admit(seq, len(seq) + 1)
    p.release(t1, seq=seq)
    # Same full chunks, longer different tail: only the 8 full-chunk
    # tokens hit (a mid-block span can't be resumed mid-block).
    seq2 = list(range(8)) + [60, 61, 62]
    t2, c2 = p.admit(seq2, len(seq2) + 1)
    assert c2 == 8
    assert t2[:2] == t1[:2] and t2[2] != t1[2]
    # A different FIRST chunk shares nothing (chain hash includes the
    # parent key, so identical later chunks do not collide).
    seq3 = [63] + list(range(1, 10))
    t3, c3 = p.admit(seq3, len(seq3) + 1)
    assert c3 == 0
    assert not set(t3) & set(t1)


def test_refcounts_shared_blocks_and_double_free():
    p = _pool(num_blocks=16)
    seq = list(range(8))
    t1, _ = p.admit(seq, len(seq) + 1)
    p.release(t1, seq=seq)
    a, ca = p.admit(seq, len(seq) + 1)
    b, cb = p.admit(seq, len(seq) + 1)
    assert ca == cb == 8
    assert a[:2] == b[:2]
    assert all(p._ref[x] == 2 for x in a[:2])
    assert p.shared_blocks() == 2
    p.release(a)
    p.release(b)
    assert p.shared_blocks() == 0
    with pytest.raises(ValueError, match="double free"):
        p.release(b)


def test_lru_eviction_drops_oldest_unreferenced_chain_first():
    p = _pool(num_blocks=8, block_size=4)      # 7 usable blocks
    old = list(range(8))
    hot = list(range(8, 16))
    t_old, _ = p.admit(old, len(old) + 1)      # 3 blocks, 2 registered
    p.release(t_old, seq=old)
    t_hot, _ = p.admit(hot, len(hot) + 1)
    p.release(t_hot, seq=hot)
    # 4 parked + 3 free; demand 5 fresh: evicts from the LRU FRONT
    # (old's chain) but must not touch hot's more recent blocks.
    big = p.alloc(5)
    assert big is not None and len(big) == 5
    assert p.evictions >= 1
    p.free(big)
    t_old2, c_old = p.admit(old, len(old) + 1)
    assert c_old == 0                          # old chain was evicted
    p.release(t_old2)                          # no seq: not re-registered
    t2, c_hot = p.admit(hot, len(hot) + 1)
    assert c_hot == 8                          # hot survived the pressure
    p.release(t2)
    # Referenced blocks are NEVER evicted: hold a ref, demand the world.
    held, c3 = p.admit(hot, len(hot) + 1)
    assert c3 == 8
    assert p.alloc(p.capacity) is None         # held blocks can't be taken
    assert all(p._ref[x] >= 1 for x in held)


def test_cow_splits_shared_tail_without_corrupting_parent():
    p = _pool(num_blocks=16, block_size=4)
    seq = list(range(10))                      # tail block holds 2 tokens
    t1, _ = p.admit(seq, len(seq) + 1)
    rng = np.random.default_rng(1)
    k = rng.normal(size=(CFG.n_layer, 10, CFG.kv_heads,
                         CFG.head_dim)).astype(np.float32)
    p.write_prefill(jnp.asarray(k), jnp.asarray(k), t1[:3])
    p.release(t1, seq=seq)
    t2, c2 = p.admit(seq, len(seq) + 1)        # full hit, shares tail
    assert c2 == 10
    tail = t2[2]
    # Writing at offset 2 would extend past the registered span-2 tail:
    # sole owner, no COW needed. Offset 1 is INSIDE it: COW required.
    assert not p.needs_cow(tail, 2)
    assert p.needs_cow(tail, 1)
    before = np.asarray(p.k[:, tail])
    nb = p.cow(tail)
    assert nb is not None and nb != tail
    # The private copy carries the parent's content; the parent block
    # itself is untouched and still matchable (parked in LRU).
    assert np.array_equal(np.asarray(p.k[:, nb]), before)
    assert np.array_equal(np.asarray(p.k[:, tail]), before)
    assert p.cow_splits == 1
    assert tail in p._lru
    t3, c3 = p.admit(seq, len(seq) + 1)        # chain STILL fully hits
    assert c3 == 10 and t3[2] == tail


def test_cow_required_when_block_has_co_readers():
    p = _pool(num_blocks=16, block_size=4)
    seq = list(range(8))
    t1, _ = p.admit(seq, len(seq) + 1)
    p.release(t1, seq=seq)
    a, _ = p.admit(seq, len(seq) + 1)
    b, _ = p.admit(seq, len(seq) + 1)
    # Both sequences share the full blocks: ANY write offset needs COW.
    assert p.needs_cow(a[0], 0) and p.needs_cow(a[1], 3)
    nb = p.cow(a[1])
    a[1] = nb
    assert p._ref[b[1]] == 1                   # b's view kept one ref
    assert p._ref[nb] == 1


def test_every_state_change_emits_an_event():
    p = _pool(num_blocks=8, block_size=4)
    seq = list(range(8))
    t1, _ = p.admit(seq, len(seq) + 1)
    p.release(t1, seq=seq)                     # register
    t2, _ = p.admit(seq, len(seq) + 1)         # share
    p.cow(t2[0])                               # cow
    p.alloc(len(p._free) + len(p._lru))        # forces evictions
    kinds = [k for _, k, _ in p.events]
    assert {"register", "share", "cow", "evict"} <= set(kinds)
    stats = p.prefix_stats()
    assert stats["registrations"] >= 2
    assert stats["hit_tokens"] == 8
    assert stats["cow_splits"] == 1
    assert stats["evictions"] >= 1


def test_hash_collision_verifies_content_and_misses():
    p = _pool(num_blocks=16, block_size=4)
    seq = list(range(8))
    t1, _ = p.admit(seq, len(seq) + 1)
    p.release(t1, seq=seq)
    key = next(iter(p._index))
    parent, chunk, bid, span = p._index[key]
    # Poison the entry's stored chunk: lookups must now verify-fail
    # (degrade to a miss), never serve wrong content.
    p._index[key] = (parent, tuple(reversed(chunk)), bid, span)
    _, cached = p.admit(seq, len(seq) + 1)
    assert cached in (0, 4)                    # poisoned link breaks there


def test_free_is_release_and_base_pool_unaffected():
    # Engine teardown calls free() on either pool flavor.
    p = _pool()
    seq = list(range(4))
    t, _ = p.admit(seq, len(seq) + 1)
    p.free(t)
    assert p.num_free == p.capacity
    with pytest.raises(ValueError, match="double free"):
        p.free(t)
    # The base pool keeps its plain-stack behavior plus the new raise.
    kv = PagedKVCache(CFG, num_blocks=8, block_size=4)
    g = kv.alloc(3)
    kv.free(g)
    with pytest.raises(ValueError, match="double free"):
        kv.free(g)
