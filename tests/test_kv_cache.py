"""Paged KV pool: allocator invariants + write/readback round trips
(llm/kv_cache.py)."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.llm.kv_cache import PagedKVCache  # noqa: E402
from ray_tpu.models.gpt import GPTConfig  # noqa: E402

CFG = GPTConfig(vocab_size=64, max_seq=64, d_model=32, n_layer=2,
                n_head=4, dtype=jnp.float32)


def test_allocator_reserves_block_zero_and_is_all_or_nothing():
    kv = PagedKVCache(CFG, num_blocks=8, block_size=4)
    assert kv.capacity == 7
    grant = kv.alloc(7)
    assert grant is not None and 0 not in grant
    assert sorted(grant) == list(range(1, 8))
    assert kv.alloc(1) is None          # empty: None, never partial
    assert kv.utilization() == 1.0
    kv.free(grant)
    assert kv.num_free == 7 and kv.utilization() == 0.0
    with pytest.raises(ValueError):
        kv.free([0])                    # scratch block is untouchable
    with pytest.raises(ValueError):
        PagedKVCache(CFG, num_blocks=1)


def test_blocks_for_tokens():
    kv = PagedKVCache(CFG, num_blocks=4, block_size=4)
    assert kv.blocks_for_tokens(1) == 1
    assert kv.blocks_for_tokens(4) == 1
    assert kv.blocks_for_tokens(5) == 2
    assert kv.blocks_for_tokens(0) == 1  # a sequence always owns a block


def test_write_prefill_roundtrip_with_ragged_tail():
    kv = PagedKVCache(CFG, num_blocks=8, block_size=4)
    T = 10                               # 2.5 blocks -> ragged tail
    grant = kv.alloc(kv.blocks_for_tokens(T))
    assert len(grant) == 3
    rng = np.random.default_rng(0)
    k = rng.normal(size=(CFG.n_layer, T, CFG.kv_heads,
                         CFG.head_dim)).astype(np.float32)
    v = rng.normal(size=k.shape).astype(np.float32)
    kv.write_prefill(jnp.asarray(k), jnp.asarray(v), grant)
    k_back, v_back = kv.gather_tokens(grant, T)
    # Rows come back as the pool holds them: a token's heads side by side.
    np.testing.assert_allclose(np.asarray(k_back).reshape(k.shape), k,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(v_back).reshape(v.shape), v,
                               atol=1e-6)
    # The scratch block stayed zero.
    assert float(jnp.abs(kv.k[:, 0]).max()) == 0.0


def test_writes_to_disjoint_grants_do_not_interfere():
    kv = PagedKVCache(CFG, num_blocks=8, block_size=4)
    g1, g2 = kv.alloc(2), kv.alloc(2)
    rng = np.random.default_rng(1)
    mk = lambda: jnp.asarray(rng.normal(size=(
        CFG.n_layer, 8, CFG.kv_heads, CFG.head_dim)).astype(np.float32))
    k1, v1, k2, v2 = mk(), mk(), mk(), mk()
    kv.write_prefill(k1, v1, g1)
    kv.write_prefill(k2, v2, g2)
    k1b, _ = kv.gather_tokens(g1, 8)
    k2b, _ = kv.gather_tokens(g2, 8)
    np.testing.assert_allclose(np.asarray(k1b).reshape(k1.shape),
                               np.asarray(k1), atol=1e-6)
    np.testing.assert_allclose(np.asarray(k2b).reshape(k2.shape),
                               np.asarray(k2), atol=1e-6)


def test_double_free_raises_and_pool_stays_usable():
    kv = PagedKVCache(CFG, num_blocks=8, block_size=4)
    g = kv.alloc(2)
    kv.free(g)
    with pytest.raises(ValueError, match="double free"):
        kv.free([g[0]])
    with pytest.raises(ValueError, match="double free"):
        kv.free(g)
    # The failed frees did not corrupt the free list.
    assert kv.num_free == kv.capacity
    g2 = kv.alloc(kv.capacity)
    assert g2 is not None
    kv.free(g2)


def test_write_prefill_rejects_overflow():
    kv = PagedKVCache(CFG, num_blocks=8, block_size=4)
    grant = kv.alloc(1)
    k = jnp.zeros((CFG.n_layer, 5, CFG.kv_heads, CFG.head_dim))
    with pytest.raises(ValueError):
        kv.write_prefill(k, k, grant)
