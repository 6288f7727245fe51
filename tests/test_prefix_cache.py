"""Prefix-cache allocator semantics (llm/kv_cache.py PrefixPool):
chunk-hash chain matching, refcounts, LRU parking/eviction, and
copy-on-write splits that never corrupt the shared parent block."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.llm import kv_cache  # noqa: E402
from ray_tpu.llm.kv_cache import (  # noqa: E402
    BlockChain, PagedKVCache, PrefixPool, WindowPool)
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


# ---------------------------------------------------------------------------
# A request's chain of block keys, made once (PR 46)
# ---------------------------------------------------------------------------
def _window_pool(num_blocks, block_size):
    from test_laguna import TINY

    return WindowPool(TINY, num_blocks=num_blocks, block_size=block_size)


POOLS = {"prefix": _pool, "window": _window_pool}


def _state(p):
    """Everything the index, the allocator and the accounting hold."""
    return (dict(p._index), dict(p._keys_of), list(p._lru), list(p._free),
            {b: r for b, r in p._ref.items() if r}, p.hit_tokens,
            p.lookup_tokens, p.evictions, p.registrations, p.cow_splits,
            p.shared_blocks(), [(k, a) for _, k, a in p.events])


@pytest.mark.parametrize("kind", ["prefix", "window"])
def test_a_requests_chain_gives_what_its_tokens_give_across_an_eviction(
        kind, monkeypatch):
    """Two pools of a kind go through one script, one handed each
    request's precomputed chain and the other the tokens alone: every
    ``match`` / ``admit`` / ``register`` / ``release`` (and the window
    kind's ``match_tail`` / ``register_tail``) returns the same tables
    and covered tokens, and after every call the index, the parked and
    free lists, the refcounts, the hit counts, the evictions and the
    events are the same, through an eviction that breaks a chain in the
    middle, a re-registration, and a sequence that grew past its
    prompt. A block the chain holds is never hashed again: through the
    whole script the pool that is handed chains hashes only ragged
    tails and the one block that filled behind a prompt."""
    bs = 4
    with_chain, alone = POOLS[kind](10, bs), POOLS[kind](10, bs)
    a = list(range(14))                        # 3 whole blocks + 2
    b = a[:8] + [40, 41, 42, 43, 44]           # shares two blocks
    chains = {id(a): BlockChain(bs, a), id(b): BlockChain(bs, b)}
    assert [len(c.keys) for c in chains.values()] == [3, 3]
    made = [list(c.keys) for c in chains.values()]
    hashed = {"chain": [], "alone": []}
    side = ["alone"]

    def counting_hash(x):
        hashed[side[0]].append(x)
        return hash(x)

    monkeypatch.setattr(kv_cache, "hash", counting_hash, raising=False)

    def both(call, owner, *args, **kw):
        """``call(pool, *args, chain=owner's, **kw)`` on one pool,
        ``call(pool, *args, **kw)`` on the other."""
        side[0] = "chain"
        got = call(with_chain, *args, chain=chains[id(owner)], **kw)
        side[0] = "alone"
        want = call(alone, *args, **kw)
        assert got == want
        assert _state(with_chain) == _state(alone)
        return got

    P = type(alone)
    ta, cached = both(P.admit, a, a, len(a) + 1)
    assert cached == 0 and len(ta) == 4
    both(P.register, a, a, ta)
    assert both(P.match, a, a) == 14
    for (key, parent, chunk), bid in zip(chains[id(a)].blocks(a), ta):
        assert with_chain._index[key] == (parent, chunk, bid, len(chunk))
    tb, cached = both(P.admit, b, b, len(b) + 1)
    assert cached == 8 and tb[:2] == ta[:2]
    assert with_chain.shared_blocks() == 2
    if kind == "window":
        both(P.register_tail, b, b, tb[1:], 1)
        assert both(P.match_tail, b, b, 13) == (13, 0, tb)
    both(P.release, b, tb, seq=b)
    # ``a`` grew two tokens past its prompt (generated ones): released
    # with the longer sequence, the same chain takes the new block in.
    grown = a + [50, 51]
    both(P.release, a, ta, seq=grown)
    assert len(chains[id(a)].keys) == 4
    assert with_chain.num_free == with_chain.capacity
    # Pressure evicts the oldest parked blocks: a's chain breaks.
    for pool in (with_chain, alone):
        hold = pool.alloc(6)
        assert hold is not None and pool.evictions > 0
        pool.release(hold)
    assert _state(with_chain) == _state(alone)
    covered = both(P.match, a, grown)
    assert covered < len(grown)
    if kind == "window":
        both(P.match_tail, a, grown, covered)
    t2, cached = both(P.admit, a, grown, len(grown) + 1)
    assert cached == covered
    both(P.register, a, grown, t2)
    assert both(P.match, a, grown) == len(grown)
    both(P.release, a, t2, seq=grown)
    # Nothing the chains held at the start was made again: beside the
    # ragged tails the chained pool hashed ONE whole block, the one
    # that filled behind a's prompt; the other pool every block, every
    # call.
    for chain, first in zip(chains.values(), made):
        assert chain.keys[:3] == first
    whole = lambda side: [x for x in hashed[side] if len(x[1]) == bs]
    assert [x[1] for x in whole("chain")] == [tuple(grown[12:16])]
    assert len(whole("alone")) > 30


def test_chain_blocks_are_the_keys_the_tokens_give():
    bs = 4
    seq = [7, 3, 9, 4, 1, 1, 2, 8, 5, 6]
    chain = BlockChain(bs, seq[:6])            # made from a prefix
    want, parent = [], 0
    for i in range(0, len(seq), bs):
        chunk = tuple(seq[i:i + bs])
        want.append((hash((parent, chunk)), parent, chunk))
        parent = want[-1][0]
    assert list(chain.blocks(seq)) == want     # extends, tail last
    assert list(chain.blocks(seq, 8)) == want[:2]
    assert list(chain.blocks(seq, 6)) == want[:1] + [
        (hash((want[0][0], (1, 1))), want[0][0], (1, 1))]
    assert [chain.reach(seq, 10).block(i, 10) for i in range(3)] == want
    assert list(chain.blocks(seq, 0)) == []
    assert list(BlockChain(bs).blocks([])) == []
    # What it keeps is two containers, whatever its length.
    assert chain.keys == [k for k, _, _ in want[:2]]
    assert chain.tokens == tuple(seq[:8])


def test_tokens_per_s_and_shared_blocks_equal_their_sums(monkeypatch):
    """The two per-step gauges read counters: after every step of a
    run with shared prefixes, a copy-on-write split, releases and an
    idle spell they equal the sums they replaced, the refcounts walked
    and the deque summed."""
    from ray_tpu.llm import engine as engine_mod
    from ray_tpu.llm.engine import LLMEngine
    from ray_tpu.models.gpt import init

    clock = [1000.0]
    monkeypatch.setattr(engine_mod.time, "time", lambda: clock[0])
    eng = LLMEngine(init(jax.random.PRNGKey(0), CFG), CFG, num_blocks=32,
                    block_size=4, max_batch=4)

    def check():
        kv = eng.kv
        assert kv.shared_blocks() == sum(1 for r in kv._ref.values()
                                         if r > 1)
        now, window = clock[0], 5.0
        live = [(t, n) for t, n in eng._token_times if t >= now - window]
        want = (sum(n for _, n in live) / max(now - live[0][0], 1e-3)
                if live else 0.0)
        assert eng.tokens_per_s() == pytest.approx(want)
        assert eng._tokens_in_window == sum(n for _, n in eng._token_times)

    prompt = [5, 6, 7, 8, 9, 10, 11, 12, 13, 14]
    eng.add_request(prompt, max_tokens=6)
    shared = 0
    for i in range(40):
        if i == 3:      # the same prompt twice more: full hits, a COW
            eng.add_request(prompt, max_tokens=5)
            eng.add_request(prompt + [3], max_tokens=4)
        clock[0] += 0.3
        live = eng.step()
        shared = max(shared, eng.kv.shared_blocks())
        check()
        if not live and i > 3:
            break
    assert shared >= 2 and eng.kv.cow_splits >= 1
    assert eng.tokens_per_s() > 0
    clock[0] += 4.0         # part of the window has lapsed
    check()
    clock[0] += 6.0         # idle past the window: the series decays
    assert eng.tokens_per_s() == 0.0
    assert not eng._token_times and eng._tokens_in_window == 0
    assert eng.kv.shared_blocks() == 0
