"""Block-allocated paged KV pool for the generation engine.

Reference layer map: this is the TPU-native analogue of vLLM's
PagedAttention block manager (Kwon et al., SOSP '23) sitting where the
reference runtime would hold framework-external model state. What every
in-flight sequence keeps of its tokens lives in device-resident pools,
token-major: ``[layers, num_blocks, block_size, row]``, for keys and
values ``kv_heads * head_dim`` wide, a token's K (or V) of every head
in one row. A sequence owns an ordered list of
block ids (its *block table*) rather than a contiguous region.
Consequences:

  * admission/finish/preempt are allocator ops (list pushes), never
    device copies or compactions;
  * fragmentation is bounded at one partial block per sequence;
  * the pool NEVER overflows: ``alloc()`` returns None when empty and
    the engine preempts a victim (freeing its blocks for the requester)
    and recomputes it on resume — admission beyond capacity degrades
    throughput, not correctness (llm/engine.py).

Block 0 is reserved as scratch: padded decode lanes and padded block-
table slots point at it, so gather indices are always in range and
masked writes need no bounds branch. The allocator never hands it out.

Writes are functional jnp scatters under jit with the pool donated —
XLA aliases the buffers so steady-state decode does not copy the pool.
Both served programs make their own (a decode step its rows, a prefill
chunk its span through models/seam.py ``scatter_span``): no write is
dispatched from the host between them.
The shape is what lets it: the indexed dimensions (layer, block, offset)
are the major ones, which is how XLA's scatter wants them, and a row of
``kv_heads * head_dim`` is a whole number of 128-lane tiles at every
served width (768 = 6 x 128 at GPT-2-small), so the TPU runtime keeps
the array at rest row-major and unpadded and a write lands where it
is. The shape comes from the model's cache description (the serving
seam, models/seam.py): a KIND of layer says what a token leaves
there (``LayerKind.rows``), one pool an entry, ``[layers of the kind,
num_blocks, block_size, row width]``: keys and values are two pools of
``kv_heads * head_dim``, latent attention ONE pool of the token's
latent row (models/kimi_k2.py pads it to whole lane tiles itself, 576
-> 640, for the reason above). A cache holds its kind's pools as a
tuple (``PagedKVCache.pools``), the writers below write each of them,
and the programs are handed the tuple and hand it back: nothing here or
in the engine names a key or a value. A model whose
layers all keep every token has one kind (``PagedKVCache`` /
``PrefixPool``); a kind with a window keeps only
the blocks that cover a sequence's last ``window`` tokens in pools of
its own (``WindowPool``), so a lane holds two block tables. What a
SEQUENCE keeps whatever its length (a state-space layer's state, the
seam's ``Serving.state``) lives in pools of SLOTS beside them
(``StatePool``): a slot a live lane, parked snapshots that the prefix
index hands to later sequences. (Head-major, ``[..., kv_heads, num_blocks, block_size, head_dim]``
with a 64-wide minor dimension, the runtime kept it in a compact layout
that no reader or writer wanted, and every program converted the whole
pool there and back: PERF.md section 6, PR 31.) No program reads a
pool any other way than stored: every model's paged call copies its
pages out of the stacked pools itself (ops/pallas/paged_fetch.py).
"""

from __future__ import annotations

import collections
import functools
import itertools
import math
import time
from typing import Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..models import scatter_span, serving


# A device trace's ``XLA Modules`` line names each program after its
# function: ``jit_kv_scatter_blocks``, ``jit_kv_copy_block``. A served
# step runs the first no more (the chunk program writes its own span);
# it is ``write_prefill``'s, for tests and tools. One program a count
# of pools, the pools donated.
@functools.lru_cache(maxsize=None)
def _scatter_program(n: int):
    def kv_scatter_blocks(*args):
        """``scatter_span`` as a program of its own: ``(*pools, *spans,
        ids)``."""
        return scatter_span(args[:n], args[n:2 * n], args[2 * n])

    return jax.jit(kv_scatter_blocks, donate_argnums=tuple(range(n)))


@functools.lru_cache(maxsize=None)
def _copy_program(n: int):
    def kv_copy_block(*args):
        """Copy-on-write split: duplicate one block's rows in every
        pool, ``(*pools, src, dst)`` (src/dst are traced scalars, so
        every split shares one compile)."""
        src, dst = args[n:]
        return tuple(pool.at[:, dst].set(pool[:, src]) for pool in args[:n])

    return jax.jit(kv_copy_block, donate_argnums=tuple(range(n)))


# The programs of a kind of keys and values, under the names they have
# always had: ``(k_pool, v_pool, k, v, ids)`` and ``(k_pool, v_pool,
# src, dst)``.
kv_scatter_blocks = _scatter_program(2)
kv_copy_block = _copy_program(2)


class PagedKVCache:
    """The pool + its free-list allocator. Sequence bookkeeping (block
    tables, context lengths) belongs to the engine; this class owns the
    device arrays and which blocks are free."""

    def __init__(self, cfg, num_blocks: int = 64, block_size: int = 16,
                 dtype=None, kind: int = 0):
        if num_blocks < 2:
            raise ValueError("need >= 2 blocks (block 0 is reserved)")
        # Which of the model's kinds of layer this pool holds.
        self.kind = serving(cfg).kinds[kind]
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self.dtype = dtype if dtype is not None else self.kind.dtype
        # One pool an entry of the kind's ``rows``, in their order: what
        # the served programs are handed and hand back, as a tuple.
        self.pools = tuple(
            jnp.zeros((len(self.kind.layers), num_blocks, block_size, w),
                      self.dtype) for w in self.kind.rows)
        # LIFO free list (hot blocks rotate), block 0 reserved.
        self._free: List[int] = list(range(num_blocks - 1, 0, -1))

    # A kind of keys and values' two pools by name (tests and tools; the
    # engine hands ``pools`` over whole).
    k = property(lambda self: self.pools[0],
                 lambda self, x: self._set_pool(0, x))
    v = property(lambda self: self.pools[1],
                 lambda self, x: self._set_pool(1, x))

    def _set_pool(self, i: int, x):
        self.pools = self.pools[:i] + (x,) + self.pools[i + 1:]

    # -- allocator ---------------------------------------------------------

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def capacity(self) -> int:
        """Allocatable blocks (excludes the reserved scratch block)."""
        return self.num_blocks - 1

    def utilization(self) -> float:
        return 1.0 - self.num_free / max(1, self.capacity)

    def blocks_for_tokens(self, num_tokens: int) -> int:
        return max(1, math.ceil(num_tokens / self.block_size))

    def alloc(self, n: int) -> Optional[List[int]]:
        """n blocks, or None if the pool can't cover them (all-or-
        nothing: a partial grant would strand blocks on a sequence that
        cannot run)."""
        if n > len(self._free):
            return None
        grant = self._free[-n:][::-1]
        del self._free[-n:]
        return grant

    def free(self, blocks: List[int]):
        seen = set(self._free)
        for b in blocks:
            if b == 0:
                raise ValueError("block 0 is reserved, never allocated")
            if b in seen:
                # A duplicate on the list-based free stack would let the
                # allocator hand the same block to two sequences.
                raise ValueError(f"double free of KV block {b}")
            seen.add(b)
        self._free.extend(blocks)

    def truncate(self, table: List[int], keep_tokens: int) -> List[int]:
        """Trim ``table`` IN PLACE to the blocks covering
        ``keep_tokens`` resident tokens, returning the surplus block
        ids to the free list (speculative-decode rollback: rejected
        proposal slots past the accept cursor spilled into blocks the
        sequence no longer needs). Garbage K/V left inside the KEPT
        tail block is invisible — attention masks by context length and
        the next decode write overwrites slot by slot. On the prefix
        pool the surplus goes through release(): refcounts drop by one,
        so a shared or still-indexed block parks/unrefs instead of
        being clobbered on the free list. Returns the freed block
        ids."""
        nb = self.blocks_for_tokens(keep_tokens)
        if nb >= len(table):
            return []
        surplus = table[nb:]
        del table[nb:]
        self.free(surplus)
        return surplus

    # -- writes ------------------------------------------------------------

    def write_prefill(self, *spans_then_block_ids):
        """Scatter a prefill's rows into the pools (``scatter_span``, in
        a program of its own: a served chunk writes its span inside the
        chunk program instead): ``write_prefill(*spans, block_ids)``, a
        span a pool, each ``[L, T, ...]`` in the pool's own order (keys
        and values: ``[L, T, kv_heads, head_dim]`` twice). The tail of
        the last block is zero-padded (masked by context_lens at read
        time)."""
        *spans, block_ids = spans_then_block_ids
        if len(spans) != len(self.pools):
            raise ValueError(f"{len(self.pools)} pools, {len(spans)} spans")
        if len(block_ids) * self.block_size < spans[0].shape[1]:
            raise ValueError(f"{len(block_ids)} blocks cannot hold "
                             f"{spans[0].shape[1]} tokens")
        self.pools = _scatter_program(len(self.pools))(
            *self.pools, *spans, jnp.asarray(block_ids, jnp.int32))

    def gather_tokens(self, block_ids: List[int], length: int):
        """Read back ``length`` tokens' rows, ``[L, length, W_i]`` a
        pool (tests / debugging — the decode path never materializes
        this)."""
        ids = jnp.asarray(block_ids, jnp.int32)
        return tuple(
            jnp.take(pool, ids, axis=1).reshape(
                pool.shape[0], -1, pool.shape[3])[:, :length]
            for pool in self.pools)


_SLICES: Dict[int, List[slice]] = {}


def _block_slices(block_size: int, n: int) -> List[slice]:
    """``slice(i * block_size, (i + 1) * block_size)`` for i < n, from
    one list a block size that only grows."""
    made = _SLICES.setdefault(block_size, [])
    for i in range(len(made), n):
        made.append(slice(i * block_size, (i + 1) * block_size))
    return made[:n]


class BlockChain:
    """The chain of block keys of ONE token sequence that only grows (a
    request's prompt, then what it generates behind it): a whole block
    is ``(key, parent, chunk)`` with ``key = hash((parent, chunk))``,
    the first block's parent 0, and a ragged tail the same over the
    remainder: the prefix index's keys. Each whole block is hashed
    ONCE, when it is first reached: the engine makes a request's chain
    in ``add_request``, on the caller's thread, and every later
    ``match`` / ``admit`` / ``register`` / ``match_tail`` /
    ``register_tail`` of that request reads it (llm/engine.py); blocks
    of generated tokens join as they fill. The last ragged tail asked
    for is kept too (a prompt's is asked for again at its admission).

    What a chain KEEPS is two containers, whatever its length: the
    keys in one list and the hashed tokens in one tuple; a block's
    ``(key, parent, chunk)`` is made as it is walked and dropped with
    the step of the walk. A tuple or two a block kept for the request's
    life is what the collector counts: it read ~2,100 more live
    containers a 16.8k-token request and ran twice as long a second
    (PERF.md section 6, PR 46)."""

    __slots__ = ("block_size", "keys", "tokens", "_tail")

    def __init__(self, block_size: int, tokens=()):
        self.block_size = int(block_size)
        self.keys: List[int] = []       # a whole block's key, in order
        self.tokens: Tuple = ()         # the tokens of those blocks
        self._tail: Tuple = (0, None)   # (length, the tail's entry)
        if tokens:
            self.reach(tokens, len(tokens))

    def reach(self, seq, n: int) -> "BlockChain":
        """Hash the whole blocks of ``seq[:n]`` that are not hashed yet,
        and its ragged tail if it is not the one kept. ``seq`` must
        begin with the tokens this chain was made from."""
        bs, keys = self.block_size, self.keys
        nfull, have = n // bs, len(keys)
        if have < nfull:
            parent = keys[-1] if keys else 0
            span = tuple(seq[have * bs:nfull * bs])
            for i in range(0, len(span), bs):
                parent = hash((parent, span[i:i + bs]))
                keys.append(parent)
            self.tokens += span
        if n % bs and self._tail[0] != n:
            parent = keys[nfull - 1] if nfull else 0
            rem = tuple(seq[nfull * bs:n])
            self._tail = (n, (hash((parent, rem)), parent, rem))
        return self

    def block(self, i: int, n: int) -> Tuple:
        """``(key, parent, chunk)`` of block ``i`` of the first ``n``
        tokens (``reach``ed before): a whole block, or the ragged tail
        behind the last whole one."""
        bs, keys = self.block_size, self.keys
        if i < n // bs:
            return (keys[i], keys[i - 1] if i else 0,
                    self.tokens[i * bs:(i + 1) * bs])
        return self._tail[1]

    def blocks(self, seq, n: Optional[int] = None):
        """Iterate ``(key, parent, chunk)`` a block of ``seq[:n]`` (all
        of it by default), the ragged tail last; each is made as the
        walk comes to it."""
        n = len(seq) if n is None else n
        self.reach(seq, n)
        bs, keys = self.block_size, self.keys
        walk = zip(keys, itertools.chain((0,), keys),
                   map(self.tokens.__getitem__,
                       _block_slices(bs, n // bs)))
        return itertools.chain(walk, (self._tail[1],)) if n % bs else walk


class PrefixPool(PagedKVCache):
    """Ref-counted, hash-indexed prefix cache over the paged pool
    (vLLM-style automatic prefix caching, Kwon et al. SOSP '23).

    A sequence's tokens are split into block-sized chunks; each chunk
    is keyed by ``hash((parent_key, chunk_tokens))`` so equal prefixes
    of different requests chain to the SAME keys. The index maps a key
    to the pool block already holding that chunk's K/V:

      * ``admit()`` walks the chain, bumps the refcount of every hit
        block (prefill for that span is skipped entirely) and allocates
        fresh blocks for the remainder — all-or-nothing like ``alloc``;
      * ``release()`` registers the sequence's now-computed chunks and
        decrements refs; refcount-0 blocks with index keys park on an
        LRU list (still matchable — a hot system prompt survives
        across requests) instead of the free list;
      * allocation pressure evicts LRU parked blocks (dropping their
        keys) — referenced blocks are never evicted;
      * a shared block about to be written in a registered span (the
        partially-filled tail a new request diverges from, or a block
        with live co-readers) is split copy-on-write via ``cow()``.

    Index entries store the full (parent_key, chunk_tokens) and are
    verified on lookup, so hash collisions degrade to misses, never to
    wrong-content hits. The partial prompt tail is registered with its
    exact remainder as the chunk, so a tail hit is always the WHOLE
    remaining prompt (an unfinished-block hit mid-prompt would force a
    mid-block prefill start).

    Every state change (share, COW split, evict, register) emits into
    ``events`` — the I408 lint row holds these sites to it.
    """

    def __init__(self, cfg, num_blocks: int = 64, block_size: int = 16,
                 dtype=None, kind: int = 0):
        super().__init__(cfg, num_blocks=num_blocks,
                         block_size=block_size, dtype=dtype, kind=kind)
        self._ref: Dict[int, int] = {}        # bid -> live references
        self._keys_of: Dict[int, List[int]] = {}  # bid -> index keys
        # key -> (parent_key, chunk_tokens, bid, span)
        self._index: Dict[int, Tuple] = {}
        # ref-0 registered blocks, eviction order (oldest first).
        self._lru: "collections.OrderedDict[int, None]" = \
            collections.OrderedDict()
        # The last walk of a request's chain, (chain, tokens, bids,
        # covered): ``match`` then ``admit`` of one admission walk
        # once, as does a head-of-line request that waits a step. A hit
        # is the same chain object at the same length; dropped whenever
        # an eviction takes index keys away or a registration adds
        # them.
        self._matched: Optional[Tuple] = None
        # Blocks with more than one live reference, counted where a
        # refcount crosses 1 <-> 2 (the per-step gauge reads it).
        self._shared = 0
        self.events: Deque[tuple] = collections.deque(maxlen=4096)
        self.hit_tokens = 0
        self.lookup_tokens = 0
        self.evictions = 0
        self.cow_splits = 0
        self.registrations = 0

    def _event(self, kind: str, **attrs) -> None:
        self.events.append((time.time(), kind, attrs))

    # -- allocator overrides ----------------------------------------------

    @property
    def num_free(self) -> int:
        """Allocatable blocks: truly free + parked (evictable) cached
        blocks. Keeps the engine invariant 'everything returned after
        drain' meaningful while hot prefixes stay resident."""
        return len(self._free) + len(self._lru)

    def alloc(self, n: int) -> Optional[List[int]]:
        """n blocks (refcount 1 each), evicting LRU parked blocks as
        needed; None if free + evictable cannot cover them."""
        free = self._free
        if n == 1 and free:             # decode/COW fast path
            b = free.pop()
            self._ref[b] = 1
            return [b]
        if n > len(free) + len(self._lru):
            return None
        while len(free) < n:
            self._evict_one()
        grant = super().alloc(n)
        for b in grant:
            self._ref[b] = 1
        return grant

    def _evict_one(self) -> None:
        bid, _ = self._lru.popitem(last=False)
        for key in self._keys_of.pop(bid, ()):
            e = self._index.get(key)
            if e is not None and e[2] == bid:
                del self._index[key]
        self._free.append(bid)
        self._matched = None            # its chain may now be broken
        self.evictions += 1
        self._event("evict", block=bid)

    def free(self, blocks: List[int]):
        """Alias of release(): engine teardown paths call free() on
        either pool flavor."""
        self.release(blocks)

    # -- prefix index ------------------------------------------------------

    def _chain(self, seq, chain: Optional[BlockChain] = None,
               n: Optional[int] = None) -> BlockChain:
        """The chain of block keys of ``seq``, hashed as far as its
        first ``n`` tokens: the request's own where it has one (no
        block it holds is hashed again), made from the tokens
        otherwise."""
        if chain is None:
            chain = BlockChain(self.block_size)
        return chain.reach(seq, len(seq) if n is None else n)

    def match(self, seq: List[int],
              chain: Optional[BlockChain] = None) -> int:
        """Tokens of ``seq`` the index covers (nothing acquired)."""
        return self._match(seq, chain)[1]

    def _match(self, seq: List[int], chain: Optional[BlockChain] = None
               ) -> Tuple[List[int], int]:
        """Longest cached chain for ``seq``: (block ids, tokens
        covered). Full block-sized chunks must match contiguously; the
        ragged tail only matches as the exact whole remainder."""
        index = self._index
        hit = self._matched
        if hit is not None and hit[0] is chain and hit[1] == len(seq):
            return list(hit[2]), hit[3]
        bids: List[int] = []
        covered = 0
        for key, parent, chunk in self._chain(seq, chain).blocks(seq):
            e = index.get(key)
            if e is None or e[0] != parent or e[1] != chunk \
                    or e[3] != len(chunk):
                break
            bids.append(e[2])
            covered += len(chunk)
        if chain is not None:
            self._matched = (chain, len(seq), tuple(bids), covered)
        return bids, covered

    def admit(self, seq: List[int], need_tokens: int,
              upto: Optional[int] = None,
              chain: Optional[BlockChain] = None
              ) -> Optional[Tuple[List[int], int]]:
        """Build a block table for a sequence: cached-chain blocks are
        acquired (ref++), the remainder freshly allocated. Returns
        (block_table, cached_tokens) or None if the pool cannot cover
        the fresh remainder (nothing acquired in that case). ``upto``
        (a whole number of blocks below the match) cuts the match
        short: another kind of layer holds less of this prefix
        (``WindowPool.match_tail``)."""
        bids, cached = self._match(seq, chain)
        if upto is not None and upto < cached:
            bids, cached = bids[:upto // self.block_size], upto
        self.lookup_tokens += len(seq)
        ref, lru = self._ref, self._lru
        for b in bids:
            r = ref.get(b, 0)
            if r == 0:
                lru.pop(b, None)
            elif r == 1:
                self._shared += 1
            ref[b] = r + 1
        fresh_n = self.blocks_for_tokens(need_tokens) - len(bids)
        grant = self.alloc(fresh_n) if fresh_n else []
        if grant is None:
            self._unref(bids)
            return None
        self.hit_tokens += cached
        if bids:
            self._event("share", blocks=len(bids), tokens=cached)
        return bids + grant, cached

    def register(self, seq: List[int], table: List[int],
                 chain: Optional[BlockChain] = None) -> None:
        """Index a sequence's computed chunks so later requests can
        reuse them. First writer wins per key; blocks already indexed
        for this chain are left as-is."""
        index = self._index
        newly = 0
        # A table shorter than the chain indexes what it holds.
        for (key, parent, chunk), bid in zip(
                self._chain(seq, chain).blocks(seq), table):
            if key not in index:
                index[key] = (parent, chunk, bid, len(chunk))
                self._keys_of.setdefault(bid, []).append(key)
                newly += 1
        if newly:
            self.registrations += newly
            self._matched = None       # a longer chain may now match
            self._event("register", blocks=newly, tokens=len(seq))

    def release(self, blocks: List[int],
                seq: Optional[List[int]] = None,
                chain: Optional[BlockChain] = None) -> None:
        """Drop one reference per block. ``seq`` (the tokens actually
        resident — prompt + generated, truncated to context_len)
        registers the now-computed chunks first, so multi-turn
        continuations and re-admissions hit them."""
        if seq:
            self.register(seq, blocks, chain)
        self._unref(blocks)

    def _unref(self, blocks: List[int]) -> None:
        ref, keys_of = self._ref, self._keys_of
        lru, free = self._lru, self._free
        for b in blocks:
            r = ref.get(b, 0)
            if r <= 0:
                raise ValueError(f"double free of KV block {b}")
            ref[b] = r - 1
            if r == 2:
                self._shared -= 1
            elif r == 1:
                if keys_of.get(b):
                    lru[b] = None           # parked, matchable, evictable
                else:
                    free.append(b)

    # -- copy-on-write -----------------------------------------------------

    def needs_cow(self, bid: int, offset: int) -> bool:
        """Must a write at ``offset`` of ``bid`` go to a private copy?
        Yes if the block has co-readers, or the write falls inside a
        registered span (index entries are immutable content — a
        later matcher must find exactly what was registered)."""
        if self._ref.get(bid, 0) > 1:
            return True
        spans = [self._index[k][3] for k in self._keys_of.get(bid, ())
                 if k in self._index]
        return bool(spans) and offset < max(spans)

    def cow(self, bid: int) -> Optional[int]:
        """Split: allocate a private copy of ``bid`` (device block
        copy), drop the caller's ref on the original. Returns the new
        block id, or None if the pool can't grant one (caller preempts
        and retries)."""
        grant = self.alloc(1)
        if grant is None:
            return None
        dst = grant[0]
        self.pools = _copy_program(len(self.pools))(
            *self.pools, jnp.asarray(bid, jnp.int32),
            jnp.asarray(dst, jnp.int32))
        self.cow_splits += 1
        self._event("cow", src=bid, dst=dst,
                    refs=self._ref.get(bid, 0))
        self._unref([bid])
        return dst

    # -- introspection -----------------------------------------------------

    def hit_rate(self) -> float:
        return self.hit_tokens / max(1, self.lookup_tokens)

    def shared_blocks(self) -> int:
        """Blocks more than one sequence holds: a count kept where a
        refcount crosses 1 <-> 2 (``admit``, ``_unref``,
        ``WindowPool.acquire``), not a walk of every live block."""
        return self._shared

    def prefix_stats(self) -> dict:
        return {
            "hit_tokens": self.hit_tokens,
            "lookup_tokens": self.lookup_tokens,
            "hit_rate": self.hit_rate(),
            "evictions": self.evictions,
            "cow_splits": self.cow_splits,
            "registrations": self.registrations,
            "shared_blocks": self.shared_blocks(),
            "cached_blocks": len(self._lru),
        }


class WindowPool(PrefixPool):
    """The pool of a kind of layer with a window: a sequence keeps only
    the blocks that cover its last ``window`` tokens, so its table here
    is ``(first, blocks)``: ``blocks[i]`` holds the sequence's block
    ``first + i``, and blocks before ``first`` have slid out and gone
    back to the pool. Keys are stored after rotary, so a block's
    content does not depend on where in a table it is read.

    What a lane keeps (``keep_from``): the blocks a query at its next
    position sees, and one block's worth of positions more, so that a
    sequence released at ``n`` tokens can be taken up again at the
    block boundary below ``n`` (preempt and resume). That is at most
    ``window / block_size + 2`` blocks a lane (``table_len``).

    The prefix index is the ``PrefixPool``'s, under the same chain
    keys, with another answer to "how much of this prefix is cached":
    a prefix of n tokens can be taken up only if the blocks covering
    the window behind position n are all still held (``match_tail``).
    Tails are indexed when a sequence is released (``release``), so a
    prefix is reusable at the ends of earlier sequences: a shared
    context that was sent alone, a conversation's last turn, a
    preempted lane. Blocks that slide out of a live lane's window
    while indexed park on the LRU list like any released block.
    """

    def __init__(self, cfg, num_blocks: int = 64, block_size: int = 16,
                 dtype=None, kind: int = 1):
        super().__init__(cfg, num_blocks=num_blocks,
                         block_size=block_size, dtype=dtype, kind=kind)
        if self.kind.window is None:
            raise ValueError(f"kind {self.kind.name!r} has no window")
        self.window = int(self.kind.window)
        self.slid_blocks = 0        # blocks that left a live window
        # Parked blocks that no later sequence has taken up yet, oldest
        # first: the first to be evicted. A tail that was matched once
        # (a shared context's) outlives the tails of finished requests,
        # which park 30-odd blocks each and are mostly never asked for
        # again: under plain LRU they pushed the contexts' tails out
        # between two requests of a context, and an 8,192-token prefix
        # was computed anew (PERF.md section 6, PR 32).
        self._cold: "collections.OrderedDict[int, None]" = \
            collections.OrderedDict()
        self._taken: set = set()    # blocks some sequence took up

    def keep_from(self, position: int) -> int:
        """First block a lane keeps when its next query sits at
        ``position``: the oldest block that query sees, less one
        block's worth of positions (class docstring)."""
        return max(0, position - self.window + 1 - self.block_size) \
            // self.block_size

    def slide(self, table: List[int], first: int, position: int) -> int:
        """Drop (IN PLACE) the blocks of ``table`` that a lane whose
        next query sits at ``position`` no longer keeps; returns the
        new ``first``."""
        n = min(self.keep_from(position) - first, len(table))
        if n <= 0:
            return first
        self._unref(table[:n])
        del table[:n]
        self.slid_blocks += n
        return first + n

    # -- prefix index ------------------------------------------------------

    def match_tail(self, seq: List[int], cached: int,
                   chain: Optional[BlockChain] = None
                   ) -> Tuple[int, int, List[int]]:
        """The longest prefix of ``seq``, at most ``cached`` tokens (the
        full kind's match: whole blocks, or all of ``seq``), whose
        window tail is held here: ``(n, first, blocks)``, the blocks
        covering the window behind position n (not acquired:
        ``acquire``), or ``(0, 0, [])``. Below ``cached`` only block
        boundaries are tried."""
        bs, index = self.block_size, self._index
        chain = self._chain(seq, chain, cached)
        n = cached
        while n > 0:
            last = -(-n // bs) - 1
            first = max(0, n - self.window) // bs
            bids = []
            for i in range(last, first - 1, -1):
                key, parent, chunk = chain.block(i, cached)
                e = index.get(key)
                if e is None or e[0] != parent or e[1] != chunk \
                        or e[3] != len(chunk):
                    break
                bids.append(e[2])
            else:
                return n, first, bids[::-1]
            n = (n - 1) // bs * bs
        return 0, 0, []

    def _unref(self, blocks: List[int]) -> None:
        super()._unref(blocks)
        for b in blocks:
            if b in self._lru and b not in self._taken:
                self._cold[b] = None

    def alloc(self, n: int) -> Optional[List[int]]:
        grant = super().alloc(n)
        for b in grant or ():
            self._taken.discard(b)      # a new life for the block
        return grant

    def _evict_one(self) -> None:
        """Never-taken parked blocks go first, oldest first; then the
        PrefixPool's order."""
        if self._cold:
            bid, _ = self._cold.popitem(last=False)
            self._lru.move_to_end(bid, last=False)
        super()._evict_one()

    def acquire(self, blocks: List[int]) -> None:
        """One reference more on each of ``blocks`` (a matched tail)."""
        for b in blocks:
            r = self._ref.get(b, 0)
            if r == 0:
                self._lru.pop(b, None)
                self._cold.pop(b, None)
            elif r == 1:
                self._shared += 1
            self._taken.add(b)
            self._ref[b] = r + 1
        if blocks:
            self._event("share", blocks=len(blocks),
                        tokens=len(blocks) * self.block_size)

    def register_tail(self, seq: List[int], table: List[int],
                      first: int,
                      chain: Optional[BlockChain] = None) -> None:
        """Index the blocks of ``table`` (the sequence's blocks from
        ``first`` on) that ``seq`` fills. First writer wins a key."""
        chain = self._chain(seq, chain)
        newly = 0
        n = len(seq)
        for i in range(first, min(-(-n // self.block_size),
                                  first + len(table))):
            key, parent, chunk = chain.block(i, n)
            if key not in self._index:
                bid = table[i - first]
                self._index[key] = (parent, chunk, bid, len(chunk))
                self._keys_of.setdefault(bid, []).append(key)
                newly += 1
        if newly:
            self.registrations += newly
            self._event("register", blocks=newly, tokens=len(seq))

    def release(self, blocks: List[int], seq: Optional[List[int]] = None,
                first: int = 0,
                chain: Optional[BlockChain] = None) -> None:
        """Drop one reference a block; ``seq`` (the resident tokens)
        indexes the tail first."""
        if seq:
            self.register_tail(seq, blocks, first, chain)
        self._unref(blocks)

    def truncate(self, table: List[int], keep_tokens: int,
                 first: int = 0) -> List[int]:
        """``PagedKVCache.truncate`` for a table that starts at the
        sequence's block ``first``."""
        nb = max(self.blocks_for_tokens(keep_tokens) - first, 0)
        if nb >= len(table):
            return []
        surplus = table[nb:]
        del table[nb:]
        self.free(surplus)
        return surplus


@functools.lru_cache(maxsize=None)
def _slot_copy_program(n: int):
    def state_copy_slot(*args):
        """A snapshot: one slot's state, every layer and part, copied to
        another, ``(*pools, src, dst)`` (traced scalars: one compile).
        ``jit_state_copy_slot`` on a device trace."""
        src, dst = args[n:]
        return tuple(pool.at[:, dst].set(pool[:, src]) for pool in args[:n])

    return jax.jit(state_copy_slot, donate_argnums=tuple(range(n)))


class StatePool:
    """Slots of what a sequence keeps in the layers that carry a state
    (``Serving.state``): one device pool a part, ``[layers, slots,
    *part]``, and who holds which slot. Slot 0 is scratch, as block 0
    is: padded decode lanes point at it and it is never handed out.

    A slot is FREE, LIVE (a lane's: the decode program moves it in
    place every step, a prefill span leaves its final state there) or
    PARKED: a snapshot of a sequence's state AT a block boundary,
    indexed under that block's chain key (``BlockChain``, the prefix
    index's keys), which a later sequence with the same prefix can
    start from. A snapshot is never written again; the sequence that
    takes it up reads it in its first span and writes its own slot, so
    any number can share one. ``match`` gives the prefix index its
    second answer: of the tokens the paged pools still hold, the
    longest prefix that ALSO has a snapshot; what was matched beyond it
    is computed again.

    Parked snapshots are evicted for a grant, those that no sequence
    ever took up first, oldest first (``WindowPool._cold``'s order and
    its reason: a finished prompt's snapshot is mostly never asked for
    again, a shared prefix's is asked for by every request), then the
    taken-up ones, least recently taken first. A snapshot that a
    request has matched and not yet read is held (``hold``) and not
    evicted. Eviction costs recomputation, never correctness."""

    def __init__(self, cfg, state_slots: int):
        kind = serving(cfg).state
        if kind is None:
            raise ValueError("the model's sequences keep no state")
        if state_slots < 2:
            raise ValueError("need >= 2 state slots (slot 0 is reserved)")
        self.num_slots = int(state_slots)
        self.pools = tuple(
            jnp.zeros((len(kind.layers), state_slots, *shape), dtype)
            for shape, dtype in kind.parts)
        self._free: List[int] = list(range(state_slots - 1, 0, -1))
        self._live: set = set()
        self._index: Dict[int, int] = {}            # chain key -> slot
        self._parked: Dict[int, Tuple[int, int]] = {}   # slot -> (key, n)
        self._cold: "collections.OrderedDict[int, None]" = \
            collections.OrderedDict()               # parked, never taken
        self._warm: "collections.OrderedDict[int, None]" = \
            collections.OrderedDict()               # parked, taken up
        self._held: Dict[int, int] = {}             # slot -> readers to come
        # A slot's index as a device scalar, made once: a snapshot's
        # copy then hands no host value over (as the engine's lanes').
        self._ids = list(jnp.arange(state_slots, dtype=jnp.int32))
        self.snapshots = 0          # taken, all time
        self.taken = 0              # times a snapshot was taken up
        self.evicted = 0
        self.resumed_tokens = 0     # matched tokens a snapshot let skip
        self.recomputed_tokens = 0  # matched tokens computed again
        self.live_peak = 0.0

    @property
    def capacity(self) -> int:
        return self.num_slots - 1

    def utilization(self) -> float:
        """Share of the slots that lanes hold or that were taken up."""
        return (len(self._live) + len(self._warm)) / max(1, self.capacity)

    # -- slots -------------------------------------------------------------

    def _evict_one(self) -> bool:
        for parked in (self._cold, self._warm):
            for slot in parked:
                if slot not in self._held:
                    del parked[slot]
                    del self._index[self._parked.pop(slot)[0]]
                    self._free.append(slot)
                    self.evicted += 1
                    return True
        return False

    def grant(self) -> Optional[int]:
        """A slot for a lane, a parked snapshot evicted for it if need
        be; None if every slot is live or held."""
        if not self._free and not self._evict_one():
            return None
        slot = self._free.pop()
        self._live.add(slot)
        self.live_peak = max(self.live_peak, self.utilization())
        return slot

    def give_back(self, slot: int) -> None:
        if slot not in self._live:
            raise ValueError(f"state slot {slot} is not a lane's")
        self._live.remove(slot)
        self._free.append(slot)

    # -- the prefix index's second answer ------------------------------------

    def match(self, chain: Optional[BlockChain], cached: int,
              limit: int) -> Tuple[int, Optional[int]]:
        """The longest prefix, at most ``min(cached, limit)`` tokens and
        whole blocks, at whose end a snapshot is parked: ``(tokens, its
        slot)`` or ``(0, None)``. ``cached`` is what the paged pools
        match of the sequence (its blocks were verified against the
        chain there, and a snapshot's key is its last block's);
        ``limit`` keeps at least one token for the sequence to
        compute."""
        if chain is None:
            return 0, None
        keys, index = chain.keys, self._index
        for i in range(min(min(cached, limit) // chain.block_size,
                           len(keys)), 0, -1):
            slot = index.get(keys[i - 1])
            if slot is not None and self._parked[slot][1] \
                    == i * chain.block_size:
                return i * chain.block_size, slot
        return 0, None

    def hold(self, slot: int) -> None:
        """A sequence will start from this snapshot: it stays until the
        sequence's first span has read it (``read``)."""
        self._held[slot] = self._held.get(slot, 0) + 1

    def read(self, slot: int) -> None:
        """The program that reads the snapshot is dispatched (or its
        sequence gave up): one hold less."""
        left = self._held[slot] - 1
        if left:
            self._held[slot] = left
        else:
            del self._held[slot]

    def take_up(self, slot: Optional[int], tokens: int, cached: int) -> None:
        """Count an admission: ``tokens`` resumed from the snapshot in
        ``slot`` (none: 0) of ``cached`` that the paged pools matched.
        A snapshot taken up outlives those that never were."""
        self.resumed_tokens += tokens
        self.recomputed_tokens += max(cached - tokens, 0)
        if slot is None:
            return
        self._cold.pop(slot, None)
        self._warm[slot] = None
        self._warm.move_to_end(slot)
        self.taken += 1
        self.live_peak = max(self.live_peak, self.utilization())

    def snapshot(self, key: int, tokens: int, src: int) -> Optional[int]:
        """Park a copy of the live slot ``src``, the state of a sequence
        at ``tokens`` tokens whose last block has chain key ``key``:
        one device copy, dispatched here behind the program that left
        the state there. Returns the snapshot's slot, or None where one
        is indexed already or no slot can be had."""
        if key in self._index or (not self._free and not self._evict_one()):
            return None
        dst = self._free.pop()
        self.pools = _slot_copy_program(len(self.pools))(
            *self.pools, self._ids[src], self._ids[dst])
        self._index[key] = dst
        self._parked[dst] = (key, tokens)
        self._cold[dst] = None
        self.snapshots += 1
        return dst

    # -- introspection -------------------------------------------------------

    def stats(self) -> dict:
        return {
            "state_slots": self.capacity,
            "state_slots_live": len(self._live),
            "state_snapshots_parked": len(self._parked),
            "state_snapshots_taken_up": len(self._warm),
            "state_snapshots": self.snapshots,
            "state_taken": self.taken,
            "state_evicted": self.evicted,
            "state_resumed_tokens": self.resumed_tokens,
            "state_recomputed_tokens": self.recomputed_tokens,
            "state_live_peak": self.live_peak,
        }
