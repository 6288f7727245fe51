"""Paged decode-attention as a Pallas TPU kernel (PagedAttention,
Kwon et al., SOSP '23 — the TPU-native analogue).

Decode-time attention for continuous batching: each sequence's KV lives
in fixed-size blocks scattered across a device-resident pool
(llm/kv_cache.py), named by a per-sequence *block table*. The kernel
gathers K/V blocks THROUGH the table — the pool is never compacted, so
admitting/finishing/preempting sequences costs allocator bookkeeping,
not device copies.

Mechanics: the block tables and context lengths ride in as
scalar-prefetch operands (``pltpu.PrefetchScalarGridSpec``), so a K/V
BlockSpec index map can address ``k_pool[:, table[b, j]]`` before the
step's DMA is issued — the gather happens in the pipeline's index
computation, not as a materialized reorder.

How the work is cut: the grid is ``(batch, compute_blocks)`` and one
grid step folds ALL KV heads of a lane over ``pages`` table slots at
once. A Pallas block can only name one run of pages and a lane's pages
are scattered, so the pool operand is passed ``pages`` times for K and
``pages`` times for V, each with a window of one page
``(kv_heads, block_size, head_dim)`` — one strided transfer moves that
page of every head — and an index map of its own slot. The body joins
the windows into one ``(kv_heads, pages * block_size, head_dim)`` block:
two batched matmuls and one online-softmax update a step, the running
max / denominator / accumulator per head in VMEM scratch across the
lane's steps, the output written at the last. XLA passes one buffer
for all the windows: the custom call takes one layer's pool head-major,
``[kv_heads, num_blocks, block_size, head_dim]``, and is still one
call. The pool at rest is token-major (llm/kv_cache.py: ``[layers,
num_blocks, block_size, kv_heads * head_dim]``); models/gpt.py makes
this operand from it once a layer, each head's lanes of every row, the
decode step's one pass over a layer's pool. (The kernel cannot copy pages
itself with ``make_async_copy`` from a ``pl.ANY`` pool: Mosaic in jax
0.9.0 sees a pool whose minor dimension is 64 padded to 128 lanes in
HBM and refuses any slice of it, "must be aligned to tiling (128)".)

Nothing is fetched or computed past a lane's context. The pipeline
copies a window only when its block index changes, so the index maps
make every dead slot repeat an index: a step past the lane's last live
compute block names that block again, a slot past the last live slot
names the page the window held one block earlier. Such steps skip the
body (``pl.when``); the ragged tail inside the last live block is
masked. A padded lane (context 1, table of zeros) costs the scratch
page once.

``pages`` follows from the shapes (``_pages_per_block``): the largest
power of two for which the K and V windows of all KV heads (double-
buffered by the pipeline, joined once more in the body, the minor
dimension padded to 128 lanes) and the block's f32 scores fit
``_VMEM_BUDGET`` = 6 MiB, and never more than the table has. 12 heads
x 64 at block 16 in bf16: 16 pages, 256 keys a step; the table need not
be a whole number of compute blocks.

GQA is native here (unlike the training kernel, which expands KV): query
heads arrive grouped per KV head as [batch, kv_heads, group, head_dim],
so the pool stores only ``kv_heads`` copies and a grid step's q block
is every head's whole group — no repeat, no extra HBM.

``interpret=None`` auto-selects interpreter mode off-TPU so tier-1 runs
the SAME kernel under ``JAX_PLATFORMS=cpu`` (the e2e serving tests and
the numerics test against the dense reference both go through here).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..attention import NEG_INF


def interpret_default() -> bool:
    """The Pallas interpreter runs the kernel only where Mosaic cannot:
    on the CPU backend (tests). On a TPU the kernel is always compiled."""
    return jax.default_backend() == "cpu"


# VMEM one grid step's pages may take: K and V of every KV head, each
# page double-buffered by the pipeline and gathered once more into the
# compute block, plus the block's f32 scores. v5e's scoped default is
# 16 MiB; the rest stays with the q/out blocks and the compiler.
_VMEM_BUDGET = 6 * 1024 * 1024


def _pages_per_block(hkv: int, rows: int, d: int, block_size: int,
                     max_nb: int, itemsize: int) -> int:
    """Pages one grid step carries: the largest power of two whose K
    and V pages for all KV heads, with the scores they give, fit
    ``_VMEM_BUDGET``; never more than a table has."""
    lanes = -(-d // 128) * 128          # VMEM pads the minor dim
    kv = 2 * 3 * hkv * block_size * lanes * itemsize
    scores = 3 * hkv * (-(-rows // 8) * 8) * block_size * 4
    fit = max(1, min(_VMEM_BUDGET // (kv + scores), max_nb))
    return 1 << (fit.bit_length() - 1)


def _decode_kernel(tables_ref, lens_ref, qlens_ref, q_ref, *refs,
                   block_size: int, pages: int, n_blocks: int,
                   scale: float, group: int):
    """One grid step: fold compute block ``blk`` of lane ``b`` — all KV
    heads, ``pages`` table slots — into the online softmax. The index
    maps already resolved the slots to pool pages, so ``refs`` opens
    with ``pages`` K pages then ``pages`` V pages, each
    ``(kv_heads, block_size, d)``; this body joins them, masks and
    accumulates. A block wholly past the lane's context is skipped.

    Generalized to ``q_len`` query rows per sequence (speculative
    verify): the q block is the flattened [q_len * group, d] span per KV
    head, row ``r`` belonging to query token ``r // group`` at absolute
    position ``ctx - q_lens[b] + r // group`` — causal within the span,
    so each query sees the resident context plus the speculative tokens
    at or before itself. Lanes with fewer than q_len real rows (short
    proposals, batch padding) clamp to the plain context mask; their
    rows are well-defined garbage the engine never reads."""
    k_refs, v_refs = refs[:pages], refs[pages:2 * pages]
    o_ref, m_ref, l_ref, acc_ref = refs[2 * pages:]
    b = pl.program_id(0)
    blk = pl.program_id(1)
    span = pages * block_size
    ctx = lens_ref[b]
    qn = qlens_ref[b]

    @pl.when(blk == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(blk * span < ctx)
    def _fold():
        q = q_ref[0]                             # (hkv, q_len*group, d)
        k_blk = jnp.concatenate([r[...] for r in k_refs], axis=1)
        v_blk = jnp.concatenate([r[...] for r in v_refs], axis=1)
        s = jax.lax.dot_general(
            q, k_blk, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale  # (hkv, rows, span)
        # Key positions beyond the context are masked: the ragged tail
        # of the last live page and the block's slots past it (they hold
        # some live page of the lane again, see the index maps). With
        # q_len > 1 the bound is additionally causal per query row:
        # query i's last visible key is its own write slot ctx - qn + i.
        k_pos = blk * span + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 2)
        qi = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) // group
        bound = jnp.minimum(ctx, ctx - qn + 1 + qi)
        s = jnp.where(k_pos < bound, s, NEG_INF)

        m, l, acc = m_ref[...], l_ref[...], acc_ref[...]
        m_new = jnp.maximum(m, s.max(-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        m_ref[...] = m_new
        l_ref[...] = l * corr + p.sum(-1, keepdims=True)
        acc_ref[...] = acc * corr + jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)

    @pl.when(blk == n_blocks - 1)
    def _write():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.lru_cache(maxsize=None)
def _make_decode_call(b: int, hkv: int, group: int, d: int,
                      num_blocks: int, block_size: int, max_nb: int,
                      q_dtype, p_dtype, interpret: bool, q_len: int = 1):
    scale = d ** -0.5
    rows = q_len * group
    pages = _pages_per_block(hkv, rows, d, block_size, max_nb,
                             jnp.dtype(p_dtype).itemsize)
    n_blocks = pl.cdiv(max_nb, pages)

    def page(i):
        """The paged gather: the pool page under slot ``i`` of compute
        block ``blk``. A step past the lane's last live block names that
        block again, and a slot past its last live slot the page this
        operand held a block earlier (in the lane's first block: the
        last live page), so the pipeline, which copies a block only
        when its index changes, fetches nothing past the context. Every
        slot read is live, so a table's padding is never followed."""
        def index(bi, blk, tables, lens, qlens):
            last = jnp.maximum(lens[bi] - 1, 0) // block_size
            blk = jnp.minimum(blk, last // pages)
            j = blk * pages + i
            j = jnp.where(j <= last, j,
                          jnp.where(blk > 0, j - pages, last))
            return (0, tables[bi, j], 0, 0)
        return pl.BlockSpec((hkv, None, block_size, d), index)

    lane = pl.BlockSpec((1, hkv, rows, d),
                        lambda bi, blk, tables, lens, qlens: (bi, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,   # block tables + context lens + q lens
        grid=(b, n_blocks),
        in_specs=[lane] + 2 * [page(i) for i in range(pages)],
        out_specs=lane,
        scratch_shapes=[
            pltpu.VMEM((hkv, rows, 1), jnp.float32),   # running max
            pltpu.VMEM((hkv, rows, 1), jnp.float32),   # denominator
            pltpu.VMEM((hkv, rows, d), jnp.float32),   # accumulator
        ],
    )
    call = pl.pallas_call(
        functools.partial(_decode_kernel, block_size=block_size,
                          pages=pages, n_blocks=n_blocks, scale=scale,
                          group=group),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, rows, d), q_dtype),
        interpret=interpret, name="paged_decode",
    )
    # One pool, ``pages`` windows onto it: the same operand per slot.
    return lambda tables, lens, qlens, q, k_pool, v_pool: call(
        tables, lens, qlens, q, *pages * [k_pool], *pages * [v_pool])


# The wrappers below stand where they stood before PR 42, which took the
# two kernels that read a pool as stored (308 lines, from here on) to
# paged_fetch.py. Their line numbers are part of the compiled program: a
# Mosaic kernel's serialized body carries the file and line of every
# frame that led to its ``pallas_call`` (``paged_verify_attention``'s
# ``call(...)`` among them), jax's compile cache hashes that body, and
# the serving benchmark's chat driver does not survive a set-up that
# compiles anew (ROADMAP.md A7: the proxy answers 504 after 60 s). So
# the lines between stay empty until A1b deletes this file whole.











































































































































































































































































































def paged_decode_attention(q, k_pool, v_pool, block_tables, context_lens,
                           *, interpret: bool | None = None):
    """Single-token attention over block-paged KV.

    Args:
      q: ``[batch, kv_heads, group, head_dim]`` — one query token per
        sequence, query heads grouped by the KV head they read
        (``group = n_head // kv_heads``; 1 for plain MHA... reshape a
        ``[batch, n_head, head_dim]`` query with ``.reshape(b, hkv,
        group, d)``, which matches the ``jnp.repeat`` GQA convention).
      k_pool / v_pool: ``[kv_heads, num_blocks, block_size, head_dim]``
        — ONE layer's slice of the paged pool.
      block_tables: ``[batch, max_blocks_per_seq]`` int32 — pool block
        ids per sequence, padded with 0 (the reserved scratch block).
      context_lens: ``[batch]`` int32 — tokens in cache per sequence,
        INCLUDING the current token (which must already be written to
        its slot: decode writes K/V first, then attends, so the token
        sees itself).

    Returns ``[batch, kv_heads, group, head_dim]`` in q's dtype.
    """
    if interpret is None:
        interpret = interpret_default()
    b, hkv, group, d = q.shape
    hkv_p, num_blocks, block_size, d_p = k_pool.shape
    if (hkv_p, d_p) != (hkv, d):
        raise ValueError(
            f"pool heads/dim {(hkv_p, d_p)} != query {(hkv, d)}")
    max_nb = block_tables.shape[1]
    call = _make_decode_call(b, hkv, group, d, num_blocks, block_size,
                             max_nb, q.dtype, k_pool.dtype, interpret)
    ones = jnp.ones((b,), jnp.int32)     # q_len 1: plain context mask
    return call(block_tables.astype(jnp.int32),
                context_lens.astype(jnp.int32), ones, q, k_pool, v_pool)


def paged_verify_attention(q, k_pool, v_pool, block_tables, context_lens,
                           q_lens, *, interpret: bool | None = None):
    """Multi-row (speculative verify) attention over block-paged KV.

    Same kernel as paged_decode_attention, generalized to ``q_len``
    query tokens per sequence in one pass — the verify step scores the
    current token plus k proposals without k extra dispatches.

    Args:
      q: ``[batch, q_len, kv_heads, group, head_dim]`` — query row j of
        lane b sits at absolute position
        ``context_lens[b] - q_lens[b] + j`` (write-then-attend: all
        ``q_lens[b]`` real rows' K/V are already in their slots).
      context_lens: ``[batch]`` int32 — resident tokens per sequence
        INCLUDING this step's ``q_lens[b]`` real rows.
      q_lens: ``[batch]`` int32 — real query rows per lane (1..q_len).
        Rows beyond ``q_lens[b]`` are padding: they attend the full
        context (mask clamped) and produce defined garbage the caller
        must not read.

    Returns ``[batch, q_len, kv_heads, group, head_dim]`` in q's dtype.
    """
    if interpret is None:
        interpret = interpret_default()
    b, q_len, hkv, group, d = q.shape
    hkv_p, num_blocks, block_size, d_p = k_pool.shape
    if (hkv_p, d_p) != (hkv, d):
        raise ValueError(
            f"pool heads/dim {(hkv_p, d_p)} != query {(hkv, d)}")
    max_nb = block_tables.shape[1]
    call = _make_decode_call(b, hkv, group, d, num_blocks, block_size,
                             max_nb, q.dtype, k_pool.dtype, interpret,
                             q_len)
    # Kernel row layout: [q_len, group] flattened, so row r is query
    # token r // group of the lane.
    qf = q.transpose(0, 2, 1, 3, 4).reshape(b, hkv, q_len * group, d)
    out = call(block_tables.astype(jnp.int32),
               context_lens.astype(jnp.int32),
               q_lens.astype(jnp.int32), qf, k_pool, v_pool)
    return out.reshape(b, hkv, q_len, group, d).transpose(0, 2, 1, 3, 4)


def paged_decode_attention_reference(q, k_pool, v_pool, block_tables,
                                     context_lens):
    """Pure-jnp ground truth: materialize the gather, run dense masked
    softmax attention. O(batch × max_ctx) memory — tests only."""
    b, hkv, group, d = q.shape
    _, _, block_size, _ = k_pool.shape
    max_nb = block_tables.shape[1]
    # [b, hkv, max_nb*bs, d] — gather each sequence's blocks.
    k = jnp.take(k_pool, block_tables, axis=1)   # [hkv, b, max_nb, bs, d]
    v = jnp.take(v_pool, block_tables, axis=1)
    k = k.transpose(1, 0, 2, 3, 4).reshape(b, hkv, max_nb * block_size, d)
    v = v.transpose(1, 0, 2, 3, 4).reshape(b, hkv, max_nb * block_size, d)
    s = jnp.einsum("bhgd,bhkd->bhgk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * (d ** -0.5)
    k_pos = jnp.arange(max_nb * block_size)[None, None, None, :]
    s = jnp.where(k_pos < context_lens[:, None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhgk,bhkd->bhgd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


def paged_verify_attention_reference(q, k_pool, v_pool, block_tables,
                                     context_lens, q_lens):
    """Pure-jnp ground truth for the q_len>1 verify pass: same gather
    as the decode reference, per-row causal bound
    ``min(ctx, ctx - q_lens + 1 + row)``. Tests only."""
    b, q_len, hkv, group, d = q.shape
    _, _, block_size, _ = k_pool.shape
    max_nb = block_tables.shape[1]
    k = jnp.take(k_pool, block_tables, axis=1)
    v = jnp.take(v_pool, block_tables, axis=1)
    k = k.transpose(1, 0, 2, 3, 4).reshape(b, hkv, max_nb * block_size, d)
    v = v.transpose(1, 0, 2, 3, 4).reshape(b, hkv, max_nb * block_size, d)
    s = jnp.einsum("bqhgd,bhkd->bhqgk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * (d ** -0.5)
    k_pos = jnp.arange(max_nb * block_size)[None, None, None, None, :]
    ctx = context_lens[:, None, None, None, None]
    qi = jnp.arange(q_len)[None, None, :, None, None]
    bound = jnp.minimum(ctx, ctx - q_lens[:, None, None, None, None]
                        + 1 + qi)
    s = jnp.where(k_pos < bound, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqgk,bhkd->bqhgd", p,
                      v.astype(jnp.float32)).astype(q.dtype)
