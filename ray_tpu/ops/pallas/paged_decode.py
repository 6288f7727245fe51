"""Paged decode-attention as a Pallas TPU kernel (PagedAttention,
Kwon et al., SOSP '23 — the TPU-native analogue).

Decode-time attention for continuous batching: each sequence's KV lives
in fixed-size blocks scattered across a device-resident pool
(llm/kv_cache.py), named by a per-sequence *block table*. The kernel
gathers K/V blocks THROUGH the table — the pool is never compacted, so
admitting/finishing/preempting sequences costs allocator bookkeeping,
not device copies.

Mechanics: the block tables and context lengths ride in as
scalar-prefetch operands (``pltpu.PrefetchScalarGridSpec``), so the K/V
BlockSpec index maps can address ``k_pool[head, table[b, j]]`` before
each grid step's DMA is issued — the gather happens in the pipeline's
index computation, not as a materialized reorder. Grid is
``(batch, kv_head, blocks_per_seq)`` with the block dimension innermost:
TPU grid steps execute sequentially, so the running online-softmax state
(max / denominator / accumulator) carries across key blocks in VMEM
scratch and the output is written once at the last block, exactly like
the training flash kernel's inner loop (ops/pallas/flash.py) unrolled
onto the grid.

GQA is native here (unlike the training kernel, which expands KV): query
heads arrive grouped per KV head as [batch, kv_heads, group, head_dim],
so the pool stores only ``kv_heads`` copies and each grid step's q block
is the whole group — no repeat, no extra HBM.

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


def _decode_kernel(tables_ref, lens_ref, qlens_ref, q_ref, k_ref, v_ref,
                   o_ref, m_ref, l_ref, acc_ref, *, block_size: int,
                   max_nb: int, scale: float, q_len: int, group: int):
    """One grid step: fold KV block ``j`` of sequence ``b`` (kv head
    ``h``) into the online softmax. The BlockSpec index maps already
    resolved ``tables_ref[b, j]`` to a pool block, so ``k_ref``/``v_ref``
    hold the gathered block; this body only masks and accumulates.

    Generalized to ``q_len`` query rows per sequence (speculative
    verify): the q block is the flattened [q_len * group, d] span, row
    ``r`` belonging to query token ``r // group`` at absolute position
    ``ctx - q_lens[b] + r // group`` — causal within the span, so each
    query sees the resident context plus the speculative tokens at or
    before itself. Lanes with fewer than q_len real rows (short
    proposals, batch padding) clamp to the plain context mask; their
    rows are well-defined garbage the engine never reads."""
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0]                              # (q_len * group, d)
    k_blk = k_ref[0, 0]                          # (block_size, d)
    v_blk = v_ref[0, 0]
    ctx = lens_ref[b]
    qn = qlens_ref[b]

    s = jax.lax.dot_general(
        q, k_blk, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale  # (q_len*group, bs)
    # Key positions beyond the context are masked — this covers both the
    # ragged tail of the last real block and whole padded table entries
    # (their table slot points at the reserved scratch block; the mask
    # makes the gathered garbage contribute exp(NEG_INF) ≈ 0). With
    # q_len > 1 the bound is additionally causal per query row: query
    # i's last visible key is its own write slot ctx - qn + i.
    k_pos = j * block_size + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 1)
    qi = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // group
    bound = jnp.minimum(ctx, ctx - qn + 1 + qi)
    s = jnp.where(k_pos < bound, s, NEG_INF)

    m, l, acc = m_ref[...], l_ref[...], acc_ref[...]
    m_new = jnp.maximum(m, s.max(-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m - m_new)
    m_ref[...] = m_new
    l_ref[...] = l * corr + p.sum(-1, keepdims=True)
    acc_ref[...] = acc * corr + jax.lax.dot_general(
        p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(j == max_nb - 1)
    def _write():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.lru_cache(maxsize=None)
def _make_decode_call(b: int, hkv: int, group: int, d: int,
                      num_blocks: int, block_size: int, max_nb: int,
                      q_dtype, p_dtype, interpret: bool, q_len: int = 1):
    scale = d ** -0.5
    rows = q_len * group
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,   # block tables + context lens + q lens
        grid=(b, hkv, max_nb),
        in_specs=[
            pl.BlockSpec((1, 1, rows, d),
                         lambda bi, hi, j, tables, lens, qlens:
                         (bi, hi, 0, 0)),
            # The paged gather: the pool block for grid step (bi, ·, j)
            # is whatever the sequence's table names. Padded table slots
            # hold 0 (the pool's reserved scratch block) so the index is
            # always in range; the kernel masks their keys out.
            pl.BlockSpec((1, 1, block_size, d),
                         lambda bi, hi, j, tables, lens, qlens:
                         (hi, tables[bi, j], 0, 0)),
            pl.BlockSpec((1, 1, block_size, d),
                         lambda bi, hi, j, tables, lens, qlens:
                         (hi, tables[bi, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, rows, d),
            lambda bi, hi, j, tables, lens, qlens: (bi, hi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rows, 1), jnp.float32),   # running max
            pltpu.VMEM((rows, 1), jnp.float32),   # running denominator
            pltpu.VMEM((rows, d), jnp.float32),   # output accumulator
        ],
    )
    return pl.pallas_call(
        functools.partial(_decode_kernel, block_size=block_size,
                          max_nb=max_nb, scale=scale, q_len=q_len,
                          group=group),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, rows, d), q_dtype),
        interpret=interpret, name="paged_decode",
    )


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
