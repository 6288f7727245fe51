"""A prefill span's attention as a Pallas TPU kernel (``chunk_attn``).

A chunk of a prompt attends over ``[the sequence's gathered context ++
the chunk itself]``: thousands of context slots, a few hundred queries.
Made by XLA, the scores of that product go through HBM in float32
between the QK product, the softmax and the PV product; here they stay
in VMEM. The kernel is flash attention's forward, tiled over keys under
one online softmax (running max, denominator and accumulator in VMEM
scratch), with what a serving chunk needs and the training kernel
(ops/pallas/flash.py) does not have:

* **Keys too many to hold.** The grid is ``(KV head, query block, key
  block)``, the key block innermost: one ``(key_block, d)`` window of K
  and of V is in VMEM at a time, double-buffered by the pipeline.
* **A context length that is data.** ``ctx_len`` and ``base`` ride in
  as scalar-prefetch operands. Key ``p`` below ``ctx_slots`` is the
  context's slot ``p`` at absolute position ``base + p``, real where
  that lies below ``ctx_len``; key ``ctx_slots + j`` is the span's own
  row ``j`` at position ``ctx_len + j``. Query ``i`` sits at
  ``ctx_len + i`` and sees real keys at or before itself, with a
  ``window`` only those less than ``window`` positions behind it. Keys
  past the span (padding up to whole key blocks) lie after every query
  and are never seen.
* **Dead blocks cost nothing.** A key block wholly of context slots
  past ``ctx_len`` (a table longer than the context: a document still
  being registered) and one wholly after the query block's last row are
  skipped: no product (``pl.when``) and no transfer (the index map
  names a block the pipeline already holds or needs next, and the
  pipeline copies only when the index changes).
* **Grouped queries.** The ``g`` query heads that read a KV head ride
  in one tile, so the MXU sees ``g x q_block`` rows against each key
  window and K/V are read once a query block, not once a query head.
* **Keys in two parts.** ``k_shared [keys, ds]`` holds trailing
  columns of a key that every head shares (latent attention's one
  rotary key a token): it is read once a key block and joined to each
  head's own columns in VMEM, not broadcast to every head in HBM.
  Key and value widths may differ (192 and 128).

* **Rows of several sequences.** A program that carries the spans of
  several prompts end to end (models/seam.py ``pack_spans``) says of
  each query row what it may see (``rows``): the context slots
  ``[lo_i, hi_i)`` of the ONE gathered table, where its sequence's
  context lies, and the span rows ``first_i..i``, where ``first_i`` is
  its span's first row. The keys stay ``[context slots ++ the
  program's rows]``. What a query block's rows see between them is two
  runs of key blocks, one of context and one of rows, and the blocks
  that every row sees whole a third; the three ride as block indices
  in the scalar-prefetch operand, a query block at a time, so the
  skips and the unmasked body are decided as they are for one
  sequence. Absent, the kernel is the one-sequence kernel, to the
  letter.

A block wholly inside the live context needs no mask and takes the
body without one. Operands keep their dtype (the pools' bfloat16),
scores and accumulators are float32, probabilities are cast to the
operand type for the PV product.

Off the TPU the same kernel runs under the Pallas interpreter
(``interpret_default()``), as the paged kernels do.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..attention import NEG_INF
from . import interpret_default

# Keys a grid step folds, and the query rows (heads of a group x
# queries) it folds them into: the step's float32 scores are at most
# ``ROWS x KEY_BLOCK`` (8 MiB), its K and V windows 512 KiB each at
# width 128. Measured on the v5e (PERF.md section 6, PR 38): one large
# product a step beats the same keys in runs of 512 (x0.7) or 256
# (x0.45), and 2,048 keys a step beat 1,024 where the queries are few.
KEY_BLOCK = 2048
ROWS = 1024
# Mosaic's scoped default is 16 MiB of the v5e's 128; a step's scores,
# their exponentials and its double-buffered windows need more.
VMEM_LIMIT = 64 * 1024 * 1024


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def padded_keys(n_keys: int) -> int:
    """The length a caller that builds K and V itself gives them, so
    that the call pads nothing: whole key blocks (one block of whole
    (16, 128) tiles where the keys fit one)."""
    return _round_up(n_keys, KEY_BLOCK if n_keys > KEY_BLOCK else 16)


def _query_block(n: int, g: int) -> int:
    """Queries a grid step carries: as many as keep ``g x block`` within
    ``ROWS``, in whole (16, 128) bfloat16 tiles, evened out over the
    blocks the span needs."""
    most = max(ROWS // g // 16 * 16, 16)
    blocks = -(-n // most)
    return _round_up(-(-n // blocks), 16)


# What a query block's rows see, as block indices in the scalar
# operand behind ``[ctx_len, base]`` (``_block_runs``): the context
# blocks some row sees, the blocks with a span key some row sees, the
# context blocks every row sees whole.
_RUNS = 6


def _kernel(sc_ref, q_ref, k_ref, v_ref, *refs, ctx_slots: int, blk_q: int,
            blk_k: int, n_kb: int, g: int, scale: float, window, shared: bool,
            described: bool):
    ks_ref = refs[0] if shared else None
    refs = refs[1:] if shared else refs
    d_ref = refs[0] if described else None
    o_ref, m_ref, l_ref, acc_ref = refs[1:] if described else refs
    qb, kb = pl.program_id(1), pl.program_id(2)
    ctx_len, base = sc_ref[0], sc_ref[1]
    p0, i0 = kb * blk_k, qb * blk_q

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if described:
        c_lo, c_hi, s_lo, s_hi, p_lo, p_hi = (
            sc_ref[2 + _RUNS * qb + t] for t in range(_RUNS))
        within = lambda a, b: jnp.logical_and(kb >= a, kb <= b)
        dead = jnp.logical_not(jnp.logical_or(within(c_lo, c_hi),
                                              within(s_lo, s_hi)))
        plain = within(p_lo, p_hi)
    else:
        in_ctx = p0 + blk_k <= ctx_slots        # no span key in the block
        dead = jnp.logical_or(
            jnp.logical_and(in_ctx, base + p0 >= ctx_len),
            p0 - ctx_slots > i0 + blk_q - 1)
        # Every key of the block real and before every query: no mask.
        plain = jnp.logical_and(in_ctx, base + p0 + blk_k <= ctx_len)

    def fold(masked: bool):
        rows = g * blk_q
        q = q_ref[...].reshape(rows, q_ref.shape[-1])
        k = k_ref[...]
        if shared:
            k = jnp.concatenate([k, ks_ref[...]], axis=-1)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale     # (rows, blk_k)
        if masked and described:
            # Each row's own: context slots [lo, hi), span rows
            # first..i. One interval of keys either side of the table's
            # end, chosen by the key.
            p = p0 + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 1)
            i = i0 + jax.lax.broadcasted_iota(jnp.int32, (blk_q, 1), 0)
            span = p >= ctx_slots
            seen = jnp.logical_and(
                p >= jnp.where(span, ctx_slots + d_ref[2], d_ref[0]),
                p < jnp.where(span, ctx_slots + 1 + i, d_ref[1]))
            s = jnp.where(seen[None], s.reshape(g, blk_q, blk_k),
                          NEG_INF).reshape(rows, blk_k)
        elif masked:
            p = p0 + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 1)
            i = i0 + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 0)
            span = p >= ctx_slots
            q_pos = ctx_len + i
            k_pos = p + jnp.where(span, ctx_len - ctx_slots, base)
            seen = k_pos <= jnp.where(span, q_pos, ctx_len - 1)
            if window is not None:
                seen = jnp.logical_and(seen, q_pos - k_pos < window)
            s = jnp.where(seen[None], s.reshape(g, blk_q, blk_k),
                          NEG_INF).reshape(rows, blk_k)   # seen by all g
        m = m_ref[...]
        m_new = jnp.maximum(m, s.max(-1, keepdims=True))
        pr = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        m_ref[...] = m_new
        l_ref[...] = l_ref[...] * corr + pr.sum(-1, keepdims=True)
        v = v_ref[...]
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            pr.astype(v.dtype), v, preferred_element_type=jnp.float32)

    live = jnp.logical_not(dead)
    if window is None:
        pl.when(jnp.logical_and(live, plain))(lambda: fold(False))
        live = jnp.logical_and(live, jnp.logical_not(plain))
    pl.when(live)(lambda: fold(True))

    @pl.when(kb == n_kb - 1)
    def _write():
        o = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = o.reshape(o_ref.shape).astype(o_ref.dtype)


@functools.lru_cache(maxsize=None)
def _make_call(kvh: int, g: int, n: int, dk: int, dv: int, ds: int,
               keys: int, ctx_slots: int, blk_q: int, blk_k: int,
               scale: float, window, dtype, interpret: bool,
               described: bool = False):
    n_kb = keys // blk_k
    first_span = ctx_slots // blk_k      # the first block with a span key

    def key_block(qb, kb, sc):
        """The key block a step reads. A dead context block names the
        first block that holds a span key (always live, and the next
        one needed); a block after the query block's last row names
        the last one before it. Both repeat an index, so the pipeline
        copies nothing for them. Where the rows are described, a block
        outside both runs names the next block of a run, or behind the
        last run its last one: an index the pipeline holds or needs
        next, as well."""
        if described:
            c_lo, c_hi, s_lo, s_hi = (sc[2 + _RUNS * qb + t]
                                      for t in range(4))
            return jnp.minimum(jnp.where(kb > c_hi, jnp.maximum(kb, s_lo),
                                         jnp.maximum(kb, c_lo)), s_hi)
        ctx_len, base = sc[0], sc[1]
        dead_ctx = jnp.logical_and((kb + 1) * blk_k <= ctx_slots,
                                   base + kb * blk_k >= ctx_len)
        last = jnp.minimum((ctx_slots + (qb + 1) * blk_q - 1) // blk_k,
                           n_kb - 1)
        return jnp.minimum(jnp.where(dead_ctx, first_span, kb), last)

    q_spec = pl.BlockSpec((None, g, blk_q, dk),
                          lambda h, qb, kb, sc: (h, 0, qb, 0))
    in_specs = [
        q_spec,
        pl.BlockSpec((None, blk_k, dk - ds),
                     lambda h, qb, kb, sc: (h, key_block(qb, kb, sc), 0)),
        pl.BlockSpec((None, blk_k, dv),
                     lambda h, qb, kb, sc: (h, key_block(qb, kb, sc), 0)),
    ]
    if ds:
        in_specs.append(pl.BlockSpec(
            (blk_k, ds), lambda h, qb, kb, sc: (key_block(qb, kb, sc), 0)))
    if described:
        # [lo | hi | first] of the block's rows, a column each.
        in_specs.append(pl.BlockSpec(
            (3, blk_q, 1), lambda h, qb, kb, sc: (0, qb, 0)))
    rows = g * blk_q
    return pl.pallas_call(
        functools.partial(_kernel, ctx_slots=ctx_slots, blk_q=blk_q,
                          blk_k=blk_k, n_kb=n_kb, g=g, scale=scale,
                          window=window, shared=bool(ds),
                          described=described),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,           # [ctx_len, base | block runs]
            grid=(kvh, n // blk_q, n_kb),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((None, g, blk_q, dv),
                                   lambda h, qb, kb, sc: (h, 0, qb, 0)),
            scratch_shapes=[
                pltpu.VMEM((rows, 1), jnp.float32),    # running max
                pltpu.VMEM((rows, 1), jnp.float32),    # denominator
                pltpu.VMEM((rows, dv), jnp.float32),   # accumulator
            ]),
        out_shape=jax.ShapeDtypeStruct((kvh, g, n, dv), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret, name="chunk_attn",
    )


def _block_runs(desc, blk_q: int, blk_k: int, ctx_slots: int, n_kb: int):
    """What each query block's rows see, as runs of key blocks, from
    the least and greatest ``lo``, ``hi`` and ``first`` of its rows:
    int32 ``[query blocks, 6]`` = the context blocks some row sees
    ``c_lo..c_hi`` (none: 0..-1; a row with no context counts for
    nothing), the blocks with a span key some row sees ``s_lo..s_hi``
    (a row sees itself: never none), the context blocks EVERY row sees
    whole ``p_lo..p_hi``. ``desc`` is ``[3, n]``, n whole query
    blocks."""
    lo, hi, first = (x.reshape(-1, blk_q) for x in desc)
    hi = jnp.minimum(hi, ctx_slots)
    some = hi > lo
    lo_min = jnp.where(some, lo, ctx_slots).min(-1)
    hi_max = jnp.where(some, hi, 0).max(-1)
    any_ctx = hi_max > lo_min
    last_row = (jnp.arange(lo.shape[0], dtype=jnp.int32) + 1) * blk_q - 1
    return jnp.stack([
        jnp.where(any_ctx, lo_min // blk_k, 0),
        jnp.where(any_ctx, (hi_max - 1) // blk_k, -1),
        (ctx_slots + first.min(-1)) // blk_k,
        jnp.minimum((ctx_slots + last_row) // blk_k, n_kb - 1),
        -(-lo.max(-1) // blk_k),
        hi.min(-1) // blk_k - 1], axis=-1).astype(jnp.int32)


def chunk_attention(q, k, v, ctx_len, *, ctx_slots: int, scale: float,
                    k_shared=None, base=0, window: int | None = None,
                    rows=None, interpret: bool | None = None):
    """A span's attention over ``[context ++ span]`` (module docstring).

    Args:
      q: ``[kv_heads, group, n, dk]``: the span's queries, a KV head's
        ``group`` query heads side by side; query ``i`` sits at
        absolute position ``ctx_len + i``.
      k: ``[kv_heads, keys, dk - ds]``, v: ``[kv_heads, keys, dv]``: the
        first ``ctx_slots`` keys are the sequence's gathered context
        slots, the next ``n`` the span's own rows; whatever follows is
        padding (``padded_keys`` gives the length that needs none
        added here).
      k_shared: ``[keys, ds]`` or None: a key's trailing ``ds`` columns
        where every head shares them.
      ctx_len: int32 scalar, the tokens resident before the span.
      base: int32 scalar, the absolute position of context slot 0 (a
        window kind's table starts at its oldest block).
      window: a query sees only keys less than this many positions
        behind it; None for all.
      rows: None, or ``(lo, hi, first)``, int32 ``[n]`` each, where the
        queries are rows of several sequences: query ``i`` sees the
        context slots ``[lo_i, hi_i)`` and the span rows ``first_i..i``
        (module docstring). ``ctx_len`` and ``base`` then say nothing
        (rotary positions are the caller's, applied before the call),
        and a ``window`` is refused.

    Returns ``[kv_heads, group, n, dv]`` in q's dtype."""
    if rows is not None and window is not None:
        raise ValueError("rows of several sequences behind a window are "
                         "not built")
    if interpret is None:
        interpret = interpret_default()
    kvh, g, n, dk = q.shape
    keys, dv = v.shape[1], v.shape[2]
    ds = 0 if k_shared is None else k_shared.shape[-1]
    if k.shape != (kvh, keys, dk - ds) or keys < ctx_slots + n:
        raise ValueError(f"keys {k.shape} / values {v.shape} do not hold "
                         f"{ctx_slots} context slots and {n} rows of "
                         f"queries {q.shape}")
    blk_q = _query_block(n, g)
    pad_q = -n % blk_q
    pad_k = padded_keys(keys) - keys
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0)))
        if ds:
            k_shared = jnp.pad(k_shared, ((0, pad_k), (0, 0)))
    blk_k = min(KEY_BLOCK, keys + pad_k)
    sc = jnp.stack([jnp.asarray(ctx_len, jnp.int32),
                    jnp.asarray(base, jnp.int32)])
    operands = [q, k, v] + ([] if k_shared is None else [k_shared])
    if rows is None:
        call = _make_call(kvh, g, n + pad_q, dk, dv, ds, keys + pad_k,
                          int(ctx_slots), blk_q, blk_k, float(scale), window,
                          q.dtype, interpret)
    else:
        # A padding row is its neighbour over again: it moves no least
        # and no greatest of its block.
        desc = jnp.pad(jnp.stack(rows).astype(jnp.int32),
                       ((0, 0), (0, pad_q)), mode="edge")
        call = _make_call(kvh, g, n + pad_q, dk, dv, ds, keys + pad_k,
                          int(ctx_slots), blk_q, blk_k, float(scale), None,
                          q.dtype, interpret, described=True)
        sc = jnp.concatenate([sc, _block_runs(
            desc, blk_q, blk_k, int(ctx_slots),
            (keys + pad_k) // blk_k).reshape(-1)])
        operands.append(desc[..., None])
    out = call(sc, *operands)
    return out[:, :, :n] if pad_q else out


def chunk_attention_reference(q, k, v, ctx_len, *, ctx_slots: int,
                              scale: float, k_shared=None, base=0,
                              window: int | None = None, rows=None):
    """Pure-jnp ground truth of ``chunk_attention``: dense masked
    softmax in float32. Tests only."""
    kvh, g, n, _ = q.shape
    keys = v.shape[1]
    k = k.astype(jnp.float32)
    if k_shared is not None:
        k = jnp.concatenate([k, jnp.broadcast_to(
            k_shared.astype(jnp.float32)[None],
            (kvh,) + k_shared.shape)], axis=-1)
    p = jnp.arange(keys)
    k_pos = jnp.where(p < ctx_slots, base + p, ctx_len + p - ctx_slots)
    real = jnp.where(p < ctx_slots, k_pos < ctx_len, p < ctx_slots + n)
    q_pos = ctx_len + jnp.arange(n)
    seen = real[None, :] & (k_pos[None, :] <= q_pos[:, None])
    if window is not None:
        seen = seen & (q_pos[:, None] - k_pos[None, :] < window)
    if rows is not None:
        lo, hi, first = (jnp.asarray(x)[:, None] for x in rows)
        j = p[None, :] - ctx_slots
        seen = jnp.where(p[None, :] < ctx_slots,
                         (p[None, :] >= lo) & (p[None, :] < hi),
                         (j >= first) & (j <= jnp.arange(n)[:, None]))
    s = jnp.einsum("hgnd,hkd->hgnk", q.astype(jnp.float32), k) * scale
    pr = jax.nn.softmax(jnp.where(seen[None, None], s, NEG_INF), axis=-1)
    return jnp.einsum("hgnk,hkd->hgnd", pr,
                      v.astype(jnp.float32)).astype(q.dtype)
