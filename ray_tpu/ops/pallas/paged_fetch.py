"""Paged decode attention over a pool AS STORED, by kernels that fetch
their own pages: keys and values in rows of ``kv_heads * head_dim``
(``attn_full`` / ``attn_window``, heads of 128: models/laguna.py,
models/nemotron_h.py, models/granite_hybrid.py, whose softmax scale is
its own and not ``head_dim ** -0.5``: the stored kernel takes the scale
as the latent one does; ``paged_decode``, 12 heads of 64: models/gpt.py)
and ``attn_latent`` (latent attention in the absorbed form, rows of
640; models/kimi_k2.py). Every decode and verify step of every served
model attends here; there is no other paged kernel.

Why not page windows. Until PR 42 these kernels named a lane's
scattered pages through ``BlockSpec`` windows, one a page (GPT-2's did
until PR 59, over a head-major copy of a layer's pool that the program
made for it once a layer: ``[kv_heads, num_blocks, block_size, 64]``,
whose 64-wide minor dimension Mosaic pads to 128 lanes and refuses to
slice). A window costs ~82 ns whatever it carries (its index map's
scalar arithmetic, the pipeline's changed-index test, a descriptor, a
semaphore wait, its part of the ``concatenate`` that joins a step's
windows): 32 windows were 2.6 us of a grid step whose bytes take 0.8 us
(latent) or 1.3 us (stored). The transfers' count set the time, not
their bytes. A stored row is whole 128-lane tiles and a page whole
sublane tiles, so Mosaic takes manual slices of these pools, and the
kernels move their own keys. A head of 64 is half a lane tile OF SUCH A
ROW: the copy still moves whole rows, never a slice of HBM, and the
head is a static lane slice of the VMEM tile (``_stored_kernel``).

The mechanism (``_fetched``, ``_page_copies``). The stacked pool stays
in HBM as stored (``memory_space=pl.ANY``) and is an operand ONCE (once
each for K and V); the block tables, the context lengths, (the query
lengths, the window starts,) and one run flag a group of ``run`` table
slots ride in as scalar prefetch. Scratch is a VMEM tile a pool, two
compute blocks deep (``[2, pages, block_size, width]``), and one DMA
semaphore a slot. A grid step first STARTS the copies of the next live
compute block (the lane's next, or the next lane's first during a
lane's last) into the other slot, then waits for its own and folds it:
one chain of copies spans the whole call, so the grid runs in order,
``("arbitrary", "arbitrary")``. Nothing is joined in VMEM, so the same
budget carries twice the keys a step (``_geometry``).

Runs. Where a group of ``run`` table slots is all live and names block
ids that ascend by one (``_page_runs``: a few XLA integer operations on
the block table; every layer of a program asks for the same ones of the
same table, so XLA computes them once a program), ONE copy of
``pool[layer, first:first + run]`` brings the group; any other group's
live pages are copied one by one, and a dead slot is not copied at all.
A context registered alone is one ascending run of the pool
(llm/kv_cache.py grants ascending ids), so ~95% of such a batch's pages
move eight to a copy; a request's own blocks do not.
``kv_pages_in_runs_x1000`` is that share, which the step programs
return with their counters; it and both makers take the run size from
``_geometry``, so the counter counts the groups the kernel fetches
whole. A pool of fewer blocks than ``_RUN_PAGES`` gets a shorter run:
a wait builds its descriptor from ``pool[layer, 0:run]``.

Every copy into a slot signals that slot's one semaphore, and a DMA
semaphore counts BYTES: a wait for ``run`` pages is satisfied by one
copy of ``run`` pages or by ``run`` copies of one page alike. So a
group whose slots are all live is waited for as one piece however it
was copied, and only a group that ends inside (a lane's last) is waited
for page by page.

What a lane can see of another's rows: nothing. A dead slot of a tile
keeps what an earlier block left there, possibly ANOTHER lane's rows,
and a live page's rows past the lane's context are whatever the block's
last owner wrote. Their scores are masked before the softmax, so their
keys cannot matter; but ``0 x NaN`` in the probabilities-times-values
product is ``NaN``, so in a lane's last live block, the only one that
can hold such rows (about one grid step in seventeen at the cells'
shapes), the VALUE rows past the lane's last key are selected to zero
before the product (``fold(slot, tail=True)``). The tiles are therefore
not cleared at the start of a call either.

The arithmetic is the page-window kernels': operands as stored
(bfloat16 in the cells), float32 scores and accumulators, the
probabilities cast for the PV product. More keys a block moves only
where the online softmax rescales.

The constants were measured on the chip at the cells' shapes (PERF.md
section 6, PR 42) and are the module's, as ``KEY_BLOCK`` is in
``chunk_attention.py``: no argument, no option. ``_VMEM_BUDGET`` 6 MiB:
64 pages a step latent (1,024 rows), 32 stored (512 keys and values of
8 heads); 3 MiB is slower (2.43 against 1.97 ms a layer latent, 5.09
against 3.09 stored) and 12 MiB no faster but on shuffled stored
tables. ``_RUN_PAGES`` 8: 4 is slower (2.25 latent); 32 is no faster on
a run table or on the cells' mix, and the longer a group the fewer
tables have one whole.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..attention import NEG_INF
from . import interpret_default

# VMEM a grid step's tiles (two compute blocks deep) and its float32
# scores may take; v5e's scoped default is 16 MiB.
_VMEM_BUDGET = 6 * 1024 * 1024

# Table-adjacent pages one copy may carry: a group of this many table
# slots whose block ids ascend by one is fetched whole.
_RUN_PAGES = 8


def _geometry(width: int, score_rows: int, block_size: int, max_nb: int,
              num_blocks: int, itemsize: int) -> tuple[int, int]:
    """(pages, run) of a kernel that fetches its own pages. ``pages`` a
    grid step: the largest power of two whose rows (``width`` columns
    over all the kernel's pools, two tiles deep, nothing joined) and
    float32 scores fit ``_VMEM_BUDGET``, never more than a table has.
    ``run``: table slots a run flag covers, ``_RUN_PAGES`` or the
    largest power of two that a step's pages and the pool's blocks
    allow. The ONE place that decides either, for the makers and for
    ``kv_pages_in_runs_x1000``."""
    page = 2 * block_size * width * itemsize \
        + 3 * (-(-score_rows // 8) * 8) * block_size * 4
    fit = max(1, min(_VMEM_BUDGET // page, max_nb))
    pages = 1 << (fit.bit_length() - 1)
    run = min(_RUN_PAGES, pages, 1 << (num_blocks.bit_length() - 1))
    return pages, run


def _page_runs(block_tables, context_lens, block_size: int, run: int,
               groups: int):
    """Which groups of ``run`` table slots one copy can fetch: every
    slot live and the block ids ascending by one. Returns (flags
    ``[batch, groups]`` int32, live slots ``[batch]``); a table shorter
    than ``groups * run`` counts as padded with dead slots."""
    b, max_nb = block_tables.shape
    t = jnp.pad(block_tables, ((0, 0), (0, groups * run - max_nb)))
    t = t.reshape(b, groups, run)
    adjacent = jnp.all(
        t == t[..., :1] + jnp.arange(run, dtype=t.dtype), axis=-1)
    live = (jnp.maximum(context_lens, 1) - 1) // block_size + 1
    whole = (jnp.arange(groups, dtype=live.dtype) + 1) * run \
        <= live[:, None]
    return jnp.logical_and(adjacent, whole).astype(jnp.int32), live


def kv_pages_in_runs_x1000(block_tables, context_lens, *pools,
                           score_rows: int):
    """1000 x the share of a decode batch's live table slots that lie
    in groups the kernel fetches in one copy: the step programs'
    counter ``kv_pages_in_runs_x1000``. ``pools`` are the stacked pools
    the kernel reads (one latent pool; K and V) and ``score_rows`` the
    query rows a lane scores against a key (``q_len`` x query heads):
    what ``_geometry`` needs to name the kernel's own run size."""
    _, num_blocks, block_size, _ = pools[0].shape
    max_nb = block_tables.shape[1]
    pages, run = _geometry(sum(p.shape[3] for p in pools), score_rows,
                           block_size, max_nb, num_blocks,
                           pools[0].dtype.itemsize)
    # The kernel's own flags (same arguments, so XLA makes them once).
    flags, live = _page_runs(block_tables.astype(jnp.int32),
                             context_lens.astype(jnp.int32), block_size,
                             run, pl.cdiv(max_nb, pages) * pages // run)
    return (flags.sum() * (1000 * run)) // jnp.maximum(live.sum(), 1)


def _page_copies(tables_ref, runs_ref, pools, tiles, sem, lane, blk, ctx,
                 slot, *, layer: int, block_size: int, pages: int,
                 run: int, start: bool):
    """The copies that bring compute block ``blk`` of lane ``lane`` out
    of the pools in HBM into ``slot`` of their tiles, started
    (``start``) or waited for. A group of ``run`` slots that
    ``runs_ref`` flags is ONE copy of ``run`` pages from the group's
    first block on; any other group's live pages go one by one, and a
    dead slot is not copied. Every copy into a slot signals that slot's
    semaphore by its bytes, so a group whose slots are all live is
    waited for as one piece of ``run`` pages however it was copied; a
    wait's descriptor is only a size (``pool[layer, 0:run]``, which
    ``_geometry`` keeps inside the pool)."""
    last = (jnp.maximum(ctx, 1) - 1) // block_size
    groups = pages // run

    def copies(page, n, at):
        return [pltpu.make_async_copy(pool.at[layer, pl.ds(page, n)],
                                      tile.at[slot, pl.ds(at, n)],
                                      sem.at[slot])
                for pool, tile in zip(pools, tiles)]

    def each_page(g, live, do):
        def body(i, carry):
            for c in copies(tables_ref[lane, blk * pages + g * run + i]
                            if start else 0, 1, g * run + i):
                do(c)
            return carry
        jax.lax.fori_loop(0, live, body, 0)

    for g in range(groups):
        j0 = blk * pages + g * run
        live = jnp.clip(last + 1 - j0, 0, run)
        if start:
            whole = runs_ref[lane, blk * groups + g] == 1

            @pl.when(whole)
            def _run(g=g, j0=j0):
                for c in copies(tables_ref[lane, j0], run, g * run):
                    c.start()

            @pl.when(jnp.logical_not(whole))
            def _pages(g=g, live=live):
                each_page(g, live, lambda c: c.start())
        else:
            @pl.when(live == run)
            def _group(g=g):
                for c in copies(0, run, g * run):
                    c.wait()

            @pl.when(live < run)
            def _pages(g=g, live=live):
                each_page(g, live, lambda c: c.wait())


def _fetched(tables_ref, runs_ref, lens_ref, pools, tiles, sem, slot_ref,
             fold, **geometry):
    """One grid step of a kernel that moves its own keys: wait for this
    step's compute block, having first started the copies of the NEXT
    live one (the lane's next block, or the next lane's first during a
    lane's last) into the tiles' other slot, then ``fold(slot, tail)``;
    ``tail`` (static) says the block is the lane's last and ends before
    its span does, so it holds rows that are not the lane's. The grid
    runs in order, so one chain of copies spans the whole call;
    ``slot_ref`` carries which slot the current block is in."""
    b = pl.program_id(0)
    blk = pl.program_id(1)
    lanes = pl.num_programs(0)
    span = geometry["pages"] * geometry["block_size"]
    ctx = lens_ref[b]
    n_live = pl.cdiv(jnp.maximum(ctx, 1), span)
    move = functools.partial(_page_copies, tables_ref, runs_ref, pools,
                             tiles, sem, **geometry)

    @pl.when(jnp.logical_and(b == 0, blk == 0))
    def _first():
        slot_ref[0] = 0
        move(0, 0, lens_ref[0], 0, start=True)

    @pl.when(blk < n_live)
    def _live():
        slot = slot_ref[0]
        in_lane = blk + 1 < n_live
        nxt = jnp.where(in_lane, b, jnp.minimum(b + 1, lanes - 1))

        @pl.when(jnp.logical_or(in_lane, b + 1 < lanes))
        def _ahead():
            move(nxt, jnp.where(in_lane, blk + 1, 0), lens_ref[nxt],
                 1 - slot, start=True)

        move(b, blk, ctx, slot, start=False)
        pl.when((blk + 1) * span <= ctx)(lambda: fold(slot, False))
        pl.when(jnp.logical_and(blk * span < ctx, ctx < (blk + 1) * span))(
            lambda: fold(slot, True))
        slot_ref[0] = 1 - slot


def _own_rows(values, first, ctx):
    """``values`` (a tile's rows from key position ``first`` on) with
    the rows at or past ``ctx`` set to zero: they are not the lane's,
    and ``0 x NaN`` is ``NaN``."""
    pos = first + jax.lax.broadcasted_iota(jnp.int32, values.shape, 0)
    return jnp.where(pos < ctx, values, jnp.zeros_like(values))


def _accumulate(s, values, m_ref, l_ref, acc_ref, at=...):
    """One online-softmax update: masked float32 scores ``s`` and their
    value rows folded into the running max, denominator and accumulator
    at index ``at`` of the scratch."""
    m, l, acc = m_ref[at], l_ref[at], acc_ref[at]
    m_new = jnp.maximum(m, s.max(-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m - m_new)
    m_ref[at] = m_new
    l_ref[at] = l * corr + p.sum(-1, keepdims=True)
    acc_ref[at] = acc * corr + jnp.dot(
        p.astype(values.dtype), values, preferred_element_type=jnp.float32)


def _fetching_call(kernel, *, name: str, interpret: bool, b: int,
                   n_blocks: int, prefetch: int, q_block, out_block,
                   out_shape, pools: int, tile, softmax_scratch):
    """The ``pallas_call`` of a kernel that copies its own pages: the
    pools stay in HBM as stored and are passed ONCE each; the block
    tables, lengths and run flags ride in as scalar prefetch; a tile a
    pool two compute blocks deep, one DMA semaphore a slot and the
    current slot's number are scratch. The grid runs in order (the
    chain of copies crosses lanes)."""
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=prefetch,
        grid=(b, n_blocks),
        in_specs=[q_block] + pools * [pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=out_block,
        scratch_shapes=pools * [tile] + [
            pltpu.SemaphoreType.DMA((2,)), pltpu.SMEM((1,), jnp.int32),
            *softmax_scratch],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec, out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret, name=name)


def _stored_kernel(*refs, layer: int | None, block_size: int, pages: int,
                   run: int, n_blocks: int, scale: float, group: int,
                   hkv: int, d: int):
    """One grid step of attention over keys and values on pages of the
    pool AS STORED, which the kernel copies itself (``_fetched``): a
    page is ``(block_size, kv_heads * d)``, a token's K (or V) of every
    head in one row.

    A head is a static lane slice of the tile, whole 128-lane tiles at
    d = 128 and half of one at d = 64, and the heads fold one after
    another, two plain matmuls each. (At d = 64 and ``group`` 1 the
    twelve pairs of one-row products a block are bound by the MXU's
    latency, not by bytes: 29% of the roofline where d = 128 stands at
    87%; PERF.md section 6, PRs 58 and 59.)

    The q block is a KV head's ``q_len * group`` rows, row ``r`` query
    token ``r // group`` at position ``ctx - q_lens[b] + r // group``:
    causal within the span, so a row sees the resident context and the
    step's rows at or before itself; a lane with fewer real rows than
    ``q_len`` (short proposals, padding) clamps to the plain context
    mask and its spare rows are defined garbage the engine never reads.
    A layer with a window adds a lower bound: row i of lane b sees key
    positions ``>= starts[b] + i`` (its table holds only the blocks
    that cover its window, so the window's start lies inside the oldest
    of them).

    ``layer`` is the caller's Python int, or ``None``: then the layer
    rides in as one more scalar-prefetch operand, the first (a model
    whose layers run under one ``lax.scan`` has one kernel a program
    and a traced index)."""
    if layer is None:
        layer_ref, *refs = refs
        layer = layer_ref[0]
    (tables_ref, lens_ref, qlens_ref, starts_ref, runs_ref, q_ref, k_pool,
     v_pool, o_ref, k_tile, v_tile, sem, slot_ref, m_ref, l_ref,
     acc_ref) = refs
    b = pl.program_id(0)
    blk = pl.program_id(1)
    span = pages * block_size
    ctx = lens_ref[b]
    qn = qlens_ref[b]
    lo = starts_ref[b]

    @pl.when(blk == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def fold(slot, tail):
        rows = q_ref.shape[2]
        k_pos = blk * span + jax.lax.broadcasted_iota(
            jnp.int32, (rows, span), 1)
        qi = jax.lax.broadcasted_iota(jnp.int32, (rows, span), 0) // group
        seen = jnp.logical_and(
            k_pos < jnp.minimum(ctx, ctx - qn + 1 + qi), k_pos >= lo + qi)
        for h in range(hkv):
            head = slice(h * d, (h + 1) * d)
            k_h = k_tile[slot, :, :, head].reshape(span, d)
            v_h = v_tile[slot, :, :, head].reshape(span, d)
            if tail:
                v_h = _own_rows(v_h, blk * span, ctx)
            s = jax.lax.dot_general(
                q_ref[0, h], k_h, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale   # (rows, span)
            _accumulate(jnp.where(seen, s, NEG_INF), v_h, m_ref, l_ref,
                        acc_ref, h)

    _fetched(tables_ref, runs_ref, lens_ref, (k_pool, v_pool),
             (k_tile, v_tile), sem, slot_ref, fold, layer=layer,
             block_size=block_size, pages=pages, run=run)

    @pl.when(blk == n_blocks - 1)
    def _write():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.lru_cache(maxsize=None)
def _make_stored_call(b: int, hkv: int, group: int, d: int,
                      layer: int | None, num_blocks: int, block_size: int,
                      max_nb: int, q_dtype, p_dtype, interpret: bool,
                      q_len: int, name: str, scale: float):
    """``layer`` ``None``: the call takes the layer, ``[1]`` int32,
    before the tables."""
    rows = q_len * group
    pages, run = _geometry(2 * hkv * d, hkv * rows, block_size, max_nb,
                           num_blocks, jnp.dtype(p_dtype).itemsize)
    n_blocks = pl.cdiv(max_nb, pages)
    lane = pl.BlockSpec(
        (1, hkv, rows, d), lambda bi, blk, *prefetched: (bi, 0, 0, 0))
    call = _fetching_call(
        functools.partial(_stored_kernel, layer=layer,
                          block_size=block_size, pages=pages, run=run,
                          n_blocks=n_blocks, scale=scale, group=group,
                          hkv=hkv, d=d),
        name=name, interpret=interpret, b=b, n_blocks=n_blocks,
        # (layer,) tables, context lens, q lens, starts, run flags
        prefetch=5 + (layer is None),
        q_block=lane, out_block=lane,
        out_shape=jax.ShapeDtypeStruct((b, hkv, rows, d), q_dtype),
        pools=2, tile=pltpu.VMEM((2, pages, block_size, hkv * d), p_dtype),
        softmax_scratch=[pltpu.VMEM((hkv, rows, 1), jnp.float32),
                         pltpu.VMEM((hkv, rows, 1), jnp.float32),
                         pltpu.VMEM((hkv, rows, d), jnp.float32)])

    def attend(tables, lens, qlens, starts, q, k_pool, v_pool, *layer):
        runs, _ = _page_runs(tables, lens, block_size, run,
                             n_blocks * pages // run)
        return call(*layer, tables, lens, qlens, starts, runs, q, k_pool,
                    v_pool)
    return attend


def paged_attention_stored(q, k_pool, v_pool, layer, block_tables,
                           context_lens, q_lens, starts, *, name: str,
                           scale: float | None = None,
                           interpret: bool | None = None):
    """Attention of a decode step (``q_len`` 1) or a speculative verify
    step (``q_len`` rows a lane in one pass) over block-paged keys and
    values, the pool as the cache stores it, with an optional window.

    Args:
      q: ``[batch, q_len, kv_heads, group, head_dim]``: query heads
        grouped by the KV head they read; row j of lane b sits at
        position ``context_lens[b] - q_lens[b] + j`` (write-then-attend:
        the lane's real rows' K and V are already in their slots, so a
        row sees itself).
      k_pool / v_pool: ``[layers, num_blocks, block_size, kv_heads *
        head_dim]``, the stacked pool of one kind of layer, untouched:
        the kernel copies its pages out of it at ``layer``. No
        head-major view is made.
      layer: the layer of the pools, a Python int, or a traced int32
        scalar (a layer body under ``lax.scan``).
      block_tables: ``[batch, max_blocks]`` int32, a lane's pool blocks,
        padded with 0 (the reserved scratch block); for a layer with a
        window the table holds the blocks from the window's oldest on.
      context_lens: ``[batch]`` int32, resident tokens a lane INCLUDING
        this step's ``q_lens[b]`` real rows (counted from the table's
        first slot).
      q_lens: ``[batch]`` int32, real rows a lane (1..q_len); rows past
        it attend the whole context and are garbage nobody reads.
      starts: ``[batch]`` int32, the first key position (in the table's
        own coordinates) that row 0 of a lane sees; row i sees from
        ``starts + i``. For a layer without a window ``-q_len`` or
        below (zeros will do at ``q_len`` 1 only: row i of a verify
        step would miss the table's first i keys).
      name: the kernel's name on a device trace.
      scale: the softmax scale, applied to the float32 scores inside
        the kernel; ``head_dim ** -0.5`` where none is given (GPT-2,
        Laguna, Nemotron-H). A model whose scale is another number
        (Granite 4.0-H's ``attention_multiplier``) says it here: a
        query scaled in front of the kernel would be rounded to the
        served dtype once more than the model's equations round it.

    Returns ``[batch, q_len, kv_heads, group, head_dim]`` in q's dtype.
    """
    if interpret is None:
        interpret = interpret_default()
    b, q_len, hkv, group, d = q.shape
    _, num_blocks, block_size, width = k_pool.shape
    if width != hkv * d:
        raise ValueError(f"pool row {width} != {hkv} kv heads x {d}")
    static = isinstance(layer, (int, np.integer))
    call = _make_stored_call(b, hkv, group, d,
                             int(layer) if static else None, num_blocks,
                             block_size, block_tables.shape[1], q.dtype,
                             k_pool.dtype, interpret, q_len, name,
                             d ** -0.5 if scale is None else float(scale))
    traced = () if static else (jnp.asarray(layer, jnp.int32).reshape(1),)
    qf = q.transpose(0, 2, 1, 3, 4).reshape(b, hkv, q_len * group, d)
    out = call(block_tables.astype(jnp.int32),
               context_lens.astype(jnp.int32), q_lens.astype(jnp.int32),
               starts.astype(jnp.int32), qf, k_pool, v_pool, *traced)
    return out.reshape(b, hkv, q_len, group, d).transpose(0, 2, 1, 3, 4)


def paged_attention_stored_reference(q, k_pool, v_pool, layer: int,
                                     block_tables, context_lens, q_lens,
                                     starts, scale: float | None = None):
    """Pure-jnp ground truth of ``paged_attention_stored``: materialize
    the gather, dense masked softmax, float32. Tests only."""
    b, q_len, hkv, group, d = q.shape

    def rows(pool):
        return jnp.take(pool[layer], block_tables, axis=0).reshape(
            b, -1, hkv, d).astype(jnp.float32)           # [b, S, hkv, d]

    k, v = rows(k_pool), rows(v_pool)
    s = jnp.einsum("bqhgd,bshd->bqhgs", q.astype(jnp.float32),
                   k) * (d ** -0.5 if scale is None else scale)
    k_pos = jnp.arange(k.shape[1])[None, None, None, None, :]
    ctx = context_lens[:, None, None, None, None]
    qi = jnp.arange(q_len)[None, :, None, None, None]
    bound = jnp.minimum(ctx, ctx - q_lens[:, None, None, None, None]
                        + 1 + qi)
    seen = jnp.logical_and(
        k_pos < bound, k_pos >= starts[:, None, None, None, None] + qi)
    p = jax.nn.softmax(jnp.where(seen, s, NEG_INF), axis=-1)
    return jnp.einsum("bqhgs,bshd->bqhgd", p, v).astype(q.dtype)


def _latent_kernel(tables_ref, lens_ref, qlens_ref, runs_ref, q_ref, pool,
                   o_ref, tile, sem, slot_ref, m_ref, l_ref, acc_ref, *,
                   layer: int, block_size: int, pages: int, run: int,
                   n_blocks: int, scale: float, heads: int, rank: int):
    """Latent attention in the absorbed form, on pages of the latent
    pool AS STORED, which the kernel copies itself (``_fetched``): a
    page is ``(block_size, width)``, a token's normed latent vector
    (``rank`` columns), its rotary key and zeros up to whole lane
    tiles, ONE row shared by every head. The lane's ``q_len * heads``
    query rows (a head's nope part already taken through ``W_uk``, its
    rotary part beside it, zeros where the page has zeros) score
    against the whole row, and the values are the first ``rank``
    columns of the same tile: a page is read once for both. Row ``r``
    is query token ``r // heads``; the causal bound is
    ``_stored_kernel``'s."""
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

    def fold(slot, tail):
        width = tile.shape[-1]
        values = tile[slot, :, :, :rank].reshape(span, rank)
        if tail:
            values = _own_rows(values, blk * span, ctx)
        s = jax.lax.dot_general(
            q_ref[0], tile[slot].reshape(span, width),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale     # (rows, span)
        k_pos = blk * span + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        qi = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // heads
        s = jnp.where(k_pos < jnp.minimum(ctx, ctx - qn + 1 + qi), s,
                      NEG_INF)
        _accumulate(s, values, m_ref, l_ref, acc_ref)

    _fetched(tables_ref, runs_ref, lens_ref, (pool,), (tile,), sem,
             slot_ref, fold, layer=layer, block_size=block_size,
             pages=pages, run=run)

    @pl.when(blk == n_blocks - 1)
    def _write():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.lru_cache(maxsize=None)
def _make_latent_call(b: int, heads: int, width: int, rank: int,
                      layer: int, num_blocks: int, block_size: int,
                      max_nb: int, q_dtype, p_dtype, interpret: bool,
                      q_len: int, scale: float, name: str):
    rows = q_len * heads
    pages, run = _geometry(width, rows, block_size, max_nb, num_blocks,
                           jnp.dtype(p_dtype).itemsize)
    n_blocks = pl.cdiv(max_nb, pages)

    def lane(w):
        return pl.BlockSpec(
            (1, rows, w),
            lambda bi, blk, tables, lens, qlens, runs: (bi, 0, 0))

    call = _fetching_call(
        functools.partial(_latent_kernel, layer=layer,
                          block_size=block_size, pages=pages, run=run,
                          n_blocks=n_blocks, scale=scale, heads=heads,
                          rank=rank),
        name=name, interpret=interpret, b=b, n_blocks=n_blocks,
        prefetch=4,      # block tables, context lens, q lens, run flags
        q_block=lane(width), out_block=lane(rank),
        out_shape=jax.ShapeDtypeStruct((b, rows, rank), q_dtype),
        pools=1, tile=pltpu.VMEM((2, pages, block_size, width), p_dtype),
        softmax_scratch=[pltpu.VMEM((rows, 1), jnp.float32),
                         pltpu.VMEM((rows, 1), jnp.float32),
                         pltpu.VMEM((rows, rank), jnp.float32)])

    def attend(tables, lens, qlens, q, pool):
        runs, _ = _page_runs(tables, lens, block_size, run,
                             n_blocks * pages // run)
        return call(tables, lens, qlens, runs, q, pool)
    return attend


def paged_attention_latent(q, pool, layer: int, block_tables, context_lens,
                           q_lens, *, rank: int, scale: float,
                           name: str = "attn_latent",
                           interpret: bool | None = None):
    """Latent attention (MLA) of a decode step in the absorbed form,
    over the latent pool as the cache stores it.

    Args:
      q: ``[batch, q_len, heads, width]``: a head's query taken into the
        latent space (``q_nope W_uk^T``, ``rank`` columns), its rotary
        part behind it, zeros in whatever columns the pool's rows pad.
      pool: ``[layers, num_blocks, block_size, width]``, the stacked
        latent pool, untouched: the kernel copies its pages out of it
        at the static ``layer``. A row is ``[c_kv (rank) | k_rope | 0]``;
        the scores are ``q . row`` over the whole width and the values
        the row's first ``rank`` columns.
      block_tables / context_lens / q_lens: as
        ``paged_attention_stored``.
      scale: the softmax scale (the model's, with its YaRN ``mscale``).

    Returns ``[batch, q_len, heads, rank]`` in q's dtype: a head's
    output in the latent space, which the caller takes up through
    ``W_uv``."""
    if interpret is None:
        interpret = interpret_default()
    b, q_len, heads, width = q.shape
    if pool.shape[3] != width:
        raise ValueError(f"pool row {pool.shape[3]} != query width {width}")
    call = _make_latent_call(b, heads, width, rank, int(layer),
                             pool.shape[1], pool.shape[2],
                             block_tables.shape[1], q.dtype, pool.dtype,
                             interpret, q_len, float(scale), name)
    out = call(block_tables.astype(jnp.int32),
               context_lens.astype(jnp.int32), q_lens.astype(jnp.int32),
               q.reshape(b, q_len * heads, width), pool)
    return out.reshape(b, q_len, heads, rank)


def paged_attention_latent_reference(q, pool, layer: int, block_tables,
                                     context_lens, q_lens, *, rank: int,
                                     scale: float):
    """Pure-jnp ground truth of ``paged_attention_latent``: materialize
    the gather, dense masked softmax, float32. Tests only."""
    b, q_len, heads, width = q.shape
    rows = jnp.take(pool[layer], block_tables, axis=0).reshape(
        b, -1, width).astype(jnp.float32)                 # [b, S, W]
    s = jnp.einsum("bqhw,bsw->bqhs", q.astype(jnp.float32), rows) * scale
    k_pos = jnp.arange(rows.shape[1])[None, None, None, :]
    ctx = context_lens[:, None, None, None]
    qi = jnp.arange(q_len)[None, :, None, None]
    bound = jnp.minimum(ctx, ctx - q_lens[:, None, None, None] + 1 + qi)
    p = jax.nn.softmax(jnp.where(k_pos < bound, s, NEG_INF), axis=-1)
    return jnp.einsum("bqhs,bsr->bqhr", p,
                      rows[..., :rank]).astype(q.dtype)
