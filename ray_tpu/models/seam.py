"""The serving seam: what the generation engine and a model family say
to each other, and the layout of the pools both read. It imports no
family and nothing of ``ray_tpu.llm``; the engine and the cache manager
take its names from ``ray_tpu.models``.

**The serving seam.** The generation engine (llm/engine.py), the paged
pools (llm/kv_cache.py), the cost model (util/perfmodel.py) and the
Serve deployment (serve/llm.py) know no model: they ask ``serving(cfg)``
for what the configuration's own module says of it, a ``Serving``:

  init      ``init(key, cfg)``: the parameters, as a checkpoint of the
            family holds them
  at_rest   ``at_rest(params)``: the parameters as the two programs
            READ them, which the engine makes once when it is built and
            keeps in place of what it was given (``served_params``).
            None where ``init``'s tree is that already (Laguna, Kimi,
            Nemotron, Xing4.0, Granite: ``cfg.dtype`` leaves). GPT's
            ``init`` makes a trainer's float32 master weights and its
            programs round
            each to ``cfg.dtype`` in front of its product, so its
            ``at_rest`` does that rounding, once (models/gpt.py
            ``params_at_rest``); a tree that is already as read comes
            back itself
  step      the decode step, ``(params, packed, *pools, *window pools,
            q=, firsts=, cfg=)`` -> ``(logits, ids, *pools, *window
            pools)``. ``packed`` is ONE int32 array ``[max_batch, W]``,
            a lane a row: the step's whole bookkeeping side by side
            (``step_columns``), which the engine keeps current from
            step to step and the program takes apart by static slices
            (``unpack_step``). ``q`` is the rows a lane, a Python int:
            a shape of the program, not a value in it. ``firsts`` is
            an int32 ``[max_batch]`` DEVICE array: where it is not
            negative it is the lane's row-0 token, which a chunk
            program queued before this step decided and the host has
            not seen (``unpack_step``); -1 takes the packed array's
  chunk     one span of a prompt as ONE program, ``(params, tokens,
            *pools, table, *window, cfg=)`` ->
            ``(row, id, *pools, *window pools)``. It writes the span's
            rows into the pools (donated, like the step's) and hands
            back the logits of the span's last real token and their
            argmax. ``table`` is one int32 array, ``[block table |
            destination blocks | ctx_len | last]`` (``pack_span``): a
            chunk's whole bookkeeping in one hand-over.
            Where ``chunk_spans`` is more than 1 the program takes up
            to that many spans, of as many sequences, in ONE call:
            ``tokens`` [1, n] are the spans' rows end to end, each in
            whole blocks; ``table`` is ``pack_spans``' array, ``[ONE
            context table: the spans' context blocks end to end |
            destination blocks | chunk_spans x (first row, first
            context slot, ctx_len, rows)]``; it hands back ``(rows
            [chunk_spans, vocab], ids [chunk_spans], *pools)``, the
            head on each span's last real row. Its shapes follow from
            n and from whether there is a table at all, never from how
            the spans divide the rows
  chunk_spans  the spans one call of ``chunk`` takes (1: one, and
            ``pack_span``). The engine reads it once, when it is built,
            and fills a program with the spans a step's budget buys
            (llm/engine.py ``_run_prefills``). The latent family takes
            4 (models/kimi_k2.py); the field, and ``pack_span`` with it,
            go when the other families' chunks take several too
            (ROADMAP A3 (c))
  kinds     the cache description: one ``LayerKind`` a kind of layer,
            which says what a token leaves in the cache there: how many
            pools the kind has and how wide a row of each is
            (``LayerKind.rows``). Keys and values are two pools of
            ``kv_heads * head_dim`` (models/gpt.py, models/laguna.py);
            latent attention is ONE pool of ``kv_lora_rank +
            qk_rope_head_dim`` (models/kimi_k2.py). ``kinds[0]`` keeps
            every token of a sequence (``*pools`` above are its pools,
            in ``rows``' order); a second kind, if there is one, has a
            ``window`` and keeps only the blocks that cover a
            sequence's last ``window`` tokens. Its pools ride after
            the full kind's; a step's packed array has its columns
            too, and a chunk takes its int32 array ``win`` after them
            (models/laguna.py: the table, its first block, the blocks
            the span is written to).
  state     what a SEQUENCE keeps, whatever its length, or None (the
            attention families): a ``StateKind``, which names the
            layers that carry a state (where every layer also holds
            something that keeps nothing, as Granite 4.0-H's expert
            blocks, the layers whose MIXER carries one) and the parts
            of one layer's (shape and dtype: a state-space layer's recurrent state and
            the last rows of its convolution's input). The cache
            manager holds them in pools of SLOTS, ``[layers, slots,
            *part]`` (llm/kv_cache.py ``StatePool``): a live lane has a
            slot, the prefix index parks snapshots in others. The
            pools ride behind every kind's in both programs (donated,
            handed back written); the step's packed array has a column
            for each lane's slot (``step_columns(..., state=True)``,
            ``step_state_slots``), slot 0 being scratch as block 0 is;
            a chunk's table ends in two slots (``pack_span``'s
            ``extra``): the one the span's initial state is read from
            (a parked snapshot's, for the first span behind a prefix
            hit) and the lane's own, which its final state is written
            to. A span from position 0 starts from zeros whatever the
            slot holds, so a slot's next tenant never sees the last
            one's state
  cost      the cost description util/perfmodel.py prices steps from
  counters  names of the int32 counters the step program appends to its
            ``ids`` as rows ``[max_batch + i]``: they ride in the one
            fetch a decode step makes

A model is served by defining ``serving(cfg)`` in the module of its
configuration class.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class LayerKind:
    """One kind of attention layer, as the cache manager sees it."""
    name: str                   # "full" | "window"
    layers: Tuple[int, ...]     # the model's layers of this kind, in order
    # What a token leaves in a layer of this kind: one pool an entry,
    # the entry the width of the token's row there. Keys and values:
    # (kv_heads * head_dim,) * 2; a latent row: (rank + rope dims,).
    rows: Tuple[int, ...]
    window: Optional[int]       # tokens a layer attends; None = all
    dtype: Any

    @property
    def kv_width(self) -> int:
        """A row of the kind's first pool (of a kind of keys and
        values: a token's K of every head, and its V is as wide)."""
        return self.rows[0]


def keys_and_values(name: str, layers, kv_heads: int, head_dim: int,
                    window: Optional[int], dtype) -> LayerKind:
    """The kind of layer that keeps a token's keys and its values of
    whole heads: two pools of ``kv_heads * head_dim``."""
    return LayerKind(name, tuple(layers), (kv_heads * head_dim,) * 2,
                     window, dtype)


@dataclass(frozen=True)
class StateKind:
    """What a sequence keeps in the layers that carry a state, as the
    cache manager sees it: one pool a part, ``[layers, slots, *shape]``."""
    layers: Tuple[int, ...]     # the model's layers with a state, in order
    parts: Tuple[Tuple[Tuple[int, ...], Any], ...]  # (shape, dtype) a part

    @property
    def slot_bytes(self) -> int:
        """Bytes of one slot: every part of every layer."""
        return len(self.layers) * sum(
            int(np.prod(shape)) * np.dtype(dtype).itemsize
            for shape, dtype in self.parts)


@dataclass(frozen=True)
class Serving:
    init: Callable
    step: Callable
    chunk: Callable
    kinds: Tuple[LayerKind, ...]
    cost: dict
    max_seq: int
    vocab_size: int
    counters: Tuple[str, ...] = ()
    state: Optional[StateKind] = None
    at_rest: Optional[Callable] = None
    chunk_spans: int = 1


def pack_span(block_table, dest, ctx_len: int, last: int, *extra: int):
    """A chunk's bookkeeping as the ONE int32 array ``Serving.chunk``
    takes: ``[block table (nb, 0-padded; nb = 0 for a span from the
    prompt's start) | destination blocks (one a block of the span) |
    ctx_len | last]``, and behind them what else the model's chunk
    takes by value (``extra``: a model with a state, the slot it reads
    and the slot it writes). Each host array handed to a program is a
    hand-over of the interpreter lock beside the serving threads
    (PERF.md section 6, PR 30), so they all ride in one."""
    return np.concatenate([block_table, dest, (ctx_len, last, *extra)],
                          dtype=np.int32)


def unpack_span(table, n: int, block_size: int, extra: int = 0):
    """``pack_span``'s array, inside the program, back into its four
    parts (and its ``extra`` trailing values, where the model's chunk
    has any). ``n`` is the span's padded length, so the block table's
    length follows from the array's own: a shape, not a value."""
    nd = n // block_size
    nb = table.shape[0] - nd - 2 - extra
    four = (table[:nb], table[nb:nb + nd], table[-2 - extra],
            table[-1 - extra])
    return four + tuple(table[-extra:]) if extra else four


def pack_spans(spans, max_nb: int, block_size: int, chunk_spans: int):
    """The bookkeeping of a chunk program that carries several spans
    (``Serving.chunk_spans`` > 1), as its ONE int32 array. ``spans``:
    up to ``chunk_spans`` of ``(context blocks, destination blocks,
    ctx_len, rows)`` in the order their rows lie in ``tokens``: the
    blocks that hold the span's ``ctx_len`` tokens of context (none
    for a span from its prompt's start), the blocks its rows are
    written to (one a block of the span, padding included), its real
    rows. Returns ``[context table | destination blocks | chunk_spans
    x (first row, first context slot, ctx_len, rows)]``: the table is
    ``max_nb`` blocks, the spans' context blocks end to end and zeros
    behind them (the caller sees that they fit), or NO blocks for a
    lone span from its prompt's start, as ``pack_span``'s is; a span
    not used has no rows and starts where the rows end. So the array's
    length says how long the table is, given the rows
    (``unpack_spans``), and nothing else about the spans is a shape."""
    bs = block_size
    table = np.zeros((max_nb if len(spans) > 1 or len(spans[0][0]) else 0,),
                     np.int32)
    dest, quads, row, nb = [], np.zeros((chunk_spans, 4), np.int32), 0, 0
    for s, (context, blocks, ctx_len, rows) in enumerate(spans):
        table[nb:nb + len(context)] = context
        dest.extend(blocks)
        quads[s] = row, nb * bs, ctx_len, rows
        row += len(blocks) * bs
        nb += len(context)
    quads[len(spans):, 0] = row
    return np.concatenate([table, dest, quads.reshape(-1)], dtype=np.int32)


def unpack_spans(table, n: int, block_size: int, chunk_spans: int):
    """``pack_spans``' array, inside the program, by static slices:
    ``(context table [nb], destination blocks [n / block_size], spans
    [chunk_spans, 4])``, a span's row ``(first row, first context
    slot, ctx_len, rows)``."""
    nd = n // block_size
    nb = table.shape[0] - nd - 4 * chunk_spans
    return (table[:nb], table[nb:nb + nd],
            table[nb + nd:].reshape(chunk_spans, 4))


def span_rows(spans, n: int):
    """What each of a packed program's ``n`` rows is, from
    ``unpack_spans``' ``spans``: ``(first, slot, ctx_len, real)``,
    int32 ``[n]`` the first three (the first row of the row's span,
    its first context slot, its context length) and bool ``[n]`` the
    last (a row of the span's ``rows``, not of its padding). Row ``i``
    sits at position ``ctx_len + i - first`` of its sequence and sees
    the context slots ``[slot, slot + ctx_len)`` and the rows
    ``first..i``."""
    i = jnp.arange(n, dtype=jnp.int32)
    # Spans lie in row order; one that is not used starts at n.
    of = (i[:, None] >= spans[None, :, 0]).sum(-1) - 1
    first, slot, ctx_len, rows = (spans[of, c] for c in range(4))
    return first, slot, ctx_len, i - first < rows


class StepColumns(NamedTuple):
    """Where each part of a lane's row of the packed step array starts
    (``step_columns``). Columns ``[0, head)`` change every step; the
    tables behind them only where a block is granted or given back."""
    tokens: int         # each per-row part is q columns wide
    positions: int
    slot_blocks: int
    slot_offsets: int
    win_slots: int      # the window kind's slot block a row (q wide, or 0)
    context_len: int
    q_len: int
    head: int           # the end of what a step writes
    state_slot: int     # the lane's state slot (one column, or none)
    win_first: int      # the window table's first block in the sequence
    win_table: int
    table: int          # the full kind's block table, to the row's end


@functools.lru_cache(maxsize=None)
def step_columns(q: int, win_len: int = 0, state: bool = False
                 ) -> StepColumns:
    """The packed step array's layout, from shapes alone: ``[tokens |
    positions | slot blocks | slot offsets (q each) | window slot
    blocks (q) | context_len | q_len | state slot | window first |
    window table (win_len) | block table]``, the window kind's three
    parts only where the model has that kind and the state slot only
    where its sequences keep a state. A padded lane, and a row past a
    lane's ``q_len``, is all zeros (scratch block 0, offset 0,
    position 0, scratch slot 0) but for ``context_len`` 1 and ``q_len``
    1."""
    ctx = (5 if win_len else 4) * q
    win = win_len + 1 if win_len else 0
    head = ctx + 2
    tail = head + bool(state)
    return StepColumns(tokens=0, positions=q,
                       slot_blocks=2 * q, slot_offsets=3 * q,
                       win_slots=4 * q, context_len=ctx, q_len=ctx + 1,
                       head=head, state_slot=head, win_first=tail,
                       win_table=tail + bool(win_len),
                       table=tail + win)


def pack_step(tokens, positions, block_tables, context_lens, q_lens,
              slot_blocks, slot_offsets, win=None, state_slots=None):
    """A decode step's bookkeeping, built from its parts, as the ONE
    int32 array ``Serving.step`` takes (``step_columns``): ``tokens`` /
    ``positions`` / ``slot_blocks`` / ``slot_offsets`` ``[b, q]``,
    ``block_tables`` ``[b, max_nb]``, ``context_lens`` / ``q_lens``
    ``[b]``, and the window kind's ``win`` ``[b, win_len + 1 + q]``
    (``[table | first block | slot block a row]``); ``state_slots``
    ``[b]`` where the model's sequences keep a state. The engine never
    calls this in a step: it keeps its array and writes what changed
    (llm/engine.py); tests and tools build one from scratch here."""
    q = np.shape(tokens)[1]
    col = lambda x: np.asarray(x, np.int32)[:, None]
    parts = [tokens, positions, slot_blocks, slot_offsets]
    tail = []
    if win is not None:
        win = np.asarray(win, np.int32)
        n = win.shape[1] - 1 - q
        parts.append(win[:, n + 1:])
        tail = [win[:, n:n + 1], win[:, :n]]
    if state_slots is not None:
        tail.insert(0, col(state_slots))
    return np.concatenate(
        [*parts, col(context_lens), col(q_lens), *tail, block_tables],
        axis=1, dtype=np.int32)


def step_state_slots(packed, q: int, win_len: int = 0):
    """Each lane's state slot ``[b]``, from a packed array laid out
    with ``step_columns(q, win_len, state=True)``."""
    return packed[:, step_columns(q, win_len, True).state_slot]


def unpack_step(packed, q: int, win_len: int = 0, firsts=None,
                state: bool = False):
    """``step_columns``' array, inside the program, back into its
    parts by static slices: ``(tokens, positions, block_tables,
    context_lens, q_lens, slot_blocks, slot_offsets, window)``, where
    ``window`` is ``(table, first, slot_blocks)`` of the kind of layer
    with a window and None without one. ``firsts`` (``Serving.step``)
    takes the place of a lane's row-0 token where it is not negative:
    the one value of a new lane that the host does not hold when it
    queues the step behind the lane's last prefill chunk. ``state``
    says the array has a state-slot column (``step_state_slots``)."""
    c = step_columns(q, win_len, state)
    if firsts is not None:
        packed = packed.at[:, c.tokens].set(
            jnp.where(firsts >= 0, firsts, packed[:, c.tokens]))
    part = lambda start: packed[:, start:start + q]
    window = None
    if win_len:
        window = (packed[:, c.win_table:c.table], packed[:, c.win_first],
                  part(c.win_slots))
    return (part(c.tokens), part(c.positions), packed[:, c.table:],
            packed[:, c.context_len], packed[:, c.q_len],
            part(c.slot_blocks), part(c.slot_offsets), window)


def scatter_span(pools, spans, ids, rows=None):
    """THE pool write of a prefill span, a pure function: the chunk
    program (a model's ``forward_prefill_chunk``) calls it on the pools
    it was donated, and ``PagedKVCache.write_prefill`` through the
    jitted ``kv_scatter_blocks`` (llm/kv_cache.py).

    ``pools``: a kind's pools (``LayerKind.rows``: keys and values, or
    one pool of latent rows), each ``[L, NB, BS, W_i]``. ``spans``: the
    span's rows for each pool, in the pool's own order: any shape that
    flattens to ``[L, T, W_i]`` will do (``[L, T, kv_heads, head_dim]``,
    whole blocks ``[L, nb, BS, W_i]``), T <= len(ids) * BS. ``ids``
    [nb] int32: the blocks written, in the span's order. The rows from
    ``rows`` on (a traced scalar or an int; None = T; or a bool
    ``[len(ids) * BS]``, which rows are real, where a program carries
    several spans and each has a tail) and the tail past T are written
    as ZEROS, masked by context_lens at read time,
    so a pool's contents do not depend on what a chunk was padded
    with. Several ids may name the scratch block 0 (a window kind's
    blocks that slid out before they were written): which of them
    lands there is nobody's business, the block is never read
    unmasked. One in-place scatter a pool when the pools are donated:
    the indexed dimension is the pool's major one after the layers.
    Returns the pools, written, as a tuple."""
    n = ids.shape[0] * pools[0].shape[2]

    def blocks(x, pool):
        L, _, bs, W = pool.shape
        x = x.reshape(L, -1, W)
        if n > x.shape[1]:
            x = jnp.pad(x, ((0, 0), (0, n - x.shape[1]), (0, 0)))
        if rows is not None:
            real = rows if jnp.ndim(rows) else jnp.arange(n) < rows
            x = jnp.where(real[None, :, None], x, 0)
        return x.reshape(L, -1, bs, W).astype(pool.dtype)

    return tuple(pool.at[:, ids].set(blocks(x, pool))
                 for pool, x in zip(pools, spans))


def window_table_len(window: int, block_size: int, rows: int = 1) -> int:
    """Most blocks a lane holds of a kind of layer with a window while
    ``rows`` new tokens are written (``WindowPool``: the window, one
    block's worth of positions of slack, wherever they start in a
    block): window / block_size + 2 for one row."""
    return -(-(window - 1 + rows) // block_size) + 2
