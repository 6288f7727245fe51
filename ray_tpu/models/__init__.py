"""Model families: the GPT decoder (models/gpt.py, trained and served),
Laguna (models/laguna.py, served: window and full attention layers,
routed experts) and Kimi-K2 (models/kimi_k2.py, served: latent
attention over one pool of latent rows, a share of sigmoid-routed
experts).

Models are pure-JAX functional: ``init(key, cfg)`` returns the param pytree;
``param_axes(cfg)`` returns the matching pytree of logical-axis annotations
consumed by parallel/sharding.py; ``forward``/``loss_fn`` are jit-friendly
and ``make_train_step`` builds the compiled SPMD training step.

**The serving seam.** The generation engine (llm/engine.py), the paged
pools (llm/kv_cache.py), the cost model (util/perfmodel.py) and the
Serve deployment (serve/llm.py) know no model: they ask ``serving(cfg)``
for what the configuration's own module says of it, a ``Serving``:

  init      ``init(key, cfg)``: the parameters as served
  step      the decode step, ``(params, tokens, positions, *pools,
            block_tables, context_lens, q_lens, slot_blocks,
            slot_offsets, *window, cfg=)`` ->
            ``(logits, ids, *pools, *window pools)``
  chunk     one span of a prompt as ONE program, ``(params, tokens,
            *pools, table, *window, cfg=)`` ->
            ``(row, id, *pools, *window pools)``. It writes the span's
            rows into the pools (donated, like the step's) and hands
            back the logits of the span's last real token and their
            argmax. ``table`` is one int32 array, ``[block table |
            destination blocks | ctx_len | last]`` (``pack_span``): a
            chunk's whole bookkeeping in one hand-over
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
            sequence's last ``window`` tokens. Its pools and its int32
            array ride after the full kind's arguments (``*window``:
            its pools, then ``win``; see models/laguna.py for the
            array, which in a chunk also names the blocks the span is
            written to).
  cost      the cost description util/perfmodel.py prices steps from
  counters  names of the int32 counters the step program appends to its
            ``ids`` as rows ``[max_batch + i]``: they ride in the one
            fetch a decode step makes

A model is served by defining ``serving(cfg)`` in the module of its
configuration class.
"""

from __future__ import annotations

import functools
import importlib
from dataclasses import dataclass
from typing import Any, Callable, Optional, Tuple

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
class Serving:
    init: Callable
    step: Callable
    chunk: Callable
    kinds: Tuple[LayerKind, ...]
    cost: dict
    max_seq: int
    vocab_size: int
    counters: Tuple[str, ...] = ()


def pack_span(block_table, dest, ctx_len: int, last: int):
    """A chunk's bookkeeping as the ONE int32 array ``Serving.chunk``
    takes: ``[block table (nb, 0-padded; nb = 0 for a span from the
    prompt's start) | destination blocks (one a block of the span) |
    ctx_len | last]``. Each host array handed to a program is a
    hand-over of the interpreter lock beside the serving threads
    (PERF.md section 6, PR 30), so the four ride in one."""
    return np.concatenate([block_table, dest, (ctx_len, last)],
                          dtype=np.int32)


def unpack_span(table, n: int, block_size: int):
    """``pack_span``'s array, inside the program, back into its four
    parts. ``n`` is the span's padded length, so the block table's
    length follows from the array's own: a shape, not a value."""
    nd = n // block_size
    nb = table.shape[0] - nd - 2
    return table[:nb], table[nb:nb + nd], table[-2], table[-1]


@functools.lru_cache(maxsize=64)
def serving(cfg) -> Serving:
    """What the module of ``cfg``'s class says of serving it."""
    return importlib.import_module(type(cfg).__module__).serving(cfg)


from . import gpt, kimi_k2, laguna, resnet  # noqa: E402,F401
