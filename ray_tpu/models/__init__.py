"""Model families: the GPT decoder (models/gpt.py, trained and served)
and Laguna (models/laguna.py, served: window and full attention layers,
routed experts).

Models are pure-JAX functional: ``init(key, cfg)`` returns the param pytree;
``param_axes(cfg)`` returns the matching pytree of logical-axis annotations
consumed by parallel/sharding.py; ``forward``/``loss_fn`` are jit-friendly
and ``make_train_step`` builds the compiled SPMD training step.

**The serving seam.** The generation engine (llm/engine.py), the paged
pools (llm/kv_cache.py), the cost model (util/perfmodel.py) and the
Serve deployment (serve/llm.py) know no model: they ask ``serving(cfg)``
for what the configuration's own module says of it, a ``Serving``:

  init      ``init(key, cfg)``: the parameters as served
  step      the decode step, ``(params, tokens, positions, k_pool,
            v_pool, block_tables, context_lens, q_lens, slot_blocks,
            slot_offsets, *window, cfg=)`` ->
            ``(logits, ids, k_pool, v_pool, *window pools)``
  chunk     one span of a prompt, ``(params, tokens, positions, k_pool,
            v_pool, block_table, ctx_len, *window, cfg=)`` ->
            ``(logits, k, v, *window k and v)``
  kinds     the cache description: one ``LayerKind`` a kind of layer.
            ``kinds[0]`` keeps every token of a sequence (its pools are
            ``k_pool`` / ``v_pool`` above); a second kind, if there is
            one, has a ``window`` and keeps only the blocks that cover a
            sequence's last ``window`` tokens. Its pools and its int32
            array ride after the full kind's arguments (``*window``:
            ``k_win, v_win, win``; see models/laguna.py for the array).
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


@dataclass(frozen=True)
class LayerKind:
    """One kind of attention layer, as the cache manager sees it."""
    name: str                   # "full" | "window"
    layers: Tuple[int, ...]     # the model's layers of this kind, in order
    kv_heads: int
    head_dim: int
    window: Optional[int]       # tokens a layer attends; None = all
    dtype: Any

    @property
    def kv_width(self) -> int:
        """A token's K (or V) of every head: one row of the pool."""
        return self.kv_heads * self.head_dim


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


@functools.lru_cache(maxsize=64)
def serving(cfg) -> Serving:
    """What the module of ``cfg``'s class says of serving it."""
    return importlib.import_module(type(cfg).__module__).serving(cfg)


from . import gpt, laguna, resnet  # noqa: E402,F401
