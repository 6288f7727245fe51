"""Model families: the GPT decoder (models/gpt.py, trained and served),
Laguna (models/laguna.py, served: window and full attention layers,
routed experts), Kimi-K2 (models/kimi_k2.py, served: latent
attention over one pool of latent rows, a share of sigmoid-routed
experts), Nemotron-H (models/nemotron_h.py, served: state-space
layers that keep a fixed state a SEQUENCE beside one attention layer's
keys and values, a share of not-gated experts in a latent space),
Xing4.0 (models/xing4.py, served: Kimi-K2's attention, router and
experts on a residual path of four streams whose mixing weights are
made from the token, ops/mhc.py) and Granite 4.0-H
(models/granite_hybrid.py, served: a Mamba-2 or attention mixer AND a
share of routed experts with a shared MLP in EVERY layer, so the pools'
layers go by mixer kind; four muP multipliers, the attention's softmax
scale among them, and a tied head).

Models are pure-JAX functional: ``init(key, cfg)`` returns the param pytree;
``param_axes(cfg)`` returns the matching pytree of logical-axis annotations
consumed by parallel/sharding.py; ``forward``/``loss_fn`` are jit-friendly
and ``make_train_step`` builds the compiled SPMD training step.

**The serving seam** (models/seam.py, re-exported here): the engine,
the paged pools, the cost model and the Serve deployment know no model;
they ask ``serving(cfg)`` for what the configuration's own module says
of it, a ``Serving``. What the served families share lies below them,
each part once: models/layers.py and models/mamba2.py. A family imports
those and the seam, no family's private name, and nothing of
``ray_tpu.llm`` (``rtpu lint`` I411).

A model is served by defining ``serving(cfg)`` in the module of its
configuration class.
"""

from __future__ import annotations

import functools
import importlib

from . import (gpt, granite_hybrid, kimi_k2, laguna,  # noqa: F401
               nemotron_h, resnet, xing4)
from .seam import (LayerKind, Serving, StateKind, StepColumns,  # noqa: F401
                   keys_and_values, pack_span, pack_spans, pack_step,
                   scatter_span, span_rows, step_columns, step_state_slots,
                   unpack_span, unpack_spans, unpack_step, window_table_len)


@functools.lru_cache(maxsize=64)
def serving(cfg) -> Serving:
    """What the module of ``cfg``'s class says of serving it."""
    return importlib.import_module(type(cfg).__module__).serving(cfg)


def served_params(params, cfg):
    """``params`` as ``cfg``'s family keeps them while they are served
    (``Serving.at_rest``): what an engine, or a draft model's proposer,
    holds and hands its programs."""
    at_rest = serving(cfg).at_rest
    return params if at_rest is None else at_rest(params)

