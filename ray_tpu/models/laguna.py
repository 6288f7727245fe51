"""Laguna (poolside, https://huggingface.co/poolside/Laguna-XS.2): a
decoder whose layers are of two kinds of attention and two kinds of
MLP, served through the generation engine (llm/engine.py). No loss and
no train step: this model is served, not trained, here.

The layer (x is [T, hidden]; layer l has kind ``layer_types[l]`` and
``H_l = num_attention_heads_per_layer[l]`` query heads over
``num_key_value_heads`` KV heads of ``head_dim``; no biases; RMSNorm):

  h = RMSNorm(x); q = h W_q [T, H_l, d]; k = h W_k, v = h W_v [T, kv, d];
  g = sigmoid(h W_g) [T, H_l]                      one gate a head
  rotary on q and k. "full_attention": the first ``partial_rotary_factor``
    of a head's dims, YaRN inverse frequencies, cos and sin scaled by
    ``attention_factor``; "sliding_attention": every dim, unscaled.
    Pairs are (i, i + rot/2), the ``rotate_half`` convention.
  query head h reads KV head h // (H_l / kv); scores q k^T / sqrt(d),
    causal; on a sliding layer query i sees key j only where
    i - j < sliding_window. o = softmax(scores) v;
  x <- x + concat_h(g_h * o_h) W_o
  h2 = RMSNorm(x). "dense": (silu(h2 W_gate) * h2 W_up) W_down.
    "sparse": p = softmax(h2 W_r) over all experts in float32; the
    ``num_experts_per_tok`` largest; w = moe_routed_scaling_factor *
    p_top / sum(p_top); out = sum_e w_e Expert_e(h2) + Shared(h2), each
    a SwiGLU, the weight on the expert's output (ops/moe.py).
  x <- x + out
Final RMSNorm, untied head.

Keys are stored AFTER rotary, so a cached key needs no position again
and a window layer's table needs no order. ``models/laguna_ref.py`` is
the plain float32 reference of the same equations.

Behind the serving seam (models/seam.py) the cache has two kinds:
"full" layers keep every token, "window" layers the blocks that cover
a sequence's last ``sliding_window`` tokens. The layers are unrolled,
the kinds interleaved as published; layer i of a kind lives at index i
of that kind's pool ``[layers_of_kind, num_blocks, block_size,
kv_heads * head_dim]``. The decode step's paged call
(ops/pallas/paged_fetch.py ``paged_attention_stored``) copies its
pages out of that pool as stored, a run of table-adjacent pages in one
copy: at head_dim 128 a row is whole lane tiles, and no head-major view
is made. A prefill chunk gathers its
table's slots and attends over [them ++ the chunk] in the
``chunk_attn`` kernel (ops/pallas/chunk_attention.py), both kinds.

The window kind's part of a decode step's bookkeeping, in a lane's row
of the step's one packed array (models/seam.py ``step_columns``):
its table (``window_table_len`` entries: the blocks from the window's
oldest on), that first block's index in the sequence, and a slot block
a row, where each row's K/V is written. A chunk takes an int32 array of
its own (``win``): ``[table, first block, destination block a block of
the chunk]``.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Any, Tuple

import jax
import jax.numpy as jnp

from ..ops import moe
from ..ops.pallas.paged_fetch import (kv_pages_in_runs_x1000,
                                      paged_attention_stored)
from .layers import (head, init_ends, normal, pool_index, rmsnorm, rotary,
                     span_attention, swiglu)
from .seam import (Serving, keys_and_values, scatter_span, unpack_span,
                   unpack_step, window_table_len)

FULL, SLIDING = "full_attention", "sliding_attention"
DENSE, SPARSE = "dense", "sparse"


@dataclass(frozen=True)
class LagunaConfig:
    """Field names are the published config.json's; ``max_seq`` is the
    deployment's limit and ``dtype`` what weights, activations and KV
    are held in. Lists arrive from JSON and are frozen to tuples; the
    two ``rope_*`` entries are (key, value) pairs of the published
    ``rope_parameters`` groups."""
    vocab_size: int = 100352
    hidden_size: int = 2048
    intermediate_size: int = 8192
    num_hidden_layers: int = 40
    num_attention_heads_per_layer: Tuple[int, ...] = ()
    num_key_value_heads: int = 8
    head_dim: int = 128
    rms_norm_eps: float = 1e-6
    num_experts: int = 256
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    moe_routed_scaling_factor: float = 2.5
    sliding_window: int = 512
    layer_types: Tuple[str, ...] = ()
    mlp_layer_types: Tuple[str, ...] = ()
    rope_full: Any = (("rope_theta", 500000.0), ("rope_type", "yarn"),
                      ("factor", 64.0),
                      ("original_max_position_embeddings", 4096),
                      ("beta_slow", 1.0), ("beta_fast", 64.0),
                      ("attention_factor", 1.4158883083359672),
                      ("partial_rotary_factor", 0.5))
    rope_sliding: Any = (("rope_type", "default"), ("rope_theta", 10000.0),
                         ("partial_rotary_factor", 1.0))
    max_seq: int = 9216
    dtype: Any = "bfloat16"

    def __post_init__(self):
        def freeze(v):
            if isinstance(v, dict):
                return tuple(sorted((k, freeze(x)) for k, x in v.items()))
            if isinstance(v, (list, tuple)):
                return tuple(freeze(x) for x in v)
            return v

        for name in ("num_attention_heads_per_layer", "layer_types",
                     "mlp_layer_types", "rope_full", "rope_sliding"):
            object.__setattr__(self, name, freeze(getattr(self, name)))
        object.__setattr__(self, "dtype", jnp.dtype(self.dtype))
        L = self.num_hidden_layers
        for name in ("num_attention_heads_per_layer", "layer_types",
                     "mlp_layer_types"):
            if len(getattr(self, name)) != L:
                raise ValueError(f"{name} needs {L} entries, one a layer")

    def layers_of(self, kind: str) -> Tuple[int, ...]:
        return tuple(l for l, t in enumerate(self.layer_types) if t == kind)

    def num_params(self) -> int:
        m, d, kv = self.hidden_size, self.head_dim, self.num_key_value_heads
        n = 2 * self.vocab_size * m + m
        for h, mlp in zip(self.num_attention_heads_per_layer,
                          self.mlp_layer_types):
            n += 2 * m + m * h * d * 2 + 2 * m * kv * d + m * h
            if mlp == DENSE:
                n += 3 * m * self.intermediate_size
            else:
                n += (m * self.num_experts
                      + 3 * m * self.moe_intermediate_size * self.num_experts
                      + 3 * m * self.shared_expert_intermediate_size)
        return n


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def init(key, cfg: LagunaConfig) -> dict:
    """Seeded random parameters in ``cfg.dtype``, a layer at a time
    (``init_layer``), so that a reader that cannot hold the model twice
    can make one layer again from the same key. Each distinct layer
    shape is one compiled program (a layer's tensors made leaf by leaf
    took 42 s for this model's 3.9 B parameters on the chip)."""
    return {
        **init_ends(jax.random.fold_in(key, 1 << 20), cfg),
        "layers": [init_layer(key, cfg, l)
                   for l in range(cfg.num_hidden_layers)],
    }


def init_layer(key, cfg: LagunaConfig, l: int) -> dict:
    """Layer ``l``'s parameters, from ``fold_in(key, l)``."""
    return _init_layer(jax.random.fold_in(key, l), cfg,
                       cfg.num_attention_heads_per_layer[l],
                       cfg.mlp_layer_types[l])


@functools.partial(jax.jit, static_argnames=("cfg", "h", "mlp"))
def _init_layer(key, cfg: LagunaConfig, h: int, mlp: str) -> dict:
    m, d, kv = cfg.hidden_size, cfg.head_dim, cfg.num_key_value_heads
    dt = cfg.dtype
    out_std = 0.02 / math.sqrt(2 * cfg.num_hidden_layers)
    k = iter(jax.random.split(key, 12))
    p = {
        "ln1": jnp.ones((m,), dt), "ln2": jnp.ones((m,), dt),
        "wq": normal(next(k), (m, h, d), dt),
        "wk": normal(next(k), (m, kv, d), dt),
        "wv": normal(next(k), (m, kv, d), dt),
        "wg": normal(next(k), (m, h), dt),
        "wo": normal(next(k), (h, d, m), dt, out_std),
    }
    if mlp == DENSE:
        f = cfg.intermediate_size
        p["w_gu"] = normal(next(k), (m, 2 * f), dt)
        p["w_down"] = normal(next(k), (f, m), dt, out_std)
    else:
        E, f = cfg.num_experts, cfg.moe_intermediate_size
        fs = cfg.shared_expert_intermediate_size
        p["router"] = normal(next(k), (m, E), dt)
        p["w1"] = normal(next(k), (E, m, 2 * f), dt)
        p["w2"] = normal(next(k), (E, f, m), dt, out_std)
        p["s_gu"] = normal(next(k), (m, 2 * fs), dt)
        p["s_down"] = normal(next(k), (fs, m), dt, out_std)
    return p


# ---------------------------------------------------------------------------
# The layer
# ---------------------------------------------------------------------------


def _mlp(h2, p, cfg: LagunaConfig, program: str):
    """h2 [T, m] -> (out [T, m], (experts hit, busiest expert's tokens)
    or None for a dense layer). The grouped product's kernel is
    ``moe_experts_<program>`` on a device trace, so a reader can tell a
    decode step's from a chunk's by name."""
    if "router" not in p:
        return swiglu(h2, p["w_gu"], p["w_down"]), None
    with jax.named_scope("moe_route"):
        _, experts, weights = moe.route(
            h2, p["router"], cfg.num_experts_per_tok,
            cfg.moe_routed_scaling_factor)
    with jax.named_scope("moe_experts"):
        y, sizes = moe.routed_experts(h2, experts, weights, p["w1"], p["w2"],
                                      name=f"moe_experts_{program}")
    out = y + swiglu(h2, p["s_gu"], p["s_down"])
    return out, ((sizes > 0).sum().astype(jnp.int32), sizes.max())


def _block(x, p, cfg: LagunaConfig, attend, program: str):
    """The one layer, on [batch, rows, m]: norm -> gated attention ->
    residual -> norm -> MLP -> residual. ``attend(q, k, v)`` is the
    mode's attention: it rotates q [b, r, H, d] and this call's own k
    [b, r, kv, d], keeps k and v where the mode keeps them, and returns
    o [b, r, H, d]. Returns (x, routing counts or None)."""
    b, r, m = x.shape
    h = rmsnorm(x, p["ln1"], cfg.rms_norm_eps)
    q = jnp.einsum("brm,mhd->brhd", h, p["wq"])
    k = jnp.einsum("brm,mhd->brhd", h, p["wk"])
    v = jnp.einsum("brm,mhd->brhd", h, p["wv"])
    gate = jax.nn.sigmoid(jnp.einsum("brm,mh->brh", h, p["wg"])
                          .astype(jnp.float32))
    o = attend(q, k, v)
    o = (o.astype(jnp.float32) * gate[..., None]).astype(x.dtype)
    x = x + jnp.einsum("brhd,hdm->brm", o, p["wo"])
    h2 = rmsnorm(x, p["ln2"], cfg.rms_norm_eps)
    out, counts = _mlp(h2.reshape(b * r, m), p, cfg, program)
    return x + out.reshape(b, r, m), counts


def _kind_index(cfg: LagunaConfig):
    """layer -> (is_window, index in its kind's pool)."""
    return [(t == SLIDING, i) for t, i in
            zip(cfg.layer_types, pool_index(cfg.layer_types))]


def _counters(counts, n_assignments: int, n_experts: int, q: int, in_runs):
    """The step's counter rows [3, q] int32 (``COUNTERS``): experts hit
    (mean over the routed layers), 1000 x the busiest expert's tokens
    over the mean (the worst layer), and ``in_runs``: 1000 x the share
    of the batch's live full-kind cache pages that the paged kernel
    fetches in whole runs."""
    moe = jnp.zeros((2,), jnp.int32)
    if counts:
        load = jnp.max(jnp.stack([c[1] for c in counts]))
        moe = jnp.stack([sum(c[0] for c in counts) // len(counts),
                         (load * (1000 * n_experts)) // n_assignments])
    return jnp.broadcast_to(jnp.append(moe, in_runs)[:, None],
                            (len(COUNTERS), q)).astype(jnp.int32)


COUNTERS = ("moe_experts_hit", "moe_load_max_x1000",
            "kv_pages_in_runs_x1000")


def forward_step(params, packed, k_pool, v_pool, k_win, v_win, *, q: int,
                 cfg: LagunaConfig, firsts=None):
    """One decode step (models/gpt.py ``forward_step``'s contract) over
    two kinds of pool. ``packed`` carries the window kind's columns too
    (module docstring); a window layer writes each row at (its index,
    window slot block, slot_offsets) and attends over the lane's window
    table, whose context counts from the table's first block.

    Returns (logits [b, q, vocab], ids [b + 3, q] int32, k_pool, v_pool,
    k_win, v_win): rows b on of ``ids`` are ``COUNTERS``."""
    B, Q = packed.shape[0], q
    kv, d = cfg.num_key_value_heads, cfg.head_dim
    bs = k_pool.shape[2]
    (tokens, positions, block_tables, context_lens, q_lens, slot_blocks,
     slot_offsets, (win_tables, win_first, win_slots)) = unpack_step(
        packed, Q, window_table_len(cfg.sliding_window, bs, Q), firsts)
    win_lens = context_lens - win_first * bs
    # Row i of a lane sits at ctx - q_len + i and sees keys from that
    # less (window - 1) on, in the window table's own coordinates.
    win_starts = win_lens - q_lens - (cfg.sliding_window - 1)
    no_start = jnp.zeros_like(context_lens)
    x = params["embed"][tokens]
    counts = []
    for (window, li), p in zip(_kind_index(cfg), params["layers"]):
        rope = cfg.rope_sliding if window else cfg.rope_full

        def attend(q, k, v, window=window, li=li, rope=rope):
            nonlocal k_pool, v_pool, k_win, v_win
            q = rotary(q, positions, rope, d)
            k = rotary(k, positions, rope, d).reshape(B, Q, kv * d)
            v = v.reshape(B, Q, kv * d)
            H = q.shape[2]
            qg = q.reshape(B, Q, kv, H // kv, d)
            if window:
                k_win = k_win.at[li, win_slots, slot_offsets].set(k)
                v_win = v_win.at[li, win_slots, slot_offsets].set(v)
                with jax.named_scope("attn_window"):
                    o = paged_attention_stored(
                        qg, k_win, v_win, li, win_tables, win_lens, q_lens,
                        win_starts, name="attn_window")
            else:
                k_pool = k_pool.at[li, slot_blocks, slot_offsets].set(k)
                v_pool = v_pool.at[li, slot_blocks, slot_offsets].set(v)
                with jax.named_scope("attn_full"):
                    o = paged_attention_stored(
                        qg, k_pool, v_pool, li, block_tables, context_lens,
                        q_lens, no_start, name="attn_full")
            return o.reshape(B, Q, H, d)

        x, c = _block(x, p, cfg, attend, "decode")
        if c is not None:
            counts.append(c)
    logits = head(params, x, cfg.rms_norm_eps)
    ids = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    # The full kind's layer with the most query heads has the most score
    # rows, so the fewest pages a step: the run size no full layer
    # exceeds.
    full_heads = max(h for h, t in zip(cfg.num_attention_heads_per_layer,
                                       cfg.layer_types) if t == FULL)
    ids = jnp.concatenate([ids, _counters(
        counts, B * Q * cfg.num_experts_per_tok, cfg.num_experts, Q,
        kv_pages_in_runs_x1000(block_tables, context_lens, k_pool, v_pool,
                               score_rows=Q * full_heads))])
    return logits, ids, k_pool, v_pool, k_win, v_win


def _chunk_layers(params, tokens, positions, k_pool, v_pool, block_table,
                  ctx_len, k_win, v_win, win_table, win_first,
                  cfg: LagunaConfig):
    """A span's layers: ``tokens`` [1, n] at ``positions`` [n] against
    the sequence's context in the pools of both kinds, which are only
    read. ``win_table`` [nbw] holds the window kind's blocks behind the
    span's first token, the first of them the sequence's block
    ``win_first``. Returns (x [1, n, hidden] before the final norm and
    head, k, v [full layers, 1, n, kv, d], k, v [window layers, 1, n,
    kv, d]): the span's own K/V."""
    kv, d = cfg.num_key_value_heads, cfg.head_dim
    bs = k_pool.shape[2]
    win_base = win_first * bs
    pos = positions[None]
    x = params["embed"][tokens]
    new = {False: ([], []), True: ([], [])}
    for (window, li), p in zip(_kind_index(cfg), params["layers"]):
        rope = cfg.rope_sliding if window else cfg.rope_full

        def attend(q, k, v, window=window, li=li, rope=rope):
            q = rotary(q, pos, rope, d)
            k = rotary(k, pos, rope, d)
            pool_k, pool_v, table, base = (
                (k_win, v_win, win_table, win_base) if window
                else (k_pool, v_pool, block_table, 0))
            slots = table.shape[0] * bs
            k_ctx = pool_k[li, table].reshape(slots, kv, d)
            v_ctx = pool_v[li, table].reshape(slots, kv, d)
            with jax.named_scope("attn_window" if window else "attn_full"):
                o = span_attention(
                    q[0], k[0], v[0], k_ctx, v_ctx, ctx_len, base,
                    cfg.sliding_window if window else None)
            new[window][0].append(k)
            new[window][1].append(v)
            return o[None]

        x, _ = _block(x, p, cfg, attend, "chunk")
    return (x, *(jnp.stack(new[w][i]) for w in (False, True)
                 for i in (0, 1)))


def forward_prefill_chunk(params, tokens, k_pool, v_pool, table,
                          k_win, v_win, win, cfg: LagunaConfig):
    """One span of a prompt as one program (models/gpt.py
    ``forward_prefill_chunk``'s contract, over two kinds of pool):
    ``tokens`` [1, n] and ``table`` = ``[block table | destination |
    ctx_len | last]`` are the full kind's, as there. ``win`` is the
    window kind's array, ``[table (nbw) | first block | destination
    (n / block_size)]``: the blocks that cover the window behind the
    span's first token AS THEY STOOD BEFORE the lane's window slid past
    the span, that table's first block's index in the sequence, and
    where each of the span's blocks is written: the scratch block 0 for
    a leading block that is already out of the window the next query
    keeps, a granted block for the rest.

    The host slides the window and grants before it dispatches, so a
    block the slide freed may be granted again at once and appear in
    the table (read) and in the destination (written) of the same span.
    That is safe only because every layer reads the pools as they came
    in and the span is written after the last layer.

    Returns (row [vocab], id, k_pool, v_pool, k_win, v_win)."""
    n = tokens.shape[1]
    bs = k_pool.shape[2]
    block_table, dest, ctx_len, last = unpack_span(table, n, bs)
    nbw = win.shape[0] - 1 - n // bs
    positions = jnp.minimum(ctx_len + jnp.arange(n, dtype=jnp.int32),
                            cfg.max_seq - 1)
    x, k, v, kw, vw = _chunk_layers(
        params, tokens, positions, k_pool, v_pool, block_table, ctx_len,
        k_win, v_win, win[:nbw], win[nbw], cfg)
    k_pool, v_pool = scatter_span((k_pool, v_pool), (k[:, 0], v[:, 0]),
                                  dest, last + 1)
    k_win, v_win = scatter_span((k_win, v_win), (kw[:, 0], vw[:, 0]),
                                win[nbw + 1:], last + 1)
    row = head(params, jax.lax.dynamic_slice_in_dim(x, last, 1, axis=1),
               cfg.rms_norm_eps)[0, 0]
    return (row, jnp.argmax(row).astype(jnp.int32), k_pool, v_pool,
            k_win, v_win)


# ---------------------------------------------------------------------------
# The serving seam
# ---------------------------------------------------------------------------


def cost_shape(cfg: LagunaConfig) -> dict:
    """The cost description util/perfmodel.py prices steps from. A
    token passes the attention and shared weights, the router, its
    ``num_experts_per_tok`` experts and the head; a step of n rows
    streams those and, of each routed layer, the experts that n * k
    uniform choices are expected to hit."""
    m, d, kv = cfg.hidden_size, cfg.head_dim, cfg.num_key_value_heads
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    expert = 3 * m * cfg.moe_intermediate_size
    active = cfg.vocab_size * m
    always = 2 * cfg.vocab_size * m
    full_coef, windows, routed = 0.0, [], 0
    for h, kind, mlp in zip(cfg.num_attention_heads_per_layer,
                            cfg.layer_types, cfg.mlp_layer_types):
        attn = 2 * m * h * d + 2 * m * kv * d + m * h
        if mlp == DENSE:
            mlp_w = 3 * m * cfg.intermediate_size
            active += attn + mlp_w
            always += attn + mlp_w
        else:
            shared = 3 * m * cfg.shared_expert_intermediate_size + m * E
            active += attn + shared + k * expert
            always += attn + shared
            routed += 1
        if kind == SLIDING:
            windows.append((4.0 * h * d, cfg.sliding_window))
        else:
            full_coef += 4.0 * h * d
    n_full = len(cfg.layers_of(FULL))

    def streamed(rows):
        hit = E * (1.0 - (1.0 - k / E) ** max(rows, 0))
        return always + routed * hit * expert

    return {
        "matmul_weights": active,
        "head_weights": cfg.vocab_size * m,
        "attn_per_ctx": full_coef,
        "chunk_attn_per_ctx": full_coef,   # a chunk's rows cost the same
        "chunk_ctx_ops": 0.0,              # a cached key is read as it is
        "attn_windows": tuple(windows),
        "num_params": cfg.num_params(),
        "streamed_params": streamed,
        "param_bytes": cfg.dtype.itemsize,
        # K+V elements a token in the layers that keep every token (the
        # window layers' reads are bounded and left out).
        "kv_bytes_per_token": 2 * n_full * kv * d,
        "m": m, "L": cfg.num_hidden_layers,
    }


def serving(cfg: LagunaConfig):
    kv, d = cfg.num_key_value_heads, cfg.head_dim
    kinds = (keys_and_values("full", cfg.layers_of(FULL), kv, d, None,
                             cfg.dtype),
             keys_and_values("window", cfg.layers_of(SLIDING), kv, d,
                             cfg.sliding_window, cfg.dtype))
    if not kinds[0].layers or not kinds[1].layers:
        raise ValueError("the served Laguna needs layers of both kinds")
    return Serving(init=init, step=forward_step,
                   chunk=forward_prefill_chunk, kinds=kinds,
                   cost=cost_shape(cfg), max_seq=cfg.max_seq,
                   vocab_size=cfg.vocab_size, counters=COUNTERS)
