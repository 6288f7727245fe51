"""Granite 4.0-H (IBM, ``model_type`` ``granitemoehybrid``;
https://huggingface.co/ibm-granite/granite-4.0-h-small): a decoder whose
EVERY layer is a mixer and an expert block, each behind its own
pre-norm, both residual adds scaled, under four muP multipliers and a
tied head:

  x0 = embedding_multiplier * Embed[tokens]
  h  = x + residual_multiplier * Mixer_l(RMSNorm_a(x))
  x' = h + residual_multiplier * (Experts_l(u) + Shared_l(u)),
       u = RMSNorm_b(h)
  logits = (RMSNorm_f(x) Embed^T) / logits_scaling

Layer ``l``'s mixer is ``layer_types[l]``. No biases but the
convolution's. Served through the generation engine (llm/engine.py); no
loss and no train step. ``models/granite_hybrid_ref.py`` is the plain
float32 reference of these equations.

``attention``: ``q = u W_q`` [heads x head_dim], ``k, v = u W_k, u W_v``
  [kv heads x head_dim], causal softmax(``q k^T *
  attention_multiplier``) ``v``, ``W_o``: the scale is the model's own
  number (1/128 where ``1/sqrt(head_dim)`` would be 1/11.3), which the
  two attention kernels are handed. NO positional embedding
  (``position_embedding_type`` ``nope``: the state-space layers carry
  position); keys are cached as projected.
``mamba``: the Mamba-2 mixer of models/mamba2.py (its docstring has the
  equations) with ``mamba_n_groups`` groups of heads: at the published
  ONE group all 128 heads read the same ``B_t`` and ``C_t`` and the
  gated norm runs over all of ``d_inner``. That module's ``mamba_step``
  and ``mamba_chunk``, and models/layers.py's ``attention_step`` and
  ``attention_chunk`` handed the scale, are called, as models/
  nemotron_h.py calls them; a group wider than a block of heads is
  ops/ssm.py's to run.
``Experts``: ``l = u W_r`` over all ``num_local_experts`` in float32;
  the ``num_experts_per_tok`` largest; ``w = softmax`` over those
  logits (ops/moe.py ``route`` at scale 1: a softmax over all, the
  largest, renormalised, is the same numbers); ``Expert_e(u) = (silu(u
  G_e) * (u U_e)) D_e`` at width ``intermediate_size``;
  ``sum_j w_j Expert_{e_j}(u)``.
``Shared``: ``(silu(u G) * (u U)) D`` at ``shared_intermediate_size``.

**What a sequence keeps** is what models/nemotron_h.py's keeps, by
MIXER kind: an attention layer a token's keys and values (the seam's
``kinds``), a Mamba-2 layer a state ``S`` [heads, head width, state] in
float32 and the last ``mamba_d_conv - 1`` rows of pre-convolution
``xBC`` (the seam's ``state``). The pools' layer indices count the
layers of one mixer kind (models/layers.py ``pool_index`` over
``layer_types``); the experts, which keep nothing, are in all of them.

**Precision as served:** ``cfg.dtype`` (bfloat16) weights, activations,
keys, values and convolution state; float32 for router logits, norms,
softmaxes, ``dt``, ``exp(dt A)``, the state ``S`` and its recurrence.
A residual add is made in float32 and rounded once.

**A share of the experts and of the vocabulary.** ``experts_held`` from
``first_expert``, as models/kimi_k2.py: the router scores all
``num_local_experts``, the layer computes its own experts' part for the
tokens routed to them, adds the shared MLP and hands that partial
result on; nothing stands in for the other chip or its exchange.
``vocab_size`` rows of the ONE tied matrix are the embedding and, read
as they lie, the head.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from ..ops import moe
from ..ops.pallas.paged_fetch import kv_pages_in_runs_x1000
from .layers import (COUNTERS, attention_chunk, attention_params,
                     attention_step, counters, held_experts, normal,
                     pool_index, rmsnorm, swiglu)
from .mamba2 import Mamba2, mamba_chunk, mamba_params, mamba_step
from .seam import (Serving, StateKind, keys_and_values, scatter_span,
                   step_state_slots, unpack_span, unpack_step)

MAMBA, ATTENTION = "mamba", "attention"
F32 = jnp.float32


@dataclass(frozen=True)
class GraniteHybridConfig:
    """Field names are the published config.json's; ``experts_held`` /
    ``first_expert`` say which routed experts this chip holds,
    ``max_seq`` is the deployment's limit and ``dtype`` what weights,
    activations, keys, values and the convolution state are held in."""
    vocab_size: int = 100352
    hidden_size: int = 4096
    num_hidden_layers: int = 40
    layer_types: tuple = ((MAMBA,) * 5 + (ATTENTION,) + (MAMBA,) * 4) * 4
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    mamba_n_heads: int = 128
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 256
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    num_local_experts: int = 72
    num_experts_per_tok: int = 10
    intermediate_size: int = 768
    shared_intermediate_size: int = 1536
    embedding_multiplier: float = 12.0
    attention_multiplier: float = 0.0078125
    residual_multiplier: float = 0.22
    logits_scaling: float = 16.0
    tie_word_embeddings: bool = True
    hidden_act: str = "silu"
    position_embedding_type: str = "nope"
    normalization_function: str = "rmsnorm"
    attention_bias: bool = False
    rms_norm_eps: float = 1e-5
    experts_held: int = 72
    first_expert: int = 0
    max_seq: int = 2816
    dtype: Any = "bfloat16"

    def __post_init__(self):
        object.__setattr__(self, "dtype", jnp.dtype(self.dtype))
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        # The forms this module builds; another value is another model.
        built = {"hidden_act": "silu", "position_embedding_type": "nope",
                 "normalization_function": "rmsnorm",
                 "tie_word_embeddings": True, "mamba_conv_bias": True,
                 "mamba_proj_bias": False, "attention_bias": False}
        for name, want in built.items():
            if getattr(self, name) != want:
                raise ValueError(f"{name}={getattr(self, name)!r}: only "
                                 f"{want!r} is built")
        if len(self.layer_types) != self.num_hidden_layers \
                or set(self.layer_types) - {MAMBA, ATTENTION}:
            raise ValueError("layer_types must give each of the layers "
                             "one of 'mamba' and 'attention'")
        if self.mamba_n_heads % self.mamba_n_groups \
                or self.num_attention_heads % self.num_key_value_heads \
                or self.hidden_size % self.num_attention_heads:
            raise ValueError("heads do not divide into their groups")
        if not 0 <= self.first_expert <= \
                self.num_local_experts - self.experts_held:
            raise ValueError("the held experts lie outside the routed ones")

    def layers_of(self, kind: str) -> tuple:
        return tuple(l for l, t in enumerate(self.layer_types) if t == kind)

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def mamba(self) -> Mamba2:
        """This family's mixer, as models/mamba2.py reads one (no
        time-step range is published: the record's own)."""
        return Mamba2(
            hidden_size=self.hidden_size, heads=self.mamba_n_heads,
            head_dim=self.mamba_d_head, groups=self.mamba_n_groups,
            state=self.mamba_d_state, conv_kernel=self.mamba_d_conv,
            chunk_size=self.mamba_chunk_size, eps=self.rms_norm_eps,
            dtype=self.dtype)

    # The scan's block length under the name benchmark/
    # reference_nemotron_h.py reads it by (``_span_fn``, which
    # benchmark/reference_granite_hybrid.py runs on this configuration).
    chunk_size = property(lambda self: self.mamba_chunk_size)

    def mixer_params(self, kind: str) -> int:
        """Parameters of one mixer of a kind, without its pre-norm."""
        m = self.hidden_size
        if kind == ATTENTION:
            H, kv, d = (self.num_attention_heads, self.num_key_value_heads,
                        self.head_dim)
            return 2 * m * H * d + 2 * m * kv * d
        return self.mamba.num_params

    @property
    def expert_params(self) -> int:
        return 3 * self.hidden_size * self.intermediate_size

    def num_params(self, experts=None, embedding: bool = True) -> int:
        """Parameters with ``experts`` routed experts a layer counted
        (default: those held here; ``num_local_experts`` is the whole
        model, ``num_experts_per_tok`` what a token passes) and the ONE
        tied matrix with them or not."""
        experts = self.experts_held if experts is None else experts
        m = self.hidden_size
        block = (m * self.num_local_experts + experts * self.expert_params
                 + 3 * m * self.shared_intermediate_size + 2 * m)
        return (embedding * self.vocab_size * m + m
                + sum(self.mixer_params(t) + block
                      for t in self.layer_types))


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

# The ONE tied matrix is drawn at this std, not at 0.02. A token's own
# row is in the residual 12 times over (``embedding_multiplier``) and is
# the head's row for that token too, so its own logit is 12 |e|^2 over
# the residual's size where every other is |e| times a unit normal: at
# std 0.02 and these widths the own logit stands 12 sigma over the rest
# and every answer is the prompt's last token repeated (my chip run,
# PR 64), which a comparison of tokens cannot tell from any other model
# that does the same. At 0.004 it stands ~2.4 sigma, under the largest
# of 50,176, and an answer depends on its context.
EMBED_STD = 0.004


def init(key, cfg: GraniteHybridConfig) -> dict:
    """Seeded random parameters in ``cfg.dtype`` (normal, std 0.02, the
    tied matrix ``EMBED_STD``; norms 1; a Mamba-2 mixer's own by
    models/mamba2.py's initialiser), a layer at a time, a routed
    expert's by its GLOBAL id (models/layers.py ``held_experts``).
    ``embed`` is the one tied matrix."""
    return {
        **_init_ends(jax.random.fold_in(key, 1 << 20), cfg),
        "layers": [init_layer(key, cfg, l)
                   for l in range(cfg.num_hidden_layers)],
    }


@functools.partial(jax.jit, static_argnames=("cfg",))
def _init_ends(key, cfg: GraniteHybridConfig) -> dict:
    m = cfg.hidden_size
    return {"embed": normal(key, (cfg.vocab_size, m), cfg.dtype, EMBED_STD),
            "norm_f": jnp.ones((m,), cfg.dtype)}


def init_layer(key, cfg: GraniteHybridConfig, l: int) -> dict:
    """Layer ``l``'s parameters, from ``fold_in(key, l)``."""
    return _init_layer(jax.random.fold_in(key, l), cfg, cfg.layer_types[l])


@functools.partial(jax.jit, static_argnames=("cfg", "kind"))
def _init_layer(key, cfg: GraniteHybridConfig, kind: str) -> dict:
    m, dt = cfg.hidden_size, cfg.dtype
    k = iter(jax.random.split(key, 12))
    p = {"ln_a": jnp.ones((m,), dt), "ln_b": jnp.ones((m,), dt)}
    if kind == ATTENTION:
        p.update(attention_params(k, m, cfg.num_attention_heads,
                                  cfg.num_key_value_heads, cfg.head_dim, dt))
    else:
        p.update(mamba_params(k, cfg.mamba))
    f, fs = cfg.intermediate_size, cfg.shared_intermediate_size
    k1, k2 = next(k), next(k)
    share = (cfg.first_expert, cfg.experts_held)
    p.update(
        router=normal(next(k), (m, cfg.num_local_experts), dt),
        w1=held_experts(k1, *share, (m, 2 * f), dt),
        w2=held_experts(k2, *share, (f, m), dt),
        s_gu=normal(next(k), (m, 2 * fs), dt),
        s_down=normal(next(k), (fs, m), dt))
    return p


# ---------------------------------------------------------------------------
# The layer's parts
# ---------------------------------------------------------------------------


def _experts(u, p, cfg: GraniteHybridConfig, program: str):
    """u [T, m] -> (Experts(u) + Shared(u) [T, m], the held experts'
    tokens [held]). The grouped products' kernel is
    ``moe_experts_<program>`` on a device trace (``_r<rows>`` behind it
    where an expert of ALL ``num_local_experts``, which the call is
    told, expects 32 rows or more: ops/moe.py ``tile_rows``)."""
    with jax.named_scope("moe_route"):
        _, experts, weights = moe.route(u, p["router"],
                                        cfg.num_experts_per_tok)
    with jax.named_scope("moe_experts"):
        y, sizes = moe.routed_experts(
            u, experts, weights, p["w1"], p["w2"], first=cfg.first_expert,
            n_experts=cfg.num_local_experts, name=f"moe_experts_{program}")
    with jax.named_scope("shared_mlp"):
        return y + swiglu(u, p["s_gu"], p["s_down"]), sizes


def _add(x, out, cfg: GraniteHybridConfig):
    """``x + residual_multiplier * out``, rounded once."""
    return (x.astype(F32) + cfg.residual_multiplier * out.astype(F32)
            ).astype(x.dtype)


def _embed(params, tokens, cfg: GraniteHybridConfig):
    x = params["embed"][tokens]
    return (x.astype(F32) * cfg.embedding_multiplier).astype(x.dtype)


def _head(params, x, cfg: GraniteHybridConfig):
    """The tied head: the embedding's rows as they lie."""
    x = rmsnorm(x, params["norm_f"], cfg.rms_norm_eps)
    logits = jnp.einsum("brm,vm->brv", x, params["embed"])
    return (logits.astype(F32) / cfg.logits_scaling).astype(x.dtype)


# ---------------------------------------------------------------------------
# The two served programs
# ---------------------------------------------------------------------------


def forward_step(params, packed, k_pool, v_pool, s_pool, c_pool, *, q: int,
                 cfg: GraniteHybridConfig, firsts=None):
    """One decode step, ONE row a lane: models/nemotron_h.py
    ``forward_step``'s contract and pools (keys and values of the
    attention layers; ``s_pool`` [mamba layers, slots, heads, head
    width, state] float32 and ``c_pool`` [mamba layers, slots,
    mamba_d_conv - 1, conv_dim], a lane's slot a column of ``packed``).

    Returns (logits [b, 1, vocab], ids [b + 4, 1] int32, k_pool, v_pool,
    s_pool, c_pool): rows b on of ``ids`` are ``COUNTERS``, the expert
    blocks of ALL layers counted."""
    if q != 1:
        raise ValueError("a state is moved one token a step: q must be 1")
    (tokens, positions, block_tables, context_lens, q_lens, slot_blocks,
     slot_offsets, _) = unpack_step(packed, q, firsts=firsts, state=True)
    slots = step_state_slots(packed, q)
    B = tokens.shape[0]
    lanes = (block_tables, context_lens, q_lens,
             jnp.zeros_like(context_lens), slot_blocks, slot_offsets)
    eps, mx = cfg.rms_norm_eps, cfg.mamba
    x = _embed(params, tokens, cfg)                      # [B, 1, m]
    sizes = []
    for li, p in zip(pool_index(cfg.layer_types), params["layers"]):
        h = rmsnorm(x, p["ln_a"], eps)
        if "wq" in p:
            out, k_pool, v_pool = attention_step(
                h, p, li, k_pool, v_pool, lanes, cfg.attention_multiplier)
        else:
            out, s_pool, c_pool = mamba_step(h, p, mx, li, slots, s_pool,
                                             c_pool)
        x = _add(x, out, cfg)
        out, s = _experts(rmsnorm(x, p["ln_b"], eps)[:, 0], p, cfg,
                          "decode")
        sizes.append(s)
        x = _add(x, out[:, None], cfg)
    logits = _head(params, x, cfg)
    ids = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    ids = jnp.concatenate([ids, counters(
        sizes, B, cfg.num_local_experts, cfg.num_experts_per_tok, 1,
        kv_pages_in_runs_x1000(
            block_tables, context_lens, k_pool, v_pool,
            score_rows=cfg.num_attention_heads))])
    return logits, ids, k_pool, v_pool, s_pool, c_pool


def forward_prefill_chunk(params, tokens, k_pool, v_pool, table, s_pool,
                          c_pool, cfg: GraniteHybridConfig):
    """One span of a prompt as one program: models/nemotron_h.py
    ``forward_prefill_chunk``'s contract (``table`` = ``[block table |
    destination | ctx_len | last | slot read | slot written]``; a
    state-space layer scans from the slot read, or from zeros where
    ``ctx_len`` is 0, into the slot written; the span's keys and values
    are written after the last layer).

    Returns (row [vocab], id, k_pool, v_pool, s_pool, c_pool)."""
    n = tokens.shape[1]
    bs = k_pool.shape[2]
    block_table, dest, ctx_len, last, src, dst = unpack_span(
        table, n, bs, extra=2)
    eps, mx = cfg.rms_norm_eps, cfg.mamba
    span = (src, dst, last, (jnp.arange(n) <= last)[:, None], ctx_len == 0)
    x = _embed(params, tokens, cfg)                      # [1, n, m]
    new_k, new_v = [], []
    for li, p in zip(pool_index(cfg.layer_types), params["layers"]):
        h = rmsnorm(x, p["ln_a"], eps)
        if "wq" in p:
            out, k, v = attention_chunk(
                h, p, li, k_pool, v_pool, block_table, ctx_len,
                cfg.attention_multiplier)
            new_k.append(k)
            new_v.append(v)
        else:
            out, s_pool, c_pool = mamba_chunk(h, p, mx, li, span, s_pool,
                                              c_pool)
        x = _add(x, out, cfg)
        out, _ = _experts(rmsnorm(x, p["ln_b"], eps)[0], p, cfg, "chunk")
        x = _add(x, out[None], cfg)
    k_pool, v_pool = scatter_span(
        (k_pool, v_pool), (jnp.stack(new_k)[:, 0], jnp.stack(new_v)[:, 0]),
        dest, last + 1)
    row = _head(params, jax.lax.dynamic_slice_in_dim(x, last, 1, axis=1),
                cfg)[0, 0]
    return (row, jnp.argmax(row).astype(jnp.int32), k_pool, v_pool, s_pool,
            c_pool)


# ---------------------------------------------------------------------------
# The serving seam
# ---------------------------------------------------------------------------


def cost_shape(cfg: GraniteHybridConfig) -> dict:
    """The cost description util/perfmodel.py prices steps from, as
    models/nemotron_h.py's (its docstring says what each entry is): a
    token passes every layer's weights outside the routed experts, of
    its ``num_experts_per_tok`` experts the share held here, and the
    tied matrix once, as the head."""
    m, E, k, held = (cfg.hidden_size, cfg.num_local_experts,
                     cfg.num_experts_per_tok, cfg.experts_held)
    L = cfg.num_hidden_layers
    n_attn, n_mamba = (len(cfg.layers_of(t)) for t in (ATTENTION, MAMBA))
    always = cfg.num_params(experts=0)
    active = (cfg.num_params(experts=0, embedding=False)
              + L * cfg.expert_params * k * held / E)
    attn = 4.0 * n_attn * cfg.num_attention_heads * cfg.head_dim

    def streamed(rows):
        hit = held * (1.0 - (1.0 - k / E) ** max(rows, 0))
        return always + L * hit * cfg.expert_params

    return {
        "matmul_weights": active,
        "head_weights": cfg.vocab_size * m,
        "attn_per_ctx": attn,
        "chunk_attn_per_ctx": attn,
        "chunk_ctx_ops": 0.0,
        "attn_windows": (),
        "num_params": cfg.num_params(),
        "streamed_params": streamed,
        "param_bytes": cfg.dtype.itemsize,
        "kv_bytes_per_token": 2 * n_attn * cfg.num_key_value_heads
        * cfg.head_dim,
        **cfg.mamba.cost(n_mamba),
        "state_bytes_per_seq": state_kind(cfg).slot_bytes,
        "m": m, "L": L,
    }


def state_kind(cfg: GraniteHybridConfig):
    """What a sequence keeps in the Mamba-2 layers."""
    return StateKind(cfg.layers_of(MAMBA), cfg.mamba.state_parts)


def serving(cfg: GraniteHybridConfig):
    full = keys_and_values("full", cfg.layers_of(ATTENTION),
                           cfg.num_key_value_heads, cfg.head_dim, None,
                           cfg.dtype)
    return Serving(init=init, step=forward_step,
                   chunk=forward_prefill_chunk, kinds=(full,),
                   cost=cost_shape(cfg), max_seq=cfg.max_seq,
                   vocab_size=cfg.vocab_size, counters=COUNTERS,
                   state=state_kind(cfg))
