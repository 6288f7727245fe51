"""Kimi-K2 (moonshotai, https://huggingface.co/moonshotai/Kimi-K2.5;
``model_type`` ``kimi_k2``, the DeepSeek-V3 block): a decoder with
latent attention (MLA) and sigmoid-routed experts, served through the
generation engine (llm/engine.py). The language model only: requests
carry token ids, no vision tower is built. No loss and no train step.

The layer (x is [T, hidden]; H heads; no biases; RMSNorm; layers below
``first_k_dense_replace`` have a dense MLP, the rest a routed one):

  h = RMSNorm(x)
  c_q = RMSNorm(h W_dq) [T, q_lora_rank]; q = c_q W_uq [T, H, nope + rope]
  [c_kv | k_r] = h W_dkv [T, kv_lora_rank + rope]; c_kv <- RMSNorm(c_kv)
  k_rope = RoPE(k_r): ONE rotary key a token, shared by every head;
  q_rope <- RoPE(q_rope). YaRN inverse frequencies over the rope dims,
    pairs (i, i + rope/2), cos and sin unscaled (mscale / mscale_all_dim
    = 1).
  [k_nope | v] = c_kv W_ukv [T, H, nope + v]
  scores (q_nope . k_nope + q_rope . k_rope) * softmax_scale, causal,
    softmax_scale = (nope + rope)^-0.5 * mscale(factor, mscale_all_dim)^2
  o = softmax(scores) v; x <- x + concat_h(o_h) W_o
  h2 = RMSNorm(x). dense: (silu(h2 W_gate) * h2 W_up) W_down.
    routed: s = sigmoid(h2 W_r) over all experts in float32; the
    ``num_experts_per_tok`` largest of s + b (b the router's correction
    bias); w = routed_scaling_factor * s_top / sum(s_top), s WITHOUT b;
    out = sum_e w_e Expert_e(h2) + Shared(h2), each a SwiGLU, the
    weight on the expert's output (ops/moe.py ``route_sigmoid``).
  x <- x + out
Final RMSNorm, untied head. ``models/kimi_k2_ref.py`` is the plain
float32 reference of these equations.

**The cache** keeps, a token a layer, ONE latent row: ``[c_kv after its
norm | k_rope after rotary | zeros]``, ``kv_lora_rank + rope`` values
padded to whole 128-lane tiles (576 -> 640): a minor dimension that is
not whole tiles gets an at-rest layout from the TPU runtime that no
reader or writer wants (PERF.md section 6, PR 31 and PR 34), and padded
by hand the row costs what the runtime would have made it cost. Behind
the serving seam (models/seam.py) that is a kind of layer with one
pool.

**Two paths over it.** A decode step attends in the ABSORBED form: a
head's ``q_nope`` goes through ``W_uk`` into the latent space, the
kernel (ops/pallas/paged_fetch.py ``paged_attention_latent``, named
``attn_latent``; it copies its own pages out of the pool, a run of
table-adjacent pages in one copy) scores the query against latent rows
as stored and sums the same rows as values, and the output comes back through
``W_uv``; no key or value of a head is ever made. A prefill chunk goes
the other way round: it takes the latent rows of context and span UP
through ``W_ukv`` to keys and values, ``HEAD_GROUP`` heads at a time,
and attends with nope+rope / v wide heads in the ``chunk_attn`` kernel
(ops/pallas/chunk_attention.py), which at a 512-token span costs about
half the absorbed form's operations (the two cross near 170 tokens).
The kernel skips context blocks wholly past the sequence's context.

**A share of the experts.** A configuration says which experts of the
``n_routed_experts`` this chip holds (``experts_held`` from
``first_expert``): the router scores all of them, the layer computes
its own experts' part for the tokens routed to them, adds the shared
expert and hands that partial result on. What the other chips of the
deployment would add is theirs; nothing here stands in for them or for
the exchange.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from ..ops import moe
from ..ops.pallas.chunk_attention import chunk_attention, padded_keys
from ..ops.pallas.paged_fetch import (kv_pages_in_runs_x1000,
                                      paged_attention_latent)
from .layers import (COUNTERS, ROUTER_BIAS_STD, counters, head, held_experts,
                     init_ends, normal, rmsnorm, rotary, swiglu, yarn_mscale)
from .seam import (LayerKind, Serving, scatter_span, span_rows, unpack_spans,
                   unpack_step)

# Heads whose keys and values a chunk makes at a time: 18,432 keys of
# 128 and as many values are 151 MB a group (all 64 heads: 0.60 GB).
HEAD_GROUP = 16
# Spans ONE chunk program takes (``Serving.chunk_spans``): the tail of
# one prompt's body, the head of the next, and so on as far as a step's
# budget goes. The weights are read, and every kernel's fixed part is
# paid, once for all of them (PERF.md section 6, PR 67).
CHUNK_SPANS = 4


@dataclass(frozen=True)
class KimiK2Config:
    """Field names are the published config.json's; ``experts_held`` /
    ``first_expert`` say which routed experts this chip holds,
    ``max_seq`` is the deployment's limit and ``dtype`` what weights,
    activations and the cache are held in. ``rope_scaling`` arrives
    from JSON as a dict and is frozen to (key, value) pairs."""
    vocab_size: int = 163840
    hidden_size: int = 7168
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 61
    num_attention_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 384
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    first_k_dense_replace: int = 1
    moe_layer_freq: int = 1
    routed_scaling_factor: float = 2.827
    norm_topk_prob: bool = True
    scoring_func: str = "sigmoid"
    topk_method: str = "noaux_tc"
    n_group: int = 1
    topk_group: int = 1
    rms_norm_eps: float = 1e-5
    rope_theta: float = 50000.0
    rope_scaling: Any = (("beta_fast", 32), ("beta_slow", 1), ("factor", 64),
                         ("mscale", 1), ("mscale_all_dim", 1),
                         ("original_max_position_embeddings", 4096),
                         ("type", "yarn"))
    experts_held: int = 384
    first_expert: int = 0
    max_seq: int = 17408
    dtype: Any = "bfloat16"

    def __post_init__(self):
        if isinstance(self.rope_scaling, dict):
            object.__setattr__(self, "rope_scaling",
                               tuple(sorted(self.rope_scaling.items())))
        object.__setattr__(self, "dtype", jnp.dtype(self.dtype))
        # The forms this module builds; another value is another model.
        built = {"scoring_func": "sigmoid", "topk_method": "noaux_tc",
                 "norm_topk_prob": True, "n_group": 1, "topk_group": 1,
                 "moe_layer_freq": 1}
        for name, want in built.items():
            if getattr(self, name) != want:
                raise ValueError(f"{name}={getattr(self, name)!r}: only "
                                 f"{want!r} is built")
        if dict(self.rope_scaling)["type"] != "yarn":
            raise ValueError("only YaRN rope_scaling is built")
        if not 0 <= self.first_expert <= \
                self.n_routed_experts - self.experts_held:
            raise ValueError("the held experts lie outside the routed ones")

    def routed(self, l: int) -> bool:
        return l >= self.first_k_dense_replace

    @property
    def latent_width(self) -> int:
        """What a token leaves in the cache a layer: c_kv and k_rope."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def row_width(self) -> int:
        """A row of the latent pool: ``latent_width`` in whole 128-lane
        tiles (module docstring)."""
        return -(-self.latent_width // 128) * 128

    @property
    def softmax_scale(self) -> float:
        r = dict(self.rope_scaling)
        width = self.qk_nope_head_dim + self.qk_rope_head_dim
        return width ** -0.5 * yarn_mscale(r["factor"],
                                          r["mscale_all_dim"]) ** 2

    @property
    def rope(self) -> tuple:
        """The rotary dims' ``rope_parameters`` group as
        models/layers.py ``rope_inv_freq`` takes it."""
        r = dict(self.rope_scaling)
        return (("rope_type", "yarn"), ("rope_theta", self.rope_theta),
                ("factor", r["factor"]),
                ("original_max_position_embeddings",
                 r["original_max_position_embeddings"]),
                ("beta_slow", r["beta_slow"]), ("beta_fast", r["beta_fast"]),
                ("attention_factor",
                 yarn_mscale(r["factor"], r["mscale"])
                 / yarn_mscale(r["factor"], r["mscale_all_dim"])),
                ("partial_rotary_factor", 1.0))

    def num_params(self) -> int:
        """Parameters held here (the share's experts, not all)."""
        m, H = self.hidden_size, self.num_attention_heads
        qk = self.qk_nope_head_dim + self.qk_rope_head_dim
        attn = (m * self.q_lora_rank + self.q_lora_rank * H * qk
                + m * self.latent_width + self.kv_lora_rank * H
                * (self.qk_nope_head_dim + self.v_head_dim)
                + H * self.v_head_dim * m
                + 2 * m + self.q_lora_rank + self.kv_lora_rank)
        expert = 3 * m * self.moe_intermediate_size
        n = 2 * self.vocab_size * m + m
        for l in range(self.num_hidden_layers):
            n += attn
            if self.routed(l):
                n += (m * self.n_routed_experts + self.n_routed_experts
                      + (self.experts_held + self.n_shared_experts) * expert)
            else:
                n += 3 * m * self.intermediate_size
        return n


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def init(key, cfg: KimiK2Config) -> dict:
    """Seeded random parameters in ``cfg.dtype`` (normal, std 0.02;
    norms 1), a layer at a time (``init_layer``), a routed expert's by
    its GLOBAL id (models/layers.py ``held_experts``)."""
    return {
        **init_ends(jax.random.fold_in(key, 1 << 20), cfg),
        "layers": [init_layer(key, cfg, l)
                   for l in range(cfg.num_hidden_layers)],
    }


def init_layer(key, cfg: KimiK2Config, l: int) -> dict:
    """Layer ``l``'s parameters, from ``fold_in(key, l)``."""
    return _init_layer(jax.random.fold_in(key, l), cfg, cfg.routed(l))


@functools.partial(jax.jit, static_argnames=("cfg", "routed"))
def _init_layer(key, cfg: KimiK2Config, routed: bool) -> dict:
    m, H, dt = cfg.hidden_size, cfg.num_attention_heads, cfg.dtype
    nope, rope, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
    rq, rkv = cfg.q_lora_rank, cfg.kv_lora_rank
    k = iter(jax.random.split(key, 12))
    p = {
        "ln1": jnp.ones((m,), dt), "ln2": jnp.ones((m,), dt),
        "q_norm": jnp.ones((rq,), dt), "kv_norm": jnp.ones((rkv,), dt),
        "w_dq": normal(next(k), (m, rq), dt),
        "w_uq": normal(next(k), (rq, H, nope + rope), dt),
        "w_dkv": normal(next(k), (m, rkv + rope), dt),
        # [k_nope | v] side by side: W_uk and W_uv of the absorbed form.
        "w_ukv": normal(next(k), (rkv, H, nope + dv), dt),
        "w_o": normal(next(k), (H, dv, m), dt),
    }
    if not routed:
        f = cfg.intermediate_size
        p["w_gu"] = normal(next(k), (m, 2 * f), dt)
        p["w_down"] = normal(next(k), (f, m), dt)
        return p
    E, f = cfg.n_routed_experts, cfg.moe_intermediate_size
    fs = cfg.n_shared_experts * f
    p["router"] = normal(next(k), (m, E), dt)
    p["router_bias"] = normal(next(k), (E,), jnp.float32, ROUTER_BIAS_STD)
    share = (cfg.first_expert, cfg.experts_held)
    p["w1"] = held_experts(next(k), *share, (m, 2 * f), dt)
    p["w2"] = held_experts(next(k), *share, (f, m), dt)
    p["s_gu"] = normal(next(k), (m, 2 * fs), dt)
    p["s_down"] = normal(next(k), (fs, m), dt)
    return p


# ---------------------------------------------------------------------------
# The layer
# ---------------------------------------------------------------------------


def mlp(h2, p, cfg: KimiK2Config, program: str):
    """h2 [T, m] -> (out [T, m], the held experts' tokens [held] or
    None for a dense layer). The grouped product's kernel is
    ``moe_experts_<program>`` on a device trace, with ``_r<rows>``
    behind it where the call's rows give an expert (of ALL
    ``n_routed_experts``, which the call is told: ``w1`` holds a share)
    32 or more and the product takes a taller tile than 16."""
    if "router" not in p:
        return swiglu(h2, p["w_gu"], p["w_down"]), None
    with jax.named_scope("moe_route"):
        _, experts, weights = moe.route_sigmoid(
            h2, p["router"], p["router_bias"], cfg.num_experts_per_tok,
            cfg.routed_scaling_factor)
    with jax.named_scope("moe_experts"):
        y, sizes = moe.routed_experts(
            h2, experts, weights, p["w1"], p["w2"], first=cfg.first_expert,
            n_experts=cfg.n_routed_experts, name=f"moe_experts_{program}")
    return y + swiglu(h2, p["s_gu"], p["s_down"]), sizes


def _project(h, p, positions, cfg: KimiK2Config):
    """The attention sublayer's projections of h [b, r, m] at
    ``positions`` [b, r]: (q_nope [b, r, H, nope], q_rope [b, r, H,
    rope] rotated, the rows' latent rows [b, r, row_width] as the cache
    keeps them)."""
    nope, rkv = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    c_q = rmsnorm(jnp.dot(h, p["w_dq"]), p["q_norm"], cfg.rms_norm_eps)
    q = jnp.einsum("brc,chd->brhd", c_q, p["w_uq"])
    q_rope = rotary(q[..., nope:], positions, cfg.rope,
                     cfg.qk_rope_head_dim)
    ckv = jnp.dot(h, p["w_dkv"])
    c_kv = rmsnorm(ckv[..., :rkv], p["kv_norm"], cfg.rms_norm_eps)
    k_rope = rotary(ckv[..., None, rkv:], positions, cfg.rope,
                     cfg.qk_rope_head_dim)[..., 0, :]
    pad = jnp.zeros(ckv.shape[:-1] + (cfg.row_width - cfg.latent_width,),
                    ckv.dtype)
    return q[..., :nope], q_rope, jnp.concatenate([c_kv, k_rope, pad], -1)


def block(x, p, cfg: KimiK2Config, attend, program: str):
    """The one layer, on [batch, rows, m]: norm -> latent attention ->
    residual -> norm -> MLP -> residual. ``attend(h, p)`` is the mode's
    attention sublayer on the normed rows: it projects them
    (``_project``), keeps their latent rows where the mode keeps them,
    and returns o [b, r, H, v]. Returns (x, the held experts' tokens or
    None)."""
    b, r, m = x.shape
    h = rmsnorm(x, p["ln1"], cfg.rms_norm_eps)
    o = attend(h, p)
    x = x + jnp.einsum("brhd,hdm->brm", o, p["w_o"])
    h2 = rmsnorm(x, p["ln2"], cfg.rms_norm_eps)
    out, sizes = mlp(h2.reshape(b * r, m), p, cfg, program)
    return x + out.reshape(b, r, m), sizes


class Residual(NamedTuple):
    """How the layers sit on the residual path, which the two programs
    below take as given: ``open`` makes what the layers carry of the
    embedded rows [b, r, m], ``block`` is one layer on it (``block``'s
    contract), ``close`` gives back rows [b, r, m] for the final norm.
    ``PLAIN`` is this family's ``x <- x + F(norm(x))``; a family that
    keeps the attention, the router and the experts and changes the
    path (models/xing4.py) hands in its own."""
    open: Callable
    block: Callable
    close: Callable


PLAIN = Residual(lambda x: x, block, lambda x: x)


def forward_step(params, packed, pool, *, q: int, cfg: KimiK2Config,
                 firsts=None, residual: Residual = PLAIN):
    """One decode step (models/gpt.py ``forward_step``'s contract) over
    ONE pool of latent rows ``[layers, num_blocks, block_size,
    row_width]``, in the absorbed form: each row's latent row is
    written at ``(layer, slot_blocks, slot_offsets)``, then the lane's
    queries, taken into the latent space, attend the pool as stored.

    Returns (logits [b, q, vocab], ids [b + 4, q] int32, pool): rows b
    on of ``ids`` are ``COUNTERS``. ``residual`` is the path the layers
    sit on (``Residual``)."""
    (tokens, positions, block_tables, context_lens, q_lens, slot_blocks,
     slot_offsets, _) = unpack_step(packed, q, firsts=firsts)
    B, Q = tokens.shape
    nope, rkv = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    x = residual.open(params["embed"][tokens])
    sizes = []
    for li, p in enumerate(params["layers"]):

        def attend(h, p, li=li):
            nonlocal pool
            q_nope, q_rope, rows = _project(h, p, positions, cfg)
            pool = pool.at[li, slot_blocks, slot_offsets].set(rows)
            w_uk, w_uv = p["w_ukv"][..., :nope], p["w_ukv"][..., nope:]
            q_lat = jnp.einsum("brhd,chd->brhc", q_nope, w_uk)
            pad = jnp.zeros(q_lat.shape[:-1]
                            + (cfg.row_width - cfg.latent_width,), q_lat.dtype)
            with jax.named_scope("attn_latent"):
                o_lat = paged_attention_latent(
                    jnp.concatenate([q_lat, q_rope, pad], -1), pool, li,
                    block_tables, context_lens, q_lens, rank=rkv,
                    scale=cfg.softmax_scale, name="attn_latent")
            return jnp.einsum("brhc,chd->brhd", o_lat, w_uv)

        x, s = residual.block(x, p, cfg, attend, "decode")
        if s is not None:
            sizes.append(s)
    logits = head(params, residual.close(x), cfg.rms_norm_eps)
    ids = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    ids = jnp.concatenate([ids, counters(
        sizes, B * Q, cfg.n_routed_experts, cfg.num_experts_per_tok, Q,
        kv_pages_in_runs_x1000(block_tables, context_lens, pool,
                               score_rows=Q * cfg.num_attention_heads))])
    return logits, ids, pool


def _chunk_attention(q_nope, q_rope, rows, ctx, seen, w_ukv,
                     cfg: KimiK2Config):
    """The attention of a program's rows over [pool context ++ the
    rows], the UP-PROJECTING form: the latent rows of context and spans
    go through ``W_ukv`` to a head's keys and values, ``HEAD_GROUP``
    heads at a time (a plain XLA product), and each group attends in
    the ``chunk_attn`` kernel (ops/pallas/chunk_attention.py): scores
    stay in VMEM under one online softmax, the one rotary key a token
    rides as the part of a key every head shares, context blocks no row
    of a query block sees are neither read nor multiplied. q_nope [n,
    H, nope], q_rope [n, H, rope]; rows [n, W]: the rows' own latent
    rows; ctx [S, W]: the gathered pool slots of the ONE table;
    ``seen`` = (lo, hi, first), int32 [n] each: row i sees the slots
    [lo_i, hi_i) and the rows first_i..i (its own sequence's context
    and its own span). Returns [n, H, v]."""
    n, H, nope = q_nope.shape
    rkv, rope = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    S, dt = ctx.shape[0], q_nope.dtype
    lat = jnp.concatenate([ctx.astype(dt), rows, jnp.zeros(
        (padded_keys(S + n) - S - n, rows.shape[1]), dt)])
    c_kv, k_rope = lat[:, :rkv], lat[:, rkv:rkv + rope]
    hg = max(d for d in range(1, min(HEAD_GROUP, H) + 1) if H % d == 0)

    def group(args):
        q, w = args                     # [hg, n, nope + rope], [hg, rkv, .]
        k = jnp.einsum("sc,hcd->hsd", c_kv, w[..., :nope])
        v = jnp.einsum("sc,hcd->hsd", c_kv, w[..., nope:])
        return chunk_attention(q[:, None], k, v, 0, ctx_slots=S,
                               scale=cfg.softmax_scale, k_shared=k_rope,
                               rows=seen)

    o = jax.lax.map(group, (
        jnp.concatenate([q_nope, q_rope], -1).transpose(1, 0, 2)
        .reshape(H // hg, hg, n, nope + rope),
        w_ukv.transpose(1, 0, 2).reshape(H // hg, hg, rkv, -1)))
    return o.reshape(H, n, -1).transpose(1, 0, 2)   # [groups, hg, 1, n, v]


def forward_prefill_chunk(params, tokens, pool, table, cfg: KimiK2Config,
                          residual: Residual = PLAIN):
    """Up to ``CHUNK_SPANS`` spans, of as many prompts, as ONE program
    (the seam's ``chunk`` where ``chunk_spans`` > 1, over one latent
    pool): ``tokens`` [1, n], the spans' rows end to end, each in whole
    blocks; ``table`` = ``pack_spans``' ``[context table | destination
    blocks | CHUNK_SPANS x (first row, first context slot, ctx_len,
    rows)]``. A row sits at its own sequence's position and attends its
    own sequence's context slots and its own span's rows up to itself
    (``span_rows``, ``chunk_attn``'s per-row description); everything
    else of a layer is a row's own (projections, the residual path,
    the router and the experts, the norms) and runs on the n rows as
    they lie. Every layer reads the pool as it came in; the rows'
    latent rows are written after the last layer, zeros for a span's
    padding. ``residual`` is the path the layers sit on (``Residual``);
    the head runs on each span's last real row, closed alone. The
    program's shapes are n's and the table's length (none at all for a
    lone span from its prompt's start): a lone span is one span used,
    and how several divide the rows is data.

    Returns (rows [CHUNK_SPANS, vocab], ids [CHUNK_SPANS], pool); what a
    span not used gets is the program's last row's."""
    n = tokens.shape[1]
    bs = pool.shape[2]
    block_table, dest, spans = unpack_spans(table, n, bs, CHUNK_SPANS)
    nb = block_table.shape[0]
    first, slot, ctx_len, real = span_rows(spans, n)
    at = ctx_len + jnp.arange(n, dtype=jnp.int32) - first
    positions = jnp.minimum(at, cfg.max_seq - 1)[None]
    seen = (slot, slot + ctx_len, first)
    x = residual.open(params["embed"][tokens])
    new = []
    for li, p in enumerate(params["layers"]):

        def attend(h, p, li=li):
            q_nope, q_rope, rows = _project(h, p, positions, cfg)
            new.append(rows)
            ctx = pool[li, block_table].reshape(nb * bs, pool.shape[3])
            with jax.named_scope("attn_latent_chunk"):
                o = _chunk_attention(q_nope[0], q_rope[0], rows[0], ctx,
                                     seen, p["w_ukv"], cfg)
            return o[None]

        x, _ = residual.block(x, p, cfg, attend, "chunk")
    pool, = scatter_span((pool,), (jnp.stack(new)[:, 0],), dest, real)
    last = jnp.clip(spans[:, 0] + spans[:, 3] - 1, 0, n - 1)
    rows = head(params, residual.close(x[:, last]), cfg.rms_norm_eps)[0]
    return rows, jnp.argmax(rows, axis=-1).astype(jnp.int32), pool


# ---------------------------------------------------------------------------
# The serving seam
# ---------------------------------------------------------------------------


def cost_shape(cfg: KimiK2Config) -> dict:
    """The cost description util/perfmodel.py prices steps from. A
    token passes attention's projections, the router, the shared expert,
    the head and, of its ``num_experts_per_tok`` experts, the share
    held here; a step streams the always-read weights and the held
    experts its rows are expected to hit. A context token costs a
    decode row ``2 x heads x (row + rank)`` operations a layer (the
    absorbed form) and ``latent_width`` cache values; a chunk pays the
    up-projection once a context token and attends with whole heads."""
    m, H, L = cfg.hidden_size, cfg.num_attention_heads, cfg.num_hidden_layers
    nope, rope, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
    rq, rkv = cfg.q_lora_rank, cfg.kv_lora_rank
    E, k, held = cfg.n_routed_experts, cfg.num_experts_per_tok, \
        cfg.experts_held
    attn = (m * rq + rq * H * (nope + rope) + m * cfg.latent_width
            + rkv * H * (nope + dv) + H * dv * m)
    expert = 3 * m * cfg.moe_intermediate_size
    dense = sum(not cfg.routed(l) for l in range(L))
    routed = L - dense
    always = (2 * cfg.vocab_size * m + L * attn
              + dense * 3 * m * cfg.intermediate_size
              + routed * (m * E + cfg.n_shared_experts * expert))
    active = always - cfg.vocab_size * m + routed * expert * k * held / E

    def streamed(rows):
        hit = held * (1.0 - (1.0 - k / E) ** max(rows, 0))
        return always + routed * hit * expert

    return {
        "matmul_weights": active,
        "head_weights": cfg.vocab_size * m,
        # A decode row against a context token, the absorbed form:
        # scores over the latent row, values over its rank columns.
        "attn_per_ctx": 2.0 * L * H * (cfg.latent_width + rkv),
        # A chunk's row against a context token, whole heads; and what
        # taking a context token up to keys and values costs a chunk.
        "chunk_attn_per_ctx": 2.0 * L * H * (nope + rope + dv),
        "chunk_ctx_ops": 2.0 * L * rkv * H * (nope + dv),
        "attn_windows": (),
        "num_params": cfg.num_params(),
        "streamed_params": streamed,
        "param_bytes": cfg.dtype.itemsize,
        # Cache elements a token: one latent row a layer, as stored.
        "kv_bytes_per_token": L * cfg.row_width,
        "m": m, "L": L,
    }


def serving(cfg: KimiK2Config):
    latent = LayerKind("full", tuple(range(cfg.num_hidden_layers)),
                       (cfg.row_width,), None, cfg.dtype)
    return Serving(init=init, step=forward_step,
                   chunk=forward_prefill_chunk, kinds=(latent,),
                   cost=cost_shape(cfg), max_seq=cfg.max_seq,
                   vocab_size=cfg.vocab_size, counters=COUNTERS,
                   chunk_spans=CHUNK_SPANS)
