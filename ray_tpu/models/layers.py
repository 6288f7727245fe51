"""What the served families share, each part defined ONCE under a
public name: rotary positions, the seeded initialiser, RMSNorm, SwiGLU,
the head, a layer's index in its kind's pool, the decode step's four
counter rows, and grouped-query attention over paged keys and values.

This module imports no family: a function takes the widths it needs or
reads them off the parameters it is handed. The float32 references
(models/*_ref.py) take ``rope_inv_freq`` from here, a table of
constants, and none of the arithmetic.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.pallas.chunk_attention import chunk_attention, padded_keys
from ..ops.pallas.paged_fetch import paged_attention_stored


def yarn_mscale(factor: float, m: float) -> float:
    """YaRN's attention scale: ``0.1 m ln(factor) + 1`` above factor 1."""
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def rope_inv_freq(rope, head_dim: int):
    """(inverse frequencies [rot/2] float64, rotated dims, cos/sin
    scale) of one ``rope_parameters`` group. ``yarn`` blends, per
    frequency, the interpolated (1 / (factor * base^(2i/rot))) and the
    extrapolated (1 / base^(2i/rot)) frequency by a linear ramp between
    the dims whose wavelength fits ``beta_fast`` and ``beta_slow``
    turns into the original context."""
    r = dict(rope)
    rot = int(head_dim * r.get("partial_rotary_factor", 1.0))
    base = float(r["rope_theta"])
    pos = base ** (np.arange(0, rot, 2, dtype=np.float64) / rot)
    if r.get("rope_type", "default") == "default":
        return 1.0 / pos, rot, 1.0
    if r["rope_type"] != "yarn":
        raise ValueError(f"rope_type {r['rope_type']!r}")
    orig = r["original_max_position_embeddings"]

    def correction_dim(turns):
        return rot * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(r["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(r["beta_slow"])), rot - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(rot // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    inv = (1.0 / (r["factor"] * pos)) * ramp + (1.0 / pos) * (1.0 - ramp)
    return inv, rot, float(r["attention_factor"])


def rotary(x, positions, rope, head_dim: int):
    """Rotate the leading ``rot`` dims of x [..., heads, d] at
    ``positions`` (x's leading dims); float32 angles, x's dtype out.
    Pairs are (i, i + rot/2), the ``rotate_half`` convention."""
    inv, rot, scale = rope_inv_freq(rope, head_dim)
    ang = positions[..., None].astype(jnp.float32) \
        * jnp.asarray(inv, jnp.float32)
    cos = (jnp.cos(ang) * scale)[..., None, :]
    sin = (jnp.sin(ang) * scale)[..., None, :]
    x32 = x.astype(jnp.float32)
    x1, x2, rest = (x32[..., :rot // 2], x32[..., rot // 2:rot],
                    x32[..., rot:])
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
        axis=-1).astype(x.dtype)


def normal(key, shape, dtype, std=0.02):
    """A seeded normal leaf: drawn in float32, scaled, then rounded."""
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


@functools.partial(jax.jit, static_argnames=("cfg",))
def init_ends(key, cfg) -> dict:
    """What an untied family holds around its layers (``cfg``: any
    configuration, by ``vocab_size``, ``hidden_size`` and ``dtype``)."""
    m, V = cfg.hidden_size, cfg.vocab_size
    ke, kh = jax.random.split(key)
    return {"embed": normal(ke, (V, m), cfg.dtype),
            "head": normal(kh, (m, V), cfg.dtype),
            "norm_f": jnp.ones((m,), cfg.dtype)}


# A sigmoid router's correction bias is drawn from the seed at this size
# (``assumed``: the published ones are learned): sigmoid scores of a
# token's 8th and 9th expert of 384 lie ~0.005 apart, so a bias of this
# size changes the chosen set for most tokens, and "choose by s + b,
# weigh by s" is exercised.
ROUTER_BIAS_STD = 0.02


def held_experts(key, first: int, n: int, shape, dtype):
    """Experts ``first`` to ``first + n`` of one stacked weight of ALL
    the routed experts, [n, *shape]: an expert's draw depends on the key
    and its GLOBAL id alone, so every share of one model holds slices
    of the same experts."""
    return jax.vmap(lambda e: normal(jax.random.fold_in(key, e), shape,
                                     dtype))(first + jnp.arange(n))


def rmsnorm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    out = x32 * jax.lax.rsqrt((x32 * x32).mean(-1, keepdims=True) + eps)
    return (out * scale.astype(jnp.float32)).astype(x.dtype)


def swiglu(h, w_gu, w_down):
    """``(silu(h W_gate) * h W_up) W_down``, gate and up side by side
    in ``w_gu``."""
    gu = jnp.dot(h, w_gu)
    f = gu.shape[-1] // 2
    act = (jax.nn.silu(gu[..., :f].astype(jnp.float32))
           * gu[..., f:].astype(jnp.float32)).astype(h.dtype)
    return jnp.dot(act, w_down)


def head(params, x, eps):
    """The final RMSNorm and the untied head: x [b, r, m] -> logits
    [b, r, vocab]."""
    x = rmsnorm(x, params["norm_f"], eps)
    return jnp.einsum("brm,mv->brv", x, params["head"])


def pool_index(kinds) -> list:
    """layer -> its index among the layers of its kind (``kinds``: a
    letter, or a mixer's name, a layer): where its rows or its state lie
    in the pools."""
    seen, out = {}, []
    for c in kinds:
        out.append(seen.get(c, 0))
        seen[c] = out[-1] + 1
    return out


# The decode step's counters where a chip holds a share of the experts.
COUNTERS = ("moe_experts_hit", "moe_load_max_x1000", "moe_held_rows",
            "kv_pages_in_runs_x1000")


def counters(sizes, rows: int, n_experts: int, top_k: int, q: int, in_runs):
    """The step's counter rows [4, q] int32 (``COUNTERS``): held experts
    that got a token (a routed layer's mean), 1000 x the busiest held
    expert's tokens over the DEPLOYMENT's mean an expert (the worst
    layer: ``rows`` tokens, ``top_k`` of ALL ``n_experts`` each), the
    assignments that fell on the held experts (a layer's mean), and
    ``in_runs``: 1000 x the share of the batch's live cache pages that
    the paged kernel fetches in whole runs. ``sizes`` is the held
    experts' tokens [held] of each routed layer."""
    counts = jnp.zeros((3,), jnp.int32)
    if sizes:
        s = jnp.stack(sizes)                              # [layers, held]
        counts = jnp.stack([
            (s > 0).sum() // len(sizes),
            (s.max() * (1000 * n_experts)) // (rows * top_k),
            s.sum() // len(sizes)])
    return jnp.broadcast_to(
        jnp.append(counts, in_runs)[:, None],
        (len(COUNTERS), q)).astype(jnp.int32)


def span_attention(q, k_tok, v_tok, k_ctx, v_ctx, ctx_len, base,
                   window: Optional[int], scale: Optional[float] = None):
    """A span's attention over [pool context ++ span] as the
    ``chunk_attn`` kernel (ops/pallas/chunk_attention.py): scores stay
    in VMEM under one online softmax, a KV head's group of query heads
    in one tile, context blocks past ``ctx_len`` neither read nor
    multiplied.

    q [c, H, d]; k_tok, v_tok [c, kv, d]: the span, whose query i sits
    at absolute position ctx_len + i. k_ctx, v_ctx [S, kv, d]: the
    sequence's gathered pool slots, slot s at absolute position
    base + s, real where that is below ctx_len. With a ``window`` a
    query sees only keys less than ``window`` positions behind it.
    ``scale`` is the softmax scale where it is not ``d ** -0.5``
    (models/granite_hybrid.py)."""
    c, H, d = q.shape
    S, kv = k_ctx.shape[:2]
    pad = jnp.zeros((padded_keys(S + c) - S - c, kv, d), q.dtype)

    def head_major(ctx, tok):                   # -> [kv, keys, d]
        return jnp.concatenate([ctx.astype(q.dtype), tok, pad],
                               axis=0).transpose(1, 0, 2)

    o = chunk_attention(
        q.reshape(c, kv, H // kv, d).transpose(1, 2, 0, 3),
        head_major(k_ctx, k_tok), head_major(v_ctx, v_tok), ctx_len,
        ctx_slots=S, scale=d ** -0.5 if scale is None else scale, base=base,
        window=window)
    return o.transpose(2, 0, 1, 3).reshape(c, H, d)     # [kv, g, c, d] ->


def attention_params(k, m: int, H: int, kv: int, d: int, dtype) -> dict:
    """The mixer below's parameters from the keys ``k`` yields (four)."""
    return dict(wq=normal(next(k), (m, H, d), dtype),
                wk=normal(next(k), (m, kv, d), dtype),
                wv=normal(next(k), (m, kv, d), dtype),
                wo=normal(next(k), (H, d, m), dtype))


def attention_step(h, p, li: int, k_pool, v_pool, lanes, scale=None):
    """An attention mixer WITHOUT rotary in a decode step: h [B, 1, m]
    after the pre-norm, ``p`` the layer's ``wq``, ``wk``, ``wv``, ``wo``
    (the widths are theirs), the layer's index ``li`` in the pools,
    ``lanes`` = (block tables, context lens, q lens, window starts, slot
    blocks, slot offsets) -> (out [B, 1, m], k_pool, v_pool) with the
    lanes' new rows written. ``scale`` is the softmax scale where it is
    not ``d ** -0.5`` (models/granite_hybrid.py)."""
    block_tables, context_lens, q_lens, starts, slot_blocks, slot_offsets \
        = lanes
    B = h.shape[0]
    kv, d = p["wk"].shape[1:]
    qh = jnp.einsum("brm,mhd->brhd", h, p["wq"])
    k = jnp.einsum("brm,mhd->brhd", h, p["wk"]).reshape(B, 1, kv * d)
    v = jnp.einsum("brm,mhd->brhd", h, p["wv"]).reshape(B, 1, kv * d)
    k_pool = k_pool.at[li, slot_blocks, slot_offsets].set(k)
    v_pool = v_pool.at[li, slot_blocks, slot_offsets].set(v)
    H = qh.shape[2]
    with jax.named_scope("attn_full"):
        o = paged_attention_stored(
            qh.reshape(B, 1, kv, H // kv, d), k_pool, v_pool, li,
            block_tables, context_lens, q_lens, starts, name="attn_full",
            scale=scale)
    out = jnp.einsum("brhd,hdm->brm", o.reshape(B, 1, H, d), p["wo"])
    return out, k_pool, v_pool


def attention_chunk(h, p, li: int, k_pool, v_pool, block_table, ctx_len,
                    scale=None):
    """The same mixer in a prefill span: h [1, n, m] against the context
    the pools hold behind ``block_table`` -> (out [1, n, m], the span's
    keys and values [1, n, kv, d], which the caller writes after the
    last layer)."""
    kv, d = p["wk"].shape[1:]
    slots = block_table.shape[0] * k_pool.shape[2]
    qh = jnp.einsum("brm,mhd->brhd", h, p["wq"])
    k = jnp.einsum("brm,mhd->brhd", h, p["wk"])
    v = jnp.einsum("brm,mhd->brhd", h, p["wv"])
    k_ctx = k_pool[li, block_table].reshape(slots, kv, d)
    v_ctx = v_pool[li, block_table].reshape(slots, kv, d)
    with jax.named_scope("attn_full"):
        o = span_attention(qh[0], k[0], v[0], k_ctx, v_ctx, ctx_len, 0, None,
                           scale)
    return jnp.einsum("brhd,hdm->brm", o[None], p["wo"]), k, v
