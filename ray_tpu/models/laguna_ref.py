"""The plain reference of models/laguna.py: the same layer equations
(that module's docstring) in straightforward ``jax.numpy`` and float32,
one sequence, no cache, no kernel, no batching, no chunks. Tests compare
the served path's logits with it (tests/test_laguna.py), and
``chip_smoke.py`` does so on the chip at the published widths.

Everything runs in float32 at matmul precision ``highest`` (on a TPU a
float32 matmul is otherwise computed in bfloat16 passes); parameters
are raised to float32 as they are used, so the served bfloat16 weights
are the reference's weights exactly. ``layer`` is one layer on the
whole sequence, so a caller short of memory can walk the layers itself
and hold one layer's float32 weights at a time. Experts are a loop:
every expert on every token, kept where the router chose it.

Departures from the published description: none known; what the config
leaves open is listed under ``assumed`` in
benchmark/configs/laguna-xs2-serve.json.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .laguna import DENSE, SLIDING, LagunaConfig
from .layers import rope_inv_freq

F32 = jnp.float32


def _f32(p):
    return jax.tree_util.tree_map(lambda a: a.astype(F32), p)


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def rotary(x, positions, rope, head_dim):
    """x [T, heads, d] at ``positions`` [T]: pairs (i, i + rot/2) of the
    first ``rot`` dims turn by position * inv_freq_i, cos and sin
    scaled by the group's attention factor."""
    inv, rot, scale = rope_inv_freq(rope, head_dim)
    ang = positions.astype(F32)[:, None] * jnp.asarray(inv, F32)[None]
    cos, sin = jnp.cos(ang)[:, None] * scale, jnp.sin(ang)[:, None] * scale
    a, b = x[..., :rot // 2], x[..., rot // 2:rot]
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin, x[..., rot:]], axis=-1)


def swiglu(h, w_gu, w_down):
    f = w_gu.shape[-1] // 2
    return (jax.nn.silu(h @ w_gu[:, :f]) * (h @ w_gu[:, f:])) @ w_down


def attention(q, k, v, window):
    """q [T, H, d], k, v [T, kv, d]: causal softmax attention, head h on
    KV head h // (H / kv), a key at most ``window - 1`` behind its
    query where there is a window. One head at a time."""
    T, H, d = q.shape
    rep = H // k.shape[1]
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    mask = j <= i
    if window is not None:
        mask = mask & (i - j < window)

    def head(args):
        qh, kh, vh = args
        s = jnp.where(mask, (qh @ kh.T) / jnp.sqrt(F32(d)), -jnp.inf)
        return jax.nn.softmax(s, axis=-1) @ vh

    kr, vr = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    o = jax.lax.map(head, (q.transpose(1, 0, 2), kr.transpose(1, 0, 2),
                           vr.transpose(1, 0, 2)))
    return o.transpose(1, 0, 2)


def routed(h, p, cfg: LagunaConfig):
    """softmax over all experts, the top k renormalised to the scaling
    factor, every expert evaluated on every token and kept by its
    weight (0 where it was not chosen)."""
    probs = jax.nn.softmax(h @ p["router"], axis=-1)
    top, idx = jax.lax.top_k(probs, cfg.num_experts_per_tok)
    w = cfg.moe_routed_scaling_factor * top / top.sum(-1, keepdims=True)
    dense_w = jnp.zeros_like(probs).at[
        jnp.arange(h.shape[0])[:, None], idx].set(w)        # [T, E]

    def add(acc, xs):
        w1, w2, we = xs
        return acc + we[:, None] * swiglu(h, w1, w2), None

    out, _ = jax.lax.scan(add, jnp.zeros_like(h),
                          (p["w1"], p["w2"], dense_w.T))
    return out


def layer(x, p, cfg: LagunaConfig, l: int, positions):
    """Layer ``l`` on the whole sequence x [T, m]; ``p`` that layer's
    parameters in any dtype."""
    p = _f32(p)
    window = cfg.sliding_window if cfg.layer_types[l] == SLIDING else None
    rope = cfg.rope_sliding if window else cfg.rope_full
    h = rmsnorm(x, p["ln1"], cfg.rms_norm_eps)
    q = jnp.einsum("tm,mhd->thd", h, p["wq"])
    k = jnp.einsum("tm,mhd->thd", h, p["wk"])
    v = jnp.einsum("tm,mhd->thd", h, p["wv"])
    g = jax.nn.sigmoid(h @ p["wg"])
    q = rotary(q, positions, rope, cfg.head_dim)
    k = rotary(k, positions, rope, cfg.head_dim)
    o = attention(q, k, v, window) * g[..., None]
    x = x + jnp.einsum("thd,hdm->tm", o, p["wo"])
    h2 = rmsnorm(x, p["ln2"], cfg.rms_norm_eps)
    if cfg.mlp_layer_types[l] == DENSE:
        return x + swiglu(h2, p["w_gu"], p["w_down"])
    return x + routed(h2, p, cfg) + swiglu(h2, p["s_gu"], p["s_down"])


def head(x, params, cfg: LagunaConfig):
    x = rmsnorm(x, params["norm_f"].astype(F32), cfg.rms_norm_eps)
    return x @ params["head"].astype(F32)


def forward(params, tokens, cfg: LagunaConfig):
    """tokens [T] int -> logits [T, vocab] float32."""
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens)
        positions = jnp.arange(tokens.shape[0])
        x = params["embed"].astype(F32)[tokens]
        for l, p in enumerate(params["layers"]):
            x = layer(x, p, cfg, l, positions)
        return head(x, params, cfg)
