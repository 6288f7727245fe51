"""The plain reference of models/kimi_k2.py: the same layer equations
(that module's docstring) in straightforward ``jax.numpy`` and float32,
in the NON-absorbed form (every head's keys and values are made from
the latent vectors and attended as ordinary heads), one sequence, no
cache, no kernel, no batching, no chunks. Tests compare the served
path's logits with it (tests/test_kimi_k2.py), and ``chip_smoke.py``
does so on the chip at the published widths.

Everything runs in float32 at matmul precision ``highest``; parameters
are raised to float32 as they are used, so the served bfloat16 weights
are the reference's weights exactly. ``layer`` is one layer on the
whole sequence. The routed layer is given the same share the served
model holds (``cfg.experts_held`` experts from ``cfg.first_expert``):
it routes over all ``n_routed_experts``, loops over the held experts,
every one on every token, kept by the router's weight where the token
chose it, and adds the shared expert.

Departures from the published description: none known; what the config
leaves open is listed under ``assumed`` in
benchmark/configs/kimi-k25-serve.json.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from .kimi_k2 import KimiK2Config

F32 = jnp.float32


def f32(p):
    return jax.tree_util.tree_map(lambda a: a.astype(F32), p)


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def mscale(s: float, m: float) -> float:
    return 1.0 if s <= 1 else 0.1 * m * math.log(s) + 1.0


def yarn_inv_freq(cfg: KimiK2Config):
    """Inverse frequencies [rope/2] (float64) of the rotary dims: per
    frequency a blend of the interpolated ``1 / (factor base^(2i/d))``
    and the extrapolated ``1 / base^(2i/d)`` by a linear ramp between
    the dims whose wavelength fits ``beta_fast`` and ``beta_slow`` turns
    into the original context."""
    r, d, base = dict(cfg.rope_scaling), cfg.qk_rope_head_dim, cfg.rope_theta
    orig = r["original_max_position_embeddings"]
    pos = base ** (np.arange(0, d, 2, dtype=np.float64) / d)

    def dim_of(turns):
        return d * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(dim_of(r["beta_fast"])), 0)
    high = min(math.ceil(dim_of(r["beta_slow"])), d - 1)
    high = high + 0.001 if low == high else high
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0.0, 1.0)
    return ramp / (r["factor"] * pos) + (1.0 - ramp) / pos


def softmax_scale(cfg: KimiK2Config) -> float:
    r = dict(cfg.rope_scaling)
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5 \
        * mscale(r["factor"], r["mscale_all_dim"]) ** 2


def rotary(x, positions, cfg: KimiK2Config):
    """x [T, heads, rope] at ``positions`` [T]: pairs (i, i + rope/2)
    turn by position * inv_freq_i; cos and sin scaled by
    mscale(factor, mscale) / mscale(factor, mscale_all_dim)."""
    r = dict(cfg.rope_scaling)
    scale = mscale(r["factor"], r["mscale"]) \
        / mscale(r["factor"], r["mscale_all_dim"])
    ang = positions.astype(F32)[:, None] \
        * jnp.asarray(yarn_inv_freq(cfg), F32)[None]
    cos, sin = jnp.cos(ang)[:, None] * scale, jnp.sin(ang)[:, None] * scale
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def swiglu(h, w_gu, w_down):
    f = w_gu.shape[-1] // 2
    return (jax.nn.silu(h @ w_gu[:, :f]) * (h @ w_gu[:, f:])) @ w_down


def attention(q, k, v, scale: float):
    """q, k [T, H, d], v [T, H, dv]: causal softmax attention, one head
    at a time."""
    T = q.shape[0]
    mask = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]

    def head(args):
        qh, kh, vh = args
        s = jnp.where(mask, (qh @ kh.T) * scale, -jnp.inf)
        return jax.nn.softmax(s, axis=-1) @ vh

    o = jax.lax.map(head, (q.transpose(1, 0, 2), k.transpose(1, 0, 2),
                           v.transpose(1, 0, 2)))
    return o.transpose(1, 0, 2)


def route(h, router, bias, cfg: KimiK2Config):
    """(experts [T, k], weights [T, k]): sigmoid scores over all
    experts, the k largest of score + bias, weights the chosen scores
    (without the bias) renormalised to the scaling factor."""
    s = jax.nn.sigmoid(h @ router)
    _, idx = jax.lax.top_k(s + bias, cfg.num_experts_per_tok)
    top = jnp.take_along_axis(s, idx, axis=-1)
    return idx, cfg.routed_scaling_factor * top / top.sum(-1, keepdims=True)


def routed(h, p, cfg: KimiK2Config):
    """The held experts' part of the routed layer: every held expert
    on every token, kept by the router's weight (0 where the token did
    not choose it)."""
    idx, w = route(h, p["router"], p["router_bias"], cfg)
    by_expert = jnp.zeros((h.shape[0], cfg.n_routed_experts), F32).at[
        jnp.arange(h.shape[0])[:, None], idx].set(w)
    mine = by_expert[:, cfg.first_expert:cfg.first_expert + cfg.experts_held]

    def add(acc, xs):
        w1, w2, we = xs
        return acc + we[:, None] * swiglu(h, w1, w2), None

    out, _ = jax.lax.scan(add, jnp.zeros_like(h),
                          (p["w1"], p["w2"], mine.T))
    return out


def attention_sublayer(x, p, cfg: KimiK2Config, positions):
    """The attention sublayer on x [T, m], its pre-norm inside and no
    residual added: ``Attn(RMSNorm(x)) W_o``; ``p`` float32."""
    nope, rkv, eps = cfg.qk_nope_head_dim, cfg.kv_lora_rank, cfg.rms_norm_eps
    h = rmsnorm(x, p["ln1"], eps)
    c_q = rmsnorm(h @ p["w_dq"], p["q_norm"], eps)
    q = jnp.einsum("tc,chd->thd", c_q, p["w_uq"])
    ckv = h @ p["w_dkv"]
    c_kv = rmsnorm(ckv[:, :rkv], p["kv_norm"], eps)
    k_rope = rotary(ckv[:, None, rkv:], positions, cfg)      # [T, 1, rope]
    kv = jnp.einsum("tc,chd->thd", c_kv, p["w_ukv"])
    H = q.shape[1]
    q = jnp.concatenate(
        [q[..., :nope], rotary(q[..., nope:], positions, cfg)], axis=-1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope, (x.shape[0], H,
                                                   k_rope.shape[-1]))], -1)
    o = attention(q, k, kv[..., nope:], softmax_scale(cfg))
    return jnp.einsum("thd,hdm->tm", o, p["w_o"])


def mlp_sublayer(x, p, cfg: KimiK2Config, l: int):
    """Layer ``l``'s MLP sublayer on x [T, m], its pre-norm inside and
    no residual added; ``p`` float32."""
    h2 = rmsnorm(x, p["ln2"], cfg.rms_norm_eps)
    if not cfg.routed(l):
        return swiglu(h2, p["w_gu"], p["w_down"])
    return routed(h2, p, cfg) + swiglu(h2, p["s_gu"], p["s_down"])


def layer(x, p, cfg: KimiK2Config, l: int, positions):
    """Layer ``l`` on the whole sequence x [T, m]; ``p`` that layer's
    parameters in any dtype."""
    p = f32(p)
    x = x + attention_sublayer(x, p, cfg, positions)
    return x + mlp_sublayer(x, p, cfg, l)


def head(x, params, cfg: KimiK2Config):
    x = rmsnorm(x, params["norm_f"].astype(F32), cfg.rms_norm_eps)
    return x @ params["head"].astype(F32)


def forward(params, tokens, cfg: KimiK2Config):
    """tokens [T] int -> logits [T, vocab] float32."""
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens)
        positions = jnp.arange(tokens.shape[0])
        x = params["embed"].astype(F32)[tokens]
        for l, p in enumerate(params["layers"]):
            x = layer(x, p, cfg, l, positions)
        return head(x, params, cfg)
