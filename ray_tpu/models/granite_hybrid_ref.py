"""The plain reference of models/granite_hybrid.py: the same layer
equations (that module's docstring) in straightforward ``jax.numpy``
and float32, one sequence, no cache, no kernel, no batching, no chunks:
attention as a dense masked softmax, the state-space recurrence as a
``lax.scan`` over TOKENS from a zero state (the served path runs the
chunked form over spans and a one-token kernel over lanes), the
convolution as a sum over shifted copies of the whole sequence, the
experts as a loop over the held ones, every one on every token, kept by
the router's weight where the token chose it. Tests compare the served
path's logits with it (tests/test_granite_hybrid.py).

Everything runs in float32 at matmul precision ``highest``; parameters
are raised to float32 as they are used, so the served bfloat16 weights
are the reference's weights exactly. The expert block is given the same
share the served model holds (``cfg.experts_held`` experts from
``cfg.first_expert``) and the same slice of the vocabulary. The router
is written as the published one (the largest logits, a softmax over
THOSE), not as ops/moe.py's (a softmax over all, the largest,
renormalised): the same numbers by another road.

Departures from the published description: none known. What the config
leaves open (no positional embedding; the gate before the norm and one
norm group in the Mamba-2 mixer; its initialiser; the state in float32)
is listed under ``assumed`` in
benchmark/configs/granite4-h-small-serve.json.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .granite_hybrid import GraniteHybridConfig

F32 = jnp.float32


def _f32(p):
    return jax.tree_util.tree_map(lambda a: a.astype(F32), p)


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def swiglu(u, w_gu, w_down):
    f = w_gu.shape[-1] // 2
    gu = u @ w_gu
    return (jax.nn.silu(gu[:, :f]) * gu[:, f:]) @ w_down


def attention(u, p, cfg: GraniteHybridConfig):
    """u [T, m] -> [T, m]: grouped-query attention, causal, no
    positional embedding, scores scaled by ``attention_multiplier``."""
    T = u.shape[0]
    group = cfg.num_attention_heads // cfg.num_key_value_heads
    q = jnp.einsum("tm,mhd->thd", u, p["wq"])
    k = jnp.repeat(jnp.einsum("tm,mhd->thd", u, p["wk"]), group, axis=1)
    v = jnp.repeat(jnp.einsum("tm,mhd->thd", u, p["wv"]), group, axis=1)
    s = jnp.einsum("thd,shd->hts", q, k) * cfg.attention_multiplier
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], s, -jnp.inf)
    o = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v)
    return jnp.einsum("thd,hdm->tm", o, p["wo"])


def mamba(u, p, cfg: GraniteHybridConfig):
    """u [T, m] -> [T, m]: the Mamba-2 mixer from a zero state."""
    T = u.shape[0]
    H, P, G, N, K = (cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_n_groups,
                     cfg.mamba_d_state, cfg.mamba_d_conv)
    di, cd = H * P, H * P + 2 * G * N
    proj = u @ p["w_in"]
    z, xBC, dt = proj[:, :di], proj[:, di:di + cd], proj[:, di + cd:]
    # Depthwise causal convolution: row t sees rows t-K+1 .. t, tap k on
    # row t-K+1+k; rows before the sequence are zeros.
    padded = jnp.concatenate([jnp.zeros((K - 1, cd), F32), xBC])
    xBC = jax.nn.silu(sum(padded[k:k + T] * p["conv_w"][k]
                          for k in range(K)) + p["conv_b"])
    x = xBC[:, :di].reshape(T, H, P)
    B = jnp.repeat(xBC[:, di:di + G * N].reshape(T, G, N), H // G, axis=1)
    C = jnp.repeat(xBC[:, di + G * N:].reshape(T, G, N), H // G, axis=1)
    dt = jax.nn.softplus(dt + p["dt_bias"])              # [T, H]
    A = -jnp.exp(p["A_log"])

    def token(S, row):
        x_t, B_t, C_t, dt_t = row
        S = (jnp.exp(dt_t * A)[:, None, None] * S
             + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :])
        return S, jnp.einsum("hpn,hn->hp", S, C_t) + p["D"][:, None] * x_t

    _, y = jax.lax.scan(token, jnp.zeros((H, P, N), F32), (x, B, C, dt))
    y = y.reshape(T, di) * jax.nn.silu(z)
    g = y.reshape(T, G, di // G)
    g = g * jax.lax.rsqrt((g * g).mean(-1, keepdims=True) + cfg.rms_norm_eps)
    return (g.reshape(T, di) * p["norm"]) @ p["w_out"]


def route(u, p, cfg: GraniteHybridConfig):
    """(experts [T, k], weights [T, k]): the k largest router logits,
    a softmax over those k."""
    top, idx = jax.lax.top_k(u @ p["router"], cfg.num_experts_per_tok)
    return idx, jax.nn.softmax(top, axis=-1)


def experts(u, p, cfg: GraniteHybridConfig):
    """u [T, m] -> [T, m]: the held experts' part plus the shared MLP."""
    T = u.shape[0]
    idx, w = route(u, p, cfg)
    by_expert = jnp.zeros((T, cfg.num_local_experts), F32).at[
        jnp.arange(T)[:, None], idx].set(w)
    mine = by_expert[:, cfg.first_expert:cfg.first_expert + cfg.experts_held]
    routed = sum(mine[:, e:e + 1] * swiglu(u, p["w1"][e], p["w2"][e])
                 for e in range(cfg.experts_held))
    return routed + swiglu(u, p["s_gu"], p["s_down"])


def layer(x, p, cfg: GraniteHybridConfig):
    p, r = _f32(p), cfg.residual_multiplier
    mixer = attention if "wq" in p else mamba
    h = x + r * mixer(rmsnorm(x, p["ln_a"], cfg.rms_norm_eps), p, cfg)
    return h + r * experts(rmsnorm(h, p["ln_b"], cfg.rms_norm_eps), p, cfg)


def forward(params, tokens, cfg: GraniteHybridConfig):
    """tokens [T] -> logits [T, vocab] float32."""
    with jax.default_matmul_precision("highest"):
        embed = params["embed"].astype(F32)
        x = cfg.embedding_multiplier * embed[jnp.asarray(tokens)]
        for p in params["layers"]:
            x = layer(x, p, cfg)
        x = rmsnorm(x, params["norm_f"].astype(F32), cfg.rms_norm_eps)
        return x @ embed.T / cfg.logits_scaling
