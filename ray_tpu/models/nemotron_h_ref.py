"""The plain reference of models/nemotron_h.py: the same layer
equations (that module's docstring) in straightforward ``jax.numpy``
and float32, one sequence, no cache, no kernel, no batching, no chunks:
attention as a dense masked softmax, the state-space recurrence as a
``lax.scan`` over TOKENS from a zero state (the served path runs the
chunked form over spans and a one-token kernel over lanes), the
convolution as a sum over shifted copies of the whole sequence. Tests
compare the served path's logits with it (tests/test_nemotron_h.py).

Everything runs in float32 at matmul precision ``highest``; parameters
are raised to float32 as they are used, so the served bfloat16 weights
are the reference's weights exactly. The expert layer is given the same
share the served model holds (``cfg.experts_held`` experts from
``cfg.first_expert``): it routes over all ``n_routed_experts``, loops
over the held experts, every one on every token, kept by the router's
weight where the token chose it, takes the sum up through ``W_up`` and
adds the shared expert.

Departures from the published description: none known. What the config
leaves open (no rotary in attention; the two latent projections before
the dispatch and after the combine; the state in float32) is listed
under ``assumed`` in benchmark/configs/nemotron3-super-serve.json.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .nemotron_h import NemotronHConfig

F32 = jnp.float32


def _f32(p):
    return jax.tree_util.tree_map(lambda a: a.astype(F32), p)


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def relu2(h, w1, w2):
    return jnp.square(jax.nn.relu(h @ w1)) @ w2


def attention(h, p, cfg: NemotronHConfig):
    """h [T, m] -> [T, m]: grouped-query attention, causal, no rotary."""
    T = h.shape[0]
    group = cfg.num_attention_heads // cfg.num_key_value_heads
    q = jnp.einsum("tm,mhd->thd", h, p["wq"])
    k = jnp.repeat(jnp.einsum("tm,mhd->thd", h, p["wk"]), group, axis=1)
    v = jnp.repeat(jnp.einsum("tm,mhd->thd", h, p["wv"]), group, axis=1)
    s = jnp.einsum("thd,shd->hts", q, k) * cfg.head_dim ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], s, -jnp.inf)
    o = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v)
    return jnp.einsum("thd,hdm->tm", o, p["wo"])


def mamba(h, p, cfg: NemotronHConfig):
    """h [T, m] -> [T, m]: the Mamba-2 mixer from a zero state."""
    T = h.shape[0]
    H, P, G, N, K = (cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.n_groups,
                     cfg.ssm_state_size, cfg.conv_kernel)
    di = cfg.d_inner
    proj = h @ p["w_in"]
    z, xBC, dt = (proj[:, :di], proj[:, di:di + cfg.conv_dim],
                  proj[:, di + cfg.conv_dim:])
    # Depthwise causal convolution: row t sees rows t-K+1 .. t, tap k on
    # row t-K+1+k; rows before the sequence are zeros.
    padded = jnp.concatenate([jnp.zeros((K - 1, cfg.conv_dim), F32), xBC])
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
    g = g * jax.lax.rsqrt((g * g).mean(-1, keepdims=True)
                          + cfg.layer_norm_epsilon)
    return (g.reshape(T, di) * p["norm"]) @ p["w_out"]


def route(h, p, cfg: NemotronHConfig):
    """(experts [T, k], weights [T, k]): sigmoid scores, the k largest
    of score + bias, weights the scores without it, renormalised to
    ``routed_scaling_factor``."""
    s = jax.nn.sigmoid(h @ p["router"])
    _, idx = jax.lax.top_k(s + p["router_bias"], cfg.num_experts_per_tok)
    top = jnp.take_along_axis(s, idx, axis=-1)
    return idx, cfg.routed_scaling_factor * top / top.sum(-1, keepdims=True)


def experts(h, p, cfg: NemotronHConfig):
    """h [T, m] -> [T, m]: the held experts' part in the latent space,
    up through ``W_up``, plus the shared expert."""
    T = h.shape[0]
    idx, w = route(h, p, cfg)
    by_expert = jnp.zeros((T, cfg.n_routed_experts), F32).at[
        jnp.arange(T)[:, None], idx].set(w)
    mine = by_expert[:, cfg.first_expert:cfg.first_expert + cfg.experts_held]
    u = h @ p["w_dn"]
    routed = sum(mine[:, e:e + 1] * relu2(u, p["w1"][e], p["w2"][e])
                 for e in range(cfg.experts_held))
    return routed @ p["w_up"] + relu2(h, p["s1"], p["s2"])


def layer(x, p, cfg: NemotronHConfig):
    p = _f32(p)
    h = rmsnorm(x, p["ln"], cfg.layer_norm_epsilon)
    mixer = attention if "wq" in p else mamba if "w_in" in p else experts
    return x + mixer(h, p, cfg)


def forward(params, tokens, cfg: NemotronHConfig):
    """tokens [T] -> logits [T, vocab] float32."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(tokens)].astype(F32)
        for p in params["layers"]:
            x = layer(x, p, cfg)
        x = rmsnorm(x, params["norm_f"].astype(F32), cfg.layer_norm_epsilon)
        return x @ params["head"].astype(F32)
