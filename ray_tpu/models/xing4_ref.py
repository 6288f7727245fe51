"""The plain reference of models/xing4.py: the same layer equations
(that module's docstring) in straightforward ``jax.numpy`` and float32,
one sequence, no cache, no kernel, no batching, no chunks, a layer at a
time. Tests compare the served path's logits with it
(tests/test_xing4.py).

The two sublayers are ``models/kimi_k2_ref.py``'s (``attention_sublayer``
in the NON-absorbed form, ``mlp_sublayer`` given the same share of the
experts), each with its pre-norm inside; what is written here is the
path around them: the streams as ``X`` [T, n, C], the coefficients made
from the token (``coefficients``), the pre-mix, the post-mix, the
opening by copies and the closing by a sum. Everything runs in float32
at matmul precision ``highest``; parameters are raised to float32 as
they are used, so the served bfloat16 weights are the reference's
weights exactly.

Departures from the published description: none known; what the config
leaves open is listed under ``assumed`` in
benchmark/configs/xing4-29b-serve.json ((a) to (g)). The config's
multi-token-prediction module is not built (models/xing4.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import kimi_k2_ref
from .kimi_k2_ref import F32, f32
from .xing4 import Xing4Config


def sinkhorn(M, iters: int, eps: float):
    """[T, n, n] positive -> doubly stochastic: ``iters`` times rows,
    then columns, ``eps`` in both denominators."""
    for _ in range(iters):
        M = M / (M.sum(-1, keepdims=True) + eps)
        M = M / (M.sum(-2, keepdims=True) + eps)
    return M


def coefficients(X, hc, cfg: Xing4Config):
    """X [T, n, C], a sublayer's ``hc`` (float32) -> (H_pre [T, n],
    H_post [T, n], H_res [T, n, n])."""
    T, n, _ = X.shape
    x = X.reshape(T, -1)
    r = jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + cfg.rms_norm_eps)
    m = (r * x) @ hc["phi"]
    a_pre, a_post, a_res = hc["a"]
    b = hc["b"]
    H_pre = jax.nn.sigmoid(a_pre * m[:, :n] + b[:n])
    H_post = 2.0 * jax.nn.sigmoid(a_post * m[:, n:2 * n] + b[n:2 * n])
    A_res = a_res * m[:, 2 * n:].reshape(T, n, n) + b[2 * n:].reshape(n, n)
    M = jnp.exp(jnp.clip(A_res, cfg.mhc_h_res_clamp_min,
                         cfg.mhc_h_res_clamp_max))
    return H_pre, H_post, sinkhorn(M, cfg.hc_sinkhorn_iters, cfg.hc_eps)


def around(X, hc, F, cfg: Xing4Config):
    """One sublayer ``F`` on the streams: X'_i = sum_j H_res[i, j] X_j
    + H_post[i] F(sum_i H_pre[i] X_i)."""
    H_pre, H_post, H_res = coefficients(X, hc, cfg)
    y = F(jnp.einsum("ti,tic->tc", H_pre, X))
    return jnp.einsum("tij,tjc->tic", H_res, X) \
        + H_post[..., None] * y[:, None, :]


def layer(X, p, cfg: Xing4Config, l: int, positions):
    """Layer ``l`` on the whole sequence's streams X [T, n, C]; ``p``
    that layer's parameters in any dtype."""
    p = f32(p)
    X = around(X, p["hc_attn"], lambda h: kimi_k2_ref.attention_sublayer(
        h, p, cfg, positions), cfg)
    return around(X, p["hc_mlp"], lambda h: kimi_k2_ref.mlp_sublayer(
        h, p, cfg, l), cfg)


def forward(params, tokens, cfg: Xing4Config):
    """tokens [T] int -> logits [T, vocab] float32."""
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens)
        positions = jnp.arange(tokens.shape[0])
        x = params["embed"].astype(F32)[tokens]
        X = jnp.broadcast_to(x[:, None, :],
                             (x.shape[0], cfg.hc_mult, x.shape[1]))
        for l, p in enumerate(params["layers"]):
            X = layer(X, p, cfg, l, positions)
        return kimi_k2_ref.head(X.sum(1), params, cfg)
