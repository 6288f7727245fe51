"""The plain reference that decides ``correct`` for the Xing4.0
configurations, and its limits: the benchmark's own copy of the layer
equations (``ray_tpu/models/xing4_ref.py`` is the repository's, which
its tests use), kept here so that no later PR can move what a cell is
judged by. A configuration names this module under ``reference``; the
interface is the one ``drivers/serve_closed_loop_ref.py`` calls
(``served_router_of``, ``compare``, ``token_checks``,
``router_checks``).

What it computes (``forward``): the model's full forward pass over
prompt + answer, one sequence, no cache, no kernel, no batching, no
chunks, in float32 at matmul precision ``highest``, on the SERVED
parameters (bfloat16, made from ``--seed`` by the model's own ``init``)
raised to float32 a layer at a time (a routed layer's experts one at a
time), attention in the NON-absorbed form in blocks of ``ROWS``
queries. It holds every routed expert, as the served model does.
Sequences are padded to one length a run so that every comparison
shares one compiled program a kind of layer (causal: the padding
reaches nothing before it).

The equations (a token's residual is n = hc_mult streams X [n, C];
every sublayer F sits on them the same way, with its own Phi, a, b):
  x = vec(X); r = rsqrt(mean(x^2) + rms_norm_eps); m = (r x) Phi
  H_pre = sigmoid(a_pre m[0:n] + b_pre); H_post = 2 sigmoid(a_post
    m[n:2n] + b_post); M = exp(clip(a_res mat(m[2n:]) + b_res, -30, 30));
    20 times: M <- M / (rowsum M + hc_eps), M <- M / (colsum M + hc_eps);
    H_res = M
  h = sum_i H_pre[i] X_i; y = F(h); X'_i = sum_j H_res[i,j] X_j
    + H_post[i] y
  F attention: g = RMSNorm(h); c_q = RMSNorm(g W_dq); q = c_q W_uq;
    [c_kv | k_r] = g W_dkv; c_kv <- RMSNorm(c_kv); k_rope = RoPE(k_r),
    one a token for all heads; q_rope <- RoPE(q_rope) (pairs (i, i +
    rope/2), YaRN); [k_nope | v] = c_kv W_ukv; causal softmax((q_nope .
    k_nope + q_rope . k_rope) (nope+rope)^-0.5 mscale^2) v; W_o
  F MLP: g = RMSNorm(h); dense: (silu(g W_gate) * g W_up) W_down;
    routed: s = sigmoid(g W_r); the 4 largest of s + b; w = 2 s_top /
    sum(s_top); sum_e w_e Expert_e(g) + Shared(g)
  opening: every stream the token's embedding; closing: logits =
    RMSNorm(sum_i X_i) W_head

What is compared, in three parts: the two of ``reference_kimi_k2``, for
its reasons (the tokens of a top-k routed model in bfloat16 cannot all
equal a float32 reference's; the router, as a function on identical
inputs, can), and the residual path as a function on identical inputs,
for the same reason (coefficients one precision lower move few tokens
of a model whose streams are bfloat16 anyway, and every coefficient):

1. TOKENS, ``token_checks``: every served token of the compared
   answers, teacher-forced; a token's margin is how far the reference
   prefers its own argmax to the served token, 0 where they agree.
   Pooled over a run's compared tokens.
2. THE ROUTER, ``router_checks``' first two lines: the function the
   served programs route with (``served_router``) against this
   reference's router ON IDENTICAL INPUTS, the reference's own router
   inputs of the compared sequences rounded to the served dtype.
3. THE RESIDUAL PATH, the last two of ``router_checks``' lines (the
   driver calls that function for what is compared as a function on
   identical inputs): the two kernels the served programs mix with
   (``SERVED_PRE``, ``SERVED_POST``: ``ray_tpu.ops.mhc``), called as
   the programs call them, on the reference's own ``X`` and ``y`` of
   EVERY sublayer of the compared sequences, rounded to the served
   dtype, against this reference's equations on the same rounded
   inputs. Read: the largest difference of any coefficient (``H_pre``,
   ``H_post``, ``H_res``; float32 on both sides: ``MAX_MHC_DIFF``), and
   the largest difference of the two mixes (``h``, ``X'``, which the
   kernels hand back in the served dtype) from the reference's, over
   the largest entry of each (``MAX_MHC_MIX_DIFF``: a rounding of the
   result is sound, a wrong coefficient is not).

``lower=True`` is the nearest precision below the float32 that the
configuration's file states for them: the router's scores and their
sigmoid, and the residual path's coefficients (``m``, and ``H_pre``,
``H_post``, ``H_res`` as they leave the chain; ``lax.reduce_precision``,
since the TPU's compiler drops an ``astype`` round trip), in bfloat16.
It has to come out NOT correct.

With ``BENCH_XING_CONTROLS`` set in the environment ``compare`` reads
the same answers again one precision lower and with each fault of
``FAULTS`` planted in the reference, and logs what the limits say of
each. They decide nothing.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib
import os

# What the references share, from the accepted ones (no file of the
# benchmark may change, so they cannot move under this one): a token's
# margin, the lookup of the served router, the YaRN blend and the final
# norm and head on this family's field names, the reference's router,
# and the router's comparison that knows "one expert fewer".
from benchmark.reference_kimi_k2 import (_head_fn, _inv_freq, _route,  # noqa: F401
                                         margins, mscale, served_router_of)
from benchmark.reference_nemotron_h import router_agreement

# Limits, each between two readings (my chip runs, PR 62: PERF.md
# section 6 has every reading by call and seed). "Sound" is the served
# path as committed: twelve runs of the cell on twelve seeds (calls B,
# C, D and F), 539-568 compared tokens each. The faults were planted in
# the reference and read against the served tokens and the served
# kernels of three of those runs (calls B, D and F,
# ``BENCH_XING_CONTROLS``; the limits were set after call C).
#
# A float32 reference and the bfloat16 served path agree on fewer
# tokens here than in the other cells: the whole 131,072-token
# vocabulary leaves a token's top two logits ~0.27 apart, and with all
# 64 experts held every top-4 choice that bfloat16 flips is another
# MLP. So the token limits stand wide and the two functions on
# identical inputs (router, residual path) carry the precision.
#
# Pooled over a run's compared tokens:
#   share of tokens equal: sound 0.789-0.860; the rope part left out
#   0.020, H_post without its 2 0.029-0.035, one expert fewer
#   0.188-0.212, H_pre left out 0.480-0.486. (a_res = 0 reads
#   0.692-0.706, one precision lower 0.770-0.790, 3 iterations
#   0.803-0.825: the coefficients' limit's to fail.)
MIN_EXACT_SHARE = 0.70
#   mean margin: sound 0.050-0.100; H_pre left out 0.342-0.360, one
#   expert fewer 0.665-0.773, H_post without its 2 1.81-1.96, the rope
#   part left out 2.47. (a_res = 0 0.116-0.155, one precision lower
#   0.093-0.121.)
MAX_MEAN_MARGIN = 0.25
# A single token: sound 1.58-3.13 in twelve runs (a token whose experts
# the served bfloat16 activations swapped, in a vocabulary of 131,072);
# the rope part left out of the scores 6.71. (H_post without its 2
# reads 4.84-5.38, on both sides of this limit, H_pre left out
# 3.83-4.15 and one expert fewer 3.35-3.52: the two limits' above to
# fail, and they do in every reading.)
MAX_MARGIN = 5.0
# Share of tokens whose 4 experts the served router and the reference's
# pick alike, on identical inputs: float32 scores every token of every
# run (64,830 of 64,830 in call B); scores and sigmoid in bfloat16
# 0.942-0.943, 3 experts a token none.
MIN_ROUTER_AGREEMENT = 0.99
# Largest difference between the served router's weights and the
# reference's on a token whose experts they pick alike (a weight is
# ~2 / 4 = 0.5): sound 0.000000 in every run; one precision lower
# 0.0019 (it fails the agreement).
MAX_WEIGHT_DIFF = 0.05
# Largest difference of a coefficient the served kernel makes (H_pre,
# H_post, H_res; float32 on both sides) from the reference's on the
# same inputs, every sublayer of every compared sequence: sound 1.3e-6
# to 2.7e-6 in twelve runs; coefficients in bfloat16 6.4e-3 to 7.2e-3,
# 3 Sinkhorn iterations 0.24-0.32, a_res = 0 0.80-0.86, H_post without
# its 2 and H_pre left out 1.00.
MAX_MHC_DIFF = 5e-4
# Largest difference of a mix (``h``, ``X'``) from the reference's over
# the largest entry of that mix: sound 3.0e-3 to 3.9e-3, the rounding
# of the kernels' results to bfloat16 (2^-8 = 3.9e-3 of an entry); 3
# iterations 0.20-0.26, a_res = 0 0.43-0.54, H_pre left out 0.83,
# H_post without its 2 1.00. (Coefficients in bfloat16 read 5.5e-3 to
# 5.9e-3 and pass: the limit above's.)
MAX_MHC_MIX_DIFF = 0.02
# The two kernels the served programs mix with, and what takes the
# first's slab apart: "module:attribute".
SERVED_PRE = "ray_tpu.ops.mhc:mhc_pre"
SERVED_POST = "ray_tpu.ops.mhc:mhc_post"
SERVED_COEFFICIENTS = "ray_tpu.ops.mhc:coefficients"
PAD_TO = 1024           # a run's sequences are padded to a multiple of this
ROWS = 1024             # queries (and MLP rows) computed at a time

# Departures planted in the reference, each of which has to fail a
# limit: name -> what it changes.
FAULTS = {
    "three_sinkhorn_iterations": "3 Sinkhorn iterations instead of 20",
    "h_post_without_its_2": "H_post without its factor 2",
    "static_h_res": "a_res = 0: H_res one static matrix",
    "h_pre_left_out": "H_pre left out: a sublayer sees the streams' plain sum",
    "one_expert_fewer": "one expert fewer a token",
    "no_rope_term": "the rope part left out of the attention scores",
}


def _served(path: str):
    module, name = path.split(":")
    return getattr(importlib.import_module(module), name)


def _bf16(x):
    """``x`` rounded to bfloat16's 8 bits of mantissa, float32 kept."""
    import jax

    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _coefficients(X, hc, cfg, lower: bool, fault):
    """The reference's coefficients of X [T, n, C] (float32) under a
    sublayer's ``hc`` (float32): (H_pre [T, n], H_post [T, n], H_res
    [T, n, n])."""
    import jax
    import jax.numpy as jnp

    T, n, _ = X.shape
    x = X.reshape(T, -1)
    r = jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + cfg.rms_norm_eps)
    m = (r * x) @ hc["phi"]
    if lower:
        m = _bf16(m)
    a_pre, a_post, a_res = hc["a"][0], hc["a"][1], hc["a"][2]
    if fault == "static_h_res":
        a_res = 0.0
    b = hc["b"]
    H_pre = jax.nn.sigmoid(a_pre * m[:, :n] + b[:n])
    if fault == "h_pre_left_out":
        H_pre = jnp.ones_like(H_pre)
    H_post = jax.nn.sigmoid(a_post * m[:, n:2 * n] + b[n:2 * n])
    if fault != "h_post_without_its_2":
        H_post = 2.0 * H_post
    M = jnp.exp(jnp.clip(
        a_res * m[:, 2 * n:].reshape(T, n, n) + b[2 * n:].reshape(n, n),
        cfg.mhc_h_res_clamp_min, cfg.mhc_h_res_clamp_max))
    iters = 3 if fault == "three_sinkhorn_iterations" \
        else cfg.hc_sinkhorn_iters
    for _ in range(iters):
        M = M / (M.sum(-1, keepdims=True) + cfg.hc_eps)
        M = M / (M.sum(-2, keepdims=True) + cfg.hc_eps)
    if lower:
        H_pre, H_post, M = _bf16(H_pre), _bf16(H_post), _bf16(M)
    return H_pre, H_post, M


def _pre_mix(X, H_pre):
    """h = sum_i H_pre[i] X_i, float32."""
    import jax.numpy as jnp

    return jnp.einsum("ti,tic->tc", H_pre, X)


def _post_mix(X, y, H_post, H_res):
    """X'_i = sum_j H_res[i, j] X_j + H_post[i] y, float32."""
    import jax.numpy as jnp

    return jnp.einsum("tij,tjc->tic", H_res, X) \
        + H_post[..., None] * y[:, None, :]


@functools.lru_cache(maxsize=None)
def _layer_fn(cfg, routed: bool, T: int, lower: bool, fault):
    """One layer on a whole padded sequence's streams [T, n, C],
    jitted: ``fn(X, p)`` -> (X out, the router's input or None for a
    dense layer, and a sublayer what the served path's comparison
    needs: the sublayer's ``X`` and ``y`` rounded to the served dtype
    and this reference's coefficients and mixes ON those)."""
    import jax
    import jax.numpy as jnp

    F32 = jnp.float32
    scores_in = jnp.dtype(cfg.dtype) if lower else jnp.dtype(F32)
    nope, rope, rkv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                       cfg.kv_lora_rank)
    eps = cfg.rms_norm_eps
    r = dict(cfg.rope_scaling)
    scale = (nope + rope) ** -0.5 * mscale(r["factor"],
                                           r["mscale_all_dim"]) ** 2
    inv, cs = _inv_freq(cfg)
    rows = min(ROWS, T)
    as_served = _bf16 if jnp.dtype(cfg.dtype) == jnp.bfloat16 \
        else (lambda x: x)

    def norm(x, w):
        return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * w

    def rotary(x):                                  # [heads, T, rope]
        ang = jnp.arange(T, dtype=F32)[:, None] * jnp.asarray(inv, F32)[None]
        cos, sin = jnp.cos(ang) * cs, jnp.sin(ang) * cs
        a, b = x[..., :rope // 2], x[..., rope // 2:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

    def by_rows(fn, x):
        out = jax.lax.map(fn, x.reshape(T // rows, rows, *x.shape[1:]))
        return out.reshape(T, *out.shape[2:])

    def swiglu(h, w_gu, w_down):
        w_gu, w_down = w_gu.astype(F32), w_down.astype(F32)
        f = w_gu.shape[-1] // 2
        return by_rows(lambda b: (jax.nn.silu(b @ w_gu[:, :f])
                                  * (b @ w_gu[:, f:])) @ w_down, h)

    def attention(h, p):
        g = norm(h, p["ln1"])
        c_q = norm(g @ p["w_dq"], p["q_norm"])
        q = jnp.einsum("tc,chd->htd", c_q, p["w_uq"])
        ckv = g @ p["w_dkv"]
        c_kv = norm(ckv[:, :rkv], p["kv_norm"])
        k_rope = rotary(ckv[None, :, rkv:])[0]               # [T, rope]
        kv = jnp.einsum("tc,chd->htd", c_kv, p["w_ukv"])
        q_rope = rotary(q[..., nope:])
        if fault == "no_rope_term":
            q_rope = jnp.zeros_like(q_rope)
        j = jnp.arange(T)[None, :]

        def head(args):
            qn, qr, kvh = args       # [T, nope], [T, rope], [T, nope + v]
            kn, vh = kvh[:, :nope], kvh[:, nope:]

            def block(b):
                qn_b, qr_b, i0 = b
                i = i0 + jnp.arange(rows)[:, None]
                s = (qn_b @ kn.T + qr_b @ k_rope.T) * scale
                s = jnp.where(j <= i, s, -jnp.inf)
                return jax.nn.softmax(s, axis=-1) @ vh

            o = jax.lax.map(block, (
                qn.reshape(T // rows, rows, nope),
                qr.reshape(T // rows, rows, rope),
                jnp.arange(0, T, rows)))
            return o.reshape(T, -1)

        o = jax.lax.map(head, (q[..., :nope], q_rope, kv))
        return jnp.einsum("htd,hdm->tm", o, p["w_o"]), None

    def mlp(h, p):
        g = norm(h, p["ln2"])
        if not routed:
            return swiglu(g, p["w_gu"], p["w_down"]), None
        idx, w = _route(g, p["router"], p["router_bias"], cfg, scores_in)
        by_expert = jnp.zeros((T, cfg.n_routed_experts), F32).at[
            jnp.arange(T)[:, None], idx].set(w)
        mine = by_expert[:, cfg.first_expert:
                         cfg.first_expert + cfg.experts_held]

        def add(acc, xs):
            w1, w2, we = xs             # one expert's, in the served dtype
            return acc + we[:, None] * swiglu(g, w1, w2), None

        out, _ = jax.lax.scan(add, jnp.zeros_like(g),
                              (p["w1"], p["w2"], mine.T))
        return out + swiglu(g, p["s_gu"], p["s_down"]), g

    def around(X, hc, F, p):
        H_pre, H_post, H_res = _coefficients(X, hc, cfg, lower, fault)
        y, extra = F(_pre_mix(X, H_pre), p)
        out = _post_mix(X, y, H_post, H_res)
        # The same equations on the inputs as the served kernels get
        # them: rounded to the served dtype.
        Xr, yr = as_served(X), as_served(y)
        cr = _coefficients(Xr, hc, cfg, lower, fault)
        served = (Xr.reshape(T, -1).astype(cfg.dtype), yr.astype(cfg.dtype),
                  *cr, _pre_mix(Xr, cr[0]),
                  _post_mix(Xr, yr, cr[1], cr[2]).reshape(T, -1))
        return out, extra, served

    # The experts stay in the served dtype until their turn in the
    # scan: a float32 copy of all 64 is 2.8 GB beside 11 GB of weights.
    stacked = ("w1", "w2")

    def fn(X, p):
        p = {k: (v if k in stacked else jax.tree_util.tree_map(
            lambda a: a.astype(F32), v)) for k, v in p.items()}
        X, _, first = around(X, p["hc_attn"], attention, p)
        X, g, second = around(X, p["hc_mlp"], mlp, p)
        return X, g, (first, second)

    return jax.jit(fn)


@functools.lru_cache(maxsize=None)
def _served_path_fn(cfg, T: int):
    """The served residual path on one sublayer's rounded inputs,
    jitted: the two kernels called as models/xing4.py calls them ->
    the largest coefficient difference, the largest mix difference over
    the largest entry of its mix."""
    import jax
    import jax.numpy as jnp

    pre, post, take = (_served(SERVED_PRE), _served(SERVED_POST),
                       _served(SERVED_COEFFICIENTS))
    n = cfg.hc_mult

    def fn(hc, X, y, H_pre, H_post, H_res, h, out):
        got_h, coef = pre(X, hc["phi"], hc["a"], hc["b"], n=n,
                          iters=cfg.hc_sinkhorn_iters, eps=cfg.hc_eps,
                          norm_eps=cfg.rms_norm_eps,
                          clamp=(cfg.mhc_h_res_clamp_min,
                                 cfg.mhc_h_res_clamp_max))
        got = take(coef, n)
        got_out = post(X, y, coef, n=n)
        over = lambda a, b: jnp.abs(a.astype(jnp.float32) - b).max()
        coef_diff = jnp.stack([over(g, w) for g, w in zip(
            got, (H_pre, H_post, H_res))]).max()
        mix_diff = jnp.maximum(over(got_h, h) / jnp.abs(h).max(),
                               over(got_out, out) / jnp.abs(out).max())
        return coef_diff, mix_diff

    return jax.jit(fn)


def forward(params, cfg, prompt: list, got: list, lower: bool = False,
            fault=None, pad_to: int = 0):
    """One full forward pass over prompt + got. Returns (logits
    [len(got), vocab] float32 at the positions that decide ``got``,
    teacher-forced; {routed layer: its router's input [len(prompt) +
    len(got), hidden] float32}; (the served residual path's largest
    coefficient difference from this reference over every sublayer,
    its largest mix difference))."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    if fault == "one_expert_fewer":
        cfg = dataclasses.replace(
            cfg, num_experts_per_tok=cfg.num_experts_per_tok - 1)
    seq = list(prompt) + list(got)
    T = max(-(-len(seq) // PAD_TO) * PAD_TO, pad_to)
    buf = np.zeros((T,), np.int32)
    buf[:len(seq)] = seq
    router_inputs, coef_diff, mix_diff = {}, 0.0, 0.0
    path = _served_path_fn(cfg, T)
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(buf)].astype(jnp.float32)
        X = jnp.broadcast_to(x[:, None, :], (T, cfg.hc_mult, x.shape[1]))
        for l, p in enumerate(params["layers"]):
            X, g, sublayers = _layer_fn(cfg, "router" in p, T, lower,
                                        fault)(X, p)
            if g is not None:
                router_inputs[l] = g[:len(seq)]
            for name, served in zip(("hc_attn", "hc_mlp"), sublayers):
                c, m = path(p[name], *served)
                coef_diff = max(coef_diff, float(c))
                mix_diff = max(mix_diff, float(m))
            del sublayers
        rows = X.sum(1)[len(prompt) - 1:len(seq) - 1]
        logits = np.asarray(_head_fn(cfg)(rows, params["norm_f"],
                                          params["head"]), np.float32)
    return logits, router_inputs, (coef_diff, mix_diff)


def _read(params, cfg, served_router, answers, lower=False, fault=None):
    tokens, lines = [], []
    same = total = 0
    weight = coef = mix = 0.0
    pad_to = max((-(-(len(p) + len(g)) // PAD_TO) * PAD_TO
                  for _, p, g in answers), default=0)
    for what, prompt, got in answers:
        logits, router_inputs, (c, x) = forward(
            params, cfg, prompt, got, lower, fault, pad_to)
        coef, mix = max(coef, c), max(mix, x)
        m = margins(logits, got)
        s, t, w = router_agreement(params, cfg, router_inputs, served_router,
                                   lower, fault)
        tokens += m
        same, total, weight = same + s, total + t, max(weight, w)
        lines.append(f"{what}: {sum(v == 0.0 for v in m)}/{len(m)} tokens "
                     f"equal, worst margin {max(m, default=0.0):.4f}, mean "
                     f"{sum(m) / max(len(m), 1):.5f}; router alike on "
                     f"{s}/{t} tokens, weights within {w:.6f}; the served "
                     f"residual path's coefficients within {c:.2e}, its "
                     f"mixes within {x:.2e}")
    n = len(tokens)
    return {"n": n, "exact": sum(v == 0.0 for v in tokens),
            "worst": max(tokens, default=0.0),
            "mean": sum(tokens) / max(n, 1),
            "router_same": same, "router_total": total,
            "router_weight_diff": weight, "mhc_diff": coef,
            "mhc_mix_diff": mix, "lines": lines}


def compare(params, cfg, served_router, answers: list,
            lower: bool = False) -> dict:
    """Every ``(what, prompt, got)`` of ``answers`` through the
    reference: the pooled readings ``token_checks`` and
    ``router_checks`` judge, and a line an answer for the log. With
    ``BENCH_XING_CONTROLS`` set, the controls' readings follow as
    further lines (module docstring)."""
    read = _read(params, cfg, served_router, answers, lower)
    if os.environ.get("BENCH_XING_CONTROLS") and not lower:
        controls = [("one precision lower", True, None)] + [
            (what, False, fault) for fault, what in FAULTS.items()]
        for name, low, fault in controls:
            r = _read(params, cfg, served_router, answers, low, fault)
            read["lines"].append(
                f"control, {name}: {r['exact']}/{r['n']} equal, mean "
                f"{r['mean']:.5f}, worst {r['worst']:.4f}; router "
                f"{r['router_same']}/{r['router_total']}, weights "
                f"{r['router_weight_diff']:.6f}; coefficients "
                f"{r['mhc_diff']:.2e}, mixes {r['mhc_mix_diff']:.2e}")
            for ok, text in token_checks(r) + router_checks(r):
                read["lines"].append(
                    f"control, {name}: {'PASSES' if ok else 'fails'}: "
                    f"{text}")
    return read


def token_checks(r: dict) -> list:
    n = r["n"]
    return [
        (n > 0 and r["exact"] >= MIN_EXACT_SHARE * n,
         f"{r['exact']}/{n} compared tokens are the float32 reference's "
         f"argmax (at least {MIN_EXACT_SHARE:.0%})"),
        (n > 0 and r["mean"] < MAX_MEAN_MARGIN,
         f"mean reference margin of the compared tokens {r['mean']:.5f} "
         f"(limit {MAX_MEAN_MARGIN})"),
        (n > 0 and r["worst"] < MAX_MARGIN,
         f"worst reference margin of a compared token {r['worst']:.4f} "
         f"(limit {MAX_MARGIN})"),
    ]


def router_checks(r: dict) -> list:
    same, total = r["router_same"], r["router_total"]
    return [
        (total > 0 and same >= MIN_ROUTER_AGREEMENT * total,
         f"the served router and the reference's pick the same experts "
         f"on {same}/{total} tokens of the compared sequences' router "
         f"inputs (at least {MIN_ROUTER_AGREEMENT:.1%})"),
        (total > 0 and r["router_weight_diff"] < MAX_WEIGHT_DIFF,
         f"their weights differ by at most {r['router_weight_diff']:.6f} "
         f"on those tokens (limit {MAX_WEIGHT_DIFF})"),
        (total > 0 and r["mhc_diff"] < MAX_MHC_DIFF,
         f"the served residual path's coefficients (mhc_pre) differ from "
         f"the reference's by at most {r['mhc_diff']:.2e} on the compared "
         f"sequences' streams (limit {MAX_MHC_DIFF})"),
        (total > 0 and r["mhc_mix_diff"] < MAX_MHC_MIX_DIFF,
         f"its mixes (mhc_pre's h, mhc_post's X') differ from the "
         f"reference's by at most {r['mhc_mix_diff']:.2e} of the largest "
         f"entry (limit {MAX_MHC_MIX_DIFF})"),
    ]
