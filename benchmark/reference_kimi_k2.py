"""The plain reference that decides ``correct`` for the Kimi-K2
configurations, and its limits: the benchmark's own copy of the layer
equations (``ray_tpu/models/kimi_k2_ref.py`` is the repository's, which
its tests use), kept here so that no later PR can move what a cell is
judged by. A configuration names this module under ``reference``; the
interface is the one ``drivers/serve_closed_loop_ref.py`` calls
(``served_router_of``, ``compare``, ``token_checks``,
``router_checks``).

What it computes (``forward``): the model's full forward pass over
prompt + answer in the NON-absorbed form (every head's keys and values
are made from the latent vectors; the served decode path never makes
them), one sequence, no cache, no kernel, no batching, no chunks, in
float32 at matmul precision ``highest``, on the SERVED parameters
(bfloat16, made from ``--seed`` by the model's own ``init``) raised to
float32 a layer at a time, and in blocks of ``ROWS`` queries (attention)
and rows (the MLPs), so that a 17,000-token sequence fits beside them.
It is given the same share the served model holds: it routes over all
``n_routed_experts``, loops over the ``experts_held`` experts from
``first_expert``, every one on every token, kept by the router's
weight, and adds the shared expert. Sequences are padded to one length
so that every comparison shares one compiled program a layer (the
causal mask keeps the padding out). With ``lower=True`` the router's
scores and their sigmoid are computed in the configuration's dtype
(bfloat16), the nearest precision below the float32 its file states for
them: the control, which has to come out NOT correct.

The equations (x [T, hidden]; H heads; no biases):
  h = RMSNorm(x); c_q = RMSNorm(h W_dq); q = c_q W_uq [T, H, nope+rope]
  [c_kv | k_r] = h W_dkv; c_kv <- RMSNorm(c_kv); k_rope = RoPE(k_r), one
    a token for all heads; q_rope <- RoPE(q_rope): pairs (i, i + rope/2)
    turn by position x inv_freq_i, YaRN frequencies, cos and sin scaled
    by mscale(factor, mscale) / mscale(factor, mscale_all_dim)
  [k_nope | v] = c_kv W_ukv [T, H, nope + v]
  causal softmax((q_nope . k_nope + q_rope . k_rope) * (nope+rope)^-0.5
    * mscale(factor, mscale_all_dim)^2) v;  x += concat_h(o_h) W_o
  h2 = RMSNorm(x); dense: x += (silu(h2 W_gate) * h2 W_up) W_down
  routed: s = sigmoid(h2 W_r); the k largest of s + b; w = scale *
    s_top / sum(s_top); x += sum_{e held} w_e Expert_e(h2) + Shared(h2)
  logits = RMSNorm(x) W_head

What is compared, in two parts as for ``reference_laguna`` and for its
reasons (the tokens of a top-k routed model in bfloat16 cannot all equal
a float32 reference's; the router, as a function on identical inputs,
can):

1. TOKENS, ``token_checks``: every served token of the compared
   answers, teacher-forced; a token's margin is how far the reference
   prefers its own argmax to the served token, 0 where they agree.
   Pooled over a run's compared tokens (the reference request's 64 and
   four documents' longest answers). These limits have to fail when
   the rope part is left out of the scores and when the softmax scale
   lacks ``mscale^2`` (planted in the reference, ``FAULTS``).
2. THE ROUTER, ``router_checks``: the function the served programs
   route with (``served_router``, ``ray_tpu.ops.moe:route_sigmoid``)
   against this reference's router ON IDENTICAL INPUTS, the reference's
   own router inputs of the compared sequences rounded to the served
   dtype: the share of tokens whose experts the two pick alike, and the
   largest difference between their weights on those tokens. These
   limits have to fail with bfloat16 router scores (``lower``), with
   the experts chosen by ``s`` alone, and with weights not
   renormalised.

With ``BENCH_KIMI_CONTROLS`` set in the environment ``compare`` reads
the same answers again one precision lower and with each fault of
``FAULTS`` planted in the reference, and logs what the limits say of
each (as ``serve_closed_loop_ref`` does under ``BENCH_LAGUNA_CONTROLS``
for the configuration it was written with; its own controls name that
configuration's fields, and no existing file of the benchmark may
change). They decide nothing.
"""

from __future__ import annotations

import functools
import math
import os

# What the two references share, from the accepted one (no file of the
# benchmark may change, so it cannot move under this one): the YaRN
# blend, the final norm and head, a token's margin, and the lookup of
# the served router the configuration's file names.
from benchmark import reference_laguna
from benchmark.reference_laguna import (_head_fn, margins,  # noqa: F401
                                        served_router_of)

# Limits, each between two readings (my chip runs, PR 34: PERF.md
# section 6 has every reading). "Sound" is the served path as
# committed: 13 runs of the cell on 12 seeds (calls 1-3), 388-435
# compared tokens each. The faults were planted in the reference and
# read against the served tokens of the first of those runs (call 1,
# ``BENCH_KIMI_CONTROLS``), 408 tokens.
#
# Pooled over a run's compared tokens:
#   share of tokens equal: sound 0.942-0.976 (median 0.969); the rope
#   part left out 0.015, the scale without mscale^2 0.049, routed
#   weights not renormalised 0.377. (The 8 experts chosen by s alone
#   read 0.868 and one precision lower 0.963: those two are the
#   router's limits' to fail, below.)
MIN_EXACT_SHARE = 0.88
#   mean margin: sound 0.0005-0.0036 (median 0.0012); experts chosen by
#   s alone 0.0237, weights not renormalised 1.19, no mscale^2 2.52, no
#   rope part 3.81.
MAX_MEAN_MARGIN = 0.012
# A single token: sound 0.05-0.37 in 12 runs and 1.12 in one (a token
# whose experts the served bfloat16 activations swapped); weights not
# renormalised 6.26, no mscale^2 6.39, no rope part 10.2.
MAX_MARGIN = 3.0
# Share of tokens whose 8 experts the served router and the reference's
# pick alike, on identical inputs: float32 scores every token of every
# run (3,570,392 of 3,570,392); scores and sigmoid in bfloat16 0.695
# (190,728 of 274,528), the 8 chosen by s alone 0.007.
MIN_ROUTER_AGREEMENT = 0.99
# Largest difference between the served router's weights and the
# reference's on a token whose experts they pick alike (a weight is
# ~2.827 / 8 = 0.35): sound 0.000000 in every run (float32 on both
# sides); weights not renormalised 2.47. (One precision lower reads
# 0.0012 and passes this one; it fails the agreement.)
MAX_WEIGHT_DIFF = 0.05
PAD_TO = 1024           # sequences are padded to a multiple of this
ROWS = 1024             # queries (and MLP rows) computed at a time

# Departures planted in the reference, each of which has to fail a
# limit: name -> what it changes.
FAULTS = {
    "no_rope_term": "the rope part left out of the scores",
    "scale_without_mscale": "the softmax scale without mscale^2",
    "chosen_by_score_alone": "the 8 experts chosen by s alone, not s + b",
    "weights_not_renormalised": "routed weights not renormalised",
}


def mscale(s: float, m: float) -> float:
    return 1.0 if s <= 1 else 0.1 * m * math.log(s) + 1.0


def _inv_freq(cfg):
    """(inverse frequencies of the rope dims, cos/sin scale): the YaRN
    blend as ``reference_laguna._inv_freq`` computes it, on this
    configuration's numbers."""
    r = dict(cfg.rope_scaling)
    inv, _, cs = reference_laguna._inv_freq({
        "rope_type": "yarn", "rope_theta": cfg.rope_theta,
        "factor": r["factor"], "beta_fast": r["beta_fast"],
        "beta_slow": r["beta_slow"],
        "original_max_position_embeddings":
            r["original_max_position_embeddings"],
        "attention_factor": mscale(r["factor"], r["mscale"])
        / mscale(r["factor"], r["mscale_all_dim"])}, cfg.qk_rope_head_dim)
    return inv, cs


def _route(h2, router, bias, cfg, scores_in, fault=None):
    """The reference's router: (experts [T, k], weights [T, k] float32)
    from scores and a sigmoid held in ``scores_in``."""
    import jax
    import jax.numpy as jnp

    s = jax.nn.sigmoid(h2.astype(scores_in) @ router.astype(scores_in))
    chosen_by = s if fault == "chosen_by_score_alone" \
        else s + bias.astype(scores_in)
    _, idx = jax.lax.top_k(chosen_by, cfg.num_experts_per_tok)
    top = jnp.take_along_axis(s, idx, axis=-1).astype(jnp.float32)
    if fault == "weights_not_renormalised":
        return idx, cfg.routed_scaling_factor * top
    return idx, cfg.routed_scaling_factor * top / top.sum(-1, keepdims=True)


@functools.lru_cache(maxsize=None)
def _layer_fn(cfg, routed: bool, T: int, lower: bool, fault):
    """One layer on a whole padded sequence [T, hidden], jitted: (x
    out, the router's input or None for a dense layer)."""
    import jax
    import jax.numpy as jnp

    F32 = jnp.float32
    scores_in = jnp.dtype(cfg.dtype) if lower else jnp.dtype(F32)
    nope, rope, rkv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                       cfg.kv_lora_rank)
    eps = cfg.rms_norm_eps
    r = dict(cfg.rope_scaling)
    scale = (nope + rope) ** -0.5
    if fault != "scale_without_mscale":
        scale *= mscale(r["factor"], r["mscale_all_dim"]) ** 2
    inv, cs = _inv_freq(cfg)
    rows = min(ROWS, T)

    def norm(x, w):
        return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * w

    def rotary(x):                                  # [heads, T, rope]
        ang = jnp.arange(T, dtype=F32)[:, None] * jnp.asarray(inv, F32)[None]
        cos, sin = jnp.cos(ang) * cs, jnp.sin(ang) * cs
        a, b = x[..., :rope // 2], x[..., rope // 2:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

    def by_rows(fn, x):
        """``fn`` on ``rows`` rows of x [T, ...] at a time."""
        out = jax.lax.map(fn, x.reshape(T // rows, rows, *x.shape[1:]))
        return out.reshape(T, *out.shape[2:])

    def swiglu(h, w_gu, w_down):
        f = w_gu.shape[-1] // 2
        return by_rows(lambda b: (jax.nn.silu(b @ w_gu[:, :f])
                                  * (b @ w_gu[:, f:])) @ w_down, h)

    def fn(x, p):
        p = jax.tree_util.tree_map(lambda a: a.astype(F32), p)
        h = norm(x, p["ln1"])
        c_q = norm(h @ p["w_dq"], p["q_norm"])
        # Heads lead from here on, so that a head's rows lie together
        # and no transposed copy of the 17,000-token q, k or v is made.
        q = jnp.einsum("tc,chd->htd", c_q, p["w_uq"])
        ckv = h @ p["w_dkv"]
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
        x = x + jnp.einsum("htd,hdm->tm", o, p["w_o"])
        h2 = norm(x, p["ln2"])
        if not routed:
            return x + swiglu(h2, p["w_gu"], p["w_down"]), None
        idx, w = _route(h2, p["router"], p["router_bias"], cfg, scores_in,
                        fault)
        by_expert = jnp.zeros((T, cfg.n_routed_experts), F32).at[
            jnp.arange(T)[:, None], idx].set(w)
        mine = by_expert[:, cfg.first_expert:
                         cfg.first_expert + cfg.experts_held]

        def add(acc, xs):
            w1, w2, we = xs
            return acc + we[:, None] * swiglu(h2, w1, w2), None

        routed_out, _ = jax.lax.scan(add, jnp.zeros_like(h2),
                                     (p["w1"], p["w2"], mine.T))
        return x + routed_out + swiglu(h2, p["s_gu"], p["s_down"]), h2

    return jax.jit(fn)


def forward(params, cfg, prompt: list, got: list, lower: bool = False,
            fault=None):
    """One full forward pass over prompt + got. Returns (logits
    [len(got), vocab] float32 at the positions that decide ``got``,
    teacher-forced; {routed layer: its router's input [len(prompt) +
    len(got), hidden] float32})."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    seq = list(prompt) + list(got)
    T = -(-len(seq) // PAD_TO) * PAD_TO
    buf = np.zeros((T,), np.int32)
    buf[:len(seq)] = seq
    router_inputs = {}
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(buf)].astype(jnp.float32)
        for l, p in enumerate(params["layers"]):
            x, h2 = _layer_fn(cfg, "router" in p, T, lower, fault)(x, p)
            if h2 is not None:
                router_inputs[l] = h2[:len(seq)]
        rows = x[len(prompt) - 1:len(seq) - 1]
        logits = np.asarray(_head_fn(cfg)(rows, params["norm_f"],
                                          params["head"]), np.float32)
    return logits, router_inputs


def router_agreement(params, cfg, router_inputs: dict, served_router,
                     lower: bool = False, fault=None) -> tuple:
    """(tokens whose experts ``served_router`` and the reference's
    router pick alike, tokens compared, the largest difference between
    the two routers' weights on a token whose experts they pick alike)
    over every routed layer, both on the reference's router inputs
    rounded to the served dtype. ``served_router(x, w, bias, k,
    scale)`` returns (_, experts [T, k], weights [T, k]), the signature
    of ``ray_tpu.ops.moe.route_sigmoid``."""
    import jax
    import jax.numpy as jnp

    scores_in = jnp.dtype(cfg.dtype) if lower else jnp.dtype(jnp.float32)
    same = total = 0
    worst = 0.0
    for l, h2 in router_inputs.items():
        p = params["layers"][l]
        x = h2.astype(cfg.dtype)
        _, served, served_w = served_router(
            x, p["router"], p["router_bias"], cfg.num_experts_per_tok,
            cfg.routed_scaling_factor)
        with jax.default_matmul_precision("highest"):
            mine, mine_w = _route(x, p["router"], p["router_bias"], cfg,
                                  scores_in, fault)
        # Both sides sorted by expert, so that weights pair up.
        so, mo = jnp.argsort(served, -1), jnp.argsort(mine, -1)
        alike = (jnp.take_along_axis(served, so, -1)
                 == jnp.take_along_axis(mine, mo, -1)).all(-1)
        diff = jnp.abs(jnp.take_along_axis(served_w, so, -1)
                       - jnp.take_along_axis(mine_w, mo, -1)).max(-1)
        same += int(alike.sum())
        total += x.shape[0]
        worst = max(worst, float(jnp.where(alike, diff, 0.0).max()))
    return same, total, worst


def _read(params, cfg, served_router, answers, lower=False, fault=None):
    tokens, lines = [], []
    same = total = 0
    weight = 0.0
    for what, prompt, got in answers:
        logits, router_inputs = forward(params, cfg, prompt, got, lower,
                                        fault)
        m = margins(logits, got)
        s, t, w = router_agreement(params, cfg, router_inputs, served_router,
                                   lower, fault)
        tokens += m
        same, total, weight = same + s, total + t, max(weight, w)
        lines.append(f"{what}: {sum(x == 0.0 for x in m)}/{len(m)} tokens "
                     f"equal, worst margin {max(m, default=0.0):.4f}, mean "
                     f"{sum(m) / max(len(m), 1):.5f}; router alike on "
                     f"{s}/{t} tokens, weights within {w:.6f}")
    n = len(tokens)
    return {"n": n, "exact": sum(x == 0.0 for x in tokens),
            "worst": max(tokens, default=0.0),
            "mean": sum(tokens) / max(n, 1),
            "router_same": same, "router_total": total,
            "router_weight_diff": weight, "lines": lines}


def compare(params, cfg, served_router, answers: list,
            lower: bool = False) -> dict:
    """Every ``(what, prompt, got)`` of ``answers`` through the
    reference: the pooled readings ``token_checks`` and
    ``router_checks`` judge, and a line an answer for the log. With
    ``BENCH_KIMI_CONTROLS`` set, the controls' readings follow as
    further lines (module docstring)."""
    read = _read(params, cfg, served_router, answers, lower)
    if os.environ.get("BENCH_KIMI_CONTROLS") and not lower:
        controls = [("one precision lower", True, None)] + [
            (what, False, fault) for fault, what in FAULTS.items()]
        for name, low, fault in controls:
            r = _read(params, cfg, served_router, answers, low, fault)
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
    ]
