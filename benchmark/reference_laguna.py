"""The plain reference that decides ``correct`` for the Laguna
configurations, and its limits: the benchmark's own copy of the layer
equations (``ray_tpu/models/laguna_ref.py`` is the repository's, which
its tests use), kept here so that no later PR can move what a cell is
judged by. A file of its own because ``benchmark/reference.py`` is
wired to ``gpt.forward`` and no existing file of the benchmark may
change; a configuration names this module under ``reference``.

What it computes (``forward``): the model's full forward pass over
prompt + answer, one sequence, no cache, no kernel, no batching, no
chunks, in float32 at matmul precision ``highest``, on the SERVED
parameters (bfloat16, made from ``--seed`` by the model's own ``init``)
raised to float32 a layer at a time so that it fits beside them. Routed
experts are a loop: every expert on every token, kept by the router's
weight. The sequence is padded to one length so that every comparison
shares one compiled program a layer (the causal mask keeps the padding
out). With ``lower=True`` the router's scores and their softmax are
computed in the configuration's dtype (bfloat16), the nearest precision
below the float32 its file states for them: the control, which has to
come out NOT correct.

The equations (x [T, hidden]; layer l of kind ``layer_types[l]`` with
``H_l`` query heads over ``kv`` KV heads of ``d``; no biases):
  h = RMSNorm(x); q = h W_q; k = h W_k; v = h W_v; g = sigmoid(h W_g)
  rotary on q, k: pairs (i, i + rot/2) of the first rot dims turn by
    position x inv_freq_i; full layers rot = d/2 with YaRN frequencies
    and cos, sin scaled by attention_factor, sliding layers rot = d.
  head h reads KV head h // (H_l / kv); causal softmax(q k^T / sqrt d) v,
    on a sliding layer only keys less than sliding_window behind;
  x += concat_h(g_h o_h) W_o;  h2 = RMSNorm(x)
  dense: x += (silu(h2 W_gate) * h2 W_up) W_down
  sparse: p = softmax(h2 W_r); top-k; w = scale * p_top / sum(p_top);
    x += sum_e w_e Expert_e(h2) + Shared(h2)
  logits = RMSNorm(x) W_head

What is compared, and why in two parts (my chip runs, PR 32; PERF.md
section 6 has every reading).

1. TOKENS, ``token_checks``: every served token of the compared
   answers, teacher-forced. A token's margin is how far the reference
   prefers its own argmax to the served token, 0 where they agree. The
   router takes the 8 largest of 256 softmax scores and the 8th and 9th
   lie ~0.03 apart in the logarithm; the served bfloat16 activations
   move a score by a few thousandths, so in about one token-layer in
   ten the served path takes the reference's 9th expert, that token's
   keys and values differ by a whole expert's output, and every later
   token attends them. A sound run therefore reads 90-94% of its tokens
   equal and margins up to 0.6, whatever the reference's precision: a
   plain pass in bfloat16 with float32 router scores reads the same as
   this float32 one (XLA keeps excess precision where it fuses, so no
   two programs round alike), and so does one with bfloat16 router
   scores. What the tokens CAN tell is a path that computes something
   else, if they are pooled over a run's ~400 compared tokens: a
   dropped expert, weights that do not sum to the scale (readings
   beside the limits below, and what they cannot tell).
2. THE ROUTER, ``router_checks``: the function the served programs
   route with (named by the configuration's file, ``served_router``)
   against this reference's router ON IDENTICAL INPUTS: the reference's
   own router inputs of the compared sequences (~36,000 tokens x 4
   layers a run), rounded to the served dtype. Same inputs, so no
   rounding upstream can swap an expert, and a float32 router picks the
   reference's 8 experts on every token; scores or softmax in bfloat16
   pick another set on 4% of them. This is the limit that the lower
   precision fails. The experts the timed programs chose are not
   observable from outside them (a step hands back token ids and two
   counters), so the router is judged as a function, on the timed
   batch's inputs.
"""

from __future__ import annotations

import functools
import importlib
import math

# Limits, each between two readings (my chip runs, PR 32: PERF.md
# section 6). "Sound" is the served path as committed: 26 runs of the
# cell, ~350-660 compared tokens each (the mean margin was logged in
# the last 12). The faults were planted in the
# reference and read against the same served tokens in six of those
# runs (call 15), and in the served path through ``LLMEngine`` alone
# against this reference (call 14).
#
# Pooled over a run's compared tokens:
#   share of tokens equal: sound 0.884-0.941 (median 0.918); a token's
#   8th expert dropped 0.69-0.73, routed weights that sum to 1 and not
#   2.5 0.44-0.50.
MIN_EXACT_SHARE = 0.80
#   mean margin: sound 0.0032-0.0083; 8th expert dropped 0.032-0.037,
#   weights that sum to 1 0.139-0.159.
MAX_MEAN_MARGIN = 0.018
# A single token: sound 0.61 at most (over 11,700 tokens); weights that
# sum to 1 0.85-1.36 (median of six runs 1.09).
MAX_MARGIN = 1.0
# NOT told apart by any of the three: a window one block (16 of 512
# positions) short reads 0.835-0.869 equal and a mean of 0.0095-0.014,
# beside sound runs' 0.884 and 0.0083: the far edge of a window carries
# a five-hundredth of a random-weight layer's attention. The CPU tests
# hold the window's edge exactly, in float32 (tests/test_laguna.py).
#
# Share of tokens whose 8 experts the served router and the reference's
# pick alike, on identical inputs: float32 scores 865,660 of 865,660
# (six runs); bfloat16 scores and softmax 0.960-0.961.
MIN_ROUTER_AGREEMENT = 0.999
PAD_TO = 1024           # sequences are padded to a multiple of this


def _inv_freq(rope: dict, head_dim: int):
    import numpy as np

    rot = int(head_dim * rope.get("partial_rotary_factor", 1.0))
    base = float(rope["rope_theta"])
    pos = base ** (np.arange(0, rot, 2, dtype=np.float64) / rot)
    if rope.get("rope_type", "default") == "default":
        return 1.0 / pos, rot, 1.0
    orig = rope["original_max_position_embeddings"]

    def dim_of(turns):
        return rot * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(dim_of(rope["beta_fast"])), 0)
    high = min(math.ceil(dim_of(rope["beta_slow"])), rot - 1)
    high = high + 0.001 if low == high else high
    ramp = np.clip((np.arange(rot // 2) - low) / (high - low), 0.0, 1.0)
    inv = ramp / (rope["factor"] * pos) + (1.0 - ramp) / pos
    return inv, rot, float(rope["attention_factor"])


@functools.lru_cache(maxsize=None)
def _layer_fn(cfg, l: int, T: int, lower: bool):
    """Layer ``l`` on a whole padded sequence [T, hidden], jitted:
    (x out, the router's input or None for a dense layer)."""
    import jax
    import jax.numpy as jnp

    F32 = jnp.float32
    scores_in = jnp.dtype(cfg.dtype) if lower else jnp.dtype(F32)
    d, eps = cfg.head_dim, cfg.rms_norm_eps
    sliding = cfg.layer_types[l] == "sliding_attention"
    inv, rot, scale = _inv_freq(
        dict(cfg.rope_sliding if sliding else cfg.rope_full), d)

    def norm(x, w):
        return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * w

    def rotary(x):
        ang = jnp.arange(T, dtype=F32)[:, None] * jnp.asarray(inv, F32)[None]
        cos = jnp.cos(ang)[:, None] * scale
        sin = jnp.sin(ang)[:, None] * scale
        a, b = x[..., :rot // 2], x[..., rot // 2:rot]
        return jnp.concatenate(
            [a * cos - b * sin, b * cos + a * sin, x[..., rot:]], axis=-1)

    def swiglu(h, w_gu, w_down):
        f = w_gu.shape[-1] // 2
        return (jax.nn.silu(h @ w_gu[:, :f]) * (h @ w_gu[:, f:])) @ w_down

    def fn(x, p):
        p = jax.tree_util.tree_map(lambda a: a.astype(F32), p)
        h = norm(x, p["ln1"])
        q = rotary(jnp.einsum("tm,mhd->thd", h, p["wq"]))
        k = rotary(jnp.einsum("tm,mhd->thd", h, p["wk"]))
        v = jnp.einsum("tm,mhd->thd", h, p["wv"])
        g = jax.nn.sigmoid(h @ p["wg"])
        rep = q.shape[1] // k.shape[1]
        i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
        mask = j <= i
        if sliding:
            mask = mask & (i - j < cfg.sliding_window)

        def head(args):
            qh, kh, vh = args
            s = jnp.where(mask, (qh @ kh.T) / math.sqrt(d), -jnp.inf)
            return jax.nn.softmax(s, axis=-1) @ vh

        o = jax.lax.map(head, (
            q.transpose(1, 0, 2),
            jnp.repeat(k, rep, axis=1).transpose(1, 0, 2),
            jnp.repeat(v, rep, axis=1).transpose(1, 0, 2)))
        o = o.transpose(1, 0, 2) * g[..., None]
        x = x + jnp.einsum("thd,hdm->tm", o, p["wo"])
        h2 = norm(x, p["ln2"])
        if cfg.mlp_layer_types[l] == "dense":
            return x + swiglu(h2, p["w_gu"], p["w_down"]), None
        idx, w = _route(h2, p["router"], cfg, scores_in)
        by_expert = jnp.zeros((T, cfg.num_experts), F32).at[
            jnp.arange(T)[:, None], idx].set(w)

        def add(acc, xs):
            w1, w2, we = xs
            return acc + we[:, None] * swiglu(h2, w1, w2), None

        routed, _ = jax.lax.scan(add, jnp.zeros_like(h2),
                                 (p["w1"], p["w2"], by_expert.T))
        return x + routed + swiglu(h2, p["s_gu"], p["s_down"]), h2

    return jax.jit(fn)


def _route(h2, router, cfg, scores_in):
    """The reference's router: (experts [T, k], weights [T, k] float32)
    from scores and a softmax held in ``scores_in``."""
    import jax
    import jax.numpy as jnp

    probs = jax.nn.softmax(
        h2.astype(scores_in) @ router.astype(scores_in), axis=-1)
    top, idx = jax.lax.top_k(probs, cfg.num_experts_per_tok)
    top = top.astype(jnp.float32)
    return idx, (cfg.moe_routed_scaling_factor * top
                 / top.sum(-1, keepdims=True))


@functools.lru_cache(maxsize=None)
def _head_fn(cfg):
    import jax
    import jax.numpy as jnp

    def fn(x, scale, w):
        x = x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True)
                              + cfg.rms_norm_eps) * scale.astype(jnp.float32)
        return x @ w.astype(jnp.float32)

    return jax.jit(fn)


def forward(params, cfg, prompt: list, got: list, lower: bool = False):
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
            x, h2 = _layer_fn(cfg, l, T, lower)(x, p)
            if h2 is not None:
                router_inputs[l] = h2[:len(seq)]
        rows = x[len(prompt) - 1:len(seq) - 1]
        logits = np.asarray(_head_fn(cfg)(rows, params["norm_f"],
                                          params["head"]), np.float32)
    return logits, router_inputs


def margins(logits, got: list) -> list:
    """A margin a token of ``got``: how far the reference prefers its
    own argmax to the served token, 0.0 where they are the same."""
    return [0.0 if int(row.argmax()) == tok else float(row.max() - row[tok])
            for row, tok in zip(logits, got)]


def router_agreement(params, cfg, router_inputs: dict, served_router,
                     lower: bool = False) -> tuple:
    """(tokens whose experts ``served_router`` and the reference's
    router pick alike, tokens compared) over every routed layer, both
    on the reference's router inputs rounded to the served dtype.
    ``served_router(x, w, k, scale)`` returns (_, experts [T, k], _),
    the signature of ``ray_tpu.ops.moe.route``."""
    import jax
    import jax.numpy as jnp

    scores_in = jnp.dtype(cfg.dtype) if lower else jnp.dtype(jnp.float32)
    same = total = 0
    for l, h2 in router_inputs.items():
        w = params["layers"][l]["router"]
        x = h2.astype(cfg.dtype)
        _, served, _ = served_router(x, w, cfg.num_experts_per_tok,
                                     cfg.moe_routed_scaling_factor)
        with jax.default_matmul_precision("highest"):
            mine, _ = _route(x, w, cfg, scores_in)
        same += int((jnp.sort(served, -1) == jnp.sort(mine, -1))
                    .all(-1).sum())
        total += x.shape[0]
    return same, total


def compare(params, cfg, served_router, answers: list,
            lower: bool = False) -> dict:
    """Every ``(what, prompt, got)`` of ``answers`` through the
    reference: the pooled readings ``token_checks`` and
    ``router_checks`` judge, and a line an answer for the log."""
    tokens, lines = [], []
    same = total = 0
    for what, prompt, got in answers:
        logits, router_inputs = forward(params, cfg, prompt, got, lower)
        m = margins(logits, got)
        s, t = router_agreement(params, cfg, router_inputs, served_router,
                                lower)
        tokens += m
        same, total = same + s, total + t
        lines.append(f"{what}: {sum(x == 0.0 for x in m)}/{len(m)} tokens "
                     f"equal, worst margin {max(m, default=0.0):.4f}, mean "
                     f"{sum(m) / max(len(m), 1):.5f}; router alike on "
                     f"{s}/{t} tokens")
    n = len(tokens)
    return {"n": n, "exact": sum(x == 0.0 for x in tokens),
            "worst": max(tokens, default=0.0),
            "mean": sum(tokens) / max(n, 1),
            "router_same": same, "router_total": total, "lines": lines}


def served_router_of(config: dict):
    """The function the configuration's file names as the served
    programs' router (``reference.served_router``: "module:function")."""
    module, name = config["reference"]["served_router"].split(":")
    return getattr(importlib.import_module(module), name)


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
    return [(total > 0 and same >= MIN_ROUTER_AGREEMENT * total,
             f"the served router and the reference's pick the same experts "
             f"on {same}/{total} tokens of the compared sequences' router "
             f"inputs (at least {MIN_ROUTER_AGREEMENT:.1%})")]
