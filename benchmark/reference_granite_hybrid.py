"""The plain reference that decides ``correct`` for the Granite 4.0-H
configurations, and its limits: the benchmark's own copy of the layer
equations (``ray_tpu/models/granite_hybrid_ref.py`` is the
repository's, which its tests use), kept here so that no later PR can
move what a cell is judged by. A configuration names this module under
``reference``; the interface is ``reference_nemotron_h``'s, which
``drivers/serve_closed_loop_ref.py`` calls (``served_router_of``,
``compare``, ``token_checks``, ``router_checks``).

What it computes (``forward``): the model's full forward pass over
prompt + answer, one sequence, no cache, no kernel, no batching, no
chunks, in float32 at matmul precision ``highest``, on the SERVED
parameters (bfloat16, made from ``--seed`` by the model's own ``init``)
raised to float32 a layer at a time. The state-space recurrence is a
``lax.scan`` over TOKENS from a zero state; the convolution is a sum
over shifted copies of the whole sequence; attention is a dense masked
softmax in blocks of ``ROWS`` queries. It is given the same share the
served model holds: it routes over all ``num_local_experts``, loops
over the ``experts_held`` experts from ``first_expert``, every one on
every token, kept by the router's weight, and adds the shared MLP; its
logits are over the held rows of the tied matrix. Sequences are padded
to a multiple of ``PAD_TO`` so that comparisons share compiled programs
(causal: the padding reaches nothing before it).

The equations (x [T, 4096]; RMSNorm eps 1e-5; no bias but the
convolution's; layer l's mixer is ``layer_types[l]``):
  x0 = 12 Embed[tokens];  h = x + 0.22 Mixer(RMSNorm_a(x));
  x' = h + 0.22 (Experts(u) + Shared(u)), u = RMSNorm_b(h);
  logits = (RMSNorm_f(x) Embed^T) / 16
  ``attention``  q = u W_q [32 x 128]; k, v = u W_k, u W_v [8 x 128];
    causal softmax(q k^T / 128) v; W_o. No positional embedding.
  ``mamba``  [z | xBC | dt] = u W_in; xBC <- silu(conv1d_causal(xBC; 4
    taps, depthwise, bias)); [x | B | C] = xBC; dt = softplus(dt +
    dt_bias); A = -exp(A_log); S_t = exp(dt_t A) S_{t-1} + dt_t x_t
    B_t^T, every head with the SAME B_t, C_t (one group); y_t = S_t C_t
    + D x_t; y <- RMSNorm(y silu(z)) over all 8,192; out = y W_out
  ``Experts``  l = u W_r [72]; the 10 largest; w = softmax over those;
    sum_{e held} w_e (silu(u G_e) * (u U_e)) D_e at width 768
  ``Shared``  (silu(u G) * (u U)) D at width 1,536

What is compared, in four parts. The first three are
``reference_nemotron_h``'s, for its reasons (the tokens of a top-k
routed model in bfloat16 cannot all equal a float32 reference's; the
router and the state's path, as functions on identical inputs, can):

1. TOKENS, ``token_checks``: every served token of the compared
   answers, teacher-forced; a token's margin is how far the reference
   prefers its own argmax to the served token, 0 where they agree, in
   the reference's logit units. Pooled over a run's compared tokens.
2. THE ROUTER, ``router_checks``: the function the served programs
   route with (``served_router``, ``ray_tpu.ops.moe:route``) against
   this reference's router (the 10 largest logits, a softmax over
   THOSE: the published form, another road to the same numbers) ON
   IDENTICAL INPUTS, the reference's own router inputs of the compared
   sequences rounded to the served dtype.
3. THE STATE, the next two of ``router_checks``' lines: what the
   served path keeps a sequence's state in and moves it with
   (``SERVED_POOL``, ``SERVED_SCAN``, ``SERVED_UPDATE``) against this
   reference's token recurrence, on the reference's own ``x``, ``dt``,
   ``B``, ``C`` of every Mamba-2 layer of the compared sequences
   (``_state_path``: spans handed on THROUGH a slot, a parked snapshot
   taken up by a second lane, the answer's tokens one at a time in
   place). ONE group of 128 heads: the case in which both kernels cut a
   group into blocks of heads.
4. THE LAST PROMPT ROW, the last of ``router_checks``' lines. Because
   ``logits / 16`` moves no argmax, a head that left ``logits_scaling``
   out would pass every token: so the logits row of the reference
   request's last prompt token, as the served CHUNK PROGRAM itself
   returns it (``SERVED_PROGRAMS``, the engine's own jitted program, run
   here over the prompt in spans of ``ROW_SPAN`` rows through pools of
   this comparison's own: the driver hands the reference tokens, not
   device arrays), is read against the reference's row in LOGIT units.
   Read for the first answer of a comparison (the reference request).

``lower=True`` is the nearest precision below the float32 that the
configuration's file states for them: the router's logits, and the
state ``S`` (rounded after every token), in bfloat16. It has to come
out NOT correct.

With ``BENCH_GRANITE_CONTROLS`` set in the environment ``compare``
reads the same answers again one precision lower and with each fault of
``FAULTS`` planted in the reference, and logs what the limits say of
each. They decide nothing.
"""

from __future__ import annotations

import functools
import os

# What the references share, from the accepted ones (no file of the
# benchmark may change, so they cannot move under this one): a token's
# margin, the lookup of the served router the configuration's file
# names, and the served state path against the recurrence (part 3: its
# ``_state_path`` names the served scan, update and pool as this module
# does below, scans in spans of ``SPAN`` rows and steps in loops of
# ``STEPS``, and reads of a configuration only ``chunk_size``, the
# scan's block length, which models/granite_hybrid.py's has under that
# name too).
from benchmark.reference_laguna import margins, served_router_of  # noqa: F401
from benchmark.reference_nemotron_h import (  # noqa: F401
    SERVED_POOL, SERVED_SCAN, SERVED_UPDATE, _bfloat16, _served, _state_path)

# Limits, each between two readings (my chip runs, PR 64: PERF.md
# section 6 has every reading by call and seed). "Sound" is the served
# path as committed: seven runs of the cell on seven seeds (calls 2 and
# 3), 1,827-1,957 compared tokens each. The faults were planted in the
# reference and read against the served tokens of three of those runs
# (``BENCH_GRANITE_CONTROLS``; three readings each). Logits here are a
# sixteenth of an untied head's off a tied matrix drawn at std 0.004
# (the model's ``EMBED_STD``): ~0.016 apart, the largest of a row
# ~0.07, so margins are small numbers. The limits were set after the
# seven runs.
#
# Pooled over a run's compared tokens:
#   share of tokens equal: sound 0.928-0.938; the scores scaled by
#   1/sqrt(128) 0.877-0.894, the convolution state dropped 0.878-0.889,
#   the state one block stale 0.819-0.846, residual_multiplier at 1
#   0.37-0.38, the shared MLP left out 0.05-0.12. (The state rounded
#   to bfloat16 in the reference reads 0.883-0.918: the state's limit's
#   to fail.)
MIN_EXACT_SHARE = 0.91
#   mean margin: sound 0.000027-0.000035; the scores scaled by
#   1/sqrt(128) 0.000081-0.000115, the convolution state dropped
#   0.00015-0.00026, logits_scaling left out 0.00043-0.00053 (sixteen
#   times sound), the state one block stale 0.00050-0.00070.
MAX_MEAN_MARGIN = 0.000055
# A single token: sound 0.0013-0.0020; the convolution state dropped
# 0.011-0.023, logits_scaling left out 0.021-0.031, the state one block
# stale 0.026-0.041. (The scores scaled by 1/sqrt(128) read 0.0031-
# 0.0042, under this limit: the two limits' above to fail, and they do
# in all three.)
MAX_MARGIN = 0.006
# Share of tokens whose 10 experts the served router and the
# reference's pick alike, on identical inputs: float32 logits on both
# sides agree on every token of every run (~80,000 a run); logits in
# bfloat16 on 0.9758-0.9768 of them.
MIN_ROUTER_AGREEMENT = 0.99
# Largest difference between the served router's weights and the
# reference's on a token whose experts they pick alike (a weight is
# ~0.1): sound 0.000000 in every run; one precision lower 0.0054.
MAX_WEIGHT_DIFF = 0.003
# Largest difference between what the served state path computes and
# holds and the token recurrence's, on identical inputs, each over the
# largest entry of its kind: sound 6.8e-5 to 2.4e-4; the reference's
# state rounded to bfloat16 every token 0.024-0.088, what the served
# side writes to a slot rounded to bfloat16 0.018-0.107.
MAX_STATE_DIFF = 2e-3
# Largest difference, in logit units, between the served chunk
# program's row of the reference request's last prompt token and the
# reference's (the row's largest logit is ~0.07): sound 0.0015-0.0024;
# the convolution state dropped 0.024-0.039, residual_multiplier at 1
# 0.024-0.027, the state one block stale 0.036-0.046, the shared MLP
# left out 0.067-0.072, logits_scaling left out 1.00-1.09. (The scores
# scaled by 1/sqrt(128) read 0.0034-0.0038 and a state rounded to
# bfloat16 0.0022-0.0047: other limits'.)
MAX_ROW_DIFF = 0.006
STATE_DTYPE = "float32"
# What the served path runs a span of a prompt as, and what describes
# its pools and packs a span's table: "module:attribute".
SERVED_PROGRAMS = "ray_tpu.llm.engine:_jit_programs"
SERVED_SEAM = "ray_tpu.models:serving"
SERVED_SPAN_TABLE = "ray_tpu.models:pack_span"
ROW_SPAN = 1024         # rows a span of the served chunk program (the
#                         cell's ``prefill_chunk_tokens``)
PAD_TO = 1024           # sequences are padded to a multiple of this
ROWS = 1024             # queries computed at a time
BLOCK = 16              # the planted faults' block: the served cache's

# Departures planted in the reference, each of which has to fail a
# limit: name -> what it changes.
FAULTS = {
    "state_in_bfloat16": "the state S rounded to bfloat16 every token",
    "served_state_in_bfloat16": "what the served path writes to a state "
                                "slot rounded to bfloat16",
    "conv_state_dropped": "the convolution state dropped where the "
                          "prompt's last span starts",
    "stale_snapshot": "the state the prompt's last span starts from one "
                      "block stale",
    "residual_multiplier_one": "residual_multiplier left at 1",
    "scores_by_rsqrt_head_dim": "the attention scores scaled by "
                                "1/sqrt(head_dim), not attention_multiplier",
    "logits_scaling_left_out": "logits_scaling left out",
    "shared_mlp_left_out": "the shared MLP left out",
}


def _route(u, router, cfg, logits_in):
    """The reference's router: (experts [T, k], weights [T, k] float32)
    from logits held in ``logits_in``: the k largest, a softmax over
    those."""
    import jax
    import jax.numpy as jnp

    logits = u.astype(logits_in) @ router.astype(logits_in)
    top, idx = jax.lax.top_k(logits, cfg.num_experts_per_tok)
    return idx, jax.nn.softmax(top.astype(jnp.float32), axis=-1)


@functools.lru_cache(maxsize=None)
def _layer_fn(cfg, kind: str, T: int, lower: bool, fault):
    """One layer (mixer and expert block) on a whole padded sequence
    [T, hidden], jitted: ``fn(x, p, b)`` -> (x out, the router's input,
    what the state's path is read against or None: the recurrence's own
    ``x``, ``dt``, ``A``, ``B``, ``C``, its outputs ``S C`` and its
    state after ``b[1]`` and after ``b[2]`` tokens). ``b[0]`` is where
    the planted faults sit (``forward``)."""
    import jax
    import jax.numpy as jnp

    F32 = jnp.float32
    eps = cfg.rms_norm_eps
    rows = min(ROWS, T)
    t = jnp.arange(T)
    r = 1.0 if fault == "residual_multiplier_one" \
        else cfg.residual_multiplier

    def norm(x, w):
        return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * w

    def swiglu(u, w_gu, w_down):
        f = w_gu.shape[-1] // 2
        gu = u @ w_gu
        return (jax.nn.silu(gu[:, :f]) * gu[:, f:]) @ w_down

    def attention(u, p, b):
        d = cfg.hidden_size // cfg.num_attention_heads
        group = cfg.num_attention_heads // cfg.num_key_value_heads
        scale = d ** -0.5 if fault == "scores_by_rsqrt_head_dim" \
            else cfg.attention_multiplier
        q = jnp.einsum("tm,mhd->htd", u, p["wq"])
        k = jnp.einsum("tm,mhd->htd", u, p["wk"])
        v = jnp.einsum("tm,mhd->htd", u, p["wv"])

        def head(args):
            qh, kvh = args
            kh, vh = k[kvh], v[kvh]

            def block(a):
                qb, i0 = a
                s = (qb @ kh.T) * scale
                s = jnp.where(t[None, :] <= i0 + jnp.arange(rows)[:, None],
                              s, -jnp.inf)
                return jax.nn.softmax(s, axis=-1) @ vh

            return jax.lax.map(block, (qh.reshape(T // rows, rows, d),
                                       jnp.arange(0, T, rows))
                               ).reshape(T, d)

        o = jax.lax.map(head, (q, jnp.arange(q.shape[0]) // group))
        return jnp.einsum("htd,hdm->tm", o, p["wo"]), None

    def mamba(u, p, marks):
        b = marks[0]
        H, P, G, N, K = (cfg.mamba_n_heads, cfg.mamba_d_head,
                         cfg.mamba_n_groups, cfg.mamba_d_state,
                         cfg.mamba_d_conv)
        di, cd = H * P, H * P + 2 * G * N
        proj = u @ p["w_in"]
        z, xBC, dt = proj[:, :di], proj[:, di:di + cd], proj[:, di + cd:]
        padded = jnp.concatenate([jnp.zeros((K - 1, cd), F32), xBC])
        conv = p["conv_b"]
        for k in range(K):
            tap = padded[k:k + T]           # row t - (K - 1) + k
            if fault == "conv_state_dropped":
                # Rows from b on see nothing from before b.
                kept = (t - (K - 1) + k >= b) | (t < b)
                tap = jnp.where(kept[:, None], tap, 0.0)
            conv = conv + tap * p["conv_w"][k]
        xBC = jax.nn.silu(conv)
        x = xBC[:, :di].reshape(T, H, P)
        B = xBC[:, di:di + G * N].reshape(T, G, N)
        C = xBC[:, di + G * N:].reshape(T, G, N)
        dt = jax.nn.softplus(dt + p["dt_bias"])
        if fault == "stale_snapshot":
            # The state at b is the state at b - BLOCK: the block's
            # tokens never reached it.
            dt = jnp.where(((t >= b - BLOCK) & (t < b))[:, None], 0.0, dt)
        A = -jnp.exp(p["A_log"])
        rounded = lower or fault == "state_in_bfloat16"

        def token(carry, row):
            S, kept = carry
            x_t, B_t, C_t, dt_t, i = row
            B_t, C_t = (jnp.repeat(a, H // G, axis=0) for a in (B_t, C_t))
            S = (jnp.exp(dt_t * A)[:, None, None] * S
                 + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :])
            if rounded:
                # ``reduce_precision``, not a pair of casts: the TPU's
                # compiler drops a round trip through a narrower type.
                S = _bfloat16(S)
            # The state after marks[1] and after marks[2] tokens.
            kept = jnp.where((i + 1 == marks[1:])[:, None, None, None],
                             S[None], kept)
            return (S, kept), \
                (S * C_t[:, None, :]).sum(-1) + p["D"][:, None] * x_t

        (_, kept), y = jax.lax.scan(
            token, (jnp.zeros((H, P, N), F32), jnp.zeros((2, H, P, N), F32)),
            (x, B, C, dt, t))
        state = (x, dt, A, B, C, y - p["D"][:, None] * x, kept)
        y = y.reshape(T, di) * jax.nn.silu(z)
        g = y.reshape(T, G, di // G)
        g = g * jax.lax.rsqrt((g * g).mean(-1, keepdims=True) + eps)
        return (g.reshape(T, di) * p["norm"]) @ p["w_out"], state

    def experts(u, p):
        logits_in = jnp.dtype(cfg.dtype) if lower else jnp.dtype(F32)
        idx, w = _route(u, p["router"], cfg, logits_in)
        by_expert = jnp.zeros((T, cfg.num_local_experts), F32).at[
            t[:, None], idx].set(w)
        mine = by_expert[:, cfg.first_expert:
                         cfg.first_expert + cfg.experts_held]

        def add(acc, xs):
            w1, w2, we = xs
            return acc + we[:, None] * swiglu(u, w1, w2), None

        out, _ = jax.lax.scan(add, jnp.zeros_like(u),
                              (p["w1"], p["w2"], mine.T))
        if fault != "shared_mlp_left_out":
            out = out + swiglu(u, p["s_gu"], p["s_down"])
        return out

    mixer = {"attention": attention, "mamba": mamba}[kind]

    def fn(x, p, b):
        p = jax.tree_util.tree_map(lambda a: a.astype(F32), p)
        out, state = mixer(norm(x, p["ln_a"]), p, b)
        h = x + r * out
        u = norm(h, p["ln_b"])
        return h + r * experts(u, p), u, state

    return jax.jit(fn)


@functools.lru_cache(maxsize=None)
def _head_fn(cfg, scaled: bool):
    import jax
    import jax.numpy as jnp

    def fn(x, scale, embed):
        x = x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True)
                              + cfg.rms_norm_eps) \
            * scale.astype(jnp.float32)
        logits = x @ embed.astype(jnp.float32).T
        return logits / cfg.logits_scaling if scaled else logits

    return jax.jit(fn)


def served_row(params, cfg, prompt: list):
    """The logits row [vocab] of ``prompt``'s last token as the served
    chunk program returns it: the engine's own jitted program
    (``SERVED_PROGRAMS``) over the prompt in spans of ``ROW_SPAN`` rows,
    each behind the blocks and the state slot the last one wrote,
    through pools of this call's own (keys and values for the prompt's
    blocks and scratch block 0; the cache manager's ``StatePool`` with
    one lane's slot)."""
    import jax.numpy as jnp
    import numpy as np

    seam = _served(SERVED_SEAM)(cfg)
    _, chunk = _served(SERVED_PROGRAMS)(cfg)
    pack_span = _served(SERVED_SPAN_TABLE)
    blocks = -(-len(prompt) // BLOCK)
    kind = seam.kinds[0]
    kv = [jnp.zeros((len(kind.layers), blocks + 1, BLOCK, w), kind.dtype)
          for w in kind.rows]
    state = _served(SERVED_POOL)(cfg, 2)
    lane, pools = state.grant(), state.pools
    table = np.arange(1, blocks + 1, dtype=np.int32)
    row = None
    for upto in range(0, len(prompt), ROW_SPAN):
        c = min(ROW_SPAN, len(prompt) - upto)
        n = -(-c // BLOCK) * BLOCK
        tokens = np.zeros((1, n), np.int32)
        tokens[0, :c] = prompt[upto:upto + c]
        first = upto // BLOCK
        span = pack_span(table if upto else table[:0],
                         table[first:first + n // BLOCK], upto, c - 1,
                         lane, lane)
        row, _, *out = chunk(params, tokens, *kv, span, *pools)
        kv, pools = out[:len(kv)], out[len(kv):]
    return np.asarray(row, np.float32)


def forward(params, cfg, prompt: list, got: list, lower: bool = False,
            fault=None):
    """One full forward pass over prompt + got. Returns (logits
    [len(got), vocab] float32 at the positions that decide ``got``,
    teacher-forced; {layer: its router's input [len(prompt) + len(got),
    hidden] float32}; (the served state path's largest difference from
    the recurrence, ``_state_path``, the worst Mamba-2 layer; the dtype
    the served pool holds ``S`` in))."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    seq = list(prompt) + list(got)
    T = -(-len(seq) // PAD_TO) * PAD_TO
    buf = np.zeros((T,), np.int32)
    buf[:len(seq)] = seq
    # The state path's marks: its snapshot at the last block boundary
    # that leaves a token of the prompt to compute, the prompt's end,
    # the sequence's. The planted faults sit at the first (``b[0]``):
    # where a sequence that resumes from a parked snapshot starts, so
    # that the prompt's last row and every answer token lie behind them.
    marks = ((len(prompt) - 1) // BLOCK * BLOCK, len(prompt), len(seq))
    b = jnp.asarray((marks[0], marks[0], marks[2]), jnp.int32)
    # Four slots: scratch, a lane's, the snapshot, the lane's that
    # resumes from it (a layer's snapshot is evicted for the next's).
    pools = _served(SERVED_POOL)(cfg, 4)
    dtype = str(pools.pools[0].dtype)
    rounded = fault == "served_state_in_bfloat16"
    router_inputs, diff = {}, 0.0
    mixers = [l for l, kind in enumerate(cfg.layer_types)
              if kind == "mamba"]
    with jax.default_matmul_precision("highest"):
        x = cfg.embedding_multiplier \
            * params["embed"][jnp.asarray(buf)].astype(jnp.float32)
        for l, (kind, p) in enumerate(zip(cfg.layer_types,
                                          params["layers"])):
            x, u, state = _layer_fn(cfg, kind, T, lower,
                                    None if rounded else fault)(x, p, b)
            router_inputs[l] = u[:len(seq)]
            if state is not None:
                # A pool in another dtype than stated fails by that
                # check; the update kernel takes none.
                diff = max(diff, _state_path(
                    cfg, pools, mixers.index(l), state, marks, rounded)
                    if dtype == STATE_DTYPE else float("inf"))
            del state
        rows = x[len(prompt) - 1:len(seq) - 1]
        logits = np.asarray(_head_fn(
            cfg, fault != "logits_scaling_left_out")(
                rows, params["norm_f"], params["embed"]), np.float32)
    return logits, router_inputs, (diff, dtype)


def router_agreement(params, cfg, router_inputs: dict, served_router,
                     lower: bool = False) -> tuple:
    """(tokens whose experts ``served_router`` and the reference's
    router pick alike, tokens compared, the largest difference between
    the two routers' weights on a token whose experts they pick alike)
    over every layer, both on the reference's router inputs rounded to
    the served dtype. ``served_router(x, w, k)`` returns (_, experts
    [T, k], weights [T, k]), the signature of ``ray_tpu.ops.moe.route``."""
    import jax
    import jax.numpy as jnp

    logits_in = jnp.dtype(cfg.dtype) if lower else jnp.dtype(jnp.float32)
    same = total = 0
    worst = 0.0
    for l, u in router_inputs.items():
        w = params["layers"][l]["router"]
        x = u.astype(cfg.dtype)
        _, served, served_w = served_router(x, w, cfg.num_experts_per_tok)
        with jax.default_matmul_precision("highest"):
            mine, mine_w = _route(x, w, cfg, logits_in)
        total += x.shape[0]
        # Both sides sorted by expert, so that weights pair up.
        so, mo = jnp.argsort(served, -1), jnp.argsort(mine, -1)
        alike = (jnp.take_along_axis(served, so, -1)
                 == jnp.take_along_axis(mine, mo, -1)).all(-1)
        diff = jnp.abs(jnp.take_along_axis(served_w, so, -1)
                       - jnp.take_along_axis(mine_w, mo, -1)).max(-1)
        same += int(alike.sum())
        worst = max(worst, float(jnp.where(alike, diff, 0.0).max()))
    return same, total, worst


def _read(params, cfg, served_router, answers, lower=False, fault=None,
          rows=None):
    """``rows``: the served chunk program's row of each answer's last
    prompt token, where it was computed (``compare`` computes the first
    answer's once and hands it to the controls' readings)."""
    import numpy as np

    tokens, lines = [], []
    same = total = 0
    weight = state = 0.0
    row_diff = None
    for i, (what, prompt, got) in enumerate(answers):
        logits, router_inputs, (diff, dtype) = forward(
            params, cfg, prompt, got, lower, fault)
        state = max(state, diff)
        m = margins(logits, got)
        s, t, w = router_agreement(params, cfg, router_inputs, served_router,
                                   lower)
        tokens += m
        same, total, weight = same + s, total + t, max(weight, w)
        line = (f"{what}: {sum(x == 0.0 for x in m)}/{len(m)} tokens "
                f"({len(set(got))} distinct) equal, worst margin "
                f"{max(m, default=0.0):.5f}, mean "
                f"{sum(m) / max(len(m), 1):.6f}; router alike on "
                f"{s}/{t} tokens, weights within {w:.6f}; the "
                f"served state path within {diff:.2e} of the recurrence")
        if rows and rows.get(i) is not None and len(got):
            d = float(np.abs(rows[i] - logits[0]).max())
            row_diff = d if row_diff is None else max(row_diff, d)
            line += (f"; the chunk program's last prompt row within "
                     f"{d:.5f} of the reference's (largest logit "
                     f"{float(np.abs(logits[0]).max()):.4f})")
        lines.append(line)
    n = len(tokens)
    return {"n": n, "exact": sum(x == 0.0 for x in tokens),
            "worst": max(tokens, default=0.0),
            "mean": sum(tokens) / max(n, 1),
            "router_same": same, "router_total": total,
            "router_weight_diff": weight, "state_diff": state,
            "state_dtype": dtype if answers else None,
            "row_diff": row_diff, "lines": lines}


def compare(params, cfg, served_router, answers: list,
            lower: bool = False) -> dict:
    """Every ``(what, prompt, got)`` of ``answers`` through the
    reference: the pooled readings ``token_checks`` and
    ``router_checks`` judge, and a line an answer for the log. The
    served chunk program runs once, over the first answer's prompt
    (module docstring, part 4). With ``BENCH_GRANITE_CONTROLS`` set, the
    controls' readings follow as further lines."""
    rows = {0: served_row(params, cfg, answers[0][1])} if answers else {}
    read = _read(params, cfg, served_router, answers, lower, rows=rows)
    if os.environ.get("BENCH_GRANITE_CONTROLS") and not lower:
        controls = [("one precision lower", True, None)] + [
            (what, False, fault) for fault, what in FAULTS.items()]
        for name, low, fault in controls:
            r = _read(params, cfg, served_router, answers, low, fault, rows)
            read["lines"].append(
                f"control, {name}: {r['exact']}/{r['n']} equal, mean "
                f"{r['mean']:.6f}, worst {r['worst']:.5f}; router "
                f"{r['router_same']}/{r['router_total']}, weights "
                f"{r['router_weight_diff']:.6f}; state "
                f"{r['state_diff']:.2e}; row {r['row_diff']}")
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
         f"mean reference margin of the compared tokens {r['mean']:.6f} "
         f"(limit {MAX_MEAN_MARGIN})"),
        (n > 0 and r["worst"] < MAX_MARGIN,
         f"worst reference margin of a compared token {r['worst']:.5f} "
         f"(limit {MAX_MARGIN})"),
    ]


def router_checks(r: dict) -> list:
    same, total = r["router_same"], r["router_total"]
    row = r["row_diff"]
    return [
        (total > 0 and same >= MIN_ROUTER_AGREEMENT * total,
         f"the served router and the reference's pick the same experts "
         f"on {same}/{total} tokens of the compared sequences' router "
         f"inputs (at least {MIN_ROUTER_AGREEMENT:.1%})"),
        (total > 0 and r["router_weight_diff"] < MAX_WEIGHT_DIFF,
         f"their weights differ by at most {r['router_weight_diff']:.6f} "
         f"on those tokens (limit {MAX_WEIGHT_DIFF})"),
        (total > 0 and r["state_diff"] < MAX_STATE_DIFF,
         f"the served scan, update and pool of state slots differ from the "
         f"reference's token recurrence by at most {r['state_diff']:.2e} of "
         f"the largest output or state on the compared sequences' inputs "
         f"(limit {MAX_STATE_DIFF})"),
        (r["state_dtype"] == STATE_DTYPE,
         f"the served pool of state slots holds S in {r['state_dtype']} "
         f"(the configuration states {STATE_DTYPE})"),
        (row is not None and row < MAX_ROW_DIFF,
         f"the served chunk program's logits row of the reference "
         f"request's last prompt token differs from the reference's by at "
         f"most {row if row is None else round(row, 6)} in logit units "
         f"(limit {MAX_ROW_DIFF})"),
    ]
