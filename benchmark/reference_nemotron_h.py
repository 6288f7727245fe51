"""The plain reference that decides ``correct`` for the Nemotron-H
configurations, and its limits: the benchmark's own copy of the layer
equations (``ray_tpu/models/nemotron_h_ref.py`` is the repository's,
which its tests use), kept here so that no later PR can move what a cell
is judged by. A configuration names this module under ``reference``; the
interface is the one ``drivers/serve_closed_loop_ref.py`` calls
(``served_router_of``, ``compare``, ``token_checks``,
``router_checks``).

What it computes (``forward``): the model's full forward pass over
prompt + answer, one sequence, no cache, no kernel, no batching, no
chunks, in float32 at matmul precision ``highest``, on the SERVED
parameters (bfloat16, made from ``--seed`` by the model's own ``init``)
raised to float32 a layer at a time. The state-space recurrence is a
``lax.scan`` over TOKENS from a zero state (the served path runs the
chunked form over spans, resumes from parked snapshots and moves lanes'
states one token at a time in a kernel: none of that is here); the
convolution is a sum over shifted copies of the whole sequence;
attention is a dense masked softmax in blocks of ``ROWS`` queries. It is
given the same share the served model holds: it routes over all
``n_routed_experts``, loops over the ``experts_held`` experts from
``first_expert``, every one on every token, kept by the router's
weight, takes the sum up through ``W_up`` and adds the shared expert.
Sequences are padded to a multiple of ``PAD_TO`` so that comparisons
share compiled programs (causal: the padding reaches nothing before it).

The equations (x [T, hidden]; every layer ``x += Mixer(RMSNorm(x))``;
no biases but the convolution's):
  ``*``  q = h W_q [32 x 128]; k, v = h W_k, h W_v [2 x 128]; causal
    softmax(q k^T / sqrt(128)) v; W_o. No rotary.
  ``M``  [z | xBC | dt] = h W_in; xBC <- silu(conv1d_causal(xBC; 4 taps,
    depthwise, bias)); [x | B | C] = xBC; dt = softplus(dt + dt_bias);
    A = -exp(A_log); S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T (head h
    uses group h // 16's B and C); y_t = S_t C_t + D x_t;
    y <- RMSNorm_grouped(y silu(z)); out = y W_out
  ``E``  s = sigmoid(h W_r); the 22 largest of s + b; w = 5 s_top /
    sum(s_top); u = h W_dn; out = (sum_{e held} w_e relu(u W1_e)^2 W2_e)
    W_up + relu(h Ws1)^2 Ws2
  logits = RMSNorm(x) W_head

What is compared, in three parts: the two of ``reference_kimi_k2``, for
its reasons (the tokens of a top-k routed model in bfloat16 cannot all
equal a float32 reference's; the router, as a function on identical
inputs, can), and the state's path, as functions on identical inputs,
for the same reason (a state rounded to bfloat16 moves few tokens of a model
whose activations are bfloat16 anyway, and all of the state's output):

1. TOKENS, ``token_checks``: every served token of the compared
   answers, teacher-forced; a token's margin is how far the reference
   prefers its own argmax to the served token, 0 where they agree.
   Pooled over a run's compared tokens (the reference request's 64 and
   four prefixes' longest answers). These limits have to fail with the
   convolution state dropped where the prompt's last span ends and with
   the state there one block stale (planted in the reference,
   ``FAULTS``).
2. THE ROUTER, ``router_checks``: the function the served programs
   route with (``served_router``, ``ray_tpu.ops.moe:route_sigmoid``)
   against this reference's router ON IDENTICAL INPUTS, the reference's
   own router inputs of the compared sequences rounded to the served
   dtype: the share of tokens whose experts the two pick alike, and the
   largest difference between their weights on those tokens. These
   limits have to fail with bfloat16 router scores (``lower``) and with
   one expert fewer a token.
3. THE STATE, the last two of ``router_checks``' lines (the driver
   calls that function for what is compared as a function on identical
   inputs): what the served path keeps a sequence's state in and moves
   it with, against this reference's token recurrence, on the
   reference's own ``x``, ``dt``, ``B``, ``C`` of every state-space
   layer of the compared sequences (``_state_path``). The cache
   manager's pool of slots (``SERVED_POOL``,
   ``ray_tpu.llm.kv_cache:StatePool``, which makes its pools in the
   dtypes the model's seam states, as the engine's does) is granted a
   lane's slot; the prompt is scanned into it in spans of ``SPAN`` rows
   by the function the served chunk program scans with
   (``SERVED_SCAN``, ``ray_tpu.ops.ssm:ssd_scan``), the state handed
   from span to span THROUGH the slot; the pool parks a snapshot at the
   last block boundary that leaves a token to compute (its own copy
   program); a second lane's first span reads the snapshot's slot and
   writes its own; the answer's tokens move that slot one at a time, in
   place, by the function the served decode program updates with
   (``SERVED_UPDATE``, ``ray_tpu.ops.ssm:ssm_update``). Read: the
   largest difference of the outputs ``S C`` over the largest output,
   and of the parked snapshot and of the slot after the answer's last
   token from the recurrence's ``S`` at the same positions, over the
   largest entry of each. This limit has to fail with the reference's
   ``S`` rounded to bfloat16 every token (``lower``, and the fault
   ``state_in_bfloat16``), with what the SERVED side writes to a slot
   rounded to bfloat16 (``served_state_in_bfloat16``: a pool or an
   update kernel one precision lower), and would with the scan's
   products at the TPU's default precision. Beside it, the dtype the
   pool holds ``S`` in has to be the float32 the configuration states.
   What this part does NOT read is the slot the timed run itself left:
   the driver hands the reference tokens, not device arrays (PERF.md
   section 7).

``lower=True`` is the nearest precision below the float32 that the
configuration's file states for them: the router's scores and their
sigmoid, and the state ``S`` (rounded after every token), in bfloat16.
It has to come out NOT correct.

With ``BENCH_NEMOTRON_CONTROLS`` set in the environment ``compare``
reads the same answers again one precision lower and with each fault of
``FAULTS`` planted in the reference, and logs what the limits say of
each. They decide nothing.
"""

from __future__ import annotations

import dataclasses
import functools
import os

# What the references share, from the accepted one (no file of the
# benchmark may change, so it cannot move under this one): a token's
# margin and the lookup of the served router the configuration's file
# names.
from benchmark.reference_laguna import margins, served_router_of  # noqa: F401

# Limits, each between two readings (my chip runs, PR 57: PERF.md
# section 6 has every reading by call and seed). "Sound" is the served
# path as committed: 16 runs of the cell on 16 seeds (calls A to E),
# 930-1,060 compared tokens each. The faults were planted in the
# reference and read against the served tokens of three of those runs
# and of one on an earlier tree (``BENCH_NEMOTRON_CONTROLS``; four
# readings each). The limits were set after the first two calls.
#
# Pooled over a run's compared tokens:
#   share of tokens equal: sound 0.943-0.961; the state one block
#   stale 0.888-0.912, the convolution state dropped 0.917-0.926 (that
#   one is the next limit's to fail). (One expert fewer reads 0.925-
#   0.933 and one precision lower 0.924-0.938: the router's limits'.)
MIN_EXACT_SHARE = 0.92
#   mean margin: sound 0.0008-0.0016; the convolution state dropped
#   0.0149-0.0191, the state one block stale 0.0171-0.0210. (One expert
#   fewer 0.0028-0.0038.)
MAX_MEAN_MARGIN = 0.007
# A single token: sound 0.06-0.26; the convolution state dropped
# 2.2-3.2. (The state one block stale reads 1.15-2.19, on both sides
# of this limit: it is the two limits' above to fail, and does in all
# four.)
MAX_MARGIN = 1.5
# Share of tokens whose 22 experts the served router and the
# reference's pick alike, on identical inputs: float32 scores on both
# sides agree on every token of every run (~100,000 a run); scores and
# sigmoid in bfloat16 on 0.617-0.625 of them, 21 experts on none.
MIN_ROUTER_AGREEMENT = 0.99
# Largest difference between the served router's weights and the
# reference's on a token whose experts they pick alike (a weight is
# ~5 / 22 = 0.23): sound 0.000000 in every run; one precision lower
# 0.0008 (it fails the agreement).
MAX_WEIGHT_DIFF = 0.05
# Largest difference between what the served state path computes and
# holds and the token recurrence's, on identical inputs, each over the
# largest entry of its kind (outputs ``S C``; the parked snapshot; the
# slot after the answer's last token): sound 1.2e-4 to 4.8e-4 (calls
# D and E, seven runs; float32 at precision ``highest`` on both sides, the
# chunked form takes differences of running sums of up to ~200); the
# reference's state rounded to bfloat16 every token 0.057, what the
# served side writes to a slot rounded to bfloat16 0.026 (one run's;
# neither moves a token limit: 0.939 and 0.956 equal).
MAX_STATE_DIFF = 2e-3
# The dtype the configuration's file states for the state ``S``
# (``assumed.ssm_state_float32``); the cache manager's pool has to hold
# it in this.
STATE_DTYPE = "float32"
# What the served path scans a span with, moves a decode step's states
# with, and keeps them in: "module:attribute".
SERVED_SCAN = "ray_tpu.ops.ssm:ssd_scan"
SERVED_UPDATE = "ray_tpu.ops.ssm:ssm_update"
SERVED_POOL = "ray_tpu.llm.kv_cache:StatePool"
SPAN = 512              # rows a span of the state's path scans
STEPS = 256             # decode steps a compiled loop of updates holds
PAD_TO = 1024           # sequences are padded to a multiple of this
ROWS = 1024             # queries computed at a time
BLOCK = 16              # the planted faults' block: the served cache's

# Departures planted in the reference, each of which has to fail a
# limit: name -> what it changes.
FAULTS = {
    "state_in_bfloat16": "the state S rounded to bfloat16 every token",
    "served_state_in_bfloat16": "what the served path writes to a state "
                                "slot rounded to bfloat16",
    "one_expert_fewer": "one expert fewer a token",
    "conv_state_dropped": "the convolution state dropped where the "
                          "prompt's last span ends",
    "stale_snapshot": "the state at the prompt's last block boundary one "
                      "block stale",
}


def _route(h, router, bias, cfg, scores_in):
    """The reference's router: (experts [T, k], weights [T, k] float32)
    from scores and a sigmoid held in ``scores_in``."""
    import jax
    import jax.numpy as jnp

    s = jax.nn.sigmoid(h.astype(scores_in) @ router.astype(scores_in))
    _, idx = jax.lax.top_k(s + bias.astype(scores_in),
                           cfg.num_experts_per_tok)
    top = jnp.take_along_axis(s, idx, axis=-1).astype(jnp.float32)
    return idx, cfg.routed_scaling_factor * top / top.sum(-1, keepdims=True)


@functools.lru_cache(maxsize=None)
def _layer_fn(cfg, letter: str, T: int, lower: bool, fault):
    """One layer on a whole padded sequence [T, hidden], jitted: ``fn(x,
    p, b)`` -> (x out, the router's input or None, what the state's
    path is read against or None: the recurrence's own ``x``, ``dt``,
    ``A``, ``B``, ``C``, its outputs ``S C`` and its state after
    ``b[1]`` and after ``b[2]`` tokens). ``b[0]`` is where the planted
    faults sit: the prompt's last block boundary."""
    import jax
    import jax.numpy as jnp

    F32 = jnp.float32
    eps = cfg.layer_norm_epsilon
    rows = min(ROWS, T)
    t = jnp.arange(T)

    def norm(x, w):
        return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * w

    def relu2(h, w1, w2):
        return jnp.square(jax.nn.relu(h @ w1)) @ w2

    def attention(h, p, b):
        d, group = cfg.head_dim, \
            cfg.num_attention_heads // cfg.num_key_value_heads
        q = jnp.einsum("tm,mhd->htd", h, p["wq"])
        k = jnp.einsum("tm,mhd->htd", h, p["wk"])
        v = jnp.einsum("tm,mhd->htd", h, p["wv"])

        def head(args):
            qh, kvh = args
            kh, vh = k[kvh], v[kvh]

            def block(a):
                qb, i0 = a
                s = (qb @ kh.T) * d ** -0.5
                s = jnp.where(t[None, :] <= i0 + jnp.arange(rows)[:, None],
                              s, -jnp.inf)
                return jax.nn.softmax(s, axis=-1) @ vh

            return jax.lax.map(block, (qh.reshape(T // rows, rows, d),
                                       jnp.arange(0, T, rows))
                               ).reshape(T, d)

        o = jax.lax.map(head, (q, jnp.arange(q.shape[0]) // group))
        return jnp.einsum("htd,hdm->tm", o, p["wo"]), None, None

    def mamba(h, p, marks):
        b = marks[0]
        H, P, G, N, K = (cfg.mamba_num_heads, cfg.mamba_head_dim,
                         cfg.n_groups, cfg.ssm_state_size, cfg.conv_kernel)
        di, cd = cfg.d_inner, cfg.conv_dim
        proj = h @ p["w_in"]
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
                # To bfloat16's 8 bits of mantissa and back. Not a pair
                # of casts: the TPU's compiler drops a round trip
                # through a narrower type (excess precision is allowed
                # by default), and the fault would not be planted.
                S = jax.lax.reduce_precision(S, exponent_bits=8,
                                             mantissa_bits=7)
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
        return (g.reshape(T, di) * p["norm"]) @ p["w_out"], None, state

    def experts(h, p, b):
        scores_in = jnp.dtype(cfg.dtype) if lower else jnp.dtype(F32)
        idx, w = _route(h, p["router"], p["router_bias"], cfg, scores_in)
        by_expert = jnp.zeros((T, cfg.n_routed_experts), F32).at[
            t[:, None], idx].set(w)
        mine = by_expert[:, cfg.first_expert:
                         cfg.first_expert + cfg.experts_held]
        u = h @ p["w_dn"]

        def add(acc, xs):
            w1, w2, we = xs
            return acc + we[:, None] * relu2(u, w1, w2), None

        routed, _ = jax.lax.scan(add, jnp.zeros_like(u),
                                 (p["w1"], p["w2"], mine.T))
        return routed @ p["w_up"] + relu2(h, p["s1"], p["s2"]), h, None

    mixer = {"*": attention, "M": mamba, "E": experts}[letter]

    def fn(x, p, b):
        p = jax.tree_util.tree_map(lambda a: a.astype(F32), p)
        out, router_input, state = mixer(norm(x, p["ln"]), p, b)
        return x + out, router_input, state

    return jax.jit(fn)


@functools.lru_cache(maxsize=None)
def _served(what: str):
    import importlib

    module, name = what.split(":")
    return getattr(importlib.import_module(module), name)


def _bfloat16(a):
    import jax

    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


@functools.lru_cache(maxsize=None)
def _span_fn(cfg, rounded: bool):
    """Rows [start, stop) of a layer's inputs (at most ``SPAN``) through
    the served scan, as the served chunk program does it: from the state
    in slot ``src`` (zeros at a sequence's start, whatever the slot
    holds), rows past ``stop`` with ``dt`` 0, the state at the span's
    end written to slot ``dst`` in the pool's dtype. ``fn(pool, x, dt,
    A, B, C, want, layer, start, stop, src, dst)`` -> (the largest
    difference of the span's outputs from ``want``'s, pool)."""
    import jax
    import jax.numpy as jnp

    scan = _served(SERVED_SCAN)

    def fn(pool, x, dt, A, B, C, want, layer, start, stop, src, dst):
        # ``SPAN`` rows that hold [start, stop): where the sequence's
        # padding ends sooner, rows before ``start`` come first, and
        # with ``dt`` 0 they hand the incoming state on as it is.
        first = jnp.minimum(start, x.shape[0] - SPAN)
        rows = lambda a: jax.lax.dynamic_slice_in_dim(a, first, SPAN)
        at = first + jnp.arange(SPAN)
        live = ((at >= start) & (at < stop))[:, None]
        y, S = scan(rows(x), jnp.where(live, rows(dt), 0.0), A, rows(B),
                    rows(C), jnp.where(start == 0, 0.0,
                                       pool[layer, src].astype(jnp.float32)),
                    cfg.chunk_size)
        if rounded:
            S = _bfloat16(S)
        diff = jnp.where(live[:, :, None], jnp.abs(y - rows(want)), 0.0)
        return diff.max(), pool.at[layer, dst].set(S.astype(pool.dtype))

    return jax.jit(fn, donate_argnums=0)


@functools.lru_cache(maxsize=None)
def _steps_fn(cfg, layer: int, rounded: bool):
    """``count`` rows from ``start`` on (at most ``STEPS``) through the
    served update, one token a call, slot ``slot`` of the pool moved in
    place as a decode step moves a lane's. ``fn(pool, x, dt, A, B, C,
    want, start, count, slot)`` -> (the largest difference of a step's
    output from ``want``'s, pool)."""
    import jax
    import jax.numpy as jnp

    update = _served(SERVED_UPDATE)

    def fn(pool, x, dt, A, B, C, want, start, count, slot):
        def step(pool, i):
            row = lambda a: jax.lax.dynamic_index_in_dim(
                a, start + i, keepdims=False)
            live = i < count
            dt_t = jnp.where(live, row(dt), 0.0)    # dt 0 moves nothing
            y, pool = update(pool, layer, slot[None],
                             jnp.exp(dt_t * A)[None],
                             (dt_t[:, None] * row(x))[None],
                             row(B)[None], row(C)[None])
            if rounded:
                pool = pool.at[layer, slot].set(_bfloat16(pool[layer, slot]))
            return pool, jnp.where(live, jnp.abs(y[0] - row(want)).max(),
                                   0.0)

        pool, diff = jax.lax.scan(step, pool, jnp.arange(STEPS))
        return diff.max(), pool

    return jax.jit(fn, donate_argnums=0)


def _state_path(cfg, pools, layer: int, state, marks, rounded: bool) -> float:
    """One state-space layer's inputs through what the served path keeps
    and moves a state with (module docstring, part 3). ``pools`` is the
    cache manager's ``StatePool``; ``layer`` the layer's index in its
    pools; ``state`` what ``_layer_fn`` handed back; ``marks`` = (the
    snapshot's position, the prompt's length, the sequence's).
    Returns the largest of: the outputs' difference from the
    recurrence's over its largest output; the parked snapshot's and the
    last slot's from the recurrence's states over their largest
    entries."""
    import jax.numpy as jnp

    x, dt, A, B, C, want, kept = state
    at, prompt, end = marks
    i32 = lambda v: jnp.asarray(v, jnp.int32)
    span, S = _span_fn(cfg, rounded), pools.pools[0]
    rest = pools.pools[1:]

    def spans(S, first, stop, src, dst):
        worst = 0.0
        for start in range(first, stop, SPAN):
            d, S = span(S, x, dt, A, B, C, want, i32(layer), i32(start),
                        i32(stop), i32(src if start == first else dst),
                        i32(dst))
            worst = max(worst, float(d))
        return worst, S

    lane = pools.grant()
    worst, parked = 0.0, None
    if at:
        worst, S = spans(S, 0, at, lane, lane)
        pools.pools = (S, *rest)
        parked = pools.snapshot(layer, at, lane)    # keyed by the layer
        pools.give_back(lane)
        lane = pools.grant()
        S, rest = pools.pools[0], pools.pools[1:]
    d, S = spans(S, at, prompt, parked if at else lane, lane)
    worst = max(worst, d)
    for start in range(prompt, end, STEPS):
        d, S = _steps_fn(cfg, layer, rounded)(
            S, x, dt, A, B, C, want, i32(start),
            i32(min(STEPS, end - start)), i32(lane))
        worst = max(worst, float(d))
    pools.pools = (S, *rest)
    over = lambda a: float(jnp.abs(a).max())
    reads = [worst / over(want[:end])]
    held = [(lane, kept[1])] + ([(parked, kept[0])] if at else [])
    for slot, want_S in held:
        reads.append(over(S[layer, slot].astype(jnp.float32) - want_S)
                     / over(want_S))
    pools.give_back(lane)
    return max(reads)


@functools.lru_cache(maxsize=None)
def _head_fn(cfg):
    import jax
    import jax.numpy as jnp

    def fn(x, scale, w):
        x = x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True)
                              + cfg.layer_norm_epsilon) \
            * scale.astype(jnp.float32)
        return x @ w.astype(jnp.float32)

    return jax.jit(fn)


def forward(params, cfg, prompt: list, got: list, lower: bool = False,
            fault=None):
    """One full forward pass over prompt + got. Returns (logits
    [len(got), vocab] float32 at the positions that decide ``got``,
    teacher-forced; {expert layer: its router's input [len(prompt) +
    len(got), hidden] float32}; (the served state path's largest
    difference from the recurrence, ``_state_path``, the worst
    state-space layer; the dtype the served pool holds ``S`` in))."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    if fault == "one_expert_fewer":
        cfg = dataclasses.replace(
            cfg, num_experts_per_tok=cfg.num_experts_per_tok - 1)
    seq = list(prompt) + list(got)
    T = -(-len(seq) // PAD_TO) * PAD_TO
    buf = np.zeros((T,), np.int32)
    buf[:len(seq)] = seq
    # Where the faults sit; then the state path's marks: its snapshot at
    # the last block boundary that leaves a token of the prompt to
    # compute, the prompt's end, the sequence's.
    marks = ((len(prompt) - 1) // BLOCK * BLOCK, len(prompt), len(seq))
    b = jnp.asarray((len(prompt) // BLOCK * BLOCK, marks[0], marks[2]),
                    jnp.int32)
    # Four slots: scratch, a lane's, the snapshot, the lane's that
    # resumes from it (a layer's snapshot is evicted for the next's).
    pools = _served(SERVED_POOL)(cfg, 4)
    dtype = str(pools.pools[0].dtype)
    rounded = fault == "served_state_in_bfloat16"
    router_inputs, diff = {}, 0.0
    mixers = [l for l, c in enumerate(cfg.hybrid_override_pattern)
              if c == "M"]
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(buf)].astype(jnp.float32)
        for l, (letter, p) in enumerate(zip(cfg.hybrid_override_pattern,
                                            params["layers"])):
            x, h, state = _layer_fn(cfg, letter, T, lower,
                                    None if rounded else fault)(x, p, b)
            if h is not None:
                router_inputs[l] = h[:len(seq)]
            if state is not None:
                # A pool in another dtype than stated fails by that
                # check; the update kernel takes none.
                diff = max(diff, _state_path(
                    cfg, pools, mixers.index(l), state, marks, rounded)
                    if dtype == STATE_DTYPE else float("inf"))
            del state
        rows = x[len(prompt) - 1:len(seq) - 1]
        logits = np.asarray(_head_fn(cfg)(rows, params["norm_f"],
                                          params["head"]), np.float32)
    return logits, router_inputs, (diff, dtype)


def router_agreement(params, cfg, router_inputs: dict, served_router,
                     lower: bool = False, fault=None) -> tuple:
    """(tokens whose experts ``served_router`` and the reference's
    router pick alike, tokens compared, the largest difference between
    the two routers' weights on a token whose experts they pick alike)
    over every expert layer, both on the reference's router inputs
    rounded to the served dtype. ``served_router(x, w, bias, k,
    scale)`` returns (_, experts [T, k], weights [T, k]), the signature
    of ``ray_tpu.ops.moe.route_sigmoid``; it is asked for the
    CONFIGURATION's experts a token, whatever the reference was told."""
    import jax
    import jax.numpy as jnp

    scores_in = jnp.dtype(cfg.dtype) if lower else jnp.dtype(jnp.float32)
    mine_cfg = cfg
    if fault == "one_expert_fewer":
        mine_cfg = dataclasses.replace(
            cfg, num_experts_per_tok=cfg.num_experts_per_tok - 1)
    same = total = 0
    worst = 0.0
    for l, h in router_inputs.items():
        p = params["layers"][l]
        x = h.astype(cfg.dtype)
        _, served, served_w = served_router(
            x, p["router"], p["router_bias"], cfg.num_experts_per_tok,
            cfg.routed_scaling_factor)
        with jax.default_matmul_precision("highest"):
            mine, mine_w = _route(x, p["router"], p["router_bias"],
                                  mine_cfg, scores_in)
        total += x.shape[0]
        if mine.shape != served.shape:
            continue                    # another number of experts a token
        # Both sides sorted by expert, so that weights pair up.
        so, mo = jnp.argsort(served, -1), jnp.argsort(mine, -1)
        alike = (jnp.take_along_axis(served, so, -1)
                 == jnp.take_along_axis(mine, mo, -1)).all(-1)
        diff = jnp.abs(jnp.take_along_axis(served_w, so, -1)
                       - jnp.take_along_axis(mine_w, mo, -1)).max(-1)
        same += int(alike.sum())
        worst = max(worst, float(jnp.where(alike, diff, 0.0).max()))
    return same, total, worst


def _read(params, cfg, served_router, answers, lower=False, fault=None):
    tokens, lines = [], []
    same = total = 0
    weight = state = 0.0
    for what, prompt, got in answers:
        logits, router_inputs, (diff, dtype) = forward(
            params, cfg, prompt, got, lower, fault)
        state = max(state, diff)
        m = margins(logits, got)
        s, t, w = router_agreement(params, cfg, router_inputs, served_router,
                                   lower, fault)
        tokens += m
        same, total, weight = same + s, total + t, max(weight, w)
        lines.append(f"{what}: {sum(x == 0.0 for x in m)}/{len(m)} tokens "
                     f"equal, worst margin {max(m, default=0.0):.4f}, mean "
                     f"{sum(m) / max(len(m), 1):.5f}; router alike on "
                     f"{s}/{t} tokens, weights within {w:.6f}; the "
                     f"served state path within {diff:.2e} of the "
                     f"recurrence")
    n = len(tokens)
    return {"n": n, "exact": sum(x == 0.0 for x in tokens),
            "worst": max(tokens, default=0.0),
            "mean": sum(tokens) / max(n, 1),
            "router_same": same, "router_total": total,
            "router_weight_diff": weight, "state_diff": state,
            "state_dtype": dtype if answers else None, "lines": lines}


def compare(params, cfg, served_router, answers: list,
            lower: bool = False) -> dict:
    """Every ``(what, prompt, got)`` of ``answers`` through the
    reference: the pooled readings ``token_checks`` and
    ``router_checks`` judge, and a line an answer for the log. With
    ``BENCH_NEMOTRON_CONTROLS`` set, the controls' readings follow as
    further lines (module docstring)."""
    read = _read(params, cfg, served_router, answers, lower)
    if os.environ.get("BENCH_NEMOTRON_CONTROLS") and not lower:
        controls = [("one precision lower", True, None)] + [
            (what, False, fault) for fault, what in FAULTS.items()]
        for name, low, fault in controls:
            r = _read(params, cfg, served_router, answers, low, fault)
            read["lines"].append(
                f"control, {name}: {r['exact']}/{r['n']} equal, mean "
                f"{r['mean']:.5f}, worst {r['worst']:.4f}; router "
                f"{r['router_same']}/{r['router_total']}, weights "
                f"{r['router_weight_diff']:.6f}; state "
                f"{r['state_diff']:.2e}")
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
        (total > 0 and r["state_diff"] < MAX_STATE_DIFF,
         f"the served scan, update and pool of state slots differ from the "
         f"reference's token recurrence by at most {r['state_diff']:.2e} of "
         f"the largest output or state on the compared sequences' inputs "
         f"(limit {MAX_STATE_DIFF})"),
        (r["state_dtype"] == STATE_DTYPE,
         f"the served pool of state slots holds S in {r['state_dtype']} "
         f"(the configuration states {STATE_DTYPE})"),
    ]
