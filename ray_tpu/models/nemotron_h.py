"""Nemotron-H (NVIDIA, ``model_type`` ``nemotron_h``;
https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16):
a decoder whose layers are ONE mixer each behind a pre-norm,

  x <- x + Mixer_l(RMSNorm_l(x)),   logits = RMSNorm_f(x) W_head,

the mixer chosen by the layer's letter in ``hybrid_override_pattern``:
``M`` a Mamba-2 state-space mixer, ``E`` routed experts in a latent
space (LatentMoE), ``*`` attention. The head is untied; no biases but
the convolution's. Served through the generation engine
(llm/engine.py); no loss and no train step. ``models/nemotron_h_ref.py``
is the plain float32 reference of these equations.

``*`` attention: ``q = h W_q`` [heads x head_dim], ``k, v = h W_k, h
  W_v`` [kv heads x head_dim], causal softmax(``q k^T / sqrt(head_dim)``)
  ``v``, ``W_o``. NO rotary: the family uses no positional embedding
  (its state-space layers carry position); keys are cached as projected.
``M`` Mamba-2: ``d_inner`` = heads x head width; ``conv_dim`` = d_inner
  + 2 x groups x state.
  ``[z | xBC | dt] = h W_in``;
  ``xBC <- silu(conv1d_causal(xBC))``, depthwise over ``conv_kernel``
  rows with a bias: it needs the previous ``conv_kernel - 1`` rows of
  ``xBC`` as they were BEFORE the convolution (the convolution state);
  ``[x | B | C] = xBC``; ``dt = softplus(dt + dt_bias)``; ``A =
  -exp(A_log)``, a scalar a head;
  ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T`` (a head's [head width,
  state]; head h uses group ``h // (heads / groups)``'s B and C);
  ``y_t = S_t C_t + D x_t``;
  ``y <- RMSNorm_grouped(y * silu(z))`` (a norm a group, the gate
  before the norm); ``out = y W_out``.
``E`` LatentMoE: ``s = sigmoid(h W_r)`` over all experts in float32;
  the ``num_experts_per_tok`` largest of ``s + b``; ``w =
  routed_scaling_factor x s_top / sum(s_top)`` (ops/moe.py
  ``route_sigmoid``); ``u = h W_dn`` [latent], ONE down-projection for
  all experts; ``Expert_e(u) = relu(u W1_e)^2 W2_e``, not gated; ``out =
  (sum_j w_j Expert_{e_j}(u)) W_up + Shared(h)``, ``Shared(h) = relu(h
  Ws1)^2 Ws2`` on the full hidden size.

**What a sequence keeps.** An attention layer keeps a token's keys and
values (two pools, the seam's ``kinds``); a Mamba-2 layer keeps nothing
a token and a fixed state a SEQUENCE: ``S`` [heads, head width, state]
in float32 and the last ``conv_kernel - 1`` rows of pre-convolution
``xBC`` in the served dtype (the seam's ``state``: two pools of slots).
A decode step updates the live lanes' states in place
(ops/ssm.py ``ssm_update``, a kernel that finds a lane's slot by a
prefetched table); a prefill span runs the chunked scan
(``ssd_scan``) from the state in the slot it is told to read and
leaves the state at the span's end in the lane's slot; a span from
position 0 starts from zeros. A decode step scores ONE row a lane:
a state cannot be rolled back, so the engine refuses speculation.

**Precision as served:** ``cfg.dtype`` (bfloat16) weights, activations,
keys, values and convolution state; float32 for router scores, norms,
softmaxes, ``dt``, ``exp(dt A)``, the state ``S`` and its recurrence.

**A share of the experts** (``experts_held`` from ``first_expert``), as
models/kimi_k2.py: the router scores all ``n_routed_experts``, the layer
computes its own experts' part for the tokens routed to them, takes it
up through ``W_up``, adds the shared expert and hands that partial
result on. Nothing stands in for the other chips or their exchange.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from ..ops import moe, ssm
from .laguna import _chunk_attention, _rmsnorm

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"
F32 = jnp.float32


@dataclass(frozen=True)
class NemotronHConfig:
    """Field names are the published config.json's; ``experts_held`` /
    ``first_expert`` say which routed experts this chip holds,
    ``max_seq`` is the deployment's limit and ``dtype`` what weights,
    activations, keys, values and the convolution state are held in."""
    vocab_size: int = 131072
    hidden_size: int = 4096
    num_hidden_layers: int = 88
    hybrid_override_pattern: str = (
        "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
        "EMEMEMEMEM*EMEMEMEM*EMEMEMEME")
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    mamba_num_heads: int = 128
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    use_conv_bias: bool = True
    mamba_hidden_act: str = "silu"
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    moe_latent_size: int = 1024
    moe_intermediate_size: int = 2688
    moe_shared_expert_intermediate_size: int = 5376
    n_routed_experts: int = 512
    n_shared_experts: int = 1
    num_experts_per_tok: int = 22
    routed_scaling_factor: float = 5.0
    norm_topk_prob: bool = True
    n_group: int = 1
    topk_group: int = 1
    mlp_hidden_act: str = "relu2"
    layer_norm_epsilon: float = 1e-5
    experts_held: int = 512
    first_expert: int = 0
    max_seq: int = 4864
    dtype: Any = "bfloat16"

    def __post_init__(self):
        object.__setattr__(self, "dtype", jnp.dtype(self.dtype))
        # The forms this module builds; another value is another model.
        built = {"mlp_hidden_act": "relu2", "mamba_hidden_act": "silu",
                 "norm_topk_prob": True, "n_group": 1, "topk_group": 1,
                 "use_conv_bias": True, "n_shared_experts": 1}
        for name, want in built.items():
            if getattr(self, name) != want:
                raise ValueError(f"{name}={getattr(self, name)!r}: only "
                                 f"{want!r} is built")
        if len(self.hybrid_override_pattern) != self.num_hidden_layers \
                or set(self.hybrid_override_pattern) \
                - {MAMBA, EXPERTS, ATTENTION}:
            raise ValueError("hybrid_override_pattern must give each of "
                             "the layers one of M, E and *")
        if self.mamba_num_heads % self.n_groups \
                or self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("heads do not divide into their groups")
        if not 0 <= self.first_expert <= \
                self.n_routed_experts - self.experts_held:
            raise ValueError("the held experts lie outside the routed ones")

    def layers_of(self, letter: str) -> tuple:
        return tuple(l for l, c in enumerate(self.hybrid_override_pattern)
                     if c == letter)

    @property
    def d_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    def layer_params(self, letter: str, experts: int) -> int:
        """Parameters of one layer of a kind, its pre-norm with it, with
        ``experts`` routed experts counted."""
        m = self.hidden_size
        if letter == ATTENTION:
            H, kv, d = (self.num_attention_heads, self.num_key_value_heads,
                        self.head_dim)
            return 2 * m * H * d + 2 * m * kv * d + m
        if letter == MAMBA:
            H = self.mamba_num_heads
            return (m * (self.d_inner + self.conv_dim + H)
                    + (self.conv_kernel + 1) * self.conv_dim + 3 * H
                    + self.d_inner + self.d_inner * m + m)
        lat, f = self.moe_latent_size, self.moe_intermediate_size
        return (m * self.n_routed_experts + self.n_routed_experts
                + 2 * m * lat + 2 * m * self.moe_shared_expert_intermediate_size
                + experts * 2 * lat * f + m)

    def num_params(self, experts=None, embedding: bool = True) -> int:
        """Parameters with ``experts`` routed experts a layer counted
        (default: those held here; ``n_routed_experts`` is the whole
        model, ``num_experts_per_tok`` what a token passes) and the
        embedding with them or not."""
        experts = self.experts_held if experts is None else experts
        n = (1 + embedding) * self.vocab_size * self.hidden_size \
            + self.hidden_size
        return n + sum(self.layer_params(c, experts)
                       for c in self.hybrid_override_pattern)


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

# The router's correction bias is drawn from the seed at this size (the
# published one is learned; models/kimi_k2.py has the reason).
ROUTER_BIAS_STD = 0.02
# The convolution's taps are drawn at the scale of the family's
# initialiser (1 / sqrt(conv_kernel)): at std 0.02 the convolved rows
# would be ~0.05 and the mixer's x, B and C all but zero.
CONV_STD = 0.5
# A = -exp(A_log) is drawn uniform in [1, 16], the family's initialiser.
A_RANGE = (1.0, 16.0)


def init(key, cfg: NemotronHConfig) -> dict:
    """Seeded random parameters in ``cfg.dtype`` (normal, std 0.02;
    norms 1; ``init_layer`` says what the state-space layer's own are),
    a layer at a time. A routed expert's weights depend on the key and
    the expert's GLOBAL id alone, so every share of one model holds
    slices of the same experts."""
    return {
        **_init_ends(jax.random.fold_in(key, 1 << 20), cfg),
        "layers": [init_layer(key, cfg, l)
                   for l in range(cfg.num_hidden_layers)],
    }


def _normal(key, shape, dtype, std=0.02):
    return (jax.random.normal(key, shape, F32) * std).astype(dtype)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _init_ends(key, cfg: NemotronHConfig) -> dict:
    m, V = cfg.hidden_size, cfg.vocab_size
    ke, kh = jax.random.split(key)
    return {"embed": _normal(ke, (V, m), cfg.dtype),
            "head": _normal(kh, (m, V), cfg.dtype),
            "norm_f": jnp.ones((m,), cfg.dtype)}


def init_layer(key, cfg: NemotronHConfig, l: int) -> dict:
    """Layer ``l``'s parameters, from ``fold_in(key, l)``. A Mamba-2
    layer's ``dt_bias`` is the inverse softplus of a step drawn
    log-uniform in [time_step_min, time_step_max] (floored at
    time_step_floor), ``A_log`` the log of a decay rate drawn uniform
    in ``A_RANGE``, ``D`` ones: the family's initialiser, under which a
    state neither dies nor explodes over thousands of tokens."""
    return _init_layer(jax.random.fold_in(key, l), cfg,
                       cfg.hybrid_override_pattern[l])


@functools.partial(jax.jit, static_argnames=("cfg", "letter"))
def _init_layer(key, cfg: NemotronHConfig, letter: str) -> dict:
    m, dt = cfg.hidden_size, cfg.dtype
    k = iter(jax.random.split(key, 10))
    p = {"ln": jnp.ones((m,), dt)}
    if letter == ATTENTION:
        H, kv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                    cfg.head_dim)
        p.update(wq=_normal(next(k), (m, H, d), dt),
                 wk=_normal(next(k), (m, kv, d), dt),
                 wv=_normal(next(k), (m, kv, d), dt),
                 wo=_normal(next(k), (H, d, m), dt))
    elif letter == MAMBA:
        p.update(mamba_params(k, cfg))
    else:
        E, lat, f = (cfg.n_routed_experts, cfg.moe_latent_size,
                     cfg.moe_intermediate_size)
        fs = cfg.moe_shared_expert_intermediate_size
        k1, k2 = next(k), next(k)
        held = cfg.first_expert + jnp.arange(cfg.experts_held)
        p.update(
            router=_normal(next(k), (m, E), dt),
            router_bias=_normal(next(k), (E,), F32, ROUTER_BIAS_STD),
            w_dn=_normal(next(k), (m, lat), dt),
            w_up=_normal(next(k), (lat, m), dt),
            w1=jax.vmap(lambda e: _normal(
                jax.random.fold_in(k1, e), (lat, f), dt))(held),
            w2=jax.vmap(lambda e: _normal(
                jax.random.fold_in(k2, e), (f, lat), dt))(held),
            s1=_normal(next(k), (m, fs), dt),
            s2=_normal(next(k), (fs, m), dt))
    return p


def mamba_params(k, cfg) -> dict:
    """A Mamba-2 mixer's own parameters from the keys ``k`` yields
    (six of them), by the family's initialiser (``init_layer``). ``cfg``
    is any configuration with the names this module reads a Mamba-2
    mixer by (models/granite_hybrid.py's has them too)."""
    m, dt, H = cfg.hidden_size, cfg.dtype, cfg.mamba_num_heads
    step = jnp.exp(jax.random.uniform(
        next(k), (H,), F32, jnp.log(cfg.time_step_min),
        jnp.log(cfg.time_step_max)))
    step = jnp.maximum(step, cfg.time_step_floor)
    return dict(
        w_in=_normal(next(k), (m, cfg.d_inner + cfg.conv_dim + H), dt),
        conv_w=_normal(next(k), (cfg.conv_kernel, cfg.conv_dim), dt,
                       CONV_STD),
        conv_b=_normal(next(k), (cfg.conv_dim,), dt),
        dt_bias=step + jnp.log(-jnp.expm1(-step)),
        A_log=jnp.log(jax.random.uniform(next(k), (H,), F32, *A_RANGE)),
        D=jnp.ones((H,), F32),
        norm=jnp.ones((cfg.d_inner,), dt),
        w_out=_normal(next(k), (cfg.d_inner, m), dt))


# ---------------------------------------------------------------------------
# The mixers
# ---------------------------------------------------------------------------


def _relu2(h, w1, w2):
    act = jnp.square(jax.nn.relu(jnp.dot(h, w1).astype(F32)))
    return jnp.dot(act.astype(h.dtype), w2)


def _experts(h, p, cfg: NemotronHConfig, program: str):
    """h [T, m] -> (out [T, m], the held experts' tokens [held]). The
    grouped products' kernel is ``moe_experts_<program>`` on a device
    trace (``_r<rows>`` behind it where an expert of ALL
    ``n_routed_experts``, which the call is told, expects 32 rows or
    more: ops/moe.py ``tile_rows``); they run at the latent width."""
    with jax.named_scope("moe_route"):
        _, experts, weights = moe.route_sigmoid(
            h, p["router"], p["router_bias"], cfg.num_experts_per_tok,
            cfg.routed_scaling_factor)
    with jax.named_scope("moe_experts"):
        y, sizes = moe.routed_experts(
            jnp.dot(h, p["w_dn"]), experts, weights, p["w1"], p["w2"],
            first=cfg.first_expert, n_experts=cfg.n_routed_experts,
            name=f"moe_experts_{program}", activation="relu2")
    return jnp.dot(y, p["w_up"]) + _relu2(h, p["s1"], p["s2"]), sizes


def _mamba_in(h, p, cfg: NemotronHConfig):
    """h [..., m] -> (z [..., d_inner], xBC before its convolution
    [..., conv_dim], dt before its softplus [..., heads])."""
    proj = jnp.dot(h, p["w_in"])
    a, b = cfg.d_inner, cfg.d_inner + cfg.conv_dim
    return proj[..., :a], proj[..., a:b], proj[..., b:]


def _convolved(rows, p):
    """The convolution's output on its window: ``rows`` [..., kernel,
    conv_dim], oldest first -> silu(sum_k rows_k w_k + bias) [...,
    conv_dim]."""
    out = (rows.astype(F32) * p["conv_w"].astype(F32)).sum(-2) \
        + p["conv_b"].astype(F32)
    return jax.nn.silu(out).astype(rows.dtype)


def _ssm_inputs(xBC, dt, p, cfg: NemotronHConfig):
    """The convolved xBC [..., conv_dim] and raw dt [..., H] -> (x
    [..., H, P], B, C [..., G, N] in the served dtype; dt [..., H]
    after its softplus and A [H], float32)."""
    H, P, G, N = (cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.n_groups,
                  cfg.ssm_state_size)
    lead = xBC.shape[:-1]
    x = xBC[..., :H * P].reshape(*lead, H, P)
    B = xBC[..., H * P:H * P + G * N].reshape(*lead, G, N)
    C = xBC[..., H * P + G * N:].reshape(*lead, G, N)
    dt = jax.nn.softplus(dt.astype(F32) + p["dt_bias"])
    return x, B, C, dt, -jnp.exp(p["A_log"])


def _mamba_out(y, x, z, p, cfg: NemotronHConfig):
    """y [..., H, P] float32 (``S C``), x [..., H, P], z [..., d_inner]
    -> the mixer's output [..., m]: the D skip, the gate, the grouped
    norm, ``W_out``."""
    G = cfg.n_groups
    y = y + p["D"][:, None] * x.astype(F32)
    y = y.reshape(z.shape) * jax.nn.silu(z.astype(F32))
    g = y.reshape(*y.shape[:-1], G, -1)
    g = g * jax.lax.rsqrt((g * g).mean(-1, keepdims=True)
                          + cfg.layer_norm_epsilon)
    y = (g.reshape(y.shape) * p["norm"].astype(F32)).astype(z.dtype)
    return jnp.dot(y, p["w_out"])


def attention_step(h, p, cfg, li: int, k_pool, v_pool, lanes, scale=None):
    """The attention mixer of a decode step: h [B, 1, m] after the
    pre-norm, the layer's index ``li`` in the pools, ``lanes`` =
    (block tables, context lens, q lens, window starts, slot blocks,
    slot offsets) -> (out [B, 1, m], k_pool, v_pool) with the lanes'
    new rows written. No rotary. ``scale`` is the softmax scale where
    it is not ``head_dim ** -0.5`` (models/granite_hybrid.py, which
    calls the four mixer functions here with its own configuration)."""
    from ..ops.pallas.paged_fetch import paged_attention_stored

    block_tables, context_lens, q_lens, starts, slot_blocks, slot_offsets \
        = lanes
    B = h.shape[0]
    kv, d = cfg.num_key_value_heads, cfg.head_dim
    qh = jnp.einsum("brm,mhd->brhd", h, p["wq"])
    k = jnp.einsum("brm,mhd->brhd", h, p["wk"]).reshape(B, 1, kv * d)
    v = jnp.einsum("brm,mhd->brhd", h, p["wv"]).reshape(B, 1, kv * d)
    k_pool = k_pool.at[li, slot_blocks, slot_offsets].set(k)
    v_pool = v_pool.at[li, slot_blocks, slot_offsets].set(v)
    H = qh.shape[2]
    with jax.named_scope("attn_full"):
        o = paged_attention_stored(
            qh.reshape(B, 1, kv, H // kv, d), k_pool, v_pool, li,
            block_tables, context_lens, q_lens, starts, name="attn_full",
            scale=scale)
    out = jnp.einsum("brhd,hdm->brm", o.reshape(B, 1, H, d), p["wo"])
    return out, k_pool, v_pool


def mamba_step(h, p, cfg, li: int, slots, s_pool, c_pool):
    """The Mamba-2 mixer of a decode step: h [B, 1, m] -> (out [B, 1,
    m], s_pool, c_pool) with the lanes' slots of layer ``li`` moved on
    by one token, in place."""
    z, xBC, dt = _mamba_in(h[:, 0], p, cfg)
    rows = jnp.concatenate([c_pool[li, slots], xBC[:, None]], 1)
    c_pool = c_pool.at[li, slots].set(rows[:, 1:])
    xs, Bs, Cs, dt, A = _ssm_inputs(_convolved(rows, p), dt, p, cfg)
    with jax.named_scope("ssm_update"):
        y, s_pool = ssm.ssm_update(
            s_pool, li, slots, jnp.exp(dt * A),
            dt[..., None] * xs.astype(F32), Bs, Cs)
    return _mamba_out(y, xs, z, p, cfg)[:, None], s_pool, c_pool


def attention_chunk(h, p, cfg, li: int, k_pool, v_pool, block_table,
                    ctx_len, scale=None):
    """The attention mixer of a prefill span: h [1, n, m] against the
    context the pools hold behind ``block_table`` -> (out [1, n, m], the
    span's keys and values [1, n, kv, d], which the caller writes after
    the last layer)."""
    kv, d = cfg.num_key_value_heads, cfg.head_dim
    slots = block_table.shape[0] * k_pool.shape[2]
    qh = jnp.einsum("brm,mhd->brhd", h, p["wq"])
    k = jnp.einsum("brm,mhd->brhd", h, p["wk"])
    v = jnp.einsum("brm,mhd->brhd", h, p["wv"])
    k_ctx = k_pool[li, block_table].reshape(slots, kv, d)
    v_ctx = v_pool[li, block_table].reshape(slots, kv, d)
    with jax.named_scope("attn_full"):
        o = _chunk_attention(qh[0], k[0], v[0], k_ctx, v_ctx, ctx_len, 0,
                             None, scale)
    return jnp.einsum("brhd,hdm->brm", o[None], p["wo"]), k, v


def mamba_chunk(h, p, cfg, li: int, span, s_pool, c_pool):
    """The Mamba-2 mixer of a prefill span: h [1, n, m]; ``span`` = (slot
    read, slot written, last real row, the real rows' mask [n, 1],
    whether the span starts a sequence) -> (out [1, n, m], s_pool,
    c_pool) with the state and the convolution rows at the span's end
    in the slot written."""
    src, dst, last, real, fresh = span
    n, K = h.shape[1], cfg.conv_kernel
    z, xBC, dt = _mamba_in(h[0], p, cfg)
    prev = jnp.where(fresh, 0, c_pool[li, src])          # [K-1, conv]
    rows = jnp.concatenate([prev, xBC])                  # [K-1+n, conv]
    c_pool = c_pool.at[li, dst].set(
        jax.lax.dynamic_slice_in_dim(rows, last + 1, K - 1))
    window = jnp.stack([rows[i:i + n] for i in range(K)], 1)
    xs, Bs, Cs, dt, A = _ssm_inputs(_convolved(window, p), dt, p, cfg)
    with jax.named_scope("ssm_scan"):
        y, S = ssm.ssd_scan(
            xs, jnp.where(real, dt, 0.0), A, Bs, Cs,
            jnp.where(fresh, 0.0, s_pool[li, src]), cfg.chunk_size)
    s_pool = s_pool.at[li, dst].set(S)
    return _mamba_out(y, xs, z, p, cfg)[None], s_pool, c_pool


def _head(params, x, cfg: NemotronHConfig):
    x = _rmsnorm(x, params["norm_f"], cfg.layer_norm_epsilon)
    return jnp.einsum("brm,mv->brv", x, params["head"])


def _pool_index(kinds) -> list:
    """layer -> its index among the layers of its kind (``kinds``: a
    letter, or a mixer's name, a layer): where its rows or its state lie
    in the pools."""
    seen, out = {}, []
    for c in kinds:
        out.append(seen.get(c, 0))
        seen[c] = out[-1] + 1
    return out


COUNTERS = ("moe_experts_hit", "moe_load_max_x1000", "moe_held_rows",
            "kv_pages_in_runs_x1000")


def _counters(sizes, rows: int, n_experts: int, top_k: int, q: int,
              in_runs):
    """The step's counter rows [4, q] int32 (``COUNTERS``), as
    models/kimi_k2.py counts them: held experts that got a token (an
    expert layer's mean), 1000 x the busiest held expert's tokens over
    the DEPLOYMENT's mean an expert (the worst layer: ``rows`` tokens,
    ``top_k`` of ``n_experts`` each), the assignments that fell on the
    held experts (a layer's mean), and 1000 x the share of the batch's
    live cache pages the paged kernel fetches in whole runs."""
    counts = jnp.zeros((3,), jnp.int32)
    if sizes:
        s = jnp.stack(sizes)                              # [layers, held]
        counts = jnp.stack([
            (s > 0).sum() // len(sizes),
            (s.max() * (1000 * n_experts)) // (rows * top_k),
            s.sum() // len(sizes)])
    return jnp.broadcast_to(
        jnp.append(counts, in_runs)[:, None],
        (len(COUNTERS), q)).astype(jnp.int32)


# ---------------------------------------------------------------------------
# The two served programs
# ---------------------------------------------------------------------------


def forward_step(params, packed, k_pool, v_pool, s_pool, c_pool, *, q: int,
                 cfg: NemotronHConfig, firsts=None):
    """One decode step (models/gpt.py ``forward_step``'s contract), ONE
    row a lane, over the attention layers' pools of keys and values
    ``[attention layers, num_blocks, block_size, kv_heads * head_dim]``
    and the state-space layers' pools of slots, ``s_pool`` [M layers,
    slots, heads, head width, state] float32 and ``c_pool`` [M layers,
    slots, conv_kernel - 1, conv_dim]: a lane's slot is a column of
    ``packed`` (``step_state_slots``; 0, the scratch slot, for a padded
    lane). Every state-space layer moves its lanes' states in place.

    Returns (logits [b, 1, vocab], ids [b + 4, 1] int32, k_pool, v_pool,
    s_pool, c_pool): rows b on of ``ids`` are ``COUNTERS``."""
    from ..ops.pallas.paged_fetch import kv_pages_in_runs_x1000
    from . import step_state_slots, unpack_step

    if q != 1:
        raise ValueError("a state is moved one token a step: q must be 1")
    (tokens, positions, block_tables, context_lens, q_lens, slot_blocks,
     slot_offsets, _) = unpack_step(packed, q, firsts=firsts, state=True)
    slots = step_state_slots(packed, q)
    B = tokens.shape[0]
    lanes = (block_tables, context_lens, q_lens,
             jnp.zeros_like(context_lens), slot_blocks, slot_offsets)
    eps = cfg.layer_norm_epsilon
    x = params["embed"][tokens]                          # [B, 1, m]
    sizes = []
    for li, p in zip(_pool_index(cfg.hybrid_override_pattern),
                     params["layers"]):
        h = _rmsnorm(x, p["ln"], eps)
        if "wq" in p:
            out, k_pool, v_pool = attention_step(h, p, cfg, li, k_pool,
                                                 v_pool, lanes)
        elif "w_in" in p:
            out, s_pool, c_pool = mamba_step(h, p, cfg, li, slots, s_pool,
                                             c_pool)
        else:
            out, s = _experts(h[:, 0], p, cfg, "decode")
            out = out[:, None]
            sizes.append(s)
        x = x + out
    logits = _head(params, x, cfg)
    ids = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    ids = jnp.concatenate([ids, _counters(
        sizes, B, cfg.n_routed_experts, cfg.num_experts_per_tok, 1,
        kv_pages_in_runs_x1000(
            block_tables, context_lens, k_pool, v_pool,
            score_rows=cfg.num_attention_heads))])
    return logits, ids, k_pool, v_pool, s_pool, c_pool


def forward_prefill_chunk(params, tokens, k_pool, v_pool, table, s_pool,
                          c_pool, cfg: NemotronHConfig):
    """One span of a prompt as one program (models/gpt.py
    ``forward_prefill_chunk``'s contract): ``tokens`` [1, n], ``table`` =
    ``[block table | destination | ctx_len | last | slot read | slot
    written]``. An attention layer reads the pools as they came in and
    the span's keys and values are written after the last layer; a
    state-space layer takes its initial state from the slot read (zeros
    where ``ctx_len`` is 0, whatever the slot holds), runs the chunked
    scan over the span's real rows (rows past ``last`` have ``dt`` 0 and
    move nothing) and writes the state at the span's end, with the last
    ``conv_kernel - 1`` real rows of pre-convolution xBC, to the slot
    written.

    Returns (row [vocab], id, k_pool, v_pool, s_pool, c_pool)."""
    from ..llm.kv_cache import scatter_span
    from . import unpack_span

    n = tokens.shape[1]
    bs = k_pool.shape[2]
    block_table, dest, ctx_len, last, src, dst = unpack_span(
        table, n, bs, extra=2)
    eps = cfg.layer_norm_epsilon
    span = (src, dst, last, (jnp.arange(n) <= last)[:, None], ctx_len == 0)
    x = params["embed"][tokens]                          # [1, n, m]
    new_k, new_v = [], []
    for li, p in zip(_pool_index(cfg.hybrid_override_pattern),
                     params["layers"]):
        h = _rmsnorm(x, p["ln"], eps)
        if "wq" in p:
            out, k, v = attention_chunk(h, p, cfg, li, k_pool, v_pool,
                                        block_table, ctx_len)
            new_k.append(k)
            new_v.append(v)
        elif "w_in" in p:
            out, s_pool, c_pool = mamba_chunk(h, p, cfg, li, span, s_pool,
                                              c_pool)
        else:
            out, _ = _experts(h[0], p, cfg, "chunk")
            out = out[None]
        x = x + out
    k_pool, v_pool = scatter_span(
        (k_pool, v_pool), (jnp.stack(new_k)[:, 0], jnp.stack(new_v)[:, 0]),
        dest, last + 1)
    row = _head(params, jax.lax.dynamic_slice_in_dim(x, last, 1, axis=1),
                cfg)[0, 0]
    return (row, jnp.argmax(row).astype(jnp.int32), k_pool, v_pool, s_pool,
            c_pool)


# ---------------------------------------------------------------------------
# The serving seam
# ---------------------------------------------------------------------------


def cost_shape(cfg: NemotronHConfig) -> dict:
    """The cost description util/perfmodel.py prices steps from. A
    token passes every layer's weights outside the routed experts, of
    its ``num_experts_per_tok`` experts the share held here, and the
    head; a step streams the always-read weights and the held experts
    its rows are expected to hit. A context token costs a decode row
    ``4 x heads x head_dim`` operations an ATTENTION layer and two rows
    of keys and values; a state-space layer costs a row the same at any
    context (``state_ops_per_row``: the update and ``S C``, 4 x heads x
    head width x state a layer) and moves its whole state in and out a
    lane a step (``state_bytes_per_seq``, counted twice by the
    pricing); a chunk's row pays the chunked scan
    (``scan_ops_per_row``: its block's scores and masked product, and
    its part of the state's hand-over)."""
    m, E, k, held = (cfg.hidden_size, cfg.n_routed_experts,
                     cfg.num_experts_per_tok, cfg.experts_held)
    H, P, G, N = (cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.n_groups,
                  cfg.ssm_state_size)
    expert = 2 * cfg.moe_latent_size * cfg.moe_intermediate_size
    n_attn, n_mamba, n_exp = (len(cfg.layers_of(c))
                              for c in (ATTENTION, MAMBA, EXPERTS))
    always = cfg.num_params(experts=0)
    active = (cfg.num_params(experts=0, embedding=False)
              + n_exp * expert * k * held / E)
    attn = 4.0 * n_attn * cfg.num_attention_heads * cfg.head_dim

    def streamed(rows):
        hit = held * (1.0 - (1.0 - k / E) ** max(rows, 0))
        return always + n_exp * hit * expert

    return {
        "matmul_weights": active,
        "head_weights": cfg.vocab_size * m,
        "attn_per_ctx": attn,
        "chunk_attn_per_ctx": attn,
        "chunk_ctx_ops": 0.0,
        "attn_windows": (),
        "num_params": cfg.num_params(),
        "streamed_params": streamed,
        "param_bytes": cfg.dtype.itemsize,
        "kv_bytes_per_token": 2 * n_attn * cfg.num_key_value_heads
        * cfg.head_dim,
        "state_ops_per_row": 4.0 * n_mamba * H * P * N,
        "scan_ops_per_row": n_mamba * (
            2.0 * cfg.chunk_size * (G * N + H * P) + 4.0 * H * P * N),
        "state_bytes_per_seq": state_kind(cfg).slot_bytes,
        "m": m, "L": cfg.num_hidden_layers,
    }


def state_kind(cfg: NemotronHConfig):
    """What a sequence keeps in the state-space layers: ``S`` in
    float32 and the convolution's last rows in the served dtype."""
    from . import StateKind

    return StateKind(cfg.layers_of(MAMBA), (
        ((cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.ssm_state_size),
         jnp.dtype(F32)),
        ((cfg.conv_kernel - 1, cfg.conv_dim), cfg.dtype)))


def serving(cfg: NemotronHConfig):
    from . import Serving, keys_and_values

    full = keys_and_values("full", cfg.layers_of(ATTENTION),
                           cfg.num_key_value_heads, cfg.head_dim, None,
                           cfg.dtype)
    return Serving(init=init, step=forward_step,
                   chunk=forward_prefill_chunk, kinds=(full,),
                   cost=cost_shape(cfg), max_seq=cfg.max_seq,
                   vocab_size=cfg.vocab_size, counters=COUNTERS,
                   state=state_kind(cfg))
