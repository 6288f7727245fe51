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
``M`` Mamba-2: the mixer of models/mamba2.py (its docstring has the
  equations), which this configuration describes to it by ``mamba``.
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
A decode step updates the live lanes' states in place; a prefill span
runs the chunked scan from the state in the slot it is told to read and
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

from ..ops import moe
from ..ops.pallas.paged_fetch import kv_pages_in_runs_x1000
from .layers import (COUNTERS, ROUTER_BIAS_STD, attention_chunk,
                     attention_params, attention_step, counters, head,
                     held_experts, init_ends, normal, pool_index, rmsnorm)
from .mamba2 import Mamba2, mamba_chunk, mamba_params, mamba_step
from .seam import (Serving, StateKind, keys_and_values, scatter_span,
                   step_state_slots, unpack_span, unpack_step)

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
    def mamba(self) -> Mamba2:
        """This family's mixer, as models/mamba2.py reads one."""
        return Mamba2(
            hidden_size=self.hidden_size, heads=self.mamba_num_heads,
            head_dim=self.mamba_head_dim, groups=self.n_groups,
            state=self.ssm_state_size, conv_kernel=self.conv_kernel,
            chunk_size=self.chunk_size, eps=self.layer_norm_epsilon,
            dtype=self.dtype,
            time_step=(self.time_step_min, self.time_step_max,
                       self.time_step_floor))

    # Read by name in models/nemotron_h_ref.py and benchmark/.
    d_inner = property(lambda self: self.mamba.d_inner)
    conv_dim = property(lambda self: self.mamba.conv_dim)

    def layer_params(self, letter: str, experts: int) -> int:
        """Parameters of one layer of a kind, its pre-norm with it, with
        ``experts`` routed experts counted."""
        m = self.hidden_size
        if letter == ATTENTION:
            H, kv, d = (self.num_attention_heads, self.num_key_value_heads,
                        self.head_dim)
            return 2 * m * H * d + 2 * m * kv * d + m
        if letter == MAMBA:
            return self.mamba.num_params + m
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


def init(key, cfg: NemotronHConfig) -> dict:
    """Seeded random parameters in ``cfg.dtype`` (normal, std 0.02;
    norms 1; a state-space layer's own by models/mamba2.py
    ``mamba_params``), a layer at a time, a routed expert's by its
    GLOBAL id (models/layers.py ``held_experts``)."""
    return {
        **init_ends(jax.random.fold_in(key, 1 << 20), cfg),
        "layers": [init_layer(key, cfg, l)
                   for l in range(cfg.num_hidden_layers)],
    }


def init_layer(key, cfg: NemotronHConfig, l: int) -> dict:
    """Layer ``l``'s parameters, from ``fold_in(key, l)``."""
    return _init_layer(jax.random.fold_in(key, l), cfg,
                       cfg.hybrid_override_pattern[l])


@functools.partial(jax.jit, static_argnames=("cfg", "letter"))
def _init_layer(key, cfg: NemotronHConfig, letter: str) -> dict:
    m, dt = cfg.hidden_size, cfg.dtype
    k = iter(jax.random.split(key, 10))
    p = {"ln": jnp.ones((m,), dt)}
    if letter == ATTENTION:
        p.update(attention_params(k, m, cfg.num_attention_heads,
                                  cfg.num_key_value_heads, cfg.head_dim, dt))
    elif letter == MAMBA:
        p.update(mamba_params(k, cfg.mamba))
    else:
        E, lat, f = (cfg.n_routed_experts, cfg.moe_latent_size,
                     cfg.moe_intermediate_size)
        fs = cfg.moe_shared_expert_intermediate_size
        k1, k2 = next(k), next(k)
        share = (cfg.first_expert, cfg.experts_held)
        p.update(
            router=normal(next(k), (m, E), dt),
            router_bias=normal(next(k), (E,), F32, ROUTER_BIAS_STD),
            w_dn=normal(next(k), (m, lat), dt),
            w_up=normal(next(k), (lat, m), dt),
            w1=held_experts(k1, *share, (lat, f), dt),
            w2=held_experts(k2, *share, (f, lat), dt),
            s1=normal(next(k), (m, fs), dt),
            s2=normal(next(k), (fs, m), dt))
    return p


# ---------------------------------------------------------------------------
# The expert mixer (models/layers.py and mamba2.py have the other two)
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
    if q != 1:
        raise ValueError("a state is moved one token a step: q must be 1")
    (tokens, positions, block_tables, context_lens, q_lens, slot_blocks,
     slot_offsets, _) = unpack_step(packed, q, firsts=firsts, state=True)
    slots = step_state_slots(packed, q)
    B = tokens.shape[0]
    lanes = (block_tables, context_lens, q_lens,
             jnp.zeros_like(context_lens), slot_blocks, slot_offsets)
    eps, mx = cfg.layer_norm_epsilon, cfg.mamba
    x = params["embed"][tokens]                          # [B, 1, m]
    sizes = []
    for li, p in zip(pool_index(cfg.hybrid_override_pattern),
                     params["layers"]):
        h = rmsnorm(x, p["ln"], eps)
        if "wq" in p:
            out, k_pool, v_pool = attention_step(h, p, li, k_pool, v_pool,
                                                 lanes)
        elif "w_in" in p:
            out, s_pool, c_pool = mamba_step(h, p, mx, li, slots, s_pool,
                                             c_pool)
        else:
            out, s = _experts(h[:, 0], p, cfg, "decode")
            out = out[:, None]
            sizes.append(s)
        x = x + out
    logits = head(params, x, eps)
    ids = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    ids = jnp.concatenate([ids, counters(
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
    n = tokens.shape[1]
    bs = k_pool.shape[2]
    block_table, dest, ctx_len, last, src, dst = unpack_span(
        table, n, bs, extra=2)
    eps, mx = cfg.layer_norm_epsilon, cfg.mamba
    span = (src, dst, last, (jnp.arange(n) <= last)[:, None], ctx_len == 0)
    x = params["embed"][tokens]                          # [1, n, m]
    new_k, new_v = [], []
    for li, p in zip(pool_index(cfg.hybrid_override_pattern),
                     params["layers"]):
        h = rmsnorm(x, p["ln"], eps)
        if "wq" in p:
            out, k, v = attention_chunk(h, p, li, k_pool, v_pool,
                                        block_table, ctx_len)
            new_k.append(k)
            new_v.append(v)
        elif "w_in" in p:
            out, s_pool, c_pool = mamba_chunk(h, p, mx, li, span, s_pool,
                                              c_pool)
        else:
            out, _ = _experts(h[0], p, cfg, "chunk")
            out = out[None]
        x = x + out
    k_pool, v_pool = scatter_span(
        (k_pool, v_pool), (jnp.stack(new_k)[:, 0], jnp.stack(new_v)[:, 0]),
        dest, last + 1)
    row = head(params, jax.lax.dynamic_slice_in_dim(x, last, 1, axis=1),
               eps)[0, 0]
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
    context and a chunk's row pays the chunked scan (models/mamba2.py
    ``Mamba2.cost``), and it moves its whole state in and out a lane a
    step (``state_bytes_per_seq``, counted twice by the pricing)."""
    m, E, k, held = (cfg.hidden_size, cfg.n_routed_experts,
                     cfg.num_experts_per_tok, cfg.experts_held)
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
        **cfg.mamba.cost(n_mamba),
        "state_bytes_per_seq": state_kind(cfg).slot_bytes,
        "m": m, "L": cfg.num_hidden_layers,
    }


def state_kind(cfg: NemotronHConfig):
    """What a sequence keeps in the state-space layers."""
    return StateKind(cfg.layers_of(MAMBA), cfg.mamba.state_parts)


def serving(cfg: NemotronHConfig):
    full = keys_and_values("full", cfg.layers_of(ATTENTION),
                           cfg.num_key_value_heads, cfg.head_dim, None,
                           cfg.dtype)
    return Serving(init=init, step=forward_step,
                   chunk=forward_prefill_chunk, kinds=(full,),
                   cost=cost_shape(cfg), max_seq=cfg.max_seq,
                   vocab_size=cfg.vocab_size, counters=COUNTERS,
                   state=state_kind(cfg))
