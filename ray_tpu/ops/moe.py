"""Routed experts with no token dropped: a softmax router over ALL the
experts, the top-k of them a token, and one grouped product over the
experts that got tokens.

How a call is cut (static shapes throughout, XLA's requirement, and no
capacity: the bound below is the worst case, so nothing is ever
dropped):

  route     ``x @ wg`` in float32, softmax over every expert, the k
            largest, renormalised to sum to ``scale``. ``route_sigmoid``
            is the other scoring served: a sigmoid an expert, chosen by
            score plus a learned bias, weighed by the score without it.
  plan      a counting sort of the T*k assignments by expert
            (``dispatch_plan``): each expert's rows are padded to whole
            tiles of ``TILE_ROWS``, so a tile belongs to ONE expert. The
            row buffer is ``T*k + E*(TILE_ROWS-1)`` rows, rounded up:
            what the assignments need if every expert gets a ragged
            tail. Tiles past the used ones are skipped.
  product   ``grouped_matmul``: a Pallas kernel (``moe_experts...`` on
            a device trace) that walks the row tiles, takes each tile's
            expert from a scalar-prefetched table and multiplies the
            tile by that expert's weights. The pipeline fetches a
            weight block only when its index changes, so an expert's
            weights are read once however many tiles it has, an expert
            with no token is never read, and the arithmetic is that of
            the assignments (plus padding rows), not of E dense experts.
            On the CPU the Pallas interpreter runs the same kernel.
  combine   each token gathers its k rows back, weighted, in float32.

Experts are SwiGLU: ``w1`` holds gate and up side by side,
``[E, d_model, 2 * d_ff]``, and ``w2`` is ``[E, d_ff, d_model]``; the
router's weight multiplies an expert's OUTPUT. The other expert served
is NOT gated (``routed_experts(..., activation="relu2")``):
``relu(x w1)^2 w2`` with ``w1`` ``[E, d_model, d_ff]``.

Expert parallelism (``ep_axis``, inside shard_map): a shard holds
``E / ep`` of the experts, routes over all E, and computes the part of
the result its own experts give for every token of its ep group
(tokens all-gathered over ``ep``, partial results summed and scattered
back): the layer is told which experts it holds, and what the others
add is the other shards'. models/laguna.py serves this layer on one
chip with every expert local; models/kimi_k2.py serves one chip's
share of it (``routed_experts(..., first=)`` with 12 of 384 experts),
as models/nemotron_h.py does with not-gated experts in a latent space
(64 of 512, 22 a token).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

HIGHEST = jax.lax.Precision.HIGHEST
# Rows of a tile of the grouped product: the sublane tile of a 16-bit
# row buffer, the smallest Mosaic takes unpadded. A decode step gives an
# expert ~2 rows, so a larger tile only adds padding rows there; no
# second value has been measured.
TILE_ROWS = 16


@dataclass(frozen=True)
class MoEConfig:
    d_model: int
    d_ff: int
    n_experts: int
    k: int = 2                    # experts per token
    scale: float = 1.0            # what a token's chosen weights sum to
    dtype: object = jnp.float32


def moe_init(key, cfg: MoEConfig):
    """Router + expert FFN params. Experts are a leading dim so the whole
    expert bank is one tensor (shardable over ep)."""
    kg, k1, k2 = jax.random.split(key, 3)
    scale_in = cfg.d_model ** -0.5
    scale_hid = cfg.d_ff ** -0.5
    return {
        "wg": (jax.random.normal(kg, (cfg.d_model, cfg.n_experts)) *
               scale_in).astype(cfg.dtype),
        "w1": (jax.random.normal(
            k1, (cfg.n_experts, cfg.d_model, 2 * cfg.d_ff)) *
            scale_in).astype(cfg.dtype),
        "w2": (jax.random.normal(k2, (cfg.n_experts, cfg.d_ff, cfg.d_model)) *
               scale_hid).astype(cfg.dtype),
    }


def route(x, wg, k: int, scale: float = 1.0):
    """x [T, d] -> (probs [T, E] float32, experts [T, k] int32, weights
    [T, k] float32): softmax over every expert in float32, the k
    largest, renormalised to sum to ``scale``."""
    logits = jnp.dot(x.astype(jnp.float32), wg.astype(jnp.float32),
                     precision=HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    top, experts = jax.lax.top_k(probs, k)
    weights = scale * top / top.sum(-1, keepdims=True)
    return probs, experts.astype(jnp.int32), weights


def route_sigmoid(x, wg, bias, k: int, scale: float = 1.0):
    """The router that scores with a sigmoid and chooses with a learned
    bias (DeepSeek-V3's ``noaux_tc`` with one group): x [T, d] ->
    (scores [T, E] float32, experts [T, k] int32, weights [T, k]
    float32). ``s = sigmoid(x @ wg)`` in float32; the k largest of
    ``s + bias`` are chosen; their weights are their ``s`` WITHOUT the
    bias, renormalised to sum to ``scale``."""
    logits = jnp.dot(x.astype(jnp.float32), wg.astype(jnp.float32),
                     precision=HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, experts = jax.lax.top_k(scores + bias.astype(jnp.float32), k)
    top = jnp.take_along_axis(scores, experts, axis=-1)
    weights = scale * top / top.sum(-1, keepdims=True)
    return scores, experts.astype(jnp.int32), weights


def plan_rows(n_assignments: int, n_experts: int) -> int:
    """Rows of the tile-aligned buffer: the assignments plus a ragged
    tail an expert, in whole tiles. A bound, not a capacity."""
    rows = n_assignments + n_experts * (TILE_ROWS - 1)
    return -(-rows // TILE_ROWS) * TILE_ROWS


def dispatch_plan(experts, n_experts: int, first=0):
    """Counting sort of the assignments ``experts`` [T, k] by expert,
    for the ``n_experts`` experts held here (global ids ``first`` ..
    ``first + n_experts - 1``; an assignment to another expert is not
    ours and gets no row).

    Returns ``(sizes [E], dest [T*k], src [M], tile_expert [M/tm],
    n_used)``: tokens a held expert got; the buffer row of each
    assignment (M where it is not held: out of range); the token each
    buffer row copies (padding rows copy token 0: finite, never read
    back); the expert of each row tile; and how many tiles hold rows."""
    T, k = experts.shape
    A, E, tm = T * k, n_experts, TILE_ROWS
    M = plan_rows(A, E)
    local = experts.reshape(A) - first
    onehot = local[:, None] == jnp.arange(E, dtype=jnp.int32)[None, :]
    held = onehot.any(axis=1)
    counts = onehot.astype(jnp.int32)
    sizes = counts.sum(axis=0)
    rank = (jnp.cumsum(counts, axis=0) * counts).sum(axis=1) - 1
    padded = -(-sizes // tm) * tm           # each expert in whole tiles
    ends = jnp.cumsum(padded)
    starts = ends - padded
    dest = jnp.where(held, starts[jnp.clip(local, 0, E - 1)] + rank, M)
    src = jnp.zeros((M,), jnp.int32).at[dest].set(
        jnp.arange(A, dtype=jnp.int32) // k, mode="drop")
    n_used = ends[-1] // tm
    tile_start = jnp.arange(M // tm, dtype=jnp.int32) * tm
    # A tile past the used ones names the last used tile's expert again,
    # so the kernel's pipeline fetches nothing for it.
    tile_expert = jnp.searchsorted(
        ends, jnp.minimum(tile_start, jnp.maximum(ends[-1] - 1, 0)),
        side="right").astype(jnp.int32)
    return sizes, dest, src, jnp.minimum(tile_expert, E - 1), n_used


def _tile_cols(k: int, n: int, itemsize: int) -> int:
    """Columns of a weight block: about 2 MiB of one expert's weights
    (double-buffered by the pipeline, well inside v5e's 16 MiB of
    scoped VMEM), a multiple of 128 lanes that divides n."""
    want = max(128, (2 * 1024 * 1024) // (k * itemsize) // 128 * 128)
    if n <= want or n % 128:
        return n
    tn = want
    while n % tn:
        tn -= 128
    return tn


def _gmm_kernel(tile_expert_ref, n_used_ref, x_ref, w_ref, o_ref):
    """One row tile times its expert's weight block; zeros for a tile
    past the used ones."""
    del tile_expert_ref
    i = pl.program_id(1)

    @pl.when(i < n_used_ref[0])
    def _product():
        o_ref[...] = jnp.dot(
            x_ref[...], w_ref[...],
            preferred_element_type=jnp.float32).astype(o_ref.dtype)

    @pl.when(i >= n_used_ref[0])
    def _unused():
        o_ref[...] = jnp.zeros_like(o_ref)


@functools.lru_cache(maxsize=None)
def _make_gmm(M: int, k: int, n: int, E: int, x_dtype, w_dtype,
              out_dtype, interpret: bool, name: str):
    tm = TILE_ROWS
    tn = _tile_cols(k, n, jnp.dtype(w_dtype).itemsize)

    def row_tile(j, i, tile_expert, n_used):
        return (jnp.maximum(jnp.minimum(i, n_used[0] - 1), 0), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,          # tile -> expert, tiles used
        grid=(n // tn, M // tm),
        in_specs=[
            pl.BlockSpec((tm, k), row_tile),
            pl.BlockSpec((None, k, tn),
                         lambda j, i, tile_expert, n_used:
                         (tile_expert[i], 0, j)),
        ],
        out_specs=pl.BlockSpec((tm, tn), lambda j, i, te, nu: (i, j)),
    )
    return pl.pallas_call(
        _gmm_kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, n), out_dtype),
        interpret=interpret, name=name)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def grouped_matmul(x, w, tile_expert, n_used, name: str = "moe_experts"):
    """``out[tile] = x[tile] @ w[tile_expert[tile]]`` for the first
    ``n_used`` row tiles of ``x`` [M, k], zeros after them; ``w`` is
    [E, k, n]. A Pallas kernel, ``name`` on a device trace, run by the
    Pallas interpreter on the CPU backend (tests)."""
    (M, k), (E, _, n) = x.shape, w.shape
    call = _make_gmm(M, k, n, E, x.dtype, w.dtype, x.dtype,
                     jax.default_backend() == "cpu", name)
    return call(tile_expert, jnp.reshape(n_used, (1,)).astype(jnp.int32),
                x, w)


def _gmm_fwd(x, w, tile_expert, n_used, name):
    out = grouped_matmul(x, w, tile_expert, n_used, name)
    return out, (x, w, tile_expert, n_used)


def _gmm_bwd(name, res, g):
    """Plain XLA, tile by tile: the layer stays trainable, at the cost
    of a gathered copy of each tile's expert weights."""
    x, w, tile_expert, n_used = res
    tiles = x.shape[0] // TILE_ROWS
    live = (jnp.arange(tiles) < n_used)[:, None, None]
    xt = x.reshape(tiles, TILE_ROWS, -1)
    gt = jnp.where(live, g.reshape(tiles, TILE_ROWS, -1), 0)
    dx = jnp.einsum("tmn,tkn->tmk", gt, w[tile_expert]).reshape(x.shape)
    dw = jnp.zeros_like(w).at[tile_expert].add(
        jnp.einsum("tmk,tmn->tkn", xt, gt).astype(w.dtype))
    return dx.astype(x.dtype), dw, None, None


grouped_matmul.defvjp(_gmm_fwd, _gmm_bwd)


def routed_experts(x, experts, weights, w1, w2, *, first=0,
                   name: str = "moe_experts", activation: str = "swiglu"):
    """The grouped product and the combine: ``sum_j weights[t, j] *
    Expert_{experts[t, j]}(x[t])`` over the experts held here (``w1``
    [E, d, 2f], ``w2`` [E, f, d], global ids from ``first``), every
    assignment computed; ``name`` is the grouped product's kernel on a
    device trace. ``activation`` "relu2" is the expert that is not
    gated, ``relu(x w1)^2 w2`` with ``w1`` [E, d, f]. Returns (y [T, d]
    in x's dtype, sizes [E]: the tokens each held expert got)."""
    T, k = experts.shape
    E, _, f2 = w1.shape
    sizes, dest, src, tile_expert, n_used = dispatch_plan(
        experts, E, first)
    rows = x[src]                                    # [M, d], by expert
    gu = grouped_matmul(rows, w1, tile_expert, n_used, name)
    if activation == "relu2":
        act = jnp.square(jax.nn.relu(gu.astype(jnp.float32))).astype(x.dtype)
    elif activation == "swiglu":
        act = (jax.nn.silu(gu[:, :f2 // 2].astype(jnp.float32))
               * gu[:, f2 // 2:].astype(jnp.float32)).astype(x.dtype)
    else:
        raise ValueError(f"unknown expert activation {activation!r}")
    out = grouped_matmul(act, w2, tile_expert, n_used, name)
    M = rows.shape[0]
    held = (dest < M).reshape(T, k)
    mine = out[jnp.minimum(dest, M - 1)].reshape(T, k, -1)
    y = jnp.einsum("tk,tkd->td", jnp.where(held, weights, 0.0),
                   mine.astype(jnp.float32))
    return y.astype(x.dtype), sizes


def load_balancing_loss(probs, sizes):
    """Switch-transformer aux loss: E * sum_e fraction_routed_e *
    mean_prob_e, from the router's probabilities [T, E] and the tokens
    each expert got [E]."""
    E = probs.shape[1]
    frac_routed = sizes / jnp.maximum(sizes.sum(), 1)
    return E * jnp.sum(frac_routed * probs.mean(axis=0))


def moe_apply(params, x, cfg: MoEConfig, *, ep_axis: Optional[str] = None):
    """x: [tokens_local, d_model] -> (y [tokens_local, d_model], aux_loss).

    With ``ep_axis`` set (inside shard_map), the expert banks are
    sharded over that axis (w1/w2 leading dim = n_experts/ep locally):
    the shards of one ep group gather their tokens, each computes what
    its own experts give, and the sum is scattered back.
    """
    first = 0
    if ep_axis:
        x = jax.lax.all_gather(x, ep_axis, axis=0, tiled=True)
        first = jax.lax.axis_index(ep_axis) * params["w1"].shape[0]
    probs, experts, weights = route(x, params["wg"], cfg.k, cfg.scale)
    y, sizes = routed_experts(x, experts, weights, params["w1"],
                              params["w2"], first=first)
    if ep_axis:
        y = jax.lax.psum_scatter(y, ep_axis, scatter_dimension=0, tiled=True)
        sizes = jax.lax.all_gather(sizes, ep_axis, axis=0, tiled=True)
    return y, load_balancing_loss(probs, sizes)


def moe_apply_sharded(params, x, cfg: MoEConfig, mesh: Mesh, *,
                      ep_axis: str = "ep",
                      batch_axes=("dp", "fsdp", "ep")):
    """Global [batch, seq, d_model] entry point: batch sharded over the data
    axes (including ep — each ep rank brings its own token shard), expert
    banks sharded over ep."""
    p_specs = {
        "wg": P(None, None),
        "w1": P(ep_axis, None, None),
        "w2": P(ep_axis, None, None),
    }
    # Batch shards over ep exactly once, whether or not the caller listed it.
    other_axes = tuple(a for a in batch_axes if a != ep_axis)
    x_spec = P(other_axes + (ep_axis,), None, None)

    def body(p, xx):
        b, s, d = xx.shape
        y, aux = moe_apply(p, xx.reshape(b * s, d), cfg, ep_axis=ep_axis)
        # aux is per ep group; average over the groups.
        for ax in other_axes:
            aux = jax.lax.pmean(aux, ax)
        return y.reshape(b, s, d), aux

    return jax.shard_map(
        body, mesh=mesh, in_specs=(p_specs, x_spec),
        out_specs=(x_spec, P()), check_vma=False,
    )(params, x)
