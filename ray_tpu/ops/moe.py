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
  tile      the rows of a tile of the grouped product follow the rows
            an expert is expected to get, ``T*k // n_experts`` over ALL
            the experts the router scores (``tile_rows``; a static fact
            of the call): 16 where an expert gets a handful (a decode
            step, a short chunk), 64 where it gets 32 or more (a 512- to
            2,048-token chunk at top-4 of 64). A weight tile pushed
            into the MXU costs the same whatever rows follow it, so a
            tall tile is that many fewer pushes and grid steps.
  plan      a counting sort of the T*k assignments by expert
            (``dispatch_plan``): each expert's rows are padded to whole
            tiles, so a tile belongs to ONE expert. The row buffer is
            ``T*k + E*(tile-1)`` rows, rounded up: what the assignments
            need if every expert gets a ragged tail. Tiles past the
            used ones are skipped.
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
(64 of 512, 22 a token) and models/granite_hybrid.py with gated ones at
the full width in every layer (36 of 72, 10 a token, ``route`` at scale
1: a softmax over the chosen logits).
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
# The least rows of a tile of the grouped product: the sublane tile of a
# 16-bit row buffer, the smallest Mosaic takes unpadded. A decode step
# gives an expert ~2 rows, so a larger tile only adds padding rows there.
MIN_TILE_ROWS = 16
# The tall tile, and the rows an expert must expect to be given it.
# Settled on a v5e at Xing4.0's widths ([M, 3584] x [64, 3584, 2048] and
# [M, 1024] x [64, 1024, 3584], top-4 of 64; PERF.md section 6, PR 63).
# The whole chunk program of 7 layers, ms, by tile, at 32 / 64 / 96 /
# 128 rows an expert (chunks of 512 / 1,024 / 1,536 / 2,048 tokens):
#     16:  28.8  38.9  53.7  65.3
#     32:  24.6  32.9  45.3  54.6
#     64:  23.7  30.8  42.5  51.5
#    128:  24.8  31.0  41.4  54.5
# The two products of one layer alone, 2,048 tokens: 5.52 at 16, 2.94 at
# 64, 2.79 at 128, 2.60 at 256 (their weights' bytes take 1.72). A tile
# that holds ALL of an expert's rows is fastest in the kernel (the next
# expert's weights are fetched under every tile, not under an expert's
# last tile alone), but the row buffer is T*k + E*(tile-1) rows whatever
# the routing, and what XLA does a buffer row (the gather, the
# activation) costs more than the kernel gets back from 128 rows on.
# From 192 to 512 rows an expert 64, 128 and 256 read within 3% of one
# another. Below 32 rows an expert nothing was measured: no program of
# the benchmark's other cells gives an expert more than 22.
TALL_TILE_ROWS = 64
TALL_TILE_FROM = 32
# What a tall tile's blocks may take of VMEM, all double-buffered by the
# pipeline: room for a weight block as wide as Xing4.0's widest (31 MB;
# the grid walks the rows once a column block, and at a third of that
# width the two products of a layer read 3.43 for 2.94). v5e has 128 MiB,
# of which a kernel gets 16 unless it asks.
_VMEM_BUDGET = 48 * 1024 * 1024


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


def tile_rows(n_assignments: int, n_experts: int) -> int:
    """Rows of a tile of the grouped product, from the rows an expert
    is expected to get: ``n_assignments`` (T * k) over the ``n_experts``
    they are routed over (all of the model's, not a held share). 16
    below 32 rows an expert, 64 from there on (the readings stand by
    the constants)."""
    if n_assignments // n_experts < TALL_TILE_FROM:
        return MIN_TILE_ROWS
    return TALL_TILE_ROWS


def plan_rows(n_assignments: int, n_experts: int, tile: int) -> int:
    """Rows of the tile-aligned buffer: the assignments plus a ragged
    tail an expert held here, in whole tiles. A bound, not a capacity."""
    rows = n_assignments + n_experts * (tile - 1)
    return -(-rows // tile) * tile


def dispatch_plan(experts, n_experts: int, tile: int, first=0):
    """Counting sort of the assignments ``experts`` [T, k] by expert,
    for the ``n_experts`` experts held here (global ids ``first`` ..
    ``first + n_experts - 1``; an assignment to another expert is not
    ours and gets no row), in tiles of ``tile`` rows.

    Returns ``(sizes [E], dest [T*k], src [M], tile_expert [M/tile],
    n_used)``: tokens a held expert got; the buffer row of each
    assignment (M where it is not held: out of range); the token each
    buffer row copies (padding rows copy token 0: finite, never read
    back); the expert of each row tile; and how many tiles hold rows."""
    T, k = experts.shape
    A, E, tm = T * k, n_experts, tile
    M = plan_rows(A, E, tm)
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


def _tile_cols(k: int, n: int, itemsize: int, tile: int) -> int:
    """Columns of a weight block, a multiple of 128 lanes that divides
    n. Beside a 16-row tile: about 2 MiB of one expert's weights
    (double-buffered by the pipeline, well inside v5e's 16 MiB of
    scoped VMEM). Beside a tall tile the widest that ``_VMEM_BUDGET``
    holds: the grid walks every row tile once a column block, and tall
    tiles no longer hide that many readings of the rows."""
    if tile == MIN_TILE_ROWS:
        want = max(128, (2 * 1024 * 1024) // (k * itemsize) // 128 * 128)
    else:
        want = max(128, _tall_cols(k, itemsize, tile) // 128 * 128)
    if n <= want or n % 128:
        return n
    tn = want
    while n % tn:
        tn -= 128
    return tn


def _vmem_bytes(k: int, tn: int, itemsize: int, tile: int) -> int:
    """What one grid step's blocks take: the row tile, the weight block
    and the result twice each (the pipeline's two buffers), and the
    product in float32 before its cast."""
    return (2 * itemsize * (tile * k + k * tn + tile * tn) + 4 * tile * tn)


def _tall_cols(k: int, itemsize: int, tile: int) -> int:
    """The most columns whose blocks ``_VMEM_BUDGET`` holds."""
    fixed = _vmem_bytes(k, 0, itemsize, tile)
    a_column = _vmem_bytes(k, 1, itemsize, tile) - fixed
    return (_VMEM_BUDGET - fixed) // a_column


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
def _make_gmm(M: int, k: int, n: int, E: int, tm: int, x_dtype, w_dtype,
              out_dtype, interpret: bool, name: str):
    itemsize = jnp.dtype(w_dtype).itemsize
    tn = _tile_cols(k, n, itemsize, tm)
    tall = {}
    if tm > MIN_TILE_ROWS:
        # The name says that the tall tile engaged; a 16-row call is
        # the kernel it was, letter for letter.
        name = f"{name}_r{tm}"
        tall["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=max(
                16 << 20, _vmem_bytes(k, tn, itemsize, tm) + (8 << 20)))

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
        interpret=interpret, name=name, **tall)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def grouped_matmul(x, w, tile_expert, n_used, name: str = "moe_experts"):
    """``out[tile] = x[tile] @ w[tile_expert[tile]]`` for the first
    ``n_used`` row tiles of ``x`` [M, k], zeros after them; ``w`` is
    [E, k, n]; a tile is ``M / len(tile_expert)`` rows. A Pallas
    kernel, ``name`` on a device trace (``<name>_r<rows>`` where a tile
    is more than 16 rows), run by the Pallas interpreter on the CPU
    backend (tests)."""
    (M, k), (E, _, n) = x.shape, w.shape
    call = _make_gmm(M, k, n, E, M // tile_expert.shape[0], x.dtype,
                     w.dtype, x.dtype, jax.default_backend() == "cpu", name)
    return call(tile_expert, jnp.reshape(n_used, (1,)).astype(jnp.int32),
                x, w)


def _gmm_fwd(x, w, tile_expert, n_used, name):
    out = grouped_matmul(x, w, tile_expert, n_used, name)
    return out, (x, w, tile_expert, n_used)


def _gmm_bwd(name, res, g):
    """Plain XLA, tile by tile: the layer stays trainable, at the cost
    of a gathered copy of each tile's expert weights."""
    x, w, tile_expert, n_used = res
    tiles = tile_expert.shape[0]
    tm = x.shape[0] // tiles                # the forward's tile
    live = (jnp.arange(tiles) < n_used)[:, None, None]
    xt = x.reshape(tiles, tm, -1)
    gt = jnp.where(live, g.reshape(tiles, tm, -1), 0)
    dx = jnp.einsum("tmn,tkn->tmk", gt, w[tile_expert]).reshape(x.shape)
    dw = jnp.zeros_like(w).at[tile_expert].add(
        jnp.einsum("tmk,tmn->tkn", xt, gt).astype(w.dtype))
    return dx.astype(x.dtype), dw, None, None


grouped_matmul.defvjp(_gmm_fwd, _gmm_bwd)


def routed_experts(x, experts, weights, w1, w2, *, first=0,
                   n_experts: Optional[int] = None,
                   name: str = "moe_experts", activation: str = "swiglu"):
    """The grouped product and the combine: ``sum_j weights[t, j] *
    Expert_{experts[t, j]}(x[t])`` over the experts held here (``w1``
    [E, d, 2f], ``w2`` [E, f, d], global ids from ``first``), every
    assignment computed. ``n_experts`` is how many experts ``experts``
    was routed over, where that is more than the E held here: the rows
    of a tile follow what ONE of them expects (``tile_rows``). ``name``
    is the grouped product's kernel on a device trace. ``activation``
    "relu2" is the expert that is not gated, ``relu(x w1)^2 w2`` with
    ``w1`` [E, d, f]. Returns (y [T, d] in x's dtype, sizes [E]: the
    tokens each held expert got)."""
    T, k = experts.shape
    E, _, f2 = w1.shape
    sizes, dest, src, tile_expert, n_used = dispatch_plan(
        experts, E, tile_rows(T * k, n_experts or E), first)
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
                              params["w2"], first=first,
                              n_experts=cfg.n_experts)
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
