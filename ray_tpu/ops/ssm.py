"""The Mamba-2 state-space mixer's recurrence in its two served forms.

A head ``h`` of ``H`` keeps a state ``S`` [P, N] (``P`` the head's
width, ``N`` the state size) and a token moves it by

  S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T,    y_t = S_t C_t

with ``A`` a negative scalar a head, ``dt_t`` a positive scalar a head a
token, ``x_t`` [P], and ``B_t``, ``C_t`` [N] shared by the ``H / G``
heads of a group. (The ``D x`` skip, the gate and the convolution in
front are the model's, models/nemotron_h.py and
models/granite_hybrid.py.) Everything here is float32: the state is
what a sequence carries for thousands of tokens.

**A group wider than a block.** Both kernels take their heads a block
at a time (``SCAN_HEADS`` of a span's, ``UPDATE_HEADS`` of a decode
step's). A block holds whole groups where a group is no wider than it
(Nemotron-3: 8 groups of 16), and lies inside ONE group where a group
is wider (Granite 4.0-H: one group of 128): then the blocks of a group
read the same ``B`` and ``C`` rows, which their index maps name by the
group and not by the block, and each keeps its own heads' state.

  ssd_scan     a span of a prompt: the chunked form (state-space
               duality, Dao & Gu 2024): blocks of ``chunk`` tokens, a
               block's own tokens against each other as one masked
               product, the state handed from block to block; an
               initial state comes in and the final one goes out. Rows
               with ``dt`` = 0 leave the state as it is, which is how a
               span is padded. A Pallas kernel (``ssm_scan`` on a
               device trace): a grid step is one block of a group's
               heads (the whole group where it has no more than
               ``SCAN_HEADS``) in one block of rows, the blocks of rows
               in order with those heads' state resident in VMEM
               between them, so a span's state is read once and written
               once.
  ssm_update   one token a lane of a decode batch, IN PLACE in the pool
               of state slots ``[layers, slots, H, P, N]``: a Pallas
               kernel (``ssm_update`` on a device trace) that takes each
               lane's slot from a scalar-prefetched table, reads the
               slot's state block by block, writes it back where it was
               (``input_output_aliases``) and hands back ``y``. A step
               moves each live state once in and once out and nothing
               else of the pool. A grid step holds ``UPDATE_HEADS``
               heads of one lane (2 MiB each way at heads of 64 by a
               state of 128) and has to stay under what those bytes
               take, ~15 cycles a vreg of state: so a head's decay is
               a scalar, ``dt x`` goes in as it lies and is stood up as
               columns once a grid step, and ``y`` is summed down the
               sublanes of a tile's transpose and leaves lane-dense:
               two passes of the cross-lane unit a vreg (the ``dt x``
               column's broadcast, the transpose), which fit under the
               block's copies; a third does not (~5 ns each on a v5e
               against 6.4 us a block of 512 vregs). On the CPU the
               Pallas interpreter runs the same kernel (tests).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
# Lanes of a vector register, and the heads of a lane that a grid step
# of the update takes.
LANES, UPDATE_HEADS = 128, 64
# The two kernels' names on a device trace (the benchmark's readers find
# them by these).
SCAN_KERNEL, UPDATE_KERNEL = "ssm_scan", "ssm_update"
# Heads of a group a grid step of the scan takes: a step holds their
# ``x dt``, outputs and states (two copies) in VMEM and unrolls over
# them. 16 heads of 64 at a block of 256 rows are 1 + 1 + 2 x 0.5 MiB,
# doubled by the pipeline: inside the 16 MiB a kernel is given.
SCAN_HEADS = 16


def _blocks(x, dt, A, B, C, chunk: int):
    """The scan's inputs in blocks of ``chunk`` rows, float32: (x dt
    [c, l, G, J, P], the running sum of dt A inside each block [c, l,
    G, J], B and C [c, l, G, N]); the tail padded with ``dt`` = 0."""
    n, H, P = x.shape
    G, N = B.shape[1:]
    pad = -n % chunk
    if pad:
        x, dt, B, C = (jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
                       for a in (x, dt, B, C))
    c, l, J = (n + pad) // chunk, chunk, H // G
    x, dt, B, C = (a.astype(F32) for a in (x, dt, B, C))
    cum = jnp.cumsum((dt * A.astype(F32)).reshape(c, l, G, J), axis=1)
    return ((x * dt[..., None]).reshape(c, l, G, J, P), cum,
            B.reshape(c, l, G, N), C.reshape(c, l, G, N))


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())), precision=HIGHEST,
                               preferred_element_type=F32)


def _scan_kernel(xdt_ref, cum_ref, b_ref, c_ref, s0_ref, y_ref, s_ref, *,
                 heads: int):
    """``heads`` heads of one group in one block of l rows. ``s_ref`` is
    those heads' state, resident across their blocks of rows: the
    initial state at the first, the final one when the last has run."""
    @pl.when(pl.program_id(1) == 0)
    def _first():
        s_ref[...] = s0_ref[...]

    Bm, Cm = b_ref[...], c_ref[...]                     # [l, N]
    l, P = Bm.shape[0], xdt_ref.shape[-1]
    scores = _dot(Cm, Bm, ((1,), (1,)))                 # [t, s]
    t = jax.lax.broadcasted_iota(jnp.int32, (l, l), 0)
    s = jax.lax.broadcasted_iota(jnp.int32, (l, l), 1)
    for j in range(heads):
        at_s = jnp.broadcast_to(cum_ref[j:j + 1, :], (l, l))   # cum[s]
        at_t = at_s.T                                          # cum[t]
        # Row t takes from row s <= t what s put in, decayed over (s, t].
        decay = jnp.exp(jnp.where(t >= s, at_t - at_s, -jnp.inf))
        xd, S = xdt_ref[j], s_ref[j]                    # [l, P], [P, N]
        rows = at_t[:, :P]                              # cum[t], P wide
        y_ref[j] = (_dot(scores * decay, xd, ((1,), (0,)))
                    + jnp.exp(rows) * _dot(Cm, S, ((1,), (1,))))
        # The block's whole sum, as a row beside ``rows`` and as a
        # column beside the state (a 1 x 1 value broadcasts neither way).
        end = at_t[l - 1:l, :P]
        # (Mosaic takes that column at a lane offset inside the FIRST
        # lane tile only; a longer block takes the sum as a row as wide
        # as the state.)
        whole = at_s[:P, l - 1:l] if l <= LANES else at_t[l - 1:l, :S.shape[1]]
        s_ref[j] = (jnp.exp(whole) * S
                    + _dot(xd * jnp.exp(end - rows), Bm, ((0,), (0,))))


def _scan_heads(J: int) -> int:
    """Heads of a group of ``J`` that a grid step of the scan takes."""
    if J > SCAN_HEADS and J % SCAN_HEADS:
        raise ValueError(f"a group of {J} heads does not cut into blocks "
                         f"of {SCAN_HEADS}")
    return min(J, SCAN_HEADS)


@functools.lru_cache(maxsize=None)
def _make_scan(c: int, l: int, G: int, K: int, J: int, P: int, N: int,
               interpret: bool):
    """``G`` groups in ``K`` blocks of ``J`` heads each: the grid's
    first axis walks the G x K head blocks, and block ``g`` reads group
    ``g // K``'s ``B`` and ``C``."""
    by_block = lambda *shape: pl.BlockSpec(
        (None, None, *shape), lambda g, i: (g, i) + (0,) * len(shape))
    by_group = by_block if K == 1 else lambda *shape: pl.BlockSpec(
        (None, None, *shape), lambda g, i: (g // K, i) + (0,) * len(shape))
    state = pl.BlockSpec((None, J, P, N), lambda g, i: (g, 0, 0, 0))
    return pl.pallas_call(
        functools.partial(_scan_kernel, heads=J),
        grid=(G * K, c),
        in_specs=[by_block(J, l, P), by_block(J, l), by_group(l, N),
                  by_group(l, N), state],
        out_specs=[by_block(J, l, P), state],
        out_shape=[jax.ShapeDtypeStruct((G * K, c, J, l, P), F32),
                   jax.ShapeDtypeStruct((G * K, J, P, N), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret, name=SCAN_KERNEL)


def ssd_scan(x, dt, A, B, C, S0, chunk: int):
    """x [n, H, P], dt [n, H] (after softplus; 0 on padding rows), A
    [H], B and C [n, G, N], S0 [H, P, N] -> (y [n, H, P], S [H, P, N]
    after the last row), all float32. ``n`` need not be a multiple of
    ``chunk``: the tail is padded with ``dt`` = 0. The kernel wants a
    head no wider than a block is long (``P <= chunk``), and of a block
    longer than a lane tile a state no wider either."""
    n, H, P = x.shape
    G, N = B.shape[1:]
    if P > chunk or (chunk > LANES and N > chunk):
        raise ValueError(f"head width {P} or state {N} over the block "
                         f"length {chunk}")
    xdt, cum, B, C = _blocks(x, dt, A, B, C, chunk)
    c, l, J = xdt.shape[0], chunk, _scan_heads(H // G)
    K = H // G // J
    call = _make_scan(c, l, G, K, J, P, N, jax.default_backend() == "cpu")
    # A group's heads lie side by side, so its K blocks of J do too.
    y, S = call(xdt.reshape(c, l, G * K, J, P).transpose(2, 0, 3, 1, 4),
                cum.reshape(c, l, G * K, J).transpose(2, 0, 3, 1),
                B.transpose(2, 0, 1, 3), C.transpose(2, 0, 1, 3),
                S0.astype(F32).reshape(G * K, J, P, N))
    return (y.transpose(1, 3, 0, 2, 4).reshape(c * l, H, P)[:n],
            S.reshape(H, P, N))


def ssm_recurrence(x, dt, A, B, C, S0):
    """``ssd_scan``'s contract as the token recurrence itself, a
    ``lax.scan`` over rows: the kernel's ground truth (tests)."""
    H, G = x.shape[1], B.shape[1]
    heads = lambda a: jnp.repeat(a.astype(F32), H // G, axis=0)  # [G,N]->[H,N]

    def token(S, row):
        x_t, dt_t, B_t, C_t = row
        S = (jnp.exp(dt_t * A)[:, None, None] * S
             + (dt_t[:, None] * x_t)[..., None] * heads(B_t)[:, None, :])
        return S, (S * heads(C_t)[:, None, :]).sum(-1)

    S, y = jax.lax.scan(token, S0.astype(F32),
                        (x.astype(F32), dt.astype(F32), B, C))
    return y, S


# ---------------------------------------------------------------------------
# The decode step's update
# ---------------------------------------------------------------------------


def _head_block(H: int, G: int) -> int:
    """Heads a grid step updates (``UPDATE_HEADS`` of a lane's): whole
    groups, or a part of ONE group that is wider than that."""
    hb = min(H, UPDATE_HEADS)
    if H % hb or (hb % (H // G) and (H // G) % hb):
        raise ValueError(f"{H} heads in {G} groups do not cut into blocks "
                         f"of {hb}")
    return hb


def _tile_heads(hb: int, P: int) -> int:
    """Heads of a block whose rows fill a lane tile (two of 64): their
    outputs leave the kernel as ONE row of ``LANES``."""
    return math.gcd(hb, max(1, LANES // P))


def _update_kernel(slots_ref, decay_ref, dtx_ref, b_ref, c_ref, s_ref, y_ref,
                   o_ref, *, hb: int, hg: int, th: int):
    """One lane's ``hb`` heads: ``S <- decay S + (dt x) B^T``, ``y = S
    C``, a tile of ``th`` heads at a time. Beside the state's own two
    products and a sum, a vreg of state costs ONE pass of the cross-lane
    unit each way: its ``dt x`` column broadcast along the state's
    lanes, and its part of the tile's transpose. A head's decay is a
    scalar (SMEM), splat where it is used. ``dt x`` comes as it lies,
    a row a tile, and is stood up as columns by one small transpose a
    grid step. ``y`` sums a tile's ``S C`` products [th * P, N] down
    the sublanes of their transpose, plain adds, and leaves as the
    tile's row, lane-dense."""
    del slots_ref
    i, k = pl.program_id(0), pl.program_id(1)
    P = s_ref.shape[1]
    cols = dtx_ref[...].T               # tile m's dt x [th * P] down lane m
    for m in range(hb // th):
        tile = []
        for r in range(th):
            j = m * th + r
            g = j // hg
            new = (decay_ref[i, k * hb + j] * s_ref[j]
                   + cols[r * P:(r + 1) * P, m:m + 1] * b_ref[g:g + 1, :])
            o_ref[j] = new
            tile.append(new * c_ref[g:g + 1, :])
        y_ref[m:m + 1, :] = jnp.sum(jnp.concatenate(tile, axis=0).T, axis=0,
                                    keepdims=True)


@functools.lru_cache(maxsize=None)
def _make_update(b: int, L: int, slots: int, H: int, P: int, N: int,
                 G: int, layer: int, interpret: bool):
    hb = _head_block(H, G)
    th = _tile_heads(hb, P)
    hg, nk = H // G, H // hb
    small = lambda rows, width: pl.BlockSpec(
        (None, None, rows, width), lambda i, k, *_: (i, k, 0, 0))
    tile_rows = small(hb // th, th * P)
    if hg <= hb:
        group_rows = small(hb // hg, N)
    else:
        # A group wider than a block: its hg // hb blocks read its ONE
        # row of ``B`` and of ``C`` ([b, G, 1, N]).
        wide = hg // hb
        group_rows = pl.BlockSpec(
            (None, None, 1, N), lambda i, k, *_: (i, k // wide, 0, 0))
        hg = hb
    state = pl.BlockSpec((None, None, hb, P, N),
                         lambda i, k, slots_ref, _: (layer, slots_ref[i], k,
                                                     0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(b, nk),
        in_specs=[tile_rows, group_rows, group_rows, state],
        out_specs=[tile_rows, state])
    return pl.pallas_call(
        functools.partial(_update_kernel, hb=hb, hg=hg, th=th),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, nk, hb // th, th * P), F32),
                   jax.ShapeDtypeStruct((L, slots, H, P, N), F32)],
        # (slots, decay, dt x, B, C, pool) -> (y, pool): the pool in place.
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret, name=UPDATE_KERNEL)


def ssm_update(pool, layer: int, slots, decay, dtx, B, C):
    """One token a lane. ``pool`` [L, slots, H, P, N] float32, donated
    by the caller's program; ``slots`` [b] int32, a lane's slot (0 for
    a padded lane: scratch); ``decay`` [b, H] = exp(dt A); ``dtx`` [b,
    H, P] = dt x; ``B``, ``C`` [b, G, N]. Returns (y [b, H, P] float32,
    the pool with the lanes' slots of ``layer`` updated)."""
    L, n_slots, H, P, N = pool.shape
    b, G = B.shape[:2]
    hb = _head_block(H, G)
    nk = H // hb
    # ``dt x`` goes in and ``y`` comes out as they lie: a block's heads
    # a tile a row.
    tiles = (b, nk, -1, _tile_heads(hb, P) * P)
    # A block's groups' rows; a group wider than a block keeps its own.
    by_block = lambda a: a.astype(F32).reshape(
        *((b, nk, G // nk) if G >= nk else (b, G, 1)), N)
    call = _make_update(b, L, n_slots, H, P, N, G, int(layer),
                        jax.default_backend() == "cpu")
    y, pool = call(slots.astype(jnp.int32), decay.astype(F32),
                   dtx.astype(F32).reshape(tiles), by_block(B), by_block(C),
                   pool)
    return y.reshape(b, H, P), pool


def ssm_update_reference(pool, layer: int, slots, decay, dtx, B, C):
    """``ssm_update`` as a gather, the recurrence and a scatter in
    plain jnp (tests)."""
    H, G = pool.shape[2], B.shape[1]
    heads = lambda a: jnp.repeat(a.astype(F32), H // G, axis=1)
    S = (decay[..., None, None] * pool[layer, slots]
         + dtx[..., None] * heads(B)[:, :, None, :])
    return ((S * heads(C)[:, :, None, :]).sum(-1),
            pool.at[layer, slots].set(S))
