"""Manifold-constrained hyper-connections (mHC, arXiv:2512.24880, on
hyper-connections, arXiv:2409.19606): a residual path of ``n`` streams
whose mixing weights are made from the token itself.

A token's residual is ``X`` in R^{n x C}, held as ONE row ``vec(X)`` of
``n C`` values, stream ``i`` at columns ``[i C, (i + 1) C)`` (a
``[rows, n, C]`` array would give the TPU a second-minor dimension of
``n`` = 4 to pad to a whole tile, at rest and in every block). Around a
sublayer ``F``:

  x = vec(X);  r = rsqrt(mean(x^2) + norm_eps)
  m = (r x) Phi                          Phi [n C, n^2 + 2n], f32 accumulate
  H_pre  = sigmoid(a_pre m[0:n] + b_pre)            [n]
  H_post = 2 sigmoid(a_post m[n:2n] + b_post)       [n]
  M = exp(clip(a_res mat(m[2n:]) + b_res, lo, hi))  [n, n]
  ``iters`` times:  M <- M / (rowsum(M) + eps);  M <- M / (colsum(M) + eps)
  H_res = M                             (Sinkhorn-Knopp: doubly stochastic)
  h = sum_i H_pre[i] X_i                what the sublayer sees, [C]
  y = F(h)
  X'_i = sum_j H_res[i, j] X_j + H_post[i] y

``a`` is ``[a_pre, a_post, a_res]``, ``b`` is ``[b_pre | b_post | b_res
row-major]`` and ``Phi``'s columns lie in ``b``'s order. Coefficients,
the Sinkhorn chain and both mixing sums are float32 whatever the
streams are held in.

Two Pallas kernels, tiled over rows, each one pass over ``X``
(``mhc_pre`` and ``mhc_post`` on a device trace, with the caller's
program behind them where it has two: ``mhc_pre_chunk``,
``mhc_post_decode``; the benchmark's readers find them by these names):

  mhc_pre    X, Phi, a, b -> h, coef.  The product ``x Phi`` on the MXU
             against ``Phi`` spread to 128 lanes, the mean square beside
             it on the VPU; the [rows, 128] pre-activations are
             TRANSPOSED so that a coefficient of every row of the tile
             is one lane-dense vector, the ``iters`` normalisations run
             on those (a row sum is a sum over 4 sublanes, a column sum
             a sum of 4 vectors), the result is transposed back, and the
             pre-mix reads the tile of ``X`` that is already in VMEM.
  mhc_post   X, y, coef -> X', in place (``X`` aliased to ``X'``).

``coef`` is the coefficients' slab ``[rows, 128]`` float32 as the first
kernel leaves it for the second: ``H_pre`` at lanes ``[0, n)``,
``H_post`` at ``[8, 8 + n)``, row ``i`` of ``H_res`` at ``[16 + 8 i,
16 + 8 i + n)`` (``coefficients`` takes it apart, ``pack`` makes one).
On the CPU the Pallas interpreter runs the same kernels (tests).
``mhc_pre_reference`` / ``mhc_post_reference`` are the equations above
in plain ``jax.numpy``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
LANES = 128
# A coefficient group's stride in the slab: one sublane tile of the
# transposed pre-activations, so that each group is an aligned slice.
GROUP = 8
PRE, POST, RES = 0, GROUP, 2 * GROUP
# Rows a grid step takes at most (a shorter array is one block).
TILE = 256
# Columns of a stream mixed at a time: bounds the float32 temporaries.
CHUNK = 512
PRE_KERNEL, POST_KERNEL = "mhc_pre", "mhc_post"


def _chunk(C: int) -> int:
    return next((c for c in (CHUNK, 256, LANES) if C % c == 0), C)


def _spread(v, n: int):
    """``[..., n^2 + 2n]`` in ``b``'s order -> ``[..., 128]`` in the
    slab's: group ``g`` of ``n`` values at lanes ``[8 g, 8 g + n)``."""
    lead = v.shape[:-1]
    v = v.reshape(*lead, n + 2, n)
    v = jnp.pad(v, [(0, 0)] * len(lead) + [(0, 0), (0, GROUP - n)])
    v = v.reshape(*lead, (n + 2) * GROUP)
    return jnp.pad(v, [(0, 0)] * len(lead)
                   + [(0, LANES - (n + 2) * GROUP)])


def _sinkhorn(M, live, iters: int, eps: float):
    """``M`` a list of the matrix's rows, row ``i`` as ``[8, rows]``
    (column ``j`` on sublane ``j``, zeros on the sublanes that are not
    ``live``), a token a lane. A dead sublane's column sum is kept at 1:
    a compiler that folds the chain of divisions into one would divide
    its 0 by ``eps`` to the power of ``iters``, which is 0."""
    def once(_, M):
        M = [Mi / (jnp.sum(Mi, axis=0, keepdims=True) + eps) for Mi in M]
        col = jnp.where(live, functools.reduce(jnp.add, M) + eps, 1.0)
        return [Mi / col for Mi in M]

    return jax.lax.fori_loop(0, iters, once, M)


def _pre_kernel(x_ref, phi_ref, aff_ref, h_ref, coef_ref, *, n: int, C: int,
                iters: int, eps: float, norm_eps: float, lo: float,
                hi: float, precision):
    tr, chunk = x_ref.shape[0], _chunk(C)
    m = jnp.dot(x_ref[...], phi_ref[...], preferred_element_type=F32,
                precision=precision)                       # [tr, 128]
    sq = jnp.zeros((tr, chunk), F32)
    for c in range(0, n * C, chunk):
        v = x_ref[:, c:c + chunk].astype(F32)
        sq = sq + v * v
    r = jax.lax.rsqrt(jnp.sum(sq, axis=1, keepdims=True) / (n * C)
                      + norm_eps)
    A = (r * m) * aff_ref[0:1, :] + aff_ref[1:2, :]
    # A coefficient of every row of the tile as one vector along lanes.
    pad = -tr % LANES
    if pad:
        A = jnp.concatenate([A, jnp.zeros((pad, LANES), F32)], axis=0)
    At = A.T                                               # [128, tr + pad]
    live = jax.lax.broadcasted_iota(jnp.int32, (GROUP, tr + pad), 0) < n
    group = lambda at: At[at:at + GROUP]
    only = lambda v: jnp.where(live, v, 0.0)
    M = _sinkhorn([only(jnp.exp(jnp.clip(group(RES + GROUP * i), lo, hi)))
                   for i in range(n)], live, iters, eps)
    slab = jnp.concatenate(
        [only(jax.nn.sigmoid(group(PRE))),
         only(2.0 * jax.nn.sigmoid(group(POST))), *M,
         jnp.zeros((LANES - (n + 2) * GROUP, tr + pad), F32)], axis=0)
    coef = slab.T[:tr]                                     # [tr, 128]
    coef_ref[...] = coef
    pre = [coef[:, PRE + i:PRE + i + 1] for i in range(n)]
    for c in range(0, C, chunk):
        acc = pre[0] * x_ref[:, c:c + chunk].astype(F32)
        for i in range(1, n):
            acc = acc + pre[i] * x_ref[:, i * C + c:i * C + c + chunk] \
                .astype(F32)
        h_ref[:, c:c + chunk] = acc.astype(h_ref.dtype)


def _post_kernel(x_ref, y_ref, coef_ref, o_ref, *, n: int, C: int):
    chunk = _chunk(C)
    coef = coef_ref[...]
    post = [coef[:, POST + i:POST + i + 1] for i in range(n)]
    res = [[coef[:, RES + GROUP * i + j:RES + GROUP * i + j + 1]
            for j in range(n)] for i in range(n)]
    for c in range(0, C, chunk):
        y = y_ref[:, c:c + chunk].astype(F32)
        xs = [x_ref[:, j * C + c:j * C + c + chunk].astype(F32)
              for j in range(n)]
        for i in range(n):
            acc = res[i][0] * xs[0]
            for j in range(1, n):
                acc = acc + res[i][j] * xs[j]
            o_ref[:, i * C + c:i * C + c + chunk] = \
                (acc + post[i] * y).astype(o_ref.dtype)


_PARAMS = dict(dimension_semantics=("parallel",),
               vmem_limit_bytes=100 * 1024 * 1024)


def _tile(rows: int) -> int:
    """Rows a grid step takes: ``TILE``, or a shorter array whole."""
    return min(rows, TILE)


def _by_rows(tr: int, width: int):
    return pl.BlockSpec((tr, width), lambda i: (i, 0))


@functools.lru_cache(maxsize=None)
def _make_pre(rows: int, tr: int, n: int, C: int, dtype, iters: int,
              eps: float, norm_eps: float, lo: float, hi: float,
              interpret: bool, name: str):
    whole = lambda a, b: pl.BlockSpec((a, b), lambda i: (0, 0))
    return pl.pallas_call(
        functools.partial(
            _pre_kernel, n=n, C=C, iters=iters, eps=eps, norm_eps=norm_eps,
            lo=lo, hi=hi,
            # Said outright: a caller's ``default_matmul_precision`` would
            # otherwise ask the MXU for float32 passes over bfloat16 rows.
            precision=jax.lax.Precision.HIGHEST if dtype == F32
            else jax.lax.Precision.DEFAULT),
        grid=(pl.cdiv(rows, tr),),
        in_specs=[_by_rows(tr, n * C), whole(n * C, LANES),
                  whole(GROUP, LANES)],
        out_specs=[_by_rows(tr, C), _by_rows(tr, LANES)],
        out_shape=[jax.ShapeDtypeStruct((rows, C), dtype),
                   jax.ShapeDtypeStruct((rows, LANES), F32)],
        compiler_params=pltpu.CompilerParams(**_PARAMS),
        interpret=interpret, name=name)


@functools.lru_cache(maxsize=None)
def _make_post(rows: int, tr: int, n: int, C: int, dtype, interpret: bool,
               name: str):
    return pl.pallas_call(
        functools.partial(_post_kernel, n=n, C=C),
        grid=(pl.cdiv(rows, tr),),
        in_specs=[_by_rows(tr, n * C), _by_rows(tr, C), _by_rows(tr, LANES)],
        out_specs=_by_rows(tr, n * C),
        out_shape=jax.ShapeDtypeStruct((rows, n * C), dtype),
        input_output_aliases={0: 0},
        compiler_params=pltpu.CompilerParams(**_PARAMS),
        interpret=interpret, name=name)


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def mhc_pre(X, phi, a, b, *, n: int, iters: int, eps: float,
            norm_eps: float, clamp=(-30.0, 30.0), name: str = PRE_KERNEL):
    """X [rows, n C] (stream i at columns [i C, (i + 1) C)), phi [n C,
    n^2 + 2n], a [3], b [n^2 + 2n] -> (h [rows, C] in X's dtype, the
    coefficients' slab [rows, 128] float32). ``name`` is the kernel's
    on a device trace: a caller with two programs says which one (a
    trace does not)."""
    rows, nC = X.shape
    if (n + 2) * GROUP > LANES or n > GROUP:
        raise ValueError(f"{n} streams do not fit the slab's 128 lanes")
    aff = jnp.concatenate([
        _spread(jnp.repeat(a.astype(F32), np.asarray([n, n, n * n])),
                n)[None],
        _spread(b.astype(F32), n)[None],
        jnp.zeros((GROUP - 2, LANES), F32)])
    call = _make_pre(rows, _tile(rows), n, nC // n, jnp.dtype(X.dtype),
                     int(iters), float(eps), float(norm_eps),
                     float(clamp[0]), float(clamp[1]), _interpret(), name)
    return tuple(call(X, _spread(phi.astype(X.dtype), n), aff))


def mhc_post(X, y, coef, *, n: int, name: str = POST_KERNEL):
    """X [rows, n C], y [rows, C], ``mhc_pre``'s slab -> X' [rows, n C]
    in X's dtype. ``X`` is aliased to the result: a caller's program
    that has no further use of it writes the streams in place."""
    rows, nC = X.shape
    return _make_post(rows, _tile(rows), n, nC // n, jnp.dtype(X.dtype),
                      _interpret(), name)(X, y.astype(X.dtype), coef)


def coefficients(coef, n: int):
    """The slab -> (H_pre [rows, n], H_post [rows, n], H_res [rows, n,
    n]), float32."""
    groups = coef[:, :(n + 2) * GROUP].reshape(-1, n + 2, GROUP)[..., :n]
    return groups[:, 0], groups[:, 1], groups[:, 2:]


def pack(H_pre, H_post, H_res):
    """``coefficients``' inverse: a slab from given coefficients."""
    n = H_pre.shape[-1]
    return _spread(jnp.concatenate(
        [H_pre, H_post, H_res.reshape(-1, n * n)], axis=-1).astype(F32), n)


def res_err(coef, n: int):
    """The largest distance from 1 of any row or column sum of any
    ``H_res`` of the slab: how far from doubly stochastic the Sinkhorn
    chain left the worst token's matrix."""
    H = coefficients(coef, n)[2]
    return jnp.maximum(jnp.abs(H.sum(-1) - 1.0).max(),
                       jnp.abs(H.sum(-2) - 1.0).max())


def mhc_pre_reference(X, phi, a, b, *, n: int, iters: int, eps: float,
                      norm_eps: float, clamp=(-30.0, 30.0)):
    """The equations in plain jnp and float32 (run it under
    ``jax.default_matmul_precision("highest")`` on a TPU): (h [rows, C],
    H_pre [rows, n], H_post [rows, n], H_res [rows, n, n])."""
    x = X.astype(F32)
    rows, nC = x.shape
    r = jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + norm_eps)
    m = (r * x) @ phi.astype(F32)
    a, b = a.astype(F32), b.astype(F32)
    H_pre = jax.nn.sigmoid(a[0] * m[:, :n] + b[:n])
    H_post = 2.0 * jax.nn.sigmoid(a[1] * m[:, n:2 * n] + b[n:2 * n])
    M = jnp.exp(jnp.clip(
        a[2] * m[:, 2 * n:].reshape(rows, n, n) + b[2 * n:].reshape(n, n),
        clamp[0], clamp[1]))
    for _ in range(iters):
        M = M / (M.sum(-1, keepdims=True) + eps)
        M = M / (M.sum(-2, keepdims=True) + eps)
    h = jnp.einsum("ri,ric->rc", H_pre, x.reshape(rows, n, nC // n))
    return h, H_pre, H_post, M


def mhc_post_reference(X, y, H_post, H_res):
    """X'_i = sum_j H_res[i, j] X_j + H_post[i] y, float32: [rows, n C]."""
    rows, n = H_post.shape
    x = X.astype(F32).reshape(rows, n, -1)
    out = jnp.einsum("rij,rjc->ric", H_res, x) \
        + H_post[..., None] * y.astype(F32)[:, None, :]
    return out.reshape(rows, -1)
