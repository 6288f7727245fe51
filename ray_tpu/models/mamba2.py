"""The Mamba-2 state-space mixer (Dao & Gu, arXiv:2405.21060), as the
served hybrids run it (models/nemotron_h.py, models/granite_hybrid.py):

  ``d_inner`` = heads x head width; ``conv_dim`` = d_inner + 2 x groups
  x state.
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

A decode step moves the live lanes' states on by one token in place
(ops/ssm.py ``ssm_update``, a kernel that finds a lane's slot by a
prefetched table); a prefill span runs the chunked scan (``ssd_scan``)
from the slot it is told to read into the slot it is told to write.

What the mixer needs of a configuration is ``Mamba2``, a record each
family builds from its own published field names: the functions here
read no family's configuration.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Tuple

import jax
import jax.numpy as jnp

from ..ops import ssm
from .layers import normal

F32 = jnp.float32

# The convolution's taps are drawn at the scale of the family's
# initialiser (1 / sqrt(conv_kernel)): at std 0.02 the convolved rows
# would be ~0.05 and the mixer's x, B and C all but zero.
CONV_STD = 0.5
# A = -exp(A_log) is drawn uniform in [1, 16], the family's initialiser.
A_RANGE = (1.0, 16.0)


@dataclass(frozen=True)
class Mamba2:
    """What a Mamba-2 mixer needs of a configuration. ``time_step`` is
    the initialiser's (min, max, floor) of a head's step: Mamba-2's own
    where a published configuration gives none (Granite 4.0-H)."""
    hidden_size: int
    heads: int
    head_dim: int
    groups: int
    state: int
    conv_kernel: int
    chunk_size: int
    eps: float
    dtype: Any
    time_step: Tuple[float, float, float] = (0.001, 0.1, 1e-4)

    @property
    def d_inner(self) -> int:
        return self.heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.groups * self.state

    @property
    def num_params(self) -> int:
        """Parameters of one mixer, without its pre-norm."""
        m, H = self.hidden_size, self.heads
        return (m * (self.d_inner + self.conv_dim + H)
                + (self.conv_kernel + 1) * self.conv_dim + 3 * H
                + self.d_inner + self.d_inner * m)

    @property
    def state_parts(self) -> tuple:
        """What a sequence keeps a layer (``StateKind.parts``): ``S`` in
        float32, the convolution's last rows in the served dtype."""
        return (((self.heads, self.head_dim, self.state), jnp.dtype(F32)),
                ((self.conv_kernel - 1, self.conv_dim), self.dtype))

    def cost(self, layers: int) -> dict:
        """A family's cost entries (util/perfmodel.py) for ``layers``
        mixers: a decode row's update and ``S C``, the same at any
        context; a chunk's row's part of the chunked scan (its block's
        scores and masked product, the state's hand-over)."""
        H, P, G, N = self.heads, self.head_dim, self.groups, self.state
        return {
            "state_ops_per_row": 4.0 * layers * H * P * N,
            "scan_ops_per_row": layers * (
                2.0 * self.chunk_size * (G * N + H * P) + 4.0 * H * P * N),
        }


def mamba_params(k, mx: Mamba2) -> dict:
    """A mixer's own parameters from the keys ``k`` yields (six of
    them). ``dt_bias`` is the inverse softplus of a step drawn
    log-uniform in ``time_step``'s [min, max] (floored at its floor),
    ``A_log`` the log of a decay rate drawn uniform in ``A_RANGE``,
    ``D`` ones: the initialiser under which a state neither dies nor
    explodes over thousands of tokens."""
    m, dt, H = mx.hidden_size, mx.dtype, mx.heads
    t_min, t_max, t_floor = mx.time_step
    step = jnp.exp(jax.random.uniform(
        next(k), (H,), F32, jnp.log(t_min), jnp.log(t_max)))
    step = jnp.maximum(step, t_floor)
    return dict(
        w_in=normal(next(k), (m, mx.d_inner + mx.conv_dim + H), dt),
        conv_w=normal(next(k), (mx.conv_kernel, mx.conv_dim), dt, CONV_STD),
        conv_b=normal(next(k), (mx.conv_dim,), dt),
        dt_bias=step + jnp.log(-jnp.expm1(-step)),
        A_log=jnp.log(jax.random.uniform(next(k), (H,), F32, *A_RANGE)),
        D=jnp.ones((H,), F32),
        norm=jnp.ones((mx.d_inner,), dt),
        w_out=normal(next(k), (mx.d_inner, m), dt))


def _mamba_in(h, p, mx: Mamba2):
    """h [..., m] -> (z [..., d_inner], xBC before its convolution
    [..., conv_dim], dt before its softplus [..., heads])."""
    proj = jnp.dot(h, p["w_in"])
    a, b = mx.d_inner, mx.d_inner + mx.conv_dim
    return proj[..., :a], proj[..., a:b], proj[..., b:]


def _convolved(rows, p):
    """The convolution's output on its window: ``rows`` [..., kernel,
    conv_dim], oldest first -> silu(sum_k rows_k w_k + bias) [...,
    conv_dim]."""
    out = (rows.astype(F32) * p["conv_w"].astype(F32)).sum(-2) \
        + p["conv_b"].astype(F32)
    return jax.nn.silu(out).astype(rows.dtype)


def _ssm_inputs(xBC, dt, p, mx: Mamba2):
    """The convolved xBC [..., conv_dim] and raw dt [..., H] -> (x
    [..., H, P], B, C [..., G, N] in the served dtype; dt [..., H]
    after its softplus and A [H], float32)."""
    H, P, G, N = mx.heads, mx.head_dim, mx.groups, mx.state
    lead = xBC.shape[:-1]
    x = xBC[..., :H * P].reshape(*lead, H, P)
    B = xBC[..., H * P:H * P + G * N].reshape(*lead, G, N)
    C = xBC[..., H * P + G * N:].reshape(*lead, G, N)
    dt = jax.nn.softplus(dt.astype(F32) + p["dt_bias"])
    return x, B, C, dt, -jnp.exp(p["A_log"])


def _mamba_out(y, x, z, p, mx: Mamba2):
    """y [..., H, P] float32 (``S C``), x [..., H, P], z [..., d_inner]
    -> the mixer's output [..., m]: the D skip, the gate, the grouped
    norm, ``W_out``."""
    y = y + p["D"][:, None] * x.astype(F32)
    y = y.reshape(z.shape) * jax.nn.silu(z.astype(F32))
    g = y.reshape(*y.shape[:-1], mx.groups, -1)
    g = g * jax.lax.rsqrt((g * g).mean(-1, keepdims=True) + mx.eps)
    y = (g.reshape(y.shape) * p["norm"].astype(F32)).astype(z.dtype)
    return jnp.dot(y, p["w_out"])


def mamba_step(h, p, mx: Mamba2, li: int, slots, s_pool, c_pool):
    """The mixer of a decode step: h [B, 1, m] -> (out [B, 1, m],
    s_pool, c_pool) with the lanes' slots of layer ``li`` moved on by
    one token, in place."""
    z, xBC, dt = _mamba_in(h[:, 0], p, mx)
    rows = jnp.concatenate([c_pool[li, slots], xBC[:, None]], 1)
    c_pool = c_pool.at[li, slots].set(rows[:, 1:])
    xs, Bs, Cs, dt, A = _ssm_inputs(_convolved(rows, p), dt, p, mx)
    with jax.named_scope("ssm_update"):
        y, s_pool = ssm.ssm_update(
            s_pool, li, slots, jnp.exp(dt * A),
            dt[..., None] * xs.astype(F32), Bs, Cs)
    return _mamba_out(y, xs, z, p, mx)[:, None], s_pool, c_pool


def mamba_chunk(h, p, mx: Mamba2, li: int, span, s_pool, c_pool):
    """The mixer of a prefill span: h [1, n, m]; ``span`` = (slot read,
    slot written, last real row, the real rows' mask [n, 1], whether
    the span starts a sequence) -> (out [1, n, m], s_pool, c_pool) with
    the state and the convolution rows at the span's end in the slot
    written."""
    src, dst, last, real, fresh = span
    n, K = h.shape[1], mx.conv_kernel
    z, xBC, dt = _mamba_in(h[0], p, mx)
    prev = jnp.where(fresh, 0, c_pool[li, src])          # [K-1, conv]
    rows = jnp.concatenate([prev, xBC])                  # [K-1+n, conv]
    c_pool = c_pool.at[li, dst].set(
        jax.lax.dynamic_slice_in_dim(rows, last + 1, K - 1))
    window = jnp.stack([rows[i:i + n] for i in range(K)], 1)
    xs, Bs, Cs, dt, A = _ssm_inputs(_convolved(window, p), dt, p, mx)
    with jax.named_scope("ssm_scan"):
        y, S = ssm.ssd_scan(
            xs, jnp.where(real, dt, 0.0), A, Bs, Cs,
            jnp.where(fresh, 0.0, s_pool[li, src]), mx.chunk_size)
    s_pool = s_pool.at[li, dst].set(S)
    return _mamba_out(y, xs, z, p, mx)[None], s_pool, c_pool
