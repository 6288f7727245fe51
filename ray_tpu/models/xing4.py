"""Xing4.0 (XingChen-AGI, https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B;
``model_type`` ``xing4_0``): Kimi-K2's block (models/kimi_k2.py: latent
attention over one pool of latent rows, sigmoid-routed experts beside a
shared one) on a CHANGED RESIDUAL PATH, manifold-constrained
hyper-connections (mHC, arXiv:2512.24880, on hyper-connections,
arXiv:2409.19606), served through the generation engine. No loss and no
train step.

The residual is ``hc_mult`` = n streams of ``hidden_size`` = C a token,
``X`` in R^{n x C}. Around EACH sublayer ``F`` (attention, then the
MLP; each with its own pre-norm inside, ``F(h) = Attn(RMSNorm(h)) W_o``
and ``F(h) = MLP(RMSNorm(h))``, the sublayers themselves Kimi's,
unchanged) the same steps, with that sublayer's own ``Phi``, ``a``,
``b``:

  x = vec(X);  r = rsqrt(mean(x^2) + rms_norm_eps)
  m = (r x) Phi,  Phi [n C, n^2 + 2n], float32 accumulate
  H_pre  = sigmoid(a_pre m[0:n] + b_pre)                      [n]
  H_post = 2 sigmoid(a_post m[n:2n] + b_post)                 [n]
  M = exp(clip(a_res mat(m[2n:]) + b_res, mhc_h_res_clamp_min,
               mhc_h_res_clamp_max))                          [n, n]
  hc_sinkhorn_iters times:  M <- M / (rowsum(M) + hc_eps);
                            M <- M / (colsum(M) + hc_eps)
  H_res = M
  h = sum_i H_pre[i] X_i;  y = F(h)
  X'_i = sum_j H_res[i, j] X_j + H_post[i] y

Opening: every stream is the token's embedding. Closing: the streams
are summed, then the final RMSNorm and the untied head. Coefficients,
the Sinkhorn chain and both mixing sums are float32; streams, weights,
activations and the cache are ``dtype``. What the published config does
not settle (rows before columns, ``hc_eps`` in both denominators, the
clamp before ``exp``, opening by copies and closing by a sum, no scale
on the norm in front of ``Phi``, the size ``a`` and ``b`` are drawn at)
is listed under ``assumed`` in benchmark/configs/xing4-29b-serve.json;
``models/xing4_ref.py`` is the plain float32 reference.

**What is Kimi's is called, not copied.** The two served programs are
``kimi_k2.forward_step`` and ``kimi_k2.forward_prefill_chunk`` with
another ``Residual`` handed in: the streams are carried as ONE row
``vec(X)`` [b, r, n C] (ops/mhc.py says why not [.., n, C]), a layer is
two calls of ``mhc_pre`` and two of ``mhc_post`` (the Pallas kernels of
ops/mhc.py; ``mhc_pre_decode``, ``mhc_post_chunk`` and so on on a device
trace) around Kimi's
``_project`` + attention path and Kimi's ``mlp``. The cache is Kimi's
kind: one pool of latent rows of ``row_width``. The decode step's
counters are Kimi's four and ``mhc_res_err_x1e6``: 1e6 x the largest
distance from 1 of any row or column sum of any ``H_res`` of the step.

**Not held:** the config's one multi-token-prediction module
(``num_nextn_predict_layers`` 1) is a draft head for speculative
decoding; a deployment that serves without speculation drops its
parameters, and so does this module (the field is kept and builds
nothing).
"""

from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ..ops import mhc
from . import kimi_k2
from .kimi_k2 import KimiK2Config
from .layers import init_ends, normal, rmsnorm


@dataclass(frozen=True)
class Xing4Config(KimiK2Config):
    """Field names are the published config.json's, on Kimi-K2's
    (``experts_held`` / ``first_expert`` / ``max_seq`` / ``dtype`` as
    there); the defaults are Xing4.0-29B-A4B's."""
    vocab_size: int = 131072
    hidden_size: int = 3584
    intermediate_size: int = 9216
    moe_intermediate_size: int = 1024
    num_hidden_layers: int = 40
    num_attention_heads: int = 32
    q_lora_rank: int = 768
    n_routed_experts: int = 64
    num_experts_per_tok: int = 4
    first_k_dense_replace: int = 2
    routed_scaling_factor: float = 2.0
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    experts_held: int = 64
    max_seq: int = 4736
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp_min: float = -30.0
    mhc_h_res_clamp_max: float = 30.0
    num_nextn_predict_layers: int = 1       # declared; not held (docstring)

    def __post_init__(self):
        super().__post_init__()
        if not 1 <= self.hc_mult <= mhc.GROUP:
            raise ValueError(f"hc_mult={self.hc_mult}: ops/mhc.py lays out "
                             f"at most {mhc.GROUP} streams")

    @property
    def hc_outputs(self) -> int:
        """Columns of a sublayer's ``Phi``: n^2 + 2n."""
        return self.hc_mult * (self.hc_mult + 2)

    def num_params(self) -> int:
        """Parameters held here: Kimi's count and, a sublayer, ``Phi``,
        three ``a`` and ``b``."""
        per = self.hc_mult * self.hidden_size * self.hc_outputs \
            + 3 + self.hc_outputs
        return super().num_params() + 2 * self.num_hidden_layers * per


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

# ``a`` and ``b`` are drawn from the seed at these sizes (``assumed``:
# the published ones are learned). ``m`` = (r x) Phi has a standard
# deviation of 0.02 sqrt(n C) = 2.4 at the published widths, so an
# ``a`` near 0.5 moves a pre-activation by ~1.2 from token to token and
# every coefficient with it; at the 0.01 that hyper-connections are
# TRAINED from, the path would be one constant matrix and "made from
# the token" would go untested.
MHC_A_MEAN, MHC_A_STD, MHC_B_STD = 0.5, 0.1, 0.5
_MHC_FOLD = 1 << 16


def init(key, cfg: Xing4Config) -> dict:
    """Seeded random parameters: Kimi's (models/kimi_k2.py ``init``),
    and a layer's two sublayers' ``hc_attn`` / ``hc_mlp``."""
    return {
        **init_ends(jax.random.fold_in(key, 1 << 20), cfg),
        "layers": [init_layer(key, cfg, l)
                   for l in range(cfg.num_hidden_layers)],
    }


def init_layer(key, cfg: Xing4Config, l: int) -> dict:
    return {**kimi_k2.init_layer(key, cfg, l),
            **_init_mhc(jax.random.fold_in(jax.random.fold_in(key, l),
                                           _MHC_FOLD), cfg)}


@functools.partial(jax.jit, static_argnames=("cfg",))
def _init_mhc(key, cfg: Xing4Config) -> dict:
    def one(key):
        kp, ka, kb = jax.random.split(key, 3)
        return {
            "phi": normal(
                kp, (cfg.hc_mult * cfg.hidden_size, cfg.hc_outputs),
                cfg.dtype),
            "a": MHC_A_MEAN + normal(ka, (3,), jnp.float32, MHC_A_STD),
            "b": normal(kb, (cfg.hc_outputs,), jnp.float32, MHC_B_STD)}

    ka, km = jax.random.split(key)
    return {"hc_attn": one(ka), "hc_mlp": one(km)}


# ---------------------------------------------------------------------------
# The residual path
# ---------------------------------------------------------------------------

COUNTERS = kimi_k2.COUNTERS + ("mhc_res_err_x1e6",)


def _mhc_kwargs(cfg: Xing4Config) -> dict:
    return dict(n=cfg.hc_mult, iters=cfg.hc_sinkhorn_iters, eps=cfg.hc_eps,
                norm_eps=cfg.rms_norm_eps,
                clamp=(cfg.mhc_h_res_clamp_min, cfg.mhc_h_res_clamp_max))


def _residual(cfg: Xing4Config, slabs=None) -> kimi_k2.Residual:
    """The four-stream path as Kimi's two programs take one. ``slabs``,
    where given, collects each sublayer's coefficients' slab."""
    n, C, eps = cfg.hc_mult, cfg.hidden_size, cfg.rms_norm_eps
    kw = _mhc_kwargs(cfg)

    def around(X, hc, sublayer, program):
        """One sublayer on the streams X [b, r, n C]: (X', what the
        sublayer handed back beside its output). The kernels are
        ``mhc_pre_<program>`` / ``mhc_post_<program>`` on a device
        trace, as Kimi's grouped product is ``moe_experts_<program>``."""
        b, r, _ = X.shape
        X = X.reshape(b * r, n * C)
        with jax.named_scope("mhc_pre"):
            h, coef = mhc.mhc_pre(X, hc["phi"], hc["a"], hc["b"],
                                  name=f"mhc_pre_{program}", **kw)
        if slabs is not None:
            slabs.append(coef)
        y, extra = sublayer(h.reshape(b, r, C))
        with jax.named_scope("mhc_post"):
            X = mhc.mhc_post(X, y.reshape(b * r, C), coef, n=n,
                             name=f"mhc_post_{program}")
        return X.reshape(b, r, n * C), extra

    def block(X, p, cfg, attend, program):
        def attention(h):
            o = attend(rmsnorm(h, p["ln1"], eps), p)
            return jnp.einsum("brhd,hdm->brm", o, p["w_o"]), None

        def mlp(h):
            b, r, m = h.shape
            out, sizes = kimi_k2.mlp(
                rmsnorm(h, p["ln2"], eps).reshape(b * r, m), p, cfg,
                program)
            return out.reshape(b, r, m), sizes

        X, _ = around(X, p["hc_attn"], attention, program)
        return around(X, p["hc_mlp"], mlp, program)

    def close(X):
        streams = X.reshape(*X.shape[:-1], n, C).astype(jnp.float32)
        return streams.sum(-2).astype(X.dtype)

    return kimi_k2.Residual(open=lambda x: jnp.tile(x, (1, 1, n)),
                            block=block, close=close)


def forward_step(params, packed, pool, *, q: int, cfg: Xing4Config,
                 firsts=None):
    """One decode step: ``kimi_k2.forward_step`` on the four-stream
    path. Returns (logits [b, q, vocab], ids [b + 5, q] int32, pool):
    rows b on of ``ids`` are ``COUNTERS``."""
    slabs = []
    logits, ids, pool = kimi_k2.forward_step(
        params, packed, pool, q=q, cfg=cfg, firsts=firsts,
        residual=_residual(cfg, slabs))
    err = jnp.nan_to_num(
        mhc.res_err(jnp.concatenate(slabs), cfg.hc_mult) * 1e6, nan=2e9)
    row = jnp.broadcast_to(jnp.clip(err, 0, 2e9).astype(jnp.int32), (1, q))
    return logits, jnp.concatenate([ids, row]), pool


def forward_prefill_chunk(params, tokens, pool, table, cfg: Xing4Config):
    """Up to ``kimi_k2.CHUNK_SPANS`` spans as one program:
    ``kimi_k2.forward_prefill_chunk`` on the four-stream path."""
    return kimi_k2.forward_prefill_chunk(params, tokens, pool, table, cfg,
                                         residual=_residual(cfg))


# ---------------------------------------------------------------------------
# The serving seam
# ---------------------------------------------------------------------------


def cost_shape(cfg: Xing4Config) -> dict:
    """Kimi's cost description and the residual path's: a row passes
    ``Phi`` twice a layer (always-read weights), and MOVES, a sublayer,
    the n streams in and out and one stream in and out,
    ``(2 n C + 2 C)`` values, whatever implements it; its operations
    are the product with ``Phi``, the norm, the pre-mix and the
    post-mix."""
    n, C, L = cfg.hc_mult, cfg.hidden_size, cfg.num_hidden_layers
    base = kimi_k2.cost_shape(cfg)
    phi = 2 * L * n * C * cfg.hc_outputs
    return {
        **base,
        "matmul_weights": base["matmul_weights"] + phi,
        "streamed_params": lambda rows: base["streamed_params"](rows) + phi,
        "stream_bytes_per_row":
            2 * L * (2 * n * C + 2 * C) * cfg.dtype.itemsize,
        "stream_ops_per_row":
            2.0 * L * (2 * n * C + 2 * n * C + 2 * (n * n + n) * C),
    }


def serving(cfg: Xing4Config):
    """Kimi's seam (the cache is its kind: one pool of latent rows)
    with this family's parameters, programs, costs and counters."""
    return dataclasses.replace(
        kimi_k2.serving(cfg), init=init, step=forward_step,
        chunk=forward_prefill_chunk, cost=cost_shape(cfg),
        counters=COUNTERS)
