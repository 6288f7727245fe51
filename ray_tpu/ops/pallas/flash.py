"""Flash attention as a Pallas TPU kernel.

Blockwise online-softmax attention with the running max / denominator /
accumulator resident in VMEM (carried through the key-block loop), one
MXU matmul per (q-block, k-block) pair plus one for the PV product.
Causal programs skip key blocks strictly above the diagonal — the inner
loop bound is computed from the q-block index, so the causal kernel does
~half the work of the dense one.

Layout: q,k,v arrive as [batch, seq, heads, head_dim] (the model's native
layout) and are transposed to [batch, heads, seq, head_dim] around the
kernel so each block's trailing dims are (seq_block, head_dim) — the TPU
lowering requires the last two block dims to be (8,128)-divisible or
equal to the array dims, which a heads-minor layout cannot satisfy.
K/V for the whole (batch, head) stay VMEM-resident across q-blocks (their
BlockSpec index does not depend on the q grid dimension, so Pallas keeps
the block loaded).

Backward: hand-written Pallas kernels. The forward additionally emits
the row logsumexp in a slim (…, 1) layout (a lane-broadcast layout was
measured to cost 100 MB/layer of residuals at the bench shape); the
backward recomputes p = exp(s − lse) blockwise. When the full-sequence
dq accumulator fits VMEM, a SINGLE fused kernel per (batch, head)
computes dq, dk and dv — s and p evaluated once per block pair (5
matmuls + 1 exp sweep vs 7 + 2 for the split dq / dkv kernels, which
remain as the long-sequence fallback). Memory stays O(seq) and every
matmul (q·kᵀ, dO·vᵀ, ds·k, pᵀ·dO, dsᵀ·q) runs on the MXU with f32
accumulation. Measured on v5e at the bench shape: the fused backward is
18% faster than the split kernels; kernel fwd speed matches jax's own
tuned TPU flash op at the same block size.

The reference framework has no attention kernels at all (it orchestrates
external libs; see SURVEY §2.4 — ring/flash attention are "not
implemented" upstream). This kernel is part of our native model stack.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..attention import NEG_INF

DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, *, blk_q: int, blk_k: int,
                nk: int, orig_sk: int, causal: bool, scale: float,
                lse_ref=None):
    """Primal-only variant reuses this with lse_ref=None, so inference
    calls skip the LSE side-output entirely (no wasted HBM writes)."""
    qi = pl.program_id(2)
    q = q_ref[0, 0, :, :]                      # (blk_q, d), input dtype
    d = q.shape[-1]

    m0 = jnp.full((blk_q, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((blk_q, 1), jnp.float32)
    acc0 = jnp.zeros((blk_q, d), jnp.float32)

    q_pos = qi * blk_q + jax.lax.broadcasted_iota(
        jnp.int32, (blk_q, blk_k), 0)

    def body(j, carry):
        m, l, acc = carry
        k_blk = k_ref[0, 0, pl.ds(j * blk_k, blk_k), :]   # (blk_k, d)
        v_blk = v_ref[0, 0, pl.ds(j * blk_k, blk_k), :]
        # q·kᵀ on the MXU in input precision, accumulated f32.
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (blk_q, blk_k)
        k_pos = j * blk_k + jax.lax.broadcasted_iota(
            jnp.int32, (blk_q, blk_k), 1)
        mask = k_pos < orig_sk                 # padded keys contribute 0
        if causal:
            mask = jnp.logical_and(mask, q_pos >= k_pos)
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1, keepdims=True))
        p = jnp.exp(s - m_new)                 # (blk_q, blk_k) f32
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # (blk_q, d)
        acc_new = acc * corr + pv
        return m_new, l_new, acc_new

    if causal:
        # Key blocks strictly above the diagonal never contribute.
        upper = jnp.minimum(((qi + 1) * blk_q + blk_k - 1) // blk_k, nk)
    else:
        upper = nk
    m, l, acc = jax.lax.fori_loop(0, upper, body, (m0, l0, acc0))
    o_ref[0, 0, :, :] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    if lse_ref is not None:
        # Row logsumexp, saved for the backward's softmax recompute.
        # Stored as (blk_q, 1) — NOT lane-broadcast to 128: at GPT-2-small
        # bench shape the broadcast layout cost 100 MB/layer of HBM
        # residuals (the difference between remat-free fitting or OOMing).
        # Finite even for rows whose keys were all masked (m is then
        # NEG_INF, not -inf, so exp(s - lse) recomputes to a harmless
        # uniform p that the zero upstream gradient kills).
        lse_ref[0, 0, :, :] = m + jnp.log(jnp.maximum(l, 1e-30))


def _fwd_kernel_with_lse(q_ref, k_ref, v_ref, o_ref, lse_ref, **kw):
    _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref=lse_ref, **kw)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   *, blk_q: int, blk_k: int, nk: int, orig_sk: int,
                   causal: bool, scale: float):
    """dq for one q block: loop over (causal-limited) k blocks, recompute
    p from the saved LSE, dp = dO·Vᵀ, ds = p (dp − Δ) scale, dq += ds·K."""
    qi = pl.program_id(2)
    q = q_ref[0, 0, :, :]
    do = do_ref[0, 0, :, :]
    lse = lse_ref[0, 0, :, :1]                 # (blk_q, 1) f32
    delta = delta_ref[0, 0, :, :1]             # (blk_q, 1) f32
    d = q.shape[-1]
    q_pos = qi * blk_q + jax.lax.broadcasted_iota(
        jnp.int32, (blk_q, blk_k), 0)

    def body(j, dq_acc):
        k_blk = k_ref[0, 0, pl.ds(j * blk_k, blk_k), :]
        v_blk = v_ref[0, 0, pl.ds(j * blk_k, blk_k), :]
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        k_pos = j * blk_k + jax.lax.broadcasted_iota(
            jnp.int32, (blk_q, blk_k), 1)
        mask = k_pos < orig_sk
        if causal:
            mask = jnp.logical_and(mask, q_pos >= k_pos)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)         # (blk_q, blk_k)
        dp = jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)            # (blk_q, blk_k)
        ds = p * (dp - delta) * scale
        return dq_acc + jax.lax.dot_general(
            ds.astype(k_blk.dtype), k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # (blk_q, d)

    if causal:
        upper = jnp.minimum(((qi + 1) * blk_q + blk_k - 1) // blk_k, nk)
    else:
        upper = nk
    dq = jax.lax.fori_loop(0, upper, body,
                           jnp.zeros((blk_q, d), jnp.float32))
    dq_ref[0, 0, :, :] = dq.astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, *, blk_q: int, blk_k: int, nq: int,
                    orig_sk: int, causal: bool, scale: float):
    """dk/dv for one k block: loop over q blocks at/below the diagonal,
    recompute p, dv += pᵀ·dO, dk += dsᵀ·q."""
    ki = pl.program_id(2)
    k_blk = k_ref[0, 0, :, :]                  # (blk_k, d)
    v_blk = v_ref[0, 0, :, :]
    d = k_blk.shape[-1]
    k_pos = ki * blk_k + jax.lax.broadcasted_iota(
        jnp.int32, (blk_q, blk_k), 1)
    key_valid = k_pos < orig_sk

    def body(i, carry):
        dk_acc, dv_acc = carry
        q = q_ref[0, 0, pl.ds(i * blk_q, blk_q), :]
        do = do_ref[0, 0, pl.ds(i * blk_q, blk_q), :]
        lse = lse_ref[0, 0, pl.ds(i * blk_q, blk_q), :1]
        delta = delta_ref[0, 0, pl.ds(i * blk_q, blk_q), :1]
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale    # (blk_q, blk_k)
        mask = key_valid
        if causal:
            q_pos = i * blk_q + jax.lax.broadcasted_iota(
                jnp.int32, (blk_q, blk_k), 0)
            mask = jnp.logical_and(mask, q_pos >= k_pos)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        dv_acc = dv_acc + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # (blk_k, d)
        dp = jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)            # (blk_q, blk_k)
        ds = p * (dp - delta) * scale
        dk_acc = dk_acc + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # (blk_k, d)
        return dk_acc, dv_acc

    if causal:
        lower = (ki * blk_k) // blk_q  # first q block at/below the diagonal
    else:
        lower = 0
    dk, dv = jax.lax.fori_loop(
        lower, nq, body,
        (jnp.zeros((blk_k, d), jnp.float32),
         jnp.zeros((blk_k, d), jnp.float32)))
    dk_ref[0, 0, :, :] = dk.astype(dk_ref.dtype)
    dv_ref[0, 0, :, :] = dv.astype(dv_ref.dtype)


def _bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dk_ref, dv_ref, dq_acc,
                      *, blk_q: int, blk_k: int, nq: int, nk: int,
                      orig_sk: int, causal: bool, scale: float):
    """Single-pass backward for one (batch, head): outer loop over k
    blocks, inner over (causal-limited) q blocks. s and p are computed
    ONCE per block pair and reused for dv, dp, dk AND the dq accumulation
    (the split dq/dkv kernels each recompute them — 7 matmuls + 2 exp
    sweeps vs 5 matmuls + 1 here). dq accumulates across k blocks in a
    full-sequence f32 VMEM scratch, written out once at the end."""
    dq_acc[...] = jnp.zeros_like(dq_acc)

    def kb_body(j, _):
        k_blk = k_ref[0, 0, pl.ds(j * blk_k, blk_k), :]   # (blk_k, d)
        v_blk = v_ref[0, 0, pl.ds(j * blk_k, blk_k), :]
        k_pos = j * blk_k + jax.lax.broadcasted_iota(
            jnp.int32, (blk_q, blk_k), 1)
        key_valid = k_pos < orig_sk

        def qi_body(i, carry):
            dk_acc, dv_acc = carry
            qs = pl.ds(i * blk_q, blk_q)
            q = q_ref[0, 0, qs, :]
            do = do_ref[0, 0, qs, :]
            lse = lse_ref[0, 0, qs, :1]
            delta = delta_ref[0, 0, qs, :1]
            s = jax.lax.dot_general(
                q, k_blk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            mask = key_valid
            if causal:
                q_pos = i * blk_q + jax.lax.broadcasted_iota(
                    jnp.int32, (blk_q, blk_k), 0)
                mask = jnp.logical_and(mask, q_pos >= k_pos)
            p = jnp.where(mask, jnp.exp(s - lse), 0.0)
            dv_acc = dv_acc + jax.lax.dot_general(
                p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(
                do, v_blk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            ds = p * (dp - delta) * scale
            dk_acc = dk_acc + jax.lax.dot_general(
                ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dq_acc[qs, :] += jax.lax.dot_general(
                ds.astype(k_blk.dtype), k_blk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return dk_acc, dv_acc

        lower = (j * blk_k) // blk_q if causal else 0
        d = k_blk.shape[-1]
        dk, dv = jax.lax.fori_loop(
            lower, nq, qi_body,
            (jnp.zeros((blk_k, d), jnp.float32),
             jnp.zeros((blk_k, d), jnp.float32)))
        dk_ref[0, 0, pl.ds(j * blk_k, blk_k), :] = dk.astype(dk_ref.dtype)
        dv_ref[0, 0, pl.ds(j * blk_k, blk_k), :] = dv.astype(dv_ref.dtype)
        return 0

    jax.lax.fori_loop(0, nk, kb_body, 0)
    dq_ref[0, 0, :, :] = dq_acc[...].astype(dq_ref.dtype)


# The fused backward pins full-sequence q/dO/dq (+ f32 dq scratch) and
# k/v/dk/dv blocks in VMEM; its total estimated footprint must stay under
# this budget or the backward falls back to the split dq / dkv kernels
# (the long-sequence path).
_FUSED_BWD_VMEM_BUDGET = 12 * 1024 * 1024


def _fused_bwd_fits(sq_p: int, sk_p: int, d: int, itemsize: int) -> bool:
    q_side = sq_p * d * (3 * itemsize + 4)   # q, dO, dq + f32 scratch
    k_side = sk_p * d * (4 * itemsize)       # k, v, dk, dv
    return q_side + k_side <= _FUSED_BWD_VMEM_BUDGET


def _pad_seq(x, blk):
    """x: [b, h, s, d] — pad s up to a multiple of blk."""
    pad = (-x.shape[2]) % blk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))
    return x


def _fwd(q, k, v, *, causal: bool, blk_q: int, blk_k: int, interpret: bool,
         with_lse: bool = True, heads_major: bool = False):
    """Returns (out, residuals) — residuals are the padded heads-major
    tensors + LSE the backward kernels consume. The primal (inference)
    path calls with with_lse=False and skips the LSE side-output entirely
    (residuals None).

    heads_major=True means q,k,v arrive as [b, heads, seq, d] — the
    kernel's native layout — and the output stays in it: no transposes,
    and (crucially) the saved residuals are the SAME arrays the caller's
    weight-gradient einsums save, so autodiff keeps one copy instead of
    two layouts of every tensor."""
    if heads_major:
        b, h, sq, d = q.shape
        sk = k.shape[2]
    else:
        b, sq, h, d = q.shape
        sk = k.shape[1]
    blk_q = min(blk_q, max(sq, 8))
    blk_k = min(blk_k, max(sk, 8))
    # heads-major layout: trailing block dims become (seq_block, head_dim).
    qp = _pad_seq(q if heads_major else q.transpose(0, 2, 1, 3), blk_q)
    kp = _pad_seq(k if heads_major else k.transpose(0, 2, 1, 3), blk_k)
    vp = _pad_seq(v if heads_major else v.transpose(0, 2, 1, 3), blk_k)
    sq_p, sk_p = qp.shape[2], kp.shape[2]
    nq, nk = sq_p // blk_q, sk_p // blk_k
    scale = d ** -0.5

    opts = dict(blk_q=blk_q, blk_k=blk_k, nk=nk, orig_sk=sk,
                causal=causal, scale=scale)
    in_specs = [
        pl.BlockSpec((1, 1, blk_q, d), lambda bi, hi, qi: (bi, hi, qi, 0)),
        pl.BlockSpec((1, 1, sk_p, d), lambda bi, hi, qi: (bi, hi, 0, 0)),
        pl.BlockSpec((1, 1, sk_p, d), lambda bi, hi, qi: (bi, hi, 0, 0)),
    ]
    o_spec = pl.BlockSpec((1, 1, blk_q, d), lambda bi, hi, qi: (bi, hi, qi, 0))
    def out_layout(o):
        if o.shape[2] != sq:
            o = o[:, :, :sq]
        return o if heads_major else o.transpose(0, 2, 1, 3)

    if not with_lse:
        out = pl.pallas_call(
            functools.partial(_fwd_kernel, **opts),
            grid=(b, h, nq), in_specs=in_specs, out_specs=o_spec,
            out_shape=jax.ShapeDtypeStruct(qp.shape, q.dtype),
            interpret=interpret, name="flash_fwd",
        )(qp, kp, vp)
        return out_layout(out), None
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel_with_lse, **opts),
        grid=(b, h, nq),
        in_specs=in_specs,
        out_specs=[
            o_spec,
            pl.BlockSpec((1, 1, blk_q, 1),
                         lambda bi, hi, qi: (bi, hi, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(qp.shape, q.dtype),
            jax.ShapeDtypeStruct((b, h, sq_p, 1), jnp.float32),
        ],
        interpret=interpret, name="flash_fwd",
    )(qp, kp, vp)
    # checkpoint_name lets a names-aware remat policy SAVE the kernel's
    # outputs: with them (and q/k/v via dots_saveable) every backward
    # residual is saved, so the remat retrace dead-code-eliminates the
    # whole forward kernel — attention is never recomputed (the
    # "dots_flash" policy in models/gpt.py).
    from jax.ad_checkpoint import checkpoint_name

    out = checkpoint_name(out, "flash")
    lse = checkpoint_name(lse, "flash")
    return out_layout(out), (qp, kp, vp, out, lse, sq, sk)


def _bwd(res, g, *, causal: bool, blk_q: int, blk_k: int, interpret: bool,
         heads_major: bool = False):
    """Flash backward: dq kernel over q blocks + dk/dv kernel over k
    blocks, both recomputing p from the saved LSE (O(seq) memory, all
    matmuls on the MXU)."""
    qp, kp, vp, op, lse, sq, sk = res
    b, h, sq_p, d = qp.shape
    sk_p = kp.shape[2]
    blk_q = min(blk_q, max(sq_p, 8))
    blk_k = min(blk_k, max(sk_p, 8))
    nq, nk = sq_p // blk_q, sk_p // blk_k
    scale = d ** -0.5

    gp = _pad_seq(g if heads_major else g.transpose(0, 2, 1, 3), blk_q)
    # Δ_i = Σ_d dO_i·O_i (the softmax-jacobian row term), f32, same slim
    # (…, 1) layout as the LSE.
    delta = jnp.sum(gp.astype(jnp.float32) * op.astype(jnp.float32),
                    axis=-1, keepdims=True)  # [b,h,sq_p,1]

    if _fused_bwd_fits(sq_p, sk_p, d, qp.dtype.itemsize):
        full = pl.BlockSpec((1, 1, sq_p, d), lambda bi, hi: (bi, hi, 0, 0))
        kfull_f = pl.BlockSpec((1, 1, sk_p, d), lambda bi, hi: (bi, hi, 0, 0))
        rows = pl.BlockSpec((1, 1, sq_p, 1), lambda bi, hi: (bi, hi, 0, 0))
        dq, dk, dv = pl.pallas_call(
            functools.partial(_bwd_fused_kernel, blk_q=blk_q, blk_k=blk_k,
                              nq=nq, nk=nk, orig_sk=sk, causal=causal,
                              scale=scale),
            grid=(b, h),
            in_specs=[full, kfull_f, kfull_f, full, rows, rows],
            out_specs=[full, kfull_f, kfull_f],
            out_shape=[jax.ShapeDtypeStruct(qp.shape, qp.dtype),
                       jax.ShapeDtypeStruct(kp.shape, kp.dtype),
                       jax.ShapeDtypeStruct(kp.shape, kp.dtype)],
            scratch_shapes=[pltpu.VMEM((sq_p, d), jnp.float32)],
            interpret=interpret, name="flash_bwd_fused",
        )(qp, kp, vp, gp, lse, delta)

        def unpad(x, s):
            x = x[:, :, :s]
            return x if heads_major else x.transpose(0, 2, 1, 3)

        return unpad(dq, sq), unpad(dk, sk), unpad(dv, sk)

    q_spec = pl.BlockSpec((1, 1, blk_q, d), lambda bi, hi, qi: (bi, hi, qi, 0))
    kfull = pl.BlockSpec((1, 1, sk_p, d), lambda bi, hi, qi: (bi, hi, 0, 0))
    row_spec = pl.BlockSpec((1, 1, blk_q, 1),
                            lambda bi, hi, qi: (bi, hi, qi, 0))

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, blk_q=blk_q, blk_k=blk_k, nk=nk,
                          orig_sk=sk, causal=causal, scale=scale),
        grid=(b, h, nq),
        in_specs=[q_spec, kfull, kfull, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(qp.shape, qp.dtype),
        interpret=interpret, name="flash_bwd_dq",
    )(qp, kp, vp, gp, lse, delta)

    k_spec = pl.BlockSpec((1, 1, blk_k, d), lambda bi, hi, ki: (bi, hi, ki, 0))
    qfull = pl.BlockSpec((1, 1, sq_p, d), lambda bi, hi, ki: (bi, hi, 0, 0))
    rowfull = pl.BlockSpec((1, 1, sq_p, 1),
                           lambda bi, hi, ki: (bi, hi, 0, 0))

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, blk_q=blk_q, blk_k=blk_k, nq=nq,
                          orig_sk=sk, causal=causal, scale=scale),
        grid=(b, h, nk),
        in_specs=[qfull, k_spec, k_spec, qfull, rowfull, rowfull],
        out_specs=[k_spec, k_spec],
        out_shape=[jax.ShapeDtypeStruct(kp.shape, kp.dtype),
                   jax.ShapeDtypeStruct(kp.shape, kp.dtype)],
        interpret=interpret, name="flash_bwd_dkv",
    )(qp, kp, vp, gp, lse, delta)

    def unpad(x, s):
        x = x[:, :, :s]
        return x if heads_major else x.transpose(0, 2, 1, 3)

    return unpad(dq, sq), unpad(dk, sk), unpad(dv, sk)


@functools.lru_cache(maxsize=None)
def _make_op(causal: bool, blk_q: int, blk_k: int, interpret: bool,
             heads_major: bool):
    @jax.custom_vjp
    def op(q, k, v):
        # Primal (inference) path: no LSE side-output.
        out, _res = _fwd(q, k, v, causal=causal, blk_q=blk_q, blk_k=blk_k,
                         interpret=interpret, with_lse=False,
                         heads_major=heads_major)
        return out

    def fwd(q, k, v):
        return _fwd(q, k, v, causal=causal, blk_q=blk_q, blk_k=blk_k,
                    interpret=interpret, heads_major=heads_major)

    def bwd(res, g):
        return _bwd(res, g, causal=causal, blk_q=blk_q, blk_k=blk_k,
                    interpret=interpret, heads_major=heads_major)

    op.defvjp(fwd, bwd)
    return op


def flash_attention_pallas(q, k, v, *, causal: bool = True,
                           block_q: int = DEFAULT_BLOCK_Q,
                           block_k: int = DEFAULT_BLOCK_K,
                           interpret: bool | None = None,
                           layout: str = "bshd"):
    """q,k,v: [batch, seq, heads, head_dim] (layout="bshd", the model
    default) or [batch, heads, seq, head_dim] (layout="bhsd", the
    kernel's native layout — zero transposes and single-copy residuals;
    the output matches the input layout).

    GQA (fewer kv heads) is expanded before the kernel. ``interpret=None``
    auto-selects interpreter mode off-TPU so the same kernel is testable
    on the CPU backend.
    """
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    if layout not in ("bshd", "bhsd"):
        raise ValueError(f"layout must be bshd|bhsd, got {layout!r}")
    heads_major = layout == "bhsd"
    h_axis = 1 if heads_major else 2
    hq, hk = q.shape[h_axis], k.shape[h_axis]
    if hq != hk:
        if hq % hk:
            raise ValueError(f"GQA requires heads({hq}) % kv_heads({hk})==0")
        k = jnp.repeat(k, hq // hk, axis=h_axis)
        v = jnp.repeat(v, hq // hk, axis=h_axis)
    op = _make_op(causal, block_q, block_k, interpret, heads_major)
    return op(q, k, v)
