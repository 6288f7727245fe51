"""Flash (blockwise, online-softmax) attention.

Two implementations behind one entry point:

  * ``_flash_reference`` — blockwise online-softmax in pure jax (lax.scan
    over key blocks). O(seq) memory instead of O(seq²); runs on any backend
    and is the autodiff path.
  * ``flash_attention_pallas`` — Pallas TPU kernel (ops/pallas/flash.py)
    keeping the running max/denominator in VMEM; always used on TPU. A
    kernel that fails to lower raises: there is no fallback.

The reference framework has no attention kernels at all (it orchestrates
external libs); this is part of the native model stack.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .attention import NEG_INF

DEFAULT_BLOCK = 512


def flash_attention(q, k, v, *, causal: bool = True,
                    block_size: int = DEFAULT_BLOCK,
                    use_pallas: Optional[bool] = None,
                    layout: str = "bshd"):
    """q,k,v: [batch, seq, heads, head_dim] (layout="bshd") or
    [batch, heads, seq, head_dim] (layout="bhsd", the kernel's native
    layout — no transposes); output matches the input layout.

    Softmax statistics are computed in f32; inputs may be bf16.
    """
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if use_pallas:
        from .pallas.flash import flash_attention_pallas

        return flash_attention_pallas(
            q, k, v, causal=causal,
            block_q=block_size, block_k=block_size, layout=layout)
    return _reference_any_layout(q, k, v, causal, block_size, layout)


def _reference_any_layout(q, k, v, causal, block_size, layout):
    """The jax reference path is bshd-native; bhsd callers transpose
    around it (the off-TPU path, not the perf path)."""
    if layout == "bhsd":
        t = lambda x: x.transpose(0, 2, 1, 3)
        return t(_flash_reference(t(q), t(k), t(v), causal=causal,
                                  block_size=block_size))
    return _flash_reference(q, k, v, causal=causal, block_size=block_size)


def _flash_reference(q, k, v, *, causal: bool, block_size: int):
    b, sq, h, d = q.shape
    sk = k.shape[1]
    orig_sq = sq
    blk = min(block_size, sq, sk)
    # Pad seq dims up to a block multiple.
    pad_q = (-sq) % blk
    pad_k = (-sk) % blk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        sq += pad_q
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        sk += pad_k
    orig_sk = sk - pad_k
    nq, nk = sq // blk, sk // blk
    scale = d ** -0.5

    # [b, h, nq, blk, d] query blocks.
    qb = q.transpose(0, 2, 1, 3).reshape(b, h, nq, blk, d)
    kb = k.transpose(0, 2, 1, 3).reshape(b, h, nk, blk, d)
    vb = v.transpose(0, 2, 1, 3).reshape(b, h, nk, blk, d)

    def per_qblock(qi, q_blk):
        # Online softmax over key blocks.
        m0 = jnp.full((b, h, blk), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((b, h, blk), jnp.float32)
        acc0 = jnp.zeros((b, h, blk, d), jnp.float32)

        def body(carry, kj):
            m, l, acc = carry
            k_blk = kb[:, :, kj]
            v_blk = vb[:, :, kj]
            s = jnp.einsum("bhqd,bhkd->bhqk", q_blk, k_blk,
                           preferred_element_type=jnp.float32) * scale
            k_pos = kj * blk + jnp.arange(blk)[None, :]
            if causal:
                q_pos = qi * blk + jnp.arange(blk)[:, None]
                s = jnp.where(q_pos >= k_pos, s, NEG_INF)
            elif pad_k:
                # Causal masking already excludes padded keys (they sit at
                # positions beyond every real query); non-causal must mask
                # them explicitly.
                s = jnp.where(k_pos < orig_sk, s, NEG_INF)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            correction = jnp.exp(m - m_new)
            l_new = l * correction + p.sum(-1)
            acc_new = acc * correction[..., None] + jnp.einsum(
                "bhqk,bhkd->bhqd", p, v_blk.astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        if causal:
            # Only key blocks at or before this query block contribute.
            n_valid = qi + 1
            ks = jnp.arange(nk)

            def masked_body(carry, kj):
                new_carry, _ = body(carry, kj)
                keep = kj < n_valid
                carry = jax.tree_util.tree_map(
                    lambda n, o: jnp.where(keep, n, o), new_carry, carry)
                return carry, None

            (m, l, acc), _ = jax.lax.scan(masked_body, (m0, l0, acc0), ks)
        else:
            (m, l, acc), _ = jax.lax.scan(body, (m0, l0, acc0), jnp.arange(nk))
        return acc / jnp.maximum(l[..., None], 1e-30)

    outs = [per_qblock(i, qb[:, :, i]) for i in range(nq)]
    out = jnp.stack(outs, axis=2)  # [b,h,nq,blk,d]
    out = out.reshape(b, h, sq, d).transpose(0, 2, 1, 3)
    return out[:, :orig_sq].astype(q.dtype)
