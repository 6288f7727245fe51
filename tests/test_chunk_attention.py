"""The prefill chunk's attention kernel (ops/pallas/chunk_attention.py)
against a dense float32 reference, under the Pallas interpreter at tiny
shapes. The module's block sizes are steered small IN THE TEST, so that
a few dozen keys are several key blocks and a dozen queries several
query blocks: the same index maps, skips and masks as at 17,920 keys."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.pallas import chunk_attention as ca

TOL = 2e-2      # bfloat16 outputs of magnitude ~1


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    monkeypatch.setattr(ca, "KEY_BLOCK", 32)
    monkeypatch.setattr(ca, "ROWS", 64)


def _operands(kvh, g, n, dk, dv, ds, keys, seed=0):
    ks = jax.random.split(jax.random.key(seed), 4)

    def rnd(key, shape):
        return jax.random.normal(key, shape, jnp.float32).astype(jnp.bfloat16)

    return (rnd(ks[0], (kvh, g, n, dk)), rnd(ks[1], (kvh, keys, dk - ds)),
            rnd(ks[2], (kvh, keys, dv)),
            rnd(ks[3], (keys, ds)) if ds else None)


def _both(q, k, v, shared, ctx_len, **kw):
    kw = dict(kw, scale=q.shape[-1] ** -0.5, k_shared=shared)
    got = ca.chunk_attention(q, k, v, jnp.int32(ctx_len), **kw)
    want = ca.chunk_attention_reference(q, k, v, ctx_len, **kw)
    assert got.shape == want.shape and got.dtype == q.dtype
    return (np.asarray(got, np.float32), np.asarray(want, np.float32))


@pytest.mark.parametrize("g", [1, 6, 8])
@pytest.mark.parametrize("dk, dv, ds", [(32, 32, 0), (48, 32, 0),
                                        (48, 32, 16)],
                         ids=["dk_eq_dv", "dk_wider", "shared_key_part"])
def test_kernel_equals_the_dense_reference(g, dk, dv, ds):
    """Grouped queries (Laguna's 6 and 8, Kimi's 1), key and value
    widths that differ (Kimi's 192 / 128), and a key's trailing columns
    shared by every head (Kimi's one rotary key a token)."""
    S, n = 96, 40
    q, k, v, shared = _operands(2, g, n, dk, dv, ds, S + n)
    got, want = _both(q, k, v, shared, 70, ctx_slots=S)
    assert np.abs(got - want).max() < TOL


@pytest.mark.parametrize("ctx_len", [0, 1, 45, 64, 96],
                         ids=["empty", "one", "mid_block", "block_edge",
                              "whole_table"])
def test_context_length_is_data(ctx_len):
    """One compiled call, any context length: none (a prompt's first
    span behind a table), inside a key block, on a block's edge, the
    whole table."""
    S, n = 96, 24
    q, k, v, _ = _operands(2, 2, n, 32, 32, 0, S + n, seed=1)
    got, want = _both(q, k, v, None, ctx_len, ctx_slots=S)
    assert np.abs(got - want).max() < TOL


def test_no_table_at_all():
    """A span from a prompt's start: no context slots, causal alone."""
    q, k, v, _ = _operands(2, 2, 24, 32, 32, 0, 24, seed=2)
    got, want = _both(q, k, v, None, 0, ctx_slots=0)
    assert np.abs(got - want).max() < TOL


@pytest.mark.parametrize("n", [5, 16, 40, 72])
def test_span_lengths_that_are_not_whole_query_blocks(n):
    """ROWS 64 at g = 2 is 32 queries a block: under one, exactly
    half, one and a bit, two and a bit."""
    S = 64
    q, k, v, _ = _operands(2, 2, n, 32, 32, 0, S + n, seed=3)
    got, want = _both(q, k, v, None, 50, ctx_slots=S)
    assert np.abs(got - want).max() < TOL


@pytest.mark.parametrize("ctx_len", [0, 20, 64])
def test_dead_blocks_skipped_equal_dead_blocks_masked(ctx_len):
    """A table much longer than the context. The blocks past the
    context are never read: poison there (NaN keys and values, which a
    product would carry into every output) changes nothing, and the
    result is the reference's, which masks them."""
    S, n = 160, 24
    q, k, v, _ = _operands(2, 2, n, 32, 32, 0, S + n, seed=4)
    live = -(-max(ctx_len, 1) // 32) * 32 if ctx_len else 0
    dead = slice(live, S // 32 * 32)
    got = np.asarray(ca.chunk_attention(
        q, k.at[:, dead].set(jnp.nan), v.at[:, dead].set(jnp.nan),
        jnp.int32(ctx_len), ctx_slots=S, scale=32 ** -0.5), np.float32)
    clean, want = _both(q, k, v, None, ctx_len, ctx_slots=S)
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got, clean)
    assert np.abs(got - want).max() < TOL


def test_keys_after_the_span_are_padding():
    """A caller that builds K and V itself pads them to whole key
    blocks (``padded_keys``): whatever lies after the span is seen by no
    query, and the call adds no pad of its own."""
    S, n = 64, 20
    keys = ca.padded_keys(S + n)
    assert keys == 96 and keys % ca.KEY_BLOCK == 0
    q, k, v, _ = _operands(2, 2, n, 32, 32, 0, keys, seed=5)
    got, _ = _both(q, k, v, None, 64, ctx_slots=S)
    short, want = _both(q, k[:, :S + n], v[:, :S + n], None, 64, ctx_slots=S)
    np.testing.assert_array_equal(got, short)
    assert np.abs(got - want).max() < TOL


@pytest.mark.parametrize("base, ctx_len, window", [
    (0, 40, 16), (48, 100, 40), (32, 96, 64), (16, 16, 8)],
    ids=["narrow", "table_starts_late", "wide", "nothing_behind"])
def test_window_and_base(base, ctx_len, window):
    """The window kind: slot s of the table sits at ``base + s``, and a
    query sees only keys less than ``window`` positions behind it, in
    the context and in the span alike."""
    S, n = 64, 24
    q, k, v, _ = _operands(2, 2, n, 32, 32, 0, S + n, seed=6)
    got, want = _both(q, k, v, None, ctx_len, ctx_slots=S, base=base,
                      window=window)
    assert np.abs(got - want).max() < TOL


def test_reference_is_the_models_equation():
    """The dense reference itself against softmax(q k^T) v written out
    for one head, so that the kernel is not checked against its own
    reading of the mask."""
    S, n, ctx_len = 16, 4, 10
    q, k, v, _ = _operands(1, 1, n, 8, 8, 0, S + n, seed=7)
    want = np.asarray(ca.chunk_attention_reference(
        q, k, v, ctx_len, ctx_slots=S, scale=1.0), np.float32)[0, 0]
    qf, kf, vf = (np.asarray(x, np.float32)[0] for x in (q[:, 0], k, v))
    for i in range(n):
        seen = list(range(ctx_len)) + [S + j for j in range(i + 1)]
        s = qf[i] @ kf[seen].T
        p = np.exp(s - s.max())
        np.testing.assert_allclose(want[i], (p / p.sum()) @ vf[seen],
                                   atol=2e-2)


def test_operands_that_do_not_fit_are_refused():
    q, k, v, _ = _operands(2, 2, 8, 32, 32, 0, 40)
    with pytest.raises(ValueError, match="context slots"):
        ca.chunk_attention(q, k, v, 0, ctx_slots=40, scale=1.0)
