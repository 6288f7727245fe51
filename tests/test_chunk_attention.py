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


# -- rows of several sequences (PR 67) ----------------------------------------

BS = 8      # a pool block, here: contexts lie in the table in whole blocks


def _described(spans, ctx_slots):
    """``spans``: (rows, ctx_len) of each sequence, their rows end to
    end and their contexts end to end in the one table, each in whole
    blocks. Returns (n, (lo, hi, first))."""
    lo, hi, first, row, slot = [], [], [], 0, 0
    for rows, ctx_len in spans:
        lo += [slot] * rows
        hi += [slot + ctx_len] * rows
        first += [row] * rows
        row += rows
        slot += -(-ctx_len // BS) * BS
    assert slot <= ctx_slots
    return row, tuple(jnp.asarray(x, jnp.int32) for x in (lo, hi, first))


def _one_by_one(q, k, v, shared, spans, ctx_slots, scale):
    """Each sequence's rows alone against its own context and its own
    rows, by the dense reference with no description: what a program a
    span computes."""
    out, row, slot = [], 0, 0
    for rows, ctx_len in spans:
        held = -(-ctx_len // BS) * BS
        pick = np.r_[slot:slot + held, ctx_slots + row:ctx_slots + row + rows]
        out.append(ca.chunk_attention_reference(
            q[:, :, row:row + rows], k[:, pick], v[:, pick], ctx_len,
            ctx_slots=held, scale=scale,
            k_shared=None if shared is None else shared[pick]))
        row += rows
        slot += held
    return np.asarray(jnp.concatenate(out, axis=2), np.float32)


SPANS = {
    "one": [(40, 70)],
    "two_ragged": [(16, 45), (24, 3)],
    "three_ragged": [(8, 19), (16, 0), (16, 64)],
    # ROWS 64 at g = 2 is 32 queries a block: rows 24..39 of the second
    # span lie in two query blocks, and block 0 holds rows of two spans
    "a_query_block_astride_two": [(24, 40), (40, 33)],
    "no_context_at_all": [(16, 0), (24, 0)],
}


@pytest.mark.parametrize("ds", [0, 16], ids=["whole_keys", "k_shared"])
@pytest.mark.parametrize("case", list(SPANS))
def test_rows_of_several_sequences_see_their_own_keys_alone(case, ds):
    """The per-row description against the dense reference given the
    same description, and against each span run alone with no
    description at all: a row attends exactly the keys it attends in a
    program of its own."""
    spans, S = SPANS[case], 160
    n, rows = _described(spans, S)
    q, k, v, shared = _operands(2, 2, n, 48, 32, ds, S + n, seed=8)
    got, want = _both(q, k, v, shared, 0, ctx_slots=S, rows=rows)
    assert np.abs(got - want).max() < TOL
    alone = _one_by_one(q, k, v, shared, spans, S, 48 ** -0.5)
    assert np.abs(got - alone).max() < TOL


def test_blocks_that_no_row_of_a_query_block_sees_are_never_read():
    """Key blocks before, between and behind what a query block's rows
    see are skipped: poison there changes nothing. Block 0 (slots 0-31)
    belongs to the first span, whose rows are query block 0; the second
    span's rows (query blocks 1-2) see slots 64-90 and their own rows."""
    S = 160
    n, rows = _described([(32, 32), (48, 27)], S)
    rows = (rows[0].at[32:].set(64), rows[1].at[:32].set(30)
            .at[32:].set(91), rows[2])
    q, k, v, _ = _operands(2, 2, n, 32, 32, 0, S + n, seed=9)
    clean, want = _both(q, k, v, None, 0, ctx_slots=S, rows=rows)
    assert np.abs(clean - want).max() < TOL
    runs = np.asarray(ca._block_runs(
        jnp.pad(jnp.stack(rows), ((0, 0), (0, 16)), mode="edge"),
        32, 32, S, 8))
    # [context run | span run | whole-context run] of each query block
    assert runs.tolist() == [[0, 0, 5, 5, 0, -1], [2, 2, 6, 6, 2, 1],
                             [2, 2, 6, 7, 2, 1]]
    dead = slice(32, 64)            # nobody's context
    got = np.asarray(ca.chunk_attention(
        q, k.at[:, dead].set(jnp.nan), v.at[:, dead].set(jnp.nan),
        jnp.int32(0), ctx_slots=S, scale=32 ** -0.5, rows=rows), np.float32)
    np.testing.assert_array_equal(got, clean)
    # ... and the first span's context is poison to the second span's
    # query blocks alone: rows 0..31 go NaN, rows 32.. do not.
    got = np.asarray(ca.chunk_attention(
        q, k.at[:, :32].set(jnp.nan), v, jnp.int32(0), ctx_slots=S,
        scale=32 ** -0.5, rows=rows), np.float32)
    assert np.isnan(got[:, :, :32]).all()
    np.testing.assert_array_equal(got[:, :, 32:], clean[:, :, 32:])


@pytest.mark.parametrize("ctx_len", [0, 45, 96])
def test_the_description_absent_gives_the_call_it_gave(ctx_len, monkeypatch):
    """No description: the call is built with the arguments it was
    built with before there was one (the scalar operand is ``[ctx_len,
    base]``, the kernel takes no fifth operand), and one sequence
    DESCRIBED gives that call's result bit for bit, the blocks it takes
    unmasked included."""
    S, n = 96, 40
    q, k, v, _ = _operands(2, 2, n, 32, 32, 0, S + n, seed=10)
    made, real = [], ca._make_call

    def spy(*args, **kw):
        call = real(*args, **kw)
        made.append((args, kw))
        return lambda sc, *ops: (made.append((sc.shape, len(ops))),
                                 call(sc, *ops))[1]

    monkeypatch.setattr(ca, "_make_call", spy)
    kw = dict(ctx_slots=S, scale=32 ** -0.5)
    plain = ca.chunk_attention(q, k, v, jnp.int32(ctx_len), **kw)
    assert made[0][1] == {} and len(made[0][0]) == 14
    assert made[1] == ((2,), 3)
    _, rows = _described([(n, ctx_len)], S)
    one = ca.chunk_attention(q, k, v, jnp.int32(0), rows=rows, **kw)
    assert made[2][1] == {"described": True} and made[3] == ((2 + 12,), 4)
    np.testing.assert_array_equal(np.asarray(plain, np.float32),
                                  np.asarray(one, np.float32))


def test_a_description_behind_a_window_is_refused():
    q, k, v, _ = _operands(2, 2, 8, 32, 32, 0, 40)
    _, rows = _described([(8, 20)], 32)
    with pytest.raises(ValueError, match="window"):
        ca.chunk_attention(q, k, v, 0, ctx_slots=32, scale=1.0, window=8,
                           rows=rows)
