"""Paged decode-attention kernel numerics (interpret mode on CPU).

The kernel (ops/pallas/paged_decode.py) gathers K/V through per-sequence
block tables; ground truth is (a) the pure-jnp paged reference and
(b) the repo's dense causal_attention over the same contiguous K/V.

Tolerances: f32 matches the reference to atol 2e-5 (one fused online-
softmax accumulation vs a dense softmax — only rounding differs);
bf16 inputs with f32 accumulation sit within atol 2e-2 (bf16 has ~3
decimal digits; both paths accumulate in f32 so the error is input
quantization, not the algorithm).
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.ops.attention import causal_attention  # noqa: E402
from ray_tpu.ops.pallas.paged_decode import (  # noqa: E402
    _pages_per_block,
    paged_decode_attention,
    paged_decode_attention_reference,
    paged_verify_attention,
    paged_verify_attention_reference,
)

ATOL_F32 = 2e-5
ATOL_BF16 = 2e-2


def _paged_case(key, *, batch, hkv, group, d, num_blocks, block_size,
                max_nb, dtype):
    """Random pool + tables + context lens (block 0 kept as scratch,
    tables padded with 0 — the layout llm/kv_cache.py produces)."""
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (batch, hkv, group, d), dtype)
    k_pool = jax.random.normal(ks[1], (hkv, num_blocks, block_size, d),
                               dtype)
    v_pool = jax.random.normal(ks[2], (hkv, num_blocks, block_size, d),
                               dtype)
    rng = np.random.default_rng(0)
    tables = np.zeros((batch, max_nb), np.int32)
    lens = np.zeros((batch,), np.int32)
    # Distinct blocks per sequence, like the allocator grants them.
    avail = list(range(1, num_blocks))
    rng.shuffle(avail)
    for b in range(batch):
        nb = int(rng.integers(1, max_nb + 1))
        lens[b] = int(rng.integers((nb - 1) * block_size + 1,
                                   nb * block_size + 1))
        grant = [avail.pop() for _ in range(nb)]
        tables[b, :nb] = grant
    return q, k_pool, v_pool, jnp.asarray(tables), jnp.asarray(lens)


def test_matches_paged_reference_f32():
    q, k, v, tables, lens = _paged_case(
        jax.random.PRNGKey(0), batch=3, hkv=2, group=1, d=16,
        num_blocks=24, block_size=8, max_nb=3, dtype=jnp.float32)
    out = paged_decode_attention(q, k, v, tables, lens, interpret=True)
    ref = paged_decode_attention_reference(q, k, v, tables, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=ATOL_F32, rtol=0)


def test_matches_paged_reference_gqa_f32():
    """group > 1: query heads share their KV head's pool blocks."""
    q, k, v, tables, lens = _paged_case(
        jax.random.PRNGKey(1), batch=2, hkv=2, group=3, d=8,
        num_blocks=16, block_size=4, max_nb=4, dtype=jnp.float32)
    out = paged_decode_attention(q, k, v, tables, lens, interpret=True)
    ref = paged_decode_attention_reference(q, k, v, tables, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=ATOL_F32, rtol=0)


def test_matches_paged_reference_bf16():
    q, k, v, tables, lens = _paged_case(
        jax.random.PRNGKey(2), batch=2, hkv=2, group=2, d=16,
        num_blocks=12, block_size=8, max_nb=2, dtype=jnp.bfloat16)
    out = paged_decode_attention(q, k, v, tables, lens, interpret=True)
    ref = paged_decode_attention_reference(q, k, v, tables, lens)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=ATOL_BF16, rtol=0)


def test_matches_dense_causal_attention():
    """The decode step IS the last row of dense causal attention: lay
    contiguous K/V into blocks, attend with the paged kernel, compare
    against ops/attention.causal_attention's final position."""
    d, heads, block_size, ctx = 16, 2, 8, 21
    key = jax.random.PRNGKey(3)
    kq, kk, kv = jax.random.split(key, 3)
    k_seq = jax.random.normal(kk, (1, ctx, heads, d), jnp.float32)
    v_seq = jax.random.normal(kv, (1, ctx, heads, d), jnp.float32)
    q_seq = jax.random.normal(kq, (1, ctx, heads, d), jnp.float32)
    dense = causal_attention(q_seq, k_seq, v_seq)[0, -1]   # [heads, d]

    nb = -(-ctx // block_size)
    num_blocks = nb + 2
    k_pool = np.zeros((heads, num_blocks, block_size, d), np.float32)
    v_pool = np.zeros((heads, num_blocks, block_size, d), np.float32)
    table = np.arange(1, nb + 1, dtype=np.int32)  # skip scratch block 0
    pad = nb * block_size - ctx
    k_pad = np.pad(np.asarray(k_seq[0]), ((0, pad), (0, 0), (0, 0)))
    v_pad = np.pad(np.asarray(v_seq[0]), ((0, pad), (0, 0), (0, 0)))
    for j in range(nb):
        blk = slice(j * block_size, (j + 1) * block_size)
        k_pool[:, j + 1] = k_pad[blk].transpose(1, 0, 2)
        v_pool[:, j + 1] = v_pad[blk].transpose(1, 0, 2)

    q = q_seq[0, -1].reshape(1, heads, 1, d)  # MHA: group == 1
    out = paged_decode_attention(
        q, jnp.asarray(k_pool), jnp.asarray(v_pool),
        jnp.asarray(table)[None], jnp.asarray([ctx], jnp.int32),
        interpret=True)
    np.testing.assert_allclose(np.asarray(out[0, :, 0]),
                               np.asarray(dense),
                               atol=ATOL_F32, rtol=0)


def _verify_case(key, *, batch, q_len, hkv, group, d, num_blocks,
                 block_size, max_nb, dtype):
    """Verify-step layout: each lane's last q_lens[b] context slots ARE
    its query rows (write-then-attend), lanes padded to q_len rows."""
    base = _paged_case(key, batch=batch, hkv=hkv, group=group, d=d,
                       num_blocks=num_blocks, block_size=block_size,
                       max_nb=max_nb, dtype=dtype)
    _, k_pool, v_pool, tables, lens = base
    rng = np.random.default_rng(7)
    q_lens = np.array([int(rng.integers(1, min(q_len, int(lens[b])) + 1))
                       for b in range(batch)], np.int32)
    q = jax.random.normal(jax.random.split(key, 5)[4],
                          (batch, q_len, hkv, group, d), dtype)
    return q, k_pool, v_pool, tables, lens, jnp.asarray(q_lens)


def test_verify_matches_reference_qlen_gt1_f32():
    q, k, v, tables, lens, q_lens = _verify_case(
        jax.random.PRNGKey(5), batch=3, q_len=4, hkv=2, group=1, d=16,
        num_blocks=24, block_size=8, max_nb=3, dtype=jnp.float32)
    out = paged_verify_attention(q, k, v, tables, lens, q_lens,
                                 interpret=True)
    ref = paged_verify_attention_reference(q, k, v, tables, lens, q_lens)
    # Padding rows (>= q_lens[b]) are defined garbage in BOTH paths
    # (the clamped mask makes them attend the full context identically),
    # so the whole tensor compares.
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=ATOL_F32, rtol=0)


def test_verify_matches_reference_gqa_bf16():
    q, k, v, tables, lens, q_lens = _verify_case(
        jax.random.PRNGKey(6), batch=2, q_len=3, hkv=2, group=3, d=8,
        num_blocks=16, block_size=4, max_nb=4, dtype=jnp.bfloat16)
    out = paged_verify_attention(q, k, v, tables, lens, q_lens,
                                 interpret=True)
    ref = paged_verify_attention_reference(q, k, v, tables, lens, q_lens)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=ATOL_BF16, rtol=0)


def test_verify_qlen1_equals_decode_kernel():
    """A verify pass with one real row per lane IS the decode step —
    the generalized mask must degenerate exactly."""
    q, k, v, tables, lens = _paged_case(
        jax.random.PRNGKey(7), batch=3, hkv=2, group=2, d=16,
        num_blocks=24, block_size=8, max_nb=3, dtype=jnp.float32)
    dec = paged_decode_attention(q, k, v, tables, lens, interpret=True)
    ver = paged_verify_attention(q[:, None], k, v, tables, lens,
                                 jnp.ones((3,), jnp.int32),
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(ver[:, 0]), np.asarray(dec),
                               atol=ATOL_F32, rtol=0)


def test_verify_causal_within_speculative_span():
    """Row j must not see rows j+1..: perturbing a LATER speculative
    slot's K/V cannot change an earlier row's output."""
    q, k, v, tables, lens, _ = _verify_case(
        jax.random.PRNGKey(8), batch=1, q_len=3, hkv=1, group=1, d=8,
        num_blocks=8, block_size=4, max_nb=2, dtype=jnp.float32)
    q_lens = jnp.asarray([3], jnp.int32)
    lens = jnp.maximum(lens, 3)            # room for 3 real rows
    out1 = paged_verify_attention(q, k, v, tables, lens, q_lens,
                                  interpret=True)
    # Perturb the LAST real slot (position lens-1, row 2's write site).
    ctx = int(lens[0])
    bs = k.shape[2]
    blk = int(tables[0, (ctx - 1) // bs])
    k2 = k.at[:, blk, (ctx - 1) % bs].add(100.0)
    v2 = v.at[:, blk, (ctx - 1) % bs].add(-50.0)
    out2 = paged_verify_attention(q, k2, v2, tables, lens, q_lens,
                                  interpret=True)
    # Rows 0 and 1 see positions <= ctx-3 / ctx-2 only: unchanged.
    np.testing.assert_allclose(np.asarray(out1[0, :2]),
                               np.asarray(out2[0, :2]),
                               atol=ATOL_F32, rtol=0)
    # Row 2 attends its own slot: it must have moved.
    assert not np.allclose(np.asarray(out1[0, 2]),
                           np.asarray(out2[0, 2]), atol=1e-3)


def test_scratch_block_garbage_is_masked():
    """Padded table slots point at block 0; whatever lives there must
    not leak into the output."""
    q, k, v, tables, lens = _paged_case(
        jax.random.PRNGKey(4), batch=2, hkv=1, group=1, d=8,
        num_blocks=8, block_size=4, max_nb=4, dtype=jnp.float32)
    out1 = paged_decode_attention(q, k, v, tables, lens, interpret=True)
    k2 = k.at[:, 0].set(1e4)
    v2 = v.at[:, 0].set(-1e4)
    out2 = paged_decode_attention(q, k2, v2, tables, lens, interpret=True)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                               atol=ATOL_F32, rtol=0)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("d", [64, 128], ids=["d64", "d128"])
@pytest.mark.parametrize("group", [1, 4], ids=["g1", "g4"])
@pytest.mark.parametrize("q_len", [1, 5], ids=["decode", "verify_q5"])
def test_cutting_into_compute_blocks_matches_reference(q_len, group, d,
                                                       dtype):
    """What the cut into grid steps of several pages can get wrong, one
    lane each, against the plain references: contexts of 1, one under,
    at and one over a compute block's edge, and the whole table; a
    table that is not a whole number of compute blocks; page ids that
    fall and repeat, within a lane and across lanes; padded lanes
    (context 1, table of zeros) between live ones; and, for verify,
    lanes with fewer real rows than q_len."""
    hkv, block_size, max_nb, num_blocks = 2, 8, 6, 40
    pages = _pages_per_block(hkv, q_len * group, d, block_size, max_nb,
                             jnp.dtype(dtype).itemsize)
    span = pages * block_size
    assert 1 < pages < max_nb and max_nb % pages, pages
    full = max_nb * block_size
    lens = np.array([1, span - 1, 1, span, span + 1, 1, full, full - 3],
                    np.int32)
    live = lambda n: -(-int(n) // block_size)
    tables = np.zeros((len(lens), max_nb), np.int32)
    tables[0, :1] = [17]
    tables[1, :live(lens[1])] = np.arange(30, 30 - live(lens[1]), -1)
    tables[3, :live(lens[3])] = np.arange(9, 9 + live(lens[3]))
    # Lane 4 shares lane 3's pages and names its last one twice.
    tables[4, :live(lens[4])] = np.r_[tables[3, :live(lens[3])],
                                      tables[3, live(lens[3]) - 1]]
    tables[6] = [5, 4, 3, 5, 4, 3]          # falls, then repeats
    tables[7] = np.arange(39, 39 - max_nb, -1)
    # Lanes 2 and 5 are padding: context 1, table of zeros.
    q_lens = np.minimum(lens, [1, 5, 1, 3, 5, 1, 2, 4]).astype(np.int32)

    kq, kk, kv = jax.random.split(jax.random.PRNGKey(q_len + group + d),
                                  3)
    k_pool = jax.random.normal(kk, (hkv, num_blocks, block_size, d),
                               dtype)
    v_pool = jax.random.normal(kv, (hkv, num_blocks, block_size, d),
                               dtype)
    args = (k_pool, v_pool, jnp.asarray(tables), jnp.asarray(lens))
    if q_len == 1:
        q = jax.random.normal(kq, (len(lens), hkv, group, d), dtype)
        out = paged_decode_attention(q, *args, interpret=True)
        ref = paged_decode_attention_reference(q, *args)
    else:
        q = jax.random.normal(kq, (len(lens), q_len, hkv, group, d),
                              dtype)
        out = paged_verify_attention(q, *args, jnp.asarray(q_lens),
                                     interpret=True)
        ref = paged_verify_attention_reference(q, *args,
                                               jnp.asarray(q_lens))
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=ATOL_F32 if dtype == jnp.float32 else ATOL_BF16, rtol=0)
