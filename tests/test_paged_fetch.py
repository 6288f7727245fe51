"""The paged kernels that copy their own pages (ops/pallas/paged_fetch.py):
``attn_latent``, ``attn_full`` and ``attn_window`` against their dense
float32 references over the shapes a block table can take, through the
Pallas interpreter; the run flags and the counter by value; and what a
lane may see of rows that are not its own: nothing.

The tables are 40 slots of 8 rows and ``_VMEM_BUDGET`` is held so that a
grid step carries 16 pages (two groups of 8): a lane has up to three
compute blocks, the last with one group the table does not have, and the
chain of copies crosses lanes of different lengths.

From "GPT-2's shapes" on: the stored kernel as models/gpt.py calls it
(heads of 64, one query head a KV head, no window, decode and verify
steps, the layer static or traced), the cases of the page-window kernel
it replaced (PR 59). Tolerances there: float32 within 2e-5 of the
reference (one fused online softmax against a dense one: rounding);
bfloat16 operands with float32 accumulation within 2e-2 (the error is
the operands' quantization, not the algorithm).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.attention import causal_attention
from ray_tpu.ops.pallas import paged_fetch

BS, MAX_NB, NUM_BLOCKS, LANES = 8, 40, 160, 3
SPAN = 16 * BS
FULL = MAX_NB * BS
CONTEXTS = {"one_on_zeros": 1, "ragged_tail": 2 * SPAN + 37,
            "whole_spans": 2 * SPAN, "full_table": FULL}
MAKERS = (paged_fetch._make_latent_call, paged_fetch._make_stored_call)


def _forget():
    for make in MAKERS:
        make.cache_clear()


@pytest.fixture
def budget(monkeypatch):
    """``budget(nbytes)`` holds ``_VMEM_BUDGET`` for one test, whatever
    else the session compiled: the makers cache by shape, not by
    budget."""
    def hold(nbytes):
        _forget()
        monkeypatch.setattr(paged_fetch, "_VMEM_BUDGET", nbytes)
    yield hold
    _forget()


@pytest.fixture
def sixteen_pages(budget):
    """Two run groups a grid step at these widths."""
    budget(16 * 9728 + 512)


def _tables(shape, ctx, rng):
    """``LANES`` block tables of the named shape, no block in two of
    them; slots past a lane's context hold 0 (the scratch block) where
    the shape says so, as the engine's do."""
    t = np.zeros((LANES, MAX_NB), np.int32)
    if ctx == 1:
        return t
    if shape == "shuffled":
        return rng.permutation(np.arange(1, NUM_BLOCKS))[
            :LANES * MAX_NB].reshape(LANES, MAX_NB).astype(np.int32)
    for lane in range(LANES):
        first = 1 + lane * (MAX_NB + 6)
        t[lane] = np.arange(first, first + MAX_NB)
        if shape == "broken_inside_a_group":
            t[lane, 11:] += 5
        if shape == "ends_inside_a_group":
            t[lane, -(-ctx // BS):] = 0
    return t


def _lens(ctx, q_len):
    """Lane 0 and 2 at the case's context, lane 1 a short ragged one;
    a lane's real query rows never outnumber its tokens."""
    lens = np.asarray([ctx, min(ctx, SPAN + 13), ctx], np.int32)
    q_lens = np.minimum(np.asarray([q_len, 1, max(1, q_len - 1)]), lens)
    return lens, q_lens.astype(np.int32)


def _close(got, want, q_lens, lanes=range(LANES)):
    for lane in lanes:                  # rows past q_lens are padding
        n = q_lens[lane]
        assert np.abs(np.asarray(got)[lane, :n]
                      - np.asarray(want)[lane, :n]).max() < 2e-5


def _case(kernel, rng, tables, lens, q_lens, q_len, num_blocks=NUM_BLOCKS):
    """(kernel call, reference call, the pools as numpy arrays) of one
    case; the calls take the pools, so a test can run them on others."""
    lanes = tables.shape[0]
    tables, lens_j, q_lens_j = map(jnp.asarray, (tables, lens, q_lens))
    if kernel == "latent":
        heads, rank, rope, width = 4, 32, 8, 128
        pool = np.zeros((2, num_blocks, BS, width), np.float32)
        pool[..., :rank + rope] = rng.standard_normal(
            (2, num_blocks, BS, rank + rope))
        q = np.zeros((lanes, q_len, heads, width), np.float32)
        q[..., :rank + rope] = rng.standard_normal(
            (lanes, q_len, heads, rank + rope))
        q = jnp.asarray(q)

        def args(pools):
            return q, jnp.asarray(pools[0]), 1, tables, lens_j, q_lens_j
        return (lambda pools: paged_fetch.paged_attention_latent(
                    *args(pools), rank=rank, scale=0.3),
                lambda pools: paged_fetch.paged_attention_latent_reference(
                    *args(pools), rank=rank, scale=0.3),
                [pool])
    hkv, group, d = 2, 2, 32
    pools = [rng.standard_normal((2, num_blocks, BS, hkv * d)).astype(
        np.float32) for _ in range(2)]
    q = jnp.asarray(rng.standard_normal(
        (lanes, q_len, hkv, group, d)).astype(np.float32))
    # A window's start lies inside the oldest block, and row 0 of a lane
    # still sees its own key.
    starts = np.minimum([5, 3, 7][:lanes], lens - q_lens) \
        if kernel == "stored_window" else np.zeros(lanes)
    starts = jnp.asarray(starts.astype(np.int32))

    def args(pools):
        return (q, jnp.asarray(pools[0]), jnp.asarray(pools[1]), 1, tables,
                lens_j, q_lens_j, starts)
    return (lambda pools: paged_fetch.paged_attention_stored(
                *args(pools), name="attn_test"),
            lambda pools: paged_fetch.paged_attention_stored_reference(
                *args(pools)),
            pools)


KERNELS = ["latent", "stored", "stored_window"]
SHAPES = ["one_run", "shuffled", "broken_inside_a_group",
          "ends_inside_a_group"]


@pytest.mark.parametrize("q_len", [1, 3])
@pytest.mark.parametrize("context", list(CONTEXTS))
@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("kernel", KERNELS)
def test_fetching_kernels_equal_their_dense_references(
        sixteen_pages, kernel, shape, context, q_len):
    rng = np.random.default_rng(len(shape) * 100 + len(context) + q_len)
    ctx = CONTEXTS[context]
    lens, q_lens = _lens(ctx, q_len)
    run, ref, pools = _case(kernel, rng, _tables(shape, ctx, rng), lens,
                            q_lens, q_len)
    got, want = run(pools), ref(pools)
    assert got.shape == want.shape
    _close(got, want, q_lens)


@pytest.mark.parametrize("shape", ["one_run", "shuffled"])
@pytest.mark.parametrize("kernel", KERNELS)
def test_rows_that_are_not_a_lanes_own_never_reach_its_output(
        sixteen_pages, kernel, shape):
    """Lane 0's whole cache is NaN, and so is everything lanes 1 and 2
    could be handed by mistake: the scratch block, every block no live
    slot names (the ones their dead table slots name among them), and
    the rows of their last live page past their last key. Lane 1's last
    block is 2 live pages of 16 and lands in the slot lane 0's blocks
    went through; lane 2's is 5. Their outputs are finite and equal to
    the clean pool's, bit for bit."""
    rng = np.random.default_rng(7)
    ctx, q_len = CONTEXTS["ragged_tail"], 3
    tables = _tables(shape, ctx, rng)
    lens, q_lens = _lens(ctx, q_len)
    run, ref, pools = _case(kernel, rng, tables, lens, q_lens, q_len)
    clean = run(pools)
    _close(clean, ref(pools), q_lens)
    dirty = [p.copy() for p in pools]
    for pool in dirty:
        owned = np.zeros(NUM_BLOCKS, bool)
        for lane in (1, 2):
            live = -(-lens[lane] // BS)
            owned[tables[lane, :live]] = True
            pool[:, tables[lane, live - 1], lens[lane] % BS:] = np.nan
        pool[:, ~owned] = np.nan
    got = np.asarray(run(dirty))
    assert np.isnan(got[0]).any()           # the planted rows were read
    assert np.isfinite(got[1:]).all()
    assert (got[1:] == np.asarray(clean)[1:]).all()


@pytest.mark.parametrize("kernel", ["latent", "stored"])
def test_a_pool_with_fewer_blocks_than_a_run(sixteen_pages, kernel):
    """Six blocks, tables of sixteen slots over them: a step carries 16
    pages, but a copy of eight would read past the pool (a wait builds
    its descriptor from ``pool[layer, 0:run]``), so a run is four."""
    rng = np.random.default_rng(11)
    tables = np.asarray([[1, 2, 3, 4, 2, 3, 4, 5, 1, 2, 3, 4, 5, 4, 3, 2],
                         [2, 3, 4, 5, 5, 4, 3, 2, 1, 2, 3, 4, 1, 2, 3, 5]],
                        np.int32)
    lens = np.asarray([16 * BS, 11 * BS + 3], np.int32)
    q_lens = np.asarray([1, 1], np.int32)
    run, ref, pools = _case(kernel, rng, tables, lens, q_lens, 1,
                            num_blocks=6)
    width = sum(p.shape[3] for p in pools)
    assert paged_fetch._geometry(width, 4, BS, 16, 6, 4) == (16, 4)
    flags, _ = paged_fetch._page_runs(
        jnp.asarray(tables), jnp.asarray(lens), BS, 4, 4)
    assert np.asarray(flags).tolist() == [[1, 1, 1, 0], [1, 0, 1, 0]]
    _close(run(pools), ref(pools), q_lens, lanes=range(2))


def _hand_made_tables():
    """20 slots of 8 rows: lane 0 holds 19 live slots (150 tokens), a
    whole run then a run broken at its fifth slot then three live slots
    of the table's short last group; lane 1 holds 16 live slots exactly,
    its second group ascending but for a repeat; lane 2 is a padded
    lane. 36 live slots."""
    tables = np.zeros((3, 20), np.int32)
    tables[0, :8] = np.arange(40, 48)
    tables[0, 8:16] = [9, 10, 11, 12, 14, 15, 16, 17]
    tables[0, 16:19] = [18, 19, 20]
    tables[1, :8] = [3, 4, 5, 6, 7, 8, 9, 11]
    tables[1, 8:16] = [20, 21, 22, 23, 24, 25, 26, 26]
    return tables, np.asarray([150, 128, 1], np.int32)


def test_run_flags_by_value():
    tables, lens = _hand_made_tables()
    flags, live = paged_fetch._page_runs(
        jnp.asarray(tables), jnp.asarray(lens), 8, 8, 3)
    assert np.asarray(flags).tolist() == [[1, 0, 0], [0, 0, 0], [0, 0, 0]]
    assert np.asarray(live).tolist() == [19, 16, 1]
    # A group whose last slot is dead is never a run however it reads:
    # lane 1's first group at 56 tokens (seven live slots), then at 57.
    tables[1, :8] = np.arange(3, 11)
    flags, _ = paged_fetch._page_runs(
        jnp.asarray(tables), jnp.asarray([150, 56, 1], np.int32), 8, 8, 3)
    assert np.asarray(flags).tolist() == [[1, 0, 0], [0, 0, 0], [0, 0, 0]]
    flags, _ = paged_fetch._page_runs(
        jnp.asarray(tables), jnp.asarray([150, 57, 1], np.int32), 8, 8, 3)
    assert np.asarray(flags).tolist() == [[1, 0, 0], [1, 0, 0], [0, 0, 0]]


@pytest.mark.parametrize("nbytes, run, in_runs", [
    # Runs of 8: lane 0's first group alone.
    (paged_fetch._VMEM_BUDGET, 8, 8),
    # VMEM holds four pages a step, so the kernel's runs are of 4 and
    # the counter's with them: lane 0's 40-43, 44-47, 9-12, 14-17 and
    # lane 1's 3-6, 20-23.
    (4 * 8960 + 512, 4, 24),
], ids=["runs_of_8", "vmem_caps_a_step_at_4_pages"])
def test_the_counter_counts_the_runs_the_kernel_takes(budget, nbytes, run,
                                                      in_runs):
    budget(nbytes)
    tables, lens = _hand_made_tables()
    pool = jnp.zeros((1, 64, 8, 128), jnp.float32)
    assert paged_fetch._geometry(128, 4, 8, 20, 64, 4)[1] == run
    share = paged_fetch.kv_pages_in_runs_x1000(
        jnp.asarray(tables), jnp.asarray(lens), pool, score_rows=4)
    assert int(share) == in_runs * 1000 // 36
    # K and V pools of half the width each are the same geometry.
    half = jnp.zeros((1, 64, 8, 64), jnp.float32)
    assert int(paged_fetch.kv_pages_in_runs_x1000(
        jnp.asarray(tables), jnp.asarray(lens), half, half,
        score_rows=4)) == int(share)


def test_one_helper_decides_the_run_size(monkeypatch, budget):
    """Both makers and the counter ask ``_geometry``, with the same
    arguments for the same pool, table and query rows."""
    budget(paged_fetch._VMEM_BUDGET)
    asked = []
    geometry = paged_fetch._geometry

    def spy(*args):
        asked.append(args)
        return geometry(*args)

    monkeypatch.setattr(paged_fetch, "_geometry", spy)
    rng = np.random.default_rng(3)
    tables = _tables("one_run", FULL, rng)
    lens, q_lens = _lens(FULL, 1)
    for kernel in ("latent", "stored"):
        run, _, pools = _case(kernel, rng, tables, lens, q_lens, 1)
        run(pools)
        paged_fetch.kv_pages_in_runs_x1000(
            jnp.asarray(tables), jnp.asarray(lens),
            *map(jnp.asarray, pools), score_rows=4)
    assert len(asked) == 4 and asked[0] == asked[1] and asked[2] == asked[3]


# -- GPT-2's shapes: heads of 64, no window, decode and verify (PR 59) --------

ATOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def _no_window(lens, q_len):
    """``starts`` of a layer without a window: below every row's own."""
    return jnp.full((len(lens),), -q_len, jnp.int32)


def _attend(q, k_pool, v_pool, layer, tables, lens, q_lens):
    """(kernel, reference) on one layer of stored pools, float32."""
    args = (q, k_pool, v_pool, layer, jnp.asarray(tables),
            jnp.asarray(lens), jnp.asarray(q_lens),
            _no_window(lens, q.shape[1]))
    return (np.asarray(paged_fetch.paged_attention_stored(
                *args, name="paged_decode"), np.float32),
            np.asarray(paged_fetch.paged_attention_stored_reference(
                *args), np.float32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("d", [64, 128], ids=["d64", "d128"])
@pytest.mark.parametrize("group", [1, 4], ids=["g1", "g4"])
@pytest.mark.parametrize("q_len", [1, 5], ids=["decode", "verify_q5"])
def test_cutting_into_compute_blocks_matches_reference(budget, q_len, group,
                                                       d, dtype):
    """What the cut into grid steps of several pages can get wrong, one
    lane each, against the plain reference: contexts of 1, one under,
    at and one over a compute block's edge, and the whole table; a
    table that is not a whole number of compute blocks (a lane's last
    block is partial); page ids that fall and repeat, within a lane and
    across lanes; padded lanes (context 1, table of zeros) between live
    ones; and, for verify, lanes with fewer real rows than q_len. Rows
    past a lane's q_lens attend its whole context in both, so the whole
    tensor compares."""
    budget(paged_fetch._VMEM_BUDGET)
    hkv, block_size, max_nb, num_blocks = 2, 8, 6, 40
    pages, run = paged_fetch._geometry(
        2 * hkv * d, hkv * q_len * group, block_size, max_nb, num_blocks,
        jnp.dtype(dtype).itemsize)
    span = pages * block_size
    assert 1 < pages < max_nb and max_nb % pages and run == pages, pages
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
    q_lens = np.minimum(lens, [1, 5, 1, 3, 5, 1, 2, 4][:len(lens)])
    q_lens = np.minimum(q_lens, q_len).astype(np.int32)

    kq, kk, kv = jax.random.split(jax.random.PRNGKey(q_len + group + d), 3)
    pool = lambda key: jax.random.normal(
        key, (3, num_blocks, block_size, hkv * d), dtype)
    q = jax.random.normal(kq, (len(lens), q_len, hkv, group, d), dtype)
    got, want = _attend(q, pool(kk), pool(kv), 2, tables, lens, q_lens)
    np.testing.assert_allclose(got, want, atol=ATOL[dtype], rtol=0)


def _laid_out(seq, block_size, num_blocks):
    """``seq`` [ctx, heads, d] laid into blocks 1.. of one stored layer
    (block 0 is scratch), and the table that names them."""
    ctx, heads, d = seq.shape
    nb = -(-ctx // block_size)
    pool = np.zeros((1, num_blocks, block_size, heads * d), np.float32)
    rows = np.pad(np.asarray(seq).reshape(ctx, heads * d),
                  ((0, nb * block_size - ctx), (0, 0)))
    pool[0, 1:nb + 1] = rows.reshape(nb, block_size, heads * d)
    return jnp.asarray(pool), np.arange(1, nb + 1, dtype=np.int32)[None]


@pytest.mark.parametrize("q_len", [1, 3], ids=["decode", "verify_q3"])
def test_a_step_is_the_last_rows_of_dense_causal_attention(q_len):
    """The decode step IS the last row of dense causal attention, and a
    verify step its last q_len rows (every one of which sees the
    sequence's FIRST key: without a window ``starts`` lies below zero):
    lay contiguous K/V into blocks as stored, attend with the kernel,
    compare with ops/attention.causal_attention's last positions."""
    d, heads, block_size, ctx = 64, 2, 8, 21
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(3), 3)
    k_seq = jax.random.normal(kk, (1, ctx, heads, d), jnp.float32)
    v_seq = jax.random.normal(kv, (1, ctx, heads, d), jnp.float32)
    q_seq = jax.random.normal(kq, (1, ctx, heads, d), jnp.float32)
    # A first key that would dominate any row that saw it.
    k_seq = k_seq.at[0, 0].set(q_seq[0, -q_len:].mean(0) * 4.0)
    dense = causal_attention(q_seq, k_seq, v_seq)[0, -q_len:]
    k_pool, table = _laid_out(k_seq[0], block_size, 6)
    v_pool, _ = _laid_out(v_seq[0], block_size, 6)
    q = q_seq[:, -q_len:].reshape(1, q_len, heads, 1, d)
    got, _ = _attend(q, k_pool, v_pool, 0, table,
                     np.asarray([ctx], np.int32),
                     np.asarray([q_len], np.int32))
    np.testing.assert_allclose(got[0, :, :, 0], np.asarray(dense),
                               atol=2e-5, rtol=0)


def test_verify_is_causal_within_the_speculative_span():
    """Write-then-attend: a lane's real rows are already in their
    slots, and row j must not see rows j+1..: perturbing a LATER
    speculative slot's K/V cannot change an earlier row's output, and
    changes its own."""
    rng = np.random.default_rng(8)
    hkv, d, bs, q_len = 1, 64, 4, 3
    pools = [jnp.asarray(rng.standard_normal((2, 8, bs, hkv * d)),
                         jnp.float32) for _ in range(2)]
    q = jnp.asarray(rng.standard_normal((1, q_len, hkv, 1, d)), jnp.float32)
    tables = np.asarray([[3, 6]], np.int32)
    ctx = 7
    lens, q_lens = np.asarray([ctx], np.int32), np.asarray([3], np.int32)
    out1, _ = _attend(q, *pools, 1, tables, lens, q_lens)
    # Perturb the LAST real slot (position ctx - 1, row 2's write site).
    blk, off = int(tables[0, (ctx - 1) // bs]), (ctx - 1) % bs
    out2, _ = _attend(q, pools[0].at[1, blk, off].add(100.0),
                      pools[1].at[1, blk, off].add(-50.0), 1, tables, lens,
                      q_lens)
    # Rows 0 and 1 see positions <= ctx-3 / ctx-2 only: unchanged.
    np.testing.assert_allclose(out1[0, :2], out2[0, :2], atol=2e-5, rtol=0)
    assert not np.allclose(out1[0, 2], out2[0, 2], atol=1e-3)


def test_scratch_block_garbage_is_masked():
    """Padded table slots point at block 0, and a padded lane (context
    1, table of zeros) reads it: whatever lives there must not reach a
    live lane's output."""
    rng = np.random.default_rng(4)
    hkv, d, bs = 2, 64, 4
    pools = [jnp.asarray(rng.standard_normal((1, 8, bs, hkv * d)),
                         jnp.float32) for _ in range(2)]
    q = jnp.asarray(rng.standard_normal((3, 1, hkv, 1, d)), jnp.float32)
    tables = np.asarray([[5, 2, 0, 0], [0, 0, 0, 0], [7, 0, 0, 0]], np.int32)
    lens, ones = np.asarray([6, 1, 3], np.int32), np.ones(3, np.int32)
    out1, ref = _attend(q, *pools, 0, tables, lens, ones)
    out2, _ = _attend(q, pools[0].at[:, 0].set(1e4),
                      pools[1].at[:, 0].set(-1e4), 0, tables, lens, ones)
    np.testing.assert_allclose(out1, ref, atol=2e-5, rtol=0)
    np.testing.assert_allclose(out1[[0, 2]], out2[[0, 2]], atol=2e-5, rtol=0)


@pytest.mark.parametrize("q_len", [1, 5], ids=["decode", "verify_q5"])
def test_a_traced_layer_under_scan_gives_the_static_layers_bits(q_len):
    """models/gpt.py runs its layers under one ``lax.scan``: the layer
    index is traced and rides into the kernel as a scalar-prefetch
    operand. Every layer of the stack through the scan equals, bit for
    bit, the call with that layer as a Python int, and the two differ
    from layer to layer (so the operand, not a constant, picks the
    pages)."""
    rng = np.random.default_rng(5)
    layers, hkv, d, bs, lanes = 3, 4, 64, 8, 3
    pools = [jnp.asarray(rng.standard_normal((layers, 24, bs, hkv * d)),
                         jnp.bfloat16) for _ in range(2)]
    q = jnp.asarray(rng.standard_normal((lanes, q_len, hkv, 1, d)),
                    jnp.bfloat16)
    tables = jnp.asarray(rng.permutation(np.arange(1, 24))[:lanes * 6]
                         .reshape(lanes, 6).astype(np.int32))
    lens = jnp.asarray([6 * bs, 13, 1], jnp.int32)
    q_lens = jnp.minimum(jnp.asarray([q_len, 2, 1]), q_len).astype(jnp.int32)

    def attend(layer):
        return paged_fetch.paged_attention_stored(
            q, *pools, layer, tables, lens, q_lens, _no_window(lens, q_len),
            name="paged_decode")

    _, scanned = jax.lax.scan(lambda c, i: (c, attend(i)), 0,
                              jnp.arange(layers))
    static = [np.asarray(attend(i), np.float32) for i in range(layers)]
    for i in range(layers):
        assert (np.asarray(scanned[i], np.float32) == static[i]).all()
    assert not (static[0] == static[1]).all()
