"""The paged kernels that copy their own pages (ops/pallas/paged_fetch.py):
``attn_latent``, ``attn_full`` and ``attn_window`` against their dense
float32 references over the shapes a block table can take, through the
Pallas interpreter; the run flags and the counter by value; and what a
lane may see of rows that are not its own: nothing.

The tables are 40 slots of 8 rows and ``_VMEM_BUDGET`` is held so that a
grid step carries 16 pages (two groups of 8): a lane has up to three
compute blocks, the last with one group the table does not have, and the
chain of copies crosses lanes of different lengths.
"""

import jax.numpy as jnp
import numpy as np
import pytest

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
