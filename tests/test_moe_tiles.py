"""The grouped product's row tile follows the rows an expert gets
(ops/moe.py ``tile_rows``, PR 63): the rule as a table over the shapes
the benchmark's cells compile, and the product against a loop over the
experts at every tile the rule yields."""

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.ops import moe


# (cell's program, rows T, experts a token, experts routed OVER, tile)
RULE = [
    ("laguna_decode", 64, 8, 256, 16),
    ("laguna_chunk_128", 128, 8, 256, 16),
    ("laguna_chunk_512", 512, 8, 256, 16),
    ("kimi_decode", 64, 8, 384, 16),
    ("kimi_chunk_128", 128, 8, 384, 16),
    ("kimi_chunk_512", 512, 8, 384, 16),
    ("nemotron_decode", 64, 22, 512, 16),
    ("nemotron_chunk_128", 128, 22, 512, 16),
    ("nemotron_chunk_512", 512, 22, 512, 16),
    ("xing_decode", 64, 4, 64, 16),
    ("xing_chunk_512", 512, 4, 64, 64),
    ("xing_chunk_1024", 1024, 4, 64, 64),
    ("xing_chunk_1536", 1536, 4, 64, 64),
    ("xing_chunk_2048", 2048, 4, 64, 64),
    ("a_training_batch", 8192, 4, 64, 64),
    ("one_row_short_of_the_tall_tile", 511, 4, 64, 16),
]


@pytest.mark.parametrize("program, T, k, n_experts, tile", RULE,
                         ids=[r[0] for r in RULE])
def test_the_tile_follows_the_rows_an_expert_expects(program, T, k,
                                                     n_experts, tile):
    assert moe.tile_rows(T * k, n_experts) == tile
    # The buffer holds every assignment however they fall: all on one
    # expert, or a ragged tail on each.
    rows = moe.plan_rows(T * k, n_experts, tile)
    assert rows % tile == 0
    assert rows >= T * k + n_experts * (tile - 1)


@pytest.mark.parametrize("held, tile", [(12, 16), (None, 64)],
                         ids=["told_all_384", "held_share_read_as_whole"])
def test_a_held_share_says_how_many_experts_there_are(held, tile):
    """Kimi's chunk holds 12 of 384 experts: 512 rows at top-8 give an
    expert ~10 rows and the 16-row tile, where ``T * k / 12`` would
    have read 341 and taken the tall one. The kernel's name says which
    ran."""
    T, k, d, f = 512, 8, 16, 8
    key = jax.random.key(0)
    x = jnp.ones((T, d))
    experts = jax.random.randint(key, (T, k), 0, 384)
    weights = jnp.ones((T, k))
    w1, w2 = jnp.ones((12, d, 2 * f)), jnp.ones((12, f, d))
    said = {"n_experts": 384} if held else {}
    text = str(jax.make_jaxpr(lambda: moe.routed_experts(
        x, experts, weights, w1, w2, first=24, name="moe_experts_chunk",
        **said))())
    assert ("moe_experts_chunk_r" in text) == (tile > 16)
    if tile > 16:
        assert f"moe_experts_chunk_r{tile}" in text


def _loop(x, experts, weights, w1, w2, first=0):
    """Every held expert (SwiGLU) on every token in float32, kept by the
    router's weight where the token chose it."""
    f = w1.shape[2] // 2
    y = jnp.zeros(x.shape, jnp.float32)
    for e in range(w1.shape[0]):
        gu = x.astype(jnp.float32) @ w1[e].astype(jnp.float32)
        out = (jax.nn.silu(gu[:, :f]) * gu[:, f:]) \
            @ w2[e].astype(jnp.float32)
        y = y + jnp.where(experts == first + e, weights, 0).sum(-1)[
            :, None] * out
    return y


def _routing(T, k, n_experts, how):
    """``even``: a token's k distinct experts by a seeded draw.
    ``skewed``: expert 0 gets EVERY row, the last expert none."""
    scores = jax.random.uniform(jax.random.key(3), (T, n_experts))
    if how == "skewed":
        scores = scores.at[:, 0].set(2.0).at[:, -1].set(-1.0)
    top, experts = jax.lax.top_k(scores, k)
    return experts.astype(jnp.int32), top / top.sum(-1, keepdims=True)


@pytest.fixture
def rows_for(monkeypatch):
    """``rows_for(tile, n_experts, k)``: the rows T of a call that gets
    ``tile``. The rule yields 16 and 64; 32 and 128 are what it would
    yield with another tall tile, which the product takes as well."""
    def rows(tile, n_experts, k):
        if tile == moe.MIN_TILE_ROWS:
            T = (moe.TALL_TILE_FROM - 1) * n_experts // k
        else:
            monkeypatch.setattr(moe, "TALL_TILE_ROWS", tile)
            T = 2 * tile * n_experts // k
        assert moe.tile_rows(T * k, n_experts) == tile
        return T
    return rows


@pytest.mark.parametrize("how", ["even", "skewed"])
@pytest.mark.parametrize("tile", [16, 32, 64, 128])
def test_every_tile_computes_every_assignment(tile, how, rows_for):
    E, k, d, f = 4, 2, 16, 32
    T = rows_for(tile, E, k)
    key = jax.random.key(tile)
    x = jax.random.normal(key, (T, d))
    w1 = jax.random.normal(jax.random.fold_in(key, 1), (E, d, 2 * f)) * 0.2
    w2 = jax.random.normal(jax.random.fold_in(key, 2), (E, f, d)) * 0.2
    experts, weights = _routing(T, k, E, how)
    y, sizes = moe.routed_experts(x, experts, weights, w1, w2)
    assert int(sizes.sum()) == T * k
    if how == "skewed":
        assert int(sizes[0]) == T and int(sizes[-1]) == 0
    assert jnp.abs(y - _loop(x, experts, weights, w1, w2)).max() < 2e-5
    assert (jnp.abs(y).sum(-1) > 0).all()                 # no zero row


@pytest.mark.parametrize("how", ["even", "skewed"])
@pytest.mark.parametrize("tile", [16, 32, 64, 128])
def test_a_held_share_computes_its_own_at_every_tile(tile, how, rows_for):
    """Four of eight experts held, from expert 2; the tile follows all
    eight, which the call is told."""
    E, held, first, k, d, f = 8, 4, 2, 2, 16, 32
    T = rows_for(tile, E, k)
    key = jax.random.key(100 + tile)
    x = jax.random.normal(key, (T, d))
    w1 = jax.random.normal(jax.random.fold_in(key, 1),
                           (held, d, 2 * f)) * 0.2
    w2 = jax.random.normal(jax.random.fold_in(key, 2), (held, f, d)) * 0.2
    experts, weights = _routing(T, k, E, how)
    if how == "skewed":                 # expert 2 (held) gets every row
        experts = jnp.where(experts == 0, first,
                            jnp.where(experts == first, 0, experts))
    text = str(jax.make_jaxpr(lambda: moe.routed_experts(
        x, experts, weights, w1, w2, first=first, n_experts=E))())
    assert (f"moe_experts_r{tile}" in text) == (tile > 16)
    y, sizes = moe.routed_experts(x, experts, weights, w1, w2,
                                  first=first, n_experts=E)
    mine = (experts >= first) & (experts < first + held)
    assert int(sizes.sum()) == int(mine.sum())
    assert jnp.abs(
        y - _loop(x, experts, weights, w1, w2, first)).max() < 2e-5


@pytest.mark.parametrize("tile", [32, 64, 128])
def test_a_rows_result_does_not_depend_on_its_tile(tile, rows_for):
    """bfloat16 rows and weights, float32 accumulation over the whole of
    k in one product: the same row against the same expert reads the
    same whatever rows share its tile."""
    E, k, d, f = 4, 2, 128, 64
    T = rows_for(tile, E, k)
    key = jax.random.key(7)
    bf = jnp.bfloat16
    x = jax.random.normal(key, (T, d)).astype(bf)
    w1 = (jax.random.normal(jax.random.fold_in(key, 1), (E, d, 2 * f))
          * 0.1).astype(bf)
    w2 = (jax.random.normal(jax.random.fold_in(key, 2), (E, f, d))
          * 0.1).astype(bf)
    experts, weights = _routing(T, k, E, "even")
    tall, _ = moe.routed_experts(x, experts, weights, w1, w2)
    # The same call told of so many experts that it keeps 16 rows.
    short, _ = moe.routed_experts(x, experts, weights, w1, w2,
                                  n_experts=64 * E)
    assert tall.dtype == bf
    assert (tall == short).all()


@pytest.mark.parametrize("tile", [16, 64, 128])
def test_the_gradient_goes_through_a_tile_of_any_height(tile, rows_for):
    """``_gmm_bwd`` follows the forward's tile: d loss / d (x, w1, w2)
    equal the loop's."""
    E, k, d, f = 4, 2, 8, 16
    T = rows_for(tile, E, k)
    key = jax.random.key(tile)
    x = jax.random.normal(key, (T, d))
    w1 = jax.random.normal(jax.random.fold_in(key, 1), (E, d, 2 * f)) * 0.3
    w2 = jax.random.normal(jax.random.fold_in(key, 2), (E, f, d)) * 0.3
    experts, weights = _routing(T, k, E, "even")

    def loss(fn):
        return lambda x, w1, w2: (fn(x, w1, w2) ** 2).mean()

    got = jax.grad(loss(lambda x, w1, w2: moe.routed_experts(
        x, experts, weights, w1, w2)[0]), argnums=(0, 1, 2))(x, w1, w2)
    want = jax.grad(loss(lambda x, w1, w2: _loop(
        x, experts, weights, w1, w2)), argnums=(0, 1, 2))(x, w1, w2)
    for g, w in zip(got, want):
        assert jnp.isfinite(g).all()
        assert jnp.abs(g - w).max() < 1e-5
