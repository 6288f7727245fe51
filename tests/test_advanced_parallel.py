"""Ring attention, Ulysses, pipeline parallelism, MoE/expert parallelism.

All run on the 8-virtual-device CPU mesh (conftest). Each strategy is
checked for exactness against an unsharded dense reference, and for
differentiability (the training path runs jax.grad through the collective
schedules).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_tpu.ops import (
    MoEConfig,
    causal_attention,
    moe_apply,
    moe_apply_sharded,
    moe_init,
    ring_attention_sharded,
    ulysses_attention_sharded,
)
from ray_tpu.parallel import MeshSpec, pipeline_apply

DATA_AXES = ("dp", "fsdp", "ep")


def _qkv(b=4, s=64, h=8, d=16):
    ks = jax.random.split(jax.random.key(0), 3)
    return tuple(jax.random.normal(k, (b, s, h, d), jnp.float32) for k in ks)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_dense(causal):
    q, k, v = _qkv()
    ref = causal_attention(q, k, v, causal=causal)
    mesh = MeshSpec(dp=2, sp=4).build()
    sh = NamedSharding(mesh, P(DATA_AXES, "sp", None, None))
    qs, ks, vs = (jax.device_put(x, sh) for x in (q, k, v))
    out = ring_attention_sharded(qs, ks, vs, mesh, causal=causal)
    assert jnp.abs(out - ref).max() < 2e-5


def test_ring_attention_full_sp_axis():
    q, k, v = _qkv(b=2, s=128)
    ref = causal_attention(q, k, v)
    mesh = MeshSpec(sp=8).build()
    sh = NamedSharding(mesh, P(DATA_AXES, "sp", None, None))
    out = ring_attention_sharded(*(jax.device_put(x, sh) for x in (q, k, v)),
                                 mesh)
    assert jnp.abs(out - ref).max() < 2e-5


def test_ulysses_attention_matches_dense():
    q, k, v = _qkv()
    ref = causal_attention(q, k, v)
    mesh = MeshSpec(dp=2, sp=4).build()
    sh = NamedSharding(mesh, P(DATA_AXES, "sp", None, None))
    out = ulysses_attention_sharded(
        *(jax.device_put(x, sh) for x in (q, k, v)), mesh)
    assert jnp.abs(out - ref).max() < 2e-5


def test_ring_attention_grad():
    q, k, v = _qkv(b=2, s=32, h=4, d=8)
    mesh = MeshSpec(sp=4, dp=2).build()
    sh = NamedSharding(mesh, P(DATA_AXES, "sp", None, None))
    qs, ks, vs = (jax.device_put(x, sh) for x in (q, k, v))

    def loss_ring(q, k, v):
        return (ring_attention_sharded(q, k, v, mesh) ** 2).mean()

    def loss_dense(q, k, v):
        return (causal_attention(q, k, v) ** 2).mean()

    g_ring = jax.grad(loss_ring)(qs, ks, vs)
    g_dense = jax.grad(loss_dense)(q, k, v)
    assert jnp.abs(g_ring - g_dense).max() < 2e-5


def test_pipeline_matches_sequential():
    S, D, B = 4, 16, 16
    W = jax.random.normal(jax.random.key(0), (S, D, D)) * 0.3
    x = jax.random.normal(jax.random.key(1), (B, D))

    def stage_fn(w, h):
        return jnp.tanh(h @ w)

    ref = x
    for i in range(S):
        ref = stage_fn(W[i], ref)

    mesh = MeshSpec(pp=4, dp=2).build()
    Wsh = jax.device_put(W, NamedSharding(mesh, P("pp", None, None)))
    xsh = jax.device_put(x, NamedSharding(mesh, P(DATA_AXES, None)))
    for n_mb in (1, 2, 4, 8):
        out = pipeline_apply(stage_fn, Wsh, xsh, n_microbatches=n_mb,
                             mesh=mesh)
        assert jnp.abs(out - ref).max() < 1e-6, n_mb


def test_pipeline_grad_matches_sequential():
    S, D, B = 4, 8, 8
    W = jax.random.normal(jax.random.key(0), (S, D, D)) * 0.3
    x = jax.random.normal(jax.random.key(1), (B, D))
    mesh = MeshSpec(pp=4, dp=2).build()
    Wsh = jax.device_put(W, NamedSharding(mesh, P("pp", None, None)))
    xsh = jax.device_put(x, NamedSharding(mesh, P(DATA_AXES, None)))

    def stage_fn(w, h):
        return jnp.tanh(h @ w)

    def loss_pipe(W):
        return (pipeline_apply(stage_fn, W, xsh, n_microbatches=4,
                               mesh=mesh) ** 2).mean()

    def loss_seq(W):
        h = x
        for i in range(S):
            h = stage_fn(W[i], h)
        return (h ** 2).mean()

    g1 = jax.grad(loss_pipe)(Wsh)
    g2 = jax.grad(loss_seq)(W)
    assert jnp.abs(g1 - g2).max() < 1e-6


def _moe_dense_reference(params, x, cfg):
    """A loop over the experts: every expert (SwiGLU) on every token,
    kept by the router's weight (softmax over all experts, the top k
    renormalised to ``cfg.scale``, 0 where not chosen). No capacity."""
    gates = jax.nn.softmax(x @ params["wg"], -1)
    top, topk_idx = jax.lax.top_k(gates, cfg.k)
    wts = cfg.scale * top / top.sum(-1, keepdims=True)
    y = jnp.zeros_like(x)
    for e in range(cfg.n_experts):
        gu = x @ params["w1"][e]
        out = (jax.nn.silu(gu[:, :cfg.d_ff]) * gu[:, cfg.d_ff:]) \
            @ params["w2"][e]
        y = y + jnp.where(topk_idx == e, wts, 0).sum(-1)[:, None] * out
    return y


def test_moe_local_matches_dense():
    cfg = MoEConfig(d_model=16, d_ff=32, n_experts=4, k=2)
    params = moe_init(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (64, cfg.d_model))
    ref = _moe_dense_reference(params, x, cfg)
    y, aux = moe_apply(params, x, cfg)
    assert jnp.abs(y - ref).max() < 2e-5
    assert jnp.isfinite(aux)


def test_moe_expert_parallel_matches_dense():
    """Each ep shard holds a quarter of the experts, routes over all of
    them and adds what its own give: the shards' parts sum to the
    uncut layer."""
    cfg = MoEConfig(d_model=16, d_ff=32, n_experts=4, k=2)
    params = moe_init(jax.random.key(0), cfg)
    B, S = 8, 8
    x = jax.random.normal(jax.random.key(1), (B, S, cfg.d_model))
    ref = _moe_dense_reference(
        params, x.reshape(-1, cfg.d_model), cfg).reshape(B, S, -1)

    mesh = MeshSpec(dp=2, ep=4).build()
    psh = {
        "wg": jax.device_put(params["wg"], NamedSharding(mesh, P(None, None))),
        "w1": jax.device_put(params["w1"],
                             NamedSharding(mesh, P("ep", None, None))),
        "w2": jax.device_put(params["w2"],
                             NamedSharding(mesh, P("ep", None, None))),
    }
    xsh = jax.device_put(x, NamedSharding(mesh, P(DATA_AXES, None, None)))
    y, aux = moe_apply_sharded(psh, xsh, cfg, mesh)
    assert jnp.abs(y - ref).max() < 2e-5
    assert jnp.isfinite(aux)


def test_moe_drops_no_token_under_skewed_routing():
    """A router that sends every token to the same two of eight experts
    (the old fixed-capacity layer dropped most of them): every token
    still gets both of its experts, and its two weights sum to the
    scale."""
    from ray_tpu.ops.moe import dispatch_plan, route, tile_rows

    cfg = MoEConfig(d_model=8, d_ff=16, n_experts=8, k=2, scale=2.5)
    params = moe_init(jax.random.key(0), cfg)
    params["wg"] = params["wg"].at[:, 2].add(40.0).at[:, 5].add(39.0)
    x = jnp.abs(jax.random.normal(jax.random.key(1), (96, cfg.d_model)))
    _, experts, weights = route(x, params["wg"], cfg.k, cfg.scale)
    assert set(np.asarray(experts).ravel().tolist()) == {2, 5}
    assert jnp.abs(weights.sum(-1) - 2.5).max() < 1e-5
    tile = tile_rows(96 * cfg.k, cfg.n_experts)     # what the call uses
    sizes, dest, _, _, n_used = dispatch_plan(experts, cfg.n_experts, tile)
    assert sizes.tolist() == [0, 0, 96, 0, 0, 96, 0, 0]
    assert len(set(np.asarray(dest).tolist())) == 192     # a row each
    assert int(n_used) == 2 * 96 // tile
    y, _ = moe_apply(params, x, cfg)
    ref = _moe_dense_reference(params, x, cfg)
    assert jnp.abs(y - ref).max() < 2e-5
    assert (jnp.abs(y).sum(-1) > 0).all()                 # no zero row


def test_moe_grad():
    cfg = MoEConfig(d_model=8, d_ff=16, n_experts=4, k=2)
    params = moe_init(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (32, cfg.d_model))

    def loss(apply):
        def f(p):
            y, aux = apply(p)
            return (y ** 2).mean() + 0.01 * aux
        return f

    g = jax.grad(loss(lambda p: moe_apply(p, x, cfg)))(params)
    for leaf in jax.tree_util.tree_leaves(g):
        assert jnp.isfinite(leaf).all()
    # ... and equal to the loop's, through the grouped product's own
    # backward (aux aside: it has no gradient in the loop).
    want = jax.grad(lambda p: (_moe_dense_reference(p, x, cfg) ** 2)
                    .mean())(params)
    got = jax.grad(lambda p: (moe_apply(p, x, cfg)[0] ** 2).mean())(params)
    for k in ("w1", "w2", "wg"):
        assert jnp.abs(got[k] - want[k]).max() < 1e-5, k


def test_flash_noncausal_padding_masked():
    """Non-causal flash with seq not a block multiple must ignore the
    zero-padded phantom keys (regression: padded keys got softmax weight)."""
    from ray_tpu.ops.flash_attention import _flash_reference

    b, s, h, d = 2, 48, 2, 8  # 48 % block(32) != 0
    ks = jax.random.split(jax.random.key(7), 3)
    q, k, v = (jax.random.normal(kk, (b, s, h, d), jnp.float32) for kk in ks)
    ref = causal_attention(q, k, v, causal=False)
    out = _flash_reference(q, k, v, causal=False, block_size=32)
    assert jnp.abs(out - ref).max() < 2e-5
