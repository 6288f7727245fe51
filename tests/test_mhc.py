"""The residual path's two kernels (ops/mhc.py) in the Pallas
interpreter against the equations in plain jnp, at small sizes in
float32: rows that are no whole tile, rows of a decode batch with
``q`` > 1, several grid steps with a ragged last one; what the
Sinkhorn chain leaves; the slab's layout; and each fault the
benchmark plants in its reference read against its limits
(benchmark/reference_xing4.py) at the small size.

Tolerances: float32 on both sides, so the kernels differ from the
reference by summation order alone: 2e-6 on coefficients that are O(1)
and 5e-6 on mixes of O(3) entries are ~10x what is seen."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import mhc

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

N, C = 4, 64
KW = dict(n=N, iters=20, eps=1e-6, norm_eps=1e-6)


def _inputs(rows, seed=0, a=(0.5, 0.6, 0.7), b_std=0.5):
    k = jax.random.split(jax.random.key(seed), 4)
    X = jax.random.normal(k[0], (rows, N * C), jnp.float32)
    phi = jax.random.normal(k[1], (N * C, N * N + 2 * N)) * 0.1
    b = jax.random.normal(k[2], (N * N + 2 * N,)) * b_std
    y = jax.random.normal(k[3], (rows, C), jnp.float32)
    return X, phi, jnp.asarray(a, jnp.float32), b, y


@pytest.fixture
def tile(monkeypatch):
    """Rows a grid step takes, steered here: the makers cache by it."""
    def set_to(rows):
        monkeypatch.setattr(mhc, "TILE", rows)
    return set_to


@pytest.mark.parametrize("rows, tile_rows", [
    (5, 256),       # fewer rows than a sublane tile: one block
    (2 * 3, 256),   # a decode batch of 2 lanes with q = 3 rows each
    (64, 256),      # the served decode batch, one block
    (40, 16),       # three grid steps, the last one ragged (8 of 16)
    (48, 16),       # three whole grid steps
])
def test_kernels_equal_the_equations(rows, tile_rows, tile):
    tile(tile_rows)
    X, phi, a, b, y = _inputs(rows, seed=rows)
    h, coef = mhc.mhc_pre(X, phi, a, b, **KW)
    want_h, H_pre, H_post, H_res = mhc.mhc_pre_reference(X, phi, a, b, **KW)
    got = mhc.coefficients(coef, N)
    assert h.shape == (rows, C) and coef.shape == (rows, 128)
    assert coef.dtype == jnp.float32
    for g, w in zip(got, (H_pre, H_post, H_res)):
        assert g.shape == w.shape
        assert np.abs(np.asarray(g - w)).max() < 2e-6
    assert np.abs(np.asarray(h - want_h)).max() < 5e-6
    out = mhc.mhc_post(X, y, coef, n=N)
    want = mhc.mhc_post_reference(X, y, H_post, H_res)
    assert out.shape == (rows, N * C)
    assert np.abs(np.asarray(out - want)).max() < 5e-6
    # The coefficients move from token to token: no constant matrix.
    assert float(np.asarray(H_res).std(0).min()) > 0.01


def test_bfloat16_streams_keep_float32_coefficients():
    """Streams and ``Phi`` in bfloat16, as served: the coefficients are
    float32 and equal the reference's on the same rounded inputs; the
    mixes come back in bfloat16, one rounding from the reference's."""
    X, phi, a, b, y = _inputs(24, seed=3)
    X, phi, y = (v.astype(jnp.bfloat16) for v in (X, phi, y))
    h, coef = mhc.mhc_pre(X, phi, a, b, **KW)
    want_h, H_pre, H_post, H_res = mhc.mhc_pre_reference(X, phi, a, b, **KW)
    assert h.dtype == jnp.bfloat16 and coef.dtype == jnp.float32
    for g, w in zip(mhc.coefficients(coef, N), (H_pre, H_post, H_res)):
        assert np.abs(np.asarray(g - w)).max() < 5e-6
    out = mhc.mhc_post(X, y, coef, n=N)
    want = mhc.mhc_post_reference(X, y, H_post, H_res)
    assert out.dtype == jnp.bfloat16
    for got, ref in ((h, want_h), (out, want)):
        ref = np.asarray(ref)
        assert np.abs(np.asarray(got.astype(jnp.float32)) - ref).max() \
            <= 2.0 ** -8 * np.abs(ref).max() + 1e-6


def test_h_res_is_doubly_stochastic_within_the_counters_reading():
    """Twenty iterations from pre-activations of a few units end at
    ``hc_eps``: every row and column sum within ``res_err`` of 1, and
    ``res_err`` is what the equations' own matrix gives. From the
    clamp's corners (pre-activations of +-30 and over) the chain has
    NOT converged, and the reading says so instead of hiding it."""
    X, phi, a, b, _ = _inputs(32, seed=5)
    _, coef = mhc.mhc_pre(X, phi, a, b, **KW)
    H = np.asarray(mhc.coefficients(coef, N)[2], np.float64)
    err = float(mhc.res_err(coef, N))
    assert err < 5e-5
    assert np.abs(H.sum(-1) - 1).max() <= err + 1e-7
    assert np.abs(H.sum(-2) - 1).max() <= err + 1e-7
    assert (H > 0).all() and (H < 1).all()
    want = np.asarray(mhc.mhc_pre_reference(X, phi, a, b, **KW)[3])
    assert abs(err - max(np.abs(want.sum(-1) - 1).max(),
                         np.abs(want.sum(-2) - 1).max())) < 1e-6
    # Far apart entries: a = 40 on m of ~1 reaches the clamp both ways.
    _, wild = mhc.mhc_pre(X, phi * 10, jnp.asarray([0.5, 0.5, 40.0]), b,
                          **KW)
    H = np.asarray(mhc.coefficients(wild, N)[2])
    assert np.isfinite(H).all() and (H >= 0).all()
    assert np.abs(H.sum(-2) - 1).max() < 1e-4       # columns come last
    assert float(mhc.res_err(wild, N)) > 1e-3       # rows have not settled


def test_the_clamp_bounds_the_pre_activation_not_the_result():
    X, phi, a, b, _ = _inputs(8, seed=7)
    kw = dict(KW, iters=0)
    tight = mhc.coefficients(
        mhc.mhc_pre(X, phi * 30, a, b, clamp=(-1.0, 1.0), **kw)[1], N)[2]
    assert float(tight.max()) <= np.e + 1e-5
    assert float(tight.min()) >= 1 / np.e - 1e-6


def test_slab_layout_and_its_inverse():
    rows = 6
    rng = np.random.default_rng(0)
    H_pre, H_post = rng.random((rows, N)), rng.random((rows, N))
    H_res = rng.random((rows, N, N))
    coef = mhc.pack(*(jnp.asarray(v, jnp.float32)
                      for v in (H_pre, H_post, H_res)))
    assert coef.shape == (rows, 128)
    got = np.asarray(coef)
    np.testing.assert_allclose(got[:, 0:4], H_pre, rtol=1e-6)
    np.testing.assert_allclose(got[:, 8:12], H_post, rtol=1e-6)
    for i in range(N):
        np.testing.assert_allclose(got[:, 16 + 8 * i:20 + 8 * i],
                                   H_res[:, i], rtol=1e-6)
    used = np.zeros(128, bool)
    for g in range(N + 2):
        used[8 * g:8 * g + N] = True
    assert np.abs(got[:, ~used]).max() == 0.0
    for g, w in zip(mhc.coefficients(coef, N), (H_pre, H_post, H_res)):
        np.testing.assert_allclose(np.asarray(g), w, rtol=1e-6)
    # mhc_post on a slab made by hand: the identity mix and no sublayer.
    X = jnp.asarray(rng.standard_normal((rows, N * C)), jnp.float32)
    y = jnp.ones((rows, C), jnp.float32)
    same = mhc.mhc_post(X, y, mhc.pack(
        jnp.zeros((rows, N)), jnp.zeros((rows, N)),
        jnp.broadcast_to(jnp.eye(N), (rows, N, N))), n=N)
    np.testing.assert_allclose(np.asarray(same), np.asarray(X), rtol=1e-6)


def test_more_streams_than_the_slab_lays_out_are_refused():
    X = jnp.zeros((4, 9 * 8), jnp.float32)
    with pytest.raises(ValueError, match="streams"):
        mhc.mhc_pre(X, jnp.zeros((72, 99)), jnp.ones((3,)),
                    jnp.zeros((99,)), n=9, iters=1, eps=1e-6,
                    norm_eps=1e-6)


# -- the benchmark's planted faults, at the small size -------------------------


def _reference_reading(fault=None, lower=False):
    """The served kernels against the benchmark's reference equations
    with ``fault`` planted, on one batch of streams: (largest
    coefficient difference, largest mix difference over the largest
    entry), as ``reference_xing4._served_path_fn`` reads them."""
    from benchmark import reference_xing4 as ref
    from ray_tpu.models import xing4

    cfg = xing4.Xing4Config(
        vocab_size=64, hidden_size=C, intermediate_size=128,
        moe_intermediate_size=32, num_hidden_layers=1,
        num_attention_heads=4, q_lora_rank=48, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        n_routed_experts=16, num_experts_per_tok=2, experts_held=16,
        first_k_dense_replace=1, max_seq=64, dtype="float32")
    T = 48
    X, phi, a, b, y = _inputs(T, seed=11)
    hc = {"phi": phi, "a": a, "b": b}
    Xs = X.reshape(T, N, C)
    coefs = ref._coefficients(Xs, hc, cfg, lower, fault)
    c, m = ref._served_path_fn(cfg, T)(
        hc, X, y, *coefs, ref._pre_mix(Xs, coefs[0]),
        ref._post_mix(Xs, y, coefs[1], coefs[2]).reshape(T, -1))
    return float(c), float(m)


def test_each_planted_fault_fails_the_residual_paths_limit():
    """Sound: the served kernels are the reference's equations to
    float32 rounding. Three Sinkhorn iterations, ``H_post`` without its
    2, a static ``H_res`` and coefficients held in bfloat16 each read
    over ``MAX_MHC_DIFF``, the first and the last by 8x and more; a
    missing 2 and a static matrix also move the mixes over theirs. (One
    expert fewer a token is the router's limit's to fail and the rope
    part left out the tokens': tests/test_xing4_benchmark.py.)"""
    from benchmark import reference_xing4 as ref

    coef, mix = _reference_reading()
    assert coef < 2e-6 and mix < 2e-6
    assert coef < ref.MAX_MHC_DIFF / 100 and mix < ref.MAX_MHC_MIX_DIFF / 100
    readings = {f: _reference_reading(f) for f in ref.FAULTS
                if f not in ("one_expert_fewer", "no_rope_term")}
    readings["lower"] = _reference_reading(lower=True)
    for fault, (c, _) in readings.items():
        assert c > 8 * ref.MAX_MHC_DIFF, (fault, c)
    for fault in ("h_post_without_its_2", "static_h_res"):
        assert readings[fault][1] > ref.MAX_MHC_MIX_DIFF, readings[fault]
    checks = ref.router_checks(
        {"router_same": 10, "router_total": 10, "router_weight_diff": 0.0,
         "mhc_diff": readings["lower"][0], "mhc_mix_diff": mix})
    assert [ok for ok, _ in checks] == [True, True, False, True]
