"""Xing4.0 behind the serving seam, at a small size on the CPU with
seeded random weights in float32: the served path (LLMEngine, chunked
prefill, ONE pool of latent rows, Kimi's two programs on the
four-stream residual path, the two mHC kernels and Kimi's in the Pallas
interpreter) against the plain reference (models/xing4_ref.py:
jax.numpy, streams as [T, n, C], no cache, no kernel, no batching).

Tolerances: everything is float32 here, so the two sides differ by
summation order alone. Logits have magnitude ~0.5; 2e-5 absolute is
~100x the error seen (1.6e-7) and far below what a fault on the
residual path moves (``test_planted_faults_move_the_logits``: 1e-3 and
up)."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm.engine import LLMEngine, _jit_programs
from ray_tpu.models import (kimi_k2, layers, pack_step, serving, xing4,
                            xing4_ref)
from ray_tpu.ops import mhc, moe

# Records every logits row an engine decides a token from, {rid: [row, ...]}.
from test_laguna import _logits_of

TINY = xing4.Xing4Config(
    vocab_size=256, hidden_size=64, intermediate_size=128,
    moe_intermediate_size=32, num_hidden_layers=3, num_attention_heads=4,
    q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=16,
    num_experts_per_tok=2, experts_held=16, first_expert=0,
    first_k_dense_replace=1,
    rope_scaling=dict(beta_fast=32, beta_slow=1, factor=64, mscale=1,
                      mscale_all_dim=1, original_max_position_embeddings=32,
                      type="yarn"),
    max_seq=160, dtype="float32")
LOGIT_TOL = 2e-5
BS = 8


@pytest.fixture(scope="module")
def params():
    return xing4.init(jax.random.key(0), TINY)


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(0, 256, n).tolist()


def _engine(params, **kw):
    kw = {"num_blocks": 64, "block_size": BS, "max_batch": 4,
          "prefill_chunk_tokens": 16, **kw}
    return LLMEngine(params, TINY, **kw)


def _drain(eng):
    while eng.step():
        pass


def _reference_rows(params, prompt, out, cfg=TINY):
    logits = np.asarray(xing4_ref.forward(params, prompt + out, cfg))
    return logits[len(prompt) - 1:len(prompt) - 1 + len(out)]


# -- the served path against the plain reference -----------------------------


def test_engine_logits_equal_the_plain_reference(params):
    """A 40-token prefix sent alone, then the prefix with a 30-token
    body: the body is prefilled in chunks of 16 against the cached
    prefix's latent rows, then 12 decode steps through the latent pool:
    every LOGITS row the engine samples from equals the reference's full
    forward pass. A cold prompt gives what the cached prefix gives."""
    eng = _engine(params)
    prefix, body = _prompt(0, 40), _prompt(1, 30)
    eng.add_request(prefix, max_tokens=1)
    _drain(eng)
    rows = _logits_of(eng)
    req = eng.add_request(prefix + body, max_tokens=12, temperature=0.7,
                          seed=3)
    _drain(eng)
    assert req.cached_tokens == len(prefix)
    want = _reference_rows(params, prefix + body, req.output)
    got = np.stack(rows[req.rid])
    assert got.shape == want.shape
    assert np.abs(got - want).max() < LOGIT_TOL
    cold = _engine(params, prefix_cache=False)
    rows_cold = _logits_of(cold)
    c = cold.add_request(prefix + body, max_tokens=12, temperature=0.7,
                         seed=3)
    _drain(cold)
    assert c.cached_tokens == 0 and c.output == req.output
    assert np.abs(np.stack(rows_cold[c.rid]) - got).max() < LOGIT_TOL


@pytest.mark.parametrize("bodies", [(24, 16, 8), (21, 13)],
                         ids=["whole_blocks", "ragged"])
def test_spans_packed_in_one_program_equal_the_plain_reference(params,
                                                               bodies):
    """Bodies behind two cached prefixes (40 tokens and 16) arrive
    together and fill ONE step's budget: their spans ride in ONE chunk
    program (the seam's ``chunk_spans`` 4), each row at its own
    sequence's positions, seeing its own prefix's slots of the one
    table and its own span. Every logits row each request samples from
    equals the reference's full forward pass of THAT request alone, and
    the tokens are those of the request sent alone."""
    from ray_tpu.util import perfmodel

    eng = _engine(params, prefill_chunk_tokens=64)
    prefixes = [_prompt(0, 40), _prompt(5, 16)]
    for p in prefixes:
        eng.add_request(p, max_tokens=1)
        _drain(eng)
    rows = _logits_of(eng)
    sent = [dict(prompt=prefixes[i % 2] + _prompt(10 + i, n), max_tokens=6,
                 temperature=0.7 * (i == 0), seed=3)
            for i, n in enumerate(bodies)]
    # The sampler rides last: its row is fetched at once, which closes
    # the program it rides in.
    reqs = [eng.add_request(**r) for r in sent[::-1]][::-1]
    eng.step()
    entry = perfmodel.device_step_events()[-1]
    assert entry["prefill_spans"] == [[-(-n // BS) * BS for n in bodies][::-1]]
    assert [r.cached_tokens for r in reqs] \
        == [len(prefixes[i % 2]) // BS * BS for i in range(len(bodies))]
    _drain(eng)
    for req, r in zip(reqs, sent):
        want = _reference_rows(params, r["prompt"], req.output)
        assert np.abs(np.stack(rows[req.rid]) - want).max() < LOGIT_TOL
        alone = _engine(params, prefill_chunk_tokens=64)
        a = alone.add_request(**r)
        _drain(alone)
        assert a.output == req.output


def _latent_rows(params, tokens):
    """The pool after one chunk program wrote ``tokens`` from position
    0 into blocks 1.., and that span's last logits row."""
    from ray_tpu.models import pack_spans

    n = len(tokens)
    assert n % BS == 0
    model = serving(TINY)
    pool = jnp.zeros((TINY.num_hidden_layers, 32, BS, model.kinds[0].rows[0]),
                     jnp.float32)
    table = pack_spans([((), np.arange(1, 1 + n // BS), 0, n)],
                       TINY.max_seq // BS, BS, model.chunk_spans)
    rows, _, pool = _jit_programs(TINY)[1](
        params, jnp.asarray([tokens], jnp.int32), pool, jnp.asarray(table))
    row = rows[0]
    return pool, np.asarray(row)


def test_chunk_and_decode_programs_agree_and_two_rows_are_two_steps(params):
    """The same sequence through the chunk program (all 24 tokens as
    one span) and through the decode program (the last token, or the
    last two as ``q`` = 2 rows of one lane, behind the first rows'
    cached latent rows): the same logits for the same positions. The
    chunk program attends with whole heads (the up-projecting form),
    the decode program in the absorbed form, and both sit on the same
    four-stream path."""
    seq = _prompt(7, 24)
    _, want_last = _latent_rows(params, seq)
    want = _reference_rows(params, seq[:22], seq[22:] + [0])    # pos 21..23
    step = _jit_programs(TINY)[0]
    max_nb = TINY.max_seq // BS
    tables = np.zeros((2, max_nb), np.int32)
    tables[0, :3] = (1, 2, 3)

    def decode(q, pool):
        first = 24 - q
        toks = np.zeros((2, q), np.int32)
        pos = np.zeros((2, q), np.int32)
        blocks = np.zeros((2, q), np.int32)
        offs = np.zeros((2, q), np.int32)
        toks[0], pos[0] = seq[first:], np.arange(first, 24)
        blocks[0], offs[0] = 1 + pos[0] // BS, pos[0] % BS
        packed = pack_step(toks, pos, tables, [24, 1], [q, 1], blocks, offs)
        logits, ids, _ = step(params, jnp.asarray(packed), pool, q=q)
        assert ids.shape == (2 + len(xing4.COUNTERS), q)
        return np.asarray(logits[0])

    # The pool holds every row of the sequence (a chunk wrote them); a
    # decode step writes its own rows again, the same values.
    pool, _ = _latent_rows(params, seq)
    one = decode(1, pool)
    assert np.abs(one[0] - want_last).max() < LOGIT_TOL
    assert np.abs(one[0] - want[2]).max() < LOGIT_TOL
    pool, _ = _latent_rows(params, seq)
    two = decode(2, pool)
    assert np.abs(two[1] - one[0]).max() < LOGIT_TOL
    assert np.abs(two - want[1:]).max() < LOGIT_TOL


def test_speculative_rows_go_through_the_four_stream_path(params):
    """q_len > 1 end to end: n-gram proposals verified in one step give
    the plain greedy tokens."""
    prompt = _prompt(8, 12) * 3
    plain = _engine(params)
    a = plain.add_request(prompt, max_tokens=12)
    _drain(plain)
    spec = _engine(params, speculative={"mode": "ngram", "k": 2})
    b = spec.add_request(prompt, max_tokens=12)
    _drain(spec)
    assert b.output == a.output


# -- the residual path ---------------------------------------------------------


def test_coefficients_move_from_token_to_token(params):
    """``assumed`` (f): ``a`` and ``b`` are drawn at a size at which the
    mixing weights are made from the token: over a batch of streams an
    entry of ``H_res`` spreads by a measurable share of its mean (at
    the published widths ``m`` is 7x wider still; with ``a`` = 0.01 the
    same spread is under 1e-3 and the path is one constant matrix)."""
    hc = params["layers"][1]["hc_mlp"]
    X = jax.random.normal(jax.random.key(2), (200, 4 * 64)) * 0.02
    kw = xing4._mhc_kwargs(TINY)
    H = np.asarray(mhc.mhc_pre_reference(X, hc["phi"], hc["a"], hc["b"],
                                         **kw)[3])
    spread = H.std(0)
    assert 0.3 < float(hc["a"].min()) and float(hc["a"].max()) < 0.8
    assert spread.min() > 0.01 and spread.mean() > 0.015
    still = np.asarray(mhc.mhc_pre_reference(
        X, hc["phi"], hc["a"] * 0.02, hc["b"], **kw)[3])
    assert still.std(0).max() < 1e-3
    assert np.abs(H.sum(-1) - 1).max() < 1e-4
    assert np.abs(H.sum(-2) - 1).max() < 1e-4


def test_streams_open_as_copies_and_close_by_a_sum():
    path = xing4._residual(TINY)
    x = jax.random.normal(jax.random.key(1), (2, 3, 64))
    X = path.open(x)
    assert X.shape == (2, 3, 4 * 64)
    for i in range(4):
        np.testing.assert_array_equal(X[..., 64 * i:64 * (i + 1)], x)
    np.testing.assert_allclose(path.close(X), 4 * x, rtol=1e-6)


def test_kimis_forward_pass_is_called_not_copied():
    """The two programs are Kimi's functions with another ``Residual``;
    the attention paths, the MLP, the head and the counters are Kimi's
    own objects, under Kimi's public names (``Xing4Config`` IS a
    ``KimiK2Config``), and what Kimi takes from models/layers.py this
    module takes from there too."""
    import inspect

    src = inspect.getsource(xing4)
    for name in ("kimi_k2.forward_step(", "kimi_k2.forward_prefill_chunk(",
                 "kimi_k2.mlp(", "kimi_k2.cost_shape(",
                 "kimi_k2.init_layer(", "kimi_k2.Residual(",
                 "kimi_k2.COUNTERS", "init_ends("):
        assert name in src, name
    for copied in ("\ndef _project", "\ndef _chunk_attention", "\ndef head",
                   "\ndef counters", "\ndef mlp", "\ndef init_ends",
                   "\ndef rmsnorm", "paged_attention_latent", "kimi_k2._"):
        assert copied not in src, copied
    for name in ("init_ends", "normal", "rmsnorm"):
        assert getattr(xing4, name) is getattr(kimi_k2, name) \
            is getattr(layers, name), name
    assert issubclass(xing4.Xing4Config, kimi_k2.KimiK2Config)
    assert kimi_k2.PLAIN.block is kimi_k2.block


# -- the seam ----------------------------------------------------------------


def test_cache_description_and_counters(params):
    from ray_tpu.util import perfmodel

    model = serving(TINY)
    (kind,) = model.kinds
    assert kind.rows == (128,) and kind.window is None
    assert model.counters == kimi_k2.COUNTERS + ("mhc_res_err_x1e6",)
    assert xing4.Xing4Config().row_width == 640
    perfmodel.clear_device_steps()
    eng = _engine(params, name="xing-counters")
    for i in range(3):
        eng.add_request(_prompt(40 + i, 30), max_tokens=5)
    _drain(eng)
    steps = [e for e in perfmodel.device_step_events()
             if e["name"] == "llm.step"
             and e.get("deployment") == "xing-counters"
             and e["decode_tokens"] > 0]
    assert steps
    for e in steps:
        assert 0 <= e["moe_experts_hit"] <= TINY.experts_held
        # 1e6 x the worst row or column sum's distance from 1: a few
        # units of hc_eps.
        assert 0 <= e["mhc_res_err_x1e6"] < 100
    assert eng.stats()["mhc_res_err_x1e6"] < 100


def test_cost_description_prices_the_streams():
    from ray_tpu.util import perfmodel

    cost, base = serving(TINY).cost, kimi_k2.cost_shape(TINY)
    L, n, C = TINY.num_hidden_layers, 4, 64
    phi = 2 * L * n * C * 24
    assert cost["matmul_weights"] == base["matmul_weights"] + phi
    assert cost["streamed_params"](8) == base["streamed_params"](8) + phi
    # A row a sublayer: the n streams in and out, one stream out and in.
    assert cost["stream_bytes_per_row"] == 2 * L * (2 * n * C + 2 * C) * 4
    assert xing4.cost_shape(xing4.Xing4Config(num_hidden_layers=1))[
        "stream_bytes_per_row"] == 2 * (2 * 14336 + 2 * 3584) * 2  # 71.7 KB
    params = jax.eval_shape(lambda: xing4.init(jax.random.key(0), TINY))
    assert cost["num_params"] == sum(
        math.prod(x.shape) for x in jax.tree_util.tree_leaves(params))
    with_streams = perfmodel.decode_step_cost(TINY, [50] * 4)
    assert with_streams.hbm_bytes > 4 * cost["stream_bytes_per_row"]
    chunk = perfmodel.prefill_cost(TINY, 16)
    assert chunk.hbm_bytes > 16 * cost["stream_bytes_per_row"]
    # The published config's count: 29 B parameters, 4 B a token.
    whole = xing4.Xing4Config()
    assert 28.5e9 < whole.num_params() < 30e9
    assert not whole.routed(1) and whole.routed(2)


def test_the_seeds_router_bias_changes_the_chosen_set(params):
    p = params["layers"][1]
    h = jax.random.normal(jax.random.key(5), (400, TINY.hidden_size))
    _, with_b, _ = moe.route_sigmoid(h, p["router"], p["router_bias"], 2)
    _, without, _ = moe.route_sigmoid(h, p["router"],
                                      jnp.zeros_like(p["router_bias"]), 2)
    differ = (jnp.sort(with_b, -1) != jnp.sort(without, -1)).any(-1).mean()
    assert 0.05 < float(differ) < 0.95


def test_a_form_that_is_not_built_is_refused():
    with pytest.raises(ValueError, match="hc_mult"):
        dataclasses.replace(TINY, hc_mult=9)
    with pytest.raises(ValueError, match="scoring_func"):
        dataclasses.replace(TINY, scoring_func="softmax")


# -- planted faults ----------------------------------------------------------


@pytest.mark.parametrize("fault, over", [
    ("h_post_without_its_2", 50), ("h_pre_left_out", 50),
    ("no_sinkhorn", 50), ("static_h_res", 5)])
def test_planted_faults_move_the_logits(params, fault, over, monkeypatch):
    """Each departure from the residual path's equations moves the
    reference's logits by more than ``LOGIT_TOL`` (``over`` times): what
    the comparison above would not let through. (Three Sinkhorn
    iterations for 20, or columns before rows, move a coefficient by
    4e-3 and these logits by 1e-5: the coefficients' own comparison
    catches those, tests/test_mhc.py.)"""
    prompt = _prompt(50, 48)
    # At 64 wide a sublayer adds ~1% to a stream, the four streams stay
    # near-copies of one another and NO doubly stochastic H_res moves
    # them: the sublayers' outputs are scaled up to the streams' own
    # size, as they are at the published widths.
    params = dict(params, layers=[
        {k: v * 30 if k in ("w_o", "w_down", "s_down", "w2") else v
         for k, v in p.items()} for p in params["layers"]])
    want = np.asarray(xing4_ref.forward(params, prompt, TINY))
    cfg = TINY
    if fault == "no_sinkhorn":
        cfg = dataclasses.replace(TINY, hc_sinkhorn_iters=0)
    else:
        real = xing4_ref.coefficients

        def faulty(X, hc, cfg):
            if fault == "static_h_res":
                hc = dict(hc, a=hc["a"].at[2].set(0.0))
            H_pre, H_post, H_res = real(X, hc, cfg)
            if fault == "h_post_without_its_2":
                H_post = H_post / 2
            if fault == "h_pre_left_out":
                H_pre = jnp.ones_like(H_pre)
            return H_pre, H_post, H_res
        monkeypatch.setattr(xing4_ref, "coefficients", faulty)
    got = np.asarray(xing4_ref.forward(params, prompt, cfg))
    assert np.abs(got - want).max() > over * LOGIT_TOL
