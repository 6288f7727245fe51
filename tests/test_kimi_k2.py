"""Kimi-K2 behind the serving seam, at a small size on the CPU with
seeded random weights in float32: the served path (LLMEngine, chunked
prefill in the up-projecting form, ONE pool of latent rows, the
absorbed decode kernel and the grouped product in the Pallas
interpreter) against the plain reference (models/kimi_k2_ref.py:
jax.numpy, NON-absorbed, no cache, no kernel, no batching), given the
same share of the experts.

Tolerances: everything is float32 here, so the two sides differ by
summation order and by the absorbed form's reassociation alone. Logits
have magnitude ~0.2; 2e-5 absolute is ~100x the error seen (1.5e-7)
and far below what a missed rope term, a wrong softmax scale or a wrong
router would move (6e-3 and up at this size, where attention adds
little to a random-weight residual: ``test_planted_faults_move_the_
logits``)."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm.engine import LLMEngine
from ray_tpu.models import kimi_k2, kimi_k2_ref, layers, serving
from ray_tpu.ops import moe
from ray_tpu.ops.pallas import paged_fetch

# Records every logits row an engine decides a token from, {rid: [row, ...]}.
from test_laguna import _logits_of

TINY = kimi_k2.KimiK2Config(
    vocab_size=256, hidden_size=64, intermediate_size=128,
    moe_intermediate_size=32, num_hidden_layers=3, num_attention_heads=4,
    q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=16,
    num_experts_per_tok=2, experts_held=4, first_expert=4,
    rope_scaling=dict(beta_fast=32, beta_slow=1, factor=64, mscale=1,
                      mscale_all_dim=1, original_max_position_embeddings=32,
                      type="yarn"),
    max_seq=160, dtype="float32")
LOGIT_TOL = 2e-5
BS = 8


@pytest.fixture(scope="module")
def params():
    return kimi_k2.init(jax.random.key(0), TINY)


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
    logits = np.asarray(kimi_k2_ref.forward(params, prompt + out, cfg))
    return logits[len(prompt) - 1:len(prompt) - 1 + len(out)]


# -- the served path against the plain reference -----------------------------


@pytest.mark.parametrize("budget", [16, 48], ids=["chunks_of_16",
                                                  "chunks_of_48"])
def test_engine_logits_equal_the_plain_reference(params, budget):
    """A 40-token prefix sent alone, then the prefix with a 30-token
    body: the body is prefilled in chunks against the cached prefix's
    latent rows (the up-projecting form), then 12 decode steps through
    the latent pool (the absorbed kernel): every logits row the engine
    samples from equals the reference's non-absorbed full forward
    pass."""
    eng = _engine(params, prefill_chunk_tokens=budget)
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


def test_a_lane_beside_seven_others_equals_itself_alone(params):
    prompt = _prompt(1, 41)
    alone = _engine(params, max_batch=8, num_blocks=128)
    rows_alone = _logits_of(alone)
    a = alone.add_request(prompt, max_tokens=10)
    _drain(alone)
    eng = _engine(params, max_batch=8, num_blocks=128)
    rows = _logits_of(eng)
    others = [eng.add_request(_prompt(10 + i, 20 + 7 * i), max_tokens=14)
              for i in range(4)]
    b = eng.add_request(prompt, max_tokens=10)
    others += [eng.add_request(_prompt(20 + i, 33 + i), max_tokens=9)
               for i in range(3)]
    _drain(eng)
    assert b.output == a.output
    assert np.abs(np.stack(rows[b.rid])
                  - np.stack(rows_alone[a.rid])).max() < LOGIT_TOL
    assert all(len(o.output) == o.max_tokens for o in others)


def test_a_prefix_hit_equals_a_cold_prompt(params):
    context, body = _prompt(2, 48), _prompt(3, 19)
    cold = _engine(params, prefix_cache=False)
    rows_cold = _logits_of(cold)
    c = cold.add_request(context + body, max_tokens=8)
    _drain(cold)
    eng = _engine(params)
    eng.add_request(context, max_tokens=1)
    _drain(eng)
    rows = _logits_of(eng)
    warm = eng.add_request(context + body, max_tokens=8)
    _drain(eng)
    assert warm.cached_tokens == len(context)
    assert warm.output == c.output
    assert np.abs(np.stack(rows[warm.rid])
                  - np.stack(rows_cold[c.rid])).max() < LOGIT_TOL
    # The same prompt again: a full hit, whose first decode write splits
    # the shared tail block of the ONE pool.
    again = eng.add_request(context + body, max_tokens=8)
    _drain(eng)
    assert again.cached_tokens == len(context + body)
    assert again.output == c.output and eng.kv.cow_splits >= 1


def test_preempt_and_resume_reproduce_the_tokens(params):
    prompts = [_prompt(30 + i, 30 + 5 * i) for i in range(3)]
    roomy = _engine(params)
    want = [roomy.add_request(p, max_tokens=20, seed=i)
            for i, p in enumerate(prompts)]
    _drain(roomy)
    tight = _engine(params, num_blocks=17)
    got = [tight.add_request(p, max_tokens=20, seed=i)
           for i, p in enumerate(prompts)]
    _drain(tight)
    assert sum(r.preemptions for r in got) > 0
    assert [r.output for r in got] == [r.output for r in want]
    assert tight.kv.num_free == tight.kv.capacity


def test_speculative_rows_go_through_the_latent_kernel(params):
    """q_len > 1 end to end: n-gram proposals verified in one step give
    the plain greedy tokens."""
    prompt = _prompt(8, 12) * 3
    plain = _engine(params)
    a = plain.add_request(prompt, max_tokens=16)
    _drain(plain)
    spec = _engine(params, speculative={"mode": "ngram", "k": 3})
    b = spec.add_request(prompt, max_tokens=16)
    _drain(spec)
    assert b.output == a.output


# -- the absorbed kernel -----------------------------------------------------


@pytest.mark.parametrize("q_len", [1, 5])
def test_latent_kernel_equals_its_jnp_reference(q_len):
    rng = np.random.default_rng(q_len)
    b, heads, rank, rope, bs, nb, max_nb = 3, 4, 32, 8, 8, 24, 6
    width = 128
    pool = np.zeros((2, nb, bs, width), np.float32)
    pool[..., :rank + rope] = rng.standard_normal((2, nb, bs, rank + rope))
    q = np.zeros((b, q_len, heads, width), np.float32)
    q[..., :rank + rope] = rng.standard_normal(
        (b, q_len, heads, rank + rope))
    tables = rng.permutation(np.arange(1, nb))[:b * max_nb].reshape(
        b, max_nb).astype(np.int32)
    ctx = np.asarray([9, 33, 48], np.int32)
    q_lens = np.asarray([1, q_len, max(1, q_len - 2)], np.int32)
    args = (jnp.asarray(q), jnp.asarray(pool), 1, jnp.asarray(tables),
            jnp.asarray(ctx), jnp.asarray(q_lens))
    got = paged_fetch.paged_attention_latent(*args, rank=rank, scale=0.3)
    want = paged_fetch.paged_attention_latent_reference(
        *args, rank=rank, scale=0.3)
    assert got.shape == (b, q_len, heads, rank)
    for lane in range(b):           # rows past a lane's q_len are padding
        n = int(q_lens[lane])
        assert np.abs(np.asarray(got)[lane, :n]
                      - np.asarray(want)[lane, :n]).max() < 2e-5


# -- the router --------------------------------------------------------------


def test_router_chooses_by_score_plus_bias_and_weighs_by_score():
    rng = np.random.default_rng(0)
    T, d, E, k, scale = 200, 32, 48, 4, 2.827
    x = rng.standard_normal((T, d)).astype(np.float32)
    wg = (rng.standard_normal((d, E)) * 0.3).astype(np.float32)
    bias = (rng.standard_normal((E,)) * 0.05).astype(np.float32)
    scores, experts, weights = moe.route_sigmoid(
        jnp.asarray(x), jnp.asarray(wg), jnp.asarray(bias), k, scale)
    assert weights.dtype == jnp.float32 and scores.dtype == jnp.float32
    moved = 0
    for t in range(T):
        s = 1.0 / (1.0 + np.exp(-(x[t].astype(np.float64) @ wg)))
        chosen = sorted(range(E), key=lambda e: -(s[e] + bias[e]))[:k]
        assert sorted(np.asarray(experts[t]).tolist()) == sorted(chosen)
        top = np.asarray([s[e] for e in np.asarray(experts[t])])
        np.testing.assert_allclose(np.asarray(weights[t]),
                                   scale * top / top.sum(), rtol=1e-5)
        moved += sorted(chosen) != sorted(
            sorted(range(E), key=lambda e: -s[e])[:k])
    np.testing.assert_allclose(np.asarray(weights.sum(-1)), scale, rtol=1e-5)
    # The bias matters: the k largest scores alone are another set for
    # a measurable share of the tokens.
    assert moved > T // 10


def test_the_seeds_router_bias_changes_the_chosen_set(params):
    """``assumed``: the bias is drawn at a size that moves the chosen
    set for a measurable share of tokens (zero would leave "choose by
    s + b, weigh by s" untested)."""
    p = params["layers"][1]
    h = jax.random.normal(jax.random.key(5), (400, TINY.hidden_size))
    _, with_b, _ = moe.route_sigmoid(h, p["router"], p["router_bias"], 2)
    _, without, _ = moe.route_sigmoid(h, p["router"],
                                      jnp.zeros_like(p["router_bias"]), 2)
    differ = (jnp.sort(with_b, -1) != jnp.sort(without, -1)).any(-1).mean()
    assert 0.05 < float(differ) < 0.95


# -- closed forms ------------------------------------------------------------


def test_yarn_frequencies_and_softmax_scale_are_the_closed_forms():
    cfg = kimi_k2.KimiK2Config()         # the published numbers
    inv, rot, cs = layers.rope_inv_freq(cfg.rope, cfg.qk_rope_head_dim)
    d, base, factor, orig = 64, 50000.0, 64.0, 4096
    assert rot == d and cs == 1.0       # mscale / mscale_all_dim = 1
    low = math.floor(d * math.log(orig / (32 * 2 * math.pi))
                     / (2 * math.log(base)))
    high = math.ceil(d * math.log(orig / (1 * 2 * math.pi))
                     / (2 * math.log(base)))
    for i in (0, low, (low + high) // 2, high, d // 2 - 1):
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        plain = base ** (-2 * i / d)
        want = ramp * plain / factor + (1 - ramp) * plain
        assert math.isclose(inv[i], want, rel_tol=1e-12)
    np.testing.assert_allclose(inv, kimi_k2_ref.yarn_inv_freq(cfg),
                               rtol=1e-12)
    m = 0.1 * math.log(64) + 1.0
    assert math.isclose(m, 1.41589, abs_tol=1e-5)
    assert math.isclose(cfg.softmax_scale, 192 ** -0.5 * m * m,
                        rel_tol=1e-12)
    assert math.isclose(cfg.softmax_scale * 192 ** 0.5, 2.0047,
                        abs_tol=1e-4)
    assert math.isclose(kimi_k2_ref.softmax_scale(cfg), cfg.softmax_scale,
                        rel_tol=1e-12)


def test_a_form_that_is_not_built_is_refused():
    for field, value in (("scoring_func", "softmax"), ("n_group", 8),
                         ("topk_group", 4), ("norm_topk_prob", False),
                         ("topk_method", "greedy"), ("moe_layer_freq", 2)):
        with pytest.raises(ValueError, match=field):
            dataclasses.replace(TINY, **{field: value})
    with pytest.raises(ValueError, match="held"):
        dataclasses.replace(TINY, first_expert=14)


# -- the share tied to the model ---------------------------------------------


def test_the_shares_routed_parts_add_up_to_the_uncut_layer():
    """Every share of one model (``first_expert`` 0, 4, 8, 12 of 16
    experts, 4 held each) holds slices of the same experts; their
    routed parts, summed, with the shared expert counted once, are the
    uncut reference's layer. The served routed layer (plan, grouped
    product, combine) is what computes each part."""
    whole = dataclasses.replace(TINY, experts_held=16, first_expert=0)
    key = jax.random.key(7)
    full = kimi_k2.init_layer(key, whole, 1)
    h2 = jax.random.normal(jax.random.key(9), (37, TINY.hidden_size))
    with jax.default_matmul_precision("highest"):
        f32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), full)
        want = kimi_k2_ref.routed(h2, f32, whole) + kimi_k2_ref.swiglu(
            h2, f32["s_gu"], f32["s_down"])
        total, shared, held_rows = 0.0, None, 0
        for first in range(0, 16, 4):
            share = dataclasses.replace(TINY, first_expert=first)
            p = kimi_k2.init_layer(key, share, 1)
            np.testing.assert_array_equal(p["w1"],
                                          full["w1"][first:first + 4])
            np.testing.assert_array_equal(p["router"], full["router"])
            out, sizes = kimi_k2.mlp(h2, p, share, "decode")
            shared = kimi_k2_ref.swiglu(h2, p["s_gu"], p["s_down"])
            total = total + (out - shared)
            held_rows += int(sizes.sum())
            # The reference, given the same share, gives the same part.
            assert np.abs(np.asarray(out - shared - kimi_k2_ref.routed(
                h2, jax.tree_util.tree_map(
                    lambda a: a.astype(jnp.float32), p), share))).max() \
                < LOGIT_TOL
        assert held_rows == 37 * TINY.num_experts_per_tok
        assert np.abs(np.asarray(total + shared - want)).max() < LOGIT_TOL


# -- the seam ----------------------------------------------------------------


def test_cache_description_is_one_kind_of_one_latent_pool(params):
    model = serving(TINY)
    (kind,) = model.kinds
    assert kind.rows == (128,) and kind.window is None     # 32 + 8 -> 128
    assert TINY.latent_width == 40 and TINY.row_width == 128
    assert model.counters == kimi_k2.COUNTERS
    eng = _engine(params)
    (pool,) = eng.kv.pools
    assert pool.shape == (3, 64, BS, 128) and eng.kv_window is None
    # The published widths: 512 + 64 in five whole lane tiles.
    assert kimi_k2.KimiK2Config().row_width == 640
    # Keys-and-values kinds say two pools of kv_heads * head_dim.
    from ray_tpu.models import gpt

    (full,) = serving(gpt.GPTConfig(
        vocab_size=128, max_seq=64, d_model=64, n_layer=2, n_head=4,
        dtype=jnp.float32)).kinds
    assert len(full.rows) == 2 and full.rows[0] == full.rows[1] \
        == full.kv_width


def test_a_latent_row_is_stored_normed_rotated_and_padded(params):
    """What the chunk program wrote for a prompt is, a token a layer,
    ``[RMSNorm(c_kv) | RoPE(k_r) | 0]`` of the reference."""
    eng = _engine(params, prefill_chunk_tokens=32)
    prompt = _prompt(4, 21)
    req = eng.add_request(prompt, max_tokens=4)
    eng.step()
    table = list(req.block_table)
    (rows,) = eng.kv.gather_tokens(table, len(prompt))
    with jax.default_matmul_precision("highest"):
        p = params["layers"][0]
        x = params["embed"][jnp.asarray(prompt)]
        h = kimi_k2_ref.rmsnorm(x, p["ln1"], TINY.rms_norm_eps)
        ckv = h @ p["w_dkv"]
        c_kv = kimi_k2_ref.rmsnorm(ckv[:, :32], p["kv_norm"],
                                   TINY.rms_norm_eps)
        k_rope = kimi_k2_ref.rotary(ckv[:, None, 32:],
                                    jnp.arange(len(prompt)), TINY)[:, 0]
    got = np.asarray(rows[0])
    assert np.abs(got[:, :32] - np.asarray(c_kv)).max() < 1e-5
    assert np.abs(got[:, 32:40] - np.asarray(k_rope)).max() < 1e-5
    assert np.abs(got[:, 40:]).max() == 0.0
    _drain(eng)


def test_step_ring_and_stats_carry_the_counters(params):
    from ray_tpu.util import perfmodel

    perfmodel.clear_device_steps()
    eng = _engine(params, name="kimi-counters")
    for i in range(3):
        eng.add_request(_prompt(40 + i, 30), max_tokens=6)
    _drain(eng)
    steps = [e for e in perfmodel.device_step_events()
             if e["name"] == "llm.step"
             and e.get("deployment") == "kimi-counters"
             and e["decode_tokens"] > 0]
    assert steps
    k, E = TINY.num_experts_per_tok, TINY.n_routed_experts
    for e in steps:
        rows = e["decode_tokens"]
        assert 0 <= e["moe_experts_hit"] <= TINY.experts_held
        # Assignments on the held experts: none to every one of the
        # padded batch's (the counters see max_batch rows).
        assert 0 <= e["moe_held_rows"] <= 4 * k
        # Over the deployment's mean, rows x k / E: at most every row.
        assert 0.0 <= e["moe_load_max"] <= E / k
        assert rows >= 1
    assert any(e["moe_held_rows"] > 0 for e in steps)
    st = eng.stats()
    assert st["kv_util_peak"] > 0
    assert st["chunk_attention"] == "interpreted"   # the kernel, off the TPU


def test_cost_description_prices_the_latent_cache_and_both_paths():
    from ray_tpu.util import perfmodel

    cost = serving(TINY).cost
    L, H = TINY.num_hidden_layers, TINY.num_attention_heads
    # A context token in the cache: one padded latent row a layer.
    assert cost["kv_bytes_per_token"] == L * 128
    # A decode row against a context token, absorbed: scores over the
    # latent row (40), values over the rank (32).
    assert cost["attn_per_ctx"] == 2.0 * L * H * (40 + 32)
    # A chunk's row: whole heads (16 + 8 and 16); and the up-projection.
    assert cost["chunk_attn_per_ctx"] == 2.0 * L * H * (24 + 16)
    assert cost["chunk_ctx_ops"] == 2.0 * L * 32 * H * 32
    one = perfmodel.decode_step_cost(TINY, [50])
    many = perfmodel.decode_step_cost(TINY, [50] * 64)
    assert many.hbm_bytes > one.hbm_bytes
    assert cost["streamed_params"](10 ** 6) <= cost["num_params"]
    cold = perfmodel.prefill_cost(TINY, 16)
    warm = perfmodel.prefill_cost(TINY, 16, ctx_tokens=64)
    assert warm.flops - cold.flops == pytest.approx(
        cost["chunk_attn_per_ctx"] * 64 * 16 + cost["chunk_ctx_ops"] * 64)
    # Parameters held here: the share's experts, counted leaf by leaf.
    params = jax.eval_shape(lambda: kimi_k2.init(jax.random.key(0), TINY))
    assert cost["num_params"] == sum(
        math.prod(x.shape) for x in jax.tree_util.tree_leaves(params))


# -- planted faults ----------------------------------------------------------


@pytest.mark.parametrize("fault", ["no_rope_term", "scale_without_mscale",
                                   "chosen_by_score_alone",
                                   "weights_not_renormalised"])
def test_planted_faults_move_the_logits(params, fault, monkeypatch):
    """Each departure from the equations that the benchmark's limits
    have to catch moves the reference's logits by far more than
    ``LOGIT_TOL`` (300x and up): what the comparison above would not
    let through."""
    prompt = _prompt(50, 48)
    want = np.asarray(kimi_k2_ref.forward(params, prompt, TINY))
    if fault == "no_rope_term":
        monkeypatch.setattr(kimi_k2_ref, "rotary",
                            lambda x, positions, cfg: jnp.zeros_like(x))
    elif fault == "scale_without_mscale":
        monkeypatch.setattr(kimi_k2_ref, "softmax_scale",
                            lambda cfg: 24 ** -0.5)
    elif fault == "chosen_by_score_alone":
        real = kimi_k2_ref.route
        monkeypatch.setattr(
            kimi_k2_ref, "route",
            lambda h, router, bias, cfg: real(h, router, 0 * bias, cfg))
    else:
        def raw(h, router, bias, cfg):
            s = jax.nn.sigmoid(h @ router)
            _, idx = jax.lax.top_k(s + bias, cfg.num_experts_per_tok)
            return idx, cfg.routed_scaling_factor * jnp.take_along_axis(
                s, idx, axis=-1)
        monkeypatch.setattr(kimi_k2_ref, "route", raw)
    got = np.asarray(kimi_k2_ref.forward(params, prompt, TINY))
    assert np.abs(got - want).max() > 100 * LOGIT_TOL


@pytest.mark.parametrize("case", ["kimi-q1", "kimi-spec"])
def test_kept_step_array_equals_one_built_from_scratch_every_step(case):
    """tests/kept_array.py's scripted run over the one latent pool: the
    engine's kept packed array equals one built from scratch at every
    decode dispatch, and the token streams and the pool's bookkeeping
    are the ones recorded on the commit before PR 46."""
    import kept_array

    kept_array.check(kept_array.engines()[case](), case)
