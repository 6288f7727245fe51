"""Laguna behind the serving seam, at a small size on the CPU with
seeded random weights in float32: the served path (LLMEngine, chunked
prefill, both pools, the paged kernel and the grouped product in the
Pallas interpreter) against the plain reference
(models/laguna_ref.py: jax.numpy, no cache, no kernel, no batching).

Tolerances: everything is float32 here, so the two sides differ by
summation order alone. Logits have magnitude ~1; 2e-4 absolute is ~100x
the error seen (2e-6) and far below what a wrong mask, a missed expert
or a wrong rotary would move (1e-2 and up)."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm.engine import LLMEngine
from ray_tpu.llm.kv_cache import WindowPool
from ray_tpu.models import (laguna, laguna_ref, layers, serving,
                            window_table_len)

KINDS = ("full_attention", "sliding_attention", "sliding_attention",
         "sliding_attention", "full_attention")
TINY = laguna.LagunaConfig(
    vocab_size=256, hidden_size=64, intermediate_size=128,
    num_hidden_layers=5, num_attention_heads_per_layer=(4, 8, 8, 8, 4),
    num_key_value_heads=2, head_dim=16, num_experts=8,
    num_experts_per_tok=2, moe_intermediate_size=32,
    shared_expert_intermediate_size=32, sliding_window=24,
    layer_types=KINDS,
    mlp_layer_types=("dense", "sparse", "sparse", "sparse", "sparse"),
    max_seq=160, dtype="float32",
    rope_full=dict(rope_theta=500000, rope_type="yarn", factor=64,
                   original_max_position_embeddings=32, beta_slow=1,
                   beta_fast=64, attention_factor=1.4158883083359672,
                   partial_rotary_factor=0.5))
LOGIT_TOL = 2e-4
BS = 8


@pytest.fixture(scope="module")
def params():
    return laguna.init(jax.random.key(0), TINY)


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(0, 256, n).tolist()


def _engine(params, **kw):
    kw = {"num_blocks": 64, "block_size": BS, "max_batch": 4,
          "prefill_chunk_tokens": 16, **kw}
    return LLMEngine(params, TINY, **kw)


def _logits_of(eng):
    """Record every logits row the engine decides a token from:
    {rid: [row, ...]}: the row its last prefill chunk's program hands
    back (fetched here whether or not the request is greedy), then one
    a decode step."""
    rows, last = {}, []
    settle, fetch, chunk = (eng._settle, eng._fetch_decisions,
                            eng._prefill_chunk)

    def on_chunk(*args):
        out = chunk(*args)
        last.append(out[0])
        return out

    def on_settle():
        # The programs not yet settled are the last ones dispatched; a
        # program that carried several spans hands back a row a span.
        spans = list(dict.fromkeys(id(ch.span) for ch in eng._pending))
        of = dict(zip(spans, last[len(last) - len(spans):]))
        for ch in eng._pending:
            if ch.done and ch.req is not None:
                row = of[id(ch.span)]
                rows.setdefault(ch.req.rid, []).append(np.asarray(
                    jax.device_get(row if ch.index is None
                                   else row[ch.index]), np.float32))
        last.clear()
        return settle()

    def on_fetch(logits, ids, all_greedy):
        got = np.asarray(jax.device_get(logits), np.float32)
        for r in eng._active:
            if r.state == "RUNNING":    # holds a lane of the kept array
                rows.setdefault(r.rid, []).append(got[r.lane, 0])
        return fetch(logits, ids, all_greedy)

    eng._settle, eng._fetch_decisions = on_settle, on_fetch
    eng._prefill_chunk = on_chunk
    return rows


def _drain(eng):
    while eng.step():
        pass


def _reference_rows(params, prompt, out):
    logits = np.asarray(laguna_ref.forward(params, prompt + out, TINY))
    return logits[len(prompt) - 1:len(prompt) - 1 + len(out)]


@pytest.mark.parametrize("budget", [16, 48], ids=[
    "chunks_of_16", "a_chunk_longer_than_the_window"])
def test_engine_logits_equal_the_plain_reference(params, budget):
    """A 70-token prompt prefilled in chunks of 16 across a window of
    24 (so chunks start inside, at and past a window boundary and
    blocks slide out mid-prompt), then 12 decode steps through both
    pools: every logits row the engine samples from equals the
    reference's full forward pass. And in a chunk of 48, whose own
    leading blocks are out of the window before they are written (they
    go to the scratch block, and the lane's table starts at the first
    block kept: before PR 33 it kept its old start and the next decode
    step ran off its end)."""
    eng = _engine(params, prefill_chunk_tokens=budget)
    rows = _logits_of(eng)
    prompt = _prompt(0, 70)
    req = eng.add_request(prompt, max_tokens=12, temperature=0.7, seed=3)
    _drain(eng)
    want = _reference_rows(params, prompt, req.output)
    got = np.stack(rows[req.rid])
    assert got.shape == want.shape
    assert np.abs(got - want).max() < LOGIT_TOL
    st = eng.stats()
    assert st["chunk_attention"] == "interpreted"   # the kernel, off the TPU
    assert st["kv_window_blocks_slid"] > 0
    assert 0 < st["kv_window_util_peak"] <= 1


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


def test_prefix_hit_at_a_contexts_end_reads_the_parked_tail(params):
    """A context sent alone parks its window tail when it is released;
    a request that extends it hits the WHOLE context in both kinds and
    gives the logits of a cold run. A context that was never released
    at its end is not taken up past what the window kind holds."""
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
    # Another body behind the same context: the tail is still parked.
    again = eng.add_request(context + _prompt(4, 11), max_tokens=4)
    _drain(eng)
    assert again.cached_tokens == len(context)
    # A prefix that ends where no sequence was released: the full kind
    # matches 16 tokens of it, the window kind holds no tail there (its
    # blocks 0 and 1 slid out of the context's window and were freed).
    assert eng.kv.match(context[:16] + _prompt(5, 9)) == 16
    mid = eng.add_request(context[:16] + _prompt(5, 9), max_tokens=3)
    _drain(eng)
    assert mid.cached_tokens == 0


def test_identical_prompt_is_a_full_hit_with_copy_on_write(params):
    """The same prompt again: every token is resident in both kinds,
    the last position is recomputed by the decode step, and its write
    splits the shared tail block of BOTH pools."""
    prompt = _prompt(6, 37)
    eng = _engine(params)
    first = eng.add_request(prompt, max_tokens=6)
    _drain(eng)
    again = eng.add_request(prompt, max_tokens=6)
    _drain(eng)
    assert again.cached_tokens == len(prompt)
    assert again.output == first.output
    assert eng.kv.cow_splits >= 1 and eng.kv_window.cow_splits >= 1


def test_preempt_and_resume_reproduce_the_tokens(params):
    """A full-kind pool too small for three sequences at once: the
    engine preempts, the victims resume through both kinds, and every
    output equals a roomy engine's."""
    prompts = [_prompt(30 + i, 30 + 5 * i) for i in range(3)]
    roomy = _engine(params)
    want = [roomy.add_request(p, max_tokens=20, seed=i)
            for i, p in enumerate(prompts)]
    _drain(roomy)
    tight = _engine(params, num_blocks=17, window_blocks=32)
    got = [tight.add_request(p, max_tokens=20, seed=i)
           for i, p in enumerate(prompts)]
    _drain(tight)
    assert sum(r.preemptions for r in got) > 0
    assert [r.output for r in got] == [r.output for r in want]
    assert tight.kv.num_free == tight.kv.capacity
    assert tight.kv_window.num_free == tight.kv_window.capacity


# -- the window allocator ----------------------------------------------------


def test_a_lane_never_holds_more_than_its_window(params):
    """Through a long prompt and a long answer a lane's window table
    stays within window / block_size + 2 blocks, and everything comes
    back when it ends."""
    eng = _engine(params)
    limit = TINY.sliding_window // BS + 2
    assert window_table_len(TINY.sliding_window, BS) == limit
    req = eng.add_request(_prompt(7, 90), max_tokens=40)
    most = 0
    while eng.step():
        most = max(most, len(req.window_table))
        assert len(req.window_table) <= limit
        if req.window_table and req.context_len:
            # The table ends with the block of the last resident token
            # (or the one granted for the next).
            last = req.window_first + len(req.window_table) - 1
            assert last in ((req.context_len - 1) // BS,
                            req.context_len // BS)
    assert most == limit or most == limit - 1
    kvw = eng.kv_window
    assert kvw.slid_blocks >= (90 + 40 - TINY.sliding_window) // BS - 2
    assert kvw.num_free == kvw.capacity          # parked counts as free
    assert eng.kv.num_free == eng.kv.capacity


def test_window_pool_slides_registers_and_matches_tails():
    kvw = WindowPool(TINY, num_blocks=32, block_size=BS)
    assert kvw.window == 24 and kvw.kind.name == "window"
    assert kvw.k.shape == (3, 32, BS, 2 * 16)
    assert kvw.keep_from(10) == 0
    # next query at 64: sees from 41, keeps from (41 - 8) // 8 = 4.
    assert kvw.keep_from(64) == 4
    seq = list(range(100, 164))                  # 64 tokens, 8 blocks
    table = kvw.alloc(8)
    free0 = kvw.num_free
    first = kvw.slide(table, 0, 64)
    assert first == 4 and len(table) == 4
    assert kvw.num_free == free0 + 4 and kvw.slid_blocks == 4
    kvw.release(list(table), seq=seq, first=first)
    assert kvw.num_free == kvw.capacity
    # The whole sequence can be taken up again: its tail is parked.
    n, f, bids = kvw.match_tail(seq + [7, 7, 7], 64)
    assert (n, f, bids) == (64, 5, table[1:])
    # ... and at the block boundary below its end too (the slack block).
    n, f, bids = kvw.match_tail(seq[:60] + [9] * 12, 56)
    assert (n, f) == (56, 4) and bids == table[:3]
    # Not lower: those blocks slid out unindexed and were reused.
    assert kvw.match_tail(seq[:40] + [9] * 12, 40) == (0, 0, [])
    kvw.acquire(bids)
    assert kvw.num_free == kvw.capacity - 3
    kvw.release(bids)
    # Double free is still an error.
    with pytest.raises(ValueError):
        kvw.release(bids)


def test_window_pool_truncate_counts_from_the_tables_first_block():
    kvw = WindowPool(TINY, num_blocks=16, block_size=BS)
    table = kvw.alloc(4)                 # the sequence's blocks 3..6
    freed = kvw.truncate(table, keep_tokens=41, first=3)   # 6 blocks
    assert len(table) == 3 and len(freed) == 1
    assert kvw.truncate(table, keep_tokens=48, first=3) == []


def test_engine_refuses_a_window_pool_that_cannot_hold_its_lanes(params):
    with pytest.raises(ValueError, match="window_blocks"):
        _engine(params, max_batch=4, window_blocks=16)


# -- the seam ----------------------------------------------------------------


def test_cache_description_has_two_kinds():
    full, window = serving(TINY).kinds
    assert (full.name, full.layers, full.window) == ("full", (0, 4), None)
    assert (window.name, window.layers, window.window) == \
        ("window", (1, 2, 3), 24)
    assert full.kv_width == window.kv_width == 32
    assert serving(TINY).counters == laguna.COUNTERS


def test_step_ring_and_stats_carry_the_new_counters(params):
    from ray_tpu.util import perfmodel

    perfmodel.clear_device_steps()
    eng = _engine(params, name="laguna-counters")
    for i in range(3):
        eng.add_request(_prompt(40 + i, 30), max_tokens=6)
    _drain(eng)
    steps = [e for e in perfmodel.device_step_events()
             if e["name"] == "llm.step"
             and e.get("deployment") == "laguna-counters"
             and e["decode_tokens"] > 0]
    assert steps
    for e in steps:
        assert 1 <= e["moe_experts_hit"] <= TINY.num_experts
        # the busiest expert's tokens over the mean: at least 1, at most
        # every assignment on one expert
        assert 1.0 <= e["moe_load_max"] <= TINY.num_experts
        assert e["window_blocks_live"] >= 1
    st = eng.stats()
    assert st["kv_window_util_peak"] > 0 and "kv_util_peak" in st


def test_cost_description_prices_experts_by_what_a_step_hits():
    from ray_tpu.util import perfmodel

    cost = serving(TINY).cost
    one = perfmodel.decode_step_cost(TINY, [50])
    many = perfmodel.decode_step_cost(TINY, [50] * 64)
    # 64 rows hit (nearly) every expert, one row its two.
    assert many.hbm_bytes > one.hbm_bytes
    assert cost["streamed_params"](10 ** 6) <= cost["num_params"]
    # A window layer's attention stops growing past its window.
    long = perfmodel.decode_step_cost(TINY, [5000])
    longer = perfmodel.decode_step_cost(TINY, [10000])
    full_only = cost["attn_per_ctx"] * 5000
    assert longer.flops - long.flops == pytest.approx(full_only)


# -- rotary ------------------------------------------------------------------


def test_yarn_frequencies_equal_the_closed_form():
    """The published full-attention group: theta 500000, factor 64,
    original context 4096, beta_fast 64, beta_slow 1, the first 64 of
    128 dims. Closed form: with r_i = i / 32 over the 32 frequencies
    and d(t) = 64 ln(4096 / (2 pi t)) / (2 ln theta), the ramp runs
    from floor(d(64)) to ceil(d(1))."""
    cfg = laguna.LagunaConfig(
        num_hidden_layers=1, num_attention_heads_per_layer=(48,),
        layer_types=("full_attention",), mlp_layer_types=("dense",))
    inv, rot, scale = layers.rope_inv_freq(cfg.rope_full, 128)
    assert rot == 64 and scale == pytest.approx(1.4158883083359672)
    theta = 500000.0
    d = lambda t: 64 * math.log(4096 / (2 * math.pi * t)) \
        / (2 * math.log(theta))
    low, high = math.floor(d(64)), math.ceil(d(1))
    assert (low, high) == (5, 16)
    for i in range(32):
        plain = theta ** (-2 * i / 64)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        want = plain * (1 - ramp) + plain / 64 * ramp
        assert inv[i] == pytest.approx(want, rel=1e-12)
    # Fast dims are untouched, slow dims divided by the factor.
    assert inv[0] == 1.0 and inv[31] == pytest.approx(
        theta ** (-62 / 64) / 64)
    # Sliding layers: every dim, unscaled, theta 10000.
    inv_s, rot_s, scale_s = layers.rope_inv_freq(cfg.rope_sliding, 128)
    assert rot_s == 128 and scale_s == 1.0
    assert inv_s[1] == pytest.approx(10000.0 ** (-2 / 128))


def test_rotary_of_the_served_path_equals_the_references():
    x = jax.random.normal(jax.random.key(5), (7, 3, 16))
    pos = jnp.arange(7) * 13
    for rope in (TINY.rope_full, TINY.rope_sliding):
        got = layers.rotary(x, pos, rope, 16)
        want = laguna_ref.rotary(x, pos, rope, 16)
        assert jnp.abs(got - want).max() < 1e-6


def test_config_counts_the_published_parameters():
    """The cell's cut: 3,870 M parameters (ISSUE 32's arithmetic)."""
    cfg = laguna.LagunaConfig(
        num_hidden_layers=5,
        num_attention_heads_per_layer=(48, 64, 64, 64, 48),
        layer_types=KINDS,
        mlp_layer_types=("dense", "sparse", "sparse", "sparse", "sparse"))
    assert round(cfg.num_params() / 1e6) == 3870
    shapes = jax.eval_shape(lambda: laguna.init(jax.random.key(0), cfg))
    n = sum(math.prod(l.shape) for l in jax.tree_util.tree_leaves(shapes))
    assert n == cfg.num_params()


@pytest.mark.parametrize("case", ["laguna-q1-window", "laguna-spec-window"])
def test_kept_step_array_equals_one_built_from_scratch_every_step(case):
    """tests/kept_array.py's scripted run over BOTH kinds of pool: the
    engine's kept packed array (the full kind's table and the window
    kind's table, first block and slot blocks) equals one built from
    scratch at every decode dispatch, through slides, grants, rollbacks,
    a preemption and finishes; the token streams and both pools'
    bookkeeping are the ones recorded on the commit before PR 46."""
    import kept_array

    kept_array.check(kept_array.engines()[case](), case)
