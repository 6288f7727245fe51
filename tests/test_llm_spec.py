"""Speculative decoding (llm/spec.py + the engine verify step):
output exactness, rejection-sampler distribution math, KV rollback,
lifecycle events, and the full-hit TTFT fast start.

The load-bearing property is BIT-IDENTICAL output: the sampler is keyed
by (seed, position) alone, so verification collapses to an equality
check against the replayed keyed draw — every determinism case here
compares token streams, not distributions. The distribution-level
primitive (sampling.rejection_sample) is tested separately against
hand-computed acceptance probabilities.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.llm import LLMEngine, PagedKVCache, PrefixPool  # noqa: E402
from ray_tpu.llm.sampling import (  # noqa: E402
    accept_draws,
    rejection_sample,
    sample,
    target_probs,
    verify_tokens,
)
from ray_tpu.llm.spec import (  # noqa: E402
    NgramProposer,
    Proposer,
    SpecConfig,
    resolve_spec_config,
)
from ray_tpu.models.gpt import GPTConfig, init  # noqa: E402

CFG = GPTConfig(vocab_size=128, max_seq=64, d_model=64, n_layer=2,
                n_head=4, dtype=jnp.float32)
PARAMS = init(jax.random.PRNGKey(0), CFG)

# Repetitive prompt: the untrained greedy model falls into a token loop
# almost immediately, so the n-gram proposer's accept rate is high —
# the workload speculative decoding exists for.
LOOPY = [5, 9, 5, 9, 5, 9, 5]
# No repeated n-gram and high-entropy sampling: proposals are rare or
# mostly rejected — the correction path does the work.
UNIQ = list(range(30, 42))

NGRAM = {"mode": "ngram", "k": 4}


def _drain(eng, max_steps=300):
    for _ in range(max_steps):
        s = eng.stats()
        if not s["in_flight"] and not s["waiting"]:
            return
        eng.step()
    raise AssertionError("engine did not drain")


def _run(speculative, reqs, *, num_blocks=32, block_size=8, max_batch=4):
    eng = LLMEngine(PARAMS, CFG, num_blocks=num_blocks,
                    block_size=block_size, max_batch=max_batch,
                    speculative=speculative)
    hs = [eng.add_request(**r) for r in reqs]
    _drain(eng)
    return eng, hs


# ---------------------------------------------------------------------------
# Determinism: spec == non-spec, token for token
# ---------------------------------------------------------------------------
def test_ngram_greedy_is_token_identical():
    _, base = _run(None, [dict(prompt=LOOPY, max_tokens=16)])
    eng, spec = _run(NGRAM, [dict(prompt=LOOPY, max_tokens=16)])
    assert spec[0].output == base[0].output
    assert spec[0].finish_reason == base[0].finish_reason
    st = eng._spec.stats()
    assert st["accepted"] > 0, "loopy greedy decode must accept proposals"
    # Fewer scheduler steps than emitted tokens is the whole point.
    assert eng._steps < len(spec[0].output)


def test_ngram_sampled_is_token_identical():
    reqs = [dict(prompt=LOOPY, max_tokens=12, temperature=0.8, seed=11),
            dict(prompt=UNIQ, max_tokens=10, temperature=1.2, seed=3,
                 top_k=8)]
    _, base = _run(None, reqs)
    _, spec = _run(NGRAM, reqs)
    for b, s in zip(base, spec):
        assert s.output == b.output


def test_draft_proposer_is_token_identical():
    # Self-draft (draft = target): greedy proposals always match the
    # greedy target, so every verify step accepts everything.
    reqs = [dict(prompt=LOOPY, max_tokens=8)]
    _, base = _run(None, reqs)
    eng, spec = _run({"mode": "draft", "k": 3}, reqs)
    assert spec[0].output == base[0].output
    assert eng._spec.accept_rate() == 1.0


def test_rejection_path_is_token_identical():
    # High temperature on a non-self-similar prompt: proposals are
    # frequently wrong, exercising the correction draw + KV rollback.
    reqs = [dict(prompt=UNIQ, max_tokens=14, temperature=1.5, seed=7)]
    _, base = _run(None, reqs)
    eng, spec = _run(NGRAM, reqs)
    assert spec[0].output == base[0].output
    assert eng._spec.rolled_back > 0, \
        "hot sampling over a unique prompt should reject some proposals"


def test_batch_recomposition_is_token_identical():
    """A request joining mid-generation must not perturb the verify
    lanes already running (and vice versa)."""
    solo = {}
    for name, req in (("a", dict(prompt=LOOPY, max_tokens=14, seed=2,
                                 temperature=0.7)),
                      ("b", dict(prompt=UNIQ, max_tokens=10))):
        _, hs = _run(NGRAM, [req])
        solo[name] = list(hs[0].output)

    eng = LLMEngine(PARAMS, CFG, num_blocks=32, block_size=8,
                    max_batch=4, speculative=NGRAM)
    a = eng.add_request(prompt=LOOPY, max_tokens=14, seed=2,
                        temperature=0.7)
    eng.step()
    eng.step()                       # a mid-generation
    assert a.finish_reason is None and len(a.output) >= 2
    b = eng.add_request(prompt=UNIQ, max_tokens=10)
    _drain(eng)
    comps = [set(rids) for _, rids in eng.step_log]
    assert {a.rid, b.rid} in comps, "batch was recomposed mid-stream"
    assert a.output == solo["a"]
    assert b.output == solo["b"]


def test_preempt_resume_on_tight_pool_is_token_identical():
    reqs = [dict(prompt=LOOPY, max_tokens=10, seed=2, temperature=0.7),
            dict(prompt=UNIQ, max_tokens=8, seed=5, temperature=0.9),
            dict(prompt=[20, 21, 20, 21, 20], max_tokens=8)]
    _, roomy = _run(None, reqs, num_blocks=64)
    ref = [list(h.output) for h in roomy]

    eng, tight = _run(NGRAM, reqs, num_blocks=5)
    assert [list(h.output) for h in tight] == ref
    assert sum(h.preemptions for h in tight) > 0, \
        "expected preemption on the tight pool"
    assert eng.kv.num_free == eng.kv.capacity


class _Replay(Proposer):
    """Proposes what is known to come: the continuation of whichever
    finished sequence ``tokens`` is a prefix of. Every proposal is
    accepted, so a step's span is always the full k + 1 tokens."""

    name = "replay"

    def __init__(self, finished):
        self.finished = [list(seq) for seq in finished]

    def propose(self, tokens, k):
        toks = list(tokens)
        seq = next(s for s in self.finished if s[:len(toks)] == toks)
        return seq[len(toks):len(toks) + k]


def test_one_builder_with_and_without_a_proposer_emits_the_same_tokens():
    """The decode step has one builder: rows a lane are 1 + proposals,
    and with no proposer that is the one-token step. Greedy and seeded
    sampled requests stream the same tokens with an n-gram proposer and
    with none, on a roomy pool and through preemption and resume on a
    tight one; and through a lane that finishes on a stop token in the
    MIDDLE of an accepted span (what the step decided past the stop is
    dropped, and only the resident span is released)."""
    free = dict(prompt=LOOPY, max_tokens=16)
    _, (probe,) = _run(None, [free])
    # The token the greedy stream turns to a few tokens in.
    stop = probe.output[-1]
    at = probe.output.index(stop)
    assert at >= 3, probe.output
    reqs = [dict(free, stop_tokens=(stop,)),
            dict(prompt=UNIQ, max_tokens=10, temperature=0.9, seed=5,
                 top_k=16),
            dict(prompt=[20, 21, 20, 21, 20], max_tokens=8)]
    _, base = _run(None, reqs, num_blocks=64)
    ref = [list(h.output) for h in base]
    assert ref[0] == probe.output[:at + 1]
    reasons = [h.finish_reason for h in base]
    assert reasons == ["stop", "length", "length"]

    for speculative, num_blocks in ((NGRAM, 64), (None, 5), (NGRAM, 5)):
        eng, hs = _run(speculative, reqs, num_blocks=num_blocks)
        assert [list(h.output) for h in hs] == ref, (speculative, num_blocks)
        assert [h.finish_reason for h in hs] == reasons
        if num_blocks == 5:
            assert sum(h.preemptions for h in hs) > 0, \
                "expected preemption on the tight pool"
        assert eng.kv.num_free == eng.kv.capacity

    # Every proposal right and k = 2: a decode step emits three tokens,
    # output[0] is the prefill's, so the stop token at index `at` has a
    # decided token behind it in its span unless it is the span's last.
    assert (at - 1) % 3 != 2, at
    eng = LLMEngine(PARAMS, CFG, num_blocks=64, block_size=8, max_batch=4,
                    speculative={"mode": "ngram", "k": 2})
    unstopped = [dict(r, stop_tokens=()) for r in reqs]
    eng._spec.proposer = _Replay(
        r["prompt"] + list(h.output)
        for r, h in zip(reqs, _run(None, unstopped, num_blocks=64)[1]))
    hs = [eng.add_request(**r) for r in reqs]
    _drain(eng)
    assert [list(h.output) for h in hs] == ref
    assert [h.finish_reason for h in hs] == reasons
    assert eng._spec.accept_rate() == 1.0
    decided = sum(a["emitted"] for _, kind, a in eng._spec.events
                  if kind == "accept" and a["rid"] == hs[0].rid)
    assert decided > len(ref[0]) - 1       # something was dropped
    assert eng.kv.num_free == eng.kv.capacity


def test_spec_stats_and_gauge_surface():
    eng, _ = _run(NGRAM, [dict(prompt=LOOPY, max_tokens=16)])
    s = eng.stats()
    assert 0.0 <= s["spec_accept_rate"] <= 1.0
    assert s["spec_tokens_per_step"] >= 1.0
    assert s["spec"]["mode"] == "ngram"
    assert s["spec"]["verify_steps"] == eng._spec.verify_steps
    # Read off the lowered decode program at this engine's k + 1 rows.
    assert s["paged_kernel"] == "interpret"
    kinds = {k for _, k, _ in eng._spec.events}
    assert {"propose", "verify", "accept"} <= kinds


def test_spec_off_has_no_spec_surface():
    eng, _ = _run(None, [dict(prompt=LOOPY, max_tokens=4)])
    assert eng._spec is None
    assert "spec_accept_rate" not in eng.stats()


# ---------------------------------------------------------------------------
# Full-hit TTFT: first token in the activation step, fast start on verify
# ---------------------------------------------------------------------------
PREFIX = [7] * 20 + [1, 2, 3]


def test_full_hit_emits_first_token_in_activation_step():
    """TTFT regression pin: a FULL prefix-cache hit computes no
    prefill, but its first token must still arrive in the SAME step
    that admits it — the held-back last position re-decodes
    write-then-attend inside that step."""
    eng = LLMEngine(PARAMS, CFG, num_blocks=64, block_size=8)
    a = eng.add_request(list(PREFIX), max_tokens=6)
    _drain(eng)
    b = eng.add_request(list(PREFIX), max_tokens=6)
    eng.step()
    assert b.cached_tokens == len(PREFIX), "expected a full hit"
    assert len(b.output) >= 1, \
        "full-hit request must emit its first token in its first step"
    _drain(eng)
    assert b.output == a.output


def test_full_hit_fast_start_through_verify_path():
    """With speculation on, the full hit's FIRST step runs through the
    verify path with proposals drawn from its own (fully known) prompt:
    several tokens land in the activation step."""
    # Trailing run of 5s: the n-gram proposer predicts more 5s from the
    # prompt alone, and the untrained greedy model indeed emits 5s.
    prompt = [5, 9] + [5] * 12
    eng = LLMEngine(PARAMS, CFG, num_blocks=64, block_size=8,
                    speculative=NGRAM)
    a = eng.add_request(list(prompt), max_tokens=8)
    _drain(eng)
    b = eng.add_request(list(prompt), max_tokens=8)
    eng.step()
    assert b.cached_tokens == len(prompt)
    assert len(b.output) >= 2, \
        "verify fast start should emit multiple tokens in step one"
    _drain(eng)
    assert b.output == a.output


# ---------------------------------------------------------------------------
# verify_tokens: the deterministic keyed collapse
# ---------------------------------------------------------------------------
def _keyed_rows(tokens, vocab=16):
    """Logits rows whose greedy draw at row j is tokens[j]."""
    rows = np.zeros((len(tokens), vocab), np.float32)
    for j, t in enumerate(tokens):
        rows[j, t] = 5.0
    return rows


def test_verify_accepts_matching_prefix_and_bonus():
    rows = _keyed_rows([3, 7, 1, 9])
    n_acc, emitted = verify_tokens(rows, [3, 7, 1])
    assert n_acc == 3
    assert emitted == [3, 7, 1, 9]          # all accepted + bonus draw


def test_verify_rejects_at_first_mismatch_with_correction():
    rows = _keyed_rows([3, 7, 1, 9])
    n_acc, emitted = verify_tokens(rows, [3, 2, 1])
    assert n_acc == 1
    assert emitted == [3, 7]                # accepted, then corrected
    # len(emitted) == n_accepted + 1 always.
    assert len(emitted) == n_acc + 1


def test_verify_matches_sequential_sampling_under_temperature():
    rng = np.random.default_rng(0)
    rows = rng.normal(size=(5, 32)).astype(np.float32)
    kw = dict(temperature=0.9, top_k=8, seed=13)
    seq = [sample(rows[j], position=100 + j, **kw) for j in range(5)]
    n_acc, emitted = verify_tokens(rows, seq[:4], start_pos=100, **kw)
    assert n_acc == 4 and emitted == seq


def test_verify_requires_one_extra_row():
    with pytest.raises(ValueError):
        verify_tokens(_keyed_rows([1, 2]), [1, 2])


@pytest.mark.parametrize("proposed", [
    [3, 7, 1], [3, 2, 1], [8, 7, 1], [3, 7, 5], []],
    ids=["all_match", "reject_second", "reject_first", "reject_last",
         "no_proposals"])
def test_accept_draws_on_ids_is_verify_tokens_on_the_rows(proposed):
    """A greedy lane's acceptance over the verify program's ids is
    verify_tokens over the rows those ids are the argmax of, and asks
    for no draw past the first mismatch."""
    target = [3, 7, 1, 9]
    asked = []

    def draw(j):
        asked.append(j)
        return target[j]

    got = accept_draws(draw, proposed)
    assert got == verify_tokens(_keyed_rows(target)[:len(proposed) + 1],
                                proposed)
    assert asked == list(range(got[0] + 1))


# ---------------------------------------------------------------------------
# The verify step decides greedy lanes' tokens on the device
# ---------------------------------------------------------------------------
def test_all_greedy_verify_step_fetches_ids_not_logits(watch_device_get):
    """Every lane greedy: a verify step brings max_batch x (k + 1) ints
    (and the program's one counter's row behind them) to the host and
    nothing else; the logits stay on the device."""
    eng = LLMEngine(PARAMS, CFG, num_blocks=32, block_size=8, max_batch=4,
                    speculative=NGRAM)
    hs = [eng.add_request(LOOPY, max_tokens=24),
          eng.add_request(UNIQ, max_tokens=12)]
    eng.step()                      # prefills fetch their last row each
    fetched = watch_device_get()
    eng.step()
    eng.step()
    assert fetched == [(eng.max_batch + 1) * (NGRAM["k"] + 1)] * 2, fetched
    _drain(eng)
    assert [h.finish_reason for h in hs] == ["length", "length"]
    assert eng.stats()["spec"]["accepted"] > 0


@pytest.mark.parametrize("host_lanes", [0, 1], ids=["all_greedy", "mixed"])
def test_verify_step_counts_tokens_by_where_they_were_decided(host_lanes):
    """Under speculation the ring's device_sampled counts greedy lanes,
    stats() counts their tokens (several a step), and a lane with a
    temperature beside them draws on the host exactly what it draws
    without speculation."""
    from ray_tpu.util import perfmodel

    reqs = [dict(prompt=UNIQ, max_tokens=8, seed=5, temperature=0.9,
                 top_k=40)][:host_lanes] + [
        dict(prompt=LOOPY, max_tokens=16),
        dict(prompt=[20, 21, 20, 21, 20], max_tokens=10)]
    _, base = _run(None, reqs)
    perfmodel.clear_device_steps()
    eng, hs = _run(NGRAM, reqs)
    assert [h.output for h in hs] == [h.output for h in base]
    ring = [e for e in perfmodel.device_step_events()
            if e["name"] == "llm.step" and e["lanes"]]
    assert ring and ring[0]["lanes"] == len(reqs)
    assert ring[0][perfmodel.DEVICE_SAMPLED] == len(reqs) - host_lanes
    assert all(e[perfmodel.DEVICE_SAMPLED] <= e["lanes"] for e in ring)
    s = eng.stats()
    # Every token of the lane with a temperature is drawn on the host;
    # a greedy lane's come from the programs' own argmax, the first
    # one (its last prefill chunk's) included.
    assert s["tokens_decided_on_host"] == sum(
        h.max_tokens for h in hs[:host_lanes])
    assert s["tokens_decided_on_device"] == sum(
        h.max_tokens for h in hs[host_lanes:])
    assert s["spec_tokens_per_step"] > 1.0
    perfmodel.clear_device_steps()


# ---------------------------------------------------------------------------
# rejection_sample: hand-computed acceptance probabilities
# ---------------------------------------------------------------------------
def test_rejection_sample_acceptance_threshold_is_exact():
    target = [0.1, 0.6, 0.3]
    draft = [0.5, 0.3, 0.2]
    # Accept prob of token 0 is min(1, 0.1/0.5) = 0.2 exactly.
    assert rejection_sample(target, draft, 0, u=0.1999)[0] is True
    assert rejection_sample(target, draft, 0, u=0.2001)[0] is False
    # Token 1: target beats draft, always accepted.
    assert rejection_sample(target, draft, 1, u=0.9999)[0] is True


def test_rejection_sample_residual_is_renormalized_excess():
    target = np.array([0.1, 0.6, 0.3])
    draft = np.array([0.5, 0.3, 0.2])
    # Residual = normalize(max(target - draft, 0)) = [0, .75, .25].
    acc, tok = rejection_sample(target, draft, 0, u=0.99, resample_u=0.74)
    assert (acc, tok) == (False, 1)
    acc, tok = rejection_sample(target, draft, 0, u=0.99, resample_u=0.76)
    assert (acc, tok) == (False, 2)


def test_rejection_sample_marginal_matches_target():
    """Accept mass + residual mass integrates back to the target
    distribution — Leviathan App. A, checked numerically. The accept
    probability per proposal is min(1, p/q) (pinned by the threshold
    test above); the residual is probed through the implementation's
    own inverse CDF on a fine resample_u grid."""
    target = np.array([0.15, 0.55, 0.30])
    draft = np.array([0.40, 0.40, 0.20])
    grid = (np.arange(2000) + 0.5) / 2000
    counts = np.zeros(3)
    for x in range(3):
        a = min(1.0, target[x] / draft[x])
        counts[x] += draft[x] * a
        if a < 1.0:
            for ru in grid:
                acc, tok = rejection_sample(target, draft, x,
                                            u=0.999999, resample_u=ru)
                assert not acc
                counts[tok] += draft[x] * (1.0 - a) / len(grid)
    np.testing.assert_allclose(counts, target, atol=2e-3)


def test_rejection_sample_zero_draft_prob_raises():
    with pytest.raises(ValueError):
        rejection_sample([0.5, 0.5], [1.0, 0.0], 1, u=0.5)


def test_target_probs_matches_sample_greedy_and_topk():
    rng = np.random.default_rng(1)
    row = rng.normal(size=24).astype(np.float32)
    p = target_probs(row)
    assert p[int(row.argmax())] == 1.0 and p.sum() == 1.0
    p = target_probs(row, temperature=0.7, top_k=5)
    assert np.isclose(p.sum(), 1.0) and (p > 0).sum() == 5


# ---------------------------------------------------------------------------
# KV rollback: truncate-to-cursor
# ---------------------------------------------------------------------------
def test_truncate_frees_surplus_blocks_only():
    kv = PagedKVCache(CFG, num_blocks=16, block_size=8)
    table = kv.alloc(4)
    free0 = kv.num_free
    surplus = kv.truncate(table, 17)        # 17 tokens -> 3 blocks
    assert len(table) == 3 and len(surplus) == 1
    assert kv.num_free == free0 + 1
    # Already-tight table: no-op.
    assert kv.truncate(table, 24) == []
    assert len(table) == 3


def test_truncate_respects_prefix_refcounts():
    """Rolling back one sequence's speculative tail must not free
    blocks a co-reader still references, and must leave parked (LRU)
    cached blocks undisturbed."""
    kv = PrefixPool(CFG, num_blocks=16, block_size=4)
    seq = list(range(12))                   # 3 full blocks
    t1, cached = kv.admit(seq, len(seq) + 1)
    assert cached == 0
    kv.register(seq, t1[:3])
    # Park an unrelated chain in the LRU (released, evictable).
    other = [99, 98, 97, 96]
    t_other, _ = kv.admit(other, len(other))
    kv.register(other, t_other[:1])
    kv.release(t_other)
    parked = len(kv._lru)
    assert parked >= 1

    # Second reader shares the registered chain (ref 2 on those blocks).
    t2, cached2 = kv.admit(seq, len(seq) + 2)
    assert cached2 == 12
    shared = [b for b in t2 if b in t1]
    assert shared, "expected cache-hit sharing"
    free0 = kv.num_free

    # Speculative tail rollback on reader 2: keep 9 tokens -> 3 blocks,
    # freeing only its PRIVATE 4th block — shared blocks keep their
    # refcount.
    surplus = kv.truncate(t2, 9)
    assert surplus, "expected surplus from the speculative tail"
    assert all(b in t1 or kv._ref.get(b, 0) >= 1 or b in kv._lru
               or b in kv._free for b in shared)
    # Reader 1's chain is still fully referenced and readable.
    assert all(kv._ref.get(b, 0) >= 1 for b in t1)
    assert kv.num_free >= free0
    assert len(kv._lru) >= parked, "parked LRU chain was disturbed"

    kv.release(t2, seq=seq)
    kv.release(t1, seq=seq)
    assert kv.num_free == kv.capacity


def test_engine_pool_is_clean_after_heavy_rejection():
    """After a run full of rejections/rollbacks, every block must come
    back (no leak, no double-free) — on both pool flavors."""
    reqs = [dict(prompt=UNIQ, max_tokens=12, temperature=1.5, seed=9)]
    for prefix_cache in (True, False):
        eng = LLMEngine(PARAMS, CFG, num_blocks=32, block_size=8,
                        prefix_cache=prefix_cache, speculative=NGRAM)
        for r in reqs:
            eng.add_request(**r)
        _drain(eng)
        assert eng.kv.num_free == eng.kv.capacity


# ---------------------------------------------------------------------------
# Config plumbing
# ---------------------------------------------------------------------------
def test_resolve_spec_config_validation():
    assert resolve_spec_config(None) is None
    cfg = resolve_spec_config({"mode": "ngram", "k": 2})
    assert isinstance(cfg, SpecConfig) and cfg.k == 2
    with pytest.raises(ValueError):
        resolve_spec_config({"mode": "warp"})
    with pytest.raises(ValueError):
        resolve_spec_config({"mode": "ngram", "k": 0})
    with pytest.raises(ValueError):
        resolve_spec_config({"bogus": 1})
    with pytest.raises(TypeError):
        resolve_spec_config(42)


def test_ngram_proposer_prefers_most_recent_match():
    p = NgramProposer(max_ngram=3, min_ngram=1)
    #        0  1  2  3  4  5  6
    toks = [1, 2, 9, 1, 2, 8, 1, 2]
    # Suffix [1, 2] most recently continued with 8 (position 4-5).
    assert p.propose(toks, 2) == [8, 1]
    assert p.propose([1, 2, 3], 3) == []    # no earlier occurrence
    assert p.propose([4], 2) == []          # history too short
