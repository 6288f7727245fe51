"""What a SEQUENCE keeps, beside the paged pools: the pool of state
slots (llm/kv_cache.py ``StatePool``) and the engine's use of it
(llm/engine.py), at a small size on the CPU in float32. Every way a
sequence can come by its state (zeros, a parked snapshot, a snapshot
below the matched prefix, after an eviction, after a preemption) has to
give the tokens a cold prompt gives."""

import jax
import numpy as np
import pytest

from ray_tpu.llm.engine import FINISHED, LLMEngine
from ray_tpu.llm.kv_cache import BlockChain, StatePool
from ray_tpu.models import gpt, nemotron_h as nh, nemotron_h_ref as ref
from ray_tpu.util import perfmodel

from test_nemotron_h import TINY

BS = 8


@pytest.fixture(scope="module")
def params():
    return nh.init(jax.random.key(0), TINY)


@pytest.fixture(scope="module")
def greedy(params):
    """``n`` greedy tokens behind a prompt by the plain reference: one
    compiled forward over a buffer of fixed length (causal, so what lies
    behind a position does not reach it)."""
    forward = jax.jit(lambda toks: ref.forward(params, toks, TINY))
    done = {}

    def answer(prompt, n):
        key = (tuple(prompt), n)
        if key not in done:
            buf = np.zeros((96,), np.int32)
            buf[:len(prompt)] = prompt
            for i in range(len(prompt), len(prompt) + n):
                buf[i] = int(np.asarray(forward(buf))[i - 1].argmax())
            done[key] = buf[len(prompt):len(prompt) + n].tolist()
        return done[key]

    return answer


def _engine(params, **kw):
    kw = {"num_blocks": 64, "block_size": BS, "max_batch": 4,
          "prefill_chunk_tokens": 16, "state_slots": 7, **kw}
    return LLMEngine(params, TINY, **kw)


def _run(eng, *reqs, steps=400):
    for _ in range(steps):
        if all(r.state == FINISHED for r in reqs):
            return [r.output for r in reqs]
        eng.step()
    raise AssertionError("the requests did not finish")


RNG = np.random.default_rng(7)
PREFIX = RNG.integers(0, 256, 32).tolist()
BODY_A = RNG.integers(0, 256, 13).tolist()      # ragged: 45 tokens
BODY_B = RNG.integers(0, 256, 16).tolist()      # whole blocks: 48


# -- the pool ------------------------------------------------------------------


def test_pool_grants_parks_matches_and_evicts_cold_before_taken_up():
    pool = StatePool(TINY, 6)                   # slots 1..5
    assert pool.capacity == 5 and [p.shape[:2] for p in pool.pools] \
        == [(2, 6), (2, 6)]
    seq = list(range(40))
    chain = BlockChain(BS, seq)
    lane = pool.grant()
    assert lane != 0 and pool.stats()["state_slots_live"] == 1
    a = pool.snapshot(chain.keys[1], 16, lane)      # at 16 tokens
    b = pool.snapshot(chain.keys[3], 32, lane)      # at 32
    assert pool.snapshot(chain.keys[3], 32, lane) is None   # indexed already
    assert {a, b, lane, 0} == {0, a, b, lane} and len({a, b, lane}) == 3
    # The longest prefix under what the paged pools matched, in whole
    # blocks, leaving a token to compute.
    assert pool.match(chain, 40, 39) == (32, b)
    assert pool.match(chain, 31, 39) == (16, a)
    assert pool.match(chain, 40, 16) == (16, a)
    assert pool.match(chain, 40, 15) == (0, None)
    assert pool.match(None, 40, 39) == (0, None)
    pool.take_up(b, 32, 40)
    assert (pool.taken, pool.resumed_tokens, pool.recomputed_tokens) \
        == (1, 32, 8)
    assert pool.utilization() == pytest.approx(2 / 5)   # a lane + b
    # Three more lanes: two free slots, then the snapshot nobody took
    # up goes, never the taken-up one while a cold one is left.
    more = [pool.grant() for _ in range(3)]
    assert None not in more and a in more and pool.evicted == 1
    assert pool.match(chain, 40, 39) == (32, b)
    # A held snapshot is not evicted: no slot can be had.
    pool.hold(b)
    assert pool.grant() is None
    pool.read(b)
    assert pool.grant() == b and pool.evicted == 2
    assert pool.match(chain, 40, 39) == (0, None)
    with pytest.raises(ValueError):
        pool.give_back(0)
    for s in (lane, *more, b):
        pool.give_back(s)
    assert pool.stats()["state_slots_live"] == 0 and len(pool._free) == 5
    assert pool.live_peak == 1.0
    with pytest.raises(ValueError, match="no state"):
        StatePool(gpt.TINY, 4)


# -- the engine ----------------------------------------------------------------


def test_a_slots_next_tenant_starts_from_zeros(params, greedy):
    """Two cold prompts one after the other in an engine with ONE lane:
    the second takes the slot the first moved for 45 + 6 tokens."""
    eng = _engine(params, max_batch=1, state_slots=2, prefix_cache=False)
    for prompt in (PREFIX + BODY_A, BODY_B + PREFIX):
        r = eng.add_request(prompt, 6)
        assert _run(eng, r)[0] == greedy(prompt, 6)
    assert eng.states.stats()["state_snapshots"] == 0   # nothing to index


def test_prefix_hit_through_a_snapshot_gives_the_cold_prompts_tokens(
        params, greedy):
    eng = _engine(params)
    cold = eng.add_request(PREFIX + BODY_A, 8)
    assert _run(eng, cold)[0] == greedy(PREFIX + BODY_A, 8)
    # A ragged prompt: its snapshot sits at its last block boundary.
    st = eng.states.stats()
    assert (st["state_snapshots"], st["state_snapshots_parked"]) == (1, 1)
    # The prefix sent alone (the benchmark's registration) is reusable
    # at its end ...
    alone = eng.add_request(PREFIX, 1)
    _run(eng, alone)
    assert eng.states.stats()["state_snapshots"] == 2
    # ... and a sharer takes it up: its whole prefix cached, one span.
    hit = eng.add_request(PREFIX + BODY_B, 6)
    assert _run(eng, hit)[0] == greedy(PREFIX + BODY_B, 6)
    assert hit.cached_tokens == len(PREFIX)
    st = eng.states.stats()
    assert (st["state_taken"], st["state_resumed_tokens"]) == (1, 32)
    assert st["state_snapshots_taken_up"] == 1
    # The same prompt again: the paged pools match all 48 tokens, a
    # state has to leave one to compute, so the hit is cut back to the
    # newest snapshot under 48 (the prefix's) and the rest recomputed.
    again = eng.add_request(PREFIX + BODY_B, 6)
    assert _run(eng, again)[0] == greedy(PREFIX + BODY_B, 6)
    assert again.cached_tokens == 32
    st = eng.states.stats()
    assert st["state_resumed_tokens"] == 64
    assert st["state_recomputed_tokens"] >= 16
    # Every slot came back, every hold was read.
    assert st["state_slots_live"] == 0 and not eng.states._held
    assert eng.kv.num_free == eng.kv.capacity


def test_an_evicted_snapshot_costs_recomputation_never_correctness(
        params, greedy):
    """Two slots beside scratch and two lanes: every parked snapshot is
    evicted for the next grant."""
    eng = _engine(params, max_batch=2, state_slots=3)
    _run(eng, eng.add_request(PREFIX, 1))
    a = eng.add_request(PREFIX + BODY_A, 6)
    b = eng.add_request(PREFIX + BODY_B, 6)
    out = _run(eng, a, b)
    assert out == [greedy(PREFIX + BODY_A, 6), greedy(PREFIX + BODY_B, 6)]
    st = eng.states.stats()
    assert st["state_evicted"] >= 1
    assert st["state_recomputed_tokens"] >= 32      # matched, no state left


def test_preempt_and_resume_gives_the_same_tokens(params, greedy):
    eng = _engine(params)
    r = eng.add_request(PREFIX + BODY_A, 10)
    while len(r.output) < 4:
        eng.step()
    slot = r.state_slot
    with eng._lock:
        eng._preempt(r)
    assert r.state_slot is None and slot not in eng.states._live
    assert _run(eng, r)[0] == greedy(PREFIX + BODY_A, 10)
    assert r.preemptions == 1
    # It resumed from its prompt's snapshot (40 of 45 + 4 tokens), not
    # from a token count.
    assert r.cached_tokens == 40
    assert eng.states.stats()["state_resumed_tokens"] == 40


def test_counters_and_phases_reach_stats_and_the_step_ring(params):
    perfmodel.clear_device_steps()
    eng = _engine(params, name="state-ring")
    _run(eng, eng.add_request(PREFIX, 1))
    _run(eng, eng.add_request(PREFIX + BODY_B, 3))
    stats = eng.stats()
    for key in ("state_slots_live", "state_snapshots_parked", "state_taken",
                "state_evicted", "state_resumed_tokens",
                "state_recomputed_tokens", "state_live_peak"):
        assert key in stats, key
    steps = [e for e in perfmodel.device_step_events()
             if e["name"] == "llm.step" and e["deployment"] == "state-ring"]
    assert all("state_slots_live" in e for e in steps)
    assert max(e["state_slots_live"] for e in steps) == 1
    phases = set().union(*(e["phases_ms"] for e in steps))
    assert {"llm.state_snapshot", "llm.state_restore"} <= phases
    # The step program's own counters, as the other routed models'.
    decoded = [e for e in steps if e["decode_tokens"]]
    assert decoded and all(
        {"moe_experts_hit", "moe_held_rows", "moe_load_max",
         "kv_pages_in_runs"} <= set(e) for e in decoded)


def test_construction_refuses_what_a_state_cannot_do(params):
    with pytest.raises(ValueError, match="rolled back"):
        _engine(params, speculative={"k": 2})
    with pytest.raises(ValueError, match="state_slots 4"):
        _engine(params, max_batch=4, state_slots=4)
    # A model without a state has no slots and takes no argument's harm.
    eng = LLMEngine(gpt.init(jax.random.key(0), gpt.TINY), gpt.TINY,
                    state_slots=9)
    assert eng.states is None and "state_slots_live" not in eng.stats()
