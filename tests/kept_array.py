"""A scripted engine run, shared by tests/test_llm_engine.py,
tests/test_laguna.py and tests/test_kimi_k2.py: admissions, a partial
and a full prefix-cache hit, a request of one token, decode steps (with
speculation: proposals, rollbacks), a preemption, finishes.

``drive`` uses the engine's public surface only (``add_request``,
``step``), so the same script runs on the commit before PR 46, whose
token streams and pool bookkeeping ``tests/data/kept_array_streams.json``
records (made by running this file: see ``__main__`` below). ``check``
runs it on this tree and holds, at EVERY decode dispatch, the engine's
kept packed array (llm/engine.py ``_inputs``) to one built from scratch
from the requests' own state, and after every step each lane's tables
to its request's lists and every free lane's row to the scratch
lane's values.
"""

import json
import os

import numpy as np

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "data", "kept_array_streams.json")
BS = 8


def _tokens(seed, n, vocab=120):
    return [int(t) for t in
            np.random.default_rng(seed).integers(1, vocab, n)]


def drive(eng, after_step=lambda: None, reqs=None):
    """The script. Returns what the run decided, as plain data; the
    requests it added are left in ``reqs`` (name -> Request) for a
    caller that gives one: the engine keeps no finished request."""
    reqs = {} if reqs is None else reqs

    def add(name, prompt, **kw):
        reqs[name] = eng.add_request(prompt, **kw)

    def steps(n):
        for _ in range(n):
            eng.step()
            after_step()

    # A prompt with a period, so an n-gram proposer has something to
    # propose (and the model, random, rejects most of it: rollbacks).
    period = _tokens(1, 4)
    base = (period * 5)[:19]
    add("first", base, max_tokens=12)
    steps(3)                    # two chunks, then it decodes: registered
    add("partial", base[:16] + _tokens(2, 5), max_tokens=10,
        temperature=0.8, top_k=20, seed=7)      # hits two whole blocks
    add("full", base, max_tokens=6)             # hits every token
    add("one", _tokens(3, 5), max_tokens=1)     # ends in its prefill
    steps(3)
    # More than the lanes and the pool hold: they queue, and the pool
    # runs dry while they grow (a preemption, a resume).
    add("long", (period * 8)[:30], max_tokens=20)
    add("sampled", _tokens(4, 25), max_tokens=18, temperature=0.7,
        seed=11)
    add("again", base + _tokens(5, 3), max_tokens=8)
    for _ in range(400):
        if not eng.step():
            break
        after_step()
    else:
        raise AssertionError("the script did not drain")
    after_step()
    out = {
        "outputs": {k: r.output for k, r in reqs.items()},
        "finish": {k: r.finish_reason for k, r in reqs.items()},
        "cached": {k: r.cached_tokens for k, r in reqs.items()},
        "preemptions": {k: r.preemptions for k, r in reqs.items()},
        "steps": eng._steps,
        # The order blocks came back in is the order they are granted
        # in next: equal lists mean equal grants and frees all along.
        "free": list(eng.kv._free),
    }
    if hasattr(eng.kv, "prefix_stats"):
        out["prefix"] = {k: v for k, v in eng.kv.prefix_stats().items()
                         if k != "hit_rate"}
        out["parked"] = list(eng.kv._lru)
    if eng.kv_window is not None:
        out["window_free"] = list(eng.kv_window._free)
        out["window_prefix"] = {
            k: v for k, v in eng.kv_window.prefix_stats().items()
            if k != "hit_rate"}
    return out


def expected_inputs(eng, packed, firsts):
    """The decode program's array built FROM SCRATCH from the requests'
    own state, as the engine built it every step before it kept one
    (zeros, then a loop over the lanes). Only a lane's row count and
    its proposals are read off ``packed`` itself."""
    from ray_tpu.models import pack_step

    c = eng._cols
    B, Q, bs = eng.max_batch, eng._q_rows, eng.kv.block_size
    tokens = np.zeros((B, Q), np.int32)
    positions = np.zeros((B, Q), np.int32)
    slot_blocks = np.zeros((B, Q), np.int32)
    slot_offsets = np.zeros((B, Q), np.int32)
    context_lens = np.ones((B,), np.int32)
    q_lens = np.ones((B,), np.int32)
    tables = np.zeros((B, eng.max_nb), np.int32)
    win = None
    if eng.kv_window is not None:
        win = np.zeros((B, eng._win_len + 1 + Q), np.int32)
    lanes = set()
    for req in eng._active:
        if req.state != "RUNNING":
            assert req.lane is None
            continue
        i, slot, table = req.lane, req.context_len, req.block_table
        assert i not in lanes and 0 <= i < B
        lanes.add(i)
        n = int(packed[i, c.q_len])
        assert 1 <= n <= Q
        fed = slot - len(req.prompt)
        if fed < len(req.output):
            tokens[i, 0] = req.prompt[slot] if fed < 0 else req.output[fed]
        else:
            # Its prompt ended in a chunk of this step: the token is on
            # the device, in ``firsts`` at its lane, and the row says 0.
            assert fed == len(req.output) and firsts[i] >= 0
        tokens[i, 1:n] = packed[i, c.tokens + 1:c.tokens + n]
        for j in range(n):
            positions[i, j] = slot + j
            slot_blocks[i, j] = table[(slot + j) // bs]
            slot_offsets[i, j] = (slot + j) % bs
        context_lens[i] = slot + n
        q_lens[i] = n
        tables[i, :len(table)] = table
        if win is not None:
            wt, first = req.window_table, req.window_first
            win[i, :len(wt)] = wt
            win[i, eng._win_len] = first
            for j in range(n):
                win[i, eng._win_len + 1 + j] = wt[(slot + j) // bs - first]
    return pack_step(tokens, positions, tables, context_lens, q_lens,
                     slot_blocks, slot_offsets, win)


def check_tables(eng):
    """Between steps: a lane's tables are its request's lists, a free
    lane's row is the scratch lane's (block 0, context 1, one row)."""
    c, held = eng._cols, {}
    for req in eng._active:
        if req.lane is not None:
            assert req.state == "RUNNING"
            held[req.lane] = req
    assert sorted(eng._free_lanes + list(held)) == list(range(eng.max_batch))
    scratch = np.zeros_like(eng._inputs[0])
    scratch[c.context_len:c.head] = 1
    for lane, row in enumerate(eng._inputs):
        req = held.get(lane)
        if req is None:
            np.testing.assert_array_equal(row, scratch)
            continue
        table = np.zeros((eng.max_nb,), np.int32)
        table[:len(req.block_table)] = req.block_table
        np.testing.assert_array_equal(row[c.table:], table)
        if eng.kv_window is not None:
            wt = np.zeros((eng._win_len,), np.int32)
            wt[:len(req.window_table)] = req.window_table
            np.testing.assert_array_equal(row[c.win_table:c.table], wt)
            assert row[c.win_first] == req.window_first


def check(eng, case):
    """Run the script on ``eng`` with both checks on; the run's result
    equals the one recorded on the parent commit under ``case``."""
    real, seen = eng._decode, []

    def decode(params, packed, *pools, q, firsts):
        assert packed is eng._inputs and q == eng._q_rows
        np.testing.assert_array_equal(
            packed, expected_inputs(eng, packed, np.asarray(firsts)))
        seen.append(int((packed[:, eng._cols.q_len] > 1).sum()))
        return real(params, packed, *pools, q=q, firsts=firsts)

    eng._decode = decode
    reqs = {}
    got = drive(eng, after_step=lambda: check_tables(eng), reqs=reqs)
    assert len(seen) > 20
    assert not eng._active and len(eng._free_lanes) == eng.max_batch
    with open(RECORDED) as f:
        want = json.load(f)[case]
    assert got == want
    # The script did what it is for.
    if eng._prefix:
        assert got["cached"]["partial"] == 16 and got["cached"]["full"] == 19
    else:       # a pool that indexes nothing: no request has a chain
        assert len(reqs) == 7
        assert all(r.chain is None for r in reqs.values())
    assert got["outputs"]["one"] and len(got["outputs"]["one"]) == 1
    assert sum(got["preemptions"].values()) >= 1
    if eng._q_rows > 1:
        assert max(seen) >= 1 and eng._spec.stats()["rolled_back"] > 0
    return got


def engines():
    """case -> a function that builds its engine (the parent's tree
    builds the same ones: nothing here is new in PR 46)."""
    import jax

    from ray_tpu.llm.engine import LLMEngine
    from ray_tpu.models import gpt, kimi_k2, laguna
    import test_kimi_k2
    import test_laguna

    gpt_cfg = gpt.GPTConfig(vocab_size=128, max_seq=64, d_model=64,
                            n_layer=2, n_head=4, dtype="float32")
    ngram = {"mode": "ngram", "k": 3}
    made = {}

    def params(name, init, cfg):
        if name not in made:
            made[name] = init(jax.random.key(0), cfg)
        return made[name]

    def build(name, init, cfg, blocks, **kw):
        return lambda: LLMEngine(
            params(name, init, cfg), cfg, num_blocks=blocks, block_size=BS,
            max_batch=3, prefill_chunk_tokens=16, **kw)

    g = ("gpt", gpt.init, gpt_cfg, 12)
    lag = ("laguna", laguna.init, test_laguna.TINY, 12)
    kim = ("kimi", kimi_k2.init, test_kimi_k2.TINY, 12)
    return {
        "gpt-q1": build(*g),
        "gpt-spec": build(*g, speculative=ngram),
        "gpt-q1-no-prefix-cache": build(*g, prefix_cache=False),
        "laguna-q1-window": build(*lag),
        "laguna-spec-window": build(*lag, speculative=ngram),
        "kimi-q1": build(*kim),
        "kimi-spec": build(*kim, speculative=ngram),
    }


if __name__ == "__main__":
    # Record the streams: run with the PARENT commit's tree first on
    # the path (PYTHONPATH=<parent> JAX_PLATFORMS=cpu python
    # tests/kept_array.py), from this tests/ directory.
    import sys

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    recorded = {case: drive(make()) for case, make in engines().items()}
    os.makedirs(os.path.dirname(RECORDED), exist_ok=True)
    with open(RECORDED, "w") as f:
        json.dump(recorded, f, indent=1, sort_keys=True)
    for case, r in recorded.items():
        print(case, r["steps"], r["preemptions"], r["cached"])
