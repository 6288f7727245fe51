"""Continuous-batching engine semantics (llm/engine.py), manually
stepped on CPU: batch recomposition mid-stream, preempt+resume
determinism, stop conditions, admission validation.

All cases drive step() directly (no background thread, no cluster) so
the scheduler's decisions are observable step by step via step_log and
the lifecycle event trace.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.llm import (  # noqa: E402
    FINISHED,
    PREEMPTED,
    PREFILL,
    RUNNING,
    WAITING,
    LLMEngine,
)
from ray_tpu.models.gpt import GPTConfig, init  # noqa: E402
from ray_tpu.models import pack_step  # noqa: E402

# f32 on CPU so decode logits are bit-reproducible across runs of the
# same process (the determinism assertions compare token ids, which
# sampling derives from (seed, position) + argmax/softmax over logits).
CFG = GPTConfig(vocab_size=128, max_seq=64, d_model=64, n_layer=2,
                n_head=4, dtype=jnp.float32)
PARAMS = init(jax.random.PRNGKey(0), CFG)


def _drain(eng, max_steps=200):
    for _ in range(max_steps):
        s = eng.stats()
        if not s["in_flight"] and not s["waiting"]:
            return
        eng.step()
    raise AssertionError("engine did not drain")


def _run_once(num_blocks, reqs, block_size=8, max_batch=4):
    eng = LLMEngine(PARAMS, CFG, num_blocks=num_blocks,
                    block_size=block_size, max_batch=max_batch)
    handles = [eng.add_request(**r) for r in reqs]
    _drain(eng)
    return eng, handles


REQS = [
    dict(prompt=[1, 2, 3, 4, 5], max_tokens=8, seed=11, temperature=0.7),
    dict(prompt=[9, 8, 7], max_tokens=12, seed=5, temperature=0.9),
    dict(prompt=[20, 21], max_tokens=6),   # greedy
]


def test_generation_completes_and_streams_all_tokens():
    _, hs = _run_once(64, REQS)
    for h, r in zip(hs, REQS):
        assert h.finish_reason == "length"
        assert len(h.output) == r["max_tokens"]
        # The stream delivers exactly the generated tokens, then closes.
        assert list(h.tokens()) == h.output
        assert h.emitted == len(h.output)


def test_batch_composition_changes_mid_stream():
    """A late request joins while an earlier one is mid-decode: the
    in-flight set must change between steps WITHOUT the first request
    leaving, and its output must be unaffected by the join."""
    _, hs = _run_once(64, REQS[:1])
    solo = list(hs[0].output)

    eng = LLMEngine(PARAMS, CFG, num_blocks=64, block_size=8, max_batch=4)
    a = eng.add_request(**REQS[0])
    eng.step()
    eng.step()                      # a is mid-decode
    assert len(a.output) >= 2 and a.finish_reason is None
    b = eng.add_request(**REQS[1])
    eng.step()                      # b admitted into the live batch
    _drain(eng)
    comps = [set(rids) for _, rids in eng.step_log]
    assert {a.rid} in comps, "a ran alone first"
    assert {a.rid, b.rid} in comps, "batch was recomposed mid-stream"
    assert a.output == solo
    assert b.finish_reason == "length" and len(b.output) == 12


def test_over_admission_preempts_and_resumes_identically():
    """Pool too small for the working set: the engine must preempt
    (never OOM) and resumed sequences must emit IDENTICAL tokens."""
    _, big = _run_once(64, REQS)
    ref = [list(h.output) for h in big]

    eng, small = _run_once(4, REQS)   # capacity 3 blocks = 24 tokens
    assert [list(h.output) for h in small] == ref
    assert sum(h.preemptions for h in small) > 0, \
        "expected at least one preemption"
    states = {s for _, _, s in eng.events()}
    assert states == {WAITING, PREFILL, RUNNING, PREEMPTED, FINISHED}
    # Preempted requests re-enter through PREFILL (recompute-on-resume).
    per_rid = {}
    for _, rid, s in eng.events():
        per_rid.setdefault(rid, []).append(s)
    for rid, trace in per_rid.items():
        for i, s in enumerate(trace):
            if s == PREEMPTED:
                assert trace[i + 1] == PREFILL, trace


def test_preemption_frees_and_reacquires_blocks():
    eng, hs = _run_once(4, REQS)
    assert eng.kv.num_free == eng.kv.capacity   # everything returned
    assert all(h.block_table == [] for h in hs)


def test_stop_token_ends_generation_early():
    eng = LLMEngine(PARAMS, CFG, num_blocks=32, block_size=8)
    # Greedy output is deterministic: find its 3rd token, then re-run
    # with that token as a stop token.
    probe = eng.add_request([1, 2, 3], max_tokens=8)
    _drain(eng)
    stop = probe.output[2]
    eng2 = LLMEngine(PARAMS, CFG, num_blocks=32, block_size=8)
    h = eng2.add_request([1, 2, 3], max_tokens=8, stop_tokens=[stop])
    _drain(eng2)
    assert h.finish_reason == "stop"
    # Generation halts at the stop token's FIRST occurrence (greedy
    # output may repeat, so that can be earlier than index 2).
    cut = probe.output.index(stop)
    assert h.output == probe.output[:cut + 1]


def test_add_request_validates_capacity_and_length():
    eng = LLMEngine(PARAMS, CFG, num_blocks=3, block_size=8)
    with pytest.raises(ValueError):
        eng.add_request([])
    with pytest.raises(ValueError):
        eng.add_request([1] * 60, max_tokens=8)     # > max_seq
    with pytest.raises(ValueError):
        # needs 3 blocks; capacity is 2 -> could never be admitted.
        eng.add_request([1] * 12, max_tokens=8)
    h = eng.add_request([1] * 8, max_tokens=8)       # exactly 2 blocks
    _drain(eng)
    assert h.finish_reason == "length"


def test_background_loop_and_stats():
    eng = LLMEngine(PARAMS, CFG, num_blocks=32, block_size=8)
    eng.start()
    try:
        h = eng.add_request([3, 1, 4, 1, 5], max_tokens=6, seed=2,
                            temperature=0.5)
        toks = list(h.tokens())          # blocks until FINISHED
        assert len(toks) == 6 and toks == h.output
        s = eng.stats()
        assert s["finished"] == 1 and s["in_flight"] == 0
        assert 0.0 <= s["kv_utilization"] <= 1.0
    finally:
        eng.stop()


def test_greedy_generation_is_reproducible():
    _, h1 = _run_once(64, REQS[2:])
    _, h2 = _run_once(64, REQS[2:])
    assert h1[0].output == h2[0].output


# ---------------------------------------------------------------------------
# Prefix cache + chunked prefill (llm/kv_cache.py PrefixPool wiring)
# ---------------------------------------------------------------------------
PREFIX = [7] * 20 + [1, 2, 3]


def test_prefix_cache_hit_is_token_identical_to_cold():
    """A cache-hit request (roomy pool, warm prefix chain) must emit
    EXACTLY the tokens a cold-cache run emits — the full-hit path holds
    back the last position and recomputes its logits in decode, so the
    sampled stream cannot drift."""
    cold = LLMEngine(PARAMS, CFG, num_blocks=64, block_size=8,
                     prefix_cache=False)
    c = cold.add_request(list(PREFIX), max_tokens=6, seed=3,
                         temperature=0.8)
    _drain(cold)

    eng = LLMEngine(PARAMS, CFG, num_blocks=64, block_size=8)
    a = eng.add_request(list(PREFIX), max_tokens=6, seed=3,
                        temperature=0.8)
    _drain(eng)
    b = eng.add_request(list(PREFIX), max_tokens=6, seed=3,
                        temperature=0.8)
    _drain(eng)
    assert a.output == c.output            # cold fill through PrefixPool
    assert b.output == c.output            # full hit, zero prefill
    assert a.cached_tokens == 0
    assert b.cached_tokens == len(PREFIX)
    s = eng.stats()
    assert s["kv_cache_hit_rate"] >= 0.5
    assert s["prefix"]["cow_splits"] >= 1  # full-hit decode COWs the tail
    assert eng.kv.num_free == eng.kv.capacity


def test_divergent_tail_partial_hit_matches_cold_output():
    tail_req = dict(prompt=PREFIX[:16] + [40, 41, 42], max_tokens=6,
                    seed=9, temperature=0.7)
    cold = LLMEngine(PARAMS, CFG, num_blocks=64, block_size=8,
                     prefix_cache=False)
    c = cold.add_request(**tail_req)
    _drain(cold)

    eng = LLMEngine(PARAMS, CFG, num_blocks=64, block_size=8)
    eng.add_request(list(PREFIX), max_tokens=4)
    _drain(eng)
    h = eng.add_request(**tail_req)        # shares the 16-token prefix
    _drain(eng)
    assert h.cached_tokens == 16
    assert h.output == c.output


def test_chunked_prefill_interleaves_decode_every_step():
    """With prefill_chunk_tokens set, a long prompt admits in chunks and
    a live decode stream keeps emitting one token EVERY step while the
    newcomer prefills — and the chunked output matches whole-prefill."""
    long_prompt = list(range(1, 41))
    ref = LLMEngine(PARAMS, CFG, num_blocks=64, block_size=8,
                    prefix_cache=False)
    r = ref.add_request(list(long_prompt), max_tokens=6)
    _drain(ref)

    eng = LLMEngine(PARAMS, CFG, num_blocks=64, block_size=8,
                    prefill_chunk_tokens=8, prefix_cache=False)
    s = eng.add_request([5, 6, 7], max_tokens=16, seed=1, temperature=0.6)
    eng.step()                             # s prefilled, now decoding
    h = eng.add_request(list(long_prompt), max_tokens=6)
    deltas = []
    for _ in range(100):
        if h.finish_reason and s.finish_reason:
            break
        before = len(s.output)
        eng.step()
        if s.finish_reason is None or len(s.output) != before:
            deltas.append(len(s.output) - before)
    # 40 tokens / 8-token chunks = 5 prefill steps; s streamed through
    # every one of them instead of stalling behind the prefill.
    assert eng.stats()["prefill_chunks"] >= 5
    assert all(d == 1 for d in deltas[:5])
    assert h.finish_reason == "length"
    assert h.output == r.output


def test_kv_util_peak_samples_high_water_inside_step():
    eng, hs = _run_once(64, REQS)
    s = eng.stats()
    assert s["kv_utilization"] == 0.0      # everything released/parked
    assert 0.0 < s["kv_util_peak"] <= 1.0  # but the peak was observed


# ---------------------------------------------------------------------------
# Device-step performance plane (util/perfmodel.py accounting)
# ---------------------------------------------------------------------------
def test_step_breakdown_in_stats_spans_and_ring():
    """Every working step prices its device spans through the shared
    cost model: stats()["last_step"] carries the host-vs-device split +
    roofline, each traced request's llm.decode_step span carries the
    per-step breakdown, and the step lands in the process-local
    device-step ring the gang profiler drains."""
    from ray_tpu.util import perfmodel, tracing

    perfmodel.clear_device_steps()
    tracing.drain_request_spans()
    t0 = __import__("time").time()
    eng = LLMEngine(PARAMS, CFG, num_blocks=64, block_size=8)
    ctx = {"trace_id": tracing.new_trace_id(),
           "span_id": tracing.new_span_id()}
    h = eng.add_request([1, 2, 3, 4], max_tokens=4, trace_ctx=ctx)
    _drain(eng)
    assert h.finish_reason == "length"

    stats = eng.stats()
    # The engine names where its pools live and how the kernel ran.
    assert stats["platform"] == "cpu" and stats["device_kind"]
    assert stats["paged_kernel"] == "interpret"
    # GPT-2's chunk keeps its XLA-made attention: no chunk_attn kernel.
    assert stats["chunk_attention"] == "xla"
    last = stats["last_step"]
    for key in ("step_ms", "device_ms", "host_gap_ms", "tokens", "flops",
                "hbm_bytes"):
        assert key in last, key
    assert last["step_ms"] >= last["device_ms"] > 0.0
    assert last["host_gap_ms"] == pytest.approx(
        last["step_ms"] - last["device_ms"], abs=1e-6)
    assert last["flops"] > 0 and last["tokens"] > 0
    # The CPU backend has no peak: counts and times, no MFU, no verdict.
    assert not {"mfu", "hbm_util", "verdict", "hardware"} & set(last)

    steps = [s for s in tracing.drain_request_spans()
             if s["name"] == "llm.decode_step"]
    # One per decode step; the prefill itself samples token 1, so a
    # 4-token generation decodes 3 times.
    assert len(steps) >= 3
    attrs = steps[0]["attributes"]
    for key in ("device_ms", "host_ms", "rid", "decode", "kv_util"):
        assert key in attrs, key
    assert "mfu" not in attrs and "verdict" not in attrs
    assert attrs["rid"] == h.rid

    ring = [e for e in perfmodel.device_step_events(since=t0)
            if e["name"] == "llm.step"]
    assert ring, "accounted steps must land in the device-step ring"
    assert all(e["device_ms"] > 0 for e in ring)
    perfmodel.clear_device_steps()


def test_step_counts_the_pages_its_kernel_fetches_in_runs():
    """The decode program appends ``kv_pages_in_runs_x1000`` to its ids
    (the seam's ``counters``), so every ``llm.step`` ring entry that
    decoded, and ``stats()``, say what share of the
    batch's live cache pages the paged kernel fetched in whole runs of
    adjacent blocks: all of them for a lane whose eight blocks a fresh
    pool granted in order, none for a lane one block long. (One lane: a
    padded lane's scratch page counts as a live page, fetched alone.)"""
    from ray_tpu.models import serving
    from ray_tpu.util import perfmodel

    assert serving(CFG).counters == ("kv_pages_in_runs_x1000",)
    for prompt, share in ((list(range(1, 59)), 1.0), ([5, 6, 7], 0.0)):
        perfmodel.clear_device_steps()
        eng = LLMEngine(PARAMS, CFG, num_blocks=64, block_size=8,
                        max_batch=1)
        eng.add_request(prompt, max_tokens=4)
        _drain(eng)
        decoded = [e for e in perfmodel.device_step_events()
                   if e["name"] == "llm.step" and e["decode_tokens"] > 0]
        assert len(decoded) == 3
        assert [e["kv_pages_in_runs"] for e in decoded] == [share] * 3
        assert eng.stats()["kv_pages_in_runs"] == share
    perfmodel.clear_device_steps()


def test_idle_engine_decays_perf_gauges_to_zero():
    """Acceptance: a drained engine must publish zeroed gauges from its
    background loop's idle ticks — the MFU/step series decay instead of
    freezing at the last busy value."""
    import time

    from ray_tpu.util.metrics import _registry

    eng = LLMEngine(PARAMS, CFG, num_blocks=32, block_size=8,
                    name="decay_test")
    eng.start()
    try:
        h = eng.add_request([3, 1, 4], max_tokens=4)
        assert len(list(h.tokens())) == 4

        def perf_rows():
            return {r["name"]: r["value"]
                    for r in _registry.snapshot()["rows"]
                    if r.get("tags", {}).get("deployment") == "decay_test"
                    and r["name"].startswith("rtpu_llm_")}

        deadline = time.monotonic() + 10
        rows = {}
        while time.monotonic() < deadline:
            # The shared GaugeIdleDecay helper holds the last busy
            # values for decay_s before zeroing; age its clock instead
            # of sleeping through the window (any still-busy publish
            # re-touches it, so rewind per poll).
            eng._idle_decay.rewind("gauges", eng._idle_decay.decay_s + 1)
            rows = perf_rows()
            if rows and all(v == 0.0 for v in rows.values()):
                break
            time.sleep(0.05)
        assert rows, "engine never published its gauges"
        for name in ("rtpu_llm_step_ms", "rtpu_llm_device_ms",
                     "rtpu_llm_host_gap_ms", "rtpu_llm_tokens_per_s"):
            assert rows.get(name) == 0.0, (name, rows)
        # No peak on the CPU backend: utilization is never published.
        assert "rtpu_llm_mfu" not in rows
        assert "rtpu_llm_hbm_util" not in rows
    finally:
        eng.stop()


def test_step_loop_death_fails_requests_instead_of_hanging(monkeypatch):
    """A step that raises (on the chip: a kernel that does not lower, a
    device out of memory) must end every stream with finish_reason
    "error" and refuse new requests — never leave consumers parked on a
    queue behind a dead loop."""
    import threading

    eng = LLMEngine(PARAMS, CFG, num_blocks=32, block_size=8,
                    name="death_test")

    def boom():
        raise RuntimeError("RESOURCE_EXHAUSTED: out of HBM")

    monkeypatch.setattr(eng, "_run_decode", boom)
    seen = []
    monkeypatch.setattr(threading, "excepthook",
                        lambda args: seen.append(args.exc_value))
    eng.start()
    h = eng.add_request([3, 1, 4], max_tokens=8)
    toks = list(h.tokens())             # returns: the stream was closed
    eng._thread.join(timeout=10)
    assert h.finish_reason == "error" and len(toks) < 8
    assert seen and "RESOURCE_EXHAUSTED" in str(seen[0])    # it propagated
    with pytest.raises(RuntimeError, match="step loop died"):
        eng.add_request([1, 2], max_tokens=2)
    eng.stop()


# ---------------------------------------------------------------------------
# Tokens decided on the device: greedy lanes take the program's own argmax
# ---------------------------------------------------------------------------
def _bf16(values):
    return jnp.asarray(np.asarray(values, np.float32), jnp.bfloat16)


def _tie_rows():
    """bf16 logits rows whose argmax is a matter of tie-breaking or lies
    at the vocabulary's padded end, by case."""
    V = 640
    base = np.random.default_rng(0).standard_normal(V).astype(np.float32)
    top = float(np.abs(base).max()) + 1.0

    def row(*at):
        r = base.copy()
        r[list(at)] = top
        return r

    # A tie only after rounding: top * (1 + 2**-10) is top in bf16 (8
    # significant bits), and the larger float32 comes second.
    rounded = row(9)
    rounded[400] = top * (1 + 2.0 ** -10)
    return {
        "tie_at_the_maximum": row(17, 300, 301),
        "maximum_in_the_padded_tail": row(V - 1),
        "tie_inside_the_padded_tail": row(V - 3, V - 1),
        "tie_of_head_and_tail": row(5, V - 1),
        "tie_made_by_bf16_rounding": rounded,
        "all_equal": np.zeros(V, np.float32),
        "all_minus_infinity": np.full(V, -np.inf, np.float32),
    }


@pytest.mark.parametrize("case", sorted(_tie_rows()))
def test_device_argmax_breaks_ties_as_the_host_sampler_does(case):
    """The id the decode and verify programs return for a row is the
    token sampling.sample(row, temperature=0) returns for it: both take
    the FIRST index of the maximum, and bf16 -> float32 is exact."""
    from ray_tpu.llm.sampling import sample
    from ray_tpu.models.gpt import _greedy_ids

    row = _bf16(_tie_rows()[case])
    want = sample(np.asarray(row), temperature=0.0)
    assert want == sample(np.asarray(row), temperature=0.7, top_k=1)
    ids = jax.jit(_greedy_ids)(jnp.stack([row, row[::-1], row]))
    assert ids.dtype == jnp.int32 and ids.shape == (3,)
    assert [int(ids[0]), int(ids[2])] == [want, want]
    assert int(ids[1]) == sample(np.asarray(row)[::-1], temperature=0.0)
    if case.startswith("tie") or case.startswith("all"):
        # The construction did make a tie at the maximum.
        r = np.asarray(row, np.float32)
        assert (r == r.max()).sum() > 1


BF16 = GPTConfig(vocab_size=128, max_seq=64, d_model=64, n_layer=2,
                 n_head=4, dtype=jnp.bfloat16)


def _tied_params(kind):
    """bf16-served weights whose vocabulary rows repeat, so every logits
    row of the program ties at its maximum ("ties": the upper half of
    the vocabulary copies the lower), or holds its maximum, tied, in the
    last rows ("tail": the rest scaled down)."""
    params = init(jax.random.PRNGKey(3), BF16)
    wte = np.array(params["wte"], np.float32)
    if kind == "ties":
        wte[64:] = wte[:64]
    else:
        wte[:-8] *= 0.01
        wte[-8] = -wte[-7]      # one of the two scores above zero
        wte[-4:] = wte[-8:-4]
    return dict(params, wte=jnp.asarray(wte, params["wte"].dtype))


@pytest.mark.parametrize("kind", ["ties", "tail"])
@pytest.mark.parametrize("Q", [1, 3], ids=["decode", "verify"])
def test_program_ids_equal_host_greedy_sample_of_its_logits(Q, kind):
    """forward_step through the engine's own jitted program, at one row
    a lane and at three: the ids output equals sample(row,
    temperature=0) on every row of the logits output, where rows tie at
    the maximum and where the maximum lies at the vocabulary's end."""
    from ray_tpu.llm.engine import _jit_programs
    from ray_tpu.llm.sampling import sample

    params = _tied_params(kind)
    decode = _jit_programs(BF16)[0]
    B, bs, nb = 4, 8, 16
    max_nb = BF16.max_seq // bs
    rng = np.random.default_rng(1)
    pool = jnp.asarray(rng.standard_normal(
        (BF16.n_layer, nb, bs, BF16.kv_heads * BF16.head_dim)), BF16.dtype)
    tables = np.zeros((B, max_nb), np.int32)
    tables[:, 0] = 1 + np.arange(B)
    slot = (1 + np.arange(B, dtype=np.int32))[:, None] + np.arange(
        Q, dtype=np.int32)
    logits, ids, _, _ = decode(
        params, pack_step(
            rng.integers(0, 60, (B, Q), dtype=np.int32), slot, tables,
            slot[:, -1] + 1, np.full((B,), Q, np.int32),
            np.broadcast_to(tables[:, :1], (B, Q)), slot),
        pool, pool + 0, q=Q)
    # (the row behind the lanes' is the program's counter)
    assert ids.dtype == jnp.int32 and ids.shape == (B + 1, Q)
    rows = np.asarray(logits, np.float32).reshape(-1, BF16.vocab_size)
    got = np.asarray(ids)[:B].reshape(-1)
    assert [sample(r, temperature=0.0) for r in rows] == got.tolist()
    if kind == "ties":
        assert all((r == r.max()).sum() >= 2 for r in rows)
        assert (got < 64).all()       # the first of the tied pair
    else:
        assert (got >= BF16.vocab_size - 8).all()
        assert (got < BF16.vocab_size - 4).all()


def test_all_greedy_decode_step_fetches_ids_not_logits(watch_device_get):
    """Every lane greedy: a decode step brings max_batch ints (and the
    program's one counter behind them) to the host and nothing else;
    the logits stay on the device."""
    eng = LLMEngine(PARAMS, CFG, num_blocks=64, block_size=8, max_batch=4)
    hs = [eng.add_request([1 + i, 2, 3], max_tokens=6) for i in range(3)]
    eng.step()                      # prefills fetch their last row each
    fetched = watch_device_get()
    eng.step()
    eng.step()
    assert fetched == [eng.max_batch + 1] * 2, fetched
    assert max(fetched) < CFG.vocab_size
    _drain(eng)
    assert all(h.finish_reason == "length" for h in hs)


SAMPLED = dict(prompt=[7, 3, 9, 4], max_tokens=10, temperature=0.8,
               top_k=40, seed=1234)
GREEDY = [dict(prompt=[20, 21, 22], max_tokens=9),
          dict(prompt=[5, 6], max_tokens=12, temperature=0.7, top_k=1)]


def _dense_reference(req):
    """The request's tokens by plain teacher forcing: gpt.forward over
    prompt + output so far, sampling.sample on the last row with the
    request's own (seed, position) key. No engine, no pool, no batch."""
    from ray_tpu.llm.sampling import sample
    from ray_tpu.models.gpt import forward

    seq = list(req["prompt"])
    for _ in range(req["max_tokens"]):
        logits = forward(PARAMS, jnp.asarray([seq], jnp.int32), CFG)
        seq.append(sample(
            np.asarray(logits[0, -1]), seed=req.get("seed", 0),
            temperature=req.get("temperature", 0.0),
            top_k=req.get("top_k", 0), position=len(seq)))
    return seq[len(req["prompt"]):]


def test_sampled_lane_beside_greedy_lanes_keeps_its_host_draws(
        monkeypatch):
    """A lane with a temperature in a batch of greedy lanes: its tokens
    are sampling.sample's draws on its own logits rows under its own
    (seed, position) keys, the same after a forced preemption; the
    sampler is never asked about a greedy lane in a decode step; and the
    greedy lanes' tokens do not depend on the sampled lane's presence."""
    import ray_tpu.llm.engine as engine_mod
    import ray_tpu.llm.sampling as sampling_mod

    calls = []
    real = sampling_mod.sample

    def spy(row, **kw):
        tok = real(row, **kw)
        calls.append((kw["seed"], kw["position"], kw["temperature"],
                      kw["top_k"], tok))
        return tok

    # The engine calls it for a prefill's first token; a decode step's
    # sampled lane reaches it through sampling.verify_tokens.
    monkeypatch.setattr(engine_mod, "sample", spy)
    monkeypatch.setattr(sampling_mod, "sample", spy)
    _, mixed = _run_once(64, [SAMPLED] + GREEDY)
    lane, others = mixed[0], mixed[1:]
    n0 = len(SAMPLED["prompt"])
    mine = [c for c in calls if c[2] == 0.8]
    # One host draw a token, keyed by the request's seed and position.
    assert [c[:4] for c in mine] == [
        (1234, n0 + j, 0.8, 40) for j in range(SAMPLED["max_tokens"])]
    assert [c[4] for c in mine] == lane.output
    # Greedy lanes never reach the sampler: their first token is the
    # chunk program's id, their decode tokens the step program's.
    assert len(calls) == len(mine)
    assert lane.output == _dense_reference(SAMPLED)
    for h, r in zip(others, GREEDY):
        assert h.output == _dense_reference(r)

    _, alone = _run_once(64, GREEDY)
    assert [h.output for h in alone] == [h.output for h in others]

    # capacity 3 blocks = 24 tokens for 18 + 12 + 14: someone is evicted
    eng, tight = _run_once(4, [SAMPLED] + GREEDY)
    assert sum(h.preemptions for h in tight) > 0
    assert [h.output for h in tight] == [h.output for h in mixed]


@pytest.mark.parametrize("reqs, host_lanes", [
    (GREEDY, 0), ([SAMPLED] + GREEDY, 1)], ids=["all_greedy", "mixed"])
def test_device_sampled_count_in_the_ring_and_stats(reqs, host_lanes):
    """Each llm.step ring entry says how many lanes took their token
    from the device; stats() totals tokens by where they were decided,
    a greedy request's first token (the chunk program's id) on the
    device's side like its decode tokens."""
    from ray_tpu.util import perfmodel

    perfmodel.clear_device_steps()
    eng = LLMEngine(PARAMS, CFG, num_blocks=64, block_size=8,
                    max_batch=4, name="decided")
    hs = [eng.add_request(**r) for r in reqs]
    eng.step()
    ring = perfmodel.device_step_events()
    assert ring[-1]["lanes"] == len(reqs)
    assert ring[-1][perfmodel.DEVICE_SAMPLED] == len(reqs) - host_lanes
    _drain(eng)
    ring = [e for e in perfmodel.device_step_events()
            if e.get("deployment") == "decided"]
    for e in ring:
        live_host = sum(1 for h in hs[:host_lanes]
                        if e["step"] < h.max_tokens)
        assert e[perfmodel.DEVICE_SAMPLED] == e["lanes"] - live_host
    s = eng.stats()
    on_device = sum(e[perfmodel.DEVICE_SAMPLED] for e in ring)
    assert s["tokens_decided_on_device"] == \
        on_device + len(reqs) - host_lanes
    assert s["tokens_decided_on_device"] + s["tokens_decided_on_host"] \
        == sum(len(h.output) for h in hs)
    # The host's share: every token of a lane with a temperature.
    assert s["tokens_decided_on_host"] == sum(
        h.max_tokens for h in hs[:host_lanes])
    perfmodel.clear_device_steps()


# ---------------------------------------------------------------------------
# One step function, one chunk function (models/gpt.py)
# ---------------------------------------------------------------------------
def test_step_at_one_row_is_row_zero_of_a_wider_step_with_padding_rows():
    """forward_step at q = 1 against the same lanes at q = 3 with
    q_lens = 1: rows 1 and 2 are padding (scratch block 0, masked by
    q_lens), so row 0's logits and ids and the pool outside the scratch
    block are what the one-row step gives. Plain decoding is the
    speculative step's shape at one row, not a second program."""
    from ray_tpu.llm.engine import _jit_programs

    step = _jit_programs(CFG)[0]
    B, bs, nb = 4, 8, 16
    max_nb = CFG.max_seq // bs
    rng = np.random.default_rng(7)
    pool = jnp.asarray(rng.standard_normal(
        (CFG.n_layer, nb, bs, CFG.kv_heads * CFG.head_dim)), CFG.dtype)
    tables = np.zeros((B, max_nb), np.int32)
    tables[:, 0] = 1 + np.arange(B)
    tok = rng.integers(0, CFG.vocab_size, (B, 1), dtype=np.int32)
    slot = (2 + np.arange(B, dtype=np.int32))[:, None]
    ones = np.ones((B,), np.int32)

    def run(q):
        pad = np.zeros((B, q - 1), np.int32)
        return step(PARAMS, pack_step(
            np.hstack([tok, pad]),
            np.hstack([slot, slot + 1 + np.arange(q - 1)]),
            tables, slot[:, 0] + 1, ones,
            np.hstack([tables[:, :1], pad]),    # padding: block 0
            np.hstack([slot, pad])), pool + 0, pool + 0, q=q)

    l1, i1, k1, v1 = run(1)
    l3, i3, k3, v3 = run(3)
    # (the lanes' rows, and the program's counter behind them: the
    # same share of pages in runs whatever the rows a lane)
    assert l1.shape == (B, 1, CFG.vocab_size) and i3.shape == (B + 1, 3)
    np.testing.assert_allclose(np.asarray(l3[:, 0]), np.asarray(l1[:, 0]),
                               atol=2e-5, rtol=0)
    assert np.asarray(i3[:, 0]).tolist() == np.asarray(i1[:, 0]).tolist()
    for wide, one in ((k3, k1), (v3, v1)):
        np.testing.assert_allclose(np.asarray(wide[:, 1:]),
                                   np.asarray(one[:, 1:]), atol=1e-6,
                                   rtol=0)
    # and the one-row step did write each lane's token where it was told
    assert not np.allclose(np.asarray(k1[:, 1:]),
                           np.asarray(pool[:, 1:]))


def _layers_and_head(cfg):
    """A span's layers and the head on every row, without the write:
    ``(params, tokens, positions, k_pool, v_pool, table, ctx_len) ->
    (logits [1, n, vocab], k, v [L, 1, n, kv_heads, head_dim])``, what
    the chunk program was before it wrote its own span (PR 33), from
    the model's own parts."""
    from ray_tpu.models import gpt

    def fn(params, tokens, positions, k_pool, v_pool, table, ctx_len):
        x, k, v = gpt._chunk_layers(params, tokens, positions, k_pool,
                                    v_pool, table, ctx_len, cfg)
        return gpt._head(params, x, cfg), k, v

    return jax.jit(fn)


def test_chunk_with_an_empty_table_is_the_plain_causal_forward():
    """A span's layers with no block table and ctx_len 0 attend over
    the span alone: the logits are gpt.forward's on the same tokens,
    and logits and K/V are those of the same span under a full-length
    table at ctx_len 0, whose pool slots are all masked."""
    from ray_tpu.models.gpt import forward

    chunk = _layers_and_head(CFG)
    bs, nb, T = 8, 16, 24
    rng = np.random.default_rng(3)
    pool = jnp.asarray(rng.standard_normal(
        (CFG.n_layer, nb, bs, CFG.kv_heads * CFG.head_dim)), CFG.dtype)
    toks = rng.integers(0, CFG.vocab_size, (1, T), dtype=np.int32)
    pos = np.arange(T, dtype=np.int32)
    logits, k, v = chunk(PARAMS, toks, pos, pool, pool,
                         np.zeros((0,), np.int32), np.int32(0))
    assert k.shape == (CFG.n_layer, 1, T, CFG.kv_heads, CFG.head_dim)
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(forward(PARAMS, toks, CFG)),
        atol=2e-5, rtol=0)
    full = np.zeros((CFG.max_seq // bs,), np.int32)
    full[:3] = [4, 5, 6]
    for got, want in zip((logits, k, v),
                         chunk(PARAMS, toks, pos, pool, pool, full,
                               np.int32(0))):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=0)


# The parent's pool layout, kept here as the reference the stored
# (token-major) pool is held to: [L, kv_heads, num_blocks, block_size,
# head_dim], written and read as models/gpt.py did before PR 31.
def _heads_first_pool(pool, cfg):
    L, nb, bs, _ = pool.shape
    return pool.reshape(L, nb, bs, cfg.kv_heads, cfg.head_dim).transpose(
        0, 3, 1, 2, 4)


def _heads_first_layers(params, x, k_pool, v_pool, attend, cfg):
    """The parent's layer scan: a layer's head-major pools come in as
    the scan's xs and whatever ``attend(h, p, kp, vp)`` carries goes out
    stacked."""
    import functools

    from ray_tpu.models import gpt

    def layer(x, xs):
        p, kp, vp = xs
        return gpt._block(x, p, cfg,
                          functools.partial(attend, kp=kp, vp=vp))

    x, carried = jax.lax.scan(layer, x, (params["blocks"], k_pool, v_pool))
    return gpt._head(params, x, cfg), carried


def _heads_first_step(params, tokens, positions, k_pool, v_pool, tables,
                     context_lens, q_lens, slot_blocks, slot_offsets, cfg):
    from ray_tpu.models import gpt
    from ray_tpu.ops.pallas.paged_fetch import (
        paged_attention_stored_reference)

    def stored(pool):
        """One layer's head-major pool as a stack of one stored layer."""
        hkv, nb, bs, d = pool.shape
        return pool.transpose(1, 2, 0, 3).reshape(1, nb, bs, hkv * d)

    def attend(h, p, kp, vp):
        B, Q = h.shape[:2]
        q, k_tok, v_tok = gpt._qkv(h, p, cfg.dtype)
        kp = kp.at[:, slot_blocks, slot_offsets].set(
            k_tok.astype(kp.dtype).transpose(2, 0, 1, 3))
        vp = vp.at[:, slot_blocks, slot_offsets].set(
            v_tok.astype(vp.dtype).transpose(2, 0, 1, 3))
        # Plain jnp, not the kernel under test: an independent witness.
        o = paged_attention_stored_reference(
            q.reshape(B, Q, cfg.kv_heads, cfg.n_head // cfg.kv_heads,
                      cfg.head_dim), stored(kp), stored(vp), 0, tables,
            context_lens, q_lens, jnp.full_like(context_lens, -Q))
        o = jnp.einsum("bqhd,hdm->bqm",
                       o.reshape(B, Q, cfg.n_head, cfg.head_dim),
                       p["wo"].astype(cfg.dtype))
        return o, (kp, vp)

    logits, (k_pool, v_pool) = _heads_first_layers(
        params, gpt._embed(params, tokens, positions, cfg), k_pool, v_pool,
        attend, cfg)
    return logits, gpt._greedy_ids(logits), k_pool, v_pool


def _heads_first_chunk(params, tokens, positions, k_pool, v_pool, table,
                      ctx_len, cfg):
    from ray_tpu.models import gpt

    def attend(h, p, kp, vp):
        q, k_tok, v_tok = gpt._qkv(h, p, cfg.dtype)
        k_ctx, v_ctx = kp[:, table], vp[:, table]
        nb, bs = k_ctx.shape[1:3]
        k_ctx = k_ctx.transpose(1, 2, 0, 3).reshape(
            1, nb * bs, cfg.kv_heads, cfg.head_dim)
        v_ctx = v_ctx.transpose(1, 2, 0, 3).reshape(
            1, nb * bs, cfg.kv_heads, cfg.head_dim)
        o = gpt._chunk_attention(q, k_tok, v_tok, k_ctx, v_ctx, ctx_len)
        return (jnp.einsum("bshd,hdm->bsm", o, p["wo"].astype(cfg.dtype)),
                (k_tok, v_tok))

    logits, (k, v) = _heads_first_layers(
        params, gpt._embed(params, tokens, positions, cfg), k_pool, v_pool,
        attend, cfg)
    return logits, k, v


@pytest.mark.parametrize("cfg", [CFG, BF16], ids=["f32", "bf16"])
@pytest.mark.parametrize("case", ["decode", "verify_3_rows", "chunk_context",
                                  "chunk_empty_table"])
def test_token_major_pool_gives_the_heads_first_pools_bits(case, cfg):
    """The stored pool [L, num_blocks, block_size, kv_heads * head_dim]
    against the parent's [L, kv_heads, num_blocks, block_size,
    head_dim], re-derived from it by reshape and transpose: a
    chunk with and without context gives the same logits bit for bit;
    a decode step and a three-row verify step, whose witness attends
    with the plain-jnp ``paged_attention_stored_reference`` over the
    head-major pool re-stored (not with the kernel the program runs),
    give the same logits, ids and pools to rounding; and gather_tokens
    reads back what the head-major pool holds under the same table."""
    import functools

    from ray_tpu.llm.engine import _jit_programs
    from ray_tpu.llm.kv_cache import PagedKVCache

    params = PARAMS if cfg is CFG else init(jax.random.PRNGKey(2), cfg)
    step, chunk = _jit_programs(cfg)[0], _layers_and_head(cfg)
    B, bs, nb = 4, 8, 16
    max_nb = cfg.max_seq // bs
    rng = np.random.default_rng(11)
    draw = lambda: jnp.asarray(rng.standard_normal(
        (cfg.n_layer, nb, bs, cfg.kv_heads * cfg.head_dim)), cfg.dtype)
    k_pool, v_pool = draw(), draw()
    k_old, v_old = (_heads_first_pool(k_pool, cfg),
                    _heads_first_pool(v_pool, cfg))
    equal = lambda a, b: np.array_equal(np.asarray(a, np.float32),
                                        np.asarray(b, np.float32))
    if case.startswith("chunk"):
        T, ctx = 16, 0 if case == "chunk_empty_table" else 20
        table = np.zeros((max_nb if ctx else 0,), np.int32)
        table[:3] = [4, 9, 2][:table.size]
        args = (rng.integers(0, cfg.vocab_size, (1, T), dtype=np.int32),
                ctx + np.arange(T, dtype=np.int32))
        got = chunk(params, *args, k_pool, v_pool, table, np.int32(ctx))
        want = jax.jit(functools.partial(_heads_first_chunk, cfg=cfg))(
            params, *args, k_old, v_old, table, np.int32(ctx))
        assert all(equal(g, w) for g, w in zip(got, want))
        return
    Q = 1 if case == "decode" else 3
    tables = np.zeros((B, max_nb), np.int32)
    tables[:, :2] = 1 + np.arange(2 * B).reshape(B, 2)
    first = np.array([3, 6, 7, 10], np.int32)[:, None]   # rows' positions
    pos = first + np.arange(Q, dtype=np.int32)           # cross a block
    args = (rng.integers(0, cfg.vocab_size, (B, Q), dtype=np.int32), pos)
    rest = (tables, pos[:, -1] + 1, np.full((B,), Q, np.int32),
            np.take_along_axis(tables, pos // bs, axis=1), pos % bs)
    logits, ids, k_new, v_new = step(params, pack_step(*args, *rest),
                                     k_pool + 0, v_pool + 0, q=Q)
    l_old, i_old, k_want, v_want = jax.jit(
        functools.partial(_heads_first_step, cfg=cfg))(
            params, *args, k_old, v_old, *rest)
    # The witness attends in plain jnp (float32 softmax over a dense
    # gather), the program through the kernel: equal to rounding.
    atol = 2e-6 if cfg is CFG else 0.02
    near = lambda a, b: np.allclose(np.asarray(a, np.float32),
                                    np.asarray(b, np.float32),
                                    atol=atol, rtol=0)
    assert near(logits, l_old)
    chosen = np.take_along_axis(
        np.asarray(l_old, np.float32),
        np.asarray(ids)[:B].reshape(B, Q, 1), axis=-1)[..., 0]
    assert near(chosen, np.asarray(l_old, np.float32).max(-1))
    assert near(_heads_first_pool(k_new, cfg), k_want)
    assert near(_heads_first_pool(v_new, cfg), v_want)
    assert not equal(k_new, k_pool)               # the rows were written
    kv = PagedKVCache(cfg, num_blocks=nb, block_size=bs)
    kv.k, kv.v = k_new, v_new
    n = int(pos[1, -1]) + 1
    k_back, v_back = kv.gather_tokens([int(b) for b in tables[1, :2]], n)
    for back, old in ((k_back, _heads_first_pool(k_new, cfg)),
                      (v_back, _heads_first_pool(v_new, cfg))):
        rows = old[:, :, tables[1, :2]].transpose(0, 2, 3, 1, 4).reshape(
            cfg.n_layer, 2 * bs, cfg.kv_heads, cfg.head_dim)[:, :n]
        # Rows come back as the pool holds them, heads side by side.
        assert equal(back.reshape(rows.shape), rows)


def test_cold_whole_prompt_prefills_through_the_chunk_program_tableless():
    """prefill_chunk_tokens=None, nothing cached: the whole prompt is
    one span from position 0, dispatched to the chunk program with an
    EMPTY table (there is no other prefill program), and the stream is
    greedy decoding over gpt.forward."""
    req = dict(prompt=[int(t) for t in
                       np.random.default_rng(5).integers(1, 120, 19)],
               max_tokens=7)
    eng = LLMEngine(PARAMS, CFG, num_blocks=64, block_size=8, max_batch=4,
                    prefill_chunk_tokens=None)
    seen = []
    real = eng._prefill_chunk

    def spy(params, toks, k, v, table):
        # table: [block table | 3 or 1 destination blocks | upto | last]
        seen.append((toks.shape, (table.size - toks.size // 8 - 2,),
                     int(table[-2])))
        return real(params, toks, k, v, table)

    eng._prefill_chunk = spy
    h = eng.add_request(**req)
    _drain(eng)
    assert seen == [((1, 24), (0,), 0)]      # 19 tokens padded to 3 blocks
    assert h.output == _dense_reference(req)
    # A second, longer prompt behind the cached first block resumes at
    # the block boundary with the request's table, padded to max_nb.
    seen.clear()
    more = dict(prompt=req["prompt"][:8] + [3, 1, 4, 1, 5], max_tokens=3)
    h2 = eng.add_request(**more)
    _drain(eng)
    assert seen == [((1, 8), (CFG.max_seq // 8,), 8)]
    assert h2.cached_tokens == 8 and h2.output == _dense_reference(more)


def test_a_decode_step_returns_the_pools_as_it_got_them_uncommitted():
    """The decode program's one array is the host's (the engine keeps
    it, a numpy array), so nothing it is handed is committed. A
    committed input would commit the pools the step returns, and every
    program that takes the pools (each chunk length, the pool writes)
    would then compile a second time: 15-22 more compilations in the
    chat cell's set-up when it was (PERF.md section 6, PR 30)."""
    eng = LLMEngine(PARAMS, CFG, num_blocks=64, block_size=8, max_batch=4)
    assert not eng.kv.k.committed and isinstance(eng._inputs, np.ndarray)
    eng.add_request([1, 2, 3], max_tokens=4)
    eng.step()
    eng.step()
    assert not eng.kv.k.committed and not eng.kv.v.committed


# ---------------------------------------------------------------------------
# The decode program's ONE packed array, kept from step to step (PR 46)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("case", ["gpt-q1", "gpt-spec",
                                  "gpt-q1-no-prefix-cache"])
def test_kept_step_array_equals_one_built_from_scratch_every_step(case):
    """A scripted run (tests/kept_array.py: admissions, a partial and a
    full prefix hit, a request of one token, greedy lanes and lanes with
    a temperature, proposals and rollbacks under speculation, a
    preemption, finishes). At every decode dispatch the engine's kept
    array equals one built from scratch from the requests' own state;
    between steps a lane's tables are its request's and a free lane is
    the scratch lane; and the token streams, the prefix hits and the
    order the blocks came back in are the ones recorded on the commit
    before PR 46. Without a prefix cache the pool indexes nothing, and
    no request is given a chain of block keys."""
    import kept_array

    kept_array.check(kept_array.engines()[case](), case)


# ---------------------------------------------------------------------------
# A step's programs are all queued before the host blocks on any (PR 47)
# ---------------------------------------------------------------------------
def _alone(req, cfg=CFG, params=PARAMS, **engine):
    """The request's tokens when it runs alone in a fresh engine."""
    engine = {"num_blocks": 64, "block_size": 8, **engine}
    eng = LLMEngine(params, cfg, **engine)
    h = eng.add_request(**req)
    _drain(eng)
    return list(h.output)


def _queued(eng):
    """(programs, programs_queued) of the engine's last step."""
    last = eng._step_perf.last
    return last["programs"], last["programs_queued"]


_LIVE = [dict(prompt=[1, 2, 3, 4, 5], max_tokens=24),
         dict(prompt=[9, 8, 7], max_tokens=24, seed=5, temperature=0.9)]
_NEW = [dict(prompt=[20, 21, 22, 23, 24, 25], max_tokens=9),
        dict(prompt=[30, 31, 32], max_tokens=7)]


def _mixed_step(new, **engine):
    """Two lanes decoding, then ``new`` arriving together: the step
    that runs their chunks beside the live lanes. Returns the engine
    after that step and every handle (the live ones first)."""
    eng = LLMEngine(PARAMS, CFG, num_blocks=64, block_size=8, max_batch=6,
                    **engine)
    live = [eng.add_request(**r) for r in _LIVE]
    eng.step()
    eng.step()
    assert all(h.state == RUNNING and len(h.output) >= 2 for h in live)
    late = [eng.add_request(**r) for r in new]
    eng.step()
    return eng, live + late


def _case_two_finishing_chunks_beside_live_lanes():
    eng, hs = _mixed_step(_NEW)
    # Two chunks and the decode program, the host blocking on none of
    # them before all three were dispatched; both new lanes decoded in
    # that very step, their first tokens handed over on the device.
    assert _queued(eng) == (3, 3)
    assert [len(h.output) for h in hs[2:]] == [2, 2]
    assert eng.stats()["last_step"]["device_ms_by"].keys() \
        == {"prefill", "decode"}
    _drain(eng)
    assert [h.output for h in hs] == [_alone(r) for r in _LIVE + _NEW]


def _case_a_sampling_request_beside_greedy_ones():
    sampled = dict(prompt=[40, 41, 42, 43], max_tokens=8, seed=3,
                   temperature=0.8, top_k=20)
    eng, hs = _mixed_step([_NEW[0], sampled, _NEW[1]])
    # The sampler needs the second chunk's logits row on the host
    # before the decode step can be built: the step blocks there, with
    # two programs queued, and the two after it see a host that waited.
    assert _queued(eng) == (4, 2)
    assert [len(h.output) for h in hs[2:]] == [2, 2, 2]
    _drain(eng)
    assert [h.output for h in hs] == [
        _alone(r) for r in _LIVE + [_NEW[0], sampled, _NEW[1]]]
    assert eng.stats()["tokens_decided_on_host"] == 24 + 8


def _case_a_first_token_that_is_a_stop_token():
    first = _alone(_NEW[0])[0]
    stopped = dict(_NEW[0], stop_tokens=[first])
    eng, hs = _mixed_step([stopped, _NEW[1]])
    h = hs[2]
    # It was handed a lane before its token was known; the token ended
    # it when its chunk was seen done: the lane and the blocks are
    # back, and the id its lane's row decoded in that step is nowhere.
    assert _queued(eng) == (3, 3)
    assert h.finish_reason == "stop" and h.output == [first]
    assert h.emitted == 1 and list(h.tokens()) == [first]
    assert h.lane is None and h.block_table == []
    assert len(eng._free_lanes) == eng.max_batch - 3
    assert len(hs[3].output) == 2
    _drain(eng)
    assert eng.kv.num_free == eng.kv.capacity
    assert [x.output for x in hs[:2] + hs[3:]] \
        == [_alone(r) for r in _LIVE + [_NEW[1]]]


def _case_a_full_prefix_hit():
    prompt = list(range(50, 66))            # two whole blocks
    eng, hs = _mixed_step([dict(prompt=prompt, max_tokens=5)])
    again = eng.add_request(prompt, max_tokens=5)
    eng.step()
    # Nothing was computed at admission, so nothing is handed over: the
    # decode step alone decides its first token.
    assert again.cached_tokens == len(prompt)
    assert _queued(eng) == (1, 1) and len(again.output) == 1
    _drain(eng)
    assert again.output == hs[2].output \
        == _alone(dict(prompt=prompt, max_tokens=5))


def _case_a_proposer_configured():
    ngram = {"mode": "ngram", "k": 3}
    eng, hs = _mixed_step(_NEW, speculative=ngram)
    # The proposer continues from a new lane's first token: the step
    # fetches it as soon as its chunk is dispatched, as it always did.
    assert _queued(eng) == (3, 1)
    _drain(eng)
    assert [h.output for h in hs] == [_alone(r) for r in _LIVE + _NEW]


def _case_preempted_with_its_chunk_in_flight():
    # Capacity 5 blocks of 8. ``old`` holds one and needs a second for
    # slot 8 in the very step that admits ``mid`` and ``new`` (two
    # blocks each: the pool is dry) and dispatches their only chunks.
    # The newest lane is the victim: ``new``, whose first token is
    # still on the device.
    old = dict(prompt=[1, 2, 3, 4, 5, 6, 7], max_tokens=12)
    mid = dict(prompt=[10, 11, 12, 13, 14, 15, 16, 17], max_tokens=6)
    new = dict(prompt=[20, 21, 22, 23, 24, 25, 26, 27], max_tokens=6)
    eng = LLMEngine(PARAMS, CFG, num_blocks=6, block_size=8)
    a = eng.add_request(**old)
    eng.step()
    assert a.context_len == 8 and len(a.block_table) == 1
    m, b = eng.add_request(**mid), eng.add_request(**new)
    dropped, settle = [], eng._settle

    def on_settle():
        dropped.extend(ch for ch in eng._pending if ch.req is None)
        return settle()

    eng._settle = on_settle
    eng.step()
    assert b.preemptions == 1 and b.state == PREEMPTED
    assert len(dropped) == 1 and dropped[0].done and dropped[0].handed
    # Its pending record was dropped: no token was emitted for it, its
    # lane is free again, and the step still closed the chunk's span.
    assert b.output == [] and b.first_token_t is None and b.lane is None
    assert len(m.output) == 2 and m.preemptions == 0
    assert eng._pending == [] and _queued(eng) == (3, 3)
    for row in eng._chunk_log:          # the ring entry's prefill_chunks
        assert row[:2] == [8, 0] and row[2] >= row[3] > 0.0
    _drain(eng)
    assert [h.output for h in (a, m, b)] \
        == [_alone(r) for r in (old, mid, new)]
    assert eng.kv.num_free == eng.kv.capacity


def _case_one_chunk_in_flight_is_awaited_before_the_decode_is_built():
    eng, hs = _mixed_step(_NEW[:1])
    # The host blocks once for it either way; its token is on the host
    # when the decode step is built, and nothing is handed over.
    assert _queued(eng) == (2, 1)
    assert len(hs[2].output) == 2
    _drain(eng)
    assert [h.output for h in hs] == [_alone(r) for r in _LIVE + _NEW[:1]]


@pytest.mark.parametrize("case", [
    _case_two_finishing_chunks_beside_live_lanes,
    _case_a_sampling_request_beside_greedy_ones,
    _case_a_first_token_that_is_a_stop_token,
    _case_a_full_prefix_hit,
    _case_a_proposer_configured,
    _case_preempted_with_its_chunk_in_flight,
    _case_one_chunk_in_flight_is_awaited_before_the_decode_is_built,
], ids=lambda f: f.__name__[len("_case_"):])
def test_a_step_queues_its_programs_and_blocks_only_where_it_must(case):
    """What decides a finishing prompt's path is what the engine can
    see in the request: every branch gives the tokens the requests get
    alone, and only a sampler, a proposer or a chunk that is alone in
    flight makes the host wait before the step's last dispatch."""
    case()


def test_the_chat_drivers_set_up_replayed():
    """``benchmark/drivers/serve_closed_loop.py``'s set-up, sizes cut
    for a CPU: the unshared reference request streams its 16 tokens
    from the background loop while, one after another as the driver
    sends them, every shared prefix is registered by a ``max_tokens:
    1`` request and one ``max_tokens: 1`` request of every tail length
    follows behind the first prefix. Each of these ends with its first
    token, arriving while another lane decodes; a tail request is a
    prefix hit whose chunk starts behind cached blocks."""
    import threading

    cfg = GPTConfig(vocab_size=128, max_seq=128, d_model=64, n_layer=2,
                    n_head=4, dtype=jnp.float32)
    params = init(jax.random.PRNGKey(1), cfg)
    bs, chunk, every = 8, 32, 8
    rng = np.random.default_rng(47)
    toks = lambda n: [int(t) for t in rng.integers(1, 128, n)]
    reference = dict(prompt=toks(40), max_tokens=16)
    prefixes = [toks(32) for _ in range(4)]
    firsts = [dict(prompt=p + toks(max(every, bs)), max_tokens=1)
              for p in prefixes]
    tails = [dict(prompt=prefixes[0] + toks(r), max_tokens=1)
             for r in range(every, chunk + 1, every)]
    engine = dict(num_blocks=96, block_size=bs, max_batch=8,
                  prefill_chunk_tokens=chunk)

    eng = LLMEngine(params, cfg, **engine)
    took_lane, take = [], eng._take_lane
    eng._take_lane = lambda req: (took_lane.append(req.rid), take(req))[1]
    eng.start()
    try:
        ref = eng.add_request(**reference)
        ref_tokens = []
        ref_thread = threading.Thread(
            target=lambda: ref_tokens.extend(ref.tokens()))
        ref_thread.start()
        sent = []
        for req in firsts + tails:
            h = eng.add_request(**req)
            assert len(list(h.tokens())) == 1       # until it has ended
            sent.append(h)
        ref_thread.join(timeout=120)
        assert not ref_thread.is_alive()
    finally:
        eng.stop()
    assert eng._fatal is None
    for h in [ref] + sent:
        assert h.finish_reason == "length", (h.rid, h.finish_reason)
    assert ref_tokens == ref.output and len(ref.output) == 16
    # Every tail request found the whole prefix, and nothing more.
    assert [h.cached_tokens for h in sent[len(firsts):]] \
        == [len(prefixes[0])] * len(tails)
    assert [h.cached_tokens for h in sent[:len(firsts)]] == [0] * 4
    # A prompt whose first token ends it never holds a lane.
    assert took_lane == [ref.rid]
    assert len(eng._free_lanes) == eng.max_batch
    # The warm-ups ran beside the reference request, not after it.
    steps = [set(rids) for _, rids in eng.step_log]
    assert any(ref.rid in s for s in steps)
    for h, req in zip([ref] + sent, [reference] + firsts + tails):
        assert h.output == _alone(req, cfg, params, **engine), h.rid


# -- a step's spans in ONE chunk program (PR 67) ------------------------------

def _latent_family():
    """Kimi's tiny configuration (``Serving.chunk_spans`` 4) and its
    parameters, made once."""
    import test_kimi_k2

    if not hasattr(_latent_family, "made"):
        from ray_tpu.models import kimi_k2

        _latent_family.made = (test_kimi_k2.TINY, kimi_k2.init(
            jax.random.PRNGKey(0), test_kimi_k2.TINY))
    return _latent_family.made


def _bodies(cfg, params, reqs, **engine):
    """``reqs`` added together to one engine and stepped ONCE. Returns
    (engine, handles, the chunk programs the step dispatched, each as
    the rows of its spans)."""
    engine = {"num_blocks": 64, "block_size": 8, "max_batch": 4, **engine}
    eng = LLMEngine(params, cfg, **engine)
    hs = [eng.add_request(**r) for r in reqs]
    programs, real = [], eng._prefill_chunk

    def spy(p, toks, *rest):
        programs.append(toks.shape[1])
        return real(p, toks, *rest)

    eng._prefill_chunk = spy
    eng.step()
    from ray_tpu.util import perfmodel

    entry = perfmodel.device_step_events()[-1]
    assert [row[0] for row in entry["prefill_chunks"]] == programs
    assert [sum(spans) for spans in entry["prefill_spans"]] == programs
    return eng, hs, entry["prefill_spans"]


_THREE = [dict(prompt=list(range(1, 1 + n)), max_tokens=4)
          for n in (16, 24, 8)]


def _case_three_bodies_fill_one_program_of_a_family_that_takes_four():
    cfg, params = _latent_family()
    eng, hs, spans = _bodies(cfg, params, _THREE, prefill_chunk_tokens=48)
    # ONE program carried the three spans, the decode program behind it.
    assert spans == [[16, 24, 8]] and _queued(eng) == (2, 2)
    assert eng.stats()["prefill_chunks"] == 1
    assert eng.stats()["prefill_spans"] == 3
    # Three chunks in flight shared it: each prompt's first token went
    # to its lane on the device, and each decoded in that very step.
    assert [len(h.output) for h in hs] == [2, 2, 2]
    _drain(eng)
    one_at_a_time = []
    for r in _THREE:
        alone = LLMEngine(params, cfg, num_blocks=64, block_size=8,
                          max_batch=4, prefill_chunk_tokens=48)
        h = alone.add_request(**r)
        _drain(alone)
        assert alone.stats()["prefill_spans"] == 1
        one_at_a_time.append(h.output)
    assert [h.output for h in hs] == one_at_a_time


def _case_three_bodies_are_three_programs_of_a_family_that_takes_one():
    eng, hs, spans = _bodies(CFG, PARAMS, _THREE, prefill_chunk_tokens=48)
    assert spans == [[16], [24], [8]] and _queued(eng) == (4, 4)
    assert eng.stats()["prefill_chunks"] == eng.stats()["prefill_spans"] == 3
    _drain(eng)
    assert [h.output for h in hs] == [_alone(r) for r in _THREE]


def _case_contexts_that_do_not_fit_one_table_ride_in_two_programs():
    cfg, params = _latent_family()
    # max_seq 160 in blocks of 8: a table of 20 blocks. Two documents
    # of 96 tokens (12 blocks each) are resident; a question behind
    # each is a span whose context does not fit the table beside the
    # other's, and a third behind a short one rides with the second.
    docs = [list(range(1, 97)), list(range(101, 197)), list(range(9, 25))]
    eng = LLMEngine(params, cfg, num_blocks=96, block_size=8, max_batch=4,
                    prefill_chunk_tokens=96)
    for d in docs:
        eng.add_request(prompt=d, max_tokens=1)
        _drain(eng)
    asks = [dict(prompt=d + [7, 8, 9, 10, 11], max_tokens=3) for d in docs]
    hs = [eng.add_request(**r) for r in asks]
    eng.step()
    from ray_tpu.util import perfmodel

    entry = perfmodel.device_step_events()[-1]
    assert [h.cached_tokens for h in hs] == [96, 96, 16]
    assert entry["prefill_spans"] == [[8], [8, 8]]
    assert [row[:2] for row in entry["prefill_chunks"]] \
        == [[8, 96], [16, 96 + 16]]
    _drain(eng)
    for h, r in zip(hs, asks):
        alone = LLMEngine(params, cfg, num_blocks=96, block_size=8,
                          max_batch=4, prefill_chunk_tokens=96)
        a = alone.add_request(**r)
        _drain(alone)
        assert h.output == a.output


def _case_preempted_with_the_packed_program_in_flight():
    cfg, params = _latent_family()
    # ``_case_preempted_with_its_chunk_in_flight``'s pool, in a family
    # whose program takes both new prompts: ``new`` is the victim while
    # the ONE program that carries its span and ``mid``'s is in flight.
    old = dict(prompt=[1, 2, 3, 4, 5, 6, 7], max_tokens=12)
    mid = dict(prompt=[10, 11, 12, 13, 14, 15, 16, 17], max_tokens=6)
    new = dict(prompt=[20, 21, 22, 23, 24, 25, 26, 27], max_tokens=6)
    eng = LLMEngine(params, cfg, num_blocks=6, block_size=8)
    a = eng.add_request(**old)
    eng.step()
    m, b = eng.add_request(**mid), eng.add_request(**new)
    seen, settle = [], eng._settle

    def on_settle():
        seen.extend(eng._pending)
        return settle()

    eng._settle = on_settle
    eng.step()
    assert b.preemptions == 1 and b.state == PREEMPTED
    kept, dropped = seen
    assert kept.span is dropped.span and kept.log is dropped.log
    assert (kept.req, kept.index, dropped.req, dropped.index) \
        == (m, 0, None, 1)
    assert b.output == [] and b.lane is None and len(m.output) == 2
    assert eng._pending == [] and _queued(eng) == (2, 2)
    assert [row[:2] for row in eng._chunk_log] == [[16, 0]]
    assert eng._span_log == [[8, 8]]
    _drain(eng)
    for h, r in ((a, old), (m, mid), (b, new)):
        alone = LLMEngine(params, cfg, num_blocks=6, block_size=8)
        x = alone.add_request(**r)
        _drain(alone)
        assert h.output == x.output
    assert eng.kv.num_free == eng.kv.capacity


def _case_a_sampler_closes_the_group_it_rides_in():
    cfg, params = _latent_family()
    sampled = dict(_THREE[1], temperature=0.8, seed=3, top_k=20)
    eng, hs, spans = _bodies(cfg, params, [_THREE[0], sampled, _THREE[2]],
                             prefill_chunk_tokens=48)
    # The sampler's row must reach the host before the decode step is
    # built: its span closes the program it rides in with the span
    # before it, and the third goes in a program of its own.
    assert spans == [[16, 24], [8]] and _queued(eng) == (3, 1)
    assert eng.stats()["tokens_decided_on_host"] == 2
    _drain(eng)
    alone = LLMEngine(params, cfg, num_blocks=64, block_size=8, max_batch=4,
                      prefill_chunk_tokens=48)
    x = alone.add_request(**sampled)
    _drain(alone)
    assert hs[1].output == x.output


@pytest.mark.parametrize("case", [
    _case_three_bodies_fill_one_program_of_a_family_that_takes_four,
    _case_three_bodies_are_three_programs_of_a_family_that_takes_one,
    _case_contexts_that_do_not_fit_one_table_ride_in_two_programs,
    _case_preempted_with_the_packed_program_in_flight,
    _case_a_sampler_closes_the_group_it_rides_in,
], ids=lambda f: f.__name__[len("_case_"):])
def test_a_steps_spans_ride_in_as_few_programs_as_the_family_takes(case):
    """The loop that cuts a step's budget into spans is one; what a
    span rides in follows what the engine can see: the seam's
    ``chunk_spans``, whether the contexts fit one table, whether a
    result has to be fetched at once. Every path gives the tokens the
    requests get one at a time."""
    case()
