"""Overhead gates of the hot paths that run once a request, a step, a
collective or a block, each asserted as a COUNT that the box's load
cannot move (tests/callcount.py): Python-level calls a unit of work, or
how often the path reaches something that must stay off it (a uuid4, a
directory scan, a hash of tokens, a membership refresh).

These were wall-clock budgets scaled by a calibration loop and meant to
run alone, first in the suite; the suite runs under six xdist workers,
so they measured the box. What each docstring names as the regression is
a property of the code, and is counted here. How fast anything is, is
said by ``benchmark/run.py`` on the chip (``PERF_LEDGER.jsonl``) and
nowhere else.

A recorded count below is what this tree does. One that rises because a
call was added on purpose is recorded again; one that rises because work
moved onto the hot path is the regression.
"""

import dataclasses
import secrets
import threading
import types
import uuid

import pytest

import ray_tpu
from callcount import calls_of, python_calls


def _calls(fn, times: int = 1) -> int:
    """Python-level calls of ``times`` runs of ``fn()``."""
    with python_calls() as c:
        for _ in range(times):
            fn()
    return c.n


def test_telemetry_sampler_overhead_gate():
    """The telemetry sampler runs on the node loop every interval: its
    hot path is O(counters + workers + rpc methods) plus ONE sum over the
    store's objects. What a sample() costs does not depend on how many
    it has taken, and an object in the store adds at most one call (its
    turn of the size sum's generator): a regression to O(expensive)
    scanning — a stat, a lock or a lookup an object — fails loudly."""
    from ray_tpu._private.telemetry import TelemetrySampler

    ray_tpu.shutdown()
    rt = ray_tpu.init(num_cpus=2)
    try:
        @ray_tpu.remote
        def tick(i):
            return ray_tpu.put(bytes(100))

        def calls_a_sample(n_tasks):
            held.extend(ray_tpu.get([tick.remote(i) for i in range(n_tasks)],
                                    timeout=60))
            sampler = TelemetrySampler(rt.node)
            sampler.sample()  # prime the anchors
            for _ in range(20):     # until no free lands between readings
                objects = len(rt.node.objects)
                first, second = _calls(sampler.sample), _calls(sampler.sample)
                if len(rt.node.objects) == objects:
                    break
            assert second == first, \
                "a sample() costs more the more samples were taken"
            return first, objects

        held = []       # the refs keep the objects in the store
        few, few_objects = calls_a_sample(50)
        many, many_objects = calls_a_sample(450)
    finally:
        ray_tpu.shutdown()
    added = many_objects - few_objects
    assert added >= 300, (few_objects, many_objects)
    # 8: a new rpc method or worker seen between the two readings.
    assert many - few <= added + 8, (
        f"telemetry sampler hot path regressed: {added} more objects in "
        f"the store cost {many - few} more calls a sample "
        f"({few} -> {many}); at most one an object")


REQUEST_SPAN_CALLS = 19     # recorded on this tree (PR 48)


def test_request_span_overhead_gate():
    """The request-tracing hot path runs on EVERY serving request,
    sampled or not (tail sampling is a head-side decision): one root
    span enter/exit with an event plus two retro emits. A regression —
    say span IDs going back to uuid4, or recording growing a
    lock-heavy stage — fails loudly here before it taxes every
    request: uuid4 is never called, and the Python-level calls a
    request are at most the recorded number, at the first request as
    at the 2,000th."""
    from ray_tpu.util import tracing

    t_wall = 1_700_000_000.0

    def request():
        with tracing.span("serve.request", kind="request",
                          attributes={"deployment": "gate"}) as root:
            tracing.emit("serve.proxy_queue", root.context(), t_wall,
                         1e-4, {"deployment": "gate"})
            tracing.emit("serve.replica_queue", root.context(), t_wall,
                         1e-4, {"deployment": "gate"})
            root.add_event("ttft", ms=1.0)

    # Warm the id-prefix seed + ring out of the counted region.
    with tracing.span("warm", kind="request"):
        pass
    tracing.drain_request_spans()
    n = 2000
    with calls_of(uuid, "uuid4") as uuid4s:
        one = _calls(request)
        many = _calls(request, n)
    tracing.drain_request_spans()
    assert uuid4s.n == 0, "span ids are made by uuid4 again"
    assert many == n * one, (one, many / n)
    assert one - 1 <= REQUEST_SPAN_CALLS, (
        f"request-span hot path regressed: {one - 1} calls a request, "
        f"{REQUEST_SPAN_CALLS} recorded")


STEP_ACCOUNTING_CALLS = 39  # recorded on this tree (PR 48)


def test_step_accounting_overhead_gate():
    """The device-step accounting runs inside the engine's scheduler
    step, under the engine lock, on EVERY decode: one begin + one
    priced add_device (an 8-lane decode_step_cost through the shape
    cache) + finish. A regression — the shape cache degenerating to
    per-call recompute, finish growing allocation-heavy — taxes every
    generated token, so it fails loudly here: over 5,000 steps the
    model's cost description is made ONCE, and a step makes the
    recorded number of calls, the first as the last."""
    from ray_tpu.models import gpt
    from ray_tpu.util import perfmodel

    acc = perfmodel.StepAccounting(
        hw=perfmodel.HARDWARE_PEAKS[perfmodel.V5E])
    ctx = [100, 200, 300, 400, 500, 600, 700, 800]
    # A configuration the per-config cache has not seen.
    cfg = dataclasses.replace(gpt.GPT2_SMALL, max_seq=1000)

    def step():
        acc.begin()
        acc.add_device(1e-3, perfmodel.decode_step_cost(cfg, ctx))
        acc.finish()

    n = 5000
    with calls_of(gpt, "cost_shape") as priced:
        step()                      # prices the configuration
        one = _calls(step)
        many = _calls(step, n)
    assert priced.n == 1, (
        f"the cost description was made {priced.n} times over {n + 2} "
        f"steps of one configuration: the shape cache does not hold")
    assert many == n * one, (one, many / n)
    assert one - 1 <= STEP_ACCOUNTING_CALLS, (
        f"step-accounting hot path regressed: {one - 1} calls a step, "
        f"{STEP_ACCOUNTING_CALLS} recorded")
    # What finish() reads of the interpreter probe (PR 60: six keys, one
    # tuple read and no call) is inside that count.
    assert {"interp_n", "interp_late_ms", "interp_late_max_ms",
            "interp_held_n", "standstill_ms", "held_long_ms"} <= set(acc.last)


FLIGHT_RECORDER_CALLS = 3   # record_enter, record_exit, _maybe_publish


def test_flight_recorder_overhead_gate():
    """The flight recorder brackets EVERY eager collective: one
    record_enter + record_exit pair (two dict/deque writes under a
    lock, throttled gauge publish). A regression — say the ring
    growing a per-op snapshot, or the gauge publish losing its
    throttle — taxes every collective, so it fails loudly here: on a
    clock that a pair advances by 20 us, 20,000 pairs (0.4 s) publish
    the three gauges twice (once every 0.2 s), and a pair that does
    not publish makes three calls."""
    from ray_tpu.parallel import flightrec
    from ray_tpu.util.metrics import Gauge

    ticks = iter(range(10**9))
    clock = types.SimpleNamespace(
        monotonic=lambda: 1000.0 + next(ticks) * 10e-6,
        time=lambda: 1_700_000_000.0)
    rec = flightrec.FlightRecorder(capacity=1024)

    def pair():
        e = rec.record_enter("gate", "allreduce", "dp", (8,), 32)
        rec.record_exit(e)

    n = 20000
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(flightrec, "time", clock)
        pair()                  # lazy gauge creation; publishes at 0 s
        quiet = _calls(pair) - 3 - 1    # less the clock's three; pair
        # This thread's writes alone: an engine that an earlier test of
        # the same xdist worker left idling publishes gauges of its own.
        with calls_of(Gauge, "set",
                      thread=threading.get_ident()) as published:
            for _ in range(n):
                pair()
    span_s = 2 * n * 10e-6
    assert published.n == 3 * round(span_s / flightrec._PUBLISH_INTERVAL_S), (
        f"{published.n} gauge writes over {n} pairs in {span_s} s of the "
        f"recorder's clock: the publish is not throttled")
    assert quiet <= FLIGHT_RECORDER_CALLS, (
        f"flight-recorder hot path regressed: {quiet} calls a pair, "
        f"{FLIGHT_RECORDER_CALLS} recorded")


def test_locality_and_spill_bookkeeping_gate():
    """The data plane's locality routing and the store's capacity
    bookkeeping both sit on the per-block scheduling path: one
    owner_addr -> NodeID resolve, one per-node handle-cache lookup, and
    one _ensure_capacity pass (cached-used fast path, amortizing the
    every-32-puts scandir resync). A regression — the resolver
    refreshing membership per call, the handle cache degenerating to
    per-call .options() re-wraps, or capacity checks scanning the arena
    on every put — taxes every block, so it fails loudly here: over
    20,000 blocks the arena is scanned once every 32 and membership is
    refreshed never."""
    import os

    from ray_tpu._private import object_store
    from ray_tpu._private.object_store import ObjectID, SharedMemoryStore
    from ray_tpu.data.execution import _LocalityResolver

    resolver = _LocalityResolver()
    addr = ("10.0.0.1", 7001)
    resolver._map = {addr: b"n" * 28}
    handle_cache = {b"n" * 28: object()}  # _remote_by_node stand-in
    store = SharedMemoryStore(secrets.token_hex(6),
                              capacity_bytes=1 << 30)
    try:
        # A populated arena so the periodic scandir resync has real work.
        for _ in range(32):
            store.put(ObjectID(secrets.token_bytes(28)), b"x" * 4096)
        # Warm the fast path out of the counted region.
        resolver.node_of(addr)
        store._ensure_capacity(1024)
        n = 20000
        with calls_of(os, "scandir") as scans, \
                calls_of(_LocalityResolver, "_refresh") as refreshes:
            for _ in range(n):
                nid = resolver.node_of(addr)
                assert handle_cache.get(nid) is not None
                store._ensure_capacity(1024)
    finally:
        store.destroy()
    every = object_store._USED_SYNC_EVERY
    assert refreshes.n == 0, "the resolver refreshes membership on a hit"
    assert resolver.hits == n + 1 and resolver.misses == 0
    # One resync closes each run of ``every`` fast-path puts.
    assert n // (every + 1) <= scans.n <= n // every + 1, (
        f"locality/spill bookkeeping regressed: {scans.n} arena scans "
        f"over {n} puts; one every {every}")


def test_prefix_pool_bookkeeping_gate():
    """The prefix-cache bookkeeping runs at EVERY admission, under the
    engine lock: a full-hit admit (a walk of the request's chain of
    block keys, which ``add_request`` made on the caller's thread:
    index verify + ref bumps + LRU pops) plus the matching release
    (re-register walk + unref parks). A regression — the index growing
    a per-lookup content scan, or LRU parking degenerating to list
    removal — taxes every admitted request, so it fails loudly here:
    with the request's ``BlockChain`` a full-hit admit + release hashes
    no token and looks the index up once a block, and the 2,000th
    makes the calls the first made."""
    pytest.importorskip("jax")
    import jax.numpy as jnp

    from ray_tpu.llm import kv_cache
    from ray_tpu.llm.kv_cache import BlockChain, PrefixPool
    from ray_tpu.models.gpt import GPTConfig

    class CountingIndex(dict):
        lookups = 0

        def _counted(name):
            def lookup(self, *args):
                self.lookups += 1
                return getattr(dict, name)(self, *args)
            return lookup

        get = _counted("get")
        __getitem__ = _counted("__getitem__")
        __contains__ = _counted("__contains__")

    cfg = GPTConfig(vocab_size=64, max_seq=256, d_model=32, n_layer=2,
                    n_head=4, dtype=jnp.float32)
    pool = PrefixPool(cfg, num_blocks=32, block_size=16)
    seq = list(range(64))                  # 4 full chunks
    chain = BlockChain(pool.block_size, seq)    # once a request
    for _ in range(2):      # registered + parked, then matched once
        warm, _ = pool.admit(seq, len(seq) + 1, chain=chain)
        pool.release(warm, seq=seq, chain=chain)
    pool._index = CountingIndex(pool._index)
    cached = []

    def admit_release():
        table, hit = pool.admit(seq, len(seq) + 1, chain=chain)
        cached.append(hit)
        pool.release(table, seq=seq, chain=chain)

    n = 2000
    with pytest.MonkeyPatch.context() as mp:
        hashed = []
        mp.setattr(kv_cache, "hash",
                   lambda x: hashed.append(x) or hash(x), raising=False)
        one = _calls(admit_release)
        many = _calls(admit_release, n)
    assert set(cached) == {len(seq)}, "gate must exercise the full-hit path"
    assert hashed == [], f"{len(hashed)} hashes of tokens the chain holds"
    blocks = len(seq) // pool.block_size
    assert pool._index.lookups <= (n + 1) * blocks, (
        f"prefix-pool bookkeeping regressed: {pool._index.lookups} index "
        f"lookups over {n + 1} admitted requests of {blocks} blocks")
    assert many == n * one, (one, many / n)


def test_spec_disabled_step_overhead_gate():
    """Speculative decoding must be FREE when off: the engine builds no
    proposer and no verify program (structural zero-overhead — step()
    keeps the plain one-token decode path behind a single attribute
    check), and the n-gram proposer itself — the per-lane, per-step
    cost once speculation IS on — must not grow with the history
    faster than the history. A regression — the guard growing work, or
    the suffix match degenerating to a quadratic rescan per call —
    taxes every decode step, so it fails loudly here: a propose() over
    a periodic 512-token history makes at most twice the calls of one
    over 256 tokens, and those are the recorded few."""
    pytest.importorskip("jax")
    import jax
    import jax.numpy as jnp

    from ray_tpu.llm.engine import LLMEngine
    from ray_tpu.llm.spec import NgramProposer
    from ray_tpu.models.gpt import GPTConfig, init

    cfg = GPTConfig(vocab_size=64, max_seq=64, d_model=32, n_layer=1,
                    n_head=2, dtype=jnp.float32)
    eng = LLMEngine(init(jax.random.PRNGKey(0), cfg), cfg, num_blocks=4,
                    block_size=16, max_batch=2, speculative=None)
    # Structural: disabled means NO spec object, and one row a lane.
    assert eng._spec is None
    assert eng._q_rows == 1
    # Enabled-path proposer cost on a worst-ish-case history: long,
    # periodic (every call walks the match loop and extends to k).
    prop = NgramProposer()
    hist = [7, 8, 9, 7, 8] * 103
    assert len(prop.propose(hist[:256], 4)) == 4
    short = _calls(lambda: prop.propose(hist[:256], 4))
    long = _calls(lambda: prop.propose(hist[:512], 4))
    assert long <= 2 * short, (
        f"n-gram propose regressed: {short} calls over 256 tokens, "
        f"{long} over 512")
    assert short <= 8, f"n-gram propose regressed: {short} calls a propose"


def test_alert_rule_evaluation_gate():
    """The head's per-beat alert pass (observe one node's sampler beat
    + run every rule's burn-rate state machine) rides the heartbeat
    path: what a beat costs is linear in the rules that receive a
    sample — the calls a beat at 100 declared rules are at most twice
    those at 50 — and a rule that receives none costs nothing."""
    from ray_tpu._private.alerting import AlertEngine
    from ray_tpu._private.telemetry import TelemetryStore

    def calls_a_beat(rules, sampled):
        eng = AlertEngine(TelemetryStore())
        for i in range(rules):
            eng.declare({"name": f"gate-rule-{i}",
                         "metric": f"alert_gate_m{i}",
                         "target": 10.0, "comparison": "<=",
                         "budget": 0.01})
        metrics = {f"alert_gate_m{i}": 1.0 for i in range(sampled)}
        beats = iter(range(10**6))

        def beat():
            ts = 1_700_000_000.0 + next(beats)
            eng.observe([{"ts": ts, "metrics": metrics}], now=ts)
            eng.evaluate(now=ts)

        # Warm one beat: window deques allocate, builtin probing settles.
        beat()
        first = _calls(beat)
        assert _calls(beat, 500) == 500 * first
        return first

    at_50 = calls_a_beat(50, 50)
    at_100 = calls_a_beat(100, 100)
    assert at_100 <= 2 * at_50, (
        f"alert evaluation hot path regressed: {at_50} calls a beat at "
        f"50 rules, {at_100} at 100")
    assert calls_a_beat(100, 50) == at_50, \
        "a rule that receives no sample costs calls"
