"""The step timeline (util/perfmodel.py PHASES): named host phases,
device spans by kind and per-step counts on the engine's and the train
session's ring entries; queue and stream waits in the serve/slo plane;
stable program names; the idle-gap reduction of `rtpu profile --device`.

CPU, manually stepped where it can be. Structure and counts only: no
time is asserted as a fact.
"""

import gc
import threading
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.llm import LLMEngine  # noqa: E402
from ray_tpu.models import gpt  # noqa: E402
from ray_tpu.models.gpt import GPTConfig, init  # noqa: E402
from ray_tpu.util import perfmodel  # noqa: E402
from ray_tpu.util.perfmodel import PHASES, StepAccounting, StepCost  # noqa: E402

CFG = GPTConfig(vocab_size=128, max_seq=64, d_model=64, n_layer=2,
                n_head=4, dtype=jnp.float32)
PARAMS = init(jax.random.PRNGKey(0), CFG)

# Kind None: the host phases, and the accounting's own annotations
# (perfmodel.ANNOTATIONS), which are never keys of phases_ms.
HOST_PHASES = {n for n, (kind, _) in PHASES.items() if kind is None} \
    - perfmodel.ANNOTATIONS
DEVICE_SPANS = {n: kind for n, (kind, _) in PHASES.items() if kind}


def _drain(eng, max_steps=300):
    for _ in range(max_steps):
        s = eng.stats()
        if not s["in_flight"] and not s["waiting"]:
            return
        eng.step()
    raise AssertionError("engine did not drain")


def _ring(name, since):
    return [e for e in perfmodel.device_step_events(since=since)
            if e["name"] == name]


def _check_partition(entry):
    assert set(entry["phases_ms"]) <= HOST_PHASES
    assert sum(entry["phases_ms"].values()) + entry["other_ms"] == \
        pytest.approx(entry["host_gap_ms"], abs=1e-6)
    assert sum(entry["device_ms_by"].values()) == \
        pytest.approx(entry["device_ms"], abs=1e-6)
    assert entry["step_ms"] == pytest.approx(
        entry["device_ms"] + entry["host_gap_ms"], abs=1e-6)


# ---------------------------------------------------------------------------
# StepAccounting
# ---------------------------------------------------------------------------
def test_phases_partition_the_host_gap_and_kinds_the_device_span():
    acc = StepAccounting()
    acc.begin()
    with acc.phase("llm.admit"):
        pass
    with acc.device("llm.prefill.device") as dev:
        pass
    assert dev.seconds >= 0.0
    acc.add_device(0.002, StepCost(1e6, 1e5, 3), kind="decode")
    with acc.phase("llm.sample"):
        pass
    with acc.phase("llm.sample"):       # a name may run twice a step
        pass
    out = acc.finish()
    _check_partition(out)
    assert set(out["phases_ms"]) == {"llm.admit", "llm.sample"}
    assert set(out["device_ms_by"]) == {"prefill", "decode"}
    assert out["tokens"] == 3
    assert "between_ms" not in out and out["idle_wait"] is False
    # begin() forgets the step before it.
    acc.begin()
    acc.add_device(0.001)
    out = acc.finish()
    assert out["phases_ms"] == {} and set(out["device_ms_by"]) == {"device"}
    assert out["between_ms"] >= 0.0


@pytest.mark.parametrize("call, name", [
    ("phase", "llm.not_a_phase"), ("device", "llm.not_a_span"),
    ("phase", "llm.decode.device"),     # a device span is not a phase
    ("device", "llm.sample"),           # nor a phase a device span
    ("step", "llm.sample"),
    # The accounting's own annotations are neither.
    ("phase", "llm.between"), ("phase", "py.gc"),
    ("device", "llm.decode.dispatch"), ("idle", "llm.sample"),
])
def test_a_name_outside_the_registry_is_an_error(call, name):
    acc = StepAccounting()
    with pytest.raises(KeyError):
        if call == "step":
            acc.step(name, 1)
        elif call == "idle":
            with acc.idle(name):
                pass
        else:
            getattr(acc, call)(name)
    with pytest.raises(KeyError):
        acc.add_device(0.001, kind="no_such_kind")


def test_registry_names_every_kind_once_and_documents_each_span():
    assert set(DEVICE_SPANS.values()) == {"prefill", "decode", "dispatch",
                                          "wait"}
    assert len(set(DEVICE_SPANS.values())) == len(DEVICE_SPANS)
    assert all(what and isinstance(what, str)
               for _, what in PHASES.values())
    assert set(perfmodel.STEPS) == {"llm.step", "train.step"}
    # The annotations are registry names with kind None and a meaning.
    assert perfmodel.ANNOTATIONS <= set(PHASES)
    assert all(PHASES[n][0] is None for n in perfmodel.ANNOTATIONS)
    with pytest.raises(KeyError):
        StepAccounting(between="llm.sample")


# One tick of the thread's CPU clock, generously: some hosts count it
# in 10 ms jiffies whatever resolution they report.
TICK_MS = max(time.get_clock_info("thread_time").resolution * 1e3, 10.0) + 1.0


def _check_interval(entry):
    """One finish() to the next, by its parts."""
    assert entry["interval_ms"] == pytest.approx(
        entry.get("between_ms", 0.0) + entry["step_ms"], abs=1e-9)
    if "between_ms" in entry:
        assert entry["lock_wait_ms"] + entry["idle_ms"] <= \
            entry["between_ms"] + 1e-6
    assert entry["idle_wait"] == (entry["idle_ms"] > 0.0)
    # The thread cannot have run for longer than the interval lasted,
    # give or take a tick of its CPU clock; what is left of the
    # interval after the sleep, the waits for the device and the CPU
    # time is the stall.
    assert 0.0 <= entry["cpu_ms"] <= entry["interval_ms"] + TICK_MS
    waits = sum(entry["device_ms_by"].values()) \
        - sum(entry["dispatch_ms_by"].values())
    assert entry["stall_ms"] == pytest.approx(
        entry["interval_ms"] - min(entry["idle_ms"],
                                   entry.get("between_ms", 0.0))
        - waits - entry["cpu_ms"], abs=1e-6)
    assert entry["stall_ms"] >= -TICK_MS
    for kind, ms in entry["dispatch_ms_by"].items():
        assert 0.0 <= ms <= entry["device_ms_by"][kind]
    assert entry["gc_ms"] >= entry["gc_max_ms"] >= 0.0
    assert (entry["gc_gen"] is None) == (entry["gc_max_ms"] == 0.0)
    # The interpreter probe's samples that ended inside the interval
    # (PR 60): counts, their lateness, and the long ones by whose they
    # were.
    assert 0 <= entry["interp_held_n"] <= entry["interp_n"]
    assert entry["interp_late_ms"] + 1e-9 >= entry["interp_late_max_ms"] >= 0.0
    assert 0.0 <= entry["standstill_ms"] + entry["held_long_ms"] <= \
        entry["interp_late_ms"] + 1e-9


def _spin(seconds):
    """Pure-Python work on this thread for ``seconds`` of wall time."""
    t_end = time.perf_counter() + seconds
    n = 0
    while time.perf_counter() < t_end:
        n += 1
    return n


def test_the_interval_is_a_sum_of_named_parts():
    acc = StepAccounting()
    for _ in range(3):
        acc.begin()
        with acc.phase("llm.admit"):
            _spin(0.002)
        with acc.dispatch("llm.decode.device") as dev:
            pass
        with dev.waiting():
            pass
        assert 0.0 <= dev.dispatch_seconds <= dev.seconds
        out = acc.finish()
        _check_partition(out)
        _check_interval(out)
        assert set(out["dispatch_ms_by"]) == {"decode"}
    assert "between_ms" in out and out["lock_wait_ms"] == 0.0
    # A thread that computes alone is running: CPU time, no stall.
    acc.begin()
    with acc.phase("llm.admit"):
        _spin(0.1)
    acc.add_device(1e-6)
    out = acc.finish()
    assert out["cpu_ms"] > 50.0 and out["stall_ms"] < out["cpu_ms"]


def test_a_spinning_neighbour_thread_shows_as_stall():
    """The thread had work and was not running: another Python thread
    held the interpreter for its share of the phase."""
    acc = StepAccounting()
    acc.begin()
    acc.add_device(1e-6)
    acc.finish()
    stop = threading.Event()

    def neighbour():
        while not stop.is_set():
            pass

    t = threading.Thread(target=neighbour, daemon=True)
    t.start()
    try:
        acc.begin()
        with acc.phase("llm.emit"):
            _spin(0.2)
        acc.add_device(1e-6)
        out = acc.finish()
    finally:
        stop.set()
        t.join(timeout=10)
    _check_interval(out)
    wall = out["phases_ms"]["llm.emit"]
    assert wall >= 200.0 and out["cpu_ms"] < wall - 20.0
    assert out["stall_ms"] > 20.0 + TICK_MS


def test_a_collection_inside_a_phase_is_named_and_leaves_the_partition():
    acc = StepAccounting()
    acc.begin()
    acc.add_device(1e-6)
    acc.finish()
    before = perfmodel.gc_totals()
    acc.begin()
    with acc.phase("llm.emit"):
        gc.collect()
    acc.add_device(1e-6)
    out = acc.finish()
    _check_partition(out)
    _check_interval(out)
    assert out["gc_ms"] > 0.0 and out["gc_gen"] == 2
    assert "py.gc" not in out["phases_ms"]
    after = perfmodel.gc_totals()
    assert after[2][0] == before[2][0] + 1
    assert after[2][1] - before[2][1] <= out["gc_ms"] / 1e3 + 1e-9
    # One hook a process, however many accountings began a step.
    StepAccounting().begin()
    assert gc.callbacks.count(perfmodel._COLLECTOR.hook) == 1
    # The next interval holds no generation-2 pass of its own.
    acc.begin()
    acc.add_device(1e-6)
    assert acc.finish()["gc_gen"] != 2


# ---------------------------------------------------------------------------
# The engine's ring entries
# ---------------------------------------------------------------------------
def test_engine_ring_entries_partition_and_name_only_registry_phases():
    perfmodel.clear_device_steps()
    t0 = time.time()
    eng = LLMEngine(PARAMS, CFG, num_blocks=64, block_size=8, max_batch=4,
                    prefill_chunk_tokens=8)
    for i in range(3):
        eng.add_request(list(range(1, 12 + i)), max_tokens=5, seed=i)
    _drain(eng)
    ring = _ring("llm.step", t0)
    assert len(ring) == eng.stats()["steps"]
    seen = set()
    for e in ring:
        _check_partition(e)
        _check_interval(e)
        assert set(e["device_ms_by"]) <= {"prefill", "decode"}
        # Both served spans are cut where their call returned.
        assert set(e["dispatch_ms_by"]) == set(e["device_ms_by"])
        seen |= set(e["phases_ms"])
    # Every host phase of the plain decode path ran and was named
    # (llm.trace copies spans of traced requests only: none here).
    assert seen >= {"llm.admit", "llm.prefill.host", "llm.slots",
                    "llm.decode.build", "llm.sample", "llm.emit",
                    "llm.trace", "llm.publish"}
    perfmodel.clear_device_steps()


@pytest.mark.parametrize("prompts", [(24, 8, 32), (21, 9, 30)],
                         ids=["whole_blocks", "ragged"])
def test_ring_counts_agree_with_the_scheduler(prompts):
    """lanes / context_tokens / decode_tokens / prefill_tokens /
    prefill_chunks are the scheduler's own numbers: checked against
    step_log and the requests' context_len, step by step."""
    perfmodel.clear_device_steps()
    t0 = time.time()
    budget, bs = 16, 8
    eng = LLMEngine(PARAMS, CFG, num_blocks=64, block_size=bs, max_batch=4,
                    prefill_chunk_tokens=budget, prefix_cache=False)
    reqs = [eng.add_request(list(range(1, 1 + n)), max_tokens=6, seed=n)
            for n in prompts]
    for _ in range(200):
        s = eng.stats()
        if not s["in_flight"] and not s["waiting"]:
            break
        before = {r.rid: r.context_len for r in reqs}
        running = [r for r in reqs if r.state == "RUNNING"]
        eng.step()
        e = _ring("llm.step", t0)[-1]
        assert e["step"] == eng.step_log[-1][0]
        assert e["max_batch"] == 4 and e["waiting"] == len(eng._waiting)
        assert e["decode_tokens"] + e["prefill_tokens"] == e["tokens"]
        assert e["prefill_tokens"] == sum(c[0] for c in e["prefill_chunks"])
        # The chunk budget holds a step. It counts prompt tokens; a
        # chunk computes them padded to whole blocks, which is what
        # the ring counts (and the cost model prices).
        assert e["prefill_tokens"] <= budget + \
            len(e["prefill_chunks"]) * (max(n % bs for n in prompts) and
                                        bs - 1)
        for tokens, ctx_tokens, device_ms, dispatch_ms in \
                e["prefill_chunks"]:
            assert tokens % bs == 0 and ctx_tokens % bs == 0
            assert 0.0 <= dispatch_ms <= device_ms
        assert sum(c[3] for c in e["prefill_chunks"]) == pytest.approx(
            e["dispatch_ms_by"].get("prefill", 0.0), abs=1e-9)
        # Lanes: what was RUNNING before the step plus what its
        # prefills activated; each lane attends its context + 1.
        lanes = [r for r in reqs
                 if r in running or (before[r.rid] < len(r.prompt)
                                     <= r.prefilled_upto
                                     and r.output)]
        assert e["lanes"] == e["decode_tokens"] == len(lanes)
        assert e["lanes"] <= len(eng.step_log[-1][1]) + sum(
            r.state == "FINISHED" for r in lanes)
        assert e["preempted"] == 0
    else:
        raise AssertionError("engine did not drain")
    ring = _ring("llm.step", t0)
    # Every prompt token was computed exactly once, padded to blocks.
    assert sum(e["prefill_tokens"] for e in ring) == sum(
        -(-len(r.prompt) // bs) * bs for r in reqs)
    # Every output token but each request's first (sampled by its
    # prefill) came from a decode lane.
    assert sum(e["decode_tokens"] for e in ring) == sum(
        len(r.output) - 1 for r in reqs)
    perfmodel.clear_device_steps()


def test_context_tokens_is_the_sum_of_the_decode_lanes_context():
    perfmodel.clear_device_steps()
    t0 = time.time()
    eng = LLMEngine(PARAMS, CFG, num_blocks=64, block_size=8, max_batch=4)
    reqs = [eng.add_request([3] * n, max_tokens=4) for n in (5, 11)]
    eng.step()          # both prefill whole and decode their first lane
    while any(r.state != "FINISHED" for r in reqs):
        want = sum(r.context_len + 1 for r in reqs if r.state == "RUNNING")
        eng.step()
        assert _ring("llm.step", t0)[-1]["context_tokens"] == want
    perfmodel.clear_device_steps()


def test_preemptions_are_counted_on_the_step_that_made_them():
    perfmodel.clear_device_steps()
    t0 = time.time()
    eng = LLMEngine(PARAMS, CFG, num_blocks=6, block_size=8, max_batch=4)
    reqs = [eng.add_request([7] * 12, max_tokens=20, seed=i)
            for i in range(3)]
    _drain(eng)
    ring = _ring("llm.step", t0)
    assert sum(e["preempted"] for e in ring) == \
        sum(r.preemptions for r in reqs) > 0
    perfmodel.clear_device_steps()


def test_between_ms_on_a_manually_stepped_engine():
    perfmodel.clear_device_steps()
    t0 = time.time()
    eng = LLMEngine(PARAMS, CFG, num_blocks=32, block_size=8)
    eng.add_request([1, 2, 3], max_tokens=4)
    _drain(eng)
    ring = _ring("llm.step", t0)
    assert "between_ms" not in ring[0]      # no finish before the first
    for e in ring[1:]:
        assert e["idle_wait"] is False      # nobody slept: no loop
        assert e["between_ms"] >= 0.0
    perfmodel.clear_device_steps()


def test_a_held_lock_between_two_manual_steps_is_lock_wait():
    perfmodel.clear_device_steps()
    t0 = time.time()
    eng = LLMEngine(PARAMS, CFG, num_blocks=32, block_size=8)
    eng.add_request([1, 2, 3], max_tokens=6)
    eng.step()
    holding, hold_s = threading.Event(), 0.05

    def holder():
        with eng._lock:
            holding.set()
            time.sleep(hold_s)

    t = threading.Thread(target=holder, daemon=True)
    t.start()
    assert holding.wait(timeout=10)
    eng.step()                      # waits out the holder
    t.join(timeout=10)
    eng.step()
    held, free = _ring("llm.step", t0)[1:3]
    _check_interval(held)
    assert held["lock_wait_ms"] >= hold_s * 1e3 * 0.8
    assert held["between_ms"] >= held["lock_wait_ms"]
    # Waiting for a lock is not running: it is stall, too.
    assert held["stall_ms"] >= held["lock_wait_ms"] * 0.8
    assert free["lock_wait_ms"] < hold_s * 1e3 * 0.5
    perfmodel.clear_device_steps()


def test_idle_seconds_are_totalled_and_arrivals_counted():
    perfmodel.clear_device_steps()
    t0 = time.time()
    eng = LLMEngine(PARAMS, CFG, num_blocks=32, block_size=8, max_batch=4,
                    name="idle_total_test")
    assert eng.stats()["idle_s"] == 0.0 and eng.stats()["idle_waits"] == 0
    eng.start()
    try:
        time.sleep(0.7)     # a wait is counted when it ends: 0.5 s
        first = eng.stats()
        assert first["idle_waits"] >= 1
        hs = [eng.add_request([5, 6, 7 + i], max_tokens=3) for i in range(3)]
        assert all(len(list(h.tokens())) == 3 for h in hs)
        time.sleep(0.2)
    finally:
        eng.stop()
    last = eng.stats()
    assert last["idle_s"] > first["idle_s"] > 0.0
    assert last["idle_s"] >= 0.6 and last["idle_waits"] > first["idle_waits"]
    assert set(last["gc"]) == {0, 1, 2} and all(
        n >= 0 and s >= 0.0 for n, s in last["gc"].values())
    ring = _ring("llm.step", t0)
    assert sum(e["arrived"] for e in ring) == 3
    assert ring[0]["arrived"] >= 1 and ring[0]["idle_wait"]
    assert ring[0]["idle_ms"] >= 600.0
    for e in ring:
        _check_interval(e)
    # What the entries hold of the sleep, the total holds too.
    assert sum(e["idle_ms"] for e in ring) / 1e3 <= last["idle_s"] + 1e-6
    perfmodel.clear_device_steps()


def test_idle_wait_marks_the_step_after_the_loop_slept():
    perfmodel.clear_device_steps()
    t0 = time.time()
    eng = LLMEngine(PARAMS, CFG, num_blocks=32, block_size=8,
                    name="idle_wait_test")
    eng.start()
    try:
        time.sleep(0.2)     # the loop is asleep on its empty engine
        for _ in range(2):
            h = eng.add_request([5, 6, 7], max_tokens=3)
            assert len(list(h.tokens())) == 3
            time.sleep(0.2)
    finally:
        eng.stop()
    ring = _ring("llm.step", t0)
    woken = [e for e in ring if e["idle_wait"]]
    # Each request's first step followed a sleep; the steps that
    # decode its other tokens followed a step.
    assert len(woken) == 2 and ring[0]["idle_wait"]
    assert len(ring) > len(woken)
    assert all(e["between_ms"] >= 0.0 for e in ring[1:])
    perfmodel.clear_device_steps()


# ---------------------------------------------------------------------------
# Waiting, measured where it happens (serve/slo.py plane)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("read", ["stream_next", "stream_poll"])
def test_phase_hist_counts_queue_ttft_and_stream_holds(read):
    """One replica, no cluster: requests through handle_request, their
    streams read one at a time through stream_next, or all at once
    through stream_poll as a handle's poller reads them. Either way a
    chunk's hold is counted once."""
    from ray_tpu.serve import slo
    from ray_tpu.serve.llm import _LLMServer
    from ray_tpu.serve.replica import REPLY_SENT, STREAM_MARKER, Replica

    slo._reset_for_tests()
    rep = Replica(_LLMServer, (CFG,), dict(
        params=PARAMS, num_blocks=64, block_size=8, max_batch=4),
        deployment_name="hist_test")
    try:
        answers = (5, 3, 7)
        sids = [rep.handle_request(
            "__call__", ({"prompt": [1, 2, 3, i + 4], "max_tokens": n},),
            {})[STREAM_MARKER] for i, n in enumerate(answers)]
        frames = {sid: [] for sid in sids}
        open_sids = set(sids)
        replies = 0
        if read == "stream_poll":
            for sid in sids:
                rep.stream_grant(sid, 16, "me")
        while open_sids:
            if read == "stream_next":
                sid = min(open_sids)
                got, done = rep.stream_next(sid, max_chunks=4)
                reply = {sid: (got, done, None)}
            else:
                reply = rep.stream_poll("me")
                del reply[REPLY_SENT]
            replies += 1
            for sid, (got, done, error) in reply.items():
                assert error is None and sid in open_sids
                frames[sid] += got
                if done:
                    open_sids.discard(sid)
        for sid, n in zip(sids, answers):
            assert [f for f in frames[sid] if "token" in f] and \
                len(frames[sid]) == n + 1 and frames[sid][-1]["done"]
        chunks = sum(len(f) for f in frames.values())
        # A caller with no stream left: a poll returns empty at its limit.
        assert rep.stream_poll("me") == {} and not rep._streams
        hist = rep.instance.engine_stats()["phase_hist"]
    finally:
        rep.instance.engine.stop()
        slo._reset_for_tests()
    for phase in ("engine_queue", "ttft", "stream_hold", "stream_pull"):
        assert hist[phase]["sum"] >= 0.0, phase
    assert hist["engine_queue"]["count"] == len(answers)
    assert hist["ttft"]["count"] == len(answers)
    # One hold a chunk: every token frame and each stream's last frame.
    assert hist["stream_hold"]["count"] == chunks == sum(answers) + 3
    # One pull a reply, the empty one too.
    assert hist["stream_pull"]["count"] == replies + 1


def test_a_resumed_request_is_not_a_second_arrival():
    """The queue wait ends at the FIRST admission: a preempted request
    keeps that stamp when it is admitted again."""
    eng = LLMEngine(PARAMS, CFG, num_blocks=6, block_size=8, max_batch=4)
    reqs = [eng.add_request([7] * 12, max_tokens=20, seed=i)
            for i in range(3)]
    assert all(r.admit_t is None for r in reqs)
    first = {}
    for _ in range(300):
        if not eng.step() and not eng.stats()["waiting"]:
            break
        for r in reqs:
            if r.admit_t is not None:
                assert first.setdefault(r.rid, r.admit_t) == r.admit_t
    assert sum(r.preemptions for r in reqs) > 0
    assert all(r.submit_t <= first[r.rid] <= r.first_token_t for r in reqs)


# ---------------------------------------------------------------------------
# The train step
# ---------------------------------------------------------------------------
def _session(**ctx):
    from ray_tpu.train import session as sess_mod

    s = sess_mod._TrainSession(sess_mod.TrainContext(**ctx))
    sess_mod._bind(s)
    return sess_mod, s


def test_wrap_step_splits_the_device_span_and_report_carries_it():
    perfmodel.clear_device_steps()
    t0 = time.time()
    sess_mod, s = _session(trial_name="split_t")
    try:
        step = sess_mod.wrap_step(jax.jit(lambda x: x * 2.0 + 1.0))
        x = jnp.ones((8, 8))
        for i in range(4):
            x = step(x)
            sess_mod.report({"i": i})
    finally:
        sess_mod._unbind()
    reports = [s.reports.get_nowait()[1] for _ in range(4)]
    assert "train_device_ms" not in reports[0]   # no step behind it yet
    for r in reports[1:]:
        assert r["train_dispatch_ms"] + r["train_ready_wait_ms"] == \
            pytest.approx(r["train_device_ms"], abs=1e-9)
        assert r["train_step_ms"] == pytest.approx(
            r["train_device_ms"] + r["train_host_gap_ms"], abs=1e-9)
        assert r["train_data_wait_ms"] == 0.0    # no dataset iterated
        assert "train_mfu" not in r              # no peak on the CPU
    ring = _ring("train.step", t0)
    assert len(ring) == 3 and all(e["trial"] == "split_t" for e in ring)
    # The probe's reading of a step reaches the report under the key the
    # benchmark's train reader takes.
    assert [r["train_standstill_ms"] for r in reports[1:]] == \
        [e["standstill_ms"] for e in ring]
    for e in ring:
        _check_partition(e)
        _check_interval(e)
        assert set(e["device_ms_by"]) == {"dispatch", "wait"}
        assert "train.report" in e["phases_ms"]
    perfmodel.clear_device_steps()


def test_a_dataset_shards_batches_are_the_steps_data_wait():
    from ray_tpu import data as rt_data

    perfmodel.clear_device_steps()
    t0 = time.time()
    rows = [{"tokens": np.arange(4, dtype=np.int32) + i} for i in range(12)]
    sess_mod, s = _session(datasets={"train": rt_data.from_items(rows)})
    try:
        step = sess_mod.wrap_step(jax.jit(lambda t: t.sum()))
        shard = sess_mod.get_dataset_shard("train")
        n = 0
        for batch in shard.iter_batches(batch_size=4, batch_format="jax"):
            step(batch["tokens"])
            sess_mod.report({"n": n})
            n += 1
        assert n == 3
        # Off a training loop's thread the iterator is the plain one.
        seen = []
        t = threading.Thread(target=lambda: seen.extend(
            shard.iter_batches(batch_size=6)))
        t.start()
        t.join(timeout=60)
        assert len(seen) == 2 and not t.is_alive()
    finally:
        sess_mod._unbind()
    reports = [s.reports.get_nowait()[1] for _ in range(3)]
    ring = _ring("train.step", t0)
    assert len(ring) == 2
    for r, e in zip(reports[1:], ring):
        assert r["train_data_wait_ms"] == \
            e["phases_ms"]["data.next_batch"] > 0.0
        _check_partition(e)
    perfmodel.clear_device_steps()


# ---------------------------------------------------------------------------
# Stable names on the device side
# ---------------------------------------------------------------------------
def _lowered(fn, *args):
    return fn.lower(*args).as_text(debug_info=True)


def test_program_names_in_the_lowered_text():
    import optax

    from ray_tpu.llm import kv_cache
    from ray_tpu.llm.engine import _jit_programs

    decode, chunk = _jit_programs(CFG)
    bs, nb, B = 8, 16, 2
    max_nb = CFG.max_seq // bs
    pool = jnp.zeros((CFG.n_layer, nb, bs, CFG.kv_heads * CFG.head_dim),
                     CFG.dtype)
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)
    def step(q):
        from ray_tpu.models import step_columns

        return decode.lower(
            PARAMS, i32(B, step_columns(q).table + max_nb), pool, pool,
            q=q).as_text(debug_info=True)

    texts = {
        "llm_decode": step(1),
        "llm_prefill_chunk": _lowered(chunk, PARAMS, i32(1, 8), pool, pool,
                                      i32(max_nb + 1 + 2)),
        "kv_scatter_blocks": _lowered(
            kv_cache.kv_scatter_blocks, pool, pool, pool[:, :2],
            pool[:, :2], i32(2)),
        "kv_copy_block": _lowered(kv_cache.kv_copy_block, pool, pool,
                                  jnp.int32(1), jnp.int32(2)),
    }
    opt = optax.adamw(1e-3)
    state = {"params": PARAMS, "opt_state": opt.init(PARAMS), "step": 0}
    texts["train_step"] = _lowered(
        gpt.make_train_step(CFG, opt, donate=False), state, i32(2, 16))
    for name, text in texts.items():
        assert f"module @jit_{name} " in text, name
    # The paged kernel's name rides its call even where the
    # interpreter stands in for it.
    assert "paged_decode" in texts["llm_decode"]
    # Under speculation (three rows a lane) the step is the same program.
    verify = step(3)
    assert "module @jit_llm_decode " in verify and "paged_decode" in verify


# ---------------------------------------------------------------------------
# The operator's reading: idle gaps by host span
# ---------------------------------------------------------------------------
D, H = "/device:TPU:0", "/host:CPU"


def _rows():
    """A hand-made trace, nanoseconds: device ops with four gaps, and
    the host's spans over them."""
    ops = [(0, 100), (200, 100), (400, 100), (900, 100), (1100, 50)]
    return [(D, "XLA Ops", f"%op.{i}", a, d) for i, (a, d) in enumerate(ops)] + [
        (D, "XLA Modules", "jit_llm_decode(1)", 0, 1150),   # not an op
        (D, "Steps", "0", 0, 1150),
        (H, "python", "llm.sample", 90, 120),       # gap 100-200: wholly
        (H, "python", "llm.emit", 300, 40),         # gap 300-400: 40 ...
        (H, "python", "llm.publish", 340, 70),      # ... and 60
        (H, "python", "PjitFunction(f)", 500, 400),  # gap 500-900: unnamed
        (H, "python", "llm.step", 0, 1150),   # has what its spans leave
        (H, "python", "llm.decode.device", 990, 200),   # gap 1000-1100
        (H, "other", "llm.decode.build", 1040, 20),     # innermost wins
    ]


def test_idle_gaps_wholly_inside_split_and_uncovered():
    from ray_tpu._private.profiler import format_idle_gaps, idle_gaps

    t = idle_gaps(_rows())
    ns = 1e-9
    assert t["idle_s"] == pytest.approx(700 * ns)
    assert t["by_phase_s"] == pytest.approx({
        "llm.sample": 100 * ns, "llm.publish": 60 * ns,
        "llm.emit": 40 * ns, "llm.decode.device": 80 * ns,
        "llm.decode.build": 20 * ns,
        # Inside the step, under none of its spans: its other_ms.
        "llm.step": 400 * ns})
    assert t["uncovered_s"] == pytest.approx(0.0, abs=1e-15)
    assert sum(t["by_phase_s"].values()) + t["uncovered_s"] == \
        pytest.approx(t["idle_s"])
    longest = {round(g[0] * 1e6): g[1:] for g in t["longest"]}
    assert longest[400] == ["llm.step", 1.0]
    assert longest[100][0] in ("llm.sample", "llm.publish",
                               "llm.decode.device")
    split = [g for g in t["longest"] if g[1] == "llm.publish"]
    assert split and split[0][2] == pytest.approx(0.6)
    text = format_idle_gaps(t)
    assert "llm.sample" in text and "(no named span)" in text
    assert "llm.step (no phase)" in text
    # Only the names asked for count; no device plane, no gap.
    only = idle_gaps(_rows(), names={"llm.sample"})
    assert set(only["by_phase_s"]) == {"llm.sample"}
    assert only["uncovered_s"] == pytest.approx(600 * ns)
    host_only = idle_gaps([r for r in _rows() if r[0] == H])
    assert host_only["idle_s"] == 0.0 and host_only["longest"] == []
    assert "no idle gap" in format_idle_gaps(host_only)


def test_idle_gaps_name_the_gap_between_steps_and_a_spans_halves():
    """A device gap between two steps lies under llm.between (under
    llm.idle where the loop slept inside it), one inside a device span
    under its dispatch or wait half, one inside a collection under
    py.gc whatever phase it interrupts."""
    from ray_tpu._private.profiler import idle_gaps

    rows = [(D, "XLA Ops", f"%op.{i}", a, d) for i, (a, d) in enumerate(
        [(0, 100), (300, 100), (1000, 100), (1300, 100), (1600, 100)])] + [
        (H, "python", "llm.step", 0, 150),
        (H, "python", "llm.between", 150, 100),         # gap 100-300
        (H, "python", "llm.step", 250, 300),
        (H, "python", "llm.between", 550, 400),         # gap 400-1000 ...
        (H, "python", "llm.idle", 600, 300),            # ... slept 300 of it
        (H, "python", "llm.decode.device", 1050, 300),  # gap 1100-1300
        (H, "python", "llm.decode.dispatch", 1050, 120),
        (H, "python", "llm.decode.wait", 1170, 180),
        (H, "python", "llm.emit", 1390, 220),           # gap 1400-1600
        (H, "other thread", "py.gc", 1450, 100),
    ]
    t = idle_gaps(rows)
    ns = 1e-9
    assert t["idle_s"] == pytest.approx(1200 * ns)
    assert t["by_phase_s"] == pytest.approx({
        "llm.step": (50 + 50 + 150) * ns,
        "llm.between": (100 + 100) * ns, "llm.idle": 300 * ns,
        "llm.decode.dispatch": 70 * ns, "llm.decode.wait": 130 * ns,
        "llm.emit": 100 * ns, "py.gc": 100 * ns})
    assert t["uncovered_s"] == pytest.approx(50 * ns)


def test_idle_gaps_name_the_serving_thread_that_ran_in_a_gap():
    """A device gap in which the engine's thread stood between two
    steps while the proxy's loop wrote a reply's frames lies under
    ``serve.flush`` for as long as that ran: the innermost span has the
    instant, whichever thread it is on."""
    from ray_tpu._private.profiler import idle_gaps

    rows = [(D, "XLA Ops", "%op.0", 0, 100), (D, "XLA Ops", "%op.1", 600, 100),
            (H, "llm-engine", "llm.step", 0, 150),
            (H, "llm-engine", "llm.between", 150, 400),     # gap 100-600
            (H, "serve-http", "serve.flush", 300, 120),
            (H, "actor", "serve.stream_poll", 200, 60),
            (H, "actor", "serve.handle_request", 700, 50)]  # under an op
    t = idle_gaps(rows)
    ns = 1e-9
    assert t["idle_s"] == pytest.approx(500 * ns)
    assert t["by_phase_s"] == pytest.approx({
        "llm.step": 50 * ns, "serve.stream_poll": 60 * ns,
        "serve.flush": 120 * ns, "llm.between": (50 + 40 + 130) * ns})
    assert t["uncovered_s"] == pytest.approx(50 * ns)   # 550-600


def test_device_steps_table_ends_with_the_longest_intervals():
    from ray_tpu._private.profiler import format_device_steps

    perfmodel.clear_device_steps()
    t0 = time.time()
    eng = LLMEngine(PARAMS, CFG, num_blocks=32, block_size=8, max_batch=4,
                    prefill_chunk_tokens=8, name="longest_test")
    eng.add_request([5] * 20, max_tokens=8)
    _drain(eng)
    ring = _ring("llm.step", t0)
    perfmodel.clear_device_steps()
    slow = dict(ring[-1], interval_ms=99999.0, between_ms=900.0,
                lock_wait_ms=12.5, idle_ms=800.0, gc_ms=30.0, gc_max_ms=30.0,
                gc_gen=2, stall_ms=45.0, arrived=3,
                cpu_ms=21.5, standstill_ms=1200.0, held_long_ms=0.0,
                interp_n=3, interp_held_n=1, interp_late_ms=1200.5,
                interp_late_max_ms=1200.0,
                phases_ms=dict(ring[-1]["phases_ms"], **{"llm.emit": 18.25}))
    lines = format_device_steps(ring + [slow]).splitlines()
    head = next(i for i, ln in enumerate(lines)
                if "longest intervals" in ln)
    assert "between steps" in lines[head] and "stall" in lines[head]
    table = lines[head + 1:]
    assert len(table) == 5 == len(lines) - head - 1
    assert table[0].split()[0] == "99999.0"
    assert "between 900.0 (lock 12.5, idle 800.0)" in table[0]
    assert "llm.emit 18.2" in table[0] or "llm.emit 18.3" in table[0]
    assert "; gc 30.0 (gen 2); cpu 21.5, stall 45.0; standstill 1200.0, " \
        "held long 0.0; " in table[0]
    (probe,) = [ln for ln in lines[:head] if "interpreter probe" in ln]
    assert "stood still 1200.0 ms" in probe
    assert table[0].endswith(f"lanes {slow['lanes']}, chunk tokens "
                             f"{slow['prefill_tokens']}, arrived 3")
    d = slow["dispatch_ms_by"]["decode"]
    assert f"decode {slow['device_ms_by']['decode']:.1f} ({d:.1f}/" in table[0]
    # Longest first; an interval without a collection names none.
    firsts = [float(ln.split()[0]) for ln in table]
    assert firsts == sorted(firsts, reverse=True)
    assert any("gc " not in ln for ln in table[1:])
    # Entries of a program from before the interval was timed: none.
    old = [{k: v for k, v in e.items() if k != "interval_ms"} for e in ring]
    assert "longest intervals" not in format_device_steps(old)


def test_device_steps_table_splits_the_step_and_sums_the_counts():
    """`rtpu profile --device` prints a window's ring entries: means of
    the split, sums of the engine's counts."""
    from ray_tpu._private.profiler import format_device_steps

    perfmodel.clear_device_steps()
    t0 = time.time()
    eng = LLMEngine(PARAMS, CFG, num_blocks=8, block_size=8, max_batch=4,
                    prefill_chunk_tokens=8, name="table_test")
    reqs = [eng.add_request([5] * 12, max_tokens=12, seed=i)
            for i in range(3)]
    _drain(eng)
    ring = _ring("llm.step", t0)
    perfmodel.clear_device_steps()
    text = format_device_steps(ring + [
        {"name": "train.step", "t_wall": t0, "trial": "t1", "step_ms": 10.0,
         "device_ms": 8.0, "host_gap_ms": 2.0, "other_ms": 0.5,
         "device_ms_by": {"dispatch": 1.0, "wait": 7.0},
         "phases_ms": {"data.next_batch": 1.5}}])
    head, phases, counts, probe, gaps, *longest, train, train_phases = \
        text.splitlines()
    assert probe.lstrip().startswith("interpreter probe: ") \
        and "stood still" in probe
    assert head.startswith(f"  llm.step x {len(ring)} (table_test): ")
    assert "decode " in head and "prefill " in head
    assert "[dispatch " in head
    assert phases.lstrip().startswith("host by phase: ")
    assert gaps.lstrip().startswith("between steps ") and len(longest) == 5
    assert set(HOST_PHASES) >= {
        w for w in phases.replace(",", " ").split() if w.startswith("llm.")}
    assert f"decode {sum(e['decode_tokens'] for e in ring)}, " in counts
    assert f"prefill {sum(e['prefill_tokens'] for e in ring)} in " in counts
    assert f"waiting {max(e['waiting'] for e in ring)} at most" in counts
    n_preempted = sum(r.preemptions for r in reqs)
    programs = sum(e["programs"] for e in ring)
    assert n_preempted > 0 and counts.endswith(
        f"preempted {n_preempted}; programs {programs}, "
        f"{sum(e['programs_queued'] for e in ring)} queued before their "
        f"step's first wait")
    assert programs == len(ring) + sum(len(e["prefill_chunks"])
                                       for e in ring) - sum(
        e["decode_tokens"] == 0 for e in ring)
    assert train == ("  train.step x 1 (t1): 10.00 ms a step = device 8.00 "
                     "(wait 7.00, dispatch 1.00) + host 2.00")
    assert train_phases == \
        "    host by phase: data.next_batch 1.50, other 0.50"
    assert format_device_steps([]) == ""


def test_trace_events_keep_named_spans_and_device_lines():
    from ray_tpu._private.profiler import _trace_events, build_merged_trace

    events = _trace_events(_rows(), t0_wall=1000.0)
    names = {e["name"] for e in events if e["ph"] == "X"}
    assert {"llm.step", "llm.sample", "llm.decode.device", "%op.0",
            "jit_llm_decode(1)"} <= names
    assert "PjitFunction(f)" not in names       # not the program's own
    first = next(e for e in events if e["name"] == "%op.1")
    assert first["ts"] == pytest.approx(1000.0 * 1e6 + 0.2)
    merged = build_merged_trace({"node:abc": {
        "t0_wall": 1000.0, "t1_wall": 1001.0, "host": {},
        "device_steps": [], "jax_trace": {"events": events}}})
    assert {"llm.step", "%op.0"} <= {
        e.get("name") for e in merged["traceEvents"]}


def test_a_profiler_session_holds_the_step_and_its_phases_by_name():
    """One CPU jax.profiler session (python tracer off) over a few
    engine steps: the spans lie on the trace's host plane under the
    registry's names. Bounded: the session runs on a thread that must
    end in time."""
    from ray_tpu._private import profiler
    from ray_tpu.serve.deployment import Router

    eng = LLMEngine(PARAMS, CFG, num_blocks=32, block_size=8, max_batch=4,
                    prefill_chunk_tokens=8)
    eng.add_request(list(range(1, 20)), max_tokens=3)
    eng.step()                      # compile outside the session
    result = {}

    def session():
        import shutil
        import tempfile

        tmp = tempfile.mkdtemp(prefix="rtpu-timeline-test-")
        try:
            profiler._start_xla_trace(tmp)
            try:
                eng.step()
                gc.collect()        # a pass inside the session
                _drain(eng)
                # A serving thread's body, as the proxy's loop runs it.
                Router("in_session")._flush([], time.time())
            finally:
                jax.profiler.stop_trace()
            result["rows"], result["start_wall"] = \
                profiler.read_xplane(tmp)
        finally:
            shutil.rmtree(tmp, ignore_errors=True)

    t = threading.Thread(target=session, daemon=True)
    t.start()
    t.join(timeout=120)
    assert not t.is_alive() and "rows" in result
    # The trace records its own start on the wall clock: the anchor of
    # its rows in the merged export.
    assert abs(result["start_wall"] - time.time()) < 600
    host = {name for plane, _, name, _, _ in result["rows"]
            if plane.startswith("/host:")}
    assert "llm.step" in host
    assert {"llm.admit", "llm.prefill.host", "llm.prefill.device",
            "llm.slots", "llm.decode.build", "llm.decode.device",
            "llm.sample", "llm.emit", "llm.publish"} <= host
    # The accounting's own intervals: the gap between two steps, the
    # collector's pass, both halves of each device span.
    assert {"llm.between", "py.gc", "llm.decode.dispatch",
            "llm.decode.wait", "llm.prefill.dispatch",
            "llm.prefill.wait"} <= host
    assert "llm.idle" not in host        # nobody slept: no loop
    assert "serve.flush" in host         # the serving side's, by name
    # On the CPU backend no device plane exists: nothing to attribute.
    assert profiler.idle_gaps(result["rows"])["idle_s"] == 0.0
