"""Tracing spans (context propagated through task specs) and profiling
hooks (cluster-wide stack dumps, memory summary).

Parity models: /root/reference/python/ray/util/tracing/
tracing_helper.py (submit/execute spans with spec-carried context),
`ray stack` and `ray memory` (python/ray/scripts/scripts.py).
"""

import os

import pytest

import ray_tpu
from ray_tpu.util import tracing


# The ``traced`` fixture (conftest.py) brackets each test with
# enable_tracing()/disable_tracing() + register/unregister_exporter.


def test_span_nesting_and_context():
    with tracing.span("outer") as outer:
        with tracing.span("inner") as inner:
            assert inner.trace_id == outer.trace_id
            assert inner.parent_id == outer.span_id
    spans = tracing.drain_local_spans()
    names = {s["name"] for s in spans}
    assert {"outer", "inner"} <= names


def test_task_spans_link_submit_to_execute(traced):
    @ray_tpu.remote
    def traced_task(x):
        return x + 1

    assert ray_tpu.get(traced_task.remote(1), timeout=60) == 2
    spans = tracing.get_spans()
    submits = [s for s in spans if s["name"].endswith("::submit")]
    execs = [s for s in spans if s["name"].endswith("::execute")]
    assert submits and execs
    # The execute span is a child of the submit span, same trace.
    sub = submits[-1]
    ex = [s for s in execs if s["parent_id"] == sub["span_id"]]
    assert ex and ex[0]["trace_id"] == sub["trace_id"]
    assert ex[0]["pid"] != os.getpid()  # ran in the worker process


def test_device_lane_spans(traced):
    @ray_tpu.remote(scheduling_strategy="device")
    def dev_task():
        return 7

    assert ray_tpu.get(dev_task.remote(), timeout=60) == 7
    spans = tracing.get_spans()
    ex = [s for s in spans if s["name"] == "task::dev_task::execute"]
    assert ex and ex[0]["attributes"].get("lane") == "device"


def test_chrome_trace_export(traced, tmp_path):
    @ray_tpu.remote
    def t():
        return 1

    ray_tpu.get(t.remote(), timeout=60)
    out = str(tmp_path / "spans.json")
    n = tracing.export_chrome_trace(out)
    assert n >= 2
    import json

    events = json.load(open(out))
    assert all(e["ph"] == "X" and "dur" in e for e in events)


def test_failed_task_span_records_error(traced):
    @ray_tpu.remote(max_retries=0)
    def boom():
        raise ValueError("kapow")

    with pytest.raises(ray_tpu.TaskError):
        ray_tpu.get(boom.remote(), timeout=60)
    spans = tracing.get_spans()
    ex = [s for s in spans if s["name"] == "task::boom::execute"]
    assert ex and "kapow" in ex[-1]["attributes"].get("error", "")


def test_nested_tasks_share_trace(traced):
    @ray_tpu.remote
    def inner(x):
        return x * 2

    @ray_tpu.remote
    def outer(x):
        import ray_tpu as rt
        return rt.get(inner.remote(x))

    assert ray_tpu.get(outer.remote(3), timeout=90) == 6
    spans = tracing.get_spans()
    out_ex = next(s for s in spans if s["name"] == "task::outer::execute")
    inner_spans = [s for s in spans
                   if s["name"].startswith("task::inner")
                   and s["trace_id"] == out_ex["trace_id"]]
    # The worker-side nested submit + its execute ride the same trace.
    assert len(inner_spans) >= 2


def test_actor_call_spans_link_submit_to_execute(traced):
    @ray_tpu.remote
    class Traced:
        def poke(self, x):
            return x + 1

    a = Traced.remote()
    assert ray_tpu.get(a.poke.remote(1), timeout=60) == 2
    spans = tracing.get_spans()
    # Actor creation carries a submit span like a plain task.
    assert any(s["name"] == "task::Traced.__init__::submit"
               for s in spans)
    subs = [s for s in spans if s["name"] == "task::Traced.poke::submit"]
    execs = [s for s in spans
             if s["name"] == "task::Traced.poke::execute"]
    assert subs and execs
    ex = [s for s in execs if s["parent_id"] == subs[-1]["span_id"]]
    assert ex and ex[0]["trace_id"] == subs[-1]["trace_id"]
    assert ex[0]["pid"] != os.getpid()  # ran in the actor's worker


def test_driver_task_subtask_parentage_chain(traced):
    """Driver span -> task -> nested subtask: the full submit/execute
    parentage chain survives the worker-span flusher plane."""
    import time

    @ray_tpu.remote
    def leaf(x):
        return x + 1

    @ray_tpu.remote
    def mid(x):
        import ray_tpu as rt
        return rt.get(leaf.remote(x))

    with tracing.span("driver_root") as root:
        ref = mid.remote(5)
        root_trace = root.trace_id
    assert ray_tpu.get(ref, timeout=90) == 6

    # Worker spans reach the node tables on the 1s flusher: poll.
    spans: list = []
    deadline = time.monotonic() + 15
    while time.monotonic() < deadline:
        spans = tracing.get_spans()
        if any(s["name"] == "task::leaf::execute" for s in spans):
            break
        time.sleep(0.2)
    by_id = {s["span_id"]: s for s in spans}
    leaf_ex = next(s for s in spans
                   if s["name"] == "task::leaf::execute")
    chain = [leaf_ex["name"]]
    cur = leaf_ex
    while cur.get("parent_id") and cur["parent_id"] in by_id:
        cur = by_id[cur["parent_id"]]
        chain.append(cur["name"])
    assert chain == ["task::leaf::execute", "task::leaf::submit",
                     "task::mid::execute", "task::mid::submit",
                     "driver_root"], chain
    assert all(by_id[s]["trace_id"] == root_trace
               for s in by_id if by_id[s]["name"] in chain)


def test_tracing_off_records_nothing(rt):
    @ray_tpu.remote
    def quiet():
        return 1

    ray_tpu.get(quiet.remote(), timeout=60)
    assert tracing.local_spans() == []


def test_cluster_stacks(rt):
    @ray_tpu.remote
    def warm():
        return 1

    ray_tpu.get(warm.remote(), timeout=60)  # ensure a worker exists
    stacks = rt.cluster_stacks()
    assert any(k.startswith("node:") for k in stacks)
    assert any(k.startswith("worker:") for k in stacks)
    node_stack = next(v for k, v in stacks.items() if k.startswith("node:"))
    assert "thread" in node_stack


def test_memory_cli_shape(rt, capsys):
    ref = ray_tpu.put(b"x" * 300_000)  # noqa: F841 - keeps the object live
    from ray_tpu.scripts.cli import cmd_memory

    class A:
        address = None

    cmd_memory(A())
    out = capsys.readouterr().out
    assert "object(s) cluster-wide" in out
    assert "node " in out


# ---------------------------------------------------------------------------
# Sampling profiler + flamegraph (reference: dashboard profile_manager
# py-spy/memray surface — VERDICT r3 item 10)
# ---------------------------------------------------------------------------
def test_sample_profile_catches_hot_function():
    import threading

    from ray_tpu._private.profiler import (render_flamegraph_svg,
                                           sample_profile)

    stop = threading.Event()

    def hot_spin_loop_xyz():
        while not stop.wait(0.0005):
            sum(i * i for i in range(200))

    t = threading.Thread(target=hot_spin_loop_xyz, daemon=True)
    t.start()
    try:
        prof = sample_profile(duration_s=0.8, hz=200)
    finally:
        stop.set()
        t.join()
    assert prof["samples"] > 50
    assert "hot_spin_loop_xyz" in prof["folded"], prof["folded"][:500]
    svg = render_flamegraph_svg(prof["folded"])
    assert svg.startswith("<svg") and "hot_spin_loop_xyz" in svg


def test_cluster_profile_covers_workers(rt):
    import ray_tpu

    @ray_tpu.remote
    def busy_worker_fn_abc(sec):
        import time
        t0 = time.monotonic()
        x = 0
        while time.monotonic() - t0 < sec:
            x += sum(i for i in range(500))
        return x

    ref = busy_worker_fn_abc.remote(4.0)
    import time
    time.sleep(1.0)  # the worker is mid-task
    from ray_tpu._private import context as context_mod

    profs = context_mod.require_context().cluster_profile(duration_s=1.5)
    ray_tpu.get(ref, timeout=60)
    worker_keys = [k for k in profs if k.startswith("worker:")]
    assert worker_keys, profs.keys()
    merged = "\n".join(p.get("folded", "") for p in profs.values()
                       if isinstance(p, dict))
    assert "busy_worker_fn_abc" in merged, merged[:800]


# ---------------------------------------------------------------------------
# Gang-coordinated device capture (`rtpu profile --device`): every
# process returns one window of accounted device steps + host timeline;
# the driver aligns clocks and merges into one Chrome trace.
# ---------------------------------------------------------------------------
def test_cluster_device_profile_merges_processes(rt, tmp_path):
    import json
    import os
    import threading
    import time

    import ray_tpu
    from ray_tpu._private.profiler import build_merged_trace
    from ray_tpu.util import perfmodel

    @ray_tpu.remote
    def stepper_xyz(started, stop):
        # A worker acting like an engine: accounted device steps land
        # in its process-local ring from the moment it says it has
        # started until it is told to stop.
        import os as _os
        import time as _t

        from ray_tpu.util import perfmodel as pm

        t0 = _t.monotonic()
        n = 0
        while not _os.path.exists(stop) and _t.monotonic() - t0 < 120:
            pm.record_device_step(
                "llm.step", _t.time(),
                {"step_ms": 2.0, "device_ms": 1.5, "host_gap_ms": 0.5,
                 "mfu": 0.3, "hbm_util": 0.2, "verdict": "compute"},
                {"deployment": "capture_test"})
            n += 1
            if n == 1:
                open(started, "w").close()
            _t.sleep(0.05)
        return n

    # The capture fans out to the workers that are ALIVE: wait for the
    # worker's word that it is stepping (it takes seconds to start
    # beside five busy xdist workers; a half-second sleep here was why
    # this test failed under load), and hold it there until the window
    # has been taken.
    started, stop = str(tmp_path / "started"), str(tmp_path / "stop")
    perfmodel.clear_device_steps()
    ref = stepper_xyz.remote(started, stop)
    deadline = time.monotonic() + 120
    while not os.path.exists(started):
        assert time.monotonic() < deadline, "the worker never stepped"
        time.sleep(0.05)
    # The driver/node process steps too (train-session shape), through
    # the window.
    driver_done = threading.Event()

    def driver_steps():
        while not driver_done.wait(0.05):
            perfmodel.record_device_step(
                "train.step", time.time(),
                {"step_ms": 10.0, "device_ms": 8.0}, {"trial": "t0"})

    stepping = threading.Thread(target=driver_steps, daemon=True)
    stepping.start()
    try:
        profs = rt.cluster_device_profile(duration_s=1.0, hz=50.0)
    finally:
        driver_done.set()
        open(stop, "w").close()
        stepping.join(timeout=30)
    offsets = rt.clock_offsets()
    assert ray_tpu.get(ref, timeout=60) > 0

    captured = {k: v for k, v in profs.items()
                if isinstance(v, dict) and "t0_wall" in v}
    assert any(k.startswith("node:") for k in captured), profs.keys()
    assert any(k.startswith("worker:") for k in captured), profs.keys()
    with_steps = [k for k, v in captured.items() if v["device_steps"]]
    assert len(with_steps) >= 2, (
        "expected accounted steps from >= 2 processes",
        {k: len(v["device_steps"]) for k, v in captured.items()})
    # Every captured process says which of its threads had the cores
    # over the window, as `rtpu profile --device` prints it.
    from ray_tpu._private.profiler import format_thread_cpu

    for source, prof in captured.items():
        a, b = prof["threads"]
        assert b["process_cpu_s"] >= a["process_cpu_s"], source
        assert "MainThread" in b["by_group"], source
        assert format_thread_cpu(a, b).lstrip().startswith("CPU by thread")
    # Single host: every node offset must be 0 by construction.
    assert offsets and all(off == 0.0 for off in offsets.values())

    merged = build_merged_trace(profs, offsets)
    evs = merged["traceEvents"]
    pids_with_steps = {e["pid"] for e in evs
                       if e.get("name") == "llm.step"} | \
                      {e["pid"] for e in evs
                       if e.get("name") == "train.step"}
    assert len(pids_with_steps) >= 2, "steps from >= 2 merged processes"
    # Step slices carry the breakdown and land on the Chrome schema.
    step_ev = next(e for e in evs if e.get("name") == "llm.step")
    assert step_ev["ph"] == "X" and step_ev["dur"] > 0
    assert step_ev["args"]["deployment"] == "capture_test"
    assert step_ev["args"]["verdict"] == "compute"
    names = {e["args"]["name"] for e in evs
             if e.get("name") == "thread_name"}
    assert "device-steps" in names and "host-cpu" in names
    json.dumps(merged)  # one serializable Chrome/Perfetto export
    perfmodel.clear_device_steps()


def test_build_merged_trace_applies_clock_offsets_and_spans():
    """Per-host wall-clock offsets shift that host's events onto the
    driver's clock; request spans ride on their own track."""
    from ray_tpu._private.profiler import build_merged_trace

    base = 1000.0
    prof = {"t0_wall": base, "t1_wall": base + 1.0,
            "host": {"timeline": [[base + 0.5, "leaf_fn (m.py:1)"]]},
            "device_steps": [
                {"name": "llm.step", "t_wall": base + 0.1,
                 "step_ms": 4.0, "device_ms": 3.0, "verdict": "hbm"}],
            "jax_trace": {"error": "disabled"}}
    spans = [{"trace_id": "aabbccdd" * 4, "name": "serve.request",
              "start": base + 0.05, "end": base + 0.30,
              "attributes": {"deployment": "d"}}]
    merged = build_merged_trace(
        {"node:aaaabbbbcccc": prof, "worker:ddddeeee:7": prof},
        offsets={"aaaabbbbcccc": 0.25, "ddddeeee": -0.5}, spans=spans)
    evs = merged["traceEvents"]
    steps = sorted(e["ts"] for e in evs if e.get("name") == "llm.step")
    # node shifted +0.25s, worker -0.5s from the same t_wall.
    assert steps == [pytest.approx((base + 0.1 - 0.5) * 1e6),
                     pytest.approx((base + 0.1 + 0.25) * 1e6)]
    hbm_ev = next(e for e in evs if e.get("name") == "llm.step")
    assert hbm_ev["cname"] == "thread_state_iowait"  # hbm verdict color
    span_ev = next(e for e in evs if e.get("name") == "serve.request")
    assert span_ev["dur"] == pytest.approx(0.25 * 1e6)
    assert span_ev["args"]["trace_id"] == "aabbccdd" * 4
    leafs = [e for e in evs if e.get("name") == "leaf_fn (m.py:1)"]
    assert len(leafs) == 2  # one host-cpu slice per process


def test_heap_snapshot_reports_allocations():
    import tracemalloc

    from ray_tpu._private.profiler import heap_snapshot

    try:
        first = heap_snapshot()
        keep = [bytearray(256_000) for _ in range(20)]  # ~5MB live
        snap = heap_snapshot(top_n=10)
        del keep
        assert not snap.get("started", False) or first["started"]
        if not snap.get("started"):
            assert snap["current_kb"] > 1000
            assert snap["top"], snap
    finally:
        # tracemalloc taxes every later allocation in this process —
        # never leave it on for the rest of the suite (the perf-floor
        # gate runs in the same interpreter).
        tracemalloc.stop()
