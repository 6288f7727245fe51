"""The interpreter's readers (PR 60): the probe that says whether a
thread that woke with work to do could have had the interpreter
(util/perfmodel.py ``_InterpreterProbe``), and the process's CPU by
thread group (_private/profiler.py ``thread_cpu``).

The probe is a clock by nature, so these assert SHARES that hold on a
loaded box (a spinning thread keeps the interpreter for a switch
interval whatever else runs; a sleeping one never has it), each taken
as the best of a few tries where load can only push it one way."""

import hashlib
import json
import os
import signal
import subprocess
import sys
import threading
import time

import pytest

from ray_tpu._private import profiler
from ray_tpu.util import perfmodel
from ray_tpu.util.perfmodel import StepAccounting


@pytest.fixture(autouse=True)
def _probe_running():
    StepAccounting().begin()        # the first step starts the probe
    assert perfmodel._PROBE.thread.is_alive()


def _beside(body, seconds=0.6):
    """(samples, held share, mean lateness s) of the probe over
    ``seconds`` in which a thread runs ``body(stop)``."""
    stop = threading.Event()
    thread = threading.Thread(target=body, args=(stop,), daemon=True)
    a = perfmodel.interp_totals()
    thread.start()
    time.sleep(seconds)
    b = perfmodel.interp_totals()
    stop.set()
    thread.join(timeout=30)
    assert not thread.is_alive()
    n = b["n"] - a["n"]
    assert n >= 3, "the probe took no samples"
    return n, (b["held_n"] - a["held_n"]) / n, (b["late_s"] - a["late_s"]) / n


def _spin(stop):
    n = 0
    while not stop.is_set():
        n += 1


def _hash(stop):
    buf = b"x" * (32 << 20)
    while not stop.is_set():
        hashlib.sha256(buf).digest()     # releases the interpreter


def _sleep(stop):
    while not stop.is_set():
        time.sleep(0.005)


def test_a_spinning_python_thread_holds_the_interpreter_for_the_probe():
    """A thread that never blocks gives the interpreter up only when
    asked, a switch interval after the probe woke; a sleeping one never
    has it. What a neighbour's load adds, it adds to both, so the
    assertion is their ORDER in one try of three (a level here, 80%
    held and under six switch intervals late, was the neighbours'
    reading, not the probe's: ROADMAP.md C11)."""
    tries = []
    for _ in range(3):
        _, held, late = _beside(_spin)
        _, idle_held, idle_late = _beside(_sleep)
        tries.append((held, idle_held, late, idle_late))
        if held > idle_held and late > idle_late:
            return
    pytest.fail(f"no try of three had the spinning thread's held share "
                f"and lateness above the sleeping one's: {tries}")


@pytest.mark.parametrize("body", [_hash, _sleep], ids=["hashlib", "sleep"])
def test_a_thread_busy_outside_the_interpreter_leaves_it_to_the_probe(body):
    """Busy in a call that releases the interpreter, or asleep: the
    probe has it when it wakes. Load can only make a sample later, so
    the best of three windows is the reading."""
    best = min(_beside(body)[1] for _ in range(3))
    assert best < 0.2, best


def test_a_step_entry_carries_what_the_probe_saw_since_the_last_one():
    acc = StepAccounting()
    acc.begin()
    acc.add_device(1e-3)
    # Totals on BOTH sides of each finish: the probe samples on while
    # this thread waits for a core, so the entry's count lies between
    # the inner and the outer difference.
    a = perfmodel.interp_totals()
    acc.finish()
    a_in = perfmodel.interp_totals()
    time.sleep(10 * perfmodel.INTERP_PERIOD_S)
    acc.begin()
    acc.add_device(1e-3)
    b_in = perfmodel.interp_totals()
    out = acc.finish()
    b = perfmodel.interp_totals()
    assert 3 <= b_in["n"] - a_in["n"] <= out["interp_n"] <= b["n"] - a["n"]
    assert out["interp_late_ms"] >= out["interp_late_max_ms"] >= 0.0
    assert 0 <= out["interp_held_n"] <= out["interp_n"]
    assert out["standstill_ms"] + out["held_long_ms"] <= \
        out["interp_late_ms"] + 1e-9
    # Cumulative and consistent: one tuple a sample.
    assert b["late_s"] >= a["late_s"] and b["period_s"] == \
        perfmodel.INTERP_PERIOD_S


_CHILD = """
import json, sys, time
from ray_tpu.util import perfmodel
perfmodel.StepAccounting().begin()
time.sleep(0.2)
print("ready", flush=True)
sys.stdin.readline()
time.sleep(0.1)
print(json.dumps(perfmodel.interp_totals()), flush=True)
"""


def test_a_stopped_process_is_a_standstill_not_a_held_interpreter():
    """SIGSTOP for 0.3 s: the probe wakes ~300 ms late and the process's
    CPU clock has not moved, so the lateness is the machine's."""
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(sys.path))
    child = subprocess.Popen(
        [sys.executable, "-c", _CHILD], stdin=subprocess.PIPE,
        stdout=subprocess.PIPE, text=True, env=env)
    try:
        assert child.stdout.readline().strip() == "ready"
        os.kill(child.pid, signal.SIGSTOP)
        time.sleep(0.3)
        os.kill(child.pid, signal.SIGCONT)
        child.stdin.write("\n")
        child.stdin.flush()
        totals = json.loads(child.stdout.readline())
    finally:
        child.kill()
        child.wait(timeout=30)
    assert totals["standstill_s"] >= 0.2, totals
    assert totals["held_long_s"] == 0.0, totals
    assert totals["held_n"] >= 1


def test_a_long_hold_by_a_thread_of_the_process_is_not_a_standstill():
    """A C call that keeps the interpreter for 150 ms while the process
    burns CPU: late like a standstill, but the process's clock ran."""
    a = perfmodel.interp_totals()
    for _ in range(3):
        # A big-int power keeps the interpreter inside one bytecode.
        t0 = time.perf_counter()
        n = 200_000
        while time.perf_counter() - t0 < 0.15:
            pow(3, n)
            n *= 2
        time.sleep(2 * perfmodel.INTERP_PERIOD_S)
        b = perfmodel.interp_totals()
        if b["held_long_s"] > a["held_long_s"]:
            break
    assert b["held_long_s"] - a["held_long_s"] >= perfmodel.INTERP_LONG_S, b


# ---------------------------------------------------------------------------
# CPU by thread
# ---------------------------------------------------------------------------

def test_the_thread_table_groups_by_name_and_only_grows():
    stop = threading.Event()
    named = [threading.Thread(target=_spin, args=(stop,), daemon=True,
                              name=name)
             for name in ("actor-1a2b3c4d_0", "actor-1a2b3c4d_1",
                          "llm-engine-LLMServer", "serve-feed-7")]
    # Threads that tests before this one left in the worker (an engine's
    # daemon loop outlives ``serve.shutdown()``: ROADMAP.md C12) are in
    # the table too: count what THIS test starts.
    before = profiler.thread_cpu()["by_group"]
    for t in named:
        t.start()
    try:
        time.sleep(0.1)
        # The process's clock on BOTH sides of each reading: a reading
        # opens a file a task while the spinners run on, so the table's
        # growth lies between the inner and the outer difference,
        # however long a loaded box makes a reading take.
        cpu0 = time.process_time()
        a = profiler.thread_cpu()
        cpu1 = time.process_time()
        # A window in the process's own seconds, not the wall's.
        deadline = time.monotonic() + 30
        while time.process_time() - cpu1 < 0.5 and time.monotonic() < deadline:
            time.sleep(0.05)
        cpu2 = time.process_time()
        b = profiler.thread_cpu()
        cpu3 = time.process_time()
    finally:
        stop.set()
        for t in named:
            t.join(timeout=30)
    groups = b["by_group"]
    started = lambda g: groups[g]["threads"] \
        - before.get(g, {"threads": 0})["threads"]
    assert started("actor") == 2
    assert started("llm-engine") == 1
    assert groups["MainThread"]["threads"] == 1
    assert started("other") >= 1                    # serve-feed-7
    assert "native" not in groups or groups["native"]["threads"] >= 0
    for group, now in groups.items():
        was = a["by_group"].get(group, {"cpu_s": 0.0})
        assert now["cpu_s"] >= was["cpu_s"], group

    def total(t):
        return sum(g["cpu_s"] for g in t["by_group"].values())

    grew, inner, outer = total(b) - total(a), cpu2 - cpu1, cpu3 - cpu0
    assert inner >= 0.5
    # A running task's record lags the process's clock by up to a tick
    # (10 ms) a thread, at either reading.
    assert inner - 0.06 <= grew <= outer + 0.06, (inner, grew, outer)
    # The spinners took it between them; where the kernel keeps
    # schedstat the wait for a core is a number too.
    spun = sum(groups[g]["cpu_s"] - a["by_group"][g]["cpu_s"]
               for g in ("actor", "llm-engine", "other"))
    assert spun > 0.6 * grew
    if os.path.exists("/proc/self/schedstat"):
        assert groups["actor"]["wait_s"] is not None
    # Ended threads keep what they had used: no group shrinks.
    c = profiler.thread_cpu()
    for group, was in groups.items():
        assert c["by_group"][group]["cpu_s"] >= was["cpu_s"] - 1e-9, group
    text = profiler.format_thread_cpu(a, b)
    assert text.lstrip().startswith("CPU by thread: ")
    assert "actor" in text and "llm-engine" in text


# ---------------------------------------------------------------------------
# The serving threads' annotations
# ---------------------------------------------------------------------------

def test_no_annotation_is_built_on_a_serving_thread_without_a_session():
    """``Router._flush``, ``Replica.stream_poll`` and
    ``Replica.handle_request`` are TraceAnnotations only while a
    ``jax.profiler`` session is open: with none, nothing is built."""
    from unittest import mock

    from ray_tpu.serve.deployment import Router
    from ray_tpu.serve.replica import REPLY_SENT, STREAM_MARKER, Replica

    def gen(n):
        yield from range(n)

    assert {"serve.flush", "serve.stream_poll",
            "serve.handle_request"} <= perfmodel.ANNOTATIONS
    with pytest.raises(KeyError):       # with or without a session
        perfmodel.session_annotation("llm.sample")
    with mock.patch.object(perfmodel, "_annotation") as built:
        rep = Replica(gen, (), {}, deployment_name="quiet")
        sid = rep.handle_request("__call__", (3,), {})[STREAM_MARKER]
        rep.stream_grant(sid, 16, "me")
        reply = rep.stream_poll("me")
        while not reply[sid][1]:
            reply = rep.stream_poll("me")
        assert REPLY_SENT in reply
        Router("quiet")._flush([], time.time())
        assert built.call_count == 0
        # With a session open each body is one annotation.
        with mock.patch.object(perfmodel, "_session_open", lambda: True):
            Router("quiet")._flush([], None)
            rep.handle_request("__call__", (0,), {})
        # (A collector's pass that falls inside these two calls is an
        # annotation of its own, ``py.gc``, and none of theirs.)
        assert [c.args[0] for c in built.call_args_list
                if c.args[0] != "py.gc"] == [
            "serve.flush", "serve.handle_request"]
