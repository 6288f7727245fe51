"""The readers of the step interval's parts (PR 37): the dispatch inside
a device span, the thread's stall, the engine's lock, the collector and
the loop's sleep. Each returns a number from a hand-made ``collected``
and None from one whose program lacks the field, which is what a commit
before PR 37 gives. Run with ``python -m pytest benchmark/tests``."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402

SERVING = ["gpt2s-serve-chat", "laguna-xs2-serve-repo",
           "kimi-k25-serve-docs"]


def _old_step(idle_wait, chunks):
    """A ring entry as the program wrote it before PR 37."""
    return {"name": "llm.step", "step_ms": 38.0, "device_ms": 30.0,
            "host_gap_ms": 8.0, "device_ms_by": {"decode": 25.0,
                                                 "prefill": 5.0},
            "phases_ms": {"llm.emit": 1.0}, "other_ms": 7.0,
            "between_ms": 1.5, "idle_wait": idle_wait,
            "prefill_chunks": [c[:3] for c in chunks]}


def _step(dispatch, chunks, stall, lock, interval, gc_max, idle_wait=False):
    return dict(_old_step(idle_wait, chunks), prefill_chunks=chunks,
                dispatch_ms_by=dispatch, stall_ms=stall, lock_wait_ms=lock,
                interval_ms=interval, gc_ms=gc_max * 1.5, gc_max_ms=gc_max,
                gc_gen=2 if gc_max else None, idle_ms=400.0 * idle_wait,
                cpu_ms=6.0, arrived=1)


def _stats(idle_s, gc):
    return {"platform": "tpu", "idle_s": idle_s, "idle_waits": 3, "gc": gc}


def _collected():
    steps = [
        _step({"decode": 3.0, "prefill": 1.0},
              [[256, 256, 5.0, 2.0], [64, 512, 4.0, 1.0]], 2.0, 0.1, 40.0,
              0.0),
        _step({"decode": 4.0}, [], 1.0, 0.3, 38.0, 1.5),
        _step({"decode": 5.0, "prefill": 3.0}, [[128, 256, 6.0, 3.0]],
              30.0, 0.2, 70.0, 22.0),
        # The loop slept before this one: its interval and its lock wait
        # are a wait for work, left out of those two readers.
        _step({"decode": 3.5}, [], 0.5, 9.0, 450.0, 0.2, idle_wait=True),
    ]
    return {
        "window_s": 50.0, "engine_steps": steps,
        "engine_stats": (
            _stats(1.0, {0: [100, 0.05], 1: [9, 0.02], 2: [1, 0.03]}),
            # As a JSON hop would hand it on: generations as strings.
            _stats(3.5, {"0": [600, 0.30], "1": [50, 0.12],
                         "2": [3, 0.18]})),
    }


WANT = {
    "decode_dispatch_ms": 3.75,         # median of 3.0, 4.0, 5.0, 3.5
    "chunk_dispatch_ms": 2.0,           # median of 2.0, 1.0, 3.0
    "engine_stall_ms": 8.375,           # mean of 2.0, 1.0, 30.0, 0.5
    "engine_stall_p99_ms": 30.0,        # nearest rank: the largest of 4
    "engine_lock_wait_ms": 0.2,         # 0.1, 0.3, 0.2; not the 9.0
    "step_interval_p99_ms": 70.0,       # 40, 38, 70; not the 450
    "gc_ms_per_s": 10.0,                # (0.60 - 0.10) s over 50 s
    "gc_pause_max_ms": 22.0,
    "engine_starved_pct": 5.0,          # 2.5 s of 50 s
}


@pytest.mark.parametrize("name", sorted(WANT))
def test_a_reader_reads_its_field_and_none_without_it(name):
    read = harness.load_module("layer_metrics", name).read
    assert read(_collected()) == pytest.approx(WANT[name])
    # The parent's program: entries and stats without PR 37's fields.
    c = _collected()
    old = {"window_s": 50.0,
           "engine_steps": [_old_step(e["idle_wait"], e["prefill_chunks"])
                            for e in c["engine_steps"]],
           "engine_stats": ({"platform": "tpu"}, {"platform": "tpu"})}
    assert read(old) is None
    # A training cell's collected holds neither key.
    assert read({"window_s": 50.0, "reports": []}) is None
    # An empty window: nothing to take a median of.
    assert read(dict(c, engine_steps=[], engine_stats=None)) is None


def test_a_window_without_chunks_or_collections_reads_as_such():
    c = _collected()
    for e in c["engine_steps"]:
        e["prefill_chunks"] = []
        e["gc_max_ms"] = 0.0
    c["engine_stats"][1]["gc"] = c["engine_stats"][0]["gc"]
    c["engine_stats"][1]["idle_s"] = c["engine_stats"][0]["idle_s"]

    def read(name):
        return harness.load_module("layer_metrics", name).read(c)

    assert read("chunk_dispatch_ms") is None
    assert read("gc_pause_max_ms") == 0.0
    assert read("gc_ms_per_s") == 0.0
    assert read("engine_starved_pct") == 0.0


def test_the_manifest_appends_the_nine_readers_for_the_serving_cells():
    """Membership, once each, and the order among themselves, not the
    manifest's last entries: the next PR appends behind them."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    metrics = {m["name"]: m for m in manifest["per_layer"]}
    assert [m["name"] for m in manifest["per_layer"]
            if m["name"] in WANT] == [
        "decode_dispatch_ms", "chunk_dispatch_ms", "engine_stall_ms",
        "engine_stall_p99_ms", "engine_lock_wait_ms",
        "step_interval_p99_ms", "gc_ms_per_s", "gc_pause_max_ms",
        "engine_starved_pct"]
    reported = {m["name"]: m.get("workloads") for m in manifest["end_to_end"]}
    for name in WANT:
        m = metrics[name]
        assert m["workloads"] == SERVING and m["better"] == "lower"
        assert m["layer"] in ("Scheduler", "Step accounting")
        assert m["source"] in ("program_span", "program_counter")
        assert set(m["workloads"]) <= set(reported[m["moves"]])
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark", "layer_metrics", name + ".py"))
