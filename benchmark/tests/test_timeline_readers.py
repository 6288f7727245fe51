"""The readers of the program's step timeline (PR 24): each returns a
number from a hand-made ``collected`` and None from one that holds
nothing for it, which is what a commit without the program's spans and
counters gives. Run with ``python -m pytest benchmark/tests``."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402

CHAT, TRAIN = "gpt2s-serve-chat", "gpt2s-train-b24"


def _manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _step(lanes, context_tokens, between_ms, idle_wait, chunks):
    return {
        "name": "llm.step", "device_ms": 300.0, "host_gap_ms": 40.0,
        "phases_ms": {"llm.admit": 1.0, "llm.slots": 2.0,
                      "llm.decode.build": 3.0, "llm.sample": 20.0,
                      "llm.emit": 10.0, "llm.publish": 0.5},
        "other_ms": 3.5, "device_ms_by": {"prefill": 80.0, "decode": 220.0},
        "between_ms": between_ms, "idle_wait": idle_wait,
        "lanes": lanes, "max_batch": 64, "context_tokens": context_tokens,
        "prefill_chunks": chunks,
    }


def _hist(**cells):
    return {"phase_hist": {k: {"sum": s, "count": n}
                           for k, (s, n) in cells.items()}}


# Found by its name, ``paged_decode``; its operands are whatever they are.
PAGED = ('%paged_decode.3 = bf16[64,12,1,64] custom-call(bf16[64,12,1,64] '
         '%q, bf16[12,2560,16,768] %k, bf16[12,2560,16,768] %v), '
         'custom_call_target="tpu_custom_call"')


def _collected():
    config = harness.read_json("configs", "gpt2-small-serve.json")
    return {
        "rehearse": False, "config": config,
        "model_fields": config["model"]["fields"],
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
        "engine_steps": [
            _step(64, 40000, 0.2, False, [[256, 256, 40.0], [64, 512, 20.0]]),
            _step(32, 20000, 0.4, False, [[128, 256, 30.0]]),
            _step(48, 30000, 500.0, True, []),
        ],
        "engine_stats": (
            _hist(engine_queue=(1.0, 10), ttft=(5.0, 10),
                  stream_hold=(100.0, 100)),
            _hist(engine_queue=(3.0, 20), ttft=(12.0, 20),
                  stream_hold=(1100.0, 600))),
        "reports": [{"train_dispatch_ms": d, "train_ready_wait_ms": 236.0,
                     "train_data_wait_ms": 0.5} for d in (1.0, 1.2, 5.0)],
        "trace": {
            "modules": {"jit_llm_decode(123)": [10, 2.5],
                        "jit_llm_prefill_chunk(7)": [4, 0.2],
                        "jit_kv_scatter_blocks(9)": [4, 0.1],
                        "jit_train_step(5)": [12, 2.85]},
            "op_self_s": {PAGED: 2.4}, "op_calls": {PAGED: 120},
        },
    }


WANT = {
    "engine_schedule_ms": 6.0, "engine_sample_ms": 20.0,
    "engine_emit_ms": 10.0,
    "engine_between_ms": 0.3,               # the idle wait is left out
    "decode_lanes_pct": 100.0 * (64 + 32 + 48) / 3 / 64,
    "engine_queue_ms": 200.0, "ttft_engine_ms": 700.0,
    "stream_hold_ms": 2000.0,
    "decode_device_ms": 220.0, "prefill_chunk_ms": 30.0,
    # 30,000 context tokens x 36,864 B at 819 GB/s, over the kernels'
    # 2.4 s in 10 executions of ``jit_llm_decode``.
    "paged_roofline_pct": 100.0 * (30000 * 36864 / 819e9) / 0.240,
    "train_dispatch_ms": 1.2, "train_ready_wait_ms": 236.0,
    "train_data_wait_ms": 0.5, "train_program_ms": 237.5,
}
NEW = sorted(WANT)
ORDER = list(WANT)     # as PR 24 appended them to the manifest


def test_the_manifest_names_exactly_these_readers_once_a_cell():
    m = _manifest()
    mine = {x["name"]: x for x in m["per_layer"] if x["name"] in WANT}
    # Membership, once each, and the order among themselves: later PRs
    # append metrics behind them and cells to their lists.
    assert sorted(mine) == NEW
    assert [x["name"] for x in m["per_layer"] if x["name"] in WANT] == ORDER
    for name, x in mine.items():
        assert x["workloads"][0] == (TRAIN if name.startswith("train_")
                                     else CHAT), name
        assert TRAIN not in x["workloads"][1:] and \
            CHAT not in x["workloads"][1:], name
        assert x["better"] == ("higher" if name.endswith("_pct")
                               else "lower"), name
        assert x["unit"] == ("%" if name.endswith("_pct") else "ms")


@pytest.mark.parametrize("name", NEW)
def test_reader_reads_a_hand_made_collected(name):
    read = harness.load_module("layer_metrics", name).read
    assert read(_collected()) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", NEW)
def test_reader_finds_nothing_where_the_program_writes_nothing(name):
    """What the parent commit hands the same reader: ring entries and
    reports without the new fields, engine_stats without phase_hist,
    programs under other names, or no trace at all."""
    read = harness.load_module("layer_metrics", name).read
    c = _collected()
    c["engine_steps"] = [{"name": "llm.step", "device_ms": 300.0,
                          "host_gap_ms": 40.0, "tokens": 70}]
    c["engine_stats"] = ({"steps": 1}, {"steps": 9})
    c["reports"] = [{"train_device_ms": 237.0, "train_host_gap_ms": 1.7}]
    c["trace"]["modules"] = {"jit_step(5)": [12, 2.85],
                             "jit__unnamed_wrapped_function_(3)": [10, 2.5]}
    assert read(c) is None
    c.update(engine_steps=[], engine_stats=None, reports=[], trace=None)
    assert read(c) is None


@pytest.mark.parametrize("cell", [CHAT, TRAIN])
def test_rehearsal_finds_something_for_every_program_reader(cell):
    """The program's own spans and counters exist on any backend: in a
    rehearsal every new ``program_span`` / ``program_counter`` reader
    of the cell must find something to read (a ``device_trace`` reader
    needs a device plane, which the CPU's profiler does not write)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--seed", "2147483659", "--seconds", "8", "--trace", "1",
         "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 3, p.stdout[-2000:] + p.stderr[-2000:]
    found = next(line for line in p.stdout.splitlines()
                 if "layer_metrics:" in line and "readers found" in line)
    for x in _manifest()["per_layer"]:
        if x["name"] in WANT and cell in x["workloads"] \
                and x["source"] != "device_trace":
            assert f"'{x['name']}'" in found, (x["name"], found)
