"""The interpreter's readers (PR 60): the probe's lateness and held
share and the machine's standstills from the ``llm.step`` ring (and a
training run's reports), the served process's CPU a frame by thread
group and the engine thread's wait for a core from
``engine_stats()["threads"]``, and a first token's way around the engine
from the serve/slo histogram. A program without the field, as every
commit before PR 60, gives each of them nothing to read: None, not 0.
Run with ``python -m pytest benchmark/tests``."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402

SERVING = ["gpt2s-serve-chat", "laguna-xs2-serve-repo",
           "kimi-k25-serve-docs", "nemotron3s-serve-agent"]
TRAIN = ["gpt2s-train-b24"]


def _reader(name):
    return harness.load_module("layer_metrics", name)


def _step(n, late, held, standstill=0.0, held_long=0.0):
    return {"name": "llm.step", "interp_n": n, "interp_late_ms": late,
            "interp_late_max_ms": late, "interp_held_n": held,
            "standstill_ms": standstill, "held_long_ms": held_long}


def _stats(frames, groups=None, hist=None, clock=0.0):
    """One ``engine_stats`` reading: ``groups`` is {group: (cpu_s,
    wait_s)}, None the parent's shape (no table)."""
    out = {"platform": "tpu", "phase_hist": dict(
        {"stream_hold": {"sum": 0.0, "count": frames}}, **(hist or {}))}
    if groups is not None:
        out["threads"] = {"process_cpu_s": clock, "by_group": {
            g: {"threads": 1, "cpu_s": cpu, "wait_s": wait}
            for g, (cpu, wait) in groups.items()}}
    return out


PARENT_STEPS = [{"name": "llm.step", "stall_ms": 1.0, "gc_max_ms": 0.0}] * 3


def test_interp_wait_and_held_share_pool_the_windows_samples():
    c = {"engine_steps": [_step(2, 1.0, 1), _step(0, 0.0, 0),
                          _step(6, 11.0, 3), {"name": "llm.step"}]}
    assert _reader("interp_wait_ms").read(c) == pytest.approx(12.0 / 8)
    assert _reader("interp_held_pct").read(c) == pytest.approx(50.0)


def test_machine_standstill_sums_the_ring_or_the_reports():
    read = _reader("machine_standstill_ms.serve").read
    assert _reader("machine_standstill_ms.train").read.__code__.co_filename \
        == read.__code__.co_filename        # one reader file for the two
    serving = {"engine_steps": [_step(1, 0.1, 0), _step(9, 2100.0, 3, 2050.0),
                                _step(2, 170.0, 1, 0.0, 160.0)]}
    assert read(serving) == pytest.approx(2050.0)
    assert read({"engine_steps": [_step(3, 0.2, 0)] * 4}) == 0.0
    training = {"reports": [{"train_device_ms": 238.0,
                             "train_standstill_ms": ms}
                            for ms in (0.0, 1190.0, 0.0)]}
    assert read(training) == pytest.approx(1190.0)


@pytest.mark.parametrize("name", [
    "interp_wait_ms", "interp_held_pct", "machine_standstill_ms.serve",
    "machine_standstill_ms.train"])
@pytest.mark.parametrize("c", [
    {}, {"engine_steps": []}, {"engine_steps": PARENT_STEPS},
    {"reports": [{"train_device_ms": 238.0}] * 3},
    {"engine_steps": [_step(0, 0.0, 0)]}],
    ids=["empty", "no_steps", "parent_ring", "parent_reports", "no_sample"])
def test_a_ring_without_the_probe_reads_none_and_not_zero(name, c):
    got = _reader(name).read(c)
    if name.startswith("machine") and c.get("engine_steps") \
            and "standstill_ms" in c["engine_steps"][0]:
        assert got == 0.0       # a window with the probe and no standstill
    else:
        assert got is None


GROUPS_OPEN = {"serve-http": (10.0, 1.0), "serve-stream-poll": (3.0, 0.5),
               "rt-core-loop": (6.0, 0.2), "actor": (4.0, 0.8),
               "device-exec": (1.0, 0.0), "asyncio": (0.5, 0.0),
               "other": (0.2, 0.0), "llm-engine": (9.0, 2.0),
               "MainThread": (30.0, 0.1), "native": (50.0, 3.0)}
GROUPS_CLOSE = {"serve-http": (29.0, 2.0), "serve-stream-poll": (8.0, 1.0),
                "rt-core-loop": (17.0, 0.4), "actor": (11.5, 1.6),
                "device-exec": (1.5, 0.0), "asyncio": (0.5, 0.0),
                "other": (0.2, 0.0), "llm-engine": (26.0, 2.6),
                "MainThread": (30.1, 0.1), "native": (75.0, 4.0),
                "interp-probe": (0.01, 0.0)}


def test_cpu_a_frame_is_the_serving_groups_growth_over_the_frames():
    c = {"engine_stats": (_stats(10_000, GROUPS_OPEN, clock=100.0),
                          _stats(210_000, GROUPS_CLOSE, clock=192.0)),
         "engine_steps": [_step(1, 0.1, 0)] * 3000}
    # serve-http 19 + poll 5 + loop 11 + actor 7.5 + exec 0.5 + the
    # probe's 0.01: not the engine's 17, the driver's, the runtimes'.
    assert _reader("serve_cpu_us_per_frame").read(c) == pytest.approx(
        43.01 / 200_000 * 1e6)
    assert _reader("runtime_cpu_us_per_frame").read(c) == pytest.approx(
        19.0 / 200_000 * 1e6)
    # 0.6 s of the engine thread's wait for a core over 3,000 steps.
    assert _reader("engine_runq_wait_ms").read(c) == pytest.approx(0.2)


def test_a_kernel_without_schedstat_gives_cpu_and_no_wait():
    def no_wait(groups):
        return {g: (cpu, None) for g, (cpu, _) in groups.items()}

    c = {"engine_stats": (_stats(0, no_wait(GROUPS_OPEN)),
                          _stats(100_000, no_wait(GROUPS_CLOSE))),
         "engine_steps": [_step(1, 0.1, 0)] * 10}
    assert _reader("serve_cpu_us_per_frame").read(c) == pytest.approx(430.1)
    assert _reader("engine_runq_wait_ms").read(c) is None


@pytest.mark.parametrize("name", [
    "serve_cpu_us_per_frame", "runtime_cpu_us_per_frame",
    "engine_runq_wait_ms"])
@pytest.mark.parametrize("c", [
    {}, {"engine_stats": None},
    {"engine_stats": (_stats(1_000), _stats(112_000)),
     "engine_steps": PARENT_STEPS},
    {"engine_stats": (_stats(1_000, GROUPS_OPEN), _stats(112_000)),
     "engine_steps": PARENT_STEPS},
    {"engine_stats": (_stats(5, GROUPS_OPEN), _stats(5, GROUPS_CLOSE))}],
    ids=["empty", "no_stats", "parent_program", "one_edge", "no_frame"])
def test_stats_without_the_thread_table_read_none_and_not_zero(name, c):
    assert _reader(name).read(c) is None


def test_a_first_tokens_way_reads_the_windows_mean_of_its_phase():
    def hist(ttft, out):
        return {"proxy_ttft": {"sum": ttft[0], "count": ttft[1]},
                "stream_out": {"sum": out[0], "count": out[1]}}

    c = {"engine_stats": (
        _stats(0, hist=hist((1.0, 20), (0.5, 400))),
        _stats(9, hist=hist((1.0 + 0.048 * 100, 120),
                            (0.5 + 0.0021 * 3000, 3400))))}
    assert _reader("ttft_server_ms").read(c) == pytest.approx(48.0)
    assert _reader("stream_out_ms").read(c) == pytest.approx(2.1)
    for name in ("ttft_server_ms", "stream_out_ms"):
        assert _reader(name).read({}) is None
        assert _reader(name).read(
            {"engine_stats": (_stats(1), _stats(9))}) is None


def test_the_program_writes_what_these_read():
    from ray_tpu._private import profiler
    from ray_tpu.serve import slo
    from ray_tpu.util import perfmodel

    assert {"proxy_ttft", "stream_out", "stream_hold"} <= set(slo.PHASES)
    acc = perfmodel.StepAccounting(
        hw=perfmodel.HARDWARE_PEAKS[perfmodel.V5E])
    acc.begin()
    acc.add_device(1e-3)
    assert set(_step(0, 0.0, 0)) - {"name"} <= set(acc.finish())
    table = profiler.thread_cpu()
    assert {"by_group", "process_cpu_s"} <= set(table)
    assert {"threads", "cpu_s", "wait_s"} == set(
        table["by_group"]["MainThread"])
    with open(os.path.join(ROOT, "ray_tpu", "train", "session.py")) as f:
        assert '"train_standstill_ms": step["standstill_ms"]' in f.read()


def _entry(unit, source, layer, moves, cells, better="lower"):
    return {"unit": unit, "better": better, "source": source,
            "layer": layer, "moves": moves, "workloads": cells}


@pytest.mark.parametrize("name, entry", [
    ("interp_wait_ms", _entry("ms", "program_span", "Entry points",
                              "serve_tokens_per_s", SERVING)),
    ("interp_held_pct", _entry("%", "program_counter", "Entry points",
                               "serve_tokens_per_s", SERVING)),
    ("machine_standstill_ms.serve", _entry(
        "ms", "program_counter", "Step accounting", "serve_tokens_per_s",
        SERVING)),
    ("machine_standstill_ms.train", _entry(
        "ms", "program_counter", "Step accounting", "train_tokens_per_s",
        TRAIN)),
    ("serve_cpu_us_per_frame", _entry(
        "us", "program_counter", "Entry points", "serve_tokens_per_s",
        SERVING)),
    ("runtime_cpu_us_per_frame", _entry(
        "us", "program_counter", "Entry points", "serve_tokens_per_s",
        SERVING)),
    ("ttft_server_ms", _entry("ms", "program_span", "Entry points",
                              "ttft_p50_ms", ["gpt2s-serve-chat"])),
    ("stream_out_ms", _entry("ms", "program_span", "Entry points",
                             "serve_tokens_per_s", SERVING)),
])
def test_the_manifest_lists_each_once_and_holds_its_cells(name, entry):
    """Membership, not equality of the cells: a later PR appends a cell
    behind them (ROADMAP's rule). ``engine_runq_wait_ms`` has a reader
    and no entry: the benchmark's host keeps no ``schedstat`` (its
    kernel reports 4.4.0 and has no /proc/<pid>/schedstat), so no cell
    there would report it, and a listed metric has to be reported."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    (found,) = [m for m in manifest["per_layer"] if m["name"] == name]
    cells = entry.pop("workloads")
    assert set(cells) <= set(found["workloads"])
    assert {k: v for k, v in found.items()
            if k not in ("name", "workloads")} == entry
    known = {w["name"]: w for w in manifest["workloads"]}
    assert set(found["workloads"]) <= set(known)
    reports = {m["name"]: m.get("workloads", list(known))
               for m in manifest["end_to_end"]}
    assert set(found["workloads"]) <= set(reports[found["moves"]])
    assert os.path.isfile(os.path.join(
        ROOT, "benchmark", "layer_metrics", name.split(".")[0] + ".py"))
