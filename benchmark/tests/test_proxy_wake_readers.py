"""``frames_per_wake`` and ``proxy_queue_ms`` (PR 59): what the proxy's
loop costs a stream frame and a request. Both read the serve/slo
histogram that ``engine_stats`` carries at the window's two edges; a
program whose proxy records no ``proxy_flush``, as every commit before
PR 59, gives the first nothing to read. Run with ``python -m pytest
benchmark/tests``."""

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


def _reader(name):
    return harness.load_module("layer_metrics", name)


def _hist(**phases):
    return {"platform": "tpu", "phase_hist": {
        phase: {"sum": total, "count": n}
        for phase, (total, n) in phases.items()}}


def test_frames_per_wake_is_the_windows_chunks_over_its_callbacks():
    c = {"engine_stats": (
        _hist(stream_hold=(2.0, 1_000), proxy_flush=(0.01, 40)),
        _hist(stream_hold=(9.0, 112_000), proxy_flush=(1.2, 3_040)))}
    assert _reader("frames_per_wake").read(c) == pytest.approx(37.0)


def test_a_stream_alone_reads_one_frame_a_wake():
    c = {"engine_stats": (_hist(), _hist(stream_hold=(0.1, 50),
                                         proxy_flush=(0.01, 50)))}
    assert _reader("frames_per_wake").read(c) == pytest.approx(1.0)


@pytest.mark.parametrize("c", [
    {}, {"engine_stats": None},
    {"engine_stats": ({"platform": "tpu"}, {"platform": "tpu"})},
    {"engine_stats": (_hist(stream_hold=(2.0, 1_000)),
                      _hist(stream_hold=(9.0, 112_000)))},
    {"engine_stats": (_hist(stream_hold=(2.0, 1_000), proxy_flush=(0.1, 9)),
                      _hist(stream_hold=(9.0, 112_000),
                            proxy_flush=(0.1, 9)))},
], ids=["empty", "no_stats", "no_hist", "parent_program", "no_wake_inside"])
def test_a_window_with_no_proxy_flush_reads_none_and_not_zero(c):
    """The parent's proxy resumes a task a frame and records no
    ``proxy_flush``: None, and the line leaves the metric out."""
    assert _reader("frames_per_wake").read(c) is None


def test_proxy_queue_ms_is_the_windows_mean():
    c = {"engine_stats": (_hist(proxy_queue=(1.0, 200)),
                          _hist(proxy_queue=(1.0 + 0.0043 * 900, 1_100)))}
    assert _reader("proxy_queue_ms").read(c) == pytest.approx(4.3)
    assert _reader("proxy_queue_ms").read(
        {"engine_stats": (_hist(), _hist(ttft=(1.0, 5)))}) is None
    assert _reader("proxy_queue_ms").read({}) is None


def test_the_program_records_the_phases_these_read():
    from ray_tpu.serve import slo

    assert {"proxy_flush", "proxy_queue", "stream_hold"} <= set(slo.PHASES)
    with open(os.path.join(ROOT, "ray_tpu", "serve", "deployment.py")) as f:
        assert 'record_phase("proxy_flush"' in f.read()


@pytest.mark.parametrize("name, entry", [
    ("frames_per_wake", {
        "unit": "frames", "better": "higher", "source": "program_counter",
        "layer": "Entry points", "moves": "serve_tokens_per_s",
        "workloads": SERVING}),
    ("proxy_queue_ms", {
        "unit": "ms", "better": "lower", "source": "program_span",
        "layer": "Entry points", "moves": "ttft_p50_ms",
        "workloads": ["gpt2s-serve-chat"]}),
])
def test_the_manifest_lists_each_once_for_its_cells(name, entry):
    """Membership, not position: the next PR appends behind them."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    (found,) = [m for m in manifest["per_layer"] if m["name"] == name]
    assert found == dict(entry, name=name)
    cells = {w["name"]: w for w in manifest["workloads"]}
    assert set(found["workloads"]) <= set(cells)
    reports = {m["name"]: m.get("workloads", list(cells))
               for m in manifest["end_to_end"]}
    assert set(found["workloads"]) <= set(reports[found["moves"]])
