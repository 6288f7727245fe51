"""``itl_p99_long_ms``: the client's 99th-percentile gap, per layer, in
the two long-context cells, where the driver's check read it too
unsteady for any bound the contract allows (PERF.md section 6, PR 54,
refusal round); end to end it stays in the chat cell alone."""

import json
import math
import os

from benchmark import harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
LONG = ["laguna-xs2-serve-repo", "kimi-k25-serve-docs"]
CHAT = "gpt2s-serve-chat"


def _collected(failed=0):
    # Two streams, frames 10 ms apart but for one gap of 250 ms; the
    # first stream's first two frames lie before the window opens.
    a = [0.0 + 0.010 * i for i in range(120)]
    b = [1.0 + 0.010 * i for i in range(100)]
    b[50:] = [t + 0.240 for t in b[50:]]
    return {"t_open": 0.015, "t_close": 10.0, "rehearse": True,
            "records": [{"t_send": 0.0, "t_tokens": a, "t_end": a[-1]},
                        {"t_send": 0.9, "t_tokens": b, "t_end": b[-1]}],
            "failed_records": [{}] * failed}


def _read(folder, name, c):
    return harness.load_module(folder, name).read(c)


def test_it_reads_what_the_end_to_end_reader_reads():
    c = _collected()
    want = _read("end_to_end", "itl_p99_ms", c)
    assert _read("layer_metrics", "itl_p99_long_ms", c) == want
    # 217 gaps, the later frame inside the window; nearest rank 215 of
    # them sorted is a 10 ms gap, the 250 ms one is the maximum.
    assert math.isclose(want, 10.0, abs_tol=1e-6)
    assert _read("layer_metrics", "itl_p95_ms", c) <= want


def test_nothing_to_read_returns_nothing_and_a_failed_request_is_no_number():
    empty = dict(_collected(), records=[])
    assert _read("layer_metrics", "itl_p99_long_ms", empty) is None
    # A failed request is an infinite sample in every tail: with three
    # of them beyond 217 gaps the 99th percentile is infinite, and the
    # reader hands over nothing rather than a number.
    assert _read("layer_metrics", "itl_p99_long_ms", _collected(3)) is None


def test_the_manifest_keeps_the_tail_end_to_end_in_the_chat_cell_alone():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    e2e = {x["name"]: x for x in m["end_to_end"]}
    assert e2e["itl_p99_ms"]["workloads"] == [CHAT]
    assert e2e["itl_p99_ms"]["bound"] == 0.1
    (mine,) = [x for x in m["per_layer"] if x["name"] == "itl_p99_long_ms"]
    assert mine["workloads"] == LONG
    assert (mine["moves"], mine["layer"], mine["source"], mine["unit"]) == \
        ("serve_tokens_per_s", "Entry points", "host_clock", "ms")
    # Nothing that a long-context cell reports may move a metric that
    # the cell no longer has; each still has a rate and the set-up time.
    for x in m["per_layer"]:
        if set(x["workloads"]) & set(LONG):
            assert x["moves"] == "serve_tokens_per_s", x["name"]
    for cell in LONG:
        assert {n for n, x in e2e.items()
                if cell in x.get("workloads", [cell])} == \
            {"serve_tokens_per_s", "setup_s"}
