"""The yardstick's own checks: run with ``python -m pytest benchmark/tests``
from the root of the repository. None of them needs a chip."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from benchmark import flops, harness, named_kernels, traffic, xplane  # noqa: E402


def _manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _cell(name):
    with open(os.path.join(BENCH, "workloads", name + ".json")) as f:
        return json.load(f)


CELLS = [w["name"] for w in _manifest()["workloads"]]
SERVING = [c for c in CELLS if "traffic" in _cell(c)]


# -- traffic ---------------------------------------------------------------

@pytest.mark.parametrize("cell", SERVING)
def test_same_seed_same_requests_other_seed_other_tokens(cell):
    spec = _cell(cell)["traffic"]
    big = 3_000_000_019                     # more than 32 signed bits hold
    a = traffic.closed_loop_plan(spec, big, 50304)
    b = traffic.closed_loop_plan(spec, big, 50304)
    c = traffic.closed_loop_plan(spec, big + 1, 50304)
    assert a["prefixes"] == b["prefixes"]
    assert a["callers"] == b["callers"]
    assert a["tokens"](3, 1, 50) == b["tokens"](3, 1, 50)
    assert a["tokens"](3, 1, 50) != c["tokens"](3, 1, 50)
    if a["prefixes"]:
        assert a["prefixes"] != c["prefixes"]
    # Every seed offers the same set of sizes, dealt in another order.
    sizes = lambda p: sorted(s for w in p["callers"] for s in w["sizes"])  # noqa: E731
    assert sizes(a) == sizes(c) == sorted(traffic.size_pool(spec))
    assert a["callers"] != c["callers"]
    shares = sorted(w["first_share"] for w in a["callers"])
    assert shares == [(k + 1) / len(shares) for k in range(len(shares))]
    cap = spec["max_total_tokens"]
    pre = spec["prefixes"]["tokens"]
    assert all(pre + body + ans <= cap for body, ans in sizes(a))


def test_token_rows_follow_the_seed():
    a = traffic.token_rows(2**31 + 7, 4, 16, 512)
    b = traffic.token_rows(2**31 + 7, 4, 16, 512)
    c = traffic.token_rows(2**31 + 8, 4, 16, 512)
    assert all((x["tokens"] == y["tokens"]).all() for x, y in zip(a, b))
    assert any((x["tokens"] != y["tokens"]).any() for x, y in zip(a, c))
    assert traffic.seed31(2**31 + 7) < 2**31


@pytest.mark.parametrize("values,p,want", [
    ([15, 20, 35, 40, 50], 5, 15), ([15, 20, 35, 40, 50], 30, 20),
    ([15, 20, 35, 40, 50], 40, 20), ([15, 20, 35, 40, 50], 50, 35),
    ([15, 20, 35, 40, 50], 100, 50), (list(range(1, 101)), 90, 90),
    (list(range(1, 101)), 95, 95), ([7], 90, 7), ([3, 1, 2], 50, 2),
])
def test_nearest_rank_percentile(values, p, want):
    assert traffic.percentile(values, p) == want


def test_percentile_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        traffic.percentile([], 50)


def test_quantile_sizes_span_the_stated_range():
    s = traffic.quantile_sizes({"dist": "log_uniform", "min": 32,
                                "max": 384}, 256)
    assert s == sorted(s) and 32 <= s[0] <= 33 and 380 <= s[-1] <= 384
    assert abs(s[128] - (32 * 384) ** 0.5) < 2     # the median is geometric
    u = traffic.quantile_sizes({"dist": "uniform", "min": 384, "max": 896}, 32)
    assert abs(sum(u) / len(u) - 640) < 1
    w = traffic.quantile_sizes({"dist": "uniform", "min": 192, "max": 576,
                                "multiple_of": 64}, 64)
    assert set(w) == set(range(192, 577, 64)) and sum(w) / len(w) == 384


# -- trace reduction ---------------------------------------------------------

def _trace():
    with open(os.path.join(HERE, "data", "trace_small.json")) as f:
        return [tuple(r) for r in json.load(f)["rows"]]


def test_trace_reduction_on_a_small_trace():
    rows = _trace()
    r = xplane.reduce_rows(rows)
    assert r["device_planes"] == ["/device:TPU:0", "/device:TPU:1"]
    # The span is the trace's own: first op start to last op end over
    # the device planes; the 2,000 ns step and host lines do not count.
    assert r["window_s"] == pytest.approx(1500e-9)
    # Busy: TPU:0 [0,1000) + [1200,1500) = 1300 ns, TPU:1 500 ns; the
    # mean over the two chips. The module and step lines and the host
    # plane are not ops.
    assert r["busy_s"] == pytest.approx((1300 + 500) / 2 * 1e-9)
    # Self time: the while holds 200 + 300 ns of children.
    assert r["op_self_s"]["while.1"] == pytest.approx(500e-9 / 2)
    assert r["op_self_s"]["fusion.1"] == pytest.approx((200 + 300 + 500)
                                                       * 1e-9 / 2)
    kernel = next(n for n in r["op_self_s"] if "custom-call.2" in n)
    assert r["op_self_s"][kernel] == pytest.approx(300e-9 / 2)
    assert xplane.short_name(kernel) == \
        "%custom-call.2 custom-call tpu_custom_call bf16[8,64]"
    assert r["op_calls"]["fusion.1"] == pytest.approx(3 / 2)
    assert sum(r["op_self_s"].values()) == pytest.approx(r["busy_s"])
    assert xplane.top_ops(r, 2)[0][0] == "fusion.1"
    # A Mosaic kernel is found by its instruction's name, and one that
    # no reader has asked for is named: 150 ns in half an execution of
    # ``jit_step`` on the average chip.
    c = {"trace": r}
    assert named_kernels.per_execution_s(c, "%paged_decode",
                                         "jit_step") is None
    assert named_kernels.unread(r) == [["%custom-call",
                                        pytest.approx(150e-9), 1]]
    assert named_kernels.per_execution_s(
        c, "%custom-call", "jit_step") == pytest.approx(300e-9)
    assert named_kernels.unread(r) == []
    assert xplane.idle_pct(r) == pytest.approx(100 * (1 - 900 / 1500))


@pytest.mark.parametrize("intervals,want_ns", [
    ([], 0), ([(0, 10)], 10), ([(0, 10), (5, 20)], 20),
    ([(0, 10), (10, 20)], 20), ([(0, 10), (30, 40), (2, 4)], 20),
])
def test_union_of_intervals(intervals, want_ns):
    assert xplane.union_s(intervals) == pytest.approx(want_ns * 1e-9)


# -- operations from shapes --------------------------------------------------

GPT2S = {"vocab_size": 50304, "max_seq": 1024, "d_model": 768,
         "n_layer": 12, "n_head": 12}


def test_operations_from_shapes():
    assert flops.matmul_weights(GPT2S) == 12 * 12 * 768 * 768 + 50304 * 768
    per_token = flops.train_flops_per_token(GPT2S, 1024)
    attn = 12 * 12 * 64 * 12 * 1025 / 2
    assert per_token == pytest.approx(6 * flops.matmul_weights(GPT2S) + attn)
    assert flops.kv_bytes_per_token(GPT2S) == 2 * 12 * 768 * 2
    assert flops.flash_flops_per_step(GPT2S, 24, 1024) == pytest.approx(
        14 * 64 * 24 * 12 * 1024 * 1025 / 2 * 12)


def test_unknown_device_kind_has_no_peaks():
    assert flops.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    for kind in ("cpu", "TPU v4", "_source"):
        with pytest.raises(KeyError):
            flops.peaks(kind)


# -- the manifest and the files it names ------------------------------------

def test_every_named_file_exists():
    m = _manifest()
    for c in m["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"])), c["file"]
    used = {w["config"] for w in m["workloads"]}
    assert used == {c["name"] for c in m["configs"]}
    for w in m["workloads"]:
        cell = _cell(w["name"])
        assert cell["config"] == w["config"]
        assert os.path.isfile(os.path.join(
            BENCH, "drivers", cell["driver"] + ".py"))
    for folder, key in (("end_to_end", "end_to_end"),
                        ("layer_metrics", "per_layer")):
        for metric in m[key]:
            assert hasattr(harness.load_module(folder, metric["name"]),
                           "read"), metric["name"]


def test_every_moves_names_a_metric_each_listed_cell_reports():
    m = _manifest()
    e2e = {x["name"]: x for x in m["end_to_end"]}
    for metric in m["per_layer"]:
        target = e2e[metric["moves"]]
        cells = metric.get("workloads", CELLS)
        assert set(cells) <= set(target.get("workloads", CELLS)), \
            metric["name"]
    for cell in CELLS:
        reported = [x for x in m["end_to_end"]
                    if cell in x.get("workloads", CELLS)]
        assert "setup_s" in {x["name"] for x in reported}
        assert len(reported) >= 2
        assert any(cell in x.get("workloads", CELLS)
                   for x in m["per_layer"])
    assert all(0 < x["bound"] <= 0.1 for x in m["end_to_end"])


def test_run_py_knows_no_cell_configuration_or_metric_by_name():
    m = _manifest()
    with open(os.path.join(BENCH, "run.py")) as f:
        text = f.read()
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in m[k]]
    assert [n for n in names if n in text] == []


# -- the rehearsal -----------------------------------------------------------

@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_exits_3_without_a_result_line(cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--seed", "3000000019", "--seconds", "3", "--trace", "1",
         "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 3, p.stdout[-2000:] + p.stderr[-2000:]
    last = p.stdout.strip().splitlines()[-1]
    assert "rehearsal finished" in last and not last.startswith("{")
    assert "FAIL" not in p.stdout


def test_without_a_tpu_there_is_no_result_line():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode not in (0, 3)
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
