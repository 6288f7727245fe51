"""What PR 32 added to the benchmark for `laguna-xs2-serve-repo`, checked
without a chip: the configuration's file against the catalog's row, the
benchmark's own copy of the plain reference against the repository's,
its token classes, the new readers on hand-made inputs, and the cell's
traffic."""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import harness, moe_cost, reference_laguna, traffic  # noqa: E402
from ray_tpu.models import laguna, laguna_ref  # noqa: E402

CELL, CONFIG = "laguna-xs2-serve-repo", "laguna-xs2-serve"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _config():
    return harness.read_json("configs", CONFIG + ".json")


def _cell():
    return harness.read_json("workloads", CELL + ".json")


def test_configuration_holds_the_published_config_untouched():
    if not os.path.isfile(CATALOG):
        pytest.skip("the catalog is not installed here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Laguna-XS.2")
    cfg = _config()
    assert cfg["source"] == row["source_url"]
    assert cfg["reduced"] == ["num_hidden_layers"]
    for key, value in row["config"].items():
        if key == "num_hidden_layers":
            assert (value, cfg[key]) == (40, 5)
        else:
            assert cfg[key] == value, key


def test_run_configuration_is_the_first_period_of_the_published_one():
    cfg = _config()
    f = cfg["model"]["fields"]
    L = f["num_hidden_layers"]
    assert L == cfg["num_hidden_layers"] == 5
    for key in ("layer_types", "mlp_layer_types",
                "num_attention_heads_per_layer"):
        assert f[key] == cfg[key][:L], key
    assert f["layer_types"].count("full_attention") == 2
    assert f["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    for key in ("vocab_size", "hidden_size", "intermediate_size",
                "num_key_value_heads", "head_dim", "num_experts",
                "num_experts_per_tok", "moe_intermediate_size",
                "shared_expert_intermediate_size", "sliding_window",
                "rms_norm_eps", "moe_routed_scaling_factor"):
        assert f[key] == cfg[key], key
    assert f["rope_full"] == cfg["rope_parameters"]["full_attention"]
    assert f["rope_sliding"] == cfg["rope_parameters"]["sliding_attention"]
    model, _ = harness.model_config(cfg, rehearse=False)
    assert round(model.num_params() / 1e6) == 3870
    for key in ("gating", "router", "activation", "residuals", "weights",
                "max_seq", "no_qk_norm_no_shared_gate"):
        assert key in cfg["assumed"], key


def test_rehearsal_sizes_keep_what_the_cell_is_about():
    tiny, _ = harness.model_config(_config(), rehearse=True)
    assert set(tiny.layer_types) == {laguna.FULL, laguna.SLIDING}
    assert tiny.mlp_layer_types[0] == laguna.DENSE
    assert tiny.num_experts >= 8 and tiny.num_experts_per_tok >= 2
    spec = harness.sized(_cell()["traffic"], True)
    assert tiny.sliding_window < spec["prefixes"]["tokens"]


def test_pools_hold_what_the_issue_sizes_them_for():
    kw = _config()["serve"]["kwargs"]
    spec = _cell()["traffic"]
    bs = kw["block_size"]
    contexts = spec["prefixes"]["count"] * spec["prefixes"]["tokens"] // bs
    own = kw["max_batch"] * -(-(spec["body_tokens"]["max"]
                                + spec["max_tokens"]["max"]) // bs)
    assert contexts + own < kw["num_blocks"]
    lane = _config()["sliding_window"] // bs + 2
    tails = spec["prefixes"]["count"] * (_config()["sliding_window"] // bs)
    assert kw["max_batch"] * lane + tails < kw["window_blocks"]
    assert spec["max_total_tokens"] == \
        _config()["model"]["fields"]["max_seq"]


def test_cell_traffic_is_what_the_issue_names():
    spec = _cell()["traffic"]
    pool = traffic.size_pool(spec)
    assert len(pool) == 256
    assert {b for b, _ in pool} == {128, 256, 384, 512}
    # ISSUE 32's fallback: 24-96, because ttft_p50_ms spread 8.6% over
    # six runs at 32-160 (PERF.md section 6).
    assert min(a for _, a in pool) >= 24 and max(a for _, a in pool) <= 96
    plan = traffic.closed_loop_plan(spec, 2147483777, 100352)
    assert len(plan["prefixes"]) == 32
    assert all(len(p) == 8192 for p in plan["prefixes"])
    sharers = [c["prefix"] for c in plan["callers"]]
    assert len(sharers) == 64
    assert all(sharers.count(i) == 2 for i in range(32))
    assert _cell()["reference_request"]["prompt_tokens"] == 1536
    assert _cell()["driver"] == "serve_closed_loop_ref"
    assert _cell()["compare_prefixes"] >= 4


# -- the benchmark's own reference -------------------------------------------


@pytest.fixture(scope="module")
def tiny():
    cfg, _ = harness.model_config(_config(), rehearse=True)
    return cfg, laguna.init(jax.random.key(3), cfg)


def test_benchmark_reference_equals_the_repositorys(tiny):
    """Two copies of the same equations, written apart: the benchmark's
    (padded, a layer at a time) and models/laguna_ref.py's."""
    cfg, params = tiny
    seq = np.random.default_rng(0).integers(0, cfg.vocab_size, 90).tolist()
    got, router_inputs = reference_laguna.forward(params, cfg, seq[:70],
                                                  seq[70:])
    want = np.asarray(laguna_ref.forward(params, seq, cfg))[69:89]
    assert got.shape == want.shape == (20, cfg.vocab_size)
    assert np.abs(got - want).max() < 2e-5
    # One router input a routed layer, the real tokens only.
    assert sorted(router_inputs) == [1, 2, 3, 4]
    assert all(h.shape == (90, cfg.hidden_size)
               for h in router_inputs.values())


def test_reference_pools_margins_and_judges_them(tiny):
    from ray_tpu.ops import moe

    cfg, params = tiny
    seq = np.random.default_rng(1).integers(0, cfg.vocab_size, 60).tolist()
    prompt, rest = seq[:40], seq[40:]
    logits, _ = reference_laguna.forward(params, cfg, prompt, rest)
    best = logits.argmax(-1).tolist()
    # Teacher-forced on a random continuation: a margin wherever a
    # token is not the argmax, by what the logits give.
    m = reference_laguna.margins(logits, rest)
    assert [x == 0.0 for x in m] == [t == b for t, b in zip(rest, best)]
    assert max(m) == pytest.approx(max(
        float(r.max() - r[t]) for r, t in zip(logits, rest)), abs=1e-6)
    r = reference_laguna.compare(params, cfg, moe.route,
                                 [("a", prompt, rest),
                                  ("b", prompt, rest[:10])])
    assert r["n"] == 30 and len(r["lines"]) == 2
    assert r["exact"] == sum(x == 0.0 for x in m + m[:10])
    assert r["worst"] == pytest.approx(max(m))
    assert r["mean"] == pytest.approx(sum(m + m[:10]) / 30, abs=1e-6)
    # The served router is the reference's on identical inputs.
    assert r["router_same"] == r["router_total"] == 4 * (60 + 50)
    assert reference_laguna.router_checks(r)[0][0]
    good = {"n": 400, "exact": 370, "worst": 0.6, "mean": 0.007,
            "router_same": 9995, "router_total": 10000}
    assert all(ok for ok, _ in reference_laguna.token_checks(good))
    assert reference_laguna.router_checks(good)[0][0]
    for bad in ({"exact": 310}, {"mean": 0.02}, {"worst": 1.1}, {"n": 0}):
        assert not all(ok for ok, _ in reference_laguna.token_checks(
            dict(good, **bad))), bad
    assert not reference_laguna.router_checks(
        dict(good, router_same=9900))[0][0]


def test_the_lower_precision_fails_the_router_limit(tiny):
    """The control: router scores and softmax held in bfloat16 pick
    other experts than the served (float32) router on the same inputs,
    more often than the limit lets pass."""
    import dataclasses

    import jax.numpy as jnp
    from ray_tpu.ops import moe

    cfg, params = tiny
    cfg = dataclasses.replace(cfg, dtype="bfloat16")
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), params)
    seq = np.random.default_rng(2).integers(0, cfg.vocab_size, 900).tolist()
    answers = [("x", seq[:880], seq[880:])]
    sound = reference_laguna.compare(params, cfg, moe.route, answers)
    assert sound["router_same"] == sound["router_total"] == 4 * 900
    assert reference_laguna.router_checks(sound)[0][0]
    lower = reference_laguna.compare(params, cfg, moe.route, answers,
                                     lower=True)
    assert lower["router_same"] < 0.999 * lower["router_total"]
    assert not reference_laguna.router_checks(lower)[0][0]
    assert reference_laguna.served_router_of(_config()) is moe.route


def test_the_cell_rehearses_with_its_controls_logged():
    """The driver end to end at the rehearsal's sizes (float32, so every
    reading is exact): the four comparisons that decide ``correct``
    hold, every program reader of the cell finds something to read, and
    with ``BENCH_LAGUNA_CONTROLS`` set the reference one precision lower
    and its planted faults are read and logged, deciding nothing."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_LAGUNA_CONTROLS="1")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "2147483999", "--seconds", "5",
         "--trace", "0", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    out = p.stdout.splitlines()
    assert p.returncode == 3, p.stdout[-2000:] + p.stderr[-2000:]
    assert not [x for x in out if "[FAIL]" in x]
    for said in ("compared tokens are the float32 reference's argmax",
                 "mean reference margin of the compared tokens",
                 "worst reference margin of a compared token",
                 "pick the same experts"):
        assert [x for x in out if "[ok]" in x and said in x], said
    controls = [x for x in out if "control, " in x]
    assert len(controls) == 4 * 4
    for name in ("one precision lower", "a window one block short",
                 "one expert fewer a token", "routed weights sum to 1"):
        assert sum(f"control, {name}:" in x for x in controls) == 4
    found = next(x for x in out if "layer_metrics:" in x)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        for x in json.load(f)["per_layer"]:
            if CELL in x["workloads"] and x["source"] != "device_trace":
                assert f"'{x['name']}'" in found, (x["name"], found)


# -- the new readers -----------------------------------------------------------

FIELDS = _config()["model"]["fields"]


def _collected(**over):
    step = {"name": "llm.step", "decode_tokens": 64, "moe_experts_hit": 220,
            "context_tokens": 64 * 8600,
            "moe_load_max": 3.5, "window_blocks_live": 2000,
            "device_ms_by": {"decode": 30.0}}
    moe = "%moe_experts_decode.3 = bf16[4352,1024]{1,0} custom-call(...)"
    chunk = "%moe_experts_chunk.3 = bf16[7936,1024]{1,0} custom-call(...)"
    full = "%attn_full.2 = bf16[64,8,6,128]{3,2,1,0} custom-call(...)"
    win = "%attn_window.5 = bf16[64,8,8,128]{3,2,1,0} custom-call(...)"
    c = {
        "model_fields": FIELDS, "device": {"kind": "TPU v5 lite"},
        "engine_steps": [dict(step), dict(step, moe_load_max=4.5),
                         dict(step, moe_load_max=2.5)],
        "engine_stats": ({}, {"kv_window_util_peak": 0.52}),
        "trace": {"modules": {"jit_llm_decode(1)": [10, 0.4],
                              "jit_llm_prefill_chunk(2)": [8, 0.2]},
                  "op_self_s": {moe: 0.080, chunk: 0.5, full: 0.12,
                                win: 0.009},
                  "op_calls": {moe: 80, chunk: 16, full: 20, win: 30}},
    }
    c.update(over)
    return c


def _read(name, c):
    return harness.load_module("layer_metrics", name).read(c)


def test_new_readers_read_a_hand_made_collected():
    c = _collected()
    # 80 ms of the kernel in 10 executions of the decode program (the
    # events' own count, 80, is not used: a call shows as two events).
    assert _read("moe_expert_ms", c) == pytest.approx(8.0)
    assert _read("attn_full_ms", c) == pytest.approx(12.0)
    assert _read("attn_window_ms", c) == pytest.approx(0.9)
    assert _read("moe_load_max", c) == 3.5
    assert _read("kv_window_live_pct", c) == pytest.approx(52.0)
    need_s = max(moe_cost.operations(64 * 8, FIELDS) / 197e12,
                 moe_cost.bytes_read(220, FIELDS) / 819e9)
    assert _read("moe_roofline_pct", c) == pytest.approx(
        100 * need_s / 0.008)
    assert 0 < _read("moe_roofline_pct", c) < 100
    # 64 lanes x 8,600 tokens x 2 full layers x 4,096 B at 819 GB/s,
    # over the 12 ms a step the kernels of that name take.
    assert _read("attn_full_roofline_pct", c) == pytest.approx(
        100 * (64 * 8600 * 2 * 4096 / 819e9) / 0.012)
    assert 0 < _read("attn_full_roofline_pct", c) < 100


def test_new_readers_find_nothing_where_the_program_writes_nothing():
    """A program without the kernels' names, the counters or the second
    pool (the parent commit, or a model without routed experts): None,
    not an error."""
    bare = _collected(
        engine_steps=[{"name": "llm.step", "decode_tokens": 64}],
        engine_stats=({}, {"kv_util_peak": 0.8}),
        trace={"modules": {}, "op_self_s": {"%paged_decode.3 = x": 0.1},
               "op_calls": {"%paged_decode.3 = x": 10}})
    for name in ("moe_expert_ms", "moe_roofline_pct", "moe_load_max",
                 "attn_full_ms", "attn_window_ms", "kv_window_live_pct",
                 "attn_full_roofline_pct"):
        assert _read(name, bare) is None, name
        assert _read(name, dict(bare, trace=None, engine_steps=[],
                                engine_stats=None)) is None, name
    gpt = dict(bare, model_fields={"d_model": 768, "n_layer": 12,
                                   "n_head": 12})
    for name in ("moe_expert_ms", "moe_roofline_pct", "attn_full_ms",
                 "attn_window_ms", "attn_full_roofline_pct"):
        assert _read(name, gpt) is None, name


def test_moe_cost_counts_assignments_and_experts_hit():
    # One assignment passes three 2048 x 512 matrices: 6 x m x f.
    assert moe_cost.operations(1, FIELDS) == 4 * 6 * 2048 * 512
    # An expert hit is read once: three matrices in bfloat16.
    assert moe_cost.bytes_read(1, FIELDS) == 4 * 3 * 2048 * 512 * 2
    # The issue's arithmetic: ~221 of 256 experts a layer, 1.6 GB x 4.
    assert moe_cost.bytes_read(256, FIELDS) == pytest.approx(6.44e9,
                                                            rel=0.01)


def test_manifest_lists_the_cell_where_the_issue_says():
    """Position-free since PR 34: later PRs append cells, configurations
    and metrics behind these, and may list their cells under the
    metrics this cell shares."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    assert [w["config"] for w in m["workloads"] if w["name"] == CELL] \
        == [CONFIG]
    assert CONFIG in [c["name"] for c in m["configs"]]
    e2e = {x["name"] for x in m["end_to_end"]
           if CELL in x.get("workloads", [CELL])}
    # Not ttft_p50_ms: its median spread 8.6-14.0% over six seeds here,
    # where a new cell may show 5% (PERF.md section 7), so the cell is
    # left off it and off the per-layer metrics that move it.
    # Nor itl_p99_ms: PR 54's check found it too unsteady for any bound
    # here; it is read per layer as itl_p99_long_ms.
    assert e2e == {"serve_tokens_per_s", "setup_s"}
    for x in m["per_layer"]:
        if x["moves"] == "ttft_p50_ms":
            assert CELL not in x["workloads"], x["name"]
    by_name = {x["name"]: x for x in m["per_layer"]}
    for name in ("moe_expert_ms", "moe_roofline_pct", "moe_load_max",
                 "attn_full_ms", "attn_window_ms", "kv_window_live_pct",
                 "attn_full_roofline_pct"):
        assert CELL in by_name[name]["workloads"], name
    # What only this configuration's programs write is read in its cell
    # alone: the readers count 256 experts all held, layers by kind.
    for name in ("moe_roofline_pct", "attn_window_ms",
                 "kv_window_live_pct", "attn_full_roofline_pct"):
        assert by_name[name]["workloads"] == [CELL], name
    # Found by the kernel's name alone, so another configuration whose
    # paged call carries it may list its cell too.
    assert CELL in by_name["attn_full_ms"]["workloads"]
    for x in m["per_layer"]:
        if x["name"] in ("paged_kernel_ms", "paged_roofline_pct"):
            assert CELL not in x["workloads"]
        if CELL in x.get("workloads", []):
            assert os.path.isfile(os.path.join(
                ROOT, "benchmark", "layer_metrics",
                x["name"].split(".")[0] + ".py")), x["name"]
