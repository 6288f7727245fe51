"""What PR 34 added to the benchmark for `kimi-k25-serve-docs`, checked
without a chip: the configuration's file against the catalog's row, the
benchmark's own copy of the plain reference against the repository's,
its limits against each planted fault at the small size, the new
readers on hand-made inputs, the cell's traffic, and the cell's
rehearsal."""

import functools
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import harness, mla_cost, reference_kimi_k2, traffic  # noqa: E402
from ray_tpu.models import kimi_k2, kimi_k2_ref  # noqa: E402
from ray_tpu.ops import moe  # noqa: E402

CELL, CONFIG = "kimi-k25-serve-docs", "kimi-k25-serve"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = {"num_hidden_layers": (61, 5), "n_routed_experts": (384, 12),
           "vocab_size": (163840, 20480)}


def _config():
    return harness.read_json("configs", CONFIG + ".json")


def _cell():
    return harness.read_json("workloads", CELL + ".json")


def _manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_configuration_holds_the_published_config_untouched():
    if not os.path.isfile(CATALOG):
        pytest.skip("the catalog is not installed here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Kimi-K2.5")
    cfg = _config()
    assert cfg["source"] == row["source_url"]
    assert cfg["reduced"] == list(REDUCED)
    for key, value in row["config"].items():
        if key in REDUCED:
            assert (value, cfg[key]) == REDUCED[key], key
            assert cfg["published"][key] == value
        else:
            assert cfg[key] == value, key


def test_run_configuration_is_one_chips_share_of_the_published_one():
    cfg = _config()
    f = cfg["model"]["fields"]
    # Every width, the router's 384 outputs and its 8 experts a token
    # as published; depth, the experts HELD and the vocabulary cut.
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "num_attention_heads", "q_lora_rank", "kv_lora_rank",
                "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
                "n_shared_experts", "num_experts_per_tok",
                "first_k_dense_replace", "moe_layer_freq",
                "routed_scaling_factor", "norm_topk_prob", "scoring_func",
                "topk_method", "n_group", "topk_group", "rms_norm_eps",
                "rope_theta", "rope_scaling", "num_hidden_layers",
                "vocab_size"):
        assert f[key] == cfg[key], key
    assert f["n_routed_experts"] == cfg["published"]["n_routed_experts"] == 384
    assert f["experts_held"] == cfg["n_routed_experts"] == 12
    assert f["first_expert"] == 0 and 384 // 12 == 32
    model, _ = harness.model_config(cfg, rehearse=False)
    assert [model.routed(l) for l in range(5)] == [False] + [True] * 4
    assert round(model.num_params() / 1e6) == 3497     # 6.99 GB in bfloat16
    assert model.row_width * 2 == \
        cfg["cache_row"]["bytes_per_token_per_layer"] == 1280
    assert cfg["cache_row"]["bytes_per_token"] == 5 * 1280
    assert model.latent_width * 2 == \
        cfg["cache_row"]["unpadded_bytes_per_token_per_layer"] == 1152
    assert set(cfg["assumed"]) == {
        "rotary_pairs", "router_bias", "residuals", "weights", "max_seq",
        "deployment", "no_vision_tower", "dtype",
        "not_of_the_forward_pass"}
    assert "32 chips" in cfg["assumed"]["deployment"]
    assert cfg["reference"]["module"] == "benchmark.reference_kimi_k2"
    assert reference_kimi_k2.served_router_of(cfg) is moe.route_sigmoid


def test_rehearsal_sizes_keep_what_the_cell_is_about():
    tiny, _ = harness.model_config(_config(), rehearse=True)
    assert not tiny.routed(0) and tiny.routed(1)       # a dense leading layer
    assert tiny.q_lora_rank < tiny.hidden_size          # both low ranks
    assert tiny.kv_lora_rank < tiny.hidden_size
    assert tiny.qk_nope_head_dim and tiny.qk_rope_head_dim
    assert tiny.n_routed_experts >= 16 and tiny.num_experts_per_tok >= 2
    assert 0 < tiny.experts_held < tiny.n_routed_experts    # a share
    assert tiny.vocab_size < 163840                         # a slice


def test_pool_and_traffic_are_what_the_issue_names():
    cfg, cell = _config(), _cell()
    kw, spec = cfg["serve"]["kwargs"], cell["traffic"]
    assert (kw["num_blocks"], kw["block_size"], kw["max_batch"],
            kw["prefill_chunk_tokens"], kw["prefix_cache"]) == \
        (24576, 16, 64, 512, True)
    bs = kw["block_size"]
    docs = spec["prefixes"]["count"] * spec["prefixes"]["tokens"] // bs
    own = kw["max_batch"] * -(-(spec["body_tokens"]["max"]
                                + spec["max_tokens"]["max"]) // bs)
    assert docs == 16384 and own == 64 * 38
    assert 0.65 < (docs + own / 2) / (kw["num_blocks"] - 1) < 0.80
    assert spec["max_total_tokens"] == cfg["model"]["fields"]["max_seq"] \
        == 17408
    pool = traffic.size_pool(spec)
    assert len(pool) == 256
    assert {b for b, _ in pool} == {128, 256, 384, 512}
    assert min(a for _, a in pool) >= 24 and max(a for _, a in pool) <= 96
    plan = traffic.closed_loop_plan(spec, 2147483777, 20480)
    assert len(plan["prefixes"]) == 16
    assert all(len(p) == 16384 and max(p) < 20480 for p in plan["prefixes"])
    sharers = [c["prefix"] for c in plan["callers"]]
    assert len(sharers) == 64
    assert all(sharers.count(i) == 4 for i in range(16))
    assert (spec["pool_size"], spec["pairing_seed"]) == (256, 23)
    assert cell["reference_request"]["prompt_tokens"] == 1536
    assert cell["reference_request"]["max_tokens"] == 64
    assert cell["driver"] == "serve_closed_loop_ref"
    assert cell["compare_prefixes"] == 4 and cell["config"] == CONFIG


def test_manifest_lists_the_cell_where_the_issue_says():
    m = _manifest()
    (cell,) = [w for w in m["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "docs-closed-64", 1)
    (config,) = [c for c in m["configs"] if c["name"] == CONFIG]
    assert config["reduced"] == list(REDUCED)
    assert config["file"] == f"benchmark/configs/{CONFIG}.json"
    e2e = {x["name"] for x in m["end_to_end"]
           if CELL in x.get("workloads", [CELL])}
    # Not itl_p99_ms since PR 54's check (too unsteady for any bound
    # here): the two latent readers move serve_tokens_per_s.
    assert e2e == {"serve_tokens_per_s", "setup_s"}
    by_name = {x["name"]: x for x in m["per_layer"]}
    mine = ("attn_latent_ms", "attn_latent_roofline_pct", "latent_chunk_ms",
            "moe_held_rows")
    for name in mine:
        assert by_name[name]["moves"] == "serve_tokens_per_s", name
        assert by_name[name]["workloads"][0] == CELL, name
    # A shared reader holds the cell, first: the three latent readers
    # count field names that another configuration of this block has
    # too (PR 62's cell stands behind this one on their lists).
    for name in ("batch_occupancy_pct", "itl_p95_ms", "engine_host_gap_ms",
                 "kv_live_peak_pct", "decode_step_ms", "decode_device_ms",
                 "device_idle_pct.serve", "engine_schedule_ms",
                 "engine_sample_ms", "engine_emit_ms", "engine_between_ms",
                 "decode_lanes_pct", "stream_hold_ms", "moe_expert_ms",
                 "moe_load_max"):
        assert CELL in by_name[name]["workloads"], name
    # Their readers count another model's work, or move a metric the
    # cell does not report.
    for x in m["per_layer"]:
        if x["name"] in ("moe_roofline_pct", "attn_full_ms",
                         "attn_full_roofline_pct", "attn_window_ms",
                         "kv_window_live_pct", "paged_kernel_ms",
                         "paged_roofline_pct") \
                or x["moves"] == "ttft_p50_ms":
            assert CELL not in x["workloads"], x["name"]
        if CELL in x.get("workloads", []):
            assert os.path.isfile(os.path.join(
                ROOT, "benchmark", "layer_metrics",
                x["name"].split(".")[0] + ".py")), x["name"]


# -- the benchmark's own reference -------------------------------------------


@pytest.fixture(scope="module")
def tiny():
    cfg, _ = harness.model_config(_config(), rehearse=True)
    return cfg, kimi_k2.init(jax.random.key(3), cfg)


def test_benchmark_reference_equals_the_repositorys(tiny):
    """Two copies of the same equations, written apart: the benchmark's
    (padded, heads leading, in blocks of queries) and
    models/kimi_k2_ref.py's."""
    cfg, params = tiny
    seq = np.random.default_rng(0).integers(0, cfg.vocab_size, 90).tolist()
    got, router_inputs = reference_kimi_k2.forward(params, cfg, seq[:70],
                                                   seq[70:])
    want = np.asarray(kimi_k2_ref.forward(params, seq, cfg))[69:89]
    assert got.shape == want.shape == (20, cfg.vocab_size)
    assert np.abs(got - want).max() < 2e-5
    # One router input a routed layer, the real tokens only.
    assert sorted(router_inputs) == [1, 2]
    assert all(h.shape == (90, cfg.hidden_size)
               for h in router_inputs.values())
    # Queries in blocks: a padded length of several blocks gives the
    # logits of one block.
    reference_kimi_k2._layer_fn.cache_clear()
    old, reference_kimi_k2.ROWS = reference_kimi_k2.ROWS, 256
    try:
        blocked, _ = reference_kimi_k2.forward(params, cfg, seq[:70],
                                               seq[70:])
    finally:
        reference_kimi_k2.ROWS = old
        reference_kimi_k2._layer_fn.cache_clear()
    assert np.abs(blocked - want).max() < 2e-5


def _served_answer(cfg, params, prompt, n):
    """``n`` greedy tokens of the served path's mathematics: the
    repository's reference stands in for the engine here (they are
    equal to 2e-7 at float32, tests/test_kimi_k2.py). One compiled
    forward over a buffer of the final length: causal, so what lies
    behind a position does not reach it."""
    forward = jax.jit(lambda toks: kimi_k2_ref.forward(params, toks, cfg))
    buf = np.zeros((len(prompt) + n,), np.int32)
    buf[:len(prompt)] = prompt
    for i in range(len(prompt), len(buf)):
        buf[i] = int(np.asarray(forward(buf))[i - 1].argmax())
    return buf[len(prompt):].tolist()


def test_reference_pools_margins_and_judges_them(tiny):
    cfg, params = tiny
    seq = np.random.default_rng(1).integers(0, cfg.vocab_size, 60).tolist()
    prompt, rest = seq[:40], seq[40:]
    logits, _ = reference_kimi_k2.forward(params, cfg, prompt, rest)
    m = reference_kimi_k2.margins(logits, rest)
    best = logits.argmax(-1).tolist()
    assert [x == 0.0 for x in m] == [t == b for t, b in zip(rest, best)]
    r = reference_kimi_k2.compare(params, cfg, moe.route_sigmoid,
                                  [("a", prompt, rest),
                                   ("b", prompt, rest[:10])])
    assert r["n"] == 30 and len(r["lines"]) == 2
    assert r["worst"] == pytest.approx(max(m))
    # The served router is the reference's on identical inputs, to the
    # last weight.
    assert r["router_same"] == r["router_total"] == 2 * (60 + 50)
    assert r["router_weight_diff"] < 1e-6
    assert all(ok for ok, _ in reference_kimi_k2.router_checks(r))
    good = {"n": 400, "exact": 390, "worst": 0.1, "mean": 0.0005,
            "router_same": 9995, "router_total": 10000,
            "router_weight_diff": 1e-6}
    assert all(ok for ok, _ in reference_kimi_k2.token_checks(good))
    assert all(ok for ok, _ in reference_kimi_k2.router_checks(good))
    for bad in ({"exact": 0}, {"mean": 10.0}, {"worst": 10.0}, {"n": 0}):
        assert not all(ok for ok, _ in reference_kimi_k2.token_checks(
            dict(good, **bad))), bad
    for bad in ({"router_same": 9800}, {"router_weight_diff": 0.5},
                {"router_total": 0}):
        assert not all(ok for ok, _ in reference_kimi_k2.router_checks(
            dict(good, **bad))), bad


def _sharpened(cfg, dtype):
    """Parameters of the small size at which a fault in the attention
    scores can show. At std 0.02 a 64-wide model's scores are ~0.03,
    every softmax is flat and no such fault moves anything, so the
    query and key projections are scaled up to scores of ~1.6 (what the
    published widths give at std 0.02) and the output projection to
    where attention carries the residual."""
    params = kimi_k2.init(jax.random.key(5), cfg)
    for p in params["layers"]:
        p["w_uq"], p["w_dkv"] = p["w_uq"] * 8, p["w_dkv"] * 8
        p["w_o"] = p["w_o"] * 30
        for name in {"w_down", "s_down", "w2"} & set(p):
            p[name] = p[name] * 0.1
    return jax.tree_util.tree_map(
        lambda a: a.astype(dtype) if a.ndim > 1 else a, params)


@pytest.fixture(scope="module", params=["float32", "bfloat16"])
def served(request):
    """(cfg, params, [(what, prompt, answer)]) of the sharpened small
    model in one dtype, with the answer its own greedy tokens."""
    import dataclasses

    cfg, _ = harness.model_config(_config(), rehearse=True)
    cfg = dataclasses.replace(cfg, dtype=request.param)
    params = _sharpened(cfg, cfg.dtype)
    prompt = np.random.default_rng(2).integers(
        0, cfg.vocab_size, 120).tolist()
    return cfg, params, [("x", prompt,
                          _served_answer(cfg, params, prompt, 16))]


def test_each_planted_fault_fails_a_limit_at_the_small_size(served):
    """The served path's own answers read against the reference with
    one fault planted: some limit fails. In float32 the sound reading
    is exact (every token equal, margin 0, routers alike to the last
    weight); the router's faults fail the router's limits and the
    attention's the tokens'. In bfloat16 the router's scores held one
    precision lower fail the router's agreement."""
    cfg, params, answers = served
    read = functools.partial(reference_kimi_k2._read, params, cfg,
                             moe.route_sigmoid, answers)
    sound = read()
    assert all(ok for ok, _ in reference_kimi_k2.router_checks(sound))
    if cfg.dtype == jnp.bfloat16:
        r = read(True)
        assert not reference_kimi_k2.router_checks(r)[0][0], r
        return
    assert sound["exact"] == sound["n"] == 16 and sound["worst"] == 0.0
    assert all(ok for ok, _ in reference_kimi_k2.token_checks(sound))
    for fault in reference_kimi_k2.FAULTS:
        r = read(False, fault)
        by_router = fault in ("chosen_by_score_alone",
                              "weights_not_renormalised")
        checks = reference_kimi_k2.router_checks(r) if by_router \
            else reference_kimi_k2.token_checks(r)
        assert not all(ok for ok, _ in checks), (fault, r)


def test_the_cell_rehearses_with_its_controls_logged():
    """The driver end to end at the rehearsal's sizes (float32, so every
    reading is exact): the comparisons that decide ``correct`` hold,
    every program reader of the cell finds something to read, and with
    ``BENCH_KIMI_CONTROLS`` set the reference one precision lower and
    its planted faults are read and logged, deciding nothing."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_KIMI_CONTROLS="1")
    env.pop("BENCH_LAGUNA_CONTROLS", None)
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
                 "pick the same experts", "their weights differ by at most",
                 "report their whole prefix cached"):
        assert [x for x in out if "[ok]" in x and said in x], said
    controls = [x for x in out if "control, " in x]
    assert len(controls) == 5 * 5
    for name in ("one precision lower", *reference_kimi_k2.FAULTS.values()):
        assert sum(f"control, {name}:" in x for x in controls) == 5
    found = next(x for x in out if "layer_metrics:" in x)
    for x in _manifest()["per_layer"]:
        if CELL in x["workloads"] and x["source"] != "device_trace":
            assert f"'{x['name']}'" in found, (x["name"], found)


# -- the new readers -----------------------------------------------------------

FIELDS = _config()["model"]["fields"]


def _collected(**over):
    step = {"name": "llm.step", "decode_tokens": 60, "moe_experts_hit": 9,
            "context_tokens": 60 * 16900, "moe_load_max": 3.0,
            "moe_held_rows": 16, "prefill_chunks": [[512, 16384, 90.0]],
            "device_ms_by": {"decode": 35.0}}
    latent = "%attn_latent.7 = bf16[64,64,512]{2,1,0} custom-call(...)"
    moe_k = "%moe_experts_decode.3 = bf16[704,4096]{1,0} custom-call(...)"
    c = {
        "model_fields": FIELDS, "device": {"kind": "TPU v5 lite"},
        "engine_steps": [
            dict(step),
            dict(step, moe_held_rows=20, prefill_chunks=[[128, 16384, 60.0]]),
            dict(step, moe_held_rows=12, prefill_chunks=[]),
            dict(step, decode_tokens=0, moe_held_rows=0,
                 prefill_chunks=[[256, 16384, 70.0]])],
        "engine_stats": ({}, {"kv_util_peak": 0.76}),
        "trace": {"modules": {"jit_llm_decode(1)": [10, 0.4],
                              "jit_llm_prefill_chunk(2)": [8, 0.7]},
                  "op_self_s": {latent: 0.180, moe_k: 0.040},
                  "op_calls": {latent: 100, moe_k: 160}},
    }
    c.update(over)
    return c


def _read(name, c):
    return harness.load_module("layer_metrics", name).read(c)


def test_new_readers_read_a_hand_made_collected():
    c = _collected()
    # 180 ms of the kernel in 10 executions of the decode program.
    assert _read("attn_latent_ms", c) == pytest.approx(18.0)
    assert _read("moe_expert_ms", c) == pytest.approx(4.0)
    assert _read("latent_chunk_ms", c) == 70.0
    # The steps that decoded: 16, 20, 12.
    assert _read("moe_held_rows", c) == pytest.approx(16.0)
    assert _read("moe_load_max", c) == 3.0
    # 60 lanes x 16,900 tokens x 5 layers x 1,152 B at 819 GB/s is the
    # longer side (the operations, 2 x 64 x 1,088 a token a layer at
    # 197 TFLOP/s, take about half as long), over the kernels' 18 ms.
    tokens = 60 * 16900
    need = tokens * 5 * 1152 / 819e9
    assert need > tokens * 5 * 2 * 64 * 1088 / 197e12 > 0.45 * need
    assert _read("attn_latent_roofline_pct", c) == pytest.approx(
        100 * need / 0.018)
    assert 0 < _read("attn_latent_roofline_pct", c) < 100


def test_new_readers_find_nothing_where_the_program_writes_nothing():
    """A program without the kernel's name or the counters (the parent
    commit, another model): None, not an error."""
    bare = _collected(
        engine_steps=[{"name": "llm.step", "decode_tokens": 64}],
        trace={"modules": {"jit_llm_decode(1)": [10, 0.4]},
               "op_self_s": {"%paged_decode.3 = x": 0.1},
               "op_calls": {"%paged_decode.3 = x": 10}})
    for name in ("attn_latent_ms", "attn_latent_roofline_pct",
                 "latent_chunk_ms", "moe_held_rows"):
        assert _read(name, bare) is None, name
        assert _read(name, dict(bare, trace=None, engine_steps=[],
                                engine_stats=None)) is None, name
        assert _read(name, {}) is None, name


def test_mla_cost_counts_a_context_tokens_bytes_and_operations():
    # One latent row a layer: 512 + 64 values in bfloat16, five layers.
    assert mla_cost.bytes_per_context_token(FIELDS) == 5 * 1152
    # Scores over 576 columns and values over 512, 64 heads, 2 ops each.
    assert mla_cost.operations_per_context_token(FIELDS) == \
        5 * 2 * 64 * 1088
    # ISSUE 34's arithmetic: ~121 operations a byte, half the v5e's ridge.
    per_byte = mla_cost.operations_per_context_token(FIELDS) \
        / mla_cost.bytes_per_context_token(FIELDS)
    assert per_byte == pytest.approx(120.9, abs=0.1)
    assert per_byte < 197e12 / 819e9
