"""What PR 64 added to the benchmark for `granite4hs-serve-chat`,
checked without a chip: the configuration's file against the catalog's
row, the benchmark's own copy of the plain reference against the
repository's, its limits against each planted fault at the small size,
the new readers and cost functions on hand-made inputs, the cell's
traffic, and the cell's rehearsal."""

import dataclasses
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

from benchmark import (granite_hybrid_cost, harness,  # noqa: E402
                       reference_granite_hybrid as reference, ssm_cost,
                       traffic)
from ray_tpu.models import granite_hybrid, granite_hybrid_ref  # noqa: E402
from ray_tpu.ops import moe  # noqa: E402

CELL, CONFIG = "granite4hs-serve-chat", "granite4-h-small-serve"
NEMOTRON = "nemotron3s-serve-agent"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = {"num_hidden_layers": (40, 10), "num_local_experts": (72, 36),
           "vocab_size": (100352, 50176)}
NEW = ("hybrid_ssm_update_roofline_pct", "hybrid_ssm_scan_roofline_pct",
       "hybrid_moe_roofline_pct")


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
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "granite-4.0-h-small")
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
    # Every width, the router's 72 outputs, its 10 experts a token and
    # the four multipliers as published; depth, the experts HELD and the
    # vocabulary cut.
    for key in ("hidden_size", "num_attention_heads", "num_key_value_heads",
                "mamba_n_heads", "mamba_d_head", "mamba_d_state",
                "mamba_n_groups", "mamba_d_conv", "mamba_chunk_size",
                "mamba_conv_bias", "mamba_proj_bias", "intermediate_size",
                "shared_intermediate_size", "num_experts_per_tok",
                "embedding_multiplier", "attention_multiplier",
                "residual_multiplier", "logits_scaling",
                "tie_word_embeddings", "hidden_act", "attention_bias",
                "position_embedding_type", "normalization_function",
                "rms_norm_eps", "num_hidden_layers", "vocab_size"):
        assert f[key] == cfg[key], key
    assert f["mamba_n_heads"] * f["mamba_d_head"] \
        == cfg["mamba_expand"] * f["hidden_size"]
    assert f["num_local_experts"] == cfg["published"]["num_local_experts"] \
        == 72
    assert f["experts_held"] == cfg["num_local_experts"] == 36
    assert f["first_expert"] == 0 and 72 // 36 == 2
    # One whole period, in the published order, from the published
    # pattern (which the file's top level keeps whole).
    assert f["layer_types"] == cfg["layer_types"][10:20] \
        == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert len(cfg["layer_types"]) == 40
    assert [i for i, t in enumerate(cfg["layer_types"])
            if t == "attention"] == [5, 15, 25, 35]
    model, _ = harness.model_config(cfg, rehearse=False)
    assert round(model.num_params() / 1e5) == 47572     # 9.51 GB in bfloat16
    state = granite_hybrid.state_kind(model)
    assert state.slot_bytes == cfg["state"]["bytes_per_slot"] \
        == 9 * cfg["state"]["bytes_per_layer"] == 38204928
    kw = cfg["serve"]["kwargs"]
    assert kw["state_slots"] == cfg["state"]["state_slots"]
    assert kw["state_slots"] - 1 >= kw["max_batch"] + 8 + 1
    assert set(cfg["assumed"]) >= {
        "no_positional_embedding", "gated_norm", "ssm_parameters",
        "ssm_state_float32", "not_of_the_forward_pass", "weights",
        "max_seq", "deployment", "dtype"}
    assert "4 pipeline stages x 2 chips" in cfg["assumed"]["deployment"]
    assert "ONE chip's share" in cfg["assumed"]["deployment"]
    assert "17.8 rows" in cfg["assumed"]["deployment"]
    assert cfg["reference"]["module"] == "benchmark.reference_granite_hybrid"
    assert reference.served_router_of(cfg) is moe.route
    assert (cfg["kind"], cfg["chips"]) == ("serve", 1)


def test_rehearsal_sizes_keep_what_the_cell_is_about():
    tiny, _ = harness.model_config(_config(), rehearse=True)
    assert set(tiny.layer_types) == {"mamba", "attention"}
    assert len(tiny.layers_of("mamba")) >= 2
    # ONE group, wider than the scan's block of heads.
    assert tiny.mamba_n_groups == 1 and tiny.mamba_n_heads > 16
    assert tiny.num_local_experts >= 12 and tiny.num_experts_per_tok >= 2
    assert 0 < tiny.experts_held < tiny.num_local_experts    # a share
    assert tiny.vocab_size < 100352                          # a slice
    full, _ = harness.model_config(_config(), rehearse=False)
    for name in ("embedding_multiplier", "attention_multiplier",
                 "residual_multiplier", "logits_scaling"):
        assert getattr(tiny, name) == getattr(full, name), name
    kw = _config()["serve"]["rehearse"]["kwargs"]
    assert kw["state_slots"] - 1 >= kw["max_batch"]


def test_pool_and_traffic_are_what_the_issue_names():
    cfg, cell = _config(), _cell()
    kw, spec = cfg["serve"]["kwargs"], cell["traffic"]
    assert (kw["block_size"], kw["max_batch"], kw["prefix_cache"]) == \
        (16, 64, True)
    bs = kw["block_size"]
    shared = spec["prefixes"]["count"] * spec["prefixes"]["tokens"] // bs
    own = kw["max_batch"] * -(-(spec["body_tokens"]["max"]
                                + spec["max_tokens"]["max"]) // bs)
    assert shared == 128 and own == 64 * 160
    assert shared + own < kw["num_blocks"] - 1
    assert spec["max_total_tokens"] == cfg["model"]["fields"]["max_seq"] \
        == 2816 == 256 + 2048 + 512
    assert (spec["kind"], spec["callers"]) == ("closed_loop", 64)
    assert spec["prefixes"] == {"count": 8, "tokens": 256}
    assert spec["body_tokens"] == {"dist": "uniform", "min": 256,
                                   "max": 2048, "multiple_of": 256}
    assert spec["max_tokens"] == {"dist": "log_uniform", "min": 128,
                                  "max": 512}
    pool = traffic.size_pool(spec)
    assert len(pool) == 256
    assert {b for b, _ in pool} == set(range(256, 2049, 256))
    assert min(a for _, a in pool) >= 128 and max(a for _, a in pool) <= 512
    plan = traffic.closed_loop_plan(spec, 2147483777, 50176)
    assert len(plan["prefixes"]) == 8
    assert all(len(p) == 256 and max(p) < 50176 for p in plan["prefixes"])
    sharers = [c["prefix"] for c in plan["callers"]]
    assert len(sharers) == 64 == kw["max_batch"]
    assert all(sharers.count(i) == 8 for i in range(8))
    # Every prompt is whole blocks, and whole chunk lengths the set-up
    # warms.
    assert all((256 + b) % bs == 0 for b, _ in pool)
    assert kw["prefill_chunk_tokens"] % spec["body_tokens"]["multiple_of"] \
        == 0
    assert (spec["pool_size"], spec["pairing_seed"], spec["stagger_s"],
            spec["ramp_s"]) == (256, 23, 10.0, 16.0)
    assert cell["reference_request"]["prompt_tokens"] == 1536
    assert cell["reference_request"]["max_tokens"] == 64
    assert cell["driver"] == "serve_closed_loop_ref"
    assert cell["compare_prefixes"] == 4 and cell["config"] == CONFIG
    nemotron = harness.read_json("workloads", NEMOTRON + ".json")
    assert cell["window"] == nemotron["window"]
    assert cell["window"]["trace_after_s"] == 5.0
    assert cell["window"]["trace_seconds"] == 4.0
    assert reference.ROW_SPAN == kw["prefill_chunk_tokens"]
    assert reference.BLOCK == bs


def test_manifest_lists_the_cell_where_the_issue_says():
    m = _manifest()
    (cell,) = [w for w in m["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["chips"]) == (CONFIG, 1)
    assert cell["why"] == _cell()["why"] and len(cell["why"]) <= 200
    assert m["workloads"][-1] is cell
    (config,) = [c for c in m["configs"] if c["name"] == CONFIG]
    assert m["configs"][-1] is config and len(config["why"]) <= 200
    assert config["reduced"] == list(REDUCED)
    assert config["file"] == f"benchmark/configs/{CONFIG}.json"
    assert config["source"] == _config()["source"]
    assert sum(w["chips"] for w in m["workloads"]) == len(m["workloads"]) == 7
    e2e = {x["name"] for x in m["end_to_end"]
           if CELL in x.get("workloads", [CELL])}
    assert e2e == {"serve_tokens_per_s", "setup_s"}
    by_name = {x["name"]: x for x in m["per_layer"]}
    # Membership and their own order, not position: later PRs append.
    assert [x["name"] for x in m["per_layer"] if x["name"] in NEW] \
        == list(NEW)
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL], name
        assert by_name[name]["moves"] == "serve_tokens_per_s", name
        assert (by_name[name]["unit"], by_name[name]["layer"]) \
            == ("%", "Kernels")
    # Every reader Nemotron's cell is on that finds its source by name
    # or in the ring, and the three that read kernels this model's
    # programs also name; the cell stands last in each list.
    joined = [x["name"] for x in m["per_layer"]
              if CELL in x["workloads"] and x["name"] not in NEW]
    for name in ("decode_step_ms", "decode_device_ms",
                 "device_idle_pct.serve", "moe_expert_ms", "moe_load_max",
                 "moe_held_rows", "kv_run_pages_pct", "chunk_attn_ms",
                 "ssm_update_ms", "ssm_scan_ms", "state_live_peak_pct",
                 "state_resume_pct", "state_snapshot_ms", "itl_p95_ms",
                 "itl_p99_long_ms", "moe_expert_chunk_ms",
                 "moe_chunk_wide_tile_pct", "attn_full_ms"):
        assert name in joined, name
    for name in joined:
        assert by_name[name]["workloads"][-1] == CELL, name
        assert by_name[name]["moves"] == "serve_tokens_per_s", name
    on_nemotron = {x["name"] for x in m["per_layer"]
                   if NEMOTRON in x["workloads"]}
    # Nemotron's three shares read its own field names (``ssm_cost``,
    # ``latent_moe_cost``): this cell's are the NEW files.
    assert on_nemotron - set(joined) == {
        "ssm_update_roofline_pct", "ssm_scan_roofline_pct",
        "latent_moe_roofline_pct"}
    assert set(joined) - on_nemotron == {
        "moe_expert_chunk_ms", "moe_chunk_wide_tile_pct", "attn_full_ms"}
    for x in m["per_layer"]:
        if CELL in x.get("workloads", []):
            assert os.path.isfile(os.path.join(
                ROOT, "benchmark", "layer_metrics",
                x["name"].split(".")[0] + ".py")), x["name"]


# -- the benchmark's own reference -------------------------------------------


@pytest.fixture(scope="module")
def tiny():
    cfg, _ = harness.model_config(_config(), rehearse=True)
    return cfg, granite_hybrid.init(jax.random.key(3), cfg)


def test_benchmark_reference_equals_the_repositorys(tiny):
    """Two copies of the same equations, written apart: the benchmark's
    (padded, attention in blocks of queries, experts in a scan) and
    models/granite_hybrid_ref.py's; and the served chunk program's row
    is both's."""
    cfg, params = tiny
    seq = np.random.default_rng(0).integers(0, cfg.vocab_size, 90).tolist()
    got, router_inputs, (state, dtype) = reference.forward(
        params, cfg, seq[:70], seq[70:])
    # The served scan, update and pool against the recurrence.
    assert state < 1e-4 and dtype == "float32"
    full = np.asarray(granite_hybrid_ref.forward(params, seq, cfg))
    want = full[69:89]
    assert got.shape == want.shape == (20, cfg.vocab_size)
    assert np.abs(got - want).max() < 1e-6
    # One router input a layer (every layer has an expert block), the
    # real tokens only.
    assert sorted(router_inputs) == list(range(cfg.num_hidden_layers))
    assert all(u.shape == (90, cfg.hidden_size)
               for u in router_inputs.values())
    # 70 tokens in spans of ROW_SPAN: one span here; two below.
    assert np.abs(reference.served_row(params, cfg, seq[:70])
                  - full[69]).max() < 1e-6


def test_the_served_row_is_handed_from_span_to_span(tiny, monkeypatch):
    cfg, params = tiny
    seq = np.random.default_rng(1).integers(0, cfg.vocab_size, 75).tolist()
    full = np.asarray(granite_hybrid_ref.forward(params, seq, cfg))
    monkeypatch.setattr(reference, "ROW_SPAN", 32)      # 32 + 32 + 11
    assert np.abs(reference.served_row(params, cfg, seq)
                  - full[74]).max() < 1e-6


def _served_answer(cfg, params, prompt, n):
    """``n`` greedy tokens of the served path's mathematics: the
    repository's reference stands in for the engine here (they are
    equal to 1e-8 at float32, tests/test_granite_hybrid.py)."""
    forward = jax.jit(
        lambda toks: granite_hybrid_ref.forward(params, toks, cfg))
    buf = np.zeros((len(prompt) + n,), np.int32)
    buf[:len(prompt)] = prompt
    for i in range(len(prompt), len(buf)):
        buf[i] = int(np.asarray(forward(buf))[i - 1].argmax())
    return buf[len(prompt):].tolist()


def _sharpened(cfg):
    """Parameters of the small size at which each fault can show. At
    std 0.02 a 64-wide model's mixers add ~0.05 to a residual of ~0.25
    and its logits lie ~0.01 apart, so every block's output projection
    is scaled up to where it carries the residual, queries and keys to
    where the scores' scale matters, the states made slow to forget (so
    that rounding and a stale block accumulate), and the tied matrix
    and the final norm to logits a few tenths apart."""
    params = granite_hybrid.init(jax.random.key(5), cfg)
    for p in params["layers"]:
        if "w_in" in p:
            p["w_out"] = p["w_out"] * 400
            p["A_log"] = p["A_log"] - 4.0
            p["dt_bias"] = p["dt_bias"] + 3.0
        else:
            p["wo"] = p["wo"] * 400
            p["wq"], p["wk"] = p["wq"] * 8, p["wk"] * 8
        p["w2"], p["s_down"] = p["w2"] * 400, p["s_down"] * 400
    params["embed"] = params["embed"] * 100
    params["norm_f"] = params["norm_f"] * 8
    return params


@pytest.fixture(scope="module")
def served():
    """(cfg, params, [(what, prompt, answer)], the served chunk
    program's row) of the sharpened small model in float32, with the
    answer its own greedy tokens."""
    cfg, _ = harness.model_config(_config(), rehearse=True)
    params = _sharpened(cfg)
    prompt = np.random.default_rng(2).integers(
        0, cfg.vocab_size, 90).tolist()
    return (cfg, params, [("x", prompt,
                           _served_answer(cfg, params, prompt, 22))],
            {0: reference.served_row(params, cfg, prompt)})


def test_reference_pools_margins_and_judges_them(served):
    cfg, params, answers, _ = served
    _, prompt, rest = answers[0]
    r = reference.compare(params, cfg, moe.route,
                          [("a", prompt, rest), ("b", prompt, rest[:10])])
    assert r["n"] == 32 and len(r["lines"]) == 2
    # In float32 the sound reading is exact, the served router is the
    # reference's on identical inputs, and the chunk program's row the
    # reference's.
    assert r["exact"] == 32 and r["worst"] == 0.0
    assert r["router_same"] == r["router_total"] \
        == cfg.num_hidden_layers * (112 + 100)
    assert r["router_weight_diff"] < 1e-6 and r["state_diff"] < 1e-4
    assert r["row_diff"] < 1e-5
    assert "last prompt row" in r["lines"][0] \
        and "last prompt row" not in r["lines"][1]
    good = {"n": 400, "exact": 390, "worst": 0.001, "mean": 0.00002,
            "router_same": 9995, "router_total": 10000,
            "router_weight_diff": 1e-6, "state_diff": 1e-6,
            "state_dtype": "float32", "row_diff": 0.001}
    assert all(ok for ok, _ in reference.token_checks(good))
    assert all(ok for ok, _ in reference.router_checks(good))
    for bad in ({"exact": 0}, {"mean": 10.0}, {"worst": 10.0}, {"n": 0}):
        assert not all(ok for ok, _ in reference.token_checks(
            dict(good, **bad))), bad
    for bad in ({"router_same": 9800}, {"router_weight_diff": 0.5},
                {"router_total": 0}, {"state_diff": 0.01},
                {"state_dtype": "bfloat16"}, {"row_diff": 1.0},
                {"row_diff": None}):
        assert not all(ok for ok, _ in reference.router_checks(
            dict(good, **bad))), bad


@pytest.mark.parametrize("fault", [*reference.FAULTS, "lower"])
def test_each_planted_fault_fails_a_limit_at_the_small_size(served, fault):
    """The served path's own answers read against the reference with
    one fault planted: some limit fails. A state rounded to bfloat16 on
    either side fails the state's (as one precision lower does: 12
    experts' logits 0.05 apart keep their order in bfloat16, which 72
    at the published widths do not: PERF.md section 6 has the chip's
    reading); ``logits_scaling`` left out moves no token and fails the
    row's; the others fail the tokens' or the row's."""
    cfg, params, answers, rows = served
    if fault == "lower":
        bf16 = dataclasses.replace(cfg, dtype="bfloat16")
        r = reference._read(
            jax.tree_util.tree_map(
                lambda a: a.astype(jnp.bfloat16) if a.ndim > 1 else a,
                params), bf16, moe.route, answers, True)
        _, _, state, _, _ = [ok for ok, _ in reference.router_checks(r)]
        assert not state, r
        return
    r = reference._read(params, cfg, moe.route, answers, False, fault, rows)
    tokens = all(ok for ok, _ in reference.token_checks(r))
    agree, weights, state, dtype, row = [
        ok for ok, _ in reference.router_checks(r)]
    assert agree and weights and dtype
    if fault in ("state_in_bfloat16", "served_state_in_bfloat16"):
        assert not state, r
    elif fault == "logits_scaling_left_out":
        assert tokens and state and not row, r
    else:
        assert state and not (tokens and row), (fault, r)


# -- the cost functions and the new readers ----------------------------------

FIELDS = _config()["model"]["fields"]


def test_cost_functions_count_the_needed_work():
    as_ssm = granite_hybrid_cost.as_ssm_fields(FIELDS)
    assert as_ssm["hybrid_override_pattern"] == "MMMMM*MMMM"
    # 64 lanes x 9 layers x 128 x 64 x 128 float32, in once and out once.
    assert ssm_cost.state_values(as_ssm) == 1048576
    assert granite_hybrid_cost.update_bytes(64, FIELDS) \
        == 2 * 64 * 9 * 4194304
    assert granite_hybrid_cost.update_operations(64, FIELDS) \
        == 4 * 64 * 9 * 1048576
    # ONE group: a row's scores are 128 wide a block of 256 rows.
    assert granite_hybrid_cost.scan_operations(1024, FIELDS) \
        == pytest.approx(1024 * 9 * (256 * (128 + 8192) + 4 * 1048576))
    # A row: x in and y out (8,192 each), B and C (128 each), dt (128),
    # in bfloat16; a span's state in and out in float32.
    assert granite_hybrid_cost.scan_bytes(1024, 1, FIELDS) == 9 * (
        1024 * (2 * 8192 + 2 * 128 + 128) * 2 + 2 * 4194304)
    # 6 x 4,096 x 768 operations an assignment, 3 x 4,096 x 768
    # parameters an expert hit, ten layers.
    assert granite_hybrid_cost.moe_operations(320, FIELDS) \
        == 6 * 4096 * 768 * 320 * 10
    assert granite_hybrid_cost.moe_bytes_read(36, FIELDS) \
        == 3 * 4096 * 768 * 2 * 36 * 10


def _collected(**over):
    step = {"name": "llm.step", "lanes": 60, "decode_tokens": 60,
            "moe_experts_hit": 36, "moe_held_rows": 300,
            "prefill_chunks": [[1024, 256, 40.0, 1.0]],
            "phases_ms": {"llm.admit": 0.2}}
    update = "%ssm_update.9 = (f32[64,2,64,128], f32[9,97,128,64,128]) " \
             "custom-call(...)"
    moe_k = "%moe_experts_decode.3 = bf16[1216,1536]{1,0} custom-call(...)"
    scan = "%ssm_scan.4 = (f32[8,4,16,256,64], f32[8,16,64,128]) " \
           "custom-call(...)"
    c = {
        "model_fields": FIELDS, "device": {"kind": "TPU v5 lite"},
        "engine_steps": [
            dict(step), dict(step),
            dict(step, prefill_chunks=[[512, 256, 22.0, 1.0]]),
            dict(step, lanes=0, decode_tokens=0, moe_held_rows=0,
                 prefill_chunks=[])],
        "trace": {"modules": {"jit_llm_decode(1)": [10, 0.3],
                              "jit_llm_prefill_chunk(2)": [8, 0.7]},
                  "op_self_s": {update: 0.100, moe_k: 0.120, scan: 0.080},
                  "op_calls": {update: 90, moe_k: 200, scan: 72}},
    }
    c.update(over)
    return c


def _reader(name, c):
    return harness.load_module("layer_metrics", name).read(c)


def test_new_readers_read_a_hand_made_collected():
    c = _collected()
    # 100 ms of the kernel in 10 executions of the decode program; 60
    # live lanes: 2 x 60 x 9 x 4.19 MB at 819 GB/s is 5.53 ms.
    need = 2 * 60 * 9 * 4194304 / 819e9
    assert _reader("hybrid_ssm_update_roofline_pct", c) == pytest.approx(
        100 * need / 10e-3)
    assert 50 < _reader("hybrid_ssm_update_roofline_pct", c) < 60
    # 36 experts hit a layer: 36 x 10 x 18.9 MB at 819 GB/s is 8.30 ms
    # of a 12 ms step; the 300 assignments are far under the ridge.
    need = 36 * 10 * 3 * 4096 * 768 * 2 / 819e9
    assert need > granite_hybrid_cost.moe_operations(300, FIELDS) / 197e12
    assert _reader("hybrid_moe_roofline_pct", c) == pytest.approx(
        100 * need / 12e-3)
    # 80 ms of the scan in 8 executions of the chunk program; the
    # window's chunks computed 1,024, 1,024 and 512 rows.
    rows = 2560 / 3
    need = max(
        granite_hybrid_cost.scan_bytes(rows, 1, FIELDS) / 819e9,
        granite_hybrid_cost.scan_operations(rows, FIELDS) / 197e12)
    assert _reader("hybrid_ssm_scan_roofline_pct", c) == pytest.approx(
        100 * need / 10e-3)
    for name in NEW:
        assert 0 < _reader(name, c) < 100, name


@pytest.mark.parametrize("name", NEW)
def test_new_readers_read_nothing_of_a_program_that_lacks_their_source(name):
    """The parent commit's programs and every other configuration's: no
    such field names, kernel or counter; None, and the line leaves the
    metric out. Nemotron's cell has both kernels under the same names
    and other field names: nothing there either."""
    step = {"name": "llm.step", "lanes": 60, "decode_tokens": 60,
            "phases_ms": {"llm.admit": 0.2}}
    c = _collected(
        model_fields=harness.read_json(
            "configs", "kimi-k25-serve.json")["model"]["fields"],
        engine_steps=[step],
        trace={"modules": {"jit_llm_decode(1)": [10, 0.2]},
               "op_self_s": {"%attn_latent.7 = bf16[1] custom-call(...)":
                             0.1}, "op_calls": {}})
    assert _reader(name, c) is None
    assert _reader(name, dict(c, trace=None, engine_steps=[])) is None
    assert _reader(name, dict(c, model_fields=None)) is None
    assert _reader(name, _collected(model_fields=harness.read_json(
        "configs", "nemotron3-super-serve.json")["model"]["fields"])) is None
    assert _reader(name, _collected(trace={
        "modules": {"jit_llm_decode(1)": [10, 0.2]}, "op_self_s": {},
        "op_calls": {}})) is None


def test_the_cell_rehearses_with_its_controls_logged():
    """The driver end to end at the rehearsal's sizes (float32, so every
    reading is exact): the comparisons that decide ``correct`` hold,
    every sharer's whole preamble is a hit THROUGH A SNAPSHOT, every
    program reader of the cell finds something to read, and with
    ``BENCH_GRANITE_CONTROLS`` set the reference one precision lower
    and its planted faults are read and logged, deciding nothing."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_GRANITE_CONTROLS="1")
    for other in ("BENCH_LAGUNA_CONTROLS", "BENCH_KIMI_CONTROLS",
                  "BENCH_NEMOTRON_CONTROLS", "BENCH_XING_CONTROLS"):
        env.pop(other, None)
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "2147483999", "--seconds", "4",
         "--trace", "0", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    out = p.stdout.splitlines()
    assert p.returncode == 3, p.stdout[-2000:] + p.stderr[-2000:]
    assert not [x for x in out if "[FAIL]" in x]
    for said in ("compared tokens are the float32 reference's argmax",
                 "mean reference margin of the compared tokens",
                 "worst reference margin of a compared token",
                 "pick the same experts", "their weights differ by at most",
                 "the served scan, update and pool of state slots differ",
                 "the served pool of state slots holds S in float32",
                 "the served chunk program's logits row",
                 "report their whole prefix cached"):
        assert [x for x in out if "[ok]" in x and said in x], said
    controls = [x for x in out if "control, " in x]
    assert len(controls) == 9 * 9       # a summary and eight limits each
    for name in ("one precision lower", *reference.FAULTS.values()):
        assert sum(f"control, {name}:" in x for x in controls) == 9
    found = next(x for x in out if "layer_metrics:" in x)
    for x in _manifest()["per_layer"]:
        if CELL in x["workloads"] and x["source"] != "device_trace":
            assert f"'{x['name']}'" in found, (x["name"], found)
