"""What PR 57 added to the benchmark for `nemotron3s-serve-agent`,
checked without a chip: the configuration's file against the catalog's
row, the benchmark's own copy of the plain reference against the
repository's, its limits against each planted fault at the small size,
the new readers and cost functions on hand-made inputs, the cell's
traffic, and the cell's rehearsal."""

import dataclasses
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

from benchmark import (harness, latent_moe_cost,  # noqa: E402
                       reference_nemotron_h, ssm_cost, traffic)
from ray_tpu.models import nemotron_h, nemotron_h_ref  # noqa: E402
from ray_tpu.ops import moe  # noqa: E402

CELL, CONFIG = "nemotron3s-serve-agent", "nemotron3-super-serve"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = {"num_hidden_layers": (88, 11), "n_routed_experts": (512, 64),
           "vocab_size": (131072, 16384)}
NEW = ("ssm_update_ms", "ssm_update_roofline_pct", "ssm_scan_ms",
       "ssm_scan_roofline_pct", "latent_moe_roofline_pct",
       "state_live_peak_pct", "state_resume_pct", "state_snapshot_ms")


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
                   if r["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16")
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
    # Every width, the router's 512 outputs and its 22 experts a token
    # as published; depth, the experts HELD and the vocabulary cut.
    for key in ("hidden_size", "num_attention_heads", "num_key_value_heads",
                "head_dim", "mamba_num_heads", "mamba_head_dim", "n_groups",
                "ssm_state_size", "conv_kernel", "chunk_size",
                "moe_latent_size", "moe_intermediate_size",
                "moe_shared_expert_intermediate_size", "n_shared_experts",
                "num_experts_per_tok", "routed_scaling_factor",
                "norm_topk_prob", "n_group", "topk_group", "mlp_hidden_act",
                "mamba_hidden_act", "use_conv_bias", "time_step_min",
                "time_step_max", "time_step_floor", "num_hidden_layers",
                "vocab_size"):
        assert f[key] == cfg[key], key
    assert f["layer_norm_epsilon"] == cfg["layer_norm_epsilon"] \
        == cfg["norm_eps"]
    assert f["n_routed_experts"] == cfg["published"]["n_routed_experts"] \
        == 512
    assert f["experts_held"] == cfg["n_routed_experts"] == 64
    assert f["first_expert"] == 0 and 512 // 64 == 8
    # One whole period, in the published order, from the published
    # pattern (which the file's top level keeps whole).
    assert f["hybrid_override_pattern"] == "EMEMEMEMEM*" \
        == cfg["hybrid_override_pattern"][26:37]
    assert len(cfg["hybrid_override_pattern"]) == 88
    model, _ = harness.model_config(cfg, rehearse=False)
    assert round(model.num_params() / 1e6) == 2752     # 5.50 GB in bfloat16
    state = nemotron_h.state_kind(model)
    assert state.slot_bytes == cfg["state"]["bytes_per_slot"] \
        == 5 * cfg["state"]["bytes_per_layer"] == 21278720
    kw = cfg["serve"]["kwargs"]
    assert kw["state_slots"] == cfg["state"]["state_slots"] == 129
    assert kw["state_slots"] - 1 == kw["max_batch"] + 16 + 48
    assert set(cfg["assumed"]) == {
        "no_rotary", "latent_projections", "ssm_state_float32",
        "router_bias", "ssm_parameters", "not_of_the_forward_pass",
        "weights", "max_seq", "deployment", "dtype"}
    assert "8 chips of a host share each layer" in \
        cfg["assumed"]["deployment"]
    assert "ONE chip's share" in cfg["assumed"]["deployment"]
    assert cfg["reference"]["module"] == "benchmark.reference_nemotron_h"
    assert reference_nemotron_h.served_router_of(cfg) is moe.route_sigmoid


def test_rehearsal_sizes_keep_what_the_cell_is_about():
    tiny, _ = harness.model_config(_config(), rehearse=True)
    letters = tiny.hybrid_override_pattern
    assert set(letters) == {"M", "E", "*"} and letters.count("M") >= 2
    assert tiny.mamba_num_heads > tiny.n_groups > 1
    assert tiny.moe_latent_size < tiny.hidden_size          # a latent space
    assert tiny.n_routed_experts >= 16 and tiny.num_experts_per_tok >= 2
    assert 0 < tiny.experts_held < tiny.n_routed_experts    # a share
    assert tiny.vocab_size < 131072                         # a slice
    kw = _config()["serve"]["rehearse"]["kwargs"]
    assert kw["state_slots"] - 1 >= kw["max_batch"]


def test_pool_and_traffic_are_what_the_issue_names():
    cfg, cell = _config(), _cell()
    kw, spec = cfg["serve"]["kwargs"], cell["traffic"]
    assert (kw["num_blocks"], kw["block_size"], kw["max_batch"],
            kw["prefill_chunk_tokens"], kw["prefix_cache"]) == \
        (9216, 16, 64, 512, True)
    bs = kw["block_size"]
    shared = spec["prefixes"]["count"] * spec["prefixes"]["tokens"] // bs
    own = kw["max_batch"] * -(-(spec["body_tokens"]["max"]
                                + spec["max_tokens"]["max"]) // bs)
    assert shared == 4096 and own == 64 * 48
    assert shared + own < kw["num_blocks"] - 1
    assert spec["max_total_tokens"] == cfg["model"]["fields"]["max_seq"] \
        == 4864 == 4096 + 512 + 256
    pool = traffic.size_pool(spec)
    assert len(pool) == 256
    assert {b for b, _ in pool} == {128, 256, 384, 512}
    assert min(a for _, a in pool) >= 64 and max(a for _, a in pool) <= 256
    plan = traffic.closed_loop_plan(spec, 2147483777, 16384)
    assert len(plan["prefixes"]) == 16
    assert all(len(p) == 4096 and max(p) < 16384 for p in plan["prefixes"])
    sharers = [c["prefix"] for c in plan["callers"]]
    assert len(sharers) == 64 == kw["max_batch"]
    assert all(sharers.count(i) == 4 for i in range(16))
    # Every prompt is whole blocks: its snapshot is taken at its end and
    # no short extra span runs.
    assert all((4096 + b) % bs == 0 for b, _ in pool)
    assert (spec["pool_size"], spec["pairing_seed"], spec["stagger_s"],
            spec["ramp_s"]) == (256, 23, 10.0, 16.0)
    assert cell["reference_request"]["prompt_tokens"] == 1536
    assert cell["reference_request"]["max_tokens"] == 64
    assert cell["driver"] == "serve_closed_loop_ref"
    assert cell["compare_prefixes"] == 4 and cell["config"] == CONFIG
    kimi = harness.read_json("workloads", "kimi-k25-serve-docs.json")
    assert cell["window"] == kimi["window"]


def test_manifest_lists_the_cell_where_the_issue_says():
    m = _manifest()
    (cell,) = [w for w in m["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "agent-closed-64", 1)
    assert cell["why"] == _cell()["why"] and len(cell["why"]) <= 200
    (config,) = [c for c in m["configs"] if c["name"] == CONFIG]
    assert config["reduced"] == list(REDUCED)
    assert config["file"] == f"benchmark/configs/{CONFIG}.json"
    assert config["source"] == _config()["source"]
    assert sum(w["chips"] for w in m["workloads"]) == len(m["workloads"]) >= 5
    e2e = {x["name"] for x in m["end_to_end"]
           if CELL in x.get("workloads", [CELL])}
    assert e2e == {"serve_tokens_per_s", "setup_s"}
    by_name = {x["name"]: x for x in m["per_layer"]}
    for name in NEW:
        # In the cell alone when PR 57 added them; since PR 64 a second
        # model names the two kernels and keeps a state, and reads its
        # shares of a roofline from files of its own.
        assert by_name[name]["workloads"] == [CELL] + (
            [] if "roofline" in name else ["granite4hs-serve-chat"]), name
        assert by_name[name]["moves"] == "serve_tokens_per_s", name
    for name in ("batch_occupancy_pct", "itl_p95_ms", "itl_p99_long_ms",
                 "engine_host_gap_ms", "kv_live_peak_pct",
                 "kv_run_pages_pct", "decode_step_ms", "decode_device_ms",
                 "device_idle_pct.serve", "engine_schedule_ms",
                 "decode_lanes_pct", "stream_hold_ms", "chunk_attn_ms",
                 "moe_expert_ms", "moe_load_max", "moe_held_rows",
                 "callers_cpu_pct"):
        # Last when PR 57 appended it; later cells stand behind it.
        after = by_name[name]["workloads"]
        after = after[after.index(CELL) + 1:]
        assert after in (["granite4hs-serve-chat"],
                         ["xing4-serve-rag", "granite4hs-serve-chat"]), name
    # Left to a ``benchmark`` PR: the issue names these by group only
    # ("the engine's"), or not at all (``attn_full_ms``), and tests
    # under benchmark/tests, which this kind of PR may not edit, pin
    # their lists by equality.
    for name in ("attn_full_ms", "decode_dispatch_ms", "chunk_dispatch_ms",
                 "engine_stall_ms", "engine_stall_p99_ms",
                 "engine_lock_wait_ms", "step_interval_p99_ms",
                 "gc_ms_per_s", "gc_pause_max_ms", "engine_starved_pct",
                 "step_queued_pct", "decode_inputs_written_pct",
                 "tokens_per_handover"):
        assert CELL not in by_name[name]["workloads"], name
    # Their readers count another configuration's fields, or move a
    # metric the cell does not report.
    for x in m["per_layer"]:
        if x["name"] in ("moe_roofline_pct", "attn_full_roofline_pct",
                         "attn_latent_ms", "attn_latent_roofline_pct",
                         "latent_chunk_ms", "attn_window_ms",
                         "kv_window_live_pct", "paged_kernel_ms",
                         "paged_roofline_pct") \
                or x["moves"] != "serve_tokens_per_s":
            assert CELL not in x["workloads"], x["name"]
        if CELL in x.get("workloads", []):
            assert os.path.isfile(os.path.join(
                ROOT, "benchmark", "layer_metrics",
                x["name"].split(".")[0] + ".py")), x["name"]


# -- the benchmark's own reference -------------------------------------------


@pytest.fixture(scope="module")
def tiny():
    cfg, _ = harness.model_config(_config(), rehearse=True)
    return cfg, nemotron_h.init(jax.random.key(3), cfg)


def test_benchmark_reference_equals_the_repositorys(tiny):
    """Two copies of the same equations, written apart: the benchmark's
    (padded, attention in blocks of queries, experts in a scan) and
    models/nemotron_h_ref.py's."""
    cfg, params = tiny
    seq = np.random.default_rng(0).integers(0, cfg.vocab_size, 90).tolist()
    got, router_inputs, (state, dtype) = reference_nemotron_h.forward(
        params, cfg, seq[:70], seq[70:])
    # The served scan, update and pool against the recurrence.
    assert state < 1e-4 and dtype == "float32"
    want = np.asarray(nemotron_h_ref.forward(params, seq, cfg))[69:89]
    assert got.shape == want.shape == (20, cfg.vocab_size)
    assert np.abs(got - want).max() < 2e-5
    # One router input an expert layer, the real tokens only.
    assert sorted(router_inputs) == list(cfg.layers_of("E"))
    assert all(h.shape == (90, cfg.hidden_size)
               for h in router_inputs.values())


def _served_answer(cfg, params, prompt, n):
    """``n`` greedy tokens of the served path's mathematics: the
    repository's reference stands in for the engine here (they are
    equal to 1e-7 at float32, tests/test_nemotron_h.py)."""
    forward = jax.jit(lambda toks: nemotron_h_ref.forward(params, toks, cfg))
    buf = np.zeros((len(prompt) + n,), np.int32)
    buf[:len(prompt)] = prompt
    for i in range(len(prompt), len(buf)):
        buf[i] = int(np.asarray(forward(buf))[i - 1].argmax())
    return buf[len(prompt):].tolist()


def _sharpened(cfg):
    """Parameters of the small size at which a fault in the state can
    show. At std 0.02 a 64-wide model's mixers add ~0.1 to a residual
    of ~1 and its logits lie ~0.05 apart, so the state-space layers'
    output projections are scaled up to where they carry the residual,
    their states made slow to forget (so that rounding and a stale
    block accumulate) and the head to logits ~3 apart."""
    params = nemotron_h.init(jax.random.key(5), cfg)
    for p in params["layers"]:
        if "w_in" in p:
            p["w_out"] = p["w_out"] * 30
            p["A_log"] = p["A_log"] - 4.0
            p["dt_bias"] = p["dt_bias"] + 3.0
        for name in {"w_up", "s2", "wo"} & set(p):
            p[name] = p[name] * 0.2
    params["head"] = params["head"] * 60
    return params


@pytest.fixture(scope="module")
def served():
    """(cfg, params, [(what, prompt, answer)]) of the sharpened small
    model in float32, with the answer its own greedy tokens."""
    cfg, _ = harness.model_config(_config(), rehearse=True)
    params = _sharpened(cfg)
    prompt = np.random.default_rng(2).integers(
        0, cfg.vocab_size, 96).tolist()
    return cfg, params, [("x", prompt,
                          _served_answer(cfg, params, prompt, 16))]


def test_reference_pools_margins_and_judges_them(served):
    cfg, params, answers = served
    _, prompt, rest = answers[0]
    r = reference_nemotron_h.compare(params, cfg, moe.route_sigmoid,
                                     [("a", prompt, rest),
                                      ("b", prompt, rest[:10])])
    assert r["n"] == 26 and len(r["lines"]) == 2
    # In float32 the sound reading is exact, and the served router is
    # the reference's on identical inputs to the last weight.
    assert r["exact"] == 26 and r["worst"] == 0.0
    routed = len(cfg.layers_of("E"))
    assert r["router_same"] == r["router_total"] == routed * (112 + 106)
    assert r["router_weight_diff"] < 1e-6 and r["state_diff"] < 1e-4
    good = {"n": 400, "exact": 390, "worst": 0.1, "mean": 0.0005,
            "router_same": 9995, "router_total": 10000,
            "router_weight_diff": 1e-6, "state_diff": 1e-6,
            "state_dtype": "float32"}
    assert all(ok for ok, _ in reference_nemotron_h.token_checks(good))
    assert all(ok for ok, _ in reference_nemotron_h.router_checks(good))
    for bad in ({"exact": 0}, {"mean": 10.0}, {"worst": 10.0}, {"n": 0}):
        assert not all(ok for ok, _ in reference_nemotron_h.token_checks(
            dict(good, **bad))), bad
    for bad in ({"router_same": 9800}, {"router_weight_diff": 0.5},
                {"router_total": 0}, {"state_diff": 0.01},
                {"state_dtype": "bfloat16"}):
        assert not all(ok for ok, _ in reference_nemotron_h.router_checks(
            dict(good, **bad))), bad


@pytest.mark.parametrize("fault", [*reference_nemotron_h.FAULTS, "lower"])
def test_each_planted_fault_fails_a_limit_at_the_small_size(served, fault):
    """The served path's own answers read against the reference with
    one fault planted: some limit fails. The router's fault (and the
    router's scores one precision lower) fail the router's limits, a
    state rounded to bfloat16 on either side the state's, the two
    faults at the prompt's last block boundary the tokens'."""
    cfg, params, answers = served
    read = functools.partial(reference_nemotron_h._read, params, cfg,
                             moe.route_sigmoid, answers)
    if fault == "lower":
        bf16 = dataclasses.replace(cfg, dtype="bfloat16")
        r = reference_nemotron_h._read(
            jax.tree_util.tree_map(
                lambda a: a.astype(jnp.bfloat16) if a.ndim > 1 else a,
                params), bf16, moe.route_sigmoid, answers, True)
        failed = [ok for ok, _ in reference_nemotron_h.router_checks(r)]
        assert not failed[0] and not failed[2], r
        return
    r = read(False, fault)
    agree, _, state, dtype = [
        ok for ok, _ in reference_nemotron_h.router_checks(r)]
    assert dtype
    if fault == "one_expert_fewer":
        assert not agree, r
    elif fault in ("state_in_bfloat16", "served_state_in_bfloat16"):
        assert not state and agree, r
    else:
        assert not all(ok for ok, _ in
                       reference_nemotron_h.token_checks(r)), (fault, r)


# -- the cost functions and the new readers ----------------------------------

FIELDS = _config()["model"]["fields"]


def test_cost_functions_count_the_needed_work():
    # 64 lanes x 5 layers x 128 x 64 x 128 float32, in once and out once.
    assert ssm_cost.state_values(FIELDS) == 1048576
    assert ssm_cost.update_bytes(64, FIELDS) == 2 * 64 * 5 * 4194304
    assert ssm_cost.update_operations(64, FIELDS) == 4 * 64 * 5 * 1048576
    assert ssm_cost.scan_operations(512, FIELDS) == pytest.approx(
        512 * 5 * (128 * (1024 + 8192) + 4 * 1048576))
    # A row: x in and y out (8,192 each), B and C (1,024 each), dt (128),
    # in bfloat16; a span's state in and out in float32.
    assert ssm_cost.scan_bytes(512, 1, FIELDS) == 5 * (
        512 * (2 * 8192 + 2 * 1024 + 128) * 2 + 2 * 4194304)
    # 4 x 1,024 x 2,688 operations an assignment, 2 x 1,024 x 2,688
    # parameters an expert hit, five expert layers.
    assert latent_moe_cost.operations(176, FIELDS) == \
        4 * 1024 * 2688 * 176 * 5
    assert latent_moe_cost.bytes_read(60, FIELDS) == \
        2 * 1024 * 2688 * 2 * 60 * 5


def _collected(**over):
    step = {"name": "llm.step", "lanes": 60, "decode_tokens": 60,
            "moe_experts_hit": 60, "moe_held_rows": 165,
            "prefill_chunks": [[512, 4096, 20.0, 1.0]],
            "phases_ms": {"llm.admit": 0.2}}
    update = "%ssm_update.9 = (f32[64,2,64,128], f32[5,129,128,64,128]) " \
             "custom-call(...)"
    moe_k = "%moe_experts_decode.3 = bf16[2368,2688]{1,0} custom-call(...)"
    scan = "%ssm_scan.4 = (f32[8,4,16,128,64], f32[8,16,64,128]) " \
           "custom-call(...)"
    c = {
        "model_fields": FIELDS, "device": {"kind": "TPU v5 lite"},
        "engine_steps": [
            dict(step, phases_ms={"llm.state_snapshot": 0.3}),
            dict(step, phases_ms={"llm.state_snapshot": 0.5}),
            dict(step, prefill_chunks=[[256, 4096, 12.0, 1.0]]),
            dict(step, lanes=0, decode_tokens=0, moe_held_rows=0,
                 prefill_chunks=[])],
        "engine_stats": (
            {"state_resumed_tokens": 4096, "state_recomputed_tokens": 8192},
            {"state_resumed_tokens": 4096 * 101,
             "state_recomputed_tokens": 8192, "state_live_peak": 0.625}),
        "trace": {"modules": {"jit_llm_decode(1)": [10, 0.2],
                              "jit_llm_prefill_chunk(2)": [8, 0.7]},
                  "op_self_s": {update: 0.040, moe_k: 0.050, scan: 0.024},
                  "op_calls": {update: 50, moe_k: 100, scan: 40}},
    }
    c.update(over)
    return c


def _read(name, c):
    return harness.load_module("layer_metrics", name).read(c)


def test_new_readers_read_a_hand_made_collected():
    c = _collected()
    # 40 ms of the kernel in 10 executions of the decode program.
    assert _read("ssm_update_ms", c) == pytest.approx(4.0)
    # 60 live lanes: 2 x 60 x 5 x 4.19 MB at 819 GB/s is 3.07 ms.
    need = 2 * 60 * 5 * 4194304 / 819e9
    assert _read("ssm_update_roofline_pct", c) == pytest.approx(
        100 * need / 4e-3)
    assert 70 < _read("ssm_update_roofline_pct", c) < 80
    # 60 experts hit a layer: 60 x 5 x 11.0 MB at 819 GB/s is 4.03 ms of
    # a 5 ms step; the 165 assignments are far under the ridge.
    need = 60 * 5 * 2 * 1024 * 2688 * 2 / 819e9
    assert _read("latent_moe_roofline_pct", c) == pytest.approx(
        100 * need / 5e-3)
    # 24 ms of the scan in 8 executions of the chunk program; the
    # window's chunks computed 512, 512 and 256 rows.
    assert _read("ssm_scan_ms", c) == pytest.approx(3.0)
    need = ssm_cost.scan_bytes(1280 / 3, 1, FIELDS) / 819e9
    assert need > ssm_cost.scan_operations(1280 / 3, FIELDS) / 197e12
    assert _read("ssm_scan_roofline_pct", c) == pytest.approx(
        100 * need / 3e-3)
    assert _read("state_live_peak_pct", c) == pytest.approx(62.5)
    assert _read("state_resume_pct", c) == pytest.approx(100.0)
    assert _read("state_snapshot_ms", c) == pytest.approx(0.4)


@pytest.mark.parametrize("name", NEW)
def test_new_readers_read_nothing_of_a_program_that_lacks_their_source(name):
    """The parent commit, and every other configuration: no such
    kernel, counter or phase; None, and the line leaves the metric
    out."""
    step = {"name": "llm.step", "lanes": 60, "decode_tokens": 60,
            "phases_ms": {"llm.admit": 0.2}}
    c = _collected(
        model_fields=harness.read_json(
            "configs", "kimi-k25-serve.json")["model"]["fields"],
        engine_steps=[step], engine_stats=({}, {"kv_util_peak": 0.5}),
        trace={"modules": {"jit_llm_decode(1)": [10, 0.2]},
               "op_self_s": {"%attn_latent.7 = bf16[1] custom-call(...)":
                             0.1}, "op_calls": {}})
    assert _read(name, c) is None
    assert _read(name, dict(c, trace=None, engine_stats=None,
                            engine_steps=[])) is None


def test_the_cell_rehearses_with_its_controls_logged():
    """The driver end to end at the rehearsal's sizes (float32, so every
    reading is exact): the comparisons that decide ``correct`` hold,
    every sharer's whole prefix is a hit THROUGH A SNAPSHOT, every
    program reader of the cell finds something to read, and with
    ``BENCH_NEMOTRON_CONTROLS`` set the reference one precision lower
    and its planted faults are read and logged, deciding nothing."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_NEMOTRON_CONTROLS="1")
    for other in ("BENCH_LAGUNA_CONTROLS", "BENCH_KIMI_CONTROLS"):
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
                 "report their whole prefix cached"):
        assert [x for x in out if "[ok]" in x and said in x], said
    controls = [x for x in out if "control, " in x]
    assert len(controls) == 6 * 8       # a summary and seven limits each
    for name in ("one precision lower",
                 *reference_nemotron_h.FAULTS.values()):
        assert sum(f"control, {name}:" in x for x in controls) == 8
    found = next(x for x in out if "layer_metrics:" in x)
    for x in _manifest()["per_layer"]:
        if CELL in x["workloads"] and x["source"] != "device_trace":
            assert f"'{x['name']}'" in found, (x["name"], found)
