"""What PR 62 added to the benchmark for `xing4-serve-rag`, checked
without a chip: the configuration's file against the catalog's row, the
benchmark's own copy of the plain reference against the repository's,
its limits against each planted fault at the small size, the new readers
on hand-made inputs, the cell's traffic, and the cell's rehearsal."""

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

from benchmark import (harness, mhc_cost, moe_routed_cost,  # noqa: E402
                       reference_xing4, traffic)
from ray_tpu.models import xing4, xing4_ref  # noqa: E402
from ray_tpu.ops import moe  # noqa: E402

CELL, CONFIG = "xing4-serve-rag", "xing4-29b-serve"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = {"num_hidden_layers": (40, 7), "first_k_dense_replace": (2, 1)}
SHARED = ("attn_latent_ms", "attn_latent_roofline_pct", "latent_chunk_ms",
          "moe_expert_ms", "moe_load_max", "chunk_attn_ms",
          "kv_run_pages_pct", "itl_p99_long_ms", "itl_p95_ms",
          "batch_occupancy_pct", "engine_host_gap_ms", "kv_live_peak_pct",
          "decode_step_ms", "decode_device_ms", "device_idle_pct.serve",
          "engine_schedule_ms", "engine_sample_ms", "engine_emit_ms",
          "engine_between_ms", "decode_lanes_pct", "stream_hold_ms",
          "stream_out_ms", "callers_cpu_pct", "frames_per_wake",
          "interp_wait_ms", "interp_held_pct", "machine_standstill_ms.serve",
          "serve_cpu_us_per_frame", "runtime_cpu_us_per_frame")
# ISSUE 62's five, and the chunk program's grouped product: the first
# traced run named it as a kernel no reader of the cell counts, 40% of
# the device's busy time (PERF.md section 6, PR 62).
NEW = ("mhc_chunk_ms", "mhc_chunk_roofline_pct", "mhc_decode_ms",
       "mhc_res_err", "moe_routed_roofline_pct", "moe_expert_chunk_ms")
# Appended behind them since, in the cell alone (PR 63: whether the
# grouped product's tall tile engaged).
LATER = ("moe_chunk_wide_tile_pct",)
# ... and behind every cell's entries, with Kimi's cell (PR 67: the
# spans a chunk program carried).
APPENDED = ("chunk_spans_per_program",)
# The cell PR 64 appended behind this one.
LATER_CELL = "granite4hs-serve-chat"


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
                   if r["name"] == "Xing4.0-29B-A4B")
    cfg = _config()
    assert cfg["source"] == row["source_url"]
    assert cfg["reduced"] == list(REDUCED)
    for key, value in row["config"].items():
        if key in REDUCED:
            assert (value, cfg[key]) == REDUCED[key], key
            assert cfg["published"][key] == value
        else:
            assert cfg[key] == value, key


def test_run_configuration_holds_whole_layers_at_published_widths():
    cfg = _config()
    f = cfg["model"]["fields"]
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "num_attention_heads", "q_lora_rank", "kv_lora_rank",
                "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
                "n_routed_experts", "n_shared_experts",
                "num_experts_per_tok", "first_k_dense_replace",
                "moe_layer_freq", "routed_scaling_factor", "norm_topk_prob",
                "scoring_func", "topk_method", "n_group", "topk_group",
                "rms_norm_eps", "rope_theta", "rope_scaling",
                "num_hidden_layers", "vocab_size", "hc_mult",
                "hc_sinkhorn_iters", "hc_eps", "mhc_h_res_clamp_min",
                "mhc_h_res_clamp_max", "num_nextn_predict_layers"):
        assert f[key] == cfg[key], key
    # Every expert, the whole vocabulary, four streams, twenty iterations.
    assert f["experts_held"] == f["n_routed_experts"] == 64
    assert (f["vocab_size"], f["hc_mult"], f["hc_sinkhorn_iters"]) == \
        (131072, 4, 20)
    model, _ = harness.model_config(cfg, rehearse=False)
    assert [model.routed(l) for l in range(7)] == [False] + [True] * 6
    assert round(model.num_params() / 1e6, 1) == 5537.7    # 11.08 GB
    assert model.row_width * 2 == \
        cfg["cache_row"]["bytes_per_token_per_layer"] == 1280
    assert cfg["cache_row"]["bytes_per_token"] == 7 * 1280
    assert sorted(k[0] for k in cfg["assumed"] if k[1] == "_") \
        == list("abcdefg")
    assert "none of its parameters is held" in \
        cfg["assumed"]["g_mtp_module_not_held"]
    assert cfg["reference"]["module"] == "benchmark.reference_xing4"
    assert reference_xing4.served_router_of(cfg) is moe.route_sigmoid
    assert cfg["guarantees"] == harness.read_json(
        "configs", "kimi-k25-serve.json")["guarantees"]
    # The two programs' temporaries, as tests/test_tpu_compile.py reads
    # them for a described v5e, leave room beside weights and pool.
    temps = cfg["programs_compiled_for_a_described_v5e"]
    kw = cfg["serve"]["kwargs"]
    pool = kw["num_blocks"] * kw["block_size"] * 7 * 1280
    assert round(pool / 1e9, 2) == 2.79
    assert model.num_params() * 2 + pool + max(
        v for v in temps.values() if isinstance(v, int)) < 15.0e9


def test_rehearsal_sizes_keep_what_the_cell_is_about():
    tiny, _ = harness.model_config(_config(), rehearse=True)
    assert tiny.hc_mult == 4 and tiny.hc_sinkhorn_iters == 20
    assert not tiny.routed(0) and tiny.routed(1)
    assert tiny.experts_held == tiny.n_routed_experts >= 16
    assert tiny.q_lora_rank < tiny.hidden_size > tiny.kv_lora_rank


def test_pool_and_traffic_are_what_the_issue_names():
    cfg, cell = _config(), _cell()
    kw, spec = cfg["serve"]["kwargs"], cell["traffic"]
    assert (kw["num_blocks"], kw["block_size"], kw["max_batch"],
            kw["prefill_chunk_tokens"], kw["prefix_cache"]) == \
        (19456, 16, 64, 2048, True)
    bs = kw["block_size"]
    a_lane = (spec["prefixes"]["tokens"] + spec["body_tokens"]["max"]
              + spec["max_tokens"]["max"]) // bs
    assert a_lane == 296
    # Every lane at its longest, and the prefixes parked: no preemption.
    assert kw["max_batch"] * a_lane + spec["prefixes"]["count"] \
        * spec["prefixes"]["tokens"] // bs < kw["num_blocks"] - 1
    assert spec["max_total_tokens"] == cfg["model"]["fields"]["max_seq"] \
        == 4736
    pool = traffic.size_pool(spec)
    assert len(pool) == 256
    assert {b for b, _ in pool} == set(range(1024, 4097, 512))
    assert min(a for _, a in pool) >= 32 and max(a for _, a in pool) <= 128
    plan = traffic.closed_loop_plan(spec, 2147483777, 131072)
    assert len(plan["prefixes"]) == 8
    assert all(len(p) == 512 and max(p) < 131072 for p in plan["prefixes"])
    sharers = [c["prefix"] for c in plan["callers"]]
    assert len(sharers) == 64
    assert all(sharers.count(i) == 8 for i in range(8))
    assert (spec["pool_size"], spec["pairing_seed"], spec["stagger_s"],
            spec["ramp_s"]) == (256, 23, 10.0, 16.0)
    assert cell["reference_request"]["prompt_tokens"] == 1536
    assert cell["reference_request"]["max_tokens"] == 64
    assert cell["driver"] == "serve_closed_loop_ref"
    assert cell["compare_prefixes"] == 4 and cell["config"] == CONFIG
    # Every chunk length the window can see is a whole 512 and is warmed
    # by the driver's tails.
    every = spec["body_tokens"]["multiple_of"]
    assert every == 512 and kw["prefill_chunk_tokens"] % every == 0
    assert spec["prefixes"]["tokens"] % every == 0


def test_manifest_lists_the_cell_where_the_issue_says():
    m = _manifest()
    # Last when PR 62 appended them; a later cell stands behind.
    assert [w["name"] for w in m["workloads"]][5:] in (
        [CELL], [CELL, LATER_CELL])
    assert [c["name"] for c in m["configs"]][5] == CONFIG
    cell, config = m["workloads"][5], m["configs"][5]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "rag-closed-64", 1)
    assert config["reduced"] == list(REDUCED)
    assert config["file"] == f"benchmark/configs/{CONFIG}.json"
    assert config["source"] == _config()["source"]
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    assert cell["why"] == _cell()["why"]
    e2e = {x["name"] for x in m["end_to_end"]
           if CELL in x.get("workloads", [CELL])}
    assert e2e == {"serve_tokens_per_s", "setup_s"}
    by_name = {x["name"]: x for x in m["per_layer"]}
    # The new entries stand together in ISSUE 62's order, in the cell
    # alone; what later PRs appended stands behind them.
    names = [x["name"] for x in m["per_layer"]]
    at = names.index(NEW[0])
    assert tuple(names[at:at + len(NEW)]) == NEW
    assert tuple(names[at + len(NEW):])[:len(LATER)] == LATER
    for name in NEW + LATER:
        # PR 64's cell names ``moe_experts_chunk`` kernels too.
        assert by_name[name]["workloads"] in (
            [CELL], [CELL, LATER_CELL]), name
        assert by_name[name]["moves"] == "serve_tokens_per_s", name
    for name in SHARED:
        after = by_name[name]["workloads"]
        assert after[after.index(CELL) + 1:] in ([], [LATER_CELL]), name
    mine = {x["name"] for x in m["per_layer"] if CELL in x["workloads"]}
    assert mine == set(SHARED) | set(NEW) | set(LATER) | set(APPENDED)
    for name in APPENDED:
        assert by_name[name]["workloads"] == [CELL, "kimi-k25-serve-docs"]
    for name in mine:
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark", "layer_metrics",
            name.split(".")[0] + ".py")), name
    # One in four cells at most may take four chips; none does.
    assert all(w["chips"] == 1 for w in m["workloads"])


# -- the benchmark's own reference -------------------------------------------


@pytest.fixture(scope="module")
def tiny():
    cfg, _ = harness.model_config(_config(), rehearse=True)
    return cfg, xing4.init(jax.random.key(3), cfg)


def test_benchmark_reference_equals_the_repositorys(tiny):
    """Two copies of the same equations, written apart: the benchmark's
    (padded, heads leading, blocks of queries, experts one at a time)
    and models/xing4_ref.py's; and the served residual path, read on
    the reference's own streams, is the reference's to rounding."""
    cfg, params = tiny
    seq = np.random.default_rng(0).integers(0, cfg.vocab_size, 90).tolist()
    got, router_inputs, (coef, mix) = reference_xing4.forward(
        params, cfg, seq[:70], seq[70:])
    want = np.asarray(xing4_ref.forward(params, seq, cfg))[69:89]
    assert got.shape == want.shape == (20, cfg.vocab_size)
    assert np.abs(got - want).max() < 2e-5
    assert sorted(router_inputs) == [1, 2]
    assert all(h.shape == (90, cfg.hidden_size)
               for h in router_inputs.values())
    assert coef < 2e-6 and mix < 2e-6
    # A longer padding (a run pads every sequence to its longest) gives
    # the same logits.
    padded, _, _ = reference_xing4.forward(params, cfg, seq[:70], seq[70:],
                                           pad_to=2048)
    assert np.abs(padded - want).max() < 2e-5


def _served_answer(cfg, params, prompt, n):
    """``n`` greedy tokens of the served path's mathematics: the
    repository's reference stands in for the engine here (they are
    equal to 2e-7 at float32, tests/test_xing4.py)."""
    forward = jax.jit(lambda toks: xing4_ref.forward(params, toks, cfg))
    buf = np.zeros((len(prompt) + n,), np.int32)
    buf[:len(prompt)] = prompt
    for i in range(len(prompt), len(buf)):
        buf[i] = int(np.asarray(forward(buf))[i - 1].argmax())
    return buf[len(prompt):].tolist()


def test_reference_judges_and_each_planted_fault_fails_a_limit(tiny):
    """The served path's own answers (float32, so the sound reading is
    exact: every token equal, the routers alike to the last weight, the
    residual path the reference's) read against the reference with one
    fault planted: some limit fails, and for the four that touch the
    coefficients it is the residual path's."""
    cfg, params = tiny
    prompt = np.random.default_rng(2).integers(
        0, cfg.vocab_size, 100).tolist()
    answers = [("x", prompt, _served_answer(cfg, params, prompt, 12))]
    read = functools.partial(reference_xing4._read, params, cfg,
                             moe.route_sigmoid, answers)
    sound = read()
    assert sound["exact"] == sound["n"] == 12 and sound["worst"] == 0.0
    assert sound["router_same"] == sound["router_total"] == 2 * 112
    assert sound["mhc_diff"] < 2e-6 and sound["mhc_mix_diff"] < 2e-6
    assert all(ok for ok, _ in reference_xing4.token_checks(sound)
               + reference_xing4.router_checks(sound))
    for fault in (None, *reference_xing4.FAULTS):
        r = read(fault is None, fault)         # None: one precision lower
        oks = [ok for ok, _ in reference_xing4.router_checks(r)]
        if fault == "one_expert_fewer":
            assert not oks[0] and oks[2] and oks[3], r
        elif fault == "no_rope_term":
            # The attention's fault leaves the residual path's functions
            # alone; at 64 wide every softmax is flat and it moves the
            # logits by less than a token (on the chip: PERF.md).
            assert all(oks) and r["mhc_diff"] < 2e-6, r
        else:
            assert not oks[2], (fault, r)
    good = {"n": 400, "exact": 390, "worst": 0.1, "mean": 0.0005,
            "router_same": 9995, "router_total": 10000,
            "router_weight_diff": 1e-6, "mhc_diff": 1e-5,
            "mhc_mix_diff": 4e-3}
    assert all(ok for ok, _ in reference_xing4.token_checks(good)
               + reference_xing4.router_checks(good))
    for bad in ({"exact": 0}, {"mean": 10.0}, {"worst": 10.0}, {"n": 0}):
        assert not all(ok for ok, _ in reference_xing4.token_checks(
            dict(good, **bad))), bad
    for bad in ({"router_same": 9800}, {"router_weight_diff": 0.5},
                {"router_total": 0}, {"mhc_diff": 4e-3},
                {"mhc_mix_diff": 0.5}):
        assert not all(ok for ok, _ in reference_xing4.router_checks(
            dict(good, **bad))), bad


def test_the_cell_rehearses_with_its_controls_logged():
    """The driver end to end at the rehearsal's sizes (float32, so every
    reading is exact): the comparisons that decide ``correct`` hold,
    every program reader of the cell finds something to read, and with
    ``BENCH_XING_CONTROLS`` set the reference one precision lower and
    its planted faults are read and logged, deciding nothing."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_XING_CONTROLS="1")
    for other in ("BENCH_LAGUNA_CONTROLS", "BENCH_KIMI_CONTROLS"):
        env.pop(other, None)
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
                 "residual path's coefficients (mhc_pre) differ",
                 "its mixes (mhc_pre's h, mhc_post's X') differ",
                 "report their whole prefix cached"):
        assert [x for x in out if "[ok]" in x and said in x], said
    controls = [x for x in out if "control, " in x]
    names = ("one precision lower", *reference_xing4.FAULTS.values())
    assert len(controls) == len(names) * 8      # a summary and 7 limits
    for name in names:
        mine = [x for x in controls if f"control, {name}:" in x]
        assert len(mine) == 8, name
        # (At the rehearsal's width a softmax is flat: the rope part
        # left out moves no token there.)
        assert any(": fails: " in x for x in mine) \
            or name == reference_xing4.FAULTS["no_rope_term"], name
    found = next(x for x in out if "layer_metrics:" in x)
    for x in _manifest()["per_layer"]:
        if CELL in x["workloads"] and x["source"] != "device_trace":
            assert f"'{x['name']}'" in found, (x["name"], found)


# -- the new readers -----------------------------------------------------------

FIELDS = _config()["model"]["fields"]


def _collected(**over):
    step = {"name": "llm.step", "decode_tokens": 55, "moe_experts_hit": 60,
            "moe_held_rows": 256, "mhc_res_err_x1e6": 2,
            "prefill_chunks": [[2048, 512, 30.0]],
            "device_ms_by": {"decode": 14.0}}
    pre = "%mhc_pre_chunk.7 = (bf16[2048,3584], f32[2048,128]) custom-call()"
    post = "%mhc_post_chunk.9 = bf16[2048,14336]{1,0} custom-call(...)"
    small = "%mhc_pre_decode.3 = (bf16[64,3584], f32[64,128]) custom-call()"
    small_post = "%mhc_post_decode.4 = bf16[64,14336]{1,0} custom-call(...)"
    moe_k = "%moe_experts_decode.3 = bf16[704,3584]{1,0} custom-call(...)"
    moe_c = "%moe_experts_chunk.5 = bf16[9152,2048]{1,0} custom-call(...)"
    namesake = "%mhc_pre_chunk_pad.1 = bf16[14336,128]{1,0} fusion(...)"
    c = {
        "model_fields": FIELDS, "device": {"kind": "TPU v5 lite"},
        "engine_steps": [
            dict(step),
            dict(step, mhc_res_err_x1e6=7, prefill_chunks=[[1024, 512, 20.0]]),
            dict(step, decode_tokens=0, mhc_res_err_x1e6=900,
                 prefill_chunks=[])],
        "engine_stats": ({}, {"kv_util_peak": 0.6}),
        "trace": {"modules": {"jit_llm_decode(1)": [10, 0.4],
                              "jit_llm_prefill_chunk(2)": [8, 0.7]},
                  "op_self_s": {pre: 0.024, post: 0.032, small: 0.010,
                                small_post: 0.004, moe_k: 0.100,
                                moe_c: 0.160, namesake: 0.5},
                  "op_calls": {pre: 112, post: 112, small: 140,
                               small_post: 140, moe_k: 120, moe_c: 96,
                               namesake: 112}},
    }
    c.update(over)
    return c


def _read(name, c):
    return harness.load_module("layer_metrics", name).read(c)


def test_new_readers_read_a_hand_made_collected():
    c = _collected()
    # Each program's two kernels over that program's executions: a
    # trace does not say which program an op ran in, the names do.
    assert _read("mhc_chunk_ms", c) == pytest.approx(1e3 * 0.056 / 8)
    assert _read("mhc_decode_ms", c) == pytest.approx(1e3 * 0.014 / 10)
    assert _read("moe_expert_chunk_ms", c) == pytest.approx(1e3 * 0.160 / 8)
    # The steps that decoded: 2 and 7 (the third decoded nothing).
    assert _read("mhc_res_err", c) == pytest.approx(7e-6)
    # 1,536 real rows a chunk x 14 sublayers x 71,680 B at 819 GB/s.
    need = 1536 * 14 * 71680 / 819e9
    assert need > 1536 * mhc_cost.operations_per_token(FIELDS) / 197e12
    assert _read("mhc_chunk_roofline_pct", c) == pytest.approx(
        100 * need / (0.056 / 8))
    # 60 experts hit a layer x 6 layers x 22.0 MB over 10 ms a step.
    need = 60 * 6 * 3 * 3584 * 1024 * 2 / 819e9
    assert need > 256 * 6 * 6 * 3584 * 1024 / 197e12
    assert _read("moe_routed_roofline_pct", c) == pytest.approx(
        100 * need / 0.010)
    assert 0 < _read("moe_routed_roofline_pct", c) < 100


def test_new_readers_find_nothing_where_the_program_writes_nothing():
    """A program without the kernels' names or the counter (the parent
    commit, another model): None, not an error."""
    bare = _collected(
        engine_steps=[{"name": "llm.step", "decode_tokens": 64}],
        trace={"modules": {"jit_llm_decode(1)": [10, 0.4]},
               "op_self_s": {"%paged_decode.3 = x": 0.1},
               "op_calls": {"%paged_decode.3 = x": 10}})
    for name in NEW:
        assert _read(name, bare) is None, name
        assert _read(name, dict(bare, trace=None, engine_steps=[],
                                engine_stats=None)) is None, name
        assert _read(name, {}) is None, name
    # Another family's fields under the shared kernel's name: Laguna's.
    laguna = harness.read_json("configs", "laguna-xs2-serve.json")
    assert _read("moe_routed_roofline_pct", _collected(
        model_fields=laguna["model"]["fields"])) is None
    assert _read("mhc_chunk_roofline_pct", _collected(
        model_fields=laguna["model"]["fields"])) is None


def test_mhc_cost_counts_one_tokens_bytes_and_operations():
    # A sublayer: the four streams in and out, one stream out and in.
    one = dict(FIELDS, num_hidden_layers=1)
    assert mhc_cost.bytes_per_token(one) == 2 * 71680
    assert mhc_cost.bytes_per_token(FIELDS) == 14 * (2 * 14336 + 2 * 3584) * 2
    # ISSUE 62's arithmetic: ~0.86 Mflop a token a sublayer, ~12 a byte.
    per = mhc_cost.operations_per_token(one) / 2
    assert per == 2 * 14336 * 24 + 4 * 14336 + 2 * 20 * 3584
    assert 0.85e6 < per < 0.90e6
    assert 11 < per / 71680 < 13 < 197e12 / 819e9
    # A 2,048-token chunk: 2.06 GB, 2.5 ms at the roofline.
    assert round(2048 * mhc_cost.bytes_per_token(FIELDS) / 1e9, 2) == 2.06
    # An expert: three matrices of 3,584 x 1,024 in bfloat16, 22.0 MB.
    assert moe_routed_cost.bytes_read(1, one | {
        "num_hidden_layers": 2}) == 3 * 3584 * 1024 * 2
    assert moe_routed_cost.operations(4, FIELDS) == 6 * 3584 * 1024 * 4 * 6
