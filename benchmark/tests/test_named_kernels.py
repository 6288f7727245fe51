"""The one finder (PR 54): a Mosaic kernel is the op whose instruction
NAME is the name on its ``pallas_call``, and a step is one execution of
a named program. Hand-made reduced traces, as a TPU trace names its op
events (the instruction's whole HLO text). Run with ``python -m pytest
benchmark/tests``."""

import glob
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness, named_kernels  # noqa: E402

TARGET = 'custom_call_target="tpu_custom_call"'
Q = "bf16[64,12,1,64]{3,2,1,0}"
HEAD_MAJOR = "bf16[12,2560,16,64]{3,2,1,0}"
STORED = "bf16[12,2560,16,768]{3,2,1,0}"


def _paged(operands: str) -> str:
    return (f"%paged_decode.3 = {Q} custom-call({operands}), {TARGET}, "
            f'operand_layout_constraints={{}}, backend_config="..."')


# The kernel as it is today (a layer's pool head-major, one window a page
# slot), as ROADMAP A1b will make it (the stacked pool as stored, taken
# once), and with no pool among its operands at all.
PAGED = {
    "head_major": _paged(f"s32[64,40]{{1,0}} %tables, {Q} %q, "
                         f"{HEAD_MAJOR} %k, {HEAD_MAJOR} %v"),
    "as_stored": _paged(f"s32[64,40]{{1,0}} %tables, {Q} %q, "
                        f"{STORED} %pool_k, {STORED} %pool_v"),
    "no_pool": _paged(f"{Q} %q"),
}
# Takes the kernel's result: holds its name, is not the kernel.
CONSUMER = (f"%fusion.12 = bf16[64,768]{{1,0}} fusion({Q} %paged_decode.3, "
            f"bf16[768,768]{{1,0}} %w), kind=kOutput")
# Holds the name at its head and is no Mosaic kernel.
NAMESAKE = f"%paged_decode_mask.1 = pred[64,40]{{1,0}} fusion(s32[64] %n)"
OPERAND = (f"%dynamic-slice_dynamic-update-slice_fusion.51 = {HEAD_MAJOR} "
           f"fusion({STORED} %pool), kind=kLoop")


def _flash(name: str) -> str:
    return (f"%{name} = bf16[24,12,1024,64]{{3,2,1,0}} custom-call("
            f"bf16[24,12,1024,64]{{3,2,1,0}} %q), {TARGET}")


CHUNK = (f"%chunk_attn.3 = bf16[8,6,512,128]{{3,2,1,0}} custom-call(s32[2]"
         f"{{0}} %stack), {TARGET}")


def _read(name, c):
    return harness.load_module("layer_metrics", name).read(c)


def _serve(ops, modules):
    config = harness.read_json("configs", "gpt2-small-serve.json")
    return {
        "rehearse": False, "config": config,
        "model_fields": config["model"]["fields"],
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
        "engine_steps": [{"name": "llm.step", "context_tokens": 30000}],
        "trace": {"modules": modules, "op_self_s": ops,
                  "op_calls": {k: 1 for k in ops}},
    }


DECODE_10 = {"jit_llm_decode(123)": [10, 2.5],
             "jit_llm_prefill_chunk(7)": [4, 0.2]}


@pytest.mark.parametrize("kind", sorted(PAGED))
def test_the_paged_kernel_is_found_whatever_it_reads(kind):
    """The proof that ROADMAP A1b's PR is not refused as PR 28 was: a
    kernel under the name ``paged_decode`` that takes the stored pool
    ``[12,2560,16,768]``, or no pool, reads what today's reads."""
    c = _serve({PAGED[kind]: 0.098, CONSUMER: 0.5, NAMESAKE: 0.3,
                OPERAND: 0.094}, DECODE_10)
    assert _read("paged_kernel_ms", c) == pytest.approx(9.8)
    # 30,000 context tokens x 36,864 B at 819 GB/s, over 9.8 ms.
    assert _read("paged_roofline_pct", c) == pytest.approx(
        100.0 * (30000 * 36864 / 819e9) / 0.0098)


def test_an_operand_of_another_op_and_a_namesake_add_nothing():
    alone = _serve({PAGED["head_major"]: 0.098}, DECODE_10)
    among = _serve({PAGED["head_major"]: 0.098, CONSUMER: 0.5,
                    NAMESAKE: 0.3}, DECODE_10)
    assert _read("paged_kernel_ms", among) == _read("paged_kernel_ms", alone)
    # Neither of the two alone is a kernel.
    assert _read("paged_kernel_ms", _serve({CONSUMER: 0.5, NAMESAKE: 0.3},
                                           DECODE_10)) is None


def test_a_step_is_an_execution_of_the_named_program_not_the_most_run():
    modules = {"jit_dynamic_slice(3)": [371, 0.1],
               "jit_llm_prefill_chunk(7)": [96, 0.2],
               "jit_llm_decode(123)": [10, 2.5]}
    c = _serve({PAGED["as_stored"]: 0.098}, modules)
    assert _read("paged_kernel_ms", c) == pytest.approx(9.8)
    # Twelve calls of one instruction or one call a layer of twelve
    # instructions: seconds over the program's executions either way.
    split = {PAGED["as_stored"].replace(".3 =", f".{i} ="): 0.098 / 12
             for i in range(12)}
    assert _read("paged_kernel_ms", _serve(split, modules)) == \
        pytest.approx(9.8)
    del modules["jit_llm_decode(123)"]
    assert _read("paged_kernel_ms", _serve({PAGED["as_stored"]: 0.098},
                                           modules)) is None


def _train(ops, modules):
    config = harness.read_json("configs", "gpt2-small-train.json")
    return {"model_fields": config["model"]["fields"], "batch": 24,
            "chips": 1, "seq": 1024,
            "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
            "trace": {"modules": modules, "op_self_s": ops,
                      "op_calls": {k: 1 for k in ops}}}


def test_the_flash_needle_takes_forward_and_both_backwards_and_no_other():
    from benchmark import flops

    ops = {_flash("flash_fwd.7"): 0.20, _flash("flash_bwd_fused.9"): 0.34,
           _flash("flash_bwd_dq.4"): 0.03, _flash("flash_bwd_dkv.5"): 0.03,
           CHUNK: 0.7, PAGED["head_major"]: 0.9,
           "%fusion.224 = f32[50304,768]{1,0} fusion(bf16[24,12,1024,64]"
           "{3,2,1,0} %flash_bwd_fused.9), kind=kLoop": 0.2}
    # The step is ``jit_train_step`` though another program ran more.
    c = _train(ops, {"jit_train_step(5)": [12, 2.85],
                     "jit_eval_step(6)": [40, 0.4]})
    assert _read("flash_kernel_ms", c) == pytest.approx(50.0)
    need = flops.flash_flops_per_step(c["model_fields"], 24, 1024)
    assert _read("flash_roofline_pct", c) == pytest.approx(
        100.0 * (need / 197e12) / 0.050)
    # What the four flash kernels left unread is named with its time.
    assert named_kernels.unread(c["trace"]) == [
        ["%paged_decode", pytest.approx(0.9), 1],
        ["%chunk_attn", pytest.approx(0.7), 1]]
    for name in ("flash_kernel_ms", "flash_roofline_pct"):
        assert _read(name, _train(ops, {"jit_step(5)": [12, 2.85]})) is None
        assert _read(name, _train({CHUNK: 0.7},
                                  {"jit_train_step(5)": [12, 2.85]})) is None
        assert _read(name, {"trace": None}) is None


def _needles() -> dict:
    """{needle: reader files} for every needle a reader's source holds,
    so that one a later PR adds is held to the same."""
    found = {}
    for path in glob.glob(os.path.join(ROOT, "benchmark", "layer_metrics",
                                       "*.py")):
        with open(path) as f:
            for needle in re.findall(r'"(%[a-z_]+)"', f.read()):
                found.setdefault(needle, []).append(os.path.basename(path))
    return found


def test_no_readers_needle_is_contained_in_anothers():
    needles = sorted(_needles())
    assert set(needles) >= {"%paged_decode", "%flash_", "%chunk_attn",
                            "%attn_full", "%attn_window", "%attn_latent",
                            "%moe_experts_decode"}
    for a in needles:
        for b in needles:
            assert a == b or a not in b, (a, b)


def test_no_reader_builds_a_shape_or_takes_the_most_run_program():
    for path in glob.glob(os.path.join(ROOT, "benchmark", "**", "*.py"),
                          recursive=True):
        if os.sep + "tests" + os.sep in path:
            continue
        with open(path) as f:
            source = f.read()
        # Spelt in halves, so that a search for the names finds none.
        for gone in ("paged_" "operand", "flash_" "operand", "mosaic" "_s",
                     "import " "kernels"):
            assert gone not in source, (path, gone)
    assert not os.path.exists(os.path.join(ROOT, "benchmark", "kernels.py"))
