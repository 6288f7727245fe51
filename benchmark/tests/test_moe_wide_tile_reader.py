"""``moe_chunk_wide_tile_pct`` (PR 63): the share of a chunk program's
grouped-product time that runs in tall row tiles, found by the kernels'
names on a made-up reduced trace. Run with ``python -m pytest
benchmark/tests``."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402

NAME = "moe_chunk_wide_tile_pct"
# Op events as a TPU trace names them: the instruction's whole HLO text.
TALL = ('%moe_experts_chunk_r64.5 = bf16[12224,2048]{1,0} custom-call(...), '
        'custom_call_target="tpu_custom_call"')
TALLER = ('%moe_experts_chunk_r128.2 = bf16[16384,3584]{1,0} custom-call(...), '
          'custom_call_target="tpu_custom_call"')
SHORT = ('%moe_experts_chunk.3 = bf16[9152,2048]{1,0} custom-call(...), '
         'custom_call_target="tpu_custom_call"')
DECODE = ('%moe_experts_decode.3 = bf16[1216,2048]{1,0} custom-call(...), '
          'custom_call_target="tpu_custom_call"')
# Takes a tall kernel's result: holds its name, is not the kernel.
CONSUMER = ('%fusion.12 = bf16[12224,1024]{1,0} fusion(bf16[12224,2048]{1,0} '
            '%moe_experts_chunk_r64.5), kind=kLoop')
PROGRAMS = {"jit_llm_decode(1)": [10, 0.4],
            "jit_llm_prefill_chunk(2)": [8, 0.7]}


def _read(name, c):
    return harness.load_module("layer_metrics", name).read(c)


def _trace(ops, modules=PROGRAMS):
    return {"trace": {"modules": modules, "op_self_s": ops,
                      "op_calls": {k: 10 for k in ops}}}


@pytest.mark.parametrize("ops, pct", [
    ({TALL: 0.09, TALLER: 0.03, DECODE: 0.2, CONSUMER: 0.05}, 100.0),
    ({TALL: 0.09, SHORT: 0.03, DECODE: 0.2}, 75.0),
], ids=["every_chunk_tall", "a_short_chunk_among_them"])
def test_the_share_is_of_the_chunk_programs_grouped_products(ops, pct):
    c = _trace(ops)
    assert _read(NAME, c) == pytest.approx(pct)
    # The readers the benchmark had read the tall kernels unedited:
    # their needles are held by the new names.
    total = sum(s for op, s in ops.items() if op in (TALL, TALLER, SHORT))
    assert _read("moe_expert_chunk_ms", c) == pytest.approx(
        total / 8 * 1e3)
    assert _read("moe_expert_ms", c) == pytest.approx(20.0)


@pytest.mark.parametrize("c", [
    {}, {"trace": None},
    _trace({SHORT: 0.03, DECODE: 0.2}),
    _trace({DECODE: 0.2}),
    _trace({TALL: 0.09}, {"jit_llm_decode(1)": [10, 0.4]}),
], ids=["empty", "no_trace", "parent_program", "no_chunk_kernel",
        "no_chunk_program"])
def test_a_program_with_one_tile_reads_none_and_not_an_error(c):
    """The parent's grouped product knows the 16-row tile alone and
    names no ``_r<rows>`` kernel: None, and the line leaves the metric
    out."""
    assert _read(NAME, c) is None


def test_the_program_names_the_kernels_this_reads():
    """Xing4.0's longest and shortest warmed chunks (top-4 of 64) trace
    to a kernel named ``_r<rows>``; its decode step's 64 rows do not."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import moe

    def traced(T, program):
        return str(jax.make_jaxpr(lambda: moe.routed_experts(
            jnp.ones((T, 8)), jnp.zeros((T, 4), jnp.int32),
            jnp.ones((T, 4)), jnp.ones((64, 8, 16)), jnp.ones((64, 8, 8)),
            name=f"moe_experts_{program}"))())

    for T in (512, 2048):
        assert f"moe_experts_chunk_r{moe.tile_rows(T * 4, 64)}" \
            in traced(T, "chunk")
    assert "moe_experts_decode_r" not in traced(64, "decode")


def test_the_manifest_lists_it_once_for_its_cell():
    """Membership, not position: the next PR appends behind it."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    (found,) = [m for m in manifest["per_layer"] if m["name"] == NAME]
    assert found == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "Kernels",
        "moves": "serve_tokens_per_s", "workloads": ["xing4-serve-rag"]}
    reports = {m["name"]: m.get("workloads") for m in manifest["end_to_end"]}
    assert set(found["workloads"]) <= set(reports[found["moves"]])
