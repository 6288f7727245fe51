"""``chunk_attn_ms`` (PR 38): the prefill chunk's attention kernel found
by its instruction's name on a made-up reduced trace, and that name kept
apart from the decode step's kernels. Run with ``python -m pytest
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

# Op events as a TPU trace names them: the instruction's whole HLO text.
FULL = ('%chunk_attn.3 = bf16[8,6,512,128]{3,2,1,0} custom-call(s32[2]{0} '
        '%stack, bf16[8,6,512,128]{3,2,1,0} %transpose.4), '
        'custom_call_target="tpu_custom_call"')
WINDOW = ('%chunk_attn.9 = bf16[8,8,512,128]{3,2,1,0} custom-call(s32[2]{0} '
          '%stack.1, bf16[8,8,512,128]{3,2,1,0} %transpose.7), '
          'custom_call_target="tpu_custom_call"')
# Takes the kernel's result: holds its name, is not the kernel.
CONSUMER = ('%fusion.12 = bf16[512,48,128]{2,1,0} fusion(bf16[8,6,512,128]'
            '{3,2,1,0} %chunk_attn.3), kind=kLoop')
DECODE = ('%attn_full.2 = bf16[64,8,6,128]{3,2,1,0} custom-call(...), '
          'custom_call_target="tpu_custom_call"')
EXPERTS = ('%moe_experts_chunk.3 = bf16[7936,1024]{1,0} custom-call(...), '
           'custom_call_target="tpu_custom_call"')


def _read(name, c):
    return harness.load_module("layer_metrics", name).read(c)


def _trace(ops, modules):
    return {"trace": {"modules": modules, "op_self_s": ops,
                      "op_calls": {k: 10 for k in ops}}}


def test_two_kernel_names_over_the_chunk_programs_executions():
    c = _trace({FULL: 0.012, WINDOW: 0.004, CONSUMER: 0.050, DECODE: 0.2,
                EXPERTS: 0.03},
               {"jit_llm_decode(1)": [10, 0.4],
                "jit_llm_prefill_chunk(2)": [8, 0.7]})
    # 16 ms of the two kernels in 8 executions of the chunk program.
    assert _read("chunk_attn_ms", c) == pytest.approx(2.0)
    # The decode step's reader sees its own kernel alone.
    assert _read("attn_full_ms", c) == pytest.approx(20.0)


@pytest.mark.parametrize("c", [
    {}, {"trace": None},
    _trace({DECODE: 0.2, EXPERTS: 0.03},
           {"jit_llm_decode(1)": [10, 0.4],
            "jit_llm_prefill_chunk(2)": [8, 0.7]}),
    _trace({FULL: 0.012}, {"jit_llm_decode(1)": [10, 0.4]}),
], ids=["empty", "no_trace", "no_kernel", "no_chunk_program"])
def test_nothing_to_read_is_none_and_not_an_error(c):
    """The parent commit's chunk has no such kernel, and GPT-2's never
    will: the line leaves the metric out."""
    assert _read("chunk_attn_ms", c) is None


def test_the_name_shares_nothing_with_the_decode_steps_needles():
    """``named_kernels`` sums every kernel whose name HOLDS a reader's
    needle, whatever program ran it: a chunk kernel named
    ``attn_full_chunk`` would be added into ``attn_full_ms``. (That no
    needle of ANY reader holds another's: ``test_named_kernels.py``.)"""
    mine = harness.load_module("layer_metrics", "chunk_attn_ms").NEEDLE
    assert mine == "%chunk_attn"
    # Every needle a reader's source holds, so that one a later PR adds
    # is held to the same.
    theirs = set()
    for path in glob.glob(os.path.join(ROOT, "benchmark", "layer_metrics",
                                       "*.py")):
        with open(path) as f:
            theirs |= set(re.findall(r'"(%[a-z_]+)"', f.read()))
    theirs.discard(mine)
    assert theirs >= {"%attn_full", "%attn_window", "%attn_latent",
                      "%moe_experts_decode", "%paged_decode", "%flash_"}
    for needle in theirs:
        assert needle not in mine and mine not in needle
        for op in (FULL, WINDOW):
            assert named_kernels.per_decode_step_s(
                _trace({op: 0.01}, {"jit_llm_decode(1)": [10, 0.4]}),
                needle) is None
