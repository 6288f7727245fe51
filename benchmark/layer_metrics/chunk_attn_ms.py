"""Device time one prefill chunk spends in its attention kernel: the
self time of the Mosaic kernels named ``chunk_attn``
(ops/pallas/chunk_attention.py, the name on its ``pallas_call``), all
layers, over the executions of the chunk program,
``jit_llm_prefill_chunk`` on the trace's ``XLA Modules`` line, by
``named_kernels.per_execution_s``: found by the INSTRUCTION's name, so
an operation that only takes the kernel's result names it among its
operands and is not counted. The name shares nothing with the decode
step's needles (``%paged_decode``, ``%attn_full``, ``%attn_window``,
``%attn_latent``, ``%moe_experts_decode``) or the training step's
(``%flash_``), so a chunk's attention is in none of their figures. A
program whose chunk has no such kernel (the parent of the PR that
brought it, models/gpt.py) reads nothing."""

from benchmark import named_kernels

NEEDLE = "%chunk_attn"


def read(c):
    s = named_kernels.per_execution_s(c, NEEDLE, named_kernels.CHUNK_PROGRAM)
    return None if s is None else s * 1e3
