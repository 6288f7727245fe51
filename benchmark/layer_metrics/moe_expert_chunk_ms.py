"""Device time one prefill chunk spends in the routed experts' grouped
products: the self time of the Mosaic kernels named
``moe_experts_chunk`` (ray_tpu/ops/moe.py ``routed_experts`` under the
name the model gives it in its chunk program: two calls a routed
layer), over the executions of the chunk program,
``jit_llm_prefill_chunk`` on the trace's ``XLA Modules`` line, by
``named_kernels.per_execution_s``. ``moe_expert_ms`` is the decode
step's. With every expert held, a 2,048-row chunk gives an expert ~128
rows: each call reads every expert's matrices for few rows of work, so
this is the chunk program's largest part and is bound by the weights'
bytes, not by the products. A program whose chunk has no such kernel
reads nothing."""

from benchmark import named_kernels

NEEDLE = "%moe_experts_chunk"


def read(c):
    s = named_kernels.per_execution_s(c, NEEDLE, named_kernels.CHUNK_PROGRAM)
    return None if s is None else s * 1e3
