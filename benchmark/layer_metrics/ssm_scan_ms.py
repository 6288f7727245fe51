"""Device time one prefill chunk spends in the state-space layers'
chunked scan: the self time of the Mosaic kernels named ``ssm_scan``
(ray_tpu/ops/ssm.py, the name on its ``pallas_call``), all state-space
layers, over the executions of the chunk program,
``jit_llm_prefill_chunk`` on the trace's ``XLA Modules`` line, by
``named_kernels.per_execution_s``. The products around the kernel (the
projections, the convolution, the running sums it is handed) are not
the scan's. A program whose chunk has no such kernel reads nothing."""

from benchmark import named_kernels

NEEDLE = "%ssm_scan"


def read(c):
    s = named_kernels.per_execution_s(c, NEEDLE, named_kernels.CHUNK_PROGRAM)
    return None if s is None else s * 1e3
