"""Device time one prefill chunk spends on the four-stream residual
path: the self time of the Mosaic kernels named ``mhc_pre_chunk`` and
``mhc_post_chunk`` (ray_tpu/ops/mhc.py's two kernels under the names
ray_tpu/models/xing4.py gives them in its chunk program), every
sublayer of every layer, over the executions of the chunk program,
``jit_llm_prefill_chunk`` on the trace's ``XLA Modules`` line, by
``named_kernels.per_execution_s``. The small XLA operations around them
(``Phi`` spread to 128 lanes, the reshapes) are not counted. A program
whose chunk has no such kernels reads nothing."""

from benchmark import named_kernels

NEEDLES = ("%mhc_pre_chunk", "%mhc_post_chunk")


def seconds(c):
    """Seconds of both kernels in one execution of the chunk program,
    or None where either is missing."""
    parts = [named_kernels.per_execution_s(c, needle,
                                           named_kernels.CHUNK_PROGRAM)
             for needle in NEEDLES]
    return None if None in parts else sum(parts)


def read(c):
    s = seconds(c)
    return None if s is None else s * 1e3
