"""Device time of the paged decode kernel in one decode step: the self
time of the Mosaic kernels named ``paged_decode`` (the name on
ops/pallas/paged_decode.py's ``pallas_call``, and on whatever kernel
takes its place under that name), all layers, over the executions of
``jit_llm_decode``, by ``named_kernels.per_execution_s``. Found by the
kernel's name on the trace's op events, never by an operand: the pool
it reads may be head-major, as stored, or not an operand at all."""

from benchmark import named_kernels

NEEDLE = "%paged_decode"


def read(c):
    s = named_kernels.per_decode_step_s(c, NEEDLE)
    return None if s is None else s * 1e3
