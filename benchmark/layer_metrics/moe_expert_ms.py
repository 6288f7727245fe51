"""Device time a decode step spends in the routed experts' grouped
product: the Mosaic kernels named ``moe_experts_decode``
(ray_tpu/ops/moe.py's kernel as models/laguna.py's decode step names
it; a chunk's are ``moe_experts_chunk``), all routed layers, by
``named_kernels.per_decode_step_s``. Found by the kernel's name on the
trace's op events, never by an operand."""

from benchmark import named_kernels

NEEDLE = "%moe_experts_decode"


def read(c):
    s = named_kernels.per_decode_step_s(c, NEEDLE)
    return None if s is None else s * 1e3
