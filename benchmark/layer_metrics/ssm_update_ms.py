"""Device time a decode step spends moving its lanes' state-space
states on by one token: the Mosaic kernels named ``ssm_update``
(ray_tpu/ops/ssm.py, the name on its ``pallas_call``), all state-space
layers, by ``named_kernels.per_decode_step_s``. Found by the kernel's
name on the trace's op events, never by an operand. A program without
such a kernel (every other configuration's, and the parent of the PR
that brought it) reads nothing."""

from benchmark import named_kernels

NEEDLE = "%ssm_update"


def read(c):
    s = named_kernels.per_decode_step_s(c, NEEDLE)
    return None if s is None else s * 1e3
