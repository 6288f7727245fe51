"""Device time of the flash attention kernels (forward and backward,
all layers) in one training step: the self time of the trace's Mosaic
custom-call events that take the step's queries, over the executions of
the step's program."""

from benchmark import kernels


def read(c):
    t = c.get("trace")
    if not t:
        return None
    per_step = kernels.mosaic_s_per_step(t, kernels.flash_operand(c))
    return None if per_step is None else per_step * 1e3
