"""Device time of the flash attention kernels (forward and backward,
all layers) in one training step: the self time of the Mosaic kernels
whose name begins ``flash_`` (ops/pallas/flash.py's names on its
``pallas_call``s: ``flash_fwd``, ``flash_bwd_fused``, ``flash_bwd_dq``,
``flash_bwd_dkv``), over the executions of ``jit_train_step``, by
``named_kernels.per_execution_s``. Found by the kernels' names on the
trace's op events, never by an operand."""

from benchmark import named_kernels

NEEDLE = "%flash_"


def read(c):
    s = named_kernels.per_execution_s(c, NEEDLE, named_kernels.TRAIN_PROGRAM)
    return None if s is None else s * 1e3
