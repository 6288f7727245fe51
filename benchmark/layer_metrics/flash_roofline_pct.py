"""Share of the compute roofline the flash kernels reach: the least
time the chip could take for the attention operations one step needs
(benchmark/flops.py ``flash_flops_per_step``: causal scores and values
forward, the backward's four products and one recomputation of the
scores) at the published bf16 peak, over the device time a training
step spends in the kernels whose name begins ``flash_``
(``flash_kernel_ms``'s seconds). The needed operations come from the
model's widths and the step's batch, not from the kernels' operands.
Compute-bound: at these shapes the operations take ~50x longer at peak
than moving q, k, v, o and their gradients at 819 GB/s."""

from benchmark import flops, named_kernels

NEEDLE = "%flash_"


def read(c):
    per_step = named_kernels.per_execution_s(c, NEEDLE,
                                             named_kernels.TRAIN_PROGRAM)
    if per_step is None:
        return None
    need = flops.flash_flops_per_step(c["model_fields"],
                                      c["batch"] // c["chips"], c["seq"])
    peak = flops.peaks(c["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * (need / peak) / per_step
