"""Share of the memory roofline the paged decode kernel reaches: the
least time the chip could take to read the keys and values a decode
step attends (the mean ``context_tokens`` of the window's ``llm.step``
ring entries, the scheduler's own sum of its decode lanes' contexts,
times ``flops.kv_bytes_per_token``) at the published HBM bandwidth,
over the device time a decode step spends in the kernels named
``paged_decode`` (``paged_kernel_ms``'s seconds). Live context only:
padded lanes, table entries past a lane's context and the lanes a tile
pads a 64-wide head to are not needed work. Memory-bound: a query row
does 4 x head_dim operations a byte pair it reads.

The needed bytes are the same work whatever kernel does it: they come
from the scheduler's counts and the model's widths, not from the
kernel's operands. A kernel that reads a head-major pool padded to 128
lanes moves two bytes for each one needed and cannot pass 50%; one that
reads unpadded pages may; none can pass 100%."""

from benchmark import flops, named_kernels, timeline

NEEDLE = "%paged_decode"


def read(c):
    per_step = named_kernels.per_decode_step_s(c, NEEDLE)
    steps = [e for e in timeline.entries(c, "context_tokens")
             if e["context_tokens"] > 0]
    if per_step is None or not steps:
        return None
    need_bytes = sum(e["context_tokens"] for e in steps) / len(steps) \
        * flops.kv_bytes_per_token(c["model_fields"])
    peak = flops.peaks(c["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * (need_bytes / peak) / per_step
