"""Share of the memory roofline the paged decode kernel reaches: the
least time the chip could take to read the keys and values a decode
step attends (the mean ``context_tokens`` of the window's ``llm.step``
ring entries, the scheduler's own sum of its decode lanes' contexts,
times ``flops.kv_bytes_per_token``) at the published HBM bandwidth,
over the kernel's device time in one decode step (``kernels.mosaic_s``
a call, times the layers). Live context only: padded lanes and table
entries past a lane's context are not needed work. Memory-bound: a
query row does 4 x head_dim operations a byte pair it reads."""

from benchmark import flops, kernels, timeline


def read(c):
    t = c.get("trace")
    steps = [e for e in timeline.entries(c, "context_tokens")
             if e["context_tokens"] > 0]
    if not t or not steps:
        return None
    secs, calls = kernels.mosaic_s(t, kernels.paged_operand(c))
    if not calls:
        return None
    per_step = secs / calls * c["model_fields"]["n_layer"]
    need_bytes = sum(e["context_tokens"] for e in steps) / len(steps) \
        * flops.kv_bytes_per_token(c["model_fields"])
    peak = flops.peaks(c["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * (need_bytes / peak) / per_step
