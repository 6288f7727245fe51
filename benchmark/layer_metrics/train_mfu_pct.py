"""Model FLOP/s utilization at the median step: the operations the
forward and backward passes of one step's tokens need (benchmark/
flops.py: matmuls and causal attention, no recomputation) over the
median report-to-report interval of the window's steps and the chips'
published bf16 peak. The median, because this is read in the traced
run, where starting and stopping the profiler stalls a few steps."""

import statistics

from benchmark import flops


def read(c):
    ts = [r["t"] for r in c["reports"]]
    if len(ts) < 3:
        return None
    step_s = statistics.median(b - a for a, b in zip(ts, ts[1:]))
    need = flops.train_flops_per_token(c["model_fields"], c["seq"]) \
        * c["batch"] * c["seq"]
    peak = flops.peaks(c["device"]["kind"])["bf16_flops_per_s"] * c["chips"]
    return 100.0 * need / step_s / peak
