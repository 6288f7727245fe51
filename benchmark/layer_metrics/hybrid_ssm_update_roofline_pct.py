"""``ssm_update_roofline_pct`` for the configurations that state their
Mamba-2 layers as ``layer_types`` (Granite 4.0-H): the least time the
chip could take to read each LIVE lane's state once and write it once,
every Mamba-2 layer (``granite_hybrid_cost.update_bytes`` at the
published HBM bandwidth, or the arithmetic at the published peak if
that were longer), over the device time a decode step spends in the
kernels named ``ssm_update``. Live lanes are the mean ``lanes`` of the
window's ``llm.step`` ring entries that decoded. A configuration with
other field names, or a program without the kernel, reads nothing."""

from benchmark import flops, granite_hybrid_cost as cost, named_kernels, \
    timeline


def read(c):
    per_step = named_kernels.per_decode_step_s(c, "%ssm_update")
    steps = [e for e in timeline.entries(c, "lanes")
             if e.get("decode_tokens", 0) > 0]
    fields = c.get("model_fields") or {}
    if per_step is None or not steps or "layer_types" not in fields:
        return None
    lanes = sum(e["lanes"] for e in steps) / len(steps)
    peak = flops.peaks(c["device"]["kind"])
    need_s = max(
        cost.update_bytes(lanes, fields) / peak["hbm_bytes_per_s"],
        cost.update_operations(lanes, fields) / peak["bf16_flops_per_s"])
    return 100.0 * need_s / per_step
