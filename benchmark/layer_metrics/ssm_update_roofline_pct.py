"""Share of its roofline the state update (``ssm_update``) reaches in a
decode step: the least time the chip could take to read each LIVE
lane's state once and write it once, every state-space layer
(``ssm_cost.update_bytes`` at the published HBM bandwidth; the
arithmetic, ``ssm_cost.update_operations`` at the published peak, is far
shorter), over the kernels' device time a step (``ssm_update_ms``'s
seconds). Live lanes are the mean ``lanes`` of the window's ``llm.step``
ring entries that decoded, the scheduler's own count: a padded lane's
pass over the scratch slot is not needed work, nor is anything a
gather-and-scatter form would copy beside the states themselves."""

from benchmark import flops, named_kernels, ssm_cost, timeline


def read(c):
    per_step = named_kernels.per_decode_step_s(c, "%ssm_update")
    steps = [e for e in timeline.entries(c, "lanes")
             if e.get("decode_tokens", 0) > 0]
    if per_step is None or not steps:
        return None
    lanes = sum(e["lanes"] for e in steps) / len(steps)
    peak = flops.peaks(c["device"]["kind"])
    fields = c["model_fields"]
    need_s = max(
        ssm_cost.update_bytes(lanes, fields) / peak["hbm_bytes_per_s"],
        ssm_cost.update_operations(lanes, fields)
        / peak["bf16_flops_per_s"])
    return 100.0 * need_s / per_step
