"""Share of its roofline the routed experts' grouped product reaches in
a decode step: the least time the chip could take for the step's
assignments (``moe_cost.operations`` at the published peak) or for
reading the experts that got a token (``moe_cost.bytes_read`` at the
published HBM bandwidth), whichever is longer, over the kernels' device
time a step (``moe_expert_ms``'s seconds). Assignments are the window's mean live
decode rows times ``num_experts_per_tok``; experts hit is the mean of
the ring's ``moe_experts_hit`` (a layer's mean, counted by the step
program itself)."""

from benchmark import flops, moe_cost, named_kernels, timeline


def read(c):
    per_step = named_kernels.per_decode_step_s(c, "%moe_experts_decode")
    steps = [e for e in timeline.entries(c, "moe_experts_hit")
             if e.get("decode_tokens", 0) > 0]
    if per_step is None or not steps:
        return None
    f = c["model_fields"]
    rows = sum(e["decode_tokens"] for e in steps) / len(steps)
    hit = sum(e["moe_experts_hit"] for e in steps) / len(steps)
    peaks = flops.peaks(c["device"]["kind"])
    need = max(
        moe_cost.operations(rows * f["num_experts_per_tok"], f)
        / peaks["bf16_flops_per_s"],
        moe_cost.bytes_read(hit, f) / peaks["hbm_bytes_per_s"])
    return 100.0 * need / per_step
