"""Share of its roofline the routed experts' grouped product reaches in
a decode step of a model whose EVERY layer has an expert block at the
full hidden size (Granite 4.0-H): the least time the chip could take
for the assignments that fell on the experts held here
(``granite_hybrid_cost.moe_operations`` at the published peak) or for
reading the held experts that got a token (``moe_bytes_read`` at the
published HBM bandwidth), whichever is longer, over the kernels' device
time a step (the Mosaic kernels named ``moe_experts_decode``,
``moe_expert_ms``'s seconds). Assignments and experts hit are the means
of the ring's ``moe_held_rows`` and ``moe_experts_hit`` over the steps
that decoded (a layer's mean each, counted by the step program itself).
A configuration with other field names reads nothing."""

from benchmark import flops, granite_hybrid_cost as cost, named_kernels, \
    timeline


def read(c):
    per_step = named_kernels.per_decode_step_s(c, "%moe_experts_decode")
    steps = [e for e in timeline.entries(c, "moe_held_rows")
             if e.get("decode_tokens", 0) > 0 and "moe_experts_hit" in e]
    fields = c.get("model_fields") or {}
    if per_step is None or not steps or "layer_types" not in fields:
        return None
    rows = sum(e["moe_held_rows"] for e in steps) / len(steps)
    hit = sum(e["moe_experts_hit"] for e in steps) / len(steps)
    peaks = flops.peaks(c["device"]["kind"])
    need = max(
        cost.moe_operations(rows, fields) / peaks["bf16_flops_per_s"],
        cost.moe_bytes_read(hit, fields) / peaks["hbm_bytes_per_s"])
    return 100.0 * need / per_step
