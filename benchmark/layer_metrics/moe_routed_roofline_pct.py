"""Share of its roofline the routed experts' grouped product reaches in
a decode step of the DeepSeek-V3 block's families: the least time the
chip could take for the assignments that fell on the experts held here
(``moe_routed_cost.operations`` at the published peak) or for reading
the held experts that got a token (``moe_routed_cost.bytes_read`` at
the published HBM bandwidth), whichever is longer, over the kernels'
device time a step (the Mosaic kernels named ``moe_experts_decode``,
``moe_expert_ms``'s seconds). Assignments and experts hit are the means
of the ring's ``moe_held_rows`` and ``moe_experts_hit`` over the steps
that decoded (a routed layer's mean each, counted by the step program
itself over its ``max_batch`` rows)."""

from benchmark import flops, moe_routed_cost, named_kernels, timeline


def read(c):
    per_step = named_kernels.per_decode_step_s(c, "%moe_experts_decode")
    steps = [e for e in timeline.entries(c, "moe_held_rows")
             if e.get("decode_tokens", 0) > 0 and "moe_experts_hit" in e]
    fields = c.get("model_fields") or {}
    if per_step is None or not steps \
            or "first_k_dense_replace" not in fields:
        return None
    rows = sum(e["moe_held_rows"] for e in steps) / len(steps)
    hit = sum(e["moe_experts_hit"] for e in steps) / len(steps)
    peaks = flops.peaks(c["device"]["kind"])
    need = max(
        moe_routed_cost.operations(rows, fields) / peaks["bf16_flops_per_s"],
        moe_routed_cost.bytes_read(hit, fields) / peaks["hbm_bytes_per_s"])
    return 100.0 * need / per_step
