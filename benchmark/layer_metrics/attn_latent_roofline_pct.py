"""Share of its roofline the latent-attention kernel (``attn_latent``)
reaches in a decode step: the least time the chip could take for the
context the step attends (the mean ``context_tokens`` of the window's
``llm.step`` ring entries, the scheduler's own sum of its decode lanes'
contexts), which is the LONGER of reading those tokens' latent rows at
the published HBM bandwidth and scoring and summing them at the
published bf16 peak (``mla_cost``: 1,152 B and 2 x 64 x 1,088
operations a token a layer at Kimi-K2.5's widths, ~121 operations a
byte against the v5e's ridge of ~240, so the memory side is the longer
one there), over the kernels' device time a step (``attn_latent_ms``'s
seconds). Live context only: padded lanes, table entries past a lane's
context and a row's padding are not needed work."""

from benchmark import flops, mla_cost, named_kernels, timeline


def read(c):
    per_step = named_kernels.per_decode_step_s(c, "%attn_latent")
    steps = [e for e in timeline.entries(c, "context_tokens")
             if e["context_tokens"] > 0]
    if per_step is None or not steps:
        return None
    tokens = sum(e["context_tokens"] for e in steps) / len(steps)
    peak = flops.peaks(c["device"]["kind"])
    fields = c["model_fields"]
    need_s = max(
        tokens * mla_cost.bytes_per_context_token(fields)
        / peak["hbm_bytes_per_s"],
        tokens * mla_cost.operations_per_context_token(fields)
        / peak["bf16_flops_per_s"])
    return 100.0 * need_s / per_step
