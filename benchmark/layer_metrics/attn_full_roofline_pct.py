"""Share of the memory roofline the paged call of the layers that keep
every token (``paged_attention_stored`` under the name ``attn_full``)
reaches in a decode step: the least time the chip could take to read
the keys and values the step attends (the mean ``context_tokens`` of
the window's ``llm.step`` ring entries, the scheduler's own sum of its
decode lanes' contexts, times ``kv_bytes_per_token``) at the published
HBM bandwidth, over the kernels' device time a step (``attn_full_ms``'s
seconds). Live context only: padded lanes and table entries past a
lane's context are not needed work. Memory-bound: a query row does
4 x head_dim operations a byte pair it reads, 6 rows a KV head."""

from benchmark import flops, named_kernels, timeline


def kv_bytes_per_token(fields: dict, kv_bytes: int = 2) -> int:
    """Bytes of keys and values one context token holds in the layers
    of kind ``full_attention`` (K and V, every KV head, at the served
    width)."""
    full = list(fields["layer_types"]).count("full_attention")
    return (2 * full * fields["num_key_value_heads"] * fields["head_dim"]
            * kv_bytes)


def read(c):
    per_step = named_kernels.per_decode_step_s(c, "%attn_full")
    steps = [e for e in timeline.entries(c, "context_tokens")
             if e["context_tokens"] > 0]
    if per_step is None or not steps:
        return None
    need_bytes = sum(e["context_tokens"] for e in steps) / len(steps) \
        * kv_bytes_per_token(c["model_fields"])
    peak = flops.peaks(c["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * (need_bytes / peak) / per_step
