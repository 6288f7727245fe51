"""Bytes and operations of latent attention (MLA) in the absorbed form
in one decode step, for ``attn_latent_roofline_pct``: the work that was
NEEDED, from what the step attended, not what a kernel happened to do.

A context token leaves one latent row a layer in the cache: the
compressed vector (``kv_lora_rank``) and one rotary key
(``qk_rope_head_dim``), at the served width. Whatever a pool pads a row
with is not needed work and is left out, so the share errs low.
A decode row scores every head against that row (``2 x heads x
(kv_lora_rank + qk_rope_head_dim)`` operations) and sums its first
``kv_lora_rank`` columns as values (``2 x heads x kv_lora_rank``). The
projections around the kernel (``W_uk`` on the query, ``W_uv`` on the
output) are not the kernel's and are left out."""


def bytes_per_context_token(fields: dict, cache_bytes: int = 2) -> float:
    """Bytes of latent rows one context token holds, all layers."""
    return (fields["num_hidden_layers"]
            * (fields["kv_lora_rank"] + fields["qk_rope_head_dim"])
            * cache_bytes)


def operations_per_context_token(fields: dict) -> float:
    """Floating-point operations one decode row spends on one context
    token, all layers: scores over the latent row, values over its
    compressed part, every head."""
    rank, rope = fields["kv_lora_rank"], fields["qk_rope_head_dim"]
    return (fields["num_hidden_layers"] * 2.0
            * fields["num_attention_heads"] * ((rank + rope) + rank))
