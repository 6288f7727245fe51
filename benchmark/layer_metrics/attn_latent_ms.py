"""Device time a decode step spends in latent attention: the Mosaic
kernels named ``attn_latent`` (models/kimi_k2.py's name on its absorbed
paged call, ops/pallas/paged_decode.py ``paged_attention_latent``), all
layers, by ``named_kernels.per_decode_step_s``. Found by the kernel's
name on the trace's op events, never by an operand."""

from benchmark import named_kernels

NEEDLE = "%attn_latent"


def read(c):
    s = named_kernels.per_decode_step_s(c, NEEDLE)
    return None if s is None else s * 1e3
