"""Device time one prefill chunk spends in its attention kernel: the
self time of the Mosaic kernels named ``chunk_attn``
(ops/pallas/chunk_attention.py, the name on its ``pallas_call``), all
layers, over the executions of the chunk program,
``jit_llm_prefill_chunk`` on the trace's ``XLA Modules`` line. Found
by the INSTRUCTION's name, the text before `` = `` of the op event's
HLO text: an operation that only takes the kernel's result names it
among its operands and is not counted. The name shares nothing with
``named_kernels``' needles (``%attn_full``, ``%attn_window``,
``%attn_latent``, ``%moe_experts_decode``), so a chunk's attention is
in none of the decode step's figures. A program whose chunk has no
such kernel (the parent of the PR that brought it, models/gpt.py)
reads nothing."""

NEEDLE = "%chunk_attn"
CHUNK_PROGRAM = "jit_llm_prefill_chunk"


def read(c):
    t = c.get("trace")
    if not t:
        return None
    secs = sum(s for name, s in t["op_self_s"].items()
               if NEEDLE in name.partition(" = ")[0])
    chunks = sum(n for name, (n, _) in t["modules"].items()
                 if CHUNK_PROGRAM in name)
    if not secs or not chunks:
        return None
    return secs / chunks * 1e3
