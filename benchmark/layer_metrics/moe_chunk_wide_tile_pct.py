"""The share of the routed experts' device time in a prefill chunk that
runs in TALL row tiles: the self time of the Mosaic kernels named
``moe_experts_chunk_r<rows>`` over that of every kernel named
``moe_experts_chunk``, both over the executions of the chunk program
(``named_kernels.per_execution_s``). ray_tpu/ops/moe.py
``routed_experts`` takes a row tile from the rows an expert expects
(``tile_rows``: 16 below 32 rows an expert, 64 from there on) and a
kernel with a tile over 16 says so in its name, so this reads whether
that rule engaged: ~100 where every chunk gives an expert 32 rows or
more (2,048-token budgets at top-4 of 64), 0 of a program whose chunks
are short. A program whose grouped product knows one tile, as every
commit before PR 63, names no such kernel and gives nothing to read."""

from benchmark import named_kernels

NEEDLE = "%moe_experts_chunk"


def read(c):
    every = named_kernels.per_execution_s(c, NEEDLE,
                                          named_kernels.CHUNK_PROGRAM)
    tall = named_kernels.per_execution_s(c, NEEDLE + "_r",
                                         named_kernels.CHUNK_PROGRAM)
    return None if not every or tall is None else 100.0 * tall / every
