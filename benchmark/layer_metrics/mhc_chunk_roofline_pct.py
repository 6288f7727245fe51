"""Share of its roofline the four-stream residual path reaches in a
prefill chunk: the least time the chip could take for the rows a chunk
program computes, the LONGER of moving their streams
(``mhc_cost.bytes_per_token`` at the published HBM bandwidth) and of the
path's arithmetic (``mhc_cost.operations_per_token`` at the published
bf16 peak), over the kernels' device time an execution
(``mhc_chunk_ms``'s seconds). Rows are the mean REAL positions a chunk
of the window computed (the first field of ``prefill_chunks`` in the
``llm.step`` ring entries, the scheduler's own count): the rows a span
is padded with are work the program chose."""

from benchmark import flops, harness, mhc_cost, timeline


def read(c):
    per_chunk = harness.load_module("layer_metrics", "mhc_chunk_ms").seconds(c)
    chunks = [chunk for e in timeline.entries(c, "prefill_chunks")
              for chunk in e["prefill_chunks"]]
    fields = c.get("model_fields") or {}
    if per_chunk is None or not chunks or "hc_mult" not in fields:
        return None
    rows = sum(chunk[0] for chunk in chunks) / len(chunks)
    peak = flops.peaks(c["device"]["kind"])
    need_s = max(
        rows * mhc_cost.bytes_per_token(fields) / peak["hbm_bytes_per_s"],
        rows * mhc_cost.operations_per_token(fields)
        / peak["bf16_flops_per_s"])
    return 100.0 * need_s / per_chunk
