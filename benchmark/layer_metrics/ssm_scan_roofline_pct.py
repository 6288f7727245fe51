"""Share of its roofline the chunked scan (``ssm_scan``) reaches in a
prefill chunk: the least time the chip could take for the rows a chunk
program scans, the LONGER of moving them and the span's state
(``ssm_cost.scan_bytes`` at the published HBM bandwidth) and of the
scan's arithmetic (``ssm_cost.scan_operations`` at the published bf16
peak, each product counted once: the kernel runs them in float32 at
precision ``highest``, several passes of the unit, which is work it
chose), over the kernels' device time an execution (``ssm_scan_ms``'s
seconds). Rows are the mean positions a chunk of the window computed
(the first field of ``prefill_chunks`` in the ``llm.step`` ring
entries, the scheduler's own count)."""

from benchmark import flops, named_kernels, ssm_cost, timeline


def read(c):
    per_chunk = named_kernels.per_execution_s(
        c, "%ssm_scan", named_kernels.CHUNK_PROGRAM)
    chunks = [chunk for e in timeline.entries(c, "prefill_chunks")
              for chunk in e["prefill_chunks"]]
    if per_chunk is None or not chunks:
        return None
    rows = sum(chunk[0] for chunk in chunks) / len(chunks)
    peak = flops.peaks(c["device"]["kind"])
    fields = c["model_fields"]
    need_s = max(
        ssm_cost.scan_bytes(rows, 1, fields) / peak["hbm_bytes_per_s"],
        ssm_cost.scan_operations(rows, fields) / peak["bf16_flops_per_s"])
    return 100.0 * need_s / per_chunk
