"""``ssm_scan_roofline_pct`` for the configurations that state their
Mamba-2 layers as ``layer_types`` (Granite 4.0-H): the least time the
chip could take for the rows a chunk program scans, the LONGER of
moving them and the span's state (``granite_hybrid_cost.scan_bytes`` at
the published HBM bandwidth) and of the scan's arithmetic
(``scan_operations`` at the published bf16 peak, each product counted
once), over the device time an execution of the chunk program spends in
the kernels named ``ssm_scan``. Rows are the mean positions a chunk of
the window computed (the first field of ``prefill_chunks`` in the
``llm.step`` ring entries). A configuration with other field names, or
a program without the kernel, reads nothing."""

from benchmark import flops, granite_hybrid_cost as cost, named_kernels, \
    timeline


def read(c):
    per_chunk = named_kernels.per_execution_s(
        c, "%ssm_scan", named_kernels.CHUNK_PROGRAM)
    chunks = [chunk for e in timeline.entries(c, "prefill_chunks")
              for chunk in e["prefill_chunks"]]
    fields = c.get("model_fields") or {}
    if per_chunk is None or not chunks or "layer_types" not in fields:
        return None
    rows = sum(chunk[0] for chunk in chunks) / len(chunks)
    peak = flops.peaks(c["device"]["kind"])
    need_s = max(
        cost.scan_bytes(rows, 1, fields) / peak["hbm_bytes_per_s"],
        cost.scan_operations(rows, fields) / peak["bf16_flops_per_s"])
    return 100.0 * need_s / per_chunk
