"""Share of a decode step's live cache pages that the paged kernel
fetched in whole runs: table-adjacent pages whose block ids ascend by
one, a group of them in ONE copy (``kv_pages_in_runs`` of the window's
``llm.step`` ring entries, which the step program counts from its block
tables and hands over with its token ids; Laguna's are its full kind's);
the mean over the steps that decoded, x 100. What the pool's
fragmentation leaves of the kernels' fast path: a registered long
context is one ascending run, a request's own blocks are not."""

from benchmark import timeline

KEY = "kv_pages_in_runs"


def read(c):
    rows = [e[KEY] for e in timeline.entries(c, KEY)
            if e.get("decode_tokens", 0) > 0]
    return 100.0 * sum(rows) / len(rows) if rows else None
