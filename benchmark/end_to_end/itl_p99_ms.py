"""Gap between consecutive token frames of one stream at the client,
all streams pooled: the nearest-rank 99th percentile. Since PR 35 a
token leaves the replica when it is made, so a gap is an engine step's
length and the 99th percentile of a window is among its longest steps.
End to end in the chat cell alone, whose ~1,600 steps a window put
sixteen of them beyond it; the long-context cells, with ~1,000 and ~650
steps, report the same reading per layer as ``itl_p99_long_ms``
(PERF.md section 6, PR 54, refusal round)."""

from benchmark import clientstats, traffic
from benchmark.harness import log


def read(c):
    samples = clientstats.gaps_ms(c)
    if not samples:
        return None
    log(f"itl_p99_ms: {len(samples)} samples" if c["rehearse"] else
        f"itl_p99_ms: {len(samples)} samples; p50 "
        f"{traffic.percentile(samples, 50):.1f}, p90 "
        f"{traffic.percentile(samples, 90):.1f}, p95 "
        f"{traffic.percentile(samples, 95):.1f}, p99 "
        f"{traffic.percentile(samples, 99):.1f}, max {max(samples):.1f} ms")
    return clientstats.finite(traffic.percentile(samples, 99))
