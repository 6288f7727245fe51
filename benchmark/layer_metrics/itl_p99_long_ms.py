"""The client's 99th-percentile gap between two token frames of one
stream, all streams pooled, in the two long-context cells: the reading
of ``end_to_end/itl_p99_ms.py``, per layer and without a bound.

In these cells a gap is an engine step's length (~40 ms Laguna, ~75 ms
Kimi) and a 51 s window holds only ~1,250 or ~680 steps, so the 99th
percentile of the pooled gaps is the window's ~12th or ~7th longest
STEP: a rank among a handful of steps that hold a full 512-token chunk
budget or a pause, which two runs of one seed place 5-9% apart. The
driver's check read it over half of the largest bound the contract
allows (PERF.md section 6, PR 54, refusal round), so it is no
end-to-end metric here; ``itl_p95_ms`` beside it is the steady tail."""

from benchmark import clientstats, traffic


def read(c):
    samples = clientstats.gaps_ms(c)
    if not samples:
        return None
    return clientstats.finite(traffic.percentile(samples, 99))
