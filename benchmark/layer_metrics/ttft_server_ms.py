"""Mean of the server's whole first token, on the server's one clock:
``engine_stats()["phase_hist"]["proxy_ttft"]``, once a streamed request,
from the proxy handler's arrival stamp (where ``proxy_queue`` starts) to
the request's first frame written to its socket. The client's mean
first token exceeds it by the wire and the rig; ``proxy_queue`` +
``replica_queue`` + ``execute`` + ``ttft`` + a first chunk's
``stream_hold`` + ``stream_out`` account for it up to the stream's
attach (``open_stream``, ``stream_grant``). A program that records no
``proxy_ttft``, as every commit before PR 60, gives nothing to read."""

import statistics

from benchmark import clientstats, timeline
from benchmark.harness import log


def read(c):
    mean = timeline.hist_mean_ms(c, "proxy_ttft")
    if mean is None or "records" not in c or c.get("rehearse"):
        return mean
    client = [x for x in clientstats.ttft_ms(c) if x != float("inf")]
    if client:
        log(f"ttft_server_ms: {mean:.2f} on the server's clock; the "
            f"client's mean first token {statistics.fmean(client):.2f} ms "
            f"over {len(client)} samples")
    return mean
