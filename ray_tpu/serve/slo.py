"""Serving-path SLO instrumentation: per-deployment request-latency
phase histograms + queue-depth gauges.

Reference parity: Serve's request-latency metrics
(serve_deployment_processing_latency_ms et al. in the reference's
metrics surface) with an explicit PHASE breakdown — the signals the
continuous-batching autoscaler consumes (ROADMAP item 1):

  * ``proxy_queue``     — HTTP arrival -> dispatched to a replica
                          (routing + proxy-side queueing)
  * ``replica_queue``   — handle submit -> replica began the request
                          (actor-lane queueing; cross-process clocks,
                          clamped at 0)
  * ``batch_wait``      — request parked in a @serve.batch queue
  * ``execute``         — user code (includes batch residency for
                          batched methods; ``execute - batch_wait``
                          isolates pure compute)
  * ``ttft`` / ``tpot`` — generation deployments only (serve/llm.py,
                          recorded on the engine's thread where a first
                          token and a finish are handed to the replica):
                          time-to-first-token and time-per-output-token
  * ``engine_queue``    — generation deployments only (serve/llm.py,
                          beside ``ttft``, from the request's own
                          stamps): add_request -> first admission into
                          the engine's batch
  * ``stream_pull`` / ``stream_hold`` — streaming responses
                          (Replica.stream_poll, stream_next): one
                          reply's duration from its call (its count is
                          the number of replies), and, once a chunk, how
                          long the chunk sat in the replica between its
                          generator yielding it (or, for a pushed
                          stream, the deployment handing it over: an
                          LLM step's tokens, all at one instant) and
                          the reply that carries it leaving
  * ``proxy_flush``     — streaming responses read on an event loop
                          (the HTTP proxy): one callback of the loop,
                          in which every stream of a ``stream_poll``
                          reply has its share written to its socket. Its
                          count is the number of wakes of the loop that
                          streams cost, so ``stream_hold``'s count over
                          it is chunks a wake
  * ``stream_out``      — beside ``proxy_flush``, once a ``stream_poll``
                          reply that a loop reads: from the instant the
                          reply left the replica (``_record_reply``'s
                          wall clock, carried in the reply) to the end
                          of the callback that wrote its shares: result
                          serialisation, the runtime's loop, the
                          poller's wake, ``call_soon_threadsafe``, the
                          loop's wake, encode and ``send``
  * ``proxy_ttft``      — once a streamed HTTP request: the handler's
                          arrival stamp (where ``proxy_queue`` starts)
                          to its first frame written to the socket. The
                          server's whole first token on one clock:
                          ``proxy_queue`` + ``replica_queue`` +
                          ``execute`` + ``ttft`` + the first chunk's
                          ``stream_hold`` + ``stream_out``, and what
                          they leave over is the stream's attach
                          (``open_stream``, ``stream_grant``)

Two sinks per observation, both cheap (a bucket increment under one
lock):

  1. process-local fixed-boundary buckets, shipped via
     ``Replica.stats()`` / ``ProxyActor.stats()`` so the controller can
     merge replicas and surface p50/p95/p99 in ``serve.status()``;
  2. the ``rtpu_serve_request_seconds`` user-metric histogram
     (tags: deployment, phase), which rides the worker 1s flusher into
     the node's telemetry sampler -> head time-series
     (``serve_p95_ms:<deployment>:<phase>`` et al.).

One replica actor runs per worker process, so the module-global
current-deployment name safely attributes batch_wait observations made
on batcher collector threads.
"""

from __future__ import annotations

import threading
from bisect import bisect_left
from typing import Dict, List, Optional

# Request-phase bucket upper bounds (seconds): sub-ms to 10s, tuned for
# serving latencies rather than the coarser task-phase defaults.
PHASE_BOUNDS: List[float] = [
    0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
    0.25, 0.5, 1.0, 2.5, 5.0, 10.0]

# ttft/tpot are generation-path phases (serve/llm.py): time-to-first-
# token from request arrival at the engine, and per-output-token latency
# (decode cadence) — the two numbers an LLM serving SLO is written in.
PHASES = ("proxy_queue", "replica_queue", "batch_wait", "execute",
          "ttft", "tpot", "engine_queue", "stream_pull", "stream_hold",
          "proxy_flush", "stream_out", "proxy_ttft")

_lock = threading.Lock()
# Deployment hosted by THIS process (set by Replica.__init__).
_deployment = ""
# (deployment, phase) -> [bucket_counts, sum, count]
_local: Dict[tuple, list] = {}
# (deployment, phase) -> [wall_ts, seconds, trace_id]: the slowest
# RECENT traced observation — the exemplar a p95/p99 row points at.
# "Recent" keeps exemplars actionable: a stored one is replaced by any
# slower observation, or by any traced observation once it ages out.
_exemplars: Dict[tuple, list] = {}
_EXEMPLAR_MAX_AGE_S = 120.0

_hist = None
_replica_gauge = None
_proxy_gauge = None
_proxy_inflight = 0


def _metrics():
    """Lazy metric construction: importing this module must not
    register metrics in processes that never serve."""
    global _hist, _replica_gauge, _proxy_gauge
    if _hist is None:
        from ray_tpu.util.metrics import Gauge, Histogram

        _hist = Histogram(
            "rtpu_serve_request_seconds",
            "Serve request latency by deployment and phase",
            boundaries=list(PHASE_BOUNDS),
            tag_keys=("deployment", "phase"))
        _replica_gauge = Gauge(
            "rtpu_serve_replica_queue_depth",
            "Ongoing requests on this replica (in-flight + parked)",
            tag_keys=("deployment",))
        _proxy_gauge = Gauge(
            "rtpu_serve_proxy_inflight",
            "HTTP requests in flight in this proxy")
    return _hist, _replica_gauge, _proxy_gauge


def set_deployment(name: str):
    global _deployment
    _deployment = name or ""


def current_deployment() -> str:
    return _deployment


def _observe_locked(key: tuple, seconds) -> None:
    """Add observations to one (deployment, phase) cell; ``_lock`` is
    held by the caller."""
    cell = _local.get(key)
    if cell is None:
        cell = _local[key] = [[0] * (len(PHASE_BOUNDS) + 1), 0.0, 0]
    for x in seconds:
        cell[0][bisect_left(PHASE_BOUNDS, x)] += 1
        cell[1] += x
    cell[2] += len(seconds)


def record_phase(phase: str, seconds: float,
                 deployment: Optional[str] = None,
                 trace_id: Optional[str] = None):
    dep = deployment if deployment else (_deployment or "?")
    seconds = max(0.0, float(seconds))
    key = (dep, phase)
    with _lock:
        _observe_locked(key, (seconds,))
        if trace_id:
            import time as _time

            now = _time.time()
            ex = _exemplars.get(key)
            if ex is None or seconds >= ex[1] \
                    or now - ex[0] > _EXEMPLAR_MAX_AGE_S:
                _exemplars[key] = [now, seconds, trace_id]
    try:
        hist, _, _ = _metrics()
        hist.observe(seconds, tags={"deployment": dep, "phase": phase})
    except Exception:  # noqa: BLE001 - SLO recording is best-effort
        pass


def record_phases(phase: str, seconds: List[float],
                  deployment: Optional[str] = None):
    """``record_phase`` for many untraced observations of one phase at
    once (a stream reply's chunks): one pass under each lock, so a burst
    does not hand the interpreter back and forth with the threads it
    shares a process with."""
    if not seconds:
        return
    dep = deployment if deployment else (_deployment or "?")
    seconds = [max(0.0, float(x)) for x in seconds]
    with _lock:
        _observe_locked((dep, phase), seconds)
    try:
        hist, _, _ = _metrics()
        tags = hist.normalized_tags({"deployment": dep, "phase": phase})
        hist.observe_normalized([(tags, x) for x in seconds])
    except Exception:  # noqa: BLE001 - SLO recording is best-effort
        pass


def set_queue_depth(depth: int, deployment: Optional[str] = None):
    try:
        _, gauge, _ = _metrics()
        gauge.set(float(depth),
                  tags={"deployment": deployment or _deployment or "?"})
    except Exception:  # noqa: BLE001 - gauge update is advisory
        pass


def proxy_inflight(delta: int) -> int:
    """Adjust + publish the proxy in-flight gauge; returns the new
    value (single-writer per proxy process, so a plain int suffices)."""
    global _proxy_inflight
    _proxy_inflight = max(0, _proxy_inflight + delta)
    try:
        _, _, gauge = _metrics()
        gauge.set(float(_proxy_inflight))
    except Exception:  # noqa: BLE001 - gauge update is advisory
        pass
    return _proxy_inflight


def phase_hist(deployment: Optional[str] = None) -> dict:
    """{phase: {"bounds", "counts", "sum", "count"}} for one deployment
    (default: this process's). Cumulative since process start — callers
    diff or merge, they don't reset."""
    dep = deployment if deployment else (_deployment or "?")
    out = {}
    with _lock:
        for (d, phase), (counts, total, n) in _local.items():
            if d != dep:
                continue
            out[phase] = {"bounds": list(PHASE_BOUNDS),
                          "counts": list(counts),
                          "sum": total, "count": n}
            ex = _exemplars.get((d, phase))
            if ex is not None:
                out[phase]["exemplar"] = {
                    "ts": ex[0], "ms": ex[1] * 1e3, "trace_id": ex[2]}
    return out


def all_phase_hists() -> dict:
    """{deployment: {phase: cell}} for every deployment observed in
    this process (the proxy records several)."""
    out: dict = {}
    with _lock:
        for (d, phase), (counts, total, n) in _local.items():
            cell = out.setdefault(d, {})[phase] = {
                "bounds": list(PHASE_BOUNDS), "counts": list(counts),
                "sum": total, "count": n}
            ex = _exemplars.get((d, phase))
            if ex is not None:
                cell["exemplar"] = {
                    "ts": ex[0], "ms": ex[1] * 1e3, "trace_id": ex[2]}
    return out


def merge_phase_hists(hists: List[dict]) -> dict:
    """Merge per-replica ``phase_hist()`` payloads (bucket-wise sum)."""
    merged: dict = {}
    for h in hists:
        for phase, cell in (h or {}).items():
            cur = merged.get(phase)
            if cur is None:
                merged[phase] = {"bounds": list(cell["bounds"]),
                                 "counts": list(cell["counts"]),
                                 "sum": cell["sum"],
                                 "count": cell["count"]}
                if cell.get("exemplar"):
                    merged[phase]["exemplar"] = dict(cell["exemplar"])
            elif cur["bounds"] == cell["bounds"]:
                cur["counts"] = [a + b for a, b in
                                 zip(cur["counts"], cell["counts"])]
                cur["sum"] += cell["sum"]
                cur["count"] += cell["count"]
                # Slowest replica's exemplar wins: the p99 row should
                # point at the worst traced request across replicas.
                ex = cell.get("exemplar")
                if ex and ex["ms"] >= cur.get(
                        "exemplar", {"ms": -1.0})["ms"]:
                    cur["exemplar"] = dict(ex)
    return merged


def latency_summary(merged: dict) -> dict:
    """{phase: {p50_ms, p95_ms, p99_ms, mean_ms, count}} from a merged
    phase-hist dict — the ``serve.status()`` latency block."""
    from ray_tpu._private.telemetry import quantile_from_buckets

    out = {}
    for phase, cell in merged.items():
        n = cell["count"]
        if not n:
            continue
        out[phase] = {
            "count": n,
            "mean_ms": cell["sum"] / n * 1e3,
            "p50_ms": quantile_from_buckets(
                cell["counts"], cell["bounds"], 0.50) * 1e3,
            "p95_ms": quantile_from_buckets(
                cell["counts"], cell["bounds"], 0.95) * 1e3,
            "p99_ms": quantile_from_buckets(
                cell["counts"], cell["bounds"], 0.99) * 1e3,
        }
        if cell.get("exemplar"):
            # p99 -> root cause: the trace id of the slowest traced
            # request behind these quantiles (state.get_trace /
            # `rtpu trace show` renders its waterfall).
            out[phase]["exemplar_trace_id"] = cell["exemplar"]["trace_id"]
            out[phase]["exemplar_ms"] = cell["exemplar"]["ms"]
    return out


def prune_deployment(deployment: str):
    """Drop this process's histogram cells AND exemplars for a deleted
    or redeployed deployment. Without this the module-global
    ``_exemplars`` keeps entries for dead deployments forever, and a
    stale exemplar trace_id (from code that no longer runs) can be
    reported as the root cause of a fresh p99 — the controller calls
    this locally and broadcasts it to live replicas/proxies on
    redeploy and teardown."""
    with _lock:
        for key in [k for k in _local if k[0] == deployment]:
            del _local[key]
        for key in [k for k in _exemplars if k[0] == deployment]:
            del _exemplars[key]


def _reset_for_tests():
    global _deployment, _proxy_inflight
    with _lock:
        _local.clear()
        _exemplars.clear()
    _deployment = ""
    _proxy_inflight = 0
