"""Replica actor: hosts one copy of a deployment's user class.

Parity target: /root/reference/python/ray/serve/_private/replica.py — the
replica wraps the user callable, tracks ongoing/total request counts for
autoscaling, applies user_config reconfiguration, and answers health
checks. Batching/multiplexing live in decorators on the user class
(serve/batching.py, serve/multiplex.py) and work unchanged here because
replicas run methods on a thread pool (max_concurrency), not an event loop.
"""

from __future__ import annotations

import threading
import time
import types
from typing import Any, Optional

from . import slo
from .multiplex import _set_request_model_id

# A request whose user code returned a generator answers with this marker;
# the caller pulls chunks from the SAME replica via stream_next
# (reference: streaming responses through the handle,
# python/ray/serve/handle.py DeploymentResponseGenerator).
STREAM_MARKER = "__rtpu_stream__"


def _with_model_id(gen, model_id: str):
    """Run each next() of a parked generator under the request's
    multiplex id (the body executes lazily on stream_next threads)."""
    while True:
        _set_request_model_id(model_id)
        try:
            try:
                v = next(gen)
            except StopIteration:
                return
        finally:
            _set_request_model_id(None)
        yield v


class Replica:
    def __init__(self, cls_or_fn, init_args, init_kwargs,
                 user_config: Optional[dict] = None,
                 deployment_name: str = ""):
        self._lock = threading.Lock()
        self._ongoing = 0
        self._total = 0
        self._window: list[float] = []  # request-arrival timestamps
        self._streams: dict[int, Any] = {}
        self._stream_counter = 0
        self._deployment = deployment_name or getattr(
            cls_or_fn, "__name__", "deployment")
        # One replica actor per worker process: the module-global lets
        # batcher collector threads attribute batch_wait observations.
        slo.set_deployment(self._deployment)
        if isinstance(cls_or_fn, type):
            self.instance = cls_or_fn(*init_args, **init_kwargs)
        else:
            self.instance = cls_or_fn  # plain function deployment
        if user_config is not None:
            self.reconfigure(user_config)

    def reconfigure(self, user_config: dict):
        """Push a new user_config without restarting (reference:
        Deployment user_config → replica.reconfigure)."""
        fn = getattr(self.instance, "reconfigure", None)
        if callable(fn):
            fn(user_config)
        return True

    def prune_slo(self, deployment: str):
        """Controller broadcast on redeploy: drop this process's SLO
        cells/exemplars for the previous code version, so a stale
        exemplar trace_id is never reported against the new one."""
        slo.prune_deployment(deployment)
        return True

    def handle_request(self, method: str, args: tuple, kwargs: dict,
                       multiplexed_model_id: str = "",
                       submit_ts: float = 0.0,
                       trace_ctx: Optional[dict] = None) -> Any:
        from ray_tpu.util import tracing

        trace_id = (trace_ctx or {}).get("trace_id")
        if submit_ts:
            # Handle-side submit stamp -> here: actor-lane queueing.
            # Cross-process wall clocks on the same host; clamped >= 0.
            queued = max(0.0, time.time() - submit_ts)
            slo.record_phase("replica_queue", queued, self._deployment,
                             trace_id=trace_id)
            # Retroactive waterfall slice for the same interval.
            tracing.emit("serve.replica_queue", trace_ctx,
                         time.time() - queued, queued,
                         {"deployment": self._deployment})
        with self._lock:
            self._ongoing += 1
            self._total += 1
            self._window.append(time.monotonic())
            if len(self._window) > 1000:
                del self._window[:-1000]
        slo.set_queue_depth(self._ongoing + len(self._streams),
                            self._deployment)
        # Replica-side span: becomes the thread's current context, so a
        # @serve.batch submit or an engine add_request inside the user
        # code inherits the request's trace without explicit plumbing.
        rspan = None
        if trace_ctx is not None:
            rspan = tracing.span(
                "serve.replica", ctx=trace_ctx, kind="request",
                attributes={"deployment": self._deployment,
                            "method": method})
            rspan.__enter__()
        t_exec0 = time.perf_counter()
        try:
            _set_request_model_id(multiplexed_model_id)
            if callable(self.instance) and method == "__call__":
                target = self.instance
            else:
                target = getattr(self.instance, method)
            result = target(*args, **kwargs)
            if isinstance(result, types.GeneratorType):
                # Streaming response: park the generator; the caller
                # drains it chunk-at-a-time from THIS replica. The body
                # runs lazily inside stream_next, so the request's
                # multiplex id must travel with it.
                if multiplexed_model_id:
                    result = _with_model_id(result, multiplexed_model_id)
                with self._lock:
                    self._stream_counter += 1
                    sid = self._stream_counter
                    self._streams[sid] = result
                return {STREAM_MARKER: sid}
            return result
        except BaseException as e:
            if rspan is not None:
                rspan.attributes["error"] = f"{type(e).__name__}: {e}"
            raise
        finally:
            # For @serve.batch methods this span includes batch
            # residency (batch_wait is recorded separately by the
            # batcher): execute - batch_wait isolates pure compute.
            slo.record_phase("execute", time.perf_counter() - t_exec0,
                             self._deployment, trace_id=trace_id)
            if rspan is not None:
                rspan.__exit__(None, None, None)
            _set_request_model_id(None)
            with self._lock:
                self._ongoing -= 1
            slo.set_queue_depth(self._ongoing + len(self._streams),
                                self._deployment)

    def stream_next(self, sid: int, max_chunks: int = 16):
        """(chunks, done) — up to max_chunks items of stream ``sid``."""
        gen = self._streams.get(sid)
        if gen is None:
            return [], True
        t_pull = time.perf_counter()
        out, yielded = [], []
        done = False
        try:
            for _ in range(max_chunks):
                out.append(next(gen))
                yielded.append(time.perf_counter())
        except StopIteration:
            self._streams.pop(sid, None)
            done = True
        except BaseException:
            self._streams.pop(sid, None)
            raise
        # How long each chunk waited here for the pull to fill: a pull
        # blocks until it holds max_chunks, so the first chunk of a
        # 16-chunk pull of a token stream sits through 15 decode steps.
        t_ret = time.perf_counter()
        slo.record_phase("stream_pull", t_ret - t_pull, self._deployment)
        slo.record_phases("stream_hold", [t_ret - t for t in yielded],
                          self._deployment)
        return out, done

    def stream_cancel(self, sid: int):
        gen = self._streams.pop(sid, None)
        if gen is not None:
            gen.close()
        return True

    def stats(self) -> dict:
        with self._lock:
            now = time.monotonic()
            recent = sum(1 for t in self._window if now - t < 10.0)
            # Parked streams ARE ongoing work: autoscaling/drain must not
            # kill a replica mid-stream.
            ongoing = self._ongoing + len(self._streams)
        return {"ongoing": ongoing,
                "total": self._total,
                "rate_10s": recent / 10.0,
                "deployment": self._deployment,
                "queue_depth": ongoing,
                "phase_hist": slo.phase_hist(self._deployment)}

    def check_health(self) -> bool:
        fn = getattr(self.instance, "check_health", None)
        if callable(fn):
            fn()
        return True
