"""Deployments, handles, and the request router.

Parity targets:
  * @serve.deployment / Deployment.bind / options —
    /root/reference/python/ray/serve/deployment.py
  * DeploymentHandle / DeploymentResponse —
    /root/reference/python/ray/serve/handle.py
  * power-of-two-choices routing —
    /root/reference/python/ray/serve/_private/router.py:295
    (PowerOfTwoChoicesReplicaScheduler): pick 2 random replicas, send to
    the one with fewer in-flight requests. Queue lengths are tracked
    client-side per handle, as the reference's handle-local tracker does.
"""

from __future__ import annotations

import random
import threading
import uuid
from collections import deque
from dataclasses import dataclass, field, replace
from typing import Any, Callable, Optional

DEFAULT_MAX_ONGOING = 100


@dataclass(frozen=True)
class AutoscalingConfig:
    """Request-rate autoscaling (parity:
    /root/reference/python/ray/serve/config.py AutoscalingConfig +
    autoscaling_policy.py): replicas sized so each sees
    ~target_ongoing_requests concurrent requests."""

    min_replicas: int = 1
    max_replicas: int = 4
    target_ongoing_requests: float = 2.0
    upscale_delay_s: float = 0.5
    downscale_delay_s: float = 2.0


@dataclass
class Deployment:
    func_or_class: Any
    name: str
    num_replicas: int = 1
    max_ongoing_requests: int = DEFAULT_MAX_ONGOING
    user_config: Optional[dict] = None
    autoscaling_config: Optional[AutoscalingConfig] = None
    ray_actor_options: dict = field(default_factory=dict)
    init_args: tuple = ()
    init_kwargs: dict = field(default_factory=dict)

    def options(self, **overrides) -> "Deployment":
        if "autoscaling_config" in overrides and isinstance(
                overrides["autoscaling_config"], dict):
            overrides["autoscaling_config"] = AutoscalingConfig(
                **overrides["autoscaling_config"])
        return replace(self, **overrides)

    def bind(self, *args, **kwargs) -> "Application":
        return Application(replace(self, init_args=args,
                                   init_kwargs=kwargs))


@dataclass
class Application:
    """A deployment bound to its init args; args may themselves be
    Applications (model composition — the bound child resolves to a
    DeploymentHandle inside the parent's constructor)."""

    deployment: Deployment

    @property
    def name(self) -> str:
        return self.deployment.name


def deployment(_func_or_class=None, *, name: Optional[str] = None,
               num_replicas: int = 1,
               max_ongoing_requests: int = DEFAULT_MAX_ONGOING,
               user_config: Optional[dict] = None,
               autoscaling_config=None,
               ray_actor_options: Optional[dict] = None):
    """@serve.deployment decorator."""
    if isinstance(autoscaling_config, dict):
        autoscaling_config = AutoscalingConfig(**autoscaling_config)

    def deco(target):
        return Deployment(
            func_or_class=target,
            name=name or getattr(target, "__name__", "deployment"),
            num_replicas=num_replicas,
            max_ongoing_requests=max_ongoing_requests,
            user_config=user_config,
            autoscaling_config=autoscaling_config,
            ray_actor_options=dict(ray_actor_options or {}),
        )

    if _func_or_class is not None:
        return deco(_func_or_class)
    return deco


class DeploymentResponse:
    """Future-like response (reference handle.py DeploymentResponse).

    ``result()`` retries once through a fresh replica when the one that
    took the request died mid-flight (reference: router failure retry —
    a dead replica is a routing event, not a user error).
    """

    def __init__(self, ref, router: "Router", replica_key, retry=None):
        self._ref = ref
        self._router = router
        self._replica_key = replica_key
        # (method, args, kwargs, model_id, trace_ctx) | None
        self._retry = retry
        self._done = False

    def result(self, timeout: Optional[float] = None):
        import ray_tpu

        try:
            return ray_tpu.get(self._ref, timeout=timeout)
        except (ray_tpu.ActorDiedError, ray_tpu.ActorUnavailableError,
                ray_tpu.WorkerCrashedError):
            if self._retry is None:
                raise
            method, args, kwargs, model_id, trace_ctx = self._retry
            self._settle()
            # Drop the dead replica locally FIRST — a controller-side
            # refresh may still list it until its health loop catches up.
            self._router.remove_replica(self._replica_key)
            import time as _time

            deadline = _time.monotonic() + 15
            while True:
                try:
                    actor, key = self._router.pick_replica(model_id)
                    break
                except RuntimeError:
                    # Sole replica died: wait for the controller's health
                    # loop to spawn a replacement.
                    if _time.monotonic() > deadline:
                        raise
                    _time.sleep(0.2)
                    self._router.maybe_refresh(force=True)
            # Retry keeps the ORIGINAL trace context: the retried hop is
            # part of the same request's story.
            self._ref = actor.handle_request.remote(
                method, args, kwargs, model_id, _time.time(), trace_ctx)
            self._replica_key = key
            self._done = False
            self._retry = None  # one retry only
            return ray_tpu.get(self._ref, timeout=timeout)
        finally:
            self._settle()

    def open_stream(self, result, chunk_batch: int = 16):
        """The handle's end of the stream that ``result`` (this
        response's value) names, or None where the deployment did not
        return a generator. ``iter_stream`` reads it from a thread
        (``take``); a reader on an event loop attaches a sink
        (``attach``), and then waiting for a chunk holds neither a
        thread nor a task."""
        from .replica import STREAM_MARKER

        self._settle()
        if not (isinstance(result, dict) and STREAM_MARKER in result):
            return None
        return self._router.open_stream(
            self._replica_key, result[STREAM_MARKER], chunk_batch)

    def iter_stream(self, timeout: Optional[float] = None,
                    chunk_batch: int = 16):
        """Iterate a STREAMING response (deployment returned a generator):
        yields each chunk as the serving replica's generator yields it
        (the router's poller for that replica brings them, one long-poll
        for all of this process's streams there). ``timeout`` bounds the
        wait for each chunk; ``chunk_batch`` is how many chunks the
        generator may run ahead of this iterator. A non-streaming result
        is yielded as the single item (reference:
        handle.options(stream=True) -> DeploymentResponseGenerator)."""
        result = self.result(timeout=timeout)
        stream = self.open_stream(result, chunk_batch)
        if stream is None:
            yield result
            return
        try:
            while (chunk := stream.take(timeout)) is not _STREAM_END:
                yield chunk
        finally:
            # Early consumer exit: free the parked generator.
            stream.close()

    def _to_object_ref(self):
        self._settle()  # ref handed off; router stops tracking it
        return self._ref

    def _settle(self):
        if not self._done:
            self._done = True
            self._router.request_done(self._replica_key)

    def __del__(self):
        # Fire-and-forget callers drop responses without result(); the
        # router's in-flight count must not leak or p2c routing skews
        # toward replicas that never served an unsettled request.
        try:
            self._settle()
        except Exception:  # lint: allow-swallow(__del__ during interpreter teardown)
            pass


_STREAM_END = object()


class _StreamEnd:
    """The handle's end of one stream: the router's poller deals what
    the replica sent into it, ONE consumer takes it out, and what the
    consumer has taken is what lets the replica's generator run on.
    Which kind of consumer is which call the reader made: a thread
    blocks in ``take``; a loop cannot block, so its reader attaches a
    sink (``attach``) and the poller has the loop call ``pump``, once a
    reply for every such stream the reply carries (``Router._flush``)."""

    def __init__(self, router: "Router", key, actor, sid: int,
                 run_ahead: int):
        self._router = router
        self._key = key
        self._actor = actor
        self.sid = sid
        self._run_ahead = max(1, run_ahead)
        self._cond = threading.Condition()
        self._chunks: deque = deque()  # dealt, not yet taken
        self._ended = False  # the replica has sent this stream's end
        self._error: Optional[BaseException] = None  # ... and it was this
        self._consumed = 0
        self._granted = 0
        # A loop's consumer: ``_sink(chunks, ended, error)`` is called
        # on ``_loop`` and nowhere else; it returns False to be given
        # no more until it calls ``resume`` (its drain waiter does).
        self._loop = None
        self._sink: Optional[Callable] = None
        self._taking = True

    def deal(self, chunks, done: bool, error):
        """Poller side: append one reply's share of this stream. Returns
        the loop that has to ``pump`` it, or None where a thread takes
        it (woken here)."""
        with self._cond:
            self._chunks.extend(chunks)
            if done or error is not None:
                self._ended, self._error = True, error
            self._cond.notify_all()
            return self._loop

    def grant(self, explicit: bool = False) -> Optional[int]:
        """How far the generator may yield, if that is further than the
        replica has been told; None otherwise. The poller asks before
        each poll and carries the answer; a consumer asks with
        ``explicit`` and gets one only when the replica's word is half a
        run-ahead behind, which is when no poll has gone out for a while
        because the generator waits for this reader."""
        with self._cond:
            upto = self._consumed + self._run_ahead
            behind = upto - self._granted
            if self._ended or behind < (
                    max(1, self._run_ahead // 2) if explicit else 1):
                return None
            self._granted = upto
            return upto

    def _has_next(self) -> bool:
        return bool(self._chunks) or self._ended

    def _pop(self):
        """The next chunk, counted as consumed; behind the last one the
        stream's error, raised, or _STREAM_END (``_cond`` is held and
        ``_has_next()`` true)."""
        if self._chunks:
            self._consumed += 1
            return self._chunks.popleft()
        if self._error is not None:
            raise self._error
        return _STREAM_END

    def _after_take(self):
        if (upto := self.grant(explicit=True)) is not None:
            try:
                self._actor.stream_grant.remote(self.sid, upto)
            except Exception:  # lint: allow-swallow(grant to a gone replica; the poller reports it)
                pass

    def take(self, timeout: Optional[float]):
        """Next chunk, or _STREAM_END; raises the stream's error, or
        GetTimeoutError after ``timeout`` seconds without one."""
        with self._cond:
            if not self._cond.wait_for(self._has_next, timeout):
                from ray_tpu._private.exceptions import GetTimeoutError

                raise GetTimeoutError(
                    f"no stream chunk within {timeout}s")
            item = self._pop()
        if item is not _STREAM_END:
            self._after_take()
        return item

    def attach(self, loop, sink: Callable):
        """A loop's reader, once, on ``loop``: from now on everything
        this stream is dealt goes to ``sink(chunks, ended, error)``, a
        reply's share in one call (what was dealt before now, first, in
        this call); ``ended`` comes with or behind the last chunks, and
        ``error`` with it. A sink that returns False is given no more
        (what arrives waits here, uncounted, so a generator stops at its
        run-ahead bound) until it calls ``resume``."""
        with self._cond:
            self._loop, self._sink = loop, sink
        self.pump()

    def resume(self):
        """The sink, on its loop: it takes again."""
        self._taking = True
        self.pump()

    def pump(self):
        """On the sink's loop: give the sink what has been dealt."""
        with self._cond:
            sink = self._sink
            if sink is None or not (self._taking and self._has_next()):
                return
            chunks = list(self._chunks)
            self._chunks.clear()
            self._consumed += len(chunks)
            ended, error = self._ended, self._error
            if ended:
                self._sink = None
        self._taking = sink(chunks, ended, error) is not False
        if chunks and not ended:
            self._after_take()

    def close(self):
        """Consumer side, always: leave the poller's books, and free the
        replica's generator unless its end has already arrived."""
        self._router.close_stream(self._key, self.sid)
        with self._cond:
            ended, self._sink = self._ended, None
        if not ended:
            try:
                self._actor.stream_cancel.remote(self.sid)
            except Exception:  # lint: allow-swallow(cancel on a gone replica)
                pass


def _replica_key(replica):
    """Stable identity for a replica across update_replicas() calls —
    in-flight counts must survive autoscale/redeploy reindexing."""
    aid = getattr(replica, "_actor_id", None)
    return aid.binary() if aid is not None else id(replica)


CONTROLLER_NAME = "SERVE_CONTROLLER"
_REFRESH_INTERVAL_S = 1.0


class Router:
    """Client-side power-of-two-choices over the replica set.

    In-flight counts and model affinity are keyed by stable replica
    identity (actor id), not list index: update_replicas() preserves
    counts for surviving replicas, so p2c load estimates stay accurate
    across autoscaling/redeploy events.

    When constructed with a deployment name, the router pulls replica
    membership from the (named, supervised) controller actor — initially,
    every ``_REFRESH_INTERVAL_S`` while in use, and immediately on
    demand after a replica failure (reference: handle routers receive
    membership via controller long-poll,
    python/ray/serve/_private/router.py).
    """

    def __init__(self, deployment_name: Optional[str] = None):
        self._lock = threading.Lock()
        self._name = deployment_name
        self._replicas: list = []
        self._keys: list = []
        self._inflight: dict = {}
        self._model_affinity: dict[str, set] = {}
        self._rng = random.Random()
        self._last_refresh = 0.0  # monotonic; 0 == never
        # Replicas observed dead locally: a controller snapshot that still
        # lists one (its health loop lags the observation) must not
        # resurrect it. key -> monotonic expiry.
        self._tombstones: dict = {}
        # Streaming: the replica keeps what its generators yield for
        # this router under ``caller_id``, and one poller thread a
        # replica with live streams brings it here.
        self.caller_id = uuid.uuid4().hex
        self._stream_ends: dict = {}  # replica key -> {sid: _StreamEnd}

    def maybe_refresh(self, force: bool = False):
        """Pull the replica set from the controller if stale (or forced).

        Refresh failures (controller restarting, slow, or gone) fall back
        to the current replica set — membership updates are best-effort,
        serving traffic is not (reference: handles keep routing on their
        last-known membership while the long-poll reconnects)."""
        if self._name is None:
            return
        import time as _time

        with self._lock:
            fresh = (_time.monotonic() - self._last_refresh
                     < _REFRESH_INTERVAL_S)
            if fresh and not force and self._replicas:
                return
            have_fallback = bool(self._replicas)
        import ray_tpu

        try:
            controller = ray_tpu.get_actor(CONTROLLER_NAME)
            replicas = ray_tpu.get(
                controller.get_replicas.remote(self._name), timeout=30)
        except Exception:
            if have_fallback:
                return  # keep serving on the last-known set
            raise
        with self._lock:
            self._last_refresh = _time.monotonic()
        self.update_replicas(replicas)

    def update_replicas(self, replicas: list):
        import time as _time

        with self._lock:
            now = _time.monotonic()
            self._tombstones = {k: t for k, t in self._tombstones.items()
                                if t > now}
            replicas = [r for r in replicas
                        if _replica_key(r) not in self._tombstones]
            self._replicas = list(replicas)
            self._keys = [_replica_key(r) for r in self._replicas]
            live = set(self._keys)
            self._inflight = {k: self._inflight.get(k, 0) for k in live}
            for mid in list(self._model_affinity):
                kept = self._model_affinity[mid] & live
                if kept:
                    self._model_affinity[mid] = kept
                else:
                    del self._model_affinity[mid]

    def pick_replica(self, multiplexed_model_id: str = ""):
        """Choose a replica; returns ``(replica, key)`` atomically (a
        concurrent update_replicas() must not be able to reindex between
        the choice and the caller reading the handle)."""
        with self._lock:
            n = len(self._replicas)
            if n == 0:
                raise RuntimeError("no replicas available")
            if n == 1:
                i = 0
            elif multiplexed_model_id and (hot := [
                    i for i, k in enumerate(self._keys)
                    if k in self._model_affinity.get(
                        multiplexed_model_id, ())]):
                # Multiplexing: prefer a replica with the model already hot.
                i = min(hot, key=lambda j: self._inflight[self._keys[j]])
            else:
                a, b = self._rng.sample(range(n), 2)
                i = (a if self._inflight[self._keys[a]]
                     <= self._inflight[self._keys[b]] else b)
            key = self._keys[i]
            self._inflight[key] += 1
            if multiplexed_model_id:
                self._model_affinity.setdefault(
                    multiplexed_model_id, set()).add(key)
            return self._replicas[i], key

    def replica(self, idx: int):
        with self._lock:
            return self._replicas[idx]

    def actor_for_key(self, key):
        """The replica actor behind a routing key (streaming pulls must
        target the replica that parked the generator)."""
        with self._lock:
            for k, r in zip(self._keys, self._replicas):
                if k == key:
                    return r
        return None

    def open_stream(self, key, sid: int, run_ahead: int) -> _StreamEnd:
        """Register stream ``sid`` of the replica behind ``key`` and
        return this side's end of it. The first live stream of a replica
        starts its poller."""
        actor = self.actor_for_key(key)
        if actor is None:
            raise RuntimeError("streaming replica is gone")
        end = _StreamEnd(self, key, actor, sid, run_ahead)
        first_grant = end.grant()
        with self._lock:
            ends = self._stream_ends.get(key)
            if ends is None:
                ends = self._stream_ends[key] = {}
                threading.Thread(
                    target=self._poll_streams, args=(key, actor),
                    daemon=True, name="serve-stream-poll").start()
            ends[sid] = end
        # On the books first, then the word to the replica: a poll in
        # flight may bring the stream's chunks the moment it lands.
        actor.stream_grant.remote(sid, first_grant, self.caller_id)
        return end

    def close_stream(self, key, sid: int):
        with self._lock:
            self._stream_ends.get(key, {}).pop(sid, None)

    def _poll_streams(self, key, actor):
        """The poller of one replica: one long-poll at a time carries
        every stream's ready chunks, dealt to each stream's end here. It
        ends when the replica has no live stream of this router."""
        import ray_tpu

        from .replica import REPLY_SENT

        while True:
            with self._lock:
                ends = self._stream_ends.get(key)
                if not ends:
                    self._stream_ends.pop(key, None)
                    return
                live = list(ends.values())
            grants = {e.sid: upto for e in live
                      if (upto := e.grant()) is not None}
            try:
                reply = ray_tpu.get(
                    actor.stream_poll.remote(self.caller_id, grants))
            except Exception as e:  # noqa: BLE001 - every waiting consumer raises it
                with self._lock:
                    ends = self._stream_ends.pop(key, {})
                self._deal([(end, ((), True, e)) for end in ends.values()])
                return
            t_reply = reply.pop(REPLY_SENT, None)
            with self._lock:
                dealt = [(ends[sid], share) for sid, share in reply.items()
                         if sid in ends]
                for sid, (_, done, error) in reply.items():
                    if done or error is not None:
                        ends.pop(sid, None)
            self._deal(dealt, t_reply)

    def _deal(self, dealt, t_reply: Optional[float] = None):
        """One reply's shares to their ends: a thread that waits in
        ``take`` is woken by its end, and the ends that a loop reads
        are pumped by ONE callback on it, whatever their number.
        ``t_reply``: when the reply left the replica (wall clock)."""
        by_loop: dict = {}
        for end, share in dealt:
            if (loop := end.deal(*share)) is not None:
                by_loop.setdefault(loop, []).append(end)
        for loop, ends in by_loop.items():
            try:
                loop.call_soon_threadsafe(self._flush, ends, t_reply)
            except RuntimeError:  # lint: allow-swallow(the loop is closed: its readers are gone)
                pass

    def _flush(self, ends, t_reply: Optional[float] = None):
        """On a reader's loop, once a reply: every sink takes its
        stream's share. ``proxy_flush`` counts the wakes (``stream_hold``
        counts the chunks, so the two give chunks a wake);
        ``stream_out`` is the reply's whole way from the replica to the
        end of this callback."""
        import time as _time

        from ray_tpu.util import perfmodel

        from . import slo

        ann = perfmodel.session_annotation("serve.flush")
        t0 = _time.perf_counter()
        for end in ends:
            end.pump()
        slo.record_phase("proxy_flush", _time.perf_counter() - t0,
                         self._name)
        if t_reply is not None:
            slo.record_phase("stream_out", _time.time() - t_reply,
                             self._name)
        if ann is not None:
            ann.__exit__(None, None, None)

    def remove_replica(self, key):
        """Drop a replica observed dead so the retry (and subsequent
        picks) can't land on it again before the controller catches up —
        the tombstone keeps a stale controller snapshot from
        resurrecting it for the next 10s."""
        import time as _time

        with self._lock:
            self._tombstones[key] = _time.monotonic() + 10.0
            for i in reversed([j for j, k in enumerate(self._keys)
                               if k == key]):
                del self._replicas[i]
                del self._keys[i]
            self._inflight.pop(key, None)
            for mid in list(self._model_affinity):
                self._model_affinity[mid].discard(key)
                if not self._model_affinity[mid]:
                    del self._model_affinity[mid]

    def request_done(self, key):
        with self._lock:
            if key in self._inflight:
                self._inflight[key] = max(0, self._inflight[key] - 1)


_process_routers: dict[str, Router] = {}
_process_routers_lock = threading.Lock()


def _clear_routers():
    """Drop per-process router caches (serve.shutdown)."""
    with _process_routers_lock:
        _process_routers.clear()


def _router_for(deployment_name: str) -> Router:
    """One router per deployment per process: every handle to the same
    deployment shares in-flight accounting, as the reference's
    handle-shared router does."""
    with _process_routers_lock:
        r = _process_routers.get(deployment_name)
        if r is None:
            r = _process_routers[deployment_name] = Router(deployment_name)
        return r


class DeploymentHandle:
    """Callable handle to a running deployment (reference handle.py).

    A handle is just (deployment name, method, model id): the replica set
    comes from the per-process router, which follows the controller.
    Handles pickle to the name alone, so they survive controller
    restarts and work from any process in the cluster (driver, replicas
    doing model composition, the HTTP proxy).
    """

    def __init__(self, deployment_name: str, router: Optional[Router] = None,
                 method_name: str = "__call__",
                 multiplexed_model_id: str = ""):
        self._name = deployment_name
        self._router = router if router is not None \
            else _router_for(deployment_name)
        self._method = method_name
        self._model_id = multiplexed_model_id

    def options(self, *, method_name: Optional[str] = None,
                multiplexed_model_id: Optional[str] = None
                ) -> "DeploymentHandle":
        return DeploymentHandle(
            self._name, self._router,
            method_name if method_name is not None else self._method,
            (multiplexed_model_id if multiplexed_model_id is not None
             else self._model_id))

    def __getattr__(self, name: str) -> "DeploymentHandle":
        if name.startswith("_"):
            raise AttributeError(name)
        return self.options(method_name=name)

    def remote(self, *args, **kwargs) -> DeploymentResponse:
        import time as _time

        from ray_tpu.util import tracing

        self._router.maybe_refresh()
        actor, key = self._router.pick_replica(self._model_id)
        # Submit stamp travels with the request so the replica can
        # attribute its actor-lane queueing (the replica_queue SLO
        # phase); the caller's trace context (the proxy's root span, or
        # an upstream replica doing model composition) rides along so
        # the replica's spans join the request's trace.
        trace_ctx = tracing.current_context.get()
        ref = actor.handle_request.remote(
            self._method, args, kwargs, self._model_id, _time.time(),
            trace_ctx)
        return DeploymentResponse(
            ref, self._router, key,
            retry=(self._method, args, kwargs, self._model_id, trace_ctx))

    def __reduce__(self):
        return (_rebuild_handle,
                (self._name, self._method, self._model_id))


def _rebuild_handle(name, method, model_id):
    return DeploymentHandle(name, None, method, model_id)
