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
import weakref
from typing import Any, Optional

from . import slo
from .multiplex import _set_request_model_id

# A request whose user code returned a generator, or a PushedStream,
# answers with this marker; the caller reads its chunks from the SAME
# replica via stream_poll (reference: streaming responses through the
# handle, python/ray/serve/handle.py DeploymentResponseGenerator). Which
# of the two it was is decided by the type of what user code returned
# and shows in nothing the caller sees: both are a ``_Stream`` on the
# replica's books, read by the same polls. A generator is run by a
# feeder thread of its own (``_feed``); a pushed stream has no thread:
# the deployment's own thread (an engine's step loop) hands it chunks,
# those of many streams under one acquisition of the stream condition
# (``push``).
STREAM_MARKER = "__rtpu_stream__"

# How far a parked generator runs ahead of its reader: a feeder stops
# while its stream has yielded this many chunks past what the reader has
# said it consumed (iter_stream's ``chunk_batch``), so a caller that
# stops reading still stops a generator that has side effects.
STREAM_RUN_AHEAD = 16

# A stream_poll with nothing to carry returns empty after this long, so
# a caller whose last stream went away stops holding a call slot.
STREAM_POLL_LIMIT_S = 1.0

# Key of a stream_poll reply beside its sids: the wall clock at which
# the reply left the replica (``_record_reply``), from which its reader
# times the reply's way out (the ``stream_out`` phase), as ``submit_ts``
# is carried the other way.
REPLY_SENT = "t_reply"


def _with_model_id(gen, model_id: str):
    """Run each next() of a parked generator under the request's
    multiplex id (the body executes lazily on the stream's feeder)."""
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


class _Stream:
    """One stream on the replica's books: the chunks no caller has taken
    yet, yielded by a parked generator that a feeder thread runs or
    handed to a ``PushedStream`` (``gen`` is None: no thread, and no
    run-ahead bound). Every field is read and written under the
    replica's stream condition."""

    __slots__ = ("sid", "gen", "caller", "ready", "yielded", "limit",
                 "ended", "error", "cancelled")

    def __init__(self, sid: int, gen):
        self.sid = sid
        self.gen = gen
        # Whose polls carry its chunks: nobody's until its reader has
        # said (stream_grant) that it is there to be dealt them.
        self.caller: Optional[str] = None
        # (chunk, t_yield), in the order yielded or handed over
        self.ready: list = []
        self.yielded = 0
        self.limit = STREAM_RUN_AHEAD  # yield no chunk past this count
        self.ended = False  # the generator returned, raised or was closed
        self.error: Optional[BaseException] = None
        self.cancelled = False


def _feed(stream: _Stream, cond: threading.Condition, deployment: str):
    """A stream's feeder thread: run the generator ahead of its caller,
    as far as ``limit`` allows, and hand each chunk on as it is yielded.
    Holds no reference to the replica, so a dropped replica is collected
    (and its finalizer ends the feeders that wait here)."""
    error = None
    try:
        while True:
            with cond:
                while stream.yielded >= stream.limit \
                        and not stream.cancelled:
                    cond.wait(STREAM_POLL_LIMIT_S)
                if stream.cancelled:
                    break
            try:
                chunk = next(stream.gen)
            except StopIteration:
                break
            t_yield = time.perf_counter()
            with cond:
                stream.ready.append((chunk, t_yield))
                stream.yielded += 1
                cond.notify_all()
    except BaseException as e:  # noqa: BLE001 - a thread has no caller to raise to: it travels to the stream's reader, behind its chunks
        from ray_tpu._private.exceptions import TaskError

        error = TaskError.from_exception(e, f"{deployment}.stream")
    finally:
        try:
            stream.gen.close()
        except Exception:  # noqa: BLE001 - a generator that refuses to close
            pass
        with cond:
            stream.error = error
            stream.ended = True
            cond.notify_all()


# The (stream condition, name for a stream's error) of the replica
# whose request the thread is running: what a PushedStream made by that
# request's user code is filled under.
_serving = threading.local()


class PushedStream(_Stream):
    """A streaming response that the deployment fills itself: user code
    makes one inside the request it answers and returns it in place of
    a generator, and hands it chunks as it has them, from a thread of
    its own, through ``push``. The replica starts no thread for it, and
    handle, proxy and client read it as they read a generator's stream.
    It has no run-ahead bound: whoever pushes is never made to wait for
    a reader. It is filled under its replica's stream condition from
    its first chunk on, so what is handed over before the replica has
    put it on its books waits in it; what is handed to a stream that
    was cancelled or abandoned, or past its end, is dropped."""

    __slots__ = ("cond", "label")

    def __init__(self):
        super().__init__(0, None)
        replica = getattr(_serving, "replica", None)
        if replica is None:
            raise RuntimeError(
                "a PushedStream is made by the request it answers")
        self.cond, self.label = replica


def push(handed):
    """Hand chunks to many pushed streams at once. ``handed`` is a list
    of ``(stream, chunks, ended, error)``: the chunks in order, then, if
    ``ended``, the stream's end (where its reader raises ``error``, if
    that is not None, behind the chunks).
    The streams of one replica are filled under ONE acquisition of its
    stream condition, with one time of arrival, and the polls that wait
    on it are woken ONCE."""
    by_cond: dict = {}
    for entry in handed:
        by_cond.setdefault(entry[0].cond, []).append(entry)
    t_yield = time.perf_counter()
    for cond, entries in by_cond.items():
        with cond:
            for stream, chunks, ended, error in entries:
                if stream.cancelled or stream.ended:
                    continue
                stream.ready += [(chunk, t_yield) for chunk in chunks]
                stream.yielded += len(chunks)
                if ended:
                    stream.ended = True
                    if error is not None:
                        from ray_tpu._private.exceptions import TaskError

                        stream.error = TaskError.from_exception(
                            error, stream.label)
            cond.notify_all()


def _abandon(streams: dict, cond: threading.Condition):
    """The replica is gone: end the feeders that wait for a reader, and
    drop what is handed to a pushed stream from now on."""
    with cond:
        for stream in streams.values():
            stream.cancelled = True
        cond.notify_all()


class Replica:
    def __init__(self, cls_or_fn, init_args, init_kwargs,
                 user_config: Optional[dict] = None,
                 deployment_name: str = ""):
        self._lock = threading.Lock()
        self._ongoing = 0
        self._total = 0
        self._window: list[float] = []  # request-arrival timestamps
        # sid -> _Stream, until a caller has taken its last chunk. The
        # condition guards the dict and every stream in it; feeders wait
        # on it for room, polls for something to carry.
        self._streams: dict[int, _Stream] = {}
        self._stream_cond = threading.Condition()
        self._stream_counter = 0
        weakref.finalize(self, _abandon, self._streams, self._stream_cond)
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
        from ray_tpu.util import perfmodel, tracing

        ann = perfmodel.session_annotation("serve.handle_request")
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
            _serving.replica = (self._stream_cond,
                                f"{self._deployment}.stream")
            if callable(self.instance) and method == "__call__":
                target = self.instance
            else:
                target = getattr(self.instance, method)
            result = target(*args, **kwargs)
            if isinstance(result, types.GeneratorType):
                # Streaming response: park the generator; a feeder
                # thread of its own (not one of the actor's call slots)
                # runs it, and the caller reads what it yields from THIS
                # replica. The body runs on that thread, so the
                # request's multiplex id must travel with it.
                if multiplexed_model_id:
                    result = _with_model_id(result, multiplexed_model_id)
                stream = _Stream(0, result)
                sid = self._book(stream)
                threading.Thread(
                    target=_feed, daemon=True, name=f"serve-feed-{sid}",
                    args=(stream, self._stream_cond, self._deployment),
                ).start()
                return {STREAM_MARKER: sid}
            if isinstance(result, PushedStream):
                # The deployment fills it: on the books with no thread
                # behind it, whatever it was handed already in it.
                if result.sid or result.cond is not self._stream_cond:
                    raise TypeError(
                        "a PushedStream answers the one request whose "
                        "user code made it")
                return {STREAM_MARKER: self._book(result)}
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
            _serving.replica = None
            with self._lock:
                self._ongoing -= 1
            slo.set_queue_depth(self._ongoing + len(self._streams),
                                self._deployment)
            if ann is not None:
                ann.__exit__(None, None, None)

    def _book(self, stream: _Stream) -> int:
        """Put a new stream on the books under the next sid."""
        with self._stream_cond:
            self._stream_counter += 1
            sid = stream.sid = self._stream_counter
            self._streams[sid] = stream
        return sid

    def _take(self, stream: _Stream, n: int):
        """(chunks, yield times, done, error) — the stream's first ``n``
        ready chunks, and its end once they are all taken. The stream
        condition is held by the caller."""
        taken, stream.ready = stream.ready[:n], stream.ready[n:]
        done = stream.ended and not stream.ready
        if done:
            self._streams.pop(stream.sid, None)
        return ([c for c, _ in taken], [t for _, t in taken], done,
                stream.error if done else None)

    def _record_reply(self, t_call: float, yielded: list) -> float:
        """One reply left: its duration, and how long each chunk it
        carries sat here since the generator yielded it. Returns the
        instant on the wall clock, for the reply to carry."""
        t_reply, t_wall = time.perf_counter(), time.time()
        slo.record_phase("stream_pull", t_reply - t_call, self._deployment)
        slo.record_phases("stream_hold", [t_reply - t for t in yielded],
                          self._deployment)
        return t_wall

    def stream_poll(self, caller_id: str, grants: Optional[dict] = None):
        """{sid: (chunks, done, error)} — everything the streams of
        ``caller_id`` have ready, as soon as any of them has anything
        (empty after STREAM_POLL_LIMIT_S with nothing), and, where it
        carries anything, under ``REPLY_SENT`` the wall clock at which
        the reply left. ``grants``
        is {sid: count}: how far each stream may now yield (what its
        reader has consumed plus its run-ahead bound)."""
        from ray_tpu.util import perfmodel

        t_call = time.perf_counter()
        deadline = t_call + STREAM_POLL_LIMIT_S
        reply, yielded = {}, []
        with self._stream_cond:
            for sid, upto in (grants or {}).items():
                self._grant(sid, upto)
            while True:
                ready = [s for s in self._streams.values()
                         if s.caller == caller_id
                         and (s.ready or s.ended)]
                wait = deadline - time.perf_counter()
                if ready or wait <= 0:
                    break
                self._stream_cond.wait(wait)
            # From here the poll is code that runs, not a wait.
            ann = perfmodel.session_annotation("serve.stream_poll")
            for stream in ready:
                chunks, times, done, error = self._take(
                    stream, len(stream.ready))
                reply[stream.sid] = (chunks, done, error)
                yielded += times
        t_sent = self._record_reply(t_call, yielded)
        if reply:
            reply[REPLY_SENT] = t_sent
        if ann is not None:
            ann.__exit__(None, None, None)
        return reply

    def stream_next(self, sid: int, max_chunks: int = 16):
        """(chunks, done) — what stream ``sid`` has ready, up to
        max_chunks items: blocks for the first chunk only. The one-stream
        reading of what stream_poll carries; what it returns counts as
        consumed."""
        t_call = time.perf_counter()
        with self._stream_cond:
            stream = self._streams.get(sid)
            if stream is None:
                return [], True
            while not (stream.ready or stream.ended):
                self._stream_cond.wait(STREAM_POLL_LIMIT_S)
            chunks, times, done, error = self._take(stream, max_chunks)
            if error is not None and chunks:
                # An error waits behind the chunks yielded before it:
                # the next call finds nothing ready, and raises.
                self._streams[sid] = stream
                done = False
            self._grant(sid, stream.yielded - len(stream.ready)
                        + STREAM_RUN_AHEAD)
        self._record_reply(t_call, times)
        if error is not None and not chunks:
            raise error
        return chunks, done

    def _grant(self, sid: int, upto: int):
        """Let stream ``sid`` yield up to ``upto`` chunks in all (the
        stream condition is held by the caller). A pushed stream has no
        feeder to tell."""
        stream = self._streams.get(sid)
        if stream is not None and stream.gen is not None \
                and upto > stream.limit:
            stream.limit = upto
            self._stream_cond.notify_all()

    def stream_grant(self, sid: int, upto: int,
                     caller_id: Optional[str] = None):
        """A reader's word, outside a poll: it has consumed enough for
        stream ``sid`` to yield up to ``upto`` chunks, and, with
        ``caller_id`` (a reader's first word), the polls of that caller
        carry the stream's chunks from now on."""
        with self._stream_cond:
            stream = self._streams.get(sid)
            if stream is not None and caller_id is not None:
                stream.caller = caller_id
                self._stream_cond.notify_all()
            self._grant(sid, upto)
        return True

    def stream_cancel(self, sid: int):
        """Free stream ``sid``: its feeder closes the generator, now if
        it waits for a reader, else when the running next() returns; a
        pushed stream drops what it is handed from now on."""
        with self._stream_cond:
            stream = self._streams.pop(sid, None)
            if stream is not None:
                stream.cancelled = True
                self._stream_cond.notify_all()
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
