"""HTTP ingress on aiohttp (asyncio, streaming-capable).

Parity: /root/reference/python/ray/serve/_private/proxy.py — uvicorn ASGI
``HTTPProxy:761`` per node routing to apps by route prefix, with
streaming responses. Ours is an aiohttp application on a dedicated event
loop thread: requests parse JSON (or raw text), dispatch through a
client-side handle (blocking handle calls run on the loop's executor so
the accept loop never blocks), and stream chunked responses when the
deployment returned a generator (newline-delimited JSON frames, raw for
bytes chunks).
"""

from __future__ import annotations

import asyncio
import json
import threading
import time
from typing import Optional


class _StreamWriter:
    """The sink of one HTTP stream (``_StreamEnd.attach``): called on
    the proxy's loop with a reply's share of the stream, it encodes the
    frames and writes them to the response then and there, several in
    one write. ``finished`` resolves at the stream's end: False, True
    where it stalled past the per-chunk deadline, or the stream's error
    (behind the chunks before it) or the connection's.

    What a task around ``await write`` gave is kept without one: when
    the transport's buffer is over its limit the sink takes no more (the
    frames wait in the stream's end, and a generator at its run-ahead
    bound) until ONE drain waiter has seen it empty; and the per-chunk
    deadline is one timer a stream, re-armed when it fires from the
    time of the last frame."""

    def __init__(self, end, request, writer, root, timeout_s: float,
                 arrived: Optional[tuple] = None):
        self.loop = asyncio.get_running_loop()
        self.finished = self.loop.create_future()
        self._end, self._request, self._writer = end, request, writer
        self._root, self._timeout_s = root, timeout_s
        # (the handler's arrival stamp, time.perf_counter(); deployment)
        self._arrived = arrived
        self._first = True
        self._drain = None  # the drain waiter, while the sink is paused
        self._last = self.loop.time()
        self._timer = self.loop.call_later(timeout_s, self._deadline)

    def __call__(self, chunks, ended, error):
        if self.finished.done():
            return False
        self._last = self.loop.time()
        if chunks:
            first, self._first = self._first, False
            if first and self._root is not None:
                # TTFT on the root span: arrival -> first streamed
                # chunk reaches the proxy.
                self._root.add_event(
                    "ttft", ms=(time.time() - self._root.start) * 1e3)
            try:
                # Without the drain ``write`` awaits nothing: run to its
                # end here, it leaves the frames with the transport.
                self._writer.write(b"".join(
                    bytes(c) if isinstance(c, (bytes, bytearray))
                    else (json.dumps(c) + "\n").encode()
                    for c in chunks), drain=False).send(None)
            except StopIteration:
                pass
            except Exception as e:  # noqa: BLE001 - a chunk JSON cannot encode, a connection that is gone: the handler raises it, the loop's other streams go on
                return self._finish(e)
            if first and self._arrived is not None:
                # The server's whole first token: the handler's arrival
                # to the first frame with the transport.
                from . import slo

                t_arrive, deployment = self._arrived
                slo.record_phase(
                    "proxy_ttft", time.perf_counter() - t_arrive, deployment,
                    trace_id=getattr(self._root, "trace_id", None))
        if ended:
            return self._finish(error)
        if self._request.protocol.writing_paused:
            self._drain = self.loop.create_task(self._drained())
            return False
        return True

    async def _drained(self):
        try:
            await self._writer.drain()
        except (ConnectionError, OSError) as e:
            self._finish(e)
            return
        self._drain = None
        self._last = self.loop.time()
        self._end.resume()

    def _deadline(self):
        """The per-chunk deadline: a generator that stalls mid-stream
        past the request timeout ends the response (a reader that
        stalls does not: its frames wait for it)."""
        idle = 0.0 if self._drain is not None \
            else self.loop.time() - self._last
        if idle < self._timeout_s:
            self._timer = self.loop.call_later(
                self._timeout_s - idle, self._deadline)
        else:
            self._finish(timed_out=True)

    def _finish(self, error=None, timed_out: bool = False):
        if not self.finished.done():
            if self._root is not None and not self._first:
                self._root.add_event(
                    "last_token",
                    ms=(time.time() - self._root.start) * 1e3,
                    aborted=timed_out)
            if error is not None:
                self.finished.set_exception(error)
            else:
                self.finished.set_result(timed_out)
        return False

    def stop(self):
        self._timer.cancel()
        if self._drain is not None:
            self._drain.cancel()
        if not self.finished.done():
            self.finished.cancel()
        elif not self.finished.cancelled():
            self.finished.exception()  # seen, where the handler was cancelled


class HTTPProxy:
    def __init__(self, controller, host: str = "127.0.0.1", port: int = 8000,
                 request_timeout_s: float = 60.0):
        self.controller = controller
        self.routes: dict[str, str] = {}  # prefix -> app name
        self.request_timeout_s = request_timeout_s
        self._loop = asyncio.new_event_loop()
        self._runner = None
        started = threading.Event()
        boot_err: list = []

        def main():
            asyncio.set_event_loop(self._loop)
            try:
                self._loop.run_until_complete(self._start(host, port))
            except BaseException as e:  # noqa: BLE001 - surfaced to ctor
                boot_err.append(e)
                started.set()
                return
            started.set()
            self._loop.run_forever()

        self._thread = threading.Thread(target=main, daemon=True,
                                        name="serve-http")
        self._thread.start()
        if not started.wait(30):
            raise TimeoutError("serve HTTP ingress did not start in 30s")
        if boot_err:
            raise boot_err[0]

    async def _start(self, host: str, port: int):
        from aiohttp import web

        app = web.Application()
        app.router.add_route("*", "/{tail:.*}", self._handle)
        self._runner = web.AppRunner(app, access_log=None)
        await self._runner.setup()
        site = web.TCPSite(self._runner, host, port)
        await site.start()
        self.port = site._server.sockets[0].getsockname()[1]

    async def _handle(self, request):
        from aiohttp import web

        from ray_tpu.util import tracing

        from . import slo

        route = self.resolve(request.path)
        if route is None:
            return web.json_response({"error": "no route"}, status=404)
        slo.proxy_inflight(+1)
        # Root span of the request trace (one per HTTP request, always
        # on — the head's tail sampler decides retention). An inbound
        # W3C traceparent header makes this a child of the caller's
        # trace instead of a new root.
        root = tracing.span(
            "serve.request", kind="request",
            ctx=tracing.parse_traceparent(
                request.headers.get("traceparent")),
            attributes={"http.path": request.path,
                        "http.method": request.method,
                        "app": route[0]})
        root.__enter__()
        try:
            resp = await self._handle_routed(request, route, root)
            status = getattr(resp, "status", 200)
            root.attributes["http.status"] = status
            if status >= 500:
                root.attributes["error"] = f"http {status}"
            try:
                # Hand the id back so a curl user can jump straight to
                # `rtpu trace show`. Streaming responses are already
                # prepared (headers sent) — skip, the id still lands in
                # the store.
                resp.headers["x-rtpu-trace-id"] = root.trace_id
            except Exception:  # noqa: BLE001 - headers already sent on a stream
                pass
            return resp
        except BaseException as e:
            root.attributes["error"] = f"{type(e).__name__}: {e}"
            raise
        finally:
            root.__exit__(None, None, None)
            slo.proxy_inflight(-1)

    async def _handle_routed(self, request, route, root):
        import contextvars
        import time as _time

        from aiohttp import web

        from ray_tpu.util import tracing

        from . import slo

        t_arrive = _time.perf_counter()
        t_wall = _time.time()
        app, is_asgi = route
        raw = await request.read()
        if is_asgi:
            # ASGI apps get the FULL request envelope; the replica runs
            # one ASGI cycle and returns {status, headers, body}
            # (serve/asgi.py).
            body = {
                "method": request.method,
                "path": request.path,
                "query_string": request.query_string.encode(),
                "headers": [(k, v) for k, v in request.headers.items()],
                "body": raw,
                "timeout_s": self.request_timeout_s,
            }
        else:
            try:
                body = json.loads(raw) if raw else None
            except json.JSONDecodeError:
                body = raw.decode()

        loop = asyncio.get_running_loop()
        try:
            handle = self.controller.get_app_handle(app)
            # Routing/submission may RPC (replica refresh): off-loop.
            # contextvars don't cross run_in_executor, so the submit
            # runs under a COPY of this task's context — the handle
            # reads the root span's trace context from it and forwards
            # it to the replica.
            cv_ctx = contextvars.copy_context()
            resp = await loop.run_in_executor(
                None, lambda: cv_ctx.run(handle.remote, body))
            # SLO phase: arrival -> dispatched to a replica (routing +
            # proxy-side queueing; replica_queue picks up from here).
            dispatch_dur = _time.perf_counter() - t_arrive
            slo.record_phase("proxy_queue", dispatch_dur, handle._name,
                             trace_id=root.trace_id)
            root.attributes["deployment"] = handle._name
            tracing.emit("serve.proxy_queue", root.context(), t_wall,
                         dispatch_dur, {"deployment": handle._name})
            try:
                # Fast path: await the result future directly — a
                # second executor hop for a blocking .result() costs
                # ~2ms of thread handoffs per request on a busy box.
                result = await asyncio.wait_for(
                    asyncio.wrap_future(resp._ref.future()),
                    self.request_timeout_s)
            except (TimeoutError, asyncio.TimeoutError):
                raise
            except Exception:  # noqa: BLE001 - dead replica et al.
                # Slow path: .result() owns the retry-through-a-fresh-
                # replica logic (and re-raises user errors).
                result = await asyncio.wait_for(
                    loop.run_in_executor(
                        None, lambda: resp.result(self.request_timeout_s)),
                    self.request_timeout_s + 5,
                )
        except (TimeoutError, asyncio.TimeoutError):
            return web.json_response({"error": "request timed out"},
                                     status=504)
        except Exception as e:  # noqa: BLE001 - surfaced as 500
            return web.json_response({"error": str(e)}, status=500)

        from .replica import STREAM_MARKER

        if isinstance(result, dict) and STREAM_MARKER in result:
            return await self._stream(request, resp, result, root,
                                      (t_arrive, handle._name))
        if is_asgi and isinstance(result, dict) and "status" in result:
            from multidict import CIMultiDict

            # Pair-list, not dict: duplicate names (Set-Cookie!) must
            # all reach the client.
            hdrs = CIMultiDict(
                (k, v) for k, v in result.get("headers", [])
                if k.lower() not in ("content-length",
                                     "transfer-encoding"))
            return web.Response(status=result["status"],
                                body=result.get("body", b""),
                                headers=hdrs)
        return web.json_response(result)

    async def _stream(self, request, resp, result, root=None,
                      arrived: Optional[tuple] = None):
        """Chunked transfer of a generator response: each chunk is a raw
        bytes frame or one newline-delimited JSON document. The frames
        are written where the handle's poller hands them to this loop
        (``_StreamWriter``, the stream's sink): this task waits for the
        stream's end and for nothing else."""
        from aiohttp import web

        headers = {"Content-Type": "application/x-ndjson"}
        if root is not None:
            headers["x-rtpu-trace-id"] = root.trace_id
        sr = web.StreamResponse(headers=headers)
        sr.enable_chunked_encoding()
        writer = await sr.prepare(request)
        end = resp.open_stream(result)
        out = _StreamWriter(end, request, writer, root,
                            self.request_timeout_s, arrived)
        try:
            end.attach(out.loop, out)
            timed_out = await out.finished
        finally:
            # Free the replica-side generator (a no-op once the stream
            # has ended): also when the client disconnected, which fails
            # the next write or cancels this handler mid-await.
            out.stop()
            end.close()
        if timed_out:
            # In-band error frame, then abort the connection WITHOUT the
            # terminating chunk: a truncated stream must not look like a
            # well-formed completed one to the client.
            try:
                await sr.write(b'{"error": "stream chunk timed out"}\n')
            except (ConnectionError, OSError):
                pass
            if request.transport is not None:
                request.transport.close()
            return sr
        await sr.write_eof()
        return sr

    def add_route(self, prefix: str, app_name: str, asgi: bool = False):
        self.routes[prefix.rstrip("/") or "/"] = (app_name, asgi)

    def set_routes(self, routes: dict):
        """Replace the whole table: {prefix: (app_name, asgi)} — the
        controller's broadcast to the proxy fleet."""
        self.routes = {p.rstrip("/") or "/": tuple(v)
                       for p, v in routes.items()}

    def prune_slo(self, deployment: str):
        """Controller broadcast on redeploy/teardown: proxies outlive
        deployments, so their SLO cells/exemplars for a dead deployment
        must be dropped explicitly."""
        from . import slo

        slo.prune_deployment(deployment)
        return True

    def resolve(self, path: str) -> Optional[tuple]:
        path = path.split("?")[0].rstrip("/") or "/"
        best = None
        for prefix, route in self.routes.items():
            if path == prefix or path.startswith(
                    prefix if prefix.endswith("/") else prefix + "/") or \
                    prefix == "/":
                if best is None or len(prefix) > len(best[0]):
                    best = (prefix, route)
        return best[1] if best else None

    def shutdown(self):
        async def stop():
            if self._runner is not None:
                await self._runner.cleanup()
            self._loop.stop()

        try:
            asyncio.run_coroutine_threadsafe(stop(), self._loop)
            self._thread.join(timeout=5)
        except Exception:  # lint: allow-swallow(best-effort shutdown)
            pass
