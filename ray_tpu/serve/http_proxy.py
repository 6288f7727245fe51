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
from typing import Optional


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
            return await self._stream(request, resp, root)
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

    async def _stream(self, request, resp, root=None):
        """Chunked transfer of a generator response: each chunk is a raw
        bytes frame or one newline-delimited JSON document."""
        import time as _time

        from aiohttp import web

        headers = {"Content-Type": "application/x-ndjson"}
        if root is not None:
            headers["x-rtpu-trace-id"] = root.trace_id
        sr = web.StreamResponse(headers=headers)
        sr.enable_chunked_encoding()
        await sr.prepare(request)
        # A waiting stream holds no thread: the handle's poller wakes
        # this task when the replica has sent the stream's next chunk.
        it = resp.aiter_stream(timeout=self.request_timeout_s)
        timed_out = False
        first_chunk = True
        try:
            try:
                # Per-chunk deadline: a generator that stalls mid-stream
                # past the request timeout ends the response, and the
                # client sees an ABORTED (not cleanly completed) stream.
                async for chunk in it:
                    if first_chunk and root is not None:
                        # TTFT on the root span: arrival -> first
                        # streamed chunk reaches the proxy.
                        root.add_event(
                            "ttft",
                            ms=(_time.time() - root.start) * 1e3)
                        first_chunk = False
                    if isinstance(chunk, (bytes, bytearray)):
                        await sr.write(bytes(chunk))
                    else:
                        await sr.write((json.dumps(chunk) + "\n").encode())
            except (TimeoutError, asyncio.TimeoutError):
                timed_out = True
            if root is not None and not first_chunk:
                root.add_event(
                    "last_token",
                    ms=(_time.time() - root.start) * 1e3,
                    aborted=timed_out)
        finally:
            # Free the replica-side generator (a no-op once the stream
            # has ended): also when the client disconnected, cancelling
            # this handler mid-await.
            await it.aclose()
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
