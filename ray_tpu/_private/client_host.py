"""Per-client session host: the cluster-side driver an rtpu:// client
drives by proxy.

One process per client session (spawned by client_server.py): attaches
to the cluster as a regular driver, serves the client's proxied context
calls over a unix socket, and holds a REGISTRY of ObjectRefs on the
client's behalf — the cluster-side refcounts live here, so a vanished
client can never leak cluster objects past its session (the proxy kills
this process when the client disconnects, and the registry dies with
it).

Reference parity: the Ray Client "specific server" — one dedicated
driver proxy process per client session
(/root/reference/python/ray/util/client/server/server.py, proto
src/ray/protobuf/ray_client.proto:326 RayletDriver service; log
streaming :466 LogStreamer).
"""

from __future__ import annotations

import asyncio
import os
import sys
import threading
from concurrent.futures import ThreadPoolExecutor

import cloudpickle

from .ids import ActorID, ObjectID, PlacementGroupID
from .object_ref import ObjectRef


class _StderrTee:
    """Forward driver stderr lines (worker log streaming lands there) to
    the client while keeping the local stream intact (reference:
    LogStreamer, ray_client.proto:466)."""

    def __init__(self, real, push):
        self._real = real
        self._push = push
        self._buf = ""

    def write(self, s):
        self._real.write(s)
        self._buf += s
        while "\n" in self._buf:
            line, self._buf = self._buf.split("\n", 1)
            if line:
                self._push(line)
        return len(s)

    def flush(self):
        self._real.flush()

    def __getattr__(self, name):
        return getattr(self._real, name)


class SessionHost:
    def __init__(self, rt):
        self.rt = rt
        # Client-held refs: id bytes -> [ObjectRef, count]. The host-side
        # ObjectRef keeps the cluster refcount; `count` mirrors how many
        # client-side handles exist.
        self.registry: dict[bytes, list] = {}
        self._reg_lock = threading.Lock()
        # Blocking runtime calls run here, never on the server loop.
        self.pool = ThreadPoolExecutor(max_workers=8,
                                       thread_name_prefix="client-host")
        self._log_conns: set = set()
        self._server_loop = None
        # Client pubsub: (id(conn), channel) -> sub_id of the fn sink
        # registered on the session runtime's node.
        self._client_subs: dict = {}

    # -- client pubsub (session-host side of the proxy) -------------------
    async def client_pubsub_subscribe(self, conn, channel: str):
        import uuid as _uuid

        key = (id(conn), channel)
        if key in self._client_subs:
            return
        sub_id = "client:" + _uuid.uuid4().hex
        self._client_subs[key] = sub_id
        loop = self._server_loop

        def forward(message, _ch=channel):
            # Called on the runtime's loop thread; the conn belongs to
            # the server loop — hop threads, fire-and-forget.
            def send():
                from .rpc import _keep_task

                _keep_task(asyncio.ensure_future(conn.notify(
                    "pubsub_msg", {"channel": _ch, "message": message})))
            try:
                loop.call_soon_threadsafe(send)
            except RuntimeError:
                pass  # server shutting down

        rt = self.rt
        await asyncio.wrap_future(asyncio.run_coroutine_threadsafe(
            rt.node.pubsub_subscribe(channel, sub_id, ("fn", forward)),
            rt.loop))

    async def client_pubsub_unsubscribe(self, conn, channel: str):
        sub_id = self._client_subs.pop((id(conn), channel), None)
        if sub_id is None:
            return
        rt = self.rt
        await asyncio.wrap_future(asyncio.run_coroutine_threadsafe(
            rt.node.pubsub_unsubscribe(channel, sub_id), rt.loop))

    async def client_pubsub_drop_conn(self, conn):
        """A disconnected client can never unsubscribe: sweep its sinks."""
        for (cid, channel) in [k for k in self._client_subs
                               if k[0] == id(conn)]:
            await self.client_pubsub_unsubscribe(conn, channel)

    # -- registry ---------------------------------------------------------
    def _track(self, ref: ObjectRef) -> bytes:
        b = ref.id.binary()
        with self._reg_lock:
            ent = self.registry.get(b)
            if ent is None:
                self.registry[b] = [ref, 1]
            else:
                ent[1] += 1
        return b

    def _ref(self, b: bytes) -> ObjectRef:
        with self._reg_lock:
            ent = self.registry.get(b)
        if ent is None:
            # A ref the client rebuilt from a serialized handle (e.g. it
            # round-tripped through client-side state) — adopt it.
            r = ObjectRef(ObjectID(b), _register=True)
            self._track(r)
            return r
        return ent[0]

    def submit_spec_nb(self, payload):
        """Fire-and-forget submit from the client (cpu-lane fast path):
        no reply — the client computed the return ids locally. Track the
        refs here (cluster-side refcounts live in this registry); a
        failed submission poisons those ids so the error surfaces on the
        client's next get()."""
        rt = self.rt
        rids = payload["rids"]
        try:
            spec = cloudpickle.loads(payload["blob"])
            refs = rt.submit_spec(spec)
        except BaseException as e:  # noqa: BLE001 - poison the returns
            from .exceptions import TaskError

            err = e if isinstance(e, TaskError) \
                else TaskError.from_exception(e, "submit")
            for b in rids:
                self._track(ObjectRef(ObjectID(b), _register=True))
                rt._call_soon(rt.node.mark_error, ObjectID(b), err)
            return
        for r in refs:
            self._track(r)

    # -- dispatch (runs in self.pool threads) ----------------------------
    def handle(self, method: str, payload):
        rt = self.rt
        if method == "submit_spec":
            spec = cloudpickle.loads(payload)
            refs = rt.submit_spec(spec)
            return [self._track(r) for r in refs]
        if method == "put":
            value = cloudpickle.loads(payload)
            return self._track(rt.put(value))
        if method == "get":
            refs = [self._ref(b) for b in payload["ids"]]
            # List in -> list out; the client re-singles.
            values = rt.get(refs, timeout=payload.get("timeout"))
            return [cloudpickle.dumps(v) for v in values]
        if method == "wait":
            refs = [self._ref(b) for b in payload["ids"]]
            ready, not_ready = rt.wait(refs,
                                       num_returns=payload["num_returns"],
                                       timeout=payload.get("timeout"))
            return {"ready": [r.id.binary() for r in ready],
                    "not_ready": [r.id.binary() for r in not_ready]}
        if method == "export_function":
            fid, blob = payload["fid"], payload["blob"]
            rt._call_soon(rt.node.functions.__setitem__, fid, blob)
            return fid
        if method == "incref":
            with self._reg_lock:
                ent = self.registry.get(payload)
                if ent is not None:
                    ent[1] += 1
            return True
        if method == "decref_batch":
            drop = []
            with self._reg_lock:
                for b in payload:
                    ent = self.registry.get(b)
                    if ent is None:
                        continue
                    ent[1] -= 1
                    if ent[1] <= 0:
                        drop.append(self.registry.pop(b)[0])
            del drop  # host ObjectRefs release their cluster counts here
            return True
        if method == "free":
            # Client-initiated eager value release (ray_tpu.free via an
            # rtpu:// session): forward to the session runtime's node.
            with self._reg_lock:
                ent = self.registry.get(payload)
            ref = ent[0] if ent is not None else None
            if ref is not None:
                rt.free(ref.id, ref.owner_addr)
            return True
        if method == "kill_actor":
            rt.kill_actor(ActorID(payload["actor_id"]),
                          payload.get("no_restart", True))
            return True
        if method == "cancel":
            rt.cancel(self._ref(payload["id"]),
                      force=payload.get("force", False))
            return True
        if method == "get_actor_by_name":
            return rt.get_actor_by_name(payload)
        if method == "kv_op":
            return rt.kv_op(payload["op"], payload["key"], payload.get("val"))
        if method == "create_pg":
            pg_id = rt.create_placement_group(payload["bundles"],
                                              payload["strategy"])
            return pg_id.binary()
        if method == "remove_pg":
            rt.remove_placement_group(PlacementGroupID(payload))
            return True
        if method == "pg_state":
            return rt.placement_group_state(PlacementGroupID(payload))
        if method == "pg_wait":
            return rt.wait_placement_group_ready(
                PlacementGroupID(payload["pg_id"]), payload.get("timeout"))
        if method == "cluster_resources":
            return rt.cluster_resources()
        if method == "available_resources":
            return rt.available_resources()
        if method == "list_nodes":
            return rt.list_nodes()
        if method == "list_pgs":
            return rt.list_placement_groups()
        if method == "cluster_state":
            return rt.cluster_state(**(payload or {}))
        if method == "timeseries":
            return rt.timeseries(**(payload or {}))
        if method == "get_trace":
            return rt.get_trace(**(payload or {}))
        if method == "list_traces":
            return rt.list_traces(**(payload or {}))
        if method == "declare_slo":
            return rt.declare_slo(**(payload or {}))
        if method == "list_alerts":
            return rt.list_alerts(**(payload or {}))
        if method == "list_incidents":
            return rt.list_incidents(**(payload or {}))
        if method == "get_incident":
            return rt.get_incident(**(payload or {}))
        if method == "cluster_logs":
            return rt.cluster_logs(**(payload or {}))
        if method == "session_info":
            return {"job_id": rt.job_id.binary(),
                    "session_id": rt.session_id,
                    "node_id": rt.node_id.binary(),
                    "worker_id": rt.worker_id.binary(),
                    "pid": os.getpid()}
        if method == "pubsub_publish":
            if payload["channel"].startswith("__"):
                raise ValueError(
                    f"channel {payload['channel']!r} is reserved")
            return rt.pubsub_publish(payload["channel"],
                                     payload["message"])
        if method == "ping":
            return "pong"
        raise ValueError(f"unknown client method {method!r}")

    def push_log(self, line: str):
        loop = self._server_loop
        if loop is None or not self._log_conns:
            return
        def send():
            from .rpc import _keep_task

            for conn in list(self._log_conns):
                try:
                    _keep_task(asyncio.ensure_future(
                        conn.notify("log", line)))
                except Exception:  # lint: allow-swallow(client stream gone; log line dropped)
                    self._log_conns.discard(conn)
        try:
            loop.call_soon_threadsafe(send)
        except RuntimeError:
            pass


async def _serve(host: SessionHost, sock_path: str):
    from .rpc import DuplexServer

    host._server_loop = asyncio.get_running_loop()

    async def handler(conn, method, payload):
        if method == "subscribe_logs":
            host._log_conns.add(conn)
            return True
        if method == "submit_spec_nb":
            # Fire-and-forget submit: handled INLINE (not on the pool)
            # so the registry holds the refs before any pool-dispatched
            # get()/wait() the client pipelined right behind it.
            host.submit_spec_nb(payload)
            return True
        if method == "pubsub_subscribe":
            # Registered here (not via host.handle) because delivery
            # needs THIS conn: a per-channel fn sink on the session
            # runtime's node forwards messages to the client.
            channel = payload["channel"]
            if channel.startswith("__"):
                return ("err", cloudpickle.dumps(ValueError(
                    f"channel {channel!r} is reserved")))
            await host.client_pubsub_subscribe(conn, channel)
            return ("ok", True)
        if method == "pubsub_unsubscribe":
            await host.client_pubsub_unsubscribe(conn,
                                                 payload["channel"])
            return ("ok", True)
        # Exception FIDELITY across the proxy: the raw RPC layer
        # flattens exceptions to strings, so client code could never
        # `except GetTimeoutError` / catch its own task errors. Ship the
        # original exception object in-band instead; the client re-raises
        # it (reference: ray client marshals real exceptions back).
        try:
            result = await host._server_loop.run_in_executor(
                host.pool, host.handle, method, payload)
            return ("ok", result)
        except BaseException as e:  # noqa: BLE001 - marshalled to client
            try:
                blob = cloudpickle.dumps(e)
            except Exception:  # noqa: BLE001 - unpicklable exception
                blob = cloudpickle.dumps(RuntimeError(repr(e)))
            return ("err", blob)

    async def on_disconnect(conn):
        host._log_conns.discard(conn)
        await host.client_pubsub_drop_conn(conn)

    server = DuplexServer(sock_path, handler, on_disconnect)
    await server.start()
    # Parent (the proxy) watches this marker to know we are up.
    with open(sock_path + ".ready", "w") as f:
        f.write(str(os.getpid()))
    await asyncio.Event().wait()


def main():
    # The session host is a cluster-side CPU process; the chip belongs
    # to the node daemon's device lane.
    os.environ["JAX_PLATFORMS"] = "cpu"
    from . import rpc as _rpc

    _rpc.discover_session_token()
    sock_path = os.environ["RT_CLIENT_HOST_SOCK"]

    import ray_tpu

    rt = ray_tpu.init(address=os.environ["RT_ADDRESS"])
    host = SessionHost(rt)
    sys.stderr = _StderrTee(sys.stderr, host.push_log)
    try:
        asyncio.run(_serve(host, sock_path))
    except KeyboardInterrupt:
        pass
    finally:
        ray_tpu.shutdown()


if __name__ == "__main__":
    main()
