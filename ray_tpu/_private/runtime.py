"""Driver-side runtime: owns the node service and bridges sync API calls.

Capability parity target: the reference's driver bring-up
(/root/reference/python/ray/_private/worker.py:1227 `init` and node.py
process orchestration). Round-1 topology: this process is simultaneously the
head node (control plane), the node-owner (device executor owns the TPU
chips) and the driver. Multi-node attach comes in later rounds via the same
RPC protocol over TCP/DCN.
"""

from __future__ import annotations

import asyncio
import atexit
import os
import threading
import time
import uuid
from concurrent.futures import Future
from typing import Any, Optional, Sequence

from . import context as context_mod
from . import serialization
from .config import get_config
from .exceptions import GetTimeoutError
from .ids import ActorID, JobID, NodeID, ObjectID, TaskID, WorkerID
from .node_service import ERROR, PENDING, NodeService, raise_stored
from .object_ref import ObjectRef
from .object_store import make_store
from .task_spec import TaskSpec, export_function


def _tune_malloc():
    """Pin glibc's mmap threshold (default: pinned at 128KiB, override
    with RT_MALLOC_MMAP_THRESHOLD bytes, 0 = leave the allocator alone).

    Why: glibc's threshold is DYNAMIC — after a few multi-MB
    malloc/free cycles it ratchets up (to 32MB), after which
    block-sized numpy buffers are served from the main heap and freed
    memory stays resident (RSS high-water ≈ everything ever alive at
    once, ~2x the true working set for streaming Data). Pinning keeps
    large buffers mmap-backed so frees return pages to the OS
    immediately. Workers inherit via MALLOC_MMAP_THRESHOLD_."""
    raw = os.environ.get("RT_MALLOC_MMAP_THRESHOLD", "131072")
    try:
        threshold = int(raw)
    except ValueError:
        return
    if threshold <= 0:
        return
    # Subprocesses (CPU-lane workers, node/head daemons) inherit the
    # same pin through glibc's tunable env var.
    os.environ.setdefault("MALLOC_MMAP_THRESHOLD_", str(threshold))
    try:
        import ctypes

        libc = ctypes.CDLL(None, use_errno=True)
        m_mmap_threshold = -3  # glibc malloc.h M_MMAP_THRESHOLD
        libc.mallopt(m_mmap_threshold, threshold)
    except (OSError, AttributeError):
        pass  # non-glibc platform: the env var still covers children


def _detect_resources(num_cpus=None, num_tpus=None, resources=None) -> dict:
    out = dict(resources or {})
    if num_cpus is None:
        num_cpus = os.cpu_count() or 1
    out.setdefault("CPU", float(num_cpus))
    if num_tpus is None and "TPU" in out:
        num_tpus = 0  # explicit resources["TPU"] wins; don't probe
    if num_tpus is None:
        # Counted in-process: this process hosts the device lane, so it
        # is the one that owns the host's chips (backend_probe.py).
        # Backend-init failures propagate.
        from .backend_probe import device_count

        num_tpus = device_count()
    out.setdefault("TPU", float(num_tpus))
    # Any local accelerator counts as the "device" lane even under the CPU
    # jax backend (tests use a virtual CPU mesh).
    out.setdefault("device", max(out["TPU"], 1.0))
    # One TPU_HOST slot per chip-bearing node: a gang worker that claims it
    # owns ALL the host's chips (one multi-controller SPMD process per
    # host). Scheduling N gang workers with {"TPU_HOST": 1} each therefore
    # lands exactly one per host. Chip-less nodes advertise 0 so spread
    # can't put a gang member where there is nothing to own.
    out.setdefault("TPU_HOST", 1.0 if out["TPU"] > 0 else 0.0)
    return out


class Runtime:
    """One per driver process; the execution context for the driver."""

    def __init__(self, num_cpus=None, num_tpus=None, resources=None,
                 system_config: dict | None = None,
                 address: str | tuple | None = None,
                 runtime_env: dict | None = None):
        from ray_tpu import runtime_env as _re

        self.cfg = get_config().apply_overrides(system_config)
        # Job-level default environment (reference: ray.init(runtime_env=)
        # applied to every task/actor of the job, merged task-side).
        self.default_runtime_env = _re.validate(runtime_env)
        self.session_id = uuid.uuid4().hex[:12]
        # Session token: every RPC connection (head, peers, workers)
        # authenticates with it in the HELLO handshake — nothing is
        # unpickled from an unauthenticated socket. A new head mints one;
        # attaching drivers/nodes must present the cluster's (via the
        # RT_SESSION_TOKEN env, set by `rtpu start` / cluster_utils).
        import secrets

        from . import rpc as _rpc

        token = os.environ.get("RT_SESSION_TOKEN")
        if not token and address is not None:
            # Attaching without an explicit credential: shared discovery
            # (env, then the head's token file).
            token = _rpc.discover_session_token()
        token = token or secrets.token_hex(16)
        os.environ["RT_SESSION_TOKEN"] = token  # children inherit
        _rpc.set_session_token(token)
        self.job_id = JobID.from_random()
        self.node_id = NodeID.from_random()
        self.worker_id = WorkerID.from_random()
        self._driver_task = TaskID.for_task(self.job_id)
        self._put_counter = 0
        self._put_lock = threading.Lock()
        if isinstance(address, str):
            host, sep, port = address.rpartition(":")
            if not sep or not host or not port.isdigit():
                raise ValueError(
                    f"address must be 'host:port', got {address!r}")
            address = (host, int(port))
        self._attach_addr = tuple(address) if address else None

        # Sweep /dev/shm debris of dead sessions (kill -9'd daemons,
        # crashed drivers) before claiming more of it, and pin glibc's
        # dynamic mmap threshold so block-sized numpy buffers return to
        # the OS on free (streaming Data would otherwise ratchet RSS to
        # its high-water mark — the reference leans on jemalloc for the
        # same reason).
        from .object_store import reap_orphan_sessions

        reap_orphan_sessions()
        _tune_malloc()
        self.shm = make_store(self.session_id)
        sock_dir = os.environ.get("RT_SOCK_DIR", "/tmp")
        self.sock_path = os.path.join(sock_dir, f"rtpu-{self.session_id}.sock")

        self.loop = asyncio.new_event_loop()
        self._loop_thread = threading.Thread(
            target=self._loop_main, daemon=True, name="rt-core-loop"
        )
        self._started = threading.Event()
        self.node: NodeService | None = None
        self.head = None
        self._startup_error: BaseException | None = None
        if self._attach_addr is not None:
            # An attaching driver contributes NO resources by default —
            # it is a client of the cluster, not extra capacity (the
            # reference's `ray.init(address=...)` driver likewise doesn't
            # add a node's worth of CPUs; its host already registered
            # them).
            self._resources = _detect_resources(
                num_cpus if num_cpus is not None else 0,
                num_tpus if num_tpus is not None else 0, resources)
            # ...but only zero what the user didn't set explicitly.
            explicit = resources or {}
            if num_tpus is None:
                if "TPU_HOST" not in explicit:
                    self._resources["TPU_HOST"] = 0.0
                if "device" not in explicit:
                    self._resources["device"] = 0.0
        else:
            self._resources = _detect_resources(num_cpus, num_tpus,
                                                resources)
        self._loop_thread.start()
        self._started.wait()
        if self._startup_error is not None:
            # Failed bring-up must not leak the shm namespace or any
            # half-started servers (atexit was never registered).
            try:
                self.loop.call_soon_threadsafe(self.loop.stop)
                self._loop_thread.join(timeout=5)
            except Exception:  # lint: allow-swallow(bring-up cleanup; startup error re-raised below)
                pass
            try:
                self.shm.destroy()
            except Exception:  # lint: allow-swallow(bring-up cleanup; startup error re-raised below)
                pass
            raise self._startup_error
        atexit.register(self.shutdown)

    def _loop_main(self):
        asyncio.set_event_loop(self.loop)
        # Concurrency net (VERDICT r4 item 10): RT_ASYNC_DEBUG=1 turns
        # on asyncio debug mode (never-retrieved exceptions, slow
        # callbacks, non-threadsafe calls); RT_LOOP_WATCHDOG_S=N starts
        # a blocked-event-loop watchdog. The test suite enables both.
        if os.environ.get("RT_ASYNC_DEBUG", "") not in ("", "0"):
            self.loop.set_debug(True)
            self.loop.slow_callback_duration = float(
                os.environ.get("RT_SLOW_CALLBACK_S", "0.5"))
        self._start_loop_watchdog()
        try:
            if self._attach_addr is not None:
                self.loop.run_until_complete(self._attach())
            else:
                self._start_head()
        except BaseException as e:  # noqa: BLE001 - surface to __init__
            self._startup_error = e
            self._started.set()
            return
        self._started.set()
        self.loop.run_forever()

    def _start_loop_watchdog(self):
        """A stalled event loop is the whole control plane stalled —
        heartbeats, dispatch, object waits. The watchdog schedules a
        beat onto the loop every period; a beat that fails to land
        within a full period means some callback is BLOCKING the loop
        (sync IO, a lock, C-level spin), and the watchdog dumps every
        thread's stack to stderr so the culprit is named (reference
        discipline: the reference's TSAN/deadlock release jobs; SURVEY
        §5 race detection)."""
        period = float(os.environ.get("RT_LOOP_WATCHDOG_S", "0") or 0)
        if period <= 0:
            return
        state = {"beat": 0, "ack": 0}

        def ack(n):
            state["ack"] = n

        def run():
            import faulthandler
            import sys as _sys

            while not getattr(self, "_shut", False):
                if self.loop.is_closed():
                    return
                state["beat"] += 1
                n = state["beat"]
                try:
                    self.loop.call_soon_threadsafe(ack, n)
                except RuntimeError:
                    return  # loop closed
                time.sleep(period)
                if state["ack"] < n and not getattr(self, "_shut", False) \
                        and self.loop.is_running():
                    _sys.stderr.write(
                        f"ray_tpu: EVENT LOOP BLOCKED >{period:.1f}s — "
                        f"thread stacks follow\n")
                    faulthandler.dump_traceback(file=_sys.stderr)

        threading.Thread(target=run, daemon=True,
                         name="rt-loop-watchdog").start()

    def _start_head(self):
        from .head import HeadService, LocalHeadClient, NodeEntry

        # The driver process is the head node (`ray start --head` shape):
        # head control plane + its own node service share this loop.
        self.head = HeadService(self.session_id, self.loop,
                                port=int(os.environ.get("RT_HEAD_PORT", "0")))
        self.loop.run_until_complete(self.head.start())
        self.node = NodeService(
            self.session_id, self.sock_path, self._resources, self.shm,
            self.loop, node_id=self.node_id, head=LocalHeadClient(self.head),
            is_head_node=True,
        )
        self.loop.run_until_complete(self.node.start())
        entry = NodeEntry(
            node_id=self.node_id, address=self.node.peer_address,
            resources=dict(self._resources),
            available=dict(self._resources),  # refreshed by heartbeats
            is_head_node=True, labels=dict(self.node.labels))
        self.head.attach_local_node(self.node, entry)

    async def _attach(self):
        """Join an existing cluster as a driver node (reference:
        ``ray.init(address=...)`` connecting a driver to a running GCS,
        python/ray/_private/worker.py:1227 'connect' path; node
        registration shares node_main.py's bring-up via
        attach_node_to_head)."""
        import sys
        import threading

        from .node_service import attach_node_to_head

        node = NodeService(
            self.session_id, self.sock_path, self._resources, self.shm,
            self.loop, node_id=self.node_id, head=None, is_head_node=False)
        # A driver's workers log to THIS driver's console (not the head's).
        node.is_driver_node = True

        reconnecting = {"active": False}

        async def on_head_lost(conn):
            if getattr(self, "_shut", False) or reconnecting["active"]:
                return  # our own shutdown closed it / already retrying
            # The head may be RESTARTING (reference: drivers survive a
            # GCS restart like raylets do, resyncing via
            # NotifyGCSRestart): retry the dial for the grace period
            # before declaring the cluster gone. In-flight tasks on
            # worker nodes keep running either way — results ride peer
            # connections, not the head.
            reconnecting["active"] = True
            try:
                from .rpc import ConnectionLost

                grace = self.cfg.head_reconnect_grace_s
                sys.stderr.write(
                    f"ray_tpu: head connection lost; retrying for "
                    f"{grace:.0f}s\n")
                deadline = self.loop.time() + grace
                while self.loop.time() < deadline:
                    if getattr(self, "_shut", False):
                        return
                    try:
                        await attach_node_to_head(
                            node, self._attach_addr, self._resources,
                            is_driver=True, on_lost=on_head_lost,
                            start=False)
                        sys.stderr.write(
                            "ray_tpu: re-registered with restarted head\n")
                        return
                    except (OSError, ConnectionLost):
                        await asyncio.sleep(1.0)
            finally:
                reconnecting["active"] = False
            # Grace exhausted: the cluster is gone. Unlike the node
            # daemon (which exits), a library must not kill the user's
            # process: tear the runtime down so later API calls fail
            # fast, and leave the process alive.
            sys.stderr.write("ray_tpu: head did not come back; shutting "
                             "down this driver's runtime\n")
            threading.Thread(target=self.shutdown, daemon=True).start()

        self.node = node
        await attach_node_to_head(node, self._attach_addr,
                                  self._resources, is_driver=True,
                                  on_lost=on_head_lost)

        # Cluster worker logs reach attached drivers over the general
        # pubsub plane on per-owner channels: the head publishes OUR
        # job's lines on __worker_logs__:<our-node-hex> and unattributed
        # lines on __worker_logs__:* — so another session's output never
        # reaches this process (the reference's per-job log
        # subscription via GCS pubsub).
        from .head import WORKER_LOG_CHANNEL
        from .node_service import format_worker_logs

        def render_logs(payload):
            text = format_worker_logs(payload.get("node_hex", ""),
                                      payload.get("entries", ()))
            if text:
                sys.stderr.write(text)

        for chan in (f"{WORKER_LOG_CHANNEL}:{self.node_id.hex()}",
                     f"{WORKER_LOG_CHANNEL}:*"):
            await node.pubsub_subscribe(chan, "driver-console",
                                        ("fn", render_logs))

    @property
    def head_address(self) -> tuple:
        if self._attach_addr is not None:
            return self._attach_addr
        return self.head.address

    def _run(self, coro, timeout=None):
        return asyncio.run_coroutine_threadsafe(coro, self.loop).result(timeout)

    def _call_soon(self, fn, *args):
        self.loop.call_soon_threadsafe(fn, *args)

    # -- context protocol --------------------------------------------------
    @property
    def current_task_id(self):
        from .worker import _running_task

        return _running_task.get()

    @property
    def current_actor_id(self):
        return None

    def incref(self, oid: ObjectID, owner_addr=None):
        if self.loop.is_running():
            # Foreign-owned refs (owner_addr of another node) register a
            # borrow with the owner so it defers the free to us.
            self._call_soon(self.node.incref_ref, oid, owner_addr)

    def decref(self, oid: ObjectID, owner_addr=None):
        if self.loop.is_running():
            try:
                self._call_soon(self.node.decref, oid)
            except RuntimeError:
                pass  # interpreter shutdown

    def free(self, oid: ObjectID, owner_addr=None):
        """Eagerly release an object's value (``ray_tpu.free``): local
        objects free on the loop thread now; foreign-owned are dropped
        locally and the free is forwarded to the owner."""
        if not self.loop.is_running():
            return
        if owner_addr is not None and \
                tuple(owner_addr) != tuple(self.node.peer_address):
            self._call_soon(
                lambda: self.node.spawn(
                    self.node._notify_free_remote(oid, tuple(owner_addr))))
        else:
            self._call_soon(self.node.free_object, oid)

    def export_function(self, fn) -> str:
        fid, blob = export_function(fn)
        if fid not in self.node.functions:
            self._call_soon(self.node.functions.__setitem__, fid, blob)
        return fid

    # -- pubsub --------------------------------------------------------
    def pubsub_subscribe(self, channel: str, sub_id: str, q) -> None:
        self._run(self.node.pubsub_subscribe(channel, sub_id, ("q", q)))

    def pubsub_unsubscribe(self, channel: str, sub_id: str) -> None:
        self._run(self.node.pubsub_unsubscribe(channel, sub_id))

    def pubsub_publish(self, channel: str, message) -> int:
        return self._run(self.node.pubsub_publish(channel, message))

    @property
    def node_addr(self) -> tuple:
        return self.node.peer_address

    def submit_spec(self, spec: TaskSpec) -> list[ObjectRef]:
        # Fire-and-forget: return ids are DETERMINISTIC (task_id +
        # index), so the caller need not wait for the loop to accept the
        # spec — a submission used to cost a full round trip into a
        # possibly-busy event loop (~1ms under load; the single biggest
        # term in serve's request path). Ordering safety: any later
        # get/wait/cancel from this thread reaches the loop through the
        # same FIFO (call_soon_threadsafe), strictly after the submit.
        # Error backchannel: with no reply to carry a submission error,
        # a failure poisons the locally computed return ids instead —
        # the same _fail_task path every other task failure takes.
        rids = spec.return_ids()
        self._call_soon(self._submit_guarded, spec)
        return [ObjectRef(r, _register=False, owner_addr=self.node_addr)
                for r in rids]

    def _submit_guarded(self, spec: TaskSpec):
        from .exceptions import TaskError

        try:
            self.node.submit(spec)
        except BaseException as e:  # noqa: BLE001 - poison the returns
            err = e if isinstance(e, TaskError) \
                else TaskError.from_exception(e, spec.name)
            self.node._fail_task(spec, err)

    def put(self, value: Any) -> ObjectRef:
        with self._put_lock:
            self._put_counter += 1
            idx = self._put_counter
        oid = ObjectID.for_put(self._driver_task, idx)
        # Refs nested inside the value are pinned by the container for its
        # lifetime (attach below) — dropping the standalone handles can't
        # free what the container still points to.
        parts, inner = serialization.serialize_with_refs_parts(value)
        total = serialization.parts_len(parts)
        # incref strictly before mark_ready: a READY object with refcount 0
        # is freed on arrival.
        self._call_soon(self.node.incref, oid)
        if inner:
            self._call_soon(self.node._attach_inner_refs, oid, inner)
        if total > self.cfg.max_inline_object_size:
            # Vectored write: big numpy buffers go caller-memory ->
            # segment in ONE copy (no flattened intermediate blob).
            self.shm.put_parts(oid, parts)
            self._call_soon(self.node.mark_ready_shm, oid, total)
        else:
            self._call_soon(self.node.mark_ready_bytes, oid,
                            b"".join(parts))
        return ObjectRef(oid, _register=False, owner_addr=self.node_addr)

    def _state_of(self, oid: ObjectID):
        return self.node.objects.get(oid)

    def cluster_state(self, include_events: bool = False,
                      light: bool = False, tables=None,
                      timeout: float = 10.0) -> dict:
        """Cluster-wide introspection: every ALIVE node's state_snapshot
        plus the head's node/PG tables (reference: the state API's GCS +
        per-node aggregation, python/ray/util/state/api.py). ``tables``
        restricts which per-node tables ship (e.g. ["actors"])."""

        async def query_node(n):
            if tuple(n["address"]) == tuple(self.node.peer_address):
                return self.node.state_snapshot(include_events, light,
                                                tables)
            try:
                # Per-node budget so one hung node costs O(its timeout),
                # not the whole query: the others still answer.
                async def ask():
                    conn = await self.node._addr_conn(tuple(n["address"]))
                    return await conn.call(
                        "state", {"events": include_events, "light": light,
                                  "tables": tables})
                return await asyncio.wait_for(ask(),
                                              max(1.0, timeout - 1.0))
            except Exception:  # lint: allow-swallow(node died mid-query; head will notice)
                return None  # node died/hung mid-query; the head will notice

        async def gather():
            nodes = await self.head_client().list_nodes()
            pgs = await self.head_client().list_pgs()
            snaps = await asyncio.gather(
                *(query_node(n) for n in nodes if n["state"] == "ALIVE"))
            return {"nodes": nodes, "placement_groups": pgs,
                    "snapshots": [s for s in snaps if s is not None]}

        return self._run(gather(), timeout)

    def timeseries(self, metric: str | None = None,
                   node_id: str | None = None, resolution: float = 1.0,
                   timeout: float = 10.0) -> dict:
        """Head-retained telemetry time-series (the cluster telemetry
        plane): {"resolution": s, "series": {metric: {node_hex:
        [[ts, value, high_water], ...]}}}. ``resolution`` snaps down to
        the nearest retention tier (1x/10x/60x the sample interval)."""
        return self._run(
            self.node.head.timeseries(metric, node_id, resolution), timeout)

    def get_trace(self, trace_id: str, timeout: float = 10.0):
        """One retained (or still-pending) request trace: its spans,
        start-sorted; None if the tail sampler dropped it."""
        return self._run(self.node.head.get_trace(trace_id), timeout)

    def list_traces(self, deployment: str | None = None,
                    min_ms: float = 0.0, errors_only: bool = False,
                    limit: int = 50, timeout: float = 10.0):
        """Retained request-trace summaries, newest first (the head's
        tail-sampled ring: errors + slowest p% + probabilistic rest)."""
        return self._run(
            self.node.head.list_traces(deployment, min_ms, errors_only,
                                       limit), timeout)

    def declare_slo(self, spec: dict, timeout: float = 10.0) -> dict:
        """Register (or replace) a head-evaluated SLO alert rule;
        returns its ``list_alerts`` row."""
        return self._run(self.node.head.declare_slo(spec), timeout)

    def list_alerts(self, timeout: float = 10.0):
        """Every declared alert rule with its live burn rates + state."""
        return self._run(self.node.head.list_alerts(), timeout)

    def list_incidents(self, state: str | None = None, limit: int = 50,
                       timeout: float = 10.0):
        """Incident rows, newest first (summaries — evidence via
        ``get_incident``)."""
        return self._run(self.node.head.list_incidents(state, limit),
                         timeout)

    def get_incident(self, incident_id: str, timeout: float = 10.0):
        """One incident with its full evidence bundle + event log."""
        return self._run(self.node.head.get_incident(incident_id), timeout)

    def head_client(self):
        return self.node.head

    def get(self, refs, timeout: float | None = None):
        single = isinstance(refs, ObjectRef)
        if single:
            refs = [refs]

        my_addr = self.node_addr

        def is_foreign(r):
            return r.owner_addr is not None and tuple(r.owner_addr) != my_addr

        async def wait_all():
            deadline = None if timeout is None else self.loop.time() + timeout
            # Foreign-owned refs: pull copies from their owners first.
            for r in refs:
                if is_foreign(r):
                    self.node.spawn(
                        self.node.ensure_object(r.id, r.owner_addr, timeout))
            for r in refs:
                # Unknown id => nothing will ever produce it (e.g. a ref from
                # a previous session) — fail fast instead of blocking forever.
                if r.id not in self.node.objects and not is_foreign(r):
                    from .exceptions import ObjectLostError

                    raise ObjectLostError(
                        f"{r} is unknown to this runtime (was it created in a "
                        f"previous session?)"
                    )
                remaining = (None if deadline is None
                             else max(0.0, deadline - self.loop.time()))
                st = await self.node.wait_object(r.id, remaining)
                if st.status == PENDING:
                    raise GetTimeoutError(f"get() timed out on {r}")

        self._run(wait_all())
        out = [self._read_value(r, timeout) for r in refs]
        return out[0] if single else out

    def _read_value(self, r: ObjectRef, timeout: float | None = None):
        """Read a terminal object's value; if its bytes were lost from the
        store, reconstruct from lineage and re-read (VERDICT r1 item 5;
        reference: object_recovery_manager.h:41)."""
        import concurrent.futures as _cf
        import time as _time

        from .exceptions import ObjectLostError

        deadline = None if timeout is None else _time.monotonic() + timeout
        for _ in range(1 + self.cfg.max_object_reconstructions):
            st = self.node.objects[r.id]
            if st.status == ERROR:
                raise_stored(st.error)
            if st.location != "shm":
                kind, val = st.value
                return (serialization.deserialize(val) if kind == "bytes"
                        else val)
            mv = self.shm.get(r.id)
            if mv is not None:
                return serialization.deserialize(mv)
            remaining = (None if deadline is None
                         else deadline - _time.monotonic())
            if remaining is not None and remaining <= 0:
                raise GetTimeoutError(
                    f"get() timed out reconstructing lost object {r}")
            try:
                recovered = self._run(
                    self.node.recover_object(r.id, remaining),
                    None if remaining is None else remaining + 5.0)
            except _cf.TimeoutError:
                raise GetTimeoutError(
                    f"get() timed out reconstructing lost object {r}") from None
            if not recovered:
                raise ObjectLostError(
                    f"{r} was lost from the object store and could not be "
                    f"reconstructed from lineage")
        raise ObjectLostError(
            f"{r} kept disappearing across "
            f"{self.cfg.max_object_reconstructions} reconstructions")

    def wait(self, refs: Sequence[ObjectRef], num_returns=1, timeout=None):
        my_addr = self.node_addr

        async def do():
            for r in refs:
                if r.owner_addr is not None and tuple(r.owner_addr) != my_addr:
                    self.node.spawn(
                        self.node.ensure_object(r.id, r.owner_addr))
            oids = [r.id for r in refs]
            deadline = None if timeout is None else self.loop.time() + timeout
            # ONE waiter per still-pending object for the whole call —
            # re-registering every wakeup is O(n·wakeups) churn that
            # fan-in workloads (1k-ref waits, BASELINE.md) punish.
            waiters: dict = {}
            try:
                while True:
                    ready = [o for o in oids
                             if self.node.objects.get(o)
                             and self.node.objects[o].status != PENDING]
                    if len(ready) >= num_returns:
                        return ready
                    remaining = (None if deadline is None
                                 else max(0.0, deadline - self.loop.time()))
                    if remaining == 0.0:
                        return ready
                    for o in oids:
                        if o in waiters:
                            continue
                        st = self.node._obj(o)
                        if st.status == PENDING:
                            f = self.loop.create_future()
                            st.waiters.append(f)
                            waiters[o] = f
                    futs = [f for f in waiters.values() if not f.done()]
                    if not futs:
                        return ready
                    await asyncio.wait(futs, timeout=remaining,
                                       return_when=asyncio.FIRST_COMPLETED)
            finally:
                for o, f in waiters.items():
                    if not f.done():
                        f.cancel()
                        st = self.node.objects.get(o)
                        if st and st.waiters:
                            st.waiters[:] = [x for x in st.waiters
                                             if x is not f]

        ready_ids = set(o.binary() for o in self._run(do()))
        ready = [r for r in refs if r.id.binary() in ready_ids]
        not_ready = [r for r in refs if r.id.binary() not in ready_ids]
        if len(ready) > num_returns:
            not_ready = ready[num_returns:] + not_ready
            ready = ready[:num_returns]
        return ready, not_ready

    def object_future(self, oid: ObjectID, owner_addr=None) -> Future:
        fut: Future = Future()

        async def do():
            if owner_addr is not None and tuple(owner_addr) != self.node_addr:
                self.node.spawn(self.node.ensure_object(oid, owner_addr))
            st = await self.node.wait_object(oid)
            return st

        def done(afut):
            try:
                st = afut.result()
                if st.status == ERROR:
                    fut.set_exception(st.error)
                    return
                if st.location == "shm":
                    mv = self.shm.get(oid)
                    fut.set_result(serialization.deserialize(mv))
                else:
                    kind, val = st.value
                    fut.set_result(serialization.deserialize(val)
                                   if kind == "bytes" else val)
            except BaseException as e:  # noqa: BLE001
                fut.set_exception(e)

        asyncio.run_coroutine_threadsafe(do(), self.loop).add_done_callback(done)
        return fut

    def cancel(self, ref: ObjectRef, force=False):
        def do():
            # Table lookup ON the loop: submission is fire-and-forget,
            # so a cancel issued right after .remote() must queue behind
            # the submit (same FIFO) or it reads an absent entry and
            # silently no-ops.
            st = self.node.objects.get(ref.id)
            if st is None or st.creating_spec is None:
                return
            self.node.cancel_task(st.creating_spec.task_id, force=force)

        self._call_soon(do)

    def kill_actor(self, actor_id: ActorID, no_restart=True):
        asyncio.run_coroutine_threadsafe(
            self.node.kill_actor_anywhere(actor_id, no_restart), self.loop)

    def get_actor_by_name(self, name: str):
        return self._run(self.node.head.get_actor_by_name(name))

    def kv_op(self, op, key, val=None):
        return self._run(self.node.head.kv_op(op, key, val))

    def _node_fanout(self, method: str, payload, local_fn,
                     timeout: float) -> dict:
        """Merged dict from one peer RPC per ALIVE node (with a per-node
        budget) + the local node's in-process answer — the shared shape
        behind cluster_stacks/cluster_logs (reference: the state API's
        per-agent aggregation)."""

        async def query(n):
            if tuple(n["address"]) == tuple(self.node.peer_address):
                out = local_fn()
                return (await out) if asyncio.iscoroutine(out) else out
            try:
                conn = await self.node._addr_conn(tuple(n["address"]))
                return await asyncio.wait_for(
                    conn.call(method, payload), timeout)
            except Exception as e:  # noqa: BLE001 - best effort
                return {f"node:{n['node_id'].hex()[:12]}":
                        f"<unreachable: {e}>"}

        async def gather():
            nodes = await self.head_client().list_nodes()
            outs = await asyncio.gather(
                *(query(n) for n in nodes if n["state"] == "ALIVE"))
            merged = {}
            for o in outs:
                merged.update(o)
            return merged

        return self._run(gather(), timeout=timeout + 5)

    def cluster_logs(self, tail_bytes: int = 16_384,
                     timeout: float = 15.0) -> dict:
        """Recent captured worker logs cluster-wide (reference: `ray
        logs`), keyed worker:<node>:<pid>."""
        return self._node_fanout(
            "logs", {"tail_bytes": tail_bytes},
            lambda: self.node.collect_logs(tail_bytes), timeout)

    def cluster_stacks(self, timeout: float = 15.0) -> dict:
        """Thread stacks of every node + worker process cluster-wide
        (reference: `ray stack`)."""
        return self._node_fanout(
            "stacks", None, self.node.collect_stacks, timeout)

    def cluster_profile(self, duration_s: float = 5.0, hz: float = 99.0,
                        timeout: float = 60.0) -> dict:
        """Sampled CPU profiles (folded stacks) of every node + worker
        cluster-wide (reference: dashboard py-spy flamegraphs,
        profile_manager.py:79). Render with
        profiler.render_flamegraph_svg / `rtpu stack --flame`."""
        payload = {"duration_s": duration_s, "hz": hz}
        return self._node_fanout(
            "profile", payload,
            lambda: self.node.collect_profile(duration_s, hz),
            max(timeout, duration_s + 15))

    def cluster_device_profile(self, duration_s: float = 2.0,
                               hz: float = 99.0,
                               timeout: float = 60.0) -> dict:
        """Gang-coordinated device-step capture cluster-wide: every node
        + worker records one window of accounted device steps (perfmodel
        ring), a host-CPU sample timeline, and a best-effort
        jax.profiler trace. Merge with profiler.build_merged_trace /
        `rtpu profile --device`."""
        payload = {"duration_s": duration_s, "hz": hz}
        return self._node_fanout(
            "device_profile", payload,
            lambda: self.node.collect_device_profile(duration_s, hz),
            max(timeout, duration_s + 15))

    def cluster_flight_records(self, tail: int = 256,
                               include_stacks: bool = True,
                               timeout: float = 15.0) -> dict:
        """Gang flight-recorder ring snapshots (eager-collective entries
        + host stacks) of every node + worker cluster-wide, keyed
        node:<id12> / worker:<node8>:<pid> — the collection leg of the
        desync watchdog. Align with parallel/flightrec.diagnose; render
        with `rtpu gang doctor` / `rtpu collectives`."""
        payload = {"tail": tail, "stacks": include_stacks}
        return self._node_fanout(
            "flight_records", payload,
            lambda: self.node.collect_flight_records(tail, include_stacks),
            timeout)

    def clock_offsets(self, timeout: float = 5.0) -> dict:
        """Per-node wall-clock offset estimates relative to THIS
        process, keyed by node-id prefix (12 hex chars, matching the
        node: keys of the capture dicts). NTP-style midpoint: offset =
        (t_send + t_recv)/2 - peer_time, so a peer timestamp PLUS its
        offset lands on our clock. The local node's offset is 0 by
        construction."""
        import time as _time

        async def probe(n):
            nid = n["node_id"].hex()[:12]
            if tuple(n["address"]) == tuple(self.node.peer_address):
                return nid, 0.0
            try:
                conn = await self.node._addr_conn(tuple(n["address"]))
                t0 = _time.time()
                out = await asyncio.wait_for(
                    conn.call("clock_probe", None), timeout)
                t1 = _time.time()
                return nid, (t0 + t1) / 2 - float(out["t_wall"])
            except Exception:  # noqa: BLE001 - best effort
                return nid, 0.0

        async def gather():
            nodes = await self.head_client().list_nodes()
            pairs = await asyncio.gather(
                *(probe(n) for n in nodes if n["state"] == "ALIVE"))
            return dict(pairs)

        return self._run(gather(), timeout=timeout + 5)

    def cluster_heap(self, top_n: int = 25, timeout: float = 30.0) -> dict:
        """tracemalloc heap snapshots cluster-wide (reference: memray
        heap profiles from the dashboard agent)."""
        return self._node_fanout(
            "heap", {"top_n": top_n},
            lambda: self.node.collect_heap(top_n), timeout)

    def resolve_runtime_env(self, env: dict | None,
                            device_lane: bool = False):
        """Merge the job default with a per-task env and upload any local
        packages (ray_tpu.runtime_env.resolve_for_upload), cached by env
        content. Returns the resolved env for the TaskSpec, or None."""
        from ray_tpu import runtime_env as _re

        if device_lane:
            # The device lane runs in the node-owner process, which cannot
            # wear a per-task environment. An explicit per-task env is a
            # user error; the job-level default is simply skipped (it
            # already applies to the driver process the lane lives in).
            if _re.validate(env):
                raise ValueError(
                    "runtime_env is not supported on device-lane "
                    "tasks/actors: the device lane runs in the node-owner "
                    "process. Drop the runtime_env or target the CPU lane.")
            return None
        merged = _re.merge(self.default_runtime_env, env)
        if not merged:
            return None
        # No spec-keyed cache: local paths are re-zipped every submit so
        # edits ship immediately; the deterministic zip's content hash
        # dedupes the KV upload, which keeps this cheap.
        return _re.resolve_for_upload(merged, self.kv_op)

    # -- placement groups --------------------------------------------------
    def create_placement_group(self, bundles, strategy):
        from .ids import PlacementGroupID

        pg_id = PlacementGroupID.from_random()
        # Feasibility gate (matches the reference's fail-fast on bundles no
        # node shape could ever satisfy): every bundle must fit on SOME
        # node's total resources.
        nodes = self._run(self.node.head.list_nodes())
        for i, b in enumerate(bundles):
            if not any(all(n["resources"].get(k, 0) >= v
                           for k, v in b.items())
                       for n in nodes if n["state"] == "ALIVE"):
                raise ValueError(
                    f"placement group infeasible: bundle {i} ({b}) fits on "
                    f"no node in the cluster")
        self._run(self.node.head.create_pg(pg_id, bundles, strategy))
        return pg_id

    def remove_placement_group(self, pg_id):
        asyncio.run_coroutine_threadsafe(
            self.node.head.remove_pg(pg_id), self.loop)

    def placement_group_state(self, pg_id) -> dict | None:
        return self._run(self.node.head.pg_state(pg_id))

    def wait_placement_group_ready(self, pg_id, timeout=None) -> bool:
        import time as _time

        deadline = None if timeout is None else _time.monotonic() + timeout
        while True:
            st = self.placement_group_state(pg_id)
            if st is not None and st["state"] == "CREATED":
                return True
            if deadline is not None and _time.monotonic() >= deadline:
                return False
            _time.sleep(0.05)

    # -- introspection -----------------------------------------------------
    def cluster_resources(self) -> dict:
        out: dict = {}
        for n in self._run(self.node.head.list_nodes()):
            if n["state"] != "ALIVE":
                continue
            for k, v in n["resources"].items():
                out[k] = out.get(k, 0) + v
        return out

    def available_resources(self) -> dict:
        out: dict = {}
        for n in self._run(self.node.head.list_nodes()):
            if n["state"] != "ALIVE":
                continue
            avail = (self.node.available if n["node_id"] == self.node_id.binary()
                     else n["available"])
            for k, v in avail.items():
                out[k] = out.get(k, 0) + v
        return out

    def list_nodes(self) -> list:
        return self._run(self.node.head.list_nodes())

    def list_placement_groups(self) -> list:
        return self._run(self.node.head.list_pgs())

    def shutdown(self):
        if getattr(self, "_shut", False):
            return
        self._shut = True
        try:
            self._run(self.node.shutdown(), timeout=10)
        except Exception:  # lint: allow-swallow(best-effort teardown)
            pass
        if self.head is not None:
            try:
                self._run(self.head.shutdown(), timeout=5)
            except Exception:  # lint: allow-swallow(best-effort teardown)
                pass
        self.loop.call_soon_threadsafe(self.loop.stop)
        self._loop_thread.join(timeout=5)
        try:
            os.unlink(self.sock_path)
        except OSError:
            pass
        self.shm.destroy()
        if context_mod.get_context() is self:
            context_mod.set_context(None)
