"""Node service: scheduler, worker pool, object directory, actor manager.

This is the per-node brain, the moral equivalent of the reference's raylet
(/root/reference/src/ray/raylet/node_manager.h:125 — dispatch loop,
worker_pool.h:156 — worker leasing/forking) fused with the owner-side task
manager (/root/reference/src/ray/core_worker/task_manager.h:195 — retries,
lineage) and, in round 1, the head-node control plane
(/root/reference/src/ray/gcs/gcs_server/gcs_server.h:78 — actor FSM, KV,
named actors). All state is owned by a single asyncio event loop.

TPU-native design choice: compute that touches the TPU runs on the
**device executor** — thread pools *inside the process that owns the chips*
(JAX requires a single process per host to own the local devices; forked
subprocesses cannot share them). CPU-only tasks go to forked worker
subprocesses, like the reference. So a node has two lanes:

    device lane:  in-process ThreadPoolExecutor(s); zero-serialization
                  results (python objects stay in the memory store)
    cpu lane:     subprocess workers leased per task; results ride the
                  shared-memory store (large) or inline bytes (small)
"""

from __future__ import annotations

import asyncio
import collections
import json
import os
import random as _random
import socket
import subprocess
import sys
import threading
import time
import traceback

import msgpack
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Any, Optional

import cloudpickle

from . import backend_probe, serialization
from .config import get_config
from .exceptions import (
    ActorDiedError,
    ObjectFreedError,
    ObjectLostError,
    OutOfMemoryError,
    RuntimeEnvSetupError,
    TaskCancelledError,
    TaskError,
    WorkerCrashedError,
)
from .ids import ActorID, NodeID, ObjectID, PlacementGroupID, TaskID, WorkerID
from .object_store import SharedMemoryStore
from .rpc import (ConnectionLost, DuplexServer, RpcTimeout, ServerConn,
                  async_connect, call_stats as rpc_call_stats)
from .task_spec import REF, VAL, SchedulingStrategy, TaskSpec

PENDING, READY, ERROR = "PENDING", "READY", "ERROR"


async def attach_node_to_head(node: "NodeService", head_addr: tuple,
                              resources: dict, *, is_driver: bool = False,
                              node_type: str = None, on_lost=None,
                              start: bool = True,
                              is_head_node: bool = False):
    """Shared node bring-up against a remote head: dial, wire head pushes,
    start the node, register, and install the re-register callback.
    Used by both the standalone node daemon (node_main.py) and attaching
    drivers (runtime._attach) so the registration handshake can't drift
    between them. ``on_lost`` (async) fires when the head connection
    drops for any reason other than our own shutdown. ``start=False``
    re-attaches an already-running node after a head restart (same
    handshake, node services untouched)."""
    from .head import RemoteHeadClient
    from .rpc import async_connect

    async def handle_head_push(conn, method, payload):
        await node.on_head_push(method, payload)
        return True

    async def on_disconnect(conn):
        if node._closing:
            return
        if on_lost is not None:
            await on_lost(conn)

    conn = await async_connect(head_addr, handle_head_push, on_disconnect)
    node.head = RemoteHeadClient(conn)
    if start:
        await node.start()

    async def register():
        reply = await conn.call("register_node", {
            "node_id": node.node_id.binary(),
            "address": node.peer_address,
            "resources": dict(resources),
            "is_driver": is_driver,
            "is_head": is_head_node,
            "node_type": node_type,
            "labels": node.labels,
            # Live state for head-restart reconciliation (reference:
            # raylet resync after NotifyGCSRestart).
            "sync": node.directory_sync(),
        })
        for row in (reply or {}).get("release_bundles", []):
            # The head no longer knows this PG (removed while we were
            # partitioned / before its restart): free the reservation.
            node.release_bundle(PlacementGroupID(row["pg_id"]),
                                row["bundle_index"])

        # Re-establish this node's pubsub channel registrations after a
        # head restart (subscriber-side re-sync: subscriber.h:329).
        node._pubsub_head_ok.clear()
        for channel in list(node.pubsub_local):
            try:
                await node.head.pubsub_sub(channel, node.node_id)
                node._pubsub_head_ok.add(channel)
            except Exception:  # noqa: BLE001 - next register retries
                pass

    node.register_cb = register
    await register()
    return conn


def _auto_node_labels(node_id: NodeID, resources: dict) -> dict:
    """Default label set every node advertises (reference: the default
    ray.io/* node labels), merged with RT_NODE_LABELS ("k=v,k2=v2")."""
    import socket

    labels = {
        "rt.io/node-id": node_id.hex(),
        "rt.io/hostname": socket.gethostname(),
        "rt.io/accelerator": ("tpu" if resources.get("TPU", 0) > 0
                              else "cpu"),
    }
    for part in os.environ.get("RT_NODE_LABELS", "").split(","):
        if "=" in part:
            k, v = part.split("=", 1)
            labels[k.strip()] = v.strip()
    return labels


def raise_stored(err):
    """Raise a table-stored exception WITHOUT mutating it. ``raise
    st.error`` attaches the caller's traceback to the stored instance,
    chaining node.objects -> error -> frame objects -> every local
    ObjectRef in those frames — which pins refs (their __del__ never
    runs) and leaks the very entries an errored/freed object should
    release. A shallow copy raises with a fresh traceback instead."""
    import copy

    try:
        clone = copy.copy(err)
        clone.__traceback__ = None
    except Exception:  # noqa: BLE001 - uncopyable custom error
        clone = err
    raise clone


@dataclass
class ObjectState:
    status: str = PENDING
    # location: "memory" (python object or bytes in-process) | "shm"
    location: str = "memory"
    value: Any = None  # ("obj", x) | ("bytes", b) | None
    error: Optional[TaskError] = None
    size: int = 0
    refcount: int = 0
    waiters: list = field(default_factory=list)  # asyncio.Future
    creating_spec: Optional[TaskSpec] = None  # lineage (reconstruction)
    # Owner-side location directory: peer address tuple -> node_id bytes for
    # every node known to hold a full copy (reference:
    # ownership_based_object_directory.h). Lazily allocated.
    holders: Optional[dict] = None
    # Borrower-side: the address we pulled this foreign copy from (the
    # owner) — freeing the copy deregisters it there.
    pulled_from: Optional[tuple] = None
    # Borrowing protocol (reference: reference_count.h:61):
    # owner side — borrower address -> node_id bytes, each holding one
    # deferred-free count until that node releases (or dies);
    # borrower side — the owner's address plus whether our aggregate
    # borrow is registered there.
    borrowers: Optional[dict] = None
    borrow_owner: Optional[tuple] = None
    borrow_registered: bool = False  # borrow_add issued
    borrow_confirmed: bool = False   # borrow_add acked by the owner
    # Refs serialized INSIDE this object's bytes ([(oid_bytes, owner)]):
    # pinned for the container's lifetime, released when it frees — a
    # container transitively keeps its contents alive (reference: the
    # reference counter's contained/inlined-ref tracking).
    inner_refs: Optional[list] = None


def format_worker_logs(node_hex: str, entries: list) -> str:
    """THE console format for streamed worker output — shared by the
    head console and every driver-side pubsub sink so the prefixes
    can't diverge (reference: the (pid=…, ip=…) prefixes the log
    monitor prints)."""
    return "".join(
        f"(pid={e['pid']}, node={node_hex[:8]}) {line}\n"
        for e in entries for line in e.get("lines", ()))


def _print_worker_logs(node_hex: str, entries: list):
    text = format_worker_logs(node_hex, entries)
    if text:
        sys.stderr.write(text)
        sys.stderr.flush()


@dataclass
class WorkerHandle:
    worker_id: WorkerID
    proc: subprocess.Popen
    conn: Optional[ServerConn] = None
    state: str = "STARTING"  # STARTING/IDLE/BUSY/DEAD
    inflight: dict = field(default_factory=dict)  # TaskID -> TaskSpec
    actor_id: Optional[ActorID] = None
    last_idle: float = field(default_factory=time.monotonic)
    registered: Optional[asyncio.Future] = None
    # Runtime-env identity this worker wears; leases only match tasks
    # with the same env (reference: worker_pool.h pools by env hash).
    env_id: str = ""
    # Captured stdout/stderr file + the tail offset already streamed.
    log_path: Optional[str] = None
    log_offset: int = 0
    # Refs this worker process holds (ref_hold/ref_drop): released in bulk
    # if the worker dies without dropping them.
    held_refs: collections.Counter = field(
        default_factory=collections.Counter)
    # Node id (bytes) of the driver that owns the task this worker is
    # (last) running: routes its log lines to that driver's console.
    owner_node: Optional[bytes] = None
    # CPU lease charge (cpu-lane fast path): the pool and amount debited
    # when this worker took its current lease. Pipelined specs piggyback
    # on the lease — the worker executes one task at a time on its
    # serial lane, so one charge covers the whole in-flight window; it
    # is credited back when inflight drains empty.
    charged_pool: Optional[dict] = None
    charged_cpu: float = 0.0


@dataclass
class ActorState:
    actor_id: ActorID
    creation_spec: TaskSpec
    state: str = "PENDING"  # PENDING/ALIVE/RESTARTING/DEAD
    is_device: bool = False
    worker: Optional[WorkerHandle] = None
    device_pool: Optional[ThreadPoolExecutor] = None
    instance: Any = None  # device actors: the live python object
    queue: collections.deque = field(default_factory=collections.deque)
    inflight: int = 0
    num_restarts: int = 0
    name: Optional[str] = None
    death_cause: Optional[str] = None
    ready_fut: Optional[asyncio.Future] = None
    # Resources held for the actor's lifetime (released on terminal DEAD,
    # kept across restarts) — reference: actors reserve their resources
    # while alive (src/ray/raylet/scheduling/cluster_resource_manager).
    charged: Optional[dict] = None


@dataclass
class RemoteActorEntry:
    """Owner-side record of an actor living on another node (the actor's
    ActorState lives on its home node; we route calls there and restart it
    elsewhere when the node dies — reference: GcsActorManager restart FSM)."""

    actor_id: ActorID
    node_id: NodeID
    address: tuple
    creation_spec: Optional[TaskSpec] = None  # None => looked up by name
    state: str = "ALIVE"  # ALIVE / RESTARTING / DEAD
    num_restarts: int = 0
    death_cause: Optional[str] = None
    queue: collections.deque = field(default_factory=collections.deque)
    pumping: bool = False
    ready: Optional[asyncio.Event] = None


@dataclass
class BundlePool:
    """Resources set aside on this node for one placement-group bundle."""

    total: dict
    available: dict


class NodeService:
    """Per-node scheduler + object directory + actor manager.

    Multi-node shape (round 2): every node registers with the head
    (head.py), heartbeats its availability, and exchanges work with peer
    nodes over TCP: an owner forwards a fully-resolved TaskSpec with
    ``remote_execute`` and the executor replies with result blobs
    (reference: the lease/PushTask pipeline of direct_task_transport.h,
    collapsed to one RPC because args are owner-resolved).
    """

    def __init__(self, session_id: str, sock_path: str, resources: dict,
                 shm_store: SharedMemoryStore, loop: asyncio.AbstractEventLoop,
                 node_id: NodeID | None = None, head=None,
                 is_head_node: bool = True, peer_port: int = 0):
        self.cfg = get_config()
        self.session_id = session_id
        self.sock_path = sock_path
        self.loop = loop
        self.shm = shm_store
        self.node_id = node_id or NodeID.from_random()
        self.head = head  # LocalHeadClient | RemoteHeadClient | None
        self.is_head_node = is_head_node
        # Other alive nodes per the last heartbeat ack: 0 ⇒ spillback
        # can never place work elsewhere, so the dispatcher pipelines
        # parked specs immediately.
        self._peer_nodes = 0
        self._spill_kick_pending = False
        self.total_resources = dict(resources)
        self.available = dict(resources)
        # Node labels for label-selector scheduling: auto labels + the
        # RT_NODE_LABELS env ("k=v,k2=v2" — cluster launchers/operators
        # tag slices) + per-process extras via set_labels(). Reference:
        # node labels in node_manager.cc / NodeLabelSchedulingStrategy.
        self.labels = _auto_node_labels(self.node_id, resources)
        # Worker stdout/stderr capture directory (reference: the session
        # log dir tailed by log_monitor.py).
        self.log_dir = os.path.join("/tmp", f"rtpu-{session_id}-logs")
        os.makedirs(self.log_dir, exist_ok=True)
        # Actor creations parked for lifetime-resource availability.
        self._pending_actor_creations: collections.deque = collections.deque()
        # kill() that raced ahead of the creation it targets.
        self._killed_before_create: set = set()

        self.objects: dict[ObjectID, ObjectState] = {}
        self.functions: dict[str, bytes] = {}  # local cache; source of truth: head
        self._fn_cache: dict[str, Any] = {}  # deserialized, device lane only

        self.workers: dict[WorkerID, WorkerHandle] = {}
        self.idle_workers: collections.deque[WorkerHandle] = collections.deque()
        # Runtime envs whose setup recently failed on this node:
        # env_id -> (error, monotonic time); entries expire (_bad_env_error).
        self._bad_envs: dict[str, tuple] = {}
        # User metrics: cumulative snapshots pushed by worker processes,
        # keyed by source worker id (in-process code is read directly);
        # dead workers' counters fold into the retired accumulator.
        self.user_metrics: dict[str, dict] = {}
        self._retired_metrics: dict[tuple, dict] = {}
        # Dead workers' final gauge snapshots, visible to the telemetry
        # sampler for exactly one beat (then discarded): a batch job
        # shorter than the sampler interval still surfaces its final
        # llm_tokens_per_s:<op> values instead of dying unsampled.
        self.dying_metrics: dict[str, dict] = {}
        # Trace spans pushed by workers (bounded; tracing is opt-in).
        self.trace_spans: collections.deque = collections.deque(maxlen=10_000)
        # Device-lane tasks currently executing (best-effort cancel).
        from .interrupt import TaskInterruptRegistry

        self._device_interrupts = TaskInterruptRegistry()
        self.pending_cpu: collections.deque[TaskSpec] = collections.deque()
        self.cancelled: set[TaskID] = set()
        self._dispatch_misses = 0  # consecutive no-worker outcomes

        self.actors: dict[ActorID, ActorState] = {}
        self.remote_actors: dict[ActorID, RemoteActorEntry] = {}

        # (pg_id, bundle_index) -> BundlePool reserved on this node.
        self.bundles: dict[tuple, BundlePool] = {}

        # General pubsub: channel -> {sub_id: sink}. Sinks are
        # ("q", queue.Queue) for in-process subscribers (driver threads),
        # ("fn", callable) for internal consumers (log rendering), or
        # ("worker", WorkerHandle) for worker-process subscribers
        # (delivered over the worker's duplex conn). Reference:
        # src/ray/pubsub/subscriber.h:329 — the node service is the
        # per-process subscriber that multiplexes local subscriptions
        # over ONE head registration per channel.
        self.pubsub_local: dict[str, dict] = {}
        self._pubsub_head_ok: set[str] = set()  # registered at the head

        # Peer plumbing: node_id -> ServerConn (lazily dialed).
        self.peer_conns: dict[NodeID, ServerConn] = {}
        self.dead_nodes: set[NodeID] = set()
        self._pending_remote: collections.deque = collections.deque()
        # Strong refs for fire-and-forget tasks: asyncio only weakly
        # references tasks, so an un-referenced pending task (an
        # in-flight _execute_remotely, a result ingest) can be GARBAGE
        # COLLECTED mid-await — observed as silently lost task replies
        # under the head-restart chaos test. spawn() parks every such
        # task until it completes.
        self._spawned_tasks: set = set()

        # Device lane: tasks with TPU resources (or strategy "device").
        self.device_pool = ThreadPoolExecutor(
            max_workers=int(os.environ.get("RT_DEVICE_POOL_THREADS", "4")),
            thread_name_prefix="device-exec",
        )
        self.server = DuplexServer(sock_path, self._handle_rpc, self._on_disconnect)
        # Peer-facing TCP server (object plane + remote execution).
        self.peer_server = DuplexServer(
            (self.cfg.head_host, peer_port), self._handle_peer_rpc, None)
        self._closing = False
        self._bg_tasks: list[asyncio.Task] = []
        # metrics / introspection counters
        self.counters = collections.Counter()
        # Object plane: in-flight inbound pulls (dedupe), outbound
        # transfer start-times per object (push-cap accounting), and
        # big-result pins awaiting the owner's pull (TTL-swept so a lost
        # reply can't leak the pinned shm segment forever).
        self._fetching: set = set()
        self._serving: dict = {}
        self._result_pins: dict = {}
        self.task_events: collections.deque = collections.deque(
            maxlen=self.cfg.task_events_buffer_size
        )
        # Latest-state row per task, bounded like the event buffer
        # (reference: GCS task events, gcs_task_manager.h:85 — state API
        # and timeline read these).
        self.task_table: "collections.OrderedDict[str, dict]" = \
            collections.OrderedDict()
        # ray_tpu_task_phase_seconds{phase=...} — created lazily on the
        # first completed task so importing the node doesn't register
        # metrics in processes that never run one. Tag tuples are
        # normalized once per phase name (hot path: every finished task
        # observes 4-5 phases).
        self._phase_hist = None
        self._phase_tag_cache: dict = {}
        self._node_hex = self.node_id.hex()
        # Telemetry plane: hop-gauge scratchpad (high-water marks between
        # sampler ticks, maintained by _gauge_queues at every dispatch-
        # queue / pipeline-window mutation site — lint-enforced), the
        # sampler itself, and the outbound sample buffer the heartbeat
        # drains to the head (bounded: a partition drops oldest).
        self.telemetry_gauges: dict = {"dispatch_queue_hw": 0,
                                       "pipeline_inflight_hw": 0}
        from .telemetry import TelemetrySampler

        self._telemetry_sampler = TelemetrySampler(self)
        self._telemetry_buf: collections.deque = collections.deque(
            maxlen=max(1, self.cfg.telemetry_buffer_max))
        # Request-trace relay: spans pushed by workers (1s flusher) wait
        # here for the next heartbeat to carry them to the head's
        # TraceStore. Bounded like telemetry: a partition drops oldest.
        self._trace_buf: collections.deque = collections.deque(
            maxlen=max(1, self.cfg.trace_buffer_max))

    async def start(self):
        await self.server.start()
        await self.peer_server.start()
        # Raw bulk-transfer lane: big-object pulls stream source-file ->
        # socket via sendfile (zero user-space copies) and land
        # socket -> destination segment mmap via recv_into (one kernel
        # copy) — the chunked RPC path costs ~5 user copies per byte
        # across both event loops (reference: plasma's memcpy-speed
        # object manager, object_manager.h:117).
        self._bulk_server = await asyncio.start_server(
            self._handle_bulk_conn, self.cfg.head_host, 0)
        self.bulk_port = self._bulk_server.sockets[0].getsockname()[1]
        self._bg_tasks.append(
            self.spawn(self._log_tail_loop()))
        self._bg_tasks.append(
            self.spawn(self._result_pin_sweep_loop()))
        if self.cfg.memory_monitor_interval_s > 0:
            self._bg_tasks.append(
                self.spawn(self._memory_monitor_loop()))
        if self.cfg.telemetry_sample_interval_s > 0:
            self._bg_tasks.append(self.spawn(self._telemetry_loop()))
        if self.head is not None:
            self._bg_tasks.append(self.spawn(self._heartbeat_loop()))
            self._bg_tasks.append(
                self.spawn(self._pending_remote_loop()))

    @property
    def peer_address(self) -> tuple:
        return self.peer_server.address

    # ------------------------------------------------------------------
    # Introspection: task events + state snapshot (reference: GCS task
    # events / state API, python/ray/util/state/api.py,
    # gcs_task_manager.h:85)
    # ------------------------------------------------------------------
    def _event(self, spec, state: str, worker: str | None = None,
               phases: dict | None = None):
        """Record one task state-transition event and upsert the task's
        latest-state row. ``phases`` carries per-phase durations in
        seconds (queue/schedule at RUNNING, the worker-reported
        arg_fetch/execute/output_serialize merged in at FINISHED)."""
        tid = spec.task_id.hex()
        ev = {"task_id": tid, "name": spec.name, "state": state,
              "ts": time.time(), "node_id": self._node_hex}
        if worker is not None:
            ev["worker"] = worker
        if spec.actor_id is not None:
            ev["actor_id"] = spec.actor_id.hex()
        if phases:
            ev["phases"] = dict(phases)
        self.task_events.append(ev)
        row = self.task_table.get(tid)
        if row is None:
            row = {"task_id": tid, "name": spec.name,
                   "node_id": ev["node_id"],
                   "actor_id": ev.get("actor_id"),
                   "submitted_ts": ev["ts"]}
            if spec.created_ts:
                row["created_ts"] = spec.created_ts
            self.task_table[tid] = row
            # Evict the oldest TERMINAL row first — a long-running task's
            # live row must not be dropped (and later resurrected with a
            # bogus submitted_ts) just because newer tasks streamed past.
            scanned = 0
            while (len(self.task_table) > self.cfg.task_events_buffer_size
                   and scanned < 16):
                old_tid, old = next(iter(self.task_table.items()))
                if old.get("state") in ("FINISHED", "FAILED") or scanned == 15:
                    self.task_table.pop(old_tid)
                else:
                    self.task_table.move_to_end(old_tid)
                scanned += 1
        else:
            self.task_table.move_to_end(tid)
        row["state"] = state
        row["ts"] = ev["ts"]
        if worker is not None:
            row["worker"] = worker
        if state == "RUNNING":
            row["start_ts"] = ev["ts"]
            # A retried attempt starts its phase ledger over — stale
            # worker-side durations from the failed attempt would
            # double-count in the per-phase summary.
            row["phases"] = dict(phases) if phases else {}
        elif phases:
            row.setdefault("phases", {}).update(phases)
        if state in ("FINISHED", "FAILED"):
            row["end_ts"] = ev["ts"]
        else:
            # Re-execution (retry/reconstruction): a stale end_ts older
            # than the new start_ts would make an in-flight task look done.
            row.pop("end_ts", None)

    # Sub-millisecond buckets on top of the defaults: scheduling phases
    # sit at ~100µs on the cpu lane, which the 1ms default floor would
    # flatten into one bucket.
    _PHASE_BOUNDARIES = [0.0001, 0.0005, 0.001, 0.005, 0.01, 0.05,
                         0.1, 0.5, 1.0, 5.0, 10.0, 60.0]

    def _dispatch_phases(self, spec) -> dict:
        """queue/schedule durations for a spec at the moment it is
        handed a worker (or the device pool). queue = pending-queue
        wait (deps + capacity); schedule = routing decision from submit
        to enqueue, plus any head placement round-trip the owner
        measured (_sched_rtt)."""
        now = time.monotonic()
        pend = getattr(spec, "_pending_since", None)
        sub = getattr(spec, "_submit_mono", None)
        ph: dict = {}
        if pend is not None:
            ph["queue"] = max(0.0, now - pend)
            if sub is not None:
                ph["schedule"] = max(0.0, pend - sub)
        elif sub is not None:
            ph["queue"] = max(0.0, now - sub)
        rtt = getattr(spec, "_sched_rtt", None)
        if rtt is not None:
            ph["schedule"] = ph.get("schedule", 0.0) + rtt
        spec._phases = ph
        return ph

    def _observe_phases(self, phases: dict):
        """Feed completed-task phase durations into the
        ray_tpu_task_phase_seconds histogram (this process's registry —
        _metrics_rows exports it, so Prometheus/`rtpu metrics` gets
        p50/p99 per phase with no extra RPC)."""
        if not phases:
            return
        if self._phase_hist is None:
            from ray_tpu.util.metrics import Histogram

            self._phase_hist = Histogram(
                "ray_tpu_task_phase_seconds",
                "Per-task phase latency: queue, schedule, arg_fetch, "
                "execute, output_serialize",
                boundaries=self._PHASE_BOUNDARIES,
                tag_keys=("phase",))
        cache = self._phase_tag_cache
        items = []
        for phase, dur in phases.items():
            try:
                tags = cache.get(phase)
                if tags is None:
                    tags = self._phase_hist.normalized_tags(
                        {"phase": phase})
                    cache[phase] = tags
                items.append((tags, max(0.0, float(dur))))
            except Exception:  # lint: allow-swallow(malformed phase tag must not fail the task)
                pass  # a malformed phase must not fail the task
        if items:
            self._phase_hist.observe_normalized(items)

    def state_snapshot(self, include_events: bool = False,
                       light: bool = False, tables=None) -> dict:
        """One node's introspection tables, plain-dict shaped for the
        state API and the CLI (everything picklable, no live objects).
        ``light`` ships only counters/metrics — no per-task/object rows —
        for metrics polls that would otherwise drag whole tables over
        the wire; ``tables`` (e.g. ["actors"]) ships just the tables a
        list_* query actually reads."""
        snap = {
            "node_id": self.node_id.hex(),
            "is_head_node": self.head is not None and self.is_head_node,
            "address": self.peer_address,
            "resources": dict(self.total_resources),
            "available": dict(self.available),
            "counters": dict(self.counters),
            "store": self._store_stats(),
            "num_workers": len(self.workers),
            "num_actors": len(self.actors),
            "metrics": self._metrics_rows(),
            # Per-method RPC latency/error/timeout counters (reference:
            # client_call.h per-call metrics surfaced via stats).
            "rpc": rpc_call_stats(),
        }
        if light:
            return snap
        want = (None if tables is None
                else {t for t in tables})
        full = {
            # Phase dicts are copied too: the row's ledger keeps mutating
            # on the loop thread while an in-process reader (driver on
            # the same host) iterates the snapshot.
            "tasks": lambda: [
                ({**r, "phases": dict(r["phases"])} if "phases" in r
                 else dict(r))
                for r in self.task_table.values()],
            "task_events": lambda: list(self.task_events),
            "actors": lambda: [
                {"actor_id": a.actor_id.hex(),
                 "name": getattr(a.creation_spec, "actor_name", None),
                 "class_name": a.creation_spec.name.removesuffix(".__init__"),
                 "state": a.state,
                 "is_device": a.is_device,
                 "num_restarts": a.num_restarts,
                 "pid": (a.worker.proc.pid
                         if a.worker is not None and a.worker.proc else None),
                 "node_id": self.node_id.hex()}
                for a in self.actors.values()],
            "objects": lambda: [
                {"object_id": o.hex(), "status": st.status,
                 "location": st.location, "size": st.size,
                 "refcount": st.refcount,
                 "owner": (st.creating_spec.name if st.creating_spec
                           is not None else "driver/put"),
                 "node_id": self.node_id.hex()}
                for o, st in self.objects.items()],
            "workers": lambda: [
                {"worker_id": w.worker_id.hex(), "pid": w.proc.pid,
                 "state": w.state,
                 "actor_id": w.actor_id.hex() if w.actor_id else None,
                 "node_id": self.node_id.hex()}
                for w in self.workers.values()],
            "spans": lambda: list(self.trace_spans),
        }
        for key, build in full.items():
            if want is None or key in want:
                snap[key] = build()
        if include_events:
            snap["events"] = list(self.task_events)
        return snap

    def _retire_worker_metrics(self, source: str):
        """Fold a dead worker's last counter/histogram snapshot into the
        node-level retired accumulator (so totals don't regress) and drop
        its gauges; the per-worker entry is pruned so user_metrics and
        the export payload stay bounded under worker churn."""
        snap = self.user_metrics.pop(source, None)
        if snap is None:
            return
        # Final gauge values stay readable for one sampler beat (the
        # sampler drains dying_metrics as it reads it); bounded so a
        # churn storm with telemetry disabled cannot grow it.
        if len(self.dying_metrics) >= 64:
            self.dying_metrics.pop(next(iter(self.dying_metrics)))
        self.dying_metrics[source] = snap
        acc = self._retired_metrics
        for r in snap.get("rows", []):
            kind = r.get("type")
            if kind == "gauge":
                continue
            key = (r["name"], tuple(sorted(r.get("tags", {}).items())))
            cur = acc.get(key)
            if cur is None:
                acc[key] = dict(r)
            elif kind == "counter":
                cur["value"] += r["value"]
            elif kind == "histogram" \
                    and cur.get("boundaries") == r.get("boundaries"):
                cur["bucket_counts"] = [
                    a + b for a, b in zip(cur["bucket_counts"],
                                          r["bucket_counts"])]
                cur["sum"] += r["sum"]
                cur["count"] += r["count"]

    def _metrics_rows(self) -> list:
        """User metrics visible on this node: the in-process registry
        (driver / device lane) plus worker pushes, stamped with source +
        node for cross-node aggregation (ray_tpu.util.prometheus_text)."""
        rows = []
        try:
            from ray_tpu.util.metrics import _registry

            local = _registry.snapshot()
            for r in local["rows"]:
                r = dict(r)
                r["source"] = f"node:{self.node_id.hex()[:8]}"
                r["node_id"] = self.node_id.hex()
                r["ts"] = local["ts"]
                rows.append(r)
        except Exception:  # lint: allow-swallow(local metrics snapshot is advisory)
            pass
        for source, snap in self.user_metrics.items():
            for r in snap.get("rows", []):
                r = dict(r)
                r["source"] = source
                r["node_id"] = self.node_id.hex()
                r["ts"] = snap.get("ts", 0.0)
                rows.append(r)
        for r in self._retired_metrics.values():
            r = dict(r)
            r["source"] = f"retired:{self.node_id.hex()[:8]}"
            r["node_id"] = self.node_id.hex()
            r["ts"] = 0.0
            rows.append(r)
        return rows

    def _store_stats(self) -> dict:
        used = sum(st.size for st in self.objects.values()
                   if st.status == READY)
        stats = {"num_objects": len(self.objects), "used_bytes": used}
        cap = getattr(self.shm, "capacity_bytes", None)
        if cap is not None:
            stats["capacity_bytes"] = cap
        native = getattr(self.shm, "stats", None)
        if callable(native):
            try:
                stats.update(native())
            except Exception:  # lint: allow-swallow(native shm stats are optional)
                pass
        return stats

    # ------------------------------------------------------------------
    # Cluster plumbing: heartbeats, peers, head pushes
    # ------------------------------------------------------------------
    async def _heartbeat_loop(self):
        while not self._closing:
            try:
                # Telemetry piggyback: buffered samples ride the beat
                # (drained optimistically; restored in order on failure
                # so a head blip loses nothing — the deque cap still
                # bounds a long partition).
                telemetry = None
                if self._telemetry_buf:
                    telemetry = list(self._telemetry_buf)
                    self._telemetry_buf.clear()
                # Request-trace piggyback: worker-pushed spans plus any
                # recorded in THIS process (driver-side proxy roots in
                # local mode share our interpreter) ride the same beat.
                from ray_tpu.util import tracing

                local_spans = tracing.drain_request_spans()
                if local_spans:
                    self._trace_buf.extend(local_spans)
                trace = None
                if self._trace_buf:
                    trace = list(self._trace_buf)
                    self._trace_buf.clear()
                try:
                    ok = await self.head.heartbeat(self.node_id,
                                                   dict(self.available),
                                                   self._demand_shapes(),
                                                   telemetry=telemetry,
                                                   trace=trace)
                except BaseException:
                    if telemetry:
                        self._telemetry_buf.extendleft(reversed(telemetry))
                    if trace:
                        self._trace_buf.extendleft(reversed(trace))
                    raise
                if ok is False:
                    # Head lost track of us (restart/expiry): re-register.
                    await self._register_with_head()
                elif isinstance(ok, int) and not isinstance(ok, bool):
                    # The ack carries the count of other alive nodes —
                    # the dispatcher's "could spillback ever help" bit.
                    self._peer_nodes = ok
            except (ConnectionLost, RpcTimeout, OSError):
                pass
            await asyncio.sleep(self.cfg.heartbeat_interval_s)

    async def _telemetry_loop(self):
        """Fixed-interval sampler: counter deltas -> rates, hop gauges
        snapshotted, sample buffered for the next heartbeat to carry to
        the head (see _private/telemetry.py)."""
        while not self._closing:
            await asyncio.sleep(self.cfg.telemetry_sample_interval_s)
            try:
                self._telemetry_buf.append(self._telemetry_sampler.sample())
            except Exception:  # noqa: BLE001 - telemetry must never kill
                pass           # the node; next tick retries

    def _gauge_queues(self):
        """Refresh dispatch-queue / pipeline-window high-water marks.

        Called from every site that mutates pending_cpu or a worker's
        inflight window (AST-lint enforced in test_concurrency_net.py):
        the sampler reads instantaneous depths itself, but spikes
        between 1s ticks only survive through these marks. O(workers);
        workers is O(num_cpus)."""
        g = self.telemetry_gauges
        d = len(self.pending_cpu)
        if d > g["dispatch_queue_hw"]:
            g["dispatch_queue_hw"] = d
        occ = 0
        for w in self.workers.values():
            if w.actor_id is None and w.proc is not None:
                occ += len(w.inflight)
        if occ > g["pipeline_inflight_hw"]:
            g["pipeline_inflight_hw"] = occ

    def _demand_shapes(self, cap: int = 100) -> list:
        """Resource shapes of work parked on this node — the per-node
        resource load the autoscaler bin-packs against (reference:
        LoadMetrics fed from raylet resource_load, autoscaler.py:171)."""
        shapes = []
        for spec in self.pending_cpu:
            shapes.append(spec.resources)
        for spec, _exclude in self._pending_remote:
            shapes.append(spec.resources)
        for spec in self._pending_actor_creations:
            shapes.append(spec.resources)
        return [dict(s) for s in shapes[:cap]]

    async def _register_with_head(self):
        cb = getattr(self, "register_cb", None)
        if cb is not None:
            await cb()

    async def _pending_remote_loop(self):
        """Retry remote placements that found no feasible node (nodes may
        join; resources free up)."""
        while not self._closing:
            await asyncio.sleep(0.25)
            n = len(self._pending_remote)
            for _ in range(n):
                spec, exclude = self._pending_remote.popleft()
                self.spawn(self._execute_remotely(spec, exclude))

    async def _addr_conn(self, address: tuple) -> ServerConn:
        """Peer connection keyed by address (object-plane fetches from an
        owner we only know by the address stamped into an ObjectRef)."""
        if not hasattr(self, "_addr_conns"):
            self._addr_conns = {}
        address = tuple(address)
        conn = self._addr_conns.get(address)
        if conn is not None and conn.alive:
            return conn

        async def on_disc(c):
            if self._addr_conns.get(address) is c:
                del self._addr_conns[address]

        conn = await async_connect(address, self._handle_peer_rpc, on_disc)
        self._addr_conns[address] = conn
        return conn

    async def ensure_object(self, oid: ObjectID, owner_addr, timeout=None):
        """Pull a copy of a foreign-owned object into the local store
        (reference: PullManager/ObjectManager chunked push-pull,
        object_manager.h:117, pull_manager.h:52, push_manager.h:30).

        Small objects ride one fetch frame. Large ones stream as bounded
        chunks with a concurrency window, sourced from the owner OR any
        registered holder copy (the owner's location directory), so a gang
        broadcast fans out as a tree instead of N serial pulls from the
        owner's event loop."""
        if owner_addr is None or tuple(owner_addr) == tuple(self.peer_address):
            return
        st = self._obj(oid)
        if st.status != PENDING:
            return
        if oid in self._fetching:
            return  # in-flight fetch will wake the waiters
        self._fetching.add(oid)
        try:
            await self._pull_object(oid, tuple(owner_addr), timeout)
        finally:
            self._fetching.discard(oid)

    async def _pull_object(self, oid: ObjectID, owner_addr: tuple, timeout):
        st = self._obj(oid)
        try:
            conn = await self._addr_conn(owner_addr)
            res = await conn.call("fetch_meta",
                                  {"oid": oid.binary(), "timeout": timeout})
        except (ConnectionLost, RpcTimeout, OSError) as e:
            self.mark_error(oid, ObjectLostError(
                f"owner of {oid.hex()[:16]} unreachable: {e}"))
            return
        # Pull loop. The owner enforces a concurrent-push cap at
        # fetch_begin ("busy"): saturated pullers back off, re-read the
        # location directory, and usually land on a freshly-registered
        # peer copy — an N-node broadcast becomes a tree instead of N
        # serial pulls from the owner (reference: push_manager.h bounds
        # concurrent chunked pushes the same way). After the busy-wait
        # deadline we force the owner to serve anyway (bounded latency).
        busy_deadline = (self.loop.time()
                         + self.cfg.object_transfer_busy_wait_s)
        buf = None
        while True:
            if st.status != PENDING:
                return
            if res[0] == "err":
                self.mark_error(oid, res[1])
                return
            if res[0] == "timeout":
                return  # stays pending; the caller's own deadline rules
            if res[0] == "b":
                self._ingest_result_blob(oid, res[1])
                return
            meta = res[1]
            sources = [tuple(a) for a in meta["holders"]
                       if tuple(a) != tuple(self.peer_address)]
            # Prefer peer copies over the owner: the owner pays for at
            # most the first max_pushes transfers, then the tree takes
            # over.
            src_addr = _random.choice(sources) if sources else owner_addr
            force = (src_addr != owner_addr
                     or self.loop.time() >= busy_deadline)
            buf = await self._pull_chunks(oid, src_addr, force=force)
            if buf == "busy":
                await asyncio.sleep(0.05)
                try:
                    res = await conn.call(
                        "fetch_meta",
                        {"oid": oid.binary(), "timeout": timeout})
                except (ConnectionLost, RpcTimeout, OSError) as e:
                    self.mark_error(oid, ObjectLostError(
                        f"owner of {oid.hex()[:16]} unreachable: {e}"))
                    return
                continue
            if buf is None:
                # Stale/dead holder, or a transient failure on the owner
                # path itself: the owner gets one fresh retry before we
                # declare the object lost (a single dropped chunk must not
                # discard a successfully-computed result).
                await asyncio.sleep(0.1)
                buf = await self._pull_chunks(oid, owner_addr, force=True)
            break
        if st.status != PENDING or self.objects.get(oid) is not st:
            # Resolved elsewhere, or freed mid-pull (borrow released):
            # ingesting into a stale/orphaned state would leak shm. The
            # bulk lane already SEALED its segment — delete it, or the
            # bytes outlive the (gone) table entry forever.
            if isinstance(buf, tuple) and buf[0] == "stored":
                self.shm.delete(oid)
            return
        if buf is None:
            self.mark_error(oid, ObjectLostError(
                f"object {oid.hex()[:16]} could not be pulled "
                f"from {src_addr} or its owner"))
            return
        if isinstance(buf, tuple) and buf[0] == "stored":
            # Bulk lane already landed the bytes in a sealed store
            # segment (recv_into the mmap) — no ingest copy. (Its own
            # counter was bumped in _pull_bulk.)
            self.mark_ready_shm(oid, buf[1])
        else:
            self._ingest_result_blob(oid, buf)
            self.counters["objects_pulled_chunked"] += 1
        st.pulled_from = owner_addr
        # Register our copy so later pullers can source from us.
        try:
            await conn.notify("copy_added", {
                "oid": oid.binary(),
                "addr": list(self.peer_address),
                "node_id": self.node_id.binary(),
            })
        except (ConnectionLost, RpcTimeout, OSError):
            pass

    async def _handle_bulk_conn(self, reader: asyncio.StreamReader,
                                writer: asyncio.StreamWriter):
        """Serve one bulk range request: framed msgpack header in, raw
        payload bytes out (sendfile when the object is a store segment).
        Authenticated with the session token like every other socket."""
        import hmac as _hmac

        from .rpc import get_session_token

        try:
            hdr_len = int.from_bytes(await reader.readexactly(4), "little")
            if hdr_len > 4096:
                return
            req = msgpack.unpackb(await reader.readexactly(hdr_len),
                                  raw=False)
            if not _hmac.compare_digest(req.get("t", ""),
                                        get_session_token()):
                return
            oid = ObjectID(req["oid"])
            off, ln = int(req["off"]), int(req["len"])
            st = self.objects.get(oid)
            if st is None or st.status != READY:
                writer.write((0).to_bytes(8, "little"))
                await writer.drain()
                return
            writer.write(ln.to_bytes(8, "little"))
            if st.location == "shm":
                # The raw-path open below bypasses shm.get(): restore the
                # segment first if the store spilled it to disk.
                if not self.shm.ensure_resident(oid):
                    return
                path = self.shm._path(oid)
                loop = asyncio.get_running_loop()
                with open(path, "rb") as f:
                    try:
                        await writer.drain()
                        await loop.sendfile(writer.transport, f,
                                            offset=off, count=ln)
                    except (asyncio.SendfileNotAvailableError,
                            NotImplementedError):
                        f.seek(off)
                        remaining = ln
                        while remaining > 0:
                            chunk = f.read(min(4 << 20, remaining))
                            if not chunk:
                                break
                            writer.write(chunk)
                            await writer.drain()
                            remaining -= len(chunk)
            else:
                kind, val = st.value
                blob = (val if kind == "bytes"
                        else serialization.serialize(val))
                writer.write(memoryview(blob)[off:off + ln])
            await writer.drain()
            self.counters["bulk_transfers_served"] += 1
        except Exception:  # noqa: BLE001 - network-facing socket: drop
            # malformed/hostile input quietly (a fuzzer's packed int
            # raises AttributeError, a non-str token TypeError, ...).
            pass
        finally:
            try:
                writer.close()
            except OSError:
                pass

    async def _pull_bulk(self, oid: ObjectID, host: str, port: int,
                         size: int):
        """Pull a whole object over N raw bulk connections straight into
        a created store segment (recv_into the mmap — no intermediate
        buffers). Returns ("stored", size) or None (caller falls back to
        the chunked RPC path)."""
        from .rpc import get_session_token

        loop = self.loop
        try:
            mv, seal = self.shm.create(oid, size)
        except Exception:  # noqa: BLE001 - e.g. store OutOfMemoryError
            # Fall back to the chunked path, whose heap-buffer ingest
            # goes through put() and its eviction machinery.
            return None
        # Fan-out scales with payload: one raw connection per
        # fetch_chunk_bytes range, capped by bulk_conns. fetch_chunk_bytes=0
        # forces the single-stream path (the microbench A/B baseline).
        chunk = self.cfg.fetch_chunk_bytes
        if chunk > 0 and size > chunk:
            n_conns = min(-(-size // chunk),
                          max(1, self.cfg.object_transfer_bulk_conns))
        else:
            n_conns = 1
        span = -(-size // n_conns)

        async def pull_range(off: int, ln: int):
            sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            sock.setblocking(False)
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            try:
                await loop.sock_connect(sock, (host, port))
                hdr = msgpack.packb({"t": get_session_token(),
                                     "oid": oid.binary(),
                                     "off": off, "len": ln})
                await loop.sock_sendall(
                    sock, len(hdr).to_bytes(4, "little") + hdr)
                reply = bytearray()
                while len(reply) < 8:
                    b = await loop.sock_recv(sock, 8 - len(reply))
                    if not b:
                        raise ConnectionResetError("bulk source closed")
                    reply += b
                granted = int.from_bytes(reply, "little")
                if granted != ln:
                    raise ConnectionResetError("bulk source refused")
                got = 0
                view = mv[off:off + ln]
                while got < ln:
                    n = await loop.sock_recv_into(sock, view[got:])
                    if n == 0:
                        raise ConnectionResetError("bulk stream truncated")
                    got += n
            finally:
                sock.close()

        tasks = [asyncio.ensure_future(
            pull_range(off, min(span, size - off)))
            for off in range(0, size, span)]
        try:
            await asyncio.gather(*tasks)
        except (OSError, ConnectionResetError, asyncio.IncompleteReadError):
            # Cancel and AWAIT the sibling ranges before abort: a task
            # suspended in sock_recv_into still holds a slice of mv, and
            # closing the mapping under it raises BufferError.
            for t in tasks:
                t.cancel()
            await asyncio.gather(*tasks, return_exceptions=True)
            mv.release()
            try:
                seal.abort()
            except BufferError:
                pass  # a straggler view; GC closes the mapping later
            return None
        mv.release()
        seal.seal()
        self.counters["object_bytes_pulled"] += size
        self.counters["objects_pulled_bulk"] += 1
        return ("stored", size)

    async def _pull_chunks(self, oid: ObjectID, addr: tuple,
                           force: bool = False):
        """Windowed chunk pull of a READY object from one source node.
        Returns the assembled bytearray, "busy" when the source declined
        (push cap, only without force), or None on failure (caller falls
        back to the owner)."""
        try:
            src = await self._addr_conn(addr)
            ok = await src.call("fetch_begin",
                                {"oid": oid.binary(), "force": force})
            if ok[0] == "busy":
                return "busy"
            if ok[0] != "ok":
                return None
            size = ok[1]
            bulk_port = ok[2] if len(ok) > 2 else 0
            if bulk_port and size >= self.cfg.object_transfer_min_chunked_bytes:
                stored = await self._pull_bulk(oid, addr[0], bulk_port,
                                               size)
                if stored is not None:
                    try:
                        await src.notify("fetch_end", oid.binary())
                    except (ConnectionLost, RpcTimeout, OSError):
                        pass
                    return stored
            buf = bytearray(size)
            chunk = self.cfg.object_transfer_chunk_bytes
            sem = asyncio.Semaphore(
                self.cfg.object_transfer_max_chunks_in_flight)

            async def pull(off: int):
                ln = min(chunk, size - off)
                async with sem:
                    r = await src.call("fetch_chunk", {
                        "oid": oid.binary(), "off": off, "len": ln})
                    if isinstance(r, (bytes, bytearray, memoryview)):
                        buf[off:off + len(r)] = r  # ENC_RAW fast path
                    elif r[0] == "c":
                        buf[off:off + len(r[1])] = r[1]
                    else:
                        raise ObjectLostError(str(r[1]))

            try:
                await asyncio.gather(
                    *[pull(off) for off in range(0, size, chunk)])
            finally:
                try:
                    await src.notify("fetch_end", oid.binary())
                except (ConnectionLost, RpcTimeout, OSError):
                    pass
            self.counters["object_bytes_pulled"] += size
            return buf
        except (ConnectionLost, OSError, ObjectLostError):
            return None

    def _attach_inner_refs(self, oid: ObjectID, refs):
        """Pin refs serialized inside a container object for the
        container's lifetime (released in _maybe_free)."""
        if not refs:
            return
        st = self._obj(oid)
        st.inner_refs = (st.inner_refs or []) + [
            (b, tuple(o) if o else None) for b, o in refs]
        for oid_b, owner in refs:
            self.incref_ref(ObjectID(oid_b),
                            tuple(owner) if owner else None)

    async def _result_pin_sweep_loop(self):
        """Reclaim big-result pins whose owner never pulled (reply lost,
        owner died): without this a dropped remote_execute reply leaks the
        pinned shm segment until node restart."""
        ttl = self.cfg.object_transfer_result_pin_ttl_s
        while not self._closing:
            await asyncio.sleep(min(30.0, ttl / 4))
            cutoff = time.time() - ttl
            for rid in [r for r, ts in self._result_pins.items()
                        if ts < cutoff]:
                self._result_pins.pop(rid, None)
                self.counters["result_pins_expired"] += 1
                self.decref(rid)

    def _serving_count(self, oid: ObjectID) -> int:
        ts = self._serving.get(oid)
        if not ts:
            return 0
        cutoff = time.time() - 60.0  # decay: crashed pullers don't leak
        ts[:] = [t for t in ts if t > cutoff]
        if not ts:
            self._serving.pop(oid, None)
            return 0
        return len(ts)

    async def _peer_conn(self, node_id: NodeID, address: tuple) -> ServerConn:
        conn = self.peer_conns.get(node_id)
        if conn is not None and conn.alive:
            return conn

        async def on_disc(c):
            if self.peer_conns.get(node_id) is c:
                del self.peer_conns[node_id]

        conn = await async_connect(tuple(address), self._handle_peer_rpc,
                                   on_disc)
        conn.meta["node_id"] = node_id
        self.peer_conns[node_id] = conn
        return conn

    # ------------------------------------------------------------------
    # Pubsub (node-local subscriber registry + head registration)
    # ------------------------------------------------------------------
    async def pubsub_subscribe(self, channel: str, sub_id: str, sink):
        """Register a local sink; the FIRST local subscriber on a channel
        registers this node with the head broker. A transient head
        failure must not poison the channel (insert-then-give-up would
        make every later subscriber see "already registered"): a
        background retry keeps trying until registered or the channel
        empties. Loop thread only."""
        subs = self.pubsub_local.setdefault(channel, {})
        first = not subs
        subs[sub_id] = sink
        if first and self.head is not None:
            try:
                await self.head.pubsub_sub(channel, self.node_id)
                self._pubsub_head_ok.add(channel)
            except (ConnectionLost, RpcTimeout, OSError):
                self.spawn(self._pubsub_head_retry(channel))

    async def _pubsub_head_retry(self, channel: str):
        while (not self._closing
               and self.pubsub_local.get(channel)
               and channel not in self._pubsub_head_ok
               and self.head is not None):
            try:
                await self.head.pubsub_sub(channel, self.node_id)
                self._pubsub_head_ok.add(channel)
                return
            except (ConnectionLost, RpcTimeout, OSError):
                await asyncio.sleep(1.0)

    async def pubsub_unsubscribe(self, channel: str, sub_id: str):
        subs = self.pubsub_local.get(channel)
        if subs is None:
            return
        subs.pop(sub_id, None)
        if not subs:
            del self.pubsub_local[channel]
            self._pubsub_head_ok.discard(channel)
            if self.head is not None:
                try:
                    await self.head.pubsub_unsub(channel, self.node_id)
                except (ConnectionLost, RpcTimeout, OSError):
                    pass

    async def pubsub_publish(self, channel: str, message) -> int:
        if self.head is None:
            self.pubsub_dispatch(channel, message)
            return 1
        return await self.head.pubsub_pub(channel, message)

    def pubsub_dispatch(self, channel: str, message):
        """Deliver one inbound message to every local sink. A sink that
        throws loses THIS message only (at-most-once contract) — a
        transient failure (e.g. a briefly-full stderr pipe in an fn
        sink) must not silently unsubscribe the consumer forever."""
        for _sub_id, sink in list(self.pubsub_local.get(channel,
                                                        {}).items()):
            kind = sink[0]
            try:
                if kind == "q":
                    sink[1].put_nowait(message)
                elif kind == "fn":
                    sink[1](message)
                else:  # worker
                    w = sink[1]
                    self.spawn(w.conn.notify(
                        "pubsub_msg", {"channel": channel,
                                       "message": message}))
            except Exception:  # noqa: BLE001 - drop message, keep sink
                self.counters["pubsub_sink_errors"] += 1

    async def on_head_push(self, method: str, payload):
        """Pushes from the head (over the node's head connection, or direct
        calls for the head node itself)."""
        if method == "node_dead":
            await self._on_node_dead(NodeID(payload["node_id"]),
                                     payload.get("cause", ""))
        elif method == "pubsub_msg":
            self.pubsub_dispatch(payload["channel"], payload["message"])
        elif method == "reserve_bundle":
            self.reserve_bundle(PlacementGroupID(payload["pg_id"]),
                                payload["bundle_index"], payload["resources"])
        elif method == "release_bundle":
            self.release_bundle(PlacementGroupID(payload["pg_id"]),
                                payload["bundle_index"])

    async def _on_node_dead(self, node_id: NodeID, cause: str):
        self.dead_nodes.add(node_id)
        conn = self.peer_conns.pop(node_id, None)
        if conn is not None:
            await conn.close()  # fails in-flight forwards -> retry paths
        # Drop the dead node from every location directory entry so new
        # pulls don't target its copies, and release every borrow it held
        # (a dead borrower can never send borrow_release).
        nid = node_id.binary()
        for oid, st in list(self.objects.items()):
            if st.holders:
                st.holders = {a: n for a, n in st.holders.items() if n != nid}
            if st.borrowers:
                for addr in [a for a, n in st.borrowers.items() if n == nid]:
                    st.borrowers.pop(addr, None)
                    self.decref(oid)
        for entry in list(self.remote_actors.values()):
            if entry.node_id == node_id and entry.state == "ALIVE":
                await self._remote_actor_died(entry, f"node died: {cause}")

    # ------------------------------------------------------------------
    # Object directory
    # ------------------------------------------------------------------
    def _obj(self, oid: ObjectID) -> ObjectState:
        st = self.objects.get(oid)
        if st is None:
            st = self.objects[oid] = ObjectState()
        return st

    def mark_ready_value(self, oid: ObjectID, value: Any):
        """Device-lane result: keep the live python object (no serialization)."""
        st = self._obj(oid)
        st.status, st.location, st.value = READY, "memory", ("obj", value)
        self._wake(oid, st)

    def mark_ready_bytes(self, oid: ObjectID, blob: bytes):
        st = self._obj(oid)
        st.status, st.location, st.value = READY, "memory", ("bytes", blob)
        st.size = len(blob)
        self._wake(oid, st)

    def mark_ready_shm(self, oid: ObjectID, size: int):
        st = self._obj(oid)
        st.status, st.location, st.value = READY, "shm", None
        st.size = size
        # Referenced objects must survive capacity eviction (native store):
        # pinned while the node's object table holds them, unpinned on free
        # (reference: raylet PinObjectIDs / local_object_manager.h:41).
        self.shm.pin(oid)
        self._wake(oid, st)

    def mark_error(self, oid: ObjectID, err: TaskError):
        st = self._obj(oid)
        st.status, st.error = ERROR, err
        self._wake(oid, st)

    def _wake(self, oid: ObjectID, st: ObjectState):
        for fut in st.waiters:
            if not fut.done():
                fut.set_result(None)
        st.waiters.clear()
        self._kick()
        # A ref dropped while the object was still pending: free on arrival.
        self._maybe_free(oid, st)

    def _start_reconstruction(self, oid: ObjectID) -> bool:
        """Lineage reconstruction: resubmit the creating task of an object
        whose bytes were lost from the store (reference:
        src/ray/core_worker/object_recovery_manager.h:41 +
        task_manager.h:432 resubmit-from-lineage). Loop thread only.

        Actor-method results are not replayable (non-idempotent state
        mutation) — matches the reference, which only reconstructs objects
        from deterministic task lineage."""
        st = self.objects.get(oid)
        if st is None or st.creating_spec is None:
            return False
        if st.status == PENDING:
            # The original task or a concurrent reconstruction is already
            # in flight — don't double-resubmit (single loop thread makes
            # this check atomic).
            return True
        spec = st.creating_spec
        if spec.actor_id is not None:
            return False
        attempts = getattr(spec, "_reconstructions", 0)
        if attempts >= self.cfg.max_object_reconstructions:
            return False
        # Every argument must still be resolvable; a freed dep means the
        # lineage is broken and the object is genuinely lost.
        for dep in spec.dependencies():
            dst = self.objects.get(dep)
            if dst is None or dst.status == ERROR:
                return False
        spec._reconstructions = attempts + 1
        self.counters["objects_reconstructed"] += 1
        for rid in spec.return_ids():
            rst = self._obj(rid)
            if rst.status != PENDING:
                rst.status, rst.location, rst.value = PENDING, None, None
                rst.error = None
            self.shm.unpin(rid)
            self.shm.delete(rid)
        # Re-pin args for the fresh run (symmetric with submit()).
        spec._deps_released = False
        for dep in spec.dependencies():
            self.incref(dep)
        for oid_b, owner in (spec.nested_refs or ()):
            self.incref_ref(ObjectID(oid_b),
                            tuple(owner) if owner else None)
        spec._remote = False
        self._event(spec, "RECONSTRUCTING")
        self._route(spec)
        return True

    async def recover_object(self, oid: ObjectID,
                             timeout: float | None = None) -> bool:
        """Recover a lost local object: first re-pin a surviving copy from
        the location directory (cheap — and the only option for
        non-replayable objects like actor results and puts), then fall
        back to lineage reconstruction (reference:
        object_recovery_manager.h:74-78 pins other copies before
        resubmitting the creating task). True = worth re-reading."""
        st = self.objects.get(oid)
        if st is not None and st.holders:
            for addr in list(st.holders):
                buf = await self._pull_chunks(oid, tuple(addr), force=True)
                if buf is not None and buf != "busy":
                    stored = isinstance(buf, tuple) and buf[0] == "stored"
                    self.shm.unpin(oid)
                    if stored:
                        # Bulk lane sealed a FRESH segment over the lost
                        # path: drop only the stale cached mmap (old
                        # inode) — deleting would unlink the new bytes.
                        self.shm.release(oid)
                    else:
                        self.shm.delete(oid)
                    st.status, st.location, st.value = \
                        PENDING, "memory", None
                    st.error = None
                    if stored:
                        self.mark_ready_shm(oid, buf[1])
                    else:
                        self._ingest_result_blob(oid, buf)
                    self.counters["objects_recovered_from_copy"] += 1
                    return True
        if not self._start_reconstruction(oid):
            return False
        st = await self.wait_object(oid, timeout)
        return st.status != PENDING

    async def wait_object(self, oid: ObjectID, timeout: float | None = None) -> ObjectState:
        st = self._obj(oid)
        if st.status == PENDING:
            fut = self.loop.create_future()
            st.waiters.append(fut)
            if timeout is None:
                await fut
            else:
                try:
                    await asyncio.wait_for(fut, timeout)
                except asyncio.TimeoutError:
                    pass
        return st

    def incref(self, oid: ObjectID, n: int = 1):
        self._obj(oid).refcount += n

    def incref_ref(self, oid: ObjectID, owner_addr=None):
        """incref that understands ownership: a count on a foreign-owned
        object additionally registers ONE aggregate borrow with the owner
        (deferring the owner's free until we release) — the borrowing
        protocol of reference_count.h:61. Loop thread only."""
        st = self._obj(oid)
        st.refcount += 1
        if owner_addr is not None:
            owner_addr = tuple(owner_addr)
            if owner_addr != tuple(self.peer_address):
                st.borrow_owner = owner_addr
                if not st.borrow_registered:
                    st.borrow_registered = True
                    self.spawn(
                        self._register_borrow(oid, owner_addr))

    async def _register_borrow(self, oid: ObjectID, owner_addr: tuple):
        try:
            conn = await self._addr_conn(owner_addr)
            await conn.call("borrow_add", {
                "oid": oid.binary(),
                "addr": list(self.peer_address),
                "node_id": self.node_id.binary(),
            })
        except (ConnectionLost, RpcTimeout, OSError):
            return  # owner gone: fetches will surface the loss
        st = self.objects.get(oid)
        if st is None:
            # Freed locally while the registration was in flight — the
            # release was deferred (never allowed to overtake the add):
            # send it now.
            await self._release_borrow(oid, owner_addr)
        else:
            st.borrow_confirmed = True

    def decref(self, oid: ObjectID, n: int = 1):
        st = self.objects.get(oid)
        if st is None:
            return
        st.refcount -= n
        self._maybe_free(oid, st)

    def free_object(self, oid: ObjectID) -> bool:
        """Eagerly release a READY object's VALUE, now, regardless of
        outstanding refcounts (``ray_tpu.free`` — reference:
        ray._private.internal_api.free + streaming_executor.py:242's
        eager consumed-block release). The entry becomes a tombstone:
        late readers get ObjectFreedError instead of a hang, dropped
        refs still pop it via the normal _maybe_free path, and lineage
        is severed (a freed object is not reconstructable — matching
        the reference, where free'd objects are gone for good).

        Skips (returns False) when the object is PENDING, errored, or
        has live waiters — freeing under an active reader would turn a
        caller's in-flight ``get`` into an error it didn't ask for.
        Loop thread only."""
        st = self.objects.get(oid)
        if st is None or st.status != READY or st.waiters:
            return False
        self._tombstone_freed(oid, st)
        # Copy-holders elsewhere release their bytes too — otherwise the
        # freed block lingers exactly on the node that materialized it,
        # and a late get there would return the value instead of the
        # tombstone error.
        for addr in list(st.holders or ()):
            self.spawn(self._notify_free_peer(oid, tuple(addr)))
        st.holders = None
        return True

    def _tombstone_freed(self, oid: ObjectID, st: ObjectState) -> None:
        """The shared freed-state transition (owner side and borrowed
        copies): value gone, transitive pins released, lineage severed,
        ObjectFreedError for any late reader. Loop thread only."""
        if st.location == "shm":
            self.shm.unpin(oid)
            self.shm.delete(oid)
        # A freed container releases what it transitively pinned.
        for oid_b, _owner in (st.inner_refs or ()):
            self.decref(ObjectID(oid_b))
        st.inner_refs = None
        st.value = None
        st.size = 0
        st.location = "memory"
        st.creating_spec = None
        st.status = ERROR
        st.error = ObjectFreedError(
            f"object {oid.hex()[:16]} was explicitly freed "
            f"(ray_tpu.free)")
        self.counters["objects_freed"] += 1

    async def _notify_free_peer(self, oid: ObjectID, addr: tuple) -> None:
        try:
            conn = await self._addr_conn(addr)
            await conn.notify("free_object", oid.binary())
        except (ConnectionLost, RpcTimeout, OSError):
            pass  # peer gone; its copy died with it

    def _maybe_free(self, oid: ObjectID, st: ObjectState):
        # PENDING entries are kept alive awaiting production — EXCEPT pure
        # borrow placeholders (foreign-owned, nothing local will ever
        # produce them): those must free on release or the borrow_release
        # below never reaches the owner and the object leaks there.
        borrow_placeholder = (st.status == PENDING
                              and st.borrow_owner is not None
                              and st.creating_spec is None)
        if (st.refcount <= 0 and not st.waiters
                and (st.status != PENDING or borrow_placeholder)):
            self.objects.pop(oid, None)
            if st.location == "shm":
                self.shm.unpin(oid)
                self.shm.delete(oid)
            if st.pulled_from is not None:
                # Foreign copy released: deregister from the owner's
                # location directory so new pullers don't target us.
                self.spawn(
                    self._notify_copy_removed(oid, st.pulled_from))
            if st.borrow_confirmed and st.borrow_owner is not None:
                # Last local count on a borrowed object: release our
                # aggregate borrow so the owner may free. (If the add is
                # still in flight, _register_borrow sends the release on
                # ack — a release must never overtake its registration.)
                self.spawn(
                    self._release_borrow(oid, st.borrow_owner))
            # A freed container releases what it transitively pinned.
            for oid_b, _owner in (st.inner_refs or ()):
                self.decref(ObjectID(oid_b))

    async def _release_borrow(self, oid: ObjectID, owner_addr: tuple):
        try:
            conn = await self._addr_conn(owner_addr)
            await conn.notify("borrow_release", {
                "oid": oid.binary(), "addr": list(self.peer_address)})
        except (ConnectionLost, RpcTimeout, OSError):
            pass

    async def _notify_copy_removed(self, oid: ObjectID, owner_addr: tuple):
        try:
            conn = await self._addr_conn(owner_addr)
            await conn.notify("copy_removed", {
                "oid": oid.binary(), "addr": list(self.peer_address)})
        except (ConnectionLost, RpcTimeout, OSError):
            pass

    async def _notify_free_remote(self, oid: ObjectID, owner_addr: tuple):
        """Forward an eager free to the object's owner; also RELEASE (not
        tombstone) any local pulled copy. The owner is the arbiter — it
        may skip the free (active waiters), so the local copy must only
        drop its bytes and become re-pullable: a later local get then
        re-fetches from the owner and observes whatever the owner
        decided (value, or ObjectFreedError)."""
        st = self.objects.get(oid)
        if st is not None and st.status == READY and not st.waiters:
            if st.location == "shm":
                self.shm.unpin(oid)
                self.shm.delete(oid)
            st.value, st.size = None, 0
            st.location = "memory"
            st.status = PENDING
            if st.pulled_from is not None:
                self.spawn(self._notify_copy_removed(oid, st.pulled_from))
                st.pulled_from = None
        await self._notify_free_peer(oid, owner_addr)

    def materialize_for_ipc(self, oid: ObjectID) -> tuple:
        """Return ("bytes", blob) | ("shm",) | ("err", e) for a READY object,
        serializing device-lane python objects on demand."""
        st = self.objects[oid]
        if st.status == ERROR:
            return ("err", st.error)
        if st.location == "shm":
            return ("shm",)
        kind, val = st.value
        if kind == "bytes":
            blob = val
            if len(blob) > self.cfg.max_inline_object_size:
                self.shm.put(oid, blob)
                self.shm.pin(oid)
                st.location, st.value, st.size = "shm", None, len(blob)
                return ("shm",)
            return ("bytes", blob)
        # Converting a live value to bytes may drop the only ObjectRefs
        # keeping nested objects alive (st.value is discarded below):
        # the container object pins them from here on.
        parts, refs = serialization.serialize_with_refs_parts(val)
        self._attach_inner_refs(oid, refs)
        total = serialization.parts_len(parts)
        if total > self.cfg.max_inline_object_size:
            # Vectored write (one copy) — device-lane numpy results go
            # value memory -> segment without a flattened blob.
            self.shm.put_parts(oid, parts)
            # Same invariant as mark_ready_shm: table-referenced segments
            # are pinned against capacity eviction.
            self.shm.pin(oid)
            st.location, st.value, st.size = "shm", None, total
            return ("shm",)
        return ("bytes", b"".join(parts))

    def value_in_process(self, oid: ObjectID):
        """Deserialize (or fetch) a READY object into a python value; device
        lane fast path."""
        st = self.objects[oid]
        if st.status == ERROR:
            raise_stored(st.error)
        if st.location == "shm":
            mv = self.shm.get(oid)
            if mv is None:
                raise ObjectLostError(f"object {oid.hex()[:16]} missing from store")
            val = serialization.deserialize(mv)
            return val
        kind, val = st.value
        if kind == "bytes":
            obj = serialization.deserialize(val)
            st.value = ("obj", obj)
            return obj
        return val

    # ------------------------------------------------------------------
    # Task submission & scheduling
    # ------------------------------------------------------------------
    def spawn(self, coro):
        """create_task with a strong reference held until completion."""
        t = self.loop.create_task(coro)
        self._spawned_tasks.add(t)
        t.add_done_callback(self._spawned_tasks.discard)
        return t

    def submit(self, spec: TaskSpec) -> list[ObjectID]:
        """Register returns + route. Loop thread only."""
        rids = spec.return_ids()
        for rid in rids:
            st = self._obj(rid)
            st.creating_spec = spec
            st.refcount += 1  # submitter's implicit ref, released by ObjectRef
        # Pin args until the task reaches a terminal state (reference:
        # task-argument pinning in the raylet's DependencyManager). Refs
        # nested inside by-value args are pinned the same way — borrowed
        # from their owner when foreign — so the submitter dropping its
        # handle mid-flight cannot free what the task carries.
        for dep in spec.dependencies():
            self.incref(dep)
        for oid_b, owner in (spec.nested_refs or ()):
            self.incref_ref(ObjectID(oid_b),
                            tuple(owner) if owner else None)
        self.counters["tasks_submitted"] += 1
        spec._submit_mono = time.monotonic()
        self._event(spec, "SUBMITTED")
        self._route(spec)
        return rids

    def _route(self, spec: TaskSpec):
        """Decide where a spec runs: this node's queues, a pinned node, a
        placement-group bundle's node, or head-chosen placement."""
        if getattr(spec, "_remote", False):
            # Forwarded to us by its owner — the routing decision is made.
            self._enqueue_local(spec)
            return
        if spec.actor_id is not None and not spec.is_actor_creation:
            if spec.actor_id in self.actors:
                self._submit_actor_task(spec)
            elif spec.actor_id in self.remote_actors:
                self._enqueue_remote_actor_task(
                    self.remote_actors[spec.actor_id], spec)
            else:
                self.spawn(self._route_unknown_actor_task(spec))
            return
        strat = spec.strategy
        if strat.kind == "node" and strat.node_id is not None \
                and strat.node_id != self.node_id.binary():
            if spec.is_actor_creation:
                # Through the remote-actor machinery, NOT the plain
                # remote-execute path: the owner needs a RemoteActorEntry
                # immediately so method calls submitted right after
                # creation queue behind the in-flight construction
                # instead of failing as "unknown actor".
                self._create_actor_remotely(spec)
            else:
                self.spawn(self._execute_remotely(
                    spec, pin_node=NodeID(strat.node_id)))
            return
        if strat.kind == "pg" and strat.pg_id is not None:
            self.spawn(self._route_pg_task(spec))
            return
        needs_placement = (strat.kind == "spread"
                           # Label selectors are head-evaluated: this
                           # node's own labels may not match.
                           or strat.kind == "labels"
                           or not self._locally_feasible(spec)
                           # Actors reserve lifetime resources: if this node
                           # lacks availability, let the head place them on
                           # one that has it instead of parking locally.
                           or (spec.is_actor_creation
                               and not self._is_device_task(spec)
                               and self._lacks_lifetime_room(spec.resources)))
        if needs_placement and self.head is not None:
            if spec.is_actor_creation:
                self._create_actor_remotely(spec)
            else:
                self.spawn(self._execute_remotely(spec))
            return
        self._enqueue_local(spec)

    def _enqueue_local(self, spec: TaskSpec):
        if spec.is_actor_creation:
            # Register the PENDING actor state SYNCHRONOUSLY: submission
            # is fire-and-forget, so the creating client's very next
            # call_soon may be a method call on this actor — it must
            # find the entry (and queue behind ready_fut), not fall into
            # the unknown-actor path.
            self._register_actor_state(spec)
            self.spawn(self._create_actor(spec))
        elif spec.actor_id is not None:
            self._submit_actor_task(spec)
        else:
            spec._pending_since = time.monotonic()
            self.pending_cpu.append(spec)
            self._gauge_queues()
            self._kick()

    def _locally_feasible(self, spec: TaskSpec) -> bool:
        if self._is_device_task(spec):
            # The device lane exists wherever this process owns chips (or
            # the CPU jax backend in tests); "device" resource advertises it.
            if spec.resources.get("TPU", 0) > 0:
                return self.total_resources.get("TPU", 0) >= spec.resources["TPU"]
            return self.total_resources.get("device", 0) > 0
        return all(self.total_resources.get(k, 0) >= v
                   for k, v in spec.resources.items() if v > 0)

    async def _route_pg_task(self, spec: TaskSpec):
        """Placement-group tasks run where their bundle is reserved."""
        try:
            info = await self.head.pg_state(spec.strategy.pg_id)
        except (ConnectionLost, RpcTimeout, OSError):
            info = None
        if info is None or info["state"] != "CREATED":
            self._fail_task(spec, TaskError(
                f"placement group {spec.strategy.pg_id.hex()[:12]} is not "
                f"ready (state={info['state'] if info else 'UNKNOWN'})"))
            return
        idx = max(spec.strategy.pg_bundle_index, 0)
        target = info["placement"].get(idx)
        if target is None:
            self._fail_task(spec, TaskError(
                f"placement group bundle {idx} has no reservation"))
            return
        target = NodeID(target)
        if target == self.node_id:
            self._enqueue_local(spec)
        else:
            await self._execute_remotely(spec, pin_node=target)

    async def _route_unknown_actor_task(self, spec: TaskSpec):
        """Actor handle deserialized away from the actor's home node (e.g.
        fetched by name): resolve home via the head directory and forward."""
        node_b = None
        if self.head is not None:
            try:
                node_b = await self.head.actor_node(spec.actor_id)
            except (ConnectionLost, RpcTimeout, OSError):
                node_b = None
        if node_b is None:
            self._fail_task(spec, ActorDiedError(
                "actor is dead: unknown actor", task_name=spec.name))
            return
        node_id = NodeID(node_b)
        if node_id == self.node_id:
            # Directory says here, but no local state: it died.
            self._fail_task(spec, ActorDiedError(
                "actor is dead", task_name=spec.name))
            return
        entry = self.remote_actors.get(spec.actor_id)
        if entry is None:
            addr = await self._node_address(node_id)
            if addr is None:
                self._fail_task(spec, ActorDiedError(
                    "actor is dead: its node is gone", task_name=spec.name))
                return
            entry = RemoteActorEntry(
                actor_id=spec.actor_id, node_id=node_id, address=addr)
            self.remote_actors[spec.actor_id] = entry
        self._enqueue_remote_actor_task(entry, spec)

    async def _node_address(self, node_id: NodeID):
        for n in await self.head.list_nodes():
            if n["node_id"] == node_id.binary() and n["state"] == "ALIVE":
                return tuple(n["address"])
        return None

    def _kick(self):
        if not self._closing:
            # Any resource release (task finish, actor death, bundle free)
            # routes through here, so parked actor creations get their retry.
            self._retry_pending_actor_creations()
            self.loop.call_soon(self._dispatch)

    def _deps_ready(self, spec: TaskSpec) -> bool:
        """True if all deps are terminal. Raises the dep's error if any dep
        failed — errors propagate through the task graph (reference:
        dependency failures poison downstream tasks)."""
        for dep in spec.dependencies():
            st = self._obj(dep)
            if st.status == ERROR:
                raise_stored(st.error)
            if st.status == PENDING:
                # _wake() on any object completion re-kicks the dispatcher,
                # so parking needs no per-spec waiter future.
                return False
        return True

    def _is_device_task(self, spec: TaskSpec) -> bool:
        return (
            spec.strategy.kind == "device"
            or spec.resources.get("TPU", 0) > 0
            or spec.resources.get("device", 0) > 0
        )

    def _dispatch(self):
        if self._closing:
            return
        still_pending = collections.deque()
        while self.pending_cpu:
            spec = self.pending_cpu.popleft()
            if spec.task_id in self.cancelled:
                self.cancelled.discard(spec.task_id)
                self._fail_task(spec, TaskCancelledError(task_name=spec.name))
                continue
            try:
                if not self._deps_ready(spec):
                    still_pending.append(spec)
                    continue
            except TaskError as e:
                self._fail_task(spec, e)
                continue
            if self._is_device_task(spec):
                self._run_on_device(spec)
                continue
            bad = self._bad_env_error(spec.env_id)
            if bad is not None:
                msg = f"runtime_env setup failed on this node: {bad}"
                self._fail_task(spec, TaskError(
                    msg, cause=RuntimeEnvSetupError(msg),
                    task_name=spec.name))
                continue
            worker = self._acquire_worker(spec)
            if worker is None:
                if self._should_spill(spec):
                    spec._spill_inflight = True
                    self.spawn(self._try_spill(spec))
                    continue
                if self._spill_candidate(spec):
                    # Parked awaiting its spillback window; nothing else
                    # may re-kick dispatch before it opens (few pending
                    # specs ⇒ no deep-queue re-kick, head task may run
                    # for minutes) — so schedule one.
                    self._schedule_spill_kick()
                still_pending.append(spec)
                self._dispatch_misses += 1
                if self._dispatch_misses >= 4:
                    # Deep-queue guard: re-scanning the whole burst on
                    # EVERY completion is O(queue^2). A few consecutive
                    # no-worker misses ⇒ the rest of the (mostly
                    # homogeneous) queue can't run either; stop and
                    # keep order. Heterogeneous smaller tasks still get
                    # a chance within the first misses — and a delayed
                    # re-kick guarantees a feasible task parked behind
                    # infeasible heads is NOT starved when no completion
                    # event is coming (idle node, 16-CPU heads).
                    still_pending.extend(self.pending_cpu)
                    self.pending_cpu.clear()
                    self.loop.call_later(0.05, self._dispatch)
                    break
                continue
            self._dispatch_misses = 0
            self.spawn(self._run_on_worker(worker, spec))
        self._dispatch_misses = 0
        self.pending_cpu = still_pending
        self._gauge_queues()
        for actor in self.actors.values():
            if actor.queue:
                self._pump_actor(actor)

    def _schedule_spill_kick(self):
        """One coalesced delayed dispatch re-run, timed so parked spill
        candidates come back through _should_spill after their
        spillback_delay_s window has opened."""
        if self._spill_kick_pending or self._closing:
            return
        self._spill_kick_pending = True

        def kick():
            self._spill_kick_pending = False
            self._dispatch()

        self.loop.call_later(self.cfg.spillback_delay_s + 0.02, kick)

    def _spill_candidate(self, spec: TaskSpec) -> bool:
        """True while the spillback path should get the first shot at a
        spec the local pool can't freshly lease: a head is attached, the
        spec is spillable (default strategy, not already spilled here),
        and no spill offer has been declined yet. Such specs park
        instead of pipelining so cluster-idle capacity wins over local
        queuing."""
        return (self.head is not None
                and self._peer_nodes > 0
                and not getattr(spec, "_remote", False)
                and spec.strategy.kind == "default"
                and spec.actor_id is None
                and getattr(spec, "_spill_cooldown", 0.0) == 0.0)

    def _should_spill(self, spec: TaskSpec) -> bool:
        """A locally-queued task stuck behind zero capacity is offered to
        the head for spillback to a node with room (reference: raylet
        spillback in local_task_manager.h)."""
        if (self.head is None or getattr(spec, "_remote", False)
                or getattr(spec, "_spill_inflight", False)
                or spec.strategy.kind != "default"
                or spec.actor_id is not None):
            return False
        now = time.monotonic()
        if now - getattr(spec, "_pending_since", now) < self.cfg.spillback_delay_s:
            return False
        cooldown = getattr(spec, "_spill_cooldown", 0.0)
        return now - cooldown >= self.cfg.spillback_delay_s

    async def _try_spill(self, spec: TaskSpec):
        try:
            placed = await self.head.schedule(
                spec.resources, "spill", [self.node_id.binary()])
        except (ConnectionLost, RpcTimeout, OSError):
            placed = None
        spec._spill_inflight = False
        if placed is None:
            spec._spill_cooldown = time.monotonic()
            self.pending_cpu.append(spec)
            self._gauge_queues()
            self._kick()
            return
        self.counters["tasks_spilled"] += 1
        await self._execute_remotely(spec,
                                     pin_node=NodeID(placed["node_id"]))

    # -- CPU worker lane ------------------------------------------------
    def _charge_pool(self, spec: TaskSpec):
        """The CPU pool a spec draws from: its reserved PG bundle when the
        bundle reserves CPU, else the node's free pool (a bundle of pure
        custom resources doesn't gate CPU)."""
        if spec.strategy.kind == "pg" and spec.strategy.pg_id is not None:
            pool = self.bundles.get(
                (spec.strategy.pg_id, max(spec.strategy.pg_bundle_index, 0)))
            if pool is not None and "CPU" in pool.total:
                return pool.available
        return self.available

    def _bad_env_error(self, env_id: str) -> Optional[str]:
        """Recent setup failure for this env on this node, if any. Entries
        expire so transient causes (KV blip, disk pressure) retry instead
        of poisoning the node forever."""
        hit = self._bad_envs.get(env_id)
        if hit is None:
            return None
        msg, t = hit
        if time.monotonic() - t > self.cfg.runtime_env_retry_s:
            del self._bad_envs[env_id]
            return None
        return msg

    def _acquire_worker(self, spec: TaskSpec) -> Optional[WorkerHandle]:
        need = spec.resources.get("CPU", 1.0)
        env_id = spec.env_id
        pool = self._charge_pool(spec)
        if pool.get("CPU", 0) >= need:
            skipped = []
            found = None
            while self.idle_workers:
                w = self.idle_workers.popleft()
                if not (w.state == "IDLE" and w.conn is not None
                        and w.conn.alive):
                    continue  # dead/stale handle: drop it
                if w.env_id != env_id:
                    skipped.append(w)  # wears a different env; keep for
                    continue           # others
                found = w
                break
            self.idle_workers.extend(skipped)
            if found is not None:
                found.state = "BUSY"
                pool["CPU"] = pool.get("CPU", 0) - need
                found.charged_pool = pool
                found.charged_cpu = need
                found.inflight[spec.task_id] = spec
                self._gauge_queues()
                return found
            # No idle worker with this env: fork one, but never more
            # STARTING workers than CPU slots could run concurrently
            # (forks cost ~2.5s on small hosts).
            live = [w for w in self.workers.values()
                    if w.state != "DEAD" and w.actor_id is None]
            starting = sum(1 for w in live if w.state == "STARTING")
            if (len(live) >= self.cfg.max_cpu_workers and skipped
                    and starting == 0):
                # Pool is full of idle workers wearing OTHER envs: evict
                # the longest-idle mismatch to make room (reference:
                # worker_pool kills idle workers for a different env).
                victim = min(skipped, key=lambda w: w.last_idle)
                try:
                    self.idle_workers.remove(victim)
                except ValueError:
                    pass
                self._kill_worker(victim)
                live = [w for w in self.workers.values()
                        if w.state != "DEAD" and w.actor_id is None]
            if (len(live) < self.cfg.max_cpu_workers
                    and starting < max(1, int(self.available.get("CPU", 1)))):
                self._spawn_worker(runtime_env=spec.runtime_env)
            # The pool can still grant a fresh lease (a fork is pending
            # or a busy worker will go idle): park rather than pipeline.
            # Pipelining here can push a spec behind a head that BLOCKS
            # on it — e.g. a nested child queued on its own parent's
            # lane deadlocks, where waiting ~2.5s for the fork does not.
            return None
        # No fresh lease possible (the pool is out of CPU, so this spec
        # can only run locally on a worker already charged for it):
        # PIPELINE the spec into the in-flight window of the
        # least-loaded busy worker whose lease already covers it (same
        # env, same pool, enough charged CPU). The worker executes its
        # window one task at a time on a serial FIFO lane, so the next
        # spec is on the worker the moment the current one finishes
        # instead of a node round trip later. Spillback gets the first
        # shot, though: while a head could still place this spec on a
        # node with idle capacity, parking beats binding it behind a
        # busy local worker — pipelining engages once the head declines
        # (or there is no head / the spec can't spill).
        depth = self.cfg.worker_pipeline_depth
        if depth > 1 and not self._spill_candidate(spec):
            best = None
            for w in self.workers.values():
                if (w.state == "BUSY" and w.actor_id is None
                        and w.conn is not None and w.conn.alive
                        and w.env_id == env_id
                        and w.charged_pool is pool
                        and w.charged_cpu >= need
                        and 0 < len(w.inflight) < depth):
                    if best is None or len(w.inflight) < len(best.inflight):
                        best = w
            if best is not None:
                spec._pipelined = True
                best.inflight[spec.task_id] = spec
                self._gauge_queues()
                return best
        return None

    def _spawn_worker(self, actor_id: ActorID | None = None,
                      preserve_platform_env: bool = False,
                      runtime_env: dict | None = None) -> WorkerHandle:
        wid = WorkerID.from_random()
        env = dict(os.environ)
        if runtime_env:
            env["RT_RUNTIME_ENV"] = json.dumps(runtime_env)
        # CPU-lane workers never open the TPU: a chip belongs to one
        # process at a time and that process is the device lane's. Force
        # the cpu platform (the ambient env may select the TPU).
        # Exception: a gang worker holding the node's TPU_HOST slot IS
        # the host's chip owner (multi-controller SPMD, one process per
        # host — reference: python/ray/train/_internal/
        # backend_executor.py:124's one-worker-per-host gang) and keeps
        # the ambient platform env; _start_actor refuses it on a node
        # whose own process already holds the chips.
        if not preserve_platform_env:
            env["JAX_PLATFORMS"] = "cpu"
        env["RT_SESSION_ID"] = self.session_id
        env["RT_SOCK_PATH"] = self.sock_path
        env["RT_WORKER_ID"] = wid.hex()
        # Per-worker log capture (reference: workers write
        # worker-<id>.out/.err under the session dir, tailed by the log
        # monitor): stdout+stderr share one file; the node tails it and
        # streams new lines to the driver console.
        log_path = os.path.join(self.log_dir, f"worker-{wid.hex()[:12]}.log")
        log_f = open(log_path, "ab", buffering=0)
        proc = subprocess.Popen(
            [sys.executable, "-m", "ray_tpu._private.worker"],
            env=env,
            stdout=log_f,
            stderr=log_f,
        )
        log_f.close()  # the child holds the fd
        from ray_tpu import runtime_env as _re

        w = WorkerHandle(worker_id=wid, proc=proc, actor_id=actor_id,
                         env_id=_re.env_id(runtime_env))
        w.log_path = log_path
        w.registered = self.loop.create_future()
        self.workers[wid] = w
        self.counters["workers_started"] += 1
        return w

    async def _run_on_worker(self, worker: WorkerHandle, spec: TaskSpec):
        worker.owner_node = getattr(spec, "_owner_node", None)
        worker.inflight[spec.task_id] = spec
        self._gauge_queues()
        pipelined = getattr(spec, "_pipelined", False)
        spec._pipelined = False
        spec._worker_started = False
        if not pipelined:
            # Head of a fresh lease: it executes the moment it lands on
            # the worker's serial lane, so RUNNING is anchored here —
            # depth-1 behavior unchanged. A pipelined spec is only
            # QUEUED on the worker; its RUNNING transition arrives via
            # the worker's task_running notify (_on_task_running), so
            # the queue phase keeps meaning "waited to execute".
            spec._worker_started = True
            self._event(spec, "RUNNING", worker=f"worker:{worker.proc.pid}",
                        phases=self._dispatch_phases(spec))
        try:
            payload = self._spec_for_ipc(spec, serial=True)
            if pipelined:
                payload["_notify_start"] = True
            reply = await worker.conn.call("execute_task", payload)
            self._handle_task_reply(spec, reply)
        except ConnectionLost:
            if getattr(spec, "_worker_started", False):
                self._retry_or_fail(
                    spec, WorkerCrashedError(task_name=spec.name))
            else:
                # Queued on the dead worker but never started: the crash
                # cannot have been its fault — requeue, don't charge a
                # retry.
                self._requeue_unstarted(spec)
        except TaskError as e:
            self._fail_task(spec, e)
        except BaseException as e:  # noqa: BLE001 - never leave returns pending
            self._fail_task(spec, TaskError.from_exception(e, spec.name))
        finally:
            worker.inflight.pop(spec.task_id, None)
            if not worker.inflight:
                # Last in-flight spec done: credit the lease charge back
                # and return the worker to the idle pool.
                if worker.charged_pool is not None:
                    worker.charged_pool["CPU"] = (
                        worker.charged_pool.get("CPU", 0)
                        + worker.charged_cpu)
                    worker.charged_pool = None
                    worker.charged_cpu = 0.0
                if worker.state == "BUSY":
                    worker.state = "IDLE"
                    worker.last_idle = time.monotonic()
                    self.idle_workers.append(worker)
            self._kick()

    def _requeue_unstarted(self, spec: TaskSpec):
        """A spec pushed into a dead worker's pipeline window that never
        began executing: back to the queue WITHOUT consuming a retry.
        Its RUNNING event never fired, so re-emitting SUBMITTED keeps
        the lifecycle stream's SUBMITTED->RUNNING ordering intact."""
        if getattr(spec, "_cancel_requested", False):
            self._fail_task(spec, TaskCancelledError(task_name=spec.name))
            return
        spec._oom_killed = False  # an unstarted spec used no memory
        spec._pending_since = time.monotonic()
        self.counters["tasks_requeued"] += 1
        self._event(spec, "SUBMITTED")
        self.pending_cpu.append(spec)
        self._gauge_queues()
        self._kick()

    def _on_task_running(self, worker: WorkerHandle, task_id: TaskID):
        """task_running notify from a worker: a pipelined spec reached
        the head of the worker's serial lane and is now executing."""
        spec = worker.inflight.get(task_id)
        if spec is None or getattr(spec, "_worker_started", False):
            return
        spec._worker_started = True
        self._event(spec, "RUNNING", worker=f"worker:{worker.proc.pid}",
                    phases=self._dispatch_phases(spec))

    def _spec_for_ipc(self, spec: TaskSpec, serial: bool = False) -> dict:
        """Resolve READY deps: memory-store values are inlined (serialized),
        shm objects stay refs (worker mmaps them). ``serial`` routes the
        push to the worker's single-thread FIFO lane (pipelined plain
        tasks and max_concurrency=1 actor calls execute in push order,
        one at a time — the lease charges CPU for ONE running task)."""
        def enc(a):
            if a[0] == REF:
                st = self.objects[a[1]]
                if st.status == ERROR:
                    raise_stored(st.error)
                mat = self.materialize_for_ipc(a[1])
                if mat[0] == "bytes":
                    return ("v", mat[1])
                return ("shm", a[1].binary())
            return a
        out = {
            "task_id": spec.task_id.binary(),
            "name": spec.name,
            "func_id": spec.func_id,
            "args": [enc(a) for a in spec.args],
            "kwargs": {k: enc(v) for k, v in spec.kwargs.items()},
            "num_returns": spec.num_returns,
            "method_name": spec.method_name,
            "actor_id": spec.actor_id.binary() if spec.actor_id else None,
            "is_actor_creation": spec.is_actor_creation,
            "trace_ctx": spec.trace_ctx,
        }
        if serial:
            out["_lane"] = "s"
        return out

    def _handle_task_reply(self, spec: TaskSpec, reply: dict):
        rids = spec.return_ids()
        if reply.get("error") is not None:
            err = reply["error"]
            if spec.retry_exceptions and spec.max_retries > 0 and spec.actor_id is None:
                spec.max_retries -= 1
                self.pending_cpu.append(spec)
                self._gauge_queues()
                self._kick()
                return
            self._fail_task(spec, err)
            return
        results = reply["results"]  # list[("b", blob) | ("shm", size)]
        if len(results) != len(rids):
            self._fail_task(spec, TaskError(
                f"task '{spec.name}' declared num_returns={len(rids)} but "
                f"returned {len(results)} values"))
            return
        # Refs serialized inside each result value are pinned for that
        # result object's lifetime — the consumer deserializing the result
        # registers its own borrow before it could ever drop the result.
        nested_per = reply.get("nested_refs") or [()] * len(rids)
        for rid, res, inner in zip(rids, results, nested_per):
            self._attach_inner_refs(rid, inner)
            if res[0] == "b":
                self.mark_ready_bytes(rid, res[1])
            else:
                self.mark_ready_shm(rid, res[1])
        self._release_deps(spec)
        self.cancelled.discard(spec.task_id)  # cancel raced completion
        self.counters["tasks_finished"] += 1
        phases = dict(getattr(spec, "_phases", None) or {})
        phases.update(reply.get("phases") or {})
        self._observe_phases(phases)
        self._event(spec, "FINISHED", phases=phases or None)

    def _release_deps(self, spec: TaskSpec):
        """Unpin task args exactly once, at the task's terminal state."""
        if getattr(spec, "_deps_released", False):
            return
        spec._deps_released = True
        for dep in spec.dependencies():
            self.decref(dep)
        for oid_b, _owner in (spec.nested_refs or ()):
            self.decref(ObjectID(oid_b))

    def cancel_task(self, task_id: TaskID, force: bool = False):
        """Cancel a task wherever it is: queued specs are dropped at
        dispatch; a task RUNNING on a CPU worker gets a best-effort
        async interrupt (force=True kills the worker process instead);
        a running device-lane task gets the same thread interrupt in
        this process. Reference: ray.cancel semantics
        (core_worker CancelTask + force kill)."""
        self.cancelled.add(task_id)
        for w in self.workers.values():
            spec = w.inflight.get(task_id)
            if spec is None:
                continue
            spec._cancel_requested = True
            if force:
                # ConnectionLost surfaces in _run_on_worker; the
                # _cancel_requested flag turns the retry path into a
                # TaskCancelledError failure.
                self._kill_worker(w, force=True)
            elif w.conn is not None and w.conn.alive:
                self.spawn(self._send_cancel(w, task_id))
        self._device_interrupts.interrupt(task_id.binary(),
                                          TaskCancelledError)
        self._kick()

    async def _send_cancel(self, w: WorkerHandle, task_id: TaskID):
        try:
            await w.conn.call("cancel_task", task_id.binary())
        except (ConnectionLost, RpcTimeout, OSError):
            pass

    def _retry_or_fail(self, spec: TaskSpec, err: TaskError):
        if getattr(spec, "_cancel_requested", False):
            self._fail_task(spec, TaskCancelledError(task_name=spec.name))
            return
        if getattr(spec, "_oom_killed", False):
            spec._oom_killed = False
            err = OutOfMemoryError(
                f"worker killed by the memory monitor while running "
                f"'{spec.name}' (host memory pressure)",
                task_name=spec.name)
        if spec.max_retries > 0 and not spec.is_actor_creation and spec.actor_id is None:
            spec.max_retries -= 1
            self.counters["tasks_retried"] += 1
            self.pending_cpu.append(spec)
            self._gauge_queues()
            self._kick()
        else:
            self._fail_task(spec, err)

    def _fail_task(self, spec: TaskSpec, err: TaskError):
        for rid in spec.return_ids():
            self.mark_error(rid, err)
        self._release_deps(spec)
        self.cancelled.discard(spec.task_id)  # terminal: no leak
        self.counters["tasks_failed"] += 1
        # Partial ledger (queue/schedule) still attributes where a doomed
        # task spent its time; failed attempts stay out of the histogram
        # so latency percentiles describe completed work only.
        self._event(spec, "FAILED",
                    phases=getattr(spec, "_phases", None) or None)

    # -- device lane ----------------------------------------------------
    def _resolve_args_in_process(self, spec: TaskSpec):
        def dec(a):
            if a[0] == REF:
                return self.value_in_process(a[1])
            if a[0] == "o":  # in-process passthrough (device lane fast path)
                return a[1]
            return serialization.deserialize(a[1])
        args = [dec(a) for a in spec.args]
        kwargs = {k: dec(v) for k, v in spec.kwargs.items()}
        return args, kwargs

    def _get_callable(self, func_id: str):
        fn = self._fn_cache.get(func_id)
        if fn is None:
            fn = cloudpickle.loads(self.functions[func_id])
            self._fn_cache[func_id] = fn
        return fn

    def _open_device_lane(self) -> TaskError | None:
        """Device-lane start-up, before the first device task or actor
        of a chip-bearing node touches jax in this process: the process
        becomes the chips' one owner (compile cache on, ownership
        recorded — backend_probe.claim_chips) unless a TPU_HOST gang
        worker already is. One of the two may exist on a host;
        _start_actor refuses the gang worker in the other order."""
        if (self.total_resources.get("TPU", 0) <= 0
                or backend_probe.holds_chips()):
            return None
        gang = next((a for a in self.actors.values()
                     if a.state != "DEAD" and not a.is_device
                     and a.creation_spec.resources.get("TPU_HOST", 0) > 0),
                    None)
        if gang is not None:
            return TaskError(
                "the device lane cannot open this host's chips: the "
                f"TPU_HOST gang worker {gang.actor_id.hex()[:12]} holds "
                "them, and a chip belongs to one process at a time. Run "
                "the work in that gang, or stop it first.")
        backend_probe.claim_chips()
        return None

    def _run_on_device(self, spec: TaskSpec, pool: ThreadPoolExecutor | None = None,
                       instance: Any = None, actor: ActorState | None = None):
        t_args0 = time.perf_counter()
        try:
            refused = None if instance is not None else self._open_device_lane()
            if refused is not None:
                raise refused
            args, kwargs = self._resolve_args_in_process(spec)
            fn = None if instance is not None else self._get_callable(spec.func_id)
        except TaskError as e:
            self._fail_task(spec, e)
            return
        except BaseException as e:  # noqa: BLE001
            self._fail_task(spec, TaskError.from_exception(e, spec.name))
            return
        arg_fetch_s = time.perf_counter() - t_args0

        def run():
            from . import worker as worker_mod

            from ray_tpu.util import tracing

            tok = worker_mod._running_task.set(spec.task_id)
            tracer = None
            # register() immediately precedes the try whose finally
            # unregisters (see worker._execute): no stale-mapping window.
            self._device_interrupts.register(spec.task_id.binary())
            t_run0 = time.perf_counter()
            try:
                tracer = (tracing.task_span(f"task::{spec.name}::execute",
                                            spec.trace_ctx,
                                            attributes={"lane": "device"})
                          if spec.trace_ctx is not None else None)
                if instance is not None:
                    method = getattr(instance, spec.method_name)
                    return (True, method(*args, **kwargs))
                return (True, fn(*args, **kwargs))
            except BaseException as e:  # noqa: BLE001
                if tracer is not None:
                    tracer.error(e)
                return (False, TaskError.from_exception(e, spec.name))
            finally:
                spec._exec_s = time.perf_counter() - t_run0
                self._device_interrupts.unregister(spec.task_id.binary())
                worker_mod._running_task.reset(tok)
                if tracer is not None:
                    tracer.finish()
                    # The node process is not a worker: route its spans
                    # into the node table itself so multi-node traces
                    # include device-lane work.
                    self.trace_spans.extend(tracing.drain_local_spans())

        ph = self._dispatch_phases(spec)
        # In-process arg resolution IS the device lane's arg-fetch phase
        # (no deserialization for passthrough values — that's the point).
        ph["arg_fetch"] = arg_fetch_s
        self._event(spec, "RUNNING", worker="device", phases=ph)
        fut = (pool or self.device_pool).submit(run)

        def done(f):
            try:
                ok, value = f.result()
            except BaseException as e:  # noqa: BLE001 - an injected cancel
                # can land OUTSIDE run()'s try (e.g. in its finally); the
                # return objects must still resolve or the caller's get
                # blocks forever and actor slots leak.
                ok = False
                value = (e if isinstance(e, TaskError)
                         else TaskError.from_exception(e, spec.name))
            def finish():
                if actor is not None:
                    actor.inflight -= 1
                    self._pump_actor(actor)
                self.cancelled.discard(spec.task_id)  # cancel raced done
                rids = spec.return_ids()
                if not ok:
                    # Same retry semantics as the CPU lane.
                    if (spec.retry_exceptions and spec.max_retries > 0
                            and spec.actor_id is None):
                        spec.max_retries -= 1
                        self.counters["tasks_retried"] += 1
                        self.pending_cpu.append(spec)
                        self._gauge_queues()
                        self._kick()
                        return
                    self._fail_task(spec, value)
                    return
                try:
                    if spec.num_returns == 1:
                        self.mark_ready_value(rids[0], value)
                    else:
                        vals = list(value)
                        if len(vals) != len(rids):
                            raise TypeError(
                                f"declared num_returns={len(rids)} but task "
                                f"returned {len(vals)} values")
                        for rid, v in zip(rids, vals):
                            self.mark_ready_value(rid, v)
                except BaseException as e:  # noqa: BLE001
                    self._fail_task(spec, TaskError.from_exception(e, spec.name))
                    return
                self._release_deps(spec)
                self.counters["tasks_finished"] += 1
                phases = dict(getattr(spec, "_phases", None) or {})
                exec_s = getattr(spec, "_exec_s", None)
                if exec_s is not None:
                    phases["execute"] = exec_s
                self._observe_phases(phases)
                self._event(spec, "FINISHED", worker="device",
                            phases=phases or None)
            self.loop.call_soon_threadsafe(finish)

        fut.add_done_callback(done)

    # ------------------------------------------------------------------
    # Remote execution (owner side)
    # ------------------------------------------------------------------
    async def _await_deps(self, spec: TaskSpec):
        """Wait until every dep is terminal; raises the first dep error."""
        for dep in spec.dependencies():
            st = await self.wait_object(dep)
            if st.status == ERROR:
                raise_stored(st.error)

    def _resolved_copy(self, spec: TaskSpec) -> tuple:
        """(spec copy, ref_sources): small REF args resolve to inline value
        blobs; large ones stay as REFs with our address recorded in
        ref_sources so the executor pulls them chunked from us instead of
        shipping multi-MB blobs inside the forward frame (reference: task
        args above max_direct_call_object_size go through the object
        plane, not the task spec). Deps must be terminal."""
        import copy as _copy

        ref_sources: dict = {}

        def enc(a):
            if a[0] != REF:
                return a
            st = self.objects[a[1]]
            if st.status == ERROR:
                raise_stored(st.error)
            form = self.materialize_for_ipc(a[1])
            if (form[0] == "shm" and st.size >
                    self.cfg.object_transfer_min_chunked_bytes):
                ref_sources[a[1].binary()] = list(self.peer_address)
                return a
            if form[0] == "bytes":
                return (VAL, form[1])
            return (VAL, self._materialize_blob(a[1]))

        out = _copy.copy(spec)
        out.args = [enc(a) for a in spec.args]
        out.kwargs = {k: enc(v) for k, v in spec.kwargs.items()}
        return out, ref_sources

    def _materialize_blob(self, oid: ObjectID) -> bytes:
        """Serialized bytes of a READY object (from memory store or shm)."""
        st = self.objects[oid]
        if st.location == "shm":
            mv = self.shm.get(oid)
            if mv is None:
                raise ObjectLostError(
                    f"object {oid.hex()[:16]} missing from store")
            return bytes(mv)
        kind, val = st.value
        return val if kind == "bytes" else serialization.serialize(val)

    def _ingest_result_blob(self, rid: ObjectID, blob: bytes):
        if len(blob) > self.cfg.max_inline_object_size:
            self.shm.put(rid, blob)
            self.mark_ready_shm(rid, len(blob))
        else:
            self.mark_ready_bytes(rid, blob)

    async def _execute_remotely(self, spec: TaskSpec,
                                exclude: frozenset | set = frozenset(),
                                pin_node: NodeID | None = None):
        """Place a spec on another node via the head and run it there.

        The full round trip: resolve deps locally -> head picks a node ->
        dial the node -> ``remote_execute`` -> ingest result blobs. Node
        death mid-flight retries elsewhere (plain tasks) or defers to the
        actor-restart path.
        """
        exclude = set(exclude)
        try:
            await self._await_deps(spec)
            payload_spec, ref_sources = self._resolved_copy(spec)
        except TaskError as e:
            self._fail_task(spec, e)
            return
        except BaseException as e:  # noqa: BLE001
            self._fail_task(spec, TaskError.from_exception(e, spec.name))
            return
        # Ensure the function is fetchable cluster-wide before forwarding.
        blob = self.functions.get(spec.func_id)
        if blob is not None:
            try:
                await self.head.export_function(spec.func_id, blob)
            except (ConnectionLost, RpcTimeout, OSError):
                pass

        while True:
            if pin_node is not None:
                gone = pin_node in self.dead_nodes
                addr = None
                if not gone:
                    addr = (self.peer_address if pin_node == self.node_id
                            else await self._node_address(pin_node))
                if gone or addr is None:
                    if spec.strategy.kind == "node" and spec.strategy.soft:
                        # Soft affinity: preferred node is gone — fall
                        # back to normal placement (reference:
                        # node_affinity_scheduling_policy.h soft).
                        pin_node = None
                        continue
                    self._fail_task(spec, WorkerCrashedError(
                        task_name=spec.name) if gone else TaskError(
                        f"node {pin_node.hex()[:12]} is not in the cluster"))
                    return
                target, address = pin_node, addr
            else:
                sched_t0 = time.monotonic()
                try:
                    placed = await self.head.schedule(
                        spec.resources, spec.strategy.kind,
                        [n.binary() for n in exclude],
                        labels_hard=spec.strategy.labels_hard,
                        labels_soft=spec.strategy.labels_soft)
                except (ConnectionLost, RpcTimeout, OSError):
                    placed = None
                # queued-at-head → scheduled-to-node: the placement
                # round-trip is this attempt's schedule phase. It rides
                # the (pickled) spec to the executor, whose RUNNING
                # event folds it into the task's phase ledger.
                spec._sched_rtt = (getattr(spec, "_sched_rtt", 0.0)
                                   + (time.monotonic() - sched_t0))
                if placed is None:
                    # Nothing feasible right now: park and retry (nodes may
                    # join / free up) — reference keeps infeasible tasks
                    # queued rather than failing them.
                    self._pending_remote.append((spec, frozenset(exclude)))
                    return
                target = NodeID(placed["node_id"])
                address = placed["address"]
            if target == self.node_id:
                self._enqueue_local(spec)
                return
            try:
                conn = await self._peer_conn(target, address)
                rtt = getattr(spec, "_sched_rtt", None)
                if rtt is not None:
                    # payload_spec was copied before the placement loop —
                    # re-stamp so the measured RTT travels with it.
                    payload_spec._sched_rtt = rtt
                self._event(spec, "FORWARDED",
                            worker=f"node:{target.hex()[:8]}",
                            phases=({"schedule": rtt}
                                    if rtt is not None else None))
                reply = await conn.call("remote_execute", {
                    "spec": payload_spec,
                    # Log-routing owner: inherit the originating driver's
                    # node for re-forwarded / nested specs (ADVICE r4).
                    "owner": getattr(spec, "_owner_node", None)
                    or self.node_id.binary(),
                    "ref_sources": ref_sources,
                })
            except (ConnectionLost, RpcTimeout, OSError):
                self.counters["remote_forward_failures"] += 1
                if spec.actor_id is not None and not spec.is_actor_creation:
                    # Actor call: restart is the actor FSM's job.
                    self._fail_task(spec, ActorDiedError(
                        "actor node died mid-call", task_name=spec.name))
                    return
                if spec.max_retries > 0 or spec.is_actor_creation:
                    if not spec.is_actor_creation:
                        spec.max_retries -= 1
                    exclude.add(target)
                    if pin_node is not None:
                        pin_node = None  # pinned node is gone; re-place
                    continue
                self._fail_task(spec, WorkerCrashedError(task_name=spec.name))
                return
            await self._handle_remote_reply(spec, reply)
            return

    async def _handle_remote_reply(self, spec: TaskSpec, reply: dict):
        rids = spec.return_ids()
        err = reply.get("error")
        if err is not None:
            for rid in rids:
                self.mark_error(rid, err if isinstance(err, TaskError)
                                else TaskError(str(err)))
            self._release_deps(spec)
            self.counters["tasks_failed"] += 1
            self._event(spec, "FAILED")
            return
        results = reply["results"]
        exec_addr = tuple(reply["addr"]) if reply.get("addr") else None
        nested_per = reply.get("nested_refs") or [()] * len(rids)
        for rid, blob, inner in zip(rids, results, nested_per):
            # Our copy of the result pins the refs inside it, exactly as
            # the executor's copy did (registered BEFORE the executor
            # releases its own pins via the decref notify below).
            self._attach_inner_refs(rid, inner)
            if isinstance(blob, tuple) and blob[0] == "ref":
                # Big result: pull it chunked from the executing node, then
                # release the transfer pin it kept for us.
                await self.ensure_object(rid, exec_addr)
                try:
                    conn = await self._addr_conn(exec_addr)
                    await conn.notify("decref", rid.binary())
                except (ConnectionLost, RpcTimeout, OSError):
                    pass
            else:
                self._ingest_result_blob(rid, blob)
        self._release_deps(spec)
        self.counters["tasks_finished"] += 1
        self.counters["tasks_finished_remote"] += 1
        self._event(spec, "FINISHED")

    # -- remote actors (owner side) -------------------------------------
    def _create_actor_remotely(self, spec: TaskSpec):
        """Place an actor whose resources this node can't satisfy.
        The RemoteActorEntry registers SYNCHRONOUSLY (submission is
        fire-and-forget: the creating client's very next call_soon may
        be a method call, which must queue on the entry rather than
        fall into the unknown-actor path); placement runs async."""
        entry = RemoteActorEntry(
            actor_id=spec.actor_id, node_id=NodeID.nil(), address=(),
            creation_spec=spec, state="RESTARTING",
            ready=asyncio.Event())
        self.remote_actors[spec.actor_id] = entry
        self.spawn(self._place_remote_actor(entry, first=True))

    async def _place_remote_actor(self, entry: RemoteActorEntry,
                                  first: bool = False,
                                  exclude: set | None = None):
        spec = entry.creation_spec
        exclude = set(exclude or ())
        try:
            await self._await_deps(spec)
            payload_spec, ref_sources = self._resolved_copy(spec)
        except BaseException as e:  # noqa: BLE001
            err = e if isinstance(e, TaskError) else \
                TaskError.from_exception(e, spec.name)
            entry.state = "DEAD"
            entry.death_cause = str(err)
            self._fail_task(spec, err)
            self._fail_remote_actor_queue(entry)
            return
        blob = self.functions.get(spec.func_id)
        if blob is not None:
            try:
                await self.head.export_function(spec.func_id, blob)
            except (ConnectionLost, RpcTimeout, OSError):
                pass
        pin = (NodeID(spec.strategy.node_id)
               if spec.strategy.kind == "node" and spec.strategy.node_id
               else None)
        while True:
            if pin is not None:
                addr = await self._node_address(pin)
                if addr is None:
                    if spec.strategy.soft:
                        # Soft affinity: preferred node is gone — place
                        # the actor like any other creation.
                        pin = None
                        continue
                    err = ActorDiedError(
                        f"actor pinned to node {pin.hex()[:12]}, which is "
                        f"not in the cluster", task_name=spec.name)
                    entry.state = "DEAD"
                    entry.death_cause = str(err)
                    self._fail_task(spec, err)
                    self._fail_remote_actor_queue(entry)
                    return
                placed = {"node_id": pin.binary(), "address": addr}
            else:
                try:
                    placed = await self.head.schedule(
                        spec.resources, spec.strategy.kind,
                        [n.binary() for n in exclude],
                        labels_hard=spec.strategy.labels_hard,
                        labels_soft=spec.strategy.labels_soft)
                except (ConnectionLost, RpcTimeout, OSError):
                    placed = None
                if placed is None:
                    await asyncio.sleep(0.25)
                    if self._closing:
                        return
                    continue
            if entry.state == "DEAD":
                # Killed mid-placement (kill_actor_anywhere marked the
                # entry while we awaited the head): placing now would
                # RESURRECT the actor and leak its lifetime resources.
                if entry.ready is not None:
                    entry.ready.set()  # release any parked pump
                return
            target = NodeID(placed["node_id"])
            if target == self.node_id:
                # Became feasible locally (e.g. the blocking resource was
                # freed): fall back to the local actor path — and HAND
                # OVER the method calls already queued on the remote
                # entry (they'd be silently dropped otherwise; the
                # local placeholder from _enqueue_local queues them
                # behind the in-flight construction).
                del self.remote_actors[entry.actor_id]
                self._enqueue_local(spec)
                for queued in entry.queue:
                    self._submit_actor_task(queued)
                entry.queue.clear()
                # A pump parked on ready.wait() must drain and exit
                # (its queue is empty now); DEAD + set() releases it.
                entry.state = "DEAD"
                entry.death_cause = "moved to the local actor path"
                if entry.ready is not None:
                    entry.ready.set()
                return
            try:
                conn = await self._peer_conn(target, placed["address"])
                reply = await conn.call("remote_execute", {
                    "spec": payload_spec,
                    "owner": getattr(spec, "_owner_node", None)
                    or self.node_id.binary(),
                    "ref_sources": ref_sources})
            except (ConnectionLost, RpcTimeout, OSError):
                exclude.add(target)
                # A pinned target stays the same next iteration (it is
                # ALIVE at the head until the heartbeat monitor rules);
                # back off instead of hammering the head's directory.
                await asyncio.sleep(0.25)
                if self._closing:
                    return
                continue
            err = reply.get("error")
            if err is not None:
                entry.state = "DEAD"
                entry.death_cause = str(err)
                self._fail_task(spec, err if isinstance(err, TaskError)
                                else ActorDiedError(str(err)))
                self._fail_remote_actor_queue(entry)
                return
            if entry.state == "DEAD":
                # Killed while the remote creation ran: don't overwrite
                # DEAD with ALIVE — kill the freshly-created instance
                # on its node instead.
                try:
                    await conn.notify("kill_actor", entry.actor_id.binary())
                except (ConnectionLost, RpcTimeout, OSError):
                    pass
                if entry.ready is not None:
                    entry.ready.set()
                return
            entry.node_id = target
            entry.address = tuple(placed["address"])
            entry.state = "ALIVE"
            if entry.ready is not None:
                entry.ready.set()
            if first:
                # Creation return = handle-ready signal (same contract as
                # the local path).
                self.mark_ready_value(spec.return_ids()[0], None)
                self._release_deps(spec)
            try:
                await self.head.record_actor_node(entry.actor_id, target)
            except (ConnectionLost, RpcTimeout, OSError):
                pass
            self._pump_remote_actor(entry)
            return

    def _enqueue_remote_actor_task(self, entry: RemoteActorEntry,
                                   spec: TaskSpec):
        if entry.state == "DEAD":
            self._fail_task(spec, ActorDiedError(
                f"actor is dead: {entry.death_cause}", task_name=spec.name))
            return
        entry.queue.append(spec)
        self._pump_remote_actor(entry)

    def _pump_remote_actor(self, entry: RemoteActorEntry):
        if entry.pumping or entry.state == "DEAD":
            return
        entry.pumping = True
        self.spawn(self._remote_actor_pump(entry))

    async def _remote_actor_pump(self, entry: RemoteActorEntry):
        """Forward queued actor tasks in submission order. Requests are
        written sequentially (ordering) but replies are awaited out of band
        up to the actor's max_concurrency (pipelining)."""
        try:
            while entry.queue and not self._closing:
                if entry.state == "RESTARTING" and entry.ready is not None:
                    await entry.ready.wait()
                if entry.state == "DEAD":
                    self._fail_remote_actor_queue(entry)
                    return
                spec = entry.queue.popleft()
                try:
                    await self._await_deps(spec)
                    payload_spec, ref_sources = self._resolved_copy(spec)
                except BaseException as e:  # noqa: BLE001
                    err = e if isinstance(e, TaskError) else \
                        TaskError.from_exception(e, spec.name)
                    self._fail_task(spec, err)
                    continue
                try:
                    conn = await self._peer_conn(entry.node_id, entry.address)
                    fut = asyncio.ensure_future(conn.call("remote_execute", {
                        "spec": payload_spec,
                        "owner": getattr(spec, "_owner_node", None)
                        or self.node_id.binary(),
                        "ref_sources": ref_sources}))
                except (ConnectionLost, RpcTimeout, OSError):
                    self._fail_task(spec, ActorDiedError(
                        "actor node unreachable", task_name=spec.name))
                    continue
                # Let the write go out before sending the next (ordering);
                # the reply resolves in its own task (pipelining).
                await asyncio.sleep(0)
                self.spawn(self._finish_remote_actor_task(
                    entry, spec, fut))
        finally:
            entry.pumping = False
            if entry.queue and entry.state != "DEAD":
                self._pump_remote_actor(entry)

    async def _finish_remote_actor_task(self, entry: RemoteActorEntry,
                                        spec: TaskSpec, fut):
        try:
            reply = await fut
        except (ConnectionLost, RpcTimeout, OSError):
            self._fail_task(spec, ActorDiedError(
                "actor node died mid-call", task_name=spec.name))
            return
        await self._handle_remote_reply(spec, reply)

    def _fail_remote_actor_queue(self, entry: RemoteActorEntry):
        while entry.queue:
            spec = entry.queue.popleft()
            self._fail_task(spec, ActorDiedError(
                f"actor is dead: {entry.death_cause}", task_name=spec.name))

    async def _remote_actor_died(self, entry: RemoteActorEntry, cause: str):
        spec = entry.creation_spec
        can_restart = (spec is not None
                       and entry.num_restarts < spec.max_restarts)
        if can_restart:
            entry.state = "RESTARTING"
            entry.num_restarts += 1
            entry.ready = asyncio.Event()
            self.counters["actors_restarted"] += 1
            await self._place_remote_actor(
                entry, exclude={entry.node_id})
        else:
            entry.state = "DEAD"
            entry.death_cause = cause
            if self.head is not None and spec is not None \
                    and spec.actor_name:
                try:
                    await self.head.unregister_named_actor(
                        spec.actor_name, entry.actor_id)
                except (ConnectionLost, RpcTimeout, OSError):
                    pass
            self._fail_remote_actor_queue(entry)

    # ------------------------------------------------------------------
    # Peer RPC (executor side + object plane)
    # ------------------------------------------------------------------
    async def _handle_peer_rpc(self, conn: ServerConn, method: str,
                               payload: Any):
        if method == "remote_execute":
            return await self._remote_execute(payload)
        if method == "stacks":
            return await self.collect_stacks()
        if method == "profile":
            p = payload if isinstance(payload, dict) else {}
            return await self.collect_profile(
                float(p.get("duration_s", 5.0)), float(p.get("hz", 99.0)))
        if method == "device_profile":
            p = payload if isinstance(payload, dict) else {}
            return await self.collect_device_profile(
                float(p.get("duration_s", 2.0)), float(p.get("hz", 99.0)))
        if method == "flight_records":
            p = payload if isinstance(payload, dict) else {}
            return await self.collect_flight_records(
                p.get("tail", 256), bool(p.get("stacks", True)))
        if method == "clock_probe":
            # Clock-alignment anchor for merged traces: the caller
            # halves the RTT around this to estimate our wall-clock
            # offset (NTP-style midpoint).
            return {"t_wall": time.time()}
        if method == "heap":
            p = payload if isinstance(payload, dict) else {}
            return await self.collect_heap(int(p.get("top_n", 25)))
        if method == "logs":
            return self.collect_logs(payload.get("tail_bytes", 16_384)
                                     if isinstance(payload, dict) else 16_384)
        if method == "fetch_object":
            oid = ObjectID(payload["oid"])
            st = await self.wait_object(oid, payload.get("timeout"))
            if st.status == PENDING:
                return ("timeout",)
            if st.status == ERROR:
                return ("err", st.error)
            try:
                return ("b", self._materialize_blob(oid))
            except ObjectLostError as e:
                # Serve-side loss: reconstruct from lineage, then retry once.
                try:
                    if await self.recover_object(oid, payload.get("timeout")):
                        st = self.objects.get(oid)
                        if st is None:
                            return ("err", e)
                        if st.status == ERROR:
                            return ("err", st.error)
                        return ("b", self._materialize_blob(oid))
                except ObjectLostError as e2:
                    e = e2
                return ("err", e)
        if method == "fetch_meta":
            # First leg of a chunked pull: resolves to the object inline
            # (small), or to {size, holders, serving} for a chunked pull
            # (reference: the pull manager asking the directory + owner).
            oid = ObjectID(payload["oid"])
            st = await self.wait_object(oid, payload.get("timeout"))
            if st.status == PENDING:
                return ("timeout",)
            if st.status == ERROR:
                return ("err", st.error)
            try:
                form = self.materialize_for_ipc(oid)
            except (KeyError, ObjectLostError) as e:
                # Serve-side loss: reconstruct from lineage, then retry once.
                try:
                    if await self.recover_object(oid, payload.get("timeout")):
                        st = self.objects.get(oid)
                        if st is None:
                            return ("err", ObjectLostError(str(e)))
                        if st.status == ERROR:
                            return ("err", st.error)
                        form = self.materialize_for_ipc(oid)
                    else:
                        return ("err", ObjectLostError(str(e)))
                except (KeyError, ObjectLostError) as e2:
                    return ("err", ObjectLostError(str(e2)))
            if form[0] == "err":
                return form
            if form[0] == "bytes":
                return ("b", form[1])
            # shm-resident: small ones still ride one frame
            st = self.objects[oid]
            if st.size <= self.cfg.object_transfer_min_chunked_bytes:
                try:
                    return ("b", self._materialize_blob(oid))
                except ObjectLostError as e:
                    return ("err", e)
            holders = [list(a) for a in (st.holders or ())]
            return ("meta", {"size": st.size, "holders": holders})
        if method == "fetch_begin":
            # msgpack-schema'd method: plain-data responses only (errors
            # as strings — the puller falls back to the owner on any err).
            oid = ObjectID(payload["oid"])
            st = self.objects.get(oid)
            if st is None or st.status != READY:
                return ("err", f"object {oid.hex()[:16]} not held here")
            if (not payload.get("force")
                    and self._serving_count(oid) >=
                    self.cfg.object_transfer_max_pushes):
                # Push cap (enforced here, not just advertised in meta, so
                # simultaneous pullers can't all slip past it).
                return ("busy",)
            try:
                form = self.materialize_for_ipc(oid)
            except (KeyError, ObjectLostError) as e:
                return ("err", str(e))
            if form[0] == "err":
                return ("err", str(form[1]))
            size = len(form[1]) if form[0] == "bytes" else st.size
            self._serving.setdefault(oid, []).append(time.time())
            self.counters["object_transfers_served"] += 1
            # Third field: this node's raw bulk-transfer port (sendfile
            # lane); pullers prefer it and fall back to chunked RPC.
            return ("ok", size, getattr(self, "bulk_port", 0))
        if method == "fetch_chunk":
            from .rpc import RawBytes

            oid = ObjectID(payload["oid"])
            st = self.objects.get(oid)
            if st is None:
                return ("err", f"object {oid.hex()[:16]} not held here")
            off, ln = payload["off"], payload["len"]
            if st.location == "shm":
                mv = self.shm.get(oid)
                if mv is None:
                    return ("err",
                            f"object {oid.hex()[:16]} missing from store")
                # ENC_RAW reply: the socket reads straight out of the
                # store mmap — no msgpack pack, no frame concat.
                return RawBytes(mv[off:off + ln])
            kind, val = st.value
            blob = val if kind == "bytes" else serialization.serialize(val)
            return RawBytes(memoryview(blob)[off:off + ln])
        if method == "fetch_end":
            ts = self._serving.get(ObjectID(payload))
            if ts:
                ts.pop(0)
                if not ts:
                    self._serving.pop(ObjectID(payload), None)
            return True
        if method == "copy_added":
            st = self.objects.get(ObjectID(payload["oid"]))
            if st is not None and st.status == READY:
                if st.holders is None:
                    st.holders = {}
                st.holders[tuple(payload["addr"])] = payload["node_id"]
            return True
        if method == "copy_removed":
            st = self.objects.get(ObjectID(payload["oid"]))
            if st is not None and st.holders:
                st.holders.pop(tuple(payload["addr"]), None)
            return True
        if method == "borrow_add":
            # A remote node now holds references to an object we own:
            # defer its free until that node releases (reference:
            # reference_count.h borrower registration / WaitForRefRemoved).
            st = self.objects.get(ObjectID(payload["oid"]))
            if st is None:
                return False  # already freed; borrower's fetches will fail
            key = tuple(payload["addr"])
            if st.borrowers is None:
                st.borrowers = {}
            if key not in st.borrowers:
                st.borrowers[key] = payload["node_id"]
                st.refcount += 1
            return True
        if method == "borrow_release":
            oid = ObjectID(payload["oid"])
            st = self.objects.get(oid)
            if (st is not None and st.borrowers
                    and st.borrowers.pop(tuple(payload["addr"]), None)
                    is not None):
                self.decref(oid)
            return True
        if method == "incref":
            self.incref(ObjectID(payload))
            return True
        if method == "decref":
            # Peer decref notifies release big-result transfer pins (the
            # only peer-plane sender, remote task completion above). Only
            # drop a count if WE still held the pin: if the TTL sweep
            # already reclaimed it, the late notify must be a no-op or a
            # live object loses a second count (ADVICE r3).
            if self._result_pins.pop(ObjectID(payload), None) is not None:
                self.decref(ObjectID(payload))
            return True
        if method == "free_object":
            # A consumer elsewhere finished with an object WE own:
            # eager-release the value (ray_tpu.free across nodes).
            self.free_object(ObjectID(payload))
            return True
        if method == "kill_actor":
            self.kill_actor(ActorID(payload))
            return True
        if method == "ping":
            return "pong"
        if method == "state":
            return self.state_snapshot(
                include_events=bool((payload or {}).get("events")),
                light=bool((payload or {}).get("light")),
                tables=(payload or {}).get("tables"))
        raise RuntimeError(f"unknown peer rpc: {method}")

    async def _remote_execute(self, payload: dict) -> dict:
        """Run a forwarded spec locally and reply with result blobs. The
        owner keeps the authoritative object states; our local copies are
        freed once the reply ships."""
        spec: TaskSpec = payload["spec"]
        spec._remote = True
        # Owner attribution for log routing: this spec's output belongs
        # on the submitting driver's console (reference: per-job log
        # subscription), not on every driver's.
        spec._owner_node = payload.get("owner")
        # Large REF args arrive unresolved with their source addresses:
        # pull them chunked into the local store before/while the task is
        # queued (the dispatch path waits on local dep readiness).
        for dep_bin, src in (payload.get("ref_sources") or {}).items():
            self.spawn(
                self.ensure_object(ObjectID(dep_bin), tuple(src)))
        self.counters["remote_tasks_received"] += 1
        if (self._is_device_task(spec) and spec.func_id is not None
                and spec.func_id not in self.functions
                and self.head is not None):
            # The device lane unpickles the callable in THIS process
            # (_get_callable); a forwarded spec's function was exported
            # to the head by its owner, so pull it here first — CPU-lane
            # workers do the same through their fetch_function RPC.
            blob = await self.head.fetch_function(spec.func_id)
            if blob is not None:
                self.functions[spec.func_id] = blob
        rids = self.submit(spec)
        results = []
        keep = set()
        err = None
        for rid in rids:
            st = await self.wait_object(rid)
            if st.status == ERROR:
                err = st.error
                break
        inner_per = []
        if err is None:
            try:
                for rid in rids:
                    form = self.materialize_for_ipc(rid)
                    if form[0] == "err":
                        err = form[1]
                        break
                    st = self.objects[rid]
                    # Inner-ref info travels with the result so the owner's
                    # copy pins the same refs our copy does.
                    inner_per.append(list(st.inner_refs or ()))
                    if (form[0] == "shm" and st.size >
                            self.cfg.object_transfer_min_chunked_bytes):
                        # Big result: reply with a reference — the owner
                        # pulls it chunked and then releases our pin with a
                        # decref notify (reference: large returns go through
                        # plasma + object transfer, never the reply frame).
                        # TTL-tracked: if the reply is lost and the decref
                        # never arrives, the sweep reclaims the pin.
                        results.append(("ref", st.size))
                        keep.add(rid)
                        self._result_pins[rid] = time.time()
                    else:
                        results.append(self._materialize_blob(rid))
            except BaseException as e:  # noqa: BLE001
                err = TaskError.from_exception(e, spec.name)
        if err is not None:
            # Error reply: owner will never pull — drop pins AND their
            # sweep entries, or the TTL sweep would decref a second time.
            for rid in keep:
                self._result_pins.pop(rid, None)
            keep.clear()
        if not spec.is_actor_creation:
            for rid in rids:
                if rid not in keep:
                    self.decref(rid)  # drop submitter ref; owner has its own
        if err is not None:
            return {"error": err}
        return {"results": results, "addr": list(self.peer_address),
                "nested_refs": inner_per if any(inner_per) else None}

    # ------------------------------------------------------------------
    # Actors
    # ------------------------------------------------------------------
    def _register_actor_state(self, spec: TaskSpec) -> "ActorState":
        """Idempotently insert the PENDING ActorState for a creation
        spec. Split from _create_actor so _enqueue_local can do it
        synchronously (method calls racing the creation must find the
        entry). Loop thread only."""
        actor = self.actors.get(spec.actor_id)
        if actor is not None:
            return actor
        actor = ActorState(
            actor_id=spec.actor_id,
            creation_spec=spec,
            is_device=self._is_device_task(spec),
            name=spec.actor_name,
            charged=None,
        )
        actor.ready_fut = self.loop.create_future()
        self.actors[spec.actor_id] = actor
        return actor

    async def _create_actor(self, spec: TaskSpec):
        aid = spec.actor_id
        if aid in self._killed_before_create:
            self._killed_before_create.discard(aid)
            err = ActorDiedError("actor was killed")
            placeholder = self.actors.pop(aid, None)
            if placeholder is not None:
                # Method calls may already be queued on the PENDING
                # placeholder — fail them or their callers hang.
                placeholder.state = "DEAD"
                placeholder.death_cause = str(err)
                for queued in placeholder.queue:
                    self._fail_task(queued, err)
                placeholder.queue.clear()
            self._fail_task(spec, err)
            return
        actor = self._register_actor_state(spec)
        if actor.state == "DEAD":
            # kill_actor processed the placeholder between registration
            # and this coroutine: charging resources / re-registering
            # the name now would leak both (the kill path released a
            # charge of None and already failed the queue).
            self._fail_task(spec, ActorDiedError("actor was killed"))
            return
        is_device = actor.is_device
        need = {k: v for k, v in spec.resources.items() if v > 0}
        if not is_device:
            # Lifetime reservation: park until the node has availability
            # (matches the reference's pending-actor semantics — an actor
            # whose resources are taken waits, it does not oversubscribe).
            if self._lacks_lifetime_room(need):
                self._pending_actor_creations.append(spec)
                return
            for k, v in need.items():
                self.available[k] = self.available.get(k, 0) - v
            actor.charged = need
        if spec.actor_name and self.head is not None:
            meths = spec.actor_methods or []
            try:
                ok = await self.head.register_named_actor(
                    spec.actor_name, aid, self.node_id, meths)
            except (ConnectionLost, RpcTimeout, OSError):
                ok = False
            if not ok:
                self._actor_creation_failed(
                    actor,
                    ActorDiedError(f"actor name '{spec.actor_name}' already taken"),
                )
                return
        elif self.head is not None:
            try:
                await self.head.record_actor_node(aid, self.node_id)
            except (ConnectionLost, RpcTimeout, OSError):
                pass
        await self._start_actor(actor)

    async def _start_actor(self, actor: ActorState):
        spec = actor.creation_spec
        if actor.is_device:
            try:
                refused = self._open_device_lane()
                if refused is not None:
                    raise refused
                args, kwargs = self._resolve_args_in_process(spec)
                cls = self._get_callable(spec.func_id)
            except BaseException as e:  # noqa: BLE001
                self._actor_creation_failed(actor, e)
                return
            actor.device_pool = ThreadPoolExecutor(
                max_workers=max(1, spec.max_concurrency),
                thread_name_prefix=f"actor-{actor.actor_id.hex()[:8]}",
            )

            def construct():
                try:
                    return (True, cls(*args, **kwargs))
                except BaseException as e:  # noqa: BLE001
                    return (False, TaskError.from_exception(e, spec.name))

            ok, value = await self.loop.run_in_executor(actor.device_pool, construct)
            if not ok:
                self._actor_creation_failed(actor, value)
                return
            actor.instance = value
            self._actor_alive(actor)
        else:
            owns_host = spec.resources.get("TPU_HOST", 0) > 0
            if owns_host and backend_probe.holds_chips():
                # One process per chip. This node's process counted its
                # chips in-process or has started its device lane, so it
                # holds them; a gang worker process could only fail or
                # hang opening them again.
                self._actor_creation_failed(actor, ActorDiedError(
                    "a TPU_HOST gang worker cannot start on this node: "
                    "the node process already holds the host's chips "
                    "for its device lane, and a chip belongs to one "
                    "process at a time. On one host use "
                    "scheduling_strategy='device' (JaxTrainer with "
                    "num_workers=1, use_tpu=True drives every local "
                    "chip from that process); gang workers need nodes "
                    "whose daemon did not open the chips (started with "
                    "an explicit TPU count, and no device-lane work "
                    "before the gang)."))
                return
            worker = self._spawn_worker(
                actor_id=actor.actor_id,
                preserve_platform_env=owns_host,
                runtime_env=spec.runtime_env,
            )
            actor.worker = worker
            try:
                await asyncio.wait_for(
                    worker.registered, self.cfg.worker_startup_timeout_s
                )
            except asyncio.TimeoutError:
                self._actor_creation_failed(
                    actor, ActorDiedError("actor worker failed to start")
                )
                return
            if worker.state == "DEAD":  # runtime_env setup failed
                bad = self._bad_envs.get(worker.env_id)
                self._actor_creation_failed(
                    actor, ActorDiedError(
                        f"runtime_env setup failed: "
                        f"{bad[0] if bad else 'unknown'}"))
                return
            try:
                reply = await worker.conn.call(
                    "create_actor", self._spec_for_ipc(spec)
                )
            except ConnectionLost:
                self._actor_creation_failed(
                    actor, ActorDiedError("actor worker died during __init__")
                )
                return
            if reply.get("error") is not None:
                self._actor_creation_failed(actor, reply["error"])
                return
            self._actor_alive(actor)

    def _release_actor_resources(self, actor: ActorState):
        """Return a dead actor's lifetime reservation to the pool and wake
        anything parked on it."""
        if actor.charged:
            for k, v in actor.charged.items():
                self.available[k] = self.available.get(k, 0) + v
            actor.charged = None
            self._kick()

    def _lacks_lifetime_room(self, resources: dict) -> bool:
        return any(self.available.get(k, 0) < v
                   for k, v in resources.items() if v > 0)

    def _retry_pending_actor_creations(self):
        if not self._pending_actor_creations:
            return
        pending = list(self._pending_actor_creations)
        self._pending_actor_creations.clear()
        for spec in pending:
            self.spawn(self._create_actor(spec))

    def _actor_alive(self, actor: ActorState):
        if actor.state == "DEAD":
            # kill() landed while the creation was in flight (its lifetime
            # reservation is already released) — tear down what just came
            # up instead of resurrecting a zombie, and resolve the creation
            # return so handle waiters don't hang.
            if actor.worker is not None:
                self._kill_worker(actor.worker)
            if actor.device_pool is not None:
                actor.device_pool.shutdown(wait=False)
                actor.instance = None
            self._fail_task(actor.creation_spec,
                            ActorDiedError("actor was killed during creation"))
            return
        actor.state = "ALIVE"
        spec = actor.creation_spec
        self._event(spec, "FINISHED",
                    worker=("device" if actor.is_device else
                            f"worker:{actor.worker.proc.pid}"))
        # The creation "return" is the handle-ready signal.
        self.mark_ready_value(spec.return_ids()[0], None)
        if actor.ready_fut and not actor.ready_fut.done():
            actor.ready_fut.set_result(None)
        self._pump_actor(actor)

    def _unregister_actor(self, actor: ActorState):
        """Drop the actor's directory entries at the head. Unregistration is
        keyed by actor id, so a duplicate-name failure never unregisters
        the original name holder."""
        if self.head is None:
            return

        async def do():
            try:
                if actor.name:
                    await self.head.unregister_named_actor(
                        actor.name, actor.actor_id)
            except (ConnectionLost, RpcTimeout, OSError):
                pass

        self.spawn(do())

    def _actor_creation_failed(self, actor: ActorState, err):
        if not isinstance(err, TaskError):
            err = ActorDiedError(f"actor creation failed: {err}")
        actor.state = "DEAD"
        actor.death_cause = str(err)
        self._release_actor_resources(actor)
        self._unregister_actor(actor)
        self._fail_task(actor.creation_spec, err)
        for spec in actor.queue:
            self._fail_task(spec, ActorDiedError(str(err), task_name=spec.name))
        actor.queue.clear()

    def _submit_actor_task(self, spec: TaskSpec):
        actor = self.actors.get(spec.actor_id)
        if actor is None or actor.state == "DEAD":
            cause = actor.death_cause if actor else "unknown actor"
            self._fail_task(spec, ActorDiedError(f"actor is dead: {cause}",
                                                 task_name=spec.name))
            return
        # queue phase = time spent behind the actor's max_concurrency gate.
        spec._pending_since = time.monotonic()
        actor.queue.append(spec)
        self._pump_actor(actor)

    def _pump_actor(self, actor: ActorState):
        if actor.state != "ALIVE":
            return
        limit = max(1, actor.creation_spec.max_concurrency)
        if limit == 1 and not actor.is_device:
            # Serial worker-backed actor: pipeline up to depth calls into
            # the worker's FIFO lane — execution stays one-at-a-time and
            # in submission order, but the next call is already on the
            # worker when the current one returns (cpu-lane fast path).
            limit = max(1, self.cfg.worker_pipeline_depth)
        while actor.queue and actor.inflight < limit:
            spec = actor.queue.popleft()
            if spec.task_id in self.cancelled:
                self.cancelled.discard(spec.task_id)
                self._fail_task(spec, TaskCancelledError(task_name=spec.name))
                continue
            try:
                if not self._deps_ready(spec):
                    actor.queue.appendleft(spec)
                    # Re-pump on dep readiness via generic kick.
                    break
            except TaskError as e:
                self._fail_task(spec, e)
                continue
            actor.inflight += 1
            if actor.is_device:
                self._run_on_device(
                    spec, pool=actor.device_pool, instance=actor.instance, actor=actor
                )
            else:
                self.spawn(self._run_actor_task(actor, spec))

    async def _run_actor_task(self, actor: ActorState, spec: TaskSpec):
        worker = actor.worker
        worker.inflight[spec.task_id] = spec
        self._gauge_queues()
        self._event(spec, "RUNNING", worker=f"worker:{worker.proc.pid}",
                    phases=self._dispatch_phases(spec))
        try:
            serial = actor.creation_spec.max_concurrency <= 1
            reply = await worker.conn.call(
                "execute_task", self._spec_for_ipc(spec, serial=serial))
            self._handle_task_reply(spec, reply)
        except (ConnectionLost, OSError):
            # OSError covers the conn dying mid-WRITE (a kill landing
            # while the request frame is in flight raises
            # ConnectionResetError, not ConnectionLost) — either way the
            # worker is gone and callers' retry logic keys on
            # ActorDiedError, not a generic TaskError.
            self._fail_task(spec, ActorDiedError("actor worker died mid-call",
                                                 task_name=spec.name))
            return  # restart handled by _on_disconnect
        except TaskError as e:
            self._fail_task(spec, e)
        except BaseException as e:  # noqa: BLE001 - never leave returns pending
            self._fail_task(spec, TaskError.from_exception(e, spec.name))
        finally:
            worker.inflight.pop(spec.task_id, None)
            actor.inflight -= 1
        self._pump_actor(actor)

    async def _restart_actor(self, actor: ActorState):
        actor.state = "RESTARTING"
        actor.num_restarts += 1
        self.counters["actors_restarted"] += 1
        await self._start_actor(actor)

    def kill_actor(self, aid: ActorID, no_restart: bool = True):
        actor = self.actors.get(aid)
        if actor is None:
            # A kill can arrive while the creation is parked on resources
            # (or mid-retry between deque and task) — record it so the
            # creation can't spring to life later.
            self._killed_before_create.add(aid)
            if len(self._killed_before_create) > 4096:
                # Bounded: kills of never-created ids would otherwise
                # accumulate forever on a long-lived node.
                self._killed_before_create.pop()
            for spec in list(self._pending_actor_creations):
                if spec.actor_id == aid:
                    self._pending_actor_creations.remove(spec)
                    self._fail_task(spec, ActorDiedError("actor was killed"))
            return
        if actor.state == "DEAD":
            return
        actor.state = "DEAD"
        actor.death_cause = "killed via kill()"
        self._release_actor_resources(actor)
        self._unregister_actor(actor)
        for spec in actor.queue:
            self._fail_task(spec, ActorDiedError("actor was killed", task_name=spec.name))
        actor.queue.clear()
        if actor.worker is not None:
            self._kill_worker(actor.worker)
        if actor.device_pool is not None:
            actor.device_pool.shutdown(wait=False)
            actor.instance = None

    async def kill_actor_anywhere(self, aid: ActorID, no_restart: bool = True):
        """kill() that also reaches actors living on other nodes."""
        if aid in self.actors:
            self.kill_actor(aid, no_restart)
            return
        entry = self.remote_actors.get(aid)
        if entry is not None and entry.state != "DEAD":
            entry.state = "DEAD"
            entry.death_cause = "killed via kill()"
            self._fail_remote_actor_queue(entry)
            try:
                conn = await self._peer_conn(entry.node_id, entry.address)
                await conn.call("kill_actor", aid.binary())
            except (ConnectionLost, RpcTimeout, OSError):
                pass
            return
        # Unknown here: resolve the home node through the head.
        if self.head is not None:
            node_b = await self.head.actor_node(aid)
            if node_b is not None and NodeID(node_b) != self.node_id:
                addr = await self._node_address(NodeID(node_b))
                if addr is not None:
                    try:
                        conn = await self._peer_conn(NodeID(node_b), addr)
                        await conn.call("kill_actor", aid.binary())
                    except (ConnectionLost, RpcTimeout, OSError):
                        pass

    def _kill_worker(self, worker: WorkerHandle, force: bool = False):
        worker.state = "DEAD"
        try:
            # force => SIGKILL: the ray force-cancel contract must hold
            # even for workers that ignore/block SIGTERM.
            (worker.proc.kill if force else worker.proc.terminate)()
        except ProcessLookupError:
            pass

    # ------------------------------------------------------------------
    # Placement groups — node-side bundle reservation (the cluster-wide
    # placement decision lives in the head, gcs_placement_group_scheduler
    # equivalent; this node just sets resources aside)
    # ------------------------------------------------------------------
    async def collect_stacks(self) -> dict:
        """Stacks of this node's process and its live workers, keyed by
        'node:<id>' / 'worker:<pid>' (reference: `ray stack`). Worker
        queries run CONCURRENTLY so N hung workers cost one 5s timeout,
        not N."""
        from .stack_dump import format_stacks

        out = {f"node:{self.node_id.hex()[:12]}": format_stacks()}
        targets = [w for w in self.workers.values()
                   if w.state in ("IDLE", "BUSY") and w.conn is not None
                   and w.conn.alive]

        async def ask(w):
            try:
                return await asyncio.wait_for(
                    w.conn.call("stack_dump", None), timeout=5)
            except Exception as e:  # noqa: BLE001 - best effort
                return f"<unavailable: {e}>"

        dumps = await asyncio.gather(*(ask(w) for w in targets))
        node = self.node_id.hex()[:8]
        for w, text in zip(targets, dumps):
            # Node-qualified keys: pids are per-host, so bare pids from
            # different machines would collide in the merged view.
            out[f"worker:{node}:{w.proc.pid}"] = text
        return out

    async def collect_profile(self, duration_s: float = 5.0,
                              hz: float = 99.0) -> dict:
        """Sampled CPU profiles (folded stacks) of this node process and
        every live worker, concurrently (reference: dashboard
        CpuProfilingManager fanning py-spy over workers)."""
        from .profiler import sample_profile

        loop = self.loop

        async def me():
            # Node's own sampler runs off-loop (it sleeps).
            return await loop.run_in_executor(
                None, lambda: sample_profile(duration_s, hz))

        targets = [w for w in self.workers.values()
                   if w.state in ("IDLE", "BUSY") and w.conn is not None
                   and w.conn.alive]

        async def ask(w):
            try:
                return await asyncio.wait_for(
                    w.conn.call("profile", {"duration_s": duration_s,
                                            "hz": hz}),
                    timeout=duration_s + 10)
            except Exception as e:  # noqa: BLE001 - best effort
                return {"folded": "", "error": str(e)}

        results = await asyncio.gather(me(), *(ask(w) for w in targets))
        node = self.node_id.hex()[:8]
        out = {f"node:{self.node_id.hex()[:12]}": results[0]}
        for w, prof in zip(targets, results[1:]):
            out[f"worker:{node}:{w.proc.pid}"] = prof
        return out

    async def collect_device_profile(self, duration_s: float = 2.0,
                                     hz: float = 99.0) -> dict:
        """Device-step capture windows (perfmodel ring + host timeline +
        best-effort jax.profiler trace) of this node process and every
        live worker, concurrently — one leg of the gang-coordinated
        `rtpu profile --device` capture."""
        from .profiler import device_profile

        loop = self.loop

        async def me():
            # Off-loop: the capture window sleeps for duration_s.
            return await loop.run_in_executor(
                None, lambda: device_profile(duration_s, hz))

        targets = [w for w in self.workers.values()
                   if w.state in ("IDLE", "BUSY") and w.conn is not None
                   and w.conn.alive]

        async def ask(w):
            try:
                return await asyncio.wait_for(
                    w.conn.call("device_profile",
                                {"duration_s": duration_s, "hz": hz}),
                    timeout=duration_s + 10)
            except Exception as e:  # noqa: BLE001 - best effort
                return {"error": str(e)}

        results = await asyncio.gather(me(), *(ask(w) for w in targets))
        node = self.node_id.hex()[:8]
        out = {f"node:{self.node_id.hex()[:12]}": results[0]}
        for w, prof in zip(targets, results[1:]):
            out[f"worker:{node}:{w.proc.pid}"] = prof
        return out

    async def collect_flight_records(self, tail: Optional[int] = 256,
                                     include_stacks: bool = True) -> dict:
        """Flight-recorder ring snapshots (plus host stacks) of this
        node's process and every live worker, concurrently — the
        collection leg of the gang desync watchdog (aligned by
        parallel/flightrec.diagnose, rendered by `rtpu gang doctor`).
        The node's own snapshot covers in-process device-lane gang
        members; worker snapshots cover subprocess gang members."""
        loop = self.loop

        def me_snap():
            # sys.modules probe, NOT an import: a process that never
            # loaded the collective plane has recorded nothing, and
            # pulling jax in here just to say so would be absurd.
            fr = sys.modules.get("ray_tpu.parallel.flightrec")
            if fr is None:
                snap = {"pid": os.getpid(), "identity": {}, "entries": [],
                        "last_completed": {}, "next_seq": {},
                        "in_flight": []}
                if include_stacks:
                    from .stack_dump import format_stacks

                    snap["stacks"] = format_stacks()
                return snap
            return fr.snapshot(include_stacks=include_stacks, tail=tail)

        async def me():
            return await loop.run_in_executor(None, me_snap)

        targets = [w for w in self.workers.values()
                   if w.state in ("IDLE", "BUSY") and w.conn is not None
                   and w.conn.alive]

        async def ask(w):
            try:
                return await asyncio.wait_for(
                    w.conn.call("flight_records",
                                {"tail": tail, "stacks": include_stacks}),
                    timeout=10)
            except Exception as e:  # noqa: BLE001 - best effort
                return {"error": str(e)}

        results = await asyncio.gather(me(), *(ask(w) for w in targets))
        node = self.node_id.hex()[:8]
        out = {f"node:{self.node_id.hex()[:12]}": results[0]}
        for w, snap in zip(targets, results[1:]):
            out[f"worker:{node}:{w.proc.pid}"] = snap
        return out

    async def collect_heap(self, top_n: int = 25) -> dict:
        """tracemalloc heap snapshots of this node + workers (reference:
        MemoryProfilingManager / memray attach)."""
        from .profiler import heap_snapshot

        targets = [w for w in self.workers.values()
                   if w.state in ("IDLE", "BUSY") and w.conn is not None
                   and w.conn.alive]

        async def ask(w):
            try:
                return await asyncio.wait_for(
                    w.conn.call("heap", {"top_n": top_n}), timeout=15)
            except Exception as e:  # noqa: BLE001
                return {"error": str(e)}

        # Local snapshot off-loop: take_snapshot over a busy heap can
        # cost seconds and must not freeze scheduling/heartbeats.
        mine = self.loop.run_in_executor(None,
                                         lambda: heap_snapshot(top_n))
        dumps = await asyncio.gather(mine, *(ask(w) for w in targets))
        node = self.node_id.hex()[:8]
        out = {f"node:{self.node_id.hex()[:12]}": dumps[0]}
        for w, h in zip(targets, dumps[1:]):
            out[f"worker:{node}:{w.proc.pid}"] = h
        return out

    # -- memory pressure (reference: src/ray/common/memory_monitor.h:52 +
    # raylet worker_killing_policy*.h: under host memory pressure, kill
    # the retriable task using the most memory so the node survives and
    # the task retries elsewhere/later) ---------------------------------
    @staticmethod
    def _read_host_memory_fraction() -> float:
        """Used/total from /proc/meminfo (MemAvailable-based, the same
        signal the reference monitor uses). Tests inject a fake."""
        try:
            info = {}
            with open("/proc/meminfo") as f:
                for line in f:
                    key, _, rest = line.partition(":")
                    info[key] = int(rest.split()[0])
            total = info["MemTotal"]
            avail = info.get("MemAvailable", info.get("MemFree", total))
            return 1.0 - avail / total
        except (OSError, KeyError, ValueError, ZeroDivisionError):
            return 0.0

    @staticmethod
    def _read_worker_rss(pid: int) -> int:
        """Resident bytes of one worker (no psutil in the image)."""
        try:
            with open(f"/proc/{pid}/statm") as f:
                return int(f.read().split()[1]) * os.sysconf("SC_PAGESIZE")
        except (OSError, ValueError, IndexError):
            return 0

    async def _memory_monitor_loop(self):
        while not self._closing:
            await asyncio.sleep(self.cfg.memory_monitor_interval_s)
            try:
                usage = self._read_host_memory_fraction()
                if usage <= self.cfg.memory_usage_threshold:
                    continue
                self._kill_fattest_worker(usage)
            except Exception:  # noqa: BLE001 - the monitor must survive
                # ANY tick failure (including a broken stderr in the kill
                # path): losing one tick is fine, losing the loop is not.
                continue

    def _kill_fattest_worker(self, usage: float):
        """Victim selection (reference: RetriableFIFOWorkerKillingPolicy
        — prefer workers whose tasks can retry; among those, the largest
        RSS)."""
        candidates = []
        for w in self.workers.values():
            if w.state not in ("IDLE", "BUSY") or not w.inflight:
                continue
            retriable = all(s.max_retries > 0 and s.actor_id is None
                            for s in w.inflight.values())
            candidates.append((retriable, self._read_worker_rss(w.proc.pid),
                               w))
        if not candidates:
            return
        # Retriable victims first; largest RSS within the class.
        retriable, rss, victim = max(
            candidates, key=lambda c: (c[0], c[1]))
        for spec in victim.inflight.values():
            spec._oom_killed = True
        sys.stderr.write(
            f"memory monitor: host usage {usage:.0%} > "
            f"{self.cfg.memory_usage_threshold:.0%}; killing worker "
            f"pid={victim.proc.pid} (rss={rss / 1e6:.0f}MB, "
            f"retriable={retriable})\n")
        self.counters["workers_oom_killed"] += 1
        self._kill_worker(victim, force=True)

    async def _log_tail_loop(self):
        """Stream new worker-log lines to the driver console (reference:
        python/ray/_private/log_monitor.py tailing the session log dir,
        publishing to the driver). Lines go to the driver's STDERR with
        a (pid=…, node=…) prefix so program stdout stays clean."""
        while not self._closing:
            await asyncio.sleep(0.5)
            if not self.cfg.log_to_driver:
                continue
            batch = []
            for w in self.workers.values():
                if w.log_path is None:
                    continue
                try:
                    size = os.path.getsize(w.log_path)
                except OSError:
                    continue
                if size <= w.log_offset:
                    continue
                window = 256 * 1024
                with open(w.log_path, "rb") as f:
                    f.seek(w.log_offset)
                    data = f.read(min(size - w.log_offset, window))
                cut = data.rfind(b"\n")
                if cut < 0:
                    if len(data) < window:
                        continue  # partial line: wait for the newline
                    # A single line longer than the window would wedge
                    # the tail forever: ship the window as one chunk.
                    cut = len(data) - 1
                w.log_offset += cut + 1
                lines = data[:cut + 1].decode("utf-8", "replace").splitlines()
                batch.append({"pid": w.proc.pid, "lines": lines,
                              "owner": w.owner_node})
            if not batch:
                continue
            if (self.head is None
                    or getattr(self, "is_driver_node", False)
                    or not hasattr(self.head, "push_worker_logs")):
                # Drivers (attached or fused-head LocalHeadClient) print
                # their own workers' output locally — a driver's tasks
                # belong on THAT driver's console. Daemon nodes forward
                # to the head, which relays to every attached driver.
                _print_worker_logs(self.node_id.hex(), batch)
            else:
                try:
                    await self.head.push_worker_logs(
                        {"node_id": self.node_id.binary(),
                         "entries": batch})
                except (ConnectionLost, RpcTimeout, OSError):
                    pass  # head restarting; lines already in the file

    def collect_logs(self, tail_bytes: int = 16_384) -> dict:
        """Last ``tail_bytes`` of every live worker's captured log,
        keyed like collect_stacks (reference: `ray logs`)."""
        out = {}
        node = self.node_id.hex()[:8]
        for w in self.workers.values():
            if not w.log_path:
                continue
            try:
                size = os.path.getsize(w.log_path)
                with open(w.log_path, "rb") as f:
                    f.seek(max(0, size - tail_bytes))
                    out[f"worker:{node}:{w.proc.pid}"] = \
                        f.read().decode("utf-8", "replace")
            except OSError:
                continue
        return out

    def directory_sync(self) -> dict:
        """What this node contributes to the head's directory tables on
        (re-)registration: live named actors, homes of actors it hosts,
        and placement-group bundles it still has reserved."""
        named = {}
        actor_ids = []
        for a in self.actors.values():
            if a.state not in ("ALIVE", "PENDING", "RESTARTING"):
                continue
            actor_ids.append(a.actor_id.binary())
            name = getattr(a.creation_spec, "actor_name", None)
            if name:
                named[name] = {
                    "actor_id": a.actor_id.binary(),
                    "methods": a.creation_spec.actor_methods or []}
        return {
            "named_actors": named,
            "actor_ids": actor_ids,
            "reservations": [
                {"pg_id": pg_id.binary(), "bundle_index": idx,
                 "resources": dict(pool.total)}
                for (pg_id, idx), pool in self.bundles.items()],
        }

    def reserve_bundle(self, pg_id: PlacementGroupID, bundle_index: int,
                       resources: dict):
        self.bundles[(pg_id, bundle_index)] = BundlePool(
            total=dict(resources), available=dict(resources))
        # Reserved resources leave the general pool so ordinary tasks
        # cannot oversubscribe them.
        for k, v in resources.items():
            self.available[k] = self.available.get(k, 0) - v

    def release_bundle(self, pg_id: PlacementGroupID, bundle_index: int):
        pool = self.bundles.pop((pg_id, bundle_index), None)
        if pool is not None:
            for k, v in pool.total.items():
                self.available[k] = self.available.get(k, 0) + v
        self._kick()

    # ------------------------------------------------------------------
    # RPC handling (worker -> node service)
    # ------------------------------------------------------------------
    async def _handle_rpc(self, conn: ServerConn, method: str, payload: Any):
        if method == "register":
            wid = WorkerID.from_hex(payload["worker_id"])
            w = self.workers.get(wid)
            if w is None:
                raise RuntimeError(f"unknown worker {payload['worker_id']}")
            w.conn = conn
            conn.meta["worker"] = w
            setup_error = payload.get("setup_error")
            if setup_error is not None:
                # The worker could not wear its runtime env; it exits after
                # this reply. Poison the env so queued tasks fail fast with
                # a typed error instead of respawning forever (reference:
                # RuntimeEnvSetupError surfaced to the submitter).
                self._bad_envs[w.env_id] = (setup_error, time.monotonic())
                w.state = "DEAD"
                if w.registered and not w.registered.done():
                    w.registered.set_result(None)  # waiters check state
                # Fail the tasks that wanted this env NOW (they triggered
                # the spawn); the timed poison only fail-fasts future
                # submissions, so a permanent failure can't respawn-loop.
                msg = f"runtime_env setup failed on this node: {setup_error}"
                keep = collections.deque()
                while self.pending_cpu:
                    spec = self.pending_cpu.popleft()
                    if spec.env_id == w.env_id:
                        self._fail_task(spec, TaskError(
                            msg, cause=RuntimeEnvSetupError(msg),
                            task_name=spec.name))
                    else:
                        keep.append(spec)
                self.pending_cpu = keep
                self._gauge_queues()
                self._kick()
                return {"session_id": self.session_id,
                        "peer_address": self.peer_address}
            if w.actor_id is None:
                w.state = "IDLE"
                w.last_idle = time.monotonic()
                self.idle_workers.append(w)
            else:
                w.state = "BUSY"  # dedicated actor worker
            if w.registered and not w.registered.done():
                w.registered.set_result(None)
            self._kick()
            return {"session_id": self.session_id,
                    "peer_address": self.peer_address}

        if method == "fetch_function":
            blob = self.functions.get(payload)
            if blob is None and self.head is not None:
                blob = await self.head.fetch_function(payload)
                if blob is not None:
                    self.functions[payload] = blob
            return blob

        if method == "export_function":
            fid, blob = payload
            if blob is not None and fid not in self.functions:
                self.functions[fid] = blob
            if self.head is not None:
                await self.head.export_function(fid, blob)
            return fid in self.functions

        if method == "submit_task":
            spec: TaskSpec = payload["spec"]
            # Nested submission: the child's worker logs belong on the
            # console of the driver that owns the SUBMITTING task, not
            # on this (possibly daemon) node's — inherit the owner
            # stamp from the PARENT TASK (per-task, not per-worker: a
            # concurrent actor serves several drivers at once).
            # (ADVICE r4; reference: per-job log subscription.)
            if getattr(spec, "_owner_node", None) is None:
                w = conn.meta.get("worker")
                parent_b = payload.get("parent")
                if w is not None:
                    parent = (w.inflight.get(TaskID(parent_b))
                              if parent_b else None)
                    spec._owner_node = (
                        getattr(parent, "_owner_node", None)
                        or w.owner_node)
            # Workers submit fire-and-forget (notify): there is no reply
            # to carry an error, so the backchannel is the refs — the
            # submitter computed spec.return_ids() locally, and a failed
            # submission poisons exactly those (same path _fail_task
            # uses for every other task failure).
            try:
                rids = self.submit(spec)
            except BaseException as e:  # noqa: BLE001 - poison returns
                err = e if isinstance(e, TaskError) \
                    else TaskError.from_exception(e, spec.name)
                self._fail_task(spec, err)
                rids = spec.return_ids()
            return [r.binary() for r in rids]

        if method == "task_running":
            w = conn.meta.get("worker")
            if w is not None:
                self._on_task_running(w, TaskID(payload))
            return True

        if method == "metrics_push":
            # Cumulative user-metric snapshot from a worker process
            # (reference: worker -> per-node metrics agent, reporter.proto).
            self.user_metrics[payload["source"]] = payload["snapshot"]
            return True

        if method == "spans_push":
            self.trace_spans.extend(payload)
            return True

        if method == "request_spans_push":
            self._trace_buf.extend(payload)
            return True

        if method == "task_events_push":
            # Worker-ring drain (1s flusher plane): fine-grained
            # transitions (ARGS_FETCHED / OUTPUT_SERIALIZED) append to
            # the node's event table only — the latest-state task row is
            # owned by the node's own transitions, which may already
            # have moved past these by the time the flush lands.
            for ev in payload:
                ev.setdefault("node_id", self.node_id.hex())
                self.task_events.append(ev)
            return True

        if method == "fetch_object":
            oid = ObjectID(payload["oid"])
            owner = payload.get("owner")
            if owner is not None:
                await self.ensure_object(oid, tuple(owner),
                                         payload.get("timeout"))
            st = await self.wait_object(oid, payload.get("timeout"))
            if st.status == PENDING:
                return ("timeout",)
            if st.status == ERROR:
                return ("err", st.error)
            return self.materialize_for_ipc(oid)

        if method == "fetch_objects":
            # Batched worker get(): one RPC for N refs, resolved
            # concurrently (remote pulls overlap instead of serializing
            # one round trip per ref). Per-ref outcomes mirror
            # fetch_object so the worker fans replies back out.
            timeout = payload.get("timeout")

            async def fetch_one(r):
                oid = ObjectID(r["oid"])
                owner = r.get("owner")
                try:
                    if owner is not None:
                        await self.ensure_object(oid, tuple(owner), timeout)
                    st = await self.wait_object(oid, timeout)
                    if st.status == PENDING:
                        return ("timeout",)
                    if st.status == ERROR:
                        return ("err", st.error)
                    return self.materialize_for_ipc(oid)
                except TaskError as e:
                    return ("err", e)
                except BaseException as e:  # noqa: BLE001 - per-ref error
                    return ("err", TaskError.from_exception(e, "get"))

            return list(await asyncio.gather(
                *[fetch_one(r) for r in payload["reqs"]]))

        if method == "wait_objects":
            oids = [ObjectID(b) for b in payload["oids"]]
            for b, owner in zip(payload["oids"],
                                payload.get("owners") or []):
                if owner is not None:
                    self.spawn(
                        self.ensure_object(ObjectID(b), tuple(owner)))
            num_returns = payload["num_returns"]
            timeout = payload.get("timeout")
            deadline = None if timeout is None else self.loop.time() + timeout
            while True:
                ready = [o.binary() for o in oids
                         if self.objects.get(o) and self.objects[o].status != PENDING]
                if len(ready) >= num_returns:
                    return ready
                remaining = None if deadline is None else max(0, deadline - self.loop.time())
                if remaining == 0:
                    return ready
                pending = [o for o in oids
                           if not (self.objects.get(o) and self.objects[o].status != PENDING)]
                futs = []
                for o in pending:
                    f = self.loop.create_future()
                    self._obj(o).waiters.append(f)
                    futs.append(f)
                try:
                    await asyncio.wait(futs, timeout=remaining,
                                       return_when=asyncio.FIRST_COMPLETED)
                finally:
                    for f in futs:
                        if not f.done():
                            f.cancel()
                    for o in oids:
                        st = self.objects.get(o)
                        if st and st.waiters:
                            st.waiters[:] = [x for x in st.waiters
                                             if not x.cancelled()]

        if method == "put_object":
            oid = ObjectID(payload["oid"])
            self._obj(oid).refcount += 1
            w = conn.meta.get("worker")
            if w is not None:
                # The put count belongs to the worker's ObjectRef; if the
                # worker dies without dropping it, disconnect cleanup
                # releases it.
                w.held_refs[oid] += 1
            self._attach_inner_refs(oid, payload.get("inner_refs"))
            if payload.get("inline") is not None:
                self.mark_ready_bytes(oid, payload["inline"])
            else:
                self.mark_ready_shm(oid, payload["size"])
            return True

        if method == "ref_hold":
            # Worker-process ref bookkeeping (nested refs an actor/task
            # keeps): counts here, borrows at the owner when foreign.
            oid = ObjectID(payload["oid"])
            owner = payload.get("owner")
            self.incref_ref(oid, tuple(owner) if owner else None)
            w = conn.meta.get("worker")
            if w is not None:
                w.held_refs[oid] += 1
            return True

        if method == "ref_drop_batch":
            w = conn.meta.get("worker")
            for oid_b in payload:
                oid = ObjectID(oid_b)
                if w is not None:
                    if w.held_refs[oid] <= 0:
                        continue  # unmatched drop (hold raced death)
                    w.held_refs[oid] -= 1
                    if w.held_refs[oid] <= 0:
                        del w.held_refs[oid]
                self.decref(oid)
            return True

        if method == "decref":
            for b in payload:
                self.decref(ObjectID(b))
            return True

        if method == "pubsub_subscribe":
            if payload["channel"].startswith("__"):
                # Internal channels (worker-log fanout etc.) are not
                # worker-subscribable: one session's console output
                # must not be readable from another session's tasks.
                raise ValueError(
                    f"channel {payload['channel']!r} is reserved")
            w = conn.meta.get("worker")
            if w is not None:
                await self.pubsub_subscribe(
                    payload["channel"], payload["sub_id"], ("worker", w))
            return True

        if method == "pubsub_unsubscribe":
            await self.pubsub_unsubscribe(payload["channel"],
                                          payload["sub_id"])
            return True

        if method == "pubsub_publish":
            if payload["channel"].startswith("__"):
                raise ValueError(
                    f"channel {payload['channel']!r} is reserved")
            return await self.pubsub_publish(payload["channel"],
                                             payload["message"])

        if method == "free_objects":
            # Worker-initiated eager free (Data executors running inside
            # actors): local-owned frees happen here; foreign-owned are
            # forwarded to the owner.
            for oid_b, owner in payload:
                oid = ObjectID(oid_b)
                if owner and tuple(owner) != tuple(self.peer_address):
                    self.spawn(self._notify_free_remote(oid, tuple(owner)))
                else:
                    self.free_object(oid)
            return True

        if method == "get_actor_by_name":
            if self.head is None:
                return None
            return await self.head.get_actor_by_name(payload)

        if method == "kv":
            op, key, val = payload
            return await self.head.kv_op(op, key, val)

        if method == "list_nodes":
            # Workers can see cluster membership (reference: ray.nodes()
            # works from tasks/actors) — e.g. the serve controller actor
            # reconciling its per-node proxy fleet. Head-less must RAISE,
            # not return []: "no membership info" and "zero nodes" have
            # very different consequences for reconcilers.
            if self.head is None:
                raise RuntimeError("cluster head is not reachable")
            return await self.head.list_nodes()

        if method == "kill_actor":
            await self.kill_actor_anywhere(ActorID(payload))
            return True

        if method == "log":
            sys.stderr.write(payload)
            return True

        if method == "state":
            return self.state_snapshot(
                include_events=bool((payload or {}).get("events")),
                light=bool((payload or {}).get("light")),
                tables=(payload or {}).get("tables"))

        raise RuntimeError(f"unknown rpc method: {method}")

    async def _on_disconnect(self, conn: ServerConn):
        w: WorkerHandle | None = conn.meta.get("worker")
        if w is None or self._closing:
            return
        was = w.state
        w.state = "DEAD"
        self.counters["workers_died"] += 1
        self._retire_worker_metrics(w.worker_id.hex())
        # A dead worker can never send its ref_drops: release them here.
        for oid, n in w.held_refs.items():
            self.decref(oid, n)
        w.held_refs.clear()
        # ...nor its pubsub unsubscribes.
        for channel in list(self.pubsub_local):
            for sub_id, sink in list(self.pubsub_local[channel].items()):
                if sink[0] == "worker" and sink[1] is w:
                    await self.pubsub_unsubscribe(channel, sub_id)
        # Plain task workers: inflight tasks handled by ConnectionLost in
        # _run_on_worker (retry path). Actor workers: restart FSM.
        if w.actor_id is not None:
            actor = self.actors.get(w.actor_id)
            if actor and actor.state in ("ALIVE", "PENDING", "RESTARTING"):
                if actor.num_restarts < actor.creation_spec.max_restarts and was != "DEAD":
                    await self._restart_actor(actor)
                else:
                    actor.state = "DEAD"
                    actor.death_cause = "worker process died"
                    self._release_actor_resources(actor)
                    self._unregister_actor(actor)
                    for spec in actor.queue:
                        self._fail_task(
                            spec, ActorDiedError("actor worker died", task_name=spec.name)
                        )
                    actor.queue.clear()

    # ------------------------------------------------------------------
    async def shutdown(self):
        self._closing = True
        for t in self._bg_tasks:
            t.cancel()
        for conn in list(self.peer_conns.values()):
            await conn.close()
        for w in self.workers.values():
            if w.state != "DEAD":
                self._kill_worker(w)
        await self.server.stop()
        await self.peer_server.stop()
        bulk = getattr(self, "_bulk_server", None)
        if bulk is not None:
            bulk.close()
            await bulk.wait_closed()
        self.device_pool.shutdown(wait=False)
        for actor in self.actors.values():
            if actor.device_pool:
                actor.device_pool.shutdown(wait=False)
        for w in self.workers.values():
            try:
                w.proc.wait(timeout=2)
            except subprocess.TimeoutExpired:
                w.proc.kill()
        # Session over: reclaim the captured-log namespace.
        import shutil

        shutil.rmtree(self.log_dir, ignore_errors=True)
