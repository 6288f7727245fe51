"""Job submission: run driver scripts ON the cluster, track their
lifecycle, stream their logs.

Capability parity target: /root/reference/dashboard/modules/job/
job_manager.py:525 (JobManager.submit_job: supervisor per job, entrypoint
subprocess with RAY_ADDRESS injected, status bookkeeping in the GCS KV)
and python/ray/dashboard/modules/job/sdk.py (JobSubmissionClient).

Shape here: the ``JobManager`` is a SUPERVISED NAMED ACTOR (like the
serve controller). Each submitted job is an entrypoint shell command run
as its own process group with ``RT_ADDRESS`` pointing at the cluster
head — ``ray_tpu.init()`` inside the entrypoint attaches as a driver.
Job table lives in the cluster KV, so a restarted manager (or any other
client) sees every job; logs go to files the manager serves on request.

Multi-tenant plane (ISSUE 15): every job carries a tenant + fair-share
weight + optional gang resource shape. Submission passes ADMISSION
CONTROL (``ray_tpu.jobs.admission`` — over-quota, malformed entrypoint,
or infeasible gang shapes are REJECTED with a machine-readable
``JobInfo.reason``); admitted jobs queue in the weighted fair-share
scheduler (``ray_tpu.jobs.scheduler.JobScheduler``) and a dispatcher
thread spawns them in stride order as quota/concurrency allows. Queued
gang shapes are published to the cluster KV
(``autoscaler:job_demand``), where ``HeadService.autoscaler_snapshot``
hands them to the autoscaler — pending gang demand is what drives
slice-shaped scale-up.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import threading
import time
import uuid
from dataclasses import asdict, dataclass, field
from typing import Optional

from ray_tpu.jobs.quota import TenantQuota
from ray_tpu.jobs.scheduler import JobScheduler

JOB_MANAGER_NAME = "JOB_MANAGER"
_KV_PREFIX = "job:"
#: KV keys shared with the autoscaler (AutoscalerMonitor constants
#: mirror these — the two planes rendezvous through the cluster KV).
JOB_DEMAND_KV_KEY = "autoscaler:job_demand"
FLEET_ENVELOPE_KV_KEY = "autoscaler:fleet_envelope"


class JobStatus:
    PENDING = "PENDING"
    RUNNING = "RUNNING"
    SUCCEEDED = "SUCCEEDED"
    FAILED = "FAILED"
    STOPPED = "STOPPED"
    #: Admission control refused the submission; ``JobInfo.reason``
    #: holds the machine-readable why (code + detail + specifics).
    REJECTED = "REJECTED"

    TERMINAL = (SUCCEEDED, FAILED, STOPPED, REJECTED)


@dataclass
class JobInfo:
    submission_id: str
    entrypoint: str
    status: str = JobStatus.PENDING
    message: str = ""
    start_time: float = field(default_factory=time.time)
    end_time: Optional[float] = None
    metadata: dict = field(default_factory=dict)
    runtime_env: dict = field(default_factory=dict)
    pid: Optional[int] = None
    log_path: str = ""
    return_code: Optional[int] = None
    # -- multi-tenant plane --
    tenant: str = "default"
    weight: float = 1.0
    resources: dict = field(default_factory=dict)  # gang shape (advisory)
    reason: Optional[dict] = None  # machine-readable rejection reason


class JobManager:
    """Named actor owning job subprocesses (reference: job supervisor
    actors; collapsed to one manager since jobs are plain processes)."""

    def __init__(self, head_address: str, log_dir: Optional[str] = None,
                 max_concurrent: Optional[int] = None):
        self._head_address = head_address
        self._log_dir = log_dir or os.path.join(
            os.environ.get("TMPDIR", "/tmp"), "rtpu-jobs")
        os.makedirs(self._log_dir, exist_ok=True)
        self._lock = threading.Lock()
        self._jobs: dict[str, JobInfo] = {}
        self._procs: dict[str, subprocess.Popen] = {}
        # 0 / None = unlimited: fairness then only bites when tenant
        # quotas (or a configured cap) create contention.
        self._max_concurrent = max_concurrent if max_concurrent \
            is not None else int(os.environ.get(
                "RT_JOBS_MAX_CONCURRENT", "0"))
        self._capacity_cache: tuple = (0.0, {})
        self._sched = JobScheduler(capacity_fn=self._cluster_capacity,
                                   envelope_fn=self._fleet_envelope)
        self._gauges = self._make_gauges()
        self._dispatch_wake = threading.Event()
        self._recover()
        threading.Thread(target=self._dispatch_loop, daemon=True,
                         name="rtpu-job-dispatcher").start()

    # -- cluster feeds ------------------------------------------------------
    def _cluster_capacity(self) -> dict:
        """Total resources across alive nodes (TTL-cached): the DRF
        denominator for dominant-share job costs."""
        import ray_tpu

        now = time.monotonic()
        ts, cached = self._capacity_cache
        if now - ts < 5.0:
            return cached
        cap: dict = {}
        try:
            for n in ray_tpu.util.state.list_nodes():
                if n.get("state") == "ALIVE":
                    for k, v in (n.get("resources") or {}).items():
                        cap[k] = cap.get(k, 0) + v
        except Exception:  # lint: allow-swallow(state API down mid-shutdown; stale/empty capacity only skews cost normalization)
            cap = cached
        self._capacity_cache = (now, cap)
        return cap

    def _fleet_envelope(self) -> list:
        """Launchable slice topologies published by the autoscaler
        monitor (admission's INFEASIBLE_SHAPE check). No publisher =>
        empty => feasibility is not enforced."""
        import ray_tpu

        try:
            blob = ray_tpu.kv_get(FLEET_ENVELOPE_KV_KEY)
            return json.loads(blob) if blob else []
        except Exception:  # lint: allow-swallow(no envelope published; admit and let the queue pend)
            return []

    def _publish_demand(self):
        """Queued gang shapes -> cluster KV -> autoscaler_snapshot ->
        slice-shaped scale-up. Callers must NOT hold self._lock."""
        import ray_tpu

        try:
            with self._lock:
                shapes = self._sched.pending_shapes()
            ray_tpu.kv_put(JOB_DEMAND_KV_KEY,
                           json.dumps(shapes).encode())
        except Exception:  # lint: allow-swallow(KV down during shutdown; demand feed is advisory)
            pass

    # -- observability ------------------------------------------------------
    def _make_gauges(self) -> dict:
        from ray_tpu.util.metrics import Gauge

        return {
            "queued": Gauge("rtpu_jobs_queued",
                            "queued jobs per tenant",
                            tag_keys=("tenant",)),
            "running": Gauge("rtpu_jobs_running",
                             "running jobs per tenant",
                             tag_keys=("tenant",)),
            "share": Gauge("rtpu_tenant_share",
                           "dominant share of running usage per tenant",
                           tag_keys=("tenant",)),
            "served": Gauge("rtpu_tenant_served_cost",
                            "cumulative dispatched fair-share cost",
                            tag_keys=("tenant",)),
        }

    def _job_event(self, kind: str, info: JobInfo, **extra):
        """Manager lifecycle events join the scheduler's decision ledger
        (one job-plane timeline) and refresh the per-tenant gauges the
        telemetry sampler exports."""
        self._sched.record(kind, info.submission_id, info.tenant, **extra)
        try:
            for tenant, row in self._sched.stats().items():
                tags = {"tenant": tenant}
                self._gauges["queued"].set(row["queued"], tags)
                self._gauges["running"].set(row["running"], tags)
                self._gauges["share"].set(row.get("share", 0.0), tags)
                self._gauges["served"].set(row["served_cost"], tags)
        except Exception:  # lint: allow-swallow(gauge refresh is best-effort observability)
            pass

    # -- persistence --------------------------------------------------------
    def _save(self, info: JobInfo):
        import ray_tpu

        ray_tpu.kv_put(_KV_PREFIX + info.submission_id,
                       json.dumps(asdict(info)).encode())

    def _recover(self):
        """Rebuild the job table from the KV after a manager restart.
        RUNNING jobs whose process survived keep running (re-monitored
        by pid, re-charged against their tenant's quota); RUNNING jobs
        whose process died are FAILED; queued PENDING jobs (never
        spawned) re-enter the fair-share queue."""
        import ray_tpu

        for key in ray_tpu.kv_keys(_KV_PREFIX):
            blob = ray_tpu.kv_get(key)
            if blob is None:
                continue
            info = JobInfo(**json.loads(blob))
            self._jobs[info.submission_id] = info
            if info.status == JobStatus.PENDING and info.pid is None:
                # Admitted but never spawned: requeue (admission already
                # passed once; quota state is rebuilt as we go).
                reason = self._sched.submit(
                    info.submission_id, tenant=info.tenant,
                    weight=info.weight, shape=info.resources,
                    entrypoint=info.entrypoint)
                if reason is not None:
                    info.status = JobStatus.REJECTED
                    info.reason = reason
                    info.message = reason.get("detail", reason["code"])
                    info.end_time = time.time()
                    self._save(info)
            elif info.status in (JobStatus.PENDING, JobStatus.RUNNING):
                if info.pid is not None and _pid_alive(info.pid):
                    self._sched.adopt_running(
                        info.submission_id, tenant=info.tenant,
                        shape=info.resources, weight=info.weight)
                    threading.Thread(target=self._monitor_pid,
                                     args=(info,), daemon=True).start()
                else:
                    info.status = JobStatus.FAILED
                    info.message = "job process died while the manager " \
                                   "was down"
                    info.end_time = time.time()
                    self._save(info)

    # -- lifecycle ----------------------------------------------------------
    def submit_job(self, entrypoint: str,
                   submission_id: Optional[str] = None,
                   runtime_env: Optional[dict] = None,
                   metadata: Optional[dict] = None,
                   tenant: str = "default",
                   weight: float = 1.0,
                   resources: Optional[dict] = None) -> str:
        """Admission-checked, fair-share-queued submission. The returned
        submission id is NOT a promise the job will run: check
        ``get_job_info`` — a rejected job is terminal ``REJECTED`` with
        the machine-readable ``reason`` attached."""
        sid = submission_id or f"rtpu-job-{uuid.uuid4().hex[:10]}"
        with self._lock:
            if sid in self._jobs and \
                    self._jobs[sid].status not in JobStatus.TERMINAL:
                raise ValueError(f"job {sid!r} already exists and is "
                                 f"{self._jobs[sid].status}")
            info = JobInfo(
                submission_id=sid, entrypoint=entrypoint,
                metadata=dict(metadata or {}),
                runtime_env=dict(runtime_env or {}),
                log_path=os.path.join(self._log_dir, f"{sid}.log"),
                tenant=tenant, weight=weight,
                resources=dict(resources or {}))
            reason = self._sched.submit(
                sid, tenant=tenant, weight=weight,
                shape=info.resources, entrypoint=entrypoint)
            if reason is not None:
                info.status = JobStatus.REJECTED
                info.reason = reason
                info.message = reason.get("detail", reason["code"])
                info.end_time = time.time()
            self._jobs[sid] = info
            if reason is None:
                self._job_event("queued", info)
        self._save(info)
        if reason is None:
            self._publish_demand()
            self._dispatch_wake.set()
        return sid

    def _dispatch_loop(self):
        """The fair-share dispatcher: drains the scheduler in stride
        order whenever capacity frees up (finish/stop/submit), spawning
        one entrypoint subprocess per dispatch decision."""
        while True:
            self._dispatch_wake.wait(timeout=1.0)
            self._dispatch_wake.clear()
            while True:
                with self._lock:
                    running = sum(
                        1 for i in self._jobs.values()
                        if i.status == JobStatus.RUNNING)
                    if self._max_concurrent \
                            and running >= self._max_concurrent:
                        break
                    decision = self._sched.next_dispatch()
                    if decision is None:
                        break
                    info = self._jobs.get(decision.job_id)
                if info is None or info.status != JobStatus.PENDING:
                    # Stopped (or lost) between queue and dispatch:
                    # give the charge straight back.
                    with self._lock:
                        self._sched.on_finish(
                            decision.job_id,
                            outcome="stopped-before-start")
                    continue
                self._spawn(info)
                self._publish_demand()

    def _spawn(self, info: JobInfo):
        sid = info.submission_id
        env = dict(os.environ)
        env["RT_ADDRESS"] = self._head_address
        env["RT_JOB_SUBMISSION_ID"] = sid
        env["RT_JOB_TENANT"] = info.tenant
        # Entrypoint drivers attach to the cluster — they must not open
        # the chip themselves (the node's device lane owns it).
        env.setdefault("JAX_PLATFORMS", "cpu")
        env.update(info.runtime_env.get("env_vars", {}))
        cwd = info.runtime_env.get("working_dir") or None
        log = open(info.log_path, "wb")
        try:
            proc = subprocess.Popen(
                info.entrypoint, shell=True, env=env, cwd=cwd,
                stdout=log, stderr=subprocess.STDOUT,
                start_new_session=True)  # own pgid: stop kills the tree
        except OSError as e:
            with self._lock:
                info.status = JobStatus.FAILED
                info.message = str(e)
                info.end_time = time.time()
            self._sched.on_finish(sid, outcome="spawn-failed")
            self._job_event("spawn_failed", info, error=str(e))
            self._save(info)
            log.close()
            return
        finally:
            log.close()
        with self._lock:
            if info.status == JobStatus.STOPPED:
                # stop_job raced the spawn: it had no pid to kill, so the
                # kill is ours to deliver.
                stopped = True
            else:
                stopped = False
                info.status = JobStatus.RUNNING
                info.pid = proc.pid
                self._procs[sid] = proc
        if stopped:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass
            # Reap the killed child so it doesn't linger as a zombie in
            # this long-lived manager actor.
            threading.Thread(target=proc.wait, daemon=True).start()
            with self._lock:
                self._sched.on_finish(sid, outcome="stopped")
            return
        self._job_event("started", info, pid=proc.pid)
        self._save(info)
        threading.Thread(target=self._monitor_proc, args=(info, proc),
                         daemon=True).start()

    def _monitor_proc(self, info: JobInfo, proc: subprocess.Popen):
        rc = proc.wait()
        self._finish(info, rc)

    def _monitor_pid(self, info: JobInfo):
        """Adopted (pre-restart) job: not our child, poll liveness."""
        while _pid_alive(info.pid):
            time.sleep(0.5)
        self._finish(info, None)

    def _finish(self, info: JobInfo, rc: Optional[int]):
        with self._lock:
            if info.status == JobStatus.STOPPED:
                return  # stop_job already settled it
            info.return_code = rc
            info.status = (JobStatus.SUCCEEDED if rc == 0
                           else JobStatus.FAILED)
            if rc != 0:
                info.message = (f"entrypoint exited with code {rc}"
                                if rc is not None else
                                "job process exited (adopted; return "
                                "code unknown)")
            info.end_time = time.time()
            self._procs.pop(info.submission_id, None)
            # Crash or success, the quota charge comes back the same
            # way — release is idempotent, so a stop racing the exit
            # cannot double-credit the tenant.
            self._sched.on_finish(
                info.submission_id,
                outcome="finished" if rc == 0 else "crashed")
        self._job_event("finished", info, return_code=rc)
        self._save(info)
        self._dispatch_wake.set()  # freed slot/quota: dispatch next

    def stop_job(self, submission_id: str) -> bool:
        with self._lock:
            info = self._jobs.get(submission_id)
            if info is None or info.status in JobStatus.TERMINAL:
                return False
            was_queued = (info.status == JobStatus.PENDING
                          and info.pid is None)
            info.status = JobStatus.STOPPED
            info.end_time = time.time()
            pid = info.pid
            self._procs.pop(submission_id, None)
            if was_queued:
                # Still in the fair-share queue: pull it out before the
                # dispatcher can spawn it. (If the dispatcher already
                # took the dispatch decision, _spawn's stop-race path
                # delivers the kill and the release instead.)
                self._sched.cancel(submission_id)
        self._save(info)
        if pid is not None:
            try:
                os.killpg(pid, signal.SIGTERM)
                time.sleep(0.5)
                if _pid_alive(pid):
                    os.killpg(pid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass
            with self._lock:
                self._sched.on_finish(submission_id, outcome="stopped")
        self._job_event("stopped", info)
        self._publish_demand()
        self._dispatch_wake.set()
        return True

    # -- tenant administration ----------------------------------------------
    def set_tenant_quota(self, tenant: str,
                         max_running_jobs: Optional[int] = None,
                         max_pending_jobs: Optional[int] = None,
                         resources: Optional[dict] = None) -> dict:
        quota = TenantQuota(max_running_jobs=max_running_jobs,
                            max_pending_jobs=max_pending_jobs,
                            resources=dict(resources or {}) or None)
        with self._lock:
            self._sched.set_quota(tenant, quota)
        return quota.to_dict()

    def get_tenant_quotas(self) -> dict:
        with self._lock:
            return {t: q.to_dict()
                    for t, q in self._sched.quotas.quotas().items()}

    def tenant_stats(self) -> dict:
        """Per-tenant fair-share view: weight, pass, share, queue depth,
        running count, served cost, quota — the `rtpu jobs` feed."""
        with self._lock:
            return self._sched.stats()

    def list_job_events(self, limit: int = 200) -> list:
        with self._lock:
            return self._sched.events(limit)

    def record_event(self, kind: str, job_id: str,
                     tenant: str = "default", extra: dict | None = None):
        """External event onto the job-plane ledger — e.g. the gang
        desync watchdog's ``gang_desync`` verdict (parallel/flightrec.
        publish_verdict), keyed by the gang/run name as job_id."""
        with self._lock:
            self._sched.record(kind, job_id, tenant, **(extra or {}))
        return True

    def set_max_concurrent(self, n: int):
        with self._lock:
            self._max_concurrent = max(0, int(n))
        self._dispatch_wake.set()

    # -- queries ------------------------------------------------------------
    def get_job_status(self, submission_id: str) -> str:
        return self._job(submission_id).status

    def get_job_info(self, submission_id: str) -> dict:
        return asdict(self._job(submission_id))

    def list_jobs(self) -> list[dict]:
        with self._lock:
            return [asdict(i) for i in self._jobs.values()]

    def get_job_logs(self, submission_id: str) -> str:
        info = self._job(submission_id)
        try:
            with open(info.log_path, "rb") as f:
                return f.read().decode(errors="replace")
        except FileNotFoundError:
            return ""

    def _job(self, submission_id: str) -> JobInfo:
        with self._lock:
            info = self._jobs.get(submission_id)
        if info is None:
            raise ValueError(f"no such job: {submission_id!r}")
        return info

    def ping(self) -> bool:
        return True


def _pid_alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
        return True
    except ProcessLookupError:
        return False
    except PermissionError:
        return True


class JobSubmissionClient:
    """Client facade (reference: ray.job_submission.JobSubmissionClient).
    Finds — or lazily creates — the JobManager actor on the cluster this
    process is attached to."""

    def __init__(self, address: Optional[str] = None):
        import ray_tpu

        if not ray_tpu.is_initialized():
            ray_tpu.init(address=address)
        self._manager = self._get_or_create_manager()

    def _get_or_create_manager(self):
        import ray_tpu

        try:
            return ray_tpu.get_actor(JOB_MANAGER_NAME)
        except Exception:  # lint: allow-swallow(no manager registered yet; created below)
            pass
        from ray_tpu._private import context as context_mod
        from ray_tpu._private.task_spec import SchedulingStrategy

        rt = context_mod.require_context()
        if hasattr(rt, "head_address"):
            host, port = rt.head_address
            addr = f"{host}:{port}"
        else:  # inside a task/actor: the worker inherited the env
            addr = os.environ["RT_ADDRESS"]
        # Pin the manager to the HEAD NODE (reference: the JobManager
        # lives on the head). Without the pin, a manager created by a
        # short-lived attached driver (e.g. `rtpu job submit`) would run
        # on that driver's transient node and die with it.
        head_node = next(n for n in ray_tpu.util.state.list_nodes()
                         if n["is_head_node"])
        strategy = SchedulingStrategy(
            kind="node", node_id=bytes.fromhex(head_node["node_id"]))
        try:
            manager = ray_tpu.remote(JobManager).options(
                name=JOB_MANAGER_NAME, max_restarts=100, max_concurrency=8,
                scheduling_strategy=strategy).remote(addr)
            ray_tpu.get(manager.ping.remote(), timeout=60)
            return manager
        except Exception:  # lint: allow-swallow(lost get-or-create race; adopt the winner)
            # Get-or-create race: a concurrent client won the name
            # registration; adopt the winner's manager.
            return ray_tpu.get_actor(JOB_MANAGER_NAME)

    def submit_job(self, *, entrypoint: str,
                   submission_id: Optional[str] = None,
                   runtime_env: Optional[dict] = None,
                   metadata: Optional[dict] = None,
                   tenant: str = "default",
                   weight: float = 1.0,
                   resources: Optional[dict] = None) -> str:
        import ray_tpu

        return ray_tpu.get(self._manager.submit_job.remote(
            entrypoint, submission_id, runtime_env, metadata,
            tenant, weight, resources), timeout=120)

    # -- tenant administration ----------------------------------------------
    def set_tenant_quota(self, tenant: str,
                         max_running_jobs: Optional[int] = None,
                         max_pending_jobs: Optional[int] = None,
                         resources: Optional[dict] = None) -> dict:
        import ray_tpu

        return ray_tpu.get(self._manager.set_tenant_quota.remote(
            tenant, max_running_jobs, max_pending_jobs, resources),
            timeout=30)

    def get_tenant_quotas(self) -> dict:
        import ray_tpu

        return ray_tpu.get(self._manager.get_tenant_quotas.remote(),
                           timeout=30)

    def tenant_stats(self) -> dict:
        import ray_tpu

        return ray_tpu.get(self._manager.tenant_stats.remote(), timeout=30)

    def list_job_events(self, limit: int = 200) -> list:
        import ray_tpu

        return ray_tpu.get(self._manager.list_job_events.remote(limit),
                           timeout=30)

    def get_job_status(self, submission_id: str) -> str:
        import ray_tpu

        return ray_tpu.get(
            self._manager.get_job_status.remote(submission_id), timeout=30)

    def get_job_info(self, submission_id: str) -> dict:
        import ray_tpu

        return ray_tpu.get(
            self._manager.get_job_info.remote(submission_id), timeout=30)

    def list_jobs(self) -> list[dict]:
        import ray_tpu

        return ray_tpu.get(self._manager.list_jobs.remote(), timeout=30)

    def stop_job(self, submission_id: str) -> bool:
        import ray_tpu

        return ray_tpu.get(
            self._manager.stop_job.remote(submission_id), timeout=30)

    def get_job_logs(self, submission_id: str) -> str:
        import ray_tpu

        return ray_tpu.get(
            self._manager.get_job_logs.remote(submission_id), timeout=30)

    def wait_until_finish(self, submission_id: str,
                          timeout: float = 300) -> str:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            status = self.get_job_status(submission_id)
            if status in JobStatus.TERMINAL:
                return status
            time.sleep(0.3)
        raise TimeoutError(
            f"job {submission_id} still "
            f"{self.get_job_status(submission_id)} after {timeout}s")
