"""Virtual-time churn harness: K tenants' gang jobs on a simulated,
shrinking-then-growing TPU fleet, driven by the REAL decision stack —
JobScheduler (admission/quota/fair-share), StandardAutoscalerV2
(instance FSM + requeue/backoff), SimulatedNodeProvider — with only the
clock and the subprocess spawn simulated.

The placement model is the repo's thesis taken literally: a TPU slice
IS the gang unit, so a job's gang occupies one whole slice whose
aggregate resources cover its shape; a slice hosts one gang at a time.
Chaos kills (`shrink`) take slices out from under running gangs, which
must requeue — never silently die — and queued gang shapes flow back
into the snapshot as `job_demand`, which is what regrows the fleet.

Used by tests/test_job_plane.py (the end-to-end churn acceptance).
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from ray_tpu.autoscaler.autoscaler import (AutoscalingConfig,
                                           v5e_node_types)
from ray_tpu.autoscaler.instance_manager import StandardAutoscalerV2
from ray_tpu.autoscaler.node_provider import (SimulatedNodeProvider,
                                              SliceHandle)
from ray_tpu.job_submission import JobInfo, JobStatus

from .quota import TenantQuota
from .scheduler import JobScheduler


@dataclass
class SimJob:
    info: JobInfo
    duration: int  # ticks of gang time to finish
    remaining: int
    slice_id: Optional[str] = None
    requeues: int = 0


def jain_index(values: List[float]) -> float:
    """Jain's fairness index: (sum x)^2 / (n * sum x^2); 1.0 = equal."""
    xs = [v for v in values if v > 0]
    if not xs:
        return 1.0
    return (sum(xs) ** 2) / (len(xs) * sum(x * x for x in xs))


class JobPlaneSim:
    def __init__(self,
                 node_types: Optional[list] = None,
                 max_slices_per_type: int = 2,
                 idle_timeout_ticks: float = 3.0,
                 boot_delay_ticks: float = 1.0,
                 launch_backoff_ticks: float = 1.0,
                 quotas: Optional[Dict[str, TenantQuota]] = None):
        self.now = 0.0
        self.config = AutoscalingConfig(
            node_types if node_types is not None
            else v5e_node_types(max_workers=max_slices_per_type),
            idle_timeout_s=idle_timeout_ticks,
            update_interval_s=1.0)
        self.provider = SimulatedNodeProvider(
            clock=lambda: self.now, boot_delay_s=boot_delay_ticks)
        self.autoscaler = StandardAutoscalerV2(
            self.config, self.provider,
            launch_backoff_s=launch_backoff_ticks)
        # Cost normalization uses the FIXED max-fleet capacity, not the
        # instantaneous one, so a dispatch costs the same before and
        # after churn and ledger shares stay comparable across the run.
        self.capacity: Dict[str, float] = {}
        for t in self.config.node_types:
            for k, v in t.resources.items():
                self.capacity[k] = self.capacity.get(k, 0) \
                    + v * t.hosts * t.max_workers
        self.sched = JobScheduler(
            capacity_fn=lambda: self.capacity,
            envelope_fn=self.config.envelope,
            clock=lambda: self.now)
        for tenant, quota in (quotas or {}).items():
            self.sched.set_quota(tenant, quota)
        self.jobs: Dict[str, SimJob] = {}
        self._slice_job: Dict[str, str] = {}  # slice_id -> job_id
        self.lost_gangs = 0  # running gangs that vanished WITHOUT requeue
        self._counter = 0

    # -- workload -----------------------------------------------------------
    def submit(self, tenant: str, weight: float = 1.0,
               shape: Optional[dict] = None, duration: int = 3,
               entrypoint: str = "sim: sleep",
               job_id: Optional[str] = None) -> JobInfo:
        self._counter += 1
        jid = job_id or f"sim-job-{self._counter}"
        info = JobInfo(submission_id=jid, entrypoint=entrypoint,
                       start_time=self.now, tenant=tenant, weight=weight,
                       resources=dict(shape or {}))
        reason = self.sched.submit(jid, tenant=tenant, weight=weight,
                                   shape=shape, entrypoint=entrypoint)
        if reason is not None:
            info.status = JobStatus.REJECTED
            info.reason = reason
            info.message = reason.get("detail", reason["code"])
            info.end_time = self.now
        else:
            self.jobs[jid] = SimJob(info=info, duration=duration,
                                    remaining=duration)
        return info

    # -- fleet views --------------------------------------------------------
    def _alive_slices(self) -> List[SliceHandle]:
        return [h for h in self.provider.non_terminated_slices()
                if self.provider.ready(h.slice_id)]

    def _slice_aggregate(self, h: SliceHandle) -> dict:
        per_host = h.meta.get("resources", {})
        return {k: v * len(h.node_ids) for k, v in per_host.items()}

    def _fits(self, h: SliceHandle, shape: dict) -> bool:
        agg = self._slice_aggregate(h)
        return all(agg.get(k, 0) >= v for k, v in shape.items() if v)

    def snapshot(self) -> dict:
        """What HeadService.autoscaler_snapshot() would say: one ALIVE
        row per booted member host (occupied slices show zero available
        — the gang owns them), plus queued gang shapes as job_demand."""
        nodes = []
        for h in self._alive_slices():
            per_host = h.meta.get("resources", {})
            busy = h.slice_id in self._slice_job
            for nid in h.node_ids:
                nodes.append({
                    "node_id": nid, "node_type": h.node_type,
                    "state": "ALIVE", "is_head_node": False,
                    "is_driver": False, "resources": dict(per_host),
                    "available": {} if busy else dict(per_host),
                    "reservations": 1 if busy else 0,
                })
        return {"nodes": nodes, "demand": [], "pending_pg_bundles": [],
                "job_demand": self.sched.pending_shapes()}

    # -- churn --------------------------------------------------------------
    def shrink(self, frac: float = 0.5, prefer_busy: bool = True) -> int:
        """Chaos: kill ceil(frac * alive) slices. Busy slices first so
        running gangs actually lose members and must requeue."""
        alive = self._alive_slices()
        if not alive:
            return 0
        n = max(1, math.ceil(frac * len(alive)))
        victims = sorted(
            alive, key=lambda h: h.slice_id not in self._slice_job
            if prefer_busy else True)[:n]
        for h in victims:
            self.provider.kill_slice(h.slice_id)
        return len(victims)

    # -- the loop -----------------------------------------------------------
    def step(self):
        self.now += 1.0

        # 1. Gang-loss detection BEFORE dispatch: any running job whose
        #    slice is gone (chaos kill, drain, death) requeues at the
        #    front of its tenant's queue — the zero-lost-work contract.
        live_ids = {h.slice_id
                    for h in self.provider.non_terminated_slices()}
        for jid, job in self.jobs.items():
            if job.info.status != JobStatus.RUNNING:
                continue
            if job.slice_id not in live_ids:
                self._slice_job.pop(job.slice_id, None)
                job.slice_id = None
                job.requeues += 1
                job.info.status = JobStatus.PENDING
                self.sched.requeue(jid)
        # Reverse index hygiene: occupied rows whose slice died while
        # the job ALSO finished this tick can linger; drop them.
        for sid in [s for s in self._slice_job if s not in live_ids]:
            if self.jobs[self._slice_job[sid]].info.status \
                    == JobStatus.RUNNING:
                self.lost_gangs += 1  # should be unreachable
            self._slice_job.pop(sid, None)

        # 2. Close the loop: pending gang demand drives the autoscaler.
        self.autoscaler.update(self.snapshot(), now=self.now)

        # 3. Fair-share dispatch onto free booted slices.
        while True:
            free = [h for h in self._alive_slices()
                    if h.slice_id not in self._slice_job]

            def can_place(tenant, job_id, shape, _free=free):
                return any(self._fits(h, shape) for h in _free)

            decision = self.sched.next_dispatch(self.capacity, can_place)
            if decision is None:
                break
            fitting = [h for h in free
                       if self._fits(h, decision.shape)]
            # Smallest fitting slice: don't burn a 4x8 on a 1x1 gang.
            h = min(fitting, key=lambda h: sum(
                self._slice_aggregate(h).values()))
            job = self.jobs[decision.job_id]
            job.slice_id = h.slice_id
            job.info.status = JobStatus.RUNNING
            self._slice_job[h.slice_id] = decision.job_id

        # 4. Gang time passes; finished jobs release their slice.
        for jid, job in self.jobs.items():
            if job.info.status != JobStatus.RUNNING:
                continue
            job.remaining -= 1
            if job.remaining <= 0:
                job.info.status = JobStatus.SUCCEEDED
                job.info.end_time = self.now
                self._slice_job.pop(job.slice_id, None)
                job.slice_id = None
                self.sched.on_finish(jid)

    def done(self) -> bool:
        return all(j.info.status in JobStatus.TERMINAL
                   for j in self.jobs.values())

    def run(self, max_ticks: int = 1000,
            shrink_at: Optional[int] = None,
            shrink_frac: float = 0.5) -> dict:
        for tick in range(max_ticks):
            if shrink_at is not None and tick == shrink_at:
                self.shrink(shrink_frac)
            self.step()
            if self.done():
                break
        return self.report()

    # -- results ------------------------------------------------------------
    def ledger_shares(self) -> Dict[str, float]:
        """Per-tenant share of dispatched cost, computed from the event
        ledger alone (the acceptance criterion's source of truth)."""
        cost: Dict[str, float] = {}
        for ev in self.sched.events():
            if ev["kind"] == "dispatched":
                cost[ev["tenant"]] = cost.get(ev["tenant"], 0.0) \
                    + ev["cost"]
        total = sum(cost.values())
        return {t: c / total for t, c in cost.items()} if total else {}

    def report(self) -> dict:
        stats = self.sched.stats(self.capacity)
        weighted_service = [
            row["served_cost"] / row["weight"]
            for row in stats.values() if row["served_cost"] > 0]
        finished = [j for j in self.jobs.values()
                    if j.info.status == JobStatus.SUCCEEDED]
        return {
            "ticks": self.now,
            "makespan": max((j.info.end_time for j in finished),
                            default=0.0),
            "jobs": len(self.jobs),
            "finished": len(finished),
            "unfinished": len(self.jobs) - len(finished),
            "requeues": sum(j.requeues for j in self.jobs.values()),
            "lost_gangs": self.lost_gangs,
            "jain_weighted": jain_index(weighted_service),
            "ledger_shares": self.ledger_shares(),
            "tenants": stats,
            "slices_killed": len(self.provider.killed),
            "fleet_slices": len(self._alive_slices()),
        }
