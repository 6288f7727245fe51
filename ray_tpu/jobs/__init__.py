"""Multi-tenant job plane: weighted fair-share scheduling, per-tenant
quotas, admission control, and the simulated churn harness that closes
the autoscaling loop against it.

Capability parity target: the reference's job manager + autoscaler pair
never grew a tenant concept; the shape here follows the classic stride
scheduler (Waldspurger & Weihl, OSDI '94) with DRF-style dominant-share
costs (Ghodsi et al., NSDI '11) so multi-resource gangs are compared on
the resource that actually binds.

Layering:

    fairshare.py   pure stride/DRF math (no clocks, no cluster)
    quota.py       per-tenant caps + idempotent charge/release ledger
    admission.py   reject-with-reason catalogue (quota / malformed /
                   infeasible-shape)
    scheduler.py   JobScheduler: the composition, with a decision ledger
    sim.py         virtual-time churn harness: K tenants x M gang jobs
                   on a shrinking-then-growing simulated fleet

``ray_tpu.job_submission.JobManager`` embeds ``JobScheduler`` for real
subprocess jobs; ``sim.py`` embeds the same scheduler plus the v2
autoscaler FSM so fairness and zero-lost-gang guarantees are testable
without processes.
"""

from .admission import (REASON_INFEASIBLE, REASON_INVALID_WEIGHT,
                        REASON_MALFORMED, REASON_QUOTA,
                        AdmissionController)
from .fairshare import FairShareQueue, dominant_share
from .quota import QuotaLedger, TenantQuota
from .scheduler import DispatchDecision, JobScheduler

__all__ = [
    "AdmissionController", "DispatchDecision", "FairShareQueue",
    "JobScheduler", "QuotaLedger", "TenantQuota", "dominant_share",
    "REASON_INFEASIBLE", "REASON_INVALID_WEIGHT", "REASON_MALFORMED",
    "REASON_QUOTA",
]
