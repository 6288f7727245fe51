"""Admission control: every rejection carries a machine-readable reason
dict (``{"code": ..., "detail": ...}`` plus code-specific fields) that
lands verbatim in ``JobInfo.reason`` — clients branch on ``code``, never
on prose.

Catalogue:

    QUOTA_EXCEEDED        a per-tenant cap bars the submission
                          (``quota`` field says which cap)
    MALFORMED_ENTRYPOINT  the entrypoint can never exec (empty,
                          unparseable shell quoting, wrong type)
    INFEASIBLE_SHAPE      no configured slice topology could EVER hold
                          the gang, even with the fleet scaled to max
    INVALID_WEIGHT        non-positive fair-share weight
"""

from __future__ import annotations

import shlex
from typing import Callable, List, Optional

REASON_QUOTA = "QUOTA_EXCEEDED"
REASON_MALFORMED = "MALFORMED_ENTRYPOINT"
REASON_INFEASIBLE = "INFEASIBLE_SHAPE"
REASON_INVALID_WEIGHT = "INVALID_WEIGHT"


def _reject(code: str, detail: str, **extra) -> dict:
    out = {"code": code, "detail": detail}
    out.update(extra)
    return out


def check_entrypoint(entrypoint) -> Optional[dict]:
    if not isinstance(entrypoint, str):
        return _reject(REASON_MALFORMED,
                       f"entrypoint must be a string, got "
                       f"{type(entrypoint).__name__}")
    if not entrypoint.strip():
        return _reject(REASON_MALFORMED, "entrypoint is empty")
    try:
        argv = shlex.split(entrypoint)
    except ValueError as e:  # unbalanced quote / trailing escape
        return _reject(REASON_MALFORMED,
                       f"entrypoint does not parse as a shell "
                       f"command: {e}")
    if not argv:
        return _reject(REASON_MALFORMED, "entrypoint is empty")
    return None


def check_feasible(shape: Optional[dict],
                   envelope: List[dict]) -> Optional[dict]:
    """``envelope``: one row per launchable slice topology —
    ``{"name", "resources" (per-host), "hosts"}``. A gang is feasible
    iff SOME single topology's aggregate (per-host x hosts) covers every
    resource of the shape jointly: a slice is the gang unit, so a shape
    no slice can hold will pend forever no matter how far the fleet
    scales out."""
    if not shape or not any(shape.values()):
        return None
    if not envelope:
        return None  # no topology info: admit (scheduler may learn later)
    for t in envelope:
        hosts = max(1, int(t.get("hosts", 1)))
        per_host = t.get("resources", {})
        if all(per_host.get(k, 0) * hosts >= v
               for k, v in shape.items() if v):
            return None
    biggest = {}
    for t in envelope:
        hosts = max(1, int(t.get("hosts", 1)))
        for k, v in t.get("resources", {}).items():
            biggest[k] = max(biggest.get(k, 0), v * hosts)
    return _reject(
        REASON_INFEASIBLE,
        f"no configured slice topology can hold the gang {shape} "
        f"(largest slice aggregate: {biggest})",
        shape=dict(shape), largest=biggest)


class AdmissionController:
    """Composes the checks; ``envelope_fn`` lazily supplies the fleet's
    launchable topologies (it may be unknown until an autoscaler
    publishes its config)."""

    def __init__(self, quotas,
                 envelope_fn: Optional[Callable[[], List[dict]]] = None):
        self.quotas = quotas
        self.envelope_fn = envelope_fn

    def check(self, tenant: str, entrypoint: str,
              shape: Optional[dict], weight: float = 1.0
              ) -> Optional[dict]:
        """Reason dict if the submission must be rejected, else None.
        Cheapest checks first; the first failure wins."""
        if not isinstance(weight, (int, float)) or weight <= 0:
            return _reject(REASON_INVALID_WEIGHT,
                           f"fair-share weight must be > 0, got "
                           f"{weight!r}")
        bad = check_entrypoint(entrypoint)
        if bad is not None:
            return bad
        violation = self.quotas.check_submit(tenant, shape)
        if violation is not None:
            return _reject(REASON_QUOTA, violation.pop("detail"),
                           **violation)
        envelope = self.envelope_fn() if self.envelope_fn else []
        return check_feasible(shape, envelope or [])
