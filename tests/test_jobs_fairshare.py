"""Pure-unit tests for the multi-tenant job plane's decision cores:
stride/DRF fair-share math, quota accounting across finish/crash/stop
races, and the admission-rejection catalogue. No cluster, no clocks —
everything here is deterministic arithmetic.
"""

import pytest

from ray_tpu.jobs import (REASON_INFEASIBLE, REASON_INVALID_WEIGHT,
                          REASON_MALFORMED, REASON_QUOTA, JobScheduler,
                          QuotaLedger, TenantQuota)
from ray_tpu.jobs.admission import (AdmissionController, check_entrypoint,
                                    check_feasible)
from ray_tpu.jobs.fairshare import (DEFAULT_JOB_COST, MIN_JOB_COST,
                                    FairShareQueue, dominant_share,
                                    job_cost)

CAP = {"CPU": 100.0, "TPU": 32.0}


# ---------------------------------------------------------------------------
# DRF cost math
# ---------------------------------------------------------------------------
def test_dominant_share_is_max_over_resources():
    assert dominant_share({"CPU": 50, "TPU": 8}, CAP) == 0.5
    assert dominant_share({"CPU": 10, "TPU": 16}, CAP) == 0.5
    assert dominant_share({}, CAP) == 0.0
    # Resources the cluster doesn't have contribute nothing.
    assert dominant_share({"GPU": 4}, CAP) == 0.0


def test_job_cost_floors():
    assert job_cost(None, CAP) == DEFAULT_JOB_COST
    assert job_cost({}, CAP) == DEFAULT_JOB_COST
    assert job_cost({"CPU": 0}, CAP) == DEFAULT_JOB_COST
    # A tiny gang still advances the pass.
    assert job_cost({"CPU": 1e-9}, CAP) == MIN_JOB_COST


# ---------------------------------------------------------------------------
# Stride scheduling
# ---------------------------------------------------------------------------
def _drain(q, n, capacity=None):
    """Dispatch n times; return the tenant sequence."""
    out = []
    for _ in range(n):
        picked = q.next_dispatch(capacity or CAP)
        if picked is None:
            break
        out.append(picked[0])
    return out


def test_stride_serves_proportionally_to_weights():
    q = FairShareQueue()
    q.tenant("a", weight=1.0)
    q.tenant("b", weight=3.0)
    for i in range(40):
        q.enqueue("a", f"a{i}", {"TPU": 4})
        q.enqueue("b", f"b{i}", {"TPU": 4})
    served = _drain(q, 40)
    # Equal-cost jobs: b should get ~3x a's dispatches in any window.
    assert served.count("b") == 30
    assert served.count("a") == 10


def test_stride_drf_equalizes_weighted_dominant_cost():
    """Unequal job sizes: the big-gang tenant gets FEWER dispatches so
    that served cost per weight stays balanced."""
    q = FairShareQueue()
    q.tenant("small", weight=1.0)
    q.tenant("big", weight=1.0)
    for i in range(64):
        q.enqueue("small", f"s{i}", {"TPU": 4})   # cost 0.125
        q.enqueue("big", f"b{i}", {"TPU": 16})    # cost 0.5
    _drain(q, 40)
    stats = q.stats(CAP)
    ratio = stats["small"]["served_cost"] / stats["big"]["served_cost"]
    assert 0.8 <= ratio <= 1.25


def test_newcomer_joins_at_virtual_time_not_zero():
    q = FairShareQueue()
    q.tenant("old", weight=1.0)
    for i in range(20):
        q.enqueue("old", f"o{i}", {"TPU": 4})
    _drain(q, 10)
    # A tenant arriving late must not replay the past: it joins at the
    # current virtual time and only competes for FUTURE capacity.
    for i in range(10):
        q.enqueue("new", f"n{i}", {"TPU": 4})
    served = _drain(q, 10)
    assert served.count("new") == 5
    assert served.count("old") == 5


def test_rejoin_after_idle_forfeits_banked_credit():
    q = FairShareQueue()
    for i in range(10):
        q.enqueue("a", f"a{i}", {"TPU": 4})
        q.enqueue("b", f"b{i}", {"TPU": 4})
    _drain(q, 4)
    # b drains completely and idles while a keeps working.
    while q.queue_depth("b"):
        assert q.next_dispatch(CAP) is not None
    _drain(q, q.queue_depth("a") - 2)
    # b re-joins: its stale low pass is forfeited, so it cannot claim
    # every remaining slot as "owed".
    q.enqueue("b", "b-back", {"TPU": 4})
    t_b = q.tenant("b")
    assert t_b.pass_value >= q.tenant("a").pass_value


def test_veto_skips_tenant_without_advancing_pass():
    q = FairShareQueue()
    q.enqueue("a", "a0", {"TPU": 4})
    q.enqueue("b", "b0", {"TPU": 4})
    before = q.tenant("a").pass_value
    picked = q.next_dispatch(CAP, can_dispatch=lambda t, j, s: t != "a")
    assert picked[0] == "b"
    assert q.tenant("a").pass_value == before
    assert q.queue_depth("a") == 1  # job still queued


def test_requeue_front_keeps_head_of_line():
    q = FairShareQueue()
    q.enqueue("a", "a0", {"TPU": 4})
    q.enqueue("a", "a1", {"TPU": 4})
    name, jid, shape, _ = q.next_dispatch(CAP)
    assert jid == "a0"
    q.on_finish(name, shape)
    q.enqueue("a", "a0", shape, front=True)
    assert q.next_dispatch(CAP)[1] == "a0"  # recovered job goes first


def test_usage_accounting_finish_and_shares():
    q = FairShareQueue()
    q.enqueue("a", "a0", {"TPU": 8})
    q.next_dispatch(CAP)
    assert q.shares(CAP)["a"] == 0.25
    q.on_finish("a", {"TPU": 8})
    assert q.shares(CAP)["a"] == 0.0
    assert q.tenant("a").running == 0
    # Double-finish must not go negative.
    q.on_finish("a", {"TPU": 8})
    assert q.tenant("a").running == 0


def test_invalid_weight_raises():
    q = FairShareQueue()
    with pytest.raises(ValueError):
        q.tenant("a", weight=0.0)
    with pytest.raises(ValueError):
        q.tenant("a", weight=-2.0)


# ---------------------------------------------------------------------------
# Quota ledger
# ---------------------------------------------------------------------------
def test_quota_pending_cap_rejects_at_admission():
    led = QuotaLedger()
    led.set_quota("t", TenantQuota(max_pending_jobs=2))
    led.note_pending("t", "j1")
    led.note_pending("t", "j2")
    v = led.check_submit("t", None)
    assert v["quota"] == "max_pending_jobs" and v["cap"] == 2


def test_quota_single_job_over_resource_cap_rejects():
    led = QuotaLedger()
    led.set_quota("t", TenantQuota(resources={"TPU": 8}))
    v = led.check_submit("t", {"TPU": 16})
    assert v["quota"] == "resources" and v["resource"] == "TPU"
    assert led.check_submit("t", {"TPU": 8}) is None


def test_quota_aggregate_resources_throttle_dispatch():
    led = QuotaLedger()
    led.set_quota("t", TenantQuota(resources={"TPU": 8}))
    led.charge("t", "j1", {"TPU": 4})
    assert led.can_start("t", {"TPU": 4})
    led.charge("t", "j2", {"TPU": 4})
    assert not led.can_start("t", {"TPU": 4})  # would exceed 8
    led.release("t", "j1")
    assert led.can_start("t", {"TPU": 4})


def test_quota_max_running_throttles_dispatch():
    led = QuotaLedger()
    led.set_quota("t", TenantQuota(max_running_jobs=1))
    assert led.can_start("t", None)
    led.charge("t", "j1", None)
    assert not led.can_start("t", None)


def test_quota_release_is_idempotent_across_races():
    """finish + crash + stop can all try to release: only the first
    call returns the shape (and credits usage)."""
    led = QuotaLedger()
    led.charge("t", "j1", {"TPU": 4})
    assert led.release("t", "j1") == {"TPU": 4}
    assert led.release("t", "j1") is None
    assert led.release("t", "j1") is None
    assert led.usage("t") == {}


# ---------------------------------------------------------------------------
# Admission catalogue
# ---------------------------------------------------------------------------
ENVELOPE = [{"name": "v5e-2x2", "resources": {"TPU": 4, "CPU": 8},
             "hosts": 1},
            {"name": "v5e-4x8", "resources": {"TPU": 4, "CPU": 8},
             "hosts": 8}]


def test_entrypoint_rejections():
    assert check_entrypoint(None)["code"] == REASON_MALFORMED
    assert check_entrypoint("")["code"] == REASON_MALFORMED
    assert check_entrypoint("   ")["code"] == REASON_MALFORMED
    assert check_entrypoint('python -c "unclosed')["code"] \
        == REASON_MALFORMED
    assert check_entrypoint("python train.py --lr 3e-4") is None


def test_feasibility_is_single_slice_joint_coverage():
    # Fits the 4x8 aggregate (TPU 32, CPU 64).
    assert check_feasible({"TPU": 32}, ENVELOPE) is None
    # No single topology holds TPU=64, even though two 4x8s would.
    r = check_feasible({"TPU": 64}, ENVELOPE)
    assert r["code"] == REASON_INFEASIBLE and r["largest"]["TPU"] == 32
    # Joint coverage: TPU fits the 4x8 but CPU=100 exceeds its 64.
    assert check_feasible({"TPU": 8, "CPU": 100},
                          ENVELOPE)["code"] == REASON_INFEASIBLE
    # Unknown envelope admits (scheduler may learn it later).
    assert check_feasible({"TPU": 10 ** 6}, []) is None


def test_admission_controller_order_and_codes():
    led = QuotaLedger()
    led.set_quota("t", TenantQuota(resources={"TPU": 8}))
    adm = AdmissionController(led, envelope_fn=lambda: ENVELOPE)
    assert adm.check("t", "run", None, weight=-1)["code"] \
        == REASON_INVALID_WEIGHT
    assert adm.check("t", "", None)["code"] == REASON_MALFORMED
    assert adm.check("t", "run", {"TPU": 16})["code"] == REASON_QUOTA
    assert adm.check("u", "run", {"TPU": 64})["code"] == REASON_INFEASIBLE
    assert adm.check("u", "run", {"TPU": 4}) is None


# ---------------------------------------------------------------------------
# JobScheduler composition: one ledger, consistent accounting
# ---------------------------------------------------------------------------
def _sched(**kw):
    ts = [0.0]

    def clock():
        ts[0] += 1.0
        return ts[0]

    return JobScheduler(capacity_fn=lambda: CAP,
                        envelope_fn=lambda: ENVELOPE, clock=clock, **kw)


def test_scheduler_submit_dispatch_finish_ledger():
    s = _sched()
    assert s.submit("j1", tenant="a", shape={"TPU": 4},
                    entrypoint="run") is None
    d = s.next_dispatch()
    assert d.job_id == "j1" and d.tenant == "a" and d.cost == 0.125
    s.on_finish("j1")
    kinds = [e["kind"] for e in s.events()]
    assert kinds == ["admitted", "dispatched", "finished"]


def test_scheduler_rejection_lands_in_ledger_with_reason():
    s = _sched()
    reason = s.submit("bad", tenant="a", shape={"TPU": 64},
                      entrypoint="run")
    assert reason["code"] == REASON_INFEASIBLE
    ev = s.events()[-1]
    assert ev["kind"] == "rejected" and ev["reason"]["code"] \
        == REASON_INFEASIBLE
    assert s.next_dispatch() is None  # nothing queued


def test_scheduler_requeue_restores_quota_and_priority():
    s = _sched()
    s.set_quota("a", TenantQuota(max_running_jobs=1))
    s.submit("j1", tenant="a", shape={"TPU": 4}, entrypoint="run")
    s.submit("j2", tenant="a", shape={"TPU": 4}, entrypoint="run")
    assert s.next_dispatch().job_id == "j1"
    assert s.next_dispatch() is None  # max_running_jobs=1
    s.requeue("j1")  # gang lost: quota charge released, j1 back at head
    assert s.next_dispatch().job_id == "j1"


def test_scheduler_on_finish_idempotent_and_crash_safe():
    s = _sched()
    s.submit("j1", tenant="a", shape={"TPU": 4}, entrypoint="run")
    s.next_dispatch()
    s.on_finish("j1", outcome="crashed")
    s.on_finish("j1", outcome="finished")  # racing settle: no-op
    stats = s.stats()
    assert stats["a"]["running"] == 0 and stats["a"]["usage"] == {}
    assert [e["kind"] for e in s.events()].count("finished") == 2
    assert s.quotas.release("a", "j1") is None


def test_scheduler_cancel_queued_job():
    s = _sched()
    s.submit("j1", tenant="a", shape={"TPU": 4}, entrypoint="run")
    assert s.cancel("j1") is True
    assert s.next_dispatch() is None
    assert s.cancel("j1") is False  # already gone


def test_scheduler_adopt_running_counts_usage_without_pass():
    s = _sched()
    s.adopt_running("j1", tenant="a", shape={"TPU": 8})
    stats = s.stats()
    assert stats["a"]["running"] == 1 and stats["a"]["usage"] == {"TPU": 8}
    assert stats["a"]["pass"] == 0.0  # no dispatch decision was made
    s.on_finish("j1")
    assert s.stats()["a"]["running"] == 0


def test_scheduler_pending_shapes_feed():
    s = _sched()
    s.submit("j1", tenant="a", shape={"TPU": 4}, entrypoint="run")
    s.submit("j2", tenant="b", shape={"TPU": 16}, entrypoint="run")
    s.submit("j3", tenant="b", shape=None, entrypoint="run")  # shapeless
    feed = s.pending_shapes()
    assert {"TPU": 4} in feed and {"TPU": 16} in feed and len(feed) == 2
