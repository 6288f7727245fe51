"""SLO alerting plane (PR 20): burn-rate math with hand-computed
window numbers, incident lifecycle (dedup, refire, hysteresis),
evidence snapshots, serve-SLO pruning, the pinned `rtpu alerts --json`
schema, and an end-to-end breach of a tight TTFT objective on a real
streaming LLM deployment.
"""

import dataclasses
import json
import time
import urllib.request

import pytest

from ray_tpu._private.alerting import AlertEngine
from ray_tpu._private.telemetry import TelemetryStore
from ray_tpu.util.slo import (BurnRatePolicy, MultiWindowBurnRate,
                              SLOObjective)

# Shared hand-check policy: budget 0.25 means a >25% violating fraction
# burns faster than budget; fast fires at burn 2.0 (50% violating),
# slow confirms at 1.2 (30% violating).
OBJ = dict(name="r", metric="m", target=100.0, comparison="<=",
           budget=0.25)
POL = dict(fast_window_s=10.0, slow_window_s=100.0, fast_burn=2.0,
           slow_burn=1.2, resolve_burn=1.0, resolve_hold_s=30.0,
           min_points=4)


def _mwbr(**pol):
    return MultiWindowBurnRate(SLOObjective(**OBJ),
                               BurnRatePolicy(**{**POL, **pol}))


# ---------------------------------------------------------------------------
# Burn-rate math (pure, hand-computed)
# ---------------------------------------------------------------------------
def test_objective_directions_and_validation():
    ceil = SLOObjective("a", "m", 100.0, "<=")
    assert ceil.violated(150.0) and not ceil.violated(100.0)
    floor = SLOObjective("b", "m", 0.5, ">=")
    assert floor.violated(0.2) and not floor.violated(0.5)
    with pytest.raises(ValueError):
        SLOObjective("c", "m", 1.0, "==")
    with pytest.raises(ValueError):
        SLOObjective("d", "m", 1.0, budget=0.0)


def test_fire_with_hand_computed_burn_rates():
    m = _mwbr()
    # t=0..3 good (50), t=4..9 violating (150): both windows hold all
    # 10 samples -> 6/10 violating / 0.25 budget = burn 2.4.
    for t in range(4):
        m.add(float(t), 50.0)
    for t in range(4, 10):
        m.add(float(t), 150.0)
    assert m.evaluate(9.0) == "fire"
    assert m.state == "firing"
    assert m.fast_burn_rate == pytest.approx(2.4)
    assert m.slow_burn_rate == pytest.approx(2.4)


def test_slow_window_confirms_before_a_fire():
    """A hot fast window alone never pages: 40 good samples of history
    hold the slow burn under threshold until the breach is sustained.
    Fire lands exactly at t=57: bad(40..57)=18 of 58 in the slow
    window -> 0.3103/0.25 = 1.24 >= 1.2 (t=56 gives 1.193 < 1.2)."""
    m = _mwbr()
    for t in range(40):
        m.add(float(t), 50.0)
    fired_at = None
    for t in range(40, 76):
        m.add(float(t), 150.0)
        tr = m.evaluate(float(t))
        if tr == "fire":
            fired_at = t
            break
        # fast window is hot almost immediately; the slow window is
        # what holds the page back.
        if t >= 45:
            assert m.fast_burn_rate >= 2.0
    assert fired_at == 57


def test_min_points_one_slow_request_never_pages():
    m = _mwbr()
    for t in range(3):
        m.add(float(t), 150.0)
    # Burn is 4.0 in both windows but only 3 samples exist.
    assert m.evaluate(2.0) is None and m.state == "ok"
    m.add(3.0, 150.0)
    assert m.evaluate(3.0) == "fire"


def test_hysteresis_resolve_after_hold():
    """Resolve needs BOTH windows below resolve_burn for resolve_hold_s
    continuously. With bad samples at t=4..9, the slow window drops
    below burn 1.0 at t=24 (6/25 = 0.24 < budget 0.25), so the resolve
    lands exactly at t=24+30=54."""
    m = _mwbr()
    for t in range(4):
        m.add(float(t), 50.0)
    for t in range(4, 10):
        m.add(float(t), 150.0)
    assert m.evaluate(9.0) == "fire"
    resolved_at = None
    for t in range(10, 60):
        m.add(float(t), 50.0)
        tr = m.evaluate(float(t))
        if tr == "resolve":
            resolved_at = t
            break
        assert m.state == "firing"
    assert resolved_at == 54
    assert m.state == "ok"


def test_window_buffer_compacts_and_counts_survive():
    """The shared sample buffer drops its dead prefix once the slow
    cursor runs past _COMPACT_AT; window counts must survive it."""
    m = _mwbr(fast_window_s=5.0, slow_window_s=10.0)
    for t in range(2000):
        m.add(float(t), 150.0 if t % 2 else 50.0)
    assert len(m._ts) < 2 * m._COMPACT_AT
    # Last add at ts=1999: slow keeps 1989..1999 (11 samples, 6 odd ->
    # violating), fast keeps 1994..1999 (6 samples, 3 violating).
    assert m.slow_total == 11 and m.slow_bad == 6
    assert m.fast_total == 6 and m.fast_bad == 3
    assert m.evaluate(1999.0) == "fire"
    assert m.fast_burn_rate == pytest.approx((3 / 6) / 0.25)
    assert m.slow_burn_rate == pytest.approx((6 / 11) / 0.25)


# ---------------------------------------------------------------------------
# AlertEngine: incidents, dedup, refire, idle-decay guard
# ---------------------------------------------------------------------------
def _engine(**kw):
    return AlertEngine(TelemetryStore(), **kw)


def _beat(eng, t, **metrics):
    eng.observe([{"ts": float(t), "metrics": metrics}], now=float(t))
    return eng.evaluate(now=float(t))


TIGHT = dict(fast_window_s=2.0, slow_window_s=4.0, fast_burn=1.0,
             slow_burn=1.0, resolve_burn=1.0, resolve_hold_s=2.0,
             min_points=2)


def test_flapping_rule_reopens_one_deduplicated_incident():
    eng = _engine()
    eng.declare({"name": "r", "metric": "m1", "target": 100.0,
                 "comparison": "<=", "budget": 0.5, **TIGHT})
    # Breach: fires on the 2nd sample (min_points=2, every sample bad).
    assert _beat(eng, 0, m1=200.0) == []
    out = _beat(eng, 1, m1=200.0)
    assert [o["transition"] for o in out] == ["fire"]
    iid = out[0]["incident"]
    # Continued breach dedups into the open incident: no transitions,
    # still exactly one incident.
    assert _beat(eng, 2, m1=200.0) == []
    assert _beat(eng, 3, m1=200.0) == []
    assert len(eng.list_incidents()) == 1

    # Recovery: samples expire, burn drops to 0, hold 2s, resolve.
    assert eng.evaluate(now=6.0) == []      # slow window still has t=3
    assert eng.evaluate(now=8.0) == []      # below starts here
    out = eng.evaluate(now=10.0)
    assert [o["transition"] for o in out] == ["resolve"]
    assert eng.get_incident(iid)["state"] == "resolved"

    # Flap back within DEDUP_S: the SAME incident reopens as a refire.
    assert _beat(eng, 11, m1=200.0) == []
    out = _beat(eng, 12, m1=200.0)
    assert [o["transition"] for o in out] == ["fire"]
    assert out[0]["incident"] == iid
    assert len(eng.list_incidents()) == 1
    inc = eng.get_incident(iid)
    assert inc["state"] == "open" and inc["refires"] == 1
    # I410 contract: every transition landed in the event log.
    assert [e["kind"] for e in inc["events"]] == \
        ["open", "resolve", "refire"]


def test_decayed_zero_series_cannot_hold_a_floor_alert_open():
    """A '>=' floor rule on a gauge that idle-decays to 0: the zeros
    count only within the shared decay window of the signal change;
    after that they are skipped, the windows drain, and the alert
    resolves instead of staying open forever on a dead producer."""
    eng = _engine()
    eng.declare({"name": "mfu-floor", "metric": "llm_mfu:d",
                 "target": 0.5, "comparison": ">=", "budget": 0.5,
                 **TIGHT})
    for t in range(5):                       # healthy
        assert _beat(eng, t, **{"llm_mfu:d": 0.9}) == []
    fired = []
    for t in range(5, 40):                   # producer died -> 0.0
        fired.extend(o["transition"]
                     for o in _beat(eng, t, **{"llm_mfu:d": 0.0}))
    # The first zeros are a real breach (signal changed) and fire...
    assert "fire" in fired
    # ...but past the decay window the zeros are skipped, so the
    # windows drained and the alert auto-resolved.
    assert "resolve" in fired
    st = eng._rules["mfu-floor"]
    assert st.mwbr.state == "ok"
    assert st.mwbr.slow_total == 0


def test_redeclare_keeps_the_open_incident():
    eng = _engine()
    eng.declare({"name": "r", "metric": "m1", "target": 100.0,
                 "budget": 0.5, **TIGHT})
    _beat(eng, 0, m1=200.0)
    out = _beat(eng, 1, m1=200.0)
    iid = out[0]["incident"]
    row = eng.declare({"name": "r", "metric": "m1", "target": 150.0,
                       "budget": 0.5, **TIGHT})
    assert row["target"] == 150.0
    assert eng._rules["r"].incident_id == iid
    assert len(eng.list_incidents()) == 1


def test_incident_store_is_bounded():
    eng = _engine()
    eng.MAX_INCIDENTS = 5
    for i in range(8):
        eng.declare({"name": f"r{i}", "metric": f"m{i}", "target": 1.0,
                     "budget": 0.5, **TIGHT})
        _beat(eng, 2 * i, **{f"m{i}": 9.0})
        _beat(eng, 2 * i + 1, **{f"m{i}": 9.0})
    assert len(eng.list_incidents(limit=100)) == 5


def test_builtin_rules_register_on_first_metric_sight():
    eng = _engine()
    _beat(eng, 0, **{"serve_p95_ms:dep:ttft": 5.0, "llm_kv_util:dep": 0.3,
                     "jobs_queued:tenantA": 2.0, "unrelated": 1.0})
    names = {a["name"]: a for a in eng.list_alerts()}
    assert "builtin-ttft-dep" in names
    assert "builtin-kv-pressure-dep" in names
    assert "builtin-queue-tenantA" in names
    assert all(a["source"] == "builtin" for a in names.values())
    assert all(a["state"] == "ok" for a in names.values())


# ---------------------------------------------------------------------------
# Evidence snapshot
# ---------------------------------------------------------------------------
class _FakeTraces:
    def list(self, deployment=None, limit=50):
        assert deployment == "mydep"
        return [
            {"trace_id": "t-fast", "duration_ms": 10.0, "error": None},
            {"trace_id": "t-slow", "duration_ms": 220.0, "error": None},
        ]


def test_incident_evidence_snapshot():
    store = TelemetryStore(interval=1.0)
    kv = {"gang_doctor/run1": json.dumps(
        {"gang": "run1", "summary": "rank 2 desynced"}),
        "other/key": "not json"}
    eng = AlertEngine(store, traces=_FakeTraces(), kv=kv)
    metric = "serve_p95_ms:mydep:ttft"
    samples = []
    for t in range(5):
        samples.append({"ts": float(t), "metrics": {
            metric: 500.0,
            "llm_roofline_verdict:mydep": 3.0 if t < 3 else 2.0,
            "llm_mfu:mydep": 0.12,
        }})
    store.ingest("node1", samples)
    eng.declare({"name": "ttft", "metric": metric, "target": 100.0,
                 "budget": 0.5, **TIGHT})
    for t in range(5):
        eng.observe([samples[t]], now=float(t))
    out = eng.evaluate(now=4.0)
    assert [o["transition"] for o in out] == ["fire"]
    inc = eng.get_incident(out[0]["incident"])
    ev = inc["evidence"]
    assert ev["metric"] == metric and ev["deployment"] == "mydep"
    assert ev["latest_value"] == 500.0
    # Timeseries window snapshotted per node.
    assert [p[1] for p in ev["window"]["node1"]] == [500.0] * 5
    # Exemplar = slowest retained trace for the deployment.
    assert ev["exemplar"]["trace_id"] == "t-slow"
    assert ev["exemplar"]["duration_ms"] == 220.0
    # Coded verdict series decodes in ts order; 0s never appear.
    assert ev["roofline"]["verdicts"] == ["host"] * 3 + ["hbm"] * 2
    assert ev["roofline"]["mfu"] == pytest.approx(0.12)
    # Only gang_doctor/ KV entries that parse as JSON.
    assert ev["gang_verdicts"] == [
        {"gang": "run1", "summary": "rank 2 desynced"}]
    assert inc["summary"].startswith(metric)
    # get_incident hands back a deep copy: mutating it cannot corrupt
    # the stored incident.
    inc["evidence"]["window"]["node1"].clear()
    assert eng.get_incident(inc["id"])["evidence"]["window"]["node1"]


def test_evidence_degrades_without_sources():
    eng = _engine()
    eng.declare({"name": "r", "metric": "plain_metric", "target": 1.0,
                 "budget": 0.5, **TIGHT})
    _beat(eng, 0, plain_metric=9.0)
    out = _beat(eng, 1, plain_metric=9.0)
    ev = eng.get_incident(out[0]["incident"])["evidence"]
    assert ev["deployment"] is None
    assert ev["exemplar"] is None and ev["roofline"] is None
    assert ev["gang_verdicts"] == []
    assert isinstance(ev["job_ledger"], list)


# ---------------------------------------------------------------------------
# serve/slo pruning (satellite 1)
# ---------------------------------------------------------------------------
def test_prune_deployment_clears_cells_and_exemplars():
    from ray_tpu.serve import slo

    slo._reset_for_tests()
    try:
        slo.record_phase("ttft", 0.2, "depA", trace_id="tA")
        slo.record_phase("execute", 0.1, "depA")
        slo.record_phase("ttft", 0.3, "depB", trace_id="tB")
        assert "depA" in slo.all_phase_hists()
        slo.prune_deployment("depA")
        hists = slo.all_phase_hists()
        assert "depA" not in hists
        # Untouched deployment keeps its cells AND its exemplar.
        assert hists["depB"]["ttft"]["exemplar"]["trace_id"] == "tB"
        with slo._lock:
            assert not any(k[0] == "depA" for k in slo._exemplars)
            assert not any(k[0] == "depA" for k in slo._local)
    finally:
        slo._reset_for_tests()


# ---------------------------------------------------------------------------
# Pinned `rtpu alerts --json` schema
# ---------------------------------------------------------------------------
def test_alerts_json_payload_schema_is_pinned():
    from ray_tpu.scripts.cli import _alerts_payload

    alerts = [{"name": "r", "metric": "m", "target": 1.0,
               "comparison": "<=", "severity": "page", "state": "firing",
               "fast_burn_rate": 2.0, "slow_burn_rate": 1.5,
               "since": 123.0, "source": "user",
               "head_grew_a_field": "must be dropped"}]
    incidents = [{"id": "inc-0001", "rule": "r", "metric": "m",
                  "severity": "page", "state": "open", "opened": 123.0,
                  "resolved": None, "refires": 0, "summary": "s",
                  "evidence": {"huge": "blob"}}]
    doc = _alerts_payload(alerts, incidents)
    assert doc["version"] == 1
    assert set(doc["alerts"][0]) == {
        "name", "metric", "target", "comparison", "severity", "state",
        "fast_burn_rate", "slow_burn_rate", "since", "source"}
    assert set(doc["incidents"][0]) == {
        "id", "rule", "metric", "severity", "state", "opened",
        "resolved", "refires", "summary"}
    # Head-side additions and the evidence blob never leak into the
    # pinned document.
    assert "head_grew_a_field" not in doc["alerts"][0]
    assert "evidence" not in doc["incidents"][0]
    json.dumps(doc)  # must be directly serializable


# ---------------------------------------------------------------------------
# End-to-end: a real streaming LLM deployment past a tight TTFT
# objective -> one deduplicated incident with resolvable evidence ->
# auto-resolve after recovery -> refire on a renewed breach.
# ---------------------------------------------------------------------------
@pytest.fixture(autouse=True)
def _restore_global_config():
    from ray_tpu._private.config import get_config

    cfg = get_config()
    saved = dataclasses.asdict(cfg)
    yield
    for k, v in saved.items():
        setattr(cfg, k, v)


def _stream_http(url, payload, timeout=180):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return [json.loads(line) for line in r.read().splitlines()
                if line.strip()]


def test_e2e_ttft_breach_incident_with_evidence_and_autoresolve(capsys):
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    import ray_tpu
    from ray_tpu.models.gpt import GPTConfig
    from ray_tpu.util import state

    cfg = GPTConfig(vocab_size=512, max_seq=128, d_model=64, n_layer=2,
                    n_head=4, dtype=jnp.float32)
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=2, system_config={
        "telemetry_sample_interval_s": 0.05})
    from ray_tpu import serve

    try:
        # Job plane FIRST, so slo_breach ledger events have a manager
        # to land in.
        from ray_tpu.job_submission import JobSubmissionClient

        client = JobSubmissionClient()

        from ray_tpu.serve.llm import build_app

        serve.run(build_app(cfg, num_blocks=64, block_size=8,
                            max_batch=4), name="llm")
        proxy = serve.start(http_port=0)
        url = f"http://127.0.0.1:{proxy.port}/"

        def hit(seed):
            frames = _stream_http(
                url, {"prompt": [1, 2, 3], "max_tokens": 4,
                      "seed": seed})
            assert frames[-1]["done"]

        for i in range(3):
            hit(i)
        # Wait for the TTFT and step-time series and a finalized trace
        # of the deployment to exist before declaring, so the incident
        # opens with full evidence (the evidence is a snapshot at open).
        want = {"serve_p95_ms:LLMServer:ttft",
                "llm_device_ms:LLMServer"}
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            if want <= set(state.timeseries_metrics()) \
                    and state.list_traces(deployment="LLMServer"):
                break
            time.sleep(0.2)
        assert want <= set(state.timeseries_metrics())
        assert state.list_traces(deployment="LLMServer")

        row = state.declare_slo({
            "name": "e2e-ttft", "metric": "serve_p95_ms:LLMServer:ttft",
            "target": 1e-6, "comparison": "<=", "budget": 0.01,
            "severity": "page", "fast_window_s": 3.0,
            "slow_window_s": 6.0, "min_points": 3,
            "resolve_hold_s": 0.5})
        assert row["name"] == "e2e-ttft" and row["state"] == "ok"

        # Breach: every TTFT sample violates a sub-microsecond target.
        deadline = time.monotonic() + 90
        incident = None
        seed = 100
        while time.monotonic() < deadline:
            hit(seed)
            seed += 1
            incs = [i for i in state.list_incidents()
                    if i["rule"] == "e2e-ttft"]
            if incs and incs[0]["state"] == "open":
                incident = incs[0]
                break
            time.sleep(0.3)
        assert incident is not None, state.list_alerts()
        assert incident["severity"] == "page"
        # Exactly ONE deduplicated incident despite many breaching
        # beats.
        assert len([i for i in state.list_incidents()
                    if i["rule"] == "e2e-ttft"]) == 1
        alerts = {a["name"]: a for a in state.list_alerts()}
        assert alerts["e2e-ttft"]["state"] == "firing"

        # Evidence bundle: trace_id resolves. (Roofline evidence from
        # synthetic series: test_incident_evidence_* above.)
        inc = state.get_incident(incident["id"])
        ev = inc["evidence"]
        assert ev["deployment"] == "LLMServer"
        assert ev["window"], ev
        assert ev["exemplar"] and ev["exemplar"]["trace_id"]
        spans = state.get_trace(ev["exemplar"]["trace_id"])
        assert spans, "exemplar trace_id must resolve via state.get_trace"
        # The CPU backend has no peak: the engine publishes no MFU and
        # no verdict, so there is no roofline evidence to attach.
        assert not ev.get("roofline")
        assert inc["events"][0]["kind"] == "open"

        # Ledger: the breach landed in the job-plane decision ledger.
        deadline = time.monotonic() + 30
        kinds = []
        while time.monotonic() < deadline:
            kinds = [e["kind"] for e in client.list_job_events(200)]
            if "slo_breach" in kinds:
                break
            time.sleep(0.3)
        assert "slo_breach" in kinds

        # Surface 1: CLI (alerts table, banner, incident render).
        import argparse

        from ray_tpu.scripts import cli

        cli.cmd_alerts(argparse.Namespace(
            address=None, temp_dir=None, json=False, limit=20))
        out = capsys.readouterr().out
        assert "e2e-ttft" in out and "firing" in out
        assert incident["id"] in out
        cli._alerts_banner()
        assert "ALERTS FIRING" in capsys.readouterr().out
        cli.cmd_incident_show(argparse.Namespace(
            address=None, temp_dir=None, json=False, id=incident["id"]))
        out = capsys.readouterr().out
        assert incident["id"] in out
        assert "roofline" not in out    # no peak on the CPU backend
        assert "serve.request" in out   # exemplar waterfall rendered

        # Surface 2: dashboard pane data.
        from ray_tpu import dashboard

        pane = dashboard._alerts()
        assert any(a["name"] == "e2e-ttft" for a in pane["alerts"])
        assert any(i["id"] == incident["id"] for i in pane["incidents"])

        # Recovery: stop traffic -> p95 deltas stop -> windows drain ->
        # hysteresis hold -> auto-resolve.
        deadline = time.monotonic() + 60
        resolved = False
        while time.monotonic() < deadline:
            if state.get_incident(incident["id"])["state"] == "resolved":
                resolved = True
                break
            time.sleep(0.5)
        assert resolved, state.list_alerts()
        kinds = {e["kind"] for e in
                 state.get_incident(incident["id"])["events"]}
        assert {"open", "resolve"} <= kinds

        # Renewed breach inside the dedup window refires the SAME
        # incident instead of opening a second one.
        deadline = time.monotonic() + 90
        reopened = None
        while time.monotonic() < deadline:
            hit(seed)
            seed += 1
            inc3 = state.get_incident(incident["id"])
            if inc3["state"] == "open" and inc3["refires"] >= 1:
                reopened = inc3
                break
            time.sleep(0.3)
        assert reopened is not None, state.list_alerts()
        assert len([i for i in state.list_incidents()
                    if i["rule"] == "e2e-ttft"]) == 1
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
