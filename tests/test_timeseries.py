"""Cluster telemetry plane (ISSUE 6): node sampler -> heartbeat ->
head ring buffers -> ``state.timeseries()``.

The invariants under test:
  * TieredRing keeps bounded windows per tier and downsamples with
    (mean, in-bucket max) so spikes survive coarsening;
  * the sampler's rate engine is RESET-SAFE: a counter that goes
    backwards reads as a restart (one zero sample, fresh anchor),
    never a negative or bogus-positive rate;
  * the dispatch-queue / pipeline-window high-water gauges catch
    between-sample bursts (mutation-site hooks, lint-enforced in
    test_concurrency_net.py);
  * serve request histograms pushed by workers become per-interval
    p50/p95/p99 series;
  * end to end, a loaded 2-node cluster yields >= 60 consecutive
    samples per hop metric from ``state.timeseries()``.
"""

import collections
import time
import types

import pytest

import ray_tpu
from ray_tpu._private.telemetry import (TelemetrySampler, TelemetryStore,
                                        TieredRing, quantile_from_buckets)
from ray_tpu.util import state


@pytest.fixture(autouse=True)
def _restore_global_config():
    """init(system_config=...) mutates the process-wide config
    singleton; without this, the 0.05s/0.0s intervals these tests set
    would leak into every later in-process runtime in the session."""
    import dataclasses

    from ray_tpu._private.config import get_config

    cfg = get_config()
    saved = dataclasses.asdict(cfg)
    yield
    for k, v in saved.items():
        setattr(cfg, k, v)


# ---------------------------------------------------------------------------
# Ring retention + downsampling
# ---------------------------------------------------------------------------
def test_tiered_ring_retention_and_downsampling():
    ring = TieredRing({1: 5, 10: 3, 60: 2})
    for i in range(100):
        ring.append(float(i), float(i), 1.0)

    base = ring.samples(1)
    assert len(base) == 5  # bounded
    assert [v for _, v, _ in base] == [95.0, 96.0, 97.0, 98.0, 99.0]

    # Tier 10: buckets of 10 base samples; bucket 9 (90..99) is still
    # open, closed buckets 6/7/8 survive in the maxlen-3 ring.
    t10 = ring.samples(10)
    assert len(t10) == 3  # bounded
    means = [v for _, v, _ in t10]
    highs = [hi for _, _, hi in t10]
    assert means == [64.5, 74.5, 84.5]  # bucket means
    assert highs == [69.0, 79.0, 89.0]  # spikes survive as the max

    # A spike inside one bucket is preserved by ``hi`` even though the
    # mean smooths it.
    ring2 = TieredRing({1: 5, 10: 3, 60: 2})
    for i in range(20):
        ring2.append(float(i), 1000.0 if i == 3 else 0.0, 1.0)
    (_, mean0, hi0) = ring2.samples(10)[0]
    assert hi0 == 1000.0 and mean0 == 100.0


def test_store_query_bounds_filters_and_drop():
    store = TelemetryStore(interval=1.0, sizes={1: 4, 10: 2, 60: 1})
    for node in ("aa", "bb"):
        store.ingest(node, [{"ts": float(i), "metrics": {"m1": float(i),
                                                         "m2": 1.0}}
                            for i in range(50)])

    out = store.query(resolution=1.0)
    assert out["resolution"] == 1.0
    assert set(out["series"]) == {"m1", "m2"}
    assert set(out["series"]["m1"]) == {"aa", "bb"}
    assert len(out["series"]["m1"]["aa"]) == 4  # base window bound

    # Coarse query snaps DOWN to the largest tier at or below request.
    coarse = store.query(metric="m1", resolution=30.0)
    assert coarse["resolution"] == 10.0
    assert set(coarse["series"]) == {"m1"}
    assert len(coarse["series"]["m1"]["aa"]) <= 2

    one = store.query(metric="m1", node_id="bb")
    assert set(one["series"]["m1"]) == {"bb"}

    assert {m for m, *_ in store.latest()} == {"m1", "m2"}
    store.drop_node("aa")
    assert set(store.query()["series"]["m1"]) == {"bb"}


def test_quantile_from_buckets():
    # 10 observations uniformly inside (1, 2].
    bounds = [1.0, 2.0, 3.0]
    counts = [0, 10, 0, 0]
    assert quantile_from_buckets(counts, bounds, 0.5) == pytest.approx(1.5)
    assert quantile_from_buckets(counts, bounds, 0.99) == pytest.approx(
        1.99)
    assert quantile_from_buckets([0, 0, 0, 0], bounds, 0.5) == 0.0
    # Mass in the +Inf bucket reads as the last finite bound.
    assert quantile_from_buckets([0, 0, 0, 5], bounds, 0.99) == 3.0


# ---------------------------------------------------------------------------
# Sampler unit tests against a fake node
# ---------------------------------------------------------------------------
class _FakeWorker:
    def __init__(self, inflight=0, state="BUSY"):
        self.actor_id = None
        self.proc = object()
        self.state = state
        self.inflight = {i: None for i in range(inflight)}


def _fake_node(pipeline_depth=4):
    return types.SimpleNamespace(
        counters=collections.defaultdict(int),
        pending_cpu=[],
        workers={},
        objects={},
        user_metrics={},
        telemetry_gauges={"dispatch_queue_hw": 0,
                          "pipeline_inflight_hw": 0},
        cfg=types.SimpleNamespace(worker_pipeline_depth=pipeline_depth))


def test_sampler_rates_survive_counter_reset():
    node = _fake_node()
    sampler = TelemetrySampler(node)

    s1 = sampler.sample()["metrics"]
    assert s1["tasks_per_s"] == 0.0  # first sample: no defensible rate

    node.counters["tasks_finished"] = 50
    time.sleep(0.01)
    s2 = sampler.sample()["metrics"]
    assert s2["tasks_per_s"] > 0.0

    # Counter reset (restart): one zero sample, then a fresh anchor.
    node.counters["tasks_finished"] = 3
    s3 = sampler.sample()["metrics"]
    assert s3["tasks_per_s"] == 0.0

    node.counters["tasks_finished"] = 13
    time.sleep(0.01)
    s4 = sampler.sample()["metrics"]
    assert s4["tasks_per_s"] > 0.0  # delta of 10 from the new anchor


def test_sampler_high_water_gauges_reset_per_sample():
    node = _fake_node(pipeline_depth=4)
    sampler = TelemetrySampler(node)
    # A burst the mutation-site hooks recorded, fully drained before
    # the sample fires: the high-water must still surface it.
    node.telemetry_gauges["dispatch_queue_hw"] = 17
    node.telemetry_gauges["pipeline_inflight_hw"] = 9
    m = sampler.sample()["metrics"]
    assert m["dispatch_queue_depth"] == 0.0
    assert m["dispatch_queue_hw"] == 17.0
    assert m["pipeline_inflight_hw"] == 9.0
    # ...and it resets so the next window measures its own burst.
    m2 = sampler.sample()["metrics"]
    assert m2["dispatch_queue_hw"] == 0.0

    node.workers = {1: _FakeWorker(inflight=4, state="BUSY"),
                    2: _FakeWorker(inflight=2, state="BUSY"),
                    3: _FakeWorker(inflight=0, state="IDLE")}
    m3 = sampler.sample()["metrics"]
    assert m3["pipeline_inflight"] == 6.0
    assert m3["pipeline_occupancy"] == pytest.approx(6 / (2 * 4))


def test_sampler_serve_histograms_become_quantiles():
    node = _fake_node()
    sampler = TelemetrySampler(node)
    bounds = [0.01, 0.1, 1.0]

    def snap(counts, n, depth):
        return {"rows": [
            {"name": "rtpu_serve_request_seconds", "type": "histogram",
             "tags": {"deployment": "D", "phase": "execute"},
             "boundaries": bounds, "bucket_counts": counts,
             "sum": 1.0, "count": n},
            {"name": "rtpu_serve_replica_queue_depth", "type": "gauge",
             "tags": {"deployment": "D"}, "value": depth},
        ]}

    node.user_metrics = {"w1": snap([0, 5, 0, 0], 5, 3.0)}
    m1 = sampler.sample()["metrics"]
    # First sighting counts as a delta from zero (a burst completing
    # before the first flush must still yield quantiles).
    assert m1["serve_queue_depth:D"] == 3.0
    assert 0.01 <= m1["serve_p95_ms:D:execute"] / 1e3 <= 0.1

    time.sleep(0.01)
    node.user_metrics = {"w1": snap([0, 5, 10, 0], 15, 1.0)}
    m2 = sampler.sample()["metrics"]
    # The window's 10 new observations all fell in (0.1, 1.0].
    assert 0.1 <= m2["serve_p50_ms:D:execute"] / 1e3 <= 1.0
    assert m2["serve_req_per_s:D:execute"] > 0.0

    # Source restart (counts went backwards): skip, re-anchor.
    node.user_metrics = {"w1": snap([0, 1, 0, 0], 1, 1.0)}
    m3 = sampler.sample()["metrics"]
    assert "serve_p50_ms:D:execute" not in m3


def test_sampler_device_step_perf_gauges_become_series():
    """The device-step performance plane rides the same worker-flusher
    path as the serve gauges: rtpu_llm_*/rtpu_train_* gauge rows keyed
    by deployment/trial tag become llm_*:<dep> / train_*:<trial>
    series. Utilizations and step breakdowns reduce with MAX across
    sources (the binding replica is the one you chase), token rates
    with SUM."""
    node = _fake_node()
    sampler = TelemetrySampler(node)

    def gauge(name, value, **tags):
        return {"name": name, "type": "gauge", "tags": tags,
                "value": value}

    node.user_metrics = {
        "w1": {"rows": [
            gauge("rtpu_llm_mfu", 0.31, deployment="chat"),
            gauge("rtpu_llm_hbm_util", 0.62, deployment="chat"),
            gauge("rtpu_llm_step_ms", 12.0, deployment="chat"),
            gauge("rtpu_llm_device_ms", 9.0, deployment="chat"),
            gauge("rtpu_llm_host_gap_ms", 3.0, deployment="chat"),
            gauge("rtpu_llm_tokens_per_s", 100.0, deployment="chat"),
        ]},
        "w2": {"rows": [
            gauge("rtpu_llm_mfu", 0.25, deployment="chat"),
            gauge("rtpu_llm_tokens_per_s", 50.0, deployment="chat"),
            gauge("rtpu_train_mfu", 0.4, trial="trial_0"),
            gauge("rtpu_train_host_gap_ms", 7.5, trial="trial_0"),
        ]},
    }
    m = sampler.sample()["metrics"]
    assert m["llm_mfu:chat"] == 0.31            # max across replicas
    assert m["llm_hbm_util:chat"] == 0.62
    assert m["llm_step_ms:chat"] == 12.0
    assert m["llm_device_ms:chat"] == 9.0
    assert m["llm_host_gap_ms:chat"] == 3.0
    assert m["llm_tokens_per_s:chat"] == 150.0  # sum across replicas
    assert m["train_mfu:trial_0"] == 0.4
    assert m["train_host_gap_ms:trial_0"] == 7.5

    # Idle decay: once the engine publishes zeros (drained queue), the
    # series must follow to zero rather than freeze at the last busy
    # value.
    node.user_metrics = {
        "w1": {"rows": [
            gauge("rtpu_llm_mfu", 0.0, deployment="chat"),
            gauge("rtpu_llm_tokens_per_s", 0.0, deployment="chat"),
        ]},
    }
    m2 = sampler.sample()["metrics"]
    assert m2["llm_mfu:chat"] == 0.0
    assert m2["llm_tokens_per_s:chat"] == 0.0


def test_sampler_sees_node_local_registry_gauges():
    """Device-lane actors and the local-mode driver share the node's
    interpreter: their gauges never ride a metrics_push, so the sampler
    must ALSO read this process's own registry — otherwise an engine on
    the TPU lane produces no perf series at all."""
    from ray_tpu.util import metrics

    node = _fake_node()
    node.user_metrics = {}
    sampler = TelemetrySampler(node)
    metrics.Gauge("rtpu_llm_mfu", "perf", tag_keys=("deployment",)).set(
        0.37, tags={"deployment": "inproc_eng"})
    metrics.Gauge("rtpu_train_host_gap_ms", "perf",
                  tag_keys=("trial",)).set(4.25, tags={"trial": "t_loc"})
    m = sampler.sample()["metrics"]
    assert m["llm_mfu:inproc_eng"] == 0.37
    assert m["train_host_gap_ms:t_loc"] == 4.25


# ---------------------------------------------------------------------------
# End to end: solo burst, then the 2-node acceptance run
# ---------------------------------------------------------------------------
def _init_fast(num_cpus=2, **cfg):
    ray_tpu.shutdown()
    return ray_tpu.init(num_cpus=num_cpus, system_config={
        "telemetry_sample_interval_s": 0.05,
        "worker_pipeline_depth": 4, **cfg})


def test_timeseries_gauges_under_pipelined_burst():
    """A pipelined burst must leave its mark in the queue/pipeline
    series even though every sample sees the queue drained."""
    rt = _init_fast(num_cpus=2)
    try:
        @ray_tpu.remote
        def tick(i):
            time.sleep(0.002)
            return i

        for _ in range(4):
            ray_tpu.get([tick.remote(i) for i in range(120)], timeout=60)

        deadline = time.monotonic() + 20
        series = {}
        while time.monotonic() < deadline:
            series = state.timeseries(resolution=0.05)["series"]
            done = series.get("tasks_per_s", {})
            if done and any(
                    any(v > 0 for _, v, _ in pts)
                    for pts in done.values()) \
                    and "pipeline_inflight_hw" in series:
                break
            time.sleep(0.25)

        for metric in ("tasks_per_s", "dispatch_queue_depth",
                       "dispatch_queue_hw", "pipeline_inflight",
                       "pipeline_inflight_hw", "pipeline_occupancy",
                       "store_used_bytes", "writer_frames_per_flush"):
            assert metric in series, (metric, sorted(series))
        assert any(v > 0 for pts in series["tasks_per_s"].values()
                   for _, v, _ in pts)
        # The burst outran the per-sample snapshots: high-water sees it.
        assert any(hi > 0 for pts in series["pipeline_inflight_hw"]
                   .values() for _, _, hi in pts)
        # Single-metric + node filters work through the public API.
        node_hex = next(iter(series["tasks_per_s"]))
        one = state.timeseries("tasks_per_s", node_id=node_hex,
                               resolution=0.05)
        assert set(one["series"]) == {"tasks_per_s"}
        assert set(one["series"]["tasks_per_s"]) == {node_hex}
        assert "tasks_per_s" in state.timeseries_metrics()
    finally:
        ray_tpu.shutdown()


def test_timeseries_two_nodes_sixty_consecutive_samples(monkeypatch):
    """Acceptance: >= 60 consecutive samples per hop metric, per node,
    on a loaded 2-node cluster (compressed via a 50ms interval)."""
    from ray_tpu.cluster_utils import Cluster

    ray_tpu.shutdown()
    # Added nodes boot from env (system_config reaches only the head).
    monkeypatch.setenv("RT_TELEMETRY_SAMPLE_INTERVAL_S", "0.05")
    cluster = Cluster(init_args={
        "num_cpus": 2,
        "system_config": {"telemetry_sample_interval_s": 0.05,
                          "worker_pipeline_depth": 4}})
    try:
        cluster.add_node(num_cpus=2)
        cluster.wait_for_nodes(2)

        @ray_tpu.remote
        def work(i):
            time.sleep(0.002)
            return bytes(2000)

        t0 = time.monotonic()
        while time.monotonic() - t0 < 4.0:
            ray_tpu.get([work.remote(i) for i in range(60)], timeout=60)

        want = ("tasks_per_s", "store_used_bytes",
                "dispatch_queue_depth", "pipeline_inflight",
                "pipeline_occupancy")
        deadline = time.monotonic() + 30
        series = {}
        while time.monotonic() < deadline:
            series = state.timeseries(resolution=0.05)["series"]
            if all(len(pts) >= 60
                   for metric in want
                   for pts in series.get(metric, {}).values()) \
                    and all(len(series.get(metric, {})) >= 2
                            for metric in want):
                break
            time.sleep(0.5)

        for metric in want:
            by_node = series.get(metric, {})
            assert len(by_node) >= 2, (metric, sorted(by_node))
            for node_hex, pts in by_node.items():
                assert len(pts) >= 60, (metric, node_hex, len(pts))
                # Consecutive: timestamps strictly increase with no gap
                # wider than a handful of missed heartbeats.
                ts = [p[0] for p in pts]
                assert all(b > a for a, b in zip(ts, ts[1:]))
                gaps = [b - a for a, b in zip(ts, ts[1:])]
                assert max(gaps) < 1.5, (metric, node_hex, max(gaps))
        assert any(v > 0 for pts in series["tasks_per_s"].values()
                   for _, v, _ in pts)
    finally:
        cluster.shutdown()


def test_serve_status_phase_latency_and_timeseries():
    """serve.status() carries the phase-latency block (p50/p95/p99 per
    phase) and the sampler turns pushed request histograms into
    serve_* series."""
    rt = _init_fast(num_cpus=2)
    serve = None
    try:
        from ray_tpu import serve

        @serve.deployment(num_replicas=1)
        class Echo:
            def __call__(self, x):
                return {"echo": x}

        h = serve.run(Echo.bind(), name="tsapp")
        for i in range(30):
            assert h.remote(i).result(timeout=30)["echo"] == i

        deadline = time.monotonic() + 30
        lat = {}
        while time.monotonic() < deadline:
            row = serve.status().get("Echo") or {}
            lat = row.get("latency") or {}
            if {"replica_queue", "execute"} <= set(lat) and all(
                    lat[p]["count"] >= 30
                    for p in ("replica_queue", "execute")):
                break
            time.sleep(0.5)
        assert {"replica_queue", "execute"} <= set(lat), lat
        for phase in ("replica_queue", "execute"):
            cell = lat[phase]
            assert cell["count"] >= 30
            assert 0.0 <= cell["p50_ms"] <= cell["p95_ms"] \
                <= cell["p99_ms"]
        assert "queue_depth" in (serve.status().get("Echo") or {})

        deadline = time.monotonic() + 30
        names = []
        while time.monotonic() < deadline:
            names = state.timeseries_metrics()
            if any(n.startswith("serve_p95_ms:Echo:") for n in names):
                break
            time.sleep(0.5)
        assert any(n.startswith("serve_queue_depth:") for n in names)
        assert any(n.startswith("serve_p95_ms:Echo:") for n in names)
    finally:
        if serve is not None:
            serve.shutdown()
        ray_tpu.shutdown()


def test_telemetry_disabled_by_config():
    ray_tpu.shutdown()
    rt = ray_tpu.init(num_cpus=1, system_config={
        "telemetry_sample_interval_s": 0.0})
    try:
        @ray_tpu.remote
        def one():
            return 1

        assert ray_tpu.get(one.remote(), timeout=30) == 1
        time.sleep(1.0)
        assert state.timeseries()["series"] == {}
    finally:
        ray_tpu.shutdown()


@pytest.fixture
def rt_python_store(monkeypatch):
    """A runtime whose node holds the pure-Python store: its stats()
    folds in the session's ``.spill_log``, which ``_spill_event``
    appends to. (The native store, the default, counts its own spills
    in C and never reads that log: under it the events this test plants
    are invisible and the series reads 0.0, which is how this test
    failed in every run of the whole suite.)"""
    from ray_tpu._private.object_store import SharedMemoryStore

    monkeypatch.setenv("RT_NATIVE_STORE", "0")
    ray_tpu.shutdown()
    rt = ray_tpu.init(num_cpus=1)
    assert type(rt.node.shm) is SharedMemoryStore
    yield rt
    ray_tpu.shutdown()


def test_spill_series_sampled_with_idle_decay(rt_python_store):
    """The sampler surfaces the store's session-wide spill/restore
    ledger as store_spill_events / store_spilled_bytes /
    store_restored_bytes, and an idle store decays the series to 0
    (the PR-10 gauge contract) instead of freezing it at the last
    cumulative value."""
    rt = rt_python_store
    sampler = TelemetrySampler(rt.node)
    m = sampler.sample()["metrics"]
    assert m["store_spill_events"] == 0.0  # quiet store reads 0

    rt.node.shm._spill_event("S", "ab" * 14, 2048)
    rt.node.shm._spill_event("R", "cd" * 14, 1024)
    m = sampler.sample()["metrics"]
    assert m["store_spill_events"] == 2.0
    assert m["store_spilled_bytes"] == 2048.0
    assert m["store_restored_bytes"] == 1024.0

    # No new events for longer than the decay window -> back to 0.
    sampler._spill_decay.rewind("spill", sampler.SPILL_DECAY_S + 1)
    m = sampler.sample()["metrics"]
    assert m["store_spill_events"] == 0.0
    assert m["store_spilled_bytes"] == 0.0
    assert m["store_restored_bytes"] == 0.0


def test_dying_worker_gauges_visible_for_one_beat(rt):
    """A worker that pushes its final gauge snapshot and dies between
    sampler beats (a batch-inference pool shorter than the sampler
    interval) still lands in exactly one sample: retirement parks the
    snapshot in dying_metrics, the next sample consumes it, and the one
    after no longer sees it (dead gauges must never freeze a series)."""
    sampler = TelemetrySampler(rt.node)
    snap = {"ts": time.time(), "rows": [
        {"name": "rtpu_llm_tokens_per_s", "type": "gauge",
         "tags": {"deployment": "ephemeral"}, "value": 123.0}]}
    rt.node.user_metrics["deadbeef"] = snap
    rt.node._retire_worker_metrics("deadbeef")
    assert "deadbeef" not in rt.node.user_metrics

    m = sampler.sample()["metrics"]
    assert m["llm_tokens_per_s:ephemeral"] == 123.0
    assert not rt.node.dying_metrics  # consumed by that sample

    m = sampler.sample()["metrics"]
    assert "llm_tokens_per_s:ephemeral" not in m
