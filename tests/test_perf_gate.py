"""Solo-pinned perf gate (VERDICT r4 weak 6): regression-DETECTING
floors, run FIRST in the suite (conftest orders it ahead of every other
test) so no sibling test's workers/daemons are alive.

The r4 gates anchored floors to the worst loaded-context mean, which
quietly tolerated ~3.3x solo regressions. The fix here is two-part:

1. this stage runs serially at the very start of the session (or solo:
   ``pytest tests/test_perf_gate.py``), with floors at 70% of the SOLO
   means recorded in this exact context (quick scale, gate-first);
2. floors are CALIBRATED to the box's instantaneous background load: a
   fixed pure-CPU reference unit (msgpack+pickle round trips — the
   runtime's own instruction mix) is timed at gate start and floors
   scale by observed/recorded. Background load slows the reference and
   our metrics together, so the gate keeps its 70% teeth; a genuine
   regression in framework code leaves the reference untouched and
   FAILS. (This box's duty driver alone swings throughput ~2x between
   'idle' samples — unscaled 70% floors would either flake or need
   3x slack, which is exactly the r4 failure mode.)

The loaded-suite floors in test_microbench.py remain as a crash net.
Reference discipline: release/release_tests.yaml thresholds.
"""

import os
import pickle
import time

import pytest

import ray_tpu
from ray_tpu.scripts import microbench

# Reference units/s recorded on the anchor box (2026-07-31, gate
# context) — see _calibrate().
_REF_UNITS_PER_S = 185000.0

# name -> 0.7 x solo gate-context mean (recorded 2026-07-31, quick
# scale, gate-first, calibration ~1.0).
SOLO_FLOORS = {
    "get_small_ops": 11000,
    "put_small_ops": 18000,
    "put_gigabytes_gb": 2.0,
    "get_gigabytes_gb": 1050,
    "task_device_sync": 3300,
    # task_device_async: re-anchored 2026-08-04 for the task-lifecycle
    # event backend, which adds ~11us node-side bookkeeping per device
    # task (SUBMITTED/RUNNING/FINISHED events + 4-phase histogram) —
    # intentional cost, ~10% on this ~90us/task in-process lane. Also
    # the pure-CPU calibration unit over-scales this lane today: the
    # reference sped up ~25% since the 07-31 anchor while the asyncio
    # round-trip lane did not (events-OFF gate runs sat borderline at
    # the old scaled floor). 0.7 x the events-on gate-context mean of
    # calibration-normalized samples (5.7-7.3k, mean ~6.5k).
    "task_device_async": 4500,
    # task_cpu_sync: re-anchored 2026-08-05 with the CPU-lane fast
    # path. The sequential fork-lane round trip is execute+reply bound
    # (pipelining never engages at window 1, A/B parity), but the
    # pure-CPU calibration unit now pegs 1.25 on this box while the
    # fork-lane round trip did not speed up with it — the old 1300
    # floor scaled to 1625 and sat above real gate-context samples
    # (1400-1704 raw, 1120-1363 calibration-normalized). 0.7 x the
    # normalized gate-context mean (~1200).
    "task_cpu_sync": 840,
    # task_cpu_async: re-anchored 2026-08-05 for pipelined worker
    # dispatch (worker_pipeline_depth=8). The old 290 floor was 0.7 x
    # the worst UNPIPELINED drain throughput (420/s) because the QUEUE
    # phase absorbed multi-x context swings; the pipelined window keeps
    # the next spec already on the worker, so the drain rate is both
    # higher and steadier (gate-context samples 2026-08-05: 842-1,340
    # raw, 674-1,072 calibration-normalized). Floor at 0.7 x the worst
    # normalized sample — deliberately ABOVE the old unpipelined drain
    # rate, so a revert to one-at-a-time dispatch fails this gate.
    "task_cpu_async": 470,
    # actor_call_sync: re-anchored 2026-08-05 alongside the serial-lane
    # rework (per-lane executor -> completion-event chaining on the
    # shared pool; A/B parity). Same calibration over-scale as
    # task_cpu_sync: gate-context samples 1479-1838 raw / 1183-1470
    # normalized vs the old floor's 1750 scaled threshold. 0.7 x the
    # normalized mean (~1280).
    "actor_call_sync": 900,
    "actor_call_async": 1700,
    "actor_call_concurrent": 1900,
    "wait_1k_refs": 4100,
    "pg_create_remove": 2700,
    "queued_5k_tasks": 4000,
    "membership_100_nodes_events": 230000,  # re-anchored after the r5
                                            # real-NodeService rewrite
                                            # (338k solo at gate scale)
}
SOLO_FETCH_FLOOR_MB_S = 420  # 0.7 x 600 recorded (16MB payload)


def _calibrate(duration: float = 0.5) -> float:
    """Observed/recorded speed of a fixed pure-CPU unit. <1 on a loaded
    box; floors scale down with it (min-capped so a totally wedged box
    still gates at 25%)."""
    import msgpack

    payload = {"k": list(range(32)), "s": "x" * 64}
    deadline = time.perf_counter() + duration
    n = 0
    while time.perf_counter() < deadline:
        blob = msgpack.packb(payload)
        msgpack.unpackb(blob, raw=False)
        pickle.loads(pickle.dumps(payload))
        n += 1
    observed = n / duration
    return max(0.25, min(1.25, observed / _REF_UNITS_PER_S))


@pytest.fixture(scope="module", autouse=True)
def quick_scale():
    os.environ["RT_MB_QUEUED"] = "5000"
    os.environ["RT_MB_NODES"] = "100"
    microbench.TRIALS = 1
    microbench.TRIAL_S = 0.4
    microbench.WARMUP_S = 0.2
    yield


def _one_pass():
    cal = _calibrate()
    ray_tpu.init(num_cpus=2)
    try:
        results = microbench.run(include_cluster=False)
    finally:
        ray_tpu.shutdown()
    by_name = {r["name"]: r["per_s"] for r in results if r}
    missing = set(SOLO_FLOORS) - set(by_name)
    assert not missing, f"benchmarks did not run: {missing}"
    failures = {
        n: (round(by_name[n], 1), round(floor * cal, 1))
        for n, floor in SOLO_FLOORS.items()
        if by_name[n] < floor * cal
    }
    return failures, cal


def test_solo_perf_gate():
    failures, cal = _one_pass()
    if failures:
        # Confirm-before-fail: 0.4s trials of thread round-trips jitter
        # ~±30% on this 1-core box in ways the CPU calibration cannot
        # see (scheduler placement, GIL handoff streaks). A genuine
        # regression reproduces; a jitter dip does not. Only metrics
        # below floor in BOTH passes fail the gate.
        failures2, cal2 = _one_pass()
        confirmed = {n: (failures[n], failures2[n])
                     for n in set(failures) & set(failures2)}
        assert not confirmed, (
            f"SOLO perf regression CONFIRMED in two passes "
            f"(calibrations {cal:.2f}/{cal2:.2f}): {confirmed}")


def test_telemetry_sampler_overhead_gate():
    """The telemetry sampler runs on the node loop every interval: its
    hot path must stay in the tens-of-microseconds class. Budget 1ms
    per sample at calibration 1.0 (~20-60us observed solo) so a
    regression to O(expensive) scanning fails loudly, scaled like every
    other floor."""
    from ray_tpu._private.telemetry import TelemetrySampler

    cal = _calibrate()
    ray_tpu.shutdown()
    rt = ray_tpu.init(num_cpus=2)
    try:
        @ray_tpu.remote
        def tick(i):
            return ray_tpu.put(bytes(100))

        ray_tpu.get([tick.remote(i) for i in range(50)], timeout=60)
        sampler = TelemetrySampler(rt.node)
        sampler.sample()  # prime the anchors
        n = 500
        t0 = time.perf_counter()
        for _ in range(n):
            sampler.sample()
        per_sample = (time.perf_counter() - t0) / n
    finally:
        ray_tpu.shutdown()
    budget = 1e-3 / cal
    assert per_sample < budget, (
        f"telemetry sampler hot path regressed: {per_sample * 1e6:.1f}us "
        f"per sample > budget {budget * 1e6:.1f}us (calibration {cal:.2f})")


def test_request_span_overhead_gate():
    """The request-tracing hot path runs on EVERY serving request,
    sampled or not (tail sampling is a head-side decision): one root
    span enter/exit with an event plus two retro emits must stay well
    under 50us at calibration 1.0 (~5-15us observed solo). A
    regression — say span IDs going back to uuid4, or recording
    growing a lock-heavy stage — fails loudly here before it taxes
    every request."""
    from ray_tpu.util import tracing

    cal = _calibrate()
    t_wall = time.time()
    n = 2000
    # Warm the id-prefix seed + ring out of the measured region.
    with tracing.span("warm", kind="request"):
        pass
    tracing.drain_request_spans()
    t0 = time.perf_counter()
    for i in range(n):
        with tracing.span("serve.request", kind="request",
                          attributes={"deployment": "gate"}) as root:
            tracing.emit("serve.proxy_queue", root.context(), t_wall,
                         1e-4, {"deployment": "gate"})
            tracing.emit("serve.replica_queue", root.context(), t_wall,
                         1e-4, {"deployment": "gate"})
            root.add_event("ttft", ms=1.0)
        if i % 500 == 0:
            tracing.drain_request_spans()  # steady-state ring, not full
    per_request = (time.perf_counter() - t0) / n
    tracing.drain_request_spans()
    budget = 50e-6 / cal
    assert per_request < budget, (
        f"request-span hot path regressed: {per_request * 1e6:.1f}us "
        f"per request > budget {budget * 1e6:.1f}us "
        f"(calibration {cal:.2f})")


def test_step_accounting_overhead_gate():
    """The device-step accounting runs inside the engine's scheduler
    step, under the engine lock, on EVERY decode: one begin + one
    priced add_device (an 8-lane decode_step_cost through the shape
    cache) + finish must stay well under 50us at calibration 1.0
    (~2-6us observed solo). A regression — the shape cache degenerating
    to per-call recompute, finish growing allocation-heavy — taxes
    every generated token, so it fails loudly here."""
    from ray_tpu.models.gpt import GPT2_SMALL
    from ray_tpu.util import perfmodel

    cal = _calibrate()
    acc = perfmodel.StepAccounting(
        hw=perfmodel.HARDWARE_PEAKS[perfmodel.V5E])
    ctx = [100, 200, 300, 400, 500, 600, 700, 800]
    # Warm the per-config shape cache out of the measured region.
    perfmodel.decode_step_cost(GPT2_SMALL, ctx)
    n = 5000
    t0 = time.perf_counter()
    for _ in range(n):
        acc.begin()
        acc.add_device(1e-3, perfmodel.decode_step_cost(GPT2_SMALL, ctx))
        acc.finish()
    per_step = (time.perf_counter() - t0) / n
    budget = 50e-6 / cal
    assert per_step < budget, (
        f"step-accounting hot path regressed: {per_step * 1e6:.1f}us "
        f"per step > budget {budget * 1e6:.1f}us (calibration {cal:.2f})")


def test_flight_recorder_overhead_gate():
    """The flight recorder brackets EVERY eager collective: one
    record_enter + record_exit pair (two dict/deque writes under a
    lock, throttled gauge publish) must stay under 5us at calibration
    1.0 (~1-2us observed solo). A regression — say the ring growing a
    per-op snapshot, or the gauge publish losing its throttle — taxes
    every collective, so it fails loudly here."""
    from ray_tpu.parallel import flightrec

    cal = _calibrate()
    rec = flightrec.FlightRecorder(capacity=1024)
    # Warm one pair outside the measured region (lazy gauge creation).
    rec.record_exit(rec.record_enter("gate", "allreduce", "dp", (8,), 32))
    n = 20000
    t0 = time.perf_counter()
    for _ in range(n):
        e = rec.record_enter("gate", "allreduce", "dp", (8,), 32)
        rec.record_exit(e)
    per_op = (time.perf_counter() - t0) / n
    budget = 5e-6 / cal
    assert per_op < budget, (
        f"flight-recorder hot path regressed: {per_op * 1e6:.2f}us "
        f"per op > budget {budget * 1e6:.2f}us (calibration {cal:.2f})")


def test_locality_and_spill_bookkeeping_gate():
    """The data plane's locality routing and the store's capacity
    bookkeeping both sit on the per-block scheduling path: one
    owner_addr -> NodeID resolve, one per-node handle-cache lookup, and
    one _ensure_capacity pass (cached-used fast path, amortizing the
    every-32-puts scandir resync) must together stay under 20us per
    scheduled block at calibration 1.0 (~1-3us observed solo). A
    regression — the resolver refreshing membership per call, the
    handle cache degenerating to per-call .options() re-wraps, or
    capacity checks scanning the arena on every put — taxes every
    block, so it fails loudly here."""
    import secrets

    from ray_tpu._private.object_store import ObjectID, SharedMemoryStore
    from ray_tpu.data.execution import _LocalityResolver

    cal = _calibrate()
    resolver = _LocalityResolver()
    addr = ("10.0.0.1", 7001)
    resolver._map = {addr: b"n" * 28}
    handle_cache = {b"n" * 28: object()}  # _remote_by_node stand-in
    store = SharedMemoryStore(secrets.token_hex(6),
                              capacity_bytes=1 << 30)
    try:
        # A populated arena so the periodic scandir resync has real work.
        for _ in range(32):
            store.put(ObjectID(secrets.token_bytes(28)), b"x" * 4096)
        # Warm the fast path out of the measured region.
        resolver.node_of(addr)
        store._ensure_capacity(1024)
        n = 20000
        t0 = time.perf_counter()
        for _ in range(n):
            nid = resolver.node_of(addr)
            handle_cache.get(nid)
            store._ensure_capacity(1024)
        per_block = (time.perf_counter() - t0) / n
    finally:
        store.destroy()
    budget = 20e-6 / cal
    assert per_block < budget, (
        f"locality/spill bookkeeping regressed: {per_block * 1e6:.2f}us "
        f"per block > budget {budget * 1e6:.2f}us (calibration {cal:.2f})")


def test_prefix_pool_bookkeeping_gate():
    """The prefix-cache bookkeeping runs at EVERY admission, under the
    engine lock: a full-hit admit (a walk of the request's chain of
    block keys, which ``add_request`` made on the caller's thread:
    index verify + ref bumps + LRU pops) plus the matching release
    (re-register walk + unref parks) must stay under 10us per admitted
    request at calibration 1.0 (~2-4us observed solo for a 64-token
    prompt). A regression — the index growing a per-lookup content
    scan, or LRU parking degenerating to list removal — taxes every
    admitted request, so it fails loudly here."""
    pytest.importorskip("jax")
    import jax.numpy as jnp

    from ray_tpu.llm.kv_cache import BlockChain, PrefixPool
    from ray_tpu.models.gpt import GPTConfig

    cal = _calibrate()
    cfg = GPTConfig(vocab_size=64, max_seq=256, d_model=32, n_layer=2,
                    n_head=4, dtype=jnp.float32)
    pool = PrefixPool(cfg, num_blocks=32, block_size=16)
    seq = list(range(64))                  # 4 full chunks
    chain = BlockChain(pool.block_size, seq)    # once a request
    warm, _ = pool.admit(seq, len(seq) + 1, chain=chain)
    pool.release(warm, seq=seq, chain=chain)    # registered + parked
    n = 2000
    cached = 0
    per_pass = []
    for _ in range(3):                     # min-of-3: GC/scheduler
        t0 = time.perf_counter()           # spikes don't fail the gate
        for _ in range(n):
            table, cached = pool.admit(seq, len(seq) + 1, chain=chain)
            pool.release(table, seq=seq, chain=chain)
        per_pass.append((time.perf_counter() - t0) / n)
    per_req = min(per_pass)
    assert cached == len(seq), "gate must exercise the full-hit path"
    budget = 10e-6 / cal
    assert per_req < budget, (
        f"prefix-pool bookkeeping regressed: {per_req * 1e6:.2f}us "
        f"per admitted request > budget {budget * 1e6:.2f}us "
        f"(calibration {cal:.2f})")


def test_spec_disabled_step_overhead_gate():
    """Speculative decoding must be FREE when off: the engine builds no
    proposer and no verify program (structural zero-overhead — step()
    keeps the plain one-token decode path behind a single attribute
    check), and the n-gram proposer itself — the per-lane, per-step
    cost once speculation IS on — must stay under 50us per propose()
    over a 256-token history at calibration 1.0 (~5-15us observed
    solo). A regression — the guard growing work, or the suffix match
    degenerating to a quadratic rescan per call — taxes every decode
    step, so it fails loudly here."""
    pytest.importorskip("jax")
    import jax
    import jax.numpy as jnp

    from ray_tpu.llm.engine import LLMEngine
    from ray_tpu.llm.spec import NgramProposer
    from ray_tpu.models.gpt import GPTConfig, init

    cal = _calibrate()
    cfg = GPTConfig(vocab_size=64, max_seq=64, d_model=32, n_layer=1,
                    n_head=2, dtype=jnp.float32)
    eng = LLMEngine(init(jax.random.PRNGKey(0), cfg), cfg, num_blocks=4,
                    block_size=16, max_batch=2, speculative=None)
    # Structural: disabled means NO spec object.
    assert eng._spec is None
    # The whole disabled-path residue inside step() is this guard.
    n = 50000
    t0 = time.perf_counter()
    for _ in range(n):
        if eng._spec is not None:
            raise AssertionError
    per_guard = (time.perf_counter() - t0) / n
    # Enabled-path proposer cost on a worst-ish-case history: long,
    # periodic (every call walks the match loop and extends to k).
    prop = NgramProposer()
    hist = ([7, 8, 9, 7, 8] * 52)[:256]
    prop.propose(hist, 4)  # warm
    n = 2000
    t0 = time.perf_counter()
    for _ in range(n):
        prop.propose(hist, 4)
    per_propose = (time.perf_counter() - t0) / n
    budget = 50e-6 / cal
    assert per_guard < budget, (
        f"spec-off step guard regressed: {per_guard * 1e6:.2f}us "
        f"per step > budget {budget * 1e6:.1f}us (calibration {cal:.2f})")
    assert per_propose < budget, (
        f"n-gram propose regressed: {per_propose * 1e6:.1f}us per call "
        f"> budget {budget * 1e6:.1f}us (calibration {cal:.2f})")


def test_solo_cross_node_fetch_gate():
    cal = _calibrate()
    os.environ["RT_MB_FETCH_MB"] = "16"
    row = microbench._cross_node_fetch()
    floor = SOLO_FETCH_FLOOR_MB_S * cal
    assert row["per_s"] > floor, (
        f"cross-node fetch regression: {row['per_s']:.1f} MB/s < "
        f"scaled floor {floor:.1f} (calibration {cal:.2f})")


def test_alert_rule_evaluation_gate():
    """The head's per-beat alert pass (observe one node's sampler beat
    + run every rule's burn-rate state machine) rides the heartbeat
    path — at 50 declared rules all receiving samples it must stay
    under 100us per beat, scaled like every other floor."""
    from ray_tpu._private.alerting import AlertEngine
    from ray_tpu._private.telemetry import TelemetryStore

    cal = _calibrate()
    eng = AlertEngine(TelemetryStore())
    for i in range(50):
        eng.declare({"name": f"gate-rule-{i}",
                     "metric": f"alert_gate_m{i}",
                     "target": 10.0, "comparison": "<=",
                     "budget": 0.01})
    metrics = {f"alert_gate_m{i}": 1.0 for i in range(50)}
    # Warm one beat: window deques allocate, builtin probing settles.
    eng.observe([{"ts": time.time(), "metrics": metrics}])
    eng.evaluate()
    n = 500
    t0 = time.perf_counter()
    for _ in range(n):
        ts = time.time()
        eng.observe([{"ts": ts, "metrics": metrics}])
        eng.evaluate()
    per_beat = (time.perf_counter() - t0) / n
    budget = 100e-6 / cal
    assert per_beat < budget, (
        f"alert evaluation hot path regressed: {per_beat * 1e6:.1f}us "
        f"per beat at 50 rules > budget {budget * 1e6:.1f}us "
        f"(calibration {cal:.2f})")
