"""A reduced-scale pass of the runtime microbenchmarks as a crash net:
every row runs to its end and returns a positive, finite rate. The rates
themselves are host rates of a box shared with five other xdist workers
and are asserted nowhere (speed is ``benchmark/run.py``'s, on the chip).
"""

import math
import os

import pytest

import ray_tpu
from ray_tpu.scripts import microbench

ROWS = {
    "get_small_ops", "put_small_ops", "put_gigabytes_gb",
    "get_gigabytes_gb", "task_device_sync", "task_device_async",
    "task_cpu_sync", "task_cpu_async", "actor_call_sync",
    "actor_call_async", "actor_call_concurrent", "wait_1k_refs",
    "pg_create_remove", "queued_5k_tasks", "membership_100_nodes_events",
}


@pytest.fixture(scope="module", autouse=True)
def quick_scale():
    os.environ["RT_MB_QUEUED"] = "5000"
    os.environ["RT_MB_NODES"] = "100"
    # the module reads its durations at import; set them here
    microbench.TRIALS = 1
    microbench.TRIAL_S = 0.4
    microbench.WARMUP_S = 0.2
    yield


def _ran(row):
    return math.isfinite(row["per_s"]) and row["per_s"] > 0


def test_microbench_floors():
    ray_tpu.init(num_cpus=2)
    try:
        results = microbench.run(include_cluster=False)
    finally:
        ray_tpu.shutdown()
    by_name = {r["name"]: r for r in results if r}
    missing = ROWS - set(by_name)
    assert not missing, f"benchmarks did not run: {missing}"
    dead = {n: r for n, r in by_name.items() if not _ran(r)}
    assert not dead, f"rows without a positive finite rate: {dead}"


def test_cross_node_fetch_floor():
    """16 MB across the loopback object plane of a two-node cluster,
    through the bulk lane: the pull ran and the bytes arrived (the row
    asserts the consumer's length itself)."""
    os.environ["RT_MB_FETCH_MB"] = "16"
    row = microbench._cross_node_fetch()
    assert _ran(row), row
