"""Scalability envelope smoke (scaled-down BASELINE.md shapes).

Parity model: /root/reference/release/benchmarks/README.md and
python/ray/_private/ray_perf.py — the envelope the reference publishes
(1M queued tasks, 10k-ref containers, 1k-ref waits). CI-scaled: the
shapes are the same, the counts fit one small box; the full-scale
numbers belong to release runs, not unit CI.
"""

import time

import numpy as np
import pytest

import ray_tpu


def test_many_queued_tasks_drain(rt):
    """Thousands of tasks queued at once all complete correctly
    (reference envelope: 1M queued on one node)."""

    @ray_tpu.remote(scheduling_strategy="device")  # in-process: queue cost
    def unit(i):
        return i

    n = 10_000
    t0 = time.monotonic()
    refs = [unit.remote(i) for i in range(n)]
    out = ray_tpu.get(refs, timeout=300)
    dt = time.monotonic() - t0
    assert out == list(range(n))
    # Recorded drain rate is ~6k tasks/s (microbench queued_50k_tasks);
    # 10s gives 6x headroom on a loaded box.
    assert dt < 10, f"{n} tasks took {dt:.1f}s"


def test_many_refs_single_get(rt):
    """One get over thousands of refs (reference: 10k plasma objects in
    one ray.get)."""
    refs = [ray_tpu.put(i) for i in range(2000)]
    assert ray_tpu.get(refs, timeout=120) == list(range(2000))


def test_thousand_ref_wait(rt):
    """1k-ref wait shape from the microbenchmark suite."""

    @ray_tpu.remote(scheduling_strategy="device")
    def unit(i):
        return i

    refs = [unit.remote(i) for i in range(1000)]
    done, not_done = ray_tpu.wait(refs, num_returns=1000, timeout=120)
    assert len(done) == 1000 and not not_done


def test_large_object_roundtrip(rt):
    """A >100MB numpy object through the shared-memory store, zero-copy
    read (reference envelope: 100GiB+ max get, scaled to CI)."""
    big = np.random.default_rng(0).integers(
        0, 255, size=(128, 1024, 1024), dtype=np.uint8)  # 128MB
    ref = ray_tpu.put(big)
    back = ray_tpu.get(ref, timeout=120)
    assert back.shape == big.shape
    assert np.array_equal(back[::37, ::53, ::71], big[::37, ::53, ::71])


def test_many_object_args_to_one_task(rt):
    """Hundreds of ref args to a single task (reference: 10k+ args)."""

    @ray_tpu.remote
    def total(*vals):
        return sum(vals)

    refs = [ray_tpu.put(i) for i in range(400)]
    assert ray_tpu.get(total.remote(*refs), timeout=120) == \
        sum(range(400))


def test_actor_call_throughput(rt):
    """Pipelined actor calls (reference: actor call microbenchmark)."""

    @ray_tpu.remote(scheduling_strategy="device", max_concurrency=4)
    class Counter:
        def __init__(self):
            self.n = 0

        def bump(self):
            self.n += 1
            return self.n

    c = Counter.remote()
    t0 = time.monotonic()
    refs = [c.bump.remote() for _ in range(2000)]
    out = ray_tpu.get(refs, timeout=180)
    dt = time.monotonic() - t0
    assert max(out) == 2000
    assert dt < 120, f"2000 actor calls took {dt:.1f}s"


@pytest.mark.skipif(not __import__("os").environ.get("RT_ENVELOPE"),
                    reason="full-scale envelope: set RT_ENVELOPE=1 "
                           "(500k queued tasks, 1000 nodes; nothing "
                           "else runs it)")
def test_full_scale_envelope_floors(rt):
    """VERDICT r4 item 5 floors at artifact scale: 500k queued tasks
    drain >= 3k/s; 1000 REAL NodeService objects churn >= 100k
    membership events/s with PG placement under churn <= 50ms."""
    from ray_tpu.scripts.microbench import _membership_churn, _queued_burst

    row = _queued_burst(500_000)
    assert row["per_s"] >= 3000, row
    ray_tpu.shutdown()
    row = _membership_churn(1000)
    assert row["per_s"] >= 100_000, row
    assert row["pg_place_under_churn_ms"] <= 50, row
