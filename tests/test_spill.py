"""Python-lane object-store spill plane: LRU spill on capacity
pressure, transparent restore on access, pin protection, and the
cross-process counter ledger.

Reference behavior: plasma's capacity-triggered spill-to-external
storage with restore-on-get (object spilling design doc); here the
"external storage" is a per-session /tmp dir recorded in a ``.spill``
sidecar for the orphan reaper.
"""

import os
import secrets
import time

import pytest

from ray_tpu._private.object_store import ObjectID, SharedMemoryStore


def _oid() -> ObjectID:
    return ObjectID(secrets.token_bytes(28))


def _current_rss() -> int:
    """Current (not high-water) resident bytes: ru_maxrss only grows."""
    try:
        with open("/proc/self/statm") as f:
            return int(f.read().split()[1]) * os.sysconf("SC_PAGESIZE")
    except (OSError, ValueError, IndexError):
        return 0


@pytest.fixture
def store(tmp_path):
    s = SharedMemoryStore(secrets.token_hex(6),
                          capacity_bytes=64 * 1024,
                          spill_dir=str(tmp_path / "spill"))
    yield s
    s.destroy()


def test_put_beyond_capacity_spills_lru(store):
    """Overflowing the arena moves the LEAST RECENTLY USED sealed
    segments to the spill dir; the shm copy is gone."""
    old = _oid()
    store.put(old, b"a" * 32 * 1024)
    time.sleep(0.02)
    hot = _oid()
    store.put(hot, b"b" * 32 * 1024)
    os.utime(store._path(hot))  # freshen the LRU clock
    store.put(_oid(), b"c" * 32 * 1024)  # overflow -> victim = old

    assert os.path.exists(store._spill_path(old))
    assert not os.path.exists(store._path(old))
    assert os.path.exists(store._path(hot)), "recently-used must survive"
    st = store.stats()
    assert st["spilled"] >= 1
    assert st["spilled_bytes"] >= 32 * 1024


def test_get_restores_spilled_segment(store):
    oid = _oid()
    blob = secrets.token_bytes(32 * 1024)
    store.put(oid, blob)
    store.put(_oid(), b"x" * 32 * 1024)
    store.put(_oid(), b"y" * 32 * 1024)  # spills `oid`
    assert os.path.exists(store._spill_path(oid))

    assert bytes(store.get(oid)) == blob  # transparent restore
    assert os.path.exists(store._path(oid))
    assert not os.path.exists(store._spill_path(oid))
    st = store.stats()
    assert st["restored"] >= 1
    assert st["restored_bytes"] >= 32 * 1024


def test_contains_and_size_see_spilled_objects(store):
    oid = _oid()
    store.put(oid, b"z" * 32 * 1024)
    store.put(_oid(), b"x" * 32 * 1024)
    store.put(_oid(), b"y" * 32 * 1024)
    assert not os.path.exists(store._path(oid))  # spilled
    assert store.contains(oid)
    assert store.size_of(oid) == 32 * 1024


def test_pinned_segment_is_never_a_victim(store):
    pinned = _oid()
    store.put(pinned, b"p" * 32 * 1024)
    store.pin(pinned)
    time.sleep(0.02)
    store.put(_oid(), b"x" * 32 * 1024)
    store.put(_oid(), b"y" * 32 * 1024)  # pressure: pinned is OLDEST
    assert os.path.exists(store._path(pinned)), \
        "pinned segment must not be spilled"
    assert not os.path.exists(store._spill_path(pinned))
    store.unpin(pinned)
    store.put(_oid(), b"z" * 32 * 1024)  # now it is fair game
    assert not os.path.exists(store._path(pinned))


def test_soft_cap_all_pinned_put_still_proceeds(store):
    oids = []
    for _ in range(2):
        o = _oid()
        store.put(o, b"p" * 32 * 1024)
        store.pin(o)
        oids.append(o)
    extra = _oid()
    store.put(extra, b"e" * 32 * 1024)  # nothing spillable: soft cap
    assert os.path.exists(store._path(extra))
    for o in oids:
        assert os.path.exists(store._path(o))


def test_counters_are_shared_across_instances(store):
    """The O_APPEND .spill_log makes stats() a session-wide ledger: a
    second client (worker process stand-in) of the same session sees
    spills this instance performed, and vice versa."""
    peer = SharedMemoryStore(store.session_id,
                             capacity_bytes=store.capacity_bytes,
                             spill_dir=store.spill_dir)
    oid = _oid()
    store.put(oid, b"a" * 32 * 1024)
    store.put(_oid(), b"b" * 32 * 1024)
    store.put(_oid(), b"c" * 32 * 1024)  # spills via `store`
    assert peer.stats()["spilled"] >= 1

    assert bytes(peer.get(oid))  # restore via `peer`
    assert store.stats()["restored"] >= 1


def test_delete_reclaims_spilled_copy(store):
    oid = _oid()
    store.put(oid, b"d" * 32 * 1024)
    store.put(_oid(), b"x" * 32 * 1024)
    store.put(_oid(), b"y" * 32 * 1024)
    assert os.path.exists(store._spill_path(oid))
    store.delete(oid)
    assert not os.path.exists(store._spill_path(oid))
    assert not store.contains(oid)


def test_destroy_removes_spill_dir(tmp_path):
    s = SharedMemoryStore(secrets.token_hex(6),
                          capacity_bytes=32 * 1024,
                          spill_dir=str(tmp_path / "sp"))
    s.put(_oid(), b"a" * 32 * 1024)
    s.put(_oid(), b"b" * 32 * 1024)
    assert os.path.isdir(s.spill_dir)
    s.destroy()
    assert not os.path.exists(s.spill_dir)
    assert not os.path.exists(s.prefix)


def test_wait_restores_spilled_segment(store):
    oid = _oid()
    store.put(oid, b"w" * 32 * 1024)
    store.put(_oid(), b"x" * 32 * 1024)
    store.put(_oid(), b"y" * 32 * 1024)
    assert not os.path.exists(store._path(oid))
    assert store.wait(oid, timeout=5.0)


def test_spill_sidecar_records_custom_dir(tmp_path):
    d = str(tmp_path / "custom")
    s = SharedMemoryStore(secrets.token_hex(6), spill_dir=d)
    try:
        with open(os.path.join(s.prefix, ".spill")) as f:
            assert f.read().strip() == d
    finally:
        s.destroy()


@pytest.mark.slow  # tier-1 budget: multi-x-capacity end-to-end sort
def test_sort_several_times_capacity_bounded_rss(monkeypatch):
    """Acceptance (ISSUE 17): a dataset >= 3x the store capacity sorts
    end to end on the pure-Python store lane — capacity pressure spills
    cold blocks to disk, gets restore them transparently, and the
    driver's resident set stays bounded by the streaming contract, not
    the dataset size."""
    import numpy as np

    import ray_tpu
    import ray_tpu.data as rd

    cap = 32 * 1024 * 1024
    monkeypatch.setenv("RT_NATIVE_STORE", "0")
    monkeypatch.setenv("RT_STORE_CAPACITY", str(cap))
    ray_tpu.shutdown()
    rt = ray_tpu.init(num_cpus=4)
    try:
        assert type(rt.shm) is SharedMemoryStore  # the Python lane
        rows, pad = 32768, 4096  # 128 MB of payload = 4x capacity

        def widen(b):
            n = len(b["id"])
            return {"k": (b["id"] * 2654435761) % 1000003,
                    "pad": np.zeros((n, pad), np.uint8)}

        ds = (rd.range(rows, override_num_blocks=16)
              .map_batches(widen).sort("k"))

        rss0 = _current_rss()
        peak_growth = 0
        total, last = 0, None
        for blk in ds.iter_blocks():
            k = np.asarray(blk["k"])
            assert (np.diff(k) >= 0).all()  # sorted within the block
            if last is not None:
                assert k[0] >= last  # and across block boundaries
            last = int(k[-1])
            total += len(k)
            peak_growth = max(peak_growth, _current_rss() - rss0)
        assert total == rows

        st = rt.shm.stats()  # session-wide ledger: worker spills count
        assert st["spilled"] > 0, "4x-capacity sort must spill"
        assert st["spilled_bytes"] > 0
        # RSS ceiling: well under the 128MB payload (streaming + spill
        # keep resident data O(capacity), with slack for allocator noise
        # and per-block mmaps).
        assert peak_growth < 3 * cap, (
            f"driver RSS grew {peak_growth / 1e6:.0f}MB on a "
            f"{rows * pad / 1e6:.0f}MB dataset")
    finally:
        ray_tpu.shutdown()
