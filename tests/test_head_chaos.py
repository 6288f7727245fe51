"""Head chaos: kill the control plane MID-WORKLOAD and lose nothing.

Parity model: the reference's GCS fault-tolerance contract
(/root/reference/python/ray/tests/test_gcs_fault_tolerance.py): raylets
and drivers survive a GCS restart (NotifyGCSRestart resync,
node_manager.proto:361); tasks already dispatched to raylets keep
running because the GCS is not on the task result path. VERDICT r3 item
4's "Done": a chaos test kills the head mid-workload and the cluster
resumes without losing running tasks — plus a 20-node membership
reconcile through a restart.
"""

import asyncio
import os
import subprocess
import sys
import time

import pytest

from ray_tpu._private.head import HeadService
from ray_tpu._private.head_store import AppendLogHeadStore
from ray_tpu._private.ids import NodeID, PlacementGroupID

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_append_log_store_roundtrip_and_compaction(tmp_path):
    path = str(tmp_path / "head.bin")
    store = AppendLogHeadStore(path)
    assert store.load() is None
    store.append("kv", ("a", b"1"))
    store.append("fn", ("f1", b"blob"))
    store.append("pg", {"pg_id": b"p1", "bundles": [{"CPU": 1}],
                        "strategy": "PACK"})
    store.append("kv", ("a", b"2"))  # overwrite
    store.append("pg_del", b"p1")
    store.close()

    s2 = AppendLogHeadStore(path)
    t = s2.load()
    assert t["kv"] == {"a": b"2"}
    assert t["functions"] == {"f1": b"blob"}
    assert t["placement_groups"] == []
    # Compaction: snapshot + truncated log; appends after it replay on top.
    s2.save(t)
    s2.append("kv", ("b", b"3"))
    s2.close()
    assert os.path.getsize(path + ".log") > 0
    t3 = AppendLogHeadStore(path).load()
    assert t3["kv"] == {"a": b"2", "b": b"3"}
    # Crash between snapshot-replace and log-truncate: stale records
    # must be seq-skipped, not re-applied over the snapshot.
    s4 = AppendLogHeadStore(path)
    t4 = s4.load()
    s4.save(t4)
    s4.close()
    assert AppendLogHeadStore(path).load()["kv"] == {"a": b"2", "b": b"3"}


def test_membership_reconcile_20_nodes_through_restart(tmp_path):
    """20 registered nodes, head dies, 15 come back (5 died during the
    outage): replayed PG definitions reconcile — bundles on survivors
    are adopted, bundles on dead nodes return to pending."""
    store_path = str(tmp_path / "head.bin")
    node_ids = [NodeID.from_random() for _ in range(20)]
    pg_id = PlacementGroupID.from_random()

    loop = asyncio.new_event_loop()
    try:
        head = HeadService("chaos", loop, store=AppendLogHeadStore(store_path))

        async def phase1():
            for i, nid in enumerate(node_ids):
                head.register_node(nid, ("127.0.0.1", 10000 + i),
                                   {"CPU": 4}, None)
            head.kv_op("put", "epoch", b"1")
            pg = await head.create_placement_group(
                pg_id, [{"CPU": 1}] * 4, "SPREAD")
            assert pg.state == "CREATED"
            return {idx: nid for idx, nid in pg.placement.items()}

        placement = loop.run_until_complete(phase1())
        assert len(placement) == 4
        head._persist_pool.submit(lambda: None).result()  # write barrier
    finally:
        loop.close()

    # ---- restart with the same store; only 15 nodes come back --------
    survivors = set(node_ids[:15])
    loop = asyncio.new_event_loop()
    try:
        head2 = HeadService("chaos", loop,
                            store=AppendLogHeadStore(store_path))
        assert head2.kv_op("get", "epoch") == b"1"
        pg = head2.placement_groups[pg_id]
        assert pg.state == "PENDING"  # definitions replay as pending

        async def phase2():
            for i, nid in enumerate(node_ids[:15]):
                # Survivors re-register WITH their live reservations.
                held = [{"pg_id": pg_id.binary(), "bundle_index": idx,
                         "resources": {"CPU": 1}}
                        for idx, owner in placement.items()
                        if owner == nid]
                head2.register_node(
                    nid, ("127.0.0.1", 10000 + i), {"CPU": 4}, None,
                    sync={"bundles": held})
            await head2.retry_pending_pgs()

        loop.run_until_complete(phase2())
        alive = [e for e in head2.nodes.values() if e.state == "ALIVE"]
        assert len(alive) == 15
        # Every bundle is placed again — adopted on survivors or
        # re-reserved on whoever has room.
        assert len(pg.placement) == 4
        for idx, nid in pg.placement.items():
            assert nid in survivors
    finally:
        loop.close()


def test_head_killed_mid_workload_tasks_survive(tmp_path, fixed_port):
    """Detached head + 2 worker nodes; 6 tasks sleeping on the workers;
    kill -9 the head mid-flight; restart it on the same port. The driver
    and nodes reconnect and every task result arrives."""
    temp = str(tmp_path / "rtpu")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("RT_SESSION_TOKEN", None)
    port = fixed_port
    cli = [sys.executable, "-m", "ray_tpu.scripts.cli", "--temp-dir", temp]

    def start_head():
        subprocess.run(cli + ["start", "--head", "--port", str(port),
                              "--num-cpus", "1"],
                       env=env, check=True, timeout=90)

    start_head()
    workers = []
    try:
        tok = os.path.join(temp, "session_token")
        for i in range(2):
            wenv = dict(env, RT_HEAD_ADDR=f"127.0.0.1:{port}",
                        RT_SESSION_ID="chaosft", RT_TOKEN_FILE=tok,
                        RT_NODE_RESOURCES='{"CPU": 1, "w": 1}')
            workers.append(subprocess.Popen(
                [sys.executable, "-m", "ray_tpu._private.node_main"],
                env=wenv))

        driver = (
            "import ray_tpu, time, os, signal, sys\n"
            "ray_tpu.init()\n"
            "from ray_tpu.util import state as S\n"
            "for _ in range(150):\n"
            "    ws = [n for n in S.list_nodes()\n"
            "          if n.get('resources', {}).get('w')"
            " and n['state'] == 'ALIVE']\n"
            "    if len(ws) >= 2: break\n"
            "    time.sleep(0.2)\n"
            "else: raise SystemExit('workers never joined')\n"
            "@ray_tpu.remote(resources={'w': 0.25})\n"
            "def slow(i):\n"
            "    import time; time.sleep(6)\n"
            "    return i * 10\n"
            "refs = [slow.remote(i) for i in range(6)]\n"
            "time.sleep(1.5)\n"  # tasks are dispatched and running
            "print('KILL_NOW', flush=True)\n"
            "sys.stdin.readline()\n"  # parent killed+restarted the head
            "vals = ray_tpu.get(refs, timeout=120)\n"
            "assert vals == [i * 10 for i in range(6)], vals\n"
            "print('ALL_RESULTS_OK', flush=True)\n"
            "@ray_tpu.remote(resources={'w': 0.25})\n"
            "def after(): return 'post-restart'\n"
            "assert ray_tpu.get(after.remote(), timeout=60) == 'post-restart'\n"
            "print('POST_RESTART_OK', flush=True)\n"
            "ray_tpu.shutdown()\n")
        denv = dict(env, RT_ADDRESS=f"127.0.0.1:{port}", RT_TOKEN_FILE=tok)
        proc = subprocess.Popen([sys.executable, "-u", "-c", driver],
                                env=denv, stdin=subprocess.PIPE,
                                stdout=subprocess.PIPE, text=True)
        # Wait for the workload to be in flight.
        line = proc.stdout.readline()
        deadline = time.time() + 60
        while "KILL_NOW" not in line and time.time() < deadline:
            line = proc.stdout.readline()
        assert "KILL_NOW" in line

        with open(os.path.join(temp, "pids")) as f:
            head_pid = int(f.read().split()[0])
        os.kill(head_pid, 9)
        time.sleep(1.0)
        os.unlink(os.path.join(temp, "pids"))
        start_head()
        proc.stdin.write("go\n")
        proc.stdin.flush()

        out, _ = proc.communicate(timeout=150)
        assert "ALL_RESULTS_OK" in out, out
        assert "POST_RESTART_OK" in out, out
    finally:
        for w in workers:
            w.kill()
        subprocess.run(cli + ["stop"], env=env, timeout=60)
