"""In-process multi-node test cluster.

Capability parity target: the reference's `ray.cluster_utils.Cluster`
(/root/reference/python/ray/cluster_utils.py:108 — add_node:174,
remove_node:247): N extra node daemons on one machine attached to the
driver's head, used to test cross-node scheduling, placement groups, and
fault tolerance without real hardware. This is the test harness the whole
multi-node axis is built against (SURVEY §4 "Simulated multi-node").

Usage (tests):

    cluster = Cluster()                       # driver process = head node
    n1 = cluster.add_node(num_cpus=2)
    n2 = cluster.add_node(num_cpus=1, resources={"x": 1})
    ...
    cluster.remove_node(n1)                   # SIGKILL + wait for DEAD
    cluster.shutdown()
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from dataclasses import dataclass

from ._private.ids import NodeID


@dataclass
class ClusterNode:
    node_id: NodeID
    proc: subprocess.Popen

    @property
    def node_id_hex(self) -> str:
        return self.node_id.hex()


class Cluster:
    """Head (the current driver runtime) + subprocess worker nodes."""

    def __init__(self, init_args: dict | None = None):
        import ray_tpu

        ray_tpu.init(**(init_args or {}))
        from ._private import context

        self.runtime = context.get_context()
        self.nodes: list[ClusterNode] = []

    @property
    def head_address(self) -> tuple:
        return self.runtime.head_address

    def add_node(self, num_cpus: int = 1, resources: dict | None = None,
                 wait: bool = True, timeout: float = 30.0,
                 labels: dict | None = None) -> ClusterNode:
        res = {"CPU": float(num_cpus), **(resources or {})}
        node_id = NodeID.from_random()
        env = dict(os.environ)
        if labels:
            env["RT_NODE_LABELS"] = ",".join(
                f"{k}={v}" for k, v in labels.items())
        else:
            env.pop("RT_NODE_LABELS", None)
        host, port = self.head_address
        env.update({
            "RT_HEAD_ADDR": f"{host}:{port}",
            "RT_SESSION_ID": self.runtime.session_id,
            "RT_NODE_ID": node_id.hex(),
            "RT_NODE_RESOURCES": json.dumps(res),
            # Added nodes share this machine with the driver, whose
            # device lane owns the chips: they run on the CPU platform.
            "JAX_PLATFORMS": "cpu",
        })
        proc = subprocess.Popen(
            [sys.executable, "-m", "ray_tpu._private.node_main"], env=env)
        node = ClusterNode(node_id=node_id, proc=proc)
        self.nodes.append(node)
        if wait:
            self._wait_node_state(node_id, "ALIVE", timeout)
        return node

    def remove_node(self, node: ClusterNode, force: bool = True,
                    timeout: float = 15.0):
        """Kill a node (SIGKILL when force — chaos-style) and wait until
        the head declares it dead."""
        if force:
            node.proc.kill()
        else:
            node.proc.terminate()
        node.proc.wait(timeout=timeout)
        self._wait_node_state(node.node_id, "DEAD", timeout)
        self.nodes = [n for n in self.nodes if n is not node]

    def _wait_node_state(self, node_id: NodeID, want: str, timeout: float):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            for n in self.runtime.list_nodes():
                if n["node_id"] == node_id.binary() and n["state"] == want:
                    return
            time.sleep(0.05)
        raise TimeoutError(
            f"node {node_id.hex()[:12]} did not reach {want} in {timeout}s")

    def wait_for_nodes(self, count: int, timeout: float = 30.0):
        """Block until the cluster has `count` ALIVE nodes (head included)."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            alive = [n for n in self.runtime.list_nodes()
                     if n["state"] == "ALIVE"]
            if len(alive) >= count:
                return
            time.sleep(0.05)
        raise TimeoutError(f"cluster did not reach {count} nodes")

    def shutdown(self):
        import glob
        import shutil

        import ray_tpu

        session = self.runtime.session_id
        for node in list(self.nodes):
            try:
                node.proc.kill()
                node.proc.wait(timeout=5)
            except Exception:  # lint: allow-swallow(best-effort teardown)
                pass
        self.nodes.clear()
        ray_tpu.shutdown()
        # SIGKILLed nodes can't clean their shm segments / sockets.
        for path in glob.glob(f"/dev/shm/rtpu-{session}-*"):
            shutil.rmtree(path, ignore_errors=True)
        for path in glob.glob(f"/tmp/rtpu-{session}-*.sock"):
            try:
                os.unlink(path)
            except OSError:
                pass
