"""Node providers — pluggable cloud/provisioning backends for the autoscaler.

Capability parity target: the reference's NodeProvider plugin interface
(/root/reference/python/ray/autoscaler/node_provider.py) with its
aws/gcp/fake_multinode implementations. TPU-native difference: the unit
of provisioning is a *slice* — a gang of host processes that joins and
leaves the cluster atomically (SURVEY §7 stage 11: "autoscaler that
scales slices via a NodeProvider-style plugin").

`LocalNodeProvider` is the in-process implementation (reference analogue:
`fake_multi_node.FakeMultiNodeProvider`): each slice is `hosts` extra
node daemons (`ray_tpu._private.node_main`) on this machine, used by the
autoscaler tests and by `AutoscalingCluster`.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

from ray_tpu._private.ids import NodeID


@dataclass
class SliceHandle:
    """One provisioned slice: provider-level id + its cluster node ids."""
    slice_id: str
    node_type: str
    node_ids: List[str]  # hex NodeIDs of the member hosts
    meta: dict = field(default_factory=dict)


class NodeProvider:
    """Interface the autoscaler drives. Implementations provision whole
    slices (1 host for CPU node types, N hosts for TPU pod slices)."""

    def create_slice(self, node_type: str, resources: dict,
                     hosts: int = 1) -> SliceHandle:
        raise NotImplementedError

    def terminate_slice(self, slice_id: str) -> None:
        raise NotImplementedError

    def non_terminated_slices(self) -> List[SliceHandle]:
        raise NotImplementedError

    def shutdown(self) -> None:
        for h in list(self.non_terminated_slices()):
            self.terminate_slice(h.slice_id)


class LocalNodeProvider(NodeProvider):
    """Slices are gangs of local `node_main` subprocesses attached to the
    driver's head — the fake_multinode-equivalent test/one-machine
    provider."""

    def __init__(self, head_address: tuple, session_id: str):
        self.head_address = tuple(head_address)
        self.session_id = session_id
        self._slices: Dict[str, SliceHandle] = {}
        self._procs: Dict[str, List[subprocess.Popen]] = {}
        self._counter = 0

    def _spawn_host(self, node_type: str, resources: dict,
                    node_id: NodeID) -> subprocess.Popen:
        env = dict(os.environ)
        host, port = self.head_address
        env.update({
            "RT_HEAD_ADDR": f"{host}:{port}",
            "RT_SESSION_ID": self.session_id,
            "RT_NODE_ID": node_id.hex(),
            "RT_NODE_TYPE": node_type,
            "RT_NODE_RESOURCES": json.dumps(resources),
            # Locally provisioned "hosts" share this machine with the
            # head, whose device lane owns the chips: CPU platform.
            "JAX_PLATFORMS": "cpu",
        })
        return subprocess.Popen(
            [sys.executable, "-m", "ray_tpu._private.node_main"], env=env)

    def create_slice(self, node_type: str, resources: dict,
                     hosts: int = 1) -> SliceHandle:
        self._counter += 1
        slice_id = f"{node_type}-{self._counter}"
        node_ids, procs = [], []
        for _ in range(hosts):
            nid = NodeID.from_random()
            procs.append(self._spawn_host(node_type, resources, nid))
            node_ids.append(nid.hex())
        handle = SliceHandle(slice_id=slice_id, node_type=node_type,
                             node_ids=node_ids)
        self._slices[slice_id] = handle
        self._procs[slice_id] = procs
        return handle

    def terminate_slice(self, slice_id: str) -> None:
        handle = self._slices.pop(slice_id, None)
        if handle is None:
            return
        for proc in self._procs.pop(slice_id, []):
            try:
                proc.kill()
                proc.wait(timeout=5)
            except Exception:  # lint: allow-swallow(already terminated)
                pass

    def non_terminated_slices(self) -> List[SliceHandle]:
        live = []
        for sid, handle in list(self._slices.items()):
            procs = self._procs.get(sid, [])
            if procs and all(p.poll() is None for p in procs):
                live.append(handle)
            elif any(p.poll() is not None for p in procs):
                # A host died => the slice is gone as a unit (gang
                # semantics); reap the rest.
                self.terminate_slice(sid)
        return live


class SimulatedNodeProvider(NodeProvider):
    """Pure in-memory provider for closed-loop sims and benches
    (reference analogue: autoscaler/v2 FakeCloud in the reference's
    scheduler tests). A slice is a table row; its member "hosts" are
    synthetic node ids the embedding harness reports ALIVE once
    ``boot_delay_s`` of (possibly virtual) clock has elapsed. Supports
    chaos (``kill_slice``) so churn tests can shrink the fleet under
    running gangs and watch the requeue machinery, not a mock of it."""

    def __init__(self, clock: Callable[[], float] = time.monotonic,
                 boot_delay_s: float = 0.0):
        self._clock = clock
        self.boot_delay_s = boot_delay_s
        self._slices: Dict[str, SliceHandle] = {}
        self._created: Dict[str, float] = {}
        self._counter = 0
        self.killed: List[str] = []  # chaos kills, for assertions

    def create_slice(self, node_type: str, resources: dict,
                     hosts: int = 1) -> SliceHandle:
        self._counter += 1
        slice_id = f"sim-{node_type}-{self._counter}"
        handle = SliceHandle(
            slice_id=slice_id, node_type=node_type,
            node_ids=[f"{slice_id}-h{i}" for i in range(hosts)],
            meta={"resources": dict(resources), "hosts": hosts})
        self._slices[slice_id] = handle
        self._created[slice_id] = self._clock()
        return handle

    def terminate_slice(self, slice_id: str) -> None:
        self._slices.pop(slice_id, None)
        self._created.pop(slice_id, None)

    def kill_slice(self, slice_id: str) -> bool:
        """Chaos: the slice dies out from under the cluster (vs. an
        orderly terminate). Gang semantics: all member hosts vanish."""
        if self._slices.pop(slice_id, None) is None:
            return False
        self._created.pop(slice_id, None)
        self.killed.append(slice_id)
        return True

    def non_terminated_slices(self) -> List[SliceHandle]:
        return list(self._slices.values())

    def ready(self, slice_id: str) -> bool:
        created = self._created.get(slice_id)
        return created is not None \
            and self._clock() - created >= self.boot_delay_s

    def ready_node_ids(self) -> List[str]:
        """Member host ids of every booted slice — what the harness
        feeds the snapshot/reconcile as ALIVE."""
        out: List[str] = []
        for sid, handle in self._slices.items():
            if self.ready(sid):
                out.extend(handle.node_ids)
        return out
