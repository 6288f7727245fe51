"""Standalone worker-node process (`python -m ray_tpu._private.node_main`).

Capability parity target: the reference raylet main
(/root/reference/src/ray/raylet/main.cc) — a per-node daemon that
registers with the head control plane, heartbeats, hosts a worker pool +
object store, and executes work forwarded by owners.

Spawned by `ray_tpu.cluster_utils.Cluster.add_node` (tests) or by cluster
tooling. Environment contract:

    RT_HEAD_ADDR       host:port of the head service
    RT_SESSION_ID      cluster session id
    RT_NODE_ID         hex node id chosen by the parent (optional)
    RT_NODE_RESOURCES  json resource dict, e.g. {"CPU": 2, "x": 1}

The process exits when the head connection drops (driver gone).
"""

from __future__ import annotations

import asyncio
import json
import os
import sys

from .config import get_config
from .ids import NodeID
from .node_service import NodeService
from .object_store import make_store


async def amain():
    # Manual node bring-up against a CLI-started head: the cluster
    # credential lives in the env or the head's token file.
    from . import rpc as _rpc

    _rpc.discover_session_token()
    head_host, head_port = os.environ["RT_HEAD_ADDR"].rsplit(":", 1)
    head_addr = (head_host, int(head_port))
    session_id = os.environ["RT_SESSION_ID"]
    node_id = (NodeID.from_hex(os.environ["RT_NODE_ID"])
               if os.environ.get("RT_NODE_ID") else NodeID.from_random())
    resources = json.loads(os.environ.get("RT_NODE_RESOURCES", '{"CPU": 1}'))
    # This daemon hosts the node's device lane, so it is the process
    # that owns the host's chips: it fills in what RT_NODE_RESOURCES
    # leaves out exactly as a local-mode driver does — TPU counted
    # in-process (0 at once under an explicit CPU platform; backend
    # errors raise), the `device` lane, and one TPU_HOST slot on a
    # chip-bearing node (virtual test nodes opt in via explicit
    # resources={"TPU_HOST": 1}).
    from .runtime import _detect_resources

    resources = _detect_resources(num_cpus=resources.get("CPU", 1),
                                  resources=resources)

    # Per-node shm namespace: this node's workers mmap segments the node
    # wrote, and vice versa; other nodes exchange bytes over the peer plane.
    node_session = f"{session_id}-{node_id.hex()[:8]}"
    shm = make_store(node_session)
    sock_dir = os.environ.get("RT_SOCK_DIR", "/tmp")
    sock_path = os.path.join(sock_dir, f"rtpu-{node_session}.sock")

    loop = asyncio.get_running_loop()
    node = NodeService(node_session, sock_path, resources, shm, loop,
                       node_id=node_id, head=None, is_head_node=False)

    from .node_service import attach_node_to_head

    node_type = os.environ.get("RT_NODE_TYPE")
    reconnecting = {"active": False}

    async def on_head_lost(conn):
        # Head gone. It may be restarting (reference: raylets survive a
        # GCS restart and resync via NotifyGCSRestart): retry the dial
        # for a grace period, re-registering with our live directory
        # state; only then conclude the cluster is gone and exit.
        if reconnecting["active"]:
            return
        reconnecting["active"] = True
        try:
            from .rpc import ConnectionLost

            cfg = get_config()
            deadline = asyncio.get_running_loop().time() \
                + cfg.head_reconnect_grace_s
            sys.stderr.write(f"node {node_id.hex()[:12]}: head connection "
                             f"lost; retrying for "
                             f"{cfg.head_reconnect_grace_s:.0f}s\n")
            while asyncio.get_running_loop().time() < deadline:
                try:
                    await attach_node_to_head(
                        node, head_addr, resources, node_type=node_type,
                        on_lost=on_head_lost, start=False,
                        is_head_node=bool(os.environ.get("RT_NODE_IS_HEAD")))
                    sys.stderr.write(f"node {node_id.hex()[:12]}: "
                                     f"re-registered with head\n")
                    return
                except (OSError, ConnectionLost):
                    # Dial refused, or the head died mid-handshake: both
                    # mean "not back yet".
                    await asyncio.sleep(1.0)
            sys.stderr.write(f"node {node_id.hex()[:12]}: head did not come "
                             f"back; exiting\n")
            os._exit(0)
        finally:
            reconnecting["active"] = False

    await attach_node_to_head(
        node, head_addr, resources, node_type=node_type,
        on_lost=on_head_lost,
        # The node daemon co-located with a detached head registers as
        # the cluster's head node (rtpu start --head sets this).
        is_head_node=bool(os.environ.get("RT_NODE_IS_HEAD")))
    sys.stderr.write(f"node {node_id.hex()[:12]} up: peer={node.peer_address} "
                     f"resources={resources}\n")
    # Park forever; work arrives via the peer server / head pushes.
    await asyncio.Event().wait()


def main():
    asyncio.run(amain())


if __name__ == "__main__":
    main()
