"""Standalone head-store replica daemon (``rtpu head-replica``).

Runs a ReplicaServer: an authenticated endpoint persisting the head's
snapshot/append stream into its own files, so cluster metadata survives
the loss of the head NODE (reference: the remote Redis GCS backend,
src/ray/gcs/store_client/redis_store_client.h). Point the head at it
with RT_HEAD_REPLICAS=host:port[,host:port...].

Env: RT_REPLICA_PORT (default 7380), RT_REPLICA_DIR (default
./rtpu-head-replica), RT_SESSION_TOKEN / RT_TOKEN_FILE (must match the
cluster's credential).
"""

from __future__ import annotations

import asyncio
import os
import sys


def main():
    os.environ["JAX_PLATFORMS"] = "cpu"  # never opens the chip
    from . import rpc as _rpc
    from .head_replica import ReplicaServer

    token = os.environ.get("RT_SESSION_TOKEN") or _rpc.discover_session_token()
    if not token:
        print("head-replica: no RT_SESSION_TOKEN / RT_TOKEN_FILE; "
              "refusing to serve unauthenticated", file=sys.stderr)
        return 2
    _rpc.set_session_token(token)

    port = int(os.environ.get("RT_REPLICA_PORT", "7380"))
    directory = os.environ.get("RT_REPLICA_DIR", "./rtpu-head-replica")

    async def serve():
        server = ReplicaServer(directory, port=port)
        addr = await server.start()
        print(f"head-store replica on {addr[0]}:{addr[1]} -> {directory}",
              flush=True)
        await asyncio.Event().wait()

    asyncio.run(serve())
    return 0


if __name__ == "__main__":
    sys.exit(main())
