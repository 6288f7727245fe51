"""CPU microseconds that the runtime's actor-call machinery spends a
token frame in the served process: ``serve_cpu_us_per_frame``'s reading
for the groups ``rt-core-loop`` (the runtime's event loop: a
``stream_poll`` call in, its result out), ``actor`` (the replica's call
slots) and ``device-exec`` alone. It is the part of the serving side's
CPU that a cheaper actor call in the device lane's process would take
away; the proxy's loop and the pollers are the rest."""

from benchmark import harness

RUNTIME = ("rt-core-loop", "actor", "device-exec")


def read(c):
    shared = harness.load_module("layer_metrics", "serve_cpu_us_per_frame")
    return shared.per_frame(c, lambda g: g in RUNTIME)
