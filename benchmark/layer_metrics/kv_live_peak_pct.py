"""Highest share of the KV pool's blocks that live requests held, as
the window closes: ``engine_stats()["kv_util_peak"]``, which the engine
raises inside every step, after admission and after decode. Blocks that
only the prefix cache keeps (parked, evictable) count as free."""


def read(c):
    stats = c.get("engine_stats")
    if not stats or "kv_util_peak" not in stats[1]:
        return None
    return 100.0 * stats[1]["kv_util_peak"]
