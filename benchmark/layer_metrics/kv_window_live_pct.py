"""Highest share of the window kind's pool that live lanes held:
``engine_stats()["kv_window_util_peak"]``, raised inside every step
from the blocks lanes hold before the step's finishes release theirs
(parked prefix tails count as free, as in ``kv_live_peak_pct``). A
lane holds at most window / block_size + 2 blocks there however long
its context, so this says how far the pool could shrink."""


def read(c):
    stats = c.get("engine_stats")
    if not stats or stats[1].get("kv_window_util_peak") is None:
        return None
    return 100.0 * stats[1]["kv_window_util_peak"]
