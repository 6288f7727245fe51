"""Highest share of the state slots that lanes held or that were parked
snapshots some sequence had taken up, as the window closes:
``engine_stats()["state_live_peak"]``, which the state pool raises
where a lane is granted a slot and where a snapshot is taken up
(ray_tpu/llm/kv_cache.py ``StatePool``). Snapshots nobody took up are
evictable and count as free, as a parked block does in
``kv_live_peak_pct``. A program whose sequences keep no state reads
nothing."""


def read(c):
    stats = c.get("engine_stats")
    if not stats or "state_live_peak" not in stats[1]:
        return None
    return 100.0 * stats[1]["state_live_peak"]
