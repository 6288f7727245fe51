"""Stream frames that reach their sockets in one wake of the proxy's
loop: the window's count of the serve/slo phase ``stream_hold`` (one a
chunk that left the replica) over its count of ``proxy_flush`` (one a
callback of the loop, in which every stream of a poll's reply has its
share written). A program whose proxy resumes a task a frame, as before
PR 59, records no ``proxy_flush`` and gives nothing to read; its
equivalent is 1."""

PHASE = "proxy_flush"


def _count(stats, phase):
    return (stats.get("phase_hist", {}).get(phase) or {"count": 0})["count"]


def read(c):
    stats = c.get("engine_stats")
    if not stats:
        return None
    a, b = stats
    wakes = _count(b, PHASE) - _count(a, PHASE)
    frames = _count(b, "stream_hold") - _count(a, "stream_hold")
    return frames / wakes if wakes > 0 else None
