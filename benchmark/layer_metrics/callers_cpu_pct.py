"""The busiest generator's CPU seconds inside the window (user + system,
all of its caller threads, as the generator reports them with its
records: ``drivers/callers.py``) over the window's seconds, x 100.

The callers are the test rig's, not the program's. Near 100 one
generator's interpreter is saturated and the rig, not the server, sets
the cell's rate: the line then says so. A run whose driver kept no
generator reports has nothing to read."""


def read(c):
    reports = c.get("callers") or []
    if not reports or c["window_s"] <= 0:
        return None
    return 100.0 * max(r["cpu_window_s"] for r in reports) / c["window_s"]
