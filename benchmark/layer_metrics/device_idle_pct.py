"""Share of the traced span in which no operation ran on the device
(``xplane.idle_pct``: first to last device operation, the device's own
clock). One reader for ``device_idle_pct.train`` and
``device_idle_pct.serve``, which differ in the metric they move."""

from benchmark import xplane


def read(c):
    t = c.get("trace")
    return xplane.idle_pct(t) if t and t["window_s"] > 0 else None
