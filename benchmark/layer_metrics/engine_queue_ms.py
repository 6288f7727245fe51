"""Mean wait of a request between ``add_request`` and its admission
into the batch, over the admissions of the window:
``engine_stats()["phase_hist"]["engine_queue"]``."""

from benchmark import timeline


def read(c):
    return timeline.hist_mean_ms(c, "engine_queue")
