"""Median time the jitted train step's call takes to return its
futures, host clock: the ``train_dispatch_ms`` that ``train.wrap_step``
hands to ``report`` (the first half of ``train_device_ms``)."""

from benchmark import timeline


def read(c):
    return timeline.reports(c, "train_dispatch_ms")
