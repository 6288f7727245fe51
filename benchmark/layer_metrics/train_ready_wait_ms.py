"""Median time ``block_until_ready`` waits on a train step's outputs,
host clock: the ``train_ready_wait_ms`` that ``train.wrap_step`` hands
to ``report`` (the second half of ``train_device_ms``: the device's
work and the host's wake-up after it)."""

from benchmark import timeline


def read(c):
    return timeline.reports(c, "train_ready_wait_ms")
