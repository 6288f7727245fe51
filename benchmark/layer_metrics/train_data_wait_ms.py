"""Median time a training step waits in the dataset shard's batch
iterator, host clock: the ``train_data_wait_ms`` that ``report``
carries (the ``data.next_batch`` phase, part of
``train_host_gap_ms``)."""

from benchmark import timeline


def read(c):
    return timeline.reports(c, "train_data_wait_ms")
