"""Median host time around the device span of a training step: the
``train_host_gap_ms`` that ``train.wrap_step`` hands to ``report``
(report-to-report wall time less dispatch-to-``block_until_ready``)."""

import statistics


def read(c):
    gaps = [r["train_host_gap_ms"] for r in c.get("reports", [])
            if "train_host_gap_ms" in r]
    return statistics.median(gaps) if gaps else None
