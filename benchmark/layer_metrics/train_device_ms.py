"""Median device span of a training step, host clock from dispatch to
``block_until_ready``: the ``train_device_ms`` that ``train.wrap_step``
hands to ``report``. With ``train_host_gap_ms`` it makes up the step:
a run that reads slow shows here whether the device's span grew (the
device, or the host's wake-up after it) or the host's part between
spans did."""

import statistics


def read(c):
    spans = [r["train_device_ms"] for r in c.get("reports", [])
             if "train_device_ms" in r]
    return statistics.median(spans) if spans else None
