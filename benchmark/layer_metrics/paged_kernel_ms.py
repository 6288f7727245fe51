"""Device time of the paged decode kernel in one decode step: the mean
self time of the trace's Mosaic custom-call events that take the
configuration's KV pool, times the model's layers (the kernel runs once
a layer in a decode step)."""

from benchmark import kernels


def read(c):
    t = c.get("trace")
    if not t:
        return None
    secs, calls = kernels.mosaic_s(t, kernels.paged_operand(c))
    if not calls:
        return None
    return secs / calls * c["model_fields"]["n_layer"] * 1e3
