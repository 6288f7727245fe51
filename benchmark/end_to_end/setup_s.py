"""Seconds from the start of the process to the first timed instant:
imports, init, weights, deployment or trainer start, warm-up of the
cell's shapes, the reference comparison, and (serving) the ramp."""


def read(c):
    return c["set_up_seconds"]
