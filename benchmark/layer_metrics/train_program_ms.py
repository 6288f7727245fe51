"""Device time of one execution of the train step's program, on the
device's clock: seconds over executions of ``jit_train_step`` on the
trace's ``XLA Modules`` line. ``train_device_ms`` less this is the
host's dispatch before the program and its wake-up after."""

from benchmark import timeline


def read(c):
    return timeline.module_ms(c, "jit_train_step")
