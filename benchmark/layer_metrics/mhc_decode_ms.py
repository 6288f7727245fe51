"""Device time one decode step spends on the four-stream residual path:
the self time of the Mosaic kernels named ``mhc_pre_decode`` and
``mhc_post_decode`` (ray_tpu/ops/mhc.py's two kernels under the names
ray_tpu/models/xing4.py gives them in its decode program), every
sublayer of every layer, over the executions of ``jit_llm_decode``. At
64 rows a step these are latency (28 launches and 14 Sinkhorn chains),
not bytes. A program whose step has no such kernels reads nothing."""

from benchmark import named_kernels

NEEDLES = ("%mhc_pre_decode", "%mhc_post_decode")


def read(c):
    parts = [named_kernels.per_decode_step_s(c, needle) for needle in NEEDLES]
    return None if None in parts else sum(parts) * 1e3
