"""How far from doubly stochastic the Sinkhorn chain left the worst
``H_res`` of the window: the largest, over the window's decode steps,
of the step program's own counter ``mhc_res_err_x1e6`` (1e6 x the
largest distance from 1 of any row or column sum of any sublayer's
``H_res`` of any row of the step; ray_tpu/models/xing4.py), over 1e6.
With ``hc_eps`` = 1e-6 in both denominators a converged chain reads
about 1e-6. A program without the counter reads nothing."""

from benchmark import timeline


def read(c):
    errs = [e["mhc_res_err_x1e6"]
            for e in timeline.entries(c, "mhc_res_err_x1e6")
            if e.get("decode_tokens", 0) > 0]
    return max(errs) / 1e6 if errs else None
