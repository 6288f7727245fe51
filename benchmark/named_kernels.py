"""Device time a decode step spends in Mosaic kernels found BY NAME:
the name on their ``pallas_call`` is the HLO instruction's name, which
opens the op event's text on a TPU trace (``%attn_full.2 = ...``).
Never by an operand's shape (``benchmark/kernels.py`` finds the older
kernels so, and PR 28 was lost to a reshaped operand).

Seconds are the kernels' self time over the traced span; a step is one
execution of the decode program, ``jit_llm_decode`` on the trace's
``XLA Modules`` line. (Not the op events' own count: on this trace a
Mosaic call shows as two events of one name, one inside the other, so
counting events halves every figure: ``moe_roofline_pct`` read 174.7%
that way in PR 32's first traced run.) An execution cut by the span's
edge counts whole, so a figure errs low by at most one step in ~50."""

from benchmark import xplane

DECODE_PROGRAM = "jit_llm_decode"


def per_decode_step_s(c, needle: str):
    """Seconds a decode step in the kernels whose name holds
    ``needle``, or None where the trace has no such kernel or no decode
    program."""
    t = c.get("trace")
    if not t:
        return None
    secs, calls = xplane.matching_s(t, [needle])
    steps = sum(n for name, (n, _) in t["modules"].items()
                if DECODE_PROGRAM in name)
    if not calls or not steps:
        return None
    return secs / steps
