"""Assignments of a decode step that fell on the experts this chip
holds, a routed layer's mean (``moe_held_rows`` of the window's
``llm.step`` ring entries, which the step program counts itself and
hands over with its token ids); the mean over the steps that decoded.
How far the share's expert load is from the deployment's: 12 of 384
experts behind 64 lanes get 64 x 8 x 12 / 384 = 16 assignments a step
where the deployment's 2,048 lanes would give them 512."""

from benchmark import timeline


def read(c):
    rows = [e["moe_held_rows"] for e in timeline.entries(c, "moe_held_rows")
            if e.get("decode_tokens", 0) > 0]
    return sum(rows) / len(rows) if rows else None
