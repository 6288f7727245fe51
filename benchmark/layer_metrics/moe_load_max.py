"""How uneven the routing of a decode step is: the busiest expert's
tokens over the mean an expert gets, the worst routed layer
(``moe_load_max`` of the window's ``llm.step`` ring entries, which the
step program counts itself and hands over with its token ids); the
median over the steps that decoded."""

from benchmark import timeline


def read(c):
    return timeline.median_or_none(
        [e["moe_load_max"] for e in timeline.entries(c, "moe_load_max")
         if e.get("decode_tokens", 0) > 0])
