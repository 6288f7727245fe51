"""Tokens that leave the engine for one call of a sink: the sum of
``tokens_handed`` over the sum of ``handovers`` of the window's
``llm.step`` ring entries. A hand-over is one lock and one wake-up on
the serving side however many lanes it carries: a step makes one where
its settled chunks' first tokens are decided and one where the decode
step's emission ends, so a full batch reads most of its lanes. An engine
that puts each token on a queue of its own behind a thread a stream, as
before PR 52, counts no hand-overs and gives nothing to read; its
equivalent is 1."""

from benchmark import timeline

KEY = "handovers"


def read(c):
    steps = timeline.entries(c, KEY)
    calls = sum(e[KEY] for e in steps)
    return sum(e["tokens_handed"] for e in steps) / calls if calls else None
