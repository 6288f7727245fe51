"""Share of the decode program's packed input array that the host wrote
in a step: ``inputs_written`` over ``inputs_size`` of the window's
``llm.step`` ring entries (the engine keeps the program's ONE
``[max_batch, W]`` int32 array from step to step and counts the
elements it writes: a lane's tokens, positions, slots and lengths every
step, a block id where a block is granted or given back, a row where a
request takes or leaves a lane); the mean over the steps that decoded,
x 100. It says the host's part of a step costs what changed since the
step before: a lane's row rebuilt whole every step would read ~100."""

from benchmark import timeline

KEY = "inputs_written"


def read(c):
    rows = [e[KEY] / e["inputs_size"] for e in timeline.entries(c, KEY)
            if e.get("decode_tokens", 0) > 0 and e.get("inputs_size")]
    return 100.0 * sum(rows) / len(rows) if rows else None
