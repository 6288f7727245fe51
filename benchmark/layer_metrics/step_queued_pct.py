"""Share of a serving step's device programs (its prefill chunks and its
decode program) that the engine dispatched before it blocked on any of
them: the sum of ``programs_queued`` over the sum of ``programs`` of the
window's ``llm.step`` ring entries, over the steps that ran at least two
programs, x 100 (a step of one program has nothing to queue behind). 100
says the device ran every step's programs back to back with no host
between them; a chunk that is alone in flight, a request that samples
with a temperature and a proposer each make the host fetch a chunk's
result before it builds the decode step, and the programs dispatched
after that fetch are not counted."""

from benchmark import timeline

KEY = "programs_queued"


def read(c):
    steps = [e for e in timeline.entries(c, KEY) if e.get("programs", 0) >= 2]
    ran = sum(e["programs"] for e in steps)
    return 100.0 * sum(e[KEY] for e in steps) / ran if ran else None
