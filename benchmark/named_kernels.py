"""Device time of Mosaic kernels and of a step, both found BY NAME.

A kernel is an op event whose INSTRUCTION's name is the name on its
``pallas_call``: on a TPU trace an op event's text is the instruction's
whole HLO text, so the name opens it (``%paged_decode.3 = ...``), and a
Mosaic kernel is a ``custom-call`` whose text holds
``custom_call_target="tpu_custom_call"``. A fusion that takes the
kernel's result names it among its operands, behind the `` = ``, and is
not counted; nor is a fusion that merely shares the name. A step is one execution of a
named program (``jit_llm_decode``, ``jit_train_step``,
``jit_llm_prefill_chunk``) on the trace's ``XLA Modules`` line. Never
an operand's shape (PR 28 was lost to a reshaped operand; the finder
that went by shapes, ``kernels.py``, went with PR 54), and never "the
program that ran most often".

Seconds are the kernels' self time over the traced span, over the
program's executions. (Not over the op events' own count: in PR 32's
first traced run a Mosaic call showed as two events of one name, one
inside the other, and counting events halved every figure,
``moe_roofline_pct`` 174.7%; PR 54's traces show one event a call,
1,476 for 123 executions of 12 layers, and the executions are right
either way.) An execution cut by the span's edge counts whole, so a
serving figure errs low by at most one step in ~50-120; the training
driver starts and stops its session between steps, so its figure is
exact.

Every kernel a reader finds is noted on the trace (``ops_read``), so
that ``unread`` can name the Mosaic kernels that no reader of the cell
asked for: a new kernel cannot go unseen.
"""

MOSAIC = 'custom_call_target="tpu_custom_call"'
DECODE_PROGRAM = "jit_llm_decode"
CHUNK_PROGRAM = "jit_llm_prefill_chunk"
TRAIN_PROGRAM = "jit_train_step"


def is_kernel(op: str, needle: str) -> bool:
    """Whether the op event named ``op`` IS a custom call whose
    instruction name holds ``needle``. (The opcode, not the Mosaic
    target: tier-1's hand-made traces in ``tests/`` write a kernel as
    ``custom-call(...)`` with no target; on the chip's traces the two
    pick the same events.)"""
    name, _, rest = op.partition(" = ")
    return needle in name and "custom-call(" in rest


def per_execution_s(c, needle: str, program: str):
    """Seconds one execution of ``program`` spends in the kernels whose
    name holds ``needle``, or None where the trace has no such kernel
    or no such program."""
    t = c.get("trace")
    if not t:
        return None
    mine = [op for op in t["op_self_s"] if is_kernel(op, needle)]
    t.setdefault("ops_read", set()).update(mine)
    runs = sum(n for name, (n, _) in t["modules"].items() if program in name)
    if not mine or not runs:
        return None
    return sum(t["op_self_s"][op] for op in mine) / runs


def per_decode_step_s(c, needle: str):
    return per_execution_s(c, needle, DECODE_PROGRAM)


def unread(trace: dict) -> list:
    """[name, seconds, ops] of the trace's Mosaic kernels that no reader
    has counted, longest first; ops of one instruction name but for its
    ``.N`` (a program compiled at several lengths) under one entry."""
    read, found = trace.get("ops_read", ()), {}
    for op, s in trace["op_self_s"].items():
        if MOSAIC in op and op not in read:
            name = op.partition(" = ")[0]
            entry = found.setdefault(name.rpartition(".")[0] or name,
                                     [0.0, 0])
            entry[0] += s
            entry[1] += 1
    return sorted(([name, s, n] for name, (s, n) in found.items()),
                  key=lambda e: -e[1])
