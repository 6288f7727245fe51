"""Spans of prompts a chunk program carried, a program: the spans of
every row of ``prefill_spans`` in the window's ``llm.step`` ring entries
over the rows (a row a chunk PROGRAM, beside its row of
``prefill_chunks``; an entry of the row a span, its rows computed).
ray_tpu/llm/engine.py ``_run_prefills`` fills a program with the spans
a step's budget buys where the family's program takes several
(``Serving.chunk_spans``), so this reads how often that engaged: 1.00
where every span rode alone (a family whose program takes one; spans
behind documents whose contexts do not fit one table), towards 2 where
a step's budget is the tail of one body and the head of the next. A
program whose ring carries no such field, as every commit before PR 67,
gives nothing to read."""

from benchmark import timeline


def read(c):
    programs = [spans for e in timeline.entries(c, "prefill_spans")
                for spans in e["prefill_spans"]]
    if not programs:
        return None
    return sum(len(spans) for spans in programs) / len(programs)
