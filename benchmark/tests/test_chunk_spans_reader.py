"""``chunk_spans_per_program`` (PR 67): how many spans of prompts a
chunk program carried, from the ``llm.step`` ring's ``prefill_spans``.
Run with ``python -m pytest benchmark/tests``."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402

NAME = "chunk_spans_per_program"


def _read(name, c):
    return harness.load_module("layer_metrics", name).read(c)


def _step(spans, ms=30.0):
    """A ring entry whose chunk programs carried ``spans`` (the rows of
    each program's spans)."""
    return {"name": "llm.step", "prefill_spans": spans,
            "prefill_chunks": [[sum(s), 512 * len(s), ms, 1.0]
                               for s in spans]}


@pytest.mark.parametrize("steps, want", [
    ([_step([[512, 1536]]), _step([[2048]]), _step([])], 1.5),
    ([_step([[512, 1024, 512]]), _step([[1024], [1024]])], 5 / 3),
    ([_step([[512]]), _step([[512], [256]])], 1.0),
], ids=["tail_and_head", "a_fallback_to_two_programs", "every_span_alone"])
def test_spans_over_programs_of_the_window(steps, want):
    c = {"engine_steps": steps}
    assert _read(NAME, c) == pytest.approx(want)
    # The readers the benchmark had divide a PROGRAM's time by a
    # program's row, whatever it carried.
    assert _read("latent_chunk_ms", c) == pytest.approx(30.0)
    assert _read("chunk_dispatch_ms", c) == pytest.approx(1.0)


@pytest.mark.parametrize("c", [
    {}, {"engine_steps": []},
    {"engine_steps": [{"name": "llm.step",
                       "prefill_chunks": [[512, 512, 30.0, 1.0]]}]},
    {"engine_steps": [_step([])]},
], ids=["empty", "no_steps", "parent_program", "no_chunk_in_the_window"])
def test_a_ring_without_the_field_reads_none_and_not_an_error(c):
    """The parent's ring entries carry ``prefill_chunks`` alone: None,
    and the line leaves the metric out."""
    assert _read(NAME, c) is None


def test_the_engine_writes_the_field_this_reads():
    """A step of the real engine (GPT-2's tiny configuration, whose
    program takes one span): a row a program in both lists."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.llm import LLMEngine
    from ray_tpu.models.gpt import GPTConfig, init
    from ray_tpu.util import perfmodel

    cfg = GPTConfig(vocab_size=64, max_seq=32, d_model=32, n_layer=1,
                    n_head=2, dtype=jnp.float32)
    eng = LLMEngine(init(jax.random.PRNGKey(0), cfg), cfg, num_blocks=16,
                    block_size=8, max_batch=2, prefill_chunk_tokens=16)
    for prompt in ([1, 2, 3, 4, 5, 6, 7, 8, 9], [4, 5, 6]):
        eng.add_request(prompt, max_tokens=2)
    eng.step()
    entry = perfmodel.device_step_events()[-1]
    assert entry["prefill_spans"] == [[16], [8]]
    assert _read(NAME, {"engine_steps": [entry]}) == 1.0


def test_the_manifest_lists_it_once_for_its_cells():
    """Membership, not position: the next PR appends behind it."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    (found,) = [m for m in manifest["per_layer"] if m["name"] == NAME]
    assert found == {
        "name": NAME, "unit": "spans", "better": "higher",
        "source": "program_counter", "layer": "Scheduler",
        "moves": "serve_tokens_per_s",
        "workloads": ["xing4-serve-rag", "kimi-k25-serve-docs"]}
    reports = {m["name"]: m.get("workloads") for m in manifest["end_to_end"]}
    assert set(found["workloads"]) <= set(reports[found["moves"]])
