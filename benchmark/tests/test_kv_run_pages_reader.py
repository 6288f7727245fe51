"""``kv_run_pages_pct`` (PR 42): the mean of the ring's
``kv_pages_in_runs`` over the steps that decoded, x 100; nothing to read
on a program that counts no runs; and the ring key it reads, which no
other reader takes and both step programs send. Run with ``python -m
pytest benchmark/tests``."""

import glob
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402

NAME = "kv_run_pages_pct"


def _reader():
    return harness.load_module("layer_metrics", NAME)


def test_mean_over_the_steps_that_decoded():
    step = {"decode_tokens": 62, "kv_pages_in_runs": 0.968}
    c = {"engine_steps": [
        step, dict(step, kv_pages_in_runs=0.952),
        # A step of prefill alone decoded nothing and counts nothing.
        dict(step, decode_tokens=0, kv_pages_in_runs=0.0)]}
    assert _reader().read(c) == pytest.approx(96.0)


@pytest.mark.parametrize("c", [
    {}, {"engine_steps": None}, {"engine_steps": []},
    {"engine_steps": [{"decode_tokens": 62, "moe_held_rows": 16}]},
    {"engine_steps": [{"decode_tokens": 0, "kv_pages_in_runs": 0.0}]},
], ids=["empty", "no_ring", "no_steps", "parent_program", "no_decode"])
def test_nothing_to_read_is_none_and_not_an_error(c):
    """The parent commit's step programs count no runs, and GPT-2's
    never will: the line leaves the metric out."""
    assert _reader().read(c) is None


def test_the_ring_key_is_this_readers_alone_and_the_programs_send_it():
    """No other reader's source holds the key (or the metric's name),
    so none sums this counter into its own; and the key is what
    ``llm/engine.py`` makes of the name both step programs give their
    counter row (a ``_x1000`` name is a ratio on the ring)."""
    key = _reader().KEY
    assert key == "kv_pages_in_runs"
    others = [p for p in glob.glob(os.path.join(
        ROOT, "benchmark", "layer_metrics", "*.py"))
        if os.path.basename(p) != NAME + ".py"]
    assert len(others) >= 50
    for path in others:
        with open(path) as f:
            source = f.read()
        assert key not in source and NAME not in source, path
        other = os.path.basename(path)[:-3]
        assert other not in key and key not in other
    from ray_tpu.models import kimi_k2, laguna
    for model in (laguna, kimi_k2):
        assert model.COUNTERS[-1] == key + "_x1000"


def test_the_manifest_lists_it_once_for_the_two_long_context_cells():
    """Membership, not position: the next PR appends behind it."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == NAME]
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "Kernels",
        "moves": "serve_tokens_per_s",
        "workloads": ["laguna-xs2-serve-repo", "kimi-k25-serve-docs"]}
