"""``step_queued_pct`` (PR 47): the window's ``programs_queued`` over its
``programs``, over the steps that ran at least two, x 100; nothing to
read on a program whose accounting counts no dispatches; the ring key it
reads, which no other reader takes and the accounting sends. Run with
``python -m pytest benchmark/tests``."""

import glob
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402

NAME = "step_queued_pct"


def _reader():
    return harness.load_module("layer_metrics", NAME)


def test_sum_over_sum_of_the_steps_that_ran_two_programs_or_more():
    c = {"engine_steps": [
        {"programs": 3, "programs_queued": 3},      # two chunks, decode
        {"programs": 2, "programs_queued": 2},
        # A request with a temperature ended its prompt here: the step
        # fetched its row after the second of four dispatches.
        {"programs": 4, "programs_queued": 2},
        # Decode alone: nothing to queue behind, left out.
        {"programs": 1, "programs_queued": 1},
        {"programs": 1, "programs_queued": 1}]}
    assert _reader().read(c) == pytest.approx(100.0 * 7 / 9)


def test_every_step_queued_whole_reads_a_hundred():
    step = {"programs": 3, "programs_queued": 3}
    assert _reader().read({"engine_steps": [step] * 5}) \
        == pytest.approx(100.0)


@pytest.mark.parametrize("c", [
    {}, {"engine_steps": None}, {"engine_steps": []},
    {"engine_steps": [{"decode_tokens": 62, "lanes": 62,
                       "device_ms_by": {"prefill": 4.7, "decode": 22.6}}]},
    {"engine_steps": [{"programs": 1, "programs_queued": 1}]},
], ids=["empty", "no_ring", "no_steps", "parent_program", "one_program"])
def test_nothing_to_read_is_none_and_not_an_error(c):
    """The parent commit's engine blocks on each program in turn and
    counts none: the line leaves the metric out."""
    assert _reader().read(c) is None


def test_the_ring_key_is_this_readers_alone_and_the_accounting_sends_it():
    """No other reader's source holds the key (or the metric's name), so
    none sums this counter into its own; and the keys are what
    ``util/perfmodel.py`` puts into a step's breakdown, which the engine
    records as its ``llm.step`` ring entry."""
    key = _reader().KEY
    assert key == "programs_queued"
    others = [p for p in glob.glob(os.path.join(
        ROOT, "benchmark", "layer_metrics", "*.py"))
        if os.path.basename(p) != NAME + ".py"]
    assert len(others) >= 52
    for path in others:
        with open(path) as f:
            source = f.read()
        assert key not in source and NAME not in source, path
    with open(os.path.join(ROOT, "ray_tpu", "util", "perfmodel.py")) as f:
        accounting = f.read()
    assert f'out["{key}"] = self._programs_queued' in accounting
    assert 'out["programs"] = self._programs' in accounting


def test_the_manifest_lists_it_once_for_the_three_serving_cells():
    """Membership, not position: the next PR appends behind it."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == NAME]
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "Scheduler",
        "moves": "serve_tokens_per_s",
        "workloads": ["gpt2s-serve-chat", "laguna-xs2-serve-repo",
                      "kimi-k25-serve-docs"]}
    cells = {w["name"] for w in manifest["workloads"]}
    assert set(entry["workloads"]) <= cells
