"""``decode_inputs_written_pct`` (PR 46): the mean over the steps that
decoded of the ring's ``inputs_written`` over ``inputs_size`` (the
engine's kept array's own size), x 100; nothing to read on a program
whose engine counts no writes; the ring keys it reads, which no other
reader takes and the engine sends. Run with ``python -m pytest
benchmark/tests``."""

import glob
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402

NAME = "decode_inputs_written_pct"


def _reader():
    return harness.load_module("layer_metrics", NAME)


def test_mean_share_of_the_arrays_own_size_over_the_steps_that_decoded():
    # The chat cell's array: 64 lanes of 4 + 2 + 64 columns.
    step = {"decode_tokens": 62, "inputs_size": 64 * 70,
            "inputs_written": 448}
    c = {"engine_steps": [
        step, dict(step, inputs_written=224),
        # A step of prefill alone ran no decode program: left out,
        # whatever a lane taken in it wrote.
        dict(step, decode_tokens=0, inputs_written=4480),
        # Another engine's array is another size: the share is of its
        # own.
        dict(step, inputs_size=64 * 1094, inputs_written=3501)]}
    assert _reader().read(c) == pytest.approx(
        100.0 * (0.10 + 0.05 + 3501 / (64 * 1094)) / 3)


def test_a_lane_rebuilt_whole_every_step_reads_a_hundred():
    step = {"decode_tokens": 64, "inputs_size": 4480,
            "inputs_written": 4480}
    assert _reader().read({"engine_steps": [step] * 3}) \
        == pytest.approx(100.0)


@pytest.mark.parametrize("c", [
    {}, {"engine_steps": None}, {"engine_steps": []},
    {"engine_steps": [{"decode_tokens": 62, "lanes": 62}]},
    {"engine_steps": [{"decode_tokens": 0, "inputs_written": 70,
                       "inputs_size": 4480}]},
], ids=["empty", "no_ring", "no_steps", "parent_program", "no_decode"])
def test_nothing_to_read_is_none_and_not_an_error(c):
    """The parent commit's engine builds its arrays anew and counts
    nothing: the line leaves the metric out."""
    assert _reader().read(c) is None


def test_the_ring_keys_are_this_readers_alone_and_the_engine_sends_them():
    """No other reader's source holds the keys (or the metric's name),
    so none sums this counter into its own; and the keys are what
    ``llm/engine.py`` puts into the ``llm.step`` ring entry."""
    key = _reader().KEY
    assert key == "inputs_written"
    others = [p for p in glob.glob(os.path.join(
        ROOT, "benchmark", "layer_metrics", "*.py"))
        if os.path.basename(p) != NAME + ".py"]
    assert len(others) >= 51
    for path in others:
        with open(path) as f:
            source = f.read()
        for word in (key, "inputs_size", NAME):
            assert word not in source, (path, word)
    with open(os.path.join(ROOT, "ray_tpu", "llm", "engine.py")) as f:
        engine = f.read()
    assert f'"{key}": self._inputs_written' in engine
    assert '"inputs_size": self._inputs.size' in engine


def test_the_manifest_lists_it_once_for_the_three_serving_cells():
    """Membership, not position: the next PR appends behind it."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == NAME]
    assert entry == {
        "name": NAME, "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "Scheduler",
        "moves": "serve_tokens_per_s",
        "workloads": ["gpt2s-serve-chat", "laguna-xs2-serve-repo",
                      "kimi-k25-serve-docs"]}
    cells = {w["name"] for w in manifest["workloads"]}
    assert set(entry["workloads"]) <= cells
