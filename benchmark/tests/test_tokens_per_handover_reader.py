"""``tokens_per_handover`` (PR 52): the window's ``tokens_handed`` over
its ``handovers``; nothing to read on a program whose engine counts no
hand-overs; the ring keys it reads, which no other reader takes and the
engine sends. Run with ``python -m pytest benchmark/tests``."""

import glob
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402

NAME = "tokens_per_handover"


def _reader():
    return harness.load_module("layer_metrics", NAME)


def test_sum_over_sum_of_the_windows_steps():
    c = {"engine_steps": [
        # Decode alone: 62 lanes left in one call.
        {"handovers": 1, "tokens_handed": 62},
        # Two chunks ended prompts: their first tokens left together,
        # ahead of the decode program's 60.
        {"handovers": 2, "tokens_handed": 62},
        # A step of chunks only, mid-prompt: nothing left.
        {"handovers": 0, "tokens_handed": 0},
        {"handovers": 2, "tokens_handed": 59}]}
    assert _reader().read(c) == pytest.approx(183 / 5)


def test_a_full_batch_alone_reads_its_lanes():
    step = {"handovers": 1, "tokens_handed": 64}
    assert _reader().read({"engine_steps": [step] * 5}) \
        == pytest.approx(64.0)


@pytest.mark.parametrize("c", [
    {}, {"engine_steps": None}, {"engine_steps": []},
    {"engine_steps": [{"decode_tokens": 62, "lanes": 62, "programs": 2,
                       "device_ms_by": {"prefill": 4.7, "decode": 22.6}}]},
    {"engine_steps": [{"handovers": 0, "tokens_handed": 0}]},
], ids=["empty", "no_ring", "no_steps", "parent_program", "no_sink"])
def test_nothing_to_read_is_none_and_not_an_error(c):
    """The parent commit's engine puts each token on its request's
    queue and counts no hand-over, as this one does for a request
    without a sink: the line leaves the metric out."""
    assert _reader().read(c) is None


def test_the_ring_keys_are_this_readers_alone_and_the_engine_sends_them():
    """No other reader's source holds the keys (or the metric's name),
    so none sums these counters into its own; and the keys are what
    ``llm/engine.py`` puts into its ``llm.step`` ring entry."""
    key = _reader().KEY
    assert key == "handovers"
    others = [p for p in glob.glob(os.path.join(
        ROOT, "benchmark", "layer_metrics", "*.py"))
        if os.path.basename(p) != NAME + ".py"]
    assert len(others) >= 53
    for path in others:
        with open(path) as f:
            source = f.read()
        for word in (key, "tokens_handed", NAME):
            assert word not in source, (path, word)
    with open(os.path.join(ROOT, "ray_tpu", "llm", "engine.py")) as f:
        engine = f.read()
    assert f'"{key}": self._handovers' in engine
    assert '"tokens_handed": self._tokens_handed' in engine


def test_the_manifest_lists_it_once_for_the_three_serving_cells():
    """Membership, not position: the next PR appends behind it."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == NAME]
    assert entry == {
        "name": NAME, "unit": "tokens", "better": "higher",
        "source": "program_counter", "layer": "Scheduler",
        "moves": "serve_tokens_per_s",
        "workloads": ["gpt2s-serve-chat", "laguna-xs2-serve-repo",
                      "kimi-k25-serve-docs"]}
    cells = {w["name"] for w in manifest["workloads"]}
    assert set(entry["workloads"]) <= cells
