"""The through-the-framework bench path (JaxTrainer + Data ingest) runs
end to end on the CPU backend — the same code the TPU bench measures."""

import sys
import os

sys.path.insert(0, os.path.dirname(os.path.dirname(__file__)))


def test_framework_bench_path_runs():
    import bench

    result = bench.run_bench_framework()
    assert "framework" in result["metric"]
    assert result["value"] > 0
    # A CPU run names its device and never borrows a device metric's
    # name or unit.
    assert result["device"]["platform"] == "cpu"
    assert "cpu" in result["metric"] and "chip" not in result["unit"]
