"""One yardstick: a statement about speed comes from ``benchmark/run.py``
on the chip and is recorded in ``PERF_LEDGER.jsonl``. The repo once had a
second benchmark (a root ``bench.py`` and two scripts) whose CPU rates
stood in JSON files at the root beside the ledger, under the names of the
benchmark's metrics. These hold the root and the imports to what is left.
"""

import ast
import fnmatch
import importlib.util
import pathlib
import subprocess

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent

GONE = {"bench", "ray_tpu.scripts.serve_bench", "ray_tpu.scripts.data_bench"}


def _root_files():
    """Tracked files at the root; the directory's listing where the
    tree is not a git checkout (the driver's copy is one)."""
    try:
        out = subprocess.run(
            ["git", "ls-files", "--", ":(glob)*"], cwd=ROOT, check=True,
            capture_output=True, text=True, timeout=60).stdout.split("\n")
    except (OSError, subprocess.SubprocessError):
        out = []
    return sorted(n for n in out if n and (ROOT / n).exists()) or \
        sorted(p.name for p in ROOT.iterdir() if p.is_file())


def test_the_only_record_of_speed_at_the_root_is_the_benchmarks():
    names = _root_files()
    assert "BENCHMARK.json" in names and "PERF_LEDGER.jsonl" in names
    records = [n for n in names
               if fnmatch.fnmatch(n, "*BENCH*.json")
               or fnmatch.fnmatch(n, "MULTICHIP_*.json")]
    assert records == ["BENCHMARK.json"], records


def _imports(path):
    """Every module a file imports, relative ones made absolute."""
    tree = ast.parse(path.read_text(), filename=str(path))
    package = ".".join(path.relative_to(ROOT).parts[:-1])
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            yield from (a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            base = importlib.util.resolve_name(
                "." * node.level + (node.module or ""), package)
            yield base
            yield from (f"{base}.{a.name}" for a in node.names)


@pytest.mark.parametrize("top", ["ray_tpu", "tests"])
def test_nothing_imports_the_second_benchmark(top):
    files = sorted((ROOT / top).rglob("*.py"))
    assert len(files) > 50
    users = {str(p.relative_to(ROOT)): sorted(GONE & set(_imports(p)))
             for p in files}
    assert not {p: m for p, m in users.items() if m}
    for gone in ("bench.py", "ray_tpu/scripts/serve_bench.py",
                 "ray_tpu/scripts/data_bench.py"):
        assert not (ROOT / gone).exists(), gone
