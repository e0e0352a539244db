"""The benchmark's own tests run on the CPU (``BENCH_PLATFORM=cpu`` is the
harness's opt-in) at small sizes, over BENCHMARK.json's cells."""

import json
import os
import shutil
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
for path in (BENCH, REPO):
    if path not in sys.path:
        sys.path.insert(0, path)

import harness  # noqa: E402

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    SPEC = json.load(_f)


def make_checkout(tmp_path, spec: dict = SPEC):
    """A checkout of the benchmark (its files, cfg/ and ``spec`` as
    BENCHMARK.json) that finds the program through PYTHONPATH."""
    shutil.copytree(os.path.join(REPO, "cfg"), tmp_path / "cfg")
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp_path


def small(cell: str) -> dict:
    """The cell at a size a test run holds: the ``small`` overlays of its
    configuration file and its mix file."""
    found = harness.load_cell(cell, SPEC)
    return {"config": found.config.get("small", {}),
            "mix": found.mix.get("small", {})}


SMALL = {w["name"]: small(w["name"]) for w in SPEC["workloads"]}
BIG_SEED = 2**31 + 12345
