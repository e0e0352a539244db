"""A later PR adds a cell, a mix, a metric or a whole configuration by
adding files and BENCHMARK.json entries only: the harness finds each by
name."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

from conftest import BENCH, BIG_SEED, REPO, SPEC, make_checkout

DRIVER = """
import json, sys
sys.path.insert(0, "benchmark")
import harness
out = harness.run(%r, %d, 1.5, %s, allow_cpu=True)
print(json.dumps(out))
"""
CONTROL = """
import json, sys
sys.path.insert(0, "benchmark")
import control, harness
checks = control.control_checks(harness.load_cell(%r), %d)
print(json.dumps({"correct": harness.passes(checks), "checks": checks}))
"""
# what a configuration made of files may not edit
SHARED = ["harness.py", "traffic.py", "trees.py", "control.py", "parts.py",
          "reference.py", "tests/conftest.py"]


def _last_line(checkout, script: str) -> dict:
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO}
    proc = subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True, timeout=300,
                          cwd=checkout, env=env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _run(checkout, cell: str, traced: bool) -> dict:
    return _last_line(checkout, DRIVER % (cell, BIG_SEED, traced))


def test_dummy_cell_mix_and_metric(tmp_path):
    checkout = make_checkout(tmp_path)
    bench = tmp_path / "benchmark"
    first = SPEC["workloads"][0]
    mix = json.loads(
        (bench / "traffic" / f"{first['traffic']}.json").read_text())
    mix["batch"], mix["pool"] = 128, 2
    (bench / "traffic" / "dummy-batch128.json").write_text(json.dumps(mix))
    (bench / "metrics" / "calls_driven.dummy.py").write_text(
        "def read(r):\n    return float(r.window['calls'])\n")
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "first.dummy", "config": first["config"],
                              "traffic": "dummy-batch128", "chips": 1,
                              "why": "a test's cell"})
    for m in spec["end_to_end"]:
        if m["name"] == "decisions_per_s":
            m["workloads"].append("first.dummy")
    spec["per_layer"].append({
        "name": "calls_driven.dummy", "unit": "calls", "better": "higher",
        "source": "host_clock", "layer": "Transport",
        "moves": "decisions_per_s", "workloads": ["first.dummy"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    out = _run(checkout, "first.dummy", False)
    assert out["correct"]
    assert set(out["metrics"]) == {"decisions_per_s", "setup_s"}
    out = _run(checkout, "first.dummy", True)
    assert out["metrics"]["calls_driven.dummy"]["value"] > 0


def test_dummy_configuration_of_new_files(tmp_path):
    """A configuration that brings its own tree builder, row kind,
    reference (a copy of ``reference.py``), mix and cell runs correct
    end to end, and its control does not, with no shared file edited."""
    checkout = make_checkout(tmp_path)
    bench = tmp_path / "benchmark"
    dummy = os.path.join(BENCH, "tests", "dummy")
    for kind in os.listdir(dummy):
        (bench / kind).mkdir(exist_ok=True)
        for name in os.listdir(os.path.join(dummy, kind)):
            assert not (bench / kind / name).exists()
            shutil.copy(os.path.join(dummy, kind, name), bench / kind / name)
    (bench / "references").mkdir(exist_ok=True)
    shutil.copy(bench / "reference.py", bench / "references" / "dummy_ref.py")
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "dummy", "source": "a test's",
                            "file": "benchmark/configs/dummy.json",
                            "reduced": [], "why": "a test's"})
    spec["workloads"].append({"name": "dummy.batch128", "config": "dummy",
                              "traffic": "dummy-batch128", "chips": 1,
                              "why": "a test's cell"})
    for m in spec["end_to_end"]:
        if m["name"] == "decisions_per_s":
            m["workloads"].append("dummy.batch128")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    out = _run(checkout, "dummy.batch128", False)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["checks"]["answers_compared"][
        "value"] >= out["attempted"]
    assert (tmp_path / ".bench_scratch" / "trees" / "dummy.batch128" /
            "rules.yaml").exists()
    ctl = _last_line(checkout, CONTROL % ("dummy.batch128", BIG_SEED))
    assert ctl["correct"] is False, ctl["checks"]
    assert ctl["checks"]["wrong_decisions"]["value"] > 0
    for name in SHARED:
        with open(os.path.join(BENCH, name), "rb") as f:
            assert (bench / name).read_bytes() == f.read(), name
