"""The harness at a small size on the CPU: every cell's result line has
exactly the contract's keys and is correct, the traced run reads its
per-layer metrics, and the command refuses what it must."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import BIG_SEED, SMALL, SPEC, make_checkout

import harness

KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]
ALG = "urn:oasis:names:tc:xacml:3.0:rule-combining-algorithm:permit-overrides"


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_cell_result_line(cell):
    out = harness.run(cell, BIG_SEED, 2.5, False, allow_cpu=True,
                      scale=SMALL[cell], spec=SPEC)
    assert list(out) == KEYS
    assert out["correct"], out["checks"]
    assert out["device"]["platform"] == "cpu"
    spec = harness.load_cell(cell, SPEC)
    assert set(out["metrics"]) == {m["name"] for m in spec.end_to_end}
    assert out["attempted"] > 0 and out["failed"] == 0


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_cell_traced(cell):
    out = harness.run(cell, BIG_SEED + 1, 2.5, True, allow_cpu=True,
                      scale=SMALL[cell], spec=SPEC)
    assert list(out) == KEYS[:-1] + ["breakdown", "checks"]
    assert out["correct"], out["checks"]
    assert out["device"]["window_s"] > 0
    declared = {m["name"]: m for m in harness.load_cell(cell, SPEC).per_layer}
    assert set(out["metrics"]) <= set(declared)
    # on the CPU only device-trace metrics may find nothing to read
    missing = set(declared) - set(out["metrics"])
    assert all(declared[m]["source"] == "device_trace" for m in missing)


def _cli(cwd, env=None):
    return subprocess.run(
        [sys.executable, "benchmark/run.py", *ARGS], capture_output=True,
        text=True, timeout=300, cwd=cwd,
        env={**os.environ, "JAX_PLATFORMS": "cpu", **(env or {})})


# the first cell at full size on the CPU
ARGS = ["--workload", SPEC["workloads"][0]["name"], "--seed", str(BIG_SEED),
        "--seconds", "2", "--trace", "0"]
PROGRAM = {"PYTHONPATH": harness.CHECKOUT}


def test_cli_refuses_cpu_without_opt_in(tmp_path):
    proc = _cli(make_checkout(tmp_path), {**PROGRAM, "BENCH_PLATFORM": ""})
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


def test_cli_prints_the_contract_line(tmp_path):
    proc = _cli(make_checkout(tmp_path), {**PROGRAM, "BENCH_PLATFORM": "cpu"})
    assert proc.returncode == 0, proc.stderr[-3000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert list(last) == KEYS and last["correct"]
    tail = proc.stderr.strip().splitlines()[-len(last["checks"]):]
    assert [t.split(":")[0] for t in tail] == [
        f"check {name}" for name in last["checks"]]


def test_cli_without_the_program(tmp_path):
    """A directory holding only BENCHMARK.json and the benchmark's files."""
    checkout = make_checkout(tmp_path)
    shutil.rmtree(checkout / "cfg")
    proc = _cli(checkout, {"BENCH_PLATFORM": "cpu", "PYTHONPATH": ""})
    assert proc.returncode != 0
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


def test_reference_refuses_the_tree_before_set_up(monkeypatch):
    """A tree the reference cannot judge (here an HR-scoped rule) fails
    the run in harness prep, before the program is started."""
    cell = sorted(SMALL)[0]
    scoped = {"id": "scoped", "effect": "PERMIT", "target": {"subjects": [
        {"id": "urn:restorecommerce:acs:names:roleScopingEntity",
         "value": "urn:restorecommerce:acs:model:organization.Organization"}]}}
    tree = [{"id": "set", "combining_algorithm": ALG, "policies": [
        {"id": "policy", "combining_algorithm": ALG, "rules": [scoped]}]}]
    monkeypatch.setattr(harness.trees, "build", lambda *_: tree)

    def no_program(*_):
        raise AssertionError("the program was started")

    monkeypatch.setattr(harness, "Serving", no_program)
    with pytest.raises(harness.reference.Unsupported):
        harness.run(cell, BIG_SEED, 1.0, False, allow_cpu=True,
                    scale=SMALL[cell], spec=SPEC)

