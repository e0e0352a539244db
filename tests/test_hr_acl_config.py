"""The benchmark's ACL-shared configuration (``benchmark/configs/
hr_acl.json``, cell ``hr_acl.batch4096``) at its test size on the CPU:
served end to end through the harness (Worker + GrpcServer,
IsAllowedBatch on the native wire path, the dense kernel's
``acs_dense_acl`` program, the over-NHR retry) it equals its plain
reference on every row of every call, and the path counters say how many
served rows carried ACL pairs and which dispatches ran the ACL program;
a batch of deep rows alone sends its ACL pairs through the retry.

Each case runs in a child process from the checkout's root, as the
benchmark's command does, so the harness's modules and JAX settings stay
out of the test session."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "hr_acl.batch4096"
SEEDS = [2**31 + 30001, 2**31 + 30002, 2**31 + 30003]

COMMON = """
import json, os, sys
sys.path.insert(0, "benchmark")
import harness, traffic
cell = harness.load_cell(%(cell)r)
scale = {"config": cell.config.get("small", {}), "mix": cell.mix["small"]}
small = harness.load_cell(%(cell)r, scale=scale)
refs = harness.reference_module(small.config)


def carries_acl(row):
    req = refs.Row(row["target"], row["context"])
    res = req.ctx_resource(dict(req.resources)[refs.U["resourceID"]])
    return bool(((res or {}).get("meta") or {}).get("acls"))
"""

SERVE = COMMON + """
seen = {}
stop, loop = harness.Serving.stop, harness.closed_loop


def spy(self):
    kernel = self.worker.evaluator._kernel
    snap = self.worker.telemetry.snapshot()
    seen["kernel"] = type(kernel).__name__
    seen["prefilter_active"] = bool(getattr(kernel, "active", False))
    seen["paths"] = dict(snap["paths"])
    seen["compiles"] = dict(snap["xla_compiles"]["programs"])
    stop(self)


def spy_loop(*args, **kwargs):
    t0, t_end, calls = loop(*args, **kwargs)
    seen["pools"] = [c.pool for c in calls if c.decisions is not None]
    return t0, t_end, calls


harness.Serving.stop, harness.closed_loop = spy, spy_loop
out = harness.run(%(cell)r, %(seed)d, 1.5, True, allow_cpu=True, scale=scale)
wl = harness.build_workload(small, %(seed)d)
acl_rows = [sum(map(carries_acl, rows)) for rows in wl.pool]
print(json.dumps({"out": out, "seen": seen, "acl_rows": acl_rows}))
"""

RETRY = COMMON + """
import copy


def hr_pairs(row):
    out = set()

    def walk(nodes, role):
        for node in nodes:
            key = node.get("role", role)
            out.add((key, node["id"]))
            walk(node.get("children") or [], key)

    walk(row["context"]["subject"]["hierarchical_scopes"], None)
    return len(out)


wl = harness.build_workload(small, %(seed)d)
rows = [r for batch in wl.pool for r in batch
        if carries_acl(r) and hr_pairs(r) > 32][:64]
if %(wide)r:
    # one more row, over the ACL instance cap: every over-cap row of the
    # batch then takes the re-encode at the full ceiling
    wide = copy.deepcopy(rows[0])
    acl = wide["context"]["resources"][0]["meta"]["acls"][0]
    acl["attributes"] = [dict(acl["attributes"][0], value=f"org-wide-{i}")
                         for i in range(9)]
    rows.append(wide)
overlay = {"seed_data": harness.trees.seed_data(
    small.config["tree"], wl.tree,
    os.path.join(harness.TREE_DIR, "test-hr-acl-retry"))}
serving = harness.Serving(overlay)
try:
    serving.wait_kernel(wl.n_rules)
    payload = serving.batch_call(traffic.batch_bytes(rows), timeout=600)
    decisions, statuses = harness.parse_batch(payload, len(rows))
    paths = dict(serving.worker.telemetry.snapshot()["paths"])
finally:
    serving.stop()
expect = harness.expected_codes(refs, refs.Reference(wl.tree), rows)
print(json.dumps({"n": len(rows), "wrong": int((decisions != expect).sum()),
                  "denied_or_indeterminate": int((expect != 0).sum()),
                  "statuses": sorted(set(statuses.tolist())),
                  "paths": paths}))
"""


def _child(script: str) -> dict:
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO}
    proc = subprocess.run([sys.executable, "-c", script], capture_output=True,
                          text=True, timeout=300, cwd=REPO, env=env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _acl_shapes(paths: dict) -> dict:
    return {k: n for k, n in paths.items() if k.startswith("acl-program@")}


@pytest.mark.parametrize("seed", SEEDS)
def test_cell_serves_every_row_as_the_reference(seed):
    got = _child(SERVE % {"cell": CELL, "seed": seed})
    out, seen, acl_rows = got["out"], got["seen"], got["acl_rows"]
    assert out["correct"], out["checks"]
    assert out["checks"]["wrong_decisions"]["value"] == 0
    assert out["checks"]["answers_missing"]["value"] == 0
    assert out["checks"]["answers_compared"]["value"] > 0
    assert out["failed"] == 0
    paths = seen["paths"]
    assert paths.get("oracle", 0) == 0
    assert seen["kernel"] == "PrefilteredKernel"
    assert seen["prefilter_active"] is False
    assert "jit(acs_dense_acl)" in seen["compiles"]
    # the warm-up sends each pool batch once, the window the ones it drove
    expect = sum(acl_rows) + sum(acl_rows[p] for p in seen["pools"])
    assert paths["native-wire-acl"] == expect
    # every dispatch carried ACL pairs: the floor batch of each call and
    # its over-NHR retry, the retry at the ceiling's HR-scope pairs
    shapes = _acl_shapes(paths)
    assert sum(shapes.values()) == paths["acl-program"]
    assert any(".NHR1024." in k for k in shapes)
    assert any(".NHR32." in k for k in shapes)
    assert paths["native-wire-ceil"] == paths["native-wire-overcap"] > 0
    assert 80 < out["metrics"]["acl_share.acl"]["value"] < 100
    assert 0 < out["metrics"]["ceil_share.hr"]["value"] < 50


@pytest.mark.parametrize("wide", [False, True], ids=["kept", "reencode"])
def test_retry_batch_carries_acl_pairs(wide):
    """A batch of deep rows alone: the floor encode flags every row over
    the HR-scope pair cap, and the retry serves each of them with its ACL
    pairs on ``acs_dense_acl``, as the reference decides: assembled from
    the first pass, or, with a row over the ACL instance cap beside them,
    re-encoded at the full ceiling, whose ACL pairs only the retry holds."""
    got = _child(RETRY % {"cell": CELL, "seed": SEEDS[0], "wide": wide})
    n, paths = got["n"], got["paths"]
    assert n >= 16
    assert got["wrong"] == 0
    assert got["denied_or_indeterminate"] > 0
    assert got["statuses"] == [200]
    assert paths["native-wire-overcap"] == paths["native-wire-ceil"] == n
    assert paths.get("native-wire-overcap-kept", 0) == (0 if wide else n)
    assert paths["native-wire-acl"] == paths["native-wire"] == n
    shapes = _acl_shapes(paths)
    assert paths["acl-program"] == 2
    retry = ".NACLI64." if wide else ".NHR1024."
    assert sum(k for key, k in shapes.items() if retry in key) == 1
