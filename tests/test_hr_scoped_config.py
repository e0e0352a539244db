"""The benchmark's HR-scoped configuration (``benchmark/configs/
hr_scoped.json``, cell ``hr_scoped.batch4096``) at its test size on the
CPU: served end to end through the harness (Worker + GrpcServer,
IsAllowedBatch on the native wire path) it is correct, the over-cap
re-encode serves its deep rows, the dense kernel serves (the tree is under
the prefilter's threshold), and its control is not correct; and the
native owner-bitplane packer equals the Python packer on its rows.

Each case runs in a child process from the checkout's root, as the
benchmark's command does, so the harness's modules and JAX settings stay
out of the test session."""

from __future__ import annotations

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "hr_scoped.batch4096"
SEED = 2**31 + 26001

SERVE = """
import json, sys
sys.path.insert(0, "benchmark")
import control, harness
seen = {}
stop = harness.Serving.stop

def spy(self):
    kernel = self.worker.evaluator._kernel
    seen["kernel"] = type(kernel).__name__
    seen["prefilter_active"] = bool(getattr(kernel, "active", False))
    seen["paths"] = dict(self.worker.telemetry.snapshot()["paths"])
    stop(self)

harness.Serving.stop = spy
cell = harness.load_cell(%(cell)r)
scale = {"config": cell.config.get("small", {}), "mix": cell.mix["small"]}
out = harness.run(%(cell)r, %(seed)d, 1.5, True, allow_cpu=True, scale=scale)
small = harness.load_cell(%(cell)r, scale=scale)
checks = control.control_checks(small, %(seed)d)
print(json.dumps({"out": out, "seen": seen, "control": checks}))
"""

OWNER_BITS = """
import json, sys
import numpy as np
import yaml
sys.path.insert(0, "benchmark")
import harness, traffic
from access_control_srv_tpu import native
from access_control_srv_tpu.core import AccessController, populate
from access_control_srv_tpu.ops import encode as pyenc
from access_control_srv_tpu.ops.compile import compile_policies
from access_control_srv_tpu.srv.gen import access_control_pb2 as pb

cell = harness.load_cell(%(cell)r)
cell = harness.load_cell(%(cell)r, scale={"mix": cell.mix["small"]})
wl = harness.build_workload(cell, %(seed)d)
with open(%(tmp)r, "w") as f:
    yaml.safe_dump({"policy_sets": wl.tree}, f)
engine = AccessController()
populate(engine, %(tmp)r)
compiled = compile_policies(engine.policy_sets, engine.urns)
enc = native.NativeBatchEncoder(compiled)
report = {"checked": 0, "overcap": 0, "ceil_eligible": 0}
for rows in wl.pool:
    messages = []
    for row in rows:
        msg = pb.Request()
        traffic.fill_pb(msg, row)
        messages.append(msg.SerializeToString())
    floor = enc.encode_wire(messages)
    over = np.flatnonzero(floor.overcap)
    deep = [messages[i] for i in over]
    batches = [floor]
    if deep:
        batches.append(enc.encode_wire(
            deep, caps=pyenc.retry_caps(floor.overcap[over])))
    for batch in batches:
        raw = {k: v for k, v in batch.arrays.items()
               if not k.startswith("r_own")}
        ref = pyenc.pack_owner_bitplanes(raw, compiled)
        for key in ("r_own_runs", "r_own_bits"):
            np.testing.assert_array_equal(ref[key], batch.arrays[key])
        report["checked"] += batch.B
    report["overcap"] += len(deep)
    if deep:
        report["ceil_eligible"] += int(batches[1].eligible.sum())
print(json.dumps(report))
"""


def _child(script: str) -> dict:
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO}
    proc = subprocess.run([sys.executable, "-c", script], capture_output=True,
                          text=True, timeout=300, cwd=REPO, env=env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_cell_serves_correct_through_the_ceiling_path():
    got = _child(SERVE % {"cell": CELL, "seed": SEED})
    out, seen, ctl = got["out"], got["seen"], got["control"]
    assert out["correct"], out["checks"]
    assert out["checks"]["wrong_decisions"]["value"] == 0
    assert out["checks"]["answers_missing"]["value"] == 0
    assert out["failed"] == 0
    paths = seen["paths"]
    assert paths.get("native-wire", 0) > 0
    assert paths.get("native-wire-ceil", 0) > 0
    assert paths.get("native-wire-overcap", 0) >= paths["native-wire-ceil"]
    # every over-cap row is over the HR-scope pairs alone, so every retry
    # comes from the first pass, and each of them fits the retry caps
    assert paths.get("native-wire-overcap-kept") == paths["native-wire-overcap"]
    assert paths["native-wire-ceil"] == paths["native-wire-overcap"]
    assert paths.get("oracle", 0) == 0
    assert seen["kernel"] == "PrefilteredKernel"
    assert seen["prefilter_active"] is False
    metrics = out["metrics"]
    for name in ("owner_bits_us_per_row.hr", "ceil_encode_us_per_row.hr"):
        assert metrics[name]["value"] > 0, name
    assert 0 < metrics["ceil_share.hr"]["value"] < 50
    # the control: the scope check always passing fabricates PERMITs
    assert ctl["wrong_decisions"]["value"] > 0


def test_native_owner_bits_equal_python_packer(tmp_path):
    got = _child(OWNER_BITS % {"cell": CELL, "seed": SEED,
                               "tmp": str(tmp_path / "tree.yml")})
    assert got["checked"] > 0
    # every row over the floor caps fits the serving path's retry caps
    assert got["overcap"] > 0
    assert got["ceil_eligible"] == got["overcap"]
