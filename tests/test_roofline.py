"""The byte count behind ``acl_program_hbm_roofline.acl``
(``benchmark/roofline.py``): against a hand count at the ACL cell's two
dispatched cap sets, the floor batch and its over-NHR retry; its operand
table against the arrays the native encoder hands the kernel at both; and
its module-event reader on a trace recorded on a v5e."""

from __future__ import annotations

import importlib.util
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
if BENCH not in sys.path:
    sys.path.append(BENCH)
_spec = importlib.util.spec_from_file_location(
    "bench_roofline", os.path.join(BENCH, "roofline.py"))
roofline = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(roofline)

CAPS = ("NR4.NI4.NP8.NSUB8.NACT4.NOP2.NOWN4.NRA8.NHR{nhr}.NROLE4.NACLE4"
        ".NACLI8.NHRR8.OWN1.REL1")
FLOOR = "acl-program@b4096.t512." + CAPS.format(nhr=32)
RETRY = "acl-program@b512.t512." + CAPS.format(nhr=1024)


def hand_count(rows: int, nhr: int) -> int:
    """One acs_dense_acl call by hand at the floor caps but NHR."""
    int32 = (4 + 2 * nhr + 32 + 1      # r_acl_ent, _hr, _inst, _short
             + 8 + 8                   # r_act_ids/_vals, r_ent_e/_vals
             + 2 * nhr + 8             # r_hr, r_hr_roles
             + 8 + 32                  # r_inst_id/_run, _owner_ent/_inst
             + 3                       # r_n_entity_attrs, r_n_ra, r_subject_id
             + 16 + 2                  # r_op_owner_ent/_inst, r_op_vals
             + 2 + 32                  # r_own_bits/_runs, 4 r_prop_* of 8
             + 16 + 24 + 2             # r_ra2, r_ra3, r_rel_bits/_runs
             + 4 + 16)                 # r_roles, r_sub_ids/_vals
    bools = 3 + 4 + 12 + 4             # flags, r_ent_valid, r_inst_*, r_op_*
    results = 4 + 1 + 4                # decision, cacheable, status
    per_row = 4 * int32 + bools + results
    # 8 HR roles x 8 instances = 64 scan steps, each reading and writing
    # validated [B, 512, 4, 8] + broken [B, 512, 4] (bool) and last_ev
    # [B, 512, 4] (int32)
    carries = 64 * 2 * rows * 512 * 4 * (8 + 1 + 4)
    return rows * per_row + carries


@pytest.mark.parametrize("key,rows,nhr", [(FLOOR, 4096, 32),
                                          (RETRY, 512, 1024)])
def test_bytes_equal_the_hand_count(key, rows, nhr):
    shape = roofline.parse_shape(key)
    assert shape["b"] == rows and shape["NHR"] == nhr and shape["t"] == 512
    assert roofline.acl_program_bytes(shape) == hand_count(rows, nhr)


def test_floor_and_retry_counts():
    assert roofline.acl_program_bytes(roofline.parse_shape(FLOOR)) \
        == 13_964_443_648
    assert roofline.acl_program_bytes(roofline.parse_shape(RETRY)) \
        == 1_753_681_920


ENCODE = """
import json, sys
import numpy as np
sys.path.insert(0, "benchmark")
import harness, roofline, traffic
import yaml
from access_control_srv_tpu import native
from access_control_srv_tpu.core import AccessController, populate
from access_control_srv_tpu.ops.compile import compile_policies
from access_control_srv_tpu.ops.kernel import dispatch_shape_key
from access_control_srv_tpu.srv.gen import access_control_pb2 as pb

cell = harness.load_cell("hr_acl.batch4096", scale={"mix": {"batch": 256}})
wl = harness.build_workload(cell, 2**31 + 30001)
with open(%(tmp)r, "w") as f:
    yaml.safe_dump({"policy_sets": wl.tree}, f)
engine = AccessController()
populate(engine, %(tmp)r)
compiled = compile_policies(engine.policy_sets, engine.urns)
enc = native.NativeBatchEncoder(compiled)
messages = []
for row in wl.pool[0]:
    msg = pb.Request()
    traffic.fill_pb(msg, row)
    messages.append(msg.SerializeToString())
first = enc.encode_wire(messages)
out = []
for batch in (first, enc.retry_from_first_pass(first)):
    key = dispatch_shape_key("acl-program", batch, 512)
    names = sorted(batch.arrays)
    bytes_a_row = sum(a.nbytes for a in batch.arrays.values()) / batch.B
    out.append({"key": key, "names": names, "bytes_a_row": bytes_a_row,
                "counted": roofline.operand_bytes_a_row(
                    roofline.parse_shape(key))})
print(json.dumps(out))
"""


def test_operand_table_matches_the_encoded_arrays(tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO}
    proc = subprocess.run(
        [sys.executable, "-c", ENCODE % {"tmp": str(tmp_path / "t.yml")}],
        capture_output=True, text=True, timeout=300, cwd=REPO, env=env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    floor, retry = json.loads(proc.stdout.strip().splitlines()[-1])
    assert ".NHR32." in floor["key"] and ".NHR1024." in retry["key"]
    for batch in (floor, retry):
        assert batch["names"] == sorted(roofline.OPERANDS)
        assert batch["bytes_a_row"] == batch["counted"]


def test_module_time_on_a_recorded_v5e_trace():
    """Five 1024x1024 matmuls (benchmark/tests/data), each one
    ``jit__lambda`` module event of ~11.85 us; the first ends on the
    device's clock before ``bench.window`` opens, so four count."""
    data = os.path.join(BENCH, "tests", "data")
    calls, seconds = roofline.module_time(data, "_lambda")
    assert calls == 4
    assert seconds == pytest.approx(4 * 11.85e-6, rel=0.01)
    assert roofline.module_time(data, "acs_dense_acl") is None
