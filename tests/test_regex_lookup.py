"""The dense matcher's regex-entity lookup (``ops/kernel.py``,
``_regex_entity_lookup``): the per-batch ``[T, K, E]`` rows of
``rgx_set`` / ``pfx_neq`` and a one-hot contraction over the batch's
entity columns.

It must give, bit for bit, what indexing both ``[W, E]`` tables at
(target row's entity-vocab row, request run's entity column) gives: the
double-index gather it replaced, written here in numpy, with JAX's
clamping of out-of-range indices and the same slot/run masks.  It runs
under ``vmap`` as the dense kernel runs it: request columns batched, the
tables and target rows not.

The structure test lowers ``DecisionKernel``'s ``acs_dense`` for the
benchmark's HR-scoped tree at its test size and checks that no gather
reading those tables carries the batch axis in its indices: the per-row
gather was most of the device time on that deployment.
"""

from __future__ import annotations

import itertools
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

import jax

from access_control_srv_tpu.ops.kernel import _regex_entity_lookup

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROWS = 8   # the vmapped batch
W = 5      # entity-vocab rows of the tables


def _gather_reference(table, t_ent_w, r_ent_e, ent_valid):
    """``table[w, e]`` per (target slot, run) as a JAX gather reads it:
    both indices clamped into the table; then the kernel's masks."""
    w = np.clip(t_ent_w, 0, table.shape[0] - 1)
    e = np.clip(r_ent_e, 0, table.shape[1] - 1)
    hit = table[w[:, :, None], e[None, None, :]]
    return hit & (t_ent_w[:, :, None] >= 0) & ent_valid[None, None, :]


@pytest.mark.parametrize(
    "E,T,K,NR", list(itertools.product((1, 32, 1024), (1, 224), (1, 2),
                                       (4, 16))))
def test_lookup_equals_double_index_gather(E, T, K, NR):
    g = np.random.default_rng([E, T, K, NR])
    rgx_set = g.random((W, E)) < 0.5
    pfx_neq = g.random((W, E)) < 0.5
    t_ent_w = g.integers(-1, W, (T, K)).astype(np.int32)
    t_ent_w.flat[0] = W - 1
    if t_ent_w.size > 1:
        t_ent_w.flat[-1] = -1            # an unused slot
    r_ent_e = g.integers(-1, E, (ROWS, NR)).astype(np.int32)
    r_ent_e[0, 0] = E - 1
    r_ent_e[1, -1] = -1                  # a padded run
    r_ent_e[2, 0] = E + 3                # clamped, as the gather clamps
    ent_valid = g.random((ROWS, NR)) < 0.8
    ent_valid[:3] = True

    def one(w, rs, pn, e, v):
        return _regex_entity_lookup(
            {"t_ent_w": w}, {"rgx_set": rs, "pfx_neq": pn, "r_ent_e": e}, v
        )

    rgx_hit, pfx_hit = jax.jit(
        jax.vmap(one, in_axes=(None, None, None, 0, 0))
    )(t_ent_w, rgx_set, pfx_neq, r_ent_e, ent_valid)
    assert rgx_hit.shape == pfx_hit.shape == (ROWS, T, K, NR)
    for i in range(ROWS):
        np.testing.assert_array_equal(
            np.asarray(rgx_hit[i]),
            _gather_reference(rgx_set, t_ent_w, r_ent_e[i], ent_valid[i]))
        np.testing.assert_array_equal(
            np.asarray(pfx_hit[i]),
            _gather_reference(pfx_neq, t_ent_w, r_ent_e[i], ent_valid[i]))


LOWER = """
import json, sys
sys.path.insert(0, "benchmark")
import numpy as np
import yaml
import jax
import harness, traffic
from access_control_srv_tpu import native
from access_control_srv_tpu.core import AccessController, populate
from access_control_srv_tpu.ops import delta
from access_control_srv_tpu.ops.kernel import DecisionKernel, lead_padding
from access_control_srv_tpu.srv.gen import access_control_pb2 as pb

cell = harness.load_cell("hr_scoped.batch4096")
cell = harness.load_cell("hr_scoped.batch4096", scale={
    "config": cell.config.get("small", {}), "mix": cell.mix["small"]})
wl = harness.build_workload(cell, %(seed)d)
with open(%(tmp)r, "w") as f:
    yaml.safe_dump({"policy_sets": wl.tree}, f)
engine = AccessController()
populate(engine, %(tmp)r)
compiled, _, _ = delta.full_bucketed_compile(engine.policy_sets, engine.urns,
                                             version=1)
messages = []
for row in wl.pool[0][:%(rows)d]:
    msg = pb.Request()
    traffic.fill_pb(msg, row)
    messages.append(msg.SerializeToString())
batch = native.NativeBatchEncoder(compiled).encode_wire(messages)
_, bucket, e_bucket, _ = lead_padding(batch)
texts = []
real_jit = jax.jit

def jit(fn, **kwargs):
    real = real_jit(fn, **kwargs)

    def call(*args):
        lowered = real.lower(*args)
        texts.append((fn.__name__, lowered.as_text()))
        return jax.tree_util.tree_map(
            lambda s: np.zeros(s.shape, s.dtype), lowered.out_info)
    return call

jax.jit = jit
DecisionKernel(compiled, dynamic_policies=True).evaluate(batch)
print(json.dumps({"bucket": bucket, "W": int(batch.rgx_set.shape[0]),
                  "E": e_bucket, "T": int(compiled.T), "programs": texts}))
"""

GATHER = re.compile(
    r'"stablehlo\.gather"\([^)]*\).* : \(tensor<([^>]*)>, tensor<([^>]*)>\)'
)


def _dims(tensor_type: str):
    *dims, dtype = tensor_type.split("x")
    return [int(d) for d in dims], dtype


def test_dense_program_has_no_per_row_table_gather(tmp_path):
    """``acs_dense`` on the HR-scoped test tree: every gather that reads
    ``rgx_set`` / ``pfx_neq`` (an ``i1`` operand ending in their
    ``[W, E]``) indexes without the batch bucket as its leading axis."""
    script = LOWER % {"seed": 2**31 + 27001, "rows": 100,
                      "tmp": str(tmp_path / "tree.yml")}
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO}
    proc = subprocess.run([sys.executable, "-c", script], capture_output=True,
                          text=True, timeout=300, cwd=REPO, env=env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    bucket, table = got["bucket"], [got["W"], got["E"]]
    assert bucket not in (got["T"], got["W"], got["E"])
    [(name, text)] = got["programs"]
    assert name == "acs_dense"
    table_gathers = []
    for line in text.splitlines():
        m = GATHER.search(line)
        if m is None:
            continue
        operand, operand_type = _dims(m.group(1))
        index, _ = _dims(m.group(2))
        if operand_type == "i1" and operand[-2:] == table:
            table_gathers.append(index)
    assert table_gathers, "no gather reads the regex-entity tables"
    for index in table_gathers:
        assert index[0] != bucket, index
