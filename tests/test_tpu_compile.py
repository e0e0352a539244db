"""The main path's device programs, compiled for a described TPU v5e.

No chip is attached: JAX compiles for a ``v5e:2x2`` topology it is given
a description of (on-chip-measurement guide, section 2), so HBM
overflows, tiling refusals and constant-baking blowups show up here, at no chip time, before any chip run.

Each kernel is driven through its own ``evaluate`` on the CPU with
``jax.jit`` stood in for by ``ChipCompiler``: every program the kernel
calls is lowered with its arguments' shapes for the described chip and
compiled there; the call returns zeros of the output shapes, so nothing
runs and nothing is compared.  The topology is described inside a
fixture, never at import (see the guide for why).

Every program compiled here is also scanned for 64-bit element types
(``wide_dtypes``): the chip has no native 64-bit arithmetic, so an
``s64`` or ``f64`` that reaches a device program is a defect even when
it compiles.
"""

from __future__ import annotations

import os
import re

import numpy as np
import pytest

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a chip compile written to the persistent cache cannot be read back
    # without a chip: keep the cache off around these compiles
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def pod_mesh(topo):
    return Mesh(np.array(topo.devices[:4]).reshape(1, 4), ("data", "model"))


WIDE_DTYPE = re.compile(r"\b(f64|s64|u64|c128)\[")


def wide_dtypes(lowered) -> list:
    """The 64-bit element types in a lowered program, read from its HLO
    text (StableHLO spells them ``i64``/``f64`` and also uses ``i64`` for
    attributes, so the HLO dialect is the one to scan)."""
    return sorted(set(WIDE_DTYPE.findall(lowered.as_text(dialect="hlo"))))


class ChipCompiler:
    """Stand-in for ``jax.jit``: calls compile for the described chip.
    Arguments that are not already shape structs take ``sharding``."""

    def __init__(self, monkeypatch, sharding):
        self.sharding = sharding
        self.lowered = []
        self.compiled = []
        real_jit = jax.jit

        def jit(fn, **kwargs):
            real = real_jit(fn, **kwargs)
            outs = {}  # one compile per argument signature, like jit

            def call(*args):
                specs = jax.tree_util.tree_map(self._spec, args)
                key = repr(specs)
                if key not in outs:
                    lowered = real.lower(*specs)
                    self.lowered.append(lowered)
                    self.compiled.append(lowered.compile())
                    outs[key] = lowered.out_info
                return jax.tree_util.tree_map(
                    lambda s: np.zeros(s.shape, s.dtype), outs[key]
                )

            return call

        monkeypatch.setattr(jax, "jit", jit)

    def _spec(self, x):
        if isinstance(x, jax.ShapeDtypeStruct):
            return x
        return jax.ShapeDtypeStruct(
            np.shape(x), np.result_type(x), sharding=self.sharding
        )

    def memory(self) -> list:
        return [c.memory_analysis() for c in self.compiled]

    def wide_dtypes(self) -> list:
        """(HLO module, 64-bit types) for each compiled program that
        holds any; [] when every program is 32-bit clean."""
        out = []
        for lowered in self.lowered:
            found = wide_dtypes(lowered)
            if found:
                out.append((lowered.as_text(dialect="hlo").split(",")[0],
                            found))
        return out

    def dot_generals(self, i: int) -> int:
        """The number of ``dot_general`` ops in the i-th program."""
        return self.lowered[i].as_text().count("stablehlo.dot_general")


# ------------------------------------------------------------ workloads

@pytest.fixture(scope="module")
def seed_engine():
    import synth

    return synth.seed_engine()


@pytest.fixture(scope="module")
def scoped_100k():
    """The 100k-rule role-scoped tree (``synth.stress_doc(100_000,
    scoped=True)``), bucketed like the serving evaluator compiles it."""
    from access_control_srv_tpu.core import AccessController
    from access_control_srv_tpu.core.loader import load_policy_sets
    from access_control_srv_tpu.ops import delta
    from synth import stress_doc

    engine = AccessController()
    for ps in load_policy_sets(stress_doc(100_000, scoped=True)[0]):
        engine.update_policy_set(ps)
    compiled, _, _ = delta.full_bucketed_compile(
        engine.policy_sets, engine.urns, version=1
    )
    assert compiled.supported and compiled.n_rules == 100_000
    return compiled


def _hr_requests(n: int):
    """``n`` HR-scoped requests shaped like chip_smoke's wire traffic."""
    from access_control_srv_tpu.srv.service import coerce_request
    from access_control_srv_tpu.srv.transport_grpc import request_from_pb
    from synth import serving_batch_msg

    msg = serving_batch_msg(n, np.random.default_rng(21), hr=True)
    return [coerce_request(request_from_pb(m)) for m in msg.requests]


def _seed_requests(n: int):
    from access_control_srv_tpu.srv.service import coerce_request
    from access_control_srv_tpu.srv.transport_grpc import request_from_pb
    from synth import serving_batch_msg

    msg = serving_batch_msg(n, np.random.default_rng(21))
    return [coerce_request(request_from_pb(m)) for m in msg.requests]


# ---------------------------------------------------------------- tests

@pytest.mark.parametrize("dynamic", [False, True],
                         ids=["baked-constants", "table-arguments"])
def test_dense_seed_program(one_chip, seed_engine, monkeypatch, dynamic):
    """The dense decision program on the seed tree at batch 4096: baked
    as XLA constants (the tree is under 1 MiB) and as arguments (the
    serving evaluator's hot-update mode)."""
    from access_control_srv_tpu.ops import compile_policies, encode_requests
    from access_control_srv_tpu.ops.kernel import DecisionKernel

    compiled = compile_policies(seed_engine.policy_sets, seed_engine.urns)
    batch = encode_requests(_seed_requests(4096), compiled)
    chip = ChipCompiler(monkeypatch, one_chip)
    DecisionKernel(compiled, dynamic_policies=dynamic).evaluate(batch)
    assert len(chip.compiled) == 1
    assert chip.memory()[0].argument_size_in_bytes > 0
    assert chip.wide_dtypes() == []


def test_sig_program_100k_scoped(one_chip, scoped_100k, monkeypatch):
    """The prefiltered signature programs (planes + per-row sig runner)
    on the 100k HR-scoped tree at batch 4096."""
    from access_control_srv_tpu.ops import encode_requests, prefilter

    batch = encode_requests(_hr_requests(4096), scoped_100k)
    assert batch.eligible.all()
    chip = ChipCompiler(monkeypatch, one_chip)
    kernel = prefilter.PrefilteredKernel(scoped_100k, dynamic_policies=True)
    assert kernel.active and kernel.needs_hr
    kernel.evaluate(batch)
    assert len(chip.compiled) == 2  # signature planes, then rows
    assert chip.wide_dtypes() == []
    # the owner checks read host-packed bitplanes: the row program holds
    # no matmul (the regex-entity contraction is the dense program's)
    assert chip.dot_generals(1) == 0


def test_reverse_program_1000_rules(one_chip, monkeypatch):
    """The whatIsAllowed reverse-query program on a 1000-rule tree."""
    from access_control_srv_tpu.ops import (
        ReverseQueryKernel,
        compile_policies,
        encode_requests,
        what_is_allowed_batch,
    )
    from synth import stress_engine

    engine, _ = stress_engine(1000)
    compiled = compile_policies(engine.policy_sets, engine.urns)
    requests = _hr_requests(64)
    batch = encode_requests(requests, compiled, skip_conditions=True,
                            skip_owner_bits=True, skip_relation_bits=True)
    chip = ChipCompiler(monkeypatch, one_chip)
    kernel = ReverseQueryKernel(compiled, engine.policy_sets)
    what_is_allowed_batch(engine, compiled, kernel, requests, batch)
    assert len(chip.compiled) == 1
    assert chip.wide_dtypes() == []


def test_pod_shard_program_4_chips(pod_mesh, scoped_100k, monkeypatch):
    """The pod-sharded shard_map program over 4 described chips, the
    100k tree's set axis split 4 ways (``parallel:pod_shards: 4``)."""
    from access_control_srv_tpu.ops import encode_requests
    from access_control_srv_tpu.parallel.pod_shard import PodShardedKernel

    real_put = jax.device_put

    def put(x, sharding=None, **kwargs):
        # a described chip holds no arrays: placement yields the shape
        if isinstance(sharding, NamedSharding) and sharding.mesh is pod_mesh:
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)
        return real_put(x, sharding, **kwargs)

    monkeypatch.setattr(jax, "device_put", put)
    chip = ChipCompiler(monkeypatch, NamedSharding(pod_mesh, P()))
    batch = encode_requests(_hr_requests(4096), scoped_100k)
    PodShardedKernel(scoped_100k, pod_mesh).evaluate(batch)
    assert len(chip.compiled) == 1
    text = chip.compiled[0].as_text()
    assert "all-reduce" in text or "all-gather" in text
    assert chip.wide_dtypes() == []


def test_dense_acl_hr_program(one_chip, monkeypatch):
    """The dense program's ACL variant with the HR pass on: the role-scope
    and ACL fixtures, with ACL-bearing rows in the batch, so every stage
    of the matcher is lowered."""
    from access_control_srv_tpu.core import populate
    from access_control_srv_tpu.ops import compile_policies, encode_requests
    from access_control_srv_tpu.ops.kernel import DecisionKernel

    from .test_kernel_differential import grid_requests
    from .utils import fixture, make_engine

    engine = make_engine("role_scopes.yml")
    populate(engine, fixture("acl_policies.yml"))
    compiled = compile_policies(engine.policy_sets, engine.urns)
    batch = encode_requests(grid_requests(n=256, seed=5), compiled)
    assert (np.asarray(batch.arrays["r_acl_ent"]) >= 0).any()
    chip = ChipCompiler(monkeypatch, one_chip)
    DecisionKernel(compiled, dynamic_policies=True).evaluate(batch)
    assert len(chip.compiled) == 1
    assert "acs_dense_acl" in chip.lowered[0].as_text(dialect="hlo")
    assert chip.wide_dtypes() == []


def test_sig_program_2000_unscoped(one_chip, monkeypatch):
    """The prefiltered signature programs without the HR pass, on a
    2000-rule tree that is not role-scoped."""
    from access_control_srv_tpu.ops import delta, encode_requests, prefilter
    from access_control_srv_tpu.srv.service import coerce_request
    from access_control_srv_tpu.srv.transport_grpc import request_from_pb
    from synth import serving_batch_msg, stress_engine

    engine, _ = stress_engine(2000)
    compiled, _, _ = delta.full_bucketed_compile(
        engine.policy_sets, engine.urns, version=1
    )
    msg = serving_batch_msg(1024, np.random.default_rng(21), wide=True)
    batch = encode_requests(
        [coerce_request(request_from_pb(m)) for m in msg.requests], compiled
    )
    chip = ChipCompiler(monkeypatch, one_chip)
    kernel = prefilter.PrefilteredKernel(compiled, dynamic_policies=True)
    assert kernel.active and not kernel.needs_hr
    kernel.evaluate(batch)
    assert len(chip.compiled) == 2  # signature planes, then rows
    assert chip.wide_dtypes() == []
    assert chip.dot_generals(1) == 0


def test_dense_relation_program(one_chip, monkeypatch):
    """The dense program over a relation-bearing tree.  The Zanzibar
    closure is folded on the host into bitplanes, so the relation pass
    adds no matmul: the program's one ``dot_general`` is the matcher's
    regex-entity lookup (ops/kernel._regex_entity_lookup)."""
    from access_control_srv_tpu.ops import compile_policies, encode_requests
    from access_control_srv_tpu.ops.kernel import DecisionKernel
    from access_control_srv_tpu.ops.relation import relation_bits_needed
    from access_control_srv_tpu.srv.relations import RelationTupleStore

    from .utils import URNS, build_request, make_engine

    doc = "urn:restorecommerce:acs:model:document.Document"
    engine = make_engine("relation_policies.yml")
    compiled = compile_policies(engine.policy_sets, engine.urns)
    assert relation_bits_needed(compiled)
    store = RelationTupleStore()
    store.create([(doc, "doc1", "viewer", "alice")])
    requests = [
        build_request(subject_id=s, resource_type=doc, resource_id=r,
                      action_type=URNS["read"])
        for s in ("alice", "bob") for r in ("doc1", "doc2")
    ]
    batch = encode_requests(requests, compiled,
                            relation_tables=store.tables_for(compiled))
    chip = ChipCompiler(monkeypatch, one_chip)
    DecisionKernel(compiled, dynamic_policies=True).evaluate(batch)
    assert len(chip.compiled) == 1
    assert chip.wide_dtypes() == []
    assert chip.dot_generals(0) == 1


def test_wide_dtype_scan_flags_64_bit_programs():
    """The scan the tests above rely on finds 64-bit types when a
    program has them, and none in a 32-bit program."""
    x32 = np.zeros(3, np.int32)
    assert wide_dtypes(jax.jit(lambda x: x + 1).lower(x32)) == []
    with jax.enable_x64(True):
        lowered = jax.jit(lambda x, y: (x + 1, y * 2.0)).lower(
            np.zeros(3, np.int64), np.zeros(3, np.float64)
        )
    assert wide_dtypes(lowered) == ["f64", "s64"]
