"""Batched whatIsAllowed differential: the device-assisted reverse query
(ops/reverse.py) must produce ReverseQuery trees AND obligations
bit-identical to the scalar oracle — including obligations accumulated
from target-match calls whose final verdict is False (the reference's
side-effecting scan, accessController.ts:592-640)."""

import copy

import pytest

from access_control_srv_tpu.core import populate
from access_control_srv_tpu.ops import (
    ReverseQueryKernel,
    compile_policies,
    encode_requests,
    what_is_allowed_batch,
)

from .test_kernel_differential import grid_requests
from .utils import fixture, make_engine


def rq_shape(rq):
    """Comparable projection of a ReverseQuery (ids, structure, masks)."""
    return {
        "sets": [
            {
                "id": ps.id,
                "ca": ps.combining_algorithm,
                "policies": [
                    {
                        "id": p.id,
                        "effect": p.effect,
                        "cacheable": p.evaluation_cacheable,
                        "has_rules": p.has_rules,
                        "rules": [
                            (r.id, r.effect, r.condition,
                             r.evaluation_cacheable)
                            for r in p.rules
                        ],
                    }
                    for p in ps.policies
                ],
            }
            for ps in rq.policy_sets
        ],
        "obligations": [
            (o.id, o.value,
             [(n.id, n.value) for n in (o.attributes or [])])
            for o in rq.obligations
        ],
        "status": (rq.operation_status.code, rq.operation_status.message),
    }


@pytest.mark.parametrize(
    "fixture_name",
    [
        "basic_policies.yml",
        "policy_targets.yml",
        "policy_set_targets.yml",
        "role_scopes.yml",
        "conditions.yml",
        "acl_policies.yml",
        "props_single.yml",
        "props_rules_noprop.yml",
        "props_multi_rules.yml",
        "props_multi_rules_entities.yml",
        "ops_multi.yml",
    ],
)
def test_reverse_differential(fixture_name):
    engine = make_engine(fixture_name)
    compiled = compile_policies(engine.policy_sets, engine.urns)
    assert compiled.supported
    kernel = ReverseQueryKernel(compiled, engine.policy_sets)

    requests = grid_requests(n=100, seed=211)
    oracle_out = [
        engine.what_is_allowed(copy.deepcopy(r)) for r in requests
    ]
    batch = encode_requests(
        [copy.deepcopy(r) for r in requests], compiled
    )
    kernel_out = what_is_allowed_batch(
        engine, compiled, kernel,
        [copy.deepcopy(r) for r in requests], batch,
    )
    n_device = 0
    for b in range(len(requests)):
        assert rq_shape(kernel_out[b]) == rq_shape(oracle_out[b]), b
        if batch.eligible[b]:
            n_device += 1
    assert n_device > 60  # the device path must actually be exercised


def test_reverse_multi_set_tree():
    engine = make_engine()
    for name in ["basic_policies.yml", "policy_targets.yml",
                 "props_multi_rules.yml"]:
        populate(engine, fixture(name))
    compiled = compile_policies(engine.policy_sets, engine.urns)
    assert compiled.supported
    kernel = ReverseQueryKernel(compiled, engine.policy_sets)
    requests = grid_requests(n=80, seed=97)
    oracle_out = [engine.what_is_allowed(copy.deepcopy(r)) for r in requests]
    kernel_out = what_is_allowed_batch(
        engine, compiled, kernel, [copy.deepcopy(r) for r in requests]
    )
    for b in range(len(requests)):
        assert rq_shape(kernel_out[b]) == rq_shape(oracle_out[b]), b


def test_evaluator_wia_batch_and_hot_mutation(monkeypatch):
    """HybridEvaluator.what_is_allowed_batch serves device-assisted and
    stays consistent across a hot tree mutation (version-pinned snapshot;
    stale compiles fall back to the oracle).  The adaptive dispatch is
    pinned to the kernel path (fixture trees sit under REVERSE_MIN_RULES)."""
    from access_control_srv_tpu.core.loader import load_policy_sets_from_file
    from access_control_srv_tpu.ops import reverse as reverse_mod
    from access_control_srv_tpu.srv.evaluator import HybridEvaluator

    monkeypatch.setattr(reverse_mod, "REVERSE_MIN_RULES", 0)
    engine = make_engine("policy_targets.yml")
    ev = HybridEvaluator(engine)
    requests = grid_requests(n=30, seed=311)

    oracle_out = [engine.what_is_allowed(copy.deepcopy(r)) for r in requests]
    batch_out = ev.what_is_allowed_batch([copy.deepcopy(r) for r in requests])
    for b in range(len(requests)):
        assert rq_shape(batch_out[b]) == rq_shape(oracle_out[b]), b
    assert ev._rq_kernel is not None  # lazily built on first use

    # hot mutation: add a second tree, refresh, answers must track it
    for ps in load_policy_sets_from_file(fixture("basic_policies.yml")):
        engine.update_policy_set(ps)
    ev.refresh(wait=True)
    oracle_out2 = [engine.what_is_allowed(copy.deepcopy(r)) for r in requests]
    batch_out2 = ev.what_is_allowed_batch([copy.deepcopy(r) for r in requests])
    for b in range(len(requests)):
        assert rq_shape(batch_out2[b]) == rq_shape(oracle_out2[b]), b


def test_adaptive_wia_dispatch():
    """Small trees serve the reverse query from the scalar walk (the
    device round-trip is taken to lose below REVERSE_MIN_RULES; no
    benchmark cell measures it); the threshold routes to the kernel
    above it."""
    from access_control_srv_tpu.ops.reverse import REVERSE_MIN_RULES
    from access_control_srv_tpu.srv.evaluator import HybridEvaluator
    from access_control_srv_tpu.srv.telemetry import Telemetry

    engine = make_engine("policy_targets.yml")
    telemetry = Telemetry()
    ev = HybridEvaluator(engine, telemetry=telemetry)
    compiled = ev._compiled
    assert compiled is not None and compiled.n_rules < REVERSE_MIN_RULES

    requests = grid_requests(n=12, seed=5)
    oracle_out = [engine.what_is_allowed(copy.deepcopy(r)) for r in requests]
    out = ev.what_is_allowed_batch([copy.deepcopy(r) for r in requests])
    assert telemetry.paths.get("oracle-wia") == len(requests)
    assert telemetry.paths.get("kernel-wia") == 0
    assert ev._rq_kernel is None  # never built below the threshold
    for b in range(len(requests)):
        assert rq_shape(out[b]) == rq_shape(oracle_out[b]), b
