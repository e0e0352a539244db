"""Explain-mode + shadow-evaluation differential suite.

Explain half: every kernel variant's packed provenance output, decoded
through srv/explain.ExplainDecoder, must match the host oracle's
``EffectEvaluation.source`` bit-for-bit — deciding rule id on rule-decided
rows, policy id on no-rules-policy rows, None on no-contribution rows,
and None (with the aborting rule still named in the richer dict) on
condition-abort rows.  The oracle is normative; the kernel output is
property-tested against it on fixture-matched requests, randomized
grids, and sharded/tenant variants, mirroring
tests/test_kernel_differential.py.

Shadow half: oracle tests for the diff report — an identical candidate
tree yields zero diffs; a candidate with exactly one flipped rule diffs
on exactly the rows whose oracle decision changes; and the honesty
invariants (never blocks, never alters production responses, never
caches, bounded queue drops are counted)."""

import copy
import json
import random
import tempfile
import time

import numpy as np
import pytest
import yaml

from access_control_srv_tpu.core import AccessController, populate
from access_control_srv_tpu.models import Attribute, Request, Target
from access_control_srv_tpu.ops import (
    DecisionKernel,
    PrefilteredKernel,
    compile_policies,
    encode_requests,
)
from access_control_srv_tpu.srv.evaluator import HybridEvaluator
from access_control_srv_tpu.srv.explain import (
    KIND_ABORT,
    KIND_NONE,
    KIND_POLICY,
    KIND_RULE,
    ExplainDecoder,
    explain_capacity_ok,
)
from access_control_srv_tpu.srv.shadow import (
    ShadowEvaluator,
    ShadowSizeClassError,
)
from access_control_srv_tpu.srv.telemetry import Telemetry

from .test_kernel_differential import DEC_CODE, grid_requests
from .test_prefilter import force_active
from .utils import URNS, build_request, fixture, make_engine

ORG = "urn:restorecommerce:acs:model:organization.Organization"
LOC = "urn:restorecommerce:acs:model:location.Location"
USER = "urn:restorecommerce:acs:model:user.User"

FIXTURES = [
    "basic_policies.yml",
    "policy_targets.yml",
    "policy_set_targets.yml",
    "role_scopes.yml",
    "conditions.yml",
    "acl_policies.yml",
    "props_multi_rules_entities.yml",
    "ops_multi.yml",
]


# --------------------------------------------------------------- requests


def _member(**kwargs):
    defaults = dict(
        subject_id="ada",
        subject_role="member",
        role_scoping_entity=ORG,
        role_scoping_instance="Org1",
        owner_indicatory_entity=ORG,
        owner_instance="Org1",
        action_type=URNS["read"],
    )
    defaults.update(kwargs)
    return build_request(**defaults)


def _abort_request():
    """Matches conditions.yml r_self_modify's target while its context
    lacks ``subject``, so the condition raises — the guaranteed
    condition-abort row (same shape as tests/test_sig_kernel.py)."""
    return Request(
        target=Target(
            subjects=[Attribute(id=URNS["role"], value="member")],
            resources=[Attribute(id=URNS["entity"], value=USER)],
            actions=[Attribute(id=URNS["actionID"], value=URNS["modify"])],
        ),
        context={
            "resources": [{"id": "someone-else"}],
            "subject": {
                "role_associations": [{"role": "member", "attributes": []}],
                "hierarchical_scopes": [],
            },
        },
    )


def matched_requests(fixture_name):
    """Fixture-matched rows guaranteeing non-vacuous provenance coverage
    (the generic grid alone leaves some fixtures all-INDETERMINATE)."""
    props = [LOC + "#id", LOC + "#name"], [ORG + "#id", ORG + "#name"]
    if fixture_name == "props_multi_rules_entities.yml":
        return [
            _member(resource_type=[LOC, ORG], resource_id=["L1", "O1"],
                    owner_instance=["Org1", "Org1"],
                    resource_property=list(props)),
            _member(resource_type=[LOC, ORG], resource_id=["L1", "O1"],
                    owner_instance=["Org1", "Org1"],
                    resource_property=[props[0],
                                       props[1] + [ORG + "#description"]]),
            _member(resource_type=[LOC, ORG], resource_id=["L1", "O1"],
                    owner_instance=["Org1", "Org1"]),
        ]
    if fixture_name == "role_scopes.yml":
        return [
            _member(resource_type=LOC, resource_id="L1"),
            _member(resource_type=LOC, resource_id="L1",
                    action_type=URNS["modify"]),
            _member(resource_type=LOC, resource_id="L1",
                    subject_role="manager",
                    role_scoping_instance="SuperOrg1",
                    action_type=URNS["modify"]),
            _member(resource_type=LOC, resource_id="L1",
                    owner_instance="otherOrg"),
        ]
    if fixture_name == "conditions.yml":
        return [_abort_request()]
    return []


def fixture_requests(fixture_name, n=96, seed=53):
    return grid_requests(n=n, seed=seed) + matched_requests(fixture_name)


# ------------------------------------------------------------ parity core


def assert_explain_parity(engine, requests, kernel, policy_sets=None):
    """Kernel explain output == oracle provenance, row for row.  Returns
    the number of rows that carried a non-None source (non-vacuity is the
    caller's assertion — it knows the fixture)."""
    compiled = kernel.compiled
    decoder = ExplainDecoder(
        policy_sets if policy_sets is not None else engine.policy_sets,
        kernel.explain_strides,
    )
    batch = encode_requests(requests, compiled)
    outputs = kernel.evaluate(batch)
    assert len(outputs) == 4, "explain kernel must emit the 4th output"
    decision, _cacheable, status, expl = outputs
    n_source = 0
    for b, request in enumerate(requests):
        if not batch.eligible[b]:
            continue
        expected = engine.is_allowed(copy.deepcopy(request))
        code = int(expl[b])
        source = decoder.source(code)
        info = decoder.decode(code)
        if int(status[b]) != 200:
            # condition abort: bare DENY + error status, NO _rule_id on
            # either side — but the explain dict names the aborting rule
            assert int(decision[b]) == DEC_CODE["DENY"]
            assert int(status[b]) == expected.operation_status.code
            assert source is None
            assert getattr(expected, "_rule_id", None) is None
            assert info is not None and info["kind"] == "condition_abort"
            assert info["rule"] is not None
            continue
        assert int(decision[b]) == DEC_CODE[expected.decision], (
            f"request {b}: decision kernel={decision[b]} "
            f"oracle={expected.decision}"
        )
        assert source == getattr(expected, "_rule_id", None), (
            f"request {b}: source kernel={source!r} "
            f"oracle={getattr(expected, '_rule_id', None)!r} "
            f"(code={code}, kind={code & 3})"
        )
        if source is not None:
            n_source += 1
            assert info is not None
            if info["kind"] == "rule":
                assert info["rule"] == source
            else:
                assert info["kind"] == "policy"
                assert info["policy"] == source
                assert info["rule"] is None
        else:
            assert info is None or info["kind"] == "condition_abort"
    return n_source


# ------------------------------------------------------- dense + sig path


@pytest.mark.parametrize("fixture_name", FIXTURES)
def test_explain_dense_matches_oracle(fixture_name):
    engine = make_engine(fixture_name)
    compiled = compile_policies(engine.policy_sets, engine.urns)
    assert compiled.supported, compiled.unsupported_reason
    kernel = DecisionKernel(compiled, explain=True)
    n = assert_explain_parity(
        engine, fixture_requests(fixture_name), kernel
    )
    assert n > 0, "no row carried provenance — the test proved nothing"


@pytest.mark.parametrize("fixture_name", FIXTURES)
def test_explain_prefilter_matches_oracle(fixture_name):
    """The sig-path kernel maps compacted rule slots back to ORIGINAL
    flat positions (rule_orig_flat), so the same decoder applies."""
    engine = make_engine(fixture_name)
    compiled = compile_policies(engine.policy_sets, engine.urns)
    assert compiled.supported
    kernel = force_active(PrefilteredKernel(compiled, explain=True))
    n = assert_explain_parity(
        engine, fixture_requests(fixture_name, seed=11), kernel
    )
    assert n > 0


def test_explain_off_keeps_three_outputs():
    """explain=False kernels emit exactly the pre-explain output tuple,
    and in one shared jit registry the explain variant keys separately:
    building and running it leaves the explain-off executables as they
    were (no new compilation under an off key)."""
    engine = make_engine("role_scopes.yml")
    compiled = compile_policies(engine.policy_sets, engine.urns)
    batch = encode_requests(grid_requests(n=16), compiled)
    assert len(DecisionKernel(compiled).evaluate(batch)) == 3
    assert len(DecisionKernel(compiled, explain=True).evaluate(batch)) == 4

    shared: dict = {}
    off = DecisionKernel(compiled, dynamic_policies=True, shared_jits=shared)
    assert len(off.evaluate(batch)) == 3
    off_sizes = {k: f._cache_size() for k, f in shared.items()}
    on = DecisionKernel(compiled, dynamic_policies=True, shared_jits=shared,
                        explain=True)
    assert len(on.evaluate(batch)) == 4
    added = set(shared) - set(off_sizes)
    assert added and all(k[-1] == "explain" for k in added)
    assert {k: shared[k]._cache_size() for k in off_sizes} == off_sizes


def test_explain_capacity_bound():
    assert explain_capacity_ok(2, 4, 8)
    assert explain_capacity_ok(1024, 64, 64)  # ~4M slots
    assert not explain_capacity_ok(1 << 14, 1 << 7, 1 << 7)  # 2^28 slots


def test_decoder_defensive_on_garbage():
    """Corrupt codes must decode to None, never raise (serving path)."""
    engine = make_engine("role_scopes.yml")
    compiled = compile_policies(engine.policy_sets, engine.urns)
    decoder = ExplainDecoder(engine.policy_sets,
                             (compiled.KP, compiled.KR))
    for code in (0, -1, (1 << 30) | KIND_RULE, (1 << 30) | KIND_POLICY,
                 (997 << 2) | KIND_ABORT):
        decoder.decode(code)  # must not raise
        decoder.source(code)
    assert decoder.decode(0) is None
    assert decoder.source((1 << 30) | KIND_RULE) is None


# --------------------------------------------------------------- sharded


def _make_2d_mesh(data, model):
    import jax
    from jax.sharding import Mesh

    devices = np.array(jax.devices()[: data * model]).reshape(data, model)
    return Mesh(devices, ("data", "model"))


@pytest.mark.parametrize(
    "fixture_name",
    ["role_scopes.yml", "props_multi_rules_entities.yml", "conditions.yml"],
)
def test_explain_rule_shard_matches_oracle(fixture_name):
    from access_control_srv_tpu.parallel.rule_shard import RuleShardedKernel

    engine = make_engine(fixture_name)
    compiled = compile_policies(engine.policy_sets, engine.urns)
    assert compiled.supported
    kernel = RuleShardedKernel(compiled, _make_2d_mesh(2, 4), explain=True)
    n = assert_explain_parity(
        engine, fixture_requests(fixture_name, n=64, seed=29), kernel
    )
    assert n > 0


@pytest.mark.parametrize(
    "fixture_name",
    ["role_scopes.yml", "props_multi_rules_entities.yml", "conditions.yml"],
)
def test_explain_pod_shard_matches_oracle(fixture_name):
    from access_control_srv_tpu.parallel.pod_shard import PodShardedKernel

    engine = make_engine(fixture_name)
    compiled = compile_policies(engine.policy_sets, engine.urns)
    assert compiled.supported
    kernel = PodShardedKernel(compiled, _make_2d_mesh(2, 4),
                              explain=True)
    n = assert_explain_parity(
        engine, fixture_requests(fixture_name, n=64, seed=31), kernel
    )
    assert n > 0


# ---------------------------------------------------------- serving path


@pytest.mark.parametrize(
    "fixture_name",
    ["role_scopes.yml", "basic_policies.yml", "conditions.yml"],
)
def test_explain_serving_path_matches_oracle(fixture_name):
    """Through HybridEvaluator.is_allowed_batch: every served row's
    ``_rule_id`` — kernel rows via the explain decode, fallback rows via
    the oracle walk — equals the oracle's, and the richer ``_explain``
    dict is consistent with it."""
    engine = make_engine(fixture_name)
    evaluator = HybridEvaluator(engine, backend="kernel", explain=True)
    try:
        assert evaluator.kernel_active
        requests = fixture_requests(fixture_name, n=64, seed=17)
        responses = evaluator.is_allowed_batch(requests)
        n_source = 0
        for request, response in zip(requests, responses):
            expected = engine.is_allowed(copy.deepcopy(request))
            assert response.decision == expected.decision
            got = getattr(response, "_rule_id", None)
            assert got == getattr(expected, "_rule_id", None)
            if got is not None:
                n_source += 1
                info = getattr(response, "_explain", None)
                if info is not None:  # kernel rows carry the rich dict
                    assert got in (info.get("rule"), info.get("policy"))
        assert n_source > 0
    finally:
        evaluator.shutdown()


def test_explain_tenant_class_shared_jits():
    """Two same-class tenant evaluators on ONE shared jit registry, both
    with explain on: per-tenant provenance stays oracle-exact and the
    second tenant's build registers no new device programs (the explain
    variant lives in the same class-shared registry)."""
    import access_control_srv_tpu.ops.delta as delta_mod

    shared = {}
    engines, evaluators = [], []
    fixtures = ["role_scopes.yml", "role_scopes.yml"]
    tree0 = make_engine(fixtures[0]).policy_sets
    _, caps, _ = delta_mod.full_bucketed_compile(
        tree0, make_engine().urns, version=0
    )
    try:
        for i, fixture_name in enumerate(fixtures):
            engine = make_engine(fixture_name)
            evaluator = HybridEvaluator(
                engine, backend="kernel", explain=True,
                shared_jits=shared, fixed_caps=caps,
                tenant=f"t{i}",
            )
            engines.append(engine)
            evaluators.append(evaluator)
        keys_after_first = None
        requests = fixture_requests("role_scopes.yml", n=32, seed=5)
        for engine, evaluator in zip(engines, evaluators):
            if keys_after_first is None:
                keys_after_first = set(shared)
            responses = evaluator.is_allowed_batch(requests)
            for request, response in zip(requests, responses):
                expected = engine.is_allowed(copy.deepcopy(request))
                assert response.decision == expected.decision
                assert getattr(response, "_rule_id", None) == getattr(
                    expected, "_rule_id", None
                )
        assert set(shared) == keys_after_first, (
            "second same-class tenant registered new device programs"
        )
    finally:
        for evaluator in evaluators:
            evaluator.shutdown()


# -------------------------------------------------------------- fuzzing


@pytest.mark.parametrize("seed", [101, 202, 303])
def test_explain_fuzz_random_grids(seed):
    """Randomized request sweeps across fixtures; the explain source must
    track the oracle on every eligible row, whatever the mix."""
    rng = random.Random(seed)
    for fixture_name in rng.sample(FIXTURES, 3):
        engine = make_engine(fixture_name)
        compiled = compile_policies(engine.policy_sets, engine.urns)
        assert compiled.supported
        kernel = DecisionKernel(compiled, explain=True)
        assert_explain_parity(
            engine,
            fixture_requests(fixture_name, n=48, seed=rng.randrange(1 << 16)),
            kernel,
        )


# ------------------------------------------------------------ shadow half


def _shadow_requests():
    return [
        _member(resource_type=LOC, resource_id="L1"),
        _member(resource_type=LOC, resource_id="L1",
                action_type=URNS["modify"]),
        _member(resource_type=LOC, resource_id="L1",
                subject_role="manager", role_scoping_instance="SuperOrg1",
                action_type=URNS["modify"]),
        _member(resource_type=LOC, resource_id="L1",
                owner_instance="otherOrg"),
    ] + grid_requests(n=28, seed=77)


def _flipped_fixture(tmp_path, rule_id="r_member_read_loc"):
    """role_scopes.yml with one rule's effect flipped PERMIT->DENY."""
    with open(fixture("role_scopes.yml")) as fh:
        doc = yaml.safe_load(fh)
    found = False
    for ps in doc["policy_sets"]:
        for pol in ps.get("policies", []):
            for rule in pol.get("rules", []):
                if rule["id"] == rule_id:
                    assert rule["effect"] == "PERMIT"
                    rule["effect"] = "DENY"
                    found = True
    assert found
    path = str(tmp_path / "candidate.yml")
    with open(path, "w") as fh:
        yaml.safe_dump(doc, fh)
    return path


@pytest.fixture()
def production():
    engine = make_engine("role_scopes.yml")
    evaluator = HybridEvaluator(engine, backend="kernel", explain=True)
    yield evaluator
    evaluator.shutdown()


def _drained_status(shadow):
    assert shadow.drain(15.0), "shadow queue failed to drain"
    # the worker may still be inside _evaluate on the popped batch
    for _ in range(200):
        status = shadow.status()
        if status["queue_depth"] == 0 and status["evaluated"] > 0:
            return status
        time.sleep(0.02)
    return shadow.status()


class TestShadow:
    def test_identical_candidate_zero_diffs(self, production):
        telemetry = Telemetry()
        shadow = ShadowEvaluator(
            production, [fixture("role_scopes.yml")], telemetry=telemetry
        )
        try:
            assert shadow.new_program_keys == [], (
                "same-size-class candidate must reuse production programs"
            )
            requests = _shadow_requests()
            responses = production.is_allowed_batch(requests)
            jits = production._shared_jits
            sizes = {k: f._cache_size() for k, f in jits.items()}
            shadow.submit(requests, responses)
            status = _drained_status(shadow)
            # the candidate ran the production executables: zero new
            # XLA compilations
            assert {k: f._cache_size() for k, f in jits.items()} == sizes
            assert status["evaluated"] == len(requests)
            assert status["diffs"] == 0
            assert status["samples"] == []
            assert telemetry.snapshot()["shadow"]["evaluated"] == len(
                requests
            )
        finally:
            shadow.stop()

    def test_flipped_rule_diffs_exactly_affected_rows(
        self, production, tmp_path
    ):
        candidate_path = _flipped_fixture(tmp_path)
        telemetry = Telemetry()
        shadow = ShadowEvaluator(
            production, [candidate_path], telemetry=telemetry
        )
        try:
            requests = _shadow_requests()
            responses = production.is_allowed_batch(requests)

            # the oracle knows exactly which rows must diff
            candidate_engine = AccessController()
            populate(candidate_engine, candidate_path)
            expected = [
                (req, resp.decision,
                 candidate_engine.is_allowed(copy.deepcopy(req)).decision)
                for req, resp in zip(requests, responses)
            ]
            expected_diffs = [
                (p, c) for _, p, c in expected if p != c
            ]
            assert expected_diffs, "flip must affect at least one row"

            shadow.submit(requests, responses)
            status = _drained_status(shadow)
            assert status["diffs"] == len(expected_diffs)
            transitions = {}
            for p, c in expected_diffs:
                key = f"{p}->{c}"
                transitions[key] = transitions.get(key, 0) + 1
            assert status["diffs_by_transition"] == transitions
            assert telemetry.shadow_diffs.snapshot() == transitions
            # sampled records carry provenance on BOTH sides
            assert status["samples"]
            sample = status["samples"][0]
            assert sample["production"]["decision"] != (
                sample["candidate"]["decision"]
            )
            assert sample["production"]["rule_id"] is not None
        finally:
            shadow.stop()

    def test_shadow_never_alters_production(self, production):
        """The mirror point is post-decision: the served objects are
        byte-for-byte what production computed, shadow on or off."""
        requests = _shadow_requests()
        baseline = production.is_allowed_batch(requests)
        shadow = ShadowEvaluator(production, [fixture("role_scopes.yml")])
        try:
            responses = production.is_allowed_batch(requests)
            shadow.submit(requests, responses)
            for base, resp in zip(baseline, responses):
                assert base.decision == resp.decision
                assert base.operation_status.code == (
                    resp.operation_status.code
                )
                assert getattr(base, "_rule_id", None) == getattr(
                    resp, "_rule_id", None
                )
            # and the shadow's evaluator can never cache a decision
            assert shadow.evaluator.decision_cache is None
            _drained_status(shadow)
        finally:
            shadow.stop()

    def test_queue_overflow_drops_counted(self, production):
        telemetry = Telemetry()
        shadow = ShadowEvaluator(
            production, [fixture("role_scopes.yml")],
            telemetry=telemetry, queue_batches=0,  # every submit overflows
        )
        try:
            requests = _shadow_requests()[:4]
            responses = production.is_allowed_batch(requests)
            t0 = time.perf_counter()
            shadow.submit(requests, responses)
            assert time.perf_counter() - t0 < 1.0, "submit must not block"
            status = shadow.status()
            assert status["dropped"] == len(requests)
            assert status["evaluated"] == 0
            assert telemetry.shadow.get("dropped") == len(requests)
        finally:
            shadow.stop()

    def test_sheds_and_expired_deadlines_not_mirrored(self, production):
        """Admission sheds (429/503/504 + INDETERMINATE) were never
        evaluated — mirroring one would fabricate an INDETERMINATE->X
        diff against a candidate that DID evaluate the row.  And the
        serving ``_deadline`` stamp (long expired by replay time) must
        not make the candidate path shed the row as deadline-expired:
        the caller was already answered, so the replay strips the stamp
        on a copy without ever mutating the shared request.  Both were
        first seen under live shadow traffic."""
        from access_control_srv_tpu.srv.admission import (
            OVERLOAD_CODE,
            overload_response,
        )

        shadow = ShadowEvaluator(production, [fixture("role_scopes.yml")])
        try:
            requests = _shadow_requests()
            responses = production.is_allowed_batch(requests)
            for request in requests:
                request._deadline = time.monotonic() - 5.0
            shed = overload_response(OVERLOAD_CODE, "shed under overload")
            shadow.submit(requests + [requests[0]], responses + [shed])
            status = _drained_status(shadow)
            assert status["evaluated"] == len(requests), (
                "shed rows must not be mirrored"
            )
            assert status["diffs"] == 0, (
                "identical candidate: any diff here is fabricated "
                "(expired-deadline shed or shed mirroring)"
            )
            assert requests[0]._deadline is not None, (
                "the shared request must never be mutated by the replay"
            )
        finally:
            shadow.stop()

    def test_tenant_filter(self, production):
        shadow = ShadowEvaluator(
            production, [fixture("role_scopes.yml")], tenant="acme"
        )
        try:
            requests = _shadow_requests()[:4]
            responses = production.is_allowed_batch(requests)
            for i, request in enumerate(requests):
                request._tenant = "acme" if i % 2 == 0 else "globex"
            shadow.submit(requests, responses)
            status = _drained_status(shadow)
            assert status["evaluated"] == 2
        finally:
            shadow.stop()

    def test_reload_bumps_shadow_epoch_only(self, production, tmp_path):
        candidate_path = _flipped_fixture(tmp_path)
        shadow = ShadowEvaluator(production, [fixture("role_scopes.yml")])
        try:
            production_version = production._version
            assert shadow.epoch == 0
            shadow.reload([candidate_path])
            assert shadow.epoch == 1
            assert production._version == production_version, (
                "candidate reload must not touch production"
            )
            requests = _shadow_requests()[:4]
            responses = production.is_allowed_batch(requests)
            shadow.submit(requests, responses)
            status = _drained_status(shadow)
            assert status["diffs"] >= 1  # the flip now reports
        finally:
            shadow.stop()

    def test_size_class_overflow_refused(self, production, tmp_path):
        """A candidate overflowing the production size class would need a
        second compiled program — the shadow refuses it outright."""
        with open(fixture("role_scopes.yml")) as fh:
            doc = yaml.safe_load(fh)
        pol = doc["policy_sets"][0]["policies"][0]
        template = copy.deepcopy(pol["rules"][0])
        for i in range(64):  # blow past the production KR bucket
            clone = copy.deepcopy(template)
            clone["id"] = f"r_pad_{i}"
            pol["rules"].append(clone)
        path = str(tmp_path / "oversized.yml")
        with open(path, "w") as fh:
            yaml.safe_dump(doc, fh)
        assert production._caps is not None
        with pytest.raises(ShadowSizeClassError):
            shadow = ShadowEvaluator(production, [path])
            shadow.stop()  # unreachable; belt for the raises-miss case


def test_shadow_through_worker_and_command(tmp_path):
    """Product-path lifecycle: Worker wires the shadow from config, the
    facade mirrors served decisions, ``shadow_status`` and health expose
    it, and teardown joins the shadow worker."""
    from access_control_srv_tpu.srv import Worker

    candidate_path = _flipped_fixture(tmp_path)
    worker = Worker().start(
        {
            "policies": {"type": "local",
                         "paths": [fixture("role_scopes.yml")]},
            "explain": {"enabled": True},
            "shadow": {"enabled": True,
                       "candidate_paths": [candidate_path]},
        }
    )
    try:
        assert worker.shadow is not None
        assert worker.service.shadow is worker.shadow
        requests = _shadow_requests()[:4]
        responses = [worker.service.is_allowed(r) for r in requests]
        assert responses[0].decision == "PERMIT"
        assert getattr(responses[0], "_rule_id", None) == (
            "r_member_read_loc"
        )
        status = worker.command_interface.command(
            "shadow_status", {"drain": True}
        )
        assert status["enabled"] and status["evaluated"] >= 4
        assert status["diffs"] >= 1
        health = worker.command_interface.command("health_check", {})
        assert health["shadow"]["diffs"] >= 1
        assert "samples" not in health["shadow"]
    finally:
        worker.stop()
    assert worker.shadow is None


def test_shadow_disabled_by_default():
    from access_control_srv_tpu.srv import Worker

    worker = Worker().start(
        {"policies": {"type": "local",
                      "paths": [fixture("role_scopes.yml")]}}
    )
    try:
        assert worker.shadow is None
        assert worker.service.shadow is None
        status = worker.command_interface.command("shadow_status", {})
        assert status == {"enabled": False}
        health = worker.command_interface.command("health_check", {})
        assert "shadow" not in health
    finally:
        worker.stop()


@pytest.mark.parametrize("explain_enabled", [True, False])
def test_explain_grpc_trailer(explain_enabled):
    """Wire surface: the io.restorecommerce Response proto has no
    provenance field, so explain rides the ``x-acs-explain`` trailing
    metadata as compact JSON — present with the deciding rule when
    explain is on, entirely absent (and response bytes identical) when
    off."""
    import grpc

    from access_control_srv_tpu.srv import Worker
    from access_control_srv_tpu.srv.gen import access_control_pb2 as pb
    from access_control_srv_tpu.srv.transport_grpc import (
        EXPLAIN_METADATA_KEY,
        GrpcServer,
        request_to_pb,
    )

    worker = Worker().start(
        {
            "policies": {"type": "local",
                         "paths": [fixture("role_scopes.yml")]},
            "explain": {"enabled": explain_enabled},
        }
    )
    server = GrpcServer(worker, "127.0.0.1:0").start()
    channel = grpc.insecure_channel(server.addr)
    try:
        fn = channel.unary_unary(
            "/acstpu.AccessControlService/IsAllowed",
            request_serializer=lambda m: m.SerializeToString(),
            response_deserializer=pb.Response.FromString,
        )
        msg = request_to_pb(_member(resource_type=LOC, resource_id="L1"))
        response, call = fn.with_call(msg)
        assert response.decision == pb.PERMIT
        trailing = dict(call.trailing_metadata() or ())
        if explain_enabled:
            info = json.loads(trailing[EXPLAIN_METADATA_KEY])
            assert info["kind"] == "rule"
            assert info["rule"] == "r_member_read_loc"
        else:
            assert EXPLAIN_METADATA_KEY not in trailing
    finally:
        channel.close()
        server.stop()
        worker.stop()
