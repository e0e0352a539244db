"""Native C++ wire encoder vs Python encoder: array-level differential.

The native encoder (access_control_srv_tpu/native) parses serialized
``acstpu.Request`` wire bytes directly; it must produce exactly the same
row arrays, eligibility mask, regex matrices and (through the kernel) the
same decisions as the Python encoder run on the deserialized requests.
"""

import numpy as np
import pytest

from access_control_srv_tpu import native
from access_control_srv_tpu.ops import (
    DecisionKernel,
    compile_policies,
    encode_requests,
)
from access_control_srv_tpu.srv.transport_grpc import request_from_pb, request_to_pb

from .test_kernel_differential import DEC_CODE, grid_requests
from .utils import make_engine

pytestmark = pytest.mark.skipif(
    not native.available(),
    reason=f"native encoder unavailable: {native.build_error()}",
)


def wire_roundtrip(requests):
    """Serialize to wire bytes + the deserialized twins the Python encoder
    sees (the honest comparison: both sides read the same wire)."""
    messages = [request_to_pb(r).SerializeToString() for r in requests]
    twins = []
    for m in messages:
        from access_control_srv_tpu.srv.gen import access_control_pb2 as pb
        from access_control_srv_tpu.srv.service import unmarshall_context

        msg = pb.Request.FromString(m)
        req = request_from_pb(msg)
        if isinstance(req.context, dict):
            req.context = unmarshall_context(req.context)
        twins.append(req)
    return messages, twins


@pytest.mark.parametrize(
    "fixture_name",
    [
        "basic_policies.yml",
        "policy_targets.yml",
        "policy_set_targets.yml",
        "role_scopes.yml",
        "acl_policies.yml",
        "props_single.yml",
        "props_multi_rules_entities.yml",
        "ops_multi.yml",
    ],
)
def test_wire_differential(fixture_name):
    engine = make_engine(fixture_name)
    compiled = compile_policies(engine.policy_sets, engine.urns)
    assert compiled.supported
    if compiled.conditions:
        pytest.skip("condition fixtures stay on the Python encoder")
    enc = native.NativeBatchEncoder(compiled)

    requests = grid_requests(n=120, seed=31)
    messages, twins = wire_roundtrip(requests)
    nb = enc.encode_wire(messages)
    # the native encoder fills the fixed floor shapes; compare the Python
    # encoder at the same caps (adaptive caps are a Python-path feature)
    from access_control_srv_tpu.ops.encode import _CAPS_FLOOR

    pb_batch = encode_requests(twins, compiled, caps=_CAPS_FLOOR)

    assert np.array_equal(nb.eligible, pb_batch.eligible)
    for name in nb.arrays:
        assert np.array_equal(nb.arrays[name], pb_batch.arrays[name]), name
    assert np.array_equal(nb.rgx_set, pb_batch.rgx_set)
    assert np.array_equal(nb.pfx_neq, pb_batch.pfx_neq)
    assert nb.eligible.sum() > 60  # the sweep must exercise the kernel path


def test_wire_decisions_match_oracle():
    engine = make_engine("role_scopes.yml")
    compiled = compile_policies(engine.policy_sets, engine.urns)
    enc = native.NativeBatchEncoder(compiled)
    kernel = DecisionKernel(compiled)

    requests = grid_requests(n=100, seed=77)
    messages, twins = wire_roundtrip(requests)
    nb = enc.encode_wire(messages)
    decision, cacheable, status = kernel.evaluate(nb)
    for b, twin in enumerate(twins):
        if not nb.eligible[b]:
            continue
        expected = engine.is_allowed(twin)
        assert decision[b] == DEC_CODE[expected.decision], b


def test_edge_shapes():
    engine = make_engine("basic_policies.yml")
    compiled = compile_policies(engine.policy_sets, engine.urns)
    enc = native.NativeBatchEncoder(compiled)
    from access_control_srv_tpu.models import Attribute, Request, Target, Urns

    urns = Urns()
    cases = [
        Request(target=None, context=None),  # no target -> ineligible
        Request(target=Target(subjects=[], resources=[], actions=[]),
                context=None),
        Request(
            target=Target(
                subjects=[Attribute(id=urns["role"], value="member")],
                resources=[],
                actions=[],
            ),
            # token subject -> host path
            context={"subject": {"token": "tok"}, "resources": []},
        ),
        Request(
            target=Target(
                subjects=[],
                # unknown resource attribute id -> ineligible
                resources=[Attribute(id="custom:attr", value="v")],
                actions=[],
            ),
            context=None,
        ),
    ]
    messages, twins = wire_roundtrip(cases)
    nb = enc.encode_wire(messages)
    pb_batch = encode_requests(twins, compiled)
    assert np.array_equal(nb.eligible, pb_batch.eligible)
    for name in nb.arrays:
        assert np.array_equal(nb.arrays[name], pb_batch.arrays[name]), name


def test_conditions_tree_rejected():
    engine = make_engine("conditions.yml")
    compiled = compile_policies(engine.policy_sets, engine.urns)
    if not compiled.conditions:
        pytest.skip("fixture has no conditions")
    with pytest.raises(RuntimeError):
        native.NativeBatchEncoder(compiled)


def test_native_wire_path_end_to_end():
    """The gRPC batch endpoint must take the native path (not silently
    fall back) and agree with the oracle."""
    import os

    from access_control_srv_tpu.srv import Worker
    from access_control_srv_tpu.srv.gen import access_control_pb2 as pb
    from access_control_srv_tpu.srv.transport_grpc import GrpcClient, GrpcServer

    from .test_grpc_transport import SEED, wire_request

    worker = Worker().start(
        {
            "policies": {"type": "database"},
            "seed_data": {
                "policy_sets": os.path.join(SEED, "policy_sets.yaml"),
                "policies": os.path.join(SEED, "policies.yaml"),
                "rules": os.path.join(SEED, "rules.yaml"),
            },
        }
    )
    server = GrpcServer(worker, "127.0.0.1:0").start()
    client = GrpcClient(server.addr)
    try:
        assert worker.evaluator.native_active, "native encoder should engage"
        batch = pb.BatchRequest(
            requests=[
                wire_request(),
                wire_request(role="nobody"),
                wire_request(),
            ]
        )
        out = client.is_allowed_batch(batch)
        decisions = [r.decision for r in out.responses]
        assert decisions == [pb.PERMIT, pb.INDETERMINATE, pb.PERMIT]
        assert all(r.operation_status.code == 200 for r in out.responses)
    finally:
        client.close()
        server.stop()
        worker.stop()


def test_malformed_wire_rows_not_fabricated():
    """Corrupt protobuf or JSON must never produce a fabricated 200
    decision from the native path -- such rows go ineligible."""
    engine = make_engine("basic_policies.yml")
    compiled = compile_policies(engine.policy_sets, engine.urns)
    enc = native.NativeBatchEncoder(compiled)

    good = wire_roundtrip(grid_requests(n=1, seed=5))[0][0]
    bad_proto = good + b"\xff\xff\xff"          # trailing garbage field
    from access_control_srv_tpu.srv.gen import access_control_pb2 as pb

    msg = pb.Request.FromString(good)
    msg.context.subject.value = b'{"id": "u", "role_assoc'  # truncated JSON
    bad_json = msg.SerializeToString()

    nb = enc.encode_wire([good, bad_proto, bad_json])
    assert nb.eligible[0]
    assert not nb.eligible[1]
    assert not nb.eligible[2]


def test_concurrent_encode_wire():
    """Concurrent batches on one encoder must stay consistent (the
    interner is shared mutable state guarded by the encoder lock)."""
    from concurrent.futures import ThreadPoolExecutor

    engine = make_engine("basic_policies.yml")
    compiled = compile_policies(engine.policy_sets, engine.urns)
    enc = native.NativeBatchEncoder(compiled)
    kernel = DecisionKernel(compiled)

    def job(seed):
        reqs = grid_requests(n=40, seed=seed)
        messages, twins = wire_roundtrip(reqs)
        nb = enc.encode_wire(messages)
        decision, _, status = kernel.evaluate(nb)
        out = []
        for b, twin in enumerate(twins):
            if nb.eligible[b] and status[b] == 200:
                out.append((b, int(decision[b]), engine.is_allowed(twin).decision))
        return out

    with ThreadPoolExecutor(max_workers=8) as pool:
        for rows in pool.map(job, range(200, 216)):
            for b, got, expected in rows:
                assert got == DEC_CODE[expected], b


def test_trailing_garbage_json_rejected():
    """JSON with trailing garbage or non-RFC numbers must not stay
    kernel-eligible (json.loads would raise on the pb path)."""
    from access_control_srv_tpu.srv.gen import access_control_pb2 as pb

    engine = make_engine("basic_policies.yml")
    compiled = compile_policies(engine.policy_sets, engine.urns)
    enc = native.NativeBatchEncoder(compiled)

    good = wire_roundtrip(grid_requests(n=1, seed=5))[0][0]
    cases = [b'{"id": "u"}garbage', b'{"n": +5}', b'{"n": -}', b'{"n": 5.}']
    messages = []
    for payload in cases:
        msg = pb.Request.FromString(good)
        msg.context.subject.value = payload
        messages.append(msg.SerializeToString())
    nb = enc.encode_wire(messages)
    assert not nb.eligible.any()


def test_deeply_nested_json_no_stack_overflow():
    """A JSON nesting bomb (well under the gRPC message cap) must not
    overflow the C stack -- past the parser depth cap the row goes
    ineligible and falls back to the Python path."""
    from access_control_srv_tpu.srv.gen import access_control_pb2 as pb

    engine = make_engine("basic_policies.yml")
    compiled = compile_policies(engine.policy_sets, engine.urns)
    enc = native.NativeBatchEncoder(compiled)

    good = wire_roundtrip(grid_requests(n=1, seed=5))[0][0]
    messages = []
    # the {"id": ...} wrapper itself consumes one depth level, so inner
    # array depth 63 hits the cap (64) exactly and 64 exceeds it
    for depth in (30, 63, 64, 200_000):
        bomb = b"[" * depth + b"]" * depth
        msg = pb.Request.FromString(good)
        msg.context.subject.value = b'{"id": ' + bomb + b"}"
        messages.append(msg.SerializeToString())
    nb = enc.encode_wire(messages)
    # depths under the cap parse fine; past the cap the row is ineligible
    assert nb.eligible[0] and nb.eligible[1]
    assert not nb.eligible[2]
    assert not nb.eligible[3]


def test_strict_string_parsing_matches_json_loads():
    """Strings json.loads rejects must make the row ineligible, never
    silently decode to garbage and serve a decision from a misparse."""
    import json as _json

    from access_control_srv_tpu.srv.gen import access_control_pb2 as pb

    engine = make_engine("basic_policies.yml")
    compiled = compile_policies(engine.policy_sets, engine.urns)
    enc = native.NativeBatchEncoder(compiled)
    good = wire_roundtrip(grid_requests(n=1, seed=5))[0][0]

    bad = [
        b'{"id": "unterminated',       # no closing quote
        b'{"id": "trunc\\u12"}',       # truncated \uXXXX
        b'{"id": "bad\\uzzzz"}',       # non-hex \uXXXX
        b'{"id": "esc\\x41"}',         # unknown escape
        b'{"id": "ctl\x01char"}',      # raw control character
        b'{"id": "end\\',              # escape at end of input
    ]
    # json.loads ACCEPTS these, but the native path cannot reproduce
    # Python's surrogate decoding — it must fall back (conservatively
    # ineligible) rather than emit CESU-8 and serve from a misparse
    conservative = [
        b'{"id": "pair\\ud83d\\ude00"}',
        b'{"id": "lone\\ud800"}',
    ]
    ok = [
        b'{"id": "fine\\u0041\\n\\"q\\\\"}',
        b'{"id": "slash\\/ok"}',
    ]
    for payload in bad:
        with pytest.raises(Exception):
            _json.loads(payload.decode("utf-8", "surrogateescape"))
    for payload in conservative + ok:
        _json.loads(payload.decode())

    messages = []
    for payload in bad + conservative + ok:
        msg = pb.Request.FromString(good)
        msg.context.subject.value = payload
        messages.append(msg.SerializeToString())
    nb = enc.encode_wire(messages)
    n_ineligible = len(bad) + len(conservative)
    assert not nb.eligible[:n_ineligible].any()
    assert nb.eligible[n_ineligible:].all()


def test_wire_acl_absent_values_ineligible():
    """ADVICE r2 (high), native side: JSON null ACL entity/instance values
    reach intern_jstr as ABSENT; such rows must fall back to the oracle
    (eligible=False), matching the Python encoder."""
    from access_control_srv_tpu.models import Attribute, Request, Target

    from .utils import URNS

    ORG = "urn:restorecommerce:acs:model:organization.Organization"
    USER = "urn:restorecommerce:acs:model:user.User"
    engine = make_engine("acl_policies.yml")
    compiled = compile_policies(engine.policy_sets, engine.urns)
    enc = native.NativeBatchEncoder(compiled)

    def mk(acls):
        return Request(
            target=Target(
                subjects=[
                    Attribute(id=URNS["role"], value="member"),
                    Attribute(id=URNS["subjectID"], value="ada"),
                ],
                resources=[
                    Attribute(id=URNS["entity"], value=ORG),
                    Attribute(id=URNS["resourceID"], value="res-1"),
                ],
                actions=[Attribute(id=URNS["actionID"], value=URNS["create"])],
            ),
            context={
                "resources": [{"id": "res-1", "meta": {"owners": [],
                                                       "acls": acls}}],
                "subject": {
                    "id": "ada",
                    "role_associations": [
                        {"role": "member", "attributes": []}
                    ],
                    "hierarchical_scopes": [],
                },
            },
        )

    requests = [
        mk([{"id": URNS["aclIndicatoryEntity"], "value": None,
             "attributes": [{"id": URNS["aclInstance"], "value": "ada"}]}]),
        mk([{"id": URNS["aclIndicatoryEntity"], "value": USER,
             "attributes": [{"id": URNS["aclInstance"], "value": None}]}]),
        mk([{"id": URNS["aclIndicatoryEntity"], "value": USER,
             "attributes": [{"id": URNS["aclInstance"], "value": "ada"}]}]),
    ]
    messages, twins = wire_roundtrip(requests)
    nb = enc.encode_wire(messages)
    pb_batch = encode_requests(twins, compiled)
    assert not nb.eligible[0]
    assert not nb.eligible[1]
    assert nb.eligible[2]
    assert np.array_equal(nb.eligible, pb_batch.eligible)


def _deep_hr_request(n_nodes: int, role="member", owner="org-1-x"):
    """A role_scopes-fixture request whose HR tree flattens to n_nodes
    pairs (over the NHR floor of 32 when n_nodes > 32)."""
    from .utils import URNS, build_request

    ORG = "urn:restorecommerce:acs:model:organization.Organization"
    LOC = "urn:restorecommerce:acs:model:location.Location"
    # wide tree (depth 2): n_nodes flattened pairs without tripping the
    # JSON parser's nesting-depth cap
    node = {
        "id": "org-0-n",
        "role": role,
        "children": [
            {"id": f"org-{i + 1}-n"} for i in range(n_nodes - 1)
        ],
    }
    return build_request(
        subject_id="deep-user",
        subject_role=role,
        role_scoping_entity=ORG,
        role_scoping_instance="org-0-n",
        resource_type=LOC,
        resource_id="L1",
        action_type="urn:restorecommerce:acs:names:action:read",
        owner_indicatory_entity=ORG,
        owner_instance=owner,
        hierarchical_scopes=[node],
    )


def test_overcap_flag_and_ceiling_reencode():
    """Rows beyond the floor caps are flagged overcap (not just
    ineligible), and a ceiling-caps re-encode makes them eligible with
    kernel decisions matching the oracle."""
    engine = make_engine("role_scopes.yml")
    compiled = compile_policies(engine.policy_sets, engine.urns)
    enc = native.NativeBatchEncoder(compiled)

    deep = _deep_hr_request(64, owner="org-40-n")
    shallow = _deep_hr_request(3, owner="org-1-n")
    messages, twins = wire_roundtrip([deep, shallow])

    floor_batch = enc.encode_wire(messages)
    assert not floor_batch.eligible[0] and floor_batch.overcap[0]
    assert floor_batch.eligible[1] and not floor_batch.overcap[1]

    from access_control_srv_tpu.ops.encode import _CAPS_CEIL

    ceil_batch = enc.encode_wire(messages, caps=dict(_CAPS_CEIL))
    assert ceil_batch.eligible.all()
    kernel = DecisionKernel(compiled)
    dec, _, status = kernel.evaluate(ceil_batch)
    for b, req in enumerate(twins):
        expected = engine.is_allowed(req)
        assert dec[b] == DEC_CODE[expected.decision], b
        assert status[b] == 200


def test_wire_path_serves_deep_hr_rows_via_ceiling():
    """The serving path keeps over-cap rows native: the evaluator
    re-encodes them at the ceiling and the telemetry records the path."""
    from access_control_srv_tpu.srv.evaluator import HybridEvaluator
    from access_control_srv_tpu.srv.telemetry import Telemetry

    engine = make_engine("role_scopes.yml")
    telemetry = Telemetry()
    ev = HybridEvaluator(engine, telemetry=telemetry)
    if not ev.native_active:
        pytest.skip("native encoder not active for this tree")

    reqs = [_deep_hr_request(64, owner="org-40-n"),
            _deep_hr_request(3, owner="org-1-n"),
            _deep_hr_request(50, owner="nowhere")]
    messages, twins = wire_roundtrip(reqs)
    out = ev.is_allowed_batch_wire(messages)
    assert out is not None
    batch, decision, cacheable, status = out
    assert bool(batch.eligible.all()), "deep rows must stay native"
    assert telemetry.paths.get("native-wire-ceil") == 2
    for b, req in enumerate(twins):
        expected = engine.is_allowed(req)
        assert decision[b] == DEC_CODE[expected.decision], b


def _wide_deep_hr_request():
    """A deep-HR request that also overflows another cap: five entity runs
    against the floor's four."""
    from access_control_srv_tpu.models import Attribute

    from .utils import URNS

    wide = _deep_hr_request(64, owner="org-40-n")
    loc = "urn:restorecommerce:acs:model:location.Location"
    owned = wide.context["resources"][0]
    wide.target.resources = []
    wide.context["resources"] = []
    for i in range(5):
        wide.target.resources += [
            Attribute(id=URNS["entity"], value=loc),
            Attribute(id=URNS["resourceID"], value=f"L{i}")]
        wide.context["resources"].append({**owned, "id": f"L{i}"})
    return wide


def _spied_evaluator():
    """A role_scopes evaluator with its native encode_wire and kernel
    dispatch recorded: (evaluator, telemetry, encode calls, dispatches)."""
    from access_control_srv_tpu.srv.evaluator import HybridEvaluator
    from access_control_srv_tpu.srv.telemetry import Telemetry

    engine = make_engine("role_scopes.yml")
    telemetry = Telemetry()
    ev = HybridEvaluator(engine, telemetry=telemetry)
    if not ev.native_active:
        pytest.skip("native encoder not active for this tree")
    calls, dispatches = [], []
    encode_wire = ev._native_encoder.encode_wire
    evaluate_async = ev._kernel.evaluate_async

    def spy_encode(msgs, caps=None, **kw):
        calls.append((len(msgs), caps))
        return encode_wire(msgs, caps=caps, **kw)

    def spy_dispatch(batch):
        dispatches.append(batch.B)
        return evaluate_async(batch)

    ev._native_encoder.encode_wire = spy_encode
    ev._kernel.evaluate_async = spy_dispatch
    return engine, ev, telemetry, calls, dispatches


@pytest.mark.parametrize("wide", [False, True], ids=["nhr_only", "wide"])
def test_wire_path_retries_deep_hr_rows_before_the_ceiling(wide):
    """Over-cap rows are retried once, at the caps their overflow asks for:
    rows over the HR-scope pair cap alone at the floor with only NHR at the
    ceiling, assembled from the first encode's own parse (one encode
    call); with a row over another cap among them, every row re-encoded at
    the full ceiling.  Both are served natively, with the oracle's
    decisions."""
    from access_control_srv_tpu.ops.encode import (
        _CAPS_CEIL, OVERCAP_NHR, OVERCAP_OTHER,
    )

    engine, ev, telemetry, calls, dispatches = _spied_evaluator()
    reqs = [_deep_hr_request(64, owner="org-40-n"),
            _deep_hr_request(3, owner="org-1-n")]
    if wide:
        reqs.append(_wide_deep_hr_request())
    messages, twins = wire_roundtrip(reqs)
    floor = ev._native_encoder.encode_wire(messages)
    codes = [OVERCAP_NHR, 0] + ([OVERCAP_OTHER] if wide else [])
    assert floor.overcap.tolist() == codes
    calls.clear()
    batch, decision, _, status = ev.is_allowed_batch_wire(messages)
    n_over = 2 if wide else 1
    if wide:
        assert calls == [(len(reqs), None), (n_over, dict(_CAPS_CEIL))]
    else:
        assert calls == [(len(reqs), None)]
    assert dispatches == [len(reqs), n_over]
    assert bool(batch.eligible.all())
    assert telemetry.paths.get("native-wire-overcap") == n_over
    assert telemetry.paths.get("native-wire-ceil") == n_over
    assert (telemetry.paths.get("native-wire-overcap-kept") or 0) == (
        0 if wide else n_over)
    for b, req in enumerate(twins):
        expected = engine.is_allowed(req)
        assert decision[b] == DEC_CODE[expected.decision], b
        assert status[b] == 200


def test_wire_path_skips_the_retry_dispatch_when_no_row_fits():
    """A row over even the ceiling (more HR-scope pairs than its 1024)
    stays ineligible for the oracle: its retry comes from the first
    encode (one encode call), and then dispatches nothing to the
    device."""
    _, ev, telemetry, calls, dispatches = _spied_evaluator()
    messages, _ = wire_roundtrip([_deep_hr_request(1100),
                                  _deep_hr_request(3, owner="org-1-n")])
    batch, _, _, status = ev.is_allowed_batch_wire(messages)
    assert [n for n, _ in calls] == [2]
    assert dispatches == [2]
    assert batch.eligible.tolist() == [False, True]
    assert status[1] == 200
    assert telemetry.paths.get("native-wire-overcap") == 1
    assert telemetry.paths.get("native-wire-overcap-kept") == 1
    assert telemetry.paths.get("native-wire-ceil") == 0


# arrays the first-pass retry must equal the re-encode's in, bit for bit
_RETRY_ARRAYS = ("r_hr", "r_acl_hr", "r_hr_roles", "r_ra3", "r_ra2",
                 "r_n_ra", "r_own_runs", "r_own_bits")


def assert_retry_equals_reencode(first, reenc):
    """The retry assembled from the first pass equals the re-encode of the
    same rows at retry_caps: eligibility, every row array (the listed ones
    above all), and, for each valid entity slot, the regex columns it
    points at (``r_ent_e`` indexes each batch's own entity columns)."""
    assert first.B == reenc.B
    np.testing.assert_array_equal(first.eligible, reenc.eligible)
    fit = reenc.eligible
    assert set(first.arrays) == set(reenc.arrays) >= set(_RETRY_ARRAYS)
    for name in first.arrays:
        if name != "r_ent_e":
            np.testing.assert_array_equal(
                first.arrays[name][fit], reenc.arrays[name][fit],
                err_msg=name)
    valid = first.arrays["r_ent_valid"][fit]
    np.testing.assert_array_equal(valid, reenc.arrays["r_ent_valid"][fit])
    for mat in ("rgx_set", "pfx_neq"):
        cols_first = getattr(first, mat)[:, first.arrays["r_ent_e"][fit][valid]]
        cols_reenc = getattr(reenc, mat)[:, reenc.arrays["r_ent_e"][fit][valid]]
        np.testing.assert_array_equal(cols_first, cols_reenc, err_msg=mat)


@pytest.mark.parametrize("reuse", [False, True], ids=["fresh", "pooled"])
def test_first_pass_retry_equals_the_reencode(reuse):
    """Rows over the HR-scope pair cap alone keep their full flattens from
    the first pass: the retry assembled from them equals the re-encode at
    retry_caps, its kernel decisions are the oracle's, and a row over the
    retry's 1024 pairs stays ineligible as the re-encode leaves it."""
    from access_control_srv_tpu.ops.encode import (
        OVERCAP_NHR, retry_caps,
    )

    engine = make_engine("role_scopes.yml")
    compiled = compile_policies(engine.policy_sets, engine.urns)
    enc = native.NativeBatchEncoder(compiled)
    reqs = [_deep_hr_request(64, owner="org-40-n"),
            _deep_hr_request(3, owner="org-1-n"),
            _deep_hr_request(1100),
            _deep_hr_request(33, role="admin", owner="org-20-n"),
            _deep_hr_request(200, owner="nowhere")]
    messages, twins = wire_roundtrip(reqs)
    floor = enc.encode_wire(messages, reuse=reuse)
    rows = [0, 2, 3, 4]
    assert np.flatnonzero(floor.overcap).tolist() == rows
    assert set(floor.overcap[rows].tolist()) == {OVERCAP_NHR}
    assert floor.kept_hr["rows"].tolist() == rows
    first = enc.retry_from_first_pass(floor)
    reenc = enc.encode_wire([messages[b] for b in rows],
                            caps=retry_caps(floor.overcap[rows]))
    assert first.eligible.tolist() == [True, False, True, True]
    assert_retry_equals_reencode(first, reenc)

    decision, _, status = DecisionKernel(compiled).evaluate(first)
    for j, b in enumerate(rows):
        if first.eligible[j]:
            expected = engine.is_allowed(twins[b])
            assert decision[j] == DEC_CODE[expected.decision], b
            assert status[j] == 200, b
    floor.release_staging()


def test_first_pass_keeps_nothing_beside_another_overflow():
    """A batch with a row over a cap other than NHR keeps no flattens: its
    retry re-encodes every over-cap row at the full ceiling; the next
    batch's side store starts empty."""
    from access_control_srv_tpu.ops.encode import OVERCAP_NHR, OVERCAP_OTHER

    engine = make_engine("role_scopes.yml")
    compiled = compile_policies(engine.policy_sets, engine.urns)
    enc = native.NativeBatchEncoder(compiled)
    messages, _ = wire_roundtrip([_deep_hr_request(64, owner="org-40-n"),
                                  _wide_deep_hr_request()])
    batch = enc.encode_wire(messages)
    assert batch.overcap.tolist() == [OVERCAP_NHR, OVERCAP_OTHER]
    assert batch.kept_hr is None
    shallow, _ = wire_roundtrip([_deep_hr_request(3, owner="org-1-n")])
    assert enc.encode_wire(shallow).kept_hr is None


POOL_RETRY = """
import json, sys
import numpy as np
import yaml
sys.path.insert(0, "benchmark")
sys.path.insert(0, ".")
import harness, traffic
from access_control_srv_tpu import native
from access_control_srv_tpu.core import AccessController, populate
from access_control_srv_tpu.ops import DecisionKernel
from access_control_srv_tpu.ops import encode as pyenc
from access_control_srv_tpu.ops.compile import compile_policies
from access_control_srv_tpu.srv.gen import access_control_pb2 as pb
from access_control_srv_tpu.srv.service import coerce_request
from access_control_srv_tpu.srv.transport_grpc import request_from_pb
from tests.test_kernel_differential import DEC_CODE
from tests.test_native_encoder import assert_retry_equals_reencode

cell = harness.load_cell(%(cell)r)
cell = harness.load_cell(%(cell)r, scale={"mix": cell.mix["small"]})
wl = harness.build_workload(cell, %(seed)d)
with open(%(tmp)r, "w") as f:
    yaml.safe_dump({"policy_sets": wl.tree}, f)
engine = AccessController()
populate(engine, %(tmp)r)
compiled = compile_policies(engine.policy_sets, engine.urns)
enc = native.NativeBatchEncoder(compiled)
kernel = DecisionKernel(compiled)
report = {"overcap": 0, "kept": 0, "retried": 0}
for rows in wl.pool:
    messages = []
    for row in rows:
        msg = pb.Request()
        traffic.fill_pb(msg, row)
        messages.append(msg.SerializeToString())
    floor = enc.encode_wire(messages)
    over = np.flatnonzero(floor.overcap)
    report["overcap"] += len(over)
    if floor.kept_hr is None:
        continue
    report["kept"] += len(floor.kept_hr["rows"])
    first = enc.retry_from_first_pass(floor)
    reenc = enc.encode_wire([messages[b] for b in over],
                            caps=pyenc.retry_caps(floor.overcap[over]))
    assert_retry_equals_reencode(first, reenc)
    decision, _, status = kernel.evaluate(first)
    for j, b in enumerate(over):
        assert first.eligible[j]
        want = engine.is_allowed(coerce_request(
            request_from_pb(pb.Request.FromString(messages[b]))))
        assert decision[j] == DEC_CODE[want.decision], int(b)
        assert status[j] == 200, int(b)
        report["retried"] += 1
print(json.dumps(report))
"""


def test_first_pass_retry_equals_the_reencode_on_the_hr_scoped_pool(tmp_path):
    """The benchmark's HR-scoped pool at its test size: every over-cap row
    is over the HR-scope pairs alone, and the retry assembled from the
    first pass equals the re-encode and gives the oracle's decisions."""
    from .test_hr_scoped_config import CELL, SEED, _child

    got = _child(POOL_RETRY % {"cell": CELL, "seed": SEED,
                               "tmp": str(tmp_path / "tree.yml")})
    assert got["overcap"] > 0
    assert got["kept"] == got["overcap"]
    assert got["retried"] == got["overcap"]


# ------------------------------------------------- owner-bit packer parity


def _owner_bits_encoder():
    """A native encoder over an HR-scoped tree (hrv vocab non-empty) whose
    vocab the fuzz below overrides per case."""
    import synth
    from access_control_srv_tpu.ops.compile import compile_policies

    if not native.available():
        pytest.skip(f"native encoder unavailable: {native.build_error()}")
    engine, _ = synth.stress_engine(600, scoped=True)
    compiled = compile_policies(engine.policy_sets, engine.urns)
    return native.NativeBatchEncoder(compiled), compiled


def test_owner_bits_native_matches_python_packer_on_wire_traffic():
    """End-to-end parity on real wire traffic: the C++ packer's
    r_own_runs/r_own_bits equal ops/encode.pack_owner_bitplanes over the
    same raw arrays."""
    from access_control_srv_tpu.ops import encode as pyenc

    enc, compiled = _owner_bits_encoder()
    orgs = [f"org-{j}" for j in range(5)]
    rng = np.random.default_rng(3)
    reqs = []
    for i in range(48):
        k = int(rng.integers(64))
        tree = [{"id": orgs[0], "role": f"role-{i % 97}",
                 "children": [{"id": o}
                              for o in orgs[1:1 + int(rng.integers(4))]]}]
        from .utils import URNS, build_request

        reqs.append(build_request(
            subject_id=f"u{i}", subject_role=f"role-{i % 97}",
            role_scoping_entity=(
                "urn:restorecommerce:acs:model:organization.Organization"
            ),
            role_scoping_instance=orgs[int(rng.integers(3))],
            resource_type=(
                f"urn:restorecommerce:acs:model:stress{k}.Stress{k}"
            ),
            resource_id=f"res-{i}", action_type=URNS["read"],
            owner_indicatory_entity=(
                "urn:restorecommerce:acs:model:organization.Organization"
            ),
            owner_instance=orgs[int(rng.integers(5))],
            hierarchical_scopes=tree,
        ))
    messages = [request_to_pb(r).SerializeToString() for r in reqs]
    batch = enc.encode_wire(messages)
    raw = {k: v for k, v in batch.arrays.items() if not k.startswith("r_own")}
    ref = pyenc.pack_owner_bitplanes(raw, compiled)
    np.testing.assert_array_equal(ref["r_own_runs"],
                                  batch.arrays["r_own_runs"])
    np.testing.assert_array_equal(ref["r_own_bits"],
                                  batch.arrays["r_own_bits"])


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_owner_bits_fuzz_matches_python_packer(seed):
    """Structure-free fuzz: random raw row arrays (random shapes, random
    ids including ABSENT) and a random role-scope vocab — the C++ packer
    must be bit-identical to the Python packer on every case, including
    wide-entry layouts (ebits > 32)."""
    from types import SimpleNamespace

    from access_control_srv_tpu.ops import encode as pyenc
    from access_control_srv_tpu.ops.encode import alloc_row_arrays

    enc, _ = _owner_bits_encoder()
    rng = np.random.default_rng(seed)
    B = int(rng.integers(1, 24))
    caps = {
        "NR": 4, "NI": int(rng.integers(1, 6)), "NP": 8, "NSUB": 8,
        "NACT": 4, "NOP": int(rng.integers(1, 4)),
        "NOWN": int(rng.integers(1, 5)), "NRA": int(rng.integers(1, 10)),
        "NHR": int(rng.integers(1, 34)), "NROLE": 4, "NACLE": 4,
        "NACLI": 8, "NHRR": 8,
    }
    a = alloc_row_arrays(B, caps)

    def rand_into(name, lo=-1, hi=12):
        arr = a[name]
        arr[...] = rng.integers(lo, hi, size=arr.shape).astype(arr.dtype)

    for name in ("r_inst_run", "r_inst_owner_ent", "r_inst_owner_inst",
                 "r_op_vals", "r_op_owner_ent", "r_op_owner_inst",
                 "r_ra3", "r_ra2", "r_hr"):
        rand_into(name)
    a["r_inst_run"][...] = rng.integers(-1, caps["NR"],
                                        size=a["r_inst_run"].shape)
    for name in ("r_inst_valid", "r_inst_present", "r_inst_has_owners",
                 "r_op_present", "r_op_has_owners"):
        a[name][...] = rng.integers(0, 2, size=a[name].shape).astype(bool)

    # random vocab, sized to also exercise the multi-word layout:
    # ebits = 2*(nru+NOP) can exceed 32 when NI (hence nru) is large
    RV = int(rng.integers(1, 40))
    hrv_role = rng.integers(-1, 12, size=RV).astype(np.int32)
    hrv_scope = rng.integers(0, 12, size=RV).astype(np.int32)
    enc._hrv_role = np.ascontiguousarray(hrv_role)
    enc._hrv_scope = np.ascontiguousarray(hrv_scope)
    fake_compiled = SimpleNamespace(arrays={
        "hrv_role": hrv_role, "hrv_scope": hrv_scope,
        "t_has_scoping": np.array([True]),
        "t_n_subjects": np.array([1]),
    })
    ref = pyenc.pack_owner_bitplanes(a, fake_compiled)
    got = enc.owner_bits_native(a, B)
    np.testing.assert_array_equal(ref["r_own_runs"], got["r_own_runs"],
                                  err_msg=f"seed {seed} runs")
    np.testing.assert_array_equal(ref["r_own_bits"], got["r_own_bits"],
                                  err_msg=f"seed {seed} bits")


def _relation_encoder():
    from .utils import fixture

    from access_control_srv_tpu.core import AccessController, populate

    engine = AccessController()
    populate(engine, fixture("relation_policies.yml"))
    compiled = compile_policies(engine.policy_sets, engine.urns)
    assert compiled.supported
    enc = native.NativeBatchEncoder(compiled)
    assert enc.needs_relation_bits
    return engine, enc, compiled


def test_relation_bits_wire_differential():
    """Relation-bearing wire traffic: the C++ packer's
    r_rel_runs/r_rel_bits (built from NATIVE-space verdict tables) equal
    the Python encoder's (HOST-space tables) on the same wire bytes —
    the two interners assign different ids post-preload, so this parity
    also pins the id-space translation in native_relation_tables."""
    from access_control_srv_tpu.ops.encode import _CAPS_FLOOR
    from access_control_srv_tpu.srv.relations import RelationTupleStore

    from .utils import URNS, build_request

    engine, enc, compiled = _relation_encoder()
    doc = "urn:restorecommerce:acs:model:document.Document"
    store = RelationTupleStore()
    store.set_rewrite(doc, "viewer",
                      [("this",), ("computed_userset", "owner")])
    store.create([
        (doc, "doc1", "owner", "alice"),
        (doc, "doc2", "viewer", "bob"),
        (doc, "doc3", "viewer",
         {"object": {"entity": "group", "id": "g"}, "relation": "member"}),
        ("group", "g", "member", "carol"),
    ])
    reqs = [
        build_request(subject_id=s, resource_type=doc, resource_id=r,
                      action_type=URNS["read"])
        for s in ("alice", "bob", "carol", "mallory")
        for r in ("doc1", "doc2", "doc3", ["doc1", "doc3"])
    ]
    messages, twins = wire_roundtrip(reqs)
    nb = enc.encode_wire(
        messages, relation_tables=enc.native_relation_tables(store)
    )
    pb_batch = encode_requests(
        twins, compiled, caps=_CAPS_FLOOR,
        relation_tables=store.tables_for(compiled),
    )
    assert np.array_equal(nb.eligible, pb_batch.eligible)
    for name in nb.arrays:
        assert np.array_equal(nb.arrays[name], pb_batch.arrays[name]), name

    # and through the kernel: wire decisions == the scalar oracle's walk
    engine.relation_store = store
    kernel = DecisionKernel(compiled)
    decision, _, status = kernel.evaluate(nb)
    n = 0
    for b, req in enumerate(twins):
        if not nb.eligible[b] or status[b] != 200:
            continue
        assert decision[b] == DEC_CODE[engine.is_allowed(req).decision], b
        n += 1
    assert n >= len(reqs) - 1


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_relation_bits_fuzz_matches_python_packer(seed):
    """Structure-free fuzz: random raw row arrays and random (valid)
    flat verdict tables — the C++ relation packer must be bit-identical
    to ops/relation.pack_relation_bitplanes on every case, including the
    multi-word layout (ebits = 2*nru > 32, forced on the later seeds by
    wide NR/NI so rows carry >16 distinct instance runs)."""
    from types import SimpleNamespace

    from access_control_srv_tpu.ops import relation as rel
    from access_control_srv_tpu.ops.encode import (
        alloc_row_arrays,
        owner_bit_layout,
    )

    _, enc, real_compiled = _relation_encoder()
    rng = np.random.default_rng(seed)
    wide = seed >= 2
    B = int(rng.integers(8, 24)) if wide else int(rng.integers(1, 16))
    caps = dict(
        NR=34 if wide else int(rng.integers(1, 8)),
        NI=48 if wide else int(rng.integers(1, 8)),
        NP=8, NSUB=8, NACT=4, NOP=2, NOWN=2, NRA=2, NHR=2, NROLE=4,
        NACLE=2, NACLI=2, NHRR=2,
    )
    a = alloc_row_arrays(B, caps)
    a["r_inst_run"][...] = rng.integers(-1, caps["NR"],
                                        size=a["r_inst_run"].shape)
    a["r_inst_valid"][...] = rng.integers(
        0, 2, size=a["r_inst_valid"].shape).astype(bool)
    a["r_ent_vals"][...] = rng.integers(-1, 12, size=a["r_ent_vals"].shape)
    a["r_inst_id"][...] = rng.integers(-1, 12, size=a["r_inst_id"].shape)
    a["r_subject_id"][...] = rng.integers(-1, 12,
                                          size=a["r_subject_id"].shape)

    RELV = int(rng.integers(1, 7))
    # random but VALID flat tables: per-(vocab, plane) sorted unique
    # object-key segments, plus one globally sorted (row<<32)|subject
    # membership array over ids drawn from the same [0, 12) pool
    segs = []
    for _ in range(2 * RELV):
        k = int(rng.integers(0, 5))
        keys = np.unique(
            (rng.integers(0, 12, size=k).astype(np.int64) << 32)
            | rng.integers(0, 12, size=k).astype(np.int64)
        )
        segs.append(np.sort(keys))
    obj_offs = np.zeros((2 * RELV + 1,), np.int64)
    obj_offs[1:] = np.cumsum([s.shape[0] for s in segs])
    obj_keys = (np.concatenate(segs) if segs
                else np.zeros((0,), np.int64)).astype(np.int64)
    pairs = []
    for row in range(obj_keys.shape[0]):
        for subj in np.unique(rng.integers(0, 12,
                                           size=int(rng.integers(0, 4)))):
            pairs.append((np.int64(row) << 32) | np.int64(subj))
    tables = {
        "obj_offs": obj_offs,
        "obj_keys": obj_keys,
        "pairs": np.sort(np.array(pairs, np.int64))
        if pairs else np.zeros((0,), np.int64),
    }

    fake_compiled = SimpleNamespace(arrays={
        "relv_path": np.zeros((RELV,), np.int32),
        "t_rel_idx": np.array([0], np.int32),
    })
    ref = rel.pack_relation_bitplanes(a, fake_compiled, tables)
    enc.compiled = fake_compiled
    try:
        got = enc.relation_bits_native(a, B, tables=tables)
    finally:
        enc.compiled = real_compiled
    if wide:
        nru = ref["r_rel_runs"].shape[1]
        ebits, epw, _, _ = owner_bit_layout(RELV, nru, 0)
        assert ebits > 32 and epw == 0, "wide seeds must hit multi-word"
    np.testing.assert_array_equal(ref["r_rel_runs"], got["r_rel_runs"],
                                  err_msg=f"seed {seed} runs")
    np.testing.assert_array_equal(
        ref["r_rel_bits"], got["r_rel_bits"].view(np.int32),
        err_msg=f"seed {seed} bits")
