"""End-to-end gRPC transport tests: a real grpc server + channel, protobuf
wire messages, the full five-service surface."""

import json
import os

import pytest

from access_control_srv_tpu.srv import Worker
from access_control_srv_tpu.srv.gen import access_control_pb2 as pb
from access_control_srv_tpu.srv.transport_grpc import GrpcClient, GrpcServer

from .utils import URNS

ORG = "urn:restorecommerce:acs:model:organization.Organization"
PO = "urn:oasis:names:tc:xacml:3.0:rule-combining-algorithm:permit-overrides"
SEED = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                    "data", "seed_data")


@pytest.fixture(scope="module")
def rig():
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
    yield worker, client
    client.close()
    server.stop()
    worker.stop()


def wire_request(role="superadministrator-r-id", action=None):
    action = action or URNS["read"]
    msg = pb.Request()
    msg.target.subjects.add(id=URNS["role"], value=role)
    msg.target.subjects.add(id=URNS["subjectID"], value="root")
    msg.target.resources.add(id=URNS["entity"], value=ORG)
    msg.target.resources.add(id=URNS["resourceID"], value="O1")
    msg.target.actions.add(id=URNS["actionID"], value=action)
    msg.context.subject.value = json.dumps(
        {
            "id": "root",
            "role_associations": [{"role": role, "attributes": []}],
            "hierarchical_scopes": [],
        }
    ).encode()
    entry = msg.context.resources.add()
    entry.value = json.dumps({"id": "O1", "meta": {"owners": []}}).encode()
    return msg


class _Batch:
    """A native wire result's batch: eligibility and the staging lease."""

    def __init__(self, eligible):
        self.eligible = eligible
        self.released = 0

    def release_staging(self):
        self.released += 1


class _Service:
    """Answers every fallback row with ``code``."""

    def __init__(self, code):
        self.code = code

    def is_allowed_batch(self, reqs, observe=True, deadline=None):
        from access_control_srv_tpu.models.model import (
            Decision,
            OperationStatus,
            Response,
        )

        return [Response(decision=Decision.DENY, evaluation_cacheable=True,
                         operation_status=OperationStatus(self.code, "fb"))
                for _ in reqs]


class _Worker:
    def __init__(self, code):
        from access_control_srv_tpu.srv.telemetry import Telemetry

        self.service = _Service(code)
        self.telemetry = Telemetry()


# rows the kernel did not serve, as the native wire result shows them
FALLBACK_KINDS = ("ineligible", "non-200", "out-of-table", "unparsable")


def native_result(n, layout):
    """``(messages, (batch, decision, cacheable, status))`` of ``n`` rows:
    served rows cycle through every (decision 0..2, cacheable -1/0/1);
    ``mixed`` puts each fallback kind at the first, middle and last rows,
    ``none-served`` makes every row a fallback row that parses."""
    import numpy as np

    decision = np.array([i % 3 for i in range(n)], np.int32)
    cacheable = np.array([i // 3 % 3 - 1 for i in range(n)], np.int32)
    status = np.full(n, 200, np.int32)
    eligible = np.ones(n, bool)
    messages = [wire_request().SerializeToString()] * n
    kinds = {}
    if layout == "mixed":
        for start in (0, n // 2, n - len(FALLBACK_KINDS)):
            for j in range(len(FALLBACK_KINDS)):
                if 0 <= start + j < n:
                    kinds[start + j] = FALLBACK_KINDS[(start + j) % 4]
    elif layout == "none-served":
        kinds = {b: FALLBACK_KINDS[b % 3] for b in range(n)}
    for b, kind in kinds.items():
        if kind == "ineligible":
            eligible[b] = False
        elif kind == "non-200":
            status[b] = 403
        elif kind == "out-of-table":
            decision[b] = 3 + b % 2
        else:
            eligible[b] = False
            messages[b] = b"\xff\xff"
    return messages, (_Batch(eligible), decision, cacheable, status)


def per_row_reply(worker, messages, out):
    """The per-row assembly the frame table replaces: a pb.Response and a
    decision count for every row."""
    from collections import Counter

    from access_control_srv_tpu.ops.compile import DECISION_NAMES
    from access_control_srv_tpu.srv.transport_grpc import (
        DECISION_TO_PB,
        PB_TO_DECISION,
        SHED_CODES,
        request_from_pb,
        resolve_fallback_rows,
    )

    batch, decision, cacheable, status = out
    responses = [None] * len(messages)
    fallback_rows, fallback_reqs = [], []
    for b, message in enumerate(messages):
        if (not batch.eligible[b] or status[b] != 200
                or int(decision[b]) not in DECISION_NAMES):
            try:
                req = request_from_pb(pb.Request.FromString(message))
            except Exception as err:
                responses[b] = pb.Response(
                    decision=pb.DENY,
                    operation_status=pb.OperationStatus(
                        code=500, message=str(err)),
                )
                continue
            fallback_rows.append(b)
            fallback_reqs.append(req)
            continue
        responses[b] = pb.Response(
            decision=DECISION_TO_PB[DECISION_NAMES[int(decision[b])]],
            evaluation_cacheable=False if cacheable[b] < 0
            else bool(cacheable[b]),
            operation_status=pb.OperationStatus(code=200, message="success"),
        )
    resolve_fallback_rows(worker, responses, fallback_rows, fallback_reqs,
                          None)
    counts = Counter(PB_TO_DECISION.get(r.decision, "DENY")
                     for r in responses)
    shed = bool(responses) and all(
        r.operation_status.code in SHED_CODES for r in responses)
    return responses, fallback_rows, dict(counts), shed


BYTE_IDENTITY = (
    [("pb", n, None) for n in (0, 1, 3, 512, 513, 2 * 512 + 17)]
    + [("table", n, layout) for n in (0, 1, 511, 512, 513, 4096)
       for layout in ("served", "mixed", "none-served", "shed")]
)


@pytest.mark.parametrize(
    "path,n,layout", BYTE_IDENTITY,
    ids=["-".join(str(p) for p in case if p is not None)
         for case in BYTE_IDENTITY])
def test_serialize_batch_response_byte_identity(path, n, layout):
    """Batch replies must be byte-identical to protobuf's own
    BatchResponse serialization of the per-row responses: the pb path's
    off-dispatch-thread serializer across the chunking threshold
    (length-delimited field-1 frames ARE the envelope), and the native
    path's frame table against per-row assembly — frames, payload,
    fallback rows, decision counts and the shed stamp."""
    from access_control_srv_tpu.srv.transport_grpc import (
        _frame,
        decode_native_rows,
        native_batch_reply,
        resolve_fallback_rows,
        serialize_batch_response,
    )

    if path == "pb":
        def row(i):
            return pb.Response(
                decision=[pb.PERMIT, pb.DENY, pb.INDETERMINATE][i % 3],
                evaluation_cacheable=bool(i % 2),
                operation_status=pb.OperationStatus(
                    code=200 if i % 5 else 403, message=f"m{i}" * (i % 7)
                ),
            )

        responses = [row(i) for i in range(n)]
        expected = pb.BatchResponse(responses=responses).SerializeToString()
        assert serialize_batch_response(responses) == expected, n
        # and the bytes parse back into the same rows
        parsed = pb.BatchResponse.FromString(
            serialize_batch_response(responses)
        )
        assert list(parsed.responses) == responses
        return

    code = 429 if layout == "shed" else 200
    messages, out = native_result(
        n, "none-served" if layout == "shed" else layout)
    responses, fallback_rows, counts, shed = per_row_reply(
        _Worker(code), messages, out)
    # the fallback service answers "fb", an unparsable row its error
    n_table = sum(r.operation_status.message == "success" for r in responses)

    rows = decode_native_rows(messages, out)
    resolve_fallback_rows(_Worker(code), rows.fallback, rows.fallback_rows,
                          rows.fallback_reqs, None)
    assert rows.fallback_rows == fallback_rows
    assert rows.n_served == n_table
    assert rows.frames() == [_frame(r) for r in responses]

    worker = _Worker(code)
    payload, shed_native = native_batch_reply(worker, messages, out, None,
                                              0.0)
    assert payload == pb.BatchResponse(
        responses=responses).SerializeToString()
    assert shed_native == shed == (layout == "shed" and n > 0)
    assert out[0].released == 1
    snap = worker.telemetry.snapshot()
    assert snap["decisions"] == counts
    assert snap["paths"].get("wire-frame-table", 0) == n_table
    assert snap["paths"].get("wire-frame-pb", 0) == n - n_table


def _raw_batch(messages):
    """A BatchRequest envelope from per-row message bytes, as sent."""
    from access_control_srv_tpu.srv.transport_grpc import _varint

    return b"".join(b"\x0a" + _varint(len(m)) + m for m in messages)


def test_stream_frames_match_unary_on_mixed_batch(rig):
    """IsAllowedStream and IsAllowedBatch assemble a mixed native batch
    (rows the kernel serves, rows it leaves to the service, a row that
    does not parse) into the same frames, and both count each row once
    under wire-frame-table or wire-frame-pb."""
    worker, client = rig
    assert worker.evaluator.native_active
    token_row = wire_request()
    token_row.context.subject.value = json.dumps(
        {"token": "unknown-token"}).encode()
    messages = []
    for i in range(24):
        msg = token_row if i % 5 == 4 else wire_request(
            role="superadministrator-r-id" if i % 2 else "nobody")
        messages.append(msg.SerializeToString())
    messages[11] = b"\xff\xff"
    raw = _raw_batch(messages)

    def paths():
        return worker.telemetry.paths.snapshot()

    before = paths()
    unary = client.channel.unary_unary(
        "/acstpu.AccessControlService/IsAllowedBatch")(raw, timeout=60)
    after_unary = paths()
    streamed = list(client.channel.stream_stream(
        "/acstpu.AccessControlService/IsAllowedStream")(
            iter([raw, raw]), timeout=60))
    after_stream = paths()
    assert streamed == [unary, unary]

    replies = pb.BatchResponse.FromString(unary).responses
    assert len(replies) == 24
    assert replies[11].operation_status.code == 500
    # the token rows (4, 9, 14, 19) and the unparsable row
    n_pb = 5
    n_table = 24 - n_pb

    def delta(after, base, key):
        return after.get(key, 0) - base.get(key, 0)

    assert delta(after_unary, before, "native-wire") == n_table
    assert delta(after_unary, before, "wire-frame-table") == n_table
    assert delta(after_unary, before, "wire-frame-pb") == n_pb
    assert delta(after_stream, after_unary, "wire-frame-table") == 2 * n_table
    assert delta(after_stream, after_unary, "wire-frame-pb") == 2 * n_pb


def test_is_allowed_over_wire(rig):
    _, client = rig
    response = client.is_allowed(wire_request())
    assert response.decision == pb.PERMIT
    assert response.operation_status.code == 200
    response = client.is_allowed(wire_request(role="nobody"))
    assert response.decision == pb.INDETERMINATE


def test_batch_over_wire(rig):
    _, client = rig
    batch = pb.BatchRequest(
        requests=[wire_request() for _ in range(8)]
        + [wire_request(role="nobody") for _ in range(8)]
    )
    response = client.is_allowed_batch(batch)
    decisions = [r.decision for r in response.responses]
    assert decisions[:8] == [pb.PERMIT] * 8
    assert decisions[8:] == [pb.INDETERMINATE] * 8


def test_what_is_allowed_over_wire(rig):
    _, client = rig
    rq = client.what_is_allowed(wire_request())
    assert rq.operation_status.code == 200
    assert len(rq.policy_sets) == 1
    assert rq.policy_sets[0].id == "global_policy_set"
    assert rq.policy_sets[0].policies[0].rules[0].id == "super_admin_rule"


def test_crud_over_wire(rig):
    worker, client = rig
    rule = pb.Rule(id="r_wire", effect="PERMIT")
    rule.target.subjects.add(id=URNS["role"], value="wire-role")
    result = client.crud("rule", "Create", pb.RuleList(items=[rule]))
    assert result.operation_status.code == 200

    policy = pb.Policy(id="p_wire", combining_algorithm=PO, rules=["r_wire"])
    client.crud("policy", "Create", pb.PolicyList(items=[policy]))
    pset = pb.PolicySet(id="ps_wire", combining_algorithm=PO,
                        policies=["p_wire"])
    client.crud("policy_set", "Create", pb.PolicySetList(items=[pset]))

    # hot-synced decision over the wire
    response = client.is_allowed(wire_request(role="wire-role"))
    assert response.decision == pb.PERMIT

    # read back
    read = client.crud("rule", "Read", pb.ReadRequest(ids=["r_wire"]),
                       pb.RuleListResponse)
    assert read.items[0].id == "r_wire"
    assert read.items[0].target.subjects[0].value == "wire-role"

    # delete flips the decision back
    client.crud("rule", "Delete", pb.DeleteRequest(ids=["r_wire"]))
    response = client.is_allowed(wire_request(role="wire-role"))
    assert response.decision == pb.INDETERMINATE


def test_command_and_health_over_wire(rig):
    _, client = rig
    assert client.health() == "SERVING"
    version = client.command("version")
    assert version["version"]


def test_what_is_allowed_batch_over_wire(rig):
    worker, client = rig
    batch = pb.BatchRequest()
    for role in ("superadministrator-r-id", "nobody"):
        batch.requests.add().CopyFrom(wire_request(role=role))
    resp = client._call("acstpu.AccessControlService", "WhatIsAllowedBatch",
                        batch, pb.BatchReverseQuery)
    assert len(resp.responses) == 2
    # per-row parity with the single-request endpoint
    for i, role in enumerate(("superadministrator-r-id", "nobody")):
        single = client.what_is_allowed(wire_request(role=role))
        assert resp.responses[i].SerializeToString() == \
            single.SerializeToString()


def test_meta_timestamps_over_wire(rig):
    worker, client = rig
    rule = pb.Rule(id="r_ts_wire", effect="PERMIT")
    client.crud("rule", "Create", pb.RuleList(items=[rule]))
    read = client.crud("rule", "Read", pb.ReadRequest(ids=["r_ts_wire"]),
                       pb.RuleListResponse)
    meta = read.items[0].meta
    assert meta.created > 0 and meta.modified >= meta.created
