"""The ACL-shared configuration's plain reference
(``benchmark/references/hr_acl.py``) against the repo's golden ACL cases
(``tests/test_acl.py``, the upstream's test/acl.spec.ts:87-410 over
``tests/fixtures/acl_policies.yml``): the same 14 requests, built in the
shape of ``tests/utils.build_request`` as plain dicts, with their expected
decisions.  Its control ``acl_open`` (verifyACL always passing) diverges
from it on the configuration's small pool, and on the golden cases it
turns the DENYs verifyACL decides into PERMITs."""

from __future__ import annotations

import importlib.util
import os
import sys

import pytest
import yaml

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
if BENCH not in sys.path:
    sys.path.append(BENCH)


def _load(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


REF = _load(os.path.join(BENCH, "references", "hr_acl.py"), "bench_ref_hr_acl")
U = REF.U
MODEL = "urn:restorecommerce:acs:model:"
ORG, USER = MODEL + "organization.Organization", MODEL + "user.User"
BUCKET = MODEL + "bucket.Bucket"
ACTION = "urn:restorecommerce:acs:names:action:"
SUBJECT_ID = "urn:oasis:names:tc:xacml:1.0:subject:subject-id"
CELL = "hr_acl.batch4096"


def fixture_tree() -> list:
    with open(os.path.join(REPO, "tests", "fixtures", "acl_policies.yml")) as f:
        return yaml.safe_load(f)["policy_sets"]


def _acl(entity: str, instances: list) -> dict:
    return {"id": U["aclIndicatoryEntity"], "value": entity,
            "attributes": [{"id": U["aclInstance"], "value": v}
                           for v in instances]}


def request(action: str, instance: str, owner: str, role: str = "Admin",
            acls: list | None = None) -> REF.Row:
    """Subject Alice holding ``role`` scoped to ``instance`` of the
    organization entity, the HR tree SuperOrg1 -> Org1 -> Org2 -> Org3
    carrying ``role``; one Bucket ``test`` owned by ``owner`` and shared
    through ``acls`` ((entity, instances) pairs)."""
    target = {"subjects": [{"id": U["role"], "value": role},
                           {"id": SUBJECT_ID, "value": "Alice"}],
              "resources": [{"id": U["entity"], "value": BUCKET},
                            {"id": U["resourceID"], "value": "test"}],
              "actions": [{"id": U["actionID"], "value": ACTION + action}]}
    meta = {"owners": [{"id": U["ownerEntity"], "value": ORG, "attributes": [
                {"id": U["ownerInstance"], "value": owner}]}],
            "acls": [_acl(e, v) for e, v in acls or []]}
    scopes = [{"id": "SuperOrg1", "role": role, "children": [
        {"id": "Org1", "children": [
            {"id": "Org2", "children": [{"id": "Org3"}]}]}]}]
    subject = {"id": "Alice", "hierarchical_scopes": scopes,
               "role_associations": [{"role": role, "attributes": [
                   {"id": U["roleScopingEntity"], "value": ORG,
                    "attributes": [{"id": U["roleScopingInstance"],
                                    "value": instance}]}]}]}
    return REF.Row(target, {"subject": subject,
                            "resources": [{"id": "test", "meta": meta}]})


ORGS_123 = ["Org1", "Org2", "Org3"]
MIXED_ALICE = [(ORG, ["Org1", "Org2"]), (USER, ["SubjectID1", "Alice"])]
CASES = {
    # create: every ACL instance inside the subject's HR scopes
    "permit_create_valid_acl_instances": ("PERMIT", dict(
        action="create", instance="SuperOrg1", owner="SuperOrg1",
        acls=[(ORG, ORGS_123)])),
    "deny_create_invalid_acl_instances": ("DENY", dict(
        action="create", instance="SuperOrg1", owner="SuperOrg1",
        acls=[(ORG, ["Org1", "Org4"])])),
    "permit_create_subject_id_acl": ("PERMIT", dict(
        action="create", instance="SuperOrg1", owner="SuperOrg1",
        acls=[(USER, ["SubjectID1", "SubjectID2"])])),
    "permit_create_mixed_acl_valid_orgs": ("PERMIT", dict(
        action="create", instance="SuperOrg1", owner="SuperOrg1",
        acls=[(ORG, ORGS_123), (USER, ["SubjectID1", "SubjectID2"])])),
    "deny_create_mixed_acl_invalid_orgs": ("DENY", dict(
        action="create", instance="SuperOrg1", owner="SuperOrg1",
        acls=[(ORG, ["Org1", "Org4"]), (USER, ["SubjectID1", "SubjectID2"])])),
    # modify / delete: a subject scope instance, or the subject's id
    "permit_modify_reduced_valid_acl": ("PERMIT", dict(
        action="modify", instance="Org1", owner="Org1",
        acls=[(ORG, ["Org1"])])),
    "permit_modify_subject_id_in_acl": ("PERMIT", dict(
        action="modify", instance="Org4", owner="Org4", acls=MIXED_ALICE)),
    "deny_modify_invalid_acl": ("DENY", dict(
        action="modify", instance="SuperOrg1", owner="SuperOrg1",
        acls=[(ORG, ["Org1", "Org4"])])),
    "permit_delete_valid_acl": ("PERMIT", dict(
        action="delete", instance="Org1", owner="Org1",
        acls=[(ORG, ["Org1", "Org2"])])),
    "permit_delete_subject_id_in_acl": ("PERMIT", dict(
        action="delete", instance="Org4", owner="Org4", acls=MIXED_ALICE)),
    "deny_delete_no_scope_or_subject_in_acl": ("DENY", dict(
        action="delete", instance="Org4", owner="Org4",
        acls=[(ORG, ["Org1", "Org2"]), (USER, ["SubjectID1"])])),
    # read by the unscoped SimpleUser rule
    "permit_read_simple_user_valid_acl": ("PERMIT", dict(
        action="read", instance="Org1", owner="Org1", role="SimpleUser",
        acls=[(ORG, ORGS_123)])),
    "permit_read_simple_user_subject_id_in_acl": ("PERMIT", dict(
        action="read", instance="Org4", owner="Org4", role="SimpleUser",
        acls=MIXED_ALICE)),
    "deny_read_simple_user_scope_not_in_acl": ("DENY", dict(
        action="read", instance="Org4", owner="Org1", role="SimpleUser",
        acls=[(ORG, ORGS_123)])),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_golden_acl_case(case):
    expected, kwargs = CASES[case]
    assert REF.Reference(fixture_tree()).decide(request(**kwargs)) == expected


def test_acl_open_permits_what_verify_acl_denies():
    """With verifyACL open every golden case is PERMITted: each DENY there
    is verifyACL's alone."""
    tree = fixture_tree()
    for case, (_, kwargs) in CASES.items():
        assert REF.AclOpenReference(tree).decide(request(**kwargs)) \
            == "PERMIT", case


@pytest.mark.parametrize("seed", [2**31 + 12345, 3, 4])
def test_acl_open_is_not_correct_on_the_small_pool(seed):
    import control
    import harness

    cell = harness.load_cell(CELL)
    small = harness.load_cell(
        CELL, scale={"config": cell.config.get("small", {}),
                     "mix": cell.mix["small"]})
    assert small.config["control"] == "acl_open"
    checks = control.control_checks(small, seed)
    assert not harness.passes(checks), checks
    # verifyACL decides a fifth of the rows: far more than a rounding
    assert checks["wrong_decisions"]["value"] > 0.05 * 8 * 256


def _two_role_case():
    """Roles A (HR subtree Org1) and B (Org2), both scoped to the
    organization entity; Alice creates a Bucket shared with [Org1, Org2]."""
    row = request("create", "Org1", "Org1", acls=[(ORG, ["Org1", "Org2"])])
    row.subject["hierarchical_scopes"] = [{"id": "Org1", "role": "A"},
                                          {"id": "Org2", "role": "B"}]
    row.subject["role_associations"] = [
        {"role": role, "attributes": [
            {"id": U["roleScopingEntity"], "value": ORG, "attributes": [
                {"id": U["roleScopingInstance"], "value": inst}]}]}
        for role, inst in (("A", "Org1"), ("B", "Org2"))]
    return row


def row_target(row, part: str) -> list:
    return [{"id": k, "value": v} for k, v in getattr(row, part)]


@pytest.mark.parametrize("roles,passes", [(["A", "B"], True), (["B"], False)])
def test_create_carries_validated_instances_across_roles(roles, passes):
    """verifyACL.ts's create scan: Org1, validated under role A, stays
    validated under role B, where Org2 then passes, so a rule naming both
    roles passes; one naming B alone breaks at Org1.  The program's scalar
    oracle (core/verify_acl.py) agrees."""
    from access_control_srv_tpu.core.verify_acl import verify_acl_list
    from access_control_srv_tpu.models.model import Attribute, Request, Target

    from .utils import URNS

    subjects = [{"id": U["role"], "value": r} for r in roles] + [
        {"id": U["roleScopingEntity"], "value": ORG}]
    resources = [{"id": U["entity"], "value": BUCKET}]
    rule = REF.Rule({"id": "r", "effect": "PERMIT", "target": {
        "subjects": subjects, "resources": resources}})
    row = _two_role_case()
    assert rule.verify_acl(row) is passes
    target = Target(subjects=[Attribute(**a) for a in subjects],
                    resources=[Attribute(**a) for a in resources])
    request_target = Target(
        subjects=[Attribute(**a) for a in row_target(row, "subjects")],
        resources=[Attribute(**a) for a in row_target(row, "resources")],
        actions=[Attribute(**a) for a in row_target(row, "actions")])
    oracle = verify_acl_list(target, Request(target=request_target,
                                             context=row.context), URNS, None)
    assert oracle is passes
