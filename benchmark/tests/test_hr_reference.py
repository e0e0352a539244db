"""The HR-scoped configuration's plain reference
(``references/hr_scoped.py``) against the repo's golden role-scope cases
(``tests/test_core_role_scopes.py`` over ``tests/fixtures/role_scopes.yml``
and ``hr_disabled.yml``), on the parts it supports: no operation targets,
no policy targets, no conditions.  Requests are built in the shape of
``tests/utils.build_request`` (the upstream's ``test/utils.ts``
buildRequest) as plain dicts, so nothing of the program is imported.  Its
two controls diverge from it on the configuration's small pool."""

from __future__ import annotations

import os

import pytest
import yaml

from conftest import BIG_SEED, REPO, SMALL, SPEC

import control
import harness
import parts

CELL = "hr_scoped.batch4096"
REF = parts.load("references", "hr_scoped")
U = REF.U
MODEL = "urn:restorecommerce:acs:model:"
ORG, LOC = MODEL + "organization.Organization", MODEL + "location.Location"
ACTION = "urn:restorecommerce:acs:names:action:"
SUBJECT_ID = "urn:oasis:names:tc:xacml:1.0:subject:subject-id"


def fixture_tree(name: str) -> list:
    """The fixture's policy sets without what the reference refuses:
    policies with targets and rules on operations."""
    with open(os.path.join(REPO, "tests", "fixtures", name)) as f:
        sets = yaml.safe_load(f)["policy_sets"]
    for ps in sets:
        ps["policies"] = [pol for pol in ps["policies"]
                          if not pol.get("target")]
        for pol in ps["policies"]:
            pol["rules"] = [
                rule for rule in pol["rules"]
                if all(a["id"] != U["operation"] for a in
                       (rule.get("target") or {}).get("resources") or [])]
    return sets


def request(role: str, resource_types: list, resource_ids: list,
            action: str, owners: list, instance: str = "Org1",
            scopes: list | None = None) -> REF.Row:
    """A request as the golden cases build it: subject ``ada`` holding
    ``role`` scoped to ``instance`` of the organization entity, one
    organization owner a resource, the default HR tree SuperOrg1 -> Org1
    -> Org2 -> Org3 carrying ``role``."""
    resources = []
    for entity, rid in zip(resource_types, resource_ids):
        resources += [{"id": U["entity"], "value": entity},
                      {"id": U["resourceID"], "value": rid}]
    target = {"subjects": [{"id": U["role"], "value": role},
                           {"id": SUBJECT_ID, "value": "ada"}],
              "resources": resources,
              "actions": [{"id": U["actionID"], "value": ACTION + action}]}
    ctx_resources = [
        {"id": rid, "meta": {"acls": [], "owners": [
            {"id": U["ownerEntity"], "value": ORG, "attributes": [
                {"id": U["ownerInstance"], "value": owner}]}]}}
        for rid, owner in zip(resource_ids, owners)]
    if scopes is None:
        scopes = [{"id": "SuperOrg1", "role": role, "children": [
            {"id": "Org1", "children": [
                {"id": "Org2", "children": [{"id": "Org3"}]}]}]}]
    subject = {"id": "ada", "hierarchical_scopes": scopes,
               "role_associations": [{"role": role, "attributes": [
                   {"id": U["roleScopingEntity"], "value": ORG,
                    "attributes": [{"id": U["roleScopingInstance"],
                                    "value": instance}]}]}]}
    return REF.Row(target, {"subject": subject, "resources": ctx_resources})


CASES = {
    # TestRoleScopes
    "permit_member_read_location": (
        "role_scopes.yml", "PERMIT",
        dict(role="member", resource_types=[LOC], resource_ids=["L1"],
             action="read", owners=["Org1"])),
    "permit_multi_entity_read": (
        "role_scopes.yml", "PERMIT",
        dict(role="member", resource_types=[LOC, ORG],
             resource_ids=["L1", "O1"], action="read",
             owners=["Org1", "Org1"])),
    "deny_multi_entity_owner_mismatch": (
        "role_scopes.yml", "DENY",
        dict(role="member", resource_types=[LOC, ORG],
             resource_ids=["L1", "O1"], action="read",
             owners=["Org1", "otherOrg"])),
    "deny_member_modify_location": (
        "role_scopes.yml", "DENY",
        dict(role="member", resource_types=[LOC], resource_ids=["L1"],
             action="modify", owners=["Org1"])),
    "permit_manager_modify_location": (
        "role_scopes.yml", "PERMIT",
        dict(role="manager", resource_types=[LOC], resource_ids=["L1"],
             action="modify", owners=["Org1"], instance="SuperOrg1")),
    "deny_manager_foreign_org": (
        "role_scopes.yml", "DENY",
        dict(role="manager", resource_types=[LOC], resource_ids=["L1"],
             action="modify", owners=["Org1"], instance="Org2",
             scopes=[{"id": "Org2", "children": [{"id": "Org3"}]}])),
    # TestHRDisabled
    "permit_direct_scope": (
        "hr_disabled.yml", "PERMIT",
        dict(role="member", resource_types=[LOC], resource_ids=["L1"],
             action="read", owners=["Org1"])),
    "deny_hierarchical_scope_disabled": (
        "hr_disabled.yml", "DENY",
        dict(role="member", resource_types=[LOC], resource_ids=["L1"],
             action="read", owners=["Org2"])),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_golden_role_scope_case(case):
    fixture, expected, kwargs = CASES[case]
    assert REF.Reference(fixture_tree(fixture)).decide(
        request(**kwargs)) == expected


def test_the_hierarchical_step_decides_the_golden_cases():
    """Without the hierarchical step the SuperOrg1-scoped manager loses
    the PERMIT its HR tree grants; with the scope check open the foreign
    owner gets one."""
    tree = fixture_tree("role_scopes.yml")
    _, _, kwargs = CASES["permit_manager_modify_location"]
    assert REF.DirectOnlyReference(tree).decide(request(**kwargs)) == "DENY"
    _, _, kwargs = CASES["deny_manager_foreign_org"]
    assert REF.ScopeOpenReference(tree).decide(request(**kwargs)) == "PERMIT"


def test_conftest_takes_the_small_size():
    assert SMALL[CELL] == {"config": {}, "mix": {"batch": 256}}


@pytest.mark.parametrize("name", sorted(REF.CONTROLS))
def test_controls_diverge_on_the_small_pool(name):
    cell = harness.load_cell(CELL, SPEC, SMALL[CELL])
    cell.config["control"] = name
    checks = control.control_checks(cell, BIG_SEED)
    assert not harness.passes(checks), checks
    assert checks["wrong_decisions"]["value"] > 0
