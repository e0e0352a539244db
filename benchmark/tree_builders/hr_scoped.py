"""``hr_scoped``: restorecommerce's role-scope fixture
(access-control-srv ``test/fixtures/roleScopes.yml``, with the
``hierarchicalRoleScoping: 'false'`` rule of
``test/fixtures/hierarchicalScopes_disabled.yml``) repeated per resource
entity: one deny-overrides policy set holding, per entity of
``row_kinds/hr_scoped.ENTITIES``, a permit-overrides policy without a
target of 7 rules:

- ``admin-r-id`` x {read, create, modify, delete}: PERMIT, scoped to the
  organization entity, hierarchical matching on;
- ``user-r-id`` x read: PERMIT, scoped the same way;
- ``user-r-id`` x modify: PERMIT, scoped, hierarchical matching off
  (only a direct owner match counts);
- a fallback DENY targeted at the entity alone (untargeted, it would deny
  every request under the set's deny-overrides).

Nothing is drawn: ``g`` is unused, the tree is the same on every seed.
"""

from __future__ import annotations

import numpy as np

import parts
import traffic

ALG = "urn:oasis:names:tc:xacml:3.0:rule-combining-algorithm:{}-overrides"
HR_SCOPING = "urn:restorecommerce:acs:names:hierarchicalRoleScoping"


def _rule(rule_id: str, effect: str, entity: str, subjects: list | None,
          action: str | None = None) -> dict:
    target = {"resources": [{"id": traffic.ENTITY, "value": entity}]}
    if subjects:
        target["subjects"] = subjects
    if action:
        target["actions"] = [{"id": traffic.ACTION_ID, "value": action}]
    return {"id": rule_id, "target": target, "effect": effect}


def build(params: dict, g: np.random.Generator) -> list:
    kind = parts.load("row_kinds", "hr_scoped")
    policies = []
    for entity in kind.ENTITIES:
        name = entity.rsplit(".", 1)[-1].lower()
        rules = []
        for role, grants in kind.GRANTS.items():
            for action, hierarchical in grants.items():
                subjects = [{"id": traffic.ROLE, "value": role},
                            {"id": kind.SCOPING_ENTITY, "value": traffic.ORG}]
                if not hierarchical:
                    subjects.append({"id": HR_SCOPING, "value": "false"})
                verb = action.rsplit(":", 1)[-1]
                rules.append(_rule(f"{name}-{role}-{verb}", "PERMIT", entity,
                                   subjects, action))
        rules.append(_rule(f"{name}-fallback", "DENY", entity, None))
        policies.append({"id": f"{name}-policy",
                         "combining_algorithm": ALG.format("permit"),
                         "rules": rules})
    return [{"id": "hr-scoped-set", "combining_algorithm": ALG.format("deny"),
             "policies": policies}]
