"""A test's tree builder: one permit-overrides policy set of ``policies``
policies, deny-overrides and permit-overrides in turn, each of ``rules``
role rules on one entity and one action; roles, entities, actions and
effects drawn from ``g``."""

from __future__ import annotations

import numpy as np

import traffic

ALGS = ["urn:oasis:names:tc:xacml:3.0:rule-combining-algorithm:"
        f"{name}-overrides" for name in ("deny", "permit")]
ENTITIES = [traffic.ORG, "urn:restorecommerce:acs:model:user.User"]


def build(params: dict, g: np.random.Generator) -> list:
    policies = []
    for p in range(params["policies"]):
        rules = []
        for q in range(params["rules"]):
            role = f"role-{int(g.integers(params['roles']))}"
            entity = ENTITIES[int(g.integers(len(ENTITIES)))]
            action = traffic.ACTIONS[int(g.integers(len(traffic.ACTIONS)))]
            rules.append({
                "id": f"dummy-rule-{p}-{q}",
                "target": {
                    "subjects": [{"id": traffic.ROLE, "value": role}],
                    "resources": [{"id": traffic.ENTITY, "value": entity}],
                    "actions": [{"id": traffic.ACTION_ID, "value": action}],
                },
                "effect": "PERMIT" if g.random() < 0.6 else "DENY",
            })
        policies.append({"id": f"dummy-policy-{p}",
                         "combining_algorithm": ALGS[p % 2], "rules": rules})
    return [{"id": "dummy-set", "combining_algorithm": ALGS[1],
             "policies": policies}]
