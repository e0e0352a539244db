"""Synthetic policy trees and wire traffic, generated from a seed.

The tests and ``chip_smoke.py`` build their large trees and request
batches here, so every caller sees the same shapes:

- ``seed_engine()``: the boot seed policy set (``data/seed_data``) in an
  engine;
- ``stress_doc(n)`` / ``stress_engine(n)``: an ``n``-rule tree of
  role/entity/action-targeted rules, optionally role-scoped;
- ``store_docs(doc)``: such a tree as the policy store's three CRUD
  payload lists;
- ``serving_batch_msg(n, rng)``: ``n`` wire requests for the seed or the
  stress tree.
"""

from __future__ import annotations

import json
import os

REPO = os.path.dirname(os.path.abspath(__file__))

ORG = "urn:restorecommerce:acs:model:organization.Organization"
PO = "urn:oasis:names:tc:xacml:3.0:rule-combining-algorithm:permit-overrides"
DO = "urn:oasis:names:tc:xacml:3.0:rule-combining-algorithm:deny-overrides"


def seed_engine():
    from access_control_srv_tpu.core import AccessController, load_seed_files

    engine = AccessController()
    seed = os.path.join(REPO, "data", "seed_data")
    for ps in load_seed_files(
        os.path.join(seed, "policy_sets.yaml"),
        os.path.join(seed, "policies.yaml"),
        os.path.join(seed, "rules.yaml"),
    ):
        engine.update_policy_set(ps)
    return engine


def stress_doc(n_rules: int, scoped: bool = False, cacheable: bool = False,
                flip_every: int = 0):
    """The synthetic stress tree as a nested ``policy_sets`` document
    (the loader's file shape): deny-overrides set of permit-overrides
    policies, role/entity/action-targeted rules with interleaved
    PERMIT/DENY.  ``scoped=True`` adds a roleScopingEntity to every
    rule's role subject (stage B non-trivial tree-wide: the enterprise
    shape).  ``cacheable=True`` marks every rule
    ``evaluation_cacheable`` (the decision-cache warm-traffic shape).
    ``flip_every=N`` inverts the effect of every Nth rule — a shadow
    candidate tree: identical size class, known deliberate
    divergences."""
    from access_control_srv_tpu.models import Urns

    urns = Urns()
    n_policies = max(1, n_rules // 400)
    per_policy = n_rules // n_policies
    entities = [
        f"urn:restorecommerce:acs:model:stress{k}.Stress{k}" for k in range(64)
    ]
    actions = [urns["read"], urns["modify"], urns["create"], urns["delete"]]
    policies = []
    rid = 0
    for p in range(n_policies):
        rules = []
        for q in range(per_policy):
            entity = entities[(p * 31 + q) % len(entities)]
            subjects = [{"id": urns["role"], "value": f"role-{rid % 97}"}]
            if scoped:
                subjects.append({
                    "id": urns["roleScopingEntity"],
                    "value": ORG,
                })
            effect = "PERMIT" if rid % 3 else "DENY"
            if flip_every and rid % flip_every == 0:
                effect = "DENY" if effect == "PERMIT" else "PERMIT"
            rules.append(
                {
                    "id": f"r{rid}",
                    "target": {
                        "subjects": subjects,
                        "resources": [{"id": urns["entity"], "value": entity}],
                        "actions": [
                            {"id": urns["actionID"],
                             "value": actions[rid % len(actions)]}
                        ],
                    },
                    "effect": effect,
                    "evaluation_cacheable": cacheable,
                }
            )
            rid += 1
        policies.append(
            {"id": f"p{p}", "combining_algorithm": PO, "rules": rules}
        )
    doc = {
        "policy_sets": [
            {"id": "stress", "combining_algorithm": DO, "policies": policies}
        ]
    }
    return doc, rid


def stress_engine(n_rules: int, scoped: bool = False,
                   cacheable: bool = False):
    """``stress_doc`` loaded into an engine; see its docstring."""
    from access_control_srv_tpu.core import AccessController
    from access_control_srv_tpu.core.loader import load_policy_sets

    doc, rid = stress_doc(n_rules, scoped=scoped, cacheable=cacheable)
    engine = AccessController()
    for ps in load_policy_sets(doc):
        engine.update_policy_set(ps)
    return engine, rid


def store_docs(doc: dict) -> tuple[list, list, list]:
    """A nested ``policy_sets`` document (``stress_doc``'s shape) as the
    three flat CRUD payload lists the policy store seeds from (children
    referenced by id): policy sets, policies, rules."""
    policy_sets, policies, rules = [], [], []
    for ps in doc["policy_sets"]:
        policy_sets.append({**ps, "policies": [p["id"] for p in ps["policies"]]})
        for pol in ps["policies"]:
            policies.append({**pol, "rules": [r["id"] for r in pol["rules"]]})
            rules.extend(pol["rules"])
    return policy_sets, policies, rules


def serving_batch_msg(n, rng, wide=False, hr=False):
    """``n`` wire requests.  Default: seed-tree traffic (half super-admin).
    ``wide``: roles and entities over the stress tree's vocabulary plus
    ~10% unknown ones.  ``hr``: the same, HR-scoped (the
    enterprise shape): the role is scoped to org-0, whose subtree
    holds org-1..7, and each resource is owned by one of org-0..11, so
    about 2/3 of the owners fall inside the subject's scope."""
    from access_control_srv_tpu.models import Urns
    from access_control_srv_tpu.srv.gen import access_control_pb2 as pb

    urns = Urns()
    batch = pb.BatchRequest()
    orgs = [f"org-{j}" for j in range(12)]
    for i in range(n):
        if wide or hr:
            role = f"role-{int(rng.integers(108))}"
            k = int(rng.integers(72))
            entity = f"urn:restorecommerce:acs:model:stress{k}.Stress{k}"
        else:
            role = ("superadministrator-r-id" if i % 2 == 0
                    else f"role-{i % 7}")
            entity = ORG
        msg = batch.requests.add()
        msg.target.subjects.add(id=urns["role"], value=role)
        msg.target.subjects.add(id=urns["subjectID"], value=f"u{i}")
        msg.target.resources.add(id=urns["entity"], value=entity)
        msg.target.resources.add(id=urns["resourceID"], value=f"res-{i}")
        msg.target.actions.add(
            id=urns["actionID"],
            value=[urns["read"], urns["modify"], urns["create"],
                   urns["delete"]][i % 4],
        )
        if not hr:
            msg.context.subject.value = json.dumps({
                "id": f"u{i}",
                "role_associations": [{"role": role, "attributes": []}],
                "hierarchical_scopes": [],
            }).encode()
            continue
        msg.context.subject.value = json.dumps({
            "id": f"u{i}",
            "role_associations": [{"role": role, "attributes": [{
                "id": urns["roleScopingEntity"], "value": ORG,
                "attributes": [{"id": urns["roleScopingInstance"],
                                "value": orgs[0]}],
            }]}],
            "hierarchical_scopes": [{
                "id": orgs[0], "role": role,
                "children": [{"id": o} for o in orgs[1:8]],
            }],
        }).encode()
        owner = orgs[int(rng.integers(len(orgs)))]
        msg.context.resources.add().value = json.dumps({
            "id": f"res-{i}",
            "meta": {"owners": [{
                "id": urns["ownerIndicatoryEntity"], "value": ORG,
                "attributes": [{"id": urns["ownerInstance"], "value": owner}],
            }], "acls": []},
        }).encode()
    return batch
