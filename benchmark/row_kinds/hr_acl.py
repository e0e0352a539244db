"""``hr_acl``: the rows of ``hr_scoped`` (its subjects, organization
forest, HR trees, owners and rank dealing, imported from
``row_kinds/hr_scoped.py``), whose context resources are shared through
ACLs as restorecommerce's ``test/acl.spec.ts`` shares them: ``meta.acls``
with an ``aclIndicatoryEntity`` per shared entity and its ``aclInstance``s.

- With probability ``acl_share`` a resource carries ACLs; the rest carry
  none (verifyACL's early all-clear).
- Every ACL-bearing resource has one ``organization.Organization`` entry of
  ``acl_org_instances`` instances (uniform), placed in thirds
  (``acl_placement``) against the user's subtrees of the roles the row's
  action is granted to: all inside; mixed, at least one inside and one
  outside (2 instances at least); all outside.  A user with no granted
  role for the action has no inside: every instance is outside.
- With probability ``acl_user_share`` a second ``user.User`` entry of
  ``acl_user_ids`` user ids follows; the requesting subject is among them
  with probability ``acl_subject_in_user_acl``.

Instances of one entry are distinct where the pool they come from allows.
No row goes over the encoder's floor ACL caps (4 entities, 8 instances).
"""

from __future__ import annotations

import numpy as np

import parts
import traffic

HR = parts.load("row_kinds", "hr_scoped")
ACL_ENTITY = "urn:restorecommerce:acs:names:aclIndicatoryEntity"
ACL_INSTANCE = "urn:restorecommerce:acs:names:aclInstance"
INSIDE, MIXED, OUTSIDE = range(3)


def span(text: str) -> tuple:
    """``"1-8"`` -> (1, 8)."""
    lo, hi = (int(v) for v in text.split("-"))
    return lo, hi


def inside(dep, user: int, action: str) -> list:
    """The organizations in the user's subtrees of the roles granted
    ``action``, in node order."""
    held = set()
    for role, i in dep.scopes[user]:
        if action in HR.GRANTS[role]:
            held.update(dep.forest.subtree[i])
    return sorted(held)


def pick(g: np.random.Generator, pool: list, k: int) -> list:
    """``k`` of ``pool``, distinct where it holds ``k``."""
    idx = g.choice(len(pool), size=k, replace=len(pool) < k)
    return [pool[int(i)] for i in idx]


def org_instances(dep, user: int, action: str, g: np.random.Generator,
                  p: dict) -> list:
    lo, hi = span(p["acl_org_instances"])
    place = int(g.integers(3))
    mine = inside(dep, user, action)
    if not mine:
        place = OUTSIDE
    if place == MIXED:
        lo = max(lo, 2)
    k = int(g.integers(lo, hi + 1))
    outside = dep.outside(user)
    if place == INSIDE:
        nodes = pick(g, mine, k)
    elif place == OUTSIDE:
        nodes = pick(g, outside, k)
    else:
        n_in = int(g.integers(1, k))
        nodes = pick(g, mine, n_in) + pick(g, outside, k - n_in)
        nodes = [nodes[int(i)] for i in g.permutation(k)]
    ids = dep.forest.ids
    return [ids[i] if i < len(ids) else "org-none" for i in nodes]


def user_ids(user: int, g: np.random.Generator, p: dict) -> list:
    lo, hi = span(p["acl_user_ids"])
    k = int(g.integers(lo, hi + 1))
    others = [int(u) for u in g.choice(p["subjects"], size=k + 1,
                                       replace=False) if int(u) != user]
    if g.random() < p["acl_subject_in_user_acl"]:
        ids = others[:k - 1]
        ids.insert(int(g.integers(len(ids) + 1)), user)
    else:
        ids = others[:k]
    return [f"u{u}" for u in ids]


def entry(entity: str, instances: list) -> dict:
    return {"id": ACL_ENTITY, "value": entity,
            "attributes": [{"id": ACL_INSTANCE, "value": v}
                           for v in instances]}


def rows(n: int, g: np.random.Generator, p: dict) -> list:
    if p["acl_placement"] != "thirds":
        raise ValueError(f"acl_placement {p['acl_placement']!r}")
    out = HR.rows(n, g, p)
    dep = HR.deployment(g, p)
    for row in out:
        if g.random() >= p["acl_share"]:
            continue
        user = int(row["context"]["subject"]["id"][1:])
        action = row["target"]["actions"][0]["value"]
        acls = [entry(traffic.ORG, org_instances(dep, user, action, g, p))]
        if g.random() < p["acl_user_share"]:
            acls.append(entry(HR.USER_ENTITY, user_ids(user, g, p)))
        row["context"]["resources"][0]["meta"]["acls"] = acls
    return out
