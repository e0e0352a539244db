"""A test's row kind: roles role-0 .. role-(``roles`` - 1), some of them
beyond the tree's, on one of two entities and one of the 4 actions, for
``subjects`` users; every draw from ``g``."""

from __future__ import annotations

import numpy as np

import traffic

ENTITIES = [traffic.ORG, "urn:restorecommerce:acs:model:user.User"]


def rows(n: int, g: np.random.Generator, p: dict) -> list:
    roles = g.integers(p["roles"], size=n)
    users = g.integers(p["subjects"], size=n)
    entities = g.integers(len(ENTITIES), size=n)
    actions = g.integers(len(traffic.ACTIONS), size=n)
    out = []
    for i in range(n):
        role, user = f"role-{int(roles[i])}", f"u{int(users[i])}"
        subject = {"id": user,
                   "role_associations": [{"role": role, "attributes": []}],
                   "hierarchical_scopes": []}
        out.append(traffic.row(role, user, ENTITIES[entities[i]],
                               f"res-{user}", traffic.ACTIONS[actions[i]],
                               subject, []))
    return out
