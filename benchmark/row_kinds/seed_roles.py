"""``seed_roles``: after ``bench_all._serving_batch_msg``'s default
(seed-tree) mix: even rows carry the super-admin role, odd rows
role-(i % ``roles``), on the organization entity, the 4 actions in turn;
subject ids are drawn Zipf(``zipf``) over ``subjects`` users and each
request reads its subject's own record (``res-<subject>``).  With
sequential ids in place of the Zipf draws the rows give ``bench_all``'s
bytes (checked once when the benchmark was first written, see PERF.md)."""

from __future__ import annotations

import numpy as np

import traffic


def rows(n: int, g: np.random.Generator, p: dict) -> list:
    users = traffic.zipf_draws(g, p["subjects"], p["zipf"], n)
    out = []
    for i in range(n):
        role = (traffic.SUPER_ADMIN if i % 2 == 0
                else f"role-{i % p['roles']}")
        user = f"u{int(users[i])}"
        subject = {"id": user,
                   "role_associations": [{"role": role, "attributes": []}],
                   "hierarchical_scopes": []}
        out.append(traffic.row(role, user, traffic.ORG, f"res-{int(users[i])}",
                               traffic.ACTIONS[i % 4], subject, []))
    return out
