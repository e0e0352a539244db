"""``hr_scoped``: requests of restorecommerce's HR-scoped deployment
(access-control-srv ``test/utils.ts`` buildRequest, ``createMetadata`` in
``src/core/utils.ts``).  The deployment is drawn once per pool from ``g``
and every batch reuses it:

- an organization forest of ``roots`` trees, ``fanout`` children a node,
  ``depth`` levels (the upstream test's SuperOrg1 -> Org1 -> Org2 -> Org3);
- ``subjects`` users, each holding 1 or 2 role associations over the
  tree's two roles, each scoped to the organization entity with 1 or 2
  scoping instances drawn by level, and one ``hierarchical_scopes`` tree
  per (role, scoping instance): that instance's subtree, ``role`` on its
  root node.

Popularity is dealt over the drawn users stratified by their number of
(role, organization) pairs: Zipf ranks in golden-ratio order take the
users in order of that number.  Drawn independently, whether the few
heaviest users (rank 1 alone sends ~10% of the rows) held a root scope
moved the share of rows over the encoder's floor caps between ~4% and
~22% from seed to seed; stratified, it stays at ~7%.

A row: a user drawn Zipf(``zipf``), one of the tree's entities drawn
uniformly with a fresh resource id, the 4 actions in turn, and a context
resource owned by an organization and by the user.  The owning
organization is placed against the user's scopes of a role that some
PERMIT rule grants for the row's action: with probability ``direct`` it is
a scoping instance itself, with ``hierarchical`` a strict descendant of a
non-leaf instance whose rule matches hierarchically (outside every scope
where the user has none), and with the rest of the probability outside
every subtree the user holds.
"""

from __future__ import annotations

import numpy as np

import traffic

MODEL = "urn:restorecommerce:acs:model:"
# restorecommerce resource services, one policy each
ENTITIES = [MODEL + name for name in (
    "organization.Organization", "location.Location", "user.User",
    "role.Role", "address.Address", "country.Country",
    "contact_point.ContactPoint", "contact_point_type.ContactPointType",
    "customer.Customer", "timezone.Timezone", "locale.Locale", "tax.Tax",
    "tax_type.TaxType", "command.Command", "product.Product",
    "product_category.ProductCategory",
    "product_prototype.ProductPrototype", "price_group.PriceGroup",
    "manufacturer.Manufacturer", "fulfillment.Fulfillment",
    "fulfillment_courier.FulfillmentCourier",
    "fulfillment_product.FulfillmentProduct", "order.Order",
    "invoice.Invoice", "credential.Credential",
    "notification.Notification",
    "notification_channel.NotificationChannel", "job.Job",
    "unit_code.UnitCode", "currency.Currency", "shop.Shop",
    "authentication_log.AuthenticationLog",
)]
USER_ENTITY = MODEL + "user.User"
ADMIN, USER = "admin-r-id", "user-r-id"
SCOPING_ENTITY = "urn:restorecommerce:acs:names:roleScopingEntity"
SCOPING_INSTANCE = "urn:restorecommerce:acs:names:roleScopingInstance"
OWNER_ENTITY = "urn:restorecommerce:acs:names:ownerIndicatoryEntity"
OWNER_INSTANCE = "urn:restorecommerce:acs:names:ownerInstance"
READ, MODIFY = traffic.ACTIONS[0], traffic.ACTIONS[1]

# the tree's PERMIT rules (tree_builders/hr_scoped.py): role -> {action:
# hierarchical matching on}
GRANTS = {ADMIN: {a: True for a in traffic.ACTIONS},
          USER: {READ: True, MODIFY: False}}
GOLDEN = (5 ** 0.5 - 1) / 2


class Forest:
    """The organization forest: ids, parents' children and subtrees."""

    def __init__(self, g: np.random.Generator, roots: int, fanout: int,
                 depth: int):
        self.levels: list = []         # level -> [node index]
        self.children: list = []       # node index -> [node index]
        frontier = []
        for _ in range(roots):
            frontier.append(self._add())
        self.levels.append(list(frontier))
        for _ in range(1, depth):
            nxt = []
            for parent in frontier:
                for _ in range(fanout):
                    child = self._add()
                    self.children[parent].append(child)
                    nxt.append(child)
            self.levels.append(nxt)
            frontier = nxt
        n = len(self.children)
        raw = g.choice(16 ** 10, size=n, replace=False)
        self.ids = [f"org-{int(v):010x}" for v in raw]
        self.subtree = [self._collect(i) for i in range(n)]
        self.json = [self._node(i) for i in range(n)]

    def _add(self) -> int:
        self.children.append([])
        return len(self.children) - 1

    def _collect(self, i: int) -> list:
        out, stack = [], [i]
        while stack:
            node = stack.pop()
            out.append(node)
            stack.extend(reversed(self.children[node]))
        return out

    def _node(self, i: int) -> dict:
        """The upstream HR-scope node shape: ``{"id", "children"}``, a
        leaf without ``children``."""
        node = {"id": self.ids[i]}
        if self.children[i]:
            node["children"] = [self._node(c) for c in self.children[i]]
        return node


class Deployment:
    """The forest and every user's role associations, drawn once."""

    def __init__(self, g: np.random.Generator, p: dict):
        self.forest = Forest(g, p["roots"], p["fanout"], p["depth"])
        n = p["subjects"]
        two_ras = g.random(n) < p["two_associations"]
        single_role = g.random(n) < p["admin_share"]
        self.scopes = []        # user -> [(role, node index)]
        self.subjects = []      # user -> context subject dict
        level_p = np.asarray(p["instance_levels"], np.float64)
        for u in range(n):
            roles = [ADMIN, USER] if two_ras[u] else \
                [ADMIN if single_role[u] else USER]
            pairs, assocs, trees = [], [], []
            for role in roles:
                k = 2 if g.random() < p["two_instances"] else 1
                nodes = []
                for _ in range(k):
                    level = self.forest.levels[
                        int(g.choice(len(level_p), p=level_p))]
                    node = level[int(g.integers(len(level)))]
                    if node not in nodes:
                        nodes.append(node)
                assocs.append({"role": role, "attributes": [{
                    "id": SCOPING_ENTITY, "value": traffic.ORG,
                    "attributes": [{"id": SCOPING_INSTANCE,
                                    "value": self.forest.ids[i]}
                                   for i in nodes]}]})
                for i in nodes:
                    pairs.append((role, i))
                    trees.append({**self.forest.json[i], "role": role})
            self.scopes.append(pairs)
            self.subjects.append({"id": f"u{u}", "role_associations": assocs,
                                  "hierarchical_scopes": trees})
        self._outside: dict = {}
        # popularity rank -> user, stratified over the users' HR-pair
        # counts: ranks in golden-ratio order take the users in order of
        # their counts (ties at random), so every run of ranks, the few
        # heaviest included, holds the profiles in their drawn shares
        size = [sum(len(self.forest.subtree[i]) for _, i in s)
                for s in self.scopes]
        by_size = np.lexsort((g.random(n), size))
        keys = (np.arange(1, n + 1) * GOLDEN) % 1.0
        self.by_rank = np.empty(n, np.int64)
        self.by_rank[np.argsort(keys)] = by_size

    def outside(self, user: int) -> list:
        """The organizations in none of the user's subtrees (a node index
        past the forest, owned by no tree, where the user holds them
        all)."""
        out = self._outside.get(user)
        if out is None:
            held = set()
            for _, i in self.scopes[user]:
                held.update(self.forest.subtree[i])
            n = len(self.forest.ids)
            out = [i for i in range(n) if i not in held] or [n]
            self._outside[user] = out
        return out


_DEPLOYMENTS: dict = {}   # id(g) -> (g, Deployment): one pool's deployment


def deployment(g: np.random.Generator, p: dict) -> Deployment:
    hit = _DEPLOYMENTS.get(id(g))
    if hit is None or hit[0] is not g:
        hit = _DEPLOYMENTS[id(g)] = (g, Deployment(g, p))
    return hit[1]


def owner_org(dep: Deployment, user: int, action: str, draw: float,
              g: np.random.Generator, p: dict) -> int:
    """The node index of the organization that owns the row's resource."""
    forest, scopes = dep.forest, dep.scopes[user]
    granted = [(r, i) for r, i in scopes if action in GRANTS[r]]
    if draw < p["direct"] and granted:
        return granted[int(g.integers(len(granted)))][1]
    if draw < p["direct"] + p["hierarchical"]:
        deep = [i for r, i in granted
                if GRANTS[r][action] and len(forest.subtree[i]) > 1]
        if deep:
            below = forest.subtree[deep[int(g.integers(len(deep)))]][1:]
            return below[int(g.integers(len(below)))]
    outside = dep.outside(user)
    return outside[int(g.integers(len(outside)))]


def rows(n: int, g: np.random.Generator, p: dict) -> list:
    dep = deployment(g, p)
    users = dep.by_rank[traffic.zipf_draws(g, p["subjects"], p["zipf"], n)]
    entities = g.integers(len(ENTITIES), size=n)
    resources = g.integers(2**40, size=n)
    draws = g.random(n)
    out = []
    for i in range(n):
        u = int(users[i])
        subject = dep.subjects[u]
        action = traffic.ACTIONS[i % 4]
        k = owner_org(dep, u, action, float(draws[i]), g, p)
        org = dep.forest.ids[k] if k < len(dep.forest.ids) else "org-none"
        resource = f"res-{int(resources[i]):010x}"
        owners = [{"id": OWNER_ENTITY, "value": traffic.ORG,
                   "attributes": [{"id": OWNER_INSTANCE, "value": org}]},
                  {"id": OWNER_ENTITY, "value": USER_ENTITY,
                   "attributes": [{"id": OWNER_INSTANCE,
                                   "value": subject["id"]}]}]
        out.append(traffic.row(
            subject["role_associations"][0]["role"], subject["id"],
            ENTITIES[int(entities[i])], resource, action, subject,
            [{"id": resource, "meta": {"owners": owners}}]))
    return out
