"""The plain reference of the ``hr_acl`` configuration: a copy of
``references/hr_scoped.py`` (restorecommerce's isAllowed with
checkHierarchicalScope, written from the reference service's semantics:
access-control-srv, src/core/accessController.ts, hierarchicalScope.ts)
with verifyACL added, written from src/core/verifyACL.ts:11-251.  It
imports nothing of the program (of ``reference.py`` only its
``Unsupported``) and reads only the benchmark's own tree documents and the
rows the traffic generator made.

It covers what the configuration's tree and mix use and refuses the rest:
policy sets and policies without targets, rules whose targets carry role
or other subject attributes, role rules scoped by ``roleScopingEntity``
(with ``hierarchicalRoleScoping``; a scoped rule without a role is
refused), entity resources (exact, then the
reference's regex pass) and exact actions, permit-overrides /
deny-overrides / first-applicable, context resources with or without
ACLs, the skipACL subject attribute, no conditions, no properties, no
operations, no token subjects.

The scope check of a scoped rule, once its target matched: the owners of
every instance the request targets on an entity the rule's entity matches
are collected (a missing context resource or one without owners fails
the rule); an instance is in scope when one of its owners of the rule's
scoping entity has an owner instance equal to a ``roleScopingInstance``
of one of the subject's associations of the rule's role (direct), or,
when hierarchical matching is on, equal to an organization of that role's
``hierarchical_scopes`` subtrees; the rule matches when every instance is
in scope.

verifyACL, run for every rule with a target once its target matched and
its scope check passed (accessController.ts:272-275), with its quirks: a
rule carrying skipACL passes; the first targeted resource whose context
resource has no ACLs passes all; an ACL entry that is not an
``aclIndicatoryEntity``, or has no instances, or an attribute that is not
an ``aclInstance``, fails; ``create`` (the first action) needs every ACL
instance of each entity inside the subject's flattened HR organizations
of a role the rule names, scanning the HR roles in order with the
validated-instance set carried across roles, the inner break at the
first instance neither eligible nor validated, and the valid flag carried
across entities; a ``user.User`` entity passes on create; an entity the
subject holds no association of a rule role scoped to fails create;
``read`` / ``modify`` / ``delete`` need one of the subject's scoping
instances of a rule role (or, on a ``user.User`` entity, the subject's
id) in the entity's ACL; any other action fails.

Decisions: PERMIT, DENY, INDETERMINATE (no policy set produced an effect).
The decision of the *last* policy set that produced any effect wins, as in
the reference.  Rules are looked up through an index on (role, action)
before they are matched: a rule with exactly one role and one action can
only match a row that carries both, so the index skips no rule that could
match; every other rule is scanned for every row.
"""

from __future__ import annotations

import re

import reference

PERMIT, DENY, INDETERMINATE = "PERMIT", "DENY", "INDETERMINATE"
CODES = {PERMIT: 0, DENY: 1, INDETERMINATE: 2}

U = {
    "entity": "urn:restorecommerce:acs:names:model:entity",
    "role": "urn:restorecommerce:acs:names:role",
    "roleScopingEntity": "urn:restorecommerce:acs:names:roleScopingEntity",
    "roleScopingInstance":
        "urn:restorecommerce:acs:names:roleScopingInstance",
    "hierarchicalRoleScoping":
        "urn:restorecommerce:acs:names:hierarchicalRoleScoping",
    "ownerEntity": "urn:restorecommerce:acs:names:ownerIndicatoryEntity",
    "ownerInstance": "urn:restorecommerce:acs:names:ownerInstance",
    "resourceID": "urn:oasis:names:tc:xacml:1.0:resource:resource-id",
    "actionID": "urn:oasis:names:tc:xacml:1.0:action:action-id",
    "skipACL": "urn:restorecommerce:acs:names:skipACL",
    "property": "urn:restorecommerce:acs:names:model:property",
    "operation": "urn:restorecommerce:acs:names:operation",
    "relation": "urn:restorecommerce:acs:names:relation",
    "aclIndicatoryEntity":
        "urn:restorecommerce:acs:names:aclIndicatoryEntity",
    "aclInstance": "urn:restorecommerce:acs:names:aclInstance",
    "user": "urn:restorecommerce:acs:model:user.User",
}
ACTION = "urn:restorecommerce:acs:names:action:"
CREATE = ACTION + "create"
READ_MODIFY_DELETE = {ACTION + "read", ACTION + "modify", ACTION + "delete"}
ALGS = {
    "urn:oasis:names:tc:xacml:3.0:rule-combining-algorithm:deny-overrides":
        "deny",
    "urn:oasis:names:tc:xacml:3.0:rule-combining-algorithm:permit-overrides":
        "permit",
    "urn:oasis:names:tc:xacml:3.0:rule-combining-algorithm:first-applicable":
        "first",
}


class Unsupported(reference.Unsupported):
    """A tree or row uses a feature this reference does not implement (a
    kind of ``reference.py``'s, which the harness catches alike)."""


def combine(alg: str, effects: list) -> str:
    """deny-overrides: a DENY wins, else the last effect; permit-overrides
    the same with PERMIT; first-applicable: the first effect."""
    if alg == "first":
        return effects[0]
    winner = DENY if alg == "deny" else PERMIT
    return winner if winner in effects else effects[-1]


def _pairs(attrs) -> list:
    return [(a["id"], a["value"]) for a in attrs or []]


def _split_urn(value: str):
    """(namespace or None, entity name, prefix before the last ':')."""
    prefix, _, tail = value.rpartition(":")
    parts = tail.split(".")
    ns = parts[0].upper() if parts[0].upper() != parts[-1].upper() else None
    return ns, parts[-1], prefix


class Rule:
    __slots__ = ("id", "effect", "roles", "others", "entities", "actions",
                 "n_subjects", "shape", "sid", "scoping", "hierarchical",
                 "has_target", "skip_acl")

    def __init__(self, doc: dict):
        target = doc.get("target") or {}
        if doc.get("condition") or doc.get("context_query"):
            raise Unsupported(f"rule {doc['id']}: conditions")
        self.id = doc["id"]
        self.has_target = bool(target)
        self.effect = doc.get("effect") or PERMIT
        subjects = [a for a in _pairs(target.get("subjects"))
                    if a[0] != U["relation"]]
        if len(subjects) != len(_pairs(target.get("subjects"))):
            raise Unsupported(f"rule {self.id}: relation subjects")
        self.n_subjects = len(target.get("subjects") or [])
        self.roles = [v for k, v in subjects if k == U["role"]]
        self.skip_acl = any(k == U["skipACL"] for k, _ in subjects)
        # the scope check reads the last value of each attribute, as the
        # reference's loop over the rule's subjects does
        self.scoping = self.hierarchical = None
        for k, v in subjects:
            if k == U["roleScopingEntity"]:
                self.scoping = v
            elif k == U["hierarchicalRoleScoping"]:
                self.hierarchical = v
        self.hierarchical = (self.hierarchical
                             if self.hierarchical is not None else "true")
        if self.scoping and not self.roles:
            raise Unsupported(f"rule {self.id}: scoping without a role")
        self.others = subjects
        self.entities = []
        for k, v in _pairs(target.get("resources")):
            if k != U["entity"]:
                raise Unsupported(f"rule {self.id}: resource attribute {k}")
            self.entities.append(v)
        self.actions = _pairs(target.get("actions"))
        # everything matching reads: rules of one shape match alike
        self.shape = (tuple(self.others), self.n_subjects,
                      tuple(self.entities), tuple(self.actions))

    # -------------------------------------------------------- matching

    def subject_matches(self, req) -> bool:
        """A role rule matches a subject holding that role (the last role
        attribute counts); a rule without a role must find each of its
        subject attributes in the request target."""
        if not self.others:
            return True
        if not self.roles:
            return all(a in req.subjects for a in self.others)
        return self.roles[-1] in req.roles

    def resources_match(self, req) -> bool:
        """Exact entity equality, or else the reference's regex pass: the
        rule's entity name is a regular expression searched in the
        request's entity name when both namespaces agree; a request entity
        with another URN prefix resets the match."""
        if not self.entities:
            return True
        req_entities = [v for k, v in req.resources if k == U["entity"]]
        if any(v in self.entities for v in req_entities):
            return True
        matched = False
        for value in req_entities:
            for rule_value in self.entities:
                hit, other_prefix = regex_compare(rule_value, value)
                if other_prefix:
                    matched = False
                if hit:
                    matched = True
        return matched

    def in_scope(self, req, hierarchical: bool = True) -> bool:
        """checkHierarchicalScope: every targeted instance of an entity
        the rule's entities match has an owner in the subject's scope of
        the rule's role, directly or, with hierarchical matching on (and
        ``hierarchical``), through that role's HR subtrees.  A rule without
        subjects or without a scoping entity passes."""
        if self.n_subjects == 0 or not self.scoping:
            return True
        if req.context is None:
            return False
        owners_of = {}
        for rule_entity in self.entities:
            matched = False
            for key, value in req.resources:
                if key == U["entity"] and value == rule_entity:
                    matched = True
                elif key == U["entity"]:
                    hit, other_prefix = regex_compare(rule_entity, value)
                    if other_prefix:
                        matched = False
                    if hit:
                        matched = True
                elif key == U["resourceID"] and matched:
                    res = req.ctx_resource(value)
                    if res is None:
                        return False      # resource not in the context
                    meta = res.get("meta")
                    owners = (meta or {}).get("owners")
                    if not meta or not owners:
                        return False      # no ownership passed
                    owners_of[value] = owners
        associations = req.subject.get("role_associations")
        if not associations:
            return False
        role = self.roles[-1] if self.roles else None
        mine = [ra for ra in associations if ra.get("role") == role]
        left = {rid: owners for rid, owners in owners_of.items()
                if not any(self._direct(owner, mine) for owner in owners)}
        if left and hierarchical and self.hierarchical == "true":
            orgs = req.hr_orgs(role)
            left = {rid: owners for rid, owners in left.items()
                    if orgs.isdisjoint(self._owner_instances(owners, mine))}
        return not left

    def _scoped_owner(self, owner: dict, attr: dict) -> bool:
        return (attr.get("id") == U["roleScopingEntity"]
                and owner.get("id") == U["ownerEntity"]
                and owner.get("value") == self.scoping
                and owner.get("value") == attr.get("value"))

    def _direct(self, owner: dict, mine: list) -> bool:
        """An owner of the scoping entity whose instance is a scoping
        instance of one of the subject's associations of the role."""
        for ra in mine:
            for attr in ra.get("attributes") or []:
                if not self._scoped_owner(owner, attr):
                    continue
                for inst in attr.get("attributes") or []:
                    if inst.get("id") != U["roleScopingInstance"]:
                        continue
                    if any(o.get("value") == inst.get("value")
                           for o in owner.get("attributes") or []):
                        return True
        return False

    def _owner_instances(self, owners: list, mine: list) -> set:
        """The owner instances of the owners the hierarchical step reads:
        those of the scoping entity that one of the subject's
        associations of the role is scoped to."""
        out = set()
        for owner in owners:
            if any(self._scoped_owner(owner, attr) for ra in mine
                   for attr in ra.get("attributes") or []):
                out.update(a.get("value") for a in owner.get("attributes")
                           or [] if a.get("id") == U["ownerInstance"])
        return out

    def verify_acl(self, req) -> bool:
        """verifyACL (verifyACL.ts:11-251) of this rule's target for
        ``req``."""
        if self.skip_acl:
            return True
        acl_of: dict = {}      # ACL entity -> its instances, in order
        for key, value in req.resources:
            if key != U["resourceID"]:
                continue
            res = req.ctx_resource(value)
            acls = ((res or {}).get("meta") or {}).get("acls")
            if not acls:
                return True           # no ACLs: no verification needed
            for acl in acls:
                if acl.get("id") != U["aclIndicatoryEntity"]:
                    return False
                instances = acl_of.setdefault(acl.get("value"), [])
                if not acl.get("attributes"):
                    return False      # an ACL entity without instances
                for attr in acl["attributes"]:
                    if attr.get("id") != U["aclInstance"]:
                        return False
                    instances.append(attr.get("value"))
        associations = req.subject.get("role_associations")
        if not associations:
            return False
        scoped_of: dict = {}   # ACL entity -> the subject's instances
        for ra in associations:
            if ra.get("role") not in self.roles:
                continue
            for attr in ra.get("attributes") or []:
                if (attr.get("id") == U["roleScopingEntity"]
                        and attr.get("value") in acl_of):
                    scoped_of.setdefault(attr["value"], []).extend(
                        inst.get("value")
                        for inst in attr.get("attributes") or []
                        if inst.get("id") == U["roleScopingInstance"])
        orgs_of = req.acl_hr_orgs()
        action = req.actions[0] if req.actions else None
        if action == (U["actionID"], CREATE):
            if not acl_of:
                return True
            valid = False
            for entity, instances in acl_of.items():
                if entity == U["user"]:
                    valid = True
                    continue
                if entity not in scoped_of:
                    return False
                validated = []
                for role, orgs in orgs_of.items():
                    if role not in self.roles:
                        continue
                    for inst in instances:
                        if inst in orgs:
                            valid = True
                            validated.append(inst)
                        elif inst not in validated:
                            valid = False
                            break
                if not valid:
                    return False
            return valid
        if action is not None and action[0] == U["actionID"] \
                and action[1] in READ_MODIFY_DELETE:
            if not acl_of:
                return True
            for entity, instances in acl_of.items():
                if entity == U["user"] and req.subject.get("id") in instances:
                    return True
                if any(v in instances for v in scoped_of.get(entity, ())):
                    return True
            return False
        return False

    def applies(self, req, scope=None, acl=None) -> bool:
        """The target matches, the scope check passes (``scope``: a
        control's replacement of it) and, on a rule with a target,
        verifyACL passes (``acl``: a control's replacement of it)."""
        if not (self.subject_matches(req)
                and all(a in req.actions for a in self.actions)
                and self.resources_match(req)):
            return False
        ok = self.in_scope(req) if scope is None else scope(self, req)
        if not ok or not self.has_target:
            return ok
        return self.verify_acl(req) if acl is None else acl(self, req)


_REGEX: dict = {}


def regex_compare(rule_value: str, req_value: str):
    """(match, prefixes differ) of the reference's regex entity pass."""
    key = (rule_value, req_value)
    out = _REGEX.get(key)
    if out is None:
        rule_ns, rule_name, rule_prefix = _split_urn(rule_value)
        req_ns, req_name, req_prefix = _split_urn(req_value)
        hit = False
        if (rule_ns and req_ns and rule_ns == req_ns) or \
                (not rule_ns and not req_ns):
            hit = re.search(rule_name, req_name) is not None
        out = _REGEX[key] = (hit, req_prefix != rule_prefix)
    return out


class Row:
    """One access request: target attribute pairs and a parsed context."""

    __slots__ = ("subjects", "resources", "actions", "roles", "_resources",
                 "context", "subject", "_hr", "_acl_hr")

    def __init__(self, target: dict, context: dict | None):
        self.subjects = _pairs(target.get("subjects"))
        self.resources = _pairs(target.get("resources"))
        self.actions = _pairs(target.get("actions"))
        if any(k in (U["property"], U["operation"])
               for k, _ in self.resources):
            raise Unsupported("property / operation resources")
        self.context = context
        subject = (context or {}).get("subject") or {}
        if subject.get("token"):
            raise Unsupported("token subjects")
        self.subject = subject
        self.roles = {ra.get("role")
                      for ra in subject.get("role_associations") or []}
        self._resources = (context or {}).get("resources") or []
        self._hr: dict = {}
        self._acl_hr = None

    def hr_orgs(self, role: str) -> set:
        """Every node id of the subject's HR-scope trees whose root
        carries ``role``.  The reference iterates the list unguarded: a
        subject without one throws (the service denies), which no row of
        the benchmark's mixes carries."""
        out = self._hr.get(role)
        if out is None:
            scopes = self.subject.get("hierarchical_scopes")
            if scopes is None:
                raise Unsupported("role associations without HR scopes")
            out = set()
            stack = [t for t in scopes if t.get("role") == role]
            while stack:
                node = stack.pop()
                if node.get("id"):
                    out.add(node["id"])
                stack.extend(node.get("children") or [])
            self._hr[role] = out
        return out

    def ctx_resource(self, instance_id: str):
        for res in self._resources:
            inst = res.get("instance")
            if inst is not None and inst.get("id") == instance_id:
                return inst
        for res in self._resources:
            if res.get("id") == instance_id:
                return res
        return None

    def acl_hr_orgs(self) -> dict:
        """verifyACL's flatten of the subject's HR-scope trees: role ->
        the node ids under it, a node without a role taking its parent's,
        in pre-order, roles in the order they first appear.  The
        reference iterates the list unguarded: a subject without one
        throws, which no row of the benchmark's mixes carries."""
        out = self._acl_hr
        if out is None:
            scopes = self.subject.get("hierarchical_scopes")
            if scopes is None:
                raise Unsupported("role associations without HR scopes")
            out = {}

            def walk(nodes, role):
                for node in nodes:
                    own = node.get("role")
                    key = own if own is not None else role
                    if node.get("id"):
                        out.setdefault(key, set()).add(node["id"])
                    walk(node.get("children") or [], key)

            walk(scopes, None)
            self._acl_hr = out
        return out


class Reference:
    """The tree as nested documents (``policy_sets`` with their policies
    and rules inline), and isAllowed over it."""

    scope = None     # a control's replacement of Rule.in_scope
    acl = None       # a control's replacement of Rule.verify_acl

    def __init__(self, policy_sets: list):
        self.sets = []
        for ps in policy_sets:
            if ps.get("target"):
                raise Unsupported(f"policy set {ps['id']}: target")
            policies = []
            for pol in ps["policies"]:
                if pol.get("target"):
                    raise Unsupported(f"policy {pol['id']}: target")
                rules = [Rule(r) for r in pol.get("rules") or []]
                policies.append([pol["id"], ALGS[pol["combining_algorithm"]],
                                 pol.get("effect"), rules])
            self.sets.append([ps["id"], ALGS[ps["combining_algorithm"]],
                              policies])
        self._index()

    def _index(self) -> None:
        """(role, action) -> [(set, policy, position, rule)]; the rest
        (rules with other or no role/action shape) are scanned always."""
        self.by_key: dict = {}
        self.scan = []
        shapes: dict = {}
        for s, (_, _, policies) in enumerate(self.sets):
            for p, (_, _, _, rules) in enumerate(policies):
                for q, rule in enumerate(rules):
                    rule.sid = shapes.setdefault(rule.shape, len(shapes))
                    entry = (s, p, q, rule)
                    if len(rule.roles) == 1 and len(rule.actions) == 1:
                        key = (rule.roles[0], rule.actions[0])
                        self.by_key.setdefault(key, []).append(entry)
                    else:
                        self.scan.append(entry)

    def candidates(self, req: Row) -> list:
        seen = list(self.scan)
        for role in req.roles:
            for action in req.actions:
                seen.extend(self.by_key.get((role, action), ()))
        return seen

    def applies(self, rule: Rule, req: Row) -> bool:
        return rule.applies(req, type(self).scope, type(self).acl)

    def matching(self, req: Row) -> list:
        """(set, policy, position, rule) of every rule that applies."""
        out, memo = [], {}
        for entry in self.candidates(req):
            rule = entry[3]
            ok = memo.get(rule.sid)
            if ok is None:
                ok = memo[rule.sid] = self.applies(rule, req)
            if ok:
                out.append(entry)
        return out

    def decide(self, req: Row) -> str:
        return self.decide_from(self.matching(req))

    def decide_from(self, matching: list) -> str:
        """The decision from the rules that apply, with their effects as
        they stand now."""
        hits: dict = {}
        for s, p, q, rule in matching:
            hits.setdefault((s, p), []).append((q, rule.effect))
        effect = None
        for s, (_, set_alg, policies) in enumerate(self.sets):
            policy_effects = []
            for p, (_, alg, pol_effect, rules) in enumerate(policies):
                if not rules:
                    if pol_effect:
                        policy_effects.append(pol_effect)
                    continue
                found = hits.get((s, p))
                if found:
                    found.sort(key=lambda x: x[0])
                    policy_effects.append(combine(alg, [e for _, e in found]))
            if policy_effects:
                effect = combine(set_alg, policy_effects)
        return effect or INDETERMINATE


class ScopeOpenReference(Reference):
    """The control for the HR-scoped tree: the scope check always passes,
    so a scoped rule grants to any holder of its role whatever owns the
    resource (fabricated PERMITs)."""

    scope = staticmethod(lambda rule, req: True)


class AclOpenReference(Reference):
    """The control for ACL-shared resources: verifyACL always passes, so a
    rule grants whatever the resource's ACL shares it with (fabricated
    PERMITs) and the fallback DENY applies where the ACL kept it off."""

    acl = staticmethod(lambda rule, req: True)


CONTROLS = {"acl_open": AclOpenReference, "scope_open": ScopeOpenReference}
