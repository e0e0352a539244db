"""Batched ``whatIsAllowed`` (reverse query) on device.

The reverse query's cost in a batch setting is the repeated target match
per (request x node): for every policy set, policy and rule the oracle
walks the whole request attribute list (reference:
accessController.ts:326-427 calling targetMatches :661-672).  The device
already computes exactly those match vectors — ``_match_targets`` with
``wia=True`` emits the whatIsAllowed-mode variants for every target row
of the batch in one dispatch.

whatIsAllowed does no HR-scope, ACL or condition work, so the only thing
the device CANNOT reproduce is the obligation side effect: masking
obligations accumulate during the scalar attribute scan, including from
calls whose final verdict is False (reference :592-640).  The split is:

- device: [B, T] wia match vectors + a conservative ``maybe_mask`` bit
  (target has properties AND its entity matched: the precondition for any
  mask append);
- host: replay the oracle's exact control flow per request, substituting
  device booleans for match results, and re-running the scalar matcher
  ONLY on rows whose maybe_mask bit is set (for its obligation side
  effects; its boolean agrees with the device by construction).

Result: bit-identical ReverseQuery trees and obligations (differential:
tests/test_reverse.py), with the scalar matcher invoked only on the small
property-relevant subset instead of every (request x node)."""

from __future__ import annotations

import copy

import numpy as np

from ..models.model import PolicyRQ, PolicySetRQ, ReverseQuery, RuleRQ
from ..models.model import OperationStatus
from .compile import CompiledPolicies
from .encode import RequestBatch, encode_requests
from .kernel import _match_targets, lead_padding, pad_cols, pow2_bucket

# per-signature RESOURCE planes emitted by the components+wia device
# program (kernel._match_targets), cached per signature; the subject fold
# happens host-side per row
_PLANE_KEYS = [
    "sig_wia_ex_p", "sig_wia_ex_d", "sig_wia_rg_p", "sig_wia_rg_d",
    "sig_maybe_ex", "sig_maybe_rg", "sig_act_ok",
]

# below this rule count the scalar reverse-query walk is taken to beat the
# device round-trip (no benchmark cell measures the crossover); mirrors
# ops/prefilter.MIN_RULES
REVERSE_MIN_RULES = 512


class ReverseQueryKernel:
    """One jitted dispatch computing the whatIsAllowed match vectors for
    every (request, target row) of a batch.

    ``policy_sets`` is deep-copied at construction: hot tree mutations
    (engine.update_rule & co. mutate combinables dicts in place) must not
    shift nodes under the compiled index arrays mid-serve — the reverse
    query serves version-pinned from this snapshot, exactly like the
    decision kernel serves from its compiled arrays."""

    def __init__(self, compiled: CompiledPolicies, policy_sets,
                 copy_tree: bool = True):
        if not compiled.supported:
            raise ValueError(
                f"policy tree unsupported by kernel: {compiled.unsupported_reason}"
            )
        import jax
        import jax.numpy as jnp

        self.compiled = compiled
        if isinstance(policy_sets, dict):
            sets = [ps for ps in policy_sets.values() if ps is not None]
        else:
            sets = [ps for ps in policy_sets if ps is not None]
        # copy_tree=False: the caller passes a tree that is already a
        # version-pinned snapshot (the evaluator publishes one alongside
        # the compiled arrays) — copying again would be pure waste
        self.sets = copy.deepcopy(sets) if copy_tree else sets
        # RuleRQ carriers are request-independent (id/target/effect/
        # condition/cacheable: pure rule data), so one shared instance per
        # rule serves every request this kernel answers — object
        # construction was the wia-large host-assembly bottleneck.  The
        # cache lives exactly as long as the version-pinned snapshot.
        # ALIASING INVARIANT: one RuleRQ instance appears in MANY
        # concurrent ReverseQuery responses — consumers must treat it as
        # immutable (serialize, never annotate in place), and the id()
        # keys are valid only because self.sets pins the rule objects
        # alive for this kernel's lifetime.
        self._rule_rq_cache: dict[int, RuleRQ] = {}
        self._c = {k: jnp.asarray(v) for k, v in compiled.arrays.items()}
        self._runs: dict[tuple, object] = {}
        self._plane_cache: dict[tuple, np.ndarray] = {}

    def _runner(self, schedule: tuple):
        """Jitted per packed-schedule: representative rows (one per NEW
        resource signature) travel as ONE int32 transfer and the
        per-signature RESOURCE planes return as one stacked readback.
        The subject fold is applied host-side, so this program runs only
        on signature-cache misses — steady-state reverse queries touch
        the device not at all."""
        import jax
        import jax.numpy as jnp

        run = self._runs.get(schedule)
        if run is None:
            # the tables enter as arguments: closed over, they would be
            # baked into the program as constants — tens of MB at 100k
            # rules, where ops/kernel.py bakes nothing over 1 MiB
            def run(c, mega, rgx_set, pfx_neq):
                def one(row):
                    offset = 0
                    rr = {"rgx_set": rgx_set, "pfx_neq": pfx_neq}
                    for k, w, tail, is_bool in schedule:
                        v = row[offset:offset + w]
                        offset += w
                        v = v.reshape(tail) if tail else v[0]
                        rr[k] = (v != 0) if is_bool else v
                    m = _match_targets(
                        c, rr, with_hr=False, wia=True, components=True
                    )
                    return jnp.stack([m[k] for k in _PLANE_KEYS])

                return jax.vmap(one)(mega)

            run.__name__ = "acs_reverse"  # compile counts, traces
            jitted = jax.jit(run)
            run = lambda *args: jitted(self._c, *args)  # noqa: E731
            self._runs[schedule] = run
        return run

    def _signature_planes(self, batch: RequestBatch, sig, first_idx):
        """[G, NK, T] resource planes for the batch's distinct signatures,
        via the plane cache; misses are computed in one device dispatch
        over the first batch row of each missing signature (the planes
        depend only on signature fields, so any representative row
        works)."""
        import jax.numpy as jnp

        G = sig.shape[0]
        T = self.compiled.arrays["t_role"].shape[0]
        NK = len(_PLANE_KEYS)
        planes = np.empty((G, NK, T), bool)
        missing = []
        gkeys = []
        for g in range(G):
            gk = (sig[g].tobytes(), self.compiled.version)
            gkeys.append(gk)
            got = self._plane_cache.get(gk)
            if got is None:
                missing.append(g)
            else:
                planes[g] = got
        if missing:
            _, _, e_bucket, _ = lead_padding(batch)
            rows = np.asarray([first_idx[g] for g in missing])
            nm_pad = pow2_bucket(len(rows), floor=8)
            schedule = []
            parts = []
            for k, v in batch.arrays.items():
                a = np.asarray(v)[rows]
                tail = a.shape[1:]
                w = int(np.prod(tail)) if tail else 1
                part = a.reshape(a.shape[0], w).astype(np.int32)
                if nm_pad != part.shape[0]:
                    part = np.concatenate(
                        [part,
                         np.zeros((nm_pad - part.shape[0], w), np.int32)],
                        axis=0,
                    )
                parts.append(part)
                schedule.append((k, w, tuple(tail),
                                 bool(a.dtype == np.bool_)))
            mega = np.ascontiguousarray(np.concatenate(parts, axis=1))
            run = self._runner(tuple(schedule))
            out = np.asarray(run(
                jnp.asarray(mega),
                jnp.asarray(pad_cols(batch.rgx_set, e_bucket)),
                jnp.asarray(pad_cols(batch.pfx_neq, e_bucket)),
            ))  # [nm_pad, NK, T]
            for j, g in enumerate(missing):
                planes[g] = out[j]
                if len(self._plane_cache) >= 4096:
                    self._plane_cache.pop(next(iter(self._plane_cache)))
                # own copy: caching a view of ``planes`` (or ``out``)
                # would pin the whole per-batch buffer for the cache's
                # lifetime
                self._plane_cache[gkeys[g]] = planes[g].copy()
        return planes

    def evaluate(self, batch: RequestBatch) -> dict[str, np.ndarray]:
        """Returns {key: [B, T] bool} for the six wia vectors.

        Split: per-signature RESOURCE planes from the device (cached —
        see kernel._match_targets components+wia), per-row subject fold
        in numpy.  The former [B, T]-per-row device program paid the
        TPU's (8, 128) tile padding on every small-trailing-dim
        intermediate and was ~90% of reverse-query wall time on the
        1000-rule tree (round-5 profile)."""
        a_ = batch.arrays
        ents = np.asarray(a_["r_ent_vals"])
        valid = np.asarray(a_["r_ent_valid"])
        ops = np.asarray(a_["r_op_vals"])
        act_ids = np.asarray(a_["r_act_ids"])
        acts = np.asarray(a_["r_act_vals"])
        hasp = np.asarray(a_["r_has_props"])
        B = ents.shape[0]

        # ordered entity runs (sticky regex state is order-sensitive) +
        # the validity bits (a VALID slot whose value interned to
        # ABSENT=-1 — e.g. a None-valued entity attribute — still drives
        # regex/prefix state and must not collide with an absent slot) +
        # sorted ops + sorted action pairs + the request has-props bit
        # (it flips the wia PERMIT property-fail, reference :592-615)
        ents_m = np.where(valid, ents, -1)
        pair_key = (act_ids.astype(np.int64) << 32) | (
            acts.astype(np.int64) & 0xFFFFFFFF
        )
        order = np.argsort(pair_key, axis=1, kind="stable")
        sig = np.concatenate(
            [ents_m, valid.astype(np.int32), np.sort(ops, 1),
             np.take_along_axis(act_ids, order, 1),
             np.take_along_axis(acts, order, 1),
             hasp.astype(np.int32).reshape(B, 1)],
            axis=1,
        )
        uniq, first_idx, inv = np.unique(
            sig, axis=0, return_index=True, return_inverse=True
        )
        inv = inv.reshape(B)
        planes = self._signature_planes(batch, uniq, first_idx)
        row_planes = planes[inv]  # [B, NK, T]
        pk = {k: i for i, k in enumerate(_PLANE_KEYS)}

        # subject fold in numpy (reference: checkSubjectMatches
        # :793-823); T x batch is bounded by the decision-kernel contract
        # (the masks dict below is [B, T] x 6 either way)
        c = self.compiled.arrays
        t_role = c["t_role"]
        roles = np.asarray(a_["r_roles"])
        role_ok = (
            (t_role[None, :, None] == roles[:, None, :]).any(-1)
            & (t_role >= 0)[None, :]
        )  # [B, T]
        # the pair-subset fold is the widest intermediate
        # ([B, T, KS, KSr]); it only decides USER-targeted rows
        # (subjects without a role attribute), so it runs compacted to
        # that row subset — zero-width for the common role-only tree
        pair_rows = np.nonzero(
            ~c["t_has_role"] & (c["t_n_subjects"] > 0)
        )[0]
        sub_ok = (c["t_n_subjects"] == 0)[None] | (
            c["t_has_role"][None] & role_ok
        )
        if pair_rows.size:
            ts_ids = c["t_sub_ids"][pair_rows]
            ts_vals = c["t_sub_vals"][pair_rows]
            sub_ids = np.asarray(a_["r_sub_ids"])
            sub_vals = np.asarray(a_["r_sub_vals"])
            eq = (
                (ts_ids[None, :, :, None] == sub_ids[:, None, None, :])
                & (ts_vals[None, :, :, None] == sub_vals[:, None, None, :])
                & (sub_ids[:, None, None, :] >= 0)
            )  # [B, Tp, KS, KSr]
            pairs_ok = ((ts_ids[None] < 0) | eq.any(-1)).all(-1)
            sub_ok[:, pair_rows] |= pairs_ok
        base = sub_ok & row_planes[:, pk["sig_act_ok"]]
        return {
            "tm_wia_ex_p": base & row_planes[:, pk["sig_wia_ex_p"]],
            "tm_wia_ex_d": base & row_planes[:, pk["sig_wia_ex_d"]],
            "tm_wia_rg_p": base & row_planes[:, pk["sig_wia_rg_p"]],
            "tm_wia_rg_d": base & row_planes[:, pk["sig_wia_rg_d"]],
            "maybe_mask_ex": row_planes[:, pk["sig_maybe_ex"]],
            "maybe_mask_rg": row_planes[:, pk["sig_maybe_rg"]],
        }


def _rule_match_cubes(compiled: CompiledPolicies, masks: dict):
    """Vectorized per-rule wia verdicts for the whole batch.

    ``rule_match[b, s, kp, kr]``: the oracle's final rule-target verdict
    (no-target rules match; otherwise exact OR regex — the regex call is a
    fallback, so the disjunction equals the sequential result).
    ``rule_maskful[b, s, kp, kr]``: some mode of the rule's target row
    could append obligations for row b — those rules must go through the
    scalar matcher in oracle order, the rest can be collected wholesale."""
    a = compiled.arrays
    rt = a["rule_target"]  # [S, KP, KR]
    deny = (a["rule_effect"] == 2)[None]
    ex = np.where(deny, masks["tm_wia_ex_d"][:, rt],
                  masks["tm_wia_ex_p"][:, rt])
    rg = np.where(deny, masks["tm_wia_rg_d"][:, rt],
                  masks["tm_wia_rg_p"][:, rt])
    has_t = a["rule_has_target"][None]
    rule_match = a["rule_valid"][None] & (~has_t | ex | rg)
    rule_maskful = has_t & (
        masks["maybe_mask_ex"][:, rt] | masks["maybe_mask_rg"][:, rt]
    )
    return rule_match, rule_maskful


def _assemble(
    engine, compiled: CompiledPolicies, sets, request, m,
    match_lists=None, maskful_any=None, rule_rq_cache=None,
) -> ReverseQuery:
    """Replay of AccessController.what_is_allowed (engine.py:373-499,
    reference accessController.ts:326-427) with device match vectors.

    ``sets``: the kernel's version-pinned tree snapshot — MUST be the tree
    the compiled index arrays were built from (live engine.policy_sets can
    mutate under a concurrent hot update).
    ``m``: {key: [T] bool} for this request.  Obligations are produced by
    the scalar matcher re-run on maybe_mask rows — identical side-effect
    order to the oracle because the control flow is identical."""
    a = compiled.arrays
    obligations = []
    engine.prepare_context(request)
    entity_urn = engine.urns.get("entity")

    def tm(row: int, target_obj, effect, regex: bool) -> bool:
        mode = "rg" if regex else "ex"
        if m[f"maybe_mask_{mode}"][row]:
            return engine._target_matches(
                target_obj, request, "whatIsAllowed", obligations,
                effect, regex,
            )
        deny = effect == "DENY"
        return bool(m[f"tm_wia_{mode}_{'d' if deny else 'p'}"][row])

    policy_sets_rq: list[PolicySetRQ] = []
    for s, policy_set in enumerate(sets):
        if policy_set.target is None or tm(
            int(a["set_target"][s]), policy_set.target, None, False
        ):
            pset = PolicySetRQ(
                id=policy_set.id,
                target=policy_set.target,
                combining_algorithm=policy_set.combining_algorithm,
            )

            exact_match = False
            policy_effect = None
            for kp, policy in enumerate(policy_set.combinables.values()):
                if policy is None:
                    continue
                if policy.effect:
                    policy_effect = policy.effect
                if policy.target and tm(
                    int(a["pol_target"][s, kp]), policy.target,
                    policy_effect, False,
                ):
                    exact_match = True
                    break

            req_entity_count = len([
                at for at in (request.target.resources or [])
                if at and at.id == entity_urn
            ])
            if exact_match and req_entity_count > 1:
                exact_match = engine._check_multiple_entities_match(
                    policy_set, request, obligations
                )

            for kp, policy in enumerate(policy_set.combinables.values()):
                if policy is None:
                    continue
                row = int(a["pol_target"][s, kp])
                if (
                    policy.target is None
                    or (exact_match
                        and tm(row, policy.target, policy_effect, False))
                    or (not exact_match
                        and tm(row, policy.target, policy_effect, True))
                ):
                    policy_rq = PolicyRQ(
                        id=policy.id,
                        target=policy.target,
                        effect=policy.effect,
                        evaluation_cacheable=policy.evaluation_cacheable,
                        combining_algorithm=policy.combining_algorithm,
                        has_rules=bool(policy.combinables),
                    )
                    rules_list = list(policy.combinables.values())
                    fast = (
                        match_lists is not None
                        and not maskful_any[s, kp]
                    )
                    if fast:
                        # no rule of this policy can append obligations for
                        # this request: collect matches wholesale from the
                        # pre-grouped (s, kp) -> [kr] lists (identical
                        # verdicts, no side effects to order)
                        matching = match_lists.get((s, kp), ())
                        rule_iter = ((kr, rules_list[kr]) for kr in matching)
                    else:
                        rule_iter = enumerate(rules_list)
                    for kr, rule in rule_iter:
                        if rule is None:
                            continue
                        if fast:
                            matches = True
                        else:
                            rrow = int(a["rule_target"][s, kp, kr])
                            matches = rule.target is None or tm(
                                rrow, rule.target, rule.effect, False
                            )
                            if not matches:
                                matches = tm(rrow, rule.target,
                                             rule.effect, True)
                        if rule.target is None or matches:
                            rq = None
                            if rule_rq_cache is not None:
                                rq = rule_rq_cache.get(id(rule))
                            if rq is None:
                                rq = RuleRQ(
                                    id=rule.id,
                                    target=rule.target,
                                    effect=rule.effect,
                                    condition=rule.condition,
                                    context_query=rule.context_query,
                                    evaluation_cacheable=(
                                        rule.evaluation_cacheable
                                    ),
                                )
                                if rule_rq_cache is not None:
                                    rule_rq_cache[id(rule)] = rq
                            policy_rq.rules.append(rq)
                    if policy_rq.effect or (
                        not policy_rq.effect and policy_rq.rules
                    ):
                        pset.policies.append(policy_rq)

            if pset.policies:
                policy_sets_rq.append(pset)

    return ReverseQuery(
        policy_sets=policy_sets_rq,
        obligations=obligations,
        operation_status=OperationStatus(),
    )


def what_is_allowed_batch(
    engine,
    compiled: CompiledPolicies,
    kernel: ReverseQueryKernel,
    requests: list,
    batch: RequestBatch | None = None,
) -> list[ReverseQuery]:
    """Batched reverse query: device match vectors + host assembly over
    the kernel's version-pinned tree snapshot; ineligible rows fall back
    to the scalar oracle wholesale."""
    if batch is None:
        # the reverse matcher never runs stage B (with_hr=False planes),
        # so the owner-bit packer is skipped alongside conditions
        batch = encode_requests(
            requests, compiled, skip_conditions=True, skip_owner_bits=True
        )
    masks = kernel.evaluate(batch)
    rule_match, rule_maskful = _rule_match_cubes(compiled, masks)
    # one vectorized pass over the whole batch replaces per-request
    # nonzero/any calls in the assembly loop: matching (b, s, kp, kr)
    # tuples grouped per request, and the per-policy "any maskful rule"
    # bit reduced once
    maskful_any = rule_maskful.any(axis=3)  # [B, S, KP]
    mb, ms, mp, mk = np.nonzero(rule_match)
    bounds = np.searchsorted(mb, np.arange(len(requests) + 1))
    out = []
    for b, request in enumerate(requests):
        if not batch.eligible[b]:
            out.append(engine.what_is_allowed(request))
            continue
        m = {k: v[b] for k, v in masks.items()}
        lo, hi = bounds[b], bounds[b + 1]
        match_lists: dict[tuple[int, int], list[int]] = {}
        for j in range(lo, hi):
            match_lists.setdefault(
                (int(ms[j]), int(mp[j])), []
            ).append(int(mk[j]))
        out.append(_assemble(
            engine, compiled, kernel.sets, request, m,
            match_lists, maskful_any[b],
            rule_rq_cache=kernel._rule_rq_cache,
        ))
    return out
