"""Differential suite for the incremental policy-update subsystem
(ops/delta.py): capacity-bucketed tables, in-place CRUD patching, scoped
decision-cache invalidation, refresh debounce.

Table-identity bar: after every mutation the patched tables must decode
to EXACTLY the same policy semantics as a from-scratch
``compile_policies`` of the final tree — compared through
:func:`canonical_tables`, which maps interner ids / vocab rows / target
rows back to strings (those numberings are representation, not
semantics: the kernel only ever consumes them through the same
indirections the canonicalizer follows).  Decision bar: kernel decisions
bit-identical to the scalar oracle on every corpus row, cache on AND off.
"""

from __future__ import annotations

import random
import threading
import time

import numpy as np
import pytest

from access_control_srv_tpu.core.engine import AccessController
from access_control_srv_tpu.models import Attribute, Request, Target, Urns
from access_control_srv_tpu.ops import compile_policies
from access_control_srv_tpu.ops import delta as delta_mod
from access_control_srv_tpu.ops.compile import TARGET_COLUMNS
from access_control_srv_tpu.srv.decision_cache import (
    DecisionCache,
    request_features,
)
from access_control_srv_tpu.srv.evaluator import HybridEvaluator
from access_control_srv_tpu.srv.store import PolicyStore

URNS = Urns()
PO = "urn:oasis:names:tc:xacml:3.0:rule-combining-algorithm:permit-overrides"
DO = "urn:oasis:names:tc:xacml:3.0:rule-combining-algorithm:deny-overrides"
FA = "urn:oasis:names:tc:xacml:3.0:rule-combining-algorithm:first-applicable"


def entity(k: int) -> str:
    return f"urn:restorecommerce:acs:model:thing{k}.Thing{k}"


def rule_doc(rid: str, k: int, effect: str = "PERMIT",
             cacheable: bool = True, action: str = "read") -> dict:
    return {
        "id": rid,
        "target": {
            "subjects": [{"id": URNS["role"], "value": f"role-{k % 5}"}],
            "resources": [{"id": URNS["entity"], "value": entity(k)}],
            "actions": [{"id": URNS["actionID"], "value": URNS[action]}],
        },
        "effect": effect,
        "evaluation_cacheable": cacheable,
    }


def make_request(k: int, who: str = "u1", action: str = "read") -> Request:
    role = f"role-{k % 5}"
    return Request(
        target=Target(
            subjects=[Attribute(id=URNS["role"], value=role),
                      Attribute(id=URNS["subjectID"], value=who)],
            resources=[Attribute(id=URNS["entity"], value=entity(k))],
            actions=[Attribute(id=URNS["actionID"], value=URNS[action])],
        ),
        context={"resources": [], "subject": {
            "id": who,
            "role_associations": [{"role": role, "attributes": []}],
            "hierarchical_scopes": [],
        }},
    )


def build_stack(n_rules: int = 12, n_policies: int = 2, cache: bool = True):
    engine = AccessController()
    decision_cache = DecisionCache() if cache else None
    evaluator = HybridEvaluator(engine, decision_cache=decision_cache)
    store = PolicyStore(engine, evaluator=evaluator)
    rules = [rule_doc(f"r{i}", i) for i in range(n_rules)]
    per = max(1, n_rules // n_policies)
    pols = [
        {"id": f"p{p}", "combining_algorithm": PO,
         "rules": [f"r{i}" for i in range(p * per, min((p + 1) * per,
                                                       n_rules))]}
        for p in range(n_policies)
    ]
    sets_ = [{"id": "s0", "combining_algorithm": DO,
              "policies": [p["id"] for p in pols]}]
    store.seed(sets_, pols, rules)
    return engine, evaluator, store, decision_cache


# ------------------------------------------------------- table canonicalizer

_T_BOOL = {"t_has_role", "t_has_scoping", "t_hr_check", "t_skip_acl",
           "t_has_props"}
_T_INT = {"t_n_subjects", "t_n_res"}
_T_VOCAB = {"t_ent_w"}


def _canon_target_row(compiled, idx: int):
    a = compiled.arrays
    interner = compiled.interner

    def s(i):
        i = int(i)
        return None if i < 0 else interner.string(i)

    out = {}
    for name, key, _dtype in TARGET_COLUMNS:
        v = np.asarray(a[name][int(idx)])
        if name in _T_BOOL:
            out[key] = bool(v)
        elif name in _T_INT:
            out[key] = int(v)
        elif name in _T_VOCAB:
            out[key] = tuple(
                None if int(w) < 0 else compiled.entity_vocab[int(w)]
                for w in v
            )
        elif v.ndim:
            out[key] = tuple(s(x) for x in v)
        else:
            out[key] = s(v)
    rs = int(a["t_rs_idx"][int(idx)])
    out["rs"] = (s(a["hrv_role"][rs]), s(a["hrv_scope"][rs]))
    return tuple(sorted(out.items()))


def _rstrip_none(items: list) -> tuple:
    while items and items[-1] is None:
        items.pop()
    return tuple(items)


def canonical_tables(compiled):
    """Representation-free decode of the compiled tables: slot layout,
    intern ids, target-row numbering, vocab ordering and padding are all
    erased; everything the kernel can observe is kept."""
    a = compiled.arrays
    out = []
    for s in range(compiled.S):
        if not a["set_valid"][s]:
            continue
        pols = []
        for kp in range(compiled.KP):
            if not a["pol_valid"][s, kp]:
                pols.append(None)
                continue
            rules = []
            for kr in range(compiled.KR):
                if not a["rule_valid"][s, kp, kr]:
                    rules.append(None)
                    continue
                cond = None
                ci = int(a["rule_cond"][s, kp, kr])
                if ci >= 0:
                    cc = compiled.conditions[ci]
                    cond = (cc.condition, repr(cc.context_query))
                rules.append((
                    int(a["rule_effect"][s, kp, kr]),
                    bool(a["rule_cacheable_raw"][s, kp, kr]),
                    bool(a["rule_cacheable_eff"][s, kp, kr]),
                    _canon_target_row(compiled, a["rule_target"][s, kp, kr])
                    if a["rule_has_target"][s, kp, kr] else None,
                    cond,
                ))
            pols.append((
                int(a["pol_ca"][s, kp]),
                int(a["pol_effect"][s, kp]),
                int(a["pol_eff_ctx"][s, kp]),
                bool(a["pol_cacheable"][s, kp]),
                bool(a["pol_has_subjects"][s, kp]),
                bool(a["pol_has_props"][s, kp]),
                int(a["pol_n_rules"][s, kp]),
                _canon_target_row(compiled, a["pol_target"][s, kp])
                if a["pol_has_target"][s, kp] else None,
                _rstrip_none(rules),
            ))
        out.append((
            int(a["set_ca"][s]),
            _canon_target_row(compiled, a["set_target"][s])
            if a["set_has_target"][s] else None,
            _rstrip_none(pols),
        ))
    return tuple(out)


def assert_tables_match_full_compile(engine, evaluator):
    patched = evaluator._compiled
    fresh = compile_policies(engine.policy_sets, engine.urns)
    assert fresh.supported
    assert canonical_tables(patched) == canonical_tables(fresh)


def assert_decisions_match_oracle(engine, evaluator, corpus_keys,
                                  subjects=("u1", "u2")):
    requests = [make_request(k, who) for k in corpus_keys
                for who in subjects]
    got = evaluator.is_allowed_batch([make_request(k, who)
                                      for k in corpus_keys
                                      for who in subjects])
    want = [engine.is_allowed(r) for r in requests]
    for g, w, r in zip(got, want, requests):
        assert g.decision == w.decision, (r.target, g, w)
        assert g.evaluation_cacheable == w.evaluation_cacheable, (g, w)


# ------------------------------------------------------------------- tests


class TestDeltaPatch:
    def test_rule_modify_patches_without_full_compile(self):
        engine, ev, store, _ = build_stack()
        base_full = ev.delta_stats()["full_compiles"]
        svc = store.get_resource_service("rule")
        svc.update([rule_doc("r0", 0, effect="DENY")])
        stats = ev.delta_stats()
        assert stats["patches"] == 1
        assert stats["full_compiles"] == base_full
        assert stats["recompiles_avoided"] == 1
        assert_tables_match_full_compile(engine, ev)
        assert_decisions_match_oracle(engine, ev, range(12))

    def test_rule_create_attach_and_delete(self):
        engine, ev, store, _ = build_stack()
        rule_svc = store.get_resource_service("rule")
        pol_svc = store.get_resource_service("policy")
        # create an unreferenced rule: certified no-op (no flush, no patch)
        rule_svc.create([rule_doc("rx", 3, effect="DENY")])
        stats = ev.delta_stats()
        assert stats["noops"] >= 1
        # attach it: a real patch
        p0 = store.collections["policy"].get("p0")
        p0["rules"] = p0["rules"] + ["rx"]
        pol_svc.update([p0])
        assert ev.delta_stats()["patches"] >= 1
        assert_tables_match_full_compile(engine, ev)
        assert_decisions_match_oracle(engine, ev, range(12))
        # detach + delete: target row goes to the free list, then reuse it
        state = ev._delta_state
        t_live = state.t_live
        p0 = store.collections["policy"].get("p0")
        p0["rules"] = [r for r in p0["rules"] if r != "rx"]
        pol_svc.update([p0])
        rule_svc.delete(ids=["rx"])
        state = ev._delta_state
        assert state.free_rows, "deleted rule's target row must be freed"
        assert state.t_live == t_live
        p0 = store.collections["policy"].get("p0")
        p0["rules"] = p0["rules"] + ["r1"]  # r1 now in both policies? no: dup
        # attach a fresh rule instead: reuses the freed row slot
        rule_svc.create([rule_doc("ry", 7, effect="DENY")])
        p0["rules"][-1] = "ry"
        pol_svc.update([p0])
        state = ev._delta_state
        assert not state.free_rows, "freed row slot must be reused"
        assert state.t_live == t_live
        assert_tables_match_full_compile(engine, ev)
        assert_decisions_match_oracle(engine, ev, range(12))

    def test_capacity_overflow_falls_back_to_full_recompile(self):
        engine, ev, store, _ = build_stack(n_rules=8, n_policies=1)
        caps = ev._caps
        rule_svc = store.get_resource_service("rule")
        pol_svc = store.get_resource_service("policy")
        extra = [rule_doc(f"ov{i}", i, effect="DENY")
                 for i in range(caps.KR + 4)]
        rule_svc.create(extra)
        p0 = store.collections["policy"].get("p0")
        p0["rules"] = p0["rules"] + [r["id"] for r in extra]
        base_full = ev.delta_stats()["full_compiles"]
        pol_svc.update([p0])
        stats = ev.delta_stats()
        assert stats["full_compiles"] == base_full + 1
        assert "capacity-rules" in stats["fallback_reasons"]
        assert ev._caps.KR > caps.KR  # buckets grew
        assert_tables_match_full_compile(engine, ev)
        assert_decisions_match_oracle(engine, ev, range(8))

    def test_combining_algorithm_change_falls_back(self):
        engine, ev, store, _ = build_stack()
        pol_svc = store.get_resource_service("policy")
        p0 = store.collections["policy"].get("p0")
        p0["combining_algorithm"] = FA
        base_full = ev.delta_stats()["full_compiles"]
        pol_svc.update([p0])
        stats = ev.delta_stats()
        assert stats["full_compiles"] == base_full + 1
        assert "combining-algorithm-changed" in stats["fallback_reasons"]
        assert_tables_match_full_compile(engine, ev)
        assert_decisions_match_oracle(engine, ev, range(12))

    def test_condition_change_falls_back(self):
        engine, ev, store, _ = build_stack()
        rule_svc = store.get_resource_service("rule")
        doc = rule_doc("r2", 2)
        doc["condition"] = "True"
        base_full = ev.delta_stats()["full_compiles"]
        rule_svc.update([doc])
        stats = ev.delta_stats()
        assert stats["full_compiles"] == base_full + 1
        assert "condition-added" in stats["fallback_reasons"]
        assert_tables_match_full_compile(engine, ev)

    def test_set_membership_change_falls_back(self):
        engine, ev, store, _ = build_stack()
        set_svc = store.get_resource_service("policy_set")
        base_full = ev.delta_stats()["full_compiles"]
        set_svc.create([{"id": "s1", "combining_algorithm": DO,
                         "policies": ["p1"]}])
        stats = ev.delta_stats()
        assert stats["full_compiles"] == base_full + 1
        assert "set-list-changed" in stats["fallback_reasons"]
        assert_tables_match_full_compile(engine, ev)
        assert_decisions_match_oracle(engine, ev, range(12))

    def test_noop_update_skips_flush_and_compile(self):
        engine, ev, store, cache = build_stack()
        ev.is_allowed_batch([make_request(0), make_request(5)])
        epoch = cache.epoch
        stores = cache.stats()["stores"]
        svc = store.get_resource_service("rule")
        svc.update([rule_doc("r0", 0)])  # identical payload (meta restamped)
        assert cache.epoch == epoch, "no-op delta must not bump the epoch"
        stats = ev.delta_stats()
        assert stats["noops"] >= 1
        # warm entries survive untouched
        ev.is_allowed_batch([make_request(0), make_request(5)])
        post = cache.stats()
        assert post["stores"] == stores
        assert post["hits"] >= 2


class TestProgramReuse:
    def test_in_capacity_patch_compiles_no_new_programs(self):
        # decision cache OFF: post-patch cache hits would shrink the miss
        # batch and legitimately enter a new (smaller) batch bucket —
        # this test isolates mutation-caused recompiles
        engine, ev, store, _ = build_stack(cache=False)
        # warm every jitted program for this traffic shape
        ev.is_allowed_batch([make_request(k) for k in range(12)])
        kernel_before = ev._kernel
        shared = ev._shared_jits
        assert shared, "delta mode must register shared jits"
        sizes_before = {k: f._cache_size() for k, f in shared.items()}
        svc = store.get_resource_service("rule")
        svc.update([rule_doc("r3", 3, effect="DENY")])
        assert ev.delta_stats()["patches"] == 1
        assert ev._kernel is not kernel_before  # swapped object...
        ev.is_allowed_batch([make_request(k) for k in range(12)])
        sizes_after = {k: f._cache_size() for k, f in ev._shared_jits.items()}
        assert sizes_after == sizes_before, (
            "an in-capacity mutation must not add XLA compilations"
        )

    def test_patched_tables_share_shapes_with_bucketed_full_compile(self):
        engine, ev, store, _ = build_stack()
        svc = store.get_resource_service("rule")
        svc.update([rule_doc("r1", 1, effect="DENY")])
        assert ev.delta_stats()["patches"] == 1
        patched = ev._compiled
        full, caps, _state = delta_mod.full_bucketed_compile(
            engine.policy_sets, engine.urns, prev_caps=ev._caps
        )
        assert caps == ev._caps
        for name, arr in patched.arrays.items():
            assert np.asarray(arr).shape == np.asarray(
                full.arrays[name]).shape, name
            assert np.asarray(arr).dtype == np.asarray(
                full.arrays[name]).dtype, name

    def test_replicas_replaying_one_journal_hold_identical_tables(self):
        """Two stacks booted apart and fed the same CRUD sequence (update,
        create, delete, upsert) converge to byte-identical compiled
        tables and equal table fingerprints (the router's convergence
        probe), and so run one program and serve the same decisions."""
        stacks = [build_stack(cache=False) for _ in range(2)]
        for _engine, _ev, store, _ in stacks:
            rules = store.get_resource_service("rule")
            rules.update([rule_doc("r3", 3, effect="DENY")])
            rules.create([rule_doc("r12", 12)])
            rules.delete(ids=["r7"])
            rules.upsert([rule_doc("r12", 12, effect="DENY")])
        (_, ev1, _, _), (_, ev2, _, _) = stacks
        a1, a2 = ev1._compiled.arrays, ev2._compiled.arrays
        assert sorted(a1) == sorted(a2)
        for name in a1:
            x, y = np.asarray(a1[name]), np.asarray(a2[name])
            assert (x.dtype, x.shape) == (y.dtype, y.shape), name
            assert x.tobytes() == y.tobytes(), name
        assert ev1.table_fingerprint() is not None
        assert ev1.table_fingerprint() == ev2.table_fingerprint()
        requests = [make_request(k) for k in range(13)]
        assert [r.decision for r in ev1.is_allowed_batch(requests)] == \
            [r.decision for r in ev2.is_allowed_batch(requests)]


def _through_admission(ev, requests):
    """Rows admitted by an enabled controller, each with its deadline."""
    from access_control_srv_tpu.srv.admission import AdmissionController

    controller = AdmissionController(enabled=True)
    deadline = time.monotonic() + 3600.0
    for request in requests:
        assert controller.admit("interactive", deadline) is None
        request._deadline = deadline
    try:
        return ev.is_allowed_batch(requests)
    finally:
        controller.release("interactive", len(requests))
        controller.observe_batch("interactive", 0.004, len(requests))


def _through_tracing(ev, requests):
    """Every row sampled, with the profiler annotations the worker uses."""
    import jax

    from access_control_srv_tpu.srv.tracing import Observability, StageTracer

    tracer = StageTracer(sample_rate=1.0,
                         annotate=jax.profiler.TraceAnnotation)
    spans = []
    for request in requests:
        request._span = tracer.start_span()
        request._sampling_done = True
        spans.append(request._span)
    ev.obs = Observability(tracer=tracer)
    try:
        served = ev.is_allowed_batch(requests)
    finally:
        ev.obs = None
    for span in spans:
        tracer.finish(span)
    stages = {s["stage"] for t in tracer.traces() for s in t["stages"]}
    assert {"encode", "device", "decode"} <= stages, stages
    return served


def _through_failpoints(ev, requests):
    """Failpoints armed on both device-boundary sites, hit on every call."""
    from access_control_srv_tpu.srv.faults import REGISTRY

    with REGISTRY.arm([
        {"site": "device.dispatch", "action": "delay", "delay_s": 0.0},
        {"site": "device.materialize", "action": "delay", "delay_s": 0.0},
    ], seed=11):
        served = ev.is_allowed_batch(requests)
        hits = dict(REGISTRY.stats()["hits_by_site"])
    assert hits.get("device.dispatch", 0) >= 1, hits
    assert hits.get("device.materialize", 0) >= 1, hits
    return served


class TestHostSideSubsystems:
    @pytest.mark.parametrize("through", [
        _through_admission, _through_tracing, _through_failpoints,
    ], ids=["admission", "tracing", "failpoints"])
    def test_rows_run_the_programs_plain_rows_compiled(self, through):
        """Admission, stage tracing and failpoints act on the host around
        the dispatch: rows passed through them run the executables that
        plain rows of the same shape compiled (no new jit key, no new
        XLA compilation) and get the same decisions."""
        _, ev, _, _ = build_stack(cache=False)
        plain = ev.is_allowed_batch([make_request(k) for k in range(12)])
        keys = set(ev._shared_jits)
        sizes = {k: f._cache_size() for k, f in ev._shared_jits.items()}
        assert keys
        served = through(ev, [make_request(k) for k in range(12)])
        assert [r.decision for r in served] == [r.decision for r in plain]
        assert set(ev._shared_jits) == keys
        assert {k: f._cache_size()
                for k, f in ev._shared_jits.items()} == sizes


class TestScopedInvalidation:
    def test_disjoint_entries_survive_rule_mutation(self):
        engine, ev, store, cache = build_stack()
        ev.is_allowed_batch([make_request(0), make_request(1),
                             make_request(6)])
        assert cache.stats()["stores"] == 3
        svc = store.get_resource_service("rule")
        svc.update([rule_doc("r0", 0, effect="DENY")])
        out = ev.is_allowed_batch([make_request(0), make_request(1),
                                   make_request(6)])
        assert out[0].decision == "DENY"  # the mutation is visible
        stats = cache.stats()
        # entity-1 and entity-6 entries survived both scoped bumps
        assert stats["scoped_survivors"] >= 2
        assert stats["hits"] >= 2
        assert_decisions_match_oracle(engine, ev, range(12))

    def test_scoped_put_refusal_preserves_epoch_race_invariant(self):
        from access_control_srv_tpu.models.model import (
            OperationStatus,
            Response,
        )

        cache = DecisionCache()
        permit = Response(decision="PERMIT", evaluation_cacheable=True,
                          operation_status=OperationStatus())
        affected = request_features(
            make_request(0), URNS["entity"], URNS["operation"]
        )
        disjoint = request_features(
            make_request(1), URNS["entity"], URNS["operation"]
        )
        footprint = delta_mod.Footprint(scopes=[delta_mod.RuleScope(
            entities=(entity(0),), acts=(URNS["read"],),
        )])
        epoch = cache.epoch
        cache.bump_scoped(footprint)  # mutation lands mid-evaluation
        # affected writer: refused exactly as a global bump would
        assert not cache.put("a\x1fk", permit, epoch=epoch,
                             features=affected)
        # disjoint writer: provably unaffected, stored fresh
        assert cache.put("b\x1fk", permit, epoch=epoch, features=disjoint)
        assert cache.get("b\x1fk") is not None
        # feature-less writer: pre-delta semantics verbatim
        assert not cache.put("c\x1fk", permit, epoch=epoch)
        # global bump still flushes everything
        cache.bump_epoch()
        assert cache.get("b\x1fk") is None

    def test_regex_entity_pattern_widens_footprint(self):
        # pattern tail "Thing" regex-matches entity tail "Thing1" under a
        # shared "sub" namespace (core/hierarchical_scope semantics)
        footprint = delta_mod.Footprint(scopes=[delta_mod.RuleScope(
            entities=("urn:restorecommerce:acs:model:sub.Thing",),
        )])
        req = Request(target=Target(
            resources=[Attribute(
                id=URNS["entity"],
                value="urn:restorecommerce:acs:model:sub.Thing1",
            )],
        ))
        hit = request_features(req, URNS["entity"], URNS["operation"])
        assert footprint.affects(hit)
        miss = request_features(
            make_request(2), URNS["entity"], URNS["operation"]
        )
        assert not footprint.affects(miss)


class TestRefreshDebounce:
    def test_refresh_storm_coalesces_compiles(self):
        engine = AccessController()
        ev = HybridEvaluator(engine, async_compile=True)
        store = PolicyStore(engine, evaluator=ev)
        rules = [rule_doc(f"r{i}", i) for i in range(6)]
        store.seed(
            [{"id": "s0", "combining_algorithm": DO, "policies": ["p0"]}],
            [{"id": "p0", "combining_algorithm": PO,
              "rules": [r["id"] for r in rules]}],
            rules,
        )
        base = ev.delta_stats()["full_compiles"]
        for _ in range(20):
            ev.refresh()  # no events: always the full path
        deadline = time.time() + 30
        while time.time() < deadline:
            with ev._compile_state_lock:
                idle = (not ev._compile_pending
                        and (ev._compile_thread is None
                             or not ev._compile_thread.is_alive()))
            if idle:
                break
            time.sleep(0.02)
        ran = ev.delta_stats()["full_compiles"] - base
        assert 1 <= ran <= 3, f"20 refreshes ran {ran} compiles"
        with ev._lock:
            assert ev._compiled.version == ev._version  # converged
        ev.shutdown()
        assert ev._compile_thread is None or not ev._compile_thread.is_alive()

    def test_shutdown_joins_compile_thread(self):
        engine = AccessController()
        ev = HybridEvaluator(engine, async_compile=True)
        store = PolicyStore(engine, evaluator=ev)
        rules = [rule_doc(f"r{i}", i) for i in range(4)]
        store.seed(
            [{"id": "s0", "combining_algorithm": DO, "policies": ["p0"]}],
            [{"id": "p0", "combining_algorithm": PO,
              "rules": [r["id"] for r in rules]}],
            rules,
        )
        ev.refresh()
        ev.shutdown(timeout=30)
        thread = ev._compile_thread
        assert thread is None or not thread.is_alive()
        # a post-shutdown refresh must not spawn a new worker
        ev.refresh()
        assert ev._compile_thread is None or not ev._compile_thread.is_alive()


def _apply_random_op(rng, store, next_id):
    rule_svc = store.get_resource_service("rule")
    pol_svc = store.get_resource_service("policy")
    pol_ids = [d["id"] for d in store.collections["policy"].all()]
    op = rng.choice(["modify", "modify", "modify", "create", "delete",
                     "toggle_cacheable"])
    if op == "modify":
        docs = store.collections["rule"].all()
        doc = rng.choice(docs)
        k = rng.randrange(16)
        effect = rng.choice(["PERMIT", "DENY"])
        rule_svc.update([rule_doc(doc["id"], k, effect=effect,
                                  cacheable=doc.get(
                                      "evaluation_cacheable", True))])
    elif op == "toggle_cacheable":
        docs = store.collections["rule"].all()
        doc = rng.choice(docs)
        new = dict(doc)
        new["evaluation_cacheable"] = not doc.get(
            "evaluation_cacheable", False
        )
        rule_svc.update([new])
    elif op == "create":
        rid = f"f{next_id[0]}"
        next_id[0] += 1
        k = rng.randrange(16)
        rule_svc.create([rule_doc(rid, k,
                                  effect=rng.choice(["PERMIT", "DENY"]))])
        pid = rng.choice(pol_ids)
        p = store.collections["policy"].get(pid)
        rules = p["rules"]
        rules.insert(rng.randrange(len(rules) + 1), rid)
        pol_svc.update([p])
    else:  # delete
        pid = rng.choice(pol_ids)
        p = store.collections["policy"].get(pid)
        if len(p["rules"]) <= 1:
            return
        victim = rng.choice(p["rules"])
        p["rules"] = [r for r in p["rules"] if r != victim]
        pol_svc.update([p])
        if not any(victim in (d.get("rules") or [])
                   for d in store.collections["policy"].all()):
            rule_svc.delete(ids=[victim])


@pytest.mark.parametrize("seed", [11, 29, 47])
def test_differential_fuzz_random_crud_sequences(seed):
    """Random create/modify/delete sequences across rules and policies
    (including mid-list inserts and free-slot reuse): after EVERY
    mutation the patched tables canonically equal a from-scratch compile
    of the final tree, and kernel decisions equal the oracle."""
    rng = random.Random(seed)
    engine, ev, store, _cache = build_stack(n_rules=10, n_policies=2)
    next_id = [0]
    for step in range(14):
        _apply_random_op(rng, store, next_id)
        assert_tables_match_full_compile(engine, ev)
        if step % 4 == 3:
            assert_decisions_match_oracle(engine, ev, range(16))
    assert_decisions_match_oracle(engine, ev, range(16))
    stats = ev.delta_stats()
    assert stats["patches"] >= 5, stats  # the delta path actually engaged


def test_differential_fuzz_with_capacity_growth():
    """The same fuzz with bursts large enough to overflow KR/T buckets:
    full-recompile fallbacks interleave with patches and the tables stay
    canonically exact throughout."""
    rng = random.Random(7)
    engine, ev, store, _cache = build_stack(n_rules=6, n_policies=1)
    rule_svc = store.get_resource_service("rule")
    pol_svc = store.get_resource_service("policy")
    next_id = [1000]
    for burst in range(3):
        grow = ev._caps.KR  # guaranteed overflow of the current bucket
        docs = [rule_doc(f"g{next_id[0] + i}", i % 16,
                         effect=rng.choice(["PERMIT", "DENY"]))
                for i in range(grow)]
        next_id[0] += grow
        rule_svc.create(docs)
        p0 = store.collections["policy"].get("p0")
        p0["rules"] = p0["rules"] + [d["id"] for d in docs]
        pol_svc.update([p0])
        assert_tables_match_full_compile(engine, ev)
        for _ in range(3):
            _apply_random_op(rng, store, next_id)
            assert_tables_match_full_compile(engine, ev)
        assert_decisions_match_oracle(engine, ev, range(16))
    stats = ev.delta_stats()
    assert stats["fallbacks"] >= 1 and stats["patches"] >= 1, stats


@pytest.mark.slow
def test_churn_soak_serving_concurrent_with_mutations():
    """Sustained CRUD churn concurrent with serving: no exceptions, every
    decision matches a post-hoc oracle run, and the final tables equal a
    from-scratch compile."""
    engine, ev, store, cache = build_stack(n_rules=24, n_policies=3)
    stop = threading.Event()
    errors: list = []

    def mutate():
        rng = random.Random(3)
        next_id = [5000]
        while not stop.is_set():
            try:
                _apply_random_op(rng, store, next_id)
            except Exception as err:  # noqa: BLE001
                errors.append(err)
                return
            time.sleep(0.002)

    def serve():
        rng = random.Random(4)
        while not stop.is_set():
            keys = [rng.randrange(16) for _ in range(16)]
            try:
                out = ev.is_allowed_batch([make_request(k) for k in keys])
            except Exception as err:  # noqa: BLE001
                errors.append(err)
                return
            for resp in out:
                if resp.decision not in ("PERMIT", "DENY",
                                         "INDETERMINATE"):
                    errors.append(AssertionError(resp))
                    return

    threads = [threading.Thread(target=mutate)] + [
        threading.Thread(target=serve) for _ in range(2)
    ]
    for t in threads:
        t.start()
    time.sleep(4.0)
    stop.set()
    for t in threads:
        t.join(30)
    assert not errors, errors[:3]
    assert_tables_match_full_compile(engine, ev)
    assert_decisions_match_oracle(engine, ev, range(16))
    assert ev.delta_stats()["patches"] >= 5
