"""ReBAC differential suite: relation tuples against the bit-reader.

The tuple store (srv/relations.py) folds Zanzibar-style relationship
closures into the stage-B bitplane format at encode time
(ops/relation.pack_relation_bitplanes); the scalar oracle walks the same
graph per decision (core/relation_path.py).  These tests pin the two
paths bit-identical across rewrite kinds (direct / computed-userset /
tuple-to-userset), path depths 1..6 and every kernel variant, plus the
store's incremental-closure identity (patched tables == from-scratch),
cycle safety, tenant isolation on a shared bus, and the serving
invariant that tuple churn swaps no compiled program.
"""

import random

import numpy as np
import pytest

from access_control_srv_tpu.core.relation_path import (
    RelationGraph,
    check_relation_path,
    parse_path,
)
from access_control_srv_tpu.ops import compile_policies, encode_requests
from access_control_srv_tpu.ops.kernel import DecisionKernel
from access_control_srv_tpu.ops.prefilter import PrefilteredKernel
from access_control_srv_tpu.ops.relation import relation_bits_needed
from access_control_srv_tpu.srv.events import EventBus
from access_control_srv_tpu.srv.relations import RelationTupleStore

from .test_kernel_differential import DEC_CODE
from .test_prefilter import force_active
from .utils import build_request, fixture, make_engine

NS = "urn:restorecommerce:acs:model:document.Document"
FOLDER = "urn:restorecommerce:acs:model:folder.Folder"
READ = "urn:restorecommerce:acs:names:action:read"


def _populate(tmp_path, value):
    from access_control_srv_tpu.core.engine import AccessController
    from access_control_srv_tpu.core.loader import populate

    text = open(fixture("relation_policies.yml")).read()
    assert text.count("value: viewer") == 1
    p = tmp_path / "rebac.yml"
    p.write_text(text.replace("value: viewer", f"value: {value}"))
    eng = AccessController()
    populate(eng, str(p))
    return eng


def _requests(subjects, resource_ids):
    return [
        build_request(
            subject_id=s, resource_type=NS, resource_id=r, action_type=READ
        )
        for s in subjects
        for r in resource_ids
    ]


def _differential(eng, store, requests, kern=None):
    """Kernel decisions on relation planes == oracle walk, row by row."""
    compiled = compile_policies(eng.policy_sets, eng.urns)
    assert compiled.supported and relation_bits_needed(compiled)
    kern = kern or DecisionKernel(compiled)
    eng.relation_store = store
    batch = encode_requests(
        requests, compiled,
        relation_tables=store.tables_for(compiled) if store else None,
    )
    decision, _, status = kern.evaluate(batch)
    n = 0
    for b, req in enumerate(requests):
        if not batch.eligible[b] or status[b] != 200:
            continue
        expected = eng.is_allowed(req)
        assert decision[b] == DEC_CODE[expected.decision], (
            b, expected.decision)
        n += 1
    assert n > 0
    return n


# ------------------------------------------------------- rewrite kinds


@pytest.mark.parametrize(
    "kind", ["direct", "computed_userset", "tuple_to_userset"]
)
def test_rewrite_kinds_kernel_vs_oracle(kind):
    eng = make_engine("relation_policies.yml")
    store = RelationTupleStore()
    if kind == "direct":
        store.create([(NS, "doc1", "viewer", "alice")])
    elif kind == "computed_userset":
        store.set_rewrite(
            NS, "viewer", [("this",), ("computed_userset", "owner")]
        )
        store.create([(NS, "doc1", "owner", "alice")])
    else:  # tuple_to_userset: doc viewers include the parent's viewers
        store.set_rewrite(
            NS, "viewer",
            [("this",), ("tuple_to_userset", "parent", "viewer")],
        )
        store.create([
            (NS, "doc1", "parent", {"object": {"entity": FOLDER,
                                               "id": "f1"}}),
            (FOLDER, "f1", "viewer", "alice"),
        ])
    reqs = _requests(["alice", "bob"], ["doc1", "doc2"])
    _differential(eng, store, reqs)
    # alice sees doc1 through every rewrite kind; bob never does
    assert eng.is_allowed(reqs[0]).decision == "PERMIT"
    assert eng.is_allowed(reqs[2]).decision == "DENY"


# ----------------------------------------------------------- path depth


@pytest.mark.parametrize("depth", [1, 2, 3, 4, 5, 6])
def test_depth_chain_kernel_vs_oracle(depth, tmp_path):
    """Multi-step path expressions (parent.parent....owner) against a
    folder chain of the matching depth; one hop short must fail."""
    path = ".".join(["parent"] * (depth - 1) + ["owner"])
    eng = _populate(tmp_path, path)
    store = RelationTupleStore()
    if depth == 1:
        store.create([(NS, "doc1", "owner", "alice")])
    else:
        chain = [(NS, "doc1", "parent",
                  {"object": {"entity": FOLDER, "id": "f1"}})]
        for i in range(1, depth - 1):
            chain.append((FOLDER, f"f{i}", "parent",
                          {"object": {"entity": FOLDER, "id": f"f{i+1}"}}))
        chain.append((FOLDER, f"f{depth-1}", "owner", "alice"))
        store.create(chain)
        # a second doc whose chain stops one folder short: never reaches
        store.create([(NS, "doc2", "parent",
                       {"object": {"entity": FOLDER, "id": f"f{depth-1}"}})])
    _differential(eng, store, _requests(["alice", "bob"], ["doc1", "doc2"]))
    assert store.check(path, NS, "doc1", "alice")
    assert not store.check(path, NS, "doc1", "bob")


# ------------------------------------------------------ kernel variants


def test_kernel_variants_agree():
    """Dense, signature-prefiltered and pod-sharded kernels read the
    same relation planes to the same decisions."""
    from jax.sharding import Mesh
    import jax

    from access_control_srv_tpu.parallel.pod_shard import PodShardedKernel

    eng = make_engine("relation_policies.yml")
    compiled = compile_policies(eng.policy_sets, eng.urns)
    store = RelationTupleStore()
    store.set_rewrite(
        NS, "viewer", [("this",), ("computed_userset", "owner")]
    )
    store.create([
        (NS, "doc1", "owner", "alice"),
        (NS, "doc2", "viewer", "bob"),
        (NS, "doc3", "viewer", {"object": {"entity": "group", "id": "g"},
                                "relation": "member"}),
        ("group", "g", "member", "carol"),
    ])
    reqs = _requests(["alice", "bob", "carol", "mallory"],
                     ["doc1", "doc2", "doc3", ["doc1", "doc3"]])
    batch = encode_requests(
        reqs, compiled, relation_tables=store.tables_for(compiled)
    )
    dense = DecisionKernel(compiled)
    pre = force_active(PrefilteredKernel(compiled))
    devices = np.array(jax.devices()[:8]).reshape(2, 4)
    sharded = PodShardedKernel(compiled, Mesh(devices, ("data", "model")))

    d_ref, c_ref, s_ref = dense.evaluate(batch)
    for kern in (pre, sharded):
        d, c, s = kern.evaluate(batch)
        el = batch.eligible
        assert np.array_equal(d[el], d_ref[el])
        assert np.array_equal(c[el], c_ref[el])
        assert np.array_equal(s[el], s_ref[el])
    eng.relation_store = store
    for b in range(len(reqs)):
        if batch.eligible[b]:
            assert d_ref[b] == DEC_CODE[eng.is_allowed(reqs[b]).decision], b


# --------------------------------------------------- incremental closure


_FUZZ_RELS = ["viewer", "owner", "editor", "parent"]
_FUZZ_USERS = [f"u{i}" for i in range(6)]
_FUZZ_OBJS = [(NS, f"doc{i}") for i in range(4)] + [
    (FOLDER, f"f{i}") for i in range(3)
]


def _random_tuple(rng):
    ns, oid = rng.choice(_FUZZ_OBJS)
    rel = rng.choice(_FUZZ_RELS)
    kind = rng.random()
    if kind < 0.6:
        subj = rng.choice(_FUZZ_USERS)
    elif kind < 0.85:
        ons, ooid = rng.choice(_FUZZ_OBJS)
        subj = {"object": {"entity": ons, "id": ooid}}
    else:
        ons, ooid = rng.choice(_FUZZ_OBJS)
        subj = {"object": {"entity": ons, "id": ooid},
                "relation": rng.choice(_FUZZ_RELS)}
    return (ns, oid, rel, subj)


@pytest.mark.parametrize("seed", [7, 1201])
def test_fuzz_patched_tables_equal_fresh(seed, tmp_path):
    """Random create/delete/rewrite churn: the incrementally-invalidated
    closure must produce byte-identical verdict tables (and fingerprint)
    to a store rebuilt from scratch at the final state — the delta
    soundness property of the dependency-recording memo cache."""
    eng = _populate(tmp_path, "viewer|parent.owner")
    compiled = compile_policies(eng.policy_sets, eng.urns)
    rng = random.Random(seed)
    store = RelationTupleStore()
    live: list[tuple] = []
    for step in range(120):
        op = rng.random()
        if op < 0.15 and live:
            victim = rng.choice(live)
            store.delete([victim])
            live.remove(victim)
        elif op < 0.25:
            ns, _ = rng.choice(_FUZZ_OBJS)
            rel = rng.choice(_FUZZ_RELS)
            rules = [("this",)]
            if rng.random() < 0.5:
                rules.append(("computed_userset", rng.choice(_FUZZ_RELS)))
            if rng.random() < 0.3:
                rules.append(("tuple_to_userset", "parent",
                              rng.choice(_FUZZ_RELS)))
            store.set_rewrite(ns, rel, rules)
        else:
            t = _random_tuple(rng)
            if store.create([t]):
                live.append(t)
        if step % 40 == 17:
            # mid-churn: warm the memo cache so later invalidations have
            # stale entries to catch
            store.tables_for(compiled)

    fresh = RelationTupleStore()
    for (ns, rel), rules in store.graph.rewrites.items():
        fresh.set_rewrite(ns, rel, rules)
    fresh.create(live)
    assert store.fingerprint() == fresh.fingerprint()
    patched = store.tables_for(compiled)
    rebuilt = fresh.tables_for(compiled)
    for name in ("obj_offs", "obj_keys", "pairs"):
        assert np.array_equal(patched[name], rebuilt[name]), name

    # cached-closure verdicts == uncached oracle walk on sampled queries
    for expr in ("viewer", "parent.owner", "owner!direct",
                 "viewer|parent.owner"):
        path = parse_path(expr)
        for ns, oid in _FUZZ_OBJS:
            for user in _FUZZ_USERS:
                assert store.check(expr, ns, oid, user) == \
                    check_relation_path(path, ns, oid, user, store.graph), \
                    (expr, ns, oid, user)


def test_cycle_safe_closure():
    """Mutually-recursive usersets terminate and stay correct: group a's
    members include group b's and vice versa."""
    g = RelationGraph()
    g.add("group", "a", "member",
          {"object": {"entity": "group", "id": "b"}, "relation": "member"})
    g.add("group", "b", "member",
          {"object": {"entity": "group", "id": "a"}, "relation": "member"})
    g.add("group", "b", "member", "alice")
    path = parse_path("member")
    assert check_relation_path(path, "group", "a", "alice", g)
    assert check_relation_path(path, "group", "b", "alice", g)
    assert not check_relation_path(path, "group", "a", "bob", g)

    store = RelationTupleStore()
    store.create([
        ("group", "a", "member",
         {"object": {"entity": "group", "id": "b"}, "relation": "member"}),
        ("group", "b", "member",
         {"object": {"entity": "group", "id": "a"}, "relation": "member"}),
        ("group", "b", "member", "alice"),
    ])
    assert store.check("member", "group", "a", "alice")
    assert store.witness("member", "group", "a", "alice") is not None
    assert not store.check("member", "group", "a", "bob")


# ---------------------------------------------------- bus + replication


def test_tenant_isolation_on_shared_bus():
    """Tenant-tagged tuple frames on one shared topic apply only to the
    matching tenant's store — cross-tenant tuples can never leak into
    another domain's closure."""
    bus = EventBus()
    a1 = RelationTupleStore(bus=bus, tenant="acme")
    a2 = RelationTupleStore(bus=bus, tenant="acme")
    b = RelationTupleStore(bus=bus, tenant="globex")
    for s in (a2, b):
        s.start_replication()
    a1.create([(NS, "doc1", "viewer", "alice")])
    assert a2.check("viewer", NS, "doc1", "alice")  # same tenant: applied
    assert not b.check("viewer", NS, "doc1", "alice")  # isolated
    assert a1.fingerprint() == a2.fingerprint()
    assert b.fingerprint() != a1.fingerprint()
    # origin-skip: the writer must not re-apply its own frame (gen stays
    # at one bump per mutation)
    assert a1.generation == 1


def test_boot_replay_converges():
    """A store attaching AFTER the churn replays the journaled topic to
    the survivor's exact fingerprint (the broker-durability boot path)."""
    bus = EventBus()
    first = RelationTupleStore(bus=bus)
    first.set_rewrite(NS, "viewer",
                      [("this",), ("computed_userset", "owner")])
    first.create([(NS, "doc1", "owner", "alice"),
                  (NS, "doc2", "viewer", "bob")])
    first.delete([(NS, "doc2", "viewer", "bob")])

    late = RelationTupleStore(bus=bus)
    late.replay()
    assert late.fingerprint() == first.fingerprint()
    assert late.check("viewer", NS, "doc1", "alice")
    assert not late.check("viewer", NS, "doc2", "bob")


# ------------------------------------------------- serving invariants


def test_fail_closed_without_store():
    """No tuple store: relation-bearing targets deny on the oracle AND
    the kernel (empty-table planes) — never PERMIT by omission."""
    eng = make_engine("relation_policies.yml")
    _differential(eng, None, _requests(["alice"], ["doc1"]))
    assert eng.is_allowed(_requests(["alice"], ["doc1"])[0]) \
        .decision == "DENY"


def test_tuple_churn_swaps_no_program():
    """The ReBAC serving invariant: tuple CRUD flips decisions with the
    compiled tables, kernel and jitted executables all byte-identical —
    only the host-side verdict tables and the decision-cache epoch move."""
    from access_control_srv_tpu.srv.evaluator import HybridEvaluator

    eng = make_engine("relation_policies.yml")
    ev = HybridEvaluator(eng)
    ev.refresh()
    store = RelationTupleStore()
    ev.attach_relation_store(store)
    req = _requests(["bob"], ["doc1"])[0]

    version = ev._compiled.version
    jits = set(ev._shared_jits.keys())
    assert ev.is_allowed(req).decision == "DENY"
    store.create([(NS, "doc1", "viewer", "bob")])
    assert ev.is_allowed(req).decision == "PERMIT"
    store.delete([(NS, "doc1", "viewer", "bob")])
    assert ev.is_allowed(req).decision == "DENY"
    assert ev._compiled.version == version
    assert set(ev._shared_jits.keys()) == jits

    # the same flip on the batch path, which dispatches to the device:
    # the executables that served the closed tuple serve the open one
    batch = _requests(["alice", "bob"], ["doc1", "doc2"])
    closed = [r.decision for r in ev.is_allowed_batch(batch)]
    sizes = {k: f._cache_size() for k, f in ev._shared_jits.items()}
    assert sizes
    store.create([(NS, "doc1", "viewer", "bob")])
    opened = [r.decision for r in ev.is_allowed_batch(batch)]
    assert closed[2] == "DENY" and opened[2] == "PERMIT"  # bob / doc1
    assert {k: f._cache_size() for k, f in ev._shared_jits.items()} == sizes
    store.delete([(NS, "doc1", "viewer", "bob")])

    # replica convergence covers tuple state: equal policy tables with
    # divergent tuple logs must fingerprint differently
    fp_before = ev.table_fingerprint()
    store.create([(NS, "doc9", "viewer", "eve")])
    assert ev.table_fingerprint() != fp_before
