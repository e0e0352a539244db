"""Decision-cache correctness (ISSUE 1 tentpole): unit coverage for TTL
expiry, the LRU bound, epoch flush and subject-prefix eviction; worker-level
coverage for all four invalidation paths (CRUD epoch, userModified /
userDeleted, flush_cache command, TTL); and the differential suite asserting
cache-on vs cache-off bit-identical decision streams under randomized
CRUD/userModified interleavings (the semantics bar: cache on/off must never
change a decision)."""

import random

import pytest

from access_control_srv_tpu.models import Decision, Response
from access_control_srv_tpu.models.model import OperationStatus
from access_control_srv_tpu.srv import Worker
from access_control_srv_tpu.srv.decision_cache import (
    DecisionCache,
    request_fingerprint,
)

from .test_srv import ORG, PO, READ, SEED, admin_request, seed_cfg
from .utils import URNS, build_request

USERS_TOPIC = "io.restorecommerce.users.resource"


def permit_response(message="success"):
    return Response(
        decision=Decision.PERMIT,
        obligations=[],
        evaluation_cacheable=True,
        operation_status=OperationStatus(code=200, message=message),
    )


class FakeClock:
    def __init__(self):
        self.now = 1000.0

    def __call__(self):
        return self.now


# ------------------------------------------------------------------ unit


class TestDecisionCacheUnit:
    def test_ttl_expiry(self):
        clock = FakeClock()
        cache = DecisionCache(ttl_s=10.0, time_fn=clock)
        cache.put("alice\x1fk1", permit_response())
        assert cache.get("alice\x1fk1").decision == Decision.PERMIT
        clock.now += 9.9
        assert cache.get("alice\x1fk1") is not None
        clock.now += 0.2  # past write + ttl
        assert cache.get("alice\x1fk1") is None
        stats = cache.stats()
        assert stats["evictions"] == 1  # lazily collected on lookup
        assert stats["entries"] == 0

    def test_lru_bound_and_recency(self):
        cache = DecisionCache(max_entries=4, shards=1)
        for i in range(4):
            cache.put(f"u\x1fk{i}", permit_response())
        # touch k0 so k1 is now least-recently-used
        assert cache.get("u\x1fk0") is not None
        cache.put("u\x1fk4", permit_response())
        assert cache.stats()["entries"] == 4
        assert cache.get("u\x1fk1") is None  # LRU victim
        assert cache.get("u\x1fk0") is not None  # recency protected it
        assert cache.stats()["evictions"] == 1

    def test_epoch_flush_is_logical(self):
        cache = DecisionCache()
        cache.put("u\x1fk", permit_response())
        cache.bump_epoch()
        assert cache.get("u\x1fk") is None
        # stale-epoch entries count as miss + eviction and are collected
        stats = cache.stats()
        assert stats["misses"] == 1 and stats["evictions"] == 1
        # writes under the new epoch serve again
        cache.put("u\x1fk", permit_response())
        assert cache.get("u\x1fk") is not None

    def test_subject_prefix_eviction(self):
        cache = DecisionCache()
        alice = build_request(subject_id="alice", subject_role="r1",
                              resource_type=ORG, resource_id="O1",
                              action_type=READ)
        # "alice2" shares a string prefix with "alice" but is a distinct
        # subject: the separator must keep it out of alice's eviction
        alice2 = build_request(subject_id="alice2", subject_role="r1",
                               resource_type=ORG, resource_id="O1",
                               action_type=READ)
        bob = build_request(subject_id="bob", subject_role="r1",
                            resource_type=ORG, resource_id="O1",
                            action_type=READ)
        keys = [request_fingerprint(r) for r in (alice, alice2, bob)]
        assert keys[0].startswith("alice\x1f")
        for key in keys:
            cache.put(key, permit_response())
        assert cache.evict_subject("alice") == 1
        assert cache.get(keys[0]) is None
        assert cache.get(keys[1]) is not None
        assert cache.get(keys[2]) is not None

    def test_flush_and_pattern_eviction(self):
        cache = DecisionCache()
        cache.put("alice\x1fk", permit_response())
        cache.put("alina\x1fk", permit_response())
        cache.put("bob\x1fk", permit_response())
        assert cache.evict_pattern("ali") == 2  # prefix semantics
        assert cache.stats()["entries"] == 1
        # empty pattern = full flush (reference flush_cache without pattern)
        epoch = cache.stats()["epoch"]
        assert cache.evict_pattern("") == 1
        stats = cache.stats()
        assert stats["entries"] == 0 and stats["epoch"] == epoch + 1

    def test_put_refuses_stale_epoch_snapshot(self):
        # a decision whose evaluation spans an epoch bump (CRUD/restore
        # completing mid-walk) must never be stored as fresh: the writer's
        # lookup-time snapshot, not the epoch at write time, stamps it
        cache = DecisionCache()
        epoch = cache.epoch  # snapshot at lookup/miss time
        cache.bump_epoch()   # tree mutation lands while computing
        assert not cache.put("u\x1fk", permit_response(), epoch=epoch)
        assert cache.get("u\x1fk") is None
        assert cache.stats()["entries"] == 0
        # a snapshot matching the current epoch stores normally
        assert cache.put("u\x1fk", permit_response(), epoch=cache.epoch)
        assert cache.get("u\x1fk") is not None

    def test_put_gates_on_cacheable_and_status(self):
        cache = DecisionCache()
        uncacheable = permit_response()
        uncacheable.evaluation_cacheable = False
        unknown = permit_response()
        unknown.evaluation_cacheable = None
        errored = permit_response()
        errored.operation_status = OperationStatus(code=500, message="boom")
        assert not cache.put("u\x1fa", uncacheable)
        assert not cache.put("u\x1fb", unknown)
        assert not cache.put("u\x1fc", errored)
        assert cache.put("u\x1fd", permit_response())
        assert cache.stats()["entries"] == 1

    def test_disabled_cache_never_stores_or_hits(self):
        cache = DecisionCache(enabled=False)
        assert not cache.put("u\x1fk", permit_response())
        assert cache.get("u\x1fk") is None
        assert cache.stats()["misses"] == 0  # disabled lookups not counted

    def test_hit_returns_fresh_response_object(self):
        cache = DecisionCache()
        cache.put("u\x1fk", permit_response())
        first = cache.get("u\x1fk")
        first.decision = Decision.DENY  # caller mutates its copy
        second = cache.get("u\x1fk")
        assert second.decision == Decision.PERMIT


class TestRequestFingerprint:
    def test_attribute_order_insensitive(self):
        base = build_request(subject_id="u1", subject_role="r1",
                             resource_type=ORG, resource_id="O1",
                             action_type=READ)
        shuffled = build_request(subject_id="u1", subject_role="r1",
                                 resource_type=ORG, resource_id="O1",
                                 action_type=READ)
        shuffled.target.subjects = list(reversed(shuffled.target.subjects))
        shuffled.target.resources = list(reversed(shuffled.target.resources))
        assert request_fingerprint(base) == request_fingerprint(shuffled)

    def test_context_changes_key(self):
        plain = build_request(subject_id="u1", subject_role="r1",
                              resource_type=ORG, resource_id="O1",
                              action_type=READ)
        scoped = build_request(subject_id="u1", subject_role="r1",
                               role_scoping_entity=ORG,
                               role_scoping_instance="system",
                               resource_type=ORG, resource_id="O1",
                               action_type=READ)
        assert request_fingerprint(plain) != request_fingerprint(scoped)

    def test_derived_context_keys_excluded(self):
        a = build_request(subject_id="u1", subject_role="r1",
                          resource_type=ORG, resource_id="O1",
                          action_type=READ)
        b = build_request(subject_id="u1", subject_role="r1",
                          resource_type=ORG, resource_id="O1",
                          action_type=READ)
        b.context["_queryResult"] = [{"id": "res"}]  # evaluation output
        assert request_fingerprint(a) == request_fingerprint(b)

    def test_no_target_not_cacheable(self):
        from access_control_srv_tpu.models import Request

        assert request_fingerprint(Request(target=None, context={})) is None


class TestFingerprintSingleComputation:
    """The fingerprint digest is memoized on the request object
    (``_dc_key``): however many layers consult the cache — batcher fast
    path, evaluator single path, batch keying — one request pays for
    exactly one blake2b computation."""

    @pytest.fixture()
    def digest_counter(self, monkeypatch):
        from access_control_srv_tpu.srv import decision_cache as dc

        calls = {"n": 0}
        real = dc.blake2b

        def counting(*args, **kwargs):
            calls["n"] += 1
            return real(*args, **kwargs)

        monkeypatch.setattr(dc, "blake2b", counting)
        return calls

    def test_unit_repeat_fingerprint_is_free(self, digest_counter):
        request = build_request(subject_id="u1", subject_role="r1",
                                resource_type=ORG, resource_id="O1",
                                action_type=READ)
        key = request_fingerprint(request)
        assert key is not None and digest_counter["n"] == 1
        assert request_fingerprint(request) == key
        assert digest_counter["n"] == 1  # memoized, not recomputed

    def test_serving_path_computes_fingerprint_once(self, worker,
                                                    digest_counter):
        request = admin_request()
        assert worker.service.is_allowed(request).decision == Decision.PERMIT
        assert digest_counter["n"] == 1  # cold miss: one digest total
        # the same object resubmitted rides its memo through every layer
        assert worker.service.is_allowed(request).decision == Decision.PERMIT
        assert digest_counter["n"] == 1
        # a fresh equivalent request pays one digest for its cache hit
        digest_counter["n"] = 0
        assert worker.service.is_allowed(
            admin_request()
        ).decision == Decision.PERMIT
        assert digest_counter["n"] == 1

    def test_batch_path_computes_one_fingerprint_per_request(
            self, worker, digest_counter):
        requests = [admin_request() for _ in range(4)]
        responses = worker.service.is_allowed_batch(requests)
        assert all(r.decision == Decision.PERMIT for r in responses)
        assert digest_counter["n"] == len(requests)


# ---------------------------------------------------------------- worker


def reader_rule(rid="r_reader", role="reader-role", effect="PERMIT",
                cacheable=True):
    return {
        "id": rid,
        "name": rid,
        "target": {
            "subjects": [{"id": URNS["role"], "value": role}],
            "resources": [{"id": URNS["entity"], "value": ORG}],
            "actions": [{"id": URNS["actionID"], "value": READ}],
        },
        "effect": effect,
        "evaluation_cacheable": cacheable,
    }


def install_reader_tree(worker, **rule_kwargs):
    worker.store.get_resource_service("rule").create(
        [reader_rule(**rule_kwargs)]
    )
    worker.store.get_resource_service("policy").create(
        [{"id": "p_readers", "combining_algorithm": PO,
          "rules": ["r_reader"], "evaluation_cacheable": True}]
    )
    worker.store.get_resource_service("policy_set").create(
        [{"id": "ps_readers", "combining_algorithm": PO,
          "policies": ["p_readers"]}]
    )


def reader_request(subject_id="u-reader"):
    return build_request(subject_id=subject_id, subject_role="reader-role",
                         role_scoping_entity=ORG,
                         role_scoping_instance="system",
                         resource_type=ORG, resource_id="O1",
                         action_type=READ)


@pytest.fixture()
def worker():
    w = Worker().start(seed_cfg())
    yield w
    w.stop()


def test_batch_cache_hits_never_reach_the_device():
    """A warm hit is answered on the host: with the kernel replaced by
    one that records every call, a repeat batch of cacheable rows is
    served from the cache alone, decision for decision."""
    from access_control_srv_tpu.models import Attribute, Request, Target
    from access_control_srv_tpu.srv.evaluator import HybridEvaluator
    from synth import stress_engine

    engine, _ = stress_engine(64, cacheable=True)
    cache = DecisionCache(ttl_s=3600.0)
    ev = HybridEvaluator(engine, decision_cache=cache)
    # row i meets rule r{i}: role-i on Stress{i}, with its action
    actions = [URNS[a] for a in ("read", "modify", "create", "delete")]
    requests = [
        Request(
            target=Target(
                subjects=[Attribute(id=URNS["role"], value=f"role-{i}"),
                          Attribute(id=URNS["subjectID"], value=f"u{i}")],
                resources=[Attribute(
                    id=URNS["entity"],
                    value=f"urn:restorecommerce:acs:model:stress{i}.Stress{i}",
                )],
                actions=[Attribute(id=URNS["actionID"], value=actions[i % 4])],
            ),
            context={"resources": [], "subject": {
                "id": f"u{i}",
                "role_associations": [{"role": f"role-{i}",
                                       "attributes": []}],
                "hierarchical_scopes": [],
            }},
        )
        for i in range(16)
    ]
    warm = ev.is_allowed_batch(requests)
    cacheable = [(r, w) for r, w in zip(requests, warm)
                 if w.evaluation_cacheable is True]
    assert cacheable

    class NoDevice:
        calls = []

        def evaluate(self, batch):
            self.calls.append(batch)
            raise AssertionError("a cache hit reached the device")

        evaluate_async = evaluate

    assert ev._kernel is not None  # the cold rows rode the device
    ev._kernel = NoDevice()
    ev._native_encoder = None
    hits = cache.stats()["hits"]
    served = ev.is_allowed_batch([r for r, _ in cacheable])
    assert NoDevice.calls == []
    assert cache.stats()["hits"] - hits == len(cacheable)
    assert [r.decision for r in served] == [w.decision for _, w in cacheable]


class TestWorkerCachePath:
    def test_repeat_traffic_served_from_cache(self, worker):
        cold = worker.service.is_allowed(admin_request())
        assert cold.decision == Decision.PERMIT
        assert cold.evaluation_cacheable is True
        hits_before = worker.decision_cache.stats()["hits"]
        warm = worker.service.is_allowed(admin_request())
        stats = worker.decision_cache.stats()
        assert stats["hits"] == hits_before + 1
        assert (warm.decision, warm.evaluation_cacheable,
                warm.operation_status.code) == \
            (cold.decision, cold.evaluation_cacheable,
             cold.operation_status.code)
        assert worker.telemetry.paths.snapshot().get("cache-hit", 0) >= 1

    def test_crud_update_invalidates_before_serving(self, worker):
        install_reader_tree(worker)
        request = reader_request()
        assert worker.service.is_allowed(request).decision == Decision.PERMIT
        assert worker.service.is_allowed(request).decision == Decision.PERMIT
        assert worker.decision_cache.stats()["hits"] >= 1
        # rule flip must serve immediately — a stale cached PERMIT after
        # the tree swap would be a correctness bug, not a staleness window
        worker.store.get_resource_service("rule").update(
            [reader_rule(effect="DENY")]
        )
        assert worker.service.is_allowed(request).decision == Decision.DENY

    def test_rule_delete_invalidates(self, worker):
        install_reader_tree(worker)
        request = reader_request()
        assert worker.service.is_allowed(request).decision == Decision.PERMIT
        worker.store.get_resource_service("rule").delete(["r_reader"])
        assert worker.service.is_allowed(request).decision != Decision.PERMIT

    def test_user_events_evict_subject(self, worker):
        warm = worker.service.is_allowed(admin_request())
        assert warm.evaluation_cacheable is True
        evictions = worker.decision_cache.stats()["evictions"]
        worker.bus.topic(USERS_TOPIC).emit("userModified", {"id": "root"})
        assert worker.decision_cache.stats()["evictions"] == evictions + 1
        # re-warm, then userDeleted takes the same eviction path
        worker.service.is_allowed(admin_request())
        evictions = worker.decision_cache.stats()["evictions"]
        worker.bus.topic(USERS_TOPIC).emit("userDeleted", {"id": "root"})
        assert worker.decision_cache.stats()["evictions"] == evictions + 1

    def test_user_event_other_subject_keeps_entries(self, worker):
        worker.service.is_allowed(admin_request())
        entries = worker.decision_cache.stats()["entries"]
        assert entries >= 1
        worker.bus.topic(USERS_TOPIC).emit("userModified", {"id": "someone"})
        assert worker.decision_cache.stats()["entries"] == entries

    def test_flush_cache_db_index_routing(self, worker):
        worker.service.is_allowed(admin_request())
        assert worker.decision_cache.stats()["entries"] >= 1
        # db 4 (subject cache analog) leaves decisions alone
        out = worker.command_interface.command(
            "flush_cache", {"data": {"db_index": 4}}
        )
        assert "decisions" not in out["flushed"]
        assert worker.decision_cache.stats()["entries"] >= 1
        # db 5 (the reference acs-client decision cache DB) flushes them
        out = worker.command_interface.command(
            "flush_cache", {"data": {"db_index": 5}}
        )
        assert out["flushed"]["decisions"] >= 1
        assert worker.decision_cache.stats()["entries"] == 0

    def test_flush_cache_string_db_index_coerced(self, worker):
        # loosely-typed JSON payloads send "5": the command must coerce
        # and flush instead of silently flushing nothing with status ok
        worker.service.is_allowed(admin_request())
        assert worker.decision_cache.stats()["entries"] >= 1
        out = worker.command_interface.command(
            "flush_cache", {"data": {"db_index": "5"}}
        )
        assert out["flushed"]["decisions"] >= 1
        assert worker.decision_cache.stats()["entries"] == 0

    def test_flush_cache_unrecognized_db_index_errors(self, worker):
        worker.service.is_allowed(admin_request())
        entries = worker.decision_cache.stats()["entries"]
        out = worker.command_interface.command(
            "flush_cache", {"data": {"db_index": 7}}
        )
        assert "error" in out
        assert worker.decision_cache.stats()["entries"] == entries
        out = worker.command_interface.command(
            "flush_cache", {"data": {"db_index": "not-a-db"}}
        )
        assert "error" in out

    def test_flush_cache_pattern_narrows_to_subject(self, worker):
        install_reader_tree(worker)
        worker.service.is_allowed(admin_request())  # subject "root"
        worker.service.is_allowed(reader_request("u-reader"))
        out = worker.command_interface.command(
            "flush_cache", {"data": {"db_index": 5, "pattern": "u-reader"}}
        )
        assert out["flushed"]["decisions"] == 1
        # root's entry survives and still serves a hit
        hits = worker.decision_cache.stats()["hits"]
        worker.service.is_allowed(admin_request())
        assert worker.decision_cache.stats()["hits"] == hits + 1

    def test_decision_spanning_epoch_bump_is_not_cached(self, worker,
                                                        monkeypatch):
        """The CRUD-during-evaluation interleaving: a decision computed
        against the old tree that completes after the epoch bump must not
        be served as fresh for a TTL."""
        install_reader_tree(worker)
        evaluator = worker.service.evaluator
        cache = worker.decision_cache
        real = evaluator._oracle_is_allowed

        def bump_mid_flight(request):
            response = real(request)
            cache.bump_epoch()  # CRUD/restore completes while in flight
            return response

        monkeypatch.setattr(evaluator, "_oracle_is_allowed", bump_mid_flight)
        stores = cache.stats()["stores"]
        assert evaluator.is_allowed(reader_request()).decision == \
            Decision.PERMIT
        # the write-through was refused: its epoch snapshot predates the
        # bump, so nothing stale entered the cache
        assert cache.stats()["stores"] == stores
        assert cache.stats()["entries"] == 0

    def test_batch_spanning_epoch_bump_is_not_cached(self, worker,
                                                     monkeypatch):
        install_reader_tree(worker)
        evaluator = worker.service.evaluator
        cache = worker.decision_cache
        real = evaluator._is_allowed_batch_uncached

        def bump_mid_flight(requests):
            responses = real(requests)
            cache.bump_epoch()
            return responses

        monkeypatch.setattr(
            evaluator, "_is_allowed_batch_uncached", bump_mid_flight
        )
        responses = evaluator.is_allowed_batch(
            [reader_request(), admin_request()]
        )
        assert all(r.decision == Decision.PERMIT for r in responses)
        assert cache.stats()["entries"] == 0

    def test_config_update_bumps_epoch(self, worker):
        epoch = worker.decision_cache.stats()["epoch"]
        worker.command_interface.command(
            "config_update", {"service:probe": True}
        )
        assert worker.decision_cache.stats()["epoch"] == epoch + 1

    def test_ttl_expiry_through_worker(self):
        w = Worker().start(seed_cfg(decision_cache={
            "enabled": True, "ttl_s": 3600, "max_entries": 1024,
            "shards": 4,
        }))
        try:
            clock = FakeClock()
            w.decision_cache._time = clock
            w.service.is_allowed(admin_request())
            hits = w.decision_cache.stats()["hits"]
            w.service.is_allowed(admin_request())
            assert w.decision_cache.stats()["hits"] == hits + 1
            clock.now += 3601.0
            misses = w.decision_cache.stats()["misses"]
            response = w.service.is_allowed(admin_request())
            assert response.decision == Decision.PERMIT
            assert w.decision_cache.stats()["misses"] > misses
        finally:
            w.stop()

    def test_disabled_by_config(self):
        w = Worker().start(seed_cfg(decision_cache={"enabled": False}))
        try:
            assert w.decision_cache is None
            response = w.service.is_allowed(admin_request())
            assert response.decision == Decision.PERMIT
            health = w.command_interface.command("health_check")
            assert "decision_cache" not in health
        finally:
            w.stop()


# ----------------------------------------------------------- differential


def response_bits(response):
    return (
        response.decision,
        response.evaluation_cacheable,
        response.operation_status.code if response.operation_status else None,
        tuple(
            (o.id, o.value) for o in (response.obligations or [])
        ),
    )


ROLES = ("superadministrator-r-id", "reader-role", "nobody")
SUBJECTS = ("root", "u-reader", "u-other")


def probe_requests():
    requests = []
    for subject in SUBJECTS:
        for role in ROLES:
            requests.append(build_request(
                subject_id=subject, subject_role=role,
                role_scoping_entity=ORG, role_scoping_instance="system",
                resource_type=ORG, resource_id="O1", action_type=READ,
            ))
    return requests


def test_differential_cache_on_off_under_random_interleaving():
    """The semantics bar: a cache-on worker and a cache-off worker fed the
    same randomized stream of decisions, rule CRUD, userModified events and
    flush commands must emit bit-identical responses at every step."""
    rng = random.Random(1312)
    on = Worker().start(seed_cfg())
    off = Worker().start(seed_cfg(decision_cache={"enabled": False}))
    workers = (on, off)
    try:
        assert on.decision_cache is not None and off.decision_cache is None

        def compare_all(step):
            # fresh request objects per worker: engines mutate context
            for a, b in zip(probe_requests(), probe_requests()):
                ra = on.service.is_allowed(a)
                rb = off.service.is_allowed(b)
                assert response_bits(ra) == response_bits(rb), (
                    f"divergence at step {step}"
                )

        def op_create():
            for w in workers:
                install_reader_tree(w)

        def op_update():
            # update of a deleted rule is a per-item 404 no-op — identical
            # on both workers, which is all the differential needs
            effect = rng.choice(("PERMIT", "DENY"))
            cacheable = rng.random() < 0.8
            for w in workers:
                w.store.get_resource_service("rule").update(
                    [reader_rule(effect=effect, cacheable=cacheable)]
                )

        def op_delete():
            for w in workers:
                w.store.get_resource_service("rule").delete(["r_reader"])

        def op_user_event():
            event = rng.choice(("userModified", "userDeleted"))
            subject = rng.choice(SUBJECTS)
            for w in workers:
                w.bus.topic(USERS_TOPIC).emit(event, {"id": subject})

        def op_flush():
            payload = rng.choice((
                {},
                {"data": {"db_index": 5}},
                {"data": {"pattern": rng.choice(SUBJECTS)}},
            ))
            for w in workers:
                w.command_interface.command("flush_cache", payload)

        def op_traffic():
            # repeat traffic between mutations so warm hits actually serve
            for a, b in zip(probe_requests(), probe_requests()):
                ra = on.service.is_allowed(a)
                rb = off.service.is_allowed(b)
                assert response_bits(ra) == response_bits(rb)

        ops = (op_create, op_update, op_delete, op_user_event, op_flush,
               op_traffic)
        compare_all("seed")
        for step in range(24):
            rng.choice(ops)()
            compare_all(step)
        # the interleaving must have exercised real cache serving
        assert on.decision_cache.stats()["hits"] > 0
    finally:
        on.stop()
        off.stop()


def test_differential_batch_path_cache_on_off():
    """Batched endpoint: warm batch traffic through the cache-on worker
    matches the cache-off worker row for row."""
    on = Worker().start(seed_cfg())
    off = Worker().start(seed_cfg(decision_cache={"enabled": False}))
    try:
        install_reader_tree(on)
        install_reader_tree(off)
        for _ in range(3):  # cold, then warm passes
            ra = on.service.is_allowed_batch(probe_requests())
            rb = off.service.is_allowed_batch(probe_requests())
            assert [response_bits(r) for r in ra] == \
                [response_bits(r) for r in rb]
        assert on.decision_cache.stats()["hits"] > 0
    finally:
        on.stop()
        off.stop()
