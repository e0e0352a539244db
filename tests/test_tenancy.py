"""Multi-tenant serving (ISSUE 14 tentpole): size-class packing onto
shared compiled programs, tenant-scoped epochs/caches/journal frames,
per-tenant admission quotas, bounded-cardinality tenant telemetry, and
the differential bar — with no tenant id anywhere, the worker's decision
stream is byte-identical to a build without the tenancy registry."""

import threading
import time

import pytest

from access_control_srv_tpu.models import (
    Attribute,
    Decision,
    Request,
    Response,
    Target,
    Urns,
)
from access_control_srv_tpu.models.model import OperationStatus
from access_control_srv_tpu.ops.delta import Capacities
from access_control_srv_tpu.srv import Worker
from access_control_srv_tpu.srv.admission import (
    INTERACTIVE,
    AdmissionController,
    tenant_from_metadata,
    valid_tenant_id,
)
from access_control_srv_tpu.srv.decision_cache import (
    DecisionCache,
    key_tenant,
    request_fingerprint,
)
from access_control_srv_tpu.srv.tenancy import (
    SIZE_CLASSES,
    TenantRegistry,
    class_caps,
    class_for_live,
    unknown_tenant_response,
)

from .test_srv import admin_request, seed_cfg

URNS = Urns()
PO = ("urn:oasis:names:tc:xacml:3.0:rule-combining-algorithm:"
      "permit-overrides")
USERS_TOPIC = "io.restorecommerce.users.resource"


def t_entity(k):
    return f"urn:restorecommerce:acs:model:tthing{k}.TThing{k}"


def t_rule(rid, k, effect="PERMIT"):
    return {"id": rid, "target": {
        "subjects": [{"id": URNS["role"], "value": f"role-{k % 3}"}],
        "resources": [{"id": URNS["entity"], "value": t_entity(k % 4)}],
        "actions": [{"id": URNS["actionID"], "value": URNS["read"]}]},
        "effect": effect, "evaluation_cacheable": True}


def t_request(k):
    role = f"role-{k % 3}"
    return Request(
        target=Target(
            subjects=[Attribute(id=URNS["role"], value=role),
                      Attribute(id=URNS["subjectID"], value=f"u{k}")],
            resources=[Attribute(id=URNS["entity"], value=t_entity(k % 4))],
            actions=[Attribute(id=URNS["actionID"], value=URNS["read"])],
        ),
        context={"resources": [], "subject": {
            "id": f"u{k}",
            "role_associations": [{"role": role, "attributes": []}],
            "hierarchical_scopes": [],
        }},
    )


def onboard(registry, tid, n_rules=2, emit=False, effect="PERMIT"):
    for j in range(n_rules):
        registry.apply(tid, "rule", "upsert",
                       t_rule(f"r{j}", j, effect=effect), emit=emit)
    registry.apply(tid, "policy", "upsert",
                   {"id": "p0", "combining_algorithm": PO,
                    "rules": [f"r{j}" for j in range(n_rules)]}, emit=emit)
    registry.apply(tid, "policy_set", "upsert",
                   {"id": "ps0", "combining_algorithm": PO,
                    "policies": ["p0"]}, emit=emit)


def permit_response(message="success"):
    return Response(
        decision=Decision.PERMIT,
        obligations=[],
        evaluation_cacheable=True,
        operation_status=OperationStatus(code=200, message=message),
    )


# --------------------------------------------------- transport metadata


class FakeGrpcContext:
    def __init__(self, metadata):
        self._metadata = metadata

    def invocation_metadata(self):
        return self._metadata


class TestTenantMetadata:
    def test_valid_id_shapes(self):
        for tid in ("acme", "acme-corp", "t.1_x", "A" * 64):
            assert valid_tenant_id(tid) == tid

    def test_invalid_id_shapes_treated_as_absent(self):
        for bad in ("", " ", "a b", "a/b", "a\x1eb", "Ä", "A" * 65,
                    "x\nY"):
            assert valid_tenant_id(bad) is None

    def test_metadata_extraction_case_insensitive(self):
        ctx = FakeGrpcContext([("X-ACS-Tenant", "acme"), ("other", "v")])
        assert tenant_from_metadata(ctx) == "acme"

    def test_metadata_invalid_value_is_absent(self):
        assert tenant_from_metadata(
            FakeGrpcContext([("x-acs-tenant", "not valid!")])
        ) is None
        assert tenant_from_metadata(FakeGrpcContext([])) is None
        assert tenant_from_metadata(object()) is None


# ------------------------------------------------------ size-class ladder


class TestSizeClassLadder:
    def test_smallest_fitting_class_wins(self):
        assert class_for_live(Capacities(S=1, KP=1, KR=2, T=4, RV=4,
                                         W=4)) == "xs"
        assert class_for_live(Capacities(S=1, KP=1, KR=8, T=4, RV=4,
                                         W=4)) == "s"
        assert class_for_live(Capacities(S=16, KP=16, KR=32, T=1024,
                                         RV=256, W=256)) == "l"

    def test_overflow_falls_off_the_ladder(self):
        live = Capacities(S=1, KP=1, KR=2, T=4096, RV=4, W=4)
        assert class_for_live(live) is None
        assert class_caps(None) is None
        assert class_caps("no-such-class") is None

    def test_class_caps_roundtrip(self):
        for name, caps in SIZE_CLASSES:
            assert class_caps(name) is caps


# ----------------------------------------------------- registry lifecycle


class TestTenantRegistry:
    def _registry(self):
        return TenantRegistry(URNS, backend="oracle")

    def test_onboard_epoch_and_serving_isolation(self):
        registry = self._registry()
        try:
            onboard(registry, "t1", effect="PERMIT")
            onboard(registry, "t2", effect="DENY")
            assert "t1" in registry and "t2" in registry
            # 2 rules + 1 policy + 1 policy set = 4 frames per tenant
            assert registry.tenant_epoch("t1") == 4
            req = t_request(0)
            r1 = registry.evaluator_for("t1").is_allowed_batch([req])[0]
            r2 = registry.evaluator_for("t2").is_allowed_batch([req])[0]
            assert r1.decision == Decision.PERMIT
            assert r2.decision == Decision.DENY
        finally:
            registry.shutdown()

    def test_unknown_tenant_envelope(self):
        registry = self._registry()
        assert registry.evaluator_for("ghost") is None
        resp = unknown_tenant_response("ghost")
        assert resp.decision == Decision.INDETERMINATE
        assert resp.operation_status.code == 404
        assert not resp.evaluation_cacheable
        assert "ghost" in resp.operation_status.message

    def test_crud_validation(self):
        registry = self._registry()
        with pytest.raises(ValueError):
            registry.apply("t1", "nonsense-kind", "upsert", {"id": "x"})
        with pytest.raises(ValueError):
            registry.apply("t1", "rule", "upsert", {"effect": "PERMIT"})
        # a rejected doc must not have onboarded the tenant
        assert "t1" not in registry
        # deletes for unknown tenants are no-ops, not onboarding events
        registry.apply("t1", "rule", "delete", {"id": "r0"})
        assert "t1" not in registry
        # unknown ops are rejected once the tenant exists (for an unknown
        # tenant the non-upsert early return wins)
        registry.apply("t2", "rule", "upsert", t_rule("r0", 0))
        with pytest.raises(ValueError):
            registry.apply("t2", "rule", "frobnicate", {"id": "r0"})

    def test_offboard_is_journal_shaped_and_drops_cache(self):
        cache = DecisionCache()
        cache.put("t1\x1eu0\x1fk", permit_response())
        cache.put("u0\x1fk", permit_response())
        registry = TenantRegistry(URNS, backend="oracle",
                                  decision_cache=cache)
        try:
            onboard(registry, "t1")
            assert registry.offboard("t1") is True
            assert "t1" not in registry
            assert registry.stats()["offboarded"] == 1
            # the tenant namespace went with it; default domain untouched
            assert cache.get("t1\x1eu0\x1fk") is None
            assert cache.get("u0\x1fk") is not None
            assert registry.offboard("t1") is False
        finally:
            registry.shutdown()

    def test_auto_offboard_when_collections_empty(self):
        registry = self._registry()
        try:
            registry.apply("t1", "rule", "upsert", t_rule("r0", 0))
            registry.apply("t1", "rule", "delete", {"id": "r0"})
            assert "t1" not in registry
        finally:
            registry.shutdown()

    def test_max_tenants_guard(self):
        registry = TenantRegistry(URNS, backend="oracle", max_tenants=1)
        try:
            registry.apply("t1", "rule", "upsert", t_rule("r0", 0))
            with pytest.raises(RuntimeError):
                registry.apply("t2", "rule", "upsert", t_rule("r0", 0))
        finally:
            registry.shutdown()

    def test_epoch_digest_order_independent(self):
        a, b = self._registry(), self._registry()
        try:
            onboard(a, "t1")
            onboard(a, "t2")
            onboard(b, "t2")  # same frames, different arrival order
            onboard(b, "t1")
            assert a.epoch_digest() == b.epoch_digest()
            before = a.epoch_digest()
            a.apply("t1", "rule", "upsert", t_rule("r9", 1))
            assert a.epoch_digest() != before
        finally:
            a.shutdown()
            b.shutdown()


# -------------------------------------------------------- program packing


class TestProgramPacking:
    """The packing claim at unit scale: same-class tenants serve from ONE
    shared program and a tenant's CRUD patches only its own tables with
    zero new compiles."""

    def test_same_class_tenants_share_compiled_programs(self):
        registry = TenantRegistry(URNS)  # hybrid: real shared-jit table
        try:
            reqs = [t_request(k) for k in range(4)]
            onboard(registry, "t1")
            registry.evaluator_for("t1").is_allowed_batch(reqs)
            first_of_class = registry.compiled_program_count()
            assert first_of_class >= 1
            for tid in ("t2", "t3"):
                onboard(registry, tid)
                registry.evaluator_for(tid).is_allowed_batch(reqs)
            assert registry.compiled_program_count() == first_of_class
            hist = registry.class_histogram()
            assert hist.get("xs") == 3
        finally:
            registry.shutdown()

    def test_crud_patch_scoped_to_one_tenant_zero_new_compiles(self):
        registry = TenantRegistry(URNS)
        try:
            reqs = [t_request(k) for k in range(4)]
            for tid in ("t1", "t2"):
                onboard(registry, tid)
                registry.evaluator_for(tid).is_allowed_batch(reqs)
            sibling_before = [
                r.decision for r in
                registry.evaluator_for("t1").is_allowed_batch(reqs)
            ]
            fp_before = registry.fingerprints()
            programs_before = registry.compiled_program_count()
            # mutate a rule the tenant tree REFERENCES (r0 is in p0)
            registry.apply("t2", "rule", "upsert",
                           t_rule("r0", 0, effect="DENY"))
            fp_after = registry.fingerprints()
            changed = sorted(
                t for t in fp_before if fp_before[t] != fp_after[t]
            )
            assert changed == ["t2"]
            assert registry.compiled_program_count() == programs_before
            assert registry.evaluator_for("t2").is_allowed_batch(
                [t_request(0)]
            )[0].decision == Decision.DENY
            sibling_after = [
                r.decision for r in
                registry.evaluator_for("t1").is_allowed_batch(reqs)
            ]
            assert sibling_after == sibling_before
        finally:
            registry.shutdown()


# ------------------------------------------------------ cache scoping


class TestTenantCacheScoping:
    def test_fingerprint_carries_tenant_namespace(self):
        plain = t_request(0)
        tagged = t_request(0)
        tagged._tenant = "acme"
        k_plain = request_fingerprint(plain)
        k_tagged = request_fingerprint(tagged)
        assert key_tenant(k_plain) is None
        assert key_tenant(k_tagged) == "acme"
        assert k_tagged == f"acme\x1e{k_plain}"

    def test_tenant_bump_spares_other_namespaces(self):
        cache = DecisionCache()
        cache.put("a\x1eu0\x1fk", permit_response())
        cache.put("b\x1eu0\x1fk", permit_response())
        cache.put("u0\x1fk", permit_response())
        cache.bump_epoch(tenant="a")
        assert cache.get("a\x1eu0\x1fk") is None
        assert cache.get("b\x1eu0\x1fk") is not None
        assert cache.get("u0\x1fk") is not None

    def test_untenanted_bump_is_a_global_flush(self):
        # an untenanted epoch bump (config_update, restore, reset) is a
        # GLOBAL logical flush — the tenant guard lives on the targeted
        # eviction paths (evict_subject / evict_pattern), not here
        cache = DecisionCache()
        cache.put("a\x1eu0\x1fk", permit_response())
        cache.put("u0\x1fk", permit_response())
        cache.bump_epoch()
        assert cache.get("u0\x1fk") is None
        assert cache.get("a\x1eu0\x1fk") is None

    def test_evict_subject_tenant_scoped(self):
        cache = DecisionCache()
        cache.put("a\x1eu0\x1fk", permit_response())
        cache.put("b\x1eu0\x1fk", permit_response())
        cache.put("u0\x1fk", permit_response())
        assert cache.evict_subject("u0", tenant="a") == 1
        assert cache.get("a\x1eu0\x1fk") is None
        assert cache.get("b\x1eu0\x1fk") is not None
        # untenanted eviction walks only the default domain
        assert cache.evict_subject("u0") == 1
        assert cache.get("u0\x1fk") is None
        assert cache.get("b\x1eu0\x1fk") is not None

    def test_evict_pattern_prefix_collision_guard(self):
        cache = DecisionCache()
        # tenant id sharing a string prefix with a default-domain subject
        cache.put("u1-corp\x1eu9\x1fk", permit_response())
        cache.put("u1\x1fk", permit_response())
        cache.put("u12\x1fk", permit_response())
        assert cache.evict_pattern("u1") == 2
        assert cache.get("u1-corp\x1eu9\x1fk") is not None
        # tenant-scoped empty pattern drops exactly that tenant
        cache.put("u1\x1fk", permit_response())
        assert cache.evict_pattern("", tenant="u1-corp") == 1
        assert cache.get("u1\x1fk") is not None


# ------------------------------------------- worker invalidation paths


class TestWorkerTenantInvalidation:
    """Satellite 3: flush_cache and userModified/userDeleted must scope
    to the originating tenant's cache namespace."""

    @pytest.fixture()
    def worker(self):
        w = Worker().start(seed_cfg(
            tenancy={"enabled": True},
            decision_cache={"enabled": True},
        ))
        yield w
        w.stop()

    def _seed_entries(self, worker):
        cache = worker.decision_cache
        cache.put("acme\x1eu0\x1fk", permit_response())
        cache.put("globex\x1eu0\x1fk", permit_response())
        cache.put("u0\x1fk", permit_response())
        return cache

    def test_flush_cache_command_tenant_scoped(self, worker):
        cache = self._seed_entries(worker)
        worker.command_interface.command(
            "flush_cache", {"data": {"db_index": 5, "pattern": "",
                                     "tenant": "acme"}}
        )
        assert cache.get("acme\x1eu0\x1fk") is None
        assert cache.get("globex\x1eu0\x1fk") is not None
        assert cache.get("u0\x1fk") is not None

    def test_flush_cache_command_untenanted_pattern_spares_tenants(
        self, worker
    ):
        # an untenanted PATTERN flush walks only default-domain keys; a
        # pattern-less untenanted flush stays a full physical flush
        # (operator semantics), so only the pattern form is scoped
        cache = self._seed_entries(worker)
        worker.command_interface.command(
            "flush_cache", {"data": {"db_index": 5, "pattern": "u0"}}
        )
        assert cache.get("u0\x1fk") is None
        assert cache.get("acme\x1eu0\x1fk") is not None
        assert cache.get("globex\x1eu0\x1fk") is not None

    def test_user_events_tenant_scoped(self, worker):
        cache = self._seed_entries(worker)
        topic = worker.bus.topic(USERS_TOPIC)
        topic.emit("userModified", {"id": "u0", "tenant": "acme"})
        assert cache.get("acme\x1eu0\x1fk") is None
        assert cache.get("globex\x1eu0\x1fk") is not None
        assert cache.get("u0\x1fk") is not None
        topic.emit("userDeleted", {"id": "u0", "tenant": "globex"})
        assert cache.get("globex\x1eu0\x1fk") is None
        assert cache.get("u0\x1fk") is not None

    def test_user_events_untenanted_spare_tenants(self, worker):
        cache = self._seed_entries(worker)
        worker.bus.topic(USERS_TOPIC).emit("userDeleted", {"id": "u0"})
        assert cache.get("u0\x1fk") is None
        assert cache.get("acme\x1eu0\x1fk") is not None
        assert cache.get("globex\x1eu0\x1fk") is not None


# --------------------------------------------------- per-tenant quotas


class TestTenantQuotas:
    def _controller(self, **overrides):
        kwargs = dict(
            enabled=True, tenant_enabled=True,
            max_queue_interactive=8, tenant_max_inflight=4,
            tenant_contention_ratio=0.5,
        )
        kwargs.update(overrides)
        return AdmissionController(**kwargs)

    def test_inflight_cap_sheds_then_releases(self):
        ctrl = self._controller(max_queue_interactive=64)
        for _ in range(4):
            assert ctrl.admit(INTERACTIVE, tenant="a") is None
        shed = ctrl.admit(INTERACTIVE, tenant="a")
        assert shed is not None
        assert shed.operation_status.code == 429
        assert "inflight cap" in shed.operation_status.message
        assert ctrl.stats()["shed_tenant_quota"] == 1
        # an untenanted request is untouched by the quota machinery
        assert ctrl.admit(INTERACTIVE) is None
        ctrl.release(INTERACTIVE, 1, tenant="a")
        assert ctrl.admit(INTERACTIVE, tenant="a") is None

    def test_fair_share_only_under_contention(self):
        ctrl = self._controller(tenant_max_inflight=64)
        # depth 3 < 8*0.5: uncontended, tenant "a" may hog the queue
        for _ in range(3):
            assert ctrl.admit(INTERACTIVE, tenant="a") is None
        # depth 4 >= 4: contended; "a" holds all slots, weight share with
        # a second active tenant bounds it to 8/2 = 4
        assert ctrl.admit(INTERACTIVE, tenant="a") is None
        assert ctrl.admit(INTERACTIVE, tenant="b") is None
        shed = ctrl.admit(INTERACTIVE, tenant="a")
        assert shed is not None
        assert "fair share" in shed.operation_status.message
        assert ctrl.stats()["shed_tenant_fair_share"] == 1
        # the lighter tenant still gets in
        assert ctrl.admit(INTERACTIVE, tenant="b") is None

    def test_weighted_share(self):
        ctrl = self._controller(
            tenant_max_inflight=64, max_queue_interactive=8,
            tenant_weights={"vip": 3.0},
        )
        for _ in range(4):
            assert ctrl.admit(INTERACTIVE, tenant="vip") is None
        assert ctrl.admit(INTERACTIVE, tenant="b") is None
        # vip's share is 3/4 of 8 = 6: two more slots before the bound
        assert ctrl.admit(INTERACTIVE, tenant="vip") is None
        assert ctrl.admit(INTERACTIVE, tenant="vip") is None
        assert ctrl.admit(INTERACTIVE, tenant="vip") is not None

    def test_release_drops_empty_tenant_slots(self):
        ctrl = self._controller()
        ctrl.admit(INTERACTIVE, tenant="a")
        assert ctrl._tenant_depth == {"a": 1}
        ctrl.release(INTERACTIVE, 1, tenant="a")
        # offboarded tenants must not pin dict slots forever
        assert ctrl._tenant_depth == {}


# -------------------------------------------------- bounded telemetry


class TestTenantTelemetry:
    def test_ten_thousand_ids_cannot_grow_the_registry(self):
        """Satellite 1 regression: tenant ids are attacker-controlled
        label values — cardinality must stay bounded."""
        from access_control_srv_tpu.srv.telemetry import (
            MetricsRegistry,
            TenantCounter,
        )

        counter = TenantCounter(max_tracked=64)
        for i in range(10_000):
            counter.inc("decision", f"tenant-{i}")
        assert counter.tracked() <= 64
        snap = counter.prom_snapshot()
        # 64 tracked ids + the __other__ overflow bucket, one event kind
        assert len(snap) <= 65
        assert snap[("decision", "__other__")] == 10_000 - 64
        registry = MetricsRegistry()
        registry.multi_counter(
            "acs_tenant_events_total", "per-tenant events",
            counter.prom_snapshot, labels=("event", "tenant"),
        )
        lines = [ln for ln in registry.render().splitlines()
                 if ln.startswith("acs_tenant_events_total{")]
        assert 0 < len(lines) <= 65
        assert any('tenant="__other__"' in ln for ln in lines)

    def test_snapshot_top_k_folds_tail(self):
        from access_control_srv_tpu.srv.telemetry import TenantCounter

        counter = TenantCounter(max_tracked=64)
        for i in range(40):
            counter.inc("shed", f"t{i}", by=i + 1)
        snap = counter.snapshot(top_k=4)["shed"]
        assert len(snap) == 5  # 4 ranked + __other__ fold
        assert snap["t39"] == 40
        assert snap["__other__"] == sum(range(1, 37))

    def test_tenant_inc_threads_through_telemetry(self):
        from access_control_srv_tpu.srv.telemetry import Telemetry

        telemetry = Telemetry()
        telemetry.tenant_inc("decision", "acme", by=3)
        snap = telemetry.snapshot()
        assert snap["tenants"]["decision"]["acme"] == 3
        rendered = telemetry.registry.render()
        assert 'acs_tenant_events_total{event="decision",tenant="acme"} 3' \
            in rendered


# ------------------------------------------------- worker serving path


class TestWorkerTenantServing:
    @pytest.fixture()
    def worker(self):
        w = Worker().start(seed_cfg(
            tenancy={"enabled": True},
            evaluator={"backend": "oracle"},
        ))
        yield w
        w.stop()

    def _submit(self, worker, req, tenant=None):
        if tenant is not None:
            req._tenant = tenant
        return worker.batcher.submit(req).result(timeout=10)

    def test_mixed_batch_routes_by_tenant(self, worker):
        onboard(worker.tenancy, "acme", emit=True, effect="PERMIT")
        onboard(worker.tenancy, "globex", emit=True, effect="DENY")
        results = {}
        threads = [
            threading.Thread(target=lambda t=t: results.update(
                {t: self._submit(worker, t_request(0), tenant=t)}
            )) for t in ("acme", "globex")
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=15)
        assert results["acme"].decision == Decision.PERMIT
        assert results["globex"].decision == Decision.DENY
        # default domain still serves the seeded tree
        assert self._submit(worker, admin_request()).decision \
            == Decision.PERMIT

    def test_unknown_tenant_gets_404_not_default_domain(self, worker):
        resp = self._submit(worker, admin_request(), tenant="ghost")
        assert resp.decision == Decision.INDETERMINATE
        assert resp.operation_status.code == 404

    def test_health_and_program_identity_tenancy_blocks(self, worker):
        onboard(worker.tenancy, "acme", emit=True)
        self._submit(worker, t_request(0), tenant="acme")
        health = worker.command_interface.command("health_check")
        block = health["tenancy"]
        assert block["tenant_count"] == 1
        assert block["evaluators_built"] == 1
        assert block["epoch_top_k"] == {"acme": 4}
        assert block["epoch_digest"]
        assert "size_classes" in block
        # program_identity is what the router polls into cluster_status
        identity = worker.command_interface.command("program_identity")
        assert identity["tenancy"]["tenant_count"] == 1
        assert identity["tenancy"]["epoch_digest"] == \
            block["epoch_digest"]


# --------------------------------------------- noisy-neighbor latency


class TestNoisyNeighborBound:
    def test_quiet_tenant_admitted_p99_inside_deadline_bound(self):
        """One tenant flooding the interactive queue must not push
        another tenant's ADMITTED p99 past the deadline bound (sheds are
        the release valve; admitted work keeps its latency contract)."""
        deadline_ms = 100.0
        worker = Worker().start(seed_cfg(
            tenancy={"enabled": True},
            decision_cache={"enabled": False},
            evaluator={"backend": "oracle"},
            admission={
                "enabled": True,
                "max_queue_interactive": 128,
                "deadline_bound_ms": deadline_ms,
                "min_batch": 8,
                # the p99 bound is a queueing bound: cap the flood's
                # queue occupancy so admitted quiet work never waits
                # behind it past the deadline
                "tenant": {"max_inflight_per_tenant": 32},
            },
        ))
        try:
            for tid in ("noisy", "quiet"):
                onboard(worker.tenancy, tid, emit=True)
            stop = threading.Event()

            def flood():
                i = 0
                while not stop.is_set():
                    req = t_request(i)
                    req._tenant = "noisy"
                    try:
                        worker.batcher.submit(req)
                    except Exception:  # noqa: BLE001 — open loop
                        pass
                    i += 1
                    if i % 64 == 0:
                        time.sleep(0.001)

            threads = [threading.Thread(target=flood, daemon=True)
                       for _ in range(2)]
            for t in threads:
                t.start()
            latencies = []
            t_end = time.monotonic() + 1.2
            i = 0
            while time.monotonic() < t_end:
                req = t_request(i)
                req._tenant = "quiet"
                t0 = time.perf_counter()
                resp = worker.batcher.submit(
                    req, deadline=time.monotonic() + deadline_ms / 1e3
                ).result(timeout=10)
                if resp.operation_status.code == 200:
                    latencies.append(time.perf_counter() - t0)
                i += 1
            stop.set()
            for t in threads:
                t.join(timeout=5)
        finally:
            worker.stop()
        assert latencies, "quiet tenant was starved outright"
        latencies.sort()
        p99_ms = latencies[
            min(len(latencies) - 1, int(len(latencies) * 0.99))
        ] * 1e3
        assert p99_ms <= deadline_ms, (
            f"quiet tenant admitted p99 {p99_ms:.1f} ms blew the "
            f"{deadline_ms} ms bound"
        )


# --------------------------------------------------- router aggregation


class TestRouterTenancyAggregation:
    def test_status_reports_tenant_convergence(self):
        from access_control_srv_tpu.srv.router import ClusterRouter

        router = ClusterRouter(["127.0.0.1:1", "127.0.0.1:2"])
        try:
            a, b = router.replicas
            a.tenancy = {"tenant_count": 3, "epoch_digest": "d1"}
            b.tenancy = {"tenant_count": 3, "epoch_digest": "d1"}
            status = router.status()
            assert status["tenancy"] == {
                "replicas_reporting": 2,
                "tenant_count": 3,
                "tenant_converged": True,
            }
            b.tenancy = {"tenant_count": 2, "epoch_digest": "d2"}
            assert router.status()["tenancy"]["tenant_converged"] is False
        finally:
            router.stop()

    def test_status_without_tenancy_blocks_is_unchanged(self):
        from access_control_srv_tpu.srv.router import ClusterRouter

        router = ClusterRouter(["127.0.0.1:1"])
        try:
            assert "tenancy" not in router.status()
        finally:
            router.stop()


# ---------------------------------------------------- journal replication


class TestTenantReplication:
    def test_tenants_converge_and_boot_by_replay(self):
        """Tenant CRUD is a journaled stream: a peer replica applies live
        frames and a late-booting replica onboards every journaled tenant
        by replay — per-tenant epochs and the epoch digest converge."""
        from access_control_srv_tpu.srv.broker import BrokerServer

        broker = BrokerServer().start()
        workers = []
        try:
            def boot():
                w = Worker().start(seed_cfg(
                    tenancy={"enabled": True},
                    evaluator={"backend": "oracle"},
                    events={"broker": {"address": broker.address}},
                ))
                workers.append(w)
                return w

            a = boot()
            b = boot()
            onboard(a.tenancy, "acme", emit=True)
            deadline = time.monotonic() + 20
            while time.monotonic() < deadline:
                if b.tenancy.tenant_epoch("acme") == 4:
                    break
                time.sleep(0.05)
            assert b.tenancy.tenant_epoch("acme") == 4
            assert b.tenancy.epoch_digest() == a.tenancy.epoch_digest()
            req = t_request(0)
            req._tenant = "acme"
            resp = b.batcher.submit(req).result(timeout=10)
            assert resp.decision == Decision.PERMIT
            # late joiner: replays the journal at boot, no live frames
            c = boot()
            assert c.tenancy.tenant_epoch("acme") == 4
            assert c.tenancy.epoch_digest() == a.tenancy.epoch_digest()
        finally:
            for w in workers:
                w.stop()
            broker.stop()


# ------------------------------------------------ byte-identity differential


class TestWorkerTenancyDifferential:
    """Acceptance bar: with no tenant id anywhere in the traffic, a
    worker with the tenancy registry wired answers byte-for-byte what a
    worker without it answers."""

    def _responses(self, tenancy_enabled):
        from access_control_srv_tpu.srv.transport_grpc import (
            response_to_pb,
            reverse_query_to_pb,
        )

        cfg = seed_cfg()
        if tenancy_enabled:
            cfg["tenancy"] = {"enabled": True}
        worker = Worker().start(cfg)
        try:
            assert (worker.tenancy is not None) is tenancy_enabled
            requests = [admin_request(), admin_request(role="nobody"),
                        admin_request()]
            single = [
                response_to_pb(
                    worker.service.is_allowed(r)
                ).SerializeToString()
                for r in requests
            ]
            batch = [
                response_to_pb(r).SerializeToString()
                for r in worker.service.is_allowed_batch(
                    [admin_request(), admin_request(role="nobody")]
                )
            ]
            reverse = reverse_query_to_pb(
                worker.service.what_is_allowed(admin_request())
            ).SerializeToString()
        finally:
            worker.stop()
        return single, batch, reverse

    def test_no_tenant_traffic_byte_identical(self):
        assert self._responses(True) == self._responses(False)
