"""Admission-control subsystem tests (srv/admission.py + the batcher /
service / adapter / identity integration): queue-bound shedding, deadline
rejection vs admission around the EWMA estimate, two-class fairness under
saturation, circuit-breaker state transitions (adapter and identity),
drain-on-shutdown semantics, and the differential check that admitted
requests produce byte-identical decisions to a no-admission run."""

import threading
import time

import pytest

from access_control_srv_tpu.core.errors import ContextQueryTransportError
from access_control_srv_tpu.models import Decision
from access_control_srv_tpu.models.model import (
    OperationStatus,
    Request,
    Response,
    ReverseQuery,
    Target,
)
from access_control_srv_tpu.srv.admission import (
    BULK,
    DEADLINE_CODE,
    INTERACTIVE,
    OVERLOAD_CODE,
    PIPELINE_BATCHES,
    SHUTDOWN_CODE,
    AdmissionController,
    CircuitBreaker,
    LatencyEwma,
    deadline_from_context,
)
from access_control_srv_tpu.srv.adapters import GraphQLAdapter
from access_control_srv_tpu.srv.batcher import MicroBatcher
from access_control_srv_tpu.srv.identity import (
    CachingIdentityClient,
    StaticIdentityClient,
)

from .test_srv import admin_request, seed_cfg


# --------------------------------------------------------------- fixtures


class FakeClock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


class StubEvaluator:
    """Deterministic evaluator double: PERMIT everything after an optional
    per-batch delay (models device/oracle latency)."""

    def __init__(self, delay_s: float = 0.0):
        self.delay_s = delay_s
        self.decision_cache = None
        self.engine = None
        self.batches: list[int] = []
        self.bulk_batches: list[int] = []

    def prepare_batch(self, requests):
        pass

    def _response(self):
        return Response(
            decision=Decision.PERMIT, obligations=[],
            evaluation_cacheable=False,
            operation_status=OperationStatus(),
        )

    def is_allowed(self, request):
        if self.delay_s:
            time.sleep(self.delay_s)
        self.batches.append(1)
        return self._response()

    def is_allowed_batch(self, requests):
        if self.delay_s:
            time.sleep(self.delay_s)
        self.batches.append(len(requests))
        return [self._response() for _ in requests]

    def what_is_allowed(self, request):
        return ReverseQuery(policy_sets=[], obligations=[],
                            operation_status=OperationStatus())

    def what_is_allowed_batch(self, requests):
        if self.delay_s:
            time.sleep(self.delay_s)
        self.bulk_batches.append(len(requests))
        return [self.what_is_allowed(r) for r in requests]


def make_request(i: int = 0) -> Request:
    return Request(target=Target(), context={"resources": []})


def controller(**kwargs) -> AdmissionController:
    kwargs.setdefault("enabled", True)
    return AdmissionController(**kwargs)


# --------------------------------------------------------- circuit breaker


class TestCircuitBreaker:
    def _breaker(self, **kwargs):
        clock = FakeClock()
        kwargs.setdefault("window_s", 10.0)
        kwargs.setdefault("min_volume", 4)
        kwargs.setdefault("failure_ratio", 0.5)
        kwargs.setdefault("open_s", 2.0)
        kwargs.setdefault("half_open_probes", 2)
        breaker = CircuitBreaker("test", time_fn=clock, **kwargs)
        return breaker, clock

    def _trip(self, breaker):
        for _ in range(4):
            assert breaker.allow()
            breaker.record_failure()

    def test_starts_closed_and_allows(self):
        breaker, _ = self._breaker()
        assert breaker.state == CircuitBreaker.CLOSED
        assert breaker.allow()

    def test_opens_at_failure_ratio_with_min_volume(self):
        breaker, _ = self._breaker()
        # below min_volume: never opens even at 100% failures
        for _ in range(3):
            breaker.record_failure()
            assert breaker.state == CircuitBreaker.CLOSED
        breaker.record_failure()  # 4th failure reaches min_volume
        assert breaker.state == CircuitBreaker.OPEN
        assert not breaker.allow()

    def test_successes_keep_ratio_below_threshold(self):
        breaker, _ = self._breaker()
        for _ in range(6):
            breaker.record_success()
        for _ in range(4):
            breaker.record_failure()
        # 4 failures / 10 calls = 0.4 < 0.5
        assert breaker.state == CircuitBreaker.CLOSED

    def test_half_open_after_jittered_cooldown(self):
        breaker, clock = self._breaker()
        self._trip(breaker)
        clock.advance(1.0)  # still inside the minimum cooldown
        assert not breaker.allow()
        clock.advance(2.1)  # past open_s * 1.5 (max jitter)
        assert breaker.state == CircuitBreaker.HALF_OPEN
        assert breaker.allow()  # probe slot

    def test_half_open_probe_success_closes(self):
        breaker, clock = self._breaker()
        self._trip(breaker)
        clock.advance(3.1)
        assert breaker.allow()
        breaker.record_success()
        assert breaker.state == CircuitBreaker.CLOSED
        # window restarted: old failures cannot re-trip it
        breaker.record_failure()
        assert breaker.state == CircuitBreaker.CLOSED

    def test_half_open_probe_failure_reopens(self):
        breaker, clock = self._breaker()
        self._trip(breaker)
        clock.advance(3.1)
        assert breaker.allow()
        breaker.record_failure()
        assert breaker.state == CircuitBreaker.OPEN
        assert not breaker.allow()

    def test_half_open_probe_slots_bounded(self):
        breaker, clock = self._breaker(half_open_probes=2)
        self._trip(breaker)
        clock.advance(3.1)
        assert breaker.allow()
        assert breaker.allow()
        assert not breaker.allow()  # both probe slots taken

    def test_stats_shape(self):
        breaker, _ = self._breaker()
        self._trip(breaker)
        stats = breaker.stats()
        assert stats["state"] == CircuitBreaker.OPEN
        assert stats["opens"] == 1


# ------------------------------------------------------------- controller


class TestAdmissionController:
    def test_disabled_admits_everything(self):
        ctl = AdmissionController(enabled=False, max_queue_interactive=0)
        for _ in range(100):
            assert ctl.admit(INTERACTIVE) is None
        # disabled controllers do not track depth either
        assert ctl.depth(INTERACTIVE) == 0

    def test_queue_bound_sheds_with_overload_status(self):
        ctl = controller(max_queue_interactive=4)
        for _ in range(4):
            assert ctl.admit(INTERACTIVE) is None
        shed = ctl.admit(INTERACTIVE)
        assert shed is not None
        assert shed.decision == Decision.INDETERMINATE
        assert shed.operation_status.code == OVERLOAD_CODE
        assert ctl.stats()["shed_queue_full"] == 1

    def test_release_frees_slots(self):
        ctl = controller(max_queue_interactive=2)
        assert ctl.admit(INTERACTIVE) is None
        assert ctl.admit(INTERACTIVE) is None
        assert ctl.admit(INTERACTIVE) is not None
        ctl.release(INTERACTIVE, 2)
        assert ctl.admit(INTERACTIVE) is None

    def test_classes_have_independent_bounds(self):
        ctl = controller(max_queue_interactive=1, max_queue_bulk=1)
        assert ctl.admit(INTERACTIVE) is None
        assert ctl.admit(BULK) is None
        assert ctl.admit(INTERACTIVE) is not None
        assert ctl.admit(BULK) is not None

    def test_deadline_rejection_around_ewma_estimate(self):
        """The admit/reject boundary must track the batch-latency EWMA:
        budgets below PIPELINE_BATCHES * estimate * headroom reject,
        comfortable budgets admit."""
        clock = FakeClock()
        ctl = controller(deadline_headroom=1.2, time_fn=clock)
        # seed the EWMA at a stable 50 ms per batch
        for _ in range(50):
            ctl.observe_batch(INTERACTIVE, 0.050, 10)
        est = ctl.estimate(INTERACTIVE)
        assert est == pytest.approx(0.050, rel=0.05)
        infeasible = est * PIPELINE_BATCHES * 1.2 * 0.9
        shed = ctl.admit(INTERACTIVE, deadline=clock() + infeasible)
        assert shed is not None
        assert shed.operation_status.code == OVERLOAD_CODE
        assert "deadline infeasible" in shed.operation_status.message
        assert ctl.stats()["deadline_rejected"] == 1
        feasible = est * PIPELINE_BATCHES * 1.2 * 1.5
        assert ctl.admit(INTERACTIVE, deadline=clock() + feasible) is None

    def test_queue_depth_tightens_the_deadline_check(self):
        """A deep queue adds per-row wait to the estimate: the same
        budget that admits at depth 0 rejects behind a long queue."""
        clock = FakeClock()
        ctl = controller(max_queue_interactive=10_000, time_fn=clock)
        for _ in range(50):
            ctl.observe_batch(INTERACTIVE, 0.010, 10)  # 1 ms per row
        budget = 0.010 * PIPELINE_BATCHES * 1.2 + 0.020
        assert ctl.admit(INTERACTIVE, deadline=clock() + budget) is None
        for _ in range(1000):  # 1000 queued rows ~ 1 s of wait
            ctl.admit(INTERACTIVE)
        shed = ctl.admit(INTERACTIVE, deadline=clock() + budget)
        assert shed is not None
        assert "queued ahead" in shed.operation_status.message

    def test_draining_sheds_with_shutdown_status(self):
        ctl = controller()
        ctl.begin_drain()
        shed = ctl.admit(INTERACTIVE)
        assert shed is not None
        assert shed.operation_status.code == SHUTDOWN_CODE

    def test_adaptive_max_batch_slow_start_grow_and_shrink(self):
        ctl = controller(deadline_bound_ms=40.0, min_batch=8,
                         adaptive_max_batch=True)
        # slow start at the floor
        assert ctl.suggest_max_batch(4096) == 8
        # comfortable FULL batches double the cap
        target = 0.040 / (PIPELINE_BATCHES + 1)
        ctl.observe_batch(INTERACTIVE, target / 4, 8)
        assert ctl.suggest_max_batch(4096) == 16
        ctl.observe_batch(INTERACTIVE, target / 4, 16)
        assert ctl.suggest_max_batch(4096) == 32
        # an overshooting batch halves it
        ctl.observe_batch(INTERACTIVE, target * 2, 32)
        assert ctl.suggest_max_batch(4096) == 16
        # the cap never exceeds the configured max
        for _ in range(20):
            ctl.observe_batch(INTERACTIVE, target / 4,
                              ctl.suggest_max_batch(64))
        assert ctl.suggest_max_batch(64) == 64

    def test_ewma_estimate_high_tracks_jitter(self):
        ewma = LatencyEwma(alpha=0.2, default_s=0.005)
        assert ewma.estimate() == 0.005
        for _ in range(100):
            ewma.observe(0.010, 10)
        # steady stream: deviation decays toward zero
        assert ewma.estimate_high() < 0.012
        for seconds in (0.002, 0.030) * 10:
            ewma.observe(seconds, 10)
        # jittery stream: the pessimistic bound spreads well above the mean
        assert ewma.estimate_high() > ewma.estimate() * 1.5


class TestDeadlineFromContext:
    class Ctx:
        def __init__(self, remaining=None, metadata=()):
            self._remaining = remaining
            self._metadata = metadata

        def time_remaining(self):
            return self._remaining

        def invocation_metadata(self):
            return self._metadata

    def test_native_grpc_deadline(self):
        deadline = deadline_from_context(self.Ctx(remaining=1.5))
        assert deadline is not None
        assert 1.0 < deadline - time.monotonic() <= 1.5

    def test_timeout_metadata_fallback(self):
        ctx = self.Ctx(metadata=(("x-acs-timeout-ms", "250"),))
        deadline = deadline_from_context(ctx)
        assert deadline is not None
        assert 0.1 < deadline - time.monotonic() <= 0.25

    def test_no_budget_stated(self):
        assert deadline_from_context(self.Ctx()) is None

    def test_int64_max_sentinel_means_no_deadline(self):
        """grpc-python reports ~int64-max SECONDS (not None) on a call
        with no client deadline — that must read as unbounded, and must
        not mask the metadata fallback."""
        assert deadline_from_context(self.Ctx(remaining=9.2e18)) is None
        ctx = self.Ctx(remaining=9.2e18,
                       metadata=(("x-acs-timeout-ms", "250"),))
        deadline = deadline_from_context(ctx)
        assert deadline is not None
        assert 0.1 < deadline - time.monotonic() <= 0.25


# ------------------------------------------------- batcher integration


def make_batcher(evaluator, admission, **kwargs):
    kwargs.setdefault("window_ms", 1.0)
    kwargs.setdefault("min_kernel_batch", 2)
    batcher = MicroBatcher(evaluator, admission=admission, **kwargs)
    batcher.start()
    return batcher


class TestBatcherAdmission:
    def test_queue_bound_shedding_under_slow_evaluator(self):
        """A saturated batcher sheds excess submits with the overload
        status instead of queueing unboundedly; every admitted request
        still resolves with a real decision."""
        ctl = controller(max_queue_interactive=8, adaptive_max_batch=False)
        batcher = make_batcher(StubEvaluator(delay_s=0.05), ctl)
        try:
            futures = [batcher.submit(make_request(i)) for i in range(64)]
            results = [f.result(timeout=30) for f in futures]
        finally:
            batcher.stop()
        shed = [r for r in results
                if r.operation_status.code == OVERLOAD_CODE]
        served = [r for r in results if r.operation_status.code == 200]
        assert shed, "saturation never shed"
        assert served, "nothing served"
        assert len(shed) + len(served) == 64
        for r in shed:  # never a fabricated PERMIT/DENY
            assert r.decision == Decision.INDETERMINATE
        for r in served:
            assert r.decision == Decision.PERMIT

    def test_deadline_expired_rows_dropped_at_dispatch(self):
        """Rows whose deadline passes while queued resolve with the
        deadline status instead of being evaluated after abandonment."""
        ctl = controller()
        evaluator = StubEvaluator(delay_s=0.15)
        batcher = make_batcher(evaluator, ctl)
        try:
            # the first submit occupies the eval worker; the deadlined one
            # expires while waiting behind it
            blocker = batcher.submit(make_request(0))
            time.sleep(0.02)  # let the first batch dispatch
            doomed = batcher.submit(
                make_request(1), deadline=time.monotonic() + 0.03
            )
            response = doomed.result(timeout=10)
            blocker.result(timeout=10)
        finally:
            batcher.stop()
        assert response.decision == Decision.INDETERMINATE
        assert response.operation_status.code == DEADLINE_CODE
        assert ctl.stats()["deadline_expired"] >= 1

    def test_two_class_fairness_under_interactive_saturation(self):
        """Bulk (whatIsAllowed) work keeps progressing while interactive
        traffic saturates the collector: the fairness interval guarantees
        a bulk round every bulk_interval interactive rounds."""
        ctl = controller(bulk_interval=4, adaptive_max_batch=False)
        evaluator = StubEvaluator(delay_s=0.005)
        batcher = make_batcher(evaluator, ctl, max_batch=16)
        stop_pump = threading.Event()

        def pump_interactive():
            while not stop_pump.is_set():
                batcher.submit(make_request())
                time.sleep(0.0005)

        pump = threading.Thread(target=pump_interactive)
        pump.start()
        try:
            time.sleep(0.05)  # interactive saturation established
            bulk = [batcher.submit_reverse(make_request(i))
                    for i in range(8)]
            results = [f.result(timeout=15) for f in bulk]
        finally:
            stop_pump.set()
            pump.join()
            batcher.stop()
        assert all(isinstance(rq, ReverseQuery) for rq in results)
        assert all(rq.operation_status.code == 200 for rq in results)
        assert evaluator.bulk_batches, "bulk starved"

    def test_bulk_sheds_when_bulk_queue_full(self):
        ctl = controller(max_queue_bulk=2)
        batcher = make_batcher(StubEvaluator(delay_s=0.05), ctl)
        try:
            futures = [batcher.submit_reverse(make_request(i))
                       for i in range(16)]
            results = [f.result(timeout=15) for f in futures]
        finally:
            batcher.stop()
        assert any(rq.operation_status.code == OVERLOAD_CODE
                   for rq in results)
        assert any(rq.operation_status.code == 200 for rq in results)

    def test_drain_on_shutdown_flushes_admitted_then_fails_queued(self):
        """stop(): admitted work is flushed to completion within the
        drain deadline; what cannot flush resolves with the distinct
        shutdown status — no future is ever left hanging."""
        ctl = controller(adaptive_max_batch=False)
        evaluator = StubEvaluator(delay_s=0.3)
        batcher = make_batcher(evaluator, ctl, max_batch=4)
        futures = [batcher.submit(make_request(i)) for i in range(32)]
        time.sleep(0.05)  # first batches in flight
        batcher.stop(drain_s=0.5)
        # every future resolved — served, or failed with shutdown status
        codes = [f.result(timeout=1).operation_status.code
                 for f in futures]
        assert all(code in (200, SHUTDOWN_CODE) for code in codes)
        assert 200 in codes, "nothing flushed during drain"
        assert SHUTDOWN_CODE in codes, "drain deadline never cut anything"
        # post-stop submits shed immediately with the shutdown status
        late = batcher.submit(make_request()).result(timeout=1)
        assert late.operation_status.code == SHUTDOWN_CODE

    def test_admission_disabled_preserves_legacy_paths(self):
        """With no controller the batcher behaves exactly as before:
        unbounded queue, no deadline logic on the hot path."""
        evaluator = StubEvaluator()
        batcher = MicroBatcher(evaluator, window_ms=1.0, min_kernel_batch=2)
        batcher.start()
        try:
            futures = [batcher.submit(make_request(i)) for i in range(32)]
            assert all(
                f.result(timeout=10).decision == Decision.PERMIT
                for f in futures
            )
        finally:
            batcher.stop()


# ------------------------------------------------- breaker integration


class TestAdapterBreaker:
    def _adapter(self, breaker, fail: dict):
        calls = []

        def transport(url, body, headers):
            calls.append(1)
            if fail["on"]:
                raise ContextQueryTransportError(503, "down")
            return b'{"data": {"op": {"details": [{"payload": {"id": 1}}]}}}'

        adapter = GraphQLAdapter(
            "http://example/graphql", transport=transport,
            retry_transient=False, breaker=breaker,
        )
        cq = type("CQ", (), {"query": "query q", "filters": []})()
        request = Request(target=Target(), context={"resources": []})
        return adapter, cq, request, calls

    def test_breaker_opens_and_fails_fast_then_recovers(self):
        clock = FakeClock()
        breaker = CircuitBreaker("adapter", min_volume=4, open_s=1.0,
                                 time_fn=clock)
        fail = {"on": True}
        adapter, cq, request, calls = self._adapter(breaker, fail)
        for _ in range(4):
            with pytest.raises(ContextQueryTransportError):
                adapter.query(cq, request)
        assert breaker.state == CircuitBreaker.OPEN
        n_transport = len(calls)
        # open circuit: transport is never touched — the row fails fast
        # down the existing deny/oracle degradation ladder
        with pytest.raises(ContextQueryTransportError) as err:
            adapter.query(cq, request)
        assert len(calls) == n_transport
        assert err.value.code == 503
        # recovery: the upstream heals, the jittered cooldown elapses,
        # one probe closes the circuit
        fail["on"] = False
        clock.advance(2.0)
        assert adapter.query(cq, request) == [{"id": 1}]
        assert breaker.state == CircuitBreaker.CLOSED

    def test_definitive_4xx_counts_as_breaker_success(self):
        breaker = CircuitBreaker("adapter", min_volume=2,
                                 time_fn=FakeClock())

        def transport(url, body, headers):
            raise ContextQueryTransportError(404, "no such resource")

        adapter = GraphQLAdapter(
            "http://example/graphql", transport=transport,
            retry_transient=False, breaker=breaker,
        )
        cq = type("CQ", (), {"query": "query q", "filters": []})()
        request = Request(target=Target(), context={"resources": []})
        for _ in range(8):
            with pytest.raises(ContextQueryTransportError):
                adapter.query(cq, request)
        # the upstream IS answering: 4xx must never trip the breaker
        assert breaker.state == CircuitBreaker.CLOSED


class TestIdentityBreaker:
    class FlakyInner:
        def __init__(self):
            self.fail = True
            self.calls = 0

        def find_by_token(self, token):
            self.calls += 1
            if self.fail:
                raise ConnectionError("identity down")
            return {"payload": {"id": "u1"},
                    "status": {"code": 200, "message": "ok"}}

    def test_breaker_opens_and_resolution_degrades_per_row(self):
        clock = FakeClock()
        breaker = CircuitBreaker("identity", min_volume=4, open_s=1.0,
                                 time_fn=clock)
        inner = self.FlakyInner()
        client = CachingIdentityClient(inner, breaker=breaker)
        for _ in range(4):
            with pytest.raises(ConnectionError):
                client.find_by_token("tok")
        assert breaker.state == CircuitBreaker.OPEN
        # open circuit: fast 5xx envelope, no inner call — the row
        # degrades to token-unresolved, and 5xx is never cached so
        # recovery is immediate
        n_calls = inner.calls
        out = client.find_by_token("tok")
        assert inner.calls == n_calls
        assert out["payload"] is None
        assert out["status"]["code"] == 503
        assert "circuit open" in out["status"]["message"]
        # recovery closes through one healthy probe
        inner.fail = False
        clock.advance(2.0)
        out = client.find_by_token("tok")
        assert out["payload"] == {"id": "u1"}
        assert breaker.state == CircuitBreaker.CLOSED

    def test_definitive_404_counts_as_breaker_success(self):
        breaker = CircuitBreaker("identity", min_volume=2,
                                 time_fn=FakeClock())
        client = CachingIdentityClient(StaticIdentityClient(),
                                       breaker=breaker)
        for i in range(8):
            out = client.find_by_token(f"unknown-{i}")
            assert out["payload"] is None
        assert breaker.state == CircuitBreaker.CLOSED


# ------------------------------------------ worker-level differential


class TestWorkerDifferential:
    """Admitted requests must produce BYTE-identical decisions to a
    no-admission run — admission decides WHETHER a request is evaluated,
    never WHAT the decision is."""

    def _responses(self, admission_enabled, faults_block=None):
        from access_control_srv_tpu.srv import Worker
        from access_control_srv_tpu.srv.transport_grpc import (
            response_to_pb,
            reverse_query_to_pb,
        )

        cfg = seed_cfg()
        cfg["admission"] = {"enabled": admission_enabled}
        if faults_block is not None:
            cfg["faults"] = faults_block
        worker = Worker().start(cfg)
        try:
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

    def test_admitted_decisions_byte_identical_to_no_admission(self):
        with_admission = self._responses(True)
        without = self._responses(False)
        assert with_admission == without

    def test_disabled_failpoints_leave_serving_byte_identical(self):
        """A faults block that is present but disabled must not perturb
        a single response byte — the failpoint framework is OFF by
        default and configure_from leaves the registry disarmed."""
        from access_control_srv_tpu.srv.faults import REGISTRY

        armed = self._responses(True, faults_block={
            "enabled": False,
            "seed": 7,
            "points": [
                {"site": "device.dispatch", "action": "error"},
                {"site": "broker.journal.write", "action": "torn"},
            ],
        })
        assert REGISTRY.enabled is False
        assert armed == self._responses(True)


# --------------------------------------------------- degraded envelope


class TestDegradedStatus:
    """The device-health envelope (admission.degraded_response) is a
    distinct honest 503: INDETERMINATE decision, never cacheable, never
    a fabricated PERMIT/DENY — and separable from the load-shed and
    drain envelopes that share the 5xx band."""

    def test_envelope_shape_and_distinct_from_shed(self):
        from access_control_srv_tpu.srv.admission import (
            DEGRADED_CODE,
            degraded_response,
            overload_response,
        )

        resp = degraded_response("device materialize timed out")
        assert resp.operation_status.code == DEGRADED_CODE == 503
        assert resp.decision == Decision.INDETERMINATE
        assert resp.evaluation_cacheable is False
        assert resp.operation_status.message.startswith("degraded")
        # shed and drain envelopes never carry the degraded marker: an
        # operator (or the router's retry policy) can tell device-health
        # 503s from load 503s by message
        for code, msg in ((OVERLOAD_CODE, "queue full"),
                          (SHUTDOWN_CODE, "draining")):
            shed = overload_response(code, msg)
            assert "degraded" not in shed.operation_status.message

    def test_degraded_rows_are_never_cached(self):
        from access_control_srv_tpu.srv.admission import degraded_response
        from access_control_srv_tpu.srv.decision_cache import DecisionCache

        cache = DecisionCache(ttl_s=60.0, max_entries=16)
        stored = cache.put("k-degraded", degraded_response("quarantined"),
                           epoch=cache.epoch)
        assert stored is False
        assert cache.get("k-degraded") is None

    def test_hang_fallback_ladder_is_honest(self):
        """Per-row resolution after a device timeout: expired rows shed
        504, oracle-answerable rows get a REAL evaluation, rows the
        oracle cannot answer get the degraded envelope — no row is ever
        a fabricated PERMIT/DENY."""
        from access_control_srv_tpu.srv.admission import DEGRADED_CODE
        from access_control_srv_tpu.srv.evaluator import HybridEvaluator

        permit = Response(
            decision=Decision.PERMIT, obligations=[],
            evaluation_cacheable=True,
            operation_status=OperationStatus(code=200, message=""),
        )
        counted = {}

        class Shim:
            _hang_fallback = HybridEvaluator._hang_fallback

            def _expired_rows(self, requests):
                return {0}

            def _oracle_is_allowed(self, request):
                if getattr(request, "broken", False):
                    raise RuntimeError("oracle cannot resolve")
                return permit

            def _count_path(self, path, rows):
                counted[path] = counted.get(path, 0) + rows

            class _slog:
                @staticmethod
                def warning(*args, **kwargs):
                    pass

        class Row:
            def __init__(self, broken=False):
                self.broken = broken

        rows = [Row(), Row(), Row(broken=True)]
        out = Shim()._hang_fallback(rows)
        assert out[0].operation_status.code == DEADLINE_CODE
        assert out[1] is permit
        assert out[2].operation_status.code == DEGRADED_CODE
        assert out[2].decision == Decision.INDETERMINATE
        assert out[2].evaluation_cacheable is False
        assert "degraded" in out[2].operation_status.message
        assert counted == {"hang-fallback-oracle": 1,
                           "hang-fallback-degraded": 1,
                           "deadline-expired": 1}


class TestBrokerFsyncInterval:
    def test_fsync_every_record_preserves_journal_semantics(self, tmp_path):
        """fsync_interval_s=0 (fsync per record) must keep journal replay
        byte-for-byte equivalent to the flush-only default."""
        from access_control_srv_tpu.srv.broker import (
            BrokerServer,
            SocketEventBus,
        )

        data_dir = str(tmp_path / "broker-data")
        server = BrokerServer(data_dir=data_dir, fsync_interval_s=0)
        server.start()
        bus = SocketEventBus(server.address)
        topic = bus.topic("t.fsync")
        for i in range(5):
            topic.emit("evt", {"i": i})
        bus.close()
        server.stop()
        # cold restart replays the fsynced journal
        server2 = BrokerServer(data_dir=data_dir).start()
        bus2 = SocketEventBus(server2.address)
        events = bus2.topic("t.fsync").read(0)
        bus2.close()
        server2.stop()
        assert [m["i"] for _, m in events] == list(range(5))

    def test_default_is_flush_only(self, tmp_path):
        from access_control_srv_tpu.srv.broker import BrokerServer

        server = BrokerServer(data_dir=str(tmp_path / "d"))
        assert server.fsync_interval_s is None
        server.start()
        server.stop()


# ------------------------------------------------- audit-sweep starvation


class TestAuditSweepStarvation:
    """Two-class fairness exercised by a REAL audit sweep job (the
    bulk-class producer from srv/audit_sweep.py), not synthetic wia
    singles — both starvation directions."""

    def _manager(self, batcher, tmp_path, **kw):
        from access_control_srv_tpu.srv.audit_sweep import AuditSweepManager

        kw.setdefault("out_dir", str(tmp_path))
        return AuditSweepManager(batcher.evaluator, batcher=batcher, **kw)

    def _spec(self, n):
        from access_control_srv_tpu.ops.lattice import LatticeSpec

        return LatticeSpec.stress(n, n, actions=("read",))

    def test_saturating_sweep_cannot_starve_interactive(self, tmp_path):
        """While a full-lattice sweep saturates the bulk queue, every
        admitted interactive request still resolves 200 with p99 well
        inside the interactive deadline bound (p99 <= 500 ms with a 5 ms
        device step)."""
        ctl = controller(bulk_interval=4, adaptive_max_batch=False)
        evaluator = StubEvaluator(delay_s=0.005)
        batcher = make_batcher(evaluator, ctl, max_batch=64)
        manager = self._manager(batcher, tmp_path, chunk_size=64)
        try:
            job = manager.start_sweep(spec=self._spec(48))  # 2304 cells
            deadline = time.monotonic() + 10
            while not evaluator.bulk_batches:
                assert time.monotonic() < deadline, "sweep never dispatched"
                time.sleep(0.002)
            latencies = []
            for i in range(40):
                t0 = time.monotonic()
                response = batcher.submit(make_request(i)).result(timeout=15)
                latencies.append(time.monotonic() - t0)
                assert response.operation_status.code == 200
                assert response.decision == Decision.PERMIT
            assert job.state in ("running", "done")
            latencies.sort()
            p99 = latencies[int(len(latencies) * 0.99) - 1]
            assert p99 <= 0.5, (
                f"interactive p99 {p99 * 1e3:.0f}ms blew the fairness "
                "bound while the sweep ran"
            )
            # the sweep genuinely saturated bulk during the measurement
            assert sum(evaluator.bulk_batches) >= 64
        finally:
            manager.stop()
            batcher.stop()

    def test_interactive_flood_cannot_starve_sweep(self, tmp_path):
        """The reverse direction: an interactive flood saturates the
        collector, yet bulk_interval still guarantees sweep progress —
        the job runs to completion under sustained interactive load."""
        ctl = controller(bulk_interval=4, adaptive_max_batch=False)
        evaluator = StubEvaluator(delay_s=0.002)
        batcher = make_batcher(evaluator, ctl, max_batch=16)
        manager = self._manager(batcher, tmp_path, chunk_size=16)
        stop_pump = threading.Event()

        def pump_interactive():
            while not stop_pump.is_set():
                batcher.submit(make_request())
                time.sleep(0.0005)

        pump = threading.Thread(target=pump_interactive)
        pump.start()
        try:
            time.sleep(0.05)  # saturation established before the sweep
            job = manager.start_sweep(spec=self._spec(8))  # 64 cells
            assert job.wait(30), "sweep starved under interactive flood"
            assert job.state == "done"
            assert job.cells_done == 64
            assert job.sheds == 0, "fairness must not rely on shedding"
            assert evaluator.bulk_batches, "bulk never dispatched"
        finally:
            stop_pump.set()
            pump.join()
            manager.stop()
            batcher.stop()
