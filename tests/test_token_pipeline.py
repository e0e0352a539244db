"""Host-side eligibility pipeline (ISSUE 3 tentpole): batched token
resolution + HR-scope rendezvous keep token-authenticated rows on the
kernel, and adapter context-query prefetch keeps context-query rows on the
kernel — every fused row bit-identical to the scalar oracle, every failure
mode degrading per-row to the oracle, never to a changed decision."""

import copy
import threading
import time

import pytest

from access_control_srv_tpu.core import AccessController
from access_control_srv_tpu.core.errors import ContextQueryTransportError
from access_control_srv_tpu.core.loader import load_policy_sets
from access_control_srv_tpu.models import Attribute, Request, Target, Urns
from access_control_srv_tpu.ops import compile_policies, encode_requests
from access_control_srv_tpu.srv.adapters import GraphQLAdapter
from access_control_srv_tpu.srv.cache import HRScopeProvider, SubjectCache
from access_control_srv_tpu.srv.evaluator import HybridEvaluator
from access_control_srv_tpu.srv.identity import (
    CachingIdentityClient,
    StaticIdentityClient,
    TokenResolutionCache,
)
from access_control_srv_tpu.srv.telemetry import Telemetry

URNS = Urns()
ORG = "urn:restorecommerce:acs:model:organization.Organization"
WIDGET = "urn:restorecommerce:acs:model:widget.Widget"
PO = "urn:oasis:names:tc:xacml:3.0:rule-combining-algorithm:permit-overrides"
DO = "urn:oasis:names:tc:xacml:3.0:rule-combining-algorithm:deny-overrides"


def role_tree(n_roles=6, entities=(ORG, WIDGET)):
    policies = []
    rid = 0
    for entity in entities:
        rules = []
        for r in range(n_roles):
            rules.append({
                "id": f"r{rid}",
                "target": {
                    "subjects": [{"id": URNS["role"], "value": f"role-{r}"}],
                    "resources": [{"id": URNS["entity"], "value": entity}],
                    "actions": [{"id": URNS["actionID"],
                                 "value": URNS["read"]}],
                },
                "effect": "PERMIT" if rid % 3 else "DENY",
            })
            rid += 1
        policies.append({"id": f"p-{entity[-6:]}", "combining_algorithm": PO,
                         "rules": rules})
    return {"policy_sets": [
        {"id": "s", "combining_algorithm": DO, "policies": policies}
    ]}


def token_request(i, token, entity=ORG):
    """A request whose subject arrives as a bare token (the production
    shape): no id, no role associations — everything comes from
    resolution."""
    return Request(
        target=Target(
            subjects=[Attribute(id=URNS["role"], value=f"role-{i % 6}"),
                      Attribute(id=URNS["subjectID"], value=f"user-{i % 8}")],
            resources=[Attribute(id=URNS["entity"], value=entity),
                       Attribute(id=URNS["resourceID"], value=f"res-{i}")],
            actions=[Attribute(id=URNS["actionID"], value=URNS["read"])],
        ),
        context={"resources": [], "subject": {"token": token}},
    )


def payload_for(i):
    return {
        "id": f"user-{i % 8}",
        "tokens": [{"token": f"tok-{i % 8}", "interactive": True}],
        "role_associations": [{"role": f"role-{i % 6}", "attributes": []}],
    }


def wired_engine(doc=None, scopes=()):
    engine = AccessController()
    for ps in load_policy_sets(doc or role_tree()):
        engine.update_policy_set(ps)
    ids = StaticIdentityClient()
    for i in range(8):
        ids.register(f"tok-{i}", payload_for(i))
    engine.identity_client = CachingIdentityClient(ids)
    cache = SubjectCache()
    for i in range(8):
        cache.set(f"cache:user-{i}:hrScopes", list(scopes))
    engine.hr_scope_provider = HRScopeProvider(cache)
    return engine


def assert_bit_identical(responses, oracle):
    for b, (got, want) in enumerate(zip(responses, oracle)):
        assert got.decision == want.decision, (b, got.decision, want.decision)
        assert got.evaluation_cacheable == want.evaluation_cacheable, b
        assert got.operation_status.code == want.operation_status.code, b
        assert got.operation_status.message == want.operation_status.message, b


class TestTokenResolutionEligibility:
    def test_resolved_token_rows_ride_the_kernel(self):
        engine = wired_engine()
        requests = [token_request(i, f"tok-{i % 8}") for i in range(32)]
        oracle = [engine.is_allowed(copy.deepcopy(r)) for r in requests]
        telemetry = Telemetry()
        ev = HybridEvaluator(engine, telemetry=telemetry)
        copies = [copy.deepcopy(r) for r in requests]
        responses = ev.is_allowed_batch(copies)
        assert_bit_identical(responses, oracle)
        # the rows actually rode the device: encode after prepare shows
        # zero ineligible rows and the kernel path counter moved
        batch = encode_requests(copies, ev._compiled)
        assert batch.eligible.all(), batch.ineligible_reasons
        assert telemetry.paths.get("kernel") == len(requests)
        assert telemetry.paths.get("token-resolved") == len(requests)

    def test_resolution_failure_degrades_per_row_to_oracle(self):
        engine = wired_engine()
        requests = [
            token_request(i, f"tok-{i % 8}" if i % 2 else "unknown-token")
            for i in range(16)
        ]
        oracle = [engine.is_allowed(copy.deepcopy(r)) for r in requests]
        telemetry = Telemetry()
        ev = HybridEvaluator(engine, telemetry=telemetry)
        copies = [copy.deepcopy(r) for r in requests]
        responses = ev.is_allowed_batch(copies)
        assert_bit_identical(responses, oracle)
        batch = encode_requests(copies, ev._compiled)
        assert int(batch.eligible.sum()) == 8
        assert batch.ineligible_reasons == {"token-unresolved": 8}
        assert telemetry.paths.get("token-unresolved") == 8

    def test_unprepared_token_rows_stay_ineligible(self):
        """Direct encodes (wire/native path) see unprepared requests: the
        pre-pipeline contract is unchanged."""
        engine = wired_engine()
        compiled = compile_policies(engine.policy_sets, engine.urns)
        batch = encode_requests([token_request(0, "tok-0")], compiled)
        assert not batch.eligible[0]
        assert batch.ineligible_reasons == {"token-subject": 1}

    def test_rendezvous_timeout_degrades_to_oracle(self):
        """A dead auth topic: resolution succeeds, the HR rendezvous times
        out, the subject keeps role associations but no scope list — the
        encoder sends the row to the oracle (missing-hr-scopes), which
        raises InvalidRequestContext exactly like the reference."""
        engine = wired_engine()

        class DeadTopic:
            def emit(self, *a, **k):
                pass

        engine.hr_scope_provider = HRScopeProvider(
            SubjectCache(), DeadTopic(), timeout_ms=50
        )
        ev = HybridEvaluator(engine)
        copies = [copy.deepcopy(token_request(i, f"tok-{i % 8}"))
                  for i in range(4)]
        ev.prepare_batch(copies)
        batch = encode_requests(copies, ev._compiled)
        assert not batch.eligible.any()
        assert batch.ineligible_reasons == {"missing-hr-scopes": 4}
        # ...and the oracle-served rows still match a fresh oracle walk
        requests = [token_request(i, f"tok-{i % 8}") for i in range(4)]
        oracle = [engine.is_allowed(copy.deepcopy(r)) for r in requests]
        responses = ev.is_allowed_batch([copy.deepcopy(r) for r in requests])
        assert_bit_identical(responses, oracle)

    def test_batch_dedups_rpcs_and_rendezvous(self):
        """32 rows over 4 distinct tokens cost 4 identity RPCs (and zero
        on the next batch, served by the TTL cache)."""
        engine = wired_engine()
        calls = []
        inner = engine.identity_client.inner
        orig = inner.find_by_token

        def counting(token):
            calls.append(token)
            return orig(token)

        inner.find_by_token = counting
        ev = HybridEvaluator(engine)
        ev.prepare_batch([copy.deepcopy(token_request(i, f"tok-{i % 4}"))
                          for i in range(32)])
        assert sorted(calls) == [f"tok-{i}" for i in range(4)]
        ev.prepare_batch([copy.deepcopy(token_request(i, f"tok-{i % 4}"))
                          for i in range(32)])
        assert len(calls) == 4  # warm cache: no second round of RPCs

    def test_mixed_batch_token_plain_and_broken_rows(self):
        engine = wired_engine()
        requests = []
        for i in range(24):
            kind = i % 4
            if kind == 0:
                requests.append(token_request(i, f"tok-{i % 8}"))
            elif kind == 1:
                requests.append(token_request(i, "unknown-token"))
            elif kind == 2:  # plain resolved subject, no token
                r = token_request(i, "unused")
                r.context["subject"] = {
                    "id": f"user-{i % 8}",
                    "role_associations": [
                        {"role": f"role-{i % 6}", "attributes": []}
                    ],
                    "hierarchical_scopes": [],
                }
                requests.append(r)
            else:  # no target: host-side 400 DENY
                requests.append(Request(target=None, context={}))
        oracle = [engine.is_allowed(copy.deepcopy(r)) for r in requests]
        ev = HybridEvaluator(engine)
        responses = ev.is_allowed_batch([copy.deepcopy(r) for r in requests])
        assert_bit_identical(responses, oracle)

    def test_wia_batch_resolves_tokens(self):
        """The reverse-query batch path prepares token rows too (the
        reference resolves tokens for whatIsAllowed as well)."""
        engine = wired_engine()
        requests = [token_request(i, f"tok-{i % 8}") for i in range(6)]
        oracle = [engine.what_is_allowed(copy.deepcopy(r)) for r in requests]
        ev = HybridEvaluator(engine)
        out = ev.what_is_allowed_batch([copy.deepcopy(r) for r in requests])
        for got, want in zip(out, oracle):
            got_ids = [(ps.id, sorted(p.id for p in ps.policies))
                       for ps in got.policy_sets]
            want_ids = [(ps.id, sorted(p.id for p in ps.policies))
                        for ps in want.policy_sets]
            assert got_ids == want_ids


class TestResolutionCache:
    def test_ttl_expiry_refetches(self):
        clock = [0.0]
        cache = TokenResolutionCache(ttl_s=10.0, time_fn=lambda: clock[0])
        entry = {"payload": {"id": "u"}, "status": {"code": 200}}
        _, gen = cache.lookup("t")
        assert cache.store("t", entry, gen)
        hit, _ = cache.lookup("t")
        assert hit["payload"] == {"id": "u"}
        clock[0] = 11.0
        hit, _ = cache.lookup("t")
        assert hit is None
        assert cache.stats()["expirations"] == 1

    def test_negative_caching_definitive_only(self):
        clock = [0.0]
        cache = TokenResolutionCache(
            ttl_s=10.0, negative_ttl_s=2.0, time_fn=lambda: clock[0]
        )
        _, gen = cache.lookup("bad")
        # definitive negative (404): cached for the negative TTL
        assert cache.store(
            "bad", {"payload": None, "status": {"code": 404}}, gen
        )
        hit, _ = cache.lookup("bad")
        assert hit is not None and hit["payload"] is None
        assert cache.stats()["negative_hits"] == 1
        clock[0] = 3.0
        assert cache.lookup("bad")[0] is None  # negative TTL elapsed
        # transport failure (5xx): never cached
        _, gen = cache.lookup("down")
        assert not cache.store(
            "down", {"payload": None, "status": {"code": 503}}, gen
        )
        assert cache.lookup("down")[0] is None

    def test_negative_cache_collapses_repeat_bad_tokens(self):
        inner = StaticIdentityClient()
        calls = []
        orig = inner.find_by_token

        def counting(token):
            calls.append(token)
            return orig(token)

        inner.find_by_token = counting
        client = CachingIdentityClient(inner)
        for _ in range(5):
            out = client.find_by_token("nope")
            assert out["payload"] is None
        assert calls == ["nope"]  # one RPC per negative-TTL window

    def test_eviction_race_blocks_stale_store(self):
        cache = TokenResolutionCache()
        _, gen = cache.lookup("t")
        cache.evict("t")  # userModified lands while resolution in flight
        assert not cache.store(
            "t", {"payload": {"id": "u"}, "status": {"code": 200}}, gen
        )
        assert cache.lookup("t")[0] is None

    def test_evict_subject_drops_all_tokens_of_user(self):
        cache = TokenResolutionCache()
        for tok in ("a", "b"):
            _, gen = cache.lookup(tok)
            cache.store(
                tok, {"payload": {"id": "ada"}, "status": {"code": 200}}, gen
            )
        _, gen = cache.lookup("c")
        cache.store(
            "c", {"payload": {"id": "gil"}, "status": {"code": 200}}, gen
        )
        assert cache.evict_subject("ada") == 2
        assert cache.lookup("a")[0] is None
        assert cache.lookup("b")[0] is None
        assert cache.lookup("c")[0] is not None

    def test_stale_cache_after_eviction_differential(self):
        """userModified eviction mid-stream: the next batch re-resolves and
        kernel rows stay bit-identical to the oracle under the NEW
        payload."""
        engine = wired_engine()
        ev = HybridEvaluator(engine)
        first = [copy.deepcopy(token_request(i, "tok-1")) for i in range(8)]
        ev.is_allowed_batch(first)
        # the user's role flips; the resolution cache is evicted like the
        # worker's userModified listener would
        engine.identity_client.inner.register("tok-1", {
            "id": "user-1",
            "tokens": [{"token": "tok-1", "interactive": True}],
            "role_associations": [{"role": "role-3", "attributes": []}],
        })
        engine.identity_client.evict_subject("user-1")
        second = [copy.deepcopy(token_request(i, "tok-1")) for i in range(8)]
        oracle = [engine.is_allowed(copy.deepcopy(r)) for r in second]
        responses = ev.is_allowed_batch(second)
        assert_bit_identical(responses, oracle)
        # the fresh payload actually landed in the encoded rows
        assert second[0].context["subject"]["role_associations"] == [
            {"role": "role-3", "attributes": []}
        ]

    def test_telemetry_counters_and_health_surface(self):
        telemetry = Telemetry()
        client = CachingIdentityClient(
            StaticIdentityClient({"t": {"id": "u"}}),
            counter=telemetry.identity,
        )
        client.find_by_token("t")
        client.find_by_token("t")
        snap = telemetry.snapshot()["identity_cache"]
        assert snap["misses"] == 1 and snap["hits"] == 1
        stats = client.cache_stats()
        assert stats["hits"] == 1 and stats["entries"] == 1
        # health_check exposes the same stats through the command interface
        from access_control_srv_tpu.srv.command import CommandInterface
        from access_control_srv_tpu.srv.config import Config

        engine = AccessController(identity_client=client)

        class Svc:
            pass

        svc = Svc()
        svc.engine = engine
        svc.evaluator = None
        health = CommandInterface(Config({}), svc).health_check({})
        assert health["status"] == "SERVING"
        assert health["token_resolution_cache"]["hits"] == 1


def cq_tree(with_later_reader=False):
    """A stress-shaped tree plus one trailing context-query rule over
    WIDGET; optionally a later role-gated rule that makes the merge
    observable (fusion must then refuse)."""
    doc = role_tree()
    cq_policies = [{
        "id": "p-cq", "combining_algorithm": PO,
        "rules": [{
            "id": "r-cq",
            "target": {"resources": [{"id": URNS["entity"],
                                      "value": WIDGET}]},
            "effect": "PERMIT",
            "context_query": {
                "filters": [{"field": "id", "operation": "eq",
                             "value": "res"}],
                "query": "query q { all { id } }",
            },
            "condition": "len(context._queryResult) > 0",
        }],
    }]
    if with_later_reader:
        cq_policies.append({
            "id": "p-later", "combining_algorithm": PO,
            "rules": [{
                "id": "r-later",
                "target": {
                    "subjects": [{"id": URNS["role"], "value": "role-0"}],
                    "resources": [{"id": URNS["entity"], "value": WIDGET}],
                },
                "effect": "DENY",
            }],
        })
    doc["policy_sets"].append(
        {"id": "cq", "combining_algorithm": DO, "policies": cq_policies}
    )
    return doc


class CountingAdapter:
    def __init__(self, fail_times=0, code=502):
        self.calls = 0
        self.fail_times = fail_times
        self.code = code

    def query(self, context_query, request):
        self.calls += 1
        if self.fail_times > 0:
            self.fail_times -= 1
            raise ContextQueryTransportError(self.code, "boom")
        return [{"id": "res"}]


class TestContextQueryPrefetch:
    def _requests(self, n=16):
        out = []
        for i in range(n):
            out.append(Request(
                target=Target(
                    subjects=[
                        Attribute(id=URNS["role"], value=f"role-{i % 6}"),
                        Attribute(id=URNS["subjectID"], value=f"u{i}"),
                    ],
                    resources=[
                        Attribute(id=URNS["entity"],
                                  value=WIDGET if i % 2 else ORG),
                        Attribute(id=URNS["resourceID"], value=f"res-{i}"),
                    ],
                    actions=[Attribute(id=URNS["actionID"],
                                       value=URNS["read"])],
                ),
                context={"resources": [], "subject": {
                    "id": f"u{i}",
                    "role_associations": [
                        {"role": f"role-{i % 6}", "attributes": []}
                    ],
                    "hierarchical_scopes": [],
                }},
            ))
        return out

    def _run(self, doc, adapter, n=16):
        engine = AccessController()
        for ps in load_policy_sets(doc):
            engine.update_policy_set(ps)
        engine.resource_adapter = adapter
        requests = self._requests(n)
        oracle = [engine.is_allowed(copy.deepcopy(r)) for r in requests]
        ev = HybridEvaluator(engine)
        responses = ev.is_allowed_batch([copy.deepcopy(r) for r in requests])
        assert_bit_identical(responses, oracle)
        batch = encode_requests(
            [copy.deepcopy(r) for r in requests], ev._compiled,
            engine.resource_adapter,
        )
        return batch

    def test_safe_rows_fuse_and_match_oracle(self):
        batch = self._run(cq_tree(), CountingAdapter())
        assert batch.eligible.all(), batch.ineligible_reasons

    def test_resolved_and_prefetched_rows_run_the_plain_program(self):
        """Token resolution and the context-query prefetch change only
        host-computed kernel inputs: bare-token rows, half of them behind
        a context query, run the executables that pre-resolved rows
        compiled (no new jit key, no new XLA compilation), all on the
        device and equal to the oracle."""
        adapter = CountingAdapter()
        engine = wired_engine(cq_tree())
        engine.resource_adapter = adapter
        telemetry = Telemetry()
        ev = HybridEvaluator(engine, telemetry=telemetry)
        plain = []
        for i in range(16):
            request = token_request(i, None)
            request.context["subject"] = {**payload_for(i),
                                          "hierarchical_scopes": []}
            plain.append(request)
        ev.is_allowed_batch(plain)
        assert adapter.calls == 0
        keys = set(ev._shared_jits)
        sizes = {k: f._cache_size() for k, f in ev._shared_jits.items()}
        assert keys
        requests = [token_request(i, f"tok-{i % 8}",
                                  entity=WIDGET if i % 2 else ORG)
                    for i in range(16)]
        oracle = [engine.is_allowed(copy.deepcopy(r)) for r in requests]
        kernel_rows = telemetry.paths.get("kernel")
        responses = ev.is_allowed_batch(requests)
        assert_bit_identical(responses, oracle)
        assert adapter.calls >= 1
        assert telemetry.paths.get("kernel") - kernel_rows == len(requests)
        assert set(ev._shared_jits) == keys
        assert {k: f._cache_size()
                for k, f in ev._shared_jits.items()} == sizes

    def test_merge_observable_rows_degrade(self):
        """A later role-gated candidate rule could see the merged context:
        those rows must take the oracle, and still match it."""
        batch = self._run(cq_tree(with_later_reader=True), CountingAdapter())
        assert batch.ineligible_reasons.get("context-query") == 8
        assert int(batch.eligible.sum()) == 8  # ORG rows stay on device

    def test_prefetch_failure_degrades_to_oracle(self):
        batch = self._run(cq_tree(), CountingAdapter(fail_times=10 ** 6))
        assert batch.ineligible_reasons.get("context-query-error") == 8
        assert int(batch.eligible.sum()) == 8

    def test_condition_error_on_merged_context_aborts_like_oracle(self):
        doc = cq_tree()
        doc["policy_sets"][-1]["policies"][0]["rules"][0]["condition"] = (
            "context._queryResult[0].missing_field.deeper == 1"
        )
        batch = self._run(doc, CountingAdapter())
        assert batch.eligible.all(), batch.ineligible_reasons
        assert batch.cond_abort.any()


class TestAdapterRetry:
    def _adapter(self, fail_times, code):
        calls = []

        def transport(url, body, headers):
            calls.append(time.monotonic())
            if len(calls) <= fail_times:
                raise ContextQueryTransportError(code, "flaky")
            return b'{"data": {"op": {"details": [{"payload": {"id": 1}}]}}}'

        adapter = GraphQLAdapter(
            "http://example/graphql", transport=transport,
            retry_backoff_s=0.01,
        )
        cq = type("CQ", (), {"query": "query q", "filters": []})()
        request = Request(target=Target(), context={"resources": []})
        return adapter, cq, request, calls

    def test_transient_5xx_retried_once(self):
        adapter, cq, request, calls = self._adapter(1, 502)
        out = adapter.query(cq, request)
        assert out == [{"id": 1}]
        assert len(calls) == 2

    def test_second_5xx_failure_surfaces(self):
        adapter, cq, request, calls = self._adapter(2, 503)
        with pytest.raises(ContextQueryTransportError):
            adapter.query(cq, request)
        assert len(calls) == 2  # exactly one retry, then give up

    def test_definitive_4xx_not_retried(self):
        adapter, cq, request, calls = self._adapter(1, 404)
        with pytest.raises(ContextQueryTransportError):
            adapter.query(cq, request)
        assert len(calls) == 1

    def test_retry_disabled_by_config(self):
        calls = []

        def transport(url, body, headers):
            calls.append(1)
            raise ContextQueryTransportError(502, "down")

        adapter = GraphQLAdapter(
            "http://example/graphql", transport=transport,
            retry_transient=False,
        )
        cq = type("CQ", (), {"query": "query q", "filters": []})()
        with pytest.raises(ContextQueryTransportError):
            adapter.query(cq, Request(target=Target(), context={}))
        assert len(calls) == 1


class TestBatcherPipeline:
    def test_pipelined_batches_resolve_in_order(self):
        """The eval-worker pipeline must preserve per-request results while
        the collector prepares the next batch during device execution."""
        from access_control_srv_tpu.srv.batcher import MicroBatcher

        engine = wired_engine()
        ev = HybridEvaluator(engine)
        batcher = MicroBatcher(ev, window_ms=1.0, min_kernel_batch=4)
        batcher.start()
        try:
            requests = [token_request(i, f"tok-{i % 8}") for i in range(64)]
            oracle = [engine.is_allowed(copy.deepcopy(r)) for r in requests]
            futures = [batcher.submit(copy.deepcopy(r)) for r in requests]
            responses = [f.result(timeout=30) for f in futures]
            assert_bit_identical(responses, oracle)
        finally:
            batcher.stop()

    def test_stop_drains_inflight_batches(self):
        from access_control_srv_tpu.srv.batcher import MicroBatcher

        engine = wired_engine()
        ev = HybridEvaluator(engine)
        batcher = MicroBatcher(ev, window_ms=1.0, min_kernel_batch=4)
        batcher.start()
        futures = [batcher.submit(copy.deepcopy(token_request(i, f"tok-{i % 8}")))
                   for i in range(16)]
        time.sleep(0.05)
        batcher.stop()
        done = [f for f in futures if f.done()]
        assert done, "stop() must drain submitted work"
        for f in done:
            assert f.result(timeout=1) is not None
