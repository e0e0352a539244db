"""Depth-N device pipeline tests: the staging buffer pool's aliasing
discipline, byte-identity of depth-N serving vs depth-1 vs the oracle
(admission on and off), per-stream response ordering under out-of-order
completion on the streaming endpoint, the unified admission/batcher
pipeline-depth config, and a slow-marked CRUD-churn soak with delta
patches landing mid-pipeline."""

import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from access_control_srv_tpu.models import Request
from access_control_srv_tpu.ops.staging import HostBufferPool
from access_control_srv_tpu.srv import Worker
from access_control_srv_tpu.srv.admission import (
    INTERACTIVE,
    AdmissionController,
)
from access_control_srv_tpu.srv.config import Config

from .test_srv import admin_request, seed_cfg
from .utils import URNS, build_request

ORG = "urn:restorecommerce:acs:model:organization.Organization"


def pipe_cfg(depth, admission=False, cache=True, **overrides):
    cfg = seed_cfg(**overrides)
    cfg["evaluator"] = {
        "pipeline_depth": depth,
        # wide window + small cap: concurrent submits aggregate into
        # kernel-sized batches deterministically
        "micro_batch_window_ms": 20,
        "micro_batch_max": 64,
    }
    if not cache:
        cfg["decision_cache"] = {"enabled": False}
    if admission:
        cfg["admission"] = {"enabled": True}
    return cfg


def mixed_request(i: int) -> Request:
    """Mixed eligible/ineligible traffic: plain kernel rows, novel-role
    rows, and token-bearing rows whose resolution FAILS (no identity
    registration) so they degrade per-row to the oracle."""
    if i % 5 == 4:
        request = admin_request()
        request.context["subject"] = {"token": f"unknown-tok-{i % 3}"}
        return request
    return build_request(
        subject_id=f"user-{i}",
        subject_role=(
            "superadministrator-r-id" if i % 2 else f"role-{i % 7}"
        ),
        role_scoping_entity=ORG,
        role_scoping_instance="system",
        resource_type=ORG,
        resource_id=f"O-{i % 11}",
        action_type=URNS["read"] if i % 3 else URNS["modify"],
    )


def response_key(response):
    return (
        str(response.decision),
        response.evaluation_cacheable,
        response.operation_status.code,
    )


# ------------------------------------------------------------ buffer pool


class TestHostBufferPool:
    def test_recycles_by_shape_and_dtype(self):
        pool = HostBufferPool()
        a = pool.acquire((4, 8), np.int32)
        pool.release(a)
        b = pool.acquire((4, 8), np.int32)
        assert b is a
        assert pool.stats()["hits"] == 1

    def test_leased_buffers_are_never_handed_out_twice(self):
        pool = HostBufferPool()
        a = pool.acquire((16,), np.int32)
        b = pool.acquire((16,), np.int32)
        assert a is not b  # a is still leased
        pool.release(a)
        c = pool.acquire((16,), np.int32)
        assert c is a
        assert c is not b

    def test_double_release_raises(self):
        pool = HostBufferPool()
        a = pool.acquire((8,), np.int32)
        pool.release(a)
        with pytest.raises(ValueError):
            pool.release(a)

    def test_foreign_buffer_release_raises(self):
        pool = HostBufferPool()
        with pytest.raises(ValueError):
            pool.release(np.zeros(8, np.int32))

    def test_distinct_dtypes_do_not_alias(self):
        pool = HostBufferPool()
        a = pool.acquire((8,), np.int32)
        pool.release(a)
        b = pool.acquire((8,), np.int64)
        assert b is not a
        assert b.dtype == np.int64

    def test_bounded_free_list(self):
        pool = HostBufferPool(max_per_key=2)
        bufs = [pool.acquire((4,), np.int32) for _ in range(5)]
        pool.release_all(bufs)
        assert pool.stats()["free"] == 2


# -------------------------------------------- prefilter staging aliasing


class TestPrefilterStagingAliasing:
    """Two batches in flight simultaneously (depth-style overlap) must
    never share a staging buffer, and results must equal the
    sequential (depth-1) evaluation."""

    @pytest.fixture(scope="class")
    def stress(self):
        import synth

        from access_control_srv_tpu.ops.compile import compile_policies
        from access_control_srv_tpu.ops.encode import encode_requests
        from access_control_srv_tpu.ops.prefilter import PrefilteredKernel

        engine, _ = synth.stress_engine(600)
        compiled = compile_policies(engine.policy_sets, engine.urns)
        kernel = PrefilteredKernel(compiled, staging=HostBufferPool())
        assert kernel.active  # >= MIN_RULES: the pooled sig path engages

        def batch_for(seed):
            rng = np.random.default_rng(seed)
            reqs = []
            for i in range(32):
                k = int(rng.integers(64))
                reqs.append(build_request(
                    subject_id=f"u{i}-{seed}",
                    subject_role=f"role-{int(rng.integers(97))}",
                    resource_type=(
                        f"urn:restorecommerce:acs:model:stress{k}.Stress{k}"
                    ),
                    resource_id=f"res-{i}",
                    action_type=URNS["read"],
                ))
            return encode_requests(reqs, compiled)

        return kernel, batch_for

    def test_overlapped_dispatch_matches_sequential(self, stress):
        kernel, batch_for = stress
        b1, b2 = batch_for(1), batch_for(2)
        ref1 = kernel.evaluate(b1)
        ref2 = kernel.evaluate(b2)
        runs = dict(kernel._runs)
        sizes = {k: f._cache_size() for k, f in runs.items()}
        # dispatch BOTH before materializing EITHER: the pool must hand
        # each batch distinct buffers (the first is still leased)
        m1 = kernel.evaluate_async(b1)
        m2 = kernel.evaluate_async(b2)
        out1, out2 = m1(), m2()
        # overlap is host orchestration: the depth-1 executables ran
        # again, nothing was traced or compiled anew
        assert kernel._runs == runs
        assert {k: f._cache_size() for k, f in runs.items()} == sizes
        for ref, out in ((ref1, out1), (ref2, out2)):
            for r, o in zip(ref, out):
                np.testing.assert_array_equal(np.asarray(r), np.asarray(o))

    def test_leases_return_after_materialize(self, stress):
        kernel, batch_for = stress
        pool = kernel.staging
        m = kernel.evaluate_async(batch_for(3))
        assert pool.leased_count() > 0
        m()
        assert pool.leased_count() == 0

    def test_recycled_buffer_cannot_leak_rows(self, stress):
        """A buffer recycled from a PERMIT-heavy batch must not leak
        rows into a later differently-shaped-content batch: evaluate a
        batch, then re-evaluate a second batch that reuses the same
        staging slots, and compare against a fresh pool."""
        kernel, batch_for = stress
        b = batch_for(4)
        warm = kernel.evaluate(b)          # leaves recycled buffers behind
        again = kernel.evaluate(batch_for(5))
        fresh_kernel_pool = kernel.staging
        kernel.staging = HostBufferPool()  # cold pool: fresh allocations
        try:
            cold = kernel.evaluate(batch_for(5))
        finally:
            kernel.staging = fresh_kernel_pool
        for r, o in zip(again, cold):
            np.testing.assert_array_equal(np.asarray(r), np.asarray(o))
        # and the original batch's results were not disturbed
        for r, o in zip(warm, kernel.evaluate(b)):
            np.testing.assert_array_equal(np.asarray(r), np.asarray(o))


# ------------------------------------------------ native arena aliasing


class TestNativeArenaAliasing:
    def _encoder(self):
        import synth

        from access_control_srv_tpu import native
        from access_control_srv_tpu.ops.compile import compile_policies

        if not native.available():
            pytest.skip(f"native encoder unavailable: {native.build_error()}")
        engine, _ = synth.stress_engine(600, scoped=True)
        compiled = compile_policies(engine.policy_sets, engine.urns)
        return native.NativeBatchEncoder(compiled)

    def _messages(self, n, seed=0):
        from access_control_srv_tpu.srv.transport_grpc import request_to_pb

        orgs = [f"org-{j}" for j in range(4)]
        rng = np.random.default_rng(seed)
        out = []
        for i in range(n):
            k = int(rng.integers(64))
            tree = [{"id": orgs[0], "role": f"role-{i % 97}",
                     "children": [{"id": o} for o in orgs[1:]]}]
            out.append(request_to_pb(build_request(
                subject_id=f"u{i}", subject_role=f"role-{i % 97}",
                role_scoping_entity=ORG, role_scoping_instance=orgs[0],
                resource_type=(
                    f"urn:restorecommerce:acs:model:stress{k}.Stress{k}"
                ),
                resource_id=f"res-{i}", action_type=URNS["read"],
                owner_indicatory_entity=ORG,
                owner_instance=orgs[1 + i % 3],
                hierarchical_scopes=tree,
            )).SerializeToString())
        return out

    def test_unreleased_batches_share_nothing(self):
        enc = self._encoder()
        msgs = self._messages(16)
        b1 = enc.encode_wire(msgs, reuse=True)
        b2 = enc.encode_wire(self._messages(16, seed=1), reuse=True)
        ids1 = {id(v) for v in b1.arrays.values()}
        ids2 = {id(v) for v in b2.arrays.values()}
        assert not ids1 & ids2
        assert id(b1.eligible.base if b1.eligible.base is not None
                  else b1.eligible) not in ids2
        b1.release_staging()
        b2.release_staging()
        # released: the next encode recycles (arena hit, no fresh numpy)
        misses_before = enc._pool.stats()["misses"]
        arena_misses_before = enc.arena_stats()["misses"]
        b3 = enc.encode_wire(msgs, reuse=True)
        assert enc.arena_stats()["hits"] >= 1
        assert enc.arena_stats()["misses"] == arena_misses_before
        assert enc._pool.stats()["misses"] == misses_before
        # ...and the recycled buffers carry the same content as b1 did
        ref = enc.encode_wire(msgs)
        for name, arr in ref.arrays.items():
            np.testing.assert_array_equal(arr, b3.arrays[name], err_msg=name)
        b3.release_staging()

    def test_release_is_idempotent(self):
        enc = self._encoder()
        batch = enc.encode_wire(self._messages(4), reuse=True)
        batch.release_staging()
        batch.release_staging()  # second call is a no-op, not a crash


# ------------------------------------------- depth-N byte differential


class TestDepthDifferential:
    """Depth-4 (async dispatch/finalize split), depth-2 (legacy), and
    depth-1 serving must produce byte-identical responses on mixed
    eligible/ineligible traffic, admission on and off — and match the
    scalar oracle backend."""

    def _serve(self, cfg):
        from access_control_srv_tpu.srv.transport_grpc import response_to_pb

        worker = Worker().start(cfg)
        try:
            # batcher path: concurrent single submits aggregate into
            # kernel batches (the depth>2 async split engages here)
            requests = [mixed_request(i) for i in range(48)]
            with ThreadPoolExecutor(max_workers=16) as pool:
                batcher_responses = list(pool.map(
                    worker.service.is_allowed, requests
                ))
            # direct batch path (evaluator async split called sync)
            direct = worker.service.is_allowed_batch(
                [mixed_request(i) for i in range(48)]
            )
        finally:
            worker.stop()
        return (
            [response_to_pb(r).SerializeToString()
             for r in batcher_responses],
            [response_to_pb(r).SerializeToString() for r in direct],
        )

    @pytest.mark.parametrize("admission", [False, True])
    def test_depths_byte_identical(self, admission):
        ref = None
        for depth in (1, 2, 4):
            got = self._serve(pipe_cfg(depth, admission=admission))
            if ref is None:
                ref = got
            else:
                assert got == ref, f"depth {depth} diverged"

    def test_depth4_matches_oracle_backend(self):
        kernel = self._serve(pipe_cfg(4))
        # same depth config, backend forced to the scalar oracle
        cfg = pipe_cfg(4)
        cfg["evaluator"]["backend"] = "oracle"
        oracle = self._serve(cfg)
        assert kernel == oracle

    def test_default_depth_is_legacy(self):
        worker = Worker().start(seed_cfg())
        try:
            assert worker.batcher.pipeline_depth == 2
            assert not worker.batcher._async_pipeline
            assert worker.wire_pipeline.depth == 2
        finally:
            worker.stop()


# -------------------------------------------------- streaming ordering


class TestStreamingOrdering:
    def _worker(self, depth=4):
        from access_control_srv_tpu.srv.transport_grpc import (
            GrpcClient,
            GrpcServer,
        )

        worker = Worker().start(pipe_cfg(depth))
        server = GrpcServer(worker, "127.0.0.1:0").start()
        client = GrpcClient(server.addr)
        return worker, server, client

    def _frames(self, sizes):
        from access_control_srv_tpu.srv.gen import access_control_pb2 as pb
        from access_control_srv_tpu.srv.transport_grpc import request_to_pb

        frames = []
        for n in sizes:
            frame = pb.BatchRequest()
            for i in range(n):
                frame.requests.add().CopyFrom(
                    request_to_pb(mixed_request(i))
                )
            frames.append(frame)
        return frames

    def test_frames_answered_in_order_with_sizes(self):
        worker, server, client = self._worker()
        try:
            sizes = [8, 12, 9, 16, 10]
            responses = list(client.is_allowed_stream(
                iter(self._frames(sizes)), timeout=60
            ))
            assert [len(r.responses) for r in responses] == sizes
        finally:
            client.close()
            server.stop()
            worker.stop()

    def test_slow_first_frame_cannot_reorder_responses(self):
        """Delay the FIRST frame's finalize so later frames complete
        device evaluation first: response frames must still arrive in
        frame order, each with its own rows."""
        worker, server, client = self._worker()
        try:
            assert worker.evaluator.native_active
            evaluator = worker.evaluator
            real = evaluator.is_allowed_batch_wire_async
            state = {"calls": 0}

            def delayed(messages, span=None, reuse=False):
                fin = real(messages, span=span, reuse=reuse)
                state["calls"] += 1
                if fin is None or state["calls"] > 1:
                    return fin

                def slow_finalize():
                    time.sleep(0.4)
                    return fin()

                return slow_finalize

            evaluator.is_allowed_batch_wire_async = delayed
            try:
                sizes = [8, 12, 9]
                responses = list(client.is_allowed_stream(
                    iter(self._frames(sizes)), timeout=60
                ))
            finally:
                evaluator.is_allowed_batch_wire_async = real
            assert state["calls"] >= 1
            assert [len(r.responses) for r in responses] == sizes
        finally:
            client.close()
            server.stop()
            worker.stop()

    def test_concurrent_streams_share_one_pipeline(self):
        worker, server, client = self._worker()
        try:
            sizes_a = [8, 9, 10]
            sizes_b = [11, 12]
            out = {}

            def run(name, sizes):
                out[name] = [
                    len(r.responses)
                    for r in client.is_allowed_stream(
                        iter(self._frames(sizes)), timeout=60
                    )
                ]

            ta = threading.Thread(target=run, args=("a", sizes_a))
            tb = threading.Thread(target=run, args=("b", sizes_b))
            ta.start()
            tb.start()
            ta.join(60)
            tb.join(60)
            assert out["a"] == sizes_a
            assert out["b"] == sizes_b
        finally:
            client.close()
            server.stop()
            worker.stop()

    def test_aborted_streams_release_permits_and_leases(self):
        """Client disconnect mid-IsAllowedStream: after N aborted streams
        (each cancelled right after its first response frame, with more
        frames still queued against the pipeline's backpressure), every
        backpressure permit must be reacquirable and the pooled staging
        buffers must show zero live leases — a leak here would brick the
        shared pipeline for every later stream."""
        from access_control_srv_tpu.ops.staging import default_pool
        from access_control_srv_tpu.srv.gen import access_control_pb2 as pb

        worker, server, client = self._worker(depth=2)
        try:
            stub = client.channel.stream_stream(
                "/acstpu.AccessControlService/IsAllowedStream",
                request_serializer=lambda m: m.SerializeToString(),
                response_deserializer=pb.BatchResponse.FromString,
            )
            frame = self._frames([8])[0]

            def endless():
                while True:  # keeps feeding until the cancel lands
                    yield frame

            for _ in range(6):
                call = stub(endless(), timeout=30)
                first = next(call)
                assert len(first.responses) == 8
                call.cancel()

            pipeline = worker.wire_pipeline
            deadline = time.monotonic() + 15

            def permits_free() -> int:
                held = 0
                for _ in range(pipeline.depth):
                    if pipeline._slots.acquire(blocking=False):
                        held += 1
                for _ in range(held):
                    pipeline._slots.release()
                return held

            while time.monotonic() < deadline:
                if (permits_free() == pipeline.depth
                        and default_pool().stats()["leased"] == 0):
                    break
                time.sleep(0.05)
            assert permits_free() == pipeline.depth
            assert default_pool().stats()["leased"] == 0
            # the pipeline still serves a fresh, well-behaved stream
            sizes = [8, 12]
            responses = list(client.is_allowed_stream(
                iter(self._frames(sizes)), timeout=60
            ))
            assert [len(r.responses) for r in responses] == sizes
        finally:
            client.close()
            server.stop()
            worker.stop()

    def test_stream_matches_unary_byte_identical(self):
        worker, server, client = self._worker()
        try:
            frames = self._frames([8, 12])
            unary = [
                client.is_allowed_batch(f).SerializeToString()
                for f in frames
            ]
            streamed = [
                r.SerializeToString()
                for r in client.is_allowed_stream(iter(frames), timeout=60)
            ]
            assert unary == streamed
        finally:
            client.close()
            server.stop()
            worker.stop()


# --------------------------------------------- unified pipeline depth


class TestUnifiedPipelineDepth:
    def test_feasibility_estimate_tracks_configured_depth(self):
        for depth in (2, 6):
            controller = AdmissionController(
                enabled=True, pipeline_depth=depth, ewma_alpha=1.0
            )
            assert controller.pipeline_batches == depth + 1
            controller.observe_batch(INTERACTIVE, 0.010, 64)
            need = (depth + 1) * 0.010 * controller.deadline_headroom
            ok = controller.admit(
                INTERACTIVE, time.monotonic() + need * 1.5
            )
            assert ok is None
            controller.release(INTERACTIVE, 1)
            shed = controller.admit(
                INTERACTIVE, time.monotonic() + need * 0.8
            )
            assert shed is not None
            assert shed.operation_status.code == 429
            assert "deadline infeasible" in shed.operation_status.message

    def test_same_budget_feasible_shallow_infeasible_deep(self):
        """The regression PIPELINE_BATCHES hardcoding would hide: one
        budget that clears a depth-2 pipeline must be rejected by a
        depth-6 one."""
        budget_s = 3.3 * 0.010 * 1.2
        outcomes = {}
        for depth in (2, 6):
            controller = AdmissionController(
                enabled=True, pipeline_depth=depth, ewma_alpha=1.0
            )
            controller.observe_batch(INTERACTIVE, 0.010, 64)
            outcomes[depth] = controller.admit(
                INTERACTIVE, time.monotonic() + budget_s
            )
        assert outcomes[2] is None
        assert outcomes[6] is not None

    def test_from_config_reads_evaluator_pipeline_depth(self):
        controller = AdmissionController.from_config(Config({
            "evaluator": {"pipeline_depth": 5},
            "admission": {"enabled": True},
        }))
        assert controller.pipeline_batches == 6
        # plain-dict config (tests/bench call sites) defaults safely
        controller = AdmissionController.from_config(
            {"admission": {"enabled": True}}
        )
        assert controller.pipeline_batches == 3

    def test_worker_wires_one_depth_everywhere(self):
        cfg = pipe_cfg(4, admission=True)
        worker = Worker().start(cfg)
        try:
            assert worker.batcher.pipeline_depth == 4
            assert worker.batcher._async_pipeline
            assert worker.wire_pipeline.depth == 4
            assert worker.admission.pipeline_batches == 5
            assert worker.admission.stats()["pipeline_batches"] == 5
        finally:
            worker.stop()


# ------------------------------------------------------- churn soak


@pytest.mark.slow
class TestChurnMidPipeline:
    def test_delta_patches_landing_mid_pipeline_stay_correct(self):
        """CRUD delta patches swap the kernel while depth-4 frames are in
        flight (PR 4's swap-stable jit registry): every response stays a
        valid decision, and after quiescing the served decisions match
        the post-churn oracle."""
        from access_control_srv_tpu.srv.transport_grpc import (
            GrpcClient,
            GrpcServer,
        )

        worker = Worker().start(pipe_cfg(4))
        server = GrpcServer(worker, "127.0.0.1:0").start()
        client = GrpcClient(server.addr)
        rule_service = worker.store.get_resource_service("rule")
        stop_churn = threading.Event()

        def churn():
            flip = 0
            while not stop_churn.is_set():
                flip += 1
                rule_service.update([{
                    "id": "super_admin_rule",
                    "name": f"churn-{flip}",
                    "target": {
                        "subjects": [{
                            "id": URNS["role"],
                            "value": "superadministrator-r-id",
                        }],
                        "resources": [{"id": URNS["entity"], "value": ORG}],
                        "actions": [{"id": URNS["actionID"],
                                     "value": URNS["read"]}],
                    },
                    "effect": "PERMIT" if flip % 2 else "DENY",
                }])
                time.sleep(0.01)

        churner = threading.Thread(target=churn, daemon=True)
        try:
            from access_control_srv_tpu.srv.gen import access_control_pb2 as pb
            from access_control_srv_tpu.srv.transport_grpc import (
                request_to_pb,
            )

            def frames(n_frames):
                for _ in range(n_frames):
                    frame = pb.BatchRequest()
                    for i in range(16):
                        frame.requests.add().CopyFrom(
                            request_to_pb(mixed_request(i))
                        )
                    yield frame

            churner.start()
            responses = list(client.is_allowed_stream(
                frames(30), timeout=120
            ))
            stop_churn.set()
            churner.join(5)
            assert len(responses) == 30
            for frame in responses:
                assert len(frame.responses) == 16
                for row in frame.responses:
                    assert row.decision in (pb.PERMIT, pb.DENY,
                                            pb.INDETERMINATE)
            # quiesced: a fresh frame must match the oracle exactly
            reqs = [mixed_request(i) for i in range(16)]
            served = worker.service.is_allowed_batch(
                [mixed_request(i) for i in range(16)]
            )
            oracle = [
                worker.evaluator._oracle_is_allowed(r) for r in reqs
            ]
            for s, o in zip(served, oracle):
                assert s.decision == o.decision
        finally:
            stop_churn.set()
            client.close()
            server.stop()
            worker.stop()


# -------------------------------------------------- lock-order soak


@pytest.mark.slow
class TestLockOrderUnderPipelineSoak:
    """Runtime complement of acs-lint's static lock discipline (see
    access_control_srv_tpu/analysis/locktrace.py): every Lock/RLock the
    serving stack CREATES during the soak is tracked, each acquisition
    with locks held records a held->acquiring edge, and a cycle in that
    graph is a deadlock the scheduler merely hasn't dealt yet."""

    def test_no_lock_order_cycles_in_churned_pipeline(self):
        from access_control_srv_tpu.analysis.locktrace import (
            lock_order_watch,
        )

        with lock_order_watch() as watch:
            worker = Worker().start(pipe_cfg(4, admission=True))
            rule_service = worker.store.get_resource_service("rule")
            stop_churn = threading.Event()

            def churn():
                flip = 0
                while not stop_churn.is_set():
                    flip += 1
                    rule_service.update([{
                        "id": "super_admin_rule",
                        "name": f"lockorder-churn-{flip}",
                        "target": {
                            "subjects": [{
                                "id": URNS["role"],
                                "value": "superadministrator-r-id",
                            }],
                            "resources": [{"id": URNS["entity"],
                                           "value": ORG}],
                            "actions": [{"id": URNS["actionID"],
                                         "value": URNS["read"]}],
                        },
                        "effect": "PERMIT" if flip % 2 else "DENY",
                    }])
                    time.sleep(0.01)

            churner = threading.Thread(target=churn, daemon=True)
            try:
                churner.start()

                def serve(seed):
                    for frame in range(20):
                        worker.service.is_allowed_batch([
                            mixed_request(seed * 31 + frame * 7 + i)
                            for i in range(16)
                        ])

                with ThreadPoolExecutor(max_workers=6) as pool:
                    futures = [pool.submit(serve, n) for n in range(6)]
                    for future in futures:
                        future.result(timeout=120)
            finally:
                stop_churn.set()
                churner.join(timeout=5)
                worker.stop()
        watch.assert_acyclic()
        # the soak must have exercised real nested acquisitions — an
        # empty graph would mean the watch missed the system entirely
        assert watch.edges(), "no lock-order edges recorded during soak"
