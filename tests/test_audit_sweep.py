"""Permission-lattice audit-engine tests (ops/lattice.py +
srv/audit_sweep.py): the combining-fold differential against the scalar
isAllowed oracle (decisions AND deciding-rule provenance), snapshot
JSONL/bitmap round trips with audit-log masking, the one-rule-flip diff
oracle (the diff must name exactly the flipped rule's cells), the sweep
job lifecycle over the batcher's BULK class (pause/resume/cancel, honest
sheds with bounded retries), the decision-cache no-pollution regression,
reverse-kernel program identity across sweep chunks, the shadow twin
loop, and the config-gated worker/command integration."""

import copy
import json
import os
import threading
import time

import numpy as np
import pytest
import yaml

import synth
from access_control_srv_tpu.core import AccessController
from access_control_srv_tpu.core.loader import load_policy_sets
from access_control_srv_tpu.models import Decision
from access_control_srv_tpu.models.model import (
    OperationStatus,
    PolicyRQ,
    PolicySetRQ,
    ReverseQuery,
    RuleRQ,
)
from access_control_srv_tpu.ops import reverse as reverse_mod
from access_control_srv_tpu.ops.lattice import (
    CODE_CONDITIONAL,
    CODE_DENY,
    CODE_NOT_APPLICABLE,
    CODE_PERMIT,
    LatticeSpec,
    SnapshotWriter,
    diff_snapshots,
    fold_reverse_query,
    load_bitmap,
    load_snapshot,
    pack_codes,
    unpack_codes,
)
from access_control_srv_tpu.srv import audit_sweep as audit_mod
from access_control_srv_tpu.srv.audit_sweep import AuditSweepManager
from access_control_srv_tpu.srv.config import Config
from access_control_srv_tpu.srv.decision_cache import DecisionCache
from access_control_srv_tpu.srv.evaluator import HybridEvaluator
from access_control_srv_tpu.srv.shadow import ShadowEvaluator
from access_control_srv_tpu.srv.telemetry import Telemetry

from .test_admission import StubEvaluator, controller, make_batcher

DO = synth.DO
PO = synth.PO

ALL_ACTIONS = ("read", "modify", "create", "delete")


def stress_engine(n_rules=48, flip_every=0):
    doc, _ = synth.stress_doc(n_rules, flip_every=flip_every)
    engine = AccessController()
    for ps in load_policy_sets(doc):
        engine.update_policy_set(ps)
    return engine


def small_spec(n=8, actions=ALL_ACTIONS):
    return LatticeSpec.stress(n, n, actions=actions)


# ------------------------------------------------------------------- fold


class TestFold:
    def test_fold_matches_is_allowed_oracle_with_provenance(self):
        """fold(whatIsAllowed(cell)) must equal isAllowed(cell) on every
        lattice cell of a condition-free tree — decision AND deciding
        rule id (the engine's EffectEvaluation.source)."""
        engine = stress_engine(48)
        for index_req in small_spec(8).chunks(256):
            for _, req in index_req:
                rq = engine.what_is_allowed(copy.deepcopy(req))
                verdict = fold_reverse_query(rq)
                resp = engine.is_allowed(copy.deepcopy(req))
                assert verdict.decision == resp.decision
                if resp.decision in (Decision.PERMIT, Decision.DENY):
                    assert verdict.rule_id == resp._rule_id

    def _rq(self, algorithm, rules, set_algorithm=DO):
        policy = PolicyRQ(
            id="p0", combining_algorithm=algorithm, has_rules=True,
            rules=[
                RuleRQ(id=rid, effect=eff, condition=cond)
                for rid, eff, cond in rules
            ],
        )
        return ReverseQuery(policy_sets=[PolicySetRQ(
            id="s0", combining_algorithm=set_algorithm, policies=[policy],
        )])

    def test_deny_overrides_first_deny_wins(self):
        v = fold_reverse_query(self._rq(DO, [
            ("r0", "PERMIT", ""), ("r1", "DENY", ""), ("r2", "DENY", ""),
        ]))
        assert (v.decision, v.rule_id) == (Decision.DENY, "r1")

    def test_deny_overrides_no_deny_takes_last(self):
        v = fold_reverse_query(self._rq(DO, [
            ("r0", "PERMIT", ""), ("r1", "PERMIT", ""),
        ]))
        assert (v.decision, v.rule_id) == (Decision.PERMIT, "r1")

    def test_permit_overrides_first_permit_wins(self):
        v = fold_reverse_query(self._rq(PO, [
            ("r0", "DENY", ""), ("r1", "PERMIT", ""), ("r2", "PERMIT", ""),
        ]))
        assert (v.decision, v.rule_id) == (Decision.PERMIT, "r1")

    def test_first_applicable_takes_first(self):
        fa = ("urn:oasis:names:tc:xacml:3.0:rule-combining-algorithm:"
              "first-applicable")
        v = fold_reverse_query(self._rq(fa, [
            ("r0", "DENY", ""), ("r1", "PERMIT", ""),
        ]))
        assert (v.decision, v.rule_id) == (Decision.DENY, "r0")

    def test_last_set_with_effects_wins(self):
        """The engine's cross-set collection overwrites: the LAST policy
        set producing effects decides (core/engine.py isAllowed loop)."""
        rq_a = self._rq(PO, [("r0", "DENY", "")])
        rq_b = self._rq(PO, [("r1", "PERMIT", "")])
        rq = ReverseQuery(
            policy_sets=rq_a.policy_sets + rq_b.policy_sets
        )
        v = fold_reverse_query(rq)
        assert (v.decision, v.rule_id) == (Decision.PERMIT, "r1")

    def test_ruleless_policy_contributes_own_effect(self):
        policy = PolicyRQ(id="p0", effect="PERMIT", has_rules=False)
        rq = ReverseQuery(policy_sets=[PolicySetRQ(
            id="s0", combining_algorithm=DO, policies=[policy],
        )])
        v = fold_reverse_query(rq)
        assert (v.decision, v.rule_id) == (Decision.PERMIT, "p0")

    def test_policy_with_rules_defined_but_none_matched_is_inert(self):
        """has_rules=True with an empty matched-rule list must NOT fall
        back to the policy effect — mirrors engine.py:285 (the effect
        stands in only for genuinely rule-less policies)."""
        policy = PolicyRQ(id="p0", effect="PERMIT", has_rules=True)
        rq = ReverseQuery(policy_sets=[PolicySetRQ(
            id="s0", combining_algorithm=DO, policies=[policy],
        )])
        assert fold_reverse_query(rq).decision == Decision.INDETERMINATE

    def test_conditional_rule_flags_cell(self):
        """whatIsAllowed never evaluates conditions, so any cell whose
        winning tree contains one is an optimistic bound — flagged and
        coded CONDITIONAL in the bitmap, never presented as definitive."""
        v = fold_reverse_query(self._rq(DO, [
            ("r0", "PERMIT", "context.subject.id === 'u1'"),
        ]))
        assert v.decision == Decision.PERMIT
        assert v.conditional and v.code == CODE_CONDITIONAL

    def test_unknown_combining_algorithm_is_honest_indeterminate(self):
        v = fold_reverse_query(self._rq("urn:custom:nope", [
            ("r0", "PERMIT", ""),
        ]))
        assert v.decision == Decision.INDETERMINATE
        assert v.rule_id is None

    def test_shed_tree_carries_code(self):
        rq = ReverseQuery(operation_status=OperationStatus(
            code=429, message="overload"
        ))
        v = fold_reverse_query(rq)
        assert v.decision == Decision.INDETERMINATE
        assert v.shed_code == 429


# --------------------------------------------------------------- snapshot


class TestSnapshot:
    def test_roundtrip_jsonl_and_bitmap(self, tmp_path):
        engine = stress_engine(48)
        spec = small_spec(6)
        path = str(tmp_path / "snap.jsonl")
        writer = SnapshotWriter(path, spec, source="production",
                                policy_epoch=7)
        expected = {}
        for chunk in spec.chunks(50):
            for index, req in chunk:
                v = fold_reverse_query(engine.what_is_allowed(req))
                writer.write(index, v)
                expected[index] = v
        summary = writer.close()
        assert summary["cells"] == spec.n_cells

        header, cells, footer = load_snapshot(path)
        assert header["shape"] == list(spec.shape)
        assert header["policy_epoch"] == 7
        assert footer["cells"] == spec.n_cells
        decided = {
            i for i, v in expected.items()
            if v.decision in (Decision.PERMIT, Decision.DENY)
        }
        assert set(cells) == {spec.unravel(i) for i in decided}
        for index in decided:
            row = cells[spec.unravel(index)]
            assert row["d"] == expected[index].decision
            assert row["r"] == expected[index].rule_id

        codes = load_bitmap(path + ".bits.npy", spec.n_cells)
        for index, v in expected.items():
            assert codes[index] == v.code

    def test_bitmap_pack_unpack_identity(self):
        rng = np.random.default_rng(5)
        codes = rng.integers(0, 4, size=997).astype(np.uint8)
        assert (unpack_codes(pack_codes(codes), 997) == codes).all()
        packed = pack_codes(codes)
        assert packed.nbytes == (997 + 3) // 4

    def test_secret_named_axis_urns_are_masked(self, tmp_path):
        """The masking guarantee of the exported matrix: axis values
        whose attribute URN names a secret (the PR 6 audit-log rule) are
        ``***`` in the header, and cell lines reference axis indices
        only — a secret principal id can never leak into the artifact."""
        spec = LatticeSpec(
            subjects=(("sup3rsecret-token-1", "admin"),),
            resources=(("res0", "urn:restorecommerce:acs:model:a.A"),),
            actions=("urn:restorecommerce:acs:names:action:read",),
            subject_id_urn="urn:restorecommerce:acs:names:token",
        )
        path = str(tmp_path / "masked.jsonl")
        writer = SnapshotWriter(path, spec)
        writer.write(0, fold_reverse_query(ReverseQuery()))
        writer.close()
        text = open(path).read()
        assert "sup3rsecret-token-1" not in text
        header, _, _ = load_snapshot(path)
        assert header["axes"]["subjects"][0]["id"] == "***"
        # roles ride a non-secret URN and stay readable
        assert header["axes"]["subjects"][0]["role"] == "admin"

    def test_cell_lines_carry_indices_never_values(self, tmp_path):
        """Schema guarantee: every non-header line is either a cell row
        ``{c, d, r?, q?, s?}`` or the summary — no attribute values."""
        engine = stress_engine(48)
        spec = small_spec(4)
        path = str(tmp_path / "schema.jsonl")
        writer = SnapshotWriter(path, spec)
        for chunk in spec.chunks(64):
            for index, req in chunk:
                writer.write(
                    index, fold_reverse_query(engine.what_is_allowed(req))
                )
        writer.close()
        lines = [json.loads(l) for l in open(path) if l.strip()]
        assert lines[0]["kind"] == "acs-lattice-snapshot"
        assert lines[-1]["kind"] == "acs-lattice-summary"
        for row in lines[1:-1]:
            assert set(row) <= {"c", "d", "r", "q", "s"}
            assert all(isinstance(i, int) for i in row["c"])


# ------------------------------------------------------------------- diff


class TestDiff:
    def test_one_rule_flip_names_exactly_the_flipped_cells(self, tmp_path):
        """The acceptance oracle: sweeping a candidate with exactly one
        rule flipped (synth.stress_doc flip_every > rid range flips
        only r0) must diff exactly the cells where the scalar oracle's
        decisions differ, and every diff cell must name r0."""
        engine_a = stress_engine(48)
        engine_b = stress_engine(48, flip_every=10 ** 9)
        spec = small_spec(10)
        paths = {}
        for name, engine in (("a", engine_a), ("b", engine_b)):
            paths[name] = str(tmp_path / f"{name}.jsonl")
            writer = SnapshotWriter(paths[name], spec, source=name)
            for chunk in spec.chunks(128):
                for index, req in chunk:
                    writer.write(
                        index,
                        fold_reverse_query(engine.what_is_allowed(req)),
                    )
            writer.close()

        diff = diff_snapshots(paths["a"], paths["b"])
        expected = set()
        for chunk in spec.chunks(256):
            for index, req in chunk:
                da = engine_a.is_allowed(copy.deepcopy(req)).decision
                db = engine_b.is_allowed(copy.deepcopy(req)).decision
                if da != db:
                    expected.add(spec.unravel(index))
        assert expected, "the flip must affect at least one cell"
        assert {tuple(c["cell"]) for c in diff["cells"]} == expected
        assert diff["cells_changed"] == len(expected)
        assert diff["rules"] == ["r0"]
        for cell in diff["cells"]:
            assert "r0" in (cell["a"]["rule"], cell["b"]["rule"])

    def test_identical_snapshots_diff_empty(self, tmp_path):
        engine = stress_engine(48)
        spec = small_spec(4)
        paths = []
        for name in ("x", "y"):
            path = str(tmp_path / f"{name}.jsonl")
            writer = SnapshotWriter(path, spec)
            for chunk in spec.chunks(64):
                for index, req in chunk:
                    writer.write(
                        index,
                        fold_reverse_query(engine.what_is_allowed(req)),
                    )
            writer.close()
            paths.append(path)
        diff = diff_snapshots(*paths)
        assert diff["cells_changed"] == 0 and diff["cells"] == []

    def test_shape_mismatch_raises(self, tmp_path):
        for name, n in (("a", 2), ("b", 3)):
            writer = SnapshotWriter(
                str(tmp_path / f"{name}.jsonl"), small_spec(n)
            )
            writer.close()
        with pytest.raises(ValueError, match="shapes differ"):
            diff_snapshots(
                str(tmp_path / "a.jsonl"), str(tmp_path / "b.jsonl")
            )

    def test_diff_limit_truncates_explicitly(self, tmp_path):
        cells_a = {(0, 0, i): {"c": [0, 0, i], "d": "PERMIT", "r": "ra"}
                   for i in range(8)}
        from access_control_srv_tpu.ops.lattice import diff_cells

        diff = diff_cells(cells_a, {}, limit=3)
        assert diff["cells_changed"] == 8
        assert len(diff["cells"]) == 3 and diff["truncated"] == 5


# ---------------------------------------------------------- sweep manager


class ShedOnceEvaluator(StubEvaluator):
    """First bulk batch sheds (an overloaded window), retries succeed."""

    def __init__(self):
        super().__init__()
        self.calls = 0

    def what_is_allowed_batch(self, requests):
        self.calls += 1
        code = 429 if self.calls == 1 else 200
        self.bulk_batches.append(len(requests))
        return [
            ReverseQuery(operation_status=OperationStatus(code=code))
            for _ in requests
        ]


class TestSweepManager:
    def test_bulk_sweep_completes_and_counts(self, tmp_path):
        telemetry = Telemetry()
        batcher = make_batcher(StubEvaluator(), controller())
        manager = AuditSweepManager(
            batcher.evaluator, batcher=batcher, telemetry=telemetry,
            out_dir=str(tmp_path), chunk_size=16,
        )
        try:
            job = manager.start_sweep(
                spec=small_spec(4, actions=("read",)), wait=True,
                wait_timeout=60,
            )
            assert job.state == "done"
            assert job.cells_done == 16
            assert os.path.exists(job.snapshot_path)
            assert os.path.exists(job.bitmap_path)
            events = telemetry.snapshot()["audit"]
            assert events["jobs_started"] == 1
            assert events["jobs_completed"] == 1
            assert events["cells"] == 16
        finally:
            manager.stop()
            batcher.stop()

    def test_pause_freezes_and_cancel_finishes_early(self, tmp_path):
        batcher = make_batcher(StubEvaluator(delay_s=0.01), controller())
        manager = AuditSweepManager(
            batcher.evaluator, batcher=batcher,
            out_dir=str(tmp_path), chunk_size=4,
        )
        try:
            job = manager.start_sweep(
                spec=LatticeSpec.stress(32, 32, actions=("read",))
            )
            deadline = time.monotonic() + 10
            while job.status()["cells_done"] == 0:
                assert time.monotonic() < deadline, "sweep never started"
                time.sleep(0.005)
            manager.pause(job.job_id)
            # the sweep acknowledges the pause once the chunk in flight
            # has landed: only then is cells_done final
            deadline = time.monotonic() + 10
            while not job.status()["parked"]:
                assert time.monotonic() < deadline, "pause never took hold"
                time.sleep(0.005)
            frozen = job.status()["cells_done"]
            time.sleep(0.15)
            assert job.status()["cells_done"] == frozen, (
                "a paused sweep kept dispatching bulk chunks"
            )
            manager.resume(job.job_id)
            deadline = time.monotonic() + 10
            while job.status()["cells_done"] <= frozen:
                assert time.monotonic() < deadline, "resume never moved"
                time.sleep(0.005)
            manager.cancel(job.job_id)
            assert job.wait(10)
            assert job.state == "cancelled"
            assert job.cells_done < job.spec.n_cells
            # the partial snapshot is still well-formed (header + footer)
            header, _, footer = load_snapshot(job.snapshot_path)
            assert footer is not None
        finally:
            manager.stop()
            batcher.stop()

    def test_shed_cells_retry_then_succeed(self, tmp_path):
        evaluator = ShedOnceEvaluator()
        batcher = make_batcher(evaluator, controller())
        manager = AuditSweepManager(
            evaluator, batcher=batcher,
            out_dir=str(tmp_path), chunk_size=8, max_retries=3,
        )
        try:
            job = manager.start_sweep(
                spec=LatticeSpec.stress(2, 4, actions=("read",)),
                wait=True, wait_timeout=60,
            )
            assert job.state == "done"
            assert job.retries >= 1
            assert job.summary["sheds"] == 0, (
                "retried cells must land as real verdicts, not sheds"
            )
        finally:
            manager.stop()
            batcher.stop()

    def test_exhausted_retries_land_as_honest_sheds(self, tmp_path):
        class AlwaysShed(StubEvaluator):
            def what_is_allowed_batch(self, requests):
                self.bulk_batches.append(len(requests))
                return [
                    ReverseQuery(operation_status=OperationStatus(code=429))
                    for _ in requests
                ]

        batcher = make_batcher(AlwaysShed(), controller())
        manager = AuditSweepManager(
            batcher.evaluator, batcher=batcher,
            out_dir=str(tmp_path), chunk_size=4, max_retries=1,
        )
        try:
            job = manager.start_sweep(
                spec=LatticeSpec.stress(2, 2, actions=("read",)),
                wait=True, wait_timeout=60,
            )
            assert job.state == "done"
            assert job.summary["sheds"] == 4
            _, cells, _ = load_snapshot(job.snapshot_path)
            assert all(row["s"] == 429 for row in cells.values())
            assert all(
                row["d"] == Decision.INDETERMINATE for row in cells.values()
            )
        finally:
            manager.stop()
            batcher.stop()

    def test_sweep_never_pollutes_decision_cache(self, tmp_path):
        """The satellite regression: submit_reverse bypasses the decision
        cache BY DESIGN (srv/batcher.py) — a full sweep must insert
        nothing into the interactive cache or its tenant namespaces."""
        engine = stress_engine(48)
        cache = DecisionCache(enabled=True)
        evaluator = HybridEvaluator(
            engine, backend="oracle", decision_cache=cache
        )
        batcher = make_batcher(evaluator, controller())
        manager = AuditSweepManager(
            evaluator, batcher=batcher,
            out_dir=str(tmp_path), chunk_size=16,
        )
        try:
            job = manager.start_sweep(
                spec=small_spec(4, actions=("read",)), wait=True,
                wait_timeout=120,
            )
            assert job.state == "done"
            stats = cache.stats()
            assert stats["stores"] == 0, "sweep traffic reached the cache"
            assert stats["entries"] == 0
            assert stats["hits"] == 0 and stats["misses"] == 0
        finally:
            manager.stop()
            batcher.stop()
            evaluator.shutdown()


# ----------------------------------------------------- program identity


class TestProgramIdentity:
    def test_sweep_reuses_reverse_kernel_programs(self, monkeypatch,
                                                  tmp_path):
        """Zero new XLA compiles across sweep chunks: after a warm
        sweep, a second identical sweep adds no jit-registry keys and
        keeps the SAME ReverseQueryKernel object (compiled program
        reuse), and XLA compiles nothing for it."""
        import jax
        from jax._src.dispatch import BACKEND_COMPILE_EVENT

        monkeypatch.setattr(reverse_mod, "REVERSE_MIN_RULES", 0)
        engine = stress_engine(48)
        telemetry = Telemetry()
        evaluator = HybridEvaluator(
            engine, backend="kernel", telemetry=telemetry
        )
        manager = AuditSweepManager(
            evaluator, out_dir=str(tmp_path), chunk_size=32,
        )
        spec = small_spec(6)
        try:
            warm = manager.start_sweep(spec=spec, wait=True,
                                       wait_timeout=120)
            assert warm.state == "done"
            kernel = evaluator._rq_kernel
            assert kernel is not None, "sweep never engaged the kernel"
            keys_before = set(kernel._runs)
            version_before = kernel.compiled.version
            # the reverse runners bind their tables into the program, so
            # compiles are counted where XLA reports them
            compiles = []

            def count(event, duration, **kwargs):
                if event == BACKEND_COMPILE_EVENT:
                    compiles.append(kwargs.get("fun_name"))

            jax.monitoring.register_event_duration_secs_listener(count)
            try:
                job = manager.start_sweep(spec=spec, wait=True,
                                          wait_timeout=120)
            finally:
                jax.monitoring.unregister_event_duration_listener(count)
            assert compiles == []
            assert job.state == "done"
            assert evaluator._rq_kernel is kernel
            assert set(kernel._runs) == keys_before, (
                "a sweep chunk traced a new reverse-kernel program"
            )
            assert kernel.compiled.version == version_before
            assert telemetry.paths.get("kernel-wia"), (
                "sweep cells must ride the device-assisted wia path"
            )
        finally:
            manager.stop()
            evaluator.shutdown()


# ------------------------------------------------------------- twin loop


class TestTwinLoop:
    def test_twin_report_names_flipped_rule_and_live_diffs(self, tmp_path):
        """The learned-policy loop: a mined candidate (here: one flipped
        rule) loads through ShadowEvaluator with zero new compiles, the
        twin sweep diffs the full lattice naming the flipped rule, and
        the same report carries the live-traffic diff counters."""
        doc_b, _ = synth.stress_doc(48, flip_every=10 ** 9)
        candidate = str(tmp_path / "candidate.yml")
        with open(candidate, "w") as fh:
            yaml.safe_dump(doc_b, fh)
        engine = stress_engine(48)
        production = HybridEvaluator(engine, backend="oracle")
        shadow = ShadowEvaluator(production, [candidate])

        class WorkerStub:
            pass

        worker = WorkerStub()
        worker.shadow = shadow
        manager = AuditSweepManager(
            production, worker=worker, out_dir=str(tmp_path), chunk_size=64,
        )
        try:
            report = manager.sweep_twin(
                spec=small_spec(8), wait_timeout=120
            )
            assert report["production"]["state"] == "done"
            assert report["candidate"]["state"] == "done"
            diff = report["lattice_diff"]
            assert diff["rules"] == ["r0"]
            assert diff["cells_changed"] >= 1
            assert report["live_traffic"]["enabled"] is True
            assert shadow.new_program_keys == []
        finally:
            manager.stop()
            shadow.stop()
            production.shutdown()

    def test_shadow_target_requires_loaded_candidate(self, tmp_path):
        manager = AuditSweepManager(
            StubEvaluator(), out_dir=str(tmp_path)
        )
        with pytest.raises(RuntimeError, match="shadow"):
            manager.start_sweep(target="shadow")
        manager.stop()


# ------------------------------------------------------- config / command


class TestConfigGating:
    def test_disabled_by_default_builds_nothing(self):
        cfg = Config({})
        assert cfg.get("audit:enabled") is False
        assert audit_mod.from_config(cfg, evaluator=StubEvaluator()) is None

    def test_enabled_builds_manager_from_block(self, tmp_path):
        cfg = Config({"audit": {
            "enabled": True,
            "out_dir": str(tmp_path),
            "chunk_size": 64,
            "max_retries": 1,
            "lattice": {"subjects": 4, "resources": 4,
                        "actions": ["read"]},
        }})
        manager = audit_mod.from_config(cfg, evaluator=StubEvaluator())
        assert isinstance(manager, AuditSweepManager)
        assert manager.chunk_size == 64
        assert manager.max_retries == 1
        job = manager.start_sweep(wait=True, wait_timeout=30)
        assert job.state == "done"
        assert job.spec.n_cells == 16
        manager.stop()


class TestWorkerIntegration:
    def test_worker_audit_command_end_to_end(self, tmp_path):
        """audit:enabled worker: the audit_sweep command starts, reports
        and diffs sweeps over the seed policies, health_check grows a
        compact audit block, and telemetry exports acs_audit_* counters."""
        from .test_srv import seed_cfg
        from access_control_srv_tpu.srv import Worker

        cfg = seed_cfg()
        cfg["audit"] = {
            "enabled": True,
            "out_dir": str(tmp_path),
            "chunk_size": 32,
            "lattice": {"subjects": 4, "resources": 4,
                        "actions": ["read"]},
        }
        worker = Worker().start(cfg)
        try:
            assert worker.audit is not None
            started = worker.command_interface.command(
                "audit_sweep", {"action": "start", "wait": True}
            )
            assert started["state"] == "done"
            assert started["cells_done"] == 16
            status = worker.command_interface.command(
                "audit_sweep", {"action": "status"}
            )
            assert status["running"] == 0
            health = worker.command_interface.command("health_check", {})
            assert health["audit"]["jobs"][0]["state"] == "done"
            assert "acs_audit_events_total" in worker.telemetry.prometheus()
            # a second sweep diffs clean against the first (same tree)
            second = worker.command_interface.command(
                "audit_sweep", {"action": "start", "wait": True}
            )
            diff = worker.command_interface.command(
                "audit_sweep",
                {"action": "diff", "a": started["job"], "b": second["job"]},
            )
            assert diff["cells_changed"] == 0
        finally:
            worker.stop()

    def test_worker_disabled_default_has_no_surface(self):
        from .test_srv import seed_cfg
        from access_control_srv_tpu.srv import Worker

        worker = Worker().start(seed_cfg())
        try:
            assert worker.audit is None
            out = worker.command_interface.command(
                "audit_sweep", {"action": "start"}
            )
            assert out == {"enabled": False}
            health = worker.command_interface.command("health_check", {})
            assert "audit" not in health
            snapshot = worker.telemetry.snapshot()
            assert "audit" not in snapshot
        finally:
            worker.stop()
