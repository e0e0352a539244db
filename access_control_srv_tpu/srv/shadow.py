"""Shadow evaluation: policy CI against live traffic at kernel speed.

A candidate policy set (the "next" tree an operator wants to ship) loads
BESIDE production as a second table set evaluated on the same compiled
device programs: the shadow's :class:`~.evaluator.HybridEvaluator` is
built with the production evaluator's pinned capacity class
(``fixed_caps``) and its shared jit registry, so candidate tables take
the identical padded shapes and every kernel dispatch hits the per-shape
caches inside the already-jitted executables — **zero new XLA
compilations** for a candidate in the same size class (asserted at
construction; an out-of-class candidate is refused with
:class:`ShadowSizeClassError` rather than silently compiling a second
program).

Live traffic is mirrored AFTER the production decision is served: the
service facade (srv/service.py) enqueues ``(requests, decisions)`` pairs
onto a bounded drop-queue and a dedicated worker thread replays them
against the candidate tree, counting decision diffs by transition
(``acs_shadow_diffs_total{transition="PERMIT->DENY"}`` ...) and
retaining a bounded sample of diff records — each carrying deciding-node
provenance for BOTH sides, recovered through the host oracle's
``EffectEvaluation.source`` walk on the sampled rows (exact, and free of
any device-program change, so the invariant below holds even with
explain mode off).

Honesty invariants (tests/test_explain.py::TestShadow):

- A shadow evaluation can NEVER alter a production decision: the mirror
  point is after response assembly, the shadow engine/evaluator objects
  are fully disjoint from production's, and the shadow evaluator is
  built with ``decision_cache=None`` so no candidate decision can ever
  be cached — let alone served — as a production one.
- A shadow evaluation can NEVER delay a production response past its
  deadline bound: ``submit`` is a lock-append-notify (drops when the
  queue is full, counted as ``dropped``), and all candidate evaluation
  runs on the shadow worker thread off the response path.
- Disabled (the default: ``shadow:enabled`` false), no shadow object
  exists and the serving path is byte-identical to pre-shadow behavior.

The shadow epoch advances independently of production's policy epoch:
``reload``/``update_policy_set`` mutate only the candidate tree and bump
only the shadow's own counter — a production CRUD never touches the
candidate, and vice versa.  With multi-tenant serving (srv/tenancy.py),
``shadow:tenant`` scopes the mirror to one tenant's traffic
(``request._tenant``) so a single tenant's candidate tree can be staged
against exactly the rows that would hit it.
"""

from __future__ import annotations

import copy
import threading
from typing import Optional

from ..core.engine import AccessController
from ..core.loader import load_policy_sets_from_file


# admission/drain sheds (srv/admission.py: OVERLOAD/SHUTDOWN/DEADLINE
# codes) answer with INDETERMINATE + an overload status — the row was
# never evaluated, so mirroring it would fabricate an
# ``INDETERMINATE->X`` diff against a candidate that DID evaluate it
_SHED_CODES = frozenset((429, 503, 504))


class ShadowSizeClassError(RuntimeError):
    """The candidate tree does not fit the production size class — a
    shadow for it would compile a second device program, which defeats
    the zero-new-compiles contract.  Stage it on a worker pinned to the
    larger class instead."""


class ShadowEvaluator:
    """Candidate-tree evaluator + diff accounting behind a drop-queue."""

    def __init__(self, production, candidate_paths: list,
                 combining_algorithms=None, telemetry=None, logger=None,
                 tenant: Optional[str] = None, sample_diffs: int = 32,
                 queue_batches: int = 64):
        from .evaluator import HybridEvaluator

        self.production = production
        self.candidate_paths = list(candidate_paths)
        self.telemetry = telemetry
        self.logger = logger
        self.tenant = tenant
        self.sample_diffs = int(sample_diffs)
        self.epoch = 0
        self._combining = combining_algorithms

        self.engine = AccessController(
            urns=production.engine.urns,
            combining_algorithms=combining_algorithms,
            logger=logger,
            identity_client=production.engine.identity_client,
            hr_scope_provider=production.engine.hr_scope_provider,
            resource_adapter=production.engine.resource_adapter,
        )
        self._load_candidate()

        jits_before = set(production._shared_jits)
        self.evaluator = HybridEvaluator(
            self.engine,
            backend=production.backend,
            logger=logger,
            telemetry=None,  # shadow rows must not skew serving-path counters
            mesh=production.mesh,
            mesh_axis=production.mesh_axis,
            model_axis=production.model_axis,
            pod_shards=production.pod_shards,
            decision_cache=None,  # INVARIANT: shadow decisions never cached
            shared_jits=production._shared_jits,
            fixed_caps=production._caps,
            explain=production.explain,
        )
        # same-size-class proof: the candidate compile under the pinned
        # class must publish the production capacities verbatim (the
        # fixed_caps fallback to per-tenant buckets means overflow)...
        prod_caps = production._caps
        mine = self.evaluator._caps
        if prod_caps is not None and (
            mine is None or mine.as_dict() != prod_caps.as_dict()
        ):
            raise ShadowSizeClassError(
                "candidate tree overflows the production size class "
                f"(production caps {prod_caps.as_dict()}, candidate "
                f"{None if mine is None else mine.as_dict()})"
            )
        # ...and construction must not have registered any new device
        # program in the shared jit registry (kernel variants key in at
        # build time; per-shape XLA compiles inside them hit the caches
        # production traffic already warmed, table shapes being equal)
        self.new_program_keys = sorted(
            set(production._shared_jits) - jits_before
        )
        assert not self.new_program_keys, (
            "shadow construction registered new device programs: "
            f"{self.new_program_keys}"
        )

        self._lock = threading.Lock()
        self._wake = threading.Condition(self._lock)
        self._queue: list = []  # guarded-by: _lock
        self._busy = False  # a popped batch is mid-evaluation
        self._queue_max = int(queue_batches)
        self._samples: list = []  # guarded-by: _lock
        self._counts = {"evaluated": 0, "diffs": 0, "dropped": 0,
                        "errors": 0}
        self._by_transition: dict[str, int] = {}
        self._stop = False
        self._thread = threading.Thread(
            target=self._run, name="acs-shadow", daemon=True
        )
        self._thread.start()

    # ------------------------------------------------------ candidate tree

    def _load_candidate(self) -> None:
        self.engine.clear_policies()
        for path in self.candidate_paths:
            for policy_set in load_policy_sets_from_file(path):
                self.engine.update_policy_set(policy_set)

    def reload(self, candidate_paths: Optional[list] = None) -> None:
        """Swap in a new candidate tree (shadow epoch++; production
        untouched).  The refresh goes through the same version-pinned
        compile+swap as production, so in-flight shadow batches finish on
        the old candidate."""
        if candidate_paths is not None:
            self.candidate_paths = list(candidate_paths)
        self._load_candidate()
        self.evaluator.refresh(wait=True)
        self.epoch += 1

    def update_policy_set(self, policy_set) -> None:
        """Hot-update one candidate policy set (shadow epoch++)."""
        self.engine.update_policy_set(policy_set)
        self.evaluator.refresh(wait=True)
        self.epoch += 1

    # ------------------------------------------------------------ mirroring

    def submit(self, requests: list, responses: list) -> None:
        """Mirror one served batch; never blocks and never raises (the
        production response is already on its way out — nothing here may
        touch it).  Requests are read shared with production POST-serving
        and are never mutated by the shadow walk."""
        try:
            rows = [
                (req, resp.decision)
                for req, resp in zip(requests, responses)
                if resp.operation_status.code not in _SHED_CODES
                and (self.tenant is None
                     or getattr(req, "_tenant", None) == self.tenant)
            ]
            if not rows:
                return
            with self._lock:
                if self._stop:
                    return
                if len(self._queue) >= self._queue_max:
                    self._counts["dropped"] += len(rows)
                    if self.telemetry is not None:
                        self.telemetry.shadow.inc("dropped", len(rows))
                    return
                self._queue.append(rows)
                self._wake.notify()
        except Exception:  # noqa: BLE001 — mirroring must never fail serving
            if self.logger:
                self.logger.exception("shadow submit failed")

    def _run(self) -> None:
        while True:
            with self._lock:
                while not self._queue and not self._stop:
                    # acs-lint: ignore[blocking-under-lock] Condition.wait
                    # RELEASES the lock while parked — producers' submit()
                    # append-notify never blocks behind this wait
                    self._wake.wait()
                if self._stop and not self._queue:
                    return
                rows = self._queue.pop(0)
                self._busy = True
            try:
                self._evaluate(rows)
            except Exception:  # noqa: BLE001 — keep draining
                self._counts["errors"] += len(rows)
                if self.telemetry is not None:
                    self.telemetry.shadow.inc("errors", len(rows))
                if self.logger:
                    self.logger.exception("shadow evaluation failed")
            finally:
                with self._lock:
                    self._busy = False

    def _evaluate(self, rows: list) -> None:
        requests = []
        for req, _ in rows:
            if getattr(req, "_deadline", None) is not None:
                # admission-gated traffic rides with a ``_deadline`` stamp
                # that has usually PASSED by replay time — the evaluator
                # would shed the row as expired and every mirrored request
                # would read as a ``*->INDETERMINATE`` diff.  The caller
                # was already answered; the candidate replay has no
                # deadline.  Strip it on a shallow copy: the shared
                # request object (production may still hold it) is never
                # mutated by the shadow walk.
                req = copy.copy(req)
                req._deadline = None
            requests.append(req)
        candidate = self.evaluator.is_allowed_batch(requests)
        diffs = []
        for (request, prod_decision), cand_resp in zip(rows, candidate):
            if cand_resp.decision != prod_decision:
                diffs.append((request, prod_decision, cand_resp))
        with self._lock:
            self._counts["evaluated"] += len(rows)
            self._counts["diffs"] += len(diffs)
            for _, prod_decision, cand_resp in diffs:
                transition = f"{prod_decision}->{cand_resp.decision}"
                self._by_transition[transition] = (
                    self._by_transition.get(transition, 0) + 1
                )
            want = max(0, self.sample_diffs - len(self._samples))
        if self.telemetry is not None:
            self.telemetry.shadow.inc("evaluated", len(rows))
            for _, prod_decision, cand_resp in diffs:
                self.telemetry.shadow_diffs.inc(
                    f"{prod_decision}->{cand_resp.decision}"
                )
        if want and diffs:
            records = [
                self._diff_record(request, prod_decision, cand_resp)
                for request, prod_decision, cand_resp in diffs[:want]
            ]
            with self._lock:
                self._samples.extend(
                    records[: self.sample_diffs - len(self._samples)]
                )

    def _diff_record(self, request, prod_decision, cand_resp) -> dict:
        """One sampled diff with deciding-node provenance on both sides.

        Provenance comes from the HOST oracle walk over each tree
        (``EffectEvaluation.source``) — exact for the sampled rows,
        identical to the kernel's explain output by the differential
        suite, and free of any device-program dependency so sampling
        works with explain mode off too.  Masking rides the audit log's
        attribute scrubber: secret-valued target attributes never land in
        a sample."""
        from .tracing import DecisionAuditLog

        def provenance(engine):
            try:
                walked = engine.is_allowed(request)
                return getattr(walked, "_rule_id", None)
            except Exception:  # noqa: BLE001 — a sample is best-effort
                return None

        target = getattr(request, "target", None)
        return {
            "production": {
                "decision": prod_decision,
                "rule_id": provenance(self.production.engine),
            },
            "candidate": {
                "decision": cand_resp.decision,
                "rule_id": getattr(
                    cand_resp, "_rule_id", None
                ) or provenance(self.engine),
                "code": cand_resp.operation_status.code,
            },
            "subjects": DecisionAuditLog._attrs(
                getattr(target, "subjects", None)
            ),
            "resources": DecisionAuditLog._attrs(
                getattr(target, "resources", None)
            ),
            "actions": DecisionAuditLog._attrs(
                getattr(target, "actions", None)
            ),
        }

    # -------------------------------------------------------------- surface

    def status(self) -> dict:
        """The ``shadow_status`` command / health surface."""
        with self._lock:
            queue_depth = len(self._queue)
            counts = dict(self._counts)
            by_transition = dict(self._by_transition)
            samples = list(self._samples)
        return {
            "enabled": True,
            "epoch": self.epoch,
            "tenant": self.tenant,
            "candidate_paths": list(self.candidate_paths),
            "kernel_active": self.evaluator.kernel_active,
            "new_program_keys": list(self.new_program_keys),
            "queue_depth": queue_depth,
            **counts,
            "diffs_by_transition": by_transition,
            "samples": samples,
        }

    def drain(self, timeout_s: float = 5.0) -> bool:
        """Block until the queue is empty AND no popped batch is still
        mid-evaluation (tests/benches); True when drained within the
        timeout."""
        import time

        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            with self._lock:
                if not self._queue and not self._busy:
                    return True
            time.sleep(0.01)
        return False

    def stop(self, timeout_s: float = 5.0) -> None:
        with self._lock:
            self._stop = True
            self._wake.notify_all()
        self._thread.join(timeout_s)
        self.evaluator.shutdown()


def from_config(cfg, production, telemetry=None,
                logger=None) -> Optional[ShadowEvaluator]:
    """Build the shadow from the ``shadow`` config block; None unless
    enabled with candidate paths (the default — no object, no overhead,
    serving byte-identical)."""
    block = cfg.get("shadow") if hasattr(cfg, "get") else None
    block = block or {}
    if not block.get("enabled"):
        return None
    paths = block.get("candidate_paths") or []
    if not paths:
        if logger:
            logger.warning("shadow enabled without candidate_paths; off")
        return None
    return ShadowEvaluator(
        production, paths,
        combining_algorithms=(
            cfg.get("policies:options:combiningAlgorithms") or None
        ),
        telemetry=telemetry,
        logger=logger,
        tenant=block.get("tenant"),
        sample_diffs=int(block.get("sample_diffs", 32)),
        queue_batches=int(block.get("queue_batches", 64)),
    )
