# acs-lint: host-only — the sweep manager schedules, folds and streams;
# every device interaction goes through the batcher's bulk class or the
# evaluator's existing wia path.
"""Bulk permission-lattice audit sweeps (docs/AUDIT.md).

A sweep walks a subject x resource x action lattice (ops/lattice.py)
through the reverse/wia kernel in admission-governed BULK-class chunks:
production sweeps ride ``MicroBatcher.submit_reverse`` — never the
interactive queue, so PR 5's two-class fairness bounds interactive p99
while a full audit runs — and candidate sweeps call the PR 16
``ShadowEvaluator``'s disjoint evaluator directly, off the serving path
entirely.  Each chunk folds to per-cell verdicts naming the deciding
rule and streams into a masked JSONL + bitmap snapshot, so memory stays
bounded by one chunk regardless of lattice size.

The learned-policy twin loop (``sweep_twin``): load a mined/learned
candidate through the shadow evaluator, sweep production and candidate
over the same lattice, and report the lattice diff *and* the shadow's
live-traffic diff in one artifact — the full policy lifecycle the
mining papers (PAPERS.md: LLMAC, DLBAC) gesture at.

Jobs expose pause/resume/cancel/status through the ``audit_sweep``
command (srv/command.py).  Everything is off by default (config
``audit:enabled``); with it off the worker builds no manager and the
serving path is byte-identical.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from typing import Any, Optional

from ..ops.lattice import (
    CellVerdict,
    LatticeSpec,
    SnapshotWriter,
    diff_snapshots,
    fold_reverse_query,
)

_DONE_STATES = frozenset(("done", "cancelled", "failed"))


class SweepJob:
    """One lattice sweep: immutable plan + mutable progress, owned by a
    single worker thread in :class:`AuditSweepManager`."""

    def __init__(
        self,
        job_id: int,
        spec: LatticeSpec,
        target: str,
        snapshot_path: str,
        policy_epoch: Optional[int] = None,
    ):
        self.job_id = job_id
        self.spec = spec
        self.target = target
        self.snapshot_path = snapshot_path
        self.bitmap_path = snapshot_path + ".bits.npy"
        self.policy_epoch = policy_epoch
        self.state = "pending"        # guarded-by: _lock
        self.error: Optional[str] = None
        self.cells_done = 0           # guarded-by: _lock
        self.chunks_done = 0          # guarded-by: _lock
        self.sheds = 0                # guarded-by: _lock
        self.retries = 0              # guarded-by: _lock
        self.summary: Optional[dict] = None
        self.started_monotonic: Optional[float] = None
        self.wall_s: Optional[float] = None
        self._lock = threading.Lock()
        self._paused = threading.Event()
        # set by the sweep thread while it waits out a pause, with no
        # chunk in flight: from then on cells_done cannot move
        self._parked = threading.Event()
        self._cancel = threading.Event()
        self._finished = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def status(self) -> dict:
        with self._lock:
            out = {
                "job": self.job_id,
                "target": self.target,
                "state": self.state,
                "cells_total": self.spec.n_cells,
                "cells_done": self.cells_done,
                "chunks_done": self.chunks_done,
                "sheds": self.sheds,
                "retries": self.retries,
                "paused": self._paused.is_set(),
                "parked": self._parked.is_set(),
                "snapshot": self.snapshot_path,
                "bitmap": self.bitmap_path,
                "policy_epoch": self.policy_epoch,
            }
            if self.wall_s is not None:
                out["wall_s"] = round(self.wall_s, 3)
            if self.summary is not None:
                out["summary"] = self.summary
            if self.error is not None:
                out["error"] = self.error
        return out

    def wait(self, timeout: Optional[float] = None) -> bool:
        return self._finished.wait(timeout)


class AuditSweepManager:
    """Sweep-job lifecycle: start/pause/resume/cancel, chunked bulk
    dispatch, snapshot/diff plumbing and the candidate twin loop.

    ``batcher`` present: production sweeps submit through the BULK class
    (the admission-fairness path — the serving deployment shape).
    ``batcher`` absent: chunks call ``evaluator.what_is_allowed_batch``
    directly (the offline/bench shape).  Candidate sweeps always use the
    shadow's own evaluator and never touch the serving queues."""

    def __init__(
        self,
        evaluator,
        batcher=None,
        worker=None,
        telemetry=None,
        logger: Optional[logging.Logger] = None,
        out_dir: str = "/tmp/acs-audit",
        chunk_size: int = 256,
        cell_timeout_s: float = 60.0,
        max_retries: int = 3,
        chunk_pause_ms: float = 0.0,
        default_lattice: Optional[dict] = None,
    ):
        self.evaluator = evaluator
        self.batcher = batcher
        self.worker = worker
        self.telemetry = telemetry
        self.logger = logger or logging.getLogger("acs.audit")
        self.out_dir = str(out_dir)
        self.chunk_size = max(1, int(chunk_size))
        self.cell_timeout_s = float(cell_timeout_s)
        self.max_retries = max(0, int(max_retries))
        self.chunk_pause_s = max(0.0, float(chunk_pause_ms) / 1e3)
        self.default_lattice = dict(default_lattice or {})
        self._jobs: dict[int, SweepJob] = {}   # guarded-by: _lock
        self._next_id = 1                      # guarded-by: _lock
        self._lock = threading.Lock()
        self._stopping = False                 # guarded-by: _lock

    # ------------------------------------------------------------- metrics

    def _count(self, event: str, by: int = 1) -> None:
        if self.telemetry is not None:
            self.telemetry.audit.inc(event, by)

    # ------------------------------------------------------------ lifecycle

    def start_sweep(
        self,
        spec: Optional[LatticeSpec] = None,
        target: str = "production",
        lattice: Optional[dict] = None,
        wait: bool = False,
        wait_timeout: float = 600.0,
    ) -> SweepJob:
        """Launch a sweep job.  ``target`` is ``production`` (bulk class
        through the batcher) or ``shadow`` (the loaded candidate tree,
        off the serving path).  ``lattice`` overrides the configured
        default axes (ops/lattice.LatticeSpec.from_config grammar)."""
        if target not in ("production", "shadow"):
            raise ValueError(f"unknown sweep target: {target!r}")
        if target == "shadow" and self._shadow() is None:
            raise RuntimeError(
                "no shadow candidate loaded (config shadow:enabled + "
                "candidate_paths, or shadow_status reload)"
            )
        if spec is None:
            block = lattice if lattice is not None else self.default_lattice
            spec = LatticeSpec.from_config(block, urns=self._urns())
        with self._lock:
            if self._stopping:
                raise RuntimeError("audit manager stopping")
            job_id = self._next_id
            self._next_id += 1
            path = os.path.join(
                self.out_dir, f"sweep-{job_id:04d}-{target}.jsonl"
            )
            job = SweepJob(
                job_id, spec, target, path, policy_epoch=self._epoch()
            )
            self._jobs[job_id] = job
            job._thread = threading.Thread(
                target=self._run, args=(job,),
                name=f"acs-audit-sweep-{job_id}", daemon=True,
            )
            with job._lock:
                job.state = "running"
            job._thread.start()
        self._count("jobs_started")
        if wait:
            if not job.wait(wait_timeout):
                raise TimeoutError(f"sweep {job_id} still running")
        return job

    def pause(self, job_id: int) -> dict:
        job = self._job(job_id)
        job._paused.set()
        self._count("jobs_paused")
        return job.status()

    def resume(self, job_id: int) -> dict:
        job = self._job(job_id)
        job._paused.clear()
        self._count("jobs_resumed")
        return job.status()

    def cancel(self, job_id: int) -> dict:
        job = self._job(job_id)
        job._cancel.set()
        job._paused.clear()
        return job.status()

    def status(self, job_id: Optional[int] = None) -> dict:
        if job_id is not None:
            return self._job(job_id).status()
        with self._lock:
            jobs = list(self._jobs.values())
        statuses = [j.status() for j in jobs]
        running = sum(1 for s in statuses if s["state"] == "running")
        return {
            "enabled": True,
            "jobs": statuses[-16:],
            "running": running,
        }

    def diff(self, job_a: int, job_b: int, limit: int = 4096) -> dict:
        a, b = self._job(job_a), self._job(job_b)
        for job in (a, b):
            state = job.status()["state"]
            if state != "done":
                raise RuntimeError(
                    f"sweep {job.job_id} is {state}, not done"
                )
        out = diff_snapshots(a.snapshot_path, b.snapshot_path, limit=limit)
        self._count("diffs")
        self._count("diff_cells", out["cells_changed"])
        return out

    def sweep_twin(
        self,
        spec: Optional[LatticeSpec] = None,
        lattice: Optional[dict] = None,
        wait_timeout: float = 600.0,
        diff_limit: int = 4096,
    ) -> dict:
        """The learned-policy twin loop: sweep production AND the loaded
        shadow candidate over one lattice, diff the snapshots, and
        return the lattice diff beside the shadow's live-traffic diff —
        one report answering both 'what would change across the whole
        permission space' and 'what changes on real traffic'."""
        shadow = self._shadow()
        if shadow is None:
            raise RuntimeError("twin loop needs a loaded shadow candidate")
        if spec is None:
            block = lattice if lattice is not None else self.default_lattice
            spec = LatticeSpec.from_config(block, urns=self._urns())
        prod = self.start_sweep(
            spec=spec, target="production",
            wait=True, wait_timeout=wait_timeout,
        )
        cand = self.start_sweep(
            spec=spec, target="shadow",
            wait=True, wait_timeout=wait_timeout,
        )
        for job in (prod, cand):
            snap = job.status()
            if snap["state"] != "done":
                raise RuntimeError(
                    f"twin sweep {job.job_id} ({job.target}) "
                    f"{snap['state']}: {snap.get('error')}"
                )
        report = {
            "production": prod.status(),
            "candidate": cand.status(),
            "lattice_diff": self.diff(
                prod.job_id, cand.job_id, limit=diff_limit
            ),
            "live_traffic": shadow.status(),
        }
        self._count("twin_reports")
        return report

    def stop(self, timeout: float = 10.0) -> None:
        with self._lock:
            self._stopping = True
            jobs = list(self._jobs.values())
        for job in jobs:
            job._cancel.set()
            job._paused.clear()
        deadline = time.monotonic() + timeout
        for job in jobs:
            thread = job._thread
            if thread is not None and thread.is_alive():
                thread.join(max(0.0, deadline - time.monotonic()))

    # -------------------------------------------------------------- helpers

    def _job(self, job_id) -> SweepJob:
        key = int(job_id)
        with self._lock:
            job = self._jobs[key] if key in self._jobs else None
        if job is None:
            raise KeyError(f"unknown sweep job {job_id}")
        return job

    def _shadow(self):
        return getattr(self.worker, "shadow", None)

    def _urns(self):
        engine = getattr(self.evaluator, "engine", None)
        return getattr(engine, "urns", None)

    def _epoch(self) -> Optional[int]:
        worker = self.worker
        if worker is not None:
            try:
                return int(worker.policy_epoch())
            except Exception:
                return None
        return None

    # ------------------------------------------------------------ the sweep

    def _run(self, job: SweepJob) -> None:
        job.started_monotonic = time.monotonic()
        writer: Optional[SnapshotWriter] = None
        try:
            writer = SnapshotWriter(
                job.snapshot_path, job.spec, source=job.target,
                policy_epoch=job.policy_epoch,
                meta={"job": job.job_id, "chunk_size": self.chunk_size},
            )
            shadow_eval = None
            if job.target == "shadow":
                shadow = self._shadow()
                if shadow is None:
                    raise RuntimeError("shadow candidate unloaded mid-sweep")
                shadow_eval = shadow.evaluator
            for chunk in job.spec.chunks(self.chunk_size):
                while job._paused.is_set() and not job._cancel.is_set():
                    job._parked.set()
                    time.sleep(0.02)
                job._parked.clear()
                if job._cancel.is_set():
                    break
                if shadow_eval is not None:
                    verdicts = self._eval_direct(shadow_eval, chunk)
                elif self.batcher is not None:
                    verdicts = self._eval_bulk(job, chunk)
                else:
                    verdicts = self._eval_direct(self.evaluator, chunk)
                for (index, _), verdict in zip(chunk, verdicts):
                    writer.write(index, verdict)
                with job._lock:
                    job.cells_done += len(chunk)
                    job.chunks_done += 1
                    job.sheds += sum(
                        1 for v in verdicts if v.shed_code is not None
                    )
                self._count("cells", len(chunk))
                self._count("chunks")
                if self.chunk_pause_s:
                    time.sleep(self.chunk_pause_s)
            summary = writer.close()
            writer = None
            with job._lock:
                job.summary = summary
                job.wall_s = time.monotonic() - job.started_monotonic
                job.state = "cancelled" if job._cancel.is_set() else "done"
            self._count(
                "jobs_cancelled" if job._cancel.is_set()
                else "jobs_completed"
            )
        except Exception as exc:  # a failed audit must never take the
            # worker down with it — the job records the error honestly
            with job._lock:
                job.state = "failed"
                job.error = f"{type(exc).__name__}: {exc}"
            self._count("jobs_failed")
            self.logger.warning(
                "audit sweep %d failed", job.job_id,
                extra={"error": job.error},
            )
        finally:
            if writer is not None:
                try:
                    writer.close()
                except Exception:
                    pass
            job._finished.set()

    def _eval_direct(self, evaluator, chunk: list) -> list:
        trees = evaluator.what_is_allowed_batch([r for _, r in chunk])
        return [fold_reverse_query(rq) for rq in trees]

    def _eval_bulk(self, job: SweepJob, chunk: list) -> list:
        """BULK-class dispatch: every cell goes through admission as the
        bulk class and waits out ``bulk_interval`` pacing under load —
        the interactive queue never sees audit traffic.  Shed cells
        (429/503/504) retry up to ``max_retries`` with a short backoff,
        then land in the snapshot as honest INDETERMINATE + shed code
        rather than a fabricated verdict."""
        futures = [
            self.batcher.submit_reverse(request) for _, request in chunk
        ]
        verdicts: list = []
        for slot, future in enumerate(futures):
            rq = future.result(timeout=self.cell_timeout_s)
            verdict = fold_reverse_query(rq)
            attempt = 0
            while (
                verdict.shed_code is not None
                and attempt < self.max_retries
                and not job._cancel.is_set()
            ):
                attempt += 1
                with job._lock:
                    job.retries += 1
                self._count("retries")
                time.sleep(0.005 * attempt)
                retry = self.batcher.submit_reverse(chunk[slot][1])
                verdict = fold_reverse_query(
                    retry.result(timeout=self.cell_timeout_s)
                )
            if verdict.shed_code is not None:
                self._count("sheds")
            verdicts.append(verdict)
        return verdicts


def from_config(
    cfg,
    worker=None,
    evaluator=None,
    batcher=None,
    telemetry=None,
    logger=None,
) -> Optional[AuditSweepManager]:
    """Build the manager from the ``audit`` config block; None unless
    ``audit:enabled`` — the serving path stays byte-identical with the
    subsystem off (no manager object, no command surface, no threads)."""
    if not cfg.get("audit:enabled", False):
        return None
    evaluator = evaluator or getattr(worker, "evaluator", None)
    if evaluator is None:
        return None
    return AuditSweepManager(
        evaluator,
        batcher=batcher if batcher is not None
        else getattr(worker, "batcher", None),
        worker=worker,
        telemetry=telemetry,
        logger=logger,
        out_dir=cfg.get("audit:out_dir", "/tmp/acs-audit"),
        chunk_size=cfg.get("audit:chunk_size", 256),
        cell_timeout_s=cfg.get("audit:cell_timeout_s", 60.0),
        max_retries=cfg.get("audit:max_retries", 3),
        chunk_pause_ms=cfg.get("audit:chunk_pause_ms", 0.0),
        default_lattice=cfg.get("audit:lattice", {}) or {},
    )
