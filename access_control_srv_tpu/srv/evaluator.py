"""Hybrid evaluator: the TPU kernel fast path fused with the scalar oracle.

Batched ``isAllowed`` requests flow through the compiled kernel; requests
outside the kernel's representable subset (or whole trees the compiler
rejects) fall back to the oracle — decisions are bit-identical either way
(enforced by the differential suite).  Kernel rows that abort with an error
status are re-run on the oracle to recover the exact error message the
reference would produce (the kernel computes codes, not message strings).

Hot policy mutation triggers a recompile; serving is version-pinned: the
old kernel keeps answering until the new compile (optionally off-thread)
is swapped in atomically (the reference just mutates Maps in place,
reference: src/core/accessController.ts:897-937 — we must not stall
serving on an XLA compile).

With the incremental-update subsystem active (ops/delta.py, the default
off the rule-sharded mesh path), compiled tables are capacity-bucketed
and CRUD mutations arrive as captured events (srv/store.py): in-capacity
deltas PATCH the host tables and swap a new kernel object that reuses the
existing jitted executables (zero new XLA compilations, sub-ms
time-to-visibility), scoped decision-cache bumps keep disjoint entries
warm, and certified-empty diffs skip the flush and the compile entirely.
Everything the delta prover cannot certify falls back to the full
recompile below, whose async variant is debounced: at most one compile
runs and at most one is pending regardless of the CRUD arrival rate.
"""

from __future__ import annotations

import copy
import threading
import time
from typing import Optional

import numpy as np

from ..core.engine import AccessController
from ..models.model import Decision, OperationStatus, Response
from ..ops import delta as delta_mod
from ..ops.compile import DECISION_NAMES, compile_policies
from ..ops.encode import encode_requests
from ..ops.kernel import DecisionKernel, batch_has_acl, dispatch_shape_key
from .decision_cache import request_features
from .tracing import (
    STAGE_CACHE,
    STAGE_DECODE,
    STAGE_DEVICE,
    STAGE_DEVICE_DISPATCH,
    STAGE_DEVICE_WAIT,
    STAGE_ENCODE,
    STAGE_ORACLE,
    STAGE_PREPARE,
    STAGE_WIRE_ENCODE,
    STAGE_WIRE_ENCODE_CEIL,
    STAGE_WIRE_OWNER_BITS,
    untimed,
)
from .watchdog import DeviceTimeoutError


def _acl_rows(batch) -> np.ndarray:
    """[B] rows that carry ACL pairs to the kernel's verifyACL pass: no
    early all-clear or malformed ACL (``r_acl_short`` 0) and at least one
    ACL entity."""
    arrays = batch.arrays
    return ((np.asarray(arrays["r_acl_short"]) == 0)
            & (np.asarray(arrays["r_acl_ent"]) >= 0).any(axis=1))


class HybridEvaluator:
    def __init__(
        self,
        engine: AccessController,
        backend: str = "hybrid",  # oracle | kernel | hybrid
        logger=None,
        async_compile: bool = False,
        telemetry=None,
        mesh=None,
        mesh_axis: str = "data",
        model_axis: str | None = None,
        pod_shards: int | None = None,
        decision_cache=None,
        observability=None,
        shared_jits: Optional[dict] = None,
        fixed_caps=None,
        tenant: Optional[str] = None,
        explain: bool = False,
    ):
        self.engine = engine
        self.backend = backend
        self.logger = logger
        self.telemetry = telemetry
        self.async_compile = async_compile
        # observability hub (srv/tracing.Observability): stage-span
        # tracing + audit attribution.  None (the default) keeps every
        # instrumentation site on the exact pre-observability path.
        self.obs = observability
        # rate-limited hot-path logging: the per-row warning sites
        # (token-unresolved, oracle fallback) must not turn the masking
        # logger into the bottleneck when an upstream is down under load
        from .telemetry import SampledLogger

        self._slog = SampledLogger(logger)
        # server-side decision cache (srv/decision_cache.py): consulted
        # batch-wide BEFORE encode so hit rows skip both the device
        # round-trip and the oracle walk; written through from every miss
        # row the engine marks evaluation_cacheable.  Policy mutations
        # invalidate via refresh() -> bump_epoch below.
        self.decision_cache = decision_cache
        # optional jax.sharding.Mesh: requests shard data-parallel over
        # ``mesh_axis`` while policy tensors replicate — the serving-path
        # multi-chip layout (the reference scales by running N stateless
        # replicas behind a load balancer, src/worker.ts:161-198; here one
        # process drives N chips).  With ``model_axis`` set (a 2-axis
        # mesh from parallel.make_mesh2), the RULE axis of the compiled
        # tensors shards over it too — trees too large to replicate per
        # chip serve through parallel/rule_shard.RuleShardedKernel.
        self.mesh = mesh
        self.mesh_axis = mesh_axis
        self.model_axis = model_axis
        # pod-sharded tier (parallel/pod_shard.py, config
        # parallel:pod_shards): the SET axis of one pod-level bucketed
        # compile shards over ``model_axis`` instead of the rule axis —
        # unlike the rule-sharded path this one IS delta-patchable
        # (shard-local relower, see PodShardedKernel.patched)
        self.pod_shards = pod_shards
        self._version = 0
        self._compiled = None
        self._kernel: Optional[DecisionKernel] = None
        self._rq_kernel = None
        self._tree_snapshot = None
        self._native_encoder = None
        # candidate index over the LIVE engine tree: oracle-fallback rows
        # skip rules that provably cannot target-match (bit-identical —
        # core/candidate_index.py).  Published as ONE (tree, index) tuple
        # so readers see a consistent pair (no TOCTOU between index and
        # identity guard); a hot replace_policy_sets swap fails the
        # identity check instantly and the refresh that follows rebuilds.
        self._cand: Optional[tuple] = None  # (tree ref, CandidateIndex)
        self._lock = threading.Lock()
        self._compile_thread: Optional[threading.Thread] = None
        # incremental-update subsystem (ops/delta.py): capacity-bucketed
        # tables + CRUD-event patching.  Disabled on the rule-sharded mesh
        # path (RuleShardedKernel repartitions per compile) and for the
        # oracle backend (nothing compiled to patch).  The pod-sharded
        # path keeps it ON: PodShardedKernel.patched re-slices only the
        # shards owning the patched set slots.
        self.delta_enabled = bool(
            (model_axis is None or pod_shards is not None)
            and backend != "oracle"
        )
        self._caps = None                   # delta_mod.Capacities
        self._delta_state = None            # delta_mod.DeltaState
        # jitted executables, swap-stable.  An INJECTED dict (multi-tenant
        # packing, srv/tenancy.py) is shared by every evaluator in one
        # size class: identical table shapes -> the per-shape cache inside
        # each jitted callable hits, so N same-class tenants cost the
        # class's compile count, not N compiles.
        self._shared_jits: dict = (
            shared_jits if shared_jits is not None else {}
        )
        # pinned capacity class (delta_mod.Capacities): full compiles go
        # through fixed_caps_compile so the published shapes never drift
        # from the class.  On class overflow the compile falls back to
        # per-tenant buckets (serving never breaks) and the tenancy
        # registry detects the caps drift and promotes the tenant.
        self.fixed_caps = fixed_caps
        # tenant id this evaluator serves (None = the default domain):
        # scopes decision-cache keys/bumps so one tenant's mutations never
        # flush another's entries
        self.tenant = tenant
        # explain mode (srv/explain.py): kernels emit one extra int32 per
        # row naming the deciding node; decoded host-side onto the
        # response (``_rule_id`` / ``_explain``).  OFF by default — the
        # False path traces the exact pre-explain computation, so the
        # lowered device program is byte-identical to explain-less builds.
        self.explain = bool(explain) and backend != "oracle"
        self._explain_decoder = None
        self._delta_counts = {
            "patches": 0, "full_compiles": 0, "noops": 0,
            "recompiles_avoided": 0, "fallbacks": 0,
        }
        self._delta_fallback_reasons: dict[str, int] = {}
        self._last_visibility_ms: Optional[float] = None
        # async full-compile debounce: at most one compile running and at
        # most one pending, however fast CRUD events arrive
        self._compile_state_lock = threading.Lock()
        self._compile_pending = False
        self._shutdown = False
        # device-health state (srv/watchdog.py): a quarantined evaluator
        # routes every decision path to the oracle until the watchdog's
        # probe restores the kernel.  Plain bool store/load — readers see
        # a flip at the next batch boundary, which is the granularity the
        # quarantine needs.
        self._watchdog = None
        self._quarantined = False
        self.refresh(wait=True)  # oracle backend builds only the index

    # ------------------------------------------------------------- lifecycle

    def refresh(self, wait: bool = False, events=None,
                footprint=None) -> None:
        """Recompile the policy tensors after a tree mutation; the previous
        kernel serves until the swap.

        ``events`` (list of ops/delta.CrudEvent, captured by the store at
        mutation time) enables the incremental path: certified-empty diffs
        skip the cache flush and the compile; in-capacity deltas patch the
        bucketed tables in place and reuse every jitted executable;
        anything else falls back to the full recompile.  ``footprint``
        (ops/delta.Footprint) scopes the post-swap decision-cache bump on
        the patch path — the pre-swap bump is the store's (the paired
        invariant of PR 1, preserved verbatim on both paths)."""
        t0 = time.perf_counter()
        if self.backend == "oracle":
            if self.decision_cache is not None:
                if footprint is not None and footprint.empty:
                    pass  # certified no-op: nothing to flush
                elif footprint is not None:
                    self.decision_cache.bump_scoped(footprint)
                else:
                    self.decision_cache.bump_epoch()
            # no compile, but the oracle walk still benefits from the
            # candidate index — in fact it is the mode where EVERY
            # request takes that walk
            self._cand = self._build_candidate_index()
            return

        if events is not None and self._delta_ready():
            if self._try_patch(events, footprint, t0):
                return

        # ------------------------------------------------ full recompile
        if self.decision_cache is not None:
            # the tree changed (CRUD hot-sync / restore / reset / policy
            # load) and no delta certificate exists: every cached decision
            # is logically flushed BEFORE the new tree serves — a stale
            # hit must never outlive the swap
            self.decision_cache.bump_epoch()
        with self._lock:
            self._version += 1

        if self.async_compile and not wait:
            # debounce: one running compile + at most one pending.  The
            # worker loop recompiles from the LATEST version at each
            # round, so a burst of N CRUD events costs at most two
            # compiles (the in-flight one and one covering the rest).
            with self._compile_state_lock:
                if self._shutdown:
                    return
                self._compile_pending = True
                thread = self._compile_thread
                if thread is None or not thread.is_alive():
                    thread = threading.Thread(
                        target=self._compile_worker, daemon=True
                    )
                    self._compile_thread = thread
                    thread.start()
        else:
            with self._lock:
                version = self._version
            self._compile_and_swap(version, t0)

    # --------------------------------------------------- incremental path

    def _delta_ready(self) -> bool:
        """The patch path engages only when the PUBLISHED compile is the
        latest version (no async full compile in flight — patching stale
        tables would silently drop the in-flight mutation) and a supported
        kernel + ownership state exist."""
        if not self.delta_enabled:
            return False
        with self._lock:
            return (
                self._compiled is not None
                and self._compiled.supported
                and self._kernel is not None
                and self._delta_state is not None
                and self._compiled.version == self._version
            )

    def _try_patch(self, events, footprint, t0) -> bool:
        """Apply a CRUD delta in place; True when the refresh is fully
        handled (patch published or certified no-op), False to fall back
        to the full recompile."""
        with self._lock:
            compiled = self._compiled
            state = self._delta_state
            claimed = self._version
            kernel_prev = self._kernel
        tree = self.engine.policy_sets
        try:
            result, patched, new_state, stats = delta_mod.apply_events(
                state, compiled, tree, events, self.engine.urns
            )
        except delta_mod.DeltaIneligible as err:
            self._delta_counts["fallbacks"] += 1
            self._delta_fallback_reasons[err.reason] = (
                self._delta_fallback_reasons.get(err.reason, 0) + 1
            )
            self._count_delta("delta-fallback")
            if self.logger:
                self.logger.info(
                    "delta ineligible; full recompile",
                    extra={"reason": err.reason},
                )
            return False
        except Exception:  # noqa: BLE001 — patching must never kill CRUD
            if self.logger:
                self.logger.exception("delta patch failed; full recompile")
            return False

        if result == "noop":
            # nothing evaluation-relevant changed: keep the compiled
            # tables, the kernel AND the decision cache; only the
            # candidate index must track the new tree identity
            cand = self._build_candidate_index()
            with self._lock:
                if self._version == claimed:
                    self._cand = cand
                    self._tree_snapshot = tree
                    self._explain_decoder = self._make_explain_decoder(
                        self._kernel, tree
                    )
                    if new_state is not None:
                        self._delta_state = new_state
            self._delta_counts["noops"] += 1
            self._count_delta("delta-noop")
            return True

        shards_patched = 0
        if getattr(kernel_prev, "supports_shard_patch", False):
            # pod-sharded path: re-slice ONLY the shards owning the
            # patched set slots; every other shard's host tables are
            # reused by reference and the jitted shard_map program comes
            # from the shared registry — zero new XLA compiles
            patched_slots = stats.get("patched_slots", [])
            kernel = kernel_prev.patched(patched, patched_slots)
            shards_patched = len({
                min(int(s) // kernel_prev.s_local,
                    kernel_prev.n_shards - 1)
                for s in patched_slots
            })
        else:
            from ..ops.prefilter import PrefilteredKernel

            kernel = PrefilteredKernel(
                patched, mesh=self.mesh, axis=self.mesh_axis,
                telemetry=self.telemetry, dynamic_policies=True,
                shared_jits=self._shared_jits, explain=self.explain,
            )
        native_encoder = self._make_native_encoder(patched, kernel)
        cand = self._build_candidate_index()
        explain_decoder = self._make_explain_decoder(kernel, tree)
        with self._lock:
            if self._version != claimed:
                return False  # a newer refresh superseded this patch
            self._version += 1
            patched.version = self._version
            self._compiled = patched
            self._kernel = kernel
            self._rq_kernel = None
            self._tree_snapshot = tree
            self._native_encoder = native_encoder
            self._cand = cand
            self._explain_decoder = explain_decoder
            self._delta_state = new_state
        if self.decision_cache is not None:
            # post-swap bump, scoped to the delta's footprint: entries
            # whose signatures are disjoint survive the mutation (the
            # pre-swap bump in store._load_locked used the same footprint)
            if footprint is not None:
                self.decision_cache.bump_scoped(footprint)
            else:
                self.decision_cache.bump_epoch()
        visibility_ms = (time.perf_counter() - t0) * 1e3
        self._last_visibility_ms = visibility_ms
        self._delta_counts["patches"] += 1
        self._delta_counts["recompiles_avoided"] += 1
        self._count_delta("delta-patch")
        if self.telemetry is not None:
            self.telemetry.policy_update_latency.observe(
                visibility_ms / 1e3
            )
            self.telemetry.delta.inc(
                "sets_patched", int(stats.get("sets_patched", 0))
            )
            if shards_patched:
                self.telemetry.delta.inc("shards_patched", shards_patched)
        return True

    def _count_delta(self, key: str) -> None:
        if self.telemetry is not None:
            self.telemetry.delta.inc(key)

    def delta_stats(self) -> dict:
        """health_check surface: patch vs full-compile counts, fallback
        catalog, last time-to-visibility and the active capacities."""
        out = {
            "enabled": self.delta_enabled,
            **self._delta_counts,
            "fallback_reasons": dict(self._delta_fallback_reasons),
            "last_visibility_ms": (
                round(self._last_visibility_ms, 3)
                if self._last_visibility_ms is not None else None
            ),
        }
        caps = self._caps
        if caps is not None:
            out["capacities"] = caps.as_dict()
        sharding = self.shard_identity()
        if sharding is not None:
            out["sharding"] = {
                "n_shards": sharding["n_shards"],
                "applied_patches": [
                    sh["applied_patches"] for sh in sharding["shards"]
                ],
            }
        return out

    def shard_identity(self) -> Optional[dict]:
        """Pod-sharding surface for health_check/program_identity: shard
        count, per-shard fingerprints/capacities and the applied-patch
        watermarks; None when the active kernel is not pod-sharded."""
        kernel = self._kernel
        if kernel is None or not getattr(
            kernel, "supports_shard_patch", False
        ):
            return None
        return kernel.shard_identity()

    def table_fingerprint(self) -> Optional[str]:
        """Digest of the compiled policy tables: every device array's
        bytes + shape + dtype, the condition sources, the entity vocab and
        the active capacities.  Two replicas that applied the same CRUD
        sequence through the delta path hold byte-identical tables, so
        their fingerprints match — the cluster tier's convergence check
        (srv/router.py health, tests/test_cluster_chaos.py,
        tests/test_delta.py::TestProgramReuse::
        test_replicas_replaying_one_journal_hold_identical_tables)."""
        from hashlib import blake2b

        compiled = self._compiled
        if compiled is None:
            return None
        h = blake2b(digest_size=16)
        for name in sorted(compiled.arrays):
            arr = np.ascontiguousarray(compiled.arrays[name])
            h.update(name.encode())
            h.update(str(arr.dtype).encode())
            h.update(repr(arr.shape).encode())
            h.update(arr.tobytes())
        h.update(repr(compiled.entity_vocab).encode())
        h.update(repr([
            (c.rule_flat_index, c.condition, repr(c.context_query), c.owner)
            for c in compiled.conditions
        ]).encode())
        caps = self._caps
        if caps is not None:
            h.update(repr(sorted(caps.as_dict().items())).encode())
        sharding = self.shard_identity()
        if sharding is not None:
            # fold the per-shard table digests in, so replicas must agree
            # on the SLICED tables too (shard boundaries, compacted
            # per-shard target subtables), not just the pod-level arrays
            h.update(sharding["pod_fingerprint"].encode())
        store = getattr(self.engine, "relation_store", None)
        if store is not None:
            # the relation-tuple state decides relation-bearing rows, so
            # replica convergence must cover it too (two replicas with
            # equal policy tables but divergent tuple logs must differ)
            h.update(store.fingerprint().encode())
        return h.hexdigest()

    # ------------------------------------------------------ full compile

    def _compile_worker(self) -> None:
        """Debounced async compile loop: drains pending requests one
        compile at a time, always from the latest version."""
        while True:
            with self._compile_state_lock:
                if not self._compile_pending or self._shutdown:
                    self._compile_thread = None
                    return
                self._compile_pending = False
            with self._lock:
                version = self._version
            try:
                self._compile_and_swap(version, time.perf_counter())
            except Exception:  # noqa: BLE001 — keep draining
                if self.logger:
                    self.logger.exception("async policy compile failed")

    def _compile_and_swap(self, version: int, t0: float) -> None:
        # snapshot FIRST, compile FROM the snapshot: the published
        # (tree, arrays) pair is then consistent by construction — a
        # hot mutation landing mid-compile bumps _version and this
        # compile is dropped below, never pairing a mutated tree with
        # stale index arrays (the reverse-query kernel assembles its
        # trees from this snapshot)
        tree_snapshot = copy.deepcopy(self.engine.policy_sets)
        caps = state = None
        if self.delta_enabled and self.fixed_caps is not None:
            try:
                compiled, caps, state = delta_mod.fixed_caps_compile(
                    tree_snapshot, self.engine.urns, self.fixed_caps,
                    version=version,
                )
            except delta_mod.DeltaIneligible as err:
                # class overflow: serve from per-tenant buckets rather
                # than fail — the tenancy registry compares the published
                # caps against the class and promotes the tenant
                if self.logger:
                    self.logger.info(
                        "tenant tree overflows pinned size class; "
                        "falling back to per-tenant capacity buckets",
                        extra={"reason": err.reason,
                               "tenant": self.tenant},
                    )
                compiled, caps, state = delta_mod.full_bucketed_compile(
                    tree_snapshot, self.engine.urns, version=version,
                    prev_caps=None,
                )
        elif self.delta_enabled:
            compiled, caps, state = delta_mod.full_bucketed_compile(
                tree_snapshot, self.engine.urns, version=version,
                prev_caps=self._caps,
            )
        else:
            compiled = compile_policies(
                tree_snapshot, self.engine.urns, version=version
            )
        kernel = None
        if compiled.supported and compiled.n_rules > 0:
            if self.pod_shards is not None and self.mesh is not None:
                # pod-sharded tier (config: parallel:pod_shards): the SET
                # axis of the bucketed compile partitions over the model
                # axis with per-shard compacted target subtables; the
                # shard_map program registers in _shared_jits, so a
                # recompile with unchanged capacities reuses it
                from ..parallel.pod_shard import PodShardedKernel

                prev = self._kernel
                kernel = PodShardedKernel(
                    compiled, self.mesh,
                    data_axis=self.mesh_axis,
                    model_axis=self.model_axis or "model",
                    explain=self.explain,
                    shared_jits=self._shared_jits,
                    prev_t_cap=getattr(prev, "t_cap", 0),
                )
            elif self.model_axis is not None and self.mesh is not None:
                # rule-axis sharding (config: parallel:model_devices):
                # the compiled tensors partition over the model axis,
                # requests over the data axis.  Evaluator-level path
                # counters (kernel/oracle rows) still record via
                # _count_path; only PrefilteredKernel's internal
                # cache counters have no sharded equivalent.
                from ..parallel.rule_shard import RuleShardedKernel

                kernel = RuleShardedKernel(
                    compiled, self.mesh,
                    data_axis=self.mesh_axis,
                    model_axis=self.model_axis,
                    explain=self.explain,
                )
            else:
                # PrefilteredKernel is a drop-in DecisionKernel that
                # keeps per-request work O(matching rules) on large
                # trees and delegates to the dense kernel below
                # MIN_RULES
                from ..ops.prefilter import PrefilteredKernel

                kernel = PrefilteredKernel(
                    compiled, mesh=self.mesh, axis=self.mesh_axis,
                    telemetry=self.telemetry,
                    dynamic_policies=self.delta_enabled,
                    shared_jits=self._shared_jits,
                    explain=self.explain,
                )
        native_encoder = self._make_native_encoder(compiled, kernel)
        cand = self._build_candidate_index()
        explain_decoder = self._make_explain_decoder(kernel, tree_snapshot)
        with self._lock:
            if version >= self._version:  # drop stale compiles
                self._compiled = compiled
                self._kernel = kernel
                self._rq_kernel = None  # lazy: built on first wia batch
                self._tree_snapshot = tree_snapshot
                self._native_encoder = native_encoder
                self._cand = cand
                self._caps = caps
                self._explain_decoder = explain_decoder
                self._delta_state = state
        self._delta_counts["full_compiles"] += 1
        self._count_delta("full-compile")
        self._last_visibility_ms = (time.perf_counter() - t0) * 1e3
        if self.telemetry is not None:
            self.telemetry.policy_update_latency.observe(
                time.perf_counter() - t0
            )
        if self.logger and not compiled.supported:
            self.logger.warning(
                "policy tree not kernel-supported; serving from oracle",
                extra={"reason": compiled.unsupported_reason},
            )

    def shutdown(self, timeout: float = 10.0) -> None:
        """Stop the async compile loop and join its thread (worker
        shutdown must not leak daemon compile threads mid-XLA-compile)."""
        with self._compile_state_lock:
            self._shutdown = True
            self._compile_pending = False
            thread = self._compile_thread
        if thread is not None and thread.is_alive():
            thread.join(timeout)

    def _build_candidate_index(self):
        """(live tree, CandidateIndex) for trees worth indexing, else
        None; the pair is published atomically (see __init__)."""
        live_tree = self.engine.policy_sets
        n_rules = sum(
            len(p.combinables)
            for ps in live_tree.values() if ps is not None
            for p in ps.combinables.values() if p is not None
        )
        if n_rules < 256:
            return None
        from ..core.candidate_index import CandidateIndex

        return (live_tree, CandidateIndex(live_tree, self.engine.urns))

    def _make_explain_decoder(self, kernel, tree):
        """ExplainDecoder paired with one published kernel, built from the
        same version-pinned tree snapshot as the compiled arrays; None
        when explain is off or the kernel cannot emit provenance."""
        if (not self.explain or kernel is None
                or not getattr(kernel, "explain", False)):
            return None
        from .explain import ExplainDecoder, explain_capacity_ok

        compiled = getattr(kernel, "compiled", None)
        if compiled is not None:
            assert explain_capacity_ok(
                compiled.S, compiled.KP, compiled.KR
            ), "policy tree exceeds the explain code's 30-bit position bound"
        return ExplainDecoder(tree, kernel.explain_strides)

    def _make_native_encoder(self, compiled, kernel):
        """C++ wire-batch encoder for the gRPC fast path; None when the
        native library or the tree shape does not support it.  Explain
        mode also disables it: wire batches carry no Response objects to
        stamp provenance on, so explain-enabled serving routes gRPC
        through the pb decode path instead of silently dropping the
        deciding-rule attribution."""
        if kernel is None or compiled.conditions or self.explain:
            return None
        try:
            from .. import native

            if not native.available():
                return None
            return native.NativeBatchEncoder(compiled)
        except Exception as err:  # toolchain-less environments
            if self.logger:
                self.logger.info("native encoder disabled: %s", err)
            return None

    # --------------------------------------------------- relation plumbing

    def attach_relation_store(self, store) -> None:
        """Wire a RelationTupleStore (srv/relations.py): the oracle gate
        reads it through ``engine.relation_store``, encode pulls the flat
        verdict tables per batch, and every tuple write bumps the
        decision cache — tuple churn changes decisions without any policy
        CRUD, but swaps NO program: the compiled tables, the kernel and
        every jitted executable stay byte-identical (the ReBAC serving
        invariant, tests/test_relations.py::
        test_tuple_churn_swaps_no_program)."""
        self.engine.relation_store = store

        def _on_change(_gen: int) -> None:
            if self.decision_cache is not None:
                self.decision_cache.bump_epoch()
            self._count_path("relation-churn", 1)

        store.on_change(_on_change)

    def _relation_tables(self, compiled):
        """The store's flat verdict tables for this compile, or None
        (encode then packs fail-closed planes / dummies)."""
        store = getattr(self.engine, "relation_store", None)
        if store is None or compiled is None:
            return None
        from ..ops.relation import relation_bits_needed

        if not relation_bits_needed(compiled):
            return None
        return store.tables_for(compiled)

    def _relation_tables_native(self, encoder):
        """Verdict tables in the NATIVE encoder's id space for the wire
        path — the C++ interner diverges from the Python one after the
        preload snapshot, so the host-space tables cannot be reused."""
        store = getattr(self.engine, "relation_store", None)
        if store is None or not encoder.needs_relation_bits:
            return None
        return encoder.native_relation_tables(store)

    def _relation_provenance(self, request, source_id):
        """Tuple-path witnesses for a relation-decided explain row: when
        the deciding node's target carries relation-path attributes, walk
        the live tuple graph for the hop list that satisfied each (path,
        instance) pair — the ReBAC analog of the rule-id stamp.  None
        whenever the row wasn't relation-gated (no store, no relation
        attrs, nothing collected), so non-ReBAC explain output is
        byte-identical."""
        store = getattr(self.engine, "relation_store", None)
        if store is None or source_id is None:
            return None
        target = self._node_target(source_id)
        if target is None:
            return None
        from ..core.relation_path import (
            collect_target_instances,
            relation_paths,
            request_subject_id,
        )

        urns = self.engine.urns
        paths = relation_paths(
            target.subjects if target is not None else None, urns
        )
        if not paths:
            return None
        instances = collect_target_instances(target, request, urns)
        subject_id = request_subject_id(request)
        if not instances or subject_id is None:
            return None
        witnesses = []
        for expr in paths:
            for ns, oid in instances:
                hops = store.witness(expr, ns, oid, subject_id)
                if hops is not None:
                    witnesses.append({
                        "path": expr,
                        "object": f"{ns}:{oid}",
                        "tuples": hops,
                    })
        return witnesses or None

    def _node_target(self, source_id):
        """The target of the tree node ``source_id`` names — deciding
        rule first, then no-rules policy (same precedence as
        ExplainDecoder.describe_source); None when the id left the tree
        under a hot mutation (provenance then degrades, never raises)."""
        for ps in self.engine.policy_sets.values():
            if ps is None:
                continue
            for pol in ps.combinables.values():
                if pol is None:
                    continue
                for rule in pol.combinables.values():
                    if rule is not None and rule.id == source_id:
                        return rule.target
        for ps in self.engine.policy_sets.values():
            if ps is None:
                continue
            for pol in ps.combinables.values():
                if pol is not None and pol.id == source_id:
                    return pol.target
        return None

    @property
    def kernel_active(self) -> bool:
        return self._kernel is not None

    def compile_state(self) -> dict:
        """What serves now: ``current`` once the published compile covers
        the latest tree version (False while an async full compile is in
        flight, and for good after one that failed), whether a kernel
        serves it, and its rule count."""
        with self._lock:
            compiled, kernel, version = (
                self._compiled, self._kernel, self._version
            )
        return {
            "current": compiled is not None and compiled.version == version,
            "kernel": kernel is not None,
            "n_rules": compiled.n_rules if compiled is not None else 0,
        }

    # --------------------------------------------- device-health plumbing

    @property
    def quarantined(self) -> bool:
        return self._quarantined

    def set_quarantined(self, flag: bool) -> None:
        """Flipped by the device watchdog (srv/watchdog.py): True routes
        every decision path to the oracle — degraded-but-correct serving
        while the kernel path heals; False restores kernel routing."""
        self._quarantined = bool(flag)

    def attach_watchdog(self, watchdog) -> None:
        self._watchdog = watchdog

    @property
    def watchdog(self):
        return self._watchdog

    def _guard_materialize(self, materialize):
        """Bound a kernel materialize under the watchdog deadline when one
        is attached; identity otherwise (the default path adds zero
        indirection beyond this None check)."""
        watchdog = self._watchdog
        if watchdog is None:
            return materialize
        return lambda: watchdog.run(materialize)

    def kernel_probe(self) -> bool:
        """One canary batch through the live kernel's dispatch+materialize
        — proves the device path answers end-to-end.  Used by the
        watchdog's restore probe (bounded there); False when no kernel is
        active.  Bypasses the watchdog wrap on purpose: the probe applies
        its own deadline."""
        with self._lock:
            kernel = self._kernel
            compiled = self._compiled
        if kernel is None or compiled is None:
            return False
        from ..models.model import Request, Target

        canary = Request(target=Target(), context={})
        batch = encode_requests(
            [canary], compiled, self.engine.resource_adapter
        )
        outputs = kernel.evaluate_async(batch)()
        return len(outputs) >= 3  # explain-enabled kernels append a 4th

    def _hang_fallback(self, requests: list) -> list:
        """Honest per-row resolution for a batch whose device materialize
        timed out: rows with an already-expired deadline shed with the
        deadline status, everything else takes the oracle walk (a real
        evaluation — its cacheability stands), and a row the oracle
        cannot answer gets the never-cacheable ``degraded`` envelope.
        Never a fabricated PERMIT/DENY."""
        from .admission import (
            DEADLINE_CODE,
            degraded_response,
            overload_response,
        )

        expired = self._expired_rows(requests)
        shed = overload_response(
            DEADLINE_CODE, "deadline expired before evaluation"
        )
        out = []
        n_oracle = 0
        n_degraded = 0
        for b, request in enumerate(requests):
            if b in expired:
                out.append(shed)
                continue
            try:
                out.append(self._oracle_is_allowed(request))
                n_oracle += 1
            except Exception:  # noqa: BLE001 — honest envelope below
                out.append(degraded_response(
                    "device materialize timed out and the oracle "
                    "fallback failed"
                ))
                n_degraded += 1
        self._count_path("hang-fallback-oracle", n_oracle)
        self._count_path("hang-fallback-degraded", n_degraded)
        self._count_path("deadline-expired", len(expired))
        self._slog.warning(
            "hang-fallback",
            "device materialize timeout: %d rows to oracle, %d shed, "
            "%d degraded", n_oracle, len(expired), n_degraded,
        )
        return out

    @property
    def native_active(self) -> bool:
        return self._native_encoder is not None

    def is_allowed_batch_wire(self, messages: list[bytes], span=None):
        """Native fast path: serialized acstpu.Request messages -> per-row
        (decision, cacheable, status, eligible).  Returns None when the
        native encoder is unavailable (caller falls back to the pb path).
        ``span`` is the RPC-level span from the transport (the native
        path has no Request objects to carry per-row spans)."""
        finalize = self.is_allowed_batch_wire_async(messages, span=span)
        return None if finalize is None else finalize()

    def is_allowed_batch_wire_async(self, messages: list[bytes], span=None,
                                    reuse: bool = False):
        """Dispatch stage of the native wire path: encode (C++) + device
        dispatch WITHOUT blocking, returning a zero-arg ``finalize`` that
        materializes and yields (batch, decision, cacheable, status) — the
        streaming pipeline (srv/pipeline.py) overlaps the next frame's
        encode/dispatch with this frame's device execution and the
        previous frame's decode.  None when the native path is
        unavailable (caller falls back to pb parsing).

        ``reuse=True`` encodes into pooled staging buffers; the CALLER
        must fire ``batch.release_staging()`` once it has finished reading
        the batch (after response assembly), never before."""
        with self._lock:
            kernel = self._kernel
            encoder = self._native_encoder
        if (kernel is None or encoder is None or self.backend == "oracle"
                or self._quarantined):
            return None
        tracer = self.obs.tracer if self.obs is not None else None
        stage = tracer.stage if tracer is not None else untimed
        with stage(STAGE_WIRE_ENCODE, span):
            rel_tables = self._relation_tables_native(encoder)
            batch = encoder.encode_wire(
                messages, reuse=reuse, relation_tables=rel_tables,
                owner_bits_stage=stage(STAGE_WIRE_OWNER_BITS),
            )
        t_device = time.perf_counter()
        # device.dispatch + device.wait split the ``device`` stage at the
        # host boundary; they go to the histograms and the profiler's
        # timeline, while the span keeps ``device`` whole
        with stage(STAGE_DEVICE_DISPATCH):
            materialize = self._dispatch(kernel, batch)

        def finalize():
            with stage(STAGE_DEVICE_WAIT):
                decision, cacheable, status = materialize()[:3]
            acl = _acl_rows(batch)
            if tracer is not None:
                tracer.record(span, STAGE_DEVICE,
                              time.perf_counter() - t_device)
            if batch.overcap is not None and batch.overcap.any():
                # adaptive caps, native path: rows that overflowed the
                # floor shapes are served natively at the caps their
                # overflow asks for (one extra kernel dispatch when any of
                # them then fits) instead of falling back to the oracle.
                # Rows over the HR-scope pairs alone are assembled from
                # the first pass's parse; any other overflow re-encodes.
                from ..ops.encode import retry_caps

                idx = [
                    b for b in range(len(messages))
                    if batch.overcap[b] and not batch.eligible[b]
                ]
                self._count_path("native-wire-overcap", len(idx))
                with stage(STAGE_WIRE_ENCODE_CEIL, span):
                    if batch.kept_hr is not None:
                        retry = encoder.retry_from_first_pass(
                            batch, relation_tables=rel_tables,
                            owner_bits_stage=stage(STAGE_WIRE_OWNER_BITS),
                        )
                        self._count_path("native-wire-overcap-kept", len(idx))
                    else:
                        retry = encoder.encode_wire(
                            [messages[b] for b in idx],
                            caps=retry_caps(batch.overcap[idx]),
                            relation_tables=rel_tables,
                            owner_bits_stage=stage(STAGE_WIRE_OWNER_BITS),
                        )
                n_retried = int(retry.eligible.sum())
                if n_retried:
                    with stage(STAGE_DEVICE_DISPATCH):
                        materialize_retry = self._dispatch(kernel, retry)
                    with stage(STAGE_DEVICE_WAIT):
                        d2, c2, s2 = materialize_retry()[:3]
                    # kernel outputs are read-only views on device buffers
                    decision = np.array(decision)
                    cacheable = np.array(cacheable)
                    status = np.array(status)
                    acl2 = _acl_rows(retry)
                    for j, b in enumerate(idx):
                        if retry.eligible[j]:
                            batch.eligible[b] = True
                            decision[b] = d2[j]
                            cacheable[b] = c2[j]
                            status[b] = s2[j]
                            acl[b] = acl2[j]
                self._count_path("native-wire-ceil", n_retried)
            n_served = sum(
                1 for b in range(len(messages))
                if batch.eligible[b] and status[b] == 200
            )
            self._count_path("native-wire", n_served)
            self._count_path("native-wire-acl", int(
                (acl & batch.eligible & (status == 200)).sum()))
            return batch, decision, cacheable, status

        return finalize

    # ------------------------------------------------- host-side pipeline

    def prepare_batch(self, requests: list) -> None:
        """Stage-traced wrapper over the eligibility pipeline: records
        the ``prepare`` stage (token resolution + HR rendezvous wall
        time) when the batch actually had unresolved token rows — the
        idempotent re-invocation from is_allowed_batch after the
        batcher already prepared is a no-op and records nothing."""
        tracer = self.obs.tracer if self.obs is not None else None
        if tracer is None:
            self._prepare_batch(requests)
            return
        t0 = time.perf_counter()
        did_work = self._prepare_batch(requests)
        if did_work:
            tracer.fan_out(requests, STAGE_PREPARE,
                           time.perf_counter() - t0)

    def _prepare_batch(self, requests: list) -> bool:
        """Host-side eligibility pipeline, stage (a): batch-resolve every
        distinct ``subject.token`` through the identity client (one RPC per
        distinct token — the TTL'd resolution cache makes repeats across
        batches nearly free) and the HR-scope rendezvous (one rendezvous
        per distinct cache key), then mark each request prepared so the
        encoder keeps resolved token rows on the kernel path.

        Idempotent and semantics-preserving by construction: after this,
        ``engine.prepare_context`` is a no-op for these requests, so kernel
        and oracle evaluate the identical resolved context.  Resolution
        failures leave ``request._token_resolved`` False and the row
        degrades per-row to the oracle exactly as unprepared token traffic
        does.  Callers that overlap device execution of batch i with this
        call for batch i+1 (srv/batcher.py) get the pipelining for free —
        everything here is host-only."""
        from ..core.common import get_field
        from ..core.engine import apply_resolved_subject

        engine = self.engine
        pending: list[tuple] = []
        for request in requests:
            if getattr(request, "_context_prepared", False):
                continue
            context = request.context
            subject = get_field(context, "subject") if context else None
            token = get_field(subject, "token") if subject is not None else None
            if token:
                pending.append((request, token))
        if not pending:
            return False

        client = engine.identity_client

        def resolve(token):
            try:
                return client.find_by_token(token)
            except Exception as err:  # noqa: BLE001 — fail the row closed
                # sampled: a down identity service under overload fires
                # this once per distinct token per batch — unbounded, it
                # would make the logger the bottleneck
                self._slog.warning(
                    "token-resolution",
                    "batch token resolution failed: %s", err,
                )
                return None

        by_token: dict[str, list] = {}
        for request, token in pending:
            by_token.setdefault(token, []).append(request)
        resolutions: dict[str, object] = {}
        if client is not None:
            tokens = list(by_token)
            if len(tokens) == 1:
                resolutions[tokens[0]] = resolve(tokens[0])
            else:
                from concurrent.futures import ThreadPoolExecutor

                with ThreadPoolExecutor(
                    max_workers=min(8, len(tokens))
                ) as pool:
                    for token, resolved in zip(
                        tokens, pool.map(resolve, tokens)
                    ):
                        resolutions[token] = resolved

        n_ok = n_fail = 0
        for token, rows in by_token.items():
            resolved = resolutions.get(token)
            payload = get_field(resolved, "payload") if resolved else None
            for request in rows:
                request._context_prepared = True
                if payload is not None:
                    # per-request copy: rows sharing a token must not share
                    # mutable payload objects
                    apply_resolved_subject(
                        get_field(request.context, "subject"),
                        copy.deepcopy(payload),
                    )
                    request._token_resolved = True
                    n_ok += 1
                else:
                    request._token_resolved = False
                    n_fail += 1
        self._count_path("token-resolved", n_ok)
        self._count_path("token-unresolved", n_fail)

        # HR scopes: one rendezvous per distinct cache key; the remaining
        # rows of each group read the freshly-written cache (no second
        # rendezvous).  A timed-out key leaves its whole group scope-less —
        # the same per-row outcome the reference's individual waits produce.
        provider = engine.hr_scope_provider
        if provider is None:
            return True
        groups: dict[str, list] = {}
        for request, _ in pending:
            if not getattr(request, "_token_resolved", False):
                continue
            subject = get_field(request.context, "subject")
            if get_field(subject, "hierarchical_scopes"):
                continue
            key = provider.hr_scopes_key(request.context)
            if key is not None:
                groups.setdefault(key, []).append(request)
        if not groups:
            return True
        firsts = [rows[0] for rows in groups.values()]
        if len(firsts) > 1:
            from concurrent.futures import ThreadPoolExecutor

            with ThreadPoolExecutor(max_workers=min(8, len(firsts))) as pool:
                list(pool.map(
                    lambda r: engine.create_hr_scope(r.context), firsts
                ))
        else:
            engine.create_hr_scope(firsts[0].context)
        for key, rows in groups.items():
            for request in rows[1:]:
                try:
                    cached = provider.cache.exists(key)
                except Exception:  # noqa: BLE001 — cache backend hiccup
                    cached = True  # fall through to the normal path
                if cached:
                    engine.create_hr_scope(request.context)
        return True

    # ------------------------------------------------------------ evaluation

    def is_allowed(self, request) -> Response:
        """Single-request path: the oracle wins below batch sizes where the
        device round-trip pays off.  The decision cache is consulted first
        — a warm cacheable request never pays the walk."""
        tracer = self.obs.tracer if self.obs is not None else None
        cache = self.decision_cache
        if cache is not None and cache.enabled:
            # epoch snapshot BEFORE the walk reads the tree: if a CRUD /
            # restore bump lands while this decision is in flight, the
            # write-through below stores a born-stale entry (logical miss)
            # instead of serving an old-tree decision as fresh
            epoch = cache.epoch
            self.engine.prepare_context(request)
            t0 = time.perf_counter() if tracer is not None else 0.0
            key = cache.fingerprint(
                request, self.engine.urns.get("subjectID") or ""
            )
            hit = cache.get(key)
            if hit is not None:
                self._count_path("cache-hit", 1)
                if tracer is not None:
                    tracer.record(getattr(request, "_span", None),
                                  STAGE_CACHE, time.perf_counter() - t0)
                    hit._path = "cache-hit"
                return hit
            response = self._traced_oracle(request, tracer)
            cache.put(key, response, epoch=epoch,
                      features=self._request_features(request))
            return response
        return self._traced_oracle(request, tracer)

    def _traced_oracle(self, request, tracer) -> Response:
        """Oracle walk with the ``oracle`` stage recorded (and the
        serving-path attribute stamped for the audit log) when the
        observability hub is wired; the bare walk otherwise."""
        if tracer is None:
            return self._oracle_is_allowed(request)
        with tracer.stage(STAGE_ORACLE, getattr(request, "_span", None)):
            response = self._oracle_is_allowed(request)
        response._path = "oracle"
        return response

    def _request_features(self, request):
        """Candidate-signature features for scoped cache invalidation
        (srv/decision_cache.request_features)."""
        urns = self.engine.urns
        return request_features(
            request, urns.get("entity"), urns.get("operation")
        )

    def _oracle_is_allowed(self, request) -> Response:
        """Oracle walk, candidate-filtered on large trees (skipped rules
        provably cannot target-match; decisions bit-identical — the
        unfiltered walk costs O(total rules) per request, ~28 ms on a
        10k-rule tree).  One read of the (tree, index) pair keeps the
        identity guard and the index consistent under concurrent swaps."""
        cand = self._cand
        if cand is not None and cand[0] is self.engine.policy_sets:
            response = self.engine.is_allowed(
                request,
                candidate_rules=cand[1].candidates(request, self.engine.urns),
            )
        else:
            response = self.engine.is_allowed(request)
        decoder = self._explain_decoder
        if decoder is not None and getattr(response, "_explain", None) is None:
            # oracle rows carry the same ``_explain`` shape as kernel
            # rows (reverse-lookup of the engine's source stamp), so the
            # wire trailer / audit surface never depends on which path
            # decided a row.  None when explain is off — zero new work.
            info = decoder.describe_source(
                getattr(response, "_rule_id", None)
            )
            if info is not None:
                response._explain = info
                rel = self._relation_provenance(
                    request, info.get("rule") or info.get("policy")
                )
                if rel is not None:
                    info["relation"] = rel
        return response

    def what_is_allowed(self, request):
        return self.engine.what_is_allowed(request)

    def what_is_allowed_batch(self, requests: list):
        """Batched reverse query: target matching for the whole batch in
        one device dispatch, tree/obligation assembly on host
        (ops/reverse.py); scalar oracle when no kernel is active.  The
        ReverseQueryKernel is built lazily on first use (deployments that
        only serve isAllowed never pay its device transfer).

        Dispatch is adaptive like the decision path's MIN_RULES: on small
        trees the scalar walk is taken to beat the device round-trip (no
        benchmark cell measures the crossover), so the kernel only engages
        at REVERSE_MIN_RULES and above."""
        from ..ops.reverse import REVERSE_MIN_RULES

        self.prepare_batch(requests)
        with self._lock:
            # one consistent snapshot: kernel/compiled/tree always published
            # together, so kernel != None implies compiled.supported
            compiled = self._compiled
            kernel = self._kernel
            rq_kernel = self._rq_kernel
            tree_snapshot = self._tree_snapshot
        if (
            self.backend == "oracle"
            or compiled is None
            or kernel is None
            or self._quarantined
            or compiled.n_rules < REVERSE_MIN_RULES
        ):
            self._count_path("oracle-wia", len(requests))
            return [self.engine.what_is_allowed(r) for r in requests]
        from ..ops.encode import encode_requests
        from ..ops.reverse import ReverseQueryKernel, what_is_allowed_batch

        if rq_kernel is None or rq_kernel.compiled.version != compiled.version:
            # tree_snapshot was published atomically with `compiled` and is
            # the exact tree the arrays were compiled from — no tearing
            # against concurrent hot mutations is possible here
            rq_kernel = ReverseQueryKernel(
                compiled, tree_snapshot, copy_tree=False
            )
            with self._lock:
                if self._compiled is compiled:
                    self._rq_kernel = rq_kernel
        # reverse queries never reach stage B: skip the owner-bit packer,
        # the relation-plane packer (wia ignores relation requirements,
        # like the HR gate) and the condition pre-pass on this encode
        batch = encode_requests(
            requests, compiled, skip_conditions=True, skip_owner_bits=True,
            skip_relation_bits=True,
        )
        out = what_is_allowed_batch(
            self.engine, compiled, rq_kernel, requests, batch
        )
        n_oracle = int((~batch.eligible).sum())
        self._count_path("oracle-wia", n_oracle)
        self._count_path("kernel-wia", len(requests) - n_oracle)
        return out

    def _dispatch(self, kernel, batch):
        """``kernel.evaluate_async(batch)`` under the watchdog's guard.  A
        batch with ACL pairs counts once under ``acl-program`` (the dense
        kernel runs it on ``acs_dense_acl``) and once under its dispatched
        shape (``ops/kernel.dispatch_shape_key``)."""
        materialize = self._guard_materialize(kernel.evaluate_async(batch))
        if self.telemetry is not None and batch_has_acl(batch):
            targets = np.shape(kernel.compiled.arrays["t_role"])[0]
            self._count_path("acl-program", 1)
            self._count_path(
                dispatch_shape_key("acl-program", batch, targets), 1)
        return materialize

    def _count_path(self, path: str, rows: int) -> None:
        if self.telemetry is not None and rows:
            self.telemetry.paths.inc(path, rows)

    @staticmethod
    def _expired_rows(requests: list) -> set[int]:
        """Indices of rows whose propagated ``_deadline`` already passed
        (empty for deadline-less traffic — the common case costs one
        getattr per row)."""
        expired: set[int] = set()
        now = None
        for b, request in enumerate(requests):
            deadline = getattr(request, "_deadline", None)
            if deadline is None:
                continue
            if now is None:
                now = time.monotonic()
            if deadline <= now:
                expired.add(b)
        return expired

    def is_allowed_batch(self, requests: list) -> list[Response]:
        """Batched decision path: decision-cache lookup batch-wide BEFORE
        encode (hit rows skip the device round-trip and the oracle walk),
        then the kernel/oracle hybrid over the miss rows, then write-through
        of every miss row the engine marked ``evaluation_cacheable``.

        Rows carrying an already-expired ``_deadline`` (admission
        plumbing, srv/admission.py — set by the transports / service
        facade) short-circuit with the deadline status before any
        evaluation: the caller has abandoned the answer, so neither the
        device nor the oracle burns time on it, and nothing is cached."""
        return self.is_allowed_batch_async(requests)()

    def is_allowed_batch_async(self, requests: list):
        """Dispatch stage of the depth-N batcher pipeline: expired-row
        shed, host eligibility pipeline, cache lookups and encode + device
        DISPATCH run now; the returned zero-arg ``finalize`` blocks on the
        device result, decodes, runs oracle fallback rows and writes the
        cache through.  Calling it immediately is byte-identical to the
        synchronous path (the depth<=2 legacy batcher does exactly that);
        deferring it lets the next batch's dispatch overlap this batch's
        device execution (srv/batcher.py, depth>2)."""
        expired = self._expired_rows(requests)
        if expired:
            from .admission import DEADLINE_CODE, overload_response

            live = [r for b, r in enumerate(requests) if b not in expired]
            fin_live = (
                self.is_allowed_batch_async(live) if live else (lambda: [])
            )
            self._count_path("deadline-expired", len(expired))
            shed = overload_response(
                DEADLINE_CODE, "deadline expired before evaluation"
            )

            def finalize_expired():
                computed = iter(fin_live())
                return [
                    shed if b in expired else next(computed)
                    for b in range(len(requests))
                ]

            return finalize_expired
        self.prepare_batch(requests)
        cache = self.decision_cache
        if cache is None or not cache.enabled:
            return self._uncached_async_entry(requests)
        subject_urn = self.engine.urns.get("subjectID") or ""
        # one epoch snapshot for the whole batch, taken before any row
        # reads the tree: rows whose evaluation spans a concurrent epoch
        # bump are written through born-stale (see DecisionCache.put)
        epoch = cache.epoch
        tracer = self.obs.tracer if self.obs is not None else None
        t_cache = time.perf_counter() if tracer is not None else 0.0
        responses: list[Optional[Response]] = [None] * len(requests)
        keys: list = [None] * len(requests)
        misses: list[int] = []
        for b, request in enumerate(requests):
            # fingerprints are taken AFTER context resolution so the key
            # reflects the subject the evaluation will actually see (and
            # so a userModified-driven re-resolution changes the key)
            self.engine.prepare_context(request)
            keys[b] = cache.fingerprint(request, subject_urn)
            hit = cache.get(keys[b])
            if hit is not None:
                responses[b] = hit
            else:
                misses.append(b)
        if tracer is not None and len(misses) < len(requests):
            tracer.fan_out(
                [r for b, r in enumerate(requests) if responses[b] is not None],
                STAGE_CACHE, time.perf_counter() - t_cache,
            )
            for response in responses:
                if response is not None:
                    response._path = "cache-hit"
        self._count_path("cache-hit", len(requests) - len(misses))
        if not misses:
            return lambda: responses
        fin_misses = self._uncached_async_entry(
            [requests[b] for b in misses]
        )

        def finalize_cached():
            computed = fin_misses()
            for j, b in enumerate(misses):
                responses[b] = computed[j]
                # write-through from BOTH serving paths: kernel rows and
                # oracle-fallback rows land here alike; put() keeps only
                # cacheable 200s
                cache.put(keys[b], computed[j], epoch=epoch,
                          features=self._request_features(requests[b]))
            return responses

        return finalize_cached

    def _is_allowed_batch_uncached(self, requests: list) -> list[Response]:
        return self._is_allowed_batch_uncached_async(requests)()

    def _uncached_async_entry(self, requests: list):
        """Route through the SYNC uncached path when a subclass or test
        double overrode it (the async split must not silently bypass an
        interposed implementation); the real dispatch/finalize split
        otherwise."""
        sync = self._is_allowed_batch_uncached
        if getattr(sync, "__func__", None) is not \
                HybridEvaluator._is_allowed_batch_uncached:
            return lambda: sync(requests)
        return self._is_allowed_batch_uncached_async(requests)

    def _is_allowed_batch_uncached_async(self, requests: list):
        with self._lock:
            kernel = self._kernel
            compiled = self._compiled
            # paired with the kernel under the same lock: provenance must
            # decode against the tree the serving program was lowered from
            decoder = self._explain_decoder
        if self.backend == "oracle" or kernel is None or self._quarantined:
            # candidate-filtered like every other oracle path (skipped
            # rules provably cannot target-match; bit-identical) — the
            # unfiltered walk costs O(total rules) per row, ~21 ms on a
            # 10k-rule tree vs sub-ms filtered.  Host-only: nothing to
            # overlap, so the walk runs at finalize.
            def run_oracle():
                self._count_path("oracle", len(requests))
                return [self._oracle_is_allowed(r) for r in requests]

            return run_oracle

        # mixed-traffic split: a handful of deep/wide rows must not
        # inflate the adaptive padding caps (and device cost) of the whole
        # batch — encode floor-fitting rows at the steady-state compiled
        # shape and only the rest at batch-max caps
        if len(requests) >= 8:
            from ..ops.encode import _CAPS_FLOOR, fits_floor, request_needs

            ext = [
                b for b, r in enumerate(requests)
                if not fits_floor(request_needs(r, compiled.urns))
            ]
            if 0 < len(ext) < len(requests):
                ext_set = set(ext)
                floor_rows = [b for b in range(len(requests))
                              if b not in ext_set]
                # both sub-batches dispatch back-to-back (they ride the
                # same device queue), then finalize in dispatch order
                fins = [
                    (rows, self._eval_encoded_async(
                        kernel, compiled, [requests[b] for b in rows], caps,
                        decoder=decoder,
                    ))
                    for rows, caps in ((floor_rows, dict(_CAPS_FLOOR)),
                                       (ext, None))
                ]

                def finalize_split():
                    out: list[Response] = [None] * len(requests)
                    for rows, fin in fins:
                        for b, resp in zip(rows, fin()):
                            out[b] = resp
                    return out

                return finalize_split
        return self._eval_encoded_async(
            kernel, compiled, requests, None, decoder=decoder
        )

    def _eval_encoded(self, kernel, compiled, requests: list, caps,
                      decoder=None):
        return self._eval_encoded_async(
            kernel, compiled, requests, caps, decoder=decoder
        )()

    def _eval_encoded_async(self, kernel, compiled, requests: list, caps,
                            decoder=None):
        tracer = self.obs.tracer if self.obs is not None else None
        stage = tracer.stage if tracer is not None else untimed
        with stage(STAGE_ENCODE, requests=requests):
            batch = encode_requests(
                requests, compiled, self.engine.resource_adapter, caps=caps,
                relation_tables=self._relation_tables(compiled),
            )
        t_device = time.perf_counter()
        with stage(STAGE_DEVICE_DISPATCH):
            materialize = self._dispatch(kernel, batch)

        def finalize():
            try:
                with stage(STAGE_DEVICE_WAIT):
                    outputs = materialize()
            except DeviceTimeoutError:
                return self._hang_fallback(requests)
            return self._decode_batch(
                requests, batch, outputs, tracer, t_device, decoder=decoder
            )

        return finalize

    def _decode_batch(self, requests, batch, outputs, tracer, t_device,
                      decoder=None):
        decision, cacheable, status = outputs[:3]
        # explain mode: 4th kernel output packs the deciding node's slot
        # position; decoded per kernel-path row below (srv/explain.py)
        expl = outputs[3] if decoder is not None and len(outputs) > 3 \
            else None
        if tracer is not None:
            # dispatch->materialize spans H2D transfer, device dispatch
            # and the D2H fetch — attributed as one ``device`` stage (the
            # host/device boundary; docs/OBSERVABILITY.md).  Pipelined
            # callers overlap it with neighbor batches' host stages; the
            # attribution stays wall time from dispatch to fetch.
            tracer.fan_out(requests, STAGE_DEVICE,
                           time.perf_counter() - t_device)
        stage = tracer.stage if tracer is not None else untimed
        with stage(STAGE_DECODE, requests=requests):
            n_oracle = sum(
                1 for b in range(len(requests))
                if not batch.eligible[b] or status[b] != 200
            )
            self._count_path("oracle", n_oracle)
            self._count_path("kernel", len(requests) - n_oracle)
            C = batch.cond_true.shape[0]
            responses: list[Response] = []
            oracle_pending: list[tuple[int, object]] = []
            for b, request in enumerate(requests):
                if batch.eligible[b] and status[b] != 200:
                    # abort row: the pre-pass cached the condition error text;
                    # when exactly one aborting condition matches the row's
                    # status code the message is unambiguous and the oracle
                    # re-run is skipped (reference error shape:
                    # accessController.ts:259-270 — DENY + code + message)
                    msgs = {
                        batch.cond_msg.get((ci, b))
                        for ci in range(C)
                        if batch.cond_abort[ci][b]
                        and batch.cond_code[ci][b] == status[b]
                    }
                    if len(msgs) == 1 and None not in msgs:
                        cach = None if cacheable[b] < 0 else bool(cacheable[b])
                        resp = Response(
                            decision=Decision.DENY,
                            obligations=[],
                            evaluation_cacheable=cach,
                            operation_status=OperationStatus(
                                code=int(status[b]), message=msgs.pop()
                            ),
                        )
                        if expl is not None:
                            # the richer explain dict names the aborting rule;
                            # no ``_rule_id`` — the oracle's abort response
                            # carries no provenance either (host parity)
                            info = decoder.decode(expl[b])
                            if info is not None:
                                resp._explain = info
                        responses.append(resp)
                        continue
                if not batch.eligible[b] or status[b] != 200:
                    # ineligible rows (and ambiguous abort rows) take the
                    # oracle path (candidate-filtered on large trees);
                    # resolved together below so adapter-backed rows can fan
                    # out concurrently
                    oracle_pending.append((len(responses), request))
                    responses.append(None)
                    continue
                cach = None if cacheable[b] < 0 else bool(cacheable[b])
                resp = Response(
                    decision=DECISION_NAMES[int(decision[b])],
                    obligations=[],
                    evaluation_cacheable=cach,
                    operation_status=OperationStatus(),
                )
                if expl is not None:
                    info = decoder.decode(expl[b])
                    if info is not None:
                        resp._explain = info
                        rel = self._relation_provenance(
                            request, info.get("rule") or info.get("policy")
                        )
                        if rel is not None:
                            # relation-decided row: the tuple-path hop list
                            # that let this subject through the deciding
                            # node's relation gate (srv/relations.witness)
                            info["relation"] = rel
                    source = decoder.source(expl[b])
                    if source is not None:
                        # identical to the oracle's EffectEvaluation.source
                        # stamp (core/engine.py) — the audit log and the
                        # transports read the same attribute either way
                        resp._rule_id = source
                responses.append(resp)
        if tracer is not None:
            for resp in responses:
                if resp is not None:
                    resp._path = "kernel"
        if oracle_pending:
            if len(requests) >= 8:
                # sampled: a down adapter under overload degrades whole
                # batches to the oracle — the signal matters, the
                # per-batch record flood does not
                self._slog.warning(
                    "oracle-fallback",
                    "%d/%d batch rows fell back to the scalar oracle",
                    len(oracle_pending), len(requests),
                )
            rows = [req for _, req in oracle_pending]
            adapter = self.engine.resource_adapter
            with stage(STAGE_ORACLE, requests=rows):
                if adapter is not None and len(rows) > 1:
                    # adapter-backed fallback rows block on remote context
                    # queries — fan the walks out so the batch stalls for
                    # at most ~one endpoint timeout instead of N sequential
                    # ones (the adapter's transport is pooled +
                    # timeout-bounded, srv/adapters.py)
                    from concurrent.futures import ThreadPoolExecutor

                    workers = min(
                        len(rows),
                        int(getattr(adapter, "max_concurrency", 8) or 8),
                    )
                    with ThreadPoolExecutor(max_workers=workers) as pool:
                        results = list(
                            pool.map(self._oracle_is_allowed, rows)
                        )
                else:
                    results = [self._oracle_is_allowed(r) for r in rows]
            if tracer is not None:
                for response in results:
                    response._path = "oracle"
            for (slot, _), response in zip(oracle_pending, results):
                responses[slot] = response
        return responses
