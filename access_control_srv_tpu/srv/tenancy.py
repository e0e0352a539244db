"""Multi-tenant serving registry: 1k policy domains on a handful of
compiled programs.

The reference is one ABAC service inside a multi-tenant commerce
platform — every deployment serves MANY policy domains (tenants), not
one giant tree.  The TPU angle (docs/MULTITENANT.md): capacity-bucketed
compiled tables (ops/delta.Capacities) mean two tenants whose trees pad
to the SAME capacity class produce byte-identical jitted programs where
the per-tenant tables are jit *arguments* — so a thousand tenant trees
serve from at most ``len(SIZE_CLASSES)`` compiled programs instead of a
thousand XLA compiles (tests/test_tenancy.py::TestProgramPacking).

Pieces:

* ``SIZE_CLASSES`` — the fixed capacity ladder.  A tenant's live tree
  (ops/delta.live_capacities of a host-side compile) picks the smallest
  class that fits; trees larger than the top class fall back to
  per-tenant capacity buckets (counted, still correct, no sharing).
* ``TenantRegistry`` — tenant id -> per-tenant document store + lazily
  built per-tenant ``HybridEvaluator`` pinned to its class capacities
  (``fixed_caps``) and sharing one jit table (``shared_jits``) across
  ALL tenants.  The batcher partitions mixed batches by tenant and
  resolves each group against its tenant's evaluator
  (srv/batcher.MicroBatcher._eval_tenants).
* **Scoped everything** — a tenant's CRUD bumps only its own epoch,
  patches only its own tables (the evaluator's delta path), and flushes
  only its own decision-cache namespace (srv/decision_cache tenant-keyed
  entries + tenant-tagged epoch bumps).
* **Journaled onboarding** — every tenant mutation is emitted on the
  same CRUD topics the global store journals to, tagged with the tenant
  id; ``PolicyReplicator`` routes tenant-tagged frames here, so a new
  tenant boots by replay and a restarting replica converges per-tenant
  epochs/fingerprints through the existing convergence oracle
  (srv/router.py).

With no registry wired (config ``tenancy:enabled`` false, the default)
nothing in this module runs and the serving path is byte-identical to
the single-tenant behavior (tests/test_tenancy.py differential check).
"""

from __future__ import annotations

import hashlib
import threading
from typing import Optional

from ..core.engine import AccessController
from ..core.loader import (
    policy_from_dict,
    policy_set_from_dict,
    rule_from_dict,
)
from ..models.model import Decision, OperationStatus, Response
from ..ops.delta import (
    Capacities,
    CrudEvent,
    footprint_from_events,
    live_capacities,
)

# the capacity ladder: padded dims per class, smallest first.  Every
# tenant in one class compiles to the same padded shapes, so the class
# shares ONE jitted program per kernel variant (jax caches per-shape
# under the shared_jits entry).  Dims follow ops/delta.Capacities
# (S policy-set slots, KP policies/set, KR rules/policy, T target rows,
# RV (role,scoping) vocab, W entity-regex vocab) at pow2 steps.
SIZE_CLASSES: tuple = (
    ("xs", Capacities(S=2, KP=2, KR=4, T=16, RV=8, W=8)),
    ("s", Capacities(S=4, KP=4, KR=8, T=64, RV=16, W=16)),
    ("m", Capacities(S=8, KP=8, KR=16, T=256, RV=64, W=64)),
    ("l", Capacities(S=16, KP=16, KR=32, T=1024, RV=256, W=256)),
)

# class name for tenants whose trees overflow the top class: they serve
# from per-tenant capacity buckets (ops/delta.capacities_for) — correct,
# but each such tenant may cost its own compile
UNPINNED = "__unpinned__"

_KINDS = ("rule", "policy", "policy_set")

_COMPOSERS = {
    "rule": rule_from_dict,
    "policy": policy_from_dict,
    "policy_set": policy_set_from_dict,
}

# journal event-name stems, matching srv/store.ResourceService.KIND_EVENT
_KIND_EVENT = {"rule": "rule", "policy": "policy", "policy_set": "policySet"}


def class_for_live(live: Capacities) -> Optional[str]:
    """Smallest size class that fits ``live`` on every dim; None when the
    tree overflows the ladder (per-tenant buckets)."""
    for name, caps in SIZE_CLASSES:
        if all(
            getattr(live, dim) <= getattr(caps, dim)
            for dim in ("S", "KP", "KR", "T", "RV", "W")
        ):
            return name
    return None


def class_caps(name: Optional[str]) -> Optional[Capacities]:
    for cls_name, caps in SIZE_CLASSES:
        if cls_name == name:
            return caps
    return None


def unknown_tenant_response(tenant: str) -> Response:
    """Honest INDETERMINATE for a tenant id with no registered policy
    domain — never a default-domain decision (isolation), never cached
    (the tenant may onboard a moment later)."""
    return Response(
        decision=Decision.INDETERMINATE,
        obligations=[],
        evaluation_cacheable=False,
        operation_status=OperationStatus(
            code=404, message=f"unknown tenant: {tenant}"
        ),
    )


class TenantState:
    """One tenant's policy domain: flat document collections (the same
    3-kind shape as srv/store.PolicyStore), a per-tenant epoch, and the
    lazily built engine + evaluator."""

    def __init__(self, tenant_id: str):
        self.tenant_id = tenant_id
        self.docs: dict[str, dict] = {kind: {} for kind in _KINDS}
        # per-tenant policy epoch: CRUD frames applied to THIS tenant —
        # the number the convergence oracle compares across replicas
        self.epoch = 0
        self.size_class: Optional[str] = None
        self.engine: Optional[AccessController] = None
        self.evaluator = None

    def empty(self) -> bool:
        return not any(self.docs[kind] for kind in _KINDS)

    def compose_tree(self) -> dict:
        """The 3-level compose srv/store.PolicyStore._load_locked runs,
        over this tenant's collections."""
        rules = {
            d["id"]: rule_from_dict(d) for d in self.docs["rule"].values()
        }
        policies = {}
        for p_doc in self.docs["policy"].values():
            child_rules = [
                rules.get(rid) for rid in p_doc.get("rules") or []
            ]
            policy = policy_from_dict(p_doc)
            policy.combinables = {
                (r.id if r is not None else f"__missing_{i}"): r
                for i, r in enumerate(child_rules)
            }
            policies[p_doc["id"]] = policy
        tree: dict = {}
        for ps_doc in self.docs["policy_set"].values():
            child_policies = [
                policies.get(pid) for pid in ps_doc.get("policies") or []
            ]
            policy_set = policy_set_from_dict(ps_doc)
            policy_set.combinables = {
                (p.id if p is not None else f"__missing_{i}"): p
                for i, p in enumerate(child_policies)
            }
            tree[policy_set.id] = policy_set
        return tree


class TenantRegistry:
    """Tenant id -> policy domain, sharing compiled programs per size
    class.  Thread-safe: the batcher's eval worker, CRUD threads and the
    replicator pump all call in concurrently."""

    def __init__(
        self,
        urns,
        logger=None,
        telemetry=None,
        decision_cache=None,
        backend: str = "hybrid",
        store=None,
        observability=None,
        max_tenants: int = 100_000,
    ):
        self.urns = urns
        self.logger = logger
        self.telemetry = telemetry
        self.decision_cache = decision_cache
        self.backend = backend
        # PolicyStore: source of the journal topics + the origin stamp
        # for emitted frames (None = journaling off, e.g. unit tests)
        self.store = store
        self.observability = observability
        self.max_tenants = int(max_tenants)
        # ONE shared jit table across every tenant evaluator: jit entries
        # are keyed by kernel variant and jax caches per padded shape
        # underneath, so tenants in one size class (identical padded
        # shapes) lower to the same compiled program.  Program count =
        # compiled_program_count() = sum of per-entry shape-cache sizes.
        self._shared_jits: dict = {}
        self._lock = threading.RLock()
        self._tenants: dict[str, TenantState] = {}  # guarded-by: _lock
        self._stats = {  # guarded-by: _lock
            "onboarded": 0, "offboarded": 0, "frames_applied": 0,
            "frames_emitted": 0, "unpinned": 0,
        }

    # ------------------------------------------------------------- lookups

    def __contains__(self, tenant: str) -> bool:
        with self._lock:
            return tenant in self._tenants

    def tenant_ids(self) -> list[str]:
        with self._lock:
            return list(self._tenants)

    def tenant_epoch(self, tenant: str) -> Optional[int]:
        with self._lock:
            state = self._tenants.get(tenant)
            return state.epoch if state is not None else None

    def evaluator_for(self, tenant: str):
        """The tenant's evaluator, built lazily on first traffic (the
        build compiles against the class-shared jit table, so a cold
        tenant in a warm class pays tracing only when it is the FIRST of
        its class+shape; after that the program is a cache hit).  None
        for unknown tenants."""
        with self._lock:
            state = self._tenants.get(tenant)
            if state is None:
                return None
            if state.evaluator is None:
                self._build_evaluator(state)
            return state.evaluator

    # ----------------------------------------------------------- lifecycle

    def _build_evaluator(self, state: TenantState) -> None:  # holds: _lock
        from ..ops.compile import compile_policies
        from .evaluator import HybridEvaluator

        tree = state.compose_tree()
        engine = AccessController(urns=self.urns, logger=self.logger)
        engine.replace_policy_sets(tree)
        fixed = None
        try:
            raw = compile_policies(tree, self.urns, version=state.epoch)
            if raw.supported:
                state.size_class = class_for_live(live_capacities(raw))
                fixed = class_caps(state.size_class)
        except Exception:  # noqa: BLE001 — classification is best-effort
            state.size_class = None
        if state.size_class is None:
            self._stats["unpinned"] += 1
        state.engine = engine
        state.evaluator = HybridEvaluator(
            engine,
            backend=self.backend,
            logger=self.logger,
            telemetry=self.telemetry,
            decision_cache=self.decision_cache,
            observability=self.observability,
            shared_jits=self._shared_jits,
            fixed_caps=fixed,
            tenant=state.tenant_id,
        )

    def offboard(self, tenant: str) -> bool:
        """Journaled offboarding: a collection-clear frame per kind (the
        same ``{"collection": True}`` Deleted frames the global store
        emits) — replicas replaying the journal converge to the tenant
        being gone.  The tenant's cache namespace is dropped with it."""
        with self._lock:
            if tenant not in self._tenants:
                return False
        for kind in _KINDS:
            self.apply(tenant, kind, "delete_all", None)
        return True

    # ----------------------------------------------------------------- CRUD

    def apply(self, tenant: str, kind: str, op: str,
              doc: Optional[dict], emit: bool = True) -> None:
        """Apply one CRUD mutation to ``tenant``'s domain: validate,
        update the tenant collections, bump the tenant epoch, scope the
        cache flush to the tenant, refresh the tenant evaluator (delta
        patch within its capacity class), and journal the frame.

        ``op``: "upsert" | "delete" | "delete_all".  An upsert for an
        unknown tenant onboards it (boot-by-replay is just this path fed
        from the journal)."""
        if kind not in _KINDS:
            raise ValueError(f"unknown resource kind: {kind}")
        if op == "upsert":
            _COMPOSERS[kind](doc)  # malformed docs rejected before state
            if not doc.get("id"):
                raise ValueError("document requires an id")
        with self._lock:
            state = self._tenants.get(tenant)
            if state is None:
                if op != "upsert":
                    return  # delete for an unknown tenant: no-op
                if len(self._tenants) >= self.max_tenants:
                    raise RuntimeError(
                        f"tenant registry full ({self.max_tenants})"
                    )
                state = TenantState(tenant)
                self._tenants[tenant] = state
                self._stats["onboarded"] += 1
            docs = state.docs[kind]
            if op == "upsert":
                events = [CrudEvent(
                    kind=kind, op="upsert", doc_id=doc["id"],
                    old_doc=docs.get(doc["id"]), new_doc=doc,
                )]
                docs[doc["id"]] = doc
            elif op == "delete":
                doc_id = (doc or {}).get("id") if isinstance(doc, dict) \
                    else doc
                if not doc_id or doc_id not in docs:
                    return
                events = [CrudEvent(
                    kind=kind, op="delete", doc_id=doc_id,
                    old_doc=docs.get(doc_id), new_doc=None,
                )]
                del docs[doc_id]
            elif op == "delete_all":
                events = [CrudEvent(kind=kind, op="delete_all", doc_id="")]
                state.docs[kind] = {}
            else:
                raise ValueError(f"unknown CRUD op: {op}")
            state.epoch += 1
            self._stats["frames_applied"] += 1
            self._sync_tenant(state, events)
            if state.empty():
                # all three collections cleared: the tenant is offboarded
                del self._tenants[tenant]
                self._stats["offboarded"] += 1
                if self.decision_cache is not None:
                    self.decision_cache.evict_pattern("", tenant=tenant)
        if emit:
            self._emit(tenant, kind, op, doc)

    def _sync_tenant(self, state: TenantState, events) -> None:
        """Tenant-scoped twin of srv/store.PolicyStore._load_locked:
        scoped cache bump BEFORE the tree swap, then engine swap, then
        evaluator refresh (delta patch or fixed-class recompile) — only
        THIS tenant's cache namespace and tables are touched."""
        # holds: _lock
        footprint = None
        try:
            footprint = footprint_from_events(
                events,
                self.urns,
                lambda kind, doc_id: state.docs[kind].get(doc_id),
                lambda kind: list(state.docs[kind].values()),
            )
        except Exception:  # noqa: BLE001 — footprint is an optimization
            footprint = None
        if self.decision_cache is not None:
            if footprint is not None and footprint.empty:
                pass
            elif footprint is not None:
                self.decision_cache.bump_scoped(
                    footprint, tenant=state.tenant_id
                )
            else:
                self.decision_cache.bump_epoch(tenant=state.tenant_id)
        if state.engine is not None:
            state.engine.replace_policy_sets(state.compose_tree())
        if state.evaluator is not None:
            state.evaluator.refresh(
                wait=True, events=events, footprint=footprint
            )

    # -------------------------------------------------------------- journal

    def _emit(self, tenant: str, kind: str, op: str,
              doc: Optional[dict]) -> None:
        """Emit the tenant-tagged CRUD frame on the same journal topics
        the global store uses — ``PolicyReplicator`` routes frames whose
        envelope carries a ``tenant`` key back into a registry."""
        store = self.store
        if store is None:
            return
        service = store.services.get(kind)
        topic = getattr(service, "topic", None)
        if topic is None:
            return
        stem = _KIND_EVENT[kind]
        if op == "upsert":
            event, payload = f"{stem}Modified", doc
        elif op == "delete":
            doc_id = doc.get("id") if isinstance(doc, dict) else doc
            event, payload = f"{stem}Deleted", {"id": doc_id}
        else:
            event, payload = f"{stem}Deleted", {"collection": True}
        topic.emit(event, {
            "payload": payload, "origin": store.origin, "tenant": tenant,
        })
        with self._lock:
            self._stats["frames_emitted"] += 1

    def apply_remote_frame(self, tenant: str, kind: str,
                           event_name: str, payload) -> None:
        """Replicator entry point: translate a journaled frame (local
        replay or a remote worker's live mutation) into an apply().  The
        frame is NOT re-emitted."""
        if not isinstance(payload, dict):
            return
        if event_name.endswith("Created") or event_name.endswith(
            "Modified"
        ):
            if payload.get("id"):
                self.apply(tenant, kind, "upsert", payload, emit=False)
        elif event_name.endswith("Deleted"):
            if payload.get("collection"):
                self.apply(tenant, kind, "delete_all", None, emit=False)
            elif payload.get("id"):
                self.apply(tenant, kind, "delete", payload, emit=False)

    # ---------------------------------------------------------------- stats

    def compiled_program_count(self) -> int:
        """Distinct lowered programs across every tenant evaluator: the
        per-shape cache size under each shared jit entry.  The packing
        claim: 1k tenants over <= len(SIZE_CLASSES) classes keep this at
        classes x kernel-variants, not O(tenants)."""
        total = 0
        for fn in dict(self._shared_jits).values():
            try:
                total += int(fn._cache_size())
            except Exception:  # noqa: BLE001 — non-jit entries count as 1
                total += 1
        return total

    def class_histogram(self) -> dict:
        with self._lock:
            hist: dict[str, int] = {}
            for state in self._tenants.values():
                name = (
                    state.size_class if state.size_class is not None
                    else (UNPINNED if state.evaluator is not None else
                          "__unbuilt__")
                )
                hist[name] = hist.get(name, 0) + 1
            return hist

    def epochs(self, top_k: int = 8) -> dict:
        """Highest per-tenant epochs (the busiest domains first) — the
        health/cluster_status surface keeps this bounded at ``top_k``."""
        with self._lock:
            items = sorted(
                ((t, s.epoch) for t, s in self._tenants.items()),
                key=lambda kv: kv[1], reverse=True,
            )
        return dict(items[:top_k])

    def epoch_digest(self) -> str:
        """Order-independent digest over (tenant, epoch) pairs: two
        replicas that applied the same journal converge to the same
        digest — the per-tenant analog of the policy epoch the router
        compares (srv/router.py cluster_status)."""
        h = hashlib.blake2b(digest_size=16)
        with self._lock:
            for tenant in sorted(self._tenants):
                state = self._tenants[tenant]
                h.update(f"{tenant}={state.epoch};".encode())
        return h.hexdigest()

    def fingerprints(self) -> dict:
        """Per-tenant table fingerprints for evaluators that are built —
        what the convergence oracle compares across replicas."""
        out = {}
        with self._lock:
            states = list(self._tenants.values())
        for state in states:
            if state.evaluator is not None:
                try:
                    out[state.tenant_id] = \
                        state.evaluator.table_fingerprint()
                except Exception:  # noqa: BLE001
                    pass
        return out

    def stats(self) -> dict:
        with self._lock:
            built = sum(
                1 for s in self._tenants.values()
                if s.evaluator is not None
            )
            out = {
                "tenant_count": len(self._tenants),
                "evaluators_built": built,
                **dict(self._stats),
            }
        out["size_classes"] = self.class_histogram()
        out["compiled_programs"] = self.compiled_program_count()
        out["epoch_top_k"] = self.epochs()
        out["epoch_digest"] = self.epoch_digest()
        return out

    def shutdown(self) -> None:
        with self._lock:
            states = list(self._tenants.values())
        for state in states:
            if state.evaluator is not None:
                try:
                    state.evaluator.shutdown()
                except Exception:  # noqa: BLE001
                    pass


def from_config(cfg, urns, logger=None, telemetry=None,
                decision_cache=None, store=None,
                observability=None) -> Optional[TenantRegistry]:
    """Build a TenantRegistry from the ``tenancy`` config block; None
    when disabled (the default — single-tenant path byte-identical)."""
    block = cfg.get("tenancy") if hasattr(cfg, "get") else None
    block = block or {}
    if not block.get("enabled", False):
        return None
    return TenantRegistry(
        urns,
        logger=logger,
        telemetry=telemetry,
        decision_cache=decision_cache,
        backend=block.get("backend") or (
            cfg.get("evaluator:backend", "hybrid")
            if hasattr(cfg, "get") else "hybrid"
        ),
        store=store,
        observability=observability,
        max_tenants=block.get("max_tenants", 100_000),
    )
