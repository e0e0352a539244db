"""Pod-sharded policy tree: set-axis model partitioning with shard-local
delta patching.

parallel/rule_shard.py shards the RULE axis — good for a handful of huge
policies, but every shard still replicates the full set/policy metadata
and the partition must be rebuilt from scratch on any mutation (it is not
delta-patchable).  This module shards the SET axis of ONE pod-level
capacity-bucketed compile (ops/delta.py): shard ``d`` owns the padded set
slots ``[d*S_loc, (d+1)*S_loc)`` with a compacted per-shard target
subtable, so a 1M-rule tree that cannot fit one chip's capacity splits
into per-shard tables that do, while the encoder, candidate index,
decision cache and reverse kernel keep operating on the single pod-level
compiled tree (one entity vocab, one condition list, one request
encoding).

Why the set axis: the delta patcher relowers affected sets IN PLACE at
stable slots (``apply_events`` never moves a set's slot, and target rows
are owned per set via ``target_owners``), so slot ownership is stable
under churn and a CRUD event touching one set re-slices exactly one
shard.  The unaffected shards' host tables are reused BY REFERENCE —
byte-identical, as tests/test_pod_shard.py asserts — and the jitted shard_map program is registered in the evaluator's
shared-jit table, so an in-capacity patch costs ZERO new XLA compiles on
any shard.

Cross-shard combining (the lattice reduce, proof sketch in
docs/SHARDING.md): whole sets are shard-local, so every order-sensitive
combining algorithm (first-DENY / first-PERMIT / first-applicable per
policy, same per set) runs inside one shard via the shared stage helpers
(ops/kernel.py `_policy_contributions` / `_per_set_effects`).  Only two
merges cross the ``model`` axis, and both are min/max reductions over
packed positional keys — associative, commutative, and order-safe
because globally unique positions occupy the high bits:

* last-set-wins: ``pmax`` over ``pack_rule_key(global_set_pos + 1,
  set_eff, set_cach)`` — max key == max position == last contributing
  set, payload rides in the low 3 bits;
* condition aborts: ``pmin`` over global flat rule order finds the first
  aborting rule; the unique owning shard broadcasts its code/cacheable
  via ``pmax`` (same scheme as rule_shard).

Only O(1) ints per request cross the ICI — never per-set or per-rule
data.

Distributed bring-up: on a real pod each process contributes its local
devices to the ``model`` axis after ``maybe_initialize_distributed``
(parallel/cluster.py, behind ``cluster:distributed``); off-chip the
LocalCluster drives the same code over virtual CPU devices
(``--xla_force_host_platform_device_count``).  See docs/SHARDING.md.
"""

from __future__ import annotations

from dataclasses import dataclass
from hashlib import blake2b

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.compile import CompiledPolicies
from ..ops.delta import _bucket, _POL_FILLS, _RULE_FILLS, _SET_FILLS
from ..ops.encode import RequestBatch
from ..ops.kernel import (
    BIG,
    _match_targets,
    _per_set_effects,
    _policy_contributions,
    _policy_gates,
    _rule_predicates,
    pack_rule_key,
    tree_needs_rel,
    unpack_rule_key,
)
from .mesh import pad_batch
from .rule_shard import _T_FIELDS

# rows x target-table rows one dispatch may hold: the per-row matcher's
# working set grows as ~1.3 KB per (row, target row) pair on v5e
# (memory_analysis of the compiled program, PR 21), so at most 2^20 pairs
# (~1.4 GB) per dispatch.  Unchunked, 4096 rows on a 10k-rule shard
# already asked the TPU compiler for one 64 GiB buffer; a 100k-rule shard
# (t_cap 2^17) runs 8 rows at a time
MAX_ROW_TARGET_PAIRS = 1 << 20

# fields sliced along the leading set axis; target-table fields
# (_T_FIELDS) are compacted per shard; acl_consts is replicated;
# hrv_role/hrv_scope are host-only (the encoder's owner bitplanes carry
# the HR verdicts, see rule_shard) and never reach the device
_SET_AXIS_FIELDS = tuple(_SET_FILLS) + tuple(_POL_FILLS) + tuple(_RULE_FILLS)
_FILL_BY_NAME = {**_SET_FILLS, **_POL_FILLS, **_RULE_FILLS}


@dataclass
class ShardTables:
    """One shard's host-side tables: set-axis slices at ``s_local`` slots
    plus the compacted target subtable at ``t_live`` rows (padded to the
    kernel's sticky t-bucket only at stack time, so the fingerprint is
    invariant under pod-wide bucket growth)."""

    index: int
    s_lo: int                      # first owned global set slot
    arrays: dict                   # name -> np.ndarray
    t_live: int                    # compacted target rows (pre-padding)
    fingerprint: str               # blake2b-16 over the live tables


def _shard_fingerprint(arrays: dict, s_lo: int, t_live: int) -> str:
    h = blake2b(digest_size=16)
    h.update(f"s_lo={s_lo};t_live={t_live};".encode())
    for name in sorted(arrays):
        v = np.ascontiguousarray(arrays[name])
        h.update(name.encode())
        h.update(str(v.dtype).encode())
        h.update(str(v.shape).encode())
        h.update(v.tobytes())
    return h.hexdigest()


def slice_shard(compiled: CompiledPolicies, index: int, s_local: int
                ) -> ShardTables:
    """Slice shard ``index``'s set slots out of the pod tables and compact
    its target subtable: a synthetic all-zeros row at local index 0 backs
    every "no target" reference, followed by only the rows this shard's
    sets/policies/rules actually reference.  The blank anchor matters for
    byte-identity: pod target rows are ordinary allocatable slots that
    in-place patches rewrite, so anchoring padding on pod row 0 would
    leak another shard's churn into this shard's bytes.  Pure per-shard:
    re-slicing one shard after a patch cannot observe any other shard's
    content."""
    a = compiled.arrays
    S = a["set_valid"].shape[0]
    lo = min(index * s_local, S)
    hi = min(lo + s_local, S)
    sl: dict[str, np.ndarray] = {}
    for name in _SET_AXIS_FIELDS:
        chunk = a[name][lo:hi]
        if hi - lo < s_local:  # pad the tail shard with inert slots
            pad_shape = (s_local - (hi - lo),) + chunk.shape[1:]
            fill = _FILL_BY_NAME[name]
            chunk = np.concatenate(
                [chunk, np.full(pad_shape, fill, chunk.dtype)], axis=0
            )
        sl[name] = np.ascontiguousarray(chunk)

    needed: set[int] = set()
    needed |= set(np.unique(sl["rule_target"][sl["rule_has_target"]]).tolist())
    needed |= set(np.unique(sl["pol_target"][sl["pol_has_target"]]).tolist())
    needed |= set(np.unique(sl["set_target"][sl["set_has_target"]]).tolist())
    order = sorted(needed)
    # remap defaults to 0 = the blank anchor, so dangling target indexes
    # on has_target=False entries can never alias a live local row
    remap = np.zeros(a["t_role"].shape[0], np.int64)
    for new, old in enumerate(order):
        remap[old] = new + 1
    for name in _T_FIELDS:
        rows = a[name][order]
        blank = np.zeros((1,) + rows.shape[1:], rows.dtype)
        sl[name] = np.ascontiguousarray(
            np.concatenate([blank, rows], axis=0)
        )
    for kind in ("rule", "pol", "set"):
        sl[f"{kind}_target"] = np.where(
            sl[f"{kind}_has_target"],
            remap[sl[f"{kind}_target"]],
            0,
        ).astype(np.int32)
    sl["acl_consts"] = np.asarray(a["acl_consts"])

    t_live = len(order) + 1
    return ShardTables(
        index=index, s_lo=lo, arrays=sl, t_live=t_live,
        fingerprint=_shard_fingerprint(sl, lo, t_live),
    )


def partition_sets(compiled: CompiledPolicies, n_shards: int
                   ) -> tuple[list[ShardTables], int]:
    """Split the (capacity-padded) set axis into ``n_shards`` contiguous
    chunks of ``s_local`` slots each; returns (shards, s_local)."""
    S = compiled.arrays["set_valid"].shape[0]
    s_local = -(-S // n_shards)
    return (
        [slice_shard(compiled, d, s_local) for d in range(n_shards)],
        s_local,
    )


def _evaluate_set_chunk(c, r, s_offset, model_axis, explain: bool = False,
                        with_rel: bool = False):
    """Per-device evaluation of one SET chunk for one request.  Stages A-F
    run locally through the shared single-device helpers (whole sets are
    shard-local, so every combining algorithm is local); only the
    last-set-wins tail and the abort-first scan reduce across ``model``
    via packed positional keys (order-safe: unique positions in the high
    bits, payload in the low bits).

    ``explain=True`` appends the packed provenance output (ops/kernel
    _combine_and_decide encoding, GLOBAL positions).  The winning set's
    global position already rides in ``k_win``'s high bits, so the unique
    owning shard recovers the full provenance locally and broadcasts the
    packed code with one extra ``pmax`` (codes are >= 1 whenever any set
    contributed; non-owners contribute 0)."""
    m = _match_targets(c, r, with_rel=with_rel)
    reached, acl_rule, has_cond, cond_t, cond_a, cond_c = _rule_predicates(
        c, r, m
    )
    pol_gate, set_gate, pol_subject = _policy_gates(c, r, m)
    if explain:
        (contrib_present, contrib_eff, contrib_cach, abort_rule,
         sel_c, no_rules_contrib) = _policy_contributions(
            c, reached, acl_rule, has_cond, cond_t, cond_a,
            pol_gate, set_gate, pol_subject, explain=True,
        )
        set_eff, set_cach, set_any, s_sel_c = _per_set_effects(
            c, contrib_present, contrib_eff, contrib_cach, explain=True
        )
    else:
        contrib_present, contrib_eff, contrib_cach, abort_rule = (
            _policy_contributions(
                c, reached, acl_rule, has_cond, cond_t, cond_a,
                pol_gate, set_gate, pol_subject,
            )
        )
        set_eff, set_cach, set_any = _per_set_effects(
            c, contrib_present, contrib_eff, contrib_cach
        )

    # ---- last-set-wins across shards: pmax over packed positional keys
    S_l = set_eff.shape[0]
    gpos = s_offset + jnp.arange(S_l)
    k_set = jnp.where(
        set_any,
        pack_rule_key(gpos + 1, set_eff, set_cach.astype(jnp.int32) & 1),
        0,
    )
    k_win = jax.lax.pmax(jnp.max(k_set), model_axis)
    have = k_win > 0
    eff_w, cach_w = unpack_rule_key(k_win)
    decision = jnp.where(have, eff_w, 0)
    cacheable = jnp.where(have, cach_w.astype(jnp.int32), -1)
    status = jnp.int32(200)

    # ---- condition aborts: first in GLOBAL flat rule order (pmin finds
    # the winner; the unique owning shard broadcasts code/cacheable)
    KPn, KRn = abort_rule.shape[1], abort_rule.shape[2]
    flat_order = (
        gpos[:, None, None] * (KPn * KRn)
        + jnp.arange(KPn)[None, :, None] * KRn
        + jnp.arange(KRn)[None, None, :]
    )
    local_abort_pos = jnp.min(jnp.where(abort_rule, flat_order, BIG))
    abort_pos = jax.lax.pmin(local_abort_pos, model_axis)
    has_abort = abort_pos < BIG
    i_own = (local_abort_pos == abort_pos) & has_abort
    abort_flat = jnp.argmin(jnp.where(abort_rule, flat_order, BIG))
    code_local = jnp.where(
        i_own, jnp.take(cond_c.reshape(-1), abort_flat), 0
    )
    cach_local = jnp.where(
        i_own,
        jnp.take(c["rule_cacheable_raw"].reshape(-1), abort_flat).astype(
            jnp.int32
        ) + 1,
        0,
    )
    abort_code = jax.lax.pmax(code_local, model_axis)
    abort_cach = jax.lax.pmax(cach_local, model_axis) - 1

    decision = jnp.where(has_abort, 2, decision)
    cacheable = jnp.where(has_abort, abort_cach, cacheable)
    status = jnp.where(has_abort, abort_code, status)
    if not explain:
        return (
            decision.astype(jnp.int32),
            cacheable.astype(jnp.int32),
            status.astype(jnp.int32),
        )

    # ---- explain recovery: the shard owning the winning set packs the
    # provenance code locally; one pmax broadcasts it (codes >= 1 when
    # any set contributed, so 0 from non-owners never wins)
    win_s_local = jnp.argmax(k_set)
    s_own = (jnp.max(k_set) == k_win) & have
    win_flat = (s_offset + win_s_local) * KPn + jnp.take(s_sel_c, win_s_local)
    win_kr = jnp.take(
        sel_c.reshape(-1),
        win_s_local * KPn + jnp.take(s_sel_c, win_s_local),
    )
    no_rules_win = jnp.take(
        no_rules_contrib.reshape(-1),
        win_s_local * KPn + jnp.take(s_sel_c, win_s_local),
    )
    rule_pos = win_flat * KRn + win_kr
    expl_local = jnp.where(
        s_own,
        jnp.where(no_rules_win, (win_flat << 2) | 2, (rule_pos << 2) | 1),
        0,
    )
    expl = jax.lax.pmax(expl_local.astype(jnp.int32), model_axis)
    expl = jnp.where(has_abort, (abort_pos << 2) | 3, expl)
    return (
        decision.astype(jnp.int32),
        cacheable.astype(jnp.int32),
        status.astype(jnp.int32),
        expl.astype(jnp.int32),
    )


class PodShardedKernel:
    """Set-axis sharded kernel over a 2-axis mesh: requests shard over
    ``data``, the pod-level compiled set slots over ``model``; per-shard
    compacted target subtables; ICI traffic is O(1) packed keys.

    Unlike RuleShardedKernel this kernel IS delta-patchable: ``patched``
    consumes ``apply_events``'s ``patched_slots`` and re-slices only the
    owning shards, so the evaluator keeps the incremental path enabled
    when ``parallel:pod_shards`` is configured."""

    supports_delta = True
    supports_shard_patch = True

    def __init__(self, compiled: CompiledPolicies, mesh: Mesh,
                 data_axis: str = "data", model_axis: str = "model",
                 shared_jits: dict | None = None, prev_t_cap: int = 0,
                 explain: bool = False,
                 _shards: list[ShardTables] | None = None,
                 _applied: list[int] | None = None):
        if not compiled.supported:
            raise ValueError(
                f"policy tree unsupported: {compiled.unsupported_reason}"
            )
        self.compiled = compiled
        self.mesh = mesh
        self.data_axis = data_axis
        self.model_axis = model_axis
        self.n_data = mesh.shape[data_axis]
        self.n_shards = mesh.shape[model_axis]
        self._shared = shared_jits if shared_jits is not None else {}
        self.explain = bool(explain)
        self.explain_strides = (compiled.KP, compiled.KR)

        if _shards is None:
            self.shards, self.s_local = partition_sets(
                compiled, self.n_shards
            )
        else:
            self.shards = _shards
            self.s_local = _shards[0].arrays["set_valid"].shape[0]
        # sticky per-pod target-row bucket (pow2, 1.25x headroom): patches
        # that stay inside it keep every stacked shape stable, so the
        # shared jit is reused and the patch costs zero new XLA compiles
        self.t_cap = max(
            prev_t_cap,
            _bucket(max(sh.t_live for sh in self.shards), 1.25, 8),
        )
        # per-shard applied-patch watermark since the last full partition
        # (surfaced through shard_identity for the convergence oracle)
        self.applied = list(_applied) if _applied is not None else (
            [0] * self.n_shards
        )

        self._place()
        self._run = self._ensure_jit()

    # ------------------------------------------------------------ placement
    def _place(self) -> None:
        spec = NamedSharding(self.mesh, P(self.model_axis))
        stacked: dict[str, np.ndarray] = {}
        for name in self.shards[0].arrays:
            parts = []
            for sh in self.shards:
                v = sh.arrays[name]
                if name in _T_FIELDS and v.shape[0] < self.t_cap:
                    # pad the compacted subtable to the sticky bucket by
                    # repeating row 0 (inert: no live index reaches pads)
                    reps = np.repeat(v[:1], self.t_cap - v.shape[0], axis=0)
                    v = np.concatenate([v, reps], axis=0)
                parts.append(v)
            stacked[name] = np.stack(parts)
        self._c = {
            k: jax.device_put(jnp.asarray(v), spec)
            for k, v in stacked.items()
        }
        self._offsets = jax.device_put(
            jnp.asarray(
                np.array([sh.s_lo for sh in self.shards], np.int32)
            ),
            spec,
        )

    def _ensure_jit(self):
        """The jitted shard_map program, registered under the shared-jit
        table (srv/evaluator.py) so patched/recompiled kernels with
        identical table shapes reuse the existing executables."""
        with_rel = tree_needs_rel(self.compiled.arrays)
        key = ("pod", self.model_axis, self.n_shards, with_rel)
        if self.explain:
            key = key + ("explain",)
        jitted = self._shared.get(key)
        if jitted is not None:
            return jitted

        model_axis = self.model_axis
        explain = self.explain
        c_specs = {k: P(model_axis) for k in self._c}

        def run(c, offsets, batch_arrays, rgx_set, pfx_neq):
            c_local = {k: v[0] for k, v in c.items()}
            s_offset = offsets[0]

            def one(ra):
                rr = {**ra, "rgx_set": rgx_set, "pfx_neq": pfx_neq}
                return _evaluate_set_chunk(
                    c_local, rr, s_offset, model_axis, explain=explain,
                    with_rel=with_rel,
                )

            return jax.vmap(one)(batch_arrays)

        run.__name__ = "acs_pod_shard"  # compile counts, traces
        # replication checking off: the per-device values stay
        # unreplicated until the packed-key lattice reduce merges them
        wrapped = jax.shard_map(
            run,
            mesh=self.mesh,
            in_specs=(c_specs, P(model_axis), P(self.data_axis), P(), P()),
            out_specs=(P(self.data_axis),) * (4 if explain else 3),
            check_vma=False,
        )
        jitted = jax.jit(wrapped)
        self._shared[key] = jitted
        return jitted

    # ------------------------------------------------------- shard-local patch
    def patched(self, new_compiled: CompiledPolicies,
                patched_slots: list[int]) -> "PodShardedKernel":
        """Shard-local relower: re-slice ONLY the shards owning
        ``patched_slots`` (apply_events stats), reusing every other
        shard's host tables by reference — their bytes cannot have
        changed, because the delta patcher rewrites only rows owned by
        the affected sets (ops/delta.py ``target_owners`` ledger) and set
        slots never move under patch.  The shared jit is reused, so an
        in-capacity patch costs zero new XLA compiles on any shard."""
        owners = sorted({
            min(int(s) // self.s_local, self.n_shards - 1)
            for s in patched_slots
        })
        shards = list(self.shards)
        for d in owners:
            shards[d] = slice_shard(new_compiled, d, self.s_local)
        applied = list(self.applied)
        for d in owners:
            applied[d] += 1
        return PodShardedKernel(
            new_compiled, self.mesh,
            data_axis=self.data_axis, model_axis=self.model_axis,
            shared_jits=self._shared, prev_t_cap=self.t_cap,
            explain=self.explain, _shards=shards, _applied=applied,
        )

    # ------------------------------------------------------------- identity
    def pod_fingerprint(self) -> str:
        """The combined pod fingerprint: a digest over the per-shard
        fingerprints in shard order (what the router/chaos convergence
        oracle compares across replicas)."""
        h = blake2b(digest_size=16)
        for sh in self.shards:
            h.update(sh.fingerprint.encode())
        return h.hexdigest()

    def shard_identity(self) -> dict:
        return {
            "n_shards": self.n_shards,
            "s_local": self.s_local,
            "t_bucket": self.t_cap,
            "pod_fingerprint": self.pod_fingerprint(),
            "shards": [
                {
                    "index": sh.index,
                    "fingerprint": sh.fingerprint,
                    "set_slots": [sh.s_lo, sh.s_lo + self.s_local],
                    "t_rows_live": sh.t_live,
                    "applied_patches": self.applied[sh.index],
                }
                for sh in self.shards
            ],
        }

    # ------------------------------------------------------------- evaluate
    def evaluate(self, batch: RequestBatch):
        return self.evaluate_async(batch)()

    def evaluate_async(self, batch: RequestBatch):
        """Dispatch without blocking (returns the materialize callable —
        the pod-sharded leg of the depth-N serving pipeline).  Batch and
        regex-matrix axes pad to power-of-two buckets divisible by the
        data-axis size, same scheme as the other kernels."""
        # failpoint (srv/faults.py): host-side dispatch boundary — fires
        # before any device work, so the lowered program is unchanged
        from ..srv.faults import REGISTRY as _faults

        _faults.fire("device.dispatch")
        arrays = dict(batch.arrays)
        arrays["cond_true"] = np.ascontiguousarray(batch.cond_true.T)
        arrays["cond_abort"] = np.ascontiguousarray(batch.cond_abort.T)
        arrays["cond_code"] = np.ascontiguousarray(batch.cond_code.T)

        from ..ops.kernel import pad_cols, pow2_bucket

        per_shard = -(-batch.B // self.n_data)
        bucket = self.n_data * pow2_bucket(per_shard)
        arrays, _ = pad_batch(arrays, batch.B, bucket)
        e_bucket = pow2_bucket(batch.rgx_set.shape[1])
        rgx_set = jnp.asarray(pad_cols(batch.rgx_set, e_bucket))
        pfx_neq = jnp.asarray(pad_cols(batch.pfx_neq, e_bucket))
        # row chunks keep the per-row matcher's [rows, t_cap] working set
        # inside HBM: both sizes are n_data x a power of two (>= 8), so
        # the chunk divides the bucket and compiles once
        pairs = max(1, MAX_ROW_TARGET_PAIRS // self.t_cap)
        chunk = min(bucket, self.n_data * max(8, 1 << (pairs.bit_length() - 1)))
        outs = [
            self._run(
                self._c,
                self._offsets,
                {k: jnp.asarray(v[lo:lo + chunk]) for k, v in arrays.items()},
                rgx_set,
                pfx_neq,
            )
            for lo in range(0, bucket, chunk)
        ]

        def materialize():
            _faults.fire("device.materialize")
            return tuple(
                np.concatenate([np.asarray(out[i]) for out in outs])[: batch.B]
                for i in range(len(outs[0]))
            )

        return materialize
