"""The batched decision kernel: one vmapped, jitted function computing
isAllowed decisions for a request batch against the compiled policy tensors.

Everything the reference evaluates with nested loops and mutable flags
(reference: src/core/accessController.ts:88-324) is expressed here as masked
boolean algebra over padded tensors:

- target matching over the flat target table ``[T]`` in PERMIT/DENY effect
  variants (the property gates are effect-asymmetric, reference: :578-588,
  644-647), exact and regex modes (regex results come from host-computed
  ``[W, E]`` matrices);
- positional property relevance via cumulative/sticky entity-match state
  per entity run (reference: :501-525 state machine);
- hierarchical-scope checks per target row (direct owner match + flattened
  HR-closure membership, sticky collection scan, reference:
  hierarchicalScope.ts:54-258);
- combining algorithms as masked position reductions along the rule/policy
  axes (first-DENY / first-PERMIT / first / last);
- the exact-match break index and its carried ``policyEffect`` (reference:
  :136-157), the multi-entity recheck (:429-463), condition aborts in flat
  rule order (:240-270) and last-set-wins decision assembly (:293-295).
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .compile import CompiledPolicies
from .encode import RequestBatch
from .interner import ABSENT

# plain numpy scalar (not jnp): creating a device array at module scope
# would initialize the jax backend as an import side effect, and the
# importing process would take the chip even on host-only code paths that
# never run the kernel
BIG = np.int32(1 << 30)

# Policy trees whose compiled tensors fit under this size are baked into the
# jitted program as XLA constants (the compiler pre-folds every
# policy-dependent subexpression once); larger trees are passed as
# device-resident arguments, since embedded constants make XLA spend
# unbounded time constant-folding and are re-embedded per batch bucket.
CONSTANT_BAKE_LIMIT_BYTES = 1 << 20


def bake_policy_constants(compiled: CompiledPolicies) -> bool:
    policy_bytes = sum(np.asarray(v).nbytes for v in compiled.arrays.values())
    return policy_bytes <= CONSTANT_BAKE_LIMIT_BYTES


def tree_needs_hr(arrays: dict) -> bool:
    """Static gate for stage B: only target rows carrying BOTH subjects
    and a scoping entity can fail the HR check (hr_trivial covers every
    other row), so trees without such rows skip the owner-check tensors
    entirely (see _match_targets with_hr)."""
    return bool(
        (np.asarray(arrays["t_has_scoping"])
         & (np.asarray(arrays["t_n_subjects"]) > 0)).any()
    )


def tree_needs_rel(arrays: dict) -> bool:
    """Static gate for the relation-plane fold (ReBAC, ops/relation.py):
    only target rows carrying a relation-path requirement can fail it, so
    relation-free trees keep their lowered programs byte-identical (the
    flag is Python-level, like with_hr)."""
    t = arrays.get("t_rel_idx")
    return t is not None and bool((np.asarray(t) >= 0).any())


def pow2_bucket(n: int, floor: int = 8) -> int:
    """Smallest power of two >= n (min `floor`): the shared padding bucket
    used by every kernel entry so varying batch/entity sizes reuse a
    handful of compiled programs instead of one XLA compile per size."""
    return max(floor, 1 << max(n - 1, 1).bit_length())


def half_pow2_bucket(n: int, floor: int = 8) -> int:
    """Smallest value >= n of the form 2^k or 1.5 * 2^k (min ``floor``):
    twice the bucket density of pow2_bucket, capping padding waste at 33%
    instead of 100% while still bounding distinct compile shapes."""
    p = pow2_bucket(n, floor)
    return p - p // 4 if n <= p - p // 4 and p - p // 4 >= floor else p


def batch_has_acl(batch) -> bool:
    """Whether ``batch`` carries ACL pairs: the dense kernel then runs
    ``acs_dense_acl`` (the full verifyACL pass), else ``acs_dense``."""
    return bool((np.asarray(batch.arrays["r_acl_ent"]) >= 0).any())


# each cap of a request batch: (array, axis) holding it; OWN and REL are
# the owner and relation bitplane words
_CAP_AXES = {
    "NR": ("r_ent_vals", 1), "NI": ("r_inst_id", 1), "NP": ("r_prop_vals", 1),
    "NSUB": ("r_sub_ids", 1), "NACT": ("r_act_ids", 1), "NOP": ("r_op_vals", 1),
    "NOWN": ("r_inst_owner_ent", 2), "NRA": ("r_ra3", 1), "NHR": ("r_hr", 1),
    "NROLE": ("r_roles", 1), "NACLE": ("r_acl_ent", 1),
    "NACLI": ("r_acl_inst", 2), "NHRR": ("r_hr_roles", 1),
    "OWN": ("r_own_bits", 1), "REL": ("r_rel_bits", 1),
}


def dispatch_shape_key(prefix: str, batch, targets: int) -> str:
    """``<prefix>@b<rows>.t<targets>.NR<n>...``: the shape a dispatch of
    ``batch`` runs at (rows padded to their bucket, the tree's target
    rows, every cap), as a path-counter key a byte count of the program
    can be made from."""
    rows = np.shape(batch.arrays["r_acl_short"])[0]
    shape = [f"b{pow2_bucket(rows)}", f"t{targets}"]
    for cap, (name, axis) in _CAP_AXES.items():
        shape.append(f"{cap}{np.shape(batch.arrays[name])[axis]}")
    return f"{prefix}@{'.'.join(shape)}"


def pack_rule_key(pos, effect, cacheable):
    """Combine-reduction key: rule position in the high bits, (effect,
    cacheable) payload in the low 3, so position min/max reductions carry
    the selected rule's effect and cacheable bits with them and no
    post-reduction gather is needed (a [S, KP]-at-[S, M] take_along_axis
    here was ~90% of the 100k-rule stress batch on TPU — round-5 profile).
    Shared with the rule-sharded kernel's packed cross-device reductions
    (parallel/rule_shard.py); position ordering is preserved because
    positions are distinct and occupy the high bits."""
    return (pos << 3) | (effect << 1) | cacheable.astype(jnp.int32)


def unpack_rule_key(key):
    """(effect, cacheable) payload of a pack_rule_key winner."""
    return (key >> 1) & 3, key & 1


def pad_cols(a: np.ndarray, width: int) -> np.ndarray:
    """Zero-pad the second axis out to `width` (conditions are [n_cond, B];
    regex matrices are [W, E])."""
    a = np.asarray(a)
    if a.shape[1] == width:
        return a
    fill = np.zeros(a.shape[:1] + (width - a.shape[1],), a.dtype)
    return np.concatenate([a, fill], axis=1)


def lead_padding(batch):
    """Shared batch-axis padding contract for every kernel entry: returns
    (b, bucket, e_bucket, pad_lead) where ``pad_lead`` zero-fills the
    leading axis out to the power-of-two bucket.  Rows are independent
    under vmap, so zero-padded rows cannot affect real rows."""
    b = batch.arrays[next(iter(batch.arrays))].shape[0]
    bucket = pow2_bucket(b)

    def pad_lead(a: np.ndarray) -> np.ndarray:
        a = np.asarray(a)
        if a.shape[0] == bucket:
            return a
        fill = np.zeros((bucket - a.shape[0],) + a.shape[1:], a.dtype)
        return np.concatenate([a, fill], axis=0)

    e_bucket = pow2_bucket(batch.rgx_set.shape[1])
    return b, bucket, e_bucket, pad_lead


def _pairs_subset(rule_ids, rule_vals, req_ids, req_vals):
    """Every valid rule (id, value) pair appears among the request pairs
    (reference: attributesMatch, accessController.ts:681-699)."""
    rule_valid = rule_ids >= 0
    # [K_rule, K_req] equality
    eq = (rule_ids[:, None] == req_ids[None, :]) & (
        rule_vals[:, None] == req_vals[None, :]
    ) & (req_ids[None, :] >= 0)
    return jnp.all(~rule_valid | eq.any(axis=1))


def _member(needle, haystack):
    """needle in haystack (1-D), ignoring ABSENT padding."""
    return jnp.any((haystack == needle) & (haystack >= 0))


def _action_kind(c: dict, r: dict):
    """0 = other, 1 = create, 2 = read/modify/delete, judged on the FIRST
    action attribute only (reference: verifyACL.ts:138-144, 177-181)."""
    k = c["acl_consts"]
    id0, val0 = r["r_act_ids"][0], r["r_act_vals"][0]
    is_action = id0 == k[2]
    create = is_action & (val0 == k[3])
    rmd = is_action & ((val0 == k[4]) | (val0 == k[5]) | (val0 == k[6]))
    return jnp.where(create, 1, jnp.where(rmd, 2, 0))


def _acl_pass(c: dict, r: dict, with_acl: bool):
    """Stage B2: tensorized verifyACL per target row -> [T] bool
    (reference: verifyACL.ts:11-251).

    The request-side encoder pre-reduces the resource walk to
    ``r_acl_short`` (0 pairs / 1 early all-clear / 2 malformed-fail) and
    (scoping entity, instances) pair arrays; the rule-dependent parts —
    skipACL, the rule's scoped roles, the create-path role scan and the
    read/modify/delete membership — run here. The create path's sequential
    break/continue semantics (validated-instance accumulation across roles,
    the carried valid flag across scoping entities, :146-175) are
    reproduced exactly with a lax.scan over the padded (role, instance)
    grid; read/modify/delete (:177-200) is a pure masked reduction.

    ``with_acl=False`` compiles only the no-pair fast path (exact whenever
    the batch carries no ACL pairs, which the kernel entry checks)."""
    k = c["acl_consts"]
    T = c["t_role"].shape[0]
    skip = c["t_skip_acl"]  # [T]
    short = r["r_acl_short"]
    kind = _action_kind(c, r)

    if not with_acl:
        # no-pair fast path: early all-clear passes; otherwise role
        # associations must exist and the first action must be CRUD
        # (create/rmd with an empty entity dict both return True,
        # :147-148, 184-185; any other action falls through to False,
        # :250). short==2 (malformed) correctly yields False here too.
        return skip | (short == 1) | (
            (short == 0) & (r["r_n_ra"] > 0) & (kind > 0)
        )

    ents = r["r_acl_ent"]        # [NACLE]
    insts = r["r_acl_inst"]      # [NACLE, NACLI]
    ev = ents >= 0
    iv = insts >= 0
    NACLE, NACLI = insts.shape
    has_ents = ev.any()

    # rule's scoped roles: subject attr pairs whose id is the role urn
    scoped_mask = (c["t_sub_ids"] == k[0]) & (c["t_sub_vals"] >= 0)  # [T,KS]
    user_e = ev & (ents == k[1])  # [NACLE]

    # subject_scoped existence per entity: any role association (role,
    # scoping-entity) pair with a rule-scoped role (:94-112, 156-157)
    ra2 = r["r_ra2"]
    ra2v = ra2[:, 1] >= 0
    ra2_scoped = (
        (ra2[None, None, :, 0] == c["t_sub_vals"][:, :, None])
        & scoped_mask[:, :, None]
    ).any(axis=1)  # [T, NRA]
    subj_exists = (
        ra2_scoped[:, None, :]
        & (ents[None, :, None] == ra2[None, None, :, 1])
        & ra2v[None, None, :]
    ).any(axis=2)  # [T, NACLE]

    # ---- read/modify/delete: >=1 subject scope instance (or the subject
    # id itself for user-entity ACLs) appears in the ACL (:177-200)
    ra3 = r["r_ra3"]
    ra3v = ra3[:, 1] >= 0
    ra3_scoped = (
        (ra3[None, None, :, 0] == c["t_sub_vals"][:, :, None])
        & scoped_mask[:, :, None]
    ).any(axis=1)  # [T, NRA]
    inst_has = (
        (insts[:, :, None] == ra3[None, None, :, 2]) & iv[:, :, None]
    ).any(axis=1)  # [NACLE, NRA] instance value present in entity's ACL
    rmd_sub = (
        ra3_scoped[:, None, :]
        & (ents[None, :, None] == ra3[None, None, :, 1])
        & inst_has[None, :, :]
        & ra3v[None, None, :]
    ).any(axis=2)  # [T, NACLE]
    subj_in = ((insts == r["r_subject_id"]) & iv).any(axis=1)  # [NACLE]
    rmd_ok = (
        ev[None, :] & ((user_e & subj_in)[None, :] | rmd_sub)
    ).any(axis=1)  # [T]
    rmd_res = ~has_ents | rmd_ok

    # ---- create: every target ACL instance inside the subject's HR org
    # scopes for a shared role (:141-175), exact sequential semantics
    hr_roles = r["r_hr_roles"]  # [NHRR]
    NHRR = hr_roles.shape[0]
    hrr_v = hr_roles >= 0
    role_scoped = (
        (hr_roles[None, None, :] == c["t_sub_vals"][:, :, None])
        & scoped_mask[:, :, None]
    ).any(axis=1) & hrr_v[None, :]  # [T, NHRR]
    ahr = r["r_acl_hr"]  # [NHR, 2] verifyACL flatten (role, org)
    ahrv = ahr[:, 1] >= 0
    # eligible_org_scopes membership per (entity, instance, hr role)
    elig = (
        (insts[:, :, None, None] == ahr[None, None, None, :, 1])
        & (hr_roles[None, None, :, None] == ahr[None, None, None, :, 0])
        & ahrv[None, None, None, :]
    ).any(axis=3) & iv[:, :, None]  # [NACLE, NACLI, NHRR]
    same_val = (
        (insts[:, :, None] == insts[:, None, :]) & iv[:, None, :]
    )  # [NACLE, NACLI(i), NACLI(j)]

    # scan over the flattened (role, instance) grid; carry the validated
    # instance set (persists across roles within an entity), the per-role
    # broken flag (inner-loop break, :169-171) and the last set/fail event
    steps = NHRR * NACLI
    r_of_s = np.arange(steps) // NACLI
    i_of_s = np.arange(steps) % NACLI
    xs = (
        jnp.asarray(np.eye(NACLI, dtype=bool)[i_of_s]),
        # [steps, NACLI] one-hot of the instance position
        elig[:, i_of_s, r_of_s].T,
        # [steps, NACLE] eligibility of (entity, current instance, role)
        jnp.moveaxis(same_val[:, i_of_s, :], 1, 0),
        # [steps, NACLE, NACLI] value-equality row of the current instance
        iv[:, i_of_s].T,                    # [steps, NACLE] instance valid
        jnp.asarray(i_of_s == 0),           # [steps] role-start reset
        jnp.asarray(r_of_s, np.int32),      # [steps] role index
    )

    def step(carry, x):
        validated, broken, last_ev = carry
        onehot, elig_cur, samev_cur, iv_cur, at_start, role_idx = x
        rsc = role_scoped[:, role_idx]  # [T]
        broken = broken & ~at_start
        active = (
            rsc[:, None] & iv_cur[None, :] & ~broken
        )  # [T, NACLE]
        in_validated = (validated & samev_cur[None, :, :]).any(axis=2)
        hit = active & elig_cur[None, :]
        fail = active & ~elig_cur[None, :] & ~in_validated
        validated = validated | (hit[:, :, None] & onehot[None, None, :])
        broken = broken | fail
        last_ev = jnp.where(hit, 1, jnp.where(fail, 2, last_ev))
        return (validated, broken, last_ev), None

    init = (
        jnp.zeros((T, NACLE, NACLI), bool),
        jnp.zeros((T, NACLE), bool),
        jnp.zeros((T, NACLE), jnp.int32),
    )
    (validated, broken, last_ev), _ = jax.lax.scan(step, init, xs)
    ev_any = last_ev > 0           # [T, NACLE]
    ev_true = last_ev == 1

    # compose entities in order with the carried valid flag (:146-175);
    # user-entity ACLs set valid and skip the per-entity check (:150-153)
    v = jnp.zeros((T,), bool)
    alive = jnp.ones((T,), bool)
    for e in range(NACLE):
        is_real = ev[e]
        is_user = user_e[e]
        v_out = jnp.where(ev_any[:, e], ev_true[:, e], v)
        fail_e = ~is_user & (~subj_exists[:, e] | ~v_out)
        v = jnp.where(is_real, jnp.where(is_user, True, v_out), v)
        alive = alive & (~is_real | ~fail_e)
    create_res = ~has_ents | alive

    # create_res/rmd_res already fold the empty-entity-dict -> True case
    # (:147-148, 184-185), so this single pair_ok covers short==0 whether
    # or not the request carries ACL pairs
    pair_ok = (
        (r["r_n_ra"] > 0)
        & jnp.where(kind == 1, create_res, jnp.where(kind == 2, rmd_res, False))
    )
    return skip | (short == 1) | ((short == 0) & pair_ok)


def _owner_bit_reader(bits, v, ebits: int):
    """Unpack accessor over the host-packed owner bitplanes
    (ops/encode.owner_bit_layout): ``bits`` is one request's packed word
    vector [NWORDS], ``v`` an int array of role-scope-vocab indices (any
    shape — target rows in the dense kernel, rule/policy planes in the
    signature kernel).  Returns ``bit(k) -> bool array shaped like v``.
    Arithmetic >> on int32 is safe here: the payload bit is extracted
    with & 1 after the shift."""
    if ebits <= 32:
        epw = 32 // ebits
        codes = jnp.take(bits, v // epw) >> ((v % epw) * ebits)

        def bit(k: int):
            return ((codes >> k) & 1) == 1

        return bit
    wpe = -(-ebits // 32)
    base = v * wpe

    def bit(k: int):
        return ((jnp.take(bits, base + k // 32) >> (k % 32)) & 1) == 1

    return bit


def _hr_pass_from_bits(r: dict, v, collect, op_hit, hr_check, trivial):
    """Stage B from host-precomputed owner bitplanes: combines the packed
    per-(row, vocab) fail verdicts with the signature/target-determined
    collection state and operation hits (reference:
    hierarchicalScope.ts:54-258 — the owner-membership side was folded
    host-side at encode, ops/encode.pack_owner_bitplanes).

    ``v``/``hr_check``/``trivial`` share a leading shape ([T] dense,
    [S, M] / [S, KP] signature planes); ``collect``/``op_hit`` carry one
    trailing run/op-slot axis.  All device work is elementwise + one tiny
    int gather per plane — no matmuls, no [RV, ...] intermediates."""
    runs = r["r_own_runs"]  # [NRU]
    nru = int(runs.shape[0])
    nop = int(op_hit.shape[-1])
    bit = _owner_bit_reader(r["r_own_bits"], v, 2 * (nru + nop))
    bad = jnp.zeros(v.shape, bool)
    n_runs = int(collect.shape[-1])
    for g in range(nru):
        # collect at group g's run: a static select over the run axis, not
        # a gather (post-reduction gathers are the TPU slow path)
        coll_g = jnp.zeros(v.shape, bool)
        for nr in range(n_runs):
            coll_g = coll_g | ((runs[g] == nr) & collect[..., nr])
        bad = bad | (coll_g & jnp.where(hr_check, bit(g), bit(nru + g)))
    for j in range(nop):
        bad = bad | (
            op_hit[..., j]
            & jnp.where(hr_check, bit(2 * nru + j), bit(2 * nru + nop + j))
        )
    ctx_ok = r["r_ctx_present"] & (r["r_n_ra"] > 0)
    return trivial | (ctx_ok & ~bad)


def _rel_pass_from_bits(r: dict, v, collect, direct, trivial):
    """Relation-path gate from host-precomputed closure bitplanes
    (ops/relation.pack_relation_bitplanes) — the ReBAC analog of
    _hr_pass_from_bits over the same packed layout with nop=0 and the
    !direct flag selecting plane B instead of hr_check.

    Unlike the owner gate there is no ctx_ok/role-association term (the
    relation check needs only the subject id and the targeted instances)
    and no operation term (relation requirements apply to resource
    instances only).  ``trivial`` is rows without a relation requirement
    (t_rel_idx < 0); a collected run with any failing instance fails."""
    runs = r["r_rel_runs"]  # [NRU]
    nru = int(runs.shape[0])
    vv = jnp.maximum(v, 0)
    bit = _owner_bit_reader(r["r_rel_bits"], vv, 2 * nru)
    bad = jnp.zeros(vv.shape, bool)
    n_runs = int(collect.shape[-1])
    for g in range(nru):
        coll_g = jnp.zeros(vv.shape, bool)
        for nr in range(n_runs):
            coll_g = coll_g | ((runs[g] == nr) & collect[..., nr])
        bad = bad | (coll_g & jnp.where(direct, bit(nru + g), bit(g)))
    return trivial | ~bad


def _hr_collect_state(c: dict, r: dict, rgx_hit, pfx_neq, ent_valid):
    """Stage B's signature-determined pieces, shared by the dense kernel
    and the components-mode planes builder: the per-(target row, entity
    run) sticky collection state (exact OR regex sets, prefix mismatch
    resets) and the per-(target row, op slot) operation hit (reference:
    hierarchicalScope.ts:61-147)."""
    em_ex_k = (
        (c["t_ent_vals"][:, :, None] == r["r_ent_vals"][None, None, :])
        & (c["t_ent_vals"][:, :, None] >= 0)
        & ent_valid[None, None, :]
    )  # [T, K_ENT, NR]
    set_k = em_ex_k | rgx_hit  # regex set wins over reset
    reset_k = pfx_neq & ~set_k

    def _sticky_k(carry, inputs):
        set_bit, reset_bit = inputs
        state = jnp.where(set_bit, True, jnp.where(reset_bit, False, carry))
        return state, state

    _, coll_t = jax.lax.scan(
        _sticky_k,
        jnp.zeros(set_k.shape[:2], bool),
        (jnp.moveaxis(set_k, 2, 0), jnp.moveaxis(reset_k, 2, 0)),
    )
    collect = jnp.moveaxis(coll_t, 0, 2).any(axis=1)  # [T, NR]
    op_hit = (
        (c["t_op_vals"][:, :, None] == r["r_op_vals"][None, None, :])
        & (c["t_op_vals"][:, :, None] >= 0)
        & (r["r_op_vals"][None, None, :] >= 0)
    ).any(axis=1)  # [T, NOP]
    return collect, op_hit


def _subject_ok(c: dict, r: dict):
    """Subject matching per target row -> [T] bool (reference:
    checkSubjectMatches, accessController.ts:793-823).  Shared by the
    full matcher and the signature-bit kernel (whose stage-A resource/
    action planes are precomputed per signature but whose subject side is
    inherently per-request)."""
    sub_pairs_ok = jax.vmap(
        lambda ids, vals: _pairs_subset(ids, vals, r["r_sub_ids"], r["r_sub_vals"])
    )(c["t_sub_ids"], c["t_sub_vals"])
    role_ok = jax.vmap(lambda role: _member(role, r["r_roles"]))(c["t_role"])
    return (c["t_n_subjects"] == 0) | jnp.where(
        c["t_has_role"], role_ok, sub_pairs_ok
    )


def _regex_entity_lookup(c: dict, r: dict, ent_valid):
    """``rgx_set`` and ``pfx_neq`` read at (target row's entity-vocab row,
    request run's batch entity column) -> two [T, K_ENT, NR] bool, masked
    to valid target slots and runs.

    The [W, E] tables are per batch and the vocab rows per tree, so the
    [T, K_ENT, E] rows are read once per batch; each run then picks its
    column with a one-hot contraction over E.  Under the dense kernel's
    vmap that is one [B * NR, E] x [E, 2 * T * K_ENT] matmul, where
    indexing by (w, e) would be a scalar gather of B * T * K_ENT * NR
    elements per table.  Exact: every product is 0 or 1 and one term of
    each sum at most is non-zero.  Columns clip to [0, E - 1], as the
    gather clamps them."""
    tables = jnp.stack([r["rgx_set"], r["pfx_neq"]])  # [2, W, E]
    E = tables.shape[-1]
    rows = tables[:, jnp.clip(c["t_ent_w"], 0, None)]  # [2, T, K_ENT, E]
    e_idx = jnp.clip(r["r_ent_e"], 0, E - 1)  # [NR]
    onehot = e_idx[:, None] == jnp.arange(E)[None, :]  # [NR, E]
    hits = jnp.einsum(
        "stke,ne->stkn",
        rows.astype(jnp.bfloat16),
        onehot.astype(jnp.bfloat16),
        preferred_element_type=jnp.float32,
    ) > 0
    valid = (c["t_ent_w"][:, :, None] >= 0) & ent_valid[None, None, :]
    return hits[0] & valid, hits[1] & valid


def _match_targets(c: dict, r: dict, with_hr: bool = True,
                   wia: bool = False, components: bool = False,
                   with_rel: bool = False):
    """Stages A (target matching) + B (HR scopes) for one request: returns
    per-target-row match vectors the rule/policy stages gather from.

    Factored out so the rule-sharded kernel (parallel/rule_shard.py) can run
    it against a per-device compacted target subtable.

    ``with_hr=False`` skips stage B entirely: exact whenever no target row
    carries both subjects and a scoping entity (then ``hr_trivial`` is True
    for every row and hr_pass degenerates to all-ones); callers assert that
    tree property statically so XLA never materializes the owner-check
    tensors.

    ``components=True`` returns the resource/action stage-A planes
    (res_ex_p/res_ex_d/res_rg_p/res_rg_d/act_ok) WITHOUT the subject fold
    — the signature-bit path precomputes exactly these per resource
    signature (they depend only on the request's entity/operation/action
    attributes, not its subject/context) and re-folds _subject_ok on
    device per row.  The caller passes a property-free pseudo-request, so
    the PERMIT property-fail reduces to has_props & entity-hit and the
    DENY skip is vacuous (reference: :578-588, 644-647 with no request
    properties).

    ``wia=True`` additionally emits the whatIsAllowed-mode match vectors
    (reference: accessController.ts:592-640 — PERMIT fails only when the
    target has properties, the request has none and the entity matched;
    DENY never property-fails; the isAllowed deny-skip is not applied) and
    conservative ``maybe_mask_*`` bits (the row COULD append masking
    obligations: target properties + an entity hit), which the host-side
    reverse-query assembler (ops/reverse.py) uses to decide when the
    scalar matcher must re-run for its side effects."""
    T = c["t_role"].shape[0]

    # ---------------------------------------------------------------- A: targets
    # subject matching (reference: checkSubjectMatches :793-823)
    sub_ok = _subject_ok(c, r)

    act_ok = jax.vmap(
        lambda ids, vals: _pairs_subset(ids, vals, r["r_act_ids"], r["r_act_vals"])
    )(c["t_act_ids"], c["t_act_vals"])

    # entity matches per (target, run): exact and regex
    ent_valid = r["r_ent_valid"]  # [NR]
    em_ex = (
        (c["t_ent_vals"][:, :, None] == r["r_ent_vals"][None, None, :])
        & (c["t_ent_vals"][:, :, None] >= 0)
        & ent_valid[None, None, :]
    ).any(axis=1)  # [T, NR]
    rgx_hit, pfx_neq = _regex_entity_lookup(c, r, ent_valid)  # [T, K, NR]
    em_rg = rgx_hit.any(axis=1)  # [T, NR]

    ent_any_ex = em_ex.any(axis=1)  # [T]
    ent_any_rg = em_rg.any(axis=1)

    # operation match (exact mode only; the regex branch has no operation
    # comparison, reference: :526-574)
    opm = (
        (c["t_op_vals"][:, :, None] == r["r_op_vals"][None, None, :])
        & (c["t_op_vals"][:, :, None] >= 0)
        & (r["r_op_vals"][None, None, :] >= 0)
    ).any(axis=(1, 2))  # [T]

    # positional entity-match state per run:
    # exact mode: cumulative OR (never resets, reference: :501-505)
    state_ex = jnp.cumsum(em_ex.astype(jnp.int32), axis=1) > 0  # [T, NR]
    # regex mode: sticky with prefix-mismatch reset (reference: :526-566)
    def _sticky(carry, inputs):
        set_bit, reset_bit = inputs
        state = jnp.where(set_bit, True, jnp.where(reset_bit, False, carry))
        return state, state

    # per run j: set if regex matched, else reset if prefix mismatched
    reset_rg = pfx_neq.any(axis=1) & ~em_rg  # [T, NR]
    _, state_rg_t = jax.lax.scan(
        _sticky,
        jnp.zeros((T,), bool),
        (em_rg.T, reset_rg.T),
    )
    state_rg = state_rg_t.T  # [T, NR]

    # property gates
    prop_valid = r["r_prop_vals"] >= 0  # [NP]
    prop_run = jnp.clip(r["r_prop_run"], 0, None)  # [NP]
    prop_has_run = r["r_prop_run"] >= 0
    # relevance (exact): entity matched at-or-before the prop's run AND the
    # target entity tail equals the prop's prefix tail (verified by the
    # encoder to coincide with the reference substring check)
    state_at_prop_ex = jnp.take(state_ex, prop_run, axis=1) & prop_has_run[None, :]
    tail_eq = (
        (c["t_ent_tails"][:, :, None] == r["r_prop_tail"][None, None, :])
        & (c["t_ent_tails"][:, :, None] >= 0)
    ).any(axis=1)  # [T, NP]
    relevant_ex = prop_valid[None, :] & state_at_prop_ex & tail_eq
    in_rule = (
        (c["t_prop_vals"][:, :, None] == r["r_prop_vals"][None, None, :])
        & (c["t_prop_vals"][:, :, None] >= 0)
    ).any(axis=1)  # [T, NP]
    sfx_in_rule = (
        (c["t_prop_sfx"][:, :, None] == r["r_prop_sfx"][None, None, :])
        & (c["t_prop_sfx"][:, :, None] >= 0)
    ).any(axis=1)  # [T, NP]
    state_at_prop_rg = jnp.take(state_rg, prop_run, axis=1) & prop_has_run[None, :]
    relevant_rg = prop_valid[None, :] & state_at_prop_rg

    has_props = c["t_has_props"]
    r_has_props = r["r_has_props"]
    # regex-mode entity state: "true at any point" feeds the per-attribute
    # PERMIT fail check; the *final* state feeds the end-of-loop entity gate
    # (a later prefix mismatch can reset it, reference: :545-566, 650-653);
    # exact-mode state is monotone so any == final
    state_any_rg = state_rg.any(axis=1)
    NRr = state_rg.shape[1]
    state_final_rg = state_rg[:, NRr - 1]
    permit_fail_ex = has_props & (
        (~r_has_props & ent_any_ex) | (relevant_ex & ~in_rule).any(axis=1)
    )
    deny_skip_ex = has_props & r_has_props & ~(relevant_ex & in_rule).any(axis=1)
    permit_fail_rg = has_props & (
        (~r_has_props & state_any_rg) | (relevant_rg & ~sfx_in_rule).any(axis=1)
    )
    deny_skip_rg = has_props & r_has_props & ~(relevant_rg & sfx_in_rule).any(axis=1)

    no_res = c["t_n_res"] == 0
    res_ex_p = no_res | ((ent_any_ex | opm) & ~permit_fail_ex)
    res_ex_d = no_res | ((ent_any_ex | opm) & ~deny_skip_ex)
    res_rg_p = no_res | (state_final_rg & ~permit_fail_rg)
    res_rg_d = no_res | (state_final_rg & ~deny_skip_rg)

    if components:
        out = {
            "sig_res_ex_p": res_ex_p,
            "sig_res_ex_d": res_ex_d,
            "sig_res_rg_p": res_rg_p,
            "sig_res_rg_d": res_rg_d,
            "sig_act_ok": act_ok,
        }
        if wia:
            # whatIsAllowed-mode RESOURCE planes at signature granularity
            # (reference: accessController.ts:592-640): everything but the
            # subject fold is (entity, operation, action, has-props)-
            # determined, so the reverse-query kernel caches these per
            # signature and folds subjects host-side (ops/reverse.py)
            wia_fail_ex = has_props & ~r_has_props & ent_any_ex
            wia_fail_rg = has_props & ~r_has_props & state_any_rg
            out["sig_wia_ex_p"] = no_res | (
                (ent_any_ex | opm) & ~wia_fail_ex
            )
            out["sig_wia_ex_d"] = no_res | ent_any_ex | opm
            out["sig_wia_rg_p"] = no_res | (
                state_final_rg & ~wia_fail_rg
            )
            out["sig_wia_rg_d"] = no_res | state_final_rg
            out["sig_maybe_ex"] = has_props & ent_any_ex
            out["sig_maybe_rg"] = has_props & state_any_rg
        if with_hr or with_rel:
            # stage B's signature-determined parts — the owner side
            # stays per-request (shared helper with the dense stage B);
            # the relation fold reuses the same collection state
            collect, op_hit = _hr_collect_state(
                c, r, rgx_hit, pfx_neq, ent_valid
            )
            out["sig_collect"] = collect
            out["sig_op_hit"] = op_hit
        return out

    base = sub_ok & act_ok
    tm_ex_p = base & res_ex_p
    tm_ex_d = base & res_ex_d
    tm_rg_p = base & res_rg_p
    tm_rg_d = base & res_rg_d

    out = {
        "tm_ex_p": tm_ex_p,
        "tm_ex_d": tm_ex_d,
        "tm_rg_p": tm_rg_p,
        "tm_rg_d": tm_rg_d,
    }
    if wia:
        # whatIsAllowed PERMIT property-fail: target props, request has no
        # props, entity matched somewhere (ref :592-615 return branch)
        wia_fail_ex = has_props & ~r_has_props & ent_any_ex
        wia_fail_rg = has_props & ~r_has_props & state_any_rg
        out["tm_wia_ex_p"] = base & (
            no_res | ((ent_any_ex | opm) & ~wia_fail_ex)
        )
        out["tm_wia_ex_d"] = base & (no_res | ent_any_ex | opm)
        out["tm_wia_rg_p"] = base & (
            no_res | (state_final_rg & ~wia_fail_rg)
        )
        out["tm_wia_rg_d"] = base & (no_res | state_final_rg)
        out["maybe_mask_ex"] = has_props & ent_any_ex
        out["maybe_mask_rg"] = has_props & state_any_rg

    # ------------------------------------------------------------- B: HR scopes
    if not with_hr and not with_rel:
        out["hr_pass"] = jnp.ones((T,), bool)
        return out
    # collection per (target, entity slot, run) with sticky state like the
    # reference HR loop (exact OR regex sets, prefix mismatch resets,
    # reference: hierarchicalScope.ts:61-124) — shared with the signature
    # planes builder.  The owner-membership side arrives as host-packed
    # bitplanes indexed by the (role, scoping) vocab (compile.py hrv_*,
    # encode.pack_owner_bitplanes), gathered per target row via t_rs_idx.
    collect, op_hit = _hr_collect_state(c, r, rgx_hit, pfx_neq, ent_valid)
    if with_hr:
        hr_trivial = (c["t_n_subjects"] == 0) | ~c["t_has_scoping"]
        hr = _hr_pass_from_bits(
            r, c["t_rs_idx"], collect, op_hit, c["t_hr_check"], hr_trivial
        )
    else:
        hr = jnp.ones((T,), bool)
    if with_rel:
        # relation-path fold (ReBAC): same collection state, packed
        # closure planes gathered per target row via t_rel_idx; ANDed
        # into hr_pass so both gate sites (hr_rule in _rule_predicates
        # and the pol_subject gate) pick it up — mirroring the oracle's
        # paired check_hierarchical_scope/check_target_relations calls
        hr = hr & _rel_pass_from_bits(
            r, c["t_rel_idx"], collect, c["t_rel_direct"],
            c["t_rel_idx"] < 0,
        )
    out["hr_pass"] = hr
    return out


def _rule_predicates(c: dict, r: dict, m: dict, with_acl: bool = True):
    """Stage C: per-rule reachability, ACL gate and condition wiring;
    shared by the single-device and rule-sharded kernels (the latter passes
    a KR-chunked ``c`` with a compacted target subtable)."""
    tm_ex_p, tm_ex_d = m["tm_ex_p"], m["tm_ex_d"]
    tm_rg_p, tm_rg_d = m["tm_rg_p"], m["tm_rg_d"]
    hr_pass = m["hr_pass"]

    def gather_t(table, idx):
        return jnp.take(table, idx, axis=0)

    rt = c["rule_target"]  # [S, KP, KR]
    rule_deny = c["rule_effect"] == 2
    tm_rule_ex = jnp.where(rule_deny, gather_t(tm_ex_d, rt), gather_t(tm_ex_p, rt))
    tm_rule_rg = jnp.where(rule_deny, gather_t(tm_rg_d, rt), gather_t(tm_rg_p, rt))
    tm_rule = ~c["rule_has_target"] | tm_rule_ex | tm_rule_rg
    hr_rule = ~c["rule_has_target"] | gather_t(hr_pass, rt)
    reached = c["rule_valid"] & tm_rule & hr_rule

    # verifyACL per target row (stage B2): full tensorized semantics when
    # the batch carries ACL pairs, the cheap no-pair formula otherwise
    acl_rule = ~c["rule_has_target"] | gather_t(_acl_pass(c, r, with_acl), rt)

    has_cond, cond_t, cond_a, cond_c = _rule_conditions(c, r)
    return reached, acl_rule, has_cond, cond_t, cond_a, cond_c


def _rule_conditions(c: dict, r: dict):
    """Per-rule condition wiring: host-evaluated predicate bits joined to
    the rule mask (reference: conditionMatches eval, utils.ts:47-56)."""
    has_cond = c["rule_cond"] >= 0
    cond_idx = jnp.clip(c["rule_cond"], 0, None)
    if r["cond_true"].shape[0] > 0:
        cond_t = jnp.take(r["cond_true"], cond_idx)
        cond_a = jnp.take(r["cond_abort"], cond_idx)
        cond_c = jnp.take(r["cond_code"], cond_idx)
    else:
        cond_t = jnp.ones_like(cond_idx, dtype=bool)
        cond_a = jnp.zeros_like(cond_idx, dtype=bool)
        cond_c = jnp.full_like(cond_idx, 200)
    return has_cond, cond_t, cond_a, cond_c


def _multi_entity_ok(c: dict, r_ent_vals, r_ent_valid):
    """Multi-entity recheck -> [S] (reference: accessController.ts
    :429-463): every requested entity must exactly match some policy's
    resources; PERMIT policies with properties never match a bare entity
    attribute.  Shared by the full kernel (request entities) and the
    signature planes builder (the signature IS the entity list)."""
    pol_ent_hit = (
        (c["pol_ent_vals"][:, :, :, None] == r_ent_vals[None, None, None, :])
        & (c["pol_ent_vals"][:, :, :, None] >= 0)
        & r_ent_valid[None, None, None, :]
    ).any(axis=2)  # [S, KP, NR]
    pol_multi_ok = pol_ent_hit & ~(
        (c["pol_effect"] == 1) & c["pol_has_props"]
    )[:, :, None] & c["pol_valid"][:, :, None]
    return jnp.all(~r_ent_valid[None, :] | pol_multi_ok.any(axis=1), axis=1)


def _policy_gates_core(c: dict, pp_ex_p, pp_ex_d, pp_rg_p, pp_rg_d,
                       multi_gate):
    """First/second policy loop on pre-gathered policy-row match planes
    ([S, KP], full target match incl. subject fold): carried policyEffect
    selection, exact-match break, and the policy gate (reference:
    accessController.ts:130-195).  Shared by the full kernel (planes
    gathered from [T] match vectors) and the signature kernel (planes
    precomputed per signature, subject side folded by the caller)."""
    ctx_deny = c["pol_eff_ctx"] == 2
    pol_tm_first = jnp.where(ctx_deny, pp_ex_d, pp_ex_p)
    pol_tm_first = pol_tm_first & c["pol_valid"] & c["pol_has_target"]  # [S, KP]
    KP = pol_tm_first.shape[1]
    kp_pos = jnp.arange(KP)
    first_kp = jnp.min(
        jnp.where(pol_tm_first, kp_pos[None, :], BIG), axis=1
    )  # [S]
    exact0 = pol_tm_first.any(axis=1)
    last_valid_kp = jnp.max(
        jnp.where(c["pol_valid"], kp_pos[None, :], -1), axis=1
    )
    eff_src_kp = jnp.where(exact0, jnp.clip(first_kp, 0, KP - 1),
                           jnp.clip(last_valid_kp, 0, KP - 1))
    eval_eff = jnp.take_along_axis(
        c["pol_eff_ctx"], eff_src_kp[:, None], axis=1
    )[:, 0]  # [S] carried policyEffect after the break (reference: :130-157)

    exact = exact0 & multi_gate

    # second loop: policy gate with the frozen carried effect
    eval_deny = (eval_eff == 2)[:, None]
    pol_tm_ex = jnp.where(eval_deny, pp_ex_d, pp_ex_p)
    pol_tm_rg = jnp.where(eval_deny, pp_rg_d, pp_rg_p)
    pol_gate = ~c["pol_has_target"] | jnp.where(exact[:, None], pol_tm_ex, pol_tm_rg)
    return pol_gate & c["pol_valid"]


def _policy_gates(c: dict, r: dict, m: dict):
    """Stage D: set-level exact match, carried policyEffect, multi-entity
    recheck and the policy/set gates (reference: accessController.ts
    :130-195, 429-463); shared by both kernels."""
    tm_ex_p, tm_ex_d = m["tm_ex_p"], m["tm_ex_d"]
    tm_rg_p, tm_rg_d = m["tm_rg_p"], m["tm_rg_d"]
    hr_pass = m["hr_pass"]

    def gather_t(table, idx):
        return jnp.take(table, idx, axis=0)

    pt = c["pol_target"]
    multi_ok = _multi_entity_ok(c, r["r_ent_vals"], r["r_ent_valid"])
    multi_gate = jnp.where(r["r_n_entity_attrs"] > 1, multi_ok, True)
    pol_gate = _policy_gates_core(
        c,
        gather_t(tm_ex_p, pt), gather_t(tm_ex_d, pt),
        gather_t(tm_rg_p, pt), gather_t(tm_rg_d, pt),
        multi_gate,
    )

    # set gate: exact mode only, PERMIT variant (reference: :131-134)
    set_gate = ~c["set_has_target"] | gather_t(tm_ex_p, c["set_target"])
    set_gate = set_gate & c["set_valid"]  # [S]

    pol_subject = ~c["pol_has_subjects"] | gather_t(hr_pass, pt)  # [S, KP]
    return pol_gate, set_gate, pol_subject


def _combine_and_decide_flat(c: dict, reached, acl_rule, has_cond, cond_t,
                             cond_a, cond_c, pol_gate, set_gate,
                             pol_subject=None, explain: bool = False):
    """Flat-rule-axis variant of _combine_and_decide for the signature
    kernel: inputs arrive as [S, KP*KR] planes and the per-policy KR
    reductions run as reduce_windows, so batched callers avoid
    [B, S, KP, KR] intermediates whose tiny trailing dim pads to the
    TPU's 128-lane tile (8x memory at KR=16).  Flat positions preserve
    the original (set, policy, rule) ordering, so first/last semantics
    and the abort's flat-order selection are unchanged.

    ``explain=True`` appends a 4th int32 output encoding the deciding
    node: ``(flat_pos << 2) | kind`` with kind 0 = no contribution,
    1 = rule at flat pos (s*KP + kp)*KR + kr, 2 = no-rules policy at
    pos s*KP + kp, 3 = condition abort at the rule's flat pos.  When the
    caller compacted the rule axis (ops/prefilter.compact_rules) it
    supplies ``c["rule_orig_flat"]`` mapping compacted slots back to
    original flat positions; the flag is Python-level, so the False
    trace is exactly the pre-explain computation."""
    S, KP, KR = c["rule_effect"].shape
    M = KP * KR
    re_f = c["rule_effect"].reshape(S, M)
    cach_eff_f = c["rule_cacheable_eff"].reshape(S, M)
    cach_raw_f = c["rule_cacheable_raw"].reshape(S, M)

    scope = set_gate[:, None] & pol_gate          # [S, KP]
    scope_f = jnp.repeat(scope, KR, axis=1)       # [S, M]
    abort_rule = reached & has_cond & cond_a & scope_f
    matches = reached & (~has_cond | cond_t) & ~(has_cond & cond_a) & acl_rule
    coll = matches & scope_f
    if pol_subject is not None:  # policy-subject HR gate (reference :188-195)
        coll = coll & jnp.repeat(pol_subject, KR, axis=1)

    m_pos = jnp.broadcast_to(
        jnp.arange(M, dtype=jnp.int32)[None, :], (S, M)
    )
    code_f = pack_rule_key(m_pos, re_f, cach_eff_f)

    def win_min(x):
        return jax.lax.reduce_window(
            x, jnp.int32(BIG), jax.lax.min, (1, KR), (1, KR), "VALID"
        )

    def win_max(x):
        return jax.lax.reduce_window(
            x, jnp.int32(-1), jax.lax.max, (1, KR), (1, KR), "VALID"
        )

    first_deny = win_min(jnp.where(coll & (re_f == 2), code_f, BIG))
    first_permit = win_min(jnp.where(coll & (re_f == 1), code_f, BIG))
    first_coll = win_min(jnp.where(coll, code_f, BIG))
    last_coll = win_max(jnp.where(coll, code_f, -1))
    any_coll = win_max(coll.astype(jnp.int32)) > 0

    sel_do = jnp.where(first_deny < BIG, first_deny, last_coll)
    sel_po = jnp.where(first_permit < BIG, first_permit, last_coll)
    sel = jnp.select(
        [c["pol_ca"] == 0, c["pol_ca"] == 1, c["pol_ca"] == 2],
        [sel_do, sel_po, first_coll],
        default=jnp.zeros_like(sel_do),
    )
    rule_eff_sel, rule_cach_sel = unpack_rule_key(sel)

    no_rules_contrib = (
        c["pol_valid"]
        & set_gate[:, None]
        & pol_gate
        & (c["pol_n_rules"] == 0)
        & (c["pol_effect"] > 0)
    )
    contrib_present = no_rules_contrib | any_coll
    contrib_eff = jnp.where(no_rules_contrib, c["pol_effect"], rule_eff_sel)
    contrib_cach = jnp.where(
        no_rules_contrib, c["pol_cacheable"], rule_cach_sel
    )
    if explain:
        decision, cacheable, win_s, have, s_sel_c = _combine_sets(
            c, contrib_present, contrib_eff, contrib_cach, explain=True
        )
    else:
        decision, cacheable = _combine_sets(
            c, contrib_present, contrib_eff, contrib_cach
        )
    status = jnp.int32(200)

    # condition aborts preempt everything, first in flat rule order
    # (s*M + m == s*(KP*KR) + kp*KR + kr: identical to the 3-D variant)
    flat_order = jnp.arange(S * M, dtype=jnp.int32).reshape(S, M)
    abort_pos = jnp.min(jnp.where(abort_rule, flat_order, BIG))
    has_abort = abort_pos < BIG
    abort_flat = jnp.clip(abort_pos, 0, S * M - 1)
    abort_code = jnp.take(cond_c.reshape(-1), abort_flat)
    abort_cach = jnp.take(cach_raw_f.reshape(-1), abort_flat).astype(
        jnp.int32
    )
    decision = jnp.where(has_abort, 2, decision)
    cacheable = jnp.where(has_abort, abort_cach, cacheable)
    status = jnp.where(has_abort, abort_code, status)
    if not explain:
        return decision.astype(jnp.int32), cacheable, status.astype(jnp.int32)

    # ------------------------------------------------- explain recovery
    # the winner's packed key already carries its flat position in the
    # high bits (pack_rule_key); re-derive (set, policy, rule) from the
    # same selections the decision used, so provenance is the decision's
    # by construction
    win_kp = jnp.take(s_sel_c, win_s)
    win_flat = win_s * KP + win_kp
    win_m = jnp.take(sel.reshape(-1), win_flat) >> 3
    no_rules_win = jnp.take(no_rules_contrib.reshape(-1), win_flat)
    orig = c.get("rule_orig_flat")
    if orig is None:
        rule_pos = win_s * M + win_m
        abort_orig = abort_flat
    else:
        orig_f = orig.reshape(-1)
        rule_pos = jnp.take(orig_f, jnp.clip(win_s * M + win_m, 0, S * M - 1))
        abort_orig = jnp.take(orig_f, abort_flat)
    expl = jnp.where(
        have,
        jnp.where(no_rules_win, (win_flat << 2) | 2, (rule_pos << 2) | 1),
        0,
    )
    expl = jnp.where(has_abort, (abort_orig << 2) | 3, expl)
    return (decision.astype(jnp.int32), cacheable, status.astype(jnp.int32),
            expl.astype(jnp.int32))


def _combine_sets(c: dict, contrib_present, contrib_eff, contrib_cach,
                  explain: bool = False):
    """Stages F-G (pre-abort): policy-effect combination per set and the
    last-set-wins decision; shared by both kernels.  ``explain=True``
    additionally returns the winning set slot, whether any set
    contributed, and the per-set selected policy slot — the coordinates
    explain recovery re-derives provenance from."""
    if explain:
        set_eff, set_cach, set_any, s_sel_c = _per_set_effects(
            c, contrib_present, contrib_eff, contrib_cach, explain=True
        )
    else:
        set_eff, set_cach, set_any = _per_set_effects(
            c, contrib_present, contrib_eff, contrib_cach
        )

    # last-set-wins (reference: :293-295); effect present but neither
    # PERMIT nor DENY folds to INDETERMINATE with the winning cacheable
    # (reference: :312-318)
    S = set_eff.shape[0]
    s_pos = jnp.arange(S)
    winner = jnp.max(jnp.where(set_any, s_pos, -1))
    have = winner >= 0
    winner_c = jnp.clip(winner, 0, S - 1)
    decision = jnp.where(have, jnp.take(set_eff, winner_c), 0)
    cacheable = jnp.where(
        have, jnp.take(set_cach, winner_c).astype(jnp.int32), -1
    )
    if explain:
        return decision, cacheable, winner_c, have, s_sel_c
    return decision, cacheable


def _per_set_effects(c: dict, contrib_present, contrib_eff, contrib_cach,
                     explain: bool = False):
    """Stage F alone: combine each set's policy contributions under its
    combining algorithm, returning per-set ``(set_eff, set_cach, set_any)``
    WITHOUT the last-set-wins tail.  Split out so the pod-sharded kernel
    (parallel/pod_shard.py) can run it shard-locally — whole sets live on
    one shard — and merge the per-set results across shards with a packed
    positional pmax instead of the local winner scan."""
    KP = contrib_present.shape[1]
    kp_pos2 = jnp.arange(KP)[None, :]
    p_first_deny = jnp.min(
        jnp.where(contrib_present & (contrib_eff == 2), kp_pos2, BIG), axis=1
    )
    p_first_permit = jnp.min(
        jnp.where(contrib_present & (contrib_eff == 1), kp_pos2, BIG), axis=1
    )
    p_first = jnp.min(jnp.where(contrib_present, kp_pos2, BIG), axis=1)
    p_last = jnp.max(jnp.where(contrib_present, kp_pos2, -1), axis=1)
    set_any = contrib_present.any(axis=1)

    s_sel_do = jnp.where(p_first_deny < BIG, p_first_deny, p_last)
    s_sel_po = jnp.where(p_first_permit < BIG, p_first_permit, p_last)
    s_sel = jnp.select(
        [c["set_ca"] == 0, c["set_ca"] == 1, c["set_ca"] == 2],
        [s_sel_do, s_sel_po, p_first],
        default=jnp.zeros_like(s_sel_do),
    )
    s_sel_c = jnp.clip(s_sel, 0, KP - 1)
    set_eff = jnp.take_along_axis(contrib_eff, s_sel_c[:, None], axis=1)[:, 0]
    set_cach = jnp.take_along_axis(contrib_cach, s_sel_c[:, None], axis=1)[:, 0]
    if explain:
        return set_eff, set_cach, set_any, s_sel_c
    return set_eff, set_cach, set_any


def _evaluate_one(c: dict, r: dict, with_acl: bool = True,
                  with_hr: bool = True, explain: bool = False,
                  with_rel: bool = False):
    """Decision for a single encoded request; vmapped over the batch.

    ``c``: compiled policy arrays (replicated across devices).
    ``r``: per-request encoded arrays.
    ``with_acl``: compile the full verifyACL stage (exact when ACL pairs
    are present; batches without pairs may use the cheaper False variant).
    ``with_hr``: compile stage B (exact when some target row carries both
    subjects and a scoping entity; see _match_targets).
    Returns (decision, cacheable, status_code) int32 scalars where
    decision: 0=INDETERMINATE 1=PERMIT 2=DENY; cacheable: -1 none 0/1 bool;
    ``explain=True`` appends the packed provenance code (see
    _combine_and_decide).
    """
    m = _match_targets(c, r, with_hr, with_rel=with_rel)
    return _evaluate_from_matches(c, r, m, with_acl, explain=explain)


def _evaluate_from_matches(c: dict, r: dict, m: dict, with_acl: bool = True,
                           explain: bool = False):
    """Stages C-G given the stage-A/B match vectors ``m``: rule
    reachability, policy/set gates, combining, aborts.  Shared by the full
    kernel (m from _match_targets) and the signature-bit kernel (m rebuilt
    from precomputed per-signature planes + the per-row subject fold)."""
    reached, acl_rule, has_cond, cond_t, cond_a, cond_c = _rule_predicates(
        c, r, m, with_acl
    )
    pol_gate, set_gate, pol_subject = _policy_gates(c, r, m)
    return _combine_and_decide(
        c, reached, acl_rule, has_cond, cond_t, cond_a, cond_c,
        pol_gate, set_gate, pol_subject, explain=explain,
    )


def _policy_contributions(c: dict, reached, acl_rule, has_cond, cond_t,
                          cond_a, pol_gate, set_gate, pol_subject,
                          explain: bool = False):
    """Stage E alone: per-policy winning-rule contributions plus the
    abort-rule mask.  Split out of _combine_and_decide so the pod-sharded
    kernel (parallel/pod_shard.py) can run stages A-F shard-locally —
    whole sets live on one shard — before its cross-shard collectives.
    ``explain=True`` additionally returns the per-policy selected rule
    slot and the no-rules-contribution mask for provenance recovery."""
    scope = set_gate[:, None, None] & pol_gate[:, :, None]
    abort_rule = reached & has_cond & cond_a & scope
    matches = reached & (~has_cond | cond_t) & ~(has_cond & cond_a) & acl_rule
    coll = matches & pol_subject[:, :, None] & scope  # [S, KP, KR]

    KR = coll.shape[2]
    kr_pos = jnp.arange(KR)[None, None, :]
    first_deny = jnp.min(
        jnp.where(coll & (c["rule_effect"] == 2), kr_pos, BIG), axis=2
    )
    first_permit = jnp.min(
        jnp.where(coll & (c["rule_effect"] == 1), kr_pos, BIG), axis=2
    )
    first_coll = jnp.min(jnp.where(coll, kr_pos, BIG), axis=2)
    last_coll = jnp.max(jnp.where(coll, kr_pos, -1), axis=2)
    any_coll = coll.any(axis=2)

    sel_do = jnp.where(first_deny < BIG, first_deny, last_coll)
    sel_po = jnp.where(first_permit < BIG, first_permit, last_coll)
    sel = jnp.select(
        [c["pol_ca"] == 0, c["pol_ca"] == 1, c["pol_ca"] == 2],
        [sel_do, sel_po, first_coll],
        default=jnp.zeros_like(sel_do),
    )
    sel_c = jnp.clip(sel, 0, KR - 1)
    rule_eff_sel = jnp.take_along_axis(c["rule_effect"], sel_c[:, :, None], axis=2)[
        :, :, 0
    ]
    rule_cach_sel = jnp.take_along_axis(
        c["rule_cacheable_eff"], sel_c[:, :, None], axis=2
    )[:, :, 0]

    no_rules_contrib = (
        c["pol_valid"]
        & set_gate[:, None]
        & pol_gate
        & (c["pol_n_rules"] == 0)
        & (c["pol_effect"] > 0)
    )
    contrib_present = no_rules_contrib | any_coll
    contrib_eff = jnp.where(no_rules_contrib, c["pol_effect"], rule_eff_sel)
    contrib_cach = jnp.where(no_rules_contrib, c["pol_cacheable"], rule_cach_sel)
    if explain:
        return (contrib_present, contrib_eff, contrib_cach, abort_rule,
                sel_c, no_rules_contrib)
    return contrib_present, contrib_eff, contrib_cach, abort_rule


def _combine_and_decide(c: dict, reached, acl_rule, has_cond, cond_t,
                        cond_a, cond_c, pol_gate, set_gate, pol_subject,
                        explain: bool = False):
    """Stages E-G: rule-effect combination per policy, policy-effect
    combination per set, last-set-wins decision and condition aborts —
    shared tail of every kernel variant.  ``explain=True`` appends a 4th
    int32 output ``(flat_pos << 2) | kind`` (see _combine_and_decide_flat)
    recovered from the same positional selections the decision used."""
    # -------------------------------------------------- E: combine rule effects
    if explain:
        (contrib_present, contrib_eff, contrib_cach, abort_rule,
         sel_c, no_rules_contrib) = _policy_contributions(
            c, reached, acl_rule, has_cond, cond_t, cond_a,
            pol_gate, set_gate, pol_subject, explain=True,
        )
    else:
        contrib_present, contrib_eff, contrib_cach, abort_rule = (
            _policy_contributions(
                c, reached, acl_rule, has_cond, cond_t, cond_a,
                pol_gate, set_gate, pol_subject,
            )
        )

    # --------------------------------------- F-G: combine + last-set-wins
    if explain:
        decision, cacheable, win_s, have, s_sel_c = _combine_sets(
            c, contrib_present, contrib_eff, contrib_cach, explain=True
        )
    else:
        decision, cacheable = _combine_sets(
            c, contrib_present, contrib_eff, contrib_cach
        )
    status = jnp.int32(200)

    # condition aborts preempt everything, first in flat rule order
    S, KP, KR = abort_rule.shape
    flat_order = (
        jnp.arange(S)[:, None, None] * (KP * KR)
        + jnp.arange(KP)[None, :, None] * KR
        + jnp.arange(KR)[None, None, :]
    )
    abort_pos = jnp.min(jnp.where(abort_rule, flat_order, BIG))
    has_abort = abort_pos < BIG
    # gather the aborting rule's condition code and raw cacheable
    abort_flat = jnp.clip(abort_pos, 0, abort_rule.size - 1)
    cond_c_flat = cond_c.reshape(-1)
    cach_raw_flat = c["rule_cacheable_raw"].reshape(-1)
    abort_code = jnp.take(cond_c_flat, abort_flat)
    abort_cach = jnp.take(cach_raw_flat, abort_flat).astype(jnp.int32)

    decision = jnp.where(has_abort, 2, decision)
    cacheable = jnp.where(has_abort, abort_cach, cacheable)
    status = jnp.where(has_abort, abort_code, status)

    if not explain:
        return decision.astype(jnp.int32), cacheable, status.astype(jnp.int32)

    # ------------------------------------------------- explain recovery
    win_kp = jnp.take(s_sel_c, win_s)
    win_flat = win_s * KP + win_kp
    win_kr = jnp.take(sel_c.reshape(-1), win_flat)
    no_rules_win = jnp.take(no_rules_contrib.reshape(-1), win_flat)
    orig = c.get("rule_orig_flat")
    if orig is None:
        rule_pos = win_flat * KR + win_kr
        abort_orig = abort_flat
    else:
        orig_f = orig.reshape(-1)
        rule_pos = jnp.take(orig_f, win_flat * KR + win_kr)
        abort_orig = jnp.take(orig_f, abort_flat)
    expl = jnp.where(
        have,
        jnp.where(no_rules_win, (win_flat << 2) | 2, (rule_pos << 2) | 1),
        0,
    )
    expl = jnp.where(has_abort, (abort_orig << 2) | 3, expl)
    return (decision.astype(jnp.int32), cacheable, status.astype(jnp.int32),
            expl.astype(jnp.int32))


class DecisionKernel:
    """Compiled-policy decision kernel with a jitted vmapped evaluate.

    ``dynamic_policies=True`` (the hot-update mode, ops/delta.py) forces
    the policy tables to enter jit as ARGUMENTS — never baked as XLA
    constants — and registers the jitted callables in ``shared_jits`` so a
    swapped-in kernel over patched tables with identical shapes reuses the
    existing executables: an in-capacity policy mutation then costs zero
    new XLA compilations."""

    def __init__(self, compiled: CompiledPolicies,
                 dynamic_policies: bool = False,
                 shared_jits: Optional[dict] = None,
                 explain: bool = False):
        if not compiled.supported:
            raise ValueError(
                f"policy tree unsupported by kernel: {compiled.unsupported_reason}"
            )
        self.compiled = compiled
        self.dynamic_policies = dynamic_policies
        # explain mode (docs/EXPLAIN.md): a 4th packed-provenance output
        # per row.  The flag is part of the shared-jit key, so explain-off
        # kernels keep their pre-explain executables byte-identical.
        self.explain = bool(explain)
        # (KP, KR) strides of the packed explain positions — the host
        # decoder (srv/explain.py) maps flat positions back to tree slots
        self.explain_strides = (compiled.KP, compiled.KR)
        self._shared = shared_jits if shared_jits is not None else {}
        # hrv_role/hrv_scope stay host-side (encode's owner-bit packer
        # consumes them; the device programs read only packed bitplanes).
        # t_rel_path/relv_path likewise: the relation packer and the store
        # consume them, the kernel reads only t_rel_idx + packed planes.
        self._c = {
            k: jnp.asarray(v) for k, v in compiled.arrays.items()
            if k not in ("hrv_role", "hrv_scope", "t_rel_path", "relv_path")
        }
        self._bake_constants = (
            not dynamic_policies and bake_policy_constants(compiled)
        )
        with_hr = tree_needs_hr(compiled.arrays)
        with_rel = tree_needs_rel(compiled.arrays)

        def make_run(with_acl: bool):
            key = ("dense", with_acl, with_hr, with_rel)
            if explain:
                key = key + ("explain",)
            if dynamic_policies and key in self._shared:
                jitted = self._shared[key]
                return lambda *args: jitted(self._c, *args)

            def run(c, batch_arrays, rgx_set, pfx_neq,
                    cond_true, cond_abort, cond_code):
                # vmap over the leading batch axis of request arrays; regex
                # matrices and compiled arrays are broadcast
                in_axes = ({k: 0 for k in batch_arrays}, None, None, 0, 0, 0)

                def one(ra, rs, pn, ct, ca, cc):
                    rr = {**ra, "rgx_set": rs, "pfx_neq": pn,
                          "cond_true": ct, "cond_abort": ca, "cond_code": cc}
                    return _evaluate_one(c, rr, with_acl, with_hr,
                                         explain=explain, with_rel=with_rel)

                return jax.vmap(one, in_axes=in_axes)(
                    batch_arrays, rgx_set, pfx_neq,
                    cond_true.T, cond_abort.T, cond_code.T,
                )

            # the program's name in compile counts and device traces
            run.__name__ = "acs_dense_acl" if with_acl else "acs_dense"
            if self._bake_constants:
                return jax.jit(partial(run, self._c))
            jitted = jax.jit(run)
            if dynamic_policies:
                self._shared[key] = jitted
            return lambda *args: jitted(self._c, *args)

        # two compiled variants: batches without ACL pairs (the common
        # serving mix) skip the create-path scan entirely; the entry
        # dispatches on the batch's actual content
        self._run_noacl = make_run(False)
        self._run_acl = make_run(True)
        self._run = self._run_noacl

    def evaluate(self, batch: RequestBatch):
        """Returns (decision, cacheable, status) numpy arrays [B]."""
        return self.evaluate_async(batch)()

    def evaluate_async(self, batch: RequestBatch):
        """Host prep + device dispatch WITHOUT blocking on the result;
        returns a zero-arg callable that materializes the (decision,
        cacheable, status) tuple — the dense-kernel leg of the depth-N
        serving pipeline (srv/batcher.py overlaps the next batch's prep
        with this batch's device execution).

        The batch axis is padded to a power-of-two bucket before entering
        jit: without bucketing every distinct batch size is a fresh XLA
        compile, which would stall a micro-batched serving path on nearly
        every call (the distinct-entity axis of the regex matrices is
        bucketed for the same reason)."""
        # failpoints (srv/faults.py): host-side only — fired before the
        # jitted call / inside the materialize thunk, so the lowered
        # device program is the same with faults armed
        # (tests/test_delta.py::TestHostSideSubsystems, failpoints)
        from ..srv.faults import REGISTRY as _faults

        _faults.fire("device.dispatch")
        b, bucket, e_bucket, pad_lead = lead_padding(batch)

        # dispatch on ACL content: only batches actually carrying ACL
        # pairs pay for the tensorized verifyACL create-scan (the no-pair
        # variant is exact for everything else, incl. short==1/2 rows)
        run = self._run_acl if batch_has_acl(batch) else self._run_noacl
        out = run(
            {k: jnp.asarray(pad_lead(v)) for k, v in batch.arrays.items()},
            jnp.asarray(pad_cols(batch.rgx_set, e_bucket)),
            jnp.asarray(pad_cols(batch.pfx_neq, e_bucket)),
            jnp.asarray(pad_cols(batch.cond_true, bucket)),
            jnp.asarray(pad_cols(batch.cond_abort, bucket)),
            jnp.asarray(pad_cols(batch.cond_code, bucket)),
        )
        def materialize():
            # hang here models a wedged D2H fetch — the watchdog
            # (srv/watchdog.py) bounds it on the serving pipeline
            _faults.fire("device.materialize")
            return tuple(np.asarray(x)[:b] for x in out)

        return materialize
