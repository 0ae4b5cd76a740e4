"""The batched scheduling step — this framework's flagship compiled program.

One device call schedules a whole pod micro-batch against the node snapshot:

  1. STATIC phase (once per batch): selector-VM evaluation + the filter masks
     and score components that cannot change intra-batch (labels, taints,
     affinity, images — node properties no pod commit can alter), plus the
     existing-term domain tables for inter-pod affinity (ops/topology.py).
  2. COMMIT phase: ``lax.scan`` over the batch in queue order. Each step
     computes the *dynamic* predicates (resource fit, ports, topology spread,
     inter-pod affinity) against the evolving carry, normalizes scores over
     that pod's feasible set, picks the winner (masked argmax + seeded uniform
     tie-break), and commits the pod's resources/ports/pod-set memberships to
     its node — the reference's assume (schedule_one.go:734) replayed inside
     the compiled program, which is what makes a K-pod batch conflict-free
     (including anti-affinity conflicts) without host round-trips.

The scan's per-step work is O(N·R + C·(N+Vd)); the expensive [P,N]-shaped work
stays in the vectorized static phase. Sequential semantic parity: the winner
for pod k is chosen against exactly the state the reference's serial loop
would see.

SPMD: the same program runs under ``shard_map`` with the node axis sharded
across a mesh (parallel/mesh.py). ``axis_name`` threads the reduction points
through collectives — normalize-max (pmax), winner selection (pmax +
argmin-of-axis tie-break), valid-node count (psum), and the per-step segment
tables (psum of small [C,Vd] partials) — the P1/P7-style node-axis sharding
of SURVEY.md §2.7/§5.7.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..ops import filters, pallas_step, scores, topology
from ..ops.topology import INT_MAX, _gmax, _gmin, _gsum
from ..ops.schema import ExprTable, NodeTensors, PodBatch, TopoBatch, TopoCounts
from ..ops.select import NEG_INF


def _tb_dict(tb: TopoBatch) -> dict:
    """TopoBatch as the field dict the compiled programs consume (one
    definition shared by the scan xs and the speculative host path)."""
    return {f.name: getattr(tb, f.name) for f in dataclasses.fields(tb)}


def domain_axis(value_words: int, vd_override: Optional[int]) -> int:
    """The general topology mode's domain-axis size Vd: the involved keys'
    vocab bucket when the caller computed it, else the full per-key vocab
    padding (``value_words`` = ExprTable.bits' 32-bit words per key)."""
    return vd_override if vd_override else int(value_words) * 32


def pallas_mode(nt: NodeTensors, axis_name, topo_enabled: bool) -> Optional[str]:
    """'compiled' | 'interpret' | None. KTPU_PALLAS=0 disables, =interpret
    forces the interpreter lowering (CPU tests of the kernel path). Read
    OUTSIDE jit and passed in as a static argument — env changes must
    retrace, not be swallowed by the jit cache."""
    import os

    flag = os.environ.get("KTPU_PALLAS", "auto")
    if flag == "0":
        return None
    if not pallas_step.shapes_supported(
        nt.capacity, nt.allocatable.shape[1], nt.port_bits.shape[1],
        axis_name, topo_enabled,
    ):
        return None
    if flag == "interpret":
        return "interpret"
    return "compiled" if pallas_step.compile_supported() else None

# ---------------------------------------------------------------------------
# DRA claim-feasibility mask (resource.k8s.io structured parameters)
#
# One vmapped predicate over the pod axis: every pod row carries its merged
# class+claim selectors as (key column, op, operand kind, operand) int32
# quadruples; the node axis carries the device-attribute table DeviceState
# syncs from node-published slices ([N, A] kind/value cells). The semantics
# are api/dra.py's DeviceSelector.matches, evaluated for all (pod, node)
# pairs in one device call — claim-bearing pods stay on the batched path
# instead of falling back to the sequential oracle.

from ..api.dra import OP_EQ, OP_GE, OP_GT, OP_LE, OP_LT, OP_NE  # noqa: E402


@jax.jit
def claim_feasibility_mask(sel_key: jax.Array, sel_op: jax.Array,
                           sel_kind: jax.Array, sel_val: jax.Array,
                           attr_kind: jax.Array, attr_val: jax.Array) -> jax.Array:
    """[P, N] bool: node attribute table satisfies every selector of each pod.

    sel_* : [P, S] int32 selector rows, op == -1 padding (always matches);
    attr_kind/attr_val : [N, A] device-attribute cells (kind 0 = absent,
    1 = int, 2 = interned string id). Single source of truth for the
    predicate: api/dra.py (host) — this is its vectorized transcription."""

    def one_pod(keys, ops, okind, oval):
        ak = attr_kind[:, keys]                      # [N, S]
        av = attr_val[:, keys]
        present = ak > 0
        same = present & (ak == okind[None, :])
        num = present & (ak == 1) & (okind[None, :] == 1)
        ov = oval[None, :]
        ok = jnp.where(ops[None, :] == OP_EQ, same & (av == ov), False)
        ok = jnp.where(ops[None, :] == OP_NE, same & (av != ov), ok)
        ok = jnp.where(ops[None, :] == OP_GE, num & (av >= ov), ok)
        ok = jnp.where(ops[None, :] == OP_GT, num & (av > ov), ok)
        ok = jnp.where(ops[None, :] == OP_LE, num & (av <= ov), ok)
        ok = jnp.where(ops[None, :] == OP_LT, num & (av < ov), ok)
        return jnp.all(jnp.where(ops[None, :] >= 0, ok, True), axis=1)  # [N]

    return jax.vmap(one_pod)(sel_key, sel_op, sel_kind, sel_val)


# ---------------------------------------------------------------------------
# gang all-or-nothing verdicts (PodGroup / Coscheduling)
#
# One device call per batch carrying gangs: gathers each gang's member rows
# out of the batch program's outputs (node_idx = the program's per-member
# choices, first_fail == 0 = decision-time feasibility) and runs the greedy
# distinct-node assigner (ops/gang.py). The host commit reads three small
# arrays instead of walking [P, N] masks per gang.


@jax.jit
def gang_verdicts(node_idx: jax.Array, first_fail: jax.Array,
                  member_idx: jax.Array, member_valid: jax.Array):
    """``member_idx`` [G, M] int32 rows into the batch pod axis (-1 pad),
    ``member_valid`` [G, M] bool. Returns (placed_all [G] bool — the batch
    program placed every member, the commit verdict; kernel_ok [G] bool —
    a distinct-node cover exists on the decision-time masks; assign [G, M]
    int32 — the greedy assignment, equal to the program's choices whenever
    they are distinct and feasible)."""
    from ..ops.gang import assign_gangs

    p = node_idx.shape[0]
    safe = jnp.clip(member_idx, 0, p - 1)
    feasible = (first_fail[safe] == 0) & member_valid[..., None]
    prefer = jnp.where(member_valid, node_idx[safe], jnp.int32(-1))
    assign, kernel_ok = assign_gangs(feasible, prefer, member_valid)
    placed_all = jnp.all((node_idx[safe] >= 0) | ~member_valid, axis=1)
    return placed_all, kernel_ok, assign


# default plugin weights on the batched path (default_plugins.go:32-51)
DEFAULT_WEIGHTS = {
    "NodeResourcesBalancedAllocation": 1.0,
    "ImageLocality": 1.0,
    "NodeResourcesFit": 1.0,
    "NodeAffinity": 2.0,
    "TaintToleration": 3.0,
    "PodTopologySpread": 2.0,
    "InterPodAffinity": 2.0,
}


# The first-fail ids the program writes: id k names entry k-1, in filter
# config order (default_plugins.go); 0 = passes. Both drivers read them here.
ATTRIBUTION_ORDER = (
    ("NodeUnschedulable", "node(s) were unschedulable"),
    ("NodeName", "node(s) didn't match the requested node name"),
    ("TaintToleration", "node(s) had untolerated taint"),
    ("NodeAffinity", "node(s) didn't match Pod's node affinity/selector"),
    ("NodePorts", "node(s) didn't have free ports for the requested pod ports"),
    ("NodeResourcesFit", "Insufficient resources"),
    ("PodTopologySpread", "node(s) didn't match pod topology spread constraints"),
    ("InterPodAffinity", "node(s) didn't match pod affinity/anti-affinity rules"),
    ("VolumeBinding", "node(s) didn't satisfy volume placement"),
    ("DynamicResources", "cannot allocate all claims"),
    ("SlicePacking", "node(s) outside the gang's planned torus slice"),
)


class BatchResult(NamedTuple):
    node_idx: jax.Array      # [P] int32 chosen GLOBAL slot, -1 = unschedulable
    best_score: jax.Array    # [P] float32
    any_feasible: jax.Array  # [P] bool
    static_masks: Dict[str, jax.Array]  # plugin name -> [P, N] (for diagnosis)
    fit_ok: jax.Array        # [P, N] resource fit at decision time
    ports_ok: jax.Array      # [P, N] port availability at decision time
    spread_ok: jax.Array     # [P, N] PodTopologySpread filter at decision time
    ipa_ok: jax.Array        # [P, N] InterPodAffinity (all three checks)
    # [P, N] int8: 0 = feasible, else 1-based index into the filter config
    # order (ATTRIBUTION_ORDER) of the first failing plugin.
    # Diagnosis on the host is ONE device→host read of this array instead of
    # eight mask reads — each read is a blocking device read.
    first_fail: jax.Array
    # the scan's evolved carry: the post-batch dynamic node state. The host
    # adopts these (DeviceState.adopt_commits) so the next sync uploads
    # nothing for commit-only changes — and the async pipeline dispatches
    # batch k+1 directly on them (still-unmaterialized device futures) while
    # the host commits batch k.
    final_requested: Optional[jax.Array] = None      # [N, R] int32
    final_nonzero: Optional[jax.Array] = None        # [N, R] int32
    final_ports: Optional[jax.Array] = None          # [N, W] uint32
    # evolved topology carry (None on the pallas / topo-disabled paths)
    final_sel_counts: Optional[jax.Array] = None     # same shape as tc.sel_counts
    final_seg_exist: Optional[jax.Array] = None      # [T, Vd] int32
    # evolved priority-class table (preemption screen input), None on pallas
    final_class_req: Optional[jax.Array] = None      # [N, C, R] int32
    # evolved adaptive-sampling rotation start (None when sampling disabled)
    final_sample_start: Optional[jax.Array] = None   # [] int32
    # PACKED RESULT BLOCK: one contiguous [P, 1 + N/4] int32 array carrying
    # everything the host commit needs per pod — column 0 is node_idx, the
    # rest is the [P, N] int8 first_fail table bitcast to int32 words. The
    # scheduler issues copy_to_host_async() on THIS array at dispatch, so
    # landing a batch is a single already-overlapped transfer instead of
    # independent node_idx/first_fail materializations (each a blocking
    # device read). None on the sharded core path
    # (mesh.py), whose callers keep the per-array reads.
    packed: Optional[jax.Array] = None               # [P, 1 + ceil(N/4)] int32


def pack_result_block(node_idx: jax.Array, first_fail: jax.Array,
                      slice_words: Optional[jax.Array] = None,
                      quota_words: Optional[jax.Array] = None) -> jax.Array:
    """[P, 1 + ceil(N/4) (+extras)] int32: node_idx in column 0, the int8
    first_fail rows bitcast into int32 words after it, then the optional
    trailing verdict columns in fixed order — slice words (see _slice_plan)
    when the batch carried slice gangs, quota words (ops/quota.py) when it
    carried screened namespaces. Traced into the batch program
    (schedule_batch's jit), so the packing is free relative to a transfer:
    one fused device buffer replaces independent node_idx/first_fail/
    verdict host reads."""
    p, n = first_fail.shape
    pad = (-n) % 4
    if pad:
        first_fail = jnp.pad(first_fail, ((0, 0), (0, pad)))
    words = lax.bitcast_convert_type(
        first_fail.reshape(p, (n + pad) // 4, 4), jnp.int32)
    cols = [node_idx[:, None], words]
    if slice_words is not None:
        cols.append(slice_words[:, None])
    if quota_words is not None:
        cols.append(quota_words[:, None])
    return jnp.concatenate(cols, axis=1)


def unpack_result_block(packed, n_nodes: int, quota_col: bool = False):
    """(node_idx [P] int32, first_fail [P, N] int8, slice_words [P] int32 or
    None, quota_words [P] int32 or None) from one materialized packed block.
    The np.asarray here is THE blocking device read of a batch commit;
    everything after is host-side reinterpretation (the int32→int8 view
    matches lax.bitcast_convert_type byte order on both CPU and TPU —
    pinned by tests/test_kernel_parity.py). Trailing-column presence is
    inferred from the block width — two extras mean slice THEN quota (the
    pack order); exactly one is the quota column iff the dispatcher passed
    quota args (``quota_col``, threaded from the dispatch site), else the
    slice column. Verdict-free batches pay nothing."""
    arr = np.asarray(packed)
    node_idx = arr[:, 0]
    ff_words = (n_nodes + 3) // 4
    extras = arr.shape[1] - 1 - ff_words
    slice_words = quota_words = None
    if extras >= 2:
        slice_words = arr[:, 1 + ff_words]
        quota_words = arr[:, 2 + ff_words]
    elif extras == 1:
        if quota_col:
            quota_words = arr[:, 1 + ff_words]
        else:
            slice_words = arr[:, 1 + ff_words]
    ff = np.ascontiguousarray(arr[:, 1:1 + ff_words]).view(np.int8)
    return (node_idx, ff.reshape(arr.shape[0], -1)[:, :n_nodes],
            slice_words, quota_words)


def _pod_port_bits(pb: PodBatch, words: int) -> jax.Array:
    """[P, W] uint32: each pod's wanted-port ids as a bitset (for commit)."""
    P, MP = pb.port_ids.shape
    word_idx = (pb.port_ids >> 5).astype(jnp.int32)
    # np.uint32, not jnp.uint32: an in-trace jax scalar becomes a captured
    # device-buffer constant instead of an HLO constant (see ops/select.py
    # NEG_INF note)
    bit = jnp.where(pb.port_ids > 0, np.uint32(1) << (pb.port_ids & 31).astype(jnp.uint32), 0)
    out = jnp.zeros((P, words), jnp.uint32)
    # ids are deduplicated at encode time, so add == bitwise-or here
    return out.at[jnp.arange(P)[:, None], word_idx].add(bit)


def _normalize(raw: jax.Array, feasible: jax.Array, reverse: bool,
               axis_name=None, axis=None) -> jax.Array:
    """DefaultNormalizeScore over a pod's (global) feasible set. ``axis``
    batches it: per-row max instead of the global one (the speculative
    path's [P, N] form) — ONE implementation for both programs, whose
    outputs must match bit for bit."""
    masked = jnp.where(feasible, raw, 0.0)
    if axis is None:
        mx = _gmax(jnp.max(masked), axis_name)
    else:
        # batched form over a LOCAL node shard: per-row max, then the
        # cross-shard elementwise pmax (the speculative path under shard_map)
        mx = _gmax(jnp.max(masked, axis=axis, keepdims=True), axis_name)
    scaled = jnp.floor(raw * 100.0 / jnp.maximum(mx, 1.0))
    if reverse:
        return jnp.where(mx == 0, 100.0, 100.0 - scaled)
    return jnp.where(mx == 0, 0.0, scaled)


def _resource_scores(alloc2: jax.Array, nz_total: jax.Array):
    """(LeastAllocated, BalancedAllocation) over the first two resource
    columns — shared by the scan step ([N, 2] inputs) and the speculative
    rounds ([P, N, 2] via broadcasting); formulas per SURVEY §8."""
    cap0, cap1 = alloc2[..., 0], alloc2[..., 1]
    r0, r1 = nz_total[..., 0], nz_total[..., 1]
    la0 = jnp.where((cap0 == 0) | (r0 > cap0), 0.0,
                    jnp.floor((cap0 - r0) * 100.0 / jnp.maximum(cap0, 1.0)))
    la1 = jnp.where((cap1 == 0) | (r1 > cap1), 0.0,
                    jnp.floor((cap1 - r1) * 100.0 / jnp.maximum(cap1, 1.0)))
    least_alloc = jnp.floor((la0 + la1) / 2.0)
    f0 = jnp.where(cap0 == 0, 1.0, jnp.minimum(1.0, r0 / jnp.maximum(cap0, 1.0)))
    f1 = jnp.where(cap1 == 0, 1.0, jnp.minimum(1.0, r1 / jnp.maximum(cap1, 1.0)))
    balanced = jnp.floor((1.0 - jnp.abs(f0 - f1) / 2.0) * 100.0)
    return least_alloc, balanced


def _speculative_core(pb, nt, weights, static_ok, static_ff, taint_raw,
                      affinity_raw, image_score, pod_bits, jitter,
                      sel0, seg0, host=None, gen=None,
                      axis_name=None, slot_offset=None,
                      ports_enabled: bool = True) -> BatchResult:
    """Speculative decode for non-topology batches (ROADMAP r3 perf 2).

    The scan commits one pod per step — P dependent steps whose per-step
    latency dominates device time at large batches. This path replaces it
    with a few vectorized decide/commit rounds while reproducing the scan's
    sequential semantics EXACTLY:

    each round, every unplaced pod scores all nodes against the current
    state and picks its argmax; the per-node winners (lowest pod index) form
    a tentative set whose picks are pairwise-DISTINCT nodes. A pod is
    FINALIZABLE this round when it either fails (no feasible node — more
    commits can only shrink feasibility, so its sequential turn fails too),
    or wins its node AND no node committed by an earlier winner now beats
    its choice (commits can RAISE a node's score — balanced-allocation —
    so this stability check guards the argmax). The round then finalizes
    only the PREFIX of pods before the first active non-finalizable index:
    every finalized pod's visible state is exactly the commits of
    lower-index pods — the scan's sequential semantics, bit for bit. The
    next round's first active pod always finalizes (it wins its node by
    index-minimality and has no earlier rivals), so each round retires ≥1
    pod and the while_loop terminates in ≤P rounds (typically ~P/(first-
    conflict index) rounds: distinct jitter spreads identical pods).

    ``host`` (optional) extends the rounds to the HOSTNAME topology fast
    path (ops/topology.py *_host): every topology table is [*, N] node-
    local there, so the same rival-mix trick yields each pod's exact
    sequential view of spread/inter-pod-affinity state. Keys: the TopoBatch
    field dict, hostkey_ok [N], affinity_ok [P, N] (the NodeAffinity static
    mask the spread filter's eligibility uses).

    ``gen`` (optional, exclusive with ``host``) extends them to the GENERAL
    domain-aggregating mode: sel_counts stays node-local (rival-mix), and
    the domain segment sums recompute per pod from the mixed counts
    (vmapped segment sums over small [P, C, Vd] tables), so every
    sel-derived quantity is each pod's exact sequential view. The
    seg_exist table ([T, Vd], domain-level) cannot be rival-mixed; instead
    a winner whose view could be touched by an earlier winner's TERM commit
    (the committing pod carries a term that interacts with this pod —
    rare: intra-batch anti-affinity/symmetric-score coupling) is
    conservatively DEFERRED to the next round, where the committed tables
    are ground truth. Keys: tb dict, affinity_ok, dom_t [T, N],
    label_val [N, L], valid [N]; the domain axis Vd is seg0's."""
    P = pb.capacity
    N = nt.capacity  # LOCAL shard size under shard_map
    alloc = nt.allocatable
    alloc_f = alloc.astype(jnp.float32)
    iota_p = jnp.arange(P, dtype=jnp.int32)
    iota_n = jnp.arange(N, dtype=jnp.int32)
    # ---- sharding seams (SURVEY §5.7: per-shard work + tiny collectives).
    # The rounds shard exactly like the scan: node-axis state is local, the
    # per-pod [P] decision vectors (choice/accepted/prefix cut) are made
    # globally consistent through elementwise pmax/pmin/psum, so every shard
    # runs the same number of rounds and finalizes the same prefix. The
    # HOSTNAME topology mode shards too — its tables are [*, N] node-local,
    # so rival-mixing is shard-local and only the per-pod reductions
    # (spread min-match, IPA totals, score normalization) psum/pmax across
    # shards. The general domain-aggregating mode keeps the scan on a mesh
    # (its segment tables are domain-global).
    # every topology mode shards: node-axis state is local; domain tables
    # psum to a replicated global view (_seg_pc); per-pod decisions are
    # made globally consistent below
    if slot_offset is None:
        slot_offset = np.int32(0)
    shard_axis = (lax.axis_index(axis_name).astype(jnp.int32)
                  if axis_name is not None else np.int32(0))

    def _gany_pods(x_bool):
        """[P] bool: any() across shards (elementwise)."""
        if axis_name is None:
            return x_bool
        return _gmax(x_bool.astype(jnp.int32), axis_name) > 0

    def _gpick(local_vals, mine, dtype=jnp.float32):
        """[P] owner-shard values → globally consistent [P] (one owner per
        pod: psum of the masked value)."""
        if axis_name is None:
            return local_vals
        return _gsum(jnp.where(mine, local_vals, jnp.zeros((), dtype)),
                     axis_name)

    def _gdom_of_choice(dom_table, local_choice, mine):
        """[T, P]: domain id of each pod's CHOSEN node. dom_table's node
        axis is shard-local, so the owner shard gathers and the result
        psums to every shard (the general mode's term-commit scatter and
        deferral matrices must see the same global domains everywhere)."""
        T = dom_table.shape[0]
        local = jnp.take_along_axis(
            dom_table,
            jnp.broadcast_to(local_choice[None, :], (T, local_choice.shape[0])),
            axis=1)
        if axis_name is None:
            return local
        return _gsum(jnp.where(mine[None, :], local, 0), axis_name)

    def _global_argmax(eff):
        """Per-pod argmax over the GLOBAL node axis: (choice in global slot
        ids, local column, mine[P] = this shard owns the winner). Ties
        resolve to the lowest shard then the local argmax — with the global
        jitter table this reproduces the single-device pick exactly."""
        local_idx = jnp.argmax(eff, axis=1).astype(jnp.int32)
        if axis_name is None:
            return local_idx, local_idx, jnp.ones((P,), bool)
        local_best = jnp.take_along_axis(eff, local_idx[:, None], 1)[:, 0]
        global_best = _gmax(local_best, axis_name)
        winner_axis = _gmin(
            jnp.where(local_best >= global_best, shard_axis, np.int32(2 ** 30)),
            axis_name)
        mine = winner_axis == shard_axis
        choice = _gsum(jnp.where(mine, local_idx + slot_offset, 0),
                       axis_name).astype(jnp.int32)
        return choice, local_idx, mine

    if axis_name is None:
        is_nom = iota_n[None, :] == pb.nominated[:, None]      # [P, N]
    else:
        is_nom = (iota_n[None, :] + slot_offset) == pb.nominated[:, None]
    w_fit = np.float32(weights["NodeResourcesFit"])
    w_bal = np.float32(weights["NodeResourcesBalancedAllocation"])
    w_taint = np.float32(weights["TaintToleration"])
    w_aff = np.float32(weights["NodeAffinity"])
    w_img = np.float32(weights["ImageLocality"])
    w_spread = np.float32(weights["PodTopologySpread"])
    w_ipa = np.float32(weights["InterPodAffinity"])
    def _mix_gather(base_table, delta_table, rows, rival):
        """Per-pod gathered counts with this round's earlier-winner column
        deltas applied on rival nodes — THE rival-mix formula, defined once
        for the host filters/scores and the gen segment paths."""
        base = base_table[rows]                                  # [P, C, N]
        if rival is None:
            return base
        return base + delta_table[rows] * rival[:, None, :]

    def _spread_norm(raw, base_mask, ignored, has_cons):
        """Spread score normalization (scoring.go:232-271), shared by the
        host and general batched paths (must stay bit-identical). Under
        shard_map the per-pod max/min reduce over the GLOBAL node axis."""
        mx = _gmax(jnp.max(jnp.where(base_mask, raw, -jnp.inf),
                           axis=1, keepdims=True), axis_name)
        mn = _gmin(jnp.min(jnp.where(base_mask, raw, jnp.inf),
                           axis=1, keepdims=True), axis_name)
        any_base = _gany_pods(jnp.any(base_mask, axis=1, keepdims=True))
        norm = jnp.where(mx == 0, 100.0,
                         jnp.floor(100.0 * (mx + mn - raw) / jnp.maximum(mx, 1.0)))
        norm = jnp.where(ignored | ~any_base, 0.0, norm)
        return jnp.where(has_cons, norm, 0.0)

    def _ipa_norm(raw, feasible):
        """IPA score normalization (clamped min/max), shared likewise."""
        mx = jnp.maximum(
            _gmax(jnp.max(jnp.where(feasible, raw, -jnp.inf),
                          axis=1, keepdims=True), axis_name),
            0.0)
        mn = jnp.minimum(
            _gmin(jnp.min(jnp.where(feasible, raw, jnp.inf),
                          axis=1, keepdims=True), axis_name),
            0.0)
        diff = mx - mn
        return jnp.where(
            diff > 0, jnp.floor(100.0 * (raw - mn) / jnp.maximum(diff, 1.0)), 0.0)

    if host is not None:
        tbx, hostkey_ok, affinity_ok = (
            host["tb"], host["hostkey_ok"], host["affinity_ok"])
        sig_mask_f = tbx["pod_sig_mask"].astype(jnp.int32)      # [P, S]
        term_mask_f = tbx["pod_term_mask"].astype(jnp.int32)    # [P, T]
        hk_f = hostkey_ok.astype(jnp.int32)                     # [N]
    if gen is not None:
        tbx, affinity_ok = gen["tb"], gen["affinity_ok"]
        vd = seg0.shape[1]             # domain axis of the [T, Vd] table
        dom_t = gen["dom_t"]                                    # [T, N]
        label_val = gen["label_val"]                            # [N, L]
        valid_n = gen["valid"]                                  # [N]
        sig_mask_f = tbx["pod_sig_mask"].astype(jnp.int32)      # [P, S]
        term_mask_f = tbx["pod_term_mask"].astype(jnp.int32)    # [P, T]

        def _dom_of(keys):
            # [P, C, N]: domain id of node n under each constraint's key
            return label_val.T[keys]                            # gather rows

        # domain ids depend only on each constraint's key and the node
        # labels, which cannot change inside a batch: read them once here,
        # outside the rounds, not twice per round in assemble()
        dom_sf = _dom_of(tbx["sf_key"])                         # [P, C, N]
        dom_ia = _dom_of(tbx["ia_key"])                         # [P, A, N]
        dom_an = _dom_of(tbx["ianti_key"])
        dom_ss = _dom_of(tbx["ss_key"])
        dom_ip = _dom_of(tbx["ip_key"])

        def _seg_pc(values, dom):
            """[P, C, N] values segment-summed by [P, C, N] domain ids →
            [P, C, Vd]: topology.seg_sum, the one helper the scan shares
            (one-hot reduce for compact domains, scatter past
            topology.ONEHOT_MAX_VD). Under shard_map the node axis is a
            local slice, so the per-domain sums psum across shards — the
            result is the GLOBAL domain table, replicated."""
            return topology.seg_sum(values, dom, vd, axis_name)


    def topo_eval(sel_view, term_view, rival, active):
        """Host-mode spread/IPA filters from a (possibly per-pod mixed)
        view: sel_view/term_view = (base [S|T, N], round-delta [S|T, N]);
        rival [P, N] selects where the delta applies (None = base only)."""
        sel_base, sel_d = sel_view
        term_base, term_d = term_view


        valid_n = nt.valid
        # ---- spread filter (topology.spread_filter_host)
        elig = valid_n[None, :] & affinity_ok & hostkey_ok[None, :] \
            & active[:, None]                                    # [P, N]
        # NOTE: the scan's elig has no `active` term — it is per-pod anyway;
        # masking by active only skips work for done pods (their rows are
        # never read) and keeps reductions well-defined.
        cnt_sf = _mix_gather(sel_base, sel_d, tbx["sf_sig"], rival)           # [P, C, N]
        # global reductions over the (possibly sharded) node axis
        minm = _gmin(jnp.min(jnp.where(elig[:, None, :], cnt_sf, INT_MAX),
                             axis=2), axis_name)
        ndom = _gsum(jnp.sum(elig.astype(jnp.int32), axis=1), axis_name)  # [P]
        any_pres = ndom > 0
        minm = jnp.where(any_pres[:, None], minm, 0)
        minm = jnp.where((tbx["sf_min_domains"] >= 0)
                         & (ndom[:, None] < tbx["sf_min_domains"]), 0, minm)
        ok_c = hostkey_ok[None, None, :] & (
            cnt_sf + tbx["sf_self"][:, :, None].astype(jnp.int32)
            - minm[:, :, None] <= tbx["sf_skew"][:, :, None])
        spread_ok = jnp.all(
            jnp.where(tbx["sf_valid"][:, :, None], ok_c, True), axis=1)

        # ---- IPA filter (topology.ipa_filter_host)
        cnt_ia = _mix_gather(sel_base, sel_d, tbx["ia_sig"], rival)           # [P, A, N]
        exist = hostkey_ok[None, None, :] & (cnt_ia > 0)
        ia_valid = tbx["ia_valid"]
        pods_exist = jnp.all(
            jnp.where(ia_valid[:, :, None], exist, True), axis=1)
        all_keys = jnp.all(
            jnp.where(ia_valid[:, :, None], hostkey_ok[None, None, :], True),
            axis=1)
        tot_mask = (ia_valid[:, :, None] & valid_n[None, None, :]
                    & hostkey_ok[None, None, :])
        total = _gsum(jnp.sum(jnp.where(tot_mask, cnt_ia, 0), axis=(1, 2)),
                      axis_name)  # [P], global over shards
        first_ok = (total == 0) & tbx["ia_self_all"]
        has_terms = jnp.any(ia_valid, axis=1)
        aff_ok = (~has_terms[:, None]) | (
            all_keys & (pods_exist | first_ok[:, None]))
        cnt_an = _mix_gather(sel_base, sel_d, tbx["ianti_sig"], rival)        # [P, A, N]
        viol = jnp.any(tbx["ianti_valid"][:, :, None]
                       & hostkey_ok[None, None, :] & (cnt_an > 0), axis=1)
        anti_ok = ~viol
        # existing-term anti check: [P,T]x[T,N] matmuls keep the [P,T,N]
        # tensor virtual (T can be large)
        m = tbx["term_filter_match"].astype(jnp.int32)           # [P, T]
        viol_cnt = m @ (term_base * hk_f[None, :])
        if rival is not None:
            viol_cnt = viol_cnt + (m @ (term_d * hk_f[None, :])) * rival
        exist_ok = viol_cnt == 0
        ipa_ok = aff_ok & anti_ok & exist_ok
        return spread_ok, ipa_ok

    def topo_scores(sel_view, term_view, rival, feasible):
        """Host-mode spread/IPA scores (topology.spread_score_host /
        ipa_score_host) against the same view, normalized per pod over its
        feasible set."""
        sel_base, sel_d = sel_view
        term_base, term_d = term_view


        # spread score
        ignored = tbx["ss_require_all"][:, None] & ~hostkey_ok[None, :]
        base_mask = feasible & ~ignored                          # [P, N]
        n_base = _gsum(jnp.sum(base_mask.astype(jnp.int32), axis=1),
                       axis_name)                                # [P] global
        w = jnp.log(n_base.astype(jnp.float32) + 2.0)[:, None]   # [P, 1]
        cnt_ss = _mix_gather(sel_base, sel_d, tbx["ss_sig"], rival).astype(jnp.float32)        # [P, C, N]
        contrib = jnp.where(
            tbx["ss_valid"][:, :, None] & hostkey_ok[None, None, :],
            cnt_ss * w[:, :, None]
            + (tbx["ss_skew"][:, :, None].astype(jnp.float32) - 1.0),
            0.0)
        raw = jnp.floor(jnp.sum(contrib, axis=1) + 0.5)          # [P, N]
        spread_score = _spread_norm(
            raw, base_mask, ignored, jnp.any(tbx["ss_valid"], axis=1)[:, None])

        # IPA score
        cnt_ip = _mix_gather(sel_base, sel_d, tbx["ip_sig"], rival).astype(jnp.float32)        # [P, PT, N]
        pref = jnp.sum(
            jnp.where(tbx["ip_valid"][:, :, None] & hostkey_ok[None, None, :],
                      tbx["ip_w"][:, :, None].astype(jnp.float32) * cnt_ip,
                      0.0),
            axis=1)                                              # [P, N]
        tsw = tbx["term_score_w"]                                # [P, T] f32
        hk_ff = hk_f.astype(jnp.float32)
        sym = tsw @ (term_base.astype(jnp.float32) * hk_ff[None, :])
        if rival is not None:
            sym = sym + (tsw @ (term_d.astype(jnp.float32)
                                * hk_ff[None, :])) * rival
        raw_ip = pref + sym
        return spread_score, _ipa_norm(raw_ip, feasible)

    def topo_eval_gen(sel_view, seg_base, rival, active):
        """General-mode spread/IPA filters, batched over pods: every
        sel-derived quantity recomputes from the (rival-mixed) per-pod
        counts, matching topology.spread_filter/ipa_filter exactly. The
        seg_exist check (existing pods' anti-affinity vs the incoming pod)
        is evaluated against the ROUND-START table; rounds where that could
        diverge defer the affected winners (term-interaction deferral in
        body())."""
        sel_base, sel_d = sel_view

        # ---- spread filter (topology.spread_filter)
        has_key = dom_sf > 0
        has_all = jnp.all(jnp.where(tbx["sf_valid"][:, :, None], has_key, True),
                          axis=1)                                # [P, N]
        elig = valid_n[None, :] & affinity_ok & has_all & active[:, None]
        cnts = _mix_gather(sel_base, sel_d, tbx["sf_sig"], rival)
        add = jnp.where(elig[:, None, :] & has_key, cnts, 0)
        seg = _seg_pc(add, dom_sf)                               # [P, C, Vd]
        pres = _seg_pc(jnp.broadcast_to(
            elig[:, None, :], dom_sf.shape).astype(jnp.int32), dom_sf) > 0
        minm = jnp.min(jnp.where(pres, seg, INT_MAX), axis=2)    # [P, C]
        any_pres = jnp.any(pres, axis=2)
        minm = jnp.where(any_pres, minm, 0)
        ndom = jnp.sum(pres.astype(jnp.int32), axis=2)
        minm = jnp.where((tbx["sf_min_domains"] >= 0)
                         & (ndom < tbx["sf_min_domains"]), 0, minm)
        cnt_at = topology.seg_read(seg, dom_sf)                  # [P, C, N]
        ok_c = has_key & (cnt_at + tbx["sf_self"][:, :, None].astype(jnp.int32)
                          - minm[:, :, None] <= tbx["sf_skew"][:, :, None])
        spread_ok = jnp.all(
            jnp.where(tbx["sf_valid"][:, :, None], ok_c, True), axis=1)

        # ---- IPA filter checks 1+2 (topology.ipa_filter)
        ia_has_key = dom_ia > 0
        ia_valid = tbx["ia_valid"]
        cnts_ia = _mix_gather(sel_base, sel_d, tbx["ia_sig"], rival)
        add_ia = jnp.where(valid_n[None, None, :] & ia_has_key, cnts_ia, 0)
        seg_ia = _seg_pc(add_ia, dom_ia)                         # [P, A, Vd]
        cnt_at_ia = topology.seg_read(seg_ia, dom_ia)
        exist = cnt_at_ia > 0
        pods_exist = jnp.all(jnp.where(ia_valid[:, :, None], exist, True), axis=1)
        all_keys = jnp.all(jnp.where(ia_valid[:, :, None], ia_has_key, True),
                           axis=1)
        total = jnp.sum(jnp.where(ia_valid[:, :, None], seg_ia, 0), axis=(1, 2))
        first_ok = (total == 0) & tbx["ia_self_all"]
        has_terms = jnp.any(ia_valid, axis=1)
        aff_ok = (~has_terms[:, None]) | (
            all_keys & (pods_exist | first_ok[:, None]))

        an_has_key = dom_an > 0
        cnts_an = _mix_gather(sel_base, sel_d, tbx["ianti_sig"], rival)
        add_an = jnp.where(valid_n[None, None, :] & an_has_key, cnts_an, 0)
        seg_an = _seg_pc(add_an, dom_an)
        an_cnt = topology.seg_read(seg_an, dom_an)
        viol = jnp.any(tbx["ianti_valid"][:, :, None] & an_has_key
                       & (an_cnt > 0), axis=1)
        anti_ok = ~viol

        # ---- IPA check 3 against the ROUND-START seg_exist (deferral
        # covers the divergence window)
        exist_at = jnp.where(dom_t > 0,
                             topology.seg_read(seg_base, dom_t), 0)  # [T, N]
        m = tbx["term_filter_match"].astype(jnp.int32)           # [P, T]
        viol_cnt = m @ exist_at
        exist_ok = viol_cnt == 0
        ipa_ok = aff_ok & anti_ok & exist_ok
        return spread_ok, ipa_ok, exist_at

    def topo_scores_gen(sel_view, exist_at, rival, feasible):
        """General-mode spread/IPA scores (topology.spread_score/ipa_score),
        batched; the symmetric existing-term score uses the round-start
        exist_at (deferral covers divergence)."""
        sel_base, sel_d = sel_view

        # spread score
        has_key = dom_ss > 0
        ss_valid = tbx["ss_valid"]
        has_all = jnp.all(jnp.where(ss_valid[:, :, None], has_key, True), axis=1)
        require_all = tbx["ss_require_all"][:, None]             # [P, 1]
        ignored = require_all & ~has_all                         # [P, N]
        base_mask = feasible & ~ignored
        pres = _seg_pc(jnp.broadcast_to(
            base_mask[:, None, :], dom_ss.shape).astype(jnp.int32), dom_ss) > 0
        sz = jnp.sum(pres.astype(jnp.int32), axis=2)             # [P, C]
        n_base = _gsum(jnp.sum(base_mask.astype(jnp.int32), axis=1),
                       axis_name)                                # [P] global
        sz = jnp.where(tbx["ss_hostname"], n_base[:, None], sz)
        w = jnp.log(sz.astype(jnp.float32) + 2.0)                # [P, C]
        elig = (valid_n[None, :] & affinity_ok
                & jnp.where(require_all, has_all, True))         # [P, N]
        cnts = _mix_gather(sel_base, sel_d, tbx["ss_sig"], rival)
        add = jnp.where(elig[:, None, :] & has_key, cnts, 0)
        seg = _seg_pc(add, dom_ss)
        cnt_at = topology.seg_read(seg, dom_ss)
        cnt = jnp.where(tbx["ss_hostname"][:, :, None], cnts, cnt_at) \
            .astype(jnp.float32)
        contrib = jnp.where(
            ss_valid[:, :, None] & has_key,
            cnt * w[:, :, None]
            + (tbx["ss_skew"][:, :, None].astype(jnp.float32) - 1.0),
            0.0)
        raw = jnp.floor(jnp.sum(contrib, axis=1) + 0.5)          # [P, N]
        spread_score = _spread_norm(
            raw, base_mask, ignored, jnp.any(ss_valid, axis=1)[:, None])

        # IPA score
        ip_has_key = dom_ip > 0
        cnts_ip = _mix_gather(sel_base, sel_d, tbx["ip_sig"], rival)
        add_ip = jnp.where(valid_n[None, None, :] & ip_has_key, cnts_ip, 0)
        seg_ip = _seg_pc(add_ip, dom_ip)
        cnt_at_ip = topology.seg_read(seg_ip, dom_ip).astype(jnp.float32)
        pref = jnp.sum(
            jnp.where(tbx["ip_valid"][:, :, None] & ip_has_key,
                      tbx["ip_w"][:, :, None].astype(jnp.float32) * cnt_at_ip,
                      0.0),
            axis=1)
        sym = tbx["term_score_w"] @ exist_at.astype(jnp.float32)  # [P, N]
        raw_ip = pref + sym
        return spread_score, _ipa_norm(raw_ip, feasible)

    def components(req_dyn, nz_dyn, port_dyn):
        """State-dependent per-(pod,node) pieces: (fit, ports, la, balanced)."""
        free = alloc[None, :, :] - req_dyn[None, :, :]          # broadcast [P]
        fit = jnp.all((pb.req[:, None, :] <= free) | (pb.req[:, None, :] == 0),
                      axis=-1)                                   # [P, N]
        if ports_enabled:
            conflict = jnp.any(port_dyn[None, :, :] & pod_bits[:, None, :],
                               axis=-1)
            ports = ~conflict
        else:
            # no pod in the batch wants a host port: the [P, N, W] conflict
            # tensor (the single largest intermediate in the round) is a
            # constant — skip it at trace time
            ports = jnp.ones(fit.shape, bool)
        nz = nz_dyn[None, :, :2].astype(jnp.float32) \
            + pb.nonzero_req[:, None, :2].astype(jnp.float32)    # [P, N, 2]
        least_alloc, balanced = _resource_scores(alloc_f[None, :, :2], nz)
        return fit, ports, least_alloc, balanced

    def assemble(fit, ports, least_alloc, balanced, active,
                 sel_view=None, term_view=None, rival=None):
        """(eff incl. jitter+nominated boost, feasible, total, spread_ok,
        ipa_ok) from the components — per-pod DefaultNormalizeScore over the
        feasible set; host mode adds the topology filters to feasibility and
        the topology scores to the total (same order as the scan step)."""
        feasible = static_ok & fit & ports & active[:, None]
        exist_at = None
        if host is not None:
            spread_ok, ipa_ok = topo_eval(sel_view, term_view, rival, active)
            feasible = feasible & spread_ok & ipa_ok
        elif gen is not None:
            spread_ok, ipa_ok, exist_at = topo_eval_gen(
                sel_view, term_view[0], rival, active)
            feasible = feasible & spread_ok & ipa_ok
        else:
            spread_ok = ipa_ok = None
        taint_n = _normalize(jnp.broadcast_to(taint_raw, feasible.shape),
                             feasible, True, axis_name=axis_name, axis=1)
        aff_n = _normalize(jnp.broadcast_to(affinity_raw, feasible.shape),
                           feasible, False, axis_name=axis_name, axis=1)
        total = (w_fit * least_alloc + w_bal * balanced + w_taint * taint_n
                 + w_aff * aff_n + w_img * image_score)
        if host is not None:
            sp_s, ip_s = topo_scores(sel_view, term_view, rival, feasible)
            total = total + w_spread * sp_s + w_ipa * ip_s
        elif gen is not None:
            sp_s, ip_s = topo_scores_gen(sel_view, exist_at, rival, feasible)
            total = total + w_spread * sp_s + w_ipa * ip_s
        eff = jnp.where(feasible, total + jitter + is_nom * np.float32(1e7),
                        NEG_INF)
        return eff, feasible, total, spread_ok, ipa_ok

    def body(carry):
        (req_dyn, nz_dyn, port_dyn, sel_dyn, term_dyn, done, out_idx, best,
         anyf_out, fit_out, ports_out, spread_out, ipa_out, ff_out,
         _progress) = carry
        active = ~done & pb.valid
        fit, ports, la, bal = components(req_dyn, nz_dyn, port_dyn)
        eff, feasible, total, _sp, _ip = assemble(
            fit, ports, la, bal, active,
            sel_view=(sel_dyn, None), term_view=(term_dyn, None))
        any_f = _gany_pods(jnp.any(feasible, axis=1))           # [P]
        choice, local_choice, mine = _global_argmax(eff)        # [P] global ids
        failing = active & ~any_f

        # ---- tentative winners: lowest pod index per chosen node (each
        # node lives on exactly one shard, so the per-node min and the
        # winner check run on the owner shard; the accepted vector is then
        # made globally consistent)
        contender = active & any_f
        # per-node min over a [P, N] one-hot, not a scatter-min: no scatter
        # runs inside the rounds (TPU scatters serialise over their updates)
        picks = ((iota_n[None, :] == local_choice[:, None])
                 & (contender & mine)[:, None])                  # [P, N]
        win = jnp.min(jnp.where(picks, iota_p[:, None], P), axis=0)
        accepted = _gany_pods(contender & mine & (win[local_choice] == iota_p))

        # ---- exact stability: rebuild each winner i's SEQUENTIAL view.
        # The only nodes whose state differs at i's sequential turn are the
        # RIVALS (nodes committed this round by winners j<i, each carrying
        # exactly its own delta — picks are distinct; in host mode the
        # topology tables are node-local too, so the same rival masking
        # covers sel_counts/term_counts). Mixing post-commit components on
        # rival nodes with round-start components elsewhere, then re-running
        # the per-pod normalization (whose max couples every node's score to
        # the feasible SET), reproduces the scan's exact eff surface for pod
        # i; the winner finalizes only if its argmax is unmoved.
        # local one-hot: only the shard owning a winner's node applies its
        # delta (mine); rival columns are node-local so each shard mixes
        # exactly its own nodes' post-commit state
        onehot = ((iota_n[None, :] == local_choice[:, None])
                  & accepted[:, None] & mine[:, None])           # [P, N_local]
        d_req = jnp.sum(onehot[:, :, None] * pb.req[:, None, :], axis=0)
        d_nz = jnp.sum(onehot[:, :, None] * pb.nonzero_req[:, None, :], axis=0)
        committed_any = jnp.any(onehot, axis=0)                  # [N]
        if ports_enabled:
            d_ports = jnp.sum(
                jnp.where(onehot[:, :, None], pod_bits[:, None, :], 0),
                axis=0).astype(jnp.uint32)
            port_mixed = port_dyn | d_ports
        else:
            port_mixed = port_dyn
        fit2, ports2, la2, bal2 = components(
            req_dyn + d_req, nz_dyn + d_nz, port_mixed)
        rival = committed_any[None, :] & (win[None, :] < iota_p[:, None])
        topo_on = host is not None or gen is not None
        if topo_on:
            onehot_i = onehot.astype(jnp.int32)
            csig = jnp.einsum("ps,pn->sn", sig_mask_f, onehot_i)
            cterm = (jnp.einsum("pt,pn->tn", term_mask_f, onehot_i)
                     if host is not None else None)
        else:
            csig = cterm = None
        fit_mix = jnp.where(rival, fit2, fit)
        ports_mix = jnp.where(rival, ports2, ports)
        eff_mix, feas_mix, tot_mix, sp_mix, ip_mix = assemble(
            fit_mix, ports_mix,
            jnp.where(rival, la2, la), jnp.where(rival, bal2, bal), active,
            sel_view=(sel_dyn, csig), term_view=(term_dyn, cterm),
            rival=rival.astype(jnp.int32) if topo_on else None)
        choice_mix, _local_mix, _mine_mix = _global_argmax(eff_mix)
        chosen_feas_mix = _gany_pods(
            mine & jnp.take_along_axis(feas_mix, local_choice[:, None], 1)[:, 0])
        # ~chosen_feas_mix guards the degenerate all-infeasible mix (IPA's
        # first-pod rule can flip globally): argmax over an all-NEG_INF row
        # returns 0, which would read as "stable" for a pod whose round-
        # start choice was slot 0. An infeasible-in-mix winner defers and
        # re-evaluates (usually failing) next round.
        unstable = accepted & ((choice_mix != choice) | ~chosen_feas_mix)
        if gen is not None:
            # seg_exist deferral: the mixed view evaluates existing-term
            # state against the ROUND-START table, so a winner i whose
            # filters/scores could be touched by an earlier winner j's TERM
            # commit must wait a round. add_term[t, j] = does accepted j's
            # commit add term t at a keyed domain; interaction = pod i's
            # anti-match or symmetric-score weight on that term.
            dcol = _gdom_of_choice(dom_t, local_choice, mine)    # [T, P]
            add_term = (term_mask_f.T * (dcol > 0)
                        * accepted[None, :].astype(jnp.int32))   # [T, P]
            m_int = tbx["term_filter_match"].astype(jnp.int32)   # [P, T]
            w_abs = jnp.abs(tbx["term_score_w"])                 # [P, T]
            interacts = ((m_int @ add_term) > 0) | (
                (w_abs @ add_term.astype(jnp.float32)) > 0)      # [P(i), P(j)]
            j_lt_i = iota_p[None, :] < iota_p[:, None]
            deferred = jnp.any(interacts & j_lt_i, axis=1)
            unstable = unstable | (accepted & deferred)
        # decision-time rows for the outputs: mixed values ARE each pod's
        # sequential view (for failing pods rival is empty, so mix ==
        # round-start — exact either way)
        ff_mix = static_ff
        ff_mix = jnp.where((ff_mix == 0) & ~ports_mix, np.int8(5), ff_mix)
        ff_mix = jnp.where((ff_mix == 0) & ~fit_mix, np.int8(6), ff_mix)
        if host is not None or gen is not None:
            ff_mix = jnp.where((ff_mix == 0) & ~sp_mix, np.int8(7), ff_mix)
            ff_mix = jnp.where((ff_mix == 0) & ~ip_mix, np.int8(8), ff_mix)

        # ---- strict prefix finalization: a pod may finalize only when every
        # lower-index active pod finalizes too, so each finalized pod's
        # visible state is exactly the commits of lower-index pods (the
        # scan's sequential contract). A failing pod's recorded masks are
        # round-start state, so it may only finalize BEFORE the round's
        # first winner (otherwise its decision-time state would include
        # same-round commits the masks don't show) — it retries next round,
        # where it is first and exact. The cut lands at the first active
        # non-finalizable index.
        a_min = jnp.min(jnp.where(accepted, iota_p, P))
        failing = failing & (iota_p < a_min)
        finalizable = failing | (accepted & ~unstable)
        blocked = active & ~finalizable
        cut = jnp.min(jnp.where(blocked, iota_p, P))
        in_prefix = iota_p < cut
        failing = failing & in_prefix
        accepted = accepted & ~unstable & in_prefix

        # ---- apply the finalized prefix (local one-hot: owner shard only)
        onehot = ((iota_n[None, :] == local_choice[:, None])
                  & accepted[:, None] & mine[:, None])
        req_dyn = req_dyn + jnp.sum(onehot[:, :, None] * pb.req[:, None, :], axis=0)
        nz_dyn = nz_dyn + jnp.sum(onehot[:, :, None] * pb.nonzero_req[:, None, :],
                                  axis=0)
        if ports_enabled:
            port_dyn = port_dyn | jnp.sum(
                jnp.where(onehot[:, :, None], pod_bits[:, None, :], 0),
                axis=0).astype(jnp.uint32)
        if host is not None:
            onehot_i = onehot.astype(jnp.int32)
            sel_dyn = sel_dyn + jnp.einsum("ps,pn->sn", sig_mask_f, onehot_i)
            term_dyn = term_dyn + jnp.einsum("pt,pn->tn", term_mask_f, onehot_i)
        elif gen is not None:
            onehot_i = onehot.astype(jnp.int32)
            sel_dyn = sel_dyn + jnp.einsum("ps,pn->sn", sig_mask_f, onehot_i)
            # seg_exist: each finalized pod's terms land at its node's
            # domains (topology.commit_update's one-hot add, batched over
            # the round's pods: a segment sum over P)
            # the [T, Vd] seg table is REPLICATED: every shard must apply
            # the identical add (dcol is already global, so no psum).
            # Reuse the deferral block's dcol — its inputs (local_choice,
            # mine) are unchanged, and recomputing would pay the [T, P]
            # gather + cross-shard psum twice per round
            add_f = (term_mask_f.T * (dcol > 0)
                     * accepted[None, :].astype(jnp.int32))      # [T, P]
            term_dyn = term_dyn + topology.seg_sum(
                add_f, dcol, term_dyn.shape[1])
        final = accepted | failing
        out_idx = jnp.where(accepted, choice, out_idx)
        best_sel = _gpick(
            jnp.take_along_axis(tot_mix, local_choice[:, None], 1)[:, 0], mine)
        best = jnp.where(final, best_sel, best)
        anyf_out = jnp.where(final, accepted, anyf_out)
        fit_out = jnp.where(final[:, None], fit_mix, fit_out)
        ports_out = jnp.where(final[:, None], ports_mix, ports_out)
        if host is not None or gen is not None:
            spread_out = jnp.where(final[:, None], sp_mix, spread_out)
            ipa_out = jnp.where(final[:, None], ip_mix, ipa_out)
        ff_out = jnp.where(final[:, None], ff_mix, ff_out)
        done = done | final
        progressed = jnp.any(final)
        return (req_dyn, nz_dyn, port_dyn, sel_dyn, term_dyn, done, out_idx,
                best, anyf_out, fit_out, ports_out, spread_out, ipa_out,
                ff_out, progressed)

    def cond(carry):
        done, progressed = carry[5], carry[14]
        return jnp.any(~done & pb.valid) & progressed

    ones_pn = jnp.ones((P, N), bool)
    init = (
        nt.requested, nt.nonzero_requested, nt.port_bits,
        sel0, seg0,                               # topo tables (host mode)
        ~pb.valid,                                # invalid pods start done
        jnp.full((P,), -1, jnp.int32),            # out_idx
        jnp.zeros((P,), jnp.float32),             # best
        jnp.zeros((P,), bool),                    # any_feasible
        ones_pn, ones_pn,                         # fit_out, ports_out
        ones_pn, ones_pn,                         # spread_out, ipa_out
        static_ff,                                # ff_out
        np.True_,
    )
    (f_req, f_nz, f_port, f_sel, f_term, _done, node_idx, best, anyf,
     fit_out, ports_out, spread_out, ipa_out, ff_out, _p) = lax.while_loop(
        cond, body, init)

    committed = node_idx >= 0
    if axis_name is None:
        in_window = committed
        local_commit = jnp.where(committed, node_idx, 0)
    else:
        # node_idx carries GLOBAL slot ids; each shard scatters only the
        # winners inside its own slot window (same as the scan path)
        in_window = committed & (node_idx >= slot_offset) \
            & (node_idx < slot_offset + N)
        local_commit = jnp.where(in_window, node_idx - slot_offset, 0)
    f_class = nt.class_req.at[local_commit, pb.prio_class].add(
        jnp.where(in_window[:, None], pb.req, 0))
    return BatchResult(
        node_idx=node_idx, best_score=best, any_feasible=anyf,
        static_masks={}, fit_ok=fit_out, ports_ok=ports_out,
        spread_ok=spread_out, ipa_ok=ipa_out, first_fail=ff_out,
        final_requested=f_req, final_nonzero=f_nz, final_ports=f_port,
        final_sel_counts=f_sel, final_seg_exist=f_term, final_class_req=f_class,
    )


def schedule_batch_core(
    pb: PodBatch,
    et: ExprTable,
    nt: NodeTensors,
    tc: TopoCounts,
    tb: TopoBatch,
    key: jax.Array,
    weights_key: Tuple[Tuple[str, float], ...],
    topo_enabled: bool = True,
    axis_name: Optional[str] = None,
    num_shards: int = 1,
    pallas: Optional[str] = None,
    topo_carry: Optional[Tuple[jax.Array, jax.Array]] = None,
    sample_k: Optional[jax.Array] = None,
    sample_start: Optional[jax.Array] = None,
    topo_mode: Optional[str] = None,
    vd_override: Optional[int] = None,
    host_key: int = 0,
    spec_decode: bool = False,
    ports_enabled: bool = True,
    extra_mask: Optional[jax.Array] = None,
    dra_mask: Optional[jax.Array] = None,
    slice_mask: Optional[jax.Array] = None,
) -> BatchResult:
    """The traceable body; nt's node axis may be a shard (axis_name set).

    ``extra_mask`` (optional [P, N] bool) is a host-computed static
    feasibility pre-pass ANDed into the static filter phase — today the
    volume-bindability screen (ops/volume_mask.py). Attributed as
    "VolumeBinding" in the first-fail table (id 9); the reference would
    blame an earlier plugin when e.g. ports ALSO fail on the same node —
    a documented attribution-precision divergence, not a placement one.
    ``dra_mask`` (optional [P, N] bool) is the claim-feasibility screen
    (claim_feasibility_mask above — usually a still-unmaterialized device
    array), attributed as "DynamicResources" (id 10); claims allocate at
    node granularity, so the mask is exact per batch and the host Reserve
    re-verifies allocation at commit.
    ``topo_enabled`` is a trace-time flag: batches with no spread constraints,
    no affinity terms and no registered count rows compile a program with the
    whole topology path dead-code-eliminated (the common fast case).

    ``topo_mode``: None derives from topo_enabled ("general"/"off").
    "host" = every involved topology key is kubernetes.io/hostname — the
    per-step segment scatters collapse to per-node count reads
    (ops/topology.py hostname fast path); the seg_exist carry slot then
    holds the per-node term-count table [T, N]. ``vd_override`` shrinks the
    general path's domain axis to the involved keys' actual vocab size."""
    weights = dict(weights_key)
    if topo_mode is None:
        topo_mode = "general" if topo_enabled else "off"
    topo_enabled = topo_mode != "off"
    N = nt.capacity  # local shard size under shard_map
    # (the `key` arg is retained for signature stability; the tie-break
    # jitter is a seeded hash now — see ops/tiebreak.py — so no PRNG key is
    # derived in-program anymore)
    if axis_name is None:
        slot_offset = np.int32(0)
    else:
        slot_offset = (lax.axis_index(axis_name) * N).astype(jnp.int32)

    # ---- static phase -----------------------------------------------------
    expr_match = filters.eval_exprs(et, nt)
    if axis_name is not None:
        # OP_NODE_NAME compares against global slot ids: shift the local iota
        n_idx = jnp.arange(N, dtype=jnp.int32)[None, :] + slot_offset
        name_mask = (pb.node_name[:, None] == -1) | (pb.node_name[:, None] == n_idx)
    else:
        name_mask = filters.filter_node_name(pb, nt)
    static_masks = {
        "NodeUnschedulable": filters.filter_unschedulable(pb, nt),
        "NodeName": name_mask,
        "TaintToleration": filters.filter_taints(pb, nt),
        "NodeAffinity": filters.filter_node_affinity(pb, et, nt, expr_match),
    }
    static_ok = nt.valid[None, :] & pb.valid[:, None]
    for m in static_masks.values():
        static_ok = static_ok & m
    if extra_mask is not None:
        static_ok = static_ok & extra_mask
    if dra_mask is not None:
        static_ok = static_ok & dra_mask
    if slice_mask is not None:
        # slice-gang members are pinned to their planned torus window (a
        # one-hot row; all-False when the plan rejected the gang) — ANDing
        # into static_ok covers the scan, speculative and pallas paths alike
        static_ok = static_ok & slice_mask

    # static half of the first-failing-plugin table (ids follow the filter
    # config order in ATTRIBUTION_ORDER; 0 = passes). Reverse
    # assignment order makes the earliest failing plugin win.
    static_ff = jnp.zeros(static_ok.shape, jnp.int8)
    if slice_mask is not None:
        static_ff = jnp.where(~slice_mask, np.int8(11), static_ff)
    if dra_mask is not None:
        static_ff = jnp.where(~dra_mask, np.int8(10), static_ff)
    if extra_mask is not None:
        static_ff = jnp.where(~extra_mask, np.int8(9), static_ff)
    for sid, name in ((4, "NodeAffinity"), (3, "TaintToleration"),
                      (2, "NodeName"), (1, "NodeUnschedulable")):
        static_ff = jnp.where(~static_masks[name], np.int8(sid), static_ff)

    taint_raw = scores.score_taint_toleration(pb, nt)            # [P, N]
    affinity_raw = scores.score_node_affinity(pb, et, nt, expr_match)
    total_nodes = jnp.maximum(_gsum(jnp.sum(nt.valid), axis_name), 1)
    image_score = scores.score_image_locality(pb, nt, total_nodes=total_nodes)

    vd = domain_axis(et.bits.shape[1], vd_override)
    if topo_mode == "general":
        topo_static = topology.make_static(
            tc.term_counts, tc.term_key, nt.label_val, nt.valid, vd, axis_name
        )
    elif topo_mode == "host":
        hostkey_ok = nt.label_val[:, host_key] > 0  # [N] node has a hostname

    # seeded tie-break jitter (SURVEY §8; replaces the threefry uniform draw,
    # which was the single most expensive block of the program on CPU): a
    # per-(pod-seed, node-NAME-hash) integer hash, identical to the oracle's
    # _select_host key (ops/tiebreak.py). Name-keyed values are the same on
    # every shard layout, so sharded-vs-single-device parity is automatic.
    from ..ops.tiebreak import jitter_table

    jitter = jitter_table(pb.tie_seed, nt.name_hash)

    # ---- commit phase -----------------------------------------------------
    pod_bits = _pod_port_bits(pb, nt.port_bits.shape[1])
    alloc_f = nt.allocatable.astype(jnp.float32)                  # [N, R]
    ones_pn = jnp.ones((N,), bool)

    if spec_decode:
        # vectorized decide/repair rounds instead of the P-step scan —
        # unsampled batches in every topology mode single-shard, and the
        # topology-OFF mode under shard_map too (VERDICT r3 item 6: the
        # flagship program must not silently fall back to the scan on a
        # real mesh); sequential parity proven per-round by the
        # prefix-stability acceptance
        assert topo_mode in ("off", "host", "general") and sample_k is None
        host_args = gen_args = None
        if topo_mode == "host":
            seg0 = tc.term_counts                      # [T, N] per-node counts
            host_args = {
                "tb": _tb_dict(tb),
                "hostkey_ok": hostkey_ok,
                "affinity_ok": static_masks["NodeAffinity"],
            }
        elif topo_mode == "general":
            seg0 = topo_static.seg_exist0              # [T, Vd] domain counts
            gen_args = {
                "tb": _tb_dict(tb),
                "affinity_ok": static_masks["NodeAffinity"],
                "dom_t": topo_static.dom_t,
                "label_val": nt.label_val,
                "valid": nt.valid,
            }
        else:
            seg0 = jnp.zeros((tc.term_counts.shape[0], 1), jnp.int32)
        sel0_, seg0_ = (tc.sel_counts, seg0) if topo_carry is None else topo_carry
        result = _speculative_core(
            pb, nt, weights, static_ok, static_ff, taint_raw,
            affinity_raw, image_score, pod_bits, jitter, sel0_, seg0_,
            host=host_args, gen=gen_args,
            axis_name=axis_name, slot_offset=slot_offset,
            ports_enabled=ports_enabled)
        return result._replace(static_masks=static_masks)

    if pallas is not None:
        # fused Pallas step: the whole per-pod dynamic computation + commit
        # in one VMEM-resident kernel (ops/pallas_step.py). No sampling
        # emulation here — returning full-evaluation results as if sampled
        # would silently drop the rotation carry.
        assert sample_k is None, "pallas path has no sampling emulation"
        interp = pallas == "interpret"
        alloc_t = nt.allocatable.T
        wvec = np.asarray([[
            weights["NodeResourcesFit"],
            weights["NodeResourcesBalancedAllocation"],
            weights["TaintToleration"],
            weights["NodeAffinity"],
            weights["ImageLocality"],
            0.0, 0.0, 0.0,
        ]], jnp.float32)

        def pstep(carry, xs):
            req_t, nz_t, port_t = carry
            (p_req, p_nz, p_static_ok, _p_affok, p_taint, p_aff, p_img, p_bits,
             p_jitter, p_valid, p_sff) = xs["row"]
            out = pallas_step.fused_step(
                alloc_t, req_t, nz_t, port_t,
                p_req[:, None], p_nz[:, None], p_bits[:, None],
                p_static_ok[None, :], p_taint[None, :], p_aff[None, :],
                p_img[None, :], p_jitter[None, :],
                p_valid.astype(jnp.int32).reshape(1, 1), wvec,
                interpret=interp,
            )
            req_t, nz_t, port_t, idx, best, anyf, fit, ports_ok = out
            ff = p_sff
            ff = jnp.where((ff == 0) & ~ports_ok[0], np.int8(5), ff)
            ff = jnp.where((ff == 0) & ~fit[0], np.int8(6), ff)
            return (req_t, nz_t, port_t), (
                idx[0, 0], best[0, 0], anyf[0, 0] > 0,
                fit[0], ports_ok[0], ones_pn, ones_pn, ff,
            )

        rows = (
            pb.req, pb.nonzero_req, static_ok, static_masks["NodeAffinity"],
            taint_raw, affinity_raw, image_score, pod_bits, jitter, pb.valid,
            static_ff,
        )
        carry0 = (nt.requested.T, nt.nonzero_requested.T, nt.port_bits.T)
        (f_req_t, f_nz_t, f_port_t), (node_idx, best, any_feasible, fit_ok, ports_ok, spread_ok, ipa_ok, first_fail) = lax.scan(
            pstep, carry0, {"row": rows})
        return BatchResult(
            node_idx=node_idx, best_score=best, any_feasible=any_feasible,
            static_masks=static_masks, fit_ok=fit_ok, ports_ok=ports_ok,
            spread_ok=spread_ok, ipa_ok=ipa_ok, first_fail=first_fail,
            final_requested=f_req_t.T, final_nonzero=f_nz_t.T,
            final_ports=f_port_t.T,
        )

    def step(carry, xs):
        # free_dyn = allocatable - requested is carried directly (the sub
        # would otherwise be a full [N, R] pass per step) and the fit test
        # folds the `req == 0 always fits` rule into a per-pod sentinel
        # (p_req_gate), halving the fit chain's [N, R] passes. The nonzero-
        # requested carry holds only the two scored columns; the full [N, R]
        # tensor is rebuilt in ONE post-scan scatter (like f_class below).
        free_dyn, nz2_dyn, port_dyn, sel_counts, seg_exist, samp_start = carry
        row = xs["row"]
        (p_req, p_req_gate, p_nz, p_static_ok, p_affinity_ok, p_taint, p_aff,
         p_img, p_bits, p_jitter, p_valid, p_sff, p_nom) = row

        fit_ok = jnp.all(free_dyn >= p_req_gate[None, :], axis=-1)
        if ports_enabled:
            conflict = jnp.any(port_dyn & p_bits[None, :], axis=-1)
            ports_ok = ~conflict
        else:
            # no pod in the batch wants a host port: skip the [N, Wport]
            # conflict pass AND the carry update below — the port carry then
            # passes through the scan unchanged (aliased, zero traffic)
            ports_ok = ones_pn

        if topo_mode == "host":
            tbx = xs["tb"]
            spread_ok = topology.spread_filter_host(
                tbx, sel_counts, hostkey_ok, nt.valid, p_affinity_ok, axis_name)
            ipa_aff_ok, ipa_anti_ok, ipa_exist_ok, exist_at = topology.ipa_filter_host(
                tbx, sel_counts, seg_exist, hostkey_ok, nt.valid, axis_name)
            ipa_ok = ipa_aff_ok & ipa_anti_ok & ipa_exist_ok
        elif topo_enabled:
            tbx = xs["tb"]
            spread_ok = topology.spread_filter(
                tbx, sel_counts, nt.label_val, nt.valid, p_affinity_ok, vd, axis_name)
            ipa_aff_ok, ipa_anti_ok, ipa_exist_ok, exist_at = topology.ipa_filter(
                tbx, sel_counts, seg_exist, topo_static.dom_t, nt.label_val,
                nt.valid, vd, axis_name)
            ipa_ok = ipa_aff_ok & ipa_anti_ok & ipa_exist_ok
        else:
            spread_ok = ones_pn
            ipa_ok = ones_pn

        feasible = p_static_ok & fit_ok & ports_ok & spread_ok & ipa_ok

        if sample_k is not None:
            # adaptive-sampling emulation (schedule_one.go:525-545 +
            # nextStartNodeIndex rotation :475-478): only the first K
            # feasible nodes in rotated slot order are eligible; the start
            # rotates past every examined node, exactly like the host's
            # early-exit loop. The reference iterates its snapshot list;
            # the device iterates slots — same-distribution sampling with a
            # different (documented) node order.
            iota_n = jnp.arange(N, dtype=jnp.int32)
            perm = (samp_start + iota_n) % N          # rotated order -> slot
            f_rot = jnp.take(feasible, perm)
            c = jnp.cumsum(f_rot.astype(jnp.int32))
            elig_rot = f_rot & (c <= sample_k)
            # scatter-back of a rotation == gather by the inverse rotation
            # (a per-step scatter costs ~200µs on TPU; a gather fuses)
            eligible = jnp.take(elig_rot, (iota_n - samp_start) % N)
            reached = jnp.any(c >= sample_k)
            kth_pos = jnp.argmax(c >= sample_k).astype(jnp.int32)
            processed = jnp.where(reached, kth_pos + 1, np.int32(N))
            # invalid pods examine nothing (no rotation burn)
            samp_start = jnp.where(p_valid, (samp_start + processed) % N, samp_start)
            # the nominated node is always examined (schedule_one.go:394
            # fast path — without this, a preemptor's rotating window
            # usually misses the node its victims were evicted from)
            if axis_name is None:
                eligible = eligible | (iota_n == p_nom)
            else:
                eligible = eligible | (iota_n + slot_offset == p_nom)
            feasible = feasible & eligible

        # resource scores against the evolving requested state (shared
        # formula with the speculative path: _resource_scores)
        nz_req = (nz2_dyn + p_nz[None, :2]).astype(jnp.float32)
        least_alloc, balanced = _resource_scores(alloc_f[:, :2], nz_req)

        total = (
            weights["NodeResourcesFit"] * least_alloc
            + weights["NodeResourcesBalancedAllocation"] * balanced
            + weights["TaintToleration"] * _normalize(p_taint, feasible, True, axis_name)
            + weights["NodeAffinity"] * _normalize(p_aff, feasible, False, axis_name)
            + weights["ImageLocality"] * p_img
        )
        if topo_mode == "host":
            total = total + weights["PodTopologySpread"] * topology.spread_score_host(
                tbx, sel_counts, hostkey_ok, nt.valid, p_affinity_ok, feasible, axis_name)
            total = total + weights["InterPodAffinity"] * topology.ipa_score_host(
                tbx, sel_counts, exist_at, hostkey_ok, feasible, axis_name)
        elif topo_enabled:
            total = total + weights["PodTopologySpread"] * topology.spread_score(
                tbx, sel_counts, nt.label_val, nt.valid, p_affinity_ok, feasible, vd, axis_name)
            total = total + weights["InterPodAffinity"] * topology.ipa_score(
                tbx, sel_counts, exist_at, nt.label_val, nt.valid, feasible, vd, axis_name)

        # nominated-node fast path (schedule_one.go:394-403): when the
        # nominated node is feasible it wins outright — the reference
        # schedules there without scoring the rest
        if axis_name is None:
            is_nom = jnp.arange(N, dtype=jnp.int32) == p_nom
        else:
            is_nom = (jnp.arange(N, dtype=jnp.int32) + slot_offset) == p_nom
        eff = jnp.where(feasible, total + p_jitter + is_nom * np.float32(1e7), NEG_INF)
        local_idx = jnp.argmax(eff).astype(jnp.int32)
        local_best = eff[local_idx]
        any_feasible = _gmax(jnp.any(feasible), axis_name) & p_valid

        if axis_name is None:
            mine = np.True_
            global_idx = local_idx
            best = total[local_idx]
        else:
            global_best = _gmax(local_best, axis_name)
            axis = lax.axis_index(axis_name).astype(jnp.int32)
            winner_axis = _gmin(jnp.where(local_best >= global_best, axis, np.int32(2**30)), axis_name)
            mine = axis == winner_axis
            global_idx = _gsum(jnp.where(mine, local_idx + slot_offset, 0), axis_name).astype(jnp.int32)
            best = _gsum(jnp.where(mine, total[local_idx], 0.0), axis_name)

        commit = any_feasible & mine
        # one-hot elementwise commits instead of scatters: each dynamic
        # scatter costs ~200µs of fixed overhead per scan step on this TPU,
        # while the [N,·] masked adds fuse into the surrounding step
        onehot_n = (jnp.arange(N, dtype=jnp.int32) == local_idx) & commit  # [N]
        free_dyn = free_dyn - onehot_n[:, None] * p_req[None, :]
        nz2_dyn = nz2_dyn + onehot_n[:, None] * p_nz[None, :2]
        if ports_enabled:
            port_dyn = jnp.where(onehot_n[:, None], port_dyn | p_bits[None, :],
                                 port_dyn)
        if topo_mode == "host":
            sel_counts, seg_exist = topology.commit_update_host(
                sel_counts, seg_exist, local_idx, any_feasible, mine,
                tbx["pod_sig_mask"], tbx["pod_term_mask"])
        elif topo_enabled:
            sel_counts, seg_exist = topology.commit_update(
                sel_counts, seg_exist, topo_static.dom_t, local_idx,
                any_feasible, mine, tbx["pod_sig_mask"], tbx["pod_term_mask"], axis_name)
        out_idx = jnp.where(any_feasible, global_idx, -1)
        ff = p_sff
        ff = jnp.where((ff == 0) & ~ports_ok, np.int8(5), ff)
        ff = jnp.where((ff == 0) & ~fit_ok, np.int8(6), ff)
        if topo_enabled:
            ff = jnp.where((ff == 0) & ~spread_ok, np.int8(7), ff)
            ff = jnp.where((ff == 0) & ~ipa_ok, np.int8(8), ff)
        return (free_dyn, nz2_dyn, port_dyn, sel_counts, seg_exist, samp_start), (
            out_idx, best, any_feasible, fit_ok, ports_ok, spread_ok, ipa_ok, ff,
        )

    # `req == 0 always fits` as a sentinel so fit is one compare+reduce
    req_gate = jnp.where(pb.req == 0, jnp.int32(-(2 ** 30)), pb.req)
    rows = (
        pb.req, req_gate, pb.nonzero_req, static_ok, static_masks["NodeAffinity"],
        taint_raw, affinity_raw, image_score, pod_bits, jitter, pb.valid,
        static_ff, pb.nominated,
    )
    xs = {"row": rows}
    if topo_mode == "host":
        xs["tb"] = _tb_dict(tb)
        seg_exist0 = tc.term_counts  # [T, N]: per-node term counts ARE the carry
    elif topo_enabled:
        xs["tb"] = _tb_dict(tb)
        seg_exist0 = topo_static.seg_exist0
    else:
        seg_exist0 = jnp.zeros((tc.term_counts.shape[0], 1), jnp.int32)
    sel0, seg0 = (tc.sel_counts, seg_exist0) if topo_carry is None else topo_carry
    start0 = (jnp.asarray(sample_start, jnp.int32) if sample_start is not None
              else jnp.zeros((), jnp.int32))
    carry0 = (nt.allocatable - nt.requested, nt.nonzero_requested[:, :2],
              nt.port_bits, sel0, seg0, start0)
    final_carry, (node_idx, best, any_feasible, fit_ok, ports_ok, spread_ok, ipa_ok, first_fail) = lax.scan(
        step, carry0, xs)
    f_free, f_nz2, f_port, f_sel, f_seg, f_start = final_carry
    f_req = nt.allocatable - f_free

    # evolve the priority-class table by the batch's commits in ONE post-scan
    # scatter (no carry needed — nothing in-scan reads it); under shard_map
    # each shard scatters only the winners inside its slot window. The full
    # [N, R] nonzero-requested tensor is rebuilt the same way — in-scan only
    # the two scored columns are carried.
    committed = node_idx >= 0
    if axis_name is None:
        in_window = committed
        local_commit = jnp.where(committed, node_idx, 0)
    else:
        in_window = committed & (node_idx >= slot_offset) & (node_idx < slot_offset + N)
        local_commit = jnp.where(in_window, node_idx - slot_offset, 0)
    f_class = nt.class_req.at[local_commit, pb.prio_class].add(
        jnp.where(in_window[:, None], pb.req, 0))
    f_nz = nt.nonzero_requested.at[local_commit].add(
        jnp.where(in_window[:, None], pb.nonzero_req, 0))

    return BatchResult(
        node_idx=node_idx,
        best_score=best,
        any_feasible=any_feasible,
        static_masks=static_masks,
        fit_ok=fit_ok,
        ports_ok=ports_ok,
        spread_ok=spread_ok,
        ipa_ok=ipa_ok,
        first_fail=first_fail,
        final_requested=f_req,
        final_nonzero=f_nz,
        final_ports=f_port,
        final_sel_counts=f_sel,
        final_seg_exist=f_seg,
        final_class_req=f_class,
        final_sample_start=f_start if sample_k is not None else None,
    )


# per-pod slice verdict word (the packed block's optional trailing column):
# bit 0 = pod is a slice-gang member, bit 1 = its gang's torus plan was
# feasible, bits 2+ = planned node slot + 1 (0 = none). The commit side
# combines bit 1 with the member's own node_idx — the mask pins members to
# their planned window, so "every member landed" IS the contiguity verdict,
# with zero extra device dispatch.
SLICE_MEMBER_BIT = 1
SLICE_PLAN_OK_BIT = 2
SLICE_TARGET_SHIFT = 2


def _slice_plan(pb: PodBatch, nt: NodeTensors, slice_members,
                slice_grid: Tuple[int, int]):
    """(slice_mask [P, N] bool, slice_words [P] int32): run the torus
    planner (ops/slice.py) inside the batch jit and lower its per-gang
    targets to the per-pod form the core and the packed block consume.
    Non-members get an all-True mask row and a zero word; members of a
    rejected gang get an all-False row (all-or-nothing by construction)."""
    from ..ops.slice import plan_slices

    member_idx, member_valid = slice_members
    targets, ok = plan_slices(nt, pb.req, member_idx, member_valid,
                              slice_grid)
    p = pb.valid.shape[0]
    n = nt.capacity
    midx = member_idx.reshape(-1)
    act = member_valid.reshape(-1)
    tgt = targets.reshape(-1)
    okf = jnp.broadcast_to(ok[:, None], member_idx.shape).reshape(-1)
    rows = jnp.where(act, midx, p)  # padding entries scatter to a spill row
    row_mask = jnp.where((okf & (tgt >= 0))[:, None],
                         jnp.arange(n, dtype=jnp.int32)[None, :]
                         == tgt[:, None], False)
    mask = jnp.ones((p + 1, n), bool).at[rows].set(row_mask)[:p]
    word = (np.int32(SLICE_MEMBER_BIT)
            | jnp.where(okf, np.int32(SLICE_PLAN_OK_BIT), 0)
            | ((tgt + 1) << SLICE_TARGET_SHIFT)).astype(jnp.int32)
    words = jnp.zeros(p + 1, jnp.int32).at[rows].set(
        jnp.where(act, word, 0))[:p]
    return mask, words


@functools.partial(jax.jit, static_argnames=(
    "weights_key", "topo_enabled", "pallas", "topo_mode", "vd_override",
    "host_key", "spec_decode", "ports_enabled", "slice_grid"))
def schedule_batch(
    pb: PodBatch,
    et: ExprTable,
    nt: NodeTensors,
    tc: TopoCounts,
    tb: TopoBatch,
    key: jax.Array,
    weights_key: Tuple[Tuple[str, float], ...] = tuple(sorted(DEFAULT_WEIGHTS.items())),
    topo_enabled: bool = True,
    pallas: Optional[str] = None,
    topo_carry: Optional[Tuple[jax.Array, jax.Array]] = None,
    sample_k: Optional[jax.Array] = None,
    sample_start: Optional[jax.Array] = None,
    topo_mode: Optional[str] = None,
    vd_override: Optional[int] = None,
    host_key: int = 0,
    spec_decode: bool = False,
    ports_enabled: bool = True,
    extra_mask: Optional[jax.Array] = None,
    dra_mask: Optional[jax.Array] = None,
    slice_members=None,
    slice_grid: Optional[Tuple[int, int]] = None,
    quota_ns: Optional[jax.Array] = None,
    quota_req: Optional[jax.Array] = None,
    quota_used: Optional[jax.Array] = None,
    quota_limit: Optional[jax.Array] = None,
) -> BatchResult:
    # slice gangs plan in-jit, ahead of the core: the plan pins members via
    # slice_mask and its verdict words ride the packed block's extra column
    if slice_members is not None and slice_grid is not None:
        slice_mask, slice_words = _slice_plan(pb, nt, slice_members,
                                              slice_grid)
    else:
        slice_mask = slice_words = None
    res = schedule_batch_core(pb, et, nt, tc, tb, key, weights_key, topo_enabled,
                              pallas=pallas, topo_carry=topo_carry,
                              sample_k=sample_k, sample_start=sample_start,
                              topo_mode=topo_mode, vd_override=vd_override,
                              host_key=host_key, spec_decode=spec_decode,
                              ports_enabled=ports_enabled,
                              extra_mask=extra_mask, dra_mask=dra_mask,
                              slice_mask=slice_mask)
    # namespace-quota screen over the core's winners, in-jit and post-core:
    # it replays the batch order against the synced usage/limit tensors and
    # its verdict words ride the packed block — zero extra dispatch
    if quota_ns is not None and quota_used is not None:
        from ..ops.quota import quota_screen

        quota_words = quota_screen(res.node_idx, quota_ns, quota_req,
                                   quota_used, quota_limit)
    else:
        quota_words = None
    # fuse the host-commit payload into one block here (inside the jit), so
    # every single-device variant — scan, speculative rounds, pallas —
    # returns it; the sharded core entry (parallel/mesh.py) bypasses this
    # wrapper and keeps packed=None
    return res._replace(packed=pack_result_block(
        res.node_idx, res.first_fail, slice_words=slice_words,
        quota_words=quota_words))


def spec_decode_eligible(sample_k) -> bool:
    """Speculative decode covers every single-shard unsampled program
    (topology off, hostname fast path, and the general domain-aggregating
    mode); only sampling forces the scan. KTPU_SPEC=1 forces it, =0 forces
    the scan; auto enables it on accelerators only — the rounds trade ~10x
    more memory traffic for ~100x fewer dependent steps, a win on HBM (TPU)
    and a loss on host RAM (measured 2.2x slower on CPU, where the scan's
    step latency is cheap)."""
    import os

    flag = os.environ.get("KTPU_SPEC", "auto")
    if flag == "0":
        return False
    if sample_k is not None:
        return False
    if flag == "auto":
        import jax

        return jax.default_backend() != "cpu"
    return True


def bucket_sig(capacity, topo_mode: Optional[str], topo_enabled: bool) -> str:
    """The batch program's bucket signature: padded pod capacity + topology
    mode, the two shape axes the sizer and the topology walk retrace over.
    The compile, dispatch and cost ledgers and the flight recorder key on
    it."""
    return f"{capacity}/{topo_mode or ('general' if topo_enabled else 'off')}"


def build_schedule_batch_fn(weights: Dict[str, float] = None):
    """Bind plugin weights statically; returns
    fn(pb, et, nt, tc, tb, key, topo_enabled=True, topo_carry=None,
    sample_k=None, sample_start=None, topo_mode=None, vd_override=None,
    host_key=0) -> BatchResult."""
    wk = tuple(sorted((weights or DEFAULT_WEIGHTS).items()))

    def fn(pb, et, nt, tc, tb, key, topo_enabled=True, topo_carry=None,
           sample_k=None, sample_start=None, topo_mode=None, vd_override=None,
           host_key=0, ports_enabled=True, extra_mask=None, dra_mask=None,
           slice_members=None, slice_grid=None, quota_ns=None, quota_req=None,
           quota_used=None, quota_limit=None):
        spec = spec_decode_eligible(sample_k)
        # the pallas fused step has no sampling emulation yet; the
        # speculative path replaces it where both apply (fewer device steps).
        # The fused kernel has no extra-mask/dra-mask/slice/quota input
        # either — volume, claim, slice and quota-screened batches take the
        # XLA path.
        mode = (None if (sample_k is not None or spec or extra_mask is not None
                         or dra_mask is not None or slice_members is not None
                         or quota_ns is not None)
                else pallas_mode(nt, None, topo_enabled))
        kw = dict(weights_key=wk, topo_enabled=topo_enabled, pallas=mode,
                  topo_carry=topo_carry, sample_k=sample_k,
                  sample_start=sample_start, topo_mode=topo_mode,
                  vd_override=vd_override, host_key=host_key,
                  spec_decode=spec, ports_enabled=ports_enabled,
                  extra_mask=extra_mask, dra_mask=dra_mask,
                  slice_members=slice_members, slice_grid=slice_grid,
                  quota_ns=quota_ns, quota_req=quota_req,
                  quota_used=quota_used, quota_limit=quota_limit)
        out = schedule_batch(pb, et, nt, tc, tb, key, **kw)
        from . import telemetry

        if telemetry.get() is not None:
            # cost ledger: AOT-lower the exact signature just dispatched and
            # keep its flops/bytes once per (program, bucket sig) — this is
            # the one place the batch program's full kwargs exist
            sig = bucket_sig(getattr(pb, "capacity", "?"), topo_mode,
                             topo_enabled)
            telemetry.cost_probe("schedule_batch", sig, schedule_batch,
                                 (pb, et, nt, tc, tb, key), kw)
        return out

    return fn
