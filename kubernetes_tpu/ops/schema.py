"""Dense tensor schemas for the device-resident cluster mirror.

Design notes (why this is NOT a transliteration of NodeInfo):

* Nodes live in fixed **slots** (stable indices into the N axis); all arrays are
  padded to static capacities so one compiled program serves the whole run.
  Slot 0 is always invalid padding (vocab-id convention), real slots start at 1?
  No — slots are 0-based with an explicit ``valid`` mask; id-like *vocab*
  columns reserve 0 for "absent".

* Labels are encoded as a dense per-registered-key table instead of bitsets:
  ``label_val[N, K]`` holds the value-id of node n for key k (per-key value
  vocab, 0 = absent) and ``label_num[N, K]`` its integer parse (INT_MIN when
  not numeric).  Selector matching then becomes *gathers*, not giant bit
  intersections: each scheduling batch compiles its unique selector
  expressions into an ExprTable evaluated once as an [E, N] bool matrix.

* Resource vectors are int32 with canonical units (api/resource.py):
  col 0 = cpu milli, 1 = memory KiB, 2 = ephemeral MiB, 3 = pod count /
  allowed pods, 4.. = scalar resources by scalar-vocab slot.

Reference mapping: framework/types.go:363 NodeInfo → NodeTensors row;
snapshot (internal/cache/snapshot.go) → the whole NodeTensors value.
"""

from __future__ import annotations

import dataclasses

import jax
import numpy as np

INT_NONE = np.int32(-(2**31))  # sentinel for "absent" numeric label

# TPU torus geometry: chips per schedulable host (a v4/v5 host exposes one
# 4-chip board to the control plane; slice sizes quoted in chips are
# node-count * CHIPS_PER_NODE)
CHIPS_PER_NODE = 4

# resource columns
COL_CPU = 0
COL_MEM = 1
COL_EPH = 2
COL_PODS = 3
N_FIXED_COLS = 4

# expression opcodes (the selector VM)
OP_TRUE = 0       # constant true (slot 0 of every ExprTable; AND-neutral padding)
OP_IN = 1         # label_val[n, key] ∈ value-id set (bitset over the key's value vocab)
OP_NOT_IN = 2     # absent key matches (labels.Requirement semantics)
OP_EXISTS = 3
OP_NOT_EXISTS = 4
OP_GT = 5         # int(label) > val; absent/non-numeric never matches
OP_LT = 6
OP_NODE_NAME = 7  # node slot == val (compiled metadata.name matchFields)

# taint effects
EFFECT_NONE = 0
EFFECT_NO_SCHEDULE = 1
EFFECT_PREFER_NO_SCHEDULE = 2
EFFECT_NO_EXECUTE = 3

# toleration operators
TOL_EQUAL = 1
TOL_EXISTS = 2


def pytree_dataclass(cls):
    cls = dataclasses.dataclass(cls)
    fields = [f.name for f in dataclasses.fields(cls)]
    jax.tree_util.register_dataclass(cls, data_fields=fields, meta_fields=[])
    return cls


@pytree_dataclass
class NodeTensors:
    """Device-resident per-node state, [N]-padded. The TPU mirror of the
    scheduler cache snapshot."""

    valid: jax.Array          # [N] bool
    unschedulable: jax.Array  # [N] bool
    allocatable: jax.Array    # [N, R] int32 (col PODS = allowed pod count)
    requested: jax.Array      # [N, R] int32 (col PODS = current pod count)
    nonzero_requested: jax.Array  # [N, R] int32 (scoring-path requests)
    label_val: jax.Array      # [N, K] int32 value-id (0 absent)
    label_num: jax.Array      # [N, K] int32 numeric parse (INT_NONE absent)
    taint_key: jax.Array      # [N, T] int32 key-id (0 = no taint in slot)
    taint_val: jax.Array      # [N, T] int32 value-id in key's vocab
    taint_effect: jax.Array   # [N, T] int32 effect code
    port_bits: jax.Array      # [N, Wport] uint32 bitset over the port vocab
    image_bits: jax.Array     # [N, Wimg] uint32 bitset over the image vocab
    image_sizes: jax.Array    # [Vimg] int32 bytes (vocab-level, not per node)
    image_num_nodes: jax.Array  # [Vimg] int32 (ImageStateSummary.NumNodes)
    # priority-class-bucketed requested sums: the device side of batched
    # preemption (preemption.go:546 DryRunPreemption's fit check becomes a
    # prefix-sum over classes sorted by priority). class 0 is reserved
    # padding with class_prio INT_MAX (never evictable).
    class_req: jax.Array      # [N, C, R] int32 requested by pods of class c
    class_prio: jax.Array     # [C] int32 priority value of class c (vocab)
    name_hash: jax.Array      # [N] uint32 fnv1a(node name) — seeded tie-break
    # torus topology axis (slice packing): superpod id and linear position
    # inside the superpod's torus, parsed from well-known node labels (or the
    # synthetic slot-derived fallback); -1 = node carries no topology
    topo_sp: jax.Array        # [N] int32 superpod id (-1 absent)
    topo_pos: jax.Array       # [N] int32 torus slot within superpod (-1 absent)

    @property
    def capacity(self) -> int:
        return self.valid.shape[0]


@pytree_dataclass
class ExprTable:
    """Batch-level deduplicated selector expressions, evaluated once per batch
    to an [E, N] match matrix. Slot 0 is OP_TRUE."""

    op: jax.Array      # [E] int32 opcode
    key: jax.Array     # [E] int32 label-key slot
    val: jax.Array     # [E] int32 (GT/LT compare value or NODE_NAME slot)
    bits: jax.Array    # [E, Wv] uint32 value-id set for IN/NOT_IN


@pytree_dataclass
class PodBatch:
    """A micro-batch of pending pods, [P]-padded, with compiled programs
    pointing into the batch ExprTable."""

    valid: jax.Array        # [P] bool
    priority: jax.Array     # [P] int32
    prio_class: jax.Array   # [P] int32 priority-class vocab id (preemption)
    req: jax.Array          # [P, R] int32 (filter-path request; col PODS == 1)
    nonzero_req: jax.Array  # [P, R] int32 (scoring-path request)
    node_name: jax.Array    # [P] int32 target slot or -1 (pod.spec.nodeName)
    nominated: jax.Array    # [P] int32 nominatedNodeName slot or -1
    tol_key: jax.Array      # [P, L] int32 (0 = wildcard key)
    tol_val: jax.Array      # [P, L] int32
    tol_op: jax.Array       # [P, L] int32 (0 = empty slot)
    tol_effect: jax.Array   # [P, L] int32 (EFFECT_NONE = matches all effects)
    tol_prefer: jax.Array   # [P, L] bool: effect ∈ {"", PreferNoSchedule} (taint Score path)
    tolerates_unschedulable: jax.Array  # [P] bool (precompiled for NodeUnschedulable)
    # node selector + required affinity: AND(sel_idx) AND OR_t(AND_e(term))
    sel_idx: jax.Array      # [P, S] int32 expr slots, AND-combined (0 = true)
    term_idx: jax.Array     # [P, TERM, EXPR] int32 expr slots
    term_valid: jax.Array   # [P, TERM] bool (no valid terms ⇒ affinity passes)
    # preferred affinity (weights; invalid slots have weight 0)
    pref_idx: jax.Array     # [P, PTERM, EXPR] int32
    pref_weight: jax.Array  # [P, PTERM] int32
    port_ids: jax.Array     # [P, MP] int32 wanted-port vocab ids (0 = empty)
    image_ids: jax.Array    # [P, C] int32 container image vocab ids (0 = empty)
    num_containers: jax.Array  # [P] int32
    tie_seed: jax.Array     # [P] uint32 per-(pod, attempt) tie-break seed

    @property
    def capacity(self) -> int:
        return self.valid.shape[0]


@pytree_dataclass
class TopoCounts:
    """Device-resident pod-set count tables — the incremental tensorization of
    the O(pods) scans in PodTopologySpread.PreFilter (filtering.go:238) and
    InterPodAffinity.PreFilter (filtering.go:86-135).

    ``sel_counts[s, n]`` = number of pods currently on node n matching
    registered pod-set signature s (a (namespace-spec, label-selector) pair —
    the unit both plugins count by). ``term_counts[t, n]`` = number of pods on
    node n *carrying* registered (anti-)affinity term t (the symmetric
    direction: existing pods' terms evaluated against the incoming pod).
    Both are maintained host-side per node generation and updated in-scan as
    batch pods commit."""

    sel_counts: jax.Array   # [S, N] int32
    term_counts: jax.Array  # [T, N] int32
    term_key: jax.Array     # [T] int32 topology-key slot of term t (0 = unused row)


@pytree_dataclass
class TopoBatch:
    """Per-batch compiled topology programs: spread constraints and
    inter-pod-affinity terms of the batch pods, pointing into TopoCounts rows.
    All index fields are 0 where invalid (row 0 of each table is a zero row)."""

    # PodTopologySpread DoNotSchedule constraints (filter), [P, C]
    sf_valid: jax.Array        # bool
    sf_sig: jax.Array          # int32 sig row
    sf_key: jax.Array          # int32 topology-key slot
    sf_skew: jax.Array         # int32 maxSkew
    sf_self: jax.Array         # bool: incoming pod matches the constraint selector
    sf_min_domains: jax.Array  # int32, -1 = unset
    # PodTopologySpread ScheduleAnyway constraints (score), [P, C]
    ss_valid: jax.Array        # bool
    ss_sig: jax.Array
    ss_key: jax.Array
    ss_skew: jax.Array
    ss_hostname: jax.Array     # bool: topologyKey == kubernetes.io/hostname
    ss_require_all: jax.Array  # [P] bool (pod-specified or non-system defaults)
    # incoming pod's required pod-affinity terms, [P, A]
    ia_valid: jax.Array
    ia_sig: jax.Array
    ia_key: jax.Array
    ia_self_all: jax.Array     # [P] bool: pod matches ALL its own affinity terms
    # incoming pod's required pod-anti-affinity terms, [P, A]
    ianti_valid: jax.Array
    ianti_sig: jax.Array
    ianti_key: jax.Array
    # incoming pod's preferred (anti-)affinity terms, [P, PT]
    ip_valid: jax.Array
    ip_sig: jax.Array
    ip_key: jax.Array
    ip_w: jax.Array            # int32 signed weight (negative = anti)
    # existing-term interactions, [P, T]
    term_filter_match: jax.Array  # bool: ANTI_REQ term t matches incoming pod p
    term_score_w: jax.Array       # float32 symmetric score weight of term t for pod p
    # commit updates (what a committing pod adds to the node it lands on)
    pod_sig_mask: jax.Array    # [P, S] bool
    pod_term_mask: jax.Array   # [P, T] bool


def round_node_capacity(n: int, floor: int = 128) -> int:
    """Node-axis padding bucket: powers of two up to 1024, then multiples of
    1024. Pow2 all the way up wastes real bandwidth — every per-step tensor
    in the batch scan is [N,·], so padding 5000 nodes to 8192 paid +64%
    memory traffic per scheduling step; 5120 pays +2.4%. Multiples of 1024
    keep the lane/sublane tiling XLA wants on TPU (and change nothing on
    CPU), while still bucketing growth so the executable cache stays small."""
    cap = max(128, floor)
    while cap < n and cap < 1024:
        cap *= 2
    if cap < n:
        cap = ((n + 1023) // 1024) * 1024
    return cap


@dataclasses.dataclass(frozen=True)
class Capacities:
    """Static padding sizes; one compiled executable per Capacities value."""

    nodes: int = 128          # N
    pods: int = 64            # P
    resources: int = 6        # R (4 fixed + scalar slots)
    label_keys: int = 16      # K
    taints: int = 4           # T per node
    tolerations: int = 4      # L per pod
    exprs: int = 64           # E per batch
    sel_exprs: int = 8        # S per pod
    terms: int = 4            # affinity terms per pod
    term_exprs: int = 4       # exprs per term
    pref_terms: int = 4       # preferred terms per pod
    value_words: int = 32     # Wv: value-vocab bitset words (per-key vocab ≤ 32*Wv)
    port_words: int = 16      # Wport
    ports: int = 8            # MP wanted ports per pod
    image_words: int = 16     # Wimg
    images: int = 1 + 16 * 32  # Vimg (vocab capacity = image_words*32, +0 slot)
    containers: int = 4       # C per pod
    sigs: int = 8             # S: registered pod-set signatures (row 0 reserved)
    ex_terms: int = 8         # T: registered existing-pod terms (row 0 reserved)
    spread_cons: int = 2      # C: topology-spread constraints per pod per kind
    ipa_terms: int = 2        # A: required (anti-)affinity terms per pod
    ipa_pref: int = 2         # PT: preferred terms per pod (both signs combined)
    prio_classes: int = 32    # distinct pod priority values (+ reserved row 0)
    superpods: int = 16       # S: torus superpods the grid axis can hold
    sp_slots: int = 16        # P: node positions per superpod torus
