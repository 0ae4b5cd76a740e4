"""Scheduler cache: assume/confirm/expire pod state machine + incremental
snapshot (internal/cache/cache.go).

State machine (interface.go:32-56 diagram):

    AssumePod → (FinishBinding → expire-after-TTL | AddPod confirms |
                 ForgetPod removes)

Assumed pods are counted in their node's NodeInfo immediately so the next
cycle sees them (the optimistic-commit that lets scheduling run ahead of
binding, schedule_one.go:734 assume).  ``cleanup(now)`` sweeps expired
assumptions (cache.go:731 run/cleanupAssumedPods — here called by the
scheduler loop instead of a background goroutine).

Snapshot updates are O(changed nodes): every NodeInfo mutation bumps its
monotonic generation; ``update_snapshot`` re-clones only nodes whose
generation is newer than the snapshot's (cache.go:198 UpdateSnapshot).  The
same generation stream drives the TPU backend's delta uploads.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, NamedTuple, Optional, Tuple

from ..api.types import Node, Pod
from ..framework.types import NodeInfo, next_generation
from ..testing import locktrace
from ..utils import tracing
from .snapshot import Snapshot

DEFAULT_ASSUME_TTL = 30.0  # durationToExpireAssumedPod (scheduler.go:311)


@dataclass
class _PodState:
    pod: Pod
    assumed: bool = False
    binding_finished: bool = False
    deadline: Optional[float] = None
    # the node generation the add of this pod gave its NodeInfo: a snapshot
    # clone at a generation in [added_gen, the removal's) holds the pod
    added_gen: int = 0


class Removal(NamedTuple):
    """What ``remove_pod`` took off a node: the node, and the node
    generations that added and removed the pod. A view of the node cloned
    at generation g holds the pod iff added_gen <= g < removed_gen."""

    node: str
    added_gen: int
    removed_gen: int


class Cache:
    def __init__(self, ttl: float = DEFAULT_ASSUME_TTL, now_fn=time.monotonic):
        self._lock = locktrace.make_rlock("Cache")
        self.ttl = ttl
        self.now_fn = now_fn
        self.nodes: Dict[str, NodeInfo] = {}
        self.pod_states: Dict[str, _PodState] = {}  # pod key -> state
        self._assumed: set = set()                  # keys with assumed=True
        # dirty-tracking so update_snapshot is O(changes), like the reference's
        # generation-ordered node list (cache.go headNode)
        self._dirty: set = set()
        self._removed: set = set()
        self._sync_generation = 0
        # priority histogram over pods assigned to nodes: lets the batched
        # preemption path prove "no evictable victim exists anywhere" in
        # O(1) instead of dry-running candidates (preemption.go:319's
        # eligibility is per-pod; this is the cluster-level shortcut)
        self._prio_counts: Dict[int, int] = {}
        # entries with a REAL Node object (ghost NodeInfos excluded):
        # node_count() sits on the per-batch hot path and a full scan of
        # self.nodes was a measured ~5ms/call at 5k nodes
        self._real_nodes = 0

    # ------------------------------------------------------------- pods

    # locked cores — ONE implementation each, shared by the per-pod verbs
    # and apply_batch so the two paths can never drift

    def _assume_locked(self, pod: Pod, node_name: str) -> None:
        key = pod.key()
        if key in self.pod_states:
            raise KeyError(f"pod {key} already in cache")
        pod.spec.node_name = node_name
        gen = self._add_pod_to_node(pod, node_name)
        self.pod_states[key] = _PodState(pod=pod, assumed=True, added_gen=gen)
        self._assumed.add(key)

    def _finish_locked(self, pod: Pod) -> None:
        ps = self.pod_states.get(pod.key())
        if ps and ps.assumed:
            ps.binding_finished = True
            ps.deadline = self.now_fn() + self.ttl

    def _forget_locked(self, pod: Pod) -> None:
        ps = self.pod_states.pop(pod.key(), None)
        self._assumed.discard(pod.key())
        if ps is not None:
            self._remove_pod_from_node(ps.pod, ps.pod.spec.node_name)

    def assume_pod(self, pod: Pod, node_name: str) -> None:
        """Optimistically commit ``pod`` to ``node_name``. Takes ownership of
        the passed object (callers pass a clone; its spec.node_name is set
        here so Reserve/Permit/Bind plugins see the assignment, matching the
        reference's assumedPod)."""
        with self._lock:
            self._assume_locked(pod, node_name)

    def finish_binding(self, pod: Pod) -> None:
        with self._lock:
            self._finish_locked(pod)

    def forget_pod(self, pod: Pod) -> None:
        """Binding failed: roll the assumption back (cache.go:416)."""
        with self._lock:
            self._forget_locked(pod)

    def add_pod(self, pod: Pod) -> None:
        """Informer confirmation of a bound pod (cache.go:497)."""
        key = pod.key()
        with self._lock:
            ps = self.pod_states.get(key)
            if ps is not None and ps.assumed:
                gen = ps.added_gen
                if ps.pod.spec.node_name != pod.spec.node_name:
                    # scheduled elsewhere than assumed: relocate
                    self._remove_pod_from_node(ps.pod, ps.pod.spec.node_name)
                    gen = self._add_pod_to_node(pod, pod.spec.node_name)
                self.pod_states[key] = _PodState(pod=pod, added_gen=gen)
                self._assumed.discard(key)
                return
            if ps is not None:
                return  # duplicate add
            gen = self._add_pod_to_node(pod, pod.spec.node_name)
            self.pod_states[key] = _PodState(pod=pod, added_gen=gen)

    def update_pod(self, old: Pod, new: Pod) -> None:
        with self._lock:
            ps = self.pod_states.get(old.key())
            if ps is None:
                self.add_pod(new)
                return
            self._remove_pod_from_node(ps.pod, ps.pod.spec.node_name)
            gen = self._add_pod_to_node(new, new.spec.node_name)
            self.pod_states[old.key()] = _PodState(pod=new, added_gen=gen)

    def remove_pod(self, pod: Pod) -> Optional[Removal]:
        """Drop a pod from the cache; returns the Removal it made, or None
        when no node held the pod."""
        with self._lock:
            ps = self.pod_states.pop(pod.key(), None)
            self._assumed.discard(pod.key())
            if ps is None:
                return None
            node = ps.pod.spec.node_name
            gen = self._remove_pod_from_node(ps.pod, node)
            return Removal(node, ps.added_gen, gen) if gen else None

    def locked(self):
        """The cache's (re-entrant) lock: a caller that keeps bookkeeping of
        its own beside pod removals and snapshot updates holds it across
        both, so each removal lands wholly before or after a snapshot."""
        return self._lock

    def apply_batch(self, ops) -> List[Optional[Exception]]:
        """Batched pod-state transitions — the cache half of the commit data
        plane: one lock round trip applies a whole scheduler batch's worth
        of assume/finish/forget transitions (per-pod calls were 2+ lock
        acquisitions per committed pod on the measured host.commit
        bottleneck). ``ops`` is a sequence of tuples:

            ("assume", pod, node_name)  — assume_pod semantics
            ("finish", pod)             — finish_binding semantics
            ("forget", pod)             — forget_pod semantics

        Each op applies independently; a failing op (assume of an already-
        cached key) records its exception and later ops still apply. Returns
        per-op None-or-exception in input order — callers decide per pod,
        exactly as with the per-pod calls."""
        out: List[Optional[Exception]] = [None] * len(ops)
        with self._lock:
            for i, op in enumerate(ops):
                verb = op[0]
                if verb == "assume":
                    try:
                        self._assume_locked(op[1], op[2])
                    except KeyError as err:
                        out[i] = err
                elif verb == "finish":
                    self._finish_locked(op[1])
                elif verb == "forget":
                    self._forget_locked(op[1])
                else:
                    out[i] = ValueError(f"unknown cache batch op {verb!r}")
        return out

    def is_assumed(self, pod_key: str) -> bool:
        with self._lock:
            ps = self.pod_states.get(pod_key)
            return bool(ps and ps.assumed)

    def cleanup(self, now: Optional[float] = None) -> List[Pod]:
        """Expire assumed-but-never-confirmed pods; returns the expired pods
        (cleanupAssumedPods, cache.go:735)."""
        now = self.now_fn() if now is None else now
        expired = []
        with self._lock:
            for key in list(self._assumed):
                ps = self.pod_states.get(key)
                if ps and ps.binding_finished and ps.deadline is not None and now > ps.deadline:
                    expired.append(ps.pod)
                    self.pod_states.pop(key)
                    self._assumed.discard(key)
                    self._remove_pod_from_node(ps.pod, ps.pod.spec.node_name)
        return expired

    # ------------------------------------------------------------- nodes

    def add_node(self, node: Node) -> None:
        with self._lock:
            ni = self.nodes.get(node.meta.name)
            if ni is None:
                ni = NodeInfo()
                self.nodes[node.meta.name] = ni
            if ni.node is None:
                self._real_nodes += 1
            ni.set_node(node)
            self._dirty.add(node.meta.name)
            self._removed.discard(node.meta.name)

    def update_node(self, node: Node) -> None:
        self.add_node(node)

    def remove_node(self, node_name: str) -> None:
        with self._lock:
            ni = self.nodes.get(node_name)
            if ni is None:
                return
            # keep the entry while pods remain (reference keeps ghost nodes
            # for pods not yet deleted), else drop
            if ni.node is not None:
                self._real_nodes -= 1
            ni.node = None
            ni.generation = next_generation()
            self._dirty.add(node_name)
            if not ni.pods:
                del self.nodes[node_name]
                self._dirty.discard(node_name)
                self._removed.add(node_name)

    def _node_info(self, node_name: str) -> NodeInfo:  # ktpu: locked
        ni = self.nodes.get(node_name)
        if ni is None:
            ni = NodeInfo()  # pod arrived before its node: ghost entry
            self.nodes[node_name] = ni
        return ni

    def _add_pod_to_node(self, pod: Pod, node_name: str) -> int:  # ktpu: locked
        """Returns the generation the add gave the node (0: no node)."""
        if not node_name:
            return 0
        ni = self._node_info(node_name)
        ni.add_pod(pod)
        self._dirty.add(node_name)
        self._removed.discard(node_name)
        prio = pod.spec.priority
        self._prio_counts[prio] = self._prio_counts.get(prio, 0) + 1
        return ni.generation

    def _remove_pod_from_node(self, pod: Pod, node_name: str) -> int:  # ktpu: locked
        """Returns the generation the removal gave the node (0: the node
        did not hold the pod)."""
        ni = self.nodes.get(node_name)
        gen = 0
        if ni is not None:
            if ni.remove_pod(pod):
                gen = ni.generation
            prio = pod.spec.priority
            left = self._prio_counts.get(prio, 0) - 1
            if left > 0:
                self._prio_counts[prio] = left
            else:
                self._prio_counts.pop(prio, None)
            self._dirty.add(node_name)
            if ni.node is None and not ni.pods:
                self.nodes.pop(node_name, None)
                self._dirty.discard(node_name)
                self._removed.add(node_name)
        return gen

    # ------------------------------------------------------------- snapshot

    def update_snapshot(self, snapshot: Snapshot) -> Snapshot:
        """Incremental: re-clone only NodeInfos dirtied since the snapshot's
        generation; O(changes) not O(nodes) (cache.go:198's generation-ordered
        list, realized as a dirty set). A snapshot older than the dirty-set
        horizon (e.g. a brand-new Snapshot) gets a full resync. The update,
        lock wait included, is one ``cache.snapshot`` span whose ``nodes``
        attribute counts the NodeInfos re-cloned."""
        with tracing.span("cache.snapshot") as sp:
            cloned = self._update_snapshot(snapshot)
            if sp is not None:
                sp.attributes["nodes"] = cloned
        return snapshot

    def _update_snapshot(self, snapshot: Snapshot) -> int:
        """update_snapshot's work; returns the number of NodeInfos cloned."""
        cloned = 0
        with self._lock:
            max_gen = snapshot.generation
            changed = False
            full = snapshot.generation < self._horizon()
            # structural = node-set membership or a zone changed → the
            # snapshot's cached interleave order must be rebuilt; pod-only
            # churn (the batch commit path) keeps it (snapshot.py refresh_lists)
            structural = full
            batch_changed = set()
            names = self.nodes.keys() if full else (self._dirty | self._removed)
            for name in names:
                ni = self.nodes.get(name)
                if ni is None:
                    if name in snapshot.node_info_map:
                        del snapshot.node_info_map[name]
                        snapshot.changed_names.add(name)
                        batch_changed.add(name)
                        changed = True
                        structural = True
                    continue
                if ni.generation > snapshot.generation:
                    if not structural and snapshot.order_affected_by(name, ni.node):
                        structural = True
                    prev = snapshot.node_info_map.get(name)
                    if prev is None or prev.node is not ni.node:
                        snapshot.node_object_version += 1
                    snapshot.node_info_map[name] = ni.clone()
                    cloned += 1
                    snapshot.changed_names.add(name)
                    batch_changed.add(name)
                    max_gen = max(max_gen, ni.generation)
                    changed = True
            if full:
                stale = [n for n in snapshot.node_info_map if n not in self.nodes]
                for n in stale:
                    del snapshot.node_info_map[n]
                    snapshot.changed_names.add(n)
                    batch_changed.add(n)
                    changed = True
            self._dirty.clear()
            self._removed.clear()
            self._sync_generation = max_gen
            if changed:
                snapshot.refresh_lists(structural=structural,
                                       changed_names=batch_changed)
            snapshot.generation = max_gen
        return cloned

    def _horizon(self) -> int:  # ktpu: locked
        """Oldest snapshot generation the dirty set can serve incrementally."""
        return self._sync_generation

    def dirty_nodes(self, since_generation: int) -> List[str]:
        """Node names whose generation advanced past ``since_generation`` —
        the TPU backend's delta-upload worklist."""
        with self._lock:
            return [n for n, ni in self.nodes.items() if ni.generation > since_generation]

    def min_pod_priority(self) -> Optional[int]:
        """Lowest priority among pods currently assigned to nodes; None when
        no pod is assigned. A pending pod with priority <= this value cannot
        have preemption victims anywhere."""
        with self._lock:
            return min(self._prio_counts) if self._prio_counts else None

    def node_count(self) -> int:
        with self._lock:
            return self._real_nodes

    def has_real_node(self, node_name: str) -> bool:
        """True iff the cache holds a LIVE Node object under this name
        (ghost entries kept for not-yet-deleted pods don't count) — the
        commit-time existence probe for placements decided while the node
        was being removed."""
        with self._lock:
            ni = self.nodes.get(node_name)
            return ni is not None and ni.node is not None

    def missing_real_nodes(self, names) -> set:
        """Subset of ``names`` with no LIVE Node object — the batched form
        of has_real_node (one lock acquisition for a whole commit's worth
        of winner probes; the commit plane is the measured bottleneck)."""
        with self._lock:
            out = set()
            for name in names:
                ni = self.nodes.get(name)
                if ni is None or ni.node is None:
                    out.add(name)
            return out

    def stats(self) -> Tuple[int, int, int]:
        """(nodes, pods, assumed_pods) — the scheduler_cache_size gauge feed
        and the /debug/cache counts (cache.go:96 Dump's totals)."""
        with self._lock:
            return self._real_nodes, len(self.pod_states), len(self._assumed)
