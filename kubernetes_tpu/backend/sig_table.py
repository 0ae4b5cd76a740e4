"""Host-side pod-set signature & term tables backing ops/schema.TopoCounts.

The reference recomputes topology-pair match counts with an O(nodes × pods)
scan in every PreFilter (podtopologyspread/filtering.go:238 calPreFilterState,
interpodaffinity/filtering.go:86-135) — per pod, per cycle. The TPU design
inverts that: counts live on device, keyed by registered *signatures*
((namespace-spec, label-selector) pairs — the unit both plugins count pods
by) and *terms* (existing pods' (anti-)affinity terms, for the symmetric
checks), maintained incrementally per node generation. A scheduling batch
then only gathers + segment-reduces — no per-pod rescans.

Row 0 of both tables is reserved (all-zero), so invalid program slots read
zero counts.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, FrozenSet, List, Optional, Tuple

import numpy as np

from ..api.types import Pod
from ..framework.plugins.interpodaffinity import (
    AffinityTerm,
    preferred_affinity_terms,
    preferred_anti_affinity_terms,
    required_affinity_terms,
    required_anti_affinity_terms,
)
from ..framework.types import NodeInfo
from ..ops.encode import CapacityError, ClusterEncoder

NsLabelsFn = Callable[[str], Dict[str, str]]

# term classes (symmetric direction: existing pod's term vs incoming pod)
AFF_REQ = 1     # required affinity     → scored at hardPodAffinityWeight
ANTI_REQ = 2    # required anti-affinity → the Filter check (filtering.go:308)
AFF_PREF = 3    # preferred affinity     → scored at +term weight
ANTI_PREF = 4   # preferred anti-affinity → scored at −term weight

SelKey = Tuple  # canonical label-selector key
SigKey = Tuple[FrozenSet[str], Optional[SelKey], SelKey]
TermKey = Tuple[int, str, FrozenSet[str], Optional[SelKey], SelKey, int]


def _sel_canonical(sel) -> SelKey:
    return sel.signature() if sel is not None else None


@dataclass
class _Sig:
    namespaces: FrozenSet[str]
    ns_selector: object  # Optional[LabelSelector]
    selector: object     # LabelSelector

    def matches(self, pod: Pod, ns_labels_fn: NsLabelsFn) -> bool:
        if pod.meta.namespace in self.namespaces:
            ns_ok = True
        elif self.ns_selector is not None:
            ns_ok = self.ns_selector.matches(ns_labels_fn(pod.meta.namespace))
        else:
            ns_ok = False
        return ns_ok and self.selector.matches(pod.meta.labels)


@dataclass
class _Term:
    klass: int
    term: AffinityTerm


def term_key_of(term: AffinityTerm, klass: int) -> TermKey:
    return (
        klass,
        term.topology_key,
        term.namespaces,
        _sel_canonical(term.namespace_selector),
        _sel_canonical(term.selector),
        term.weight,
    )


class SigTable:
    """Registered signatures/terms + host-truth count matrices.

    ``sel_counts[s, n]`` / ``term_counts[t, n]`` are numpy (host truth);
    DeviceState uploads them when ``version`` advances past the uploaded one.
    """

    def __init__(self, encoder: ClusterEncoder, ns_labels_fn: Optional[NsLabelsFn] = None):
        self.encoder = encoder
        self.caps = encoder.caps
        self.ns_labels_fn: NsLabelsFn = ns_labels_fn or (lambda ns: {})
        self._sigs: Dict[SigKey, int] = {}
        self._sig_rows: List[Optional[_Sig]] = [None]  # row 0 reserved
        self._terms: Dict[TermKey, int] = {}
        self._term_rows: List[Optional[_Term]] = [None]
        self.sel_counts = np.zeros((self.caps.sigs, self.caps.nodes), np.int32)
        self.term_counts = np.zeros((self.caps.ex_terms, self.caps.nodes), np.int32)
        self.term_key_slots = np.zeros(self.caps.ex_terms, np.int32)
        self.version = 0
        # node slot -> pods currently counted there (set by recount_node)
        self._slot_pods: Dict[int, List[Pod]] = {}
        # per-bucket all-zeros TopoBatch cache: a topology-free batch with no
        # registered signatures/terms encodes to pure zeros — reuse one
        # device-resident instance instead of re-uploading ~24 zero arrays
        # per batch (a fixed ~10ms/batch on the headline workload)
        self._zero_topo: Dict[int, object] = {}

    @property
    def n_sigs(self) -> int:
        return len(self._sig_rows)

    @property
    def n_terms(self) -> int:
        return len(self._term_rows)

    # ---------------------------------------------------------------- register

    def sig_id(self, namespaces: FrozenSet[str], ns_selector, selector) -> int:
        key: SigKey = (namespaces, _sel_canonical(ns_selector), _sel_canonical(selector))
        sid = self._sigs.get(key)
        if sid is not None:
            return sid
        sid = len(self._sig_rows)
        if sid >= self.caps.sigs:
            raise CapacityError("sigs", sid + 1, self.caps.sigs)
        sig = _Sig(namespaces, ns_selector, selector)
        self._sigs[key] = sid
        self._sig_rows.append(sig)
        # backfill the new row over every populated node slot
        for slot, pods in self._slot_pods.items():
            c = sum(1 for p in pods if sig.matches(p, self.ns_labels_fn))
            if c:
                self.sel_counts[sid, slot] = c
        self.version += 1
        return sid

    def term_sig_id(self, term: AffinityTerm) -> int:
        return self.sig_id(term.namespaces, term.namespace_selector, term.selector)

    def term_id(self, term: AffinityTerm, klass: int) -> int:
        key = term_key_of(term, klass)
        tid = self._terms.get(key)
        if tid is not None:
            return tid
        tid = len(self._term_rows)
        if tid >= self.caps.ex_terms:
            raise CapacityError("ex_terms", tid + 1, self.caps.ex_terms)
        self._terms[key] = tid
        self._term_rows.append(_Term(klass, term))
        self.term_key_slots[tid] = self.encoder.key_slot(term.topology_key)
        for slot, pods in self._slot_pods.items():
            c = sum(1 for p in pods if key in self._pod_term_keys(p))
            if c:
                self.term_counts[tid, slot] = c
        self.version += 1
        return tid

    # ---------------------------------------------------------------- counting

    @staticmethod
    def _pod_terms(pod: Pod):
        """Per-pod (klass, term) list, cached on the pod object — term
        extraction walks the affinity tree and sits on the recount hot path
        (pod clones share spec, so the clone inherits the cache)."""
        cached = pod.__dict__.get("_sig_terms_all")
        if cached is None:
            cached = []
            for klass, terms in (
                (AFF_REQ, required_affinity_terms(pod)),
                (ANTI_REQ, required_anti_affinity_terms(pod)),
                (AFF_PREF, preferred_affinity_terms(pod)),
                (ANTI_PREF, preferred_anti_affinity_terms(pod)),
            ):
                cached.extend((klass, t) for t in terms)
            pod.__dict__["_sig_terms_all"] = cached
        return cached

    @classmethod
    def _pod_term_keys(cls, pod: Pod) -> FrozenSet[TermKey]:
        cached = pod.__dict__.get("_sig_term_keys")
        if cached is None:
            cached = frozenset(
                term_key_of(t, klass) for klass, t in cls._pod_terms(pod))
            pod.__dict__["_sig_term_keys"] = cached
        return cached

    def track_slot_pods(self, slot: int, ni: Optional[NodeInfo]) -> None:
        """Bookkeeping-only recount for the reconcile fast path: with NO
        registered signatures or terms both count tables are identically
        zero, so a full recount_node exists only to keep ``_slot_pods``
        fresh (the backfill source when a sig/term registers later). Any
        pod that could register a sig/term reaches the table first — the
        batched path registers at encode time (n_sigs/n_terms > 1 before
        its commit reconciles, taking the full-recount branch), and the
        fallback/sync paths recount on the next drain — so skipping the
        per-pod matching loops here loses nothing."""
        pods = list(ni.pods) if ni is not None else []
        if pods:
            self._slot_pods[slot] = pods
        else:
            self._slot_pods.pop(slot, None)

    def recount_node(self, slot: int, ni: Optional[NodeInfo]) -> None:
        """Recompute both count columns for one node slot from its pod list
        (called by DeviceState.sync for generation-dirty nodes)."""
        pods = list(ni.pods) if ni is not None else []
        if not pods and slot not in self._slot_pods:
            return  # nothing stored for this slot and nothing to count
        # register every term carried by this node's pods BEFORE counting, so
        # existing pods' anti-affinity is never invisible to the batch kernel
        for p in pods:
            for klass, t in self._pod_terms(p):
                self.term_id(t, klass)
        old_sel = self.sel_counts[:, slot].copy()
        old_term = self.term_counts[:, slot].copy()
        self.sel_counts[:, slot] = 0
        self.term_counts[:, slot] = 0
        for sid in range(1, self.n_sigs):
            sig = self._sig_rows[sid]
            self.sel_counts[sid, slot] = sum(
                1 for p in pods if sig.matches(p, self.ns_labels_fn)
            )
        if self.n_terms > 1:
            for p in pods:
                for key in self._pod_term_keys(p):
                    tid = self._terms.get(key)
                    if tid is not None:
                        self.term_counts[tid, slot] += 1
        if pods:
            self._slot_pods[slot] = pods
        else:
            self._slot_pods.pop(slot, None)
        if not np.array_equal(old_sel, self.sel_counts[:, slot]) or not np.array_equal(
            old_term, self.term_counts[:, slot]
        ):
            self.version += 1

    # ---------------------------------------------------------------- matching

    def pod_sig_mask(self, pod: Pod) -> np.ndarray:
        """[S] bool: which registered pod-sets this pod belongs to (the in-scan
        commit update when the pod lands on a node)."""
        m = np.zeros(self.caps.sigs, bool)
        for sid in range(1, self.n_sigs):
            m[sid] = self._sig_rows[sid].matches(pod, self.ns_labels_fn)
        return m

    def pod_term_mask(self, pod: Pod) -> np.ndarray:
        """[T] bool: which registered term rows this pod carries."""
        m = np.zeros(self.caps.ex_terms, bool)
        for key in self._pod_term_keys(pod):
            tid = self._terms.get(key)
            if tid is not None:
                m[tid] = True
        return m

    # ---------------------------------------------------------------- encoding

    def topo_counts(self):
        """Device TopoCounts view of the host-truth matrices."""
        import jax.numpy as jnp

        from ..ops.schema import TopoCounts

        return TopoCounts(
            sel_counts=jnp.asarray(self.sel_counts),
            term_counts=jnp.asarray(self.term_counts),
            term_key=jnp.asarray(self.term_key_slots),
        )

    def _zero_arrays(self, P: int) -> dict:
        caps = self.caps
        C, A, PT, S, T = (caps.spread_cons, caps.ipa_terms, caps.ipa_pref,
                          caps.sigs, caps.ex_terms)
        z = np.zeros
        return {
            "sf_valid": z((P, C), bool), "sf_sig": z((P, C), np.int32),
            "sf_key": z((P, C), np.int32), "sf_skew": z((P, C), np.int32),
            "sf_self": z((P, C), bool), "sf_min_domains": np.full((P, C), -1, np.int32),
            "ss_valid": z((P, C), bool), "ss_sig": z((P, C), np.int32),
            "ss_key": z((P, C), np.int32), "ss_skew": z((P, C), np.int32),
            "ss_hostname": z((P, C), bool), "ss_require_all": z(P, bool),
            "ia_valid": z((P, A), bool), "ia_sig": z((P, A), np.int32),
            "ia_key": z((P, A), np.int32), "ia_self_all": z(P, bool),
            "ianti_valid": z((P, A), bool), "ianti_sig": z((P, A), np.int32),
            "ianti_key": z((P, A), np.int32),
            "ip_valid": z((P, PT), bool), "ip_sig": z((P, PT), np.int32),
            "ip_key": z((P, PT), np.int32), "ip_w": z((P, PT), np.int32),
            "term_filter_match": z((P, T), bool), "term_score_w": z((P, T), np.float32),
            "pod_sig_mask": z((P, S), bool), "pod_term_mask": z((P, T), bool),
        }

    def _build_zero_topo(self, P: int):
        import jax.numpy as jnp

        from ..ops.schema import TopoBatch

        return TopoBatch(**{k: jnp.asarray(v)
                            for k, v in self._zero_arrays(P).items()})

    def encode_topo(self, pods: List[Pod], hard_pod_affinity_weight: int = 1,
                    ignore_preferred: bool = False, capacity=None):
        """Compile a pod batch's topology programs → TopoBatch.

        Two passes: first register every signature/term the batch introduces
        (so pod i's match rows see pod j<i's terms — intra-batch symmetric
        anti-affinity), then fill the arrays."""
        import jax.numpy as jnp

        from ..api.types import DO_NOT_SCHEDULE, MATCH_NOTHING, SCHEDULE_ANYWAY
        from ..framework.plugins.podtopologyspread import HOSTNAME_KEY
        from ..ops.schema import TopoBatch

        caps = self.caps
        # pad to a smaller pod bucket when asked (must match encode_pods —
        # the compiled program's step count is the padded size)
        P = caps.pods if capacity is None else min(int(capacity), caps.pods)
        if len(pods) > caps.pods:
            raise CapacityError("pods", len(pods), caps.pods)
        assert len(pods) <= P, "bucket smaller than the batch"

        if self.n_sigs <= 1 and self.n_terms <= 1 and not any(
            pod.spec.topology_spread_constraints
            or (pod.spec.affinity is not None
                and (pod.spec.affinity.pod_affinity is not None
                     or pod.spec.affinity.pod_anti_affinity is not None))
            for pod in pods
        ):
            cached = self._zero_topo.get(P)
            if cached is None:
                cached = self._build_zero_topo(P)
                self._zero_topo[P] = cached
            self.last_topo_summary = {"hostname_only": False, "vd_needed": 1}
            return cached

        # ---- pass 1: registration
        for pod in pods:
            for c in pod.spec.topology_spread_constraints:
                sel = c.label_selector if c.label_selector is not None else MATCH_NOTHING
                self.sig_id(frozenset({pod.meta.namespace}), None, sel)
                self.encoder.key_slot(c.topology_key)
            for klass, terms in (
                (AFF_REQ, required_affinity_terms(pod)),
                (ANTI_REQ, required_anti_affinity_terms(pod)),
                (AFF_PREF, preferred_affinity_terms(pod)),
                (ANTI_PREF, preferred_anti_affinity_terms(pod)),
            ):
                for t in terms:
                    self.term_id(t, klass)
                    self.term_sig_id(t)

        # ---- pass 2: arrays
        C, A, PT = caps.spread_cons, caps.ipa_terms, caps.ipa_pref
        out = self._zero_arrays(P)

        for p, pod in enumerate(pods):
            sf = [c for c in pod.spec.topology_spread_constraints
                  if c.when_unsatisfiable == DO_NOT_SCHEDULE]
            ss = [c for c in pod.spec.topology_spread_constraints
                  if c.when_unsatisfiable == SCHEDULE_ANYWAY]
            if len(sf) > C:
                raise CapacityError("spread_cons", len(sf), C)
            if len(ss) > C:
                raise CapacityError("spread_cons", len(ss), C)
            for i, c in enumerate(sf):
                sel = c.label_selector if c.label_selector is not None else MATCH_NOTHING
                out["sf_valid"][p, i] = True
                out["sf_sig"][p, i] = self.sig_id(frozenset({pod.meta.namespace}), None, sel)
                out["sf_key"][p, i] = self.encoder.key_slot(c.topology_key)
                out["sf_skew"][p, i] = c.max_skew
                out["sf_self"][p, i] = sel.matches(pod.meta.labels)
                if c.min_domains is not None:
                    out["sf_min_domains"][p, i] = c.min_domains
            for i, c in enumerate(ss):
                sel = c.label_selector if c.label_selector is not None else MATCH_NOTHING
                out["ss_valid"][p, i] = True
                out["ss_sig"][p, i] = self.sig_id(frozenset({pod.meta.namespace}), None, sel)
                out["ss_key"][p, i] = self.encoder.key_slot(c.topology_key)
                out["ss_skew"][p, i] = c.max_skew
                out["ss_hostname"][p, i] = c.topology_key == HOSTNAME_KEY
            # pod-specified constraints ⇒ require-all-topology-keys at PreScore
            out["ss_require_all"][p] = bool(pod.spec.topology_spread_constraints)

            ia = required_affinity_terms(pod)
            if len(ia) > A:
                raise CapacityError("ipa_terms", len(ia), A)
            for i, t in enumerate(ia):
                out["ia_valid"][p, i] = True
                out["ia_sig"][p, i] = self.term_sig_id(t)
                out["ia_key"][p, i] = self.encoder.key_slot(t.topology_key)
            out["ia_self_all"][p] = all(t.matches(pod, self.ns_labels_fn) for t in ia)

            ianti = required_anti_affinity_terms(pod)
            if len(ianti) > A:
                raise CapacityError("ipa_terms", len(ianti), A)
            for i, t in enumerate(ianti):
                out["ianti_valid"][p, i] = True
                out["ianti_sig"][p, i] = self.term_sig_id(t)
                out["ianti_key"][p, i] = self.encoder.key_slot(t.topology_key)

            prefs = [(t, t.weight) for t in preferred_affinity_terms(pod)] + [
                (t, -t.weight) for t in preferred_anti_affinity_terms(pod)]
            if len(prefs) > PT:
                raise CapacityError("ipa_pref", len(prefs), PT)
            for i, (t, w) in enumerate(prefs):
                out["ip_valid"][p, i] = True
                out["ip_sig"][p, i] = self.term_sig_id(t)
                out["ip_key"][p, i] = self.encoder.key_slot(t.topology_key)
                out["ip_w"][p, i] = w

            fmatch, w = self.term_match_rows(pod, hard_pod_affinity_weight, ignore_preferred)
            out["term_filter_match"][p] = fmatch
            out["term_score_w"][p] = w
            out["pod_sig_mask"][p] = self.pod_sig_mask(pod)
            out["pod_term_mask"][p] = self.pod_term_mask(pod)

        # topology-mode summary for the compiled-program selection: which
        # key slots this batch (plus every REGISTERED existing term — they
        # participate in every batch) touches, and the domain capacity a
        # compact segment axis would need for the non-hostname ones
        host_slot = self.encoder.key_slot(HOSTNAME_KEY)
        involved = set(int(k) for k in self.term_key_slots[1:self.n_terms])
        for fld in ("sf_key", "ss_key", "ia_key", "ianti_key", "ip_key"):
            v_fld = fld.replace("_key", "_valid")
            involved.update(np.unique(out[fld][out[v_fld]]).tolist())
        involved.discard(0)
        others = involved - {host_slot}
        # domain capacity the GENERAL path needs: every involved key's vocab
        # — including hostname when involved (a mixed batch, or the
        # duplicate-hostname fallback, aggregates hostname domains too)
        vd_needed = 1
        for ks in involved:
            vv = self.encoder.value_vocabs.get(ks)
            if vv is not None:
                vd_needed = max(vd_needed, len(vv))
        self.last_topo_summary = {
            "hostname_only": bool(involved) and not others,
            "vd_needed": vd_needed,
        }
        return TopoBatch(**{k: jnp.asarray(v) for k, v in out.items()})

    def term_match_rows(self, pod: Pod, hard_pod_affinity_weight: int = 1,
                        ignore_preferred: bool = False) -> Tuple[np.ndarray, np.ndarray]:
        """For an incoming pod: ([T] bool ANTI_REQ-matches for the Filter check,
        [T] float32 symmetric score weights) — term.matches(incoming) evaluated
        host-side (interpodaffinity filtering.go:174, scoring.go:79)."""
        fmatch = np.zeros(self.caps.ex_terms, bool)
        w = np.zeros(self.caps.ex_terms, np.float32)
        for tid in range(1, self.n_terms):
            row = self._term_rows[tid]
            if not row.term.matches(pod, self.ns_labels_fn):
                continue
            if row.klass == ANTI_REQ:
                fmatch[tid] = True
            if row.klass == AFF_REQ:
                w[tid] = float(hard_pod_affinity_weight)
            elif row.klass == AFF_PREF and not ignore_preferred:
                w[tid] = float(row.term.weight)
            elif row.klass == ANTI_PREF and not ignore_preferred:
                w[tid] = -float(row.term.weight)
        return fmatch, w
