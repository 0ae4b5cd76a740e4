"""Device-resident cluster mirror with generation-keyed delta uploads.

The TPU analog of the incremental snapshot (cache.go:198): the host tracks the
last-uploaded generation per node slot; ``sync`` encodes only dirty NodeInfos
into row blocks and applies them with one batched scatter per field —
the `dynamic_update_slice` pipeline of SURVEY.md §7 step 3.

Capacity growth: encoders raise CapacityError when a vocab/axis overflows; the
caller rebuilds DeviceState with grown Capacities and resyncs from scratch
(recompilation policy: double the offending axis — bucketed static shapes).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..cache.snapshot import Snapshot
from ..framework.types import NodeInfo
from ..ops.encode import CapacityError, ClusterEncoder
from ..ops.schema import Capacities, INT_NONE, NodeTensors

_ROW_FIELDS = (
    ("valid", bool), ("unschedulable", bool),
    ("allocatable", np.int32), ("requested", np.int32), ("nonzero_requested", np.int32),
    ("label_val", np.int32), ("label_num", np.int32),
    ("taint_key", np.int32), ("taint_val", np.int32), ("taint_effect", np.int32),
    ("port_bits", np.uint32), ("image_bits", np.uint32), ("class_req", np.int32),
    ("name_hash", np.uint32), ("topo_sp", np.int32), ("topo_pos", np.int32),
)


def _apply_rows(nt: NodeTensors, slots: jax.Array, updates: dict,
                image_sizes: jax.Array, image_num_nodes: jax.Array) -> NodeTensors:
    """One fused scatter of all dirty rows into the node tensors, jitted.
    Slot counts are bucketed by the caller so this compiles once per bucket,
    not once per distinct dirty-row count (no donation: image_sizes may alias
    a field of nt when the image vocab is unchanged)."""
    new_fields = {f: getattr(nt, f).at[slots].set(updates[f]) for f in updates}
    new_fields["image_sizes"] = image_sizes
    new_fields["image_num_nodes"] = image_num_nodes
    new_fields["class_prio"] = nt.class_prio
    return NodeTensors(**new_fields)


_apply_rows_jit = jax.jit(_apply_rows)


def _release_rows(requested: jax.Array, nonzero: jax.Array,
                  class_req: jax.Array, sel_counts: Optional[jax.Array],
                  slots: jax.Array, req: jax.Array, nz: jax.Array,
                  cls: jax.Array, sig: Optional[jax.Array]):
    """Subtract released pods' rows from the carry, jitted: ``req``/``nz``
    [B, R] at ``slots`` [B], ``req`` again at (slot, class ``cls``), and
    with a topology carry its [S, N] counts by the pods' [B, S] signature
    rows. Padding rows are zeros at slot 0; a slot may repeat (adds
    accumulate)."""
    out = (requested.at[slots].add(-req), nonzero.at[slots].add(-nz),
           class_req.at[slots, cls].add(-req))
    if sel_counts is None:
        return out + (None,)
    return out + (sel_counts.at[:, slots].add(-sig.T),)


_release_rows_jit = jax.jit(_release_rows)

# a row value at the int32 clamp no longer says what it sums: a release
# there cannot be subtracted exactly
_CLAMP = np.int32(2 ** 31 - 1)


def _bucket(n: int, floor: int = 8) -> int:
    """Next power of two ≥ n (≥ floor) — the static-shape recompile guard."""
    b = floor
    while b < n:
        b *= 2
    return b


class DeviceState:
    def __init__(self, caps: Capacities, ns_labels_fn=None):
        from .sig_table import SigTable

        self.caps = caps
        self.encoder = ClusterEncoder(caps)
        self.sig_table = SigTable(self.encoder, ns_labels_fn)
        self.nt = self._empty_tensors()
        self._n_prio = len(self.encoder.prio_vocab)  # uploaded class_prio size
        self._tc = None                           # cached device TopoCounts
        self._tc_version = -1
        self._uploaded_gen: Dict[str, int] = {}   # node name -> generation on device
        self._image_counts: Dict[str, int] = {}   # image -> num nodes (host truth)
        self._image_sizes: Dict[str, int] = {}
        self._node_images: Dict[str, frozenset] = {}
        self.syncs = 0
        self.rows_uploaded = 0
        self.rows_elided = 0
        self.nodes_removed = 0  # removal-sweep tombstones (elastic churn)
        # transfer telemetry: bytes scattered device-ward by the last /
        # all sync calls (the padded row-block size — what actually goes
        # host→device), read by backend/telemetry.py and /debug
        self.last_upload_bytes = 0
        self.upload_bytes = 0
        # host-side mirror of the device row content: lets sync skip rows
        # whose re-encoded content already matches the device (in particular
        # rows whose only change was an adopted batch commit). Initialized to
        # the empty-row encoding, matching _empty_tensors (label_num is
        # INT_NONE-filled, not zero).
        empty_row = self.encoder.encode_node_row(NodeInfo())
        self._mirror: Dict[str, np.ndarray] = {
            field: np.broadcast_to(
                np.asarray(empty_row[field], dtype), (caps.nodes,) + np.shape(empty_row[field])
            ).copy()
            for field, dtype in _ROW_FIELDS
        }
        # Node OBJECT identity at the last mirror write per node name: while
        # unchanged, the row's static fields (labels/taints/allocatable/
        # images) cannot differ from the mirror, so reconcile only needs to
        # compare the pod-commit-dynamic fields
        self._mirror_node: Dict[str, object] = {}
        # --- device-attribute table (resource.k8s.io DRA) -----------------
        # [nodes, A] kind/value cells synced from node-published device
        # slices (NodeStatus.device_attributes): kind 0 = absent, 1 = int,
        # 2 = interned string id. Kept OUTSIDE NodeTensors on purpose —
        # attributes are static per node object (no batch commit ever
        # touches them), so they need none of the mirror/adoption machinery;
        # the claim-feasibility kernel (backend/batch.py) reads them
        # directly. The attribute-key axis grows by doubling (bucketed
        # static shapes, same policy as Capacities).
        self.attr_slots: Dict[str, int] = {}   # attribute key -> column
        self.attr_val_ids: Dict[str, int] = {} # string value vocab (ids from 1)
        # refcounted release for the attribute-value vocab (the label/taint
        # vocab treatment from the elastic PR, ROADMAP item 5 follow-up):
        # per-value publishing-node counts; an id freed at refcount zero
        # joins the free-list and is recycled before the counter grows, so
        # node churn with fresh attribute values cannot grow the vocab
        # monotonically. Selector operands interned without a publishing
        # node stay pinned (bounded by distinct configured operand values).
        self._attr_val_refs: Dict[str, int] = {}
        self._attr_val_free: List[int] = []
        self._attr_val_next = 1
        self._node_attr_values: Dict[str, frozenset] = {}
        self._attr_cols = 8
        self._attr_kind_m = np.zeros((caps.nodes, self._attr_cols), np.int32)
        self._attr_val_m = np.zeros((caps.nodes, self._attr_cols), np.int32)
        # jnp.array (copying), never asarray: the host mirror keeps mutating
        # and a zero-copy alias would silently corrupt the device view
        self.attr_kind = jnp.array(self._attr_kind_m)
        self.attr_val = jnp.array(self._attr_val_m)
        self._node_attrs: Dict[str, dict] = {}  # name -> last-synced mapping
        # --- per-namespace quota screen tensors (ops/quota.py) -----------
        # [NS, Q] usage/limit pair the batch program's over-quota screen
        # judges winners against: synced from the host quota ledger before
        # dispatch (content-diffed — an unchanged table re-uploads nothing)
        # and carried to remote devices by the delta channel's quotaTable
        # payload. Kept OUTSIDE NodeTensors like the attribute table: the
        # namespace axis is its own bucketed shape (grown by doubling) and
        # no batch commit mutates it device-side — the evolving copy lives
        # only inside the screen's scan carry.
        from ..ops.quota import QUOTA_DIMS, QUOTA_NO_LIMIT

        self.nsq_slots: Dict[str, int] = {}   # namespace -> tensor row
        self._nsq_rows = 8
        self._nsq_used_m = np.zeros((self._nsq_rows, QUOTA_DIMS), np.int32)
        self._nsq_limit_m = np.full((self._nsq_rows, QUOTA_DIMS),
                                    QUOTA_NO_LIMIT, np.int32)
        # jnp.array (copying) for the same aliasing reason as attr_kind
        self.nsq_used = jnp.array(self._nsq_used_m)
        self.nsq_limit = jnp.array(self._nsq_limit_m)
        self.nsq_uploads = 0  # content-diff re-uploads (telemetry/debug)
        # O(changes) reconcile/has_dirty: names this device previously left
        # dirty, and the snapshot structure version it last fully walked.
        # While the structure version is unchanged, only changed_names ∪
        # _recon_pending can possibly be gen-stale — the full-N walk is
        # reserved for membership/zone changes (snapshot.py changed_names).
        self._recon_pending: set = set()
        self._seen_struct: int = -1
        # the topology carry the newest dispatch evolved ((sel_counts,
        # seg_exist) futures, None without topology): the pipelined next
        # batch starts from it, less the pods released since
        self.topo_carry = None

    @property
    def tc(self):
        """Device TopoCounts, re-uploaded only when the host truth changed."""
        if self._tc is None or self._tc_version != self.sig_table.version:
            self._tc = self.sig_table.topo_counts()
            self._tc_version = self.sig_table.version
        return self._tc

    @property
    def topo_enabled(self) -> bool:
        return self.sig_table.n_sigs > 1 or self.sig_table.n_terms > 1

    def _empty_tensors(self) -> NodeTensors:
        c = self.caps
        z = np.zeros
        return NodeTensors(
            valid=jnp.asarray(z(c.nodes, bool)),
            unschedulable=jnp.asarray(z(c.nodes, bool)),
            allocatable=jnp.asarray(z((c.nodes, c.resources), np.int32)),
            requested=jnp.asarray(z((c.nodes, c.resources), np.int32)),
            nonzero_requested=jnp.asarray(z((c.nodes, c.resources), np.int32)),
            label_val=jnp.asarray(z((c.nodes, c.label_keys), np.int32)),
            label_num=jnp.asarray(np.full((c.nodes, c.label_keys), INT_NONE, np.int32)),
            taint_key=jnp.asarray(z((c.nodes, c.taints), np.int32)),
            taint_val=jnp.asarray(z((c.nodes, c.taints), np.int32)),
            taint_effect=jnp.asarray(z((c.nodes, c.taints), np.int32)),
            port_bits=jnp.asarray(z((c.nodes, c.port_words), np.uint32)),
            image_bits=jnp.asarray(z((c.nodes, c.image_words), np.uint32)),
            image_sizes=jnp.asarray(z(c.images, np.int32)),
            image_num_nodes=jnp.asarray(z(c.images, np.int32)),
            class_req=jnp.asarray(z((c.nodes, c.prio_classes, c.resources), np.int32)),
            class_prio=jnp.asarray(self.encoder.class_prio_array()),
            name_hash=jnp.asarray(z(c.nodes, np.uint32)),
            topo_sp=jnp.asarray(np.full(c.nodes, -1, np.int32)),
            topo_pos=jnp.asarray(np.full(c.nodes, -1, np.int32)),
        )

    # ------------------------------------------------------- device attributes

    def attr_slot(self, key: str) -> int:
        """Column for an attribute key, registering (and growing the axis by
        doubling) on first sight. Selector encoding registers keys too, so a
        selector on a never-published key gets a real, all-absent column."""
        slot = self.attr_slots.get(key)
        if slot is None:
            slot = len(self.attr_slots)
            self.attr_slots[key] = slot
            while slot >= self._attr_cols:
                self._grow_attr_cols()
        return slot

    def _grow_attr_cols(self) -> None:
        cols = self._attr_cols * 2
        pad = ((0, 0), (0, cols - self._attr_cols))
        self._attr_kind_m = np.pad(self._attr_kind_m, pad)
        self._attr_val_m = np.pad(self._attr_val_m, pad)
        self._attr_cols = cols
        self.attr_kind = jnp.array(self._attr_kind_m)
        self.attr_val = jnp.array(self._attr_val_m)

    def attr_value_id(self, value: str) -> int:
        """Interned id for a string attribute value (shared by node rows and
        selector operands — string equality becomes id equality). Freed ids
        (refcount-zero releases) are recycled before the counter grows."""
        vid = self.attr_val_ids.get(value)
        if vid is None:
            if self._attr_val_free:
                vid = self._attr_val_free.pop()
            else:
                vid = self._attr_val_next
                self._attr_val_next += 1
            self.attr_val_ids[value] = vid
        return vid

    def _retain_attr_values(self, name: str, attrs: dict) -> None:
        """Refcount the STRING attribute values ``name`` publishes; a value
        no node publishes anymore frees its vocab id to the free-list.
        Rows re-encode per sync and selector rows rebuild per batch, so a
        recycled id can never be read through a stale compiled artifact."""
        from ..api import dra as dra_api

        new = set()
        for raw in attrs.values():
            kind, val = dra_api.attr_kind_val(raw)
            if kind == dra_api.KIND_STR:
                new.add(val)
        new = frozenset(new)
        old = self._node_attr_values.get(name, frozenset())
        if new == old:
            return
        for v in new - old:
            self._attr_val_refs[v] = self._attr_val_refs.get(v, 0) + 1
        for v in old - new:
            left = self._attr_val_refs.get(v, 0) - 1
            if left > 0:
                self._attr_val_refs[v] = left
                continue
            self._attr_val_refs.pop(v, None)
            vid = self.attr_val_ids.pop(v, None)
            if vid is not None:
                self._attr_val_free.append(vid)
        if new:
            self._node_attr_values[name] = new
        else:
            self._node_attr_values.pop(name, None)

    def _track_attrs(self, name: str, ni: Optional[NodeInfo], slot: int,
                     pending: Dict[int, dict]) -> None:
        """Record a dirty node's published attribute map for upload (called
        from sync's dirty walk — attribute changes always ride a node-object
        change, so the generation probe covers them)."""
        node = ni.node if ni is not None else None
        attrs = (dict(getattr(node.status, "device_attributes", None) or {})
                 if node is not None else {})
        if self._node_attrs.get(name, {}) == attrs:
            return
        # refcounted value retention BEFORE the row encodes: a value whose
        # last publisher just left frees its id here, so the encode below
        # can already recycle it for this sync's newcomers
        self._retain_attr_values(name, attrs)
        if attrs:
            self._node_attrs[name] = attrs
        else:
            self._node_attrs.pop(name, None)
        for key in attrs:
            self.attr_slot(key)  # register first: rows encode after growth
        pending[slot] = attrs

    def _upload_attrs(self, pending: Dict[int, dict]) -> None:
        if not pending:
            return
        from ..api import dra as dra_api

        for slot, attrs in pending.items():
            krow = np.zeros(self._attr_cols, np.int32)
            vrow = np.zeros(self._attr_cols, np.int32)
            for key, raw in attrs.items():
                kind, val = dra_api.attr_kind_val(raw)
                if kind == dra_api.KIND_ABSENT:
                    continue
                col = self.attr_slot(key)
                krow[col] = kind
                vrow[col] = val if kind == dra_api.KIND_INT else self.attr_value_id(val)
            self._attr_kind_m[slot] = krow
            self._attr_val_m[slot] = vrow
        # full re-upload, not a scatter: attribute maps change only with
        # node-object churn (rare), and [N, A] int32 is small next to the
        # row tensors — not worth a third scatter program
        self.attr_kind = jnp.array(self._attr_kind_m)
        self.attr_val = jnp.array(self._attr_val_m)

    # ------------------------------------------------- namespace quota table

    def _grow_nsq_rows(self) -> None:
        from ..ops.quota import QUOTA_NO_LIMIT

        rows = self._nsq_rows * 2
        grow = rows - self._nsq_rows
        self._nsq_used_m = np.pad(self._nsq_used_m, ((0, grow), (0, 0)))
        self._nsq_limit_m = np.concatenate([
            self._nsq_limit_m,
            np.full((grow, self._nsq_limit_m.shape[1]), QUOTA_NO_LIMIT,
                    np.int32)])
        self._nsq_rows = rows

    def set_ns_quota(self, table: Dict[str, Tuple]) -> bool:
        """Sync the namespace-quota tensor pair from a host ledger view
        (ns -> (used row, limit row) in ops/quota.QUOTA_DIM_ORDER ints).
        Content-diffed against the host mirror, so a steady-state table
        uploads nothing; returns whether a re-upload happened. ``table`` is
        the COMPLETE desired state: a registered namespace absent from it
        (quota deleted) resets to never-flags rows — a stale screening row
        for an unquota'd namespace would otherwise reject-and-requeue the
        same pod forever (the gate re-admits what the screen re-flags)."""
        from ..ops.quota import QUOTA_NO_LIMIT

        cap = int(QUOTA_NO_LIMIT)
        dirty = False
        for ns in self.nsq_slots:
            if ns not in table:
                slot = self.nsq_slots[ns]
                if (self._nsq_used_m[slot].any()
                        or (self._nsq_limit_m[slot] != cap).any()):
                    self._nsq_used_m[slot] = 0
                    self._nsq_limit_m[slot] = cap
                    dirty = True
        for ns, (used_row, limit_row) in table.items():
            slot = self.nsq_slots.get(ns)
            if slot is None:
                slot = len(self.nsq_slots)
                self.nsq_slots[ns] = slot
                while slot >= self._nsq_rows:
                    self._grow_nsq_rows()
                dirty = True
            u = np.clip(np.asarray(used_row, np.int64), 0, cap).astype(np.int32)
            lim = np.clip(np.asarray(limit_row, np.int64), 0, cap).astype(np.int32)
            if not np.array_equal(self._nsq_used_m[slot], u):
                self._nsq_used_m[slot] = u
                dirty = True
            if not np.array_equal(self._nsq_limit_m[slot], lim):
                self._nsq_limit_m[slot] = lim
                dirty = True
        if dirty:
            # full re-upload, not a scatter: [NS, Q] int32 is tiny next to
            # the row tensors (the attribute-table treatment)
            self.nsq_used = jnp.array(self._nsq_used_m)
            self.nsq_limit = jnp.array(self._nsq_limit_m)
            self.nsq_uploads += 1
            self.upload_bytes += (self._nsq_used_m.nbytes
                                  + self._nsq_limit_m.nbytes)
        return dirty

    # ------------------------------------------------------------------ sync

    def _refresh_class_prio(self) -> None:
        """Upload the priority-class vocab whenever it grew — independent of
        row changes (class_req content usually reaches the device via batch
        ADOPTION, so row uploads may be elided forever while the vocab
        array would stay stale at INT_MAX = nothing-evictable)."""
        if self._n_prio != len(self.encoder.prio_vocab):
            import dataclasses as _dc

            self._n_prio = len(self.encoder.prio_vocab)
            self.nt = _dc.replace(
                self.nt, class_prio=jnp.asarray(self.encoder.class_prio_array()))

    def sync(self, snapshot: Snapshot) -> int:
        """Upload rows for nodes whose generation advanced; returns number of
        rows uploaded. Raises CapacityError when the cluster outgrows caps."""
        self._refresh_class_prio()
        self.last_upload_bytes = 0
        dirty: List[Tuple[int, NodeInfo]] = []
        images_changed = False
        attr_pending: Dict[int, dict] = {}
        from . import telemetry

        # removed nodes FIRST: tombstone their rows (zeroed on device, slot
        # to the free-list, vocab retentions dropped), so a node added in
        # the SAME sync reuses the freed slot immediately instead of
        # growing the axis for one generation. Membership comes from the
        # ENCODER's slot map, not _uploaded_gen — commit-repair paths pop a
        # node's gen to force re-upload, and a node deleted in that window
        # would otherwise leak its slot (and stale mirror row) forever.
        current = snapshot.node_info_map
        removed = [n for n in self.encoder.node_slots if n not in current]
        for name in removed:
            self._uploaded_gen.pop(name, None)
            self._mirror_node.pop(name, None)
            slot = self.encoder.release_node_slot(name)
            self.nodes_removed += 1
            telemetry.event("node_remove", node=name,
                            slot=slot if slot is not None else -1)
            if slot is not None:
                dirty.append((slot, NodeInfo()))  # empty row: valid=False
                self.sig_table.recount_node(slot, None)
                self._track_attrs(name, None, slot, attr_pending)
            else:
                self._node_attrs.pop(name, None)
            images_changed |= self._track_images(name, None)
        for name, ni in current.items():
            if self._uploaded_gen.get(name) == ni.generation:
                continue
            reuses0 = self.encoder.slot_reuses
            slot = self.encoder.node_slot(name)
            if self.encoder.slot_reuses != reuses0:
                # a tombstoned row was handed to this node: the free-list
                # kept row capacity bounded instead of growing the axis
                telemetry.event("slot_reclaim", node=name, slot=slot)
            dirty.append((slot, ni))
            self._uploaded_gen[name] = ni.generation
            images_changed |= self._track_images(name, ni)
            self._track_attrs(name, ni, slot, attr_pending)
            if ni.node is not self._mirror_node.get(name):
                # labels/taints can only change with the Node OBJECT; rows
                # dirtied by commits alone skip the retention re-diff (the
                # same identity gate the static-row cache rides)
                self.encoder.retain_node_values(name, ni.node)
            self.sig_table.recount_node(slot, ni)
        if removed and dirty:
            # a slot tombstoned AND re-assigned within this sync appears
            # twice in the worklist; the scatter must see only the LAST
            # write per slot (duplicate indices in .at[].set are undefined)
            dirty = list({slot: (slot, ni) for slot, ni in dirty}.values())
        # device-attribute table upload happens even when every row upload
        # below gets content-elided (attrs live outside the row mirror)
        self._upload_attrs(attr_pending)

        # the full walk leaves every gen aligned: reset the O(changes) probes.
        # Duck-typed snapshots (wire service, test shims) may lack the
        # bookkeeping fields; they always take the full-walk paths.
        self._seen_struct = getattr(snapshot, "structure_version", -1)
        self._recon_pending.clear()
        getattr(snapshot, "changed_names", set()).clear()

        if not dirty:
            return 0
        # content-diff against the mirror: a row whose re-encoded content
        # already matches the device (e.g. its only change was an adopted
        # batch commit) needs no upload
        changed: List[Tuple[int, dict]] = []
        for slot, ni in dirty:
            row = self.encoder.encode_node_row(ni)
            if ni.node is not None:
                self._mirror_node[ni.node.meta.name] = ni.node
            if all(
                np.array_equal(np.asarray(row[f], dtype), self._mirror[f][slot])
                for f, dtype in _ROW_FIELDS
            ):
                self.rows_elided += 1
                continue
            for f, dtype in _ROW_FIELDS:
                self._mirror[f][slot] = np.asarray(row[f], dtype)
            changed.append((slot, row))
        if not changed and not images_changed:
            return 0
        if not changed:
            # vocab-level image arrays changed but no rows did: reuse slot 0
            changed = [(0, {f: self._mirror[f][0] for f, _ in _ROW_FIELDS})]
        # bucket-pad the row count to a power of two so the fused scatter
        # compiles once per bucket; padding repeats row 0 (idempotent set)
        n = len(changed)
        b = _bucket(n)
        slots = np.empty(b, np.int32)
        slots[:n] = [s for s, _ in changed]
        slots[n:] = slots[0]
        rows = [r for _, r in changed]
        updates = {}
        for field, dtype in _ROW_FIELDS:
            stacked = np.empty((b,) + np.shape(rows[0][field]), dtype)
            stacked[:n] = np.stack([r[field] for r in rows]).astype(dtype)
            stacked[n:] = stacked[0]
            updates[field] = stacked
        nt = self.nt
        if images_changed:
            sizes = np.zeros(self.caps.images, np.int32)
            counts = np.zeros(self.caps.images, np.int32)
            for img, cnt in self._image_counts.items():
                iid = self.encoder.image_id(img)
                counts[iid] = cnt
                sizes[iid] = min(self._image_sizes.get(img, 0), 2**31 - 1)
            image_sizes = jnp.asarray(sizes)
            image_num_nodes = jnp.asarray(counts)
        else:
            image_sizes = nt.image_sizes
            image_num_nodes = nt.image_num_nodes
        with telemetry.dispatch("apply_rows", bucket=str(b)):
            dev_slots = jnp.asarray(slots)
            self.nt = _apply_rows_jit(nt, dev_slots, updates,
                                      image_sizes, image_num_nodes)
        telemetry.cost_probe("apply_rows", str(b), _apply_rows_jit,
                             (nt, dev_slots, updates, image_sizes,
                              image_num_nodes))
        self.syncs += 1
        self.rows_uploaded += n
        nbytes = sum(arr.nbytes for arr in updates.values()) + slots.nbytes
        self.last_upload_bytes = int(nbytes)
        self.upload_bytes += int(nbytes)
        telemetry.transfer("upload", nbytes)
        return n

    def reconcile(self, snapshot: Snapshot) -> int:
        """Elide-only sync for the pipelined steady state: refresh
        ``_uploaded_gen`` for dirty rows whose re-encoded content already
        equals the mirror — i.e. rows whose only change was an adopted batch
        commit. Rows that would need a REAL upload are left dirty on
        purpose: at reconcile time the device may already carry the NEXT
        dispatched batch's adopted state, and scattering host rows into it
        would erase in-flight commits (device/host divergence the content
        diff then elides forever). Leaving them dirty makes the next
        ``has_dirty`` probe break the carry chain, and the safe drain+sync
        path repairs everything. Returns the number of rows left dirty."""
        self._refresh_class_prio()
        left = 0
        mirror = self._mirror
        req_m, nz_m = mirror["requested"], mirror["nonzero_requested"]
        ports_m, creq_m = mirror["port_bits"], mirror["class_req"]
        if getattr(snapshot, "structure_version", None) == self._seen_struct:
            # membership/zones unchanged since the last full walk: only the
            # names update_snapshot re-cloned (plus rows we previously left
            # dirty) can be gen-stale — O(changes), not O(nodes)
            names = snapshot.changed_names | self._recon_pending
            items = [(n, snapshot.node_info_map[n]) for n in names
                     if n in snapshot.node_info_map]
            check_removals = False
        else:
            items = list(snapshot.node_info_map.items())
            check_removals = True
        pending = set()
        for name, ni in items:
            if self._uploaded_gen.get(name) == ni.generation:
                continue
            if name not in self._uploaded_gen:
                left += 1  # new node: needs a real upload
                pending.add(name)
                continue
            if ni.node is not self._mirror_node.get(name):
                left += 1  # node OBJECT replaced: static fields may differ
                pending.add(name)
                continue
            if self._node_images.get(name, frozenset()) != frozenset(ni.image_states):
                left += 1  # image vocab change: needs a real upload
                pending.add(name)
                continue
            slot = self.encoder.node_slots.get(name)
            if slot is None:
                left += 1
                pending.add(name)
                continue
            try:
                # static fields are pinned by the identity check above; only
                # the pod-commit-dynamic fields can have moved
                row = self.encoder.encode_dynamic_fields(ni)
            except CapacityError:
                left += 1
                pending.add(name)
                continue
            if (np.array_equal(row["requested"], req_m[slot])
                    and np.array_equal(row["nonzero_requested"], nz_m[slot])
                    and np.array_equal(row["port_bits"], ports_m[slot])
                    and np.array_equal(row["class_req"], creq_m[slot])):
                self._uploaded_gen[name] = ni.generation
                self.rows_elided += 1
                # per-row recount is the reconcile constant-factor hot spot
                # (O(sigs × pods-on-node) python per elided row); with no
                # sigs/terms registered the counts are all zero and only
                # the _slot_pods bookkeeping matters
                st = self.sig_table
                if st.n_sigs > 1 or st.n_terms > 1:
                    st.recount_node(slot, ni)
                else:
                    st.track_slot_pods(slot, ni)
            else:
                left += 1
                pending.add(name)
        if check_removals:
            removed = [n for n in self.encoder.node_slots
                       if n not in snapshot.node_info_map]
            left += len(removed)
            pending.update(removed)
            self._seen_struct = getattr(snapshot, "structure_version", -1)
        self._recon_pending = pending
        getattr(snapshot, "changed_names", set()).clear()
        return left

    def has_dirty(self, snapshot: Snapshot) -> bool:
        """Cheap generation-only probe: would sync() find any dirty or
        removed node? In the async pipeline, any dirtiness at dispatch time
        is by construction an EXTERNAL change (the in-flight batch's commits
        are not in the cache yet), which breaks the device-carry chain.
        O(changes): while the snapshot's structure version is the one this
        device last fully walked, only changed/pending names can be stale;
        a structure change conservatively reports dirty (the drain+sync it
        triggers realigns the version)."""
        if getattr(snapshot, "structure_version", None) != self._seen_struct:
            return True
        for name in snapshot.changed_names | self._recon_pending:
            ni = snapshot.node_info_map.get(name)
            if ni is None or self._uploaded_gen.get(name) != ni.generation:
                return True
        return False

    def adopt_device(self, result) -> None:
        """Adopt the batch program's evolved dynamic state as the new device
        truth. The arrays may still be unmaterialized futures — this never
        blocks, which is what lets the pipeline dispatch the next batch while
        the host commits this one."""
        import dataclasses as _dc

        self.topo_carry = (None if result.final_sel_counts is None else
                           (result.final_sel_counts, result.final_seg_exist))
        if result.final_requested is None:
            return
        updates = dict(
            requested=result.final_requested,
            nonzero_requested=result.final_nonzero,
            port_bits=result.final_ports,
        )
        if result.final_class_req is not None:
            updates["class_req"] = result.final_class_req
        self.nt = _dc.replace(self.nt, **updates)

    def adopt_commits(self, result, host_pb: dict, node_idx: np.ndarray) -> None:
        """Advance the host mirror by the batch's per-slot adds, so the next
        sync's content diff elides every row whose only change was this
        batch's commits (the delta-upload saving of returning the carry).

        ``host_pb`` is the encoder's host-side copy of the pod batch
        (ClusterEncoder.last_host_pb) — reading the device PodBatch back
        would cost a blocking device read per array. Runs at COMMIT time (the
        mirror only matters before the next sync, which a drain precedes);
        adopt_device runs at dispatch time and never blocks."""
        if result.final_requested is None:
            return
        req = host_pb["req"]
        nz = host_pb["nonzero_req"]
        port_ids = host_pb["port_ids"]
        # mirror only what the device evolved: the pallas path returns no
        # final_class_req, so the device class table is refreshed by row
        # upload instead of adoption there
        prio_class = host_pb.get("prio_class") if result.final_class_req is not None else None
        for i, slot in enumerate(node_idx):
            if slot < 0:
                continue
            self._mirror["requested"][slot] += req[i]
            self._mirror["nonzero_requested"][slot] += nz[i]
            if prio_class is not None:
                self._mirror["class_req"][slot, prio_class[i]] += req[i]
            for pid in port_ids[i]:
                if pid > 0:
                    self._mirror["port_bits"][slot, pid >> 5] |= np.uint32(1) << np.uint32(pid & 31)

    def release(self, removals) -> Tuple[int, int]:
        """Put deleted pods' requests back on the carry: one scatter that
        subtracts each pod's request rows from the device's requested,
        nonzero_requested and class_req (and its signature counts from the
        topology carry), and the same from the host mirror, so the next
        sync or reconcile finds the row equal to the cache's. Never blocks:
        the device arrays may still be futures.

        ``removals``: (pod, cache.Removal) pairs. A pod is subtracted only
        where this row is known to hold it: the row was last uploaded or
        reconciled at a generation in [added_gen, removed_gen). A row
        uploaded after the removal no longer holds the pod, and the pod is
        skipped. A row confirmed before the add may or may not hold it (a
        dispatch adopts its own placements onto the carry before their
        commit is reconciled), so it is inexact. Returns (pods subtracted,
        rows that cannot be subtracted exactly — their uploaded generation
        is dropped, so the next sync repairs them)."""
        names: List[str] = []
        slots: List[int] = []
        rows: List[Tuple[np.ndarray, np.ndarray]] = []
        classes: List[int] = []
        pods: List = []
        inexact = 0
        for pod, rm in removals:
            slot = self.encoder.node_slots.get(rm.node)
            up = self._uploaded_gen.get(rm.node)
            if slot is None or up is None or up >= rm.removed_gen:
                continue
            row = None
            if up >= rm.added_gen:
                try:
                    row = self.encoder.pod_request_rows(pod)
                    cid = self.encoder.prio_class_id(pod.spec.priority)
                except CapacityError:
                    row = None
            if row is None:
                self._uploaded_gen.pop(rm.node, None)
                inexact += 1
                continue
            names.append(rm.node)
            slots.append(slot)
            rows.append(row)
            classes.append(cid)
            pods.append(pod)
        if not slots:
            return 0, inexact
        mirror = self._mirror
        slot_arr = np.asarray(slots, np.int32)
        req = np.stack([r for r, _ in rows])
        nz = np.stack([z for _, z in rows])
        cls = np.asarray(classes, np.int32)
        bad = ((req >= _CLAMP).any(1) | (nz >= _CLAMP).any(1)
               | (mirror["requested"][slot_arr] >= _CLAMP).any(1)
               | (mirror["nonzero_requested"][slot_arr] >= _CLAMP).any(1))
        if bad.any():
            for i in np.nonzero(bad)[0]:
                self._uploaded_gen.pop(names[i], None)
            inexact += int(bad.sum())
            keep = ~bad
            slot_arr, req, nz, cls = slot_arr[keep], req[keep], nz[keep], cls[keep]
            pods = [p for p, k in zip(pods, keep) if k]
            if not pods:
                return 0, inexact
        np.subtract.at(mirror["requested"], slot_arr, req)
        np.subtract.at(mirror["nonzero_requested"], slot_arr, nz)
        np.subtract.at(mirror["class_req"], (slot_arr, cls), req)
        n = len(pods)
        b = _bucket(n)
        pad = b - n
        slot_arr = np.pad(slot_arr, (0, pad))
        req = np.pad(req, ((0, pad), (0, 0)))
        nz = np.pad(nz, ((0, pad), (0, 0)))
        cls = np.pad(cls, (0, pad))
        sel = sig = None
        if self.topo_carry is not None and self.sig_table.n_sigs > 1:
            sig = np.zeros((b, self.caps.sigs), np.int32)
            for i, pod in enumerate(pods):
                sig[i] = self.sig_table.pod_sig_mask(pod)
            if sig.any():
                sel = self.topo_carry[0]
            else:
                sig = None
        nt = self.nt
        args = (nt.requested, nt.nonzero_requested, nt.class_req, sel,
                slot_arr, req, nz, cls, sig)
        from . import telemetry

        with telemetry.dispatch("release_rows", bucket=str(b)):
            requested, nonzero, class_req, sel_out = _release_rows_jit(*args)
        telemetry.cost_probe("release_rows", str(b), _release_rows_jit, args)
        import dataclasses as _dc

        self.nt = _dc.replace(nt, requested=requested,
                              nonzero_requested=nonzero, class_req=class_req)
        if sel_out is not None:
            self.topo_carry = (sel_out, self.topo_carry[1])
        return n, inexact

    def warm_release(self, max_pods: int) -> None:
        """Compile the release scatter at every bucket up to ``max_pods``
        released at once (and its topology-carry variant while signatures
        are registered): zero rows at slot 0, results dropped."""
        nt = self.nt
        sels = [None]
        if self.sig_table.n_sigs > 1:
            sels.append(self.tc.sel_counts)
        b = _bucket(1)
        while True:
            for sel in sels:
                sig = (None if sel is None
                       else np.zeros((b, self.caps.sigs), np.int32))
                out = _release_rows_jit(
                    nt.requested, nt.nonzero_requested, nt.class_req, sel,
                    np.zeros(b, np.int32),
                    np.zeros((b, self.caps.resources), np.int32),
                    np.zeros((b, self.caps.resources), np.int32),
                    np.zeros(b, np.int32), sig)
                np.asarray(out[0])  # land the compile
            if b >= max_pods:
                return
            b *= 2

    def _track_images(self, name: str, ni: Optional[NodeInfo]) -> bool:
        """Maintain global image num-node counts (first-seen size wins,
        mirroring cache.addNodeImageStates). Returns True if vocab changed."""
        old = self._node_images.get(name, frozenset())
        new = frozenset(ni.image_states) if ni is not None else frozenset()
        if old == new:
            return False
        for img in new - old:
            self._image_counts[img] = self._image_counts.get(img, 0) + 1
            if img not in self._image_sizes and ni is not None:
                self._image_sizes[img] = ni.image_states[img]
        for img in old - new:
            c = self._image_counts.get(img, 0) - 1
            if c <= 0:
                self._image_counts.pop(img, None)
                self._image_sizes.pop(img, None)
                # no node reports it anymore: free the vocab id so image
                # churn cannot grow the image axis monotonically
                self.encoder.release_image(img)
            else:
                self._image_counts[img] = c
        if new:
            self._node_images[name] = new
        else:
            self._node_images.pop(name, None)
        return True

    def slot_to_name(self) -> Dict[int, str]:
        """LIVE reverse map (maintained by the encoder) — rebuilding a
        5k-entry dict per commit was a fixed ~2ms/batch. Callers read only;
        anyone needing a stable copy must dict() it."""
        return self.encoder.slot_names


def caps_for_cluster(n_nodes: int, batch: int = 128) -> Capacities:
    """Pick static capacities for a cluster size (node-count buckets 1k/5k/...;
    hostname value vocab must cover every node)."""
    from ..ops.schema import round_node_capacity

    nodes = round_node_capacity(n_nodes)
    value_words = max(32, (nodes + 2 + 31) // 32)  # hostname vocab ≥ node count
    # synthetic torus fallback assigns sp = slot // sp_slots: the superpod
    # axis must cover every slot or the first sync of a large cluster spins
    # through CapacityError growth
    superpods = max(16, (nodes + 15) // 16)
    return Capacities(nodes=nodes, pods=batch, value_words=value_words,
                      superpods=superpods)
