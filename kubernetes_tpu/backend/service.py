"""The batched device service seam (SURVEY §5.8 hop 6).

The reference's only out-of-process scheduling extension is the per-pod JSON
extender webhook (extender.go:42,247) — one HTTP POST per pod per extender,
which is exactly its performance failure. This service batches and adds
state: the control plane streams generation-keyed node deltas
(``ApplyDeltas``) and submits whole pod micro-batches (``ScheduleBatch``);
the device side keeps the encoded mirror across calls, so steady-state
requests carry only dirty rows and the pod batch.

Three pieces:
  * ``DeviceService`` — transport-agnostic server core owning a DeviceState
    and the compiled batch program. Its device half is TPUScheduler's own:
    both run the shared step in backend/device_step.py (capacity growth,
    the program's arguments, the dispatch, the preemption screen); the
    service adds the session/hold bookkeeping around it.
  * ``serve``/``DeviceServiceHTTP`` — stdlib HTTP/JSON binding on localhost
    (the in-process path stays the fast mode; this seam exists to measure
    and bound the serialization/transport cost the reference pays at
    QPS-5000, scheduler_perf util.go:86-90).
  * ``WireScheduler`` — a Scheduler whose filter/score middle goes over the
    wire; queue/cache/assume/bind/failure handling stay the same host
    machinery (the north-star seam: the control plane does not know whether
    the backend is in-process or remote).

HA topology (ISSUE 6): N WireScheduler replicas share ONE DeviceService.
Requests carry a ``clientId``/``sessionGen``; the service keeps per-client
sessions with leases, overlays adopted-but-unconfirmed placements as holds,
validates every placement at commit time (typed ``conflict`` verdicts on
cross-client races), and fences dead clients so survivors adopt the freed
capacity. See README "HA topology".

Wire envelope: {"apiVersion": "ktpu/v1", ...}; objects use api/codec.py.
"""

from __future__ import annotations

import http.client
import itertools
import json
import os
import socket
import threading
import time
import urllib.parse
from collections import OrderedDict, deque
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from types import SimpleNamespace
from typing import Deque, Dict, List, Optional

import numpy as np

from ..api.codec import from_wire, to_wire
from ..api.types import Node, Pod
from ..framework.types import Diagnosis, NodeInfo, QueuedPodInfo
from ..framework.interface import Status
from ..metrics import latency_ledger
from ..ops.encode import CapacityError
from ..scheduler.scheduler import Scheduler
from ..testing import locktrace
from ..utils import heap, tracing
from . import telemetry, wire
from . import device_step
from .batch import ATTRIBUTION_ORDER, bucket_sig, build_schedule_batch_fn
from .circuit import CircuitBreaker, OPEN, STATE_VALUES
from .device_state import DeviceState
from .errors import (
    ConflictError,
    DeviceServiceError,
    PermanentDeviceError,
    RetryPolicy,
    StaleEpochError,
    TransientDeviceError,
    raise_injected_fault,
)

API_VERSION = "ktpu/v1"

# session lease: a scheduler replica that stops heartbeating for this long
# is declared dead and FENCED — its uncommitted capacity is released for the
# survivors and any late request from the dead incarnation gets a Conflict
# (the fencing-token rule: a fenced writer can never commit)
DEFAULT_LEASE_TTL_S = 15.0

# process-epoch minting: unique per DeviceService INSTANCE (a restarted
# sidecar is a new instance holding a fresh empty DeviceState; the epoch is
# how the client tells a restart from a healthy peer — etcd's cluster-id /
# member-id check on reconnect plays the same role)
_EPOCH_IDS = itertools.count(1)


def _new_epoch() -> str:
    return f"{os.getpid():x}-{next(_EPOCH_IDS)}"


class ClientSession:
    """Per-client sync state (the server half of what used to be the single
    unnamed client's ``_sent_gens``): which node generations THIS client has
    pushed, its delta sequence, its idempotency cache, and its lease. A
    fresh/rejoining client resets only its own slice — other clients' state
    is untouched."""

    # idempotency-cache depth: a PIPELINED client keeps up to K batches in
    # flight, so a transport retry can be for any of its last K logical
    # batches, not just the newest (the single-entry cache of the strictly
    # request/response era). Bounded well above any sane pipeline depth —
    # a retry falling off this cache is re-COMPUTED, which the ownership
    # check resolves as the owner re-deciding its own holds (no double
    # bind), but the replayed-result fast path is lost.
    IDEMPOTENCY_DEPTH = 32

    __slots__ = ("client_id", "gen", "created_at", "last_seen", "delta_seq",
                 "sent_gens", "last_batches", "batch_replays", "batches",
                 "fenced", "fenced_seq", "fence_seq_seen", "released_holds",
                 "replicator", "last_push_seq")

    def __init__(self, client_id: str, gen: int, now: float):
        self.client_id = client_id
        # warm-standby replication session (DeviceFabric): its node claims
        # keep the warm DeviceState alive across the promote-time full
        # resync, but never block another client's ghost sweep — the
        # replicator mirrors a PAST truth; the resyncing client IS truth
        self.replicator = False
        # service delta_seq at this session's last applied push: a
        # replicator "lapped" by a direct client's full resync (the resync
        # happened after the replicator's last contact) must reseed — its
        # next push could re-create nodes the resync swept
        self.last_push_seq = 0
        self.gen = gen                      # session incarnation (rejoin bumps)
        self.created_at = now
        self.last_seen = now                # lease heartbeat clock
        self.delta_seq = 0
        self.sent_gens: Dict[str, int] = {}  # node -> last gen this client pushed
        # batchId -> response, insertion-ordered, bounded (see above)
        self.last_batches: "OrderedDict[str, dict]" = OrderedDict()
        self.batch_replays = 0
        self.batches = 0
        self.fenced = False
        self.fenced_seq = 0                 # fence-log seq of OUR fencing
        self.fence_seq_seen = 0             # fence-log cursor for heartbeats
        self.released_holds = 0

    @property
    def last_batch(self) -> Optional[tuple]:
        """(batchId, response) of the NEWEST cached batch (None when the
        cache is empty/poisoned) — the single-entry era's introspection
        surface, kept for the fence tests and /debug/sessions."""
        if not self.last_batches:
            return None
        bid = next(reversed(self.last_batches))
        return (bid, self.last_batches[bid])

    def cache_batch(self, batch_id: str, response: dict) -> None:
        self.last_batches[batch_id] = response
        while len(self.last_batches) > self.IDEMPOTENCY_DEPTH:
            self.last_batches.popitem(last=False)


class _Hold:
    """One adopted-but-unconfirmed placement: the device committed the pod
    for ``owner``, but no client's host truth includes it yet. While held,
    every delta for the node re-overlays the pod so another replica's
    (lagging) push can never erase the capacity and hand it out twice.
    ``batch_id`` names the batch that created it: a PIPELINED owner's delta
    push may predate its processing of that batch's reply, so omission from
    the owner's content releases the hold only once the owner no longer
    lists the batch as in flight."""

    __slots__ = ("pod", "node_name", "owner", "seen", "batch_id")

    def __init__(self, pod: Pod, node_name: str, owner: str,
                 batch_id: Optional[str] = None):
        self.pod = pod
        self.node_name = node_name
        self.owner = owner
        self.seen: set = set()  # client ids whose pushed content included it
        self.batch_id = batch_id


class DeviceService:
    """Server core: node mirror + device state + one compiled batch program.

    Multi-tenant (active-active HA): any number of scheduler replicas share
    this one service. Every request may carry a ``clientId`` (+ the
    ``sessionGen`` the service answered with); the service keeps per-client
    sessions, overlays adopted-but-unconfirmed placements onto the shared
    mirror (``_Hold``), validates every placement at commit time against
    current ownership/occupancy (cross-client races get a typed ``conflict``
    verdict, never a double-bind), and fences clients whose lease expires —
    releasing their uncommitted capacity to the survivors."""

    def __init__(self, batch_size: int = 512,
                 percentage_of_nodes_to_score: int = 0,
                 lease_ttl_s: float = DEFAULT_LEASE_TTL_S,
                 now_fn=time.monotonic):
        self.batch_size = batch_size
        self.percentage_of_nodes_to_score = percentage_of_nodes_to_score
        self.lease_ttl_s = lease_ttl_s
        self.now_fn = now_fn
        # state-resync protocol: the epoch names THIS process incarnation;
        # delta_seq counts applied delta generations within it. A client
        # whose expectEpoch disagrees gets a stale-state error instead of
        # silently having its deltas applied against the wrong (empty) base.
        self.epoch = _new_epoch()
        self.delta_seq = 0
        # per-client sessions (idempotency caches live inside — one entry
        # per client suffices: each client is sequential and only ever
        # retries its most recent batch). batch_replays stays as the
        # aggregate counter the single-client tests read.
        self.sessions: Dict[str, ClientSession] = {}
        self._session_gens = itertools.count(1)
        self.batch_replays = 0
        # adopted-but-unconfirmed placements: pod key -> _Hold
        self.holds: Dict[str, _Hold] = {}
        # pod key -> node for pods present in pushed CONTENT (host truth):
        # the ownership check's "already bound" index
        self._pod_nodes: Dict[str, str] = {}
        self._node_pod_keys: Dict[str, set] = {}
        # delta_seq of the most recent DIRECT (non-replicator) full
        # resync: the lap marker for replicator sessions (see
        # ClientSession.last_push_seq)
        self._last_direct_full_seq = 0
        # fence log: (seq, client_id) — heartbeat responses tell survivors
        # which peers were fenced since their last beat
        self._fences: List[tuple] = []
        self._fence_seq = 0
        self.takeovers = 0
        self.commit_conflicts = 0
        self.infos: Dict[str, NodeInfo] = {}
        # duck-typed Snapshot: the wire service mirrors nodes wholesale per
        # delta, so every sync is a "structure changed" full walk — the
        # changed_names/structure_version fields exist only to satisfy
        # DeviceState's O(changes) bookkeeping (a fresh version each sync
        # forces the full path, which is correct here)
        self.snap = SimpleNamespace(node_info_map=self.infos,
                                    changed_names=set(), structure_version=0)
        self.ns_labels: Dict[str, Dict[str, str]] = {}
        # ns -> (used row, limit row): the client's quota-ledger export for
        # the device over-quota screen, replaced whole by each delta
        # payload that carries a quotaTable (it is tiny, so the client
        # ships the complete desired state whenever it changes)
        self.quota_table: Dict[str, tuple] = {}
        self.device: Optional[DeviceState] = None
        self.schedule_batch_fn = build_schedule_batch_fn()
        self.batch_counter = 0
        self._start_carry = None  # adaptive-sampling rotation (device scalar)
        self._lock = locktrace.make_lock("DeviceService")

    # ------------------------------------------------------------- epoch

    def check_epoch(self, req: dict) -> None:
        """Refuse a request stamped with another incarnation's epoch: the
        client's incremental deltas assume a base THIS process never had.
        A full resync (``full: true``) establishes a new base, so it is
        exempt — it is exactly the recovery move the error demands."""
        expect = req.get("expectEpoch")
        if expect and expect != self.epoch and not req.get("full"):
            raise StaleEpochError(self.epoch)

    def _stamp(self, out: dict) -> dict:  # ktpu: locked
        out["epoch"] = self.epoch
        out["deltaSeq"] = self.delta_seq
        return out

    # ------------------------------------------------------------ sessions

    def _live_sessions(self) -> List[ClientSession]:  # ktpu: locked
        return [s for s in self.sessions.values() if not s.fenced]

    def _session_for(self, req: dict) -> ClientSession:  # ktpu: locked
        """Resolve (creating/rejoining as needed) the request's session and
        touch its lease. Caller holds the lock. Raises ConflictError for a
        fenced incarnation: a dead-declared client must rejoin (fresh
        sessionGen + full resync), never silently keep committing."""
        now = self.now_fn()
        self._sweep_leases(now)
        cid = req.get("clientId") or ""
        gen = req.get("sessionGen")
        s = self.sessions.get(cid)
        if s is None or (s.fenced and gen is None):
            # first contact, or an explicit rejoin after a fence: a fresh
            # incarnation with its own generation and empty sync state.
            # History starts NOW — fences that predate this session are not
            # takeover news for it.
            s = ClientSession(cid, next(self._session_gens), now)
            s.fence_seq_seen = self._fence_seq
            self.sessions[cid] = s
        if s.fenced:
            raise ConflictError(
                f"client {cid!r} session {gen} was fenced (lease expired "
                f"after {self.lease_ttl_s}s); rejoin with a full resync")
        if gen is not None and gen != s.gen:
            # a zombie from a previous incarnation of the same clientId:
            # its view of its own holds is gone — it must not commit
            raise ConflictError(
                f"client {cid!r} session {gen} superseded by {s.gen}")
        if req.get("replicator"):
            s.replicator = True
        s.last_seen = now
        return s

    def _sweep_leases(self, now: float) -> None:  # ktpu: locked
        """Fence every named session whose lease expired. Anonymous
        (legacy, clientId-less) sessions never expire — they are the
        single-client demo topology and send no heartbeats."""
        for cid, s in list(self.sessions.items()):
            if not cid or s.fenced:
                continue
            if now - s.last_seen > self.lease_ttl_s:
                self._fence(s)

    def _fence(self, s: ClientSession) -> None:  # ktpu: locked
        """Declare a client dead: poison its idempotency cache server-side
        (a late transport retry of its last batch will NOT be replayed),
        and release its adopted-but-unconfirmed rows so a survivor adopts
        the freed capacity — the scheduler-death twin of PR 5's device
        poison-and-requeue."""
        last_batch_id = s.last_batch[0] if s.last_batch else None
        s.fenced = True
        s.last_batches.clear()  # poison: a zombie retry must never replay
        self._fence_seq += 1
        s.fenced_seq = self._fence_seq
        self._fences.append((self._fence_seq, s.client_id))
        self.takeovers += 1
        released_before = s.released_holds
        for key, hold in list(self.holds.items()):
            if hold.owner != s.client_id:
                continue
            # only never-confirmed capacity is released: a hold whose pod
            # is in the node's current pushed content — or was EVER seen in
            # any client's truth — is really bound; removing it would free
            # capacity a live pod still occupies and hand it out twice
            confirmed = (key in self._node_pod_keys.get(hold.node_name, ())
                         or hold.seen)
            if not confirmed:
                ni = self.infos.get(hold.node_name)
                if ni is not None:
                    ni.remove_pod(hold.pod)
                s.released_holds += 1
            del self.holds[key]
        telemetry.event("fence", client=s.client_id, epoch=self.epoch,
                        batchId=last_batch_id,
                        releasedHolds=s.released_holds - released_before)

    def _prune_fences(self) -> None:  # ktpu: locked
        """Bound the fence bookkeeping (lock held): default client ids are
        unique per scheduler process, so routine replica redeploys would
        otherwise accrete one dead ClientSession (O(nodes) sent_gens) and
        one fence-log entry FOREVER. Once every live session's heartbeat
        cursor has passed a fence, the log entry and the dead session are
        droppable — the fencing token lives in the session GENERATION (a
        zombie's stamped gen can never match a newly minted one), not in
        the fenced record."""
        live = [s for s in self.sessions.values()
                if not s.fenced and s.client_id]
        if not live:
            return
        horizon = min(s.fence_seq_seen for s in live)
        if self._fences and self._fences[0][0] <= horizon:
            self._fences = [(seq, cid) for seq, cid in self._fences
                            if seq > horizon]
        # dead session records stay inspectable (/debug/sessions) for a
        # grace window, then drop once every live peer has been told
        grace = 10.0 * self.lease_ttl_s
        now = self.now_fn()
        for cid, s in list(self.sessions.items()):
            if (s.fenced and s.fenced_seq <= horizon
                    and now - s.last_seen > grace):
                del self.sessions[cid]

    def heartbeat(self, req: dict) -> dict:
        """Lease renewal + takeover signal: touching the session IS the
        renewal; the response carries every peer fenced since this
        client's previous beat so a survivor can adopt the dead replica's
        queue slice (and count scheduler_ha_takeovers_total)."""
        with self._lock:
            s = self._session_for(req)
            fenced = [cid for seq, cid in self._fences
                      if seq > s.fence_seq_seen and cid != s.client_id]
            s.fence_seq_seen = self._fence_seq
            self._prune_fences()
            return self._stamp({
                "apiVersion": API_VERSION,
                "sessionGen": s.gen,
                "leaseTtlS": self.lease_ttl_s,
                "sessions": len(self._live_sessions()),
                "fenced": fenced,
            })

    def sessions_dump(self, req: Optional[dict] = None) -> dict:
        """/v1/sessions (the /debug/sessions body): per-client lease age,
        delta sequence, in-flight hold count, replay/fence counters."""
        with self._lock:
            now = self.now_fn()
            per_owner: Dict[str, int] = {}
            for hold in self.holds.values():
                per_owner[hold.owner] = per_owner.get(hold.owner, 0) + 1
            sessions = []
            for cid in sorted(self.sessions):
                s = self.sessions[cid]
                sessions.append({
                    "clientId": cid,
                    "sessionGen": s.gen,
                    "leaseAgeS": now - s.last_seen,
                    "leaseTtlS": self.lease_ttl_s if cid else None,
                    "deltaSeq": s.delta_seq,
                    "sentNodes": len(s.sent_gens),
                    "batches": s.batches,
                    "batchReplays": s.batch_replays,
                    "inflightHolds": per_owner.get(cid, 0),
                    "releasedHolds": s.released_holds,
                    "fenced": s.fenced,
                })
            return self._stamp({
                "apiVersion": API_VERSION,
                "enabled": True,
                "leaseTtlS": self.lease_ttl_s,
                "takeovers": self.takeovers,
                "commitConflicts": self.commit_conflicts,
                "holds": len(self.holds),
                "sessions": sessions,
            })

    # ------------------------------------------------------------- deltas

    def apply_deltas(self, req: dict) -> dict:
        self.check_epoch(req)
        # server half of W3C-traceparent propagation: the delta sync parents
        # under the client's scheduling.cycle span (no-op, one global read,
        # when tracing is disabled)
        with tracing.span_from_remote(req.get("traceparent"),
                                      "device.apply_deltas",
                                      nodes=len(req.get("nodes", ()))):
            return self._apply_deltas_traced(req)

    def _apply_deltas_traced(self, req: dict) -> dict:
        # decode OUTSIDE the lock: the wire payload is request-local and the
        # from_wire walk is O(nodes × pods) pure-CPU work — holding the
        # service lock across it starves peer replicas' heartbeats for no
        # consistency gain (found by the locktrace hold-time review)
        decoded = []
        for e in req.get("nodes", ()):
            node = from_wire(Node, e["node"])
            pods = [from_wire(Pod, pw) for pw in e.get("pods", ())]
            decoded.append((node, pods, e.get("gen")))
        # pipelined clients name the batches whose replies they have not
        # processed yet: holds created by those batches must survive
        # owner-content omission (the owner's truth CANNOT include them)
        inflight_ids = set(req.get("inflightBatchIds") or ())
        with self._lock:
            s = self._session_for(req)
            if s.replicator and self._last_direct_full_seq > s.last_push_seq:
                # LAPPED replicator: a direct client full-resynced this
                # service after the replicator's last contact (promote, or
                # a failback reseed window). Its pending push was built
                # against a pre-resync world and could re-CREATE nodes the
                # resync swept — refuse it and demand a fresh full seed
                # (the fabric's ConflictError handler reseeds). The cursor
                # advances so the reseed itself is accepted.
                s.last_push_seq = self.delta_seq
                raise ConflictError(
                    "replicator lapped by a direct full resync; reseed")
            full_set = 0
            if req.get("full"):
                # full resync replaces THIS client's contribution only. A
                # mirror node no other live session claims and the full set
                # omits is a ghost (a dead predecessor's world) — sweep it.
                # With a single session this degenerates to the old
                # clear-everything semantics. A full sync sent in parts
                # names its whole set in the first part (``fullNodes``):
                # the sweep runs once, here, and keeps the nodes the later
                # parts bring.
                s.sent_gens.clear()
                pushed = {node.meta.name for node, _, _ in decoded}
                pushed.update(req.get("fullNodes") or ())
                full_set = len(pushed)
                # the anonymous (legacy single-client) session never claims
                # nodes: it predates sessions, sends no heartbeats, and its
                # full pushes keep the old everything-or-nothing contract
                others = [o for o in self._live_sessions()
                          if o is not s and o.client_id]
                # a REPLICATOR session's claims never block the sweep: it
                # mirrors a past truth, and a node it alone still claims
                # after a scheduler client's full resync is exactly the
                # ghost the sweep exists to drop (the fabric's delta
                # stream repairs the replicator's view separately). It DOES
                # count for device retention below — dropping the warm
                # DeviceState at promote would throw the O(dirty) resync
                # away.
                claimers = [o for o in others if not o.replicator]
                if s.replicator:
                    # a replicator's full RESEED outranks direct claims
                    # older than its own previous contact (a healed
                    # ex-active's idle session would otherwise pin its
                    # stale tenure claims — and their ghost nodes —
                    # forever); claims refreshed by a newer direct push
                    # still win (the promote-resync case)
                    claimers = [o for o in claimers
                                if o.last_push_seq > s.last_push_seq]
                for name in list(self.infos):
                    if name in pushed:
                        continue
                    if any(name in o.sent_gens for o in claimers):
                        continue
                    self._drop_node(name)
                    for o in others:
                        o.sent_gens.pop(name, None)
                if not others:
                    self.ns_labels.clear()
                    self.quota_table.clear()
                    self.device = None
            live_ids = {o.client_id for o in self._live_sessions()}
            # a REPLICATOR mirrors a client's PAST pushes: if a direct
            # (non-replicator) session has already pushed a node at the
            # same or a newer generation, the replicator's entry is stale
            # — skip it. This closes the promote-time race where an
            # in-flight replication push lands AFTER the promoted
            # replica's full resync: the client's rows can never be
            # overwritten backward (worst case a skipped row stays for
            # the next delta to repair — extra upload bytes, never wrong
            # truth).
            direct = ([o for o in self._live_sessions()
                       if o is not s and not o.replicator and o.client_id]
                      if s.replicator else [])
            # ...but only direct sessions that pushed SINCE the
            # replicator's previous contact outrank the stream wholesale
            # (removals/sweeps below): a healed ex-active's idle session
            # keeps stale claims alive forever (its lease is deliberately
            # kept warm), and deferring to those would strand deleted
            # nodes in the standby mirror until the next promote.
            # s.last_push_seq still holds the PREVIOUS contact here — it
            # advances only after this push applies.
            direct_newer = [o for o in direct
                            if o.last_push_seq > s.last_push_seq]
            for node, pods, gen in decoded:
                name = node.meta.name
                if s.replicator and gen is not None and any(
                        o.sent_gens.get(name) is not None
                        and o.sent_gens[name] >= gen for o in direct):
                    continue
                ni = NodeInfo(node)
                content_keys = set()
                for pod in pods:
                    ni.add_pod(pod)
                    content_keys.add(pod.key())
                if gen is not None:
                    ni.generation = gen
                    s.sent_gens[name] = gen
                # hold reconciliation: the pusher's content is authoritative
                # for its OWN holds (assumed pods live in its cache, so an
                # omission means surrendered/forgotten/expired — release);
                # other owners' holds are re-overlaid until every live
                # client's truth has caught up (else a lagging replica's
                # push would erase capacity another replica just committed
                # and the next batch would hand it out twice)
                for key, hold in list(self.holds.items()):
                    if hold.node_name != name:
                        continue
                    if key in content_keys:
                        hold.seen.add(s.client_id)
                        if live_ids <= hold.seen:
                            del self.holds[key]  # durable in everyone's truth
                    elif (hold.owner == s.client_id
                          and not (hold.batch_id
                                   and hold.batch_id in inflight_ids)):
                        del self.holds[key]      # owner surrendered it
                    else:
                        # overlay: capacity stays taken — a peer's unconfirmed
                        # hold, or the pusher's OWN hold from a batch still in
                        # flight on its pipelined transport (its truth cannot
                        # include the placement before it processes the reply)
                        ni.add_pod(hold.pod)
                for key in self._node_pod_keys.get(name, ()):
                    # only drop index entries still pointing HERE: a pod
                    # deleted and re-bound elsewhere under the same key has
                    # a live entry for its new node that must survive this
                    # node's stale key list
                    if self._pod_nodes.get(key) == name:
                        del self._pod_nodes[key]
                self._node_pod_keys[name] = content_keys
                for key in content_keys:
                    self._pod_nodes[key] = name
                self.infos[name] = ni
            for name in req.get("removed", ()):
                if s.replicator and any(name in o.sent_gens
                                        for o in direct_newer):
                    # stale replicated removal: a direct client has pushed
                    # the node SINCE this replicator's previous contact —
                    # its truth wins
                    s.sent_gens.pop(name, None)
                    continue
                self._drop_node(name)
                s.sent_gens.pop(name, None)
            # namespace labels ride along so namespaceSelector terms match
            # identically to the in-process path (sig_table ns_labels_fn)
            for ns, labels in (req.get("namespaces") or {}).items():
                self.ns_labels[ns] = dict(labels)
            # the quota screen table rides the same channel: presence means
            # the client shipped its COMPLETE ledger view (absent namespaces
            # lost their quota — set_ns_quota resets their rows)
            qt = req.get("quotaTable")
            if qt is not None:
                self.quota_table = {
                    ns: (rows.get("used") or [], rows.get("limit") or [])
                    for ns, rows in qt.items()}
            # a full sync sizes a new device for its whole set, so one sent
            # in parts ends on the capacities one message would give
            self._sync(min_nodes=full_set)
            self.delta_seq += 1
            s.delta_seq += 1
            s.last_push_seq = self.delta_seq
            if req.get("full") and not s.replicator and s.client_id:
                self._last_direct_full_seq = self.delta_seq
            return self._stamp({"apiVersion": API_VERSION,
                                "nodes": len(self.infos),
                                "sessionGen": s.gen})

    def _drop_node(self, name: str) -> None:  # ktpu: locked
        """Remove a node and every index/hold anchored to it (lock held)."""
        self.infos.pop(name, None)
        for key in self._node_pod_keys.pop(name, ()):
            if self._pod_nodes.get(key) == name:  # see _apply_deltas_traced
                del self._pod_nodes[key]
        for key, hold in list(self.holds.items()):
            if hold.node_name == name:
                del self.holds[key]

    def _ns_labels(self, ns: str) -> Dict[str, str]:  # ktpu: locked
        # the mirror's ns_labels_fn: device.sync calls it, and the device
        # syncs only under the service lock
        return self.ns_labels.get(ns, {})

    def _ensure_device(self, min_nodes: int = 0) -> None:  # ktpu: locked
        self.device = device_step.fit_nodes(
            self.device, max(len(self.infos), min_nodes, 1),
            batch=self.batch_size, ns_labels_fn=self._ns_labels)

    def _grow(self, err: CapacityError) -> None:  # ktpu: locked
        self.device = device_step.grown(self.device, err,
                                        ns_labels_fn=self._ns_labels)

    def _sync(self, min_nodes: int = 0) -> None:  # ktpu: locked
        self._ensure_device(min_nodes)

        def sync():  # ktpu: locked
            # deliberate blocking-under-lock: the mirror the device syncs
            # from must not change until the batch that judged against it
            # commits — the commit-time validation contract
            locktrace.note_blocking(
                "device_sync", "DeviceService.sync",
                allowed="mirror must stay frozen from sync to commit")
            with tracing.span("device.sync"):
                self.device.sync(self.snap)

        if not device_step.retry_grown(sync, self._grow)[0]:
            # typed per the taxonomy: deterministic (the same delta
            # re-raises), so the client must never burn retry budget on it
            raise PermanentDeviceError("device capacities refuse to converge")

    # --------------------------------------------------------------- health
    def health(self, req: dict) -> dict:
        """Cheap liveness/identity verb: no device work, no epoch check (a
        stale client calling this LEARNS the current epoch — exactly what a
        half-open circuit probe needs instead of pushing a full batch
        through a maybe-dead service)."""
        with self._lock:
            return self._stamp({"apiVersion": API_VERSION,
                                "status": "serving",
                                "nodes": len(self.infos)})

    # ------------------------------------------------------------- schedule

    def schedule_batch(self, req: dict) -> dict:
        self.check_epoch(req)
        batch_id = req.get("batchId")
        session_req = {"clientId": req.get("clientId"),
                       "sessionGen": req.get("sessionGen")}
        with self._lock:
            s = self._session_for(session_req)
            if batch_id and batch_id in s.last_batches:
                # transport retry of a batch this session already committed
                # (with pipelining the retry can be for ANY of the last K
                # batches, not just the newest): replay the stored response
                s.batch_replays += 1
                self.batch_replays += 1
                return s.last_batches[batch_id]
        pods = [from_wire(Pod, pw) for pw in req.get("pods", ())]
        tie_seeds = req.get("tieSeeds") or None
        # parent the whole server-side batch under the client's
        # scheduling.cycle span (W3C traceparent riding the request dict):
        # one trace then covers scheduler pop → wire → device commit
        with tracing.span_from_remote(req.get("traceparent"),
                                      "device.schedule_batch",
                                      batch=len(pods)):
            out = self._schedule_batch_traced(pods, tie_seeds,
                                              req.get("claims"),
                                              session_req=session_req,
                                              batch_id=batch_id)
        if batch_id:
            with self._lock:
                cur = self.sessions.get(session_req.get("clientId") or "")
                if cur is not None and not cur.fenced:
                    cur.cache_batch(batch_id, out)
        return out

    def _placement_fits(self, ni: NodeInfo, pod: Pod) -> bool:
        """Commit-time occupancy re-check of one proposed placement against
        the CURRENT mirror (content + holds), via the same fitsRequest the
        admission-time Filter runs — commit and filter can never disagree.
        The kernel judged against the same state under the same lock, so a
        miss here means the capacity raced between this client's sync and
        its batch — conflict, not double-bind."""
        from ..framework.plugins.noderesources import fits_request

        return not fits_request(pod.resource_request(), ni)

    def _validate_placements(self, cid: str, pods: List[Pod],
                             node_idx: np.ndarray,
                             slot_names: Dict[int, str],
                             batch_id=None) -> Dict[int, str]:  # ktpu: locked
        """Ownership check (lock held): every proposed placement is judged
        against current ownership and occupancy AT COMMIT TIME. Accepted
        placements become holds (overlaid into the mirror immediately, so
        later pods in this batch and every later batch from any client see
        the capacity taken); rejected ones return {batch index: reason} and
        are answered with a typed conflict verdict. Two replicas racing for
        the same pod or the same capacity can never both win."""
        conflicts: Dict[int, str] = {}
        for i, pod in enumerate(pods):
            idx = int(node_idx[i])
            if idx < 0 or idx not in slot_names:
                continue
            key = pod.key()
            node_name = slot_names[idx]
            bound = self._pod_nodes.get(key)
            if bound is not None:
                conflicts[i] = f"pod already bound on {bound}"
                continue
            hold = self.holds.get(key)
            if hold is not None and hold.owner != cid:
                conflicts[i] = (f"pod already committed by client "
                                f"{hold.owner!r}")
                continue
            ni = self.infos.get(node_name)
            if ni is None:
                conflicts[i] = f"node {node_name} left the mirror"
                continue
            if hold is not None:
                # the owner re-deciding its own pod (retry after a failed
                # host commit): surrender the old hold before re-checking
                old_ni = self.infos.get(hold.node_name)
                if old_ni is not None:
                    old_ni.remove_pod(hold.pod)
                del self.holds[key]
            if not self._placement_fits(ni, pod):
                conflicts[i] = (f"node {node_name} occupancy changed "
                                "(capacity raced)")
                continue
            ni.add_pod(pod)
            self.holds[key] = _Hold(pod, node_name, cid, batch_id=batch_id)
        if conflicts:
            self.commit_conflicts += len(conflicts)
            for i, reason in conflicts.items():
                telemetry.event("conflict", client=cid, batchId=batch_id,
                                pod=pods[i].key(), reason=reason)
        return conflicts

    def _schedule_batch_traced(self, pods: List[Pod], tie_seeds,
                               claims=None, session_req=None,
                               batch_id=None) -> dict:
        with self._lock:
            # re-validate the session at COMMIT time (the fencing-token
            # rule): a client fenced between accepting the request and
            # committing the batch must not mutate shared state
            s = self._session_for(session_req or {})
            s.batches += 1
            cid = s.client_id
            self._ensure_device()

            def sync_encode():  # ktpu: locked
                with tracing.span("device.sync"):
                    self.device.sync(self.snap)
                with tracing.span("device.encode", batch=len(pods)):
                    # no capacity: every request runs the full pod axis
                    return device_step.encode(self.device, pods,
                                              capacity=None,
                                              tie_seeds=tie_seeds)

            ok, enc = device_step.retry_grown(sync_encode, self._grow)
            if not ok:
                raise PermanentDeviceError(
                    "device capacities refuse to converge")
            pb, et, tb = enc
            host_pb = self.device.encoder.last_host_pb
            self.batch_counter += 1
            sample = device_step.sample_window(
                self.percentage_of_nodes_to_score, len(self.infos),
                self._start_carry)
            # resource.k8s.io claims: the client ships pre-resolved selector
            # rows (it has the store; this process does not) and the mask
            # builds against THIS device's attribute table — the same
            # claim_feasibility_mask the in-process path dispatches
            dra_mask = None
            if claims:
                from .claim_mask import build_dra_mask, wire_claims_to_entries

                pad_to = len(host_pb["req"])
                dra_mask = build_dra_mask(
                    self.device, wire_claims_to_entries(claims), pad_to)
            bucket = int(pb.capacity)
            # namespace-quota screen: sync the client-shipped ledger table
            # into this device and build the batch's ns/req columns — the
            # same builder the in-process dispatch uses, so both transports
            # screen identically
            quota = (None, None)
            if self.quota_table or self.device.nsq_slots:
                from ..ops.quota import build_quota_batch_args

                quota = build_quota_batch_args(
                    pods, self.device, table=self.quota_table,
                    pad_to=bucket)
            slices = device_step.slice_args(pods, self.device)
            sig = bucket_sig(bucket, None, self.device.topo_enabled)
            telemetry.event("dispatch", batchId=batch_id, client=cid,
                            epoch=self.epoch, bucket=bucket, sig=sig,
                            pods=len(pods))
            # deliberate blocking-under-lock: dispatch+commit must run against
            # exactly the synced mirror — releasing here would let a peer's
            # delta interleave between the kernel's view and the ownership
            # check, re-opening the double-bind window PR 6 closed
            locktrace.note_blocking(
                "device_dispatch", "DeviceService.schedule_batch",
                allowed="kernel must judge under the same lock as commit")
            with tracing.span("device.dispatch", batch=len(pods)):
                # the served program: no topology mode, the ports filter
                # always compiled in, no carry or volume mask
                result = device_step.dispatch(
                    self.schedule_batch_fn, self.device, pb, et, tb,
                    np.int32(self.batch_counter), topo_mode=None,
                    vd_override=None, host_key=0, ports_enabled=True,
                    topo_carry=None, extra_mask=None, dra_mask=dra_mask,
                    sample=sample, slices=slices, quota=quota)
            t_dispatch = self.now_fn()
            if result.final_sample_start is not None:
                self._start_carry = result.final_sample_start
            # adopt exactly like the in-process path: the client will assume
            # these placements; its next delta push re-encodes any row the
            # host view disagrees on and the content diff repairs it
            with tracing.span("device.commit", batch=len(pods),
                              packed="packed" if result.packed is not None
                              else "fallback"):
                # THE blocking read: the packed result block lands node_idx
                # AND first_fail in one materialization (the per-array reads
                # were one blocking device read each) — the
                # same commit-plane materializer the in-process commit runs
                from .commit_plane import materialize_profiled

                (node_idx, ff, slice_words, quota_words,
                 _), disp = materialize_profiled(
                    result, self.device.caps.nodes,
                    program="schedule_batch", bucket=sig,
                    t_submit=t_dispatch, now_fn=self.now_fn,
                    batch_id=batch_id, pods=len(pods),
                    quota_col=quota[0] is not None,
                    event_extra={"client": cid})
                self.device.adopt_device(result)
                self.device.adopt_commits(result, host_pb, node_idx)
            slot_names = self.device.slot_to_name()
            # ownership check: judge every proposed placement against
            # current ownership/occupancy; winners become holds (overlaid
            # into host truth so no later sync from a lagging replica can
            # erase them), losers get a typed conflict verdict. The device
            # arrays adopted the loser too — the next sync's content diff
            # repairs that row from the (hold-free) host truth, exactly the
            # PR-4 gang-surrender repair path.
            conflicts = self._validate_placements(cid, pods, node_idx,
                                                  slot_names,
                                                  batch_id=batch_id)
            if telemetry.get() is not None:
                # placed= is an O(batch) scan — keep it behind the enabled
                # check so the disabled hot path stays one global read
                extra = {}
                if disp is not None:
                    extra = {"device_ms": round(disp["execS"] * 1e3, 3),
                             "fetch_ms": round(disp["fetchS"] * 1e3, 3)}
                telemetry.event(
                    "commit", batchId=batch_id, client=cid, epoch=self.epoch,
                    bucket=bucket, pods=len(pods),
                    placed=int(sum(1 for i in range(len(pods))
                                   if int(node_idx[i]) >= 0
                                   and i not in conflicts)),
                    conflicts=len(conflicts), **extra)
            # device preemption screen for the batch's failures (ROADMAP
            # wire-hardening: hints ride back with unschedulable results so
            # the client's PostFilter skips hopeless candidates)
            screen = best = None
            if any(int(node_idx[i]) < 0 for i in range(len(pods))):
                try:
                    pres = device_step.screen_preemption(
                        self.device, pb, result.static_masks,
                        node_idx[:len(pods)] < 0)
                    screen = np.asarray(pres.screen)
                    best = np.asarray(pres.best)
                except Exception:  # noqa: BLE001 — hints are optional
                    screen = best = None
            results: List[dict] = []
            for i in range(len(pods)):
                idx = int(node_idx[i])
                if i in conflicts:
                    results.append({"nodeName": None, "conflict": True,
                                    "error": conflicts[i]})
                    continue
                if idx >= 0 and idx in slot_names:
                    results.append({"nodeName": slot_names[idx]})
                    continue
                if ff is None:  # packless (sharded-core) results only
                    ff = np.asarray(result.first_fail)
                # REAL slots only — padding slots fail the fit check and
                # would pollute the plugin attribution (queue gating)
                plugins = set()
                statuses = {}
                for slot, name in slot_names.items():
                    fid = int(ff[i][slot])
                    if fid > 0:
                        plugins.add(fid)
                        if len(statuses) < 64:  # payload-bounded sample
                            statuses[name] = ATTRIBUTION_ORDER[fid - 1][0]
                r = {
                    "nodeName": None,
                    "unschedulablePlugins": [
                        ATTRIBUTION_ORDER[fid - 1][0] for fid in sorted(plugins)],
                    "statuses": statuses,
                }
                if screen is not None:
                    all_cands = [name for slot, name in slot_names.items()
                                 if bool(screen[i][slot])]
                    best_name = (slot_names.get(int(best[i]))
                                 if best is not None and best[i] >= 0 else None)
                    if len(all_cands) <= 1024:
                        # an exact screen only: a truncated candidate list
                        # would wrongly mark the dropped nodes hopeless
                        # (defaultpreemption treats the screen as exact)
                        r["preempt"] = {"candidates": all_cands,
                                        "best": best_name}
                    elif best_name is not None:
                        # too many candidates to ship: the ranked best alone
                        # still helps (preferred-node fast path)
                        r["preempt"] = {"candidates": None, "best": best_name}
                results.append(r)
            if slice_words is not None and slices[0] is not None:
                # ship each member's verdict word so the client can split
                # plan-infeasible from lost-in-flight without a second trip
                member_idx, member_valid = slices[0]
                for i in member_idx[member_valid]:
                    results[i]["slice"] = int(slice_words[i])
            if quota_words is not None:
                # every screened pod's quota verdict word rides back: the
                # client rejects flagged winners against its authoritative
                # ledger (screen staleness can only reject, never bind)
                for i in range(len(pods)):
                    w = int(quota_words[i])
                    if w:
                        results[i]["quota"] = w
            # stamp INSIDE the lock: epoch/deltaSeq are mutated by
            # concurrent apply_deltas calls from peer replicas — stamping
            # after release could pair this batch's results with a peer's
            # half-advanced deltaSeq (found by the locks pass)
            out = {"apiVersion": API_VERSION, "results": results,
                   "sessionGen": s.gen}
            if batch_id:
                # echo the idempotency key: a pipelined client matches
                # out-of-order replies to their requests by this id
                out["batchId"] = batch_id
            if disp is not None:
                # echo the server-side device time so the (pipelined)
                # client can attribute its round trip: device vs transport
                out["deviceTime"] = {
                    "dwellMs": round(disp["dwellS"] * 1e3, 3),
                    "execMs": round(disp["execS"] * 1e3, 3),
                    "fetchMs": round(disp["fetchS"] * 1e3, 3),
                    "deviceMs": round(
                        (disp["execS"] + disp["fetchS"]) * 1e3, 3),
                }
            return self._stamp(out)


# ---------------------------------------------------------------- transport


class ServiceBinding:
    """Mutable service slot behind a running server: the handler dispatches
    through it, so a crash-and-restart fault (or an operator restart) can
    swap in a FRESH DeviceService — new epoch, empty DeviceState — without
    tearing down the listener, exactly like a sidecar process restart
    behind a stable Service IP."""

    def __init__(self, service: DeviceService, fault_plan=None):
        self.service = service
        self.fault_plan = fault_plan
        self.restarts = 0

    def restart(self) -> DeviceService:
        old = self.service
        self.service = DeviceService(
            batch_size=old.batch_size,
            percentage_of_nodes_to_score=old.percentage_of_nodes_to_score,
            lease_ttl_s=old.lease_ttl_s, now_fn=old.now_fn)
        self.restarts += 1
        return self.service


_OPS = {"/v1/applyDeltas": "apply_deltas", "/v1/scheduleBatch": "schedule_batch",
        "/v1/health": "health", "/v1/heartbeat": "heartbeat",
        "/v1/sessions": "sessions_dump"}


class _Handler(BaseHTTPRequestHandler):
    binding: ServiceBinding = None  # set by serve()

    def log_message(self, *args):  # quiet
        pass

    def _json(self, code: int, out: dict) -> None:
        payload = json.dumps(out).encode()
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(payload)))
        self.end_headers()
        self.wfile.write(payload)

    def do_POST(self):  # noqa: N802 — stdlib naming
        op = _OPS.get(self.path)
        if op is None:
            self.send_error(404)
            return
        n = int(self.headers.get("Content-Length", 0))
        body = json.loads(self.rfile.read(n) or b"{}")
        plan = self.binding.fault_plan
        fault = plan.next_server(op) if plan is not None else None
        if fault is not None:
            if fault.kind == "crash":
                # the sidecar dies mid-request and supervision restarts it:
                # swap in a fresh service (new epoch, empty state) and sever
                # the connection — the client sees a reset, not a response
                self.binding.restart()
                self.close_connection = True
                try:
                    self.connection.shutdown(socket.SHUT_RDWR)
                except OSError:
                    pass
                return
            if fault.kind == "conflict":
                # scripted cross-client race: the 409-conflict body, so the
                # taxonomy tests can drive the client mapping without
                # staging a real two-replica collision
                self._json(409, {"error": "injected conflict",
                                 "conflict": True})
                return
            if fault.kind == "torn":
                # torn mid-stream disconnect: the request is PROCESSED (the
                # service's state advances — a batch commits, holds land)
                # but the reply never leaves. The client's transport retry
                # re-sends the same batchId and the idempotency cache
                # replays the committed result — the lost-response case.
                try:
                    getattr(self.binding.service, op)(body)
                except Exception:  # noqa: BLE001 — the reply is lost either way
                    pass
                self.close_connection = True
                try:
                    self.connection.shutdown(socket.SHUT_RDWR)
                except OSError:
                    pass
                return
            self._json(fault.status,
                       {"error": f"injected fault: {fault.kind}"})
            return
        try:
            out = getattr(self.binding.service, op)(body)
        except StaleEpochError as exc:
            # 409: the client must full-resync (distinct from 5xx so the
            # retry loop does not burn its budget re-sending stale deltas)
            self._json(409, {"error": str(exc), "staleEpoch": True,
                             "epoch": exc.epoch})
            return
        except ConflictError as exc:
            # 409 too, but a DIFFERENT 409: the state base is fine and a
            # resync cannot help — another client owns the pod/session.
            # The body's ``conflict`` flag is the discriminator.
            self._json(409, {"error": str(exc), "conflict": True})
            return
        except Exception as exc:  # noqa: BLE001 — wire errors must be JSON
            self._json(500, {"error": f"{type(exc).__name__}: {exc}"})
            return
        self._json(200, out)


def serve(service: DeviceService, port: int = 0, fault_plan=None):
    """Start the HTTP binding on localhost; returns (server, port). The
    caller owns shutdown (server.shutdown()). ``server.binding`` exposes
    the live service slot (restartable; chaos tests script crashes through
    ``fault_plan``, a testing.faults.FaultPlan)."""
    binding = ServiceBinding(service, fault_plan=fault_plan)
    handler = type("BoundHandler", (_Handler,), {"binding": binding})
    server = ThreadingHTTPServer(("127.0.0.1", port), handler)
    server.binding = binding
    t = threading.Thread(target=server.serve_forever, daemon=True)
    t.start()
    return server, server.server_address[1]


class WireClient:
    """HTTP/JSON transport with the full fault story: split connect/read
    deadlines (a hung accept and a slow batch are different failures), the
    typed error taxonomy (backend/errors.py), and retry-with-backoff for
    transient failures inside the RetryPolicy's per-call deadline budget.
    ``fault_plan`` intercepts calls before the socket for deterministic
    chaos tests."""

    def __init__(self, endpoint: str, connect_timeout: float = 5.0,
                 read_timeout: float = 60.0, retry: Optional[RetryPolicy] = None,
                 fault_plan=None, on_request_bytes=None):
        self.on_request_bytes = on_request_bytes  # see backend/wire.py
        self.endpoint = endpoint.rstrip("/")
        u = urllib.parse.urlsplit(self.endpoint)
        scheme = u.scheme or "http"
        if scheme not in ("http", "https") or not u.netloc:
            # a scheme-less endpoint ('127.0.0.1:5000', the gRPC form)
            # would silently parse as a PATH and hit port 80 forever —
            # loud error now beats permanent breaker-open later
            raise ValueError(
                f"device-service endpoint must be http(s)://host:port, "
                f"got {endpoint!r}")
        self._conn_cls = (http.client.HTTPSConnection if scheme == "https"
                          else http.client.HTTPConnection)
        self._host = u.hostname or "127.0.0.1"
        self._port = u.port or (443 if scheme == "https" else 80)
        self._base_path = u.path.rstrip("/")
        self.connect_timeout = connect_timeout
        self.read_timeout = read_timeout
        self.retry = retry if retry is not None else RetryPolicy()
        self.fault_plan = fault_plan

    def _do_post(self, path: str, data: bytes) -> dict:
        # socket IO must never run under a traced lock (a slow device
        # service would wedge whatever component held it)
        locktrace.note_blocking("http", path)
        conn = self._conn_cls(self._host, self._port,
                              timeout=self.connect_timeout)
        try:
            try:
                conn.connect()
                # connected: the remaining budget is the READ deadline
                conn.sock.settimeout(self.read_timeout)
                conn.request("POST", self._base_path + path, body=data,
                             headers={"Content-Type": "application/json"})
                resp = conn.getresponse()
                status = resp.status
                body = resp.read()
            except (ConnectionError, http.client.HTTPException, socket.timeout,
                    TimeoutError, OSError) as e:
                # refused/reset/timeout/torn response: the transient family
                raise TransientDeviceError(
                    f"device service unreachable: {type(e).__name__}: {e}") from e
        finally:
            conn.close()
        try:
            out = json.loads(body or b"{}")
        except ValueError as e:
            # classify by status first: a torn/HTML body on an
            # infrastructure 5xx is still the transient family
            if status in (502, 503, 504):
                raise TransientDeviceError(
                    f"device service {status}: non-JSON body") from e
            raise PermanentDeviceError(f"malformed device response: {e}") from e
        if status == 409 and out.get("staleEpoch"):
            raise StaleEpochError(out.get("epoch", ""), out.get("error", ""))
        if status == 409 and out.get("conflict"):
            raise ConflictError(out.get("error", "commit conflict"))
        if status in (502, 503, 504):
            # infrastructure-flavored 5xx (overload, proxy, restart in
            # progress) MAY clear: give the retry loop a chance before the
            # breaker counts it
            raise TransientDeviceError(
                f"device service {status}: {out.get('error', '')}")
        if status >= 400:
            # includes 500: the handler answers it only for a service-side
            # exception, which is deterministic — re-sending the identical
            # batch re-raises it (matches gRPC's UNKNOWN → permanent)
            raise PermanentDeviceError(
                f"device service {status}: {out.get('error', '')}")
        if "error" in out:
            raise PermanentDeviceError(out["error"])
        return out

    def _post(self, path: str, payload: dict, op: str) -> dict:
        return self._post_bytes(path, json.dumps(payload).encode(), op)

    def _post_bytes(self, path: str, data: bytes, op: str) -> dict:
        def attempt():
            raise_injected_fault(self.fault_plan, op, self.read_timeout)
            return self._do_post(path, data)

        return self.retry.run(op, attempt)

    # the JSON transport is schema-free: claim rows ride the request as-is
    supports_dra = True
    supports_health = True
    supports_sessions = True

    # the encoding backend/wire.py cuts ApplyDeltas parts with: a request
    # body is the head's JSON with the entries' JSON spliced in as "nodes"
    @staticmethod
    def _encode_node_delta(entry: dict):
        data = json.dumps(entry).encode()
        return data, len(data) + 2  # with its ", " separator

    @staticmethod
    def _deltas_request(head: dict, encoded):
        body = json.dumps(head).encode()
        if encoded:
            body = (body[:-1] + (b", " if len(body) > 2 else b"")
                    + b'"nodes": [' + b", ".join(encoded) + b"]}")
        return body, len(body)

    def _send_deltas(self, body: bytes) -> dict:
        return self._post_bytes("/v1/applyDeltas", body, "apply_deltas")

    @staticmethod
    def _batch_request(payload: dict):
        body = json.dumps(payload).encode()
        return body, len(body)

    def _send_batch(self, body: bytes) -> dict:
        return self._post_bytes("/v1/scheduleBatch", body, "schedule_batch")

    def apply_deltas(self, payload: dict) -> dict:
        return wire.apply_deltas(self, payload)

    def schedule_batch(self, payload: dict) -> dict:
        return wire.schedule_batch(self, payload)

    def health(self) -> dict:
        """The cheap identity/liveness verb (half-open probe)."""
        return self._post("/v1/health", {"apiVersion": API_VERSION}, "health")

    def heartbeat(self, payload: dict) -> dict:
        """Lease renewal for this client's session (HA topology)."""
        return self._post("/v1/heartbeat", payload, "heartbeat")

    def sessions_dump(self) -> dict:
        """Session-table introspection (/debug/sessions passthrough)."""
        return self._post("/v1/sessions", {"apiVersion": API_VERSION},
                          "sessions")


# ---------------------------------------------------------------- pipeline


class _WireInflight:
    """One wire batch submitted but whose reply has not been processed —
    the wire twin of tpu_scheduler._Inflight (a dispatched-but-uncommitted
    ring entry). ``payload`` is kept whole so a stale-epoch drain can
    re-send the identical logical batch (same idempotent batchId) after
    the resync."""

    __slots__ = ("qps", "payload", "batch_id", "pod_cycle", "t0", "t_sent",
                 "era")

    def __init__(self, qps: List[QueuedPodInfo], payload: dict,
                 pod_cycle: int, t0: float, t_sent: float, era: int):
        self.qps = qps
        self.payload = payload
        self.batch_id = payload["batchId"]
        self.pod_cycle = pod_cycle
        self.t0 = t0          # pop time: the attempt-latency clock
        self.t_sent = t_sent  # submit time: the sizer's service-span clock
        self.era = era        # sync era at submit (see _wire_sync_era)


class WirePipeline:
    """Concurrent transport lanes for the pipelined wire path: up to
    ``depth`` ScheduleBatch calls ride their own connections at once (the
    "second connection" form of the streaming channel), and every reply is
    deposited into a completion map keyed by the batchId the server echoes
    — so replies that arrive OUT OF ORDER, duplicated, or on the wrong
    lane (testing/faults.py ``reorder``/``dup_reply``) still route to
    exactly the in-flight batch they answer.

    Lane threads run ONLY transport work (``send_fn`` — the full
    retry/taxonomy client call); every scheduler-state mutation (commit,
    resync, requeue, breaker) stays on the scheduling thread, which blocks
    in ``claim`` for the batch it wants next. Lanes are spawned on demand
    and exit when the submit queue drains — no idle threads linger."""

    OP = "schedule_batch"

    def __init__(self, send_fn, depth: int, fault_plan=None):
        self._send = send_fn
        self.depth = max(1, int(depth))
        self.fault_plan = fault_plan
        self._cv = threading.Condition(locktrace.make_lock("WirePipeline"))
        self._submitted: Deque[dict] = deque()
        # batchId -> ("ok", reply) | ("err", exc); claimable while expected
        self._completions: Dict[str, tuple] = {}
        self._expected: set = set()
        self._lanes = 0
        self.duplicate_replies = 0  # late/duplicate/foreign deliveries dropped

    def submit(self, payload: dict) -> None:
        with self._cv:
            self._expected.add(payload["batchId"])
            self._submitted.append(payload)
            if self._lanes < self.depth:
                self._lanes += 1
                threading.Thread(target=self._lane, name="ktpu-wire-lane",
                                 daemon=True).start()

    def claim(self, batch_id: str, timeout: Optional[float] = None):
        """Block until the reply for ``batch_id`` arrives, then return it
        (or raise the transport error that ended its call). The wait is on
        the COMPLETION of that id, not on any particular lane — replies
        for newer batches landing first are simply left for their own
        claims (out-of-order tolerated by construction)."""
        with self._cv:
            self._cv.wait_for(
                lambda: batch_id in self._completions,  # ktpu: unguarded-ok(wait_for predicate is evaluated by Condition with its lock held)
                timeout=timeout)
            self._expected.discard(batch_id)
            outcome = self._completions.pop(batch_id, None)
        if outcome is None:
            raise TransientDeviceError(
                f"pipelined reply for batch {batch_id} never arrived")
        kind, value = outcome
        if kind == "err":
            raise value
        return value

    def inflight(self) -> int:
        with self._cv:
            return len(self._expected)

    # ------------------------------------------------------------ internals

    def _lane(self) -> None:
        while True:
            with self._cv:
                if not self._submitted:
                    self._lanes -= 1
                    return
                payload = self._submitted.popleft()
            sent_id = payload["batchId"]
            fault = (self.fault_plan.next_reply(self.OP)
                     if self.fault_plan is not None else None)
            try:
                out = self._send(payload)
            except BaseException as exc:  # noqa: BLE001 — routed, not raised here
                # transport errors carry no reply id: they belong to the
                # batch THIS lane was sending
                self._deposit(sent_id, ("err", exc))
                continue
            if (fault is not None and fault.kind == "reorder"
                    and fault.rendezvous is not None):
                # scripted cross-lane delivery: this lane receives the
                # OTHER call's reply — the router below must still pair it
                # with the right in-flight batch via the echoed batchId
                out = fault.rendezvous.swap(out)
            reply_id = out.get("batchId") or sent_id
            self._deposit(reply_id, ("ok", out))
            if fault is not None and fault.kind == "dup":
                self._deposit(reply_id, ("ok", out))  # duplicated delivery

    def _deposit(self, batch_id: str, outcome: tuple) -> None:
        with self._cv:
            if batch_id not in self._expected or batch_id in self._completions:
                # a reply nobody is (still) waiting on: a duplicate
                # delivery, a reply after its claim, or a foreign id —
                # dropping it is the only safe move (idempotent batchIds
                # mean the real reply was or will be processed exactly once)
                self.duplicate_replies += 1
                telemetry.event("pipeline_dup_reply", batchId=batch_id)
                return
            self._completions[batch_id] = outcome
            self._cv.notify_all()


# ---------------------------------------------------------------- scheduler


class WireScheduler(Scheduler):
    """Control plane driving the device service over the wire: the batched
    analog of the HTTP extender, with the same host machinery around it as
    TPUScheduler (queue order, assume/bind, failure handling + backoff)."""

    def __init__(self, *args, endpoint, batch_size: int = 256,
                 transport: str = "http",
                 connect_timeout: float = 5.0, read_timeout: float = 60.0,
                 wire_max_retries: int = 3, wire_backoff_base: float = 0.05,
                 wire_backoff_max: float = 2.0, wire_deadline_s: float = 90.0,
                 breaker_threshold: int = 3, breaker_reset_s: float = 5.0,
                 client_id: Optional[str] = None,
                 heartbeat_interval_s: float = 5.0,
                 fabric_probe_interval_s: float = 5.0,
                 wire_pipeline_depth: Optional[int] = None,
                 batch_deadline_ms: Optional[float] = None,
                 standby_replication: bool = True,
                 fault_plan=None, sleep_fn=None, **kwargs):
        super().__init__(*args, **kwargs)
        self.retry_policy = RetryPolicy(
            max_retries=wire_max_retries, backoff_base=wire_backoff_base,
            backoff_max=wire_backoff_max, deadline_s=wire_deadline_s,
            sleep_fn=sleep_fn if sleep_fn is not None else time.sleep,
            now_fn=self.now_fn,
            on_retry=lambda op: self.smetrics.wire_retries.inc(op))
        # ``endpoint`` names one device service ("http://host:port"), a
        # comma-separated list, or a sequence — more than one enables the
        # device-side HA fabric (backend/fabric.py): primary/standby
        # selection with failover riding the epoch-resync machinery.
        # ``fault_plan`` may be a matching list for per-endpoint chaos.
        endpoints = ([e.strip() for e in endpoint.split(",") if e.strip()]
                     if isinstance(endpoint, str)
                     else [str(e) for e in endpoint])
        if not endpoints:
            raise ValueError("WireScheduler needs at least one endpoint")
        plans = (list(fault_plan) if isinstance(fault_plan, (list, tuple))
                 else [fault_plan] * len(endpoints))
        if len(plans) != len(endpoints):
            raise ValueError(
                f"fault_plan list ({len(plans)}) must match endpoints "
                f"({len(endpoints)})")

        def count_bytes(op, nbytes):  # every transport client's requests
            self.smetrics.wire_request_bytes.inc(op, value=float(nbytes))

        if transport == "grpc":
            from .grpc_service import GrpcClient

            def make_client(ep, plan, retry=None):
                return GrpcClient(ep, read_timeout=read_timeout,
                                  retry=retry or self.retry_policy,
                                  fault_plan=plan,
                                  on_request_bytes=count_bytes)
        else:
            def make_client(ep, plan, retry=None):
                return WireClient(ep, connect_timeout=connect_timeout,
                                  read_timeout=read_timeout,
                                  retry=retry or self.retry_policy,
                                  fault_plan=plan,
                                  on_request_bytes=count_bytes)
        if len(endpoints) > 1:
            from .fabric import DeviceFabric

            # fabric health probes of maybe-dead replicas run on the
            # scheduling thread: a single-attempt probe client (no retry,
            # no backoff sleeps) bounds a blackholed standby's cost to one
            # connect timeout per probe window, not the full retry budget
            probe_retry = RetryPolicy(
                max_retries=0, backoff_base=wire_backoff_base,
                backoff_max=wire_backoff_max, deadline_s=wire_deadline_s,
                sleep_fn=sleep_fn if sleep_fn is not None else time.sleep,
                now_fn=self.now_fn)
            self.client = DeviceFabric(
                endpoints,
                lambda ep, i: make_client(ep, plans[i]),
                probe_client_factory=lambda ep, i: make_client(
                    ep, plans[i], retry=probe_retry),
                metrics=self.smetrics, now_fn=self.now_fn,
                probe_interval_s=fabric_probe_interval_s,
                # warm standbys: background delta fan-out so a promoted
                # standby resyncs O(dirty) instead of O(cluster)
                replication=standby_replication)
        else:
            # single-replica fast path: the plain transport client, zero
            # fabric indirection on the per-batch hot path
            self.client = make_client(endpoints[0], plans[0])
        self.batch_size = batch_size
        # circuit breaker + oracle degradation: N consecutive transport
        # failures open the breaker and every pod takes the sequential
        # oracle path until a half-open probe heals the wire (scheduling
        # never stops with a dead sidecar)
        self.breaker = CircuitBreaker(
            failure_threshold=breaker_threshold,
            reset_timeout_s=breaker_reset_s, now_fn=self.now_fn,
            on_state_change=self._on_breaker_state)
        self.smetrics.backend_circuit_state.set(value=0)
        self._degraded_since: Optional[float] = None
        self.degraded_pods = 0
        # pods the sequential oracle placed because the wire path does not
        # cover their features (TPUScheduler.fallback_scheduled's twin)
        self.fallback_scheduled = 0
        # state-resync protocol: last epoch the device answered with; a
        # mismatch (restart) surfaces as StaleEpochError → full resync
        self._device_epoch: Optional[str] = None
        self.resyncs = 0
        # idempotency keys for schedule_batch: one id per LOGICAL batch
        # (transport retries re-send the same id, so a server that already
        # committed replays its response instead of double-committing)
        self._batch_id_prefix = _new_epoch()
        self._batch_ids = itertools.count(1)
        self._sent_gens: Dict[str, int] = {}
        # names ever pushed to the CURRENT device base: the removal list is
        # computed from this set, not _sent_gens — _invalidate_node pops a
        # node's sent gen to force a re-send, and a node deleted in that
        # window would otherwise never be named in `removed` (a ghost row
        # on the service swept only by a full resync)
        self._pushed_nodes: set = set()
        self._sent_ns: Dict[str, dict] = {}
        # last quotaTable payload acknowledged by the service — change-
        # tracked whole (the table is tiny), like _sent_ns for labels
        self._sent_quota: Dict[str, dict] = {}
        self._batchable_cache: Dict[str, bool] = {}
        self.settle_abandoned = False
        # HA session: this replica's identity on the shared device service.
        # sessionGen is learned from the first response; a ConflictError
        # (fenced/zombie session, or a raced pod) never counts against the
        # breaker — the service is healthy, another replica just won.
        self.client_id = client_id or f"ktpu-{_new_epoch()}"
        self.heartbeat_interval_s = heartbeat_interval_s
        self._session_gen: Optional[int] = None
        self._last_heartbeat = self.now_fn()
        self.session_rejoins = 0
        self.ha_takeovers = 0
        # claim resolution for the wire dra_mask path (the builder only
        # reads the store; the mask itself builds server-side)
        from .claim_mask import ClaimMaskBuilder

        self._claim_masks = ClaimMaskBuilder(self.store)
        # ---- pipelined wire transport (ROADMAP item 2, wire half) ----
        # Up to K logical batches ride the wire at once, each on its own
        # connection lane, replies matched by the server-echoed batchId —
        # the wire twin of the in-process in-flight ring (_Inflight/
        # _drain_inflight): batch K's server-side device work overlaps
        # batch K-1's host commit AND the next pop/encode, instead of the
        # strictly request/response transport forfeiting the overlap.
        # Depth semantics mirror KTPU_PIPELINE_DEPTH; 0 = synchronous.
        # Default 3: the wire RTT is long relative to host work, so the
        # wire ring runs one deeper than the in-process default (bench
        # A/B: depth 3 > 2 > 0 on both transports at iso-conditions).
        if wire_pipeline_depth is None:
            if os.environ.get("KTPU_WIRE_PIPELINE", "1") == "0":
                wire_pipeline_depth = 0
            else:
                wire_pipeline_depth = max(0, int(os.environ.get(
                    "KTPU_WIRE_PIPELINE_DEPTH", "3")))
        self.wire_pipeline_depth = wire_pipeline_depth
        self._wire_inflight: Deque[_WireInflight] = deque()
        self._wire_pipeline: Optional[WirePipeline] = None
        if wire_pipeline_depth:
            # lanes run the raw transport call only (full retry/taxonomy);
            # every recovery move — resync, rejoin, requeue, breaker —
            # happens at claim time on the scheduling thread
            self._wire_pipeline = WirePipeline(
                self.client.schedule_batch, wire_pipeline_depth,
                fault_plan=plans[0] if len(endpoints) == 1 else None)
        self.pipelined_wire_batches = 0
        # sync ERA: bumped by every full resync and session rejoin. A
        # pipelined reply completed before the bump carries epoch/session
        # stamps of the pre-resync world — its RESULTS are valid (the
        # server committed them under a then-live session), but adopting
        # its stamps would regress the freshly-learned epoch/sessionGen
        self._wire_sync_era = 0
        # the stall-aware sizer, reused from the in-process ring: the
        # controlled quantity is the same pop→processed attempt latency,
        # and the claim-blocked residual feeds the stall model so the
        # batch size settles where wire round-trip time balances the
        # overlapped host window
        from .sizer import BatchSizer

        if batch_deadline_ms is None:
            batch_deadline_ms = float(os.environ.get(
                "KTPU_BATCH_DEADLINE_MS", "500"))
        self.wire_sizer = BatchSizer(batch_size, batch_deadline_ms / 1000.0)

    # ------------------------------------------------------- degraded mode

    def _on_breaker_state(self, old: str, new: str) -> None:
        self.smetrics.backend_circuit_state.set(value=STATE_VALUES[new])
        now = self.now_fn()
        if new == "open" and self._degraded_since is None:
            self._degraded_since = now
        elif new == "closed" and self._degraded_since is not None:
            self.smetrics.degraded_seconds.inc(value=now - self._degraded_since)
            self._degraded_since = None

    def _accrue_degraded(self) -> None:
        """Fold elapsed degraded time into the counter incrementally so a
        long-open breaker is visible before it heals."""
        if self._degraded_since is not None:
            now = self.now_fn()
            self.smetrics.degraded_seconds.inc(value=now - self._degraded_since)
            self._degraded_since = now

    def _wire_supported(self, pod: Pod) -> bool:
        """Same gating as TPUScheduler.batch_supported: the service runs the
        compiled DEFAULT plugin set — volume pods and custom profiles take
        the local sequential path. Claim pods ride the wire when every
        claim resolves AND the transport carries the dra_mask input
        (ROADMAP PR 1 follow-up: the request schema ships resolved
        selector rows; the server builds the mask against its own
        attribute table)."""
        if pod.spec.volumes:
            return False
        if pod.spec.resource_claims:
            if not getattr(self.client, "supports_dra", False):
                return False
            if not self._claim_masks.batchable(pod):
                return False
        fwk = self.framework_for_pod(pod)
        cached = self._batchable_cache.get(fwk.profile_name)
        if cached is None:
            from ..framework.registry import DEFAULT_PLUGINS

            cached = all(
                [(p.name(), w) for p, w in fwk.points.get(point, [])]
                == list(DEFAULT_PLUGINS.get(point, []))
                for point in ("pre_filter", "filter", "pre_score", "score")
            )
            self._batchable_cache[fwk.profile_name] = cached
        return cached

    def _build_entries(self, skip_unsent_check: bool = False):
        """(entries, pending_gens) over the current snapshot — the one wire
        shape for per-node deltas, shared by the incremental push and the
        full resync so the two payloads can never drift apart."""
        entries: List[dict] = []
        pending_gens: Dict[str, int] = {}
        for name, ni in self.snapshot.node_info_map.items():
            if ni.node is None:
                continue
            if not skip_unsent_check and self._sent_gens.get(name) == ni.generation:
                continue
            entries.append({
                "gen": ni.generation,
                "node": to_wire(ni.node),
                "pods": [to_wire(p) for p in ni.pods],
            })
            pending_gens[name] = ni.generation
        return entries, pending_gens

    def _push_deltas(self) -> None:
        """Incremental state sync. Bookkeeping (_sent_gens/_sent_ns) commits
        only AFTER the wire call succeeds, every part of it acknowledged
        (backend/wire.py cuts a large push): a failed push must leave the rows
        marked unsent, or the retry after recovery would skip them and the
        device mirror would silently diverge from host truth."""
        self.cache.update_snapshot(self.snapshot)
        current = self.snapshot.node_info_map
        removed = [n for n in self._pushed_nodes if n not in current]
        entries, pending_gens = self._build_entries()
        namespaces = {}
        for ns, obj in self.store.namespaces.items():
            labels = dict(obj.meta.labels)
            if self._sent_ns.get(ns) != labels:
                namespaces[ns] = labels
        quota_table = self._wire_quota_table()
        if not (entries or removed or namespaces) and quota_table is None:
            return
        payload = {"apiVersion": API_VERSION, "nodes": entries,
                   "removed": removed, "namespaces": namespaces}
        if quota_table is not None:
            payload["quotaTable"] = quota_table
        self._stamp_session(payload)
        self._stamp_inflight(payload)
        if self._device_epoch:
            payload["expectEpoch"] = self._device_epoch
        else:
            # epoch unknown = WE are the fresh process (client restart): a
            # surviving device may hold a mirror from our predecessor —
            # ghost nodes we cannot name in `removed` (_sent_gens is empty).
            # The first contact is therefore a FULL sync, establishing a
            # clean base exactly like the informer relist on startup.
            payload["full"] = True
        tp = tracing.format_traceparent()
        if tp:
            payload["traceparent"] = tp
        try:
            out = self.client.apply_deltas(payload)
        except StaleEpochError as exc:
            # the device restarted under us: its mirror is a fresh empty
            # state — incremental deltas are meaningless against it
            self._full_resync(exc.epoch)
            return
        self._device_epoch = out.get("epoch", self._device_epoch)
        self._session_gen = out.get("sessionGen", self._session_gen)
        self._sent_gens.update(pending_gens)
        self._pushed_nodes.update(pending_gens)
        for n in removed:
            self._sent_gens.pop(n, None)
            self._pushed_nodes.discard(n)
        for ns, labels in namespaces.items():
            self._sent_ns[ns] = labels
        if quota_table is not None:
            self._sent_quota = quota_table

    def warm_buckets(self, sample_pods=None) -> int:
        """Compile the service's batch program before the first batch, as
        TPUScheduler.warm_buckets does in process: without it the first
        batch pays the compile and the sizer's latency model collapses. A
        state push, then one ScheduleBatch with no pods: the service pads
        every batch to its pod capacity, so that is the program the real
        batches run, for the topology the synced state registers.
        ``sample_pods`` are not sent (the service would place them).
        Returns the number of programs warmed: 0 when the service cannot be
        reached, which the breaker then handles on the first batch. Once
        warm, the heap leaves the collector (utils/heap.py); with the service
        in this process, that covers both."""
        try:
            self._push_deltas()
            self._send_batch_payload(self._build_batch_payload([]))
        except DeviceServiceError:
            return 0
        heap.settle_warm_heap(self.smetrics)
        return 1

    def _wire_quota_table(self) -> Optional[Dict[str, dict]]:
        """The COMPLETE quota-ledger export for the device screen when it
        changed since the last acknowledged push, else None. Shipped whole
        (it is tiny — one used/limit row pair per quota'd namespace), so
        apply_deltas can treat every payload as the full desired state;
        limits already fold in borrowable cohort headroom."""
        plugin = self._quota_plugin()
        if plugin is None:
            return None
        table = {ns: {"used": list(used), "limit": list(limit)}
                 for ns, (used, limit)
                 in plugin.device_quota_table().items()}
        if table == self._sent_quota:
            return None
        return table

    def _full_resync(self, new_epoch: Optional[str] = None) -> None:
        """Epoch-mismatch recovery: forget everything we believe the device
        holds and ship the complete host truth as one ``full`` delta (the
        informer relist of the crash-only contract, pointed at the device),
        sent in bounded parts when it is large (backend/wire.py). A failure
        on any part leaves nothing marked as sent and the epoch unknown, so
        the next push is a full sync again."""
        self.resyncs += 1
        self._wire_sync_era += 1
        self._sent_gens.clear()
        self._pushed_nodes.clear()
        self._sent_ns.clear()
        self._sent_quota = {}
        self._device_epoch = new_epoch
        # a new epoch = a new service INSTANCE: no session of ours survived
        # it. Stamping the dead incarnation's sessionGen would read as a
        # zombie (ConflictError) — rejoin fresh and learn the new gen from
        # the resync response.
        self._session_gen = None
        self.cache.update_snapshot(self.snapshot)
        entries, pending_gens = self._build_entries(skip_unsent_check=True)
        namespaces = {ns: dict(obj.meta.labels)
                      for ns, obj in self.store.namespaces.items()}
        payload = {"apiVersion": API_VERSION, "full": True, "nodes": entries,
                   "removed": [], "namespaces": namespaces}
        quota_table = self._wire_quota_table()
        if quota_table is not None:
            payload["quotaTable"] = quota_table
        self._stamp_session(payload)
        self._stamp_inflight(payload)
        tp = tracing.format_traceparent()
        if tp:
            payload["traceparent"] = tp
        try:
            out = self.client.apply_deltas(payload)
        except DeviceServiceError:
            self._device_epoch = None
            raise
        self._device_epoch = out.get("epoch", new_epoch)
        self._session_gen = out.get("sessionGen", self._session_gen)
        self._sent_gens.update(pending_gens)
        self._pushed_nodes.update(pending_gens)
        self._sent_ns.update(namespaces)
        if quota_table is not None:
            self._sent_quota = quota_table

    # ------------------------------------------------------------ HA session

    def _stamp_session(self, payload: dict) -> None:
        payload["clientId"] = self.client_id
        if self._session_gen is not None:
            payload["sessionGen"] = self._session_gen
        else:
            payload.pop("sessionGen", None)  # re-stamp after a rejoin

    def _stamp_inflight(self, payload: dict) -> None:
        """Name the batches whose replies this client has not yet processed
        (pipelined transport): the service must keep their commit holds
        alive through this push's owner-content reconciliation — our truth
        cannot include placements we have not seen yet."""
        if self._wire_inflight:
            payload["inflightBatchIds"] = [e.batch_id
                                           for e in self._wire_inflight]

    def _session_rejoin(self) -> None:
        """This incarnation was fenced (or superseded): forget the session
        AND everything we believe the service holds for us, so the next
        push re-establishes a fresh session with a full resync — the
        scheduler-side twin of the stale-epoch recovery."""
        self.session_rejoins += 1
        self._wire_sync_era += 1
        self._session_gen = None
        self._device_epoch = None
        self._sent_gens.clear()
        self._pushed_nodes.clear()
        self._sent_ns.clear()

    def _periodic_housekeeping(self, now: Optional[float] = None) -> None:
        super()._periodic_housekeeping(now)
        if not getattr(self.client, "supports_sessions", False):
            return
        if self.breaker.state == OPEN:
            # device presumed down: a heartbeat would just burn the retry
            # budget's backoff sleeps inside the degraded loop. The breaker
            # probe owns re-discovery; if our lease died meanwhile, the
            # first post-heal request gets fenced and rejoins.
            return
        now = self.now_fn()
        if (self.heartbeat_interval_s
                and now - self._last_heartbeat >= self.heartbeat_interval_s):
            self._last_heartbeat = now
            self._heartbeat()

    def _heartbeat(self) -> None:
        payload = {"apiVersion": API_VERSION}
        self._stamp_session(payload)
        try:
            out = self.client.heartbeat(payload)
        except ConflictError:
            self._session_rejoin()
            return
        except DeviceServiceError:
            return  # transport trouble: the breaker path owns the wire story
        self._session_gen = out.get("sessionGen", self._session_gen)
        self.smetrics.client_sessions.set(value=out.get("sessions", 1))
        for cid in out.get("fenced", ()):
            self.ha_takeovers += 1
            self.smetrics.ha_takeovers.inc()
            telemetry.event("takeover", client=self.client_id,
                            fencedPeer=cid)
            self._adopt_after_takeover(cid)

    def _adopt_after_takeover(self, dead_client: str) -> None:
        """A peer replica was fenced: its uncommitted capacity is already
        released server-side; adopt its orphaned queue slice. Unbound pods
        this replica is (now) responsible for but is not tracking re-enter
        the queue, and parked unschedulable pods get the capacity-freed
        wake-up (the fence released real capacity, like an assigned-pod
        delete)."""
        from ..queue import events as qevents

        pending = {qp.pod.key() for qp in self.queue.pending_pod_infos()}
        for pod in list(self.store.pods.values()):
            if pod.spec.node_name or not self._responsible_for(pod):
                continue
            key = pod.key()
            if key in pending or key in self.waiting_pods:
                continue
            self.queue.add(pod)
        self.queue.move_all_to_active_or_backoff_queue(
            qevents.SCHEDULER_TAKEOVER)

    def schedule_batch_cycle(self) -> int:
        if self.informer_factory is not None:
            self.informer_factory.pump()  # see TPUScheduler: the batched
            # loop pumps the informer bus exactly like schedule_one
        self._periodic_housekeeping()
        # the stall-aware sizer bounds the SYNCHRONOUS pop exactly like
        # the in-process ring's cycle (deadline-cut batches keep the
        # pop→processed p99 inside the budget). The PIPELINED pop takes
        # the full batch: the server serializes batches under its service
        # lock, so a pipelined batch's latency is dominated by its ~K-cycle
        # ring dwell — cutting the batch cannot shorten it (measured: the
        # deadline model collapses the target to min_batch and costs ~2.5x
        # wire throughput); the latency lever there is the DEPTH, and the
        # sizer keeps recording spans/waits as evidence.
        target = (self.batch_size if self._wire_pipeline is not None
                  else min(self.batch_size, self.wire_sizer.target()))
        qps = self.queue.pop_batch(target)
        if not qps:
            # nothing new to overlap with: land the in-flight wire batches
            # so their binds/failures settle before the caller judges
            # settlement (the ring's empty-pop drain, on the wire)
            self._drain_wire_inflight()
            return 0
        t0 = self.now_fn()
        pod_cycle = self.queue.scheduling_cycle
        buffer: List[QueuedPodInfo] = []
        for qp in qps:
            pod = self.store.get_pod(qp.pod.key())
            if pod is None or pod.spec.node_name or not self._responsible_for(pod):
                # deleted/bound meanwhile: drop the pop-opened ledger entry
                latency_ledger.close_skipped(qp.pod.key(), pod)
                continue
            qp.pod = pod
            # host-side gang quorum + namespace-quota gates (the remote
            # program models neither) — same rules as the in-process path
            from ..framework.plugins.coscheduling import gang_precheck_status
            from ..framework.plugins.quota import quota_precheck_status

            fwk = self.framework_for_pod(pod)
            quota_st = quota_precheck_status(fwk, pod)
            if quota_st is not None:
                self.metrics.inc("schedule_attempts")
                self.smetrics.observe_attempt(
                    "unschedulable", fwk.profile_name, self.now_fn() - t0)
                self._handle_scheduling_failure(
                    fwk, self._new_cycle_state(), qp, quota_st,
                    Diagnosis(unschedulable_plugins={"QuotaAdmission"}),
                    pod_cycle)
                continue
            gang_st = gang_precheck_status(fwk, pod)
            if gang_st is not None:
                self.metrics.inc("schedule_attempts")
                self.smetrics.observe_attempt(
                    "unschedulable", fwk.profile_name, self.now_fn() - t0)
                self._handle_scheduling_failure(
                    fwk, self._new_cycle_state(), qp, gang_st,
                    Diagnosis(unschedulable_plugins={"Coscheduling"}),
                    pod_cycle)
                continue
            if self._wire_supported(pod):
                buffer.append(qp)
                continue
            # strict pop order: flush the wire batch before a fallback pod so
            # a lower-priority local pod never jumps a batched one — and
            # land everything in flight first (same rule on the pipeline)
            self._flush_wire(buffer, pod_cycle, t0)
            buffer = []
            self._drain_wire_inflight()
            self.cache.update_snapshot(self.snapshot)
            before = self.metrics["scheduled"]
            self.schedule_one_pod(qp, pod_cycle)
            if self.metrics["scheduled"] > before:
                self.fallback_scheduled += 1
        self._flush_wire(buffer, pod_cycle, t0)
        return len(qps)

    def _flush_wire(self, batch: List[QueuedPodInfo], pod_cycle: int, t0: float) -> None:
        if not batch:
            return
        # one scheduling.cycle span per wire batch: the traceparent injected
        # below makes the server's device.sync/encode/dispatch/commit spans
        # children of this span — a single trace from pop to device commit
        with tracing.span("scheduling.cycle", batch=len(batch),
                          transport=type(self.client).__name__):
            self._flush_wire_traced(batch, pod_cycle, t0)

    def _flush_wire_traced(self, batch: List[QueuedPodInfo], pod_cycle: int, t0: float) -> None:
        if not self.breaker.allow():
            # breaker open: the device is presumed down — land what is
            # already in flight (the entries fail with their own errors and
            # requeue), then route the whole batch through the sequential
            # oracle path (scheduling never stops); the next allow() past
            # the reset timeout probes
            self._drain_wire_inflight()
            self._accrue_degraded()
            self._schedule_degraded(batch, pod_cycle)
            return
        from .circuit import HALF_OPEN

        if (self.breaker.state == HALF_OPEN
                and getattr(self.client, "supports_health", False)):
            # half-open probe = the cheap health RPC, not a full batch
            # pushed through a maybe-dead service: a dead sidecar costs one
            # tiny request and this batch degrades immediately; a live one
            # answers in microseconds and the real push proceeds
            try:
                self.client.health()
            except DeviceServiceError as exc:
                self.breaker.record_failure(exc)  # half-open: re-opens
                self._accrue_degraded()
                self._schedule_degraded(batch, pod_cycle)
                return
        try:
            self._push_deltas()
            if self._wire_pipeline is not None:
                # pipelined: the batch rides a transport lane; replies are
                # claimed oldest-first once the ring exceeds its depth, so
                # K batches stay in flight across the wire while this
                # thread pops/encodes the next one
                payload = self._build_batch_payload(batch)
                entry = _WireInflight(batch, payload, pod_cycle, t0,
                                      self.now_fn(), self._wire_sync_era)
                self._wire_inflight.append(entry)
                if len(self._wire_inflight) > 1:
                    self.pipelined_wire_batches += 1
                self.smetrics.wire_inflight.set(
                    value=len(self._wire_inflight))
                # ledger: the batch rides a transport lane — device.inflight
                # dwell is the wire ring's K-cycle residency, correlated by
                # the idempotent batchId
                latency_ledger.transition_many(
                    [qp.pod.key() for qp in batch], "device.inflight",
                    batch_id=entry.batch_id)
                self._wire_pipeline.submit(payload)
                while len(self._wire_inflight) > self.wire_pipeline_depth:
                    self._drain_oldest_wire()
                return
            payload = self._build_batch_payload(batch)
            latency_ledger.transition_many(
                [qp.pod.key() for qp in batch], "device.inflight",
                batch_id=payload["batchId"])
            t_send = self.now_fn()
            res = self._send_batch_payload(payload)
        except ConflictError as exc:
            # fenced session / cross-client race: the service is HEALTHY, so
            # this never counts against the breaker. Rejoin under a fresh
            # session and give the pods back to the backoffQ — the next
            # attempt runs on a clean session against whatever the winning
            # replica left behind.
            self._wire_conflict(batch, exc, pod_cycle, t0)
            return
        except DeviceServiceError as exc:
            self._wire_transport_failure(batch, exc, pod_cycle, t0)
            return
        self.breaker.record_success()
        self._note_device_time(res, len(batch), payload["batchId"],
                               self.now_fn() - t_send)
        self._process_wire_results(batch, res, pod_cycle, t0)
        # feed the deadline model on the synchronous path too — it is the
        # mode whose pop the sizer actually cuts, so it must observe real
        # pop→processed spans (not run forever on its seeds)
        bucket = self.wire_sizer.bucket_for(len(batch))
        self.wire_sizer.update(bucket, self.now_fn() - t0)

    def _wire_conflict(self, batch: List[QueuedPodInfo], exc: Exception,
                       pod_cycle: int, t0: float) -> None:
        """Typed conflict verdict (fenced session / cross-client race):
        rejoin + backoffQ requeue, never a breaker count — identical for
        the synchronous path and a pipelined entry's claimed reply."""
        self.smetrics.commit_conflicts.inc(self.client_id)
        telemetry.event("conflict", client=self.client_id,
                        pods=len(batch), reason=str(exc)[:200])
        self._session_rejoin()
        self._requeue_wire_failure(batch, exc, pod_cycle, t0)

    def _wire_transport_failure(self, batch: List[QueuedPodInfo],
                                exc: Exception, pod_cycle: int,
                                t0: float,
                                batch_id: Optional[str] = None) -> None:
        """Transport-failure tail shared by both paths. Deliberately counts
        PERMANENT errors too: a deterministically broken device (version
        skew answering 4xx forever) should open the breaker and degrade to
        the oracle — the alternative is an endless requeue→fail loop with
        zero wire throughput. The breaker's lastError (/debug/circuit)
        keeps the bug visible."""
        self.breaker.record_failure(exc)
        if self.breaker.state == OPEN:
            # threshold crossed (or a failed half-open probe): degrade
            # THIS batch immediately rather than bouncing it off backoff
            self._accrue_degraded()
            self._schedule_degraded(batch, pod_cycle)
        else:
            # breaker still counting: rate-limited requeue — the pods
            # re-enter via the backoff queue with their attempt counts,
            # never hot-looping the active queue
            self._requeue_wire_failure(batch, exc, pod_cycle, t0,
                                       batch_id=batch_id)

    # ------------------------------------------------------ pipelined drain

    def _drain_wire_inflight(self) -> int:
        """Land every in-flight wire batch, oldest first — the wire twin of
        the ring's _drain_inflight: the synchronization point before
        fallback pods, degraded mode, and settlement judgment."""
        n = 0
        while self._wire_inflight:
            n += self._drain_oldest_wire()
        return n

    def _drain_oldest_wire(self) -> int:
        """Claim and process the OLDEST in-flight batch's reply. Replies
        arriving out of order are matched by batchId inside the pipeline's
        completion router; recovery (stale resync + re-send, conflict
        rejoin, breaker/requeue) runs here on the scheduling thread with
        semantics identical to the synchronous path."""
        entry = self._wire_inflight.popleft()
        self.smetrics.wire_inflight.set(value=len(self._wire_inflight))
        batch, pod_cycle, t0 = entry.qps, entry.pod_cycle, entry.t0
        t_wait0 = self.now_fn()
        try:
            try:
                res = self._wire_pipeline.claim(entry.batch_id)
                # adopt the reply's epoch/session only when no resync or
                # rejoin happened since this batch was SUBMITTED (the sync
                # era matches): an earlier entry's drain may have moved to
                # a fresh incarnation/session while this (older) reply was
                # already complete — re-adopting its stamps would cost a
                # spurious second full resync on the next push, or restore
                # a superseded sessionGen that then reads as a zombie
                if entry.era == self._wire_sync_era:
                    ep = res.get("epoch")
                    if ep:
                        self._device_epoch = ep
                        self._session_gen = res.get("sessionGen",
                                                    self._session_gen)
            except StaleEpochError as exc:
                # the device restarted (or a fabric failover promoted a
                # fresh standby) while this batch was in flight: re-seed
                # via the existing full resync — unless an earlier entry's
                # drain ALREADY resynced to exactly this epoch (K in-flight
                # batches all bounce off the same restart; one O(cluster)
                # resync suffices) — then re-send the SAME logical batch
                # (same idempotent batchId — nothing can double-commit)
                # through the bounded stale-retry loop
                if not (exc.epoch and exc.epoch == self._device_epoch):
                    self._full_resync(exc.epoch)
                self._restamp_batch_payload(entry.payload)
                res = self._send_batch_payload(entry.payload)
        except ConflictError as exc:
            self._wire_conflict(batch, exc, pod_cycle, t0)
            return len(batch)
        except DeviceServiceError as exc:
            # the in-flight batch died with its transport (replica loss,
            # torn stream, retry budget exhausted): the typed poison —
            # requeue via backoffQ exactly like in-process ring poison,
            # zero replays thanks to the per-client idempotent batchId
            telemetry.event("pipeline_poison", batchId=entry.batch_id,
                            pods=len(batch),
                            error=f"{type(exc).__name__}: {exc}"[:200])
            self._wire_transport_failure(batch, exc, pod_cycle, t0,
                                         batch_id=entry.batch_id)
            return len(batch)
        wait = self.now_fn() - t_wait0
        self.breaker.record_success()
        self._note_device_time(res, len(batch), entry.batch_id,
                               self.now_fn() - entry.t_sent)
        self._process_wire_results(batch, res, pod_cycle, t0)
        # stall-aware sizing, the in-process ring's controller: the span
        # fed is the batch's SERVICE time (submit → claimed), not its full
        # pop→processed attempt latency — a pipelined batch deliberately
        # dwells ~K cycles in the ring, and feeding that dwell into the
        # a+b·B fit reads as per-pod cost and collapses the target (a
        # measured 2.5x wire-throughput loss). The claim-blocked residual
        # still feeds the stall model, capping the batch where wire
        # latency outruns the overlapped host window.
        bucket = self.wire_sizer.bucket_for(len(batch))
        self.wire_sizer.update(bucket, self.now_fn() - entry.t_sent)
        self.wire_sizer.update_wait(bucket, wait)
        return len(batch)

    def _note_device_time(self, res: dict, pods: int, batch_id: str,
                          rtt_s: float) -> None:
        """Attribute the server-echoed per-batch device time against this
        client's round trip: the residual (rtt − server device time) is the
        TRANSPORT dwell — serialization, the wire, and (pipelined) ring
        residency — which no server-side profiler can see. One global read
        when the profiler is off or the server didn't echo (older server:
        degrade silently, same rule as every wire feature)."""
        rec = telemetry.get()
        if rec is None:
            return
        dt = res.get("deviceTime")
        if not isinstance(dt, dict):
            return
        try:
            exec_s = float(dt.get("execMs") or 0.0) / 1e3
            fetch_s = float(dt.get("fetchMs") or 0.0) / 1e3
            device_s = float(dt.get("deviceMs") or 0.0) / 1e3
        except (TypeError, ValueError):
            return
        transport_s = max(0.0, rtt_s - device_s)
        rec.dispatch_ledger.record_phases(
            "wire_schedule_batch", str(self.wire_sizer.bucket_for(pods)),
            dwell_s=transport_s, exec_s=exec_s, fetch_s=fetch_s,
            wait_s=max(rtt_s, device_s), batch_id=batch_id, pods=pods)
        telemetry.event("wire_device_time", batchId=batch_id,
                        device_ms=round(device_s * 1e3, 3),
                        transport_ms=round(transport_s * 1e3, 3))

    def _build_batch_payload(self, batch: List[QueuedPodInfo]) -> dict:
        """The ScheduleBatch request for one logical batch, stamped with a
        fresh idempotent batchId — the one payload shape shared by the
        synchronous path, the pipelined lanes, and stale-epoch re-sends."""
        from ..ops.tiebreak import seeds_for
        from .claim_mask import wire_claims_for_batch

        payload = {"apiVersion": API_VERSION,
                   "pods": [to_wire(qp.pod) for qp in batch],
                   "tieSeeds": [int(s) for s in seeds_for(batch)],
                   "batchId": f"{self._batch_id_prefix}-{next(self._batch_ids)}"}
        self._stamp_session(payload)
        claims = wire_claims_for_batch(self.store, [qp.pod for qp in batch])
        if claims:
            payload["claims"] = claims
        tp = tracing.format_traceparent()
        if tp:
            payload["traceparent"] = tp
        if self._device_epoch:
            payload["expectEpoch"] = self._device_epoch
        return payload

    def _send_batch_payload(self, payload: dict) -> dict:
        """Send one batch payload with the bounded stale-epoch recovery
        loop; commits epoch/session learned from the response. Runs on the
        SCHEDULING thread only (resync/rejoin mutate scheduler state)."""
        # device restarted between the delta push and this batch (or again
        # mid-recovery — a crash-looping sidecar): each stale answer costs
        # one cheap full resync, bounded so a restart storm falls through to
        # the breaker instead of spinning here
        stale_retries = 0
        while True:
            try:
                res = self.client.schedule_batch(payload)
                break
            except StaleEpochError as exc:
                stale_retries += 1
                if stale_retries > 2:
                    raise
                self._full_resync(exc.epoch)
                self._restamp_batch_payload(payload)
        self._device_epoch = res.get("epoch", self._device_epoch)
        self._session_gen = res.get("sessionGen", self._session_gen)
        return res

    def _restamp_batch_payload(self, payload: dict) -> None:
        """Refresh a payload's epoch/session stamps after a resync or
        rejoin changed them (the batchId stays — same logical batch)."""
        if self._device_epoch:
            payload["expectEpoch"] = self._device_epoch
        else:
            payload.pop("expectEpoch", None)
        self._stamp_session(payload)

    def _schedule_degraded(self, batch: List[QueuedPodInfo], pod_cycle: int) -> None:
        telemetry.event("degrade", client=self.client_id, pods=len(batch),
                        reason="wire breaker open")
        self.degraded_pods += len(batch)
        self.cache.update_snapshot(self.snapshot)
        for qp in batch:
            self.schedule_one_pod(qp, pod_cycle)

    def _requeue_wire_failure(self, batch: List[QueuedPodInfo],
                              exc: Exception, pod_cycle: int, t0: float,
                              batch_id: Optional[str] = None) -> None:
        telemetry.event("requeue", client=self.client_id, pods=len(batch),
                        batchId=batch_id,
                        error=f"{type(exc).__name__}: {exc}"[:200])
        for qp in batch:
            fwk = self.framework_for_pod(qp.pod)
            self.metrics.inc("schedule_attempts")
            self.metrics.inc("errors")
            self.smetrics.observe_attempt(
                "error", fwk.profile_name, self.now_fn() - t0)
            self._handle_scheduling_failure(
                fwk, self._new_cycle_state(), qp,
                Status.error(f"device service: {exc}"), Diagnosis(), pod_cycle)

    def _invalidate_node(self, node_name: str) -> None:
        """Force ``node_name``'s row back through the delta channel: the
        device adopted a placement the host is rejecting, and the host
        generation did NOT advance (nothing was assumed), so without this
        the server would keep the phantom commit forever — its sync skips
        rows whose generation matches and its mirror already holds the
        adopted state. Bumping the cache generation makes the next push
        re-send host truth; the server's content diff then repairs the row
        (the wire twin of TPUScheduler's ``_uploaded_gen`` pop)."""
        from ..framework.types import next_generation

        with self.cache._lock:
            ni = self.cache.nodes.get(node_name)
            if ni is not None:
                ni.generation = next_generation()
                # the incremental snapshot walks the dirty set, not raw
                # generations — without this the bump is never revisited
                self.cache._dirty.add(node_name)
        self._sent_gens.pop(node_name, None)

    def _process_wire_results(self, batch: List[QueuedPodInfo], res: dict,
                              pod_cycle: int, t0: float) -> None:
        # the whole wire commit (binds + requeues) coalesces its queue
        # moves, and the winners land through the batched commit engine —
        # the same commit data plane the in-process path runs
        with self.queue.coalesce_moves():
            self._process_wire_results_coalesced(batch, res, pod_cycle, t0)

    def _process_wire_results_coalesced(self, batch: List[QueuedPodInfo],
                                        res: dict, pod_cycle: int,
                                        t0: float) -> None:
        from ..framework.plugins.coscheduling import pod_group_key
        from .commit_plane import BindItem

        # ledger: the reply is claimed — the batch leaves the wire ring and
        # enters the host commit tail
        latency_ledger.transition_many(
            [qp.pod.key() for qp in batch], "commit.host")

        bind_items: List[BindItem] = []
        # hint-screen scaffolding, shared by every failed pod in the batch
        hint_names = hint_slot_of = None
        # gang all-or-nothing: a gang with any unplaced member is rejected
        # WHOLE — placed members surrender their slots instead of parking a
        # partial gang at Permit (mirror of the in-process _judge_gangs)
        gang_rejected: Dict[int, str] = {}
        groups: Dict[str, List[int]] = {}
        slice_groups: Dict[str, List[int]] = {}
        from ..ops.slice import is_slice_pod
        from .batch import SLICE_PLAN_OK_BIT

        # device over-quota screen verdicts (echoed words): a flagged winner
        # surrenders its placement and requeues through the quota gate —
        # the host ledger stays authoritative, so staleness only retries
        from ..ops.quota import QUOTA_OK_BIT, QUOTA_SCREEN_BIT

        quota_rejected: set = set()
        for i, r in enumerate(res["results"]):
            w = int(r.get("quota") or 0)
            if (r.get("nodeName") and (w & QUOTA_SCREEN_BIT)
                    and not (w & QUOTA_OK_BIT)):
                quota_rejected.add(i)
        for i, qp in enumerate(batch):
            gkey = pod_group_key(qp.pod)
            if gkey is not None:
                if is_slice_pod(qp.pod):
                    slice_groups.setdefault(gkey, []).append(i)
                else:
                    groups.setdefault(gkey, []).append(i)
        for gkey, idxs in groups.items():
            # a quota-screened member is unlandable: all-or-nothing means
            # the whole gang surrenders (never half-admitted past quota)
            if any(not res["results"][i].get("nodeName")
                   or i in quota_rejected for i in idxs):
                for i in idxs:
                    gang_rejected[i] = gkey
                plugin = self.framework_for_pod(
                    batch[idxs[0]].pod).plugin("Coscheduling")
                if plugin is not None:
                    plugin.reject_gang(gkey, "incomplete")
        # slice gangs, the wire twin of _judge_slice_gangs: verdict from the
        # reply alone (every member placed ⟺ the pinned window landed), the
        # echoed verdict word splitting plan-infeasible from lost-in-flight
        for gkey, idxs in slice_groups.items():
            now = self.now_fn()
            if all(res["results"][i].get("nodeName") and i not in quota_rejected
                   for i in idxs):
                telemetry.event("slice_assign", client=self.client_id,
                                gang=gkey, members=len(idxs))
                self.smetrics.slice_wait_duration.observe(
                    now - t0, "scheduled")
                continue
            plan_ok = all(
                res["results"][i].get("slice", SLICE_PLAN_OK_BIT)
                & SLICE_PLAN_OK_BIT for i in idxs)
            reason = "incomplete" if plan_ok else "infeasible"
            telemetry.event("slice_reject", client=self.client_id,
                            gang=gkey, members=len(idxs), reason=reason)
            self.smetrics.slice_wait_duration.observe(now - t0, "rejected")
            for i in idxs:
                gang_rejected[i] = gkey
            fwk = self.framework_for_pod(batch[idxs[0]].pod)
            plugin = fwk.plugin("Coscheduling")
            if plugin is not None:
                plugin.reject_gang(gkey, reason)
            sp = fwk.plugin("SlicePacking")
            if sp is not None:
                # a rejected gang's oracle plan (if any) must not keep its
                # node reservations pinned across the retry
                sp.forget_gang(gkey)
        for i, (qp, r) in enumerate(zip(batch, res["results"])):
            fwk = self.framework_for_pod(qp.pod)
            self.metrics.inc("schedule_attempts")
            node_name = r.get("nodeName")
            if r.get("conflict") and i not in gang_rejected:
                # another replica owns the pod (or won the capacity): the
                # typed verdict maps to a rate-limited backoffQ requeue —
                # by the retry either the winner's bind is visible (pod
                # skipped at pop) or this replica gets a clean shot
                self.smetrics.commit_conflicts.inc(self.client_id)
                telemetry.event("conflict", client=self.client_id,
                                pod=qp.pod.key(),
                                reason=(r.get("error") or "raced")[:200])
                self.metrics.inc("errors")
                self.smetrics.observe_attempt(
                    "error", fwk.profile_name, self.now_fn() - t0)
                self._handle_scheduling_failure(
                    fwk, self._new_cycle_state(), qp,
                    Status.error(
                        f"commit conflict: {r.get('error') or 'raced'}"),
                    Diagnosis(), pod_cycle)
                continue
            if i in gang_rejected:
                if node_name:
                    # the device already adopted this member's placement;
                    # surrendering it must re-send the node's host truth
                    self._invalidate_node(node_name)
                d = Diagnosis(unschedulable_plugins={"Coscheduling"})
                d.unschedulable_plugins.update(
                    r.get("unschedulablePlugins") or ())
                self._handle_scheduling_failure(
                    fwk, self._new_cycle_state(), qp, Status.unschedulable(
                        f'gang "{gang_rejected[i]}" could not be fully '
                        "placed"), d, pod_cycle)
                self.smetrics.observe_attempt(
                    "unschedulable", fwk.profile_name, self.now_fn() - t0)
                continue
            if i in quota_rejected:
                # the device adopted the placement before the screen flagged
                # it: surrender the slot and requeue through the quota gate
                # (host ledger re-admits once headroom is real)
                from ..framework.plugins.quota import ERR_REASON_QUOTA_EXCEEDED
                if node_name:
                    self._invalidate_node(node_name)
                self._handle_scheduling_failure(
                    fwk, self._new_cycle_state(), qp, Status.unresolvable(
                        f'{ERR_REASON_QUOTA_EXCEEDED}: namespace '
                        f'"{qp.pod.meta.namespace}" over quota at decision '
                        "time (device screen)"),
                    Diagnosis(unschedulable_plugins={"QuotaAdmission"}),
                    pod_cycle)
                self.smetrics.observe_attempt(
                    "unschedulable", fwk.profile_name, self.now_fn() - t0)
                continue
            if node_name:
                if self.snapshot.get(node_name) is None:
                    # ghost placement: the device named a node the host no
                    # longer knows (a desync window the resync protocol
                    # hasn't closed yet) — error-requeue the pod instead of
                    # binding it to a nonexistent node
                    self.metrics.inc("errors")
                    self.smetrics.observe_attempt(
                        "error", fwk.profile_name, self.now_fn() - t0)
                    self._handle_scheduling_failure(
                        fwk, self._new_cycle_state(), qp,
                        Status.error(f"device placed pod on unknown node "
                                     f"{node_name}"), Diagnosis(), pod_cycle)
                    continue
                state = self._new_cycle_state()
                if qp.pod.spec.resource_claims or qp.pod.spec.volumes:
                    # Reserve allocates claims from PreFilter cycle state
                    # (and re-verifies the claims still exist) — exactly
                    # the in-process commit rule
                    _, pre_st = fwk.run_pre_filter_plugins(state, qp.pod)
                    if not pre_st.is_success():
                        # host rejected what the device adopted: re-send
                        # the node's truth on the next push
                        self._invalidate_node(node_name)
                        self.cache.update_snapshot(self.snapshot)
                        self.schedule_one_pod(qp, pod_cycle)
                        continue
                bind_items.append(BindItem(fwk, qp, qp.pod, node_name, state))
            else:
                d = Diagnosis()
                for name, plugin in (r.get("statuses") or {}).items():
                    reason = dict(ATTRIBUTION_ORDER).get(plugin, "unschedulable")
                    d.node_to_status[name] = Status.unschedulable(reason).with_plugin(plugin)
                d.unschedulable_plugins.update(r.get("unschedulablePlugins") or ())
                state = self._new_cycle_state()
                hint = r.get("preempt")
                if hint is not None:
                    # rebuild the screen over OUR node names: candidates the
                    # service listed pass, every other known node fails,
                    # unknown (post-snapshot) nodes stay permissive. A None
                    # candidate list means the service truncated (screen
                    # inexact): pass everything and keep only the ranked
                    # best as the preferred-node fast path.
                    from ..framework.plugins.defaultpreemption import DefaultPreemption

                    if hint_slot_of is None:  # loop-invariant: build once
                        hint_names = list(self._sent_gens)
                        hint_slot_of = {n: i for i, n in enumerate(hint_names)}
                    if hint.get("candidates") is None:
                        row = np.ones(len(hint_names), bool)
                    else:
                        row = np.zeros(len(hint_names), bool)
                        for n in hint["candidates"]:
                            if n in hint_slot_of:
                                row[hint_slot_of[n]] = True
                    state.write(DefaultPreemption.HINTS_KEY,
                                (row, hint_slot_of, hint.get("best")))
                self._handle_scheduling_failure(
                    fwk, state, qp, Status.unschedulable("no feasible node"),
                    d, pod_cycle)
                self.smetrics.observe_attempt(
                    "unschedulable", fwk.profile_name, self.now_fn() - t0)
        if bind_items:
            self.commit_plane.commit_bindings(bind_items, pod_cycle, t0)
            for item in bind_items:
                if item.outcome == "failed":
                    # host rejected what the device adopted: re-send the
                    # node's truth on the next push
                    self._invalidate_node(item.node_name)

    def run_until_settled(self, max_cycles: int = 100000, flush: bool = True,
                          idle_wait: float = 0.005, max_no_progress: int = 200) -> int:
        # the shared batched settle loop (Scheduler.run_batched_until_settled),
        # incl. the idle-wait backoff for flapping pods
        return self.run_batched_until_settled(
            max_cycles=max_cycles, flush=flush, idle_wait=idle_wait,
            max_no_progress=max_no_progress)

    def debug_sessions(self) -> dict:
        """/debug/sessions body: this replica's session identity plus the
        device service's whole session table (lease ages, per-client
        deltaSeq, in-flight hold counts) fetched over the wire."""
        out = {
            "enabled": True,
            "clientId": self.client_id,
            "sessionGen": self._session_gen,
            "sessionRejoins": self.session_rejoins,
            "haTakeovers": self.ha_takeovers,
            "heartbeatIntervalS": self.heartbeat_interval_s,
        }
        if getattr(self.client, "supports_sessions", False):
            try:
                out["service"] = self.client.sessions_dump()
            except DeviceServiceError as exc:
                out["service"] = {"error": f"{type(exc).__name__}: {exc}"}
        else:
            out["service"] = {"error": "transport lacks the sessions verb"}
        return out

    def debug_fabric(self) -> dict:
        """/debug/fabric body: the device-side HA fabric's replica table
        (active endpoint, per-endpoint health/breaker/epoch) plus the
        bounded failover journal; a single-endpoint transport reports
        enabled=False (no fabric in the path)."""
        dump = getattr(self.client, "dump", None)
        if dump is None:
            return {"enabled": False,
                    "endpoint": getattr(self.client, "endpoint", None)}
        return dump()

    def debug_circuit(self) -> dict:
        """/debug/circuit body: breaker state + resync/degradation story +
        the pipelined-transport occupancy."""
        out = self.breaker.dump()
        out.update({
            "enabled": True,
            "deviceEpoch": self._device_epoch,
            "resyncs": self.resyncs,
            "degradedPods": self.degraded_pods,
            "wirePipelineDepth": self.wire_pipeline_depth,
            "wireInflight": len(self._wire_inflight),
            "pipelinedBatches": self.pipelined_wire_batches,
            "duplicateReplies": (self._wire_pipeline.duplicate_replies
                                 if self._wire_pipeline is not None else 0),
            "retryPolicy": {
                "maxRetries": self.retry_policy.max_retries,
                "backoffBase": self.retry_policy.backoff_base,
                "backoffMax": self.retry_policy.backoff_max,
                "deadlineS": self.retry_policy.deadline_s,
            },
        })
        return out
