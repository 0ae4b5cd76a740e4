"""The Scheduler: wiring + the per-pod scheduling cycle.

Mirrors pkg/scheduler/scheduler.go (object + New wiring), eventhandlers.go
(informer → cache/queue routing, node-diff → ClusterEvent) and
schedule_one.go (the cycle: snapshot → PreFilter → Filter(+nominated 2-pass) →
(adaptive node sampling + rotation) → PreScore/Score → selectHost → assume →
Reserve → Permit → PreBind → Bind).

This is the *sequential oracle path* — semantically the reference scheduler.
The TPU batched path (backend/) replaces schedule_pod's filter+score middle
with one device call over a pod micro-batch; everything around it (queue,
cache, assume, bind, failure handling) is shared.
"""

from __future__ import annotations

import random
import threading
import time
from typing import Dict, List, Optional, Tuple

from ..api.types import Node, Pod
from ..apiserver.store import ADDED, DELETED, MODIFIED, ClusterStore
from ..cache import Cache, Snapshot
from ..framework import interface as fw
from ..framework.interface import CycleState, Status
from ..framework.runtime import Framework
from ..framework.types import (
    ADD,
    Diagnosis,
    FitError,
    NODE,
    QueuedPodInfo,
    UPDATE_NODE_ALLOCATABLE,
    UPDATE_NODE_CONDITION,
    UPDATE_NODE_LABEL,
    UPDATE_NODE_TAINT,
    ClusterEvent,
)
from ..metrics import SchedulerMetrics, latency_ledger
from ..queue import SchedulingQueue
from ..queue import events as qevents
from ..utils.events import EventRecorder, TYPE_NORMAL, TYPE_WARNING
from ..utils.trace import Trace

MIN_FEASIBLE_NODES_TO_FIND = 100           # schedule_one.go:52
MIN_FEASIBLE_NODES_PERCENTAGE_TO_FIND = 5  # :56


import dataclasses as _dc


@_dc.dataclass
class WaitingPod:
    """One Permit-parked pod (runtime/waiting_pods_map.go waitingPod):
    binding resumes on allow, unreserve+failure on reject, and the
    housekeeping sweep rejects it once ``deadline`` passes."""

    fwk: "Framework"
    state: CycleState
    pod: Pod
    node_name: str
    pod_cycle: int
    t0: float
    deadline: Optional[float] = None
    plugin: str = ""  # the plugin that voted WAIT


class WaitingPods:
    """The Handle surface Permit plugins use to release or reject parked
    pods (interface.go Handle.IterateOverWaitingPods/GetWaitingPod) —
    Coscheduling drives whole-gang release/teardown through this."""

    def __init__(self, sched: "Scheduler"):
        self._sched = sched

    def iterate(self) -> List[Tuple[str, Pod]]:
        return [(k, wp.pod) for k, wp in self._sched.waiting_pods.items()]

    def allow(self, pod_key: str) -> bool:
        return self._sched.allow_waiting_pod(pod_key)

    def reject(self, pod_key: str, reason: str = "rejected while waiting on permit",
               plugins: Tuple[str, ...] = ()) -> bool:
        return self._sched.reject_waiting_pod(pod_key, reason=reason,
                                              plugins=plugins)


class _SyncCounters(dict):
    """The scheduler's coarse outcome counters (scheduled/attempts/errors),
    with an atomic ``inc``: the commit worker (backend/commit_plane.py)
    lands batch outcomes concurrently with the scheduling thread's precheck
    failures, and a bare ``d[k] += 1`` from two threads can lose updates.
    Plain dict reads everywhere else are unchanged."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self._mu = threading.Lock()

    def inc(self, key: str, n: int = 1) -> None:
        with self._mu:
            self[key] = self.get(key, 0) + n


class Scheduler:
    def __init__(
        self,
        store: ClusterStore,
        profiles: Optional[Dict[str, Framework]] = None,
        percentage_of_nodes_to_score: int = 0,
        seed: int = 0,
        pod_initial_backoff: float = 1.0,
        pod_max_backoff: float = 10.0,
        assume_ttl: float = 30.0,
        now_fn=time.monotonic,
        extenders=None,
        metrics=None,
        recorder=None,
        informer_factory=None,
    ):
        self.store = store
        self.informer_factory = informer_factory
        self.extenders = list(extenders or [])
        self.smetrics = metrics if metrics is not None else SchedulerMetrics()
        self.recorder = recorder if recorder is not None else EventRecorder()
        self.trace_threshold_s = 0.1  # LogIfLong(100ms), schedule_one.go:313
        self.now_fn = now_fn
        self.cache = Cache(ttl=assume_ttl, now_fn=now_fn)
        self.snapshot = Snapshot()
        self.percentage_of_nodes_to_score = percentage_of_nodes_to_score
        self.next_start_node_index = 0
        self.rng = random.Random(seed)
        self.metrics: Dict[str, int] = _SyncCounters(
            schedule_attempts=0, scheduled=0, unschedulable=0, errors=0)
        # external-change sequence for the commit data plane's carry gate:
        # bumped (under _ext_mu — a lost bump would silently keep a stale
        # device carry) on every event that can change NODE-side truth the
        # device mirrors — node add/update/remove, and bound-pod add/update/
        # delete NOT caused by this scheduler's own commits. New PENDING
        # pods don't bump: they enter the queue, not the node tensors.
        self._ext_mu = threading.Lock()
        self._external_events = 0
        self._commit_plane = None  # built lazily (backend import is heavy)
        self.waiting_pods: Dict[str, WaitingPod] = {}
        self._reject_depth = 0  # nested teardown guard (reject_waiting_pod)
        self._last_cleanup = now_fn()
        self._last_unsched_flush = now_fn()
        self._reclaim_drainer = None  # built on first quota reclaim evict

        # Profiles are specs (plugin_config/plugin_args/registry dicts), NOT
        # pre-built Frameworks: the Scheduler owns the handle context, so
        # plugins always get a live snapshot_fn/client (profile.NewMap analog).
        handle_base = {
            "snapshot_fn": lambda: self.snapshot.list(),
            "ns_labels_fn": store.ns_labels,
            "client": store,
            "extenders": self.extenders,
            "metrics": self.smetrics,
            "now_fn": now_fn,
            "waiting_pods": WaitingPods(self),
        }
        specs = profiles or {"default-scheduler": {}}
        self.profiles: Dict[str, Framework] = {}
        for name, spec in specs.items():
            if isinstance(spec, Framework):  # escape hatch for tests
                self.profiles[name] = spec
                continue
            self.profiles[name] = Framework(
                dict(handle_base),
                plugin_config=spec.get("plugin_config"),
                plugin_args=spec.get("plugin_args"),
                registry=spec.get("registry"),
                profile_name=name,
            )

        event_map = {}
        for fwk in self.profiles.values():
            for ev, plugins in fwk.cluster_event_map().items():
                event_map.setdefault(ev, set()).update(plugins)
        first = next(iter(self.profiles.values()))
        from ..framework.plugins.coscheduling import pod_group_key
        from ..framework.plugins.names import QUOTA_ADMISSION

        self.queue = SchedulingQueue(
            less_key=first.queue_sort_key(),
            initial_backoff=pod_initial_backoff,
            max_backoff=pod_max_backoff,
            cluster_event_map=event_map,
            now_fn=now_fn,
            metrics=self.smetrics,
            gang_key_fn=pod_group_key,
            pre_enqueue_fn=self._pre_enqueue_gate,
            ns_weight_fn=self._ns_fair_weight,
        )
        # targeted quota-release moves: a released charge wakes exactly the
        # gated pods the freed headroom admits (shadow-ledger gate), never
        # the whole parked backlog of a still-over-quota namespace. Every
        # profile's QuotaAdmission shares ONE ledger — usage is cluster
        # state, and Reserve charges land in the pod's own profile's
        # instance while release/fair-share read through _quota_plugin().
        shared_quota = None
        for fwk in self.profiles.values():
            plugin = fwk.plugin(QUOTA_ADMISSION)
            if plugin is not None:
                plugin.on_release = self._on_quota_release
                plugin.on_evict = self._quota_evict
                if shared_quota is None:
                    shared_quota = plugin
                else:
                    plugin.share_ledger(shared_quota)
        self._add_all_event_handlers()

    # ------------------------------------------------------- quota admission

    def _quota_plugin(self, pod: Optional[Pod] = None):
        """The pod's profile's QuotaAdmission, else ANY profile's (the
        ledger is shared, so instances are interchangeable — and a custom
        first profile without the plugin must not hide the others')."""
        from ..framework.plugins.names import QUOTA_ADMISSION

        fwk = (self.profiles.get(pod.spec.scheduler_name)
               if pod is not None else None)
        if fwk is not None:
            plugin = fwk.plugin(QUOTA_ADMISSION)
            if plugin is not None:
                return plugin
        for fwk in self.profiles.values():
            plugin = fwk.plugin(QUOTA_ADMISSION)
            if plugin is not None:
                return plugin
        return None

    def _pre_enqueue_gate(self, pod: Pod):
        """SchedulingQueue admission gate: the pod's profile's PreEnqueue
        plugins. None = admit; a non-success Status = park gated."""
        fwk = self.profiles.get(pod.spec.scheduler_name)
        if fwk is None:
            return None
        status = fwk.run_pre_enqueue_plugins(pod)
        return None if status.is_success() else status

    def _ns_fair_weight(self, ns: str) -> Optional[float]:
        """Fair-share weight for the queue's DRR layer (None = the
        namespace is not a tenant and shares the default bucket)."""
        plugin = self._quota_plugin()
        return plugin.weight_for(ns) if plugin is not None else None

    def _on_quota_release(self, ns: str) -> int:
        plugin = self._quota_plugin()
        if plugin is None:
            return 0
        return self.queue.move_gated_pods(
            namespace=ns, plugin=plugin.name(),
            admit_fn=plugin.shadow_admitter(ns))

    def _notify_quota_pod_bound(self, pod: Pod) -> None:
        """A pod observed bound (assumed-confirmation is a no-op; an
        external binder's pod still charges the namespace ledger)."""
        plugin = self._quota_plugin(pod)
        if plugin is not None:
            plugin.pod_observed_bound(pod)

    def _notify_quota_pod_deleted(self, pod: Pod) -> None:
        """Release the pod's quota charge (if any) BEFORE the queue's
        reactivation wave runs, so the wave's gate re-check sees the freed
        headroom."""
        plugin = self._quota_plugin(pod)
        if plugin is not None:
            plugin.pod_deleted(pod)

    def _quota_evict(self, pods: List[Pod], reason: str) -> int:
        """Borrower preemption for the quota reclaim pass: whole-gang
        eviction through the drain orchestrator (delete + recreate unbound
        + targeted EVICTION queue move), built lazily on first reclaim."""
        orch = self._reclaim_drainer
        if orch is None:
            from ..controllers.drain import DrainOrchestrator

            orch = DrainOrchestrator(self.store, metrics=self.smetrics,
                                     queue=self.queue, now_fn=self.now_fn)
            self._reclaim_drainer = orch
        return orch.evict_pods(pods, reason=reason)

    # ----------------------------------------------------------- event wiring

    def _add_all_event_handlers(self) -> None:
        """eventhandlers.go:249 addAllEventHandlers.

        With an informer factory, events arrive through the shared-informer
        bus (reflector → DeltaFIFO → fan-out) and the loop pumps it each
        cycle. Without one, handlers sit directly on the store with the
        initial LIST replayed as ADDs (same ListAndWatch contract,
        reflector.go:254, minus the queueing)."""
        if self.informer_factory is not None:
            evmap = {"add": ADDED, "update": MODIFIED, "delete": DELETED}
            pod_inf = self.informer_factory.informer_for("Pod")
            node_inf = self.informer_factory.informer_for("Node")
            pod_inf.add_event_handler(lambda e, old, new: self._on_pod_event(evmap[e], old, new))
            node_inf.add_event_handler(lambda e, old, new: self._on_node_event(evmap[e], old, new))
            self.informer_factory.wait_for_cache_sync()
            # dynamic plugin-requested kinds (SchedulingQuota, PodGroup …)
            # have no informers — they ride the store's direct handler bus in
            # BOTH topologies. Skipping them here strands gated pods forever
            # on the production server: a quota raise would fire no queue
            # move, and gated pods are exempt from the timeout flush.
            self._add_dynamic_event_handlers()
            return
        for node in list(self.store.nodes.values()):
            self._on_node_event(ADDED, None, node)
        for pod in list(self.store.pods.values()):
            self._on_pod_event(ADDED, None, pod)
        self.store.add_event_handler("Pod", self._on_pod_event)
        self.store.add_event_handler("Node", self._on_node_event)
        self._add_dynamic_event_handlers()

    def _add_dynamic_event_handlers(self) -> None:
        """eventhandlers.go:249's dynamic-informer arm: a plugin that
        registered interest in a GVK the static wiring doesn't cover (e.g.
        a CRD-served kind) gets a handler that re-activates pods it failed —
        the extension story that makes plugin-requested custom kinds
        meaningful."""
        from ..framework.types import ClusterEvent, ALL

        static = {"Pod", "Node"}
        wanted = set()
        for fwk in self.profiles.values():
            for ev in fwk.cluster_event_map():
                kind = str(ev.resource)
                if kind not in static and not ev.is_wildcard():
                    wanted.add((kind, ev.resource))
        for kind, resource in wanted:
            def _handler(event, old, new, _res=resource):
                self.queue.move_all_to_active_or_backoff_queue(
                    ClusterEvent(_res, ALL))
            # registration is unconditional: handlers for kinds not served
            # yet simply never fire until a CRD starts serving the kind
            self.store.add_event_handler(kind, _handler)

    def _on_pod_event(self, event: str, old: Optional[Pod], new: Optional[Pod]) -> None:
        if event == ADDED:
            if new.spec.node_name:
                self._bump_external()  # pre-bound pod: external node truth
                self.cache.add_pod(new)
                self._notify_quota_pod_bound(new)
                self.queue.assigned_pod_updated_or_added(new)
            elif self._responsible_for(new):
                self.queue.add(new)
        elif event == MODIFIED:
            if new.spec.node_name:
                if old is not None and not old.spec.node_name:
                    if not self.cache.is_assumed(new.key()):
                        # an EXTERNAL binder's pod (a peer replica, a test
                        # poking the store) changes node truth; confirming
                        # our own assume does not — the device carry
                        # already holds that placement
                        self._bump_external()
                    self.cache.add_pod(new)  # binding confirmation
                    self._notify_quota_pod_bound(new)
                    self.queue.assigned_pod_updated_or_added(new)
                else:
                    self._bump_external()
                    self.cache.update_pod(old, new)
                    self.queue.assigned_pod_updated_or_added(new)
            elif self._responsible_for(new):
                self.queue.update(old, new)
        elif event == DELETED:
            if old is not None:
                self.smetrics.clear_unschedulable(old.key())
                # quota release first: the POD_DELETE reactivation wave
                # below must re-gate against the freed headroom
                self._notify_quota_pod_deleted(old)
            if old is not None and old.spec.node_name:
                self._remove_bound_pod(old)
                self.queue.move_all_to_active_or_backoff_queue(qevents.POD_DELETE)
            elif old is not None:
                self.queue.delete(old)
            if old is not None:
                self._notify_gang_pod_deleted(old)

    def _remove_bound_pod(self, pod: Pod) -> None:
        """A bound pod left the store: an external node-truth change, then
        its removal from the cache."""
        self._bump_external()
        self.cache.remove_pod(pod)

    def _notify_gang_pod_deleted(self, pod: Pod) -> None:
        """PodGroup lifecycle on member deletion: the Coscheduling plugin's
        bound-count cache must decrement (and GC when the gang empties) or
        a re-created gang is judged against stale quorum."""
        from ..framework.plugins.coscheduling import pod_group_key

        if pod_group_key(pod) is None or not self._responsible_for(pod):
            return
        plugin = self.framework_for_pod(pod).plugin("Coscheduling")
        if plugin is not None:
            plugin.pod_deleted(pod)

    def _on_node_event(self, event: str, old: Optional[Node], new: Optional[Node]) -> None:
        self._bump_external()  # any node event invalidates the device carry
        if event == ADDED:
            self.smetrics.node_events.inc("add")
            self.cache.add_node(new)
            # targeted capacity wake-up: pods parked Unschedulable on
            # resource pressure (NodeResourcesFit registers NODE|ADD)
            # reactivate the moment new capacity joins the cluster
            self.queue.move_all_to_active_or_backoff_queue(qevents.NODE_ADD)
        elif event == MODIFIED:
            self.smetrics.node_events.inc("update")
            self.cache.update_node(new)
            ev = self._node_scheduling_properties_change(old, new)
            if ev is not None:
                self.queue.move_all_to_active_or_backoff_queue(ev)
        elif event == DELETED:
            self.smetrics.node_events.inc("delete")
            self.cache.remove_node(old.meta.name)

    @staticmethod
    def _node_scheduling_properties_change(old: Node, new: Node) -> Optional[ClusterEvent]:
        """eventhandlers.go:423: minimal ClusterEvent from a node diff."""
        if old is None:
            return qevents.NODE_ADD
        if old.status.allocatable != new.status.allocatable:
            return ClusterEvent(NODE, UPDATE_NODE_ALLOCATABLE, "NodeAllocatableChange")
        if old.meta.labels != new.meta.labels:
            return ClusterEvent(NODE, UPDATE_NODE_LABEL, "NodeLabelChange")
        if old.spec.taints != new.spec.taints or old.spec.unschedulable != new.spec.unschedulable:
            return ClusterEvent(NODE, UPDATE_NODE_TAINT, "NodeTaintChange")
        if old.status.ready != new.status.ready:
            return ClusterEvent(NODE, UPDATE_NODE_CONDITION, "NodeConditionChange")
        return None

    def _responsible_for(self, pod: Pod) -> bool:
        return pod.spec.scheduler_name in self.profiles

    def framework_for_pod(self, pod: Pod) -> Framework:
        return self.profiles[pod.spec.scheduler_name]

    # -------------------------------------------------------- commit plane

    @property
    def commit_plane(self):
        """The batched commit engine (backend/commit_plane.py), built on
        first use — plain oracle schedulers never pay the backend import."""
        if self._commit_plane is None:
            from ..backend.commit_plane import CommitPlane

            self._commit_plane = CommitPlane(self)
        return self._commit_plane

    def _bump_external(self) -> int:
        """Record one external node-truth change (see _external_events);
        returns the sequence number it made."""
        with self._ext_mu:
            self._external_events += 1
            return self._external_events

    def external_change_seq(self) -> int:
        """Monotonic count of external node-truth changes — the commit data
        plane's carry gate compares snapshots of this across a pipelined
        chain instead of walking cache generations."""
        return self._external_events  # ktpu: unguarded-ok(monotonic int probe; a racing bump reads as a changed seq on the NEXT gate check — conservative chain break, never a missed change)

    # ----------------------------------------------------------- the cycle

    def schedule_one(self) -> bool:
        """One scheduling cycle (schedule_one.go:66). Returns False when the
        active queue is empty."""
        if self.informer_factory is not None:
            self.informer_factory.pump()
        self._periodic_housekeeping()
        qp = self.queue.pop()
        if qp is None:
            return False
        pod = self.store.get_pod(qp.pod.key())
        if pod is None or pod.spec.node_name or not self._responsible_for(pod):
            # skipPodSchedule (:285): deleted/bound meanwhile — close the
            # ledger entry the pop just transitioned (no-op when absent)
            latency_ledger.close_skipped(qp.pod.key(), pod)
            return True
        qp.pod = pod
        self.schedule_one_pod(qp, self.queue.scheduling_cycle)
        return True

    def schedule_one_pod(self, qp: QueuedPodInfo, pod_cycle: int) -> None:
        """Sequential scheduling of one pod: schedule_pod + failure handling +
        assume/bind tail. Shared by schedule_one and the batch fallback path."""
        pod = qp.pod
        fwk = self.framework_for_pod(pod)
        self.metrics.inc("schedule_attempts")
        state = self._new_cycle_state()
        t0 = self.now_fn()
        try:
            node_name = self.schedule_pod(fwk, state, pod, attempts=qp.attempts)
        except FitError as fe:
            self.smetrics.observe_attempt("unschedulable", fwk.profile_name, self.now_fn() - t0)
            self._handle_scheduling_failure(fwk, state, qp, Status.unschedulable(*fe.args), fe.diagnosis, pod_cycle)
            return
        except Exception as e:  # noqa: BLE001 — cycle errors re-enqueue the pod
            self.metrics.inc("errors")
            self.smetrics.observe_attempt("error", fwk.profile_name, self.now_fn() - t0)
            self._handle_scheduling_failure(fwk, state, qp, Status.error(str(e)), Diagnosis(), pod_cycle)
            return
        self.smetrics.scheduling_algorithm_duration.observe(self.now_fn() - t0, fwk.profile_name)
        self.assume_and_bind(fwk, state, qp, pod, node_name, pod_cycle, t0=t0)

    # plugin-metrics sampling period: the reference samples ~10% of attempts;
    # a sampled attempt pays per-(node, plugin) filter observes, which in
    # Python is a bigger relative cost than in Go, so the default is 1-in-20
    PLUGIN_METRICS_SAMPLE_PERIOD = 20

    def _new_cycle_state(self) -> CycleState:
        """CycleState with the plugin-metrics sampling decision made
        (extension-point totals are always recorded; per-plugin durations
        only on sampled cycles). Attempt 1 always samples, so short runs
        still surface per-plugin samples."""
        state = CycleState()
        # (attempts - 1) % period: attempt 1 always samples, and period=1
        # degrades to sample-everything instead of sample-nothing
        state.record_plugin_metrics = (
            (self.metrics["schedule_attempts"] - 1)
            % self.PLUGIN_METRICS_SAMPLE_PERIOD == 0)
        return state

    def assume_and_bind(self, fwk: Framework, state: CycleState, qp: QueuedPodInfo, pod: Pod, node_name: str, pod_cycle: int, t0: Optional[float] = None) -> None:
        """The post-decision tail shared by the sequential and TPU-batched
        paths: assume → Reserve → Permit → binding cycle."""
        if t0 is None:
            t0 = self.now_fn()
        # assume (schedule_one.go:734): next cycle sees this pod immediately;
        # the clone (with node_name set by assume_pod) is what every later
        # extension point receives, like the reference's assumedPod
        assumed = pod.clone()
        self.cache.assume_pod(assumed, node_name)
        fwk.nominator.delete_nominated_pod_if_exists(pod)

        status = fwk.run_reserve_plugins_reserve(state, assumed, node_name)
        if status.is_success():
            status = fwk.run_permit_plugins(state, assumed, node_name)
        if status.code == fw.WAIT:
            # park: stays assumed; binding resumes on allow_waiting_pod
            # (runtime/waiting_pods_map.go; WaitOnPermit schedule_one.go:199).
            # The WAIT plugin's timeout (clock-injected via now_fn) bounds
            # the park: the housekeeping sweep rejects expired waiters.
            from ..framework.runtime import DEFAULT_PERMIT_WAIT_S, PERMIT_TIMEOUT_KEY

            try:
                timeout = float(state.read(PERMIT_TIMEOUT_KEY))
            except KeyError:
                timeout = DEFAULT_PERMIT_WAIT_S
            self.waiting_pods[assumed.key()] = WaitingPod(
                fwk, state, assumed, node_name, pod_cycle, t0,
                deadline=self.now_fn() + timeout, plugin=status.plugin)
            latency_ledger.transition(assumed.key(), "gang.permit_park",
                                      namespace=assumed.meta.namespace,
                                      create=False)
            return
        if not status.is_success():
            fwk.run_reserve_plugins_unreserve(state, assumed, node_name)
            self.cache.forget_pod(assumed)
            self._handle_scheduling_failure(fwk, state, qp, status, Diagnosis(), pod_cycle)
            return

        self._binding_cycle(fwk, state, qp, assumed, node_name, pod_cycle, t0)

    def allow_waiting_pod(self, pod_key: str) -> bool:
        """Approve a Permit-parked pod: continue its binding cycle."""
        wp = self.waiting_pods.pop(pod_key, None)
        if wp is None:
            return False
        latency_ledger.transition(pod_key, "commit.host",
                                  namespace=wp.pod.meta.namespace,
                                  create=False)
        self._binding_cycle(wp.fwk, wp.state, QueuedPodInfo(pod=wp.pod),
                            wp.pod, wp.node_name, wp.pod_cycle, wp.t0)
        return True

    def reject_waiting_pod(self, pod_key: str,
                           reason: str = "pod rejected while waiting on permit",
                           plugins: Tuple[str, ...] = ()) -> bool:
        """Reject a parked pod: unreserve (which may cascade — a gang
        member's rejection tears down its siblings through Coscheduling's
        Unreserve), forget the assume, and requeue with the rejecting
        plugins attributed so event gating can reactivate it."""
        wp = self.waiting_pods.pop(pod_key, None)
        if wp is None:
            return False
        self._reject_depth += 1
        try:
            wp.fwk.run_reserve_plugins_unreserve(wp.state, wp.pod, wp.node_name)
            self.cache.forget_pod(wp.pod)
            diagnosis = Diagnosis(
                unschedulable_plugins=set(p for p in plugins if p))
            self._handle_scheduling_failure(
                wp.fwk, wp.state, QueuedPodInfo(pod=wp.pod),
                Status.unschedulable(reason), diagnosis, wp.pod_cycle)
            self.smetrics.observe_attempt(
                "unschedulable", wp.fwk.profile_name, self.now_fn() - wp.t0)
        finally:
            self._reject_depth -= 1
        # the forget released real capacity: pods parked on resource/port
        # fit can now succeed — the assumed pod's release is the moral
        # equivalent of an assigned-pod delete for queue gating. Fired once
        # per teardown, not per member: a whole-gang cascade (unreserve →
        # Coscheduling.reject_gang → nested rejects) re-enters this method,
        # and only the OUTERMOST frame pays the full-queue move.
        if self._reject_depth == 0:
            self.queue.move_all_to_active_or_backoff_queue(qevents.POD_DELETE)
        return True

    def _sweep_expired_waiting_pods(self, now: float) -> None:
        """WaitOnPermit timeout (waiting_pods_map.go per-pod timer, driven
        inline off the housekeeping tick): a parked pod past its deadline is
        rejected — for a gang member the WHOLE gang is torn down first so no
        partial gang survives the timeout."""
        expired = [(k, wp) for k, wp in self.waiting_pods.items()
                   if wp.deadline is not None and now >= wp.deadline]
        if not expired:
            return
        from ..framework.plugins.coscheduling import pod_group_key

        for key, wp in expired:
            if key not in self.waiting_pods:
                continue  # a gang cascade already rejected it
            gkey = pod_group_key(wp.pod)
            plugin = wp.fwk.plugin("Coscheduling") if gkey else None
            if gkey is not None and plugin is not None:
                plugin.reject_gang(gkey, "timeout")
            if key in self.waiting_pods:  # no cascade (bare framework)
                self.reject_waiting_pod(key, reason="permit wait timeout",
                                        plugins=(wp.plugin,))

    def _periodic_housekeeping(self, now: Optional[float] = None) -> None:
        """The reference's background tickers, driven inline: assume-expiry
        sweep (1s, cache.go:731) and the unschedulable-timeout flush (30s,
        scheduling_queue.go:463). ``now`` lets an override evaluate its own
        pre-sweep gates against the SAME clock read the sweep uses (a
        second read could cross the tick boundary the gate just tested)."""
        if now is None:
            now = self.now_fn()
        if now - self._last_cleanup >= 1.0:
            self._last_cleanup = now
            self._sweep_expired_waiting_pods(now)
            for pod in self.cache.cleanup(now):
                current = self.store.get_pod(pod.key())
                if current is not None and not current.spec.node_name:
                    self.queue.add(current)
            # cache-size + worker gauges ride the 1s sweep (the reference's
            # periodic updateSchedulerCacheSize / binding-goroutine gauges)
            nodes, pods, assumed = self.cache.stats()
            self.smetrics.sync_cache_gauges(nodes, pods, assumed)
            self.smetrics.goroutines.set("binding", value=len(self.waiting_pods))
            # WAL auto-compaction rides the 1s sweep: a durable store whose
            # log outgrew KTPU_WAL_COMPACT_LINES folds it into a snapshot
            # (no-op without an attached WAL or with the default-off gate)
            wal = getattr(self.store, "_wal", None)
            if wal is not None:
                wal.maybe_compact(self.store)
            # cohort quota reclaim-by-preemption rides the same sweep: a
            # lender whose pod parked on "cohort exhausted by loans" evicts
            # borrower pods newest-loan-first (cooldown + SLO breaker
            # paced inside the pass; no-op without recorded demand)
            quota = self._quota_plugin()
            if quota is not None:
                quota.run_reclaim(now)
        if now - self._last_unsched_flush >= 30.0:
            self._last_unsched_flush = now
            self.queue.flush_unschedulable_left_over()

    def _binding_cycle(self, fwk: Framework, state: CycleState, qp: QueuedPodInfo, assumed: Pod, node_name: str, pod_cycle: int, t0: Optional[float] = None) -> None:
        """(schedule_one.go:193) — synchronous here; the perf harness measures
        end-to-end anyway and the in-process store makes binds cheap."""
        latency_ledger.transition(assumed.key(), "bind",
                                  namespace=assumed.meta.namespace,
                                  create=False)
        status = fwk.run_pre_bind_plugins(state, assumed, node_name)
        if status.is_success():
            status = self._extenders_binding(assumed, node_name)
        if status is None:
            status = fwk.run_bind_plugins(state, assumed, node_name)
        if not status.is_success():
            fwk.run_reserve_plugins_unreserve(state, assumed, node_name)
            self.cache.forget_pod(assumed)
            self._handle_scheduling_failure(fwk, state, qp, status, Diagnosis(), pod_cycle)
            return
        self.cache.finish_binding(assumed)
        self.metrics.inc("scheduled")
        self.smetrics.clear_unschedulable(assumed.key())
        latency_ledger.close(assumed.key(), "scheduled")
        self.smetrics.observe_attempt(
            "scheduled", fwk.profile_name,
            self.now_fn() - t0 if t0 is not None else 0.0,
        )
        self.recorder.eventf(
            assumed.key(), TYPE_NORMAL, "Scheduled", "Binding",
            f"Successfully assigned {assumed.key()} to {node_name}",
        )
        fwk.run_post_bind_plugins(state, assumed, node_name)

    def _extenders_binding(self, pod: Pod, node_name: str) -> Optional[Status]:
        """(schedule_one.go:774) first interested binder extender wins; None
        means no extender claimed the bind (fall through to bind plugins)."""
        for ext in self.extenders:
            if ext.is_binder() and ext.is_interested(pod):
                try:
                    ext.bind(pod, node_name)
                    return Status()
                except Exception as e:  # noqa: BLE001 — bind failure fails the cycle
                    return Status.error(f"extender bind: {e}")
        return None

    def schedule_pod(self, fwk: Framework, state: CycleState, pod: Pod,
                     attempts: int = 0) -> str:
        """(schedule_one.go:311) returns the chosen node name or raises FitError."""
        from ..utils import tracing

        with tracing.span("scheduling.cycle", pod=pod.key()):
            return self._schedule_pod_traced(fwk, state, pod, attempts)

    def _schedule_pod_traced(self, fwk: Framework, state: CycleState, pod: Pod,
                             attempts: int = 0) -> str:
        trace = Trace("Scheduling", now_fn=self.now_fn, pod=pod.key())
        self.cache.update_snapshot(self.snapshot)
        trace.step("Snapshotting scheduler cache and node infos done")
        all_nodes = self.snapshot.list()
        if not all_nodes:
            raise FitError(pod, 0, Diagnosis())

        feasible, diagnosis = self.find_nodes_that_fit_pod(fwk, state, pod, all_nodes)
        trace.step("Computing predicates done")
        if not feasible:
            trace.log_if_long(self.trace_threshold_s)
            raise FitError(pod, len(all_nodes), diagnosis)
        if len(feasible) == 1:
            trace.log_if_long(self.trace_threshold_s)
            return feasible[0].node.meta.name

        fwk.run_pre_score_plugins(state, pod, [ni.node for ni in feasible])
        totals = fwk.run_score_plugins(state, pod, feasible)
        trace.step("Prioritizing done")
        trace.log_if_long(self.trace_threshold_s)
        if self.extenders:
            # prioritizeNodes (:662-691): extender scores are raw·weight added
            # onto the plugin totals (extender max is 10, not 100)
            nodes = [ni.node for ni in feasible]
            for ext in self.extenders:
                if not ext.is_interested(pod):
                    continue
                try:
                    prios = ext.prioritize(pod, nodes)
                except Exception:  # noqa: BLE001 — prioritize errors are ignored (:673)
                    continue
                for name, score in prios.items():
                    if name in totals:
                        totals[name] += score * ext.weight()
        return self._select_host(totals, pod=pod, attempts=attempts)

    def find_nodes_that_fit_pod(self, fwk: Framework, state: CycleState, pod: Pod, all_nodes) -> Tuple[List, Diagnosis]:
        """(schedule_one.go:364) PreFilter → (restricted) node list → filters
        with adaptive sampling + round-robin start (:449-:545).

        The "filter" EXTENSION-POINT duration is observed here, once per
        attempt over the node walk only (the reference observes Filter at
        this level, schedule_one.go:373 defer — per-node observation would
        put a histogram write on every node visit). The clock starts AFTER
        PreFilter, which already has its own extension-point histogram;
        timing it into "filter" too would double-count PreFilter-heavy
        plugins and misattribute their latency."""
        diagnosis = Diagnosis()
        result, status = fwk.run_pre_filter_plugins(state, pod)
        if not status.is_success():
            if status.is_unschedulable():
                diagnosis.unschedulable_plugins.add(status.plugin)
                for ni in all_nodes:
                    diagnosis.node_to_status[ni.node.meta.name] = status
                raise FitError(pod, len(all_nodes), diagnosis)
            raise RuntimeError(f"prefilter error: {status}")

        nodes = all_nodes
        if result is not None and not result.all_nodes():
            nodes = [ni for ni in all_nodes if ni.node.meta.name in result.node_names]

        t_filter = time.perf_counter()
        filter_status = "Error"  # overwritten unless an exception escapes
        try:
            # nominated-node fast path (schedule_one.go:394-403): a pod that
            # preempted evaluates its nominated node first and schedules
            # there when feasible — without it, adaptive sampling usually
            # misses the node the victims were evicted from
            if pod.status.nominated_node_name:
                ni = next((n for n in nodes
                           if n.node.meta.name == pod.status.nominated_node_name), None)
                if ni is not None:
                    st = fwk.run_filter_plugins_with_nominated_pods(state, pod, ni)
                    if st.is_success():
                        filter_status = "Success"
                        return [ni], diagnosis

            num_to_find = self.num_feasible_nodes_to_find(len(nodes))
            feasible = []
            checked = 0
            start = self.next_start_node_index % len(nodes) if nodes else 0
            for i in range(len(nodes)):
                ni = nodes[(start + i) % len(nodes)]
                checked += 1
                st = fwk.run_filter_plugins_with_nominated_pods(state, pod, ni)
                if st.is_success():
                    feasible.append(ni)
                    if len(feasible) >= num_to_find:
                        break
                else:
                    diagnosis.node_to_status[ni.node.meta.name] = st
                    diagnosis.unschedulable_plugins.add(st.plugin)
            self.next_start_node_index = (start + checked) % len(nodes) if nodes else 0
            if feasible and self.extenders:
                feasible = self._find_nodes_that_pass_extenders(pod, feasible, diagnosis)
            filter_status = "Success" if feasible else "Unschedulable"
            return feasible, diagnosis
        finally:
            self.smetrics.framework_extension_point_duration.observe(
                time.perf_counter() - t_filter, "filter", filter_status,
                fwk.profile_name)

    def _find_nodes_that_pass_extenders(self, pod: Pod, feasible: List, diagnosis: Diagnosis) -> List:
        """(schedule_one.go:547) run each interested extender's Filter verb;
        ignorable extender failures drop the extender, not the cycle."""
        from .extender import ExtenderError

        by_name = {ni.node.meta.name: ni for ni in feasible}
        nodes = [ni.node for ni in feasible]
        for ext in self.extenders:
            if not nodes:
                break
            if not ext.is_interested(pod):
                continue
            try:
                nodes, failed, unresolvable = ext.filter(pod, nodes)
            except ExtenderError:
                if ext.is_ignorable():
                    continue
                raise
            for name, reason in failed.items():
                diagnosis.node_to_status[name] = Status.unschedulable(reason)
            for name, reason in unresolvable.items():
                # excluded from preemption candidates (preemption.go:363)
                diagnosis.node_to_status[name] = Status.unresolvable(reason)
        return [by_name[n.meta.name] for n in nodes]

    def num_feasible_nodes_to_find(self, num_all_nodes: int) -> int:
        return num_feasible_nodes_to_find(num_all_nodes,
                                          self.percentage_of_nodes_to_score)

    def _select_host(self, totals: Dict[str, int], pod: Optional[Pod] = None,
                     attempts: int = 0) -> str:
        """(schedule_one.go:709) argmax + uniform tie-break. The reference's
        reservoir draw is unseeded; here the tie set is broken by the seeded
        per-(pod, attempt, node-name) hash the device batch program also uses
        (ops/tiebreak.py, SURVEY §8) — same uniform choice, but exactly
        replayable. Without a pod (legacy callers), falls back to the
        seeded-RNG reservoir."""
        best_score = None
        winner = None
        if pod is not None:
            from ..ops.tiebreak import name_hash, pod_seed, tie_key

            seed = pod_seed(pod.key(), attempts)
            best_key = -1
            for name, score in totals.items():
                if best_score is None or score > best_score:
                    best_score, winner = score, name
                    best_key = tie_key(seed, name_hash(name))
                elif score == best_score:
                    k = tie_key(seed, name_hash(name))
                    if k > best_key:
                        winner, best_key = name, k
            return winner
        cnt = 0
        for name, score in totals.items():
            if best_score is None or score > best_score:
                best_score, winner, cnt = score, name, 1
            elif score == best_score:
                cnt += 1
                if self.rng.random() < 1.0 / cnt:
                    winner = name
        return winner

    def _handle_scheduling_failure(self, fwk: Framework, state: CycleState, qp: QueuedPodInfo, status: Status, diagnosis: Diagnosis, pod_cycle: int) -> None:
        """(schedule_one.go:812 + scheduler.go:352 MakeDefaultErrorFunc):
        try PostFilter (preemption) on fit errors, then re-enqueue w/ backoff."""
        pod = qp.pod
        nominated_node = ""
        if status.is_unschedulable():
            self.metrics.inc("unschedulable")
            self.smetrics.mark_unschedulable(
                pod.key(), fwk.profile_name, diagnosis.unschedulable_plugins)
            if diagnosis.node_to_status and fwk.points.get("post_filter"):
                self.smetrics.preemption_attempts.inc()
                nominated, pf_status = fwk.run_post_filter_plugins(state, pod, diagnosis.node_to_status)
                if pf_status.is_success() and nominated:
                    nominated_node = nominated
            self.recorder.eventf(
                pod.key(), TYPE_WARNING, "FailedScheduling", "Scheduling",
                "; ".join(status.reasons) or "unschedulable",
            )
        if nominated_node:
            fwk.nominator.add_nominated_pod(pod, nominated_node)
            try:
                self.store.update_pod_nominated_node(pod.key(), nominated_node)
            except Exception:  # noqa: BLE001 — pod vanished; drop nomination
                fwk.nominator.delete_nominated_pod_if_exists(pod)
        # re-check existence/binding before re-queueing (MakeDefaultErrorFunc)
        current = self.store.get_pod(pod.key())
        if current is None or current.spec.node_name:
            self.smetrics.clear_unschedulable(pod.key())  # gone or bound
            # gone (deleted mid-cycle) or bound by an external binder:
            # either way the entry must not linger until the cap evicts it
            latency_ledger.close_skipped(pod.key(), current)
            return
        qp.pod = current
        qp.unschedulable_plugins = set(diagnosis.unschedulable_plugins)
        # error-status pods (device batch failure, bind error) take the
        # rate-limited backoff requeue — no plugin failed, so no ClusterEvent
        # would ever wake them from the unschedulable map
        self.queue.add_unschedulable_if_not_present(
            qp, pod_cycle, error=not status.is_unschedulable())

    # ----------------------------------------------------------- driving

    def run_until_settled(self, max_cycles: int = 100000, flush: bool = True) -> int:
        """Drive schedule_one until the active queue drains (test/perf helper;
        the reference's sched.Run loop is wait.Until on scheduleOne)."""
        cycles = 0
        while cycles < max_cycles:
            if not self.schedule_one():
                if flush:
                    self.queue.flush_backoff_completed()
                    if self.queue.pending_pods()["active"] > 0:
                        continue
                break
            cycles += 1
        return cycles

    def run_batched_until_settled(self, max_cycles: int = 100000, flush: bool = True,
                                  idle_wait: float = 0.005,
                                  max_no_progress: int = 200) -> int:
        """Shared settle loop for batched frontends (TPUScheduler,
        WireScheduler): drive ``schedule_batch_cycle`` until the queue
        settles, with a bounded no-progress spin (a pod flapping between
        queues cannot turn this into a hot loop) and ``settle_abandoned``
        surfaced for harness consumers."""
        import time as _time

        cycles = 0
        no_progress = 0
        self.settle_abandoned = False
        while cycles < max_cycles:
            before_sched = self.metrics["scheduled"]
            before_pending = self.queue.pending_pods()
            before_unsched = (before_pending["unschedulable"]
                              + before_pending.get("gated", 0))
            n = self.schedule_batch_cycle()
            if n == 0:
                if flush:
                    self.queue.flush_backoff_completed()
                    if self.queue.pending_pods()["active"] > 0:
                        no_progress += 1
                        if no_progress > max_no_progress:
                            self._abandon_settle()
                            break
                        continue
                break
            cycles += n
            pending = self.queue.pending_pods()
            # Progress = placements OR pods newly parked unschedulable (they
            # stay parked until an external event; failure-draining a batch
            # IS progress toward settling). Only cycles that neither place
            # nor park — a pod flapping straight back into activeQ — pay the
            # wait and count toward the bound.
            if (self.metrics["scheduled"] > before_sched
                    or pending["unschedulable"] + pending.get("gated", 0)
                    > before_unsched):
                no_progress = 0
            else:
                no_progress += 1
                if no_progress > max_no_progress:
                    self._abandon_settle()
                    break
                _time.sleep(idle_wait * min(no_progress, 10))
        return cycles

    def _abandon_settle(self) -> None:
        """Mark and log a no-progress early exit so callers (perf Runner,
        bench) can tell a settled queue from an abandoned one instead of
        silently reporting numbers over a partial workload."""
        import logging

        self.settle_abandoned = True
        self.metrics["settle_abandoned"] = self.metrics.get("settle_abandoned", 0) + 1
        logging.getLogger(__name__).warning(
            "run_until_settled: no progress after bound; %s pods still pending",
            self.queue.pending_pods())


def num_feasible_nodes_to_find(num_all_nodes: int, percentage: int = 0) -> int:
    """Adaptive sampling (:525): 100% under 100 nodes; else
    percentageOfNodesToScore or adaptive 50 − N/125, floored at 5%. Shared
    by the sequential, batched, and wire-service paths."""
    if num_all_nodes < MIN_FEASIBLE_NODES_TO_FIND or percentage >= 100:
        return num_all_nodes
    pct = percentage
    if pct == 0:
        pct = int(50 - num_all_nodes / 125)
        if pct < MIN_FEASIBLE_NODES_PERCENTAGE_TO_FIND:
            pct = MIN_FEASIBLE_NODES_PERCENTAGE_TO_FIND
    num = num_all_nodes * pct // 100
    if num < MIN_FEASIBLE_NODES_TO_FIND:
        return MIN_FEASIBLE_NODES_TO_FIND
    return num
