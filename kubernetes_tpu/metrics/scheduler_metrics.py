"""Scheduler metric set with the reference's metric names and labels
(pkg/scheduler/metrics/metrics.go:42-176) so a scheduler_perf-style
metricsCollector scrapes identically (SURVEY.md §5.5 build mapping)."""

from __future__ import annotations

import threading
import time
from typing import Dict, FrozenSet, Iterable, Tuple

from .registry import Counter, Gauge, Histogram, Registry, exponential_buckets

SCHEDULER_SUBSYSTEM = "scheduler"

# result labels (metrics.go)
SCHEDULED = "scheduled"
UNSCHEDULABLE = "unschedulable"
ERROR = "error"


class SchedulerMetrics:
    def __init__(self, registry: Registry = None):
        self.registry = registry or Registry()
        r = self.registry
        self.schedule_attempts = r.register(Counter(
            "scheduler_schedule_attempts_total",
            "Number of attempts to schedule pods, by the result.",
            ["result", "profile"],
        ))
        self.scheduling_attempt_duration = r.register(Histogram(
            "scheduler_scheduling_attempt_duration_seconds",
            "Scheduling attempt latency (algorithm + binding).",
            ["result", "profile"],
        ))
        self.scheduling_algorithm_duration = r.register(Histogram(
            "scheduler_scheduling_algorithm_duration_seconds",
            "Scheduling algorithm latency.",
            ["profile"],
        ))
        self.framework_extension_point_duration = r.register(Histogram(
            "scheduler_framework_extension_point_duration_seconds",
            "Latency per extension point.",
            ["extension_point", "status", "profile"],
        ))
        self.plugin_execution_duration = r.register(Histogram(
            "scheduler_plugin_execution_duration_seconds",
            "Plugin execution latency (sampled).",
            ["plugin", "extension_point", "status"],
        ))
        self.pending_pods = r.register(Gauge(
            "scheduler_pending_pods",
            "Pending pods by queue (active|backoff|unschedulable|gated).",
            ["queue"],
        ))
        self.queue_incoming_pods = r.register(Counter(
            "scheduler_queue_incoming_pods_total",
            "Pods added to queues by event and queue.",
            ["queue", "event"],
        ))
        self.preemption_attempts = r.register(Counter(
            "scheduler_preemption_attempts_total",
            "Total preemption attempts in the cluster.",
        ))
        self.preemption_victims = r.register(Histogram(
            "scheduler_preemption_victims",
            "Number of selected preemption victims.",
            buckets=[1, 2, 4, 8, 16, 32, 64],
        ))
        self.unschedulable_pods = r.register(Gauge(
            "scheduler_unschedulable_pods",
            "Unschedulable pods broken down by plugin.",
            ["plugin", "profile"],
        ))
        self.cache_size = r.register(Gauge(
            "scheduler_scheduler_cache_size",
            "Scheduler cache entries (nodes|pods|assumed_pods).",
            ["type"],
        ))
        self.goroutines = r.register(Gauge(
            "scheduler_goroutines",
            "Number of running goroutines split by work (device-step analog).",
            ["work"],
        ))
        # TPU-path extensions (new metrics, framework-specific)
        self.device_batch_duration = r.register(Histogram(
            "scheduler_tpu_batch_duration_seconds",
            "Device schedule_batch call latency.",
            ["phase"],  # upload|compute|commit
        ))
        self.device_batch_size = r.register(Histogram(
            "scheduler_tpu_batch_size",
            "Pods per device batch.",
            buckets=[1, 2, 4, 8, 16, 32, 64, 128, 256, 512],
        ))
        # async commit pipeline (backend/tpu_scheduler.py in-flight ring):
        # current dispatched-but-uncommitted batch count, and cumulative
        # seconds the commit site spent blocked on device execution AFTER
        # the packed-block transfer was already staged at dispatch (the
        # residual stall the ring exists to hide — a growing rate here says
        # the ring is too shallow or the host fell behind)
        self.pipeline_inflight = r.register(Gauge(
            "scheduler_pipeline_inflight",
            "Dispatched device batches not yet committed (ring occupancy).",
        ))
        self.pipeline_stall_seconds = r.register(Counter(
            "scheduler_pipeline_stall_seconds_total",
            "Seconds the batch commit site blocked waiting on device results.",
        ))
        # resource.k8s.io (DRA): claim allocation outcomes at Reserve time
        # (allocated|conflict) and Unreserve rollbacks (released)
        self.dra_claim_allocations = r.register(Counter(
            "scheduler_dynamic_resources_claim_allocations_total",
            "ResourceClaim allocation outcomes by result.",
            ["result"],
        ))
        # gang scheduling (Coscheduling/PodGroup): gang-level rejection
        # events (timeout at Permit, a member's failure, a device batch that
        # could not place the whole gang) and how long a gang's first member
        # waits at Permit before the gang releases or is torn down
        self.gangs_rejected = r.register(Counter(
            "scheduler_gangs_rejected_total",
            "PodGroup gang rejection events by reason.",
            ["reason"],
        ))
        self.gang_wait_duration = r.register(Histogram(
            "scheduler_gang_wait_duration_seconds",
            "Gang wait at Permit from first parked member to release/rejection.",
            ["result"],  # scheduled|rejected
        ))
        # slice-topology packing (ops/slice.py): per-superpod fragmentation
        # (1 - largest free contiguous run / free nodes; 0 = one unbroken
        # run or nothing free) refreshed from the host mirror at each slice
        # commit, and how long a slice gang's pods waited from queue pop to
        # a contiguous torus placement landing
        self.slice_fragmentation = r.register(Gauge(
            "scheduler_slice_fragmentation",
            "Torus fragmentation score per superpod (0 contiguous, ->1 shredded).",
            ["superpod"],
        ))
        self.slice_wait_duration = r.register(Histogram(
            "scheduler_slice_wait_duration_seconds",
            "Slice gang wait from batch pop to contiguous placement commit.",
            ["result"],  # scheduled|rejected
        ))
        # fault-tolerant wire path (backend/service.py): transport retries,
        # the bytes of each request sent (backend/wire.py), breaker state
        # (0 closed, 1 half-open, 2 open), and cumulative time spent
        # scheduling through the sequential oracle because the device
        # service was unavailable
        self.wire_retries = r.register(Counter(
            "scheduler_wire_retries_total",
            "Device-service transport retries by operation.",
            ["op"],
        ))
        self.wire_request_bytes = r.register(Counter(
            "scheduler_wire_request_bytes_total",
            "Serialized bytes of device-service requests sent, by operation.",
            ["op"],
        ))
        self.backend_circuit_state = r.register(Gauge(
            "scheduler_backend_circuit_state",
            "Device-service circuit breaker state (0 closed, 1 half-open, 2 open).",
        ))
        self.degraded_seconds = r.register(Counter(
            "scheduler_degraded_seconds_total",
            "Seconds spent in breaker-open degraded (oracle fallback) mode.",
        ))
        # active-active HA (per-client device-service sessions): live
        # session count as seen by the last heartbeat, peer-fence takeover
        # events this replica observed (and adopted after), and typed
        # commit conflicts (another replica owned the pod/capacity)
        self.client_sessions = r.register(Gauge(
            "scheduler_client_sessions",
            "Live scheduler sessions on the shared device service.",
        ))
        self.ha_takeovers = r.register(Counter(
            "scheduler_ha_takeovers_total",
            "Peer scheduler sessions fenced and adopted by this replica.",
        ))
        self.commit_conflicts = r.register(Counter(
            "scheduler_commit_conflicts_total",
            "Ownership-check conflicts at device commit time.",
            ["client"],
        ))
        # device-side HA fabric (backend/fabric.py): which replica the
        # fabric currently routes to (index into the endpoint list),
        # primary failovers by trigger family, and per-endpoint replica
        # health (1 healthy / 0 down) as seen by calls + Health probes
        self.fabric_active_replica = r.register(Gauge(
            "scheduler_fabric_active_replica",
            "Index of the device-service replica the fabric routes to.",
        ))
        self.fabric_failovers = r.register(Counter(
            "scheduler_fabric_failovers_total",
            "Device-fabric primary failovers by triggering error family.",
            ["reason"],
        ))
        self.fabric_replica_health = r.register(Gauge(
            "scheduler_fabric_replica_health",
            "Device-service replica health by endpoint (1 up, 0 down).",
            ["endpoint"],
        ))
        # pipelined wire transport + warm-standby replication: wire batches
        # submitted but not yet processed (the wire ring's occupancy), how
        # many delta generations each standby's mirror lags the primary
        # stream, and the wire bytes the background replicator shipped to
        # standbys (full seeds vs incremental deltas) — the denominator of
        # the O(dirty)-resync-at-promote evidence
        self.wire_inflight = r.register(Gauge(
            "scheduler_wire_inflight",
            "Wire batches in flight on the pipelined transport.",
        ))
        self.standby_replication_lag = r.register(Gauge(
            "scheduler_standby_replication_lag",
            "Delta generations a standby replica lags the primary stream.",
            ["endpoint"],
        ))
        self.standby_resync_bytes = r.register(Counter(
            "scheduler_standby_resync_bytes_total",
            "Wire bytes shipped to standbys by the background replicator "
            "(full = seed/reseed, delta = incremental dirty set).",
            ["kind"],
        ))
        # device-runtime observability (backend/telemetry.py): XLA compile
        # ledger per (program, bucket signature) with retrace counts (a
        # compile beyond a program's first — the BatchSizer's bucket walk
        # shows up here when it recompiles mid-run), accelerator memory
        # stats, host<->device transfer volume, and flight-recorder event
        # counts by type
        self.xla_compilations = r.register(Counter(
            "scheduler_xla_compilations_total",
            "XLA backend compilations by program and bucket signature.",
            ["program", "bucket"],
        ))
        self.xla_compile_duration = r.register(Histogram(
            "scheduler_xla_compile_seconds",
            "XLA backend compile latency by program.",
            ["program"],
            buckets=exponential_buckets(0.01, 2, 14),
        ))
        self.xla_retraces = r.register(Counter(
            "scheduler_xla_retraces_total",
            "XLA compilations beyond a program's first (retraces).",
            ["program"],
        ))
        self.hbm_bytes = r.register(Gauge(
            "scheduler_device_hbm_bytes",
            "Device memory stats sample (in_use|peak|limit).",
            ["kind"],
        ))
        self.device_transfer_bytes = r.register(Counter(
            "scheduler_device_transfer_bytes_total",
            "Host<->device transfer volume (upload = row sync, fetch = "
            "packed result block).",
            ["direction"],
        ))
        # the Python runtime under the program (utils/heap.py): objects
        # moved out of the collector's reach once the program was warm
        self.gc_frozen_objects = r.register(Gauge(
            "scheduler_gc_frozen_objects",
            "Objects frozen out of the garbage collector after warm-up.",
        ))
        self.flight_events = r.register(Counter(
            "scheduler_flight_recorder_events_total",
            "Batch flight-recorder events by type.",
            ["type"],
        ))
        # dispatch profiler (backend/telemetry.py DispatchLedger): the
        # commit-wait waterfall per program — dwell (submit→exec start,
        # inferred from the in-flight ring overlap), exec (device run
        # time), fetch (packed-block device→host transfer)
        self.device_dispatch_duration = r.register(Histogram(
            "scheduler_device_dispatch_seconds",
            "Per-dispatch device-time decomposition by program and phase "
            "(dwell|exec|fetch).",
            ["program", "phase"],
            buckets=exponential_buckets(0.0002, 2, 16),
        ))
        # multi-tenant admission (SchedulingQuota + QuotaAdmission plugin):
        # the scheduler-side ledger per (namespace, dimension), admission
        # decisions at the gate/Reserve, gated pods woken by targeted
        # quota-release moves, and the fair-share dequeuer's tenant turns
        # (the denominator of the quota-weighted fairness bound)
        self.quota_usage = r.register(Gauge(
            "scheduler_quota_usage",
            "Scheduler-side quota ledger usage by namespace and dimension.",
            ["namespace", "resource"],
        ))
        self.quota_decisions = r.register(Counter(
            "scheduler_quota_admission_decisions_total",
            "Pod-level quota admission outcomes by namespace "
            "(admitted at Reserve charge; rejected once per over-quota "
            "episode, not per re-check).",
            ["namespace", "result"],
        ))
        self.quota_released_pods = r.register(Counter(
            "scheduler_quota_released_pods_total",
            "Gated pods re-admitted by targeted quota-release queue moves.",
            ["namespace"],
        ))
        # cohort borrowing: the loaned portion of the ledger (subset of
        # scheduler_quota_usage) and reclaim-by-preemption pass outcomes
        # (evicted / noop / suspended-by-breaker)
        self.quota_borrowed = r.register(Gauge(
            "scheduler_quota_borrowed",
            "Ledger usage charged against cohort headroom (loans) by "
            "namespace and dimension.",
            ["namespace", "resource"],
        ))
        self.quota_reclaims = r.register(Counter(
            "scheduler_quota_reclaims_total",
            "Cohort reclaim-by-preemption pass outcomes.",
            ["result"],
        ))
        self.fair_share_turns = r.register(Counter(
            "scheduler_fair_share_turns_total",
            "Deficit-round-robin dequeue turns served per tenant namespace.",
            ["namespace"],
        ))
        # elastic clusters (node churn / drain / spot reclamation): informer
        # node-event volume by action, evictions by machinery (drain wave,
        # spot NoExecute storm, taint manager), and device row-slot reuse
        # (the free-list keeping DeviceState capacity bounded under churn)
        self.node_events = r.register(Counter(
            "scheduler_node_events_total",
            "Node informer events observed by the scheduler, by action.",
            ["action"],
        ))
        self.evicted_pods = r.register(Counter(
            "scheduler_evicted_pods_total",
            "Pods evicted by the elasticity machinery, by reason "
            "(drain|spot|taint).",
            ["reason"],
        ))
        self.device_slot_reuse = r.register(Counter(
            "scheduler_device_slot_reuse_total",
            "Tombstoned DeviceState row slots handed to new nodes "
            "(bounded-capacity churn instead of monotonic growth).",
        ))
        # device-carry chain (backend/tpu_scheduler.py): per pipelined
        # encode attempt, whether the batch rode the in-flight carry or why
        # it took the drain+sync path; and the deleted pods whose requests
        # went back on the carry instead of breaking it
        self.carry_chain_batches = r.register(Counter(
            "scheduler_carry_chain_batches_total",
            "Pipelined encode attempts by outcome: kept, or the reason the "
            "device-carry chain broke.",
            ["outcome"],
        ))
        self.carry_released_pods = r.register(Counter(
            "scheduler_carry_released_pods_total",
            "Deleted bound pods whose requests were subtracted from the "
            "device carry.",
        ))
        # commit data plane (backend/commit_plane.py): per-batch commit
        # engine stage latencies (assume → bind → finish → notify →
        # post_bind, plus the whole-batch total) and the notification /
        # WAL / queue-move deliveries coalesced into per-batch operations
        # instead of per-pod ones
        self.commit_batch_duration = r.register(Histogram(
            "scheduler_commit_batch_duration_seconds",
            "Batched commit engine latency by stage.",
            ["stage"],
        ))
        self.commit_coalesced_events = r.register(Counter(
            "scheduler_commit_coalesced_events_total",
            "Per-pod deliveries coalesced into batched commit operations "
            "(queue_move|wal_record|cache_op|post_bind).",
            ["kind"],
        ))
        # pod-lifetime latency ledger (metrics/latency_ledger.py): one entry
        # per pod from first enqueue to bind (or terminal delete), spanning
        # every attempt — the end-to-end complement of the per-attempt
        # histogram. Segments are the named wall-clock slices of that
        # lifetime (queue.active/backoff/unschedulable/gated/drr_wait,
        # cycle.host, gang.permit_park, device.inflight, commit.host, bind);
        # the tenant histogram is the per-namespace SLO feed, its label set
        # bounded by the quota tenant index. Buckets reach ~160s: a pod can
        # legitimately dwell minutes across backoff/gate parks.
        _e2e_buckets = exponential_buckets(0.005, 2, 16)
        self.pod_e2e_duration = r.register(Histogram(
            "scheduler_pod_e2e_duration_seconds",
            "Pod end-to-end latency from first enqueue to bind (or terminal "
            "delete), across all attempts.",
            ["result"],
            buckets=_e2e_buckets,
        ))
        self.pod_latency_segment = r.register(Histogram(
            "scheduler_pod_latency_segment_seconds",
            "Per-pod lifetime wall-clock attribution by named segment "
            "(observed once per segment at pod close).",
            ["segment"],
            buckets=_e2e_buckets,
        ))
        self.tenant_e2e_duration = r.register(Histogram(
            "scheduler_tenant_e2e_duration_seconds",
            "Pod end-to-end latency per tenant namespace (quota tenants "
            "only — the fair-share SLO feed).",
            ["namespace"],
            buckets=_e2e_buckets,
        ))
        self.ledger_evicted = r.register(Counter(
            "scheduler_pod_ledger_evicted_total",
            "Latency-ledger entries evicted at the live-entry cap (oldest "
            "first; nonzero means e2e attribution lost pods).",
        ))

        # continuous rebalancing (controllers/rebalance.py): the background
        # descheduler's control-loop evidence — executed/empty/suspended
        # wave outcomes, total pods migrated, the packing-entropy score the
        # trigger band watches (1.0 = load smeared evenly over every node,
        # ->0 = consolidated), and whether the SLO guardrail breaker
        # currently has rebalancing suspended (0/1).
        self.rebalance_waves = r.register(Counter(
            "scheduler_rebalance_waves_total",
            "Rebalance wave attempts by outcome (executed / empty / "
            "suspended).",
            ["result"],
        ))
        self.rebalance_migrations = r.register(Counter(
            "scheduler_rebalance_migrations_total",
            "Pods evicted by rebalance migration waves (each re-binds via "
            "the normal requeue path).",
        ))
        self.packing_entropy = r.register(Gauge(
            "scheduler_packing_entropy",
            "Mean normalized bin-packing entropy over live resource axes "
            "(the rebalance trigger's score; lower is better packed).",
        ))
        self.rebalance_suspended = r.register(Gauge(
            "scheduler_rebalance_suspended",
            "1 while the tenant-SLO guardrail breaker holds rebalancing "
            "suspended, else 0.",
        ))

        # unschedulable_pods bookkeeping: gauge value = number of pods
        # CURRENTLY unschedulable attributed to each (plugin, profile); a
        # pod's attribution is replaced on every failed attempt and removed
        # when it schedules or is deleted (the reference decrements via
        # its pending-pods recorder; a bare set(1) never comes back down)
        self._unsched_lock = threading.Lock()
        self._unsched_pods: Dict[str, Tuple[str, FrozenSet[str]]] = {}
        self._unsched_counts: Dict[Tuple[str, str], int] = {}

    def observe_attempt(self, result: str, profile: str, duration_s: float) -> None:
        self.schedule_attempts.inc(result, profile)
        self.scheduling_attempt_duration.observe(duration_s, result, profile)

    def mark_unschedulable(self, pod_key: str, profile: str,
                           plugins: Iterable[str]) -> None:
        """Attribute ``pod_key``'s unschedulability to ``plugins``,
        replacing any previous attribution for the pod."""
        with self._unsched_lock:
            self._clear_unschedulable_locked(pod_key)
            ps = frozenset(p for p in plugins if p)
            if not ps:
                return
            self._unsched_pods[pod_key] = (profile, ps)
            for p in ps:
                k = (p, profile)
                n = self._unsched_counts.get(k, 0) + 1
                self._unsched_counts[k] = n
                self.unschedulable_pods.set(p, profile, value=n)

    def clear_unschedulable(self, pod_key: str) -> None:
        """Drop the pod's attribution (it scheduled, was deleted, or was
        bound by someone else)."""
        with self._unsched_lock:
            self._clear_unschedulable_locked(pod_key)

    def _clear_unschedulable_locked(self, pod_key: str) -> None:  # ktpu: locked
        prev = self._unsched_pods.pop(pod_key, None)
        if prev is None:
            return
        profile, ps = prev
        for p in ps:
            k = (p, profile)
            n = max(self._unsched_counts.get(k, 0) - 1, 0)
            self._unsched_counts[k] = n
            self.unschedulable_pods.set(p, profile, value=n)

    def sync_queue_gauges(self, pending: dict) -> None:
        for q, n in pending.items():
            self.pending_pods.set(q, value=n)

    def sync_cache_gauges(self, nodes: int, pods: int, assumed: int) -> None:
        self.cache_size.set("nodes", value=nodes)
        self.cache_size.set("pods", value=pods)
        self.cache_size.set("assumed_pods", value=assumed)


_global = None


def global_metrics() -> SchedulerMetrics:
    """legacyregistry analog: one process-wide metric set."""
    global _global
    if _global is None:
        _global = SchedulerMetrics()
    return _global
