"""TPUScheduler: the batched execution backend wired into the scheduler.

Replaces the per-pod findNodesThatFitPod/prioritizeNodes middle of the cycle
(schedule_one.go:364,:605) with one compiled device call per pod micro-batch;
queue, cache, assume, bind, and failure handling are the same host machinery
as the sequential path (the BASELINE.json north star, minus the gRPC hop —
the control plane here is in-process Python rather than a Go sidecar peer).

Flow per batch cycle:
  1. drain up to `batch_size` pods from the queue in queue order;
  2. update the cache snapshot; delta-sync the device mirror;
  3. split batch-supported pods from fallback pods (features the kernel
     doesn't cover yet go through the sequential oracle path — graceful
     degradation, SURVEY.md §5.3 build mapping);
  4. one `schedule_batch` call: static masks + in-scan sequential commit;
  5. host: assume + bind winners in order; losers get reference-shaped
     Diagnosis (first-failing-plugin per node, reconstructed from the masks
     in filter config order) and re-queue with backoff.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
from collections import deque
from typing import Callable, Deque, Dict, List, Optional, Set, Tuple

import jax
import numpy as np

from ..api.types import Pod
from ..framework.interface import CycleState, Status
from ..framework.plugins.coscheduling import gang_precheck_status, pod_group_key
from ..framework.plugins.quota import quota_precheck_status
from ..framework.types import Diagnosis, QueuedPodInfo
from ..metrics import latency_ledger
from ..ops.encode import CapacityError
from ..ops.topology import seg_mode
from ..scheduler.scheduler import Scheduler
from ..utils import heap
from . import device_step
from .batch import (ATTRIBUTION_ORDER, BatchResult, bucket_sig,
                    build_schedule_batch_fn, domain_axis)
from .device_state import DeviceState
from .sizer import BatchSizer


@dataclasses.dataclass
class _Inflight:
    """One dispatched-but-uncommitted batch (SURVEY §2.7 P3: the device
    computes batch k+1 while the host commits batch k). The result's arrays
    are unmaterialized device futures until the commit touches them."""

    qps: List[QueuedPodInfo]
    result: BatchResult
    pod_cycle: int
    t0: float  # batch pop time — the attempt-latency clock
    host_pb: dict  # encoder's host copy of req/nonzero_req/port_ids
    pb: object = None  # device PodBatch — preemption screen input on failures
    mode_info: tuple = ()  # (topo_mode, vd_bucket, host_key): carry-shape id
    batch_id: str = ""  # flight-recorder identity (in-process: "b<counter>")
    bucket: int = 0  # padded pod capacity the program ran at
    # encoder.reclaim_gen at dispatch: a winner slot released after this
    # (node removed / tombstone reused) gets a typed rejection at commit
    # instead of a ghost placement (None = guard by cache existence only)
    reclaim_gen: Optional[int] = None
    # the DeviceState instance this batch was computed on: a commit (worker
    # or inline) finding a DIFFERENT live device poisons the batch instead
    # of committing foreign-device results against a rebuilt mirror — the
    # race-free form of "clear the whole ring on device death"
    device: object = None
    # now_fn timestamp when the async dispatch returned — the dispatch
    # profiler's dwell clock starts here (0.0 = unset: dwell collapses
    # into the wait window)
    t_submit: float = 0.0
    # whether the batch program ran the namespace-quota screen — the
    # packed block then carries a quota verdict column, and the unpack
    # must know which a SINGLE trailing column is (slice vs quota)
    quota_col: bool = False


# the fixed in-checkout cache directory: the path is part of the cache key,
# so a directory that moves between runs never hits
_CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def compilation_cache_dir() -> Optional[str]:
    """The directory ``_enable_compilation_cache`` points JAX at: None when
    JAX_COMPILATION_CACHE_DIR is set (JAX reads that itself and the code
    sets nothing), else the fixed directory inside the checkout."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    return _CHECKOUT_CACHE_DIR


def _enable_compilation_cache() -> None:
    """Persistent XLA compilation cache: the batched kernels compile once per
    (bucket, batch) shape per cache directory, not per process — first-run
    warmup is the dominant cost otherwise (§5.4: persist nothing beyond
    compiled-executable caches)."""
    if getattr(_enable_compilation_cache, "_done", False):
        return
    _enable_compilation_cache._done = True
    cache_dir = compilation_cache_dir()
    if cache_dir is not None:
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)


class TPUScheduler(Scheduler):
    def __init__(self, *args, batch_size: int = 128, comparer_every_n: int = 0,
                 batch_deadline_ms: Optional[float] = None,
                 device_breaker_threshold: Optional[int] = None,
                 device_probe_interval_s: Optional[float] = None, **kwargs):
        super().__init__(*args, **kwargs)
        _enable_compilation_cache()
        self.batch_size = batch_size
        # in-process device breaker (PR 3 carryover): repeated device-commit
        # failures (a device that errors on every program) stop burning a
        # rebuild+dispatch per cycle — pods take the oracle path while the
        # breaker is open. Probing in-process costs one local dispatch
        # (microseconds of host work), not a wire round trip, so the
        # half-open interval defaults to 0.5s instead of the wire breaker's
        # 5s — a healed device is re-adopted ~10x sooner.
        from .circuit import CircuitBreaker

        if device_breaker_threshold is None:
            device_breaker_threshold = int(os.environ.get(
                "KTPU_DEVICE_BREAKER_THRESHOLD", "3"))
        if device_probe_interval_s is None:
            device_probe_interval_s = float(os.environ.get(
                "KTPU_DEVICE_PROBE_S", "0.5"))
        self.device_breaker = CircuitBreaker(
            failure_threshold=device_breaker_threshold,
            reset_timeout_s=device_probe_interval_s, now_fn=self.now_fn,
            on_state_change=self._device_breaker_state_change)
        self.device_degraded_pods = 0
        # degraded-window accounting for the IN-PROCESS breaker: the wire
        # path accrues scheduler_degraded_seconds_total on its own breaker
        # (backend/service.py); before this, a device-breaker-open window on
        # the in-process backend was invisible to the SLO metric
        self._device_degraded_since: Optional[float] = None
        # scripted device-fault hook (soak workloads / chaos rigs): called
        # with the op name ("commit") before each batch materialization;
        # a returned exception is raised through the real device-death path
        # (breaker count, ring poison, backoffQ requeue, device rebuild) —
        # the in-process analog of testing/faults.FaultPlan on the wire
        self.device_fault_fn: Optional[Callable[[str], Optional[BaseException]]] = None
        if batch_deadline_ms is None:
            # ON by default (VERDICT r3 item 4): the iso-p99 contract needs
            # pop→commit bounded, so the sizer cuts batches to fit each
            # batch's own stages in the deadline. 500ms keeps ≥90% of
            # uncapped throughput on the CPU fallback (scan step ~1.8ms/pod)
            # and never binds on accelerators (per-pod cost far below the
            # budget). "0" disables.
            batch_deadline_ms = float(os.environ.get("KTPU_BATCH_DEADLINE_MS", "500"))
        self.sizer = BatchSizer(batch_size, batch_deadline_ms / 1000.0)
        # device/host comparer (SURVEY.md §5.2 mapping of the cache drift
        # detector): every Nth device commit, re-check the placement with
        # the scalar oracle filters; 0 disables
        self.comparer_every_n = comparer_every_n
        self.comparer_checks = 0
        self.comparer_mismatches = 0
        self.device: Optional[DeviceState] = None
        # high-water mark of encoder.slot_reuses already exported to the
        # scheduler_device_slot_reuse_total counter (device rebuilds reset
        # the encoder counter; the metric stays cumulative)
        self._slot_reuses_seen = 0
        self._batchable_cache: Dict[str, bool] = {}
        self.schedule_batch_fn = build_schedule_batch_fn()
        self.batch_counter = 0
        self.fallback_scheduled = 0
        self.batch_scheduled = 0
        # run_until_settled sets this when it gives up with pods still
        # pending (ADVICE r2: harness consumers must be able to distinguish
        # settled from abandoned)
        self.settle_abandoned = False
        # adaptive-sampling rotation start: a device scalar chained from the
        # previous batch's evolved carry (schedule_one.go:475 rotation)
        self._start_carry = None
        # ---- commit data plane (backend/commit_plane.py) ----
        # The commit WORKER lands ring-overflow batches on its own thread,
        # overlapping batch K's host commit with batch K+1's encode/
        # dispatch/device execution. The device mutex (owned by the commit
        # plane so the per-class static lock pass analyzes the classes that
        # own state, while KTPU_LOCKTRACE traces the protocol end to end)
        # serializes the two owners' device-touching phases: the scheduling
        # thread's sync/encode/dispatch vs the worker's adopt/judge/
        # reconcile. PLATFORM-AWARE default (device_step.default_full_batch's
        # rule): on an accelerator the device executes off-host and the worker's
        # overlap is free; on the CPU fallback "device compute" is host CPU
        # time, so a second thread only contends with XLA (measured ~18%
        # slower on the 2-core bench box) — commits stay inline there.
        # KTPU_COMMIT_WORKER=1/0 overrides either way.
        self.commit_worker = None
        worker_env = os.environ.get("KTPU_COMMIT_WORKER", "")
        if worker_env in ("0", "1"):
            want_worker = worker_env == "1"
        else:
            want_worker = jax.default_backend() != "cpu"
        # async pipeline (SURVEY §2.7 P3 analog), generalized to a bounded
        # multi-batch in-flight RING: up to ``pipeline_depth`` dispatched
        # batches ride the device at once (oldest commits first), so the
        # host work of landing batch k overlaps the device execution of
        # k+1..k+K instead of just the dispatch of k+1. KTPU_PIPELINE=0
        # forces the synchronous path; KTPU_PIPELINE_DEPTH sets K. Each
        # ring slot adds a cycle of dwell to every batch's pop→commit
        # (which the deadline sizer leaves out of what it controls). Inline
        # the default K is 2. With the commit worker it is 1: the worker's
        # own queue overlaps the commits, and the ring only has to hold the
        # newest batch, whose adopted carry the next encode chains on.
        if os.environ.get("KTPU_PIPELINE", "1") == "0":
            self.pipeline_depth = 0
        else:
            # depth 0 is a valid setting: synchronous, same as KTPU_PIPELINE=0
            self.pipeline_depth = max(0, int(os.environ.get(
                "KTPU_PIPELINE_DEPTH", "1" if want_worker else "2")))
        self._inflight: Deque[_Inflight] = deque()
        self.pipelined_batches = 0
        if self.pipeline_depth and want_worker:
            from .commit_plane import CommitWorker

            self.commit_worker = CommitWorker(self._commit_inflight)
        # worker-owned snapshot for commit-side reconciles: the scheduling
        # thread keeps self.snapshot; sharing one Snapshot object across
        # threads would let reconcile iterate node_info_map mid-update
        from ..cache import Snapshot

        self._commit_snapshot = Snapshot()
        # carry gate for the async pipeline: the pipelined encode rides the
        # device carry only while (a) no EXTERNAL node-truth change arrived
        # since the last full sync (Scheduler.external_change_seq) and (b)
        # no host-rejected commit invalidated a device row (_chain_dirty).
        # The has_dirty cache walk the synchronous pipeline uses cannot
        # distinguish the worker's own in-progress commits from external
        # changes, so the worker mode gates on events instead.
        self._chain_ext_seq = -1
        self._chain_dirty = False
        # a deleted bound pod is no external change when the carry can take
        # its requests back exactly: its (pod, cache.Removal) pair waits
        # here for the next dispatch, drain-path sync or commit reconcile,
        # which subtracts it (DeviceState.release). Recorded under the cache
        # lock, so every removal lands wholly before or after a snapshot the
        # releases are taken with. A deletion the carry cannot take back
        # bumps the external sequence; (the sequence number that bump made,
        # its reason) names a chain break at exactly that number.
        self._releases: List[tuple] = []
        self._blocked: Tuple[int, Optional[str]] = (-1, None)
        # volume-bindability pre-pass (ops/volume_mask.py): lets PVC-bearing
        # pods ride the batched path with a [P, N] static screen + exact
        # host verify of the chosen node at commit (VERDICT r4 item 4)
        from ..ops.volume_mask import VolumeMaskBuilder

        self._volume_masks = VolumeMaskBuilder(self.store)
        # claim-feasibility pre-pass (backend/claim_mask.py): resource.k8s.io
        # claim-bearing pods ride the batched path with a [P, N] device mask
        # over the node attribute table + exact Reserve verify at commit
        from .claim_mask import ClaimMaskBuilder

        self._claim_masks = ClaimMaskBuilder(self.store)
        # continuous rebalancing (controllers/rebalance.py): opt-in via
        # enable_rebalancer(); driven from _periodic_housekeeping so it
        # only ever runs on the scheduling thread, in commit-idle gaps
        self.rebalancer = None

    def _device_breaker_state_change(self, _old: str, new: str) -> None:
        """Device breaker transition: publish the circuit gauge and accrue
        scheduler_degraded_seconds_total over the open→closed window (the
        in-process mirror of WireScheduler's degraded accounting). A
        half-open probe neither closes nor restarts the window — only a
        successful close books the seconds."""
        from .circuit import STATE_VALUES

        self.smetrics.backend_circuit_state.set(value=STATE_VALUES[new])
        now = self.now_fn()
        if new == "open" and self._device_degraded_since is None:
            self._device_degraded_since = now
        elif new == "closed" and self._device_degraded_since is not None:
            self.smetrics.degraded_seconds.inc(
                value=now - self._device_degraded_since)
            self._device_degraded_since = None

    # ------------------------------------------------------------- device mgmt

    def _sync_slot_reuse_metric(self) -> None:
        """Export the encoder's slot-reuse count delta into the cumulative
        scheduler_device_slot_reuse_total counter."""
        if self.device is None:
            return
        reuses = self.device.encoder.slot_reuses
        if reuses < self._slot_reuses_seen:  # fresh device: counter reset
            self._slot_reuses_seen = 0
        if reuses > self._slot_reuses_seen:
            self.smetrics.device_slot_reuse.inc(
                value=reuses - self._slot_reuses_seen)
            self._slot_reuses_seen = reuses

    def _ensure_device(self) -> None:
        """Build or grow the device mirror (device_step.fit_nodes). Always
        called on the scheduling thread; a grow first drains OUTSIDE the
        device mutex (the commit worker needs the mutex to finish its
        commits: old-device results must land first), then the rebuild and
        sync run under it."""
        n = max(self.cache.node_count(), 1)
        with self.commit_plane.device_mutex:
            device = self.device
        if device is not None:
            if device.caps.nodes >= n:
                return
            self._drain_inflight()
        with self.commit_plane.device_mutex:
            device = device_step.fit_nodes(self.device, n,
                                           batch=self.batch_size,
                                           ns_labels_fn=self.store.ns_labels)
            if device is not self.device:
                self.device = device
                device.sync(self.snapshot)

    def _resync_grown(self, err: CapacityError) -> None:
        """Grow exactly the offending capacity axis and rebuild the mirror.
        Callers raise CapacityError OUTSIDE the device mutex (the drain
        below must let the commit worker take it)."""
        self._drain_inflight()
        if self.device is None:  # the drain's commit killed the device
            self._ensure_device()
            return
        with self.commit_plane.device_mutex:
            self.device = device_step.grown(self.device, err,
                                            ns_labels_fn=self.store.ns_labels)
            self.device.sync(self.snapshot)

    # ------------------------------------------------------------- batch support

    def _topo_mode_info(self) -> tuple:
        """(topo_mode, vd_bucket, host_key) for the CURRENT sig-table state +
        last-encoded batch: selects the hostname fast path or a compact
        domain axis (ops/topology.py). Also the carry-shape identity the
        pipelined chain must match on."""
        if not self.device.topo_enabled:
            return ("off", None, 0)
        summary = getattr(self.device.sig_table, "last_topo_summary", None)
        if summary is None:
            return ("general", None, 0)
        if summary["hostname_only"]:
            from ..framework.plugins.podtopologyspread import HOSTNAME_KEY

            host_slot = self.device.encoder.key_slot(HOSTNAME_KEY)
            # the fast path treats every node as its own domain — only valid
            # when hostname label values are actually node-unique (a
            # --hostname-override collision must fall back to the general
            # domain-aggregating path)
            valid = self.device._mirror["valid"]
            vals = self.device._mirror["label_val"][valid, host_slot]
            if len(np.unique(vals)) == len(vals):
                return ("host", None, host_slot)
        vd = 64
        while vd < summary["vd_needed"]:
            vd *= 2
        return ("general", vd, 0)

    def batch_supported(self, pod: Pod) -> bool:
        """Features the batched kernel covers today; the rest take the
        sequential oracle path (config fallback knob, SURVEY.md §7).
        Topology spread and inter-pod affinity run on device via the
        sig-count kernels (ops/topology.py). Volume-bearing pods ride the
        batch too when their claims are screenable: a host-vectorized
        [P, N] bindability mask joins the static filter phase
        (ops/volume_mask.py) and the commit path re-runs the exact volume
        filters on the chosen node (VERDICT r4 item 4). Unscreenable claims
        (missing PVC, immediate-unbound) keep the oracle fallback.
        resource.k8s.io claim-bearing pods likewise ride the batch behind
        the claim-feasibility mask (backend/claim_mask.py) as long as every
        claim object resolves; a not-yet-materialized claim keeps the
        oracle path, whose PreFilter parks the pod until the resourceclaim
        controller catches up."""
        # a non-default plugin set would diverge from the compiled program's
        # semantics: only batch pods whose profile IS the default set
        if not self._framework_batchable(self.framework_for_pod(pod)):
            return False
        if pod.spec.volumes:
            if os.environ.get("KTPU_VOLUME_BATCH", "1") == "0":
                return False
            if not self._volume_masks.batchable(pod):
                return False
        if pod.spec.resource_claims:
            if os.environ.get("KTPU_DRA_BATCH", "1") == "0":
                return False
            if not self._claim_masks.batchable(pod):
                return False
        return True

    def _framework_batchable(self, fwk) -> bool:
        """True iff the profile's filter/score plugin sets and weights match
        what the compiled batch program implements (the default set). Custom
        profiles fall back to the sequential oracle path wholesale."""
        cached = self._batchable_cache.get(fwk.profile_name)
        if cached is not None:
            return cached
        from ..framework.registry import DEFAULT_PLUGINS

        ok = True
        for point in ("pre_filter", "filter", "pre_score", "score"):
            have = [(p.name(), w) for p, w in fwk.points.get(point, [])]
            want = list(DEFAULT_PLUGINS.get(point, []))
            if have != want:
                ok = False
                break
        self._batchable_cache[fwk.profile_name] = ok
        return ok

    # ------------------------------------------------------------- the batch cycle

    def schedule_batch_cycle(self) -> int:
        """Schedule up to one micro-batch; returns pods processed.

        Queue order is preserved across the batch/fallback split: pods are
        walked in pop order, consecutive batch-supported pods accumulate into
        one device call, and hitting a fallback pod first flushes the
        accumulated batch — so a high-priority fallback pod never loses its
        turn to lower-priority batched pods (reference strict-serial order)."""
        if self.informer_factory is not None:
            # the batched loop must pump the shared-informer bus exactly like
            # schedule_one does — without this the cmd-binary topology
            # (setup() wires a SharedInformerFactory) never delivers pod/node
            # events to the batched frontends and the queue stays empty.
            # Coalesced: a pump delivering a whole commit's worth of bind
            # confirmations fires ONE queue-move scan, not one per pod.
            with self.queue.coalesce_moves():
                self.informer_factory.pump()
        self._periodic_housekeeping()
        qps = self.queue.pop_batch(self.sizer.target())
        if not qps:
            # nothing new to overlap with: land the in-flight batch so its
            # failures requeue before the caller judges settlement
            self._drain_inflight()
            return 0
        # Attempt-latency clock for every pod in this batch: pop → commit.
        # Batching trades per-pod latency for throughput; the p99 of this
        # histogram is the iso-latency evidence BASELINE.md demands.
        t_pop = self.now_fn()
        pod_cycle = self.queue.scheduling_cycle

        buffer: List[QueuedPodInfo] = []
        # device breaker: while OPEN, the device path is presumed dead —
        # every pod takes the sequential oracle path and no device state is
        # touched (no rebuild+dispatch burned per cycle). allow() past the
        # (cheap, in-process) probe interval admits the next batch as the
        # half-open probe.
        device_ok = self.device_breaker.allow()
        if self._device_degraded_since is not None:
            # streaming accrual while the breaker stays open (the wire
            # service's periodic-sample pattern): consumers see degraded
            # seconds grow DURING the outage, not only after the close
            now = self.now_fn()
            self.smetrics.degraded_seconds.inc(
                value=now - self._device_degraded_since)
            self._device_degraded_since = now
        if device_ok:
            self._ensure_device()
        for qp in qps:
            pod = self.store.get_pod(qp.pod.key())
            if pod is None or pod.spec.node_name or not self._responsible_for(pod):
                latency_ledger.close_skipped(qp.pod.key(), pod)
                continue  # skipPodSchedule
            qp.pod = pod
            fwk = self.framework_for_pod(pod)
            # host-side namespace-quota gate (QuotaAdmission's PreFilter —
            # the compiled program does not model tenant quota): an
            # over-quota pod fails here without spending a device slot.
            # Usually PreEnqueue already parked it; this closes the race
            # where usage grew between enqueue and pop.
            quota_st = quota_precheck_status(fwk, pod)
            if quota_st is not None:
                self.metrics.inc("schedule_attempts")
                self._fail(fwk, qp, quota_st, pod_cycle,
                           Diagnosis(unschedulable_plugins={"QuotaAdmission"}))
                self.smetrics.observe_attempt(
                    "unschedulable", fwk.profile_name, self.now_fn() - t_pop)
                continue
            # host-side gang quorum gate (Coscheduling's PreFilter, which
            # the compiled program does not model): a member whose gang
            # cannot reach quorum — or sits in rejection backoff — fails
            # here without spending a device slot
            gang_st = gang_precheck_status(fwk, pod)
            if gang_st is not None:
                self.metrics.inc("schedule_attempts")
                self._fail(fwk, qp, gang_st, pod_cycle,
                           Diagnosis(unschedulable_plugins={"Coscheduling"}))
                self.smetrics.observe_attempt(
                    "unschedulable", fwk.profile_name, self.now_fn() - t_pop)
                continue
            batchable = self.batch_supported(pod)
            if device_ok and batchable:
                buffer.append(qp)
                continue
            if not device_ok and batchable:
                # only pods the breaker actually diverted count as degraded
                # (the permanent oracle-fallback population is not device
                # impact)
                self.device_degraded_pods += 1
                from . import telemetry

                telemetry.event("degrade", pod=pod.key(),
                                reason="device breaker open")
            # fallback pod: flush what's queued first (strict pop order) and
            # land it, then give the sequential path a fresh snapshot
            self._flush_batch(buffer, pod_cycle, t_pop)
            buffer = []
            self._drain_inflight()
            self.cache.update_snapshot(self.snapshot)
            self._schedule_fallback(qp, pod_cycle)
        self._flush_batch(buffer, pod_cycle, t_pop)
        return len(qps)

    def _periodic_housekeeping(self, now: Optional[float] = None) -> None:
        """The 1s sweep (assume expiry, permit timeouts) mutates waiting-pod
        and plugin ledger state the commit worker's Reserve/Permit phases
        also touch: land the in-flight commits first so the sweep judges
        settled state instead of racing a half-committed batch. ONE clock
        read feeds both this gate and the base sweep — two reads straddling
        the tick boundary would skip the flush yet still run the sweep,
        iterating waiting_pods while the worker parks into it. A call that
        runs a sweep (the 1s tick, the 30s flush, or a rebalancer score) is
        one scheduling.housekeeping span; the others open none."""
        from ..utils import tracing

        if now is None:
            now = self.now_fn()
        tick = now - self._last_cleanup >= 1.0
        sweep = (tick or now - self._last_unsched_flush >= 30.0
                 or (self.rebalancer is not None and self.rebalancer.due(now)))
        with (tracing.span("scheduling.housekeeping") if sweep
              else contextlib.nullcontext()):
            if (self.commit_worker is not None and tick
                    and not self.commit_worker.idle()):
                self._flush_commit_worker()
            super()._periodic_housekeeping(now)
            if self.rebalancer is not None:
                # after the sweep (settled ledgers), gated internally on the
                # score interval + commit-plane idleness
                self.rebalancer.maybe_run(now)

    def enable_rebalancer(self, **kwargs):
        """Attach the background Rebalancer (controllers/rebalance.py) —
        a second consumer of the device backend, scored and executed from
        housekeeping's idle gaps. Returns it for knob access."""
        from ..controllers.rebalance import Rebalancer

        self.rebalancer = Rebalancer(self, now_fn=kwargs.pop(
            "now_fn", self.now_fn), **kwargs)
        return self.rebalancer

    def _flush_batch(self, batched: List[QueuedPodInfo], pod_cycle: int,
                     t_pop: Optional[float] = None) -> None:
        if not batched:
            return
        from ..utils import tracing

        # one scheduling.cycle span per in-process batch: the device.* phase
        # spans below (and the overlapped commit of the PREVIOUS batch, which
        # lands inside this cycle by pipelining design) parent under it
        with tracing.span("scheduling.cycle", batch=len(batched)):
            self._flush_batch_traced(batched, pod_cycle, t_pop)

    def _flush_batch_traced(self, batched: List[QueuedPodInfo], pod_cycle: int,
                            t_pop: Optional[float] = None) -> None:
        from ..utils import tracing

        t0 = self.now_fn()
        t_pop = t_pop if t_pop is not None else t0
        mutex = self.commit_plane.device_mutex
        with tracing.span("device.encode.pipelined",
                          batch=len(batched)) as sp:
            with mutex:
                enc, outcome, released = self._try_pipelined_encode(batched)
                device = self.device  # instance the encode ran against
            if sp is not None:
                sp.attributes.update(chain=outcome, released=released)
        self.smetrics.carry_chain_batches.inc(outcome)
        if enc is not None:
            pb, et, tb, extra_mask, dra_mask = enc
            t_sync = t0  # nothing to upload: the in-flight carry IS the state
        else:
            # the drain lands the PREVIOUS batch (its commit spans are its
            # own); only sync+encode below belong to THIS batch's spans
            self._drain_inflight()
            self._ensure_device()  # the drain's commit may have killed it
            # carry-gate baseline, the releases and the snapshot in one
            # cache critical section: an event racing in after it reads as
            # a changed seq (or a pending release) on the next pipelined
            # probe, and every taken release is in the snapshot
            with self.cache.locked():
                ext_seq = self.external_change_seq()
                taken = self._take_releases()
                self.cache.update_snapshot(self.snapshot)

            def sync_encode():
                with mutex:
                    with tracing.span("device.sync") as sync_sp:
                        # releases first: the rows they free then diff
                        # equal to the snapshot and upload nothing
                        n_rel, _ = self._apply_releases(taken)
                        taken.clear()  # a retry's grown mirror syncs afresh
                        if sync_sp is not None:
                            sync_sp.attributes["released"] = n_rel
                        self.device.sync(self.snapshot)
                    self._sync_slot_reuse_metric()
                    t_sync = self.now_fn()
                    with tracing.span("device.encode", batch=len(batched)):
                        pb, et, tb, extra_mask, dra_mask = self._encode(
                            batched)
                    return (pb, et, tb, extra_mask, dra_mask, t_sync,
                            self.device)

            # a grow runs outside the mutex: it drains, and the commit
            # worker needs the mutex to finish its commits
            ok, out = device_step.retry_grown(sync_encode, self._resync_grown)
            if not ok:
                for qp in batched:  # capacities refuse to converge
                    self._schedule_fallback(qp, pod_cycle)
                return
            pb, et, tb, extra_mask, dra_mask, t_sync, device = out
            self._chain_ext_seq = ext_seq
            self._chain_dirty = False
        t_enc = self.now_fn()
        self.batch_counter += 1
        from . import telemetry

        batch_id = f"b{self.batch_counter}"
        bucket = int(pb.capacity)
        # scalar seed, not an eager jax.random.PRNGKey: the key derivation is
        # traced into the program (an eager PRNGKey costs two extra device
        # dispatches, and the host waits on both, per batch)
        key = np.int32(self.batch_counter)
        prev = self._inflight[-1] if self._inflight else None
        sample = device_step.sample_window(self.percentage_of_nodes_to_score,
                                           self.cache.node_count(),
                                           self._start_carry)
        with mutex:
            if self.device is not device:
                # a worker-side poison killed (or a rebuild replaced) the
                # device between encode and dispatch: the encoded batch
                # references dead arrays — requeue it via backoffQ exactly
                # like a poisoned in-flight batch, never dispatch it
                with self.queue.coalesce_moves():
                    for qp in batched:
                        fwk = self.framework_for_pod(qp.pod)
                        self._fail(fwk, qp, Status.error(
                            "device replaced while batch encoding"),
                            pod_cycle)
                return
            host_pb = device.encoder.last_host_pb
            # cross-batch topology carry: batch k+1 starts from the NEWEST
            # dispatch's evolved sel_counts/seg_exist (less the pods
            # released since) instead of the (stale, pre-k) host tables —
            # the ring chains carries end to end. Only valid on the
            # pipelined path — after a drain the host recounts and device.tc
            # is the truth again (prev is None then).
            carry = device.topo_carry if prev is not None else None
            mode_info = self._topo_mode_info()
            topo_mode, vd_bucket, host_key = mode_info
            telemetry.event("encode", batchId=batch_id, bucket=bucket,
                            pods=len(batched), pipelined=enc is not None)
            quota = self._quota_batch_args(batched, device, bucket)
            # slice gangs plan in-jit (ops/slice.py): the member index rides
            # in, the verdicts ride the packed block out
            slices = device_step.slice_args([qp.pod for qp in batched],
                                            device)
            span_attrs = {"topo": topo_mode}
            if topo_mode == "general":
                # the domain axis and the segment-sum form the program
                # takes at it (topology.seg_mode, the same trace-time test)
                vd = domain_axis(et.bits.shape[1], vd_bucket)
                span_attrs.update(vd=vd, seg=seg_mode(vd))
            with tracing.span("device.dispatch", **span_attrs):
                result = device_step.dispatch(
                    self.schedule_batch_fn, device, pb, et, tb, key,
                    topo_mode=topo_mode, vd_override=vd_bucket,
                    host_key=host_key,
                    ports_enabled=device.encoder.last_has_ports,
                    topo_carry=carry, extra_mask=extra_mask,
                    dra_mask=dra_mask, sample=sample, slices=slices,
                    quota=quota)
                # the program's evolved arrays (still futures) become the
                # device truth now; the host mirror advances at commit
                # (adopt_commits), where node_idx is materialized anyway
                device.adopt_device(result)
            if result.final_sample_start is not None:
                # keep the rotation index across unsampled batches too (the
                # reference's nextStartNodeIndex persists across attempts) —
                # only sampled batches advance it
                self._start_carry = result.final_sample_start
            t_dispatch = self.now_fn()
            try:
                # stage the one host-read the moment the batch is
                # dispatched: the device→host copy of the packed result
                # block rides along with the execution (and the ring's
                # later batches) instead of paying its own round-trip
                # inside commit_wait
                (result.packed if result.packed is not None
                 else result.node_idx).copy_to_host_async()
            except Exception:  # noqa: BLE001 — optional fast path only
                pass
            self._inflight.append(_Inflight(batched, result, pod_cycle,
                                            t_pop, host_pb, pb, mode_info,
                                            batch_id, bucket,
                                            device.encoder.reclaim_gen,
                                            device, t_dispatch,
                                            quota_col=quota[0] is not None))
        sig = bucket_sig(bucket, topo_mode, device.topo_enabled)
        telemetry.event("dispatch", batchId=batch_id, bucket=bucket,
                        pods=len(batched), topo=topo_mode, sig=sig,
                        packed=result.packed is not None,
                        inflight=len(self._inflight))
        # ledger: the whole batch enters device.inflight (ring dwell),
        # batchId-correlated with the flight recorder's dispatch/commit
        latency_ledger.transition_many(
            [qp.pod.key() for qp in batched], "device.inflight",
            batch_id=batch_id)
        self.smetrics.pipeline_inflight.set(value=len(self._inflight))
        # land the oldest batches beyond the ring depth: their host commits
        # overlap the device execution of everything dispatched after them
        # (depth 0 = synchronous: the batch just dispatched commits now).
        # With the commit worker the handoff is a queue push — batch K's
        # commit runs on the worker thread while this thread pops/encodes/
        # dispatches K+1. The backpressure wait (bounded worker backlog)
        # carries its own span so bench attribution can't mistake a
        # commit-bound pipeline for free overlap.
        while len(self._inflight) > self.pipeline_depth:
            fl = self._inflight.popleft()
            if self.pipeline_depth:
                self.pipelined_batches += 1
            if self.commit_worker is not None:
                # two at least: with a one-slot ring the worker still holds
                # one batch's commit while the next one waits its turn
                backlog = max(2, self.pipeline_depth)
                if self.commit_worker.depth() >= backlog:
                    with tracing.span("device.commit.backpressure"):
                        t_bp = self.now_fn()
                        self.commit_worker.wait_below(backlog)
                        self.smetrics.device_batch_duration.observe(
                            self.now_fn() - t_bp, "commit_backpressure")
                self.commit_worker.submit(fl)
            else:
                self._commit_inflight(fl)
        dur = self.smetrics.device_batch_duration
        dur.observe(t_sync - t0, "upload")
        dur.observe(t_enc - t_sync, "encode")
        dur.observe(t_dispatch - t_enc, "compute")
        self.smetrics.device_batch_size.observe(len(batched))
        # (the sizer's latency observations are fed at the commit site,
        # where the batch's own stages are known)

    def _try_pipelined_encode(self, batched: List[QueuedPodInfo]):
        """Encode the next batch for dispatch directly on the in-flight
        batch's adopted device carry — legal only when (a) nothing external
        touched the cluster since the in-flight dispatch and (b) encoding
        registers no new signature/term (a fresh row is backfilled from host
        counts that cannot see the in-flight commits). Deletions since the
        last dispatch go back on the carry first. Returns (_encode's tuple
        or None to take the drain+sync path, the outcome: "kept" or why the
        chain broke, pods released onto the carry). Caller holds the device
        mutex."""
        if not self.pipeline_depth or not self._inflight or self.device is None:
            return None, "no_ring", 0
        if self.commit_worker is not None:
            # async-commit mode: the worker's own in-progress commits dirty
            # the cache, so the has_dirty walk below cannot tell them from
            # external changes. Gate on the event-driven signals instead:
            # any external node-truth event since the chain's last full
            # sync, or a host-rejected commit (device row invalidated),
            # breaks the chain — both strictly conservative.
            if self._chain_dirty:
                return None, "dirty", 0
            seq = self.external_change_seq()
            if seq != self._chain_ext_seq:
                at, reason = self._blocked
                return None, (reason if at == seq and reason
                              else "external"), 0
            if any(qp.pod.spec.volumes for qp in batched):
                # the volume prescreen reads self.snapshot, which must not
                # be refreshed while the worker's commit tail may be
                # reading it — PVC batches take the drain+sync path
                return None, "volumes", 0
        else:
            self.cache.update_snapshot(self.snapshot)
            if self.device.has_dirty(self.snapshot):
                # external change or deletion breaks the device-carry chain
                return None, "dirty", 0
        with self.cache.locked():
            taken = self._take_releases()
        released, inexact = self._apply_releases(taken)
        if inexact:
            # a row the release could not subtract still holds its pod
            return None, "inexact", released
        st = self.device.sig_table
        vocab0 = (st.n_sigs, st.n_terms)
        try:
            enc = self._encode(batched)
        except CapacityError:
            # grow via the drain+sync path (idempotent re-encode)
            return None, "capacity", released
        if (st.n_sigs, st.n_terms) != vocab0:
            return None, "vocab", released
        if self._topo_mode_info() != self._inflight[-1].mode_info:
            # the carry shapes (seg_exist vs term_cnt, vd bucket) differ —
            # land the in-flight batches and restart the chain on host truth
            return None, "mode", released
        return enc, "kept", released

    # ------------------------------------------------------- carry releases

    @staticmethod
    def _release_blocker(pod: Pod) -> Optional[str]:
        """Why the carry cannot take a deleted bound pod's requests back
        exactly (None: it can). Host ports set bits another pod may share;
        volume and claim bookkeeping and (anti-)affinity terms live in
        tables the carry keeps no per-pod count of."""
        if pod.host_ports():
            return "ports"
        if pod.spec.volumes:
            return "pvcs"
        if pod.spec.resource_claims:
            return "claims"
        a = pod.spec.affinity
        if a is not None and (a.pod_affinity is not None
                              or a.pod_anti_affinity is not None):
            return "topology"
        return None

    def _remove_bound_pod(self, pod: Pod) -> None:
        """A completed (or evicted) bound pod: its removal from the cache
        and its release, recorded together under the cache lock. A pod the
        carry cannot take back breaks the chain as an external change."""
        blocker = self._release_blocker(pod)
        with self.cache.locked():
            removal = self.cache.remove_pod(pod)
            if blocker is None and removal is not None:
                self._releases.append((pod, removal))
                return
            self._blocked = (self._bump_external(), blocker)

    def _take_releases(self) -> List[tuple]:
        """The pending releases, emptied; caller holds the cache lock."""
        taken, self._releases = self._releases, []
        return taken

    def _apply_releases(self, taken: List[tuple]):
        """Subtract ``taken`` from the device carry and mirror; caller holds
        the device mutex. Returns (pods released, rows it could not
        subtract exactly — invalidated, which breaks the chain). Without a
        device there is nothing to subtract: the next one syncs from the
        cache, which no longer holds the pods."""
        if not taken or self.device is None:
            return 0, 0
        released, inexact = self.device.release(taken)
        if released:
            self.smetrics.carry_released_pods.inc(value=released)
        if inexact:
            self._chain_dirty = True
        return released, inexact

    def _encode(self, batched: List[QueuedPodInfo]):
        """(pb, et, tb, extra_mask, dra_mask) at the batch's sizer bucket,
        with the volume and claim masks. Caller holds the device mutex."""
        from ..ops.tiebreak import seeds_for

        bucket = self.sizer.bucket_for(len(batched))
        pb, et, tb = device_step.encode(
            self.device, [qp.pod for qp in batched], capacity=bucket,
            tie_seeds=seeds_for(batched))
        extra_mask = self._volume_masks.build(
            batched, self.snapshot, self.device.encoder,
            self.device.caps.nodes, bucket)
        dra_mask = self._claim_masks.build(batched, self.device, bucket)
        return pb, et, tb, extra_mask, dra_mask

    def _drain_inflight(self) -> None:
        """Land every in-flight batch, oldest first. With the commit worker
        this submits the remaining ring and BLOCKS on the worker's flush —
        the one synchronization point the sync/fallback/settle paths rely
        on. A device-death commit poisons the rest (worker backlog stolen
        in one sweep; ring stragglers fail the device-instance check)."""
        if self.commit_worker is not None:
            while self._inflight:
                self.commit_worker.submit(self._inflight.popleft())
            self._flush_commit_worker()
            return
        from ..utils import tracing

        with tracing.span("scheduling.drain") as sp:
            landed = 0
            while self._inflight:
                self._commit_inflight(self._inflight.popleft())
                landed += 1
            if sp is not None:
                sp.attributes["batches"] = landed

    def _flush_commit_worker(self) -> None:
        """Block until the commit worker has landed every submitted batch,
        as one scheduling.drain span counting the batches it landed."""
        from ..utils import tracing

        worker = self.commit_worker
        with tracing.span("scheduling.drain") as sp:
            landed0 = worker.committed
            try:
                worker.flush()
            finally:
                if sp is not None:
                    sp.attributes["batches"] = worker.committed - landed0

    def _commit_inflight(self, fl: _Inflight) -> None:
        """Land one dispatched batch on the host — on the scheduling thread
        (synchronous mode) or the commit worker. Materializing the PACKED
        result block (node_idx + first_fail in one buffer, its device→host
        copy already staged at dispatch) is the ONE device sync of the batch
        cycle; everything else is async dispatch. A device failure at
        materialization (e.g. the device erroring mid-flight) fails the
        whole IN-FLIGHT RING back to the queue and rebuilds the device from
        the host cache — crash-only, §5.3. Batches reaching here after a
        death (worker-ring stragglers) carry a stale device instance and
        poison individually without committing."""
        from ..utils import tracing

        from . import telemetry
        from .commit_plane import materialize_profiled

        t0 = self.now_fn()
        wait: Optional[float] = None
        disp: Optional[dict] = None
        packed_ok = fl.result.packed is not None
        mutex = self.commit_plane.device_mutex
        on_worker = self.commit_worker is not None
        if fl.device is not None and fl.device is not self.device:
            # computed on a device that has since died or been rebuilt:
            # slot maps and adopted state no longer correspond — requeue
            # without committing (the per-batch form of ring poison)
            self._poison_batches((fl,), RuntimeError(
                "device rebuilt while batch in flight"), count_breaker=False)
            return
        try:
            from ..utils import device_sync

            if self.device_fault_fn is not None:
                # scripted device fault (soak flap / chaos): surfaces at the
                # same point a real device death would — the materialization
                # read — and takes the identical poison/requeue/rebuild path
                fault = self.device_fault_fn("commit")
                if fault is not None:
                    raise fault
            device_sync.count_sync("commit-read")  # THE one blocking read per batch
            # the packed tag keeps bench critical-path attribution honest on
            # mesh-sharded runs: packed=None falls back to per-array reads,
            # a materially different commit-wait shape
            with tracing.span("device.commit.wait", batch=len(fl.qps),
                              packed="packed" if packed_ok else "fallback",
                              worker="commit" if on_worker else "inline"):
                t_wait0 = self.now_fn()
                sig = bucket_sig(
                    fl.bucket, fl.mode_info[0] if fl.mode_info else None,
                    getattr(fl.device, "topo_enabled", True))
                (node_idx, ff, slice_words, quota_words,
                 _), disp = materialize_profiled(
                    fl.result, self.device.caps.nodes,
                    program="schedule_batch", bucket=sig,
                    t_submit=fl.t_submit or None, now_fn=self.now_fn,
                    batch_id=fl.batch_id, pods=len(fl.qps),
                    quota_col=fl.quota_col,
                    event_extra={"bucket": fl.bucket})
                wait = self.now_fn() - t_wait0
                self.smetrics.device_batch_duration.observe(wait, "commit_wait")
                # residual stall: the transfer was staged at dispatch, so any
                # time spent here is the pipeline waiting on device execution
                self.smetrics.pipeline_stall_seconds.inc(value=wait)
            with mutex:
                self.device.adopt_commits(fl.result, fl.host_pb, node_idx)
            with tracing.span("host.commit", batch=len(fl.qps),
                              worker="commit" if on_worker else "inline"):
                t_host0 = self.now_fn()
                # the whole commit — winner binds AND loser requeues — runs
                # inside one queue-move coalescing window: every POD_ADD/
                # POD_DELETE wave its store events fire is one union scan
                with self.queue.coalesce_moves():
                    self._commit_batch(fl.qps, fl.result, fl.pod_cycle, fl.t0,
                                       node_idx, fl.pb, ff, fl.reclaim_gen,
                                       fl.batch_id, slice_words, quota_words)
                self.smetrics.device_batch_duration.observe(
                    self.now_fn() - t_host0, "commit_host")
            # reconcile: the commits above advanced node generations; the
            # ELIDE-ONLY reconcile refreshes _uploaded_gen for rows whose
            # content matches the adopted mirror, so the next
            # _try_pipelined_encode keeps the carry chain instead of
            # breaking it every batch. Rows needing a real upload (external
            # change, host-rejected commit repair) stay dirty → chain break
            # → safe drain+sync. A host-rejected pod's phantom topology
            # commit can thus survive in the carry for as long as the ring
            # holds already-dispatched batches (conservative direction:
            # nodes look MORE occupied), after which the break resyncs from
            # host truth. The worker reconciles against its OWN snapshot
            # (self.snapshot belongs to the scheduling thread) and reports
            # rows left dirty through the chain gate instead.
            if self.device is not None:
                with tracing.span("device.commit.reconcile",
                                  batch=len(fl.qps),
                                  worker="commit" if on_worker
                                  else "inline") as rec_sp:
                    t_rec0 = self.now_fn()
                    snap = (self._commit_snapshot if on_worker
                            else self.snapshot)
                    with mutex:
                        # the deletions this snapshot holds go back on the
                        # carry first, so their rows compare equal
                        with self.cache.locked():
                            taken = self._take_releases()
                            self.cache.update_snapshot(snap)
                        n_rel, _ = self._apply_releases(taken)
                        left = self.device.reconcile(snap)
                    if rec_sp is not None:
                        rec_sp.attributes["released"] = n_rel
                    if left:
                        self._chain_dirty = True
                    self.smetrics.device_batch_duration.observe(
                        self.now_fn() - t_rec0, "commit_reconcile")
        except Exception as exc:  # noqa: BLE001 — backend death must not kill us
            import logging

            logging.getLogger(__name__).exception("batch commit failed; requeueing")
            # everything dispatched after fl was computed on the dead
            # device; those futures are poison too. Worker mode: steal the
            # worker backlog in one sweep — ring entries still owned by the
            # scheduling thread fail the device-instance check when they
            # arrive. Synchronous mode: clear the ring here (same thread).
            with mutex:
                self.device = None  # full rebuild on next _ensure_device
            self._start_carry = None  # dead-backend future
            # the rebuilt device starts its chain from a drain+sync, never
            # from ring stragglers of the dead one
            self._chain_dirty = True
            if self.commit_worker is not None:
                stale = self.commit_worker.steal_pending()
            else:
                stale = list(self._inflight)
                self._inflight.clear()
            self._poison_batches((fl, *stale), exc)
        else:
            self.device_breaker.record_success()
            extra = {}
            if disp is not None:  # profiler on: the commit event alone can
                # spot a slow-program outlier batch on /debug/flightrecorder
                extra = {"device_ms": round(disp["execS"] * 1e3, 3),
                         "fetch_ms": round(disp["fetchS"] * 1e3, 3)}
            telemetry.event("commit", batchId=fl.batch_id, bucket=fl.bucket,
                            pods=len(fl.qps), packed=packed_ok,
                            wait_s=round(wait, 6) if wait is not None else None,
                            **extra)
            telemetry.sample_hbm()
        self.smetrics.pipeline_inflight.set(value=len(self._inflight))
        self.smetrics.device_batch_duration.observe(self.now_fn() - t0, "commit")
        # the sizer controls the latency of the batch's OWN stages: its pop
        # to dispatch on the scheduling thread (fl.t0 = its pop time) plus
        # this commit, device wait included. Its dwell in the ring and in
        # the worker's queue is left out: that is a count of batches set by
        # the ring depth and the worker's backlog, each batch's own stages
        # bounded by this same target — fed into the a+b·B fit it reads as
        # per-pod cost and halves the batch without shortening the dwell
        # (the wire path feeds its service time for the same reason). The
        # size fed is the BUCKET (padded program length) — that is what the
        # latency actually tracks. The commit-wait residual feeds the stall
        # model, which caps the bucket where device time outruns the
        # overlapped host window.
        bucket = self.sizer.bucket_for(len(fl.qps))
        own = max(fl.t_submit - fl.t0, 0.0) + (self.now_fn() - t0)
        self.sizer.update(bucket, own)
        if wait is not None:
            self.sizer.update_wait(bucket, wait)

    def _poison_batches(self, batches, exc: BaseException,
                        count_breaker: bool = True) -> None:
        """Fail dispatched-but-uncommitted batches back to the queue
        (poison + requeue flight events per batch, backoffQ re-entry per
        pod) — the shared tail of ring poison and the stale-device check.
        Requeue moves coalesce into one scan."""
        from . import telemetry

        if count_breaker:
            # device breaker: count the death; past the threshold (or on a
            # failed half-open probe) the batch path degrades to the oracle
            # until the cheap-cadence probe heals it
            self.device_breaker.record_failure(exc)
        with self.queue.coalesce_moves():
            for batch in batches:
                telemetry.event("poison", batchId=batch.batch_id,
                                bucket=batch.bucket, pods=len(batch.qps),
                                error=f"{type(exc).__name__}: {exc}"[:200])
                for qp in batch.qps:
                    fwk = self.framework_for_pod(qp.pod)
                    self._fail(fwk, qp,
                               Status.error(f"device batch failed: {exc}"),
                               batch.pod_cycle)
                telemetry.event("requeue", batchId=batch.batch_id,
                                pods=len(batch.qps))

    _VOLUME_FILTERS = frozenset((
        "VolumeRestrictions", "NodeVolumeLimits", "EBSLimits", "GCEPDLimits",
        "AzureDiskLimits", "CinderLimits", "VolumeBinding", "VolumeZone",
    ))

    def _verify_volumes_on_node(self, fwk, state: CycleState, pod: Pod,
                                node_name: str) -> Status:
        """Exact volume-filter check of the device's chosen node (the host
        half of the volume pre-pass; binder.go FindPodVolumes for ONE node)."""
        ni = self.snapshot.get(node_name)
        if ni is None or ni.node is None:
            return Status.error(f"chosen node {node_name} left the snapshot")
        for plugin, _w in fwk.points.get("filter", []):
            if plugin.name() not in self._VOLUME_FILTERS:
                continue
            st = plugin.filter(state, pod, ni)
            if not st.is_success():
                return st
        return Status()

    # default bind-path plugins that tolerate absent PreFilter state (their
    # state is only written for volume-/claim-bearing pods, and those pods
    # run the host prefilter explicitly in _commit_batch; Coscheduling's
    # Permit/Reserve recompute from the store and the waiting-pods map;
    # QuotaAdmission's Reserve charge reads only the pod + its own ledger —
    # its absence from this set silently put a FULL host PreFilter on every
    # batch-committed pod after PR 8, the single largest slice of the
    # r08-measured host.commit bottleneck)
    _DEFAULT_BIND_PATH_PLUGINS = frozenset(
        ("VolumeBinding", "DynamicResources", "Coscheduling",
         "QuotaAdmission"))

    @classmethod
    def _bind_path_needs_prefilter(cls, fwk) -> bool:
        """True when a non-default reserve/permit/pre-bind plugin is present
        (out-of-tree plugins may require PreFilter cycle state)."""
        for point in ("reserve", "permit", "pre_bind"):
            for plugin, _w in fwk.points.get(point, []):
                if plugin.name() not in cls._DEFAULT_BIND_PATH_PLUGINS:
                    return True
        return False

    def _commit_batch(self, qps: List[QueuedPodInfo], result: BatchResult,
                      pod_cycle: int, t0: float, node_idx: np.ndarray, pb,
                      ff: Optional[np.ndarray], reclaim_gen: Optional[int],
                      batch_id: str, slice_words: Optional[np.ndarray],
                      quota_words: Optional[np.ndarray]) -> None:
        """The host commit of one landed batch; the caller holds the
        queue's move-coalescing window."""
        # ledger: claim time — the batch leaves the device ring and enters
        # the host commit tail (one lock round trip for the whole batch)
        latency_ledger.transition_many(
            [qp.pod.key() for qp in qps], "commit.host", batch_id=batch_id)
        slot_names = self.device.slot_to_name()
        # ff (first_fail) normally arrives unpacked from the packed result
        # block — already on host, zero extra syncs; the lazy reads below
        # only fire for packless (sharded-core) results

        # elastic-cluster commit guard: a winner whose slot was released
        # since dispatch (node removed; possibly already reused by a NEW
        # node), or whose named node left the host cache while the batch
        # was in flight, gets a TYPED rejection + backoffQ requeue — never
        # a ghost placement on a node the kernel did not judge. O(winners).
        stale: Dict[int, str] = {}
        encoder = self.device.encoder
        to_probe: Dict[str, List[int]] = {}
        for i in range(len(qps)):
            idx = int(node_idx[i])
            if idx < 0:
                continue
            if reclaim_gen is not None and encoder.slot_stale_since(
                    idx, reclaim_gen):
                stale[i] = f"slot {idx} reclaimed since dispatch"
                continue
            name = slot_names.get(idx)
            if name is not None:
                to_probe.setdefault(name, []).append(i)
        if to_probe:
            # one cache-lock round trip for the whole batch (per-winner
            # has_real_node calls would put N acquisitions on host.commit,
            # the measured critical-path bottleneck)
            for name in self.cache.missing_real_nodes(to_probe):
                for i in to_probe[name]:
                    stale[i] = f"node {name} removed while batch in flight"

        # device over-quota screen (ops/quota.py): a SCREENED winner whose
        # charge crossed the decision-time used/limit rows surrenders its
        # placement — requeue through the quota gate, which re-judges it
        # against the authoritative host ledger. Losers never flag.
        quota_rejected: Set[int] = set()
        if quota_words is not None:
            from ..ops.quota import QUOTA_OK_BIT, QUOTA_SCREEN_BIT

            for i in range(len(qps)):
                w = int(quota_words[i])
                if (int(node_idx[i]) >= 0 and (w & QUOTA_SCREEN_BIT)
                        and not (w & QUOTA_OK_BIT)):
                    quota_rejected.add(i)

        # gang all-or-nothing (PodGroup/Coscheduling): one vmapped device
        # pass over the batch's gangs decides per-gang verdicts; any gang
        # with an unplaced member is rejected WHOLE — no member of it is
        # assumed or bound, so a partial gang can never strand (the N
        # sequential cycles the oracle path would spend are one kernel here)
        gang_rejected: Dict[int, str] = {}  # batch index -> group key
        gang_members: Dict[str, List[int]] = {}
        slice_gangs: Dict[str, List[int]] = {}
        from ..ops.slice import is_slice_pod

        for i, qp in enumerate(qps):
            gkey = pod_group_key(qp.pod)
            if gkey is not None:
                # slice gangs never take the vmapped gang kernel: their
                # verdict is already on host (planned members are pinned to
                # their torus window, so "every member landed" == placed
                # contiguously) — zero extra device dispatch, zero reads
                if is_slice_pod(qp.pod):
                    slice_gangs.setdefault(gkey, []).append(i)
                else:
                    gang_members.setdefault(gkey, []).append(i)
        if gang_members:
            gang_rejected = self._judge_gangs(qps, result, node_idx,
                                              gang_members)
        if slice_gangs:
            gang_rejected.update(self._judge_slice_gangs(
                qps, node_idx, slice_gangs, slice_words, batch_id, t0))
            gang_members = {**gang_members, **slice_gangs}
        if gang_members and (stale or quota_rejected):
            # a stale or quota-screened member poisons its WHOLE gang: the
            # kernel "placed" it (so _judge_gangs saw the gang complete),
            # but the placement is unlandable — all-or-nothing means every
            # sibling surrenders (a PodGroup never half-admits past quota)
            for gkey, idxs in gang_members.items():
                if idxs[0] in gang_rejected or not any(
                        i in stale or i in quota_rejected for i in idxs):
                    continue
                for i in idxs:
                    gang_rejected[i] = gkey
                plugin = self.framework_for_pod(
                    qps[idxs[0]].pod).plugin("Coscheduling")
                if plugin is not None:
                    plugin.reject_gang(gkey, "incomplete")

        # device preemption screen+rank, ONE call for every failed pod in the
        # batch (the batched analog of DryRunPreemption's parallel fan-out;
        # runs against the current device state, which may already include
        # the next dispatched batch's adopted commits — conservative, and the
        # host verifies the chosen candidate exactly before acting)
        preempt_hints = None
        if pb is not None and any(
            int(node_idx[i]) < 0 for i in range(len(qps))
        ) and self._preemption_wired():
            # cluster-level futility shortcut: when no assigned pod anywhere
            # has lower priority than a failed pod, eviction cannot help —
            # synthesize an all-false screen instead of running the device
            # screen program (it builds [P,N,C,R]-scale intermediates; at 5k
            # nodes on CPU fallback that one execution dominated the
            # Unschedulable row's p99)
            min_prio = self.cache.min_pod_priority()
            failed_prios = [qp.pod.spec.priority for i, qp in enumerate(qps)
                            if int(node_idx[i]) < 0]
            if min_prio is None or all(p <= min_prio for p in failed_prios):
                screen = np.zeros((len(qps), self.device.caps.nodes), bool)
                best = np.full(len(qps), -1, np.int32)
                preempt_hints = (screen, best, dict(self.device.encoder.node_slots))
            if preempt_hints is None:
                try:
                    # the screen's class refresh replaces device.nt: it must
                    # not interleave with a dispatch's adopt
                    with self.commit_plane.device_mutex:
                        pres = device_step.screen_preemption(
                            self.device, pb, result.static_masks,
                            node_idx[:len(qps)] < 0)
                    from ..utils import device_sync

                    device_sync.count_sync("preempt-read")
                    screen = np.asarray(pres.screen)
                    best = np.asarray(pres.best)
                    slot_of = dict(self.device.encoder.node_slots)
                    preempt_hints = (screen, best, slot_of)
                except Exception:  # noqa: BLE001 — hints are an optimization only
                    import logging

                    logging.getLogger(__name__).exception("preempt screen failed")

        from .commit_plane import BindItem

        bind_items: List[BindItem] = []
        for i, qp in enumerate(qps):
            pod = qp.pod
            fwk = self.framework_for_pod(pod)
            self.metrics.inc("schedule_attempts")
            idx = int(node_idx[i])
            if i in gang_rejected:
                gkey = gang_rejected[i]
                if idx >= 0:
                    # the program placed (and device-adopted) this member,
                    # but a sibling missed: surrender the placement —
                    # invalidating the row's uploaded generation makes the
                    # next sync repair the device copy from host truth
                    node_name = slot_names.get(idx)
                    if node_name is not None:
                        self._invalidate_device_row(node_name)
                    diagnosis = Diagnosis(
                        unschedulable_plugins={"Coscheduling"})
                else:
                    if ff is None:
                        from ..utils import device_sync

                        device_sync.count_sync("diagnosis-read")
                        ff = np.asarray(result.first_fail)
                    diagnosis = self._diagnose(ff[i], slot_names)
                    diagnosis.unschedulable_plugins.add("Coscheduling")
                self._fail(fwk, qp, Status.unschedulable(
                    f'gang "{gkey}" could not be fully placed'),
                    pod_cycle, diagnosis)
                self.smetrics.observe_attempt(
                    "unschedulable", fwk.profile_name, self.now_fn() - t0)
                continue
            if i in stale:
                # typed rejection: the device adopted this commit, but the
                # slot's node is gone (or the slot now names a node the
                # kernel never judged). Invalidate whatever row the slot
                # maps to so the next sync repairs the device copy, and
                # requeue via backoffQ — never bind.
                from . import telemetry

                node_name = slot_names.get(idx)
                if node_name is not None:
                    self._invalidate_device_row(node_name)
                telemetry.event("slot_reclaim", batchId=batch_id,
                                pod=pod.key(), slot=idx, reason=stale[i])
                self.metrics.inc("errors")
                self._fail(fwk, qp,
                           Status.error(f"stale placement: {stale[i]}"),
                           pod_cycle)
                self.smetrics.observe_attempt(
                    "error", fwk.profile_name, self.now_fn() - t0)
                continue
            if i in quota_rejected:
                # surrender the placement like a gang-rejected member: the
                # device adopted the commit, so repair the row from host
                # truth, and park the pod back behind the quota gate — the
                # host ledger (commit-time Reserve) stays authoritative, so
                # a stale screen row can only cost a retry, never
                # oversubscribe
                from ..framework.plugins.quota import (
                    ERR_REASON_QUOTA_EXCEEDED)

                node_name = slot_names.get(idx)
                if node_name is not None:
                    self._invalidate_device_row(node_name)
                self._fail(fwk, qp, Status.unresolvable(
                    f'{ERR_REASON_QUOTA_EXCEEDED}: namespace '
                    f'"{pod.meta.namespace}" over quota at decision time '
                    '(device screen)'),
                    pod_cycle,
                    Diagnosis(unschedulable_plugins={"QuotaAdmission"}))
                self.smetrics.observe_attempt(
                    "unschedulable", fwk.profile_name, self.now_fn() - t0)
                continue
            if idx >= 0:
                node_name = slot_names.get(idx)
                if node_name is None:  # stale slot — should not happen
                    self._fail(fwk, qp, Status.error(f"stale node slot {idx}"), pod_cycle)
                    self.smetrics.observe_attempt(
                        "error", fwk.profile_name, self.now_fn() - t0)
                    continue
                state = self._new_cycle_state()
                # Reserve/Permit/PreBind plugins may read PreFilter state;
                # with the default set only VolumeBinding/DynamicResources
                # do (both tolerate absence), so skip the per-pod host
                # prefilter for volume-less, claim-less pods — it is pure
                # overhead on the batch path. Claim pods NEED it: Reserve
                # allocates from the PreFilter claim state, and the re-read
                # also re-verifies the claims still exist at commit time.
                if (pod.spec.volumes or pod.spec.resource_claims
                        or self._bind_path_needs_prefilter(fwk)):
                    _, pre_st = fwk.run_pre_filter_plugins(state, pod)
                    if not pre_st.is_success():
                        # e.g. VolumeRestrictions' RWOP exclusivity rejects
                        # at PreFilter — semantics the compiled program does
                        # not model. The exact sequential path owns the pod
                        # (it re-runs PreFilter and records the proper
                        # unschedulable/unresolvable condition).
                        self._invalidate_device_row(node_name)
                        self.cache.update_snapshot(self.snapshot)
                        self._schedule_fallback(qp, pod_cycle)
                        continue
                if pod.spec.volumes:
                    # the device's volume screen over-admits by design
                    # (ops/volume_mask.py): re-run the EXACT volume filters
                    # on the chosen node only — this both verifies and
                    # populates VolumeBinding's node_bindings for Reserve/
                    # PreBind. O(PVs) once per pod, not per node.
                    st = self._verify_volumes_on_node(fwk, state, pod, node_name)
                    if not st.is_success():
                        # over-admitted choice: the mask was approximate for
                        # this pod. Re-batching could pick the same node
                        # (deterministic tie-break) — route to the EXACT
                        # sequential path instead, which terminates.
                        self._invalidate_device_row(node_name)
                        self.cache.update_snapshot(self.snapshot)
                        self._schedule_fallback(qp, pod_cycle)
                        continue
                if (self.comparer_every_n
                        and self.batch_scheduled % self.comparer_every_n == 0):
                    self._compare_with_oracle(fwk, pod, node_name)
                # the batched bind tail (commit_plane.py) lands the whole
                # batch's winners after the loop: one cache lock round
                # trip, one store transaction, one group-commit WAL line
                bind_items.append(BindItem(fwk, qp, pod, node_name, state))
            else:
                if ff is None:
                    # one [P, N] int8 read covers diagnosis for the whole
                    # batch (vs 8 separate mask transfers)
                    from ..utils import device_sync

                    device_sync.count_sync("diagnosis-read")
                    ff = np.asarray(result.first_fail)
                diagnosis = self._diagnose(ff[i], slot_names)
                state = self._new_cycle_state()
                if preempt_hints is not None:
                    from ..framework.plugins.defaultpreemption import DefaultPreemption

                    screen, best, slot_of = preempt_hints
                    best_name = slot_names.get(int(best[i])) if best[i] >= 0 else None
                    state.write(DefaultPreemption.HINTS_KEY,
                                (screen[i], slot_of, best_name))
                self._fail(fwk, qp, Status.unschedulable("no feasible node"), pod_cycle,
                           diagnosis, state=state)
                self.smetrics.observe_attempt(
                    "unschedulable", fwk.profile_name, self.now_fn() - t0)
        if bind_items:
            stats = self.commit_plane.commit_bindings(bind_items, pod_cycle,
                                                      t0)
            # waiting (Permit-parked) pods hold their assume exactly like
            # the per-pod path's WAIT outcome — they count as batch-landed
            self.batch_scheduled += stats.bound + stats.waiting
            for item in bind_items:
                if item.outcome == "failed":
                    # host rejected what the device already adopted (assume/
                    # reserve/bind failure): invalidate the row's uploaded
                    # generation so the next sync re-encodes it from host
                    # truth and the content diff repairs the device copy
                    self._invalidate_device_row(item.node_name)

    def _invalidate_device_row(self, node_name: str) -> None:
        """Drop a node row's uploaded generation (the next sync re-encodes
        it from host truth) and break the pipelined carry chain — the
        device adopted state the host is rejecting."""
        with self.commit_plane.device_mutex:
            if self.device is not None:
                self.device._uploaded_gen.pop(node_name, None)
        self._chain_dirty = True

    def _judge_gangs(self, qps: List[QueuedPodInfo], result: BatchResult,
                     node_idx: np.ndarray,
                     gang_members: Dict[str, List[int]]) -> Dict[int, str]:
        """Per-gang verdicts for one committed batch: run the vmapped gang
        kernel (ops/gang.py via batch.gang_verdicts) over the batch's
        gangs and return {batch index -> group key} for every member of a
        gang that must be rejected whole. Shapes are power-of-two bucketed
        so the kernel compiles once per (gangs, members) bucket."""
        from .batch import gang_verdicts
        from .claim_mask import _bucket

        keys = list(gang_members)
        g_cap = _bucket(len(keys), floor=2)
        m_cap = _bucket(max(len(v) for v in gang_members.values()), floor=2)
        member_idx = np.full((g_cap, m_cap), -1, np.int32)
        member_valid = np.zeros((g_cap, m_cap), bool)
        for g, gkey in enumerate(keys):
            for m, i in enumerate(gang_members[gkey]):
                member_idx[g, m] = i
                member_valid[g, m] = True
        kernel_ok: Optional[np.ndarray] = None
        try:
            from ..utils import device_sync
            from . import telemetry

            with telemetry.dispatch("gang_verdicts",
                                    bucket=f"{g_cap}x{m_cap}"):
                placed_all_d, kernel_ok_d, _assign = gang_verdicts(
                    result.node_idx, result.first_fail,
                    member_idx, member_valid)
            telemetry.cost_probe("gang_verdicts", f"{g_cap}x{m_cap}",
                                 gang_verdicts,
                                 (result.node_idx, result.first_fail,
                                  member_idx, member_valid))
            device_sync.count_sync("gang-read")
            placed_all = np.asarray(placed_all_d)
            kernel_ok = np.asarray(kernel_ok_d)
        except Exception:  # noqa: BLE001 — verdicts must never kill the commit
            import logging

            logging.getLogger(__name__).exception("gang kernel failed")
            placed_all = np.array([
                all(int(node_idx[i]) >= 0 for i in gang_members[k])
                for k in keys] + [True] * (g_cap - len(keys)))
        rejected: Dict[int, str] = {}
        for g, gkey in enumerate(keys):
            if bool(placed_all[g]):
                continue
            # reason by kernel verdict: "incomplete" = a distinct-node
            # cover existed on the decision-time masks but the program's
            # sequential evolution (capacity taken by earlier pods) broke
            # it; "infeasible" = no cover exists at all
            reason = ("incomplete"
                      if kernel_ok is not None and bool(kernel_ok[g])
                      else "infeasible")
            for i in gang_members[gkey]:
                rejected[i] = gkey
            fwk = self.framework_for_pod(qps[gang_members[gkey][0]].pod)
            plugin = fwk.plugin("Coscheduling")
            if plugin is not None:
                # tears down waiting members from earlier batches and arms
                # the rejection backoff (the PreFilter fast-fail window)
                plugin.reject_gang(gkey, reason)
        return rejected

    def _quota_batch_args(self, batched: List[QueuedPodInfo], device,
                          bucket: int):
        """(ns_idx, req) columns for the batch program's namespace-quota
        screen, or (None, None) when no pod rides a screened namespace.
        Syncs the quota ledger's used/limit rows (own hard + borrowable
        cohort headroom) into the device first, so the screen judges the
        freshest decision-time view. Runs under the device mutex (the
        table sync uploads tensors)."""
        plugin = self._quota_plugin()
        if plugin is None:
            return None, None
        table = plugin.device_quota_table()
        if not table and not device.nsq_slots:
            return None, None
        from ..ops.quota import build_quota_batch_args

        return build_quota_batch_args([qp.pod for qp in batched], device,
                                      table=table, pad_to=bucket)

    def _judge_slice_gangs(self, qps: List[QueuedPodInfo],
                           node_idx: np.ndarray,
                           slice_gangs: Dict[str, List[int]],
                           slice_words: Optional[np.ndarray],
                           batch_id: str, t0: float) -> Dict[int, str]:
        """Slice-gang verdicts from data already on host: the packed
        block's verdict words (plan feasibility) plus node_idx (whether
        every pinned member actually landed — the plan mask makes landing
        equivalent to contiguous placement). No kernel dispatch, no device
        read: the one-blocking-sync guard covers slice batches unchanged."""
        from . import telemetry
        from .batch import SLICE_PLAN_OK_BIT

        rejected: Dict[int, str] = {}
        now = self.now_fn()
        for gkey, idxs in slice_gangs.items():
            plan_ok = slice_words is None or all(
                int(slice_words[i]) & SLICE_PLAN_OK_BIT for i in idxs)
            if all(int(node_idx[i]) >= 0 for i in idxs):
                telemetry.event("slice_assign", batchId=batch_id, gang=gkey,
                                members=len(idxs))
                self.smetrics.slice_wait_duration.observe(
                    now - t0, "scheduled")
                continue
            # "infeasible" = the in-jit planner found no contiguous window
            # on decision-time state; "incomplete" = a window was planned
            # but a pinned member lost it to the scan's sequential evolution
            reason = "incomplete" if plan_ok else "infeasible"
            telemetry.event("slice_reject", batchId=batch_id, gang=gkey,
                            members=len(idxs), reason=reason)
            self.smetrics.slice_wait_duration.observe(now - t0, "rejected")
            for i in idxs:
                rejected[i] = gkey
            fwk = self.framework_for_pod(qps[idxs[0]].pod)
            plugin = fwk.plugin("Coscheduling")
            if plugin is not None:
                plugin.reject_gang(gkey, reason)
            sp = fwk.plugin("SlicePacking")
            if sp is not None:
                # release the oracle plan's node reservations so the retried
                # gang replans against post-rejection state
                sp.forget_gang(gkey)
        self._update_slice_frag_metrics()
        return rejected

    def _update_slice_frag_metrics(self) -> None:
        """Refresh scheduler_slice_fragmentation per superpod from the host
        mirror (numpy — no device sync) and emit an edge-triggered
        frag_alert when a superpod's score crosses the alert threshold
        (KTPU_FRAG_ALERT, default 0.5). Re-arms when the score drops back
        below, so a persistently-shredded superpod alerts once, not per
        batch."""
        device = self.device
        if device is None:
            return
        from ..ops.schema import COL_PODS
        from ..ops.slice import fragmentation_host
        from . import telemetry

        mirror = device._mirror
        valid = mirror["valid"]
        node_free = valid & (mirror["requested"][:, COL_PODS] == 0)
        rows = fragmentation_host(
            mirror["topo_sp"], mirror["topo_pos"], valid, node_free,
            (device.caps.superpods, device.caps.sp_slots))
        threshold = float(os.environ.get("KTPU_FRAG_ALERT", "0.5"))
        alerted = getattr(self, "_frag_alerted", None)
        if alerted is None:
            alerted = self._frag_alerted = set()
        for row in rows:
            self.smetrics.slice_fragmentation.set(
                str(row["sp"]), value=row["frag"])
            if row["frag"] >= threshold and row["sp"] not in alerted:
                alerted.add(row["sp"])
                telemetry.event("frag_alert", superpod=row["sp"],
                                frag=round(row["frag"], 4),
                                largestRun=row["largest_run"],
                                free=row["free"])
            elif row["frag"] < threshold:
                alerted.discard(row["sp"])

    # one immutable Status per attribution id, shared across every node and
    # every diagnosis — building 5k fresh Status objects per failed pod was
    # ~15ms of the Unschedulable row's tail
    _SHARED_STATUSES = tuple(
        Status.unschedulable(reason).with_plugin(plugin)
        for plugin, reason in ATTRIBUTION_ORDER)

    def _diagnose(self, ff_row: np.ndarray, slot_names: Dict[int, str]) -> Diagnosis:
        """Per-node first-failing plugin in filter config order, read straight
        from the device-computed first_fail ids, so failure messages and queue
        gating stay reference-shaped (SURVEY.md §8 'filter short-circuit
        semantics'). Vectorized: one nonzero pass over the row, shared Status
        instances per plugin id."""
        d = Diagnosis()
        failing = np.nonzero(ff_row)[0]
        statuses = self._SHARED_STATUSES
        for slot in failing:
            name = slot_names.get(int(slot))
            if name is None:
                continue
            st = statuses[int(ff_row[slot]) - 1]
            d.node_to_status[name] = st
            d.unschedulable_plugins.add(st.plugin)
        return d

    def _fail(self, fwk, qp: QueuedPodInfo, status: Status, pod_cycle: int,
              diagnosis: Optional[Diagnosis] = None,
              state: Optional[CycleState] = None) -> None:
        self._handle_scheduling_failure(fwk, state or CycleState(), qp, status,
                                        diagnosis or Diagnosis(), pod_cycle)

    def _preemption_wired(self) -> bool:
        """True when any profile runs a PostFilter (screen computation is
        wasted otherwise)."""
        cached = getattr(self, "_preempt_wired", None)
        if cached is None:
            cached = any(f.points.get("post_filter") for f in self.profiles.values())
            self._preempt_wired = cached
        return cached

    def _compare_with_oracle(self, fwk, pod: Pod, node_name: str) -> None:
        """Device/host comparer (§5.2): re-run the scalar oracle filters for
        this pod against the CURRENT snapshot (which reflects all commits the
        device saw before this pod, since assume updates the cache in commit
        order) and flag placements the oracle rejects."""
        import logging

        self.cache.update_snapshot(self.snapshot)
        ni = self.snapshot.get(node_name)
        self.comparer_checks += 1
        if ni is None or ni.node is None:
            self.comparer_mismatches += 1
            logging.getLogger(__name__).warning(
                "comparer: device placed %s on unknown node %s", pod.key(), node_name)
            return
        state = CycleState()
        _, status = fwk.run_pre_filter_plugins(state, pod)
        if status.is_success():
            status = fwk.run_filter_plugins(state, pod, ni)
        if not status.is_success():
            self.comparer_mismatches += 1
            logging.getLogger(__name__).warning(
                "comparer: oracle rejects device placement %s -> %s: %s",
                pod.key(), node_name, "; ".join(status.reasons))

    def warm_buckets(self, sample_pods=None) -> int:
        """Precompile the batch program at every sizer bucket for the
        CURRENT device/topo configuration (both the fresh and the
        pipelined-carry trace variants). Deadline-cut batches switch pod
        buckets at runtime; without warmup the first batch at each bucket
        pays a multi-second jit compile inside the measured window, which
        poisons both the latency histogram and the sizer's model. Returns
        the number of (bucket, variant) programs compiled/hit in cache.

        ``sample_pods``: pods shaped like the INCOMING workload (not yet in
        the store). Encoding them registers their topology signatures/terms
        first, so the warmed programs are the topo-mode variants the real
        batches will run — without a sample, a cluster whose first spread/
        affinity pods arrive in the measured window would warm the
        topology-off program and compile the topo one mid-measure."""
        from . import telemetry

        # deliberate precompilation: retraces keep counting (the bench's
        # measured-phase delta is taken after this), storms are not
        # flagged — a warmup sweep is not a mid-run bucket walk
        with telemetry.calibration():
            warmed = self._warm_buckets_inner(sample_pods)
        # the program is warm: take its heap out of the collector
        heap.settle_warm_heap(self.smetrics)
        return warmed

    def _warm_buckets_inner(self, sample_pods=None) -> int:
        from ..api.wrappers import make_pod

        self._drain_inflight()
        self._ensure_device()
        self.cache.update_snapshot(self.snapshot)
        self.device.sync(self.snapshot)
        if sample_pods:
            pods_for_warm = list(sample_pods)
        else:
            pods_for_warm = [make_pod("__bucket_warm__").req({"cpu": "1m"}).obj()]
        # registration pass: encoding the sample grows the sig/term tables
        # FIRST, so the topo-mode decision below matches what the real
        # batches will select (capacity growth retried like _flush_batch)
        ok, _ = device_step.retry_grown(
            lambda: device_step.encode(
                self.device, pods_for_warm,
                capacity=self.sizer.bucket_for(len(pods_for_warm)),
                tie_seeds=None),
            self._resync_grown)
        if not ok:
            import logging

            logging.getLogger(__name__).warning(
                "warm_buckets: capacities refused to converge for the "
                "sample; warming with unregistered topology (degraded)")
        self.device.sync(self.snapshot)  # refresh counts for new sigs
        sample = device_step.sample_window(self.percentage_of_nodes_to_score,
                                           self.cache.node_count(), None)
        topo_mode, vd_bucket, host_key = self._topo_mode_info()
        warmed = 0
        timings = []  # (bucket, warm execution seconds)
        for bucket in sorted({self.sizer.bucket_for(b)
                              for b in self.sizer._ladder()}):
            # a sample larger than the bucket truncates rather than skipping:
            # small buckets are exactly the ones deadline cuts switch to
            warm_slice = pods_for_warm[:bucket]
            try:
                pb, et, tb = device_step.encode(self.device, warm_slice,
                                                capacity=bucket,
                                                tie_seeds=None)
            except CapacityError:
                continue
            common = dict(topo_mode=topo_mode, vd_override=vd_bucket,
                          host_key=host_key,
                          ports_enabled=self.device.encoder.last_has_ports,
                          extra_mask=None, dra_mask=None, sample=sample,
                          slices=(None, None), quota=(None, None))

            def run(key=0, carry=None, **over):
                res = device_step.dispatch(
                    self.schedule_batch_fn, self.device, pb, et, tb,
                    np.int32(key), topo_carry=carry, **dict(common, **over))
                np.asarray(res.node_idx)  # land compile + first execution
                return res

            res = run()
            # ports_enabled is a static argname → two executables per bucket.
            # Warm the variant the sample did NOT exercise too, so a batch
            # whose port-bearing mix differs from the warm sample doesn't
            # compile inside the measured window.
            run(ports_enabled=not common["ports_enabled"])
            # volume workloads dispatch with an extra_mask tensor, claim
            # workloads with a dra_mask, mixed batches with both: each is a
            # trace signature of its own, and so is its carry variant, which
            # the pipelined steady state runs (PreemptionPVs compiled it
            # mid-measure). Warm each the sample exercises (all-True masks).
            ones = np.ones((bucket, self.device.caps.nodes), bool)
            has_vol = any(p.spec.volumes for p in warm_slice)
            masks = [dict(extra_mask=ones)] if has_vol else []
            if any(p.spec.resource_claims for p in warm_slice):
                masks.append(dict(dra_mask=ones))
                if has_vol:
                    masks.append(dict(extra_mask=ones, dra_mask=ones))
            for m in masks:
                res_m = run(**m)
                if res_m.final_sel_counts is not None:
                    run(carry=(res_m.final_sel_counts, res_m.final_seg_exist),
                        **m)
            warmed += 1
            # time a clean second execution: the calibration sample
            t0 = self.now_fn()
            run(key=1)
            timings.append((bucket, self.now_fn() - t0))
            if res.final_sel_counts is not None:
                # the pipelined path re-traces with a carry: warm it too.
                # BLOCK on it — an unmaterialized warm program would execute
                # lazily ahead of the first real batch and hand it a
                # multi-hundred-ms stall (the p99 tail this warmup exists
                # to remove).
                run(carry=(res.final_sel_counts, res.final_seg_exist))
                warmed += 1
            if self._preemption_wired() and res.static_masks:
                # failure-path program: the preemption screen compiles on
                # the first batch with failures — a workload whose failures
                # only appear mid-measure (Unschedulable) would pay it
                # inside the window otherwise
                try:
                    pres = device_step.screen_preemption(
                        self.device, pb, res.static_masks,
                        np.ones(len(warm_slice), bool))
                    np.asarray(pres.best)
                    warmed += 1
                except Exception:  # noqa: BLE001 — warm-only optimization
                    pass
        # completions put pods back on the carry at any count up to a
        # couple of batches' worth between two dispatches
        self.device.warm_release(2 * self.batch_size)
        self._calibrate_sizer(timings)
        return warmed

    def _calibrate_sizer(self, timings) -> None:
        """Seed the BatchSizer's latency model from the warm runs' measured
        per-bucket execution times (least squares on exec(B) = ea + eb·B).
        A batch's own stages (what the commit site feeds the sizer) hold
        its execution once, so the seed is a ≈ ea + host overhead, b ≈ eb.
        Without this the model starts from blind seeds and the first dozen
        measured batches are spent oscillating through buckets (each flip
        breaking the pipelined carry chain)."""
        if len(timings) < 2:
            return
        xs = np.array([float(b) for b, _ in timings])
        ys = np.array([t for _, t in timings])
        eb, ea = np.polyfit(xs, ys, 1)
        if eb <= 0:
            return
        s = self.sizer
        s._a = max(ea, 0.0) + 0.03
        s._b = eb
        s.updates = max(s.updates, 3)
        s._outliers = 0
        # the warm runs time EXECUTION directly (idle host): seed the stall
        # model with wait ≈ exec — conservative (the steady state subtracts
        # the overlapped host window), and the commit-site observations
        # correct it within a few batches
        s._wfit.a = max(ea, 0.0)
        s._wfit.b = eb
        s._wfit.updates = max(s._wfit.updates, 3)
        s._bucket = None  # let target() re-derive from the calibrated model
        s.target()  # pin the sticky bucket now

    def _schedule_fallback(self, qp: QueuedPodInfo, pod_cycle: int) -> None:
        """Sequential oracle path for pods the kernel doesn't cover."""
        before = self.metrics["scheduled"]
        self.schedule_one_pod(qp, pod_cycle)
        if self.metrics["scheduled"] > before:
            self.fallback_scheduled += 1

    # ------------------------------------------------------------- driving

    def run_until_settled(self, max_cycles: int = 100000, flush: bool = True,
                          idle_wait: float = 0.005, max_no_progress: int = 200) -> int:
        """Drive cycles until the queue settles (the shared batched loop,
        Scheduler.run_batched_until_settled), then land any in-flight batch."""
        cycles = self.run_batched_until_settled(
            max_cycles=max_cycles, flush=flush, idle_wait=idle_wait,
            max_no_progress=max_no_progress)
        self._drain_inflight()
        return cycles
