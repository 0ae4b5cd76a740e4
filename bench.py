"""Benchmark: SchedulingBasic/5000Nodes headline plus the BASELINE.md
workload matrix (SchedulingPodAntiAffinity, TopologySpreading,
SchedulingPodAffinity, PreemptionBasic at reference sizes).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.

value        = TPU-batched path throughput (pods scheduled / second, measured
               phase only, end-to-end through queue+cache+bind).
vs_baseline  = speedup over "baseline": the sequential python-oracle path in
               this repo, the stand-in for the Go kube-scheduler (no Go
               toolchain in this image). The oracle is roughly an order of
               magnitude slower than the Go scheduler, so vs_baseline
               overstates the ratio vs the real reference — compare the
               absolute pods/s instead.
attempt_latency_s = p50/p90/p99 of scheduling_attempt_duration_seconds over
               the measured phase (pop → commit per pod; BASELINE's iso-p99).
workloads    = per-workload pods/s + attempt p99 for the matrix rows.

Runs on whatever device JAX gives it and names it in the record
(``platform``, ``device_kind``, ``device_count``). It never switches to the
CPU by itself: a CPU record comes only from an explicit JAX_PLATFORMS=cpu,
and a platform that cannot initialize fails the run with no number. Every
phase runs in this one process (a chip belongs to one process at a time),
and a phase that fails makes the run exit non-zero.

Env knobs: BENCH_NODES, BENCH_INIT_PODS, BENCH_PODS, BENCH_SEQ_PODS,
BENCH_BATCH, BENCH_MATRIX=0, BENCH_BUDGET_S.
"""

from __future__ import annotations

import json
import os
import sys
import time


def build_cluster(store, n_nodes):
    from kubernetes_tpu.api.wrappers import make_node

    for i in range(n_nodes):
        store.create_node(
            make_node(f"node-{i}")
            .capacity({"cpu": "32", "memory": "128Gi", "pods": 110})
            .label("zone", f"zone-{i % 10}")
            .label("region", f"region-{i % 3}")
            .obj()
        )


def make_pods(store, name_prefix, n):
    from kubernetes_tpu.api.wrappers import make_pod

    for i in range(n):
        store.create_pod(
            make_pod(f"{name_prefix}-{i}")
            .req({"cpu": "900m", "memory": "2Gi"})
            .obj()
        )


# Every span name the package emits on the batch/wire cycle path. This is
# the critical-path attribution table: _critical_path_from_spans buckets
# cycle wall time by these names, and tools/check_metrics.py's span lint
# fails tier-1 when code emits a span that is neither listed here nor
# matched by the lint's explicit ignore list — a new phase span must either
# join the attribution or be consciously ignored, never silently dropped.
CRITICAL_PATH_SPANS = frozenset({
    "scheduling.cycle",
    "device.sync",
    "device.encode",
    "device.encode.pipelined",
    "device.dispatch",
    "device.commit",          # device-service server-side commit
    "device.commit.wait",
    # dispatch-profiler children of device.commit.wait (telemetry.py
    # emit_phase_spans): the wait's dwell/exec/fetch waterfall. Grand-
    # children of scheduling.cycle, so the cycle attribution above never
    # double-counts them; _commit_wait_breakdown consumes them instead.
    "device.dispatch.dwell",
    "device.dispatch.exec",
    "device.dispatch.fetch",
    "device.commit.reconcile",
    "device.commit.backpressure",  # dispatcher blocked on the commit worker
    "host.commit",
    "device.apply_deltas",    # wire: server half of the delta push
    "device.schedule_batch",  # wire: server half of the batch call
    # wire: client half of each call (backend/wire.py), the server halves'
    # parents: one span per ApplyDeltas part on the scheduling thread, one
    # per ScheduleBatch on the thread that sends it
    "wire.apply_deltas",
    "wire.schedule_batch",
    # the scheduling loop's own work and the runtime under it: the wait on
    # the commit worker (or the inline commits) at a drain, the cache
    # snapshot update, the housekeeping sweep, and GC pauses and XLA
    # compiles on whichever thread they hit
    "scheduling.drain",
    "cache.snapshot",
    "scheduling.housekeeping",
    "runtime.gc",
    "xla.compile",
    # once, at the end of warm-up: the collect and freeze that take the
    # warm heap out of the collector (utils/heap.py)
    "runtime.gc_freeze",
})


def _critical_path_from_spans(spans):
    """Span-based critical-path breakdown (ROADMAP PR2 follow-up): per
    scheduling.cycle span, attribute its wall time to child phase spans
    (sync/encode/dispatch + the overlapped previous batch's commit.wait /
    host.commit / commit.reconcile, which land inside the cycle by
    pipelining design) plus an "other" residual, and report which phase
    DOMINATED each cycle. Commit spans outside any cycle (queue-empty
    drains) are aggregated under "drain". The per-phase shares complement
    batch_phase_ms: means say where time goes on average, the dominant
    counts say what the slowest path through a typical cycle actually is."""
    by_id = {s.span_id: s for s in spans}
    cycles = []
    children = {}
    for s in spans:
        parent = by_id.get(s.parent_id) if s.parent_id else None
        if s.name == "scheduling.cycle":
            cycles.append(s)
        elif parent is not None and parent.name == "scheduling.cycle":
            children.setdefault(parent.span_id, []).append(s)
    if not cycles:
        return None
    dominant = {}
    totals = {}
    wall_total = 0.0
    for c in cycles:
        wall = c.duration_s
        wall_total += wall
        phase_ms = {}
        for ch in children.get(c.span_id, ()):
            phase_ms[ch.name] = phase_ms.get(ch.name, 0.0) + ch.duration_s
        other = wall - sum(phase_ms.values())
        if other > 0:
            phase_ms["other"] = other
        for name, dur in phase_ms.items():
            totals[name] = totals.get(name, 0.0) + dur
        if phase_ms:
            top = max(phase_ms, key=phase_ms.get)
            dominant[top] = dominant.get(top, 0) + 1
    # commit-WORKER spans run on their own thread with no cycle parent —
    # the commit data plane's whole point is taking host.commit OFF the
    # cycle's critical path. Bucket them separately from queue-empty
    # drains so the overlap is visible, not mistaken for drain cost.
    worker = {}
    worker_batches = 0
    for s in spans:
        if s.attributes.get("worker") != "commit":
            continue
        worker[s.name] = worker.get(s.name, 0.0) + s.duration_s
        if s.name == "host.commit":
            worker_batches += 1
    # commits that landed outside a cycle (drain at queue-empty / settle end)
    drain = sum(s.duration_s for s in spans
                if s.name.startswith(("device.commit", "host.commit"))
                and (s.parent_id not in by_id
                     or by_id[s.parent_id].name != "scheduling.cycle")
                and s.attributes.get("worker") != "commit")
    # mesh-sharded packed=None commits take the per-array fallback read —
    # a materially different commit-wait shape. Counting the tag keeps the
    # attribution honest on sharded runs instead of silently averaging two
    # different transfer regimes into one "commit.wait" number.
    fallback_commits = sum(
        1 for s in spans
        if s.name in ("device.commit.wait", "device.commit")
        and s.attributes.get("packed") == "fallback")
    out = {
        "cycles": len(cycles),
        "dominant": dict(sorted(dominant.items(), key=lambda kv: -kv[1])),
        "share_pct": {name: round(100.0 * t / max(wall_total, 1e-9), 1)
                      for name, t in sorted(totals.items(), key=lambda kv: -kv[1])},
        "cycle_wall_ms_mean": round(wall_total / len(cycles) * 1000, 2),
        "packed_fallback_commits": fallback_commits,
    }
    if drain > 0:
        out["drain_commit_ms_total"] = round(drain * 1000, 2)
    if worker_batches:
        # commit_plane evidence: per-batch mean of the worker-side commit
        # phases plus the share of cycle wall the async offload hides —
        # overlap_pct near 100 means the host commit fully rides under the
        # next batches' device execution
        wall_worker = sum(worker.values())
        out["commit_plane"] = {
            "async_batches": worker_batches,
            "worker_commit_ms_mean": round(
                worker.get("host.commit", 0.0) / worker_batches * 1000, 2),
            "worker_phase_ms_total": {
                name: round(t * 1000, 2)
                for name, t in sorted(worker.items(), key=lambda kv: -kv[1])},
            "overlap_pct": round(
                100.0 * min(wall_worker, wall_total) / max(wall_total, 1e-9),
                1),
        }
    return out


def _commit_wait_breakdown(spans):
    """Dispatch-profiler waterfall (ROADMAP item 2): decompose the total
    device.commit.wait wall into its dwell / exec / fetch children
    (telemetry.emit_phase_spans window partition — the three phases are
    clamped into the wait window, so their sum tracks the wait total by
    construction; any residual is wait time outside a profiled record,
    e.g. the ready-poll slack before the first record lands)."""
    wait_total = 0.0
    waits = 0
    phase = {"dwell": 0.0, "exec": 0.0, "fetch": 0.0}
    for s in spans:
        if s.name == "device.commit.wait":
            wait_total += s.duration_s
            waits += 1
        elif s.name.startswith("device.dispatch."):
            key = s.name[len("device.dispatch."):]
            if key in phase:
                phase[key] += s.duration_s
    if not waits or wait_total <= 0:
        return None
    return {
        "commit_wait_ms_total": round(wait_total * 1000, 2),
        "batches": waits,
        "phase_ms": {k: round(v * 1000, 2) for k, v in phase.items()},
        "share_pct": {k: round(100.0 * v / wait_total, 1)
                      for k, v in phase.items()},
        "phase_ms_per_batch": {k: round(v / waits * 1000, 3)
                               for k, v in phase.items()},
    }


def _device_program_table(tele, top_n=8):
    """Per-program device-time table from the DispatchLedger running stats
    + cost ledger: where device seconds went by program@bucket, with the
    XLA cost-analysis flops/bytes (and the achieved rates derived from
    them) when the one-shot AOT probe captured them."""
    dump = tele.dispatch_ledger.dump(limit=0)
    programs = dump.get("programs") or {}
    if not programs:
        return None
    rows = sorted(programs.items(), key=lambda kv: -kv[1].get("execS", 0.0))
    out = {}
    for name, st in rows[:top_n]:
        row = {
            "count": st["count"],
            "exec_ms_total": round(st["execS"] * 1000, 2),
            "dwell_ms_total": round(st["dwellS"] * 1000, 2),
            "fetch_ms_total": round(st["fetchS"] * 1000, 2),
            "fetch_bytes": st["fetchBytes"],
        }
        if "flops" in st:
            row["flops"] = st["flops"]
            row["bytes_accessed"] = st.get("bytesAccessed", 0)
            if "achievedFlopsPerS" in st:
                row["achieved_flops_per_s"] = round(st["achievedFlopsPerS"])
            if "achievedBytesPerS" in st:
                row["achieved_bytes_per_s"] = round(st["achievedBytesPerS"])
        out[name] = row
    return out


def run_tpu(n_nodes, n_init, n_measured, batch):
    from kubernetes_tpu.apiserver import ClusterStore
    from kubernetes_tpu.backend import TPUScheduler, telemetry
    from kubernetes_tpu.metrics import latency_ledger
    from kubernetes_tpu.utils import tracing

    store = ClusterStore()
    # comparer on (every 256th placement re-checked by the scalar oracle):
    # the throughput number carries placement-validity evidence (VERDICT r2)
    sched = TPUScheduler(store, batch_size=batch,
                         comparer_every_n=int(os.environ.get("BENCH_COMPARER_N", "256")))
    # device-runtime ledger: XLA compile counts per (program, bucket), HBM
    # stats, per-batch transfer bytes — the bench evidence for ROADMAP items
    # 1/2 (encode is device_put-heavy; 100k-node sharding is HBM-bounded)
    tele = telemetry.enable(sched.smetrics)
    # pod-lifetime latency ledger: per-pod e2e + per-segment attribution —
    # the iso-p99 evidence now covers the WHOLE pod lifetime, not just the
    # winning attempt
    latency_ledger.enable(sched.smetrics, tenant_fn=sched._ns_fair_weight)
    build_cluster(store, n_nodes)
    make_pods(store, "init", n_init)
    sched.run_until_settled()  # init phase + jit warmup
    assert sched.metrics["scheduled"] == n_init, sched.metrics
    assert not sched.settle_abandoned, "init phase abandoned with pods pending"
    # compile every deadline-cutting pod bucket OUTSIDE the measured window
    sched.warm_buckets()

    hist = sched.smetrics.scheduling_attempt_duration
    snap = hist.snapshot("scheduled", "default-scheduler")
    dur = sched.smetrics.device_batch_duration
    phase_names = ("upload", "encode", "compute", "commit",
                   "commit_wait", "commit_host", "commit_reconcile",
                   "commit_backpressure")
    # snapshot sums/counts so phase means cover ONLY the measured phase
    # (the init phase pays the one-off jit compile)
    pre = {ph: (dur.sum(ph), dur.count(ph)) for ph in phase_names}
    # span capture over the measured phase only (in-memory, ~10 spans per
    # batch): feeds the critical-path breakdown below
    own_tracer = tracing.get() is None
    exporter = tracing.enable(tracing.InMemoryExporter()).exporter \
        if own_tracer else None
    e2e_hist = sched.smetrics.pod_e2e_duration
    e2e_snap = e2e_hist.snapshot("scheduled")
    seg_hist = sched.smetrics.pod_latency_segment
    seg_pre = {lv[0]: seg_hist.sum(*lv) for lv in seg_hist.label_sets()}
    stall_pre = sched.smetrics.pipeline_stall_seconds.labels()
    coal = sched.smetrics.commit_coalesced_events
    coal_pre = {k: coal.labels(k)
                for k in ("queue_move", "wal_record", "cache_op", "post_bind")}
    cbd = sched.smetrics.commit_batch_duration
    cbd_stages = ("assume", "reserve", "permit", "pre_bind", "bind",
                  "finish", "total")
    cbd_pre = {st: (cbd.sum(st), cbd.count(st)) for st in cbd_stages}
    # measured-phase deltas of the device-runtime ledger: compiles landing
    # in HERE (after warm_buckets) are exactly the retrace cost the sizer's
    # bucket walk can inflict mid-run
    comp_pre = tele.ledger.total_compilations()
    retrace_pre = tele.ledger.total_retraces()
    xfer_pre = dict(tele.transfer_bytes)
    batches_pre = sched.batch_counter
    make_pods(store, "meas", n_measured)
    t0 = time.perf_counter()
    sched.run_until_settled()
    dt = time.perf_counter() - t0
    critical = None
    commit_wait_breakdown = None
    if exporter is not None:
        critical = _critical_path_from_spans(exporter.spans)
        commit_wait_breakdown = _commit_wait_breakdown(exporter.spans)
        tracing.disable()
    assert sched.metrics["scheduled"] == n_init + n_measured, sched.metrics
    assert not sched.settle_abandoned, "measured phase abandoned with pods pending"
    latency = {
        "p50": round(hist.percentile_since(snap, 0.50, "scheduled", "default-scheduler"), 4),
        "p90": round(hist.percentile_since(snap, 0.90, "scheduled", "default-scheduler"), 4),
        "p99": round(hist.percentile_since(snap, 0.99, "scheduled", "default-scheduler"), 4),
    }
    phases = {ph: round((dur.sum(ph) - pre[ph][0])
                        / max(dur.count(ph) - pre[ph][1], 1) * 1000, 2)
              for ph in phase_names}
    evidence = {
        "comparer_checks": sched.comparer_checks,
        "comparer_mismatches": sched.comparer_mismatches,
        "pipelined_batches": sched.pipelined_batches,
        "fallback_scheduled": sched.fallback_scheduled,
        # iso-p99 machinery (VERDICT r3 item 4): the declared deadline and
        # where the sizer converged — p99 should sit within the deadline
        "batch_deadline_ms": round(sched.sizer.deadline_s * 1000, 1),
        "batch_target_final": sched.sizer.target(),
        # async-commit-pipeline evidence: ring depth, seconds the commit
        # site blocked on device execution over the MEASURED phase only
        # (init/jit-compile waits snapshotted out, like the phase means),
        # and where the stall controller pinned the bucket
        "pipeline_depth": sched.pipeline_depth,
        "pipeline_stall_s": round(
            sched.smetrics.pipeline_stall_seconds.labels() - stall_pre, 3),
        "stall_target_ms": round(sched.sizer.stall_target_s * 1000, 1),
        # device-runtime observability (backend/telemetry.py): process-total
        # XLA compiles + retraces, the measured-phase slice (should be ~0 —
        # warm_buckets exists to keep compiles out of the window), HBM peak
        # (0 on CPU: no memory_stats), and per-batch transfer volume over
        # the measured phase (upload = row sync, fetch = packed block)
        "xla_compilations": tele.ledger.total_compilations(),
        "retraces": tele.ledger.total_retraces(),
        "measured_compilations": tele.ledger.total_compilations() - comp_pre,
        "measured_retraces": tele.ledger.total_retraces() - retrace_pre,
        "retrace_storms": sum(tele.ledger.storms.values()),
        "hbm_bytes_peak": tele.hbm_peak,
    }
    # commit data plane evidence (ROADMAP item 1): engine batch counts, the
    # per-pod deliveries coalesced into batched operations over the measured
    # phase, per-stage engine latencies, and whether the async commit worker
    # ran (platform-aware: accelerators only by default)
    evidence["commit_plane"] = {
        "engine_batches": sched.commit_plane.batches,
        "engine_pods_bound": sched.commit_plane.pods_bound,
        "worker_enabled": sched.commit_worker is not None,
        "coalesced_events": {k: round(coal.labels(k) - coal_pre[k])
                             for k in coal_pre},
        "stage_ms_mean": {
            st: round((cbd.sum(st) - cbd_pre[st][0])
                      / max(cbd.count(st) - cbd_pre[st][1], 1) * 1000, 3)
            for st in cbd_stages},
    }
    # pod-lifetime e2e over the measured phase + where the lifetime went
    # (top segment shares of the measured-phase segment-seconds delta)
    if e2e_hist.count_since(e2e_snap, "scheduled"):
        evidence["e2e_latency_s"] = {
            "p50": round(e2e_hist.percentile_since(e2e_snap, 0.50, "scheduled"), 4),
            "p99": round(e2e_hist.percentile_since(e2e_snap, 0.99, "scheduled"), 4),
        }
        seg_delta = {lv[0]: seg_hist.sum(*lv) - seg_pre.get(lv[0], 0.0)
                     for lv in seg_hist.label_sets()}
        seg_total = sum(v for v in seg_delta.values() if v > 0)
        if seg_total > 0:
            evidence["segment_shares_pct"] = {
                seg: round(100.0 * v / seg_total, 1)
                for seg, v in sorted(seg_delta.items(), key=lambda kv: -kv[1])
                if v > 0}
    meas_batches = max(sched.batch_counter - batches_pre, 1)
    evidence["upload_bytes_per_batch"] = round(
        (tele.transfer_bytes.get("upload", 0) - xfer_pre.get("upload", 0))
        / meas_batches)
    evidence["fetch_bytes_per_batch"] = round(
        (tele.transfer_bytes.get("fetch", 0) - xfer_pre.get("fetch", 0))
        / meas_batches)
    if critical is not None:
        evidence["critical_path"] = critical
    # dispatch-profiler evidence (ROADMAP item 2): the commit-wait
    # waterfall (dwell/exec/fetch shares of device.commit.wait) and the
    # per-program device-time table with cost-ledger flops/bytes
    if commit_wait_breakdown is not None:
        evidence["commit_wait_breakdown"] = commit_wait_breakdown
    device_programs = _device_program_table(tele)
    if device_programs is not None:
        evidence["device_programs"] = device_programs
    # release the module-global ledger so later rows (run_wire's Runner)
    # can own a fresh one against their own registry
    latency_ledger.disable()
    return n_measured / dt, latency, phases, evidence


# BASELINE.md canonical rows (VERDICT r3 item 5: >=9 incl. volume + churn).
# Order matters: if the bench budget runs out, later rows skip — the four
# r3-continuity rows and the newly-required failure/churn/volume rows come
# first, scoring-breadth rows last.
MATRIX_ROWS = ("SchedulingPodAntiAffinity", "TopologySpreading",
               "SchedulingPodAffinity", "PreemptionBasic",
               "Unschedulable", "SchedulingWithChurn",
               "SchedulingSecrets", "SchedulingInTreePVs", "SchedulingCSIPVs",
               "MixedSchedulingBasePod", "SchedulingPreferredPodAffinity",
               "SchedulingPreferredPodAntiAffinity",
               "SchedulingNodeAffinity", "PreferredTopologySpreading",
               "MigratedInTreePVs", "PreemptionPVs",
               "SchedulingRequiredPodAntiAffinityWithNSSelector",
               "SchedulingElastic", "SchedulingSlices", "SchedulingReplay",
               "SchedulingBorrow")


def run_matrix(budget_deadline):
    """Per-workload results (BASELINE.md matrix rows) on the batched path.

    Rows run one after another in THIS process — a chip belongs to one
    process at a time, so a child could not reach it while the parent holds
    it. A row starts only while at least 30 s of the bench budget remain;
    a row that raises records its error and fails the run."""
    out = {}
    for name in MATRIX_ROWS:
        if budget_deadline - time.perf_counter() < 30:
            out[name] = {"skipped": "bench time budget exhausted"}
            continue
        try:
            out[name] = run_matrix_row(name)
        except Exception as exc:  # noqa: BLE001 — recorded; fails the run
            out[name] = {"error": f"{type(exc).__name__}: {exc}"[:200]}
    return out


def run_matrix_row(name: str) -> dict:
    """One matrix row at the workload factory's reference-default sizes.
    Each Runner enables the latency ledger (per-workload e2e evidence,
    lifted into the row below)."""
    from kubernetes_tpu.perf.harness import run_workload
    from kubernetes_tpu.perf.workloads import TEST_CASES

    entry = {}
    if name == "SchedulingBorrow":
        # the A/B workload: the row's headline items come from the
        # borrowing-ON arm, and the OFF arm (same caps, same arrivals,
        # no cohort) supplies the baseline for the utilization-lift
        # and lender-p99-delta evidence the fence judges
        items = run_workload(TEST_CASES[name](borrowing=True),
                             backend="tpu", ledger=True)
        off_items = run_workload(TEST_CASES[name](borrowing=False),
                                 backend="tpu", ledger=True)

        def _one(data_items, label, ns=None):
            for it in data_items:
                if it.labels.get("Name") == label and (
                        ns is None or it.labels.get("namespace") == ns):
                    return it.data
            return {}

        on_inv = _one(items, "BorrowInvariants")
        off_inv = _one(off_items, "BorrowInvariants")
        on_lender = _one(items, "BorrowTenant", "borrow-lender")
        off_lender = _one(off_items, "BorrowTenant", "borrow-lender")
        entry["borrowing"] = {
            "util_mean_on": round(on_inv.get(
                "PoolUtilizationMean", 0.0), 4),
            "util_mean_off": round(off_inv.get(
                "PoolUtilizationMean", 0.0), 4),
            "util_lift": round(
                on_inv.get("PoolUtilizationMean", 0.0)
                - off_inv.get("PoolUtilizationMean", 0.0), 4),
            "reclaims": on_inv.get("Reclaims", 0.0),
            "loans_peak": on_inv.get("LoansOutstandingPeak", 0.0),
            "oversubscription": (
                on_inv.get("OversubscriptionViolations", 0.0)
                + off_inv.get("OversubscriptionViolations", 0.0)),
            "lender_p99_on_s": round(on_lender.get("E2eP99", 0.0), 4),
            "lender_p99_off_s": round(off_lender.get("E2eP99", 0.0), 4),
            "lender_p99_delta_s": round(
                on_lender.get("E2eP99", 0.0)
                - off_lender.get("E2eP99", 0.0), 4),
        }
    else:
        items = run_workload(TEST_CASES[name](), backend="tpu", ledger=True)
    for it in items:
        label = it.labels.get("Name")
        # phase-driven workloads (SchedulingElastic) emit their
        # throughput under the workload's own label, not the measured
        # SchedulingThroughput item
        if label in ("SchedulingThroughput", name):
            entry["pods_per_s"] = round(it.data["Average"], 2)
        elif label == "scheduling_attempt_duration_seconds" \
                and it.labels.get("result") == "scheduled":
            entry["attempt_p99_s"] = round(it.data["Perc99"], 4)
        elif label == "ElasticInvariants":
            # the elasticity acceptance evidence rides the bench row:
            # zero lost/oversubscribed, bounded capacity, slot reuse,
            # upload back at 0 — judged by eye/tests, not the fence
            entry["elastic"] = {k: it.data[k] for k in (
                "LostPods", "Oversubscribed", "RowCapacity",
                "SlotReuses", "UploadBytesSteady", "HbmPeakBytes")}
        elif label == "SliceStats":
            # slice-packing acceptance evidence (ISSUE 16): placement
            # quality + correctness counters ride the bench row; the
            # fence judges wait_p99_s/frag_max, the zero-counters are
            # judged by eye/tests
            entry["slices"] = {
                "frag_max": round(it.data["FragmentationMax"], 4),
                "frag_mean": round(it.data["FragmentationMean"], 4),
                "contiguity_violations": it.data["ContiguityViolations"],
                "bound_gangs": it.data["BoundSliceGangs"],
                "rejected": it.data["SliceRejected"],
                "fallback": it.data["FallbackScheduled"],
                "wait_p50_s": round(it.data["SliceWaitP50"], 4),
                "wait_p99_s": round(it.data["SliceWaitP99"], 4),
            }
        elif label == "ReplayInvariants":
            # continuous-rebalancing acceptance evidence (ISSUE 18):
            # the fence judges packing_eff (higher better) and
            # tenant_p99_s (a tenant's e2e SLO must not move); wave/
            # migration counters are judged by eye/tests
            entry["replay"] = {
                "packing_eff": round(it.data["PackingEff"], 4),
                "final_entropy": round(it.data["FinalEntropy"], 4),
                "tenant_p99_s": round(it.data["TenantP99Max"], 4),
                "waves": it.data["Waves"],
                "migrations": it.data["Migrations"],
                "suspended": it.data["Suspended"],
                "pending_at_end": it.data["PendingAtEnd"],
            }
        elif label == "pod_e2e_duration_seconds" \
                and it.labels.get("result") == "scheduled":
            # pod-lifetime e2e (latency ledger): the fence's
            # workload_e2e_p99_s tolerance judges this row r11+
            entry["e2e_p50_s"] = round(it.data["Perc50"], 4)
            entry["e2e_p99_s"] = round(it.data["Perc99"], 4)
        elif label == "pod_latency_segments":
            total = sum(v for v in it.data.values() if v > 0)
            if total > 0:
                shares = sorted(it.data.items(), key=lambda kv: -kv[1])
                entry["segments_top_pct"] = {
                    seg: round(100.0 * v / total, 1)
                    for seg, v in shares[:4] if v > 0}
    return entry


def run_wire(n_nodes=1000, n_init=200, n_measured=500, backend="wire"):
    """Transport-inclusive row: the batched device service behind a real
    localhost socket (SURVEY §5.8 hop 6) — the serialization + wire cost the
    in-process number does not pay. backend="wire" is HTTP/JSON;
    backend="grpc" is the hardened gRPC + template-dedup transport."""
    entry = {"transport": backend}

    def one(depth_env):
        """One measured run; depth_env='' keeps the session default."""
        from kubernetes_tpu.perf.harness import Runner
        from kubernetes_tpu.perf.workloads import scheduling_basic

        prior = os.environ.get("KTPU_WIRE_PIPELINE_DEPTH")
        if depth_env != "":
            os.environ["KTPU_WIRE_PIPELINE_DEPTH"] = depth_env
        try:
            test_case = scheduling_basic(nodes=n_nodes, init_pods=n_init,
                                         measured=n_measured)
            r = Runner(scheduler_config=test_case.get("schedulerConfig"),
                       backend=backend, ledger=True)
            try:
                r.run_ops(test_case["ops"])
                sched = r.scheduler
                out = {
                    "wire_pipeline_depth": getattr(
                        sched, "wire_pipeline_depth", 0),
                    "pipelined_batches": getattr(
                        sched, "pipelined_wire_batches", 0),
                }
                pipeline = getattr(sched, "_wire_pipeline", None)
                if pipeline is not None:
                    out["duplicate_replies"] = pipeline.duplicate_replies
            finally:
                r.close()
            for it in r.data_items:
                if it.labels.get("Name") == "SchedulingThroughput":
                    out["pods_per_s"] = round(it.data["Average"], 2)
                elif (it.labels.get("Name")
                      == "scheduling_attempt_duration_seconds"
                      and it.labels.get("result") == "scheduled"):
                    out["attempt_p99_s"] = round(it.data["Perc99"], 4)
                elif (it.labels.get("Name") == "pod_e2e_duration_seconds"
                      and it.labels.get("result") == "scheduled"):
                    out["e2e_p50_s"] = round(it.data["Perc50"], 4)
                    out["e2e_p99_s"] = round(it.data["Perc99"], 4)
            return out
        finally:
            if depth_env != "":
                if prior is None:
                    os.environ.pop("KTPU_WIRE_PIPELINE_DEPTH", None)
                else:
                    os.environ["KTPU_WIRE_PIPELINE_DEPTH"] = prior

    # headline row: the pipelined transport at its default depth, plus a
    # SAME-RUN depth-0 control — the pipelining lift is judged at
    # iso-conditions inside one record, not across rounds
    entry.update(one(""))
    sync = one("0")
    entry["sync_pods_per_s"] = sync.get("pods_per_s")
    entry["sync_attempt_p99_s"] = sync.get("attempt_p99_s")
    return entry


def run_pallas_check():
    """The fused Pallas step against the XLA scan: schedule a small cluster
    with the kernel as the commit program (speculative decode off, full
    batch) and again with it off; report the mode used and placement
    parity. On the chip the kernel compiles; on the CPU it runs in interpret
    mode. Raises when the kernel did not run or the placements differ."""
    import jax

    from kubernetes_tpu.api.wrappers import make_node, make_pod
    from kubernetes_tpu.apiserver import ClusterStore
    from kubernetes_tpu.backend import TPUScheduler
    from kubernetes_tpu.backend.batch import pallas_mode

    kernel = "interpret" if jax.default_backend() == "cpu" else "auto"

    def one(flag):
        knobs = {"KTPU_PALLAS": flag, "KTPU_SPEC": "0", "KTPU_FULL_BATCH": "1"}
        prior = {k: os.environ.get(k) for k in knobs}
        os.environ.update(knobs)
        try:
            store = ClusterStore()
            sched = TPUScheduler(store, batch_size=16)
            for i in range(64):
                store.create_node(
                    make_node(f"n{i}").capacity(
                        {"cpu": "8", "memory": "16Gi", "pods": 20}).obj())
            for i in range(48):
                store.create_pod(
                    make_pod(f"p{i}").req({"cpu": "1", "memory": "1Gi"}).obj())
            sched.run_until_settled()
            objs, _rv = store.list_objects("Pod")
            mode = pallas_mode(sched.device.nt, None, sched.device.topo_enabled)
            return {p.meta.name: p.spec.node_name
                    for p in objs if p.spec.node_name}, mode
        finally:
            for k, v in prior.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v

    b_pallas, mode = one(kernel)
    b_xla, _ = one("0")
    if mode != ("interpret" if kernel == "interpret" else "compiled"):
        raise RuntimeError(f"fused Pallas step did not run (mode={mode})")
    if b_pallas != b_xla or len(b_pallas) != 48:
        raise RuntimeError("Pallas placements differ from the XLA scan")
    return {"mode": mode, "placement_parity": True}


def run_agreement(n_nodes=1000, n_pods=300):
    """Default-config placement agreement (VERDICT r3 item 9): run the
    sequential oracle and the batched path over IDENTICAL clusters at the
    default config (percentageOfNodesToScore=0) and report how often they
    pick the same node. Ties break by different RNG streams (reservoir vs
    jitter), so 100% is not expected even with identical semantics; the
    companion validity signal is the in-run comparer (0 mismatches = every
    batched placement passes the oracle's filters)."""
    from kubernetes_tpu.api.wrappers import make_node, make_pod
    from kubernetes_tpu.apiserver import ClusterStore
    from kubernetes_tpu.backend import TPUScheduler
    from kubernetes_tpu.scheduler import Scheduler

    def build(store):
        # HETEROGENEOUS cluster + deterministic pre-load: on a uniform
        # empty cluster every node ties and the tie-break lottery (two
        # different RNG streams) makes exact agreement meaningless noise;
        # varied capacity/occupancy gives distinct scores so an argmax
        # divergence is a semantic signal, not luck
        for i in range(n_nodes):
            # near-unique per-node capacity: distinct LeastAllocated/
            # Balanced scores collapse the tie groups, so the tie-break
            # RNG (reservoir vs jitter — random in the reference too)
            # stops dominating the comparison
            cpu = str(8 + (i * 7) % 57)
            mem = f"{32 + (i * 11) % 193}Gi"
            store.create_node(
                make_node(f"node-{i}")
                .capacity({"cpu": cpu, "memory": mem, "pods": 110})
                .label("zone", f"zone-{i % 10}").obj())
        for i in range(n_nodes // 2):  # pre-bound load, identical per run
            store.create_pod(
                make_pod(f"pre-{i}")
                .req({"cpu": f"{(i % 7) + 1}", "memory": f"{(i % 5) + 1}Gi"})
                .node(f"node-{(i * 13) % n_nodes}").obj())

    def run(make_sched):
        store = ClusterStore()
        sched = make_sched(store)
        build(store)
        make_pods(store, "agree", n_pods)
        sched.run_until_settled()
        return {k: p.spec.node_name for k, p in store.pods.items()
                if p.spec.node_name and k.startswith("default/agree")}

    def agree(a, b):
        common = set(a) & set(b)
        same = sum(1 for k in common if a[k] == b[k])
        return {"pods": len(common),
                "exact_pct": round(100.0 * same / max(len(common), 1), 2),
                "both_scheduled": len(common) == n_pods}

    oracle = run(lambda s: Scheduler(s, seed=7))
    # default config: on CPU both paths sample adaptively, but the
    # oracle's rotating window walks the host node list while the device
    # emulation walks slot order (the DOCUMENTED divergence, PARITY
    # §2.7 P2) — they examine different subsets; and under score TIES
    # (integer-floored scores collapse hard) the tie-break RNG streams
    # differ, so exact-match is structurally low for the same reason two
    # runs of the REFERENCE disagree. Report it for transparency...
    batched = run(lambda s: TPUScheduler(s, batch_size=128, seed=7))
    entry = {"default_config_exact": agree(oracle, batched)}
    # ...and pin the real parity claim: ARGMAX-EQUIVALENCE. Replay the
    # batched path's placements pod-by-pod under ORACLE semantics
    # (full evaluation, oracle state evolution) and check each chosen
    # node is feasible and ties the oracle's best score — i.e. every
    # batch decision is one the reference could have made.
    os.environ["KTPU_FULL_BATCH"] = "1"
    try:
        batched_full = run(lambda s: TPUScheduler(s, batch_size=128, seed=7))
        entry["argmax_equivalence"] = _argmax_equivalence(
            build, batched_full, n_pods)
    finally:
        os.environ.pop("KTPU_FULL_BATCH", None)
    return entry


def _argmax_equivalence(build, placements, n_pods):
    """Replay `placements` ({pod key: node}) under oracle semantics: a fresh
    cluster, pods bound in commit order; per pod, the oracle's filter+score
    pass must accept the chosen node with a score equal to the oracle's own
    best (tie-equivalent argmax). Returns the equivalence stats."""
    from kubernetes_tpu.api.types import Binding
    from kubernetes_tpu.api.wrappers import make_pod
    from kubernetes_tpu.apiserver import ClusterStore
    from kubernetes_tpu.framework.interface import CycleState
    from kubernetes_tpu.framework.types import NodeInfo
    from kubernetes_tpu.scheduler import Scheduler

    store = ClusterStore()
    build(store)
    o = Scheduler(store, percentage_of_nodes_to_score=100, seed=7)
    equivalent = infeasible = suboptimal = 0
    for i in range(n_pods):
        key = f"default/agree-{i}"
        chosen = placements.get(key)
        if chosen is None:
            continue
        pod = make_pod(f"agree-{i}").req({"cpu": "900m", "memory": "2Gi"}).obj()
        store.create_pod(pod)
        o.cache.update_snapshot(o.snapshot)
        fwk = o.framework_for_pod(pod)
        state = CycleState()
        fwk.run_pre_filter_plugins(state, pod)
        feasible = []
        for name, ni in o.snapshot.node_info_map.items():
            if ni.node is not None and fwk.run_filter_plugins(state, pod, ni).is_success():
                feasible.append(ni)
        if chosen not in {ni.node.meta.name for ni in feasible}:
            infeasible += 1
        else:
            fwk.run_pre_score_plugins(state, pod, [ni.node for ni in feasible])
            totals = fwk.run_score_plugins(state, pod, feasible)
            if totals.get(chosen) == max(totals.values()):
                equivalent += 1
            else:
                suboptimal += 1
        # ALWAYS mirror the audited run's placement — the replay must track
        # the batched scheduler's actual state, or one early mismatch would
        # cascade spurious classifications onto every later pod
        store.bind(Binding(pod_key=key, node_name=chosen))
    checked = equivalent + infeasible + suboptimal
    return {
        "pods": checked,
        "equivalent_pct": round(100.0 * equivalent / max(checked, 1), 2),
        "infeasible": infeasible,
        "suboptimal": suboptimal,
    }


def run_sequential(n_nodes, n_init, n_measured):
    from kubernetes_tpu.apiserver import ClusterStore
    from kubernetes_tpu.scheduler import Scheduler

    store = ClusterStore()
    sched = Scheduler(store)
    build_cluster(store, n_nodes)
    make_pods(store, "init", n_init)
    sched.run_until_settled()
    make_pods(store, "meas", n_measured)
    t0 = time.perf_counter()
    sched.run_until_settled()
    dt = time.perf_counter() - t0
    assert sched.metrics["scheduled"] == n_init + n_measured, sched.metrics
    return n_measured / dt


def _write_trend(record: dict) -> None:
    """Side-effect artifact: TREND.md/json comparing this run against every
    committed BENCH_r*.json (regressions >20% flagged loudly). Never breaks
    the one-JSON-line stdout contract.

    Write-once guard (VERDICT r4 weak #5): smoke/test invocations of bench.py
    must not clobber the round's recorded trend. TREND.* is only written when
    this run is explicitly the round bench: `--record` argv flag or
    BENCH_RECORD=1 in the environment."""
    if "--record" not in sys.argv and os.environ.get("BENCH_RECORD") != "1":
        return
    try:
        sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "tools"))
        from trend import write_trend

        doc = write_trend(record)
        if doc.get("regressions"):
            record["trend_regressions"] = doc["regressions"]
    except Exception:  # noqa: BLE001 — trend is evidence, not a gate
        pass


def run_fence(argv) -> int:
    """SLO regression fence: compare a bench record against the prior
    BENCH_r*.json/TREND history (tools/trend.py declared tolerances) and
    exit nonzero on a violating regression.

    The record under judgment is, in order: an explicit path after
    ``--fence``, ``$BENCH_FENCE_RECORD``, else the NEWEST committed
    BENCH_r*.json (so `bench.py --record && bench.py --fence` is the CI
    gate: measure, snapshot, then refuse the merge if the snapshot
    regressed). Prints one JSON line either way."""
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools"))
    from trend import _load_rounds, fence, recover_record

    import re

    idx = argv.index("--fence")
    path = next((a for a in argv[idx + 1:] if not a.startswith("-")), None)
    path = path or os.environ.get("BENCH_FENCE_RECORD")
    rounds = _load_rounds()
    if path:
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, json.JSONDecodeError) as exc:
            print(json.dumps({"metric": "slo_fence",
                              "error": f"unreadable record {path}: {exc}"}))
            return 2
        # same recovery rule as _load_rounds: parsed, else the record
        # rebuilt from a parsed:null wrapper's stdout tail, else the doc
        # itself (a bare record) — fencing a recoverable snapshot by name
        # must not fail where the no-arg mode would judge it
        current = recover_record(doc) or doc
        # the record under judgment must never be its own baseline: a path
        # naming a committed round (CI fencing the file --record just
        # wrote) drops that round from the prior pool
        m = re.search(r"BENCH_r(\d+)\.json$", os.path.abspath(path))
        if m:
            rounds = [r for r in rounds if r.get("_round") != int(m.group(1))]
    else:
        if not rounds:
            print(json.dumps({"metric": "slo_fence",
                              "error": "no BENCH_r*.json snapshots to judge"}))
            return 2
        # rounds[-1] is the newest RECOVERABLE round; if a newer snapshot
        # exists on disk but was dropped (parsed:null with an unrecoverable
        # tail), judging the older one would green-light the exact run the
        # gate cannot see — refuse instead
        from trend import round_files
        newest = max((n for n, _ in round_files()), default=None)
        if newest is not None and newest != rounds[-1].get("_round"):
            print(json.dumps({"metric": "slo_fence",
                              "error": f"newest snapshot BENCH_r{newest:02d}"
                                       ".json is unjudgeable (parsed:null, "
                                       "unrecoverable tail); refusing to "
                                       "judge an older round in its place"}))
            return 2
        current, rounds = rounds[-1], rounds[:-1]
    if current.get("value") is None:
        # an unjudgeable record (e.g. a parsed:null wrapper) must FAIL the
        # gate distinctly, not sail through with zero checks performed
        print(json.dumps({"metric": "slo_fence",
                          "error": "record carries no judgeable fields "
                                   "(no 'value'); refusing to pass the gate"}))
        return 2
    out = fence(current, rounds)
    out["record"] = path or f"BENCH_r{current.get('_round', '?')}.json"
    if not out["checked"]:
        if out.get("epochBoundary"):
            # a DECLARED platform-epoch boundary (trend.PLATFORM_EPOCHS):
            # earlier rounds exist but were measured on a different
            # environment class, so "no baseline" is the reviewed,
            # committed state — pass with the note, don't fail closed
            print(json.dumps({"metric": "slo_fence", "violations": 0,
                              "fence": out}))
            return 0
        # zero comparisons performed (e.g. no same-platform baseline
        # round): the gate has judged NOTHING and must say so, not pass
        print(json.dumps({"metric": "slo_fence",
                          "error": "no comparison performed "
                                   f"({out.get('note', 'checked=0')}); "
                                   "refusing to pass the gate",
                          "fence": out}))
        return 2
    print(json.dumps({"metric": "slo_fence",
                      "violations": len(out["violations"]), "fence": out}))
    return 1 if out["violations"] else 0


def main():
    if "--fence" in sys.argv:
        raise SystemExit(run_fence(sys.argv))
    import jax

    # no fallback: a platform that cannot initialize raises here, before any
    # number exists; CPU only when JAX_PLATFORMS=cpu says so
    devices = jax.devices()

    n_nodes = int(os.environ.get("BENCH_NODES", 5000))
    n_init = int(os.environ.get("BENCH_INIT_PODS", 1000))
    n_measured = int(os.environ.get("BENCH_PODS", 1000))
    n_seq = int(os.environ.get("BENCH_SEQ_PODS", 100))
    batch = int(os.environ.get("BENCH_BATCH", 512))

    record = {
        "metric": f"scheduling_throughput SchedulingBasic/{n_nodes}Nodes",
        "value": 0.0,
        "unit": "pods/s",
        "vs_baseline": 0.0,
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        # The sequential path is this repo's Python oracle scheduler, NOT the
        # Go kube-scheduler (no Go toolchain in this image) — it is roughly an
        # order of magnitude slower than the Go scheduler it stands in for.
        "baseline": "python-oracle",
    }
    budget_deadline = time.perf_counter() + float(os.environ.get("BENCH_BUDGET_S", "5400"))
    failed = False
    try:
        tpu_tput, latency, phases, evidence = run_tpu(n_nodes, n_init, n_measured, batch)
        seq_tput = run_sequential(n_nodes, min(100, n_init), n_seq)
        record["value"] = round(tpu_tput, 2)
        record["vs_baseline"] = round(tpu_tput / seq_tput, 2)
        record["attempt_latency_s"] = latency
        record["batch_phase_ms"] = phases
        record["baseline_pods_per_s"] = round(seq_tput, 2)
        record.update(evidence)
        record["pallas"] = run_pallas_check()
        if os.environ.get("BENCH_AGREEMENT", "1") != "0":
            record["agreement"] = run_agreement()
        if os.environ.get("BENCH_WIRE", "1") != "0":
            record["wire"] = run_wire(min(n_nodes, 1000))
            record["wire_grpc"] = run_wire(min(n_nodes, 1000), backend="grpc")
        if os.environ.get("BENCH_MATRIX", "1") != "0":
            record["workloads"] = run_matrix(budget_deadline)
            failed = any("error" in row for row in record["workloads"].values())
        _write_trend(record)
    except Exception as exc:  # noqa: BLE001 — recorded, then the run fails
        record["error"] = f"{type(exc).__name__}: {exc}"[:300]
        failed = True
    print(json.dumps(record))
    if failed:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
