"""Device-runtime observability: compile/retrace ledger, dispatch
profiler, HBM & transfer telemetry, and the batch flight recorder.

PR 2 instrumented the *scheduling pipeline* (extension points, spans,
/debug); this module watches the JAX/XLA *device runtime* underneath it:

  * **CompileLedger** — every XLA backend compile is counted and timed per
    (program, bucket signature). Call sites wrap their jitted dispatches in
    ``telemetry.dispatch("schedule_batch", bucket="128/host")``; a
    ``jax.monitoring`` duration listener attributes each
    ``backend_compile_duration`` event to the active dispatch context.
    A *retrace* is any compile beyond a program's first; a *retrace storm*
    (>= STORM_RETRACES retraces of one program within STORM_WINDOW of its
    dispatches — e.g. the BatchSizer walking buckets mid-run) is flagged
    once per storm and exposed on /debug/flightrecorder and in bench
    evidence.
  * **DispatchLedger** — per-dispatch device-time attribution: every
    batch's blocking commit wait decomposes into *dwell* (submit →
    execution start, inferred from the in-flight ring overlap: the device
    serializes batches, so batch K+1 cannot start before batch K's
    execution ends), *execute* (device run time, measured by blocking on
    the device-side result before the host fetch), and *fetch* (the
    packed-block device→host transfer staged by ``copy_to_host_async`` at
    dispatch). Records feed a bounded ring (/debug/dispatch), per-
    (program, bucket) running stats, the
    ``scheduler_device_dispatch_seconds{program,phase}`` histogram, and —
    once per (program, bucket), riding the CompileLedger's first compile —
    an XLA **cost ledger** (``compiled.cost_analysis()`` flops / bytes
    accessed) so achieved FLOP/s and bytes/s are derivable per program.
  * **HBM & transfer telemetry** — ``sample_hbm()`` reads the accelerator's
    ``memory_stats()`` into ``scheduler_device_hbm_bytes{kind}`` gauges;
    ``transfer(direction, nbytes)`` accumulates per-batch host->device
    (row upload) and device->host (packed-block fetch) byte counts, also
    annotated onto the active span as ``device.upload``/``device.fetch``.
  * **FlightRecorder** — a bounded ring of structured batch lifecycle
    events (encode/dispatch/commit/poison/requeue/conflict/fence/degrade/
    takeover/packed_fallback) carrying batchId, client, epoch, bucket.
    Dumped via /debug/flightrecorder; chaos suites read it for
    postmortems instead of print-debugging.

Disabled contract (the PR 2 disabled-tracer rule): the process recorder is
``None`` by default and every hook is one module-global read before
returning — enabling the layer must change *no* scheduling decision, only
counters and the ring (tests/test_telemetry.py pins both halves).
"""

from __future__ import annotations

import contextlib
import itertools
import threading
import time
from collections import deque
from typing import Dict, List, Optional

from ..utils import tracing

_recorder: Optional["DeviceTelemetry"] = None

# the jax.monitoring event that fires exactly once per XLA backend compile
# (never on an executable-cache hit — verified against jax 0.4.x)
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

# compiles attributed while no dispatch context is open (helper jits,
# warm-path internals) land here instead of being dropped
OTHER_PROGRAM = "(other)"

# retrace-storm detector: >= STORM_RETRACES compiles of one program within
# STORM_WINDOW dispatches of that program, after its first compile
STORM_RETRACES = 3
STORM_WINDOW = 32

# The declared flight-recorder event-kind registry. Every LITERAL kind the
# package passes to ``telemetry.event(...)`` / ``FlightRecorder.record(...)``
# must appear here — enforced by ``python -m tools.ktpu_check --pass events``
# (the span-lint twin), so a new lifecycle event cannot ship unattributed:
# adding a kind means declaring it, which keeps this table the one place
# the postmortem vocabulary is documented.
EVENT_KINDS = frozenset({
    # batch lifecycle (in-process ring + wire)
    "encode", "dispatch", "commit", "poison", "requeue",
    # degradation / sessions / HA
    "conflict", "fence", "degrade", "takeover",
    # device runtime
    "packed_fallback", "retrace_storm",
    # elasticity
    "slot_reclaim", "node_remove", "evict_wave",
    # device-side fabric + replication
    "replica_down", "replica_rejoin", "failover", "replication",
    # pipelined wire transport
    "pipeline_poison", "pipeline_dup_reply",
    # slice-topology packing (ops/slice.py): per-gang torus placement
    # verdicts and the edge-triggered superpod fragmentation alert
    "slice_assign", "slice_reject", "frag_alert",
    # dispatch profiler: server-echoed device time attributed by the wire
    # client against its own transport dwell
    "wire_device_time",
    # continuous rebalancing (controllers/rebalance.py): executed migration
    # waves, the SLO-guardrail breaker tripping open, and its half-open
    # probe healing the suspension
    "rebalance_wave", "rebalance_suspended", "rebalance_resume",
    # cohort quota borrowing (framework/plugins/quota.py): loan grants,
    # executed reclaim-by-preemption waves, and the reclaim SLO breaker
    # tripping open
    "borrow_grant", "borrow_reclaim", "reclaim_suspended",
})

# The declared dispatch-program registry. Every LITERAL program name the
# package passes to ``telemetry.dispatch(...)`` must appear here, and every
# jitted entry point's host-side call sites must sit inside such a dispatch
# context — both enforced by ``python -m tools.ktpu_check --pass dispatch``,
# so a future kernel can never run device time off the ledger. Names here
# key the CompileLedger, the DispatchLedger, and the cost ledger alike.
PROGRAM_NAMES = frozenset({
    "schedule_batch",   # the batch program (backend/batch.py)
    "gang_verdicts",    # host-oracle gang re-judgement kernel
    "claim_mask",       # DRA claim feasibility screen
    "preempt_screen",   # preemption victim screen
    "apply_rows",       # device-state row upload kernel
    "release_rows",     # deleted pods' requests taken off the carry
    # ledger-only program: client-side attribution of a wire batch (the
    # record is fed from the server's echoed deviceTime, not a local jit)
    "wire_schedule_batch",
    "packing_entropy",  # whole-cluster packing scorer (controllers/rebalance.py)
})


class FlightRecorder:
    """Bounded, lock-cheap ring of batch lifecycle events. ``deque.append``
    with a maxlen is atomic under the GIL, so the hot path takes no lock;
    ``dump`` snapshots with a C-level ``list()`` the same way the queue
    dump does."""

    def __init__(self, capacity: int = 4096):
        self.capacity = capacity
        self._ring: deque = deque(maxlen=capacity)
        self._seq = itertools.count(1)
        self.recorded = 0  # total ever recorded (evictions = recorded - len)

    def record(self, etype: str, **fields) -> dict:
        ev = {"seq": next(self._seq), "t": time.time(), "type": etype}
        ev.update(fields)
        self._ring.append(ev)
        # store-of-seq, not +=: the read-modify-write would lose counts
        # under concurrent writers; a plain store of the monotone seq can
        # only transiently understate (self-heals on the next event)
        self.recorded = ev["seq"]
        return ev

    def dump(self, limit: Optional[int] = None) -> List[dict]:
        events = list(self._ring)
        if limit is not None and limit >= 0:
            events = events[-limit:] if limit else []
        return events

    def events(self, etype: Optional[str] = None,
               batch_id=None) -> List[dict]:
        """Filtered view (test/postmortem convenience)."""
        return [e for e in self._ring
                if (etype is None or e["type"] == etype)
                and (batch_id is None or e.get("batchId") == batch_id)]

    def __len__(self) -> int:
        return len(self._ring)


class CompileLedger:
    """Per-(program, bucket) XLA compile counts and times, with the
    retrace-storm detector. Attribution rides a thread-local dispatch
    context; the jax.monitoring listener calls ``record_compile`` from
    whatever thread runs the trace (the dispatching one)."""

    def __init__(self, metrics=None, flight: Optional[FlightRecorder] = None):
        # a shared list when owned by DeviceTelemetry (attach_metrics
        # appends into it), a fresh one when constructed standalone
        self.metrics_sets = (metrics if isinstance(metrics, list)
                             else [metrics] if metrics is not None else [])
        self.flight = flight
        self._lock = threading.Lock()
        self._local = threading.local()
        self.compilations: Dict[tuple, int] = {}   # (program, bucket) -> n
        self.compile_seconds: Dict[str, float] = {}  # program -> total s
        self.dispatches: Dict[str, int] = {}       # program -> dispatch count
        self.retraces: Dict[str, int] = {}         # recompiling dispatches
        self.storms: Dict[str, int] = {}           # storms flagged per program
        # deliberate-precompilation windows (warm_buckets): retraces still
        # count (bench reports measured-phase deltas), storms do not — a
        # warmup sweep compiling every bucket back-to-back is not a storm
        self.calibrating = 0
        # per program: the dispatch ordinal of its FIRST compile (one jit
        # call fires several backend sub-compiles; only a compile in a LATER
        # dispatch is a retrace) and the last dispatch already counted as a
        # retrace (so a retracing dispatch's sub-compiles count once)
        self._first_compile_disp: Dict[str, int] = {}
        self._retrace_disp: Dict[str, int] = {}
        # per program: dispatch indices at which retraces landed (bounded)
        self._compile_marks: Dict[str, deque] = {}

    @contextlib.contextmanager
    def dispatch(self, program: str, bucket: Optional[str] = None):
        """Mark ``program`` (at ``bucket``) as the owner of any XLA compile
        fired while the body runs."""
        prev = getattr(self._local, "ctx", None)
        self._local.ctx = (program, bucket or "-")
        with self._lock:
            self.dispatches[program] = self.dispatches.get(program, 0) + 1
        try:
            yield
        finally:
            self._local.ctx = prev

    @contextlib.contextmanager
    def probe_guard(self):
        """Suppress compile accounting on this thread while the dispatch
        profiler's AOT cost probe runs: ``lower().compile()`` for
        ``cost_analysis()`` duplicates a compile the ledger already counted
        (or will count) for the real dispatch, and bench fences
        compile/retrace totals."""
        self._local.probing = True
        try:
            yield
        finally:
            self._local.probing = False

    def record_compile(self, duration_s: float) -> None:
        if getattr(self._local, "probing", False):
            return
        program, bucket = getattr(self._local, "ctx", None) or (OTHER_PROGRAM,
                                                                "-")
        storm = False
        retrace = False
        with self._lock:
            key = (program, bucket)
            self.compilations[key] = self.compilations.get(key, 0) + 1
            self.compile_seconds[program] = (
                self.compile_seconds.get(program, 0.0) + duration_s)
            cur_disp = self.dispatches.get(program, 0)
            first = self._first_compile_disp.setdefault(program, cur_disp)
            if cur_disp > first and self._retrace_disp.get(program) != cur_disp:
                retrace = True
                self._retrace_disp[program] = cur_disp
                self.retraces[program] = self.retraces.get(program, 0) + 1
                if not self.calibrating:
                    marks = self._compile_marks.setdefault(
                        program, deque(maxlen=STORM_RETRACES))
                    marks.append(cur_disp)
                    if (len(marks) == STORM_RETRACES
                            and marks[-1] - marks[0] <= STORM_WINDOW):
                        self.storms[program] = self.storms.get(program, 0) + 1
                        marks.clear()  # one flag per storm, then re-arm
                        storm = True
        for m in self.metrics_sets:
            m.xla_compilations.inc(program, bucket)
            m.xla_compile_duration.observe(duration_s, program)
            if retrace:
                m.xla_retraces.inc(program)
        if storm:
            import logging

            logging.getLogger(__name__).warning(
                "XLA retrace storm: %d recompiles of %r within %d dispatches "
                "(bucket walk or shape churn mid-run)",
                STORM_RETRACES, program, STORM_WINDOW)
            if self.flight is not None:
                self.flight.record("retrace_storm", program=program,
                                   bucket=bucket)

    @contextlib.contextmanager
    def calibration(self):
        """Mark a deliberate-precompilation window (warm_buckets): compiles
        and retraces keep counting, storms are not flagged."""
        with self._lock:
            self.calibrating += 1
        try:
            yield
        finally:
            with self._lock:
                self.calibrating -= 1

    def total_compilations(self) -> int:
        with self._lock:
            return sum(self.compilations.values())

    def total_retraces(self) -> int:
        with self._lock:
            return sum(self.retraces.values())

    def dump(self) -> dict:
        with self._lock:
            return {
                "compilations": {f"{p}@{b}": n for (p, b), n
                                 in sorted(self.compilations.items())},
                "compileSeconds": {p: round(s, 4) for p, s
                                   in sorted(self.compile_seconds.items())},
                "dispatches": dict(self.dispatches),
                "retraces": dict(self.retraces),
                "storms": dict(self.storms),
            }


class DispatchLedger:
    """Per-dispatch device-time attribution: ring of timing records, per-
    (program, bucket) running stats, and the XLA cost ledger.

    The phase decomposition of one blocking commit wait:

      * **dwell** — submit → execution start. The device serializes batch
        programs, so batch K+1's execution cannot start before batch K's
        execution ends: ``exec_start = max(t_submit, prev_exec_end)``
        (clamped to ``t_exec_done``), tracked as a monotone device-busy
        horizon under the ledger lock. Under a depth-1 ring dwell is ~0;
        under pipelining it is the queueing the overlap buys.
      * **execute** — execution start → device result ready (the profiler
        blocks on the device array before the host fetch to observe this
        edge; profiler-off keeps the single opaque blocking read).
      * **fetch** — result ready → packed block on host (the
        ``copy_to_host_async`` transfer staged at dispatch).

    Each record also carries ``window``: the same three phases clamped into
    the observed wait window ``[t_wait0, t_wait_end]`` so they sum to the
    wait *exactly* — that partition backs the ``device.dispatch.*`` child
    spans under ``device.commit.wait`` and the bench waterfall.
    """

    def __init__(self, metrics=None, capacity: int = 2048,
                 compile_ledger: Optional[CompileLedger] = None):
        self.metrics_sets = (metrics if isinstance(metrics, list)
                             else [metrics] if metrics is not None else [])
        self.capacity = capacity
        self._lock = threading.Lock()
        self._ring: deque = deque(maxlen=capacity)
        self.recorded = 0
        self.stats: Dict[tuple, dict] = {}   # (program, bucket) -> sums
        self.costs: Dict[tuple, dict] = {}   # (program, bucket) -> flops/bytes
        self._last_exec_end = 0.0            # device-busy horizon (now_fn domain)
        self._compile_ledger = compile_ledger

    def record_window(self, program: str, bucket: Optional[str] = None, *,
                      t_submit: float, t_wait0: float, t_exec_done: float,
                      t_wait_end: float, batch_id: str = "", pods: int = 0,
                      fetch_bytes: int = 0) -> dict:
        """Record one dispatch from its raw timestamps (all in the caller's
        ``now_fn`` domain). ``t_submit`` is when the async dispatch
        returned; ``t_wait0``/``t_wait_end`` bracket the blocking commit
        wait; ``t_exec_done`` is when the device-side result was ready."""
        with self._lock:
            exec_start = min(max(t_submit, self._last_exec_end), t_exec_done)
            if t_exec_done > self._last_exec_end:
                self._last_exec_end = t_exec_done
        dwell = max(0.0, exec_start - t_submit)
        exec_s = max(0.0, t_exec_done - exec_start)
        fetch = max(0.0, t_wait_end - max(t_exec_done, t_wait0))
        wait = max(0.0, t_wait_end - t_wait0)
        # the wait-window partition: clamp each phase edge into the window
        # so dwell+exec+fetch == wait exactly (dwell/exec overlapped with
        # host work before t_wait0 belong to the full phases above, not to
        # the blocking wait the critical path sees)
        a = min(max(exec_start, t_wait0), t_wait_end)
        b = min(max(t_exec_done, a), t_wait_end)
        window = {"dwell": a - t_wait0, "exec": b - a, "fetch": t_wait_end - b}
        return self._commit_record(program, bucket, dwell, exec_s, fetch,
                                   wait, window, batch_id, pods, fetch_bytes)

    def record_phases(self, program: str, bucket: Optional[str] = None, *,
                      dwell_s: float, exec_s: float, fetch_s: float,
                      wait_s: Optional[float] = None, batch_id: str = "",
                      pods: int = 0, fetch_bytes: int = 0) -> dict:
        """Record one dispatch from pre-computed phase durations (the wire
        client's path: the server echoes exec/fetch, transport residual is
        the dwell). Does not move the device-busy horizon — the phases were
        measured in another process's clock domain."""
        if wait_s is None:
            wait_s = dwell_s + exec_s + fetch_s
        window = {"dwell": dwell_s, "exec": exec_s, "fetch": fetch_s}
        return self._commit_record(program, bucket, dwell_s, exec_s, fetch_s,
                                   wait_s, window, batch_id, pods, fetch_bytes)

    def _commit_record(self, program, bucket, dwell, exec_s, fetch, wait,
                       window, batch_id, pods, fetch_bytes) -> dict:
        rec = {
            "t": time.time(), "program": program, "bucket": bucket or "-",
            "batchId": batch_id, "pods": int(pods),
            "dwellS": dwell, "execS": exec_s, "fetchS": fetch,
            "waitS": wait, "fetchBytes": int(fetch_bytes), "window": window,
        }
        with self._lock:
            self._ring.append(rec)
            self.recorded += 1
            st = self.stats.setdefault((program, rec["bucket"]), {
                "count": 0, "dwellS": 0.0, "execS": 0.0, "fetchS": 0.0,
                "waitS": 0.0, "fetchBytes": 0})
            st["count"] += 1
            st["dwellS"] += dwell
            st["execS"] += exec_s
            st["fetchS"] += fetch
            st["waitS"] += wait
            st["fetchBytes"] += int(fetch_bytes)
        for m in self.metrics_sets:
            m.device_dispatch_duration.observe(dwell, program, "dwell")
            m.device_dispatch_duration.observe(exec_s, program, "exec")
            m.device_dispatch_duration.observe(fetch, program, "fetch")
        return rec

    def maybe_cost(self, program: str, bucket: Optional[str], fn,
                   args=(), kwargs=None) -> None:
        """Capture XLA ``cost_analysis()`` flops/bytes for (program, bucket)
        once: the slot is claimed (as ``{}``) before probing so a failing
        probe is never retried per batch. The probe's own AOT compile is
        suppressed from the CompileLedger via ``probe_guard`` (the real
        dispatch already accounts it)."""
        key = (program, bucket or "-")
        with self._lock:
            if key in self.costs:
                return
            self.costs[key] = {}
        cost = self._probe_cost(fn, args, kwargs or {})
        if cost:
            with self._lock:
                self.costs[key] = cost

    def _probe_cost(self, fn, args, kwargs) -> Optional[dict]:
        lower = getattr(fn, "lower", None)
        if lower is None:
            return None
        guard = (self._compile_ledger.probe_guard()
                 if self._compile_ledger is not None
                 else contextlib.nullcontext())
        try:
            with guard:
                analysis = lower(*args, **kwargs).compile().cost_analysis()
        except Exception:  # noqa: BLE001 — a backend without cost analysis
            return None
        if isinstance(analysis, (list, tuple)):  # older jax: one per device
            analysis = analysis[0] if analysis else None
        if not isinstance(analysis, dict):
            return None
        out = {}
        if analysis.get("flops") is not None:
            out["flops"] = float(analysis["flops"])
        if analysis.get("bytes accessed") is not None:
            out["bytesAccessed"] = float(analysis["bytes accessed"])
        return out or None

    def dump(self, limit: Optional[int] = None) -> dict:
        """The /debug/dispatch body: ring stats, the per-(program, bucket)
        table (with achieved FLOP/s / bytes/s where the cost ledger has the
        program's flops/bytes), and the most recent records."""
        with self._lock:
            records = list(self._ring)
            held = len(records)
            recorded = self.recorded
            stats = {k: dict(v) for k, v in self.stats.items()}
            costs = {k: dict(v) for k, v in self.costs.items()}
        if limit is not None and limit >= 0:
            records = records[-limit:] if limit else []
        programs = {}
        for (program, bucket), st in sorted(stats.items()):
            entry = {
                "count": st["count"],
                "dwellS": round(st["dwellS"], 6),
                "execS": round(st["execS"], 6),
                "fetchS": round(st["fetchS"], 6),
                "waitS": round(st["waitS"], 6),
                "fetchBytes": st["fetchBytes"],
            }
            cost = costs.get((program, bucket))
            if cost:
                entry.update(cost)
                if st["execS"] > 0 and cost.get("flops"):
                    entry["achievedFlopsPerS"] = round(
                        cost["flops"] * st["count"] / st["execS"], 1)
                if st["execS"] > 0 and cost.get("bytesAccessed"):
                    entry["achievedBytesPerS"] = round(
                        cost["bytesAccessed"] * st["count"] / st["execS"], 1)
            programs[f"{program}@{bucket}"] = entry
        out = {
            "enabled": True,
            "ring": {"capacity": self.capacity, "recorded": recorded,
                     "held": held},
            "programs": programs,
            "records": records,
        }
        if len(records) < held:
            out["truncated"] = {"records": held}
        return out


class DeviceTelemetry:
    """The process recorder: ledger + flight recorder + transfer/HBM
    counters, optionally feeding a SchedulerMetrics set."""

    def __init__(self, metrics=None, ring_capacity: int = 4096):
        self.metrics_sets = [metrics] if metrics is not None else []
        self.flight = FlightRecorder(ring_capacity)
        # the ledgers share the list object, so attach_metrics reaches all
        self.ledger = CompileLedger(self.metrics_sets, self.flight)
        self.dispatch_ledger = DispatchLedger(self.metrics_sets,
                                              compile_ledger=self.ledger)
        self._lock = threading.Lock()
        self.transfer_bytes: Dict[str, int] = {"upload": 0, "fetch": 0}
        self.transfers: Dict[str, int] = {"upload": 0, "fetch": 0}
        self.hbm: dict = {}          # last memory_stats sample (or {})
        self.hbm_peak: int = 0       # max peak_bytes_in_use ever sampled

    def attach_metrics(self, metrics) -> None:
        """Bind an ADDITIONAL SchedulerMetrics set — a second scheduler set
        up in the same process gets the telemetry families in its own
        registry instead of silently feeding the first one's."""
        if metrics is not None and all(m is not metrics
                                       for m in self.metrics_sets):
            self.metrics_sets.append(metrics)

    def event(self, etype: str, **fields) -> None:
        self.flight.record(etype, **fields)
        for m in self.metrics_sets:
            m.flight_events.inc(etype)

    def transfer(self, direction: str, nbytes: int) -> None:
        with self._lock:
            self.transfer_bytes[direction] = (
                self.transfer_bytes.get(direction, 0) + int(nbytes))
            self.transfers[direction] = self.transfers.get(direction, 0) + 1
        for m in self.metrics_sets:
            m.device_transfer_bytes.inc(direction, value=float(nbytes))
        # annotate the active span (device.sync / device.commit.wait) so the
        # bench critical path can see the bytes behind each phase
        tracing.annotate(**{f"device.{direction}": int(nbytes)})

    def sample_hbm(self) -> Optional[dict]:
        """One ``memory_stats()`` read of device 0 (a host-side C call, no
        device round-trip). Returns the sample, or None when the backend
        (CPU) exposes no stats."""
        try:
            import jax

            stats = jax.local_devices()[0].memory_stats()
        except Exception:  # noqa: BLE001 — telemetry must never take us down
            stats = None
        if not stats:
            return None
        sample = {k: stats[k] for k in
                  ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
                  if k in stats}
        with self._lock:
            self.hbm = sample
            self.hbm_peak = max(self.hbm_peak,
                                int(sample.get("peak_bytes_in_use", 0)))
        kinds = {"bytes_in_use": "in_use", "peak_bytes_in_use": "peak",
                 "bytes_limit": "limit"}
        for m in self.metrics_sets:
            for k, kind in kinds.items():
                if k in sample:
                    m.hbm_bytes.set(kind, value=float(sample[k]))
        return sample

    def dump(self, limit: Optional[int] = None) -> dict:
        """The /debug/flightrecorder body."""
        with self._lock:
            transfer = {
                "uploadBytes": self.transfer_bytes.get("upload", 0),
                "fetchBytes": self.transfer_bytes.get("fetch", 0),
                "uploads": self.transfers.get("upload", 0),
                "fetches": self.transfers.get("fetch", 0),
            }
            hbm = dict(self.hbm, peak_ever=self.hbm_peak) if self.hbm else {}
        events = self.flight.dump(limit)
        held = len(self.flight)
        out = {
            "enabled": True,
            "ring": {"capacity": self.flight.capacity,
                     "recorded": self.flight.recorded,
                     "held": held},
            "compile": self.ledger.dump(),
            "transfer": transfer,
            "hbm": hbm,
            "events": events,
        }
        if len(events) < held:
            # same cap-marker contract as every other /debug handler: a
            # capped list is never indistinguishable from a short one
            out["truncated"] = {"events": held}
        return out


# --------------------------------------------------------------- module API
#
# Every hot-path hook below starts with one read of the module global and
# returns immediately when telemetry is disabled — the near-zero disabled
# cost the tier-1 guard asserts.

_NULL_CM = contextlib.nullcontext()
_listener_installed = False


def _install_listener() -> None:
    """Register the jax.monitoring compile listener once per process: it
    feeds the CompileLedger while telemetry is on and back-fills an
    ``xla.compile`` span, ``[end - duration, end]`` on the compiling thread,
    while the tracer is on. ``enable()`` here and ``tracing.enable()`` both
    install it. Each side is disabled-guarded, so with both off it costs two
    global reads per *compile event* (compiles are rare by definition)."""
    global _listener_installed
    if _listener_installed:
        return
    try:
        import jax.monitoring as mon

        def _on_duration(name, duration_s, **kw):
            if name != _COMPILE_EVENT:
                return
            end = time.time_ns()
            t = _recorder
            if t is not None:
                try:
                    t.ledger.record_compile(duration_s)
                except Exception:  # noqa: BLE001 — never fail a compile
                    pass
            tracing.emit("xla.compile", end - int(duration_s * 1e9), end,
                         fun=kw.get("fun_name", ""))

        mon.register_event_duration_secs_listener(_on_duration)
        _listener_installed = True
    except Exception:  # noqa: BLE001 — no monitoring API: ledger stays zero
        _listener_installed = True  # don't retry per enable


tracing.on_enable(_install_listener)


def enable(metrics=None, ring_capacity: int = 4096) -> DeviceTelemetry:
    """Install the process recorder (idempotent refresh). ``metrics`` is a
    SchedulerMetrics set to feed the scheduler_xla_*/hbm/transfer/flight
    metric families; None keeps the internal counters only."""
    global _recorder
    _install_listener()
    _recorder = DeviceTelemetry(metrics, ring_capacity)
    return _recorder


def disable() -> None:
    global _recorder
    _recorder = None


def get() -> Optional[DeviceTelemetry]:
    return _recorder


def maybe_enable_from_env(metrics=None) -> None:
    """KTPU_TELEMETRY=1 turns the layer on at setup (the KTPU_TRACE_FILE
    twin); 0/unset leaves it off (the zero-cost default)."""
    import os

    if os.environ.get("KTPU_TELEMETRY") != "1":
        return
    if _recorder is None:
        enable(metrics)
    elif metrics is not None:
        # a second scheduler set up in the same process: bind its registry
        # too instead of silently feeding only the first one's
        _recorder.attach_metrics(metrics)


def event(etype: str, **fields) -> None:
    """Record one flight-recorder event; no-op when disabled (one global
    read)."""
    t = _recorder
    if t is None:
        return
    t.event(etype, **fields)


def dispatch(program: str, bucket: Optional[str] = None):
    """Compile-attribution context for one jitted dispatch; the shared
    null context manager when disabled (no allocation)."""
    t = _recorder
    if t is None:
        return _NULL_CM
    return t.ledger.dispatch(program, bucket)


def calibration():
    """Storm-suppressed precompilation window; the shared null context
    manager when disabled."""
    t = _recorder
    if t is None:
        return _NULL_CM
    return t.ledger.calibration()


def dispatch_window(program: str, bucket: Optional[str] = None,
                    **kw) -> Optional[dict]:
    """Record one dispatch's device-time decomposition from raw timestamps
    (see DispatchLedger.record_window); returns the record, or None when
    disabled (one global read)."""
    t = _recorder
    if t is None:
        return None
    return t.dispatch_ledger.record_window(program, bucket, **kw)


def dispatch_phases(program: str, bucket: Optional[str] = None,
                    **kw) -> Optional[dict]:
    """Record one dispatch from pre-computed phase durations (the wire
    client's server-echoed path); None when disabled."""
    t = _recorder
    if t is None:
        return None
    return t.dispatch_ledger.record_phases(program, bucket, **kw)


def cost_probe(program: str, bucket: Optional[str], fn,
               args=(), kwargs=None) -> None:
    """Capture the program's XLA cost analysis once per (program, bucket);
    no-op when disabled (one global read) or after the slot is claimed."""
    t = _recorder
    if t is None:
        return
    t.dispatch_ledger.maybe_cost(program, bucket, fn, args, kwargs)


def emit_phase_spans(rec: Optional[dict]) -> None:
    """Emit ``device.dispatch.{dwell,exec,fetch}`` child spans for one
    dispatch record, anchored so the window partition ends *now* — call
    inside the still-open ``device.commit.wait`` span so they parent under
    it and sum to it exactly. No-op when the record is None (profiler off)
    or tracing is disabled."""
    if rec is None:
        return
    if tracing.get() is None:
        return
    anchor = time.time_ns()
    win = rec["window"]
    end_off = 0.0
    for phase in ("fetch", "exec", "dwell"):  # walk back from the wait end
        start_off = end_off + max(0.0, win[phase])
        tracing.emit(f"device.dispatch.{phase}",
                     anchor - int(start_off * 1e9),
                     anchor - int(end_off * 1e9),
                     program=rec["program"], batchId=rec["batchId"],
                     bucket=rec["bucket"])
        end_off = start_off


def transfer(direction: str, nbytes: int) -> None:
    """Count one host<->device transfer (direction: upload|fetch)."""
    t = _recorder
    if t is None:
        return
    t.transfer(direction, nbytes)


def sample_hbm() -> None:
    t = _recorder
    if t is None:
        return
    t.sample_hbm()
