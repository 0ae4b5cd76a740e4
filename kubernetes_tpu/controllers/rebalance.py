"""Continuous rebalancing: an SLO-guarded descheduler (ROADMAP item 3).

One-shot placement decays under churn — pods come and go, and a week of
elastic arrivals leaves the load smeared thin across many half-empty
nodes even though the batch program packed every individual decision
well.  Production clusters run a descheduler for exactly this reason.
The ``Rebalancer`` here is that background pass, built as a second
*consumer* of the device backend:

  * **Scoring** — the whole cluster's packing is judged by a small
    device program (``packing_entropy``): normalized Shannon entropy of
    the per-node used-resource distribution, per resource axis.  Load
    spread evenly over every node scores ~1.0 (maximally fragmented);
    load consolidated onto few nodes scores low.  On device-backed
    schedulers the inputs are the device mirror's own row tensors —
    read under the commit plane's device mutex, dispatched only in the
    idle gaps ``CommitWorker.idle()`` exposes, so scoring never delays
    a scheduling batch.  PR 15's per-superpod slice fragmentation rides
    along as a second trigger axis.
  * **Migration waves** — when the trigger band is exceeded, the
    lowest-occupancy victim nodes (bounded by a per-wave migration
    budget) are pushed through ``DrainOrchestrator.drain_wave`` with
    ``uncordon_after=True``: gang-atomic closure, PDB budget gate,
    evict-then-requeue on the existing backoffQ/ledger paths.  Because
    the victims are cordoned until their pods re-bind ELSEWHERE, the
    wave consolidates regardless of the scoring strategy — and a
    crashed or killed wave degrades to plain requeues: zero lost pods,
    zero double-binds, at worst a node left cordoned.
  * **Self-defense** — hysteresis (arm above the high-water band,
    re-arm only after recovering below the low-water band), a per-wave
    cooldown, and an **SLO guardrail circuit breaker**: between waves
    the Rebalancer reads the PR 14 per-tenant e2e histograms and trips
    OPEN (``rebalance_suspended`` flight event, gauge 1) when any
    tenant's windowed p99 regresses past the fence tolerance of its
    pre-wave baseline.  The breaker heals through the same half-open
    probe discipline as the device breakers: after the probe interval
    one wave is admitted, and only a clean SLO check closes it.

Threading: the Rebalancer runs on the scheduling thread (driven from
``_periodic_housekeeping``), so its own state needs no lock; the only
shared surface it touches is the device, serialized by the commit
plane's ``DeviceMutex`` — which KTPU_LOCKTRACE traces end to end.
"""

from __future__ import annotations

import time
from collections import deque
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..backend import telemetry
from ..backend.circuit import CircuitBreaker

#: resource axes of the [N, 4] requested/allocatable row blocks
#: (ops/schema.py COL_* order)
AXIS_NAMES = ("cpu", "memory", "ephemeral", "pods")


@jax.jit
def packing_entropy(requested: jax.Array, valid: jax.Array):
    """Per-axis normalized bin-packing entropy over valid nodes.

    ``requested`` [N, R] float32 used resources per node, ``valid`` [N]
    bool.  Each axis's usage is normalized into a distribution over
    nodes; its Shannon entropy, divided by log(n_valid), lands in
    [0, 1]: 1.0 = spread perfectly evenly (worst packing), ->0 = all
    load on one node.  Axes with zero total usage are dead and excluded
    from the mean.  Returns (mean_entropy scalar, per_axis [R])."""
    used = jnp.where(valid[:, None], requested, 0.0)
    total = jnp.sum(used, axis=0)                              # [R]
    p = used / jnp.maximum(total[None, :], 1e-9)
    h = -jnp.sum(jnp.where(p > 0, p * jnp.log(p), 0.0), axis=0)  # [R]
    n = jnp.maximum(jnp.sum(valid.astype(jnp.float32)), 2.0)
    per_axis = h / jnp.log(n)
    live = total > 0
    mean = (jnp.sum(jnp.where(live, per_axis, 0.0))
            / jnp.maximum(jnp.sum(live.astype(jnp.float32)), 1.0))
    return mean, jnp.where(live, per_axis, 0.0)


def _entropy_of(requested: np.ndarray, valid: np.ndarray) -> Dict[str, float]:
    """Dispatch the scorer and pull the scalars host-side."""
    with telemetry.dispatch("packing_entropy", bucket=str(len(valid))):
        mean_d, per_axis_d = packing_entropy(
            jnp.asarray(requested, jnp.float32), jnp.asarray(valid, bool))
    per_axis = np.asarray(per_axis_d)
    out = {"entropy": float(np.asarray(mean_d))}
    for i, name in enumerate(AXIS_NAMES[:per_axis.shape[0]]):
        out[f"entropy_{name}"] = float(per_axis[i])
    return out


def score_cluster(sched) -> Optional[Dict[str, float]]:
    """Whole-cluster packing score for any scheduler flavor.

    Device-backed schedulers are scored from the device mirror (the
    tensors the batch program itself packs against) under the device
    mutex; plain oracle schedulers fall back to the host snapshot, so
    the replay harness can A/B oracle rows too.  Returns None only when
    no node truth exists yet.  ``frag_max`` is PR 15's per-superpod
    fragmentation (device mirror path; 0.0 when no slice topology)."""
    device = getattr(sched, "device", None)
    if device is not None:
        with sched.commit_plane.device_mutex:
            mirror = device._mirror
            valid = mirror["valid"].reshape(-1).astype(bool).copy()
            sched_ok = valid & ~mirror["unschedulable"].reshape(-1).astype(bool)
            requested = mirror["requested"].astype(np.float32).copy()
            frag = _mirror_frag_max(device, mirror, valid)
        if not sched_ok.any():
            return None
        out = _entropy_of(requested, sched_ok)
        out["frag_max"] = frag
        return out
    return score_from_snapshot(sched)


def score_from_snapshot(sched) -> Optional[Dict[str, float]]:
    """Packing score off the host cache snapshot — the backend-agnostic
    read the replay harness uses for evidence, so oracle and tpu rows
    are judged by the same instrument (store truth, no device sync)."""
    rows = [ni for ni in sched.snapshot.list() if ni.node is not None]
    if not rows:
        return None
    requested = np.zeros((len(rows), 4), np.float32)
    valid = np.zeros(len(rows), bool)
    for i, ni in enumerate(rows):
        valid[i] = not ni.node.spec.unschedulable
        r = ni.requested
        requested[i] = (r.milli_cpu, r.memory, r.ephemeral_storage,
                        len(ni.pods))
    if not valid.any():
        return None
    out = _entropy_of(requested, valid)
    out["frag_max"] = 0.0
    return out


def _mirror_frag_max(device, mirror, valid: np.ndarray) -> float:
    """Max per-superpod fragmentation off the device mirror (the
    ``_update_slice_frag_metrics`` read, caller holds the mutex)."""
    from ..ops.schema import COL_PODS
    from ..ops.slice import fragmentation_host

    caps = device.caps
    grid = (getattr(caps, "superpods", 0), getattr(caps, "sp_slots", 0))
    if not grid[0] or not grid[1]:
        return 0.0
    topo_sp = mirror["topo_sp"].reshape(-1)
    if not (topo_sp[valid] >= 0).any():
        return 0.0
    free = valid & (mirror["requested"][:, COL_PODS] == 0)
    rows = fragmentation_host(topo_sp, mirror["topo_pos"].reshape(-1),
                              valid, free, grid)
    return max((r["frag"] for r in rows), default=0.0)


class Rebalancer:
    """SLO-guarded continuous descheduler. Construct with the scheduler
    it serves (any flavor — device mirror used when present) and drive
    ``maybe_run`` from housekeeping; every knob has an operational
    default. See the module docstring for the control loop."""

    def __init__(self, sched, *,
                 entropy_high: float = 0.92, entropy_low: float = 0.80,
                 frag_high: float = 0.60, frag_low: float = 0.40,
                 max_migrations_per_wave: int = 8,
                 cooldown_s: float = 30.0,
                 score_interval_s: float = 5.0,
                 slo_tolerance_pct: float = 50.0,
                 slo_floor_s: float = 0.02,
                 slo_min_samples: int = 20,
                 breaker_threshold: int = 2,
                 probe_interval_s: float = 120.0,
                 headroom_factor: float = 1.2,
                 now_fn=None):
        from .drain import DrainOrchestrator

        self.sched = sched
        self.now_fn = now_fn or getattr(sched, "now_fn", time.monotonic)
        self.drain = DrainOrchestrator(
            sched.store, metrics=getattr(sched, "smetrics", None),
            queue=getattr(sched, "queue", None), now_fn=self.now_fn)
        self.entropy_high, self.entropy_low = entropy_high, entropy_low
        self.frag_high, self.frag_low = frag_high, frag_low
        self.max_migrations_per_wave = max_migrations_per_wave
        self.cooldown_s = cooldown_s
        self.score_interval_s = score_interval_s
        self.slo_tolerance_pct = slo_tolerance_pct
        self.slo_floor_s = slo_floor_s
        self.slo_min_samples = slo_min_samples
        self.headroom_factor = headroom_factor
        self.breaker = CircuitBreaker(
            failure_threshold=breaker_threshold,
            reset_timeout_s=probe_interval_s, now_fn=self.now_fn,
            on_state_change=self._slo_state_change)
        self.armed = False
        self.suspended = False
        self.last_score: Optional[Dict[str, float]] = None
        self.waves_executed = 0
        self.migrations = 0
        self.last_waves: deque = deque(maxlen=64)
        self._last_score_at = float("-inf")
        self._last_wave_at = float("-inf")
        # per-tenant SLO watch armed by each wave: {ns: (baseline_p99, snap)}
        self._slo_watch: Dict[str, tuple] = {}

    # ------------------------------------------------------------ control

    def due(self, now: float) -> bool:
        """Whether the score interval has elapsed (a tick would score)."""
        return now - self._last_score_at >= self.score_interval_s

    def maybe_run(self, now: Optional[float] = None) -> Dict[str, object]:
        """One control-loop tick (housekeeping cadence). Cheap unless the
        score interval elapsed AND the commit plane is idle."""
        if now is None:
            now = self.now_fn()
        self.drain.poll_pending_uncordons()
        worker = getattr(self.sched, "commit_worker", None)
        if worker is not None and not worker.idle():
            return {"ran": False, "reason": "commit-plane-busy"}
        if not self.due(now):
            return {"ran": False, "reason": "interval"}
        self._last_score_at = now
        score = score_cluster(self.sched)
        if score is None:
            return {"ran": False, "reason": "no-node-truth"}
        self.last_score = score
        metrics = getattr(self.sched, "smetrics", None)
        if metrics is not None:
            metrics.packing_entropy.set(value=score["entropy"])
        self._judge_slo()
        self._update_trigger(score)
        if not self.armed:
            return {"ran": False, "reason": "in-band", "score": score}
        if now - self._last_wave_at < self.cooldown_s:
            return {"ran": False, "reason": "cooldown", "score": score}
        if not self.breaker.allow():
            if metrics is not None:
                metrics.rebalance_waves.inc("suspended")
            return {"ran": False, "reason": "slo-suspended", "score": score}
        return self._run_wave(now, score)

    def _update_trigger(self, score: Dict[str, float]) -> None:
        """Hysteresis band: arm above high water, disarm only after the
        cluster recovers below low water (no wave flapping on the edge)."""
        hot = (score["entropy"] >= self.entropy_high
               or score["frag_max"] >= self.frag_high)
        cool = (score["entropy"] <= self.entropy_low
                and score["frag_max"] <= self.frag_low)
        if not self.armed and hot:
            self.armed = True
        elif self.armed and cool:
            self.armed = False

    # -------------------------------------------------------------- waves

    def _run_wave(self, now: float, score: Dict[str, float]) -> Dict[str, object]:
        metrics = getattr(self.sched, "smetrics", None)
        victims = self._pick_victims()
        if not victims:
            if metrics is not None:
                metrics.rebalance_waves.inc("empty")
            return {"ran": False, "reason": "no-victims", "score": score}
        self._arm_slo_watch()
        result = self.drain.drain_wave(
            victims, uncordon_after=True,
            allow_fn=self.drain._pdb_disruption_gate())
        self._last_wave_at = now
        self.waves_executed += 1
        self.migrations += result["evicted"]
        telemetry.event("rebalance_wave", nodes=result["nodes"],
                        pods=result["evicted"], gangs=result["gangs"],
                        entropy=round(score["entropy"], 4),
                        frag=round(score["frag_max"], 4))
        if metrics is not None:
            metrics.rebalance_waves.inc("executed")
            metrics.rebalance_migrations.inc(value=result["evicted"])
        self.last_waves.append({
            "at": now, "nodes": victims, "evicted": result["evicted"],
            "gangs": result["gangs"], "entropy": score["entropy"],
            "frag": score["frag_max"]})
        return {"ran": True, "wave": result, "score": score}

    def _pick_victims(self) -> List[str]:
        """Lowest-occupancy schedulable nodes whose eviction most improves
        the score, bounded by the per-wave migration budget and a headroom
        check: a victim's load must fit (with ``headroom_factor`` slack)
        into the remaining schedulable nodes' free capacity, or the wave
        would just thrash pods through the queue."""
        rows = [ni for ni in self.sched.snapshot.list()
                if ni.node is not None and not ni.node.spec.unschedulable]
        occupied = [ni for ni in rows if ni.pods]
        if len(occupied) <= 1:
            return []

        def occ(ni) -> float:
            a, r = ni.allocatable, ni.requested
            axes = []
            if a.milli_cpu:
                axes.append(r.milli_cpu / a.milli_cpu)
            if a.memory:
                axes.append(r.memory / a.memory)
            if a.allowed_pod_number:
                axes.append(len(ni.pods) / a.allowed_pod_number)
            return sum(axes) / max(len(axes), 1)

        occupied.sort(key=occ)
        free = np.zeros(3, np.float64)  # cpu, memory, pod slots
        for ni in rows:
            free += (max(ni.allocatable.milli_cpu - ni.requested.milli_cpu, 0),
                     max(ni.allocatable.memory - ni.requested.memory, 0),
                     max(ni.allocatable.allowed_pod_number - len(ni.pods), 0))
        victims: List[str] = []
        budget = self.max_migrations_per_wave
        # never empty the whole occupied set: the densest node must survive
        for ni in occupied[:-1]:
            need = np.array((ni.requested.milli_cpu, ni.requested.memory,
                             len(ni.pods)), np.float64)
            node_free = np.array(
                (ni.allocatable.milli_cpu - ni.requested.milli_cpu,
                 ni.allocatable.memory - ni.requested.memory,
                 ni.allocatable.allowed_pod_number - len(ni.pods)), np.float64)
            if len(ni.pods) > budget:
                break  # sorted ascending: nothing further fits either
            if np.any(need * self.headroom_factor > free - node_free):
                continue  # no room elsewhere for this node's load
            victims.append(ni.node.meta.name)
            budget -= len(ni.pods)
            free -= node_free + need  # the node leaves the pool entirely
        return victims

    # ------------------------------------------------------ SLO guardrail

    def _tenant_hist(self):
        metrics = getattr(self.sched, "smetrics", None)
        return getattr(metrics, "tenant_e2e_duration", None)

    def _arm_slo_watch(self) -> None:
        """Snapshot every tenant's e2e histogram at wave time: the window
        AFTER this point is what the guardrail judges, against the
        tenant's whole-run p99 as the baseline."""
        hist = self._tenant_hist()
        if hist is None:
            return
        for labels in hist.label_sets():
            ns = labels[0]
            if hist.count(ns):
                self._slo_watch[ns] = (hist.percentile(0.99, ns),
                                       hist.snapshot(ns))

    def _judge_slo(self) -> None:
        """Between waves: compare each watched tenant's windowed p99 with
        its armed baseline. A regression past tolerance feeds the breaker
        (which may trip OPEN = suspend); a clean window with enough
        samples heals it (HALF_OPEN probe -> CLOSED)."""
        hist = self._tenant_hist()
        if hist is None or not self._slo_watch:
            return
        judged = False
        worst = None
        for ns, (baseline, snap) in list(self._slo_watch.items()):
            if hist.count_since(snap, ns) < self.slo_min_samples:
                continue
            p99 = hist.percentile_since(snap, 0.99, ns)
            fence = baseline * (1.0 + self.slo_tolerance_pct / 100.0) \
                + self.slo_floor_s
            if p99 > fence:
                if worst is None or p99 - fence > worst[1]:
                    worst = (ns, p99 - fence, p99, baseline)
            # roll the window forward so each judgement is fresh
            self._slo_watch[ns] = (baseline, hist.snapshot(ns))
            judged = True
        if worst is not None:
            self.breaker.record_failure()
            telemetry.event("rebalance_suspended", tenant=worst[0],
                            p99=round(worst[2], 4),
                            baseline=round(worst[3], 4))
        elif judged and self.waves_executed and self.breaker.state != "open":
            # a clean window heals — but an OPEN breaker must wait for its
            # half-open probe wave; success without a probe would skip the
            # discipline the device breakers follow
            self.breaker.record_success()

    def _slo_state_change(self, _old: str, new: str) -> None:
        metrics = getattr(self.sched, "smetrics", None)
        if new == "open":
            self.suspended = True
            if metrics is not None:
                metrics.rebalance_suspended.set(value=1)
        elif new == "closed" and self.suspended:
            self.suspended = False
            telemetry.event("rebalance_resume")
            if metrics is not None:
                metrics.rebalance_suspended.set(value=0)

    # -------------------------------------------------------------- debug

    def debug_dump(self, limit: Optional[int] = None) -> Dict[str, object]:
        waves = list(self.last_waves)
        truncated = None
        if limit is not None and len(waves) > limit:
            truncated = len(waves)
            waves = waves[-limit:]
        out = {
            "enabled": True,
            "armed": self.armed,
            "suspended": self.suspended,
            "score": self.last_score,
            "bands": {"entropy_high": self.entropy_high,
                      "entropy_low": self.entropy_low,
                      "frag_high": self.frag_high,
                      "frag_low": self.frag_low},
            "budget": {"max_migrations_per_wave": self.max_migrations_per_wave,
                       "cooldown_s": self.cooldown_s},
            "breaker": self.breaker.dump(),
            "waves_executed": self.waves_executed,
            "migrations": self.migrations,
            "last_waves": waves,
            "pending_uncordons": [dict(w) for w in
                                  self.drain.pending_uncordons],
        }
        if truncated is not None:
            out["truncated"] = {"last_waves": truncated}
        return out
