"""The device-side batch step that ``TPUScheduler`` (in process) and
``DeviceService`` (behind the wire) share: size and grow the mirror, build
the batch program's arguments, dispatch it, screen failed pods for
preemption.

It takes no lock and never drains. The in-process scheduler drains its
ring outside the device mutex before a grow and holds the mutex around
rebuild, sync, dispatch and screen; the service holds its lock for the
whole batch. Where the drivers differ (pod-axis capacity, ports filter,
topology mode, the in-process carry and volume mask), the value has no
default here, so each caller's choice reads at its call.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Callable, Optional, Sequence

import jax
import numpy as np

from ..api.types import Pod
from ..ops.encode import CapacityError
from ..scheduler.scheduler import num_feasible_nodes_to_find
from . import telemetry
from .batch import BatchResult, bucket_sig
from .device_state import DeviceState, caps_for_cluster
from .errors import PermanentDeviceError

# CapacityError.dimension -> the Capacities field(s) to double: the exact
# names the encoders raise (ops/encode.py, backend/sig_table.py), most of
# them the field's own; "value vocab for '<key>'" matches by prefix
GROW_FIELDS = {
    **{d: (d,) for d in (
        "nodes", "pods", "resources", "label_keys", "taints", "tolerations",
        "exprs", "sel_exprs", "terms", "term_exprs", "pref_terms", "ports",
        "containers", "sigs", "ex_terms", "spread_cons", "ipa_terms",
        "ipa_pref", "prio_classes", "superpods", "sp_slots")},
    "ports vocab": ("port_words",),
    "image vocab": ("image_words", "images"),
}

# syncs (or encodes) a batch tries before its capacities count as refusing
# to converge
GROW_ATTEMPTS = 8


# ------------------------------------------------------------ capacity policy


def fit_nodes(device: Optional[DeviceState], n: int, *, batch: int,
              ns_labels_fn) -> DeviceState:
    """The mirror for an ``n``-node cluster: ``device`` itself when its node
    axis covers n; a new mirror sized for n when there is none; else a new
    one whose node axis (and the hostname value vocab, which must cover it)
    doubles until it covers n, every other grown axis kept. The caller
    swaps it in and syncs it."""
    if device is None:
        return DeviceState(caps_for_cluster(n, batch=batch),
                           ns_labels_fn=ns_labels_fn)
    caps = device.caps
    if caps.nodes >= n:
        return device
    nodes = caps.nodes
    while nodes < n:
        nodes *= 2
    return DeviceState(dataclasses.replace(
        caps, nodes=nodes,
        value_words=max(caps.value_words, (nodes + 2 + 31) // 32)),
        ns_labels_fn=ns_labels_fn)


def grown(device: DeviceState, err: CapacityError, *,
          ns_labels_fn) -> DeviceState:
    """A new mirror with the axis ``err`` names doubled until it holds
    ``err.needed``. A dimension no axis answers to raises
    PermanentDeviceError (deterministic per backend/errors.py: never
    retried)."""
    fields = GROW_FIELDS.get(err.dimension)
    if fields is None and err.dimension.startswith("value vocab"):
        fields = ("value_words",)
    if fields is None:
        raise PermanentDeviceError(
            f"unknown capacity dimension {err.dimension!r}") from err
    caps = device.caps
    updates = {}
    for f in fields:
        v = getattr(caps, f)
        while v < err.needed:
            v *= 2
        updates[f] = v
    return DeviceState(dataclasses.replace(caps, **updates),
                       ns_labels_fn=ns_labels_fn)


def retry_grown(step: Callable, grow: Callable[[CapacityError], None]):
    """Run ``step()`` until it raises no CapacityError, calling ``grow(err)``
    (which swaps a grown mirror in) after each, at most GROW_ATTEMPTS times.
    Returns (True, step's value), or (False, None) when the capacities
    refuse to converge."""
    for _attempt in range(GROW_ATTEMPTS):
        try:
            return True, step()
        except CapacityError as e:
            grow(e)
    return False, None


def encode(device: DeviceState, pods: Sequence[Pod], *,
           capacity: Optional[int], tie_seeds):
    """The batch's (PodBatch, ExprTable, TopoBatch), the pod axis padded to
    ``capacity`` rows (None: the mirror's whole pod axis)."""
    pb, et = device.encoder.encode_pods(pods, capacity=capacity,
                                        tie_seeds=tie_seeds)
    return pb, et, device.sig_table.encode_topo(pods, capacity=capacity)


# ------------------------------------------------------- the program's args


def default_full_batch() -> bool:
    """Whether the adaptive percentageOfNodesToScore default (0) evaluates
    the FULL node batch (accelerators) or the reference's adaptive sample
    (CPU). KTPU_FULL_BATCH=1/0 overrides the platform choice."""
    env = os.environ.get("KTPU_FULL_BATCH", "")
    if env in ("0", "1"):
        return env == "1"
    return jax.default_backend() != "cpu"


def sample_window(percentage: int, n_valid: int, start_carry) -> tuple:
    """(sample_k, sample_start) for percentageOfNodesToScore, or (None,
    None) to evaluate every node. An explicit percentage gets the exact
    rotating window (schedule_one.go:525-545), from ``start_carry``, the
    previous batch's evolved start (None: node 0). The adaptive default (0)
    samples as the reference does (50 − N/125, floored at 5%) on the CPU,
    where the scan step is real host time, and evaluates the full batch on
    accelerators, where that is cheaper than the emulated early exit and
    unlocks the speculative program (SURVEY §2.7 P2; a documented
    divergence, SURVEY §7 hard-part 3)."""
    if percentage or not default_full_batch():
        k = num_feasible_nodes_to_find(n_valid, percentage)
    else:
        k = n_valid
    if k >= n_valid:
        return None, None
    return np.int32(k), (start_carry if start_carry is not None
                         else np.int32(0))


def slice_args(pods: Sequence[Pod], device: DeviceState) -> tuple:
    """(slice_members, slice_grid) for the in-jit slice planner
    (ops/slice.py): the bucketed member index of the batch's slice gangs
    (marker label + PodGroup key), rows in batch order (= queue order, the
    ordinal the host oracle assigns), and the torus grid. (None, None) when
    the batch has none, the common case, whose program is unchanged."""
    from ..framework.plugins.coscheduling import pod_group_key
    from ..ops.slice import is_slice_pod

    groups = {}
    for i, pod in enumerate(pods):
        if is_slice_pod(pod):
            gkey = pod_group_key(pod)
            if gkey is not None:
                groups.setdefault(gkey, []).append(i)
    if not groups:
        return None, None
    from .claim_mask import _bucket

    g_cap = _bucket(len(groups), floor=2)
    m_cap = _bucket(max(len(v) for v in groups.values()), floor=2)
    member_idx = np.full((g_cap, m_cap), -1, np.int32)
    member_valid = np.zeros((g_cap, m_cap), bool)
    for g, idxs in enumerate(groups.values()):
        member_idx[g, :len(idxs)] = idxs
        member_valid[g, :len(idxs)] = True
    return ((member_idx, member_valid),
            (device.caps.superpods, device.caps.sp_slots))


# ------------------------------------------------------------- the dispatch


def dispatch(schedule_batch_fn, device: DeviceState, pb, et, tb, key, *,
             topo_mode: Optional[str], vd_override: Optional[int],
             host_key: int, ports_enabled: bool, topo_carry, extra_mask,
             dra_mask, sample: tuple, slices: tuple,
             quota: tuple) -> BatchResult:
    """Dispatch the batch program on ``device`` (async: nothing blocks)
    under its bucket signature. ``sample``, ``slices`` and ``quota`` are
    the pairs sample_window, slice_args and ops/quota.build_quota_batch_args
    return. A program the compiler refuses raises: no path swaps in another.
    The caller adopts the result into the mirror."""
    sample_k, sample_start = sample
    slice_members, slice_grid = slices
    quota_ns, quota_req = quota
    screened = quota_ns is not None
    sig = bucket_sig(pb.capacity, topo_mode, device.topo_enabled)
    with telemetry.dispatch("schedule_batch", bucket=sig):
        return schedule_batch_fn(
            pb, et, device.nt, device.tc, tb, key,
            topo_enabled=device.topo_enabled, topo_carry=topo_carry,
            sample_k=sample_k, sample_start=sample_start,
            topo_mode=topo_mode, vd_override=vd_override, host_key=host_key,
            ports_enabled=ports_enabled, extra_mask=extra_mask,
            dra_mask=dra_mask, slice_members=slice_members,
            slice_grid=slice_grid, quota_ns=quota_ns, quota_req=quota_req,
            quota_used=device.nsq_used if screened else None,
            quota_limit=device.nsq_limit if screened else None)


def screen_preemption(device: DeviceState, pb, static_masks,
                      failed: np.ndarray):
    """Preemption screen + candidate rank for the batch's failed pods
    (``failed``: one bool per batch pod) -> ops/preempt.PreemptResult. A
    priority class first seen since the last sync is INT_MAX (never
    evictable) on the device until refreshed here; the refresh replaces
    device.nt, so the caller serializes it against a dispatch's adopt."""
    from ..ops.preempt import _screen_jit, screen_prefix

    device._refresh_class_prio()
    bucket = str(pb.capacity)
    with telemetry.dispatch("preempt_screen", bucket=bucket):
        pres = screen_prefix(pb, device.nt, static_masks, failed)
    if telemetry.get() is not None:
        failed_pad = np.zeros(pb.capacity, bool)
        failed_pad[:len(failed)] = failed
        telemetry.cost_probe("preempt_screen", bucket, _screen_jit,
                             (pb, device.nt, static_masks, failed_pad))
    return pres
