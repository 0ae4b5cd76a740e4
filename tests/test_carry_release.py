"""Completions keep the device-carry chain: a deleted bound pod's requests
go back on the in-flight carry (DeviceState.release) instead of draining,
re-snapshotting and re-syncing. The commit worker is forced on, as on an
accelerator; the cells are the benchmark's own traffic at a small size."""

import os
import sys

import numpy as np
import pytest

from kubernetes_tpu.api.types import LabelSelector
from kubernetes_tpu.api.wrappers import make_node, make_pod
from kubernetes_tpu.apiserver import ClusterStore
from kubernetes_tpu.backend import TPUScheduler
from kubernetes_tpu.backend.device_state import DeviceState, caps_for_cluster
from kubernetes_tpu.cache import Cache, Snapshot

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402

ZONE = "topology.kubernetes.io/zone"
ROW_FIELDS = ("requested", "nonzero_requested", "class_req")


def _outcomes(sched):
    m = sched.smetrics.carry_chain_batches
    return {ls[0]: m.labels(*ls) for ls in m.label_sets()}


def _released(sched):
    return sched.smetrics.carry_released_pods.labels()


# ------------------------------------------------------ the benchmark cells


@pytest.fixture(scope="module", params=["basic-backlog", "spread-backlog"])
def driven(request):
    """One small run of a backlog cell with one completion per binding: a
    warm phase, then a measured phase; the ring landed at the end."""
    mp = pytest.MonkeyPatch()
    mp.setenv("KTPU_COMMIT_WORKER", "1")
    spec = harness.load_spec(ROOT)
    cell = next(w["name"] for w in spec["workloads"]
                if w["traffic"] == request.param)
    _cell, config, traffic = harness.load_cell(spec, cell, ROOT)
    # 100 nodes: the CPU's adaptive sample still scores every node, so the
    # comparison is exact
    config = dict(config, nodes=100, running_pods=200, batch_size=32)
    traffic = dict(traffic, pending=600)
    run = harness.Run(config, traffic, 2 ** 31 + 29)
    try:
        run.build()
        sample, _ = run.mix.pod("warm-sample")
        run.sched.warm_buckets(sample_pods=[sample])
        run.drive(1.0, "w", 1000)
        before = _outcomes(run.sched)
        first_group = len(run.groups)
        run.drive(2.0, "m", 0)
        after = _outcomes(run.sched)
        run.land()
        judge = run.judge(first_group)
        # one more batch: its dispatch (drain+sync path, the ring being
        # empty) takes land()'s completions back, its landing reconciles
        run.closed = False
        run.sched.schedule_batch_cycle()
        run.closed = True
        run.sched.schedule_batch_cycle()
        window = {k: after.get(k, 0) - before.get(k, 0) for k in after}
        yield run, window, judge
    finally:
        run.close()
        mp.undo()


def test_completions_keep_the_chain(driven):
    run, window, _judge = driven
    batches = sum(window.values())
    assert batches > 0
    assert window.get("kept", 0) >= 0.9 * batches, window
    assert _released(run.sched) > 0


def _assert_rows_match_the_cache(sched):
    """Every node row of the device, the mirror and a fresh re-encode of
    the cache agree, and so do the topology carry's counts and a recount
    from the cache."""
    device = sched.device
    snap = Snapshot()
    sched.cache.update_snapshot(snap)
    dev = {f: np.asarray(getattr(device.nt, f)) for f in ROW_FIELDS}
    st = device.sig_table
    sel = (np.asarray(device.topo_carry[0])
           if device.topo_carry is not None else None)
    for name, ni in snap.node_info_map.items():
        slot = device.encoder.node_slots[name]
        row = device.encoder.encode_dynamic_fields(ni)
        for f in ROW_FIELDS:
            np.testing.assert_array_equal(row[f], device._mirror[f][slot], f)
            np.testing.assert_array_equal(row[f], dev[f][slot], f)
        if sel is not None:
            for sid in range(1, st.n_sigs):
                want = sum(1 for p in ni.pods
                           if st._sig_rows[sid].matches(p, st.ns_labels_fn))
                assert sel[sid, slot] == want, (name, sid)


def test_carry_rows_match_a_fresh_encode_after_land(driven):
    """No drift after a window of completions on the carried chain."""
    run, _window, _judge = driven
    _assert_rows_match_the_cache(run.sched)


def test_completions_judged_correct(driven):
    _run, _window, judge = driven
    assert judge["checked"] > 0
    for key in ("infeasible", "off_argmax", "over_capacity", "unknown_node"):
        assert judge[key] == 0, judge


# --------------------------------------------------- what breaks the chain


def _ring_cluster(store, victim):
    for i in range(8):
        store.create_node(make_node(f"n{i}").capacity(
            {"cpu": "16", "memory": "32Gi", "pods": 50}).obj())
    store.create_pod(victim.obj())
    for i in range(40):
        store.create_pod(make_pod(f"p{i}").req({"cpu": "100m"}).obj())


@pytest.mark.parametrize("kind,outcome", [
    ("plain", "kept"),
    ("ports", "ports"),
    ("pvcs", "pvcs"),
    ("claims", "claims"),
    ("topology", "topology"),
])
def test_deleted_pod_breaks_the_chain_under_its_reason(kind, outcome,
                                                       monkeypatch):
    monkeypatch.setenv("KTPU_COMMIT_WORKER", "1")
    victim = make_pod("victim").req({"cpu": "1"}).node("n0")
    if kind == "ports":
        victim.host_port(8080)
    elif kind == "pvcs":
        victim.pvc("data")
    elif kind == "claims":
        victim.resource_claim("gpu", claim_name="gpu-claim")
    elif kind == "topology":
        victim.label("app", "v").pod_affinity(
            ZONE, LabelSelector(match_labels={"app": "v"}), anti=True)
    store = ClusterStore()
    sched = TPUScheduler(store, batch_size=4)
    try:
        _ring_cluster(store, victim)
        sched.schedule_batch_cycle()  # drain+sync path: starts the ring
        sched.schedule_batch_cycle()  # rides the carry
        # the worker lands the first batch before the deletion: its
        # reconcile would otherwise see the deleted row first and break the
        # chain as "dirty" ahead of the reason under test
        sched.commit_worker.flush()
        before = _outcomes(sched)
        assert before.get("kept", 0) == 1, before
        released0 = _released(sched)
        store.delete_pod(victim.obj().key())
        sched.schedule_batch_cycle()
        after = _outcomes(sched)
        assert after.get(outcome, 0) - before.get(outcome, 0) == 1, after
        assert _released(sched) - released0 == (1 if kind == "plain" else 0)
        sched.run_until_settled()
        assert sched.metrics["scheduled"] == 40
    finally:
        sched.commit_worker.stop()


def test_delete_before_its_own_reconcile_breaks_the_chain(monkeypatch):
    """A pod deleted after its bind and before the reconcile of its own
    commit is on the carry (its dispatch adopted it) while the device row
    is not yet confirmed holding it: the next batch must not ride that
    carry. The worker is held after the first batch's binds until the
    next batch's pipelined encode has run."""
    import threading

    monkeypatch.setenv("KTPU_COMMIT_WORKER", "1")
    store = ClusterStore()
    sched = TPUScheduler(store, batch_size=4)
    bound, go = threading.Event(), threading.Event()
    first = []
    commit_batch = sched._commit_batch
    try_encode = sched._try_pipelined_encode

    def hold_after_bind(qps, *args):
        out = commit_batch(qps, *args)
        if not first:
            first.extend(qp.pod.key() for qp in qps)
            bound.set()
            assert go.wait(30)
        return out

    def encode_then_release(batched):
        out = try_encode(batched)
        go.set()
        return out

    monkeypatch.setattr(sched, "_commit_batch", hold_after_bind)
    try:
        for i in range(8):
            store.create_node(make_node(f"n{i}").capacity(
                {"cpu": "16", "memory": "32Gi", "pods": 50}).obj())
        for i in range(40):
            store.create_pod(make_pod(f"p{i}").req({"cpu": "1"}).obj())
        # the dispatch after the ring is full hands the first batch to the
        # worker, which holds it until the next batch's encode has run
        for _ in range(sched.pipeline_depth + 1):
            sched.schedule_batch_cycle()
        assert bound.wait(30)
        victim = store.get_pod(first[0])
        assert victim.spec.node_name
        store.delete_pod(victim.key())
        before = _outcomes(sched)
        monkeypatch.setattr(sched, "_try_pipelined_encode",
                            encode_then_release)
        # no 1 s housekeeping tick in this cycle: its flush would wait on
        # the batch the worker is held in
        sched._last_cleanup = sched.now_fn()
        sched.schedule_batch_cycle()
        after = _outcomes(sched)
        assert after.get("kept", 0) == before.get("kept", 0), after
        assert after.get("inexact", 0) - before.get("inexact", 0) == 1, after
        sched.run_until_settled()
        assert sched.metrics["scheduled"] == 40
        _assert_rows_match_the_cache(sched)
    finally:
        go.set()
        sched.commit_worker.stop()


def test_spread_sees_deletions_before_its_pop_only(monkeypatch):
    """Zone spread (maxSkew 1) with zone a one labelled pod over the
    others: a is infeasible for the next spread pod until one of a's
    labelled pods completes. A pod popped before that completion is
    placed off a; the pod popped after it takes a (the emptiest node),
    on the carried chain — the completion went back on the carry's
    signature counts."""
    monkeypatch.setenv("KTPU_COMMIT_WORKER", "1")
    sel = LabelSelector(match_labels={"app": "s"})
    store = ClusterStore()
    sched = TPUScheduler(store, batch_size=2)
    try:
        for name, zone, cpu in (("na", "a", "64"), ("nb", "b", "4"),
                                ("nc", "c", "4")):
            store.create_node(make_node(name).label(ZONE, zone).capacity(
                {"cpu": cpu, "memory": "64Gi", "pods": 50}).obj())
        for name, node in (("a0", "na"), ("a1", "na"), ("b0", "nb"),
                           ("c0", "nc")):
            store.create_pod(make_pod(name).label("app", "s")
                             .req({"cpu": "1"}).node(node).obj())

        def spread_pod(name):
            store.create_pod(make_pod(name).label("app", "s")
                             .req({"cpu": "1"})
                             .spread_constraint(1, ZONE, selector=sel).obj())

        spread_pod("early")
        sched.schedule_batch_cycle()  # dispatched on the drain+sync path
        store.delete_pod("default/a0")  # completes after early's pop
        released0 = _released(sched)
        before = _outcomes(sched)
        spread_pod("late")
        sched.schedule_batch_cycle()
        after = _outcomes(sched)
        assert after.get("kept", 0) - before.get("kept", 0) == 1, after
        assert _released(sched) - released0 == 1
        sched.run_until_settled()
        bound = {p.meta.name: p.spec.node_name
                 for p in store.list_objects("Pod")[0]}
        assert bound["early"] in ("nb", "nc")
        assert bound["late"] == "na"
    finally:
        sched.commit_worker.stop()


# --------------------------------------------------------- the release rule


def _device_with(cache, snap):
    device = DeviceState(caps_for_cluster(4, batch=8))
    cache.update_snapshot(snap)
    device.sync(snap)
    return device


@pytest.mark.parametrize("case", ["held", "added_after_upload",
                                  "removed_before_upload"])
def test_release_subtracts_only_what_the_row_holds(case):
    """A release subtracts a pod only from a row uploaded while the cache
    held it, and never one an upload already took off. A row confirmed
    before the pod's add is inexact (the carry may hold the pod through
    its own dispatch): the release drops the row's uploaded generation, so
    the next sync uploads it afresh."""
    cache = Cache()
    cache.add_node(make_node("n0").capacity(
        {"cpu": "8", "memory": "8Gi", "pods": 20}).obj())
    keep = make_pod("keep").req({"cpu": "1"}).node("n0").obj()
    gone = make_pod("gone").req({"cpu": "2", "memory": "1Gi"}) \
        .node("n0").obj()
    cache.add_pod(keep)
    if case != "added_after_upload":
        cache.add_pod(gone)
    snap = Snapshot()
    device = _device_with(cache, snap)
    if case == "added_after_upload":
        cache.add_pod(gone)
    removal = cache.remove_pod(gone)
    assert removal is not None and removal.node == "n0"
    if case == "removed_before_upload":
        cache.update_snapshot(snap)
        device.sync(snap)
    released, inexact = device.release([(gone, removal)])
    assert released == (1 if case == "held" else 0)
    assert inexact == (1 if case == "added_after_upload" else 0)
    cache.update_snapshot(snap)
    if case == "added_after_upload":
        assert device.has_dirty(snap)
        device.sync(snap)
    slot = device.encoder.node_slots["n0"]
    row = device.encoder.encode_dynamic_fields(snap.node_info_map["n0"])
    for f in ROW_FIELDS:
        np.testing.assert_array_equal(row[f], device._mirror[f][slot], f)
        np.testing.assert_array_equal(
            row[f], np.asarray(getattr(device.nt, f))[slot], f)


def test_release_record_and_take_stay_atomic_under_racing_deletes():
    """Threads delete bound pods (each deletion removes the pod from the
    cache and records its release) while a taker takes the pending
    releases together with a snapshot update, as the drain path and the
    commit reconcile do: every taken removal is already in the snapshot it
    was taken with, and no release is lost or taken twice."""
    store = ClusterStore()
    sched = TPUScheduler(store, batch_size=4)
    for i in range(4):
        store.create_node(make_node(f"n{i}").capacity(
            {"cpu": "64", "memory": "64Gi", "pods": 500}).obj())
    keys = [[] for _ in range(4)]
    for t in range(4):
        for j in range(150):
            pod = make_pod(f"t{t}-{j}").req({"cpu": "10m"}) \
                .node(f"n{(t + j) % 4}").obj()
            store.create_pod(pod)
            keys[t].append(pod.key())

    def delete(mine):
        for key in mine:
            store.delete_pod(key)

    import threading

    snap = Snapshot()
    taken_keys = []
    prior = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=delete, args=(k,)) for k in keys]
        for th in threads:
            th.start()
        while True:
            done = not any(th.is_alive() for th in threads)
            with sched.cache.locked():
                taken = sched._take_releases()
                sched.cache.update_snapshot(snap)
            for pod, rm in taken:
                ni = snap.node_info_map[rm.node]
                assert ni.generation >= rm.removed_gen
                assert pod.key() not in {p.key() for p in ni.pods}
                taken_keys.append(pod.key())
            if done:
                break
        for th in threads:
            th.join(timeout=30)
            assert not th.is_alive()
    finally:
        sys.setswitchinterval(prior)
    want = sorted(k for mine in keys for k in mine)
    assert sorted(taken_keys) == want
