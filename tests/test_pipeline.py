"""Async batch pipeline (SURVEY §2.7 P3): batch k+1 dispatches on batch k's
adopted device carry while the host commits batch k. These tests prove the
overlap actually happens and that it never changes placements."""

import os

import pytest

from kubernetes_tpu.api.types import LabelSelector
from kubernetes_tpu.api.wrappers import make_node, make_pod
from kubernetes_tpu.apiserver import ClusterStore
from kubernetes_tpu.backend import TPUScheduler
from kubernetes_tpu.scheduler import Scheduler


def _bound(store):
    objs, _rv = store.list_objects("Pod")
    return {p.meta.name: p.spec.node_name for p in objs if p.spec.node_name}


def _run(pipeline: bool, build):
    os.environ["KTPU_PIPELINE"] = "1" if pipeline else "0"
    try:
        store = ClusterStore()
        sched = TPUScheduler(store, batch_size=4, comparer_every_n=1)
        build(store)
        sched.run_until_settled()
        return store, sched
    finally:
        os.environ.pop("KTPU_PIPELINE", None)


def _basic_cluster(store):
    for i in range(8):
        store.create_node(
            make_node(f"n{i}").capacity({"cpu": "4", "memory": "8Gi", "pods": 10})
            .label("zone", f"z{i % 2}").obj())
    for i in range(20):
        store.create_pod(make_pod(f"p{i}").req({"cpu": "1", "memory": "1Gi"}).obj())


def test_pipeline_overlaps_and_matches_synchronous():
    store_p, sched_p = _run(True, _basic_cluster)
    store_s, sched_s = _run(False, _basic_cluster)
    assert sched_p.metrics["scheduled"] == 20
    # overlap evidence: at least one batch was dispatched on the carry
    assert sched_p.pipelined_batches > 0
    assert sched_s.pipelined_batches == 0
    # same decisions (deterministic batch numbering keys the tie-break PRNG)
    assert _bound(store_p) == _bound(store_s)
    assert sched_p.comparer_mismatches == 0


@pytest.mark.parametrize("depth", [2, 3])
def test_ring_depth_k_matches_synchronous(depth, monkeypatch):
    """Placement parity at ring depth K≥2 (ISSUE 5 acceptance): with
    multiple batches in flight on the carry chain, placements must equal
    the synchronous run's exactly — including under anti-affinity, where a
    stale carry would immediately show as a same-zone double-place."""
    monkeypatch.setenv("KTPU_PIPELINE_DEPTH", str(depth))

    def build(store):
        for i in range(8):
            store.create_node(
                make_node(f"n{i}").capacity({"cpu": "8", "memory": "16Gi", "pods": 10})
                .label("zone", f"z{i % 2}").obj())
        sel = LabelSelector(match_labels={"app": "x"})
        for i in range(6):
            store.create_pod(
                make_pod(f"aa{i}").req({"cpu": "1"}).label("app", "x")
                .pod_affinity("zone", sel, anti=True).obj())
        for i in range(18):
            store.create_pod(make_pod(f"p{i}").req({"cpu": "1", "memory": "1Gi"}).obj())

    store_p, sched_p = _run(True, build)
    monkeypatch.delenv("KTPU_PIPELINE_DEPTH")
    store_s, sched_s = _run(False, build)
    assert sched_p.pipelined_batches > 0
    assert _bound(store_p) == _bound(store_s)
    assert sched_p.comparer_mismatches == 0


def test_pipeline_capacity_respected_across_batches():
    """The r2 stale-device failure mode, now across PIPELINED batches: a
    1-slot cluster must admit exactly one pod even when later batches are
    dispatched before the first batch's host commit."""
    def build(store):
        store.create_node(
            make_node("only").capacity({"cpu": "2", "memory": "4Gi", "pods": 1}).obj())
        for i in range(9):
            store.create_pod(make_pod(f"p{i}").req({"cpu": "1", "memory": "1Gi"}).obj())

    store, sched = _run(True, build)
    assert sched.metrics["scheduled"] == 1
    assert len(_bound(store)) == 1
    assert sched.comparer_mismatches == 0


def test_pipeline_topo_carry_across_batches():
    """Anti-affinity committed in batch k must be visible to batch k+1 even
    though k+1 is dispatched BEFORE k's host commit (the sel_counts/seg_exist
    carry chain — without it, k+1 would read the stale pre-k host tables)."""
    def build(store):
        for i in range(8):
            store.create_node(
                make_node(f"n{i}").capacity({"cpu": "8", "memory": "16Gi", "pods": 10})
                .label("zone", f"z{i % 2}").obj())
        sel = LabelSelector(match_labels={"app": "x"})
        for i in range(8):
            store.create_pod(
                make_pod(f"p{i}").req({"cpu": "1"}).label("app", "x")
                .pod_affinity("zone", sel, anti=True).obj())

    store, sched = _run(True, build)
    bound = _bound(store)
    # 2 zones ⇒ exactly 2 of the 8 mutually-anti-affine pods can place
    assert len(bound) == 2, bound
    zones = {int(n[1:]) % 2 for n in bound.values()}
    assert zones == {0, 1}
    assert sched.comparer_mismatches == 0


def test_pipeline_chain_breaks_on_external_change():
    """A node created between cycles makes has_dirty trip: the chain must
    break (drain + resync) and the new node must become schedulable."""
    os.environ["KTPU_PIPELINE"] = "1"
    try:
        store = ClusterStore()
        sched = TPUScheduler(store, batch_size=4, comparer_every_n=1)
        store.create_node(
            make_node("small").capacity({"cpu": "4", "memory": "8Gi", "pods": 4}).obj())
        for i in range(4):
            store.create_pod(make_pod(f"a{i}").req({"cpu": "1", "memory": "1Gi"}).obj())
        sched.run_until_settled()
        assert sched.metrics["scheduled"] == 4

        # external change: a big node appears; the next pods must see it
        store.create_node(
            make_node("big").capacity({"cpu": "64", "memory": "128Gi", "pods": 100}).obj())
        for i in range(8):
            store.create_pod(make_pod(f"b{i}").req({"cpu": "2", "memory": "2Gi"}).obj())
        sched.run_until_settled()
        assert sched.metrics["scheduled"] == 12
        bound = _bound(store)
        assert sum(1 for n in bound.values() if n == "big") == 8
        assert sched.comparer_mismatches == 0
    finally:
        os.environ.pop("KTPU_PIPELINE", None)


def test_reconcile_elides_matching_rows_and_leaves_divergent_dirty():
    """DeviceState.reconcile must refresh generations ONLY for rows whose
    content matches the mirror (adopted commits); divergent rows must stay
    dirty so the pipelined chain breaks instead of scattering host rows
    into an adopted-ahead carry (code-review r3 finding)."""
    from kubernetes_tpu.backend.device_state import DeviceState, caps_for_cluster
    from kubernetes_tpu.cache.cache import Cache
    from kubernetes_tpu.cache.snapshot import Snapshot

    cache = Cache()
    snap = Snapshot()
    nodes = {}
    for i in range(3):
        n = make_node(f"n{i}").capacity({"cpu": "4", "memory": "8Gi", "pods": 10}).obj()
        nodes[n.meta.name] = n
        cache.add_node(n)
    cache.update_snapshot(snap)
    dev = DeviceState(caps_for_cluster(3))
    dev.sync(snap)
    assert not dev.has_dirty(snap)

    # bump n0's generation WITHOUT changing content: reconcile elides it
    cache.update_node(nodes["n0"])
    cache.update_snapshot(snap)
    assert dev.has_dirty(snap)
    left = dev.reconcile(snap)
    assert left == 0
    assert not dev.has_dirty(snap)

    # change n1's content (labels): reconcile must leave it dirty
    n1 = make_node("n1").capacity({"cpu": "4", "memory": "8Gi", "pods": 10}).label("zone", "z9").obj()
    cache.update_node(n1)
    cache.update_snapshot(snap)
    left = dev.reconcile(snap)
    assert left == 1
    assert dev.has_dirty(snap)
    # the full sync then repairs it
    dev.sync(snap)
    assert not dev.has_dirty(snap)


def test_batch_sizer_deadline_controller():
    """BatchSizer: a + b·B ≤ deadline over the POP→COMMIT attempt latency,
    clamped to [min, max] and floored to a compile bucket, from EMA
    estimates of fixed and per-pod latency cost."""
    from kubernetes_tpu.backend.sizer import BatchSizer

    s = BatchSizer(max_batch=512, deadline_s=0.0)
    assert s.target() == 512  # disabled: always max

    s = BatchSizer(max_batch=512, deadline_s=0.3)
    # feed consistent observations: a=40ms fixed, b=1ms/pod — the decayed
    # least-squares fit must recover them exactly
    for _ in range(30):
        s.update(128, 0.040 + 0.001 * 128)
        s.update(256, 0.040 + 0.001 * 256)
    assert abs(s._a - 0.040) < 0.005 and abs(s._b - 0.001) < 0.0001
    t = s.target()
    # budget = 300ms·headroom(0.6) − a(40ms) = ~140ms; /1ms ≈ 140 → bucket
    # 128 (the headroom keeps the observed p99 — ~1.6-2x the mean span —
    # inside the declared deadline, not just the average)
    assert 64 <= t <= 256, t
    # sustained latency spike → smaller batches (the first few spikes are
    # outlier-rejected as suspected compile blips, then accepted)
    for _ in range(30):
        s.update(t, 0.100 + 0.004 * t)
    assert s.target() < t
    # tiny deadline → clamps to min
    s2 = BatchSizer(max_batch=512, deadline_s=0.01)
    for _ in range(10):
        s2.update(64, 0.05 + 0.001 * 64)
    assert s2.target() == s2.min_batch


def test_deadline_bounds_pop_size_end_to_end():
    """With a deadline set, the scheduler pops bounded batches but still
    schedules everything correctly."""
    os.environ["KTPU_BATCH_DEADLINE_MS"] = "120"
    try:
        store = ClusterStore()
        sched = TPUScheduler(store, batch_size=256)
        for i in range(16):
            store.create_node(
                make_node(f"n{i}").capacity({"cpu": "16", "memory": "32Gi", "pods": 40}).obj())
        for i in range(300):
            store.create_pod(make_pod(f"p{i}").req({"cpu": "500m", "memory": "512Mi"}).obj())
        sched.run_until_settled()
        assert sched.metrics["scheduled"] == 300
        # env wiring + real observations reached the controller
        assert sched.sizer.deadline_s == 0.12
        assert sched.sizer.updates > 0
        # and the POP SITE consults the sizer: force a tiny target and check
        # every subsequent pop is cut to it (machine-speed independent)
        class _Stub:
            def target(self):
                return 9

            def update(self, *a):
                pass

            def update_wait(self, *a):
                pass

            def bucket_for(self, n):
                return 16  # the encode bucket the program pads to

        sched.sizer = _Stub()
        pops = []
        orig_pop = sched.queue.pop_batch
        sched.queue.pop_batch = lambda k: (pops.append(k), orig_pop(k))[1]
        for i in range(40):
            store.create_pod(make_pod(f"q{i}").req({"cpu": "100m", "memory": "64Mi"}).obj())
        sched.run_until_settled()
        assert sched.metrics["scheduled"] == 340
        assert pops and all(k == 9 for k in pops), pops
    finally:
        os.environ.pop("KTPU_BATCH_DEADLINE_MS", None)


def test_adaptive_sampling_on_batch_path():
    """percentageOfNodesToScore emulation (schedule_one.go:525): with the
    knob restricting, each pod's winner must come from the first K feasible
    slots in rotated order, and the rotation must advance across pods."""
    import jax
    import numpy as np

    from kubernetes_tpu.api.wrappers import make_node, make_pod
    from kubernetes_tpu.backend.batch import schedule_batch
    from kubernetes_tpu.backend.sig_table import SigTable
    from kubernetes_tpu.framework.types import NodeInfo
    from kubernetes_tpu.ops.encode import ClusterEncoder
    from kubernetes_tpu.ops.schema import Capacities

    n_nodes = 64
    # identical nodes: every node feasible and score-tied, so the winner is
    # the jitter tie-break WITHIN the eligible window — the assertions below
    # verify window membership and rotation, not score ordering
    infos = []
    for i in range(n_nodes):
        nw = make_node(f"n{i}").capacity({"cpu": "64", "memory": "128Gi", "pods": 200})
        infos.append(NodeInfo(nw.obj()))
    enc = ClusterEncoder(Capacities(nodes=n_nodes, pods=8, value_words=32))
    sig = SigTable(enc)
    nt = enc.encode_snapshot(infos)
    pods = [make_pod(f"p{i}").req({"cpu": "1", "memory": "1Gi"}).obj() for i in range(8)]
    pb, et = enc.encode_pods(pods)
    tb = sig.encode_topo(pods)
    tc = sig.topo_counts()
    key = jax.random.PRNGKey(0)

    k = 16
    res = schedule_batch(pb, et, nt, tc, tb, key, topo_enabled=False,
                         sample_k=np.int32(k), sample_start=np.int32(0))
    idx = np.asarray(res.node_idx)
    start = 0
    for i in range(8):
        # all nodes feasible → window = slots [start, start+k) mod N
        window = {(start + j) % n_nodes for j in range(k)}
        assert int(idx[i]) in window, (i, idx[i], start)
        start = (start + k) % n_nodes  # K-th feasible found at position k-1
    assert int(np.asarray(res.final_sample_start)) == start


def test_adaptive_sampling_scheduler_equivalence_small_cluster():
    """Below the 100-node threshold K == N: the sampling knob must not
    change placements vs the full-evaluation program."""
    store_a = ClusterStore()
    sched_a = TPUScheduler(store_a, batch_size=8)
    store_b = ClusterStore()
    sched_b = TPUScheduler(store_b, batch_size=8)
    for store in (store_a, store_b):
        for i in range(12):
            store.create_node(
                make_node(f"n{i}").capacity({"cpu": "8", "memory": "16Gi", "pods": 20}).obj())
        for i in range(20):
            store.create_pod(make_pod(f"p{i}").req({"cpu": "1", "memory": "1Gi"}).obj())
    sched_a.run_until_settled()
    sched_b.run_until_settled()
    assert _bound(store_a) == _bound(store_b)
    assert sched_a.metrics["scheduled"] == 20


def test_explicit_sampling_spreads_on_large_cluster():
    """An EXPLICIT percentageOfNodesToScore gets the exact rotating-window
    emulation (the adaptive default now runs full-batch evaluation — the
    SURVEY §2.7 P2 divergence): at 150 nodes / 66% the window restricts to
    K=100 and the batch path must still place everything, with the comparer
    confirming validity."""
    store = ClusterStore()
    sched = TPUScheduler(store, batch_size=16, comparer_every_n=4,
                         percentage_of_nodes_to_score=66)
    for i in range(150):
        store.create_node(
            make_node(f"n{i}").capacity({"cpu": "8", "memory": "16Gi", "pods": 20}).obj())
    for i in range(60):
        store.create_pod(make_pod(f"p{i}").req({"cpu": "1", "memory": "1Gi"}).obj())
    sched.run_until_settled()
    assert sched.metrics["scheduled"] == 60
    assert sched.comparer_mismatches == 0
    assert sched._start_carry is not None  # the sampling path actually ran


def test_pipeline_equivalence_with_heterogeneous_batches():
    """Mixed spread + affinity + plain pods across several batches: pipelined
    and synchronous runs must produce identical placements."""
    def build(store):
        for i in range(12):
            store.create_node(
                make_node(f"n{i}").capacity({"cpu": "8", "memory": "16Gi", "pods": 20})
                .label("zone", f"z{i % 3}").label("disk", "ssd" if i % 2 else "hdd").obj())
        sel = LabelSelector(match_labels={"app": "web"})
        for i in range(18):
            pw = make_pod(f"p{i}").req({"cpu": "1", "memory": "1Gi"})
            if i % 3 == 0:
                pw.label("app", "web").spread_constraint(1, "zone", selector=sel)
            if i % 4 == 0:
                pw.node_affinity_in("disk", ["ssd"])
            store.create_pod(pw.obj())

    store_p, sched_p = _run(True, build)
    store_s, sched_s = _run(False, build)
    assert sched_p.pipelined_batches > 0
    assert _bound(store_p) == _bound(store_s)
    assert sched_p.metrics["scheduled"] == sched_s.metrics["scheduled"]
    assert sched_p.comparer_mismatches == 0


def test_adaptive_default_samples_on_cpu(monkeypatch):
    """Platform-aware adaptive default (VERDICT r4): on CPU the default
    config (percentageOfNodesToScore=0) keeps the reference's adaptive
    sampling — at 150 nodes the window is 48% ≈ 72→100 floor — while
    KTPU_FULL_BATCH=1 restores the accelerator full-batch behavior."""
    def run(full_batch_flag):
        monkeypatch.setenv("KTPU_FULL_BATCH", full_batch_flag)
        store = ClusterStore()
        sched = TPUScheduler(store, batch_size=16)
        for i in range(150):
            store.create_node(
                make_node(f"n{i}").capacity({"cpu": "8", "memory": "16Gi", "pods": 20}).obj())
        for i in range(30):
            store.create_pod(make_pod(f"p{i}").req({"cpu": "1", "memory": "1Gi"}).obj())
        sched.run_until_settled()
        assert sched.metrics["scheduled"] == 30
        return sched

    sampled = run("0")   # reference adaptive sampling path
    assert sampled._start_carry is not None, "sampling path did not run"
    full = run("1")      # accelerator-style full batch
    assert full._start_carry is None, "full-batch path unexpectedly sampled"


def test_batch_sizer_deadline_bounds_batches():
    """The deadline-based sizer (ON by default, KTPU_BATCH_DEADLINE_MS=500)
    shrinks the target batch when observed cycles are slow, and never below
    min_batch."""
    from kubernetes_tpu.backend.sizer import BatchSizer

    sizer = BatchSizer(max_batch=512, deadline_s=0.5)
    for _ in range(20):
        sizer.update(512, 2.0)  # 2s cycles: way over deadline
    assert sizer.min_batch <= sizer.target() < 512
    fast = BatchSizer(max_batch=512, deadline_s=0.5)
    for _ in range(20):
        fast.update(512, 0.02)  # fast cycles: deadline never binds
    assert fast.target() == 512
