"""Speculative decode (vectorized decide/repair rounds) vs the sequential
scan: EXACT placement parity — the prefix-stability acceptance must
reproduce the scan's per-pod choices, not just the same load shape."""

import numpy as np
import pytest

from kubernetes_tpu.api.wrappers import make_node, make_pod
from kubernetes_tpu.apiserver import ClusterStore
from kubernetes_tpu.backend import TPUScheduler
from kubernetes_tpu.backend.batch import build_schedule_batch_fn


def _mk_inputs(n_nodes, pods, batch):
    store = ClusterStore()
    sched = TPUScheduler(store, batch_size=batch)
    for i in range(n_nodes):
        store.create_node(
            make_node(f"n{i}")
            .capacity({"cpu": ["4", "8", "16"][i % 3], "memory": "16Gi",
                       "pods": 20})
            .label("zone", f"z{i % 3}").obj())
    sched._ensure_device()
    sched.cache.update_snapshot(sched.snapshot)
    sched.device.sync(sched.snapshot)
    pb, et = sched.device.encoder.encode_pods(pods)
    tb = sched.device.sig_table.encode_topo(pods)
    return sched, pb, et, tb


def _run(monkeypatch, flag, n_nodes, pods, batch):
    monkeypatch.setenv("KTPU_SPEC", flag)
    monkeypatch.setenv("KTPU_PALLAS", "0")
    sched, pb, et, tb = _mk_inputs(n_nodes, pods, batch)
    fn = build_schedule_batch_fn()
    r = fn(pb, et, sched.device.nt, sched.device.tc, tb, np.int32(7),
           topo_enabled=False)
    return (np.asarray(r.node_idx), np.asarray(r.any_feasible),
            np.asarray(r.final_requested), np.asarray(r.first_fail),
            np.asarray(r.final_class_req))


class TestExactParity:
    def _check(self, monkeypatch, pods, n_nodes=24, batch=32):
        idx_a, anyf_a, req_a, ff_a, cls_a = _run(
            monkeypatch, "0", n_nodes, pods, batch)
        idx_b, anyf_b, req_b, ff_b, cls_b = _run(
            monkeypatch, "1", n_nodes, pods, batch)
        np.testing.assert_array_equal(idx_a, idx_b)
        np.testing.assert_array_equal(anyf_a, anyf_b)
        np.testing.assert_array_equal(req_a, req_b)
        np.testing.assert_array_equal(cls_a, cls_b)
        # decision-time rows must match for every VALID pod — failures
        # (first_fail drives per-node failure attribution) AND winners
        # (mixed components are each pod's exact sequential view)
        valid = (idx_a >= 0) | ~anyf_a
        np.testing.assert_array_equal(ff_a[valid], ff_b[valid])

    def test_uniform_pods(self, monkeypatch):
        pods = [make_pod(f"p{i}").req({"cpu": "500m", "memory": "1Gi"}).obj()
                for i in range(24)]
        self._check(monkeypatch, pods)

    def test_mixed_sizes_with_conflicts(self, monkeypatch):
        # big pods force intra-batch capacity conflicts -> multiple rounds
        pods = [make_pod(f"p{i}").req(
            {"cpu": ["3500m", "7", "300m"][i % 3], "memory": "2Gi"}).obj()
            for i in range(30)]
        self._check(monkeypatch, pods)

    def test_unschedulable_pods(self, monkeypatch):
        pods = [make_pod(f"p{i}").req({"cpu": "500m"}).obj() for i in range(6)]
        pods.append(make_pod("huge").req({"cpu": "64"}).obj())
        pods.append(make_pod("huge2").req({"cpu": "64"}).obj())
        idx_a, anyf_a, *_ = _run(monkeypatch, "0", 8, pods, 16)
        idx_b, anyf_b, *_ = _run(monkeypatch, "1", 8, pods, 16)
        np.testing.assert_array_equal(idx_a, idx_b)
        np.testing.assert_array_equal(anyf_a, anyf_b)
        assert idx_a[6] == -1 and idx_a[7] == -1

    def test_host_ports_conflict(self, monkeypatch):
        pods = [make_pod(f"p{i}").req({"cpu": "100m"}).host_port(8080).obj()
                for i in range(6)]
        self._check(monkeypatch, pods, n_nodes=4, batch=8)

    def test_priorities_and_selectors(self, monkeypatch):
        pods = []
        for i in range(20):
            pw = make_pod(f"p{i}").req({"cpu": "800m"}).priority(i % 4)
            if i % 5 == 0:
                pw.node_selector({"zone": "z1"})
            if i % 7 == 0:
                pw.preferred_node_affinity(5, "zone", ["z2"])
            pods.append(pw.obj())
        self._check(monkeypatch, pods)

    def test_normalization_coupling_near_capacity(self, monkeypatch):
        # the stability hazard: preferred-affinity max nodes fill up mid
        # round, shrinking later pods' feasible sets and rescaling every
        # normalized score — the exact-mix check must keep parity
        pods = []
        for i in range(24):
            pw = make_pod(f"p{i}").req({"cpu": "3500m"})  # ~1 pod per 4-cpu node
            pw.preferred_node_affinity(10, "zone", ["z0"])
            pw.preferred_node_affinity(3, "zone", ["z1"])
            pods.append(pw.obj())
        self._check(monkeypatch, pods, n_nodes=12, batch=32)

    def test_interleaved_failures_and_commits(self, monkeypatch):
        # failing pods interleaved between winners exercise the fail-before-
        # first-winner prefix rule
        pods = []
        for i in range(16):
            if i % 3 == 2:
                pods.append(make_pod(f"big{i}").req({"cpu": "64"}).obj())
            else:
                pods.append(make_pod(f"p{i}").req({"cpu": "900m"}).obj())
        self._check(monkeypatch, pods, n_nodes=6, batch=16)

    def test_one_slot_node_capacity_conflict(self, monkeypatch):
        # the flagship conflict case: 3 identical pods, one 1-pod node
        store = ClusterStore()
        monkeypatch.setenv("KTPU_SPEC", "1")
        monkeypatch.setenv("KTPU_PALLAS", "0")
        sched = TPUScheduler(store, batch_size=8)
        store.create_node(make_node("only").capacity(
            {"cpu": "1", "memory": "2Gi", "pods": 10}).obj())
        for i in range(3):
            store.create_pod(make_pod(f"p{i}").req({"cpu": "900m"}).obj())
        sched.run_until_settled(max_no_progress=3)
        bound = [p for p in store.pods.values() if p.spec.node_name]
        assert len(bound) == 1


def _zone_node(i):
    return (make_node(f"n{i}").capacity({"cpu": "8", "memory": "16Gi", "pods": 20})
            .label("zone", f"z{i % 3}"))


def _run_sched(monkeypatch, flag, build_workload, n_nodes=12, batch=16,
               node=_zone_node, comparer=True):
    """Full TPUScheduler run under KTPU_SPEC=flag; returns {pod: node}."""
    monkeypatch.setenv("KTPU_SPEC", flag)
    monkeypatch.setenv("KTPU_PALLAS", "0")
    store = ClusterStore()
    sched = TPUScheduler(store, batch_size=batch,
                         comparer_every_n=1 if comparer else 0)
    for i in range(n_nodes):
        store.create_node(node(i).obj())
    build_workload(store)
    sched.run_until_settled(max_no_progress=5)
    assert sched.comparer_mismatches == 0
    return {p.meta.name: p.spec.node_name for p in store.pods.values()}


class TestHostModeTopologyParity:
    """Hostname-topology batches (the host fast path) through the
    speculative rounds: placements must match the scan exactly, with the
    oracle comparer checking every placement on both runs."""

    def _check(self, monkeypatch, build_workload, **kw):
        a = _run_sched(monkeypatch, "0", build_workload, **kw)
        b = _run_sched(monkeypatch, "1", build_workload, **kw)
        assert a == b

    def test_hostname_spread(self, monkeypatch):
        from kubernetes_tpu.api.types import LabelSelector

        def workload(store):
            for i in range(20):
                store.create_pod(
                    make_pod(f"p{i}").req({"cpu": "500m"}).label("app", "web")
                    .spread_constraint(
                        1, "kubernetes.io/hostname",
                        selector=LabelSelector(match_labels={"app": "web"}))
                    .obj())

        self._check(monkeypatch, workload)

    def test_hostname_anti_affinity(self, monkeypatch):
        from kubernetes_tpu.api.types import LabelSelector

        def workload(store):
            for i in range(14):
                store.create_pod(
                    make_pod(f"p{i}").req({"cpu": "500m"}).label("app", "db")
                    .pod_affinity("kubernetes.io/hostname",
                                  LabelSelector(match_labels={"app": "db"}),
                                  anti=True)
                    .obj())

        self._check(monkeypatch, workload, n_nodes=10)

    def test_hostname_anti_affinity_overflow_unschedulable(self, monkeypatch):
        # more exclusive pods than nodes: the tail must fail identically
        from kubernetes_tpu.api.types import LabelSelector

        def workload(store):
            for i in range(8):
                store.create_pod(
                    make_pod(f"p{i}").req({"cpu": "100m"}).label("app", "x")
                    .pod_affinity("kubernetes.io/hostname",
                                  LabelSelector(match_labels={"app": "x"}),
                                  anti=True)
                    .obj())

        a = _run_sched(monkeypatch, "0", workload, n_nodes=5, batch=8)
        b = _run_sched(monkeypatch, "1", workload, n_nodes=5, batch=8)
        assert a == b
        assert sum(1 for v in a.values() if v) == 5  # one per node

    def test_required_self_affinity_first_pod_rule(self, monkeypatch):
        # IPA's first-pod rule (total==0 & self-match ⇒ feasible anywhere)
        # flips globally once the first pod lands: a mid-round winner's
        # mixed view can collapse to all-infeasible — the stability check's
        # chosen-feasibility guard must defer it, keeping scan parity
        from kubernetes_tpu.api.types import LabelSelector

        def workload(store):
            for i in range(10):
                store.create_pod(
                    make_pod(f"p{i}").req({"cpu": "500m"}).label("app", "herd")
                    .pod_affinity("kubernetes.io/hostname",
                                  LabelSelector(match_labels={"app": "herd"}))
                    .obj())

        a = _run_sched(monkeypatch, "0", workload, n_nodes=6, batch=16)
        b = _run_sched(monkeypatch, "1", workload, n_nodes=6, batch=16)
        assert a == b
        # required colocation on hostname: everyone lands on ONE node
        nodes = {v for v in a.values() if v}
        assert len(nodes) == 1

    def test_mixed_spread_affinity_priorities(self, monkeypatch):
        from kubernetes_tpu.api.types import LabelSelector, SCHEDULE_ANYWAY

        def workload(store):
            for i in range(24):
                pw = (make_pod(f"p{i}").req({"cpu": ["250m", "1"][i % 2]})
                      .label("app", f"svc{i % 2}").priority(i % 3))
                if i % 2 == 0:
                    pw.spread_constraint(
                        2, "kubernetes.io/hostname",
                        when_unsatisfiable=SCHEDULE_ANYWAY,
                        selector=LabelSelector(match_labels={"app": "svc0"}))
                else:
                    pw.preferred_pod_affinity(
                        10, "kubernetes.io/hostname",
                        LabelSelector(match_labels={"app": "svc1"}))
                store.create_pod(pw.obj())

        self._check(monkeypatch, workload)


class TestGeneralModeTopologyParity:
    """Zone-keyed (general domain-aggregating) topology through the
    speculative rounds: exact scan parity, all placements oracle-checked."""

    def _check(self, monkeypatch, build_workload, **kw):
        a = _run_sched(monkeypatch, "0", build_workload, **kw)
        b = _run_sched(monkeypatch, "1", build_workload, **kw)
        assert a == b

    def test_zone_spread_do_not_schedule(self, monkeypatch):
        from kubernetes_tpu.api.types import LabelSelector

        def workload(store):
            for i in range(18):
                store.create_pod(
                    make_pod(f"p{i}").req({"cpu": "500m"}).label("app", "web")
                    .spread_constraint(
                        1, "zone",
                        selector=LabelSelector(match_labels={"app": "web"}))
                    .obj())

        self._check(monkeypatch, workload)

    def test_zone_anti_affinity(self, monkeypatch):
        from kubernetes_tpu.api.types import LabelSelector

        def workload(store):
            # 3 zones: only 3 of 5 exclusive pods can place
            for i in range(5):
                store.create_pod(
                    make_pod(f"p{i}").req({"cpu": "250m"}).label("app", "zdb")
                    .pod_affinity("zone",
                                  LabelSelector(match_labels={"app": "zdb"}),
                                  anti=True)
                    .obj())

        a = _run_sched(monkeypatch, "0", workload, n_nodes=9, batch=8)
        b = _run_sched(monkeypatch, "1", workload, n_nodes=9, batch=8)
        assert a == b
        assert sum(1 for v in a.values() if v) == 3

    def test_zone_required_affinity_colocates(self, monkeypatch):
        from kubernetes_tpu.api.types import LabelSelector

        def workload(store):
            for i in range(9):
                store.create_pod(
                    make_pod(f"p{i}").req({"cpu": "500m"}).label("app", "herd")
                    .pod_affinity("zone",
                                  LabelSelector(match_labels={"app": "herd"}))
                    .obj())

        a = _run_sched(monkeypatch, "0", workload, n_nodes=9, batch=16)
        b = _run_sched(monkeypatch, "1", workload, n_nodes=9, batch=16)
        assert a == b
        zones = {int(v[1:]) % 3 for v in a.values() if v}
        assert len(zones) == 1  # required zone colocation

    def test_mixed_zone_spread_preferred_affinity(self, monkeypatch):
        from kubernetes_tpu.api.types import LabelSelector, SCHEDULE_ANYWAY

        def workload(store):
            for i in range(24):
                pw = (make_pod(f"p{i}").req({"cpu": ["250m", "1"][i % 2]})
                      .label("app", f"svc{i % 2}"))
                if i % 2 == 0:
                    pw.spread_constraint(
                        2, "zone", when_unsatisfiable=SCHEDULE_ANYWAY,
                        selector=LabelSelector(match_labels={"app": "svc0"}))
                else:
                    pw.preferred_pod_affinity(
                        10, "zone", LabelSelector(match_labels={"app": "svc1"}))
                store.create_pod(pw.obj())

        self._check(monkeypatch, workload)

    def test_zone_spread_min_domains_and_self_anti(self, monkeypatch):
        from kubernetes_tpu.api.types import LabelSelector

        def workload(store):
            # spread + anti-affinity interactions across one batch
            for i in range(12):
                pw = (make_pod(f"p{i}").req({"cpu": "500m"})
                      .label("app", "mix"))
                pw.spread_constraint(
                    1, "zone",
                    selector=LabelSelector(match_labels={"app": "mix"}))
                if i % 4 == 0:
                    pw.pod_affinity("zone",
                                    LabelSelector(match_labels={"app": "mix"}),
                                    anti=True)
                store.create_pod(pw.obj())

        self._check(monkeypatch, workload)


ZONE_KEY = "topology.kubernetes.io/zone"


def _topology_spreading_node(i):
    """scheduler_perf's node-default.yaml node, zone-labelled as
    TopologySpreading's labelNodePrepareStrategy does: node i in zone i % 3."""
    return (make_node(f"n{i}").capacity({"cpu": "4", "memory": "32Gi", "pods": 110})
            .label(ZONE_KEY, f"zone-{i % 3}"))


def _topology_spreading_workload(n_pending, n_skewed):
    """TopologySpreading's pods (100m/500Mi, zone DoNotSchedule, maxSkew 5,
    selecting their own label) behind ``n_skewed`` already-bound pods of the
    same label, all in zone-0, so the skew filter binds from the first pod."""
    from kubernetes_tpu.api.types import LabelSelector

    sel = LabelSelector(match_labels={"app": "spread-backlog"})

    def pod(name):
        return (make_pod(name).req({"cpu": "100m", "memory": "500Mi"})
                .label("app", "spread-backlog")
                .spread_constraint(5, ZONE_KEY, selector=sel))

    def workload(store):
        for i in range(n_skewed):
            store.create_pod(pod(f"bound{i}").node(f"n{3 * i}").obj())
        for i in range(n_pending):
            store.create_pod(pod(f"p{i}").obj())

    return workload


class TestSpreadBacklogShape:
    """The spread-backlog cell's constraint, scaled to seconds on the CPU:
    3 zones on a node-default-shaped cluster, maxSkew 5."""

    def test_zone_max_skew_5_parity(self, monkeypatch):
        # The oracle comparer is off here: it re-runs the filters on the
        # snapshot before the batch's bind tail lands, so it misses the
        # batch's own earlier winners, and skew rejections are exactly what
        # that view gets wrong. The skew rule is replayed below instead.
        n_pending, n_skewed = 48, 9
        workload = _topology_spreading_workload(n_pending, n_skewed)
        a = _run_sched(monkeypatch, "0", workload, n_nodes=30, batch=16,
                       node=_topology_spreading_node, comparer=False)
        b = _run_sched(monkeypatch, "1", workload, n_nodes=30, batch=16,
                       node=_topology_spreading_node, comparer=False)
        assert a == b
        # pods pop in creation order: each one's zone must satisfy
        # count + 1 - min <= maxSkew on the zones its predecessors left
        counts = [n_skewed, 0, 0]
        for i in range(n_pending):
            zone = int(a[f"p{i}"][1:]) % 3
            assert counts[zone] + 1 - min(counts) <= 5, (i, counts, zone)
            counts[zone] += 1
        # nine pods start in zone-0: the filter keeps the first pods out
        assert all(int(a[f"p{i}"][1:]) % 3 != 0 for i in range(4))

    def test_dispatch_span_names_onehot_segment_sums(self, monkeypatch):
        from kubernetes_tpu.utils import tracing

        monkeypatch.setenv("KTPU_SPEC", "1")
        monkeypatch.setenv("KTPU_PALLAS", "0")
        tracer = tracing.enable()
        try:
            store = ClusterStore()
            sched = TPUScheduler(store, batch_size=16)
            for i in range(12):
                store.create_node(_topology_spreading_node(i).obj())
            _topology_spreading_workload(n_pending=20, n_skewed=3)(store)
            sched.run_until_settled(max_no_progress=5)
            spans = tracer.exporter.by_name("device.dispatch")
        finally:
            tracing.disable()
        general = [s.attributes for s in spans
                   if s.attributes.get("topo") == "general"]
        assert general
        for attrs in general:
            assert attrs["vd"] == 64 and attrs["seg"] == "onehot"


class TestEndToEndForcedSpec:
    def test_full_scheduler_with_spec_decode(self, monkeypatch):
        monkeypatch.setenv("KTPU_SPEC", "1")
        monkeypatch.setenv("KTPU_PALLAS", "0")
        store = ClusterStore()
        sched = TPUScheduler(store, batch_size=64)
        for i in range(16):
            store.create_node(make_node(f"n{i}").capacity(
                {"cpu": "8", "memory": "16Gi", "pods": 30}).obj())
        for i in range(200):
            store.create_pod(make_pod(f"p{i}").req(
                {"cpu": "500m", "memory": "512Mi"}).obj())
        sched.run_until_settled()
        assert sched.metrics["scheduled"] == 200
        assert sched.comparer_mismatches == 0
