"""The collector's schedule after warm-up (utils/heap.py): collect, then
freeze, then raise the young threshold, once per process; the span and the
gauge that show it engaged; TPUScheduler.warm_buckets calling it; and no
per-pod leak once the warm heap is frozen."""

import gc
import weakref

from kubernetes_tpu.api.wrappers import make_node, make_pod
from kubernetes_tpu.apiserver.store import ClusterStore
from kubernetes_tpu.backend import TPUScheduler
from kubernetes_tpu.metrics.scheduler_metrics import SchedulerMetrics
from kubernetes_tpu.utils import heap, tracing


class _Cycle:
    """A reference cycle: garbage only the collector can reclaim."""

    def __init__(self):
        self.me = self


def _in_young_generations(obj) -> bool:
    return any(o is obj for g in range(3) for o in gc.get_objects(g))


def test_it_collects_before_it_freezes():
    live = _Cycle()
    dead = _Cycle()
    gone = weakref.ref(dead)
    del dead
    assert gone() is not None  # only a collection reclaims a cycle
    frozen = heap.settle_warm_heap()
    assert gone() is None
    # (frozen objects that die by refcount leave the count)
    assert frozen >= gc.get_freeze_count() > frozen - 100
    assert not _in_young_generations(live)  # frozen, out of every walk


def test_a_second_call_changes_nothing():
    first = heap.settle_warm_heap()
    thresholds = gc.get_threshold()
    later = [_Cycle() for _ in range(100)]
    assert heap.settle_warm_heap() == first
    assert gc.get_freeze_count() <= first
    assert gc.get_threshold() == thresholds
    assert all(_in_young_generations(o) for o in later[:3])


def test_it_sets_the_thresholds_and_restore_puts_them_back():
    before = gc.get_threshold()
    heap.settle_warm_heap()
    assert gc.get_threshold() == (heap.YOUNG_THRESHOLD,
                                  heap.MIDDLE_THRESHOLD, before[2])
    assert heap.settled()
    heap.restore()
    assert gc.get_threshold() == before
    assert gc.get_freeze_count() == 0 and not heap.settled()


def test_it_sets_the_gauge():
    m = SchedulerMetrics()
    frozen = heap.settle_warm_heap(m)
    assert m.gc_frozen_objects.labels() == float(frozen) > 0
    other = SchedulerMetrics()
    assert heap.settle_warm_heap(other) == frozen
    assert other.gc_frozen_objects.labels() == float(frozen)


def test_it_emits_one_freeze_span_with_tracing_on():
    exp = tracing.InMemoryExporter()
    tracing.enable(exp)
    try:
        frozen = heap.settle_warm_heap()
        heap.settle_warm_heap()
    finally:
        tracing.disable()
    [s] = exp.by_name("runtime.gc_freeze")
    assert s.attributes == {"threshold": heap.YOUNG_THRESHOLD,
                            "frozen": frozen}
    assert frozen > 0
    # the collection it runs is a gen-2 runtime.gc span inside it
    inner = [g for g in exp.by_name("runtime.gc")
             if g.parent_id == s.span_id]
    assert [g.attributes["generation"] for g in inner] == [2]


def _cluster(n_nodes: int, n_running: int) -> ClusterStore:
    store = ClusterStore()
    for i in range(n_nodes):
        store.create_node(make_node(f"node-{i}").capacity(
            {"cpu": "4", "memory": "32Gi", "pods": 110}).obj())
    for j in range(n_running):
        pod = make_pod(f"run-{j}").req({"cpu": "100m", "memory": "500Mi"}).obj()
        pod.spec.node_name = f"node-{j % n_nodes}"
        pod.status.phase = "Running"
        store.create_pod(pod)
    return store


def _sample():
    return make_pod("warm-sample").req({"cpu": "100m", "memory": "500Mi"}).obj()


def test_tpu_warm_buckets_settles_the_heap():
    sched = TPUScheduler(_cluster(20, 10), batch_size=32)
    assert sched.warm_buckets(sample_pods=[_sample()]) > 0
    assert heap.settled() and gc.get_freeze_count() > 0
    assert sched.smetrics.gc_frozen_objects.labels() >= float(
        gc.get_freeze_count())
    assert gc.get_threshold()[0] == heap.YOUNG_THRESHOLD


def _churn(store, sched, tag: str, n: int) -> None:
    """Create ``n`` pods, schedule every one, then delete them all."""
    keys = []
    for j in range(n):
        pod = make_pod(f"{tag}-{j}").req({"cpu": "100m", "memory": "500Mi"}).obj()
        store.create_pod(pod)
        keys.append(pod.key())
    sched.run_until_settled()
    assert all(store.pods[k].spec.node_name for k in keys)
    for k in keys:
        store.delete_pod(k)
    sched.run_until_settled()


def _tracked_graph(obj) -> int:
    """Tracked objects reachable from ``obj``, classes left out."""
    seen = {id(obj)}
    todo = [obj]
    while todo:
        for ref in gc.get_referents(todo.pop()):
            if (gc.is_tracked(ref) and not isinstance(ref, type)
                    and id(ref) not in seen):
                seen.add(id(ref))
                todo.append(ref)
    return len(seen)


def _unfrozen() -> int:
    gc.collect()
    return len(gc.get_objects())


def test_the_frozen_heap_leaks_nothing_per_pod():
    """200 nodes, 400 running pods frozen by the warm-up. Deleting those
    pods frees their frozen objects by refcount; 2000 more pods scheduled
    and deleted leave the unfrozen heap where the freeze left it, but for
    the scheduler's event record (one Event per binding, by its contract);
    and nothing frozen died in a cycle the collector can no longer see."""
    store = _cluster(200, 400)
    # the store's watch journal holds the last events' objects (4096 by
    # default): a short one lets the deleted pods leave it within the churn
    store._journal_capacity = 256
    running = list(store.pods)
    one_pod = _tracked_graph(store.pods[running[0]])
    sched = TPUScheduler(store, batch_size=32)
    sched.warm_buckets(sample_pods=[_sample()])
    frozen0, unfrozen0 = gc.get_freeze_count(), _unfrozen()
    events0 = len(sched.recorder.events)

    for key in running:
        store.delete_pod(key)
    _churn(store, sched, "a", 200)
    gc.collect()
    freed = frozen0 - gc.get_freeze_count()
    # each pod's own objects at least; the cache's wrappers of it besides
    assert freed >= len(running) * one_pod, (freed, one_pod)

    for r in range(4):
        _churn(store, sched, f"b{r}", 200)
    mid = _unfrozen() - (len(sched.recorder.events) - events0)
    for r in range(5):
        _churn(store, sched, f"c{r}", 200)
    end = _unfrozen() - (len(sched.recorder.events) - events0)
    assert len(sched.recorder.events) - events0 == 2000
    # bounded: the re-cloned snapshot, the journal, the queue's tables
    assert end - unfrozen0 < 10_000, (end, unfrozen0)
    # and none of it grows with the pods: 1000 more add nothing per pod
    assert end - mid < 100, (end, mid)

    heap.restore()
    assert gc.collect() < len(running)
