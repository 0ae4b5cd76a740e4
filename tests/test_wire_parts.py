"""Bounded ApplyDeltas parts (backend/wire.py): a full sync larger than one
part goes through a gRPC server with its default message limits in several
parts, each under the bound, and leaves the mirror a one-message sync
leaves; the ghost sweep runs once; a failure partway leaves nothing marked
as sent; a message-size rejection is not retried; the client's wire spans
parent the service's; the wire warm-up compiles before the first batch and
then settles the heap."""

import gc

import grpc
import pytest

from kubernetes_tpu.api.wrappers import make_node, make_pod
from kubernetes_tpu.apiserver.store import ClusterStore
from kubernetes_tpu.backend import wire
from kubernetes_tpu.backend.device_state import caps_for_cluster
from kubernetes_tpu.backend.errors import (PermanentDeviceError, RetryPolicy,
                                           TransientDeviceError)
from kubernetes_tpu.backend.grpc_service import (GrpcClient, pb2,
                                                 pb2_available, serve_grpc)
from kubernetes_tpu.backend.service import (DeviceService, WireClient,
                                            WireScheduler, serve)
from kubernetes_tpu.utils import heap, tracing

pytestmark = pytest.mark.skipif(not pb2_available(),
                                reason="no protobuf module for the gRPC binding")


def _cluster(n_nodes: int, n_running: int) -> ClusterStore:
    """scheduler_perf's node-default and pod-default shapes: ``n_running``
    pods bound, one per node."""
    store = ClusterStore()
    for i in range(n_nodes):
        store.create_node(make_node(f"node-{i}").capacity(
            {"cpu": "4", "memory": "32Gi", "pods": 110}).obj())
    for j in range(n_running):
        pod = make_pod(f"run-{j}").req({"cpu": "100m", "memory": "500Mi"}).obj()
        pod.spec.node_name = f"node-{(j * 7) % n_nodes}"
        pod.status.phase = "Running"
        store.create_pod(pod)
    return store


def _mirror(svc: DeviceService) -> dict:
    return {
        "nodes": {name: (ni.generation, sorted(p.key() for p in ni.pods))
                  for name, ni in svc.infos.items()},
        "namespaces": dict(svc.ns_labels),
        "quota": dict(svc.quota_table),
    }


class _Parts:
    """Records every ApplyDeltas request a client sends, by its size."""

    def __init__(self, client, fail_on=None):
        self.sizes = []
        self.full = []
        self.fail_on = fail_on
        self._send = client._send_deltas
        client._send_deltas = self

    def __call__(self, request):
        if isinstance(request, bytes):
            self.sizes.append(len(request))
            self.full.append(b'"full": true' in request)
        else:
            self.sizes.append(request.ByteSize())
            self.full.append(bool(request.full))
        if self.fail_on is not None and len(self.sizes) == self.fail_on:
            raise TransientDeviceError("connection reset on this part")
        return self._send(request)


@pytest.fixture
def grpc_service():
    svc = DeviceService(batch_size=128)
    server, port = serve_grpc(svc)
    yield svc, port
    server.stop(0).wait()


def _wire_scheduler(store, port, **kw):
    return WireScheduler(store, endpoint=f"127.0.0.1:{port}", batch_size=128,
                         transport="grpc", **kw)


def test_full_sync_at_5000_nodes_fits_default_grpc_limits(grpc_service,
                                                          monkeypatch):
    """SchedulingBasic 5000Nodes with its 1000 running pods: one message
    would pass gRPC's 4 MiB; the parts go through, each under the bound,
    and the mirror equals that of a one-message sync over HTTP."""
    svc, port = grpc_service
    store = _cluster(5000, 1000)
    sched = _wire_scheduler(store, port)
    parts = _Parts(sched.client)
    sched._push_deltas()
    assert len(parts.sizes) > 1
    assert max(parts.sizes) <= wire.MAX_DELTA_PART_BYTES
    assert sum(parts.sizes) > 4 * 1024 * 1024  # one message would not fit
    assert parts.full == [True] + [False] * (len(parts.sizes) - 1)
    assert len(sched._sent_gens) == 5000
    got = _mirror(svc)
    assert {n: g for n, (g, _) in got["nodes"].items()} == sched._sent_gens
    assert sum(len(p) for _, p in got["nodes"].values()) == 1000
    assert svc.device.caps.nodes == caps_for_cluster(5000).nodes
    sched.client.close()

    # the same state as one message to an HTTP service
    ref = DeviceService(batch_size=128)
    server, http_port = serve(ref)
    try:
        monkeypatch.setattr(wire, "MAX_DELTA_PART_BYTES", 1 << 30)
        client = WireClient(f"http://127.0.0.1:{http_port}")
        one = _Parts(client)
        entries, _gens = sched._build_entries(skip_unsent_check=True)
        client.apply_deltas({"apiVersion": "ktpu/v1", "full": True,
                             "nodes": entries, "removed": [],
                             "namespaces": {}, "clientId": sched.client_id})
    finally:
        server.shutdown()
        server.server_close()
    assert len(one.sizes) == 1
    assert _mirror(ref) == got
    assert ref.device.caps == svc.device.caps


def test_ghost_swept_once_and_live_nodes_kept(grpc_service, monkeypatch):
    """A node the service holds from a predecessor and the full set omits
    is dropped once, in the first part; no node of the set is dropped,
    though most of them arrive in later parts."""
    monkeypatch.setattr(wire, "MAX_DELTA_PART_BYTES", 16 * 1024)
    svc, port = grpc_service
    store = _cluster(120, 40)
    # the predecessor's world: a live node and a ghost
    svc.apply_deltas({"full": True, "nodes": [
        {"node": {"meta": {"name": n}, "spec": {}, "status": {
            "allocatable": {"cpu": "4", "memory": "32Gi", "pods": "110"}}},
         "pods": [], "gen": 1} for n in ("node-3", "ghost-0")]})
    dropped = []
    drop = svc._drop_node
    monkeypatch.setattr(svc, "_drop_node",
                        lambda name: (dropped.append(name), drop(name)))
    sched = _wire_scheduler(store, port)
    parts = _Parts(sched.client)
    sched._push_deltas()
    sched.client.close()
    assert len(parts.sizes) >= 3
    assert dropped == ["ghost-0"]
    assert set(svc.infos) == {f"node-{i}" for i in range(120)}
    assert svc.infos["node-3"].generation == sched._sent_gens["node-3"]


@pytest.mark.parametrize("path", ["first_contact", "epoch_resync"])
def test_failure_on_part_two_leaves_nothing_sent(grpc_service, monkeypatch,
                                                 path):
    monkeypatch.setattr(wire, "MAX_DELTA_PART_BYTES", 16 * 1024)
    svc, port = grpc_service
    store = _cluster(120, 40)
    sched = _wire_scheduler(store, port)
    if path == "epoch_resync":
        sched._push_deltas()
        assert sched._sent_gens
    parts = _Parts(sched.client, fail_on=2)
    with pytest.raises(TransientDeviceError):
        if path == "epoch_resync":
            sched._full_resync(svc.epoch)
        else:
            sched._push_deltas()
    assert sched._sent_gens == {}
    assert sched._pushed_nodes == set()
    assert sched._device_epoch is None
    parts.fail_on = None
    sent = len(parts.sizes)
    sched._push_deltas()  # the next push is a full sync again
    assert parts.full[sent] is True and not any(parts.full[sent + 1:])
    assert len(sched._sent_gens) == 120
    assert set(svc.infos) == set(sched._sent_gens)
    sched.client.close()


def _counting_client(port):
    retries = []
    policy = RetryPolicy(max_retries=3, backoff_base=0.0, backoff_max=0.0,
                         sleep_fn=lambda s: None,
                         on_retry=lambda op: retries.append(op))
    return GrpcClient(f"127.0.0.1:{port}", retry=policy), retries


def test_size_rejection_is_not_retried(grpc_service):
    """A request over the server's receive limit is refused for its size:
    permanent, sent once."""
    _svc, port = grpc_service
    client, retries = _counting_client(port)
    p = pb2()
    req = p.ApplyDeltasRequest(full=True)
    req.nodes.append(p.NodeDelta(node_json=b"x" * (5 << 20), gen=1))
    with pytest.raises(PermanentDeviceError, match="larger than max"):
        client._send_deltas(req)
    assert retries == []
    client.close()


class _Exhausted(grpc.RpcError):
    def __init__(self, details):
        self._details = details

    def code(self):
        return grpc.StatusCode.RESOURCE_EXHAUSTED

    def details(self):
        return self._details


@pytest.mark.parametrize("details,error,sends", [
    ("Received message larger than max (4542224 vs. 4194304)",
     PermanentDeviceError, 1),
    ("server overloaded: too many concurrent streams",
     TransientDeviceError, 4),
])
def test_resource_exhausted_by_cause(grpc_service, details, error, sends):
    """Only the message-size rejection is permanent; other
    RESOURCE_EXHAUSTED answers stay transient and are retried."""
    _svc, port = grpc_service
    client, _retries = _counting_client(port)
    calls = []

    def stub(request, timeout=None):
        calls.append(request)
        raise _Exhausted(details)
    with pytest.raises(error):
        client._call("apply_deltas", stub, object())
    assert len(calls) == sends
    client.close()


def test_wire_spans_parent_the_service_spans(grpc_service, monkeypatch):
    """One wire.apply_deltas span per part under the scheduling cycle, one
    wire.schedule_batch per batch call; each server span is the child of
    the client span whose traceparent its request carried; the request
    bytes counter counts both kinds."""
    monkeypatch.setattr(wire, "MAX_DELTA_PART_BYTES", 16 * 1024)
    svc, port = grpc_service
    store = _cluster(120, 40)
    for i in range(30):
        store.create_pod(make_pod(f"p-{i}").req(
            {"cpu": "100m", "memory": "500Mi"}).obj())
    tracer = tracing.enable(tracing.InMemoryExporter())
    try:
        sched = _wire_scheduler(store, port)
        sched.run_until_settled()
    finally:
        tracing.disable()
        sched.client.close()
    assert sched.metrics["scheduled"] == 30
    spans = tracer.exporter.spans
    by_id = {s.span_id: s for s in spans}
    pushes = [s for s in spans if s.name == "wire.apply_deltas"]
    first = [s for s in pushes if s.attributes["parts"] > 1]
    assert first and first[0].attributes["full"] is True
    for s in pushes:
        assert set(s.attributes) == {"nodes", "bytes", "full", "part", "parts"}
        assert 0 < s.attributes["bytes"] <= wire.MAX_DELTA_PART_BYTES
        assert by_id[s.parent_id].name == "scheduling.cycle"
    for server, client in (("device.apply_deltas", "wire.apply_deltas"),
                           ("device.schedule_batch", "wire.schedule_batch")):
        served = [s for s in spans if s.name == server]
        assert served
        for s in served:
            assert by_id[s.parent_id].name == client
    calls = [s for s in spans if s.name == "wire.schedule_batch"]
    assert sum(s.attributes["pods"] for s in calls) == 30
    m = sched.smetrics.wire_request_bytes
    assert m.labels("apply_deltas") == sum(s.attributes["bytes"]
                                           for s in pushes)
    assert m.labels("schedule_batch") == sum(s.attributes["bytes"]
                                             for s in calls)


def test_warm_buckets_compiles_before_the_first_batch(grpc_service):
    """The wire twin of TPUScheduler.warm_buckets: a full push and one
    ScheduleBatch with no pods, which the service runs on the program every
    batch runs, placing nothing; a dead service warms nothing."""
    svc, port = grpc_service
    store = _cluster(40, 10)
    sched = _wire_scheduler(store, port)
    sample = make_pod("warm-sample").req({"cpu": "100m"}).obj()
    assert sched.warm_buckets(sample_pods=[sample]) == 1
    assert len(svc.infos) == 40 and svc.batch_counter == 1
    assert not svc.holds and "default/warm-sample" not in svc._pod_nodes
    for i in range(5):
        store.create_pod(make_pod(f"p-{i}").req({"cpu": "100m"}).obj())
    sched.run_until_settled()
    assert sched.metrics["scheduled"] == 5 and sched.degraded_pods == 0
    sched.client.close()

    dead = WireScheduler(ClusterStore(), endpoint="http://127.0.0.1:1",
                         batch_size=8, connect_timeout=0.2,
                         wire_max_retries=0)
    assert dead.warm_buckets() == 0


def test_warm_buckets_settles_the_heap(grpc_service):
    """Once the wire warm-up has reached the service, the process's warm
    heap leaves the collector (utils/heap.py), as after
    TPUScheduler.warm_buckets: client and service share this process."""
    _svc, port = grpc_service
    sched = _wire_scheduler(_cluster(40, 10), port)
    assert not heap.settled()
    assert sched.warm_buckets() == 1
    assert heap.settled() and gc.get_freeze_count() > 0
    assert gc.get_threshold()[0] == heap.YOUNG_THRESHOLD
    assert sched.smetrics.gc_frozen_objects.labels() > 0
    sched.client.close()
