"""gRPC binding of the batched device service (SURVEY §5.8 hop 6).

Hardened transport per ROADMAP round-3 item 5: real gRPC framing (HTTP/2,
protobuf messages generated from native/ktpu_device.proto), pod-template
deduplication on ScheduleBatch (the QPS-5000 workloads reuse a handful of
pod shapes, so the steady-state request is one template table + name refs
instead of N full pod objects), and device-computed preemption hints
riding back with unschedulable results.

Message size: the server keeps gRPC's default 4 MiB receive limit, as a
scheduler plugin on grpc-go's defaults keeps its own. The client cuts each
ApplyDeltas request into bounded parts (backend/wire.py,
MAX_DELTA_PART_BYTES = 1 MiB): a full sync of 5000 nodes goes as about five,
the first carrying ``full_nodes`` so the service's ghost sweep runs once over
the whole set. A request refused for its size is a permanent error, not
retried.

grpc service stubs are not generated (grpc_tools is absent from the image);
the server registers generic method handlers and the client uses
channel.unary_unary — functionally identical to protoc-gen-grpc output.
Messages come from the vendored module tools/gen_pb2.py emits into
kubernetes_tpu/native/ktpu_device_pb2.py (trusted while its embedded
PROTO_SHA256 matches the .proto source); a stale vendored module falls
back to `protoc --python_out` into native/build (cached while its stamp
matches the .proto's hash, utils/protoc.py), and
when protoc is absent too the error names the regeneration command.
"""

from __future__ import annotations

import json
import os
import threading
from typing import Dict, Optional

from . import wire
from .errors import (
    ConflictError,
    PermanentDeviceError,
    RetryPolicy,
    StaleEpochError,
    TransientDeviceError,
    raise_injected_fault,
)

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_PROTO_DIR = os.path.join(_REPO_ROOT, "native")
_PROTO = os.path.join(_PROTO_DIR, "ktpu_device.proto")
_PB2 = os.path.join(_PROTO_DIR, "build", "ktpu_device_pb2.py")

_pb2 = None
_pb2_lock = threading.Lock()

SERVICE = "ktpu.v1.Device"


def _proto_sha256() -> str:
    from ..utils.protoc import source_sha256

    return source_sha256(_PROTO)


def _vendored_hash() -> Optional[str]:
    """PROTO_SHA256 literal read from the vendored module's TEXT — the
    staleness check must run BEFORE the module is imported: executing a
    stale module registers 'ktpu_device.proto' in the process-default
    descriptor pool, and the protoc-built fallback would then raise
    duplicate-file instead of loading."""
    import re

    try:
        with open(os.path.join(_REPO_ROOT, "kubernetes_tpu", "native",
                               "ktpu_device_pb2.py"), encoding="utf-8") as f:
            head = f.read(4096)
    except OSError:
        return None
    m = re.search(r'^PROTO_SHA256 = "([0-9a-f]{64})"', head, re.M)
    return m.group(1) if m else None


def _vendored_pb2():
    """The tools/gen_pb2.py-vendored module, or None when it is absent or
    stale against the current .proto source (hash-gated so a proto edit
    without regeneration can never speak a stale schema)."""
    if _vendored_hash() != _proto_sha256():
        return None
    try:
        from ..native import ktpu_device_pb2 as vendored
    except ImportError:
        return None
    return vendored


def pb2_available() -> bool:
    """True when pb2() will succeed: a hash-fresh vendored module, a
    cached protoc build, or protoc itself."""
    from ..utils.protoc import build_available

    if _vendored_pb2() is not None:
        return True
    return build_available(_pb2, _PB2, _PROTO)


def pb2():
    """Import the protobuf message module: the vendored gen_pb2.py output
    when fresh, else a protoc build (cached while its hash stamp matches)."""
    global _pb2
    if _pb2 is not None:
        return _pb2
    with _pb2_lock:
        if _pb2 is not None:
            return _pb2
        vendored = _vendored_pb2()
        if vendored is not None:
            _pb2 = vendored
            return _pb2
        from ..utils.protoc import protoc_build

        try:
            protoc_build(_PROTO, _PB2)
        except FileNotFoundError as e:
            # typed: deterministic config/availability failure — a retry
            # of the identical call cannot help
            raise PermanentDeviceError(
                "vendored ktpu_device_pb2 is stale or missing and protoc "
                "is not installed; run `python tools/gen_pb2.py`") from e
        import importlib.util

        spec = importlib.util.spec_from_file_location("ktpu_device_pb2", _PB2)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _pb2 = mod
        return _pb2


# ----------------------------------------------------------- dict <-> proto
# (the transport speaks backend/service.py's dict payloads at both ends, so
# DeviceService and WireScheduler stay transport-agnostic)


def _varint_size(n: int) -> int:
    """Bytes of ``n`` as a protobuf varint."""
    size = 1
    while n >= 0x80:
        n >>= 7
        size += 1
    return size


def _node_delta(e: dict):
    return pb2().NodeDelta(
        node_json=json.dumps(e["node"]).encode(),
        pod_json=[json.dumps(pw).encode() for pw in e.get("pods", ())],
        gen=int(e.get("gen", 0)))


def _deltas_to_proto(payload: dict):
    p = pb2()
    req = p.ApplyDeltasRequest(full=bool(payload.get("full")))
    req.nodes.extend(_node_delta(e) for e in payload.get("nodes", ()))
    req.removed.extend(payload.get("removed", ()))
    for ns, labels in (payload.get("namespaces") or {}).items():
        req.namespaces[ns] = json.dumps(labels).encode()
    req.traceparent = payload.get("traceparent") or ""
    req.expect_epoch = payload.get("expectEpoch") or ""
    fields = req.DESCRIPTOR.fields_by_name
    if "inflight_batch_ids" in fields:
        # pipelined clients: holds from these batches survive owner-content
        # omission (a stale pb2 just drops them — legacy request/response)
        req.inflight_batch_ids.extend(payload.get("inflightBatchIds") or ())
    if "replicator" in fields:
        req.replicator = bool(payload.get("replicator"))
    if "full_nodes" in fields:
        req.full_nodes.extend(payload.get("fullNodes") or ())
    _stamp_session_proto(req, payload)
    return req


def _stamp_session_proto(req, payload: dict) -> None:
    """clientId/sessionGen onto a request proto (0 = not yet joined); a
    stale pb2 without the fields just drops them (legacy single-client)."""
    fields = req.DESCRIPTOR.fields_by_name
    if "client_id" in fields:
        req.client_id = payload.get("clientId") or ""
        req.session_gen = int(payload.get("sessionGen") or 0)


def _session_from_proto(req) -> dict:
    fields = req.DESCRIPTOR.fields_by_name
    if "client_id" not in fields:
        return {}
    out = {"clientId": req.client_id or None}
    if req.session_gen:
        out["sessionGen"] = int(req.session_gen)
    return out


def _deltas_from_proto(req) -> dict:
    out = {
        "full": req.full,
        "nodes": [{
            "node": json.loads(e.node_json),
            "pods": [json.loads(b) for b in e.pod_json],
            "gen": e.gen,
        } for e in req.nodes],
        "removed": list(req.removed),
        "namespaces": {ns: json.loads(b) for ns, b in req.namespaces.items()},
    }
    if req.traceparent:
        out["traceparent"] = req.traceparent
    if req.expect_epoch:
        out["expectEpoch"] = req.expect_epoch
    fields = req.DESCRIPTOR.fields_by_name
    if "inflight_batch_ids" in fields and req.inflight_batch_ids:
        out["inflightBatchIds"] = list(req.inflight_batch_ids)
    if "replicator" in fields and req.replicator:
        out["replicator"] = True
    if "full_nodes" in fields and req.full_nodes:
        out["fullNodes"] = list(req.full_nodes)
    out.update(_session_from_proto(req))
    return out


def _batch_to_proto(payload: dict):
    """Template-dedup encode: per pod, strip the only per-pod fields (name/
    uid) out of the wire dict; identical remainders share one table entry."""
    p = pb2()
    req = p.ScheduleBatchRequest()
    table: Dict[bytes, int] = {}
    for pw in payload.get("pods", ()):
        meta = dict(pw.get("meta") or {})
        name = meta.pop("name", "")
        uid = meta.pop("uid", "")
        namespace = meta.get("namespace", "default")
        tmpl = json.dumps(dict(pw, meta=meta), sort_keys=True).encode()
        idx = table.get(tmpl)
        if idx is None:
            idx = len(req.templates)
            table[tmpl] = idx
            req.templates.append(tmpl)
        req.pods.append(p.PodRef(template=idx, name=name,
                                 namespace=namespace, uid=uid))
    req.tie_seeds.extend(int(s) for s in payload.get("tieSeeds", ()))
    req.traceparent = payload.get("traceparent") or ""
    req.expect_epoch = payload.get("expectEpoch") or ""
    req.batch_id = payload.get("batchId") or ""
    from ..api import dra

    for c in payload.get("claims") or ():
        pc = req.claims.add()
        pc.pod = int(c.get("pod", 0))
        for key, op, kind, operand in c.get("selectors") or ():
            s = pc.selectors.add()
            s.key = str(key)
            s.op = int(op)
            s.kind = int(kind)
            if int(kind) == dra.KIND_INT:
                s.int_val = int(operand)
            else:
                s.str_val = str(operand)
        pc.allocated_nodes.extend(c.get("allocatedNodes") or ())
    _stamp_session_proto(req, payload)
    return req


def _batch_from_proto(req) -> dict:
    templates = [json.loads(t) for t in req.templates]
    pods = []
    for ref in req.pods:
        tmpl = templates[ref.template]
        meta = dict(tmpl.get("meta") or {})
        meta["name"] = ref.name
        meta["namespace"] = ref.namespace or meta.get("namespace", "default")
        if ref.uid:
            meta["uid"] = ref.uid
        pods.append(dict(tmpl, meta=meta))
    out = {"pods": pods}
    if req.tie_seeds:
        out["tieSeeds"] = list(req.tie_seeds)
    if req.traceparent:
        out["traceparent"] = req.traceparent
    if req.expect_epoch:
        out["expectEpoch"] = req.expect_epoch
    if req.batch_id:
        out["batchId"] = req.batch_id
    if req.claims:
        from ..api import dra

        out["claims"] = [{
            "pod": pc.pod,
            "selectors": [
                [s.key, s.op, s.kind,
                 s.int_val if s.kind == dra.KIND_INT else s.str_val]
                for s in pc.selectors],
            "allocatedNodes": list(pc.allocated_nodes),
        } for pc in req.claims]
    out.update(_session_from_proto(req))
    return out


def _results_to_proto(out: dict):
    p = pb2()
    resp = p.ScheduleBatchResponse()
    has_conflict = "conflict" in p.PodResult.DESCRIPTOR.fields_by_name
    for r in out.get("results", ()):
        pr = p.PodResult(node_name=r.get("nodeName") or "")
        if has_conflict and r.get("conflict"):
            pr.conflict = True
            pr.error = r.get("error") or ""
            resp.results.append(pr)
            continue
        if not pr.node_name:
            pr.unschedulable_plugins.extend(r.get("unschedulablePlugins") or ())
            pr.statuses_json = json.dumps(r.get("statuses") or {}).encode()
            hint = r.get("preempt")
            if hint:
                if hint.get("candidates") is None:
                    pr.preempt.truncated = True
                else:
                    pr.preempt.candidates.extend(hint["candidates"])
                pr.preempt.best = hint.get("best") or ""
        resp.results.append(pr)
    return resp


def _results_from_proto(resp) -> dict:
    results = []
    pod_result_fields = (
        resp.DESCRIPTOR.fields_by_name["results"].message_type.fields_by_name)
    has_conflict = "conflict" in pod_result_fields
    for pr in resp.results:
        if has_conflict and pr.conflict:
            results.append({"nodeName": None, "conflict": True,
                            "error": pr.error or ""})
            continue
        if pr.node_name:
            results.append({"nodeName": pr.node_name})
            continue
        r = {
            "nodeName": None,
            "unschedulablePlugins": list(pr.unschedulable_plugins),
            "statuses": json.loads(pr.statuses_json) if pr.statuses_json else {},
        }
        if pr.HasField("preempt"):
            r["preempt"] = {
                "candidates": (None if pr.preempt.truncated
                               else list(pr.preempt.candidates)),
                "best": pr.preempt.best or None,
            }
        results.append(r)
    return {"results": results}


def _device_time_to_proto(resp, out: dict) -> None:
    """Stamp the dispatch profiler's echoed deviceTime onto the response
    (no-op when the profiler was off or the vendored pb2 predates the
    field — the uniform stale-pb2 degradation rule)."""
    dt = out.get("deviceTime")
    if (not isinstance(dt, dict)
            or "device_time" not in resp.DESCRIPTOR.fields_by_name):
        return
    resp.device_time.dwell_ms = float(dt.get("dwellMs") or 0.0)
    resp.device_time.exec_ms = float(dt.get("execMs") or 0.0)
    resp.device_time.fetch_ms = float(dt.get("fetchMs") or 0.0)
    resp.device_time.device_ms = float(dt.get("deviceMs") or 0.0)


def _device_time_from_proto(resp) -> Optional[dict]:
    """The client half: the HTTP-shaped deviceTime dict, or None when the
    server didn't echo one (profiler off / older server or pb2)."""
    if ("device_time" not in resp.DESCRIPTOR.fields_by_name
            or not resp.HasField("device_time")):
        return None
    return {"dwellMs": resp.device_time.dwell_ms,
            "execMs": resp.device_time.exec_ms,
            "fetchMs": resp.device_time.fetch_ms,
            "deviceMs": resp.device_time.device_ms}


# ------------------------------------------------------------------ server


def serve_grpc(service, port: int = 0):
    """Bind a DeviceService to a localhost gRPC server; returns
    (server, port). Generic handlers stand in for generated service stubs."""
    import grpc
    from concurrent import futures

    p = pb2()

    def _abort_stale(ctx, exc):
        # FAILED_PRECONDITION carries the CURRENT epoch in the details so
        # the client can resync and re-stamp in one round trip (the HTTP
        # binding's 409 + staleEpoch body)
        ctx.abort(grpc.StatusCode.FAILED_PRECONDITION,
                  f"stale epoch; current={exc.epoch}")

    def _abort_conflict(ctx, exc):
        # ABORTED = the cross-client race / fenced-session verdict (the
        # HTTP binding's 409 + conflict body): the state base is fine, a
        # resync cannot help — rejoin/requeue, never retry the transport
        ctx.abort(grpc.StatusCode.ABORTED, f"commit conflict: {exc}")

    def apply_deltas(request, ctx):
        try:
            out = service.apply_deltas(_deltas_from_proto(request))
        except StaleEpochError as exc:
            _abort_stale(ctx, exc)
        except ConflictError as exc:
            _abort_conflict(ctx, exc)
        resp = p.ApplyDeltasResponse(nodes=int(out.get("nodes", 0)),
                                     epoch=out.get("epoch", ""),
                                     delta_seq=int(out.get("deltaSeq", 0)))
        if "session_gen" in p.ApplyDeltasResponse.DESCRIPTOR.fields_by_name:
            resp.session_gen = int(out.get("sessionGen") or 0)
        return resp

    def schedule_batch(request, ctx):
        try:
            out = service.schedule_batch(_batch_from_proto(request))
        except StaleEpochError as exc:
            _abort_stale(ctx, exc)
        except ConflictError as exc:
            _abort_conflict(ctx, exc)
        resp = _results_to_proto(out)
        resp.epoch = out.get("epoch", "")
        resp.delta_seq = int(out.get("deltaSeq", 0))
        fields = p.ScheduleBatchResponse.DESCRIPTOR.fields_by_name
        if "session_gen" in fields:
            resp.session_gen = int(out.get("sessionGen") or 0)
        if "batch_id" in fields:
            resp.batch_id = out.get("batchId") or ""
        _device_time_to_proto(resp, out)
        return resp

    def heartbeat(request, ctx):
        req_dict = _session_from_proto(request)
        if ("replicator" in request.DESCRIPTOR.fields_by_name
                and request.replicator):
            req_dict["replicator"] = True
        try:
            out = service.heartbeat(req_dict)
        except ConflictError as exc:
            _abort_conflict(ctx, exc)
        resp = p.HeartbeatResponse(
            epoch=out.get("epoch", ""),
            session_gen=int(out.get("sessionGen") or 0),
            sessions=int(out.get("sessions") or 0),
            lease_ttl_s=float(out.get("leaseTtlS") or 0.0),
            delta_seq=int(out.get("deltaSeq") or 0))
        resp.fenced.extend(out.get("fenced") or ())
        return resp

    def sessions_dump(request, ctx):
        out = service.sessions_dump({})
        return p.SessionsResponse(sessions_json=json.dumps(out).encode())

    def health(request, ctx):
        out = service.health({})
        return p.HealthResponse(status=out.get("status", "serving"),
                                epoch=out.get("epoch", ""),
                                delta_seq=int(out.get("deltaSeq", 0)),
                                nodes=int(out.get("nodes", 0)))

    rpc_handlers = {
        "ApplyDeltas": grpc.unary_unary_rpc_method_handler(
            apply_deltas,
            request_deserializer=p.ApplyDeltasRequest.FromString,
            response_serializer=p.ApplyDeltasResponse.SerializeToString),
        "ScheduleBatch": grpc.unary_unary_rpc_method_handler(
            schedule_batch,
            request_deserializer=p.ScheduleBatchRequest.FromString,
            response_serializer=p.ScheduleBatchResponse.SerializeToString),
        "Health": grpc.unary_unary_rpc_method_handler(
            health,
            request_deserializer=p.HealthRequest.FromString,
            response_serializer=p.HealthResponse.SerializeToString),
    }
    if hasattr(p, "HeartbeatRequest"):  # stale pb2: no session verbs
        rpc_handlers["Heartbeat"] = grpc.unary_unary_rpc_method_handler(
            heartbeat,
            request_deserializer=p.HeartbeatRequest.FromString,
            response_serializer=p.HeartbeatResponse.SerializeToString)
        rpc_handlers["Sessions"] = grpc.unary_unary_rpc_method_handler(
            sessions_dump,
            request_deserializer=p.SessionsRequest.FromString,
            response_serializer=p.SessionsResponse.SerializeToString)
    handlers = grpc.method_handlers_generic_handler(SERVICE, rpc_handlers)
    server = grpc.server(futures.ThreadPoolExecutor(max_workers=8))
    server.add_generic_rpc_handlers((handlers,))
    bound = server.add_insecure_port(f"127.0.0.1:{port}")
    server.start()
    return server, bound


class GrpcClient:
    """Drop-in for service.WireClient over gRPC: same dict payloads, same
    error taxonomy and retry policy. gRPC status codes map onto the
    taxonomy: UNAVAILABLE/DEADLINE_EXCEEDED and RESOURCE_EXHAUSTED are
    transient, except a message-size rejection (the same bytes are refused
    again), FAILED_PRECONDITION is the stale-epoch signal, everything else
    is permanent (a deterministic server exception re-raises on re-send)."""

    _STALE_PREFIX = "stale epoch; current="
    # grpc core's words for a message over a receive or send limit
    _SIZE_REJECTION = "message larger than max"

    def __init__(self, endpoint: str, read_timeout: float = 60.0,
                 retry: Optional[RetryPolicy] = None, fault_plan=None,
                 on_request_bytes=None):
        import grpc

        p = pb2()
        self.on_request_bytes = on_request_bytes  # see backend/wire.py
        self.read_timeout = read_timeout
        self.retry = retry if retry is not None else RetryPolicy()
        self.fault_plan = fault_plan
        self._grpc = grpc
        self._channel = grpc.insecure_channel(endpoint)
        self._apply = self._channel.unary_unary(
            f"/{SERVICE}/ApplyDeltas",
            request_serializer=p.ApplyDeltasRequest.SerializeToString,
            response_deserializer=p.ApplyDeltasResponse.FromString)
        self._schedule = self._channel.unary_unary(
            f"/{SERVICE}/ScheduleBatch",
            request_serializer=p.ScheduleBatchRequest.SerializeToString,
            response_deserializer=p.ScheduleBatchResponse.FromString)
        # feature-detect against the COMPILED schema: a stale pb2 built
        # from an older proto must degrade (claim pods fall back to the
        # local sequential path; the half-open probe pushes a full batch)
        # rather than crash mid-request
        self.supports_dra = (
            "claims" in p.ScheduleBatchRequest.DESCRIPTOR.fields_by_name)
        self.supports_health = hasattr(p, "HealthRequest")
        self._health = (self._channel.unary_unary(
            f"/{SERVICE}/Health",
            request_serializer=p.HealthRequest.SerializeToString,
            response_deserializer=p.HealthResponse.FromString)
            if self.supports_health else None)
        self.supports_sessions = (
            hasattr(p, "HeartbeatRequest")
            and "client_id" in p.ApplyDeltasRequest.DESCRIPTOR.fields_by_name)
        self._heartbeat = (self._channel.unary_unary(
            f"/{SERVICE}/Heartbeat",
            request_serializer=p.HeartbeatRequest.SerializeToString,
            response_deserializer=p.HeartbeatResponse.FromString)
            if self.supports_sessions else None)
        self._sessions = (self._channel.unary_unary(
            f"/{SERVICE}/Sessions",
            request_serializer=p.SessionsRequest.SerializeToString,
            response_deserializer=p.SessionsResponse.FromString)
            if self.supports_sessions else None)

    def _call(self, op: str, stub, request):
        grpc = self._grpc

        def attempt():
            raise_injected_fault(self.fault_plan, op, self.read_timeout)
            try:
                return stub(request, timeout=self.read_timeout)
            except grpc.RpcError as e:
                code = e.code()
                details = e.details() or ""
                if code == grpc.StatusCode.FAILED_PRECONDITION:
                    epoch = ""
                    if self._STALE_PREFIX in details:
                        epoch = details.split(self._STALE_PREFIX, 1)[1].strip()
                    raise StaleEpochError(epoch, details) from e
                if code == grpc.StatusCode.ABORTED:
                    # the typed conflict verdict: fenced session or a
                    # cross-client pod/capacity race — rejoin/requeue
                    raise ConflictError(details or "commit conflict") from e
                if (code == grpc.StatusCode.RESOURCE_EXHAUSTED
                        and self._SIZE_REJECTION in details):
                    raise PermanentDeviceError(
                        f"device service {code.name}: {details}") from e
                if code in (grpc.StatusCode.UNAVAILABLE,
                            grpc.StatusCode.DEADLINE_EXCEEDED,
                            grpc.StatusCode.RESOURCE_EXHAUSTED):
                    raise TransientDeviceError(
                        f"device service {code.name}: {details}") from e
                raise PermanentDeviceError(
                    f"device service {code.name}: {details}") from e

        return self.retry.run(op, attempt)

    @staticmethod
    def _session_gen_out(resp, out: dict) -> dict:
        if ("session_gen" in resp.DESCRIPTOR.fields_by_name
                and resp.session_gen):
            out["sessionGen"] = int(resp.session_gen)
        return out

    # the encoding backend/wire.py cuts ApplyDeltas parts with
    @staticmethod
    def _encode_node_delta(entry: dict):
        delta = _node_delta(entry)
        n = delta.ByteSize()
        # its share of a request: the field's tag, length prefix and body
        return delta, 1 + _varint_size(n) + n

    @staticmethod
    def _deltas_request(head: dict, encoded):
        req = _deltas_to_proto(head)
        req.nodes.extend(encoded)
        return req, req.ByteSize()

    def _send_deltas(self, req) -> dict:
        resp = self._call("apply_deltas", self._apply, req)
        out = {"nodes": resp.nodes}
        if resp.epoch:
            out["epoch"] = resp.epoch
            out["deltaSeq"] = resp.delta_seq
        return self._session_gen_out(resp, out)

    @staticmethod
    def _batch_request(payload: dict):
        req = _batch_to_proto(payload)
        return req, req.ByteSize()

    def apply_deltas(self, payload: dict) -> dict:
        return wire.apply_deltas(self, payload)

    def schedule_batch(self, payload: dict) -> dict:
        return wire.schedule_batch(self, payload)

    def _send_batch(self, req) -> dict:
        resp = self._call("schedule_batch", self._schedule, req)
        out = _results_from_proto(resp)
        if resp.epoch:
            out["epoch"] = resp.epoch
            out["deltaSeq"] = resp.delta_seq
        if ("batch_id" in resp.DESCRIPTOR.fields_by_name and resp.batch_id):
            # echoed idempotency key: the pipelined reply router matches
            # out-of-order replies to their in-flight batches by this id
            out["batchId"] = resp.batch_id
        dt = _device_time_from_proto(resp)
        if dt is not None:
            out["deviceTime"] = dt
        return self._session_gen_out(resp, out)

    def heartbeat(self, payload: dict) -> dict:
        """Lease renewal + takeover signal (HA session verb)."""
        if self._heartbeat is None:
            raise PermanentDeviceError("Heartbeat RPC unsupported by this pb2")
        p = pb2()
        req = p.HeartbeatRequest(
            client_id=payload.get("clientId") or "",
            session_gen=int(payload.get("sessionGen") or 0))
        if ("replicator" in req.DESCRIPTOR.fields_by_name
                and payload.get("replicator")):
            req.replicator = True
        resp = self._call("heartbeat", self._heartbeat, req)
        return {"epoch": resp.epoch, "sessionGen": int(resp.session_gen),
                "sessions": int(resp.sessions),
                "fenced": list(resp.fenced),
                "leaseTtlS": float(resp.lease_ttl_s),
                "deltaSeq": int(resp.delta_seq)}

    def sessions_dump(self) -> dict:
        """Session-table introspection (/debug/sessions passthrough)."""
        if self._sessions is None:
            raise PermanentDeviceError("Sessions RPC unsupported by this pb2")
        resp = self._call("sessions", self._sessions, pb2().SessionsRequest())
        return json.loads(resp.sessions_json or b"{}")

    def health(self) -> dict:
        """The cheap identity/liveness verb (half-open circuit probe)."""
        if self._health is None:
            raise PermanentDeviceError("Health RPC unsupported by this pb2")
        resp = self._call("health", self._health, pb2().HealthRequest())
        return {"status": resp.status, "epoch": resp.epoch,
                "deltaSeq": resp.delta_seq, "nodes": resp.nodes}

    def close(self) -> None:
        self._channel.close()
