"""The client half of the wire hop, shared by the HTTP (``service.WireClient``)
and gRPC (``grpc_service.GrpcClient``) clients: bounded ApplyDeltas parts,
the ``wire.*`` client spans, and the request-bytes hook.

Every ApplyDeltas request is cut into parts of at most
``MAX_DELTA_PART_BYTES`` serialized bytes. A payload that fits goes as one
part, unchanged. A larger one (the full sync of a big cluster, or a push
after heavy churn) goes as several, in order, each acknowledged before the
next is sent:

  * the first part carries everything but node entries that do not fit:
    ``full``, ``removed``, ``namespaces`` and ``quotaTable``. For a full sync
    it also carries ``fullNodes``, the name of every node in the set, so the
    service's ghost sweep runs once, over the whole set, and keeps the nodes
    the later parts bring;
  * the later parts carry node entries only, stamped with the epoch and
    session generation the previous part's reply named.

The call returns the last part's reply, or raises the first part's error:
the caller commits its bookkeeping only when every part was acknowledged.

A transport client supplies the encoding (duck-typed):

  ``_encode_node_delta(entry) -> (encoded, nbytes)``: one node entry and
  its share of a request's size;
  ``_deltas_request(head, encoded) -> (request, nbytes)``: a request from
  the payload's other fields and encoded entries, and its serialized size;
  ``_send_deltas(request)`` / ``_batch_request(payload)`` /
  ``_send_batch(request)``: the calls themselves, with the client's retry
  policy and error taxonomy;
  ``on_request_bytes``: None, or ``fn(op, nbytes)`` called once per request.
"""

from __future__ import annotations

from typing import List

from ..utils import tracing
from .errors import PermanentDeviceError

# The largest serialized ApplyDeltas request a client sends. A gRPC receiver
# refuses a message over 4 MiB unless it raised its own limit, and neither
# a scheduler plugin on grpc-go's defaults nor serve_grpc raises it. A
# quarter of that keeps each part well clear of the limit and keeps one
# part's decode on the service short (a full sync of 5000 nodes takes five).
MAX_DELTA_PART_BYTES = 1 << 20

# bytes kept free in every part for stamps that change from part to part:
# the part's own traceparent, the epoch and session generation the previous
# reply named
_STAMP_ROOM = 256

# payload fields only the first part carries
_FIRST_PART_ONLY = ("full", "fullNodes", "removed", "namespaces", "quotaTable")


def _node_name(entry: dict) -> str:
    return entry["node"]["meta"]["name"]


def _note_bytes(client, op: str, span, nbytes: int) -> None:
    if span is not None:
        span.attributes["bytes"] = nbytes
    hook = getattr(client, "on_request_bytes", None)
    if hook is not None:
        hook(op, nbytes)


def delta_parts(client, payload: dict) -> List[tuple]:
    """``[(head, encoded_entries), ...]``: the payload cut into parts whose
    requests stay under MAX_DELTA_PART_BYTES (the later parts' heads are
    re-stamped from each reply as they are sent)."""
    entries = list(payload.get("nodes") or ())
    encoded = [client._encode_node_delta(e) for e in entries]
    head = {k: v for k, v in payload.items() if k != "nodes"}
    head_bytes = client._deltas_request(head, ())[1]
    if head_bytes + sum(n for _, n in encoded) + _STAMP_ROOM \
            <= MAX_DELTA_PART_BYTES:
        return [(head, [enc for enc, _ in encoded])]
    if head.get("full"):
        head["fullNodes"] = [_node_name(e) for e in entries]
        head_bytes = client._deltas_request(head, ())[1]
    rest = {k: v for k, v in head.items() if k not in _FIRST_PART_ONLY}
    rest_bytes = client._deltas_request(rest, ())[1]
    parts = [(head, [])]
    room = MAX_DELTA_PART_BYTES - _STAMP_ROOM - head_bytes
    for entry, (enc, n) in zip(entries, encoded):
        # a new part when this entry does not fit, unless the part is an
        # empty later one: the first part may go with its head alone
        if n > room and (parts[-1][1] or len(parts) == 1):
            parts.append((rest, []))
            room = MAX_DELTA_PART_BYTES - _STAMP_ROOM - rest_bytes
        if n > room:
            raise PermanentDeviceError(
                f"node {_node_name(entry)!r} alone needs {n} bytes, more "
                f"than an ApplyDeltas part of {MAX_DELTA_PART_BYTES} holds")
        parts[-1][1].append(enc)
        room -= n
    return parts


def apply_deltas(client, payload: dict) -> dict:
    """Send one ApplyDeltas payload in bounded parts (module docstring);
    one ``wire.apply_deltas`` span per part, whose traceparent the part
    carries, so the service's ``device.apply_deltas`` is its child."""
    parts = delta_parts(client, payload)
    stamps: dict = {}
    out: dict = {}
    for i, (head, encoded) in enumerate(parts):
        if i:
            head = dict(head, **stamps)
        with tracing.span_from_remote(
                payload.get("traceparent"), "wire.apply_deltas",
                nodes=len(encoded), full=bool(head.get("full")),
                part=i + 1, parts=len(parts)) as span:
            tp = tracing.format_traceparent()
            if tp:
                head = dict(head, traceparent=tp)
            request, nbytes = client._deltas_request(head, encoded)
            if nbytes > MAX_DELTA_PART_BYTES:
                raise PermanentDeviceError(
                    f"ApplyDeltas part of {nbytes} bytes is over the "
                    f"{MAX_DELTA_PART_BYTES}-byte bound")
            _note_bytes(client, "apply_deltas", span, nbytes)
            out = client._send_deltas(request)
        if out.get("epoch"):
            stamps["expectEpoch"] = out["epoch"]
        if out.get("sessionGen") is not None:
            stamps["sessionGen"] = out["sessionGen"]
    return out


def schedule_batch(client, payload: dict) -> dict:
    """Send one ScheduleBatch payload inside a ``wire.schedule_batch`` span on
    the calling thread (a pipeline lane, or the scheduling thread when the
    transport is not pipelined), parented under the payload's traceparent;
    the request carries the span's own, so the service's
    ``device.schedule_batch`` is its child."""
    with tracing.span_from_remote(
            payload.get("traceparent"), "wire.schedule_batch",
            pods=len(payload.get("pods") or ())) as span:
        tp = tracing.format_traceparent()
        if tp:
            payload = dict(payload, traceparent=tp)
        request, nbytes = client._batch_request(payload)
        _note_bytes(client, "schedule_batch", span, nbytes)
        return client._send_batch(request)
