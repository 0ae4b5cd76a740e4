# Generated by tools/gen_pb2.py from native/ktpu_device.proto — DO NOT EDIT.
#
# protoc-free equivalent of `protoc --python_out` (neither protoc nor
# grpcio-tools is in the image): the serialized FileDescriptorProto below
# feeds the descriptor pool and the builder materializes the message
# classes, exactly as protoc-generated modules do.  After editing the
# .proto, regenerate with:
#
#     python tools/gen_pb2.py
#
# backend/grpc_service.pb2() only uses this module while PROTO_SHA256
# matches the current .proto source.
"""Vendored protobuf messages for the batched device service wire format."""

from google.protobuf import descriptor_pool as _descriptor_pool
from google.protobuf.internal import builder as _builder

PROTO_SHA256 = "b787a56e05a6209ebf024f6a1d3f71c723fce3d9cb5404a94058ae625b414ee8"

DESCRIPTOR = _descriptor_pool.Default().AddSerializedFile(
        b'\n\x11ktpu_device.proto\x12\x07ktpu.v1"=\n\tNodeDelta\x12\x11\n\tnod'
    b'e_json\x18\x01 \x01(\x0c\x12\x10\n\x08pod_json\x18\x02 \x03(\x0c\x12\x0b\n\x03gen\x18\x03 \x01(\x03"\xe1\x02\n\x12'
    b'ApplyDeltasRequest\x12\x0c\n\x04full\x18\x01 \x01(\x08\x12!\n\x05nodes\x18\x02 \x03(\x0b2'
    b'\x12.ktpu.v1.NodeDelta\x12\x0f\n\x07removed\x18\x03 \x03(\t\x12?\n\nnamespac'
    b'es\x18\x04 \x03(\x0b2+.ktpu.v1.ApplyDeltasRequest.Namespaces'
    b'Entry\x12\x13\n\x0btraceparent\x18\x05 \x01(\t\x12\x14\n\x0cexpect_epoch\x18\x06 \x01(\t'
    b'\x12\x11\n\tclient_id\x18\x07 \x01(\t\x12\x13\n\x0bsession_gen\x18\x08 \x01(\x03\x12\x1a\n\x12infl'
    b'ight_batch_ids\x18\t \x03(\t\x12\x12\n\nreplicator\x18\n \x01(\x08\x12\x12\n\nfull'
    b'_nodes\x18\x0b \x03(\t\x1a1\n\x0fNamespacesEntry\x12\x0b\n\x03key\x18\x01 \x01(\t\x12\r\n\x05'
    b'value\x18\x02 \x01(\x0c:\x028\x01"[\n\x13ApplyDeltasResponse\x12\r\n\x05nodes\x18'
    b'\x01 \x01(\x05\x12\r\n\x05epoch\x18\x02 \x01(\t\x12\x11\n\tdelta_seq\x18\x03 \x01(\x03\x12\x13\n\x0bsessi'
    b'on_gen\x18\x04 \x01(\x03"H\n\x06PodRef\x12\x10\n\x08template\x18\x01 \x01(\x05\x12\x0c\n\x04name'
    b'\x18\x02 \x01(\t\x12\x11\n\tnamespace\x18\x03 \x01(\t\x12\x0b\n\x03uid\x18\x04 \x01(\t"X\n\rClaimS'
    b'elector\x12\x0b\n\x03key\x18\x01 \x01(\t\x12\n\n\x02op\x18\x02 \x01(\x05\x12\x0c\n\x04kind\x18\x03 \x01(\x05\x12\x0f'
    b'\n\x07int_val\x18\x04 \x01(\x03\x12\x0f\n\x07str_val\x18\x05 \x01(\t"\\\n\tPodClaims\x12\x0b\n'
    b'\x03pod\x18\x01 \x01(\x05\x12)\n\tselectors\x18\x02 \x03(\x0b2\x16.ktpu.v1.ClaimSel'
    b'ector\x12\x17\n\x0fallocated_nodes\x18\x03 \x03(\t"\xe4\x01\n\x14ScheduleBatch'
    b'Request\x12\x11\n\ttemplates\x18\x01 \x03(\x0c\x12\x1d\n\x04pods\x18\x02 \x03(\x0b2\x0f.ktpu.'
    b'v1.PodRef\x12\x11\n\ttie_seeds\x18\x03 \x03(\r\x12\x13\n\x0btraceparent\x18\x04 \x01('
    b'\t\x12\x14\n\x0cexpect_epoch\x18\x05 \x01(\t\x12\x10\n\x08batch_id\x18\x06 \x01(\t\x12"\n\x06cla'
    b'ims\x18\x07 \x03(\x0b2\x12.ktpu.v1.PodClaims\x12\x11\n\tclient_id\x18\x08 \x01(\t'
    b'\x12\x13\n\x0bsession_gen\x18\t \x01(\x03"\x0f\n\rHealthRequest"Q\n\x0eHealth'
    b'Response\x12\x0e\n\x06status\x18\x01 \x01(\t\x12\r\n\x05epoch\x18\x02 \x01(\t\x12\x11\n\tdelta'
    b'_seq\x18\x03 \x01(\x03\x12\r\n\x05nodes\x18\x04 \x01(\x05"B\n\x0bPreemptHint\x12\x12\n\ncand'
    b'idates\x18\x01 \x03(\t\x12\x0c\n\x04best\x18\x02 \x01(\t\x12\x11\n\ttruncated\x18\x03 \x01(\x08"\x9c\x01'
    b'\n\tPodResult\x12\x11\n\tnode_name\x18\x01 \x01(\t\x12\x1d\n\x15unschedulable_'
    b'plugins\x18\x02 \x03(\t\x12\x15\n\rstatuses_json\x18\x03 \x01(\x0c\x12%\n\x07preempt\x18'
    b'\x04 \x01(\x0b2\x14.ktpu.v1.PreemptHint\x12\x10\n\x08conflict\x18\x05 \x01(\x08\x12\r\n'
    b'\x05error\x18\x06 \x01(\t"T\n\nDeviceTime\x12\x10\n\x08dwell_ms\x18\x01 \x01(\x01\x12\x0f\n\x07'
    b'exec_ms\x18\x02 \x01(\x01\x12\x10\n\x08fetch_ms\x18\x03 \x01(\x01\x12\x11\n\tdevice_ms\x18\x04 \x01'
    b'(\x01"\xaf\x01\n\x15ScheduleBatchResponse\x12#\n\x07results\x18\x01 \x03(\x0b2\x12.'
    b'ktpu.v1.PodResult\x12\r\n\x05epoch\x18\x02 \x01(\t\x12\x11\n\tdelta_seq\x18\x03 '
    b'\x01(\x03\x12\x13\n\x0bsession_gen\x18\x04 \x01(\x03\x12\x10\n\x08batch_id\x18\x05 \x01(\t\x12(\n\x0bde'
    b'vice_time\x18\x06 \x01(\x0b2\x13.ktpu.v1.DeviceTime"N\n\x10Heartbea'
    b'tRequest\x12\x11\n\tclient_id\x18\x01 \x01(\t\x12\x13\n\x0bsession_gen\x18\x02 \x01(\x03'
    b'\x12\x12\n\nreplicator\x18\x03 \x01(\x08"\x81\x01\n\x11HeartbeatResponse\x12\r\n\x05ep'
    b'och\x18\x01 \x01(\t\x12\x13\n\x0bsession_gen\x18\x02 \x01(\x03\x12\x10\n\x08sessions\x18\x03 \x01(\x05'
    b'\x12\x0e\n\x06fenced\x18\x04 \x03(\t\x12\x13\n\x0blease_ttl_s\x18\x05 \x01(\x01\x12\x11\n\tdelta_s'
    b'eq\x18\x06 \x01(\x03"\x11\n\x0fSessionsRequest")\n\x10SessionsResponse\x12'
    b'\x15\n\rsessions_json\x18\x01 \x01(\x0cb\x06proto3'
)

_globals = globals()
_builder.BuildMessageAndEnumDescriptors(DESCRIPTOR, _globals)
_builder.BuildTopDescriptorsAndMessages(DESCRIPTOR, "ktpu_device_pb2",
                                        _globals)
