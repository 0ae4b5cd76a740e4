"""The readers of the wire hop's client spans (benchmark/metrics/
delta_push_ms.steady.py and wire_kib.steady.py) on synthetic spans: the
per-batch division, the parent filter, and a window with no wire span."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402

MS = 1_000_000


class _S:
    def __init__(self, name, span_id, parent_id, start_ms, end_ms, **attrs):
        self.name = name
        self.span_id = span_id
        self.parent_id = parent_id
        self.start = start_ms * MS
        self.end = end_ms * MS
        self.attributes = attrs


def _window():
    """Two cycles. The first pushes its deltas in two parts (6 and 4 ms,
    3000 and 1000 bytes), the second in one (2 ms, 500 bytes); the batch
    calls ride a lane (2048 and 1024 bytes). A replicator's push outside
    any cycle (8 ms, 700 bytes) and the server's spans are left out of the
    push time; the bytes count every wire request."""
    return [
        _S("scheduling.cycle", "c1", None, 0, 20, batch=64),
        _S("wire.apply_deltas", "a1", "c1", 1, 7, bytes=3000, part=1, parts=2),
        _S("device.apply_deltas", "d1", "a1", 2, 6),
        _S("wire.apply_deltas", "a2", "c1", 7, 11, bytes=1000, part=2, parts=2),
        _S("wire.schedule_batch", "b1", "c1", 12, 30, bytes=2048, pods=64),
        _S("device.schedule_batch", "e1", "b1", 13, 29),
        _S("scheduling.cycle", "c2", None, 40, 50, batch=32),
        _S("wire.apply_deltas", "a3", "c2", 41, 43, bytes=500, part=1, parts=1),
        _S("wire.schedule_batch", "b2", "c2", 44, 60, bytes=1024, pods=32),
        _S("wire.apply_deltas", "r1", None, 70, 78, bytes=700, part=1, parts=1),
    ]


def _without(*names):
    return [s for s in _window() if s.name not in names]


CASES = [
    ("delta_push_ms.steady", _window(), (6 + 4 + 2) / 2),
    ("delta_push_ms.steady", _without("wire.apply_deltas"), None),
    ("delta_push_ms.steady", _without("scheduling.cycle"), None),
    ("wire_kib.steady", _window(),
     (3000 + 1000 + 2048 + 500 + 1024 + 700) / 1024 / 2),
    ("wire_kib.steady", _without("wire.schedule_batch"),
     (3000 + 1000 + 500 + 700) / 1024 / 2),
    ("wire_kib.steady", _without("wire.apply_deltas", "wire.schedule_batch"),
     None),
    ("wire_kib.steady", _without("scheduling.cycle"), None),
]


@pytest.mark.parametrize("metric,spans,want", CASES)
def test_wire_span_readers(metric, spans, want):
    got = bench_run.load_reader(metric)({"spans": spans})
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want)


def test_transport_reader_reads_the_rpc_wait():
    """transport_ms.steady: each timed ScheduleBatch call minus the one
    server span inside it; no timed call, no reading."""
    read = bench_run.load_reader("transport_ms.steady")
    spans = _window()
    rpc = [(12 * MS, 30 * MS), (44 * MS, 60 * MS)]
    # the second call holds no server span: only the first is read
    assert read({"spans": spans, "rpc": rpc}) == pytest.approx(18 - 16)
    assert read({"spans": spans, "rpc": []}) is None
