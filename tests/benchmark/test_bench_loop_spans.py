"""The readers of the scheduling loop's spans and the runtime spans
(benchmark/metrics/*.py over benchmark/loop_spans.py) on synthetic spans:
the per-batch division, the parent filter, and the cases with none."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import loop_spans  # noqa: E402
from benchmark import run as bench_run  # noqa: E402

MS = 1_000_000


class _S:
    def __init__(self, name, span_id, parent_id, start_ms, end_ms):
        self.name = name
        self.span_id = span_id
        self.parent_id = parent_id
        self.start = start_ms * MS
        self.end = end_ms * MS
        self.attributes = {}


def _window():
    """Two cycles of 100 ms. Under them: drains of 30 and 10 ms, snapshots
    of 4 and 6 ms, a 50 ms GC pause and a 20 ms compile. Outside them: a
    housekeeping sweep of 8 ms with a drain of 5 ms inside, a 7 ms snapshot
    on another thread, a 2 ms GC pause and a 3 ms compile."""
    return [
        _S("scheduling.cycle", "c1", None, 0, 100),
        _S("scheduling.drain", "d1", "c1", 1, 31),
        _S("cache.snapshot", "s1", "c1", 31, 35),
        _S("runtime.gc", "g1", "c1", 40, 90),
        _S("scheduling.cycle", "c2", None, 200, 300),
        _S("scheduling.drain", "d2", "c2", 201, 211),
        _S("cache.snapshot", "s2", "c2", 211, 217),
        _S("xla.compile", "x1", "c2", 220, 240),
        _S("scheduling.housekeeping", "h1", None, 150, 158),
        _S("scheduling.drain", "d3", "h1", 151, 156),
        _S("cache.snapshot", "s3", "w", 160, 167),
        _S("runtime.gc", "g2", "w", 170, 172),
        _S("xla.compile", "x2", None, 180, 183),
    ]


def _without(*names):
    return [s for s in _window() if s.name not in names]


# (metric, the spans, whether the program records runtime spans, reading)
CASES = [
    # only drains under a cycle: (30 + 10) / 2
    ("drain_wait_ms.backlog", _window(), True, 20.0),
    # drains only outside a cycle: recorded, none under one
    ("drain_wait_ms.backlog",
     [s for s in _window() if s.span_id not in ("d1", "d2")], True, 0.0),
    ("drain_wait_ms.backlog", _without("scheduling.drain"), True, None),
    ("drain_wait_ms.backlog", _without("scheduling.cycle"), True, None),
    # the worker thread's snapshot is left out: (4 + 6) / 2
    ("snapshot_ms.backlog", _window(), True, 5.0),
    ("snapshot_ms.backlog", _without("cache.snapshot"), True, None),
    ("housekeeping_ms.backlog", _window(), True, 4.0),
    ("housekeeping_ms.backlog", _without("scheduling.housekeeping"), True,
     None),
    # GC on any thread: (50 + 2) / 2
    ("gc_pause_ms.backlog", _window(), True, 26.0),
    ("gc_pause_ms.backlog", _without("runtime.gc"), True, 0.0),
    ("gc_pause_ms.backlog", _without("runtime.gc"), False, None),
    ("gc_pause_max_ms.steady", _window(), True, 50.0),
    ("gc_pause_max_ms.steady", _without("runtime.gc"), True, 0.0),
    ("gc_pause_max_ms.steady", _without("runtime.gc"), False, None),
    ("compile_ms.steady", _window(), True, 23.0),
    ("compile_ms.steady", _without("xla.compile"), True, 0.0),
    ("compile_ms.steady", _without("xla.compile"), False, None),
]


@pytest.mark.parametrize("metric,spans,recorded,want", CASES)
def test_loop_span_readers(metric, spans, recorded, want, monkeypatch):
    monkeypatch.setattr(loop_spans, "runtime_spans_recorded",
                        lambda: recorded)
    got = bench_run.load_reader(metric)({"spans": spans})
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want)


def test_the_program_records_runtime_spans():
    assert loop_spans.runtime_spans_recorded()
