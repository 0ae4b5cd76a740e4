"""The garbage collector's schedule once the program is warm.

CPython's collector walks every tracked object of a generation on each of
its collections, and stops every thread while it does. After warm-up the
heap holds what lives as long as the process: the code, JAX's caches, the
cluster's nodes, NodeInfos and running pods. A full collection walks all of
it, and in CPython 3.12 a full collection runs once the objects promoted
since the last one pass a quarter of the heap that survived it. So each
pause grows with the warm heap, their number falls with it, and their total
is set by the promotion rate: the batch-scoped temporaries that are still
alive when the young generation's default threshold of 700 net allocations
trips in the middle of a batch.

``settle_warm_heap`` changes that schedule once, at the end of the
program's warm-up, in this order:

1. ``gc.collect()``: nothing that is garbage now is frozen, and the
   collector's count of long-lived objects is left at the warm heap's size,
   so the next full collection is deferred rather than brought forward.
2. ``gc.freeze()``: the warm heap moves to the permanent generation, which
   no later collection walks.
3. ``gc.set_threshold(YOUNG_THRESHOLD, MIDDLE_THRESHOLD, ...)``: the young
   generation outlives a batch's burst of allocations, so a batch's
   temporaries die by refcount before any collection sees them.

The constants come from a CPU sweep at SchedulingBasic/5000Nodes (PERF.md,
section 6). The tracked objects that one 128-pod scheduling cycle holds
above its start peak at 2,568 in the median cycle and 4,111 in the largest,
and the whole loop, the API clients' pod creations included, keeps about
540 a cycle. A young threshold of 20,000 lies well above one batch's burst, and
no gen-2 collection ran in a 20 s window. Of the young thresholds swept
(10,000, 20,000, 50,000, 100,000), 20,000 gave the shortest longest pause
at a steady 800 pods/s. A higher one collects less often but walks a
longer young list each time: the objects of older generations that die
also count it down. Lowering the middle threshold to 5 brought a gen-1
collection into that window, so it stays at the default, as gen-2 does.

A frozen object that later dies in a reference cycle is never reclaimed, so
what the program frees after warm-up has to die by refcount; a running pod
frozen here does (tests/test_heap_settle.py).
"""

from __future__ import annotations

import gc
import threading
from typing import Optional, Tuple

from . import tracing

YOUNG_THRESHOLD = 20_000
MIDDLE_THRESHOLD = 10

_lock = threading.Lock()
# (frozen objects, the thresholds before) once settled, else None
_settled: Optional[Tuple[int, Tuple[int, int, int]]] = None


def settle_warm_heap(metrics=None) -> int:
    """Collect, freeze and raise the young threshold, once per process;
    later calls change nothing. Returns the number of objects frozen and
    sets ``metrics.gc_frozen_objects`` (a SchedulerMetrics) to it."""
    global _settled
    with _lock:
        if _settled is None:
            with tracing.span("runtime.gc_freeze",
                              threshold=YOUNG_THRESHOLD) as s:
                gc.collect()
                gc.freeze()
                frozen = gc.get_freeze_count()
                before = gc.get_threshold()
                gc.set_threshold(YOUNG_THRESHOLD, MIDDLE_THRESHOLD,
                                 before[2])
                if s is not None:
                    s.attributes["frozen"] = frozen
            _settled = (frozen, before)
        frozen = _settled[0]
    if metrics is not None:
        metrics.gc_frozen_objects.set(value=float(frozen))
    return frozen


def restore() -> None:
    """Undo ``settle_warm_heap`` (tests only): unfreeze and put the old
    thresholds back, so the next settle acts again."""
    global _settled
    with _lock:
        if _settled is None:
            return
        gc.unfreeze()
        gc.set_threshold(*_settled[1])
        _settled = None


def settled() -> bool:
    """Whether the schedule is in force (settled and not restored)."""
    return _settled is not None
