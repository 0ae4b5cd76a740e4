"""Reductions over the scheduling loop's own spans and the runtime spans
under it (scheduling.drain, cache.snapshot, scheduling.housekeeping,
runtime.gc, xla.compile). "Per batch" divides by the scheduling.cycle
spans of the window, as encode_sync_ms.backlog does."""

from __future__ import annotations

from typing import Iterable, Optional

from .spans import named


def runtime_spans_recorded() -> bool:
    """Whether the program's tracer records runtime.gc and xla.compile
    spans: a window without one then reads 0, where a program that records
    none gets no reading."""
    from kubernetes_tpu.utils import tracing

    return hasattr(tracing, "gc_callback")


def per_batch_ms(spans: Iterable, name: str, under_cycle: bool = False,
                 recorded: Optional[bool] = None) -> Optional[float]:
    """Total duration of the ``name`` spans per scheduling.cycle span, in
    ms; with ``under_cycle`` only those whose parent is one of the window's
    cycles. None without a cycle, or when the program records no such span
    (``recorded``; by default, when the window holds none at all)."""
    spans = list(spans)
    cycles = named(spans, "scheduling.cycle")
    mine = named(spans, name)
    if recorded is None:
        recorded = bool(mine)
    if not cycles or not recorded:
        return None
    if under_cycle:
        ids = {c.span_id for c in cycles}
        mine = [s for s in mine if s.parent_id in ids]
    return sum(s.end - s.start for s in mine) / len(cycles) / 1e6
