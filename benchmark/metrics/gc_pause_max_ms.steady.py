"""gc_pause_max_ms.steady: the longest runtime.gc span in the window (0 if
none): the largest single stall the garbage collector put on a thread."""

from benchmark.loop_spans import runtime_spans_recorded
from benchmark.spans import named


def read(ctx):
    if not runtime_spans_recorded():
        return None
    return max((s.end - s.start for s in named(ctx["spans"], "runtime.gc")),
               default=0) / 1e6
