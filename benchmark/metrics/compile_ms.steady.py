"""compile_ms.steady: total xla.compile time in the window (0 if none):
XLA compiles, or compile-cache loads, of a shape the warm-up missed."""

from benchmark.loop_spans import runtime_spans_recorded
from benchmark.spans import named


def read(ctx):
    if not runtime_spans_recorded():
        return None
    return sum(s.end - s.start for s in named(ctx["spans"], "xla.compile")) / 1e6
