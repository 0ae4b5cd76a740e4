"""gc_pause_ms.backlog: per batch, the runtime.gc spans on any thread (the
Python garbage collector's pauses, every generation)."""

from benchmark.loop_spans import per_batch_ms, runtime_spans_recorded


def read(ctx):
    return per_batch_ms(ctx["spans"], "runtime.gc",
                        recorded=runtime_spans_recorded())
