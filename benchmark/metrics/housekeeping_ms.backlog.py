"""housekeeping_ms.backlog: per batch, the scheduling.housekeeping spans
(the 1 s assume-expiry sweep, the 30 s unschedulable flush, and the
commit-worker flush ahead of them)."""

from benchmark.loop_spans import per_batch_ms


def read(ctx):
    return per_batch_ms(ctx["spans"], "scheduling.housekeeping")
