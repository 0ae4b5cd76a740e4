"""snapshot_ms.backlog: per batch, the cache.snapshot spans directly under
a scheduling.cycle: the scheduling thread's update of its cache snapshot
(re-cloning the nodes the last commits changed) before the device sync."""

from benchmark.loop_spans import per_batch_ms


def read(ctx):
    return per_batch_ms(ctx["spans"], "cache.snapshot", under_cycle=True)
