"""drain_wait_ms.backlog: per batch, the scheduling.drain spans directly
under a scheduling.cycle: the scheduling thread blocked until the commit
worker has landed the batches in flight, before it syncs the device."""

from benchmark.loop_spans import per_batch_ms


def read(ctx):
    return per_batch_ms(ctx["spans"], "scheduling.drain", under_cycle=True)
