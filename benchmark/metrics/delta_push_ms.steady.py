"""delta_push_ms.steady: per batch, the client's wire.apply_deltas spans
directly under a scheduling.cycle: the scheduling thread sending the state
sync (every part of it) to the device service before the batch."""

from benchmark.loop_spans import per_batch_ms


def read(ctx):
    return per_batch_ms(ctx["spans"], "wire.apply_deltas", under_cycle=True)
