"""wire_kib.steady: per batch, the KiB of serialized requests the client
sent to the device service, ApplyDeltas parts and ScheduleBatch calls
together, from the bytes attribute of their wire.* spans."""

from benchmark.spans import named

WIRE_SPANS = ("wire.apply_deltas", "wire.schedule_batch")


def read(ctx):
    spans = ctx["spans"]
    cycles = named(spans, "scheduling.cycle")
    sent = [s for s in spans if s.name in WIRE_SPANS]
    if not cycles or not sent:
        return None
    total = sum(int(s.attributes.get("bytes", 0)) for s in sent)
    return total / 1024 / len(cycles)
