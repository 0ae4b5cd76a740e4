"""The gRPC sidecar cell's comparison against the plain reference, driven
end to end at a small size on the CPU: with the ApplyDeltas part bound
lowered so that the state sync takes several parts, the wire path comes out
correct; an answer altered where it is bound, or half of each batch left
out, makes it false. One process, one device service: no exchange between
chips to leave out."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402
from benchmark import run as bench_run  # noqa: E402
from kubernetes_tpu.backend import wire  # noqa: E402

CELL = "grpc-5k.wire-steady"
PART_BYTES = 16 * 1024


def _planted(fault, store):
    """A fault in the timed path, where the program binds its answers.

    ``alter`` sends every answer to one node. Over the wire the comparison
    accepts a node as full as the best one could have been with the events a
    lane may or may not have seen; on 40 evenly filled nodes the next node is
    often within that, more so on a loaded host, so moving each answer to
    its neighbour is not a fault this size can always show. One node taking
    every pod is off the argmax within a few pods and over capacity soon
    after, whatever the lanes saw."""
    orig = store.bind_batch
    if fault == "alter":
        def bind_batch(bindings):
            for b in bindings:  # the answer, altered as it is bound
                b.node_name = "node-0"
            return orig(bindings)
        store.bind_batch = bind_batch
    elif fault == "drop_half":
        def bind_half(bindings):
            # every other binding acknowledged but never written
            kept = orig(bindings[::2])
            return [kept[i // 2] if i % 2 == 0 else None
                    for i in range(len(bindings))]
        store.bind_batch = bind_half


def _execute(fault, monkeypatch, settle_s=20.0):
    monkeypatch.setattr(wire, "MAX_DELTA_PART_BYTES", PART_BYTES)
    spec = harness.load_spec(ROOT)
    cell, config, traffic = harness.load_cell(spec, CELL, ROOT)
    assert config["frontend"] == "grpc"
    config = dict(config, nodes=40, running_pods=160, batch_size=64)
    traffic = dict(traffic, rate_per_s=300)
    parts = []  # (size, parts in its push)

    def build(config, store, seed):
        sched, close = harness.build_scheduler(config, store, seed)
        client = sched.client
        send = client._send_deltas

        def send_deltas(request):
            parts.append(request.ByteSize())
            return send(request)
        client._send_deltas = send_deltas
        _planted(fault, store)
        return sched, close

    out = bench_run.execute(spec, cell, config, traffic, 2 ** 31 + 91, 1.0,
                            False, t_start=0.0, scheduler_factory=build,
                            say=lambda m: None, warm_phase_s=0.3,
                            settle_s=settle_s)
    return out, parts


def test_wire_cell_correct_in_parts(monkeypatch):
    out, parts = _execute(None, monkeypatch)
    assert out["correct"], out["compared"]
    assert out["compared"]["checked"]["value"] > 0
    assert out["compared"]["fallback"]["value"] == 0
    # the first (full) sync alone took several parts, each under the bound
    assert len(parts) > 3 and max(parts) <= PART_BYTES


@pytest.mark.parametrize("fault", ["alter", "drop_half"])
def test_wire_cell_fault_makes_it_false(fault, monkeypatch):
    # a dropped binding never lands: a short settle reads it unbound
    out, _parts = _execute(fault, monkeypatch, settle_s=2.0)
    assert out["correct"] is False, out["compared"]
