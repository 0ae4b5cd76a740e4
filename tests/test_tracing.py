"""OTel-style span tracing (SURVEY §5.1): nesting, OTLP shape, JSON export,
the runtime spans (GC pauses, XLA compiles), and the scheduler's cycle-phase
and loop spans on both paths."""

import contextlib
import gc
import json
import threading

import numpy as np
import pytest

from kubernetes_tpu.api.wrappers import make_node, make_pod
from kubernetes_tpu.apiserver import ClusterStore
from kubernetes_tpu.backend import TPUScheduler
from kubernetes_tpu.scheduler.scheduler import Scheduler
from kubernetes_tpu.utils import tracing


@contextlib.contextmanager
def _no_gc():
    """Automatic collections off: under enable() a collection at any
    allocation adds a runtime.gc span, which tests of exact span lists must
    not see."""
    was = gc.isenabled()
    gc.disable()
    try:
        yield
    finally:
        if was:
            gc.enable()


class TestTracer:
    def teardown_method(self):
        tracing.disable()

    @_no_gc()
    def test_nesting_and_otlp_shape(self):
        tracer = tracing.enable()
        with tracing.span("parent", cluster="test") as parent:
            with tracing.span("child") as child:
                pass
        exp = tracer.exporter
        assert [s.name for s in exp.spans] == ["child", "parent"]
        c, p = exp.spans
        assert c.trace_id == p.trace_id and c.parent_id == p.span_id
        otlp = p.to_otlp()
        assert otlp["name"] == "parent" and otlp["parentSpanId"] == ""
        assert {"key": "cluster", "value": {"stringValue": "test"}} in otlp["attributes"]
        assert c.duration_s >= 0

    def test_disabled_is_noop(self):
        with tracing.span("nothing") as s:
            assert s is None

    def test_json_file_exporter(self, tmp_path):
        path = str(tmp_path / "spans.jsonl")
        tracing.enable(tracing.JsonFileExporter(path))
        with tracing.span("one"):
            pass
        line = json.loads(open(path).read().strip())
        assert line["name"] == "one" and line["endTimeUnixNano"] > 0

    def test_env_enable(self, tmp_path, monkeypatch):
        monkeypatch.setenv("KTPU_TRACE_FILE", str(tmp_path / "t.jsonl"))
        tracing.maybe_enable_from_env()
        assert tracing.get() is not None


class TestTraceparent:
    def teardown_method(self):
        tracing.disable()

    def test_format_and_parse_roundtrip(self):
        tracing.enable()
        assert tracing.format_traceparent() is None  # no open span
        with tracing.span("outer") as s:
            tp = tracing.format_traceparent()
            assert tp == f"00-{s.trace_id}-{s.span_id}-01"
            assert tracing.parse_traceparent(tp) == (s.trace_id, s.span_id)
        for bad in (None, "", "junk", "00-short-short-01", 42):
            assert tracing.parse_traceparent(bad) is None

    def test_disabled_is_noop(self):
        assert tracing.format_traceparent() is None
        with tracing.span_from_remote("00-" + "a" * 32 + "-" + "b" * 16 + "-01",
                                      "child") as s:
            assert s is None

    def test_span_from_remote_parents_across_boundary(self):
        tracer = tracing.enable()
        with tracing.span("client.op") as parent:
            tp = tracing.format_traceparent()
        with tracing.span_from_remote(tp, "server.op") as child:
            with tracing.span("server.inner") as inner:
                pass
        assert child.trace_id == parent.trace_id
        assert child.parent_id == parent.span_id
        assert inner.trace_id == parent.trace_id and inner.parent_id == child.span_id
        # malformed context falls back to a fresh local trace
        with tracing.span_from_remote("not-a-traceparent", "server.op") as s:
            assert s.parent_id is None and s.trace_id != parent.trace_id

    @_no_gc()
    def test_tail(self):
        tracing.enable()
        for i in range(5):
            with tracing.span(f"s{i}"):
                pass
        assert [s.name for s in tracing.tail(2)] == ["s3", "s4"]
        tracing.disable()
        assert tracing.tail() == []


class TestRuntimeSpans:
    def teardown_method(self):
        tracing.disable()

    def test_gc_collect_yields_runtime_gc_span(self):
        tracer = tracing.enable()
        with tracing.span("outer") as outer:
            gc.collect()
        gcs = tracer.exporter.by_name("runtime.gc")
        assert any(s.attributes["generation"] == 2 for s in gcs)
        full = [s for s in gcs if s.attributes["generation"] == 2][-1]
        assert full.parent_id == outer.span_id
        assert outer.start <= full.start <= full.end <= outer.end
        assert isinstance(full.attributes["collected"], int)

    def test_disable_removes_gc_hook(self):
        tracing.enable()
        tracing.enable()  # a second enable installs no second hook
        assert gc.callbacks.count(tracing.gc_callback) == 1
        tracing.disable()
        assert tracing.gc_callback not in gc.callbacks
        gc.collect()  # no tracer, no hook: nothing recorded, nothing raised
        assert tracing.get() is None

    def test_gc_while_exporter_holds_its_lock(self, tmp_path):
        """A collection that starts inside JsonFileExporter.export (its plain
        lock held) neither deadlocks nor loses the span: the span waits for
        the next ordinary export."""
        path = str(tmp_path / "spans.jsonl")
        exporter = tracing.JsonFileExporter(path)
        tracing.enable(exporter)

        def collect_under_lock():
            with exporter._lock:
                gc.collect()

        t = threading.Thread(target=collect_under_lock, daemon=True)
        t.start()
        t.join(timeout=30)
        assert not t.is_alive(), "GC hook deadlocked on the exporter lock"
        with tracing.span("after"):
            pass
        names = [json.loads(line)["name"] for line in open(path)]
        assert "runtime.gc" in names and names[-1] == "after"

    def test_disable_exports_deferred_spans(self):
        tracer = tracing.enable()
        gc.collect()
        tracing.disable()
        assert tracer.exporter.by_name("runtime.gc")

    def test_jit_compile_yields_xla_compile_span(self):
        import jax

        from kubernetes_tpu.backend import telemetry  # noqa: F401 — its
        # import registers the compile listener with the tracer

        x = np.arange(7, dtype=np.float32)
        tracer = tracing.enable()
        with tracing.span("outer") as outer:
            jax.jit(lambda v: v * 3.0 + 1.0)(x).block_until_ready()
        compiles = tracer.exporter.by_name("xla.compile")
        assert len(compiles) == 1
        c = compiles[0]
        assert c.parent_id == outer.span_id
        assert outer.start <= c.start <= c.end <= outer.end


class _Clock:
    def __init__(self):
        self.t = 1000.0

    def __call__(self):
        return self.t


class TestSchedulerSpans:
    def teardown_method(self):
        tracing.disable()

    def test_sequential_cycle_span(self):
        tracer = tracing.enable()
        store = ClusterStore()
        sched = Scheduler(store)
        store.create_node(make_node("n1").capacity(
            {"cpu": "4", "memory": "8Gi", "pods": 10}).obj())
        store.create_pod(make_pod("p").req({"cpu": "100m"}).obj())
        sched.run_until_settled()
        cycles = tracer.exporter.by_name("scheduling.cycle")
        assert cycles and cycles[0].attributes["pod"] == "default/p"

    def test_batch_phase_spans(self):
        tracer = tracing.enable()
        store = ClusterStore()
        sched = TPUScheduler(store, batch_size=8)
        for i in range(4):
            store.create_node(make_node(f"n{i}").capacity(
                {"cpu": "4", "memory": "8Gi", "pods": 10}).obj())
        for i in range(6):
            store.create_pod(make_pod(f"p{i}").req({"cpu": "100m"}).obj())
        sched.run_until_settled()
        names = {s.name for s in tracer.exporter.spans}
        assert {"device.encode", "device.dispatch", "device.commit.wait",
                "host.commit", "scheduling.cycle"} <= names

    def test_loop_spans_with_commit_worker(self, monkeypatch):
        """With the commit worker on, each batch that takes the drain path
        opens scheduling.drain and cache.snapshot directly under its
        scheduling.cycle; scheduling.housekeeping appears only on the cycles
        where a sweep is due (1 s apart on the scheduler's clock)."""
        monkeypatch.setenv("KTPU_COMMIT_WORKER", "1")
        clock = _Clock()
        tracer = tracing.enable()
        store = ClusterStore()
        sched = TPUScheduler(store, batch_size=2, now_fn=clock)
        try:
            assert sched.commit_worker is not None
            for i in range(4):
                store.create_node(make_node(f"n{i}").capacity(
                    {"cpu": "4", "memory": "8Gi", "pods": 10}).obj())
            for i in range(6):
                store.create_pod(make_pod(f"p{i}").req({"cpu": "100m"}).obj())
            sweeps = 0
            for _ in range(10):
                clock.t += 0.4
                sweeps += clock.t - sched._last_cleanup >= 1.0
                sched.schedule_batch_cycle()
            sched.run_until_settled()
        finally:
            sched.commit_worker.stop()
        assert sched.metrics["scheduled"] == 6
        spans = tracer.exporter.spans
        by_id = {s.span_id: s for s in spans}
        cycles = {s.span_id for s in spans if s.name == "scheduling.cycle"}
        for name in ("scheduling.drain", "cache.snapshot"):
            under = [s for s in spans if s.name == name
                     and s.parent_id in cycles]
            assert under, name
        drains = [s for s in spans if s.name == "scheduling.drain"]
        assert sum(s.attributes["batches"] for s in drains) >= 1
        assert all(s.attributes["nodes"] >= 0
                   for s in spans if s.name == "cache.snapshot")
        hk = [s for s in spans if s.name == "scheduling.housekeeping"]
        assert 2 <= len(hk) == sweeps
        assert all(by_id.get(s.parent_id) is None for s in hk)

    def test_sequential_cycle_has_extension_point_children(self):
        tracer = tracing.enable()
        store = ClusterStore()
        sched = Scheduler(store)
        for i in range(3):
            store.create_node(make_node(f"n{i}").capacity(
                {"cpu": "4", "memory": "8Gi", "pods": 10}).obj())
        store.create_pod(make_pod("p").req({"cpu": "100m"}).obj())
        sched.run_until_settled()
        spans = tracer.exporter.spans
        cycle = tracer.exporter.by_name("scheduling.cycle")[0]
        children = {s.name for s in spans if s.trace_id == cycle.trace_id}
        # the instrumented framework runtime gives the cycle per-point and
        # per-plugin spans (framework.* / plugin.*); bind happens after the
        # cycle span closes and roots its own framework.bind span
        assert {"framework.pre_filter", "framework.filter",
                "framework.score"} <= children
        assert any(n.startswith("plugin.") for n in children)
        assert tracer.exporter.by_name("framework.bind")


class TestCrossBoundaryTrace:
    """Acceptance: after a wire-backend run the JSON-lines export contains a
    trace in which the backend device.commit span's trace_id/parent chain
    resolves to the originating scheduling.cycle span."""

    def teardown_method(self):
        tracing.disable()

    def test_wire_backend_commit_parents_under_cycle(self, tmp_path):
        from kubernetes_tpu.backend.service import (DeviceService,
                                                    WireScheduler, serve)

        path = str(tmp_path / "spans.jsonl")
        tracing.enable(tracing.JsonFileExporter(path))
        store = ClusterStore()
        svc = DeviceService(batch_size=8)
        server, port = serve(svc)
        try:
            sched = WireScheduler(store, endpoint=f"http://127.0.0.1:{port}",
                                  batch_size=8)
            for i in range(4):
                store.create_node(make_node(f"n{i}").capacity(
                    {"cpu": "4", "memory": "8Gi", "pods": 10}).obj())
            for i in range(6):
                store.create_pod(make_pod(f"p{i}").req({"cpu": "100m"}).obj())
            sched.run_until_settled()
        finally:
            server.shutdown()
            server.server_close()
        assert sched.metrics["scheduled"] == 6
        spans = [json.loads(line) for line in open(path)]
        by_id = {s["spanId"]: s for s in spans}
        commits = [s for s in spans if s["name"] == "device.commit"]
        assert commits, {s["name"] for s in spans}
        for c in commits:
            chain = []
            cur = c
            while cur["parentSpanId"]:
                assert cur["parentSpanId"] in by_id, "broken parent chain"
                cur = by_id[cur["parentSpanId"]]
                chain.append(cur["name"])
                assert cur["traceId"] == c["traceId"]
            # device.commit → device.schedule_batch → scheduling.cycle:
            # ONE trace covers scheduler pop → wire hop → device commit
            assert chain[-1] == "scheduling.cycle", chain
