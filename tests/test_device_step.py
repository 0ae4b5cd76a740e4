"""The shared device-side batch step (backend/device_step.py): the mirror's
capacity policy, the sampling rule, the one dispatch both drivers reach,
and the served path's independence from the in-process scheduler."""

import ast
import dataclasses
import os
import re

import numpy as np
import pytest

from kubernetes_tpu.api.wrappers import make_node, make_pod
from kubernetes_tpu.apiserver import ClusterStore
from kubernetes_tpu.backend import device_step
from kubernetes_tpu.backend.device_state import DeviceState, caps_for_cluster
from kubernetes_tpu.backend.errors import PermanentDeviceError
from kubernetes_tpu.ops.encode import CapacityError
from kubernetes_tpu.scheduler.scheduler import num_feasible_nodes_to_find

BACKEND = os.path.join(os.path.dirname(__file__), os.pardir, "kubernetes_tpu",
                       "backend")
OPS = os.path.join(os.path.dirname(__file__), os.pardir, "kubernetes_tpu",
                   "ops")

# every dimension the encoders raise (ops/encode.py, then backend/
# sig_table.py), with the Capacities fields a grow must double
GROW_CASES = [
    ("nodes", ("nodes",)),
    ("pods", ("pods",)),
    ("resources", ("resources",)),
    ("label_keys", ("label_keys",)),
    ("taints", ("taints",)),
    ("tolerations", ("tolerations",)),
    ("exprs", ("exprs",)),
    ("sel_exprs", ("sel_exprs",)),
    ("terms", ("terms",)),
    ("term_exprs", ("term_exprs",)),
    ("pref_terms", ("pref_terms",)),
    ("ports", ("ports",)),
    ("ports vocab", ("port_words",)),
    ("image vocab", ("image_words", "images")),
    ("containers", ("containers",)),
    ("prio_classes", ("prio_classes",)),
    ("superpods", ("superpods",)),
    ("sp_slots", ("sp_slots",)),
    ("sigs", ("sigs",)),
    ("ex_terms", ("ex_terms",)),
    ("spread_cons", ("spread_cons",)),
    ("ipa_terms", ("ipa_terms",)),
    ("ipa_pref", ("ipa_pref",)),
    ("value vocab for 'zone'", ("value_words",)),
]


@pytest.fixture(scope="module")
def small_device():
    return DeviceState(caps_for_cluster(4, batch=8))


@pytest.mark.parametrize("dimension,fields",
                         GROW_CASES + [("bogus axis", None)],
                         ids=[c[0] for c in GROW_CASES] + ["unknown"])
def test_capacity_policy_grows_the_named_axis(small_device, dimension,
                                              fields):
    """A CapacityError grows exactly the axis it names, doubling until it
    holds what was needed, and returns a new mirror without swapping it
    in; a dimension no axis answers to is permanent."""
    caps = small_device.caps
    have = max(getattr(caps, f) for f in fields or ("nodes",))
    err = CapacityError(dimension, have * 3, have)
    if fields is None:
        with pytest.raises(PermanentDeviceError) as exc:
            device_step.grown(small_device, err, ns_labels_fn=None)
        assert exc.value.__cause__ is err
        return
    new = device_step.grown(small_device, err, ns_labels_fn=None)
    assert new is not small_device and small_device.caps is caps
    for f in fields:  # the old size doubled, to the first that holds it
        old, grew = getattr(caps, f), getattr(new.caps, f)
        assert err.needed <= grew < 2 * err.needed
        assert grew % old == 0 and (grew // old) & (grew // old - 1) == 0
    untouched = [f.name for f in dataclasses.fields(caps)
                 if f.name not in fields]
    assert all(getattr(new.caps, f) == getattr(caps, f) for f in untouched)


def test_every_raised_dimension_has_a_case():
    """The grow cases above cover every literal dimension the encoders
    raise, and the policy's table answers to each."""
    raised = set()
    for path in (os.path.join(OPS, "encode.py"),
                 os.path.join(BACKEND, "sig_table.py")):
        raised |= set(re.findall(r'CapacityError\(\s*"([^"]+)"',
                                 open(path).read()))
    cased = {d for d, _ in GROW_CASES}
    assert raised <= cased, raised - cased
    assert raised == set(device_step.GROW_FIELDS)


@pytest.mark.parametrize("have,n,want_nodes", [
    (None, 200, caps_for_cluster(200).nodes),  # a new mirror for n
    (4, 100, None),                            # covered: the same mirror
    (4, 300, 512),                             # node axis doubles past n
])
def test_fit_nodes(have, n, want_nodes):
    device = (DeviceState(caps_for_cluster(have, batch=8))
              if have is not None else None)
    out = device_step.fit_nodes(device, n, batch=8, ns_labels_fn=None)
    if want_nodes is None:
        assert out is device
        return
    assert out is not device and out.caps.nodes == want_nodes >= n
    # the hostname value vocab covers every node slot
    assert out.caps.value_words * 32 >= out.caps.nodes + 2
    if device is not None:  # every other grown axis kept
        assert out.caps.pods == device.caps.pods


def test_retry_grown_bounds_the_attempts():
    grows = []

    def never():
        raise CapacityError("pods", 2, 1)

    assert device_step.retry_grown(never, grows.append) == (False, None)
    assert len(grows) == device_step.GROW_ATTEMPTS
    tries = iter([CapacityError("pods", 2, 1), None])

    def second_time():
        e = next(tries)
        if e is not None:
            raise e
        return "synced"

    assert device_step.retry_grown(second_time, grows.append) == (
        True, "synced")


@pytest.mark.parametrize("percentage,full_batch,n,expect_k", [
    (10, None, 1000, num_feasible_nodes_to_find(1000, 10)),  # explicit
    (0, None, 1000, num_feasible_nodes_to_find(1000, 0)),    # adaptive, CPU
    (0, "1", 1000, None),                                    # KTPU_FULL_BATCH
    (10, "1", 1000, num_feasible_nodes_to_find(1000, 10)),   # explicit wins
    (0, None, 50, None),                                     # small cluster
])
def test_sampling_rule(monkeypatch, percentage, full_batch, n, expect_k):
    """Explicit percentage: the rotating window; adaptive (0): the
    reference's sample on the CPU, the full batch where KTPU_FULL_BATCH=1
    (the accelerator default); the window starts at the carry."""
    if full_batch is None:
        monkeypatch.delenv("KTPU_FULL_BATCH", raising=False)
    else:
        monkeypatch.setenv("KTPU_FULL_BATCH", full_batch)
    carry = np.int32(17)
    k, start = device_step.sample_window(percentage, n, carry)
    if expect_k is None:
        assert (k, start) == (None, None)
        return
    assert int(k) == expect_k < n and start is carry
    assert int(device_step.sample_window(percentage, n, None)[1]) == 0


def _plain(store):
    for i in range(4):
        store.create_node(
            make_node(f"n{i}").capacity({"cpu": "4", "memory": "8Gi",
                                         "pods": 10}).obj())
    for i in range(6):
        store.create_pod(make_pod(f"p{i}").req({"cpu": "1",
                                                "memory": "1Gi"}).obj())


def _full(store):
    """Two full nodes of low-priority pods and one high-priority pod that
    fits nowhere until something is evicted."""
    for i in range(2):
        store.create_node(
            make_node(f"n{i}").capacity({"cpu": "2", "memory": "4Gi",
                                         "pods": 10}).obj())
        store.create_pod(make_pod(f"lo{i}").req({"cpu": "2", "memory": "1Gi"})
                         .priority(0).node(f"n{i}").obj())
    store.create_pod(make_pod("hi").req({"cpu": "2", "memory": "1Gi"})
                     .priority(1000).obj())


def _drive(driver, build):
    """Build the cluster and run one driver on it until it settles."""
    store = ClusterStore()
    if driver == "inproc":
        from kubernetes_tpu.backend import TPUScheduler

        sched = TPUScheduler(store, batch_size=8)
        build(store)
        sched.run_until_settled()
        if sched.commit_worker is not None:
            sched.commit_worker.stop()
        return sched
    from kubernetes_tpu.backend.service import (DeviceService, WireScheduler,
                                                serve)

    server, port = serve(DeviceService(batch_size=32))
    try:
        sched = WireScheduler(store, endpoint=f"http://127.0.0.1:{port}",
                              batch_size=8)
        build(store)
        sched.run_until_settled()
    finally:
        server.shutdown()
    return sched


def _spy(monkeypatch, name):
    calls = []
    real = getattr(device_step, name)

    def spy(*args, **kwargs):
        calls.append((args, kwargs))
        return real(*args, **kwargs)

    monkeypatch.setattr(device_step, name, spy)
    return calls


@pytest.mark.parametrize("driver", ["inproc", "served"])
def test_both_drivers_reach_the_one_dispatch(monkeypatch, driver):
    """An in-process batch and a served batch both dispatch through
    device_step.dispatch (the served path with its own caller values)."""
    calls = _spy(monkeypatch, "dispatch")
    sched = _drive(driver, _plain)
    assert sched.metrics["scheduled"] == 6
    assert calls
    modes = {(kw["topo_mode"], kw["ports_enabled"]) for _, kw in calls}
    if driver == "served":
        assert modes == {(None, True)}
    else:
        assert {m for m, _ in modes} <= {"off", "general", "host"}


@pytest.mark.parametrize("driver", ["inproc", "served"])
def test_both_drivers_screen_through_the_one_function(monkeypatch, driver):
    """A pod that fails on both paths is screened for preemption by
    device_step.screen_preemption, with its row marked failed."""
    calls = _spy(monkeypatch, "screen_preemption")
    _drive(driver, _full)
    assert calls
    assert all(np.asarray(args[3]).any() for args, _ in calls)


@pytest.mark.parametrize("module", ["service.py", "grpc_service.py",
                                    "fabric.py"])
def test_served_path_imports_nothing_from_the_in_process_scheduler(module):
    tree = ast.parse(open(os.path.join(BACKEND, module)).read())
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            names = [node.module or ""] + [a.name for a in node.names]
        elif isinstance(node, ast.Import):
            names = [a.name for a in node.names]
        else:
            continue
        assert not any(n.split(".")[-1] == "tpu_scheduler" for n in names), (
            module, node.lineno)
