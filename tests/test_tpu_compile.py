"""AOT compiles of the main-path programs for a described TPU v5e chip.

Nothing runs: the TPU compiler installed beside jaxlib compiles for a chip
that is described, not attached (``get_topology_desc``), and refuses what
the chip's compiler would refuse — a tiling the kernel's layout cannot take,
more VMEM than a kernel may use, a program that does not fit HBM. Interpret-
mode tests of the Pallas kernel cannot see any of that.

The topology is described inside a module fixture (never at import, in a
``skipif`` or in ``parametrize``): only one process may load libtpu, and
every xdist worker imports every test file. Keep these compiles in this one
file, so they all land on the worker that loaded the library.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

NODES = 5000   # SchedulingBasic/5000Nodes, the headline cell
BATCH = 512    # bench.py / chip_smoke.py batch size


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else libtpu logs under /tmp
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent cache
        # but cannot be read back without one: keep the cache out of it
        prior = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", prior)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def headline_batch():
    """Host arrays of one SchedulingBasic batch at the capacities the
    scheduler pads 5000 nodes x batch 512 to (only their shapes matter)."""
    from kubernetes_tpu.api.wrappers import make_node, make_pod
    from kubernetes_tpu.backend.device_state import caps_for_cluster
    from kubernetes_tpu.backend.sig_table import SigTable
    from kubernetes_tpu.framework.types import NodeInfo
    from kubernetes_tpu.ops.encode import ClusterEncoder

    enc = ClusterEncoder(caps_for_cluster(NODES, BATCH))
    sig_table = SigTable(enc)
    nt = enc.encode_snapshot([NodeInfo(
        make_node("node-0").capacity(
            {"cpu": "32", "memory": "128Gi", "pods": 110}).obj())])
    pods = [make_pod("p-0").req({"cpu": "900m", "memory": "2Gi"}).obj()]
    pb, et = enc.encode_pods(pods)
    return pb, et, nt, sig_table.topo_counts(), sig_table.encode_topo(pods)


def _shapes(tree, sharding):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype,
                                       sharding=sharding), tree)


def _fused_args(n, r, w, sharding):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    return ([s((r, n), jnp.int32)] * 3
            + [s((w, n), jnp.uint32), s((r, 1), jnp.int32),
               s((r, 1), jnp.int32), s((w, 1), jnp.uint32),
               s((1, n), jnp.bool_)]
            + [s((1, n), jnp.float32)] * 4
            + [s((1, 1), jnp.int32), s((1, 8), jnp.float32)])


def _largest_supported_nodes(r, w):
    from kubernetes_tpu.ops.pallas_step import shapes_supported

    return max(n for n in range(128, 1 << 20, 128)
               if shapes_supported(n, r, w, None, False))


@pytest.mark.parametrize("nodes,r,w", [
    (5120, 6, 16),    # 5000 nodes padded; default resource/port widths
    (5120, 9, 24),    # widths off the sublane tile: extended resources
    (None, 6, 16),    # the largest node axis shapes_supported accepts
])
def test_fused_step_compiles(one_chip, nodes, r, w):
    from kubernetes_tpu.ops import pallas_step

    n = nodes or _largest_supported_nodes(r, w)
    compiled = jax.jit(pallas_step.fused_step).lower(
        *_fused_args(n, r, w, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis() is not None


@pytest.mark.parametrize("variant", ["spec_decode", "pallas"])
def test_schedule_batch_compiles_at_headline_size(one_chip, headline_batch,
                                                  variant):
    """The whole batch program at 5000 nodes x 512 pods. The static
    arguments the scheduler would pick on a chip are passed explicitly:
    jax.default_backend() reads CPU here."""
    from kubernetes_tpu.backend.batch import schedule_batch

    pb, et, nt, tc, tb = _shapes(headline_batch, one_chip)
    key = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    kw = ({"spec_decode": True} if variant == "spec_decode"
          else {"pallas": "compiled"})
    compiled = schedule_batch.lower(pb, et, nt, tc, tb, key,
                                    topo_enabled=False, **kw).compile()
    mem = compiled.memory_analysis()
    # one program must fit one v5e chip's 16 GB of HBM with room to spare
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 1 << 30
    if variant == "pallas":
        assert "tpu_custom_call" in compiled.as_text()


def test_zone_spread_rounds_compile_at_128_pods(one_chip):
    """The speculative rounds in the general topology mode at 5000 nodes x
    128 pods over a 256-value domain axis, the largest the one-hot segment
    sums take: the per-site [P, C, N, Vd] one-hot must stay fused, not
    materialise (it would be 335 MB a site at this size)."""
    from kubernetes_tpu.api.types import LabelSelector
    from kubernetes_tpu.api.wrappers import make_node, make_pod
    from kubernetes_tpu.backend.batch import schedule_batch
    from kubernetes_tpu.backend.device_state import caps_for_cluster
    from kubernetes_tpu.backend.sig_table import SigTable
    from kubernetes_tpu.framework.types import NodeInfo
    from kubernetes_tpu.ops.encode import ClusterEncoder

    zone = "topology.kubernetes.io/zone"
    enc = ClusterEncoder(caps_for_cluster(NODES, 128))
    sig_table = SigTable(enc)
    nt = enc.encode_snapshot([NodeInfo(
        make_node(f"node-{i}").capacity(
            {"cpu": "4", "memory": "32Gi", "pods": 110})
        .label(zone, f"zone-{i}").obj()) for i in range(3)])
    sel = LabelSelector(match_labels={"app": "spread"})
    pods = [make_pod("p-0").req({"cpu": "100m", "memory": "500Mi"})
            .label("app", "spread").spread_constraint(5, zone, selector=sel)
            .obj()]
    pb, et = enc.encode_pods(pods)
    args = _shapes((pb, et, nt, sig_table.topo_counts(),
                    sig_table.encode_topo(pods)), one_chip)
    key = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = schedule_batch.lower(
        *args, key, topo_mode="general", spec_decode=True,
        vd_override=256, ports_enabled=False).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 28


@pytest.mark.parametrize("topology", [False, True])
def test_release_rows_compiles_at_headline_size(one_chip, headline_batch,
                                                topology):
    """The completion release (DeviceState.release) at 5000 nodes and 256
    released pods: the scatter-subtract onto requested, nonzero_requested
    and class_req, with the topology carry's signature counts or without."""
    from kubernetes_tpu.backend.device_state import _release_rows_jit

    _pb, _et, nt, tc, _tb = headline_batch
    b = 256
    s, r = np.shape(tc.sel_counts)[0], np.shape(nt.requested)[1]
    args = _shapes((nt.requested, nt.nonzero_requested, nt.class_req,
                    tc.sel_counts if topology else None,
                    np.zeros(b, np.int32), np.zeros((b, r), np.int32),
                    np.zeros((b, r), np.int32), np.zeros(b, np.int32),
                    np.zeros((b, s), np.int32) if topology else None),
                   one_chip)
    compiled = _release_rows_jit.lower(*args).compile()
    assert compiled.memory_analysis() is not None


def test_sharded_schedule_batch_compiles_on_2x2_mesh(topo, headline_batch):
    """parallel/mesh.py's node-sharded program over the four described
    chips: node axis split four ways, winners reduced by collectives."""
    from kubernetes_tpu.parallel import mesh as pm

    mesh = Mesh(np.array(topo.devices), (pm.AXIS,))

    def on(spec):
        return NamedSharding(mesh, spec)

    pb, et, nt, tc, tb = headline_batch
    nt_s = jax.tree_util.tree_map(
        lambda a, spec: jax.ShapeDtypeStruct(np.shape(a), a.dtype,
                                             sharding=on(spec)),
        nt, pm._nt_specs())
    tc_s = type(tc)(**{
        name: jax.ShapeDtypeStruct(np.shape(a), a.dtype, sharding=on(spec))
        for name, a, spec in (
            ("sel_counts", tc.sel_counts, PartitionSpec(None, pm.AXIS)),
            ("term_counts", tc.term_counts, PartitionSpec(None, pm.AXIS)),
            ("term_key", tc.term_key, PartitionSpec()))})
    rep = on(PartitionSpec())
    fn = pm.make_sharded_schedule_fn(mesh, topo_enabled=False,
                                     spec_decode=True)
    compiled = fn.lower(_shapes(pb, rep), _shapes(et, rep), nt_s, tc_s,
                        _shapes(tb, rep),
                        jax.ShapeDtypeStruct((), jnp.int32,
                                             sharding=rep)).compile()
    assert "all-reduce" in compiled.as_text()
    assert compiled.memory_analysis() is not None
