"""The topology segment sums shared by the commit scan and the speculative
rounds (ops/topology.py ``seg_sum``/``seg_read``): exact against
``jax.ops.segment_sum`` on both sides of the one-hot threshold, and no
scatter left inside the speculative rounds for compact domain axes."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from kubernetes_tpu.api.types import LabelSelector
from kubernetes_tpu.api.wrappers import make_node, make_pod
from kubernetes_tpu.backend.batch import schedule_batch
from kubernetes_tpu.backend.sig_table import SigTable
from kubernetes_tpu.framework.types import NodeInfo
from kubernetes_tpu.ops import topology
from kubernetes_tpu.ops.encode import ClusterEncoder
from kubernetes_tpu.ops.schema import Capacities

# [C, N] is the scan path's shape (one pod's constraints), [P, C, N] the
# speculative rounds' (every pod of a round at once)
SHAPES = [(2, 640), (16, 2, 640)]
VDS = [64, 256, 512]


def _inputs(shape, vd, seed):
    """int32 per-node counts up to 5000 (past bf16's exact 256) and domain
    ids that use the whole axis, a few zones, and the no-key column 0."""
    rng = np.random.default_rng(seed)
    values = rng.integers(0, 5000, size=shape, dtype=np.int32)
    values[..., ::7] = rng.integers(300, 5000, size=values[..., ::7].shape)
    dom = rng.integers(0, vd, size=shape, dtype=np.int32)
    dom[..., : shape[-1] // 2] = rng.integers(
        0, 4, size=dom[..., : shape[-1] // 2].shape)
    return jnp.asarray(values), jnp.asarray(dom)


def _segment_sum_ref(values, dom, vd):
    flat_v = values.reshape(-1, values.shape[-1])
    flat_d = dom.reshape(-1, dom.shape[-1])
    seg = jax.vmap(lambda v, d: jax.ops.segment_sum(v, d, num_segments=vd))(
        flat_v, flat_d)
    return seg.reshape(*values.shape[:-1], vd)


@pytest.mark.parametrize("vd", VDS)
@pytest.mark.parametrize("shape", SHAPES, ids=["scan_CN", "spec_PCN"])
def test_seg_sum_exact(shape, vd):
    values, dom = _inputs(shape, vd, seed=vd + len(shape))
    got = jax.jit(topology.seg_sum, static_argnums=2)(values, dom, vd)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(_segment_sum_ref(values, dom, vd)))
    assert got.dtype == jnp.int32


@pytest.mark.parametrize("vd", VDS)
@pytest.mark.parametrize("shape", SHAPES, ids=["scan_CN", "spec_PCN"])
def test_seg_read_exact(shape, vd):
    values, dom = _inputs(shape, vd, seed=3 * vd + len(shape))
    seg = _segment_sum_ref(values, dom, vd)
    got = jax.jit(topology.seg_read)(seg, dom)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(jnp.take_along_axis(seg, dom, axis=-1)))


@pytest.mark.parametrize("vd,mode", [(64, "onehot"), (256, "onehot"),
                                     (257, "scatter"), (512, "scatter")])
def test_seg_mode_threshold(vd, mode):
    assert topology.seg_mode(vd) == mode


@pytest.mark.parametrize("vd", [64, 512])
def test_seg_sum_psums_node_shards(vd):
    """Under shard_map each shard sums its node slice; the psum'd result is
    the global table on every shard."""
    devs = np.array(jax.devices()[:4])
    mesh = Mesh(devs, ("n",))
    values, dom = _inputs((16, 2, 640), vd, seed=11 * vd)
    fn = jax.jit(jax.shard_map(
        lambda v, d: topology.seg_sum(v, d, vd, "n"), mesh=mesh,
        in_specs=(P(None, None, "n"), P(None, None, "n")),
        out_specs=P(), check_vma=False))
    np.testing.assert_array_equal(np.asarray(fn(values, dom)),
                                  np.asarray(_segment_sum_ref(values, dom, vd)))


# ----------------------------------------------------- the lowered rounds


def _block_end(txt, start):
    """End offset of the brace block that opens at or after ``start``."""
    depth = 0
    for j in range(txt.index("{", start), len(txt)):
        if txt[j] == "{":
            depth += 1
        elif txt[j] == "}":
            depth -= 1
            if depth == 0:
                return j + 1
    raise ValueError("unbalanced braces")


def _while_text(module: str) -> str:
    """The StableHLO of every while loop's cond and body regions, plus the
    private functions they call, transitively."""
    parts = []
    for m in re.finditer(r"stablehlo\.while", module):
        cond_end = _block_end(module, m.end())
        parts.append(module[m.start():_block_end(module, cond_end)])
    text = "\n".join(parts)
    seen, todo = set(), set(re.findall(r"call @([\w.$-]+)", text))
    while todo:
        name = todo.pop()
        seen.add(name)
        m = re.search(r"func\.func private @%s\(" % re.escape(name), module)
        fn = module[m.start():_block_end(module, m.end())]
        text += "\n" + fn
        todo |= set(re.findall(r"call @([\w.$-]+)", fn)) - seen
    return text


def _zone_batch():
    """A zone-labelled cluster and a batch of TopologySpreading's pods
    (zone DoNotSchedule, maxSkew 5, selecting their own label)."""
    n_nodes, n_pods = 12, 8
    infos = [NodeInfo(make_node(f"n{i}")
                      .capacity({"cpu": "4", "memory": "32Gi", "pods": 110})
                      .label("topology.kubernetes.io/zone", f"zone-{i % 3}")
                      .obj())
             for i in range(n_nodes)]
    enc = ClusterEncoder(Capacities(nodes=n_nodes, pods=n_pods, value_words=32))
    sig = SigTable(enc)
    nt = enc.encode_snapshot(infos)
    sel = LabelSelector(match_labels={"app": "spread"})
    pods = [make_pod(f"p{i}").req({"cpu": "100m", "memory": "500Mi"})
            .label("app", "spread")
            .spread_constraint(5, "topology.kubernetes.io/zone", selector=sel)
            .obj() for i in range(n_pods)]
    pb, et = enc.encode_pods(pods)
    return pb, et, nt, sig.topo_counts(), sig.encode_topo(pods)


@pytest.mark.parametrize("topo_mode,vd,scatters", [
    ("general", 64, False),    # zone-keyed: the one-hot form
    ("general", 512, True),    # past the threshold: the scatter stays
    ("off", None, False),      # the per-node winner table is no scatter
])
def test_no_scatter_in_speculative_rounds(topo_mode, vd, scatters):
    pb, et, nt, tc, tb = _zone_batch()
    module = schedule_batch.lower(
        pb, et, nt, tc, tb, np.int32(1), topo_mode=topo_mode,
        spec_decode=True, vd_override=vd).as_text()
    rounds = _while_text(module)
    assert "stablehlo.while" in rounds
    assert ("scatter" in rounds) == scatters
