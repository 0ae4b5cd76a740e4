"""Segment-reduction kernels for the two "hard" plugins (SURVEY.md §7 step 5):
PodTopologySpread and InterPodAffinity on the batched device path.

Topology domains are label-value-id buckets: a constraint/term's per-domain
pod counts are one segment sum of TopoCounts rows over ``label_val[:, key]``,
and per-node reads are one read back (``seg_sum``/``seg_read``) — the
tensorization of the reference's ``map[{topologyKey,value}]int`` bookkeeping
(podtopologyspread/filtering.go:40 preFilterState,
interpodaffinity/filtering.go:155 topologyToMatchedTermCount).

These kernels serve both commit paths of backend/batch.py: the per-pod commit
scan calls them one pod at a time, and the speculative rounds
(``_speculative_core``) call ``seg_sum``/``seg_read`` batched over the pods of
a round. Either way counts evolve as batch pods commit, so pod k sees exactly
the topology state the reference's serial loop would (anti-affinity
violations within one batch are impossible by construction, SURVEY.md §7
hard-part 4).

Sharding: segment sums run over the local node shard, then one psum merges
the per-shard segment tables; reads stay shard-local. seg_exist
(existing-term domain counts) is replicated and updated on every shard via a
psum'd commit-domain broadcast.
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

# Python literal, not jnp.int32(...): module-level jax scalars become
# captured device-buffer constants instead of HLO constants (see
# ops/select.py NEG_INF note).
INT_MAX = 2**31 - 1


def _gsum(x, axis_name):
    return x if axis_name is None else lax.psum(x, axis_name)


def _gmax(x, axis_name):
    return x if axis_name is None else lax.pmax(x, axis_name)


def _gmin(x, axis_name):
    return x if axis_name is None else lax.pmin(x, axis_name)


class TopoStatic(NamedTuple):
    """Per-batch static context (node labels cannot change intra-batch)."""

    dom_t: jax.Array      # [T, N] domain id of node n under term t's topology key
    seg_exist0: jax.Array  # [T, Vd] per-domain counts of pods carrying term t


def make_static(term_counts: jax.Array, term_key: jax.Array, label_val: jax.Array,
                valid: jax.Array, vd: int, axis_name: Optional[str] = None) -> TopoStatic:
    dom_t = label_val[:, term_key].T                                  # [T, N]
    add = jnp.where(valid[None, :] & (dom_t > 0), term_counts, 0)
    return TopoStatic(dom_t=dom_t, seg_exist0=seg_sum(add, dom_t, vd, axis_name))


# Domain axes up to this size take the one-hot form of seg_sum/seg_read;
# past it a [..., N, Vd] one-hot no longer pays and the scatter/gather stays.
ONEHOT_MAX_VD = 256


def seg_mode(vd: int) -> str:
    """The form seg_sum/seg_read take at domain axis ``vd``: "onehot" or
    "scatter" (a trace-time choice; the scheduler reports it on its
    dispatch span)."""
    return "onehot" if vd <= ONEHOT_MAX_VD else "scatter"


def seg_sum(values: jax.Array, dom: jax.Array, vd: int, axis_name=None):
    """[..., N] int32 values segment-summed by [..., N] domain ids →
    [..., Vd] (psum'd global under shard_map). Ids outside [0, vd) are
    dropped, as a scatter drops them.

    For compact domains the sum is a compare-select-reduce against the
    domain one-hot, which XLA fuses into one reduction (the one-hot is never
    materialised): TPU scatters serialise over their updates and carry a
    fixed overhead each, in the scan steps and the speculative rounds alike.
    The sum stays in int32, so counts are exact for any int32 inputs — no
    bf16 rounding of per-node counts above 256."""
    if seg_mode(vd) == "onehot":
        hit = dom[..., None] == jnp.arange(vd, dtype=dom.dtype)
        seg = jnp.sum(jnp.where(hit, values[..., None], 0), axis=-2,
                      dtype=values.dtype)
    else:
        lead, n = dom.shape[:-1], dom.shape[-1]
        rows = math.prod(lead)
        r_iota = jnp.arange(rows, dtype=jnp.int32)[:, None]
        flat = jnp.broadcast_to(values, dom.shape).reshape(rows, n)
        seg = jnp.zeros((rows, vd), values.dtype).at[
            r_iota, dom.reshape(rows, n)].add(flat).reshape(*lead, vd)
    return _gsum(seg, axis_name)


def seg_read(seg: jax.Array, dom: jax.Array) -> jax.Array:
    """Per-node read back of a segment table: seg [..., Vd] at each node's
    domain id dom [..., N] → [..., N] (seg's own dtype). Same one-hot rule
    as seg_sum; an id outside [0, Vd) reads 0 in the one-hot form."""
    vd = seg.shape[-1]
    if seg_mode(vd) == "onehot":
        hit = dom[..., None] == jnp.arange(vd, dtype=dom.dtype)
        return jnp.sum(jnp.where(hit, seg[..., None, :], 0), axis=-1,
                       dtype=seg.dtype)
    return jnp.take_along_axis(seg, dom, axis=-1)


def _seg_counts(sig: jax.Array, key: jax.Array, sel_counts: jax.Array,
                label_val: jax.Array, elig: jax.Array, vd: int, axis_name):
    """Shared segment reduction: per-domain sums of sel_counts[sig] over
    eligible nodes. sig/key [C]; elig [C, N] or [N]. Returns (dom [C,N],
    has_key [C,N], seg [C,Vd] global, cnt_at [C,N])."""
    dom = label_val[:, key].T                                          # [C, N]
    has_key = dom > 0
    if elig.ndim == 1:
        elig = jnp.broadcast_to(elig[None, :], dom.shape)
    cnts = sel_counts[sig]                                             # [C, N]
    # nodes lacking the topology key are never counted (the reference skips
    # them: tv == None). Keeps segment column 0 empty so whole-table sums
    # (the first-pod-in-cluster check) match the oracle.
    add = jnp.where(elig & has_key, cnts, 0)
    seg = seg_sum(add, dom, vd, axis_name)
    cnt_at = seg_read(seg, dom)                                        # [C, N]
    return dom, has_key, seg, cnt_at


# ----------------------------------------------------------------- filters


def spread_filter(xs, sel_counts, label_val, valid, affinity_ok, vd, axis_name):
    """PodTopologySpread Filter (filtering.go:335): per DoNotSchedule
    constraint, matchNum + selfMatch − minMatchNum ≤ maxSkew over domains of
    eligible nodes (nodes matching the pod's node affinity AND carrying every
    constraint's topology key). Returns [N] bool."""
    sf_valid, sf_sig, sf_key, sf_skew, sf_self, sf_min_dom = (
        xs["sf_valid"], xs["sf_sig"], xs["sf_key"], xs["sf_skew"], xs["sf_self"], xs["sf_min_domains"],
    )
    dom = label_val[:, sf_key].T                                       # [C, N]
    has_key = dom > 0
    has_all = jnp.all(jnp.where(sf_valid[:, None], has_key, True), axis=0)   # [N]
    elig = valid & affinity_ok & has_all
    _, _, seg, cnt_at = _seg_counts(sf_sig, sf_key, sel_counts, label_val, elig, vd, axis_name)

    pres = seg_sum(jnp.broadcast_to(elig[None, :], dom.shape).astype(jnp.int32),
                   dom, vd, axis_name) > 0                             # [C, Vd]
    minm = jnp.min(jnp.where(pres, seg, INT_MAX), axis=1)              # [C]
    any_pres = jnp.any(pres, axis=1)
    minm = jnp.where(any_pres, minm, 0)
    ndom = jnp.sum(pres, axis=1)
    minm = jnp.where((sf_min_dom >= 0) & (ndom < sf_min_dom), 0, minm)

    ok_c = has_key & (cnt_at + sf_self[:, None].astype(jnp.int32) - minm[:, None] <= sf_skew[:, None])
    return jnp.all(jnp.where(sf_valid[:, None], ok_c, True), axis=0)


def ipa_filter(xs, sel_counts, seg_exist, dom_t, label_val, valid, vd, axis_name):
    """InterPodAffinity Filter's three checks (filtering.go:377-387).
    Returns (aff_ok, anti_ok, exist_ok, exist_at) — exist_at [T, N] is the
    per-node existing-term domain count matrix, reused by the score path."""
    # 1. incoming pod's required affinity (+ first-pod-in-cluster case)
    ia_valid, ia_sig, ia_key = xs["ia_valid"], xs["ia_sig"], xs["ia_key"]
    _, has_key, seg, cnt_at = _seg_counts(ia_sig, ia_key, sel_counts, label_val, valid, vd, axis_name)
    # reference counts only pods on nodes that carry the key (tv != None)
    exist = cnt_at > 0
    pods_exist = jnp.all(jnp.where(ia_valid[:, None], exist, True), axis=0)
    all_keys = jnp.all(jnp.where(ia_valid[:, None], has_key, True), axis=0)
    total = jnp.sum(jnp.where(ia_valid[:, None], seg, 0))
    first_ok = (total == 0) & xs["ia_self_all"]
    has_terms = jnp.any(ia_valid)
    aff_ok = ~has_terms | (all_keys & (pods_exist | first_ok))

    # 2. incoming pod's required anti-affinity
    an_valid, an_sig, an_key = xs["ianti_valid"], xs["ianti_sig"], xs["ianti_key"]
    _, an_has_key, _, an_cnt = _seg_counts(an_sig, an_key, sel_counts, label_val, valid, vd, axis_name)
    viol = jnp.any(an_valid[:, None] & an_has_key & (an_cnt > 0), axis=0)
    anti_ok = ~viol

    # 3. existing pods' required anti-affinity vs the incoming pod
    exist_at = jnp.where(dom_t > 0, seg_read(seg_exist, dom_t), 0)     # [T, N]
    viol_cnt = jnp.einsum("t,tn->n", xs["term_filter_match"].astype(jnp.int32), exist_at)
    exist_ok = viol_cnt == 0
    return aff_ok, anti_ok, exist_ok, exist_at


# ----------------------------------------------------------------- scores


def spread_score(xs, sel_counts, label_val, valid, affinity_ok, feasible, vd, axis_name):
    """PodTopologySpread Score+Normalize (scoring.go:196-271). Returns [N]
    normalized float scores (ignored/infeasible nodes 0)."""
    ss_valid, ss_sig, ss_key, ss_skew, ss_host = (
        xs["ss_valid"], xs["ss_sig"], xs["ss_key"], xs["ss_skew"], xs["ss_hostname"],
    )
    require_all = xs["ss_require_all"]
    has_cons = jnp.any(ss_valid)

    dom = label_val[:, ss_key].T                                       # [C, N]
    has_key = dom > 0
    has_all = jnp.all(jnp.where(ss_valid[:, None], has_key, True), axis=0)
    ignored = require_all & ~has_all                                   # [N]
    base = feasible & ~ignored

    # domain sizes over filtered non-ignored nodes; hostname uses node count
    pres = seg_sum(jnp.broadcast_to(base[None, :], dom.shape).astype(jnp.int32),
                   dom, vd, axis_name) > 0
    sz = jnp.sum(pres, axis=1)                                          # [C]
    n_base = _gsum(jnp.sum(base.astype(jnp.int32)), axis_name)
    sz = jnp.where(ss_host, n_base, sz)
    w = jnp.log(sz.astype(jnp.float32) + 2.0)                           # [C]

    # counts over eligible nodes (affinity match + require-all key rule)
    elig = valid & affinity_ok & jnp.where(require_all, has_all, True)
    _, _, _, cnt_at = _seg_counts(ss_sig, ss_key, sel_counts, label_val, elig, vd, axis_name)
    cnt = jnp.where(ss_host[:, None], sel_counts[ss_sig], cnt_at).astype(jnp.float32)

    contrib = jnp.where(
        ss_valid[:, None] & has_key,
        cnt * w[:, None] + (ss_skew[:, None].astype(jnp.float32) - 1.0),
        0.0,
    )
    raw = jnp.floor(jnp.sum(contrib, axis=0) + 0.5)                     # math.Round, ≥0

    mx = _gmax(jnp.max(jnp.where(base, raw, -jnp.inf)), axis_name)
    mn = _gmin(jnp.min(jnp.where(base, raw, jnp.inf)), axis_name)
    any_base = _gmax(jnp.any(base), axis_name)
    norm = jnp.where(
        mx == 0, 100.0, jnp.floor(100.0 * (mx + mn - raw) / jnp.maximum(mx, 1.0))
    )
    norm = jnp.where(ignored | ~any_base, 0.0, norm)
    return jnp.where(has_cons, norm, 0.0)


def ipa_score(xs, sel_counts, exist_at, label_val, valid, feasible, vd, axis_name):
    """InterPodAffinity Score+Normalize (scoring.go): incoming preferred terms
    vs existing pods + symmetric existing-term weights, normalized over the
    feasible set with min/max floored/ceiled at 0. Returns [N] float."""
    ip_valid, ip_sig, ip_key, ip_w = xs["ip_valid"], xs["ip_sig"], xs["ip_key"], xs["ip_w"]
    _, has_key, _, cnt_at = _seg_counts(ip_sig, ip_key, sel_counts, label_val, valid, vd, axis_name)
    pref = jnp.sum(
        jnp.where(ip_valid[:, None] & has_key, ip_w[:, None].astype(jnp.float32) * cnt_at, 0.0),
        axis=0,
    )
    sym = jnp.einsum("t,tn->n", xs["term_score_w"], exist_at.astype(jnp.float32))
    raw = pref + sym

    mx = jnp.maximum(_gmax(jnp.max(jnp.where(feasible, raw, -jnp.inf)), axis_name), 0.0)
    mn = jnp.minimum(_gmin(jnp.min(jnp.where(feasible, raw, jnp.inf)), axis_name), 0.0)
    diff = mx - mn
    return jnp.where(diff > 0, jnp.floor(100.0 * (raw - mn) / jnp.maximum(diff, 1.0)), 0.0)


# ----------------------------------------------------------------- commit


# ------------------------------------------------------- hostname fast path
#
# ``kubernetes.io/hostname`` is the dominant topology key in the reference's
# benchmark configs (SchedulingPodAntiAffinity/Affinity,
# performance-config.yaml:23-50) and it degenerates: every node is its own
# domain, so the [C, Vd] segment scatters collapse to direct per-node count
# reads. A batch whose every involved key is hostname takes these paths —
# no scatters in the scan at all (measured 1.4s → ~0.1s per 512-pod batch
# on v5e).


def spread_filter_host(xs, sel_counts, hostkey_ok, valid, affinity_ok, axis_name):
    """Spread filter with hostname domains: matchNum at node n is simply
    sel_counts[sig, n]; minMatchNum is the min over eligible nodes."""
    sf_valid, sf_sig, sf_skew, sf_self, sf_min_dom = (
        xs["sf_valid"], xs["sf_sig"], xs["sf_skew"], xs["sf_self"], xs["sf_min_domains"],
    )
    elig = valid & affinity_ok & hostkey_ok
    cnt = sel_counts[sf_sig]                                           # [C, N]
    minm = _gmin(jnp.min(jnp.where(elig[None, :], cnt, INT_MAX), axis=1), axis_name)
    ndom = _gsum(jnp.sum(elig.astype(jnp.int32)), axis_name)
    any_pres = ndom > 0
    minm = jnp.where(any_pres, minm, 0)
    minm = jnp.where((sf_min_dom >= 0) & (ndom < sf_min_dom), 0, minm)
    ok_c = hostkey_ok[None, :] & (
        cnt + sf_self[:, None].astype(jnp.int32) - minm[:, None] <= sf_skew[:, None])
    return jnp.all(jnp.where(sf_valid[:, None], ok_c, True), axis=0)


def ipa_filter_host(xs, sel_counts, term_cnt, hostkey_ok, valid, axis_name):
    """InterPodAffinity filter with hostname domains: cnt_at == the node's
    own sel_counts row; exist_at == the carried per-node term counts."""
    ia_valid, ia_sig = xs["ia_valid"], xs["ia_sig"]
    cnt_at = sel_counts[ia_sig]                                        # [A, N]
    exist = hostkey_ok[None, :] & (cnt_at > 0)
    pods_exist = jnp.all(jnp.where(ia_valid[:, None], exist, True), axis=0)
    all_keys = jnp.all(jnp.where(ia_valid[:, None], hostkey_ok[None, :], True), axis=0)
    total = _gsum(jnp.sum(jnp.where(ia_valid[:, None] & valid[None, :] & hostkey_ok[None, :],
                                    cnt_at, 0)), axis_name)
    first_ok = (total == 0) & xs["ia_self_all"]
    has_terms = jnp.any(ia_valid)
    aff_ok = ~has_terms | (all_keys & (pods_exist | first_ok))

    an_valid, an_sig = xs["ianti_valid"], xs["ianti_sig"]
    an_cnt = sel_counts[an_sig]                                        # [A, N]
    viol = jnp.any(an_valid[:, None] & hostkey_ok[None, :] & (an_cnt > 0), axis=0)
    anti_ok = ~viol

    exist_at = jnp.where(hostkey_ok[None, :], term_cnt, 0)             # [T, N]
    viol_cnt = jnp.einsum("t,tn->n", xs["term_filter_match"].astype(jnp.int32), exist_at)
    exist_ok = viol_cnt == 0
    return aff_ok, anti_ok, exist_ok, exist_at


def spread_score_host(xs, sel_counts, hostkey_ok, valid, affinity_ok, feasible, axis_name):
    """Spread score with hostname domains (scoring.go:196-271): size = count
    of non-ignored nodes, counts read directly per node."""
    ss_valid, ss_sig, ss_skew = xs["ss_valid"], xs["ss_sig"], xs["ss_skew"]
    require_all = xs["ss_require_all"]
    has_cons = jnp.any(ss_valid)
    ignored = require_all & ~hostkey_ok
    base = feasible & ~ignored
    n_base = _gsum(jnp.sum(base.astype(jnp.int32)), axis_name)
    w = jnp.log(n_base.astype(jnp.float32) + 2.0)
    cnt = sel_counts[ss_sig].astype(jnp.float32)                        # [C, N]
    contrib = jnp.where(
        ss_valid[:, None] & hostkey_ok[None, :],
        cnt * w + (ss_skew[:, None].astype(jnp.float32) - 1.0),
        0.0,
    )
    raw = jnp.floor(jnp.sum(contrib, axis=0) + 0.5)
    mx = _gmax(jnp.max(jnp.where(base, raw, -jnp.inf)), axis_name)
    mn = _gmin(jnp.min(jnp.where(base, raw, jnp.inf)), axis_name)
    any_base = _gmax(jnp.any(base), axis_name)
    norm = jnp.where(mx == 0, 100.0, jnp.floor(100.0 * (mx + mn - raw) / jnp.maximum(mx, 1.0)))
    norm = jnp.where(ignored | ~any_base, 0.0, norm)
    return jnp.where(has_cons, norm, 0.0)


def ipa_score_host(xs, sel_counts, exist_at, hostkey_ok, feasible, axis_name):
    ip_valid, ip_sig, ip_w = xs["ip_valid"], xs["ip_sig"], xs["ip_w"]
    cnt_at = sel_counts[ip_sig]                                         # [PT, N]
    pref = jnp.sum(
        jnp.where(ip_valid[:, None] & hostkey_ok[None, :],
                  ip_w[:, None].astype(jnp.float32) * cnt_at.astype(jnp.float32), 0.0),
        axis=0,
    )
    sym = jnp.einsum("t,tn->n", xs["term_score_w"], exist_at.astype(jnp.float32))
    raw = pref + sym
    mx = jnp.maximum(_gmax(jnp.max(jnp.where(feasible, raw, -jnp.inf)), axis_name), 0.0)
    mn = jnp.minimum(_gmin(jnp.min(jnp.where(feasible, raw, jnp.inf)), axis_name), 0.0)
    diff = mx - mn
    return jnp.where(diff > 0, jnp.floor(100.0 * (raw - mn) / jnp.maximum(diff, 1.0)), 0.0)


def commit_update_host(sel_counts, term_cnt, local_idx, commit, mine,
                       pod_sig_mask, pod_term_mask):
    """Hostname-mode commit: both tables are [*, N] and take a single-column
    add at the winning node — no domain broadcast needed (each shard owns
    its columns). One-hot elementwise instead of scatters (per-step scatter
    overhead, see seg_sum)."""
    col = ((jnp.arange(sel_counts.shape[1], dtype=jnp.int32) == local_idx)
           & commit & mine).astype(jnp.int32)                           # [N]
    sel_counts = sel_counts + pod_sig_mask.astype(jnp.int32)[:, None] * col[None, :]
    term_cnt = term_cnt + pod_term_mask.astype(jnp.int32)[:, None] * col[None, :]
    return sel_counts, term_cnt


def commit_update(sel_counts, seg_exist, dom_t, local_idx, commit, mine,
                  pod_sig_mask, pod_term_mask, axis_name):
    """Apply a committed pod's membership to the evolving count tables:
    sel_counts[:, node] += pod_sig_mask on the owning shard; seg_exist gets the
    pod's carried terms added at the winning node's domains on EVERY shard
    (replicated table — the winner broadcasts its domain column via psum)."""
    col = ((jnp.arange(sel_counts.shape[1], dtype=jnp.int32) == local_idx)
           & commit & mine).astype(jnp.int32)                           # [N]
    sel_counts = sel_counts + pod_sig_mask.astype(jnp.int32)[:, None] * col[None, :]
    dom_col = dom_t[:, local_idx]                                       # [T] local
    if axis_name is not None:
        dom_col = _gsum(jnp.where(mine, dom_col, 0), axis_name)
    add = jnp.where(commit & (dom_col > 0), pod_term_mask.astype(jnp.int32), 0)
    # elementwise one-hot add instead of a scatter (fuses; scatters carry
    # ~200µs fixed overhead per scan step on TPU)
    vd = seg_exist.shape[1]
    onehot = (jnp.arange(vd, dtype=dom_col.dtype)[None, :] == dom_col[:, None])
    seg_exist = seg_exist + add[:, None] * onehot.astype(jnp.int32)
    return sel_counts, seg_exist
