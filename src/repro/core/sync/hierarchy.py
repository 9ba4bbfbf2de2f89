"""Two-tier star-of-stars coordination on top of the staged kernel.

The fleet of m learners is partitioned into g contiguous equal clusters
(cluster c owns learners ``c*k .. (c+1)*k-1``, k = m/g). Every round, fully
inside the scanned round body:

1. **intra tier** — the flat protocol (the learner's ``ProtocolConfig``)
   runs *vmapped over clusters*: each cluster has its own reference model,
   violation counter, and RNG (a ``SyncState`` with a leading cluster
   axis), and sees only its members' availability mask. A cluster's
   coordinator is its *edge aggregator*.
2. **edge aggregators** — each aggregator's model is its cluster's
   availability-masked (weighted) mean after the intra step; a cluster is
   reachable at the upper tier iff any member is.
3. **inter tier** — ``HierarchyConfig.inter`` runs the SAME staged kernel
   over the g aggregator models (own cadence/threshold/payload size), with
   per-cluster reference + violation state carried in a second
   ``SyncState``.
4. **commit down** — clusters whose aggregator synchronized push the
   inter-tier adjustment (new minus old aggregate) to their reachable
   members: intra-cluster diversity survives, cluster means move to the
   inter-tier agreement, and each receiving member's link carries one
   model download.

Accounting is exact per tier: member links count intra transfers +
down-pushes + intra control messages (priced at the intra payload size by
the engine's ledger); the g aggregator↔top-coordinator uplinks count the
inter tier's transfers and messages (priced at ``inter.bytes_per_param`` —
a quantized backhaul stays exact). Scalar ``CommRecord`` counts are merged
for reporting, but with mixed payload sizes the ledger — not
``transfers × model_bytes`` — is the source of truth for bytes.

Layout: an intra (or inter) spec with ``layout="flat"`` runs its staged
round on the blocked fleet plane INSIDE this composition with no edits
here — under the intra ``vmap`` each block gains the cluster axis, and
the blocks are the stacked leaves themselves, so the aggregator means,
down-push and per-tier accounting below always see pytrees.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.config import HierarchyConfig, ProtocolConfig
from repro.core.sync import stages
from repro.core.sync.kernel import (
    CommRecord, StageResult, SyncState, apply_staged,
)
from repro.core.sync.spec import ProtocolSpec, resolve_spec


class HierSyncState(NamedTuple):
    intra: SyncState   # leaves carry a leading (g,) cluster axis
    inter: SyncState   # over the g aggregator models


class HierResult(NamedTuple):
    params: object             # (m, ...) committed configuration
    state: HierSyncState
    rec: CommRecord            # merged scalar record (ledger is exact)
    member_xfers: jnp.ndarray  # (m,) models over member links
    member_msgs: jnp.ndarray   # (m,) control messages over member links
    agg_xfers: jnp.ndarray     # (g,) models over aggregator uplinks
    agg_msgs: jnp.ndarray      # (g,) control messages over aggregator uplinks


def validate_hierarchy(tiers: HierarchyConfig, m: int) -> int:
    """Cluster size k, or a clear error when the fleet doesn't partition."""
    g = tiers.num_clusters
    if m % g != 0:
        raise ValueError(
            f"hierarchy needs equal clusters: m={m} learners do not "
            f"partition into num_clusters={g} (m % g == {m % g}). "
            f"Pick g dividing m.")
    return m // g


def init_hier_state(base_model, tiers: HierarchyConfig, seed: int = 0,
                    m: Optional[int] = None,
                    intra_spec: Optional[ProtocolSpec] = None,
                    inter_spec: Optional[ProtocolSpec] = None
                    ) -> HierSyncState:
    """Per-cluster intra states (all clusters start from the shared init)
    plus one inter-tier state over the aggregators. Specs that carry
    extra state (e.g. bounded-staleness counters) get one instance per
    cluster at the intra tier (leading (g,) axis, vmapped with the rest
    of the intra state) and one over the g aggregators at the inter
    tier; ``m`` is required whenever the intra spec carries any."""
    g = tiers.num_clusters

    def extra_for(spec, n):
        if spec is None or not spec.extra_state:
            return {}
        return spec.init_extra(n)

    intra_extra = {}
    if intra_spec is not None and intra_spec.extra_state:
        if m is None:
            raise ValueError(
                "init_hier_state needs the fleet size m to build the "
                f"intra spec's extra state {intra_spec.extra_state}")
        k = validate_hierarchy(tiers, m)
        intra_extra = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (g,) + x.shape).copy(),
            extra_for(intra_spec, k))
    intra = SyncState(
        ref=stages.broadcast_model(base_model, g),
        v=jnp.zeros((g,), jnp.int32),
        rng=jax.random.split(jax.random.PRNGKey(seed ^ 0x417E7), g),
        step=jnp.zeros((g,), jnp.int32),
        extra=intra_extra,
    )
    inter = SyncState(
        ref=base_model,
        v=jnp.zeros((), jnp.int32),
        rng=jax.random.PRNGKey(seed ^ 0x1A7E2),
        step=jnp.zeros((), jnp.int32),
        extra=extra_for(inter_spec, g),
    )
    return HierSyncState(intra=intra, inter=inter)


def apply_hierarchical(cfg: ProtocolConfig, tiers: HierarchyConfig,
                       stacked, hstate: HierSyncState, weights=None,
                       active: Optional[jnp.ndarray] = None) -> HierResult:
    """One hierarchical round: intra tier (vmapped over clusters) →
    aggregators → inter tier → commit down. Pure and jit/scan-compatible;
    ``active`` is the flat (m,) reachability mask."""
    m = stages.num_learners(stacked)
    g = tiers.num_clusters
    k = m // g
    if not resolve_spec(cfg).param("weighted"):
        # same contract as the flat kernel: Algorithm-2 weights only enter
        # (the aggregator means and the inter tier's cluster weights) when
        # the intra config asks for them
        weights = None

    clustered = jax.tree.map(
        lambda x: x.reshape((g, k) + x.shape[1:]), stacked)
    w_gk = weights.reshape(g, k) if weights is not None else None
    act_gk = active.reshape(g, k) if active is not None else None

    # --- 1. intra tier: the flat staged operator, one instance per cluster
    def intra_fn(stk, st, w, act):
        return apply_staged(cfg, stk, st, w, active=act)

    res: StageResult = jax.vmap(
        intra_fn,
        in_axes=(0, 0, 0 if w_gk is not None else None,
                 0 if act_gk is not None else None),
    )(clustered, hstate.intra, w_gk, act_gk)

    # --- 2. edge aggregators: masked cluster means of the post-intra models
    member_mask = (act_gk if act_gk is not None
                   else jnp.ones((g, k), bool))
    if w_gk is not None:
        agg = jax.vmap(stages.aggregate_mean)(res.params, member_mask, w_gk)
        cluster_w = jnp.sum(w_gk, axis=1)
    else:
        agg = jax.vmap(lambda s, msk: stages.aggregate_mean(s, msk))(
            res.params, member_mask)
        cluster_w = None
    agg_active = jnp.any(member_mask, axis=1) if act_gk is not None else None

    # --- 3. inter tier: the same kernel over the g aggregator models.
    # Under Algorithm 2 each aggregator carries its cluster's sampling
    # mass (sum of member B^i): the inter tier MUST weight by it or a full
    # two-hop sync would land on the unweighted mean of cluster means, not
    # the weighted global mean — so the intra tier's weighting turns the
    # inter tier weighted too, whatever tiers.inter.weighted says.
    inter_cfg = tiers.inter
    if cluster_w is not None and not inter_cfg.weighted:
        inter_cfg = dataclasses.replace(inter_cfg, weighted=True)
    inter_res: StageResult = apply_staged(
        inter_cfg, agg, hstate.inter, cluster_w, active=agg_active)

    # --- 4. commit down: clusters that synchronized at the upper tier push
    # the inter-tier adjustment to their reachable members (keeps
    # intra-cluster diversity; moves the cluster mean to the agreement)
    delta = jax.tree.map(lambda a, b: a - b, inter_res.params, agg)
    participated = inter_res.xfers > 0                       # (g,)
    down_mask = participated[:, None] & member_mask          # (g, k)

    def push(c, d):
        dm = down_mask.reshape(down_mask.shape + (1,) * (c.ndim - 2))
        return jnp.where(dm, c + d[:, None], c)

    new_clustered = jax.tree.map(push, res.params, delta)
    n_down = jnp.sum(down_mask).astype(jnp.int32)

    # --- accounting: per-tier link counts + merged scalar record
    member_xfers = (res.xfers + down_mask.astype(jnp.int32)).reshape(m)
    member_msgs = res.link_msgs.reshape(m)
    intra_sum = CommRecord(*(jnp.sum(f).astype(jnp.int32) for f in res.rec))
    rec = CommRecord(
        model_up=intra_sum.model_up + inter_res.rec.model_up,
        model_down=intra_sum.model_down + inter_res.rec.model_down + n_down,
        messages=intra_sum.messages + inter_res.rec.messages,
        syncs=((intra_sum.syncs + inter_res.rec.syncs) > 0)
        .astype(jnp.int32),
        # "full" at the fleet level: the inter tier averaged every
        # reachable aggregator (the hierarchy's analogue of all-reachable)
        full_syncs=inter_res.rec.full_syncs)

    params = jax.tree.map(
        lambda x: x.reshape((m,) + x.shape[2:]), new_clustered)
    return HierResult(
        params=params,
        state=HierSyncState(intra=res.state, inter=inter_res.state),
        rec=rec,
        member_xfers=member_xfers,
        member_msgs=member_msgs,
        agg_xfers=inter_res.xfers,
        agg_msgs=inter_res.link_msgs,
    )
