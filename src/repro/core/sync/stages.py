"""Stage library: the four phases every synchronization operator shares.

A round of any operator factors into

* **trigger**   — should the sync machinery run at all? (cadence ``t % b``,
                  sigma_Delta's divergence condition, or the bounded-
                  staleness counters)
* **cohort**    — WHO participates: everyone reachable, a random
                  C-fraction, the balancing augmentation's growing set, or
                  a neighborhood mixing matrix — all availability-masked
* **aggregate** — WHAT they agree on: masked (weighted) mean, or one
                  Metropolis–Hastings mixing step
* **commit**    — APPLY and ACCOUNT: per-learner select, reference /
                  violation-counter updates, CommRecord math, per-link
                  transfer and control-message counts (the bytes ledger's
                  inputs)

The first half of this module is the arithmetic library — pure functions
of scalars and pytrees, kept expression-for-expression identical to the
pre-kernel monoliths so any composition of them reproduces the PR-2
engine bitwise (pinned by ``tests/golden_pr2_engine.json``). The second
half registers the built-in stages into the named registries
(``repro.core.sync.registry``) under the contracts a ``ProtocolSpec``
composes; the six preset protocols in ``kernel.py`` are nothing but
spec-level wirings of these registrations.

Under the plane layouts (``layout="flat"``/``"sharded"``) the stages
run on the *blocked fleet plane*: the stacked leaves themselves, each
block ``(m, *leaf_shape)`` pinned on its learner axis
(``repro.core.shard.constrain_rows``, the identity off-mesh) and carried
as ``ctx.stacked`` with ``ctx.plane`` set; a row — the reference, an
aggregate — is the model pytree. The leaves are never concatenated into
one ``(m, P)`` matrix: on TPU the ``(8, 128)`` tiling of such a matrix
puts eight learners in each tile while a block keeps each learner's slab
contiguous, so every conversion between the two is a relayout of the
whole fleet. Distances, means, commits and gossip's mixing step are
therefore the same per-leaf expressions on every layout (XLA fuses the
distance reduction into the local update's last write of each block).
``ctx.plane`` selects the plane's one algorithm of its own: the
balancing augmentation keeps an incremental running row sum, so each
iteration costs O(P) instead of a full O(m*P) fleet re-aggregation.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.divergence import (
    per_learner_sq_distance, sq_distance, tree_mean, tree_weighted_mean,
    tree_weighted_sum,
)
from repro.core.sync.registry import (
    CohortOut, CommRecord, StageContract, StageCtx, SyncOut, carried_v,
    register_aggregate, register_cohort, register_commit, register_trigger,
)


# ---------------------------------------------------------------------------
# shared pytree helpers
# ---------------------------------------------------------------------------

def num_learners(stacked) -> int:
    return jax.tree.leaves(stacked)[0].shape[0]


def zeros_i32(m: int) -> jnp.ndarray:
    return jnp.zeros((m,), jnp.int32)


def tree_select(mask, new, old):
    """Per-learner select: leaf (m, ...) <- new where mask[i] else old."""
    def sel(n, o):
        m = mask.reshape((-1,) + (1,) * (n.ndim - 1))
        return jnp.where(m, n, o)
    return jax.tree.map(sel, new, old)


def broadcast_model(model, m: int):
    """Replicate a single-model pytree along a fresh leading learner axis."""
    return jax.tree.map(lambda x: jnp.broadcast_to(x[None], (m,) + x.shape),
                        model)


# stage-internal helpers ------------------------------------------------------

def _ref_dists(ctx):
    """(m,) f32 squared distances of the configuration to the reference."""
    return per_learner_sq_distance(ctx.stacked, ctx.state.ref)


def _select_commit(ctx, mask, mean):
    """Cohort members adopt the aggregate: one per-learner select per
    leaf, on whichever configuration the round carries."""
    return commit_select(ctx.stacked, mask, mean)


def _ref_if_commit(ctx, moved, mean):
    return commit_ref_if(moved, mean, ctx.state.ref)


def _cond_dists(ctx):
    """The (m,) distances a conditional trigger already computed this
    round (``StageCtx.cond_aux``), or None."""
    if isinstance(ctx.cond_aux, dict):
        return ctx.cond_aux.get("dists")
    return None


# ---------------------------------------------------------------------------
# trigger arithmetic
# ---------------------------------------------------------------------------

def cadence_fire(b: int, t) -> jnp.ndarray:
    """The schedule half of every trigger: sync machinery runs when
    ``t % b == 0``."""
    return (t % b) == 0


def divergence_trigger(delta: float, stacked, ref, reach):
    """sigma_Delta's condition half: which reachable learners violate
    ``||f_i - r||^2 > Delta``. Returns ``(dists, violated, nviol)`` — the
    distances double as the balancing cohort's augmentation priority."""
    dists = per_learner_sq_distance(stacked, ref)
    violated = (dists > delta) & reach
    return dists, violated, jnp.sum(violated).astype(jnp.int32)


# ---------------------------------------------------------------------------
# cohort arithmetic
# ---------------------------------------------------------------------------

def cohort_all(m: int, active: Optional[jnp.ndarray]) -> jnp.ndarray:
    """sigma_b's cohort: every reachable learner."""
    return jnp.ones((m,), bool) if active is None else active


def cohort_fraction_ideal(sub, m: int, k: int) -> jnp.ndarray:
    """FedAvg's cohort on an ideal network: a uniform random k-subset."""
    perm = jax.random.permutation(sub, m)
    return jnp.zeros((m,), bool).at[perm[:k]].set(True)


def cohort_fraction_masked(sub, m: int, k: int, active) -> jnp.ndarray:
    """FedAvg's cohort under availability: rank the reachable learners by
    a fresh uniform draw and take the first min(k, |active|) — the same
    C-fraction target, restricted to whoever answered this round."""
    r = jax.random.uniform(sub, (m,))
    ranks = jnp.argsort(jnp.argsort(jnp.where(active, r, -jnp.inf)))
    return (ranks >= m - jnp.minimum(k, jnp.sum(active))) & active


def cohort_balanced(delta: float, augmentation: str, stacked, ref, violated,
                    rng, weights=None, reach=None, dists=None):
    """sigma_Delta's cohort: coordinator balancing. Augment the violator
    set B until the partial average re-enters the safe zone
    ``||mean_B - r||^2 <= Delta`` or B covers every REACHABLE learner
    (B = [m] on an ideal network).

    This is the one stage where cohort and aggregate iterate together —
    each augmentation step re-aggregates to test the safe zone — so it
    returns both ``(mask B, mean_B)``. The caller derives poll counts from
    the mask: it is the single source of truth for who the coordinator
    contacted. ``dists`` accepts the (m,) distances the divergence
    trigger already computed this round (the augmentation priority), so a
    round never pays for the monitoring pass twice.
    """
    m = num_learners(stacked)
    if reach is None:
        reach = jnp.ones((m,), bool)
    if dists is None:
        dists = per_learner_sq_distance(stacked, ref)  # (m,) — priority

    if augmentation == "random":
        prio = jax.random.uniform(rng, (m,))
    elif augmentation == "max_distance":
        prio = dists
    else:  # "all": jump straight to full sync on any violation
        prio = jnp.full((m,), jnp.inf)

    def mean_dist(mask):
        mean = aggregate_mean(stacked, mask, weights)
        d = sum(
            jnp.sum(jnp.square(a.astype(jnp.float32) - b.astype(jnp.float32)))
            for a, b in zip(jax.tree.leaves(mean), jax.tree.leaves(ref)))
        return mean, d

    if augmentation == "all":
        mean = aggregate_mean(stacked, reach, weights)
        return reach, mean

    _, d0 = mean_dist(violated)

    def cond(carry):
        mask, d = carry
        return jnp.logical_and(jnp.any(reach & ~mask), d > delta)

    def body(carry):
        mask, _ = carry
        cand = jnp.where(mask | ~reach, -jnp.inf, prio)
        nxt = jnp.argmax(cand)
        mask = mask.at[nxt].set(True)
        _, d = mean_dist(mask)
        return mask, d

    mask, _ = jax.lax.while_loop(cond, body, (violated, d0))
    mean = aggregate_mean(stacked, mask, weights)
    return mask, mean


def cohort_balanced_flat(delta: float, augmentation: str, X, ref, violated,
                         rng, weights=None, reach=None, dists=None):
    """The balancing augmentation on the blocked fleet-plane, with an
    INCREMENTAL running sum: the loop carries ``(sum_B, wsum_B)`` and each
    augmentation step adds one row (``sum += w[nxt] * X[nxt]``, block by
    block) and tests the safe zone on ``||sum/wsum - r||^2`` — O(P) per
    iteration, so the whole balancing pass is O(m*P) instead of the tree
    layout's O(m^2*P) worst case (a full fleet re-aggregation per step).

    Same contract as ``cohort_balanced``: returns ``(mask B, mean_B)``
    with the final mean recomputed as the aggregate stage's masked mean,
    not the running sum's association."""
    m = num_learners(X)
    if reach is None:
        reach = jnp.ones((m,), bool)

    if augmentation == "all":   # jump straight to full sync: no priority
        return reach, aggregate_mean(X, reach, weights)

    if augmentation == "random":
        prio = jax.random.uniform(rng, (m,))
    else:  # "max_distance"
        prio = (per_learner_sq_distance(X, ref) if dists is None
                else dists)

    w = (weights.astype(jnp.float32) if weights is not None
         else jnp.ones((m,), jnp.float32))

    def safe_dist(s, ws):
        denom = jnp.where(ws > 0, ws, jnp.ones_like(ws))
        return sq_distance(
            jax.tree.map(lambda a: a / denom.astype(a.dtype), s), ref)

    w0 = violated.astype(jnp.float32) * w
    s0 = tree_weighted_sum(X, w0)
    ws0 = jnp.sum(w0)

    def cond(carry):
        mask, _, _, d = carry
        return jnp.logical_and(jnp.any(reach & ~mask), d > delta)

    def body(carry):
        mask, s, ws, _ = carry
        cand = jnp.where(mask | ~reach, -jnp.inf, prio)
        nxt = jnp.argmax(cand)
        mask = mask.at[nxt].set(True)
        s = jax.tree.map(
            lambda a, x: a + w[nxt].astype(a.dtype) * x[nxt].astype(a.dtype),
            s, X)
        ws = ws + w[nxt]
        return mask, s, ws, safe_dist(s, ws)

    mask, _, _, _ = jax.lax.while_loop(
        cond, body, (violated, s0, ws0, safe_dist(s0, ws0)))
    return mask, aggregate_mean(X, mask, weights)


def cohort_neighborhood(m: int, active: Optional[jnp.ndarray], adjacency):
    """Gossip's cohort: the availability-masked peer overlay plus its
    Metropolis–Hastings mixing matrix
        W_ij = 1 / (1 + max(deg_i, deg_j))   for active edges i~j
        W_ii = 1 - sum_j W_ij
    which is doubly stochastic for a symmetric adjacency, so the
    configuration mean is preserved. Unreachable (or isolated) learners
    have W row e_i and keep their model bitwise. Returns ``(A, W)``."""
    act = jnp.ones((m,), bool) if active is None else active
    A = (jnp.asarray(adjacency, bool) & act[None, :] & act[:, None]
         & ~jnp.eye(m, dtype=bool))
    deg = jnp.sum(A, axis=1).astype(jnp.float32)
    W = jnp.where(A, 1.0 / (1.0 + jnp.maximum(deg[:, None], deg[None, :])),
                  0.0)
    W = W + jnp.diag(1.0 - jnp.sum(W, axis=1))
    return A, W


# ---------------------------------------------------------------------------
# aggregate arithmetic
# ---------------------------------------------------------------------------

def aggregate_mean(stacked, mask, weights=None):
    """Mean of the masked subset of learners (optionally B^i-weighted).
    An empty mask yields the zero model (``tree_weighted_mean`` guards the
    0/0) — commits keep the previous configuration via their selects."""
    w = mask.astype(jnp.float32)
    if weights is not None:
        w = w * weights.astype(jnp.float32)
    return tree_weighted_mean(stacked, w)


def aggregate_mean_ideal(stacked, m: int, weights=None):
    """The ideal-network (no-mask) aggregate: ``tree_mean`` unweighted —
    the exact expression the pre-network engine used, preserved for the
    bitwise regression — or the all-ones weighted mean."""
    if weights is None:
        return tree_mean(stacked)
    return aggregate_mean(stacked, jnp.ones((m,), bool), weights)


def aggregate_mix(stacked, W):
    """One mixing step: every learner's model becomes its W-row combination
    of the neighborhood's models. The matmul runs in the promoted
    accumulation dtype (at least f32) and narrows back to the leaf dtype —
    the float32 Metropolis–Hastings weights are never downcast to a
    sub-f32 leaf dtype (f32 leaves: expression-identical to the goldens)."""
    def mix(x):
        acc = jnp.promote_types(x.dtype, jnp.float32)
        return jnp.tensordot(W.astype(acc), x.astype(acc),
                             axes=1).astype(x.dtype)
    return jax.tree.map(mix, stacked)


# ---------------------------------------------------------------------------
# commit arithmetic
# ---------------------------------------------------------------------------

def commit_select(stacked, mask, mean):
    """Cohort members adopt the aggregate; everyone else keeps their model."""
    m = num_learners(stacked)
    return tree_select(mask, broadcast_model(mean, m), stacked)


def commit_ref_if(moved, mean, ref):
    """Reference update gated on a scalar condition (``periodic``/``fedavg``:
    anyone averaged; ``dynamic``: the sync covered every reachable
    learner)."""
    return jax.tree.map(lambda a, b: jnp.where(moved, a, b), mean, ref)


def xfers_cohort(mask) -> jnp.ndarray:
    """Coordinator-link transfer counts: each cohort member's uplink
    carries its model up and the aggregate back down (2 per member), so
    ``sum(xfers) == model_up + model_down``."""
    return mask.astype(jnp.int32) * 2


def xfers_neighborhood(A) -> jnp.ndarray:
    """Gossip transfer counts: every exchanged model occupies the links of
    BOTH endpoints, so ``sum(xfers) == 2 * (model_up + model_down)``."""
    return (2 * jnp.sum(A, axis=1)).astype(jnp.int32)


# ===========================================================================
# registered stages: the built-in entries of the four registries
# ===========================================================================

def _validate_b(params):
    b = params["b"]
    if not (isinstance(b, int) and b >= 1):
        raise ValueError(f"cadence period b must be an int >= 1, got {b!r}")


def _validate_delta(params):
    _validate_b(params)
    if not params["delta"] > 0:
        raise ValueError(
            f"divergence threshold delta must be > 0, got {params['delta']!r}")


def _validate_fraction(params):
    if not 0.0 < params["fedavg_c"] <= 1.0:
        raise ValueError(
            f"fedavg_c must be in (0, 1], got {params['fedavg_c']!r}")


def _validate_balanced(params):
    if params["augmentation"] not in ("max_distance", "random", "all"):
        raise ValueError(
            f"augmentation must be max_distance|random|all, "
            f"got {params['augmentation']!r}")
    if not params["delta"] > 0:
        raise ValueError(
            f"balanced cohort needs delta > 0, got {params['delta']!r}")


# ---- triggers -------------------------------------------------------------

@register_trigger("never", contract=StageContract(
    summary="statically-never gate; no state, no condition"))
def trigger_never(ctx: StageCtx):
    """nosync's trigger: the Python constant False — the compiled round
    skips the sync machinery entirely (no ``lax.cond`` is traced)."""
    return False


@register_trigger("cadence", params={"b": 1}, validate=_validate_b,
                  contract=StageContract(
                      summary="scalar bool gate t % b == 0; stateless"))
def trigger_cadence(ctx: StageCtx):
    """sigma_b's trigger: fire every ``b`` rounds, unconditionally."""
    return cadence_fire(ctx.params["b"], ctx.t)


def _divergence_condition(ctx: StageCtx):
    dists, violated, nviol = divergence_trigger(
        ctx.params["delta"], ctx.stacked, ctx.state.ref, ctx.reach)
    # the distances double as the balancing cohort's augmentation
    # priority — thread them so the round pays for the monitoring pass
    # exactly once
    return violated, nviol, {"dists": dists}


@register_trigger("divergence", condition=_divergence_condition,
                  params={"b": 1, "delta": 0.5}, validate=_validate_delta,
                  contract=StageContract(
                      summary="conditional gate; threads the (m,) f32 "
                              "monitoring distances to downstream stages",
                      cond_aux=("dists",)))
def trigger_divergence(ctx: StageCtx):
    """sigma_Delta's trigger: check every ``b`` rounds (the gate); the
    condition marks reachable learners with ``||f_i - r||^2 > Delta``."""
    return cadence_fire(ctx.params["b"], ctx.t)


# ---- cohorts --------------------------------------------------------------

@register_cohort("all_reachable", provides=("full-cohort",),
                 contract=StageContract(
                     summary="(m,) bool mask = reachability; no counter"))
def cohort_all_stage(ctx: StageCtx, hot, nhot, rng) -> CohortOut:
    """sigma_b's cohort: every reachable learner; on the ideal network the
    full fleet (``ideal=True`` keeps the pre-network expressions)."""
    return CohortOut(mask=cohort_all(ctx.m, ctx.active), rng=rng,
                     ideal=ctx.active is None)


@register_cohort("fraction", provides=("subset",),
                 params={"fedavg_c": 0.3}, validate=_validate_fraction,
                 contract=StageContract(
                     summary="(m,) bool random C-fraction; static subset "
                             "size k in aux",
                     aux=("k",)))
def cohort_fraction_stage(ctx: StageCtx, hot, nhot, rng) -> CohortOut:
    """FedAvg's cohort: a random ceil(C*m)-subset, drawn from the
    REACHABLE learners under availability masks."""
    k = max(1, int(round(ctx.params["fedavg_c"] * ctx.m)))
    rng, sub = jax.random.split(rng)
    if ctx.active is None:
        mask = cohort_fraction_ideal(sub, ctx.m, k)
    else:
        mask = cohort_fraction_masked(sub, ctx.m, k, ctx.active)
    return CohortOut(mask=mask, rng=rng, aux={"k": k})


@register_cohort("balanced", provides=("balance",), needs_condition=True,
                 params={"delta": 0.5, "augmentation": "max_distance"},
                 validate=_validate_balanced,
                 contract=StageContract(
                     summary="balancing augmentation; owns the int32 "
                             "violation counter and the full-sync flag",
                     manages_v=True))
def cohort_balanced_stage(ctx: StageCtx, hot, nhot, rng) -> CohortOut:
    """sigma_Delta's cohort: coordinator balancing (Algorithm 1). Owns the
    violation counter: the hot count accumulates into ``v``, ``v >= m``
    forces a full sync, and any sync covering every reachable learner
    resets it."""
    rng, sub = jax.random.split(rng)
    v_new = ctx.state.v + nhot
    # if the counter reaches m, force a sync of every reachable learner
    # and reset it
    force_full = v_new >= ctx.m
    base = jnp.where(force_full, ctx.reach, hot)
    v_reset = jnp.where(force_full, jnp.int32(0), v_new)
    balance = cohort_balanced_flat if ctx.plane else cohort_balanced
    mask, _ = balance(
        ctx.params["delta"], ctx.params["augmentation"], ctx.stacked,
        ctx.state.ref, base, sub, ctx.weights, ctx.reach,
        dists=_cond_dists(ctx))
    full = jnp.all(mask == ctx.reach)
    v_final = jnp.where(full, jnp.int32(0), v_reset)
    return CohortOut(mask=mask, rng=rng, v=v_final, full=full)


@register_cohort("neighborhood", provides=("mixing",), uses_overlay=True,
                 uses_coordinator=False,
                 contract=StageContract(
                     summary="peer overlay cohort: (m, m) bool active "
                             "adjacency A + f32 mixing matrix W in aux",
                     aux=("A", "W")))
def cohort_neighborhood_stage(ctx: StageCtx, hot, nhot, rng) -> CohortOut:
    """Gossip's cohort: the availability-masked peer overlay and its
    Metropolis–Hastings mixing matrix. No coordinator."""
    if ctx.adjacency is None:
        raise ValueError(
            "gossip needs an adjacency matrix — configure a NetworkConfig "
            "topology (the engine passes it through)")
    A, W = cohort_neighborhood(ctx.m, ctx.active, ctx.adjacency)
    return CohortOut(mask=cohort_all(ctx.m, ctx.active), rng=rng,
                     aux={"A": A, "W": W})


# ---- aggregates -----------------------------------------------------------

@register_aggregate("mean", contract=StageContract(
    summary="masked (weighted) cohort mean in the leaf dtypes",
    out="model"))
def aggregate_mean_stage(ctx: StageCtx, cout: CohortOut):
    """Masked (weighted) mean of the cohort; the full-fleet ideal path
    (``cout.ideal``) keeps the pre-network ``tree_mean`` expression
    bitwise. The same per-leaf expressions run on the blocked plane."""
    if cout.ideal:
        return aggregate_mean_ideal(ctx.stacked, ctx.m, ctx.weights)
    return aggregate_mean(ctx.stacked, cout.mask, ctx.weights)


@register_aggregate("mix", needs=("mixing",), contract=StageContract(
    summary="one M-H mixing step: per-learner output, not a single model",
    out="fleet"))
def aggregate_mix_stage(ctx: StageCtx, cout: CohortOut):
    """One Metropolis–Hastings mixing step over the neighborhood: ``W @
    X`` as one tensordot per leaf, on either layout."""
    return aggregate_mix(ctx.stacked, cout.aux["W"])


# ---- commits --------------------------------------------------------------

@register_commit("average", needs=("full-cohort",), contract=StageContract(
    summary="cohort adopts the mean; ref moves when anyone averaged"))
def commit_average(ctx: StageCtx, cout: CohortOut, mean, hot, nhot) -> SyncOut:
    """sigma_b's commit: every cohort member adopts the aggregate; the
    reference moves whenever anybody was actually averaged."""
    m = ctx.m
    if cout.ideal:
        newcfg = broadcast_model(mean, m)
        rec = CommRecord(
            model_up=jnp.int32(m), model_down=jnp.int32(m),
            messages=jnp.int32(0), syncs=jnp.int32(1),
            full_syncs=jnp.int32(1))
        return SyncOut(newcfg, mean, carried_v(ctx, cout), cout.rng,
                       ctx.state.extra, rec, jnp.full((m,), 2, jnp.int32),
                       zeros_i32(m))
    mask = cout.mask
    nsync = jnp.sum(mask).astype(jnp.int32)
    newcfg = _select_commit(ctx, mask, mean)
    # the reference only moves when somebody was actually averaged
    new_ref = _ref_if_commit(ctx, nsync > 0, mean)
    rec = CommRecord(
        model_up=nsync, model_down=nsync, messages=jnp.int32(0),
        syncs=(nsync > 0).astype(jnp.int32),
        # sigma_b always averages every reachable learner
        full_syncs=(nsync > 0).astype(jnp.int32))
    return SyncOut(newcfg, new_ref, carried_v(ctx, cout), cout.rng,
                   ctx.state.extra, rec, xfers_cohort(mask), zeros_i32(m))


@register_commit("subset", needs=("subset",), contract=StageContract(
    summary="subset adopts the mean; full when it covered every "
            "reachable learner"))
def commit_subset(ctx: StageCtx, cout: CohortOut, mean, hot, nhot) -> SyncOut:
    """FedAvg's commit: the subset adopts the aggregate; a sync is "full"
    when the subset covered every reachable learner."""
    m = ctx.m
    mask = cout.mask
    newcfg = _select_commit(ctx, mask, mean)
    if ctx.active is None:
        k = cout.aux["k"]
        rec = CommRecord(
            model_up=jnp.int32(k), model_down=jnp.int32(k),
            messages=jnp.int32(0), syncs=jnp.int32(1),
            full_syncs=jnp.int32(1 if k == m else 0))
        return SyncOut(newcfg, mean, carried_v(ctx, cout), cout.rng,
                       ctx.state.extra, rec, xfers_cohort(mask),
                       zeros_i32(m))
    nsel = jnp.sum(mask).astype(jnp.int32)
    new_ref = _ref_if_commit(ctx, nsel > 0, mean)
    rec = CommRecord(
        model_up=nsel, model_down=nsel, messages=jnp.int32(0),
        syncs=(nsel > 0).astype(jnp.int32),
        # full = the subset covered every reachable learner
        full_syncs=((nsel > 0) & (nsel == jnp.sum(ctx.active)))
        .astype(jnp.int32))
    return SyncOut(newcfg, new_ref, carried_v(ctx, cout), cout.rng,
                   ctx.state.extra, rec, xfers_cohort(mask), zeros_i32(m))


@register_commit("balancing", needs=("balance",), needs_condition=True,
                 contract=StageContract(
                     summary="balanced cohort adopts the partial average; "
                             "per-link chatter on the sending links"))
def commit_balancing(ctx: StageCtx, cout: CohortOut, mean, hot,
                     nhot) -> SyncOut:
    """sigma_Delta's commit: the balanced cohort adopts the partial
    average, the reference moves only on a full sync (Algorithm 1), and
    the per-link chatter is attributed to the links that sent it."""
    mask, full = cout.mask, cout.full
    newcfg = _select_commit(ctx, mask, mean)
    # reference model updates only on full sync (Algorithm 1)
    new_ref = _ref_if_commit(ctx, full, mean)
    nsync = jnp.sum(mask).astype(jnp.int32)
    # every member of the final B that did not itself violate was polled
    # by the coordinator — counting nsync - nhot covers the balancing loop
    # AND the forced-full path (where the balanced cohort starts from an
    # all-true mask). Per link that is one violation notice on each true
    # violator's link and one poll request on each polled member's link,
    # so the ledger sees the same chatter the scalar record counts.
    polls = nsync - nhot
    link_msgs = (hot.astype(jnp.int32) + (mask & ~hot).astype(jnp.int32))
    rec = CommRecord(
        model_up=nsync,          # violators push + coordinator polls
        model_down=nsync,        # partial average pushed back to B
        messages=nhot + polls,   # violation notices + poll requests
        syncs=jnp.int32(1),
        full_syncs=full.astype(jnp.int32))
    return SyncOut(newcfg, new_ref, carried_v(ctx, cout), cout.rng,
                   ctx.state.extra, rec, xfers_cohort(mask), link_msgs)


@register_commit("mix", needs=("mixing",), contract=StageContract(
    summary="every learner adopts its mixing row; transfers occupy both "
            "endpoints' links; the reference never moves"))
def commit_mix(ctx: StageCtx, cout: CohortOut, mixed, hot, nhot) -> SyncOut:
    """Gossip's commit: every learner adopts its mixing-row combination;
    transfers occupy BOTH endpoints' links; the reference never moves
    (there is no coordinator to hold one)."""
    A = cout.aux["A"]
    edges = jnp.sum(A).astype(jnp.int32)           # directed count = 2E
    up = edges // 2
    na = jnp.sum(cout.mask).astype(jnp.int32)
    rec = CommRecord(
        model_up=up, model_down=edges - up,         # == up by symmetry
        messages=jnp.int32(0),
        syncs=(edges > 0).astype(jnp.int32),
        # "all reachable averaged": the active subgraph is complete, so
        # one mixing step couples every reachable learner
        full_syncs=((edges > 0) & (edges == na * (na - 1)))
        .astype(jnp.int32))
    return SyncOut(mixed, ctx.state.ref, carried_v(ctx, cout), cout.rng,
                   ctx.state.extra, rec, xfers_neighborhood(A),
                   zeros_i32(ctx.m))
