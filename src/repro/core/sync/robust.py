"""Byzantine-robust synchronization: registered defenses for faulty fleets.

The fault plane (``repro.network.faults``) makes learners crash and
rejoin cold, ship NaN/Inf payloads, or adversarially sign-flip/scale
their updates. Against that, plain ``aggregate_mean`` is defenseless —
one non-finite row poisons the committed configuration AND the
reference model forever, and a single sign-flipper drags the mean far
from the honest fleet. This module lands the defenses as registered
stages — zero kernel/engine edits, the PR-4 contract:

* **robust aggregates** — coordinate-wise ``trimmed_mean`` (drop the
  ``trim_frac`` smallest and largest finite values per coordinate, mean
  the rest) and ``median``. Both are finite-guarded: a NaN/Inf entry is
  simply excluded from its coordinate's order statistics, so corrupted
  payloads cannot poison the aggregate. Both reduce each leaf along its
  learner axis, on every layout, and ignore Algorithm-2 weights by
  design (weighting by self-reported sample counts is itself an attack
  surface — an adversary would just claim the largest B^i).
* **the ``quarantine`` commit** — flags suspect cohort rows (any
  non-finite row, or one whose squared distance to the reference
  exceeds ``quarantine_mult`` x the cohort's finite median distance),
  withholds the aggregate from them, and warm-starts them from the
  reference model instead — the recovery path for crashed learners that
  rejoined cold AND for adversaries (whose rows get forcibly reset
  every sync). Its scalar CommRecord and per-link counts are
  expression-identical to ``commit_average``, so on an honest fleet the
  comm counters stay bitwise vs the ``mean``/``average`` pipeline.
* **robust triggers** — ``robust_cadence`` / ``robust_divergence`` are
  the cadence/divergence triggers plus per-learner health counters in
  ``SyncState.extra``: ``health`` counts CONSECUTIVE quarantined
  commits (reset to zero the first clean commit), ``recovered`` flags
  this round's recovery commits (a previously-quarantined learner whose
  commit came back clean). The engine surfaces them as
  ``num_quarantined``/``num_recovered`` per round.

Pair the quarantine commit with a robust aggregate: the aggregate
excludes bad values from WHAT is agreed on, the commit excludes bad
rows from WHO adopts it and heals them. (Quarantine + plain ``mean``
still warm-starts bad rows, but the mean they do not adopt — and the
reference — can still be dragged or poisoned.)

Presets: ``robust_periodic`` (robust_cadence -> all_reachable ->
trimmed_mean -> quarantine) and ``robust_dynamic`` (the same with the
divergence condition gating syncs). ``hardened(spec)`` rewrites any
cadence/divergence-triggered mean/average spec onto its robust
counterpart, mirroring ``asyncify``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.divergence import _acc_dtype
from repro.core.sync.registry import (
    CohortOut, CommRecord, StageContract, StageCtx, SyncOut, carried_v,
    register_aggregate, register_commit, register_trigger,
)
from repro.core.sync.spec import ProtocolSpec
from repro.core.sync.kernel import register_protocol
from repro.core.sync.stages import (
    _divergence_condition, _ref_dists, _ref_if_commit, _select_commit,
    _validate_b, _validate_delta, broadcast_model, cadence_fire,
    tree_select, xfers_cohort, zeros_i32,
)

# absolute slack on the outlier threshold so a perfectly-converged
# cohort (median distance exactly zero) does not flag honest rows over
# float dust
_SUSPECT_EPS = 1e-12


# ---------------------------------------------------------------------------
# suspect-row detection (shared by the quarantine commit and the robust
# triggers' health counters — XLA CSE dedupes the repeated computation)
# ---------------------------------------------------------------------------

def _finite_rows(ctx: StageCtx) -> jnp.ndarray:
    """(m,) bool — rows whose every parameter is finite."""
    finite = None
    for leaf in jax.tree.leaves(ctx.stacked):
        f = jnp.all(jnp.isfinite(leaf), axis=tuple(range(1, leaf.ndim)))
        finite = f if finite is None else finite & f
    return finite


def _masked_median(x: jnp.ndarray, valid: jnp.ndarray) -> jnp.ndarray:
    """Median of ``x[valid]`` (scalar; 0 when nothing is valid)."""
    order = jnp.sort(jnp.where(valid, x, jnp.inf))
    n = jnp.sum(valid).astype(jnp.int32)
    lo = order[jnp.maximum((n - 1) // 2, 0)]
    hi = order[n // 2]
    return jnp.where(n > 0, 0.5 * (lo + hi), jnp.zeros_like(lo))


def _suspect_rows(ctx: StageCtx, mask: jnp.ndarray) -> jnp.ndarray:
    """(m,) bool — cohort rows the quarantine flags: non-finite, or a
    distance-to-reference outlier (squared distance beyond
    ``quarantine_mult`` x the cohort's finite median). The median keeps
    its robustness as long as suspect rows stay a minority of the
    cohort — at >= 50% adversaries the median itself is captured, the
    classical breakdown point."""
    finite = _finite_rows(ctx)
    d = _ref_dists(ctx)
    med = _masked_median(d, mask & finite)
    far = d > ctx.params["quarantine_mult"] * med + _SUSPECT_EPS
    return mask & (~finite | far)


# ---------------------------------------------------------------------------
# robust triggers: cadence/divergence + per-learner health counters
# ---------------------------------------------------------------------------

_HEALTH_STATE = (("health", "int32"), ("recovered", "int32"))


def _health(ctx: StageCtx):
    if "health" not in ctx.state.extra:
        raise ValueError(
            "the robust triggers carry per-learner health counters in "
            "SyncState.extra['health'/'recovered'] — build the state with "
            "init_state(ref, seed, spec=spec, m=m) (the engine does this "
            "automatically)")
    return ctx.state.extra["health"], ctx.state.extra["recovered"]


def _health_init(params, m: int):
    return {"health": jnp.zeros((m,), jnp.int32),
            "recovered": jnp.zeros((m,), jnp.int32)}


def _health_commit(ctx: StageCtx, mask):
    # ``health``: consecutive commits a learner was quarantined —
    # suspect rows increment, a clean commit resets to zero (that reset
    # IS the recovery: the learner re-adopted the fleet's aggregate),
    # learners outside the cohort keep their count. ``recovered`` marks
    # THIS round's recoveries (previously-quarantined learners whose
    # commit came back clean); it is per-round, not cumulative — an
    # unbounded int32 scan carry is exactly what the jaxpr auditor
    # forbids — so the engine folds the running total host-side, the
    # bytes-ledger pattern.
    h, _ = _health(ctx)
    bad = _suspect_rows(ctx, mask)
    cleared = mask & ~bad
    rec = (cleared & (h > 0)).astype(jnp.int32)
    h = jnp.where(bad, h + 1, jnp.where(cleared, jnp.int32(0), h))
    return {"health": h, "recovered": rec}


def _health_skip(ctx: StageCtx):
    h, _ = _health(ctx)
    return {"health": h, "recovered": jnp.zeros_like(h)}


def _robust_divergence_condition(ctx: StageCtx):
    # sigma_Delta's condition with a finite guard: a NaN distance
    # compares False against delta, so a NaN-corrupted learner would
    # never trip the plain condition and would drift unhealed between
    # cadence-less syncs. Here a reachable row with a non-finite
    # distance IS a violation — corruption forces the sync that
    # quarantines it. (Inf distances already violate; this closes NaN.)
    violated, _, aux = _divergence_condition(ctx)
    violated = violated | (~jnp.isfinite(aux["dists"]) & ctx.reach)
    return violated, jnp.sum(violated).astype(jnp.int32), aux


def _validate_mult(params):
    mult = params["quarantine_mult"]
    if not mult > 1.0:
        raise ValueError(
            f"quarantine_mult must be > 1 (a multiple of the cohort's "
            f"median squared distance), got {mult!r}")


def _validate_robust_cadence(params):
    _validate_b(params)
    _validate_mult(params)


def _validate_robust_divergence(params):
    _validate_delta(params)
    _validate_mult(params)


@register_trigger("robust_cadence", init_extra=_health_init,
                  commit_extra=_health_commit, skip_extra=_health_skip,
                  params={"b": 1, "quarantine_mult": 16.0},
                  validate=_validate_robust_cadence,
                  contract=StageContract(
                      summary="cadence gate + per-learner quarantine "
                              "health counters",
                      extra_state=_HEALTH_STATE))
def trigger_robust_cadence(ctx: StageCtx):
    """sigma_b's schedule with the quarantine health counters carried in
    ``SyncState.extra`` — the robust counterpart of ``cadence``."""
    return cadence_fire(ctx.params["b"], ctx.t)


@register_trigger("robust_divergence",
                  condition=_robust_divergence_condition,
                  init_extra=_health_init, commit_extra=_health_commit,
                  skip_extra=_health_skip,
                  params={"b": 1, "delta": 0.5, "quarantine_mult": 16.0},
                  validate=_validate_robust_divergence,
                  contract=StageContract(
                      summary="divergence condition + per-learner "
                              "quarantine health counters",
                      extra_state=_HEALTH_STATE, cond_aux=("dists",)))
def trigger_robust_divergence(ctx: StageCtx):
    """sigma_Delta's condition with the quarantine health counters — the
    robust counterpart of ``divergence``. The condition doubles as the
    fault alarm: an adversarial or cold-restarted row is far from the
    reference and a corrupted row has a non-finite distance
    (``_robust_divergence_condition``'s finite guard), so either pulls
    the fleet into a (robust) sync instead of drifting unhealed."""
    return cadence_fire(ctx.params["b"], ctx.t)


# ---------------------------------------------------------------------------
# robust aggregates: coordinate-wise trimmed mean and median
# ---------------------------------------------------------------------------

def _rows(v: jnp.ndarray, ndim: int) -> jnp.ndarray:
    """An (m,) vector shaped to broadcast along a block's learner axis."""
    return v.reshape((-1,) + (1,) * (ndim - 1))


def _sorted_valid(X: jnp.ndarray, mask: jnp.ndarray):
    """Per-coordinate ascending sort (along the learner axis 0) of the
    masked FINITE entries (invalid entries pushed to the end as +inf)
    and the count of valid entries per coordinate."""
    valid = _rows(mask, X.ndim) & jnp.isfinite(X)
    order = jnp.sort(jnp.where(valid, X, jnp.inf), axis=0)
    n = jnp.sum(valid, axis=0).astype(jnp.int32)
    return order, n


def flat_trimmed_mean(X: jnp.ndarray, mask: jnp.ndarray,
                      trim_frac: float) -> jnp.ndarray:
    """Coordinate-wise trimmed mean over the masked rows of an (m, ...)
    block: per coordinate, drop the ``floor(trim_frac * n)`` smallest
    and largest finite values and mean the rest. ``trim_frac=0`` is the plain
    finite-guarded mean (to reassociation tolerance: the sum runs in
    sorted order). An all-invalid coordinate yields 0 — commits keep
    the previous configuration via their selects."""
    order, n = _sorted_valid(X, mask)
    k = jnp.floor(trim_frac * n.astype(X.dtype)).astype(jnp.int32)
    idx = _rows(jnp.arange(X.shape[0], dtype=jnp.int32), X.ndim)
    keep = (idx >= k[None]) & (idx < (n - k)[None])
    cnt = jnp.maximum(n - 2 * k, 1).astype(X.dtype)
    out = jnp.sum(jnp.where(keep, order, jnp.zeros_like(order)),
                  axis=0) / cnt
    return jnp.where(n > 0, out, jnp.zeros_like(out))


def flat_median(X: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """Coordinate-wise median over the masked finite entries of an
    (m, ...) block (midpoint of the two central order statistics for
    even counts)."""
    order, n = _sorted_valid(X, mask)
    lo = jnp.take_along_axis(order, jnp.maximum((n - 1) // 2, 0)[None],
                             axis=0)[0]
    hi = jnp.take_along_axis(order, (n // 2)[None], axis=0)[0]
    out = jnp.asarray(0.5, X.dtype) * (lo + hi)
    return jnp.where(n > 0, out, jnp.zeros_like(out))


def _per_coordinate(ctx: StageCtx, fn):
    """A per-coordinate aggregate, leaf by leaf: ``fn`` reduces each
    (m, ...) leaf along its learner axis, with no reshape, in the leaf's
    accumulation dtype (at least f32), narrowed back to the leaf
    dtype."""
    return jax.tree.map(
        lambda x: fn(x.astype(_acc_dtype(x.dtype))).astype(x.dtype),
        ctx.stacked)


def _validate_trim(params):
    tf = params["trim_frac"]
    if not 0.0 <= tf < 0.5:
        raise ValueError(
            f"trim_frac must be in [0, 0.5) — trimming half the cohort "
            f"from each side leaves nothing — got {tf!r}")


@register_aggregate("trimmed_mean", params={"trim_frac": 0.2},
                    validate=_validate_trim,
                    contract=StageContract(
                        summary="coordinate-wise finite-guarded trimmed "
                                "mean; ignores Algorithm-2 weights",
                        out="model"))
def aggregate_trimmed_mean(ctx: StageCtx, cout: CohortOut):
    """Coordinate-wise trimmed mean of the cohort. Robust to
    ``floor(trim_frac * n)`` arbitrary (even non-finite) values per
    coordinate; unweighted by design (see the module docstring)."""
    tf = ctx.params["trim_frac"]
    mask = jnp.ones((ctx.m,), bool) if cout.ideal else cout.mask
    return _per_coordinate(ctx, lambda X: flat_trimmed_mean(X, mask, tf))


@register_aggregate("median", contract=StageContract(
    summary="coordinate-wise finite-guarded median; ignores "
            "Algorithm-2 weights",
    out="model"))
def aggregate_median(ctx: StageCtx, cout: CohortOut):
    """Coordinate-wise median of the cohort — the maximal trim, robust
    up to (but not at) 50% arbitrary values per coordinate."""
    mask = jnp.ones((ctx.m,), bool) if cout.ideal else cout.mask
    return _per_coordinate(ctx, lambda X: flat_median(X, mask))


# ---------------------------------------------------------------------------
# the quarantine commit
# ---------------------------------------------------------------------------

def _quarantine_select(ctx: StageCtx, bad, newcfg):
    """Suspect rows are warm-started from the reference model instead of
    adopting the committed configuration."""
    return tree_select(bad, broadcast_model(ctx.state.ref, ctx.m), newcfg)


@register_commit("quarantine", needs=("full-cohort",),
                 params={"quarantine_mult": 16.0}, validate=_validate_mult,
                 contract=StageContract(
                     summary="cohort adopts the aggregate except suspect "
                             "rows, which warm-start from the reference; "
                             "ledger identical to 'average'"))
def commit_quarantine(ctx: StageCtx, cout: CohortOut, mean, hot,
                      nhot) -> SyncOut:
    """``commit_average`` with a quarantine: suspect cohort rows
    (non-finite or distance outliers, ``_suspect_rows``) do NOT adopt
    the aggregate — they are warm-started from the reference model,
    which both resets adversarial rows every sync and gives a
    cold-restarted learner a live model to rejoin from. The scalar
    CommRecord and per-link counts are expression-identical to
    ``commit_average`` — a quarantined member still shipped its model
    up and got one pushed back down, it just received the reference —
    so honest-fleet comm counters stay bitwise vs the plain pipeline."""
    m = ctx.m
    if cout.ideal:
        bad = _suspect_rows(ctx, jnp.ones((m,), bool))
        newcfg = _quarantine_select(ctx, bad, broadcast_model(mean, m))
        rec = CommRecord(
            model_up=jnp.int32(m), model_down=jnp.int32(m),
            messages=jnp.int32(0), syncs=jnp.int32(1),
            full_syncs=jnp.int32(1))
        return SyncOut(newcfg, mean, carried_v(ctx, cout), cout.rng,
                       ctx.state.extra, rec, jnp.full((m,), 2, jnp.int32),
                       zeros_i32(m))
    mask = cout.mask
    bad = _suspect_rows(ctx, mask)
    nsync = jnp.sum(mask).astype(jnp.int32)
    newcfg = _quarantine_select(ctx, bad, _select_commit(ctx, mask, mean))
    new_ref = _ref_if_commit(ctx, nsync > 0, mean)
    rec = CommRecord(
        model_up=nsync, model_down=nsync, messages=jnp.int32(0),
        syncs=(nsync > 0).astype(jnp.int32),
        full_syncs=(nsync > 0).astype(jnp.int32))
    return SyncOut(newcfg, new_ref, carried_v(ctx, cout), cout.rng,
                   ctx.state.extra, rec, xfers_cohort(mask), zeros_i32(m))


# ---------------------------------------------------------------------------
# hardened(spec): the robust rewriter, mirroring asyncify
# ---------------------------------------------------------------------------

_ROBUST_TRIGGER = {
    "cadence": "robust_cadence",
    "divergence": "robust_divergence",
    "robust_cadence": "robust_cadence",        # idempotent
    "robust_divergence": "robust_divergence",
}

_ROBUST_AGGREGATE = {
    "mean": "trimmed_mean",
    "trimmed_mean": "trimmed_mean",
    "median": "median",
}

_ROBUST_COMMIT = {"average": "quarantine", "quarantine": "quarantine"}


def hardened(spec: ProtocolSpec, *, aggregate=None, trim_frac=None,
             quarantine_mult=None) -> ProtocolSpec:
    """Rewrite ``spec`` onto its Byzantine-robust counterpart: the
    trigger gains the health counters, ``mean`` becomes the robust
    ``aggregate`` (default ``trimmed_mean``), ``average`` becomes
    ``quarantine``. Parameters are preserved; ``trim_frac`` /
    ``quarantine_mult`` override the robust knobs. Raises for
    compositions with no robust counterpart (staleness/events triggers,
    mix/aircomp aggregates, balancing/subset/mix commits) — for a
    divergence-balanced protocol use the ``robust_dynamic`` preset,
    which trades the balancing augmentation for a full robust sync."""
    if spec.trigger not in _ROBUST_TRIGGER:
        raise ValueError(
            f"don't know the robust counterpart of trigger "
            f"{spec.trigger!r} (hardened rewrites: "
            f"{sorted(set(_ROBUST_TRIGGER))})")
    agg = aggregate if aggregate is not None else \
        _ROBUST_AGGREGATE.get(spec.aggregate)
    if agg not in ("trimmed_mean", "median"):
        raise ValueError(
            f"don't know the robust counterpart of aggregate "
            f"{spec.aggregate!r} (hardened rewrites "
            f"{sorted(_ROBUST_AGGREGATE)}; aggregate= accepts "
            f"'trimmed_mean' or 'median', got {aggregate!r})")
    if spec.commit not in _ROBUST_COMMIT:
        raise ValueError(
            f"don't know the robust counterpart of commit "
            f"{spec.commit!r} (hardened rewrites "
            f"{sorted(_ROBUST_COMMIT)}) — for the balancing pipeline "
            f"use the 'robust_dynamic' preset instead")
    params = dict(spec.params)
    if trim_frac is not None:
        params["trim_frac"] = trim_frac
    if quarantine_mult is not None:
        params["quarantine_mult"] = quarantine_mult
    return ProtocolSpec(
        name=f"robust_{spec.name or spec.trigger}",
        trigger=_ROBUST_TRIGGER[spec.trigger], cohort=spec.cohort,
        aggregate=agg, commit=_ROBUST_COMMIT[spec.commit], params=params)


# ---------------------------------------------------------------------------
# presets
# ---------------------------------------------------------------------------

ROBUST_PERIODIC = ProtocolSpec(
    name="robust_periodic", trigger="robust_cadence",
    cohort="all_reachable", aggregate="trimmed_mean", commit="quarantine")

ROBUST_DYNAMIC = ProtocolSpec(
    name="robust_dynamic", trigger="robust_divergence",
    cohort="all_reachable", aggregate="trimmed_mean", commit="quarantine")

register_protocol("robust_periodic", ROBUST_PERIODIC)
register_protocol("robust_dynamic", ROBUST_DYNAMIC)
