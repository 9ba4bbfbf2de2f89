"""Named stage registries: the extension points of the protocol-spec API.

A synchronization protocol Π = (φ, σ) is declared as a ``ProtocolSpec``
(``repro.core.sync.spec``) naming one stage per slot:

    trigger  -> cohort  -> aggregate -> commit
    (fire?)     (who)      (what)       (apply + account)

Each slot has a registry (``TRIGGERS`` / ``COHORTS`` / ``AGGREGATES`` /
``COMMITS``) populated through the ``@register_*`` decorators; the built-in
stages live in ``repro.core.sync.stages``, and new protocols add stages
here WITHOUT touching the kernel or the engine (see
``repro.core.sync.staleness`` for the worked example). Name collisions
raise at import time — two stages may not share a slot name.

``PROTOCOLS`` is the preset registry: complete specs under a protocol
name. The six built-in kinds (nosync/periodic/continuous/fedavg/dynamic/
gossip) are registered by ``kernel.py``; ``register_protocol`` makes a new
composition available to ``ProtocolConfig(kind=...)`` as well.

Stage contracts (all pure, jit/vmap/scan-compatible; ``StageCtx`` carries
the round's inputs):

* **trigger** — the decorated function is the *gate*: ``gate(ctx) ->
  scalar bool`` (or the Python constant ``False`` for a never-firing
  trigger), evaluated every round. An optional ``condition(ctx) ->
  (hot, nhot)`` runs inside the gated branch and yields the per-learner
  "wants to sync" mask; when present the cohort/aggregate/commit pipeline
  only runs when ``nhot > 0`` (sigma_Delta's shape). A condition may
  return a third element — a dict of auxiliary arrays (e.g. the (m,)
  distances the divergence check already paid for) — which the compiled
  round threads to the downstream stages as ``StageCtx.cond_aux`` so the
  round computes them exactly once. Triggers own their
  extra carried state via ``init_extra(params, m) -> dict``,
  ``commit_extra(ctx, mask) -> dict`` (after a sync; ``mask`` is the
  committed cohort) and ``skip_extra(ctx) -> dict`` (any round without a
  sync commit).
* **cohort** — ``fn(ctx, hot, nhot, rng) -> CohortOut``: WHO participates.
  Declares capabilities: ``uses_overlay`` (needs the peer adjacency),
  ``uses_coordinator`` (star traffic to a hub — hierarchies require it),
  ``provides`` (labels downstream stages can depend on), and
  ``needs_condition`` (requires a conditional trigger's hot/nhot).
* **aggregate** — ``fn(ctx, cohort_out) -> model``: WHAT the cohort
  agrees on. ``needs`` names the cohort labels it depends on.
* **commit** — ``fn(ctx, cohort_out, aggregate, hot, nhot) -> SyncOut``:
  APPLY the agreement and ACCOUNT for it (CommRecord + the per-link
  transfer/message counts the bytes ledger prices).

Every stage may declare ``params`` (name -> default, merged into the
spec's parameter space) and ``validate(params)`` (raise ``ValueError`` at
spec CONSTRUCTION, not trace time).

Every stage MUST declare a ``StageContract`` — the shape/dtype promises
the static contract checker (``repro.analysis.contracts``) verifies by
abstract evaluation across every preset × layout × hierarchy combination:
counters stay int32, masks stay bool, the committed configuration keeps
the input dtypes exactly, aggregate outputs match their declared kind
(a model row vs an (m, ...) fleet), and trigger-owned extra state keeps
its declared dtypes through commit and skip paths. The repo lint
(``repro.analysis.lint``) rejects ``register_*`` calls without one.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Optional

import jax.numpy as jnp


# ---------------------------------------------------------------------------
# shared carried-state / result types (kernel.py re-exports these)
# ---------------------------------------------------------------------------

class SyncState(NamedTuple):
    ref: Any             # reference model r (single-model pytree)
    v: jnp.ndarray       # violation counter (scalar int32)
    rng: jnp.ndarray     # PRNG key for subsampling / random augmentation
    step: jnp.ndarray    # round counter t (scalar int32)
    extra: Any = {}      # trigger-declared extra carried state (dict of
    #   arrays, e.g. the staleness counters); {} for the built-in presets,
    #   so the carry pytree is unchanged vs the pre-spec engine


class CommRecord(NamedTuple):
    model_up: jnp.ndarray     # models sent learner -> coordinator
    model_down: jnp.ndarray   # models sent coordinator -> learner
    messages: jnp.ndarray     # small control messages (violations, polls)
    syncs: jnp.ndarray        # 1 if any averaging happened this round
    full_syncs: jnp.ndarray   # 1 if ALL (reachable) learners were averaged

    @staticmethod
    def zero():
        z = jnp.zeros((), jnp.int32)
        return CommRecord(z, z, z, z, z)


class StageResult(NamedTuple):
    """What one staged round produces: the committed configuration, the
    carried sync state, the scalar comm record, and the per-link counts
    (model transfers + control messages) the bytes ledger prices."""
    params: Any
    state: SyncState
    rec: CommRecord
    xfers: jnp.ndarray       # (m,) int32 models crossing each learner's link
    link_msgs: jnp.ndarray   # (m,) int32 control messages per learner link


class StageCtx(NamedTuple):
    """One round's inputs, shared by every stage.

    Under the plane layouts (``"flat"``/``"sharded"``) the compiled round
    hands the stages the blocked fleet plane (``repro.core.sync.stages``):
    ``stacked`` is then the same leaves, each pinned on its learner axis,
    and ``plane`` is set; stages branch on it only where the plane runs
    its own algorithm (the incremental balancing loop). Under the default
    ``layout="tree"`` it stays False and the per-leaf pytree expressions
    run bitwise-unchanged. ``cond_aux``
    carries whatever a conditional trigger computed beyond (hot, nhot) —
    e.g. the divergence trigger's (m,) distances, which the balancing
    cohort reuses as its augmentation priority instead of recomputing
    them from scratch."""
    params: Dict[str, Any]           # the spec's resolved (static) params
    stacked: Any                     # (m, ...) model pytree
    state: SyncState
    weights: Optional[jnp.ndarray]   # Algorithm-2 B^i weights (or None)
    active: Optional[jnp.ndarray]    # (m,) reachability, None = ideal net
    adjacency: Optional[jnp.ndarray]  # (m, m) peer overlay (or None)
    m: int                           # fleet size (static)
    t: jnp.ndarray                   # this round's index (state.step + 1)
    reach: jnp.ndarray               # (m,) bool; all-ones when active=None
    plane: bool = False              # the plane layouts' blocked plane
    cond_aux: Any = None             # trigger-condition extras (e.g. dists)


class CohortOut(NamedTuple):
    """A cohort stage's output. ``v``/``full`` are None unless the cohort
    manages the violation counter (the balancing cohort); ``ideal`` is a
    PYTHON bool marking the ideal-network full-participation fast path
    (``active is None`` + everyone in), which downstream stages use to
    keep the pre-network expressions bitwise."""
    mask: jnp.ndarray                # (m,) bool participants
    rng: jnp.ndarray                 # carried PRNG key (split or untouched)
    v: Optional[jnp.ndarray] = None
    full: Optional[jnp.ndarray] = None
    ideal: bool = False
    aux: Any = None                  # stage-specific extras (e.g. A, W)


class SyncOut(NamedTuple):
    """A commit stage's output — everything that crosses the trigger's
    ``lax.cond`` boundary."""
    params: Any
    ref: Any
    v: jnp.ndarray
    rng: jnp.ndarray
    extra: Any
    rec: CommRecord
    xfers: jnp.ndarray
    link_msgs: jnp.ndarray


def carried_v(ctx: StageCtx, cout: CohortOut) -> jnp.ndarray:
    """The violation counter a commit stage should carry forward."""
    return ctx.state.v if cout.v is None else cout.v


# ---------------------------------------------------------------------------
# stage contracts
# ---------------------------------------------------------------------------

class StageContract(NamedTuple):
    """The static shape/dtype promises of one registered stage.

    Declared at registration (``register_*(..., contract=...)``) and
    verified — never trusted — by ``repro.analysis.contracts``, which
    abstract-evaluates the stage (``jax.eval_shape``, zero FLOPs) under
    every registered preset × layout × hierarchy combination. Slots read
    only the fields that concern them:

    * **trigger** — ``extra_state``: ``(name, dtype)`` pairs for the
      arrays the trigger threads through ``SyncState.extra`` (each (m,);
      ``init_extra``/``commit_extra``/``skip_extra`` must all produce
      exactly this structure). ``cond_aux``: the keys of the condition's
      auxiliary-output dict (each an (m,) array).
    * **cohort** — ``manages_v``: the stage owns the violation counter
      (returns a scalar int32 ``v`` and a scalar bool ``full``;
      everything else must leave both ``None``). ``aux``: the keys of
      ``CohortOut.aux``.
    * **aggregate** — ``out``: ``"model"`` (a single-model pytree, the
      plane's row) or ``"fleet"`` (an (m, ...) stacked pytree, the
      plane's blocks).

    Universal promises (not declarable — always enforced): the committed
    configuration and reference keep the input shapes AND dtypes bitwise,
    ``v``/``CommRecord``/``xfers``/``link_msgs`` are int32, masks are
    bool, the RNG key dtype is preserved.
    """
    summary: str = ""
    extra_state: tuple = ()       # trigger: ((name, dtype-str), ...)
    cond_aux: tuple = ()          # trigger: condition aux dict keys
    manages_v: bool = False       # cohort: owns v/full
    aux: tuple = ()               # cohort: CohortOut.aux dict keys
    out: str = "model"            # aggregate: "model" | "fleet"


# ---------------------------------------------------------------------------
# stage records
# ---------------------------------------------------------------------------

def _default_init_extra(params, m):
    return {}


def _default_commit_extra(ctx, mask):
    return ctx.state.extra


def _default_skip_extra(ctx):
    return ctx.state.extra


class TriggerStage(NamedTuple):
    name: str
    gate: Callable                    # ctx -> scalar bool (or False)
    condition: Optional[Callable]     # ctx -> (hot, nhot); None = always
    init_extra: Callable              # (params, m) -> dict of arrays
    commit_extra: Callable            # (ctx, mask) -> dict
    skip_extra: Callable              # ctx -> dict
    params: Dict[str, Any]
    validate: Optional[Callable]
    contract: Optional[StageContract] = None

    @property
    def conditional(self) -> bool:
        return self.condition is not None


class CohortStage(NamedTuple):
    name: str
    fn: Callable                      # (ctx, hot, nhot, rng) -> CohortOut
    provides: frozenset               # labels downstream stages may need
    uses_overlay: bool                # needs the peer adjacency matrix
    uses_coordinator: bool            # star traffic to a hub (hierarchies)
    needs_condition: bool             # requires a conditional trigger
    params: Dict[str, Any]
    validate: Optional[Callable]
    contract: Optional[StageContract] = None


class AggregateStage(NamedTuple):
    name: str
    fn: Callable                      # (ctx, cohort_out) -> model pytree
    needs: frozenset                  # cohort labels this stage depends on
    params: Dict[str, Any]
    validate: Optional[Callable]
    contract: Optional[StageContract] = None


class CommitStage(NamedTuple):
    name: str
    fn: Callable                      # (ctx, cout, agg, hot, nhot) -> SyncOut
    needs: frozenset
    needs_condition: bool
    params: Dict[str, Any]
    validate: Optional[Callable]
    contract: Optional[StageContract] = None


# ---------------------------------------------------------------------------
# the registries + decorators
# ---------------------------------------------------------------------------

TRIGGERS: Dict[str, TriggerStage] = {}
COHORTS: Dict[str, CohortStage] = {}
AGGREGATES: Dict[str, AggregateStage] = {}
COMMITS: Dict[str, CommitStage] = {}


def _enter(registry: Dict[str, Any], slot: str, name: str, record) -> None:
    if name in registry:
        raise ValueError(
            f"{slot} stage {name!r} is already registered — stage names "
            f"must be unique per slot (known: {sorted(registry)})")
    registry[name] = record


def register_trigger(name: str, *, condition: Optional[Callable] = None,
                     init_extra: Optional[Callable] = None,
                     commit_extra: Optional[Callable] = None,
                     skip_extra: Optional[Callable] = None,
                     params: Optional[Dict[str, Any]] = None,
                     validate: Optional[Callable] = None,
                     contract: Optional[StageContract] = None):
    """Register the decorated function as trigger ``name``'s gate."""
    def deco(gate: Callable) -> Callable:
        _enter(TRIGGERS, "trigger", name, TriggerStage(
            name=name, gate=gate, condition=condition,
            init_extra=init_extra or _default_init_extra,
            commit_extra=commit_extra or _default_commit_extra,
            skip_extra=skip_extra or _default_skip_extra,
            params=dict(params or {}), validate=validate,
            contract=contract))
        return gate
    return deco


def register_cohort(name: str, *, provides=(), uses_overlay: bool = False,
                    uses_coordinator: bool = True,
                    needs_condition: bool = False,
                    params: Optional[Dict[str, Any]] = None,
                    validate: Optional[Callable] = None,
                    contract: Optional[StageContract] = None):
    def deco(fn: Callable) -> Callable:
        _enter(COHORTS, "cohort", name, CohortStage(
            name=name, fn=fn, provides=frozenset(provides),
            uses_overlay=uses_overlay, uses_coordinator=uses_coordinator,
            needs_condition=needs_condition, params=dict(params or {}),
            validate=validate, contract=contract))
        return fn
    return deco


def register_aggregate(name: str, *, needs=(),
                       params: Optional[Dict[str, Any]] = None,
                       validate: Optional[Callable] = None,
                       contract: Optional[StageContract] = None):
    def deco(fn: Callable) -> Callable:
        _enter(AGGREGATES, "aggregate", name, AggregateStage(
            name=name, fn=fn, needs=frozenset(needs),
            params=dict(params or {}), validate=validate,
            contract=contract))
        return fn
    return deco


def register_commit(name: str, *, needs=(), needs_condition: bool = False,
                    params: Optional[Dict[str, Any]] = None,
                    validate: Optional[Callable] = None,
                    contract: Optional[StageContract] = None):
    def deco(fn: Callable) -> Callable:
        _enter(COMMITS, "commit", name, CommitStage(
            name=name, fn=fn, needs=frozenset(needs),
            needs_condition=needs_condition, params=dict(params or {}),
            validate=validate, contract=contract))
        return fn
    return deco


def _get(registry: Dict[str, Any], slot: str, name: str):
    if name not in registry:
        raise KeyError(
            f"unknown {slot} stage {name!r}; known: {sorted(registry)}")
    return registry[name]


def get_trigger(name: str) -> TriggerStage:
    return _get(TRIGGERS, "trigger", name)


def get_cohort(name: str) -> CohortStage:
    return _get(COHORTS, "cohort", name)


def get_aggregate(name: str) -> AggregateStage:
    return _get(AGGREGATES, "aggregate", name)


def get_commit(name: str) -> CommitStage:
    return _get(COMMITS, "commit", name)


# ---------------------------------------------------------------------------
# protocol presets: complete specs under a name
# ---------------------------------------------------------------------------

PROTOCOLS: Dict[str, Any] = {}   # name -> ProtocolSpec


def register_protocol(name: str, spec) -> None:
    """Make ``spec`` available as preset ``name`` — and thereby as a valid
    ``ProtocolConfig(kind=name)``."""
    if name in PROTOCOLS:
        raise ValueError(
            f"protocol {name!r} is already registered "
            f"(known: {sorted(PROTOCOLS)})")
    PROTOCOLS[name] = spec


def get_protocol(name: str):
    if name not in PROTOCOLS:
        raise KeyError(
            f"unknown protocol {name!r}; known: {sorted(PROTOCOLS)}")
    return PROTOCOLS[name]
