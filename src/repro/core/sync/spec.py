"""ProtocolSpec: a synchronization protocol as a declarative, serializable
composition of registered stages.

    spec = ProtocolSpec(trigger="divergence", cohort="balanced",
                        aggregate="mean", commit="balancing",
                        params={"b": 2, "delta": 0.5})

A spec names one stage per slot (``repro.core.sync.registry``), carries
the stages' static parameters, validates the composition at CONSTRUCTION
(unknown stages, incompatible combinations, bad parameter values — never
at trace time), and ``compile()``s into the staged round function the
scanned engine runs: ``(stacked, state, weights, active, adjacency) ->
StageResult``. Specs are frozen and hashable, so compilation is cached
and a spec can key a jit trace.

Serialization: ``to_dict``/``from_dict`` and ``to_json``/``from_json``
round-trip exactly, so checkpoints restore the precise protocol and
benchmarks can run arbitrary specs from a file
(``python -m benchmarks.run --protocol spec.json``).

``resolve_spec`` maps the legacy sugar onto this API: a ``ProtocolConfig``
resolves to its ``PROTOCOLS`` preset with the config's parameter fields
overlaid — the six built-in kinds are just presets (``kernel.py``), and
``register_protocol`` makes new compositions available to
``ProtocolConfig(kind=...)`` too.
"""
from __future__ import annotations

import dataclasses
import functools
import json
from dataclasses import dataclass, field
from typing import Any, Dict, Tuple

import jax

from repro.core import shard
from repro.core.sync import registry, stages
from repro.core.sync.registry import (
    CommRecord, StageCtx, StageResult, SyncOut, get_protocol,
)

# parameters every spec understands regardless of its stages. ``layout``
# picks the fleet arithmetic: "tree" (per-leaf pytree expressions,
# bitwise vs the goldens), "flat" (the blocked fleet plane,
# repro.core.sync.stages — params to float reassociation tolerance, and the
# same sync decisions hence bitwise comm counters, except in the
# measure-zero case where a distance lands within reassociation error
# of the Delta threshold and the differently-associated sums disagree
# about the comparison), or "sharded" (the same plane with its m
# axis split over a device mesh, repro.core.shard — identical arithmetic
# to "flat"; ``shard_devices`` caps how many visible devices the fleet
# mesh uses, 0 = all of them, and m % n_devices must be 0)
GLOBAL_PARAMS: Dict[str, Any] = {"weighted": False, "bytes_per_param": 4,
                                 "layout": "tree", "shard_devices": 0}

# the registered fleet layouts. A new backend joins by adding its name
# here and branching in the stages — the static contract checker
# (repro.analysis.contracts) then holds every registered preset to
# abstract tree-equivalence automatically.
LAYOUTS = ("tree", "flat", "sharded")

# layouts that run the blocked fleet-plane arithmetic. "sharded" is
# "flat" plus sharding constraints that are identity off-mesh, so both
# the compile below and the contract checker treat them as one family.
PLANE_LAYOUTS = ("flat", "sharded")


def pin_rows(stacked):
    """The blocked fleet plane of a stacked configuration: the same
    leaves, each pinned on its learner axis (``shard.constrain_rows``;
    the identity with no active fleet). Moves no data."""
    return jax.tree.map(shard.constrain_rows, stacked)


# the ProtocolConfig fields that overlay onto a preset's params (only the
# ones the preset's stages actually consume are applied)
_CONFIG_PARAM_FIELDS = ("b", "delta", "fedavg_c", "augmentation",
                        "weighted", "bytes_per_param", "layout",
                        "shard_devices")


def _canonical(v):
    """Numpy scalar -> plain Python number; everything else untouched."""
    import numbers
    if isinstance(v, (bool, int, float, str)):
        return v
    if isinstance(v, numbers.Integral):
        return int(v)
    if isinstance(v, numbers.Real):
        return float(v)
    return v


@dataclass(frozen=True)
class ProtocolSpec:
    """One protocol = four named stages + their static parameters.

    ``params`` accepts a dict at construction and is canonicalized to a
    sorted tuple of items, so specs are hashable and order-insensitive.
    ``name`` is cosmetic (presets carry their kind)."""
    trigger: str
    cohort: str = "all_reachable"
    aggregate: str = "mean"
    commit: str = "average"
    params: Any = ()
    name: str = ""

    def __post_init__(self):
        raw = self.params
        if isinstance(raw, dict):
            items = raw.items()
        else:
            items = (tuple(kv) for kv in raw)
        # canonicalize: numpy scalars become plain Python numbers so specs
        # built from np sweeps validate, hash and JSON-serialize the same
        # as hand-written ones; anything else non-scalar (jax arrays,
        # lists) would only explode later — at the compile cache or in
        # to_json — so reject it here, at construction
        items = tuple(sorted((k, _canonical(v)) for k, v in items))
        for k, v in items:
            if not isinstance(v, (bool, int, float, str, type(None))):
                raise ValueError(
                    f"spec param {k!r} must be a plain Python scalar "
                    f"(bool/int/float/str), got {type(v).__name__}: {v!r}")
        object.__setattr__(self, "params", items)
        self._validate()

    # ---- stage access ------------------------------------------------
    def stage_records(self):
        return (registry.get_trigger(self.trigger),
                registry.get_cohort(self.cohort),
                registry.get_aggregate(self.aggregate),
                registry.get_commit(self.commit))

    @property
    def known_params(self) -> Dict[str, Any]:
        """name -> default for every parameter this spec's stages (plus
        the globals) consume."""
        merged = dict(GLOBAL_PARAMS)
        for rec in self.stage_records():
            merged.update(rec.params)
        return merged

    def resolved_params(self) -> Dict[str, Any]:
        p = self.known_params
        p.update(dict(self.params))
        return p

    def param(self, name: str):
        return self.resolved_params()[name]

    def with_params(self, **overrides) -> "ProtocolSpec":
        merged = dict(self.params)
        merged.update(overrides)
        return dataclasses.replace(self, params=merged)

    # ---- capabilities ------------------------------------------------
    @property
    def uses_overlay(self) -> bool:
        """Needs the (m, m) peer adjacency (the engine supplies the
        implied star on an ideal network)."""
        return registry.get_cohort(self.cohort).uses_overlay

    @property
    def uses_coordinator(self) -> bool:
        """Traffic is a star to a hub — the shape hierarchies require."""
        return registry.get_cohort(self.cohort).uses_coordinator

    @property
    def extra_state(self) -> Tuple[str, ...]:
        """Names of the extra carried-state arrays this spec's trigger
        threads through ``SyncState.extra``."""
        trig = registry.get_trigger(self.trigger)
        return tuple(sorted(trig.init_extra(self.resolved_params(), 1)))

    @property
    def bytes_per_param(self) -> int:
        return self.param("bytes_per_param")

    def init_extra(self, m: int) -> Dict[str, Any]:
        """Initial extra carried state for an m-learner fleet."""
        trig = registry.get_trigger(self.trigger)
        return trig.init_extra(self.resolved_params(), m)

    # ---- construction-time validation --------------------------------
    def _validate(self) -> None:
        trig, coh, agg, com = self.stage_records()   # KeyError on unknowns
        label = self.name or (
            f"{self.trigger}/{self.cohort}/{self.aggregate}/{self.commit}")
        if (coh.needs_condition or com.needs_condition) and not \
                trig.conditional:
            needer = coh.name if coh.needs_condition else com.name
            raise ValueError(
                f"spec {label!r}: stage {needer!r} needs a conditional "
                f"trigger (one that marks hot learners, e.g. divergence "
                f"or staleness), but trigger {trig.name!r} is "
                f"unconditional")
        for rec, slot in ((agg, "aggregate"), (com, "commit")):
            missing = rec.needs - coh.provides
            if missing:
                raise ValueError(
                    f"spec {label!r}: {slot} stage {rec.name!r} needs "
                    f"{sorted(missing)} which cohort {coh.name!r} does "
                    f"not provide (provides: {sorted(coh.provides)})")
        known = self.known_params
        unknown = [k for k, _ in self.params if k not in known]
        if unknown:
            raise ValueError(
                f"spec {label!r}: params {unknown} are not consumed by "
                f"any of its stages (known: {sorted(known)})")
        resolved = self.resolved_params()
        if not (isinstance(resolved["bytes_per_param"], int)
                and resolved["bytes_per_param"] >= 1):
            raise ValueError(
                f"bytes_per_param must be an int >= 1, got "
                f"{resolved['bytes_per_param']!r}")
        if resolved["layout"] not in LAYOUTS:
            raise ValueError(
                f"layout must be one of {LAYOUTS}, got "
                f"{resolved['layout']!r}")
        if not (isinstance(resolved["shard_devices"], int)
                and not isinstance(resolved["shard_devices"], bool)
                and resolved["shard_devices"] >= 0):
            raise ValueError(
                f"shard_devices must be an int >= 0 (0 = all visible "
                f"devices), got {resolved['shard_devices']!r}")
        for rec in (trig, coh, agg, com):
            if rec.validate is not None:
                rec.validate(resolved)

    # ---- serialization -----------------------------------------------
    def to_dict(self) -> Dict[str, Any]:
        return {
            "name": self.name,
            "trigger": self.trigger,
            "cohort": self.cohort,
            "aggregate": self.aggregate,
            "commit": self.commit,
            "params": dict(self.params),
        }

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "ProtocolSpec":
        allowed = {"name", "trigger", "cohort", "aggregate", "commit",
                   "params"}
        unknown = set(d) - allowed
        if unknown:
            raise ValueError(
                f"unknown ProtocolSpec keys {sorted(unknown)}; "
                f"schema: {sorted(allowed)}")
        if "trigger" not in d:
            raise ValueError("a ProtocolSpec dict needs at least 'trigger'")
        kw = dict(d)
        # JSON has no tuples; params may round-trip as a dict (canonical)
        kw["params"] = dict(kw.get("params", {}))
        return cls(**kw)

    def to_json(self, indent: int = 1) -> str:
        return json.dumps(self.to_dict(), indent=indent, sort_keys=True)

    @classmethod
    def from_json(cls, s: str) -> "ProtocolSpec":
        return cls.from_dict(json.loads(s))

    @classmethod
    def from_file(cls, path: str) -> "ProtocolSpec":
        with open(path) as f:
            return cls.from_json(f.read())

    # ---- compilation -------------------------------------------------
    def compile(self):
        """The staged round function:
        ``(stacked, state, weights=None, active=None, adjacency=None) ->
        StageResult``. Cached per spec (specs are frozen + hashable)."""
        return _compiled_round(self)


@functools.lru_cache(maxsize=None)
def _compiled_round(spec: ProtocolSpec):
    """Wire the spec's four stages into one scanned round.

    The skeleton mirrors the shape the monolithic operators shared, so
    preset specs reproduce the PR-2 engine bitwise (pinned by
    ``tests/golden_pr2_engine.json``):

        gate = trigger.gate(ctx)                  # every round
        lax.cond(gate):
          true:  [hot, nhot = trigger.condition(ctx)   # conditional
                  lax.cond(nhot > 0):]                 # triggers only
                    cohort -> aggregate -> commit
          false: identity + zero accounting (extra state still ages)

    Under the plane layouts ("flat"/"sharded") the stages run their
    blocked fleet-plane forms (``repro.core.sync.stages``): the plane is
    the stacked leaves themselves and the row the reference pytree, so
    nothing is raveled before the condition or unraveled after the
    commit, and the scan carry keeps one layout whether the gate fires
    or not. ``layout="sharded"`` runs the identical arithmetic and only
    pins each leaf's learner axis (``shard.constrain_rows``) on the way
    in and out: at trace time the pins read the fleet mesh the ENGINE
    activated (``shard.use_fleet``) and split the m axis over its
    devices; with no active fleet (eval_shape in the contract gate, the
    jaxpr audit) they are the identity, so "sharded" stays abstractly
    equal to "flat".
    """
    trig, coh, agg, com = spec.stage_records()
    p = spec.resolved_params()
    flat_layout = p["layout"] in PLANE_LAYOUTS

    def round_fn(stacked, state, weights=None, active=None, adjacency=None):
        m = stages.num_learners(stacked)
        t = state.step + 1
        reach = stages.cohort_all(m, active)
        ctx = StageCtx(params=p, stacked=stacked, state=state,
                       weights=weights, active=active, adjacency=adjacency,
                       m=m, t=t, reach=reach)

        def skip_out(rng):
            return SyncOut(stacked, state.ref, state.v, rng,
                           trig.skip_extra(ctx), CommRecord.zero(),
                           stages.zeros_i32(m), stages.zeros_i32(m))

        def pipeline(sctx, hot, nhot, rng):
            with jax.named_scope("repro.sync.cohort"):
                cout = coh.fn(sctx, hot, nhot, rng)
            with jax.named_scope("repro.sync.aggregate"):
                agg_out = agg.fn(sctx, cout)
            with jax.named_scope("repro.sync.commit"):
                out = com.fn(sctx, cout, agg_out, hot, nhot)
                out = out._replace(extra=trig.commit_extra(sctx, cout.mask))
            if flat_layout:
                out = out._replace(params=pin_rows(out.params))
            return out

        def sync(rng):
            sctx = ctx
            if flat_layout:
                sctx = ctx._replace(stacked=pin_rows(stacked), plane=True)
            if trig.condition is None:
                return pipeline(sctx, reach, None, rng)
            with jax.named_scope("repro.sync.condition"):
                cond = trig.condition(sctx)
            hot, nhot = cond[0], cond[1]
            if len(cond) > 2:     # condition extras -> downstream stages
                sctx = sctx._replace(cond_aux=cond[2])
            return jax.lax.cond(
                nhot > 0, lambda r: pipeline(sctx, hot, nhot, r),
                skip_out, rng)

        # the gate, its cond and the stages carry stable named scopes,
        # which a profile shows as each device op's name path; the scope
        # holds the cond itself, so the copies XLA puts at its boundary
        # count as the sync's too
        with jax.named_scope("repro.sync"):
            gate = trig.gate(ctx)
            if gate is False:  # statically-never trigger (nosync): no cond
                out = skip_out(state.rng)
            else:
                out = jax.lax.cond(gate, sync, skip_out, state.rng)
        new_state = state._replace(ref=out.ref, v=out.v, rng=out.rng,
                                   step=t, extra=out.extra)
        return StageResult(out.params, new_state, out.rec, out.xfers,
                           out.link_msgs)

    return round_fn


# ---------------------------------------------------------------------------
# ProtocolConfig sugar -> spec resolution
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _resolve_config(proto) -> ProtocolSpec:
    preset = get_protocol(proto.kind)
    known = preset.known_params
    # params a preset PINS explicitly are part of its identity and win
    # over the config overlay — a ProtocolConfig cannot distinguish its
    # dataclass defaults from user-set fields, so letting the overlay
    # through would silently clobber e.g. a registered preset's tuned b
    # with the config default. Pinned knobs are tuned via the spec API
    # (preset.with_params(...)), not the kind sugar.
    pinned = dict(preset.params)
    overrides = {f: getattr(proto, f) for f in _CONFIG_PARAM_FIELDS
                 if f in known and f not in pinned}
    return preset.with_params(**overrides)


def resolve_spec(proto) -> ProtocolSpec:
    """A ``ProtocolSpec`` passes through; a ``ProtocolConfig`` (anything
    with a ``.kind``) resolves to its preset with the config's parameter
    fields overlaid — only the fields the preset's stages consume apply,
    so e.g. ``delta`` never leaks into ``periodic``."""
    if isinstance(proto, ProtocolSpec):
        return proto
    if hasattr(proto, "kind"):
        return _resolve_config(proto)
    raise TypeError(
        f"expected a ProtocolSpec or a ProtocolConfig, got {proto!r}")
