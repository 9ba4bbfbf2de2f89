"""Event-driven asynchronous synchronization + over-the-air aggregation.

The ROADMAP's async open item, landed the PR-4 way: registered stages
plus specs, zero kernel/engine edits. Three pieces:

* **Event-driven triggers** ``"events"`` / ``"events_divergence"`` — the
  cadence/staleness/divergence conditions re-based on a per-learner
  LOCAL clock with messages in flight. Each learner carries, in
  ``SyncState.extra``:

  - ``lclock`` (m,) int32 — the local cadence phase: how many idle
    rounds into its current period the learner is. It only advances
    while the learner is idle, so a learner's cadence period is ``b``
    local rounds plus however long its last exchange flew.
  - ``inflight`` (m,) int32 — rounds until its launched exchange lands.
  - ``ring`` (m, max_delay) int32 — the bounded-delay arrival buffer
    (``repro.network.events``): slot ``t % max_delay`` marks whose
    exchange lands at round ``t``.
  - ``age`` (m,) int32 — rounds since the learner last synced (the
    PR-4 staleness counter, carried by every async trigger so the
    telemetry chunk snapshots always expose staleness ages).

  When a learner's alarm condition holds (local cadence tick, staleness
  deadline, or divergence violation) it LAUNCHES an exchange: the
  message flies for ``k = ceil(round_trip / budget) - 1`` whole rounds
  (``events.flight_rounds``, from the ``repro.network.cost`` link
  classes), and the learner participates in a sync only when the
  arrival round is reached. ``k = 0`` — an ideal network, or a round
  budget that covers the slowest link's round trip — reduces every
  composition EXACTLY to its synchronous original: same gate values,
  same hot sets, same rng stream, bitwise-equal counters, ledger and
  parameters (pinned by ``tests/test_async.py``).

  Arrivals landing while their learner is unreachable are dropped (the
  fleet's availability mask wins); the learner goes idle again and
  re-launches at its next alarm.

* **``"aircomp"`` aggregate** — the cohort mean computed over an analog
  multiple-access channel: every member transmits simultaneously and
  the channel itself sums the waveforms (the ``air_comp`` hook in the
  Federated-Edge-AI-For-6G exemplar, SNIPPETS.md). The receiver sees
  the mean plus Gaussian noise at ``snr_db`` relative to the
  aggregate's RMS, attenuated by the cohort size (n aligned
  transmissions add amplitudes, the receiver noise does not). The draw
  is pure in ``(air_seed, t)`` and drawn per leaf on every layout (the
  plane's row is the model pytree); counters and ledger are
  layout-invariant as always.

* **``"aircomp"`` commit** — the pricing dual: one shared-medium
  transmission in the paper's c(f) (``model_up = model_down = 1`` per
  sync, however large the cohort), while the per-link ledger bills each
  member's analog frame occupancy (1 transfer per member link). Like
  gossip's 2x occupancy note in ``per_link_bytes``, the ledger's sum is
  deliberately NOT c(f) here — it is ``nsync * model_bytes`` of radio
  airtime against ``2 * model_bytes`` of effective fleet throughput.

``asyncify`` rewrites any synchronous spec into its event-driven
counterpart (the ``AsyncConfig`` engine hook); ``"aircomp"``,
``"async_periodic"`` and ``"async_dynamic"`` are registered presets.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.sync.kernel import register_protocol
from repro.core.sync.registry import (
    CohortOut, CommRecord, StageContract, StageCtx, SyncOut, carried_v,
    register_aggregate, register_commit, register_trigger,
)
from repro.core.sync.spec import ProtocolSpec
from repro.core.sync.stages import (
    _ref_dists, _ref_if_commit, _select_commit, _validate_b,
    aggregate_mean_stage, broadcast_model, zeros_i32,
)
from repro.network import events


# ---------------------------------------------------------------------------
# the shared timeline: extra-state keys + per-round transition
# ---------------------------------------------------------------------------

_EXTRA_KEYS = ("age", "inflight", "lclock", "ring")
_EXTRA_CONTRACT = (("age", "int32"), ("inflight", "int32"),
                   ("lclock", "int32"), ("ring", "int32"))


def _timeline(ctx: StageCtx) -> dict:
    """The decoded timeline state this round: who is due (their exchange
    lands at round t), who is idle (free to launch), and whose local
    cadence phase ticks."""
    extra = ctx.state.extra
    missing = [k for k in _EXTRA_KEYS if k not in extra]
    if missing:
        raise ValueError(
            f"the event-driven triggers carry {list(_EXTRA_KEYS)} in "
            f"SyncState.extra (missing: {missing}) — build the state with "
            f"init_state(ref, seed, spec=spec, m=m) (the engine does this "
            f"automatically)")
    p = ctx.params
    k = events.flight_rounds(p["link_classes"], ctx.m, p["payload_bytes"],
                             p["budget"])
    due = events.due_mask(extra["ring"], ctx.t)
    idle = extra["inflight"] == 0
    # the LOCAL cadence: lclock is the learner's idle-round phase within
    # its period, so the tick fires b idle rounds after its last one —
    # flight rounds (and the arrival round itself) do not advance it
    tick = ((extra["lclock"] + 1) % p["b"]) == 0
    return {"ring": extra["ring"], "inflight": extra["inflight"],
            "lclock": extra["lclock"], "age": extra["age"],
            "k": k, "due": due, "idle": idle, "tick": tick}


def _advance(ctx: StageCtx, tl: dict, launch, mask) -> dict:
    """One timeline transition: consume arrivals, schedule launches,
    advance idle local clocks, age everyone (``mask`` — the committed
    cohort — resets its ages; None on skip rounds)."""
    ring = events.ring_step(tl["ring"], ctx.t, launch, tl["k"])
    inflight = jnp.where(launch, tl["k"],
                         jnp.maximum(tl["inflight"] - 1, 0))
    advance = tl["idle"] & ~tl["due"]
    lclock = jnp.where(advance, (tl["lclock"] + 1) % ctx.params["b"],
                       tl["lclock"])
    age = tl["age"] + 1
    if mask is not None:
        age = jnp.where(mask, jnp.int32(0), age)
    return {"age": age, "inflight": inflight, "lclock": lclock,
            "ring": ring}


def _events_init(params, m: int) -> dict:
    return {"age": jnp.zeros((m,), jnp.int32),
            "inflight": jnp.zeros((m,), jnp.int32),
            "lclock": jnp.zeros((m,), jnp.int32),
            "ring": events.empty_ring(m, params["max_delay"])}


def _validate_delay(params) -> None:
    budget = params["budget"]
    if not (isinstance(budget, (int, float)) and budget > 0):
        raise ValueError(f"round budget must be > 0 seconds, got {budget!r}")
    depth = params["max_delay"]
    if not (isinstance(depth, int) and depth >= 1):
        raise ValueError(f"max_delay must be an int >= 1, got {depth!r}")
    payload = params["payload_bytes"]
    if not (isinstance(payload, int) and payload >= 0):
        raise ValueError(
            f"payload_bytes must be an int >= 0, got {payload!r}")
    kmax = events.max_flight_rounds(params["link_classes"], payload,
                                    float(budget))
    if kmax >= depth:
        raise ValueError(
            f"slowest link class flies {kmax} rounds but the arrival ring "
            f"only holds max_delay={depth} — raise max_delay above {kmax}, "
            f"raise the round budget, or shrink the payload")


# ---------------------------------------------------------------------------
# trigger "events": cadence / staleness alarms on the local clock
# ---------------------------------------------------------------------------

def _events_alarm(ctx: StageCtx, tl: dict):
    """Who wants to launch this round. The cadence base is UNMASKED like
    ``trigger_cadence`` (the schedule does not depend on reachability);
    the staleness base mirrors ``trigger_staleness``'s reach-masked
    deadline on the carried ages."""
    alarm = tl["tick"] & tl["idle"] & ~tl["due"]
    if ctx.params["base"] == "staleness":
        alarm &= ctx.reach & (tl["age"] + 1 >= ctx.params["tau"])
    return alarm


def _events_condition(ctx: StageCtx):
    tl = _timeline(ctx)
    alarm = _events_alarm(ctx, tl)
    # alarms on a zero-flight link fire immediately (the synchronous
    # limit); the rest launch, and participate at their arrival round.
    # nhot counts UNMASKED fires so the pipeline always runs when the
    # synchronous original would have (the fedavg rng stream depends on
    # pipeline entries, not on who was reachable).
    fire = (tl["due"] & ctx.reach) | (alarm & (tl["k"] == 0))
    hot = fire & ctx.reach
    return hot, jnp.sum(fire).astype(jnp.int32)


def _events_commit(ctx: StageCtx, mask) -> dict:
    tl = _timeline(ctx)
    launch = _events_alarm(ctx, tl) & (tl["k"] > 0)
    return _advance(ctx, tl, launch, mask)


def _events_skip(ctx: StageCtx) -> dict:
    # launch-only rounds land here (nothing due, nothing immediate, so
    # the pipeline is skipped) — the ring still has to record them
    tl = _timeline(ctx)
    launch = _events_alarm(ctx, tl) & (tl["k"] > 0)
    return _advance(ctx, tl, launch, None)


def _validate_events(params) -> None:
    _validate_b(params)
    _validate_delay(params)
    if params["base"] not in ("cadence", "staleness"):
        raise ValueError(
            f"events base must be cadence|staleness, got {params['base']!r}")
    tau = params["tau"]
    if not (isinstance(tau, int) and tau >= 1):
        raise ValueError(f"staleness bound tau must be an int >= 1, "
                         f"got {tau!r}")


@register_trigger(
    "events", condition=_events_condition, init_extra=_events_init,
    commit_extra=_events_commit, skip_extra=_events_skip,
    params={"base": "cadence", "b": 1, "tau": 5, "budget": 1.0,
            "max_delay": 8, "link_classes": "", "payload_bytes": 0},
    validate=_validate_events,
    contract=StageContract(
        summary="event-driven cadence/staleness alarm on the per-learner "
                "local clock; launches fly k rounds through the bounded-"
                "delay arrival ring",
        extra_state=_EXTRA_CONTRACT))
def trigger_events(ctx: StageCtx):
    """Gate: any local tick on an idle learner, or any arrival landing
    this round — between those events the round skips the sync machinery
    entirely."""
    tl = _timeline(ctx)
    return jnp.any(tl["tick"] & tl["idle"]) | jnp.any(tl["due"])


# ---------------------------------------------------------------------------
# trigger "events_divergence": sigma_Delta's condition on the local clock
# ---------------------------------------------------------------------------

def _events_div_alarm(ctx: StageCtx, tl: dict, dists):
    violated = (dists > ctx.params["delta"]) & ctx.reach
    return violated & tl["tick"] & tl["idle"] & ~tl["due"]


def _events_div_condition(ctx: StageCtx):
    tl = _timeline(ctx)
    dists = _ref_dists(ctx)
    alarm = _events_div_alarm(ctx, tl, dists)
    launch = alarm & (tl["k"] > 0)
    fire = (tl["due"] & ctx.reach) | (alarm & (tl["k"] == 0))
    # fire is already reach-masked (violations and arrivals both are);
    # its count feeds the balanced cohort's violation counter exactly
    # like the synchronous nviol — a learner is counted once, the round
    # its violation PARTICIPATES, never at launch
    return fire, jnp.sum(fire).astype(jnp.int32), \
        {"dists": dists, "launch": launch}


def _events_div_launch(ctx: StageCtx, tl: dict):
    """The launch set. On commit rounds the condition already computed it
    (threaded via ``cond_aux``); on skip rounds — the engine's skip path
    sees the pre-condition ctx — the monitoring pass reruns, which is the
    documented extra cost of divergence monitoring on non-sync rounds."""
    if isinstance(ctx.cond_aux, dict) and "launch" in ctx.cond_aux:
        return ctx.cond_aux["launch"]
    return _events_div_alarm(ctx, tl, _ref_dists(ctx)) & (tl["k"] > 0)


def _events_div_commit(ctx: StageCtx, mask) -> dict:
    tl = _timeline(ctx)
    return _advance(ctx, tl, _events_div_launch(ctx, tl), mask)


def _events_div_skip(ctx: StageCtx) -> dict:
    tl = _timeline(ctx)
    return _advance(ctx, tl, _events_div_launch(ctx, tl), None)


def _validate_events_div(params) -> None:
    _validate_b(params)
    _validate_delay(params)
    if not params["delta"] > 0:
        raise ValueError(
            f"divergence threshold delta must be > 0, got {params['delta']!r}")


@register_trigger(
    "events_divergence", condition=_events_div_condition,
    init_extra=_events_init, commit_extra=_events_div_commit,
    skip_extra=_events_div_skip,
    params={"b": 1, "delta": 0.5, "budget": 1.0, "max_delay": 8,
            "link_classes": "", "payload_bytes": 0},
    validate=_validate_events_div,
    contract=StageContract(
        summary="sigma_Delta's divergence condition checked on idle "
                "learners' local ticks; violations on slow links fly "
                "before participating",
        extra_state=_EXTRA_CONTRACT,
        cond_aux=("dists", "launch")))
def trigger_events_divergence(ctx: StageCtx):
    """Gate: any idle learner's local tick (a divergence check might
    fire) or any arrival landing this round."""
    tl = _timeline(ctx)
    return jnp.any(tl["tick"] & tl["idle"]) | jnp.any(tl["due"])


# ---------------------------------------------------------------------------
# aggregate + commit "aircomp": over-the-air analog superposition
# ---------------------------------------------------------------------------

def _validate_air(params) -> None:
    snr = params["snr_db"]
    if not isinstance(snr, (int, float)):
        raise ValueError(f"snr_db must be a number, got {snr!r}")
    if not isinstance(params["air_seed"], int):
        raise ValueError(f"air_seed must be an int, "
                         f"got {params['air_seed']!r}")


@register_aggregate(
    "aircomp", params={"snr_db": 20.0, "air_seed": 0},
    validate=_validate_air,
    contract=StageContract(
        summary="cohort mean over the analog MAC: Gaussian receiver "
                "noise at snr_db, attenuated by the cohort size; draw "
                "pure in (air_seed, t)",
        out="model"))
def aggregate_aircomp(ctx: StageCtx, cout: CohortOut):
    """The cohort mean as the analog channel computes it: every member
    transmits simultaneously, the superposed waveform IS the sum, and
    the receiver adds Gaussian noise at ``snr_db`` below the aggregate's
    RMS. n aligned transmissions add amplitudes while the receiver noise
    stays fixed, so the post-averaging noise std shrinks as 1/n."""
    mean = aggregate_mean_stage(ctx, cout)
    n = (jnp.float32(ctx.m) if cout.ideal
         else jnp.maximum(jnp.sum(cout.mask), 1).astype(jnp.float32))
    scale = jnp.float32(10.0 ** (-float(ctx.params["snr_db"]) / 20.0))
    key = jax.random.fold_in(
        jax.random.PRNGKey(ctx.params["air_seed"] ^ 0xA17C0), ctx.t)

    def noisy(i, x):
        xf = x.astype(jnp.float32)
        rms = jnp.sqrt(jnp.mean(xf * xf) + jnp.float32(1e-12))
        eps = jax.random.normal(jax.random.fold_in(key, i), x.shape,
                                jnp.float32)
        return (xf + (rms * scale / n) * eps).astype(x.dtype)

    leaves, treedef = jax.tree.flatten(mean)
    return jax.tree.unflatten(
        treedef, [noisy(i, x) for i, x in enumerate(leaves)])


@register_commit(
    "aircomp", needs=("full-cohort",),
    contract=StageContract(
        summary="cohort adopts the noisy analog aggregate; c(f) counts "
                "ONE shared-medium exchange; the ledger bills each "
                "member's analog frame airtime"))
def commit_aircomp(ctx: StageCtx, cout: CohortOut, mean, hot,
                   nhot) -> SyncOut:
    """The analog channel's pricing: the simultaneous uplink plus the
    broadcast downlink are ONE exchange in the paper's c(f)
    (``model_up = model_down = 1`` regardless of cohort size — the
    physics that makes aircomp fundamentally cheaper), while the
    per-link ledger bills every member's radio one analog frame of
    airtime. Like gossip's both-endpoints occupancy, the ledger's sum is
    intentionally not c(f): nsync frames of airtime vs 2 payloads of
    fleet throughput."""
    m = ctx.m
    if cout.ideal:
        newcfg = broadcast_model(mean, m)
        rec = CommRecord(
            model_up=jnp.int32(1), model_down=jnp.int32(1),
            messages=jnp.int32(0), syncs=jnp.int32(1),
            full_syncs=jnp.int32(1))
        return SyncOut(newcfg, mean, carried_v(ctx, cout), cout.rng,
                       ctx.state.extra, rec, jnp.ones((m,), jnp.int32),
                       zeros_i32(m))
    mask = cout.mask
    nsync = jnp.sum(mask).astype(jnp.int32)
    newcfg = _select_commit(ctx, mask, mean)
    new_ref = _ref_if_commit(ctx, nsync > 0, mean)
    moved = (nsync > 0).astype(jnp.int32)
    rec = CommRecord(model_up=moved, model_down=moved,
                     messages=jnp.int32(0), syncs=moved, full_syncs=moved)
    return SyncOut(newcfg, new_ref, carried_v(ctx, cout), cout.rng,
                   ctx.state.extra, rec, mask.astype(jnp.int32),
                   zeros_i32(m))


# ---------------------------------------------------------------------------
# asyncify: any synchronous spec -> its event-driven counterpart
# ---------------------------------------------------------------------------

_ASYNC_TRIGGER = {
    "cadence": "events",
    "staleness": "events",
    "divergence": "events_divergence",
    "events": "events",
    "events_divergence": "events_divergence",
}


def asyncify(spec: ProtocolSpec, async_net, network=None,
             model_bytes=None) -> ProtocolSpec:
    """Rewrite ``spec`` to run on the event-driven timeline: the trigger
    is re-based on the local clock with the ``AsyncConfig``'s delay
    regime (flight times from the ``network``'s link classes and
    ``model_bytes`` payload), and — with ``async_net.aircomp`` — the
    mean/average pair is swapped for the over-the-air stages. The engine
    calls this when an ``AsyncConfig`` is attached; ``"never"`` passes
    through untouched (there is no timeline to rewrite)."""
    params = dict(spec.params)
    new_trigger = spec.trigger
    if spec.trigger != "never":
        if spec.trigger not in _ASYNC_TRIGGER:
            raise ValueError(
                f"don't know the event-driven counterpart of trigger "
                f"{spec.trigger!r} — register it (or extend "
                f"async_sync._ASYNC_TRIGGER)")
        new_trigger = _ASYNC_TRIGGER[spec.trigger]
        if spec.trigger in ("cadence", "staleness"):
            params["base"] = spec.trigger
        payload = async_net.payload_bytes
        if payload is None:
            payload = int(model_bytes) if model_bytes else 0
        params.update(
            budget=float(async_net.round_budget),
            max_delay=int(async_net.max_delay),
            link_classes=(",".join(network.link_classes)
                          if network is not None else ""),
            payload_bytes=int(payload))
    aggregate, commit = spec.aggregate, spec.commit
    if async_net.aircomp:
        if not (spec.aggregate == "mean" and spec.commit == "average"):
            raise ValueError(
                f"aircomp models the coordinator mean/average exchange "
                f"over the analog channel — aggregate={spec.aggregate!r}, "
                f"commit={spec.commit!r} has no over-the-air counterpart")
        aggregate, commit = "aircomp", "aircomp"
        params.update(snr_db=float(async_net.snr_db),
                      air_seed=int(async_net.air_seed))
    return ProtocolSpec(
        name=f"async_{spec.name or spec.trigger}", trigger=new_trigger,
        cohort=spec.cohort, aggregate=aggregate, commit=commit,
        params=params)


# ---------------------------------------------------------------------------
# presets
# ---------------------------------------------------------------------------

# cadence-synced fleet over the analog channel; b stays overridable via
# the ProtocolConfig sugar like "periodic"
AIRCOMP = ProtocolSpec(
    name="aircomp", trigger="cadence", cohort="all_reachable",
    aggregate="aircomp", commit="aircomp")
register_protocol("aircomp", AIRCOMP)

# sigma_b on the event timeline over a heterogeneous lte/edge fleet:
# edge learners' exchanges fly 1 round at the default 1 s budget, lte
# learners land synchronously — the smallest preset that exercises
# launches, flights and arrival waves (and the jaxpr audit over them)
ASYNC_PERIODIC = ProtocolSpec(
    name="async_periodic", trigger="events", cohort="all_reachable",
    aggregate="mean", commit="average",
    params={"link_classes": "lte,edge", "payload_bytes": 100_000})
register_protocol("async_periodic", ASYNC_PERIODIC)

# sigma_Delta on the event timeline: violations on slow links fly before
# they participate in the balancing augmentation
ASYNC_DYNAMIC = ProtocolSpec(
    name="async_dynamic", trigger="events_divergence", cohort="balanced",
    aggregate="mean", commit="balancing",
    params={"link_classes": "lte,edge", "payload_bytes": 100_000})
register_protocol("async_dynamic", ASYNC_DYNAMIC)
