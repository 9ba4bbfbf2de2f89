"""The preset table: the paper's sigmas as ``ProtocolSpec`` compositions.

The six built-in protocol kinds are nothing but specs over the registered
stage library (``repro.core.sync.stages``), entered into the ``PROTOCOLS``
registry — ``ProtocolConfig(kind=...)`` is sugar that resolves to the
preset with the config's parameter fields overlaid
(``repro.core.sync.spec.resolve_spec``):

  * ``nosync``      — trigger=never (identity)
  * ``periodic``    — sigma_b: cadence -> all-reachable -> mean -> average
  * ``continuous``  — sigma_b with b=1, same composition
  * ``fedavg``      — cadence -> random C-fraction -> mean -> subset
                      (McMahan et al.)
  * ``dynamic``     — sigma_Delta: divergence -> balancing augmentation ->
                      mean -> balancing commit (Algorithm 1 / Algorithm 2)
  * ``gossip``      — cadence -> neighborhood -> M–H mix -> mix
                      (coordinator-free, over the network topology)

Every preset compiles through the one generic skeleton in ``spec.py`` and
is bitwise-identical to the pre-spec monolithic operators
(``tests/golden_pr2_engine.json`` pins the PR-2 engine). New protocols
register stages + a spec (see ``repro.core.sync.staleness``) — this
module and the engine need no edits.

``apply_operator`` keeps the pre-kernel 4-tuple signature
``(new_config, new_state, CommRecord, xfers)``; ``apply_staged`` is the
same dispatch returning the full ``StageResult`` (its extra ``link_msgs``
field carries the per-link control-message counts — violation notices on
the violators' links, poll requests on the polled members' links — the
second input of the engine's per-link bytes ledger; ``sum(link_msgs) ==
CommRecord.messages`` always). Both accept a ``ProtocolConfig`` or a
``ProtocolSpec``.

Availability (``active``: optional (m,) bool mask from
``repro.network.availability``): unavailable learners keep training
locally but cannot communicate — they neither violate, nor get polled,
nor receive averages. ``active=None`` is the ideal always-on network and
preserves the pre-network engine's numerics bitwise.

Layout (the global ``layout`` spec param, ``ProtocolConfig.layout``
sugar): every preset runs either on the per-leaf pytree expressions
(``"tree"``, the default — bitwise vs the goldens) or on the blocked
fleet plane (``"flat"``, ``repro.core.sync.stages`` — params to
float-reassociation tolerance, identical sync decisions hence bitwise
comm counters away from razor-edge threshold ties, balancing in
O(m*P)). The same registered stages serve both; no preset is
layout-specific.
"""
from __future__ import annotations

from typing import Optional

import jax

# re-exported shared types (the historical import surface)
from repro.core.sync.registry import (  # noqa: F401
    CommRecord, PROTOCOLS, StageContract, StageResult, SyncState,
    register_protocol,
)
from repro.core.sync.spec import (
    _CONFIG_PARAM_FIELDS, ProtocolSpec, resolve_spec,
)


def init_state(ref_model, seed: int = 0,
               spec: Optional[ProtocolSpec] = None,
               m: Optional[int] = None) -> SyncState:
    """Fresh carried state. ``spec`` + ``m`` build the spec's extra
    carried state (e.g. the staleness counters); the built-in presets
    carry none, so plain ``init_state(ref)`` keeps working."""
    extra = {}
    if spec is not None and spec.extra_state:
        if m is None:
            raise ValueError(
                f"spec {spec.name or spec.trigger!r} carries extra state "
                f"{spec.extra_state} — init_state needs the fleet size m")
        extra = spec.init_extra(m)
    return SyncState(
        ref=ref_model,
        v=jax.numpy.zeros((), jax.numpy.int32),
        rng=jax.random.PRNGKey(seed),
        step=jax.numpy.zeros((), jax.numpy.int32),
        extra=extra,
    )


# ---------------------------------------------------------------------------
# the preset table — the ONLY place protocol kinds are enumerated
# ---------------------------------------------------------------------------

register_protocol("nosync", ProtocolSpec(name="nosync", trigger="never"))
register_protocol("periodic", ProtocolSpec(name="periodic",
                                           trigger="cadence"))
register_protocol("continuous", ProtocolSpec(name="continuous",
                                             trigger="cadence"))
register_protocol("fedavg", ProtocolSpec(name="fedavg", trigger="cadence",
                                         cohort="fraction",
                                         commit="subset"))
register_protocol("dynamic", ProtocolSpec(name="dynamic",
                                          trigger="divergence",
                                          cohort="balanced",
                                          commit="balancing"))
register_protocol("gossip", ProtocolSpec(name="gossip", trigger="cadence",
                                         cohort="neighborhood",
                                         aggregate="mix", commit="mix"))


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------

def apply_staged(proto, stacked, state: SyncState, weights=None,
                 active=None, adjacency=None) -> StageResult:
    """Run one round of the configured protocol (a ``ProtocolConfig`` or a
    ``ProtocolSpec``), returning the full ``StageResult`` (the engine's
    entry — per-link control-message counts feed the bytes ledger).

    ``active``: optional (m,) bool reachability mask for this round;
    ``adjacency``: optional (m, m) bool peer overlay (required by specs
    with ``uses_overlay``, e.g. gossip).
    """
    spec = resolve_spec(proto)
    if not spec.param("weighted"):
        weights = None
    return spec.compile()(stacked, state, weights, active=active,
                          adjacency=adjacency)


def apply_operator(proto, stacked, state: SyncState, weights=None,
                   active=None, adjacency=None):
    """The pre-kernel entry point, signature unchanged: returns
    ``(new_config, new_state, CommRecord, xfers)``."""
    res = apply_staged(proto, stacked, state, weights, active=active,
                       adjacency=adjacency)
    return res.params, res.state, res.rec, res.xfers


# ---------------------------------------------------------------------------
# legacy named operators (compatibility surface): each forces its preset's
# composition and reads parameters from the passed config
# ---------------------------------------------------------------------------

def _preset_op(kind: str):
    def op(cfg, stacked, state: SyncState, weights=None, active=None,
           adjacency=None) -> StageResult:
        preset = PROTOCOLS[kind]
        overrides = {f: getattr(cfg, f) for f in _CONFIG_PARAM_FIELDS
                     if f in preset.known_params and hasattr(cfg, f)}
        spec = preset.with_params(**overrides)
        # pre-spec contract of the NAMED operators: an explicitly passed
        # ``weights`` is used as-is — the weighted/unweighted gate lives
        # in ``apply_staged``, not here
        return spec.compile()(stacked, state, weights, active=active,
                              adjacency=adjacency)
    op.__name__ = kind
    op.__doc__ = (f"The {kind!r} preset as a standalone operator "
                  f"(weights, when passed, are applied as-is).")
    return op


nosync = _preset_op("nosync")
periodic = _preset_op("periodic")
fedavg = _preset_op("fedavg")
dynamic = _preset_op("dynamic")
gossip = _preset_op("gossip")

OPERATORS = {
    "nosync": nosync,
    "periodic": periodic,
    "continuous": periodic,     # cfg.b == 1
    "fedavg": fedavg,
    "dynamic": dynamic,
    "gossip": gossip,
}
