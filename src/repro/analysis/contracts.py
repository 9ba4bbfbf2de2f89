"""Abstract contract checking for the protocol-spec API — zero FLOPs.

Every registered stage (``repro.core.sync.registry``) declares a
``StageContract``; this module VERIFIES those declarations instead of
trusting them, by abstract evaluation (``jax.eval_shape``) of each stage
and of each compiled round over a mixed-dtype model template. Nothing is
ever executed on a device: the whole preset × layout × weighted ×
availability matrix (plus the two-tier hierarchy for every coordinator
preset) traces in seconds and proves, for each combination:

* **trigger** — the gate is a scalar bool; a conditional trigger's hot
  mask is (m,) bool and its count int32; condition auxiliaries match the
  declared ``cond_aux`` keys; trigger-owned extra state keeps its
  declared names/dtypes through ``init_extra``/``commit_extra``/
  ``skip_extra``.
* **cohort** — the mask is (m,) bool, the RNG key dtype is carried
  unchanged, the violation counter is owned exactly by stages declaring
  ``manages_v`` (scalar int32 + scalar bool full flag), ``aux`` keys
  match the declaration.
* **aggregate** — the output matches its declared kind: ``"model"`` is a
  single-model pytree, ``"fleet"`` an (m, ...) stacked pytree, in the
  leaf dtypes on every layout (the plane layouts' blocked plane is the
  stacked leaves and its row the model pytree).
* **commit + round** — the committed configuration and reference keep
  the input shapes AND dtypes bitwise (no promotion drift past the
  boundary), ``v``/``step``/``CommRecord``/``xfers``/``link_msgs`` are
  int32, and no weak type leaks into the scan carry.
* **layout equivalence** — the tree and flat rounds produce abstractly
  IDENTICAL ``StageResult`` trees (shape, dtype, weak type). This is the
  conformance harness for any future layout (e.g. a device-sharded
  plane): add the layout string to ``spec.LAYOUTS`` and every registered
  preset is checked against the tree reference for free.

``check_all()`` is the CI entry point (``python -m repro.analysis
--contracts``): registry coverage + the full preset matrix.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.analysis.report import Finding
from repro.core.sync import registry, stages
from repro.core.sync.registry import (
    AGGREGATES, COHORTS, COMMITS, PROTOCOLS, TRIGGERS, StageCtx, SyncState,
)
from repro.core.sync.spec import (
    GLOBAL_PARAMS, LAYOUTS, PLANE_LAYOUTS, ProtocolSpec, pin_rows,
)

__all__ = [
    "DEFAULT_M", "mixed_template", "abstract_state", "check_registry",
    "check_spec", "check_round", "check_layout_equivalence",
    "check_hierarchy", "check_preset_matrix", "check_all",
]

DEFAULT_M = 4            # fleet size of the abstract template
DEFAULT_CLUSTERS = 2     # hierarchy width (must divide DEFAULT_M)


def mixed_template(m: int = DEFAULT_M):
    """A deliberately mixed-dtype (f32 + bf16) stacked model template:
    promotion bugs that a homogeneous-f32 fleet can never exhibit (a
    weight vector downcast to bfloat16, a mean accumulated in the leaf
    dtype) change the abstract output here and fail the check."""
    return {
        "w": jax.ShapeDtypeStruct((m, 3, 2), jnp.float32),
        "b": jax.ShapeDtypeStruct((m, 2), jnp.bfloat16),
    }


def _num_learners(template) -> int:
    return jax.tree.leaves(template)[0].shape[0]


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def abstract_state(spec: ProtocolSpec, template) -> SyncState:
    """The abstract ``SyncState`` matching ``init_state(ref, seed, spec=,
    m=)`` for a template fleet — extra state included, no arrays built."""
    m = _num_learners(template)
    ref = jax.tree.map(lambda s: _sds(s.shape[1:], s.dtype), template)
    extra = jax.eval_shape(lambda: spec.init_extra(m))
    i32 = _sds((), jnp.int32)
    return SyncState(ref=ref, v=i32, rng=_sds((2,), jnp.uint32), step=i32,
                     extra=extra)


# ---------------------------------------------------------------------------
# abstract-signature helpers
# ---------------------------------------------------------------------------

def _sig(x):
    """(shape, dtype) signature of one abstract leaf (None passes through)."""
    if x is None:
        return None
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        return (tuple(x.shape), jnp.dtype(x.dtype).name)
    return ("py", type(x).__name__)


def _wsig(x):
    """Signature including the weak-type bit — the round-boundary check:
    a weak scalar leaking into the scan carry retraces every round."""
    if x is None:
        return None
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        return (tuple(x.shape), jnp.dtype(x.dtype).name,
                bool(getattr(x, "weak_type", False)))
    return ("py", type(x).__name__)


def _sig_tree(t, sig=_sig):
    return jax.tree.map(sig, t)


def _is_scalar(x, dtype) -> bool:
    return (x is not None and hasattr(x, "shape") and tuple(x.shape) == ()
            and jnp.dtype(x.dtype) == jnp.dtype(dtype))


def _is_vec(x, n, dtype) -> bool:
    return (x is not None and hasattr(x, "shape") and tuple(x.shape) == (n,)
            and jnp.dtype(x.dtype) == jnp.dtype(dtype))


def _fmt(e: Exception) -> str:
    msg = f"{type(e).__name__}: {e}"
    return msg if len(msg) <= 300 else msg[:297] + "..."


# ---------------------------------------------------------------------------
# the stage harness: one abstract trace through every slot
# ---------------------------------------------------------------------------

def _trace_slots(spec: ProtocolSpec, template, *, weighted: bool,
                 with_active: bool) -> Dict[str, Any]:
    """Abstract-evaluate every slot of ``spec`` on ``template``, mirroring
    ``_compiled_round``'s exact ctx wiring, and return the per-slot
    ``ShapeDtypeStruct`` trees."""
    trig, coh, agg, com = spec.stage_records()
    p = spec.resolved_params()
    flat_layout = p["layout"] in PLANE_LAYOUTS
    m = _num_learners(template)
    state = abstract_state(spec, template)
    w = _sds((m,), jnp.float32) if weighted else None
    act = _sds((m,), jnp.bool_) if with_active else None
    adj = _sds((m, m), jnp.bool_)

    def run(stacked, st, weights, active, adjacency):
        out = {}
        t = st.step + 1
        reach = stages.cohort_all(m, active)
        ctx = StageCtx(params=p, stacked=stacked, state=st, weights=weights,
                       active=active, adjacency=adjacency, m=m, t=t,
                       reach=reach)
        g = trig.gate(ctx)
        out["gate"] = jnp.asarray(g) if isinstance(g, bool) else g
        if flat_layout:
            ctx = ctx._replace(stacked=pin_rows(stacked), plane=True)
        hot, nhot = reach, None
        if trig.condition is not None:
            cond = trig.condition(ctx)
            hot, nhot = cond[0], cond[1]
            out["hot"], out["nhot"] = hot, nhot
            if len(cond) > 2:
                out["cond_aux"] = cond[2]
                ctx = ctx._replace(cond_aux=cond[2])
        cout = coh.fn(ctx, hot, nhot, st.rng)
        out["cohort"] = cout
        out["aggregate"] = agg.fn(ctx, cout)
        out["commit"] = com.fn(ctx, cout, out["aggregate"], hot, nhot)
        out["commit_extra"] = trig.commit_extra(ctx, cout.mask)
        out["skip_extra"] = trig.skip_extra(ctx)
        return out

    traced = jax.eval_shape(run, template, state, w, act, adj)
    traced["init_extra"] = state.extra
    traced["rng"] = state.rng
    return traced


def _variant_label(spec: ProtocolSpec, *, weighted: bool,
                   with_active: bool) -> str:
    name = spec.name or (f"{spec.trigger}/{spec.cohort}/"
                         f"{spec.aggregate}/{spec.commit}")
    tags = [spec.param("layout")]
    if weighted:
        tags.append("weighted")
    if not with_active:
        tags.append("ideal")
    return f"{name}[{','.join(tags)}]"


def check_spec(spec: ProtocolSpec, template=None, *, weighted: bool = False,
               with_active: bool = True) -> List[Finding]:
    """Verify every slot of one spec against its stages' declared
    contracts by abstract evaluation. Empty list = clean."""
    template = mixed_template() if template is None else template
    m = _num_learners(template)
    trig, coh, agg, com = spec.stage_records()
    label = _variant_label(spec, weighted=weighted, with_active=with_active)
    out: List[Finding] = []

    def bad(rule, slot, msg):
        out.append(Finding("contracts", rule, f"{label}/{slot}", msg))

    try:
        tr = _trace_slots(spec, template, weighted=weighted,
                          with_active=with_active)
    except Exception as e:  # noqa: BLE001 — any trace failure is a finding
        return [Finding("contracts", "trace-error", label, _fmt(e))]

    ref_sig = _sig_tree(jax.tree.map(lambda s: _sds(s.shape[1:], s.dtype),
                                     template))
    tmpl_sig = _sig_tree(template)
    key_sig = _sig(tr["rng"])

    # ---- trigger ------------------------------------------------------
    gate = tr["gate"]
    if not (hasattr(gate, "shape") and tuple(gate.shape) == ()
            and jnp.dtype(gate.dtype) == jnp.dtype(jnp.bool_)):
        bad("gate-shape", f"trigger:{trig.name}",
            f"gate must be a scalar bool, got {_sig(gate)}")
    if trig.condition is not None:
        if not _is_vec(tr["hot"], m, jnp.bool_):
            bad("hot-mask", f"trigger:{trig.name}",
                f"condition hot mask must be ({m},) bool, "
                f"got {_sig(tr['hot'])}")
        if not _is_scalar(tr["nhot"], jnp.int32):
            bad("hot-count", f"trigger:{trig.name}",
                f"condition count must be scalar int32, "
                f"got {_sig(tr['nhot'])}")
        declared_aux = tuple(sorted(trig.contract.cond_aux)) \
            if trig.contract else ()
        got_aux = tr.get("cond_aux")
        got_keys = tuple(sorted(got_aux)) if isinstance(got_aux, dict) \
            else ()
        if got_keys != declared_aux:
            bad("cond-aux", f"trigger:{trig.name}",
                f"condition aux keys {list(got_keys)} != declared "
                f"{list(declared_aux)}")
        for k in got_keys:
            vsig = _sig(got_aux[k])
            if vsig is None or vsig[0] == "py" or vsig[0][:1] != (m,):
                bad("cond-aux", f"trigger:{trig.name}",
                    f"condition aux {k!r} must be an (m, ...) array, "
                    f"got {vsig}")

    # trigger-owned extra state: declared names/dtypes, identical
    # signatures through the init/commit/skip paths
    declared = dict(trig.contract.extra_state) if trig.contract else {}
    init_sig = _sig_tree(tr["init_extra"])
    if sorted(init_sig) != sorted(declared):
        bad("extra-state", f"trigger:{trig.name}",
            f"init_extra keys {sorted(init_sig)} != declared "
            f"{sorted(declared)}")
    else:
        for k, dt in declared.items():
            shape, got_dt = init_sig[k]
            if got_dt != jnp.dtype(dt).name:
                bad("extra-state", f"trigger:{trig.name}",
                    f"extra {k!r} is {got_dt}, declared {dt}")
    for path in ("commit_extra", "skip_extra"):
        if _sig_tree(tr[path]) != init_sig:
            bad("extra-state", f"trigger:{trig.name}",
                f"{path} signature {_sig_tree(tr[path])} != init_extra "
                f"{init_sig} — the carried dict must be shape/dtype "
                f"stable across sync and skip rounds")

    # ---- cohort -------------------------------------------------------
    cout = tr["cohort"]
    if not _is_vec(cout.mask, m, jnp.bool_):
        bad("cohort-mask", f"cohort:{coh.name}",
            f"mask must be ({m},) bool, got {_sig(cout.mask)}")
    if _sig(cout.rng) != key_sig:
        bad("rng-dtype", f"cohort:{coh.name}",
            f"carried RNG key {_sig(cout.rng)} != input key {key_sig}")
    manages = bool(coh.contract and coh.contract.manages_v)
    if manages:
        if not _is_scalar(cout.v, jnp.int32):
            bad("counter-dtype", f"cohort:{coh.name}",
                f"declares manages_v: v must be scalar int32, "
                f"got {_sig(cout.v)}")
        if not _is_scalar(cout.full, jnp.bool_):
            bad("counter-dtype", f"cohort:{coh.name}",
                f"declares manages_v: full must be scalar bool, "
                f"got {_sig(cout.full)}")
    else:
        if cout.v is not None or cout.full is not None:
            bad("counter-owner", f"cohort:{coh.name}",
                "returns v/full without declaring manages_v")
    declared_aux = tuple(sorted(coh.contract.aux)) if coh.contract else ()
    got_keys = tuple(sorted(cout.aux)) if isinstance(cout.aux, dict) else ()
    if got_keys != declared_aux:
        bad("cohort-aux", f"cohort:{coh.name}",
            f"aux keys {list(got_keys)} != declared {list(declared_aux)}")

    # ---- aggregate ----------------------------------------------------
    kind = agg.contract.out if agg.contract else "model"
    agg_sig = _sig_tree(tr["aggregate"])
    want = ref_sig if kind == "model" else tmpl_sig
    if agg_sig != want:
        bad("aggregate-out", f"aggregate:{agg.name}",
            f"declared out={kind!r}: abstract output {agg_sig} != "
            f"expected {want}")

    # ---- commit -------------------------------------------------------
    sout = tr["commit"]
    if _sig_tree(sout.params) != tmpl_sig:
        bad("commit-params", f"commit:{com.name}",
            f"committed configuration {_sig_tree(sout.params)} != input "
            f"{tmpl_sig} — shapes and dtypes must be preserved bitwise")
    if _sig_tree(sout.ref) != ref_sig:
        bad("commit-ref", f"commit:{com.name}",
            f"committed reference {_sig_tree(sout.ref)} != input "
            f"{ref_sig}")
    if not _is_scalar(sout.v, jnp.int32):
        bad("counter-dtype", f"commit:{com.name}",
            f"carried v must be scalar int32, got {_sig(sout.v)}")
    if _sig(sout.rng) != key_sig:
        bad("rng-dtype", f"commit:{com.name}",
            f"carried RNG key {_sig(sout.rng)} != input key {key_sig}")
    for fname, fval in sout.rec._asdict().items():
        if not _is_scalar(fval, jnp.int32):
            bad("ledger-dtype", f"commit:{com.name}",
                f"CommRecord.{fname} must be scalar int32, "
                f"got {_sig(fval)}")
    if not _is_vec(sout.xfers, m, jnp.int32):
        bad("ledger-dtype", f"commit:{com.name}",
            f"xfers must be ({m},) int32, got {_sig(sout.xfers)}")
    if not _is_vec(sout.link_msgs, m, jnp.int32):
        bad("ledger-dtype", f"commit:{com.name}",
            f"link_msgs must be ({m},) int32, got {_sig(sout.link_msgs)}")
    return out


# ---------------------------------------------------------------------------
# full-round checks (through spec.compile, the thing the engine scans)
# ---------------------------------------------------------------------------

def _round_sds(spec: ProtocolSpec, template, *, weighted: bool,
               with_active: bool):
    m = _num_learners(template)
    state = abstract_state(spec, template)
    w = _sds((m,), jnp.float32) if weighted else None
    act = _sds((m,), jnp.bool_) if with_active else None
    adj = _sds((m, m), jnp.bool_)
    fn = spec.compile()
    return jax.eval_shape(
        lambda s, st, w_, a_, ad: fn(s, st, w_, active=a_, adjacency=ad),
        template, state, w, act, adj)


def check_round(spec: ProtocolSpec, template=None, *,
                weighted: bool = False,
                with_active: bool = True) -> List[Finding]:
    """The round-boundary invariants of one compiled spec: the
    ``StageResult`` that enters the scan carry keeps the input signatures
    exactly — dtypes, shapes AND weak-type bits."""
    template = mixed_template() if template is None else template
    m = _num_learners(template)
    label = _variant_label(spec, weighted=weighted, with_active=with_active)
    out: List[Finding] = []

    def bad(rule, msg):
        out.append(Finding("contracts", rule, f"{label}/round", msg))

    try:
        res = _round_sds(spec, template, weighted=weighted,
                         with_active=with_active)
    except Exception as e:  # noqa: BLE001
        return [Finding("contracts", "trace-error", f"{label}/round",
                        _fmt(e))]

    tmpl_wsig = _sig_tree(template, _wsig)
    ref_wsig = _sig_tree(jax.tree.map(lambda s: _sds(s.shape[1:], s.dtype),
                                      template), _wsig)
    if _sig_tree(res.params, _wsig) != tmpl_wsig:
        bad("round-params",
            f"committed configuration {_sig_tree(res.params, _wsig)} != "
            f"input {tmpl_wsig} — promotion or weak-type drift across the "
            f"round boundary would retrace every scan iteration")
    if _sig_tree(res.state.ref, _wsig) != ref_wsig:
        bad("round-ref",
            f"carried reference {_sig_tree(res.state.ref, _wsig)} != "
            f"input {ref_wsig}")
    state0 = abstract_state(spec, template)
    for fname in ("v", "step"):
        got = getattr(res.state, fname)
        if _wsig(got) != ((), "int32", False):
            bad("round-counters",
                f"state.{fname} must be a strong scalar int32, "
                f"got {_wsig(got)}")
    if _wsig(res.state.rng) != _wsig(state0.rng):
        bad("rng-dtype",
            f"carried RNG key {_wsig(res.state.rng)} != input "
            f"{_wsig(state0.rng)}")
    if _sig_tree(res.state.extra, _wsig) != _sig_tree(state0.extra, _wsig):
        bad("round-extra",
            f"carried extra state {_sig_tree(res.state.extra, _wsig)} != "
            f"initial {_sig_tree(state0.extra, _wsig)}")
    for fname, fval in res.rec._asdict().items():
        if _wsig(fval) != ((), "int32", False):
            bad("ledger-dtype",
                f"CommRecord.{fname} must be a strong scalar int32, "
                f"got {_wsig(fval)}")
    for fname in ("xfers", "link_msgs"):
        if _wsig(getattr(res, fname)) != ((m,), "int32", False):
            bad("ledger-dtype",
                f"{fname} must be a strong ({m},) int32, "
                f"got {_wsig(getattr(res, fname))}")
    return out


def check_layout_equivalence(spec: ProtocolSpec, template=None, *,
                             layouts: Sequence[str] = LAYOUTS,
                             weighted: bool = False,
                             with_active: bool = True) -> List[Finding]:
    """Prove the layouts are abstractly INTERCHANGEABLE: every layout's
    compiled round maps the same inputs to an identical ``StageResult``
    signature tree (shape, dtype, weak type — structure included).

    This is the conformance harness for new fleet backends: a future
    ``layout="sharded"`` plane joins the check by appearing in
    ``spec.LAYOUTS``, and every registered preset is then held to the
    tree reference without writing a single new test."""
    template = mixed_template() if template is None else template
    out: List[Finding] = []
    sigs = {}
    for layout in layouts:
        s = spec.with_params(layout=layout)
        label = _variant_label(s, weighted=weighted, with_active=with_active)
        try:
            res = _round_sds(s, template, weighted=weighted,
                             with_active=with_active)
        except Exception as e:  # noqa: BLE001
            out.append(Finding("contracts", "trace-error",
                               f"{label}/round", _fmt(e)))
            continue
        sigs[layout] = (jax.tree.structure(res, is_leaf=lambda x: x is None),
                        _sig_tree(res, _wsig))
    if len(sigs) < 2:
        return out
    base_layout = next(iter(sigs))
    base = sigs[base_layout]
    name = spec.name or spec.trigger
    for layout, sig in sigs.items():
        if layout != base_layout and sig != base:
            out.append(Finding(
                "contracts", "layout-equivalence",
                f"{name}[{base_layout} vs {layout}]",
                f"abstract StageResult trees differ between layouts: "
                f"{base[1]} vs {sig[1]}"))
    return out


def check_hierarchy(spec: ProtocolSpec, template=None, *,
                    num_clusters: int = DEFAULT_CLUSTERS) -> List[Finding]:
    """Abstract conformance of the two-tier hierarchy for one intra-tier
    spec: the committed configuration keeps the input signatures, and the
    member/aggregator ledger vectors are int32 of the right lengths."""
    from repro.config import HierarchyConfig, ProtocolConfig
    from repro.core.sync.hierarchy import apply_hierarchical, init_hier_state
    from repro.core.sync.spec import resolve_spec

    template = mixed_template() if template is None else template
    m = _num_learners(template)
    g = num_clusters
    name = spec.name or spec.trigger
    label = f"{name}[{spec.param('layout')},hier:{g}]"
    out: List[Finding] = []

    def bad(rule, msg):
        out.append(Finding("contracts", rule, label, msg))

    tiers = HierarchyConfig(num_clusters=g,
                            inter=ProtocolConfig(kind="periodic"))
    ref = jax.tree.map(lambda s: _sds(s.shape[1:], s.dtype), template)
    act = _sds((m,), jnp.bool_)
    try:
        hstate = jax.eval_shape(
            lambda r: init_hier_state(r, tiers, 0, m=m, intra_spec=spec,
                                      inter_spec=resolve_spec(tiers.inter)),
            ref)
        res = jax.eval_shape(
            lambda s, hs, a: apply_hierarchical(spec, tiers, s, hs, None,
                                                active=a),
            template, hstate, act)
    except Exception as e:  # noqa: BLE001
        return [Finding("contracts", "trace-error", label, _fmt(e))]

    if _sig_tree(res.params, _wsig) != _sig_tree(template, _wsig):
        bad("round-params",
            f"hierarchical round output {_sig_tree(res.params, _wsig)} != "
            f"input {_sig_tree(template, _wsig)}")
    for fname, n in (("member_xfers", m), ("member_msgs", m),
                     ("agg_xfers", g), ("agg_msgs", g)):
        if _wsig(getattr(res, fname)) != ((n,), "int32", False):
            bad("ledger-dtype",
                f"{fname} must be a strong ({n},) int32, "
                f"got {_wsig(getattr(res, fname))}")
    for fname, fval in res.rec._asdict().items():
        if _wsig(fval) != ((), "int32", False):
            bad("ledger-dtype",
                f"CommRecord.{fname} must be a strong scalar int32, "
                f"got {_wsig(fval)}")
    return out


# ---------------------------------------------------------------------------
# registry coverage + the full matrix
# ---------------------------------------------------------------------------

def check_registry() -> List[Finding]:
    """Every registered stage must DECLARE a contract; triggers' declared
    extra state must match their abstract ``init_extra`` output."""
    out: List[Finding] = []
    for slot, reg in (("trigger", TRIGGERS), ("cohort", COHORTS),
                      ("aggregate", AGGREGATES), ("commit", COMMITS)):
        for name, rec in sorted(reg.items()):
            if rec.contract is None:
                out.append(Finding(
                    "contracts", "missing-contract", f"{slot}:{name}",
                    "registered without a StageContract — declare the "
                    "stage's shape/dtype promises at registration"))
    m = DEFAULT_M
    for name, rec in sorted(TRIGGERS.items()):
        if rec.contract is None:
            continue
        params = dict(GLOBAL_PARAMS)
        params.update(rec.params)
        try:
            extra = jax.eval_shape(lambda: rec.init_extra(params, m))
        except Exception as e:  # noqa: BLE001
            out.append(Finding("contracts", "trace-error",
                               f"trigger:{name}/init_extra", _fmt(e)))
            continue
        got = _sig_tree(extra)
        declared = dict(rec.contract.extra_state)
        if sorted(got) != sorted(declared):
            out.append(Finding(
                "contracts", "extra-state", f"trigger:{name}",
                f"init_extra keys {sorted(got)} != declared "
                f"{sorted(declared)}"))
            continue
        for k, dt in declared.items():
            shape, got_dt = got[k]
            if got_dt != jnp.dtype(dt).name:
                out.append(Finding(
                    "contracts", "extra-state", f"trigger:{name}",
                    f"extra {k!r} is {got_dt}, declared {dt}"))
    return out


def check_preset_matrix(template=None,
                        presets: Optional[Sequence[str]] = None
                        ) -> List[Finding]:
    """Every registered preset × layout × {weighted, unweighted} ×
    {masked, ideal} combination, plus layout equivalence per preset and
    the two-tier hierarchy for every coordinator preset."""
    template = mixed_template() if template is None else template
    out: List[Finding] = []
    names = sorted(PROTOCOLS) if presets is None else list(presets)
    for name in names:
        preset = registry.get_protocol(name)
        for layout in LAYOUTS:
            s = preset.with_params(layout=layout)
            for weighted in (False, True):
                for with_active in (True, False):
                    out += check_spec(s, template, weighted=weighted,
                                      with_active=with_active)
                    out += check_round(s, template, weighted=weighted,
                                       with_active=with_active)
        for weighted in (False, True):
            out += check_layout_equivalence(preset, template,
                                            weighted=weighted)
        if preset.uses_coordinator:
            for layout in LAYOUTS:
                out += check_hierarchy(preset.with_params(layout=layout),
                                       template)
    return out


def check_all(template=None) -> List[Finding]:
    """The CI gate: registry coverage + the full preset matrix."""
    return check_registry() + check_preset_matrix(template)
