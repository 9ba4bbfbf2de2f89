"""Jaxpr auditor: walk the traced computation for forbidden patterns.

``repro.analysis.contracts`` proves the SIGNATURES of the protocol
machinery; this module inspects the PROGRAM. It traces the exact
scanned-round shape the engine runs (``jax.lax.scan`` over the compiled
spec round) and recursively walks the jaxpr — into scan bodies, cond
branches, while loops and closed calls — flagging:

* **callback-in-scan** — host callbacks (``pure_callback``,
  ``io_callback``, ``jax.debug.*``) inside a scanned body: a host
  round-trip per round, the single worst thing that can happen to the
  protocol hot loop.
* **float64-leak** / **complex-leak** — any equation producing a 64-bit
  float (or complex) value. The simulator is a 32-bit program end to
  end; a float64 appearing in the trace means a Python float promoted
  something past f32 (or someone enabled x64 halfway).
* **weak-type-carry** — a scan carry leaf whose output aval is weakly
  typed: the second iteration retraces with the strong type, so the
  carry never stabilizes.
* **dynamic-shape** — an equation output whose shape is not fully
  static (polymorphic dims); the fleet plane is a statically-shaped
  program by construction.
* **int32-accumulator** — a narrow-int scan carry that grows by a
  data-dependent amount each iteration with no reset, i.e. one that can
  wrap silently. The engine's legitimate int32 counters pass: literal
  ``+1`` increments (the step clock) and counters that feed a
  ``select_n`` reset (the violation counter, the staleness ages) are
  exempt; 64-bit ledgers (the host-side bytes ledger) are exempt by
  width.

``audit_spec`` is the per-spec entry point used by the CI gate;
``audit_fn`` audits an arbitrary callable on abstract inputs. The
HLO-text backend (``audit_hlo``) applies the same dtype/callback rules
to a compiled module via the regex helpers in ``repro.analysis.hlo`` —
useful when only the lowered text of a run survives (the artifact the
roofline tooling already consumes).
"""
from __future__ import annotations

from typing import Any, List, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.analysis import hlo as hlo_mod
from repro.analysis.report import Finding

__all__ = ["audit_jaxpr", "audit_fn", "audit_spec", "audit_hlo",
           "audit_presets"]

# host-callback primitives; any of these inside a scan body is a finding
CALLBACK_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "debug_print",
    "callback", "host_callback_call", "outside_call", "infeed", "outfeed",
})

# value-preserving unary ops the accumulator analysis sees through
_TRANSPARENT = frozenset({
    "convert_element_type", "broadcast_in_dim", "reshape", "squeeze",
    "copy", "stop_gradient",
})

_BAD_DTYPES = {"float64": "float64-leak", "complex128": "complex-leak"}


def _is_literal(v) -> bool:
    return hasattr(v, "val")          # core.Literal carries .val; Var doesn't


def _sub_jaxprs(params):
    """Every sub-jaxpr referenced by one equation's params."""
    subs = []
    for val in params.values():
        vals = val if isinstance(val, (tuple, list)) else (val,)
        for v in vals:
            if hasattr(v, "jaxpr"):          # ClosedJaxpr
                subs.append(v.jaxpr)
            elif hasattr(v, "eqns"):         # raw Jaxpr
                subs.append(v)
    return subs


def _aval(v):
    return getattr(v, "aval", None)


def _dtype_name(aval) -> str:
    try:
        return jnp.dtype(aval.dtype).name
    except Exception:  # noqa: BLE001 — abstract tokens etc. have no dtype
        return ""


# ---------------------------------------------------------------------------
# the int32-accumulator rule
# ---------------------------------------------------------------------------

def _producer(jaxpr, var):
    for eqn in jaxpr.eqns:
        if any(o is var for o in eqn.outvars):
            return eqn
    return None


def _reaches(jaxpr, var, targets, depth: int = 8) -> bool:
    """Does ``var`` trace back to any of ``targets`` through producers?"""
    if _is_literal(var):
        return False
    if any(var is t for t in targets):
        return True
    if depth == 0:
        return False
    eqn = _producer(jaxpr, var)
    if eqn is None:
        return False
    return any(_reaches(jaxpr, o, targets, depth - 1)
               for o in eqn.invars if not _is_literal(o))


def _feeds_select(jaxpr, var) -> bool:
    """Is ``var`` an input of a ``select_n`` in the same jaxpr? That is
    the reset idiom (``jnp.where(done, 0, acc)``) — a bounded counter."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "select_n" and \
                any(o is var for o in eqn.invars if not _is_literal(o)):
            return True
    return False


def _is_invariant(jaxpr, var, invariants, carry_ins, depth: int = 6) -> bool:
    """Is ``var`` loop-invariant — a literal, a scan const/constvar, or a
    pure function of those? Carry leaves and per-iteration xs inputs (any
    var of unknown origin) are NOT invariant."""
    if _is_literal(var):
        return True
    if any(var is i for i in invariants):
        return True
    if any(var is c for c in carry_ins):
        return False
    if depth == 0:
        return False
    eqn = _producer(jaxpr, var)
    if eqn is None:
        return False                  # xs input / outer var: varies per step
    return all(_is_invariant(jaxpr, o, invariants, carry_ins, depth - 1)
               for o in eqn.invars)


def _unbounded_growth(jaxpr, var, carry_ins, invariants,
                      depth: int = 6) -> Optional[str]:
    """Classify how carry-out ``var`` was produced: returns a description
    of an unbounded data-dependent increment, or None when the update is
    safe (pass-through, loop-invariant step, reset via select, bounded
    op). An add is an accumulator when an operand chains back to a carry
    leaf; its increment is data-dependent when MORE than one operand is
    non-invariant (e.g. ``acc + f(y)`` where ``y`` is carried data or a
    per-iteration input)."""
    if any(var is c for c in carry_ins):
        return None                              # pass-through
    if _is_literal(var):
        return None
    eqn = _producer(jaxpr, var)
    if eqn is None:
        return None                              # invar/constvar: no growth
    name = eqn.primitive.name
    if name in _TRANSPARENT:
        ops = [o for o in eqn.invars if not _is_literal(o)]
        return _unbounded_growth(jaxpr, ops[0], carry_ins, invariants,
                                 depth) if ops and depth else None
    if name in ("add", "add_any", "sub"):
        if not any(not _is_literal(o) and _reaches(jaxpr, o, carry_ins)
                   for o in eqn.invars):
            return None                          # not an accumulator at all
        variable = [o for o in eqn.invars
                    if not _is_invariant(jaxpr, o, invariants, carry_ins)]
        if len(variable) <= 1:
            # the single non-invariant operand is the accumulator itself;
            # the step is constant (the t+1 clock) — bounded by the scan
            # length the caller chose
            return None
        if _feeds_select(jaxpr, var):
            return None                          # reset idiom downstream
        return (f"grows by a data-dependent amount each iteration "
                f"({name} with a non-constant operand) and is never reset")
    if name == "select_n":
        # a select over candidates: unbounded only if EVERY candidate is
        ops = [o for o in eqn.invars[1:] if not _is_literal(o)]
        if not depth:
            return None
        results = [_unbounded_growth(jaxpr, o, carry_ins, invariants,
                                     depth - 1) for o in ops]
        if results and all(r is not None for r in results):
            return results[0]
        return None
    if name == "cond":
        branches = eqn.params.get("branches", ())
        idx = next(i for i, o in enumerate(eqn.outvars) if o is var)
        ops = eqn.invars[1:]                     # invars[0] is the index
        if not depth:
            return None
        for br in branches:
            bj = br.jaxpr if hasattr(br, "jaxpr") else br
            tr_carries, tr_inv = [], list(bj.constvars)
            for i, o in enumerate(ops):
                if i >= len(bj.invars):
                    break
                if not _is_literal(o) and _reaches(jaxpr, o, carry_ins):
                    tr_carries.append(bj.invars[i])
                elif _is_invariant(jaxpr, o, invariants, carry_ins):
                    tr_inv.append(bj.invars[i])
            r = _unbounded_growth(bj, bj.outvars[idx], tr_carries, tr_inv,
                                  depth - 1)
            if r is not None:
                return r
        return None
    # max/min/clamp/and/or/mul-by-mask/...: treated as bounded
    return None


# ---------------------------------------------------------------------------
# the walker
# ---------------------------------------------------------------------------

def _audit_scan_carries(eqn, where: str, findings: List[Finding]) -> None:
    closed = eqn.params["jaxpr"]
    body = closed.jaxpr if hasattr(closed, "jaxpr") else closed
    nc = eqn.params.get("num_consts", 0)
    ncar = eqn.params.get("num_carry", 0)
    carry_ins = list(body.invars[nc:nc + ncar])
    carry_outs = list(body.outvars[:ncar])
    invariants = list(body.invars[:nc]) + list(body.constvars)
    for i, (cin, cout) in enumerate(zip(carry_ins, carry_outs)):
        aval = _aval(cout) or _aval(cin)
        if aval is None:
            continue
        if getattr(aval, "weak_type", False):
            findings.append(Finding(
                "audit", "weak-type-carry", f"{where}/carry[{i}]",
                f"scan carry leaf is weakly typed ({_dtype_name(aval)}): "
                f"the strong-typed second iteration forces a retrace"))
        dt = _dtype_name(aval)
        if dt.startswith("int") and jnp.dtype(aval.dtype).itemsize < 8:
            why = _unbounded_growth(body, cout, carry_ins, invariants)
            if why is not None:
                findings.append(Finding(
                    "audit", "int32-accumulator", f"{where}/carry[{i}]",
                    f"{dt} scan carry {why} — it can wrap silently; "
                    f"accumulate in int64 on the host (the bytes-ledger "
                    f"pattern) or reset it inside the loop"))


def audit_jaxpr(jaxpr, where: str = "jaxpr",
                _in_scan: bool = False) -> List[Finding]:
    """Recursively audit one (closed or raw) jaxpr. ``where`` prefixes
    the finding locations."""
    if hasattr(jaxpr, "jaxpr"):
        jaxpr = jaxpr.jaxpr
    findings: List[Finding] = []
    for v in list(jaxpr.invars) + list(jaxpr.outvars):
        aval = _aval(v)
        if aval is None:
            continue
        rule = _BAD_DTYPES.get(_dtype_name(aval))
        if rule:
            findings.append(Finding(
                "audit", rule, f"{where}/signature",
                f"jaxpr boundary carries a {_dtype_name(aval)} value "
                f"{tuple(aval.shape)}"))
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in CALLBACK_PRIMS and _in_scan:
            findings.append(Finding(
                "audit", "callback-in-scan", f"{where}/{name}",
                f"host callback {name!r} inside a scanned body: one "
                f"host round-trip per iteration"))
        for o in eqn.outvars:
            aval = _aval(o)
            if aval is None:
                continue
            rule = _BAD_DTYPES.get(_dtype_name(aval))
            if rule:
                findings.append(Finding(
                    "audit", rule, f"{where}/{name}",
                    f"{name} produces {_dtype_name(aval)} "
                    f"{tuple(aval.shape)} — the simulator is a 32-bit "
                    f"program (device side)"))
            if not all(isinstance(d, int) for d in aval.shape):
                findings.append(Finding(
                    "audit", "dynamic-shape", f"{where}/{name}",
                    f"{name} output shape {aval.shape} is not static"))
        if name == "scan":
            _audit_scan_carries(eqn, f"{where}/scan", findings)
            findings += audit_jaxpr(eqn.params["jaxpr"], f"{where}/scan",
                                    _in_scan=True)
        else:
            for sub in _sub_jaxprs(eqn.params):
                findings += audit_jaxpr(sub, f"{where}/{name}",
                                        _in_scan=_in_scan)
    return findings


def audit_fn(fn, *abstract_args, where: str = "fn") -> List[Finding]:
    """Trace ``fn`` on ``ShapeDtypeStruct`` (or array) arguments and audit
    the resulting jaxpr."""
    closed = jax.make_jaxpr(fn)(*abstract_args)
    return audit_jaxpr(closed, where)


# ---------------------------------------------------------------------------
# the spec entry points (what the CI gate runs)
# ---------------------------------------------------------------------------

def audit_spec(spec, template=None, *, rounds: int = 3) -> List[Finding]:
    """Audit the exact program the engine runs for ``spec``: ``rounds``
    compiled round calls under one ``lax.scan`` (availability-masked, so
    every stage path is in the trace)."""
    from repro.analysis.contracts import (
        abstract_state, mixed_template, _num_learners, _variant_label,
    )
    template = mixed_template() if template is None else template
    m = _num_learners(template)
    state = abstract_state(spec, template)
    acts = jax.ShapeDtypeStruct((rounds, m), jnp.bool_)
    adj = jax.ShapeDtypeStruct((m, m), jnp.bool_)
    round_fn = spec.compile()

    def chunk(stacked, st, act_seq, adjacency):
        def body(carry, act):
            cfg, s = carry
            res = round_fn(cfg, s, None, active=act, adjacency=adjacency)
            return (res.params, res.state), (res.rec, res.xfers,
                                             res.link_msgs)
        return jax.lax.scan(body, (stacked, st), act_seq)

    label = _variant_label(spec, weighted=False, with_active=True)
    try:
        closed = jax.make_jaxpr(chunk)(template, state, acts, adj)
    except Exception as e:  # noqa: BLE001
        msg = f"{type(e).__name__}: {e}"
        return [Finding("audit", "trace-error", label,
                        msg if len(msg) <= 300 else msg[:297] + "...")]
    return audit_jaxpr(closed, label)


def audit_presets(template=None,
                  presets: Optional[Sequence[str]] = None) -> List[Finding]:
    """Audit every registered preset's scanned round, on both layouts."""
    from repro.core.sync.registry import PROTOCOLS, get_protocol
    from repro.core.sync.spec import LAYOUTS
    findings: List[Finding] = []
    names = sorted(PROTOCOLS) if presets is None else list(presets)
    for name in names:
        preset = get_protocol(name)
        for layout in LAYOUTS:
            findings += audit_spec(preset.with_params(layout=layout),
                                   template)
    return findings


# ---------------------------------------------------------------------------
# HLO-text backend (repro.analysis.hlo is the parser)
# ---------------------------------------------------------------------------

_HLO_CALLBACK_MARKERS = ("custom-call", "CustomCall")
_HLO_CALLBACK_TARGETS = ("callback", "xla_python_cpu_callback",
                         "xla_ffi_python", "EmitPythonCallback")


def audit_hlo(hlo_text: str, where: str = "hlo") -> List[Finding]:
    """Apply the dtype and callback rules to compiled HLO text — the same
    artifact ``repro.analysis.hlo.parse_collectives`` (and the roofline
    report) already consumes."""
    findings: List[Finding] = []
    for i, line in enumerate(hlo_text.splitlines(), 1):
        mdef = hlo_mod._DEF_RE.match(line)
        if mdef is not None:
            # _DEF_RE: (name, shape, op); _SHAPE_RE: (dtype, dims)
            for mshape in hlo_mod._SHAPE_RE.finditer(mdef.group(2)):
                dt = mshape.group(1)
                rule = _BAD_DTYPES.get({"f64": "float64",
                                        "c128": "complex128"}.get(dt, dt))
                if rule:
                    findings.append(Finding(
                        "audit", rule, f"{where}:{i}",
                        f"compiled module materializes a {dt} tensor: "
                        f"{line.strip()[:120]}"))
        if any(mk in line for mk in _HLO_CALLBACK_MARKERS) and \
                any(tg in line for tg in _HLO_CALLBACK_TARGETS):
            findings.append(Finding(
                "audit", "host-callback", f"{where}:{i}",
                f"compiled module calls back into Python: "
                f"{line.strip()[:120]}"))
    return findings
