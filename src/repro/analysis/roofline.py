"""Three-term roofline model from the compiled dry-run artifact.

    compute term    = HLO_FLOPs_per_chip / peak_FLOP/s
    memory term     = HLO_bytes_per_chip / HBM_bw
    collective term = wire_bytes_per_chip / link_bw

The peaks come from ``CHIP_PEAKS``, one table keyed by the chip's
``device_kind`` with its source.

``cost_analysis()`` on the compiled executable reports the per-device
(post-SPMD-partition) module, so its flops/bytes are already per chip;
collective wire bytes come from ``repro.analysis.hlo``. MODEL_FLOPS uses
the 6·N·D rule (N = params, D = tokens; N_active for MoE) to measure how
much of the compiled compute is useful.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import NamedTuple, Optional

from repro.analysis import hlo as hlo_mod


class ChipPeaks(NamedTuple):
    """Published per-chip peak rates: the roofline's denominators."""
    flops_bf16: float          # FLOP/s
    hbm_bw: float              # bytes/s
    ici_bw_per_link: float     # bytes/s per interchip link


# Keyed by ``jax.Device.device_kind``. Source: Google Cloud TPU
# documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s
# of interchip interconnect per chip over 4 links (50 GB/s each).
CHIP_PEAKS = {
    "TPU v5 lite": ChipPeaks(flops_bf16=197e12, hbm_bw=819e9,
                             ici_bw_per_link=50e9),
}


def chip_peaks(device_kind: str) -> ChipPeaks:
    """The peaks of ``device_kind``; a kind not in the table is an error,
    never a default."""
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}; "
            f"known: {sorted(CHIP_PEAKS)}") from None


@dataclass
class RooflineReport:
    name: str
    num_devices: int
    flops_per_chip: float
    bytes_per_chip: float
    wire_bytes_per_chip: float
    compute_s: float
    memory_s: float
    collective_s: float
    bottleneck: str
    model_flops: Optional[float] = None          # 6*N*D (total, all chips)
    useful_fraction: Optional[float] = None      # model / (hlo * chips)
    collectives: dict = field(default_factory=dict)
    memory_stats: dict = field(default_factory=dict)

    def as_dict(self) -> dict:
        return {k: getattr(self, k) for k in (
            "name", "num_devices", "flops_per_chip", "bytes_per_chip",
            "wire_bytes_per_chip", "compute_s", "memory_s", "collective_s",
            "bottleneck", "model_flops", "useful_fraction", "collectives",
            "memory_stats")}


def model_flops_for(cfg, shape, mode: str) -> float:
    """6·N·D rule. Train counts fwd+bwd (6ND); prefill 2ND; decode 2N·B."""
    n = cfg.active_param_count()
    if mode in ("train", "train_dynamic", "train_periodic"):
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if mode == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    # decode: one token per sequence
    return 2.0 * n * shape.global_batch


def analyze(name: str, compiled, num_devices: int, device_kind: str,
            model_flops: Optional[float] = None) -> RooflineReport:
    """Roofline of a compiled program on ``num_devices`` chips of
    ``device_kind`` (peaks from :data:`CHIP_PEAKS`)."""
    from repro.compat import cost_analysis
    peaks = chip_peaks(device_kind)
    ca = cost_analysis(compiled)
    flops = float(ca.get("flops", 0.0))
    bytes_acc = float(ca.get("bytes accessed", 0.0))
    stats = hlo_mod.parse_collectives(compiled.as_text(), num_devices)
    wire = stats.total_wire_bytes

    compute_s = flops / peaks.flops_bf16
    memory_s = bytes_acc / peaks.hbm_bw
    collective_s = wire / peaks.ici_bw_per_link
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    bottleneck = max(terms, key=terms.get)

    mem = {}
    try:
        ms = compiled.memory_analysis()
        if ms is not None:
            mem = {
                "argument_bytes": int(ms.argument_size_in_bytes),
                "output_bytes": int(ms.output_size_in_bytes),
                "temp_bytes": int(ms.temp_size_in_bytes),
                "alias_bytes": int(ms.alias_size_in_bytes),
            }
    except Exception:
        pass

    useful = None
    if model_flops and flops:
        useful = model_flops / (flops * num_devices)
    return RooflineReport(
        name=name, num_devices=num_devices,
        flops_per_chip=flops, bytes_per_chip=bytes_acc,
        wire_bytes_per_chip=wire,
        compute_s=compute_s, memory_s=memory_s, collective_s=collective_s,
        bottleneck=bottleneck, model_flops=model_flops,
        useful_fraction=useful, collectives=stats.summary(),
        memory_stats=mem)


# ---------------------------------------------------------------------------
# jaxpr-level FLOP estimation (static, pre-compilation)
# ---------------------------------------------------------------------------
#
# ``analyze`` above costs a COMPILED executable; the telemetry plane's
# cost attribution (repro.telemetry.costs) needs per-BRANCH costs of an
# uncompiled round — how much compute the sync branch of the protocol's
# ``lax.cond`` would burn vs. its skip branch — which only the jaxpr still
# exposes (XLA folds the branches into one module). This is a first-order
# traversal: matmuls/convs counted exactly, reductions and elementwise ops
# at one FLOP per element, control flow by its trip count/worst branch.

def _size(aval) -> int:
    shape = getattr(aval, "shape", None)
    if shape is None:
        return 0
    return math.prod(shape) if shape else 1


def _out_size(eqn) -> int:
    return sum(_size(getattr(v, "aval", None)) for v in eqn.outvars)


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


def _dot_general_flops(eqn) -> float:
    (lc, rc), (lb, _rb) = eqn.params["dimension_numbers"]
    lhs = eqn.invars[0].aval.shape
    rhs = eqn.invars[1].aval.shape
    batch = math.prod(lhs[i] for i in lb) if lb else 1
    contract = math.prod(lhs[i] for i in lc) if lc else 1
    lfree = math.prod(d for i, d in enumerate(lhs)
                      if i not in lb and i not in lc)
    rfree = math.prod(d for i, d in enumerate(rhs)
                      if i not in _rb and i not in rc)
    return 2.0 * batch * contract * lfree * rfree


def _conv_flops(eqn) -> float:
    out = _size(eqn.outvars[0].aval)
    rhs = eqn.invars[1].aval.shape
    dn = eqn.params.get("dimension_numbers")
    out_ch = rhs[dn.rhs_spec[0]] if dn is not None else rhs[-1]
    # each output element = one dot over the kernel's in-features window
    return 2.0 * out * (math.prod(rhs) / max(out_ch, 1))


_REDUCE_PRIMS = frozenset({
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod", "reduce_and",
    "reduce_or", "argmax", "argmin", "cumsum", "cumlogsumexp", "cummax",
    "cummin", "cumprod",
})

# structural primitives that only forward values — no arithmetic
_FREE_PRIMS = frozenset({
    "broadcast_in_dim", "reshape", "transpose", "squeeze", "slice",
    "dynamic_slice", "dynamic_update_slice", "concatenate", "convert_element_type",
    "copy", "gather", "scatter", "rev", "pad", "iota", "stop_gradient",
    "split",
})


def _eqn_flops(eqn) -> float:
    name = eqn.primitive.name
    params = eqn.params
    if name == "scan":
        body = params.get("jaxpr")
        return params.get("length", 1) * jaxpr_flops(body)
    if name == "while":
        # trip count is dynamic: count one body + one cond evaluation
        return (jaxpr_flops(params.get("body_jaxpr"))
                + jaxpr_flops(params.get("cond_jaxpr")))
    if name == "cond":
        return max((jaxpr_flops(b) for b in params.get("branches", ())),
                   default=0.0)
    if name == "dot_general":
        return _dot_general_flops(eqn)
    if name == "conv_general_dilated":
        return _conv_flops(eqn)
    if name in _REDUCE_PRIMS:
        return float(_size(eqn.invars[0].aval))
    if name in _FREE_PRIMS:
        return 0.0
    subs = _sub_jaxprs(params)
    if subs:          # pjit / remat / custom_* / closed_call wrappers
        return sum(jaxpr_flops(s) for s in subs)
    # elementwise default: one FLOP per output element
    return float(_out_size(eqn))


def jaxpr_flops(jaxpr) -> float:
    """First-order FLOP estimate of a jaxpr (``ClosedJaxpr`` or raw
    ``Jaxpr``): matmul/conv exactly, reductions/elementwise at one FLOP
    per element, ``scan`` by trip count, ``cond`` by its worst branch."""
    if jaxpr is None:
        return 0.0
    jx = getattr(jaxpr, "jaxpr", jaxpr)
    return float(sum(_eqn_flops(e) for e in jx.eqns))


def format_table(reports) -> str:
    hdr = (f"{'program':44s} {'chips':>5s} {'compute_s':>10s} "
           f"{'memory_s':>10s} {'coll_s':>10s} {'bneck':>10s} "
           f"{'useful':>7s} {'arg_GB':>8s} {'temp_GB':>8s}")
    lines = [hdr, "-" * len(hdr)]
    for r in reports:
        arg = r.memory_stats.get("argument_bytes", 0) / 1e9
        tmp = r.memory_stats.get("temp_bytes", 0) / 1e9
        uf = f"{r.useful_fraction:.3f}" if r.useful_fraction else "-"
        lines.append(
            f"{r.name:44s} {r.num_devices:5d} {r.compute_s:10.3e} "
            f"{r.memory_s:10.3e} {r.collective_s:10.3e} {r.bottleneck:>10s} "
            f"{uf:>7s} {arg:8.2f} {tmp:8.2f}")
    return "\n".join(lines)
