import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) program.

The lines above run before ANY other import (jax locks the platform and the
device count on first init): the dry-run — and only the dry-run — sees 512
placeholder host devices so ``jax.make_mesh`` can build the production
meshes, and it stays on the CPU even where a TPU is attached, so a compile
rehearsal never takes the chip.

Usage:
    python -m repro.launch.dryrun --arch llama3-8b --shape train_4k --mesh single
    python -m repro.launch.dryrun --all --mesh both --out-dir experiments/dryrun
    python -m repro.launch.dryrun --arch mixtral-8x22b --shape train_4k \
        --mesh multi --mode train_dynamic

Per program it prints/records ``compiled.memory_analysis()`` (proves the
per-device footprint), ``compiled.cost_analysis()`` (FLOPs/bytes for the
roofline) and the parsed collective schedule.
"""
import argparse
import gc
import json
import time
import traceback

import jax

from repro.analysis import roofline as rl
from repro.config import INPUT_SHAPES, get_arch
from repro.configs import ASSIGNED_ARCHS
from repro.launch.mesh import PRODUCTION_DEVICE_KIND, make_production_mesh
from repro.launch.specs import build_program
from repro.telemetry import console_handler, get_logger

# long_500k needs sub-quadratic decode; pure full-attention archs skip it
# (DESIGN.md §Arch-applicability). llama3-8b-swa is the sliding-window
# VARIANT of a dense arch that makes the 524k shape tractable (the
# assignment's dense-arch carve-out).
LONG_CONTEXT_ARCHS = (
    "mamba2-2.7b", "hymba-1.5b", "mixtral-8x22b", "llama3-8b-swa")


def pairs_for(arch: str):
    for shape in ("train_4k", "prefill_32k", "decode_32k", "long_500k"):
        if shape == "long_500k" and arch not in LONG_CONTEXT_ARCHS:
            continue
        yield shape


def _compile(cfg, shape, mesh, mode):
    prog = build_program(cfg, shape, mesh, mode=mode)
    with mesh:
        lowered = jax.jit(
            prog.fn, in_shardings=prog.in_shardings,
            out_shardings=prog.out_shardings).lower(*prog.args)
        compiled = lowered.compile()
    return prog, compiled


def _costs(compiled, mesh) -> tuple:
    from repro.compat import cost_analysis
    ca = cost_analysis(compiled)
    stats = __import__("repro.analysis.hlo", fromlist=["hlo"]).parse_collectives(
        compiled.as_text(), mesh.size)
    return (float(ca.get("flops", 0.0)),
            float(ca.get("bytes accessed", 0.0)),
            float(stats.total_wire_bytes))


def run_one(arch: str, shape_name: str, mesh_kind: str, mode: str = "auto",
            verbose: bool = True, calibrate: bool = True) -> dict:
    """Lower+compile one (arch, shape, mesh) program and derive its roofline.

    XLA's cost analysis counts a ``while``-loop body once regardless of trip
    count, so the scan-over-layers model under-reports per-step cost. We
    therefore compile the REAL program (scan, full depth) for the artifact +
    memory analysis, plus two small UNROLLED variants (1 and 2 layers) whose
    cost difference calibrates the true per-layer flops/bytes/collectives:
        total(L) = cost(L=1) + (L - 1) * (cost(L=2) - cost(L=1)).
    """
    import dataclasses
    cfg = get_arch(arch)
    shape = INPUT_SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    t0 = time.perf_counter()
    prog, compiled = _compile(cfg, shape, mesh, mode)
    t_compile = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    report = rl.analyze(
        f"{prog.name}@{mesh_kind}", compiled, mesh.size,
        PRODUCTION_DEVICE_KIND,
        model_flops=rl.model_flops_for(cfg, shape, prog.meta["mode"]))

    if calibrate:
        c1cfg = dataclasses.replace(cfg, num_layers=1, scan_layers=False)
        c2cfg = dataclasses.replace(cfg, num_layers=2, scan_layers=False)
        _, comp1 = _compile(c1cfg, shape, mesh, mode)
        _, comp2 = _compile(c2cfg, shape, mesh, mode)
        f1, b1, w1 = _costs(comp1, mesh)
        f2, b2, w2 = _costs(comp2, mesh)
        L = cfg.num_layers
        report.flops_per_chip = f1 + (L - 1) * max(f2 - f1, 0.0)
        report.bytes_per_chip = b1 + (L - 1) * max(b2 - b1, 0.0)
        report.wire_bytes_per_chip = w1 + (L - 1) * max(w2 - w1, 0.0)
        peaks = rl.chip_peaks(PRODUCTION_DEVICE_KIND)
        report.compute_s = report.flops_per_chip / peaks.flops_bf16
        report.memory_s = report.bytes_per_chip / peaks.hbm_bw
        report.collective_s = report.wire_bytes_per_chip / peaks.ici_bw_per_link
        terms = {"compute": report.compute_s, "memory": report.memory_s,
                 "collective": report.collective_s}
        report.bottleneck = max(terms, key=terms.get)
        if report.model_flops:
            report.useful_fraction = report.model_flops / (
                report.flops_per_chip * mesh.size)
        del comp1, comp2
    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "mode": prog.meta["mode"], "num_devices": mesh.size,
        "compile_s": round(t_compile, 2),
        "ok": True,
        "calibrated": calibrate,
        "roofline": report.as_dict(),
    }
    if verbose:
        # structured events, not prints: this is library code — the CLI
        # entry points attach the text formatter (repro.telemetry)
        log = get_logger()
        log.event("dryrun_program", program=prog.name, mesh=mesh_kind,
                  chips=mesh.size, compile_s=t_compile)
        log.event("dryrun_memory", program=prog.name,
                  memory_analysis=str(mem))
        log.event("dryrun_roofline", program=prog.name,
                  flops_per_chip=report.flops_per_chip,
                  bytes_per_chip=report.bytes_per_chip,
                  wire_bytes_per_chip=report.wire_bytes_per_chip,
                  compute_s=report.compute_s, memory_s=report.memory_s,
                  collective_s=report.collective_s,
                  bottleneck=report.bottleneck,
                  collectives=str(report.collectives["by_kind"]))
    del compiled
    gc.collect()
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single",
                    choices=("single", "multi", "both"))
    ap.add_argument("--mode", default="auto")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out-dir", default="experiments/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    os.makedirs(args.out_dir, exist_ok=True)
    # the CLI is where events become text: attach the console formatter
    get_logger().add_handler(console_handler())
    meshes = ("single", "multi") if args.mesh == "both" else (args.mesh,)
    if args.all:
        if args.mode.startswith("train"):
            jobs = [(a, "train_4k") for a in ASSIGNED_ARCHS]
        else:
            jobs = [(a, s) for a in ASSIGNED_ARCHS for s in pairs_for(a)]
    else:
        if not (args.arch and args.shape):
            raise SystemExit("--arch/--shape or --all")
        jobs = [(args.arch, args.shape)]

    failures = []
    for arch, shape in jobs:
        for mk in meshes:
            tag = f"{arch}_{shape}_{mk}_{args.mode}".replace("/", "-")
            path = os.path.join(args.out_dir, tag + ".json")
            if args.skip_existing and os.path.exists(path):
                print(f"-- skip {tag} (exists)")
                continue
            try:
                rec = run_one(arch, shape, mk, args.mode)
            except Exception as e:  # noqa: BLE001 — record and continue
                traceback.print_exc()
                rec = {"arch": arch, "shape": shape, "mesh": mk,
                       "mode": args.mode, "ok": False, "error": repr(e)}
                failures.append(tag)
            with open(path, "w") as f:
                json.dump(rec, f, indent=1, default=str)
    if failures:
        print("FAILURES:", failures)
        raise SystemExit(1)
    print("dry-run complete:", len(jobs), "pairs x", len(meshes), "meshes")


if __name__ == "__main__":
    main()
