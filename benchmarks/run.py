"""Benchmark orchestrator: one benchmark per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only NAME] [--list]
    PYTHONPATH=src python -m benchmarks.run --protocol spec.json

Prints one CSV block per benchmark plus a summary line
``name,seconds,claim_check`` and persists per-benchmark JSON under
experiments/bench/. ``--list`` enumerates the registered benchmarks
(name + paper reference) without running anything — the registry contract
CI and humans can check cheaply.

``--protocol`` runs an ARBITRARY serialized ``ProtocolSpec`` (the JSON
written by ``ProtocolSpec.to_json`` / saved next to checkpoints) through
the scan driver on the drift-MLP task and reports loss / communication —
new stage compositions are benchmarkable without writing a fig module.
Combine with ``--full`` for paper-scale rounds.
"""
from __future__ import annotations

import argparse
import sys
import time

from benchmarks import (
    async_bench,
    dynamic_amortized,
    fig5_1_dynamic_vs_periodic,
    fig5_2_fedavg,
    fig5_4_drift,
    fig5_5_deepdrive,
    fig6_1_scaleout,
    fig6_2_init_heterogeneity,
    figA6_optimizers,
    figC_unbalanced,
    fig_hierarchy,
    fig_network_regimes,
    kernel_bench,
    robust_bench,
    roofline_table,
    scan_driver,
    shard_bench,
    sync_bench,
)

ALL = [
    scan_driver,
    fig5_1_dynamic_vs_periodic,
    dynamic_amortized,
    fig5_2_fedavg,
    fig5_4_drift,
    fig5_5_deepdrive,
    fig6_1_scaleout,
    fig6_2_init_heterogeneity,
    figA6_optimizers,
    figC_unbalanced,
    fig_network_regimes,
    fig_hierarchy,
    sync_bench,
    shard_bench,
    async_bench,
    robust_bench,
    kernel_bench,
    roofline_table,
]


def run_protocol_spec(path: str, full: bool = False, m: int = 8,
                      seed: int = 0, telemetry: str = None) -> dict:
    """Drive one serialized ``ProtocolSpec`` through the scanned engine
    (drift-MLP smoke task) and report loss/communication. ``telemetry``
    streams the run's per-round records to that JSONL path
    (``repro.telemetry``)."""
    from repro.config import TelemetryConfig, TrainConfig, get_arch
    from repro.core.sync.spec import ProtocolSpec
    from repro.data.synthetic import GraphicalModelStream
    from repro.models.cnn import cnn_loss, init_cnn_params
    from repro.train.loop import run_protocol_training

    spec = ProtocolSpec.from_file(path)
    rounds = 2000 if full else 200
    cfg = get_arch("drift_mlp", smoke=True)
    telem = (TelemetryConfig(path=telemetry, per_link=True, profile=True)
             if telemetry else None)
    dl, traj = run_protocol_training(
        lambda p, b: cnn_loss(cfg, p, b),
        lambda k: init_cnn_params(cfg, k),
        GraphicalModelStream(seed=0, drift_prob=0.0),
        m=m, rounds=rounds, protocol=spec,
        train=TrainConfig(optimizer="sgd", learning_rate=0.05),
        batch=10, seed=seed, record_every=max(1, rounds // 10),
        telemetry=telem)
    if dl.recorder is not None:
        dl.recorder.close()
    row = {
        "spec": spec.to_dict(),
        "m": m,
        "rounds": rounds,
        "cumulative_loss": dl.cumulative_loss,
        "mean_round_loss": dl.cumulative_loss / (rounds * m),
        "syncs": dl.comm_totals["syncs"],
        "full_syncs": dl.comm_totals["full_syncs"],
        "model_up": dl.comm_totals["model_up"],
        "messages": dl.comm_totals["messages"],
        "comm_bytes": dl.comm_bytes(),
        "loss_curve": traj.cumulative_loss,
        "bytes_curve": traj.cumulative_bytes,
    }
    return row


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale rounds (slow)")
    ap.add_argument("--only", default=None)
    ap.add_argument("--list", action="store_true",
                    help="enumerate registered benchmarks and exit")
    ap.add_argument("--protocol", default=None, metavar="SPEC_JSON",
                    help="run a serialized ProtocolSpec through the scan "
                         "driver and report loss/comm")
    ap.add_argument("--telemetry", default=None, metavar="JSONL",
                    help="with --protocol: stream per-round telemetry "
                         "records to this JSONL file (repro.telemetry)")
    args = ap.parse_args()

    if args.telemetry and not args.protocol:
        ap.error("--telemetry requires --protocol (it instruments the "
                 "spec run)")

    if args.list:
        for mod in ALL:
            print(f"{mod.NAME}\t{mod.PAPER_REF}")
        return

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    if args.protocol:
        import re
        from benchmarks.common import save_rows
        t0 = time.perf_counter()
        row = run_protocol_spec(args.protocol, full=args.full,
                                telemetry=args.telemetry)
        name = re.sub(r"[^\w.-]", "_", row["spec"]["name"]) or "custom"
        print(f"=== protocol_spec  [{args.protocol}] ===")
        for k, v in row.items():
            if not isinstance(v, (list, dict)):
                print(f"  {k}={v}")
        path = save_rows(f"protocol_spec_{name}", [row])
        if args.telemetry:
            print(f"  -> telemetry {args.telemetry}")
        print(f"  -> saved {path} ({time.perf_counter() - t0:.1f}s)")
        return

    summary = []
    for mod in ALL:
        if args.only and args.only not in mod.NAME:
            continue
        t0 = time.perf_counter()
        print(f"\n=== {mod.NAME}  [{mod.PAPER_REF}] ===", flush=True)
        try:
            rows = mod.run(quick=not args.full)
            verdict = mod.check(rows)
            for r in rows:
                print("  " + ",".join(
                    f"{k}={v}" for k, v in r.items()
                    if not isinstance(v, (list, dict))))
        except Exception as e:  # noqa: BLE001
            import traceback
            traceback.print_exc()
            verdict = f"ERROR:{e!r}"
        dt = time.perf_counter() - t0
        print(f"  -> {verdict} ({dt:.1f}s)")
        summary.append((mod.NAME, dt, verdict))

    print("\n==== SUMMARY (name,seconds,claim_check) ====")
    ok = True
    for name, dt, verdict in summary:
        print(f"{name},{dt:.1f},{verdict}")
        ok &= not str(verdict).startswith("ERROR")
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
