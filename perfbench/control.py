"""Readings that set a cell's limits: the program's, the control's and the
planted faults', each compared with the float32 reference by the same
numbers the benchmark's check uses. The benchmark's own runs never run
this.

    python3 perfbench/control.py --workload <cell> --seeds 1,2,3 \
        [--program] [--variants bf16,half_batch,frozen,no_exchange]

``--program`` runs the cell through the harness once per seed, in this
one process, with a short window, and prints its checks. Each variant is
the reference put in the program's place: ``bf16`` computes it in
bfloat16, the precision below the configuration's float32 (the
control); ``half_batch`` leaves out half of each batch and takes the
mean over the rest; ``frozen`` returns the state unchanged;
``no_exchange`` cuts the fleet into its cell's shards (quarters, where the
fleet is flat) and leaves out the exchange between them: each learner
takes its own part's cohort mean. One JSON line per seed and reading.
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

VARIANTS = {
    "bf16": {"dtype": "bfloat16"},
    "half_batch": {"fault": "half_batch"},
    "frozen": {"fault": "frozen"},
    "no_exchange": {"groups": "shards"},
}


def variant_readings(cell, seed: int, variants, rounds=None):
    """{variant: checks} for one seed, each against the f32 reference."""
    import jax.numpy as jnp

    from perfbench import harness, reference

    cfg, tr = cell.config, cell.traffic
    data = importlib.import_module(
        f"perfbench.data.{cfg['data']['generator']}")
    seed = harness.fold_seed(seed)
    rounds = rounds or int(tr["chunk"])

    def fleet(**kw):
        return reference.run_fleet(cell.member, cfg, tr,
                                   data.make(seed, cfg["data"]["args"]),
                                   seed, rounds, **kw)

    ref = fleet()
    out = {}
    for name in variants:
        kw = dict(VARIANTS[name])
        if "dtype" in kw:
            kw["dtype"] = jnp.dtype(kw["dtype"])
        if kw.get("groups") == "shards":
            shards = int(tr["shard_devices"])
            kw["groups"] = shards if shards > 1 else 4
        got = fleet(**kw)
        out[name] = harness.compare(
            cell, {"round_loss": got.round_loss,
                   "leaf_names": got.leaf_names,
                   "change_norm": got.change_norm,
                   "spread": got.spread, "ledger": got.ledger}, ref)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--program", action="store_true")
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--variants", default="")
    args = ap.parse_args(argv)

    from perfbench import harness

    cell = harness.Cell(args.workload)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    variants = [v for v in args.variants.split(",") if v]
    for seed in seeds:
        if args.program:
            t0 = time.perf_counter()
            r = harness.run_cell(cell, seed, args.seconds, False, t0)
            print(json.dumps({"seed": seed, "reading": "program",
                              "checks": r["checks"],
                              "correct": r["correct"],
                              "metrics": r["metrics"]}), flush=True)
        if variants:
            for name, checks in variant_readings(cell, seed,
                                                 variants).items():
                print(json.dumps({"seed": seed, "reading": name,
                                  "checks": checks}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
