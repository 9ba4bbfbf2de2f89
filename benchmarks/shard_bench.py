"""Device-sharded fleet plane vs single-device flat plane (ISSUE 8).

Sweeps the scanned engine under ``layout="sharded"`` across 1/2/4/8
simulated host devices (``XLA_FLAGS=--xla_force_host_platform_device_count``
— the benchmark re-execs itself in a subprocess when the parent jax
initialized with fewer devices, since the flag must precede jax init)
on two fleet members:

* the drift MLP at m ∈ {200, 2000} (quick) + 10000 (--full) — the
  production-scale regime the sharded plane exists for, and
* the paper's 1,199,882-parameter MNIST CNN at m = 200 only: the
  (m, P) plane at m = 2000 × 1.2M params is ~19 GB of carry, beyond the
  CI runner — the memory bound is exactly why the m axis shards; the
  row documents it rather than silently skipping.

Every sharded run asserts counter equality against a ``layout="flat"``
run of the identical fixture (same seeds, same chunks, same number of
``run_chunk`` dispatches) — comm counters and the per-link transfer
totals must match bitwise. Reported per row: steady-state rounds/sec
(best-of-reps over a warm chunk), speedup vs the 1-device sharded run,
and bytes-crossing-devices per round — measured at the largest device
count by parsing the compiled round's collectives
(``repro.analysis.hlo.parse_collectives``: the gated all-reduce is the
worst-case sync the paper's bound prices), with the ring-all-reduce
estimate ``2 (n-1)/n · P · 4`` per device alongside.

Where scaling does NOT show: forced host devices time-slice the same
CPU cores, so rounds/sec only scales when the runner has spare physical
cores (the meta row records ``cores``; with cores < 2 the sweep is a
correctness sweep, and ``check`` does not demand speedup it cannot
observe). Worse, at large P the host backend's cross-shard collectives
are thread rendezvous on those shared cores: the 1.2M CNN at d=2 on a
1-core host measured 0.01 rounds/sec (~60x slower than flat), so quick
mode runs the CNN's sharded config at d=1 only and ``--full`` owns the
CNN multi-device sweep. Real scaling needs real devices — the point of
the sweep is that the SAME engine program is what runs there.

Rows persist to experiments/bench/shard_bench.json (nightly
``BENCH_shard`` artifact).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

from benchmarks.common import save_rows, timed

NAME = "shard_bench"
PAPER_REF = "ISSUE 8 tentpole (device-sharded fleet plane)"

FORCED_DEVICES = 8
DEVICE_SWEEP = (1, 2, 4, 8)
_CHILD_FLAG = "--emit-rows"


def _engine(arch_smoke, m, layout, devices, rounds, batch, reps):
    """Run one fixture: warm-up chunk + best-of-reps timed chunks.
    Returns (row, comm_totals, link_xfers, dl)."""
    import numpy as np
    from repro.config import ProtocolConfig, TrainConfig, get_arch
    from repro.core.divergence import flat_size
    from repro.core.protocol import DecentralizedLearner
    from repro.data.pipeline import LearnerStreams
    from repro.data.synthetic import GraphicalModelStream, SyntheticMNIST
    from repro.models.cnn import cnn_loss, init_cnn_params

    arch, smoke = arch_smoke
    cfg = get_arch(arch, smoke=smoke)
    if arch == "mnist_cnn":
        src = SyntheticMNIST(seed=0, image_size=14 if smoke else 28)
    else:
        src = GraphicalModelStream(seed=0, drift_prob=0.0)
    streams = LearnerStreams(src, m, batch=batch, seed=0)
    print(f"[shard_bench] {arch} m={m} {layout} devices={devices}...",
          file=sys.stderr, flush=True)
    proto = ProtocolConfig(kind="dynamic", b=2, delta=0.5, layout=layout,
                           shard_devices=devices)
    dl = DecentralizedLearner(
        lambda p, b: cnn_loss(cfg, p, b),
        lambda k: init_cnn_params(cfg, k), m, proto,
        TrainConfig(optimizer="sgd", learning_rate=0.05))
    chunk = streams.next_chunk(rounds)
    dl.run_chunk(chunk)                       # compile + steady state
    best = float("inf")
    for _ in range(reps):
        _, dt = timed(lambda: dl.run_chunk(chunk))
        best = min(best, dt)
    row = {
        "arch": arch, "m": m, "layout": layout, "devices": devices,
        "params": flat_size(dl.sync_state.ref),
        "rounds_per_sec": round(rounds / best, 2),
    }
    return row, dict(dl.comm_totals), np.asarray(dl.link_xfer_totals), dl


def _wire_bytes(dl, streams_batch):
    """Static collective bytes of ONE compiled round on the fleet mesh —
    the gated worst-case sync (both branches lower)."""
    import jax
    from repro.analysis.hlo import parse_collectives
    from repro.core import shard

    with shard.use_fleet(dl.fleet):
        compiled = jax.jit(dl._make_step()).lower(
            dl.params, dl.opt_state, dl.sync_state,
            streams_batch).compile()
    stats = parse_collectives(compiled.as_text(), dl.fleet.n_devices)
    return stats.summary()


def _sweep(quick: bool):
    import jax
    import numpy as np

    n_dev = len(jax.devices())
    platform = jax.devices()[0].platform
    rows = [{
        "layout": "meta", "platform": platform, "visible_devices": n_dev,
        "cores": os.cpu_count(),
        "scaling_expected": (os.cpu_count() or 1) >= 4,
        "note": ("forced host devices share the runner's cores; "
                 "rounds/sec scales only with spare physical cores"),
    }]
    # (arch, m, rounds, batch, reps, device subset). Quick mode keeps the
    # full 1/2/4/8 sweep on the MLP; the 1.2M CNN's multi-device configs
    # are gated behind --full: on forced host devices every cross-shard
    # collective is a thread rendezvous on the runner's core(s), and at
    # 1.2M params that rendezvous dominates — measured 0.01 rounds/sec at
    # d=2 on a 1-core host (~100 s/round, 60x slower than flat) with XLA
    # repeatedly logging stuck-participant warnings. Real meshes pay a
    # NIC, not a mutex; quick mode proves CNN counter equality at d=1
    # and leaves the d>1 wall-clock to hardware that has devices.
    cases = [(("drift_mlp", True), 200, 16, 10, 1, DEVICE_SWEEP),
             (("drift_mlp", True), 2000, 4, 10, 1, DEVICE_SWEEP)]
    if not quick:
        cases.append((("drift_mlp", True), 10000, 4, 10, 1, DEVICE_SWEEP))
    # the paper's 1.2M CNN: m = 200 only — the (m, P) carry at m = 2000
    # is ~19 GB (params + opt state + plane), past the runner; noted in
    # the meta row above and the module docstring
    cases.append((("mnist_cnn", False), 200, 2, 2, 1,
                  (1,) if quick else DEVICE_SWEEP))
    rows[0]["cnn_memory_bound"] = (
        "mnist_cnn swept at m=200 only: the (m, P) carry at m=2000 x "
        "1.2M params is ~19 GB")
    rows[0]["cnn_host_collective_bound"] = (
        "quick mode runs mnist_cnn sharded at d=1 only: host-device "
        "collectives rendezvous on shared cores — 0.01 rounds/sec "
        "measured at d=2 on 1 core; --full sweeps 1/2/4/8")

    for arch_smoke, m, rounds, batch, reps, devs in cases:
        base_row, base_comm, base_xf, base_dl = _engine(
            arch_smoke, m, "flat", 0, rounds, batch, reps)
        base_row["platform"] = platform
        rows.append(base_row)
        del base_dl
        one_dev_rps = None
        sweep = [d for d in devs if d <= n_dev and m % d == 0]
        for d in sweep:
            row, comm, xf, dl = _engine(
                arch_smoke, m, "sharded", d, rounds, batch, reps)
            row["platform"] = platform
            row["counters_equal"] = bool(
                comm == base_comm and np.array_equal(xf, base_xf))
            if d == 1:
                one_dev_rps = row["rounds_per_sec"]
            if one_dev_rps:
                row["speedup_vs_1dev"] = round(
                    row["rounds_per_sec"] / one_dev_rps, 2)
            if d == max(sweep):
                P = row["params"]
                row["ring_allreduce_bytes_per_dev"] = int(
                    2 * (d - 1) / d * P * 4)
                if arch_smoke[0] == "drift_mlp":
                    # measured collective bytes need one extra compile of
                    # the bare step — minutes for the 1.2M CNN on a CI
                    # core, so the CNN row carries the ring estimate only
                    summ = _wire_bytes(dl, jax.tree.map(
                        lambda x: x[0], _chunk_batch(arch_smoke, m,
                                                     batch)))
                    row["hlo_collective_ops"] = summ["num_ops"]
                    row["hlo_wire_bytes_per_round"] = int(
                        summ["total_wire_bytes"])
            rows.append(row)
            del dl
    return rows


def _chunk_batch(arch_smoke, m, batch):
    """One (1, m, B, ...) chunk of the fixture's stream — the step
    program's batch argument shape for the HLO probe."""
    from repro.data.pipeline import LearnerStreams
    from repro.data.synthetic import GraphicalModelStream, SyntheticMNIST

    arch, smoke = arch_smoke
    if arch == "mnist_cnn":
        src = SyntheticMNIST(seed=0, image_size=14 if smoke else 28)
    else:
        src = GraphicalModelStream(seed=0, drift_prob=0.0)
    return LearnerStreams(src, m, batch=batch, seed=0).next_chunk(1)


def run(quick: bool = True):
    import jax

    if (jax.default_backend() == "cpu"
            and len(jax.devices()) >= FORCED_DEVICES):
        rows = _sweep(quick)
    else:
        # a forced-host correctness sweep: the device count only takes
        # effect before jax initializes, so re-exec the sweep in a child
        # held to the CPU — it never needs (or takes) a chip this process
        # may hold
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                            f" --xla_force_host_platform_device_count="
                            f"{FORCED_DEVICES}").strip()
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        src = os.path.join(repo, "src")
        if src not in env.get("PYTHONPATH", ""):
            env["PYTHONPATH"] = (src + os.pathsep +
                                 env.get("PYTHONPATH", "")).rstrip(
                                     os.pathsep)
        cmd = [sys.executable, "-m", "benchmarks.shard_bench", _CHILD_FLAG]
        if not quick:
            cmd.append("--full")
        r = subprocess.run(cmd, capture_output=True, text=True, env=env,
                           cwd=repo, timeout=3600)
        if r.returncode != 0:
            raise RuntimeError(
                f"shard_bench child failed:\n{r.stderr[-3000:]}")
        rows = json.loads(r.stdout.split("ROWS:")[1])
    save_rows(NAME, rows)
    return rows


def check(rows) -> str:
    meta = rows[0]
    sharded = [r for r in rows if r.get("layout") == "sharded"]
    if not sharded or not all(r["counters_equal"] for r in sharded):
        return "MIXED"
    if not meta.get("scaling_expected", False):
        return "PASS"      # correctness sweep: no spare cores to scale on
    big = [r for r in sharded
           if r["m"] >= 2000 and r.get("speedup_vs_1dev")]
    ok = any(r["speedup_vs_1dev"] >= 1.2 for r in big)
    return "PASS" if ok else "MIXED"


if __name__ == "__main__":
    if _CHILD_FLAG in sys.argv:
        out = _sweep(quick="--full" not in sys.argv)
        print("ROWS:" + json.dumps(out))
    else:
        for r in run():
            print(r)
