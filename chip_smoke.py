"""Smoke run of the dynamic-averaging fleet on the TPU — not a benchmark.

Drives the paper's MNIST CNN (Kamp et al. 2018, Table 1: 1,199,882
weights, full width, random weights from a seed) through the entry
points a user calls — ``get_arch``, ``SyntheticMNIST``,
``run_protocol_training`` — under the dynamic averaging protocol, and
checks what comes out. The times it prints come from two chunks and one
compile: they say the program ran, they are not rates to compare.

    python3 chip_smoke.py             # one chip
    python3 chip_smoke.py --chips 4   # four chips: the sharded phase only

One chip: m=128 learners, B=10, ``layout="flat"``, two chunks of 16
rounds, the local condition checked every round (b=1) against Δ=0.05, so
some checks end in partial syncs and some in full ones. Checks: finite
loss and syncs > 0; the blocked fleet plane's distances agree with the
tree layout's on the live fleet; the compiled chunk copies, slices,
pads or concatenates nothing the size of the fleet inside its loops; the
same fleet under ``layout="tree"``, with
b=10 and a Δ every check exceeds (full syncs only), gives bitwise-equal
comm counters and per-link ledger, and parameters and loss within
tolerance of flat.

``--chips 4``: m=256 under ``layout="sharded", shard_devices=4`` (finite
loss, syncs > 0, the blocked distances against the tree layout's on the
live sharded fleet), then against ``layout="flat"`` on one chip with the same
seed, under full syncs.

Each layout comparison runs both fleets at full f32 matmul precision (see
``check_layouts``); the first run is the user's default precision.

The last line of stdout is one JSON object naming the device JAX ran on.
Any failed check raises (non-zero exit, no result line); so does a run
where JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.config import (  # noqa: E402
    ProtocolConfig, TelemetryConfig, TrainConfig, get_arch,
)
from repro.analysis.hlo import loop_data_movement  # noqa: E402
from repro.core import shard  # noqa: E402
from repro.core.divergence import per_learner_sq_distance  # noqa: E402
from repro.core.sync.spec import pin_rows  # noqa: E402
from repro.data.pipeline import LearnerStreams  # noqa: E402
from repro.data.synthetic import SyntheticMNIST  # noqa: E402
from repro.models.cnn import cnn_loss, init_cnn_params  # noqa: E402
from repro.train.loop import run_protocol_training  # noqa: E402

SEED = 0
BATCH = 10                 # the paper's B
CHUNK, CHUNKS = 16, 2      # rounds per scanned chunk, chunks per run
# (check period b, Δ). PARTIAL checks every round; its syncs are partial
# and full. FULL is the paper's b=10 with a Δ every learner has passed by
# a wide margin at each check (0.03-0.4 in a CPU run of this fleet at
# m=64), so every check fires a full sync whatever the distances. The
# layout comparisons use FULL: a partial sync leaves the learners it
# skips just under Δ, where a decision can flip within float
# reassociation error (README, "Equivalence contract") — on the chip,
# tree and flat part by one cohort member under PARTIAL even at full f32
# precision.
PARTIAL, FULL = (1, 0.05), (10, 0.01)
LEARNING_RATE = 0.05
M_ONE_CHIP, M_FOUR_CHIPS = 128, 256
# Layout equivalence on the chip. The layouts compile to different
# programs, whose local updates already differ in the last bit of a
# parameter after one round; 32 rounds of training grow that. So each
# parameter leaf is compared by its relative error in the 2-norm (an
# elementwise tolerance fails on the small elements a cancellation
# leaves), and the cumulative loss by its relative difference.
PARAM_RTOL, LOSS_RTOL = 1e-3, 1e-6
DIST_RTOL = 1e-5


def _require(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def require_tpu(count: int):
    devices = jax.devices()
    _require(devices[0].platform == "tpu",
             f"no TPU visible (JAX platform {devices[0].platform!r})")
    _require(len(devices) >= count,
             f"{count} TPU chips requested, {len(devices)} visible")
    return devices


def run_fleet(m: int, layout: str, shard_devices: int = 0, cfg=None,
              sync=PARTIAL):
    """The fleet through ``run_protocol_training``; returns the learner.
    Telemetry (ring buffer only) times each chunk on the host clock, up
    to the blocking fetch of its counters."""
    cfg = cfg or get_arch("mnist_cnn")
    b, delta = sync
    proto = ProtocolConfig(kind="dynamic", b=b, delta=delta,
                           layout=layout, shard_devices=shard_devices)
    dl, _ = run_protocol_training(
        lambda p, b: cnn_loss(cfg, p, b),
        lambda k: init_cnn_params(cfg, k),
        SyntheticMNIST(seed=SEED, image_size=cfg.input_shape[0]),
        m=m, rounds=CHUNK * CHUNKS, protocol=proto,
        train=TrainConfig(optimizer="sgd", learning_rate=LEARNING_RATE),
        batch=BATCH, seed=SEED, record_every=CHUNK, chunk_size=CHUNK,
        telemetry=TelemetryConfig(profile=True))
    jax.block_until_ready(dl.params)
    return dl


def report(tag: str, dl, seconds: float) -> None:
    chunks = [r for r in dl.recorder.sink.records if r["kind"] == "chunk"]
    walls = [r["wall_s"] for r in chunks]
    print(f"[smoke {tag}] m={dl.m} P={dl.model_size} rounds={dl.rounds} "
          f"syncs={dl.comm_totals['syncs']} bytes={dl.comm_bytes()} "
          f"loss={dl.cumulative_loss!r}")
    print(f"[smoke {tag}] run_s={seconds!r} chunk_s={walls!r} "
          f"compile_s(first chunk - second)={walls[0] - walls[-1]!r}")


def peak_bytes() -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def compiled_chunk_text(dl, image_size: int) -> str:
    """HLO of the chunk program the run dispatched: lowering the jitted
    chunk again with the run's argument shapes hits the in-memory
    compilation cache."""
    batches = LearnerStreams(SyntheticMNIST(seed=SEED, image_size=image_size),
                             dl.m, batch=BATCH, seed=SEED).next_chunk(CHUNK)
    return dl._chunk.lower(dl.params, dl.opt_state, dl.sync_state,
                           batches).compile().as_text()


def check_distances_on_blocks(dl) -> float:
    """The blocked plane's (m,) distances to the reference against the
    tree layout's on the live fleet. A sharded learner's blocks stay
    split over its devices, as in the engine's round."""
    def rows(params, ref, blocked):
        return per_learner_sq_distance(
            pin_rows(params) if blocked else params, ref)

    with (shard.use_fleet(dl.fleet) if dl.fleet is not None
          else contextlib.nullcontext()):
        blocked, tree = (np.asarray(jax.jit(rows, static_argnums=2)(
            dl.params, dl.sync_state.ref, b)) for b in (True, False))
    np.testing.assert_allclose(blocked, tree, rtol=DIST_RTOL)
    return float(np.max(np.abs(blocked - tree) / np.abs(tree)))


def fleet_sized_moves(dl, hlo: str):
    """Data-movement ops inside the compiled chunk's loops (copies,
    slices, pads, concatenations) at least the size of the fleet's
    largest leaf: each would be a fleet-sized relayout every round."""
    big = max(x.nbytes for x in jax.tree.leaves(dl.params))
    return [op for op in loop_data_movement(hlo) if op[3] >= big]


def check_same_fleet(a, b, what: str) -> None:
    """Comm counters and the per-link ledger bitwise; parameters and the
    cumulative loss within tolerance."""
    _require(a.comm_totals == b.comm_totals,
             f"{what}: comm counters {a.comm_totals} != {b.comm_totals}")
    _require(np.array_equal(a.link_bytes_totals, b.link_bytes_totals),
             f"{what}: per-link bytes ledgers differ")
    _require(np.array_equal(a.link_xfer_totals, b.link_xfer_totals),
             f"{what}: per-link transfer counts differ")
    param_err = float(max(
        np.linalg.norm((x - y).astype(np.float64))
        / np.linalg.norm(y.astype(np.float64))
        for x, y in zip(jax.tree.leaves(jax.device_get(a.params)),
                        jax.tree.leaves(jax.device_get(b.params)))))
    loss_err = (abs(a.cumulative_loss - b.cumulative_loss)
                / abs(b.cumulative_loss))
    print(f"[smoke {what}] worst leaf relative 2-norm error {param_err!r}; "
          f"cumulative loss relative difference {loss_err!r}")
    _require(param_err <= PARAM_RTOL,
             f"{what}: parameters differ by {param_err} > {PARAM_RTOL}")
    _require(loss_err <= LOSS_RTOL,
             f"{what}: cumulative loss differs by {loss_err} > {LOSS_RTOL}")


def timed_fleet(tag: str, m: int, layout: str, shard_devices: int = 0,
                cfg=None, sync=PARTIAL):
    t0 = time.perf_counter()
    dl = run_fleet(m, layout, shard_devices=shard_devices, cfg=cfg,
                   sync=sync)
    report(tag, dl, time.perf_counter() - t0)
    return dl


def check_layouts(m: int, cfg, layout: str, shard_devices: int = 0) -> None:
    """``layout`` against ``layout="flat"`` on one chip, same seed, under
    full syncs.

    Both run at full f32 matmul precision: the equivalence contract is
    about f32 arithmetic. At the TPU's default precision a convolution
    rounds its f32 operands to bfloat16, so the last-bit differences
    between the layouts' sums cross bfloat16 rounding boundaries and come
    back as gradient differences of about 1e-3 within a few rounds."""
    with jax.default_matmul_precision("highest"):
        other = timed_fleet(f"{layout} f32", m, layout, shard_devices, cfg,
                            FULL)
        flat = timed_fleet("flat f32", m, "flat", cfg=cfg, sync=FULL)
    check_same_fleet(other, flat, f"{layout} vs flat")
    print(f"[smoke {layout}] comm counters + ledger bitwise equal to flat "
          f"(one chip); params and loss within tolerance; "
          f"peak_bytes_in_use(chip 0)={peak_bytes()}")


def one_chip(m: int = M_ONE_CHIP, cfg=None) -> None:
    cfg = cfg or get_arch("mnist_cnn")
    flat = timed_fleet("flat", m, "flat", cfg=cfg)
    _require(np.isfinite(flat.cumulative_loss), "flat loss not finite")
    _require(flat.comm_totals["syncs"] > 0, "no sync fired")
    rel = check_distances_on_blocks(flat)
    moves = fleet_sized_moves(flat, compiled_chunk_text(flat,
                                                        cfg.input_shape[0]))
    _require(not moves, f"compiled chunk relays out the fleet: {moves}")
    print(f"[smoke flat] blocked vs tree distances: max rel err {rel!r}; "
          f"no fleet-sized copy in the compiled chunk's loops; "
          f"peak_bytes_in_use={peak_bytes()}")
    del flat
    check_layouts(m, cfg, "tree")


def four_chips(m: int = M_FOUR_CHIPS, n: int = 4, cfg=None) -> None:
    sharded = timed_fleet("sharded", m, "sharded", n, cfg)
    _require(np.isfinite(sharded.cumulative_loss), "sharded loss not finite")
    _require(sharded.comm_totals["syncs"] > 0, "no sync fired")
    rel = check_distances_on_blocks(sharded)
    print(f"[smoke sharded] blocked vs tree distances on the sharded "
          f"fleet: max rel err {rel!r}")
    del sharded
    check_layouts(m, cfg, "sharded", n)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded-vs-flat phase")
    args = ap.parse_args()
    devices = require_tpu(args.chips)
    cache = enable_compile_cache()
    warm = os.path.isdir(cache) and bool(os.listdir(cache))
    print(f"[smoke] compile cache: {cache} (warm at start: {warm})")
    if args.chips == 4:
        four_chips()
    else:
        one_chip()
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
