"""One run of one benchmark cell: set-up, a measured window, the check.

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a model
configuration (``configs/<config>.json``) and a traffic mix
(``traffic/<traffic>.json``); the configuration's ``"member"`` key names
the module of its model family (``members/<member>.py``), which gives the
program's init and loss and the reference's; the limits of its check are
in ``limits/<cell>.json`` and every metric is read by
``metrics/<name>.py``. Nothing here names a cell, a configuration, a
model family or a metric.

The window drives the program's own entry point,
``repro.train.loop.run_protocol_training``, which builds a fleet, runs
it in scanned chunks and does its per-chunk bookkeeping. Each call
builds a new fleet, so a run makes two calls:

1. a warm call of two chunks, which compiles (or loads from the compile
   cache) the chunk, sampler and fold programs and times one warm chunk;
2. the timed call, of one chunk plus as many as fill ``--seconds``. Its
   first chunk, which traces the programs again, is set-up; the window
   runs from the start of its second chunk to its return.

The harness sees chunk boundaries only through the data source it passes
in (``WindowSource``), whose ``concept()`` the fused sampler calls once
at the start of every chunk. At the window's start it finds the fleet
the entry point built by walking up from that call's frame, and reads
(never changes) its state: the rounds run so far, the comm counters, and
each parameter leaf's change from the initial fleet.

The check (``correct``) compares what the timed call produced over its
first chunk with ``reference.run_fleet``, which recomputes those rounds
in float32 at full matmul precision after the program's state is freed:
the fleet loss of every round, per parameter leaf the norm of the
fleet's change over the chunk, the fleet's spread about its mean at the
chunk's end, and the sync ledger (syncs, full syncs, models sent up).
"""
from __future__ import annotations

import gc
import importlib
import json
import math
import os
import shutil
import sys
import tempfile
import time
from typing import Callable, Optional

import numpy as np

from perfbench import members

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# a traced run traces a window this long at most: per-layer metrics need
# a few steady chunks, not the whole end-to-end window
TRACE_SECONDS = 5.0
# leaves whose round-1 reference gradient is under this share of the
# median leaf's move by round-off alone and are left out of the change
# comparison
STILL_LEAF_SHARE = 1e-3
# the sync counters the check compares with the reference's
LEDGER = ("syncs", "full_syncs", "model_up")


class NoChip(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell asks for."""


# ---------------------------------------------------------------------------
# the cell's files
# ---------------------------------------------------------------------------

def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class Cell:
    """A workload entry of ``BENCHMARK.json`` with the files it names."""

    def __init__(self, name: str, root: str = ROOT):
        bench = _load(os.path.join(root, "BENCHMARK.json"))
        wl = {w["name"]: w for w in bench["workloads"]}
        if name not in wl:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                           f"known: {sorted(wl)}")
        self.name = name
        self.root = root
        self.workload = wl[name]
        self.chips = int(self.workload["chips"])
        entry = {c["name"]: c for c in bench["configs"]}[
            self.workload["config"]]
        self.config = _load(os.path.join(root, entry["file"]))
        self.member = members.load(self.config, entry["name"])
        self.traffic = _load(os.path.join(
            root, "perfbench", "traffic", self.workload["traffic"] + ".json"))
        limits = os.path.join(root, "perfbench", "limits", name + ".json")
        self.limits = _load(limits) if os.path.exists(limits) else {}

        def applies(metric):
            return name in metric.get("workloads", [name])

        self.end_to_end = [m for m in bench["end_to_end"] if applies(m)]
        self.per_layer = [m for m in bench["per_layer"] if applies(m)]


def _metric_reader(name: str) -> Callable:
    return importlib.import_module(f"perfbench.metrics.{name}").read


def fold_seed(seed: int) -> int:
    """The program seeds ``PRNGKey`` and NumPy from 31 bits; a larger
    ``--seed`` folds its high bits in rather than being cut off."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"--seed must be a whole number >= 0, got {seed}")
    folded = 0
    while seed:
        folded ^= seed & 0x7FFFFFFF
        seed >>= 31
    return folded


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------

class WindowSource:
    """The cell's data source, as the entry point sees it: every call
    passes through to the wrapped generator; ``concept()``, which the
    fused chunk sampler calls once per chunk, also stamps the host clock
    and calls ``on_chunk``."""

    def __init__(self, inner, on_chunk: Optional[Callable] = None):
        self.inner = inner
        self.on_chunk = on_chunk
        self.stamps = []

    def concept(self):
        self.stamps.append(time.perf_counter())
        if self.on_chunk is not None:
            self.on_chunk()
        return self.inner.concept()

    def sample_from(self, concept, key, batch, **kw):
        return self.inner.sample_from(concept, key, batch, **kw)

    def sample(self, key, batch, **kw):
        return self.inner.sample(key, batch, **kw)


def find_learner():
    """The ``DecentralizedLearner`` that a caller up the stack holds."""
    from repro.core.protocol import DecentralizedLearner
    frame = sys._getframe(1)
    while frame is not None:
        for value in frame.f_locals.values():
            if isinstance(value, DecentralizedLearner):
                return value
        frame = frame.f_back
    return None


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

class Run:
    """What one run measured; the metric readers take their numbers from
    it (``metrics/<name>.py``: ``read(run) -> float | None``)."""

    def __init__(self, cell: Cell):
        self.cell = cell
        self.m = int(cell.traffic["m"])
        self.batch = int(cell.traffic["batch"])
        self.chunk = int(cell.traffic["chunk"])
        self.setup_s = None
        self.window_s = None
        self.rounds = 0             # rounds completed in the window
        self.syncs = 0              # syncs in the window (comm counter)
        self.memory_peak_bytes = None
        self.trace = None           # trace.Summary of a traced window
        self.devices = []
        self.chips = 0


def _memory_peak(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return int(max(peaks)) if peaks else None


def _program_state(params, w0_model):
    """The leaf names; per leaf, ||theta_i - theta_0|| over the whole
    fleet; and the fleet's spread sum_i ||theta_i - mean theta||^2.
    Computed on the device in one program."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def read(params, w0):
        leaves = jax.tree.leaves(params)
        change = [jnp.sqrt(jnp.sum(jnp.square(p - w[None])))
                  for p, w in zip(leaves, jax.tree.leaves(w0))]
        spread = sum(jnp.sum(jnp.square(p - jnp.mean(p, axis=0)))
                     for p in leaves)
        return change, spread

    names = tuple(jax.tree_util.keystr(path) for path, _ in
                  jax.tree_util.tree_flatten_with_path(params)[0])
    change, spread = jax.device_get(read(params, w0_model))
    return names, np.asarray(change, np.float64), float(spread)


def readings(program: dict, ref) -> dict:
    """The numbers a check can compare, program against reference:

    ``loss_gap``    the widest relative gap of the fleet loss over the
                    rounds of the first chunk;
    ``change_gap``  per parameter leaf, the gap between the norms of the
                    fleet's change over the first chunk, relative to the
                    reference's norm or the median leaf's, whichever is
                    larger: the worst leaf;
    ``spread_gap``  the gap between the fleet's spreads about its mean at
                    the chunk's end, relative to the reference's whole
                    change sum_i ||theta_i - theta_0||^2: what averaging
                    left unequal;
    ``ledger_gap``  the sum of the gaps of the sync counters (syncs, full
                    syncs, models sent up), an exact count.

    Leaves whose round-1 reference gradient is under ``STILL_LEAF_SHARE``
    of the median leaf's move by round-off alone and are left out of
    ``change_gap``."""
    rounds = len(ref.round_loss)
    p_loss = np.asarray(program["round_loss"][:rounds], np.float64)
    out = {"loss_gap": float(np.max(np.abs(p_loss - ref.round_loss)
                                    / np.abs(ref.round_loss)))}
    if program["leaf_names"] != ref.leaf_names:
        out["change_gap"] = math.inf
    else:
        g = ref.first_grad_norm
        moving = g >= STILL_LEAF_SHARE * np.median(g)
        scale = np.maximum(ref.change_norm, np.median(ref.change_norm))
        gaps = np.abs(program["change_norm"] - ref.change_norm) / scale
        out["change_gap"] = float(np.max(gaps[moving]))
    out["spread_gap"] = float(abs(program["spread"] - ref.spread)
                              / np.sum(np.square(ref.change_norm)))
    out["ledger_gap"] = float(sum(abs(program["ledger"][k] - ref.ledger[k])
                                  for k in LEDGER))
    return out


def compare(cell: Cell, program: dict, ref) -> dict:
    """The numbers the cell's limits file names, each beside its limit;
    a cell without limits (one being calibrated) shows every reading,
    each with the limit ``None``."""
    found = readings(program, ref)
    names = [n for n in found if n in cell.limits] or list(found)
    return {n: {"value": found[n],
                "limit": cell.limits.get(n, {}).get("limit")}
            for n in names}


def passes(checks: dict) -> bool:
    """Every number within its limit; a number without one fails."""
    return all(c["limit"] is not None and c["value"] <= c["limit"]
               for c in checks.values())


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, require_chip: bool = True) -> dict:
    """One run; returns the result line's object."""
    import jax

    from repro.compile_cache import enable_compile_cache
    from repro.config import ProtocolConfig, TrainConfig
    from repro.train.loop import run_protocol_training

    from perfbench import reference

    devices = jax.devices()
    if require_chip:
        if devices[0].platform != "tpu":
            raise NoChip(f"JAX finds no TPU (platform "
                         f"{devices[0].platform!r}); the benchmark does not "
                         f"run on the CPU")
        if len(devices) < cell.chips:
            raise NoChip(f"cell {cell.name} needs {cell.chips} chips, JAX "
                         f"finds {len(devices)}")
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    tr, cfg_json = cell.traffic, cell.config
    seed = fold_seed(seed)
    data = importlib.import_module(
        f"perfbench.data.{cfg_json['data']['generator']}")
    protocol = ProtocolConfig(
        kind=tr["protocol"], b=int(tr["b"]), delta=float(tr["delta"]),
        augmentation=tr["augmentation"], layout=tr["layout"],
        shard_devices=int(tr["shard_devices"]))
    train = TrainConfig(optimizer=tr["optimizer"],
                        learning_rate=float(tr["learning_rate"]))
    init_fn, loss_fn = cell.member.program(cfg_json)

    run = Run(cell)
    run.devices = devices
    run.chips = cell.chips
    m, chunk = run.m, run.chunk

    def train_call(source, chunks):
        return run_protocol_training(
            loss_fn, init_fn, source, m=m, rounds=chunk * chunks,
            protocol=protocol, train=train, batch=run.batch, seed=seed,
            record_every=int(tr["record_every"]), chunk_size=chunk)

    # 1. the warm call: compile or load every program, time one chunk
    warm = WindowSource(data.make(seed, cfg_json["data"]["args"]))
    dl, traj = train_call(warm, 2)
    chunk_s = time.perf_counter() - warm.stamps[1]
    del dl, traj
    gc.collect()

    # 2. the timed call
    target = min(seconds, TRACE_SECONDS) if trace else seconds
    chunks = 1 + max(2, min(100_000, math.ceil(target / chunk_s)))
    k_init = jax.random.split(jax.random.PRNGKey(seed), 3)[0]
    w0 = cell.member.reference_init(cfg_json, k_init)
    at_start = {}
    trace_dir = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="perfbench_trace_")

    def on_chunk():
        if at_start:
            return
        learner = find_learner()
        if learner is None:
            raise RuntimeError("no DecentralizedLearner up the stack of the "
                               "sampler's concept() call")
        if learner.rounds == 0:
            return
        names, change, spread = _program_state(learner.params, w0)
        at_start.update(rounds=learner.rounds, leaf_names=names,
                        change_norm=change, spread=spread,
                        ledger={k: learner.comm_totals[k] for k in LEDGER})
        if trace:
            from perfbench.trace import WINDOW_SPAN
            jax.profiler.start_trace(trace_dir)
            at_start["span"] = jax.profiler.TraceAnnotation(WINDOW_SPAN)
            at_start["span"].__enter__()
        at_start["t"] = time.perf_counter()

    timed = WindowSource(data.make(seed, cfg_json["data"]["args"]),
                         on_chunk)
    dl, traj = train_call(timed, chunks)
    t_end = time.perf_counter()
    if trace:
        at_start.pop("span").__exit__(None, None, None)
        jax.profiler.stop_trace()
    run.setup_s = at_start["t"] - t_start
    run.window_s = t_end - at_start["t"]
    run.rounds = dl.rounds - at_start["rounds"]
    run.syncs = dl.comm_totals["syncs"] - at_start["ledger"]["syncs"]
    run.memory_peak_bytes = _memory_peak(devices[:cell.chips])
    cum = np.asarray(traj.cumulative_loss, np.float64)
    round_loss = np.diff(np.concatenate([[0.0], cum]))
    failed = int(np.sum(~np.isfinite(round_loss[at_start["rounds"]:])))
    program = {"round_loss": round_loss[:at_start["rounds"]],
               "leaf_names": at_start["leaf_names"],
               "change_norm": at_start["change_norm"],
               "spread": at_start["spread"], "ledger": at_start["ledger"]}
    del dl, traj, timed
    gc.collect()

    if trace:
        from perfbench import trace as trace_mod
        try:
            run.trace = trace_mod.reduce_dir(trace_dir, cell.chips)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)

    # 3. the check, once the program's state is freed
    ref = reference.run_fleet(
        cell.member, cfg_json, tr, data.make(seed, cfg_json["data"]["args"]),
        seed, at_start["rounds"])
    checks = compare(cell, program, ref)
    correct = failed == 0 and passes(checks)

    wanted = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for spec in wanted:
        value = _metric_reader(spec["name"])(run)
        if value is not None:
            metrics[spec["name"]] = {"value": value, "unit": spec["unit"]}
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices),
              "memory_peak_bytes": run.memory_peak_bytes}
    result = {"correct": bool(correct), "attempted": int(run.rounds),
              "failed": failed, "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        result["breakdown"] = run.trace.breakdown()
    result["checks"] = checks
    return result
