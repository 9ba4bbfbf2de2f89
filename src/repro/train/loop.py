"""High-level protocol training loop used by examples and benchmarks.

Runs a ``DecentralizedLearner`` against a data source for T rounds, with
optional concept drift, recording per-round cumulative loss/communication
trajectories (the quantities the paper plots).

The driver is CHUNKED: rounds are executed ``chunk_size`` at a time through
``DecentralizedLearner.run_chunk`` — one ``jax.lax.scan`` program per chunk
instead of one jitted dispatch per round. Trajectory records at arbitrary
``record_every`` points are reconstructed exactly from the chunk's stacked
per-round metrics (integer comm counters cumsum bitwise-identically; losses
differ from the per-round driver only in float32 summation order)."""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, List, Optional

import jax.numpy as jnp
import numpy as np

from repro.config import (
    AsyncConfig, FaultConfig, NetworkConfig, TelemetryConfig, TrainConfig,
)
from repro.core import operators as ops
from repro.core.protocol import DecentralizedLearner
from repro.data.pipeline import LearnerStreams
from repro.telemetry.trace import span

# Default scan length: long enough that per-chunk dispatch is noise, short
# enough that the stacked (n, m, B, ...) batch chunk stays small on CPU.
DEFAULT_CHUNK = 64


@dataclass
class Trajectory:
    rounds: List[int] = field(default_factory=list)
    cumulative_loss: List[float] = field(default_factory=list)
    cumulative_bytes: List[int] = field(default_factory=list)
    syncs: List[int] = field(default_factory=list)
    drift_rounds: List[int] = field(default_factory=list)
    network_time: List[float] = field(default_factory=list)  # simulated s

    def as_dict(self):
        return {
            "rounds": self.rounds,
            "cumulative_loss": self.cumulative_loss,
            "cumulative_bytes": self.cumulative_bytes,
            "syncs": self.syncs,
            "drift_rounds": self.drift_rounds,
            "network_time": self.network_time,
        }


def run_drift_segments(dl, streams, source, rounds: int, drift_rounds=()):
    """Run ``rounds`` rounds as scanned chunks segmented at KNOWN drift
    rounds, calling ``source.force_drift()`` at each boundary. Returns the
    per-round cumulative ``(sync_curve, loss_curve)`` arrays the drift
    figures plot, reconstructed from each chunk's stacked metrics.

    ``drift_rounds`` must lie strictly inside (0, rounds) — a drift at
    round 0 is just a different initial concept and a drift at/after the
    last round is unobservable.
    """
    bounds = sorted(set(int(d) for d in drift_rounds))
    if bounds and (bounds[0] <= 0 or bounds[-1] >= rounds):
        raise ValueError(
            f"drift_rounds must lie strictly inside (0, {rounds}): {bounds}")
    sync_curve, loss_curve = [], []
    for start, end in zip([0] + bounds, bounds + [rounds]):
        if start in bounds:
            source.force_drift()
        metrics = dl.run_chunk(streams.next_chunk(end - start))
        s0 = sync_curve[-1] if sync_curve else 0
        l0 = loss_curve[-1] if loss_curve else 0.0
        sync_curve.extend(
            (s0 + np.cumsum(np.asarray(metrics.comm.syncs, np.int64)))
            .tolist())
        loss_curve.extend(
            (l0 + np.cumsum(np.sum(
                np.asarray(metrics.loss_per_learner, np.float64), axis=1)))
            .tolist())
    return np.asarray(sync_curve), np.asarray(loss_curve)


def run_protocol_training(
    loss_fn: Callable,
    init_fn: Callable,
    source,
    m: int,
    rounds: int,
    protocol,   # ProtocolConfig sugar or a ProtocolSpec composition
    train: TrainConfig = TrainConfig(),
    batch: int = 10,
    seed: int = 0,
    record_every: int = 10,
    drift: bool = False,
    batch_sizes=None,
    init_heterogeneity: float = 0.0,
    sample_kw: Optional[dict] = None,
    chunk_size: int = DEFAULT_CHUNK,
    network: Optional[NetworkConfig] = None,
    telemetry: Optional[TelemetryConfig] = None,
    async_net: Optional[AsyncConfig] = None,
    faults: Optional[FaultConfig] = None,
) -> tuple:
    """Returns (learner, trajectory). A ``telemetry`` config attaches
    the fleet telemetry plane (``repro.telemetry``): one schema'd record
    per round streamed to JSONL, with no change to the training
    numerics and no extra device transfers."""
    streams = LearnerStreams(source, m, batch=batch, seed=seed,
                             batch_sizes=batch_sizes, **(sample_kw or {}))
    dl = DecentralizedLearner(
        loss_fn, init_fn, m, protocol, train, seed=seed,
        init_heterogeneity=init_heterogeneity,
        sample_weights=streams.weights, network=network,
        telemetry=telemetry, async_net=async_net, faults=faults)
    traj = Trajectory()
    chunk = max(1, min(chunk_size, rounds))
    t = 0
    drifting = drift and hasattr(source, "maybe_drift")
    while t < rounds:
        n = min(chunk, rounds - t)

        def on_round(i, t=t):
            if source.maybe_drift():
                traj.drift_rounds.append(t + i)

        with span("repro.loop.chunk", round=t):
            base_loss = dl.cumulative_loss
            base_totals = dict(dl.comm_totals)
            base_net_time = dl.network_time
            base_ledger = int(dl.link_bytes_totals.sum())
            metrics = dl.run_chunk(streams.next_chunk(
                n, on_round=on_round if drifting else None))

            # reconstruct the per-round cumulative trajectory from the
            # chunk
            with span("repro.loop.trajectory", round=t):
                loss_cum = base_loss + np.cumsum(np.asarray(
                    jnp.sum(metrics.loss_per_learner, axis=1), np.float64))
                comm_cum = {k: base_totals[k] + np.cumsum(
                    np.asarray(getattr(metrics.comm, k), np.int64))
                    for k in ops.CommRecord._fields}
                # under a hierarchy the tiers move different payload
                # sizes, so the byte curve comes from the per-round ledger
                # (link counts priced host-side at each link's payload
                # size), not the scalar counts
                ledger_cum = base_ledger + np.cumsum(
                    dl.price_link_counts(
                        np.asarray(metrics.link_counts, np.int64)).sum(axis=1))
                net_cum = base_net_time + np.cumsum(
                    np.asarray(metrics.net_time, np.float64))
                for i in range(n):
                    g = t + i
                    if (g + 1) % record_every == 0 or g == rounds - 1:
                        traj.rounds.append(g + 1)
                        traj.cumulative_loss.append(float(loss_cum[i]))
                        traj.cumulative_bytes.append(
                            int(ledger_cum[i]) if dl.tiers is not None
                            else dl.comm_bytes_of(
                                {k: int(v[i]) for k, v in comm_cum.items()}))
                        traj.syncs.append(int(comm_cum["syncs"][i]))
                        traj.network_time.append(float(net_cum[i]))
        t += n
    return dl, traj
