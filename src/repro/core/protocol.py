"""The decentralized learning simulator: m learners, one protocol.

Faithful to the paper's setting (Section 2): in each round t every learner i
observes a sample E_t^i of size B, updates its local model with the learning
algorithm phi (vmap'd over the learner axis), and every b rounds the
synchronization operator sigma runs (``repro.core.operators``).

The whole round — local updates + protocol — is one jitted function, so the
paper's experiments (m up to 200, ~1.2M-weight CNNs) run fast on CPU, and
the identical code path runs under pjit on a mesh (the learner axis then
shards over devices).

Communication is accounted exactly: model transfers and scalar messages as
integers, converted to bytes in ``comm_bytes``.

With a ``NetworkConfig`` the round runs inside a simulated network
environment (``repro.network``): per-round availability masks are sampled
inside the scanned round (pure in the round counter — no host sync), the
operators become availability-aware, and the link-cost model turns each
round's transfers into simulated wall-clock (``net_time``) and per-link
bytes. ``network=None`` is the ideal always-on star and reproduces the
pre-network engine bitwise.

Synchronization runs through the staged sync kernel (``repro.core.sync``):
the protocol argument — a ``ProtocolConfig`` (sugar for a ``PROTOCOLS``
preset) or a ``ProtocolSpec`` directly — resolves to a compiled stage
composition, and the spec's capabilities (``uses_overlay``,
``uses_coordinator``, ``extra_state``) drive the engine's wiring instead
of kind strings. The kernel also supplies the per-round **bytes ledger**:
every link's exact byte count (model payloads at that link's tier payload
size + control messages attributed to the link that sent them),
accumulated host-side in int64.
With ``ProtocolConfig.tiers`` (a ``HierarchyConfig``) the round becomes the
two-tier star-of-stars: the configured protocol runs inside each cluster,
``tiers.inter`` runs among the edge aggregators, and the ledger grows g
aggregator-uplink rows priced at the inter tier's payload size.
``tiers=None`` reproduces the flat engine bitwise.
"""
from __future__ import annotations

import functools
import time
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import (
    AsyncConfig, FaultConfig, NetworkConfig, ProtocolConfig, TelemetryConfig,
    TrainConfig,
)
from repro.core import operators as ops
from repro.core import shard
from repro.core.divergence import divergence, flat_size
from repro.core.sync.async_sync import asyncify
from repro.core.sync.hierarchy import (
    apply_hierarchical, init_hier_state, validate_hierarchy,
)
from repro.core.sync.spec import resolve_spec
from repro.network import availability as net_availability
from repro.network import cost as net_cost
from repro.network import faults as net_faults
from repro.network import topology as net_topology
from repro.optim import make_optimizer
from repro.telemetry import sink
from repro.telemetry.trace import ChunkProfiler, span


class ProtocolMetrics(NamedTuple):
    loss_per_learner: jnp.ndarray    # (m,) this-round in-place loss
    comm: ops.CommRecord
    divergence: jnp.ndarray
    num_active: jnp.ndarray          # scalar int32 — reachable learners
    net_time: jnp.ndarray            # scalar float32 — simulated seconds
    link_xfers: jnp.ndarray          # (m,) int32 — models per learner link
    link_counts: jnp.ndarray         # (L, 2) int32 — the ledger's inputs:
    #   [model transfers, control messages] per link this round. L = m
    #   learner links, plus num_clusters aggregator uplinks under a
    #   hierarchy. Counts stay small int32 on device; the HOST prices them
    #   into int64 bytes (per-link payload size × transfers + msg_bytes ×
    #   messages), so billion-parameter payloads never overflow
    num_inflight: jnp.ndarray        # scalar int32 — learners whose sync
    #   exchange is in flight after this round (0 without an async
    #   timeline)
    max_age: jnp.ndarray             # scalar int32 — the oldest
    #   rounds-since-sync counter the trigger carries (staleness/async
    #   age; 0 for stateless triggers)
    num_faulty: jnp.ndarray          # scalar int32 — learners under ANY
    #   injected fault this round (crashed/restarting/bursting/corrupted/
    #   Byzantine; 0 with faults=None)
    num_quarantined: jnp.ndarray     # scalar int32 — learners currently
    #   quarantined (health counter > 0; 0 for non-robust triggers)
    num_recovered: jnp.ndarray       # scalar int32 — learners whose
    #   commit came back clean THIS round after a quarantine (0 for
    #   non-robust triggers)


class DecentralizedLearner:
    """m local learners + a synchronization protocol Pi = (phi, sigma).

    ``protocol`` is a ``ProtocolConfig`` (kind sugar resolving to a
    ``PROTOCOLS`` preset) or a ``ProtocolSpec`` directly — any registered
    stage composition, e.g. one loaded from JSON, runs through the same
    scanned engine."""

    def __init__(
        self,
        loss_fn: Callable[[Any, Any], jnp.ndarray],
        init_fn: Callable[[jax.Array], Any],
        m: int,
        protocol,
        train: TrainConfig = TrainConfig(),
        seed: int = 0,
        init_heterogeneity: float = 0.0,
        sample_weights: Optional[jnp.ndarray] = None,
        track_divergence: bool = False,
        network: Optional[NetworkConfig] = None,
        telemetry: Optional[TelemetryConfig] = None,
        async_net: Optional[AsyncConfig] = None,
        faults: Optional[FaultConfig] = None,
    ):
        self.m = m
        self.protocol = protocol
        # the engine consumes the protocol as a spec: a ProtocolConfig is
        # sugar for its PROTOCOLS preset, and a ProtocolSpec (e.g. loaded
        # from JSON, or a custom registered composition) runs directly
        self.spec = resolve_spec(protocol)
        self.train = train
        self.loss_fn = loss_fn
        self.opt = make_optimizer(train)
        self.track_divergence = track_divergence
        self.network = network
        # fault-injection plane (repro.network.faults): gated STATICALLY
        # on ``faults is not None`` — a fault-free run traces none of it
        # and stays bitwise vs the fault-unaware engine
        self.faults = faults
        self._nonfinite_reported = False
        key = jax.random.PRNGKey(seed)
        k_init, k_noise, k_state = jax.random.split(key, 3)

        base = init_fn(k_init)
        # paper init: all learners start from ONE random model; Fig. 6.2
        # studies heterogeneous inits parameterized by a noise scale epsilon
        stacked = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (m,) + x.shape).copy(), base)
        if init_heterogeneity > 0.0:
            # noise at scale eps *relative to the init scale of each leaf*
            # (paper Fig. 6.2 / A.8: eps measured relative to the scale of
            # the homogeneous Glorot initialization)
            noise_keys = jax.random.split(k_noise, m)
            leaves, treedef = jax.tree.flatten(base)
            new_leaves = []
            for li, x in enumerate(leaves):
                scale = init_heterogeneity * (jnp.std(x) + 1e-12)

                def one(k, x=x, li=li, scale=scale):
                    return jax.random.normal(
                        jax.random.fold_in(k, li), x.shape, x.dtype) * scale

                new_leaves.append(x[None] + jax.vmap(one)(noise_keys))
            stacked = jax.tree.unflatten(treedef, new_leaves)

        self.params = stacked
        self.opt_state = jax.vmap(self.opt.init)(self.params)
        self.sample_weights = sample_weights
        self.model_size = flat_size(base)
        self.model_bytes = self.model_size * self.spec.bytes_per_param

        # event-driven async timeline (AsyncConfig): rewrite the protocol
        # onto per-learner local clocks with messages in flight BEFORE any
        # state init — the rewritten spec carries the timeline's ring
        # buffers / clocks in SyncState.extra and (with aircomp) the
        # over-the-air stages. Under a hierarchy the intra tier runs the
        # rewritten spec; tiers.inter stays synchronous.
        self.async_net = async_net
        if async_net is not None:
            self.spec = asyncify(self.spec, async_net, network,
                                 self.model_bytes)

        # two-tier hierarchy (ProtocolConfig.tiers): per-cluster intra
        # state + inter-tier state; aggregator uplinks get their own
        # ledger rows and payload size (tiers.inter.bytes_per_param)
        self.tiers = getattr(protocol, "tiers", None)
        if self.tiers is not None:
            validate_hierarchy(self.tiers, m)
            self.sync_state = init_hier_state(
                base, self.tiers, seed, m=m, intra_spec=self.spec,
                inter_spec=resolve_spec(self.tiers.inter))
            self.inter_model_bytes = (
                self.model_size * self.tiers.inter.bytes_per_param)
            self.num_links = m + self.tiers.num_clusters
        else:
            self.sync_state = ops.init_state(base, seed, spec=self.spec,
                                             m=m)
            self.inter_model_bytes = 0
            self.num_links = m

        # network environment: link profile + peer overlay. A static
        # topology is built once here (concrete matrix closed over by the
        # jitted round); a mobile one is re-derived per scanned round from
        # the round counter. An overlay-using spec (``uses_overlay``, e.g.
        # gossip) needs SOME overlay — an ideal network means the implied
        # star.
        self._link_bw = self._link_lat = None
        self._agg_bw = self._agg_lat = None
        self._static_adj = None
        self._mobile = False
        if network is not None:
            self._link_bw, self._link_lat = net_cost.link_profile(network, m)
            self._mobile = net_topology.is_mobile(network)
            if not self._mobile:
                self._static_adj = net_topology.adjacency(network, m)
            if self.tiers is not None:
                self._agg_bw, self._agg_lat = net_cost.uniform_profile(
                    self.tiers.link_class, self.tiers.num_clusters)
        elif self.spec.uses_overlay:
            self._static_adj = net_topology.star(m)

        # device-sharded fleet plane (layout="sharded"): build the fleet
        # mesh and give the scan carry its NamedSharding home — learner-
        # stacked leaves (params, opt state, staleness ages) split over
        # the "fleet" axis, the reference model and scalar counters
        # replicated. The jitted round then traces against committed
        # sharded inputs (plus the constrain_rows pins the compiled round
        # inserts under the active fleet below), so per-learner updates,
        # distance reductions and commits execute per-shard and only
        # trigger votes + cohort means cross devices.
        self.fleet = None
        if self.spec.param("layout") == "sharded":
            self.fleet = shard.fleet_sharding(
                m, self.spec.param("shard_devices"))
            self.params = shard.put_fleet(self.fleet, self.params)
            self.opt_state = shard.put_fleet(self.fleet, self.opt_state)
            if self.tiers is None:
                self.sync_state = shard.put_sync_state(
                    self.fleet, self.sync_state)
            else:
                # per-cluster hierarchy state carries (g, ...) leaves —
                # cluster-indexed, not learner-indexed — so it replicates;
                # the per-cluster sync runs flat arithmetic under vmap
                # (constrain_rows no-ops on the (k, P) cluster planes)
                # while the fleet carry around it stays device-sharded
                self.sync_state = shard.put_replicated(
                    self.fleet, self.sync_state)

        # cumulative counters (host-side python ints / floats / numpy)
        self.cumulative_loss = 0.0
        self.cumulative_loss_per_learner = np.zeros((m,), np.float32)
        self.comm_totals = {k: 0 for k in ops.CommRecord._fields}
        self.rounds = 0
        self.network_time = 0.0                    # simulated seconds
        self.active_rounds_total = 0               # sum of per-round |active|
        self.link_xfer_totals = np.zeros((m,), np.int64)
        # the bytes ledger: int64 cumulative bytes per link (learner links,
        # then aggregator uplinks under a hierarchy) — exact even when the
        # tiers move different payload sizes. Pricing happens host-side:
        # per-link payload sizes × device-side transfer counts.
        self.link_bytes_totals = np.zeros((self.num_links,), np.int64)
        self.msg_bytes = network.msg_bytes if network is not None else 64
        self.link_payload_bytes = np.full((m,), self.model_bytes, np.int64)
        if self.tiers is not None:
            self.link_payload_bytes = np.concatenate([
                self.link_payload_bytes,
                np.full((self.tiers.num_clusters,), self.inter_model_bytes,
                        np.int64)])

        # under a fleet the jitted callables run (and hence TRACE) inside
        # the active-fleet context, so the compiled round's constrain_rows
        # pins resolve to this engine's mesh
        self._step = self._with_fleet(jax.jit(self._make_step()))
        self._chunk = self._with_fleet(jax.jit(self._make_chunk()))
        self._fold_step = jax.jit(self._make_fold(chunked=False))
        self._fold_chunk = jax.jit(self._make_fold(chunked=True))

        # telemetry plane (repro.telemetry): a recorder streaming one
        # schema'd record per round, materialized from the SAME per-chunk
        # fold fetch — the telemetry=None path above is untouched (and
        # stays bitwise vs the goldens)
        self.telemetry = telemetry
        self.recorder = None
        self._profiler = None
        if telemetry is not None:
            from repro.telemetry.recorder import RoundRecorder
            self._profiler = ChunkProfiler()
            self.recorder = RoundRecorder(
                telemetry, m=m, num_links=self.num_links,
                model_size=self.model_size, model_bytes=self.model_bytes,
                msg_bytes=self.msg_bytes,
                link_payload_bytes=self.link_payload_bytes,
                link_classes=self.link_class_names(),
                spec=self.spec.to_dict(),
                tiers=self._tiers_meta())
            self._fold_step_t = jax.jit(
                self._make_fold(chunked=False, telemetry=True))
            self._fold_chunk_t = jax.jit(
                self._make_fold(chunked=True, telemetry=True))

    # ------------------------------------------------------------------
    def _with_fleet(self, fn):
        """Run ``fn`` under this engine's active-fleet context (identity
        without one). The compiled round reads the fleet at trace time —
        and tracing happens inside the jitted call — so the wrapper must
        surround every dispatch, not just the first."""
        if self.fleet is None:
            return fn

        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            with shard.use_fleet(self.fleet):
                return fn(*args, **kwargs)

        return wrapped

    # ------------------------------------------------------------------
    def _make_step(self):
        loss_fn, opt = self.loss_fn, self.opt
        weights = self.sample_weights
        spec = self.spec
        faults = self.faults
        tiers = self.tiers
        track_div = self.track_divergence
        fleet = self.fleet
        m, net = self.m, self.network
        model_bytes = self.model_bytes
        inter_model_bytes = self.inter_model_bytes
        static_adj, mobile = self._static_adj, self._mobile
        bw, lat = self._link_bw, self._link_lat
        agg_bw, agg_lat = self._agg_bw, self._agg_lat
        # full availability needs no mask at all — the operators then follow
        # the pre-network code path, bitwise
        sample_masks = net is not None and not net.full_availability

        def local_update(params, opt_state, batch):
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            params, opt_state = opt.update(params, grads, opt_state)
            return params, opt_state, loss

        # under a fleet each device updates its own learners: the vmapped
        # update runs per shard, not GSPMD-partitioned
        update = jax.vmap(local_update)
        if fleet is not None:
            update = shard.per_shard(fleet, update)

        def step(params, opt_state, sync_state, batches):
            t = (sync_state.step if tiers is None
                 else sync_state.inter.step)          # this round's index
            if faults is not None:
                # fault plane, pure in (fault_seed, t) like availability.
                # A learner REJOINING this round (crashed at t-1, up now)
                # lost its local state: its params / optimizer / per-
                # learner sync-state rows are zeroed — it rejoins COLD.
                # (Hierarchy extra state is cluster-indexed, not learner-
                # indexed, so it is left alone under tiers.)
                crashed = net_faults.crash_mask(faults, m, t)
                restart = net_faults.restart_mask(faults, m, t)
                params = net_faults.lose_state(params, restart, m)
                opt_state = net_faults.lose_state(opt_state, restart, m)
                if tiers is None:
                    sync_state = sync_state._replace(
                        extra=net_faults.lose_state(
                            sync_state.extra, restart, m))
            # availability means REACHABILITY: every learner still takes its
            # local SGD step; unavailable ones just cannot communicate
            with jax.named_scope("repro.local_update"):
                upd, opt_upd, losses = update(params, opt_state, batches)
            if faults is not None:
                # a learner mid-outage is STATELESS, not just unreachable:
                # its training freezes (the update is discarded) and it
                # observes no loss this round
                params = net_faults.freeze_state(upd, params, crashed, m)
                opt_state = net_faults.freeze_state(
                    opt_upd, opt_state, crashed, m)
                losses = jnp.where(crashed, jnp.zeros_like(losses), losses)
                # corrupted / Byzantine rows are perturbed IN the carry:
                # the garbage is what the fleet syncs against, and it
                # persists until a commit (or quarantine warm-start)
                # overwrites the row
                params = net_faults.perturb_params(faults, params, m, t)
            else:
                params, opt_state = upd, opt_upd
            active = (net_availability.sample(net, m, t)
                      if sample_masks else None)
            if faults is not None:
                # crashed + bursting learners drop out of the availability
                # mask — the composition only ever REMOVES learners
                active = net_faults.compose_active(faults, active, m, t)
            if tiers is None:
                adj = (net_topology.adjacency(net, m, t) if mobile
                       else static_adj)
                res = ops.apply_staged(
                    spec, params, sync_state, weights, active=active,
                    adjacency=adj)
                params, sync_state, rec = res.params, res.state, res.rec
                xfers = res.xfers
                # the ledger's inputs: transfer + message counts per link
                # (priced into bytes host-side, in int64)
                link_counts = jnp.stack([xfers, res.link_msgs], axis=-1)
                if net is not None:
                    net_time = net_cost.round_network_time(
                        xfers, res.link_msgs, model_bytes, bw, lat)
                else:
                    net_time = jnp.float32(0.0)
            else:
                # the intra tier runs THIS engine's (possibly asyncified)
                # spec — resolve_spec on a spec is the identity, so the
                # hierarchy sees exactly the stages the flat path would
                hres = apply_hierarchical(
                    spec, tiers, params, sync_state, weights, active)
                params, sync_state, rec = hres.params, hres.state, hres.rec
                xfers = hres.member_xfers
                link_counts = jnp.stack([
                    jnp.concatenate([hres.member_xfers, hres.agg_xfers]),
                    jnp.concatenate([hres.member_msgs, hres.agg_msgs]),
                ], axis=-1)
                if net is not None:
                    # the round's network time is the two tiers back to
                    # back: members sync with their aggregator, then the
                    # aggregators with the top coordinator
                    net_time = (
                        net_cost.round_network_time(
                            hres.member_xfers, hres.member_msgs,
                            model_bytes, bw, lat)
                        + net_cost.round_network_time(
                            hres.agg_xfers, hres.agg_msgs,
                            inter_model_bytes, agg_bw, agg_lat))
                else:
                    net_time = jnp.float32(0.0)
            if fleet is not None:
                # pin the committed carry back to its input placement so
                # chunk-to-chunk carry sharding is a fixpoint (no reshard
                # between calls); leaves without a leading learner axis
                # (e.g. scalar optimizer counts) pass through untouched
                params = shard.constrain_fleet(fleet, params)
                opt_state = shard.constrain_fleet(fleet, opt_state)
            div = divergence(params) if track_div else jnp.zeros(())
            num_active = (jnp.sum(active).astype(jnp.int32)
                          if active is not None else jnp.int32(m))
            # async-timeline observability: summarize the trigger-carried
            # state AFTER the round. Key membership is static, so
            # protocols without a timeline/age trade zero device work for
            # the constant zeros.
            extra = (sync_state.extra if tiers is None
                     else sync_state.intra.extra)
            num_inflight = (jnp.sum(extra["inflight"] > 0).astype(jnp.int32)
                            if "inflight" in extra else jnp.int32(0))
            age_key = next(
                (k for k in ("age", "staleness") if k in extra), None)
            max_age = (jnp.max(extra[age_key]).astype(jnp.int32)
                       if age_key is not None else jnp.int32(0))
            # fault/robustness observability — same static-key-membership
            # pattern: fault-free runs of non-robust specs trade zero
            # device work for the constant zeros
            num_faulty = (net_faults.num_faulty(faults, m, t)
                          if faults is not None else jnp.int32(0))
            num_quar = (jnp.sum(extra["health"] > 0).astype(jnp.int32)
                        if "health" in extra else jnp.int32(0))
            num_rec = (jnp.sum(extra["recovered"]).astype(jnp.int32)
                       if "recovered" in extra else jnp.int32(0))
            return params, opt_state, sync_state, ProtocolMetrics(
                losses, rec, div, num_active, net_time, xfers, link_counts,
                num_inflight, max_age, num_faulty, num_quar, num_rec)

        return step

    def _make_chunk(self):
        """n rounds as ONE compiled program: jax.lax.scan over the round
        axis, carry = (params, opt_state, sync_state), stacked per-round
        ``ProtocolMetrics`` as the scan output. Dispatch (and the host
        sync on counters) happens once per chunk instead of once per round,
        which is where the per-round Python loop spent nearly all of its
        wall-clock at simulator scale."""
        step = self._make_step()

        def chunk(params, opt_state, sync_state, batches):
            def body(carry, batch):
                params, opt_state, sync_state = carry
                params, opt_state, sync_state, metrics = step(
                    params, opt_state, sync_state, batch)
                return (params, opt_state, sync_state), metrics

            (params, opt_state, sync_state), metrics = jax.lax.scan(
                body, (params, opt_state, sync_state), batches)
            return params, opt_state, sync_state, metrics

        return chunk

    # ------------------------------------------------------------------
    def _make_fold(self, chunked: bool, telemetry: bool = False):
        """The host-counter fold as ONE device program: every per-call
        reduction the cumulative counters need, computed on device and
        fetched in a single transfer — ``step``/``run_chunk`` used to pay
        ~6 separate ``float(...)``/``int(...)``/``np.asarray(...)``
        device syncs per call.

        With ``telemetry`` the fold additionally carries the PER-ROUND
        series the recorder materializes records from (``per_round``: a
        dict of (n, ...) arrays) — still one device program and one
        transfer; the ``telemetry=False`` program is byte-identical to
        the pre-telemetry fold."""
        fields = ops.CommRecord._fields
        carries_state = bool(self.spec.extra_state)
        has_faults = self.faults is not None
        carries_health = "health" in self.spec.extra_state

        def fold(metrics: ProtocolMetrics):
            if chunked:     # leaves carry a leading round axis: reduce it
                out = {
                    "loss": jnp.sum(metrics.loss_per_learner),
                    "loss_per_learner": jnp.sum(
                        metrics.loss_per_learner, axis=0),
                    "comm": {k: jnp.sum(getattr(metrics.comm, k))
                             for k in fields},
                    "net_time": jnp.sum(metrics.net_time),
                    "num_active": jnp.sum(metrics.num_active),
                    "link_xfers": jnp.sum(metrics.link_xfers, axis=0),
                    "link_counts": jnp.sum(metrics.link_counts, axis=0),
                }
            else:
                out = {
                    "loss": jnp.sum(metrics.loss_per_learner),
                    "loss_per_learner": metrics.loss_per_learner,
                    "comm": {k: getattr(metrics.comm, k) for k in fields},
                    "net_time": metrics.net_time,
                    "num_active": metrics.num_active,
                    "link_xfers": metrics.link_xfers,
                    "link_counts": metrics.link_counts,
                }
            if telemetry:
                # normalize the single-round case to a length-1 round axis
                lead = (lambda x: x) if chunked else (lambda x: x[None])
                out["per_round"] = {
                    "loss": jnp.sum(lead(metrics.loss_per_learner), axis=1),
                    "divergence": lead(metrics.divergence),
                    "num_active": lead(metrics.num_active),
                    "net_time": lead(metrics.net_time),
                    "comm": {k: lead(getattr(metrics.comm, k))
                             for k in fields},
                    "link_counts": lead(metrics.link_counts),
                }
                if carries_state:
                    # in-flight / staleness-age series, only for triggers
                    # that actually carry state (async timeline, stale) —
                    # records of stateless runs stay unchanged
                    out["per_round"]["num_inflight"] = lead(
                        metrics.num_inflight)
                    out["per_round"]["max_age"] = lead(metrics.max_age)
                # fault-plane / robust-trigger series: key membership is
                # static, so JSONL streams of fault-free runs of the
                # non-robust presets stay byte-identical
                if has_faults:
                    out["per_round"]["num_faulty"] = lead(
                        metrics.num_faulty)
                if carries_health:
                    out["per_round"]["num_quarantined"] = lead(
                        metrics.num_quarantined)
                    out["per_round"]["num_recovered"] = lead(
                        metrics.num_recovered)
            return out

        return fold

    def _accumulate(self, host: dict, n: int) -> None:
        """Fold one call's (already host-side) reductions into the
        cumulative counters."""
        self.rounds += n
        per = host.get("per_round")
        if per is None:
            self.cumulative_loss += float(host["loss"])
            self.network_time += float(host["net_time"])
        else:
            # telemetry attached: accumulate the float counters as the
            # SEQUENTIAL float64 sum of the per-round series — exactly
            # the ``base + np.cumsum`` arithmetic the recorder's cum_*
            # columns use, so the stream's last record equals these
            # counters bitwise (np.sum pairwise-reassociates; cumsum[-1]
            # is the running sum)
            self.cumulative_loss += float(
                np.cumsum(np.asarray(per["loss"], np.float64))[-1])
            self.network_time += float(
                np.cumsum(np.asarray(per["net_time"], np.float64))[-1])
        self.cumulative_loss_per_learner += host["loss_per_learner"]
        if not self._nonfinite_reported:
            bad = ~np.isfinite(self.cumulative_loss_per_learner)
            if bad.any() or not np.isfinite(self.cumulative_loss):
                # one-shot: the first fold where any loss counter goes
                # non-finite names the offending learners, then stays
                # quiet — a diverging fleet would otherwise flood
                self._nonfinite_reported = True
                sink.get_logger().event(
                    "nonfinite_loss", round=self.rounds,
                    learners=[int(i) for i in np.flatnonzero(bad)])
        for k in ops.CommRecord._fields:
            self.comm_totals[k] += int(host["comm"][k])
        self.active_rounds_total += int(host["num_active"])
        self.link_xfer_totals += host["link_xfers"].astype(np.int64)
        self.link_bytes_totals += self.price_link_counts(
            host["link_counts"].astype(np.int64))

    # ------------------------------------------------------------------
    def step(self, batches) -> ProtocolMetrics:
        """One round. ``batches``: pytree with leading (m, B, ...) leaves."""
        if self.fleet is not None:
            # each device receives only its own learners' samples — the
            # batch never materializes whole on any single device
            batches = shard.put_fleet(self.fleet, batches, axis=0)
        if self.recorder is not None:
            return self._run_observed(self._step, "step", self._fold_step_t,
                                      batches, 1)
        self.params, self.opt_state, self.sync_state, metrics = self._step(
            self.params, self.opt_state, self.sync_state, batches)
        self._accumulate(jax.device_get(self._fold_step(metrics)), 1)
        return metrics

    # ------------------------------------------------------------------
    def run_chunk(self, batches) -> ProtocolMetrics:
        """n rounds in one compiled program (the scanned dual of ``step``).

        ``batches``: pytree with leading (n, m, B, ...) leaves — round t of
        the chunk is ``batches[t]``. Returns stacked ``ProtocolMetrics``
        whose leaves carry the round axis: ``loss_per_learner`` is (n, m),
        every ``CommRecord`` field is (n,). Host-side cumulative counters
        are folded in once per chunk — one device reduction program plus
        one transfer; protocol numerics are identical to n
        calls of ``step`` (same traced round function), so comm counters
        match bitwise and losses to float32 summation order.

        jit recompiles per distinct chunk length n — drive it with a fixed
        chunk size (plus at most one remainder) as ``train.loop`` does.
        """
        n = int(jax.tree.leaves(batches)[0].shape[0])
        r = self.rounds
        with span("repro.engine.run_chunk", round=r):
            if self.fleet is not None:   # (n, m, B, ...): learner axis 1
                batches = shard.put_fleet(self.fleet, batches, axis=1)
            if self.recorder is not None:
                return self._run_observed(self._chunk, "chunk",
                                          self._fold_chunk_t, batches, n)
            with span("repro.engine.dispatch", round=r):
                (self.params, self.opt_state, self.sync_state,
                 metrics) = self._chunk(self.params, self.opt_state,
                                        self.sync_state, batches)
                folded = self._fold_chunk(metrics)
            with span("repro.engine.fetch", round=r):
                host = jax.device_get(folded)
            with span("repro.engine.accumulate", round=r):
                self._accumulate(host, n)
            return metrics

    # ------------------------------------------------------------------
    def _run_observed(self, compute, program: str, fold, batches, n: int):
        """The telemetered dual of ``step``/``run_chunk``: identical
        device programs (the round/chunk computation is byte-for-byte the
        untelemetered one — only the fold carries the extra ``per_round``
        reductions), ONE ``device_get`` of (fold output, trigger-carried
        state snapshot), then host-side record materialization.
        ``program`` is ``compute``'s name in ``trace_counts``."""
        profiling = self.telemetry.profile
        r = self.rounds
        base = self.counters_snapshot()
        if profiling:
            self._profiler.begin(program)
            t0 = time.perf_counter()
        with span("repro.engine.dispatch", round=r):
            self.params, self.opt_state, self.sync_state, metrics = compute(
                self.params, self.opt_state, self.sync_state, batches)
            folded = fold(metrics), self._state_extra()
        # one transfer, and it blocks on the whole round program — the
        # wall-clock below covers execution, not async dispatch
        with span("repro.engine.fetch", round=r):
            host, extra = jax.device_get(folded)
        wall = compiled = recompiles = None
        if profiling:
            wall = time.perf_counter() - t0
            compiled = self._profiler.end()
            recompiles = self._profiler.recompiles
        with span("repro.engine.accumulate", round=r):
            self._accumulate(host, n)
            self.recorder.observe(
                host["per_round"], base, extra, n, wall_s=wall,
                compiled=compiled, recompiles=recompiles)
        return metrics

    def _state_extra(self):
        """The trigger-declared carried state (e.g. staleness ages) as a
        device pytree — snapshotted once per observed chunk."""
        if self.tiers is not None:
            return {"intra": self.sync_state.intra.extra,
                    "inter": self.sync_state.inter.extra}
        return self.sync_state.extra

    def link_class_names(self):
        """(L,) link-class names matching the ledger's rows: learner
        links in round-robin ``NetworkConfig.link_classes`` order
        (``"ideal"`` without a network), then the aggregator uplinks'
        class under a hierarchy."""
        if self.network is None:
            names = ["ideal"] * self.m
        else:
            lc = self.network.link_classes
            names = [lc[i % len(lc)] for i in range(self.m)]
        if self.tiers is not None:
            names += [self.tiers.link_class] * self.tiers.num_clusters
        return tuple(names)

    def _tiers_meta(self):
        if self.tiers is None:
            return None
        return {
            "num_clusters": self.tiers.num_clusters,
            "link_class": self.tiers.link_class,
            "inter": resolve_spec(self.tiers.inter).to_dict(),
        }

    # ------------------------------------------------------------------
    def counters_snapshot(self) -> dict:
        """The cumulative counters the telemetry plane bases its per-round
        ``cum_*`` series on — taken BEFORE a chunk is accumulated."""
        return {
            "rounds": self.rounds,
            "cumulative_loss": self.cumulative_loss,
            "network_time": self.network_time,
            "syncs": self.comm_totals["syncs"],
            "cum_bytes": self.comm_bytes(),
            "link_bytes_totals": self.link_bytes_totals.copy(),
        }

    def counters_state(self) -> dict:
        """JSON-ready snapshot of ALL cumulative counters, for
        checkpointing (``repro.checkpoint.io.save_protocol_state``): a
        resumed run restores these and its telemetry stream continues as
        one continuous record."""
        return {
            "rounds": int(self.rounds),
            "cumulative_loss": float(self.cumulative_loss),
            "cumulative_loss_per_learner": [
                float(x) for x in self.cumulative_loss_per_learner],
            "comm_totals": {k: int(v) for k, v in self.comm_totals.items()},
            "network_time": float(self.network_time),
            "active_rounds_total": int(self.active_rounds_total),
            "link_xfer_totals": [int(x) for x in self.link_xfer_totals],
            "link_bytes_totals": [int(x) for x in self.link_bytes_totals],
        }

    def restore_counters(self, d: dict) -> None:
        """Restore counters saved by :meth:`counters_state`. With a
        recorder attached, re-emits the stream's meta record tagged with
        the resume point so the JSONL stays self-describing."""
        if len(d["cumulative_loss_per_learner"]) != self.m:
            raise ValueError(
                f"counters were saved for m="
                f"{len(d['cumulative_loss_per_learner'])} learners, "
                f"this engine has m={self.m}")
        if len(d["link_bytes_totals"]) != self.num_links:
            raise ValueError(
                f"counters were saved for {len(d['link_bytes_totals'])} "
                f"links, this engine has {self.num_links} (did the "
                f"hierarchy change?)")
        unknown = sorted(set(d["comm_totals"]) - set(self.comm_totals))
        if unknown:
            raise ValueError(f"unknown comm counters in checkpoint: "
                             f"{unknown}")
        self.rounds = int(d["rounds"])
        self.cumulative_loss = float(d["cumulative_loss"])
        self.cumulative_loss_per_learner = np.asarray(
            d["cumulative_loss_per_learner"], np.float32)
        self.comm_totals = {k: int(v) for k, v in d["comm_totals"].items()}
        self.network_time = float(d["network_time"])
        self.active_rounds_total = int(d["active_rounds_total"])
        self.link_xfer_totals = np.asarray(d["link_xfer_totals"], np.int64)
        self.link_bytes_totals = np.asarray(
            d["link_bytes_totals"], np.int64)
        if self.recorder is not None:
            self.recorder.resume(self.rounds)

    # ------------------------------------------------------------------
    def price_link_counts(self, counts: np.ndarray) -> np.ndarray:
        """(..., L, 2) int64 [transfers, messages] -> (..., L) int64 bytes:
        each link's tier payload size times its transfers, plus the control
        messages it sent — exact host-side int64 math, immune to the
        billion-parameter payload sizes that would overflow device int32."""
        return (counts[..., 0] * self.link_payload_bytes
                + counts[..., 1] * self.msg_bytes)

    # ------------------------------------------------------------------
    def comm_bytes_of(self, totals, msg_bytes: Optional[int] = None) -> int:
        """Bytes for a comm-counter dict (paper's c(f) accounting).
        ``msg_bytes`` defaults to the configured ``NetworkConfig.msg_bytes``
        (64 on an ideal network)."""
        if msg_bytes is None:
            msg_bytes = self.network.msg_bytes if self.network else 64
        return (
            (totals["model_up"] + totals["model_down"]) * self.model_bytes
            + totals["messages"] * msg_bytes
        )

    def comm_bytes(self, msg_bytes: Optional[int] = None) -> int:
        """Cumulative communication in bytes (paper's c(f) accounting).

        Under a hierarchy the tiers move different payload sizes, so the
        scalar ``transfers × model_bytes`` formula no longer applies — the
        total is the bytes ledger's sum (exact; ``msg_bytes`` overrides are
        ignored because the configured size is already priced in)."""
        if self.tiers is not None:
            return int(self.link_bytes_totals.sum())
        return self.comm_bytes_of(self.comm_totals, msg_bytes)

    def per_link_bytes(self) -> np.ndarray:
        """The bytes ledger: (L,) cumulative int64 bytes each link carried
        — model payloads at that link's tier payload size PLUS the control
        messages the link sent (violation notices on violators' links,
        poll requests on polled members' links). Rows ``0..m-1`` are the
        learner links; under a hierarchy rows ``m..m+g-1`` are the
        aggregator↔top-coordinator uplinks.

        For coordinator protocols (periodic/fedavg/dynamic, flat or
        hierarchical) ``sum(per_link_bytes()) == comm_bytes()`` — the
        ledger is the per-link breakdown of the paper's c(f), exact even
        with per-tier payload sizes. For ``gossip`` every transfer
        occupies BOTH endpoints' links, so the ledger's sum is exactly
        ``2 * comm_bytes()`` (link occupancy, not fleet throughput)."""
        return self.link_bytes_totals.copy()

    def mean_active(self) -> float:
        """Average fraction of the fleet reachable per executed round."""
        if self.rounds == 0:
            return 1.0
        return self.active_rounds_total / (self.rounds * self.m)

    def mean_model(self):
        from repro.core.divergence import tree_mean
        return tree_mean(self.params)

    def learner_model(self, i: int):
        return jax.tree.map(lambda x: x[i], self.params)


# ---------------------------------------------------------------------------
# serial baseline (paper's ``serial``: one model, all data)
# ---------------------------------------------------------------------------

class SerialLearner:
    """One model, all data — scanned the same way the fleet engine is:
    ``run_chunk`` rolls n rounds into one ``lax.scan`` program, so
    benchmarks sweeping the serial reference pay one jitted dispatch per
    chunk instead of one per round."""

    def __init__(self, loss_fn, init_fn, train: TrainConfig = TrainConfig(),
                 seed: int = 0):
        self.loss_fn = loss_fn
        self.opt = make_optimizer(train)
        self.params = init_fn(jax.random.PRNGKey(seed))
        self.opt_state = self.opt.init(self.params)
        self.cumulative_loss = 0.0

        def _round(params, opt_state, batch):
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            params, opt_state = self.opt.update(params, grads, opt_state)
            return params, opt_state, loss

        @jax.jit
        def _chunk(params, opt_state, batches):
            def body(carry, batch):
                params, opt_state = carry
                params, opt_state, loss = _round(params, opt_state, batch)
                return (params, opt_state), loss

            (params, opt_state), losses = jax.lax.scan(
                body, (params, opt_state), batches)
            return params, opt_state, losses

        self._step = jax.jit(_round)
        self._chunk = _chunk

    def step(self, batch):
        self.params, self.opt_state, loss = self._step(
            self.params, self.opt_state, batch)
        self.cumulative_loss += float(loss)
        return loss

    def run_chunk(self, batches) -> jnp.ndarray:
        """n rounds as one compiled program. ``batches``: pytree with
        leading (n, B, ...) leaves — round t consumes ``batches[t]``.
        Returns the (n,) per-round losses; numerics are identical to n
        ``step`` calls (same traced round body, and ``cumulative_loss``
        accumulates the per-round losses in float64 exactly like the
        per-round driver), so both the loss curve and the running total
        match the ``step`` loop bitwise. jit recompiles per distinct chunk
        length — drive it with a fixed chunk size as ``train.loop`` does."""
        self.params, self.opt_state, losses = self._chunk(
            self.params, self.opt_state, batches)
        # one host transfer + one float64 sum instead of a Python loop.
        # Bitwise-identical to the per-round accumulation whenever the
        # chunk's float32 losses stay within ~29 bits of dynamic range of
        # each other (then every float64 partial sum of the 24-bit-
        # mantissa terms is exact and association cannot matter — pinned
        # by test_serial_run_chunk_matches_step_loop_bitwise); a chunk
        # mixing wildly diverged and normal losses may differ from the
        # step loop in the last ulp
        self.cumulative_loss += float(np.asarray(losses, np.float64).sum())
        return losses


def make_protocol(kind: str, **kw) -> ProtocolConfig:
    return ProtocolConfig(kind=kind, **kw)
