"""Configuration system for the repro framework.

Dataclass-based, no external deps. A ``ModelConfig`` fully describes one of
the supported architecture families:

* dense decoder (GQA, optional QKV bias, optional sliding window)
* MoE decoder (top-k routing, optional shared experts, optional MLA)
* SSM decoder (Mamba2 / SSD)
* hybrid decoder (parallel attention + SSM heads, Hymba-style)
* CNN classifiers / regressors (the paper's own MNIST and deep-driving nets)

``ShapeConfig`` describes one of the assigned input shapes, ``MeshConfig``
the device mesh, ``ProtocolConfig`` the paper's synchronization protocol and
``TrainConfig`` the optimizer/loop settings.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Optional, Sequence, Tuple

# ---------------------------------------------------------------------------
# Model configuration
# ---------------------------------------------------------------------------

BLOCK_ATTN = "attn"
BLOCK_SSM = "ssm"
BLOCK_HYBRID = "hybrid"

ATTN_FULL = "full"
ATTN_SLIDING = "sliding"

MODALITY_TEXT = "text"
MODALITY_VISION = "vision"   # VLM: stub patch embeddings + text tokens
MODALITY_AUDIO = "audio"     # audio: decoder over codec tokens (stub frontend)


@dataclass(frozen=True)
class MoEConfig:
    """Mixture-of-experts settings for the FFN of a block."""
    num_experts: int = 8
    num_experts_per_tok: int = 2
    num_shared_experts: int = 0          # DeepSeek-style always-on experts
    expert_d_ff: int = 0                 # per-expert hidden dim (0 -> use d_ff)
    capacity_factor: float = 1.25        # dispatch capacity per expert
    router_aux_loss_coef: float = 0.01   # load-balance loss weight


@dataclass(frozen=True)
class MLAConfig:
    """Multi-head latent attention (DeepSeek-V2)."""
    kv_lora_rank: int = 512
    q_lora_rank: int = 0                 # 0 -> full-rank queries
    rope_head_dim: int = 64              # decoupled rope dims per head


@dataclass(frozen=True)
class SSMConfig:
    """Mamba2 / SSD settings."""
    d_state: int = 128
    d_conv: int = 4
    expand: int = 2
    head_dim: int = 64
    chunk_size: int = 64
    ngroups: int = 1


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                           # dense | moe | ssm | hybrid | vlm | audio | cnn
    num_layers: int
    d_model: int
    num_heads: int = 0                    # 0 for attention-free
    num_kv_heads: int = 0
    d_ff: int = 0
    vocab_size: int = 0
    head_dim: int = 0                     # 0 -> d_model // num_heads
    block_type: str = BLOCK_ATTN          # attn | ssm | hybrid
    attn_type: str = ATTN_FULL            # full | sliding
    sliding_window: int = 4096
    qkv_bias: bool = False
    rope_theta: float = 10_000.0
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    modality: str = MODALITY_TEXT
    moe: Optional[MoEConfig] = None
    moe_layer_period: int = 1             # every k-th layer is MoE (1 = all)
    mla: Optional[MLAConfig] = None
    ssm: Optional[SSMConfig] = None
    # CNN-only fields (paper's MNIST / deep-driving nets)
    cnn_spec: Optional[Tuple[Any, ...]] = None
    input_shape: Optional[Tuple[int, ...]] = None   # per-example, CNN/MLP only
    num_outputs: int = 0                            # CNN/MLP head size
    dtype: str = "float32"
    source: str = ""                      # citation for the config
    # scan layers with lax.scan (small HLO). False -> unrolled python loop;
    # used by the roofline tooling to calibrate per-layer costs, since XLA
    # cost_analysis counts a while-loop body ONCE regardless of trip count.
    scan_layers: bool = True

    # ---- derived -----------------------------------------------------
    @property
    def resolved_head_dim(self) -> int:
        if self.head_dim:
            return self.head_dim
        if self.num_heads:
            return self.d_model // self.num_heads
        return 0

    @property
    def is_moe(self) -> bool:
        return self.moe is not None

    @property
    def is_attention_free(self) -> bool:
        return self.block_type == BLOCK_SSM

    @property
    def supports_long_context(self) -> bool:
        """True if decode at 500k+ tokens is sub-quadratic / bounded-state."""
        return (
            self.block_type in (BLOCK_SSM, BLOCK_HYBRID)
            or self.attn_type == ATTN_SLIDING
        )

    def param_count(self) -> int:
        """Analytic parameter count (embedding + blocks + head)."""
        if self.family == "cnn":
            return -1  # computed from the actual pytree instead
        d, L = self.d_model, self.num_layers
        hd = self.resolved_head_dim
        n = 0
        # embeddings
        n += self.vocab_size * d
        if not self.tie_embeddings:
            n += self.vocab_size * d
        per_layer = 0
        # norms
        per_layer += 2 * d
        if self.block_type in (BLOCK_ATTN, BLOCK_HYBRID):
            if self.mla is not None:
                r, rh = self.mla.kv_lora_rank, self.mla.rope_head_dim
                per_layer += d * (r + rh)                       # kv down + shared rope k
                per_layer += r * self.num_heads * (hd + hd)     # k/v up
                if self.mla.q_lora_rank:
                    per_layer += d * self.mla.q_lora_rank
                    per_layer += self.mla.q_lora_rank * self.num_heads * (hd + rh)
                else:
                    per_layer += d * self.num_heads * (hd + rh)
                per_layer += self.num_heads * hd * d            # out proj
            else:
                per_layer += d * self.num_heads * hd            # q
                per_layer += 2 * d * self.num_kv_heads * hd     # k, v
                per_layer += self.num_heads * hd * d            # o
                if self.qkv_bias:
                    per_layer += (self.num_heads + 2 * self.num_kv_heads) * hd
        if self.block_type in (BLOCK_SSM, BLOCK_HYBRID):
            s = self.ssm
            d_in = s.expand * d
            nheads = d_in // s.head_dim
            per_layer += d * 2 * d_in                           # in proj (x, z)
            per_layer += d * (2 * s.ngroups * s.d_state + nheads)  # B, C, dt proj
            per_layer += s.d_conv * (d_in + 2 * s.ngroups * s.d_state)
            per_layer += nheads * 2                             # A_log, D
            per_layer += d_in * d                               # out proj
        # FFN
        if self.is_moe:
            eff = self.moe.expert_d_ff or self.d_ff
            n_moe_layers = L // self.moe_layer_period
            n_dense_layers = L - n_moe_layers
            per_moe = (self.moe.num_experts + self.moe.num_shared_experts) * 3 * d * eff
            per_moe += d * self.moe.num_experts                 # router
            n += n_moe_layers * per_moe + n_dense_layers * (3 * d * self.d_ff)
            n += L * per_layer
        else:
            if self.d_ff:
                per_layer += 3 * d * self.d_ff                  # swiglu
            n += L * per_layer
        return n

    def active_param_count(self) -> int:
        """Params touched per token (MoE: only routed top-k + shared)."""
        if not self.is_moe:
            return self.param_count()
        d, L = self.d_model, self.num_layers
        eff = self.moe.expert_d_ff or self.d_ff
        total = self.param_count()
        n_moe_layers = L // self.moe_layer_period
        all_exp = (self.moe.num_experts + self.moe.num_shared_experts) * 3 * d * eff
        act_exp = (self.moe.num_experts_per_tok + self.moe.num_shared_experts) * 3 * d * eff
        return total - n_moe_layers * (all_exp - act_exp)


# ---------------------------------------------------------------------------
# Input shapes (assigned)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                     # train | prefill | decode


INPUT_SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}


# ---------------------------------------------------------------------------
# Mesh
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class MeshConfig:
    shape: Tuple[int, ...] = (16, 16)
    axes: Tuple[str, ...] = ("data", "model")

    @property
    def num_devices(self) -> int:
        n = 1
        for s in self.shape:
            n *= s
        return n

    @property
    def multi_pod(self) -> bool:
        return "pod" in self.axes

    def axis_size(self, name: str) -> int:
        if name not in self.axes:
            return 1
        return self.shape[self.axes.index(name)]


SINGLE_POD_MESH = MeshConfig((16, 16), ("data", "model"))
MULTI_POD_MESH = MeshConfig((2, 16, 16), ("pod", "data", "model"))


# ---------------------------------------------------------------------------
# The paper's protocol
# ---------------------------------------------------------------------------

PROTO_NOSYNC = "nosync"
PROTO_PERIODIC = "periodic"
PROTO_CONTINUOUS = "continuous"
PROTO_FEDAVG = "fedavg"
PROTO_DYNAMIC = "dynamic"
PROTO_GOSSIP = "gossip"


@dataclass(frozen=True)
class ProtocolConfig:
    """Synchronization protocol Π = (φ, σ).

    ``kind`` selects the operator σ; ``b`` is the check/sync period in local
    steps; ``delta`` the divergence threshold Δ for σ_Δ; ``fedavg_c`` the
    subsampled fraction C for FedAvg; ``augmentation`` selects the
    coordinator's balancing strategy for dynamic averaging. ``gossip`` is
    the coordinator-free baseline: neighborhood averaging over the network
    topology (``NetworkConfig``) every ``b`` rounds.

    ``tiers`` turns the flat protocol into a two-tier star-of-stars
    (``HierarchyConfig``): THIS config becomes the intra-tier operator
    (learners ↔ their cluster's edge aggregator, own ``b``/``delta``) and
    ``tiers.inter`` runs among the edge aggregators. ``tiers=None`` is the
    flat single-coordinator protocol, bitwise-identical to the
    pre-hierarchy engine.

    ``layout`` selects the sync arithmetic: ``"tree"`` (default) runs the
    per-leaf pytree expressions, bitwise-identical to the pre-flat
    engine; ``"flat"`` carries the fleet through the sync stages as the
    blocked fleet plane (``repro.core.sync.stages``) — parameters
    equal to float-reassociation tolerance, identical sync decisions
    (hence bitwise comm counters) unless a distance lands within
    reassociation error of the Delta threshold, and the balancing
    augmentation drops from O(m^2 P) to O(m P); ``"sharded"`` is the
    flat plane with the learner axis split over a device mesh
    (``repro.core.shard``) — same arithmetic as flat, the engine places
    the scan carry so per-learner updates, distances, and commits run
    per-shard and only trigger votes + cohort means cross devices.
    ``shard_devices`` caps how many visible devices the fleet mesh uses
    (0 = all); ``m % n_devices`` must be 0 — every device owns the same
    number of learner rows.
    """
    kind: str = PROTO_DYNAMIC
    b: int = 10
    delta: float = 0.5
    fedavg_c: float = 0.3
    augmentation: str = "max_distance"   # max_distance | random | all
    weighted: bool = False               # Algorithm 2 (unbalanced B^i)
    bytes_per_param: int = 4
    layout: str = "tree"                 # tree | flat | sharded
    shard_devices: int = 0               # sharded: device cap, 0 = all
    tiers: Optional[HierarchyConfig] = None   # two-tier hierarchy on top

    def __post_init__(self):
        if self.b < 1:
            raise ValueError(f"sync period b must be >= 1, got {self.b!r}")
        if not 0.0 < self.fedavg_c <= 1.0:
            raise ValueError(
                f"fedavg_c must be in (0, 1], got {self.fedavg_c!r}")
        # resolving against the protocol preset registry validates the
        # kind (unknown kinds raise with the known list — registering a
        # new protocol makes it a valid kind) and the parameters the
        # preset's stages consume: delta > 0 rejects a dynamic config but
        # never a periodic one, which doesn't read it
        spec = self._spec()
        if self.tiers is not None and not spec.uses_coordinator:
            raise ValueError(
                f"{self.kind} cannot be the intra-tier operator of a "
                "hierarchy: it has no coordinator — a cluster's members "
                "talk to their edge aggregator over uplinks. Use a "
                "coordinator protocol (periodic/fedavg/dynamic) per tier.")

    def _spec(self):
        """The ``ProtocolSpec`` this config resolves to (its preset with
        this config's parameter fields overlaid)."""
        from repro.core.sync.spec import resolve_spec
        return resolve_spec(self)


@dataclass(frozen=True)
class HierarchyConfig:
    """Two-tier star-of-stars coordinator hierarchy.

    The fleet is partitioned into ``num_clusters`` contiguous, equal-size
    clusters (the engine rejects ``m % num_clusters != 0`` at construction
    with a clear message). Each round the enclosing ``ProtocolConfig`` runs
    as the *intra-tier* operator inside every cluster (members ↔ their edge
    aggregator, with per-cluster reference/violation state), the edge
    aggregator model is the availability-masked cluster mean, and
    ``inter`` runs among the ``num_clusters`` aggregator models (its own
    cadence ``b``, threshold ``delta``, and payload ``bytes_per_param`` —
    e.g. a quantized backhaul). When the inter tier synchronizes a set of
    clusters, their reachable members receive the inter-tier adjustment.

    ``link_class`` is the aggregator↔top-coordinator uplink class used by
    the network cost model (edge servers usually sit on wired backhaul);
    member links keep their ``NetworkConfig.link_classes`` assignment.
    """
    num_clusters: int
    inter: ProtocolConfig
    link_class: str = "wired"

    def __post_init__(self):
        if self.num_clusters < 2:
            raise ValueError(
                f"a hierarchy needs >= 2 clusters, got {self.num_clusters} "
                "(one cluster is just the flat protocol — drop tiers=)")
        if not self.inter._spec().uses_coordinator:
            raise ValueError(
                f"the inter-tier operator cannot be {self.inter.kind}: "
                "edge aggregators talk to the top coordinator over a star "
                "of uplinks, not a peer overlay. Use a coordinator "
                "protocol (periodic/fedavg/dynamic/nosync).")
        if self.inter.tiers is not None:
            raise ValueError(
                "hierarchies do not nest: tiers.inter must have tiers=None "
                "(the hierarchy is exactly two tiers).")
        if self.link_class not in LINK_CLASS_NAMES:
            raise KeyError(
                f"unknown aggregator link class {self.link_class!r}; "
                f"known: {sorted(LINK_CLASS_NAMES)}")


# ---------------------------------------------------------------------------
# Network environment (topology, availability, link costs)
# ---------------------------------------------------------------------------

TOPO_STAR = "star"
TOPO_RING = "ring"
TOPO_TORUS = "torus"
TOPO_ERDOS_RENYI = "erdos_renyi"
TOPO_GEOMETRIC = "geometric"

TOPOLOGIES = (
    TOPO_STAR, TOPO_RING, TOPO_TORUS, TOPO_ERDOS_RENYI, TOPO_GEOMETRIC,
)

# Link-class registry contract: the names configs may reference. The
# bandwidth/latency numbers live in ``repro.network.cost.LINK_CLASSES``
# (which asserts it covers exactly these names) — configs validate
# membership HERE so a typo fails at construction, not at trace time.
LINK_CLASS_NAMES = ("wired", "wifi", "lte", "edge")


@dataclass(frozen=True)
class NetworkConfig:
    """Simulated network environment for a fleet of learners.

    Three orthogonal aspects (see ``repro.network``):

    * **topology** — the peer overlay as an (m, m) symmetric adjacency
      matrix: ``star`` | ``ring`` | ``torus`` | ``erdos_renyi`` |
      ``geometric``. ``geometric`` with ``redraw_every=k`` models mobility:
      node positions (hence edges) are re-drawn every k rounds, as a pure
      function of ``(seed, t)`` so it evaluates inside ``lax.scan``.
      Coordinator operators (periodic/fedavg/dynamic) talk over
      learner↔coordinator uplinks and are constrained by *availability*
      only; the overlay governs the coordinator-free ``gossip`` operator.
    * **availability** — per-round (m,) active masks: i.i.d. Bernoulli
      ``act_prob`` dropout, a ``straggler_frac`` subset with its own lower
      ``straggler_act_prob``, and scheduled outages (every ``outage_every``
      rounds a random ``outage_frac`` of the fleet goes dark for
      ``outage_length`` rounds). Unavailable learners keep training
      locally but neither violate, get polled, nor receive averages.
    * **link costs** — per-learner bandwidth/latency classes
      (``repro.network.cost.LINK_CLASSES``) assigned round-robin from
      ``link_classes``; model transfers convert to simulated per-round
      wall-clock and per-link bytes.
    """
    topology: str = TOPO_STAR
    er_p: float = 0.3                    # Erdős–Rényi edge probability
    geo_radius: float = 0.5              # geometric connection radius in [0,1]^2
    redraw_every: int = 0                # >0: re-draw geometric graph every k rounds
    act_prob: float = 1.0                # Bernoulli availability per learner/round
    straggler_frac: float = 0.0          # fraction of learners that straggle
    straggler_act_prob: float = 0.5      # their (lower) availability
    outage_every: int = 0                # 0 = no scheduled outages
    outage_length: int = 1               # rounds an outage lasts
    outage_frac: float = 0.25            # fraction of fleet taken down
    link_classes: Tuple[str, ...] = ("wired",)
    msg_bytes: int = 64                  # control-message size for time accounting
    seed: int = 0

    def __post_init__(self):
        if self.topology not in TOPOLOGIES:
            raise KeyError(
                f"unknown topology {self.topology!r}; "
                f"known: {sorted(TOPOLOGIES)}")
        if not 0.0 <= self.er_p <= 1.0:
            raise ValueError(
                f"er_p is an edge probability, must be in [0, 1]: "
                f"got {self.er_p!r}")
        if not self.geo_radius > 0.0:
            raise ValueError(
                f"geo_radius must be > 0, got {self.geo_radius!r}")
        if self.redraw_every < 0:
            raise ValueError(
                f"redraw_every must be >= 0 (0 = static graph), "
                f"got {self.redraw_every!r}")
        # mobility is a property of the geometric graph (positions move);
        # reject the combo instead of silently keeping other overlays static
        if self.redraw_every != 0 and self.topology != TOPO_GEOMETRIC:
            raise ValueError(
                f"redraw_every only applies to topology='geometric', "
                f"got {self.topology!r}")
        if not 0.0 < self.act_prob <= 1.0:
            raise ValueError(
                f"act_prob is a per-round availability probability, must "
                f"be in (0, 1]: got {self.act_prob!r}")
        if not 0.0 <= self.straggler_frac <= 1.0:
            raise ValueError(
                f"straggler_frac must be in [0, 1], "
                f"got {self.straggler_frac!r}")
        if not 0.0 < self.straggler_act_prob <= 1.0:
            raise ValueError(
                f"straggler_act_prob must be in (0, 1], "
                f"got {self.straggler_act_prob!r}")
        if self.outage_every < 0:
            raise ValueError(
                f"outage_every must be >= 0 (0 = no outages), "
                f"got {self.outage_every!r}")
        if self.outage_length < 1:
            raise ValueError(
                f"outage_length must be >= 1 round, "
                f"got {self.outage_length!r}")
        # an outage longer than its period is a permanent blackout, not a
        # scheduled one — reject rather than silently darken the fleet
        if self.outage_every != 0 and self.outage_length > self.outage_every:
            raise ValueError(
                f"outage_length ({self.outage_length}) must not exceed "
                f"outage_every ({self.outage_every}) — that is a permanent "
                f"blackout, not a scheduled outage")
        if not 0.0 <= self.outage_frac <= 1.0:
            raise ValueError(
                f"outage_frac must be in [0, 1], got {self.outage_frac!r}")
        if len(self.link_classes) < 1:
            raise ValueError(
                "link_classes must name at least one link class "
                "(assigned round-robin over the learner index)")
        unknown = [c for c in self.link_classes if c not in LINK_CLASS_NAMES]
        if unknown:
            raise KeyError(
                f"unknown link class(es) {unknown}; "
                f"known: {sorted(LINK_CLASS_NAMES)}")

    @property
    def full_availability(self) -> bool:
        """True when every learner is reachable every round (the engine
        then skips mask sampling entirely — the pre-network fast path)."""
        return (self.act_prob >= 1.0 and self.straggler_frac == 0.0
                and self.outage_every == 0)


# ---------------------------------------------------------------------------
# Asynchronous timeline
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class AsyncConfig:
    """The event-driven network timeline (``repro.network.events`` +
    ``repro.core.sync.async_sync``), threaded through the engine like
    ``NetworkConfig``.

    Attaching one to a ``DecentralizedLearner`` (directly or via
    ``run_protocol_training(async_net=...)``) rewrites the protocol's
    trigger onto per-learner local clocks with messages in flight: each
    sync exchange flies ``k = ceil(round_trip / round_budget) - 1``
    whole rounds, with the round trip priced from the ``NetworkConfig``
    link classes and the payload size (``payload_bytes``; None = the
    model's own byte size). ``round_budget`` is the simulated seconds
    one scanned round represents — a budget covering the slowest link's
    round trip reproduces the synchronous engine bitwise.

    ``aircomp`` additionally swaps the coordinator's mean/average pair
    for the over-the-air analog-superposition stages: the cohort mean
    arrives through one shared-medium transmission with Gaussian
    receiver noise ``snr_db`` below the aggregate's RMS (draw pure in
    ``(air_seed, t)``).
    """
    round_budget: float = 1.0     # simulated seconds per scanned round
    max_delay: int = 8            # arrival-ring depth (max flight rounds + 1)
    payload_bytes: Optional[int] = None   # None = the engine's model_bytes
    aircomp: bool = False         # swap mean/average -> over-the-air stages
    snr_db: float = 20.0          # receiver SNR below the aggregate's RMS
    air_seed: int = 0             # noise stream seed (pure in (seed, t))

    def __post_init__(self):
        if not self.round_budget > 0:
            raise ValueError(
                f"round_budget must be > 0 simulated seconds, "
                f"got {self.round_budget!r}")
        if self.max_delay < 1:
            raise ValueError(
                f"max_delay must be >= 1 round, got {self.max_delay!r}")
        if self.payload_bytes is not None and self.payload_bytes < 0:
            raise ValueError(
                f"payload_bytes must be >= 0 (or None for the model's "
                f"size), got {self.payload_bytes!r}")


# ---------------------------------------------------------------------------
# Fault injection
# ---------------------------------------------------------------------------

FAULT_BYZANTINE_MODES = ("sign_flip", "scale")


@dataclass(frozen=True)
class FaultConfig:
    """The fault-injection plane (``repro.network.faults``), threaded
    through the engine like ``NetworkConfig``/``AsyncConfig``.

    Attaching one to a ``DecentralizedLearner`` (directly or via
    ``run_protocol_training(faults=...)``) injects faults INSIDE the
    scanned round, every mask a pure function of ``(fault_seed, t)``:

    * **crash/restart episodes** — time is cut into windows of
      ``crash_every`` rounds; in each window a learner crashes with
      probability ``crash_prob`` at a sampled offset for a sampled
      ``outage_min..outage_max``-round outage. While crashed it neither
      trains nor participates (the crash mask composes with the
      availability mask); on restart it rejoins COLD — params, optimizer
      state, and per-learner sync state (staleness counters, arrival
      rings, health) are zeroed, modeling a node that lost local state.
    * **payload corruption** — each round each learner's parameters go
      non-finite with probability ``corrupt_prob`` (NaN on odd rounds,
      Inf on even), the silent poison a plain ``mean`` spreads forever.
    * **Byzantine adversaries** — a fixed ``byzantine_frac`` subset
      (drawn once from ``fault_seed``) replaces its parameters every
      round: ``sign_flip`` negates them, ``scale`` multiplies by
      ``byzantine_scale``.
    * **straggler bursts** — in each ``straggler_every``-round window,
      with probability ``straggler_prob``, a random ``straggler_frac``
      of the fleet goes dark for the window (AND-composed with the
      availability mask like a crash, but without state loss).

    ``faults=None`` leaves the engine bitwise-identical to the
    fault-free path (no fault code is traced at all); a default
    ``FaultConfig()`` has every fault disabled and produces bitwise
    identical results through the traced fault ops. Defenses are
    registered stages (``repro.core.sync.robust``): the
    ``trimmed_mean``/``median`` aggregates, the ``quarantine`` commit,
    the ``robust_periodic``/``robust_dynamic`` presets and the
    ``hardened(spec)`` rewriter.
    """
    fault_seed: int = 0
    crash_prob: float = 0.0       # per-learner per-window crash probability
    crash_every: int = 16         # episode window length (rounds)
    outage_min: int = 1           # shortest outage (rounds)
    outage_max: int = 4           # longest outage (rounds)
    corrupt_prob: float = 0.0     # per-learner per-round NaN/Inf corruption
    byzantine_frac: float = 0.0   # fraction of the fleet that is adversarial
    byzantine_mode: str = "sign_flip"   # sign_flip | scale
    byzantine_scale: float = 10.0       # multiplier for mode="scale"
    straggler_prob: float = 0.0   # per-window burst probability
    straggler_every: int = 8      # burst window length (rounds)
    straggler_frac: float = 0.5   # fraction straggling during a burst

    def __post_init__(self):
        for name in ("crash_prob", "corrupt_prob", "straggler_prob",
                     "straggler_frac"):
            v = getattr(self, name)
            if not 0.0 <= v <= 1.0:
                raise ValueError(
                    f"{name} is a probability/fraction, must be in "
                    f"[0, 1]: got {v!r}")
        if not 0.0 <= self.byzantine_frac < 1.0:
            raise ValueError(
                f"byzantine_frac must be in [0, 1) — a fully adversarial "
                f"fleet has nothing to defend; got {self.byzantine_frac!r}")
        if self.byzantine_mode not in FAULT_BYZANTINE_MODES:
            raise KeyError(
                f"unknown byzantine_mode {self.byzantine_mode!r}; "
                f"known: {sorted(FAULT_BYZANTINE_MODES)}")
        if self.crash_every < 1:
            raise ValueError(
                f"crash_every must be >= 1 round, got {self.crash_every!r}")
        if self.straggler_every < 1:
            raise ValueError(
                f"straggler_every must be >= 1 round, "
                f"got {self.straggler_every!r}")
        if not 1 <= self.outage_min <= self.outage_max:
            raise ValueError(
                f"need 1 <= outage_min <= outage_max, got "
                f"outage_min={self.outage_min!r}, "
                f"outage_max={self.outage_max!r}")
        if self.outage_max > self.crash_every:
            raise ValueError(
                f"outage_max ({self.outage_max}) must not exceed "
                f"crash_every ({self.crash_every}) — a crash outliving its "
                f"episode window is a permanent loss, not a restart")


# ---------------------------------------------------------------------------
# Telemetry
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class TelemetryConfig:
    """The fleet telemetry plane (``repro.telemetry``).

    Attaching one to a ``DecentralizedLearner`` (directly or via
    ``run_protocol_training(telemetry=...)`` /
    ``benchmarks/run.py --telemetry``) streams a schema'd round record
    per executed round — loss, divergence, trigger accounting, cohort
    size, reachability, simulated net-time, exact cumulative bytes — to
    ``path`` as JSONL, with the newest ``ring`` records also held in
    memory. Records are materialized host-side from the per-chunk fold
    the engine already fetches: zero extra device work, zero extra
    transfers. No config (``telemetry=None``) leaves the engine
    bitwise-identical to the untelemetered path.

    ``per_link`` adds the per-link byte vector to every round record
    (L integers per round — sizeable for large fleets, hence opt-in).
    ``profile`` adds wall-clock + recompile accounting per chunk
    (``perf_counter`` around a blocked dispatch; whether the chunk
    compiled comes from ``repro.telemetry.trace.trace_counts``). Named
    spans and scopes show up in any ``jax.profiler`` trace without a
    config (``repro.telemetry.trace``).
    """
    path: Optional[str] = None    # JSONL sink; None = ring buffer only
    append: bool = False          # append to path (checkpoint resume)
    ring: int = 1024              # in-memory ring capacity (records)
    per_link: bool = False        # per-link bytes on every round record
    profile: bool = False         # wall-clock + recompile spans per chunk

    def __post_init__(self):
        if self.ring < 1:
            raise ValueError(
                f"ring must hold >= 1 record, got {self.ring!r}")


# ---------------------------------------------------------------------------
# Training
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class TrainConfig:
    optimizer: str = "sgd"                 # sgd | momentum | adam | rmsprop
    learning_rate: float = 0.1
    momentum: float = 0.9
    beta1: float = 0.9
    beta2: float = 0.999
    eps: float = 1e-8
    weight_decay: float = 0.0
    micro_batch: int = 0                   # 0 -> no microbatching
    remat: bool = True                     # activation checkpointing per layer
    seed: int = 0


@dataclass(frozen=True)
class RunConfig:
    model: ModelConfig
    shape: ShapeConfig
    mesh: MeshConfig = SINGLE_POD_MESH
    protocol: ProtocolConfig = ProtocolConfig()
    train: TrainConfig = TrainConfig()
    num_learners: int = 1                  # m; learner axis for dynamic mode
    network: Optional[NetworkConfig] = None  # None = ideal always-on star

    def replace(self, **kw) -> "RunConfig":
        return dataclasses.replace(self, **kw)


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_ARCH_REGISTRY: dict = {}


def register_arch(name: str, full_fn, smoke_fn) -> None:
    _ARCH_REGISTRY[name] = (full_fn, smoke_fn)


def get_arch(name: str, smoke: bool = False) -> ModelConfig:
    import repro.configs  # noqa: F401  (triggers registration)
    if name not in _ARCH_REGISTRY:
        raise KeyError(
            f"unknown arch {name!r}; known: {sorted(_ARCH_REGISTRY)}")
    full_fn, smoke_fn = _ARCH_REGISTRY[name]
    return smoke_fn() if smoke else full_fn()


def list_archs() -> Sequence[str]:
    import repro.configs  # noqa: F401
    return sorted(_ARCH_REGISTRY)
