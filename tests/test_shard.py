"""The device-sharded fleet plane (ISSUE 8): ``layout="sharded"`` is the
blocked fleet plane with the learner axis split over a device mesh —
same ``ProtocolSpec`` compile, third execution backend.

The equivalence contract under test is the acceptance criterion: for
every registered preset (the six kinds + ``"stale"``), under
availability masks and a two-tier hierarchy, ``layout="sharded"`` must
reproduce ``layout="flat"``'s communication EXACTLY — comm counters,
the per-link bytes ledger, simulated network time — and its parameters
to float-reassociation tolerance. A sharded and a flat run of the same
spec with ``TelemetryConfig`` attached must stream interchangeable
JSONL round records, and checkpoint-resume counter continuity must
survive the sharded carry.

On one device every constraint is a no-op placement, so sharded == flat
bitwise; the multi-device tests (skipped unless >1 device is visible —
CI forces 8 host devices via ``XLA_FLAGS``) additionally assert the
carry is REALLY split across the mesh and the same equalities hold
across real per-shard execution.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import (
    HierarchyConfig, NetworkConfig, ProtocolConfig, TelemetryConfig,
    TrainConfig, get_arch,
)
from repro.core import shard
from repro.core.protocol import DecentralizedLearner
from repro.core.sync.spec import (
    LAYOUTS, PLANE_LAYOUTS, ProtocolSpec, pin_rows, resolve_spec,
)
from repro.data.pipeline import LearnerStreams
from repro.data.synthetic import GraphicalModelStream
from repro.models.cnn import cnn_loss, init_cnn_params

N_DEV = len(jax.devices())
multi_device = pytest.mark.skipif(
    N_DEV < 2, reason="needs >1 device (CI forces 8 via "
    "XLA_FLAGS=--xla_force_host_platform_device_count=8)")

# every registered preset, smallest parameters that make its trigger
# fire within the fixture's horizon (mirrors test_flatten.py)
PRESETS = {
    "nosync": dict(kind="nosync"),
    "periodic": dict(kind="periodic", b=3),
    "continuous": dict(kind="continuous", b=1),
    "fedavg": dict(kind="fedavg", b=2, fedavg_c=0.5),
    "dynamic": dict(kind="dynamic", b=2, delta=0.5),
    "gossip": dict(kind="gossip", b=2),
    "stale": dict(kind="stale"),
}


def _run_engine(proto, rounds=30, m=8, seed=0, telemetry=None):
    cfg = get_arch("drift_mlp", smoke=True)
    src = GraphicalModelStream(seed=0, drift_prob=0.0)
    streams = LearnerStreams(src, m, batch=10, seed=seed)
    dl = DecentralizedLearner(
        lambda p, b: cnn_loss(cfg, p, b),
        lambda k: init_cnn_params(cfg, k), m, proto,
        TrainConfig(optimizer="sgd", learning_rate=0.05),
        network=NetworkConfig(act_prob=0.6, topology="ring",
                              link_classes=("wifi", "lte")),
        telemetry=telemetry)
    dl.run_chunk(streams.next_chunk(rounds))
    return dl


def _assert_comm_equal(a, b):
    assert a.comm_totals == b.comm_totals
    np.testing.assert_array_equal(a.link_xfer_totals, b.link_xfer_totals)
    np.testing.assert_array_equal(a.link_bytes_totals, b.link_bytes_totals)
    assert a.network_time == b.network_time


def _assert_params_close(a, b, rtol=2e-4, atol=1e-6):
    for x, y in zip(jax.tree.leaves(a.params), jax.tree.leaves(b.params)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=rtol, atol=atol)


# ---------------------------------------------------------------------------
# registration: the third layout is a first-class spec citizen
# ---------------------------------------------------------------------------

def test_sharded_is_a_registered_layout():
    assert "sharded" in LAYOUTS
    assert "sharded" in PLANE_LAYOUTS and "tree" not in PLANE_LAYOUTS
    spec = resolve_spec(ProtocolConfig(kind="dynamic", layout="sharded",
                                       shard_devices=1))
    assert spec.param("layout") == "sharded"
    assert spec.param("shard_devices") == 1
    # serialization round-trips the layout like any other param
    back = ProtocolSpec.from_json(spec.to_json())
    assert back == spec


def test_spec_rejects_bad_shard_devices():
    with pytest.raises(ValueError, match="shard_devices"):
        ProtocolSpec(trigger="divergence", cohort="balanced",
                     aggregate="mean", commit="balancing",
                     params={"b": 2, "delta": 0.5, "shard_devices": -1})


def test_fleet_sharding_validates_divisibility():
    if N_DEV > 1:     # m % 1 == 0 always — nothing to reject on one device
        with pytest.raises(ValueError, match="m % n_devices"):
            shard.fleet_sharding(N_DEV + 1, N_DEV)
    with pytest.raises(ValueError, match="device"):
        shard.fleet_sharding(8, N_DEV + 1)   # more than visible
    fs = shard.fleet_sharding(4 * N_DEV, 0)
    assert fs.n_devices == N_DEV
    assert fs.rows_per_device == 4


def test_engine_rejects_indivisible_fleet():
    if N_DEV == 1:
        pytest.skip("m % 1 == 0 always — nothing to reject on one device")
    with pytest.raises(ValueError, match="m % n_devices"):
        _run_engine(ProtocolConfig(kind="dynamic", b=2, delta=0.5,
                                   layout="sharded"), m=N_DEV + 1)


def test_constrain_rows_is_identity_without_a_fleet():
    x = jnp.ones((4, 3))
    assert shard.constrain_rows(x) is x
    fs = shard.fleet_sharding(4, 1)
    with shard.use_fleet(fs):
        assert shard.current_fleet() is fs
        y = shard.constrain_rows(x)
        assert y.shape == x.shape
        # a non-fleet leading dim (the hierarchy's per-cluster plane)
        # passes through untouched
        z = jnp.ones((2, 3))
        assert shard.constrain_rows(z) is z
    assert shard.current_fleet() is None


def test_kernel_distances_per_shard_match_dense():
    """Under an active fleet the Pallas row kernel runs per shard
    (shard_map over "fleet", interpret mode on the CPU) and agrees with
    the dense rows."""
    from repro.core.divergence import per_learner_sq_distance_flat
    m, n = 8, 65536 + 3              # one kernel column block + a tail
    fs = shard.fleet_sharding(m, 0)
    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    X = jax.device_put(jax.random.normal(k1, (m, n)), fs.row_sharding(2))
    r = jax.random.normal(k2, (n,))
    with shard.use_fleet(fs):
        got = jax.jit(lambda X, r: per_learner_sq_distance_flat(
            X, r, use_kernel=True))(X, r)
    want = per_learner_sq_distance_flat(X, r, use_kernel=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5)


def test_balancing_cohort_per_shard_matches_off_mesh():
    """Under an active fleet the balancing loop's incremental running
    sum adds rows of learner-sharded blocks: the same cohort, bitwise,
    and the same mean as off the mesh."""
    from repro.core.divergence import per_learner_sq_distance, tree_mean
    from repro.core.sync import stages
    m = 4 * N_DEV
    fs = shard.fleet_sharding(m, 0)
    k1, k2 = jax.random.split(jax.random.PRNGKey(5))
    X = {"w": jax.device_put(jax.random.normal(k1, (m, 3, 4)),
                             fs.row_sharding(3)),
         "b": jax.device_put(jax.random.normal(k2, (m, 5), jnp.bfloat16),
                             fs.row_sharding(2))}
    ref = tree_mean(X)
    dists = per_learner_sq_distance(X, ref)
    violated = dists >= jnp.max(dists)
    delta = float(jnp.median(dists)) / 10

    def balance(X, rng):
        return stages.cohort_balanced_flat(
            delta, "max_distance", pin_rows(X), ref, violated, rng)

    rng = jax.random.PRNGKey(0)
    with shard.use_fleet(fs):
        mask, mean = jax.jit(balance)(X, rng)
    want_mask, want_mean = balance(X, rng)
    assert np.array_equal(np.asarray(mask), np.asarray(want_mask))
    assert int(jnp.sum(mask)) > 1
    for name in X:
        assert mean[name].dtype == X[name].dtype
        np.testing.assert_allclose(np.asarray(mean[name], np.float32),
                                   np.asarray(want_mean[name], np.float32),
                                   rtol=1e-2, atol=1e-6)


# ---------------------------------------------------------------------------
# the acceptance criterion: sharded == flat for every preset
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(PRESETS))
def test_sharded_engine_matches_flat_engine(name):
    flat = _run_engine(ProtocolConfig(layout="flat", **PRESETS[name]))
    shd = _run_engine(ProtocolConfig(layout="sharded", **PRESETS[name]))
    _assert_comm_equal(flat, shd)
    _assert_params_close(flat, shd)


def test_sharded_hierarchy_matches_flat():
    tiers = HierarchyConfig(num_clusters=4,
                            inter=ProtocolConfig(kind="periodic", b=6))
    base = dict(kind="dynamic", b=2, delta=0.5, tiers=tiers)
    flat = _run_engine(ProtocolConfig(layout="flat", **base))
    shd = _run_engine(ProtocolConfig(layout="sharded", **base))
    _assert_comm_equal(flat, shd)
    _assert_params_close(flat, shd)


def test_sharded_device_subset_matches_full_mesh():
    """``shard_devices`` caps the mesh; any cap yields the same run."""
    base = dict(kind="dynamic", b=2, delta=0.5)
    full = _run_engine(ProtocolConfig(layout="sharded", **base))
    one = _run_engine(ProtocolConfig(layout="sharded", shard_devices=1,
                                     **base))
    _assert_comm_equal(full, one)
    _assert_params_close(full, one)


# ---------------------------------------------------------------------------
# telemetry: sharded and flat stream interchangeable round records
# ---------------------------------------------------------------------------

def _stream(tmp_path, layout, tag):
    path = str(tmp_path / f"{tag}.jsonl")
    dl = _run_engine(
        ProtocolConfig(kind="dynamic", b=2, delta=0.5, layout=layout),
        telemetry=TelemetryConfig(path=path, per_link=True))
    dl.recorder.close()
    with open(path) as f:
        recs = [json.loads(line) for line in f]
    return dl, recs


def test_telemetry_streams_identical_across_layouts(tmp_path):
    fdl, frecs = _stream(tmp_path, "flat", "flat")
    sdl, srecs = _stream(tmp_path, "sharded", "sharded")
    _assert_comm_equal(fdl, sdl)
    fr = [r for r in frecs if r["kind"] == "round"]
    sr = [r for r in srecs if r["kind"] == "round"]
    assert len(fr) == len(sr) == 30
    for a, b in zip(fr, sr):
        # integer accounting bitwise; float series to float32 resolution
        # (cross-device reductions may reassociate)
        for k, va in a.items():
            vb = b[k]
            if isinstance(va, float):
                np.testing.assert_allclose(va, vb, rtol=1e-5, atol=1e-7)
            else:
                assert va == vb, (k, va, vb)
    # the meta records differ only in the spec's layout param
    fmeta = [r for r in frecs if r["kind"] == "meta"][0]
    smeta = [r for r in srecs if r["kind"] == "meta"][0]
    assert fmeta["spec"]["params"]["layout"] == "flat"
    assert smeta["spec"]["params"]["layout"] == "sharded"


def test_counter_continuity_across_resume_under_sharded_carry(tmp_path):
    """checkpoint counters -> restore into a FRESH sharded engine -> the
    stream continues as one contiguous record (the sharded carry changes
    nothing about host-side counter arithmetic)."""
    path = str(tmp_path / "resume.jsonl")
    proto = ProtocolConfig(kind="dynamic", b=2, delta=0.5,
                           layout="sharded")
    dl = _run_engine(proto, rounds=15,
                     telemetry=TelemetryConfig(path=path, per_link=True))
    dl.recorder.close()
    saved = dl.counters_state()
    assert saved["rounds"] == 15

    dl2 = DecentralizedLearner(
        dl.loss_fn, lambda k: init_cnn_params(
            get_arch("drift_mlp", smoke=True), k), dl.m, proto,
        TrainConfig(optimizer="sgd", learning_rate=0.05),
        network=NetworkConfig(act_prob=0.6, topology="ring",
                              link_classes=("wifi", "lte")),
        telemetry=TelemetryConfig(path=path, per_link=True, append=True))
    dl2.params, dl2.sync_state = dl.params, dl.sync_state
    dl2.restore_counters(saved)
    assert dl2.comm_totals == dl.comm_totals
    streams = LearnerStreams(GraphicalModelStream(seed=0, drift_prob=0.0),
                             dl.m, batch=10, seed=0)
    streams.next_chunk(15)                       # replay consumed data
    dl2.run_chunk(streams.next_chunk(15))
    dl2.recorder.close()

    with open(path) as f:
        recs = [json.loads(line) for line in f]
    rounds = [r["round"] for r in recs if r["kind"] == "round"]
    assert rounds == list(range(1, 31))          # contiguous across resume
    metas = [r for r in recs if r["kind"] == "meta"]
    assert metas[-1]["resumed_rounds"] == 15
    last = [r for r in recs if r["kind"] == "round"][-1]
    assert last["cum_syncs"] == dl2.comm_totals["syncs"]
    assert last["cum_bytes"] == dl2.comm_bytes()


# ---------------------------------------------------------------------------
# multi-device: the carry is REALLY split (CI: 8 forced host devices)
# ---------------------------------------------------------------------------

@multi_device
def test_sharded_carry_lives_on_all_devices():
    dl = _run_engine(ProtocolConfig(kind="dynamic", b=2, delta=0.5,
                                    layout="sharded"))
    for leaf in jax.tree.leaves(dl.params):
        assert len(leaf.sharding.device_set) == N_DEV, leaf.sharding
        assert leaf.sharding.spec[0] == shard.FLEET_AXIS
    # the reference model replicates; the scalar counters too
    for leaf in jax.tree.leaves(dl.sync_state.ref):
        assert leaf.sharding.is_fully_replicated


@multi_device
@pytest.mark.parametrize("name", ["dynamic", "gossip", "stale"])
def test_sharded_multi_device_matches_flat(name):
    """Same comm accounting across real per-shard execution; parameters
    to reassociation tolerance (cross-device means may re-associate)."""
    flat = _run_engine(ProtocolConfig(layout="flat", **PRESETS[name]))
    shd = _run_engine(ProtocolConfig(layout="sharded", **PRESETS[name]))
    _assert_comm_equal(flat, shd)
    _assert_params_close(flat, shd)


@multi_device
def test_sharded_two_device_subset():
    """shard_devices=2 places the fleet on exactly two devices and still
    reproduces the flat run."""
    flat = _run_engine(ProtocolConfig(kind="dynamic", b=2, delta=0.5,
                                      layout="flat"))
    shd = _run_engine(ProtocolConfig(kind="dynamic", b=2, delta=0.5,
                                     layout="sharded", shard_devices=2))
    leaf = jax.tree.leaves(shd.params)[0]
    assert len(leaf.sharding.device_set) == 2
    _assert_comm_equal(flat, shd)
    _assert_params_close(flat, shd)
