"""Compile-only rehearsals for a described TPU v5e (``v5e:2x2``): the TPU
compiler refuses here, without a chip, what the chip would refuse —
misaligned tiles, VMEM overruns, a Mosaic kernel it cannot partition —
and shows the layout copies it would add. Nothing runs; the programs are
compiled from shapes.

The topology is described inside a module fixture (never at import), so
every pytest worker collects the same tests and only the worker that
runs this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

# the paper's MNIST CNN: 1,199,882 weights per learner
P_MNIST = 1_199_882


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile_sqdist_rows(m, sharding):
    from repro.kernels.sqdist import sqdist_rows
    x = jax.ShapeDtypeStruct((m, P_MNIST), jnp.float32, sharding=sharding)
    r = jax.ShapeDtypeStruct((P_MNIST,), jnp.float32, sharding=sharding)
    return jax.jit(
        lambda x, r: sqdist_rows(x, r, interpret=False)).lower(x, r).compile()


def test_sqdist_rows_compiles_full_width_one_chip(one_chip):
    """(128, 1,199,882) f32: the fleet plane the one-chip smoke run
    monitors, at block_m=8 — the kernel is in the compiled program."""
    compiled = _compile_sqdist_rows(128, one_chip)
    assert "tpu_custom_call" in compiled.as_text()


def test_sqdist_rows_compiles_ragged_m(one_chip):
    """m=12 is not a multiple of the 8-row sublane tile: the row block
    must still be one the chip accepts (never a (1, block) tile)."""
    compiled = _compile_sqdist_rows(12, one_chip)
    assert "tpu_custom_call" in compiled.as_text()


def test_sharded_plane_kernel_compiles_per_shard(topo, monkeypatch):
    """A row-sharded (128, P) plane over four chips under an active
    fleet: the kernel runs per shard (shard_map over "fleet"), so it
    compiles and the plane is never all-gathered."""
    from repro.compat import make_mesh
    from repro.core import shard
    from repro.core.divergence import per_learner_sq_distance_flat
    from repro.kernels import ops

    # this process's backend is the CPU: steer the kernel wrapper to the
    # compiled (not interpreted) kernel the chip would run
    monkeypatch.setattr(ops, "_interp", lambda: False)
    m = 128
    mesh = make_mesh((4,), (shard.FLEET_AXIS,), devices=topo.devices)
    fleet = shard.FleetSharding(mesh=mesh, m=m)
    rows = NamedSharding(mesh, PartitionSpec(shard.FLEET_AXIS, None))
    x = jax.ShapeDtypeStruct((m, P_MNIST), jnp.float32, sharding=rows)
    r = jax.ShapeDtypeStruct((P_MNIST,), jnp.float32,
                             sharding=NamedSharding(mesh, PartitionSpec()))
    with shard.use_fleet(fleet):
        compiled = jax.jit(
            lambda x, r: per_learner_sq_distance_flat(x, r, use_kernel=True)
        ).lower(x, r).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-gather" not in text


def test_sharded_round_updates_learners_per_shard(topo):
    """The sharded engine's round for the MNIST CNN over four chips: each
    chip updates its own learners (the vmapped update runs in a shard_map),
    so the compiled round carries no all-to-all — GSPMD is not left to
    partition the grouped convolutions of m learners' gradients."""
    from repro.config import ProtocolConfig, TrainConfig, get_arch
    from repro.compat import make_mesh
    from repro.core import shard
    from repro.core.protocol import DecentralizedLearner
    from repro.data.pipeline import LearnerStreams
    from repro.data.synthetic import SyntheticMNIST
    from repro.models.cnn import cnn_loss, init_cnn_params

    cfg, m = get_arch("mnist_cnn"), 256
    proto = ProtocolConfig(kind="dynamic", b=1, delta=0.05, layout="sharded")
    dl = DecentralizedLearner(
        lambda p, b: cnn_loss(cfg, p, b), lambda k: init_cnn_params(cfg, k),
        m, proto, TrainConfig(optimizer="sgd", learning_rate=0.05))
    mesh = make_mesh((4,), (shard.FLEET_AXIS,), devices=topo.devices)
    fleet = shard.FleetSharding(mesh=mesh, m=m)
    dl.fleet = fleet
    batch = LearnerStreams(SyntheticMNIST(seed=0, image_size=28), m,
                           batch=10, seed=0).next_chunk(1)

    def spec(x, axis=0):
        lead = jnp.ndim(x) > axis and jnp.shape(x)[axis] == m
        sh = (fleet.row_sharding(jnp.ndim(x), axis) if lead
              else fleet.replicated())
        return jax.ShapeDtypeStruct(jnp.shape(x), x.dtype, sharding=sh)

    st = dl.sync_state
    args = (jax.tree.map(spec, dl.params), jax.tree.map(spec, dl.opt_state),
            st._replace(ref=jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                               sharding=fleet.replicated()),
                st.ref), v=spec(st.v), rng=spec(st.rng), step=spec(st.step),
                extra=jax.tree.map(spec, st.extra)),
            jax.tree.map(lambda x: spec(x[0]), batch))
    with shard.use_fleet(fleet):
        text = jax.jit(dl._make_step()).lower(*args).compile().as_text()
    assert "all-to-all" not in text


# ---------------------------------------------------------------------------
# the fleet plane moves no fleet-sized data inside the round loop
# ---------------------------------------------------------------------------

def test_flat_chunk_moves_no_fleet_sized_copies(one_chip):
    """The engine's chunk for the MNIST CNN under layout="flat" (dynamic,
    b=1, every round a full sync): inside the scan no copy, concatenate,
    slice, pad or dynamic-update-slice the size of the fleet, bare or as
    a fusion of nothing but data movement — the sync stages run on the
    stacked leaves, so the fleet is never concatenated into one (m, P)
    matrix and never relaid out between the local update and the
    sync."""
    from repro.analysis.hlo import loop_data_movement
    from repro.config import ProtocolConfig, TrainConfig, get_arch
    from repro.core.protocol import DecentralizedLearner
    from repro.data.pipeline import LearnerStreams
    from repro.data.synthetic import SyntheticMNIST
    from repro.models.cnn import cnn_loss, init_cnn_params

    cfg, m = get_arch("mnist_cnn"), 16
    proto = ProtocolConfig(kind="dynamic", b=1, delta=1e-9,
                           augmentation="max_distance", layout="flat")
    dl = DecentralizedLearner(
        lambda p, b: cnn_loss(cfg, p, b), lambda k: init_cnn_params(cfg, k),
        m, proto, TrainConfig(optimizer="sgd", learning_rate=0.05))
    batches = LearnerStreams(SyntheticMNIST(seed=0, image_size=28), m,
                             batch=10, seed=0).next_chunk(4)

    def on_chip(x):
        return jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x),
                                    sharding=one_chip)

    args = jax.tree.map(on_chip, (dl.params, dl.opt_state, dl.sync_state,
                                  batches))
    text = jax.jit(dl._make_chunk()).lower(*args).compile().as_text()
    assert " while(" in text
    # m x 1,000,000 f32 elements: below one model per learner, above any
    # activation or batch of this fleet
    fleet_sized = [op for op in loop_data_movement(text)
                   if op[3] >= m * 1_000_000 * 4]
    assert fleet_sized == []
