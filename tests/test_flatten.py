"""The fleet plane: the blocked plane's round and balancing cohort,
layout="flat" equivalence against the default tree layout for every
preset, and the engine/hierarchy integration.

The equivalence contract under test is the acceptance criterion:
``layout="tree"`` stays bitwise (the golden regression in
test_sync_kernel.py covers that); ``layout="flat"`` must reproduce the
tree layout's communication EXACTLY (comm counters, per-link transfers,
cohort decisions — guaranteed whenever no distance sits within
float-reassociation error of the Delta threshold, which holds for every
deterministic fixture here) and its parameters to float-reassociation
tolerance.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.config import (
    HierarchyConfig, NetworkConfig, ProtocolConfig, TrainConfig, get_arch,
)
from repro.core import operators as ops
from repro.core.divergence import (
    per_learner_sq_distance, per_learner_sq_distance_flat, tree_mean,
)
from repro.core.protocol import DecentralizedLearner
from repro.core.sync import PROTOCOLS, stages
from repro.data.pipeline import LearnerStreams
from repro.data.synthetic import GraphicalModelStream
from repro.models.cnn import cnn_loss, init_cnn_params
from repro.network import topology

from conftest import make_stacked


# ---------------------------------------------------------------------------
# the blocked plane: the stages run on the stacked leaves themselves
# ---------------------------------------------------------------------------

_FLOAT_DTYPES = (jnp.float32, jnp.bfloat16, jnp.float16)

# primitives that would join or cut the leaves into one (m, P) matrix;
# the blocked plane needs none
_JOINS = {"concatenate", "pad", "dynamic_update_slice"}


def _all_primitives(jaxpr):
    """Every primitive of a jaxpr, sub-jaxprs (jit, cond, while) included."""
    found = set()
    for e in jaxpr.eqns:
        found.add(e.primitive.name)
        for v in e.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    found |= _all_primitives(inner)
    return found


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000), m=st.integers(2, 6),
       nleaves=st.integers(1, 4), data=st.data())
def test_plane_round_joins_no_leaves(seed, m, nleaves, data):
    """The flat layout's dynamic round (condition, balancing cohort,
    aggregate, commit) over random model pytrees (mixed float dtypes,
    mixed ranks incl. scalars) traces to no concatenate, pad or
    dynamic-update-slice, and commits each leaf in its own shape and
    dtype."""
    key = jax.random.PRNGKey(seed)
    tree = {}
    for i in range(nleaves):
        rank = data.draw(st.integers(0, 3))
        shape = tuple(data.draw(st.integers(1, 5)) for _ in range(rank))
        dtype = data.draw(st.sampled_from(_FLOAT_DTYPES))
        key, sub = jax.random.split(key)
        tree[f"w{i}"] = jax.random.normal(sub, (m,) + shape, dtype)
    spec = PROTOCOLS["dynamic"].with_params(b=1, delta=1e-3, layout="flat")
    state = ops.init_state(tree_mean(tree), seed)
    round_fn = spec.compile()
    prims = _all_primitives(jax.make_jaxpr(round_fn)(tree, state).jaxpr)
    assert "while" in prims          # the balancing loop was traced
    assert not prims & _JOINS, prims & _JOINS
    out = round_fn(tree, state).params
    assert jax.tree.structure(out) == jax.tree.structure(tree)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
        assert a.dtype == b.dtype and a.shape == b.shape


def test_plane_pins_are_the_identity_off_mesh():
    """With no active fleet the blocked plane is the configuration itself:
    ``pin_rows`` returns the very leaves it was given, bitwise, and
    traces to no equation at all."""
    from repro.core.sync.spec import pin_rows
    stacked = make_stacked(jax.random.PRNGKey(0), 4)
    pinned = pin_rows(stacked)
    for a, b in zip(jax.tree.leaves(stacked), jax.tree.leaves(pinned)):
        assert a is b
    assert jax.make_jaxpr(pin_rows)(stacked).eqns == []


def test_flat_distances_match_tree_distances():
    """The condition's distances under the flat layout equal the tree
    layout's bitwise (the same per-leaf reduction on the same leaves),
    and the Pallas row kernel over the same rows concatenated into one
    (m, P) matrix agrees to rounding."""
    stacked = make_stacked(jax.random.PRNGKey(2), 5)
    ref = tree_mean(stacked)
    want = per_learner_sq_distance(stacked, ref)
    dists = {}
    for layout in ("tree", "flat"):
        spec = PROTOCOLS["dynamic"].with_params(b=1, delta=0.05,
                                                layout=layout)
        ctx = stages.StageCtx(
            params=spec.resolved_params(), stacked=stacked,
            state=ops.init_state(ref), weights=None, active=None,
            adjacency=None, m=5, t=jnp.int32(1), reach=jnp.ones(5, bool),
            plane=layout == "flat")
        dists[layout] = stages._divergence_condition(ctx)[2]["dists"]
    assert np.array_equal(np.asarray(dists["flat"]), np.asarray(want))
    assert np.array_equal(np.asarray(dists["tree"]), np.asarray(want))
    X = jnp.concatenate([x.reshape(5, -1) for x in jax.tree.leaves(stacked)],
                        axis=1)
    r = jnp.concatenate([x.reshape(-1) for x in jax.tree.leaves(ref)])
    got_k = per_learner_sq_distance_flat(X, r, use_kernel=True)
    np.testing.assert_allclose(np.asarray(got_k), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def _mixed_fleet(m=6, seed=3):
    """A mixed f32 + bf16 fleet with ranks 1-3 and its mean."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    stacked = {"w": jax.random.normal(k1, (m, 4, 3), jnp.float32),
               "b": jax.random.normal(k2, (m, 5), jnp.bfloat16),
               "s": 3.0 * jax.random.normal(k3, (m,), jnp.float32)}
    return stacked, tree_mean(stacked)


def _assert_rows_close(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert x.dtype == y.dtype and x.shape == y.shape
        np.testing.assert_allclose(np.asarray(x, np.float32),
                                   np.asarray(y, np.float32),
                                   rtol=1e-2 if x.dtype == jnp.bfloat16
                                   else 1e-5, atol=1e-6)


@pytest.mark.parametrize("case", [
    "balanced_max_distance", "balanced_random", "balanced_weighted",
    "balanced_all", "balanced_own_priority", "balanced_unreachable",
])
def test_blocked_primitives_match_tree_forms(case):
    """The blocked plane's balancing cohort — an incremental running row
    sum, one row added per step — equals the tree form's full
    re-aggregation on a mixed f32 + bf16 fleet: the same cohort and the
    same mean in the leaf dtypes."""
    m = 6
    stacked, ref = _mixed_fleet(m)
    weights = jnp.arange(1.0, m + 1.0)
    aug = {"balanced_random": "random",
           "balanced_all": "all"}.get(case, "max_distance")
    w = weights if case in ("balanced_weighted", "balanced_all") else None
    reach = (jnp.asarray([1, 1, 0, 1, 1, 1], bool)
             if case == "balanced_unreachable" else None)
    dists = per_learner_sq_distance(stacked, ref)
    # the two farthest learners violate; the safe zone takes more
    delta = float(np.median(np.asarray(dists))) / 10
    violated = dists > jnp.sort(dists)[-3]
    if reach is not None:
        violated = violated & reach
    given = None if case == "balanced_own_priority" else dists
    rng = jax.random.PRNGKey(1)
    t_mask, t_mean = stages.cohort_balanced(
        delta, aug, stacked, ref, violated, rng, w, reach, dists=given)
    f_mask, f_mean = stages.cohort_balanced_flat(
        delta, aug, stacked, ref, violated, rng, w, reach, dists=given)
    assert np.array_equal(np.asarray(t_mask), np.asarray(f_mask))
    # the augmentation ran: the cohort grew past the violators
    assert int(jnp.sum(f_mask)) > int(jnp.sum(violated))
    if reach is not None:
        assert not bool(f_mask[2])
    _assert_rows_close(f_mean, t_mean)


# ---------------------------------------------------------------------------
# operator-level equivalence: flat == tree (counters bitwise, params close)
# ---------------------------------------------------------------------------

ALL_KINDS = ["nosync", "periodic", "fedavg", "dynamic", "gossip"]


def _counters_equal(a, b):
    return all(int(getattr(a, f)) == int(getattr(b, f))
               for f in a._fields)


@settings(max_examples=30, deadline=None)
@given(kind=st.sampled_from(ALL_KINDS), m=st.integers(2, 8),
       seed=st.integers(0, 10_000), mask_bits=st.integers(0, 255),
       weighted=st.booleans())
def test_flat_operator_matches_tree_operator(kind, m, seed, mask_bits,
                                             weighted):
    """One staged round per layout from identical state: comm record and
    per-link counts bitwise, parameters to reassociation tolerance, and
    untouched learners bitwise."""
    stacked = make_stacked(jax.random.PRNGKey(seed), m)
    active = jnp.asarray([(mask_bits >> i) & 1 == 1 for i in range(m)])
    kw = dict(b=1)
    if kind == "dynamic":
        kw["delta"] = 0.05
    weights = jnp.arange(1.0, m + 1.0) if weighted else None
    adj = topology.ring(m) if kind == "gossip" else None
    res = {}
    for layout in ("tree", "flat"):
        cfg = ProtocolConfig(kind=kind, weighted=weighted, layout=layout,
                             **kw)
        res[layout] = ops.apply_staged(
            cfg, stacked, ops.init_state(tree_mean(stacked), seed),
            weights, active=active, adjacency=adj)
    t, f = res["tree"], res["flat"]
    assert _counters_equal(t.rec, f.rec)
    assert np.array_equal(np.asarray(t.xfers), np.asarray(f.xfers))
    assert np.array_equal(np.asarray(t.link_msgs), np.asarray(f.link_msgs))
    for a, b in zip(jax.tree.leaves(t.params), jax.tree.leaves(f.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
    # learners the tree layout left untouched are BITWISE untouched on
    # the flat layout too (the blocked plane moves no data)
    untouched = np.flatnonzero(
        (np.asarray(t.xfers) == 0) & (np.asarray(t.link_msgs) == 0)
        & ~np.asarray(active))
    for i in untouched:
        for x, y in zip(jax.tree.leaves(stacked),
                        jax.tree.leaves(f.params)):
            assert np.array_equal(np.asarray(x[i]), np.asarray(y[i]))


def test_flat_spec_path_without_config_sugar():
    """layout is a spec param: preset.with_params(layout='flat') runs the
    plane without any ProtocolConfig involved."""
    m = 5
    stacked = make_stacked(jax.random.PRNGKey(7), m)
    spec = PROTOCOLS["dynamic"].with_params(b=1, delta=0.05, layout="flat")
    res = ops.apply_staged(spec, stacked,
                           ops.init_state(tree_mean(stacked)))
    ref = ops.apply_staged(
        PROTOCOLS["dynamic"].with_params(b=1, delta=0.05), stacked,
        ops.init_state(tree_mean(stacked)))
    assert _counters_equal(res.rec, ref.rec)
    # round-trips through JSON like any other param
    from repro.core.sync.spec import ProtocolSpec
    assert ProtocolSpec.from_json(spec.to_json()) == spec


def test_unknown_layout_rejected_at_construction():
    with pytest.raises(ValueError):
        ProtocolConfig(kind="periodic", layout="diagonal")
    with pytest.raises(ValueError):
        PROTOCOLS["periodic"].with_params(layout="diagonal")


def test_balanced_cohort_reuses_threaded_dists():
    """The dists computed by the divergence condition feed the balancing
    priority: passing them explicitly must not change the cohort."""
    m = 6
    stacked = jax.tree.map(lambda x: x * 3.0,
                           make_stacked(jax.random.PRNGKey(4), m))
    ref = tree_mean(stacked)
    dists = per_learner_sq_distance(stacked, ref)
    violated = dists > 0.5
    rng = jax.random.PRNGKey(0)
    a = stages.cohort_balanced(0.5, "max_distance", stacked, ref,
                               violated, rng)
    b = stages.cohort_balanced(0.5, "max_distance", stacked, ref,
                               violated, rng, dists=dists)
    assert np.array_equal(np.asarray(a[0]), np.asarray(b[0]))


# ---------------------------------------------------------------------------
# engine-level equivalence: every preset + stale, scanned, under masks
# ---------------------------------------------------------------------------

PRESETS = {
    "nosync": dict(kind="nosync"),
    "periodic": dict(kind="periodic", b=3),
    "continuous": dict(kind="continuous", b=1),
    "fedavg": dict(kind="fedavg", b=2, fedavg_c=0.5),
    "dynamic": dict(kind="dynamic", b=2, delta=0.5),
    "gossip": dict(kind="gossip", b=2),
    "stale": dict(kind="stale"),
}


def _run_engine(proto, rounds=30, m=6, seed=0):
    cfg = get_arch("drift_mlp", smoke=True)
    src = GraphicalModelStream(seed=0, drift_prob=0.0)
    streams = LearnerStreams(src, m, batch=10, seed=seed)
    dl = DecentralizedLearner(
        lambda p, b: cnn_loss(cfg, p, b),
        lambda k: init_cnn_params(cfg, k), m, proto,
        TrainConfig(optimizer="sgd", learning_rate=0.05),
        network=NetworkConfig(act_prob=0.6, topology="ring",
                              link_classes=("wifi", "lte")))
    dl.run_chunk(streams.next_chunk(rounds))
    return dl


@pytest.mark.parametrize("name", sorted(PRESETS))
def test_flat_engine_matches_tree_engine(name):
    """ISSUE-5 acceptance: 30 scanned rounds under availability masks,
    flat vs tree — comm counters and the per-link ledger bitwise,
    parameters to reassociation tolerance."""
    tree_dl = _run_engine(ProtocolConfig(layout="tree", **PRESETS[name]))
    flat_dl = _run_engine(ProtocolConfig(layout="flat", **PRESETS[name]))
    assert tree_dl.comm_totals == flat_dl.comm_totals, name
    assert np.array_equal(tree_dl.link_xfer_totals,
                          flat_dl.link_xfer_totals), name
    assert np.array_equal(tree_dl.link_bytes_totals,
                          flat_dl.link_bytes_totals), name
    assert tree_dl.network_time == flat_dl.network_time, name
    for a, b in zip(jax.tree.leaves(tree_dl.params),
                    jax.tree.leaves(flat_dl.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=1e-6, err_msg=name)


def test_flat_hierarchy_matches_tree_hierarchy():
    """The vmapped per-cluster intra path picks the plane up with no
    hierarchy edits: same counters, close params."""
    tiers = HierarchyConfig(num_clusters=3,
                            inter=ProtocolConfig(kind="periodic", b=6))
    out = {}
    for layout in ("tree", "flat"):
        out[layout] = _run_engine(
            ProtocolConfig(kind="dynamic", b=2, delta=0.5, layout=layout,
                           tiers=tiers))
    assert out["tree"].comm_totals == out["flat"].comm_totals
    assert np.array_equal(out["tree"].link_bytes_totals,
                          out["flat"].link_bytes_totals)
    for a, b in zip(jax.tree.leaves(out["tree"].params),
                    jax.tree.leaves(out["flat"].params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=1e-6)
