"""The check catches a broken timed path: a run on the CPU at a tiny
size, through the harness with only its look for a chip skipped, with a
fault planted underneath ``run_protocol_training``, comes out not
correct. The tiny cell carries mnist_sync_heavy's limits."""
import time

import pytest

from perfbench import harness
from perfbench.tests.conftest import make_root


@pytest.fixture
def cell(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    return harness.Cell("tiny", root=make_root(str(tmp_path)))


def _run(cell):
    return harness.run_cell(cell, 2147483659, 0.5, False,
                            time.perf_counter(), require_chip=False)


def test_sound(cell):
    r = _run(cell)
    assert r["correct"], r["checks"]


def test_state_left_unchanged(cell, monkeypatch):
    """Every step returns the parameters it was given."""
    import repro.core.protocol as protocol
    from repro.optim.optimizers import Optimizer

    made = protocol.make_optimizer

    def frozen(train):
        opt = made(train)
        return Optimizer(opt.init, lambda p, g, s: (p, opt.update(p, g, s)[1]),
                         opt.name)

    monkeypatch.setattr(protocol, "make_optimizer", frozen)
    r = _run(cell)
    assert not r["correct"], r["checks"]


def test_half_the_batch_left_out(cell, monkeypatch):
    """Each learner's batch loses its second half; the loss is the mean
    over the rest."""
    import jax

    from repro.data.pipeline import LearnerStreams

    whole = LearnerStreams.next_chunk

    def half(self, n, on_round=None):
        b = whole(self, n, on_round)
        return jax.tree.map(lambda x: x[:, :, : x.shape[2] // 2], b)

    monkeypatch.setattr(LearnerStreams, "next_chunk", half)
    r = _run(cell)
    assert not r["correct"], r["checks"]


def test_half_the_cohort_keeps_its_own_model(cell, monkeypatch):
    """The commit leaves the second half of the fleet's learners as they
    were, leaf by leaf: the cohort's mean reaches only half of it."""
    import jax
    import jax.numpy as jnp

    from repro.core.sync import stages

    whole = stages._select_commit

    def half(ctx, mask, mean):
        new = whole(ctx, mask, mean)
        keep = jnp.arange(ctx.m) >= ctx.m // 2

        def leaf(old, committed):
            k = keep.reshape((-1,) + (1,) * (old.ndim - 1))
            return jnp.where(k, old, committed)

        return jax.tree.map(leaf, ctx.stacked, new)

    monkeypatch.setattr(stages, "_select_commit", half)
    r = _run(cell)
    assert not r["correct"], r["checks"]


SHARDED = """
import json, sys, time
t0 = time.perf_counter()
sys.path[:0] = [{root!r}, {src!r}]
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from perfbench import harness
from repro.compat import shard_map
from repro.core import shard
from repro.core.sync import stages

plain = stages.aggregate_mean

def first_shard_only(stacked, mask, weights=None):
    # the exchange between chips left out: each chip sums its own
    # learners, leaf by leaf, and the commit takes chip 0's partial mean
    # as the cohort's
    fleet = shard.current_fleet()
    if ({plant} and fleet is not None and weights is None
            and mask.shape[0] == fleet.m):
        def local(x, w):
            w = w.astype(x.dtype)
            return (jnp.tensordot(w, x, axes=1)[None], jnp.sum(w)[None])

        def leaf(x):
            rest = (None,) * (x.ndim - 1)
            s, n = shard_map(local, mesh=fleet.mesh,
                             in_specs=(P("fleet", *rest), P("fleet")),
                             out_specs=(P("fleet", *rest), P("fleet")))(
                                 x, mask)
            return s[0] / jnp.where(n[0] > 0, n[0], 1)

        return jax.tree.map(leaf, stacked)
    return plain(stacked, mask, weights)

stages.aggregate_mean = first_shard_only
cell = harness.Cell("tiny", root={root!r})
r = harness.run_cell(cell, 2147483659, 0.5, False, t0, require_chip=False)
print(json.dumps(r))
"""


@pytest.mark.parametrize("plant", [False, True])
def test_exchange_between_chips_left_out(tmp_path, plant):
    """A fleet of 8 over four (CPU) devices: sound, it is correct; with
    the cohort mean taken over the first device's rows alone, it is not."""
    import json
    import os
    import subprocess
    import sys

    from perfbench.tests.conftest import ROOT, tiny_traffic

    root = make_root(str(tmp_path), traffic=tiny_traffic(
        layout="sharded", shard_devices=4), chips=4)
    code = SHARDED.format(root=root, src=os.path.join(ROOT, "src"),
                          plant=plant)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    env.pop("PYTHONPATH", None)
    p = subprocess.run([sys.executable, "-c", code], env=env, cwd=root,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    assert r["correct"] is (not plant), r["checks"]
