"""The control fails the check: the reference put in the program's place
and computed in bfloat16, the precision below the configuration's
float32, reads above the limits; so do the planted faults. At a tiny
size on the CPU; ``perfbench/control.py`` reads the same at a cell's own
size on the chip."""
import pytest

from perfbench import control, harness
from perfbench.tests.conftest import make_root

VARIANTS = ["bf16", "half_batch", "frozen", "no_exchange"]


@pytest.fixture(scope="module")
def readings(tmp_path_factory):
    cell = harness.Cell("tiny", root=make_root(str(tmp_path_factory.mktemp(
        "checkout"))))
    return cell, control.variant_readings(cell, 2147483659, VARIANTS)


@pytest.mark.parametrize("variant", VARIANTS)
def test_variant_fails_a_limit(readings, variant):
    cell, got = readings
    checks = got[variant]
    assert not harness.passes(checks), checks


def test_reference_joins_no_leaves():
    """No array of the reference's sync round, the condition and the
    balancing loop among it, holds m x P elements: the fleet stays the
    stacked leaves."""
    import functools

    import jax
    import jax.numpy as jnp

    from perfbench import reference

    m = 8
    model = {"w": jnp.ones((6, 5)), "b": jnp.ones((5,)), "u": jnp.ones((3,))}
    fleet = jax.tree.map(
        lambda x: x[None] * jnp.arange(1.0, m + 1).reshape(
            (m,) + (1,) * x.ndim), model)
    P = sum(x.size for x in jax.tree.leaves(model))
    sync = functools.partial(reference.sync_round, delta=1.0, block=4)
    jaxpr = jax.make_jaxpr(sync)(fleet, model, jnp.int32(0))

    def sizes(jx):
        for eqn in jx.eqns:
            for v in eqn.outvars:
                yield int(getattr(v.aval, "size", 1))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from sizes(sub)

    assert max(sizes(jaxpr.jaxpr)) < m * P
