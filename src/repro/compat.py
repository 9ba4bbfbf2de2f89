"""The few jax entry points this repo wraps, written for jax 0.9.0 (the
one installation it runs on, CPU and TPU alike).

* ``make_mesh`` / ``abstract_mesh`` — meshes with ``AxisType.Auto`` axes.
  jax 0.9 makes ``Explicit`` axes by default; every sharding rule here
  (``with_sharding_constraint`` pins, ``NamedSharding`` placement, the
  fleet's ``shard_map``) is written for GSPMD's auto partitioning.
* ``shard_map`` — ``jax.shard_map`` with the mesh passed by keyword,
  unchecked.
* ``cost_analysis`` — ``compiled.cost_analysis()`` as a dict (``{}``
  where XLA reports nothing).

Import these from here, never from jax directly:

    from repro.compat import abstract_mesh, make_mesh, shard_map
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def _auto(n_axes: int) -> tuple:
    return (AxisType.Auto,) * n_axes


def make_mesh(shape: Sequence[int], axes: Sequence[str], *,
              devices=None) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with Auto-typed axes."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=_auto(len(axes)), devices=devices)


def abstract_mesh(shape: Sequence[int], axes: Sequence[str]):
    """Device-free mesh (Auto axes) for sharding-rule evaluation."""
    return jax.sharding.AbstractMesh(tuple(shape), tuple(axes),
                                     axis_types=_auto(len(axes)))


def cost_analysis(compiled) -> dict:
    """``compiled.cost_analysis()``; ``{}`` for a program XLA does not
    cost."""
    return compiled.cost_analysis() or {}


def shard_map(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` over ``mesh``, unchecked (``check_vma=False``).

    Every caller maps a row-local function — each device computes its
    own rows — whose outputs are split over the mesh, so there is no
    replicated output for the check to guard."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
