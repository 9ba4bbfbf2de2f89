"""The flat fleet-plane: one contiguous ``(m, P)`` matrix per fleet.

Every sync stage is linear algebra over the fleet's parameter rows —
per-learner distances, masked weighted means, mixing matmuls, per-learner
selects — but the pytree layout forces each of them to re-walk the model
leaf by leaf. A ``FleetAdapter`` derives the ravel/unravel maps ONCE from
the (static) leaf structure and carries the fleet configuration as a
single dense matrix:

    adapter = fleet_adapter(stacked)        # cached on (treedef, shapes)
    X = adapter.ravel(stacked)              # (m, P) plane
    r = adapter.ravel_model(ref)            # (P,) row
    ... dense stage arithmetic ...
    new = adapter.unravel(X_new)            # back to the (m, ...) pytree

The plane dtype is the promotion of the leaf dtypes (at least float32),
so float32/bfloat16/float16 leaves round-trip BITWISE through
``unravel(ravel(x))`` — narrowing back to the leaf dtype after a widening
cast is exact. Non-floating leaves are rejected at adapter construction:
the plane is a parameter space, not a carrier for integer state. The
tree-layout aggregates accumulate in the same promoted dtype
(``repro.core.divergence._acc_dtype``), and the static contract checker
(``repro.analysis.contracts``) pins the two layouts to identical
abstract outputs on a mixed f32+bf16 template.

Offsets and shapes are plain Python/numpy metadata, so ``ravel``/
``unravel`` trace to pure reshape+concatenate (no arithmetic), under the
named scopes ``repro.plane.ravel``/``repro.plane.unravel``, and work
under ``jit``, ``vmap`` (the hierarchy's per-cluster path) and
``lax.scan`` — an unchanged row survives a ravel/unravel round trip
bit-for-bit, which keeps non-participants bitwise across flat commits.
"""
from __future__ import annotations

import math
from typing import Any, NamedTuple, Tuple

import jax
import jax.numpy as jnp


class FleetAdapter(NamedTuple):
    """Static ravel/unravel maps for one model structure.

    ``shapes`` are the per-leaf TRAILING shapes (the leading learner axis
    is whatever the raveled array carries); ``offsets`` are the column
    starts of each leaf's slab in the plane; ``P`` is the model size."""
    treedef: Any
    shapes: Tuple[Tuple[int, ...], ...]
    dtypes: Tuple[Any, ...]
    offsets: Tuple[int, ...]
    sizes: Tuple[int, ...]
    P: int
    plane_dtype: Any

    # ---- fleet (m, ...) <-> (m, P) ----------------------------------
    def ravel(self, stacked) -> jnp.ndarray:
        """Stacked (m, ...) pytree -> one (m, P) plane."""
        with jax.named_scope("repro.plane.ravel"):
            leaves = self.treedef.flatten_up_to(stacked)
            return jnp.concatenate(
                [x.reshape(x.shape[0], -1).astype(self.plane_dtype)
                 for x in leaves], axis=1)

    def unravel(self, X: jnp.ndarray):
        """(m, P) plane -> stacked (m, ...) pytree with the leaf dtypes."""
        m = X.shape[0]
        with jax.named_scope("repro.plane.unravel"):
            leaves = [
                X[:, o:o + s].reshape((m,) + shp).astype(dt)
                for o, s, shp, dt in zip(self.offsets, self.sizes,
                                         self.shapes, self.dtypes)]
        return jax.tree.unflatten(self.treedef, leaves)

    # ---- single model (...) <-> (P,) --------------------------------
    def ravel_model(self, model) -> jnp.ndarray:
        with jax.named_scope("repro.plane.ravel"):
            leaves = self.treedef.flatten_up_to(model)
            return jnp.concatenate(
                [x.reshape(-1).astype(self.plane_dtype) for x in leaves])

    def unravel_model(self, x: jnp.ndarray):
        with jax.named_scope("repro.plane.unravel"):
            leaves = [
                x[o:o + s].reshape(shp).astype(dt)
                for o, s, shp, dt in zip(self.offsets, self.sizes,
                                         self.shapes, self.dtypes)]
        return jax.tree.unflatten(self.treedef, leaves)


_ADAPTERS: dict = {}


def fleet_adapter(stacked) -> FleetAdapter:
    """The (cached) adapter for a stacked (m, ...) model configuration.

    The cache key is the static structure — treedef + per-leaf trailing
    shape/dtype — so every round of every protocol shares one adapter and
    the offset table is computed exactly once per model architecture."""
    leaves, treedef = jax.tree.flatten(stacked)
    if not leaves:
        raise ValueError("cannot build a FleetAdapter for an empty pytree")
    shapes = tuple(tuple(x.shape[1:]) for x in leaves)
    dtypes = tuple(jnp.dtype(x.dtype) for x in leaves)
    key = (treedef, shapes, dtypes)
    hit = _ADAPTERS.get(key)
    if hit is not None:
        return hit
    for shp, dt in zip(shapes, dtypes):
        if not jnp.issubdtype(dt, jnp.floating):
            raise TypeError(
                f"the flat fleet-plane carries floating-point parameters "
                f"only; got a leaf with dtype {dt} (shape {shp})")
    plane = jnp.dtype(jnp.float32)
    for dt in dtypes:
        plane = jnp.promote_types(plane, dt)
    sizes = tuple(int(math.prod(shp)) for shp in shapes)
    offsets, off = [], 0
    for s in sizes:
        offsets.append(off)
        off += s
    adapter = FleetAdapter(
        treedef=treedef, shapes=shapes, dtypes=dtypes,
        offsets=tuple(offsets), sizes=sizes, P=off, plane_dtype=plane)
    _ADAPTERS[key] = adapter
    return adapter
