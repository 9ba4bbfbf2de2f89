"""The device-sharded fleet plane: ``layout="sharded"`` mesh machinery.

``layout="flat"`` carries the fleet through every sync stage as the
blocked fleet plane (``repro.core.sync.stages``): per-leaf blocks,
each with the learner axis leading — exactly the layout GSPMD wants.
This module
supplies the three pieces that turn that plane into a multi-device
backend of the SAME ``ProtocolSpec`` compile:

* ``FleetSharding`` — a 1-D device mesh with a single ``"fleet"`` axis
  (built through ``repro.compat.make_mesh``, never raw jax) plus the
  fleet size it partitions. ``m % n_devices == 0`` is validated at
  construction: every device owns exactly ``m / n_devices`` learner rows.
* placement helpers — ``put_fleet``/``put_sync_state`` give the scan
  carry its ``NamedSharding`` home (learner-stacked leaves split over
  ``"fleet"``, the reference model and scalar counters replicated), and
  ``constrain_fleet`` re-asserts that placement on the jitted round's
  outputs so the carry sharding is a fixpoint (no reshard between
  chunks, no second trace).
* the **active-fleet context** — ``use_fleet``/``constrain_rows``. The
  compiled round function (``core/sync/spec.py``) is cached per spec and
  knows nothing about devices; under ``layout="sharded"`` it calls
  ``constrain_rows`` on each block of the plane, which reads the fleet the
  ENGINE activated around its jit call (trace-time lookup) and inserts a
  ``with_sharding_constraint`` splitting the m axis over ``"fleet"``.
  With no active fleet — ``jax.eval_shape`` in the static contract gate,
  the jaxpr audit, a plain ``apply_staged`` call — it is the identity,
  so the sharded round stays abstractly bit-identical to ``flat``.

The row gate ``X.shape[0] == fleet.m`` keeps the constraint out of the
hierarchy's per-cluster vmap (there a block's leading dim is the
cluster size k = m/g, and pinning k rows to the fleet axis would be
wrong); per-cluster sync then runs with plane arithmetic while the fleet
carry around it stays device-sharded.

Everything device-visible goes through the fleet's mesh, so the sharded
layout executes per-shard: the per-learner update (inside a
``shard_map``, :func:`per_shard`), the distance reductions over each
block's trailing axes, the commits and the per-link ledger rows are
local to each device's learners, and only the trigger vote (an
``any()`` over (m,) scalars) and the cohort means (a sum over the
learner axis per block) cross devices — plus, in a partial sync, one
all-gather of each block per balancing step, where the loop indexes a
learner's row out of the sharded blocks.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from repro.compat import make_mesh, shard_map

FLEET_AXIS = "fleet"


class FleetSharding(NamedTuple):
    """One fleet's device partition: a 1-D ``("fleet",)`` mesh and the
    learner count it splits. Hashable/static — safe to close over in
    jitted code."""
    mesh: jax.sharding.Mesh
    m: int

    @property
    def n_devices(self) -> int:
        return self.mesh.shape[FLEET_AXIS]

    @property
    def rows_per_device(self) -> int:
        return self.m // self.n_devices

    # ---- shardings ---------------------------------------------------
    def row_sharding(self, ndim: int, axis: int = 0) -> NamedSharding:
        """NamedSharding splitting dimension ``axis`` over the fleet."""
        spec = [None] * ndim
        spec[axis] = FLEET_AXIS
        return NamedSharding(self.mesh, PartitionSpec(*spec))

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, PartitionSpec())


def fleet_sharding(m: int, devices: int = 0) -> FleetSharding:
    """Build the fleet's mesh over the first ``devices`` visible devices
    (``0`` = all of them). ``m`` must divide evenly: learner rows never
    straddle devices, so ``m % n_devices == 0`` is required — pad the
    fleet or pick a divisor device count."""
    avail = jax.devices()
    n = len(avail) if devices in (0, None) else int(devices)
    if n < 1 or n > len(avail):
        raise ValueError(
            f"shard_devices={devices} but {len(avail)} device(s) are "
            f"visible — pass 0 (all) or 1..{len(avail)}")
    if m % n != 0:
        raise ValueError(
            f"layout='sharded' needs m % n_devices == 0 so every device "
            f"owns the same number of learner rows; got m={m}, "
            f"n_devices={n} (remainder {m % n}). Pad the fleet or set "
            f"shard_devices to a divisor of m.")
    mesh = make_mesh((n,), (FLEET_AXIS,), devices=avail[:n])
    return FleetSharding(mesh=mesh, m=m)


# ---------------------------------------------------------------------------
# carry placement (host-side device_put; engine init + batch feeding)
# ---------------------------------------------------------------------------

def _fleet_leaf(fleet: FleetSharding, x, axis: int = 0) -> bool:
    """Is this leaf learner-stacked (dimension ``axis`` is the fleet)?"""
    shape = jnp.shape(x)
    return len(shape) > axis and shape[axis] == fleet.m


def put_fleet(fleet: FleetSharding, tree, axis: int = 0):
    """Place a learner-stacked pytree: leaves whose dim ``axis`` is the
    fleet size are split over ``"fleet"``; anything else (a scalar count
    an optimizer forgot to vmap, say) is replicated."""
    def put(x):
        sh = (fleet.row_sharding(jnp.ndim(x), axis)
              if _fleet_leaf(fleet, x, axis) else fleet.replicated())
        return jax.device_put(x, sh)
    return jax.tree.map(put, tree)


def put_replicated(fleet: FleetSharding, tree):
    """Replicate every leaf over the fleet's mesh (the reference model,
    the hierarchy's per-cluster state)."""
    return jax.tree.map(
        lambda x: jax.device_put(x, fleet.replicated()), tree)


def put_sync_state(fleet: FleetSharding, state):
    """Place a flat ``SyncState``: the reference model and the scalar
    counters/rng replicate; trigger-owned extra arrays with a leading
    (m,) axis (staleness ages) live with their learners."""
    return state._replace(
        ref=put_replicated(fleet, state.ref),
        v=jax.device_put(state.v, fleet.replicated()),
        rng=jax.device_put(state.rng, fleet.replicated()),
        step=jax.device_put(state.step, fleet.replicated()),
        extra=put_fleet(fleet, state.extra))


# ---------------------------------------------------------------------------
# in-trace constraints (inside the jitted round/chunk)
# ---------------------------------------------------------------------------

def constrain_fleet(fleet: FleetSharding, tree, axis: int = 0):
    """``with_sharding_constraint`` mirror of :func:`put_fleet`, for the
    jitted round's OUTPUTS: pins the committed carry to the same layout
    the inputs entered with, so chunk-to-chunk carry sharding is a
    fixpoint instead of whatever the partitioner last inferred."""
    def pin(x):
        if not _fleet_leaf(fleet, x, axis):
            return x
        return jax.lax.with_sharding_constraint(
            x, fleet.row_sharding(jnp.ndim(x), axis))
    return jax.tree.map(pin, tree)


def per_shard(fleet: FleetSharding, fn):
    """Run ``fn`` on each device's own learner rows, with no partitioner
    in between: every argument and output leaf is split over ``"fleet"``
    along its leading (learner) axis, and ``fn`` sees the local
    ``m / n_devices`` rows. For row-local work — the vmapped per-learner
    update — this is the sharded layout's whole contract, spelled out
    instead of left to GSPMD."""
    rows = PartitionSpec(FLEET_AXIS)
    return shard_map(fn, mesh=fleet.mesh, in_specs=rows, out_specs=rows)


# The compiled round (core/sync/spec.py) is cached per ProtocolSpec and
# mesh-agnostic; the engine activates its fleet around the jit call and
# the round picks it up at TRACE time. Thread-local so concurrent engines
# (or a test driving two meshes) cannot see each other's fleet.
_ACTIVE = threading.local()


def current_fleet() -> Optional[FleetSharding]:
    stack = getattr(_ACTIVE, "stack", None)
    return stack[-1] if stack else None


@contextlib.contextmanager
def use_fleet(fleet: FleetSharding):
    stack = getattr(_ACTIVE, "stack", None)
    if stack is None:
        stack = _ACTIVE.stack = []
    stack.append(fleet)
    try:
        yield fleet
    finally:
        stack.pop()


def constrain_rows(X: jnp.ndarray) -> jnp.ndarray:
    """Split a fleet-plane's rows over the active fleet's devices.

    Identity when no fleet is active (eval_shape in the contract gate,
    the jaxpr audit, plain ``apply_staged``) or when the leading dim is
    not the fleet size (the hierarchy's per-cluster (k, P) plane under
    vmap) — so ``layout="sharded"`` degrades to exactly ``layout="flat"``
    arithmetic everywhere a mesh placement would be meaningless."""
    fleet = current_fleet()
    if fleet is None or X.shape[0] != fleet.m:
        return X
    return jax.lax.with_sharding_constraint(
        X, fleet.row_sharding(X.ndim))
