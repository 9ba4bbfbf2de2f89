"""Model divergence and local conditions (paper Eq. 2 and Section 3).

All functions treat models as pytrees. A *model configuration* is a pytree
whose leaves carry a leading learner axis ``m`` (the vmap layout used by the
simulator): leaf shape ``(m, ...)``.

The divergence of a configuration is
    delta(f) = 1/m sum_i || f_i - mean(f) ||^2
and the local condition of learner i w.r.t. reference model r is
    || f_i - r ||^2 <= Delta.

``sq_distance`` optionally routes through the fused Pallas kernel
(`repro.kernels.ops.sqdist`). ``per_learner_sq_distance_flat`` is the
batched dual over a concatenated ``(m, P)`` matrix: one ``(m, P) x (P,)``
pass, routed through the row-tiled Pallas kernel on TPU backends. The
engine no longer builds such a matrix — every layout reduces the stacked
leaves (``per_learner_sq_distance``), which XLA fuses into the local
update — so the kernel serves only its own tests and benchmarks.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.compat import shard_map
from repro.core import shard


def flat_size(tree) -> int:
    return sum(x.size for x in jax.tree.leaves(tree))


def _acc_dtype(dt):
    """Accumulation dtype for reductions over a leaf: at least float32.

    Sub-f32 leaves (bfloat16/float16) accumulate in float32 and narrow
    back instead of summing m terms in an 8-bit-mantissa format. For
    float32 leaves every cast below is a no-op, so the pre-fix
    expressions — and the PR-2 goldens — are reproduced bitwise."""
    return jnp.promote_types(dt, jnp.float32)


def tree_mean(stacked):
    """Mean over the leading learner axis of every leaf (accumulated in
    ``_acc_dtype``, returned in the leaf dtype)."""
    def mean1(x):
        return jnp.mean(x, axis=0, dtype=_acc_dtype(x.dtype)).astype(x.dtype)
    return jax.tree.map(mean1, stacked)


def tree_weighted_sum(stacked, weights):
    """``sum_i w_i f_i`` over the learner axis, per leaf in
    ``_acc_dtype`` and left there (the balancing loop keeps adding rows
    to it). weights: (m,)."""
    def wsum(x):
        acc = _acc_dtype(x.dtype)
        w = weights.reshape((-1,) + (1,) * (x.ndim - 1)).astype(acc)
        return jnp.sum(x.astype(acc) * w, axis=0)
    return jax.tree.map(wsum, stacked)


def tree_weighted_mean(stacked, weights):
    """Weighted mean over the learner axis (Algorithm 2). weights: (m,).

    An all-zero weight vector (an empty active set under availability
    masking) yields the zero model instead of 0/0 = NaN — the operators'
    selection masks then keep the previous configuration unchanged, so no
    NaN ever reaches the scan carry.

    Weighting happens in ``_acc_dtype`` (at least float32) and narrows
    back to the leaf dtype: the B^i weights are never downcast to a
    sub-f32 leaf dtype, and the sum over m learners never accumulates in
    bfloat16 — the dtype-promotion contract the static contract checker
    (``repro.analysis.contracts``) verifies.
    """
    wsum = jnp.sum(weights)
    denom = jnp.where(wsum > 0, wsum, jnp.ones_like(wsum))
    return jax.tree.map(
        lambda s, x: (s / denom.astype(s.dtype)).astype(x.dtype),
        tree_weighted_sum(stacked, weights), stacked)


def sq_distance(a, b, use_kernel: bool = False) -> jnp.ndarray:
    """|| a - b ||^2 summed over every leaf of two same-structure pytrees."""
    if use_kernel:
        from repro.kernels import ops as kops
        return sum(
            kops.sqdist(x.reshape(-1), y.reshape(-1))
            for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
        )
    return sum(
        jnp.sum(jnp.square(x.astype(jnp.float32) - y.astype(jnp.float32)))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
    )


def per_learner_sq_distance_flat(X, r,
                                 use_kernel: Optional[bool] = None
                                 ) -> jnp.ndarray:
    """(m,) squared distances over a concatenated (m, P) configuration
    matrix ``X`` against the (P,) reference row ``r``, in one batched
    pass. On a TPU backend it runs the row-tiled Pallas kernel
    (``repro.kernels.ops.sqdist_rows``); elsewhere the kernel would
    execute in interpret mode (Python, orders of magnitude slower than
    XLA), so the dense jnp row reduction is used instead.
    ``use_kernel`` forces the choice either way.

    Under an active fleet the kernel runs inside a ``shard_map`` over
    the ``"fleet"`` axis: each device reduces its own rows against the
    replicated reference, so the matrix is never gathered (a Mosaic
    kernel cannot be partitioned automatically)."""
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    if not use_kernel:
        d = X.astype(jnp.float32) - r.astype(jnp.float32)[None]
        return jnp.sum(d * d, axis=1)
    from repro.kernels import ops as kops
    fleet = shard.current_fleet()
    if fleet is None or X.shape[0] != fleet.m:
        return kops.sqdist_rows(X, r)
    P = jax.sharding.PartitionSpec
    return shard_map(kops.sqdist_rows, mesh=fleet.mesh,
                     in_specs=(P(shard.FLEET_AXIS, None), P()),
                     out_specs=P(shard.FLEET_AXIS))(X, r)


def per_learner_sq_distance(stacked, ref) -> jnp.ndarray:
    """(m,) squared distances || f_i - r ||^2; leaves of ``stacked`` carry a
    leading m axis, ``ref`` is a single model."""
    def leaf(x, r):
        d = x.astype(jnp.float32) - r.astype(jnp.float32)[None]
        return jnp.sum(d.reshape(d.shape[0], -1) ** 2, axis=1)
    parts = jax.tree.leaves(jax.tree.map(leaf, stacked, ref))
    return sum(parts)


def divergence(stacked) -> jnp.ndarray:
    """delta(f) = 1/m sum_i || f_i - mean(f) ||^2  (paper Eq. 2)."""
    mean = tree_mean(stacked)
    d = per_learner_sq_distance(stacked, mean)
    return jnp.mean(d)


def local_condition_violated(stacked, ref, delta: float) -> jnp.ndarray:
    """(m,) bool — which learners violate || f_i - r ||^2 > Delta."""
    return per_learner_sq_distance(stacked, ref) > delta
