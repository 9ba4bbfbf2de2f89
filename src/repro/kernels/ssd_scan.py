"""Chunked SSD (state-space duality) scan kernel — Mamba2's core compute.

TPU adaptation of the Dao & Gu chunked algorithm: the grid walks
(batch*head, chunk) with the chunk dimension innermost and sequential, so
the inter-chunk recurrent state (P, N) lives in VMEM scratch across the
sweep (the GPU version parallelizes chunks across SMs and does a separate
state-passing pass; TPU's sequential grid makes the recurrence free).
Within a chunk the quadratic attention-like term runs on the MXU:

    y_diag = (C B^T * L) (dt * x)        L = exp(segsum(dt*a)), lower-tri
    y_off  = exp(cum) * (C h_prev^T)
    h     <- exp(cum[-1]) h + ((dt * decay * x)^T B)

Block shapes: x (chunk, P), b/c (chunk, N) staged HBM->VMEM per grid step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, h_ref, h_scr, *,
                num_chunks: int):
    cj = pl.program_id(1)

    @pl.when(cj == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    x = x_ref[0].astype(jnp.float32)          # (cs, P)
    dt = dt_ref[0].astype(jnp.float32)        # (cs,)
    a = a_ref[0].astype(jnp.float32)          # ()
    b = b_ref[0].astype(jnp.float32)          # (cs, N)
    c = c_ref[0].astype(jnp.float32)          # (cs, N)

    da = dt * a                               # (cs,) <= 0
    cum = jnp.cumsum(da)                      # within-chunk cumulative decay
    cs = x.shape[0]

    # intra-chunk quadratic term
    seg = cum[:, None] - cum[None, :]         # (cs, cs)
    ii = jax.lax.broadcasted_iota(jnp.int32, (cs, cs), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (cs, cs), 1)
    L = jnp.where(jj <= ii, jnp.exp(seg), 0.0)
    cb = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (cs, cs)
    xdt = x * dt[:, None]
    y = jax.lax.dot_general(cb * L, xdt, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)   # (cs, P)

    # inter-chunk contribution from the carried state
    h = h_scr[...]                            # (P, N)
    y += jnp.exp(cum)[:, None] * jax.lax.dot_general(
        c, h, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)

    # state update
    decay = jnp.exp(cum[-1] - cum)            # (cs,)
    xw = xdt * decay[:, None]                 # (cs, P)
    h_scr[...] = jnp.exp(cum[-1]) * h + jax.lax.dot_general(
        xw, b, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    y_ref[0] = y.astype(y_ref.dtype)

    @pl.when(cj == num_chunks - 1)
    def _finish():
        h_ref[0] = h_scr[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, a, b, c, *, chunk: int = 64, interpret: bool):
    """x: (BH, S, P); dt: (BH, S); a: (BH,); b, c: (BH, S, N).

    Returns (y (BH, S, P), h (BH, P, N)) — matching ``ref.ssd_scan_ref``.
    S must be a chunk multiple (callers pad)."""
    BH, S, P = x.shape
    N = b.shape[-1]
    if S % chunk != 0:
        raise ValueError(
            f"sequence length must be a chunk multiple (callers pad): "
            f"S={S}, chunk={chunk}")
    nc = S // chunk
    y, h = pl.pallas_call(
        functools.partial(_ssd_kernel, num_chunks=nc),
        grid=(BH, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, P), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, chunk), lambda i, j: (i, j)),
            pl.BlockSpec((1,), lambda i, j: (i,)),
            pl.BlockSpec((1, chunk, N), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, chunk, N), lambda i, j: (i, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, P), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, P, N), lambda i, j: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct((BH, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(x, dt, a, b, c)
    return y, h
