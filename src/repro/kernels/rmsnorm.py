"""Fused RMS-norm kernel: one HBM pass per (row-block, D) tile.

Normalization statistics, the rsqrt and the scale multiply are fused in
VMEM (f32 accumulation); the unfused jnp version reads x twice (once for
the variance, once for the normalize) and materializes the f32 upcast.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _rmsnorm_kernel(x_ref, s_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    o_ref[...] = (y * s_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("eps", "block_rows", "interpret"))
def rmsnorm(x, scale, *, eps: float = 1e-5, block_rows: int = 128,
            interpret: bool):
    """x: (..., D), scale: (D,). Rows processed in (block_rows, D) tiles."""
    orig_shape = x.shape
    d = x.shape[-1]
    rows = x.size // d
    x2 = x.reshape(rows, d)
    pad = (-rows) % block_rows
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    nb = x2.shape[0] // block_rows
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x2.shape, x.dtype),
        interpret=interpret,
    )(x2, scale)
    if pad:
        out = out[:rows]
    return out.reshape(orig_shape)
