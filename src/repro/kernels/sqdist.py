"""Fused squared-distance reduction kernels — the protocol's monitoring
hot-spot: every learner evaluates ``||theta - r||^2`` every b steps
(Algorithm 1's local condition).

``sqdist`` is the single-model reduction: one HBM pass, each grid step
stages a (1, block) tile of both vectors into VMEM and accumulates
``sum((x - r)^2)`` in f32 into a (1, 1) output tile that every grid step
maps to (TPU grid iteration is sequential, so the accumulation is
race-free). No materialized difference tensor.

``sqdist_rows`` is the FLEET-PLANE variant the flat sync path runs:
``(m, P) x (P,) -> (m,)`` over a 2-D row x column-block grid. Each grid
step stages a ``(block_m, block)`` tile of the plane plus the matching
``(1, block)`` slice of the reference row, and accumulates per-row
partial sums into a ``(block_m, 1)`` output tile revisited across the
column sweep — the whole fleet's local conditions in one pass over the
``(m, P)`` matrix, instead of m kernel launches (or 2xleaf-count HBM
walks on the pytree layout).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _sqdist_kernel(x_ref, r_ref, o_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    d = x_ref[...].astype(jnp.float32) - r_ref[...].astype(jnp.float32)
    o_ref[0, 0] += jnp.sum(d * d)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def sqdist(x, r, *, block: int = 65536, interpret: bool):
    """||x - r||^2 over flattened inputs. Pads to a block multiple with
    equal values (zero contribution)."""
    xf = x.reshape(-1)
    rf = r.reshape(-1)
    n = xf.shape[0]
    pad = (-n) % block
    if pad:
        xf = jnp.pad(xf, (0, pad))
        rf = jnp.pad(rf, (0, pad))
    nb = xf.shape[0] // block
    x2 = xf.reshape(nb, block)
    r2 = rf.reshape(nb, block)
    out = pl.pallas_call(
        _sqdist_kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((1, block), lambda i: (i, 0)),
            pl.BlockSpec((1, block), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.float32),
        interpret=interpret,
    )(x2, r2)
    return out[0, 0]


def _sqdist_rows_kernel(x_ref, r_ref, o_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    d = x_ref[...].astype(jnp.float32) - r_ref[...].astype(jnp.float32)
    o_ref[...] += jnp.sum(d * d, axis=1, keepdims=True)


# Mosaic's tiling rule: the last two dims of a block are multiples of
# (8, 128) or equal the array's dims. Of the 16 MiB of VMEM Mosaic grants
# a kernel by default, the double-buffered tiles may take this much.
_SUBLANES, _LANES = 8, 128
_VMEM_BUDGET = 12 * 2**20


def _row_block(m: int, block_m: int) -> int:
    """The row tile the chip accepts: all ``m`` rows when they fit one
    block (a block equal to the full dim is always legal), else
    ``block_m`` rounded up to the sublane multiple of 8. A ragged last
    row block is a partial edge block: its out-of-range rows read
    unspecified values into output rows that are never written back."""
    bm = -(-block_m // _SUBLANES) * _SUBLANES
    return m if m <= bm else bm


def _check_tiles(bm: int, block: int, x_dtype, r_dtype) -> None:
    if block % _LANES:
        raise ValueError(
            f"sqdist_rows: block={block} must be a multiple of {_LANES} "
            f"(the TPU lane width)")

    def tile_bytes(rows, dtype):
        item = jnp.dtype(dtype).itemsize
        sub = _SUBLANES * max(1, 4 // item)      # packed sublanes
        return -(-rows // sub) * sub * block * item

    need = 2 * (tile_bytes(bm, x_dtype) + tile_bytes(1, r_dtype))
    if need > _VMEM_BUDGET:
        raise ValueError(
            f"sqdist_rows: a ({bm}, {block}) tile double-buffered needs "
            f"{need} bytes of VMEM, over the {_VMEM_BUDGET}-byte budget; "
            f"pass a smaller block_m or block")


@functools.partial(jax.jit,
                   static_argnames=("block_m", "block", "interpret"))
def sqdist_rows(x, r, *, block_m: int = 8, block: int = 65536,
                interpret: bool):
    """Row-wise ``||x[i] - r||^2``: x ``(m, n)``, r ``(n,)`` -> ``(m,)``.

    No padding copies of the plane: the grid only visits the
    block-aligned column prefix of the FULL array (every column tile
    stays in bounds, so nothing is re-materialized — a ``jnp.pad`` to a
    block multiple would silently copy the whole (m, n) matrix first)
    and the ragged tail (< ``block`` columns, if any) is reduced with a
    plain jnp row pass and added. The row tile comes from
    :func:`_row_block`, so every ``m`` compiles for the chip; a tile
    over the VMEM budget raises. The grid sweeps columns innermost, so
    each ``(block_m, 1)`` output tile accumulates its rows' partials
    sequentially (race-free on TPU's sequential grid)."""
    m, n = x.shape
    rf = r.reshape(-1)
    bm = _row_block(m, block_m)
    _check_tiles(bm, block, x.dtype, r.dtype)
    n0 = n - n % block

    def tail_sums(xt, rt):
        d = xt.astype(jnp.float32) - rt.astype(jnp.float32)[None]
        return jnp.sum(d * d, axis=1)

    if n0 == 0:                    # everything is tail: no kernel launch
        return tail_sums(x, rf)
    out = pl.pallas_call(
        _sqdist_rows_kernel,
        grid=(pl.cdiv(m, bm), n0 // block),
        in_specs=[
            pl.BlockSpec((bm, block), lambda i, j: (i, j)),
            pl.BlockSpec((1, block), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, 1), jnp.float32),
        interpret=interpret,
    )(x, rf.reshape(1, n))[:, 0]
    if n0 < n:
        out = out + tail_sums(x[:, n0:], rf[n0:])
    return out
