"""Blockwise online-softmax attention (TPU flash attention).

TPU adaptation of the flash-attention idea: the grid walks (batch*head,
q-block, k-block) with the k dimension innermost — TPU grid iteration is
sequential per core, so the running max / denominator / accumulator live in
VMEM scratch across the k sweep instead of in GPU shared memory per CTA.
BlockSpecs stage (block_q, d) and (block_k, d) tiles HBM->VMEM; block sizes
default to 128 to align the MXU matmul dims.

Causal and sliding-window masking are applied via broadcasted iotas; GQA is
handled by the ops.py wrapper (folding the group into the batch-head grid
axis).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, window: int,
                  block_q: int, block_k: int, num_k_blocks: int,
                  q_offset: int):
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                 # (bq, d)
    k = k_ref[0].astype(jnp.float32)                 # (bk, d)
    v = v_ref[0].astype(jnp.float32)                 # (bk, d)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale  # (bq, bk)

    qi = pl.program_id(1)
    qpos = (qi * block_q + q_offset
            + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0))
    kpos = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = jnp.ones(s.shape, jnp.bool_)
    if causal:
        mask = mask & (kpos <= qpos)
    if window > 0:
        mask = mask & (kpos > qpos - window)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    alpha = jnp.exp(m_prev - m_new)                  # (bq,)
    p = jnp.exp(s - m_new[:, None])
    p = jnp.where(mask, p, 0.0)                      # kill fully-masked rows
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1)
    acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(kj == num_k_blocks - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=(
        "causal", "window", "scale", "block_q", "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    scale: float | None = None, block_q: int = 128,
                    block_k: int = 128, interpret: bool):
    """q: (B, Sq, d), k/v: (B, Sk, d) -> (B, Sq, d).

    Sq may be shorter than Sk (the causal diagonal is right-aligned, as in
    decode/chunked prefill)."""
    B, Sq, d = q.shape
    Sk = k.shape[1]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    pad_q = (-Sq) % block_q
    pad_k = (-Sk) % block_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        # padded k positions fall outside the causal mask of real q rows
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0)))
    nq = q.shape[1] // block_q
    nk = k.shape[1] // block_k

    out = pl.pallas_call(
        functools.partial(
            _flash_kernel, scale=scale, causal=causal, window=window,
            block_q=block_q, block_k=block_k, num_k_blocks=nk,
            q_offset=Sk - Sq),
        grid=(B, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    if pad_q:
        out = out[:, :Sq]
    return out
