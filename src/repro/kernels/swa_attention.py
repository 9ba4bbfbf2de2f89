"""Banded sliding-window attention kernel — the Pallas twin of the XLA-graph
blocking in ``models.attention._banded_sdpa`` (EXPERIMENTS.md §Perf pair 3).

Where ``flash_attention`` sweeps EVERY k block and masks, this kernel's grid
is (batch, q_block, 2): for query block i only k blocks i-1 and i are ever
staged into VMEM (they cover the whole window when block == window), so HBM
traffic and MXU work drop by the same S/(2w) factor the graph-level path
achieves — but with no (B, nb, 2w, d) gathered-key intermediate at all.
Online-softmax state is carried in VMEM scratch across the 2-step k sweep.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _swa_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                scale: float, window: int):
    i = pl.program_id(1)
    t = pl.program_id(2)              # 0: previous k block, 1: own k block

    @pl.when(t == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)          # (w, d)
    k = k_ref[0].astype(jnp.float32)          # (w, d)
    v = v_ref[0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    # absolute positions: q row r -> i*w + r; k col c -> (i - 1 + t)*w + c
    qpos = i * window + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    kblock = i - 1 + t
    kpos = kblock * window + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = (kpos <= qpos) & (kpos > qpos - window) & (kblock >= 0)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(mask, jnp.exp(s - m_new[:, None]), 0.0)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1)
    acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(t == 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "scale", "interpret"))
def swa_attention(q, k, v, *, window: int, scale: float | None = None,
                  interpret: bool):
    """q, k, v: (B, S, d) with S % window == 0 and S >= window.

    Causal sliding-window attention (window == block size): each query
    attends to the ``window`` most recent positions including itself."""
    B, S, d = q.shape
    if S % window != 0 or S < window:
        raise ValueError(
            f"sequence length must be a multiple of the window and at "
            f"least one window long: S={S}, window={window}")
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    nb = S // window

    def k_index(b, i, t):
        # clamp block -1 to 0; its contribution is masked out in-kernel
        return (b, jnp.maximum(i - 1 + t, 0), 0)

    return pl.pallas_call(
        functools.partial(_swa_kernel, scale=scale, window=window),
        grid=(B, nb, 2),
        in_specs=[
            pl.BlockSpec((1, window, d), lambda b, i, t: (b, i, 0)),
            pl.BlockSpec((1, window, d), k_index),
            pl.BlockSpec((1, window, d), k_index),
        ],
        out_specs=pl.BlockSpec((1, window, d), lambda b, i, t: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((window,), jnp.float32),
            pltpu.VMEM((window,), jnp.float32),
            pltpu.VMEM((window, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
