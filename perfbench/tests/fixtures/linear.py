"""A test-only member that is not a CNN: softmax regression on the
flattened input, two leaves (``w``, ``b``). Its program and its
reference are the same few lines, so a cell of it is correct."""
import math

import jax
import jax.numpy as jnp

LEARNER_BLOCK = 4


def _size(cfg):
    return math.prod(cfg["input_shape"])


def reference_init(cfg, key):
    n, k = _size(cfg), int(cfg["num_outputs"])
    lim = math.sqrt(6.0 / (n + k))
    return {"w": jax.random.uniform(key, (n, k), jnp.float32, -lim, lim),
            "b": jnp.zeros((k,), jnp.float32)}


def reference_loss(cfg, params, batch, *, half_batch=False):
    x, y = batch["x"], batch["y"]
    if half_batch:
        x, y = x[: x.shape[0] // 2], y[: y.shape[0] // 2]
    out = x.reshape(x.shape[0], -1) @ params["w"] + params["b"]
    lp = jax.nn.log_softmax(out.astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(lp, y[:, None], axis=-1))


def program(cfg):
    return (jax.jit(lambda k: reference_init(cfg, k)),
            lambda params, batch: reference_loss(cfg, params, batch))


def flops_per_sample(cfg):
    # forward and weight gradient; the data needs no input gradient
    return 2 * 2 * _size(cfg) * int(cfg["num_outputs"])
