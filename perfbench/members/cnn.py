"""The paper's CNN members (Kamp et al. 2018, Tables 1 and 5): a
configuration names them with ``"member": "cnn"`` and gives its layers
as ``cnn_spec`` (``["conv", out_channels, k, stride]``, ``["pool", k]``,
``["flatten"]``, ``["dense", n]``, ``["dropout", rate]``), its
``input_shape`` and ``num_outputs``.

The program's side is ``repro.models.cnn``; the reference's is written
again here in plain JAX: Glorot-uniform weights and zero biases, valid
convolutions and dense layers with ReLU between them, max pooling, and
softmax cross-entropy (squared error for a single output).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from perfbench import flops

# learners the reference updates at once: 64 MNIST learners' activations
# and gradients fit beside a fleet of 1,024
LEARNER_BLOCK = 64


def _spec(cfg):
    return tuple(tuple(layer) for layer in cfg["cnn_spec"])


# ---------------------------------------------------------------------------
# the program's member
# ---------------------------------------------------------------------------

def program(cfg: dict):
    from repro.config import ModelConfig
    from repro.models.cnn import cnn_loss, init_cnn_params

    model = ModelConfig(
        name=cfg["name"], family="cnn", num_layers=0, d_model=0,
        cnn_spec=_spec(cfg), input_shape=tuple(cfg["input_shape"]),
        num_outputs=int(cfg["num_outputs"]), source=cfg["source"])

    def loss_fn(params, batch):
        return cnn_loss(model, params, batch)

    return jax.jit(lambda k: init_cnn_params(model, k)), loss_fn


def flops_per_sample(cfg: dict) -> int:
    return flops.model_flops_per_sample(cfg["cnn_spec"], cfg["input_shape"])


# ---------------------------------------------------------------------------
# the reference's member
# ---------------------------------------------------------------------------

def init_model(spec, input_shape, key, dtype=jnp.float32):
    """Glorot-uniform weights and zero biases, one key split per layer
    with parameters. Returns ``{"layers": [per-layer dict]}``."""
    if len(input_shape) == 1:
        h = w = c = 0
        flat = input_shape[0]
    else:
        h, w, c = input_shape
        flat = None
    layers = []
    for layer in spec:
        kind = layer[0]
        if kind == "conv":
            _, c_out, k, s = layer
            key, sub = jax.random.split(key)
            lim = math.sqrt(6.0 / (k * k * (c + c_out)))
            layers.append({
                "w": jax.random.uniform(sub, (k, k, c, c_out), dtype,
                                        -lim, lim),
                "b": jnp.zeros((c_out,), dtype)})
            h, w, c = (h - k) // s + 1, (w - k) // s + 1, c_out
        elif kind == "pool":
            layers.append({})
            h, w = h // layer[1], w // layer[1]
        elif kind == "flatten":
            layers.append({})
            if c:
                flat = h * w * c
        elif kind == "dense":
            key, sub = jax.random.split(key)
            lim = math.sqrt(6.0 / (flat + layer[1]))
            layers.append({
                "w": jax.random.uniform(sub, (flat, layer[1]), dtype,
                                        -lim, lim),
                "b": jnp.zeros((layer[1],), dtype)})
            flat = layer[1]
        elif kind == "dropout":
            layers.append({})
        else:
            raise ValueError(f"unknown layer {layer!r}")
    return {"layers": layers}


def forward(spec, params, x):
    """(B, H, W, C) or (B, d) -> (B, outputs). Dropout is the identity:
    the fleet's loss is evaluated without a dropout key."""
    n_dense = sum(1 for layer in spec if layer[0] == "dense")
    seen = 0
    for layer, p in zip(spec, params["layers"]):
        kind = layer[0]
        if kind == "conv":
            s = layer[3]
            x = jax.lax.conv_general_dilated(
                x, p["w"], window_strides=(s, s), padding="VALID",
                dimension_numbers=("NHWC", "HWIO", "NHWC"))
            x = jax.nn.relu(x + p["b"])
        elif kind == "pool":
            k = layer[1]
            x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                      (1, k, k, 1), (1, k, k, 1), "VALID")
        elif kind == "flatten":
            x = x.reshape(x.shape[0], -1)
        elif kind == "dense":
            seen += 1
            x = x @ p["w"] + p["b"]
            if seen < n_dense:
                x = jax.nn.relu(x)
    return x


def loss(spec, num_outputs, params, batch, half_batch=False):
    """Squared error for a single output, else softmax cross-entropy
    (log-softmax in float32). ``half_batch`` is a planted fault: the mean
    over the first half of the batch only."""
    x, y = batch["x"], batch["y"]
    if half_batch:
        x, y = x[: x.shape[0] // 2], y[: y.shape[0] // 2]
    out = forward(spec, params, x)
    if num_outputs == 1:
        return jnp.mean(jnp.square(out[:, 0].astype(jnp.float32)
                                   - y.astype(jnp.float32)))
    lp = jax.nn.log_softmax(out.astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(lp, y[:, None], axis=-1))


def reference_init(cfg: dict, key):
    return init_model(_spec(cfg), tuple(cfg["input_shape"]), key)


def reference_loss(cfg: dict, params, batch, *, half_batch=False):
    return loss(_spec(cfg), int(cfg["num_outputs"]), params, batch,
                half_batch)
