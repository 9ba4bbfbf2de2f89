"""Operations and weights of the CNN members, counted from their
``cnn_spec``, for utilisation.

``model_flops_per_sample`` counts what one learner's SGD step requires
per training sample: the forward pass, the weight gradients, and the
input gradients of every layer but the first (the data needs none).
Multiply-adds count as two operations; biases, activations and pooling
are left out, as is the sync arithmetic, which is not the model's work.
"""
from __future__ import annotations


def layer_macs(spec, input_shape):
    """[(kind, multiply-adds of the forward pass per sample)] for the
    layers that multiply: convolutions and dense layers."""
    if len(input_shape) == 1:
        h = w = c = 0
        flat = input_shape[0]
    else:
        h, w, c = input_shape
        flat = None
    out = []
    for layer in spec:
        kind = layer[0]
        if kind == "conv":
            _, c_out, k, s = layer
            h, w = (h - k) // s + 1, (w - k) // s + 1
            out.append(("conv", h * w * k * k * c * c_out))
            c = c_out
        elif kind == "pool":
            h, w = h // layer[1], w // layer[1]
        elif kind == "flatten":
            if c:
                flat = h * w * c
        elif kind == "dense":
            out.append(("dense", flat * layer[1]))
            flat = layer[1]
    return out


def weight_count(spec, input_shape) -> int:
    if len(input_shape) == 1:
        h = w = c = 0
        flat = input_shape[0]
    else:
        h, w, c = input_shape
        flat = None
    n = 0
    for layer in spec:
        kind = layer[0]
        if kind == "conv":
            _, c_out, k, s = layer
            n += k * k * c * c_out + c_out
            h, w, c = (h - k) // s + 1, (w - k) // s + 1, c_out
        elif kind == "pool":
            h, w = h // layer[1], w // layer[1]
        elif kind == "flatten":
            if c:
                flat = h * w * c
        elif kind == "dense":
            n += flat * layer[1] + layer[1]
            flat = layer[1]
    return n


def model_flops_per_sample(spec, input_shape) -> int:
    macs = [m for _, m in layer_macs(spec, input_shape)]
    forward = sum(macs)
    weight_grads = sum(macs)
    input_grads = sum(macs[1:])
    return 2 * (forward + weight_grads + input_grads)

