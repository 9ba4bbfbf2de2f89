"""Road frames for the deep-driving fleet: a verbatim copy of
``DeepDriveStream`` from ``src/repro/data/synthetic.py`` at commit 4a8f582,
kept here so that the benchmark's inputs cannot move with the program.
Procedural road curves stand in for the paper's camera frames.

``make(seed, args)`` builds the source a cell feeds to
``run_protocol_training``; the configuration file names this module under
``data.generator`` and gives its keyword arguments under ``data.args``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


class DeepDriveStream:
    """Procedural road frames -> steering angle (deep-driving case study).

    A 'road' is a quadratic curve; the frame renders the road as bright
    pixels on a dark background from a forward-looking viewpoint; the target
    steering angle is proportional to the curvature ahead. Concept drift =
    changing road texture/curvature statistics (e.g. a new country).
    """

    def __init__(self, seed: int = 0, height: int = 68, width: int = 320,
                 curvature_scale: float = 1.0):
        self.h, self.w = height, width
        self._rng = np.random.RandomState(seed)
        self.curvature_scale = curvature_scale

    def force_drift(self):
        self.curvature_scale = float(self._rng.uniform(0.5, 2.0))

    def concept(self):
        return jnp.float32(self.curvature_scale)

    def sample_from(self, concept, key, batch: int):
        k1, k2, k3 = jax.random.split(key, 3)
        curv = concept * jax.random.normal(k1, (batch,)) * 0.3
        offset = jax.random.normal(k2, (batch,)) * 0.2
        ys = jnp.linspace(1.0, 0.0, self.h)                   # depth rows
        xs = jnp.linspace(-1.0, 1.0, self.w)

        def frame(c, o):
            center = o + c * (1.0 - ys) ** 2                  # (h,)
            halfw = 0.08 + 0.5 * ys                           # road widens nearby
            img = jnp.exp(-((xs[None, :] - center[:, None]) / halfw[:, None]) ** 2)
            return img

        imgs = jax.vmap(frame)(curv, offset)
        imgs = imgs + 0.05 * jax.random.normal(k3, imgs.shape)
        rgb = jnp.stack([imgs, imgs * 0.8, imgs * 0.6], axis=-1)
        steering = -2.0 * curv - 0.5 * offset                 # steer against curve
        return {"x": rgb, "y": steering}

    def sample(self, key, batch: int):
        return self.sample_from(self.concept(), key, batch)


def make(seed: int, args: dict) -> DeepDriveStream:
    return DeepDriveStream(seed=seed, **args)
