"""Image stream for the MNIST fleet: a verbatim copy of ``SyntheticMNIST``
from ``src/repro/data/synthetic.py`` at commit 4a8f582, kept here so that
the benchmark's inputs cannot move with the program.

``make(seed, args)`` builds the source a cell feeds to
``run_protocol_training``; the configuration file names this module under
``data.generator`` and gives its keyword arguments under ``data.args``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


class SyntheticMNIST:
    """10-class 28x28 images from class templates + noise + translation."""

    def __init__(self, seed: int = 0, num_classes: int = 10,
                 image_size: int = 28, noise: float = 0.35):
        self.num_classes = num_classes
        self.image_size = image_size
        self.noise = noise
        rng = np.random.RandomState(seed)
        # smooth class templates: random low-frequency patterns
        freqs = rng.randn(num_classes, 4, 4)
        t = np.linspace(0, 2 * np.pi, image_size)
        basis = np.stack([np.sin((i + 1) * t / 2) for i in range(4)])  # (4,S)
        self.templates = np.einsum("cij,ih,jw->chw", freqs, basis, basis)
        self.templates /= np.abs(self.templates).max(axis=(1, 2), keepdims=True)
        self._templates_dev = jnp.asarray(self.templates, jnp.float32)

    def concept(self):
        return self._templates_dev

    def sample_from(self, concept, key, batch: int):
        k1, k2, k3, k4 = jax.random.split(key, 4)
        labels = jax.random.randint(k1, (batch,), 0, self.num_classes)
        temps = concept[labels]                                        # (B,H,W)
        shift = jax.random.randint(k2, (batch, 2), -2, 3)
        temps = jax.vmap(lambda img, s: jnp.roll(img, s, axis=(0, 1)))(temps, shift)
        imgs = temps + self.noise * jax.random.normal(k3, temps.shape)
        return {"x": imgs[..., None], "y": labels}

    def sample(self, key, batch: int):
        return self.sample_from(self.concept(), key, batch)


def make(seed: int, args: dict) -> SyntheticMNIST:
    return SyntheticMNIST(seed=seed, **args)
