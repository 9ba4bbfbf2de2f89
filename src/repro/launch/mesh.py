"""Production meshes (TPU v5e pods).

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state. The dry-run entry
point sets ``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before
any jax import; everything else sees the real (single) CPU device.

Mesh construction goes through ``repro.compat`` (Auto-typed axes).
"""
from __future__ import annotations

from repro.compat import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for CPU tests (requires forced host device count)."""
    return make_mesh(shape, axes)


# The chip the production meshes are built for (``jax.Device.device_kind``
# of a TPU v5e); its peaks are ``repro.analysis.roofline.CHIP_PEAKS``.
PRODUCTION_DEVICE_KIND = "TPU v5 lite"
