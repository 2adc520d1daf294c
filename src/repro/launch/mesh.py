"""Production mesh construction.

`make_production_mesh` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state. The dry-run
launchers need XLA_FLAGS=--xla_force_host_platform_device_count=512 in the
environment before Python starts; smoke tests and benchmarks see the real
devices.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < need:
        raise RuntimeError(
            f"mesh {shape} needs {need} devices, found {len(devices)} — "
            "for a CPU dry run set JAX_PLATFORMS=cpu and "
            "XLA_FLAGS=--xla_force_host_platform_device_count=512 in the "
            "environment before Python starts")
    return Mesh(np.asarray(devices[:need]).reshape(shape), axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    need = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < need:
        raise RuntimeError(f"mesh {shape} needs {need} devices")
    return Mesh(np.asarray(devices[:need]).reshape(shape), axes)


def make_test_mesh(axes: Tuple[str, ...] = ("data", "model")) -> Optional[Mesh]:
    """Largest mesh the current process supports (1 device => (1, 1))."""
    n = len(jax.devices())
    if len(axes) == 2:
        a = 2 if n >= 2 else 1
        b = max(1, min(n // a, 4))
        return make_mesh((a, b) if a * b <= n else (1, 1), axes)
    return make_mesh((1,) * len(axes), axes)


# v5e hardware constants used by the roofline (per chip)
PEAK_FLOPS_BF16 = 197e12        # FLOP/s
HBM_BW = 819e9                  # B/s
ICI_BW = 50e9                   # B/s per link (~per exchange direction)
HBM_PER_CHIP = 16 * 2**30       # 16 GiB
