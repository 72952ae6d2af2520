"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module must never
touch jax device state (the dry-run sets the 512-device XLA flag before any
jax initialization)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    # Auto axes: the sharding rules leave dims UNCONSTRAINED for the
    # compiler to choose, which Explicit axes (the make_mesh default
    # since JAX 0.8) reject.
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


# Published per-chip peaks, keyed by ``jax.Device.device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (system architecture):
# 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI per chip.
DEVICE_PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_s": 819e9,
                    "ici_link_bytes_s": 50e9},
}


def device_peaks(device_kind: str) -> dict:
    """Peaks of one chip of ``device_kind``; a device that is not in the
    table is an error, never a default."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} (known: {sorted(DEVICE_PEAKS)})"
                       ) from None

