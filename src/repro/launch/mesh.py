"""Production mesh definitions and the per-chip peak table.

``make_production_mesh`` is a function (never a module-level constant) so that
importing this module does not touch jax device state. Every mesh is built
with ``AxisType.Auto`` axes: the serving engine runs eager ops on the outputs
of its ``shard_map`` steps, which explicit-axis meshes (``jax.make_mesh``'s
default) refuse outside a ``jax.set_mesh`` context.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
from jax.sharding import AxisType

# Published per-chip peaks, keyed by ``jax.Device.device_kind``. A kind that
# is not here is an error wherever a peak is needed, never a default.
# TPU v5e — Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
# 16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI (4 links x 50 GB/s).
V5E = "TPU v5 lite"
PEAKS: Dict[str, Dict[str, float]] = {
    V5E: {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
          "ici_bytes_per_s_per_link": 50e9, "hbm_bytes": 16e9},
}


def device_peaks(kind: Optional[str] = None) -> Dict[str, float]:
    """Peaks of device kind ``kind`` (default: this process's first device).

    Raises ``ValueError`` for a kind the table does not list — a CPU host
    has no peak to divide by, and guessing one would put a made-up device
    number under a real device's name.
    """
    if kind is None:
        kind = jax.devices()[0].device_kind
    if kind not in PEAKS:
        raise ValueError(f"no published peaks for device kind {kind!r}; "
                         f"known kinds: {sorted(PEAKS)}")
    return PEAKS[kind]


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(n_data: int = 1, n_model: int = 1):
    """Tiny mesh over however many local devices exist (tests)."""
    return make_mesh((n_data, n_model), ("data", "model"))


def parse_mesh(spec: str):
    """``"DxM"`` -> ``(n_data, n_model)`` — the CLI mesh-shape syntax used
    by the serving bench (``--mesh 1x2``)."""
    parts = spec.lower().split("x")
    if len(parts) != 2:
        raise ValueError(f"mesh spec {spec!r} is not of the form 'DxM'")
    try:
        n_data, n_model = int(parts[0]), int(parts[1])
    except ValueError:
        raise ValueError(f"mesh spec {spec!r} is not of the form 'DxM'")
    if n_data < 1 or n_model < 1:
        raise ValueError(f"mesh spec {spec!r} must have positive axes")
    return n_data, n_model
