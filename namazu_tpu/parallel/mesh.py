"""Mesh construction helpers."""

from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import Mesh


def default_device_count() -> int:
    return len(jax.devices())


def device_summary() -> dict:
    """The devices this process's JAX backend reports — what the search
    states once per install/reply so a caller can tell a chip run from
    a CPU one: ``{"platform", "kind", "count"}``."""
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices)}


def make_mesh(n_devices: Optional[int] = None, axis: str = "i") -> Mesh:
    """1-D mesh over the first ``n_devices`` devices (default: all).

    The island axis is the only mesh axis the search needs: genomes are
    embarrassingly parallel within an island (vmap), islands communicate
    only during migration (ppermute) and stats (psum).
    """
    devices = jax.devices()
    if n_devices is not None:
        if n_devices > len(devices):
            raise ValueError(
                f"requested {n_devices} devices, have {len(devices)}"
            )
        devices = devices[:n_devices]
    return jax.make_mesh((len(devices),), (axis,), devices=devices)
