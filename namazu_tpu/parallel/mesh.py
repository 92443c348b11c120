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


def make_topology_mesh(
    n_devices: Optional[int] = None,
    host_size: int = 4,
    axes: tuple = ("h", "i"),
) -> Mesh:
    """``h x i`` mesh grouped by physical host for meshes PAST one
    host's chips: ``host_size`` chips per row (the 2x4 host-chip
    topology's 4; a 16-device pod slice becomes 4x4), so the ``i``-axis
    ring permutes neighbors over ICI within a host and only the thin
    ``h``-axis ring crosses DCN. A device count that IS one host's worth
    (or less) falls back to the flat single-axis mesh — no reason to pay
    a second collective axis. Delegates to
    ``distributed.make_hybrid_mesh`` for the process-grouping rules in
    real multi-host runs."""
    n = n_devices if n_devices is not None else len(jax.devices())
    if n <= host_size:
        return make_mesh(n_devices, axis=axes[1])
    if n % host_size != 0:
        raise ValueError(
            f"{n} devices do not divide into hosts of {host_size}"
        )
    from namazu_tpu.parallel.distributed import make_hybrid_mesh

    devs = jax.devices()[:n] if n_devices is not None else None
    return make_hybrid_mesh(n_hosts=n // host_size, devices=devs,
                            axes=axes)


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
