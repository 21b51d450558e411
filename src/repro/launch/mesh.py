"""Production meshes.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state.  The dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax import.
"""
from __future__ import annotations

import jax


def _auto(axis_names):
    return (jax.sharding.AxisType.Auto,) * len(axis_names)


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips.
    Multi-pod: (pod=2, data=16, model=16) = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(axes))


def make_host_mesh(data: int = 1, model: int = 1, expert: int = 0,
                   axis_names=None):
    """Small mesh over whatever devices exist (tests / examples).

    ``expert > 0`` grows a third "expert" axis — the 3D (data, model,
    expert) meshes MoE configs train on; the default stays 2D so existing
    callers are unchanged."""
    n = len(jax.devices())
    if expert:
        assert data * model * expert <= n, (data, model, expert, n)
        axes = axis_names or ("data", "model", "expert")
        return jax.make_mesh((data, model, expert), axes,
                             axis_types=_auto(axes))
    assert data * model <= n, (data, model, n)
    axes = axis_names or ("data", "model")
    return jax.make_mesh((data, model), axes, axis_types=_auto(axes))


def host_device_map(num_hosts: int, devices=None):
    """Partition the visible devices into per-host groups: host i owns a
    contiguous equal slice.  The elastic layer (core/elastic_loop.py)
    shrinks/grows meshes host-group-wise, mirroring how a real failure
    takes out a whole host's devices at once."""
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    assert num_hosts > 0 and n % num_hosts == 0, (n, num_hosts)
    per = n // num_hosts
    return {h: devices[h * per:(h + 1) * per] for h in range(num_hosts)}
