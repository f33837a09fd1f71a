"""Production meshes.

Target hardware: TPU v5e pods, 16x16 = 256 chips per pod, 2 pods = 512.
Single-pod mesh: (16, 16) = ('data', 'model'); multi-pod adds a leading
'pod' axis: (2, 16, 16) = ('pod', 'data', 'model').

``make_production_mesh`` is a FUNCTION (never a module-level constant) so
importing this module touches no jax device state; the dry-run sets
--xla_force_host_platform_device_count=512 before any jax import and then
calls it.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax

HW = {
    "name": "tpu-v5e",
    "peak_flops_bf16": 197e12,     # per chip
    "hbm_bw": 819e9,               # bytes/s per chip
    "ici_bw": 50e9,                # bytes/s per link (~ per direction)
    "hbm_bytes": 16e9,             # per chip
}


def make_production_mesh(*, multi_pod: bool = False):
    shape: Tuple[int, ...] = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = math.prod(shape)
    devs = jax.devices()
    if len(devs) < need:
        raise RuntimeError(
            f"mesh {shape} needs {need} devices, found {len(devs)} — run "
            "under launch/dryrun.py (it forces 512 host-platform devices)")
    return jax.make_mesh(shape, axes, devices=devs[:need])


def make_local_mesh(model_axis: Optional[int] = None, *,
                    axis_names: Tuple[str, str] = ("data", "model"),
                    max_devices: Optional[int] = None):
    """Whatever the host actually has — for smoke tests and examples.

    Tolerates emulated host platforms with many devices
    (``--xla_force_host_platform_device_count=N``): ``max_devices`` caps
    how many are meshed (default: all of them), and ``axis_names``
    renames the two axes — the sim's sharded device pool builds its
    1-wide-model ('devices', ...) mesh through here instead of growing a
    second local-mesh factory.

    Both axes are ``Auto``: arrays that leave a shard_map stay plain
    device-sharded values, so host-side eager updates such as
    ``params.at[j].set(...)`` need no output-sharding annotation (the
    ``Explicit`` default of ``jax.make_mesh`` would reject them)."""
    devs = jax.devices()
    n = len(devs) if max_devices is None else min(max_devices, len(devs))
    m = model_axis or 1
    if n < m:
        raise RuntimeError(f"model_axis={m} needs {m} devices, found {n}")
    n = (n // m) * m                    # drop any remainder (historical)
    return jax.make_mesh((n // m, m), axis_names,
                         (jax.sharding.AxisType.Auto,) * 2, devices=devs[:n])
