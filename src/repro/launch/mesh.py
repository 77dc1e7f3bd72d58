"""Production mesh construction (TPU v5e pods).

Kept as functions — importing this module never touches jax device state,
so unit tests keep their single CPU device unless a caller explicitly
builds a mesh (the dry-run sets XLA_FLAGS for 512 host devices first).
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
from jax.sharding import AxisType

from repro.sharding.ctx import CLIENTS_AXIS

SINGLE_POD = (16, 16)                  # 256 chips / pod
MULTI_POD = (2, 16, 16)                # 2 pods = 512 chips


def make_production_mesh(*, multi_pod: bool = False):
    """(data=16, model=16) single-pod or (pod=2, data=16, model=16) multi-pod.

    Uses the first prod(shape) devices, so a 512-device host platform serves
    both meshes.
    """
    shape = MULTI_POD if multi_pod else SINGLE_POD
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = math.prod(shape)
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devices)} — "
            "set XLA_FLAGS=--xla_force_host_platform_device_count=512 before "
            "importing jax (launch/dryrun.py does this)")
    return jax.make_mesh(shape, axes, devices=devices[:n],
                         axis_types=(AxisType.Auto,) * len(shape))


def make_debug_mesh(data: int = 2, model: int = 2, pods: int = 0):
    """Small mesh for CPU sharding tests (requires >= data*model*max(pods,1)
    host devices)."""
    if pods:
        shape, axes = (pods, data, model), ("pod", "data", "model")
    else:
        shape, axes = (data, model), ("data", "model")
    n = math.prod(shape)
    return jax.make_mesh(shape, axes, devices=jax.devices()[:n],
                         axis_types=(AxisType.Auto,) * len(shape))


def make_clients_mesh(shards: int = 0):
    """1-D ``("clients",)`` mesh for cohort-parallel execution.

    ``shards=0`` is host-count-aware: it uses every visible device, so the
    same call serves a real TPU slice and a CPU CI runner that forced 2-4
    host devices via ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
    (which must be set before jax initializes its backend). A single-device
    host yields a valid 1-shard mesh — the mesh executor then degenerates to
    the per-client path on one device, which is what the shard-scaling
    benchmark uses as its baseline.
    """
    devices = jax.devices()
    n = shards or len(devices)
    if n > len(devices):
        raise RuntimeError(
            f"need {n} devices for a {n}-shard clients mesh, have "
            f"{len(devices)} — set XLA_FLAGS=--xla_force_host_platform_"
            f"device_count={n} before importing jax")
    return jax.make_mesh((n,), (CLIENTS_AXIS,), devices=devices[:n],
                         axis_types=(AxisType.Auto,))


class DevicePeaks(NamedTuple):
    """Published per-chip peaks for the roofline model."""
    flops_bf16: float       # FLOP/s
    hbm_bw: float           # bytes/s
    ici_bw_per_link: float  # bytes/s per link
    hbm_bytes: int


# keyed by ``jax.Device.device_kind``. TPU v5e: Google Cloud documentation,
# "TPU v5e" (197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI per
# chip over 4 links = 50 GB/s per link)
DEVICE_PEAKS = {
    "TPU v5 lite": DevicePeaks(flops_bf16=197e12, hbm_bw=819e9,
                               ici_bw_per_link=50e9, hbm_bytes=16 * 1024 ** 3),
}
# the chip the production meshes above are built for
PRODUCTION_DEVICE_KIND = "TPU v5 lite"


def device_peaks(kind: str) -> DevicePeaks:
    """Peaks of the device ``kind``; an unknown kind is an error, never a
    silent default."""
    try:
        return DEVICE_PEAKS[kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind {kind!r}; "
                         f"known: {sorted(DEVICE_PEAKS)}") from None
