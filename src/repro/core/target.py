"""TargetSpec registry — the `--target lrz:supermuc-ng` analogue (§2.1).

A TargetSpec captures everything the AutoTuner needs to inject
target-specific building bricks: chip roofline constants, HBM capacity,
mesh topology, the local scheduler dialect, and which kernel library the
target supports.
"""

from __future__ import annotations

import dataclasses
import math


@dataclasses.dataclass(frozen=True)
class TargetSpec:
    name: str
    chip: str                       # tpu-v5e | cpu
    mesh_shape: tuple[int, ...]
    mesh_axes: tuple[str, ...]
    peak_flops: float               # per chip, bf16
    hbm_bw: float                   # bytes/s per chip
    hbm_bytes: float                # capacity per chip
    ici_bw: float                   # bytes/s per link
    scheduler: str = "slurm"        # slurm | pbs | local
    kernels: str = "pallas"         # pallas | reference
    # per-core VMEM capacity: the static budget Pallas block + scratch
    # shapes are linted against (analysis/lint vmem-budget rule).  CPU
    # targets keep the v5e figure — interpret-mode kernels must fit the
    # real accelerator they are rehearsing for.
    vmem_bytes: float = 128 * 2**20
    description: str = ""

    @property
    def num_chips(self) -> int:
        return math.prod(self.mesh_shape)


# TPU v5e per-chip peaks, published in Google Cloud's "TPU v5e"
# documentation: 197 TFLOP/s bf16, 819 GB/s HBM bandwidth, 16 GiB HBM,
# 1,600 Gbit/s inter-chip interconnect (~50 GB/s per link of four).
_V5E = dict(peak_flops=197e12, hbm_bw=819e9, hbm_bytes=16 * 2**30,
            ici_bw=50e9)

TARGETS: dict[str, TargetSpec] = {}


def register(t: TargetSpec) -> TargetSpec:
    TARGETS[t.name] = t
    return t


register(TargetSpec(
    name="lrz:tpu-v5e-pod", chip="tpu-v5e",
    mesh_shape=(16, 16), mesh_axes=("data", "model"),
    scheduler="slurm", kernels="pallas",
    description="single v5e pod, 256 chips, 16x16 (data, model)", **_V5E))

register(TargetSpec(
    name="lrz:tpu-v5e-2pod", chip="tpu-v5e",
    mesh_shape=(2, 16, 16), mesh_axes=("pod", "data", "model"),
    scheduler="slurm", kernels="pallas",
    description="two v5e pods, 512 chips, pod axis is pure DP", **_V5E))

register(TargetSpec(
    name="local:tpu-v5e", chip="tpu-v5e",
    mesh_shape=(1,), mesh_axes=("data",),
    scheduler="local", kernels="pallas",
    description="one attached v5e chip (single process owns it)", **_V5E))

register(TargetSpec(
    name="local:tpu-v5e-2x2", chip="tpu-v5e",
    mesh_shape=(2, 2), mesh_axes=("data", "model"),
    scheduler="local", kernels="pallas",
    description="one v5e host, four chips as a 2x2 (data, model) mesh",
    **_V5E))

register(TargetSpec(
    name="local:cpu", chip="cpu",
    mesh_shape=(1,), mesh_axes=("data",),
    peak_flops=5e10, hbm_bw=2e10, hbm_bytes=8e9, ici_bw=1e9,
    scheduler="local", kernels="reference",
    description="single-process CPU debug target (smoke tests, examples)"))

register(TargetSpec(
    name="local:cpu-mesh8", chip="cpu",
    mesh_shape=(2, 4), mesh_axes=("data", "model"),
    peak_flops=5e10, hbm_bw=2e10, hbm_bytes=8e9, ici_bw=1e9,
    scheduler="local", kernels="reference",
    description="8 forced host devices — integration tests of the SPMD path"))


# attached accelerators -> target: (device_kind, device count) as JAX
# reports them.  A device missing here is an error, never assumed peaks.
DEVICE_TARGETS: dict[tuple[str, int], str] = {
    ("TPU v5 lite", 1): "local:tpu-v5e",
    ("TPU v5 lite", 4): "local:tpu-v5e-2x2",
}


def get_target(name: str | None = None) -> TargetSpec:
    """A registered target by name; None is the target of the devices this
    process sees (the default of every entry point)."""
    if name is None:
        return target_for_devices()
    if name not in TARGETS:
        raise KeyError(f"unknown target {name!r}; known: {sorted(TARGETS)}")
    return TARGETS[name]


def target_for_devices(devices=None) -> TargetSpec:
    """The target describing the devices this process sees.

    The CPU platform maps to ``local:cpu``; an accelerator maps through
    ``DEVICE_TARGETS`` by its ``device_kind`` and the device count, and an
    unlisted one raises instead of borrowing another chip's peaks.
    """
    if devices is None:
        import jax
        devices = jax.devices()
    dev = devices[0]
    if dev.platform == "cpu":
        return get_target("local:cpu")
    key = (dev.device_kind, len(devices))
    if key not in DEVICE_TARGETS:
        raise KeyError(
            f"no target for {len(devices)} x {dev.device_kind!r} "
            f"({dev.platform}); known: {sorted(DEVICE_TARGETS)}")
    return get_target(DEVICE_TARGETS[key])


def serve_target(name: str | None = None) -> TargetSpec:
    """The serving entry points' target: `name` if given, else the target
    of the FIRST attached device alone.  Serving runs on one chip (a
    router's replicas share it); no serving path shards over a mesh, so a
    four-chip host serves from one of its chips."""
    if name is not None:
        return get_target(name)
    import jax
    return target_for_devices(jax.devices()[:1])

