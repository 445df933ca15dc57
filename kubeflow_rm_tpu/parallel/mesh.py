"""Device-mesh construction.

Axis convention used throughout the framework:

- ``dp``   pure data parallelism (params replicated) — maps to DCN
           across slices in multi-slice jobs.
- ``pp``   pipeline parallelism (layer-stack sharded into stages,
           GPipe microbatch schedule in ``parallel.pipeline``). Its
           traffic is one point-to-point activation transfer per
           microbatch — the lowest-bandwidth axis, so it sits just
           inside dp and can span DCN too.
- ``fsdp`` data parallelism with parameter sharding (ZeRO-3 style);
           rides ICI within a slice so the per-layer all-gathers are
           cheap.
- ``ep``   expert parallelism (MoE expert dim sharded; the dispatch
           einsums become XLA all-to-alls over ICI —
           ``parallel.moe``).
- ``sp``   sequence/context parallelism (ring attention) — also ICI.
- ``tp``   tensor (megatron-style) parallelism — innermost axis so its
           per-matmul collectives take the fastest ICI hops.

Axis order in the mesh tuple is outermost-to-innermost exactly as above:
``jax.make_mesh`` assigns the innermost mesh axis to the most-local
device neighbourhoods, which is where tp's latency-sensitive
all-reduces belong.

The platform half of this repo guarantees the env this module consumes:
the webhook injects TPU_WORKER_ID/TPU_WORKER_HOSTNAMES (SURVEY.md §2.6)
and the controller renders the slice topology into the pod.
"""

from dataclasses import dataclass

import jax
from jax.sharding import AxisType, Mesh

AXES = ("dp", "pp", "fsdp", "ep", "sp", "tp")


@dataclass(frozen=True)
class MeshConfig:
    dp: int = 1
    pp: int = 1
    fsdp: int = -1  # -1: absorb all remaining devices
    ep: int = 1
    sp: int = 1
    tp: int = 1

    def resolve(self, n_devices: int) -> tuple[int, ...]:
        sizes = [self.dp, self.pp, self.fsdp, self.ep, self.sp, self.tp]
        known = 1
        for s in sizes:
            if s != -1:
                known *= s
        n_wild = sizes.count(-1)
        if n_wild > 1:
            raise ValueError("at most one mesh axis may be -1")
        if n_wild == 1:
            if n_devices % known:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes {known}"
                )
            sizes[sizes.index(-1)] = n_devices // known
        import math
        if math.prod(sizes) != n_devices:
            raise ValueError(
                f"mesh {dict(zip(AXES, sizes))} does not cover {n_devices} devices"
            )
        return tuple(sizes)


def make_hybrid_mesh(config: MeshConfig | None = None, *,
                     n_slices: int, devices=None) -> Mesh:
    """Multislice mesh: ``dp`` spans slices over DCN; fsdp/sp/tp stay
    inside each slice on ICI (the scaling-book layout — parameters are
    gathered over fast links, only gradients cross the data-center
    network). ``config.dp`` must equal ``n_slices`` (or -1).

    Uses ``mesh_utils.create_hybrid_device_mesh`` so device order
    respects slice locality; under multislice the platform guarantees
    slice-major process ids (``distributed.initialize``), which is what
    makes the per-slice device blocks contiguous here.
    """
    from jax.experimental import mesh_utils

    config = config or MeshConfig()
    devices = devices if devices is not None else jax.devices()
    if config.dp == -1:
        config = MeshConfig(dp=n_slices, pp=config.pp, fsdp=config.fsdp,
                            ep=config.ep, sp=config.sp, tp=config.tp)
    shape = config.resolve(len(devices))
    if shape[0] != n_slices:
        raise ValueError(
            f"dp axis ({shape[0]}) must equal n_slices ({n_slices}) — "
            "dp is the DCN axis in a multislice job")
    per_slice = len(devices) // n_slices
    dev_mesh = mesh_utils.create_hybrid_device_mesh(
        mesh_shape=(1, *shape[1:]),
        dcn_mesh_shape=(n_slices,) + (1,) * (len(AXES) - 1),
        devices=devices,
        process_is_granule=False,
        should_sort_granules_by_key=True,
    ) if _has_slice_index(devices) else _reshape_fallback(devices, shape)
    return Mesh(dev_mesh.reshape(shape), AXES,
                axis_types=(AxisType.Auto,) * len(AXES))


def _has_slice_index(devices) -> bool:
    return getattr(devices[0], "slice_index", None) is not None


def _reshape_fallback(devices, shape):
    """CPU-mesh tests have no slice_index: slice-major order is just
    the device list order (the dryrun contract)."""
    import numpy as np
    return np.asarray(devices).reshape(shape)


def make_mesh(config: MeshConfig | None = None, devices=None) -> Mesh:
    """Build the framework-standard 4-axis mesh over ``devices``."""
    config = config or MeshConfig()
    devices = devices if devices is not None else jax.devices()
    shape = config.resolve(len(devices))
    # Auto axis types: shardings are annotations and XLA's SPMD
    # partitioner propagates + inserts collectives (GSPMD), rather than
    # jax 0.9's default Explicit sharding-in-types mode.
    return jax.make_mesh(
        shape, AXES, devices=devices,
        axis_types=(AxisType.Auto,) * len(AXES))
