"""Device-mesh construction helpers.

The reference sizes its "cluster" as ``numWorkers = min(numTasks,
df partitions)`` and forms a TCP ring over exactly that many native workers
(SURVEY.md §3.1).  The TPU analog is a ``jax.sharding.Mesh`` over the chips
visible to this process group; the data-parallel GBDT shards rows over the
``"data"`` axis and every collective rides ICI (or DCN across slices) via the
same mesh — no rendezvous machinery of our own.
"""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

import jax
from jax.sharding import Mesh

# The row-sharding axis used by data-parallel training (the moral equivalent
# of LightGBM's tree_learner=data worker ring — SURVEY.md §2 parallelism).
DATA_AXIS = "data"

# The fast intra-host axis of the 2D pod mesh (ISSUE 14): devices that share
# a host (ICI neighbours) line up on this axis, so the hierarchical histogram
# merge's psum_scatter rides the fast links while only the tiny winner
# exchange crosses DATA_AXIS (the slow inter-host / DCN axis).
FEATURE_AXIS = "feature"

# Row shards of the 2D mesh span BOTH axes (every device holds a distinct
# row block of n / (H·d) rows); global reductions name the tuple.
ROW_AXES = (DATA_AXIS, FEATURE_AXIS)


def default_mesh(
    num_devices: Optional[int] = None,
    axis_name: str = DATA_AXIS,
    devices: Optional[Sequence] = None,
) -> Mesh:
    """A 1-D mesh over (a prefix of) the visible devices.

    ``num_devices`` mirrors the reference's ``numTasks`` param (cap the
    worker count below the cluster size); ``None`` uses every device.
    """
    devs = list(devices) if devices is not None else list(jax.devices())
    if num_devices is not None:
        if num_devices > len(devs):
            raise ValueError(
                f"requested {num_devices} devices but only {len(devs)} visible"
            )
        devs = devs[:num_devices]
    return Mesh(np.asarray(devs), (axis_name,))


def mesh2d(
    num_hosts: Optional[int] = None,
    devices_per_host: Optional[int] = None,
    devices: Optional[Sequence] = None,
) -> Mesh:
    """The 2D ``(data × feature)`` pod mesh (ISSUE 14).

    Rows of the device grid are HOSTS (slow inter-host links — DCN across
    slices on real pods), columns are the devices WITHIN a host (fast ICI
    links), so a collective over :data:`FEATURE_AXIS` alone never leaves a
    host.  With no arguments the grid is derived from the process topology:
    ``jax.devices()`` grouped by ``process_index`` (call after
    ``initialize_distributed``), one mesh row per process.  Explicit
    ``(num_hosts, devices_per_host)`` overrides support virtual topologies —
    a single-process 8-CPU-device test models a (2 hosts × 4 devices) pod —
    and capping a real one.
    """
    devs = list(devices) if devices is not None else list(jax.devices())
    if num_hosts is None or devices_per_host is None:
        by_proc: dict = {}
        for d in devs:
            by_proc.setdefault(getattr(d, "process_index", 0), []).append(d)
        groups = [by_proc[p] for p in sorted(by_proc)]
        sizes = {len(g) for g in groups}
        if len(sizes) != 1:
            raise ValueError(
                f"uneven per-process device counts {sorted(sizes)}; pass "
                "explicit (num_hosts, devices_per_host)"
            )
        H = num_hosts if num_hosts is not None else len(groups)
        d_per = devices_per_host if devices_per_host is not None else sizes.pop()
        devs = [dev for g in groups for dev in g]
    else:
        H, d_per = num_hosts, devices_per_host
    if H * d_per > len(devs):
        raise ValueError(
            f"requested {H}×{d_per} mesh but only {len(devs)} devices visible"
        )
    grid = np.asarray(devs[: H * d_per]).reshape(H, d_per)
    return Mesh(grid, (DATA_AXIS, FEATURE_AXIS))


def mesh_num_devices(mesh: Optional[Mesh]) -> int:
    if mesh is None:
        return 1
    return int(np.prod(mesh.devices.shape))


def mesh_axis_size(mesh: Optional[Mesh], axis_name: str) -> int:
    """Size of one named mesh axis (1 when the mesh lacks the axis)."""
    if mesh is None:
        return 1
    return int(dict(zip(mesh.axis_names, mesh.devices.shape)).get(axis_name, 1))


def is_mesh_2d(mesh: Optional[Mesh]) -> bool:
    """True for the :func:`mesh2d` topology (both named axes present)."""
    return (
        mesh is not None
        and DATA_AXIS in mesh.axis_names
        and FEATURE_AXIS in mesh.axis_names
    )
