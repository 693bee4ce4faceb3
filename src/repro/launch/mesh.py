"""Production mesh construction.

A FUNCTION, not a module-level constant, so importing this module never
touches jax device state (the dry-run sets XLA_FLAGS before first init).

Every mesh is built here with Auto axes: ``jax.make_mesh`` otherwise
defaults to Explicit axes, under which host-side slicing of a sharded
result and jitting over sharded inputs raise ``ShardingTypeError``.
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """Auto-axis mesh over the visible devices (tests / elastic re-mesh)."""
    axes = tuple(axes)
    return jax.make_mesh(tuple(shape), axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def graph_mesh(hosts: int, per_host: int):
    """The 2-D (host, device) worker mesh of the hierarchical graph
    executor: axis ``"h"`` spans hosts, axis ``"w"`` the devices within
    one host, and the flat row-major device order (d = h * per_host + t)
    is the worker-block order, so ``jax.lax.all_to_all`` over ``"w"``
    exchanges within replica groups {h*T..h*T+T-1} (intra-host) and over
    ``"h"`` within column groups {t, T+t, 2T+t, ...} (inter-host) — the
    two collective levels the hierarchical exchanges ride.

    Single-process: force enough host devices before importing jax
    (``XLA_FLAGS=--xla_force_host_platform_device_count=H*T``; the CLIs
    do this) — the mesh then *simulates* the hierarchy, which is what
    the parity/bench suites run.  Multi-process: call
    ``jax.distributed.initialize`` first (one process per host, T local
    devices each) and the same mesh maps ``"h"`` onto real process
    boundaries, because ``jax.make_mesh`` orders global devices
    process-major."""
    hosts, per_host = int(hosts), int(per_host)
    need = hosts * per_host
    if need > len(jax.devices()):
        raise RuntimeError(
            f"graph_mesh({hosts}, {per_host}) needs {need} devices but "
            f"only {len(jax.devices())} are visible")
    return make_mesh((hosts, per_host), ("h", "w"))


def dp_axes(mesh) -> tuple:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def mp_axis(mesh) -> str:
    return "model"
