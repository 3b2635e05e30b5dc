"""Production mesh builders.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state — the dry-run sets
``--xla_force_host_platform_device_count=512`` before first jax init, and
smoke tests must keep seeing 1 device.

Topology: v5e pod of 256 chips as (data=16, model=16); two pods add a
leading ``pod`` axis used as an outer data axis (pure DP across pods — the
only cross-pod collective is the gradient all-reduce, the right shape when
inter-pod DCI bandwidth ≪ intra-pod ICI).
"""
from __future__ import annotations

import os

import jax

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def mesh_shape_dict(multi_pod: bool = False):
    return ({"pod": 2, "data": 16, "model": 16} if multi_pod
            else {"data": 16, "model": 16})


def n_chips(multi_pod: bool = False) -> int:
    return 512 if multi_pod else 256


def make_node_mesh(mesh_nodes: int):
    """1-D ``("nodes",)`` mesh for the sharded cluster-retrieval scans
    (core/cluster_index.py): the embarrassingly-parallel node axis of the
    stacked cache slabs maps one shard of nodes per device.  Raises
    ``ValueError`` when the backend has fewer devices than requested.

    The axis is ``Auto``: the donated ``.at[node, slots].set`` row
    updates are plain jitted scatters, and sharding propagation routes
    each write to the owning shard.  (``jax.make_mesh`` defaults to
    ``Explicit`` axes, under which those scatters would need an
    ``out_sharding`` at every call site.)"""
    from jax.sharding import AxisType

    if mesh_nodes < 1:
        raise ValueError(f"mesh_nodes must be >= 1, got {mesh_nodes}")
    avail = len(jax.devices())
    if avail < mesh_nodes:
        raise ValueError(
            f"mesh_nodes={mesh_nodes} needs that many devices, backend has "
            f"{avail}; on CPU force more with ensure_host_devices() BEFORE "
            "first jax use")
    return jax.make_mesh((mesh_nodes,), ("nodes",),
                         axis_types=(AxisType.Auto,))


def ensure_host_devices(n: int) -> bool:
    """Ask the CPU backend for ``n`` devices (``jax_num_cpu_devices``).
    Only the CPU backend is affected: on a TPU host the device count is
    the chips present, so callers still check ``len(jax.devices())``.
    Only effective BEFORE the backends initialise (jax import alone does
    not initialise them; first device/array use does).  Returns False
    when they are already up with fewer than ``n`` devices."""
    try:
        jax.config.update("jax_num_cpu_devices", n)
    except RuntimeError:                          # backends already up
        return len(jax.devices()) >= n
    return True


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already
    reads it and nothing else is set here.  Otherwise the cache lives at
    the fixed path ``<repo>/.cache/jax_compile``: the path is part of the
    cache key, so it must not move between runs."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(REPO_ROOT, ".cache", "jax_compile")
        jax.config.update("jax_compilation_cache_dir", path)
    # cache every program: the serving buckets compile in seconds each,
    # under the defaults' one-second floor for some of them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
