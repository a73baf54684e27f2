"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state — required because the dry-run must set
XLA_FLAGS=--xla_force_host_platform_device_count=512 *before* first jax
initialization, while smoke tests and benchmarks must see 1 device.
"""
from __future__ import annotations

import warnings

import jax

from repro.kernels._util import on_tpu


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """16x16 single-pod (256 chips) or 2x16x16 two-pod (512 chips) mesh.

    Axes: ("data", "model") single-pod; ("pod", "data", "model") multi-pod.
    The "pod" axis carries pure data parallelism across the inter-pod DCN
    link; "model" is the intra-pod ICI tensor/expert-parallel axis.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh() -> jax.sharding.Mesh:
    """Trivial 1x1 mesh over the real local device (tests / examples)."""
    return jax.make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])


def replica_devices(n: int) -> list[jax.Device]:
    """One device per data-parallel engine replica along the "data" axis.

    With more replicas than devices the assignment wraps (replicas share a
    device) — tests run with 1 CPU device and the fleet benchmark emulates
    a mesh with ``--xla_force_host_platform_device_count=N`` (set before
    first jax initialization, exactly like the dry-run's 512-chip override;
    the benchmark's ``--devices`` flag does this pre-import)."""
    if n < 1:
        raise ValueError(f"need at least one replica, got {n}")
    return [g[0] for g in replica_submeshes(n, 1)]


def replica_submeshes(
    n_replicas: int, shards_per_replica: int = 1
) -> list[list[jax.Device]]:
    """Carve the device list into per-replica "model"-axis submeshes.

    Replica ``i`` owns the ``shards_per_replica`` contiguous devices starting
    at ``i * shards_per_replica`` — contiguity is what keeps a tensor-
    parallel psum on intra-group links.  Assignment rules:

    * ``shards_per_replica == 1`` — the PR 8 behavior: with more replicas
      than devices the assignment wraps silently (replicas share a device;
      how single-CPU tests run an N-replica fleet).
    * ``shards_per_replica > 1`` and one physical device — on CPU every
      replica gets the single device repeated (pure emulation: the TP layer
      runs its shards under ``vmap`` on that device), with a warning; on
      TPU this is an error, since the shards would all share one chip.
    * ``shards_per_replica > 1`` on a real mesh — a replica whose group
      would straddle the device-list end non-contiguously (wrap-around
      mixing the first and last devices of the "model" axis) is REJECTED:
      the wrapped group's psum would hop the mesh seam every layer.  Grow
      the emulated mesh (``--xla_force_host_platform_device_count``) or
      drop the replica count.
    """
    if n_replicas < 1:
        raise ValueError(f"need at least one replica, got {n_replicas}")
    if shards_per_replica < 1:
        raise ValueError(f"need at least one shard per replica, got {shards_per_replica}")
    devs = jax.devices()
    d = len(devs)
    if shards_per_replica == 1:
        return [[devs[i % d]] for i in range(n_replicas)]
    if d == 1:
        if on_tpu():
            raise ValueError(
                f"{shards_per_replica}-way tensor parallelism needs "
                f"{shards_per_replica} TPU devices, found 1"
            )
        warnings.warn(
            f"{shards_per_replica}-way tensor parallelism on a single device: "
            "shards will be vmap-emulated, not distributed",
            stacklevel=2,
        )
        return [[devs[0]] * shards_per_replica for _ in range(n_replicas)]
    groups = []
    for i in range(n_replicas):
        start = (i * shards_per_replica) % d
        if start + shards_per_replica > d:
            raise ValueError(
                f"replica {i}'s {shards_per_replica}-device submesh would wrap "
                f"non-contiguously around the {d}-device mesh (start {start}); "
                f"the model axis must stay contiguous — use "
                f"n_replicas * shards_per_replica <= {d} (or a multiple)"
            )
        groups.append(list(devs[start : start + shards_per_replica]))
    return groups
