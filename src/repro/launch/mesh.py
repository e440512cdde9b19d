"""Production mesh definitions.

Functions (never module-level constants) so importing this module never
touches jax device state — the dry-run must set XLA_FLAGS before any jax
initialization.

Single pod: (16, 16) = 256 chips, axes (data, model).
Multi-pod:  (2, 16, 16) = 512 chips, axes (pod, data, model) — the pod axis
is pure data parallelism across pods (or, in EchoPFL-over-pods mode, one FL
client per pod slice).

Plane mesh: the server's parameter plane (core/plane.py) shards its
(capacity, dim) row store over a dedicated "plane" axis (rows = cluster
centers / anchors / per-client last uploads) and optionally "model" (the
flat parameter dim). Built by :func:`make_plane_mesh`; selected at runtime
by the ``REPRO_PLANE_MESH`` env knob via :func:`plane_mesh_from_env`.
"""
from __future__ import annotations

import os

import jax
from jax.sharding import AxisType

# TPU v5e roofline constants (per chip) — used by benchmarks/bench_roofline.
PEAK_FLOPS_BF16 = 197e12  # FLOP/s
HBM_BW = 819e9  # bytes/s
ICI_BW = 50e9  # bytes/s per link


def _make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with Auto axes: the plane, fleet and model code
    place operands with ``NamedSharding``/``shard_map`` and leave the rest to
    the partitioner, which Explicit axes (the ``make_mesh`` default) refuse."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_smoke_mesh() -> jax.sharding.Mesh:
    """1-device mesh with the production axis names — smoke tests and the
    quickstart use it so the same shardings lower everywhere."""
    return _make_mesh((1, 1), ("data", "model"))


def make_plane_mesh(row_shards: int | None = None, *, dim_shards: int = 1) -> jax.sharding.Mesh:
    """Mesh for the sharded parameter plane: axes ("plane",) or, when
    ``dim_shards > 1``, ("plane", "model"). Rows (fleet state: centers,
    anchors, per-client last uploads) spread over "plane"; the flat
    parameter dim may additionally spread over "model" for models whose
    single row outgrows one device."""
    n = len(jax.devices())
    if dim_shards < 1 or n % dim_shards != 0:
        raise ValueError(f"dim_shards {dim_shards} must divide device count {n}")
    if row_shards is None:
        row_shards = n // dim_shards
    if dim_shards == 1:
        return _make_mesh((row_shards,), ("plane",))
    return _make_mesh((row_shards, dim_shards), ("plane", "model"))


def _mesh_from_spec(spec: str) -> jax.sharding.Mesh | None:
    """Shared mesh-spec grammar: ""/"0"/"off"/"none" -> None (single-device,
    the default); "auto" -> all local devices on the "plane" axis; "R" ->
    exactly R row shards (so "1" is a 1-device mesh, not auto); "RxM" -> R
    row shards x M dim shards."""
    spec = spec.strip().lower()
    if spec in ("", "0", "off", "none"):
        return None
    if spec == "auto":
        n = len(jax.devices())
        return None if n == 1 else make_plane_mesh(n)
    if "x" in spec:
        rows, dims = (int(p) for p in spec.split("x", 1))
        return make_plane_mesh(rows, dim_shards=dims)
    return make_plane_mesh(int(spec))


def plane_mesh_from_env() -> jax.sharding.Mesh | None:
    """Mesh for the *server* parameter plane, from ``REPRO_PLANE_MESH``."""
    return _mesh_from_spec(os.environ.get("REPRO_PLANE_MESH", ""))


def fleet_mesh_from_env() -> jax.sharding.Mesh | None:
    """Mesh for the *client fleet* engine (its model plane and the batched
    ``(clients, n, dim)`` data tensors), from ``REPRO_FLEET_MESH``. Same
    grammar as ``REPRO_PLANE_MESH``; kept separate so server-plane sharding
    experiments do not silently reshard the simulated devices too."""
    return _mesh_from_spec(os.environ.get("REPRO_FLEET_MESH", ""))


def batch_axes(mesh: jax.sharding.Mesh) -> tuple[str, ...]:
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def axis_size(mesh: jax.sharding.Mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.axis_names else 1
