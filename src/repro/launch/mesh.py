"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this
module touches no jax device state — required because the dry-run process
must set XLA_FLAGS before the first jax initialization.

Axis semantics:
  pod   — cross-pod data parallelism (federated clients span pods too)
  data  — within-pod data parallelism = the federated-client axis
  model — tensor/expert parallelism within a client's shard
"""
from __future__ import annotations

import jax


#: GSPMD partitions these meshes (``jax.make_mesh`` defaults to Explicit)
_AUTO = jax.sharding.AxisType.Auto


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(_AUTO,) * len(axes))


def make_host_mesh(data: int = 2, model: int = 2) -> jax.sharding.Mesh:
    """Small mesh over forced host devices (unit tests)."""
    return jax.make_mesh(
        (data, model), ("data", "model"), axis_types=(_AUTO, _AUTO)
    )


def data_axis_size(mesh: jax.sharding.Mesh) -> int:
    n = mesh.shape["data"]
    if "pod" in mesh.axis_names:
        n *= mesh.shape["pod"]
    return n
