"""Mesh construction: the one place meshes are built.

`make_production_mesh()` is a FUNCTION (never a module-level constant) so that
importing this module never touches jax device state — the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 *before* any jax import,
and smoke tests / benches must keep seeing 1 device.

Every mesh gets Auto axis types.  `jax.make_mesh` defaults to Explicit axes,
under which eager slicing and reshapes of sharded arrays, and carries that
mix sharded and unsharded values, need an explicit `out_sharding`; the code
here leaves placement inside jitted programs to the compiler.

Mesh axes (v5e-pod oriented):
  single pod:  (data=16, model=16)          — 256 chips
  multi-pod:   (pod=2, data=16, model=16)   — 512 chips, 'pod' is pure DP
                                              over DCN (slow links)

Sharding semantics (see repro.dist.sharding):
  'pod','data'  -> batch / FSDP axes
  'model'       -> tensor / expert parallel axis
"""

from __future__ import annotations

from collections.abc import Sequence

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...], *,
              devices: Sequence | None = None) -> Mesh:
    """A mesh of `shape` over `devices` (default: the first devices JAX
    reports), with Auto axis types."""
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model: int = 1) -> Mesh:
    """Mesh over whatever devices exist locally (smoke / examples)."""
    n = jax.device_count()
    assert n % model == 0, (n, model)
    return make_mesh((n // model, model), ("data", "model"))


def describe(mesh: Mesh) -> str:
    return " x ".join(f"{a}={s}" for a, s in
                      zip(mesh.axis_names, mesh.devices.shape))
