"""Production meshes. Functions (not module constants) so importing this
module never touches jax device state (DESIGN.md / dry-run contract)."""
from __future__ import annotations

from typing import Optional

import jax


def _make_mesh(shape, axes):
    # Auto axes: shardings propagate through jit and shard_map opens the
    # manual regions (DESIGN.md §8)
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_local_mesh(n_data: int = 1, n_model: int = 1):
    """Small mesh over however many (possibly host) devices are available."""
    return _make_mesh((n_data, n_model), ("data", "model"))


def parse_mesh(spec: Optional[str]):
    """Parse a ``--mesh`` flag into a (data, model) mesh, or None.

    Accepted forms: ``"1"``/``""``/None (single device, no mesh), ``"4"``
    (data=4, model=1), ``"2x4"`` (data=2, model=4). The total must not
    exceed ``jax.device_count()`` — use
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` to fake host
    devices for CPU validation.
    """
    if not spec or spec == "1":
        return None
    parts = spec.lower().split("x")
    if len(parts) == 1:
        n_data, n_model = int(parts[0]), 1
    elif len(parts) == 2:
        n_data, n_model = int(parts[0]), int(parts[1])
    else:
        raise ValueError(f"bad mesh spec {spec!r}; expected 'D' or 'DxM'")
    if n_data * n_model == 1:
        return None
    avail = jax.device_count()
    if n_data * n_model > avail:
        raise ValueError(
            f"mesh {spec!r} needs {n_data * n_model} devices, have {avail} "
            "(set XLA_FLAGS=--xla_force_host_platform_device_count=N)"
        )
    return make_local_mesh(n_data, n_model)
