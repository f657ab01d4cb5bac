"""Current-mesh context so model code can open shard_map regions.

The launcher (train/serve/dryrun) sets the active mesh; layers that need
explicit collectives (MoE expert parallelism, sequence-parallel decode) read
it. Without an active mesh every layer runs its pure-local path — that is
what CPU unit tests use.
"""
from __future__ import annotations

import contextlib
from typing import Optional

from jax.sharding import Mesh

__all__ = ["get_mesh", "use_mesh", "dp_axes", "has_axis"]

_CURRENT: list[Optional[Mesh]] = [None]


def get_mesh() -> Optional[Mesh]:
    return _CURRENT[0]


@contextlib.contextmanager
def use_mesh(mesh: Optional[Mesh]):
    prev = _CURRENT[0]
    _CURRENT[0] = mesh
    try:
        if mesh is not None:
            with mesh:
                yield mesh
        else:
            yield None
    finally:
        _CURRENT[0] = prev


def dp_axes(mesh: Mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def has_axis(mesh: Optional[Mesh], name: str) -> bool:
    return mesh is not None and name in mesh.shape
