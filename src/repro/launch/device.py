"""Process-level device setup shared by every entry point.

``enable_compile_cache`` gives JAX one persistent compilation cache per
checkout; ``kernel_interpret`` turns a caller's explicit interpret choice into
the flag the Pallas kernels take, refusing to fall back to interpret mode on
a host without a TPU; ``device_summary`` names the devices a result came from.
"""
from __future__ import annotations

import os

import jax

# fixed, in-checkout default: the cache key includes the path, so a directory
# that moved between runs would never hit
_DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..", ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and wins;
    otherwise the cache lives at ``<checkout>/.jax_cache`` (gitignored).
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.normpath(_DEFAULT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def kernel_interpret(interpret: bool) -> bool:
    """The ``interpret`` flag for the Pallas kernels of a run.

    Interpret mode is only ever an explicit choice (``--interpret`` flags,
    tests). Without it the kernels compile for the chip, so a host without a
    TPU is an error here rather than a silent switch to the interpreter.
    """
    platform = jax.devices()[0].platform
    if not interpret and platform != "tpu":
        raise SystemExit(
            f"the Pallas kernels need a TPU, found {platform!r}; pass "
            "--interpret to run them in interpret mode instead")
    return interpret


def device_summary() -> dict:
    """Platform, kind and count of the devices JAX sees."""
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
