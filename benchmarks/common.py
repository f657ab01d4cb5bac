"""Shared benchmark utilities: structured QKV generators, timing, row output."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.mra import full_attention
from repro.launch.device import device_summary

# every row names the device it ran on: a CPU row (interpret-mode kernels
# included) is never a device measurement
CSV_HEADER = "name,us_per_call,derived,device"


def emit_row(name, us, derived) -> None:
    d = device_summary()
    print(f"{name},{us:.1f},{derived},platform={d['platform']} "
          f"kind={d['kind']} count={d['count']}", flush=True)


def structured_qkv(rng, B=1, H=8, N=512, D=64, *, n_clusters=12, locality=0.7,
                   n_global=4, scale=1.0):
    """Q/K/V that produce trained-transformer-like attention (paper Fig. 8):
    banded structure (positional drift), block structure (content clusters),
    and a few global columns. This is the offline stand-in for the paper's
    "Q, K, V from a pretrained model" protocol (§5.1).
    """
    t = np.linspace(0, 6 * np.pi, N)
    drift = np.stack([np.sin(t + p) for p in np.linspace(0, np.pi, D // 2)], -1)
    drift = np.concatenate([drift, np.cos(drift)], -1)[:, :D]  # (N, D)
    centers = rng.standard_normal((n_clusters, D))
    assign = np.sort(rng.integers(0, n_clusters, N))  # contiguous-ish clusters
    content_q = centers[assign] + 0.4 * rng.standard_normal((N, D))
    content_k = centers[assign] + 0.4 * rng.standard_normal((N, D))

    def mix(content):
        out = np.zeros((B, H, N, D), np.float32)
        for b in range(B):
            for h in range(H):
                w = locality * (0.5 + rng.random())
                noise = 0.3 * rng.standard_normal((N, D))
                out[b, h] = (w * drift + (1 - w) * content + noise) * scale
        return out

    q = mix(content_q)
    k = mix(content_k)
    # global tokens: a few keys with large norm attract most queries
    gidx = rng.integers(0, N, n_global)
    k[:, :, gidx] *= 3.0
    v = rng.standard_normal((B, H, N, D)).astype(np.float32)
    return jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)


def rel_error(approx, q, k, v):
    """Paper's metric: ||D^A^V - DAV||_F / ||DAV||_F."""
    ref = full_attention(q, k, v)
    return float(jnp.linalg.norm(approx - ref) / jnp.linalg.norm(ref))


def time_call(fn, *args, iters=3, warmup=1):
    """Median wall time (us) of a jitted call on this host."""
    jfn = jax.jit(fn)
    out = jfn(*args)
    jax.block_until_ready(out)
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = jfn(*args)
        jax.block_until_ready(out)
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts) * 1e6)
