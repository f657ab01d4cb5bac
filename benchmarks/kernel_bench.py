"""Kernel-vs-jnp timing: fwd and fwd+bwd through the MRA-2 attention paths.

Times three routes over the same inputs/selection budget:

  * jnp           — pure gather/scatter path (mra2_attention, no kernel)
  * kernel        — Pallas fwd + fused Pallas bwd
  * kernel_jnpbwd — Pallas fwd + jnp fallback bwd (the dispatch boundary)

plus the serving-side twin (PR 5, DESIGN.md §11): chunk/decode attention
against a KV cache through the fused Pallas serving kernel vs. the pure-jnp
gather path, with the max |out| difference as the online parity check. The
serving kernel is dual-mode (PR 7): ``kernel_mode="auto"`` resolves decode
to latency (single-query) tiles and chunks to throughput (multi-query MXU)
tiles; extra rows force each mode on the chunk shape to price the tile
choice and pin both against the jnp oracle.

The kernels compile for the TPU; off-TPU the run fails unless interpret
mode is asked for explicitly (``benchmarks.run --interpret``), and then the
absolute numbers only demonstrate that the paths execute end-to-end. The
derived column reports the max |grad| difference vs the jnp path (a cheap
online correctness check).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.attention import AttentionSpec, chunk_attention, decode_attention
from repro.core.mra import MraConfig, mra2_attention
from repro.launch.device import kernel_interpret

from .common import structured_qkv, time_call


def run(emit, interpret=False):
    rng = np.random.default_rng(5)
    interpret = kernel_interpret(interpret)
    # interpret mode executes the kernel body per grid step in Python — keep
    # its shape small; TPU runs get a production-ish shape.
    N, H, D, b = (128, 2, 16, 16) if interpret else (512, 4, 64, 32)
    q, k, v = structured_qkv(rng, B=1, H=H, N=N, D=D)

    def cfg(use_kernel, bwd="pallas"):
        return MraConfig(block_size=b, blocks_per_row=4, causal=True,
                         use_kernel=use_kernel, kernel_bwd=bwd,
                         interpret=interpret)

    routes = {
        "jnp": cfg(False),
        "kernel": cfg(True),
        "kernel_jnpbwd": cfg(True, bwd="jnp"),
    }

    def loss_fn(c):
        return lambda q, k, v: jnp.sum(jnp.tanh(mra2_attention(q, k, v, c)))

    grads = {}
    for name, c in routes.items():
        us_f = time_call(lambda q, k, v: mra2_attention(q, k, v, c), q, k, v)
        emit(f"kernel_bench_fwd_{name}", us_f, f"interpret={interpret}")
        gfn = jax.jit(jax.grad(loss_fn(c), argnums=(0, 1, 2)))
        grads[name] = jax.block_until_ready(gfn(q, k, v))  # doubles as warmup
        us_b = time_call(gfn, q, k, v)
        emit(f"kernel_bench_fwdbwd_{name}", us_b, f"interpret={interpret}")

    # online parity check: kernel-route grads vs the jnp path
    for name in ("kernel", "kernel_jnpbwd"):
        diff = max(
            float(jnp.abs(a - b).max())
            for a, b in zip(grads[name], grads["jnp"])
        )
        emit(f"kernel_bench_graddiff_{name}", 0.0, f"{diff:.2e}")

    # ---- serving kernel: chunk/decode attention vs the KV cache (§11) ----- #
    B, Hq, Hkv, S, Dd, bd, C, m = (
        (2, 4, 2, 128, 16, 16, 8, 4) if interpret else
        (4, 8, 2, 2048, 64, 32, 16, 16))
    _, kc, vc = structured_qkv(rng, B=B, H=Hkv, N=S, D=Dd)
    lengths = jnp.full((B,), S, jnp.int32)
    q_pos = jnp.broadcast_to(jnp.arange(S - C, S), (B, C))
    qc = jnp.asarray(rng.standard_normal((B, Hq, C, Dd)), jnp.float32)
    q1 = qc[:, :, :1]
    for route, use_kernel in (("jnp", False), ("kernel", True)):
        spec = AttentionSpec(kind="mra2", block_size=bd, decode_blocks=m,
                             use_kernel=use_kernel, interpret=interpret)
        us = time_call(
            lambda q: decode_attention(q, kc, vc, lengths, spec), q1)
        emit(f"kernel_bench_decode_{route}", us, f"interpret={interpret}")
        us = time_call(
            lambda q: chunk_attention(q, kc, vc, lengths, q_pos, spec), qc)
        emit(f"kernel_bench_chunk_c{C}_{route}", us, f"interpret={interpret}")
    spec_j = AttentionSpec(kind="mra2", block_size=bd, decode_blocks=m)
    spec_k = spec_j.replace(use_kernel=True, interpret=interpret)
    diff = float(jnp.abs(
        chunk_attention(qc, kc, vc, lengths, q_pos, spec_k)
        - chunk_attention(qc, kc, vc, lengths, q_pos, spec_j)).max())
    emit("kernel_bench_chunk_outdiff_kernel", 0.0, f"{diff:.2e}")

    # forced tile modes (DESIGN.md §11): "auto" resolves decode to latency
    # tiles and chunks to throughput tiles, so the rows above already time
    # the production pairing. Forcing the off-diagonal — a C-token chunk
    # through latency (single-query) tiles — prices the MXU-shaped tile
    # against C single-row dispatch steps and pins both modes to the jnp
    # oracle on the same inputs.
    ref = chunk_attention(qc, kc, vc, lengths, q_pos, spec_j)
    for mode in ("latency", "throughput"):
        spec_m = spec_k.replace(kernel_mode=mode)
        us = time_call(
            lambda q: chunk_attention(q, kc, vc, lengths, q_pos, spec_m), qc)
        diff = float(jnp.abs(
            chunk_attention(qc, kc, vc, lengths, q_pos, spec_m) - ref).max())
        emit(f"kernel_bench_chunk_c{C}_kernel_{mode}", us,
             f"interpret={interpret} outdiff={diff:.2e}")
