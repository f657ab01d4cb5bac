"""Beyond-paper: MRA decode (top-k KV-block selection) quality + cost.

Per decoded token, MRA decode reads O(S/b + m*b) of the KV cache instead of
O(S). This benchmark sweeps the exact-block budget m and reports the
attention-output error vs exact decode, plus host wall-time.

Mesh-aware: under an active mesh (``benchmarks/run.py --mesh DxM``, or this
module's own ``--mesh`` flag when run standalone) the query/cache tensors
are placed batch-over-data / kv-heads-over-model and the attention runs
through the shard_map TP decode path (distributed/shard_attn.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.attention import AttentionSpec, decode_attention
from repro.distributed import mesh_utils
from repro.distributed.shard_attn import attention_partition
from repro.launch.device import kernel_interpret

from .common import CSV_HEADER, emit_row, structured_qkv, time_call


def run(emit, interpret=False):
    interpret = kernel_interpret(interpret)
    rng = np.random.default_rng(3)
    B, Hq, Hkv, S, D, b = 4, 8, 2, 4096, 64, 32
    _, k, v = structured_qkv(rng, B=B, H=Hkv, N=S, D=D)
    q = jnp.asarray(rng.standard_normal((B, Hq, 1, D)), jnp.float32)
    lengths = jnp.full((B,), S, jnp.int32)

    mesh = mesh_utils.get_mesh()
    shard = mesh is not None
    if shard:
        # place operands with the exact partition the shard_map in_specs will
        # use (distributed/shard_attn.py) — any other rule means a reshard on
        # entry and the benchmark would time data movement, not attention.
        parts = attention_partition(mesh, B, Hkv)
        if parts is not None:
            bpart, hpart = parts
            s4 = NamedSharding(mesh, P(bpart, hpart, None, None))
            q = jax.device_put(q, s4)
            k = jax.device_put(k, s4)
            v = jax.device_put(v, s4)
            lengths = jax.device_put(lengths, NamedSharding(mesh, P(bpart)))

    full_spec = AttentionSpec(kind="full", shard=shard)
    ref = decode_attention(q, k, v, lengths, full_spec)
    for m in (4, 16, 64):
        spec = AttentionSpec(kind="mra2", block_size=b, decode_blocks=m,
                             shard=shard)
        out = decode_attention(q, k, v, lengths, spec)
        err = float(jnp.linalg.norm(out - ref) / jnp.linalg.norm(ref))
        us = time_call(
            lambda q, k, v: decode_attention(q, k, v, lengths, spec), q, k, v)
        emit(f"mra_decode_s4096_m{m}", us, f"{err:.4f}")
    us = time_call(
        lambda q, k, v: decode_attention(q, k, v, lengths, full_spec), q, k, v)
    emit("full_decode_s4096", us, "0.0000")

    # ring-paged decode (DESIGN.md §9): a 6144-token stream served through the
    # 4096-token ring — live window is blocks nb/2 .. 3nb/2-1, with the newer
    # half wrapped onto pages 0..nb/2-1 (ring layout). Conformance: must match
    # the same window laid out contiguously (rebased); derived = that error.
    nb = S // b
    spec = AttentionSpec(kind="mra2", block_size=b, decode_blocks=16,
                         shard=shard)
    lengths2 = jnp.full((B,), S + S // 2, jnp.int32)
    blocks_contig = jnp.arange(nb, dtype=jnp.int32) + nb // 2  # ascending
    pb_contig = jnp.broadcast_to(blocks_contig[None], (B, nb))
    # ring placement: block y lives at page y % nb -> roll the contiguous
    # layout by half a ring
    pb_ring = jnp.roll(pb_contig, nb // 2, axis=1)
    k_ring = jnp.roll(k, (nb // 2) * b, axis=2)
    v_ring = jnp.roll(v, (nb // 2) * b, axis=2)
    if shard:
        parts = attention_partition(mesh, B, Hkv)
        if parts is not None:
            bpart = parts[0]
            pb_contig = jax.device_put(pb_contig, NamedSharding(mesh, P(bpart, None)))
            pb_ring = jax.device_put(pb_ring, NamedSharding(mesh, P(bpart, None)))
            k_ring = jax.device_put(k_ring, s4)
            v_ring = jax.device_put(v_ring, s4)
    ref2 = decode_attention(q, k, v, lengths2, spec, page_blocks=pb_contig)
    out2 = decode_attention(q, k_ring, v_ring, lengths2, spec,
                            page_blocks=pb_ring)
    err = float(jnp.abs(out2 - ref2).max())
    us = time_call(
        lambda q, k_ring, v_ring: decode_attention(
            q, k_ring, v_ring, lengths2, spec, page_blocks=pb_ring),
        q, k_ring, v_ring)
    emit("mra_decode_paged_ring_s4096", us, f"{err:.6f}")

    # fused Pallas serving kernel rows (DESIGN.md §11): same selection, the
    # gather + two-level softmax + background + normalize fused on-chip.
    # Under --interpret the absolute time only proves the path runs
    # end-to-end. The derived column doubles as the online parity check vs
    # the jnp rows.
    kspec = AttentionSpec(kind="mra2", block_size=b, decode_blocks=16,
                          use_kernel=True, interpret=interpret, shard=shard)
    out_k = decode_attention(q, k, v, lengths, kspec)
    err = float(jnp.linalg.norm(out_k - ref) / jnp.linalg.norm(ref))
    us = time_call(
        lambda q, k, v: decode_attention(q, k, v, lengths, kspec), q, k, v)
    emit("mra_decode_s4096_m16_kernel", us, f"{err:.4f}")
    out2k = decode_attention(q, k_ring, v_ring, lengths2, kspec,
                             page_blocks=pb_ring)
    err = float(jnp.abs(out2k - ref2).max())
    us = time_call(
        lambda q, k_ring, v_ring: decode_attention(
            q, k_ring, v_ring, lengths2, kspec, page_blocks=pb_ring),
        q, k_ring, v_ring)
    emit("mra_decode_paged_ring_s4096_kernel", us, f"{err:.6f}")


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", default="1",
                    help="device mesh 'D' or 'DxM' (default: 1 = no mesh)")
    ap.add_argument("--interpret", action="store_true",
                    help="run the Pallas kernel rows in interpret mode "
                         "(required off-TPU)")
    args = ap.parse_args()

    from repro.launch.mesh import parse_mesh

    print(CSV_HEADER)
    with mesh_utils.use_mesh(parse_mesh(args.mesh)):
        run(emit_row, interpret=args.interpret)


if __name__ == "__main__":
    main()
