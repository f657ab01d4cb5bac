"""shard_map wrappers running attention (incl. the Pallas kernels) per-shard.

Under ``jit`` auto-partitioning XLA cannot see inside a ``pallas_call``, so
the block-sparse kernel would be resolved by gathering its operands onto
every device. MRA-2 attention is *embarrassingly parallel* over (batch,
kv-head): the pyramid, the top-k block selection, and the block-sparse
kernel all act independently per (b, h) slice, and the sequence axis stays
unsharded — so the correct mesh mapping is a ``shard_map`` over

  * batch  -> the data axes ("pod", "data"), and
  * heads  -> the model axis ("model"), kv-head aligned (query heads move
    with their GQA group: q is laid out group-major, Hq = Hkv * G, so
    splitting Hkv over |model| splits Hq into the matching contiguous
    chunks).

Inside the region every path (jnp, Pallas fwd + custom_vjp bwd, and the
fused chunk/decode serving kernel of DESIGN.md §11 — its ``use_kernel`` /
``interpret`` / ``kernel_mode`` fields travel inside the spec dataclass
like every other flag, so the latency and throughput tile shapes both run
per-shard without any code here knowing about them; the in-kernel top-m
selection is per-(batch, kv-head) independent exactly like the rest of
the math) runs its ordinary single-device code on the local shard; no
collectives are needed in the forward, and the backward's grad all-reduce
over the batch axes is the ``shard_map`` transpose of the batch in_specs (a
psum placed by JAX, not by us — see DESIGN.md §8).

Dispatch contract: callers (core/attention.py) route here when
``AttentionSpec.shard`` is set; these functions return ``None`` when no mesh
is active or when the shapes do not divide the mesh axes, and the caller
falls through to the bit-identical single-device path. Divisibility
fallback mirrors distributed/sharding.py: an axis that does not divide is
replicated, never an error.

The speculative draft path (DESIGN.md §10) rides through unchanged: the
coarse-only draft is just an ``AttentionSpec`` with ``coarse_only`` set, and
the spec dataclass travels into the shard_map body verbatim
(``spec.replace(shard=False)`` keeps every other field), so draft decode
steps and chunked verify dispatches run under the same DP×TP mapping as
plain serving — coarse selection and the pyramid background are per-(batch,
kv-head) independent exactly like the budgeted variants. TP spec-engine
parity is pinned in the shard CI tier (tests/test_engine.py).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
from jax.sharding import PartitionSpec as P

from . import mesh_utils

# attention kinds whose per-(batch, kv-head) slices are independent; the
# baselines (never on the production path) are excluded.
SHARDABLE_KINDS = ("full", "mra2", "mra2_s", "local")


def _batch_axes(mesh, batch: int):
    """Data axes that divide ``batch`` (greedy, widest first), possibly ()."""
    dp = mesh_utils.dp_axes(mesh)
    while dp and batch % math.prod(mesh.shape[a] for a in dp) != 0:
        dp = dp[1:]
    return dp


def _head_axis(mesh, kv_heads: int) -> Optional[str]:
    """"model" when the kv-head axis divides it (GQA stays aligned), else None."""
    if not mesh_utils.has_axis(mesh, "model") or mesh.shape["model"] == 1:
        return None
    return "model" if kv_heads % mesh.shape["model"] == 0 else None


def attention_partition(mesh, batch: int, kv_heads: int):
    """(batch_part, head_part) PartitionSpec entries, or None if unshardable.

    Public so callers that pre-place operands (benchmarks, engines) use the
    *same* decision as the shard_map in_specs — a tensor placed by a
    different rule would be resharded on entry.
    """
    dp = _batch_axes(mesh, batch)
    hax = _head_axis(mesh, kv_heads)
    if not dp and hax is None:
        return None
    return (dp if dp else None), hax


def sharded_self_attention(q, k, v, spec, *, causal, key_mask=None):
    """shard_map'd full-sequence attention; None if the mesh can't shard it."""
    mesh = mesh_utils.get_mesh()
    if mesh is None or spec.kind not in SHARDABLE_KINDS:
        return None
    parts = attention_partition(mesh, q.shape[0], k.shape[1])
    if parts is None:
        return None
    bpart, hpart = parts
    s4 = P(bpart, hpart, None, None)
    local_spec = spec.replace(shard=False)

    args = {"q": q, "k": k, "v": v}
    in_specs = {"q": s4, "k": s4, "v": s4}
    if key_mask is not None:
        args["km"] = key_mask
        in_specs["km"] = P(bpart, None)

    def body(a):
        from repro.core.attention import self_attention

        return self_attention(
            a["q"], a["k"], a["v"], local_spec, causal=causal,
            key_mask=a.get("km"),
        )

    return jax.shard_map(
        body, mesh=mesh, in_specs=(in_specs,), out_specs=s4, check_vma=False
    )(args)


def _sharded_kv_attention(q, k_cache, v_cache, lengths, spec, *, q_pos=None,
                          pyramid=None, page_blocks=None, k_scale=None,
                          v_scale=None):
    """Shared shard_map plumbing for attention over the decode state.

    The KV cache, the pyramid block sums, and the int8 dequant scales all
    carry (batch, kv_heads, ...) leading axes, so one (batch -> data,
    kv_heads -> model) mapping covers the whole state; ``lengths``, the ring
    page table (``page_blocks``, shared by every kv head), and the chunk
    query positions (``q_pos``, whose presence selects the chunked-prefill
    callee over single-token decode) shard over batch only. Returns None
    when the mesh can't shard it.
    """
    mesh = mesh_utils.get_mesh()
    if mesh is None or spec.kind not in SHARDABLE_KINDS:
        return None
    parts = attention_partition(mesh, q.shape[0], k_cache.shape[1])
    if parts is None:
        return None
    bpart, hpart = parts
    s4 = P(bpart, hpart, None, None)
    s3 = P(bpart, hpart, None)
    local_spec = spec.replace(shard=False)

    args = {"q": q, "k": k_cache, "v": v_cache, "len": lengths}
    in_specs = {"q": s4, "k": s4, "v": s4, "len": P(bpart)}
    if q_pos is not None:
        args["qp"] = q_pos
        in_specs["qp"] = P(bpart, None)
    if pyramid is not None:
        args["pk"], args["pv"] = pyramid.k_sum, pyramid.v_sum
        in_specs["pk"] = in_specs["pv"] = s4
        if pyramid.upper is not None:
            # H-level hierarchy (DESIGN.md §14): the collapsed-level + tail
            # means carry the same (batch, kv_heads, ...) leading axes as
            # the pyramid; entry counts shard over batch only (shared by
            # every kv head, like the page table).
            args["uk"] = pyramid.upper.k_mean
            args["uv"] = pyramid.upper.v_mean
            args["uc"] = pyramid.upper.counts
            in_specs["uk"] = in_specs["uv"] = s4
            in_specs["uc"] = P(bpart, None)
    if page_blocks is not None:
        args["pb"] = page_blocks
        in_specs["pb"] = P(bpart, None)
    if k_scale is not None:
        args["ks"], args["vs"] = k_scale, v_scale
        in_specs["ks"] = in_specs["vs"] = s3

    def body(a):
        from repro.core.attention import chunk_attention, decode_attention
        from repro.core.hier import HierUpper
        from repro.core.mra_decode import PyramidState

        upper = (HierUpper(a["uk"], a["uv"], a["uc"])
                 if "uk" in a else None)
        pyr = (PyramidState(a["pk"], a["pv"], upper)
               if "pk" in a else None)
        kw = dict(pyramid=pyr, page_blocks=a.get("pb"), k_scale=a.get("ks"),
                  v_scale=a.get("vs"))
        if "qp" in a:
            return chunk_attention(a["q"], a["k"], a["v"], a["len"], a["qp"],
                                   local_spec, **kw)
        return decode_attention(a["q"], a["k"], a["v"], a["len"], local_spec,
                                **kw)

    return jax.shard_map(
        body, mesh=mesh, in_specs=(in_specs,), out_specs=s4, check_vma=False
    )(args)


def sharded_decode_attention(
    q, k_cache, v_cache, lengths, spec, *, pyramid=None, page_blocks=None,
    k_scale=None, v_scale=None
):
    """shard_map'd single-token decode attention (TP serving path)."""
    return _sharded_kv_attention(
        q, k_cache, v_cache, lengths, spec, pyramid=pyramid,
        page_blocks=page_blocks, k_scale=k_scale, v_scale=v_scale)


def sharded_chunk_attention(
    q, k_cache, v_cache, lengths, q_pos, spec, *, pyramid=None,
    page_blocks=None, k_scale=None, v_scale=None
):
    """shard_map'd chunked-prefill attention (serving engine prefill path)."""
    return _sharded_kv_attention(
        q, k_cache, v_cache, lengths, spec, q_pos=q_pos, pyramid=pyramid,
        page_blocks=page_blocks, k_scale=k_scale, v_scale=v_scale)


def sharded_window_attention(q, k_new, v_new, k_cache, v_cache, kv_pos,
                             positions, token_valid, *, window: int, hd: int):
    """shard_map'd sliding-window ring attention (hybrid serving path).

    Same (batch -> data, kv_heads -> model) mapping as the MRA decode state:
    the ring cache and chunk projections are per-(batch, kv-head)
    independent, while ``kv_pos`` (ring entry positions, shared across kv
    heads), ``positions`` and ``token_valid`` shard over batch only. Returns
    None when the mesh can't shard it (caller falls through to the
    bit-identical single-device core).
    """
    mesh = mesh_utils.get_mesh()
    if mesh is None:
        return None
    parts = attention_partition(mesh, q.shape[0], k_cache.shape[1])
    if parts is None:
        return None
    bpart, hpart = parts
    s4 = P(bpart, hpart, None, None)
    s2 = P(bpart, None)

    args = {"q": q, "kn": k_new, "vn": v_new, "kc": k_cache, "vc": v_cache,
            "pc": kv_pos, "pos": positions, "tv": token_valid}
    in_specs = {"q": s4, "kn": s4, "vn": s4, "kc": s4, "vc": s4,
                "pc": s2, "pos": s2, "tv": s2}

    def body(a):
        from repro.models.recurrentgemma import window_attention_core

        return window_attention_core(
            a["q"], a["kn"], a["vn"], a["kc"], a["vc"], a["pc"], a["pos"],
            a["tv"], window=window, hd=hd)

    return jax.shard_map(
        body, mesh=mesh, in_specs=(in_specs,), out_specs=s4, check_vma=False
    )(args)
