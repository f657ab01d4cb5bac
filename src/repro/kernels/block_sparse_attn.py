"""Pallas TPU kernels: data-dependent block-sparse attention, fwd + bwd.

This is the TPU-native replacement for the paper's custom CUDA block-sparsity
kernels (paper §6: "Overcoming this limitation required implementing custom
CUDA kernels for some generic block sparsity operators").

Design (DESIGN.md §3):
  * Selected (query-block, key-block) index pairs live in SMEM via
    ``PrefetchScalarGridSpec`` — the BlockSpec ``index_map`` performs the
    data-dependent HBM→VMEM DMA, replacing CUDA thread-level gathers.
  * The grid is ``(rows, pairs)``; the wrapper sorts block pairs by the block
    id that addresses the *output* tile (query block for fwd/dq, key block
    for dk/dv) so revisits of the same tile are consecutive — Pallas keeps
    the accumulator tiles resident in VMEM between consecutive grid steps
    that map to the same block (the sequential-grid equivalent of CUDA
    atomics).
  * Flash-style online softmax: the forward keeps a per-token running max
    ``mt`` (seeded with the coarse background max ``c`` as a floor) and
    rescales the resident numerator/row-sum tiles when a new block raises
    it. Attention weights never exceed exp(0) = 1, so neither the forward
    nor the recompute backward can overflow fp32 — the property that makes
    the kernel trainable. ``mt`` is emitted so the caller can align the
    MRA-2 coarse background with the exact same per-token stabilizer the
    pure-jnp path uses (core/mra.py); it is gradient-transparent by
    contract (stabilizers cancel in the normalized output).
  * GQA without KV expansion: K/V are indexed at ``bhg // group`` in the
    ``index_map`` so grouped query heads share the KV tiles in HBM. The
    backward dk/dv kernel instead flattens each KV head's G groups of pairs
    into one sorted-by-key-block list, so the G-way gradient reduction is a
    by-product of the same resident-tile accumulation.
  * Key-padding masks ride along as a per-key-block (1, b) VMEM tile, so
    ``use_kernel=True`` serves arbitrary (padded) sequence lengths.
  * The backward is a flash-style recompute: no O(m·b²) attention weights
    are saved; both bwd kernels rebuild ``a = mask·exp(qk·scale − mt)``
    from the forward residuals inside the kernel.
  * fp32 accumulation regardless of input dtype (MXU-native
    ``preferred_element_type``).

Forward outputs are the *unnormalized* block-sparse numerator, the row sums,
and the per-token stabilizer; the caller divides (and adds the MRA-2 coarse
background) outside.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.core.mra import NEG_INF  # shared finite "minus infinity" sentinel


def _block_mask(flags, km, b):
    """(b, b) boolean mask for one score tile.

    flags bit0: pair valid; bit1: causal triangular mask (diagonal block).
    km (b,) fp32 > 0 marks valid keys (columns).
    """
    valid = (flags & 1) == 1
    diag = (flags & 2) == 2
    rows = jax.lax.broadcasted_iota(jnp.int32, (b, b), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (b, b), 1)
    # pure boolean algebra: Mosaic has no select over i1 vectors
    mask = ((rows >= cols) | ~diag) & valid
    return mask & (km > 0)[None, :]


def _rows(x, b):
    """(R, n) -> (R, n // b, 1, b): one (1, b) row per block. A (1, 1, 1, b)
    block then has last two dims equal to the array's, as Mosaic's tiling
    requires of every block."""
    return x.reshape(x.shape[0], x.shape[1] // b, 1, b)


def _dot(a, b_, dims):
    return jax.lax.dot_general(a, b_, (dims, ((), ())),
                               preferred_element_type=jnp.float32)


def _recompute_weights(q_ref, k_ref, mt_ref, flags, km_ref, scale, b):
    """Backward-pass recompute of a = mask·exp(s − mt) for one block pair.

    mt is the forward's final per-token stabilizer, an upper bound of every
    visited score, so the exp argument is ≤ 0 — weights cannot overflow.
    """
    q = q_ref[0].astype(jnp.float32)
    k = k_ref[0].astype(jnp.float32)
    s = _dot(q, k, ((1,), (1,))) * scale - mt_ref[0, 0, 0][:, None]
    mask = _block_mask(flags, km_ref[0, 0, 0], b)
    return jnp.where(mask, jnp.exp(jnp.minimum(s, 0.0)), 0.0), q, k


# --------------------------------------------------------------------------- #
# Forward
# --------------------------------------------------------------------------- #
def _fwd_kernel(
    # scalar prefetch (SMEM)
    x_idx_ref,  # (BHG, m) query-block ids, sorted per bhg
    y_idx_ref,  # (BHG, m) key-block ids
    first_ref,  # (BHG, m) 1 when this grid step first visits its output tile
    flags_ref,  # (BHG, m) bit0: block valid; bit1: apply causal tri mask
    # VMEM tiles
    q_ref,  # (1, b, d)
    k_ref,  # (1, b, d)
    v_ref,  # (1, b, d)
    c_ref,  # (1, 1, 1, 1) stabilizer floor for this query block (coarse bg max)
    km_ref,  # (1, 1, 1, b) key validity for this key block
    o_ref,  # (1, b, d) accumulated numerator (stabilized by mt)
    r_ref,  # (1, 1, 1, b) accumulated row sums
    mt_ref,  # (1, 1, 1, b) running per-token max stabilizer
    *,
    scale: float,
    block_size: int,
):
    bhg = pl.program_id(0)
    i = pl.program_id(1)
    b = block_size

    @pl.when(first_ref[bhg, i] == 1)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
        r_ref[...] = jnp.zeros_like(r_ref)
        mt_ref[...] = jnp.zeros_like(mt_ref) + c_ref[0, 0, 0, 0]

    q = q_ref[0].astype(jnp.float32)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    s = _dot(q, k, ((1,), (1,))) * scale
    mask = _block_mask(flags_ref[bhg, i], km_ref[0, 0, 0], b)

    # online rescale (flash-attention): raise the running max, shrink the
    # resident accumulators, then add this block at the new stabilizer.
    m_old = mt_ref[0, 0, 0]
    m_new = jnp.maximum(m_old, jnp.max(jnp.where(mask, s, NEG_INF), axis=1))
    alpha = jnp.exp(m_old - m_new)  # ≤ 1
    # valid entries have s ≤ m_new by construction; the min guards the
    # masked lanes from computing exp(+large) → inf before the where
    a = jnp.where(mask, jnp.exp(jnp.minimum(s - m_new[:, None], 0.0)), 0.0)

    o_ref[0] = o_ref[0] * alpha[:, None] + _dot(a, v, ((1,), (0,)))
    r_ref[0, 0, 0] = r_ref[0, 0, 0] * alpha + jnp.sum(a, axis=1)
    mt_ref[0, 0, 0] = m_new


def block_sparse_attention_fwd(
    q: jax.Array,  # (BHG, n, d)
    k: jax.Array,  # (BHKV, n, d)
    v: jax.Array,  # (BHKV, n, d)
    x_idx: jax.Array,  # (BHG, m) int32, sorted ascending per row
    y_idx: jax.Array,  # (BHG, m) int32
    first: jax.Array,  # (BHG, m) int32 first-visit flags
    flags: jax.Array,  # (BHG, m) int32 bit0 valid, bit1 causal-diag
    c: jax.Array,  # (BHG, nb) fp32 stabilizer floor (> NEG_INF/2 clamped)
    km: jax.Array,  # (BHKV, n) fp32, >0 = valid key
    *,
    scale: float,
    block_size: int,
    interpret: bool = False,
):
    BHG, n, d = q.shape
    BHKV = k.shape[0]
    group = BHG // BHKV
    m = x_idx.shape[1]
    b = block_size

    nb = n // b

    kernel = functools.partial(_fwd_kernel, scale=scale, block_size=b)
    out_shapes = (
        jax.ShapeDtypeStruct((BHG, n, d), jnp.float32),
        jax.ShapeDtypeStruct((BHG, nb, 1, b), jnp.float32),
        jax.ShapeDtypeStruct((BHG, nb, 1, b), jnp.float32),
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(BHG, m),
        in_specs=[
            pl.BlockSpec((1, b, d), lambda bhg, i, xi, yi, fi, fl: (bhg, xi[bhg, i], 0)),
            pl.BlockSpec((1, b, d), lambda bhg, i, xi, yi, fi, fl: (bhg // group, yi[bhg, i], 0)),
            pl.BlockSpec((1, b, d), lambda bhg, i, xi, yi, fi, fl: (bhg // group, yi[bhg, i], 0)),
            pl.BlockSpec((1, 1, 1, 1), lambda bhg, i, xi, yi, fi, fl: (bhg, xi[bhg, i], 0, 0)),
            pl.BlockSpec((1, 1, 1, b), lambda bhg, i, xi, yi, fi, fl: (bhg // group, yi[bhg, i], 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, b, d), lambda bhg, i, xi, yi, fi, fl: (bhg, xi[bhg, i], 0)),
            pl.BlockSpec((1, 1, 1, b), lambda bhg, i, xi, yi, fi, fl: (bhg, xi[bhg, i], 0, 0)),
            pl.BlockSpec((1, 1, 1, b), lambda bhg, i, xi, yi, fi, fl: (bhg, xi[bhg, i], 0, 0)),
        ],
    )
    out, rowsum, mt = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shapes,
        interpret=interpret,
    )(x_idx, y_idx, first, flags, q, k, v, c.reshape(BHG, nb, 1, 1),
      _rows(km, b))
    return out, rowsum.reshape(BHG, n), mt.reshape(BHG, n)


# --------------------------------------------------------------------------- #
# Backward, kernel 1: dq (pairs sorted by query block)
# --------------------------------------------------------------------------- #
def _bwd_dq_kernel(
    x_idx_ref, y_idx_ref, first_ref, flags_ref,  # SMEM, all (BHG, M1)
    q_ref,   # (1, b, d)
    k_ref,   # (1, b, d)
    v_ref,   # (1, b, d)
    mt_ref,  # (1, 1, 1, b) forward per-token stabilizer for this query block
    do_ref,  # (1, b, d) numerator cotangent tile
    dr_ref,  # (1, 1, 1, b) row-sum cotangent tile
    km_ref,  # (1, 1, 1, b)
    dq_ref,  # (1, b, d) out
    *,
    scale: float,
    block_size: int,
):
    bhg = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(first_ref[bhg, i] == 1)
    def _init():
        dq_ref[...] = jnp.zeros_like(dq_ref)

    a, _, k = _recompute_weights(
        q_ref, k_ref, mt_ref, flags_ref[bhg, i], km_ref, scale, block_size
    )
    do = do_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    # da[i,j] = <do_i, v_j> + dr_i ; ds = a ⊙ da  (softmax-free: the
    # normalization lives outside the kernel contract)
    ds = a * (_dot(do, v, ((1,), (1,))) + dr_ref[0, 0, 0][:, None])
    dq_ref[0] += _dot(ds, k, ((1,), (0,))) * scale


# --------------------------------------------------------------------------- #
# Backward, kernel 2: dk + dv (pairs flattened per KV head, sorted by key
# block; the G-way GQA reduction happens via consecutive accumulation)
# --------------------------------------------------------------------------- #
def _bwd_dkv_kernel(
    row_ref, x_idx_ref, y_idx_ref, first_ref, flags_ref,  # SMEM, all (BHKV, M2)
    q_ref,   # (1, b, d) query block of the owning BHG row
    k_ref,   # (1, b, d)
    v_ref,   # (1, b, d)
    mt_ref,  # (1, 1, 1, b)
    do_ref,  # (1, b, d)
    dr_ref,  # (1, 1, 1, b)
    km_ref,  # (1, 1, 1, b)
    dk_ref,  # (1, b, d) out
    dv_ref,  # (1, b, d) out
    *,
    scale: float,
    block_size: int,
):
    kv = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(first_ref[kv, i] == 1)
    def _init():
        dk_ref[...] = jnp.zeros_like(dk_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)

    a, q, _ = _recompute_weights(
        q_ref, k_ref, mt_ref, flags_ref[kv, i], km_ref, scale, block_size
    )
    do = do_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    ds = a * (_dot(do, v, ((1,), (1,))) + dr_ref[0, 0, 0][:, None])

    dk_ref[0] += _dot(ds, q, ((0,), (0,))) * scale  # ds^T q
    dv_ref[0] += _dot(a, do, ((0,), (0,)))  # a^T do


def block_sparse_attention_bwd(
    q: jax.Array,  # (BHG, n, d)
    k: jax.Array,  # (BHKV, n, d)
    v: jax.Array,  # (BHKV, n, d)
    mt: jax.Array,  # (BHG, n) forward per-token stabilizer
    do: jax.Array,  # (BHG, n, d)
    dr: jax.Array,  # (BHG, n)
    km: jax.Array,  # (BHKV, n) fp32
    # pairs sorted by query block (dq pass), (BHG, M1) each
    xq: jax.Array, yq: jax.Array, firstq: jax.Array, flagsq: jax.Array,
    # pairs flattened per KV head and sorted by key block (dk/dv pass),
    # (BHKV, M2) each; rowk[kv, i] is the owning BHG row of pair i
    rowk: jax.Array, xk: jax.Array, yk: jax.Array, firstk: jax.Array,
    flagsk: jax.Array,
    *,
    scale: float,
    block_size: int,
    interpret: bool = False,
):
    """Fused backward: (dq, dk, dv), all fp32.

    The stabilizer is gradient-transparent (DESIGN.md §3): dc ≡ 0 by the
    kernel contract, so no dc pass exists.

    Contract: every query block id must appear in ``xq`` and every key block
    id in ``yk`` at least once per row (invalid pairs count) — unvisited
    output tiles are never initialized. ``ops._bwd`` guarantees this by
    padding the pair list with one invalid pair per block id.
    """
    BHG, n, d = q.shape
    BHKV = k.shape[0]
    group = BHG // BHKV
    b = block_size
    M1 = xq.shape[1]
    M2 = xk.shape[1]
    mt, dr, km = _rows(mt, b), _rows(dr, b), _rows(km, b)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, block_size=b),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(BHG, M1),
            in_specs=[
                pl.BlockSpec((1, b, d), lambda g, i, xi, yi, fi, fl: (g, xi[g, i], 0)),
                pl.BlockSpec((1, b, d), lambda g, i, xi, yi, fi, fl: (g // group, yi[g, i], 0)),
                pl.BlockSpec((1, b, d), lambda g, i, xi, yi, fi, fl: (g // group, yi[g, i], 0)),
                pl.BlockSpec((1, 1, 1, b), lambda g, i, xi, yi, fi, fl: (g, xi[g, i], 0, 0)),
                pl.BlockSpec((1, b, d), lambda g, i, xi, yi, fi, fl: (g, xi[g, i], 0)),
                pl.BlockSpec((1, 1, 1, b), lambda g, i, xi, yi, fi, fl: (g, xi[g, i], 0, 0)),
                pl.BlockSpec((1, 1, 1, b), lambda g, i, xi, yi, fi, fl: (g // group, yi[g, i], 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, b, d), lambda g, i, xi, yi, fi, fl: (g, xi[g, i], 0)),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct((BHG, n, d), jnp.float32)],
        interpret=interpret,
    )(xq, yq, firstq, flagsq, q, k, v, mt, do, dr, km)[0]

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, block_size=b),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(BHKV, M2),
            in_specs=[
                pl.BlockSpec((1, b, d), lambda kv, i, ro, xi, yi, fi, fl: (ro[kv, i], xi[kv, i], 0)),
                pl.BlockSpec((1, b, d), lambda kv, i, ro, xi, yi, fi, fl: (kv, yi[kv, i], 0)),
                pl.BlockSpec((1, b, d), lambda kv, i, ro, xi, yi, fi, fl: (kv, yi[kv, i], 0)),
                pl.BlockSpec((1, 1, 1, b), lambda kv, i, ro, xi, yi, fi, fl: (ro[kv, i], xi[kv, i], 0, 0)),
                pl.BlockSpec((1, b, d), lambda kv, i, ro, xi, yi, fi, fl: (ro[kv, i], xi[kv, i], 0)),
                pl.BlockSpec((1, 1, 1, b), lambda kv, i, ro, xi, yi, fi, fl: (ro[kv, i], xi[kv, i], 0, 0)),
                pl.BlockSpec((1, 1, 1, b), lambda kv, i, ro, xi, yi, fi, fl: (kv, yi[kv, i], 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, b, d), lambda kv, i, ro, xi, yi, fi, fl: (kv, yi[kv, i], 0)),
                pl.BlockSpec((1, b, d), lambda kv, i, ro, xi, yi, fi, fl: (kv, yi[kv, i], 0)),
            ],
        ),
        out_shape=(
            jax.ShapeDtypeStruct((BHKV, n, d), jnp.float32),
            jax.ShapeDtypeStruct((BHKV, n, d), jnp.float32),
        ),
        interpret=interpret,
    )(rowk, xk, yk, firstk, flagsk, q, k, v, mt, do, dr, km)

    return dq, dk, dv
