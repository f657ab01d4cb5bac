"""Pallas TPU kernel: ring-paged chunk/decode MRA attention for serving.

This is the serving-side twin of the training kernels in
``block_sparse_attn.py`` (DESIGN.md §11). Everything after the shared page
statistics — coarse page scoring, the causal block mask, own-block force
selection, top-m selection, the gathered exact term, the coarse pyramid
background and the final normalization — runs *inside one kernel*:

  * in-kernel selection — the coarse scores ``q · k̄_y · scale`` are an
    MXU matmul against the resident ``k_ds`` page-means tile, so the
    ``(B, Hkv, G, C, nb)`` coarse-score tensor never exists in HBM and the
    separate ``jax.lax.top_k`` pass disappears. Top-m is m static rounds of
    (row-max, lowest-column-among-ties) — exactly ``jax.lax.top_k``'s
    first-index tie-break — masked to the *valid* pages
    (live ∧ causally allowed); the query's own live block is force-selected
    via the shared FORCE_BONUS, matching the jnp oracle bit-for-bit in
    which pages get selected.
  * MXU-shaped tiles — the grid is ``(B·Hkv, C/C_tile)``: each step scores a
    ``(G·C_tile, b)`` tile per page and a ``(G·C_tile, nb)`` coarse tile,
    real matmuls instead of the old single-query-row dots.
  * gather by manual DMA — selected K/V pages are copied HBM→VMEM with
    ``pltpu.make_async_copy`` from ``ANY``-space cache refs, one
    ``pl.when``-guarded fetch per page in the selection union, fused with a
    flash-style online softmax (running per-row max, rescaled accumulators)
    and the exact ``pos_k <= q_pos`` mask. No ``(…, m, b, D)`` gather tensor
    ever reaches HBM. int8 pages apply their per-token scale rows to the
    page's scores and softmax weights (``s·ks``, ``(a·vs) @ v``).
  * background + normalize — the coarse background
    ``Σ_bg exp(μ − c)·count_y · v̄_y`` is a ``(rows, nb) @ (nb, D)`` matmul
    against the resident ``v_ds`` tile, aligned onto the two-level
    stabilizer ``c_tok = max(c, fine_max)``; the normalized output is
    emitted directly (all-masked rows → exact zeros).
  * H-level far field (DESIGN.md §14) — when the cache is hierarchical
    (``levels >= 3``), the collapsed-level + tail means arrive as two more
    resident ``(NU, D)`` tiles with an (NU,) count row; the fold is one
    extra ``(rows, NU)`` score matmul + ``(rows, NU) @ (NU, D)`` background
    matmul inside the same stabilizer. Selection stays in-kernel and
    untouched — the hierarchy only widens the background. At levels == 2
    the operands are static dummies and the fold is compiled out, keeping
    the two-level program identical.

Dual mode (DESIGN.md §11): the same body is instantiated at two static
query-tile widths, selected per dispatch —

  * ``latency``    — C_tile = 1: one wave per (batch·kv-head) row; minimal
    work per step, the decode (C == 1) shape.
  * ``throughput`` — C_tile = min(C, 8): multi-query tiles for verify
    chunks and chunked prefill; the MXU sees (G·C_tile, ·) operands.

``mode="auto"`` resolves at trace time (C == 1 → latency, else throughput),
which is how the engine picks per dispatch: decode waves trace with C == 1,
prefill/verify chunks with C == chunk. ``EngineConfig.kernel_mode`` forces
one mode for every dispatch. Ragged chunks (C not a multiple of C_tile) are
padded with ``q_pos = -1`` rows, which select nothing and are sliced off.

Forward-only by design: the serving path is never differentiated (training
uses the §3 kernels). Differentiating through this op raises at trace time.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.core.mra import NEG_INF, FORCE_BONUS

KERNEL_MODES = ("auto", "latency", "throughput")
THROUGHPUT_C_TILE = 8  # query-tile width of the throughput instantiation
# removal sentinel for already-picked selection entries: strictly below
# NEG_INF so a picked page can never win a later round, and below any
# masked-off score so exhausted rows keep re-picking an already-dead column
_PICKED = -2e9


def resolve_kernel_mode(mode: str, C: int) -> str:
    """'auto' → latency for single-query (decode) traces, else throughput."""
    if mode not in KERNEL_MODES:
        raise ValueError(
            f"kernel_mode must be one of {KERNEL_MODES}, got {mode!r}")
    if mode == "auto":
        return "latency" if C == 1 else "throughput"
    return mode


def _dot(a, b_, dims):
    return jax.lax.dot_general(a, b_, (dims, ((), ())),
                               preferred_element_type=jnp.float32)


def _chunk_kernel(
    # VMEM tiles
    q_ref,       # (1, G, Ct, D) query tile (fp32); (1, Ct, G, D) in latency
    qpos_ref,    # (1, G, Ct, 1) int32 global positions (-1 = padded row),
                 # laid out like q_ref
    kds_ref,     # (1, nb, D) per-page K means (coarse scoring keys)
    vds_ref,     # (1, nb, D) per-page V means (coarse background values)
    counts_ref,  # (1, 1, nb) f32 valid tokens per page
    pb_ref,      # (1, 1, nb) int32 page table row (logical block, -1 dead)
    hk_ref,      # (1, NU, D) f32 collapsed-level + tail K means (§14);
                 # (1, 1, D) zero dummy when with_upper is False
    hv_ref,      # (1, NU, D) f32 collapsed-level + tail V means
    hcnt_ref,    # (1, 1, NU) f32 per-entry token counts (0 = dead entry)
    # ANY-space refs (manual DMA sources)
    k_any,       # (BKV, nb, b, D) cache dtype
    v_any,       # (BKV, nb, b, D)
    ks_any,      # (BKV, nb, 1, b) f32 dequant scales ((1,1,1,1) dummy)
    vs_any,      # (BKV, nb, 1, b)
    # output
    o_ref,       # (1, G, Ct, D) f32, laid out like q_ref
    # scratch
    kpage,       # (b, D) VMEM landing pad for one K page
    vpage,       # (b, D)
    kspage,      # (1, b) per-token K scales for the page
    vspage,      # (1, b)
    sems,        # (4,) DMA semaphores
    acc_ref,     # (rows, D) f32 online-softmax numerator
    rs_ref,      # (rows, 1) f32 row sum
    mt_ref,      # (rows, 1) f32 running fine-score max
    *,
    scale: float,
    block_size: int,
    m: int,
    quant: bool,
    include_bg: bool,
    with_upper: bool,
):
    r = pl.program_id(0)
    b = block_size
    # rows are (G, Ct) or (Ct, G) flattened: every row is an independent
    # query, so the order only has to agree between q, qpos and the output
    _, t1, t2, D = q_ref.shape
    nb = kds_ref.shape[1]
    rows = t1 * t2

    q = q_ref[0].reshape(rows, D)                 # fp32 query tile
    qp = qpos_ref[0].reshape(rows, 1)             # int32, lane dim kept
    kds = kds_ref[0]                              # (nb, D)
    pbrow = pb_ref[0]                             # (1, nb)
    cnt = counts_ref[0]                           # (1, nb)

    # ---- in-kernel coarse scores + causal/validity masks -------------------
    coarse = _dot(q, kds, ((1,), (1,))) * scale   # (rows, nb) — MXU matmul
    jq = qp // b                                  # query block (−1 for pads)
    live = cnt > 0.0
    allowed = live & (pbrow <= jq)                # live past+own pages
    ownl = (pbrow == jq) & (pbrow >= 0) & live    # query's own live block
    # a page is a valid exact-attention target iff causally allowed and live
    # (own ⊆ allowed when live); dead own blocks are NOT force-selected —
    # the selection-validity contract shared with the jnp oracle.
    coarse_m = jnp.where(allowed, coarse, NEG_INF)
    selsc = coarse_m + FORCE_BONUS * ownl.astype(jnp.float32)

    # ---- in-kernel top-m: m rounds of (row max, first column among ties) ---
    col = jax.lax.broadcasted_iota(jnp.int32, (rows, nb), 1)
    sel_grid = jnp.zeros((rows, nb), dtype=bool)
    for _ in range(m):
        val = jnp.max(selsc, axis=1, keepdims=True)
        pick = jnp.min(jnp.where(selsc == val, col, nb), axis=1, keepdims=True)
        one = col == pick
        sel_grid = sel_grid | (one & allowed)     # invalid picks select nothing
        selsc = jnp.where(one, _PICKED, selsc)

    # ---- exact term: DMA-gather the selection union, online softmax --------
    acc_ref[...] = jnp.zeros_like(acc_ref)
    rs_ref[...] = jnp.zeros_like(rs_ref)
    mt_ref[...] = jnp.zeros_like(mt_ref) + NEG_INF
    col1 = jax.lax.broadcasted_iota(jnp.int32, (1, nb), 1)
    sel_any = jnp.max(sel_grid.astype(jnp.float32), axis=0, keepdims=True)

    def page_body(j, _):
        picked = jnp.sum(jnp.where(col1 == j, sel_any, 0.0)) > 0.0

        @pl.when(picked)
        def _fetch_and_accumulate():
            cp_k = pltpu.make_async_copy(k_any.at[r, j], kpage, sems.at[0])
            cp_v = pltpu.make_async_copy(v_any.at[r, j], vpage, sems.at[1])
            cp_k.start()
            cp_v.start()
            if quant:
                cp_ks = pltpu.make_async_copy(ks_any.at[r, j], kspage,
                                              sems.at[2])
                cp_vs = pltpu.make_async_copy(vs_any.at[r, j], vspage,
                                              sems.at[3])
                cp_ks.start()
                cp_vs.start()
            cp_k.wait()
            cp_v.wait()
            k = kpage[...].astype(jnp.float32)
            vv = vpage[...].astype(jnp.float32)
            s = _dot(q, k, ((1,), (1,))) * scale          # (rows, b) on MXU
            if quant:  # int8 pages: per-token scales fold in on the key axis
                cp_ks.wait()
                cp_vs.wait()
                s = s * kspage[...]
            blk = jnp.sum(jnp.where(col1 == j, pbrow, 0))  # logical block id
            pos = blk * b + jax.lax.broadcasted_iota(jnp.int32, (1, b), 1)
            selcol = jnp.max(
                jnp.where(col1 == j, sel_grid.astype(jnp.float32), 0.0),
                axis=1, keepdims=True) > 0.0              # (rows, 1)
            ok = selcol & (pos >= 0) & (pos <= qp)
            # flash-style online stabilization: raise the running max, shrink
            # the resident accumulators, add this page at the new max
            m_old = mt_ref[...]
            m_new = jnp.maximum(
                m_old, jnp.max(jnp.where(ok, s, NEG_INF), axis=1,
                               keepdims=True))
            alpha = jnp.exp(m_old - m_new)
            a = jnp.where(ok, jnp.exp(s - m_new), 0.0)
            av = a * vspage[...] if quant else a      # a @ (diag(vs) · v)
            acc_ref[...] = acc_ref[...] * alpha + _dot(av, vv, ((1,), (0,)))
            rs_ref[...] = rs_ref[...] * alpha + jnp.sum(a, axis=1,
                                                        keepdims=True)
            mt_ref[...] = m_new

        return 0

    jax.lax.fori_loop(0, nb, page_body, 0)

    # ---- background + two-level stabilizer + normalize ---------------------
    c = jnp.maximum(jnp.max(coarse_m, axis=1, keepdims=True), NEG_INF * 0.5)
    if include_bg and with_upper:
        # H-level hierarchy (DESIGN.md §14): score the resident collapsed-
        # level + tail means. Entries hold only evicted (strictly past)
        # tokens — liveness is the one gate — and their maxima join the row
        # stabilizer before any exp: far history can dominate the window.
        hmu = _dot(q, hk_ref[0], ((1,), (1,))) * scale   # (rows, NU)
        hlive = hcnt_ref[0] > 0.0                        # (1, NU)
        hmu = jnp.where(hlive, hmu, NEG_INF)
        c = jnp.maximum(c, jnp.max(hmu, axis=1, keepdims=True))
    mt = mt_ref[...]
    c_tok = jnp.maximum(c, mt)                    # two-level stabilizer
    fine_adj = jnp.exp(mt - c_tok)                # mt ≤ c_tok, so ≤ 1
    out = acc_ref[...] * fine_adj
    rs = rs_ref[...] * fine_adj
    if include_bg:  # MRA-2 "full": coarse pyramid background
        bg = allowed & ~ownl & ~sel_grid
        w = jnp.where(bg, jnp.exp(coarse_m - c), 0.0) * cnt
        adj = jnp.exp(c - c_tok)
        vds = vds_ref[0]                          # (nb, D)
        out = out + adj * _dot(w, vds, ((1,), (0,)))   # (rows, nb)@(nb, D)
        rs = rs + adj * jnp.sum(w, axis=1, keepdims=True)
        if with_upper:
            wh = jnp.where(hlive, jnp.exp(hmu - c), 0.0) * hcnt_ref[0]
            out = out + adj * _dot(wh, hv_ref[0], ((1,), (0,)))
            rs = rs + adj * jnp.sum(wh, axis=1, keepdims=True)
    alive = rs > 0.0
    o = jnp.where(alive, out, 0.0) / jnp.where(alive, rs, 1.0)
    o_ref[0] = o.reshape(t1, t2, D)


def _no_grad(*args, **kw):
    raise NotImplementedError(
        "mra2 chunk/decode kernel is forward-only (serving path); training "
        "differentiates through the §3 block-sparse kernels instead")


@functools.partial(
    jax.custom_jvp, nondiff_argnums=(13, 14, 15, 16, 17, 18, 19, 20))
def _chunk_attention_call(
    q4, qpos4, kds3, vds3, counts3, pb3, hk3, hv3, hcnt3, k4, v4, ks4, vs4,
    scale, block_size, m, c_tile, quant, include_bg, with_upper, interpret,
):
    """pallas_call entry. q4 (BKV, G, Cp, D) fp32; qpos4 (BKV, G, Cp, 1)
    int32 (−1 = padded row) — both (BKV, Cp, G, ·) when ``c_tile == 1``, so
    a one-query tile is a full (G, ·) slab and the last two block dims are
    the array's (Mosaic's tiling rule); kds3/vds3 (BKV, nb, D) fp32; counts3/pb3
    (B, 1, nb); hk3/hv3 (BKV, NU, D) fp32 collapsed-level + tail means with
    hcnt3 (B, 1, NU) counts when ``with_upper`` (zero (1, 1, D)/(1, 1, 1)
    dummies otherwise — the fold is statically skipped); k4/v4 (BKV, nb, b, D)
    cache dtype; ks4/vs4 (BKV, nb, 1, b) fp32 scales ((1, 1, 1, 1) dummies
    when not ``quant``). ``Cp`` must be a multiple of the static query-tile
    width ``c_tile``."""
    c_major = c_tile == 1
    if c_major:
        BKV, Cp, G, D = q4.shape
        q_block, q_index = (1, 1, G), lambda r, t: (r, t, 0, 0)
    else:
        BKV, G, Cp, D = q4.shape
        q_block, q_index = (1, G, c_tile), lambda r, t: (r, 0, t, 0)
    nb, b = k4.shape[1], k4.shape[2]
    B = counts3.shape[0]
    hkv = BKV // B
    rows = G * c_tile
    nu = hk3.shape[1]

    kernel = functools.partial(
        _chunk_kernel, scale=scale, block_size=b, m=m, quant=quant,
        include_bg=include_bg, with_upper=with_upper)
    grid = (BKV, Cp // c_tile)
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    # per-batch side rows are (B, 1, n) with a (1, 1, n) block: the last two
    # block dims then equal the array's, which Mosaic's tiling requires
    batch_row = lambda r, t: (r // hkv, 0, 0)  # noqa: E731
    if with_upper:  # resident tiles, one row per (batch·kv-head) like kds
        hmean_spec = pl.BlockSpec((1, nu, D), lambda r, t: (r, 0, 0))
        hcnt_spec = pl.BlockSpec((1, 1, nu), batch_row)
    else:  # single shared dummy tile, never read
        hmean_spec = pl.BlockSpec((1, 1, D), lambda r, t: (0, 0, 0))
        hcnt_spec = pl.BlockSpec((1, 1, 1), lambda r, t: (0, 0, 0))
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((*q_block, D), q_index),
            pl.BlockSpec((*q_block, 1), q_index),
            pl.BlockSpec((1, nb, D), lambda r, t: (r, 0, 0)),
            pl.BlockSpec((1, nb, D), lambda r, t: (r, 0, 0)),
            pl.BlockSpec((1, 1, nb), batch_row),
            pl.BlockSpec((1, 1, nb), batch_row),
            hmean_spec,
            hmean_spec,
            hcnt_spec,
            any_spec,  # K pages: fetched by explicit per-page DMA
            any_spec,
            any_spec,
            any_spec,
        ],
        out_specs=pl.BlockSpec((*q_block, D), q_index),
        out_shape=jax.ShapeDtypeStruct(q4.shape, jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((b, D), k4.dtype),
            pltpu.VMEM((b, D), v4.dtype),
            pltpu.VMEM((1, b), jnp.float32),
            pltpu.VMEM((1, b), jnp.float32),
            pltpu.SemaphoreType.DMA((4,)),
            pltpu.VMEM((rows, D), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            # grid steps are fully independent (no cross-step accumulators),
            # so the (batch·kv-head) axis may run on both megacore cores; the
            # chunk-tile axis stays sequential to keep kds/vds tiles resident
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(q4, qpos4, kds3, vds3, counts3, pb3, hk3, hv3, hcnt3, k4, v4, ks4, vs4)
    return out


_chunk_attention_call.defjvp(_no_grad)


def chunk_attention_kernel(
    pre,
    k_cache: jax.Array,
    v_cache: jax.Array,
    q_pos: jax.Array,
    *,
    m: int,
    k_scale=None,
    v_scale=None,
    include_bg: bool = True,
    interpret: bool = False,
    mode: str = "auto",
) -> jax.Array:
    """Fused chunk/decode attention from the shared page-stats prelude.

    ``pre`` is ``core.mra_decode.ChunkPrelude`` (grouped queries + page
    table/counts + k_ds/v_ds page means) — selection itself happens inside
    the kernel. ``m`` is the static top-m budget, ``mode`` one of
    ``{"auto", "latency", "throughput"}`` (see ``resolve_kernel_mode``).
    Returns (B, Hq, C, D) fp32; the caller casts to q.dtype.
    """
    B, Hkv, G, C, D = pre.qg.shape
    S = k_cache.shape[2]
    b = pre.block_size
    nb = S // b
    BKV = B * Hkv
    if (k_scale is None) != (v_scale is None):
        raise ValueError(
            "k_scale and v_scale must be provided together (int8 cache), got "
            f"k_scale={'set' if k_scale is not None else None} "
            f"v_scale={'set' if v_scale is not None else None}")
    if q_pos.shape != (B, C):
        raise ValueError(
            f"q_pos shape {q_pos.shape} does not match the (B, C) = "
            f"({B}, {C}) of queries {pre.qg.shape}")

    c_tile = 1 if resolve_kernel_mode(mode, C) == "latency" \
        else min(C, THROUGHPUT_C_TILE)
    pad = (-C) % c_tile
    Cp = C + pad

    q4 = pre.qg.astype(jnp.float32).reshape(BKV, G, C, D)
    qpos4 = jnp.broadcast_to(
        q_pos[:, None, None, :], (B, Hkv, G, C)
    ).astype(jnp.int32).reshape(BKV, G, C)[..., None]
    if pad:  # ragged chunk boundary: padded rows select nothing, sliced off
        q4 = jnp.pad(q4, ((0, 0), (0, 0), (0, pad), (0, 0)))
        qpos4 = jnp.pad(qpos4, ((0, 0), (0, 0), (0, pad), (0, 0)),
                        constant_values=-1)
    if c_tile == 1:  # single-query tiles take the (BKV, Cp, G, ·) layout
        q4, qpos4 = q4.swapaxes(1, 2), qpos4.swapaxes(1, 2)

    k4 = k_cache.reshape(BKV, nb, b, *k_cache.shape[3:])
    v4 = v_cache.reshape(BKV, nb, b, *v_cache.shape[3:])
    quant = k_scale is not None
    if quant:
        ks4 = k_scale.astype(jnp.float32).reshape(BKV, nb, 1, b)
        vs4 = v_scale.astype(jnp.float32).reshape(BKV, nb, 1, b)
    else:  # dummy tiles keep the arity static; never DMA'd (static skip)
        ks4 = jnp.zeros((1, 1, 1, 1), jnp.float32)
        vs4 = ks4
    kds3 = pre.k_ds.astype(jnp.float32).reshape(BKV, nb, D)
    vds3 = pre.v_ds.astype(jnp.float32).reshape(BKV, nb, D)
    counts3 = pre.counts.astype(jnp.float32)[:, None]
    pb3 = pre.pb.astype(jnp.int32)[:, None]
    with_upper = pre.upper is not None
    if with_upper:  # H-level hierarchy (§14): levels + tail as resident tiles
        nu = pre.upper.k_mean.shape[2]
        hk3 = pre.upper.k_mean.astype(jnp.float32).reshape(BKV, nu, D)
        hv3 = pre.upper.v_mean.astype(jnp.float32).reshape(BKV, nu, D)
        hcnt3 = pre.upper.counts.astype(jnp.float32)[:, None]
    else:  # dummy tiles keep the arity static; the fold is compiled out
        hk3 = jnp.zeros((1, 1, D), jnp.float32)
        hv3 = hk3
        hcnt3 = jnp.zeros((1, 1, 1), jnp.float32)

    out = _chunk_attention_call(
        q4, qpos4, kds3, vds3, counts3, pb3, hk3, hv3, hcnt3, k4, v4, ks4,
        vs4, pre.scale, b, m, c_tile, quant, include_bg, with_upper,
        interpret,
    )
    if c_tile == 1:
        out = out.swapaxes(1, 2)
    return out[:, :, :C].reshape(B, Hkv * G, C, D)
