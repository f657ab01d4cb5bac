"""MRA-2 attention for autoregressive decode (one query vs. a long KV cache).

The paper evaluates bidirectional encoders; this module is the beyond-paper
adaptation of the same two-level scheme to decoding (DESIGN.md §7): the KV
cache is viewed as ``nb = S/b`` key blocks, coarse scores
``mu_y = exp(q (K~_b)_y^T * scale)`` pick the top-``m`` blocks for *exact*
attention, all remaining valid blocks contribute the coarse background
(``variant="full"``) exactly as in the prefill formulation with a 1-row query
block. Complexity per decoded token: O(S/b + m*b) instead of O(S) — this is
what makes the ``long_500k`` shapes sub-quadratic end-to-end.

An incrementally-maintained block-sum pyramid (``PyramidState``) makes the
coarse scores O(1) to update per appended token instead of O(S) to recompute.

Ring-paged cache (DESIGN.md §9): the physical cache of ``nb`` block-sized
pages can serve a *logical* stream longer than the cache. ``page_blocks``
(B, nb) int32 maps physical page -> logical block index (-1 = never
written); position ``p`` lives at physical index ``p % S`` and its block at
page ``(p // b) % nb``, so appending evicts the oldest background block in
ring order while the pyramid entry *is* the page-table row. All attention
entry points below accept ``page_blocks``; ``None`` means the identity table
(page y holds block y), which reproduces the dense layout bit-for-bit.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from .mra import MraConfig, NEG_INF, FORCE_BONUS


class PyramidState(NamedTuple):
    """Incremental block-sum pyramid over the KV cache.

    k_sum / v_sum: (B, Hkv, nb, D) running sums of keys/values per block.
    The block mean is ``sum / count`` with ``count`` derived from ``length``.
    upper: optional ``core.hier.HierUpper`` — the collapsed-level + tail
    view of *evicted* history in an H-level hierarchy (DESIGN.md §14).
    ``None`` (the default, and always at levels=2) keeps every attention
    path byte-identical to the two-level scheme.
    """

    k_sum: jax.Array
    v_sum: jax.Array
    upper: Optional[NamedTuple] = None

    @staticmethod
    def init(batch: int, kv_heads: int, nb: int, d: int, dtype=jnp.float32):
        z = jnp.zeros((batch, kv_heads, nb, d), dtype)
        return PyramidState(z, z)

    def append(self, k_new: jax.Array, v_new: jax.Array, pos: jax.Array, block: int):
        """Add one token's K/V at position ``pos`` (per-batch array (B,)).

        Dense layout only: ``pos`` must lie inside the ``nb * block`` capacity.
        Past it the target block does not exist — an unguarded scatter would be
        clamped by JAX to ``nb - 1``, silently corrupting the last block's sums
        — so out-of-capacity appends are dropped instead (no-op for that
        slot). Ring streams that outlive the capacity must go through
        ``ring_pyramid_update``, which recycles pages instead of dropping.
        """
        nb = self.k_sum.shape[2]
        blk = pos // block  # (B,)
        in_cap = (blk < nb)[:, None, None]
        b_idx = jnp.arange(self.k_sum.shape[0])
        blk = jnp.minimum(blk, nb - 1)  # clamp AFTER masking the contribution
        k_sum = self.k_sum.at[b_idx, :, blk].add(
            jnp.where(in_cap, k_new.astype(self.k_sum.dtype), 0))
        v_sum = self.v_sum.at[b_idx, :, blk].add(
            jnp.where(in_cap, v_new.astype(self.v_sum.dtype), 0))
        return PyramidState(k_sum, v_sum)


def identity_page_table(batch: int, nb: int) -> jax.Array:
    """Dense layout: physical page y holds logical block y."""
    return jnp.broadcast_to(jnp.arange(nb, dtype=jnp.int32)[None], (batch, nb))


def paged_block_counts(lengths: jax.Array, page_blocks: jax.Array, block: int):
    """(B, nb) valid tokens per *page* given the page table and total length.

    A live page holding logical block y contains tokens [y*b, min(len, y*b+b));
    only the newest block is ever partial (eviction replaces whole pages), so
    for the identity table this is the dense per-block count clip.
    """
    starts = page_blocks * block
    c = jnp.clip(lengths[:, None] - starts, 0, block)
    return jnp.where(page_blocks >= 0, c, 0)


def paged_position_mask(lengths: jax.Array, page_blocks: jax.Array, S: int,
                        block: int) -> jax.Array:
    """(B, S) validity of each physical cache index under the page table."""
    idx = jnp.arange(S)
    pb = jnp.take(page_blocks, idx // block, axis=1)  # (B, S)
    pos = pb * block + (idx % block)[None, :]
    return (pb >= 0) & (pos < lengths[:, None])


def ring_pyramid_update(
    pyramid: PyramidState,
    page_blocks: jax.Array,
    k_new: jax.Array,
    v_new: jax.Array,
    pos: jax.Array,
    block: int,
    active: Optional[jax.Array] = None,
):
    """Append one token's K/V (B, Hkv, D) at global position ``pos`` (B,).

    Ring-paged version of ``PyramidState.append``: the target page is
    ``(pos // block) % nb``; when the token starts a new block the page is
    *recycled* — its old block sum is dropped (eviction) and ownership moves
    to the new logical block. Slots with ``active`` False are left untouched
    bit-for-bit. Returns (PyramidState, page_blocks).
    """
    nb = pyramid.k_sum.shape[2]
    b_idx = jnp.arange(pyramid.k_sum.shape[0])
    blk = pos // block
    page = blk % nb
    if active is None:
        active = jnp.ones(pos.shape, bool)
    k_old = pyramid.k_sum[b_idx, :, page]
    v_old = pyramid.v_sum[b_idx, :, page]
    # recycle the page (drop the evicted block's sums) only when an *active*
    # slot writes the first token of a new block
    keep = ~(active & ((pos % block) == 0))
    k_base = jnp.where(keep[:, None, None], k_old, 0.0)
    v_base = jnp.where(keep[:, None, None], v_old, 0.0)
    am = active[:, None, None]
    k_sum = pyramid.k_sum.at[b_idx, :, page].set(
        k_base + jnp.where(am, k_new.astype(pyramid.k_sum.dtype), 0.0))
    v_sum = pyramid.v_sum.at[b_idx, :, page].set(
        v_base + jnp.where(am, v_new.astype(pyramid.v_sum.dtype), 0.0))
    old_owner = page_blocks[b_idx, page]
    page_blocks = page_blocks.at[b_idx, page].set(
        jnp.where(active, blk.astype(page_blocks.dtype), old_owner))
    return PyramidState(k_sum, v_sum), page_blocks


def quantize_kv(x: jax.Array):
    """Per-token-per-head int8 quantization. x (B,H,*,D) -> (int8, scale)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax, 1e-6) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]), -127, 127)
    return q.astype(jnp.int8), scale


def mra2_decode_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    lengths: jax.Array,
    cfg: MraConfig,
    *,
    decode_blocks: int = 16,
    pyramid: Optional[PyramidState] = None,
    page_blocks: Optional[jax.Array] = None,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
) -> jax.Array:
    """One-step decode attention with MRA block selection.

    Args:
      q: (B, Hq, 1, D) the new token's query.
      k_cache/v_cache: (B, Hkv, S, D), S a static multiple of cfg.block_size.
      lengths: (B,) valid prefix length (includes the token being decoded).
      cfg: MraConfig (block_size, variant, compute dtype are honored).
      decode_blocks: selection budget m (number of exact key blocks).
      pyramid: optional incremental block sums; recomputed from the cache
        when absent.
      page_blocks: (B, nb) ring page table (physical page -> logical block,
        -1 dead); None = dense identity layout (page y is block y).
      k_scale/v_scale: (B, Hkv, S) per-token dequant scales when the cache is
        int8 (§Perf Y3); gathered blocks are dequantized after the gather.

    Returns:
      (B, Hq, 1, D) attention output.
    """
    # the decode step IS chunked-prefill attention with a C == 1 chunk whose
    # query sits at the newest position — one implementation, one set of
    # stabilizer/paging/dequant semantics (tests/test_engine.py pins the
    # equivalence; the engine relies on it for its conformance contract)
    return mra2_chunk_attention(
        q, k_cache, v_cache, lengths, (lengths - 1)[:, None], cfg,
        decode_blocks=decode_blocks, pyramid=pyramid, page_blocks=page_blocks,
        k_scale=k_scale, v_scale=v_scale,
    )


def mra2_coarse_decode_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    lengths: jax.Array,
    cfg: MraConfig,
    *,
    pyramid: Optional[PyramidState] = None,
    page_blocks: Optional[jax.Array] = None,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
) -> jax.Array:
    """Coarse-only decode attention: the speculative draft pass (DESIGN.md §10).

    ``mra2_decode_attention`` with the selection budget collapsed to the one
    mandatory block — the query's own (force-selected, exactly masked) block.
    Every other live page contributes only through its pyramid block sum, so
    a draft token costs O(S/b) with no O(m*b) gather at all: the pyramid
    pages the ring cache already maintains *are* the draft model. The serving
    dispatch reaches the same math through ``AttentionSpec.coarse_only``
    (budget_blocks == 1); this named form exists for direct measurement —
    the fidelity of the coarse level is what bounds speculative decoding's
    acceptance rate, and benchmarks/approx_error.py reports it next to the
    budgeted variants.
    """
    return mra2_decode_attention(
        q, k_cache, v_cache, lengths, cfg, decode_blocks=1, pyramid=pyramid,
        page_blocks=page_blocks, k_scale=k_scale, v_scale=v_scale,
    )


class ChunkPrelude(NamedTuple):
    """Shared jnp half of chunk/decode MRA attention (DESIGN.md §11).

    Only the page *statistics* — grouped queries, the page table/counts and
    the k/v page means. Coarse scoring, the causal block mask, own-block
    force selection and top-m all happen downstream: in jnp on the oracle
    route (``_select_pages``), *inside the kernel* on the Pallas route
    (``kernels/chunk_attn.py``), so no coarse-score tensor reaches HBM
    there. ``scale``/``block_size`` are static trace-time values.
    """

    qg: jax.Array        # (B, Hkv, G, C, D) grouped queries, compute dtype
    pb: jax.Array        # (B, nb) page table (identity when unpaged)
    counts: jax.Array    # (B, nb) valid tokens per page
    k_ds: jax.Array      # (B, Hkv, nb, D) per-page K means (coarse keys)
    v_ds: jax.Array      # (B, Hkv, nb, D) per-page V means
    scale: float
    block_size: int
    # H-level hierarchy (DESIGN.md §14): collapsed-level + tail means/counts
    # of evicted history (core.hier.HierUpper), folded into the background
    # softmax at their own resolution. None on every two-level path.
    upper: Optional[NamedTuple] = None


class PageSelection(NamedTuple):
    """jnp-route top-m page selection (the kernel's in-chip mirror)."""

    coarse_m: jax.Array  # (B, Hkv, G, C, nb) masked coarse scores
    y_idx: jax.Array     # (B, Hkv, G, C, m) selected physical pages
    sel_ok: jax.Array    # (B, Hkv, G, C, m) selection validity
    allowed: jax.Array   # (B, 1, 1, C, nb) valid-target support mask
    ownl: jax.Array      # (B, 1, 1, C, nb) query's own *live* block


def _chunk_prelude(q, k_cache, v_cache, lengths, q_pos, cfg, decode_blocks,
                   pyramid, page_blocks) -> ChunkPrelude:
    """Page stats shared by the jnp and Pallas routes."""
    B, Hq, C, D = q.shape
    Hkv, S = k_cache.shape[1], k_cache.shape[2]
    b = cfg.block_size
    if Hq % Hkv != 0:
        raise ValueError(
            f"query heads {Hq} (q {q.shape}) are not a multiple of KV heads "
            f"{Hkv} (k_cache {k_cache.shape}); GQA grouping is impossible")
    if S % b != 0:
        raise ValueError(
            f"KV cache length {S} (k_cache {k_cache.shape}) is not a "
            f"multiple of block_size {b}; the cache cannot be paged into "
            f"whole pyramid blocks")
    G = Hq // Hkv
    nb = S // b
    scale = cfg.softmax_scale if cfg.softmax_scale is not None else 1.0 / (D**0.5)
    cdt = cfg.compute_dtype

    pb = page_blocks if page_blocks is not None else identity_page_table(B, nb)
    if pb.shape != (B, nb):
        raise ValueError(
            f"page_blocks shape {pb.shape} does not match (B, nb) = "
            f"({B}, {nb}) for k_cache {k_cache.shape}, block_size {b}")
    counts = paged_block_counts(lengths, pb, b).astype(cdt)  # (B, nb)
    if pyramid is None:
        mask = paged_position_mask(lengths, pb, S, b).astype(k_cache.dtype)
        k_sum = jnp.sum(
            (k_cache * mask[:, None, :, None]).reshape(B, Hkv, nb, b, D),
            axis=3, dtype=cdt)
        v_sum = jnp.sum(
            (v_cache * mask[:, None, :, None]).reshape(B, Hkv, nb, b, D),
            axis=3, dtype=cdt)
    else:
        k_sum, v_sum = pyramid.k_sum.astype(cdt), pyramid.v_sum.astype(cdt)
    denom = jnp.maximum(counts, 1.0)[:, None, :, None]
    k_ds = k_sum / denom  # (B, Hkv, nb, D)
    v_ds = v_sum / denom

    qg = q.reshape(B, Hkv, G, C, D).astype(cdt)
    upper = pyramid.upper if pyramid is not None else None
    return ChunkPrelude(qg, pb, counts, k_ds, v_ds, scale, b, upper)


def _select_pages(pre: ChunkPrelude, q_pos, m: int) -> PageSelection:
    """Coarse scores, causal block mask, and top-m selection (jnp oracle).

    The Pallas route mirrors this math on-chip (kernels/chunk_attn.py); the
    two must select identical page sets, so any change here is a kernel
    contract change (tests/test_chunk_kernel.py pins the equivalence).

    Selection validity is carried as an explicit mask: a page is a valid
    exact-attention target iff it is live and causally allowed (the query's
    own block, when live, is always allowed). A *dead* own block — a fresh
    slot whose query block holds zero live tokens — is neither
    force-selected nor valid, so such rows produce exact zeros instead of
    attending stale cache garbage. (The old sentinel ``top_vals >
    NEG_INF * 0.5`` let the FORCE_BONUS of a dead own block pass the
    threshold; the mask-derived ``sel_ok`` cannot.)

    H-level walk (DESIGN.md §14): selection IS the coarse->fine refinement
    of the hierarchy, organised by residency rather than recursion. Context
    outside the fine window lives only at the collapsed levels
    (``pre.upper``) and folds into the softmax at its own resolution — the
    coarser the level, the older and more compressed the span — while this
    function walks the finest resident level: every in-window page is
    scored through its level-1 mean (the coarse read), the top-m subtrees
    refine to exact token attention (the fine read), and the rest fold
    through the same level-1 means as background. Each query therefore
    refines only its top-scoring subtrees; distant context is summarised at
    the coarsest resolution that still holds it. Per-query descent *within*
    the window (score level 2 first, open only promising level-2 entries
    into their level-1 children) is a future refinement — it changes this
    kernel contract, so it rides the same pinned-parity process as any
    selection change.
    """
    b = pre.block_size
    live = pre.counts > 0  # (B, nb)
    jq = q_pos // b  # (B, C) query block index
    pb_q = pre.pb[:, None, None, None, :]  # (B,1,1,1,nb)
    jq_q = jq[:, None, None, :, None]  # (B,1,1,C,1)
    # causal at block granularity: past blocks are background candidates, the
    # query's own live block is force-selected (exactly masked), future
    # excluded. allowed == the valid-selection mask (own ∧ live ⊆ allowed).
    allowed = live[:, None, None, None, :] & (pb_q <= jq_q)
    ownl = (pb_q == jq_q) & (pb_q >= 0) & live[:, None, None, None, :]
    coarse = jnp.einsum("bhgcd,bhyd->bhgcy", pre.qg, pre.k_ds) * pre.scale
    coarse_m = jnp.where(allowed, coarse, NEG_INF)  # (B,Hkv,G,C,nb)
    sel_scores = coarse_m + FORCE_BONUS * ownl
    _, y_idx = jax.lax.top_k(sel_scores, m)  # (B, Hkv, G, C, m)
    sel_ok = jnp.take_along_axis(
        jnp.broadcast_to(allowed, sel_scores.shape), y_idx, axis=-1)
    return PageSelection(coarse_m, y_idx, sel_ok, allowed, ownl)


def mra2_chunk_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    lengths: jax.Array,
    q_pos: jax.Array,
    cfg: MraConfig,
    *,
    decode_blocks: int = 16,
    pyramid: Optional[PyramidState] = None,
    page_blocks: Optional[jax.Array] = None,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
) -> jax.Array:
    """Chunked-prefill attention: C queries vs. the (ring-paged) KV cache.

    The chunked generalization of ``mra2_decode_attention``: per query token
    at global position ``p`` the coarse page scores pick the top-``m`` live
    pages among blocks strictly before ``p // b`` for exact attention, the
    query's own (partial) block is force-selected and masked exactly to
    ``pos_k <= p``, and the remaining live past pages contribute the coarse
    background. With C == 1 and ``q_pos == lengths - 1`` this is numerically
    identical to the decode path (tests/test_engine.py pins it).

    With ``cfg.use_kernel`` only the page-stats prelude stays here: coarse
    scoring, top-m selection and the gather/two-level-softmax/background/
    normalize tail all run inside the fused Pallas serving kernel
    (``kernels/chunk_attn.py``, DESIGN.md §11) in one of two MXU-shaped
    modes — ``cfg.kernel_mode`` "latency" (single-query tiles) or
    "throughput" (multi-query tiles), with "auto" resolving at trace time
    from C. Forward-only — the serving path is never differentiated. This
    jnp route is the differential oracle the kernel is pinned against.

    Args:
      q: (B, Hq, C, D) chunk queries; their K/V must already be in the cache.
      lengths: (B,) total written length (chunk included).
      q_pos: (B, C) global position of each query token; -1 marks a row
        that carries no query (it selects no page and comes out zero).
      page_blocks: (B, nb) ring page table; None = dense identity layout.

    Returns:
      (B, Hq, C, D) attention output.
    """
    B, Hq, C, D = q.shape
    Hkv, S = k_cache.shape[1], k_cache.shape[2]
    G = Hq // Hkv
    b = cfg.block_size
    nb = S // b
    cdt = cfg.compute_dtype
    if q_pos.shape != (B, C):
        raise ValueError(
            f"q_pos shape {q_pos.shape} does not match (B, C) = ({B}, {C}) "
            f"of q {q.shape}")

    pre = _chunk_prelude(q, k_cache, v_cache, lengths, q_pos, cfg,
                         decode_blocks, pyramid, page_blocks)
    m = min(decode_blocks, nb)
    if cfg.use_kernel:
        from repro.kernels.chunk_attn import chunk_attention_kernel

        out = chunk_attention_kernel(
            pre, k_cache, v_cache, q_pos, m=m, k_scale=k_scale,
            v_scale=v_scale, include_bg=cfg.variant == "full",
            interpret=cfg.interpret, mode=cfg.kernel_mode)
        return out.astype(q.dtype)

    qg, pb, counts = pre.qg, pre.pb, pre.counts
    v_ds, scale = pre.v_ds, pre.scale
    sel = _select_pages(pre, q_pos, m)
    coarse_m, y_idx, sel_ok = sel.coarse_m, sel.y_idx, sel.sel_ok
    allowed, own = sel.allowed, sel.ownl

    c = jnp.maximum(jnp.max(coarse_m, axis=-1), NEG_INF * 0.5)  # (B,Hkv,G,C)
    up = pre.upper
    if up is not None and cfg.variant == "full":
        # Collapsed levels + tail (DESIGN.md §14): score the per-entry means.
        # Entries hold only *evicted* (strictly past) tokens, so there is no
        # causal mask — liveness (count > 0) and a real query row (q_pos >=
        # 0) are the only gates. Their maxima join the row stabilizer:
        # collapsed history can dominate the live window, and the
        # background exp must not overflow.
        hlive = ((up.counts > 0)[:, None, None, None, :]
                 & (q_pos >= 0)[:, None, None, :, None])  # (B,1,1,C,NU)
        hmu = jnp.einsum(
            "bhgcd,bhyd->bhgcy", qg, up.k_mean.astype(cdt)) * scale
        hmu = jnp.where(hlive, hmu, NEG_INF)
        c = jnp.maximum(c, jnp.max(hmu, axis=-1))

    # ---- exact term over selected pages ------------------------------------
    k_blocks = k_cache.reshape(B, Hkv, nb, b, D)[:, :, None, None]
    v_blocks = v_cache.reshape(B, Hkv, nb, b, D)[:, :, None, None]
    gidx = jnp.broadcast_to(y_idx[..., None, None], y_idx.shape + (1, 1))
    k_sel = jnp.take_along_axis(k_blocks, gidx, axis=4).astype(cdt)
    v_sel = jnp.take_along_axis(v_blocks, gidx, axis=4).astype(cdt)
    if k_scale is not None:  # int8 cache: dequantize the gathered blocks only
        gs = jnp.broadcast_to(y_idx[..., None], y_idx.shape + (1,))
        ks = jnp.take_along_axis(
            k_scale.reshape(B, Hkv, nb, b)[:, :, None, None], gs, axis=4
        ).astype(cdt)
        vs = jnp.take_along_axis(
            v_scale.reshape(B, Hkv, nb, b)[:, :, None, None], gs, axis=4
        ).astype(cdt)
        k_sel = k_sel * ks[..., None]
        v_sel = v_sel * vs[..., None]

    s = jnp.einsum("bhgcd,bhgcmjd->bhgcmj", qg, k_sel) * scale
    blk_sel = jnp.take_along_axis(
        jnp.broadcast_to(pb[:, None, None, None, :], (B, Hkv, G, C, nb)),
        y_idx, axis=-1)
    pos = blk_sel[..., None] * b + jnp.arange(b)  # (B,Hkv,G,C,m,b)
    ok = (pos >= 0) & (pos <= q_pos[:, None, None, :, None, None])
    ok = ok & sel_ok[..., None]
    fine_max = jnp.max(jnp.where(ok, s, NEG_INF), axis=(-1, -2))
    c_tok = jax.lax.stop_gradient(jnp.maximum(c, fine_max))  # (B,Hkv,G,C)
    adj = jnp.exp(c - c_tok)
    a = jnp.where(ok, jnp.exp(jnp.minimum(s - c_tok[..., None, None], 80.0)), 0.0)
    out = jnp.einsum("bhgcmj,bhgcmjd->bhgcd", a, v_sel)
    rs = jnp.sum(a, axis=(-1, -2))  # (B,Hkv,G,C)

    # ---- coarse background ---------------------------------------------------
    if cfg.variant == "full":
        sel_grid = jnp.any(
            (y_idx[..., None] == jnp.arange(nb)) & sel_ok[..., None], axis=-2
        )  # (B,Hkv,G,C,nb)
        bg = allowed & ~own & ~sel_grid
        if cfg.draft_level > 1:
            # Coarser far field (DESIGN.md §14): fold the background over
            # groups of 2^(draft_level-1) physically adjacent ring pages. A
            # group is aggregated only when *every* member is a background
            # page (all live, causal, unselected) — the group mean is then a
            # count-weighted convex combination of member means, so its
            # score never exceeds the row stabilizer. Mixed groups (own /
            # selected / partial pages near the ring head) fall back to the
            # per-page background below.
            gsz = 1 << (cfg.draft_level - 1)
            if nb % gsz:
                raise ValueError(
                    f"draft_level={cfg.draft_level} aggregates the "
                    f"background over {gsz}-page groups, but nb={nb} pages "
                    f"do not divide evenly")
            ng = nb // gsz
            grp = bg.reshape(*bg.shape[:-1], ng, gsz).all(axis=-1)
            cnt_g = counts.reshape(B, ng, gsz).sum(axis=-1)  # (B, ng)
            den_g = jnp.maximum(cnt_g, 1.0)[:, None, :, None]
            kmean_g = (pre.k_ds * counts[:, None, :, None]).reshape(
                B, Hkv, ng, gsz, D).sum(axis=3) / den_g
            vmean_g = (v_ds * counts[:, None, :, None]).reshape(
                B, Hkv, ng, gsz, D).sum(axis=3) / den_g
            mu_g = jnp.einsum("bhgcd,bhyd->bhgcy", qg, kmean_g) * scale
            wg = jnp.where(grp, jnp.exp(mu_g - c[..., None]), 0.0)
            wg = wg * cnt_g[:, None, None, None, :] * adj[..., None]
            out = out + jnp.einsum("bhgcy,bhyd->bhgcd", wg, vmean_g)
            rs = rs + jnp.sum(wg, axis=-1)
            bg = bg & ~jnp.repeat(grp, gsz, axis=-1)
        w = jnp.where(bg, jnp.exp(coarse_m - c[..., None]), 0.0)
        w = w * counts[:, None, None, None, :] * adj[..., None]
        out = out + jnp.einsum("bhgcy,bhyd->bhgcd", w, v_ds)
        rs = rs + jnp.sum(w, axis=-1)
        if up is not None:
            wh = jnp.where(hlive, jnp.exp(hmu - c[..., None]), 0.0)
            wh = wh * up.counts[:, None, None, None, :] * adj[..., None]
            out = out + jnp.einsum(
                "bhgcy,bhyd->bhgcd", wh, up.v_mean.astype(cdt))
            rs = rs + jnp.sum(wh, axis=-1)

    alive = rs > 0
    out = jnp.where(alive[..., None], out, 0.0) / jnp.where(alive, rs, 1.0)[..., None]
    return out.reshape(B, Hq, C, D).astype(q.dtype)


def full_chunk_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    lengths: jax.Array,
    q_pos: jax.Array,
    *,
    softmax_scale: Optional[float] = None,
    compute_dtype=jnp.float32,
    local_window: Optional[int] = None,
) -> jax.Array:
    """Exact chunked-prefill attention oracle: C queries vs. a dense cache.

    Each query at position p attends keys at positions <= p (optionally
    restricted to the last ``local_window`` positions). O(C*S) per chunk.
    """
    B, Hq, C, D = q.shape
    Hkv, S = k_cache.shape[1], k_cache.shape[2]
    G = Hq // Hkv
    scale = softmax_scale if softmax_scale is not None else 1.0 / (D**0.5)
    qg = q.reshape(B, Hkv, G, C, D).astype(compute_dtype)
    s = jnp.einsum("bhgcd,bhjd->bhgcj", qg, k_cache.astype(compute_dtype)) * scale
    kp = jnp.arange(S)
    ok = (kp[None, None, :] <= q_pos[:, :, None]) & (kp[None, None, :] < lengths[:, None, None])
    if local_window is not None:
        ok = ok & (kp[None, None, :] > q_pos[:, :, None] - local_window)
    s = jnp.where(ok[:, None, None], s, NEG_INF)  # (B,1,1,C,S) -> broadcast
    p = jax.nn.softmax(s, axis=-1)
    has = jnp.any(ok, axis=-1)[:, None, None]  # all-masked rows -> zeros
    out = jnp.einsum("bhgcj,bhjd->bhgcd", p, v_cache.astype(compute_dtype))
    out = jnp.where(has[..., None], out, 0.0)
    return out.reshape(B, Hq, C, D).astype(q.dtype)


def full_decode_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    lengths: jax.Array,
    *,
    softmax_scale: Optional[float] = None,
    compute_dtype=jnp.float32,
) -> jax.Array:
    """Exact decode attention oracle. O(S) per token.

    Length-0 slots have every key masked; softmax over the finite ``NEG_INF``
    sentinel would be uniform and return a garbage V-average, so all-masked
    rows are zeroed — the same contract as ``full_chunk_attention`` (and as
    the MRA paths' ``alive`` guard), pinned by tests/test_engine.py.
    """
    B, Hq, _, D = q.shape
    Hkv, S = k_cache.shape[1], k_cache.shape[2]
    G = Hq // Hkv
    scale = softmax_scale if softmax_scale is not None else 1.0 / (D**0.5)
    qg = q.reshape(B, Hkv, G, D).astype(compute_dtype)
    s = jnp.einsum("bhgd,bhjd->bhgj", qg, k_cache.astype(compute_dtype)) * scale
    s = jnp.where((jnp.arange(S) < lengths[:, None])[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgj,bhjd->bhgd", p, v_cache.astype(compute_dtype))
    has = (lengths > 0)[:, None, None, None]  # all-masked rows -> zeros
    out = jnp.where(has, out, 0.0)
    return out.reshape(B, Hq, 1, D).astype(q.dtype)
