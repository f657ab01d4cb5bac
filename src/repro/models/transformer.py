"""Transformer model family: dense GQA LMs, MoE LMs, HuBERT encoder, VLM.

One stack implementation covers the assigned architectures:
  * dense:      qwen2-7b, llama3.2-3b, qwen3-1.7b, yi-6b (causal GQA LMs)
  * moe:        kimi-k2-1t-a32b, granite-moe-3b-a800m (MoE FFN)
  * hubert:     hubert-xlarge (bidirectional encoder; audio-frame frontend stub)
  * internvl:   internvl2-1b (vision-patch frontend stub + causal LM backbone)

The paper's technique plugs in through ``cfg.attention`` (AttentionSpec):
kind="mra2"/"mra2_s" routes every attention layer through MRA.

Batch formats (built by repro.data / launch.input_specs):
  dense/moe:  {"tokens": (B,S) i32, "targets": (B,S) i32}
  hubert:     {"frames": (B,S,Fd) f32, "mask_positions": (B,S) bool,
               "targets": (B,S) i32}
  internvl:   {"tokens": (B,S_text) i32, "patches": (B,P,Fd) f32,
               "targets": (B,S_text) i32}
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import hier
from repro.core.attention import chunk_attention, decode_attention, self_attention
from repro.core.mra_decode import PyramidState
from . import layers as L
from .moe import moe_block, moe_specs
from .params import ParamSpec


# --------------------------------------------------------------------------- #
# Specs
# --------------------------------------------------------------------------- #
def layer_specs(cfg: ModelConfig):
    p = {
        "ln1": L.norm_specs(cfg),
        "attn": L.attn_specs(cfg),
        "ln2": L.norm_specs(cfg),
    }
    if cfg.family == "moe" and cfg.moe is not None:
        p["moe"] = moe_specs(cfg)
    else:
        p["mlp"] = L.mlp_specs(cfg)
    return p


def param_specs(cfg: ModelConfig):
    from .params import stack_specs

    if cfg.scan_layers:
        layers = stack_specs(layer_specs(cfg), cfg.num_layers)
    else:
        layers = [layer_specs(cfg) for _ in range(cfg.num_layers)]
    p = {
        "embed": L.embed_specs(cfg),
        "ln_f": L.norm_specs(cfg),
        "layers": layers,
    }
    if cfg.frontend == "audio_frames":
        p["frontend"] = {
            "proj": ParamSpec((cfg.frontend_dim, cfg.d_model), (None, "d_model"),
                              dtype=cfg.pdt),
            "mask_embed": ParamSpec((cfg.d_model,), ("d_model",), dtype=cfg.pdt,
                                    init="embed"),
        }
    if cfg.frontend == "vision_patches":
        p["frontend"] = {
            "proj": ParamSpec((cfg.frontend_dim, cfg.d_model), (None, "d_model"),
                              dtype=cfg.pdt),
        }
    return p


# --------------------------------------------------------------------------- #
# Forward (full sequence: training / prefill)
# --------------------------------------------------------------------------- #
def _input_embed(params, cfg: ModelConfig, batch):
    """Returns x (B, S, d) activations and target positions info."""
    if cfg.family == "hubert":
        x = jnp.einsum(
            "bsf,fd->bsd", batch["frames"].astype(cfg.adt),
            params["frontend"]["proj"].astype(cfg.adt),
        )
        mask_emb = params["frontend"]["mask_embed"].astype(cfg.adt)
        mp = batch["mask_positions"][..., None]
        x = jnp.where(mp, mask_emb[None, None, :], x)
        if cfg.pos == "learned":
            x = x + jnp.take(params["embed"]["pos"], jnp.arange(x.shape[1]),
                             axis=0).astype(cfg.adt)
        return x
    if cfg.family == "internvl":
        patches = jnp.einsum(
            "bpf,fd->bpd", batch["patches"].astype(cfg.adt),
            params["frontend"]["proj"].astype(cfg.adt),
        )
        text = L.embed(batch["tokens"], params["embed"], cfg)
        return jnp.concatenate([patches, text], axis=1)
    return L.embed(batch["tokens"], params["embed"], cfg)


def _layer_fwd(x, p, cfg: ModelConfig, key_mask):
    aux = {}
    h = L.apply_norm(x, p["ln1"], cfg)
    x = x + L.attn_block(h, p["attn"], cfg, key_mask=key_mask)
    h = L.apply_norm(x, p["ln2"], cfg)
    if "moe" in p:
        mo, aux = moe_block(h, p["moe"], cfg)
        x = x + mo
    else:
        x = x + L.mlp_block(h, p["mlp"], cfg)
    return x, aux


def forward(params, cfg: ModelConfig, batch, *, key_mask=None):
    """Full-sequence forward; returns (logits, aux_losses)."""
    x = _input_embed(params, cfg, batch)
    aux_total = jnp.zeros((), jnp.float32)
    if cfg.scan_layers:
        def body(carry, lp):
            x, aux_tot = carry
            x, aux = _layer_fwd(x, lp, cfg, key_mask)
            for v in aux.values():
                aux_tot = aux_tot + v
            return (x, aux_tot), None

        body = L.remat_wrap(body, cfg)
        (x, aux_total), _ = jax.lax.scan(body, (x, aux_total), params["layers"])
    else:
        body = L.remat_wrap(
            functools.partial(_layer_fwd, cfg=cfg, key_mask=key_mask), cfg
        )
        for p in params["layers"]:
            x, aux = body(x, p)
            for v in aux.values():
                aux_total = aux_total + v
    x = L.apply_norm(x, params["ln_f"], cfg)
    logits = L.unembed(x, params["embed"], cfg)
    return logits, aux_total


def _layers_iter(params, cfg: ModelConfig):
    """Iterate per-layer param trees regardless of stacking."""
    from .params import layer_slice

    if cfg.scan_layers:
        return [layer_slice(params["layers"], i) for i in range(cfg.num_layers)]
    return params["layers"]


def loss_fn(params, cfg: ModelConfig, batch, *, key_mask=None):
    logits, aux = forward(params, cfg, batch, key_mask=key_mask)
    targets = batch["targets"]
    if cfg.family == "internvl":
        logits = logits[:, cfg.num_patches :]
    nll = L.lm_nll(logits, targets, cfg)
    if cfg.family == "hubert":
        w = batch["mask_positions"].astype(jnp.float32)  # predict only masked
        loss = jnp.sum(nll * w) / jnp.maximum(jnp.sum(w), 1.0)
    else:
        loss = jnp.mean(nll)
    metrics = {"loss": loss, "aux_loss": aux, "nll": loss}
    return loss + aux, metrics


# --------------------------------------------------------------------------- #
# Serving: KV cache, prefill, decode
# --------------------------------------------------------------------------- #
def cache_specs(cfg: ModelConfig, batch: int, max_len: int):
    """KV cache as ParamSpecs (so the dry-run can make abstract caches).

    Per-layer entries (lists), not one stacked (L, ...) array: scatters into
    a stacked cache fuse into whole-cache updates (and XLA-CPU lowers bf16
    scatter via a full fp32 round-trip — §Perf iteration Y2); per-layer
    tensors bound the update working set to one layer.
    """
    hd, Hkv, Lx = cfg.hd, cfg.kv_heads, cfg.num_layers
    dt = cfg.adt
    quant = cfg.attention.kv_quant and cfg.attention.kind in ("mra2", "mra2_s")
    kv_dt = jnp.int8 if quant else dt
    kv_spec = ParamSpec((batch, Hkv, max_len, hd),
                        ("batch", "kv_heads", "kv_seq", None), dtype=kv_dt,
                        init="zeros")
    c = {
        "k": [kv_spec for _ in range(Lx)],
        "v": [kv_spec for _ in range(Lx)],
        "lengths": ParamSpec((batch,), ("batch",), dtype=jnp.int32, init="zeros"),
    }
    if quant:
        sc_spec = ParamSpec((batch, Hkv, max_len),
                            ("batch", "kv_heads", "kv_seq"), dtype=jnp.float32,
                            init="zeros")
        c["k_scale"] = [sc_spec for _ in range(Lx)]
        c["v_scale"] = [sc_spec for _ in range(Lx)]
    if cfg.attention.kind in ("mra2", "mra2_s"):
        nb = max_len // cfg.attention.block_size
        pyr_spec = ParamSpec((batch, Hkv, nb, hd),
                             ("batch", "kv_heads", None, None),
                             dtype=jnp.float32, init="zeros")
        c["pyr_k"] = [pyr_spec for _ in range(Lx)]
        c["pyr_v"] = [pyr_spec for _ in range(Lx)]
        # ring page table (shared by all layers: every layer writes the same
        # positions): physical page -> logical block, -1 = never written.
        # Makes the fixed-size cache a ring over the newest ~max_len tokens —
        # decode past max_len evicts the oldest background block per slot
        # (DESIGN.md §9) instead of overflowing.
        c["page_blocks"] = ParamSpec((batch, nb), ("batch", None),
                                     dtype=jnp.int32, init="fill", scale=-1)
        if cfg.attention.levels >= 3:
            # H-level pyramid (core/hier.py, DESIGN.md §14): collapsed rings
            # over *evicted* history. Per level: int8 per-entry means (int4
            # precision via the clip range at levels >= 3) + fp32 scales per
            # layer; the owner/count tables are shared across layers exactly
            # like page_blocks (every layer evicts the same blocks). The
            # fp32 tail absorbs history past the top level. At levels == 2
            # none of these keys exist and the cache tree is byte-identical
            # to the two-level scheme.
            n = cfg.attention.hier_pages or nb
            hmean = ParamSpec((batch, Hkv, n, hd),
                              ("batch", "kv_heads", None, None),
                              dtype=jnp.int8, init="zeros")
            hscale = ParamSpec((batch, Hkv, n), ("batch", "kv_heads", None),
                               dtype=jnp.float32, init="zeros")
            for lvl in range(2, cfg.attention.levels):
                c[f"hier_k{lvl}"] = [hmean for _ in range(Lx)]
                c[f"hier_v{lvl}"] = [hmean for _ in range(Lx)]
                c[f"hier_ks{lvl}"] = [hscale for _ in range(Lx)]
                c[f"hier_vs{lvl}"] = [hscale for _ in range(Lx)]
                c[f"hier_own{lvl}"] = ParamSpec(
                    (batch, n), ("batch", None), dtype=jnp.int32,
                    init="fill", scale=-1)
                c[f"hier_cnt{lvl}"] = ParamSpec(
                    (batch, n), ("batch", None), dtype=jnp.int32,
                    init="zeros")
            tail = ParamSpec((batch, Hkv, hd), ("batch", "kv_heads", None),
                             dtype=jnp.float32, init="zeros")
            c["tail_k"] = [tail for _ in range(Lx)]
            c["tail_v"] = [tail for _ in range(Lx)]
            c["tail_cnt"] = ParamSpec((batch,), ("batch",), dtype=jnp.int32,
                                      init="zeros")
    return c


def layer_cache_kinds(cfg: ModelConfig):
    """Per-layer serving-cache kinds (serve/cache protocol, DESIGN.md §12).

    Every transformer layer holds KV state: ring-paged with pyramid block
    sums under the MRA attention kinds, plain dense KV otherwise.
    """
    kind = "paged_kv" if cfg.attention.kind in ("mra2", "mra2_s") else "kv"
    return [kind] * cfg.num_layers


def prefill(params, cfg: ModelConfig, batch, cache):
    """Run the full prompt, fill the cache, return (last_logits, cache)."""
    x = _input_embed(params, cfg, batch)
    B, S, d = x.shape
    positions = jnp.arange(S)
    new_cache = dict(cache)
    for i, p in enumerate(_layers_iter(params, cfg)):
        h = L.apply_norm(x, p["ln1"], cfg)
        q, k, v = L.qkv_project(h, p["attn"], cfg, positions)
        ke, ve = L.expand_kv_slots(k, v, cfg)
        q, ke, ve = L._tp_attn_constraint(cfg, q, ke, ve)
        o = self_attention(q, ke, ve, cfg.attn_spec, causal=cfg.causal)
        if cfg.padded_heads != cfg.num_heads:
            o = o * L.head_mask(cfg)[None, :, None, None].astype(o.dtype)
        x = x + jnp.einsum("bhsk,hkd->bsd", o, p["attn"]["wo"].astype(x.dtype))
        h = L.apply_norm(x, p["ln2"], cfg)
        if "moe" in p:
            mo, _ = moe_block(h, p["moe"], cfg)
            x = x + mo
        else:
            x = x + L.mlp_block(h, p["mlp"], cfg)
        new_cache["k"] = list(new_cache["k"])
        new_cache["v"] = list(new_cache["v"])
        if "k_scale" in new_cache:  # int8 KV cache (§Perf Y3)
            from repro.core.mra_decode import quantize_kv

            kq, ksc = quantize_kv(k)
            vq, vsc = quantize_kv(v)
            new_cache["k_scale"] = list(new_cache["k_scale"])
            new_cache["v_scale"] = list(new_cache["v_scale"])
            new_cache["k_scale"][i] = new_cache["k_scale"][i].at[:, :, :S].set(ksc)
            new_cache["v_scale"][i] = new_cache["v_scale"][i].at[:, :, :S].set(vsc)
            new_cache["k"][i] = new_cache["k"][i].at[:, :, :S].set(kq)
            new_cache["v"][i] = new_cache["v"][i].at[:, :, :S].set(vq)
        else:
            new_cache["k"][i] = new_cache["k"][i].at[:, :, :S].set(
                k.astype(new_cache["k"][i].dtype))
            new_cache["v"][i] = new_cache["v"][i].at[:, :, :S].set(
                v.astype(new_cache["v"][i].dtype))
        if "pyr_k" in new_cache:
            bs = cfg.attention.block_size
            kb = k.reshape(B, cfg.kv_heads, S // bs, bs, cfg.hd).sum(3, dtype=jnp.float32)
            vb = v.reshape(B, cfg.kv_heads, S // bs, bs, cfg.hd).sum(3, dtype=jnp.float32)
            new_cache["pyr_k"] = list(new_cache["pyr_k"])
            new_cache["pyr_v"] = list(new_cache["pyr_v"])
            new_cache["pyr_k"][i] = new_cache["pyr_k"][i].at[:, :, : S // bs].set(kb)
            new_cache["pyr_v"][i] = new_cache["pyr_v"][i].at[:, :, : S // bs].set(vb)
    if "page_blocks" in new_cache:
        nbp = new_cache["page_blocks"].shape[1]
        written = jnp.arange(nbp) < S // cfg.attention.block_size
        new_cache["page_blocks"] = jnp.where(
            written[None], jnp.arange(nbp, dtype=jnp.int32)[None],
            new_cache["page_blocks"])
    new_cache["lengths"] = jnp.full_like(cache["lengths"], S)
    x = L.apply_norm(x, params["ln_f"], cfg)
    logits = L.unembed(x[:, -1:], params["embed"], cfg)
    return logits[:, 0], new_cache


def prefill_chunk(params, cfg: ModelConfig, cache, tokens, num_valid, *,
                  all_logits=False, collect_kv=False):
    """Chunked batched prefill: C prompt tokens per slot, ragged lengths.

    The serving engine's prefill path (DESIGN.md §9): each call advances every
    prefilling slot by up to C prompt tokens in ONE jitted dispatch — the
    chunk's K/V (and pyramid block sums) are written directly into the cache
    at the slot's current offset, then the chunk's queries run MRA chunk
    attention against the updated cache. O(ceil(P/C)) dispatches per prompt
    instead of the O(P) single-token decode replays of the old engine, and a
    slot's writes never touch other slots' rows (bit-exact slot isolation).

    Speculative verification (DESIGN.md §10) reuses this dispatch unchanged:
    the chunk is [fed token, drafts] instead of prompt tokens, and it may
    start at any offset — including past the ring capacity, where a chunk
    token opening a new block recycles its page exactly like
    ``ring_pyramid_update`` (drop the evicted block's sums before adding).

    Args:
      tokens: (B, C) int32 prompt chunk per slot (padding arbitrary).
      num_valid: (B,) int32 count of real tokens in each slot's chunk;
        0 freezes the slot for this call (cache rows preserved bit-for-bit).
        Rows past it attend as ``q_pos = -1`` and come out of attention
        zero: their outputs and logits are meaningless.
      all_logits: return logits at every chunk position, not just the last
        valid one — speculative verify needs the target distribution after
        each draft.
      collect_kv: also return the chunk's per-layer fp32 K/V
        ((L, B, Hkv, C, D) each) — the exact values the pyramid adds used,
        so a partial ring rewind can replay accepted-prefix contributions
        bit-for-bit even when the cache itself stores int8 pages.

    Returns:
      (logits (B, V) — or (B, C, V) when ``all_logits`` — , cache), with
      ``(chunk_k, chunk_v)`` appended when ``collect_kv``.
    """
    B, C = tokens.shape
    offsets = cache["lengths"]  # (B,)
    positions = offsets[:, None] + jnp.arange(C, dtype=offsets.dtype)  # (B,C)
    tv = jnp.arange(C) < num_valid[:, None]  # (B,C) chunk-token validity
    # a row with no prompt token (a decoding slot's, or past a ragged chunk's
    # end) attends as q_pos = -1: it selects no page, and a kernel tile of
    # such rows runs no page loop; RoPE, the writes and the page table keep
    # the real positions
    q_pos = jnp.where(tv, positions, -1)
    lengths_new = offsets + num_valid.astype(offsets.dtype)
    x = L.embed(tokens, params["embed"], cfg, positions=positions)
    new_cache = dict(cache)
    paged = "page_blocks" in cache
    bs = cfg.attention.block_size
    b_idx2 = jnp.broadcast_to(jnp.arange(B)[:, None], (B, C))
    tv_kv = tv[:, :, None, None]  # (B,C,1,1) masks (B,C,Hkv,hd) writes

    def scatter_tokens(arr, vals):
        """Masked per-token write: vals (B, Hkv, C, ...) -> arr (B,Hkv,S,...)."""
        widx = positions % arr.shape[2]  # distinct per lane while C <= S
        vt = jnp.swapaxes(vals, 1, 2).astype(arr.dtype)  # (B,C,Hkv,...)
        old = arr[b_idx2, :, widx]
        m = tv_kv if vt.ndim == 4 else tv[:, :, None]
        return arr.at[b_idx2, :, widx].set(jnp.where(m, vt, old))

    hplans = []
    if paged and hier.has_hier(cache):
        # H-level collapse (DESIGN.md §14), plan phase: which pages this
        # chunk recycles and where their evicted owners land in the
        # hierarchy depends only on the shared tables + positions, so the
        # carry chains run ONCE here; each layer replays the same plans on
        # its own sums inside the loop. Evictions are processed
        # oldest-block-first — the order sequential decode would use — so
        # cascades into higher levels match one-token-at-a-time collapse
        # exactly (the spec-rewind replay and the order-invariance property
        # test both pin this).
        npages = cache["page_blocks"].shape[1]
        page_c = (positions // bs) % npages
        startm = ((positions % bs) == 0) & tv
        fresh_pages = jnp.any(
            (page_c[:, :, None] == jnp.arange(npages)) & startm[:, :, None],
            axis=1)
        ht = dict(cache)
        child_cnt = jnp.full((B,), bs, jnp.int32)
        for blk_j, on_j in hier.eviction_schedule(
                cache["page_blocks"], fresh_pages, C // bs + 1):
            tupd, plan = hier.cache_collapse_tables(ht, blk_j, child_cnt, on_j)
            ht.update(tupd)
            new_cache.update(tupd)
            hplans.append((plan, blk_j % npages))

    chunk_k, chunk_v = [], []
    for i, p in enumerate(_layers_iter(params, cfg)):
        h = L.apply_norm(x, p["ln1"], cfg)
        q, k_new, v_new = L.qkv_project(h, p["attn"], cfg, positions)
        ks = vs = None
        if "k_scale" in new_cache:  # int8 KV cache (§Perf Y3)
            from repro.core.mra_decode import quantize_kv

            kq, ksc = quantize_kv(k_new)
            vq, vsc = quantize_kv(v_new)
            new_cache["k_scale"] = list(new_cache["k_scale"])
            new_cache["v_scale"] = list(new_cache["v_scale"])
            ks = scatter_tokens(new_cache["k_scale"][i], ksc)
            vs = scatter_tokens(new_cache["v_scale"][i], vsc)
            new_cache["k_scale"][i] = ks
            new_cache["v_scale"][i] = vs
            k_write, v_write = kq, vq
        else:
            k_write, v_write = k_new, v_new
        kc = scatter_tokens(new_cache["k"][i], k_write)
        vc = scatter_tokens(new_cache["v"][i], v_write)
        new_cache["k"] = list(new_cache["k"])
        new_cache["v"] = list(new_cache["v"])
        new_cache["k"][i] = kc
        new_cache["v"][i] = vc
        if collect_kv:
            chunk_k.append(k_new.astype(jnp.float32))
            chunk_v.append(v_new.astype(jnp.float32))
        pyramid = None
        if "pyr_k" in new_cache:
            npages = new_cache["pyr_k"][i].shape[2]
            page = (positions // bs) % npages  # (B, C)
            # dense one-hot token->page map: deterministic segment-sum (no
            # scatter-add ordering concerns), npages is small
            ind = ((page[:, :, None] == jnp.arange(npages)) & tv[:, :, None])
            ind = ind.astype(jnp.float32)
            base_k, base_v = new_cache["pyr_k"][i], new_cache["pyr_v"][i]
            if paged:
                # H-level collapse, value phase: the evicted owners' sums
                # (still intact in base_k/base_v) carry up the hierarchy
                # before the fresh-zeroing below drops them from the fine
                # pyramid. No-op list when the cache is two-level.
                for plan, pg_j in hplans:
                    hier.cache_store_layer(
                        new_cache, i,
                        hier.cache_collapse_layer(
                            new_cache, i, plan,
                            base_k[jnp.arange(B), :, pg_j],
                            base_v[jnp.arange(B), :, pg_j]))
                # ring recycle (the chunked analogue of ring_pyramid_update's
                # keep mask): a chunk token that *starts* a new block evicts
                # the page's previous owner — drop its sums before adding.
                # During prompt prefill the recycled page holds zeros (slot
                # reset), so this is exactly the pre-existing math there.
                fresh = jnp.any(
                    (ind > 0) & ((positions % bs) == 0)[:, :, None], axis=1)
                base_k = jnp.where(fresh[:, None, :, None], 0.0, base_k)
                base_v = jnp.where(fresh[:, None, :, None], 0.0, base_v)
            pk = base_k + jnp.einsum(
                "bcy,bhcd->bhyd", ind, k_new.astype(jnp.float32))
            pv = base_v + jnp.einsum(
                "bcy,bhcd->bhyd", ind, v_new.astype(jnp.float32))
            new_cache["pyr_k"] = list(new_cache["pyr_k"])
            new_cache["pyr_v"] = list(new_cache["pyr_v"])
            new_cache["pyr_k"][i] = pk
            new_cache["pyr_v"][i] = pv
            pyramid = PyramidState(pk, pv, hier.cache_upper_view(new_cache, i))
            if i == 0 and paged:  # page table is shared across layers
                touched = jnp.any(ind > 0, axis=1)  # (B, npages)
                blk_new = jnp.max(
                    jnp.where(ind > 0, (positions // bs)[:, :, None], -1),
                    axis=1).astype(jnp.int32)
                new_cache["page_blocks"] = jnp.where(
                    touched, blk_new, new_cache["page_blocks"])
        o = chunk_attention(
            q, kc, vc, lengths_new, q_pos, cfg.attn_spec, pyramid=pyramid,
            page_blocks=new_cache.get("page_blocks"), k_scale=ks, v_scale=vs)
        if cfg.padded_heads != cfg.num_heads:
            o = o * L.head_mask(cfg)[None, :, None, None].astype(o.dtype)
        x = x + jnp.einsum("bhsk,hkd->bsd", o, p["attn"]["wo"].astype(x.dtype))
        h = L.apply_norm(x, p["ln2"], cfg)
        if "moe" in p:
            mo, _ = moe_block(h, p["moe"], cfg)
            x = x + mo
        else:
            x = x + L.mlp_block(h, p["mlp"], cfg)
    x = L.apply_norm(x, params["ln_f"], cfg)
    if all_logits:
        logits = L.unembed(x, params["embed"], cfg)  # (B, C, V)
    else:
        last = jnp.clip(num_valid - 1, 0, C - 1)
        x_last = x[jnp.arange(B), last]  # (B, d)
        logits = L.unembed(x_last[:, None], params["embed"], cfg)[:, 0]
    new_cache["lengths"] = lengths_new
    if collect_kv:
        return logits, new_cache, (jnp.stack(chunk_k), jnp.stack(chunk_v))
    return logits, new_cache


def decode_step(params, cfg: ModelConfig, cache, tokens, active=None):
    """One decode step. tokens (B,) int32 -> (logits (B,V), cache).

    ``active`` (B,) bool restricts the step to a subset of slots: inactive
    slots' cache rows (KV, scales, pyramid, page table, length) are preserved
    bit-for-bit so ragged continuous batching cannot perturb them, and their
    logits are garbage to be ignored by the caller. ``None`` = all active.

    With a ring-paged cache (``page_blocks`` present, DESIGN.md §9) the write
    position wraps modulo the physical cache, recycling the oldest
    background block once a slot's length exceeds the cache capacity.
    """
    B = tokens.shape[0]
    act = jnp.ones((B,), bool) if active is None else active
    lengths = cache["lengths"] + act.astype(cache["lengths"].dtype)
    x = L.embed(tokens[:, None], params["embed"], cfg)
    new_cache = dict(cache)
    b_idx = jnp.arange(B)
    paged = "page_blocks" in cache
    pos = lengths - 1  # the new token's global position (active slots)
    am2 = act[:, None]          # (B, 1)
    am3 = act[:, None, None]    # (B, 1, 1)
    hplan = page_e = None
    if paged and hier.has_hier(cache):
        # H-level collapse (DESIGN.md §14), plan phase: a token that starts
        # a new block recycles its ring page — the page's previous owner
        # carries into the hierarchy. The shared tables update once here
        # (like page_blocks); every layer replays the plan on its own sums
        # below, reading the evicted sums from its pyramid *before*
        # ring_pyramid_update zeroes them.
        bs0 = cfg.attention.block_size
        npages = cache["page_blocks"].shape[1]
        page_e = (pos // bs0) % npages
        old_owner = cache["page_blocks"][b_idx, page_e]
        evict = act & ((pos % bs0) == 0) & (old_owner >= 0)
        tupd, hplan = hier.cache_collapse_tables(
            cache, old_owner, jnp.full((B,), bs0, jnp.int32), evict)
        new_cache.update(tupd)
    for i, p in enumerate(_layers_iter(params, cfg)):
        h = L.apply_norm(x, p["ln1"], cfg)
        q, k_new, v_new = L.qkv_project(h, p["attn"], cfg, pos[:, None])
        S_phys = new_cache["k"][i].shape[2]
        widx = pos % S_phys if paged else pos
        ks = vs = None
        if "k_scale" in new_cache:  # int8 KV cache (§Perf Y3)
            from repro.core.mra_decode import quantize_kv

            kq, ksc = quantize_kv(k_new[:, :, 0])
            vq, vsc = quantize_kv(v_new[:, :, 0])
            new_cache["k_scale"] = list(new_cache["k_scale"])
            new_cache["v_scale"] = list(new_cache["v_scale"])
            ks = new_cache["k_scale"][i]
            vs = new_cache["v_scale"][i]
            ks = ks.at[b_idx, :, widx].set(jnp.where(am2, ksc, ks[b_idx, :, widx]))
            vs = vs.at[b_idx, :, widx].set(jnp.where(am2, vsc, vs[b_idx, :, widx]))
            new_cache["k_scale"][i] = ks
            new_cache["v_scale"][i] = vs
            k_write, v_write = kq, vq
        else:
            k_write = k_new[:, :, 0].astype(new_cache["k"][i].dtype)
            v_write = v_new[:, :, 0].astype(new_cache["v"][i].dtype)
        kc = new_cache["k"][i]
        vc = new_cache["v"][i]
        kc = kc.at[b_idx, :, widx].set(jnp.where(am3, k_write, kc[b_idx, :, widx]))
        vc = vc.at[b_idx, :, widx].set(jnp.where(am3, v_write, vc[b_idx, :, widx]))
        new_cache["k"] = list(new_cache["k"])
        new_cache["v"] = list(new_cache["v"])
        new_cache["k"][i] = kc
        new_cache["v"][i] = vc
        pyramid = None
        if "pyr_k" in new_cache:
            from repro.core.mra_decode import ring_pyramid_update

            bs = cfg.attention.block_size
            pb = new_cache["page_blocks"] if paged else None
            if paged:
                if hplan is not None:  # H-level collapse, value phase (§14)
                    hier.cache_store_layer(
                        new_cache, i,
                        hier.cache_collapse_layer(
                            new_cache, i, hplan,
                            new_cache["pyr_k"][i][b_idx, :, page_e],
                            new_cache["pyr_v"][i][b_idx, :, page_e]))
                pyramid, pb = ring_pyramid_update(
                    PyramidState(new_cache["pyr_k"][i], new_cache["pyr_v"][i]),
                    pb, k_new[:, :, 0], v_new[:, :, 0], pos, bs, active=act)
                new_cache["page_blocks"] = pb
                pyramid = PyramidState(
                    pyramid.k_sum, pyramid.v_sum,
                    hier.cache_upper_view(new_cache, i))
            else:
                blk = pos // bs
                contrib_k = jnp.where(am3, k_new[:, :, 0].astype(jnp.float32), 0.0)
                contrib_v = jnp.where(am3, v_new[:, :, 0].astype(jnp.float32), 0.0)
                pyramid = PyramidState(
                    new_cache["pyr_k"][i].at[b_idx, :, blk].add(contrib_k),
                    new_cache["pyr_v"][i].at[b_idx, :, blk].add(contrib_v))
            new_cache["pyr_k"] = list(new_cache["pyr_k"])
            new_cache["pyr_v"] = list(new_cache["pyr_v"])
            new_cache["pyr_k"][i] = pyramid.k_sum
            new_cache["pyr_v"][i] = pyramid.v_sum
        o = decode_attention(q, kc, vc, lengths, cfg.attn_spec, pyramid=pyramid,
                             page_blocks=new_cache.get("page_blocks"),
                             k_scale=ks, v_scale=vs)
        if cfg.padded_heads != cfg.num_heads:
            o = o * L.head_mask(cfg)[None, :, None, None].astype(o.dtype)
        x = x + jnp.einsum("bhsk,hkd->bsd", o, p["attn"]["wo"].astype(x.dtype))
        h = L.apply_norm(x, p["ln2"], cfg)
        if "moe" in p:
            mo, _ = moe_block(h, p["moe"], cfg)
            x = x + mo
        else:
            x = x + L.mlp_block(h, p["mlp"], cfg)
    x = L.apply_norm(x, params["ln_f"], cfg)
    logits = L.unembed(x, params["embed"], cfg)[:, 0]
    new_cache["lengths"] = lengths
    return logits, new_cache
