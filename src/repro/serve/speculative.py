"""Resolution-speculative decoding: coarse-pyramid draft + chunked MRA verify.

The MRA decomposition gives every serving slot a free draft model
(DESIGN.md §10): the pyramid block sums the ring-paged cache already
maintains ARE a cheap low-resolution view of the whole context. Per
speculative round, for every slot in the decode wave:

  1. snapshot — ``kv_cache.spec_snapshot`` captures the bounded ring window
     the round may destroy (O(K) per slot, never a cache copy);
  2. draft — K ordinary ``decode_step`` dispatches under the *coarse-only*
     AttentionSpec (own block exact, everything else through the pyramid
     sums; no top-m gather) autoregressively propose K tokens, writing
     draft K/V into the ring exactly like real decode;
  3. rewind — the draft's writes are rolled back (draft activations ran
     under coarse attention, so its K/V are approximations the verified
     stream must not keep);
  4. verify — ONE ``prefill_chunk`` dispatch (the PR 3 C-query path,
     unchanged) feeds [fed token, drafts] as a (K+1)-chunk: it rewrites the
     window with exact full-MRA K/V and returns the target distribution
     after every draft;
  5. accept — ``sampling.spec_verify_batch`` runs rejection sampling per
     slot (greedy degenerates to argmax-match, so greedy speculative decode
     is token-identical to the non-speculative oracle); the final
     ``spec_rewind`` trims each slot to its accepted prefix + correction
     token, replaying the kept positions' pyramid contributions bit-for-bit.

All five steps are batched across slots with ragged per-slot acceptance;
slots mid-prefill or frozen ride along untouched (``active`` masking), and
under a mesh every step runs tensor-parallel through the same shard_map
attention paths as normal serving (distributed/shard_attn.py — the spec
pytree carries ``coarse_only`` through unchanged).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import get_model

from .sampling import draft_batch, greedy_batch, spec_verify_batch

__all__ = ["SpecDecoder", "draft_config"]


def draft_config(cfg: ModelConfig, draft_level: int = 1) -> ModelConfig:
    """The draft model IS the target model under coarse-only attention.

    ``draft_level`` > 1 coarsens the draft's background one more rung
    (DESIGN.md §14): eligible groups of 2^(draft_level-1) adjacent pages
    fold through their merged mean instead of per-page means. The grouped
    fold only exists on the jnp route, so it forces ``use_kernel`` off for
    draft dispatches (verify dispatches keep the target config untouched).
    """
    attn = cfg.attention.replace(coarse_only=True, draft_level=draft_level)
    if draft_level > 1:
        attn = attn.replace(use_kernel=False)
    return cfg.replace(attention=attn)


@functools.lru_cache(maxsize=None)
def _make_spec_fns(cfg: ModelConfig, draft_level: int = 1):
    """Jitted (draft_step, verify, accept) for a (config, draft_level).

    Cached on the (frozen, hashable) ModelConfig like the engine's own fns
    so every Engine instance shares compiled executables. None of the
    wrappers closes over the draft length K — draft steps are single-token
    and verify/accept retrace per chunk shape under jit — so engines that
    differ only in ``spec_k`` share them too. ``draft_level`` changes the
    draft dispatch's traced program, so it is part of the cache key.
    """
    model = get_model(cfg)
    dcfg = draft_config(cfg, draft_level)

    scope = f"serve.{cfg.family}.spec"  # profiler grouping (DESIGN.md §13)

    def draft_step(params, cache, tokens, active, any_sampling, temp, top_k,
                   top_p, seed, step):
        with jax.named_scope(f"{scope}.draft"):
            logits, cache = model.decode_step(params, dcfg, cache, tokens,
                                              active=active)
        # all-greedy rounds skip the sort/softmax pipeline (cf. the engine's
        # decode fast path); the greedy branch's q_probs are never read
        q, nxt = jax.lax.cond(
            any_sampling,
            lambda lg: draft_batch(lg, temp, top_k, top_p, seed, step,
                                   vocab=cfg.vocab),
            lambda lg: (jnp.zeros_like(lg, jnp.float32),
                        greedy_batch(lg, vocab=cfg.vocab)),
            logits)
        return jnp.where(active, nxt, tokens), q, cache

    def verify(params, cache, tokens, num_valid):
        with jax.named_scope(f"{scope}.verify"):
            return model.prefill_chunk(params, cfg, cache, tokens, num_valid,
                                       all_logits=True, collect_kv=True)

    def accept(logits, draft, q_probs, temp, top_k, top_p, seed, step0,
               active):
        return spec_verify_batch(logits, draft, q_probs, temp, top_k, top_p,
                                 seed, step0, active, vocab=cfg.vocab)

    return jax.jit(draft_step), jax.jit(verify), jax.jit(accept)


class SpecDecoder:
    """Drives one speculative round per engine iteration (Engine.spec_k)."""

    def __init__(self, cfg: ModelConfig, spec_k: int, draft_level: int = 1):
        if cfg.attention.kind not in ("mra2", "mra2_s"):
            raise NotImplementedError(
                "speculative decoding drafts through the MRA pyramid; "
                f"attention kind {cfg.attention.kind!r} has no coarse level")
        assert spec_k >= 1
        assert draft_level >= 1
        self.cfg = cfg
        self.k = spec_k
        self._draft, self._verify, self._accept = _make_spec_fns(
            cfg, draft_level)

    def split_wave(self, kv, active: np.ndarray):
        """(speculable, plain) split of the decode wave.

        A slot is speculable when its round window (L0, L0 + K] contains no
        ring-eviction boundary (a block start at position >= the fine
        window, ``kv.window_tokens`` — ``capacity`` is an admission limit
        and is None on H>=3 collapse-up caches): a chunked verify writes the
        whole window before attending, so a boundary strictly inside it
        would evict (or at H>=3 collapse) a block that the window's earlier
        queries still see in the oracle. A boundary exactly AT L0 is fine —
        the fed token's write evicts it for every query, same as the
        oracle. Affected slots take plain decode steps instead: up to K
        consecutive waves approaching each block crossing (~K/block of
        post-window tokens), until the boundary sits at the window start.
        Shrinking the draft window to the boundary instead (ragged per-slot
        K) would keep those waves speculative — ROADMAP open item.
        """
        L0 = kv.lengths
        last_boundary = (L0 + self.k) // kv.block * kv.block
        unsafe = (last_boundary > L0) & (last_boundary >= kv.window_tokens)
        return active & ~unsafe, active & unsafe

    def round(self, engine, sched, active: np.ndarray) -> np.ndarray:
        """One speculative round over the decode wave ``active``.

        Slots whose round window straddles a ring-eviction boundary (see
        ``split_wave``) are left out and returned, for the engine's plain
        decode step; the rest run one draft/verify round. Inactive slots'
        state is preserved bit-for-bit through every dispatch.
        """
        tel = engine.telemetry
        with tel.span("spec_round", k=self.k):
            with tel.span("spec_round.wait"):  # split_wave reads the lengths
                wave, plain = self.split_wave(engine.kv, active)
            if wave.any():
                self._round(engine, sched, wave)
        return plain

    def _round(self, engine, sched, active: np.ndarray) -> None:
        """One batched draft(K) -> rewind -> verify -> accept -> trim round."""
        K = self.k
        kv = engine.kv
        tel = engine.telemetry
        snap = kv.spec_snapshot(K + 1)
        act = jnp.asarray(active)
        fed = jnp.asarray(sched.feed_tokens())
        temp, top_k, top_p, seed, step0 = map(jnp.asarray,
                                              sched.sampler_arrays())
        any_s = jnp.asarray(sched.any_sampling())

        tok, drafts, qs = fed, [], []
        for j in range(K):
            with tel.span("draft", step=j):
                tok, q, kv.tree = self._draft(
                    engine.params, kv.tree, tok, act, any_s, temp, top_k,
                    top_p, seed, step0 + j)
            drafts.append(tok)
            qs.append(q)
            tel.metrics.inc("draft_dispatches")
        # roll the draft's approximate writes back before the exact rewrite
        kv.spec_rewind(snap, snap["lengths"], act)

        chunk = jnp.stack([fed] + drafts, axis=1)  # (B, K+1)
        num_valid = jnp.where(act, K + 1, 0).astype(jnp.int32)
        with tel.span("verify", k=K):
            logits, kv.tree, chunk_kv = self._verify(
                engine.params, kv.tree, chunk, num_valid)
        tel.metrics.inc("verify_dispatches")

        out, n_out, n_acc = self._accept(
            logits, jnp.stack(drafts, axis=1), jnp.stack(qs, axis=1),
            temp, top_k, top_p, seed, step0, act)
        # trim each slot to accepted prefix + correction/bonus token: the
        # last emitted token is never fed, so the kept stream is L0 + n_out
        kv.spec_rewind(snap, snap["lengths"] + n_out, act, chunk_kv)

        with tel.span("spec_round.wait"):
            out, n_out, n_acc = map(np.asarray, (out, n_out, n_acc))
        emitted = 0
        for s in np.flatnonzero(active):
            emitted += sched.on_spec_tokens(
                int(s), out[s, : n_out[s]], int(n_acc[s]))
        m = tel.metrics
        m.inc("generated_tokens", emitted)
        m.inc("spec_rounds")
        m.inc("spec_drafted_tokens", int(K * active.sum()))
        m.inc("spec_accepted_tokens", int(n_acc[active].sum()))
        # delivered to requests (surplus past max_new_tokens is discarded)
        m.inc("spec_emitted_tokens", emitted)
