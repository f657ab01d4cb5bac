"""Serving engine: ragged continuous batching with chunked prefill + sampling.

The production-serving loop (DESIGN.md §9), lifted above the cache type by
the per-layer cache protocol (serve/cache/, DESIGN.md §12): the engine
resolves every model through one uniform registry contract —
``cache_specs / layer_cache_kinds / prefill_chunk / decode_step`` — so the
paged-pyramid transformer families, the RWKV-6 recurrent family, and the
hybrid local/rglru recurrentgemma family all serve through the same loop.
Per engine iteration:

  1. admission — pending requests bind to FREE slots; the slot's cache rows
     are reset bit-exactly (cache.CacheBackend.reset_slots).
  2. chunked prefill — ONE jitted ``prefill_chunk`` dispatch advances every
     PREFILL slot by up to ``chunk`` prompt tokens (ragged ``num_valid``),
     writing KV + pyramid block sums (paged), wkv states (recurrent), or
     window rings + RG-LRU states (hybrid) directly. O(ceil(P/chunk))
     dispatches per prompt instead of the O(P) per-token decode replays of
     the old engine. Slots whose prompt completes sample their first token
     from the chunk's last-position logits.
  3. decode — ONE jitted ``decode_step`` + fused ``sample_batch`` dispatch
     advances every DECODE slot (active-masked: other slots' state is
     untouched bit-for-bit), each at its own ragged length. With
     ``spec_k > 0`` the decode wave instead runs a resolution-speculative
     round (serve/speculative.py, DESIGN.md §10): K coarse-pyramid draft
     steps + one chunked full-MRA verify dispatch emit up to K+1 tokens per
     slot, with rejection sampling keeping output distributions — and greedy
     outputs bit — identical to this non-speculative path. Speculation needs
     the paged backend (pyramid draft + ring rewind).

Slots never wait for each other: a slot can decode while its neighbor is
mid-prefill, and finished slots readmit immediately. With ``mesh`` set the
engine serves tensor-parallel (params/cache placed by ParamSpec axes;
attention through shard_map when ``cfg.attn_shard``).

Observability (serve/telemetry.py, DESIGN.md §13): every engine owns a
``Telemetry`` whose metric set is declared in ``reset_stats`` — typed
counters for dispatches/tokens, bounded histograms for decode-step wall time
and the request-derived TTFT / queue-wait / inter-token latencies, gauges
for scheduler slot occupancy and cache page/eviction occupancy, and a
Chrome-trace request lifecycle. Each phase of ``_iterate`` runs inside a
``serve.<phase>`` span (``Telemetry.span``); every blocking device read the
loop makes sits in a ``serve.<phase>.wait`` child. ``Engine.stats``
survives as a typed view over the registry (undeclared keys raise).
``EngineConfig(telemetry=False)`` is the pinned no-op path.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.distributed import mesh_utils
from repro.models import get_model

from .cache import make_cache
from .sampling import SamplingParams, greedy_batch, sample_batch
from .scheduler import Request, Scheduler, SlotState
from .telemetry import StatsView, Telemetry

__all__ = ["Engine", "EngineConfig", "Request", "SamplingParams"]


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Engine construction knobs (the old ``Engine(**kwargs)`` sprawl).

    slots: concurrent sequences served.
    max_len: per-slot cache window. For MRA attention this is the ring
      capacity (must divide into pyramid blocks): prompts must fit, but
      generation beyond it evicts the oldest background pages. For dense
      attention kinds it is a hard prompt+generation cap. The recurrent /
      sliding-window backends hold O(1)/O(window) state per slot, so for
      them it only sizes the window ring (no admission cap).
    chunk: prefill chunk size (tokens per slot per prefill dispatch);
      clamped to ``max_len`` and to the backend's ``chunk_cap`` (a window
      ring absorbs at most W tokens per dispatch).
    spec_k: speculative draft length (0 = plain decode); requires an MRA
      attention kind and the paged cache backend, and ``spec_k + 1 <=
      max_len``.
    draft_level: resolution the speculative draft reads the background at
      (DESIGN.md §14). 1 (default) is the per-page coarse draft; level d > 1
      folds groups of 2^(d-1) adjacent pages through their merged mean —
      cheaper draft attention, unchanged output distribution (verify is
      always full-MRA). Requires ``decode_blocks % 2^(d-1) == 0``.
    mesh: jax device mesh for tensor-parallel serving (None = single device).
    default_sampling: sampler settings applied to requests submitted with
      ``sampling=None`` (None = greedy).
    kernel_mode: fused-serving-kernel tile shape when ``cfg.attn_use_kernel``
      (kernels/chunk_attn.py, DESIGN.md §11). "auto" (default) is the
      per-dispatch pick: decode waves trace with C == 1 and run the
      ``latency`` instantiation (single-query tiles, one wave per
      batch·kv-head), while chunked prefill and speculative verify trace
      with C == chunk / spec_k + 1 and run ``throughput`` (multi-query MXU
      tiles). "latency" / "throughput" force one tile shape for every
      dispatch — token streams are bit-identical in all three settings
      (tests/test_chunk_kernel.py pins it); only the tiling changes.
    telemetry: enable the full observability path — request-lifecycle
      tracing, latency histograms, occupancy gauges, profiler annotations
      (serve/telemetry.py, DESIGN.md §13). ``False`` is the no-op fast
      path: only the plain dispatch/token counters keep counting; token
      streams are bit-identical either way and serve_bench pins the
      enabled-path overhead at tok/s ratio >= 0.95.
    """

    slots: int = 4
    max_len: int = 512
    chunk: int = 32
    spec_k: int = 0
    draft_level: int = 1
    mesh: Optional[object] = None
    default_sampling: Optional[SamplingParams] = None
    kernel_mode: str = "auto"
    telemetry: bool = True

    def replace(self, **kw) -> "EngineConfig":
        return dataclasses.replace(self, **kw)


@functools.lru_cache(maxsize=None)
def _make_engine_fns(cfg: ModelConfig):
    """Jitted (prefill_chunk, decode+sample, sample) for a config.

    Cached on the (frozen, hashable) ModelConfig so every Engine instance for
    the same config shares compiled executables.
    """
    model = get_model(cfg)
    missing = [name for name in
               ("cache_specs", "layer_cache_kinds", "prefill_chunk",
                "decode_step")
               if not hasattr(model, name)]
    if missing:
        raise NotImplementedError(
            f"family {cfg.family!r} does not implement the serving contract "
            f"(missing {missing}; see models/registry.py)")

    # trace-time profiler annotations (zero runtime cost): device profiles
    # group each serving entry point by family + kernel mode (DESIGN.md §13)
    scope = f"serve.{cfg.family}.{cfg.attn_kernel_mode}"

    def prefill_chunk(params, cache, tokens, num_valid):
        with jax.named_scope(f"{scope}.prefill_chunk"):
            return model.prefill_chunk(params, cfg, cache, tokens, num_valid)

    def decode_and_sample(params, cache, tokens, active, any_sampling, temp,
                          top_k, top_p, seed, step):
        with jax.named_scope(f"{scope}.decode_step"):
            logits, cache = model.decode_step(params, cfg, cache, tokens,
                                              active=active)
        # all-greedy batches (the common case) skip the sort/softmax/cumsum
        # sampling pipeline entirely; greedy_batch is sample_batch's own
        # temperature == 0 path, so the token is identical either way
        nxt = jax.lax.cond(
            any_sampling,
            lambda lg: sample_batch(lg, temp, top_k, top_p, seed, step,
                                    vocab=cfg.vocab),
            lambda lg: greedy_batch(lg, vocab=cfg.vocab),
            logits)
        return jnp.where(active, nxt, tokens), cache

    def sample_only(logits, any_sampling, temp, top_k, top_p, seed, step):
        return jax.lax.cond(
            any_sampling,
            lambda lg: sample_batch(lg, temp, top_k, top_p, seed, step,
                                    vocab=cfg.vocab),
            lambda lg: greedy_batch(lg, vocab=cfg.vocab),
            logits)

    return jax.jit(prefill_chunk), jax.jit(decode_and_sample), jax.jit(sample_only)


class Engine:
    """Batched request server over ``config.slots`` concurrent sequences.

    Construction: ``Engine(cfg, params, EngineConfig(...))``. The pre-
    EngineConfig keyword signature (``Engine(cfg, params, slots=...,
    max_len=..., chunk=..., spec_k=..., mesh=...)``) survives as a
    deprecated shim for one release and warns on use.

    Serves every registered family through the uniform contract: the cache
    backend is selected from the model's per-layer cache kinds
    (serve/cache.make_cache), chunked prefill goes through the family's
    ``prefill_chunk`` (paged KV scatter, chunked wkv, or chunked
    window/RG-LRU), and decode through its active-masked ``decode_step``.
    """

    def __init__(self, cfg: ModelConfig, params,
                 config: Optional[EngineConfig] = None, **kwargs):
        if kwargs:
            known = {f.name for f in dataclasses.fields(EngineConfig)}
            unknown = set(kwargs) - known
            if unknown:
                raise TypeError(
                    f"Engine() got unexpected keyword arguments {sorted(unknown)}")
            warnings.warn(
                "Engine(cfg, params, slots=..., max_len=..., ...) is "
                "deprecated; pass an EngineConfig instead",
                DeprecationWarning, stacklevel=2)
            config = dataclasses.replace(config or EngineConfig(), **kwargs)
        elif config is None:
            config = EngineConfig()
        if config.kernel_mode not in ("auto", "latency", "throughput"):
            raise ValueError(
                "EngineConfig.kernel_mode must be 'auto' | 'latency' | "
                f"'throughput', got {config.kernel_mode!r}")
        if config.kernel_mode != "auto":
            # forced mode rides the (frozen, hashable) ModelConfig into
            # _make_engine_fns, so each forced mode compiles its own
            # executables; "auto" resolves per entry point at trace time
            cfg = cfg.replace(attn_kernel_mode=config.kernel_mode)
        self.config = config
        self.cfg = cfg
        self.model = get_model(cfg)
        self.slots = config.slots
        self.max_len = config.max_len
        self.spec_k = config.spec_k
        self.mesh = config.mesh
        self.kv = make_cache(cfg, self.model, self.slots, self.max_len,
                             mesh=self.mesh)
        self.chunk = min(config.chunk, self.max_len)
        if self.kv.chunk_cap is not None:
            self.chunk = min(self.chunk, self.kv.chunk_cap)
        self._spec = None
        if self.spec_k:
            from .speculative import SpecDecoder

            if self.spec_k + 1 > self.max_len:
                raise ValueError(
                    f"spec_k {self.spec_k} + 1 exceeds the cache window "
                    f"{self.max_len}")
            self._spec = SpecDecoder(cfg, self.spec_k,
                                     draft_level=config.draft_level)
            if not self.kv.supports_spec:
                raise NotImplementedError(
                    "speculative decoding needs the ring-paged MRA cache "
                    f"backend; {type(self.kv).__name__} has no "
                    "snapshot/rewind (DESIGN.md §12)")
        if self.mesh is not None:
            from repro.models.params import param_shardings

            params = jax.tree.map(
                jax.device_put, params,
                param_shardings(self.model.param_specs(cfg), self.mesh))
        self.params = params
        self._prefill, self._decode, self._sample = _make_engine_fns(cfg)
        self.reset_stats()

    def reset_stats(self) -> None:
        """Re-declare the engine's full metric set, zeroed (DESIGN.md §13).

        This is the *only* place serving metrics come into existence: every
        counter any component ever writes — the engine's own dispatch/token
        counters AND the speculative keys SpecDecoder increments
        (``draft_dispatches``, ``spec_rounds``, …) — is declared here, so a
        write to an undeclared name raises ``UndeclaredMetric`` at the
        write site instead of silently minting a new key.
        """
        tel = Telemetry(enabled=self.config.telemetry, tags={
            "family": self.cfg.family,
            "cache": type(self.kv).__name__,
            "kernel_mode": self.config.kernel_mode,
        })
        m = tel.metrics
        m.declare_counter(
            "prefill_dispatches", "decode_dispatches", "prefill_tokens",
            "prefill_rows_skipped", "generated_tokens", "requests_completed",
            # speculative decoding (spec_k > 0; serve/speculative.py)
            "spec_rounds", "draft_dispatches", "verify_dispatches",
            "spec_drafted_tokens", "spec_accepted_tokens",
            "spec_emitted_tokens")
        # decode-wave wall time (it ends in a blocking read) + request-derived
        # latencies; bounded reservoirs (a long-lived engine must not grow
        # host memory per step)
        m.declare_histogram(
            "decode_step_seconds", "ttft_seconds", "queue_wait_seconds",
            "prefill_seconds", "inter_token_seconds")
        # occupancy gauges, refreshed once per engine iteration; the cache
        # keys come from the backend itself (set_occupancy prefixes them
        # with "cache_") so backends with extra gauges — e.g. the H-level
        # cache's per-level entry/token counts (DESIGN.md §14) — declare
        # them without the engine enumerating every backend's set
        m.declare_gauge(
            "queue_depth", "slots_free", "slots_prefill", "slots_decode",
            *("cache_" + k for k in self.kv.occupancy()))
        m.declare_series("spec_accept_by_slot")
        self.telemetry = tel

    @property
    def stats(self) -> StatsView:
        """Typed view over the telemetry registry (legacy ``stats`` dict
        shape: counters read/write as ints, ``decode_step_seconds`` reads as
        the reservoir list; undeclared keys raise)."""
        return StatsView(self.telemetry.metrics)

    # ------------------------------------------------------------------ #
    def run(self, requests: List[Request]) -> List[Request]:
        """Serve ``requests`` to completion; returns them with ``out`` filled
        (completion order, which may differ from submission order)."""
        sched = Scheduler(self.slots, self.kv.capacity, self.chunk,
                          ring=self.kv.paged,
                          default_sampling=self.config.default_sampling,
                          telemetry=self.telemetry)
        for r in requests:
            sched.submit(r)
        with mesh_utils.use_mesh(self.mesh):
            while sched.busy():
                self._iterate(sched)
        self.telemetry.metrics.inc("requests_completed", len(sched.done))
        return sched.done

    # ------------------------------------------------------------------ #
    def _iterate(self, sched: Scheduler) -> None:
        tel = self.telemetry
        with tel.span("iterate"):
            with tel.span("admit"):
                newly = sched.admit()
                if newly:
                    mask = np.zeros((self.slots,), bool)
                    mask[newly] = True
                    self.kv.reset_slots(mask)

            plan = sched.prefill_plan()
            if plan is not None:
                self._prefill_chunk(sched, *plan)

            active = sched.decode_mask()
            if active.any():
                t0 = tel.now() if tel.enabled else 0.0
                if self._spec is not None:
                    active = self._spec.round(self, sched, active)
                if active.any():
                    self._plain_decode(sched, active)
                if tel.enabled:
                    tel.metrics.observe("decode_step_seconds", tel.now() - t0)
            if tel.enabled:
                with tel.span("occupancy"):
                    states = [s.state for s in sched.slots]
                    with tel.span("occupancy.wait"):
                        occ = self.kv.occupancy()
                    tel.set_occupancy(
                        {"queue_depth": len(sched.pending),
                         "slots_free": states.count(SlotState.FREE),
                         "slots_prefill": states.count(SlotState.PREFILL),
                         "slots_decode": states.count(SlotState.DECODE)},
                        occ)

    def _prefill_chunk(self, sched: Scheduler, tokens: np.ndarray,
                       num_valid: np.ndarray, finishing: List[int]) -> None:
        """One jitted prefill_chunk dispatch for every PREFILL slot; slots
        whose prompt completes sample their first token from it."""
        tel = self.telemetry
        with tel.span("prefill_chunk", tokens=int(num_valid.sum())):
            with tel.span("prefill_chunk.args"):
                args = jnp.asarray(tokens), jnp.asarray(num_valid)
            with tel.span("prefill_chunk.launch"):
                logits, self.kv.tree = self._prefill(self.params, self.kv.tree,
                                                     *args)
            if finishing:
                with tel.span("prefill_chunk.sample"):
                    first = self._sample(
                        logits, jnp.asarray(sched.any_sampling(finishing)),
                        *map(jnp.asarray, sched.sampler_arrays()))
                with tel.span("prefill_chunk.wait"):
                    first = np.asarray(first)
            tel.metrics.inc("prefill_dispatches")
            tel.metrics.inc("prefill_tokens", int(num_valid.sum()))
            # dispatched rows with no prompt token: they attend nothing
            tel.metrics.inc("prefill_rows_skipped",
                            int(tokens.size - num_valid.sum()))
            for s in finishing:
                tel.on_prefill_done(sched.slots[s].req)
                sched.on_sampled(s, first[s])
            tel.metrics.inc("generated_tokens", len(finishing))

    def _plain_decode(self, sched: Scheduler, active: np.ndarray) -> None:
        """One fused decode_step + sample dispatch for the ``active`` slots."""
        tel = self.telemetry
        with tel.span("decode_step", slots=int(active.sum())):
            with tel.span("decode_step.args"):
                temp, top_k, top_p, seed, step = sched.sampler_arrays()
                args = [jnp.asarray(a) for a in (
                    sched.feed_tokens(), active, sched.any_sampling(), temp,
                    top_k, top_p, seed, step)]
            with tel.span("decode_step.launch"):
                nxt, self.kv.tree = self._decode(self.params, self.kv.tree,
                                                 *args)
            with tel.span("decode_step.wait"):
                nxt = np.asarray(nxt)
            tel.metrics.inc("decode_dispatches")
            for s in np.flatnonzero(active):
                sched.on_sampled(int(s), nxt[s])
            tel.metrics.inc("generated_tokens", int(active.sum()))
