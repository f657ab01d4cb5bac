"""Serving-engine benchmark: continuous batching under mixed traffic.

Drives the full Engine (chunked prefill + ragged decode + sampling) on a
smoke-scale model and reports production serving metrics:

  * requests/sec and generated tokens/sec vs. slot count,
  * p50 / p99 inter-token latency (wall time of each batched decode step),
  * jitted-dispatch economy of chunked prefill vs. the token-replay
    baseline (one decode dispatch per prompt token — what the engine did
    before DESIGN.md §9): the acceptance claim is >= 5x fewer dispatches
    for a 128-token prompt.

Mesh-aware like decode_bench: under ``--mesh DxM`` the engine places
params/KV by ParamSpec axes and serves tensor-parallel.

Also serves the recurrent/hybrid families (rwkv6, recurrentgemma) through
the same engine via the per-layer cache protocol (DESIGN.md §12), reporting
req/s, tok/s, and the chunked-recurrent-prefill dispatch ratio vs. token
replay (acceptance: >= 5x).

Telemetry rows (DESIGN.md §13): TTFT p50/p99 and queue-wait from the
request-lifecycle histograms, cache-occupancy peaks for all three cache
families, per-slot speculative acceptance, and the pinned no-op-path
overhead claim — telemetry-on vs telemetry-off tok/s ratio >= 0.95 with
bit-identical token streams. ``--trace out.jsonl`` (or ``benchmarks.run
--trace``) exports the speculative engine's Chrome-trace JSONL as the CI
artifact.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from repro.configs import get_smoke_config
from repro.distributed import mesh_utils
from repro.launch.device import kernel_interpret
from repro.models import get_model, init_params
from repro.serve import Engine, EngineConfig, Request, SamplingParams
from repro.serve.telemetry import load_trace_jsonl, validate_chrome_events

from .common import CSV_HEADER, emit_row


def _requests(rng, vocab, lens, new_tokens):
    reqs = []
    for i, ln in enumerate(lens):
        sp = SamplingParams(temperature=0.8, top_k=8, seed=i) if i % 2 else \
            SamplingParams()
        reqs.append(Request(prompt=rng.integers(1, vocab, size=ln),
                            max_new_tokens=new_tokens, sampling=sp))
    return reqs


def _long_ctx(emit, cfg, params, mesh, *, smoke, interpret=False):
    """H=3 collapse-up serving (DESIGN.md §14): context >> the fine window.

    One slot streams a prompt far past ``max_len`` through chunked prefill —
    every evicted page collapses into the int8/int4 level rings + fp32 tail
    instead of vanishing — then decodes from the collapsed state. The row's
    throughput is context tokens processed per second (prefill-dominated);
    the derived column pins the memory claim: live fine tokens stay bounded
    by the window while the tail absorbs the distant history. The smoke
    variant (scripts/ci.sh fast) shrinks the stream and routes attention
    through the serving kernel (``interpret`` mode off-TPU) so the in-kernel
    upper-level fold is exercised end-to-end.
    """
    hcfg = cfg.replace(attention=cfg.attention.replace(levels=3))
    if smoke:
        hcfg = hcfg.replace(attn_use_kernel=True, attn_interpret=interpret)
    S, max_len, chunk = (2048, 256, 128) if smoke else (65536, 1024, 512)
    rng = np.random.default_rng(42)
    eng = Engine(hcfg, params, EngineConfig(
        slots=1, max_len=max_len, chunk=chunk, mesh=mesh))
    req = Request(prompt=rng.integers(1, cfg.vocab, size=S), max_new_tokens=4)
    t0 = time.perf_counter()
    done = eng.run([req])
    dt = time.perf_counter() - t0
    assert len(done) == 1 and len(done[0].out) == req.max_new_tokens
    g = eng.telemetry.snapshot()["gauges"]
    live = g["cache_tokens_live"]["peak"]
    tail = g["cache_tail_tokens"]["peak"]
    assert g["cache_level2_entries"]["peak"] > 0, "no collapsed entries"
    assert tail > 0, "long context never reached the tail"
    assert live <= max_len, (live, max_len)
    tok = S + len(done[0].out)
    tag = "serve_longctx_smoke" if smoke else "serve_longctx"
    emit(f"{tag}_tok_per_s", dt / tok * 1e6,
         f"{tok / dt:.0f} ctx={S} window={max_len} live_peak={live:.0f} "
         f"tail_peak={tail:.0f}")


def run(emit, trace_path=None, interpret=False):
    interpret = kernel_interpret(interpret)
    mesh = mesh_utils.get_mesh()
    cfg = get_smoke_config("qwen3-1.7b")
    cfg = cfg.replace(attn_shard=mesh is not None)
    params = init_params(get_model(cfg).param_specs(cfg), jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    chunk = 32
    new_tokens = 8

    # prompt-length mix: short chat-style + long document-style
    mixes = {"short": [8, 12, 5, 9, 14, 7], "mixed": [8, 128, 24, 96, 12, 64]}
    ttft_all, queue_all = [], []
    for slots in (2, 4):
        for mix_name, lens in mixes.items():
            eng = Engine(cfg, params, EngineConfig(
                slots=slots, max_len=256, chunk=chunk, mesh=mesh))
            reqs = _requests(rng, cfg.vocab, lens, new_tokens)
            eng.run(reqs[:1])  # warmup: compile prefill + decode + sample
            eng.reset_stats()
            t0 = time.perf_counter()
            done = eng.run(reqs)
            dt = time.perf_counter() - t0
            assert len(done) == len(reqs)
            gen = eng.stats["generated_tokens"]
            snap = eng.telemetry.snapshot()
            itl = snap["histograms"]["decode_step_seconds"]
            name = f"serve_s{slots}_{mix_name}"
            emit(f"{name}_req_per_s", dt / max(len(reqs), 1) * 1e6,
                 f"{len(reqs) / dt:.2f}")
            emit(f"{name}_tok_per_s", dt / max(gen, 1) * 1e6, f"{gen / dt:.1f}")
            emit(f"{name}_itl_p50", itl["p50"] * 1e6,
                 f"{itl['p50'] * 1e3:.2f}ms")
            emit(f"{name}_itl_p99", itl["p99"] * 1e6,
                 f"{itl['p99'] * 1e3:.2f}ms")
            ttft_all += eng.stats["ttft_seconds"]
            queue_all += eng.stats["queue_wait_seconds"]

    # request-lifecycle telemetry across the slot/mix sweep (DESIGN.md §13):
    # TTFT = submit -> first token, decomposable into queue + prefill via the
    # queue_wait/prefill histograms the same snapshot carries
    def pct(xs, q):
        xs = sorted(xs)
        return xs[min(len(xs) - 1, int(len(xs) * q))] if xs else 0.0

    ttft_p50, ttft_p99 = pct(ttft_all, 0.5), pct(ttft_all, 0.99)
    emit("serve_ttft_p50", ttft_p50 * 1e6, f"{ttft_p50 * 1e3:.2f}ms")
    emit("serve_ttft_p99", ttft_p99 * 1e6, f"{ttft_p99 * 1e3:.2f}ms")
    emit("serve_queue_wait_p50", pct(queue_all, 0.5) * 1e6,
         f"{pct(queue_all, 0.5) * 1e3:.2f}ms")
    assert len(ttft_all) >= 4 * len(mixes["short"]) - 4, len(ttft_all)
    # ring-paged cache occupancy peaks from the last (s4, mixed) run
    g = snap["gauges"]
    emit("serve_cache_occupancy", dt * 1e6,
         f"pages_live_peak={g['cache_pages_live']['peak']:.0f} "
         f"tokens_live_peak={g['cache_tokens_live']['peak']:.0f} "
         f"evicted_peak={g['cache_tokens_evicted']['peak']:.0f}")
    assert g["cache_pages_live"]["peak"] > 0

    # no-op fast path (DESIGN.md §13): telemetry must be a pure observer —
    # token streams bit-identical with it on or off, and the enabled path's
    # throughput within a few percent. Best-of-3 guards CPU timer noise.
    def overhead_leg(telemetry_on):
        eng = Engine(cfg, params, EngineConfig(
            slots=4, max_len=256, chunk=chunk, mesh=mesh,
            telemetry=telemetry_on))
        mk = lambda: _requests(np.random.default_rng(7), cfg.vocab,
                               mixes["short"], new_tokens)  # noqa: E731
        eng.run(mk()[:1])  # warmup: compile prefill + decode + sample
        best_tps, done = 0.0, None
        for _ in range(3):
            reqs = mk()
            t0 = time.perf_counter()
            done = eng.run(reqs)
            dt_leg = time.perf_counter() - t0
            gen_leg = sum(len(r.out) for r in done)
            best_tps = max(best_tps, gen_leg / dt_leg)
        return best_tps, {len(r.prompt): r.out for r in done}

    off_tps, off_out = overhead_leg(False)
    on_tps, on_out = overhead_leg(True)
    match = all(np.array_equal(on_out[k], off_out[k]) for k in off_out)
    ratio = on_tps / off_tps
    emit("serve_telemetry_overhead_ratio", 1e6 / max(on_tps, 1e-9),
         f"{ratio:.3f} tokens_match={match}")
    assert match, "telemetry changed the token stream"
    assert ratio >= 0.95, (on_tps, off_tps)

    # dispatch economy: one 128-token prompt through chunked prefill vs. the
    # token-replay baseline (= prompt_len decode dispatches, the pre-§9 engine)
    eng = Engine(cfg, params, EngineConfig(
        slots=2, max_len=256, chunk=chunk, mesh=mesh))
    prompt_len = 128
    t0 = time.perf_counter()
    eng.run([Request(prompt=rng.integers(1, cfg.vocab, size=prompt_len),
                     max_new_tokens=2)])
    dt = time.perf_counter() - t0
    chunked = eng.stats["prefill_dispatches"]
    replay = prompt_len  # baseline: one whole-batch decode dispatch per token
    ratio = replay / max(chunked, 1)
    emit(f"serve_prefill_dispatches_p{prompt_len}", dt * 1e6,
         f"{chunked} vs {replay} replay ({ratio:.0f}x fewer)")
    assert ratio >= 5.0, (chunked, replay)

    # fused Pallas serving kernel (DESIGN.md §11): the same engine with
    # chunked prefill + decode attention routed through kernels/chunk_attn.py
    # (under --interpret the tok/s is a does-it-run row, not a speedup claim;
    # the derived column pins the token streams equal).
    kcfg = cfg.replace(attn_use_kernel=True, attn_interpret=interpret)
    lens = [8, 12, 5]
    reqs = _requests(rng, cfg.vocab, lens, new_tokens)
    ref = Engine(cfg, params, EngineConfig(
        slots=2, max_len=64, chunk=8, mesh=mesh)).run(
        [Request(prompt=r.prompt, max_new_tokens=r.max_new_tokens,
                 sampling=r.sampling) for r in reqs])
    eng = Engine(kcfg, params, EngineConfig(
        slots=2, max_len=64, chunk=8, mesh=mesh))
    eng.run(reqs[:1])  # warmup: compile the kernel-path prefill + decode
    eng.reset_stats()
    t0 = time.perf_counter()
    done = eng.run(reqs)
    dt = time.perf_counter() - t0
    gen = eng.stats["generated_tokens"]
    by = {len(r.prompt): r.out for r in ref}
    match = all(np.array_equal(r.out, by[len(r.prompt)]) for r in done)
    emit("serve_kernel_tok_per_s", dt / max(gen, 1) * 1e6,
         f"{gen / dt:.1f} tokens_match={match}")
    assert match

    # dual-mode contract (DESIGN.md §11): forcing either tile mode for every
    # dispatch must leave the engine's token streams bit-identical to the
    # jnp reference — the mode is a performance knob, never a numerics knob.
    for mode in ("latency", "throughput"):
        eng = Engine(kcfg, params, EngineConfig(
            slots=2, max_len=64, chunk=8, mesh=mesh, kernel_mode=mode))
        eng.run(reqs[:1])  # warmup: compile the forced-mode executables
        eng.reset_stats()
        t0 = time.perf_counter()
        done = eng.run(reqs)
        dt = time.perf_counter() - t0
        gen = eng.stats["generated_tokens"]
        match = all(np.array_equal(r.out, by[len(r.prompt)]) for r in done)
        emit(f"serve_kernel_{mode}_tok_per_s", dt / max(gen, 1) * 1e6,
             f"{gen / dt:.1f} tokens_match={match}")
        assert match, mode

    # resolution-speculative engine telemetry (DESIGN.md §10/§13): per-slot
    # acceptance series land in the snapshot, and this engine's trace — the
    # richest lifecycle (queued/prefill/decode spans + draft/verify
    # dispatches) — is the exported Chrome-trace JSONL artifact.
    seng = Engine(cfg, params, EngineConfig(
        slots=2, max_len=64, chunk=8, spec_k=2, mesh=mesh))
    sreqs = [Request(prompt=rng.integers(1, cfg.vocab, size=ln),
                     max_new_tokens=12) for ln in (19, 7, 11, 5)]
    seng.run([Request(prompt=rng.integers(1, cfg.vocab, size=6),
                      max_new_tokens=4)])  # warmup
    seng.reset_stats()
    t0 = time.perf_counter()
    sdone = seng.run(sreqs)
    dt = time.perf_counter() - t0
    assert len(sdone) == len(sreqs)
    snap = seng.telemetry.snapshot()
    series = snap["series"]["spec_accept_by_slot"]
    per_slot = " ".join(
        f"slot{k}={np.mean(v):.2f}/round" for k, v in sorted(series.items()))
    emit("serve_spec_accept_per_slot", dt * 1e6, per_slot or "none")
    assert series, "speculative run recorded no per-slot acceptance"
    if trace_path:
        n = seng.telemetry.trace.export_jsonl(trace_path)
        validate_chrome_events(load_trace_jsonl(trace_path))
        emit("serve_trace_events", dt * 1e6,
             f"{n} events -> {trace_path} (validated)")

    # H=3 collapse-up long context (DESIGN.md §14): a 64k-token stream
    # served from a 1k-token fine window — the REQUIRED_ROWS memory claim
    _long_ctx(emit, cfg, params, mesh, smoke=False)

    # recurrent/hybrid families through the same engine (DESIGN.md §12):
    # rwkv6's O(1) wkv state and recurrentgemma's RG-LRU + window ring serve
    # under identical continuous batching; the dispatch-economy claim is the
    # chunked recurrent prefill vs. token-by-token state replay
    for arch in ("rwkv6-7b", "recurrentgemma-9b"):
        rcfg = get_smoke_config(arch).replace(attn_shard=mesh is not None)
        rparams = init_params(get_model(rcfg).param_specs(rcfg),
                              jax.random.PRNGKey(0))
        lens = [8, 96, 24, 64, 12, 48]
        eng = Engine(rcfg, rparams, EngineConfig(
            slots=4, max_len=256, chunk=chunk, mesh=mesh))
        reqs = _requests(rng, rcfg.vocab, lens, new_tokens)
        eng.run(reqs[:1])  # warmup: compile prefill + decode + sample
        eng.reset_stats()
        t0 = time.perf_counter()
        done = eng.run(reqs)
        dt = time.perf_counter() - t0
        assert len(done) == len(reqs)
        gen = eng.stats["generated_tokens"]
        pre_tok = eng.stats["prefill_tokens"]
        pre_disp = eng.stats["prefill_dispatches"]
        ratio = pre_tok / max(pre_disp, 1)
        tag = arch.split("-")[0]
        emit(f"serve_{tag}_req_per_s", dt / max(len(reqs), 1) * 1e6,
             f"{len(reqs) / dt:.2f}")
        emit(f"serve_{tag}_tok_per_s", dt / max(gen, 1) * 1e6,
             f"{gen / dt:.1f}")
        emit(f"serve_{tag}_prefill_dispatch_ratio", dt * 1e6,
             f"{pre_disp} dispatches for {pre_tok} tokens "
             f"({ratio:.0f}x fewer than replay)")
        assert ratio >= 5.0, (pre_disp, pre_tok)
        # state/window cache occupancy (DESIGN.md §13): recurrent state
        # absorbs history (evicted stays 0); the hybrid window ring holds
        # min(L, W) entries and counts older positions as evicted
        g = eng.telemetry.snapshot()["gauges"]
        emit(f"serve_{tag}_cache_occupancy", dt * 1e6,
             f"tokens_live_peak={g['cache_tokens_live']['peak']:.0f} "
             f"pages_live_peak={g['cache_pages_live']['peak']:.0f} "
             f"evicted_peak={g['cache_tokens_evicted']['peak']:.0f}")
        assert g["cache_tokens_live"]["peak"] > 0


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", default="1",
                    help="device mesh 'D' or 'DxM' (default: 1 = no mesh)")
    ap.add_argument("--trace", default=None,
                    help="export the speculative engine's request/dispatch "
                         "trace as Chrome-trace JSONL to this path")
    ap.add_argument("--long-ctx-smoke", action="store_true",
                    help="run only the H=3 collapse-up long-context smoke "
                         "(small stream through the serving kernel; the "
                         "scripts/ci.sh fast leg)")
    ap.add_argument("--interpret", action="store_true",
                    help="run the Pallas serving kernel in interpret mode "
                         "(required off-TPU)")
    args = ap.parse_args()

    from repro.launch.mesh import parse_mesh

    print(CSV_HEADER)
    with mesh_utils.use_mesh(parse_mesh(args.mesh)):
        if args.long_ctx_smoke:
            mesh = mesh_utils.get_mesh()
            cfg = get_smoke_config("qwen3-1.7b").replace(
                attn_shard=mesh is not None)
            params = init_params(get_model(cfg).param_specs(cfg),
                                 jax.random.PRNGKey(0))
            _long_ctx(emit_row, cfg, params, mesh, smoke=True,
                      interpret=kernel_interpret(args.interpret))
        else:
            run(emit_row, trace_path=args.trace, interpret=args.interpret)


if __name__ == "__main__":
    main()
