"""Batched serving with MRA decode: top-k KV-block selection per new token.

Loads a (randomly initialized or checkpointed) model and serves a batch of
requests through the continuous-batching engine — chunked prefill, ragged
slots, per-request sampling — then compares MRA decode against exact decode
attention on the same prompts (greedy mode).

    PYTHONPATH=src python examples/serve_decode.py
    PYTHONPATH=src python examples/serve_decode.py --temperature 0.8 --seed 7
"""
import argparse
import dataclasses

import jax
import numpy as np

from repro.checkpoint import latest_step, restore
from repro.configs import get_smoke_config
from repro.models import get_model, init_params
from repro.serve import Engine, EngineConfig, Request, SamplingParams

RECURRENT_ARCHS = ("rwkv6-7b", "recurrentgemma-9b")


def main():
    ap = argparse.ArgumentParser()
    # the continuous-batching engine serves every registered family through
    # the per-layer cache protocol (DESIGN.md §12): transformer archs get the
    # paged KV cache and the MRA-vs-exact comparison below; recurrent archs
    # (rwkv6, recurrentgemma) serve through their state caches (one pass, no
    # attention-kind comparison — rwkv6 has no attention to approximate)
    ap.add_argument("--arch", default="qwen3-1.7b",
                    choices=["qwen3-1.7b", "qwen2-7b", "llama3.2-3b", "yi-6b",
                             "kimi-k2-1t-a32b", "granite-moe-3b-a800m",
                             *RECURRENT_ARCHS])
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--chunk", type=int, default=16,
                    help="prefill chunk size (tokens per slot per dispatch)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy; > 0 samples (top-k/top-p below)")
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=0,
                    help="request sampling seed (req i uses seed + i)")
    ap.add_argument("--mesh", default="1",
                    help="device mesh 'D' or 'DxM' (data x model; default 1 = "
                         "single device; TP decode via shard_map)")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative draft length (0 = plain decode; MRA "
                         "kinds only — the pyramid is the draft model, "
                         "DESIGN.md §10)")
    ap.add_argument("--use-kernel", action="store_true",
                    help="route MRA chunk/decode attention through the fused "
                         "Pallas serving kernel (DESIGN.md §11)")
    ap.add_argument("--interpret", action="store_true",
                    help="run the --use-kernel serving kernel in interpret "
                         "mode (required off-TPU; slow, same tokens)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="export the serving engine's request-lifecycle + "
                         "dispatch trace as Chrome-trace JSONL (load in "
                         "chrome://tracing or Perfetto; DESIGN.md §13)")
    ap.add_argument("--metrics", action="store_true",
                    help="print the engine's Prometheus-format telemetry "
                         "snapshot (TTFT/inter-token/queue histograms, "
                         "dispatch counters, occupancy gauges) after the run")
    args = ap.parse_args()
    from repro.launch.device import enable_compile_cache, kernel_interpret
    from repro.launch.mesh import parse_mesh

    enable_compile_cache()
    interpret = args.use_kernel and kernel_interpret(args.interpret)
    mesh = parse_mesh(args.mesh)

    def dump_telemetry(eng):
        """Export the engine's observability surfaces (DESIGN.md §13)."""
        if args.metrics:
            print(eng.telemetry.prometheus_text(), end="")
        if args.trace:
            n = eng.telemetry.trace.export_jsonl(args.trace)
            print(f"wrote {n} Chrome-trace events to {args.trace} "
                  "(open in chrome://tracing or ui.perfetto.dev)")

    def make_requests(cfg):
        rng = np.random.default_rng(0)
        return [Request(prompt=rng.integers(1, cfg.vocab, size=ln),
                        max_new_tokens=args.new_tokens,
                        sampling=SamplingParams(
                            temperature=args.temperature, top_k=args.top_k,
                            top_p=args.top_p, seed=args.seed + i))
                for i, ln in enumerate((5, 9, 13, 7))]

    if args.arch in RECURRENT_ARCHS:
        # recurrent/hybrid serving: same engine, state cache backend;
        # speculation and the MRA serving kernel are paged-KV-only paths
        if args.spec_k or args.use_kernel:
            ap.error("--spec-k/--use-kernel need the MRA paged-KV cache "
                     "(transformer archs)")
        cfg = get_smoke_config(args.arch).replace(attn_shard=mesh is not None)
        model = get_model(cfg)
        params = init_params(model.param_specs(cfg), jax.random.PRNGKey(0))
        if args.ckpt_dir:
            step = latest_step(args.ckpt_dir)
            if step is not None:
                params = restore(args.ckpt_dir, step, params)
                print(f"restored checkpoint step {step}")
        eng = Engine(cfg, params, EngineConfig(
            slots=4, max_len=128, chunk=args.chunk, mesh=mesh))
        done = eng.run(make_requests(cfg))
        print(f"[{args.arch}] generated "
              f"({eng.stats['prefill_dispatches']} prefill + "
              f"{eng.stats['decode_dispatches']} decode dispatches):")
        for r in done:
            print(f"  req ({len(r.prompt)} prompt toks) -> {r.out.tolist()}")
        dump_telemetry(eng)
        return

    outs = {}
    for kind in ("mra2", "full"):
        cfg = get_smoke_config(args.arch)
        # the serving kernel is an MRA path; the exact-attention reference
        # engine always runs the dense jnp oracle
        use_kernel = args.use_kernel and kind.startswith("mra")
        cfg = cfg.replace(attention=dataclasses.replace(
            cfg.attention, kind=kind, decode_blocks=2),
            attn_shard=mesh is not None,
            attn_use_kernel=use_kernel,
            attn_interpret=use_kernel and interpret)
        model = get_model(cfg)
        params = init_params(model.param_specs(cfg), jax.random.PRNGKey(0))
        if args.ckpt_dir:
            step = latest_step(args.ckpt_dir)
            if step is not None:
                params = restore(args.ckpt_dir, step, params)
                print(f"restored checkpoint step {step}")
        # speculation needs the MRA pyramid; the exact-attention reference
        # engine always decodes plainly
        spec_k = args.spec_k if kind.startswith("mra") else 0
        eng = Engine(cfg, params, EngineConfig(
            slots=4, max_len=128, chunk=args.chunk, spec_k=spec_k, mesh=mesh))
        done = eng.run(make_requests(cfg))
        outs[kind] = {len(r.prompt): r.out.tolist() for r in done}
        spec_note = ""
        if spec_k:
            st = eng.stats
            rate = st["spec_accepted_tokens"] / max(st["spec_drafted_tokens"], 1)
            spec_note = (f" + {st['draft_dispatches']} draft + "
                         f"{st['verify_dispatches']} verify; "
                         f"accept rate {rate:.2f}")
        print(f"[{kind}] generated "
              f"({eng.stats['prefill_dispatches']} prefill + "
              f"{eng.stats['decode_dispatches']} decode dispatches"
              f"{spec_note}):")
        for r in done:
            print(f"  req ({len(r.prompt)} prompt toks) -> {r.out.tolist()}")
        if kind.startswith("mra"):
            # the MRA engine (speculative when --spec-k) is the interesting
            # trace; the exact-attention reference is just the oracle
            dump_telemetry(eng)

    keys = sorted(outs["full"])
    agree = sum(int(outs["mra2"][k] == outs["full"][k]) for k in keys)
    mode = "greedy argmax" if args.temperature <= 0 else "seeded sampling"
    print(f"\nMRA decode vs exact decode: {agree}/{len(keys)} "
          f"sequences identical ({mode} robustness to approximation)")


if __name__ == "__main__":
    main()
