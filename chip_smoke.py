#!/usr/bin/env python3
"""Chip smoke: serve qwen3-1.7b at published widths on a TPU, end to end.

The model is qwen3-1.7b at full width and depth (28 layers, d_model 2048,
16 query / 8 KV heads of dim 128, tied embeddings) with bf16 weights drawn
from ``--seed``. MRA-2 attention runs through the fused Pallas serving
kernel (kernels/chunk_attn.py), compiled for the chip. Phases:

  parity  the kernel route and the pure-jnp route, from the same weights,
          the same cache state and the same (teacher-forced) tokens: logits
          of several prefill chunks and decode steps, gated on relative
          error. The prompts outgrow the ``decode_blocks`` budget, so the
          top-m page selection really selects. Activations run in float32
          and matmuls at highest precision here, so that the gate sees the
          kernel and not bf16 rounding.
  serve   ``Engine.run`` on requests of 300-3000 prompt tokens, 16 new
          tokens each, over 4 slots and a 4096-token cache: chunked prefill
          runs the throughput tile mode, decode waves the latency tile mode.

``--four-chips`` runs only the tensor-parallel check: the same model on a
1x4 (data x model) mesh, 2 KV heads per chip, kernel inside shard_map,
against the model on ``devices[0]``.

Without a TPU it exits non-zero and prints no result line. The last line
of a passing run is ``{"ok": true, "device": {...}}``.

    python3 chip_smoke.py               # one chip
    python3 chip_smoke.py --four-chips  # one host with four chips
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "qwen3-1.7b"
SLOTS, MAX_LEN, CHUNK = 4, 4096, 512
PROMPT_LENS = (300, 3000, 1200, 700, 2100, 450)
NEW_TOKENS = 16
# parity: 18 chunks of 128 tokens fill 18 of the 32 pages, past the 16-page
# decode budget. Float32 activations double the cache and the jnp route's
# gather temp grows with the chunk, so parity runs 2 slots and a chunk
# smaller than the serving one to fit one chip next to the weights.
PARITY_SLOTS, PARITY_CHUNK, PARITY_CHUNKS, PARITY_DECODE = 2, 128, 18, 4
# max over (step, slot) of ||a - b|| / ||b|| on the logits. In bf16, rounding
# noise alone moves the logits of a 2-layer smoke model by ~2e-2 between two
# summation orders, so parity computes in float32 at highest matmul
# precision: the routes then differ by summation order, plus whatever a
# near-tie in the page scores flips between exact and background. A broken
# selection mask, page DMA or stabilizer moves the logits by far more.
PARITY_BOUND = 1e-2


def log(msg: str) -> None:
    print(msg, flush=True)


def smoke_config(**overrides):
    """qwen3-1.7b at published widths, bf16 weights, compiled serving kernel."""
    from repro.configs import get_config

    kw = dict(param_dtype="bfloat16", attn_use_kernel=True,
              attn_interpret=False)
    kw.update(overrides)
    return get_config(ARCH, **kw)


def make_params(cfg, seed: int):
    import jax

    from repro.models import get_model, init_params

    return init_params(get_model(cfg).param_specs(cfg),
                       jax.random.PRNGKey(seed))


def parity_inputs(cfg, slots: int, chunk: int, n_chunks: int, n_decode: int,
                  seed: int):
    """Teacher-forced tokens: (n_chunks, B, C) prefill chunks with ragged
    per-slot lengths in the last chunk, (n_chunks, B) valid counts, and
    (n_decode, B) decode tokens."""
    rng = np.random.default_rng(seed)
    tokens = rng.integers(1, cfg.vocab, (n_chunks, slots, chunk), np.int32)
    num_valid = np.full((n_chunks, slots), chunk, np.int32)
    num_valid[-1] -= np.arange(slots, dtype=np.int32) * (chunk // (2 * slots))
    decode = rng.integers(1, cfg.vocab, (n_decode, slots), np.int32)
    return tokens, num_valid, decode


def route_logits(cfg, params, tokens, num_valid, decode_tokens, *, slots: int,
                 max_len: int, mesh=None):
    """Logits of prefill chunks then decode steps from an empty cache.

    Drives the model's serving entry points (``prefill_chunk`` and
    ``decode_step``, the functions the Engine jits) with fixed tokens, so
    two routes see identical inputs at every step, in float32 activations
    at highest matmul precision. Returns the
    (steps, B, vocab) fp32 logits on the host and the seconds of the first
    prefill and first decode call (compilation included).
    """
    import jax

    from repro.distributed import mesh_utils
    from repro.models import get_model
    from repro.serve.cache import make_cache

    cfg = cfg.replace(activ_dtype="float32")
    model = get_model(cfg)
    cache = make_cache(cfg, model, slots, max_len, mesh=mesh).tree
    prefill = jax.jit(lambda p, c, t, n: model.prefill_chunk(p, cfg, c, t, n))
    decode = jax.jit(lambda p, c, t: model.decode_step(p, cfg, c, t))
    out, first = [], {}
    with mesh_utils.use_mesh(mesh), jax.default_matmul_precision("highest"):
        for name, fn, steps in (("prefill", prefill, zip(tokens, num_valid)),
                                ("decode", decode,
                                 ((t,) for t in decode_tokens))):
            for args in steps:
                t0 = time.perf_counter()
                logits, cache = fn(params, cache, *args)
                out.append(np.asarray(logits[:, :cfg.vocab], np.float32))
                first.setdefault(name, time.perf_counter() - t0)
    return np.stack(out), first


def compare(a, b) -> tuple:
    """(max relative L2 error over (step, slot), greedy-token agreement)."""
    err = np.linalg.norm(a - b, axis=-1) / np.linalg.norm(b, axis=-1)
    agree = float(np.mean(np.argmax(a, -1) == np.argmax(b, -1)))
    return float(err.max()), agree


def parity_phase(cfg, params, *, slots=PARITY_SLOTS, max_len=MAX_LEN,
                 chunk=PARITY_CHUNK, n_chunks=PARITY_CHUNKS,
                 n_decode=PARITY_DECODE, seed=0) -> float:
    """Kernel route vs jnp route on the same inputs; returns the error."""
    inputs = parity_inputs(cfg, slots, chunk, n_chunks, n_decode, seed)
    got = {}
    for route, use_kernel in (("kernel", True), ("jnp", False)):
        rcfg = cfg.replace(attn_use_kernel=use_kernel)
        got[route], first = route_logits(rcfg, params, *inputs, slots=slots,
                                         max_len=max_len)
        log(f"parity {route}: first prefill {first['prefill']:.1f}s, first "
            f"decode {first['decode']:.1f}s (compilation included)")
    err, agree = compare(got["kernel"], got["jnp"])
    log(f"parity kernel vs jnp: {n_chunks} prefill chunks of {chunk} + "
        f"{n_decode} decode steps x {slots} slots, max rel err {err:.3e} "
        f"(bound {PARITY_BOUND:.0e}), greedy agreement {agree:.3f} (not "
        "gated: random weights give near-flat logits)")
    if not np.isfinite(got["kernel"]).all():
        raise AssertionError("kernel route produced non-finite logits")
    if not err <= PARITY_BOUND:
        raise AssertionError(f"kernel route off the jnp route: {err:.3e}")
    return err


def serve_phase(cfg, params, *, slots=SLOTS, max_len=MAX_LEN, chunk=CHUNK,
                prompt_lens=PROMPT_LENS, new_tokens=NEW_TOKENS,
                seed=0) -> dict:
    """Serve mixed-length requests through ``Engine.run``; returns counts."""
    from repro.kernels.chunk_attn import resolve_kernel_mode
    from repro.serve import Engine, EngineConfig, Request

    eng = Engine(cfg, params, EngineConfig(slots=slots, max_len=max_len,
                                           chunk=chunk))
    log(f"serve: tile modes prefill={resolve_kernel_mode('auto', eng.chunk)} "
        f"decode={resolve_kernel_mode('auto', 1)}")
    rng = np.random.default_rng(seed)
    t0 = time.perf_counter()
    eng.run([Request(prompt=rng.integers(1, cfg.vocab, size=prompt_lens[0]),
                     max_new_tokens=2)])
    log(f"serve: warm-up request {time.perf_counter() - t0:.1f}s "
        "(compiles prefill, decode and sample)")
    eng.reset_stats()
    reqs = [Request(prompt=rng.integers(1, cfg.vocab, size=n),
                    max_new_tokens=new_tokens) for n in prompt_lens]
    t0 = time.perf_counter()
    done = eng.run(reqs)
    wall = time.perf_counter() - t0
    st = eng.stats
    outs = [np.asarray(r.out) for r in done]
    if len(done) != len(reqs) or any(len(o) != new_tokens for o in outs):
        raise AssertionError(
            f"served {[len(o) for o in outs]} tokens for {len(reqs)} requests")
    if any(((o < 0) | (o >= cfg.vocab)).any() for o in outs):
        raise AssertionError("sampled token outside the vocabulary")
    if st["prefill_tokens"] != sum(prompt_lens) or \
            st["generated_tokens"] != len(reqs) * new_tokens:
        raise AssertionError(
            f"engine counted {st['prefill_tokens']} prompt / "
            f"{st['generated_tokens']} generated tokens")
    res = {"requests": len(done), "prompt_tokens": st["prefill_tokens"],
           "generated_tokens": st["generated_tokens"],
           "prefill_dispatches": st["prefill_dispatches"],
           "decode_dispatches": st["decode_dispatches"], "wall_s": wall}
    log(f"serve: {res}")
    return res


def four_chip_phase(cfg, params, *, slots=PARITY_SLOTS, max_len=MAX_LEN,
                    chunk=PARITY_CHUNK, n_chunks=PARITY_CHUNKS,
                    n_decode=PARITY_DECODE, seed=0) -> float:
    """TP=4 (1x4 mesh) vs the same model on ``devices[0]``, kernel route."""
    import jax

    from repro.launch.mesh import make_local_mesh
    from repro.models import get_model
    from repro.models.params import param_shardings

    mesh = make_local_mesh(1, 4)
    tcfg = cfg.replace(attn_shard=True)
    sharded = jax.device_put(
        params, param_shardings(get_model(tcfg).param_specs(tcfg), mesh))
    inputs = parity_inputs(cfg, slots, chunk, n_chunks, n_decode, seed)
    ref, _ = route_logits(cfg, params, *inputs, slots=slots, max_len=max_len)
    got, first = route_logits(tcfg, sharded, *inputs, slots=slots,
                              max_len=max_len, mesh=mesh)
    err, agree = compare(got, ref)
    log(f"four chips: mesh {dict(mesh.shape)}, first prefill "
        f"{first['prefill']:.1f}s, first decode {first['decode']:.1f}s; TP=4 "
        f"vs devices[0] max rel err {err:.3e} (bound {PARITY_BOUND:.0e}), "
        f"greedy agreement {agree:.3f}")
    if not err <= PARITY_BOUND:
        raise AssertionError(f"TP=4 logits off the one-chip logits: {err:.3e}")
    return err


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and tokens")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the TP=4 vs one-chip logits check")
    args = ap.parse_args(argv)

    import jax

    from repro.launch.device import device_summary, enable_compile_cache

    cache_dir = enable_compile_cache()
    n_cached = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    dev = device_summary()
    log(f"device: {dev}; compile cache {cache_dir} ({n_cached} entries)")
    need = 4 if args.four_chips else 1
    if dev["platform"] != "tpu" or dev["count"] < need:
        log(f"FAIL: needs {need} TPU chip(s), JAX found {dev}")
        return 1

    t0 = time.perf_counter()
    cfg = smoke_config()
    params = make_params(cfg, args.seed)
    jax.block_until_ready(params)
    log(f"params: {ARCH} L={cfg.num_layers} d={cfg.d_model} "
        f"H={cfg.num_heads}/{cfg.kv_heads}x{cfg.hd} {cfg.param_dtype}, "
        f"{sum(p.size for p in jax.tree.leaves(params)):,} parameters, "
        f"{time.perf_counter() - t0:.1f}s")

    if args.four_chips:
        four_chip_phase(cfg, params, seed=args.seed)
    else:
        parity_phase(cfg, params, seed=args.seed)
        serve_phase(cfg, params, seed=args.seed)

    stats = jax.devices()[0].memory_stats() or {}
    log(f"peak HBM on device 0: {stats.get('peak_bytes_in_use', 0) / 2**30:.2f}"
        f" GiB of {stats.get('bytes_limit', 0) / 2**30:.2f} GiB; total "
        f"{time.perf_counter() - t0:.1f}s; compile cache now "
        f"{len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0} "
        "entries")
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
