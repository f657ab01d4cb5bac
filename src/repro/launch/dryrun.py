import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import (jax locks device count on first init).
"""Multi-pod dry-run: lower + compile every (architecture x input-shape x mesh)
cell against the production meshes and record memory/cost/collective stats.

  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-6b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all          # every cell
  PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod-only

Results are cached as JSON under results/dryrun/ (one file per cell); the
roofline tool (launch/roofline.py) and EXPERIMENTS.md read from there.
"""
import argparse
import json
import re
import time
import traceback

import jax

from repro.configs import ARCHS, SHAPES, get_config, shape_skips
from repro.distributed import mesh_utils
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import (
    batch_specs,
    cache_abstract,
    decode_tokens_abstract,
    params_abstract,
)
from repro.models import get_model
from repro.optim import AdamW, cosine_schedule
from repro.train import TrainConfig, make_train_step

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "results", "dryrun")
TARGET_DEVICE_KIND = "TPU v5 lite"  # jax's device_kind for a TPU v5e chip

_COLL_RE = re.compile(
    r"%?([\w.-]+)\s*=\s*(\S+)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(", re.IGNORECASE,
)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
    "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
    "f8e4m3fn": 1, "f8e5m2": 1,
}


def _parse_type_bytes(type_str: str) -> int:
    """'f32[16,256]' or tuple '(f32[2], f32[3])' -> total bytes."""
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Sum per-device operand bytes of every collective in partitioned HLO."""
    out = {"all-reduce": 0, "all-gather": 0, "reduce-scatter": 0,
           "all-to-all": 0, "collective-permute": 0, "count": 0}
    for line in hlo_text.splitlines():
        line = line.strip()
        m = _COLL_RE.search(line)
        if not m or "-done" in line.split("=")[0]:
            continue
        kind = m.group(3).lower()
        # operand bytes: parse types inside the call parens from operand list —
        # approximate with the *result* type (equals operand total for
        # all-reduce/permute; gather output >= input so this upper-bounds).
        out[kind] += _parse_type_bytes(m.group(2))
        out["count"] += 1
    return out


def model_flops(cfg, shape) -> float:
    """6*N*D (train) / 2*N*D (inference) with MoE active-param correction."""
    from repro.models import count_params, get_model

    model = get_model(cfg)
    specs = model.param_specs(cfg)
    total = count_params(specs)
    active = total
    if cfg.moe is not None:
        from repro.models.moe import moe_specs
        from repro.models import count_params as cp

        expert_per_layer = cp(moe_specs(cfg)) - cfg.d_model * cfg.moe.num_experts
        n_moe = cfg.num_layers
        expert_total = expert_per_layer * n_moe
        active = total - expert_total + expert_total * cfg.moe.top_k / cfg.moe.num_experts
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * active * tokens
    tokens = shape.global_batch  # one token per sequence per serve_step
    return 2.0 * active * tokens


# §Perf optimized-variant overrides (EXPERIMENTS.md §Perf; the paper-faithful
# baseline never applies these). Applied with --opt / opt=True.
OPT_OVERRIDES = {
    # TP-shard attention via head padding for archs whose heads don't divide 16
    "qwen2-7b": {"pad_attn_heads_to": 16},
    "llama3.2-3b": {"pad_attn_heads_to": 16},
    "internvl2-1b": {"pad_attn_heads_to": 16},
    "granite-moe-3b-a800m": {"pad_attn_heads_to": 16},
}

# int8 KV cache for decode shapes (§Perf Y3) — every MRA decoder arch
OPT_ATTN_OVERRIDES_DECODE = {"kv_quant": True}

# FSDP-style weight sharding over the data axes for params that dwarf HBM
# (kimi-k2: 1T params; GSPMD inserts the per-layer weight all-gathers)
OPT_RULES = {
    "kimi-k2-1t-a32b": {"d_model": (("data",),)},
}
OPT_CONFIG = {
    "kimi-k2-1t-a32b": {"moe_dispatch": "a2a", "param_dtype": "bfloat16"},
}


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool, do_compile: bool = True,
               attention_override: dict | None = None, opt: bool = False,
               config_override: dict | None = None):
    from repro.distributed.sharding import ShardingRules

    cfg = get_config(arch)
    rules = None
    if opt and arch in OPT_OVERRIDES:
        cfg = cfg.replace(**OPT_OVERRIDES[arch])
    if opt and arch in OPT_CONFIG:
        cfg = cfg.replace(**OPT_CONFIG[arch])
    if opt and arch in OPT_RULES:
        rules = ShardingRules().override(**OPT_RULES[arch])
    if opt and SHAPES[shape_name].kind == "decode" and cfg.attention.kind in ("mra2", "mra2_s"):
        attention_override = {**OPT_ATTN_OVERRIDES_DECODE, **(attention_override or {})}
    if config_override:
        cfg = cfg.replace(**config_override)
    if attention_override:
        import dataclasses

        cfg = cfg.replace(attention=dataclasses.replace(cfg.attention, **attention_override))
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    model = get_model(cfg)
    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": 512 if multi_pod else 256,
        # the chip the production meshes model (the roofline's peak table key)
        "device_kind": TARGET_DEVICE_KIND,
        "kind": shape.kind,
        "attention": cfg.attention.kind,
    }

    t0 = time.time()
    with mesh_utils.use_mesh(mesh):
        params = params_abstract(cfg, mesh, rules)
        if shape.kind == "train":
            optimizer = AdamW()
            lr_fn = cosine_schedule(1e-4, 10, 1000)
            tc = TrainConfig(microbatches=1)
            step_fn = make_train_step(cfg, tc, optimizer, lr_fn)
            opt_state = optimizer.abstract_state(params, mesh, rules)
            batch = batch_specs(cfg, shape, mesh, rules)
            lowered = jax.jit(step_fn, donate_argnums=(0, 1)).lower(params, opt_state, batch)
        elif shape.kind == "prefill":
            batch = batch_specs(cfg, shape, mesh, rules)
            cache = cache_abstract(cfg, shape, mesh, rules)

            def prefill_fn(p, b, c):
                return model.prefill(p, cfg, b, c)

            lowered = jax.jit(prefill_fn, donate_argnums=(2,)).lower(params, batch, cache)
        else:  # decode
            cache = cache_abstract(cfg, shape, mesh, rules)
            tokens = decode_tokens_abstract(cfg, shape, mesh, rules)

            def serve_step(p, c, t):
                return model.decode_step(p, cfg, c, t)

            lowered = jax.jit(serve_step, donate_argnums=(1,)).lower(params, cache, tokens)
        result["lower_s"] = round(time.time() - t0, 2)

        if do_compile:
            t1 = time.time()
            compiled = lowered.compile()
            result["compile_s"] = round(time.time() - t1, 2)
            mem = compiled.memory_analysis()
            result["memory"] = {
                "argument_bytes": mem.argument_size_in_bytes,
                "output_bytes": mem.output_size_in_bytes,
                "temp_bytes": mem.temp_size_in_bytes,
                "alias_bytes": mem.alias_size_in_bytes,
                "total_per_device_bytes": (
                    mem.argument_size_in_bytes + mem.output_size_in_bytes
                    + mem.temp_size_in_bytes - mem.alias_size_in_bytes
                ),
            }
            ca = compiled.cost_analysis()
            result["cost"] = {
                "flops_per_device": float(ca.get("flops", 0.0)),
                "bytes_accessed_per_device": float(ca.get("bytes accessed", 0.0)),
                "transcendentals": float(ca.get("transcendentals", 0.0)),
            }
            result["collectives"] = collective_bytes(compiled.as_text())

        # XLA cost analysis does not descend into `while` bodies (verified
        # empirically, DESIGN.md §6) — for scanned train cells, recover true
        # per-step costs by lowering unrolled depth-1 and depth-2 variants and
        # extrapolating linearly in depth.
        if do_compile and shape.kind == "train" and cfg.scan_layers:
            period = max(len(cfg.block_pattern), 1)
            sub = {}
            for mult in (1, 2):
                cfg_small = cfg.replace(num_layers=period * mult, scan_layers=False)
                step_small = make_train_step(
                    cfg_small, TrainConfig(microbatches=1), AdamW(),
                    cosine_schedule(1e-4, 10, 1000),
                )
                p_s = params_abstract(cfg_small, mesh, rules)
                o_s = AdamW().abstract_state(p_s, mesh, rules)
                b_s = batch_specs(cfg_small, shape, mesh, rules)
                comp = jax.jit(step_small, donate_argnums=(0, 1)).lower(p_s, o_s, b_s).compile()
                ca_s = comp.cost_analysis()
                sub[mult] = {
                    "flops": float(ca_s.get("flops", 0.0)),
                    "bytes": float(ca_s.get("bytes accessed", 0.0)),
                    "coll": collective_bytes(comp.as_text()),
                }
            n_units = cfg.num_layers / period
            def _ext(a, b):
                return a + (n_units - 1) * (b - a)
            coll1, coll2 = sub[1]["coll"], sub[2]["coll"]
            result["cost_extrapolated"] = {
                "flops_per_device": _ext(sub[1]["flops"], sub[2]["flops"]),
                "bytes_accessed_per_device": _ext(sub[1]["bytes"], sub[2]["bytes"]),
                "method": f"unrolled depth {period}/{2*period} linear extrapolation",
            }
            result["collectives_extrapolated"] = {
                k: _ext(coll1[k], coll2[k]) for k in coll1
            }
        result["model_flops_total"] = model_flops(cfg, shape)
    return result


def run_cell(arch, shape_name, multi_pod, *, force=False):
    os.makedirs(RESULTS_DIR, exist_ok=True)
    mesh_tag = "2x16x16" if multi_pod else "16x16"
    fname = os.path.join(RESULTS_DIR, f"{arch}__{shape_name}__{mesh_tag}.json")
    if os.path.exists(fname) and not force:
        cached = json.load(open(fname))
        if cached.get("status") in ("ok", "skipped"):
            print(f"[cached] {arch} x {shape_name} x {mesh_tag}")
            return cached
    skip = shape_skips(arch, shape_name)
    if skip:
        res = {"arch": arch, "shape": shape_name, "mesh": mesh_tag,
               "status": "skipped", "reason": skip}
    else:
        try:
            res = lower_cell(arch, shape_name, multi_pod=multi_pod)
            res["status"] = "ok"
            print(f"[ok] {arch} x {shape_name} x {mesh_tag}: "
                  f"lower {res['lower_s']}s compile {res.get('compile_s', '-')}s "
                  f"mem {res.get('memory', {}).get('total_per_device_bytes', 0) / 2**30:.2f} GiB/dev")
        except Exception as e:  # noqa: BLE001 — a failed cell is a recorded bug
            res = {"arch": arch, "shape": shape_name, "mesh": mesh_tag,
                   "status": "error", "error": f"{type(e).__name__}: {e}",
                   "traceback": traceback.format_exc()[-3000:]}
            print(f"[FAIL] {arch} x {shape_name} x {mesh_tag}: {type(e).__name__}: {e}")
    with open(fname, "w") as f:
        json.dump(res, f, indent=1)
    return res


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--single-pod-only", action="store_true")
    ap.add_argument("--multi-pod-only", action="store_true")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else list(ARCHS)
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = [False, True]
    if args.single_pod_only:
        meshes = [False]
    if args.multi_pod_only or args.multi_pod:
        meshes = [True]

    n_fail = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                res = run_cell(arch, shape, mp, force=args.force)
                if res.get("status") == "error":
                    n_fail += 1
    print(f"done; failures: {n_fail}")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
