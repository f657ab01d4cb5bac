"""End-to-end driver: train a small causal LM with MRA-2 attention and compare
against exact-softmax attention on the same data.

Default preset trains a ~15M-param model for a few hundred steps on the
synthetic corpus (CPU-feasible); --preset full is the 100M-class config for
real hardware.

    PYTHONPATH=src python examples/train_lm.py --steps 200
"""
import argparse

from repro.configs.base import ModelConfig, ShapeCfg
from repro.core.attention import AttentionSpec
from repro.train import TrainConfig, train

PRESETS = {
    # ~15M params: CPU-runnable end-to-end demo
    "small": dict(num_layers=4, d_model=256, num_heads=8, kv_heads=4, d_ff=1024,
                  vocab=8192, head_dim=32, seq=256, batch=8),
    # ~110M params: the "train ~100M for a few hundred steps" driver (device-sized)
    "full": dict(num_layers=12, d_model=768, num_heads=12, kv_heads=12, d_ff=3072,
                 vocab=32768, head_dim=64, seq=1024, batch=32),
}


def build_cfg(p, kind: str) -> ModelConfig:
    return ModelConfig(
        name=f"train-lm-{kind}",
        family="dense",
        num_layers=p["num_layers"], d_model=p["d_model"], num_heads=p["num_heads"],
        kv_heads=p["kv_heads"], d_ff=p["d_ff"], vocab=p["vocab"],
        head_dim=p["head_dim"],
        attention=AttentionSpec(kind=kind, block_size=32, blocks_per_row=4),
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="small", choices=list(PRESETS))
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--attention", default="mra2,full",
                    help="comma-separated attention kinds to train")
    ap.add_argument("--use-kernel", action="store_true",
                    help="route MRA attention through the fused Pallas "
                         "fwd+bwd kernels")
    ap.add_argument("--interpret", action="store_true",
                    help="run the --use-kernel kernels in interpret mode "
                         "(required off-TPU)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--mesh", default="1",
                    help="device mesh 'D' or 'DxM' (data x model; default 1 = "
                         "single device; attention shards via shard_map)")
    args = ap.parse_args()
    from repro.launch.device import enable_compile_cache, kernel_interpret
    from repro.launch.mesh import parse_mesh

    enable_compile_cache()
    interpret = args.use_kernel and kernel_interpret(args.interpret)
    mesh = parse_mesh(args.mesh)

    p = PRESETS[args.preset]
    shape = ShapeCfg("train", p["seq"], p["batch"], "train")
    curves = {}
    for kind in args.attention.split(","):
        cfg = build_cfg(p, kind)
        tc = TrainConfig(steps=args.steps, lr=1e-3, warmup=20, log_every=20,
                         ckpt_dir=args.ckpt_dir and f"{args.ckpt_dir}/{kind}",
                         use_kernel=args.use_kernel or None,
                         kernel_interpret=interpret,
                         shard_attention=True if mesh is not None else None)
        hist = []
        print(f"=== training with attention={kind} ===")
        train(cfg, shape, tc, mesh=mesh,
              on_metrics=lambda s, m: hist.append(m["loss"]))
        curves[kind] = hist

    print("\nfinal losses:")
    for kind, hist in curves.items():
        k = max(len(hist) // 10, 1)
        print(f"  {kind:8s} start={sum(hist[:k])/k:.4f} "
              f"final={sum(hist[-k:])/k:.4f}")
    if "mra2" in curves and "full" in curves:
        k = max(len(curves["mra2"]) // 10, 1)
        gap = sum(curves["mra2"][-k:]) / k - sum(curves["full"][-k:]) / k
        print(f"  MRA-2 vs full final-loss gap: {gap:+.4f} "
              "(paper Tab. 2: MRA-2 trains on par with softmax attention)")


if __name__ == "__main__":
    main()
