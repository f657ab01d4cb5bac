"""Quickstart: MRA-2 attention as a drop-in module.

    PYTHONPATH=src python examples/quickstart.py              # on a TPU
    PYTHONPATH=src python examples/quickstart.py --interpret  # elsewhere
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import AttentionSpec, MraConfig, full_attention, mra2_attention, self_attention
from repro.launch.device import enable_compile_cache, kernel_interpret


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--interpret", action="store_true",
                    help="run the Pallas kernel step in interpret mode "
                         "(required off-TPU)")
    args = ap.parse_args()
    enable_compile_cache()
    interpret = kernel_interpret(args.interpret)
    rng = np.random.default_rng(0)
    B, Hq, Hkv, N, D = 2, 8, 2, 1024, 64  # GQA: 8 query heads share 2 KV heads
    q = jnp.asarray(rng.standard_normal((B, Hq, N, D)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((B, Hkv, N, D)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((B, Hkv, N, D)), jnp.bfloat16)

    # 1) direct: the paper's MRA-2 with R={32, 1}, budget 4 blocks/row
    cfg = MraConfig(block_size=32, blocks_per_row=4)
    out = jax.jit(lambda q, k, v: mra2_attention(q, k, v, cfg))(q, k, v)
    ref = full_attention(q, k, v)
    err = float(jnp.linalg.norm((out - ref).astype(jnp.float32))
                / jnp.linalg.norm(ref.astype(jnp.float32)))
    print(f"MRA-2 (b=32, 4 blocks/row)  rel error vs softmax: {err:.4f}")

    # 2) budget sweep: accuracy/cost dial of the paper (Tab. 7)
    for bpr in (1, 2, 8, 16):
        c = MraConfig(block_size=32, blocks_per_row=bpr)
        o = mra2_attention(q, k, v, c)
        e = float(jnp.linalg.norm((o - ref).astype(jnp.float32))
                  / jnp.linalg.norm(ref.astype(jnp.float32)))
        frac = c.budget(N) * 32 * 32 / (N * N)
        print(f"  blocks/row={bpr:>2}  entries kept={frac:5.1%}  rel err={e:.4f}")

    # 3) through the model-facing dispatch (what the architectures use)
    spec = AttentionSpec(kind="mra2", block_size=32, blocks_per_row=4)
    out2 = self_attention(q, k, v, spec, causal=True)
    print("dispatch (causal mra2):", out2.shape, out2.dtype)

    # 4) the Pallas TPU kernel path (interpret mode only when asked for)
    cfg_k = MraConfig(block_size=32, blocks_per_row=4, use_kernel=True,
                      interpret=interpret)
    out3 = mra2_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                          v.astype(jnp.float32), cfg_k)
    print("kernel path max |diff| vs jnp path:",
          float(jnp.abs(out3 - mra2_attention(q.astype(jnp.float32),
                                              k.astype(jnp.float32),
                                              v.astype(jnp.float32),
                                              MraConfig(block_size=32, blocks_per_row=4))).max()))


if __name__ == "__main__":
    main()
