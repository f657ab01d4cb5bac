"""The Pallas kernels compiled for a described TPU v5e at qwen3-1.7b widths.

Interpret mode runs a kernel's body but never its lowering: block shapes that
break the chip's (8, 128) tiling, selects over i1 vectors or misaligned DMA
slices only fail in Mosaic. These tests compile each kernel of the serving
and training paths for a ``v5e:2x2`` topology described by the installed
TPU compiler — no chip attached, nothing runs — and check that the program
holds the kernel. Every TPU compile test lives in this one file: describing
the topology loads libtpu, which one process at a time may hold, so it
happens only inside the module fixture below.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.attention import AttentionSpec, chunk_attention
from repro.core.hier import HierUpper
from repro.core.mra_decode import PyramidState
from repro.kernels.ops import block_sparse_attention

# qwen3-1.7b serving widths: 4 slots x 4096-token ring (32 pages of 128),
# 16 query heads over 8 KV heads of dim 128, decode budget 16 pages
B, HQ, HKV, D, BLOCK, MAX_LEN, BUDGET, CHUNK = 4, 16, 8, 128, 128, 4096, 16, 512
NU = 9  # collapsed-level entries + tail of an H=3 hierarchy


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip cannot be read back from the persistent
    # cache without one; keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile_text(fn, args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _serving_args(one_chip, C, kv_dtype):
    nb = MAX_LEN // BLOCK

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return (s((B, HQ, C, D), jnp.bfloat16), s((B, HKV, MAX_LEN, D), kv_dtype),
            s((B, HKV, MAX_LEN, D), kv_dtype), s((B,), jnp.int32),
            s((B, C), jnp.int32), s((B, nb), jnp.int32),
            s((B, HKV, MAX_LEN), jnp.float32), s((B, HKV, MAX_LEN), jnp.float32),
            s((B, HKV, nb, D), jnp.float32), s((B, HKV, nb, D), jnp.float32),
            s((B, HKV, NU, D), jnp.float32), s((B, HKV, NU, D), jnp.float32),
            s((B, NU), jnp.float32))


def _serving_fn(mode, quant, upper):
    spec = AttentionSpec(kind="mra2", block_size=BLOCK, decode_blocks=BUDGET,
                         use_kernel=True, kernel_mode=mode)

    def fn(q, k, v, lengths, q_pos, pb, ks, vs, ksum, vsum, hk, hv, hcnt):
        pyr = PyramidState(ksum, vsum,
                           HierUpper(hk, hv, hcnt) if upper else None)
        return chunk_attention(q, k, v, lengths, q_pos, spec, pyramid=pyr,
                               page_blocks=pb, k_scale=ks if quant else None,
                               v_scale=vs if quant else None)

    return fn


@pytest.mark.parametrize("mode,C,name", [
    # decode wave -> latency (single-query tiles)
    ("auto", 1, "mra_chunk_attn_latency"),
    # chunked prefill -> throughput (8-query tiles)
    ("auto", CHUNK, "mra_chunk_attn_throughput"),
    # a chunk forced through single-query tiles
    ("latency", 16, "mra_chunk_attn_latency"),
], ids=["latency-decode", "throughput-chunk", "latency-forced-chunk"])
def test_serving_kernel_compiles(one_chip, mode, C, name):
    """The kernel compiles, and each tile mode's custom call carries its own
    name, so a device trace can tell the two instantiations apart."""
    text = _compile_text(_serving_fn(mode, quant=False, upper=False),
                         _serving_args(one_chip, C, jnp.bfloat16))
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert calls and all(f"%{name}" in line for line in calls)


@pytest.mark.parametrize("C", [1, CHUNK], ids=["decode", "chunk"])
def test_serving_kernel_int8_cache_compiles(one_chip, C):
    text = _compile_text(_serving_fn("auto", quant=True, upper=False),
                         _serving_args(one_chip, C, jnp.int8))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("C", [1, CHUNK], ids=["decode", "chunk"])
def test_serving_kernel_with_upper_compiles(one_chip, C):
    text = _compile_text(_serving_fn("auto", quant=False, upper=True),
                         _serving_args(one_chip, C, jnp.bfloat16))
    assert "tpu_custom_call" in text


def _training_args(one_chip):
    # one sequence of qwen3-1.7b heads at train_4k: b=128, d=128, n=4096,
    # 4 selected key blocks per query block
    n, G, d, b = 4096, HQ // HKV, D, BLOCK
    nb, m = n // b, 4 * (n // b)

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return (s((HKV * G, n, d), jnp.bfloat16), s((HKV, n, d), jnp.bfloat16),
            s((HKV, n, d), jnp.bfloat16), s((HKV * G, nb), jnp.float32),
            s((HKV * G, m), jnp.int32), s((HKV * G, m), jnp.int32),
            s((HKV * G, m), jnp.int32), s((HKV, n), jnp.int32))


def _training_fwd(q, k, v, c, x, y, f, km):
    return block_sparse_attention(q, k, v, c, x, y, f, km, scale=D ** -0.5,
                                  block_size=BLOCK)


def _training_loss(*args):
    out, rowsum, _ = _training_fwd(*args)
    return jnp.sum(out) + jnp.sum(rowsum)


@pytest.mark.parametrize("fn,kernels", [
    (_training_fwd, 1),
    (jax.grad(_training_loss, argnums=(0, 1, 2)), 3),  # fwd + dq + dk/dv
], ids=["fwd", "fwd+bwd"])
def test_training_kernels_compile(one_chip, fn, kernels):
    text = _compile_text(fn, _training_args(one_chip))
    assert text.count("tpu_custom_call") >= kernels
