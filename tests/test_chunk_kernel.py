"""Differential suite for the fused Pallas chunk/decode serving kernel.

Pins ``kernels/chunk_attn.py`` (interpret mode — the TPU serving path run on
CPU) to the pure-jnp ``mra2_chunk_attention`` / ``mra2_decode_attention``
formulation across the axes where a data-dependent paged kernel can silently
go wrong (DESIGN.md §11): ring paging × int8 quantization × coarse_only ×
GQA × ragged lengths × chunk-vs-decode × MRA-2/MRA-2-s, plus the exact
softmax oracle at full budget and the engine-level token conformance test
(the jnp engine and the kernel engine must emit identical streams).
"""
from __future__ import annotations

import dataclasses
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.mra import MraConfig
from repro.core.mra_decode import (
    PyramidState,
    full_chunk_attention,
    identity_page_table,
    mra2_chunk_attention,
    mra2_decode_attention,
    paged_position_mask,
    quantize_kv,
)


@dataclasses.dataclass(frozen=True)
class Case:
    """One point of the serving-kernel differential sweep."""

    paged: bool = False        # ring layout (stream longer than the cache)
    quant: bool = False        # int8 pages + per-token scales
    coarse_only: bool = False  # m = 1: own block + pyramid background only
    group: int = 1             # GQA: Hq = group * Hkv
    ragged: bool = False       # per-slot lengths (incl. a zero-length slot)
    variant: str = "full"
    B: int = 2
    Hkv: int = 2
    S: int = 64
    D: int = 8
    b: int = 16
    m: int = 3
    seed: int = 0

    @property
    def id(self) -> str:
        return (
            f"{'ring' if self.paged else 'dense'}-{'int8' if self.quant else 'fp'}"
            f"-{'coarse' if self.coarse_only else f'm{self.m}'}-g{self.group}"
            f"-{'ragged' if self.ragged else 'full'}-{self.variant}"
        )


# every combination of the risky axes (64 cases x {decode, chunk})
SWEEP = [
    Case(paged=p, quant=qz, coarse_only=co, group=g, ragged=rg, variant=v,
         seed=i)
    for i, (p, qz, co, g, rg, v) in enumerate(
        itertools.product([False, True], [False, True], [False, True], [1, 2],
                          [False, True], ["full", "sparse"])
    )
]


def _cfgs(case: Case, mode: str = "auto"):
    kw = dict(block_size=case.b, causal=True, variant=case.variant)
    return (MraConfig(**kw),
            MraConfig(**kw, use_kernel=True, interpret=True,
                      kernel_mode=mode))


def make_case_inputs(case: Case, *, C: int = 1, min_len: int = 0):
    """(q, k, v, lengths, q_pos, page_blocks, k_scale, v_scale) for a case.

    ``min_len`` bounds the ragged lengths from below. The serving contract is
    ``q_pos <= lengths - 1`` (chunk queries are tokens already written to the
    cache); with ``min_len < C`` some q_pos run past the stream — fine for
    kernel↔jnp parity (identical math both sides) but out of contract for
    exact-oracle comparisons, which must pass ``min_len=C``.
    """
    r = np.random.default_rng(case.seed)
    B, Hkv, S, D, b = case.B, case.Hkv, case.S, case.D, case.b
    nb = S // b
    Hq = Hkv * case.group
    k = jnp.asarray(r.standard_normal((B, Hkv, S, D)), jnp.float32)
    v = jnp.asarray(r.standard_normal((B, Hkv, S, D)), jnp.float32)
    q = jnp.asarray(r.standard_normal((B, Hq, C, D)), jnp.float32)
    page_blocks = None
    if case.paged:
        # a 1.5x-capacity stream through the ring: logical blocks
        # nb/2 .. 3nb/2-1, block y on physical page y % nb
        lengths = np.full((B,), S + S // 2)
        page_blocks = jnp.roll(
            jnp.broadcast_to((jnp.arange(nb, dtype=jnp.int32) + nb // 2)[None],
                             (B, nb)), nb // 2, axis=1)
    elif case.ragged:
        lengths = np.array([min_len] + list(r.integers(max(min_len, 1), S + 1,
                                                       B - 1)))
    else:
        lengths = np.full((B,), S)
    lengths = jnp.asarray(lengths, jnp.int32)
    q_pos = jnp.maximum(lengths[:, None] - C, 0) + jnp.arange(C)
    k_scale = v_scale = None
    if case.quant:
        k, k_scale = quantize_kv(k)
        v, v_scale = quantize_kv(v)
    return q, k, v, lengths, q_pos, page_blocks, k_scale, v_scale


@pytest.mark.parametrize("case", SWEEP, ids=lambda c: c.id)
@pytest.mark.parametrize("mode", ["decode", "chunk"])
def test_kernel_matches_jnp(case: Case, mode: str):
    """Fused kernel == jnp path across the full risky-axis sweep."""
    C = 1 if mode == "decode" else 8
    q, k, v, lengths, q_pos, pb, ks, vs = make_case_inputs(case, C=C)
    m = 1 if case.coarse_only else case.m
    cfg, cfgk = _cfgs(case)
    kw = dict(decode_blocks=m, page_blocks=pb, k_scale=ks, v_scale=vs)
    if mode == "decode":
        ref = mra2_decode_attention(q, k, v, lengths, cfg, **kw)
        out = mra2_decode_attention(q, k, v, lengths, cfgk, **kw)
    else:
        ref = mra2_chunk_attention(q, k, v, lengths, q_pos, cfg, **kw)
        out = mra2_chunk_attention(q, k, v, lengths, q_pos, cfgk, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=1e-5)


# a small cross-section of the sweep re-run with the mode *forced* (the main
# sweep covers both instantiations through "auto": decode -> latency, chunk
# -> throughput; this pins the off-diagonal pairings — latency tiling on
# chunks, throughput tiling on single queries — without doubling wall time)
FORCED = [Case(), Case(paged=True, quant=True, seed=21),
          Case(ragged=True, group=2, seed=33),
          Case(quant=True, variant="sparse", coarse_only=True, seed=40)]


@pytest.mark.parametrize("case", FORCED, ids=lambda c: c.id)
@pytest.mark.parametrize("mode", ["latency", "throughput"])
@pytest.mark.parametrize("C,masked", [(1, False), (5, False), (5, True)],
                         ids=["C1", "C5", "C5-no-query"])
def test_kernel_forced_modes_match_jnp(case: Case, mode: str, C: int,
                                       masked: bool):
    """Each forced tile shape == jnp at both a decode (C=1) and a *ragged*
    chunk width (C=5: not a multiple of the throughput C_tile, so the padded
    tail rows must select nothing and slice away cleanly) — and on a chunk
    whose rows past 2 in slot 0 and every row of slot 1 carry no query
    (q_pos = -1, as prefill_chunk passes them), which both routes return
    as exact zeros."""
    q, k, v, lengths, q_pos, pb, ks, vs = make_case_inputs(case, C=C)
    if masked:
        q_pos = jnp.where(jnp.asarray([[2], [-1]]) >= jnp.arange(C), q_pos, -1)
    m = 1 if case.coarse_only else case.m
    cfg, cfgk = _cfgs(case, mode)
    kw = dict(decode_blocks=m, page_blocks=pb, k_scale=ks, v_scale=vs)
    ref = mra2_chunk_attention(q, k, v, lengths, q_pos, cfg, **kw)
    out = mra2_chunk_attention(q, k, v, lengths, q_pos, cfgk, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=1e-5)
    none = (np.asarray(q_pos) < 0)  # (B, C)
    for got in (out, ref):
        rows = np.asarray(got).transpose(0, 2, 1, 3)[none]
        assert (rows.size > 0) == masked
        assert np.abs(rows).max(initial=0.0) == 0.0


@pytest.mark.parametrize("mode", ["latency", "throughput"])
def test_kernel_oversubscribed_budget(mode: str):
    """m > live blocks: the padded selection slots (top_k returns m indices
    even when fewer pages are valid) must contribute nothing, in-kernel and
    in jnp alike — budget == nb with mostly-dead rings."""
    case = Case(m=4, seed=5)  # m == nb: every slot oversubscribed below
    q, k, v, lengths, q_pos, pb, ks, vs = make_case_inputs(case, C=5)
    lengths = jnp.asarray([1, 17], jnp.int32)  # 1 and 2 live blocks of 4
    q_pos = jnp.maximum(lengths[:, None] - 5, 0) + jnp.arange(5)
    cfg, cfgk = _cfgs(case, mode)
    kw = dict(decode_blocks=case.m)
    ref = mra2_chunk_attention(q, k, v, lengths, q_pos, cfg, **kw)
    out = mra2_chunk_attention(q, k, v, lengths, q_pos, cfgk, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=1e-5)
    # and against the exact oracle where the contract holds (slot 1: full
    # budget over its live prefix, C <= len): approximation == exact softmax
    exact = full_chunk_attention(q, k, v, lengths, q_pos)
    np.testing.assert_allclose(np.asarray(out)[1], np.asarray(exact)[1],
                               atol=1e-4)


@pytest.mark.parametrize("route", ["jnp", "latency", "throughput"])
def test_fresh_slot_zero_live_query_block_is_zero(route: str):
    """Regression (PR 7): a query whose block holds zero live tokens — a
    fresh slot attending before any cache write lands — must produce exact
    zeros. The old selection sentinel (``top_vals > NEG_INF * 0.5``) let the
    FORCE_BONUS of the dead own block pass the threshold, so the row
    attended stale cache garbage through the position mask."""
    case = Case(seed=13)
    q, k, v, _, _, _, _, _ = make_case_inputs(case, C=2)
    lengths = jnp.asarray([0, 37], jnp.int32)
    q_pos = jnp.asarray([[0, 1], [35, 36]], jnp.int32)  # slot 0: dead block
    cfg, cfgk = _cfgs(case, route if route != "jnp" else "auto")
    use = cfg if route == "jnp" else cfgk
    out = np.asarray(mra2_chunk_attention(q, k, v, lengths, q_pos, use,
                                          decode_blocks=case.m))
    assert np.abs(out[0]).max() == 0.0  # exact zeros, not garbage
    assert np.abs(out[1]).max() > 0.0  # the live slot still attends
    if route != "jnp":  # and the routes agree on the live slot
        ref = mra2_chunk_attention(q, k, v, lengths, q_pos, cfg,
                                   decode_blocks=case.m)
        np.testing.assert_allclose(out, np.asarray(ref), atol=2e-5, rtol=1e-5)


# --------------------------------------------------------------------------- #
# Rows with no query (q_pos = -1): what prefill_chunk passes for the rows of
# decoding slots and the rows past a ragged chunk's end (DESIGN.md §11).
# --------------------------------------------------------------------------- #
NO_QUERY_C = 12  # one full 8-row throughput tile + a padded 4-row one


def _no_query_inputs(kind: str):
    """(q, k, v, lengths, real q_pos, masked q_pos, kw) for a 3-slot chunk:
    slot 0 prefills all 12 rows, slot 1 decodes (no prompt token: every row
    is -1 in the masked q_pos), slot 2 ends its prompt after 5 rows, so its
    second throughput tile holds no query at all. ``kind`` is "fp", "int8"
    (quantized pages) or "upper" (an H-level hierarchy's resident tiles)."""
    case = Case(B=3, group=2, quant=kind == "int8", seed=17)
    q, k, v, _, _, _, ks, vs = make_case_inputs(case, C=NO_QUERY_C)
    C = NO_QUERY_C
    offsets = jnp.asarray([28, 50, 30], jnp.int32)
    num_valid = jnp.asarray([C, 0, 5], jnp.int32)
    lengths = offsets + num_valid
    real = offsets[:, None] + jnp.arange(C, dtype=jnp.int32)
    masked = jnp.where(jnp.arange(C) < num_valid[:, None], real, -1)
    kw = dict(decode_blocks=case.m, k_scale=ks, v_scale=vs)
    if kind == "upper":
        from repro.core.hier import HierUpper

        B, Hkv, S, D, b = case.B, case.Hkv, case.S, case.D, case.b
        nb, nu = S // b, 3
        kf = k.astype(jnp.float32)
        vf = v.astype(jnp.float32)
        mask = paged_position_mask(lengths, identity_page_table(B, nb), S, b)
        mask = mask.astype(jnp.float32)[:, None, :, None]
        r = np.random.default_rng(case.seed)
        upper = HierUpper(
            jnp.asarray(r.standard_normal((B, Hkv, nu, D)), jnp.float32),
            jnp.asarray(r.standard_normal((B, Hkv, nu, D)), jnp.float32),
            jnp.asarray([[16.0, 0.0, 5.0]] * B, jnp.float32))
        kw["pyramid"] = PyramidState(
            jnp.sum((kf * mask).reshape(B, Hkv, nb, b, D), axis=3),
            jnp.sum((vf * mask).reshape(B, Hkv, nb, b, D), axis=3), upper)
    return case, q, k, v, lengths, real, masked, kw


NO_QUERY_KINDS = ["fp", "int8", "upper"]


@pytest.mark.parametrize("kind", NO_QUERY_KINDS)
@pytest.mark.parametrize("mode", ["latency", "throughput"])
def test_no_query_rows_leave_valid_rows_bit_identical(kind: str, mode: str):
    """Setting the other rows' q_pos to -1 changes nothing in the rows that
    carry a query: same pages, same arithmetic, the same bits. The no-query
    rows come out as exact zeros on both routes: a whole tile of them (a
    decoding slot's chunk, the tail of a ragged one), those inside a tile
    that holds queries too, and an all -1 chunk."""
    case, q, k, v, lengths, real, masked, kw = _no_query_inputs(kind)
    cfg, cfgk = _cfgs(case, mode)
    before = np.asarray(mra2_chunk_attention(q, k, v, lengths, real, cfgk, **kw))
    after = np.asarray(mra2_chunk_attention(q, k, v, lengths, masked, cfgk,
                                            **kw))
    valid = np.asarray(masked) >= 0  # (B, C)
    for s in range(case.B):
        np.testing.assert_array_equal(after[s][:, valid[s]],
                                      before[s][:, valid[s]])
    assert np.abs(after[1]).max() == 0.0          # the decoding slot
    assert np.abs(after[2][:, 8:]).max() == 0.0   # slot 2's all-pad tile
    assert np.abs(after[2][:, 5:8]).max() == 0.0  # pad rows of a mixed tile
    assert np.abs(after[0]).min(axis=-1).max() > 0.0 and valid[0].all()
    ref = np.asarray(mra2_chunk_attention(q, k, v, lengths, masked, cfg, **kw))
    assert np.abs(ref.transpose(0, 2, 1, 3)[~valid]).max() == 0.0
    none = jnp.full_like(masked, -1)
    out = np.asarray(mra2_chunk_attention(q, k, v, lengths, none, cfgk, **kw))
    assert np.abs(out).max() == 0.0


@pytest.mark.parametrize("local_window", [None, 4])
def test_dense_routes_no_query_rows_are_zero(local_window):
    """The exact (non-MRA) chunk routes — full and local attention — see
    the same q_pos = -1 rows from prefill_chunk: they allow no key and must
    come out as finite zeros, not NaN, while query rows still attend."""
    case, q, k, v, lengths, _, masked, _ = _no_query_inputs("fp")
    out = np.asarray(full_chunk_attention(q, k, v, lengths, masked,
                                          local_window=local_window))
    assert np.isfinite(out).all()
    pad = ~(np.asarray(masked) >= 0)
    assert np.abs(out.transpose(0, 2, 1, 3)[pad]).max() == 0.0
    assert np.abs(out.transpose(0, 2, 1, 3)[~pad]).min(axis=-1).max() > 0.0


def test_bad_shapes_raise_value_errors():
    """Shape misuse fails with named shapes, not bare asserts (which vanish
    under ``python -O``) — S % b, GQA grouping, q_pos, kernel_mode."""
    case = Case()
    q, k, v, lengths, q_pos, _, _, _ = make_case_inputs(case, C=1)
    cfg, cfgk = _cfgs(case)
    with pytest.raises(ValueError, match="multiple of block_size"):
        mra2_chunk_attention(q, k[:, :, :60], v[:, :, :60], lengths, q_pos,
                             cfg, decode_blocks=2)
    with pytest.raises(ValueError, match="q_pos shape"):
        mra2_chunk_attention(q, k, v, lengths, jnp.zeros((2, 3), jnp.int32),
                             cfg, decode_blocks=2)
    q3 = jnp.concatenate([q, q[:, :1]], axis=1)  # 3 query heads, 2 KV heads
    with pytest.raises(ValueError, match="KV heads"):
        mra2_chunk_attention(q3, k, v, lengths, q_pos, cfg, decode_blocks=2)
    with pytest.raises(ValueError, match="kernel_mode"):
        mra2_chunk_attention(q, k, v, lengths, q_pos,
                             dataclasses.replace(cfgk, kernel_mode="warp"),
                             decode_blocks=2)


def test_kernel_full_budget_equals_exact_oracle():
    """Budget >= all live pages: the kernel == exact softmax attention —
    an implementation-independent anchor (same as the jnp-path pin)."""
    case = Case(ragged=True, group=2, seed=7)
    q, k, v, lengths, q_pos, pb, ks, vs = make_case_inputs(case, C=8, min_len=8)
    _, cfgk = _cfgs(case)
    out = mra2_chunk_attention(q, k, v, lengths, q_pos, cfgk,
                               decode_blocks=case.S // case.b)
    exact = full_chunk_attention(q, k, v, lengths, q_pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exact), atol=1e-4)


def test_kernel_decode_equals_chunk_c1():
    """Kernel route: decode is the C == 1 chunk, same as the jnp contract."""
    case = Case(ragged=True, group=2, seed=3)
    q, k, v, lengths, q_pos, pb, ks, vs = make_case_inputs(case, C=1)
    _, cfgk = _cfgs(case)
    dec = mra2_decode_attention(q, k, v, lengths, cfgk, decode_blocks=2)
    chk = mra2_chunk_attention(q, k, v, lengths, (lengths - 1)[:, None], cfgk,
                               decode_blocks=2)
    np.testing.assert_allclose(np.asarray(chk), np.asarray(dec), atol=1e-6)


def test_kernel_with_incremental_pyramid():
    """The engine's real dataflow: the pyramid block sums ride in instead of
    being recomputed from the cache; kernel == jnp on that path too."""
    case = Case(seed=11)
    q, k, v, lengths, q_pos, _, _, _ = make_case_inputs(case, C=1)
    B, Hkv, S, D, b = case.B, case.Hkv, case.S, case.D, case.b
    nb = S // b
    mask = paged_position_mask(lengths, identity_page_table(B, nb), S,
                               b).astype(jnp.float32)
    pyr = PyramidState(
        jnp.sum((k * mask[:, None, :, None]).reshape(B, Hkv, nb, b, D), axis=3),
        jnp.sum((v * mask[:, None, :, None]).reshape(B, Hkv, nb, b, D), axis=3))
    cfg, cfgk = _cfgs(case)
    ref = mra2_decode_attention(q, k, v, lengths, cfg, decode_blocks=2,
                                pyramid=pyr)
    out = mra2_decode_attention(q, k, v, lengths, cfgk, decode_blocks=2,
                                pyramid=pyr)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_pyramid_append_past_capacity_is_dropped():
    """Regression (PR 5): ``PyramidState.append`` at ``pos >= nb * block``
    used to scatter at an out-of-range block index, which JAX clamps to
    ``nb - 1`` — silently corrupting the last block's sums. Past-capacity
    appends must be no-ops per slot (ring streams that outlive the capacity
    go through ``ring_pyramid_update`` instead)."""
    r = np.random.default_rng(0)
    B, Hkv, D, nb, block = 2, 2, 4, 4, 8
    kn = r.standard_normal((B, Hkv, D)).astype(np.float32)
    vn = r.standard_normal((B, Hkv, D)).astype(np.float32)
    pyr = PyramidState.init(B, Hkv, nb, D)
    # slot 0 in capacity (lands in block 1), slot 1 exactly at capacity
    pyr = pyr.append(jnp.asarray(kn), jnp.asarray(vn),
                     jnp.asarray([block + 3, nb * block]), block)
    np.testing.assert_allclose(np.asarray(pyr.k_sum)[0, :, 1], kn[0], atol=0)
    assert np.abs(np.asarray(pyr.k_sum)[1]).max() == 0.0  # dropped, not clamped
    assert np.abs(np.asarray(pyr.v_sum)[1]).max() == 0.0
    # way past capacity: still a no-op, nothing NaNs or wraps
    pyr2 = pyr.append(jnp.asarray(kn), jnp.asarray(vn),
                      jnp.asarray([10 * nb * block, nb * block + 1]), block)
    np.testing.assert_array_equal(np.asarray(pyr2.k_sum), np.asarray(pyr.k_sum))
    np.testing.assert_array_equal(np.asarray(pyr2.v_sum), np.asarray(pyr.v_sum))


def test_kernel_is_forward_only():
    """The serving kernel must refuse differentiation loudly (training goes
    through the §3 block-sparse kernels, not this op)."""
    case = Case()
    q, k, v, lengths, q_pos, _, _, _ = make_case_inputs(case, C=1)
    _, cfgk = _cfgs(case)

    def loss(q):
        return jnp.sum(mra2_decode_attention(q, k, v, lengths, cfgk,
                                             decode_blocks=2))

    with pytest.raises(NotImplementedError, match="forward-only"):
        jax.grad(loss)(q)


# --------------------------------------------------------------------------- #
# Engine-level conformance: the kernel serves the same tokens (test_engine.py
# pins the jnp engine to the oracle; this pins the kernel engine to the jnp
# engine, closing the chain end-to-end through prefill_chunk / decode_step).
# --------------------------------------------------------------------------- #
def _engine_requests():
    from repro.serve import Request, SamplingParams

    return [
        Request(prompt=np.arange(1, 20), max_new_tokens=6,
                sampling=SamplingParams(temperature=0.9, seed=7)),
        Request(prompt=np.array([5, 11, 2]), max_new_tokens=2,
                sampling=SamplingParams(temperature=1.0, top_k=5, seed=3)),
        Request(prompt=np.arange(2, 12), max_new_tokens=4),  # greedy
    ]


def _no_query_requests():
    """5 requests over 3 slots at chunk 8: the prefills see 0, 1 and 2 (all
    the other) slots decoding, and most prompts end in a ragged chunk — the
    rows prefill_chunk attends as q_pos = -1."""
    from repro.serve import Request, SamplingParams

    return [Request(prompt=np.arange(1, 20), max_new_tokens=12),
            Request(prompt=np.array([5, 11, 2]), max_new_tokens=4),
            Request(prompt=np.arange(2, 12), max_new_tokens=8,
                    sampling=SamplingParams(temperature=0.9, seed=5)),
            Request(prompt=np.arange(3, 16), max_new_tokens=3),
            Request(prompt=np.arange(7, 28), max_new_tokens=2)]


def _smoke_engine_parts():
    from repro.configs import get_smoke_config
    from repro.models import get_model, init_params
    from repro.serve import EngineConfig

    cfg = get_smoke_config("qwen3-1.7b")
    params = init_params(get_model(cfg).param_specs(cfg), jax.random.PRNGKey(0))
    return cfg, params, EngineConfig(slots=3, max_len=64, chunk=8)


@pytest.mark.parametrize("requests", [_engine_requests, _no_query_requests],
                         ids=["sampled", "decoding-slots"])
def test_engine_kernel_path_matches_jnp_engine(requests):
    """Ragged continuous batching through the fused kernel emits identical
    token streams (chunked prefill + decode waves both route through it) —
    under the per-dispatch "auto" mode pick AND with either tile shape
    forced via EngineConfig.kernel_mode (DESIGN.md §11 dual-mode contract);
    also when prefills run beside 0, 1 and 2 decoding slots."""
    from repro.serve import Engine

    cfg, params, ecfg = _smoke_engine_parts()
    eng = Engine(cfg, params, ecfg)
    plans, prefill = [], eng._prefill

    def spy(params, tree, tokens, num_valid):
        plans.append(np.asarray(num_valid))
        return prefill(params, tree, tokens, num_valid)

    eng._prefill = spy
    by = {len(r.prompt): r.out for r in eng.run(requests())}
    if requests is _no_query_requests:
        # the traffic the case is for: 0, 1 and 2 of 3 slots without a
        # prompt token beside a prefill, and ragged chunk ends
        assert {0, 1, 2} <= {int((nv == 0).sum()) for nv in plans}
        assert any(0 < v < ecfg.chunk for nv in plans for v in nv)
    kcfg = cfg.replace(attn_use_kernel=True, attn_interpret=True)
    for mode in ("auto", "latency", "throughput"):
        got = Engine(kcfg, params, ecfg.replace(kernel_mode=mode)).run(
            requests())
        assert sorted(len(r.prompt) for r in got) == sorted(by)
        for r in got:
            np.testing.assert_array_equal(r.out, by[len(r.prompt)],
                                          err_msg=f"kernel_mode={mode}")


def test_engine_kernel_prefill_lowered_once():
    """One jitted prefill program serves every mix of rows with and without
    a prompt token: the mask travels as data, so it is lowered once (counted
    through ``jax.monitoring``, as bench/run.py counts programs lowered)."""
    from repro.serve import Engine
    from repro.serve import engine as engine_mod

    cfg, params, ecfg = _smoke_engine_parts()
    kcfg = cfg.replace(attn_use_kernel=True, attn_interpret=True)
    engine_mod._make_engine_fns.cache_clear()  # a fresh prefill to lower
    lowered = []

    def on(name, _secs, fun_name="", **_kw):
        if (name == "/jax/core/compile/jaxpr_to_mlir_module_duration"
                and fun_name == "jit(prefill_chunk)"):
            lowered.append(fun_name)

    jax.monitoring.register_event_duration_secs_listener(on)
    try:
        eng = Engine(kcfg, params, ecfg)
        eng.run(_no_query_requests())
    finally:
        jax.monitoring.unregister_event_duration_listener(on)
    assert eng.stats["prefill_dispatches"] >= 5
    assert lowered == ["jit(prefill_chunk)"]


def test_engine_rejects_unknown_kernel_mode():
    from repro.configs import get_smoke_config
    from repro.models import get_model, init_params
    from repro.serve import Engine, EngineConfig

    cfg = get_smoke_config("qwen3-1.7b")
    params = init_params(get_model(cfg).param_specs(cfg), jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="kernel_mode"):
        Engine(cfg, params, EngineConfig(slots=1, kernel_mode="fast"))


def test_engine_kernel_path_speculative_matches_jnp_engine():
    """Speculative serving through the kernel: the coarse-only draft steps,
    the chunked verify dispatch, and ring eviction all hit the fused path
    and still emit the jnp engine's exact tokens (DESIGN.md §10 + §11) —
    in the "auto" per-dispatch pick and with either tile shape forced."""
    from repro.configs import get_smoke_config
    from repro.models import get_model, init_params
    from repro.serve import Engine, EngineConfig, Request

    cfg = get_smoke_config("qwen3-1.7b")
    params = init_params(get_model(cfg).param_specs(cfg), jax.random.PRNGKey(0))

    def reqs():
        return [Request(prompt=np.arange(1, 9), max_new_tokens=20),  # evicts
                Request(prompt=np.array([5, 11, 2]), max_new_tokens=6)]

    ecfg = EngineConfig(slots=2, max_len=32, chunk=8, spec_k=3)
    ref = Engine(cfg, params, ecfg).run(reqs())
    by = {len(r.prompt): r.out for r in ref}
    kcfg = cfg.replace(attn_use_kernel=True, attn_interpret=True)
    for mode in ("auto", "latency", "throughput"):
        eng = Engine(kcfg, params, ecfg.replace(kernel_mode=mode))
        got = eng.run(reqs())
        for r in got:
            np.testing.assert_array_equal(r.out, by[len(r.prompt)],
                                          err_msg=f"kernel_mode={mode}")
        assert eng.stats["spec_rounds"] > 0
