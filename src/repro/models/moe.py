"""Mixture-of-Experts FFN with expert parallelism.

Dispatch is the sort + static-capacity-buffer formulation (MegaBlocks-style
grouping without ragged ops — every shape is static, so it jits and shards):

  1. top-k routing (per token),
  2. assignments sorted by expert id; position-in-expert via exclusive
     cumsum of counts; over-capacity assignments dropped (``mode='drop'``
     scatters — the standard TPU capacity-dropping semantics),
  3. dense per-expert matmuls on (E_local, C, d) buffers — *no* one-hot
     dispatch einsum, so HLO FLOPs equal active FLOPs (× capacity factor),
  4. combine via scatter-add weighted by the router gate.

Distribution ("replicated-psum" EP): inside a shard_map over the model axis
each device processes the full local-batch token set but only its own
E/|model| expert slice; partial outputs are psum'd. The all-to-all variant
is a §Perf iteration (EXPERIMENTS.md). Experts not divisible by the model
axis (granite's 40) fall back to per-expert d_ff tensor parallelism.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, MoESpec
from repro.distributed import mesh_utils
from .params import ParamSpec


def moe_specs(cfg: ModelConfig):
    m = cfg.moe
    d, E, f = cfg.d_model, m.num_experts, m.d_ff_expert
    return {
        "router": ParamSpec((d, E), ("d_model", None), dtype=cfg.pdt, scale=0.02),
        "wi": ParamSpec((E, d, f), ("experts", "d_model", "expert_ff"), dtype=cfg.pdt),
        "wg": ParamSpec((E, d, f), ("experts", "d_model", "expert_ff"), dtype=cfg.pdt),
        "wo": ParamSpec((E, f, d), ("experts", "expert_ff", "d_model"), dtype=cfg.pdt),
    }


def _route(x, wr, spec: MoESpec):
    """x (T, d) -> gates (T, k), idx (T, k), aux losses."""
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32), wr.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, spec.top_k)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    # Switch-style load-balance loss + router z-loss
    E = logits.shape[-1]
    me = jnp.mean(probs, axis=0)  # (E,)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(idx, E, dtype=jnp.float32), axis=1), axis=0
    )
    aux = {
        "load_balance": E * jnp.sum(me * ce) * spec.aux_loss_coef,
        "router_z": jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2) * spec.router_z_coef,
    }
    return gates, idx, aux


def _dispatch(x, idx, *, e0: int, e_local: int, capacity: int):
    """Sort assignments, build the (e_local, capacity, d) expert buffers.

    Returns (buf, meta) where meta carries the scatter coordinates for the
    combine step."""
    T, d = x.shape
    k = idx.shape[-1]
    flat_e = idx.reshape(-1)  # (T*k,)
    local_e = flat_e - e0
    in_range = (local_e >= 0) & (local_e < e_local)
    sort_key = jnp.where(in_range, local_e, e_local)  # out-of-range sorts last
    order = jnp.argsort(sort_key)  # (T*k,)
    se = sort_key[order]
    tok = order // k
    counts = jnp.bincount(se, length=e_local + 1)[:e_local]
    starts = jnp.concatenate([jnp.zeros((1,), counts.dtype), jnp.cumsum(counts)[:-1]])
    starts_pad = jnp.concatenate([starts, jnp.zeros((1,), starts.dtype)])
    slot = jnp.arange(se.shape[0]) - starts_pad[se]
    keep = (se < e_local) & (slot < capacity)
    e_scatter = jnp.where(keep, se, e_local)  # dropped -> out-of-bounds
    s_scatter = jnp.where(keep, slot, capacity)
    buf = jnp.zeros((e_local, capacity, d), x.dtype)
    buf = buf.at[e_scatter, s_scatter].set(x[tok], mode="drop")
    return buf, (order, e_scatter, s_scatter, keep, tok)


def _expert_ffn(buf, wi, wg, wo):
    adt = buf.dtype
    h = jnp.einsum("ecd,edf->ecf", buf, wi.astype(adt))
    g = jnp.einsum("ecd,edf->ecf", buf, wg.astype(adt))
    return jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * h, wo.astype(adt))


def _combine(y, meta, gates, T):
    order, e_scatter, s_scatter, keep, tok = meta
    adt = y.dtype
    gates_f = gates.reshape(-1)
    y_tok = y.at[e_scatter, s_scatter].get(mode="fill", fill_value=0)  # (T*k, d)
    y_tok = y_tok * (gates_f[order] * keep).astype(adt)[:, None]
    return jnp.zeros((T, y.shape[-1]), adt).at[tok].add(y_tok)


def _expert_compute(
    x, gates, idx, wi, wg, wo, *, e0: int, e_local: int, capacity: int
):
    """Local dense-expert compute for experts [e0, e0+e_local).

    x (T, d) fp32/bf16; returns (T, d) partial output.
    """
    buf, meta = _dispatch(x, idx, e0=e0, e_local=e_local, capacity=capacity)
    y = _expert_ffn(buf, wi, wg, wo)
    return _combine(y, meta, gates, x.shape[0])


def moe_block(x, p, cfg: ModelConfig):
    """x (B, S, d) -> (B, S, d), plus aux losses dict.

    Opens a shard_map over the model axis when a mesh with one is active.
    """
    B, S, d = x.shape
    spec = cfg.moe
    E = spec.num_experts
    mesh = mesh_utils.get_mesh()
    ep = mesh_utils.has_axis(mesh, "model") and E % mesh.shape["model"] == 0

    def local(xl, wr, wi, wg, wo, *, e0, e_local):
        T = xl.shape[0] * xl.shape[1]
        xt = xl.reshape(T, d)
        gates, idx, aux = _route(xt, wr, spec)
        cap = max(int(T * spec.top_k * spec.capacity_factor / E + 1), 4)
        out = _expert_compute(
            xt, gates, idx, wi, wg, wo, e0=e0, e_local=e_local, capacity=cap
        )
        return out.reshape(xl.shape), aux

    if mesh is None or not mesh_utils.has_axis(mesh, "model"):
        return local(x, p["router"], p["wi"], p["wg"], p["wo"], e0=0, e_local=E)

    # batch spec: shard over whatever data axes divide B (decode batches can
    # be smaller than the dp extent — fall back to replicated tokens then)
    dp = mesh_utils.dp_axes(mesh)
    import math as _math

    while dp and B % _math.prod(mesh.shape[a] for a in dp) != 0:
        dp = dp[1:]
    bspec = dp if dp else None

    def _finish(out, aux):
        out = jax.lax.psum(out, "model")
        # aux losses vary over the token (data) axes only — mean them there so
        # the result is replicated (satisfies out_specs=P()); they are already
        # invariant over "model" (routing uses replicated tokens + router).
        if dp:
            aux = jax.tree.map(lambda a: jax.lax.pmean(a, dp), aux)
        return out, aux

    if not ep:
        # TP fallback (experts not divisible by |model|): shard expert d_ff.
        def tp_body(xl, wr, wi, wg, wo):
            out, aux = local(xl, wr, wi, wg, wo, e0=0, e_local=E)
            return _finish(out, aux)

        return jax.shard_map(
            tp_body,
            mesh=mesh,
            in_specs=(P(bspec, None, None), P(), P(None, None, "model"),
                      P(None, None, "model"), P(None, "model", None)),
            out_specs=(P(bspec, None, None), P()),
        )(x, p["router"], p["wi"], p["wg"], p["wo"])

    ms = mesh.shape["model"]
    e_local = E // ms

    # a2a dispatch (§Perf K2): sequence-sharded tokens, all_to_all exchange to
    # expert owners and back. Requires S divisible by the model axis (decode
    # S=1 falls back to psum).
    if cfg.moe_dispatch == "a2a" and S % ms == 0:
        def a2a_body(xl, wr, wi, wg, wo):
            # xl: (B_loc, S/ms, d) sequence shard
            T = xl.shape[0] * xl.shape[1]
            xt = xl.reshape(T, d)
            gates, idx, aux = _route(xt, wr, spec)
            cap = max(int(T * spec.top_k * spec.capacity_factor / E + 1), 4)
            buf, meta = _dispatch(xt, idx, e0=0, e_local=E, capacity=cap)
            # (E, C, d) -> exchange expert groups -> (E/ms, ms*C, d)
            recv = jax.lax.all_to_all(buf, "model", split_axis=0, concat_axis=1,
                                      tiled=True)
            y = _expert_ffn(recv, wi, wg, wo)
            back = jax.lax.all_to_all(y, "model", split_axis=1, concat_axis=0,
                                      tiled=True)  # (E, C, d)
            out = _combine(back, meta, gates, T)
            if dp:
                aux = jax.tree.map(lambda a: jax.lax.pmean(a, dp + ("model",)), aux)
            else:
                aux = jax.tree.map(lambda a: jax.lax.pmean(a, ("model",)), aux)
            return out.reshape(xl.shape), aux

        return jax.shard_map(
            a2a_body,
            mesh=mesh,
            in_specs=(P(bspec, "model", None), P(), P("model", None, None),
                      P("model", None, None), P("model", None, None)),
            out_specs=(P(bspec, "model", None), P()),
        )(x, p["router"], p["wi"], p["wg"], p["wo"])

    def ep_body(xl, wr, wi, wg, wo):
        # xl: local batch, replicated over model; wi/wg/wo: this shard's experts
        shard = jax.lax.axis_index("model")
        e0 = shard * e_local
        out, aux = local(xl, wr, wi, wg, wo, e0=e0, e_local=e_local)
        return _finish(out, aux)

    return jax.shard_map(
        ep_body,
        mesh=mesh,
        in_specs=(P(bspec, None, None), P(), P("model", None, None),
                  P("model", None, None), P("model", None, None)),
        out_specs=(P(bspec, None, None), P()),
    )(x, p["router"], p["wi"], p["wg"], p["wo"])
