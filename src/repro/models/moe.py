"""Mixture-of-Experts block: top-k router + sort-based expert-parallel
dispatch (MaxText/megablocks style).

Dispatch is computed *per batch row* (each row of the data-parallel axis
routes its own T*k assignments into per-expert capacity buffers), so the
buffer tensor is (B, E, C, D) — sharded batch-over-data and experts-over-
model — and no (tokens, E, C) one-hot is ever materialized. Assignment uses
an argsort over expert ids + rank-within-expert (tokens beyond capacity are
dropped, standard Switch semantics), which lowers to TPU-friendly sorts and
scatters instead of giant one-hot einsums.

Router weights stay replicated/full-precision by default (< 0.01% of
params); expert weights are (E, K, N) stacks — quantizable as stacked
QTensors, exercised by the serving path.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.core.quantize import QTensor
from repro.models.layers import Runtime, dense, init_dense_weight, shard_hint

Params = dict[str, Any]

__all__ = ["moe_init", "moe_apply"]


def moe_init(key, d: int, f: int, num_experts: int, activation: str) -> Params:
    ks = jax.random.split(key, 4)
    e = num_experts
    p = {
        "router": init_dense_weight(ks[0], d, e),
        "up": jax.vmap(lambda k: init_dense_weight(k, d, f))(jax.random.split(ks[1], e)),
        "down": jax.vmap(lambda k: init_dense_weight(k, f, d))(jax.random.split(ks[2], e)),
    }
    if activation == "swiglu":
        p["gate"] = jax.vmap(lambda k: init_dense_weight(k, d, f))(jax.random.split(ks[3], e))
    return p


def _edense(x: jax.Array, w, rt: Runtime) -> jax.Array:
    """Per-expert dense: x (E, B, C, D) @ w (E, D, F) -> (E, B, C, F)."""
    if isinstance(w, QTensor):
        return jax.vmap(
            lambda xe, *leaves: dense(
                xe, QTensor(dict(zip(w.data.keys(), leaves)), w.meta), rt
            )
        )(x, *w.data.values())
    return jnp.einsum("ebcd,edf->ebcf", x.astype(rt.compute_dtype),
                      w.astype(rt.compute_dtype))


def _expert_ffn(p: Params, x: jax.Array, rt: Runtime, activation: str) -> jax.Array:
    if activation == "swiglu":
        h = jax.nn.silu(_edense(x, p["gate"], rt)) * _edense(x, p["up"], rt)
    elif activation == "relu2":
        h = jnp.square(jax.nn.relu(_edense(x, p["up"], rt)))
    else:
        h = jax.nn.gelu(_edense(x, p["up"], rt))
    h = shard_hint(h, rt, "experts", "batch", None, "ffn")
    return _edense(h, p["down"], rt)


def moe_apply(
    p: Params,
    x: jax.Array,  # (B, T, D)
    rt: Runtime,
    cfg,
) -> tuple[jax.Array, jax.Array]:
    """Returns (output (B, T, D), load-balancing aux loss)."""
    b, t, d = x.shape
    e, k = cfg.num_experts, cfg.experts_per_token
    cap = max(1, -(-int(rt.capacity_factor * t * k) // e))
    cap = min(cap, t * k)

    logits = dense(x, p["router"], rt).astype(jnp.float32)  # (B, T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, idx = jax.lax.top_k(probs, k)  # (B, T, k)
    gate_vals = gate_vals / (jnp.sum(gate_vals, axis=-1, keepdims=True) + 1e-9)

    # Switch aux loss: E * sum_e mean_tokens(P_e) * mean_tokens(assigned_e)
    me = jnp.mean(probs, axis=(0, 1))
    ce = jnp.mean(jax.nn.one_hot(idx[..., 0], e, dtype=jnp.float32), axis=(0, 1))
    aux = e * jnp.sum(me * ce)

    def dispatch_row(xg, idxg, gateg):
        """xg (T, D), idxg/gateg (T, k) -> buffer (E, C, D) + combine meta."""
        eid = idxg.reshape(-1)  # (T*k,)
        order = jnp.argsort(eid)
        s_eid = eid[order]
        # rank of each assignment within its expert (stable: sorted order)
        first = jnp.searchsorted(s_eid, s_eid, side="left")
        rank = jnp.arange(t * k, dtype=jnp.int32) - first.astype(jnp.int32)
        keep = rank < cap
        rankc = jnp.minimum(rank, cap - 1)
        tok = (order // k).astype(jnp.int32)
        gat = gateg.reshape(-1)[order]
        contrib = xg[tok].astype(rt.compute_dtype) * keep[:, None].astype(rt.compute_dtype)
        buf = jnp.zeros((e, cap, d), rt.compute_dtype).at[s_eid, rankc].add(contrib)
        return buf, (s_eid, rankc, tok, gat * keep)

    buf, meta = jax.vmap(dispatch_row)(x, idx, gate_vals)  # buf (B, E, C, D)
    buf = shard_hint(buf.swapaxes(0, 1), rt, "experts", "batch", None, None)

    out_buf = _expert_ffn(p, buf, rt, cfg.activation)  # (E, B, C, D)
    out_buf = shard_hint(out_buf, rt, "experts", "batch", None, None)

    def combine_row(bufg, m):
        """bufg (E, C, D); meta (T*k,)-arrays -> (T, D)."""
        s_eid, rankc, tok, w = m
        vals = bufg[s_eid, rankc] * w[:, None].astype(bufg.dtype)
        return jnp.zeros((t, d), bufg.dtype).at[tok].add(vals)

    if rt.rules is not None and rt.rules.assignments.get("experts") and rt.mesh is not None:
        out = _combine_ep_shardmap(out_buf, meta, rt, t, d, e)
    else:
        out = jax.vmap(combine_row)(out_buf.swapaxes(0, 1), meta)
    return out.astype(rt.compute_dtype), aux


def _combine_ep_shardmap(out_buf, meta, rt: Runtime, t: int, d: int, e: int):
    """Expert-parallel combine with the all-reduce at (T, D) width.

    The naive gather-from-E-sharded-buffer makes SPMD all-reduce the full
    (T*k, D) gathered tensor (each shard contributes zeros for remote
    experts). Doing the combine *inside* shard_map lets each shard gather
    only its local experts' outputs, scatter-add them into a local (T, D)
    partial, and psum THAT — k (=8 for the assigned MoEs) times fewer
    collective bytes (EXPERIMENTS.md §Perf cell B).

    out_buf: (E, B, C, D) sharded (experts->model, batch on B);
    meta arrays: (B, T*k) replicated over model."""
    from jax.sharding import PartitionSpec as P

    mesh = rt.mesh
    msize = mesh.shape["model"]
    e_local = e // msize
    batch_ax = rt.rules.assignments.get("batch")

    def local_combine(bufl, s_eid, rankc, tok, w):
        # bufl (E/m, B_loc, C, D); meta (B_loc, T*k)
        e_lo = jax.lax.axis_index("model") * e_local

        def one_row(bufr, se, rk, tk, ww):
            loc = se.astype(jnp.int32) - e_lo
            ok = (loc >= 0) & (loc < e_local)
            locc = jnp.clip(loc, 0, e_local - 1)
            vals = bufr[locc, rk] * (ww * ok).astype(bufr.dtype)[:, None]
            return jnp.zeros((t, d), bufr.dtype).at[tk].add(vals)

        part = jax.vmap(one_row, in_axes=(1, 0, 0, 0, 0))(bufl, s_eid, rankc, tok, w)
        return jax.lax.psum(part, "model")

    fn = jax.shard_map(
        local_combine, mesh=mesh,
        in_specs=(P("model", batch_ax), P(batch_ax), P(batch_ax),
                  P(batch_ax), P(batch_ax)),
        out_specs=P(batch_ax),
        check_vma=False)
    s_eid, rankc, tok, w = meta
    return fn(out_buf, s_eid, rankc, tok, w)
