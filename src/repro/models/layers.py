"""Transformer building blocks — pure JAX, quantization-aware.

Every matmul weight flows through :func:`dense`, which dispatches on the
leaf type: a plain ``jax.Array`` (training / fp serving) or a
:class:`~repro.core.quantize.QTensor` (ITQ3_S-family quantized serving).
That single seam is how the paper's format becomes a first-class feature of
the whole framework: any architecture in the zoo can be served quantized by
mapping ``quantize`` over its parameter tree.

Attention uses query-chunked softmax (scan over query blocks, full-width
keys) so 32k-token prefill never materializes a (T, T) score tensor; KV
cache layout is (B, KV_heads, T, head_dim) to give the sharding layer a
clean choice between head-sharding and sequence-sharding (see
sharding/rules.py).
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core.qlinear import qmatmul
from repro.core.quantize import QTensor
from repro.kernels.attn_decode import decode_attn_q8, prefill_attn_q8
from repro.serve.kv_quant import kv_encode

__all__ = [
    "Runtime", "dense", "norm_apply", "rope", "mlp_init", "mlp_apply",
    "attention_init", "attention_apply", "init_dense_weight", "shard_hint",
]

Params = dict[str, Any]


@dataclasses.dataclass(frozen=True)
class Runtime:
    """Execution-time knobs threaded through every apply function."""

    compute_dtype: Any = jnp.bfloat16
    quant_mode: str = "activations"  # qmatmul mode for QTensor weights
    backend: str = "auto"  # qmatmul backend: auto | ref | pallas
    use_kernel: bool = False  # deprecated: force backend="pallas"
    tile_m: Any = None  # Pallas tile override; None = autotune cache/defaults
    tile_n: Any = None
    autotune: bool = False  # eagerly tune kernel tiles on engine boot (TPU)
    attn_chunk: int = 512  # query-chunk size for softmax attention
    attn_tile_q: Any = None  # quantized-cache attention query-tile; None = default
    attn_tile_k: Any = None  # quantized-cache attention key-tile; None = default
    capacity_factor: float = 1.25  # MoE expert capacity factor
    remat: bool = False  # rematerialize each layer (training)
    remat_policy: str = "none"  # none | dots  (what each layer may save)
    decode_token_cache: bool = True  # O(1)-byte decode cache writes (perf log A2)
    kv_quant: bool = False  # rotated-int8 KV cache (serve/kv_quant.py codec)
    # W3A8 integer compute path: rotate + int8-quantize activations and
    # contract against the ternary codes with int32 accumulation
    # (core/act_quant.py). Off by default — the float path stays
    # bit-identical to historical streams; QMeta.act_quant opts individual
    # weight paths out even when this is on.
    act_quant: bool = False
    rwkv_mode: str = "chunked"  # chunked (MXU) | scan (stepwise reference)
    rules: Any = None  # sharding.rules.Rules | None
    mesh: Any = None
    # Tensor-parallel serving: run quantized matmuls / fused cache attention
    # as explicit shard_maps over the mesh (serve/tp.py) instead of leaving
    # the partitioning to GSPMD. Required on real TPU (GSPMD cannot split a
    # pallas_call); optional on CPU/ref where both paths are bit-identical.
    tp_shard_map: bool = False


def shard_hint(x: jax.Array, rt: Runtime, *names: Optional[str]) -> jax.Array:
    """Apply a logical sharding constraint if rules are active."""
    if rt.rules is None:
        return x
    return rt.rules.constrain(x, names, mesh=rt.mesh)


def dense(x: jax.Array, w, rt: Runtime, bias=None) -> jax.Array:
    """``x @ w (+ bias)`` with QTensor dispatch (the quantization seam).

    The ref-vs-Pallas choice lives inside :func:`qmatmul` — this seam only
    forwards the Runtime knobs, so every registered format (and every
    future one) serves through the same line of code."""
    if isinstance(w, QTensor):
        backend = "pallas" if rt.use_kernel else rt.backend
        if rt.tp_shard_map and rt.rules is not None:
            from repro.serve import tp as tp_mod  # lazy: layers <-> serve
            y = tp_mod.tp_qmatmul(x, w, rt.rules, mode=rt.quant_mode,
                                  backend=backend,
                                  compute_dtype=rt.compute_dtype,
                                  tm=rt.tile_m, tn=rt.tile_n,
                                  act_quant=rt.act_quant)
        else:
            y = qmatmul(x, w, mode=rt.quant_mode, backend=backend,
                        compute_dtype=rt.compute_dtype,
                        tm=rt.tile_m, tn=rt.tile_n,
                        act_quant=rt.act_quant)
    else:
        y = jnp.matmul(x.astype(rt.compute_dtype), w.astype(rt.compute_dtype))
    if bias is not None:
        y = y + bias.astype(y.dtype)
    return y


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def init_dense_weight(key, k: int, n: int, dtype=jnp.float32) -> jax.Array:
    std = 1.0 / math.sqrt(k)
    return jax.random.truncated_normal(key, -3, 3, (k, n), dtype) * std


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def norm_init(d: int, kind: str) -> Params:
    p = {"scale": jnp.ones((d,), jnp.float32)}
    if kind == "layernorm":
        p["bias"] = jnp.zeros((d,), jnp.float32)
    return p


def norm_apply(p: Params, x: jax.Array, kind: str, eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    if kind == "rmsnorm":
        x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    elif kind == "layernorm":
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.var(x, axis=-1, keepdims=True)
        x = (x - mu) * jax.lax.rsqrt(var + eps)
    else:
        raise ValueError(f"unknown norm {kind!r}")
    x = x * p["scale"]
    if "bias" in p:
        x = x + p["bias"]
    return x.astype(dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope(x: jax.Array, positions: jax.Array, theta: float, pct: float = 1.0) -> jax.Array:
    """Rotary embedding on the trailing head_dim of x (..., T, HD).

    ``positions``: (..., T) int32 absolute positions. ``pct`` < 1 rotates
    only the leading fraction of head_dim (stablelm partial rotary)."""
    hd = x.shape[-1]
    rot = int(hd * pct)
    rot -= rot % 2
    if rot == 0:
        return x
    xr, xp = x[..., :rot], x[..., rot:]
    half = rot // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., T, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = xr[..., :half], xr[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([out.astype(x.dtype), xp], axis=-1)


# ---------------------------------------------------------------------------
# MLP (swiglu | gelu | relu2)
# ---------------------------------------------------------------------------

def mlp_init(key, d: int, f: int, activation: str) -> Params:
    ks = jax.random.split(key, 3)
    p = {"down": init_dense_weight(ks[2], f, d)}
    if activation == "swiglu":
        p["gate"] = init_dense_weight(ks[0], d, f)
        p["up"] = init_dense_weight(ks[1], d, f)
    else:
        p["up"] = init_dense_weight(ks[1], d, f)
    return p


def mlp_apply(p: Params, x: jax.Array, rt: Runtime, activation: str) -> jax.Array:
    if activation == "swiglu":
        h = jax.nn.silu(dense(x, p["gate"], rt)) * dense(x, p["up"], rt)
    elif activation == "gelu":
        h = jax.nn.gelu(dense(x, p["up"], rt))
    elif activation == "relu2":
        h = jnp.square(jax.nn.relu(dense(x, p["up"], rt)))
    else:
        raise ValueError(f"unknown activation {activation!r}")
    h = shard_hint(h, rt, "batch", "seq", "ffn")
    return dense(h, p["down"], rt)


# ---------------------------------------------------------------------------
# Attention (GQA, query-chunked softmax, optional cross-attention)
# ---------------------------------------------------------------------------

def attention_init(key, d: int, heads: int, kv_heads: int, head_dim: int,
                   qkv_bias: bool = False) -> Params:
    ks = jax.random.split(key, 4)
    p = {
        "wq": init_dense_weight(ks[0], d, heads * head_dim),
        "wk": init_dense_weight(ks[1], d, kv_heads * head_dim),
        "wv": init_dense_weight(ks[2], d, kv_heads * head_dim),
        "wo": init_dense_weight(ks[3], heads * head_dim, d),
    }
    if qkv_bias:
        p["bq"] = jnp.zeros((heads * head_dim,), jnp.float32)
        p["bk"] = jnp.zeros((kv_heads * head_dim,), jnp.float32)
        p["bv"] = jnp.zeros((kv_heads * head_dim,), jnp.float32)
    return p


def _sdpa_chunked(q, k, v, rt: Runtime, *, causal: bool, q_offset=None,
                  kv_len=None):
    """q (B, KV, G, Tq, HD); k,v (B, KV, Tk, HD) -> (B, KV, G, Tq, HD).

    Scans over query chunks; each chunk sees the full key width, with a
    causal mask from absolute positions (q_offset (B,) + local index).
    kv_len (B,) masks out unwritten cache slots during decode — positions
    are per-batch-row vectors so slot-batched serving works ragged."""
    b, kvh, g, tq, hd = q.shape
    tk = k.shape[2]
    scale = 1.0 / math.sqrt(hd)
    # keep K/V in their storage dtype (bf16): the MXU accumulates in f32
    # via preferred_element_type, so upcasting the whole 32k cache per
    # layer (2x its bytes in pure convert traffic) buys nothing.
    kf, vf = k, v
    kpos = jnp.arange(tk)
    if q_offset is None:
        q_offset = jnp.zeros((b,), jnp.int32)

    chunk = max(1, min(rt.attn_chunk, tq))
    pad = (-tq) % chunk
    if pad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, 0), (0, pad), (0, 0)))
    nq = q.shape[3] // chunk
    qc = q.reshape(b, kvh, g, nq, chunk, hd)
    qc = jnp.moveaxis(qc, 3, 0)  # (nq, B, KV, G, chunk, HD)

    def one_chunk(ci, qi):
        s = jnp.einsum("bkgqd,bktd->bkgqt", qi.astype(kf.dtype), kf,
                       preferred_element_type=jnp.float32) * scale
        # masks broadcast as (B, 1, 1, chunk, tk)
        mask = jnp.ones((b, 1, 1, chunk, tk), bool)
        if causal:
            qpos = q_offset[:, None] + ci * chunk + jnp.arange(chunk)  # (B, chunk)
            mask = mask & (kpos[None, None, None, None, :]
                           <= qpos[:, None, None, :, None])
        if kv_len is not None:
            mask = mask & (kpos[None, None, None, None, :]
                           < kv_len[:, None, None, None, None])
        s = jnp.where(mask, s, -1e30)
        w = jax.nn.softmax(s, axis=-1)  # f32 softmax
        return jnp.einsum("bkgqt,bktd->bkgqd", w.astype(vf.dtype), vf,
                          preferred_element_type=jnp.float32)

    if nq == 1:
        out = one_chunk(0, qc[0])[None]
    else:
        # checkpoint each chunk: backward recomputes scores/softmax instead
        # of saving (B, KV, G, chunk, Tk) residuals per chunk (flash-style)
        body = jax.checkpoint(lambda args: one_chunk(*args))
        out = jax.lax.map(body, (jnp.arange(nq), qc))
    out = jnp.moveaxis(out, 0, 3).reshape(b, kvh, g, nq * chunk, hd)
    return out[..., :tq, :].astype(rt.compute_dtype)


def _decode_q8(q, cache, k_tok, v_tok, kv_len, rt: Runtime):
    """Quantized-cache decode attention, shard_mapped over kv_heads when
    tensor-parallel serving is active (serve/tp.py)."""
    if rt.tp_shard_map and rt.rules is not None:
        from repro.serve import tp as tp_mod  # lazy: layers <-> serve
        return tp_mod.tp_decode_attn_q8(q, cache, k_tok, v_tok, kv_len,
                                        rt.rules, backend=rt.backend,
                                        tt=rt.attn_tile_k)
    return decode_attn_q8(q, cache, k_tok, v_tok, kv_len,
                          backend=rt.backend, tt=rt.attn_tile_k)


def _prefill_q8(q, cache, kv_len, q_offset, rt: Runtime):
    """Quantized-cache prefill attention, shard_mapped under TP."""
    if rt.tp_shard_map and rt.rules is not None:
        from repro.serve import tp as tp_mod  # lazy: layers <-> serve
        return tp_mod.tp_prefill_attn_q8(q, cache, kv_len, q_offset,
                                         rt.rules, backend=rt.backend,
                                         tq=rt.attn_tile_q,
                                         tt=rt.attn_tile_k)
    return prefill_attn_q8(q, cache, kv_len, q_offset, backend=rt.backend,
                           tq=rt.attn_tile_q, tt=rt.attn_tile_k)


def attention_apply(
    p: Params,
    x: jax.Array,  # (B, T, D)
    rt: Runtime,
    cfg,
    *,
    causal: bool = True,
    cache: Optional[Params] = None,  # {"k","v": (B, KV, S, HD)}
    pos: int | jax.Array = 0,
    memory: Optional[jax.Array] = None,  # cross-attention source (B, S, D)
    cross: bool = False,
    token_cache: bool = False,  # decode: return token K/V, don't rewrite cache
) -> tuple[jax.Array, Optional[Params]]:
    """Returns (output (B, T, D), updated cache or None).

    Self-attention (cross=False): RoPE on q/k, causal, optional rolling KV
    cache written at ``pos``. Cross-attention (cross=True): K/V projected
    from ``memory`` when given (train / prefill, cache overwritten), or read
    straight from the cache (decode)."""
    b, t, d = x.shape
    h, kvh = cfg.num_heads, cfg.num_kv_heads
    hd = cfg.resolved_head_dim
    g = h // kvh

    q = dense(x, p["wq"], rt, p.get("bq"))
    q = q.reshape(b, t, kvh, g, hd)

    if cross:
        if memory is not None:
            k = dense(memory, p["wk"], rt).reshape(b, memory.shape[1], kvh, hd)
            v = dense(memory, p["wv"], rt).reshape(b, memory.shape[1], kvh, hd)
            k, v = k.swapaxes(1, 2), v.swapaxes(1, 2)
            new_cache = None
            if cache is not None:
                new_cache = {"k": k.astype(cache["k"].dtype),
                             "v": v.astype(cache["v"].dtype)}
        else:
            if cache is None:
                raise ValueError("cross-attention decode needs cached memory K/V")
            k, v = cache["k"], cache["v"]
            new_cache = cache
        q = q.reshape(b, t, kvh * g, hd).swapaxes(1, 2).reshape(b, kvh, g, t, hd)
        q = shard_hint(q, rt, "batch", "kv_heads", None, None, None)
        out = _sdpa_chunked(q, k, v, rt, causal=False, q_offset=0, kv_len=None)
        out = out.reshape(b, h, -1, hd)[:, :, :t, :].swapaxes(1, 2).reshape(b, t, h * hd)
        return dense(out, p["wo"], rt), new_cache

    # ---- self-attention ----
    k = dense(x, p["wk"], rt, p.get("bk")).reshape(b, t, kvh, hd)
    v = dense(x, p["wv"], rt, p.get("bv")).reshape(b, t, kvh, hd)

    # positions are per-batch-row (ragged slot-batched serving); scalars
    # broadcast to a vector.
    pos_vec = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    qpos = pos_vec[:, None] + jnp.arange(t)  # (B, T)
    q = rope(q.reshape(b, t, kvh * g, hd).swapaxes(1, 2),
             qpos[:, None, :], cfg.rope_theta, cfg.rotary_pct)  # (B, H, T, HD)
    q = q.reshape(b, kvh, g, t, hd)
    k = rope(k.swapaxes(1, 2), qpos[:, None, :], cfg.rope_theta, cfg.rotary_pct)
    v = v.swapaxes(1, 2)  # (B, KV, T, HD)

    q = shard_hint(q, rt, "batch", "kv_heads", None, None, None)
    kv_len = None
    new_cache = None
    quant_cache = cache is not None and "k_scale" in cache
    if cache is not None and t == 1 and token_cache:
        # vLLM-style decode: do NOT rewrite the cache functionally — attend
        # against the stale cache (kv_len masks slot >= pos) plus an
        # explicit self-term for the new token, and hand the (B, KV, 1, HD)
        # token K/V back to the caller, which writes just that slice into
        # the scan-carried cache buffer. Cuts the per-layer cache write
        # from O(T) to O(1) bytes (EXPERIMENTS.md §Perf, cell A).
        if quant_cache:
            # rotated-int8 cache: the token's K/V go through the codec HERE
            # so the self term attends against exactly the values every
            # later step will read back from the cache.
            kq, ks = kv_encode(k)
            vq, vs = kv_encode(v)
            out = _decode_q8(q, cache, (kq, ks), (vq, vs), pos_vec, rt)
            out = out.astype(rt.compute_dtype)
            tok = {"k_tok": kq, "v_tok": vq,
                   "k_scale_tok": ks, "v_scale_tok": vs}
        else:
            out = _sdpa_decode_token(q, cache["k"], cache["v"], k, v, rt,
                                     kv_len=pos_vec)
            tok = {"k_tok": k, "v_tok": v}
        out = out.reshape(b, h, 1, hd).swapaxes(1, 2).reshape(b, t, h * hd)
        return dense(out, p["wo"], rt), tok
    if quant_cache:
        # prefill (or functional-cache decode) over the quantized cache:
        # encode the new K/V span and write codes+scales at pos.
        kq, ks = kv_encode(k)
        vq, vs = kv_encode(v)
        if "table" in cache:
            # paged pool: scatter the span through the block table. Leaves
            # are (NB, KV, BS, X); token p of slot b lands in block
            # tbl[b, p // BS] at offset p % BS. Slots whose rows point at
            # the reserved null block 0 (padding / inactive) scatter finite
            # garbage there — never read, masked by kv_len.
            tbl = cache["table"]
            bs = cache["k"].shape[2]
            span = pos_vec[:, None] + jnp.arange(t)  # (B, T)
            blk = jnp.take_along_axis(tbl, span // bs, axis=1)  # (B, T)
            off = span % bs

            def scat(pool, vals):  # pool (NB, KV, BS, X); vals (B, KV, T, X)
                return pool.at[blk, :, off, :].set(
                    jnp.swapaxes(vals, 1, 2).astype(pool.dtype))

            with jax.named_scope("kv_cache"):
                new_cache = {"k": scat(cache["k"], kq),
                             "v": scat(cache["v"], vq),
                             "k_scale": scat(cache["k_scale"], ks),
                             "v_scale": scat(cache["v_scale"], vs)}
            read_cache = dict(new_cache, table=tbl)
        else:
            upd = jax.vmap(partial(jax.lax.dynamic_update_slice_in_dim, axis=1))
            with jax.named_scope("kv_cache"):
                ck = upd(cache["k"], kq, pos_vec)
                cks = upd(cache["k_scale"], ks.astype(cache["k_scale"].dtype),
                          pos_vec)
                cv = upd(cache["v"], vq, pos_vec)
                cvs = upd(cache["v_scale"], vs.astype(cache["v_scale"].dtype),
                          pos_vec)
                ck = shard_hint(ck, rt, "batch", "kv_heads", "kv_seq", None)
                cv = shard_hint(cv, rt, "batch", "kv_heads", "kv_seq", None)
                cks = shard_hint(cks, rt, "batch", "kv_heads", "kv_seq", None)
                cvs = shard_hint(cvs, rt, "batch", "kv_heads", "kv_seq", None)
            new_cache = {"k": ck, "v": cv, "k_scale": cks, "v_scale": cvs}
            read_cache = new_cache
        if t == 1:
            # single-token decode WITHOUT the scan-carry mechanism (hybrid's
            # shared attention block, or decode_token_cache=False): same
            # dequantize-free path as the token-cache branch — attend the
            # PRE-write cache plus the encoded self term — instead of
            # dequantizing the whole max_len cache every step. Only the
            # functional write above touches the full buffers.
            out = _decode_q8(q, cache, (kq, ks), (vq, vs), pos_vec, rt)
        else:
            # prefill: fused q-tile attention straight over the POST-write
            # codes. Scores stay in the rotated domain ((Hq).(Hk) == q.k)
            # and the span's own keys were just written at
            # pos..pos+t-1, so the causal mask (kpos <= pos + qpos) merges
            # the in-flight span's self-attention block into the same
            # cache pass — the decode path's self-token merge generalized
            # to a width-t span. The full cache buffer is NEVER
            # dequantized: chunked prefill streams int8 codes only.
            out = _prefill_q8(q, read_cache, pos_vec + t, pos_vec, rt)
        out = out.astype(rt.compute_dtype)
        out = out.reshape(b, h, t, hd).swapaxes(1, 2).reshape(b, t, h * hd)
        return dense(out, p["wo"], rt), new_cache
    elif cache is not None:
        upd = jax.vmap(partial(jax.lax.dynamic_update_slice_in_dim, axis=1))
        with jax.named_scope("kv_cache"):
            ck = upd(cache["k"], k.astype(cache["k"].dtype), pos_vec)
            cv = upd(cache["v"], v.astype(cache["v"].dtype), pos_vec)
            ck = shard_hint(ck, rt, "batch", "kv_heads", "kv_seq", None)
            cv = shard_hint(cv, rt, "batch", "kv_heads", "kv_seq", None)
        new_cache = {"k": ck, "v": cv}
        k, v = ck, cv
        kv_len = pos_vec + t
        causal = t > 1  # within-step causality only; cache masked by kv_len
    else:
        k = shard_hint(k, rt, "batch", "kv_heads", "kv_seq", None)
        v = shard_hint(v, rt, "batch", "kv_heads", "kv_seq", None)

    out = _sdpa_chunked(q, k, v, rt, causal=causal, q_offset=pos_vec,
                        kv_len=kv_len)
    out = out.reshape(b, h, -1, hd)[:, :, :t, :].swapaxes(1, 2).reshape(b, t, h * hd)
    return dense(out, p["wo"], rt), new_cache


def _sdpa_decode_token(q, ck, cv, k_tok, v_tok, rt: Runtime, *, kv_len):
    """Single-token decode attention against a cache that does NOT yet
    contain the current token: softmax over [cached scores | self score].

    q (B, KV, G, 1, HD); ck/cv (B, KV, Tk, HD); k_tok/v_tok (B, KV, 1, HD);
    kv_len (B,) = number of valid cached positions (== current pos)."""
    b, kvh, g, _, hd = q.shape
    tk = ck.shape[2]
    scale = 1.0 / math.sqrt(hd)
    qc = q.astype(ck.dtype)
    s_cache = jnp.einsum("bkgqd,bktd->bkgqt", qc, ck,
                         preferred_element_type=jnp.float32) * scale
    kpos = jnp.arange(tk)
    mask = kpos[None, None, None, None, :] < kv_len[:, None, None, None, None]
    s_cache = jnp.where(mask, s_cache, -1e30)
    s_self = jnp.einsum("bkgqd,bkqd->bkgq", qc, k_tok.astype(qc.dtype),
                        preferred_element_type=jnp.float32)[..., None] * scale
    s = jnp.concatenate([s_cache, s_self], axis=-1)
    w = jax.nn.softmax(s, axis=-1)
    w_cache, w_self = w[..., :tk], w[..., tk:]
    out = jnp.einsum("bkgqt,bktd->bkgqd", w_cache.astype(cv.dtype), cv,
                     preferred_element_type=jnp.float32)
    out = out + w_self.astype(jnp.float32) * v_tok[:, :, None].astype(jnp.float32)
    return out.astype(rt.compute_dtype)
