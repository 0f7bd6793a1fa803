"""Model assembly for all assigned architecture families.

One parameter/apply pair per family, all built from the same blocks and all
scanning over stacked per-layer parameters (so a 94-layer MoE compiles one
layer body, not 94):

  dense / vlm    — [frontend] + GQA attention + MLP
  moe            — GQA attention + sort-dispatch MoE
  ssm (rwkv6)    — RWKV6 time-mix/channel-mix layers (attention-free)
  hybrid (zamba2)— Mamba2 backbone with ONE shared attention block applied
                   every ``attn_every`` layers; expressed as a scan over
                   macroblocks (attn + ``every`` mambas) so the shared
                   weights are reused by construction and the KV-cache
                   slots align with scan steps (no in-scan cond/gather)
  audio (enc-dec)— encoder stack (non-causal) + decoder stack with
                   cross-attention (seamless)

The serving cache is a pytree matching the family: attention KV, Mamba2
(ssm, conv) state, RWKV6 (wkv, shift) state, or a mix.

Compiled programs carry ``jax.named_scope`` names in their HLO ``op_name``
metadata, so a device trace can put each operation down to a part of the
model: ``embed``; per layer ``attn`` (norm, QKV, rope, the attention
kernel, out projection), ``mlp`` and ``kv_cache`` (the stacked cache's
per-layer slice and token write, and the pool's planes laid out for the
kernel); ``head``; the engine's ``sample``; and ``itq3_planes``, the
ITQ3_S planes laid out for the kernels (``kernels/itq3_matmul.py``).
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core import formats as fmt_mod
from repro.core.quantize import QTensor
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (
    Runtime, attention_apply, attention_init, dense, init_dense_weight,
    mlp_apply, mlp_init, norm_apply, norm_init, shard_hint,
)

Params = dict[str, Any]

__all__ = [
    "init_params", "forward", "decode_step", "score_tokens", "advance_cache",
    "init_cache", "model_flops", "sample_tokens", "top_mask", "finite_rows",
]


# ===========================================================================
# Init
# ===========================================================================

def _layer_init(key, cfg, *, cross: bool = False) -> Params:
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 6)
    p: Params = {"ln1": norm_init(d, cfg.norm)}
    p["attn"] = attention_init(ks[0], d, cfg.num_heads, cfg.num_kv_heads,
                               cfg.resolved_head_dim, cfg.qkv_bias)
    if cross:
        p["ln_x"] = norm_init(d, cfg.norm)
        p["xattn"] = attention_init(ks[1], d, cfg.num_heads, cfg.num_kv_heads,
                                    cfg.resolved_head_dim, False)
    p["ln2"] = norm_init(d, cfg.norm)
    if cfg.num_experts:
        p["moe"] = moe_mod.moe_init(ks[2], d, f, cfg.num_experts, cfg.activation)
    else:
        p["mlp"] = mlp_init(ks[3], d, f, cfg.activation)
    return p


def _stack_init(key, n: int, fn) -> Params:
    return jax.vmap(fn)(jax.random.split(key, n))


def init_params(key, cfg) -> Params:
    ks = jax.random.split(key, 8)
    d = cfg.d_model
    p: Params = {
        "embed": jax.random.normal(ks[0], (cfg.vocab_size, d), jnp.float32) * 0.02,
        "ln_f": norm_init(d, cfg.norm),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = init_dense_weight(ks[1], d, cfg.vocab_size)

    fam = cfg.family
    if fam in ("dense", "vlm", "moe"):
        p["layers"] = _stack_init(ks[2], cfg.num_layers, lambda k: _layer_init(k, cfg))
    elif fam == "ssm":
        p["layers"] = _stack_init(ks[2], cfg.num_layers, lambda k: ssm_mod.rwkv6_init(k, cfg))
    elif fam == "hybrid":
        every = cfg.attn_every
        n_full = cfg.num_layers // every
        tail = cfg.num_layers % every
        p["shared_attn"] = {
            "ln": norm_init(d, cfg.norm),
            "attn": attention_init(ks[3], d, cfg.num_heads, cfg.num_kv_heads,
                                   cfg.resolved_head_dim, False),
        }
        p["mamba_blocks"] = jax.vmap(
            lambda k: _stack_init(k, every, lambda kk: _mamba_layer_init(kk, cfg))
        )(jax.random.split(ks[4], n_full))
        if tail:
            p["mamba_tail"] = _stack_init(ks[5], tail, lambda k: _mamba_layer_init(k, cfg))
    elif fam == "audio":
        p["encoder"] = _stack_init(ks[2], cfg.encoder_layers, lambda k: _layer_init(k, cfg))
        p["enc_ln_f"] = norm_init(d, cfg.norm)
        p["layers"] = _stack_init(ks[6], cfg.num_layers,
                                  lambda k: _layer_init(k, cfg, cross=True))
    else:
        raise ValueError(f"unknown family {fam!r}")

    if cfg.frontend:
        p["frontend_proj"] = init_dense_weight(ks[7], cfg.frontend_dim, d)
    return p


def _mamba_layer_init(key, cfg) -> Params:
    return {"ln": norm_init(cfg.d_model, cfg.norm),
            "mamba": ssm_mod.mamba2_init(key, cfg)}


# ===========================================================================
# Caches / states
# ===========================================================================

def init_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16,
               *, kv_quant: bool = False) -> Params:
    """Serving cache pytree. ``kv_quant=True`` lays the self-attention KV
    cache out as rotated-int8 codes plus per-token fp16 scales (the
    serve/kv_quant.py codec): 8.25 bits/element instead of 16/32. The
    cross-attention memory (audio) stays fp — it is written once at prefill
    and re-read every step, so re-dequantizing it each step would trade its
    one-time bytes for per-step compute. Requires a power-of-two head_dim
    (every arch in the zoo qualifies)."""
    from repro.core.fwht import is_pow2

    kvh, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    fam = cfg.family
    if kv_quant and not is_pow2(hd):
        raise ValueError(f"kv_quant needs a power-of-two head_dim, got {hd}")

    def kv(n_layers, length, quant=kv_quant):
        if quant:
            return {
                "k": jnp.zeros((n_layers, batch, kvh, length, hd), jnp.int8),
                "v": jnp.zeros((n_layers, batch, kvh, length, hd), jnp.int8),
                "k_scale": jnp.zeros((n_layers, batch, kvh, length, 1),
                                     jnp.float16),
                "v_scale": jnp.zeros((n_layers, batch, kvh, length, 1),
                                     jnp.float16),
            }
        return {
            "k": jnp.zeros((n_layers, batch, kvh, length, hd), dtype),
            "v": jnp.zeros((n_layers, batch, kvh, length, hd), dtype),
        }

    if fam in ("dense", "vlm", "moe"):
        length = max_len + (cfg.frontend_len if cfg.frontend else 0)
        return {"attn": kv(cfg.num_layers, length)}
    if fam == "ssm":
        states = jax.vmap(lambda _: ssm_mod.rwkv6_empty_state(cfg, batch))(
            jnp.arange(cfg.num_layers))
        return {"ssm": states}
    if fam == "hybrid":
        every = cfg.attn_every
        n_attn = cfg.num_layers // every + (1 if cfg.num_layers % every else 0)
        states = jax.vmap(lambda _: ssm_mod.mamba2_empty_state(cfg, batch))(
            jnp.arange(cfg.num_layers))
        return {"attn": kv(n_attn, max_len), "ssm": states}
    if fam == "audio":
        # self-attn cache + cross-attn memory (filled by prefill)
        return {"attn": kv(cfg.num_layers, max_len),
                "xattn": kv(cfg.num_layers, cfg.frontend_len, quant=False)}
    raise ValueError(fam)


# ===========================================================================
# Decoder stacks
# ===========================================================================

def _residual(x, rt):
    """Keep the residual stream whole on every model-axis device. Column-
    parallel projections return N-sharded outputs; gathering them here
    (exact) lets the next norm reduce over D on one device, where a sharded
    D would all-reduce per-shard partial sums in another float order than
    one device does."""
    return shard_hint(x, rt, "batch", "seq", None)


def _dense_layer_apply(lp, x, rt, cfg, *, cache, pos, memory=None, causal=True,
                       token_cache=False):
    with jax.named_scope("attn"):
        h, new_kv = attention_apply(
            lp["attn"], norm_apply(lp["ln1"], x, cfg.norm), rt, cfg,
            causal=causal, cache=None if cache is None else cache["attn"],
            pos=pos, token_cache=token_cache)
        x = _residual(x + h, rt)
        new_cache = None
        if "xattn" in lp:
            xc, new_xkv = attention_apply(
                lp["xattn"], norm_apply(lp["ln_x"], x, cfg.norm), rt, cfg,
                cross=True, memory=memory,
                cache=None if cache is None else cache.get("xattn"))
            x = _residual(x + xc, rt)
            if cache is not None:
                new_cache = {"attn": new_kv, "xattn": new_xkv}
        elif cache is not None:
            new_cache = {"attn": new_kv}
    with jax.named_scope("mlp"):
        aux = jnp.zeros((), jnp.float32)
        hn = norm_apply(lp["ln2"], x, cfg.norm)
        if "moe" in lp:
            m, aux = moe_mod.moe_apply(lp["moe"], hn, rt, cfg)
        else:
            m = mlp_apply(lp["mlp"], hn, rt, cfg.activation)
        return _residual(x + m, rt), new_cache, aux


def _maybe_remat(body, rt):
    """Per-layer rematerialization: wrap the scan body so backward re-runs
    the layer instead of saving its internals (attention weights at 32k
    would otherwise dominate memory — the flash-attention discipline)."""
    if not rt.remat:
        return body
    policy = (jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
              if rt.remat_policy == "dots" else None)
    return jax.checkpoint(body, policy=policy)


def _run_decoder(params, x, rt, cfg, *, cache, pos, memory=None, causal=True):
    """Scan the main layer stack. cache: stacked leaves (L, ...) or None.
    Returns (x, new_cache, aux)."""
    fam = cfg.family

    if fam in ("dense", "vlm", "moe", "audio"):
        if cache is not None and x.shape[1] == 1 and rt.decode_token_cache:
            return _run_decoder_token(params, x, rt, cfg, cache=cache, pos=pos)

        # Paged pool: the block table (B, MAXB) has no layer axis, so it
        # cannot ride the scan xs — thread it via closure instead and merge
        # it into each layer's attn-cache slice inside the body.
        tbl = cache.get("table") if cache is not None else None

        def body(xc, inp):
            lp, c = inp
            if tbl is not None:
                c = dict(c)
                c["attn"] = dict(c["attn"], table=tbl)
            xnew, cnew, aux = _dense_layer_apply(
                lp, xc, rt, cfg, cache=c, pos=pos, memory=memory, causal=causal)
            return xnew, (cnew, aux)

        body = _maybe_remat(body, rt)

        layer_cache = None
        if cache is not None:
            layer_cache = {"attn": _kv_tree(cache["attn"])}
            if "xattn" in cache:
                layer_cache["xattn"] = _kv_tree(cache["xattn"])
        x, (new_cache, auxs) = jax.lax.scan(body, x, (params["layers"], layer_cache))
        return x, new_cache, jnp.mean(auxs)

    if fam == "ssm":
        def body(xc, inp):
            lp, st = inp
            xnew, stnew = ssm_mod.rwkv6_apply(lp, xc, rt, cfg, state=st,
                                              decode=(x.shape[1] == 1 and cache is not None))
            return xnew, stnew

        body = _maybe_remat(body, rt)
        states = cache["ssm"] if cache is not None else None
        if states is None:
            # training: still thread zero states (scan needs uniform xs)
            b = x.shape[0]
            states = jax.vmap(lambda _: ssm_mod.rwkv6_empty_state(cfg, b))(
                jnp.arange(cfg.num_layers))
            x, _ = jax.lax.scan(body, x, (params["layers"], states))
            return x, None, jnp.zeros((), jnp.float32)
        x, new_states = jax.lax.scan(body, x, (params["layers"], states))
        return x, {"ssm": new_states}, jnp.zeros((), jnp.float32)

    if fam == "hybrid":
        return _run_hybrid(params, x, rt, cfg, cache=cache, pos=pos)

    raise ValueError(fam)


def _kv_tree(kv):
    # shallow copy of every cache leaf (k/v, plus scale planes when the
    # cache is rotated-int8 quantized)
    return dict(kv)


def _write_token_kv(stacked, tok, layer_idx, pos_vec):
    """Write (B, KV, 1, HD) token K/V into the stacked (L, B, KV, T, HD)
    cache at [layer_idx, b, :, pos_b, :] — the O(1)-bytes decode write."""
    def upd(cacheB, tokB, p):
        # cacheB (L, KV, T, HD); tokB (KV, 1, HD)
        return jax.lax.dynamic_update_slice(
            cacheB, tokB[None].astype(cacheB.dtype),
            (layer_idx, jnp.int32(0), p, jnp.int32(0)))
    return jax.vmap(upd, in_axes=(1, 0, 0), out_axes=1)(stacked, tok, pos_vec)


def _write_token_kv_paged(stacked, tok, layer_idx, tbl, pos_vec):
    """Paged analogue of :func:`_write_token_kv`: scatter (B, KV, 1, HD)
    token K/V into the stacked pool (L, NB, KV, BS, HD) through the block
    table. Slot b's token at logical position p lands in pool block
    ``tbl[b, p // BS]`` at offset ``p % BS``. Inactive slots must keep
    their table rows pointing at the reserved null block 0 so their
    (garbage but finite) writes never land in a live block."""
    bs = stacked.shape[3]
    blk = jnp.take_along_axis(tbl, (pos_vec // bs)[:, None], axis=1)[:, 0]
    off = pos_vec % bs
    return stacked.at[layer_idx, blk, :, off, :].set(
        tok[:, :, 0, :].astype(stacked.dtype))


# attn-cache leaf -> the token-slice key attention_apply returns for it.
# fp caches carry {k, v}; rotated-int8 caches also carry the scale planes.
_TOK_KEYS = {"k": "k_tok", "v": "v_tok",
             "k_scale": "k_scale_tok", "v_scale": "v_scale_tok"}


def _run_decoder_token(params, x, rt, cfg, *, cache, pos):
    """Single-token decode for attention families: the KV cache rides the
    scan CARRY and each layer writes only its new token's K/V slice —
    instead of functionally rewriting the full (B, KV, T, HD) cache per
    layer through scan ys (which costs O(T) write bandwidth per layer per
    token). See EXPERIMENTS.md §Perf cell A.

    The carry is a dict over whatever leaves the attn cache has — (k, v)
    for fp caches, (k, v, k_scale, v_scale) for the rotated-int8 layout —
    so the O(1)-byte write discipline covers both."""
    b = x.shape[0]
    pos_vec = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    has_x = "xattn" in cache
    leaf_keys = sorted(cache["attn"].keys())
    tbl = cache.get("table")

    def body(carry, inp):
        xc, cdict, i = carry
        with jax.named_scope("kv_cache"):
            layer_attn = {lk: jax.lax.dynamic_index_in_dim(cdict[lk], i, 0,
                                                           False)
                          for lk in leaf_keys}
        if tbl is not None:
            layer_attn["table"] = tbl
        if has_x:
            lp, xk, xv = inp
            layer_cache = {"attn": layer_attn, "xattn": {"k": xk, "v": xv}}
        else:
            lp = inp
            layer_cache = {"attn": layer_attn}
        xnew, cnew, aux = _dense_layer_apply(
            lp, xc, rt, cfg, cache=layer_cache, pos=pos_vec, token_cache=True)
        with jax.named_scope("kv_cache"):
            if tbl is not None:
                cdict = {lk: _write_token_kv_paged(
                    cdict[lk], cnew["attn"][_TOK_KEYS[lk]], i, tbl, pos_vec)
                    for lk in leaf_keys}
            else:
                cdict = {lk: _write_token_kv(
                    cdict[lk], cnew["attn"][_TOK_KEYS[lk]], i, pos_vec)
                    for lk in leaf_keys}
        return (xnew, cdict, i + 1), aux

    xs = (params["layers"], cache["xattn"]["k"], cache["xattn"]["v"]) if has_x \
        else params["layers"]
    (x, cdict, _), auxs = jax.lax.scan(
        body, (x, dict(cache["attn"]), jnp.int32(0)), xs)
    new_cache = {"attn": cdict}
    if has_x:
        new_cache["xattn"] = _kv_tree(cache["xattn"])
    return x, new_cache, jnp.mean(auxs)


def _run_hybrid(params, x, rt, cfg, *, cache, pos):
    """Zamba2: scan over macroblocks (shared-attn + `every` mamba layers)."""
    every = cfg.attn_every
    n_full = cfg.num_layers // every
    tail = cfg.num_layers % every
    decode = cache is not None and x.shape[1] == 1
    b = x.shape[0]
    sa = params["shared_attn"]

    def zero_states(n):
        return jax.vmap(lambda _: ssm_mod.mamba2_empty_state(cfg, b))(jnp.arange(n))

    if cache is not None:
        ssm_states = cache["ssm"]
        kv_cache = _kv_tree(cache["attn"])
    else:
        ssm_states = zero_states(cfg.num_layers)
        kv_cache = None

    def split_states(st, lo, n):
        return jax.tree.map(lambda a: a[lo:lo + n], st)

    def mamba_seq(xc, mparams, states):
        def mbody(xx, inp):
            lp, st = inp
            h, stnew = ssm_mod.mamba2_apply(
                lp["mamba"], norm_apply(lp["ln"], xx, cfg.norm), rt, cfg,
                state=st, decode=decode)
            return xx + h, stnew
        return jax.lax.scan(mbody, xc, (mparams, states))

    def attn_once(xc, kv_slice):
        h, new_kv = attention_apply(
            sa["attn"], norm_apply(sa["ln"], xc, cfg.norm), rt, cfg,
            causal=True, cache=kv_slice, pos=pos)
        return xc + h, new_kv

    main_states = jax.tree.map(
        lambda a: a[: n_full * every].reshape(n_full, every, *a.shape[1:]),
        ssm_states)

    def block_body(xc, inp):
        mparams, mstates, kv_slice = inp
        xc, new_kv = attn_once(xc, kv_slice)
        xc, new_mstates = mamba_seq(xc, mparams, mstates)
        return xc, (new_mstates, new_kv)

    if kv_cache is not None:
        kv_main = jax.tree.map(lambda a: a[:n_full], kv_cache)
        x, (new_main_states, new_kv_main) = jax.lax.scan(
            _maybe_remat(block_body, rt), x,
            (params["mamba_blocks"], main_states, kv_main))
    else:
        def block_body_nokv(xc, inp):
            mparams, mstates = inp
            xc, _ = attn_once(xc, None)
            xc, new_mstates = mamba_seq(xc, mparams, mstates)
            return xc, new_mstates
        x, new_main_states = jax.lax.scan(
            _maybe_remat(block_body_nokv, rt), x,
            (params["mamba_blocks"], main_states))
        new_kv_main = None

    if tail:
        tail_states = split_states(ssm_states, n_full * every, tail)
        if kv_cache is not None:
            kv_tail = jax.tree.map(lambda a: a[n_full], kv_cache)
            x, new_kv_tail = attn_once(x, kv_tail)
        else:
            x, _ = attn_once(x, None)
            new_kv_tail = None
        x, new_tail_states = mamba_seq(x, params["mamba_tail"], tail_states)
    else:
        new_tail_states = None
        new_kv_tail = None

    new_cache = None
    if cache is not None:
        flat_main = jax.tree.map(
            lambda a: a.reshape(n_full * every, *a.shape[2:]), new_main_states)
        if tail:
            new_ssm = jax.tree.map(
                lambda a, t2: jnp.concatenate([a, t2], axis=0),
                flat_main, new_tail_states)
            new_kv = jax.tree.map(
                lambda m, t2: jnp.concatenate([m, t2[None]], axis=0),
                new_kv_main, new_kv_tail)
        else:
            new_ssm, new_kv = flat_main, new_kv_main
        new_cache = {"ssm": new_ssm, "attn": new_kv}
    return x, new_cache, jnp.zeros((), jnp.float32)


# ===========================================================================
# Public API: forward / decode_step
# ===========================================================================

@jax.named_scope("embed")
def _embed(params, tokens, rt, cfg):
    table = params["embed"]
    if isinstance(table, QTensor):
        # a policy quantized the tied table: stored transposed (D, V),
        # blocked along D, so the tied head can matmul it directly; the
        # gather path reconstructs the table on the fly — O(D*V) dequant
        # work per call, comparable to the head matmul it ties to, and the
        # price of keeping only packed planes resident. The head path
        # dequantizes the same QTensor; XLA CSE merges the two identical
        # subexpressions inside one jitted step. Policies that can't pay
        # the cost should pin embed fp (fmt=None) and quantize lm_head only.
        emb = fmt_mod.dequantize(table, rt.compute_dtype).T
    else:
        emb = table.astype(rt.compute_dtype)
    # table gathers are row-local when D is model-sharded: shard D only
    emb = shard_hint(emb, rt, None, "embed")
    x = jnp.take(emb, tokens, axis=0)
    return shard_hint(x, rt, "batch", "seq", None)


def _head_weight(params, rt):
    """(D, V) head weight (array or QTensor). The tied embedding table is
    resharded for the head matmul — V over model, D replicated: re-laying
    it out once per step costs table-bytes, vs. psum-ing full (B, T, V)
    logits every chunk if the contraction dim stayed sharded."""
    w = params.get("lm_head")
    if w is None:
        w = params["embed"]
        if isinstance(w, QTensor):  # already stored as (D, V): matmul-ready
            return w
        w = shard_hint(w.T, rt, None, "vocab")
    return w


@jax.named_scope("head")
def _head(params, x, rt, cfg):
    x = norm_apply(params["ln_f"], x, cfg.norm)
    logits = dense(x, _head_weight(params, rt), rt)
    return shard_hint(logits, rt, "batch", "seq", "vocab")


def _encode(params, frames, rt, cfg):
    """Audio encoder (seamless): frames (B, S, F) -> memory (B, S, D)."""
    x = dense(frames.astype(rt.compute_dtype), params["frontend_proj"], rt)

    def body(xc, lp):
        xnew, _, _ = _dense_layer_apply(lp, xc, rt, cfg, cache=None, pos=0,
                                        causal=False)
        return xnew, None

    x, _ = jax.lax.scan(_maybe_remat(body, rt), x, params["encoder"])
    return norm_apply(params["enc_ln_f"], x, cfg.norm)


def forward(
    params: Params,
    tokens: jax.Array,  # (B, T)
    rt: Runtime,
    cfg,
    *,
    frontend_feats: Optional[jax.Array] = None,  # (B, P, F) patches/frames
    cache: Optional[Params] = None,
    pos: int | jax.Array = 0,
    last_only: bool = False,
    last_idx: Optional[jax.Array] = None,
) -> tuple[jax.Array, Optional[Params], jax.Array]:
    """Full-sequence forward (train / prefill).

    Returns (logits (B, T, V) — or (B, 1, V) when ``last_only`` or
    ``last_idx``, the serving prefill modes: the LM head over 32k x 152k
    logits would dwarf everything else — new_cache | None, moe_aux).
    ``last_idx`` (B,) gathers a per-row position BEFORE the head, so a
    padded-bucket prefill pays one head row per slot, at its true last
    prompt token, instead of V logits for every pad position."""
    x = _embed(params, tokens, rt, cfg)
    memory = None
    if cfg.family == "audio":
        assert frontend_feats is not None, "seamless needs encoder frames"
        memory = _encode(params, frontend_feats, rt, cfg)
    elif cfg.frontend and frontend_feats is not None:
        prefix = dense(frontend_feats.astype(rt.compute_dtype),
                       params["frontend_proj"], rt)
        x = jnp.concatenate([prefix, x], axis=1)

    x, new_cache, aux = _run_decoder(params, x, rt, cfg, cache=cache, pos=pos,
                                     memory=memory)
    if cfg.frontend and frontend_feats is not None and cfg.family != "audio":
        x = x[:, frontend_feats.shape[1]:]
    with jax.named_scope("head"):
        if last_only:
            x = x[:, -1:]
        elif last_idx is not None:
            x = x[jnp.arange(x.shape[0]), last_idx][:, None]
    return _head(params, x, rt, cfg), new_cache, aux


def forward_xent(
    params: Params,
    tokens: jax.Array,  # (B, T)
    labels: jax.Array,  # (B, T)
    rt: Runtime,
    cfg,
    *,
    frontend_feats: Optional[jax.Array] = None,
    chunk: int = 512,
) -> tuple[jax.Array, jax.Array]:
    """Full forward + cross-entropy WITHOUT materializing (B, T, V) logits:
    the LM head + logsumexp run per sequence-chunk inside a rematerialized
    scan, so peak memory holds one (B, chunk, V) slice. For vocab 152k at
    T=4096 this is the difference between ~50 GB of logits copies and
    ~1.5 GB (EXPERIMENTS.md §Perf, memory term).

    Returns (mean_xent, moe_aux)."""
    x = _embed(params, tokens, rt, cfg)
    memory = None
    if cfg.family == "audio":
        assert frontend_feats is not None
        memory = _encode(params, frontend_feats, rt, cfg)
    elif cfg.frontend and frontend_feats is not None:
        prefix = dense(frontend_feats.astype(rt.compute_dtype),
                       params["frontend_proj"], rt)
        x = jnp.concatenate([prefix, x], axis=1)
    x, _, aux = _run_decoder(params, x, rt, cfg, cache=None, pos=0,
                             memory=memory)
    if cfg.frontend and frontend_feats is not None and cfg.family != "audio":
        x = x[:, frontend_feats.shape[1]:]
    x = norm_apply(params["ln_f"], x, cfg.norm)

    w = _head_weight(params, rt)
    b, t, d = x.shape
    chunk = max(1, min(chunk, t))
    pad = (-t) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
    nc = x.shape[1] // chunk
    xc = jnp.moveaxis(x.reshape(b, nc, chunk, d), 1, 0)
    yc = jnp.moveaxis(labels.reshape(b, nc, chunk), 1, 0)

    def body(tot, inp):
        xs, ys = inp  # (B, C, D), (B, C)
        logits = dense(xs, w, rt).astype(jnp.float32)
        logits = shard_hint(logits, rt, "batch", None, "vocab")
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, jnp.maximum(ys, 0)[..., None],
                                 axis=-1)[..., 0]
        valid = (ys >= 0).astype(jnp.float32)
        return tot + jnp.sum((lse - ll) * valid), None

    tot, _ = jax.lax.scan(jax.checkpoint(body), jnp.zeros((), jnp.float32),
                          (xc, yc))
    return tot / (b * t), aux


def decode_step(
    params: Params,
    tokens: jax.Array,  # (B, 1)
    cache: Params,
    pos: jax.Array,  # int32 scalar or (B,): per-row current write index
    rt: Runtime,
    cfg,
) -> tuple[jax.Array, Params]:
    """One autoregressive step with persistent cache. Returns (logits (B, 1, V),
    new_cache)."""
    x = _embed(params, tokens, rt, cfg)
    x, new_cache, _ = _run_decoder(params, x, rt, cfg, cache=cache, pos=pos)
    return _head(params, x, rt, cfg), new_cache


def score_tokens(
    params: Params,
    tokens: jax.Array,  # (B, T) — T consecutive tokens per row
    cache: Params,
    pos: jax.Array,  # int32 scalar or (B,): write index of tokens[:, 0]
    rt: Runtime,
    cfg,
) -> tuple[jax.Array, Params]:
    """Score a T-token window per row against the persistent cache in ONE
    forward pass — the speculative-decoding verify primitive. Token ``t``
    is written to cache position ``pos + t`` and attends causally to
    everything at or before it, so ``logits[:, t]`` is the model's
    next-token distribution after consuming ``tokens[:, :t+1]`` — exactly
    what ``decode_step`` would produce after T sequential steps. Under
    ``kv_quant`` this routes through the batched ``prefill_attn_q8`` q-tile
    kernel (one fused pass over the rotated-int8 cache for all T
    positions). Returns (logits (B, T, V), new_cache with the span
    appended)."""
    x = _embed(params, tokens, rt, cfg)
    x, new_cache, _ = _run_decoder(params, x, rt, cfg, cache=cache, pos=pos)
    return _head(params, x, rt, cfg), new_cache


def advance_cache(
    params: Params,
    tokens: jax.Array,  # (B, T)
    cache: Params,
    pos: jax.Array,
    rt: Runtime,
    cfg,
) -> Params:
    """Append a token span to the cache WITHOUT computing head logits —
    used when only the KV state matters (e.g. the draft model's final
    propose step must cache position ``pos + T - 1`` so a fully-accepted
    window leaves no stale hole, but its logits are never sampled).
    Returns the new cache."""
    x = _embed(params, tokens, rt, cfg)
    _, new_cache, _ = _run_decoder(params, x, rt, cfg, cache=cache, pos=pos)
    return new_cache


def finite_rows(logits: jax.Array) -> jax.Array:
    """Per-row numeric health: True where every logit in the row is finite.

    The serving engine folds this into the jitted decode step (quantized
    stacks can degenerate at runtime — an inf/NaN KV scale plane poisons a
    row's attention — and the check must ride the step's existing token
    transfer rather than add a host sync). Reduces (..., V) -> (...) bool
    on device; rows that pass are untouched, so healthy streams stay
    bit-identical."""
    return jnp.all(jnp.isfinite(logits.astype(jnp.float32)), axis=-1)


def top_mask(
    logits: jax.Array,  # (B, V) float32
    top_k: Optional[jax.Array] = None,  # (B,) int32; 0 disables per row
    top_p: Optional[jax.Array] = None,  # (B,) float32; 1.0 disables per row
) -> jax.Array:
    """Mask logits outside the per-row top-k / top-p (nucleus) sets to -inf.

    Both filters reduce to a per-row VALUE threshold against the
    descending-sorted logits, so the whole batch is masked with one sort +
    one cumsum — no per-row loops, heterogeneous k/p in one trace. Every
    row keeps at least its argmax (k is clipped to >= 1 when enabled; the
    first nucleus token is always kept since its preceding mass is 0).
    Row-independent by construction, which the engine's batched==sequential
    bit-parity contract relies on."""
    v = logits.shape[-1]
    sorted_desc = -jnp.sort(-logits, axis=-1)
    thresh = jnp.full(logits.shape[:-1], -jnp.inf, jnp.float32)
    if top_k is not None:
        k = jnp.asarray(top_k, jnp.int32)
        kth = jnp.take_along_axis(
            sorted_desc, jnp.clip(k - 1, 0, v - 1)[..., None], axis=-1)[..., 0]
        thresh = jnp.maximum(thresh, jnp.where(k > 0, kth, -jnp.inf))
    if top_p is not None:
        p = jnp.asarray(top_p, jnp.float32)
        probs = jax.nn.softmax(sorted_desc, axis=-1)
        # keep a token iff the mass STRICTLY BEFORE it is < p: the token
        # that crosses the p boundary is included (standard nucleus rule)
        keep = (jnp.cumsum(probs, axis=-1) - probs) < p[..., None]
        pth = jnp.min(jnp.where(keep, sorted_desc, jnp.inf), axis=-1)
        thresh = jnp.maximum(thresh, jnp.where(p < 1.0, pth, -jnp.inf))
    return jnp.where(logits >= thresh[..., None], logits, -jnp.inf)


def sample_tokens(
    logits: jax.Array,  # (..., V)
    key: Optional[jax.Array] = None,
    temperature: jax.Array | float = 0.0,
    *,
    top_k: Optional[jax.Array] = None,  # (B,) per-row; None disables
    top_p: Optional[jax.Array] = None,  # (B,) per-row; None disables
) -> jax.Array:
    """Greedy argmax (``key=None``) or temperature/top-k/top-p sampling,
    on device.

    Designed to live INSIDE the jitted decode step: the engine then moves
    one (slots,) int32 vector per step across the device->host boundary
    instead of one logits row per slot. Greedy decoding passes ``key=None``
    so the hot loop traces to a bare argmax — no PRNG work (threefry over
    (B, V) is real cost on CPU). With a key, ``temperature`` is traced
    (flipping it never recompiles); both the categorical and the argmax are
    computed and selected with where, since temp <= 0 must still mean
    greedy.

    The serving path passes PER-ROW vectors: ``temperature``/``top_k``/
    ``top_p`` of shape (B,) and ``key`` as a (B, 2) batch of uint32 keys —
    every row then samples under its own knobs and its own PRNG stream
    (vmapped categorical), so heterogeneous requests batch in one jitted
    decode and each row's draw is bit-identical to sampling that row alone
    with its key. A single (2,) key with scalar temperature keeps the
    legacy shared-stream behavior."""
    logits = logits.astype(jnp.float32)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if key is None:
        return greedy
    temp = jnp.asarray(temperature, jnp.float32)
    # temperature BEFORE the nucleus filter (the standard order): top-p's
    # keep-set is computed on the distribution actually sampled from, so
    # temp > 1 widens the nucleus and temp < 1 narrows it. top-k is
    # scale-invariant either way. (Greedy rows scale by 1/1e-6; softmax's
    # max-subtraction keeps that finite, and `where` discards the draw.)
    scaled = logits / jnp.maximum(temp, 1e-6)[..., None] \
        if temp.ndim else logits / jnp.maximum(temp, 1e-6)
    if top_k is not None or top_p is not None:
        scaled = top_mask(scaled, top_k, top_p)
    if key.ndim == 2:  # (B, 2) raw key batch: one private stream per row
        sampled = jax.vmap(
            lambda k, row: jax.random.categorical(k, row, axis=-1)
        )(key, scaled).astype(jnp.int32)
    else:  # single key (typed, or raw (2,)): legacy shared stream
        sampled = jax.random.categorical(key, scaled, axis=-1).astype(jnp.int32)
    return jnp.where(temp > 0, sampled, greedy)


# ===========================================================================
# Analytic FLOPs (roofline MODEL_FLOPS term)
# ===========================================================================

def model_flops(cfg, seq_len: int, batch: int, *, decode: bool = False) -> float:
    """6*N_active*D-style estimate: matmul params * tokens * (2 fwd [+4 bwd])."""
    d, f, v = cfg.d_model, cfg.d_ff, cfg.vocab_size
    hd = cfg.resolved_head_dim
    attn_p = d * hd * (cfg.num_heads * 2 + cfg.num_kv_heads * 2)
    if cfg.activation == "swiglu":
        mlp_p = 3 * d * f
    else:
        mlp_p = 2 * d * f
    if cfg.num_experts:
        mlp_p = cfg.experts_per_token * mlp_p + d * cfg.num_experts
    if cfg.family == "ssm":
        h = cfg.num_heads
        attn_p = 5 * d * d + d * d  # r,k,v,g,o + lora-ish
        mlp_p = 2 * d * f
    if cfg.family == "hybrid":
        ed = cfg.ssm_expand * d
        n_attn = cfg.num_layers // cfg.attn_every + 1
        mamba_p = d * (2 * ed + 2 * cfg.ssm_state + ed // 64) + ed * d
        per_layer = mamba_p
        total_p = cfg.num_layers * per_layer + n_attn * 0 + (attn_p + mlp_p)
    else:
        total_p = cfg.num_layers * (attn_p + mlp_p)
        if cfg.is_encoder_decoder:
            total_p += cfg.encoder_layers * (attn_p + mlp_p)
    total_p += v * d  # head
    tokens = batch * (1 if decode else seq_len)
    flops = 2.0 * total_p * tokens
    # attention score/value FLOPs (dense attention archs)
    if cfg.family not in ("ssm",):
        kv_len = seq_len
        q_len = 1 if decode else seq_len
        n_attn = (cfg.num_layers if cfg.family != "hybrid"
                  else cfg.num_layers // cfg.attn_every + 1)
        flops += 4.0 * batch * cfg.num_heads * hd * q_len * kv_len * n_attn * (
            0.5 if not decode else 1.0)
    return flops
