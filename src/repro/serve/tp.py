"""Tensor-parallel serving placement: PartitionSpecs and shard_map wrappers
for the packed ITQ3_S planes and the rotated-int8 KV cache.

The serving TP layout is **column-parallel everywhere**:

* Every packed QTensor data plane (``plane2``/``plane1``/``scales``/``zps``)
  is sharded along its leading output-feature dim N over the ``model`` axis
  (`sharding/rules.py` `_qtensor_leaf_spec`). The per-256-block FWHT/IFWHT
  is block-local along K, so N-sharding never splits a transform: each
  device unpacks, dequantizes, and contracts only its own tiles. The packed
  reduction stream (3.125 bpw) is replicated — cheap, and it keeps the
  decode hot loop free of weight all-gathers.
* The rotated-int8 KV cache shards its codes *and* scale planes along the
  kv_heads dim: each device holds the full time axis for its own heads, so
  decode/prefill attention (per-head online softmax) is device-local with
  NO collective inside the softmax. GQA head counts that don't divide the
  ``model`` axis fall back to a **replicated** cache — a too-small KV is
  the one shape where correctness beats memory.
* fp leaves that survive quantization (norms, biases, routers, SSM decay
  vectors) are replicated; the embedding table shards its D column (the
  gather is exact under column sharding). Row-parallel fp TP (K-sharded
  ``wo``/``down`` + psum) exists on the training side (`R.param_pspecs`);
  serving deliberately avoids it because a psum is a cross-device float
  reduction — the one thing that would break the engine's bit-identical
  token-stream contract. All collectives the serving layout ever needs are
  all-gathers, which are exact.

Two execution paths share these specs:

* **sharding-constrained jit** (default off-TPU): operands carry
  NamedShardings, `shard_hint` constraints steer GSPMD, XLA partitions the
  ref einsums itself.
* **shard_map** (``Runtime.tp_shard_map``, default on real TPU): GSPMD
  cannot partition a ``pallas_call``, so :func:`tp_qmatmul` /
  :func:`tp_decode_attn_q8` / :func:`tp_prefill_attn_q8` explicitly
  shard_map the kernels — each device runs the full fused kernel on its own
  N- (or head-) shard, collective-free.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.qlinear import qmatmul
from repro.core.quantize import QTensor
from repro.kernels.attn_decode import decode_attn_q8, prefill_attn_q8
from repro.sharding import rules as R

__all__ = [
    "serve_rules", "serve_param_pspecs", "param_shardings", "shard_params",
    "cache_pspecs", "shard_cache", "cache_bytes_per_device",
    "restore_shardings", "place_draft", "can_tp_qmatmul", "tp_qmatmul",
    "tp_decode_attn_q8", "tp_prefill_attn_q8",
]


# ---------------------------------------------------------------------------
# Rules / specs
# ---------------------------------------------------------------------------

def serve_rules(mesh: Mesh, cfg) -> R.Rules:
    """Serving variant of :func:`repro.sharding.rules.make_rules`: no FSDP
    (serving weights are read-only), and no sequence-sharded KV — the fused
    attention path runs one online softmax per head, and splitting that
    softmax across devices would put a collective inside the decode loop.
    When GQA kv_heads don't divide the model axis the KV cache is simply
    REPLICATED (``kv_heads=None, kv_seq=None``), trading memory for an
    intact per-head kernel."""
    rules = R.make_rules(mesh, cfg, fsdp=False)
    assignments = dict(rules.assignments)
    assignments["kv_seq"] = None  # never split a serving softmax
    assignments["seq_sp"] = None  # decode is T=1; SP buys nothing here
    return R.Rules(mesh=mesh, assignments=assignments)


def serve_param_pspecs(params, cfg, rules: R.Rules):
    """PartitionSpec pytree for a SERVING params tree (quantized or mixed).

    Packed QTensor planes: N over ``model`` (expert dim for MoE stacks) via
    the shared `_qtensor_leaf_spec`. The embed table column-shards D (exact
    gather). Every other fp leaf is replicated — see the module docstring
    for why serving refuses row-parallel fp psums."""
    msize = rules.mesh.shape.get("model", 1)

    def spec_of(path_parts, leaf):
        parts = [str(getattr(p, "key", getattr(p, "name", p)))
                 for p in path_parts]
        path = "/".join(parts)
        name = parts[-1]
        stacked = R._stack_depth(path_parts)
        if not hasattr(leaf, "shape"):
            return P()
        if "data" in parts and name in R._QDATA:
            return R._qtensor_leaf_spec(path, name, tuple(leaf.shape), rules,
                                        msize, stacked)
        if name == "embed" and leaf.ndim == 2:
            dshard = msize > 1 and leaf.shape[1] % msize == 0
            return P(None, "model" if dshard else None)
        return P(*([None] * leaf.ndim))

    return jax.tree_util.tree_map_with_path(spec_of, params)


def param_shardings(params, cfg, rules: R.Rules):
    """NamedSharding pytree matching ``params`` leaf-for-leaf (including
    the arrays inside each QTensor)."""
    specs = serve_param_pspecs(params, cfg, rules)
    return jax.tree.map(lambda s: NamedSharding(rules.mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def shard_params(params, cfg, rules: R.Rules):
    """Place a (host or device) params tree into the serving TP layout."""
    return jax.device_put(params, param_shardings(params, cfg, rules))


def cache_pspecs(cache, cfg, rules: R.Rules):
    """Specs for a serving cache pytree (`lm.init_cache` layout).

    Attention K/V planes — int8 codes AND their fp16 scale planes, or the
    fp cache — are (L, B, KV, T, HD[|1]): kv_heads over ``model`` when they
    divide, else fully replicated (the GQA fallback). SSM/RWKV recurrent
    states stay replicated (head-sharding them is a named leftover —
    they're O(1) in decoded tokens, so the KV planes dominate)."""
    msize = rules.mesh.shape.get("model", 1)
    kv_ax = rules.assignments.get("kv_heads")

    def spec_of(path_parts, leaf):
        parts = [str(getattr(p, "key", getattr(p, "name", p)))
                 for p in path_parts]
        if not hasattr(leaf, "ndim"):
            return P()
        if parts and parts[0] in ("attn", "xattn") and leaf.ndim == 5:
            ax = kv_ax if (kv_ax and leaf.shape[2] % msize == 0) else None
            return P(None, None, ax, None, None)
        return P(*([None] * leaf.ndim))

    return jax.tree_util.tree_map_with_path(spec_of, cache)


def shard_cache(cache, cfg, rules: R.Rules):
    specs = cache_pspecs(cache, cfg, rules)
    shardings = jax.tree.map(lambda s: NamedSharding(rules.mesh, s), specs,
                             is_leaf=lambda x: isinstance(x, P))
    return jax.device_put(cache, shardings)


def place_draft(draft_params, draft_cfg, mesh: Mesh, draft_rt):
    """Place a speculative DRAFT model into the same serving TP layout as
    the target: its own rules (head/column splits follow the draft's shape,
    which may differ from the target's), threaded into the draft Runtime so
    shard_hint / shard_map dispatch inside the propose loop matches the
    target path's. Returns ``(sharded_params, draft_rt_with_rules)``."""
    rules = serve_rules(mesh, draft_cfg)
    draft_rt = dataclasses.replace(draft_rt, rules=rules, mesh=mesh)
    return shard_params(draft_params, draft_cfg, rules), draft_rt


def cache_bytes_per_device(cache) -> int:
    """Max bytes any single device holds for this cache — the number that
    actually binds a deployment (replicated leaves count fully on every
    device; head-sharded planes count 1/msize)."""
    per: dict[Any, int] = {}
    for leaf in jax.tree.leaves(cache):
        shards = getattr(leaf, "addressable_shards", None)
        if shards is None:  # host array (tests): bill it whole
            per[None] = per.get(None, 0) + int(leaf.nbytes)
            continue
        for s in shards:
            key = s.device.id
            per[key] = per.get(key, 0) + int(s.data.nbytes)
    return max(per.values()) if per else 0


def restore_shardings(cfg, mesh: Mesh) -> Callable[[str, Any], Any]:
    """Restore-to-sharding callback for :func:`repro.checkpoint.ckpt.
    restore_tree`: maps each loaded leaf (by dotted path) to its serving
    placement so a checkpoint's packed planes are ``device_put`` shard-by-
    shard AT LOAD TIME — a 235B plane set never materializes as one
    device-resident tree. QTensor leaves return a per-data-key dict of
    NamedShardings (`_put_qtensor` consumes it)."""
    rules = serve_rules(mesh, cfg)
    msize = mesh.shape.get("model", 1)

    def place(dotted: str, leaf):
        parts = dotted.split(".")
        if parts and parts[0] == "params":  # TrainState checkpoints
            parts = parts[1:]
        path = "/".join(parts)
        stacked = R._stack_depth(parts)
        if isinstance(leaf, QTensor):
            return {k: NamedSharding(mesh, R._qtensor_leaf_spec(
                        path, k, tuple(v.shape), rules, msize, stacked))
                    for k, v in leaf.data.items()}
        if not hasattr(leaf, "shape"):
            return None
        if parts[-1] == "embed" and leaf.ndim == 2:
            dshard = msize > 1 and leaf.shape[1] % msize == 0
            return NamedSharding(mesh, P(None, "model" if dshard else None))
        return NamedSharding(mesh, P(*([None] * leaf.ndim)))

    return place


# ---------------------------------------------------------------------------
# shard_map wrappers over the fused kernels
# ---------------------------------------------------------------------------
# GSPMD partitions einsums but not pallas_call: on real TPU the quantized
# matmul/attention kernels must be shard_mapped explicitly. Each device runs
# the UNMODIFIED kernel on its own column (N) or head shard — the layout is
# chosen so no wrapper ever needs a psum; the only collective shard_map
# introduces is the (exact) gather of a replicated-in_spec operand.

def can_tp_qmatmul(qt: QTensor, mesh: Mesh) -> bool:
    """Column-parallel eligibility: 2-D weight, N divides the model axis,
    and every N-carrying plane row-divides too (dsign is replicated)."""
    msize = mesh.shape.get("model", 1)
    if msize <= 1 or len(qt.meta.shape) != 2 or qt.meta.n % msize:
        return False
    return all(v.shape[0] % msize == 0
               for k, v in qt.data.items() if k != "dsign")


def _qdata_specs(qt: QTensor, msize: int):
    """QTensor-shaped pytree of PartitionSpecs: leading N dim over model."""
    def spec(key, v):
        if key != "dsign" and v.shape[0] % msize == 0:
            return P(*(["model"] + [None] * (v.ndim - 1)))
        return P(*([None] * v.ndim))
    return QTensor({k: spec(k, v) for k, v in qt.data.items()}, qt.meta)


def tp_qmatmul(x: jax.Array, qt: QTensor, rules: R.Rules, *, mode: str,
               backend: str, compute_dtype, tm=None, tn=None,
               act_quant: bool = False) -> jax.Array:
    """Column-parallel ``x @ W_hat`` under shard_map: planes N-sharded, x
    replicated (shard_map gathers it exactly if it arrives sharded), each
    device runs the full qmatmul/itq3_matvec dispatch on its N/msize shard.
    Output is N-sharded; ineligible shapes fall through to plain qmatmul
    (replicated planes). ``act_quant`` composes freely with column
    parallelism: the activation codec depends only on x (replicated), so
    every device quantizes identically and contracts its own N shard."""
    mesh = rules.mesh
    if not can_tp_qmatmul(qt, mesh):
        return qmatmul(x, qt, mode=mode, backend=backend,
                       compute_dtype=compute_dtype, tm=tm, tn=tn,
                       act_quant=act_quant)
    msize = mesh.shape["model"]
    k, n = qt.meta.shape
    local_meta = dataclasses.replace(qt.meta, shape=(k, n // msize))

    def local_fn(xs, q_local):
        q_local = QTensor(q_local.data, local_meta)
        return qmatmul(xs, q_local, mode=mode, backend=backend,
                       compute_dtype=compute_dtype, tm=tm, tn=tn,
                       act_quant=act_quant)

    out_spec = P(*([None] * (x.ndim - 1) + ["model"]))
    fn = jax.shard_map(local_fn, mesh=mesh,
                       in_specs=(P(), _qdata_specs(qt, msize)),
                       out_specs=out_spec, check_vma=False)
    return fn(x, qt)


def _can_tp_heads(kv_heads: int, mesh: Mesh) -> bool:
    msize = mesh.shape.get("model", 1)
    return msize > 1 and kv_heads % msize == 0


_CACHE_KEYS = ("k", "v", "k_scale", "v_scale")


def tp_decode_attn_q8(q, cache, k_tok, v_tok, kv_len, rules: R.Rules, *,
                      backend: str = "auto", tt=None) -> jax.Array:
    """Head-sharded decode attention: each device runs the fused (or ref)
    decode kernel over its own kv_heads slice of codes + scale planes. The
    per-head online softmax never crosses a device. GQA counts that don't
    divide fall through to the plain (replicated-cache) call."""
    mesh = rules.mesh
    if not _can_tp_heads(q.shape[1], mesh):
        return decode_attn_q8(q, cache, k_tok, v_tok, kv_len,
                              backend=backend, tt=tt)
    hq = P(None, "model", None, None, None)   # q (B, KV, G, 1, HD)
    # cache planes: dense (B, KV, T, HD|1) or paged pool (NB, KV, BS, HD|1)
    # — the kv_heads axis is axis 1 either way, so one spec covers both.
    hc = P(None, "model", None, None)
    cache_spec = {key: hc for key in _CACHE_KEYS}
    cache_arg = {key: cache[key] for key in _CACHE_KEYS}
    if "table" in cache:
        # block table (B, MAXB): replicated — block ids index the pool's
        # block axis, which is unsharded; each shard gathers its own heads.
        cache_spec["table"] = P(None, None)
        cache_arg["table"] = cache["table"]
    fn = jax.shard_map(
        lambda q_, c_, kt_, vt_, kl_: decode_attn_q8(
            q_, c_, kt_, vt_, kl_, backend=backend, tt=tt),
        mesh=mesh,
        in_specs=(hq, cache_spec, (hc, hc), (hc, hc), P(None)),
        out_specs=hq, check_vma=False)
    return fn(q, cache_arg, k_tok, v_tok, kv_len)


def tp_prefill_attn_q8(q, cache, kv_len, q_offset, rules: R.Rules, *,
                       backend: str = "auto", tq=None, tt=None) -> jax.Array:
    """Head-sharded prefill counterpart (q is (B, KV, G, TQ, HD))."""
    mesh = rules.mesh
    if not _can_tp_heads(q.shape[1], mesh):
        return prefill_attn_q8(q, cache, kv_len, q_offset,
                               backend=backend, tq=tq, tt=tt)
    hq = P(None, "model", None, None, None)
    hc = P(None, "model", None, None)
    cache_spec = {key: hc for key in _CACHE_KEYS}
    cache_arg = {key: cache[key] for key in _CACHE_KEYS}
    if "table" in cache:
        cache_spec["table"] = P(None, None)
        cache_arg["table"] = cache["table"]
    fn = jax.shard_map(
        lambda q_, c_, kl_, off_: prefill_attn_q8(
            q_, c_, kl_, off_, backend=backend, tq=tq, tt=tt),
        mesh=mesh,
        in_specs=(hq, cache_spec, P(None), P(None)),
        out_specs=hq, check_vma=False)
    return fn(q, cache_arg, kv_len, q_offset)
