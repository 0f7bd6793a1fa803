"""Pallas TPU kernel: fused attention over the rotated-int8 KV cache.

The serving counterpart of ``serve/kv_quant.py`` (paper §7.2): the cache
stores each K/V token vector FWHT-rotated and int8-quantized with a
per-vector fp16 scale. Because H is an isometry,

    q . k  =  (H q) . (H k)

so the score pass needs NO K-side dequantization: the kernel streams int8
K tiles straight from the cache, contracts them against the *rotated*
query on the MXU, and multiplies the per-token scale into the score row.
V dequantizes per tile, but only to its ROTATED values and only after the
softmax weight is known: the kernel folds the per-token V scale into the
weight row (``(p * v_scale) @ v_codes``), accumulates the weighted sum in
the rotated domain, and leaves the single inverse FWHT for the caller —
``sum_t w_t (H v_t) = H (sum_t w_t v_t)``, so one head_dim-point transform
per query span undoes the rotation for every cached token at once. A full
dequantized K/V buffer is never materialized anywhere.

Both serving regimes run one online-softmax step per (slot, kv head, key
tile) (:func:`_online_step`), on two grids chosen by the operands' rank:

* **decode** (``q_len == 1``): grid ``(B, NT)``, one step per (slot, key
  tile) with ALL the slot's kv heads in it: ``(KV, TT, HD)`` code blocks
  and ``(KV, TT)`` scales read straight from the cache's own ``(B, KV, T,
  HD)`` planes or the pool's ``(NB, KV, BS, HD)``, the query and outputs
  ``(KV, G, HD)``, scratch ``(KV*G, HD)``. Inside a step each head runs
  the same arithmetic in the same order as a row of the row grid, so the
  results are bitwise equal; a head's key tile is too small to pay for a
  grid step of its own. The current token rides OUTSIDE the cache, so the
  kernel runs causal-free over ``kv_len`` cached positions and returns the
  UNNORMALIZED ``(acc, m, l)`` triple; :func:`decode_attn_q8` merges the
  encoded self-token term (one more online-softmax step) and normalizes.
* **prefill** (``q_len > 1``, speculative verify spans included): the row
  grid ``(R, NQ, NT)`` over ``R = B*KV`` rows — a query-tile
  dimension with key tiles innermost. The in-flight span's K/V codes are
  already written into the cache at ``q_offset..q_offset+q_len-1``, so the
  causal mask ``q_offset + qpos >= kpos`` inside the key-tile loop merges
  the span's self-attention block into the same cache pass — the
  width-``q_len`` generalization of the decode path's
  :func:`_merge_self_token`. Chunked prefill therefore NEVER dequantizes
  the cache buffer; :func:`prefill_attn_q8` normalizes and applies the one
  inverse FWHT per query span.

Each row of the row grid is one (batch, kv_head) pair with a running
online-softmax state in VMEM scratch (the decode grid keeps KV such states):

    m   (TQ*G, 1)   running max over key tiles
    l   (TQ*G, 1)   running denominator
    acc (TQ*G, HD)  running weighted V sum (unnormalized)

Tiles are masked by ``kv_len[r]`` (per-row valid cache length: slot-batched
serving is ragged), so pad tiles and unwritten cache slots contribute
nothing.

Dispatch mirrors qmatmul: ``backend="auto"`` runs the kernel on real TPU
hardware for power-of-two head dims (64 included: a (TT, 64) code block
spans the array's whole minor dim, which the TPU block rule accepts), and
falls back to the jnp reference — the same math as einsums — in interpret
mode or for non-pow2 shapes; ``backend="pallas"`` on an unsupported shape
fails fast with a ValueError naming the gate instead of dying in Pallas
lowering. The
backends share score/weight formulas exactly (scores from codes, V scale
folded into the weight row), so greedy token streams are identical.

**Paged layout** (serve/paged.py): the same kernels also read a BLOCK-POOL
cache — K/V planes stored as ``(num_blocks, KV, block_size, HD)``. The
decode grid reads the slot's int32 block table ``(B, MAXB)`` as a scalar-
prefetch operand, so its key-tile index map does exactly one more gather:
``block = table[b, tile // tiles_per_block]`` instead of ``b``. The row
grid (prefill) sees the pool as ``(num_blocks*KV, block_size, HD)`` rows
and takes a per-row table from :func:`paged_row_table`. The kernel body,
masks, and early exit are untouched (masks key on the LOGICAL grid
position), so paged and dense attention are bitwise identical whenever the
gathered blocks hold the same codes/scales — the property tests/test_paged.py
pins. The jnp reference path gathers ``pool[table]`` back into the dense
per-slot view and reuses the dense reference math.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.fwht import fwht, is_pow2

__all__ = [
    "attn_q8_pallas", "attn_decode_q8_pallas", "decode_attn_q8",
    "decode_attn_q8_ref", "prefill_attn_q8", "prefill_attn_q8_ref",
    "paged_row_table", "paged_to_dense",
    "kernel_supported", "resolve_attn_path", "DEFAULT_TT", "DEFAULT_TQ",
    "ATTN_BACKENDS",
]

DEFAULT_TT = 256  # key-tile width (tokens streamed per grid step)
DEFAULT_TQ = 128  # query-tile width (prefill rows per grid step)
# VMEM for the decode grid's K and V code blocks of all kv heads, double
# buffered; v5e's default scoped VMEM is 16 MiB
FOLD_VMEM_BUDGET = 8 * 1024 * 1024
NEG_INF = -1e30
ATTN_BACKENDS = ("auto", "ref", "pallas")


def kernel_supported(head_dim: int) -> bool:
    """Shape gate for the fused kernel: a power-of-two head_dim (the FWHT
    the cache codec rotates by needs one). Every such width from 32 to 256
    compiles for TPU v5e, dense and paged, decode and prefill
    (tests/test_tpu_compile.py pins 64 and 128)."""
    return is_pow2(head_dim)


def _use_kernel(backend: str, head_dim: int, *, interpret: bool) -> bool:
    """Resolve the backend knob to kernel-or-ref, failing FAST (mirroring
    qmatmul's dispatch errors) when ``backend="pallas"`` is forced onto a
    shape the kernel can't lower — a non-pow2 head_dim would otherwise die
    deep inside Pallas. ``"auto"`` takes the kernel wherever it compiles
    for the chip (never in interpret mode: the reference is the CPU path)."""
    if backend not in ATTN_BACKENDS:
        raise ValueError(f"backend {backend!r} not in {ATTN_BACKENDS}")
    if backend == "pallas":
        if not kernel_supported(head_dim):
            raise ValueError(
                f"attention kernel shape gate: head_dim {head_dim} must be a "
                f"power of two; use backend='ref' or 'auto' for this shape")
        return True
    if backend == "ref":
        return False
    return not interpret and kernel_supported(head_dim)


def resolve_attn_path(backend: str, head_dim: int) -> str:
    """``"pallas"`` or ``"ref"``: the implementation the quantized-cache
    attention runs for ``backend`` on this process's default device."""
    from repro.kernels.ops import auto_interpret  # local: avoid import cycle

    use = _use_kernel(backend, head_dim, interpret=auto_interpret())
    return "pallas" if use else "ref"


def _tile_limit(len_val, off_val, qi, *, tq: int, causal: bool):
    """Exclusive key-position bound for query tile ``qi``: valid cache
    length, tightened under causality to the tile's LAST query row (no key
    past ``off + (qi+1)*tq - 1`` can ever be attended by this tile)."""
    limit = len_val
    if causal:
        limit = jnp.minimum(limit, off_val + (qi + 1) * tq)
    return limit


def _last_tile(limit, *, tt: int):
    """Index of the last key tile carrying any valid position:
    ``ceil(limit/tt) - 1``, floored at 0 (an empty row still needs one
    well-defined block index)."""
    return jnp.maximum((limit + tt - 1) // tt - 1, 0)


def _online_step(q, kc, ks, vc, vs, valid, m_old, dn, acc, *, sm_scale):
    """One key tile of the online softmax for one (slot, kv head): scores
    straight from the K codes (``(Hq).(Hk) == q.k``) times the per-token
    scale, masked, then the V scale folded into the weight row
    (``(p * v_scale) @ v_codes``). q (rows, HD) f32; kc/vc (TT, HD) int8;
    ks/vs (1, TT) f32; valid (1|rows, TT); m_old/dn (rows, 1) and acc
    (rows, HD) the running state. Returns the new ``(m, dn, acc)``."""
    s = jax.lax.dot_general(q, kc.astype(jnp.float32),
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = s * (ks * sm_scale)  # (rows, TT) * (1, TT)
    s = jnp.where(valid, s, NEG_INF)
    m_new = jnp.maximum(m_old, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_old - m_new)
    p = jnp.exp(s - m_new)
    p = jnp.where(valid, p, 0.0)  # NEG_INF - NEG_INF would leak exp(0)
    dn = dn * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc = acc * alpha + jax.lax.dot_general(
        p * vs, vc.astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return m_new, dn, acc


def _attn_q8_kernel(
    len_ref,  # (R,) int32 scalar-prefetch — valid cache length per row
    off_ref,  # (R,) int32 scalar-prefetch — absolute position of query 0
    q_ref,    # (1, TQ, G, HD) f32 — rotated query tile
    kc_ref,   # (1, TT, HD) int8 — K codes tile
    ks_ref,   # (1, 1, TT) f32 — K per-token scales
    vc_ref,   # (1, TT, HD) int8 — V codes tile
    vs_ref,   # (1, 1, TT) f32 — V per-token scales
    o_ref,    # (1, TQ, G, HD) f32 — unnormalized weighted V sum
    m_ref,    # (1, TQ, G, 1) f32 — running max
    l_ref,    # (1, TQ, G, 1) f32 — running denominator
    acc_ref,  # scratch (TQ*G, HD) f32
    mx_ref,   # scratch (TQ*G, 1) f32
    dn_ref,   # scratch (TQ*G, 1) f32
    *,
    sm_scale: float,
    tq: int,
    g: int,
    tt: int,
    nt: int,
    causal: bool,
    early_exit: bool,
):
    r = pl.program_id(0)
    qt = pl.program_id(1)
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        mx_ref[...] = jnp.full_like(mx_ref, NEG_INF)
        dn_ref[...] = jnp.zeros_like(dn_ref)

    limit = _tile_limit(len_ref[r], off_ref[r], qt, tq=tq, causal=causal)
    # Tile-level early exit: grid steps past ceil(limit/tt) tiles are
    # fully masked (every kpos fails the len/causal test), so skip their
    # compute entirely — their DMA was already elided by the clamped
    # index maps (same block index => Pallas skips the re-fetch). The
    # masks below keep using the GRID position t, so a skipped tile
    # contributes exactly nothing either way (the early_exit=False parity
    # configuration runs the full loop to prove it).
    run = (t * tt < limit) if early_exit else (t >= 0)

    @pl.when(run)
    def _update():
        rows = tq * g
        hd = q_ref.shape[-1]
        q = q_ref[0].reshape(rows, hd)  # (TQ*G, HD) f32, already rotated
        kpos = t * tt + jax.lax.broadcasted_iota(jnp.int32, (1, tt), 1)
        valid = kpos < len_ref[r]  # (1, TT)
        if causal:
            # flattened row i is query (i // g): absolute position off +
            # qt*TQ + i//g must not look past itself into the key tile
            qpos = (off_ref[r] + qt * tq
                    + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) // g)
            valid = valid & (kpos <= qpos)  # (rows, TT)
        mx_ref[...], dn_ref[...], acc_ref[...] = _online_step(
            q, kc_ref[0], ks_ref[0], vc_ref[0], vs_ref[0], valid,
            mx_ref[...], dn_ref[...], acc_ref[...], sm_scale=sm_scale)

    @pl.when(t == nt - 1)
    def _flush():
        hd = q_ref.shape[-1]
        o_ref[...] = acc_ref[...].reshape(1, tq, g, hd)
        m_ref[...] = mx_ref[...].reshape(1, tq, g, 1)
        l_ref[...] = dn_ref[...].reshape(1, tq, g, 1)


def _attn_q8_decode_kernel(
    len_ref,  # (B,) int32 scalar-prefetch — valid cache length per slot
    q_ref,    # (1, KV, G, HD) f32 — rotated queries of every kv head
    kc_ref,   # (1, KV, TT, HD) int8 — K codes tile of every kv head
    ks_ref,   # (1, KV, TT) f32 — K per-token scales
    vc_ref,   # (1, KV, TT, HD) int8 — V codes tile
    vs_ref,   # (1, KV, TT) f32 — V per-token scales
    o_ref,    # (1, KV, G, HD) f32 — unnormalized weighted V sum
    m_ref,    # (1, KV, G, 1) f32 — running max
    l_ref,    # (1, KV, G, 1) f32 — running denominator
    acc_ref,  # scratch (KV*G, HD) f32
    mx_ref,   # scratch (KV*G, 1) f32
    dn_ref,   # scratch (KV*G, 1) f32
    *,
    sm_scale: float,
    g: int,
    tt: int,
    nt: int,
    early_exit: bool,
):
    """Decode over one (slot, key tile) with every kv head of the slot in
    the step: each head runs :func:`_online_step` on its own rows of the
    scratch, in head order, exactly as a row of the row grid would."""
    b = pl.program_id(0)
    t = pl.program_id(1)
    kv = q_ref.shape[1]

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        mx_ref[...] = jnp.full_like(mx_ref, NEG_INF)
        dn_ref[...] = jnp.zeros_like(dn_ref)

    # one kv_len a slot: every head of the step exits together
    run = (t * tt < len_ref[b]) if early_exit else (t >= 0)

    @pl.when(run)
    def _update():
        kpos = t * tt + jax.lax.broadcasted_iota(jnp.int32, (1, tt), 1)
        valid = kpos < len_ref[b]  # (1, TT)
        for h in range(kv):
            rows = slice(h * g, (h + 1) * g)
            (mx_ref[rows, :], dn_ref[rows, :],
             acc_ref[rows, :]) = _online_step(
                q_ref[0, h], kc_ref[0, h], ks_ref[0, h:h + 1, :],
                vc_ref[0, h], vs_ref[0, h:h + 1, :], valid,
                mx_ref[rows, :], dn_ref[rows, :], acc_ref[rows, :],
                sm_scale=sm_scale)

    @pl.when(t == nt - 1)
    def _flush():
        for h in range(kv):
            rows = slice(h * g, (h + 1) * g)
            o_ref[0, h] = acc_ref[rows, :]
            m_ref[0, h] = mx_ref[rows, :]
            l_ref[0, h] = dn_ref[rows, :]


@functools.partial(jax.jit, static_argnames=("tq", "tt", "causal",
                                             "interpret", "sm_scale",
                                             "early_exit", "block_size"))
def attn_q8_pallas(
    q_rot: jax.Array,     # (R, TQ_total, G, HD) f32 — ROTATED queries
    k_codes: jax.Array,   # (R, T, HD) int8 — or (PR, BS, HD) pooled blocks
    k_scale: jax.Array,   # (R, T) f16/f32 — or (PR, BS)
    v_codes: jax.Array,   # (R, T, HD) int8 — or (PR, BS, HD)
    v_scale: jax.Array,   # (R, T) f16/f32 — or (PR, BS)
    kv_len: jax.Array,    # (R,) int32 — valid cache positions per row
    q_offset: jax.Array | None = None,  # (R,) int32 — position of query 0
    table: jax.Array | None = None,  # (R, MAXB) int32 pool-row block table
    *,
    sm_scale: float,
    causal: bool = True,
    tq: int = DEFAULT_TQ,
    tt: int = DEFAULT_TT,
    interpret: bool = True,
    early_exit: bool = True,
    block_size: int | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Online-softmax attention over the quantized cache, tiled over both
    queries and keys (grid ``(R, NQ, NT)``, key tiles innermost).

    ``kv_len``/``q_offset`` ride as SCALAR-PREFETCH operands
    (:class:`pltpu.PrefetchScalarGridSpec`), so the K/V tile index maps can
    read them: with ``early_exit=True`` (default) every key-tile index past
    ``ceil(limit/tt)`` — where ``limit`` is the row's valid length,
    causally tightened per query tile — is CLAMPED to the last needed tile.
    Pallas skips the DMA for a revisited block index and ``pl.when``
    predicates away the compute, so a 4-token decode against a 32k-slot
    cache streams one tile, not 128. ``early_exit=False`` runs the full
    key loop (the parity configuration: both must agree bitwise, because
    skipped tiles are exactly the fully-masked ones).

    With ``table``/``block_size`` set, the K/V operands are a BLOCK POOL:
    ``(pool_rows, block_size, ...)`` planes whose row for logical key tile
    ``ti`` of grid row ``i`` is ``table[i, ti*tt // block_size]`` — the
    per-slot block table already multiplied out to pool-row units by the
    caller (serve/paged.py). The index maps do that one extra gather; the
    kernel body and its kv_len/causal masks keep using LOGICAL positions
    ``ti*tt + j``, so a paged pass is bitwise identical to the dense pass
    over the same token contents. ``tt`` is clamped to divide
    ``block_size`` (a key tile never straddles two pool blocks).

    Returns the UNNORMALIZED triple ``(acc (R, TQ, G, HD), m (R, TQ, G, 1),
    l (R, TQ, G, 1))`` so the caller chooses what to merge before
    normalizing (decode merges the in-flight token's self term; prefill,
    whose span is already in the cache, just divides).

    **Slot planes.** Four-dimensional codes ``(B, KV, T, HD)`` (dense) or
    ``(NB, KV, BS, HD)`` (the pool's own planes), with scales ``(B|NB, KV,
    T|BS)`` and ``q_rot`` ``(B, KV, G, HD)``, take the decode grid
    ``(B, NT)`` instead: one query position, causal-free (``causal`` must
    be False; ``tq`` and ``q_offset`` are unused), ``kv_len`` and ``table``
    per slot — ``table`` is then the per-slot pool-BLOCK table ``(B,
    MAXB)``. Returns ``(acc (B, KV, G, HD), m, l (B, KV, G, 1))``, bitwise
    equal to the row grid over the same data."""
    if k_codes.ndim == 4:
        if causal:
            raise ValueError("slot planes take the causal-free decode grid; "
                             "pass causal=False, or (rows, T, HD) planes")
        return _attn_q8_slots(q_rot, k_codes, k_scale, v_codes, v_scale,
                              kv_len, table, sm_scale=sm_scale, tt=tt,
                              interpret=interpret, early_exit=early_exit,
                              block_size=block_size)
    r, tq_total, g, hd = q_rot.shape
    if q_offset is None:
        q_offset = jnp.zeros((r,), jnp.int32)
    paged = table is not None
    if paged:
        if block_size is None:
            raise ValueError("paged attention needs block_size with table")
        bs = int(block_size)
        if k_codes.shape[1] != bs:
            raise ValueError(
                f"pooled K/V planes must be (pool_rows, block_size, ...); "
                f"got {k_codes.shape} for block_size {bs}")
        # a key tile must never straddle two pool blocks: largest common
        # divisor keeps power-of-two tunings intact (min of the two)
        tt = math.gcd(max(1, min(tt, bs)), bs)
        tpb = bs // tt  # key tiles per pool block
        nt = table.shape[1] * tpb  # logical tiles = MAXB blocks * tpb
    else:
        t = k_codes.shape[1]
        tt = max(1, min(tt, t))
        pad_t = (-t) % tt
        if pad_t:
            pad3 = ((0, 0), (0, pad_t), (0, 0))
            k_codes = jnp.pad(k_codes, pad3)
            v_codes = jnp.pad(v_codes, pad3)
            k_scale = jnp.pad(k_scale, ((0, 0), (0, pad_t)))
            v_scale = jnp.pad(v_scale, ((0, 0), (0, pad_t)))
        nt = k_codes.shape[1] // tt

    tq = max(1, min(tq, tq_total))
    pad_q = (-tq_total) % tq
    if pad_q:
        # pad queries attend to extra (still kv_len-masked) keys and are
        # sliced away below: zero rows, never NaN rows
        q_rot = jnp.pad(q_rot, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    nq = q_rot.shape[1] // tq

    def kv_tile(i, qi, ti, len_ref, off_ref):
        if not early_exit:
            return (i, ti, 0)
        limit = _tile_limit(len_ref[i], off_ref[i], qi, tq=tq, causal=causal)
        # revisit the last needed tile for every ti beyond it: an unchanged
        # block index is Pallas's "don't re-DMA" signal
        return (i, jnp.minimum(ti, _last_tile(limit, tt=tt)), 0)

    def kv_tile_paged(i, qi, ti, len_ref, off_ref, tbl_ref):
        if early_exit:
            limit = _tile_limit(len_ref[i], off_ref[i], qi, tq=tq,
                                causal=causal)
            ti = jnp.minimum(ti, _last_tile(limit, tt=tt))
        # the one extra scalar-prefetch gather paging costs: logical tile
        # -> (pool row via the block table, tile offset within the block)
        return (tbl_ref[i, ti // tpb], ti % tpb, 0)

    def kv_scale_tile(i, qi, ti, *refs):
        row, tile, _ = (kv_tile_paged if paged else kv_tile)(i, qi, ti, *refs)
        return (row, 0, tile)

    kv_map = kv_tile_paged if paged else kv_tile

    def q_map(i, qi, ti, *refs):
        return (i, qi, 0, 0)

    kernel = functools.partial(_attn_q8_kernel, sm_scale=sm_scale, tq=tq,
                               g=g, tt=tt, nt=nt, causal=causal,
                               early_exit=early_exit)
    if paged:
        # scalar-prefetch refs lead the kernel args; the body never reads
        # the table (only the index maps do), so drop it before dispatch
        kernel = functools.partial(_drop_table_ref, kernel)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3 if paged else 2,  # kv_len, q_offset[, table]
        grid=(r, nq, nt),
        in_specs=[
            pl.BlockSpec((1, tq, g, hd), q_map),
            pl.BlockSpec((1, tt, hd), kv_map),
            pl.BlockSpec((1, 1, tt), kv_scale_tile),
            pl.BlockSpec((1, tt, hd), kv_map),
            pl.BlockSpec((1, 1, tt), kv_scale_tile),
        ],
        out_specs=[
            pl.BlockSpec((1, tq, g, hd), q_map),
            pl.BlockSpec((1, tq, g, 1), q_map),
            pl.BlockSpec((1, tq, g, 1), q_map),
        ],
        scratch_shapes=[
            pltpu.VMEM((tq * g, hd), jnp.float32),
            pltpu.VMEM((tq * g, 1), jnp.float32),
            pltpu.VMEM((tq * g, 1), jnp.float32),
        ],
    )
    scalars = [kv_len.astype(jnp.int32), q_offset.astype(jnp.int32)]
    if paged:
        scalars.append(table.astype(jnp.int32))
    out, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((r, nq * tq, g, hd), jnp.float32),
            jax.ShapeDtypeStruct((r, nq * tq, g, 1), jnp.float32),
            jax.ShapeDtypeStruct((r, nq * tq, g, 1), jnp.float32),
        ],
        interpret=interpret,
    )(*scalars, q_rot.astype(jnp.float32), k_codes,
      _scale_rows(k_scale), v_codes, _scale_rows(v_scale))
    if pad_q:
        out, m, l = out[:, :tq_total], m[:, :tq_total], l[:, :tq_total]
    return out, m, l


def _fold_tt(tt: int, kv: int, hd: int) -> int:
    """Key-tile width for the decode grid: ``tt`` halved (while it stays a
    multiple of 128, the scale block's lane rule) until the K and V code
    blocks of all ``kv`` heads fit :data:`FOLD_VMEM_BUDGET` double-buffered.
    An int8 ``(TT, HD)`` tile fills whole 128-lane rows in VMEM."""
    lanes = -(-hd // 128) * 128
    while tt % 256 == 0 and 2 * 2 * kv * tt * lanes > FOLD_VMEM_BUDGET:
        tt //= 2
    return tt


def _attn_q8_slots(q_rot, k_codes, k_scale, v_codes, v_scale, kv_len, table,
                   *, sm_scale, tt, interpret, early_exit, block_size):
    """The decode grid ``(B, NT)`` over slot planes (see
    :func:`attn_q8_pallas`): a step moves the ``(KV, TT, HD)`` code tiles
    and ``(KV, TT)`` scales of every kv head of one slot."""
    b, kv, g, hd = q_rot.shape
    paged = table is not None
    if paged:
        if block_size is None:
            raise ValueError("paged attention needs block_size with table")
        bs = int(block_size)
        if k_codes.shape[2] != bs:
            raise ValueError(
                f"pool planes must be (num_blocks, kv_heads, block_size, "
                f"HD); got {k_codes.shape} for block_size {bs}")
        tt = _fold_tt(math.gcd(max(1, min(tt, bs)), bs), kv, hd)
        tpb = bs // tt  # key tiles per pool block
        nt = table.shape[1] * tpb
    else:
        t = k_codes.shape[2]
        tt = _fold_tt(max(1, min(tt, t)), kv, hd)
        pad_t = (-t) % tt
        if pad_t:
            k_codes = jnp.pad(k_codes, ((0, 0), (0, 0), (0, pad_t), (0, 0)))
            v_codes = jnp.pad(v_codes, ((0, 0), (0, 0), (0, pad_t), (0, 0)))
            k_scale = jnp.pad(k_scale, ((0, 0), (0, 0), (0, pad_t)))
            v_scale = jnp.pad(v_scale, ((0, 0), (0, 0), (0, pad_t)))
        nt = k_codes.shape[2] // tt

    def tile(i, ti, len_ref, *tbl):
        if early_exit:
            ti = jnp.minimum(ti, _last_tile(len_ref[i], tt=tt))
        if paged:
            # the slot's block table, read directly: (pool block, tile in it)
            return tbl[0][i, ti // tpb], ti % tpb
        return i, ti

    def codes_map(i, ti, *refs):
        blk, tj = tile(i, ti, *refs)
        return (blk, 0, tj, 0)

    def scale_map(i, ti, *refs):
        blk, tj = tile(i, ti, *refs)
        return (blk, 0, tj)

    def q_map(i, ti, *refs):
        return (i, 0, 0, 0)

    kernel = functools.partial(_attn_q8_decode_kernel, sm_scale=sm_scale,
                               g=g, tt=tt, nt=nt, early_exit=early_exit)
    if paged:
        kernel = functools.partial(_drop_slot_table_ref, kernel)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2 if paged else 1,  # kv_len[, table]
        grid=(b, nt),
        in_specs=[
            pl.BlockSpec((1, kv, g, hd), q_map),
            pl.BlockSpec((1, kv, tt, hd), codes_map),
            pl.BlockSpec((1, kv, tt), scale_map),
            pl.BlockSpec((1, kv, tt, hd), codes_map),
            pl.BlockSpec((1, kv, tt), scale_map),
        ],
        out_specs=[
            pl.BlockSpec((1, kv, g, hd), q_map),
            pl.BlockSpec((1, kv, g, 1), q_map),
            pl.BlockSpec((1, kv, g, 1), q_map),
        ],
        scratch_shapes=[
            pltpu.VMEM((kv * g, hd), jnp.float32),
            pltpu.VMEM((kv * g, 1), jnp.float32),
            pltpu.VMEM((kv * g, 1), jnp.float32),
        ],
    )
    scalars = [kv_len.astype(jnp.int32)]
    if paged:
        scalars.append(table.astype(jnp.int32))
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, kv, g, hd), jnp.float32),
            jax.ShapeDtypeStruct((b, kv, g, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, kv, g, 1), jnp.float32),
        ],
        interpret=interpret,
    )(*scalars, q_rot.astype(jnp.float32), k_codes,
      k_scale.astype(jnp.float32), v_codes, v_scale.astype(jnp.float32))


def _scale_rows(scale: jax.Array) -> jax.Array:
    """(R, T) per-token scales -> (R, 1, T) f32: a unit sublane axis lets
    the kernel tile the time axis as (1, 1, TT), which meets the TPU's
    (8, 128) block rule for any R (a (1, TT) block over (R, T) does not)."""
    return scale.astype(jnp.float32)[:, None, :]


def _drop_table_ref(kernel, len_ref, off_ref, tbl_ref, *rest):
    """Adapter for the paged call: the block table is scalar-prefetch
    operand #3 (index maps read it) but the kernel body has no use for it."""
    return kernel(len_ref, off_ref, *rest)


def _drop_slot_table_ref(kernel, len_ref, tbl_ref, *rest):
    """The decode grid's counterpart of :func:`_drop_table_ref`."""
    return kernel(len_ref, *rest)


def attn_decode_q8_pallas(
    q_rot: jax.Array,    # (R, G, HD) f32 — ROTATED queries, R = B*KV rows
    k_codes: jax.Array,  # (R, T, HD) int8 — or (PR, BS, HD) pooled blocks
    k_scale: jax.Array,  # (R, T) f16/f32 — or (PR, BS)
    v_codes: jax.Array,  # (R, T, HD) int8 — or (PR, BS, HD)
    v_scale: jax.Array,  # (R, T) f16/f32 — or (PR, BS)
    kv_len: jax.Array,   # (R,) int32 — valid cache positions per row
    table: jax.Array | None = None,  # (R, MAXB) int32 pool-row block table
    *,
    sm_scale: float,
    tt: int = DEFAULT_TT,
    interpret: bool = True,
    early_exit: bool = True,
    block_size: int | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Decode attention over the quantized cache: the TQ=1, causal-free
    specialization of :func:`attn_q8_pallas` (decode attends a cache that
    does not yet contain the current token, so no in-span causality
    exists). Returns the unnormalized ``(acc (R, G, HD), m (R, G, 1),
    l (R, G, 1))`` triple — see :func:`decode_attn_q8` for the self-token
    merge.

    Slot planes (4-D codes, ``q_rot`` ``(B, KV, G, HD)``, ``kv_len`` and
    the block ``table`` per slot) run the decode grid ``(B, NT)`` and
    return ``(B, KV, G, ...)``; row planes run the row grid ``(R, 1,
    NT)``, whose results the decode grid matches bitwise."""
    slots = k_codes.ndim == 4
    acc, m, l = attn_q8_pallas(
        q_rot if slots else q_rot[:, None], k_codes, k_scale, v_codes,
        v_scale, kv_len, None, table, sm_scale=sm_scale, causal=False, tq=1,
        tt=tt, interpret=interpret, early_exit=early_exit,
        block_size=block_size)
    return (acc, m, l) if slots else (acc[:, 0], m[:, 0], l[:, 0])


def _merge_self_token(acc, m, l, s_self, v_self):
    """One more online-softmax step for the current token, then normalize.

    acc (..., G, HD), m/l (..., G, 1); s_self (..., G, 1) score of the new
    token; v_self (..., 1, HD) its dequantized V row."""
    m_tot = jnp.maximum(m, s_self)
    alpha = jnp.exp(m - m_tot)
    p_self = jnp.exp(s_self - m_tot)  # (..., G, 1)
    l_tot = l * alpha + p_self
    out = acc * alpha + p_self * v_self
    return out / l_tot


def decode_attn_q8_ref(
    q_rot: jax.Array,       # (B, KV, G, HD) f32 rotated queries
    k_codes: jax.Array,     # (B, KV, T, HD) int8
    k_scale: jax.Array,     # (B, KV, T, 1)
    v_codes: jax.Array,     # (B, KV, T, HD) int8
    v_scale: jax.Array,     # (B, KV, T, 1)
    kv_len: jax.Array,      # (B,) int32
    *,
    sm_scale: float,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """jnp reference for the kernel's cache pass: identical score and
    V-scale-folding formulas, plain (non-online) max/sum over the full key
    width. Returns the same unnormalized (acc, m, l) triple."""
    s = jnp.einsum("bkgd,bktd->bkgt", q_rot.astype(jnp.float32),
                   k_codes.astype(jnp.float32))
    s = s * (jnp.swapaxes(k_scale.astype(jnp.float32), -1, -2) * sm_scale)
    tk = k_codes.shape[2]
    kpos = jnp.arange(tk)
    valid = kpos[None, None, None, :] < kv_len[:, None, None, None]
    s = jnp.where(valid, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)  # (B, KV, G, 1)
    p = jnp.where(valid, jnp.exp(s - m), 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    pv = p * jnp.swapaxes(v_scale.astype(jnp.float32), -1, -2)
    acc = jnp.einsum("bkgt,bktd->bkgd", pv, v_codes.astype(jnp.float32))
    return acc, m, l


def prefill_attn_q8_ref(
    q_rot: jax.Array,       # (B, KV, G, TQ, HD) f32 rotated queries
    k_codes: jax.Array,     # (B, KV, T, HD) int8
    k_scale: jax.Array,     # (B, KV, T, 1)
    v_codes: jax.Array,     # (B, KV, T, HD) int8
    v_scale: jax.Array,     # (B, KV, T, 1)
    kv_len: jax.Array,      # (B,) int32
    q_offset: jax.Array,    # (B,) int32
    *,
    sm_scale: float,
    causal: bool = True,
    chunk: int = DEFAULT_TQ,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """jnp reference for the q-tile cache pass: same score and
    V-scale-folding formulas as the kernel, scanned over query chunks so a
    32k-token prefill never materializes a (TQ, T) score tensor for the
    whole span at once — and never a dequantized K/V buffer (scores come
    straight from the codes). Returns unnormalized (acc (B, KV, G, TQ, HD),
    m, l (B, KV, G, TQ, 1))."""
    b, kv, g, tq_total, hd = q_rot.shape
    tk = k_codes.shape[2]
    kc = k_codes.astype(jnp.float32)
    vc = v_codes.astype(jnp.float32)
    ks_row = jnp.swapaxes(k_scale.astype(jnp.float32), -1, -2)  # (B,KV,1,Tk)
    vs_row = jnp.swapaxes(v_scale.astype(jnp.float32), -1, -2)
    kpos = jnp.arange(tk)
    len_mask = kpos[None, None, None, None, :] < kv_len[
        :, None, None, None, None]

    chunk = max(1, min(chunk, tq_total))
    pad = (-tq_total) % chunk
    q = q_rot.astype(jnp.float32)
    if pad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, 0), (0, pad), (0, 0)))
    nq = q.shape[3] // chunk
    qc = jnp.moveaxis(q.reshape(b, kv, g, nq, chunk, hd), 3, 0)

    def one_chunk(ci, qi):
        s = jnp.einsum("bkgqd,bktd->bkgqt", qi, kc)
        s = s * (ks_row[:, :, None] * sm_scale)  # (B,KV,1,1,Tk) broadcast
        valid = len_mask
        if causal:
            qpos = (q_offset[:, None] + ci * chunk
                    + jnp.arange(chunk))  # (B, chunk)
            valid = valid & (kpos[None, None, None, None, :]
                             <= qpos[:, None, None, :, None])
        s = jnp.where(valid, s, NEG_INF)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.where(valid, jnp.exp(s - m), 0.0)
        l = jnp.sum(p, axis=-1, keepdims=True)
        pv = p * vs_row[:, :, None]
        acc = jnp.einsum("bkgqt,bktd->bkgqd", pv, vc)
        return acc, m, l

    if nq == 1:
        acc, m, l = one_chunk(0, qc[0])
        acc, m, l = acc[None], m[None], l[None]
    else:
        body = jax.checkpoint(lambda args: one_chunk(*args))
        acc, m, l = jax.lax.map(body, (jnp.arange(nq), qc))

    def unchunk(a):
        a = jnp.moveaxis(a, 0, 3)  # (B, KV, G, nq, chunk, ...)
        a = a.reshape(b, kv, g, nq * chunk, a.shape[-1])
        return a[:, :, :, :tq_total]
    return unchunk(acc), unchunk(m), unchunk(l)


def paged_row_table(table: jax.Array, kv_heads: int) -> jax.Array:
    """Expand a per-slot pool-BLOCK table (B, MAXB) to the per-(b, kv_head)
    pool-ROW table (B*KV, MAXB) the row grid's index maps consume: pooled
    planes flatten (num_blocks, KV, ...) to row ``block*KV + head``, so the
    head offset folds into the table once, outside the kernel. Prefill
    only: the decode grid reads the block table itself."""
    b, maxb = table.shape
    rows = (table[:, None, :] * kv_heads
            + jnp.arange(kv_heads, dtype=table.dtype)[None, :, None])
    return rows.reshape(b * kv_heads, maxb)


def paged_to_dense(cache: dict) -> dict:
    """Gather the dense per-slot view back out of a paged cache dict —
    ``pool[table]`` per plane. The jnp reference path (non-TPU backends)
    runs the UNCHANGED dense reference math over this view, so paged ref
    results are bitwise identical to dense by construction; it is also the
    bit-parity oracle the paged kernel is tested against."""
    nb, kvh, bs, _ = cache["k"].shape
    tbl = cache["table"]

    def g(leaf):  # (NB, KV, BS, X) -> (B, KV, MAXB*BS, X)
        x = jnp.swapaxes(leaf[tbl], 1, 2)  # (B, KV, MAXB, BS, X)
        return x.reshape(x.shape[0], kvh, -1, x.shape[-1])

    return {key: g(cache[key]) for key in ("k", "v", "k_scale", "v_scale")}


def _row_planes(cache: dict, rows: int, hd: int) -> tuple:
    """The cache's (k, k_scale, v, v_scale) planes as the kernels' row
    operands: codes (rows, T, HD), scales (rows, T), where a row is one
    (slot, kv head) of a dense cache or one (block, kv head) of the paged
    pool. Named ``kv_cache`` in the compiled program, where laying the
    planes out for the kernel may copy them."""
    with jax.named_scope("kv_cache"):
        return (cache["k"].reshape(rows, -1, hd),
                cache["k_scale"].reshape(rows, -1),
                cache["v"].reshape(rows, -1, hd),
                cache["v_scale"].reshape(rows, -1))


def _slot_planes(cache: dict) -> tuple:
    """The cache's (k, k_scale, v, v_scale) planes as the decode grid's
    operands: codes as stored, ``(B, KV, T, HD)`` dense or the pool's own
    ``(NB, KV, BS, HD)``; scales without their unit axis. Named
    ``kv_cache`` in the compiled program, like :func:`_row_planes`."""
    with jax.named_scope("kv_cache"):
        return (cache["k"], cache["k_scale"][..., 0],
                cache["v"], cache["v_scale"][..., 0])


def decode_attn_q8(
    q: jax.Array,            # (B, KV, G, 1, HD) UNROTATED queries
    cache: dict,             # {"k","v": int8 (B,KV,T,HD); "k_scale","v_scale": (B,KV,T,1)}
    k_tok: tuple[jax.Array, jax.Array],  # encoded current-token K: (codes (B,KV,1,HD), scale (B,KV,1,1))
    v_tok: tuple[jax.Array, jax.Array],  # encoded current-token V
    kv_len: jax.Array,       # (B,) int32 — valid cached positions (== pos)
    *,
    backend: str = "auto",
    interpret: bool | None = None,
    tt: int | None = None,
    early_exit: bool = True,
) -> jax.Array:
    """Single-token decode attention against the rotated-int8 cache.

    The current token rides OUTSIDE the cache (same discipline as the fp
    ``_sdpa_decode_token``): its K/V arrive already encoded through the same
    codec that will write them to the cache, so the self term sees exactly
    the values every later step will read back — greedy streams match the
    dequantize-then-attend reference bit-for-decision.

    The kernel runs the decode grid ``(B, NT)`` over the cache's own
    planes. A PAGED cache dict (extra ``"table"`` key; planes laid out
    (num_blocks, KV, block_size, HD|1) — serve/paged.py) adds the slot's
    block table as a second scalar-prefetch operand; the reference runs
    over the gathered :func:`paged_to_dense` view.

    Returns (B, KV, G, 1, HD) f32."""
    from repro.kernels.ops import auto_interpret  # local: avoid import cycle

    if interpret is None:
        interpret = auto_interpret()
    b, kv, g, _, hd = q.shape
    sm_scale = 1.0 / math.sqrt(hd)
    use_kernel = _use_kernel(backend, hd, interpret=interpret)
    q_rot = fwht(q[..., 0, :].astype(jnp.float32))  # (B, KV, G, HD)
    paged = "table" in cache

    if use_kernel:
        cache_len = (cache["table"].shape[1] * cache["k"].shape[2]
                     if paged else cache["k"].shape[2])
        if tt is None:
            # autotune-cache lookup keyed on (cache length, head_dim,
            # kv heads); deterministic defaults in interpret mode
            from repro.kernels.autotune import get_attn_tiles
            _, tt = get_attn_tiles(cache_len, hd, kv, interpret=interpret)
        acc, m, l = attn_decode_q8_pallas(
            q_rot, *_slot_planes(cache), kv_len, cache.get("table"),
            sm_scale=sm_scale, tt=tt, interpret=interpret,
            early_exit=early_exit,
            block_size=cache["k"].shape[2] if paged else None)
    else:
        dc = paged_to_dense(cache) if paged else cache
        acc, m, l = decode_attn_q8_ref(
            q_rot, dc["k"], dc["k_scale"], dc["v"],
            dc["v_scale"], kv_len, sm_scale=sm_scale)

    kc_tok, ks_tok = k_tok
    vc_tok, vs_tok = v_tok
    # self score through the SAME dequantize-free formula: (Hq).codes * scale
    s_self = jnp.einsum("bkgd,bkd->bkg", q_rot,
                        kc_tok[..., 0, :].astype(jnp.float32))[..., None]
    s_self = s_self * (ks_tok[..., 0, :].astype(jnp.float32)[:, :, None]
                       * sm_scale)
    # codes * scale recovers the ROTATED V row (H v); it stays rotated here
    v_self = (vc_tok.astype(jnp.float32)
              * vs_tok.astype(jnp.float32))  # (B, KV, 1, HD)
    out = _merge_self_token(acc, m, l, s_self, v_self)
    # The cache holds H v, so the weighted sum is sum_t w_t (H v_t)
    # = H (sum_t w_t v_t): the rotation commutes with the convex combination
    # and ONE inverse FWHT per step — outside the key-tile loop, outside the
    # kernel — undoes it for every cached token at once.
    out = fwht(out)
    return out[..., None, :]  # (B, KV, G, 1, HD)


def prefill_attn_q8(
    q: jax.Array,          # (B, KV, G, TQ, HD) UNROTATED queries
    cache: dict,           # {"k","v": int8 (B,KV,T,HD); "k_scale","v_scale": (B,KV,T,1)}
    kv_len: jax.Array,     # (B,) int32 — valid cached positions (incl. span)
    q_offset: jax.Array,   # (B,) int32 — absolute position of the span's query 0
    *,
    backend: str = "auto",
    interpret: bool | None = None,
    tq: int | None = None,
    tt: int | None = None,
    early_exit: bool = True,
) -> jax.Array:
    """Query-span (chunked-prefill) attention against the rotated-int8
    cache — the q-tile counterpart of :func:`decode_attn_q8`.

    Unlike decode, the in-flight span's K/V codes are already WRITTEN into
    the cache at ``q_offset..q_offset+TQ-1`` (``attention_apply`` encodes
    and writes the span before attending), so the causal mask
    ``q_offset + qpos >= kpos`` merges the span's self-attention block into
    the cache pass itself — no separate self term, and the cache buffer is
    never dequantized. Every query row sees at least its own position, so
    the online-softmax denominator is strictly positive.

    Returns (B, KV, G, TQ, HD) f32 (rotation already inverted: one inverse
    FWHT over the whole span, outside the kernel)."""
    from repro.kernels.ops import auto_interpret  # local: avoid import cycle

    if interpret is None:
        interpret = auto_interpret()
    b, kv, g, tq_total, hd = q.shape
    sm_scale = 1.0 / math.sqrt(hd)
    use_kernel = _use_kernel(backend, hd, interpret=interpret)
    q_rot = fwht(jnp.swapaxes(q, 2, 3).astype(jnp.float32))  # (B,KV,TQ,G,HD)
    paged = "table" in cache

    if use_kernel:
        cache_len = (cache["table"].shape[1] * cache["k"].shape[2]
                     if paged else cache["k"].shape[2])
        if tq is None or tt is None:
            from repro.kernels.autotune import SPEC_QWIDTH_MAX, get_attn_tiles
            # Narrow spans (speculative K+1 verify windows) have their own
            # tile family: a tq tuned for 512-wide prefill is useless when
            # the span is 5 rows. Wide spans fall through to the base key.
            qw = tq_total if tq_total <= SPEC_QWIDTH_MAX else None
            tuned_tq, tuned_tt = get_attn_tiles(
                cache_len, hd, kv, interpret=interpret, q_width=qw)
            tq = tq if tq else tuned_tq
            tt = tt if tt else tuned_tt
        r = b * kv
        if paged:
            nb, _, bs, _ = cache["k"].shape
            pool_rows = nb * kv
            acc, m, l = attn_q8_pallas(
                q_rot.reshape(r, tq_total, g, hd),
                *_row_planes(cache, pool_rows, hd),
                jnp.broadcast_to(kv_len[:, None], (b, kv)).reshape(r),
                jnp.broadcast_to(q_offset[:, None], (b, kv)).reshape(r),
                paged_row_table(cache["table"], kv),
                sm_scale=sm_scale, causal=True, tq=tq, tt=tt,
                interpret=interpret, early_exit=early_exit, block_size=bs)
        else:
            acc, m, l = attn_q8_pallas(
                q_rot.reshape(r, tq_total, g, hd), *_row_planes(cache, r, hd),
                jnp.broadcast_to(kv_len[:, None], (b, kv)).reshape(r),
                jnp.broadcast_to(q_offset[:, None], (b, kv)).reshape(r),
                sm_scale=sm_scale, causal=True, tq=tq, tt=tt,
                interpret=interpret, early_exit=early_exit)
        acc = jnp.swapaxes(acc.reshape(b, kv, tq_total, g, hd), 2, 3)
        l = jnp.swapaxes(l.reshape(b, kv, tq_total, g, 1), 2, 3)
    else:
        dc = paged_to_dense(cache) if paged else cache
        acc, m, l = prefill_attn_q8_ref(
            jnp.swapaxes(q_rot, 2, 3), dc["k"], dc["k_scale"],
            dc["v"], dc["v_scale"], kv_len, q_offset,
            sm_scale=sm_scale, causal=True, chunk=tq if tq else DEFAULT_TQ)
    out = acc / l
    # one inverse FWHT per query span — outside the tile loops, outside the
    # kernel — undoes the rotation for every cached token at once
    return fwht(out)
