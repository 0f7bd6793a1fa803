"""Pallas TPU kernel: fused ITQ3_S dequantize + rotate + matmul.

The TPU analogue of the paper's ``load_tiles_itq3_s`` + MMQ pipeline (§5.2):
packed 3-bit weights stream from HBM at 3.125 bits/weight and are expanded
to a full-precision weight tile *inside VMEM*, never materialized in HBM.

Per weight tile (output strip j, reduction block k) the expansion is:

  1. **Load** the packed planes for TN output features of block k in the
     kernels' **K-major, lane-dense** layout (:func:`kernel_planes`):
     ``plane2`` (64, TN) uint8 and ``plane1`` (32, TN) uint8 — 96 bytes per
     256 weights, the paper's exact storage budget — with the output
     features on the 128-wide lanes, so every block meets the TPU's (8, 128)
     tiling rule.
  2. **Unpack** with lane-parallel shifts/masks. The planar-interleaved
     layout (packing.py) yields four contiguous 64-row chunks per uniform
     shift — the VREG version of the paper's DP4A nibble interleave.
  3. **Dequantize** on the grid: ``w = d_k * (q - z_k)`` (ternary) or the
     5-level escape decode (itq3_x), or sub-block scales (itq3_s_sub).
  4. **Rotate** (``rotate_weights=True``, paper-faithful): apply the inverse
     FWHT as one (256, 256) @ (256, TN) MXU matmul against H_256 —
     replacing the CUDA 8-stage shared-memory butterfly with a systolic
     pass (DESIGN.md §2).

The expanded tile is the TRANSPOSED weight block ``W_hat[k-block, j-strip]``
of shape (256, TN), so the contraction is the MXU's native
``(TM, 256) @ (256, TN)``.

That expansion is the expensive part of the kernel, and it depends only on
(j, k) — never on the M tile. Two grid schedules share it:

* **flat** (grid ``(MB, NB, KB)``, K innermost): the tile is expanded per
  (i, j, k) cell — no extra scratch, but the same weight tile is re-decoded
  and re-rotated for every M tile. Used when M fits one tile (decode) or
  when the hoist scratch would not fit VMEM.
* **hoisted** (grid ``(NB, MB, KB)``, K innermost, M middle): a
  (KB, 256, TN) VMEM scratch caches the expanded strip for the current j;
  it is filled once at i == 0 and *reused* by every subsequent M tile —
  prefill-width batches stop paying MB redundant unpack+dequant+IFWHT
  passes per weight strip. Requires the grid to execute sequentially
  (TPU grids and interpret mode both do).

Both schedules accumulate ``acc += x_tile @ w_tile`` in (TM, TN) f32
scratch with K innermost and flush the output tile once at k == KB-1, and
both consume the expanded tile through one dot per k-block — so they are
bit-identical to each other (and to kernels/itq3_matvec.py, which uses the
same ``dequant_rotate_tile`` helper in the same order).

With ``rotate_weights=False`` the same pipeline skips step 4 — used both
for the IQ3_S no-rotation baseline and for the beyond-paper
*activation-domain* path (ops.py rotates x blockwise first; the zero-point
then couples in the rotated domain with no extra term since z is folded
into the dequantized tile).

**W3A8 integer variants** (``itq3_matmul_int8_pallas``): when the
activations themselves are quantized into the rotation domain
(core/act_quant.py), steps 3-4 disappear entirely — the tile expansion is
unpack + integer zero-point fold (``decode_wint_tile``, exact in int8
because z is integer-valued), the MAC is int8 x int8 -> int32
(``preferred_element_type=jnp.int32``, the MXU's DP4A analogue), the
per-block weight scale ``d`` lands on the int32 partial, and the per-row
activation scale is applied once at flush. Same flat/hoisted schedules;
the hoisted int8 strip costs 1/4 of the float scratch bytes.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.fwht import hadamard_matrix

__all__ = ["itq3_matmul_pallas", "itq3_matmul_int8_pallas",
           "dequant_rotate_tile", "decode_wint_tile", "kernel_planes",
           "lane_tile", "BLOCK"]

BLOCK = 256
NCHUNK = 4  # 256 = 4 chunks of 64 (one per 2-bit position in a plane2 byte)
CHUNK = BLOCK // NCHUNK  # 64
LANE = 128  # TPU lane width: a partial output strip spans whole lanes

# Hoisting caches the expanded (KB, 256, TN) weight strip in VMEM scratch;
# don't hoist past this budget. v5e's default scoped VMEM limit is 16 MiB
# and the pipelined x/plane/output blocks need the rest. The chip's
# compiler is the check (memory_analysis() does not report scoped VMEM):
# tests/test_tpu_compile.py compiles a strip at this budget for v5e, and a
# 16 MiB strip is refused.
HOIST_VMEM_BUDGET = int(os.environ.get("REPRO_HOIST_VMEM_BUDGET", 8 * 2**20))


def lane_tile(tn: int, n: int, *, interpret: bool) -> int:
    """Clamp an output-strip width to N. On a TPU a partial strip must fill
    whole 128-wide lanes (the (8, 128) block rule); interpret mode takes any
    width so CPU tests can sweep tiny shapes."""
    tn = max(1, min(tn, n))
    if not interpret and tn < n and tn % LANE:
        raise ValueError(
            f"tn={tn} must be a multiple of {LANE} (or >= N={n}) on TPU")
    return tn


@jax.named_scope("itq3_planes")
def kernel_planes(tn: int, plane2, plane1, scales, zps):
    """Stored output-major operands -> the kernels' K-major lane-dense
    layout, with N zero-padded to a multiple of ``tn``:

      plane2 (N, KB, 64) u8        -> (KB, 64, Np) u8
      plane1 (N, KB, 32) u8        -> (KB, 32, Np) u8
      scales (N, KB[, SUB]) f16    -> (KB, 1|SUB, Np) f32
      zps    (N, KB) f16           -> (KB, 1, Np) f32

    Every kernel block is then ``(1, rows, TN)`` with ``rows`` the whole
    second-minor dim and TN on the lanes, which the TPU compiler accepts
    for any TN that is a lane multiple (or the whole padded N). The
    transpose runs in XLA on every call, outside the kernel, under the
    name ``itq3_planes`` in the compiled program."""
    if scales.ndim == 2:
        scales = scales[..., None]
    ops = (plane2, plane1, scales.astype(jnp.float32),
           zps.astype(jnp.float32)[..., None])
    pad_n = (-plane2.shape[0]) % tn
    out = []
    for a in ops:
        a = jnp.transpose(a, (1, 2, 0))
        if pad_n:
            a = jnp.pad(a, ((0, 0), (0, 0), (0, pad_n)))
        out.append(a)
    return tuple(out)


def _plane_specs(tn: int, sc_rows: int, idx):
    """BlockSpecs of the four K-major operands for grid index map ``idx``
    returning (k, j)."""
    kj = lambda *g: (idx(*g)[0], 0, idx(*g)[1])
    return [pl.BlockSpec((1, CHUNK, tn), kj),
            pl.BlockSpec((1, BLOCK // 8, tn), kj),
            pl.BlockSpec((1, sc_rows, tn), kj),
            pl.BlockSpec((1, 1, tn), kj)]


def _decode_chunk_int(p2, p1, c: int, *, fivelevel: bool):
    """Chunk c (elements c*64..c*64+63, one per row) integer grid values
    from the planes, as int32 — shared by the float expansion (which
    casts) and the W3A8 integer kernels.

    p2: (64, TN) uint8, p1: (32, TN) uint8. Planar-interleaved layout:
    plane2 byte i, bit-pair c  <-> element c*64 + i;
    plane1 byte i, bit b       <-> element b*32 + i.
    """
    payload = ((p2.astype(jnp.int32) >> (2 * c)) & 0x3) - 1  # {-1,0,1}
    if not fivelevel:
        return payload
    p1 = p1.astype(jnp.int32)
    sel_lo = (p1 >> (2 * c)) & 0x1        # elements c*64 + [0..31]
    sel_hi = (p1 >> (2 * c + 1)) & 0x1    # elements c*64 + [32..63]
    sel = jnp.concatenate([sel_lo, sel_hi], axis=0)
    return payload * (1 + sel)


def dequant_rotate_tile(h_ref, p2, p1, sc, zp, *, rotate_weights: bool,
                        fivelevel: bool, sub_blocks: int) -> jax.Array:
    """Expand one packed weight tile to its (256, TN) f32 dequantized (and
    optionally IFWHT-rotated) form — steps 2-4 of the pipeline above.
    ``sc`` is (1|SUB, TN), ``zp`` (1, TN).

    Shared by every kernel variant (flat/hoisted/matvec) so they stay
    bit-identical: same chunk order, same per-chunk ops, same MXU pass.
    """
    chunks = []
    for c in range(NCHUNK):
        q = _decode_chunk_int(p2, p1, c, fivelevel=fivelevel)
        q = q.astype(jnp.float32)  # (64, TN)
        if sub_blocks:
            # element e = c*64 + i lives in sub-block e // (256//SUB)
            per = BLOCK // sub_blocks
            lo = (c * CHUNK) // per
            d_c = jnp.concatenate(
                [jnp.broadcast_to(sc[lo + s:lo + s + 1], (per, q.shape[1]))
                 for s in range(CHUNK // per)], axis=0)
            chunks.append(d_c * q)
        else:
            chunks.append(sc * (q - zp))
    w = jnp.concatenate(chunks, axis=0)  # (256, TN)
    if not rotate_weights:
        return w
    # IFWHT via MXU: H is symmetric, so the rotated block is H @ w
    return jnp.dot(h_ref[...], w, preferred_element_type=jnp.float32)


def decode_wint_tile(p2, p1, zp, *, fivelevel: bool,
                     sub_blocks: int) -> jax.Array:
    """Expand one packed weight tile to its (256, TN) **int8** integer form
    ``wint = q - z`` — the W3A8 counterpart of :func:`dequant_rotate_tile`.

    No rotation and no float math: the zero-point is integer-valued by
    construction (sub-block formats store z = 0), so the tile is exact in
    int8 ({-2..2} ternary / {-4..4} fivelevel) and feeds the MXU as an
    int8 x int8 -> int32 contraction operand. Shared by the flat, hoisted
    and matvec int8 kernels so they stay bit-identical.
    """
    w = jnp.concatenate(
        [_decode_chunk_int(p2, p1, c, fivelevel=fivelevel)
         for c in range(NCHUNK)], axis=0)  # (256, TN) int32
    if not sub_blocks:
        w = w - zp.astype(jnp.int32)  # (1, TN) integer-valued
    return w.astype(jnp.int8)


def _accumulate_int8(acc_ref, xq, w, sc, *, sub_blocks: int):
    """acc += d_k * (xq . wint) with int32 MACs; the per-block weight
    scale lands on the int32 partial (it varies per (n, k) so it cannot be
    deferred to the flush like the activation row scale)."""
    if sub_blocks:
        per = BLOCK // sub_blocks
        for s in range(sub_blocks):
            p = jnp.dot(xq[:, s * per:(s + 1) * per],
                        w[s * per:(s + 1) * per],
                        preferred_element_type=jnp.int32)
            acc_ref[...] += p.astype(jnp.float32) * sc[s:s + 1]
    else:
        p = jnp.dot(xq, w, preferred_element_type=jnp.int32)
        acc_ref[...] += p.astype(jnp.float32) * sc


def _accumulate(acc_ref, x_ref, w):
    x = x_ref[...].astype(jnp.float32)
    acc_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)


def _float_tile(h_ref, p2_ref, p1_ref, sc_ref, zp_ref, **kw):
    return dequant_rotate_tile(h_ref, p2_ref[0], p1_ref[0], sc_ref[0],
                               zp_ref[0], **kw)


def _int8_tile(p2_ref, p1_ref, zp_ref, **kw):
    return decode_wint_tile(p2_ref[0], p1_ref[0], zp_ref[0], **kw)


def _itq3_matmul_int8_kernel(
    x_ref,    # (TM, 256) int8 — rotation-domain activation codes
    xs_ref,   # (TM, 1) f32 — per-row activation scale
    p2_ref,   # (1, 64, TN) uint8
    p1_ref,   # (1, 32, TN) uint8
    sc_ref,   # (1, 1|SUB, TN) f32
    zp_ref,   # (1, 1, TN) f32 (integer-valued)
    o_ref,    # (TM, TN)
    acc_ref,  # scratch (TM, TN) f32
    *,
    fivelevel: bool,
    sub_blocks: int,
    kb: int,
):
    """Flat int8 schedule: grid (MB, NB, KB). No Hadamard operand and no
    in-kernel rotation — the FWHT already happened once on the activation
    side (act_encode), so the per-tile work is unpack + one int dot."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = _int8_tile(p2_ref, p1_ref, zp_ref, fivelevel=fivelevel,
                   sub_blocks=sub_blocks)
    _accumulate_int8(acc_ref, x_ref[...], w, sc_ref[0], sub_blocks=sub_blocks)

    @pl.when(k == kb - 1)
    def _flush():
        o_ref[...] = (acc_ref[...] * xs_ref[...]).astype(o_ref.dtype)


def _itq3_matmul_int8_hoisted_kernel(
    x_ref, xs_ref, p2_ref, p1_ref, sc_ref, zp_ref, o_ref,
    acc_ref,  # scratch (TM, TN) f32
    w_ref,    # scratch (KB, 256, TN) int8 — expanded strip for current j
    *,
    fivelevel: bool,
    sub_blocks: int,
    kb: int,
):
    """Hoisted int8 schedule: grid (NB, MB, KB); the int8 strip costs 1/4
    of the float path's scratch bytes, so it fits VMEM at 4x the KB*TN."""
    i = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(i == 0)
    def _expand():
        w_ref[k] = _int8_tile(p2_ref, p1_ref, zp_ref, fivelevel=fivelevel,
                              sub_blocks=sub_blocks)

    _accumulate_int8(acc_ref, x_ref[...], w_ref[k], sc_ref[0],
                     sub_blocks=sub_blocks)

    @pl.when(k == kb - 1)
    def _flush():
        o_ref[...] = (acc_ref[...] * xs_ref[...]).astype(o_ref.dtype)


def _itq3_matmul_kernel(
    h_ref,    # (256, 256) f32 — Hadamard (only read when rotate_weights)
    x_ref,    # (TM, 256)
    p2_ref,   # (1, 64, TN) uint8
    p1_ref,   # (1, 32, TN) uint8
    sc_ref,   # (1, 1|SUB, TN) f32
    zp_ref,   # (1, 1, TN) f32
    o_ref,    # (TM, TN)
    acc_ref,  # scratch (TM, TN) f32
    *,
    rotate_weights: bool,
    fivelevel: bool,
    sub_blocks: int,
    kb: int,
):
    """Flat schedule: grid (MB, NB, KB), expand the weight tile per cell."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = _float_tile(h_ref, p2_ref, p1_ref, sc_ref, zp_ref,
                    rotate_weights=rotate_weights, fivelevel=fivelevel,
                    sub_blocks=sub_blocks)
    _accumulate(acc_ref, x_ref, w)

    @pl.when(k == kb - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _itq3_matmul_hoisted_kernel(
    h_ref, x_ref, p2_ref, p1_ref, sc_ref, zp_ref, o_ref,
    acc_ref,  # scratch (TM, TN) f32
    w_ref,    # scratch (KB, 256, TN) f32 — expanded strip for current j
    *,
    rotate_weights: bool,
    fivelevel: bool,
    sub_blocks: int,
    kb: int,
):
    """Hoisted schedule: grid (NB, MB, KB). The expanded weight strip for
    output tile j is computed once (first M tile) and served from VMEM
    scratch for every later M tile."""
    i = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(i == 0)
    def _expand():
        w_ref[k] = _float_tile(h_ref, p2_ref, p1_ref, sc_ref, zp_ref,
                               rotate_weights=rotate_weights,
                               fivelevel=fivelevel, sub_blocks=sub_blocks)

    _accumulate(acc_ref, x_ref, w_ref[k])

    @pl.when(k == kb - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _grid(hoist: bool, mb: int, nb: int, kb: int):
    """Grid and the (i, j, k) view of a grid index for either schedule."""
    if hoist:
        # grid (j, i, k): i (M tiles) revisits j's weight strip; the strip
        # is expanded once at i == 0 into scratch and reused after.
        return (nb, mb, kb), lambda j, i, k: (i, j, k)
    return (mb, nb, kb), lambda i, j, k: (i, j, k)


@functools.partial(
    jax.jit,
    static_argnames=(
        "rotate_weights", "fivelevel", "sub_blocks", "tm", "tn", "interpret",
        "out_dtype", "hoist",
    ),
)
def itq3_matmul_pallas(
    x: jax.Array,        # (M, K_pad) — K_pad = KB * 256
    plane2: jax.Array,   # (N, KB, 64) uint8
    plane1: jax.Array,   # (N, KB, 32) uint8
    scales: jax.Array,   # (N, KB) f16/f32  |  (N, KB, SUB)
    zps: jax.Array,      # (N, KB) f16/f32
    *,
    rotate_weights: bool = True,
    fivelevel: bool = False,
    sub_blocks: int = 0,
    tm: int = 256,
    tn: int = 256,
    interpret: bool = True,
    out_dtype=jnp.float32,
    hoist: bool | None = None,
) -> jax.Array:
    """Fused ITQ3_S matmul: returns ``x @ W_hat`` of shape (M, N).

    ``hoist=None`` auto-selects the hoisted schedule when there is more than
    one M tile and the expanded weight strip fits the VMEM budget.
    """
    m, kpad = x.shape
    n, kb = plane2.shape[0], plane2.shape[1]
    if kpad != kb * BLOCK:
        raise ValueError(f"x K dim {kpad} != KB*256 = {kb * BLOCK}")

    tm = max(1, min(tm, m))
    tn = lane_tile(tn, n, interpret=interpret)
    pad_m = (-m) % tm
    if pad_m:
        x = jnp.pad(x, ((0, pad_m), (0, 0)))
    p2, p1, sc, zp = kernel_planes(tn, plane2, plane1, scales, zps)
    mp, np_ = x.shape[0], p2.shape[-1]
    mb = mp // tm
    h = hadamard_matrix(BLOCK, dtype=jnp.float32)

    if hoist is None:
        hoist = mb > 1 and kb * tn * BLOCK * 4 <= HOIST_VMEM_BUDGET
    grid, ijk = _grid(hoist, mb, np_ // tn, kb)
    kernel_kw = dict(rotate_weights=rotate_weights, fivelevel=fivelevel,
                     sub_blocks=sub_blocks, kb=kb)
    scratch = [pltpu.VMEM((tm, tn), jnp.float32)]
    if hoist:
        kernel = functools.partial(_itq3_matmul_hoisted_kernel, **kernel_kw)
        scratch.append(pltpu.VMEM((kb, BLOCK, tn), jnp.float32))
    else:
        kernel = functools.partial(_itq3_matmul_kernel, **kernel_kw)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((BLOCK, BLOCK), lambda *_: (0, 0)),  # H resident
            pl.BlockSpec((tm, BLOCK), lambda *g: ijk(*g)[::2]),  # (i, k)
            *_plane_specs(tn, sc.shape[1], lambda *g: ijk(*g)[:0:-1]),
        ],
        out_specs=pl.BlockSpec((tm, tn), lambda *g: ijk(*g)[:2]),
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
        scratch_shapes=scratch,
        interpret=interpret,
    )(h, x, p2, p1, sc, zp)
    return out[:m, :n]


@functools.partial(
    jax.jit,
    static_argnames=(
        "fivelevel", "sub_blocks", "tm", "tn", "interpret", "out_dtype",
        "hoist",
    ),
)
def itq3_matmul_int8_pallas(
    xq: jax.Array,       # (M, K_pad) int8 — act_encode codes, K_pad = KB*256
    xscale: jax.Array,   # (M, 1) f32 — per-row activation scale
    plane2: jax.Array,   # (N, KB, 64) uint8
    plane1: jax.Array,   # (N, KB, 32) uint8
    scales: jax.Array,   # (N, KB) f16/f32  |  (N, KB, SUB)
    zps: jax.Array,      # (N, KB) f16/f32 (integer-valued)
    *,
    fivelevel: bool = False,
    sub_blocks: int = 0,
    tm: int = 256,
    tn: int = 256,
    interpret: bool = True,
    out_dtype=jnp.float32,
    hoist: bool | None = None,
) -> jax.Array:
    """W3A8 fused matmul: ``(M, N) = xscale * ((xq @ wint) scaled by d)``
    with int8 x int8 -> int32 MACs. The activations arrive already rotated
    and quantized (kernels/ops.py / core/act_quant.py); there is no
    Hadamard operand and no in-kernel rotation. ``hoist=None`` auto-selects
    the hoisted schedule under the same VMEM budget as the float kernel —
    the int8 strip is 4x smaller, so it hoists at 4x the KB*TN.
    """
    m, kpad = xq.shape
    n, kb = plane2.shape[0], plane2.shape[1]
    if xq.dtype != jnp.int8:
        raise ValueError(f"int8 kernel expects int8 codes, got {xq.dtype}")
    if kpad != kb * BLOCK:
        raise ValueError(f"xq K dim {kpad} != KB*256 = {kb * BLOCK}")

    tm = max(1, min(tm, m))
    tn = lane_tile(tn, n, interpret=interpret)
    pad_m = (-m) % tm
    if pad_m:
        xq = jnp.pad(xq, ((0, pad_m), (0, 0)))
        xscale = jnp.pad(xscale, ((0, pad_m), (0, 0)))
    p2, p1, sc, zp = kernel_planes(tn, plane2, plane1, scales, zps)
    mp, np_ = xq.shape[0], p2.shape[-1]
    mb = mp // tm
    xscale = xscale.astype(jnp.float32)

    if hoist is None:
        hoist = mb > 1 and kb * tn * BLOCK <= HOIST_VMEM_BUDGET
    grid, ijk = _grid(hoist, mb, np_ // tn, kb)
    kernel_kw = dict(fivelevel=fivelevel, sub_blocks=sub_blocks, kb=kb)
    scratch = [pltpu.VMEM((tm, tn), jnp.float32)]
    if hoist:
        kernel = functools.partial(_itq3_matmul_int8_hoisted_kernel,
                                   **kernel_kw)
        scratch.append(pltpu.VMEM((kb, BLOCK, tn), jnp.int8))
    else:
        kernel = functools.partial(_itq3_matmul_int8_kernel, **kernel_kw)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tm, BLOCK), lambda *g: ijk(*g)[::2]),  # (i, k)
            pl.BlockSpec((tm, 1), lambda *g: (ijk(*g)[0], 0)),
            *_plane_specs(tn, sc.shape[1], lambda *g: ijk(*g)[:0:-1]),
        ],
        out_specs=pl.BlockSpec((tm, tn), lambda *g: ijk(*g)[:2]),
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
        scratch_shapes=scratch,
        interpret=interpret,
    )(xq, xscale, p2, p1, sc, zp)
    return out[:m, :n]
