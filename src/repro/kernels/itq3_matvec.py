"""Pallas TPU kernel: decode-shaped (small-M) fused ITQ3_S matvec.

Low-bit decode is weight-streaming-bound (TWLA, TernaryLLM): at M = a few
slots the matmul grid machinery of ``itq3_matmul_pallas`` — M tiling, M
padding, an (TM, 256) x-tile stream per grid cell — is pure overhead, and
what matters is draining the packed planes from HBM at full bandwidth.

This kernel is the memory-side specialization for M <= ``MATVEC_MAX_M``:

* **No M grid.** The grid is (NB, KB) — output strips outermost,
  reduction innermost — so the packed planes of each strip stream exactly
  once; there is no M loop to re-stream them for.
* **No x-tile machinery.** x rides along as one thin (M, 256) block per
  reduction step; the whole activation row set stays VREG-resident.
* **Lane-dense planes.** The packed planes arrive in the K-major
  ``(KB, 64|32, N)`` layout of :func:`~repro.kernels.itq3_matmul.kernel_planes`,
  one ``(64, TN)`` / ``(32, TN)`` block per grid step with the output
  features on the lanes.
* **(M, TN) register-tile accumulator.** One f32 scratch tile accumulates
  across KB and flushes once per strip.

The weight-tile expansion is byte-for-byte the tiled kernel's
``dequant_rotate_tile`` (same chunk order, same MXU pass, K ascending),
so results are **bit-identical** to ``itq3_matmul_pallas`` for every format
in the ternary family — ``qmatmul`` dispatches between them purely by shape
(see kernels/ops.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.fwht import hadamard_matrix
from repro.kernels.itq3_matmul import (
    BLOCK, _accumulate_int8, _float_tile, _int8_tile, _plane_specs,
    kernel_planes, lane_tile,
)

__all__ = ["itq3_matvec_pallas", "itq3_matvec_int8_pallas", "MATVEC_MAX_M"]

MATVEC_MAX_M = 16  # decode / small-batch regime; above this, tile the M dim


def _itq3_matvec_kernel(
    h_ref,    # (256, 256) f32 — Hadamard (only read when rotate_weights)
    x_ref,    # (M, 256) — reduction block k of the activations
    p2_ref,   # (1, 64, TN) uint8
    p1_ref,   # (1, 32, TN) uint8
    sc_ref,   # (1, 1|SUB, TN) f32
    zp_ref,   # (1, 1, TN) f32
    o_ref,    # (M, TN)
    acc_ref,  # scratch (M, TN) f32
    *,
    rotate_weights: bool,
    fivelevel: bool,
    sub_blocks: int,
    kb: int,
):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = _float_tile(h_ref, p2_ref, p1_ref, sc_ref, zp_ref,
                    rotate_weights=rotate_weights, fivelevel=fivelevel,
                    sub_blocks=sub_blocks)
    x = x_ref[...].astype(jnp.float32)
    acc_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(k == kb - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("rotate_weights", "fivelevel", "sub_blocks", "tn",
                     "interpret", "out_dtype"),
)
def itq3_matvec_pallas(
    x: jax.Array,        # (M, K_pad), M <= MATVEC_MAX_M
    plane2: jax.Array,   # (N, KB, 64) uint8
    plane1: jax.Array,   # (N, KB, 32) uint8
    scales: jax.Array,   # (N, KB) f16/f32  |  (N, KB, SUB)
    zps: jax.Array,      # (N, KB) f16/f32
    *,
    rotate_weights: bool = True,
    fivelevel: bool = False,
    sub_blocks: int = 0,
    tn: int = 256,
    interpret: bool = True,
    out_dtype=jnp.float32,
) -> jax.Array:
    """Decode-shaped fused matvec: returns ``x @ W_hat`` of shape (M, N)."""
    m, kpad = x.shape
    n, kb = plane2.shape[0], plane2.shape[1]
    if m > MATVEC_MAX_M:
        raise ValueError(f"matvec kernel is for M <= {MATVEC_MAX_M}, got {m}")
    if kpad != kb * BLOCK:
        raise ValueError(f"x K dim {kpad} != KB*256 = {kb * BLOCK}")

    tn = lane_tile(tn, n, interpret=interpret)
    p2, p1, sc, zp = kernel_planes(tn, plane2, plane1, scales, zps)
    np_ = p2.shape[-1]
    h = hadamard_matrix(BLOCK, dtype=jnp.float32)

    kernel = functools.partial(
        _itq3_matvec_kernel,
        rotate_weights=rotate_weights,
        fivelevel=fivelevel,
        sub_blocks=sub_blocks,
        kb=kb,
    )
    out = pl.pallas_call(
        kernel,
        grid=(np_ // tn, kb),
        in_specs=[
            pl.BlockSpec((BLOCK, BLOCK), lambda j, k: (0, 0)),  # H resident
            pl.BlockSpec((m, BLOCK), lambda j, k: (0, k)),
            *_plane_specs(tn, sc.shape[1], lambda j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((m, tn), lambda j, k: (0, j)),
        out_shape=jax.ShapeDtypeStruct((m, np_), out_dtype),
        scratch_shapes=[pltpu.VMEM((m, tn), jnp.float32)],
        interpret=interpret,
    )(h, x, p2, p1, sc, zp)
    return out[:, :n]


def _itq3_matvec_int8_kernel(
    x_ref,    # (M, 256) int8 — reduction block k of the activation codes
    xs_ref,   # (M, 1) f32 — per-row activation scale
    p2_ref,   # (1, 64, TN) uint8
    p1_ref,   # (1, 32, TN) uint8
    sc_ref,   # (1, 1|SUB, TN) f32
    zp_ref,   # (1, 1, TN) f32 (integer-valued)
    o_ref,    # (M, TN)
    acc_ref,  # scratch (M, TN) f32
    *,
    fivelevel: bool,
    sub_blocks: int,
    kb: int,
):
    """W3A8 decode matvec: same (NB, KB) streaming grid, but the per-strip
    work drops to unpack + integer zero-point fold + one int8 dot — no
    Hadamard operand, no IFWHT MXU passes. Decode is weight-streaming
    bound, so the win is dual: fewer VPU/MXU ops per tile AND 4x fewer
    activation bytes re-read per strip."""
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = _int8_tile(p2_ref, p1_ref, zp_ref, fivelevel=fivelevel,
                   sub_blocks=sub_blocks)
    _accumulate_int8(acc_ref, x_ref[...], w, sc_ref[0], sub_blocks=sub_blocks)

    @pl.when(k == kb - 1)
    def _flush():
        o_ref[...] = (acc_ref[...] * xs_ref[...]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("fivelevel", "sub_blocks", "tn", "interpret",
                     "out_dtype"),
)
def itq3_matvec_int8_pallas(
    xq: jax.Array,       # (M, K_pad) int8, M <= MATVEC_MAX_M
    xscale: jax.Array,   # (M, 1) f32
    plane2: jax.Array,   # (N, KB, 64) uint8
    plane1: jax.Array,   # (N, KB, 32) uint8
    scales: jax.Array,   # (N, KB) f16/f32  |  (N, KB, SUB)
    zps: jax.Array,      # (N, KB) f16/f32 (integer-valued)
    *,
    fivelevel: bool = False,
    sub_blocks: int = 0,
    tn: int = 256,
    interpret: bool = True,
    out_dtype=jnp.float32,
) -> jax.Array:
    """Decode-shaped W3A8 matvec (int8 codes in, (M, N) out); the integer
    counterpart of :func:`itq3_matvec_pallas` with the same grid and
    accumulation order as ``itq3_matmul_int8_pallas`` (bit-identical
    dispatch, see kernels/ops.py)."""
    m, kpad = xq.shape
    n, kb = plane2.shape[0], plane2.shape[1]
    if m > MATVEC_MAX_M:
        raise ValueError(f"matvec kernel is for M <= {MATVEC_MAX_M}, got {m}")
    if xq.dtype != jnp.int8:
        raise ValueError(f"int8 kernel expects int8 codes, got {xq.dtype}")
    if kpad != kb * BLOCK:
        raise ValueError(f"xq K dim {kpad} != KB*256 = {kb * BLOCK}")

    tn = lane_tile(tn, n, interpret=interpret)
    p2, p1, sc, zp = kernel_planes(tn, plane2, plane1, scales, zps)
    np_ = p2.shape[-1]
    xscale = xscale.astype(jnp.float32)

    kernel = functools.partial(
        _itq3_matvec_int8_kernel,
        fivelevel=fivelevel,
        sub_blocks=sub_blocks,
        kb=kb,
    )
    out = pl.pallas_call(
        kernel,
        grid=(np_ // tn, kb),
        in_specs=[
            pl.BlockSpec((m, BLOCK), lambda j, k: (0, k)),
            pl.BlockSpec((m, 1), lambda j, k: (0, 0)),
            *_plane_specs(tn, sc.shape[1], lambda j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((m, tn), lambda j, k: (0, j)),
        out_shape=jax.ShapeDtypeStruct((m, np_), out_dtype),
        scratch_shapes=[pltpu.VMEM((m, tn), jnp.float32)],
        interpret=interpret,
    )(xq, xscale, p2, p1, sc, zp)
    return out[:, :n]
