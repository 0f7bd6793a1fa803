"""Jitted public wrappers around the Pallas kernels.

``qmatmul_kernel`` is the kernel-backed counterpart of
:func:`repro.core.qlinear.qmatmul`: it accepts the same QTensor and mode
vocabulary and dispatches twice:

**mode** (where the rotation lands):

  mode="weights"      -> fused kernel with in-kernel IFWHT (paper §5.2)
  mode="activations"  -> blocked-FWHT kernel on x, then the same fused
                         kernel with rotation disabled (DESIGN.md §2
                         dual-domain optimization)

**shape** (which kernel runs the contraction):

  M <= MATVEC_MAX_M   -> kernels/itq3_matvec.py — the decode-shaped
                         weight-streaming kernel (no M tiling); ``tm``
                         is ignored there.
  M >  MATVEC_MAX_M   -> kernels/itq3_matmul.py — the tiled kernel, with
                         the weight-tile expansion hoisted across M tiles
                         when it fits VMEM.

The two kernels share the weight-tile expansion helper and accumulate in
the same order, so the dispatch is bit-exact: callers never observe which
kernel ran.

``tm``/``tn`` default to None = resolve via :mod:`repro.kernels.autotune`
(cached per-device winners, deterministic defaults in interpret mode).
``interpret`` defaults to "auto": interpret=True unless running on real TPU
hardware (on a TPU it is never chosen implicitly). All wrappers handle reduction-dim padding and arbitrary leading
batch dims.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import formats as fmt_mod
from repro.core.act_quant import act_encode
from repro.core.qlinear import resolve_mode
from repro.core.quantize import QTensor, pad_last_dim
from repro.kernels import autotune as autotune_mod
from repro.kernels.fwht_kernel import fwht_pallas
from repro.kernels.itq3_matmul import (
    BLOCK, itq3_matmul_int8_pallas, itq3_matmul_pallas,
)
from repro.kernels.itq3_matvec import (
    MATVEC_MAX_M, itq3_matvec_int8_pallas, itq3_matvec_pallas,
)

__all__ = ["auto_interpret", "blocked_fwht_op", "qmatmul_kernel"]


def auto_interpret() -> bool:
    return jax.default_backend() != "tpu"


def blocked_fwht_op(x: jax.Array, block: int = 256, *, interpret: bool | None = None) -> jax.Array:
    """Blockwise FWHT along the last axis for any-rank ``x``."""
    if interpret is None:
        interpret = auto_interpret()
    lead = x.shape[:-1]
    k = x.shape[-1]
    x2 = x.reshape(-1, k)
    out = fwht_pallas(x2, block=block, interpret=interpret)
    return out.reshape(*lead, k)


def qmatmul_kernel(
    x: jax.Array,
    qt: QTensor,
    *,
    mode: str = "weights",
    act_quant: bool = False,
    tm: int | None = None,
    tn: int | None = None,
    interpret: bool | None = None,
    out_dtype=jnp.float32,
) -> jax.Array:
    """Kernel-backed ``x (..., K) @ W_hat (K, N) -> (..., N)`` for the
    ITQ3_S format family.

    ``act_quant=True`` runs the W3A8 integer path: rotate + int8-quantize
    the activations once (Pallas blocked FWHT + act_encode), then dispatch
    by shape to the int8 kernels — int8 x int8 -> int32 MACs, weight scale
    on the block partial, row scale at flush. ``mode`` is moot there (the
    rotation always lands on the activation side); tiles resolve through
    the autotune cache under the int8 key family.
    """
    if interpret is None:
        interpret = auto_interpret()
    m = qt.meta
    if not fmt_mod.get_format(m.fmt).supports_fused:
        raise ValueError(f"kernel path supports the ternary family, got {m.fmt}")

    mode = resolve_mode(x, m, mode)
    lead = x.shape[:-1]
    xp = pad_last_dim(x.reshape(-1, x.shape[-1]), m.block)

    dsign = qt.data.get("dsign")
    if act_quant:
        xq, xs = act_encode(
            xp, block=m.block, rotate=m.rotate, dsign=dsign,
            fwht_fn=lambda a, b: blocked_fwht_op(a, b, interpret=interpret))
        rows = xq.shape[0]
        if tm is None or tn is None:
            a_tm, a_tn = autotune_mod.get_tiles(
                rows, m.n, m.shape[0], m.fmt, interpret=interpret,
                act_quant=True)
            tm = a_tm if tm is None else tm
            tn = a_tn if tn is None else tn
        common = dict(fivelevel=m.fivelevel, sub_blocks=m.sub_blocks, tn=tn,
                      interpret=interpret, out_dtype=out_dtype)
        if rows <= MATVEC_MAX_M:
            out = itq3_matvec_int8_pallas(
                xq, xs, qt.data["plane2"], qt.data["plane1"],
                qt.data["scales"], qt.data["zps"], **common)
        else:
            out = itq3_matmul_int8_pallas(
                xq, xs, qt.data["plane2"], qt.data["plane1"],
                qt.data["scales"], qt.data["zps"], tm=tm, **common)
        return out.reshape(*lead, m.n)

    rotate = m.rotate
    if rotate:
        if mode == "activations":
            xb = xp.reshape(xp.shape[0], -1, m.block)
            if dsign is not None:
                xb = xb * dsign.astype(xb.dtype)
            xp = xb.reshape(xp.shape)
            xp = blocked_fwht_op(xp, block=m.block, interpret=interpret)
            rotate_weights = False
        elif mode == "weights":
            if dsign is not None:
                # w_hat = D H v  =>  y = (H v)^T (D x): pre-scale x by D.
                xb = xp.reshape(xp.shape[0], -1, m.block) * dsign.astype(xp.dtype)
                xp = xb.reshape(xp.shape)
            rotate_weights = True
        else:
            raise ValueError(f"unknown kernel mode {mode!r}")
    else:
        rotate_weights = False  # iq3_s baseline: contract codes directly

    rows = xp.shape[0]
    if tm is None or tn is None:
        # key on the LOGICAL K (m.shape[0]) — the same K the tuner records
        # under — not xp's block-padded width, which diverges whenever the
        # model dim isn't a multiple of 256 (e.g. smollm's d_model=576)
        a_tm, a_tn = autotune_mod.get_tiles(rows, m.n, m.shape[0], m.fmt,
                                            interpret=interpret)
        tm = a_tm if tm is None else tm
        tn = a_tn if tn is None else tn

    common = dict(rotate_weights=rotate_weights, fivelevel=m.fivelevel,
                  sub_blocks=m.sub_blocks, tn=tn, interpret=interpret,
                  out_dtype=out_dtype)
    if rows <= MATVEC_MAX_M:
        out = itq3_matvec_pallas(
            xp, qt.data["plane2"], qt.data["plane1"], qt.data["scales"],
            qt.data["zps"], **common)
    else:
        out = itq3_matmul_pallas(
            xp, qt.data["plane2"], qt.data["plane1"], qt.data["scales"],
            qt.data["zps"], tm=tm, **common)
    return out.reshape(*lead, m.n)
