"""Quantized linear forward — the online half of ITQ3_S.

:func:`qmatmul` is the ONE entrypoint for ``y = x @ W_hat`` on a QTensor.
It owns two orthogonal dispatch decisions:

**mode** — where the rotation FLOPs land (see
:meth:`repro.core.formats.TernaryFormat.contract` for the math):

  * ``"dequant"``      — materialize W_hat then matmul (oracle / baseline).
  * ``"weights"``      — paper-faithful fused path: unpack -> dequantize ->
    inverse-FWHT the *weight* tiles, then matmul.
  * ``"activations"``  — dual-domain path: rotate each activation block once
    and contract against the raw ternary codes.
  * ``"auto"``         — side-adaptive: H is involutory, so the transform can
    land on either operand — put it on the SMALLER side. Decode (few rows)
    rotates activations; prefill/training-width batches rotate weight tiles.

**backend** — which implementation runs the chosen contraction:

  * ``"ref"``     — the pure-JAX expression (``Format.contract``); CPU/GPU
    portable, and the oracle the kernels are tested against.
  * ``"pallas"``  — the fused Pallas TPU kernel (kernels/ops.py); formats
    without a fused kernel (fp16/bf16/q8_0/q4_0) and ``mode="dequant"`` fall
    back to ``"ref"`` so mixed-precision trees serve through one code path.
  * ``"auto"``    — ``"pallas"`` on real TPU hardware for fused-capable
    formats, ``"ref"`` everywhere else.

All modes are bit-identical in exact arithmetic (tested); ref and pallas
agree within kernel tolerance for every registered ternary format.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import formats as fmt_mod
from repro.core.quantize import QTensor

__all__ = ["qmatmul", "resolve_mode", "resolve_backend", "QLINEAR_MODES",
           "QMATMUL_BACKENDS"]

QLINEAR_MODES = ("dequant", "weights", "activations", "auto")
QMATMUL_BACKENDS = ("auto", "ref", "pallas")


def resolve_mode(x: jax.Array, m, mode: str) -> str:
    """Resolve mode="auto" side-adaptively: rotate the smaller operand."""
    if mode != "auto":
        return mode
    rows = 1
    for d in x.shape[:-1]:
        rows *= d
    return "activations" if rows <= m.n else "weights"


def resolve_backend(backend: str, fmt: str, mode: str) -> str:
    """The implementation (``"pallas"`` or ``"ref"``) a ``fmt`` weight runs
    under ``backend``/``mode`` on this process's default device."""
    if backend not in QMATMUL_BACKENDS:
        raise ValueError(f"backend {backend!r} not in {QMATMUL_BACKENDS}")
    if not fmt_mod.get_format(fmt).supports_fused or mode == "dequant":
        return "ref"
    if backend == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "ref"
    return backend


def qmatmul(
    x: jax.Array,
    qt: QTensor,
    *,
    mode: str = "activations",
    backend: str = "auto",
    compute_dtype=jnp.bfloat16,
    tm: int | None = None,
    tn: int | None = None,
    interpret: bool | None = None,
    act_quant: bool = False,
) -> jax.Array:
    """``x (..., K) @ W_hat (K, N) -> (..., N)`` for a quantized weight.

    ``tm``/``tn``/``interpret`` only affect the Pallas backend (tile sizes
    and interpret-mode override for CPU testing). ``tm=None``/``tn=None``
    resolve through :mod:`repro.kernels.autotune`: the cached per-device
    winner for this shape if one exists, deterministic defaults otherwise
    (always, in interpret mode). The kernel wrapper additionally dispatches
    small-M calls to the decode-shaped matvec kernel by shape.

    ``act_quant=True`` selects the W3A8 integer compute path: activations
    are rotated + int8-quantized (core/act_quant.py) and contracted against
    the int8 integer weights with int32 accumulation — no per-tile weight
    rotation at all. It is honoured only where it makes sense: fused-capable
    (ternary) formats whose :class:`~repro.core.quantize.QMeta` opts in
    (``meta.act_quant``, settable per path via QuantPolicy), and never for
    an explicit ``mode="dequant"`` oracle call. Everything else falls back
    to the float contraction, so mixed trees serve through one entrypoint
    and ``act_quant=False`` stays bit-identical to the historical streams.
    """
    m = qt.meta
    if len(m.shape) != 2:
        raise ValueError(f"qmatmul expects 2-D weights, got shape {m.shape}")
    if mode not in QLINEAR_MODES:
        raise ValueError(f"mode {mode!r} not in {QLINEAR_MODES}")

    spec = fmt_mod.get_format(m.fmt)
    mode = resolve_mode(x, m, mode)
    backend = resolve_backend(backend, m.fmt, mode)
    if not spec.supports_fused:
        mode = "dequant"  # non-ternary formats only store dense values

    act = (act_quant and spec.supports_fused and m.act_quant
           and mode != "dequant")
    if backend == "pallas":
        from repro.kernels.ops import qmatmul_kernel  # lazy: core<->kernels

        return qmatmul_kernel(x, qt, mode=mode, act_quant=act, tm=tm, tn=tn,
                              interpret=interpret, out_dtype=compute_dtype)
    if act:
        return spec.contract_int8(x, qt, compute_dtype=compute_dtype)
    return spec.contract(x, qt, mode=mode, compute_dtype=compute_dtype)
