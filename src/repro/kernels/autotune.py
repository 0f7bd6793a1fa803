"""Benchmark-driven (tm, tn) tile selection with an on-disk JSON cache.

The fused kernels take tile sizes as static arguments; the best choice
depends on the matmul shape, format, and the device generation — exactly
the knobs a human would sweep by hand. This module owns that sweep:

  * :func:`get_tiles` — the *lookup* used by ``qmatmul(..., tm=None)``:
    returns the cached winner for (device_kind, backend, fmt, M, N, K), or
    the deterministic defaults (DEFAULT_TM, DEFAULT_TN) on a miss. Pure
    lookup — never benchmarks — so it is safe to call at trace time, and in
    interpret mode (no real accelerator; timings would be meaningless) it
    is the *only* path: interpret keys never get benchmarked entries unless
    a caller explicitly forces tuning (tests do, on tiny shapes).
  * :func:`autotune` — the *sweep*: times the real kernel over the
    candidate lattice and records the winner in the cache file.
  * :func:`tune_params_shapes` — eager whole-model warmup: collect every
    QTensor matmul shape in a param tree and tune each at batch M. Wired to
    ``ServeEngine`` via ``Runtime(autotune=True)`` and to
    ``launch/serve.py --autotune``.

Cache file: ``$REPRO_AUTOTUNE_CACHE`` or ``~/.cache/repro/autotune.json``,
keyed per device kind so one home directory can serve CPU + several TPU
generations. M is bucketed (matvec regime below MATVEC_MAX_M, else next
power of two) so a decode shape tuned at 4 slots also serves 3.
"""
from __future__ import annotations

import json
import os
import tempfile
import time
import warnings
from pathlib import Path
from typing import Optional

import jax
import numpy as np

__all__ = [
    "DEFAULT_TM", "DEFAULT_TN", "get_tiles", "record", "autotune",
    "tune_params_shapes", "cache_path", "clear_memory_cache", "candidates",
    "get_attn_tiles", "record_attn", "autotune_attn", "attn_candidates",
]

DEFAULT_TM = 256
DEFAULT_TN = 256
_TM_LADDER = (8, 16, 32, 64, 128, 256)
_TN_LADDER = (128, 256, 512)  # partial strips must fill 128-wide lanes
_TQ_LADDER = (32, 64, 128, 256)   # attention query-tile widths
_TT_LADDER = (128, 256, 512)      # attention key-tile widths

_mem_cache: Optional[dict] = None


def cache_path() -> Path:
    env = os.environ.get("REPRO_AUTOTUNE_CACHE")
    if env:
        return Path(env)
    return Path.home() / ".cache" / "repro" / "autotune.json"


def clear_memory_cache() -> None:
    """Drop the in-process cache so the next lookup re-reads the file."""
    global _mem_cache
    _mem_cache = None


def _load() -> dict:
    global _mem_cache
    if _mem_cache is None:
        p = cache_path()
        try:
            with open(p) as f:
                _mem_cache = json.load(f)
        except FileNotFoundError:
            _mem_cache = {}
        except (OSError, ValueError) as e:
            # a corrupt or unreadable cache (e.g. torn by a concurrent
            # writer) degrades to "no tuned entries" — the defaults are
            # shape-safe everywhere, so warn instead of killing the caller
            warnings.warn(
                f"ignoring unreadable autotune cache {p} ({e}); "
                f"falling back to default tiles", RuntimeWarning,
                stacklevel=2)
            _mem_cache = {}
    return _mem_cache


def _save(cache: dict) -> None:
    p = cache_path()
    p.parent.mkdir(parents=True, exist_ok=True)
    # unique tmp per writer: a fixed tmp name lets two concurrent processes
    # (parallel CI shards) interleave writes and publish a torn file
    fd, tmp = tempfile.mkstemp(dir=p.parent, prefix=p.name + ".",
                               suffix=".tmp")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(cache, f, indent=1, sort_keys=True)
        os.replace(tmp, p)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


def device_kind(interpret: bool = False) -> str:
    if interpret:
        return "interpret"
    return jax.devices()[0].device_kind.replace(" ", "_")


def _bucket_m(m: int) -> int:
    """Round M up so nearby batch sizes share one tuned entry."""
    from repro.kernels.itq3_matvec import MATVEC_MAX_M

    if m <= MATVEC_MAX_M:
        return MATVEC_MAX_M  # matvec regime: tm is M itself, only tn matters
    b = MATVEC_MAX_M
    while b < m:
        b *= 2
    return b


def _key(m: int, n: int, k: int, fmt: str, *, backend: str,
         interpret: bool, act_quant: bool = False) -> str:
    # the W3A8 integer kernels have their own cost surface (no IFWHT MXU
    # passes, int8 operand tiling), so int8-path winners live under a
    # distinct key component; float-path keys are unchanged, preserving
    # every previously tuned cache entry.
    path = "|int8" if act_quant else ""
    return (f"{device_kind(interpret)}|{backend}|{fmt}{path}"
            f"|m{_bucket_m(m)}|n{n}|k{k}")


def candidates(m: int, n: int, k: int) -> list[tuple[int, int]]:
    """The (tm, tn) lattice worth sweeping for this shape."""
    from repro.kernels.itq3_matvec import MATVEC_MAX_M

    tms = [t for t in _TM_LADDER if t <= max(m, 8)] or [max(m, 1)]
    if m <= MATVEC_MAX_M:
        tms = [m]  # matvec kernel: no M tiling
    tns = [t for t in _TN_LADDER if t <= n] or [n]
    return [(tm, tn) for tm in tms for tn in tns]


def get_tiles(m: int, n: int, k: int, fmt: str, *, backend: str = "pallas",
              interpret: bool = False,
              act_quant: bool = False) -> tuple[int, int]:
    """Cached winner for this shape, or the deterministic defaults.

    Never benchmarks — interpret mode (and any untuned shape) always
    resolves to (DEFAULT_TM, DEFAULT_TN); the kernels clamp to the actual
    M/N, so the defaults are shape-safe everywhere. ``act_quant=True``
    looks up the int8-path key family.
    """
    ent = _load().get(_key(m, n, k, fmt, backend=backend, interpret=interpret,
                           act_quant=act_quant))
    if ent:
        return int(ent["tm"]), int(ent["tn"])
    return DEFAULT_TM, DEFAULT_TN


def record(m: int, n: int, k: int, fmt: str, tm: int, tn: int, *,
           backend: str = "pallas", interpret: bool = False,
           act_quant: bool = False, us: Optional[float] = None,
           save: bool = True) -> str:
    """Store a winner (used by :func:`autotune` and by tests)."""
    cache = _load()
    key = _key(m, n, k, fmt, backend=backend, interpret=interpret,
               act_quant=act_quant)
    cache[key] = {"tm": int(tm), "tn": int(tn)}
    if us is not None:
        cache[key]["us"] = round(float(us), 2)
    if save:
        _save(cache)
    return key


def _time_call(fn, iters: int = 3) -> float:
    for _ in range(1):
        jax.block_until_ready(fn())
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        times.append(time.perf_counter() - t0)
    return float(np.median(times) * 1e6)


def autotune(m: int, n: int, k: int, fmt: str = "itq3_s", *,
             mode: str = "weights", act_quant: bool = False,
             interpret: Optional[bool] = None,
             iters: int = 3, save: bool = True,
             force_interpret_bench: bool = False) -> tuple[int, int]:
    """Benchmark the candidate lattice for one shape and cache the winner.

    In interpret mode the sweep is skipped (timings there measure the
    Pallas interpreter, not hardware) and the defaults are returned —
    unless ``force_interpret_bench`` (tests, tiny shapes only).
    ``act_quant=True`` sweeps the W3A8 integer kernels and records under
    the int8 key family, so ``qmatmul(tm=None)`` autotunes both paths.
    """
    from repro.core import formats
    from repro.kernels.ops import auto_interpret, qmatmul_kernel

    if interpret is None:
        interpret = auto_interpret()
    if interpret and not force_interpret_bench:
        return DEFAULT_TM, DEFAULT_TN

    rng = np.random.default_rng(0)
    w = np.asarray(rng.normal(size=(k, n)) * 0.02, np.float32)
    x = np.asarray(rng.normal(size=(m, k)), np.float32)
    qt = formats.quantize(w, fmt)

    best, best_us = (DEFAULT_TM, DEFAULT_TN), float("inf")
    for tm, tn in candidates(m, n, k):
        us = _time_call(
            lambda: qmatmul_kernel(x, qt, mode=mode, act_quant=act_quant,
                                   tm=tm, tn=tn,
                                   interpret=interpret), iters=iters)
        if us < best_us:
            best, best_us = (tm, tn), us
    record(m, n, k, fmt, *best, interpret=interpret, act_quant=act_quant,
           us=best_us, save=save)
    return best


# --- fused-attention (tq, tt) tiles ----------------------------------------
#
# The attn_decode kernel's tiles live in the SAME cache file under their own
# key family: (device, "attn", cache-length bucket, head_dim, n_heads).
# Sequence length buckets to the next power of two (a cache tuned at 32k
# serves 20k), head counts matter because the grid row count R = B*KV trades
# against per-row tile work.

def _bucket_t(t: int) -> int:
    b = 256
    while b < t:
        b *= 2
    return b


# Speculative-decoding verify passes run the q-tile kernel at a NARROW
# query width (K+1 draft-window positions, typically <= 16) over a long
# cache — a cost surface the wide-prefill winners don't transfer to (the
# best tq is the window itself, and the best tt trades differently when
# the per-row q work is tiny). Narrow widths therefore get their own key
# component: a ``|qN`` suffix with N the window bucketed to a power of
# two. Wide-prefill keys are unchanged, preserving every previously tuned
# cache entry.
SPEC_QWIDTH_MAX = 16


def _bucket_q(q_width: int) -> int:
    b = 1
    while b < q_width:
        b *= 2
    return b


def _attn_key(t: int, head_dim: int, n_heads: int, *, interpret: bool,
              q_width: Optional[int] = None) -> str:
    qpart = f"|q{_bucket_q(q_width)}" if q_width is not None else ""
    return (f"{device_kind(interpret)}|attn|t{_bucket_t(t)}"
            f"|hd{head_dim}|h{n_heads}{qpart}")


def attn_candidates(t: int, head_dim: int, *, decode: bool = False,
                    q_width: Optional[int] = None) -> list[tuple[int, int]]:
    """The (tq, tt) lattice worth sweeping. Decode is the TQ=1
    specialization — only the key-tile width matters. A narrow ``q_width``
    (speculative verify) caps the query tile at the window itself: wider
    tiles would only pad."""
    tts = [c for c in _TT_LADDER if c <= max(t, _TT_LADDER[0])] or [max(t, 1)]
    if decode:
        tqs = [1]
    elif q_width is not None:
        tqs = sorted({w for w in (1, 2, 4, 8, _bucket_q(q_width))
                      if w <= _bucket_q(q_width)})
    else:
        tqs = list(_TQ_LADDER)
    return [(tq, tt) for tq in tqs for tt in tts]


def get_attn_tiles(t: int, head_dim: int, n_heads: int, *,
                   interpret: bool = False,
                   q_width: Optional[int] = None) -> tuple[int, int]:
    """Cached (tq, tt) winner for this attention shape, or the
    deterministic defaults. Pure lookup, exactly like :func:`get_tiles`:
    interpret mode always resolves to (DEFAULT_TQ, DEFAULT_TT) unless a
    test recorded an entry explicitly. With ``q_width`` the narrow-window
    key family is consulted first, falling back to the base (wide) key so
    an untuned verify shape still benefits from a tuned tt."""
    from repro.kernels.attn_decode import DEFAULT_TQ, DEFAULT_TT

    cache = _load()
    if q_width is not None:
        ent = cache.get(_attn_key(t, head_dim, n_heads, interpret=interpret,
                                  q_width=q_width))
        if ent:
            return int(ent["tq"]), int(ent["tt"])
    ent = cache.get(_attn_key(t, head_dim, n_heads, interpret=interpret))
    if ent:
        return int(ent["tq"]), int(ent["tt"])
    return DEFAULT_TQ, DEFAULT_TT


def record_attn(t: int, head_dim: int, n_heads: int, tq: int, tt: int, *,
                interpret: bool = False, us: Optional[float] = None,
                save: bool = True, q_width: Optional[int] = None) -> str:
    """Store an attention tile winner (used by :func:`autotune_attn` and by
    tests)."""
    cache = _load()
    key = _attn_key(t, head_dim, n_heads, interpret=interpret,
                    q_width=q_width)
    cache[key] = {"tq": int(tq), "tt": int(tt)}
    if us is not None:
        cache[key]["us"] = round(float(us), 2)
    if save:
        _save(cache)
    return key


def autotune_attn(t: int, head_dim: int, n_heads: int, *, batch: int = 4,
                  g: int = 1, decode: bool = False,
                  interpret: Optional[bool] = None, iters: int = 3,
                  save: bool = True, q_width: Optional[int] = None,
                  force_interpret_bench: bool = False) -> tuple[int, int]:
    """Benchmark the fused attention kernel's (tq, tt) lattice on a
    synthetic rotated-int8 cache and record the winner. Interpret mode
    skips the sweep (same contract as :func:`autotune`). ``q_width``
    sweeps (and records under) the narrow-window verify family."""
    from repro.kernels.attn_decode import (
        DEFAULT_TQ, DEFAULT_TT, attn_q8_pallas,
    )
    from repro.kernels.ops import auto_interpret

    if interpret is None:
        interpret = auto_interpret()
    if interpret and not force_interpret_bench:
        return DEFAULT_TQ, DEFAULT_TT

    rng = np.random.default_rng(0)
    if decode:
        # decode runs the slot-plane grid: every kv head of a slot per step
        lead, q_shape = (batch, n_heads), (batch, n_heads, g, head_dim)
    else:
        tq_total = q_width if q_width is not None else min(t, 512)
        lead = (batch * n_heads,)
        q_shape = (lead[0], tq_total, g, head_dim)
    q = np.asarray(rng.normal(size=q_shape), np.float32)
    kc = rng.integers(-127, 128, size=lead + (t, head_dim)).astype(np.int8)
    vc = rng.integers(-127, 128, size=lead + (t, head_dim)).astype(np.int8)
    ks = np.abs(rng.normal(size=lead + (t,))).astype(np.float32) * 0.02
    vs = np.abs(rng.normal(size=lead + (t,))).astype(np.float32) * 0.02
    kv_len = np.full(lead[:1], t, np.int32)
    off = None if decode else np.zeros(lead, np.int32)

    best, best_us = (DEFAULT_TQ, DEFAULT_TT), float("inf")
    for tq, tt in attn_candidates(t, head_dim, decode=decode,
                                  q_width=q_width):
        us = _time_call(
            lambda: attn_q8_pallas(
                q, kc, ks, vc, vs, kv_len, off,
                sm_scale=head_dim ** -0.5, causal=not decode, tq=tq, tt=tt,
                interpret=interpret), iters=iters)
        if us < best_us:
            best, best_us = (tq, tt), us
    record_attn(t, head_dim, n_heads, *best, interpret=interpret,
                us=best_us, save=save, q_width=q_width)
    return best


def tune_params_shapes(params, m: int, *, interpret: Optional[bool] = None,
                       act_quant: bool = False,
                       **kw) -> list[tuple[int, int, int, str]]:
    """Tune every distinct QTensor matmul shape in ``params`` at batch M.

    Returns the list of (m, n, k, fmt) shapes tuned; empty in interpret
    mode (CPU serving keeps the deterministic defaults). With
    ``act_quant=True`` each shape is additionally tuned on the W3A8
    integer kernels (its own key family), so an engine booted with the
    integer path on warms both caches.
    """
    from repro.core.quantize import QTensor
    from repro.kernels.ops import auto_interpret

    if interpret is None:
        interpret = auto_interpret()
    if interpret:
        return []
    shapes = set()
    for leaf in jax.tree.leaves(
            params, is_leaf=lambda x: isinstance(x, QTensor)):
        if isinstance(leaf, QTensor) and len(leaf.meta.shape) == 2:
            shapes.add((leaf.meta.shape[0], leaf.meta.n, leaf.meta.fmt))
    tuned = []
    for k, n, fmt in sorted(shapes):
        autotune(m, n, k, fmt, interpret=interpret, **kw)
        if act_quant:
            autotune(m, n, k, fmt, interpret=interpret, act_quant=True, **kw)
        tuned.append((m, n, k, fmt))
    return tuned
