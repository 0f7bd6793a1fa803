"""Plain reference of the dense family, in ``jax.numpy``.

It follows the published architecture (Qwen2 / Llama blocks: RMSNorm,
rotary embedding on the halves of each head, grouped-query attention,
SwiGLU, tied head) at the precision the configuration states, each step
written out from its description and nothing imported from the program:

* ITQ3_S weights (paper Algorithm 1, Eq. 10): per 256-block along the
  reduction dim, rotate by the normalized Hadamard matrix, scale
  ``d = 0.7979 * std`` stored in float16, zero point
  ``z = clip(-round(mean / d), -1, 1)``, codes
  ``q = clip(round(w' / d) + z, -1, 1)``; the weight is ``H (d (q - z))``.
* KV cache: each key (after rotary) and value vector rotated by
  ``H_head_dim``, int8 with one float16 absmax scale, then read back.
* float32 elsewhere, every product at ``highest`` precision.

``operands=jnp.float8_e4m3fn`` rounds every matmul operand to fp8 first
(float32 accumulation): the reference one precision below the
configuration's bfloat16 operands, put in the program's place by
``run.py --control fp8``.

Weights come from :func:`harness.model.float_params` (the benchmark's
seeded draw), never from the program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from harness.model import Sizes, float_params

ALPHA = 0.7979  # paper Eq. 8: optimal ternary scale over the block std
BLOCK = 256
ROW_BLOCK = 512  # query rows per attention block
SEQ_PAD = 2048  # sequences pad to a multiple: few lengths, few compiles
HEAD_ROWS = 256  # positions per block of head logits
HIGHEST = jax.lax.Precision.HIGHEST


def hadamard(n: int) -> np.ndarray:
    h = np.array([[1.0]])
    while h.shape[0] < n:
        h = np.block([[h, h], [h, -h]])
    return (h / np.sqrt(n)).astype(np.float32)


def itq3_s(w):
    """(K, N) float -> the ITQ3_S weight it is served as (float32)."""
    k, n = w.shape
    kp = -(-k // BLOCK) * BLOCK
    b = jnp.pad(w, ((0, kp - k), (0, 0))).reshape(kp // BLOCK, BLOCK, n)
    h = jnp.asarray(hadamard(BLOCK))
    r = jnp.einsum("ij,bjn->bin", h, b, precision=HIGHEST)
    d = (ALPHA * jnp.std(r, axis=1, keepdims=True)).astype(
        jnp.float16).astype(jnp.float32)
    safe = jnp.where(d > 0, d, 1.0)
    z = jnp.clip(-jnp.round(jnp.mean(r, axis=1, keepdims=True) / safe),
                 -1, 1)
    q = jnp.clip(jnp.round(r / safe) + z, -1, 1)
    back = jnp.einsum("ij,bjn->bin", h, d * (q - z), precision=HIGHEST)
    return back.reshape(kp, n)[:k]


def kv_int8(x):
    """Rotated-int8 round trip of (..., head_dim) cache vectors."""
    h = jnp.asarray(hadamard(x.shape[-1]))
    xr = jnp.matmul(x, h, precision=HIGHEST)
    f16 = np.finfo(np.float16)
    scale = jnp.clip(jnp.max(jnp.abs(xr), axis=-1, keepdims=True) / 127.0,
                     float(f16.tiny), float(f16.max))
    scale = scale.astype(jnp.float16).astype(jnp.float32)
    q = jnp.clip(jnp.round(xr / scale), -127, 127)
    return jnp.matmul(q * scale, h, precision=HIGHEST)


@functools.partial(jax.jit, static_argnames=("s",))
def prepare(key_data, s: Sizes):
    """The seeded weights as the reference reads them: every projection
    through the ITQ3_S arithmetic."""
    p = float_params(key_data, s)
    layers = dict(p["layers"])
    for grp in ("attn", "mlp"):
        layers[grp] = {
            leaf: (jax.vmap(itq3_s)(w) if leaf[0] != "b" else w)
            for leaf, w in layers[grp].items()}
    return dict(p, layers=layers)


def _rmsnorm(x, scale, eps):
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * scale


def _rope(x, pos, theta):
    """x (T, heads, hd): rotate the two halves of each head."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _mm(operands):
    """A float32 product, its operands first rounded to ``operands``."""
    rnd = ((lambda a: a) if operands is None else
           (lambda a: a.astype(operands).astype(jnp.float32)))
    return lambda spec, a, b: jnp.einsum(spec, rnd(a), rnd(b),
                                         precision=HIGHEST)


@functools.partial(jax.jit, static_argnames=("s", "operands"))
def hidden(p, tokens, s: Sizes, operands=None):
    """tokens (T,), T a multiple of SEQ_PAD -> final hidden (T, d),
    before the last norm. Causal, so padding after the real tokens
    changes none of their rows."""
    t = tokens.shape[0]
    hd, kvh = s.head_dim, s.kv_heads
    g = s.heads // kvh
    pos = jnp.arange(t)
    ein = _mm(operands)
    mm = functools.partial(ein, "...k,kn->...n")

    def layer(x, lp):
        a = lp["attn"]
        h = _rmsnorm(x, lp["ln1"]["scale"], s.norm_eps)
        q, k, v = mm(h, a["wq"]), mm(h, a["wk"]), mm(h, a["wv"])
        if s.qkv_bias:
            q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
        q = _rope(q.reshape(t, s.heads, hd), pos, s.rope_theta)
        k = _rope(k.reshape(t, kvh, hd), pos, s.rope_theta)
        k = kv_int8(k)
        v = kv_int8(v.reshape(t, kvh, hd))
        qb = q.reshape(t // ROW_BLOCK, ROW_BLOCK, kvh, g, hd)

        def attend(args):
            i, qi = args
            sc = ein("qkgd,tkd->kgqt", qi, k) / np.sqrt(hd)
            qpos = i * ROW_BLOCK + jnp.arange(ROW_BLOCK)
            sc = jnp.where(pos[None, None, None, :] <= qpos[None, None, :,
                                                              None],
                           sc, -1e30)
            w = jax.nn.softmax(sc, axis=-1)
            return ein("kgqt,tkd->qkgd", w, v)

        o = jax.lax.map(attend, (jnp.arange(t // ROW_BLOCK), qb))
        x = x + mm(o.reshape(t, s.heads * hd), a["wo"])
        m = lp["mlp"]
        h = _rmsnorm(x, lp["ln2"]["scale"], s.norm_eps)
        x = x + mm(jax.nn.silu(mm(h, m["gate"])) * mm(h, m["up"]),
                   m["down"])
        return x, None

    x = jnp.take(p["embed"], tokens, axis=0)
    x, _ = jax.lax.scan(layer, x, p["layers"])
    return x


@functools.partial(jax.jit, static_argnames=("s", "operands"))
def head(p, rows, s: Sizes, operands=None):
    """(HEAD_ROWS, d) hidden rows -> (HEAD_ROWS, vocab) logits."""
    x = _rmsnorm(rows, p["ln_f"]["scale"], s.norm_eps)
    return _mm(operands)("rd,vd->rv", x, p["embed"])


def served_rows(plen: int, n_out: int) -> np.ndarray:
    """Sequence positions whose logits chose the served tokens: the last
    prompt position chose token 0 (prefill), each later one the next."""
    return np.arange(plen - 1, plen - 1 + n_out)


def logits_blocks(p, prompt, out, s: Sizes, operands=None):
    """Yield (first row, rows, logits (HEAD_ROWS, vocab)) over the
    positions that chose ``out``, for the sequence ``prompt + out[:-1]``;
    the block's rows past ``rows`` are padding."""
    seq = np.concatenate([np.asarray(prompt, np.int32),
                          np.asarray(out[:-1], np.int32)])
    t = -(-len(seq) // SEQ_PAD) * SEQ_PAD
    toks = jnp.asarray(np.pad(seq, (0, t - len(seq))))
    h = hidden(p, toks, s, operands)
    rows = served_rows(len(prompt), len(out))
    for i in range(0, len(rows), HEAD_ROWS):
        idx = rows[i:i + HEAD_ROWS]
        sel = jnp.asarray(np.pad(idx, (0, HEAD_ROWS - len(idx))))
        yield i, len(idx), head(p, h[sel], s, operands)


@jax.jit
def choice_stats(logits, chosen):
    """Per row: how far the chosen token's logit lies below the row's
    best, and the best's margin over the runner-up."""
    top2 = jax.lax.top_k(logits, 2)[0]
    val = jnp.take_along_axis(logits, chosen[:, None], axis=1)[:, 0]
    return top2[:, 0] - val, top2[:, 0] - top2[:, 1]


@jax.jit
def first_choice(logits):
    return jnp.argmax(logits, axis=1).astype(jnp.int32)


def noise_scale(gaps, margins) -> float:
    """Maximum-likelihood spread of the chooser's error in the difference
    of two logits, from where it chose: a row whose best led by ``m`` and
    was kept says the error stayed under ``m`` (probability Phi(m / s));
    a row whose choice lay ``g`` below the best says it crossed ``g``
    (Phi(-g / s)). Unlike the mean gap, it weighs the near ties that held
    as well as those that flipped, so it reads the error's size rather
    than how many near ties a seed's sequences happen to hold. 0 where
    nothing flipped."""
    from scipy.special import log_ndtr
    gaps, margins = np.asarray(gaps, np.float64), np.asarray(margins,
                                                             np.float64)
    flip = gaps > 0
    if not flip.any():
        return 0.0
    d = np.where(flip, gaps, margins)
    grid = np.geomspace(1e-5, 10.0, 361)
    z = d[None, :] / grid[:, None]
    ll = np.where(flip[None, :], log_ndtr(-z), log_ndtr(z)).sum(axis=1)
    return float(grid[int(ll.argmax())])
