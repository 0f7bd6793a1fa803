"""Operations and bytes that each measured kernel, and each served token,
need — worked out from shapes alone.

Bytes are what the stored formats hold: ITQ3_S keeps per 256-weight
block a 64-byte 2-bit plane, a 32-byte 1-bit plane, and a float16 scale
and zero point (100 bytes); the reduction dim is zero-padded to whole
blocks, so a 576-wide input reads three blocks (768). The rotated-int8
cache keeps per token, per KV head, head_dim int8 codes and one float16
scale, for K and for V. Activations are float32.
"""
from __future__ import annotations

from harness.model import Sizes

BLOCK = 256
ITQ3_BLOCK_BYTES = 64 + 32 + 2 + 2


def k_pad(k: int) -> int:
    return -(-k // BLOCK) * BLOCK


def itq3_matmul(m: int, k: int, n: int) -> tuple[float, float]:
    """(FLOPs, bytes) of ``x (m, k) @ W_hat (k, n)`` from packed planes:
    the planes and scales once, the padded activations in, f32 out."""
    kp = k_pad(k)
    weights = n * (kp // BLOCK) * ITQ3_BLOCK_BYTES
    return 2.0 * m * kp * n, float(weights + 4 * m * kp + 4 * m * n)


def step_projections(s: Sizes) -> list[tuple[int, int]]:
    """(k, n) of every ITQ3_S projection one token passes through, per
    layer, in the order the layer runs them."""
    q, kv = s.heads * s.head_dim, s.kv_heads * s.head_dim
    return [(s.d, q), (s.d, kv), (s.d, kv), (q, s.d),
            (s.d, s.d_ff), (s.d, s.d_ff), (s.d_ff, s.d)]


def attn_decode(s: Sizes, kv_len: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one decode query row over ``kv_len`` cached
    positions, in one layer: all query heads, QK and PV; the cached codes
    and scales of K and V once, the query in and the output out."""
    flops = 4.0 * kv_len * s.heads * s.head_dim
    cache = kv_len * s.kv_heads * 2 * (s.head_dim + 2)
    return flops, float(cache + 2 * 4 * s.heads * s.head_dim)


def token_flops(s: Sizes, kv_len: int, *, head: bool) -> float:
    """Model FLOPs of one token that attends to ``kv_len`` positions
    (itself included): every matmul weight of every layer, attention's QK
    and PV, and the tied head when the token's logits are needed."""
    layers, head_p = s.param_counts()
    return (2.0 * layers + (2.0 * head_p if head else 0.0)
            + 4.0 * kv_len * s.heads * s.head_dim * s.layers)


def prefill_flops(s: Sizes, plen: int) -> float:
    """A prompt of ``plen`` real tokens, causal, with one head row (the
    last token's, which picks the first output token)."""
    layers, head_p = s.param_counts()
    attn = 4.0 * s.heads * s.head_dim * s.layers * plen * (plen + 1) / 2
    return 2.0 * layers * plen + 2.0 * head_p + attn
