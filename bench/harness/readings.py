"""What the metric readers share: the client's timings and the traced
slice's work, counted from the requests the client saw.

Kernel and program names as they appear in the device trace: the
readers match them by these patterns.
"""
from __future__ import annotations

import numpy as np

from harness import costs

DECODE_PROGRAM = r"_decode_impl"
ATTN_OP = r"attn_q8_pallas"


def p95_ms(values) -> float | None:
    return float(np.percentile(values, 95) * 1e3) if len(values) else None


def window_gaps(run) -> list:
    """Every gap between consecutive tokens of a request, as the client
    saw them, whose later token came inside the window."""
    out = []
    for r in run.records:
        for a, b in zip(r.times, r.times[1:]):
            if run.t_open <= b < run.t_close:
                out.append(b - a)
    return out


def window_tokens(run) -> int:
    return sum(run.t_open <= t < run.t_close
               for r in run.records for t in r.times)


def traced_tokens(run):
    """(prompt length, token index) of every token the traced ticks made:
    index 0 came from the prefill, index i >= 1 from a decode step that
    attended to prompt + i positions."""
    k0, k1 = run.ticks
    for r in run.records:
        for i, tick in enumerate(r.ticks):
            if k0 < tick <= k1:
                yield len(r.req.prompt), i


def decode_steps(run) -> int:
    k0, k1 = run.ticks
    return k1 - k0


def min_time(flops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take: compute or memory bound."""
    return max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])


def model_flops(run) -> float:
    s = run.sizes
    tot = 0.0
    for plen, i in traced_tokens(run):
        tot += (costs.prefill_flops(s, plen) if i == 0
                else costs.token_flops(s, plen + i, head=True))
    return tot


def decode_op_ns(run, pattern: str) -> float:
    """Device time of the matching operations that ran inside a decode
    program (prefill runs the same kernels at other shapes)."""
    from harness.trace import matching
    progs = matching(run.trace.programs, DECODE_PROGRAM)
    t, j = 0.0, 0
    for _, start, dur in matching(run.trace.ops, pattern):
        while j < len(progs) and progs[j][1] + progs[j][2] <= start:
            j += 1
        if j < len(progs) and progs[j][1] <= start:
            t += dur
    return t


def itq3_roofline(run, pattern: str) -> float | None:
    """Share of its roofline an ITQ3_S kernel reached in the decode steps
    of the traced slice: every projection of every layer once per step at
    M = the engine's slots, least time from shapes over the kernel's
    device time."""
    s, slots = run.sizes, run.cell["engine"]["slots"]
    t, n = decode_op_ns(run, pattern), decode_steps(run)
    if not t or not n:
        return None
    need = sum(min_time(*costs.itq3_matmul(slots, k, m), run.peaks)
               for k, m in costs.step_projections(s)) * s.layers * n
    return 100.0 * need / (t / 1e9)
