"""The one traffic generator. A mix is a data file of parameters
(``bench/traffic/<mix>.json``); the cell supplies its rate and the
engine's ``max_len``.

Every seed gets the same work: the lengths and the gaps between
arrivals are fixed quantiles of the mix's distributions, and the seed
only shuffles them (and draws the prompt ids). Runs with different seeds
then differ by arrangement, not by the amount of work, so their spread
measures the system and not the draw.

Two arrival kinds:

* ``open_loop``: arrivals at ``rate_per_s`` with exponential gaps (a
  Poisson process, stratified). Requests due in ``[0, window)`` are the
  ones measured; a second block keeps the load on after the window
  closes, while the window's last requests wait for their first token.
* ``backlog``: an offline batch, every request due at 0, in blocks of
  ``slots`` requests that each hold the same lengths, so the first fill
  of the slots is the same for every seed.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np

# After the window the open loop keeps sending for this long at most,
# while the window's requests still wait for their first token (and the
# traced slice of a ``--trace 1`` run follows the window).
DRAIN_S = 60.0


@dataclasses.dataclass
class Req:
    rid: int
    due: float          # seconds after the window opens
    prompt: np.ndarray  # (plen,) int32
    max_new: int
    in_window: bool     # due inside the measured window


def _quantiles(dist: dict, n: int) -> np.ndarray:
    """``n`` stratified draws: the distribution's (i + 0.5) / n quantiles,
    rounded to whole tokens and clipped to [min, max]."""
    u = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(p) for p in u])
        x = dist["median"] * np.exp(dist["sigma"] * z)
    elif kind == "uniform":
        x = dist["min"] + u * (dist["max"] - dist["min"])
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


def _block(mix: dict, n: int, rng: np.random.Generator, max_len: int):
    """(prompt lengths, output lengths) of ``n`` requests, shuffled apart
    so a long prompt is not always paired with a long output. Outputs are
    cut to what ``max_len`` leaves after the prompt."""
    plens = rng.permutation(_quantiles(mix["prompt"], n))
    outs = rng.permutation(_quantiles(mix["output"], n))
    plens = np.minimum(plens, max_len - mix["output"]["min"])
    outs = np.minimum(outs, max_len - plens)
    return plens, outs


def schedule(mix: dict, seed: int, *, window_s: float, max_len: int,
             vocab: int, slots: int, rate_per_s: float | None = None
             ) -> list[Req]:
    """The seeded request list of one run, sorted by due time."""
    rng = np.random.default_rng(seed)
    kind = mix["arrivals"]
    if kind == "open_loop":
        if not rate_per_s:
            raise ValueError("an open-loop mix needs the cell's rate_per_s")
        blocks = [(max(1, round(rate_per_s * window_s)), 0.0, True),
                  (max(1, math.ceil(rate_per_s * DRAIN_S)), window_s, False)]
        reqs = []
        for n, t0, in_window in blocks:
            plens, outs = _block(mix, n, rng, max_len)
            gaps = rng.permutation(
                -np.log1p(-(np.arange(n) + 0.5) / n) / rate_per_s)
            # the first gap is drawn like the rest: no request at exactly t0
            dues = t0 + np.cumsum(gaps)
            if in_window:
                dues *= window_s / max(dues[-1] - t0 + gaps.mean(), 1e-9)
            for p, o, d in zip(plens, outs, dues):
                reqs.append((float(d), int(p), int(o), in_window))
    elif kind == "backlog":
        # enough for the window at the mix's fastest turnover, plus a
        # full refill: the backlog never runs dry inside the window
        n_blocks = mix["blocks"]
        reqs = []
        for _ in range(n_blocks):
            plens, outs = _block(mix, slots, rng, max_len)
            reqs += [(0.0, int(p), int(o), True) for p, o in zip(plens, outs)]
    else:
        raise ValueError(f"unknown arrivals {kind!r}")
    out = []
    for rid, (due, plen, new, in_window) in enumerate(reqs):
        prompt = rng.integers(0, vocab, size=plen, dtype=np.int32)
        out.append(Req(rid, due, prompt, new, in_window))
    out.sort(key=lambda r: (r.due, r.rid))
    return out


def prefill_buckets(reqs: list[Req], prompt_pad: int, max_len: int
                    ) -> dict[int, int]:
    """{bucket: a prompt length that reaches it}: the prompt buckets these
    requests reach, padded as the engine pads a wave of one
    (``ServeEngine._bucket``), which are the prefill shapes a cell warms."""
    out: dict[int, int] = {}
    for r in reqs:
        p = len(r.prompt)
        out.setdefault(p + min((-p) % prompt_pad, max(0, max_len - 1 - p)),
                       p)
    return out
