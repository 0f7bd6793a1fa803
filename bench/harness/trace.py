"""From a profiler trace to per-layer numbers.

A trace reduces to three lists of ``(name, start_ns, dur_ns)`` events on
one clock: the device's operations, the device's whole programs, and the
harness's own host spans (``jax.profiler.TraceAnnotation``: ``wait_due``,
``submit``, ``engine_tick``, ``record``). Everything below works on those
lists, so it is checked on a small recorded trace
(``bench/tests/data/trace_small.json``) without a chip.

The traced window runs from the first harness span to the end of the
last one. Device busy time is the union of the operation intervals in
it; an idle gap is a stretch of the window with no operation, labelled
by the harness span that covers most of it.
"""
from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import re

HOST_SPANS = ("wait_due", "submit", "engine_tick", "record")


@dataclasses.dataclass
class Trace:
    ops: list       # device operations
    programs: list  # device programs (one event per executed module)
    spans: list     # harness host spans

    @classmethod
    def from_xplane(cls, logdir: str, device: int = 0) -> "Trace":
        """Read the ``.xplane.pb`` the profiler wrote under ``logdir``:
        the ops and modules lines of ``/device:TPU:<device>``, the host
        spans from every host thread."""
        from jax.profiler import ProfileData
        paths = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
        if len(paths) != 1:
            raise RuntimeError(f"expected one xplane under {logdir}, "
                               f"found {paths}")
        pd = ProfileData.from_file(paths[0])
        ops, programs, spans = [], [], []
        want = f"/device:TPU:{device}"
        for plane in pd.planes:
            if plane.name == want:
                for line in plane.lines:
                    dest = {"XLA Ops": ops, "XLA Modules": programs}.get(
                        line.name)
                    if dest is not None:
                        dest += [(e.name, e.start_ns, e.duration_ns)
                                 for e in line.events]
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    spans += [(e.name, e.start_ns, e.duration_ns)
                              for e in line.events if e.name in HOST_SPANS]
        for lst in (ops, programs, spans):
            lst.sort(key=lambda e: e[1])
        return cls(ops, programs, spans)

    @classmethod
    def load(cls, path: str) -> "Trace":
        with gzip.open(path, "rt") if path.endswith(".gz") else \
                open(path) as f:
            d = json.load(f)
        return cls(*(sorted((tuple(e) for e in d[k]), key=lambda e: e[1])
                     for k in ("ops", "programs", "spans")))

    def window(self) -> tuple[float, float]:
        if not self.spans:
            raise ValueError("trace holds no harness spans")
        return (self.spans[0][1],
                max(s + d for _, s, d in self.spans))


def busy_intervals(events, lo: float, hi: float) -> list:
    """Union of the events' intervals, clipped to [lo, hi], as sorted
    disjoint (start, end) pairs."""
    out: list = []
    for _, s, d in sorted(events, key=lambda e: e[1]):
        a, b = max(s, lo), min(s + d, hi)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def busy_ns(events, lo: float, hi: float) -> float:
    return float(sum(b - a for a, b in busy_intervals(events, lo, hi)))


def idle_share(tr: Trace) -> float:
    lo, hi = tr.window()
    return 1.0 - busy_ns(tr.ops, lo, hi) / (hi - lo)


def matching(events, pattern: str) -> list:
    rx = re.compile(pattern)
    return [e for e in events if rx.search(e[0])]


def time_ns(events, pattern: str) -> float:
    """Summed device time of the events whose name matches."""
    return float(sum(d for _, _, d in matching(events, pattern)))


def idle_gaps(tr: Trace) -> list:
    """(label, ns) of every idle stretch of the window, longest first,
    labelled by the harness span that overlaps it most."""
    lo, hi = tr.window()
    gaps, t = [], lo
    for a, b in busy_intervals(tr.ops, lo, hi) + [[hi, hi]]:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    out = []
    for a, b in gaps:
        best, label = 0.0, "none"
        for name, s, d in tr.spans:
            ov = min(b, s + d) - max(a, s)
            if ov > best:
                best, label = ov, name
        out.append((label, float(b - a)))
    out.sort(key=lambda g: -g[1])
    return out


def op_label(name: str) -> str:
    """'%attn_q8_pallas.13 = (f32[...]) custom-call(...)' -> 'attn_q8_pallas'."""
    return re.sub(r"\.\d+$", "", name.split(" = ")[0].lstrip("%"))


# control flow that encloses other operations on the same line
ENCLOSING = ("while", "conditional", "call")


def top_ops(tr: Trace, n: int = 10) -> list:
    """[[name, seconds]] of the device operations that took most time,
    summed by operation name (enclosing loops left out)."""
    lo, hi = tr.window()
    tot: dict = {}
    for name, s, d in tr.ops:
        label = op_label(name)
        if lo <= s < hi and label not in ENCLOSING:
            tot[label] = tot.get(label, 0.0) + d
    return [[k, v / 1e9] for k, v in sorted(tot.items(),
                                            key=lambda kv: -kv[1])[:n]]


def idle_by_span(tr: Trace, n: int = 10) -> list:
    """[[label, seconds]]: idle time of the window summed by what the
    host was doing."""
    tot: dict = {}
    for label, ns in idle_gaps(tr):
        tot[label] = tot.get(label, 0.0) + ns
    return [[k, v / 1e9] for k, v in sorted(tot.items(),
                                            key=lambda kv: -kv[1])[:n]]
