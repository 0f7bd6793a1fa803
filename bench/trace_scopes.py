#!/usr/bin/env python3
"""Put one cell's decode step down to model scopes and its idle time down
to engine phases, on the chip.

    python3 bench/trace_scopes.py --workload qwen05b.chat --seed 7 \
        [--seconds 51] [--out chiprun_out/scopes] [--save PATH]

Sets the cell up as ``run.py`` does and serves its traffic for
``--seconds``, the length of a benchmark window. Then it times the
engine's ticks for 3 s with no profiler, and for 3 s more under the
profiler with the harness's and the engine's spans, and reduces the
traced slice (``harness/scopes.py``): the decode program's device time
per step by scope with the unscoped rest, the idle time by engine phase,
the share of the slice inside admissions, and the host time of a tick
traced against untraced. Prints one JSON object last; ``--out`` keeps
it with the decode program's HLO text. ``--save`` writes a few decode
steps around one admission, with the scope of every decode operation,
as a recorded trace for ``bench/tests``.
"""
from __future__ import annotations

import argparse
import gzip
import json
import os
import shutil
import statistics
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(HERE), "src")]

import run as bench_run  # noqa: E402
from harness import scopes as S  # noqa: E402
from harness.readings import DECODE_PROGRAM  # noqa: E402
from harness.spec import Registry  # noqa: E402
from harness.trace import Trace, idle_share, matching  # noqa: E402
from harness.traffic import prefill_buckets, schedule  # noqa: E402

log = bench_run.log


class TickClock:
    """The client's span hook: times every ``engine_tick`` that ran one
    decode step and nothing else (no admission: one host sync), and while
    ``tracing`` writes each span into the profiler's trace as well."""

    def __init__(self, eng):
        self.eng = eng
        self.ticks: list = []
        self.tracing = False

    def __call__(self, name: str):
        return _Span(self, name)


class _Span:
    def __init__(self, clock: TickClock, name: str):
        self.clock, self.name, self.inner = clock, name, None

    def __enter__(self):
        if self.clock.tracing:
            import jax
            self.inner = jax.profiler.TraceAnnotation(self.name)
            self.inner.__enter__()
        eng = self.clock.eng
        self.before = (eng.decode_steps, eng.host_syncs)
        self.t0 = time.perf_counter()

    def __exit__(self, *exc):
        dt = time.perf_counter() - self.t0
        if self.inner is not None:
            self.inner.__exit__(*exc)
        eng = self.clock.eng
        if (self.name == "engine_tick" and eng.decode_steps - self.before[0]
                == 1 == eng.host_syncs - self.before[1]):
            self.clock.ticks.append(dt)
        return False


def shapes_of(args):
    """Abstract stand-ins for a call's arguments, to lower it again."""
    import jax
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding,
                                       weak_type=getattr(a, "weak_type",
                                                         False))
        if isinstance(a, jax.Array) else a, args)


def serve_slice(client, eng, clock: TickClock, seconds: float) -> dict:
    """Serve ``seconds`` more: the slice's decode steps, its wall time per
    step, and the median host time of a tick that only decoded."""
    clock.ticks = []
    k0, t0 = eng.decode_steps, time.perf_counter()
    client.run(lambda now: now >= t0 + seconds)
    wall, steps = time.perf_counter() - t0, eng.decode_steps - k0
    return {"steps": steps, "wall_s": wall,
            "ms_per_step": wall / steps * 1e3 if steps else None,
            "decode_ticks": len(clock.ticks),
            "decode_tick_ms": statistics.median(clock.ticks) * 1e3
            if clock.ticks else None}


def excerpt(trace: Trace, spans: list, scope_map: dict) -> dict:
    """A few decode steps around the slice's first admission: the lists
    that ``Trace.load`` reads, the engine spans, and the scope of every
    decode operation in them."""
    decodes = matching(trace.programs, DECODE_PROGRAM)
    admit = next((s for s in spans if s[0] == "serve.admit"), None)
    if admit is None or len(decodes) < 4:
        return {}
    before = [p for p in decodes if p[1] + p[2] <= admit[1]][-2:]
    after = [p for p in decodes if p[1] >= admit[1] + admit[2]][:2]
    if len(before) < 2 or len(after) < 2:
        return {}
    lo, hi = before[0][1], after[-1][1] + after[-1][2]
    inside = lambda e: lo <= e[1] and e[1] + e[2] <= hi
    # names cut to the instruction's, which is all the readers look at
    ops = [(f"%{S.instr_name(e[0])} = ...",) + tuple(e[1:])
           for e in trace.ops if inside(e)]
    names = {S.instr_name(e[0]) for e in ops}
    return {"ops": ops, "programs": [e for e in trace.programs if inside(e)],
            "spans": [e for e in trace.spans if inside(e)],
            "engine_spans": [e for e in spans if inside(e)],
            "decode_scopes": {n: s for n, s in scope_map.items()
                              if n in names},
            "decode_steps": len(before) + len(after)}


def reduce_slice(trace: Trace, spans: list, scope_map: dict,
                 steps: int) -> dict:
    """The per-step scope table, idle by phase, and span shares."""
    by = S.scope_ns(trace, DECODE_PROGRAM, scope_map)
    total = sum(by.values())
    lo, hi = trace.window()
    phase_s: dict = {}
    for name, _, d in spans:
        phase_s[name] = phase_s.get(name, 0.0) + d / 1e9
    return {
        "decode_steps": steps,
        "decode_program_ms": sum(d for _, _, d in matching(
            trace.programs, DECODE_PROGRAM)) / steps / 1e6,
        "decode_ops_ms": total / steps / 1e6,
        "scope_ms": {str(k): v / steps / 1e6 for k, v in sorted(
            by.items(), key=lambda kv: -kv[1])},
        "unscoped_share": (by.get(None, 0.0) + by.get("?", 0.0)) / total
        if total else None,
        "unscoped_ops": S.unscoped_ops(trace, DECODE_PROGRAM, scope_map),
        "window_s": (hi - lo) / 1e9,
        "idle_share": idle_share(trace),
        "idle_by_phase": S.idle_by_phase(trace, spans),
        "admit_share": S.span_share(trace, spans, "serve.admit"),
        "phase_s": phase_s,
    }


def main(argv=None, reg: Registry | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--out", default=None,
                    help="directory for the result and the decode HLO text")
    ap.add_argument("--save", default=None,
                    help="write a recorded trace excerpt (.json.gz) here")
    args = ap.parse_args(argv)
    reg = reg or Registry()
    wl, cell = reg.workload(args.workload), reg.cell(args.workload)
    conf, mix = reg.config(cell["config"]), reg.mix(cell["traffic"])
    dev, _ = bench_run.device_info(reg, wl["chips"])
    if dev is None:
        log("trace_scopes: needs a TPU with the cell's chips; no result")
        return 2

    import jax
    from repro.compile_cache import setup_compile_cache
    from repro.serve.engine import Request
    from harness.client import Client
    from harness.model import (Sizes, make_engine, program_config,
                               served_params)

    log(f"compile cache: {setup_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # the cache's key leaves op_name metadata out by default, so a program
    # cached before the scopes changed would come back with stale ones
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    sizes, eng_set = Sizes.of(conf), cell["engine"]
    reqs = schedule(mix, args.seed, window_s=args.seconds,
                    max_len=eng_set["max_len"], vocab=sizes.vocab,
                    slots=eng_set["slots"], rate_per_s=cell.get("rate_per_s"))
    buckets = prefill_buckets(reqs, eng_set["prompt_pad"], eng_set["max_len"])
    eng = make_engine(served_params(args.seed, sizes), program_config(conf),
                      eng_set)
    bench_run.check_paths(eng.stats())
    bench_run.warm_up(eng, Request, buckets, sizes.vocab, args.seed)

    # the decode call's arguments, to lower the same program again later
    jit_decode, captured = eng._jit_decode, []

    def capture(*a):
        if not captured:
            captured.append(shapes_of(a))
        return jit_decode(*a)

    eng._jit_decode = capture
    clock = TickClock(eng)
    backlog = mix["arrivals"] == "backlog"
    client = Client(eng, reqs, t0=time.perf_counter(),
                    max_wave=cell.get("max_wave"), Request=Request)
    client.span = clock
    if backlog:
        client.run(lambda now: sum(len(r.tokens) > 0 for r in client.recs)
                   >= eng_set["slots"])
    t_open = time.perf_counter()
    if not backlog:
        for rec in client.recs:
            rec.due = t_open + rec.req.due
    client.run(lambda now: now >= t_open + args.seconds)
    eng._jit_decode = jit_decode

    off = serve_slice(client, eng, clock, bench_run.TRACE_S)
    tdir = tempfile.mkdtemp(prefix="bench_scopes_")
    jax.profiler.start_trace(tdir)
    clock.tracing = True
    on = serve_slice(client, eng, clock, bench_run.TRACE_S)
    clock.tracing = False
    jax.profiler.stop_trace()
    trace = Trace.from_xplane(tdir)
    spans = S.read_engine_spans(tdir)
    shutil.rmtree(tdir, ignore_errors=True)
    client.stop()

    hlo = jit_decode.lower(*captured[0]).compile().as_text()
    scope_map = S.hlo_scopes(hlo)
    missing = {S.instr_name(e[0]) for e in S.program_ops(
        trace, DECODE_PROGRAM)} - set(scope_map)
    out = {"workload": args.workload, "seed": args.seed, "device": dev,
           "untraced": off, "traced": on,
           "instructions_not_in_hlo": sorted(missing)[:20]}
    out.update(reduce_slice(trace, spans, scope_map, on["steps"]))
    if args.out:
        os.makedirs(args.out, exist_ok=True)
        stem = os.path.join(args.out, f"{args.workload}.{args.seed}")
        with gzip.open(stem + ".decode.hlo.gz", "wt") as f:
            f.write(hlo)
        with open(stem + ".json", "w") as f:
            json.dump(out, f, indent=1)
    if args.save:
        ex = excerpt(trace, spans, scope_map)
        if ex:
            with gzip.open(args.save, "wt") as f:
                json.dump(ex, f)
        log(f"excerpt: {len(ex.get('ops', []))} ops -> {args.save}")
    for k in ("scope_ms", "unscoped_share", "unscoped_ops", "idle_by_phase",
              "admit_share", "untraced", "traced"):
        log(f"{k}: {out[k]}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
