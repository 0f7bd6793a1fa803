#!/usr/bin/env python3
"""One benchmark run of one cell on the chip it is started on.

    python3 bench/run.py --workload qwen05b.chat --seed 7 --seconds 51 --trace 0

Set-up (counted in ``setup_s``): the compile cache, seeded ITQ3_S weights
built on the device in one jitted call, the engine as the cell says, and
a warm-up of exactly the shapes the cell's seeded schedule reaches. Then
the client serves the schedule for ``--seconds``. With ``--trace 0`` the
result holds the cell's end-to-end metrics; with ``--trace 1`` the 3 s
right after the window are traced, under the same load, and the result
holds the cell's per-layer metrics.

After the window: a seeded sample of served requests, the longest among
them, goes through the plain reference (``harness/reference.py``), and
``correct`` says whether the served tokens lie within the cell's limits
of the reference's choices: the widest and the mean gap of a served
token's logit below the reference's best, and the spread of logit error
those choices imply. Each number compared is printed beside its limit,
last on stderr and last in the result line. ``--control`` puts a lower
precision in the program's place; the comparison has to find it not
correct.

The last line of stdout is one JSON object. A run that finds no TPU, or
fewer chips than the cell asks for, prints no result and exits 2.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(HERE), "src")]

import numpy as np  # noqa: E402

from harness.spec import Registry  # noqa: E402
from harness.trace import Trace, busy_ns, idle_by_span, top_ops  # noqa: E402
from harness.traffic import DRAIN_S, prefill_buckets, schedule  # noqa: E402

# the traced slice, right after the window
TRACE_S = 3.0
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CONTROLS = {
    "fp8": "the reference with every matmul operand rounded to fp8 chooses "
           "each token",
    "bf16": "the program serves in bfloat16 (Runtime compute_dtype)",
    "w3a8": "the program serves through its int8-activation path",
}


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def device_info(reg: Registry, chips: int):
    """The device as JAX reports it, or None (no TPU, too few chips)."""
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}
    log(f"device: platform={info['platform']} kind={info['kind']!r} "
        f"devices={len(devs)}; cell asks for {chips}")
    if info["platform"] != "tpu" or len(devs) < chips:
        return None, devs
    return info, devs


def peaks_for(reg: Registry, kind: str) -> dict:
    with open(os.path.join(reg.bench, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def warm_up(eng, Request, buckets: dict, vocab: int, seed: int) -> None:
    """Compile every prefill shape the schedule reaches (one prompt per
    wave) and the decode step, through the engine's own path."""
    rng = np.random.default_rng(seed ^ 0x5EED)
    for i, (bucket, plen) in enumerate(sorted(buckets.items())):
        req = Request(rid=-1 - i, max_new=2,
                      prompt=rng.integers(0, vocab, plen, dtype=np.int32))
        for _ in eng.generate([req]):
            pass
        if req.finish_reason != "length":
            raise RuntimeError(f"warm-up bucket {bucket}: "
                               f"{req.finish_reason}")


def check_sample(recs, seed: int, tokens: int) -> list:
    """Requests to compare: the one with the longest served sequence,
    then a seeded draw until ``tokens`` served tokens are in. Finished
    requests count whole; one still decoding when serving stopped counts
    with the tokens it was served (a long generation outlasts a window)."""
    done = [r for r in recs if r.finish == "length"
            or (r.finish is None and len(r.tokens) >= 2)]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.req.prompt) + len(r.tokens),
                                       r.req.rid))
    out, n = [longest], len(longest.tokens)
    rng = np.random.default_rng(seed)
    for i in rng.permutation(len(done)):
        if n >= tokens:
            break
        if done[i] is not longest:
            out.append(done[i])
            n += len(done[i].tokens)
    return out


# the numbers compared, by their name in a cell's "check" and in compare()
CHECKED = {"max_logit_gap": "widest", "mean_logit_gap": "mean",
           "logit_noise": "noise"}


def compare(sample, seed: int, sizes, control: str | None = None) -> dict:
    """How far each chosen token's logit lies below the reference's best,
    over the sample: the widest gap, the mean gap, and the spread of the
    chooser's logit error that those choices imply (``noise_scale``); and
    whether every request is whole. The chooser is the served path, or
    with ``control="fp8"`` the reference one precision down, put in the
    program's place: at each position of the same prompts and served
    tokens, the token it ranks first."""
    import jax.numpy as jnp
    from harness import reference
    from harness.model import seed_key
    p = reference.prepare(seed_key(seed), sizes)
    low = jnp.float8_e4m3fn if control == "fp8" else None
    gaps, margins, whole = [], [], True
    for rec in sample:
        toks = np.asarray(rec.tokens)
        whole &= ((rec.finish is None or len(toks) == rec.req.max_new)
                  and bool(((toks >= 0) & (toks < sizes.vocab)).all()))
        lows = (reference.logits_blocks(p, rec.req.prompt, toks, sizes, low)
                if low is not None else None)
        for i, n, lg in reference.logits_blocks(p, rec.req.prompt, toks,
                                                sizes):
            if lows is not None:
                chosen = reference.first_choice(next(lows)[2])
            else:
                chosen = np.zeros(lg.shape[0], np.int32)
                chosen[:n] = toks[i:i + n]
            g, m = reference.choice_stats(lg, chosen)
            gaps.append(np.asarray(g)[:n])
            margins.append(np.asarray(m)[:n])
    gaps, margins = np.concatenate(gaps), np.concatenate(margins)
    return {"widest": float(gaps.max()), "mean": float(gaps.mean()),
            "noise": reference.noise_scale(gaps, margins),
            "flips": int((gaps > 0).sum()), "served": len(gaps),
            "whole": whole, "requests": len(sample)}


_COMPILES: list = []
_LISTENING: list = []


def compile_times() -> list:
    """perf_counter times of this process's backend compiles (the listener
    is registered once, however many runs one process makes)."""
    import jax
    if not _LISTENING:
        _LISTENING.append(True)
        jax.monitoring.register_event_duration_secs_listener(
            lambda name, secs, **_: _COMPILES.append(time.perf_counter())
            if name == COMPILE_EVENT else None)
    return _COMPILES


def check_paths(stats: dict) -> None:
    """The served path must run the Pallas kernels: no silent fallback."""
    if stats["matmul_path"] != "pallas" or stats["attn_path"] != "pallas":
        raise RuntimeError(f"served path is not the kernels': matmul "
                           f"{stats['matmul_path']}, attn {stats['attn_path']}")


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", choices=CONTROLS, default=None,
                    help="run a control in the program's place, which the "
                    "comparison has to find not correct: " + "; ".join(
                        f"{k}: {v}" for k, v in CONTROLS.items()))
    ap.add_argument("--rate", type=float, default=None,
                    help="offer this many requests a second instead of the "
                    "cell's rate (to find a chat cell's knee)")
    return ap.parse_args(argv)


def run_cell(args, reg: Registry):
    """Set up, serve the window, read the metrics. Returns None where the
    device is not the cell's, else the run (records, metrics, device)."""
    wl = reg.workload(args.workload)
    cell = reg.cell(args.workload)
    conf = reg.config(cell["config"])
    mix = reg.mix(cell["traffic"])
    kinds = "per_layer" if args.trace else "end_to_end"
    metrics = reg.metrics_for(args.workload, kinds)
    readers = {m["name"]: reg.reader(m["name"]) for m in metrics}

    dev, devs = device_info(reg, wl["chips"])
    if dev is None:
        return None
    peaks = peaks_for(reg, dev["kind"])

    import jax
    from repro.compile_cache import setup_compile_cache
    from repro.serve.engine import Request
    from harness.client import Client, no_span
    from harness.model import (Sizes, make_engine, program_config,
                               served_params)

    log(f"compile cache: {setup_compile_cache()}")
    # every program goes to the cache, however quick its compile: a run's
    # set-up then loads what the first run in the checkout compiled
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compiles = compile_times()

    sizes = Sizes.of(conf)
    eng_set = cell["engine"]
    reqs = schedule(mix, args.seed, window_s=args.seconds,
                    max_len=eng_set["max_len"], vocab=sizes.vocab,
                    slots=eng_set["slots"],
                    rate_per_s=args.rate or cell.get("rate_per_s"))
    buckets = prefill_buckets(reqs, eng_set["prompt_pad"], eng_set["max_len"])
    t = time.perf_counter()
    params = served_params(args.seed, sizes)
    eng = make_engine(params, program_config(conf), eng_set,
                      path=args.control if args.control != "fp8" else None)
    st = eng.stats()
    log(f"weights + engine {time.perf_counter() - t:.2f}s; matmul_path="
        f"{st['matmul_path']} attn_path={st['attn_path']}; pool "
        f"{st['cache_bytes']} B, {st['cache_bytes_per_token']} B/token")
    check_paths(st)
    t = time.perf_counter()
    warm_up(eng, Request, buckets, sizes.vocab, args.seed)
    log(f"warm-up {time.perf_counter() - t:.2f}s: decode + "
        f"{len(buckets)} prefill buckets {sorted(buckets)}; "
        f"{len(compiles)} compiles so far")

    backlog = mix["arrivals"] == "backlog"
    client = Client(eng, reqs, t0=time.perf_counter(),
                    max_wave=cell.get("max_wave"), Request=Request)
    if backlog:
        # fill every slot before the window opens, one prompt a tick
        slots = eng_set["slots"]
        client.run(lambda now: sum(len(r.tokens) > 0 for r in client.recs)
                   >= slots)
    t_open = time.perf_counter()
    setup_s = t_open - T_START
    t_close = t_open + args.seconds
    if not backlog:
        for rec in client.recs:  # due times count from the window's start
            rec.due = t_open + rec.req.due
    log(f"set-up {setup_s:.3f}s; window {args.seconds}s")

    client.run(lambda now: now >= t_close)
    in_window_compiles = sum(t_open <= c < t_close for c in compiles)
    due = [r for r in client.recs if r.req.in_window]
    log(f"at the window's close {sum(not r.tokens for r in due)} of "
        f"{len(due)} due requests had no token yet (a backlog that grows "
        f"with the rate: past the knee)")
    trace, ticks = None, None
    if args.trace:
        # the traced slice follows the window under the same load, so the
        # profiler's start and stop never stall the window itself
        tdir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(tdir)
        client.span = jax.profiler.TraceAnnotation
        k0 = eng.decode_steps
        t_end = time.perf_counter() + TRACE_S
        client.run(lambda now: now >= t_end)
        ticks = (k0, eng.decode_steps)
        jax.profiler.stop_trace()
        client.span = no_span
        trace = Trace.from_xplane(tdir)
        shutil.rmtree(tdir, ignore_errors=True)
    # the window's last requests still wait for their first token
    waiting = [r for r in client.recs if r.req.in_window and not backlog]
    client.run(lambda now: now >= t_close + DRAIN_S
               or all(r.tokens for r in waiting))
    client.close()
    stats = eng.stats()
    log("engine: " + ", ".join(f"{k}={stats[k]}" for k in (
        "decode_steps", "tokens_decoded", "max_concurrent", "preemptions",
        "pool_exhausted", "quarantined", "waiting")))
    mem = devs[0].memory_stats() or {}
    dev["memory_peak_bytes"] = int(mem.get("peak_bytes_in_use", 0))
    lags = [r.released - r.due for r in client.recs
            if r.released is not None and r.req.in_window]
    if lags:
        log(f"client lag behind due times: p50 {np.percentile(lags, 50) * 1e3:.3f} "
            f"ms, p99 {np.percentile(lags, 99) * 1e3:.3f} ms, max "
            f"{max(lags) * 1e3:.3f} ms over {len(lags)} requests")
    ttft = [r.times[0] - r.due for r in client.recs
            if r.req.in_window and r.times and not backlog]
    if ttft:
        log("ttft ms p50/p90/p95/max " + " ".join(
            f"{np.percentile(ttft, q) * 1e3:.1f}" for q in (50, 90, 95, 100))
            + f" over {len(ttft)} requests")
    # free the pool and the weights before the reference runs
    jax.tree.map(lambda a: a.delete(), (eng.cache, eng.params))
    del client.eng, eng, params
    gc.collect()

    recs = client.recs
    sent = [r for r in recs if r.req.in_window and r.engine_req is not None]
    # an error finish fails; so does a chat request left without a token
    failed = sum(r.finish not in (None, "length")
                 or (not backlog and not r.tokens) for r in sent)
    run = types.SimpleNamespace(
        records=recs, t_open=t_open, t_close=t_close, window_s=args.seconds,
        setup_s=setup_s, compiles_in_window=in_window_compiles, trace=trace,
        ticks=ticks, sizes=sizes, peaks=peaks, cell=cell, stats=stats,
        backlog=backlog)
    values = {}
    for m in metrics:
        v = readers[m["name"]](run)
        if v is not None:
            values[m["name"]] = {"value": float(v), "unit": m["unit"]}
        log(f"{m['name']}: {v} {m['unit']}")
    run.values, run.dev, run.sent, run.failed = values, dev, sent, failed
    return run


def main(argv=None, reg: Registry | None = None) -> int:
    args = parse(argv)
    reg = reg or Registry()
    run = run_cell(args, reg)
    if run is None:
        log("bench: needs a TPU with the cell's chips; no result")
        return 2
    recs, sizes, cell, trace, dev = (run.records, run.sizes, run.cell,
                                     run.trace, run.dev)
    t = time.perf_counter()
    check = cell["check"]
    sample = check_sample(recs, args.seed, check["tokens"])
    cmp = compare(sample, args.seed, sizes, args.control) if sample else None
    log(f"reference: {cmp and cmp['requests']} requests, "
        f"{cmp and cmp['served']} served tokens, {cmp and cmp['flips']} not "
        f"the reference's first choice; widest {cmp and cmp['widest']}, "
        f"mean {cmp and cmp['mean']}, noise {cmp and cmp['noise']}; "
        f"{time.perf_counter() - t:.2f}s"
        + (f"; control {args.control}" if args.control else ""))
    checks = {name: {"value": cmp and cmp[key], "limit": check[name]}
              for name, key in CHECKED.items() if name in check}
    checks["requests_whole"] = {"value": int(bool(cmp and cmp["whole"])),
                                "limit": 1}
    correct = bool(cmp and cmp["whole"] and all(
        checks[n]["value"] <= checks[n]["limit"] for n in CHECKED
        if n in checks))
    out = {"correct": correct, "attempted": len(run.sent),
           "failed": int(run.failed), "metrics": run.values, "device": dev}
    if trace is not None:
        lo, hi = trace.window()
        dev["busy_s"] = busy_ns(trace.ops, lo, hi) / 1e9
        dev["window_s"] = (hi - lo) / 1e9
        out["breakdown"] = {"device_ops": top_ops(trace),
                            "idle_gaps": idle_by_span(trace)}
    out["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
