"""Serve-path benchmark: the decode hot loop, measured end to end.

Runs the continuous-batching engine on a reduced model (random init — this
measures plumbing, not quality) in both sampling modes:

  * ``host``   — the pre-overhaul decode discipline: logits shipped out of
    the jitted step, one host argmax (= one device->host sync) per active
    slot per step.
  * ``device`` — the overhauled path: sampling inside the jitted decode,
    one (slots,) token-vector transfer per step.

and records tok/s, wall seconds, host syncs per decoded token, and the
derived speedup. Greedy decoding makes the two modes token-identical, which
is asserted — a perf number for a wrong answer is worthless.

Each engine is run once untimed (jit warmup) and then timed on a fresh
request batch; engines are reused across batches so compile time never
lands in the measurement.

Request-lifecycle records (PR 4):

  * ``serve/cache_donation`` — asserts the jitted decode's donated cache
    buffers actually engaged (``cache_bytes_moved == 0``): a regression
    back to per-step functional cache copies fails the bench.
  * ``serve/sched_{fifo,priority,sjf}`` — streams a saturating queue
    through ``ServeEngine.generate`` under each admission policy and
    records mean queue wait, mean TTFT, and end-to-end tok/s.

Resilience records (PR 7):

  * ``serve/robust_overhead`` — the same fifo workload with deadlines,
    a bounded queue, and the watchdog armed: the fault-free cost of the
    resilience layer (token output asserted identical).
  * ``serve/faults_chaos`` — a seeded compound failure scenario (KV-scale
    poison, clock-skip deadline expiry, stalled step, queue overflow,
    priority preemption); asserts every resilience counter moved.
  * The **serving-SLO gate**: before overwriting the committed
    trajectory, a full run is compared against it and fails on
    ``serve/sched_*`` TTFT / queue-wait / tok_s regressions beyond
    ``SERVE_SLO_MAX_RATIO`` (benchmarks/common.py).

Speculative-decoding records (PR 10):

  * ``serve/spec_baseline`` / ``serve/spec_selfdraft`` — the
    propose/verify/commit pipeline on an acceptance-friendly self-draft
    pair (target layers >= 1 are exact no-ops): decode tok/s speedup with
    acceptance rate and mean committed tokens/step, greedy token parity
    asserted.
  * ``serve/calibration`` — wall time of a fixed jitted probe on the
    machine that produced the trajectory; the SLO gate widens its
    tolerance by the measured slowdown when a different machine checks.

Emits ``BENCH_serve.json`` at the repo root (schema: benchmarks/common.py;
the scheduler/donation/fault records carry required metric keys the CI
bench-smoke job validates). Smoke mode writes ``BENCH_serve.smoke.json``
instead — a post-run smoke must never clobber the committed full-size
trajectory.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import (
    BenchSuite, CALIBRATION_RECORD, assert_no_slo_regression,
    calibration_wall_ms, repo_root,
)
from repro.configs.base import get_config, reduced
from repro.models import lm
from repro.models.layers import Runtime
from repro.serve.engine import Request, ServeEngine
from repro.serve.quantized import quantize_params

RT = Runtime(compute_dtype=jnp.float32)


def _requests(n: int, vocab: int, max_new: int, seed: int) -> list[Request]:
    rng = np.random.default_rng(seed)
    return [Request(rid=i, prompt=rng.integers(0, vocab, size=6 + i % 5),
                    max_new=max_new) for i in range(n)]


def _run_mode(params, cfg, *, sample_on_host: bool, slots: int,
              n_requests: int, max_new: int, max_len: int, repeats: int = 3):
    eng = ServeEngine(params, cfg, slots=slots, max_len=max_len, rt=RT,
                      sample_on_host=sample_on_host)
    eng.run(_requests(n_requests, cfg.vocab_size, max_new, seed=1))  # warmup
    walls, out, tokens = [], None, 0
    syncs0, toks0 = eng.host_syncs, eng.tokens_decoded
    for _ in range(repeats):  # median over repeats: CPU walltime is noisy
        reqs = _requests(n_requests, cfg.vocab_size, max_new, seed=2)
        t0 = time.perf_counter()
        done = eng.run(reqs)
        walls.append(time.perf_counter() - t0)
        tokens = sum(len(r.out) for r in done)
        cur = [r.out for r in done]
        assert out is None or out == cur, "engine run is not deterministic"
        out = cur
    wall = float(np.median(walls))
    return {
        "wall_s": wall,
        "tokens": tokens,
        "tok_s": tokens / wall,
        "host_syncs": (eng.host_syncs - syncs0) // repeats,
        "syncs_per_token": (eng.host_syncs - syncs0) / max(
            eng.tokens_decoded - toks0, 1),
        "out": out,
        "engine_stats": eng.stats(),
    }


def _run_scheduler(params, cfg, *, policy: str, slots: int, n_requests: int,
                   max_new: int, max_len: int, eng_kw: dict | None = None,
                   deadline_ms: float | None = None):
    """Submit a full queue up front and stream via ``generate()``: measures
    the lifecycle numbers admission policy actually moves — queue wait and
    TTFT — plus end-to-end tok/s. Prompt lengths and priorities are spread
    so fifo/priority/sjf produce genuinely different admission orders.
    ``eng_kw``/``deadline_ms`` arm the resilience layer (the
    ``serve/robust_overhead`` record measures its fault-free cost)."""
    eng = ServeEngine(params, cfg, slots=slots, max_len=max_len, rt=RT,
                      scheduler=policy, **(eng_kw or {}))
    rng = np.random.default_rng(5)

    def make():
        return [Request(rid=i,
                        prompt=rng.integers(0, cfg.vocab_size,
                                            size=4 + (i * 7) % 13),
                        max_new=max_new, priority=i % 3,
                        deadline_ms=deadline_ms)
                for i in range(n_requests)]

    for _ in eng.generate(make()):  # warmup: compile every wave shape
        pass
    reqs = make()
    t0 = time.perf_counter()
    n_events = sum(1 for _ in eng.generate(reqs))
    wall = time.perf_counter() - t0
    tokens = sum(len(r.out) for r in reqs)
    assert n_events == tokens, "one StreamEvent per emitted token"
    ttft = float(np.mean([r.t_first - r.t_submit for r in reqs]))
    queue_wait = float(np.mean([r.t_admit - r.t_submit for r in reqs]))
    return {
        "policy": policy,
        "wall_s": wall,
        "tokens": tokens,
        "tok_s": tokens / wall,
        "ttft_ms": 1e3 * ttft,
        "queue_wait_ms": 1e3 * queue_wait,
    }


def add_fault_records(suite: BenchSuite, params, cfg, *, smoke: bool) -> None:
    """``serve/faults_chaos``: drive the engine through a seeded compound
    failure scenario — KV-scale poisoning, deadline expiry via clock skip,
    a stalled step, queue overflow, and priority preemption — and record
    how every resilience path fired. The record asserts each counter
    actually moved: a resilience path that silently stopped firing is a
    regression even when throughput looks fine."""
    from repro.serve.faults import Fault, FaultClock, FaultPlan, burst

    rtq = Runtime(compute_dtype=jnp.float32, kv_quant=True)
    slots = 4
    n_low, n_high = (4, 3) if smoke else (6, 4)
    max_new = 8 if smoke else 16
    clk = FaultClock()
    eng = ServeEngine(params, cfg, slots=slots, max_len=64, rt=rtq,
                      scheduler="priority", clock=clk, max_queue=slots,
                      shed_policy="shed_lowest", watchdog_timeout_s=0.5)
    # warmup compiles the wave shapes WITHOUT arming faults
    for _ in eng.generate(burst(slots, cfg.vocab_size, seed=8,
                                max_new=max_new)):
        pass
    s0 = eng.decode_steps
    eng.faults = FaultPlan([
        Fault("kv_nan", step=s0 + 2, slot=0),
        Fault("clock_skip", step=s0 + 6, dt=1.0),
        Fault("stall", step=s0 + 6, dt=2.0),
    ], clock=clk)
    counters0 = {k: getattr(eng, k) for k in (
        "quarantined", "deadline_expired", "requests_rejected",
        "requests_shed", "preemptions", "resumes", "stalled_steps")}
    toks0 = eng.tokens_decoded
    # low-priority work first (deadline-carrying), then a queue-filling
    # second wave, then a high-priority burst mid-stream: forces
    # shed_lowest overflow AND should_preempt eviction in one run
    lows = burst(slots, cfg.vocab_size, seed=9, max_new=max_new,
                 rid0=100, priority=0, deadline_ms=400.0)
    lows_q = burst(n_low, cfg.vocab_size, seed=9, max_new=max_new,
                   rid0=150, priority=0, deadline_ms=400.0)
    highs = burst(n_high, cfg.vocab_size, seed=10, max_new=max_new,
                  rid0=200, priority=2)
    t0 = time.perf_counter()
    it = eng.generate(lows)
    for _ in range(slots + 2):  # lows are live, mid-decode
        next(it)
    for r in lows_q + highs:
        eng.submit_request(r)
    for _ in it:
        pass
    wall = time.perf_counter() - t0
    reqs = lows + lows_q + highs
    assert all(r.done for r in reqs), "chaos run left unfinished requests"
    delta = {k: getattr(eng, k) - counters0[k] for k in counters0}
    for k in ("quarantined", "deadline_expired", "stalled_steps"):
        assert delta[k] >= 1, f"chaos scenario never exercised {k}"
    assert delta["requests_rejected"] + delta["requests_shed"] >= 1, \
        "chaos burst never overflowed max_queue"
    assert delta["preemptions"] >= 1, "priority burst never preempted"
    tokens = eng.tokens_decoded - toks0
    suite.add("serve/faults_chaos",
              us_per_call=1e6 * wall / max(tokens, 1),
              tok_s=round(tokens / wall, 2),
              tokens=tokens,
              requests=len(reqs),
              quarantined=delta["quarantined"],
              deadline_expired=delta["deadline_expired"],
              rejected=delta["requests_rejected"],
              shed=delta["requests_shed"],
              preempted=delta["preemptions"],
              resumed=delta["resumes"],
              stalled_steps=delta["stalled_steps"],
              all_terminal=True)


def add_paged_records(suite: BenchSuite, params, cfg, *, smoke: bool) -> None:
    """``serve/paged_*``: dense reservation vs the paged block pool at EQUAL
    cache bytes on a heterogeneous-length burst. The dense engine caps
    concurrency at ``slots`` because every slot reserves ``max_len``
    positions; the paged engine only holds blocks for live tokens, so the
    same bytes serve >= 2x the concurrent requests (the acceptance bar this
    record asserts). Token streams are checked identical request-by-request
    — paging must change capacity, never content."""
    rtq = Runtime(compute_dtype=jnp.float32, kv_quant=True)
    max_len, block_size = 64, 16
    dense_slots = 4
    # equal token capacity: dense reserves 4 x 64 = 256 positions; the pool
    # gets 256 / 16 = 16 usable blocks (+ the reserved null block)
    num_blocks = dense_slots * max_len // block_size + 1
    paged_slots = 16
    n = 12 if smoke else 24

    def reqs():
        # per-request tokens (plen + max_new) <= 15: one block each, so the
        # pool can host paged_slots concurrent requests without thrashing
        rng = np.random.default_rng(11)
        return [Request(rid=i,
                        prompt=rng.integers(0, cfg.vocab_size,
                                            size=3 + i % 7).astype(np.int32),
                        max_new=6) for i in range(n)]

    def bench(paged: bool):
        kw = dict(paged=True, num_blocks=num_blocks,
                  block_size=block_size) if paged else {}
        eng = ServeEngine(params, cfg, slots=paged_slots if paged
                          else dense_slots, max_len=max_len, rt=rtq, **kw)
        eng.run(reqs())  # warmup: compile every wave shape
        eng.max_concurrent = 0
        peak_util = 0.0
        batch = reqs()
        t0 = time.perf_counter()
        for _ in eng.generate(batch):
            if paged:
                peak_util = max(peak_util, eng.pool.utilization())
        wall = time.perf_counter() - t0
        st = eng.stats()
        return {"wall_s": wall,
                "tokens": sum(len(r.out) for r in batch),
                "out": {r.rid: list(r.out) for r in batch},
                "max_concurrent": st["max_concurrent"],
                "cache_bytes": st["cache_bytes"],
                "pool_utilization": round(peak_util, 4) if paged else 1.0,
                "stats": st}

    dense = bench(paged=False)
    paged = bench(paged=True)
    assert paged["out"] == dense["out"], \
        "paged engine token streams diverged from dense"
    assert paged["max_concurrent"] >= 2 * dense["max_concurrent"], (
        f"paged concurrency {paged['max_concurrent']} is not >= 2x dense "
        f"{dense['max_concurrent']} at equal cache bytes")
    for name, r, extra in (
            ("serve/paged_dense_baseline", dense,
             dict(slots=dense_slots)),
            ("serve/paged_pool", paged,
             dict(slots=paged_slots, block_size=block_size,
                  pool_blocks=num_blocks - 1,
                  blocks_swapped=paged["stats"]["blocks_swapped"],
                  prefix_hits=paged["stats"]["prefix_hits"],
                  concurrency_vs_dense=round(
                      paged["max_concurrent"]
                      / max(dense["max_concurrent"], 1), 2)))):
        suite.add(name,
                  us_per_call=1e6 * r["wall_s"] / max(r["tokens"], 1),
                  tok_s=round(r["tokens"] / r["wall_s"], 2),
                  wall_s=round(r["wall_s"], 3),
                  tokens=r["tokens"],
                  requests=n,
                  max_concurrent=r["max_concurrent"],
                  pool_utilization=r["pool_utilization"],
                  cache_bytes=r["cache_bytes"],
                  tokens_match=True,
                  **extra)


def add_spec_records(suite: BenchSuite, cfg, *, smoke: bool) -> None:
    """``serve/spec_*``: speculative decoding on an acceptance-friendly
    pair. The target's layers >= 1 get ZERO residual projections (wo/down)
    — each is an exact passthrough, so the 1-layer self-draft computes the
    target's logits and greedy acceptance sits at ~100%. That is the
    honest upper-bound workload for the propose/verify/commit pipeline:
    it isolates the pipeline's speedup (draft steps are cheap, one batched
    verify replaces K+1 decode ticks) from draft quality, which is a
    model-training question, not a serving one. Token parity with the
    non-speculative engine is asserted — a speedup that changes greedy
    output is a bug, not a result."""
    from repro.serve import spec as spec_mod

    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    layers = {k: dict(v) if isinstance(v, dict) else v
              for k, v in params["layers"].items()}
    layers["attn"]["wo"] = layers["attn"]["wo"].at[1:].set(0.0)
    layers["mlp"]["down"] = layers["mlp"]["down"].at[1:].set(0.0)
    params = dict(params, layers=layers)
    qparams = quantize_params(params, "itq3_s")
    draft, dcfg = spec_mod.draft_from_params(qparams, cfg, 1)

    rtq = Runtime(compute_dtype=jnp.float32, kv_quant=True)
    slots = 4
    # long decodes: the speedup under measurement is the DECODE pipeline's;
    # admission prefill (identical work on both sides) must not dilute it
    n, max_new, max_len, k = ((4, 12, 64, 4) if smoke
                              else (8, 96, 128, 8))

    def reqs():
        rng = np.random.default_rng(17)
        return [Request(rid=i,
                        prompt=rng.integers(0, cfg.vocab_size,
                                            size=4 + i % 5).astype(np.int32),
                        max_new=max_new) for i in range(n)]

    def bench(spec_on: bool):
        kw = dict(draft_params=draft, draft_cfg=dcfg,
                  num_draft_tokens=k) if spec_on else {}
        eng = ServeEngine(qparams, cfg, slots=slots, max_len=max_len,
                          rt=rtq, **kw)
        eng.run(reqs())  # warmup: compile every wave shape
        batch = reqs()
        t0 = time.perf_counter()
        eng.run(batch)
        wall = time.perf_counter() - t0
        st = eng.stats()
        return {"wall_s": wall,
                "tokens": sum(len(r.out) for r in batch),
                "out": {r.rid: list(r.out) for r in batch},
                "stats": st}

    base = bench(spec_on=False)
    spec_r = bench(spec_on=True)
    assert spec_r["out"] == base["out"], \
        "greedy speculative streams diverged from the non-speculative engine"
    st = spec_r["stats"]
    speedup = (base["wall_s"] / spec_r["wall_s"])
    assert st["acceptance_rate"] >= 0.9, (
        f"no-op-tail self-draft should verify ~always, got "
        f"{st['acceptance_rate']:.1%}")
    if not smoke:  # smoke batches are too small for stable wall-clock
        assert speedup >= 1.5, (
            f"speculative decode speedup {speedup:.2f}x < 1.5x on the "
            f"acceptance-friendly workload")
    suite.add("serve/spec_baseline",
              us_per_call=1e6 * base["wall_s"] / max(base["tokens"], 1),
              tok_s=round(base["tokens"] / base["wall_s"], 2),
              wall_s=round(base["wall_s"], 3),
              tokens=base["tokens"],
              acceptance_rate=0.0,
              tokens_per_step=1.0,
              slots=slots)
    suite.add("serve/spec_selfdraft",
              us_per_call=1e6 * spec_r["wall_s"] / max(spec_r["tokens"], 1),
              tok_s=round(spec_r["tokens"] / spec_r["wall_s"], 2),
              wall_s=round(spec_r["wall_s"], 3),
              tokens=spec_r["tokens"],
              acceptance_rate=round(st["acceptance_rate"], 4),
              tokens_per_step=round(st["tokens_per_step"], 3),
              speedup_vs_baseline=round(speedup, 3),
              draft_layers=1,
              num_draft_tokens=k,
              spec_steps=st["spec_steps"],
              tokens_match=True,
              slots=slots)


_TP_SCRIPT = textwrap.dedent("""
    import json, time
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs.base import get_config, reduced
    from repro.models import lm
    from repro.models.layers import Runtime
    from repro.serve.engine import Request, ServeEngine
    from repro.serve.quantized import quantize_params
    from repro.launch.mesh import make_host_mesh

    smoke = {smoke}
    cfg = reduced(get_config("qwen1.5-0.5b"))  # kv=4: head-sharded cache
    params = quantize_params(lm.init_params(jax.random.PRNGKey(0), cfg),
                             "itq3_s")
    rt = Runtime(compute_dtype=jnp.float32, kv_quant=True)
    n_requests, max_new = (4, 8) if smoke else (8, 24)

    def reqs(seed):
        rng = np.random.default_rng(seed)
        return [Request(rid=i,
                        prompt=rng.integers(0, cfg.vocab_size, size=6 + i % 5),
                        max_new=max_new) for i in range(n_requests)]

    def bench(mesh, sm):
        eng = ServeEngine(params, cfg, slots=4, max_len=64, rt=rt,
                          mesh=mesh, tp_shard_map=sm)
        eng.run(reqs(1))  # warmup: compile every wave shape
        t0 = time.perf_counter()
        done = eng.run(reqs(2))
        wall = time.perf_counter() - t0
        tokens = sum(len(r.out) for r in done)
        st = eng.stats()
        return {{"wall_s": wall, "tokens": tokens, "tok_s": tokens / wall,
                 "cache_bytes": st["cache_bytes"],
                 "cache_bytes_per_device": st.get("cache_bytes_per_device",
                                                  st["cache_bytes"]),
                 "out": [list(r.out) for r in done]}}

    base = bench(None, None)
    mesh = make_host_mesh(1, 2)
    tp_sm = bench(mesh, True)
    tp_gspmd = bench(mesh, False)
    for r in (tp_sm, tp_gspmd):
        assert r["out"] == base["out"], "TP stream diverged from baseline"
        r["devices"] = mesh.devices.size
    for r in (base, tp_sm, tp_gspmd):
        r.pop("out")
    print("TPBENCH " + json.dumps(
        {{"single": base, "shard_map": tp_sm, "gspmd": tp_gspmd}}))
""")


def _require_cpu() -> None:
    """The serve suite's TP phase starts a JAX child process. On a chip
    host this process already holds the chip, so the child could not get
    it: refuse before any work instead of hanging."""
    backend = jax.default_backend()
    if backend != "cpu":
        raise RuntimeError(
            f"benchmarks.serve_bench runs its tensor-parallel phase in a "
            f"forced-host-device child process, which only works when this "
            f"process runs on the CPU; it runs on {backend!r}, whose chip "
            f"this process holds. Run it with JAX_PLATFORMS=cpu.")


def add_tp_records(suite: BenchSuite, *, smoke: bool) -> None:
    """``serve/tp*`` records: 2-forced-host-device run of the mesh engine
    (shard_map and GSPMD paths) against the single-device baseline, token
    parity asserted inside the subprocess. Forced host devices measure
    PLUMBING overhead on CPU (a 1-core container shows TP as pure cost) —
    the record's job is tracking that overhead and the per-device cache
    split, not projecting TPU scaling. CPU only: see :func:`_require_cpu`."""
    _require_cpu()
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=2 "
                        + env.get("XLA_FLAGS", "")).strip()
    env["PYTHONPATH"] = (str(repo_root() / "src") + os.pathsep
                         + env.get("PYTHONPATH", "")).rstrip(os.pathsep)
    res = subprocess.run(
        [sys.executable, "-c", _TP_SCRIPT.format(smoke=smoke)],
        capture_output=True, text=True, timeout=1800, env=env)
    line = next((ln for ln in res.stdout.splitlines()
                 if ln.startswith("TPBENCH ")), None)
    if line is None:
        raise RuntimeError(f"tp bench subprocess failed:\n"
                           f"{res.stdout}\n{res.stderr}")
    data = json.loads(line[len("TPBENCH "):])
    for name, rec in (("serve/tp_single_device", data["single"]),
                      ("serve/tp_shard_map", data["shard_map"]),
                      ("serve/tp_gspmd", data["gspmd"])):
        suite.add(name,
                  us_per_call=1e6 * rec["wall_s"] / max(rec["tokens"], 1),
                  tok_s=round(rec["tok_s"], 2),
                  wall_s=round(rec["wall_s"], 3),
                  tokens=rec["tokens"],
                  cache_bytes_per_device=rec["cache_bytes_per_device"],
                  cache_bytes=rec["cache_bytes"],
                  devices=rec.get("devices", 1),
                  tokens_match=True)


def main(smoke: bool = False) -> None:
    _require_cpu()
    suite = BenchSuite("serve", smoke=smoke)
    # machine-speed stamp: the SLO gate on FUTURE runs divides out this
    # machine's speed relative to whoever committed the trajectory
    suite.add(CALIBRATION_RECORD, wall_ms=round(calibration_wall_ms(), 3))
    cfg = reduced(get_config("smollm-135m"))
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    qparams = quantize_params(params, "itq3_s")

    slots = 4
    n_requests = 4 if smoke else 8
    max_new = 8 if smoke else 24
    max_len = 64

    results = {}
    for mode in ("host", "device"):
        r = _run_mode(qparams, cfg, sample_on_host=(mode == "host"),
                      slots=slots, n_requests=n_requests, max_new=max_new,
                      max_len=max_len, repeats=1 if smoke else 3)
        results[mode] = r
        suite.add(f"serve/decode_{mode}_sampling",
                  us_per_call=1e6 * r["wall_s"] / max(r["tokens"], 1),
                  tok_s=round(r["tok_s"], 2),
                  wall_s=round(r["wall_s"], 3),
                  tokens=r["tokens"],
                  host_syncs=r["host_syncs"],
                  syncs_per_token=round(r["syncs_per_token"], 3),
                  slots=slots)

    if results["host"]["out"] != results["device"]["out"]:
        raise AssertionError("greedy decode diverged between sampling modes")
    host, dev = results["host"], results["device"]
    suite.add("serve/device_vs_host",
              speedup_wall=round(host["wall_s"] / dev["wall_s"], 3),
              syncs_reduction=round(
                  host["syncs_per_token"] / max(dev["syncs_per_token"], 1e-9),
                  2),
              tokens_match=True)

    # donated decode cache: the per-step functional copy must be GONE —
    # a nonzero bytes-moved counter means jit stopped donating in place
    est = dev["engine_stats"]
    if est["cache_bytes_moved"] != 0:
        raise AssertionError(
            f"decode cache copied {est['cache_bytes_moved']} bytes over "
            f"{est['decode_steps']} steps: donation did not engage")
    suite.add("serve/cache_donation",
              donated=bool(est["cache_donated"]),
              bytes_moved=est["cache_bytes_moved"],
              decode_steps=est["decode_steps"],
              cache_bytes=est["cache_bytes"])

    # request-lifecycle scheduling: queue wait / TTFT / tok/s per policy
    sched = {}
    for policy in ("fifo", "priority", "sjf"):
        r = _run_scheduler(qparams, cfg, policy=policy, slots=slots,
                           n_requests=2 * n_requests, max_new=max_new,
                           max_len=max_len)
        sched[policy] = r
        suite.add(f"serve/sched_{policy}",
                  us_per_call=1e6 * r["wall_s"] / max(r["tokens"], 1),
                  policy=policy,
                  ttft_ms=round(r["ttft_ms"], 2),
                  queue_wait_ms=round(r["queue_wait_ms"], 2),
                  tok_s=round(r["tok_s"], 2),
                  tokens=r["tokens"],
                  slots=slots)

    # fault-free cost of the resilience layer: same fifo workload with
    # deadlines armed, a bounded queue, and the watchdog on — the deadline
    # and finiteness checks ride existing transfers, so this should be
    # noise-level (the record tracks that claim across PRs)
    rr = _run_scheduler(
        qparams, cfg, policy="fifo", slots=slots, n_requests=2 * n_requests,
        max_new=max_new, max_len=max_len,
        eng_kw=dict(max_queue=8 * n_requests, watchdog_timeout_s=60.0),
        deadline_ms=600_000.0)
    assert rr["tokens"] == sched["fifo"]["tokens"], \
        "resilience knobs changed fault-free token output"
    suite.add("serve/robust_overhead",
              tok_s_base=round(sched["fifo"]["tok_s"], 2),
              tok_s_resilient=round(rr["tok_s"], 2),
              overhead_ratio=round(
                  sched["fifo"]["tok_s"] / max(rr["tok_s"], 1e-9), 3),
              tokens=rr["tokens"],
              tokens_match=True)

    add_fault_records(suite, qparams, cfg, smoke=smoke)
    add_paged_records(suite, qparams, cfg, smoke=smoke)
    add_spec_records(suite, cfg, smoke=smoke)
    add_tp_records(suite, smoke=smoke)

    from benchmarks.attn_bench import add_serve_records
    add_serve_records(suite, smoke=smoke)

    # the serving-SLO gate: a full run must not regress the committed
    # scheduler trajectory beyond tolerance BEFORE it overwrites it (smoke
    # runs are sized differently and never gate)
    committed = repo_root() / "BENCH_serve.json"
    if not smoke and committed.exists():
        assert_no_slo_regression(committed, suite.records, require_all=True)

    suite.write()


if __name__ == "__main__":
    main()
