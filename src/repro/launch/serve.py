"""Serving launcher: quantize a model with a format or QuantPolicy and run
batched inference through the continuous-batching engine.

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m --reduced \
        --fmt itq3_s --requests 8

Mixed-precision serving via a policy (the arch's default recipe, or any
JSON file with {"rules": [{"pattern": ..., "fmt": ...}, ...]}):

    ... --policy mixed                 # configs.base.mixed_precision_recipe
    ... --policy recipes/my_policy.json

The quantized tree can be checkpointed and served straight from disk
(packed planes + QMeta; Algorithm 1 runs once, offline):

    ... --policy mixed --save-quantized /tmp/qckpt     # quantize + save
    ... --load-quantized /tmp/qckpt                    # boot from planes

Optionally restores trained weights from a checkpoint directory (as written
by launch/train.py) before quantizing — the full offline pipeline of the
paper: train/load fp weights -> Algorithm 1 -> deploy packed planes.

Request-lifecycle serving (PR 4): per-request sampling knobs
(``--temperature/--top-k/--top-p/--sampling-seed/--stop-token``), pluggable
admission policy (``--scheduler fifo|priority|sjf``), and ``--stream`` to
print StreamEvents (finish reason, TTFT, queue wait) as requests complete
instead of waiting for the closed batch.

Failure-hardened serving (PR 7): ``--max-queue``/``--shed-policy`` bound
admission (overflow -> terminal ``rejected`` events), ``--deadline-ms``
arms per-request deadlines, ``--watchdog-timeout-s`` counts stalled decode
steps, and ``--chaos`` runs the whole thing under a seeded
``serve/faults.py`` FaultPlan (KV-scale poison + clock skip + stall) to
demo that every failure mode drains to a terminal finish reason:

    ... --reduced --kv-quant --chaos --stream --scheduler priority \
        --max-queue 4 --shed-policy shed_lowest

Speculative decoding (PR 10): ``--draft-depth N`` serves with an N-layer
self-draft (a prefix of the target sharing embedding/head weights) and a
``--num-draft-tokens``-wide propose/verify/commit window per decode step;
the run report adds acceptance rate and mean committed tokens/step:

    ... --reduced --kv-quant --draft-depth 2 --num-draft-tokens 4
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import ckpt as ckpt_mod
from repro.compile_cache import setup_compile_cache
from repro.configs.base import get_config, mixed_precision_recipe, reduced as reduced_cfg
from repro.models import lm
from repro.models.layers import Runtime
from repro.serve.engine import Request, SamplingParams, ServeEngine
from repro.serve.quantized import (
    QuantPolicy, describe_quantized, quantize_params, quantized_bytes,
)
from repro.serve.scheduler import SCHEDULERS
from repro.train import loop as train_loop


def _load_policy(spec: str, cfg) -> QuantPolicy:
    if spec == "mixed":
        return QuantPolicy.from_dict(mixed_precision_recipe(cfg))
    with open(spec) as f:
        return QuantPolicy.from_dict(json.load(f))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--fmt", default="itq3_s")
    ap.add_argument("--rule", default="paper")
    ap.add_argument("--policy", default=None,
                    help="'mixed' or path to a QuantPolicy JSON; overrides --fmt")
    ap.add_argument("--quant-mode", default="activations",
                    choices=["activations", "weights", "dequant", "auto"])
    ap.add_argument("--backend", default="auto",
                    choices=["auto", "ref", "pallas"])
    ap.add_argument("--ckpt-dir", default=None,
                    help="restore fp train-state weights before quantizing")
    ap.add_argument("--save-quantized", default=None,
                    help="write the quantized param tree as a checkpoint")
    ap.add_argument("--load-quantized", default=None,
                    help="serve a previously saved quantized checkpoint")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy (default); >0 samples on device")
    ap.add_argument("--top-k", type=int, default=0,
                    help="top-k filter (0 = disabled)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus (top-p) filter (1.0 = disabled)")
    ap.add_argument("--sampling-seed", type=int, default=None,
                    help="per-request PRNG seed base (request i uses seed+i); "
                         "default derives deterministic keys from rid")
    ap.add_argument("--stop-token", type=int, action="append", default=None,
                    help="stop-token id finishing a request early "
                         "(repeatable)")
    ap.add_argument("--scheduler", default="fifo", choices=sorted(SCHEDULERS),
                    help="admission policy: fifo | priority (Request."
                         "priority, demoed with rid%%3) | sjf "
                         "(shortest-prompt-first)")
    ap.add_argument("--stream", action="store_true",
                    help="print StreamEvents as tokens arrive instead of "
                         "waiting for the closed batch")
    ap.add_argument("--autotune", action="store_true",
                    help="benchmark kernel tile sizes for this model's "
                         "shapes on boot (TPU only; no-op in interpret mode)")
    ap.add_argument("--tile-m", type=int, default=None,
                    help="explicit Pallas tile override (else autotune cache)")
    ap.add_argument("--tile-n", type=int, default=None)
    ap.add_argument("--sample-on-host", action="store_true",
                    help="pre-overhaul per-slot host argmax (baseline mode)")
    ap.add_argument("--kv-quant", action="store_true",
                    help="rotated-int8 KV cache (8.25 bits/element; fused "
                         "Pallas decode attention on TPU, einsum fallback "
                         "elsewhere)")
    ap.add_argument("--paged", action="store_true",
                    help="paged KV cache: block-pool allocator + per-slot "
                         "block table over the rotated-int8 planes "
                         "(requires --kv-quant; concurrency bounded by live "
                         "tokens instead of slots x max_len reservation)")
    ap.add_argument("--num-blocks", type=int, default=None,
                    help="pool size for --paged (default: enough for every "
                         "slot to reach max_len, i.e. dense-equivalent)")
    ap.add_argument("--block-size", type=int, default=16,
                    help="tokens per pool block for --paged")
    ap.add_argument("--act-quant", action="store_true",
                    help="W3A8 integer compute path: quantize activations "
                         "to int8 in the rotation domain and contract "
                         "against ternary codes with int32 accumulation "
                         "(QuantPolicy act_quant=False pins paths to float)")
    ap.add_argument("--mesh", default=None, metavar="DATA,MODEL",
                    help="tensor-parallel serving over a data,model device "
                         "mesh (e.g. --mesh 1,2: packed ITQ3_S planes "
                         "column-sharded and KV cache head-sharded over the "
                         "model axis; clamped to available devices)")
    ap.add_argument("--tp-shard-map", action="store_true",
                    help="force explicit shard_map over the quantized "
                         "kernels instead of GSPMD-partitioned jit (the "
                         "automatic default on real TPU, where GSPMD cannot "
                         "split a pallas_call)")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="bound the waiting queue; overflow follows "
                         "--shed-policy (terminal 'rejected' events)")
    ap.add_argument("--shed-policy", default="reject",
                    choices=["reject", "shed_lowest"],
                    help="queue-overflow policy: turn the newcomer away, or "
                         "drop the lowest-priority waiting request instead")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request submit->done deadline; expired "
                         "requests finish with finish_reason='deadline'")
    ap.add_argument("--watchdog-timeout-s", type=float, default=None,
                    help="arm the decode-step watchdog: steps slower than "
                         "this are counted in stats()['stalled_steps']")
    ap.add_argument("--chaos", action="store_true",
                    help="serve under a seeded FaultPlan (KV-scale poison + "
                         "clock skip + stall): demos quarantine/deadline/"
                         "watchdog draining to terminal events")
    ap.add_argument("--chaos-seed", type=int, default=0)
    ap.add_argument("--draft-depth", type=int, default=0,
                    help="speculative decoding with a self-draft: serve "
                         "with an N-layer prefix of the target as the "
                         "draft model (0 = off). The decode tick becomes "
                         "propose/verify/commit; greedy streams stay "
                         "bit-identical to non-speculative serving")
    ap.add_argument("--num-draft-tokens", type=int, default=4,
                    help="speculative window size K: draft proposes K "
                         "tokens per slot per step, one batched target "
                         "pass verifies all K+1 positions")
    args = ap.parse_args()
    setup_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced_cfg(cfg)
    mesh = None
    if args.mesh:
        from repro.launch.mesh import make_host_mesh
        d, m = (int(x) for x in args.mesh.split(","))
        mesh = make_host_mesh(d, m)
        print(f"serving mesh: {dict(mesh.shape)} "
              f"({mesh.devices.size} devices)")
    rt = Runtime(compute_dtype=jnp.float32, quant_mode=args.quant_mode,
                 backend=args.backend, autotune=args.autotune,
                 tile_m=args.tile_m, tile_n=args.tile_n,
                 kv_quant=args.kv_quant, act_quant=args.act_quant)

    if args.load_quantized:
        t0 = time.time()
        shardings = None
        if mesh is not None:
            # restore-to-sharding: each packed plane goes straight to its
            # column shard as it loads off disk
            from repro.serve import tp as tp_mod
            shardings = tp_mod.restore_shardings(cfg, mesh)
        params, step = ckpt_mod.restore_params(args.load_quantized,
                                               shardings=shardings)
        print(f"loaded quantized step-{step} tree from {args.load_quantized} "
              f"in {time.time()-t0:.1f}s ({quantized_bytes(params)/1e6:.1f}MB)")
    else:
        key = jax.random.PRNGKey(0)
        params = lm.init_params(key, cfg)
        if args.ckpt_dir:
            state = train_loop.init_train_state(key, cfg)
            state, step = ckpt_mod.restore(args.ckpt_dir, state)
            params = state.params
            print(f"restored step-{step} weights from {args.ckpt_dir}")

        fp_bytes = sum(np.prod(x.shape) * 2 for x in jax.tree.leaves(params))
        t0 = time.time()
        if args.policy:
            policy = _load_policy(args.policy, cfg)
            params = quantize_params(params, policy)
            fmts = sorted(set(describe_quantized(params).values()))
            print(f"policy quantized ({len(policy.rules)} rules -> {fmts})")
        elif args.fmt not in ("fp16", "bf16"):
            params = quantize_params(params, args.fmt, rule=args.rule)
        qb = quantized_bytes(params)
        print(f"quantized in {time.time()-t0:.1f}s: "
              f"{qb/1e6:.1f}MB vs bf16 {fp_bytes/1e6:.1f}MB "
              f"({fp_bytes/max(qb,1):.2f}x smaller)")
        if args.save_quantized:
            path = ckpt_mod.save(args.save_quantized, 0, params)
            print(f"saved quantized tree to {path}")

    faults = None
    if args.chaos:
        from repro.serve.faults import Fault, FaultPlan
        faults = FaultPlan([
            Fault("kv_nan", step=3, slot=0,
                  plane="k_scale" if args.kv_quant else "k"),
            Fault("clock_skip", step=6, dt=1.0),
            Fault("stall", step=6, dt=2.0),
        ], seed=args.chaos_seed)
        if args.watchdog_timeout_s is None:
            args.watchdog_timeout_s = 0.5
        if args.deadline_ms is None:
            args.deadline_ms = 400.0
        print(f"chaos mode: {len(faults.faults)} seeded faults armed "
              f"(seed {args.chaos_seed}, deterministic clock)")
    draft_kw = {}
    if args.draft_depth:
        from repro.serve import spec as spec_mod
        dparams, dcfg = spec_mod.draft_from_params(params, cfg,
                                                   args.draft_depth)
        draft_kw = dict(draft_params=dparams, draft_cfg=dcfg,
                        num_draft_tokens=args.num_draft_tokens)
        print(f"speculative decoding: {args.draft_depth}-layer self-draft, "
              f"K={args.num_draft_tokens} tokens/window")
    eng = ServeEngine(params, cfg, slots=args.slots, max_len=args.max_len,
                      rt=rt, temperature=args.temperature,
                      sample_on_host=args.sample_on_host,
                      scheduler=args.scheduler, mesh=mesh,
                      tp_shard_map=True if args.tp_shard_map else None,
                      max_queue=args.max_queue, shed_policy=args.shed_policy,
                      watchdog_timeout_s=args.watchdog_timeout_s,
                      faults=faults, paged=args.paged,
                      num_blocks=args.num_blocks, block_size=args.block_size,
                      **draft_kw)
    if args.kv_quant:
        print(f"kv_quant cache: {eng.cache_bytes/1e6:.1f}MB "
              f"({eng.stats()['cache_bytes_per_token']:.0f} B/token)")
    if args.paged:
        st0 = eng.stats()
        print(f"paged pool: {st0['pool_blocks']} blocks x "
              f"{st0['block_size']} tokens "
              f"({st0['cache_bytes_reserved']/1e6:.2f}MB reserved)")
    if args.act_quant:
        print("act_quant: W3A8 integer compute path "
              "(int8 rotation-domain activations, int32 accumulation)")
    if mesh is not None:
        st0 = eng.stats()
        print(f"tp cache: {st0['cache_bytes_per_device']/1e6:.2f}MB/device "
              f"x {st0['devices']} devices "
              f"(shard_map={'on' if st0['tp_shard_map'] else 'off'})")
    rng = np.random.default_rng(0)
    reqs = []
    for i in range(args.requests):
        sp = SamplingParams(
            temperature=args.temperature, top_k=args.top_k, top_p=args.top_p,
            seed=None if args.sampling_seed is None else args.sampling_seed + i,
            stop=tuple(args.stop_token or ()))
        reqs.append(Request(
            rid=i, prompt=rng.integers(0, cfg.vocab_size, size=8 + i % 5),
            max_new=args.max_new, sampling=sp,
            priority=i % 3 if args.scheduler == "priority" else 0,
            deadline_ms=args.deadline_ms))
    t0 = time.time()
    if args.stream:
        for ev in eng.generate(reqs):
            if ev.finished:
                st = ev.stats or {}
                print(f"  rid={ev.rid} finished [{ev.finish_reason}] "
                      f"{st.get('tokens', 0)} tokens, "
                      f"ttft {st.get('ttft_s', float('nan'))*1e3:.0f}ms, "
                      f"queue {st.get('queue_wait_s', 0)*1e3:.0f}ms")
        done = reqs
    else:
        done = eng.run(reqs)
    dt = time.time() - t0
    total_tokens = sum(len(r.out) for r in done)
    st = eng.stats()
    print(f"served {len(done)} requests / {total_tokens} tokens "
          f"in {dt:.2f}s ({total_tokens/dt:.1f} tok/s on {jax.default_backend()}, "
          f"{st['syncs_per_token']:.2f} host syncs/token, "
          f"scheduler={st['scheduler']}, "
          f"cache bytes moved {st['cache_bytes_moved']})")
    if args.draft_depth:
        print(f"speculation: acceptance {st['acceptance_rate']:.1%} "
              f"({st['draft_accepted']}/{st['draft_proposed']} drafts), "
              f"{st['tokens_per_step']:.2f} tokens/step over "
              f"{st['spec_steps']} windows")
    resil = {k: st[k] for k in ("quarantined", "deadline_expired",
                                "requests_rejected", "requests_shed",
                                "preemptions", "stalled_steps") if st.get(k)}
    if resil or args.chaos:
        from collections import Counter
        reasons = Counter(r.finish_reason for r in done)
        print(f"resilience: {resil or 'no faults fired'}; "
              f"finish reasons {dict(reasons)}")
        if faults is not None:
            print(f"fault log: {faults.log}")
    for r in done[:3]:
        print(f"  rid={r.rid} -> {r.out[:10]}")


if __name__ == "__main__":
    main()
