#!/usr/bin/env python3
"""Smoke run of the ITQ3_S serving path on TPU: qwen1.5-0.5b at its
published widths, random ``itq3_s`` weights from ``--seed``.

    python chip_smoke.py              # one chip: kernels, then serving
    python chip_smoke.py --chips 4    # tensor-parallel engine vs one chip

One chip: the Pallas ITQ3_S kernels are checked against the jnp reference
at the model's projection shapes, then ``ServeEngine`` (paged rotated-int8
cache, ``backend="auto"``) serves 8 greedy requests through ``generate``
and its outputs are checked. Four chips: only the tensor-parallel engine
runs, and its greedy streams must equal a one-chip engine's.

Every check that fails raises; nothing is caught. The run exits non-zero
on any platform other than ``tpu``. The last line of stdout is one JSON
object, ``{"ok": true, "device": {...}}``. Times printed here include
compilation: they are smoke-run set-up times, not measurements.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import re
import sys
import time
from importlib import metadata

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.compile_cache import setup_compile_cache  # noqa: E402
from repro.configs.base import get_config  # noqa: E402
from repro.core import formats  # noqa: E402
from repro.core.qlinear import qmatmul  # noqa: E402
from repro.kernels.ops import auto_interpret  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.models import lm  # noqa: E402
from repro.models.layers import Runtime  # noqa: E402
from repro.serve import paged as paged_mod  # noqa: E402
from repro.serve.engine import Request, ServeEngine  # noqa: E402
from repro.serve.quantized import quantize_params  # noqa: E402

ARCH = "qwen1.5-0.5b"
SLOTS, MAX_LEN, MAX_NEW, BLOCK_SIZE = 8, 2048, 32, 16
PROMPT_LENS = (16, 48, 100, 200, 333, 512, 777, 1024)
# Relative L2 of the Pallas kernels against the jnp reference (computed at
# "highest" matmul precision). Both contract the same ITQ3_S codes; the gap
# is MXU rounding of f32 operands, ~1e-3 at these widths.
KERNEL_TOL = 1e-2
# Prefill logits of the engine's path (Pallas matmuls + paged attention
# kernel) against lm.forward with backend="ref" over the same int8 KV codec:
# the kernel gaps above, carried through 24 layers.
LOGITS_TOL = 5e-2


def check(ok, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def rel_l2(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def finite(a) -> bool:
    return bool(np.isfinite(np.asarray(a)).all())


def kernel_phase(seed: int) -> None:
    """qmatmul backend="pallas" vs "ref" at the model's (K, N) shapes, on
    the matvec (M <= 16) and tiled (M > 16) kernels, float path in both
    rotation modes and the int8 (W3A8) path."""
    cfg = get_config(ARCH)
    d, f = cfg.d_model, cfg.d_ff
    rng = np.random.default_rng(seed)
    for k, n in ((d, d), (d, f), (f, d)):
        w = jnp.asarray(rng.normal(size=(k, n)) / np.sqrt(k), jnp.float32)
        qt = formats.quantize(w, "itq3_s")
        for m in (8, 256):
            x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
            for mode, act in (("activations", False), ("weights", False),
                              ("activations", True)):
                kw = dict(mode=mode, compute_dtype=jnp.float32,
                          act_quant=act)
                got = qmatmul(x, qt, backend="pallas", **kw)
                with jax.default_matmul_precision("highest"):
                    want = qmatmul(x, qt, backend="ref", **kw)
                err = rel_l2(got, want)
                name = (f"qmatmul K={k} N={n} M={m} "
                        f"{'int8' if act else mode}")
                print(f"kernel {name}: rel_l2 {err:.3e} (tol {KERNEL_TOL})",
                      flush=True)
                check(got.shape == (m, n) and finite(got), f"{name} finite")
                check(err <= KERNEL_TOL, f"{name} rel_l2 {err}")


def custom_call_kernels(compiled) -> set[str]:
    """Names of the Pallas kernels a compiled program calls (the jitted
    wrapper each ``tpu_custom_call`` was traced under)."""
    names = set()
    for line in compiled.as_text().splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            names.update(re.findall(r"jit\((\w+_pallas)\)", line))
    return names


def make_params(seed: int):
    cfg = get_config(ARCH)
    # one compiled program instead of ~1k eagerly dispatched ones
    build = jax.jit(lambda key: quantize_params(lm.init_params(key, cfg),
                                                "itq3_s"))
    return jax.block_until_ready(build(jax.random.PRNGKey(seed))), cfg


def make_engine(params, cfg, mesh=None):
    """The engine as ``launch/serve.py --kv-quant --paged`` builds it."""
    rt = Runtime(compute_dtype=jnp.float32, quant_mode="activations",
                 backend="auto", kv_quant=True)
    return ServeEngine(params, cfg, slots=SLOTS, max_len=MAX_LEN, rt=rt,
                       mesh=mesh, tp_shard_map=True if mesh else None,
                       paged=True, block_size=BLOCK_SIZE)


def make_requests(vocab: int, seed: int):
    rng = np.random.default_rng(seed)
    return [Request(rid=i, prompt=rng.integers(0, vocab, size=p,
                                               dtype=np.int32),
                    max_new=MAX_NEW)
            for i, p in enumerate(PROMPT_LENS)]


def serve(eng, reqs) -> list[list[int]]:
    """Drive ``generate`` and check every request's terminal state."""
    finished = {}
    for ev in eng.generate(reqs):
        if ev.finished:
            finished[ev.rid] = ev.finish_reason
    vocab = eng.cfg.vocab_size
    for r in reqs:
        check(finished.get(r.rid) == "length" == r.finish_reason,
              f"rid {r.rid} finished {finished.get(r.rid)!r}")
        check(len(r.out) == MAX_NEW, f"rid {r.rid}: {len(r.out)} tokens")
        check(all(0 <= t < vocab for t in r.out), f"rid {r.rid} token ids")
    st = eng.stats()
    check(st["quarantined"] == 0, "no slot saw non-finite logits")
    eng.pool.check()
    return [list(map(int, r.out)) for r in reqs]


def decode_and_prefill_kernels(eng) -> tuple[set[str], set[str]]:
    """Compile the engine's model step (its own Runtime, params and paged
    cache) at decode and prefill shapes and list the Pallas kernels each
    calls."""
    cfg, rt = eng.cfg, eng.rt
    table = jnp.zeros((SLOTS, -(-MAX_LEN // BLOCK_SIZE)), jnp.int32)

    def decode(params, cache, toks, pos, tbl):
        return lm.decode_step(params, toks, {**cache, "table": tbl}, pos,
                              rt, cfg)[0]

    def prefill(params, cache, toks, tbl):
        return lm.forward(params, toks, rt, cfg, cache={**cache, "table": tbl},
                          pos=jnp.zeros(toks.shape[0], jnp.int32),
                          last_idx=jnp.zeros(toks.shape[0], jnp.int32))[0]

    toks = jnp.zeros((SLOTS, 1), jnp.int32)
    dec = jax.jit(decode).lower(eng.params, eng.cache, toks,
                                jnp.zeros(SLOTS, jnp.int32), table).compile()
    pre = jax.jit(prefill).lower(eng.params, eng.cache,
                                 jnp.zeros((SLOTS, 64), jnp.int32),
                                 table).compile()
    return custom_call_kernels(dec), custom_call_kernels(pre)


def prefill_logits_phase(eng, prompt) -> float:
    """Last-token prefill logits of the engine's path (its Runtime over a
    paged cache) against ``lm.forward`` with backend="ref" over a dense
    cache with the same rotated-int8 codec, at "highest" precision."""
    cfg, plen = eng.cfg, len(prompt)
    toks = jnp.asarray(prompt, jnp.int32)[None]
    last = jnp.asarray([plen - 1], jnp.int32)
    nblk = -(-plen // BLOCK_SIZE)
    pool = paged_mod.init_paged_cache(cfg, nblk + 1, BLOCK_SIZE)
    table = jnp.arange(1, nblk + 1, dtype=jnp.int32)[None]

    @jax.jit
    def engine_path(params, cache):
        return lm.forward(params, toks, eng.rt, cfg,
                          cache={**cache, "table": table},
                          pos=jnp.zeros(1, jnp.int32), last_idx=last)[0]

    ref_rt = dataclasses.replace(eng.rt, backend="ref")

    @jax.jit
    def ref_path(params):
        cache = lm.init_cache(cfg, 1, plen, kv_quant=True)
        return lm.forward(params, toks, ref_rt, cfg, cache=cache,
                          pos=jnp.zeros(1, jnp.int32), last_idx=last)[0]

    got = engine_path(eng.params, pool)
    with jax.default_matmul_precision("highest"):
        want = ref_path(eng.params)
    check(finite(got) and finite(want), "prefill logits finite")
    err = rel_l2(got, want)
    print(f"prefill logits (plen {plen}): engine path vs ref rel_l2 "
          f"{err:.3e} (tol {LOGITS_TOL})", flush=True)
    check(err <= LOGITS_TOL, f"prefill logits rel_l2 {err}")
    return err


def one_chip(seed: int) -> None:
    check(auto_interpret() is False, "interpret mode is off on the chip")
    kernel_phase(seed)

    t0 = time.perf_counter()
    params, cfg = make_params(seed)
    eng = make_engine(params, cfg)
    st = eng.stats()
    print(f"engine: {ARCH} slots={SLOTS} max_len={MAX_LEN} paged "
          f"block_size={BLOCK_SIZE}; matmul_path={st['matmul_path']} "
          f"attn_path={st['attn_path']}; set-up "
          f"{time.perf_counter() - t0:.1f}s", flush=True)
    check(st["matmul_path"] == "pallas", "matmuls resolve to pallas")
    check(st["attn_path"] == "pallas", "head_dim-64 attention is pallas")

    dec, pre = decode_and_prefill_kernels(eng)
    print(f"decode step kernels: {sorted(dec)}; prefill: {sorted(pre)}",
          flush=True)
    check({"itq3_matvec_pallas", "attn_q8_pallas"} <= dec,
          "decode step runs the matvec and attention kernels")
    check({"itq3_matmul_pallas", "attn_q8_pallas"} <= pre,
          "prefill runs the tiled matmul and attention kernels")

    reqs = make_requests(cfg.vocab_size, seed)
    t0 = time.perf_counter()
    serve(eng, reqs)
    dt = time.perf_counter() - t0
    print(f"smoke serve (not a measurement): {len(reqs)} requests, "
          f"{sum(len(r.out) for r in reqs)} tokens, prompts "
          f"{min(PROMPT_LENS)}-{max(PROMPT_LENS)}, {dt:.1f}s wall incl. "
          f"compilation", flush=True)
    prefill_logits_phase(eng, reqs[2].prompt)


def four_chips(seed: int) -> None:
    """Tensor-parallel streams on a (1, 4) mesh == one chip's streams."""
    params, cfg = make_params(seed)
    mesh = make_host_mesh(1, 4)
    check(mesh.devices.size == 4, f"mesh over 4 devices, got {mesh.shape}")
    tp = make_engine(params, cfg, mesh=mesh)
    st = tp.stats()
    print(f"tp engine: devices={st['devices']} "
          f"tp_shard_map={st['tp_shard_map']} "
          f"matmul_path={st['matmul_path']} attn_path={st['attn_path']} "
          f"cache {st['cache_bytes']} B total, "
          f"{st['cache_bytes_per_device']} B/device", flush=True)
    check(st["devices"] == 4 and st["tp_shard_map"], "shard_map TP on 4")
    check(st["cache_bytes_per_device"] * 4 == st["cache_bytes"],
          "KV cache head-sharded 4 ways")
    wq = tp.params["layers"]["attn"]["wq"].data["plane2"]
    check(len(wq.sharding.device_set) == 4, "packed planes span 4 devices")
    one = make_engine(params, cfg)
    check(len(jax.tree.leaves(one.params)[0].devices()) == 1,
          "reference engine on one chip")

    t0 = time.perf_counter()
    tp_streams = serve(tp, make_requests(cfg.vocab_size, seed))
    t_tp = time.perf_counter() - t0
    one_streams = serve(one, make_requests(cfg.vocab_size, seed))
    same = sum(a == b for a, b in zip(tp_streams, one_streams))
    print(f"tp streams identical to one chip: {same}/{len(tp_streams)} "
          f"(tp serve {t_tp:.1f}s wall incl. compilation, not a "
          f"measurement)", flush=True)
    check(tp_streams == one_streams, "TP greedy streams == one chip")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cache_dir = setup_compile_cache()

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    check(len(devices) >= args.chips,
          f"--chips {args.chips} but JAX sees {len(devices)} devices")
    print(f"jax {jax.__version__} jaxlib {metadata.version('jaxlib')} "
          f"libtpu {metadata.version('libtpu')}; device_kind "
          f"{dev.device_kind!r} x{len(devices)}; compile cache {cache_dir}",
          flush=True)

    compile_s = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **_: compile_s.append(secs)
        if name == "/jax/core/compile/backend_compile_duration" else None)
    t0 = time.perf_counter()
    (four_chips if args.chips == 4 else one_chip)(args.seed)
    print(f"smoke run: {time.perf_counter() - t0:.1f}s wall, "
          f"{len(compile_s)} backend compiles taking {sum(compile_s):.1f}s "
          f"(not a measurement)", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": args.chips}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
