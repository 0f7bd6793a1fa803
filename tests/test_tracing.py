"""The serving path's tracing: the engine's phase spans in a profiler
trace, the model's scope names in the compiled programs' HLO metadata,
and greedy tokens unchanged by either (the committed paged goldens).

The kernel path's scope (``itq3_planes``) and the kernel instruction
names are checked where the kernels compile for the chip:
``tests/test_tpu_compile.py``."""
import glob
import importlib.util
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _prng import prng_layout

from repro.configs.base import get_config, reduced
from repro.models import lm
from repro.models.layers import Runtime
from repro.serve.engine import Request, ServeEngine

# the model scopes of every served program, off the kernel path
SCOPES = ("embed", "kv_cache", "attn", "mlp", "head", "sample")
SPANS = ("serve.admit", "serve.prefill_sync", "serve.decode_prep",
         "serve.decode_dispatch", "serve.decode_sync", "serve.commit")
DECODE_PHASES = SPANS[2:]
# the goldens' Runtime (tests/goldens/capture_paged_goldens.py)
RTQ = Runtime(compute_dtype=jnp.float32, kv_quant=True)
GOLDENS = os.path.join(os.path.dirname(__file__), "goldens")


@pytest.fixture(scope="module")
def model():
    cfg = reduced(get_config("smollm-135m"))
    return cfg, lm.init_params(jax.random.PRNGKey(0), cfg)


def _engine(model):
    cfg, params = model
    return ServeEngine(params, cfg, slots=4, max_len=64, prompt_pad=16,
                       rt=RTQ, paged=True, block_size=16)


def _host_events(logdir) -> list:
    """(name, start_ns, end_ns, stats) of every ``serve.*`` event on the
    trace's host planes, by start."""
    from jax.profiler import ProfileData
    (path,) = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                         dict(e.stats)) for e in line.events
                        if e.name.startswith("serve.")]
    return sorted(out, key=lambda e: e[1])


def test_trace_holds_every_engine_phase_nested(model, tmp_path):
    cfg, _ = model
    eng = _engine(model)
    rng = np.random.default_rng(3)
    prompt = lambda n: rng.integers(0, cfg.vocab_size, n).astype(np.int32)
    eng.run([Request(rid=99, prompt=prompt(6), max_new=3)])  # compile
    steps0 = eng.decode_steps
    jax.profiler.start_trace(str(tmp_path))
    eng.run([Request(rid=i, prompt=prompt(5 + 4 * i), max_new=4)
             for i in range(3)])
    jax.profiler.stop_trace()
    ev = _host_events(tmp_path)
    assert {e[0] for e in ev} == set(SPANS)
    by = {n: [e for e in ev if e[0] == n] for n in SPANS}
    # one span per phase of a step, never one per slot
    steps = eng.decode_steps - steps0
    for name in DECODE_PHASES:
        assert len(by[name]) == steps, name
    assert [e[3]["step"] for e in by["serve.decode_sync"]] == list(
        range(steps0 + 1, eng.decode_steps + 1))
    assert all(1 <= e[3]["live"] <= 3 for e in by["serve.decode_sync"])
    # a step's phases run in order and do not overlap
    for k in range(steps):
        seq = [by[name][k] for name in DECODE_PHASES]
        assert all(a[2] <= b[1] for a, b in zip(seq, seq[1:]))
    # every wave's blocking fetch of first tokens sits inside its admission
    assert len(by["serve.prefill_sync"]) == len(by["serve.admit"]) >= 1
    for admit, sync in zip(by["serve.admit"], by["serve.prefill_sync"]):
        assert admit[1] <= sync[1] and sync[2] <= admit[2]
        assert {"rids", "bucket"} <= set(admit[3])
    # admissions and decode steps never nest in one another
    for admit in by["serve.admit"]:
        for name in DECODE_PHASES:
            assert all(e[2] <= admit[1] or e[1] >= admit[2]
                       for e in by[name])


def _compiled_text(eng, program: str) -> str:
    slots = eng.slots
    table = jnp.asarray(eng._table)
    if program == "decode":
        lowered = eng._jit_decode.lower(
            eng.params, eng.cache, jnp.zeros((slots, 1), jnp.int32),
            jnp.zeros((slots,), jnp.int32), None, None, None, None, None,
            table)
    else:
        lowered = eng._jit_prefill.lower(
            eng.params, eng.cache, jnp.zeros((1, 16), jnp.int32),
            jnp.zeros((1,), jnp.int32), jnp.full((1,), 4, jnp.int32),
            jnp.zeros((1,), jnp.int32), None, None, None, None,
            table[:1], plen=16, fresh=True)
    return lowered.compile().as_text()


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_compiled_program_carries_scope_names(model, program):
    """Each scope reaches the op_name metadata of the compiled program,
    which keeps the module name the benchmark's trace reduction matches
    (``_decode_impl`` / ``_prefill_impl``)."""
    text = _compiled_text(_engine(model), program)
    assert text.startswith(f"HloModule jit__{program}_impl")
    paths = re.findall(r'op_name="([^"]*)"', text)
    parts = {p for path in paths for p in re.split(r"[/;]", path)}
    missing = [s for s in SCOPES if s not in parts]
    assert not missing, missing


def _golden_requests():
    spec = importlib.util.spec_from_file_location(
        "capture_paged_goldens",
        os.path.join(GOLDENS, "capture_paged_goldens.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.golden_requests


def test_tokens_match_goldens_while_traced(model, tmp_path):
    """The committed paged goldens, captured before the spans and scopes
    existed, come out token for token with the profiler recording."""
    cfg, _ = model
    with open(os.path.join(GOLDENS, "paged_dense_streams.json")) as f:
        want = json.load(f)[prng_layout()]
    eng = _engine(model)
    with jax.profiler.trace(str(tmp_path)):
        done = eng.run(_golden_requests()(cfg.vocab_size))
    assert {str(r.rid): [int(t) for t in r.out] for r in done} == want
