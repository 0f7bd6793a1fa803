"""Speculative decoding: the propose/verify/commit refactor of the decode
tick, judged against the committed pre-refactor goldens
(tests/goldens/spec_decode_streams.json, captured on the one-token engine
BEFORE speculation existed).

The contract, per layout (dense fp32, dense rotated-int8, paged pool):

* spec OFF  -> streams byte-identical to the goldens (the refactor is a
  structural no-op when no draft model is configured);
* spec ON, greedy slots -> committed streams byte-identical to the SAME
  goldens (lossless verification: acceptance only reorders work, never
  tokens);
* spec ON, per-request opt-out (``draft=False`` / ``draft_tokens=0``)
  -> byte-identical for EVERY request, sampled ones included (the kvec=0
  window reuses the non-speculative PRNG stream);
* paged runs drain the block pool to zero with allocator invariants
  intact (no leaked lookahead blocks).
"""
import dataclasses
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _prng import prng_layout

from repro.configs.base import get_config, reduced
from repro.models import lm
from repro.models.layers import Runtime
from repro.serve import spec
from repro.serve.engine import Request, ServeEngine
from repro.serve.faults import Fault, FaultPlan
from repro.serve.paged import blocks_needed
from repro.serve.sampling import (
    FINISH_CANCELLED, FINISH_DEADLINE, FINISH_LENGTH, FINISH_REASONS,
    FINISH_STOP, SamplingParams,
)

KEY = jax.random.PRNGKey(0)
_GOLDEN_DIR = os.path.join(os.path.dirname(__file__), "goldens")

# The three layouts the goldens were captured with — engine kwargs must
# match tests/goldens/capture_spec_goldens.py exactly for bit-identity.
LAYOUTS = {
    "dense_fp": dict(rt=Runtime(compute_dtype=jnp.float32)),
    "dense_q8": dict(rt=Runtime(compute_dtype=jnp.float32, kv_quant=True)),
    "paged_q8": dict(rt=Runtime(compute_dtype=jnp.float32, kv_quant=True),
                     paged=True, block_size=16),
}
GREEDY_RIDS = [str(i) for i in range(7)] + ["203"]   # 203: greedy + stop
SAMPLED_RIDS = ["200", "201", "202"]


def _load_golden_module():
    s = importlib.util.spec_from_file_location(
        "capture_spec_goldens",
        os.path.join(_GOLDEN_DIR, "capture_spec_goldens.py"))
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod


golden_requests = _load_golden_module().golden_requests

with open(os.path.join(_GOLDEN_DIR, "spec_decode_streams.json")) as _f:
    GOLDENS = json.load(_f)[prng_layout()]


@pytest.fixture(scope="module")
def model():
    cfg = reduced(get_config("smollm-135m"))
    return cfg, lm.init_params(KEY, cfg)


@pytest.fixture(scope="module")
def draft(model):
    cfg, params = model
    return spec.draft_from_params(params, cfg, 1)


def _engine(model, layout, **kw):
    cfg, params = model
    base = dict(LAYOUTS[layout])
    base.update(kw)
    return ServeEngine(params, cfg, slots=4, max_len=64, prompt_pad=16,
                      **base)


def _streams(done):
    return {str(r.rid): [int(t) for t in r.out] for r in done}


def _check_drained(eng):
    if eng.paged:
        assert eng.pool.used() == 0, "leaked pool blocks after drain"
        eng.pool.check(eng._table)


# ---------------------------------------------------------------------------
# Parity vs the pre-refactor goldens
# ---------------------------------------------------------------------------

@pytest.mark.timeout(600)
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_spec_off_byte_identical_to_goldens(model, layout):
    """No draft model configured: the refactored engine must reproduce the
    pre-refactor goldens byte-for-byte — every rid, sampled included."""
    cfg, _ = model
    eng = _engine(model, layout)
    got = _streams(eng.run(golden_requests(cfg.vocab_size)))
    assert got == GOLDENS[layout]
    assert not eng.stats().get("speculative", False)
    _check_drained(eng)


@pytest.mark.timeout(600)
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_greedy_spec_bit_identical_lossless(model, draft, layout):
    """Greedy speculative streams equal the non-speculative goldens
    regardless of draft quality (here: a 1-layer self-draft whose
    proposals are mostly wrong). Sampled slots use a different PRNG
    stream by design — checked for sanity, not parity."""
    cfg, _ = model
    dparams, dcfg = draft
    eng = _engine(model, layout, draft_params=dparams, draft_cfg=dcfg,
                  num_draft_tokens=4)
    got = _streams(eng.run(golden_requests(cfg.vocab_size)))
    for rid in GREEDY_RIDS:
        assert got[rid] == GOLDENS[layout][rid], (
            f"greedy rid {rid} diverged under speculation ({layout})")
    for rid in SAMPLED_RIDS:
        want = GOLDENS[layout][rid]
        assert len(got[rid]) == len(want)  # same max_new budget honored
        assert all(0 <= t < cfg.vocab_size for t in got[rid])
    st = eng.stats()
    assert st["speculative"] and st["spec_steps"] >= 1
    assert st["draft_proposed"] > 0
    # one transfer per window + one per admission wave, nothing else
    assert st["decode_steps"] < st["host_syncs"] <= st["decode_steps"] + 11
    assert st["cache_donated"]
    _check_drained(eng)


@pytest.mark.timeout(600)
@pytest.mark.parametrize("optout", ["draft", "draft_tokens"])
def test_spec_optout_bitwise_for_all_rids(model, draft, optout):
    """draft=False (or draft_tokens=0) routes a slot through the kvec=0
    window: one token per step on the natural PRNG stream — bit-identical
    to the non-speculative engine for sampled requests too."""
    cfg, _ = model
    dparams, dcfg = draft
    eng = _engine(model, "dense_q8", draft_params=dparams, draft_cfg=dcfg,
                  num_draft_tokens=4)
    off = (dict(draft=False) if optout == "draft"
           else dict(draft_tokens=0))
    reqs = []
    for r in golden_requests(cfg.vocab_size):
        sp = (dataclasses.replace(r.sampling, **off) if r.sampling
              else SamplingParams(**off))
        reqs.append(Request(rid=r.rid, prompt=r.prompt, max_new=r.max_new,
                            sampling=sp))
    got = _streams(eng.run(reqs))
    assert got == GOLDENS["dense_q8"]
    assert eng.stats()["draft_accepted"] == 0  # nothing was ever proposed


@pytest.mark.timeout(600)
def test_perfect_draft_full_acceptance_accounting(model):
    """A full-depth self-draft is the target model: every greedy proposal
    verifies, so acceptance is exactly 100% and each window commits K+1
    tokens (modulo stream-end truncation). Pins the accounting split
    between engine stats and per-request stats."""
    cfg, params = model
    dparams, dcfg = spec.draft_from_params(params, cfg, cfg.num_layers)
    k = 4
    eng = _engine(model, "dense_q8", draft_params=dparams, draft_cfg=dcfg,
                  num_draft_tokens=k)
    reqs = [Request(rid=i, prompt=(np.arange(5 + 3 * i) % cfg.vocab_size
                                   ).astype(np.int32), max_new=12)
            for i in range(3)]
    done = eng.run(reqs)
    st = eng.stats()
    assert st["acceptance_rate"] == pytest.approx(1.0)
    assert st["draft_accepted"] == st["draft_proposed"] > 0
    assert st["tokens_per_step"] > 2.0
    # with everything accepted each slot needs ceil(12 / (k+1)) windows
    assert all(r.spec_windows == -(-r.max_new // (k + 1)) for r in reqs)
    for r in done:
        assert r.finish_reason == FINISH_LENGTH
        rs = r.stats()
        assert rs["draft_accepted"] == rs["draft_proposed"] == r.drafted
        assert rs["acceptance_rate"] == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# Chaos mid-window: cancel / deadline / preempt
# ---------------------------------------------------------------------------

@pytest.mark.timeout(600)
def test_chaos_midwindow_cancel_preempt_deadline(model, draft):
    """Faults landing between speculative windows on a PAGED spec engine:
    a cancel, a forced preempt (with later resume), and a decode-timeout
    expiry. Every request ends in exactly one terminal StreamEvent, event
    indices stay dense per rid, and the pool drains with no block leaked
    by the lookahead allocation."""
    cfg, _ = model
    dparams, dcfg = draft
    plan = FaultPlan([Fault("cancel", step=3, rid=0),
                      Fault("preempt", step=4, rid=1)])
    reqs = [Request(rid=0, prompt=np.arange(6, dtype=np.int32), max_new=30),
            Request(rid=1, prompt=np.arange(9, dtype=np.int32), max_new=20),
            Request(rid=2, prompt=np.arange(4, dtype=np.int32), max_new=20,
                    decode_timeout_ms=0.0),
            Request(rid=3, prompt=np.arange(7, dtype=np.int32), max_new=6)]
    eng = _engine(model, "paged_q8", draft_params=dparams, draft_cfg=dcfg,
                  num_draft_tokens=4, faults=plan)
    events = list(eng.generate(reqs))
    assert reqs[0].finish_reason == FINISH_CANCELLED
    assert reqs[1].finish_reason == FINISH_LENGTH and reqs[1].preemptions >= 1
    assert reqs[2].finish_reason == FINISH_DEADLINE
    assert 1 <= len(reqs[2].out) < reqs[2].max_new
    assert reqs[3].finish_reason in (FINISH_LENGTH, FINISH_STOP)
    for r in reqs:
        assert r.finish_reason in FINISH_REASONS
        term = [e for e in events if e.rid == r.rid and e.finished]
        assert len(term) == 1, f"rid {r.rid}: {len(term)} terminal events"
        idx = [e.index for e in events if e.rid == r.rid]
        assert idx == sorted(set(idx)), f"rid {r.rid} event indices not dense"
    assert len({(e.rid, e.index) for e in events}) == len(events)
    assert all(r is None for r in eng.active)
    assert (eng._slot_draft_k == 0).all()
    _check_drained(eng)


# ---------------------------------------------------------------------------
# Paged lookahead sizing
# ---------------------------------------------------------------------------

def test_blocks_needed_lookahead():
    assert blocks_needed(0, 16) == 1
    assert blocks_needed(15, 16) == 1
    assert blocks_needed(16, 16) == 2
    # a K=4 window starting at pos 13 can commit through pos 17: 2 blocks
    assert blocks_needed(13, 16, lookahead=4) == 2
    assert blocks_needed(11, 16, lookahead=4) == 1
    assert blocks_needed(31, 16, lookahead=1) == 3


@pytest.mark.timeout(600)
def test_paged_tiny_pool_spec_preempts_and_stays_lossless(model, draft):
    """A starved pool must preempt/resume around speculative windows and
    still commit greedy streams identical to the goldens."""
    cfg, _ = model
    dparams, dcfg = draft
    eng = _engine(model, "paged_q8", num_blocks=8, draft_params=dparams,
                  draft_cfg=dcfg, num_draft_tokens=4)
    got = _streams(eng.run(golden_requests(cfg.vocab_size)))
    for rid in GREEDY_RIDS:
        assert got[rid] == GOLDENS["paged_q8"][rid]
    _check_drained(eng)


# ---------------------------------------------------------------------------
# Constructor gates
# ---------------------------------------------------------------------------

def test_spec_constructor_validation(model, draft):
    cfg, params = model
    dparams, dcfg = draft
    with pytest.raises(ValueError, match="draft_cfg"):
        ServeEngine(params, cfg, slots=2, max_len=48,
                    rt=Runtime(compute_dtype=jnp.float32),
                    draft_params=dparams)
    with pytest.raises(ValueError, match="sample_on_host"):
        ServeEngine(params, cfg, slots=2, max_len=48,
                    rt=Runtime(compute_dtype=jnp.float32),
                    draft_params=dparams, draft_cfg=dcfg,
                    sample_on_host=True)
    with pytest.raises(ValueError, match="num_draft_tokens"):
        ServeEngine(params, cfg, slots=2, max_len=48,
                    rt=Runtime(compute_dtype=jnp.float32),
                    draft_params=dparams, draft_cfg=dcfg,
                    num_draft_tokens=0)
    with pytest.raises(ValueError, match="vocab"):
        ServeEngine(params, cfg, slots=2, max_len=48,
                    rt=Runtime(compute_dtype=jnp.float32),
                    draft_params=dparams,
                    draft_cfg=dataclasses.replace(
                        dcfg, vocab_size=cfg.vocab_size + 1))
    with pytest.raises(ValueError, match="famil"):
        ServeEngine(params, cfg, slots=2, max_len=48,
                    rt=Runtime(compute_dtype=jnp.float32),
                    draft_params=dparams,
                    draft_cfg=dataclasses.replace(dcfg, family="ssm"))


def test_draft_from_params_gates(model):
    cfg, params = model
    with pytest.raises(ValueError, match="depth"):
        spec.draft_from_params(params, cfg, cfg.num_layers + 1)
    with pytest.raises(ValueError, match="famil"):
        spec.draft_from_params(params, dataclasses.replace(cfg,
                                                           family="ssm"), 1)
    dparams, dcfg = spec.draft_from_params(params, cfg, 1)
    assert dcfg.num_layers == 1
    # embedding / head leaves shared by reference, layers sliced
    assert dparams["embed"] is params["embed"]
    lead = jax.tree.leaves(dparams["layers"])[0]
    assert lead.shape[0] == 1


def test_sampling_params_spec_knob_validation():
    with pytest.raises(ValueError, match="draft_tokens"):
        SamplingParams(draft_tokens=-1)
    sp = SamplingParams(draft=False)
    assert sp.draft is False and sp.draft_tokens is None
