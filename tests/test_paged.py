"""Paged rotated-int8 KV cache: BlockPool allocator invariants, block-table
kernel parity, and engine-level bit-identity against the committed dense
goldens (tests/goldens/paged_dense_streams.json, captured on the dense
engine BEFORE paging existed — the acceptance bar for the subsystem)."""
import importlib.util
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _prng import prng_layout

from repro.configs.base import get_config, reduced
from repro.serve import kv_quant
from repro.kernels import attn_decode as ad
from repro.models import lm
from repro.models.layers import Runtime
from repro.serve.engine import Request, ServeEngine
from repro.serve.faults import Fault, FaultPlan, burst
from repro.serve.paged import (
    NULL_BLOCK, BlockPool, PoolExhausted, init_paged_cache, zero_blocks,
)
from repro.serve.sampling import FINISH_ERROR, FINISH_LENGTH, FINISH_REASONS

from _hypothesis_compat import given, settings, st

KEY = jax.random.PRNGKey(0)
# Matches tests/goldens/capture_paged_goldens.py exactly — bit-identity
# requires the identical Runtime the goldens were captured with.
RTQ = Runtime(compute_dtype=jnp.float32, kv_quant=True)

_GOLDEN_DIR = os.path.join(os.path.dirname(__file__), "goldens")


def _load_golden_module():
    spec = importlib.util.spec_from_file_location(
        "capture_paged_goldens",
        os.path.join(_GOLDEN_DIR, "capture_paged_goldens.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


golden_requests = _load_golden_module().golden_requests

with open(os.path.join(_GOLDEN_DIR, "paged_dense_streams.json")) as _f:
    GOLDEN_STREAMS = json.load(_f)[prng_layout()]


@pytest.fixture(scope="module")
def model():
    cfg = reduced(get_config("smollm-135m"))
    return cfg, lm.init_params(KEY, cfg)


def _paged_engine(model, **kw):
    cfg, params = model
    kw.setdefault("slots", 4)
    kw.setdefault("max_len", 64)
    kw.setdefault("prompt_pad", 16)
    kw.setdefault("rt", RTQ)
    return ServeEngine(params, cfg, paged=True, block_size=16, **kw)


# ---------------------------------------------------------------------------
# BlockPool allocator
# ---------------------------------------------------------------------------

def test_blockpool_validation():
    with pytest.raises(ValueError, match="blocks"):
        BlockPool(1, 16)
    with pytest.raises(ValueError, match="block_size"):
        BlockPool(4, 0)
    pool = BlockPool(5, 16)
    assert pool.capacity == 4 and pool.available() == 4
    assert pool.ref[NULL_BLOCK] == 1  # pinned


def test_blockpool_alloc_free_refcount_cycle():
    pool = BlockPool(4, 8)
    a, b, c = pool.alloc(), pool.alloc(), pool.alloc()
    assert sorted((a, b, c)) == [1, 2, 3]
    with pytest.raises(PoolExhausted):
        pool.alloc()
    pool.incref(b)
    assert not pool.decref(b)   # still shared
    assert pool.decref(b)       # now freed
    assert pool.available() == 1
    with pytest.raises(AssertionError, match="double free"):
        pool.decref(b)
    assert pool.decref(a) and pool.decref(c)
    assert pool.available() == pool.capacity
    pool.check()


def test_blockpool_chain_hash_is_context_sensitive():
    """hash(block i) must fold in the whole prefix: identical block CONTENT
    under different contexts must not alias (causal K/V differ)."""
    a = np.arange(32, dtype=np.int32)
    b = np.concatenate([a[:16] + 1, a[16:]])  # same 2nd block, new context
    ha = BlockPool.chain_hashes(a, 16)
    hb = BlockPool.chain_hashes(b, 16)
    assert len(ha) == len(hb) == 2
    assert ha[0] != hb[0] and ha[1] != hb[1]
    # true shared prefix DOES collide (that's the sharing condition)
    c = np.concatenate([a[:16], a[16:] + 5])
    hc = BlockPool.chain_hashes(c, 16)
    assert hc[0] == ha[0] and hc[1] != ha[1]
    # partial tail contributes no hash
    assert BlockPool.chain_hashes(a[:20], 16) == [ha[0]]


def test_blockpool_alloc_prompt_shares_full_prefix_blocks():
    pool = BlockPool(8, 4)
    p = np.arange(10, dtype=np.int32)  # 2 full blocks + partial tail
    first = pool.alloc_prompt(p)
    second = pool.alloc_prompt(p)
    assert first[:2] == second[:2]      # full blocks shared
    assert first[2] != second[2]        # partial tail always private
    assert pool.prefix_hits == 2
    assert pool.used() == 4             # 3 + 1, not 6
    pool.check([first, second])
    # all-or-nothing: a prompt that cannot fully fit leaves no residue
    with pytest.raises(PoolExhausted):
        pool.alloc_prompt(np.arange(40, dtype=np.int32))
    assert pool.used() == 4
    pool.check([first, second])


@settings(max_examples=30)
@given(st.integers(0, 10_000))
def test_blockpool_invariants_under_random_op_sequences(seed):
    """Property test: any interleaving of admit/grow/finish/preempt/resume
    keeps the allocator consistent — no double free, no leaked block, free
    list disjoint from referenced blocks, prefix map never points at a
    freed block. pool.check() asserts all of it after every op."""
    rng = np.random.default_rng(seed)
    pool = BlockPool(int(rng.integers(3, 12)), int(rng.integers(1, 6)))
    tables: dict[int, list[int]] = {}   # live slot -> block chain
    swapped: dict[int, int] = {}        # preempted rid -> chain length
    next_id = 0
    for _ in range(40):
        op = rng.choice(["admit", "grow", "finish", "preempt", "resume"])
        if op == "admit":
            prompt = rng.integers(0, 50, size=int(rng.integers(1, 20)))
            try:
                tables[next_id] = pool.alloc_prompt(prompt.astype(np.int32))
                next_id += 1
            except PoolExhausted:
                pass
        elif op == "grow" and tables:
            sid = int(rng.choice(list(tables)))
            try:
                tables[sid].append(pool.alloc())
            except PoolExhausted:
                pass
        elif op == "finish" and tables:
            sid = int(rng.choice(list(tables)))
            for blk in tables.pop(sid):
                pool.decref(blk)
        elif op == "preempt" and tables:
            sid = int(rng.choice(list(tables)))
            chain = tables.pop(sid)
            swapped[sid] = len(chain)
            for blk in chain:
                pool.decref(blk)
        elif op == "resume" and swapped:
            sid = int(rng.choice(list(swapped)))
            n = swapped[sid]
            got: list[int] = []
            try:
                for _ in range(n):
                    got.append(pool.alloc())
                tables[sid] = got
                del swapped[sid]
            except PoolExhausted:
                for blk in got:  # all-or-nothing, like the engine
                    pool.decref(blk)
        pool.check(tables.values())
    # drain everything: the pool must return to pristine
    for chain in tables.values():
        for blk in chain:
            pool.decref(blk)
    assert pool.available() == pool.capacity
    pool.check()


# ---------------------------------------------------------------------------
# Paged cache planes + kernel parity
# ---------------------------------------------------------------------------

def test_init_paged_cache_shapes_and_guards(model):
    cfg, _ = model
    cache = init_paged_cache(cfg, num_blocks=6, block_size=8)
    kvh, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    assert cache["attn"]["k"].shape == (cfg.num_layers, 6, kvh, 8, hd)
    assert cache["attn"]["k"].dtype == jnp.int8
    assert cache["attn"]["k_scale"].shape == (cfg.num_layers, 6, kvh, 8, 1)
    assert cache["attn"]["k_scale"].dtype == jnp.float16
    import dataclasses
    bad = dataclasses.replace(cfg, family="ssm")
    with pytest.raises(ValueError, match="famil"):
        init_paged_cache(bad, num_blocks=6, block_size=8)


def test_zero_blocks_zeroes_only_targets(model):
    cfg, _ = model
    cache = init_paged_cache(cfg, num_blocks=4, block_size=4)
    attn = {k: v + 1 for k, v in cache["attn"].items()}
    out = zero_blocks({"attn": attn}, [2])["attn"]
    for leaf in out.values():
        assert float(jnp.abs(leaf[:, 2]).max()) == 0.0
        assert float(jnp.abs(leaf[:, 1]).min()) == 1.0


def _dense_and_paged_caches(rng, b=2, kvh=2, bs=8, maxb=3, hd=128):
    """A random quantized dense cache and its paged twin: pool blocks hold
    the same rows, scattered through a shuffled block table."""
    t = maxb * bs
    kc, ks = kv_quant.kv_encode(
        jnp.asarray(rng.normal(size=(b, kvh, t, hd)), jnp.float32))
    vc, vs = kv_quant.kv_encode(
        jnp.asarray(rng.normal(size=(b, kvh, t, hd)), jnp.float32))
    dense = {"k": kc, "k_scale": ks, "v": vc, "v_scale": vs}
    nb = b * maxb + 1
    table = jnp.asarray(
        1 + rng.permutation(b * maxb).reshape(b, maxb), jnp.int32)
    paged = {"table": table}
    for key, leaf in dense.items():
        x = leaf.reshape(b, kvh, maxb, bs, -1)       # cut T into blocks
        x = jnp.swapaxes(x, 1, 2).reshape(b * maxb, kvh, bs, -1)
        pool = jnp.zeros((nb,) + x.shape[1:], leaf.dtype)
        paged[key] = pool.at[table.reshape(-1)].set(x)
    return dense, paged


def test_paged_to_dense_gather_matches(rng):
    dense, paged = _dense_and_paged_caches(rng)
    out = ad.paged_to_dense(paged)
    for key in dense:
        np.testing.assert_array_equal(np.asarray(out[key]),
                                      np.asarray(dense[key]))


def test_paged_decode_ref_bitwise_vs_dense(rng):
    dense, paged = _dense_and_paged_caches(rng)
    b, kvh, t, hd = dense["k"].shape
    q = jnp.asarray(rng.normal(size=(b, kvh, 2, 1, hd)), jnp.float32)
    ktok = kv_quant.kv_encode(
        jnp.asarray(rng.normal(size=(b, kvh, 1, hd)), jnp.float32))
    vtok = kv_quant.kv_encode(
        jnp.asarray(rng.normal(size=(b, kvh, 1, hd)), jnp.float32))
    kl = jnp.asarray([t - 3, 5], jnp.int32)  # ragged, mid-block lengths
    want = ad.decode_attn_q8(q, dense, ktok, vtok, kl, backend="ref")
    got = ad.decode_attn_q8(q, paged, ktok, vtok, kl, backend="ref")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_paged_prefill_ref_bitwise_vs_dense(rng):
    dense, paged = _dense_and_paged_caches(rng)
    b, kvh, t, hd = dense["k"].shape
    span = 4
    q = jnp.asarray(rng.normal(size=(b, kvh, 2, span, hd)), jnp.float32)
    kl = jnp.asarray([t, t - 7], jnp.int32)
    pos = kl - span
    want = ad.prefill_attn_q8(q, dense, kl, pos, backend="ref")
    got = ad.prefill_attn_q8(q, paged, kl, pos, backend="ref")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.slow
@pytest.mark.timeout(600)
def test_paged_decode_kernel_interpret_bitwise_vs_dense(rng):
    """Kernel path (interpret mode): paged and dense agree bitwise when the
    effective key-tile matches (tt divides block_size, so both run the
    identical flash-attention accumulation order)."""
    dense, paged = _dense_and_paged_caches(rng, bs=8, maxb=2)
    b, kvh, t, hd = dense["k"].shape
    q = jnp.asarray(rng.normal(size=(b, kvh, 2, 1, hd)), jnp.float32)
    ktok = kv_quant.kv_encode(
        jnp.asarray(rng.normal(size=(b, kvh, 1, hd)), jnp.float32))
    vtok = kv_quant.kv_encode(
        jnp.asarray(rng.normal(size=(b, kvh, 1, hd)), jnp.float32))
    kl = jnp.asarray([t, t - 5], jnp.int32)
    nb, _, bs, _ = paged["k"].shape
    qr = jnp.asarray(rng.normal(size=(b, kvh, 2, hd)), jnp.float32)
    for tt in (4, 8):
        want = ad.decode_attn_q8(q, dense, ktok, vtok, kl,
                                 backend="pallas", interpret=True, tt=tt)
        got = ad.decode_attn_q8(q, paged, ktok, vtok, kl,
                                backend="pallas", interpret=True, tt=tt)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        # the decode grid over the pool's own planes and the slot's block
        # table == the row grid over pool rows and the per-row table
        kw = dict(sm_scale=hd ** -0.5, tt=tt, interpret=True, block_size=bs)
        fold = ad.attn_decode_q8_pallas(
            qr, paged["k"], paged["k_scale"][..., 0], paged["v"],
            paged["v_scale"][..., 0], kl, paged["table"], **kw)
        rows = ad.attn_decode_q8_pallas(
            qr.reshape(b * kvh, 2, hd), paged["k"].reshape(nb * kvh, bs, hd),
            paged["k_scale"].reshape(nb * kvh, bs),
            paged["v"].reshape(nb * kvh, bs, hd),
            paged["v_scale"].reshape(nb * kvh, bs), jnp.repeat(kl, kvh),
            ad.paged_row_table(paged["table"], kvh), **kw)
        for a, w in zip(fold, rows):
            np.testing.assert_array_equal(np.asarray(a).reshape(w.shape),
                                          np.asarray(w))


# ---------------------------------------------------------------------------
# Engine: bit-identity against the pre-paging dense goldens
# ---------------------------------------------------------------------------

def _assert_matches_goldens(done):
    streams = {str(r.rid): [int(tok) for tok in r.out] for r in done}
    assert set(streams) == set(GOLDEN_STREAMS)
    for rid, want in GOLDEN_STREAMS.items():
        assert streams[rid] == want, f"rid {rid} diverged from dense golden"


@pytest.mark.timeout(600)
def test_paged_engine_bit_identical_to_dense_goldens(model):
    cfg, _ = model
    eng = _paged_engine(model)
    done = eng.run(golden_requests(cfg.vocab_size))
    _assert_matches_goldens(done)
    st_ = eng.stats()
    assert st_["paged"] and st_["prefix_hits"] >= 1  # rid 100/101 shared
    assert st_["pool_blocks_used"] == 0              # fully drained
    eng.pool.check(eng._table)


@pytest.mark.timeout(600)
def test_paged_tiny_pool_preempts_swaps_and_stays_bit_identical(model):
    """4 usable blocks for an 11-request burst: the engine must preempt,
    host-swap block sets, and resume — with every stream still bit-equal
    to the dense goldens."""
    cfg, _ = model
    eng = _paged_engine(model, num_blocks=5)
    done = eng.run(golden_requests(cfg.vocab_size))
    _assert_matches_goldens(done)
    st_ = eng.stats()
    assert st_["preemptions"] >= 1 and st_["resumes"] >= 1
    assert st_["blocks_swapped"] >= 1
    assert st_["pool_blocks_used"] == 0
    eng.pool.check(eng._table)


@pytest.mark.timeout(300)
def test_paged_prefix_sharing_dedups_pool_blocks(model):
    """Two live requests over the same 32-token prefix must hold the full
    prefix blocks ONCE (refcount 2), not twice."""
    cfg, _ = model
    eng = _paged_engine(model, slots=2)
    shared = (np.arange(32) % cfg.vocab_size).astype(np.int32)
    reqs = [Request(rid=0, prompt=shared.copy(), max_new=8),
            Request(rid=1, prompt=np.concatenate(
                [shared, np.asarray([7], np.int32)]), max_new=8)]
    it = eng.generate(reqs)
    next(it)
    assert eng.pool.prefix_hits == 2        # both 16-token prefix blocks
    shared_blocks = set(eng._slot_blocks[0]) & set(eng._slot_blocks[1])
    assert len(shared_blocks) == 2
    assert all(eng.pool.ref[b] == 2 for b in shared_blocks)
    eng.pool.check(eng._table)
    list(it)
    assert eng.pool.used() == 0


@pytest.mark.timeout(300)
def test_paged_oversize_prompt_finishes_error_not_crash(model):
    cfg, _ = model
    eng = _paged_engine(model, num_blocks=3)  # 2 usable blocks = 32 tokens
    big = Request(rid=0, prompt=(np.arange(40) % cfg.vocab_size
                                 ).astype(np.int32), max_new=4)
    ok = Request(rid=1, prompt=np.arange(4, dtype=np.int32), max_new=3)
    list(eng.generate([big, ok]))
    assert big.finish_reason == FINISH_ERROR and big.out == []
    assert ok.finish_reason == FINISH_LENGTH
    assert eng.stats()["pool_exhausted"] >= 1
    assert eng.pool.used() == 0


def test_paged_requires_kv_quant(model):
    cfg, params = model
    with pytest.raises(ValueError, match="kv_quant"):
        ServeEngine(params, cfg, slots=2, max_len=48, paged=True,
                    rt=Runtime(compute_dtype=jnp.float32))


# ---------------------------------------------------------------------------
# Satellites: stats split, mesh guard
# ---------------------------------------------------------------------------

@pytest.mark.timeout(300)
def test_stats_reserved_vs_live_split(model):
    """cache_bytes_reserved counts allocation (blocks / dense planes);
    cache_bytes_live is position-weighted — live <= reserved always, and
    both exist on dense AND paged engines."""
    cfg, params = model
    dense = ServeEngine(params, cfg, slots=2, max_len=48, rt=RTQ)
    reqs = [Request(rid=0, prompt=np.arange(6, dtype=np.int32), max_new=4)]
    it = dense.generate(reqs)
    next(it)
    st_ = dense.stats()
    assert st_["cache_bytes_reserved"] == dense.cache_bytes
    assert 0 < st_["cache_bytes_live"] <= st_["cache_bytes_reserved"]
    list(it)
    assert dense.stats()["cache_bytes_live"] == 0

    eng = _paged_engine(model, slots=2)
    reqs = [Request(rid=0, prompt=np.arange(18, dtype=np.int32), max_new=4)]
    it = eng.generate(reqs)
    next(it)
    st_ = eng.stats()
    # 18 tokens -> 2 blocks reserved (32 token-slots), 19+ live tokens
    assert st_["cache_bytes_reserved"] > st_["cache_bytes_live"] > 0
    assert st_["pool_utilization"] > 0
    assert st_["max_concurrent"] == 1
    list(it)
    assert eng.stats()["cache_bytes_live"] == 0
    assert eng.stats()["pool_utilization"] == 0


def test_mesh_with_data_axis_raises_clear_error(model):
    cfg, params = model
    class _FakeMesh:
        shape = {"data": 2, "model": 1}
    with pytest.raises(ValueError, match="data"):
        ServeEngine(params, cfg, slots=2, max_len=48, rt=RTQ,
                    mesh=_FakeMesh())


# ---------------------------------------------------------------------------
# Chaos under paging
# ---------------------------------------------------------------------------

@pytest.mark.timeout(600)
def test_paged_kv_nan_quarantine_zeroes_blocks_healthy_stream_intact(model):
    """The fault router must follow the block table: poisoning slot 0 (a)
    errors that stream, (b) leaves the neighbor bit-identical to a
    fault-free paged run, (c) returns ZEROED blocks to the pool so the
    next tenant decodes as in a fresh engine."""
    cfg, _ = model
    def reqs():
        return [Request(rid=i, prompt=(np.arange(4 + i) % cfg.vocab_size
                                       ).astype(np.int32), max_new=6)
                for i in range(2)]
    clean = reqs()
    _paged_engine(model, slots=2).run(clean)

    plan = FaultPlan([Fault("kv_nan", step=2, slot=0, plane="k_scale",
                            value=math.nan)])
    eng = _paged_engine(model, slots=2, faults=plan)
    faulted = reqs()
    list(eng.generate(faulted))
    poisoned, healthy = faulted
    assert poisoned.finish_reason == FINISH_ERROR
    assert healthy.finish_reason == FINISH_LENGTH
    assert healthy.out == clean[1].out
    assert eng.quarantined == 1
    assert eng.pool.used() == 0
    eng.pool.check(eng._table)
    # poisoned blocks were zeroed before returning to the free list: a new
    # tenant reusing them decodes exactly as in a fresh engine
    again = [Request(rid=10, prompt=np.arange(4, dtype=np.int32), max_new=4)]
    list(eng.generate(again))
    ref = [Request(rid=10, prompt=np.arange(4, dtype=np.int32), max_new=4)]
    _paged_engine(model, slots=2).run(ref)
    assert again[0].out == ref[0].out


@pytest.mark.timeout(600)
def test_paged_chaos_burst_everything_terminates(model):
    """Full chaos plan over a paged engine with a tight pool: every request
    reaches a terminal finish_reason from the closed vocabulary and the
    pool drains to zero — no leaked or wedged blocks."""
    cfg, _ = model
    plan = FaultPlan([
        Fault("kv_nan", step=3, slot=0),
        Fault("clock_skip", step=5, dt=1.0),
        Fault("stall", step=5, dt=2.0),
    ])
    eng = _paged_engine(model, slots=2, num_blocks=7, max_queue=4,
                        shed_policy="shed_lowest", scheduler="priority",
                        watchdog_timeout_s=0.5, faults=plan)
    reqs = burst(8, cfg.vocab_size, max_new=6)
    for i, r in enumerate(reqs):
        r.priority = i % 3
        if i % 2:
            r.deadline_ms = 400.0
    for r in reqs:
        eng.submit_request(r)
    list(eng.generate())
    assert all(r.done for r in reqs)
    assert all(r.finish_reason in FINISH_REASONS for r in reqs)
    assert all(r is None for r in eng.active)
    assert len(eng.scheduler) == 0 and eng.stats()["swapped"] == 0
    assert eng.pool.used() == 0
    eng.pool.check(eng._table)
