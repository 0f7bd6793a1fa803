"""Quantized-KV serving path: fused decode-attention kernel parity, shape
dispatch, and the engine invariants (1 sync/step, cache shrink, token
parity with the dequantize-then-attend reference)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _prng import prng_layout

from repro.configs.base import get_config, kv_cache_bytes_per_token, reduced
from repro.core.fwht import fwht
from repro.kernels import attn_decode as ad
from repro.models import lm
from repro.models.layers import Runtime
from repro.serve import kv_quant
from repro.serve.engine import Request, ServeEngine

KEY = jax.random.PRNGKey(0)
RT = Runtime(compute_dtype=jnp.float32, capacity_factor=8.0)
RTQ = Runtime(compute_dtype=jnp.float32, kv_quant=True, capacity_factor=8.0)


def _quant_cache(rng, b, kv, t, hd):
    k = jnp.asarray(rng.normal(size=(b, kv, t, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, kv, t, hd)), jnp.float32)
    kc, ks = kv_quant.kv_encode(k)
    vc, vs = kv_quant.kv_encode(v)
    return {"k": kc, "k_scale": ks, "v": vc, "v_scale": vs}, k, v


# ---------------------------------------------------------------------------
# Kernel: parity with the jnp reference and with dequantized-cache attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,kv,g,hd,t", [
    (2, 1, 4, 32, 48), (1, 3, 2, 64, 33), (2, 2, 1, 128, 17),
])
def test_kernel_matches_ref_backend(rng, b, kv, g, hd, t):
    cache, _, _ = _quant_cache(rng, b, kv, t, hd)
    q = jnp.asarray(rng.normal(size=(b, kv, g, 1, hd)), jnp.float32)
    ktok = kv_quant.kv_encode(
        jnp.asarray(rng.normal(size=(b, kv, 1, hd)), jnp.float32))
    vtok = kv_quant.kv_encode(
        jnp.asarray(rng.normal(size=(b, kv, 1, hd)), jnp.float32))
    kl = jnp.asarray(rng.integers(1, t + 1, size=b), jnp.int32)
    ref = ad.decode_attn_q8(q, cache, ktok, vtok, kl, backend="ref")
    ker = ad.decode_attn_q8(q, cache, ktok, vtok, kl, backend="pallas",
                            interpret=True)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_kernel_online_softmax_tiling_invariant(rng):
    """Multi-tile online softmax == single-pass reference, ragged T."""
    b, kv, g, hd, t = 2, 2, 3, 64, 50
    cache, _, _ = _quant_cache(rng, b, kv, t, hd)
    q = jnp.asarray(rng.normal(size=(b, kv, g, 1, hd)), jnp.float32)
    qr = fwht(q[..., 0, :])
    kl = jnp.asarray([13, 50], jnp.int32)
    sm = 1.0 / np.sqrt(hd)
    r = b * kv
    args = (qr.reshape(r, g, hd),
            cache["k"].reshape(r, t, hd), cache["k_scale"].reshape(r, t),
            cache["v"].reshape(r, t, hd), cache["v_scale"].reshape(r, t),
            jnp.broadcast_to(kl[:, None], (b, kv)).reshape(r))
    acc_r, m_r, l_r = ad.decode_attn_q8_ref(
        qr, cache["k"], cache["k_scale"], cache["v"], cache["v_scale"], kl,
        sm_scale=sm)
    want = np.asarray(acc_r / l_r)
    for tt in (8, 16, 64):  # 50 is ragged for every one of these
        acc, m, l = ad.attn_decode_q8_pallas(*args, sm_scale=sm, tt=tt,
                                             interpret=True)
        got = np.asarray((acc / l).reshape(b, kv, g, hd))
        np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


def test_decode_matches_dequantized_cache_attention(rng):
    """The dequantize-free path == decode the cache, then fp attention."""
    b, kv, g, hd, t = 2, 2, 2, 64, 24
    cache, _, _ = _quant_cache(rng, b, kv, t, hd)
    q = jnp.asarray(rng.normal(size=(b, kv, g, 1, hd)), jnp.float32)
    k_tok_fp = jnp.asarray(rng.normal(size=(b, kv, 1, hd)), jnp.float32)
    v_tok_fp = jnp.asarray(rng.normal(size=(b, kv, 1, hd)), jnp.float32)
    ktok = kv_quant.kv_encode(k_tok_fp)
    vtok = kv_quant.kv_encode(v_tok_fp)
    kl = jnp.asarray([7, 24], jnp.int32)
    got = ad.decode_attn_q8(q, cache, ktok, vtok, kl, backend="ref")

    # reference: roundtrip the cache AND the token through the codec, then
    # ordinary fp attention with the same masking
    kf = kv_quant.kv_decode(cache["k"], cache["k_scale"])
    vf = kv_quant.kv_decode(cache["v"], cache["v_scale"])
    k_tok = kv_quant.kv_decode(*ktok)
    v_tok = kv_quant.kv_decode(*vtok)
    sm = 1.0 / np.sqrt(hd)
    s_c = jnp.einsum("bkgqd,bktd->bkgqt", q, kf) * sm
    mask = jnp.arange(t)[None, None, None, None, :] < kl[:, None, None, None, None]
    s_c = jnp.where(mask, s_c, -1e30)
    s_s = jnp.einsum("bkgqd,bktd->bkgqt", q, k_tok) * sm
    w = jax.nn.softmax(jnp.concatenate([s_c, s_s], -1), axis=-1)
    want = (jnp.einsum("bkgqt,bktd->bkgqd", w[..., :t], vf)
            + w[..., t:] * v_tok[:, :, None])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=1e-4)


def test_kernel_shape_gate():
    assert ad.kernel_supported(128)
    assert ad.kernel_supported(64)    # compiles for the chip at 64 too
    assert ad.kernel_supported(32)
    assert not ad.kernel_supported(48)    # non-pow2: never


# ---------------------------------------------------------------------------
# Model plumbing: quantized cache through forward/decode_step
# ---------------------------------------------------------------------------

def test_decode_step_matches_dequantized_reference():
    """Greedy decode over the int8 cache == decoding the SAME cache to fp
    and running the fp einsum path (the acceptance-criteria reference)."""
    cfg = reduced(get_config("smollm-135m"))
    params = lm.init_params(KEY, cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 11), 0,
                              cfg.vocab_size)
    qc = lm.init_cache(cfg, 2, 32, dtype=jnp.float32, kv_quant=True)
    _, qc, _ = lm.forward(params, toks[:, :10], RTQ, cfg, cache=qc, pos=0)
    fc = {"attn": {
        "k": kv_quant.kv_decode(qc["attn"]["k"], qc["attn"]["k_scale"]),
        "v": kv_quant.kv_decode(qc["attn"]["v"], qc["attn"]["v_scale"])}}
    pos = jnp.int32(10)
    for _ in range(3):
        dq, qc = lm.decode_step(params, toks[:, 10:11], qc, pos, RTQ, cfg)
        df, fc = lm.decode_step(params, toks[:, 10:11], fc, pos, RT, cfg)
        tq, tf = jnp.argmax(dq[:, 0], -1), jnp.argmax(df[:, 0], -1)
        assert bool(jnp.all(tq == tf))
        np.testing.assert_allclose(np.asarray(dq), np.asarray(df), atol=0.05)
        toks = jnp.concatenate([toks[:, :10], tq[:, None]], axis=1)
        pos = pos + 1


def test_hybrid_decode_matches_dequantized_reference():
    """The functional-write decode branch (hybrid's shared attention block
    runs without the scan-carry token cache) uses the same dequantize-free
    path: tokens match the decode-the-cache-then-attend reference."""
    cfg = reduced(get_config("zamba2-7b"))
    params = lm.init_params(KEY, cfg)
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, 9), 0,
                              cfg.vocab_size)
    qc = lm.init_cache(cfg, 2, 24, dtype=jnp.float32, kv_quant=True)
    _, qc, _ = lm.forward(params, toks[:, :8], RTQ, cfg, cache=qc, pos=0)
    fc = dict(qc)
    fc["attn"] = {
        "k": kv_quant.kv_decode(qc["attn"]["k"], qc["attn"]["k_scale"]),
        "v": kv_quant.kv_decode(qc["attn"]["v"], qc["attn"]["v_scale"])}
    pos = jnp.int32(8)
    for _ in range(3):
        dq, qc = lm.decode_step(params, toks[:, 8:9], qc, pos, RTQ, cfg)
        df, fc = lm.decode_step(params, toks[:, 8:9], fc, pos, RT, cfg)
        tq, tf = jnp.argmax(dq[:, 0], -1), jnp.argmax(df[:, 0], -1)
        assert bool(jnp.all(tq == tf))
        np.testing.assert_allclose(np.asarray(dq), np.asarray(df), atol=0.05)
        toks = jnp.concatenate([toks[:, :8], tq[:, None]], axis=1)
        pos = pos + 1


def test_stats_per_token_excludes_recurrent_state():
    cfg = reduced(get_config("rwkv6-3b"))
    params = lm.init_params(KEY, cfg)
    eng = ServeEngine(params, cfg, slots=1, max_len=16, rt=RT)
    assert eng.stats()["cache_bytes_per_token"] == 0  # attention-free
    assert eng.cache_bytes > 0  # ...but the recurrent state is counted


def test_init_cache_quant_layout_and_bytes():
    cfg = reduced(get_config("smollm-135m"))
    c = lm.init_cache(cfg, 2, 16, kv_quant=True)["attn"]
    hd = cfg.resolved_head_dim
    assert c["k"].dtype == jnp.int8 and c["k"].shape[-1] == hd
    assert c["k_scale"].dtype == jnp.float16 and c["k_scale"].shape[-1] == 1
    # bytes/token matches the configs helper exactly
    per_tok = sum(a.nbytes for a in c.values()) / (2 * 16)
    assert per_tok == kv_cache_bytes_per_token(cfg, kv_quant=True)
    # ~0.52x of the bf16 layout for pow2 head dims
    ratio = (kv_cache_bytes_per_token(cfg, kv_quant=True)
             / kv_cache_bytes_per_token(cfg, kv_quant=False))
    assert abs(ratio - kv_quant.cache_bytes_ratio(hd)) < 1e-6
    assert 0.5 < ratio < 0.54


def test_init_cache_quant_rejects_odd_head_dim():
    cfg = reduced(get_config("smollm-135m"))
    import dataclasses
    bad = dataclasses.replace(cfg, head_dim=48)
    with pytest.raises(ValueError, match="power-of-two"):
        lm.init_cache(bad, 1, 8, kv_quant=True)


# ---------------------------------------------------------------------------
# Engine: hot-loop invariants under kv_quant
# ---------------------------------------------------------------------------

def test_engine_kv_quant_backend_parity_and_one_sync():
    """pallas(interpret) and ref backends emit identical greedy streams,
    and the 1-transfer-per-step discipline survives quantization."""
    cfg = reduced(get_config("smollm-135m"))
    params = lm.init_params(KEY, cfg)
    outs = {}
    for backend in ("ref", "pallas"):
        rt = Runtime(compute_dtype=jnp.float32, kv_quant=True,
                     backend=backend)
        eng = ServeEngine(params, cfg, slots=2, max_len=32, rt=rt)
        reqs = [Request(rid=i, prompt=np.arange(4 + i) + 1, max_new=5)
                for i in range(2)]
        assert eng.admit(reqs) == 2
        assert eng.host_syncs == 1
        for _ in range(4):
            before = eng.host_syncs
            eng.step()
            assert eng.host_syncs - before == 1
        outs[backend] = [r.out for r in reqs]
    assert outs["ref"] == outs["pallas"]


def test_engine_kv_quant_vs_ssm_noop():
    """kv_quant on an attention-free arch is a no-op (no attn cache)."""
    cfg = reduced(get_config("rwkv6-3b"))
    params = lm.init_params(KEY, cfg)
    eng = ServeEngine(params, cfg, slots=1, max_len=24, rt=RTQ)
    [r] = eng.run([Request(rid=0, prompt=np.arange(5) + 1, max_new=3)])
    assert len(r.out) >= 3


@pytest.mark.parametrize("arch", ["smollm-135m", "zamba2-7b", "olmoe-1b-7b"])
def test_engine_cache_bytes_shrink(arch):
    cfg = reduced(get_config(arch))
    params = lm.init_params(KEY, cfg)
    attn_leaves = lambda e: e.cache.get("attn", {})
    eng_f = ServeEngine(params, cfg, slots=2, max_len=32, rt=RT,
                        cache_dtype=jnp.bfloat16)
    eng_q = ServeEngine(params, cfg, slots=2, max_len=32, rt=RTQ)
    fb = sum(a.nbytes for a in attn_leaves(eng_f).values())
    qb = sum(a.nbytes for a in attn_leaves(eng_q).values())
    ratio = qb / fb
    want = kv_quant.cache_bytes_ratio(cfg.resolved_head_dim)
    assert abs(ratio - want) < 1e-6, (ratio, want)
    assert eng_q.cache_bytes < eng_f.cache_bytes
    assert eng_q.stats()["cache_bytes"] == eng_q.cache_bytes


def test_engine_kv_quant_matches_dequant_reference_rollout():
    """Acceptance: engine greedy stream under kv_quant == hand-rolled
    prefill+decode over the same quantized cache (which tests the whole
    write-encoded / read-quantized plumbing end to end)."""
    cfg = reduced(get_config("qwen1.5-0.5b"))
    params = lm.init_params(KEY, cfg)
    prompt = (np.arange(6) + 1) % cfg.vocab_size
    eng = ServeEngine(params, cfg, slots=1, max_len=32, rt=RTQ, prompt_pad=8)
    [req] = eng.run([Request(rid=0, prompt=prompt, max_new=4)])

    cache = lm.init_cache(cfg, 1, 32, dtype=jnp.float32, kv_quant=True)
    logits, cache, _ = lm.forward(params, jnp.asarray(prompt[None]), RTQ,
                                  cfg, cache=cache, pos=0)
    out = [int(jnp.argmax(logits[0, -1]))]
    pos = len(prompt)
    for _ in range(3):
        l, cache = lm.decode_step(params, jnp.asarray([[out[-1]]], jnp.int32),
                                  cache, jnp.int32(pos), RTQ, cfg)
        out.append(int(jnp.argmax(l[0, 0])))
        pos += 1
    assert req.out[:4] == out[:4]


# ---------------------------------------------------------------------------
# Prefill: fused q-tile kernel vs dequantize-then-attend reference
# ---------------------------------------------------------------------------

def _dequant_prefill_reference(q, cache, kv_len, q_offset):
    """PR-4-era composition: decode the WHOLE cache, then fp attention with
    the same kv_len + causal(q_offset) masks — the oracle the fused q-tile
    path replaces."""
    b, kv, g, span, hd = q.shape
    t = cache["k"].shape[2]
    kf = kv_quant.kv_decode(cache["k"], cache["k_scale"])
    vf = kv_quant.kv_decode(cache["v"], cache["v_scale"])
    sm = 1.0 / np.sqrt(hd)
    s = jnp.einsum("bkgqd,bktd->bkgqt", q, kf) * sm
    kpos = jnp.arange(t)[None, None, None, None, :]
    qpos = (q_offset[:, None] + jnp.arange(span))[:, None, None, :, None]
    mask = (kpos < kv_len[:, None, None, None, None]) & (kpos <= qpos)
    w = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1)
    return jnp.einsum("bkgqt,bktd->bkgqd", w, vf)


@pytest.mark.parametrize("b,kv,g,hd,t,span", [
    (2, 1, 4, 32, 48, 7), (1, 3, 2, 64, 33, 16), (2, 2, 1, 128, 24, 24),
])
def test_prefill_matches_dequantize_reference(rng, b, kv, g, hd, t, span):
    """Fused q-tile path == dequantize-the-cache-then-attend, per-row
    ragged offsets, both backends."""
    cache, _, _ = _quant_cache(rng, b, kv, t, hd)
    q = jnp.asarray(rng.normal(size=(b, kv, g, span, hd)), jnp.float32)
    off = jnp.asarray(rng.integers(0, t - span + 1, size=b), jnp.int32)
    kl = off + span
    want = _dequant_prefill_reference(q, cache, kl, off)
    for kwargs in (dict(backend="ref"),
                   dict(backend="pallas", interpret=True)):
        got = ad.prefill_attn_q8(q, cache, kl, off, **kwargs)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=1e-4)


def test_prefill_kernel_tiling_invariant(rng):
    """Multi-tile online softmax over BOTH grid axes == single-pass
    reference: ragged kv width for every (tq, tt) choice."""
    b, kv, g, hd, t, span = 2, 2, 3, 64, 50, 12
    cache, _, _ = _quant_cache(rng, b, kv, t, hd)
    q = jnp.asarray(rng.normal(size=(b, kv, g, span, hd)), jnp.float32)
    off = jnp.asarray([13, 38], jnp.int32)
    kl = off + span
    want = np.asarray(ad.prefill_attn_q8(q, cache, kl, off, backend="ref"))
    for tq in (1, 5, 8, 16):
        for tt in (8, 64):  # 50 keys is ragged for both
            got = ad.prefill_attn_q8(q, cache, kl, off, backend="pallas",
                                     interpret=True, tq=tq, tt=tt)
            np.testing.assert_allclose(np.asarray(got), want,
                                       atol=1e-5, rtol=1e-5)


def test_prefill_causal_boundary_at_span_edge(rng):
    """Row i of the span sees exactly positions <= q_offset + i: a width-1
    span through the prefill entry (post-write cache, causal mask) must
    match the decode entry (pre-write cache + merged self term) on the
    same token."""
    b, kv, g, hd, t = 2, 2, 2, 64, 20
    pos = 9
    cache, k, v = _quant_cache(rng, b, kv, t, hd)
    q = jnp.asarray(rng.normal(size=(b, kv, g, 1, hd)), jnp.float32)
    pos_vec = jnp.full((b,), pos, jnp.int32)
    # decode view: the cache does NOT yet hold the token at `pos`
    ktok = (cache["k"][:, :, pos:pos + 1], cache["k_scale"][:, :, pos:pos + 1])
    vtok = (cache["v"][:, :, pos:pos + 1], cache["v_scale"][:, :, pos:pos + 1])
    dec = ad.decode_attn_q8(q, cache, ktok, vtok, pos_vec, backend="ref")
    # prefill view: same token already written at `pos`, causal mask stops
    # the span at its own edge — positions > pos must contribute nothing
    pre = ad.prefill_attn_q8(q, cache, pos_vec + 1, pos_vec, backend="ref")
    np.testing.assert_allclose(np.asarray(pre), np.asarray(dec),
                               atol=2e-5, rtol=1e-4)
    pre_k = ad.prefill_attn_q8(q, cache, pos_vec + 1, pos_vec,
                               backend="pallas", interpret=True, tq=4, tt=8)
    np.testing.assert_allclose(np.asarray(pre_k), np.asarray(dec),
                               atol=2e-5, rtol=1e-4)


def test_pallas_backend_shape_gate_fails_fast():
    """Forced backend="pallas" on a shape the kernel can't lower raises the
    named gate up front (mirroring qmatmul's dispatch errors) instead of
    dying inside Pallas lowering."""
    rng = np.random.default_rng(0)

    def args(hd, span):
        # raw planes (not kv_encode: the codec itself rejects non-pow2) —
        # the gate must fire before any array math happens
        cache = {
            "k": jnp.asarray(rng.integers(-127, 128, size=(1, 1, 16, hd)),
                             jnp.int8),
            "v": jnp.asarray(rng.integers(-127, 128, size=(1, 1, 16, hd)),
                             jnp.int8),
            "k_scale": jnp.ones((1, 1, 16, 1), jnp.float16),
            "v_scale": jnp.ones((1, 1, 16, 1), jnp.float16),
        }
        q = jnp.asarray(rng.normal(size=(1, 1, 2, span, hd)), jnp.float32)
        return q, cache

    q, cache = args(48, 1)  # non-pow2: never supported
    ktok = (cache["k"][:, :, :1], cache["k_scale"][:, :, :1])
    vtok = (cache["v"][:, :, :1], cache["v_scale"][:, :, :1])
    kl = jnp.asarray([8], jnp.int32)
    with pytest.raises(ValueError, match="power of two"):
        ad.decode_attn_q8(q, cache, ktok, vtok, kl, backend="pallas",
                          interpret=True)
    q, cache = args(48, 4)
    with pytest.raises(ValueError, match="power of two"):
        ad.prefill_attn_q8(q, cache, kl, jnp.asarray([4], jnp.int32),
                           backend="pallas", interpret=True)
    # the gate fires before lowering whatever the interpret mode
    with pytest.raises(ValueError, match="power of two"):
        ad.prefill_attn_q8(q, cache, kl, jnp.asarray([4], jnp.int32),
                           backend="pallas", interpret=False)
    q, cache = args(64, 4)
    with pytest.raises(ValueError, match="not in"):
        ad.prefill_attn_q8(q, cache, kl, jnp.asarray([4], jnp.int32),
                           backend="cuda")


# ---------------------------------------------------------------------------
# Model plumbing: prefill over the quantized cache never dequantizes it
# ---------------------------------------------------------------------------

def test_attention_apply_prefill_no_full_cache_dequant(monkeypatch):
    """Acceptance: the prefill branch streams codes — kv_decode over the
    cache buffer is GONE from the model path for every family."""
    import repro.models.layers as layers_mod

    assert not hasattr(layers_mod, "kv_decode")  # the import itself is gone
    monkeypatch.setattr(
        kv_quant, "kv_decode",
        lambda *a, **k: (_ for _ in ()).throw(
            AssertionError("prefill dequantized the cache buffer")))
    for arch in ("smollm-135m", "zamba2-7b"):
        cfg = reduced(get_config(arch))
        params = lm.init_params(KEY, cfg)
        toks = jax.random.randint(jax.random.PRNGKey(2), (2, 9), 0,
                                  cfg.vocab_size)
        cache = lm.init_cache(cfg, 2, 24, dtype=jnp.float32, kv_quant=True)
        logits, cache, _ = lm.forward(params, toks, RTQ, cfg, cache=cache,
                                      pos=0)
        assert bool(jnp.all(jnp.isfinite(logits)))
        # chunked continuation (pos > 0) takes the same fused path
        logits, _, _ = lm.forward(params, toks[:, :4], RTQ, cfg, cache=cache,
                                  pos=9)
        assert bool(jnp.all(jnp.isfinite(logits)))


def test_runtime_attn_tile_knobs_thread_through(rng, monkeypatch):
    """Runtime.attn_tile_q/attn_tile_k REACH the kernel (spied at the
    pallas entry — stream equality alone would also pass if the knobs were
    silently dropped) and forced-pallas streams are identical across tile
    choices."""
    import repro.kernels.attn_decode as ad_mod

    calls = []
    real = ad_mod.attn_q8_pallas

    def spy(*a, **kw):
        calls.append((kw.get("tq"), kw.get("tt"), kw.get("causal")))
        return real(*a, **kw)

    monkeypatch.setattr(ad_mod, "attn_q8_pallas", spy)
    cfg = reduced(get_config("smollm-135m"))
    params = lm.init_params(KEY, cfg)
    outs = {}
    for tiles in (None, (4, 8)):
        rt = Runtime(compute_dtype=jnp.float32, kv_quant=True,
                     backend="pallas",
                     attn_tile_q=None if tiles is None else tiles[0],
                     attn_tile_k=None if tiles is None else tiles[1])
        eng = ServeEngine(params, cfg, slots=2, max_len=32, rt=rt)
        calls.clear()
        reqs = [Request(rid=i, prompt=np.arange(4 + i) + 1, max_new=4)
                for i in range(2)]
        eng.run(reqs)
        outs[tiles] = [r.out for r in reqs]
        want_tq = ad.DEFAULT_TQ if tiles is None else tiles[0]
        want_tt = ad.DEFAULT_TT if tiles is None else tiles[1]
        # the admission wave's prefill call carries the q-tile knobs...
        assert (want_tq, want_tt, True) in calls, calls
        # ...and the decode steps the key-tile knob at tq=1
        assert (1, want_tt, False) in calls, calls
    assert outs[None] == outs[(4, 8)]


# ---------------------------------------------------------------------------
# Engine: prefill streams bit-identical to the PR 4 dequantize-then-attend
# composition (goldens captured at PR 4 HEAD on this CPU image)
# ---------------------------------------------------------------------------

# per threefry bit layout of the weights (tests/_prng.py)
GOLDEN_PR4_DENSE = {
    "legacy": [[37, 148, 42, 227, 11, 11], [37, 42, 108, 42, 227, 227]],
    "partitionable": [[318, 318, 318, 318, 152, 59],
                      [169, 318, 301, 169, 454, 469]],
}
GOLDEN_PR4_HYBRID = {
    "legacy": [[141, 272, 453, 227, 314, 430], [499, 77, 314, 299, 272, 77]],
    "partitionable": [[374, 391, 323, 40, 205, 205],
                      [0, 107, 346, 206, 65, 340]],
}


def test_engine_bucketed_prefill_stream_matches_pr4_head():
    cfg = reduced(get_config("smollm-135m"))
    params = lm.init_params(KEY, cfg)
    eng = ServeEngine(params, cfg, slots=2, max_len=48, rt=RTQ, prompt_pad=8)
    reqs = [Request(rid=i, prompt=(np.arange(6 + 3 * i) + 1) % cfg.vocab_size,
                    max_new=6) for i in range(2)]
    eng.run(reqs)
    assert [r.out for r in reqs] == GOLDEN_PR4_DENSE[prng_layout()]


def test_engine_chunk_ladder_prefill_stream_matches_pr4_head():
    """SSM/hybrid chunk-ladder admission (prompt lengths 11/13 with
    prompt_chunk=8 -> multi-chunk ladders incl. width-1 tail chunks) over
    the quantized cache: token streams bit-identical to PR 4 HEAD's
    whole-cache-dequantize prefill."""
    cfg = reduced(get_config("zamba2-7b"))
    params = lm.init_params(KEY, cfg)
    eng = ServeEngine(params, cfg, slots=2, max_len=48, rt=RTQ,
                      prompt_chunk=8)
    reqs = [Request(rid=i,
                    prompt=(np.arange(11 + 2 * i) + 1) % cfg.vocab_size,
                    max_new=6) for i in range(2)]
    eng.run(reqs)
    assert [r.out for r in reqs] == GOLDEN_PR4_HYBRID[prng_layout()]


# ---------------------------------------------------------------------------
# Tile-level early exit: ceil(kv_len/TT) clamped index maps (PR 6)
# ---------------------------------------------------------------------------

def test_early_exit_bitwise_matches_full_loop_decode(rng):
    """Decode kernel with clamped key-tile index maps is BITWISE equal to
    the full key loop — skipped tiles are exactly the fully-masked ones,
    so not one float may differ."""
    b, kv, g, hd, t = 1, 3, 2, 32, 640
    cache, _, _ = _quant_cache(rng, b, kv, t, hd)
    q = jnp.asarray(rng.normal(size=(b * kv, g, hd)), jnp.float32)
    r = b * kv
    args = (q, cache["k"].reshape(r, t, hd), cache["k_scale"].reshape(r, t),
            cache["v"].reshape(r, t, hd), cache["v_scale"].reshape(r, t),
            jnp.asarray([5, 300, 640], jnp.int32))  # tiny, mid, full rows
    for tt in (64, 128, 256):
        full = ad.attn_decode_q8_pallas(*args, sm_scale=hd ** -0.5, tt=tt,
                                        interpret=True, early_exit=False)
        fast = ad.attn_decode_q8_pallas(*args, sm_scale=hd ** -0.5, tt=tt,
                                        interpret=True, early_exit=True)
        for a, b_ in zip(full, fast):
            assert np.array_equal(np.asarray(a), np.asarray(b_)), tt


def test_early_exit_bitwise_matches_full_loop_prefill(rng):
    """Causal prefill: the per-query-tile limit (kv_len AND causal bound)
    clamps key tiles; bitwise parity with the unclamped loop across ragged
    offsets and tile widths."""
    r, t, g, hd, tq_total = 3, 512, 2, 32, 96
    kc = jnp.asarray(rng.integers(-127, 128, size=(r, t, hd)), jnp.int8)
    vc = jnp.asarray(rng.integers(-127, 128, size=(r, t, hd)), jnp.int8)
    ks = jnp.asarray(np.abs(rng.normal(size=(r, t))) * 0.02, jnp.float32)
    vs = jnp.asarray(np.abs(rng.normal(size=(r, t))) * 0.02, jnp.float32)
    q = jnp.asarray(rng.normal(size=(r, tq_total, g, hd)), jnp.float32)
    kl = jnp.asarray([100, 300, 512], jnp.int32)
    off = jnp.asarray([4, 204, 416], jnp.int32)  # spans end at kv_len
    for tq, tt in ((32, 64), (96, 128), (64, 256)):
        kw = dict(sm_scale=hd ** -0.5, causal=True, tq=tq, tt=tt,
                  interpret=True)
        full = ad.attn_q8_pallas(q, kc, ks, vc, vs, kl, off,
                                 early_exit=False, **kw)
        fast = ad.attn_q8_pallas(q, kc, ks, vc, vs, kl, off,
                                 early_exit=True, **kw)
        for a, b_ in zip(full, fast):
            assert np.array_equal(np.asarray(a), np.asarray(b_)), (tq, tt)


def test_early_exit_empty_rows(rng):
    """kv_len=0 rows (freshly admitted slots): the clamped index map floors
    at tile 0 and the masked update leaves the init state; the engine's
    self-token merge then owns the whole softmax."""
    r, t, g, hd = 2, 256, 1, 32
    kc = jnp.asarray(rng.integers(-127, 128, size=(r, t, hd)), jnp.int8)
    ks = jnp.asarray(np.abs(rng.normal(size=(r, t))) * 0.02, jnp.float32)
    q = jnp.asarray(rng.normal(size=(r, g, hd)), jnp.float32)
    kl = jnp.zeros((r,), jnp.int32)
    acc, m, l = ad.attn_decode_q8_pallas(
        q, kc, ks, kc, ks, kl, sm_scale=hd ** -0.5, tt=64, interpret=True)
    assert np.all(np.asarray(acc) == 0.0)
    assert np.all(np.asarray(l) == 0.0)
    assert np.all(np.asarray(m) == ad.NEG_INF)


# ---------------------------------------------------------------------------
# Attention tile autotuning: (tq, tt) in the shared autotune cache (PR 6)
# ---------------------------------------------------------------------------

def test_attn_tiles_roundtrip_and_interpret_defaults(tmp_path, monkeypatch):
    from repro.kernels import autotune as at

    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "tune.json"))
    at.clear_memory_cache()
    # miss -> deterministic defaults (the interpret-mode contract)
    assert at.get_attn_tiles(4096, 64, 8, interpret=True) == (
        ad.DEFAULT_TQ, ad.DEFAULT_TT)
    key = at.record_attn(4096, 64, 8, 64, 512, interpret=True, us=12.5)
    assert "attn" in key and "hd64" in key and "h8" in key
    assert at.get_attn_tiles(4096, 64, 8, interpret=True) == (64, 512)
    # T buckets to the next power of two: 3000 shares 4096's entry
    assert at.get_attn_tiles(3000, 64, 8, interpret=True) == (64, 512)
    # distinct head count = distinct entry
    assert at.get_attn_tiles(4096, 64, 4, interpret=True) == (
        ad.DEFAULT_TQ, ad.DEFAULT_TT)
    at.clear_memory_cache()


def test_autotune_attn_sweeps_and_records(tmp_path, monkeypatch):
    """Forced interpret-mode sweep on a tiny shape: every candidate runs,
    a winner lands in the cache, and the lookup the kernels use finds it."""
    from repro.kernels import autotune as at

    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "tune.json"))
    at.clear_memory_cache()
    best = at.autotune_attn(64, 32, 2, batch=1, decode=True, interpret=True,
                            iters=1, force_interpret_bench=True)
    assert best[0] == 1  # decode sweeps the TQ=1 specialization only
    assert at.get_attn_tiles(64, 32, 2, interpret=True) == best
    # without the force flag, interpret mode never benchmarks
    assert at.autotune_attn(128, 32, 2, interpret=True) == (
        ad.DEFAULT_TQ, ad.DEFAULT_TT)
    at.clear_memory_cache()


def test_decode_uses_tuned_tt(rng, tmp_path, monkeypatch):
    """decode_attn_q8(tt=None) resolves the key-tile width through the
    autotune cache (spied at the pallas entry)."""
    import repro.kernels.attn_decode as ad_mod
    from repro.kernels import autotune as at

    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "tune.json"))
    at.clear_memory_cache()
    b, kv, g, hd, t = 1, 2, 2, 32, 64
    at.record_attn(t, hd, kv, 1, 16, interpret=True)
    cache, _, _ = _quant_cache(rng, b, kv, t, hd)
    q = jnp.asarray(rng.normal(size=(b, kv, g, 1, hd)), jnp.float32)
    ktok = kv_quant.kv_encode(
        jnp.asarray(rng.normal(size=(b, kv, 1, hd)), jnp.float32))
    vtok = kv_quant.kv_encode(
        jnp.asarray(rng.normal(size=(b, kv, 1, hd)), jnp.float32))
    kl = jnp.asarray([t], jnp.int32)
    seen = []
    real = ad_mod.attn_decode_q8_pallas

    def spy(*a, **kw):
        seen.append(kw.get("tt"))
        return real(*a, **kw)

    monkeypatch.setattr(ad_mod, "attn_decode_q8_pallas", spy)
    out_tuned = ad.decode_attn_q8(q, cache, ktok, vtok, kl,
                                  backend="pallas", interpret=True)
    assert seen == [16]  # the recorded winner, not DEFAULT_TT
    out_default = ad.decode_attn_q8(q, cache, ktok, vtok, kl,
                                    backend="pallas", interpret=True,
                                    tt=ad_mod.DEFAULT_TT)
    np.testing.assert_allclose(np.asarray(out_tuned),
                               np.asarray(out_default), atol=1e-6)
    at.clear_memory_cache()


# ---------------------------------------------------------------------------
# Narrow-q-width tile family: speculative K+1 verify windows (PR 10)
# ---------------------------------------------------------------------------

def test_qwidth_key_family_and_fallback(tmp_path, monkeypatch):
    """Narrow verify spans get their own |q{bucket} autotune entries;
    lookup falls back to the base (wide-prefill) key, then to defaults."""
    from repro.kernels import autotune as at

    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "tune.json"))
    at.clear_memory_cache()
    # bucketing: pow2 round-up, distinct buckets = distinct keys
    assert at._bucket_q(1) == 1 and at._bucket_q(5) == 8
    assert at._attn_key(4096, 64, 8, interpret=True, q_width=5) \
        == at._attn_key(4096, 64, 8, interpret=True, q_width=8)
    assert at._attn_key(4096, 64, 8, interpret=True, q_width=5) \
        != at._attn_key(4096, 64, 8, interpret=True)
    # no entries at all -> defaults
    assert at.get_attn_tiles(4096, 64, 8, interpret=True, q_width=5) == (
        ad.DEFAULT_TQ, ad.DEFAULT_TT)
    # base (wide) winner recorded -> narrow lookup falls back to it
    at.record_attn(4096, 64, 8, 64, 512, interpret=True)
    assert at.get_attn_tiles(4096, 64, 8, interpret=True, q_width=5) == (
        64, 512)
    # dedicated narrow winner shadows the base entry for its bucket only
    at.record_attn(4096, 64, 8, 4, 128, interpret=True, q_width=5)
    assert at.get_attn_tiles(4096, 64, 8, interpret=True, q_width=5) == (
        4, 128)
    assert at.get_attn_tiles(4096, 64, 8, interpret=True, q_width=3) == (
        64, 512)  # different bucket: still the base entry
    assert at.get_attn_tiles(4096, 64, 8, interpret=True) == (64, 512)
    at.clear_memory_cache()


def test_qwidth_candidates_capped_at_bucket():
    from repro.kernels import autotune as at

    for qw in (1, 5, 8, 16):
        for tq, tt in at.attn_candidates(1024, 64, q_width=qw):
            assert tq <= at._bucket_q(qw)
    # wide prefill sweep is unchanged by the family's existence
    wide = at.attn_candidates(1024, 64)
    assert any(tq > at.SPEC_QWIDTH_MAX for tq, _ in wide)


def test_prefill_narrow_span_uses_qwidth_entry(rng, tmp_path, monkeypatch):
    """prefill_attn_q8 with a speculative-width span resolves tiles
    through the q-width key (spied at the pallas entry) and matches the
    default-tile output bitwise."""
    import repro.kernels.attn_decode as ad_mod
    from repro.kernels import autotune as at

    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "tune.json"))
    at.clear_memory_cache()
    b, kv, g, hd, t, span = 1, 2, 2, 32, 64, 5
    at.record_attn(t, hd, kv, 2, 16, interpret=True, q_width=span)
    cache, _, _ = _quant_cache(rng, b, kv, t, hd)
    q = jnp.asarray(rng.normal(size=(b, kv, g, span, hd)), jnp.float32)
    kl = jnp.asarray([t], jnp.int32)
    off = jnp.asarray([t - span], jnp.int32)
    seen = []
    real = ad_mod.attn_q8_pallas

    def spy(*a, **kw):
        seen.append((kw.get("tq"), kw.get("tt")))
        return real(*a, **kw)

    monkeypatch.setattr(ad_mod, "attn_q8_pallas", spy)
    out_tuned = ad.prefill_attn_q8(q, cache, kl, off, backend="pallas",
                                   interpret=True)
    assert seen == [(2, 16)]  # the narrow-span winner, not DEFAULT_TQ
    out_default = ad.prefill_attn_q8(q, cache, kl, off, backend="pallas",
                                     interpret=True, tq=ad_mod.DEFAULT_TQ,
                                     tt=ad_mod.DEFAULT_TT)
    np.testing.assert_allclose(np.asarray(out_tuned),
                               np.asarray(out_default), atol=1e-5)
    at.clear_memory_cache()
