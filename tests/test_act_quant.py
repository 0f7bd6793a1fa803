"""W3A8 integer compute path (rotation-domain activations, PR 8).

Covers the activation codec (isometry, scale safety), the int8 Pallas
kernels vs the integer reference, int-vs-float parity across every fused
format, the dispatch/policy plumbing, and the two contracts the PR must
not break: ``act_quant=False`` token streams stay bit-identical to PR 7
HEAD, and the restructured ref path materializes no full-weight-size f32
tensor before the contraction.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _prng import prng_layout

from repro.configs.base import get_config, reduced
from repro.core import formats
from repro.core.act_quant import ACT_QMAX, act_decode, act_encode
from repro.core.fwht import blocked_fwht
from repro.core.qlinear import qmatmul
from repro.core.quantize import QMeta
from repro.kernels import ref
from repro.kernels.itq3_matmul import itq3_matmul_int8_pallas
from repro.kernels.itq3_matvec import MATVEC_MAX_M, itq3_matvec_int8_pallas
from repro.models import lm
from repro.models.layers import Runtime
from repro.serve.engine import Request, ServeEngine
from repro.serve.quantized import (MATMUL_LEAVES, QuantPolicy, QuantRule,
                                   quantize_params)

KEY = jax.random.PRNGKey(0)
FUSED_FMTS = ["itq3_s", "itq3_s_sub", "itq3_x", "iq3_s", "quip3"]


def _rel_l2(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.linalg.norm(a - b) / max(np.linalg.norm(a), 1e-12)


def _encode_for(qt, x):
    """Activation codes matching a QTensor's rotation convention."""
    m = qt.meta
    return act_encode(x, block=m.block, rotate=m.rotate,
                      dsign=qt.data.get("dsign"))


# ---------------------------------------------------------------------------
# Codec: FWHT isometry + scale safety
# ---------------------------------------------------------------------------

def test_codec_isometry_roundtrip(rng):
    """encode rotates into the Hadamard domain; decode + one more (self-
    inverse) FWHT lands back on x within int8 quantization error."""
    x = jnp.asarray(rng.normal(size=(4, 512)), jnp.float32)
    codes, scale = act_encode(x, rotate=True)
    assert codes.dtype == jnp.int8 and scale.shape == (4, 1)
    back = blocked_fwht(act_decode(codes, scale), 256)
    assert _rel_l2(x, back) < 2e-2
    # rotate=False is the identity codec (plain per-row absmax int8)
    codes0, scale0 = act_encode(x, rotate=False)
    assert _rel_l2(x, act_decode(codes0, scale0)) < 2e-2


def test_codec_dot_isometry(rng):
    """The load-bearing identity: x . Hw == (Hx) . w per block."""
    x = jnp.asarray(rng.normal(size=(256,)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(256,)), jnp.float32)
    lhs = jnp.dot(x, blocked_fwht(w[None], 256)[0])
    rhs = jnp.dot(blocked_fwht(x[None], 256)[0], w)
    np.testing.assert_allclose(np.asarray(lhs), np.asarray(rhs), atol=1e-3)


def test_codec_scale_extremes(rng):
    """Rows at 1e6/1e7/1e-7 magnitudes and an all-zero row: codes stay in
    the int8 grid, scales stay finite, zero rows produce zero codes and a
    zero scale (no 0/0 NaN), and nonzero rows use the full grid."""
    base = rng.normal(size=(4, 512)).astype(np.float32)
    base[3] = 0.0
    mags = np.asarray([1e6, 1e7, 1e-7, 1.0], np.float32)[:, None]
    x = jnp.asarray(base * mags)
    codes, scale = act_encode(x, rotate=True)
    c, s = np.asarray(codes), np.asarray(scale)
    assert np.all(np.isfinite(s)) and np.all(np.abs(c) <= ACT_QMAX)
    assert np.all(c[3] == 0) and s[3, 0] == 0.0
    for row in range(3):  # absmax rule pins the largest element to +-127
        assert np.max(np.abs(c[row])) == ACT_QMAX
    assert np.all(np.isfinite(np.asarray(act_decode(codes, scale))))


def test_codec_dsign_matches_manual_fold(rng):
    """quip3 convention: dsign multiplies x per block before the FWHT."""
    x = jnp.asarray(rng.normal(size=(3, 512)), jnp.float32)
    dsign = jnp.asarray(rng.choice([-1.0, 1.0], size=(2, 256)), jnp.float32)
    got_c, got_s = act_encode(x, rotate=True, dsign=dsign)
    folded = (x.reshape(3, 2, 256) * dsign).reshape(3, 512)
    want_c, want_s = act_encode(folded, rotate=True)
    assert np.array_equal(np.asarray(got_c), np.asarray(want_c))
    assert np.array_equal(np.asarray(got_s), np.asarray(want_s))


# ---------------------------------------------------------------------------
# Kernels: int8 Pallas variants vs the integer reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fmt", FUSED_FMTS)
@pytest.mark.parametrize("hoist", [False, True])
def test_int8_kernel_matches_int8_ref(rng, fmt, hoist):
    w = jnp.asarray(rng.standard_t(df=4, size=(512, 320)) * 0.02, jnp.float32)
    x = jnp.asarray(rng.normal(size=(24, 512)), jnp.float32)
    qt = formats.quantize(w, fmt)
    xq, xs = _encode_for(qt, x)
    m = qt.meta
    args = (xq, xs, qt.data["plane2"], qt.data["plane1"],
            qt.data["scales"], qt.data["zps"])
    kw = dict(fivelevel=m.fivelevel, sub_blocks=m.sub_blocks)
    want = np.asarray(ref.itq3_matmul_int8_ref(*args, **kw))
    got = np.asarray(itq3_matmul_int8_pallas(
        *args, **kw, tm=8, tn=128, interpret=True, hoist=hoist))
    np.testing.assert_allclose(got, want, atol=1e-4)


@pytest.mark.parametrize("fmt", FUSED_FMTS)
@pytest.mark.parametrize("m", [1, MATVEC_MAX_M])
def test_int8_matvec_matches_int8_ref(rng, fmt, m):
    w = jnp.asarray(rng.normal(size=(512, 192)) * 0.05, jnp.float32)
    x = jnp.asarray(rng.normal(size=(m, 512)), jnp.float32)
    qt = formats.quantize(w, fmt)
    xq, xs = _encode_for(qt, x)
    meta = qt.meta
    args = (xq, xs, qt.data["plane2"], qt.data["plane1"],
            qt.data["scales"], qt.data["zps"])
    kw = dict(fivelevel=meta.fivelevel, sub_blocks=meta.sub_blocks)
    want = np.asarray(ref.itq3_matmul_int8_ref(*args, **kw))
    got = np.asarray(itq3_matvec_int8_pallas(*args, **kw, tn=64,
                                             interpret=True))
    np.testing.assert_allclose(got, want, atol=1e-4)


# ---------------------------------------------------------------------------
# Parity: integer path vs float path, both backends, ragged shapes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fmt", FUSED_FMTS)
@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_int_vs_float_parity_formats(rng, fmt, backend):
    """qmatmul(act_quant=True) tracks the float contraction within the
    int8 activation-quantization error on every registered fused format."""
    w = jnp.asarray(rng.standard_t(df=4, size=(512, 320)) * 0.02, jnp.float32)
    x = jnp.asarray(rng.normal(size=(6, 512)), jnp.float32)
    qt = formats.quantize(w, fmt)
    kw = dict(mode="activations", backend=backend, compute_dtype=jnp.float32,
              interpret=True)
    y_float = np.asarray(qmatmul(x, qt, **kw))
    y_int = np.asarray(qmatmul(x, qt, act_quant=True, **kw))
    assert _rel_l2(y_float, y_int) < 5e-2
    # and both track the dequantized oracle
    y0 = np.asarray(jnp.matmul(x, formats.dequantize(qt, jnp.float32)))
    assert _rel_l2(y0, y_int) < 5e-2


@pytest.mark.parametrize("m,n,k", [
    (1, 128, 300),     # decode-shaped matvec dispatch, ragged K -> pad 512
    (4, 192, 576),     # matvec dispatch, ragged K -> pad 768
    (130, 320, 576),   # tiled dispatch, ragged M/N/K vs tiles
    (256, 256, 512),   # tile-aligned
])
def test_act_quant_dispatch_shapes(rng, m, n, k):
    """Backend parity through the public entrypoint: the pallas dispatch
    (matvec for m <= MATVEC_MAX_M, tiled above) matches the ref integer
    contraction on ragged non-multiple-of-256 K."""
    w = jnp.asarray(rng.normal(size=(k, n)) * 0.05, jnp.float32)
    x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    qt = formats.quantize(w, "itq3_s")
    kw = dict(mode="activations", compute_dtype=jnp.float32,
              act_quant=True, interpret=True)
    y_ref = np.asarray(qmatmul(x, qt, backend="ref", **kw))
    y_pal = np.asarray(qmatmul(x, qt, backend="pallas", **kw))
    np.testing.assert_allclose(y_pal, y_ref, atol=2e-3)
    y0 = np.asarray(jnp.matmul(x, formats.dequantize(qt, jnp.float32)))
    assert _rel_l2(y0, y_pal) < 5e-2


# ---------------------------------------------------------------------------
# Satellite (a): ref-path cast traffic — codes stay int8 until the MAC
# ---------------------------------------------------------------------------

def _big_f32_eqns(jaxpr, thresh):
    hits = []

    def walk(j):
        for eqn in j.eqns:
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)
            for v in eqn.outvars:
                aval = v.aval
                if (getattr(aval, "dtype", None) == jnp.float32
                        and np.prod(aval.shape, dtype=int) >= thresh):
                    hits.append((eqn.primitive.name, tuple(aval.shape)))

    walk(jaxpr.jaxpr)
    return hits


def test_ref_cast_traffic_budget(rng):
    """The PR 5 leftover, fixed. Integer path: codes stay int8 until the
    MAC — ZERO weight-size f32 tensors anywhere in the jaxpr (the mixed
    f32 x int8 dot converts inside the GEMM). Float path: the exact
    integer zero-point fold removed the decode -> subtract -> correction
    chain, leaving one fused scale-and-cast (convert + mul, a single
    elementwise fusion for XLA) feeding one full-K GEMM — at most two
    weight-size f32 equations, and the self-contained ref oracle
    (kernels/ref.py) also carries none."""
    K, N = 512, 768
    w = jnp.asarray(rng.normal(size=(K, N)) * 0.05, jnp.float32)
    x = jnp.asarray(rng.normal(size=(4, K)), jnp.float32)
    qt = formats.quantize(w, "itq3_s")
    thresh = N * K

    int8_jaxpr = jax.make_jaxpr(lambda a: qmatmul(
        a, qt, mode="activations", backend="ref", act_quant=True,
        compute_dtype=jnp.float32))(x)
    assert _big_f32_eqns(int8_jaxpr, thresh) == []

    oracle_jaxpr = jax.make_jaxpr(lambda a: ref.itq3_matmul_ref(
        a, qt.data["plane2"], qt.data["plane1"], qt.data["scales"],
        qt.data["zps"], rotate_weights=False))(x)
    assert _big_f32_eqns(oracle_jaxpr, thresh) == []

    float_jaxpr = jax.make_jaxpr(lambda a: qmatmul(
        a, qt, mode="activations", backend="ref",
        compute_dtype=jnp.float32))(x)
    hits = _big_f32_eqns(float_jaxpr, thresh)
    assert len(hits) <= 2, hits


# ---------------------------------------------------------------------------
# Policy + meta plumbing
# ---------------------------------------------------------------------------

def test_qmeta_act_quant_backcompat(rng):
    qt = formats.quantize(
        jnp.asarray(rng.normal(size=(256, 64)), jnp.float32), "itq3_s")
    assert qt.meta.act_quant is True  # checkpoints predating the field opt in
    d = qt.meta.to_dict()
    d.pop("act_quant")
    assert QMeta.from_dict(d).act_quant is True


def test_policy_act_quant_opt_out(rng):
    """QuantRule(act_quant=False) pins matching paths to the float
    contraction even when the runtime knob is on — bit-identical to the
    act_quant=False call — while opted-in paths take the integer path."""
    policy = QuantPolicy((
        QuantRule(r"(^|\.)lm_head$", "itq3_s", act_quant=False),
        QuantRule(MATMUL_LEAVES, "itq3_s"),
    ))
    params = {"lm_head": jnp.asarray(rng.normal(size=(256, 64)), jnp.float32),
              "wq": jnp.asarray(rng.normal(size=(256, 64)), jnp.float32)}
    qp = quantize_params(params, policy)
    assert qp["lm_head"].meta.act_quant is False
    assert qp["wq"].meta.act_quant is True
    # round-trips through the policy serialization
    rt = QuantPolicy.from_dict(policy.to_dict())
    assert rt.rules[0].act_quant is False and rt.rules[1].act_quant is None

    x = jnp.asarray(rng.normal(size=(3, 256)), jnp.float32)
    kw = dict(mode="activations", backend="ref", compute_dtype=jnp.float32)
    y_off = np.asarray(qmatmul(x, qp["lm_head"], **kw))
    y_on = np.asarray(qmatmul(x, qp["lm_head"], act_quant=True, **kw))
    assert np.array_equal(y_off, y_on)  # opted out: knob is a no-op
    z_off = np.asarray(qmatmul(x, qp["wq"], **kw))
    z_on = np.asarray(qmatmul(x, qp["wq"], act_quant=True, **kw))
    assert not np.array_equal(z_off, z_on)  # opted in: integer path taken
    assert _rel_l2(z_off, z_on) < 5e-2


def test_autotune_int8_key_family(tmp_path, monkeypatch):
    """int8-path winners live under their own key component; float-path
    entries are untouched and lookups never cross over."""
    from repro.kernels import autotune as at

    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "tune.json"))
    at.clear_memory_cache()
    key = at.record(8, 320, 512, "itq3_s", 16, 64, interpret=True,
                    act_quant=True, us=3.0)
    assert "|int8|" in key
    fkey = at.record(8, 320, 512, "itq3_s", 32, 128, interpret=True, us=5.0)
    assert "int8" not in fkey and key != fkey
    assert at.get_tiles(8, 320, 512, "itq3_s", interpret=True,
                        act_quant=True) == (16, 64)
    assert at.get_tiles(8, 320, 512, "itq3_s", interpret=True) == (32, 128)
    # untuned int8 shape -> deterministic defaults (interpret contract)
    assert at.get_tiles(8, 320, 1024, "itq3_s", interpret=True,
                        act_quant=True) == (at.DEFAULT_TM, at.DEFAULT_TN)
    at.clear_memory_cache()


# ---------------------------------------------------------------------------
# Engine: act_quant=False streams bit-identical to PR 7 HEAD (goldens
# captured on this CPU image before any PR 8 code change), act_quant=True
# passes tolerance-based quality parity, stats() reports the knob.
# ---------------------------------------------------------------------------

GOLDEN_PR7 = {  # per threefry bit layout of the weights (tests/_prng.py)
    "legacy": {
        ("smollm-135m", "itq3_s", True): [[227, 227, 227, 227, 198, 198],
                                          [227, 227, 227, 227, 51, 51]],
        ("smollm-135m", "itq3_x", False): [[291, 242, 83, 83, 370, 83],
                                           [242, 344, 344, 344, 173, 173]],
        ("zamba2-7b", "itq3_s_sub", True): [[148, 153, 186, 222, 153, 223],
                                            [147, 432, 224, 432, 448, 431]],
    },
    "partitionable": {
        ("smollm-135m", "itq3_s", True): [[33, 33, 33, 33, 33, 33],
                                          [33, 33, 33, 33, 179, 179]],
        ("smollm-135m", "itq3_x", False): [[36, 229, 229, 294, 412, 349],
                                           [492, 33, 36, 36, 264, 264]],
        ("zamba2-7b", "itq3_s_sub", True): [[193, 509, 509, 258, 258, 258],
                                            [426, 397, 397, 397, 65, 65]],
    },
}


def _run_engine(arch, fmt, kv_quant, act_quant):
    cfg = reduced(get_config(arch))
    params = quantize_params(lm.init_params(KEY, cfg), fmt)
    rt = Runtime(compute_dtype=jnp.float32, kv_quant=kv_quant,
                 capacity_factor=8.0, act_quant=act_quant)
    eng = ServeEngine(params, cfg, slots=2, max_len=48, rt=rt)
    reqs = [Request(rid=i, prompt=(np.arange(6 + 3 * i) + 1) % cfg.vocab_size,
                    max_new=6) for i in range(2)]
    eng.run(reqs)
    return eng, [list(map(int, r.out)) for r in reqs]


@pytest.mark.parametrize("arch,fmt,kvq",
                         sorted(GOLDEN_PR7["legacy"], key=str))
def test_engine_streams_bit_identical_to_pr7_head(arch, fmt, kvq):
    eng, streams = _run_engine(arch, fmt, kvq, act_quant=False)
    assert streams == GOLDEN_PR7[prng_layout()][(arch, fmt, kvq)]
    assert eng.stats()["act_quant"] is False


def test_engine_act_quant_stream_quality_parity():
    """Greedy streams under the integer path: tolerance-based parity (the
    int8 codec perturbs logits ~1-2% rel L2, so near-total token
    agreement, not bitwise equality, is the contract)."""
    eng, streams = _run_engine("smollm-135m", "itq3_s", True, act_quant=True)
    golden = GOLDEN_PR7[prng_layout()][("smollm-135m", "itq3_s", True)]
    agree = sum(a == b for s, g in zip(streams, golden)
                for a, b in zip(s, g))
    total = sum(len(g) for g in golden)
    assert agree >= total - 2, (streams, golden)
    st = eng.stats()
    assert st["act_quant"] is True and "kv_quant" in st and "backend" in st


def test_model_logits_parity_act_quant():
    """Full-model logits under the integer path stay within the measured
    codec error envelope (1.4% smollm / 2.2% zamba on this image)."""
    cfg = reduced(get_config("smollm-135m"))
    params = quantize_params(lm.init_params(KEY, cfg), "itq3_s")
    toks = jnp.asarray((np.arange(24) + 1) % cfg.vocab_size)[None, :]
    outs = {}
    for aq in (False, True):
        rt = Runtime(compute_dtype=jnp.float32, act_quant=aq)
        outs[aq] = np.asarray(lm.forward(params, toks, rt, cfg)[0])
    assert _rel_l2(outs[False], outs[True]) < 6e-2
    agree = np.mean(outs[False].argmax(-1) == outs[True].argmax(-1))
    assert agree >= 0.8
