"""Unified quantization API: format registry, QuantPolicy, backend-dispatching
qmatmul, and QTensor checkpointing."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import formats, qlinear
from repro.core.quantize import QMeta, QTensor
from repro.checkpoint import ckpt
from repro.serve.quantized import (
    MATMUL_LEAVES, QuantPolicy, QuantRule, describe_quantized, quantize_params,
)

TERNARY = ["iq3_s", "quip3", "itq3_s", "itq3_s_sub", "itq3_x"]


def heavy_tailed(rng, k=512, n=96, scale=0.02):
    return jnp.asarray(rng.standard_t(df=4, size=(k, n)) * scale, jnp.float32)


# ---------------------------------------------------------------------------
# Format registry
# ---------------------------------------------------------------------------

def test_registry_contents():
    for name in TERNARY + ["fp16", "bf16", "q8_0", "q4_0"]:
        spec = formats.get_format(name)
        assert spec.name == name
        assert spec.supports_fused == (name in TERNARY)
    with pytest.raises(ValueError):
        formats.get_format("no_such_fmt")


def test_register_custom_format(rng):
    """A third-party format plugs in via @register_format and flows through
    quantize/dequantize/qmatmul with zero changes elsewhere."""

    @formats.register_format
    class Demo(formats.TernaryFormat):
        def __init__(self):
            super().__init__("itq3_demo", rotate=True, sub_blocks=4)

    try:
        w = heavy_tailed(rng)
        qt = formats.quantize(w, "itq3_demo")
        assert qt.meta.fmt == "itq3_demo" and qt.meta.sub_blocks == 4
        wh = formats.dequantize(qt, jnp.float32)
        assert wh.shape == w.shape
        x = jnp.asarray(rng.normal(size=(3, 512)), jnp.float32)
        y0 = qlinear.qmatmul(x, qt, mode="dequant", compute_dtype=jnp.float32)
        ya = qlinear.qmatmul(x, qt, mode="activations", backend="ref",
                             compute_dtype=jnp.float32)
        yp = qlinear.qmatmul(x, qt, mode="weights", backend="pallas",
                             interpret=True, tm=8, tn=32,
                             compute_dtype=jnp.float32)
        np.testing.assert_allclose(np.asarray(ya), np.asarray(y0), atol=2e-3)
        np.testing.assert_allclose(np.asarray(yp), np.asarray(y0), atol=2e-3)
    finally:
        del formats.FORMATS["itq3_demo"]


def test_sub_blocks_override(rng):
    w = heavy_tailed(rng)
    qt = formats.quantize(w, "itq3_s", sub_blocks=4)
    assert qt.meta.sub_blocks == 4
    assert qt.data["scales"].shape[-1] == 4
    wh = formats.dequantize(qt, jnp.float32)
    rel = float(jnp.linalg.norm(wh - w) / jnp.linalg.norm(w))
    assert rel < 0.8


# ---------------------------------------------------------------------------
# Unified qmatmul backend dispatch
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fmt", TERNARY)
@pytest.mark.parametrize("mode", ["weights", "activations", "auto"])
def test_backend_parity(rng, fmt, mode):
    """ref and pallas backends agree for every registered ternary format."""
    w = heavy_tailed(rng)
    x = jnp.asarray(rng.normal(size=(6, 512)), jnp.float32)
    qt = formats.quantize(w, fmt)
    yr = qlinear.qmatmul(x, qt, mode=mode, backend="ref",
                         compute_dtype=jnp.float32)
    yp = qlinear.qmatmul(x, qt, mode=mode, backend="pallas", interpret=True,
                         tm=8, tn=32, compute_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(yp), np.asarray(yr), atol=2e-3)


def test_backend_pallas_falls_back_for_dense_formats(rng):
    """Non-fused formats (and mode="dequant") serve through the ref path even
    under backend="pallas" — one code path for mixed-precision trees."""
    w = heavy_tailed(rng)
    x = jnp.asarray(rng.normal(size=(2, 512)), jnp.float32)
    for fmt in ("q8_0", "bf16"):
        qt = formats.quantize(w, fmt)
        y0 = qlinear.qmatmul(x, qt, mode="dequant", compute_dtype=jnp.float32)
        yp = qlinear.qmatmul(x, qt, mode="activations", backend="pallas",
                             compute_dtype=jnp.float32)
        np.testing.assert_allclose(np.asarray(yp), np.asarray(y0), atol=1e-5)


def test_backend_validation(rng):
    qt = formats.quantize(heavy_tailed(rng), "itq3_s")
    x = jnp.ones((2, 512), jnp.float32)
    with pytest.raises(ValueError):
        qlinear.qmatmul(x, qt, backend="cuda")


# ---------------------------------------------------------------------------
# QuantPolicy
# ---------------------------------------------------------------------------

def fake_params(rng):
    arr = lambda *s: jnp.asarray(rng.normal(size=s) * 0.05, jnp.float32)
    return {
        "embed": arr(300, 128),
        "layers": {
            "attn": {"wq": arr(128, 128), "wo": arr(128, 128)},
            "mlp": {"gate": arr(128, 256), "up": arr(128, 256),
                    "down": arr(256, 128)},
            "ln1": {"scale": jnp.ones((128,), jnp.float32)},
            "moe": {"router": arr(128, 8)},
        },
        "lm_head": arr(128, 300),
    }


def test_policy_precedence_first_match_wins(rng):
    policy = QuantPolicy((
        (r"(^|\.)lm_head$", "q8_0"),
        (r"(^|\.)(gate|up|down)$", "itq3_s_sub"),
        (MATMUL_LEAVES, "itq3_s"),
    ))
    q = quantize_params(fake_params(rng), policy)
    got = describe_quantized(q)
    assert got["lm_head"] == "q8_0"
    assert got["layers.mlp.down"] == "itq3_s_sub"
    assert got["layers.attn.wq"] == "itq3_s"
    # safety rails: norms / tiny router / unmatched embed stay fp
    assert "layers.ln1.scale" not in got
    assert "layers.moe.router" not in got
    assert "embed" not in got


def test_policy_none_fmt_pins_fp(rng):
    policy = QuantPolicy((
        (r"(^|\.)wq$", None),  # explicit fp pin beats the catch-all below
        (MATMUL_LEAVES, "itq3_s"),
    ))
    got = describe_quantized(quantize_params(fake_params(rng), policy))
    assert "layers.attn.wq" not in got
    assert got["layers.attn.wo"] == "itq3_s"


def test_policy_full_path_rules(rng):
    """Rules see the whole dotted path, not just the leaf name."""
    policy = QuantPolicy((
        (r"^layers\.mlp\.", "itq3_x"),
        (MATMUL_LEAVES, "itq3_s"),
    ))
    got = describe_quantized(quantize_params(fake_params(rng), policy))
    assert got["layers.mlp.up"] == "itq3_x"
    assert got["layers.attn.wq"] == "itq3_s"


def test_policy_per_rule_overrides(rng):
    policy = QuantPolicy(
        (QuantRule(r"(^|\.)wq$", "itq3_s", rule="lloyd", seed=7, sub_blocks=4),
         QuantRule(MATMUL_LEAVES, "itq3_s")),
        rule="paper")
    q = quantize_params(fake_params(rng), policy)
    wq = q["layers"]["attn"]["wq"]
    assert wq.meta.rule == "lloyd" and wq.meta.sub_blocks == 4
    assert q["layers"]["attn"]["wo"].meta.rule == "paper"
    assert q["layers"]["attn"]["wo"].meta.sub_blocks == 0


def test_policy_embed_rule_quantizes_transposed(rng):
    policy = QuantPolicy(((r"(^|\.)embed$", "q8_0"),))
    q = quantize_params(fake_params(rng), policy)
    assert isinstance(q["embed"], QTensor)
    assert q["embed"].meta.shape == (128, 300)  # stored (D, V) for tied head


def test_policy_dict_roundtrip():
    policy = QuantPolicy(
        (QuantRule(r"(^|\.)lm_head$", "q8_0"),
         QuantRule(r"(^|\.)wq$", None),
         QuantRule(MATMUL_LEAVES, "itq3_s", rule="lloyd", sub_blocks=8)),
        rule="paper", seed=3)
    d = policy.to_dict()
    import json
    assert QuantPolicy.from_dict(json.loads(json.dumps(d))) == policy


def test_policy_rejects_unknown_format():
    with pytest.raises(ValueError):
        QuantRule("wq$", "nope_fmt")


def test_policy_rejects_sub_blocks_on_dense_format():
    with pytest.raises(ValueError):
        QuantRule("wq$", "q8_0", sub_blocks=4)


def test_policy_accepts_tuple_and_dict_rules(rng):
    a = QuantPolicy(((r"(^|\.)wq$", "q8_0"),))
    b = QuantPolicy(({"pattern": r"(^|\.)wq$", "fmt": "q8_0"},))
    assert a == b
    got = describe_quantized(quantize_params(fake_params(rng), b))
    assert got == {"layers.attn.wq": "q8_0"}


def test_uniform_policy_matches_legacy_call(rng):
    params = fake_params(rng)
    a = describe_quantized(quantize_params(params, "itq3_s"))
    b = describe_quantized(
        quantize_params(params, QuantPolicy.uniform("itq3_s")))
    assert a == b and "layers.attn.wq" in a


# ---------------------------------------------------------------------------
# QTensor checkpointing
# ---------------------------------------------------------------------------

def test_ckpt_qtensor_roundtrip(tmp_path, rng):
    d = str(tmp_path)
    w = heavy_tailed(rng)
    tree = {"layer": {"wq": formats.quantize(w, "itq3_s_sub"),
                      "scale": jnp.ones((4,), jnp.float32)}}
    ckpt.save(d, 1, tree)
    restored, step = ckpt.restore(d, tree)
    assert step == 1
    qt0, qt1 = tree["layer"]["wq"], restored["layer"]["wq"]
    assert qt1.meta == qt0.meta
    for k in qt0.data:
        np.testing.assert_array_equal(np.asarray(qt1.data[k]),
                                      np.asarray(qt0.data[k]))


def test_ckpt_restore_qtensor_into_fp_template(tmp_path, rng):
    """The serve-from-disk path: the template holds fp weights, the
    checkpoint holds packed planes — restore yields the quantized tree."""
    d = str(tmp_path)
    w = heavy_tailed(rng)
    ckpt.save(d, 0, {"wq": formats.quantize(w, "itq3_s")})
    restored, _ = ckpt.restore(d, {"wq": w})
    assert isinstance(restored["wq"], QTensor)
    np.testing.assert_array_equal(
        np.asarray(restored["wq"].data["plane2"]),
        np.asarray(formats.quantize(w, "itq3_s").data["plane2"]))


def test_ckpt_restore_tree_without_template(tmp_path, rng):
    d = str(tmp_path)
    tree = {"a": {"b": formats.quantize(heavy_tailed(rng), "itq3_x"),
                  "c": jnp.arange(4, dtype=jnp.int32)}}
    ckpt.save(d, 2, tree)
    restored, step = ckpt.restore_tree(d)
    assert step == 2
    assert isinstance(restored["a"]["b"], QTensor)
    assert restored["a"]["b"].meta == tree["a"]["b"].meta
    np.testing.assert_array_equal(restored["a"]["c"], np.arange(4))


def test_ckpt_restore_shardings_align_past_qtensor(tmp_path, rng):
    """Shardings stay paired with their template leaves even when an
    earlier leaf is a QTensor (whose data dict spans several arrays)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import make_mesh

    d = str(tmp_path)
    tree = {"a_q": formats.quantize(heavy_tailed(rng), "itq3_s"),
            "b": jnp.arange(6, dtype=jnp.float32)}
    ckpt.save(d, 1, tree)
    mesh = make_mesh((1,), ("data",))
    sh = jax.tree.map(lambda _: NamedSharding(mesh, P()), tree)
    restored, _ = ckpt.restore(d, tree, shardings=sh)
    assert isinstance(restored["a_q"], QTensor)
    assert restored["b"].sharding == NamedSharding(mesh, P())
    np.testing.assert_array_equal(np.asarray(restored["b"]), np.arange(6))
    # the QTensor's packed planes land in the prescribed layout too
    for arr in restored["a_q"].data.values():
        assert arr.sharding == NamedSharding(mesh, P())


def test_ckpt_async_with_qtensors(tmp_path, rng):
    d = str(tmp_path)
    tree = {"wq": formats.quantize(heavy_tailed(rng), "quip3")}
    ckpt.save_async(d, 4, tree).join()
    restored, _ = ckpt.restore_tree(d)
    assert restored["wq"].meta == tree["wq"].meta
    np.testing.assert_array_equal(np.asarray(restored["wq"].data["dsign"]),
                                  np.asarray(tree["wq"].data["dsign"]))


# ---------------------------------------------------------------------------
# End to end: policy -> checkpoint -> ServeEngine, bit-identical logits
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_e2e_policy_ckpt_serve_identical(tmp_path):
    from repro.configs.base import get_config, mixed_precision_recipe, reduced
    from repro.models import lm
    from repro.models.layers import Runtime
    from repro.serve.engine import Request, ServeEngine

    cfg = reduced(get_config("smollm-135m"))
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    policy = QuantPolicy.from_dict(mixed_precision_recipe(cfg))
    q = quantize_params(params, policy)
    fmts = set(describe_quantized(q).values())
    assert {"q8_0", "itq3_s_sub", "itq3_s"} <= fmts

    d = str(tmp_path)
    ckpt.save(d, 0, q)

    rt = Runtime(compute_dtype=jnp.float32)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab_size)
    l_live, _, _ = lm.forward(q, toks, rt, cfg)
    restored, _ = ckpt.restore_tree(d)
    l_disk, _, _ = lm.forward(restored, toks, rt, cfg)
    assert bool(jnp.all(l_live == l_disk))  # bit-identical logits

    mk = lambda: [Request(rid=i, prompt=np.arange(4 + i) % cfg.vocab_size,
                          max_new=4) for i in range(3)]
    out_live = [r.out for r in
                ServeEngine(q, cfg, slots=2, max_len=32, rt=rt).run(mk())]
    out_disk = [r.out for r in
                ServeEngine.from_checkpoint(d, cfg, slots=2, max_len=32,
                                            rt=rt).run(mk())]
    assert out_live == out_disk
