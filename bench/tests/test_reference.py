"""The plain reference against the program at a tiny size on the CPU,
where both compute in float32: the same weights through the program's
own quantizer and forward (``lm.forward``, ``backend="ref"``, the
rotated-int8 cache) give the reference's logits to float rounding."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import TINY
from harness import reference
from harness.model import (Sizes, float_params, program_config, seed_key,
                           served_params)


@pytest.mark.parametrize("bias", [True, False])
def test_reference_matches_program_forward(bias):
    from repro.models import lm
    from repro.models.layers import Runtime
    conf = dict(TINY, attention_bias=bias)
    s, cfg = Sizes.of(conf), program_config(conf)
    seed = 2**40 + 3
    params = served_params(seed, s)
    rng = np.random.default_rng(0)
    seq = rng.integers(0, s.vocab, 200, dtype=np.int32)
    rt = Runtime(compute_dtype=jnp.float32, backend="ref", kv_quant=True)
    with jax.default_matmul_precision("highest"):
        cache = lm.init_cache(cfg, 1, len(seq), kv_quant=True)
        want, _, _ = lm.forward(params, jnp.asarray(seq)[None], rt, cfg,
                                cache=cache, pos=jnp.zeros(1, jnp.int32))
    want = np.asarray(want[0])
    p = reference.prepare(seed_key(seed), s)
    got = np.zeros_like(want)
    out = np.concatenate([seq[1:], [0]])  # rows 0.. of a 1-token prompt
    for i, n, lg in reference.logits_blocks(p, seq[:1], out, s):
        got[i:i + n] = np.asarray(lg)[:n]
    err = np.abs(got - want).max() / np.abs(want).max()
    assert err < 1e-4, err


def test_itq3_matches_program_quantizer():
    """The reference's ITQ3_S arithmetic gives the program's dequantized
    weights, the 576 -> 768 pad of the reduction dim included."""
    from repro.core import formats
    rng = np.random.default_rng(1)
    for k, n in ((576, 384), (1024, 256)):
        w = jnp.asarray(rng.normal(size=(k, n)) / np.sqrt(k), jnp.float32)
        want = np.asarray(formats.dequantize(formats.quantize(w, "itq3_s"),
                                             jnp.float32))
        got = np.asarray(reference.itq3_s(w))
        assert got.shape == want.shape == (k, n)
        np.testing.assert_allclose(got, want, atol=1e-6)


def test_kv_codec_matches_program():
    from repro.serve.kv_quant import kv_decode, kv_encode
    x = jnp.asarray(np.random.default_rng(2).normal(size=(7, 3, 64)) * 3,
                    jnp.float32)
    want = np.asarray(kv_decode(*kv_encode(x)))
    got = np.asarray(reference.kv_int8(x))
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_weights_have_the_program_tree():
    """The benchmark's seeded weights fill the program's parameter tree
    leaf for leaf, shape for shape."""
    from repro.models import lm
    for bias in (True, False):
        conf = dict(TINY, attention_bias=bias)
        s, cfg = Sizes.of(conf), program_config(conf)
        ours = jax.eval_shape(lambda: float_params(seed_key(0), s))
        theirs = jax.eval_shape(lambda: lm.init_params(
            jax.random.PRNGKey(0), cfg))
        assert jax.tree.structure(ours) == jax.tree.structure(theirs)
        assert [a.shape for a in jax.tree.leaves(ours)] == \
            [a.shape for a in jax.tree.leaves(theirs)]


def test_same_seed_same_weights():
    s = Sizes.of(TINY)
    a = jax.jit(lambda k: float_params(k, s))(seed_key(2**35 + 1))
    b = jax.jit(lambda k: float_params(k, s))(seed_key(2**35 + 1))
    c = jax.jit(lambda k: float_params(k, s))(seed_key(2**35 + 2))
    assert all(np.array_equal(x, y) for x, y in
               zip(jax.tree.leaves(a), jax.tree.leaves(b)))
    assert not np.array_equal(a["embed"], c["embed"])


@pytest.mark.parametrize("spread", [0.003, 0.01, 0.03])
def test_noise_scale_reads_the_error_spread(spread):
    """Rows with known margins, chosen by the best logit plus Gaussian
    error in the difference: the estimate finds the error's spread, and
    rows that only held (no flip) read 0."""
    rng = np.random.default_rng(3)
    margins = rng.exponential(0.3, 20000)
    err = rng.normal(0, spread, margins.size)
    flip = err > margins
    gaps = np.where(flip, margins, 0.0)
    kept = np.where(flip, rng.exponential(0.3, margins.size), margins)
    got = reference.noise_scale(gaps, kept)
    assert 0.8 * spread < got < 1.25 * spread, got
    assert reference.noise_scale(np.zeros(5), np.ones(5)) == 0.0


def test_choice_stats_gap_and_margin():
    lg = jnp.asarray([[0.0, 3.0, 1.0, 2.5], [5.0, 1.0, 4.0, 0.0]])
    gap, margin = reference.choice_stats(lg, jnp.asarray([1, 2], jnp.int32))
    np.testing.assert_allclose(np.asarray(gap), [0.0, 1.0])
    np.testing.assert_allclose(np.asarray(margin), [0.5, 1.0])
    np.testing.assert_array_equal(np.asarray(reference.first_choice(lg)),
                                  [1, 0])
