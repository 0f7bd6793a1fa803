"""Training substrate: loss goes down, grad-accumulation equivalence,
optimizer math, lr schedule, gradient compression error-feedback."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config, reduced
from repro.data.pipeline import SyntheticCorpus
from repro.models.layers import Runtime
from repro.train import loop as tl, optim

KEY = jax.random.PRNGKey(0)


def test_loss_decreases():
    cfg = reduced(get_config("smollm-135m"))
    rt = Runtime(compute_dtype=jnp.float32)
    step = jax.jit(tl.make_train_step(cfg, rt, warmup=5, total_steps=120,
                                      lr_peak=3e-3))
    state = tl.init_train_state(KEY, cfg)
    corpus = SyntheticCorpus(cfg.vocab_size, seed=3)
    losses = []
    for s in range(120):
        b = corpus.batch(s, 16, 64)
        state, m = step(state, {k: jnp.asarray(v) for k, v in b.items()})
        losses.append(float(m["loss"]))
    assert np.mean(losses[-10:]) < np.mean(losses[:10]) - 0.5, (
        losses[:3], losses[-3:])


def test_grad_accumulation_equivalence():
    cfg = reduced(get_config("qwen1.5-0.5b"))
    rt = Runtime(compute_dtype=jnp.float32)
    s1 = jax.jit(tl.make_train_step(cfg, rt, num_micro=1, total_steps=10))
    s4 = jax.jit(tl.make_train_step(cfg, rt, num_micro=4, total_steps=10))
    state = tl.init_train_state(KEY, cfg)
    corpus = SyntheticCorpus(cfg.vocab_size, seed=1)
    batch = {k: jnp.asarray(v) for k, v in corpus.batch(0, 8, 32).items()}
    st1, m1 = s1(jax.tree.map(jnp.copy, state), batch)
    st4, m4 = s4(jax.tree.map(jnp.copy, state), batch)
    assert abs(float(m1["loss"]) - float(m4["loss"])) < 1e-4
    d = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))),
                     st1.params, st4.params)
    assert max(jax.tree.leaves(d)) < 1e-4


def test_adamw_vs_reference():
    params = {"w": jnp.asarray([1.0, -2.0, 3.0], jnp.float32)}
    grads = {"w": jnp.asarray([0.1, 0.2, -0.3], jnp.float32)}
    st = optim.adamw_init(params)
    new_p, st2, gnorm = optim.adamw_update(grads, st, params, lr=1e-2,
                                           weight_decay=0.0, grad_clip=1e9)
    # hand-rolled first step: m=0.1g, v=0.05g^2, mhat=g, vhat=g^2
    g = np.asarray([0.1, 0.2, -0.3])
    want = np.asarray(params["w"]) - 1e-2 * g / (np.abs(g) + 1e-8)
    np.testing.assert_allclose(np.asarray(new_p["w"]), want, atol=1e-5)
    assert abs(float(gnorm) - np.linalg.norm(g)) < 1e-6


def test_grad_clip():
    params = {"w": jnp.ones((4,), jnp.float32)}
    grads = {"w": jnp.full((4,), 100.0, jnp.float32)}
    st = optim.adamw_init(params)
    _, _, gnorm = optim.adamw_update(grads, st, params, lr=0.0, grad_clip=1.0)
    assert float(gnorm) == 200.0  # reported pre-clip


def test_cosine_lr():
    lr0 = float(optim.cosine_lr(jnp.int32(0), peak=1.0, warmup=10, total=100))
    lr_peak = float(optim.cosine_lr(jnp.int32(10), peak=1.0, warmup=10, total=100))
    lr_end = float(optim.cosine_lr(jnp.int32(100), peak=1.0, warmup=10, total=100))
    assert lr0 == 0.0 and abs(lr_peak - 1.0) < 0.01 and lr_end <= 0.11


def test_compressed_allreduce_error_feedback():
    """int8 + error feedback: mean of quantized exchanges converges to the
    true mean across steps (residual replay)."""
    rng = np.random.default_rng(0)
    g_pods = [rng.normal(size=(64,)).astype(np.float32) for _ in range(2)]
    true_mean = np.mean(g_pods, axis=0)
    errs = [np.zeros(64, np.float32) for _ in range(2)]
    acc = np.zeros(64, np.float64)
    for step in range(8):
        xs = [g + e for g, e in zip(g_pods, errs)]
        amax = max(np.abs(x).max() for x in xs)
        scale = max(amax, 1e-12) / 127.0
        qs = [np.clip(np.round(x / scale), -127, 127) for x in xs]
        deqs = [q * scale for q in qs]
        errs = [x - d for x, d in zip(xs, deqs)]
        out = sum(qs) * scale / 2
        acc += out
    # time-averaged compressed mean ~= true mean (error feedback property)
    np.testing.assert_allclose(acc / 8, true_mean, atol=scale)


SHARDMAP_COMPRESS = """
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from repro.train.grad import compressed_pod_allreduce, zeros_error_buf

mesh = make_mesh((2, 4), ("pod", "data"))
rng = np.random.default_rng(0)
g = {"w": jnp.asarray(rng.normal(size=(2, 64)), jnp.float32)}  # per-pod partials
e = {"w": jnp.zeros((2, 64), jnp.float32)}
true_mean = np.mean(np.asarray(g["w"]), axis=0)

with mesh:
    acc = np.zeros(64)
    for step in range(6):
        red, e = jax.jit(lambda a, b: compressed_pod_allreduce(a, b, mesh))(g, e)
        acc += np.asarray(red["w"][0])
    # both pods see identical reduced values
    assert np.allclose(np.asarray(red["w"][0]), np.asarray(red["w"][1]))
    # error feedback: time-average converges to the true mean
    err = np.max(np.abs(acc / 6 - true_mean))
assert err < 0.02, err
print("COMPRESS_OK", err)
"""


def test_compressed_pod_allreduce_shardmap():
    """8-device shard_map execution of the compressed allreduce. The
    historical "hang" here (skip-on-expiry quarantine since PR 3) was never
    the shard_map: the stripped subprocess env dropped JAX_PLATFORMS, so the
    child's ``import jax`` went platform-probing for minutes. With the env
    inherited (tests/_subproc.py) the same test passes in ~1s, so the
    quarantine is gone — a timeout now fails loudly like any regression."""
    from _subproc import run_py
    res = run_py(SHARDMAP_COMPRESS, devices=8, timeout=120)
    assert "COMPRESS_OK" in res.stdout, res.stdout + res.stderr
