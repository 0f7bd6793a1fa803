"""Sizes, weights and the engine of one configuration.

The weights are the benchmark's own: drawn from the seed by
:func:`float_params`, in the layout of the program's parameter tree.
The program gets them quantized by its own ``quantize_params`` in the
same jitted call; the plain reference regenerates the float weights
from the seed and applies its own ITQ3_S arithmetic
(``harness/reference.py``), so it takes nothing the program made.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class Sizes:
    """The published sizes the harness and the reference work from."""
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    qkv_bias: bool
    rope_theta: float
    norm_eps: float

    @classmethod
    def of(cls, conf: dict) -> "Sizes":
        if conf["family"] != "dense":
            raise ValueError(f"family {conf['family']!r}: the harness and "
                             f"the reference cover the dense family only")
        heads = conf["num_attention_heads"]
        return cls(layers=conf["num_hidden_layers"], d=conf["hidden_size"],
                   heads=heads, kv_heads=conf["num_key_value_heads"],
                   head_dim=conf["hidden_size"] // heads,
                   d_ff=conf["intermediate_size"], vocab=conf["vocab_size"],
                   qkv_bias=bool(conf["attention_bias"]),
                   rope_theta=float(conf["rope_theta"]),
                   norm_eps=float(conf["rms_norm_eps"]))

    def param_counts(self) -> tuple[int, int]:
        """(matmul weights of all layers, tied LM head): what a token
        multiplies through; the embedding gather does no arithmetic."""
        hd = self.head_dim
        attn = self.d * hd * (2 * self.heads + 2 * self.kv_heads)
        mlp = 3 * self.d * self.d_ff
        return self.layers * (attn + mlp), self.vocab * self.d


def seed_key(seed: int) -> np.ndarray:
    """Any whole number (seeds may exceed 32 bits) -> threefry key data,
    passed as an argument so every seed runs the same compiled program."""
    return np.random.SeedSequence(seed).generate_state(2, np.uint32)


# leaf -> (reduction dim, output dim) of each matmul weight, per layer
def _matrices(s: Sizes) -> dict:
    q, kv = s.heads * s.head_dim, s.kv_heads * s.head_dim
    return {("attn", "wq"): (s.d, q), ("attn", "wk"): (s.d, kv),
            ("attn", "wv"): (s.d, kv), ("attn", "wo"): (q, s.d),
            ("mlp", "gate"): (s.d, s.d_ff), ("mlp", "up"): (s.d, s.d_ff),
            ("mlp", "down"): (s.d_ff, s.d)}


def float_params(key_data, s: Sizes) -> dict:
    """Seeded float32 weights in the program's tree layout (dense family).

    Matrices are N(0, 1/K) (unit-variance outputs, the usual init scale),
    the tied embedding N(0, 0.02^2), norm scales 1 + N(0, 0.1^2) and QKV
    biases N(0, 0.1^2): every parameter the served path reads is nonzero
    and differs from its neighbours, so the check covers it."""
    key = jax.random.wrap_key_data(jnp.asarray(key_data, jnp.uint32))
    n = iter(range(10_000))

    def normal(shape, std):
        return jax.random.normal(jax.random.fold_in(key, next(n)), shape,
                                 jnp.float32) * std

    L, d = s.layers, s.d
    layers: dict = {"ln1": {"scale": 1.0 + normal((L, d), 0.1)},
                    "ln2": {"scale": 1.0 + normal((L, d), 0.1)},
                    "attn": {}, "mlp": {}}
    for (grp, leaf), (k, m) in _matrices(s).items():
        layers[grp][leaf] = normal((L, k, m), 1.0 / np.sqrt(k))
    if s.qkv_bias:
        for leaf, m in (("bq", s.heads), ("bk", s.kv_heads),
                        ("bv", s.kv_heads)):
            layers["attn"][leaf] = normal((L, m * s.head_dim), 0.1)
    return {"embed": normal((s.vocab, d), 0.02),
            "ln_f": {"scale": 1.0 + normal((d,), 0.1)},
            "layers": layers}


def program_config(conf: dict):
    """The program's ``ModelConfig`` with the configuration file's sizes."""
    from repro.configs.base import ModelConfig
    s = Sizes.of(conf)
    return ModelConfig(
        name=conf["name"], family="dense", num_layers=s.layers, d_model=s.d,
        num_heads=s.heads, num_kv_heads=s.kv_heads, head_dim=s.head_dim,
        d_ff=s.d_ff, vocab_size=s.vocab, norm="rmsnorm",
        activation="swiglu", qkv_bias=s.qkv_bias, rope_theta=s.rope_theta,
        tie_embeddings=True)


def served_params(seed: int, s: Sizes):
    """The program's weights: the seeded float tree, quantized to ITQ3_S
    by the program's own ``quantize_params``, in one jitted call."""
    from repro.serve.quantized import quantize_params
    build = jax.jit(lambda kd: quantize_params(float_params(kd, s), "itq3_s"))
    return jax.block_until_ready(build(seed_key(seed)))


def make_engine(params, cfg, engine: dict, *, path: str | None = None):
    """The engine as ``launch/serve.py --kv-quant --paged`` builds it: the
    paged rotated-int8 cache, ``backend="auto"`` (the Pallas kernels on a
    TPU), f32 activations; sizes from the cell's ``engine`` settings.
    ``path`` switches on one of the program's lower-precision paths, a
    control: "bf16" (bfloat16 activations) or "w3a8" (int8 activations)."""
    from repro.models.layers import Runtime
    from repro.serve.engine import ServeEngine
    if path not in (None, "bf16", "w3a8"):
        raise ValueError(f"no program path {path!r}")
    rt = Runtime(compute_dtype=jnp.bfloat16 if path == "bf16"
                 else jnp.float32, quant_mode="activations", backend="auto",
                 kv_quant=True, act_quant=path == "w3a8")
    return ServeEngine(params, cfg, slots=engine["slots"],
                       max_len=engine["max_len"], rt=rt, paged=True,
                       block_size=engine["block_size"],
                       num_blocks=engine["num_blocks"],
                       prompt_pad=engine["prompt_pad"])
