"""Compile the serving path's Pallas kernels for a TPU v5e that is described,
not attached: the chip's own compiler checks block tiling, VMEM use and
lowering at real model widths, where interpret mode checks none of them.

Shapes: qwen1.5-0.5b's projections (K, N = 1024x1024, 1024x2816,
2816x1024), smollm-135m's K=576 (padded to 768 on the 256-block grid), and
the rotated-int8 attention at head_dim 64 and 128, dense and paged. Every
test asserts the compiled program calls a ``tpu_custom_call``.

The topology is described inside a module fixture, never at import: only
the process that runs these tests loads the TPU compiler library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.attn_decode import attn_q8_pallas
from repro.kernels.autotune import attn_candidates, candidates
from repro.kernels.fwht_kernel import fwht_pallas
from repro.kernels.itq3_matmul import (
    BLOCK, HOIST_VMEM_BUDGET, itq3_matmul_int8_pallas, itq3_matmul_pallas,
)
from repro.kernels.itq3_matvec import (
    itq3_matvec_int8_pallas, itq3_matvec_pallas,
)

MATMUL_SHAPES = [(1024, 1024), (1024, 2816), (2816, 1024), (576, 1536)]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler library here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip: keep it out of the cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def _planes(sharding, k, n):
    kb = -(-k // BLOCK)
    return kb, (_shape(sharding, (n, kb, BLOCK // 4), jnp.uint8),
                _shape(sharding, (n, kb, BLOCK // 8), jnp.uint8),
                _shape(sharding, (n, kb), jnp.float16),
                _shape(sharding, (n, kb), jnp.float16))


@pytest.mark.parametrize("k,n", MATMUL_SHAPES)
@pytest.mark.parametrize("path", ["weights", "activations", "int8"])
@pytest.mark.parametrize("kernel", ["matvec", "flat", "hoisted"])
def test_itq3_matmul_compiles(one_chip, k, n, path, kernel):
    kb, planes = _planes(one_chip, k, n)
    m = 8 if kernel == "matvec" else 256
    tiled = dict(tm=128, hoist=kernel == "hoisted")  # 2 M tiles per strip
    if path == "int8":
        xq = _shape(one_chip, (m, kb * BLOCK), jnp.int8)
        xs = _shape(one_chip, (m, 1), jnp.float32)
        if kernel == "matvec":
            fn = lambda *a: itq3_matvec_int8_pallas(*a, tn=256,
                                                    interpret=False)
        else:
            fn = lambda *a: itq3_matmul_int8_pallas(*a, tn=256,
                                                    interpret=False, **tiled)
        _assert_kernel(_compile(fn, xq, xs, *planes))
        return
    x = _shape(one_chip, (m, kb * BLOCK), jnp.float32)
    rot = path == "weights"
    if kernel == "matvec":
        fn = lambda *a: itq3_matvec_pallas(*a, rotate_weights=rot, tn=256,
                                           interpret=False)
    else:
        fn = lambda *a: itq3_matmul_pallas(*a, rotate_weights=rot, tn=256,
                                           interpret=False, **tiled)
    _assert_kernel(_compile(fn, x, *planes))


def test_hoist_budget_fits_scoped_vmem(one_chip):
    """The hoisted kernel keeps a (KB, 256, TN) f32 strip in VMEM scratch.
    ``compiled.memory_analysis()`` does not report scoped VMEM, so the
    compile itself is the check: a strip at the full HOIST_VMEM_BUDGET
    (plus the pipelined blocks) must still fit v5e's scoped limit."""
    tn = 256
    kb = HOIST_VMEM_BUDGET // (BLOCK * tn * 4)
    _, planes = _planes(one_chip, kb * BLOCK, tn)
    x = _shape(one_chip, (512, kb * BLOCK), jnp.float32)
    fn = lambda *a: itq3_matmul_pallas(*a, rotate_weights=True, tm=256,
                                       tn=tn, hoist=True, interpret=False)
    _assert_kernel(_compile(fn, x, *planes))


@pytest.mark.parametrize("tm,tn", candidates(256, 2816, 1024))
@pytest.mark.parametrize("int8", [False, True], ids=["float", "int8"])
def test_autotune_matmul_tiles_compile(one_chip, tm, tn, int8):
    """The autotuner only offers (tm, tn) tiles the chip's compiler takes."""
    kb, planes = _planes(one_chip, 1024, 2816)
    if int8:
        args = (_shape(one_chip, (256, kb * BLOCK), jnp.int8),
                _shape(one_chip, (256, 1), jnp.float32))
        fn = lambda *a: itq3_matmul_int8_pallas(*a, tm=tm, tn=tn,
                                                interpret=False)
    else:
        args = (_shape(one_chip, (256, kb * BLOCK), jnp.float32),)
        fn = lambda *a: itq3_matmul_pallas(*a, tm=tm, tn=tn, interpret=False)
    _assert_kernel(_compile(fn, *args, *planes))


@pytest.mark.parametrize("m", [3, 8, 256])
@pytest.mark.parametrize("k", [768, 1024])
def test_fwht_compiles(one_chip, m, k):
    x = _shape(one_chip, (m, k), jnp.float32)
    _assert_kernel(_compile(lambda a: fwht_pallas(a, interpret=False), x))


@pytest.mark.parametrize("head_dim", [64, 128])
@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
@pytest.mark.parametrize("q_len", [1, 5, 512], ids=["decode", "verify",
                                                    "prefill"])
def test_attention_compiles(one_chip, head_dim, paged, q_len):
    """qwen1.5-0.5b serving shapes: 8 slots x 16 kv heads, 2048 positions;
    the paged pool holds 16-token blocks."""
    rows, t, bs = 8 * 16, 2048, 16
    if paged:
        pool_rows = (8 * (t // bs) + 1) * 16
        codes = _shape(one_chip, (pool_rows, bs, head_dim), jnp.int8)
        scale = _shape(one_chip, (pool_rows, bs), jnp.float16)
        extra = [_shape(one_chip, (rows, t // bs), jnp.int32)]
    else:
        codes = _shape(one_chip, (rows, t, head_dim), jnp.int8)
        scale = _shape(one_chip, (rows, t), jnp.float16)
        extra = []
    q = _shape(one_chip, (rows, q_len, 1, head_dim), jnp.float32)
    lens = _shape(one_chip, (rows,), jnp.int32)
    fn = lambda *a: attn_q8_pallas(
        *a, sm_scale=head_dim ** -0.5, causal=q_len > 1, tq=128, tt=256,
        interpret=False, block_size=bs if paged else None)
    _assert_kernel(_compile(fn, q, codes, scale, codes, scale, lens, lens,
                            *extra))


# the benchmark cells' served decode shapes: (slots, pool blocks, table
# width in 256-token blocks, kv heads, query heads per kv head)
SERVED_DECODE = {
    "qwen05b.chat": (64, 309, 10, 16, 1),
    "smollm135m.chat": (128, 658, 8, 3, 3),
    "qwen05b.longdecode": (12, 347, 40, 16, 1),
}


@pytest.mark.parametrize("cell", sorted(SERVED_DECODE))
@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_decode_grid_compiles_at_served_shapes(one_chip, cell, paged):
    """The decode grid (a step per slot and key tile, every kv head in it)
    at each cell's served shape, over the pool's own planes or a dense
    cache of the same length: block rule and VMEM checked by the chip's
    compiler, and the kernel keeps the instruction name the benchmark's
    trace reduction matches."""
    import re

    slots, nb, maxb, kv, g = SERVED_DECODE[cell]
    bs, hd = 256, 64
    lead, t = (nb, bs) if paged else (slots, maxb * bs)
    codes = _shape(one_chip, (lead, kv, t, hd), jnp.int8)
    scale = _shape(one_chip, (lead, kv, t), jnp.float16)
    extra = [_shape(one_chip, (slots, maxb), jnp.int32)] if paged else []
    q = _shape(one_chip, (slots, kv, g, hd), jnp.float32)
    lens = _shape(one_chip, (slots,), jnp.int32)
    fn = lambda q_, kc, ks, vc, vs, kl, *tbl: attn_q8_pallas(
        q_, kc, ks, vc, vs, kl, None, *tbl, sm_scale=hd ** -0.5,
        causal=False, tt=256, interpret=False,
        block_size=bs if paged else None)
    compiled = _compile(fn, q, codes, scale, codes, scale, lens, *extra)
    _assert_kernel(compiled)
    assert re.search(r"^\s+(ROOT )?%attn_q8_pallas(\.\d+)? = ",
                     compiled.as_text(), re.M)


def test_decode_grid_vmem_budget_compiles(one_chip):
    """A key tile asked wider than FOLD_VMEM_BUDGET allows for every kv
    head's code blocks is narrowed from the shape, and the narrowed tile at
    the budget's edge still fits v5e's scoped VMEM."""
    from repro.kernels.attn_decode import FOLD_VMEM_BUDGET, _fold_tt

    kv, hd, t = 16, 128, 4096
    tt = _fold_tt(4096, kv, hd)
    assert 4 * kv * tt * hd == FOLD_VMEM_BUDGET
    codes = _shape(one_chip, (2, kv, t, hd), jnp.int8)
    scale = _shape(one_chip, (2, kv, t), jnp.float16)
    q = _shape(one_chip, (2, kv, 1, hd), jnp.float32)
    lens = _shape(one_chip, (2,), jnp.int32)
    fn = lambda *a: attn_q8_pallas(*a, sm_scale=hd ** -0.5, causal=False,
                                   tt=4096, interpret=False)
    _assert_kernel(_compile(fn, q, codes, scale, codes, scale, lens))


@pytest.mark.parametrize(
    "tq,tt", attn_candidates(2048, 64) + attn_candidates(2048, 64,
                                                         decode=True))
def test_autotune_attention_tiles_compile(one_chip, tq, tt):
    """Every (tq, tt) the attention autotuner offers compiles (dense cache,
    head_dim 64)."""
    rows, t, hd = 8 * 16, 2048, 64
    q = _shape(one_chip, (rows, max(tq, 1), 1, hd), jnp.float32)
    codes = _shape(one_chip, (rows, t, hd), jnp.int8)
    scale = _shape(one_chip, (rows, t), jnp.float16)
    lens = _shape(one_chip, (rows,), jnp.int32)
    fn = lambda *a: attn_q8_pallas(*a, sm_scale=hd ** -0.5, causal=tq > 1,
                                   tq=tq, tt=tt, interpret=False)
    _assert_kernel(_compile(fn, q, codes, scale, codes, scale, lens, lens))


def test_qwen_decode_step_compiles(one_chip, monkeypatch):
    """The whole qwen1.5-0.5b decode step at its published widths, as the
    engine runs it on the chip (Pallas matmuls, paged rotated-int8
    attention), calls the matvec, FWHT and attention kernels."""
    import repro.kernels.ops as ops
    from repro.configs.base import get_config
    from repro.models import lm
    from repro.models.layers import Runtime
    from repro.serve import paged as paged_mod
    from repro.serve.quantized import quantize_params

    # code that asks jax.default_backend() sees the CPU here: steer it
    monkeypatch.setattr(ops, "auto_interpret", lambda: False)
    cfg = get_config("qwen1.5-0.5b")
    slots, max_len, bs = 8, 2048, 16
    maxb = max_len // bs
    place = lambda tree: jax.tree.map(
        lambda a: _shape(one_chip, a.shape, a.dtype), tree)
    params = place(jax.eval_shape(
        lambda key: quantize_params(lm.init_params(key, cfg), "itq3_s"),
        jax.random.PRNGKey(0)))
    cache = place(jax.eval_shape(
        lambda: paged_mod.init_paged_cache(cfg, slots * maxb + 1, bs)))
    rt = Runtime(compute_dtype=jnp.float32, backend="pallas", kv_quant=True)

    def decode(p, c, toks, pos, table):
        return lm.decode_step(p, toks, {**c, "table": table}, pos, rt, cfg)[0]

    compiled = _compile(decode, params, cache,
                        _shape(one_chip, (slots, 1), jnp.int32),
                        _shape(one_chip, (slots,), jnp.int32),
                        _shape(one_chip, (slots, maxb), jnp.int32))
    text = compiled.as_text()
    for name in ("itq3_matvec_pallas", "fwht_pallas", "attn_q8_pallas"):
        assert f"jit({name})" in text, name
    _assert_kernel(compiled)


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_served_programs_keep_names_and_carry_scopes(one_chip, monkeypatch,
                                                     program):
    """The engine's decode and prefill programs of a small model, compiled
    for the chip with the kernels on: every model scope reaches the
    op_name metadata, and the kernel and program names the benchmark's
    trace reduction matches are still the instruction and module names."""
    import re

    import repro.kernels.ops as ops
    from repro.configs.base import get_config, reduced
    from repro.models import lm
    from repro.models.layers import Runtime
    from repro.serve.engine import ServeEngine
    from repro.serve.quantized import quantize_params

    cfg = reduced(get_config("smollm-135m"))
    params = quantize_params(lm.init_params(jax.random.PRNGKey(0), cfg),
                             "itq3_s")
    rt = Runtime(compute_dtype=jnp.float32, backend="pallas", kv_quant=True)
    eng = ServeEngine(params, cfg, slots=4, max_len=512, prompt_pad=256,
                      rt=rt, paged=True, block_size=256, num_blocks=9)
    # code that asks jax.default_backend() sees the CPU here: steer it
    monkeypatch.setattr(ops, "auto_interpret", lambda: False)
    place = lambda tree: jax.tree.map(
        lambda a: _shape(one_chip, a.shape, a.dtype), tree)
    ints = lambda *shape: _shape(one_chip, shape, jnp.int32)
    maxb = eng._table.shape[1]
    if program == "decode":
        lowered = eng._jit_decode.lower(
            place(eng.params), place(eng.cache), ints(4, 1), ints(4), None,
            None, None, None, None, ints(4, maxb))
        kernels = ("attn_q8_pallas", "itq3_matvec_pallas")
    else:
        lowered = eng._jit_prefill.lower(
            place(eng.params), place(eng.cache), ints(1, 256), ints(1),
            ints(1), ints(1), None, None, None, None, ints(1, maxb),
            plen=256, fresh=True)
        kernels = ("attn_q8_pallas", "itq3_matmul_pallas")
    text = lowered.compile().as_text()
    assert text.startswith(f"HloModule jit__{program}_impl")
    for name in kernels:
        assert re.search(rf"^\s+(ROOT )?%{name}(\.\d+)? = ", text, re.M), name
    parts = {p for path in re.findall(r'op_name="([^"]*)"', text)
             for p in re.split(r"[/;]", path)}
    scopes = ("embed", "kv_cache", "attn", "mlp", "head", "sample",
              "itq3_planes")
    assert not [s for s in scopes if s not in parts]
