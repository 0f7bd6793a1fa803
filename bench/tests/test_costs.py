"""Kernel bytes and FLOPs against hand arithmetic at the two models'
shapes."""
import json
import os

import pytest

from conftest import BENCH
from harness import costs
from harness.model import Sizes


def sizes(name):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return Sizes.of(json.load(f))


def test_matvec_qwen_up_projection():
    # K=1024 (4 blocks), N=2816, 64 slots: 2816 columns x 4 blocks x
    # (64 + 32 + 2 + 2) bytes of planes and scales, x in f32, y out f32
    flops, nbytes = costs.itq3_matmul(64, 1024, 2816)
    assert flops == 2 * 64 * 1024 * 2816
    assert nbytes == 2816 * 4 * 100 + 64 * 1024 * 4 + 64 * 2816 * 4


def test_matvec_smollm_pads_576_to_768():
    # K=576 reads three 256-blocks: 768 rows of codes, x padded to 768
    flops, nbytes = costs.itq3_matmul(128, 576, 1536)
    assert flops == 2 * 128 * 768 * 1536
    assert nbytes == 1536 * 3 * 100 + 128 * 768 * 4 + 128 * 1536 * 4


def test_step_weight_bytes_qwen():
    """Per decode step the 24 layers stream ~120 MB of ITQ3_S planes."""
    s = sizes("qwen1.5-0.5b")
    per_layer = sum(n * costs.k_pad(k) // 256 * 100
                    for k, n in costs.step_projections(s))
    # 4 x 1024x1024 attention + 3 x 1024x2816 MLP, 100 B per 256 weights
    assert per_layer == (4 * 1024 * 1024 + 3 * 1024 * 2816) * 100 // 256
    assert 115e6 < per_layer * s.layers < 125e6


def test_smollm_projections():
    s = sizes("smollm-135m")
    assert costs.step_projections(s) == [
        (576, 576), (576, 192), (576, 192), (576, 576),
        (576, 1536), (576, 1536), (1536, 576)]


@pytest.mark.parametrize("name,kv_heads", [("qwen1.5-0.5b", 16),
                                           ("smollm-135m", 3)])
def test_attention_decode(name, kv_heads):
    s = sizes(name)
    flops, nbytes = costs.attn_decode(s, 5000)
    # per cached token, per KV head: 64 int8 codes + a 2-byte scale, K and V
    assert nbytes == 5000 * kv_heads * 2 * 66 + 2 * 4 * s.heads * 64
    assert flops == 4 * 5000 * s.heads * 64


def test_kv_bytes_per_token_qwen():
    """24 layers x 16 heads x 2 x (64 + 2) = 50,688 B per cached token;
    the engine's pool reports 50,737 with the block-table's null block
    and round-up spread over its tokens."""
    s = sizes("qwen1.5-0.5b")
    f1, b1 = costs.attn_decode(s, 1)
    f0, b0 = costs.attn_decode(s, 0)
    assert (b1 - b0) * s.layers == 50688


def test_model_flops():
    s = sizes("qwen1.5-0.5b")
    layers, head = s.param_counts()
    assert layers == 24 * (4 * 1024 * 1024 + 3 * 1024 * 2816)
    assert head == 151936 * 1024
    assert costs.token_flops(s, 10, head=True) == \
        2 * layers + 2 * head + 4 * 10 * 16 * 64 * 24
    # a 3-token prompt attends 1 + 2 + 3 positions, one head row
    assert costs.prefill_flops(s, 3) == \
        3 * 2 * layers + 2 * head + 4 * 16 * 64 * 24 * 6
