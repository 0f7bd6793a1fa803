"""Tests of the benchmark itself, on the CPU at tiny sizes:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
import json
import os
import shutil
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

TINY = {
    "name": "tiny", "source": "a tiny stand-in for CPU tests",
    "family": "dense", "hidden_size": 128, "intermediate_size": 256,
    "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "vocab_size": 8192, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-5, "attention_bias": True,
}


@pytest.fixture
def tiny_root(tmp_path):
    """A checkout-shaped root holding the benchmark's metric readers and
    peaks, with one tiny chat cell and one tiny backlog cell."""
    root = tmp_path / "root"
    shutil.copytree(os.path.join(BENCH, "metrics"), root / "bench" / "metrics")
    shutil.copy(os.path.join(BENCH, "peaks.json"), root / "bench")
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {
        "tiny.chat": {"config": "tiny", "traffic": "tinychat", "max_wave": 1,
                      "rate_per_s": 3.0,
                      "engine": {"slots": 4, "max_len": 160, "block_size": 16,
                                 "num_blocks": 41, "prompt_pad": 64},
                      "check": {"max_logit_gap": 1e-3, "mean_logit_gap": 1e-5,
                                "logit_noise": 1e-3,
                                "tokens": 1000}},
        "tiny.long": {"config": "tiny", "traffic": "tinylong", "max_wave": 1,
                      "engine": {"slots": 2, "max_len": 160, "block_size": 16,
                                 "num_blocks": 21, "prompt_pad": 64},
                      "check": {"max_logit_gap": 1e-3, "mean_logit_gap": 1e-5,
                                "logit_noise": 1e-3,
                                "tokens": 24}},
    }
    mixes = {
        "tinychat": {"arrivals": "open_loop",
                     "prompt": {"dist": "lognormal", "median": 40,
                                "sigma": 0.5, "min": 8, "max": 100},
                     "output": {"dist": "lognormal", "median": 32,
                                "sigma": 0.3, "min": 24, "max": 48}},
        "tinylong": {"arrivals": "backlog", "blocks": 3,
                     "prompt": {"dist": "uniform", "min": 20, "max": 60},
                     "output": {"dist": "uniform", "min": 8, "max": 16}},
    }
    for sub, items in (("cells", cells), ("traffic", mixes),
                       ("configs", {"tiny": TINY})):
        (root / "bench" / sub).mkdir(parents=True)
        for name, body in items.items():
            (root / "bench" / sub / f"{name}.json").write_text(
                json.dumps(body))
    renames = {"qwen05b.chat": "tiny.chat", "smollm135m.chat": "tiny.chat",
               "qwen05b.longdecode": "tiny.long"}
    for kind in ("end_to_end", "per_layer"):
        for m in bench[kind]:
            if "workloads" in m:
                m["workloads"] = sorted({renames[w] for w in m["workloads"]})
    bench["workloads"] = [
        {"name": "tiny.chat", "config": "tiny", "traffic": "tinychat",
         "chips": 1, "why": "test"},
        {"name": "tiny.long", "config": "tiny", "traffic": "tinylong",
         "chips": 1, "why": "test"}]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root
